"""What decides ``correct`` for the recommender train cell
(``train.youtube-dnn-1m``), driven on the CPU at a size a test can hold.

The reference follows the program's own step: the per-example draws are
the program's ids under the same key, with the exact log q of each over
the whole catalog, and the loss, the first clipped gradient and the change
after two steps agree to float32 rounding.  Then each test skips the
harness's look for a chip and drives the rest of a run (set-up, window,
reference, verdict) against the cell's own limits: the sound program
passes; with the timed path broken underneath (a step that returns its
state unchanged, half of the batch left out) or with the float8 control in
the reference's place, ``correct`` comes out false.  The control is read
at twice the widths (CONTROL): at RECSYS the float8 hidden states part the
draws from the reference's too little to show on every seed.
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import compare, families, harness, reference
from bench.kinds import train
from test_bench_check_lm import _broken_step

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))

#: the cell cut to a test's size: 4,096 items, watch embeddings of 32, 5
#: watches, a (64, 32) tower, 16 negatives per example in blocks of 32
RECSYS = dict(vocab_size=4096, d_model=32, history_len=5, user_feature_dim=8,
              tower_dims=[64, 32], m_negatives=16, sampler_block=32)
#: the size the control is read at: 8,192 items, widths of 64, a (128, 64)
#: tower, 32 negatives per example in blocks of 64, 32 examples a batch
CONTROL = dict(vocab_size=8192, d_model=64, history_len=5,
               user_feature_dim=16, tower_dims=[128, 64], m_negatives=32,
               sampler_block=64)


@pytest.fixture(scope="module")
def recsys():
    """The cell under its own limits, cut to RECSYS, 16 examples a batch
    and a ring of 4."""
    cell = harness.load_cell(ROOT, "train.youtube-dnn-1m")
    return dataclasses.replace(
        cell, config=dict(cell.config, **RECSYS),
        traffic=dict(cell.traffic, batch=16, ring=4))


def test_draws_mirror_the_programs_with_exact_logq(recsys, args):
    """The program's per-example sampler and the reference's draw, on the
    same head table, hidden states and key: the same ids, and the
    reference's log q is the exact q_i = K(h, w_i) / sum_j K(h, w_j)."""
    from repro.core.samplers import sampler_from_config

    cfg = recsys.config
    inp = train.Inputs(recsys, args.seed)
    p = inp.params0()
    h, _ = families.load(cfg).hidden(p, inp.ring()[0], cfg)
    w = families.load(cfg).head_table(p)
    key = inp.step_keys()[0]
    sampler = sampler_from_config(inp.arch)
    n = jnp.asarray(cfg["vocab_size"], jnp.int32)
    runtime = sampler.hydrate(sampler.init_state(key, w, n_valid=n), n)
    ids, logq = jax.jit(lambda h, k: sampler.sample_batch(
        runtime, h, cfg["m_negatives"], k))(h, key)
    ref_ids, ref_logq = jax.jit(lambda h, w, k: reference.draw(
        h, w, k, cfg))(h, w, key)
    assert ref_ids.shape == (h.shape[0], cfg["m_negatives"])
    np.testing.assert_array_equal(ids, ref_ids)
    np.testing.assert_allclose(logq, ref_logq, rtol=1e-5, atol=1e-5)
    h64, w64 = np.asarray(h, np.float64), np.asarray(w, np.float64)
    k = cfg["sampler_alpha"] * np.square(h64 @ w64.T) + 1.0
    exact = np.log(k / k.sum(axis=1, keepdims=True))
    want = np.take_along_axis(exact, np.asarray(ref_ids), axis=1)
    np.testing.assert_allclose(ref_logq, want, rtol=1e-5, atol=1e-5)


def test_reference_follows_the_programs_two_steps(recsys, args):
    """The loss of each step, the first clipped gradient and the change
    after two steps, the program's against the float32 reference's: on the
    CPU both compute in float32, so they agree to its rounding."""
    inputs = train.Inputs(recsys, args.seed)
    setup = train.Setup(inputs, lambda msg: None)
    prog = setup.first_steps()
    ref = inputs.reference(setup.ring[:2], setup.step_keys[:2],
                           against={"program": prog.pop("grad1")})
    prog["grad_diff_norm"] = ref["diff_norm"]["program"]
    prog["rows_gap"] = ref["rows_gap"]["program"]
    numbers = compare.train_numbers(prog, ref)
    assert numbers["rows_gap"] == 0.0
    for name in ("loss_gap", "loss_gap2", "grad_gap", "grad_diff",
                 "change_gap"):
        assert numbers[name] < 1e-4, numbers


def test_sound_recsys_run_is_correct(recsys, args, env):
    res = train.run(recsys, args, env)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_recsys_fault_is_not_correct(recsys, fault, monkeypatch, args, env):
    _broken_step(monkeypatch, fault)
    res = train.run(recsys, args, env)
    assert not res["correct"], res["checks"]


def test_recsys_control_is_not_correct(recsys, args, env):
    cell = dataclasses.replace(
        recsys, config=dict(recsys.config, **CONTROL),
        traffic=dict(recsys.traffic, batch=32))
    readings = train.control(cell, args.seed, env.log)
    ok, checks = compare.verdict(readings["control"], cell.checks)
    assert not ok, checks
    assert compare.verdict(readings["program"], cell.checks)[0]
