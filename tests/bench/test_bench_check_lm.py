"""What decides ``correct`` for the LM train cell, driven on the CPU at a
size a test can hold.

Each test skips the harness's look for a chip and drives the rest of a run
(set-up, window, reference, verdict) of a cell cut to a tiny size
(``conftest.py``), against the cell's own limits: the sound program
passes; with the timed path broken underneath (a step that returns its
state unchanged, half of the batch left out) or with the float8 control in
the reference's place, ``correct`` comes out false.  The control is read
with the program in bfloat16, as the configuration states, at four layers:
at two the float8 hidden states part the draws from the reference's too
little to show at these widths.
"""
import dataclasses

import jax
import numpy as np
import jax.numpy as jnp
import pytest

from bench import compare
from bench.kinds import train

def _broken_step(monkeypatch, fault):
    from repro.train import step as step_mod

    real = step_mod.make_train_step

    def make(cfg, ctx, opt, **kw):
        inner = real(cfg, ctx, opt, **kw)

        def step(state, batch, key):
            if fault == "half_batch":
                half = {k: v[: max(1, v.shape[0] // 2)]
                        if v.shape[0] > 1 else v[:, : v.shape[1] // 2]
                        for k, v in batch.items()}
                return inner(state, half, key)
            new, met = inner(state, batch, key)
            return jax.tree_util.tree_map(jnp.copy, state), met

        return step

    monkeypatch.setattr(step_mod, "make_train_step", make)


@pytest.mark.parametrize("which", ["lm"])
def test_sound_train_run_is_correct(which, request, args, env):
    res = train.run(request.getfixturevalue(which), args, env)
    assert res["correct"], res["checks"]


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
@pytest.mark.parametrize("which", ["lm"])
def test_train_fault_is_not_correct(which, fault, request, monkeypatch,
                                    args, env):
    _broken_step(monkeypatch, fault)
    res = train.run(request.getfixturevalue(which), args, env)
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("which", ["lm"])
def test_train_control_is_not_correct(which, request, args, env):
    # the program and the reference's storage in bfloat16, as the
    # configuration states, against the float8 control
    cell = request.getfixturevalue(which)
    cell = dataclasses.replace(cell, config=dict(
        cell.config, dtype="bfloat16", param_dtype="bfloat16", n_layers=4))
    readings = train.control(cell, args.seed, env.log)
    ok, checks = compare.verdict(readings["control"], cell.checks)
    assert not ok, checks


def test_rows_gap_counts_rows_moved_on_one_side_only():
    from bench import reference

    want = np.zeros((6, 3), np.float32)
    want[[0, 2, 3]] = 1.0
    got = np.zeros((6, 3), np.float32)
    got[[0, 2, 5], 1] = -2.0
    moved = [np.asarray(reference.moved_rows(g)) for g in (got, want)]
    assert moved[1].tolist() == [True, False, True, True, False, False]
    assert reference.rows_gap(*moved) == 2 / 3
    assert reference.rows_gap(np.zeros(6, bool), moved[1]) == 1.0


def test_stored_parameters_are_rounded_to_their_type():
    from bench import reference

    x = jnp.asarray([1.0, 1.0 + 2.0 ** -10], jnp.float32)
    got = jax.jit(lambda v: reference.round_to(jnp.bfloat16, v + 0.0))(x)
    assert np.asarray(got).tolist() == [1.0, 1.0]
    assert reference.round_to(jnp.float32, x) is x
