"""The serving kind (``bench/kinds/serve.py``), its reference and readers,
driven on the CPU at a size a test can hold.

The cell ``serve.youtube-dnn-1m.softmax.dense`` is cut to a 4,096-item
catalog, watch embeddings and user vectors of 32, a pool of 1,024 users
and a window of half a second.  Each test skips the harness's look for a
chip.  The sound program reads ``correct`` under the cell's own limits;
the reference put in its place in float8, over half of the catalog or
in ascending order does not, and neither does a run whose decode is
broken underneath (an answer altered where it is produced, half of each
microbatch's rows left out).  Under the absolute softmax of
``youtube-dnn-1m`` the reference ranks by |o|, and a decode that ranks by
o fails.
"""
import argparse
import dataclasses
import os
import time

import jax.numpy as jnp
import numpy as np
import pytest

from bench import decode_roofline, harness, serve_reference, tracing
from bench.kinds import serve
from conftest import SEED

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
CELL = "serve.youtube-dnn-1m.softmax.dense"
#: the configuration cut to a test's size
TINY = dict(vocab_size=4096, d_model=32, history_len=5, user_feature_dim=8,
            tower_dims=[64, 32])


@pytest.fixture(scope="module")
def tiny():
    """The cell under its own limits, cut to TINY: 1,024 users, 1,000
    requests a second batched for up to 20 ms (so that every microbatch
    holds several, and the CPU keeps up under a loaded test run), 256 of
    them checked."""
    cell = harness.load_cell(ROOT, CELL)
    mix = cell.traffic
    return dataclasses.replace(
        cell, config=dict(cell.config, **TINY),
        traffic=dict(mix, users=dict(mix["users"], batch=256, ring=4),
                     rate=1000.0, max_wait_ms=20.0, check_sample=256))


@pytest.fixture
def run_args():
    return argparse.Namespace(seed=SEED, seconds=0.5, trace=0)


def test_the_program_reads_correct(tiny, env, run_args):
    res = serve.run(tiny, run_args, env)
    assert res["correct"], res["checks"]
    assert res["attempted"] == 500 and res["failed"] == 0
    assert res["e2e"]["serve_p90_ms"] > 0
    inp = res["layer_input"]
    assert inp["completed"] == 500 and inp["batch_real"] == 500
    assert inp["batch_slots"] >= inp["batch_real"] and inp["microbatches"]


def test_an_answer_held_up_past_a_second_is_late_not_failed(tiny, env,
                                                           run_args,
                                                           monkeypatch):
    """The window's first decode stalls 1.2 s, as a host that stands still
    would: every request still answers, late, and the run reads correct,
    its tail showing the stall."""
    replay, calls = serve.replay, []

    def stalled(engine, *a, **kw):
        calls.append(engine)
        if len(calls) == 2:  # the window; set-up's warm-up came first
            decode, stalled_once = engine._decode, []

            def slow(*args):
                if not stalled_once:
                    stalled_once.append(True)
                    time.sleep(1.2)
                return decode(*args)
            engine._decode = slow
        return replay(engine, *a, **kw)

    monkeypatch.setattr(serve, "replay", stalled)
    res = serve.run(tiny, run_args, env)
    assert res["failed"] == 0 and res["correct"], res["checks"]
    assert res["e2e"]["serve_p90_ms"] > 600.0


def _altered(topk):
    """The decode with the last answer of every row altered: the next id,
    its logit kept."""
    def broken(w, h, k, n_valid=None):
        ids, vals = topk(w, h, k, n_valid=n_valid)
        return ids.at[:, -1].set((ids[:, -1] + 1) % w.shape[0]), vals
    return broken


def _half_rows(topk):
    """The decode with half of each microbatch's rows left out: every odd
    row answered from the even row before it."""
    def broken(w, h, k, n_valid=None):
        return topk(w, jnp.repeat(h[0::2], 2, axis=0), k, n_valid=n_valid)
    return broken


@pytest.mark.parametrize("fault", [_altered, _half_rows])
def test_a_broken_decode_reads_not_correct(tiny, env, run_args, monkeypatch,
                                           fault):
    from repro.serve import retrieval

    monkeypatch.setattr(retrieval, "dense_topk", fault(retrieval.dense_topk))
    res = serve.run(tiny, run_args, env)
    assert res["failed"] == 0
    assert not res["correct"], res["checks"]


def test_the_control_and_each_fault_fail_the_limits(tiny):
    """``python3 -m bench.control``'s readings at the test's size: the
    program within every limit, each other reading outside one."""
    from bench import compare

    readings = serve.control(tiny, SEED + 1, lambda m: None)
    verdicts = {name: compare.verdict(numbers, tiny.checks)[0]
                for name, numbers in readings.items()}
    assert verdicts == {"program": True, "control": False,
                        "half_catalog": False, "ascending": False}
    assert readings["ascending"]["unsorted"] == 256


def test_under_abs_softmax_the_reference_ranks_by_abs_and_raw_o_fails(tiny):
    """Against the reference of an ``abs_softmax`` configuration, answers
    ranked by o (not |o|) miss about half of the due ids."""
    from bench import compare

    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.normal(0, 0.05, (4096, 32)), jnp.float32)
    h = rng.normal(0, 1, (64, 32)).astype(np.float32)
    ref = serve_reference.reference_topk(h, w, 100, abs_mode=True)
    o = h.astype(np.float64) @ np.asarray(w, np.float64).T
    want = np.argsort(-np.abs(o), axis=1)[:, :100]
    assert np.array_equal(np.sort(ref["ids"], 1), np.sort(want, 1))
    ids, logits = serve_reference.control_answers(h, w, 100, True, "raw_o")
    numbers = serve_reference.numbers(
        ids, logits, ref, serve_reference.scores_of(h, w, ids, True))
    assert numbers["topk_miss"] > 0.3
    assert not compare.verdict(numbers, tiny.checks)[0]


def test_reference_topk_and_its_due_ids():
    """Top k over blocks equals a float64 top k; an id is due unless its
    score lies within the two rounding bounds of the best id left out,
    and a program that rounds as the configuration states misses none."""
    rng = np.random.default_rng(1)
    w = jnp.asarray(rng.normal(0, 0.05, (1000, 16)), jnp.float32)
    h = rng.normal(0, 1, (8, 16)).astype(np.float32)
    ref = serve_reference.reference_topk(h, w, 10, abs_mode=False, block=256)
    w64, h64 = np.asarray(w, np.float64), h.astype(np.float64)
    o = h64 @ w64.T
    order = np.argsort(-o, axis=1)
    assert np.array_equal(ref["ids"], order[:, :10])
    np.testing.assert_allclose(ref["s"], np.take_along_axis(o, order[:, :10],
                                                            1), rtol=1e-5)
    e = serve_reference.gamma(16) * (np.abs(h64) @ np.abs(w64).T)
    rest = np.take_along_axis(o + e, order[:, 10:], 1).max(axis=1)
    due = np.take_along_axis(o - e, order[:, :10], 1) > rest[:, None]
    assert np.array_equal(ref["due"], due)
    assert 0.5 < due.mean() < 1.0
    # one bfloat16 pass, as the TPU computes float32 at default precision
    def bf(x):
        return np.asarray(jnp.asarray(x, jnp.bfloat16), np.float64)

    ob = bf(h) @ bf(np.asarray(w)).T
    got = np.argsort(-ob, axis=1)[:, :10]
    numbers = serve_reference.numbers(
        got, np.take_along_axis(ob, got, 1), ref,
        serve_reference.scores_of(h, w, got, False))
    assert numbers["topk_miss"] == 0.0 and numbers["unsorted"] == 0.0
    assert numbers["logit_gap"] < 0.01


class _StallingEngine:
    """Answers at once, but its first submit stalls the client 50 ms."""

    def __init__(self):
        self.calls = 0

    def submit(self, h):
        from repro.serve.server import ServeResult

        self.calls += 1
        if self.calls == 1:
            time.sleep(0.05)
        r = ServeResult(np.zeros(1, np.int32), np.zeros(1, np.float32), True,
                        None, 0, False, 0.0)
        return argparse.Namespace(result_wait=lambda timeout=None: r)

    def counters(self):
        return {"queue_depth": 0}


def test_a_stalled_client_shows_as_latency():
    harness.add_program_path(ROOT)
    times = np.linspace(0.0, 0.02, 11)
    pool = np.zeros((4, 2), np.float32)
    sent = serve.replay(_StallingEngine(), pool, times, np.zeros(11, int))
    got = serve.collect(sent, times, 1)
    assert got["ok"].all()
    # requests due during the stall wait for it, though the engine
    # answered each at once
    for i in range(1, 6):
        assert got["latency_ms"][i] >= 50.0 - times[i] * 1e3 - 1e-6
    np.testing.assert_allclose(got["latency_ms"], got["late_ms"])


def test_percentile_of_a_known_sample():
    values = np.random.default_rng(2).permutation(np.arange(1, 101))
    assert serve.percentile(values, 90) == 90
    assert serve.percentile(values, 99) == 99
    assert serve.percentile(values, 50) == 50
    assert serve.percentile(np.arange(1, 11), 90) == 9
    assert serve.percentile(np.arange(1, 11), 95) == 10
    assert serve.percentile([7.5], 99) == 7.5


def test_a_schedule_offers_the_same_work_on_every_seed():
    a_t, a_u = serve.schedule(2 ** 40 + 3, 2, 1000.0, 2.0, 500)
    b_t, b_u = serve.schedule(5, 2, 1000.0, 2.0, 500)
    assert len(a_t) == len(b_t) == 2000
    assert np.all(np.diff(a_t) >= 0) and 0 <= a_t[0] and a_t[-1] < 2.0
    assert a_u.min() >= 0 and a_u.max() < 500
    again_t, again_u = serve.schedule(2 ** 40 + 3, 2, 1000.0, 2.0, 500)
    assert np.array_equal(a_t, again_t) and np.array_equal(a_u, again_u)
    assert not np.array_equal(a_t, b_t)


#: the cell's size and the v5e's peaks
N, D, K = 1_000_000, 256, 100
PEAK_FLOPS, PEAK_BW = 197e12, 819e9


@pytest.mark.parametrize("bucket", [16, 64, 256])
def test_decode_bytes_and_flops_at_the_cell_size(bucket):
    table = 1_000_000 * 256 * 4
    assert decode_roofline.decode_bytes(bucket, N, D, K) == (
        table + bucket * 256 * 4 + bucket * 100 * (4 + 4))
    assert decode_roofline.decode_flops(bucket, N, D) == (
        2 * bucket * 1_000_000 * 256)
    assert decode_roofline.memory_bound(bucket, N, D, K, PEAK_FLOPS, PEAK_BW)
    assert decode_roofline.roofline_seconds(
        bucket, N, D, K, PEAK_FLOPS, PEAK_BW) == pytest.approx(
        decode_roofline.decode_bytes(bucket, N, D, K) / PEAK_BW)


def test_window_roofline_is_the_sum_of_its_microbatches():
    counts = {16: 5, 64: 3, 256: 7}
    each = sum(c * decode_roofline.roofline_seconds(b, N, D, K, PEAK_FLOPS,
                                                    PEAK_BW)
               for b, c in counts.items())
    whole = decode_roofline.window_roofline_seconds(
        sum(counts.values()), sum(b * c for b, c in counts.items()), N, D, K,
        PEAK_FLOPS, PEAK_BW)
    assert whole == pytest.approx(each, rel=1e-12)
    assert whole == pytest.approx(15 * 1.25e-3, rel=0.01)


def _serve_run(**over):
    """A traced serving window's reader input: 10 ms, the device busy 4 of
    it over two ops, 2 microbatches of 16 slots holding 20 requests."""
    trace = tracing.Trace(
        devices={"/device:TPU:0": [(0, 1e6, "fusion.1"), (5e5, 2e6, "top-k"),
                                   (6e6, 8e6, "fusion.1")]},
        spans=[(0, 1e7, "bench.window")])
    run = dict(kind="serve", trace=trace, lo=0.0, hi=1e7, chips=1,
               peak_flops=PEAK_FLOPS, peak_bytes_per_s=PEAK_BW,
               microbatches=2, batch_slots=32, batch_real=20, completed=20,
               seconds=0.01, n=N, d=D, k=K, table_itemsize=4)
    run.update(over)
    return run


def _read(name, run):
    return harness.metric_reader(ROOT, name)(run)


def test_serving_readers_on_a_synthetic_trace():
    run = _serve_run()
    assert _read("decode_ms.serve", run) == pytest.approx(2.0)
    assert _read("idle_share.serve", run) == pytest.approx(60.0)
    assert _read("batch_fill.serve", run) == pytest.approx(62.5)
    least = 2 * (N * D * 4) / PEAK_BW + 32 * (D * 4 + K * 8) / PEAK_BW
    assert _read("topk_roofline.serve", run) == pytest.approx(
        100 * least / 4e-3)
    assert _read("mfu.serve", run) == pytest.approx(
        100 * 2 * N * D * 20 / (0.01 * PEAK_FLOPS))


@pytest.mark.parametrize("name", ["decode_ms.serve", "batch_fill.serve",
                                  "idle_share.serve", "topk_roofline.serve",
                                  "mfu.serve"])
def test_serving_readers_find_nothing_in_a_train_run(name):
    assert _read(name, _serve_run(kind="train")) is None
    if name != "mfu.serve":
        empty = _serve_run(microbatches=0, batch_slots=0,
                           trace=tracing.Trace({}, []))
        assert _read(name, empty) is None
