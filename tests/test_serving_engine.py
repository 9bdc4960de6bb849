"""Async serving engine: continuous batching, zero-downtime refresh, cache,
deadlines, counters (DESIGN.md §5.1).

The refresh-under-load test is the atomicity contract's teeth: while a
stream of queries is in flight the index is swapped mid-stream, and every
single answer must equal EITHER the old index's output or the new index's
output for that query — never a mix — and must match the version the
engine says served it.
"""
import threading
import time

import jax
import numpy as np
import pytest

from repro.serve import retrieval
from repro.serve.server import LatencyHistogram, ServingEngine
from repro.sharding.rules import local_ctx

CTX = local_ctx()
N, D, K = 256, 16, 5


def _table(seed: int) -> np.ndarray:
    """Clustered class-embedding table (mixture of a few directions) so the
    retrieval hierarchy has real structure to exploit."""
    rng = np.random.default_rng(seed)
    centers = rng.normal(size=(8, D))
    w = centers[rng.integers(0, 8, N)] + 0.3 * rng.normal(size=(N, D))
    return w.astype(np.float32)


def _decode_fn(head: np.ndarray):
    """(index, h) -> (ids, logits); index=None is the dense path.  The
    branch is on the PYTREE STRUCTURE of index, so both paths jit-compile
    as distinct treedefs and an index swap never recompiles."""
    w = np.asarray(head)

    def decode(index, h):
        if index is None:
            return retrieval.dense_topk(w, h, K, n_valid=N)
        return retrieval.decode_topk(index, h, K, None, CTX)

    return decode


def _queries(seed: int, n: int) -> np.ndarray:
    return np.random.default_rng(seed).normal(size=(n, D)).astype(np.float32)


# --- batching correctness ---------------------------------------------------


@pytest.mark.parametrize("use_index", [False, True], ids=["dense", "index"])
def test_bucket_padding_matches_direct(use_index):
    """7 concurrent queries into buckets (4, 8): the non-divisible arrival
    pads up to 8 and the masked rows must not leak into any answer."""
    w = _table(0)
    index = retrieval.build_index(w, CTX) if use_index else None
    h = _queries(1, 7)
    if use_index:
        ref_ids, ref_lg = retrieval.decode_topk(index, h, K, None, CTX)
    else:
        ref_ids, ref_lg = retrieval.dense_topk(w, h, K, n_valid=N)

    eng = ServingEngine(_decode_fn(w), D, K, buckets=(4, 8),
                        max_wait_ms=5.0, index=index).start()
    try:
        futs = [eng.submit(h[i]) for i in range(7)]
        results = [f.result_wait(30.0) for f in futs]
    finally:
        eng.stop()
    for i, r in enumerate(results):
        assert r.ok, r.error
        np.testing.assert_array_equal(r.ids, np.asarray(ref_ids)[i])
        np.testing.assert_allclose(r.logits, np.asarray(ref_lg)[i],
                                   rtol=1e-5, atol=1e-5)
    c = eng.counters()
    assert c["completed"] == 7
    assert c["batch_real"] == 7
    assert c["batch_slots"] >= 7  # padded


def test_single_query_roundtrip_dense():
    w = _table(0)
    eng = ServingEngine(_decode_fn(w), D, K, buckets=(1, 4)).start()
    try:
        h = _queries(2, 1)[0]
        r = eng.decode(h)
        ref_ids, _ = retrieval.dense_topk(w, h[None], K, n_valid=N)
        assert r.ok and r.index_version == 0 and not r.cached
        np.testing.assert_array_equal(r.ids, np.asarray(ref_ids)[0])
    finally:
        eng.stop()


# --- zero-downtime refresh --------------------------------------------------


def test_refresh_under_load_never_mixes_indexes():
    """Swap v0 -> v1 while ~200 queries stream through: every answer is
    entirely v0's or entirely v1's, matches its reported version, and no
    request fails."""
    w0, w1 = _table(0), _table(7)
    idx0 = retrieval.build_index(w0, CTX)
    idx1 = retrieval.build_index(w1, CTX)
    pool = _queries(3, 16)
    ref = {
        0: np.asarray(retrieval.decode_topk(idx0, pool, K, None, CTX)[0]),
        1: np.asarray(retrieval.decode_topk(idx1, pool, K, None, CTX)[0]),
    }

    eng = ServingEngine(_decode_fn(w0), D, K, buckets=(2, 4, 8),
                        max_wait_ms=1.0, default_deadline_ms=30_000.0,
                        index=idx0, index_version=0).start()
    swapped = threading.Event()

    def swapper():
        time.sleep(0.03)  # let some of the stream run on v0
        eng.swap_index(idx1, version=1, train_step=1)
        swapped.set()

    th = threading.Thread(target=swapper)
    th.start()
    try:
        futs = []
        for i in range(200):
            futs.append((i % 16, eng.submit(pool[i % 16])))
            if i % 20 == 19:
                time.sleep(0.005)  # spread the stream across the swap
        results = [(pid, f.result_wait(60.0)) for pid, f in futs]
    finally:
        th.join()
        eng.stop()

    versions = set()
    for pid, r in results:
        assert r.ok, r.error
        assert r.index_version in (0, 1)
        versions.add(r.index_version)
        # the whole answer belongs to the version the engine reported —
        # a mixed-index answer would match neither reference exactly
        np.testing.assert_array_equal(r.ids, ref[r.index_version][pid])
    assert swapped.is_set()
    assert versions == {0, 1}, (
        f"swap did not land mid-stream (saw versions {versions}); "
        "timing too skewed to exercise the contract")
    c = eng.counters()
    assert c["index_swaps"] == 1
    assert c["completed"] == 200 and c["expired"] == 0


# --- deadlines ---------------------------------------------------------------


def test_deadline_expiry_fails_fast():
    w = _table(0)
    eng = ServingEngine(_decode_fn(w), D, K, buckets=(1, 2))
    # submit BEFORE start so the request provably sits past its deadline
    fut = eng.submit(_queries(4, 1)[0], deadline_ms=1.0)
    time.sleep(0.05)
    eng.start()
    try:
        r = fut.result_wait(10.0)
        assert not r.ok and r.error == "deadline exceeded"
        assert r.ids is None
        live = eng.decode(_queries(5, 1)[0])  # engine still serves
        assert live.ok
        c = eng.counters()
        assert c["expired"] == 1 and c["completed"] == 1
        assert c["submitted"] == 2
    finally:
        eng.stop()


def test_stop_fails_pending():
    w = _table(0)
    eng = ServingEngine(_decode_fn(w), D, K)  # never started
    fut = eng.submit(_queries(6, 1)[0])
    eng.stop()
    r = fut.result_wait(1.0)
    assert not r.ok and r.error == "engine stopped"


# --- hot-query cache ---------------------------------------------------------


def test_cache_hit_equivalence_and_swap_invalidation():
    w0, w1 = _table(0), _table(7)
    idx0 = retrieval.build_index(w0, CTX)
    idx1 = retrieval.build_index(w1, CTX)
    h = _queries(8, 1)[0]
    ref0 = np.asarray(retrieval.decode_topk(idx0, h[None], K, None, CTX)[0])[0]
    ref1 = np.asarray(retrieval.decode_topk(idx1, h[None], K, None, CTX)[0])[0]

    eng = ServingEngine(_decode_fn(w0), D, K, buckets=(1, 2),
                        cache_size=32, index=idx0, index_version=0).start()
    try:
        r1 = eng.decode(h)
        assert r1.ok and not r1.cached
        np.testing.assert_array_equal(r1.ids, ref0)
        r2 = eng.decode(h)
        assert r2.ok and r2.cached, "identical query must hit the cache"
        np.testing.assert_array_equal(r2.ids, r1.ids)
        np.testing.assert_array_equal(r2.logits, r1.logits)
        assert r2.index_version == 0

        # version-scoped keys: the swap is an implicit full invalidation
        eng.swap_index(idx1, version=1)
        r3 = eng.decode(h)
        assert r3.ok and not r3.cached, "swap must invalidate cached answers"
        assert r3.index_version == 1
        np.testing.assert_array_equal(r3.ids, ref1)

        c = eng.counters()
        assert c["cache_hits"] == 1 and c["cache_misses"] == 2
        assert abs(c["cache_hit_rate"] - 1 / 3) < 1e-9
    finally:
        eng.stop()


def test_cache_quantization_buckets_nearby_queries():
    w = _table(0)
    h = _queries(9, 1)[0]
    eng = ServingEngine(_decode_fn(w), D, K, buckets=(1,),
                        cache_size=8, cache_quant=1e-2).start()
    try:
        r1 = eng.decode(h)
        r2 = eng.decode(h + 1e-4)  # within quantization bucket
        assert not r1.cached and r2.cached
        np.testing.assert_array_equal(r1.ids, r2.ids)
    finally:
        eng.stop()


# --- observability -----------------------------------------------------------


def test_counters_and_staleness():
    w = _table(0)
    idx = retrieval.build_index(w, CTX)
    eng = ServingEngine(_decode_fn(w), D, K, buckets=(1, 2), index=idx,
                        index_version=0, index_train_step=100).start()
    try:
        for q in _queries(10, 4):
            eng.decode(q)
        eng.note_train_step(130)
        c = eng.counters()
        assert c["index_staleness_steps"] == 30
        assert c["submitted"] == c["completed"] + c["expired"] == 4
        assert 0.0 < c["batch_occupancy"] <= 1.0
        assert c["latency_ms"]["count"] == 4
        assert c["latency_ms"]["p99"] >= c["latency_ms"]["p50"] > 0.0
        eng.swap_index(idx, version=1, train_step=130)
        assert eng.counters()["index_staleness_steps"] == 0
    finally:
        eng.stop()


def test_latency_histogram_percentiles():
    hist = LatencyHistogram(lo_ms=0.01, hi_ms=1000.0, growth=1.1)
    rng = np.random.default_rng(0)
    xs = rng.uniform(1.0, 100.0, 5000)
    for x in xs:
        hist.record(float(x))
    snap = hist.snapshot()
    assert snap["count"] == 5000
    # log-bucketed readout: ~10% relative error tolerance
    assert abs(snap["p50"] - np.percentile(xs, 50)) / np.percentile(xs, 50) \
        < 0.15
    assert abs(snap["p99"] - np.percentile(xs, 99)) / np.percentile(xs, 99) \
        < 0.15
    assert snap["max"] == pytest.approx(xs.max())
    assert hist.percentile(0) <= snap["p50"] <= snap["p90"] <= snap["p99"]


def test_rejects_bad_query_dim_and_bad_buckets():
    w = _table(0)
    eng = ServingEngine(_decode_fn(w), D, K)
    with pytest.raises(ValueError, match="d_model"):
        eng.submit(np.zeros(D + 1, np.float32))
    with pytest.raises(ValueError, match="buckets"):
        ServingEngine(_decode_fn(w), D, K, buckets=(4, 2))


def test_failing_decode_fails_every_request_and_stop_reraises():
    """A decode that raises must not leave futures waiting: the in-flight
    and queued requests fail with the error, later submissions fail at
    once, and stop() re-raises the worker's exception."""

    def broken(index, h):
        raise ValueError("boom")

    eng = ServingEngine(broken, D, K, buckets=(4,),
                        max_wait_ms=20.0).start(warmup=False)
    futs = [eng.submit(q) for q in _queries(4, 6)]
    results = [f.result_wait(30.0) for f in futs]
    late = eng.submit(_queries(5, 1)[0]).result_wait(1.0)
    for r in results + [late]:
        assert not r.ok and r.ids is None
        assert r.error == "decode failed: ValueError: boom", r.error
    assert eng.counters()["failed"] == 7
    with pytest.raises(RuntimeError, match="serving worker died") as info:
        eng.stop()
    assert isinstance(info.value.__cause__, ValueError)


def test_make_decode_fn_passes_head_as_argument():
    """The head table enters the compiled decode as an input: closed over,
    it would be baked into the program as an (n, d) constant."""
    from repro.configs import get_config
    from repro.serve.engine import make_decode_fn

    cfg = get_config("youtube-dnn").reduced(vocab_size=N)
    w = _table(0)
    eng = ServingEngine(make_decode_fn(cfg, CTX, w, K), D, K, buckets=(2,))
    lowered = eng._decode.lower(eng._fn, None, np.zeros((2, D), np.float32))
    shapes = [a.shape for a in jax.tree_util.tree_leaves(lowered.args_info)]
    assert (N, D) in shapes, shapes
    eng.start()
    try:
        h = _queries(6, 1)[0]
        r = eng.decode(h)
    finally:
        eng.stop()
    ref_ids, _ = retrieval.dense_topk(w, h[None], K, n_valid=N)
    assert r.ok
    np.testing.assert_array_equal(r.ids, np.asarray(ref_ids)[0])
