"""Hierarchy-backed top-k MIPS serving (serve/retrieval.py, DESIGN.md §5):
full-beam exactness against the dense head, the recall/beam knob on a
trained toy model, index export + checkpoint round trip, and the max-norm
upper-bound statistic.  The 2x4-mesh variant lives in
tests/dist_scripts/check_decode_topk.py."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import hierarchy
from repro.data.pipeline import batch_iterator_for
from repro.models import api
from repro.optim import make_optimizer
from repro.serve import engine, retrieval
from repro.sharding.rules import local_ctx
from repro.train.step import (
    export_retrieval_index,
    init_train_state,
    make_train_step,
)

CTX = local_ctx()


@pytest.mark.parametrize("cluster", [False, True])
@pytest.mark.parametrize("n,leaf", [(1000, 8), (256, 16), (130, 4)])
def test_full_beam_matches_dense(n, leaf, cluster):
    """beam >= num_leaves scores every class: ids identical to the dense
    top-k head, logits equal (both are fp32 dots against the same rows)."""
    d = 16
    w = jax.random.normal(jax.random.PRNGKey(n), (n, d)) * 0.3
    h = jax.random.normal(jax.random.PRNGKey(1), (6, d))
    idx = retrieval.build_index(w, leaf_size=leaf, cluster=cluster)
    ids, logits = retrieval.decode_topk(idx, h, 10)
    tids, tlog = retrieval.dense_topk(w, h, 10)
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(tids))
    np.testing.assert_allclose(np.asarray(logits), np.asarray(tlog),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("r", [16, 300], ids=["whole-space", "subspace"])
def test_spectral_stats_match_dense_eigh(r):
    """The spectral bound's top-s eigenpairs and residual cap agree with a
    float64 dense eigendecomposition: exactly when r fits the subspace,
    and after the power iterations on a decaying spectrum wider than it."""
    rng = np.random.default_rng(r)
    w = (rng.normal(size=(3, 2 * r, r))
         * np.linspace(2.0, 0.1, r)).astype(np.float32)
    z = np.einsum("nkr,nks->nrs", w, w)
    (evecs,), (evals,) = retrieval.spectral_stats((jnp.asarray(z),), s=4)
    want = np.linalg.eigvalsh(z.astype(np.float64))[:, ::-1][:, :5]
    assert np.abs(np.asarray(evals) - want).max() < 1e-5 * want.max()
    v = np.asarray(evecs, np.float64)                      # (3, 4, r)
    resid = np.einsum("nrs,nks->nkr", z, v) - want[:, :4, None] * v
    assert np.abs(resid).max() < 1e-4 * want[:, 0].max()


def test_narrow_beam_bounds_are_sound():
    """Every class the narrow beam returns carries its exact dense logit
    (approximation can only DROP candidates, never mis-score them)."""
    n, d = 512, 12
    w = jax.random.normal(jax.random.PRNGKey(3), (n, d)) * 0.4
    h = jax.random.normal(jax.random.PRNGKey(4), (5, d))
    idx = retrieval.build_index(w, leaf_size=8)
    ids, logits = retrieval.decode_topk(idx, h, 8, beam=4)
    dense = np.asarray(h.astype(jnp.float32) @ w.astype(jnp.float32).T)
    got = np.asarray(logits)
    for t in range(5):
        np.testing.assert_allclose(got[t], dense[t, np.asarray(ids)[t]],
                                   rtol=1e-5, atol=1e-5)
        assert (got[t][:-1] >= got[t][1:]).all()  # sorted descending


def test_ub_statistic_build_update_consistency():
    """levels_ub is max ||w||^2 per node, maintained by update_rows exactly
    as a rebuild would produce it (same cadence as the Gram sums)."""
    n, d = 256, 8
    w = jax.random.normal(jax.random.PRNGKey(8), (n, d))
    stats = hierarchy.build(w, 8, full_tree=True)
    # build: leaf ub equals the max squared row norm of each leaf block
    norms = np.asarray(jnp.sum(stats.wq * stats.wq, axis=-1))
    np.testing.assert_allclose(np.asarray(stats.levels_ub[-1]),
                               norms.max(axis=-1), rtol=1e-6)
    # and every parent is the max of its children
    for lvl in range(stats.depth):
        child = np.asarray(stats.levels_ub[lvl + 1])
        np.testing.assert_allclose(
            np.asarray(stats.levels_ub[lvl]),
            np.maximum(child[0::2], child[1::2]), rtol=1e-6)
    # update_rows == rebuild (including a shrinking max)
    ids = jnp.array([0, 17, 130, 255, 64])
    w_new = jax.random.normal(jax.random.PRNGKey(9), (5, d)) * 0.01
    upd = hierarchy.update_rows(stats, ids, w_new)
    rebuilt = hierarchy.build(w.at[ids].set(w_new), 8, full_tree=True)
    for a, b in zip(upd.levels_ub, rebuilt.levels_ub):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-7)


def test_heap_round_trip_rebuilds_ub():
    """from_heap recomputes levels_ub exactly (it is a pure fn of wq)."""
    w = jax.random.normal(jax.random.PRNGKey(5), (200, 8))
    stats = hierarchy.build(w, 8, full_tree=True)
    z, cnt = hierarchy.to_heap(stats)
    back = hierarchy.from_heap(z, cnt, stats.wq, stats.n_valid, stats.n)
    for a, b in zip(back.levels_ub, stats.levels_ub):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _train_toy(vocab=512, steps=300):
    cfg = get_config("youtube-dnn").reduced(
        vocab_size=vocab, sampler_block=64, tower_dims=(64, 32))
    cfg = dataclasses.replace(cfg, sampler="block-quadratic", m_negatives=64)
    opt = make_optimizer("adamw", 2e-2, weight_decay=0.0)
    data = batch_iterator_for(cfg, CTX, global_batch=128, seq_len=0, seed=0)
    state = init_train_state(jax.random.PRNGKey(0), cfg, CTX, opt, max_len=8)
    step = jax.jit(make_train_step(cfg, CTX, opt))
    for i in range(steps):
        state, _ = step(state, next(data),
                        jax.random.fold_in(jax.random.PRNGKey(9), i))
    batch = next(data)
    h, _, _ = api.backbone_hidden(state.params, batch, cfg, CTX)
    return cfg, state, h


def test_trained_model_full_beam_exact_and_narrow_beam_recall():
    """On a briefly-trained toy model: full beam == dense argmax
    bit-identically, and a narrow beam (25% of classes scored) keeps
    recall@10 >= 0.95."""
    cfg, state, h = _train_toy()
    head = api.head_table(state.params, cfg)
    idx = export_retrieval_index(state, cfg, CTX, leaf_size=4)

    # full beam: identical to the dense path (untrained covered above)
    ids, logits = retrieval.decode_topk(idx, h, 10)
    tids, tlog = retrieval.dense_topk(head, h, 10, n_valid=cfg.vocab_size)
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(tids))
    np.testing.assert_allclose(np.asarray(logits), np.asarray(tlog),
                               rtol=1e-6, atol=1e-6)

    # narrow beam: 32 of 128 leaves -> 25% of classes exactly scored
    beam = idx.num_leaves_shard // 4
    recall = retrieval.recall_at_k(idx, head, h, 10, beam)
    assert recall >= 0.95, (recall, beam)
    # engine-level consistency: decode_topk top-1 == the greedy argmax path
    ids1, _ = engine.decode_topk(cfg, CTX, head, h, 1, index=idx)
    dense1, _ = engine.decode_topk(cfg, CTX, head, h, 1)
    np.testing.assert_array_equal(np.asarray(ids1), np.asarray(dense1))


def test_make_topk_step_matches_greedy_decode():
    """The serving-engine topk step: ids[:, 0] == make_decode_step's greedy
    token, with and without an index."""
    B, S = 2, 8
    cfg = get_config("llama3-8b").reduced()
    params = api.init_params(jax.random.PRNGKey(0), cfg, CTX, max_len=S + 1)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                                cfg.vocab_size)
    _, caches = engine.make_prefill_step(cfg, CTX, max_len=S + 1)(
        params, {"tokens": tokens})
    nxt_ref, _ = engine.make_decode_step(cfg, CTX)(
        params, tokens[:, -1:], caches, jnp.full((B,), S, jnp.int32))

    head = api.head_table(params, cfg)
    idx = retrieval.build_index(head, leaf_size=16,
                                vocab_size=cfg.vocab_size)
    for kwargs in ({}, {"index": idx}):
        _, caches2 = engine.make_prefill_step(cfg, CTX, max_len=S + 1)(
            params, {"tokens": tokens})
        ids, logits, _ = engine.make_topk_step(cfg, CTX, 5, **kwargs)(
            params, tokens[:, -1:], caches2, jnp.full((B,), S, jnp.int32))
        assert ids.shape == (B, 5) and logits.shape == (B, 5)
        np.testing.assert_array_equal(np.asarray(ids[:, 0]),
                                      np.asarray(nxt_ref))


def test_index_checkpoint_round_trip(tmp_path):
    """RetrievalIndex is a plain pytree: save/restore through the
    CheckpointManager and serve identically without a rebuild."""
    from repro.checkpoint import CheckpointManager

    w = jax.random.normal(jax.random.PRNGKey(2), (300, 12)) * 0.5
    h = jax.random.normal(jax.random.PRNGKey(3), (4, 12))
    idx = retrieval.build_index(w, leaf_size=8)
    mgr = CheckpointManager(str(tmp_path), keep=2)
    mgr.save(1, idx, blocking=True)
    like = jax.tree_util.tree_map(jnp.zeros_like, idx)
    restored, _ = mgr.restore(like=like)
    assert restored.n == idx.n and restored.v_shard == idx.v_shard
    ids_a, log_a = retrieval.decode_topk(idx, h, 7, beam=8)
    ids_b, log_b = retrieval.decode_topk(restored, h, 7, beam=8)
    np.testing.assert_array_equal(np.asarray(ids_a), np.asarray(ids_b))
    np.testing.assert_array_equal(np.asarray(log_a), np.asarray(log_b))


def test_leaf_dots_kernel_matches_ref():
    """The dot-mode leaf kernel (retrieval's exact scorer) == the oracle."""
    from repro.kernels import ops, ref

    h = jax.random.normal(jax.random.PRNGKey(0), (37, 16))
    table = jax.random.normal(jax.random.PRNGKey(1), (6, 8, 16))
    idx = jax.random.randint(jax.random.PRNGKey(2), (37, 4), 0, 6)
    np.testing.assert_allclose(np.asarray(ops.leaf_dots(h, table, idx)),
                               np.asarray(ref.leaf_dots_ref(h, table, idx)),
                               rtol=1e-5, atol=1e-5)
