"""Hierarchy-backed top-k decode on a 2x4 mesh (DESIGN.md §5): the index
arrays ride the vocab-sharded P('model') layout, each shard beams over its
local subtree, and the cross-shard merge reproduces the dense sharded
argmax/top-k bit-identically at full beam — on an untrained AND a
briefly-trained model, including non-divisible vocab padding.  The dense
sharded top-k, whose per-shard step is the two-stage ``logits_topk``,
equals a global ``lax.top_k`` bit for bit, ties included."""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.core import distributed, topk
from repro.launch.mesh import make_debug_mesh
from repro.models import api
from repro.models.transformer import init_cache
from repro.optim import make_optimizer
from repro.serve import engine, retrieval
from repro.serve.engine import make_decode_step, make_topk_step
from repro.sharding.rules import head_fd_axes, mesh_ctx
from repro.train.step import (
    export_retrieval_index,
    init_train_state,
    make_train_step,
)

B, S, K = 4, 16, 8

mesh = make_debug_mesh(dp=2, tp=4)
mctx = mesh_ctx(mesh)
# vocab 250 does not divide by tp=4: exercises padded rows (2 pads on the
# last shard) which must never be retrieved.
cfg = get_config("llama3-8b").reduced(vocab_size=250, m_negatives=32,
                                      sampler_block=16)
opt = make_optimizer("adamw", 1e-3)
state = init_train_state(jax.random.PRNGKey(0), cfg, mctx, opt, max_len=S)
step_fn = jax.jit(make_train_step(cfg, mctx, opt))


def batch_for(key):
    return {
        "tokens": jax.random.randint(key, (B, S), 0, cfg.vocab_size),
        "labels": jax.random.randint(jax.random.fold_in(key, 1), (B, S), 0,
                                     cfg.vocab_size),
    }


def check_stage(tag, params):
    head = api.head_table(params, cfg)
    h2d = jax.random.normal(jax.random.PRNGKey(7), (B, cfg.d_model))
    from repro.core.samplers import empty_state
    index = export_retrieval_index(
        type(state)(params=params, opt_state=None,
                    sampler_state=empty_state(),
                    step=jnp.zeros((), jnp.int32)), cfg, mctx, leaf_size=8)

    # full beam == dense sharded top-k (ids bit-identical, logits equal)
    ids_i, log_i = jax.jit(
        lambda h: engine.decode_topk(cfg, mctx, head, h, K, index=index))(h2d)
    ids_d, log_d = jax.jit(
        lambda h: engine.decode_topk(cfg, mctx, head, h, K))(h2d)
    np.testing.assert_array_equal(np.asarray(ids_i), np.asarray(ids_d))
    np.testing.assert_allclose(np.asarray(log_i), np.asarray(log_d),
                               rtol=1e-5, atol=1e-5)
    # ... and both equal the host-side dense oracle over the true vocab
    dense = (np.asarray(h2d, np.float32)
             @ np.asarray(head, np.float32)[:cfg.vocab_size].T)
    oracle_ids = np.argsort(-dense, axis=1)[:, :K]
    np.testing.assert_array_equal(np.asarray(ids_i), oracle_ids)
    assert (np.asarray(ids_i) < cfg.vocab_size).all(), "padding retrieved"

    # narrow beam: every returned candidate still carries its exact logit
    ids_n, log_n = jax.jit(lambda h: engine.decode_topk(
        cfg, mctx, head, h, K, index=index, beam=2))(h2d)
    got = np.asarray(log_n)
    for t in range(B):
        np.testing.assert_allclose(
            got[t], dense[t, np.asarray(ids_n)[t]], rtol=1e-5, atol=1e-5)
    print(f"{tag}: full-beam == dense top-{K}; narrow-beam logits exact")


check_stage("untrained", state.params)
for i in range(3):
    state, metrics = step_fn(state, batch_for(jax.random.PRNGKey(i)),
                             jax.random.PRNGKey(100 + i))
    assert np.isfinite(float(metrics["loss"]))
check_stage("trained(3 steps)", state.params)

# engine integration: topk step on the mesh agrees with the greedy decoder
index = export_retrieval_index(state, cfg, mctx, leaf_size=8)
caches = init_cache(cfg, B, S, mctx)
tok = jnp.zeros((B, 1), jnp.int32)
pos = jnp.full((B,), S - 1, jnp.int32)
nxt, _ = jax.jit(make_decode_step(cfg, mctx))(state.params, tok, caches,
                                              pos)
caches2 = init_cache(cfg, B, S, mctx)
ids, logits, _ = jax.jit(make_topk_step(cfg, mctx, K, index=index))(
    state.params, tok, caches2, pos)
np.testing.assert_array_equal(np.asarray(ids[:, 0]), np.asarray(nxt))
print("topk step top-1 == greedy decode on 2x4 mesh")


# --- the per-shard two-stage top-k (core/topk.py) ---------------------------
# Quarter-valued operands make every logit exact whatever the summation
# order, so the global lax.top_k over the same logits is the oracle, bit
# for bit, ties (to the lowest id) included.
mdl = mctx.model_axis
TP = mesh.shape[mdl]


def quarters(key, shape):
    return jnp.round(jax.random.normal(key, shape) * 2) / 4


def sharded_topk(w, h, k, n_valid):
    v_l = w.shape[0] // TP

    def island(w_l, h_l):
        my = jax.lax.axis_index(mdl)
        bias = jnp.where(jnp.arange(v_l) < n_valid - my * v_l, 0.0, -jnp.inf)
        return distributed.sharded_logits_topk(w_l, h_l, k, axis_name=mdl,
                                               bias_local=bias)

    return jax.jit(jax.shard_map(
        island, mesh=mesh, check_vma=False, in_specs=(P(mdl, None), P()),
        out_specs=(P(), P())))(w, h)


def oracle(w, h, k, n_valid):
    logits = jnp.where(jnp.arange(w.shape[0]) < n_valid, h @ w.T, -jnp.inf)
    vals, ids = jax.lax.top_k(logits, k)
    return ids, vals


# (shard width, k, valid rows): a split shard whose width G does not divide;
# k = 1 (plain: a max reduction); a split shard and a -inf tail past
# n_valid; a plain shard
for v_l, k, n_valid in [(10_000, 8, 40_000), (5_000, 1, 20_000),
                        (10_240, 8, 35_000), (2_000, 8, 8_000)]:
    split = topk.group_size(v_l, k) is not None
    assert split == (v_l >= 5_000 and k > 1), (v_l, k)
    w = quarters(jax.random.PRNGKey(v_l), (TP * v_l, 16))
    h = quarters(jax.random.PRNGKey(k), (6, 16))
    ids, vals = sharded_topk(w, h, k, n_valid)
    want_ids, want_vals = oracle(w, h, k, n_valid)
    np.testing.assert_array_equal(np.asarray(vals), np.asarray(want_vals))
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(want_ids))
    print(f"sharded top-{k}, {v_l} rows a shard (split {split}): "
          "== global lax.top_k")

# the engine's mesh decode over the head as given: G does not divide a shard
cfg_big = cfg.reduced(vocab_size=39_990, d_model=16)
head = jax.device_put(quarters(jax.random.PRNGKey(3), (TP * 10_000, 16)),
                      NamedSharding(mesh, P(mdl, head_fd_axes(mctx))))
decode = engine.make_decode_fn(cfg_big, mctx, head, K)
assert decode.args[0] is head and topk.group_size(10_000, K) is not None
h = quarters(jax.random.PRNGKey(4), (B, 16))
ids, vals = jax.jit(lambda fn, h: fn(None, h))(decode, h)
want_ids, want_vals = oracle(head, h, K, cfg_big.vocab_size)
np.testing.assert_array_equal(np.asarray(vals), np.asarray(want_vals))
np.testing.assert_array_equal(np.asarray(ids), np.asarray(want_ids))
print("mesh decode over an uneven head == global lax.top_k")

print("DECODE TOPK CHECKS PASSED")
