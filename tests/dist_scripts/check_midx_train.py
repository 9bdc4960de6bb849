"""MIDX sampler on the mesh (DESIGN.md §2.9): the quantized two-level
stats carried in TrainState P('model')-sharded, the stratified draw through
``sharded_sampled_softmax_loss`` reconstructed exactly on the host, and
end-to-end train steps on a 2x4 mesh in BOTH refresh modes."""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.core import distributed as dist
from repro.core.samplers import MIDXSampler
from repro.data.pipeline import batch_iterator_for
from repro.launch.mesh import make_debug_mesh
from repro.optim import make_optimizer
from repro.sharding.rules import mesh_ctx
from repro.train.loop import fit
from repro.train.step import init_train_state, make_train_step

# ---- sharded loss == host reconstruction ------------------------------------
# Stratified midx draw over a vocab-sharded head: each shard samples m/tp
# from ITS local quantized index; the eq.-2 loss with global q~ = q_local/tp
# must equal a host-side replay of every shard's draws (bit-level sampling
# parity: same per-shard key fold, same deterministic k-means build).
mesh8 = jax.make_mesh((8,), ("model",))
n, d, T, m = 1024, 32, 16, 256
w = jax.random.normal(jax.random.PRNGKey(1), (n, d)) * 0.2
h = jax.random.normal(jax.random.PRNGKey(2), (T, d))
labels = jax.random.randint(jax.random.PRNGKey(3), (T,), 0, n)
sampler = MIDXSampler(codewords=8, list_size=8)


def loss_fn(w_local, h_rep, labels_rep):
    state_local = sampler.init(jax.random.PRNGKey(7), w_local)
    return dist.sharded_sampled_softmax_loss(
        w_local, h_rep, labels_rep, sampler, state_local, m,
        jax.random.PRNGKey(42), axis_name="model")


got = np.asarray(jax.jit(jax.shard_map(
    loss_fn, mesh=mesh8, check_vma=False,
    in_specs=(P("model"), P(), P()), out_specs=P()))(w, h, labels))
assert np.isfinite(got).all()

n_l = n // 8
o_full = np.asarray(h @ w.T, np.float64)
pos = o_full[np.arange(T), np.asarray(labels)]
neg_parts = []
for s in range(8):  # replay each shard's draws on the host
    st_s = sampler.init(jax.random.PRNGKey(7), w[s * n_l:(s + 1) * n_l])
    ids_s, logq_s = sampler.sample_batch(
        st_s, h, m // 8, jax.random.fold_in(jax.random.PRNGKey(42), s))
    gids = np.asarray(ids_s) + s * n_l                     # (T, m/8)
    lq = np.asarray(logq_s, np.float64) - np.log(8.0)      # global q~
    o_adj = (np.take_along_axis(o_full, gids, axis=1) - lq - np.log(m))
    hit = gids == np.asarray(labels)[:, None]
    neg_parts.append(np.where(hit, -np.inf, o_adj))
allx = np.concatenate([pos[:, None]] + neg_parts, axis=1)
c = allx.max(axis=1)
want = np.log(np.exp(allx - c[:, None]).sum(axis=1)) + c - pos
np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
print("sharded midx loss == host reconstruction OK")

# ---- end-to-end train, 2x4 mesh, sync refresh -------------------------------
B, S = 4, 16
mctx = mesh_ctx(make_debug_mesh(dp=2, tp=4))
cfg = get_config("llama3-8b").reduced(
    m_negatives=32, sampler="midx", sampler_block=16,
    sampler_proj_rank=None, sampler_refresh_every=2)
opt = make_optimizer("adamw", 1e-3)
state = init_train_state(jax.random.PRNGKey(0), cfg, mctx, opt, max_len=S)
stats = state.sampler_state.stats
assert stats["codes"].shape[1] == 2, stats["codes"].shape
assert stats["wq"].shape[0] * stats["wq"].shape[1] == stats["perm"].shape[0]
step_fn = jax.jit(make_train_step(cfg, mctx, opt))


def batch_for(key):
    return {
        "tokens": jax.random.randint(key, (B, S), 0, cfg.vocab_size),
        "labels": jax.random.randint(jax.random.fold_in(key, 1), (B, S), 0,
                                     cfg.vocab_size),
    }


losses = []
for i in range(4):
    state, metrics = step_fn(state, batch_for(jax.random.PRNGKey(i)),
                             jax.random.PRNGKey(100 + i))
    losses.append(float(metrics["loss"]))
print("midx mesh losses (sync):", [f"{x:.3f}" for x in losses])
assert np.isfinite(losses).all()
# Carried stats populated by the step-0 refresh: every shard's posting-list
# counts sum to its n_valid slice, totalling the vocab once across shards.
cnt = np.asarray(state.sampler_state.stats["cnt"])
assert float(cnt.sum()) == float(cfg.vocab_size), (cnt.sum(), cfg.vocab_size)
assert float(np.abs(np.asarray(state.sampler_state.stats["c1"])).sum()) > 0

# ---- end-to-end train, 2x4 mesh, overlapped refresh island ------------------
cfg_o = dataclasses.replace(cfg, refresh_mode="overlap",
                            sampler_refresh_every=3, refresh_stale_steps=1)
data_o = batch_iterator_for(cfg_o, mctx, global_batch=B, seq_len=S, seed=0)
res_o = fit(cfg_o, mctx, opt, data_o, steps=6, log_every=0, max_len=S)
assert np.all(np.isfinite(res_o.losses)), res_o.losses
assert res_o.refresh_swaps > 0, res_o.refresh_swaps
print("midx mesh losses (overlap):", [f"{x:.3f}" for x in res_o.losses],
      "swaps:", res_o.refresh_swaps)

print("MIDX TRAIN CHECKS PASSED")
