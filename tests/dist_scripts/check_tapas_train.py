"""TAPAS two-pass sampler on the mesh (8 host devices).

Two halves:
  * the sharded "sample → all-gather pool → re-score → resample" loss
    (DESIGN.md §2.8) equals a single-host reconstruction over the UNION of
    every shard's pool draws — pool order = all-gather (shard) order, the
    per-shard resample keys fold the shard index, and the eq. 2 correction
    is logq + ln m with no stratification factor (every shard draws from
    the same composed global q);
  * 2x4-mesh train steps with sampler="tapas": finite losses, the base
    family's carried statistics populated and refreshed on cadence.
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.core import distributed as dist
from repro.core.estimators import make_estimator
from repro.core.samplers import (
    BlockSampler,
    TapasSampler,
    categorical_rows,
    pool_log_inclusion,
)
from repro.launch.mesh import make_debug_mesh
from repro.optim import make_optimizer
from repro.sharding.rules import mesh_ctx
from repro.train.step import init_train_state, make_train_step

# --- part 1: sharded loss == single-host reconstruction ----------------------
mesh = jax.make_mesh((8,), ("model",))
n, d, T, m, pool = 1024, 32, 16, 64, 256
n_local, m_local, p_local = n // 8, m // 8, pool // 8
w = jax.random.normal(jax.random.PRNGKey(1), (n, d)) * 0.2
h = jax.random.normal(jax.random.PRNGKey(2), (T, d))
labels = jax.random.randint(jax.random.PRNGKey(3), (T,), 0, n)

sampler = TapasSampler(base=BlockSampler(block_size=32, shared=True),
                       pool=pool)
KEY = jax.random.PRNGKey(42)


def est_loss(w_local, h_rep, labels_rep, est_name):
    state_local = sampler.init(jax.random.PRNGKey(7), w_local)
    return dist.sharded_estimator_loss(
        make_estimator(est_name), w_local, h_rep, labels_rep, sampler,
        state_local, m, KEY, axis_name="model")


# Host reconstruction: replay each shard's pool draw and resample exactly.
k_pool, k_draw = jax.random.split(KEY)
pool_gids, pool_logpi = [], []
for s in range(8):
    w_s = w[s * n_local:(s + 1) * n_local]
    st_s = sampler.base.init(jax.random.PRNGKey(7), w_s)
    k_s = jax.random.fold_in(k_pool, s)
    pids, lq1 = sampler.base.sample_batch(st_s, h, p_local, k_s)
    pool_gids.append(np.asarray(pids) + s * n_local)
    # owner-shard inclusion IS the global inclusion (local q1, p_local draws)
    pool_logpi.append(np.asarray(pool_log_inclusion(lq1, p_local)))
pool_gids = np.concatenate(pool_gids)          # all-gather order = shard order
pool_logpi = np.concatenate(pool_logpi)

o_pool = jnp.einsum("td,pd->tp", h.astype(jnp.float32),
                    w[pool_gids].astype(jnp.float32))
mult = np.sum(pool_gids[None, :] == pool_gids[:, None], axis=0)
s_mat = (o_pool / sampler.tau
         - jnp.asarray(pool_logpi + np.log(mult), jnp.float32)[None, :])
lz = jax.nn.logsumexp(s_mat, axis=-1)
union_o, union_logq, union_gid = [], [], []
for s in range(8):
    k_s = jax.random.fold_in(k_draw, s)
    slots = categorical_rows(k_s, s_mat, m_local)
    union_o.append(np.asarray(jnp.take_along_axis(o_pool, slots, axis=1)))
    union_logq.append(np.asarray(
        jnp.take_along_axis(o_pool / sampler.tau, slots, axis=1)
        - lz[:, None]))
    union_gid.append(pool_gids[np.asarray(slots)])
union_o = np.concatenate(union_o, axis=1)          # (T, m)
union_logq = np.concatenate(union_logq, axis=1)
union_gid = np.concatenate(union_gid, axis=1)

o_full = np.asarray(h @ w.T)
pos_full = o_full[np.arange(T), np.asarray(labels)]
hit = union_gid == np.asarray(labels)[:, None]
o_adj = np.where(hit, -np.inf, union_o - union_logq - np.log(m))

for est_name in ("sampled-softmax", "sampled-logistic"):
    f = jax.jit(jax.shard_map(
        lambda wl, hr, lr, e=est_name: est_loss(wl, hr, lr, e),
        mesh=mesh, check_vma=False,
        in_specs=(P("model"), P(), P()), out_specs=P()))
    got = np.asarray(f(w, h, labels))
    if est_name == "sampled-softmax":
        want = np.log(np.exp(o_adj).sum(-1) + np.exp(pos_full)) - pos_full
    else:
        want = (np.logaddexp(0.0, -pos_full)
                + np.where(np.isneginf(o_adj), 0.0,
                           np.logaddexp(0.0, o_adj)).sum(-1))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
    assert np.isfinite(got).all()
print("sharded tapas loss == single-host pool-union reconstruction OK")

# Gradients flow through the pool all-gather back to the owning shard.
f_sum = jax.jit(jax.shard_map(
    lambda wl, hr, lr: jnp.sum(est_loss(wl, hr, lr, "sampled-softmax")),
    mesh=mesh, check_vma=False,
    in_specs=(P("model"), P(), P()), out_specs=P()))
gw, gh = jax.jit(jax.grad(f_sum, argnums=(0, 1)))(w, h, labels)
assert np.isfinite(np.asarray(gw)).all() and float(
    jnp.linalg.norm(gw)) > 0, "no gradient reached the head shards"
assert np.isfinite(np.asarray(gh)).all()
print("tapas pool-gather gradients OK")

# --- part 2: 2x4-mesh train steps --------------------------------------------
mesh24 = make_debug_mesh(dp=2, tp=4)
mctx = mesh_ctx(mesh24)
cfg = get_config("llama3-8b").reduced(
    m_negatives=32, sampler="tapas", tapas_pool=64, sampler_block=16,
    sampler_refresh_every=2)
B, S = 4, 16
opt = make_optimizer("adamw", 1e-3)
state = init_train_state(jax.random.PRNGKey(0), cfg, mctx, opt, max_len=S)
# tapas delegates its carried state to the pass-1 base (block-shared):
assert set(state.sampler_state.stats) == {"z", "cnt", "wq"}, (
    sorted(state.sampler_state.stats))
step_fn = jax.jit(make_train_step(cfg, mctx, opt))
losses = []
for i in range(4):
    batch = {
        "tokens": jax.random.randint(jax.random.PRNGKey(i), (B, S), 0,
                                     cfg.vocab_size),
        "labels": jax.random.randint(jax.random.PRNGKey(100 + i), (B, S), 0,
                                     cfg.vocab_size),
    }
    state, metrics = step_fn(state, batch, jax.random.PRNGKey(200 + i))
    losses.append(float(metrics["loss"]))
print("tapas mesh losses:", [f"{x:.3f}" for x in losses])
assert np.isfinite(losses).all()
# Carried statistics populated by the step-0 refresh: per-shard counts sum
# to the vocab.
cnt = np.asarray(state.sampler_state.stats["cnt"])
assert float(cnt.sum()) == float(cfg.vocab_size), (cnt.sum(), cfg.vocab_size)
print("TAPAS TRAIN CHECKS PASSED")
