"""Scratch validation of the vocab-sharded sampled softmax (8 host devices)."""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import distributed as dist
from repro.core.sampled_softmax import full_softmax_loss
from repro.core.samplers import BlockSampler, UniformSampler

mesh = jax.make_mesh((8,), ("model",))
n, d, T, m = 1024, 32, 16, 256
key = jax.random.PRNGKey(0)
w = jax.random.normal(jax.random.PRNGKey(1), (n, d)) * 0.2
h = jax.random.normal(jax.random.PRNGKey(2), (T, d))
labels = jax.random.randint(jax.random.PRNGKey(3), (T,), 0, n)

sampler = BlockSampler(block_size=32, shared=True)


def loss_fn(w_local, h_rep, labels_rep):
    # build the local sampler state in-island (rank-0 n_valid stays inside)
    state_local = sampler.init(jax.random.PRNGKey(7), w_local)
    return dist.sharded_sampled_softmax_loss(
        w_local, h_rep, labels_rep, sampler, state_local, m,
        jax.random.PRNGKey(42), axis_name="model")


loss_sharded = jax.jit(jax.shard_map(
    loss_fn, mesh=mesh, check_vma=False,
    in_specs=(P("model"), P(), P()),
    out_specs=P()))

loss = loss_sharded(w, h, labels)
print("sharded sampled loss:", np.asarray(loss.mean()))
ref = full_softmax_loss(w, h, labels)
print("full softmax loss:   ", np.asarray(ref.mean()))
assert np.isfinite(np.asarray(loss)).all()

# Full-softmax sharded eval must match the unsharded reference exactly.
eval_sharded = jax.jit(jax.shard_map(
    lambda wl, hr, lr: dist.sharded_full_softmax_loss(
        wl, hr, lr, axis_name="model"),
    mesh=mesh, in_specs=(P("model"), P(), P()), out_specs=P()))
ev = eval_sharded(w, h, labels)
np.testing.assert_allclose(np.asarray(ev), np.asarray(ref), rtol=2e-5,
                           atol=2e-5)
print("sharded full softmax == reference OK")

# Argmax agrees with dense argmax.
am_sharded = jax.jit(jax.shard_map(
    lambda wl, hr: dist.sharded_logits_argmax(wl, hr, axis_name="model"),
    mesh=mesh, in_specs=(P("model"), P()), out_specs=(P(), P())))
ids, best = am_sharded(w, h)
ref_ids = np.argmax(np.asarray(h @ w.T), axis=-1)
np.testing.assert_array_equal(np.asarray(ids), ref_ids)
print("sharded argmax OK")

# Per-example negatives: the fused-head branch (default impl="auto") must
# match the einsum branch exactly — loss AND (dL/dw, dL/dh) — with and
# without accidental-hit masking (DESIGN.md §4).
sampler_pe = BlockSampler(block_size=32, shared=False)


def loss_impl(w_local, h_rep, labels_rep, impl, mask):
    state_local = sampler_pe.init(jax.random.PRNGKey(7), w_local)
    return jnp.sum(dist.sharded_sampled_softmax_loss(
        w_local, h_rep, labels_rep, sampler_pe, state_local, m,
        jax.random.PRNGKey(42), axis_name="model", impl=impl,
        mask_accidental_hits=mask))


for mask in (True, False):
    vals = {}
    for impl in ("auto", "einsum"):
        f = jax.jit(jax.shard_map(
            lambda wl, hr, lr, impl=impl, mask=mask: loss_impl(
                wl, hr, lr, impl, mask),
            mesh=mesh, check_vma=False,
            in_specs=(P("model"), P(), P()), out_specs=P()))
        vals[impl] = (f(w, h, labels),
                      jax.jit(jax.grad(f, argnums=(0, 1)))(w, h, labels))
    np.testing.assert_allclose(np.asarray(vals["auto"][0]),
                               np.asarray(vals["einsum"][0]), rtol=2e-5)
    for g_a, g_e in zip(vals["auto"][1], vals["einsum"][1]):
        np.testing.assert_allclose(np.asarray(g_a), np.asarray(g_e),
                                   rtol=2e-5, atol=2e-5)
print("sharded fused head == einsum (loss + grads, masked/unmasked) OK")

# Estimator seam (DESIGN.md §6.2): the sharded nce / sampled-logistic
# losses must equal a host-side reconstruction over the union of every
# shard's stratified draws (global q~ = q_local / tp), including the
# hits-kept vs hits-masked distinction.
from repro.core.estimators import make_estimator  # noqa: E402


def est_loss(w_local, h_rep, labels_rep, est_name):
    state_local = sampler.init(jax.random.PRNGKey(7), w_local)
    return dist.sharded_estimator_loss(
        make_estimator(est_name), w_local, h_rep, labels_rep, sampler,
        state_local, m, jax.random.PRNGKey(42), axis_name="model")


n_local = n // 8
o_full = np.asarray(h @ w.T)
pos_full = o_full[np.arange(T), np.asarray(labels)]
for est_name in ("nce", "sampled-logistic"):
    f = jax.jit(jax.shard_map(
        lambda wl, hr, lr, e=est_name: est_loss(wl, hr, lr, e),
        mesh=mesh, check_vma=False,
        in_specs=(P("model"), P(), P()), out_specs=P()))
    got = np.asarray(f(w, h, labels))
    neg_terms = np.zeros(T)
    for s in range(8):  # replay each shard's draws on the host
        st_s = sampler.init(jax.random.PRNGKey(7),
                            w[s * n_local:(s + 1) * n_local])
        key_s = jax.random.fold_in(jax.random.PRNGKey(42), s)
        ids_s, logq_s = sampler.sample_batch(st_s, h, m // 8, key_s)
        gids = np.asarray(ids_s) + s * n_local          # (m/8,) shared
        lq = np.asarray(logq_s) - np.log(8.0)           # global q~
        o_adj = o_full[:, gids] - lq[None, :] - np.log(m)
        sp = np.logaddexp(0.0, o_adj)
        if est_name == "sampled-logistic":
            hit = gids[None, :] == np.asarray(labels)[:, None]
            sp = np.where(hit, 0.0, sp)
        neg_terms += sp.sum(-1)
    want = np.logaddexp(0.0, -pos_full) + neg_terms
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
print("sharded nce/sampled-logistic == host reconstruction OK")

# Statistical sanity: with MANY samples the sampled loss approaches full loss.
sampler_u = UniformSampler()
state_u = {"n": n // 8}  # static local-vocab state, same on every shard


def loss_u(w_local, h_rep, labels_rep, key):
    return dist.sharded_sampled_softmax_loss(
        w_local, h_rep, labels_rep, sampler_u, state_u, 8192, key,
        axis_name="model")


loss_u_sharded = jax.jit(jax.shard_map(
    loss_u, mesh=mesh, in_specs=(P("model"), P(), P(), P()),
    out_specs=P()))
losses = []
for i in range(20):
    losses.append(np.asarray(
        loss_u_sharded(w, h, labels, jax.random.PRNGKey(i)).mean()))
print("uniform m=8192 mean sampled loss:", np.mean(losses), "ref:",
      np.asarray(ref.mean()))
assert abs(np.mean(losses) - np.asarray(ref.mean())) < 0.05
print("ALL DISTRIBUTED CHECKS PASSED")
