"""Estimator registry (core/estimators.py): the shared eq.-2-corrected
contract, each estimator's dense oracle, and the gradients through it.

"Dense oracle" here means an independent closed-form reference computed
from the FULL logit matrix and the same draws — the estimator must match
it in value AND in gradient (w.r.t. both the embedding table and the
hidden states), which pins the whole loss_from_embeddings dispatch
(gathers, corrections, hit masks, fused-head seam) to first principles.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import estimators
from repro.core.sampled_softmax import full_softmax_loss

NAMES = ["sampled-softmax", "nce", "sampled-logistic", "full"]


def _toy(t=6, n=24, d=8, m=10, collide=False):
    # numpy's generator: the toy does not move with JAX's PRNG defaults
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.standard_normal((n, d)) * 0.5, jnp.float32)
    h = jnp.asarray(rng.standard_normal((t, d)), jnp.float32)
    labels = jnp.asarray(rng.integers(0, n, (t,)), jnp.int32)
    ids = jnp.asarray(rng.integers(0, n, (t, m)), jnp.int32)
    if collide:  # force an accidental hit in slot 0 of every row
        ids = ids.at[:, 0].set(labels)
    logq = jnp.full((t, m), -np.log(n))
    return w, h, labels, ids, logq


def test_registry_contract():
    assert estimators.estimator_names() == sorted(NAMES)
    for name in NAMES:
        est = estimators.make_estimator(name)
        assert est.name == name
        assert est.needs_sampling == (name != "full")
    with pytest.raises(KeyError, match="unknown estimator 'nope'"):
        estimators.make_estimator("nope")


def _dense_reference(name, w, h, labels, ids, logq):
    """Closed-form dense oracle per estimator (independent formulas); hit
    handling is always derived from ids, per each estimator's policy."""
    o = h.astype(jnp.float32) @ w.astype(jnp.float32).T  # (t, n)
    pos = jnp.take_along_axis(o, labels[:, None], 1)[:, 0]
    m = ids.shape[1]
    o_neg = jnp.take_along_axis(o, ids, 1) - logq - np.log(m)
    hit = ids == labels[:, None]
    if name == "full":
        return jax.nn.logsumexp(o, axis=-1) - pos
    if name == "sampled-softmax":
        o_neg = jnp.where(hit, -jnp.inf, o_neg)
        return (jax.nn.logsumexp(
            jnp.concatenate([pos[:, None], o_neg], 1), -1) - pos)
    per_slot = jax.nn.softplus(o_neg)
    if name == "sampled-logistic":
        per_slot = jnp.where(hit, 0.0, per_slot)
    return jax.nn.softplus(-pos) + per_slot.sum(-1)


@pytest.mark.parametrize("name", NAMES)
@pytest.mark.parametrize("collide", [False, True])
def test_value_and_grad_vs_dense_oracle(name, collide):
    w, h, labels, ids, logq = _toy(collide=collide)
    est = estimators.make_estimator(name)

    def ours(w_, h_):
        return jnp.sum(estimators.loss_from_embeddings(
            est, w_, h_, labels, ids, logq, impl="einsum"))

    def ref(w_, h_):
        return jnp.sum(_dense_reference(name, w_, h_, labels, ids, logq))

    np.testing.assert_allclose(float(ours(w, h)), float(ref(w, h)),
                               rtol=1e-5)
    gw, gh = jax.grad(ours, argnums=(0, 1))(w, h)
    gw_r, gh_r = jax.grad(ref, argnums=(0, 1))(w, h)
    np.testing.assert_allclose(np.asarray(gw), np.asarray(gw_r), rtol=1e-4,
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(gh), np.asarray(gh_r), rtol=1e-4,
                               atol=1e-6)


def test_nce_keeps_hits_logistic_masks_them():
    """The taxonomy distinction: with a forced collision in slot 0 (plus
    whatever chance collisions the draws produce), nce and sampled-logistic
    must differ by EXACTLY the collided slots' softplus terms."""
    w, h, labels, ids, logq = _toy(collide=True)
    nce = estimators.loss_from_embeddings(
        estimators.make_estimator("nce"), w, h, labels, ids, logq)
    logi = estimators.loss_from_embeddings(
        estimators.make_estimator("sampled-logistic"), w, h, labels, ids,
        logq)
    o = h.astype(jnp.float32) @ w.astype(jnp.float32).T
    o_neg = jnp.take_along_axis(o, ids, 1) - logq - np.log(ids.shape[1])
    hit = ids == labels[:, None]
    hit_terms = jnp.where(hit, jax.nn.softplus(o_neg), 0.0).sum(-1)
    np.testing.assert_allclose(np.asarray(nce - logi),
                               np.asarray(hit_terms), rtol=1e-5)
    # and the masked slot contributes zero gradient for sampled-logistic
    g = jax.grad(lambda hh: jnp.sum(estimators.loss_from_embeddings(
        estimators.make_estimator("sampled-logistic"), w, hh, labels,
        ids.at[:, 1:].set(0), logq)))(h)
    assert np.isfinite(np.asarray(g)).all()


def test_full_estimator_equals_reference_loss():
    w, h, labels, _, _ = _toy()
    est = estimators.make_estimator("full")
    got = estimators.loss_from_embeddings(est, w, h, labels, None, None)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(full_softmax_loss(w, h, labels)),
                               rtol=1e-6)
    with pytest.raises(TypeError, match="dense"):
        est.loss(jnp.zeros(3), jnp.zeros((3, 4)), jnp.zeros((3, 4)), None)


def test_shared_negatives_broadcast():
    """A shared (m,) negative set runs through every sampled estimator."""
    w, h, labels, ids, logq = _toy()
    for name in ("sampled-softmax", "nce", "sampled-logistic"):
        est = estimators.make_estimator(name)
        got = estimators.loss_from_embeddings(
            est, w, h, labels, ids[0], logq[0], impl="einsum")
        per = estimators.loss_from_embeddings(
            est, w, h, labels, jnp.tile(ids[0][None], (h.shape[0], 1)),
            jnp.tile(logq[0][None], (h.shape[0], 1)), impl="einsum")
        np.testing.assert_allclose(np.asarray(got), np.asarray(per),
                                   rtol=1e-5, err_msg=name)


def test_fused_seam_preserved_for_sampled_softmax():
    """The default estimator still routes per-example negatives through the
    fused head: impl='chunked' (the off-TPU fused path) must agree with the
    einsum oracle in value and gradient through loss_from_embeddings."""
    w, h, labels, ids, logq = _toy(collide=True)
    est = estimators.make_estimator("sampled-softmax")

    def f(impl):
        def loss(w_, h_):
            return jnp.sum(estimators.loss_from_embeddings(
                est, w_, h_, labels, ids, logq, impl=impl))
        (v, (gw, gh)) = (loss(w, h), jax.grad(loss, (0, 1))(w, h))
        return v, gw, gh

    v_e, gw_e, gh_e = f("einsum")
    v_c, gw_c, gh_c = f("chunked")
    np.testing.assert_allclose(float(v_c), float(v_e), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(gw_c), np.asarray(gw_e),
                               rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(np.asarray(gh_c), np.asarray(gh_e),
                               rtol=1e-4, atol=1e-6)


@pytest.mark.parametrize("name", ["nce", "sampled-logistic", "full"])
def test_estimators_train_end_to_end(name):
    """Every registry estimator learns through the real train step
    (mesh=None recsys smoke config)."""
    from repro.configs import get_config
    from repro.data.pipeline import batch_iterator_for
    from repro.optim import make_optimizer
    from repro.sharding.rules import local_ctx
    from repro.train.step import init_train_state, make_train_step

    cfg = get_config("youtube-dnn").reduced(
        vocab_size=128, m_negatives=32, sampler="block-quadratic",
        sampler_block=16, estimator=name, tower_dims=(64, 32),
        user_feature_dim=64, history_len=3)
    ctx = local_ctx()
    opt = make_optimizer("adamw", 1e-2, weight_decay=0.0)
    data = batch_iterator_for(cfg, ctx, global_batch=64, seq_len=0, seed=0)
    state = init_train_state(jax.random.PRNGKey(0), cfg, ctx, opt, max_len=8)
    step = jax.jit(make_train_step(cfg, ctx, opt))
    losses = []
    for i in range(40):
        state, metrics = step(state, next(data),
                              jax.random.fold_in(jax.random.PRNGKey(9), i))
        losses.append(float(metrics["loss"]))
    assert np.isfinite(losses).all(), name
    assert np.mean(losses[-5:]) < np.mean(losses[:5]), (
        name, np.mean(losses[:5]), np.mean(losses[-5:]))
