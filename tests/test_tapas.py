"""TAPAS two-pass sampler edge cases and protocol conformance.

The statistical-exactness gates live in test_sampler_stats.py; this file
covers the corners where a composed two-stage q can silently go wrong:
duplicate pool draws (multiplicity weighting), resampling MORE slots than
the pool holds, single-query batches, accidental label hits flowing into
every estimator, and the construction/facade/validation seams
(DESIGN.md §2.8).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.api import SoftmaxHead
from repro.configs import get_config
from repro.core.estimators import (
    loss_from_embeddings,
    local_sampled_loss,
    make_estimator,
)
from repro.core.samplers import (
    TapasSampler,
    make_sampler,
    pool_log_inclusion,
    sampler_names,
)

EST_NAMES = ("sampled-softmax", "nce", "sampled-logistic")


def _mk(n=8, d=6, t=3, pool=64, base=None, tau=1.0, seed=0):
    k = jax.random.PRNGKey(seed)
    w = jax.random.normal(k, (n, d)) * 0.4
    h = jax.random.normal(jax.random.fold_in(k, 1), (t, d))
    sampler = TapasSampler(base=base or make_sampler("uniform"),
                           pool=pool, tau=tau)
    state = sampler.init(jax.random.fold_in(k, 2), w)
    labels = jax.random.randint(jax.random.fold_in(k, 3), (t,), 0, n)
    return sampler, state, w, h, labels


# --- sampling corners ---------------------------------------------------------

def test_duplicate_pool_draws_are_multiplicity_weighted():
    """pool >> vocab guarantees duplicates; the composed q must stay a
    probability (distinct-class mass <= 1) and logq finite."""
    sampler, state, w, h, _ = _mk(n=8, pool=64)
    key = jax.random.PRNGKey(5)
    pool_ids, logq1 = sampler.draw_pool(state, h, key)
    mult = np.bincount(np.asarray(pool_ids), minlength=8)
    assert mult.max() > 1, "pool=64 over n=8 must contain duplicates"
    ids, logq = sampler.resample_from_pool(state, pool_ids, logq1, h, 16,
                                           jax.random.fold_in(key, 1))
    assert np.isfinite(np.asarray(logq)).all()
    assert (np.asarray(logq) <= 1e-5).all(), "composed prob > 1"
    # with every class ~surely in the pool the composed q is ~the softmax
    # over re-scored logits: distinct-class mass ~ 1
    for t in range(h.shape[0]):
        o = np.asarray(h[t] @ w.T, np.float64) / sampler.tau
        logpi = np.asarray(pool_log_inclusion(logq1, sampler.pool),
                           np.float64)
        s = o[np.asarray(pool_ids)] - logpi - np.log(mult[np.asarray(
            pool_ids)])
        lz = np.log(np.exp(s - s.max()).sum()) + s.max()
        seen = {}
        for slot, cls in enumerate(np.asarray(pool_ids)):
            seen[int(cls)] = np.exp(o[cls] - lz)
        mass = sum(seen.values())
        assert 0.0 < mass <= 1.0 + 1e-6


def test_resample_wider_than_pool():
    """m >= pool is legal: resampling is with replacement from the pool."""
    sampler, state, w, h, labels = _mk(n=32, pool=16)
    ids, logq = sampler.sample_batch(state, h, 48, jax.random.PRNGKey(9))
    assert ids.shape == (3, 48) and logq.shape == (3, 48)
    assert np.isfinite(np.asarray(logq)).all()
    # at most `pool` distinct classes can appear per example
    for t in range(3):
        assert len(np.unique(np.asarray(ids[t]))) <= sampler.pool
    for est_name in EST_NAMES:
        loss = loss_from_embeddings(make_estimator(est_name), w, h, labels,
                                    ids, logq)
        assert np.isfinite(np.asarray(loss)).all(), est_name


def test_single_query_batch():
    sampler, state, w, h, _ = _mk(t=1)
    ids, logq = sampler.sample_batch(state, h, 8, jax.random.PRNGKey(3))
    assert ids.shape == (1, 8) and logq.shape == (1, 8)
    ids1, logq1 = sampler.sample(state, h[0], 8, jax.random.PRNGKey(3))
    np.testing.assert_array_equal(np.asarray(ids[0]), np.asarray(ids1))


def test_label_hits_masked_to_zero_mass():
    """Tiny vocab forces accidental hits; the eq. 2 estimator must stay
    finite and the masked loss must equal a manual recomputation with the
    collided slots dropped entirely."""
    sampler, state, w, h, labels = _mk(n=6, pool=32, t=4)
    m = 24
    ids, logq = sampler.sample_batch(state, h, m, jax.random.PRNGKey(21))
    hit = np.asarray(ids) == np.asarray(labels)[:, None]
    assert hit.any(), "n=6, m=24 must produce label hits"

    loss = np.asarray(loss_from_embeddings(
        make_estimator("sampled-softmax"), w, h, labels, ids, logq))
    assert np.isfinite(loss).all()
    o = np.asarray(jnp.einsum("td,nd->tn", h, w), np.float64)
    pos = o[np.arange(4), np.asarray(labels)]
    o_adj = (np.take_along_axis(o, np.asarray(ids), axis=1)
             - np.asarray(logq, np.float64) - np.log(m))
    o_adj[hit] = -np.inf                      # dropped, not just down-weighted
    want = np.log(np.exp(o_adj).sum(-1) + np.exp(pos)) - pos
    np.testing.assert_allclose(loss, want, rtol=2e-4, atol=2e-4)

    # logistic family: sampled-logistic zeroes hit slots, nce keeps them
    s_logistic = np.asarray(loss_from_embeddings(
        make_estimator("sampled-logistic"), w, h, labels, ids, logq))
    s_nce = np.asarray(loss_from_embeddings(
        make_estimator("nce"), w, h, labels, ids, logq))
    assert np.isfinite(s_logistic).all() and np.isfinite(s_nce).all()
    assert (s_nce - s_logistic).min() > -1e-6  # masking only removes mass
    assert (s_nce - s_logistic).max() > 1e-6   # ...and hits DID carry mass


@settings(max_examples=20, deadline=None)
@given(st.integers(4, 24), st.integers(1, 40), st.integers(1, 48),
       st.integers(1, 4))
def test_tapas_shapes_and_finiteness_property(n, pool, m, t):
    """Any (n, pool, m, T) combination — including pool=1 and m > pool —
    yields well-shaped draws, finite logq <= 0, and finite losses."""
    sampler, state, w, h, labels = _mk(n=n, pool=pool, t=t, seed=n + pool)
    ids, logq = sampler.sample_batch(state, h, m, jax.random.PRNGKey(m))
    assert ids.shape == (t, m) and logq.shape == (t, m)
    ids_np, logq_np = np.asarray(ids), np.asarray(logq)
    assert ((ids_np >= 0) & (ids_np < n)).all()
    assert np.isfinite(logq_np).all() and (logq_np <= 1e-5).all()
    loss = loss_from_embeddings(make_estimator("sampled-softmax"), w, h,
                                labels, ids, logq)
    assert np.isfinite(np.asarray(loss)).all()


# --- construction / protocol / facade ----------------------------------------

def test_registry_and_validation():
    assert "tapas" in sampler_names()
    with pytest.raises(ValueError, match="cannot nest"):
        TapasSampler(base=TapasSampler())
    with pytest.raises(ValueError, match="pool size"):
        TapasSampler(pool=0)
    with pytest.raises(ValueError, match="tau"):
        TapasSampler(tau=0.0)
    with pytest.raises(ValueError, match="tapas"):
        get_config("youtube-dnn").reduced(sampler="tapas",
                                          tapas_pool=-4).validate()


def test_carried_state_delegates_to_base():
    """carries_state / hydrate / island_runtime follow the base family."""
    uni = TapasSampler(base=make_sampler("uniform"), pool=8)
    assert not uni.carries_state
    blk = TapasSampler(base=make_sampler("block-quadratic-shared",
                                         block_size=4), pool=8)
    assert blk.carries_state
    with pytest.raises(TypeError, match="island_runtime"):
        uni.hydrate(None, None)
    w = jax.random.normal(jax.random.PRNGKey(0), (16, 4))
    n_valid = jnp.asarray(16, jnp.int32)
    rt = uni.island_runtime(None, w, n_valid)
    assert set(rt) == {"base", "w", "n_valid"}
    assert rt["w"] is w
    # refresh swaps the scoring table in the runtime dict
    state = blk.init(jax.random.PRNGKey(1), w)
    w2 = w + 1.0
    state2 = blk.refresh(state, w2)
    np.testing.assert_array_equal(np.asarray(state2["w"]), np.asarray(w2))


def _facade_cfg(**over):
    base = dict(vocab_size=128, m_negatives=16, sampler="tapas",
                tapas_pool=64, tapas_base="block-quadratic-shared",
                sampler_block=16, tower_dims=(64, 32), user_feature_dim=64,
                history_len=3)
    base.update(over)
    return get_config("youtube-dnn").reduced(**base)


def test_facade_sample_requires_table():
    head = SoftmaxHead(_facade_cfg())
    k = jax.random.PRNGKey(0)
    w = jax.random.normal(k, (128, 32)) * 0.3
    h = jax.random.normal(jax.random.fold_in(k, 1), (5, 32))
    state = head.init(jax.random.fold_in(k, 2), w)
    with pytest.raises(ValueError, match="pass w="):
        head.sample(state, h, jax.random.fold_in(k, 3))
    ids, logq = head.sample(state, h, jax.random.fold_in(k, 3), w=w)
    assert ids.shape == (5, 16) and logq.shape == (5, 16)
    assert np.isfinite(np.asarray(logq)).all()


def test_facade_loss_and_grads():
    cfg = _facade_cfg()
    head = SoftmaxHead(cfg)
    k = jax.random.PRNGKey(7)
    w = jax.random.normal(k, (128, 32)) * 0.3
    h = jax.random.normal(jax.random.fold_in(k, 1), (5, 32))
    labels = jax.random.randint(jax.random.fold_in(k, 2), (5,), 0, 128)
    state = head.init(jax.random.fold_in(k, 3), w)
    loss = head.loss(w, h, labels, state=state, key=jax.random.fold_in(k, 4))
    assert loss.shape == (5,) and np.isfinite(np.asarray(loss)).all()
    gw, gh = jax.grad(
        lambda ww, hh: jnp.sum(head.loss(ww, hh, labels, state=state,
                                         key=jax.random.fold_in(k, 4))),
        argnums=(0, 1))(w, h)
    assert np.isfinite(np.asarray(gw)).all() and float(
        jnp.linalg.norm(gw)) > 0
    assert np.isfinite(np.asarray(gh)).all() and float(
        jnp.linalg.norm(gh)) > 0
    # the facade loss IS the mesh=None island path
    direct = local_sampled_loss(
        head.estimator, head.sampler, w, h, labels, state, cfg.m_negatives,
        jax.random.fold_in(k, 4),
        n_valid=jnp.asarray(cfg.vocab_size, jnp.int32),
        abs_mode=cfg.abs_softmax, impl=cfg.head_impl)
    np.testing.assert_allclose(np.asarray(loss), np.asarray(direct),
                               rtol=1e-5, atol=1e-5)


def test_local_train_steps():
    """mesh=None train smoke: tapas through the full train step fits one
    fixed batch.  Fresh random tokens and labels every step carry nothing
    to learn, so the loss is followed on a repeated batch: over 8 steps it
    must fall clearly below the first step's (measured drop ~0.9 nats for
    this seed; the margin leaves room for the sampled loss's noise)."""
    from repro.optim import make_optimizer
    from repro.sharding.rules import local_ctx
    from repro.train.step import init_train_state, make_train_step

    cfg = get_config("llama3-8b").reduced(
        m_negatives=16, sampler="tapas", tapas_pool=64, sampler_block=16)
    ctx = local_ctx()
    opt = make_optimizer("adamw", 1e-3)
    state = init_train_state(jax.random.PRNGKey(0), cfg, ctx, opt,
                             max_len=16)
    step = jax.jit(make_train_step(cfg, ctx, opt))
    batch = {
        "tokens": jax.random.randint(jax.random.PRNGKey(0), (2, 16), 0,
                                     cfg.vocab_size),
        "labels": jax.random.randint(jax.random.PRNGKey(100), (2, 16), 0,
                                     cfg.vocab_size),
    }
    losses = []
    for i in range(8):
        state, metrics = step(state, batch, jax.random.PRNGKey(200 + i))
        losses.append(float(metrics["loss"]))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0] - 0.3, losses
