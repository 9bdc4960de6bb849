"""Sampled softmax loss + correction (paper §2.2, eq. 2-5)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.sampled_softmax import (
    adjust_neg_logits,
    full_softmax_grad_wrt_logits,
    full_softmax_loss,
    sampled_softmax_from_embeddings,
    sampled_softmax_grad_wrt_logits,
    sampled_softmax_loss,
)
from repro.core.samplers import make_sampler, softmax_oracle


def test_adjusted_logits_eq2():
    o = jnp.array([1.0, -2.0, 0.5])
    logq = jnp.log(jnp.array([0.2, 0.5, 0.3]))
    got = adjust_neg_logits(o, logq, m=10)
    want = o - jnp.log(10 * jnp.array([0.2, 0.5, 0.3]))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)


def test_softmax_sampling_logits_identity_eq13():
    """For q = softmax, sum_k exp(o'_k) == sum_l exp(o_l) holds for EVERY
    sample (appendix eq. 13) — not just in expectation."""
    n, m = 50, 7
    o = jax.random.normal(jax.random.PRNGKey(0), (n,)) * 2
    logq = jax.nn.log_softmax(o)
    for seed in range(5):
        ids = jax.random.categorical(jax.random.PRNGKey(seed), logq,
                                     shape=(m,))
        adj = adjust_neg_logits(o[ids], logq[ids], m)
        np.testing.assert_allclose(float(jnp.exp(adj).sum()),
                                   float(jnp.exp(o).sum()), rtol=1e-4)


def test_loss_with_all_classes_equals_full_softmax():
    """Sampling every class exactly once with q uniform and m = n recovers
    the full softmax loss up to the constant correction."""
    n, d, t = 32, 8, 6
    w = jax.random.normal(jax.random.PRNGKey(1), (n, d))
    h = jax.random.normal(jax.random.PRNGKey(2), (t, d)) * 0.5
    labels = jnp.arange(t) % n
    # m -> infinity limit check instead: huge uniform sample approx.
    m = 20000
    ids = jax.random.randint(jax.random.PRNGKey(3), (m,), 0, n)
    logq = jnp.full((m,), -np.log(n))
    loss_s = sampled_softmax_from_embeddings(w, h, labels, ids, logq)
    loss_f = full_softmax_loss(w, h, labels)
    np.testing.assert_allclose(np.asarray(loss_s), np.asarray(loss_f),
                               rtol=0.05, atol=0.05)


def test_abs_softmax_mode():
    n, d, t = 16, 4, 5
    w = jax.random.normal(jax.random.PRNGKey(4), (n, d))
    h = jax.random.normal(jax.random.PRNGKey(5), (t, d))
    labels = jnp.arange(t)
    loss_abs = full_softmax_loss(w, h, labels, abs_mode=True)
    logits = jnp.abs(h @ w.T)
    ref = (jax.nn.logsumexp(logits, axis=-1)
           - jnp.take_along_axis(logits, labels[:, None], 1)[:, 0])
    np.testing.assert_allclose(np.asarray(loss_abs), np.asarray(ref),
                               rtol=1e-5)


# (family, m, atol): softmax is EXACTLY unbiased at any m (Theorem 2.1, so
# m = 4 with a Monte-Carlo-noise-sized tolerance); every other family is
# consistent — the eq. 2 correction drives the bias to 0 as m grows — so the
# kernel families and even uniform/unigram must land within a small band at
# m = 64.  Small-m bias of the non-softmax families is the paper's negative
# result, asserted separately below.
EQ5_FAMILIES = [
    ("softmax", 4, 0.03),
    ("uniform", 64, 0.15),
    ("unigram", 64, 0.18),
    ("quadratic-oracle", 64, 0.08),
    ("quartic-oracle", 64, 0.08),
    ("rff-oracle", 64, 0.08),
]


def _family_neg_logq(name, w, h, label):
    """The family's OWN oracle distribution over the negatives: all-class
    log q from actual embeddings, positive excluded (the theorem's q — a
    positive drawn as a negative would double-count in the partition
    estimate), renormalized."""
    n = w.shape[0]
    kwargs = {"rff-oracle": dict(dim=512)}.get(name, {})
    sampler = make_sampler(name, **kwargs)
    state = sampler.init(jax.random.PRNGKey(2), w)
    if name == "uniform":
        logq = jnp.full((n,), -np.log(n))
    elif name == "unigram":
        state = sampler.set_counts(state, 1000.0 / (1.0 + jnp.arange(n)))
        logq = state["logp"]
    else:
        logq = sampler.logq_all(state, h)
    logq = jnp.where(jnp.arange(n) == label, -jnp.inf, logq)
    return logq - jax.nn.logsumexp(logq)


@pytest.mark.parametrize("name,m,atol", EQ5_FAMILIES)
def test_gradient_estimator_eq5_families(name, m, atol):
    """Monte-Carlo check of Theorem 2.1 / consistency of eq. 5 across EVERY
    sampler family's oracle-q (softmax, uniform, unigram, quadratic, quartic,
    RFF) instead of a single hand-built q: E[eq. 5] ~ p - y (eq. 4)."""
    n, d, reps = 12, 6, 20000
    key = jax.random.PRNGKey(6)
    w = jax.random.normal(key, (n, d)) * 0.6
    h = jax.random.normal(jax.random.fold_in(key, 1), (d,))
    o = w @ h
    labels = jnp.asarray(3)
    logq = _family_neg_logq(name, w, h, labels)
    full = full_softmax_grad_wrt_logits(o[None], labels[None])[0]

    def one(key):
        ids = jax.random.categorical(key, logq, shape=(m,))
        return sampled_softmax_grad_wrt_logits(o, labels, ids, logq[ids],
                                               n=n)

    keys = jax.random.split(jax.random.PRNGKey(7), reps)
    est = jax.vmap(one)(keys).mean(0)
    np.testing.assert_allclose(np.asarray(est), np.asarray(full), atol=atol)


def test_partition_estimator_unbiased_any_q():
    """The eq. 2 correction makes sum_k exp(o'_k) an unbiased estimator of
    the partition over the negatives for ANY q with full support — checked
    on the most-mismatched family (uniform) where the GRADIENT is biased."""
    n, m, reps = 12, 4, 40000
    o = jax.random.normal(jax.random.PRNGKey(12), (n,)) * 1.5
    logq = jnp.full((n,), -np.log(n))

    def one(key):
        ids = jax.random.randint(key, (m,), 0, n)
        return jnp.exp(adjust_neg_logits(o[ids], logq[ids], m)).sum()

    keys = jax.random.split(jax.random.PRNGKey(13), reps)
    est = float(jax.vmap(one)(keys).mean())
    true = float(jnp.exp(o).sum())
    np.testing.assert_allclose(est, true, rtol=0.02)


def test_gradient_estimator_uniform_biased():
    """With q uniform and small m the estimator must be measurably biased
    (the paper's core negative result)."""
    n, m, reps = 12, 2, 6000
    o = jax.random.normal(jax.random.PRNGKey(8), (n,)) * 3
    labels = jnp.asarray(0)
    logq = jnp.full((n,), -np.log(n))
    full = full_softmax_grad_wrt_logits(o[None], labels[None])[0]

    def one(key):
        ids = jax.random.randint(key, (m,), 0, n)
        return sampled_softmax_grad_wrt_logits(o, labels, ids, logq[ids],
                                               n=n)

    keys = jax.random.split(jax.random.PRNGKey(9), reps)
    est = jax.vmap(one)(keys).mean(0)
    bias = float(jnp.max(jnp.abs(est - full)))
    assert bias > 0.05, f"uniform sampling should be biased, bias={bias}"


@pytest.mark.parametrize("impl", ["einsum", "chunked"])
def test_accidental_hit_masking_shrinks_eq5_bias(impl):
    """Rigged high-collision case: q puts half its mass on the label, so
    ~m/2 negatives collide with the positive.  Unmasked, the collided slots
    re-enter the eq. 3 partition with a bogus eq. 2 correction and the
    eq. 5 gradient estimator is visibly biased; masking them to zero mass
    (Rawat et al. 2019) must shrink the bias by a large factor.  Identity
    embeddings make dL/dh the eq. 5 estimate of dL/do directly."""
    n, m, reps = 12, 32, 4000
    # fixed logits: the rigged case must not move with JAX's PRNG defaults
    o = jnp.asarray([1.7852458, -1.6495332, 0.6655177, 0.8977045,
                     -0.58784336, 1.0389296, 0.6902753, -3.1028671,
                     -0.32157266, -1.484746, -1.0183957, 0.4104386])
    label = jnp.asarray(3)
    logq = jnp.log(jnp.where(jnp.arange(n) == label, 0.5, 0.5 / (n - 1)))
    w = jnp.eye(n)
    full = full_softmax_grad_wrt_logits(o[None], label[None])[0]

    def estimate(mask):
        def one(k):
            ids = jax.random.categorical(k, logq, shape=(1, m))
            f = lambda hh: jnp.sum(sampled_softmax_from_embeddings(
                w, hh, label[None], ids, logq[ids],
                mask_accidental_hits=mask, impl=impl))
            return jax.grad(f)(o[None])[0]
        keys = jax.random.split(jax.random.PRNGKey(1), reps)
        return jax.vmap(one)(keys).mean(0)

    bias_raw = float(jnp.max(jnp.abs(estimate(False) - full)))
    bias_masked = float(jnp.max(jnp.abs(estimate(True) - full)))
    # unmasked is badly biased; masked is within finite-m consistency noise
    assert bias_raw > 0.08, bias_raw
    assert bias_masked < 0.6 * bias_raw, (bias_masked, bias_raw)
    assert bias_masked < 0.06, bias_masked


def test_masked_loss_shared_matches_manual():
    """Shared negatives: collided slots drop out of the eq. 3 cross entropy
    exactly (masked == recomputing without the collided column)."""
    n, d, t = 16, 6, 5
    w = jax.random.normal(jax.random.PRNGKey(22), (n, d))
    h = jax.random.normal(jax.random.PRNGKey(23), (t, d))
    labels = jnp.full((t,), 2)
    ids = jnp.asarray([2, 5, 9, 11])  # first one collides for every row
    m = ids.shape[0]
    logq = jnp.full((m,), -np.log(n))
    got = sampled_softmax_from_embeddings(w, h, labels, ids, logq)
    o = h @ w.T
    pos = o[:, 2]
    neg = o[:, ids[1:]] - logq[1:] - np.log(m)  # collided column removed
    want = (jax.nn.logsumexp(jnp.concatenate([pos[:, None], neg], 1), -1)
            - pos)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5)


def test_shared_vs_per_example_shapes():
    n, d, t, m = 20, 6, 4, 8
    w = jax.random.normal(jax.random.PRNGKey(10), (n, d))
    h = jax.random.normal(jax.random.PRNGKey(11), (t, d))
    labels = jnp.zeros((t,), jnp.int32)
    ids_shared = jnp.arange(m)
    logq = jnp.full((m,), -np.log(n))
    l1 = sampled_softmax_from_embeddings(w, h, labels, ids_shared, logq)
    ids_per = jnp.tile(ids_shared[None], (t, 1))
    logq_per = jnp.tile(logq[None], (t, 1))
    l2 = sampled_softmax_from_embeddings(w, h, labels, ids_per, logq_per)
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l2), rtol=1e-5)
