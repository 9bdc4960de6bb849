"""Per-kernel shape/dtype sweeps vs the pure-jnp oracles (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.kernels import ops, ref

DTYPES = [jnp.float32, jnp.bfloat16]


def _tol(dt):
    return dict(rtol=2e-2, atol=2e-2) if dt == jnp.bfloat16 else \
        dict(rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("nb,b,r", [(4, 32, 16), (7, 64, 8), (1, 128, 32)])
def test_zstats(nb, b, r, dtype):
    w = (jax.random.normal(jax.random.PRNGKey(nb), (nb, b, r)) * 0.5
         ).astype(dtype)
    got = ops.zstats(w)
    want = ref.zstats_ref(w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **_tol(dtype))


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("t,n,r", [(16, 8, 16), (100, 13, 8), (128, 4, 32),
                                   (1, 1, 8)])
def test_block_scores(t, n, r, dtype):
    h = (jax.random.normal(jax.random.PRNGKey(t), (t, r)) * 0.5).astype(dtype)
    z = ref.zstats_ref(jax.random.normal(jax.random.PRNGKey(n), (n, 32, r)))
    cnt = jnp.arange(n, dtype=jnp.float32) + 1
    got = ops.block_scores(h, z, cnt, alpha=100.0)
    want = ref.block_scores_ref(h, z, cnt, 100.0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-2 if dtype == jnp.bfloat16 else 3e-4)


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("g,b,r", [(16, 8, 16), (100, 4, 8), (128, 32, 32),
                                   (1, 16, 8)])
def test_leaf_scores(g, b, r, dtype):
    """g queries, each drawing 3 of 5 leaves of a (5, b, r) table."""
    h = (jax.random.normal(jax.random.PRNGKey(g), (g, r)) * 0.5).astype(dtype)
    table = (jax.random.normal(jax.random.PRNGKey(b), (5, b, r)) * 0.5
             ).astype(dtype)
    idx = jax.random.randint(jax.random.PRNGKey(r), (g, 3), 0, 5)
    got = ops.leaf_scores(h, table, idx, alpha=100.0)
    want = ref.leaf_scores_ref(h, table, idx, 100.0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-2 if dtype == jnp.bfloat16 else 3e-4)


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("t,d,m", [(32, 16, 64), (37, 48, 70), (128, 8, 8),
                                   (5, 32, 200)])
def test_sampled_loss(t, d, m, dtype):
    h = (jax.random.normal(jax.random.PRNGKey(t), (t, d)) * 0.3).astype(dtype)
    wn = (jax.random.normal(jax.random.PRNGKey(d), (m, d)) * 0.3
          ).astype(dtype)
    logq = jax.nn.log_softmax(jax.random.normal(jax.random.PRNGKey(m), (m,)))
    pos = jax.random.normal(jax.random.PRNGKey(7), (t,))
    got = ops.sampled_loss(h, wn, logq, pos, m_total=m)
    want = ref.sampled_loss_ref(h, wn, logq, pos, m)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               **_tol(dtype))


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("l,b,d,feat", [(4, 16, 12, 96), (5, 8, 8, 100),
                                        (1, 32, 16, 128), (9, 4, 24, 40)])
def test_rff_features(l, b, d, feat, dtype):
    """Fused phi(w) + per-leaf reduction vs the jnp oracle, with a ragged
    validity mask and a nonzero log-domain shift."""
    w = (jax.random.normal(jax.random.PRNGKey(l), (l, b, d)) * 0.4
         ).astype(dtype)
    omega = jax.random.normal(jax.random.PRNGKey(feat), (feat, d))
    mask = (jax.random.uniform(jax.random.PRNGKey(b), (l, b)) > 0.25
            ).astype(jnp.float32)
    shift = jnp.asarray(0.9)
    got = ops.rff_features(w, omega, mask, shift, tau=1.5)
    want = ref.rff_features_ref(w, omega, mask, shift, 1.5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=4e-2 if dtype == jnp.bfloat16 else 3e-4,
                               atol=1e-4)


# --- property-based shape/dtype coverage (hypothesis when installed, fixed
# bounds + midpoints through the shim otherwise) ------------------------------


@settings(max_examples=8, deadline=None)
@given(st.integers(1, 197), st.integers(1, 300), st.integers(4, 48),
       st.booleans())
def test_sampled_loss_property(t, m, d, bf16):
    """Uneven T/m tile edges (prime-ish sizes), m far from the 128 block,
    single-row batches, and bf16 inputs all reduce to the oracle."""
    dtype = jnp.bfloat16 if bf16 else jnp.float32
    h = (jax.random.normal(jax.random.PRNGKey(t), (t, d)) * 0.3).astype(dtype)
    wn = (jax.random.normal(jax.random.PRNGKey(m + 1), (m, d)) * 0.3
          ).astype(dtype)
    logq = jax.nn.log_softmax(
        jax.random.normal(jax.random.PRNGKey(d + 2), (m,)))
    pos = jax.random.normal(jax.random.PRNGKey(7), (t,))
    got = ops.sampled_loss(h, wn, logq, pos, m_total=m)
    assert got.shape == (t,) and got.dtype == jnp.float32
    want = ref.sampled_loss_ref(h, wn, logq, pos, m)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               **_tol(dtype))


@settings(max_examples=8, deadline=None)
@given(st.integers(1, 197), st.integers(1, 63), st.integers(4, 48),
       st.booleans())
def test_leaf_scores_property(g, b, r, bf16):
    """Both modes of the leaf kernel (quadratic scores and raw dots) across
    ragged query counts, odd leaf widths, single rows, and bf16."""
    dtype = jnp.bfloat16 if bf16 else jnp.float32
    h = (jax.random.normal(jax.random.PRNGKey(g), (g, r)) * 0.5).astype(dtype)
    table = (jax.random.normal(jax.random.PRNGKey(b + 1), (7, b, r)) * 0.5
             ).astype(dtype)
    idx = jax.random.randint(jax.random.PRNGKey(g + b), (g, 2), 0, 7)
    got = ops.leaf_scores(h, table, idx, alpha=100.0)
    assert got.shape == (g, 2, b) and got.dtype == jnp.float32
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref.leaf_scores_ref(h, table, idx, 100.0)),
        rtol=4e-2 if bf16 else 3e-4, atol=2e-2)
    dots = ops.leaf_dots(h, table, idx)
    np.testing.assert_allclose(np.asarray(dots),
                               np.asarray(ref.leaf_dots_ref(h, table, idx)),
                               rtol=4e-2 if bf16 else 3e-4, atol=2e-2)


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("b,s,h,kv,hd", [(1, 64, 2, 2, 16), (2, 100, 4, 2, 16),
                                         (1, 33, 2, 1, 32)])
def test_flash_attention(b, s, h, kv, hd, causal, dtype):
    q = (jax.random.normal(jax.random.PRNGKey(0), (b, s, h, hd)) * 0.5
         ).astype(dtype)
    k = (jax.random.normal(jax.random.PRNGKey(1), (b, s, kv, hd)) * 0.5
         ).astype(dtype)
    v = jax.random.normal(jax.random.PRNGKey(2), (b, s, kv, hd)).astype(dtype)
    got = ops.flash_attention(q, k, v, causal=causal, q_tile=32, kv_tile=32)
    kf = jnp.repeat(k, h // kv, axis=2)
    vf = jnp.repeat(v, h // kv, axis=2)
    want = ref.flash_attention_ref(q.astype(jnp.float32),
                                   kf.astype(jnp.float32),
                                   vf.astype(jnp.float32), causal=causal)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want), **_tol(dtype))


def test_flash_matches_model_chunked_attention():
    """The Pallas kernel and the model's pure-jnp chunked attention agree —
    the kernel can drop in for the backbone hot spot."""
    from repro.models.layers import chunked_attention
    q = jax.random.normal(jax.random.PRNGKey(3), (2, 64, 4, 16))
    k = jax.random.normal(jax.random.PRNGKey(4), (2, 64, 2, 16))
    v = jax.random.normal(jax.random.PRNGKey(5), (2, 64, 2, 16))
    a = ops.flash_attention(q, k, v, causal=True, q_tile=32, kv_tile=32)
    b = chunked_attention(q, k, v, causal=True, chunk=16)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4,
                               atol=2e-4)
