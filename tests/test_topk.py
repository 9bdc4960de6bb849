"""Exact two-stage top-k (core/topk.py, DESIGN.md §5): ``exact_topk`` and
``logits_topk`` give ``lax.top_k``'s values and ids bit for bit, and the
dense decode that runs them equals a plain ``lax.top_k`` over the same
logits, the served table left as it was given.  The sharded per-shard
variant is in tests/dist_scripts/check_decode_topk.py."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from repro.configs import get_config
from repro.core import topk
from repro.serve import engine, retrieval
from repro.sharding.rules import local_ctx


def _rows(shape, mode, seed):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal(shape).astype(np.float32)
    if mode == "quarters":          # heavy ties
        x = np.round(x * 4) / 4
    elif mode == "neginf_tail":     # as rows past n_valid are masked
        x[..., 90:] = -np.inf
    elif mode == "constant":        # every value tied
        x[:] = 0.5
    elif mode == "tail_max":        # the largest values past the last group
        x[..., -30:] = 9.0 + np.round(x[..., -30:])
    return jnp.asarray(x)


CASES = {
    "normal": ((4, 200_000), 100, "normal"),
    "quarters": ((4, 200_000), 100, "quarters"),
    "quarters_8_rows": ((8, 200_000), 100, "quarters"),
    "neginf_tail": ((4, 120_000), 100, "neginf_tail"),
    "n_not_multiple": ((3, 200_037), 100, "quarters"),
    "n_not_multiple_8_rows": ((16, 150_001), 100, "normal"),
    "n_not_multiple_tail_max": ((8, 200_037), 100, "tail_max"),
    "k_1": ((3, 5_000), 1, "quarters"),
    # n = 8 k G at G = 128: the fewest groups the split takes, 8 k
    "k_near_groups": ((8, 131_072), 128, "quarters"),
    "constant": ((2, 40_000), 5, "constant"),
    "leading_dims": ((2, 3, 110_000), 100, "quarters"),
    "plain_below_threshold": ((4, 100_000), 100, "quarters"),
}
#: the cases that ``lax.top_k`` runs as it is: below 8 k G, and k = 1
PLAIN = {"plain_below_threshold", "k_1"}


@pytest.mark.parametrize("name", sorted(CASES))
def test_exact_topk_matches_lax_top_k(name):
    shape, k, mode = CASES[name]
    x = _rows(shape, mode, seed=len(name))
    n = shape[-1]
    g = topk.group_size(n, k)
    assert (g is None) == (name in PLAIN)
    want_v, want_i = lax.top_k(x, k)
    got_v, got_i = jax.jit(topk.exact_topk, static_argnums=1)(x, k)
    assert got_i.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(got_v), np.asarray(want_v))
    np.testing.assert_array_equal(np.asarray(got_i), np.asarray(want_i))


def test_group_size_follows_the_shape():
    """G is a power of two near sqrt(n / k), at least 128; the split runs
    only where n >= 8 k G, which leaves at least 8 k - 1 whole groups, and
    never at k = 1, a max reduction with no sort to save."""
    assert topk.group_size(1_000_000, 100) == 128
    assert topk.group_size(1_000_000, 1) is None
    assert topk.group_size(128_256, 1) is None
    assert topk.group_size(100_000_000, 100) == 1024
    assert topk.group_size(8 * 100 * 128, 100) == 128
    assert topk.group_size(8 * 100 * 128 - 1, 100) is None
    for n, k in [(10**6, 100), (10**7, 10), (3 * 10**5, 2)]:
        g = topk.group_size(n, k)
        assert g & (g - 1) == 0 and n // g >= 8 * k - 1


LOGITS_CASES = {
    # (n, k, n_valid, with bias)
    "whole_groups": (204_800, 100, None, False),
    "tail": (200_037, 100, None, False),
    "tail_masked": (200_037, 100, 200_010, False),
    "mask_in_body": (200_037, 100, 150_000, True),
    "bias": (150_001, 50, None, True),
    "k_1": (200_037, 1, 199_000, True),
}


@pytest.mark.parametrize("name", sorted(LOGITS_CASES))
def test_logits_topk_matches_lax_top_k(name):
    """The two dots (whole groups, tail) and their stages give the top k of
    the one dot h . w^T, masked and biased as asked."""
    n, k, n_valid, with_bias = LOGITS_CASES[name]
    kw, kh, kb = jax.random.split(jax.random.PRNGKey(len(name)), 3)
    w = jnp.round(jax.random.normal(kw, (n, 16)) * 2) / 4
    h = jnp.round(jax.random.normal(kh, (8, 16)) * 2) / 4
    # the largest logits at the last rows, past any whole group
    w = w.at[-20:].multiply(8.0)
    bias = (jnp.round(jax.random.normal(kb, (n,)) * 4) / 4
            if with_bias else None)

    def reference(w, h, bias):
        x = h @ w.T
        if bias is not None:
            x = x + bias[None, :]
        if n_valid is not None:
            x = jnp.where(jnp.arange(n) < n_valid, x, -jnp.inf)
        return lax.top_k(x, k)

    got_v, got_i = jax.jit(lambda w, h, b: topk.logits_topk(
        h, w, k, bias=b, n_valid=n_valid))(w, h, bias)
    want_v, want_i = jax.jit(reference)(w, h, bias)
    np.testing.assert_array_equal(np.asarray(got_v), np.asarray(want_v))
    np.testing.assert_array_equal(np.asarray(got_i), np.asarray(want_i))


def _hlo(k, n, rows=8):
    x = jax.ShapeDtypeStruct((rows, n), jnp.float32)
    return jax.jit(topk.exact_topk, static_argnums=1).lower(
        x, k).compile().as_text()


def test_named_scopes_mark_the_split():
    text = _hlo(100, 200_000)
    assert "decode.topk_groups/" in text and "decode.topk_final/" in text
    for plain in (_hlo(100, 100_000), _hlo(1, 1_000_000)):
        assert "decode.topk_groups" not in plain
        assert "decode.topk_final" not in plain


@pytest.mark.parametrize("n_valid", [None, 199_950])
def test_dense_topk_matches_plain_reference(n_valid):
    n, d, k = 200_000, 16, 100
    kw, kh = jax.random.split(jax.random.PRNGKey(3))
    # quarter-valued operands: exact dot products, and ties among them
    w = jnp.round(jax.random.normal(kw, (n, d)) * 2) / 4
    h = jnp.round(jax.random.normal(kh, (4, d)) * 2) / 4

    @jax.jit
    def reference(w, h):
        logits = h @ w.T
        if n_valid is not None:
            logits = jnp.where(jnp.arange(n) < n_valid, logits, -jnp.inf)
        vals, ids = lax.top_k(logits, k)
        return ids, vals

    assert topk.group_size(n, k) is not None
    ids, vals = jax.jit(lambda w, h: retrieval.dense_topk(
        w, h, k, n_valid=n_valid))(w, h)
    want_ids, want_vals = reference(w, h)
    np.testing.assert_array_equal(np.asarray(vals), np.asarray(want_vals))
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(want_ids))
    if n_valid is not None:
        assert int(jnp.max(ids)) < n_valid


def test_decode_fn_serves_an_uneven_head_as_given():
    """``make_decode_fn`` keeps a 200,037-row head as it is given (no copy
    of the table); the served answers are the plain top-k over its rows."""
    n, d, k = 200_037, 16, 100
    cfg = get_config("llama3-8b").reduced(vocab_size=n, d_model=d)
    w = jnp.round(jax.random.normal(jax.random.PRNGKey(5), (n, d)) * 2) / 4
    h = jnp.round(jax.random.normal(jax.random.PRNGKey(6), (8, d)) * 2) / 4
    fn = engine.make_decode_fn(cfg, local_ctx(), w, k)
    assert fn.args[0] is w
    ids, vals = jax.jit(lambda fn, h: fn(None, h))(fn, h)
    want_vals, want_ids = lax.top_k(h @ w.T, k)
    np.testing.assert_array_equal(np.asarray(vals), np.asarray(want_vals))
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(want_ids))
