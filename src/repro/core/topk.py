"""Exact top-k over wide rows in two stages (DESIGN.md §5).

``lax.top_k`` over a row of n values sorts the whole row; at a serving
catalog (n = 1M, k = 100) that sort, not the h . W^T that made the row,
is nearly all of a dense decode's device time.  An exact top-k does not
need it.  Cut each row into n // G whole groups of G contiguous columns,
and a tail of the n % G columns left over:

  1. ``decode.topk_groups``: the maximum of every whole group, and the top
     k of those maxima; the chosen groups put back in ascending order;
  2. ``decode.topk_final``: the k G columns of the chosen groups gathered
     from the row (nothing is scored again), then the tail, and the top k
     of those k G + n % G candidates.

Why it is exact, ties included.  Order elements by value descending, then
id ascending (``lax.top_k``'s order), and groups by their maximum the same
way, then group index ascending.  Take x among the top k of the whole
groups' columns, in group g.  If g were not among the k first groups,
each of those k groups c would hold an element that comes before x: its
maximum either exceeds x, or equals a value >= x in a group c < g, whose
ids all lie below x's.  That is k elements before x, so x would not be in
that top k.  Hence the chosen groups hold the top k of the whole groups,
and with the tail, the top k of the row.  The candidates lie in ascending
id order (chosen groups, then the tail), so stage 2's ``lax.top_k`` gives
the same values, and on ties the same (lowest) ids.

Memory traffic: the logits are read once for the maxima, and k G + n % G
of each row's values once more.  Each (row, group) is viewed as one row
of G columns in the order a TPU tile holds them (8 rows of one group,
then the next group), a bitcast of row-major logits whose width is whole
groups.  ``logits_topk`` makes the rows so: one dot writes the whole
groups' logits, a second the tail's, and nothing is padded or copied at
full width.  ``exact_topk`` takes rows already made, and slices off the
tail where G does not divide n.

G is a power of two near sqrt(n / k), at least 128 (the TPU's lane
width).  The split runs where n >= 8 k G and k > 1; elsewhere
``lax.top_k`` runs as it is: below 8 k G the two stages save little, and
at k = 1 the top-k is a max reduction with no sort to save (the greedy
decode's argmax).  n >= 8 k G also gives at least 8 k - 1 whole groups,
so there are always k groups to choose.  At n = 1M, k = 100: G = 128,
and each row's sorts see 7,812 maxima and 12,864 candidates.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

Array = jax.Array

#: the smallest group: one TPU lane row
MIN_GROUP = 128
#: the split runs where n >= SPLIT_FACTOR * k * G
SPLIT_FACTOR = 8
#: the rows of a float32 TPU tile
SUBLANES = 8


def group_size(n: int, k: int) -> int | None:
    """G for top-k over rows of n, or None where ``lax.top_k`` runs as it
    is (k = 1, or n < 8 k G)."""
    g = max(MIN_GROUP, 1 << round(math.log2(max(n / k, 1.0)) / 2))
    return g if k > 1 and n >= SPLIT_FACTOR * k * g else None


def exact_topk(x: Array, k: int) -> tuple[Array, Array]:
    """``lax.top_k(x, k)`` over the last axis, bit for bit: (values,
    int32 indices), values descending, ties to the lowest index.

    Where G does not divide n, the whole groups are a slice of ``x``; on a
    TPU that slice is a copy unless n is a multiple of 128, so a caller
    that makes its rows by a matmul runs ``logits_topk`` instead."""
    n = x.shape[-1]
    g = group_size(n, k)
    if g is None:
        return lax.top_k(x, k)
    whole = n // g * g
    vals, ids = _two_stage(x.reshape(-1, n)[:, :whole],
                           x.reshape(-1, n)[:, whole:], k, g)
    lead = x.shape[:-1]
    return vals.reshape(*lead, k), ids.reshape(*lead, k)


def logits_topk(h: Array, w: Array, k: int, *, bias: Array | None = None,
                n_valid: int | None = None) -> tuple[Array, Array]:
    """The top k of the float32 logits h . w^T (plus ``bias``, and -inf at
    columns from ``n_valid`` on): ``exact_topk`` of them, at the default
    precision.

    h: (T, d); w: (n, d) -> (values, int32 ids), each (T, k).  Where the
    split runs, the whole groups' logits and the tail's come from two dots
    over row ranges of ``w``: the first writes a row of whole groups, which
    the stages view in place, and neither ``w`` nor the logits is copied.
    Each logit is the same product at the same precision as in one dot of
    the whole table, but a backend may sum it in another order for another
    shape (the CPU does), so its last bit may differ from that dot's."""
    n = w.shape[0]
    g = group_size(n, k)
    whole = n if g is None else n // g * g

    def logits(lo, hi):
        x = h.astype(jnp.float32) @ w[lo:hi].astype(jnp.float32).T
        if bias is not None:
            x = x + bias[None, lo:hi]
        if n_valid is not None and n_valid < hi:
            x = jnp.where(jnp.arange(lo, hi) < n_valid, x, -jnp.inf)
        return x

    if g is None:
        return lax.top_k(logits(0, n), k)
    return _two_stage(logits(0, whole), logits(whole, n), k, g)


def _two_stage(body: Array, tail: Array, k: int,
               g: int) -> tuple[Array, Array]:
    """Top k over the rows of ``[body | tail]``: body (R, n_groups G) in
    whole groups, tail (R, < G) their candidates as they are."""
    rows, whole = body.shape
    n_groups = whole // g
    # (row, group) pairs in a TPU tile's order: `sub` rows of one group,
    # then the next group; a permutation of rows, so a bitcast and no copy
    sub = SUBLANES if rows % SUBLANES == 0 else 1
    grouped = (body.reshape(rows // sub, sub, n_groups, g)
               .transpose(0, 2, 1, 3).reshape(-1, g))
    with jax.named_scope("decode.topk_groups"):
        gmax = (jnp.max(grouped, axis=-1)
                .reshape(rows // sub, n_groups, sub)
                .transpose(0, 2, 1).reshape(rows, n_groups))
        _, sel = lax.top_k(gmax, k)
        sel = jnp.sort(sel, axis=-1)
    with jax.named_scope("decode.topk_final"):
        r = jnp.arange(rows, dtype=sel.dtype)[:, None]
        flat = (((r // sub) * n_groups + sel) * sub + r % sub).reshape(-1)
        cand = jnp.concatenate(
            [jnp.take(grouped, flat, axis=0).reshape(rows, k * g), tail],
            axis=-1)
        vals, pos = lax.top_k(cand, k)
        grp = jnp.take_along_axis(sel, jnp.minimum(pos // g, k - 1), axis=-1)
        ids = jnp.where(pos < k * g, grp * g + pos % g,
                        whole + pos - k * g)
    return vals, ids
