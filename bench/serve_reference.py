"""The plain reference of the dense top-k decode, and the numbers that
decide ``correct`` for a serving cell.

Nothing here imports the program under test or takes anything it made: the
table comes from the benchmark's own seeded weights (``weights.py``), the
hidden states are the requests the window submitted.

For each sampled request the reference scores every row of the table in
float32 at ``precision=HIGHEST``, o = h . w_i, in blocks of rows, applies
the configuration's output function (|o| under ``abs_softmax``, paper eq.
11, else o) and keeps the top k.  Beside each score s_i it keeps the bound
e_i = gamma(d) * sum_j |h_j| |w_ij| on the error of a float32 matmul at the
TPU's default precision, one bfloat16 pass (the precision the
configuration states): each operand rounded to bfloat16 (relative error at
most 2^-8) and the d products summed in float32.

The numbers:
  logit_gap   the worst |returned logit - the reference's s for the same
              id| / |s|, over the sampled requests and their k answers
  topk_miss   the share of the reference's DUE ids that were not returned.
              An id of the reference's top k is due when its score less
              its bound exceeds every other id's score plus that id's
              bound: no program that computes at the stated precision
              could rank it out.  The ids left out are the ties within
              rounding of the k-th score, a few in a hundred
  unsorted    the requests whose returned logits do not descend
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import F8_MAX, HIGHEST

#: the error bound of one bfloat16 pass over d products, relative to
#: sum_j |h_j w_ij|: two operands rounded to 8 significant bits each,
#: (1 + 2^-8)^2 - 1, and the float32 sum, d * 2^-24 (``gamma``)
BF16_PRODUCT = 2.0 ** -7 + 2.0 ** -16
#: rows of the table scored at once, and requests at once
TABLE_BLOCK = 65536
QUERY_BLOCK = 1024


def gamma(d: int) -> float:
    return BF16_PRODUCT + d * 2.0 ** -24


def _blocks(w, block: int):
    """(nb, block, d) table blocks, the last padded, and the row count."""
    n, d = w.shape
    nb = -(-n // block)
    pad = nb * block - n
    return jnp.pad(w, ((0, pad), (0, 0))).reshape(nb, block, d), n


@functools.partial(jax.jit, static_argnames=("k", "abs_mode", "block"))
def _topk_with_bounds(h, w, *, k: int, abs_mode: bool, block: int):
    """Top k of s over the table, with each one's bound, and the largest
    s + e over the rest: (s (Q, k), ids (Q, k), e (Q, k), other (Q,))."""
    blocks, n = _blocks(w.astype(jnp.float32), block)
    h = h.astype(jnp.float32)
    g = gamma(h.shape[-1])
    q = h.shape[0]
    neg = jnp.full((q, k), -jnp.inf, jnp.float32)
    zero_ids = jnp.zeros((q, k), jnp.int32)

    def step(carry, xs):
        top_s, top_i, top_e, up_u, up_i = carry
        wb, b = xs
        o = jnp.dot(h, wb.T, precision=HIGHEST)
        e = g * jnp.dot(jnp.abs(h), jnp.abs(wb).T, precision=HIGHEST)
        s = jnp.abs(o) if abs_mode else o
        ids = b * block + jnp.arange(block, dtype=jnp.int32)
        s = jnp.where(ids < n, s, -jnp.inf)
        ids_q = jnp.broadcast_to(ids, s.shape)
        cat_s = jnp.concatenate([top_s, s], axis=1)
        cat_i = jnp.concatenate([top_i, ids_q], axis=1)
        cat_e = jnp.concatenate([top_e, e], axis=1)
        top_s, sel = jax.lax.top_k(cat_s, k)
        top_i = jnp.take_along_axis(cat_i, sel, axis=1)
        top_e = jnp.take_along_axis(cat_e, sel, axis=1)
        # the k + 1 largest s + e: at least one of them lies outside the
        # final top k of s, and the first such is the largest outside it
        cat_u = jnp.concatenate([up_u, s + e], axis=1)
        cat_ui = jnp.concatenate([up_i, ids_q], axis=1)
        up_u, sel = jax.lax.top_k(cat_u, k + 1)
        up_i = jnp.take_along_axis(cat_ui, sel, axis=1)
        return (top_s, top_i, top_e, up_u, up_i), None

    init = (neg, zero_ids, jnp.zeros((q, k), jnp.float32),
            jnp.full((q, k + 1), -jnp.inf, jnp.float32),
            jnp.full((q, k + 1), -1, jnp.int32))
    nb = blocks.shape[0]
    (top_s, top_i, top_e, up_u, up_i), _ = jax.lax.scan(
        step, init, (blocks, jnp.arange(nb, dtype=jnp.int32)))
    inside = (up_i[:, :, None] == top_i[:, None, :]).any(axis=2)
    other = jnp.max(jnp.where(inside, -jnp.inf, up_u), axis=1)
    return top_s, top_i, top_e, other


@functools.partial(jax.jit, static_argnames=("abs_mode",))
def _scores_of(h, w, ids, *, abs_mode: bool):
    """s of the given ids (Q, k) at the reference's precision."""
    rows = w[ids].astype(jnp.float32)
    o = jnp.einsum("qd,qkd->qk", h.astype(jnp.float32), rows,
                   precision=HIGHEST)
    return jnp.abs(o) if abs_mode else o


def _chunks(q: int, size: int = QUERY_BLOCK):
    return [slice(i, min(i + size, q)) for i in range(0, q, size)]


def reference_topk(h, w, k: int, abs_mode: bool,
                   block: int = TABLE_BLOCK) -> dict:
    """The reference's answers for hidden states ``h`` (Q, d) against the
    table ``w`` (n, d), on the host: ``ids`` and ``s`` (Q, k) descending,
    and ``due`` (Q, k), whether each is due (module docstring)."""
    h = np.asarray(h, np.float32)
    parts = [jax.device_get(_topk_with_bounds(
        jnp.asarray(h[c]), w, k=k, abs_mode=abs_mode,
        block=min(block, w.shape[0]))) for c in _chunks(len(h))]
    s, ids, e, other = (np.concatenate([p[i] for p in parts])
                        for i in range(4))
    return {"ids": ids, "s": s, "due": (s - e) > other[:, None]}


def scores_of(h, w, ids, abs_mode: bool) -> np.ndarray:
    """The reference's s for the ids (Q, k) of each request."""
    h, ids = np.asarray(h, np.float32), np.asarray(ids, np.int32)
    return np.concatenate([np.asarray(_scores_of(
        jnp.asarray(h[c]), w, jnp.asarray(ids[c]), abs_mode=abs_mode))
        for c in _chunks(len(h))])


@jax.jit
def _to_fp8(w):
    """The table in float8 e4m3 under a per-tensor scale (its largest
    magnitude maps to the type's largest finite value), and the scale.
    The float8 array is a program's output, so it is rounded for certain:
    a float32 -> float8 -> float32 round trip inside one program may be
    dropped by the compiler as excess precision."""
    dtype = jnp.float8_e4m3fn
    scale = F8_MAX[dtype] / jnp.maximum(jnp.max(jnp.abs(w)), 1e-30)
    return (w.astype(jnp.float32) * scale).astype(dtype), scale


def control_answers(h, w, k: int, abs_mode: bool, mode: str,
                    block: int = TABLE_BLOCK) -> tuple:
    """(ids, logits) of the reference put in the program's place, broken
    as ``mode`` says: ``control``, the table rounded to float8 e4m3 under
    a per-tensor scale (the precision below the stated one);
    ``half_catalog``, top k over the first half of the rows; ``raw_o``,
    ranked by o where the configuration ranks by |o|; ``ascending``, the
    right answers in ascending order."""
    if mode == "control":
        w8, scale = _to_fp8(w)
        w = w8.astype(jnp.float32) / scale
    elif mode == "half_catalog":
        w = w[: w.shape[0] // 2]
    elif mode == "raw_o":
        abs_mode = False
    elif mode != "ascending":
        raise ValueError(f"unknown control mode {mode!r}")
    ref = reference_topk(h, w, k, abs_mode, block)
    if mode == "ascending":
        return ref["ids"][:, ::-1], ref["s"][:, ::-1]
    return ref["ids"], ref["s"]


def numbers(ids, logits, ref: dict, ref_s_of_ids) -> dict:
    """The numbers compared, from the returned ``ids`` and ``logits`` (Q,
    k), the reference's answers ``ref`` and its s of the returned ids."""
    ids, logits = np.asarray(ids), np.asarray(logits, np.float64)
    want = np.asarray(ref_s_of_ids, np.float64)
    gap = np.abs(logits - want) / np.maximum(np.abs(want), 1e-30)
    returned = (ref["ids"][:, :, None] == ids[:, None, :]).any(axis=2)
    due = ref["due"]
    missed = np.sum(due & ~returned)
    return {"logit_gap": float(np.max(gap)) if gap.size else 0.0,
            "topk_miss": float(missed / max(int(np.sum(due)), 1)),
            "unsorted": float(np.sum(np.any(np.diff(logits, axis=1) > 0,
                                            axis=1)))}
