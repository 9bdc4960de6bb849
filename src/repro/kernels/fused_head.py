"""Pallas kernels: fused sampled-softmax head (gather + eq. 2 + LSE + VJP).

The training-loss hot path of the paper is

    loss_t = logsumexp_k(adj[t, k]) - t_pos[t],
    adj[t, k] = transform(<h_t, w_{ids[t,k]}> + bias_{ids[t,k]}) - corr[t, k]

over K = 1 + m gathered head rows per token (column 0 = positive with
corr 0, columns 1..m = sampled negatives with the eq. 2 correction
``ln(m q)`` folded into ``corr`` — accidental hits and padding carry
``corr ~ 1e30`` so they contribute exactly zero mass).  The naive einsum
path gathers a (T, m, d) negative tensor into HBM before contracting it;
these kernels never materialize it:

  * forward (``fused_lse``): grid (T, K).  Step (t, k) block-fetches ONE
    head row w[ids[t, k]] via a scalar-prefetch index map — the gather is
    the block fetch itself — dots it against h_t on the VPU, applies the
    bias / abs-mode transform / correction, and folds the result into a
    per-token online (max, sumexp) pair living in VMEM scratch (the flash-
    attention trick, applied over the class axis).  The final k-step writes
    the per-token logsumexp.  HBM traffic: K rows of d floats per token,
    once, and nothing written back but (T,) scalars.

  * backward (``fused_lse_bwd``): same grid, flash-style recompute.  Each
    step re-fetches its row, rebuilds adj, forms the softmax weight
    p = exp(adj - lse) * gbar (lse saved from the forward — the only
    residual besides the primals), and
      - accumulates dL/dh_t in the resident (1, d) output block,
      - scatter-adds p * h_t into dL/dw inside a VMEM-resident (n, d)
        accumulator block (written back to HBM once, at the end),
      - emits the per-(t, k) coefficient so the caller can route exact
        cotangents into ``corr`` (-p) and the bias gather (+p) with plain
        jnp scatters of (T, K) scalars — no d-sized tensors involved.

Layout: rows travel as (1, d) tiles of the (n, 1, d) / (T, 1, d) views
(leading dim squeezed), and the per-token (K,) corrections, biases and
slot cotangents as lane-dense (1, K) rows; step k picks its slot with a
lane mask, so no (1, 1) blocks and no dynamic lane indexing exist.

Constraints (documented, checked by the wrapper in ops.py): the backward
dL/dw accumulator holds the full (n, d) table shard in VMEM, so the Pallas
backward is only dispatched when n * d * 4 bytes fits the budget; larger
shards fall back to the chunked path in ops.py.  Grid iteration must be
sequential (the default on TPU) — the online LSE and both accumulators
carry state across steps.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array

NEG_INF = -1e30
#: corr value that forces a column's mass to exactly zero (masked / padded).
MASK_CORR = 1e30


def _slot(row, k):
    """Element k of a lane-dense (1, K) row as a (1, 1) value."""
    lane = jax.lax.broadcasted_iota(jnp.int32, row.shape, 1)
    return jnp.sum(jnp.where(lane == k, row, 0.0), axis=-1, keepdims=True)


def _adjusted(abs_mode, k, w_ref, h_ref, corr_ref, bias_ref):
    """(raw logit o, corrected logit adj) of slot k, both (1, 1)."""
    w_row = w_ref[...].astype(jnp.float32)           # (1, d)
    h_row = h_ref[...].astype(jnp.float32)           # (1, d)
    o = (jnp.sum(w_row * h_row, axis=-1, keepdims=True)
         + _slot(bias_ref[...], k))
    tl = jnp.abs(o) if abs_mode else o
    return o, tl - _slot(corr_ref[...], k)


def _fwd_kernel(abs_mode, ids_ref, w_ref, h_ref, corr_ref, bias_ref,
                lse_ref, m_scr, s_scr):
    k = pl.program_id(1)
    nk = pl.num_programs(1)

    @pl.when(k == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr[...], NEG_INF)
        s_scr[...] = jnp.zeros_like(s_scr[...])

    _, adj = _adjusted(abs_mode, k, w_ref, h_ref, corr_ref, bias_ref)
    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, adj)
    s_scr[...] = s_scr[...] * jnp.exp(m_prev - m_new) + jnp.exp(adj - m_new)
    m_scr[...] = m_new

    @pl.when(k == nk - 1)
    def _finalize():
        lse_ref[...] = jnp.log(s_scr[...]) + m_scr[...]


def _row_specs(k, d):
    """Block specs shared by both passes: the gathered head row, the
    token's hidden row, and its lane-dense corr / bias rows."""
    return [
        pl.BlockSpec((None, 1, d),
                     lambda i, j, ids_ref: (ids_ref[i * k + j], 0, 0)),
        pl.BlockSpec((None, 1, d), lambda i, j, ids_ref: (i, 0, 0)),
        pl.BlockSpec((None, 1, k), lambda i, j, ids_ref: (i, 0, 0)),
        pl.BlockSpec((None, 1, k), lambda i, j, ids_ref: (i, 0, 0)),
    ]


def _operands(w, h, ids, corr, biasg):
    t, k = ids.shape
    return (ids.reshape(-1), w[:, None, :], h[:, None, :],
            corr.reshape(t, 1, k), biasg.reshape(t, 1, k))


@functools.partial(jax.jit, static_argnames=("abs_mode", "interpret"))
def fused_lse(w: Array, h: Array, ids: Array, corr: Array, biasg: Array, *,
              abs_mode: bool = False, interpret: bool = False) -> Array:
    """w: (n, d); h: (T, d); ids/corr/biasg: (T, K) -> per-token fp32
    logsumexp (T,) of the corrected gathered logits (module docstring)."""
    t, k = ids.shape
    kernel = functools.partial(_fwd_kernel, abs_mode)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(t, k),
        in_specs=_row_specs(k, h.shape[1]),
        out_specs=pl.BlockSpec((None, 1, 1), lambda i, j, ids_ref: (i, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
        ],
    )
    lse = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((t, 1, 1), jnp.float32),
        interpret=interpret,
    )(*_operands(w, h, ids, corr, biasg))
    return lse.reshape(t)


def _bwd_kernel(abs_mode, ids_ref, w_ref, h_ref, corr_ref, bias_ref,
                lse_ref, gbar_ref, dw_ref, dh_ref, dcoef_ref, dcorr_ref):
    i = pl.program_id(0)
    k = pl.program_id(1)
    nk = pl.num_programs(1)

    @pl.when(jnp.logical_and(i == 0, k == 0))
    def _init_dw():
        dw_ref[...] = jnp.zeros_like(dw_ref[...])

    @pl.when(k == 0)
    def _init_token():
        dh_ref[...] = jnp.zeros_like(dh_ref[...])
        dcoef_ref[...] = jnp.zeros_like(dcoef_ref[...])
        dcorr_ref[...] = jnp.zeros_like(dcorr_ref[...])

    o, adj = _adjusted(abs_mode, k, w_ref, h_ref, corr_ref, bias_ref)
    p = jnp.exp(adj - lse_ref[...]) * gbar_ref[...]  # (1, 1) softmax weight
    lane = jax.lax.broadcasted_iota(jnp.int32, dcorr_ref.shape, 1)
    # corr enters AFTER the |.| transform: its cotangent is the unsigned
    # weight; w / h / bias sit before it and take the sign chain.
    dcorr_ref[...] = jnp.where(lane == k, -p, dcorr_ref[...])
    if abs_mode:
        p = p * jnp.sign(o)                          # |.| chain rule
    dcoef_ref[...] = jnp.where(lane == k, p, dcoef_ref[...])
    dh_ref[...] += p * w_ref[...].astype(jnp.float32)          # (1, d)
    idx = ids_ref[i * nk + k]
    dw_ref[pl.ds(idx, 1), :] += p * h_ref[...].astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("abs_mode", "interpret"))
def fused_lse_bwd(w: Array, h: Array, ids: Array, corr: Array, biasg: Array,
                  lse: Array, gbar: Array, *, abs_mode: bool = False,
                  interpret: bool = False
                  ) -> tuple[Array, Array, Array, Array]:
    """VJP of ``fused_lse`` wrt (w, h, biasg, corr).

    lse: (T,) forward output; gbar: (T,) upstream cotangent.  Returns
    (dw (n, d), dh (T, d), dcoef (T, K), dcorr (T, K)) all fp32 — dcoef is
    the sign-chained per-slot softmax weight (the biasg cotangent verbatim);
    dcorr is minus the unsigned weight (the corr cotangent — corr applies
    after the abs transform, so it skips the sign chain)."""
    n, d = w.shape
    t, k = ids.shape
    kernel = functools.partial(_bwd_kernel, abs_mode)
    token = pl.BlockSpec((None, 1, 1), lambda i, j, ids_ref: (i, 0, 0))
    slots = pl.BlockSpec((None, 1, k), lambda i, j, ids_ref: (i, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(t, k),
        in_specs=_row_specs(k, d) + [token, token],
        out_specs=(
            pl.BlockSpec((n, d), lambda i, j, ids_ref: (0, 0)),
            pl.BlockSpec((None, 1, d), lambda i, j, ids_ref: (i, 0, 0)),
            slots,
            slots,
        ),
    )
    dw, dh, dcoef, dcorr = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=(
            jax.ShapeDtypeStruct((n, d), jnp.float32),
            jax.ShapeDtypeStruct((t, 1, d), jnp.float32),
            jax.ShapeDtypeStruct((t, 1, k), jnp.float32),
            jax.ShapeDtypeStruct((t, 1, k), jnp.float32),
        ),
        # The resident (n, d) accumulator is double-buffered like any
        # output block; leave headroom for the row tiles beside it.
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=2 * n * d * 4 + 8 * 1024 * 1024),
        interpret=interpret,
    )(*_operands(w, h, ids, corr, biasg), lse.reshape(t, 1, 1),
      gbar.reshape(t, 1, 1))
    return dw, dh.reshape(t, d), dcoef.reshape(t, k), dcorr.reshape(t, k)
