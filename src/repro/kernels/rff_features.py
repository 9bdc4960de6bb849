"""Pallas kernel: fused positive-RFF features + per-leaf feature-sum reduction.

The statistics-refresh hot spot of the RFF sampler (DESIGN.md §2.7): build
the leaf level of the feature-sum hierarchy

    out[l, k] = sum_b mask[l, b] * phi_k(w[l, b])
    phi_k(x)  = D^{-1/2} exp( <omega_k, x>/sqrt(tau) - |x|^2/(2 tau)
                              - logshift )

in ONE pass — the (n, D) feature matrix never exists in HBM.  The class
rows ride flat as (L*B, d).  Grid is (leaves x D tiles x row tiles), the
row tiles of one leaf innermost: each step loads a (Bt, d) class tile and
a (Dt, d) direction tile into VMEM, runs one MXU contraction for the
direction projections, applies the log-domain shift + exp on the VPU, and
folds the masked row sum into the leaf's resident (1, Dt) output row with a
second MXU contraction against the lane-dense (1, Bt) mask row.

``mask`` is REQUIRED: zero padding rows still carry phi = exp(-logshift) > 0
(unlike the Gram build, where w w^T = 0 masks for free), so validity must be
explicit.  ``logshift`` is a traced scalar (shape (1, 1)) — the build-time
log-domain normalization (kernel_fns.rff_logshift_bound) that keeps every
exp in range; it scales all masses uniformly and cancels in sampling.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

Array = jax.Array

_HIGHEST = jax.lax.Precision.HIGHEST


def _rff_features_kernel(inv_sqrt_tau, inv_2tau, inv_sqrt_d, w_ref, om_ref,
                         mask_ref, shift_ref, out_ref):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    w = w_ref[...].astype(jnp.float32)          # (Bt, d)
    om = om_ref[...].astype(jnp.float32)        # (Dt, d)
    dots = jax.lax.dot_general(
        w, om, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32, precision=_HIGHEST)  # (Bt, Dt)
    nrm = jnp.sum(w * w, axis=-1, keepdims=True)                 # (Bt, 1)
    feats = jnp.exp(dots * inv_sqrt_tau - nrm * inv_2tau - shift_ref[...])
    out_ref[...] += inv_sqrt_d * jax.lax.dot_general(
        mask_ref[...].astype(jnp.float32), feats, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32, precision=_HIGHEST)  # (1, Dt)


@functools.partial(
    jax.jit, static_argnames=("tau", "d_total", "b_tile", "d_tile",
                              "interpret"))
def rff_features(w: Array, omega: Array, mask: Array, logshift: Array, *,
                 tau: float = 1.0, d_total: int | None = None,
                 b_tile: int | None = None, d_tile: int = 128,
                 interpret: bool = False) -> Array:
    """w: (L, B, d); omega: (D, d); mask: (L, B); logshift: (1, 1)
    -> (L, D) fp32 per-leaf feature sums.

    B must divide by b_tile and D by d_tile (ops.py pads); ``d_total`` is
    the TRUE feature dim for the D^{-1/2} normalization when D is padded."""
    n_leaves, b, d = w.shape
    n_feat = omega.shape[0]
    b_tile = b_tile or b
    assert b % b_tile == 0 and n_feat % d_tile == 0, (
        b, n_feat, b_tile, d_tile)
    nb = b // b_tile
    d_total = d_total or n_feat
    kernel = functools.partial(
        _rff_features_kernel, float(tau) ** -0.5, 0.5 / float(tau),
        float(d_total) ** -0.5)
    out = pl.pallas_call(
        kernel,
        grid=(n_leaves, n_feat // d_tile, nb),
        in_specs=[
            pl.BlockSpec((b_tile, d), lambda i, j, c: (i * nb + c, 0)),
            pl.BlockSpec((d_tile, d), lambda i, j, c: (j, 0)),
            pl.BlockSpec((None, 1, b_tile), lambda i, j, c: (i * nb + c, 0, 0)),
            pl.BlockSpec((1, 1), lambda i, j, c: (0, 0)),
        ],
        out_specs=pl.BlockSpec((None, 1, d_tile), lambda i, j, c: (i, 0, j)),
        out_shape=jax.ShapeDtypeStruct((n_leaves, 1, n_feat), jnp.float32),
        interpret=interpret,
    )(w.reshape(n_leaves * b, d), omega,
      mask.reshape(n_leaves * nb, 1, b_tile), logshift)
    return out.reshape(n_leaves, n_feat)
