"""Pallas kernel: batched quadratic forms — the sampler's root level.

scores[t, n] = alpha * h_t^T Z_n h_t + cnt_n

for queries h: (T, r) against block statistics Z: (N, r, r).  Grid is
(T tiles x N tiles); each step loads a (Tt, r) query tile and an
(Nt, r, r) statistics tile into VMEM and produces the (Tt, Nt) score tile
with one MXU contraction and one sublane reduction:

    u[n*, i, t] = Z[n, i, j] . h[t, j]      (reshaped (Nt*r, r) @ (r, Tt))
    s[t, n]     = sum_i u[n, i, t] * h[t, i]

Arithmetic intensity is ~Tt flops/byte on the Z tile, so Tt >= 128 makes the
root step compute-bound rather than HBM-bound.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

Array = jax.Array


def _block_scores_kernel(alpha, h_ref, z_ref, cnt_ref, out_ref):
    h = h_ref[...].astype(jnp.float32)          # (Tt, r)
    z = z_ref[...].astype(jnp.float32)          # (Nt, r, r)
    cnt = cnt_ref[...].astype(jnp.float32)      # (1, Nt)
    nt, r, _ = z.shape
    u = jax.lax.dot_general(
        z.reshape(nt * r, r), h, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST)     # (Nt*r, Tt)
    u = u.reshape(nt, r, h.shape[0])
    s = jnp.sum(u * h.T[None], axis=1)           # (Nt, Tt)
    out_ref[...] = alpha * s.T + cnt


@functools.partial(jax.jit,
                   static_argnames=("alpha", "t_tile", "n_tile", "interpret"))
def block_scores(h: Array, z: Array, cnt: Array, *, alpha: float = 100.0,
                 t_tile: int = 128, n_tile: int = 8,
                 interpret: bool = False) -> Array:
    """h: (T, r); z: (N, r, r); cnt: (N,) -> (T, N) fp32 kernel masses.

    T must divide by t_tile and N by n_tile (ops.py pads).  ``cnt`` rides
    lane-dense as one (1, N) row."""
    t, r = h.shape
    n = z.shape[0]
    assert t % t_tile == 0 and n % n_tile == 0, (t, n, t_tile, n_tile)
    kernel = functools.partial(_block_scores_kernel, alpha)
    return pl.pallas_call(
        kernel,
        grid=(t // t_tile, n // n_tile),
        in_specs=[
            pl.BlockSpec((t_tile, r), lambda i, j: (i, 0)),
            pl.BlockSpec((n_tile, r, r), lambda i, j: (j, 0, 0)),
            pl.BlockSpec((1, n_tile), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((t_tile, n_tile), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((t, n), jnp.float32),
        interpret=interpret,
    )(h, z, cnt.reshape(1, n))
