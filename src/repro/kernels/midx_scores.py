"""Pallas kernel for the MIDX sampler's stage-1 hot loop (DESIGN.md §2.9).

Codeword-pair masses.  Every posting list j quantizes to the codeword pair
(a1_j, a2_j) of the c1 x c2 cross-product; its sampling mass is

    mass[t, j] = cnt_j * (alpha * <h_t, c1[a1_j] + c2[a2_j]>^2 + 1)

``midx_pair_masses`` consumes the PAIR-EXPANDED table ct[j] = c1[a1_j] +
c2[a2_j] (an O(P d) XLA gather in the ops.py wrapper — two int32 rows per
list is what travels in the carried state / serialized index; the
expansion is recomputed each call and never stored).  The kernel fuses the
(T, P) matvec, the kernel transform and the count multiply in one VMEM
pass: grid (T tiles x P tiles), one MXU contraction h @ ct^T per step, and
the (T, P) dot tensor never round-trips through HBM.

Stage 2 (exact within-list member scores) is the quadratic-kernel mode of
``leaf_scores``: the posting lists are a (P, L, d) leaf table and each draw
gathers its list by block index.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

Array = jax.Array


def _pair_masses_kernel(alpha, h_ref, ct_ref, cnt_ref, out_ref):
    h = h_ref[...].astype(jnp.float32)          # (Tt, d)
    ct = ct_ref[...].astype(jnp.float32)        # (Pt, d)
    cnt = cnt_ref[...].astype(jnp.float32)      # (1, Pt)
    dots = jax.lax.dot_general(
        h, ct, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST)     # (Tt, Pt)
    out_ref[...] = cnt * (alpha * dots * dots + 1.0)


@functools.partial(jax.jit,
                   static_argnames=("alpha", "t_tile", "p_tile", "interpret"))
def midx_pair_masses(h: Array, ct: Array, cnt: Array, *,
                     alpha: float = 100.0, t_tile: int = 128,
                     p_tile: int = 128, interpret: bool = False) -> Array:
    """h: (T, d); ct: (P, d) pair-expanded codewords; cnt: (P,)
    -> (T, P) fp32 stage-1 sampling masses.

    T must divide by t_tile and P by p_tile (ops.py pads; padded lists
    carry cnt 0 and therefore mass exactly 0).  ``cnt`` rides lane-dense
    as one (1, P) row."""
    t, d = h.shape
    p = ct.shape[0]
    assert t % t_tile == 0 and p % p_tile == 0, (t, p, t_tile, p_tile)
    kernel = functools.partial(_pair_masses_kernel, alpha)
    return pl.pallas_call(
        kernel,
        grid=(t // t_tile, p // p_tile),
        in_specs=[
            pl.BlockSpec((t_tile, d), lambda i, j: (i, 0)),
            pl.BlockSpec((p_tile, d), lambda i, j: (j, 0)),
            pl.BlockSpec((1, p_tile), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((t_tile, p_tile), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((t, p), jnp.float32),
        interpret=interpret,
    )(h, ct, cnt.reshape(1, p))
