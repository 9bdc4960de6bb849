"""Pallas kernel: per-draw within-leaf scores — the leaf level of the
level-synchronous sampling descent (DESIGN.md §2.6), the MIDX within-list
step (DESIGN.md §2.9) and the serving-side beam retrieval (DESIGN.md §5).

Two modes over the same body (one VMEM schedule, one contraction):

    kernel mode:  scores[t, j, b] = alpha * (table[idx[t, j], b, :] . h[t, :])^2 + 1
                  — the paper's quadratic kernel K (§3.3), used by the
                  within-leaf categorical of the samplers.
    dot mode:     scores[t, j, b] = table[idx[t, j], b, :] . h[t, :]
                  — the raw logit <h, w>, used by ``serve/retrieval.py`` to
                  score surviving leaves exactly for top-k MIPS decode.

for a leaf table (L, B, r), queries h: (T, r) and per-query leaf ids
idx: (T, m).  The gather IS the block fetch: ``idx`` is scalar-prefetched
and the table's block index map picks leaf ``idx[t, j]`` (leading dim
squeezed), so the (T, m, B, r) gathered tensor never exists in HBM.  Grid
is (T, B tiles, m): each step loads one (Bt, r) leaf tile and dots it
against its query on the VPU (each draw owns a distinct leaf, so there is
nothing for the MXU to batch over).  The (m, Bt) output block stays
resident across the innermost draw axis and each step writes its own row.
Padding rows inside a leaf are zero, so they score exactly alpha*0+1
(kernel mode) or 0 (dot mode); the callers mask them with their ``n_valid``
grids — this kernel and its ops.py wrappers return raw scores.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array


def _leaf_scores_kernel(alpha, square, idx_ref, h_ref, rows_ref, out_ref):
    j = pl.program_id(2)
    h = h_ref[...].astype(jnp.float32)              # (1, r)
    rows = rows_ref[...].astype(jnp.float32)        # (Bt, r)
    dots = jnp.sum(rows[None] * h[:, None, :], axis=-1)   # (1, Bt)
    out_ref[pl.ds(j, 1), :] = alpha * dots * dots + 1.0 if square else dots


@functools.partial(
    jax.jit, static_argnames=("alpha", "square", "b_tile", "interpret"))
def leaf_scores(h: Array, table: Array, idx: Array, *, alpha: float = 100.0,
                square: bool = True, b_tile: int | None = None,
                interpret: bool = False) -> Array:
    """h: (T, r); table: (L, B, r); idx: (T, m) leaf ids -> (T, m, B) fp32.

    ``square=True`` gives quadratic-kernel scores alpha*dot^2+1;
    ``square=False`` gives raw dots (alpha is ignored).  B must divide by
    ``b_tile`` (ops.py picks it from a VMEM budget)."""
    t, r = h.shape
    _, b, _ = table.shape
    m = idx.shape[1]
    b_tile = b_tile or b
    assert b % b_tile == 0, (b, b_tile)
    kernel = functools.partial(_leaf_scores_kernel, alpha, square)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(t, b // b_tile, m),
        in_specs=[
            pl.BlockSpec((None, 1, r), lambda i, c, j, idx_ref: (i, 0, 0)),
            pl.BlockSpec((None, b_tile, r),
                         lambda i, c, j, idx_ref: (idx_ref[i * m + j], c, 0)),
        ],
        out_specs=pl.BlockSpec((None, m, b_tile),
                               lambda i, c, j, idx_ref: (i, 0, c)),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((t, m, b), jnp.float32),
        interpret=interpret,
    )(idx.reshape(-1).astype(jnp.int32), h.reshape(t, 1, r), table)
