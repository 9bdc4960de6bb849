"""Quantized serving-side retrieval index (DESIGN.md §2.9 + §5).

The SAME two-level MIDX structure the ``"midx"`` sampler carries in
TrainState (``core/midx.py``) exported as a standalone serving index: the
class table lives as P balanced posting lists, each quantized to a
codeword PAIR over the c1 x c2 codebook cross-product, plus the packed
member rows for exact re-scoring.  ``decode_topk`` is a two-stage beam
search:

  stage 1   rank every posting list by the QUANTIZED MIPS surrogate
            t_j = <h, c1[a1_j] + c2[a2_j]> (two (K, d) matvecs + an O(P)
            gather — note: the RAW dot, not the sampling kernel; decode
            wants the max logit, not kernel mass) and keep the top
            ``beam`` lists.
  stage 2   exactly re-score the survivors' members with dequantized
            rows and take the flat top-k.

``bits=8`` stores the member rows int8 with per-row absmax scales — the
payload the ``IndexRefresher`` ships every swap shrinks ~4x vs the fp32
``RetrievalIndex`` (the member table dominates both; measured in
``BENCH_sampler_cost.json`` payload rows) at <1% logit error on unit-scale
embeddings.  ``bits=32`` keeps fp32 rows (exact twin of the beam search).

Same mesh contract as ``serve/retrieval.py``: all arrays P('model')-
sharded over their leading axis, per-shard beam + ONE (T, tp*k)
all-gather merge, ``perm`` mapping packed positions back to original ids.
A plain pytree — ``CheckpointManager.save``/``restore`` and the serving
engine's double-buffered ``swap_index`` handle it as-is, and
``engine.decode_topk`` dispatches on its treedef so the same jitted
decode function serves either index family.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.core import midx
from repro.sharding.rules import gather_head_fd, head_fd_axes

Array = jax.Array


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class QuantizedRetrievalIndex:
    """Packed quantized serving index — the midx carried state, standalone.

    c1:      (tp * K1, d) fp32 coarse codebook (per shard).
    c2:      (tp * K2, d) fp32 residual codebook.
    codes:   (tp * P, 2) int32 codeword pair per posting list.
    cnt:     (tp * P,) fp32 valid rows per list.
    perm:    (tp * P * L,) int32 packed position -> original local row id.
    rows:    (tp * P, L, d) member rows — int8 when bits == 8, fp32 when
             bits == 32.
    scale:   (tp * P, L) fp32 per-row dequantization scales (ones for the
             fp32 variant): row_fp32 ~= rows * scale[..., None].
    n:       static — true global class count.
    tp:      static — vocab-parallel degree (1 when built without a mesh).
    v_shard: static — embedding rows per shard (global id = shard *
             v_shard + original local id).
    bits:    static — 8 or 32; the row-payload width.
    """

    c1: Array
    c2: Array
    codes: Array
    cnt: Array
    perm: Array
    rows: Array
    scale: Array
    n: int = dataclasses.field(metadata=dict(static=True))
    tp: int = dataclasses.field(metadata=dict(static=True))
    v_shard: int = dataclasses.field(metadata=dict(static=True))
    bits: int = dataclasses.field(metadata=dict(static=True))

    @property
    def num_lists_shard(self) -> int:
        return self.rows.shape[0] // self.tp

    @property
    def list_size(self) -> int:
        return self.rows.shape[1]


def payload_bytes(index) -> int:
    """Serialized size of an index pytree: the bytes the train->serve seam
    ships per swap (and the engine's ``index_payload_bytes`` counter).
    Works for ANY index — QuantizedRetrievalIndex or the fp32
    ``RetrievalIndex`` — since both are flat array pytrees."""
    return int(sum(leaf.size * leaf.dtype.itemsize
                   for leaf in jax.tree_util.tree_leaves(index)))


def _quantize_rows(rows: Array, bits: int) -> tuple[Array, Array]:
    """(P, L, d) fp32 -> (rows', (P, L) scales).  int8: symmetric per-row
    absmax; fp32: identity with unit scales (one code path downstream)."""
    if bits == 32:
        return rows, jnp.ones(rows.shape[:2], jnp.float32)
    amax = jnp.max(jnp.abs(rows), axis=-1)                    # (P, L)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.round(rows / scale[..., None]), -127, 127)
    return q.astype(jnp.int8), scale


def _dequant(rows: Array, scale: Array) -> Array:
    return rows.astype(jnp.float32) * scale[..., None]


def _build_local(w_local: Array, n_valid, *, codewords: int, codebooks: int,
                 list_size: int | None, bits: int):
    s = midx.build(w_local, codewords=codewords, codebooks=codebooks,
                   list_size=list_size, n_valid=n_valid)
    rows, scale = _quantize_rows(s.wq, bits)
    return s.c1, s.c2, s.codes, s.cnt, s.perm, rows, scale


def build_quantized_index(w: Array, ctx=None, *, codewords: int = 16,
                          codebooks: int = 2, list_size: int | None = None,
                          bits: int = 8,
                          vocab_size: int | None = None
                          ) -> QuantizedRetrievalIndex:
    """Build the quantized serving index from a class-embedding table.

    w: (n, d) head table, UNPROJECTED (stage-2 dots are the true logits up
    to row quantization).  With a mesh ``ctx``, ``w`` is the vocab-sharded
    P('model', Fd) head and the build runs as a per-shard island — the
    same contract as ``retrieval.build_index``."""
    if bits not in (8, 32):
        raise ValueError(f"bits must be 8 or 32, got {bits}")
    n_rows, d = w.shape
    n = vocab_size if vocab_size is not None else n_rows
    if ctx is None or ctx.mesh is None:
        parts = _build_local(w, jnp.asarray(n, jnp.int32),
                             codewords=codewords, codebooks=codebooks,
                             list_size=list_size, bits=bits)
        return QuantizedRetrievalIndex(*parts, n=n, tp=1, v_shard=n_rows,
                                       bits=bits)

    tp = ctx.tp
    mdl = ctx.model_axis
    v_l = n_rows // tp

    def island(w_l):
        w_full = gather_head_fd(ctx, w_l)  # undo the 'Fd' feature sharding
        my = lax.axis_index(mdl)
        n_valid = jnp.clip(n - my * v_l, 0, v_l)
        return _build_local(w_full, n_valid, codewords=codewords,
                            codebooks=codebooks, list_size=list_size,
                            bits=bits)

    parts = jax.shard_map(
        island, mesh=ctx.mesh, check_vma=False,
        in_specs=(P(mdl, head_fd_axes(ctx)),),
        out_specs=(P(mdl),) * 7)(w)
    return QuantizedRetrievalIndex(*parts, n=n, tp=tp, v_shard=v_l,
                                   bits=bits)


def _local_topk(index: QuantizedRetrievalIndex, c1, c2, codes, cnt, perm,
                rows, scale, h: Array, k: int, beam: int | None, n_valid
                ) -> tuple[Array, Array]:
    """One shard's beam search: h (T, d) -> (packed-perm-mapped local ids
    (T, k), exact logits (T, k)), best first."""
    num_lists, leaf, d = rows.shape
    b = num_lists if beam is None else min(beam, num_lists)
    assert k <= b * leaf, f"k={k} needs beam*list_size >= k, got {b}*{leaf}"
    h32 = h.astype(jnp.float32)
    # Stage 1: quantized MIPS surrogate over the codeword-pair grid.
    t = (h32 @ c1.T)[:, codes[:, 0]] + (h32 @ c2.T)[:, codes[:, 1]]
    t = jnp.where(cnt[None, :] > 0, t, -jnp.inf)
    _, lists = lax.top_k(t, b)                                # (T, B)
    # Stage 2: exact re-scoring of the survivors' members.
    sub = _dequant(rows[lists], scale[lists])                 # (T, B, L, d)
    dots = jnp.einsum("tbld,td->tbl", sub, h32)
    pos = lists[..., None] * leaf + jnp.arange(leaf)          # packed pos
    dots = jnp.where(pos < n_valid, dots, -jnp.inf)
    tq = h.shape[0]
    logits, sel = lax.top_k(dots.reshape(tq, b * leaf), k)
    picked = jnp.take_along_axis(pos.reshape(tq, b * leaf), sel, axis=1)
    return perm[picked], logits


def decode_topk(index: QuantizedRetrievalIndex, h: Array, k: int,
                beam: int | None = None, ctx=None) -> tuple[Array, Array]:
    """Top-k ids + logits over the full vocab through the quantized index.

    h: (T, d) -> (ids (T, k) int32 GLOBAL class ids, logits (T, k) fp32
    exact dequantized dots), sorted descending.  ``beam`` = posting lists
    re-scored per shard (None / >= num_lists is exhaustive over lists —
    exact up to row quantization).  Mesh contract identical to
    ``retrieval.decode_topk``: per-shard beam, ONE (T, tp*k) all-gather."""
    if ctx is None or ctx.mesh is None:
        ids, logits = _local_topk(
            index, index.c1, index.c2, index.codes, index.cnt, index.perm,
            index.rows, index.scale, h, k, beam,
            jnp.asarray(index.n, jnp.int32))
        return ids.astype(jnp.int32), logits

    mdl = ctx.model_axis
    v_l = index.v_shard
    dsp = ctx.data_spec()
    dataspec = None if h.shape[0] % ctx.dp else dsp

    def island(c1_l, c2_l, codes_l, cnt_l, perm_l, rows_l, scale_l, h_l):
        my = lax.axis_index(mdl)
        n_valid = jnp.clip(index.n - my * v_l, 0, v_l)
        ids_l, logits_l = _local_topk(index, c1_l, c2_l, codes_l, cnt_l,
                                      perm_l, rows_l, scale_l, h_l, k, beam,
                                      n_valid)
        ids_g = ids_l + my * v_l  # original local -> global
        all_ids = lax.all_gather(ids_g, mdl, axis=1, tiled=True)
        all_logits = lax.all_gather(logits_l, mdl, axis=1, tiled=True)
        logits, sel = lax.top_k(all_logits, k)
        return (jnp.take_along_axis(all_ids, sel, axis=1).astype(jnp.int32),
                logits)

    return jax.shard_map(
        island, mesh=ctx.mesh, check_vma=False,
        in_specs=(P(mdl),) * 7 + (P(dataspec, None),),
        out_specs=(P(dataspec, None), P(dataspec, None)))(
            index.c1, index.c2, index.codes, index.cnt, index.perm,
            index.rows, index.scale, h)


def recall_at_k(index: QuantizedRetrievalIndex, w: Array, h: Array, k: int,
                beam: int | None, ctx=None) -> float:
    """|retrieved ∩ dense top-k| / k averaged over queries — the quantized
    index's recall knob, against the fp32 dense argmax reference."""
    from repro.serve import retrieval

    ids, _ = decode_topk(index, h, k, beam, ctx)
    true_ids, _ = retrieval.dense_topk(w, h, k, n_valid=index.n)
    hits = (ids[:, :, None] == true_ids[:, None, :]).any(axis=1)
    return float(jnp.mean(jnp.sum(hits, axis=-1) / k))
