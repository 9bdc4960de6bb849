"""Hierarchy-backed top-k MIPS retrieval for serving (DESIGN.md §5).

The training-side Gram hierarchy (``core/hierarchy.py``) is, unchanged, a
maximum-inner-product-search index over the class embeddings: for any node
(class set) C the statistics bound the best logit inside it,

    max_{j in C} <h, w_j>  <=  min( sqrt(h^T Z_C h),              [gram]
                                    ||h|| * sqrt(max ||w_j||^2),  [norm]
                                    <h, mu_C> + ||h|| * rad_C )   [ball]

— [gram] from sum-of-squares (h^T Z_C h = sum_j <h, w_j>^2), [norm] from
Cauchy-Schwarz via the ``levels_ub`` max-norm statistic, and [ball] from
the node's centroid ``mu_C`` and covering radius ``rad_C = max ||w_j -
mu_C||`` (the IVF/cell-ranking bound; tightest once leaves are clustered).
[gram] costs r^2 flops per node, so wide levels use its rank-s SPECTRAL
compression instead,

    h^T Z_C h  <=  sum_{i<s} lam_i <h, v_i>^2 + lam_res ||h||^2   [spec]

(top-s eigenpairs of Z_C plus the next eigenvalue as a residual cap; above
r = ``SPECTRAL_SUBSPACE`` they come from a subspace iteration) —
s*r flops per node, empirically within a few percent of the exact kernel
bound's pruning quality.  All serving statistics are built once per index
build on the same cadence as the Gram sums and carried heap-packed in the
index; none of them run in the training hot path.  This module turns those
bounds into a serving-side retrieval subsystem:

  * ``beam_descent``  — batched LEVEL-SYNCHRONOUS beam search: all T queries
                        advance one level per step, expanding the beam's
                        children and keeping the top-``beam`` nodes by upper
                        bound (when the exact gram bound is enabled via
                        ``gram_cap``, its dense-level quadratic forms route
                        through the ``block_scores`` Pallas kernel; the
                        default spectral/ball/norm bounds are plain XLA).
  * ``topk``          — exact scoring of the surviving leaves' classes
                        (raw dots through the ``leaf_scores`` Pallas kernel
                        in dot mode) and a flat top-k over them.
  * ``RetrievalIndex``— the heap-packed (z, cnt, wq) triple as a standalone
                        pytree, sharded P('model') exactly like TrainState's
                        sampler statistics (top log2(tp) levels = TP axis,
                        DESIGN.md §2.5), checkpointable as-is.
  * ``decode_topk``   — mesh-aware entry point: per-shard beam retrieval over
                        the local subtree, then one all-gather of (T, k)
                        candidates over the model axis and a global merge.

Because the training hierarchy partitions classes in id order (an arbitrary
partition is all sampling needs — §3.2.1's telescoping argument holds for
any fixed partition), the bounds discriminate poorly on such leaves.  The
serving index therefore CO-CLUSTERS classes first: a balanced PC-bisection
(recursively split each node's classes by their projection onto the node's
top principal direction — the inverted-multi-index idea from the related
Chen et al. line) permutes rows so leaves hold similar embeddings, and the
permutation is carried in the index to map retrieved positions back to
original class ids.  Measured on a trained toy model this roughly doubles
recall at a fixed beam (see ``benchmarks/decode_topk.py``).

Work: a beam of B leaves scores ``B * leaf_size`` classes per query
(~ 2B * depth * s * r flops of bound evaluations + B * leaf * r exact
dots) instead of the dense head's n * d — sublinear in n for fixed beam.
``beam`` is the recall knob: ``beam >= num_leaves`` scores every class and
is EXACT (equal to the dense argmax/top-k path); narrower beams trade
recall for work, and ``recall_at_k`` measures the trade-off.  The index
must be built UNPROJECTED (the leaf dots are the true logits);
sampling-side low-rank projection (DESIGN.md §2.3) does not apply here.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.core import hierarchy
from repro.core.hierarchy import HierarchyStats
from repro.core.midx import pc_bisect_perm  # noqa: F401  (canonical home
# moved to core/midx.py — the midx posting lists and this serving index
# share ONE balanced bisection; re-exported for existing callers)
from repro.core.topk import logits_topk
from repro.sharding.rules import gather_head_fd, head_fd_axes
from repro.utils.misc import log2_int, next_pow2

Array = jax.Array


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class RetrievalIndex:
    """Packed serving index — TrainState's statistics carriage, standalone.

    z:       (tp * 2L_l, r, r) fp32 heap-packed per-level Gram sums
             (``hierarchy.to_heap`` layout per shard), sharded P('model').
    cnt:     (tp * 2L_l,) fp32 heap-packed per-node true-class counts.
    wq:      (tp * L_l, leaf, r) fp32 leaf table — an EXACT (unprojected)
             copy of the class embeddings, so leaf dots are the logits.
    mu:      (tp * 2L_l, r) fp32 heap-packed per-node centroids (mean of the
             node's valid rows) — the ball bound's center.
    rad:     (tp * 2L_l,) fp32 heap-packed covering radii
             ``max_j ||w_j - mu_C||`` — the ball bound's radius.
    evecs:   (tp * 2L_l, s, r) fp32 heap-packed top-s eigenvectors of each
             node's Gram sum — the spectral kernel bound's directions.
    evals:   (tp * 2L_l, s + 1) fp32 heap-packed top-s eigenvalues plus the
             residual cap (the (s+1)-th eigenvalue; 0 when s == r).
    perm:    (tp * L_l * leaf,) int32 — packed position -> ORIGINAL local
             row id within the shard (identity when built unclustered).
             Valid positions (< the shard's n_valid) always map to valid
             local ids: clustering permutes valid rows among themselves.
    n:       static — true global class count (rows at/after it are padding).
    tp:      static — vocab-parallel degree the heap was packed for (1 when
             built without a mesh).
    v_shard: static — embedding rows per shard (global id of a shard's
             original local row i is ``shard * v_shard + i``); >= n when
             tp == 1.

    A plain pytree: ``CheckpointManager.save``/``restore`` handle it as-is,
    so a trained model serves from the exported index without a rebuild.
    """

    z: Array
    cnt: Array
    wq: Array
    mu: Array
    rad: Array
    evecs: Array
    evals: Array
    perm: Array
    n: int = dataclasses.field(metadata=dict(static=True))
    tp: int = dataclasses.field(metadata=dict(static=True))
    v_shard: int = dataclasses.field(metadata=dict(static=True))

    @property
    def num_leaves_shard(self) -> int:
        return self.wq.shape[0] // self.tp

    @property
    def leaf_size(self) -> int:
        return self.wq.shape[1]


def default_leaf_size(n_rows: int, d: int) -> int:
    """Serving leaf size: wide enough to amortize the gather, power of two."""
    return next_pow2(max(2, min(n_rows, max(d, 32))))


def ball_stats(w_pad: Array, n_valid: Array | int, depth: int
               ) -> tuple[tuple[Array, ...], tuple[Array, ...]]:
    """Per-level ball-bound statistics from the PACKED row table.

    w_pad: (n_pad, r) rows in leaf order (post-clustering), padding zeroed.
    Returns (levels_mu root..leaf of (nodes, r), levels_rad of (nodes,)):
    exact centroid of each node's valid rows and the exact covering radius.
    O(n r) per level; built once per index build — serving statistics share
    the Gram sums' refresh cadence but never run in the training hot path."""
    n_pad, r = w_pad.shape
    valid = jnp.arange(n_pad) < n_valid
    mus, rads = [], []
    for lvl in range(depth + 1):
        nodes = 1 << lvl
        grp = n_pad // nodes
        wv = w_pad.reshape(nodes, grp, r)
        vv = valid.reshape(nodes, grp)
        cnt = jnp.sum(vv, axis=1)
        mu = jnp.sum(wv, axis=1) / jnp.maximum(cnt, 1)[:, None]
        d2 = jnp.sum(jnp.square(wv - mu[:, None, :]), axis=-1)
        rads.append(jnp.sqrt(jnp.max(jnp.where(vv, d2, 0.0), axis=1)))
        mus.append(mu)
    return tuple(mus), tuple(rads)


#: subspace the spectral statistics are computed in, and its power
#: iterations.  The TPU compiles a dense ``eigh`` above 256 x 256 into a
#: program that takes minutes to compile (over six at r = 3072); up to
#: this width the subspace is the whole space and the result exact.
SPECTRAL_SUBSPACE, SPECTRAL_ITERS = 128, 16


def _top_eigh(z: Array) -> tuple[Array, Array]:
    """Top eigenpairs of a batch of symmetric PSD (N, r, r): values (N, p)
    descending and vectors (N, p, r), p = min(r, SPECTRAL_SUBSPACE).

    Block power iteration from a fixed start, then Rayleigh-Ritz: the
    only ``eigh`` is p x p.  Exact when p == r (the start spans the whole
    space); otherwise the Ritz values approach the top eigenvalues from
    below at rate (lam_{p+1} / lam_i) ** SPECTRAL_ITERS."""
    r = z.shape[-1]
    p = min(r, SPECTRAL_SUBSPACE)
    mm = functools.partial(jnp.matmul, precision=lax.Precision.HIGHEST)
    q = jnp.broadcast_to(
        jax.random.normal(jax.random.PRNGKey(0), (r, p), z.dtype),
        z.shape[:-1] + (p,))
    q = jnp.linalg.qr(q)[0]
    if p < r:
        q = lax.fori_loop(0, SPECTRAL_ITERS,
                          lambda _, q_: jnp.linalg.qr(mm(z, q_))[0], q)
    vals, u = jnp.linalg.eigh(mm(jnp.swapaxes(q, -1, -2), mm(z, q)))
    vecs = jnp.swapaxes(mm(q, u), -1, -2)             # (N, p, r) ascending
    return vals[..., ::-1], vecs[..., ::-1, :]


def spectral_stats(levels_z, s: int = 4
                   ) -> tuple[tuple[Array, ...], tuple[Array, ...]]:
    """Rank-s spectral compression of every node's Gram sum.

    For each node, the top-s eigenpairs of Z_C plus a residual cap give the
    quadratic-form bound h^T Z_C h <= sum lam_i <h,v_i>^2 + lam_res ||h||^2
    at s*r flops per node (vs r^2 for the exact form).  Returns
    (levels_evecs of (nodes, s, r), levels_evals of (nodes, s+1)) with
    evals[..., s] the residual cap (0 when s >= r).  Up to
    ``SPECTRAL_SUBSPACE`` wide the eigenpairs are exact and the bound is
    sound; wider, they come from ``_top_eigh``'s subspace and the cap can
    fall short of lam_{s+1} — the bound then only ranks the beam, and a
    full-beam decode stays exact whatever it says.  Build-time only."""
    r = levels_z[0].shape[-1]
    s = min(s, r)
    evecs_lvls, evals_lvls = [], []
    for z in levels_z:
        vals, vecs = _top_eigh(z)
        res = (vals[..., s] if s < r
               else jnp.zeros(vals.shape[:-1], vals.dtype))
        evecs_lvls.append(vecs[..., :s, :])
        evals_lvls.append(
            jnp.concatenate([vals[..., :s], res[..., None]], axis=-1))
    return tuple(evecs_lvls), tuple(evals_lvls)


def _build_local(w_local: Array, leaf: int, n_valid, cluster: bool):
    """One shard's (or the unsharded) build: pad, cluster, build, pack.

    w_local: (v_l, d) local embedding rows -> heap arrays + wq + perm."""
    v_l, d = w_local.shape
    leaf = next_pow2(leaf)
    num_leaves = next_pow2(max(1, -(-v_l // leaf)))
    n_pad = num_leaves * leaf
    w_pad = jnp.pad(w_local.astype(jnp.float32), ((0, n_pad - v_l), (0, 0)))
    # Zero rows at/after n_valid NOW (hierarchy.build would anyway): vocab
    # divisibility padding is random-initialized head rows, which must not
    # pollute the clustering directions or the ball centroids/radii.
    row_ok = jnp.arange(n_pad) < n_valid
    w_pad = jnp.where(row_ok[:, None], w_pad, 0.0)
    if cluster:
        perm = pc_bisect_perm(w_pad, n_valid, log2_int(num_leaves))
        w_pad = w_pad[perm]
    else:
        perm = jnp.arange(n_pad, dtype=jnp.int32)
    stats = hierarchy.build(w_pad, leaf, n_valid=n_valid, full_tree=True)
    z, cnt = hierarchy.to_heap(stats)
    mus, rads = ball_stats(w_pad, n_valid, stats.depth)
    evecs, evals = spectral_stats(stats.levels_z)
    pack = hierarchy.pack_levels
    return (z, cnt, stats.wq, pack(list(mus)), pack(list(rads)),
            pack(list(evecs)), pack(list(evals)), perm)


def build_index(w: Array, ctx=None, *, leaf_size: int | None = None,
                vocab_size: int | None = None,
                cluster: bool = True) -> RetrievalIndex:
    """Build the serving index from a class-embedding table.

    w: (n, d) — the head table / item tower output embeddings, UNPROJECTED.
    ctx: ShardCtx; with a mesh, ``w`` is the vocab-sharded P('model', Fd)
    head and the build runs as a per-shard island (each shard builds the
    subtree over its local vocab rows; heap arrays come out P('model')).
    vocab_size: true class count when ``w`` carries divisibility padding.
    cluster: PC-bisection co-clustering of each shard's rows (recommended;
    narrow-beam recall roughly doubles).  Clustering is shard-local, so the
    P('model') layout and the top-levels-are-the-TP-axis mapping are
    untouched.
    """
    n_rows, d = w.shape
    n = vocab_size if vocab_size is not None else n_rows
    if ctx is None or ctx.mesh is None:
        leaf = leaf_size or default_leaf_size(n_rows, d)
        z, cnt, wq, mu, rad, evc, evl, perm = _build_local(
            w, leaf, jnp.asarray(n, jnp.int32), cluster)
        return RetrievalIndex(z, cnt, wq, mu, rad, evc, evl, perm, n=n,
                              tp=1, v_shard=n_rows)

    tp = ctx.tp
    mdl = ctx.model_axis
    v_l = n_rows // tp
    leaf = leaf_size or default_leaf_size(v_l, d)

    def island(w_l):
        w_full = gather_head_fd(ctx, w_l)  # undo the 'Fd' feature sharding
        my = lax.axis_index(mdl)
        n_valid = jnp.clip(n - my * v_l, 0, v_l)
        return _build_local(w_full, leaf, n_valid, cluster)

    z, cnt, wq, mu, rad, evc, evl, perm = jax.shard_map(
        island, mesh=ctx.mesh, check_vma=False,
        in_specs=(P(mdl, head_fd_axes(ctx)),),
        out_specs=(P(mdl),) * 8)(w)
    return RetrievalIndex(z, cnt, wq, mu, rad, evc, evl, perm, n=n, tp=tp,
                          v_shard=v_l)


def index_stats(index: RetrievalIndex, shard: int = 0,
                n_valid: Array | int | None = None) -> HierarchyStats:
    """Rehydrate one shard's heap slices into ``HierarchyStats``.

    Call inside the P('model') island with ``shard``-local slices already in
    hand; the tp == 1 (unsharded) form takes the whole arrays."""
    if n_valid is None:
        n_valid = jnp.clip(index.n - shard * index.v_shard, 0, index.v_shard)
    return hierarchy.from_heap(index.z, index.cnt, index.wq, n_valid)


# --- batched beam descent (the serving twin of hierarchy.descend) -----------


def _ub_dense(stats: HierarchyStats, lvl: int, hq: Array, hnorm: Array,
              ball, spec, with_gram: bool, use_kernels: bool) -> Array:
    """Upper-bound table for EVERY node at one level: (T, nodes_l)."""
    z, cnt, ub2 = (stats.levels_z[lvl], stats.levels_cnt[lvl],
                   stats.levels_ub[lvl])
    bound = hnorm[:, None] * jnp.sqrt(ub2)[None, :]
    if with_gram:
        if use_kernels:
            from repro.kernels import ops
            quad = ops.block_scores(hq, z, jnp.zeros_like(cnt), alpha=1.0)
        else:
            quad = jnp.einsum("nij,ti,tj->tn", z, hq, hq)
        bound = jnp.minimum(bound, jnp.sqrt(jnp.maximum(quad, 0.0)))
    elif spec is not None:
        evc, evl = spec[0][lvl], spec[1][lvl]  # (N, s, r), (N, s+1)
        proj = jnp.einsum("nsr,tr->tns", evc, hq)
        quad_ub = (jnp.einsum("ns,tns->tn", evl[:, :-1], proj * proj)
                   + evl[None, :, -1] * (hnorm * hnorm)[:, None])
        bound = jnp.minimum(bound, jnp.sqrt(jnp.maximum(quad_ub, 0.0)))
    if ball is not None:
        mu, rad = ball[0][lvl], ball[1][lvl]
        bound = jnp.minimum(bound,
                            hq @ mu.T + hnorm[:, None] * rad[None, :])
    return jnp.where(cnt[None, :] > 0, bound, -jnp.inf)


def _ub_gathered(stats: HierarchyStats, lvl: int, hq: Array, hnorm: Array,
                 ball, spec, with_gram: bool, nodes: Array) -> Array:
    """Upper bounds of per-query gathered nodes: hq (T, r), nodes (T, C)."""
    z, cnt, ub2 = (stats.levels_z[lvl], stats.levels_cnt[lvl],
                   stats.levels_ub[lvl])
    bound = hnorm[:, None] * jnp.sqrt(ub2[nodes])
    if with_gram:
        quad = jnp.einsum("tcij,ti,tj->tc", z[nodes], hq, hq)
        bound = jnp.minimum(bound, jnp.sqrt(jnp.maximum(quad, 0.0)))
    elif spec is not None:
        evc, evl = spec[0][lvl], spec[1][lvl]
        proj = jnp.einsum("tcsr,tr->tcs", evc[nodes], hq)
        quad_ub = (jnp.einsum("tcs,tcs->tc", evl[nodes][..., :-1],
                              proj * proj)
                   + evl[nodes][..., -1] * (hnorm * hnorm)[:, None])
        bound = jnp.minimum(bound, jnp.sqrt(jnp.maximum(quad_ub, 0.0)))
    if ball is not None:
        mu, rad = ball[0][lvl], ball[1][lvl]
        bound = jnp.minimum(
            bound, jnp.einsum("tcr,tr->tc", mu[nodes], hq)
            + hnorm[:, None] * rad[nodes])
    return jnp.where(cnt[nodes] > 0, bound, -jnp.inf)


def beam_descent(stats: HierarchyStats, h: Array, beam: int, *,
                 ball=None, spec=None, use_kernels: bool | None = None,
                 dense_cap: int | None = None,
                 gram_cap: int | None = None) -> Array:
    """Level-synchronous batched beam search down the Gram hierarchy.

    h: (T, r) queries in the statistics' space (unprojected for serving).
    Per level: expand every beam node into its two children — ONE batched
    bound evaluation for all (T, candidates) — and keep the top-``beam``
    candidates per query by upper bound.  Children of distinct parents are
    distinct, so the beam needs no dedup.  Levels with at most ``dense_cap``
    nodes evaluate the full (T, nodes) bound table; deeper levels gather
    per-candidate statistics.  ``use_kernels`` routes the exact gram
    bound's dense tables through the ``block_scores`` Pallas kernel — it
    only engages on levels where ``gram_cap`` enables that bound.

    Bound cost policy: the norm and ball bounds cost O(r) per node and the
    spectral kernel bound O(s*r); they run at every level and keep the
    total bound work well under the dense head's n*d — which is what makes
    the beam path cheaper at serving time.  The EXACT quadratic-kernel
    (gram) bound costs O(r^2) per node; ``gram_cap`` (default 0) replaces
    the spectral form with it on levels with at most that many nodes —
    research use, the spectral form prunes within a few percent of it.

    ``ball`` / ``spec``: optional (levels_mu, levels_rad) /
    (levels_evecs, levels_evals) root..leaf tuples — the index's
    heap-carried serving statistics.

    Returns (T, min(beam, num_leaves)) leaf indices, best-bound-first.
    ``beam >= num_leaves`` keeps every node — exhaustive, hence exact.
    """
    if use_kernels is None:
        use_kernels = jax.default_backend() == "tpu"
    if dense_cap is None:
        dense_cap = max(64, 2 * beam)
    if gram_cap is None:
        gram_cap = 0
    hq = h.astype(jnp.float32)
    hnorm = jnp.sqrt(jnp.sum(hq * hq, axis=-1))
    t = hq.shape[0]
    idx = jnp.zeros((t, 1), jnp.int32)
    for lvl in range(1, stats.depth + 1):
        nodes_l = stats.levels_z[lvl].shape[0]
        with_gram = nodes_l <= gram_cap
        cand = jnp.concatenate([2 * idx, 2 * idx + 1], axis=1)
        if nodes_l <= dense_cap:
            table = _ub_dense(stats, lvl, hq, hnorm, ball, spec, with_gram,
                              use_kernels)
            ub = jnp.take_along_axis(table, cand, axis=1)
        else:
            ub = _ub_gathered(stats, lvl, hq, hnorm, ball, spec, with_gram,
                              cand)
        keep = min(beam, cand.shape[1])
        _, sel = lax.top_k(ub, keep)
        idx = jnp.take_along_axis(cand, sel, axis=1)
    return idx


def leaf_topk(stats: HierarchyStats, h: Array, leaves: Array, k: int, *,
              use_kernels: bool | None = None) -> tuple[Array, Array]:
    """Exact top-k over the classes of the surviving leaves.

    h: (T, r); leaves: (T, B) leaf indices -> ids (T, k) int32 local class
    ids and logits (T, k) fp32 exact dots, sorted descending.  Padding rows
    (local id >= n_valid) score -inf.  The B * leaf_size gathered rows are
    scored by the ``leaf_scores`` kernel in dot mode when ``use_kernels``,
    which fetches each surviving leaf by block index (no gathered copy).
    """
    if use_kernels is None:
        use_kernels = jax.default_backend() == "tpu"
    hq = h.astype(jnp.float32)
    t, b = leaves.shape
    leaf = stats.leaf_size
    assert k <= b * leaf, (
        f"k={k} needs beam*leaf_size >= k, got {b}*{leaf}")
    if use_kernels:
        from repro.kernels import ops
        dots = ops.leaf_dots(hq, stats.wq, leaves)
    else:
        rows = stats.wq[leaves]  # (T, B, leaf, r)
        dots = jnp.einsum("tblr,tr->tbl", rows, hq)
    ids = leaves[..., None] * leaf + jnp.arange(leaf)  # (T, B, leaf)
    dots = jnp.where(ids < stats.n_valid, dots, -jnp.inf)
    logits, sel = lax.top_k(dots.reshape(t, b * leaf), k)
    ids = jnp.take_along_axis(ids.reshape(t, b * leaf), sel, axis=1)
    return ids.astype(jnp.int32), logits


def topk(stats: HierarchyStats, h: Array, k: int, beam: int | None = None, *,
         ball=None, spec=None, use_kernels: bool | None = None,
         dense_cap: int | None = None,
         gram_cap: int | None = None) -> tuple[Array, Array]:
    """Single-shard top-k MIPS: beam descent + exact leaf scoring.

    h: (T, r) -> (ids (T, k) int32, logits (T, k) fp32), best first.
    ``ids`` are PACKED positions in the stats' leaf table — callers holding
    a clustered ``RetrievalIndex`` map them through ``index.perm``
    (``decode_topk`` does).  ``beam=None`` (or >= num_leaves) is exhaustive
    and exact."""
    if beam is None:
        beam = stats.num_leaves
    leaves = beam_descent(stats, h, beam, ball=ball, spec=spec,
                          use_kernels=use_kernels, dense_cap=dense_cap,
                          gram_cap=gram_cap)
    return leaf_topk(stats, h, leaves, k, use_kernels=use_kernels)


# --- mesh-aware decode (vocab-sharded P('model') layout) --------------------


def decode_topk(index: RetrievalIndex, h: Array, k: int,
                beam: int | None = None, ctx=None, *,
                use_kernels: bool | None = None,
                dense_cap: int | None = None,
                gram_cap: int | None = None) -> tuple[Array, Array]:
    """Top-k ids + logits over the full vocab through the packed index.

    h: (T, d) hidden states -> (ids (T, k) int32 GLOBAL class ids,
    logits (T, k) fp32 exact dots), sorted descending per query.

    Unsharded (ctx is None / no mesh): one local beam retrieval.  On a mesh
    the index arrays are P('model')-sharded and each shard runs the beam
    over its local subtree (the top log2(tp) levels of the global hierarchy
    ARE the shard index, DESIGN.md §2.5), takes its local top-k, and the
    shards merge with ONE all-gather of (T, k) candidates over the model
    axis — never a gathered (T, n) logit tensor.
    """
    depth = log2_int(index.num_leaves_shard)
    if ctx is None or ctx.mesh is None:
        stats = index_stats(index)
        ball = (hierarchy.unpack_levels(index.mu, depth),
                hierarchy.unpack_levels(index.rad, depth))
        spec = (hierarchy.unpack_levels(index.evecs, depth),
                hierarchy.unpack_levels(index.evals, depth))
        pos, logits = topk(stats, h, k, beam, ball=ball, spec=spec,
                           use_kernels=use_kernels, dense_cap=dense_cap,
                           gram_cap=gram_cap)
        return index.perm[pos], logits

    mdl = ctx.model_axis
    v_l = index.v_shard
    dsp = ctx.data_spec()
    dataspec = None if h.shape[0] % ctx.dp else dsp

    def island(z_l, cnt_l, wq_l, mu_l, rad_l, evc_l, evl_l, perm_l, h_l):
        my = lax.axis_index(mdl)
        n_valid = jnp.clip(index.n - my * v_l, 0, v_l)
        stats = hierarchy.from_heap(z_l, cnt_l, wq_l, n_valid)
        ball = (hierarchy.unpack_levels(mu_l, depth),
                hierarchy.unpack_levels(rad_l, depth))
        spec = (hierarchy.unpack_levels(evc_l, depth),
                hierarchy.unpack_levels(evl_l, depth))
        pos, logits_l = topk(stats, h_l, k, beam, ball=ball, spec=spec,
                             use_kernels=use_kernels, dense_cap=dense_cap,
                             gram_cap=gram_cap)
        ids_g = perm_l[pos] + my * v_l  # packed -> original local -> global
        # Merge: every shard contributes k candidates; one (T, tp*k) gather.
        all_ids = lax.all_gather(ids_g, mdl, axis=1, tiled=True)
        all_logits = lax.all_gather(logits_l, mdl, axis=1, tiled=True)
        logits, sel = lax.top_k(all_logits, k)
        return jnp.take_along_axis(all_ids, sel, axis=1), logits

    return jax.shard_map(
        island, mesh=ctx.mesh, check_vma=False,
        in_specs=(P(mdl),) * 8 + (P(dataspec, None),),
        out_specs=(P(dataspec, None), P(dataspec, None)))(
            index.z, index.cnt, index.wq, index.mu, index.rad, index.evecs,
            index.evals, index.perm, h)


# --- measurement ------------------------------------------------------------


def dense_topk(w: Array, h: Array, k: int,
               n_valid: int | None = None) -> tuple[Array, Array]:
    """Exact top-k by dense logits: the served decode without an index
    (``engine.decode_topk`` on one device), and the oracle of the
    ``recall_at_k`` measurements.

    O(n d): h . W^T in float32 at the default precision, rows at or past
    ``n_valid`` masked to -inf, then the top k by ``topk.logits_topk`` --
    the same values and ids as ``lax.top_k`` over the logits, found in two
    narrow stages at a large n instead of one sort of the whole row
    (DESIGN.md §5)."""
    vals, ids = logits_topk(h, w, k, n_valid=n_valid)
    return ids.astype(jnp.int32), vals


def recall_at_k(index: RetrievalIndex, w: Array, h: Array, k: int,
                beam: int, ctx=None) -> float:
    """Measured recall knob: |retrieved ∩ true top-k| / k, averaged over T."""
    ids, _ = decode_topk(index, h, k, beam, ctx)
    true_ids, _ = dense_topk(w, h, k, n_valid=index.n)
    hits = (ids[:, :, None] == true_ids[:, None, :]).any(axis=1)
    return float(jnp.mean(jnp.sum(hits, axis=-1) / k))


def scored_classes(index: RetrievalIndex, beam: int | None) -> int:
    """Classes exactly scored per query — the beam path's 'work' metric."""
    b = index.num_leaves_shard if beam is None else min(
        beam, index.num_leaves_shard)
    return index.tp * b * index.leaf_size
