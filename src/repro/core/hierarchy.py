"""Shared hierarchical Gram-statistics core (DESIGN.md §2.1, §2.6).

Both kernel samplers — the paper-faithful binary tree (§3.2, ``core/tree.py``)
and the TPU two-level block sampler (DESIGN.md §2.2, ``core/blocks.py``) —
are views over the SAME object: a hierarchy of class sets whose per-node
summary statistic is the Gram sum ``Z_C = sum_{j in C} w_j w_j^T`` plus a
true-class count, so that the quadratic-kernel mass of any node is

    <phi(h), z(C)> = alpha * h^T Z_C h + |C|            (DESIGN.md §2.1)

This module owns everything the two previously duplicated:

  * ``build``        — leaf Gram blocks from one batched matmul, padding and
                       runtime ``n_valid`` masking, count bookkeeping, and the
                       bottom-up pairwise parent sums (full tree) or a single
                       leaf level (two-level form).
  * ``update_rows``  — the paper's Fig. 1b sparse refresh: scatter
                       ``Delta(w w^T)`` into every level along each
                       leaf-to-root path.
  * ``descend``      — the LEVEL-SYNCHRONOUS batched descent (DESIGN.md §2.6):
                       all (T, m) in-flight draws advance one tree level per
                       step, each level being one batched mass evaluation
                       (dense levels route through the ``block_scores`` Pallas
                       kernel, the within-leaf categorical through
                       ``leaf_scores``) instead of T*m*depth sequential
                       Bernoulli draws.
  * ``to_heap`` / ``from_heap`` — pack the per-level tuple into two flat
                       arrays so tree statistics can be carried in
                       ``TrainState`` and sharded ``P('model')`` exactly like
                       block statistics (DESIGN.md §2.5).

Alongside the Gram sums every level also carries a MAX-UPPER-BOUND statistic
(``levels_ub``): the largest squared row norm of any class in the node.
Together with the Gram sum it bounds the best logit inside a subtree,

    max_{j in C} <h, w_j>  <=  min( sqrt(h^T Z_C h), ||h|| * sqrt(ub(C)) )

which is what the serving-side beam retrieval prunes with
(``serve/retrieval.py``, DESIGN.md §5).  The statistic is built, refreshed,
and sparsely updated on exactly the same cadence as the Gram sums; it is a
pure function of ``wq`` so the heap carriage stays two arrays and
``from_heap`` rebuilds it in O(n r).

The reported log-q is always the EXACT log-probability of the draw under the
hierarchy's distribution (the telescoping product of eq. 9 times the
within-leaf conditional), which is what the eq. 2 correction requires.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core.kernel_fns import (
    SamplingKernel,
    gram_set_mass,
    rff_log_phi,
    rff_logshift_bound,
    rff_phi,
)
from repro.utils.misc import log2_int, next_pow2

Array = jax.Array


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class HierarchyStats:
    """Per-level Gram statistics + the (possibly projected) sampling table.

    When carried in ``TrainState`` or a serving index, the heap-packed form
    of this object is sharded ``P('model')`` over the leading (node / leaf)
    axis: the top log2(tp) tree levels ARE the TP shard index and every
    shard owns the subtree over its local vocab rows (DESIGN.md §2.5).

    levels_z:   tuple over levels root..leaf of (nodes_l, r, r) fp32 Gram
                sums ``Z_C = sum_{j in C} w_j w_j^T`` (paper eq. 8's summary
                statistic z(C), realized as a matrix — DESIGN.md §2.1);
                level l of a full binary tree holds 2^l nodes, and the
                two-level form holds only the leaf level.
    levels_cnt: tuple over levels of (nodes_l,) fp32 true (non-padding)
                counts |C| — the constant part of the quadratic-kernel mass.
    levels_ub:  tuple over levels of (nodes_l,) fp32 max squared row norms
                ``ub(C) = max_{j in C} ||w_j||^2`` (padding rows are zero and
                never attain the max of a non-empty node).  Serving-side
                retrieval prunes with it (DESIGN.md §5); sampling ignores it.
    wq:         (num_leaves, leaf_size, r) fp32 sampling copy of the class
                embeddings (projected if proj is not None; zero rows for
                padding and for rows at/after ``n_valid``).  Leaf scoring and
                therefore the reported log-q are exact w.r.t. this copy.
    n_valid:    scalar int32 — number of real classes.  Dynamic so sharded
                tables whose last shard carries padding rows keep
                exactly-zero probability on the pads (runtime-masked).
    n:          static row-count bound (the table size at trace time); used
                only by the all-class test oracles for static slicing.
    """

    levels_z: tuple[Array, ...]
    levels_cnt: tuple[Array, ...]
    levels_ub: tuple[Array, ...]
    wq: Array
    n_valid: Array
    n: int = dataclasses.field(metadata=dict(static=True))

    @property
    def depth(self) -> int:
        return len(self.levels_z) - 1

    @property
    def num_leaves(self) -> int:
        return self.wq.shape[0]

    @property
    def leaf_size(self) -> int:
        return self.wq.shape[1]

    @property
    def n_pad(self) -> int:
        return self.num_leaves * self.leaf_size


def project(w: Array, proj: Array | None) -> Array:
    """fp32 copy of ``w``, optionally moved to the rank-r sampling space."""
    w32 = w.astype(jnp.float32)
    if proj is None:
        return w32
    return w32 @ proj.astype(jnp.float32).T


def leaf_counts(n_valid: Array, num_leaves: int, leaf_size: int) -> Array:
    """True (non-padding) class count of each leaf block.

    n_valid: scalar int32 (may be traced) -> (num_leaves,) fp32 counts.
    """
    return jnp.clip(
        n_valid.astype(jnp.float32)
        - jnp.arange(num_leaves, dtype=jnp.float32) * leaf_size,
        0.0, float(leaf_size))


def leaf_ub(wq: Array) -> Array:
    """Max squared row norm of each leaf block: wq (L, B, r) -> (L,) fp32.

    Padding / masked rows are exactly zero in ``wq`` so they contribute 0 —
    harmless, since an all-padding node also has zero Gram mass and is
    excluded from retrieval by its zero count."""
    return jnp.max(jnp.sum(wq * wq, axis=-1), axis=-1)


def ub_levels_from_wq(wq: Array, depth: int) -> tuple[Array, ...]:
    """Rebuild the per-level max-norm statistic bottom-up from ``wq``.

    O(n r + num_leaves): cheap enough that the heap carriage does not store
    it — ``from_heap`` calls this so carried/restored statistics always have
    the bound on the same refresh cadence as the Gram sums."""
    levels = [leaf_ub(wq)]
    for _ in range(depth):
        child = levels[0]
        levels.insert(0, jnp.maximum(child[0::2], child[1::2]))
    return tuple(levels)


def build(w: Array, leaf_size: int, *, proj: Array | None = None,
          n_valid: Array | int | None = None,
          full_tree: bool = True) -> HierarchyStats:
    """Build the hierarchy bottom-up: leaf Gram blocks, then pairwise sums.

    w: (n, d) class embeddings (one vocab shard's rows when called inside
    the P('model') island).  Cost: one batched matmul for the leaves +
    O(num_leaves * r^2) for the upper levels; the max-norm bound rides along
    in O(n r).  ``full_tree=True`` rounds the
    leaf count to a power of two and builds every binary level up to the
    root; ``full_tree=False`` keeps only the leaf level (the two-level TPU
    form, whose "root" is a softmax over all leaf blocks).
    ``n_valid``: number of real classes (rows beyond it must carry no mass);
    may be a traced scalar for sharded tables with padding rows.
    Returns a ``HierarchyStats`` whose level tuples are ordered root..leaf.
    """
    n_rows, _ = w.shape
    if n_valid is None:
        n_valid = n_rows
    n_valid = jnp.asarray(n_valid, jnp.int32)
    wq = project(w, proj)
    r = wq.shape[-1]
    if full_tree:
        leaf_size = next_pow2(leaf_size)
        num_leaves = next_pow2(max(1, -(-n_rows // leaf_size)))
    else:
        num_leaves = -(-n_rows // leaf_size)
    pad = num_leaves * leaf_size - n_rows
    wq = jnp.pad(wq, ((0, pad), (0, 0)))
    # Runtime-zero any rows at/after n_valid (pads must carry no mass).
    row_ok = jnp.arange(num_leaves * leaf_size) < n_valid
    wq = jnp.where(row_ok[:, None], wq, 0.0)
    wq = wq.reshape(num_leaves, leaf_size, r)

    z = jnp.einsum("lbi,lbj->lij", wq, wq)  # (num_leaves, r, r)
    cnt = leaf_counts(n_valid, num_leaves, leaf_size)

    levels_z = [z]
    levels_cnt = [cnt]
    levels_ub = [leaf_ub(wq)]
    if full_tree:
        while levels_z[0].shape[0] > 1:
            child_z = levels_z[0]
            child_c = levels_cnt[0]
            child_u = levels_ub[0]
            levels_z.insert(0, child_z[0::2] + child_z[1::2])
            levels_cnt.insert(0, child_c[0::2] + child_c[1::2])
            levels_ub.insert(0, jnp.maximum(child_u[0::2], child_u[1::2]))
    return HierarchyStats(tuple(levels_z), tuple(levels_cnt),
                          tuple(levels_ub), wq, n_valid, n_rows)


def update_rows(stats: HierarchyStats, ids: Array, w_new: Array,
                proj: Array | None = None) -> HierarchyStats:
    """Paper Fig. 1b: after embeddings of ``ids`` change to ``w_new``, update
    the statistics along each leaf->root path with Delta(w w^T).

    ids: (k,) LOCAL class indices (shard-local when the table is a vocab
    shard); w_new: (k, d).  Cost O(k * depth * r^2) for the Gram sums plus
    O(k * depth) for the max-norm bound (touched leaves recompute their max
    from ``wq``, then the max propagates up the same leaf->root paths).
    Duplicate ids are NOT allowed (undefined order of old-row reads).
    """
    wq_new = project(w_new, proj)
    leaf_of = ids // stats.leaf_size
    off = ids % stats.leaf_size
    wq_old = stats.wq[leaf_of, off]
    delta = (jnp.einsum("ki,kj->kij", wq_new, wq_new)
             - jnp.einsum("ki,kj->kij", wq_old, wq_old))
    wq = stats.wq.at[leaf_of, off].set(wq_new)

    depth = stats.depth
    new_z = []
    for lvl in range(depth + 1):
        node_of = leaf_of >> (depth - lvl)
        new_z.append(stats.levels_z[lvl].at[node_of].add(delta))
    # Max-norm bound: a max cannot be sparsely decremented, so touched
    # leaves recompute from wq, then parents take max-of-children bottom-up.
    new_ub = list(stats.levels_ub)
    new_ub[depth] = new_ub[depth].at[leaf_of].set(leaf_ub(wq[leaf_of]))
    for lvl in range(depth - 1, -1, -1):
        node_of = leaf_of >> (depth - lvl)
        child = new_ub[lvl + 1]
        new_ub[lvl] = new_ub[lvl].at[node_of].set(
            jnp.maximum(child[2 * node_of], child[2 * node_of + 1]))
    return HierarchyStats(tuple(new_z), stats.levels_cnt, tuple(new_ub), wq,
                          stats.n_valid, stats.n)


# --- flat heap packing (TrainState carriage; DESIGN.md §2.5) -----------------


def heap_rows(num_leaves: int) -> int:
    """Rows of the packed heap: 2^(d+1)-1 nodes padded to an even 2*L."""
    return 2 * num_leaves


def pack_levels(levels) -> Array:
    """Heap-pack a root..leaf tuple of per-level arrays into one flat array.

    Level l occupies rows [2^l - 1, 2^(l+1) - 1); one zero padding row
    rounds the total to an even 2L.  This is THE heap layout contract —
    TrainState's statistics carriage and the serving ``RetrievalIndex``
    both speak it (any per-node statistic of any trailing shape packs the
    same way)."""
    pad = jnp.zeros((1, *levels[0].shape[1:]), levels[0].dtype)
    return jnp.concatenate(list(levels) + [pad], axis=0)


def unpack_levels(heap: Array, depth: int) -> tuple[Array, ...]:
    """Inverse of ``pack_levels``: static slices back to root..leaf."""
    out, off = [], 0
    for lvl in range(depth + 1):
        size = 1 << lvl
        out.append(heap[off:off + size])
        off += size
    return tuple(out)


def to_heap(stats: HierarchyStats) -> tuple[Array, Array]:
    """Pack levels root..leaf into flat (2L, r, r) / (2L,) arrays.

    The flat ``pack_levels`` layout is what TrainState and the serving
    ``RetrievalIndex`` carry, sharded P('model') over the leading axis.
    The max-norm bound is intentionally not packed — ``from_heap`` rebuilds
    it exactly from ``wq`` (see ``ub_levels_from_wq``).
    """
    return pack_levels(stats.levels_z), pack_levels(stats.levels_cnt)


def from_heap(z_heap: Array, cnt_heap: Array, wq: Array, n_valid: Array,
              n: int | None = None) -> HierarchyStats:
    """Inverse of ``to_heap``: static slices back into per-level tuples.

    z_heap: (2L, r, r); cnt_heap: (2L,); wq: (L, leaf, r) — one shard's
    slices when the carried arrays are P('model')-sharded.  The max-norm
    bound is NOT stored in the heap; it is an O(n r) pure function of ``wq``
    and is rebuilt here, so rehydrated statistics carry it on the same
    cadence as the Gram sums."""
    num_leaves = wq.shape[0]
    depth = log2_int(num_leaves)
    assert z_heap.shape[0] == heap_rows(num_leaves), (
        z_heap.shape, num_leaves)
    if n is None:
        n = num_leaves * wq.shape[1]
    return HierarchyStats(unpack_levels(z_heap, depth),
                          unpack_levels(cnt_heap, depth),
                          ub_levels_from_wq(wq, depth), wq,
                          jnp.asarray(n_valid, jnp.int32), n)


# --- level-synchronous batched descent (DESIGN.md §2.6) ----------------------


def _mass_table(kernel: SamplingKernel, z: Array, cnt: Array, hq: Array,
                use_kernels: bool) -> Array:
    """Kernel mass of EVERY node at one level for every query: (T, nodes)."""
    if use_kernels:
        from repro.kernels import ops
        return ops.block_scores(hq, z, cnt, alpha=kernel.alpha)
    quad = jnp.einsum("nij,ti,tj->tn", z, hq, hq)
    return kernel.alpha * quad + cnt[None, :]


def _gathered_mass(kernel: SamplingKernel, z: Array, cnt: Array, hq: Array,
                   nodes: Array) -> Array:
    """Kernel mass of per-draw gathered nodes: hq (T, r), nodes (T, m)."""

    def one_query(h, idx_row):
        return jax.vmap(lambda i: gram_set_mass(kernel, z[i], cnt[i], h))(
            idx_row)

    return jax.vmap(one_query)(hq, nodes)


def leaf_logits(stats: HierarchyStats, kernel: SamplingKernel, hq: Array,
                leaf_idx: Array, use_kernels: bool) -> Array:
    """Exact within-leaf kernel log-scores, padding masked to -inf.

    The Fig. 1c leaf step: classes inside a sampled leaf are scored exactly
    with K(h, w) = alpha <h,w>^2 + 1 (paper §3.3) through the
    ``leaf_scores`` Pallas kernel when ``use_kernels``.

    hq: (T, r) projected queries; leaf_idx: (T, m) sampled leaf indices
    -> (T, m, leaf_size) log kernel scores.
    """
    b = stats.leaf_size
    if use_kernels:
        from repro.kernels import ops
        # the kernel fetches each drawn leaf by block index: no gather
        scores = ops.leaf_scores(hq, stats.wq, leaf_idx, alpha=kernel.alpha)
    else:
        rows = stats.wq[leaf_idx]  # (T, m, B, r)
        scores = kernel.of_dot(jnp.einsum("tmbr,tr->tmb", rows, hq))
    ids = leaf_idx[..., None] * b + jnp.arange(b)
    scores = jnp.where(ids < stats.n_valid, scores, 0.0)
    return jnp.where(scores > 0, jnp.log(jnp.maximum(scores, 1e-30)),
                     -jnp.inf)


def descend(stats: HierarchyStats, kernel: SamplingKernel, hq: Array,
            keys: Array, *, use_kernels: bool | None = None,
            dense_cap: int | None = None) -> tuple[Array, Array]:
    """Level-synchronous batched descent: (T, m) draws, depth+1 steps total.

    hq:   (T, r) projected queries.
    keys: (T, m) PRNG keys, one per draw — the SAME key layout the sequential
          per-draw descent consumes, so a fixed key yields identical draws.

    Each level is ONE batched mass evaluation: levels with at most
    ``dense_cap`` nodes compute the full (T, nodes) table (routed through the
    ``block_scores`` Pallas kernel when ``use_kernels``) and gather the two
    child masses per draw; deeper levels gather per-draw child statistics
    directly (O(T m r^2), the paper's per-draw bound).  ``dense_cap=0``
    forces the gathered form everywhere — arithmetic-identical to the
    sequential reference.  The within-leaf categorical routes through the
    ``leaf_scores`` Pallas kernel.

    Returns ids: (T, m) int32 and logq: (T, m) exact log sampling
    probabilities (telescoping product of eq. 9 + within-leaf conditional).
    """
    assert kernel.degree == 2, "hierarchy statistics require a degree-2 kernel"
    if use_kernels is None:
        # Off-TPU the Pallas kernels run in interpret mode (correctness
        # validation only, ~10x slower than XLA); route through them only
        # where they are compiled.
        use_kernels = jax.default_backend() == "tpu"
    # Draws are non-differentiable by contract (the loss stop-gradients the
    # sampled ids/logq); cut the tape here so the Pallas kernels never see
    # tangents (pallas_call has no JVP rule).
    hq = jax.lax.stop_gradient(hq)
    t, m = keys.shape[0], keys.shape[1]
    depth = stats.depth
    if dense_cap is None:
        # Dense tables cost T*nodes*r^2 contiguous flops; the gathered form
        # costs ~2*T*m*r^2 scattered ones.  Prefer dense until the level is
        # several times wider than the draw count.
        dense_cap = max(256, 4 * m)
    # Per-draw, per-level keys: identical split tree to the sequential path.
    klev = jax.vmap(jax.vmap(lambda k: jax.random.split(k, depth + 1)))(keys)

    idx = jnp.zeros((t, m), jnp.int32)
    logq = jnp.zeros((t, m), jnp.float32)
    for lvl in range(1, depth + 1):
        z = stats.levels_z[lvl]
        cnt = stats.levels_cnt[lvl]
        left, right = 2 * idx, 2 * idx + 1
        if z.shape[0] <= dense_cap:
            table = _mass_table(kernel, z, cnt, hq, use_kernels)
            mass_l = jnp.take_along_axis(table, left, axis=1)
            mass_r = jnp.take_along_axis(table, right, axis=1)
        else:
            mass_l = _gathered_mass(kernel, z, cnt, hq, left)
            mass_r = _gathered_mass(kernel, z, cnt, hq, right)
        # Numerical floor: padding-only subtrees have exactly zero mass.
        p_r = mass_r / jnp.maximum(mass_l + mass_r, 1e-30)
        go_right = jax.vmap(jax.vmap(jax.random.bernoulli))(
            klev[:, :, lvl - 1], p_r)
        idx = jnp.where(go_right, right, left)
        logq = logq + jnp.log(jnp.where(go_right, p_r, 1.0 - p_r))

    logits = leaf_logits(stats, kernel, hq, idx, use_kernels)
    within = jax.vmap(jax.vmap(jax.random.categorical))(
        klev[:, :, depth], logits)
    log_within = jnp.take_along_axis(
        jax.nn.log_softmax(logits, axis=-1), within[..., None], axis=-1
    )[..., 0]
    ids = idx * stats.leaf_size + within
    return ids.astype(jnp.int32), logq + log_within


def _all_class_from_levels(level_log_mass, within_logits, n: int) -> Array:
    """Telescoping node probabilities + within-leaf conditional -> (n,) logq.

    level_log_mass: list over levels root..leaf of (nodes_l,) log node masses.
    within_logits: (num_leaves, leaf_size) within-leaf log scores (-inf pads).
    Shared by the Gram and the feature-sum oracles."""
    log_node_prev = jnp.zeros((1,))
    for lvl, lm in enumerate(level_log_mass):
        if lvl == 0:
            log_node = jnp.zeros((lm.shape[0],))
        else:
            parent = jnp.repeat(log_node_prev, 2)
            sibling_sum = jnp.repeat(jnp.logaddexp(lm[0::2], lm[1::2]), 2)
            log_node = parent + lm - sibling_sum
        log_node_prev = log_node
    # Entirely-dead leaves (all rows at/after n_valid) would NaN through
    # log_softmax; their entries are exactly zero-probability.
    log_within = jnp.where(jnp.isneginf(within_logits), -jnp.inf,
                           jax.nn.log_softmax(within_logits, axis=-1))
    out = (log_node_prev[:, None] + log_within).reshape(-1)
    return out[:n]


def all_class_logq(stats: HierarchyStats, kernel: SamplingKernel,
                   hq: Array) -> Array:
    """Exact log-probability the hierarchy assigns to EVERY class (oracle).

    Computes node probabilities level by level (parent prob x branch prob)
    and multiplies by the within-leaf conditional.  O(n r^2) — test use only.
    hq: (r,) one projected query.  Returns (n,) for the static row bound n.
    """
    level_lm = [
        jnp.log(jnp.maximum(
            gram_set_mass(kernel, stats.levels_z[lvl],
                          stats.levels_cnt[lvl], hq), 1e-30))
        for lvl in range(stats.depth + 1)]
    # Within-leaf conditionals.
    scores = kernel.of_dot(jnp.einsum("lbr,r->lb", stats.wq, hq))
    ids = (jnp.arange(stats.num_leaves)[:, None] * stats.leaf_size
           + jnp.arange(stats.leaf_size)[None, :])
    scores = jnp.where(ids < stats.n_valid, scores, 0.0)
    logit = jnp.where(scores > 0, jnp.log(jnp.maximum(scores, 1e-30)),
                      -jnp.inf)
    return _all_class_from_levels(level_lm, logit, stats.n)


# --- feature-sum hierarchy (positive RFF / exp kernel; DESIGN.md §2.7) -------
#
# The quadratic hierarchy realizes the paper's summary statistic z(C) as a
# Gram MATRIX because the degree-2 feature space factors that way.  For the
# exp kernel the feature space is the explicit positive-RFF map phi: R^d ->
# R^D (kernel_fns.rff_phi), and z(C) is literally what eq. 8 says it is:
#
#     z(C) = sum_{j in C} phi(w_j)        (nodes, D) per level
#     <phi(h), z(C)>  ~  sum_{j in C} exp(<h, w_j> / tau)
#
# so every level-mass evaluation is ONE matmul of the query features against
# the level's feature-sum table, and the SAME level-synchronous descent,
# heap packing, and sparse path refresh apply verbatim.  Within a sampled
# leaf the classes are scored with the EXACT exp kernel (log score =
# <h, w>/tau — no features, no exp/overflow), so the reported log-q is the
# exact log-probability of the draw under the hierarchy's distribution; the
# RFF approximation only shapes q at the node level, never the correctness
# of the eq. 2 estimator.
#
# Log-domain normalization: features are built as exp(log phi - logshift)
# with a build-time shift (rff_logshift_bound) and queries as
# exp(log phi - max_k), so nothing overflows; both shifts scale all masses
# of a level uniformly and cancel in eq. 9's branch probabilities.


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class FeatureStats:
    """Per-level positive-RFF feature sums + the raw sampling table.

    levels_f:  tuple over levels root..leaf of (nodes_l, D) fp32 NON-NEGATIVE
               feature sums z(C) = sum_{j in C} phi(w_j) (eq. 8's summary
               statistic, materialized — DESIGN.md §2.7); level l of the full
               binary tree holds 2^l nodes.
    wq:        (num_leaves, leaf_size, d) fp32 RAW class embeddings (no
               projection — the exact exp-kernel leaf scores and therefore
               the reported log-q need original-space dots; zero rows for
               padding and rows at/after ``n_valid``).
    logshift:  () fp32 log-domain shift baked into every feature in
               ``levels_f`` (common to all nodes, cancels in sampling).
               ``update_feature_rows`` must reuse it so deltas stay on the
               same scale.
    n_valid:   scalar int32 — number of real classes (runtime-masked pads).
    n:         static row-count bound (table size at trace time).
    """

    levels_f: tuple[Array, ...]
    wq: Array
    logshift: Array
    n_valid: Array
    n: int = dataclasses.field(metadata=dict(static=True))

    @property
    def depth(self) -> int:
        return len(self.levels_f) - 1

    @property
    def num_leaves(self) -> int:
        return self.wq.shape[0]

    @property
    def leaf_size(self) -> int:
        return self.wq.shape[1]

    @property
    def n_pad(self) -> int:
        return self.num_leaves * self.leaf_size

    @property
    def feature_dim(self) -> int:
        return self.levels_f[0].shape[-1]


def build_features(w: Array, leaf_size: int, omega: Array, tau: float, *,
                   n_valid: Array | int | None = None,
                   use_kernels: bool | None = None) -> FeatureStats:
    """Build the RFF hierarchy bottom-up: leaf feature sums, pairwise parents.

    w: (n, d) class embeddings (one vocab shard's rows inside the P('model')
    island); omega: (D, d) fixed Gaussian directions (the RFF analogue of the
    JL projection — drawn once, carried like ``proj``).  Cost: one (n, D)
    feature matmul (the ``rff_features`` Pallas kernel fuses it with the
    per-leaf reduction) + O(num_leaves * D) for the upper levels.
    """
    if use_kernels is None:
        use_kernels = jax.default_backend() == "tpu"
    n_rows, _ = w.shape
    if n_valid is None:
        n_valid = n_rows
    n_valid = jnp.asarray(n_valid, jnp.int32)
    wq = w.astype(jnp.float32)
    d = wq.shape[-1]
    leaf_size = next_pow2(leaf_size)
    num_leaves = next_pow2(max(1, -(-n_rows // leaf_size)))
    pad = num_leaves * leaf_size - n_rows
    wq = jnp.pad(wq, ((0, pad), (0, 0)))
    row_ok = jnp.arange(num_leaves * leaf_size) < n_valid
    wq = jnp.where(row_ok[:, None], wq, 0.0)
    # Zero rows still have phi = exp(-logshift) > 0, so padding needs an
    # explicit mask (the Gram build gets this for free from w w^T = 0).
    mask = row_ok.astype(jnp.float32).reshape(num_leaves, leaf_size)
    wq = wq.reshape(num_leaves, leaf_size, d)
    logshift = rff_logshift_bound(wq.reshape(-1, d), omega, tau)

    if use_kernels:
        from repro.kernels import ops
        f_leaf = ops.rff_features(wq, omega, mask, logshift, tau=tau)
    else:
        feats = rff_phi(wq, omega, tau, logshift)  # (L, B, D)
        f_leaf = jnp.einsum("lbk,lb->lk", feats, mask)

    levels_f = [f_leaf]
    while levels_f[0].shape[0] > 1:
        child = levels_f[0]
        levels_f.insert(0, child[0::2] + child[1::2])
    return FeatureStats(tuple(levels_f), wq, logshift, n_valid, n_rows)


def update_feature_rows(stats: FeatureStats, ids: Array, w_new: Array,
                        omega: Array, tau: float) -> FeatureStats:
    """Paper Fig. 1b for the feature hierarchy: scatter Delta phi(w) along
    each leaf->root path after the embeddings of ``ids`` change to ``w_new``.

    ids: (k,) LOCAL class indices; w_new: (k, d).  Cost O(k * D * (d + depth)).
    New features reuse the stats' stored ``logshift`` (a grown row may exceed
    exp(0) = 1 — harmless far below fp32 overflow).  Duplicate ids are NOT
    allowed (undefined order of old-row reads).
    """
    leaf_of = ids // stats.leaf_size
    off = ids % stats.leaf_size
    w32 = w_new.astype(jnp.float32)
    phi_new = rff_phi(w32, omega, tau, stats.logshift)
    phi_old = rff_phi(stats.wq[leaf_of, off], omega, tau, stats.logshift)
    delta = phi_new - phi_old  # (k, D)
    wq = stats.wq.at[leaf_of, off].set(w32)

    depth = stats.depth
    new_f = []
    for lvl in range(depth + 1):
        node_of = leaf_of >> (depth - lvl)
        new_f.append(stats.levels_f[lvl].at[node_of].add(delta))
    return FeatureStats(tuple(new_f), wq, stats.logshift, stats.n_valid,
                        stats.n)


def count_levels(n_valid: Array, num_leaves: int, leaf_size: int,
                 depth: int) -> tuple[Array, ...]:
    """Per-level true class counts root..leaf (pure function of n_valid)."""
    levels = [leaf_counts(n_valid, num_leaves, leaf_size)]
    for _ in range(depth):
        child = levels[0]
        levels.insert(0, child[0::2] + child[1::2])
    return tuple(levels)


def to_feature_heap(stats: FeatureStats) -> tuple[Array, Array]:
    """Pack the feature levels into the flat heap carriage (DESIGN.md §2.5).

    Returns (f_heap: (2L, D), aux_heap: (2L,)).  The f heap is
    ``pack_levels`` of the per-level feature sums — the same layout contract
    as the Gram heap, with trailing shape (D,) instead of (r, r).  The aux
    heap carries the per-node true counts (diagnostics / load telemetry) and
    stores ``logshift`` in the heap's single padding row (the last row, zero
    by the packing contract and owned per shard) so carried statistics can be
    sparsely updated on the same scale they were built."""
    aux = pack_levels(count_levels(stats.n_valid, stats.num_leaves,
                                   stats.leaf_size, stats.depth))
    aux = aux.at[-1].set(stats.logshift)
    return pack_levels(stats.levels_f), aux


def from_feature_heap(f_heap: Array, aux_heap: Array, wq: Array,
                      n_valid: Array, n: int | None = None) -> FeatureStats:
    """Inverse of ``to_feature_heap``: static slices back into level tuples.

    f_heap: (2L, D); aux_heap: (2L,) with logshift in the final padding row;
    wq: (L, leaf, d) — one shard's slices when carried P('model')-sharded."""
    num_leaves = wq.shape[0]
    depth = log2_int(num_leaves)
    assert f_heap.shape[0] == heap_rows(num_leaves), (
        f_heap.shape, num_leaves)
    if n is None:
        n = num_leaves * wq.shape[1]
    return FeatureStats(unpack_levels(f_heap, depth), wq, aux_heap[-1],
                        jnp.asarray(n_valid, jnp.int32), n)


def _query_features(h: Array, omega: Array, tau: float) -> Array:
    """Per-query log-domain-normalized features: (T, d) -> (T, D).

    The per-query max shift is exact (cheap, O(T D)) and cancels in the
    within-query branch probabilities."""
    lphi = rff_log_phi(h, omega, tau)  # (T, D)
    c = jax.lax.stop_gradient(jnp.max(lphi, axis=-1, keepdims=True))
    return jnp.exp(lphi - c)


def leaf_logits_exp(stats: FeatureStats, hq: Array, leaf_idx: Array,
                    tau: float, use_kernels: bool) -> Array:
    """EXACT within-leaf exp-kernel log-scores: log K = <h, w>/tau.

    Works in log domain end to end — no exp, no overflow, no positivity
    floor.  Routed through the ``leaf_scores`` kernel's raw-dot mode when
    ``use_kernels``.  hq: (T, d) raw queries; leaf_idx: (T, m) ->
    (T, m, leaf_size) log scores, padding masked to -inf.
    """
    b = stats.leaf_size
    if use_kernels:
        from repro.kernels import ops
        dots = ops.leaf_dots(hq, stats.wq, leaf_idx)
    else:
        rows = stats.wq[leaf_idx]  # (T, m, B, d)
        dots = jnp.einsum("tmbr,tr->tmb", rows, hq)
    logit = dots / jnp.asarray(tau, jnp.float32)
    ids = leaf_idx[..., None] * b + jnp.arange(b)
    return jnp.where(ids < stats.n_valid, logit, -jnp.inf)


def descend_features(stats: FeatureStats, omega: Array, tau: float,
                     h: Array, keys: Array, *,
                     use_kernels: bool | None = None,
                     dense_cap: int | None = None) -> tuple[Array, Array]:
    """Level-synchronous batched descent over RFF masses (DESIGN.md §2.6/2.7).

    h:    (T, d) RAW queries (feature projection happens here, leaf scoring
          stays in the original space).
    keys: (T, m) PRNG keys, one per draw — the same layout as ``descend``.

    Each level is one (T, D) x (D, nodes) matmul (dense form) or a per-draw
    gather of child feature sums (deep levels); the within-leaf categorical
    uses exact exp-kernel scores.  Returns ids: (T, m) int32 and logq:
    (T, m) exact log sampling probabilities under the hierarchy's
    distribution.
    """
    if use_kernels is None:
        use_kernels = jax.default_backend() == "tpu"
    h = jax.lax.stop_gradient(h.astype(jnp.float32))
    t, m = keys.shape[0], keys.shape[1]
    depth = stats.depth
    if dense_cap is None:
        dense_cap = max(256, 4 * m)
    phi_h = _query_features(h, omega, tau)  # (T, D)
    klev = jax.vmap(jax.vmap(lambda k: jax.random.split(k, depth + 1)))(keys)

    idx = jnp.zeros((t, m), jnp.int32)
    logq = jnp.zeros((t, m), jnp.float32)
    for lvl in range(1, depth + 1):
        f = stats.levels_f[lvl]  # (nodes, D)
        left, right = 2 * idx, 2 * idx + 1
        if f.shape[0] <= dense_cap:
            table = phi_h @ f.T  # (T, nodes)
            mass_l = jnp.take_along_axis(table, left, axis=1)
            mass_r = jnp.take_along_axis(table, right, axis=1)
        else:
            mass_l = jnp.einsum("tmk,tk->tm", f[left], phi_h)
            mass_r = jnp.einsum("tmk,tk->tm", f[right], phi_h)
        # Numerical floor: padding-only subtrees have exactly zero mass.
        p_r = mass_r / jnp.maximum(mass_l + mass_r, 1e-30)
        go_right = jax.vmap(jax.vmap(jax.random.bernoulli))(
            klev[:, :, lvl - 1], p_r)
        idx = jnp.where(go_right, right, left)
        logq = logq + jnp.log(jnp.where(go_right, p_r, 1.0 - p_r))

    logits = leaf_logits_exp(stats, h, idx, tau, use_kernels)
    within = jax.vmap(jax.vmap(jax.random.categorical))(
        klev[:, :, depth], logits)
    log_within = jnp.take_along_axis(
        jax.nn.log_softmax(logits, axis=-1), within[..., None], axis=-1
    )[..., 0]
    ids = idx * stats.leaf_size + within
    return ids.astype(jnp.int32), logq + log_within


def all_class_logq_features(stats: FeatureStats, omega: Array, tau: float,
                            h: Array) -> Array:
    """Exact log-probability the RFF hierarchy assigns to EVERY class.

    The test oracle for the feature-sum sampler: node probabilities from the
    RFF masses, within-leaf conditional from the exact exp kernel — the same
    distribution ``descend_features`` draws from.  O(n D) — test use only.
    h: (d,) one raw query.  Returns (n,) for the static row bound n.
    """
    phi_h = _query_features(h[None], omega, tau)[0]  # (D,)
    level_lm = [
        jnp.log(jnp.maximum(stats.levels_f[lvl] @ phi_h, 1e-30))
        for lvl in range(stats.depth + 1)]
    dots = jnp.einsum("lbr,r->lb", stats.wq, h.astype(jnp.float32))
    logit = dots / jnp.asarray(tau, jnp.float32)
    ids = (jnp.arange(stats.num_leaves)[:, None] * stats.leaf_size
           + jnp.arange(stats.leaf_size)[None, :])
    logit = jnp.where(ids < stats.n_valid, logit, -jnp.inf)
    return _all_class_from_levels(level_lm, logit, stats.n)
