"""Quantized inverted multi-index (MIDX) sampling core (DESIGN.md §2.9).

The third hierarchy backend beside Gram trees and RFF feature heaps (Chen
et al. 2025, "Adaptive Sampled Softmax with Inverted Multi-Index", arXiv
2501.08563 — PAPERS.md): the class table is partitioned into P balanced
posting lists, each list is product-quantized into a PAIR of codewords
(a coarse codebook c1 and a residual codebook c2), and sampling runs in
two stages:

  stage 1   score every list by its QUANTIZED kernel mass
                mass_j = cnt_j * K(<h, c1[a1_j] + c2[a2_j]>)
            — two (K, d) matmuls plus an O(P) gather instead of the
            O(P d^2) Gram contraction of the block sampler: the codebook
            cross-product carries the geometry, the list only carries two
            small integers.  Draw a list from the normalized masses.
  stage 2   score the drawn list's members with the EXACT kernel
            K(<h, w_i>) and draw within (O(L d) per draw).

The reported log-q is the exact composed probability

    logq = log softmax(list masses)[j] + log softmax(within scores)[i]

under the distribution ACTUALLY sampled from, so the eq. 2 correction
stays unbiased no matter how coarse the codebooks are — quantization
error moves q away from the kernel target (bias-of-q, like staleness,
DESIGN.md §2.4) but never breaks exactness.  Support is total: every
valid class lives in a list with cnt > 0 and kernel scores are >= 1, so
q > 0 everywhere (the PR-3 exactness contract).

Layout invariants (what makes every shape static under jit/shard_map):

  * lists are BALANCED: ``pc_bisect_perm`` sorts rows level by level
    along principal directions and splits in half, so all P = 2^depth
    lists hold exactly L rows and padding stays a contiguous suffix.
    Per-list valid counts are then closed-form:
    cnt_j = clip(n_valid - j L, 0, L).
  * the codebooks quantize LIST CENTROIDS (the mean of each list's valid
    rows) with a deterministic fixed-iteration Lloyd's k-means — no PRNG,
    so the sampler carries no constants and a refresh is a pure function
    of the head table.
  * ``perm`` maps packed position -> original local row id; sampling and
    the all-class oracle translate through it, exactly like the serving
    index (serve/retrieval.py).

The same structure exports as the serving-side
``serve.quantized_index.QuantizedRetrievalIndex`` (int8 rows, beam
search over posting lists) — one index for training-time sampling and
decode-time retrieval.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core.kernel_fns import SamplingKernel
from repro.utils.misc import log2_int, next_pow2

Array = jax.Array


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class MidxStats:
    """Statistics of the two-level quantized index.

    c1:      (K1, d) fp32 coarse codebook (k-means centroids of the list
             centroids).
    c2:      (K2, d) fp32 residual codebook (k-means of centroid - c1
             residuals); a single zero row when built with codebooks=1.
    codes:   (P, 2) int32 codeword PAIR (a1, a2) per posting list — the
             cross-product cell the list quantizes to.
    cnt:     (P,) fp32 valid rows per list (padding is a contiguous
             suffix, so this is closed-form in n_valid).
    perm:    (P*L,) int32 packed position -> original local row id.
    wq:      (P, L, d) fp32 member rows in packed order (padding zeroed)
             — stage 2's exact scoring table.
    n_valid: () int32 — number of real classes; dynamic so sharded tables
             whose last shard carries padding keep zero mass on pads.
    """

    c1: Array
    c2: Array
    codes: Array
    cnt: Array
    perm: Array
    wq: Array
    n_valid: Array

    @property
    def num_lists(self) -> int:
        return self.wq.shape[0]

    @property
    def list_size(self) -> int:
        return self.wq.shape[1]

    @property
    def n_pad(self) -> int:
        return self.num_lists * self.list_size


def list_dims(n: int, d: int, list_size: int | None = None
              ) -> tuple[int, int]:
    """ONE formula for (num_lists P, list size L) — shared by ``build``
    and ``MIDXSampler.state_shapes``; a drift between them is a
    declared-vs-built shape mismatch that only surfaces at shard_map
    trace time."""
    leaf = next_pow2(max(2, min(n, list_size if list_size else d)))
    return next_pow2(max(1, -(-n // leaf))), leaf


def pc_bisect_perm(w: Array, n_valid: Array | int, depth: int,
                   iters: int = 8) -> Array:
    """Balanced PC-bisection co-clustering permutation.

    w: (n_pad, d) with n_pad = 2^depth * leaf_size.  Level by level, each
    node's rows are sorted by their projection onto the node's top principal
    direction (a few power iterations on the uncentered second moment) and
    split in half — after ``depth`` levels, each leaf holds similar
    embeddings.  Rows at/after ``n_valid`` sort with key +inf, so padding
    stays a contiguous suffix (the invariant the closed-form per-list
    counts and runtime masking rely on).  Returns (n_pad,) int32: packed
    position -> original row.  O(depth * n * (d + iters * d)).

    Canonical home of the bisection used by BOTH the serving index
    (serve/retrieval.py re-exports it) and the midx posting lists — one
    clustering, two consumers."""
    n_pad, d = w.shape
    w32 = w.astype(jnp.float32)
    perm = jnp.arange(n_pad, dtype=jnp.int32)
    for lvl in range(depth):
        nb = 1 << lvl
        bs = n_pad >> lvl
        blocks = w32[perm].reshape(nb, bs, d)
        v = jnp.sum(blocks, axis=1)
        v = v / (jnp.linalg.norm(v, axis=-1, keepdims=True) + 1e-9)
        for _ in range(iters):
            u = jnp.einsum("nbd,nd->nb", blocks, v)
            v = jnp.einsum("nbd,nb->nd", blocks, u)
            v = v / (jnp.linalg.norm(v, axis=-1, keepdims=True) + 1e-9)
        key = jnp.einsum("nbd,nd->nb", blocks, v)
        key = jnp.where(perm.reshape(nb, bs) < n_valid, key, jnp.inf)
        order = jnp.argsort(key, axis=1)
        perm = jnp.take_along_axis(perm.reshape(nb, bs), order,
                                   axis=1).reshape(-1)
    return perm


def kmeans(x: Array, k: int, iters: int = 8,
           mask: Array | None = None) -> tuple[Array, Array]:
    """Deterministic fixed-iteration Lloyd's k-means.

    x: (n, d) points; mask: (n,) bool — points excluded from centroid
    updates (their returned assignment is arbitrary).  Init is strided
    over the (spatially pre-sorted, post-bisection) point order — no PRNG
    key, so codebooks are a pure function of the table and the carried
    state needs no constants.  Empty clusters keep their previous
    centroid.  Returns (centroids (k, d) fp32, assignments (n,) int32)."""
    n, _ = x.shape
    x32 = x.astype(jnp.float32)
    if mask is None:
        mask = jnp.ones((n,), bool)
    c = x32[(jnp.arange(k) * n) // k]

    def assign(c_):
        d2 = (jnp.sum(x32 * x32, axis=1, keepdims=True)
              - 2.0 * x32 @ c_.T + jnp.sum(c_ * c_, axis=1)[None, :])
        return jnp.argmin(d2, axis=1)

    for _ in range(iters):
        a = assign(c)
        hot = ((a[:, None] == jnp.arange(k)[None, :])
               & mask[:, None]).astype(jnp.float32)
        csum = hot.T @ x32
        ccnt = jnp.sum(hot, axis=0)
        c = jnp.where(ccnt[:, None] > 0, csum / jnp.maximum(ccnt, 1)[:, None],
                      c)
    return c, assign(c).astype(jnp.int32)


def build(w: Array, *, codewords: int, codebooks: int = 2,
          list_size: int | None = None,
          n_valid: Array | int | None = None,
          kmeans_iters: int = 8) -> MidxStats:
    """(Re)build the full index from a class table — the refresh step.

    w: (n, d) local class embeddings (a head shard inside the refresh
    island, or the whole table unsharded).  Cost: one bisection pass
    O(log P * n d) + two small k-means O(iters * P * K * d) — far below a
    fwd/bwd, same cadence class as a Gram rebuild."""
    n_rows, d = w.shape
    if n_valid is None:
        n_valid = jnp.asarray(n_rows, jnp.int32)
    num_lists, leaf = list_dims(n_rows, d, list_size)
    n_pad = num_lists * leaf
    w_pad = jnp.pad(w.astype(jnp.float32), ((0, n_pad - n_rows), (0, 0)))
    row_ok = jnp.arange(n_pad) < n_valid
    w_pad = jnp.where(row_ok[:, None], w_pad, 0.0)
    perm = pc_bisect_perm(w_pad, n_valid, log2_int(num_lists))
    rows = w_pad[perm].reshape(num_lists, leaf, d)
    # Balanced lists + contiguous padding suffix -> closed-form counts.
    cnt = jnp.clip(n_valid - jnp.arange(num_lists) * leaf, 0,
                   leaf).astype(jnp.float32)
    live = cnt > 0
    mu = jnp.sum(rows, axis=1) / jnp.maximum(cnt, 1.0)[:, None]
    c1, a1 = kmeans(mu, codewords, kmeans_iters, live)
    if codebooks == 2:
        c2, a2 = kmeans(mu - c1[a1], codewords, kmeans_iters, live)
    else:
        c2 = jnp.zeros((1, d), jnp.float32)
        a2 = jnp.zeros((num_lists,), jnp.int32)
    codes = jnp.stack([a1, a2], axis=1).astype(jnp.int32)
    return MidxStats(c1=c1, c2=c2, codes=codes, cnt=cnt, perm=perm,
                     wq=rows, n_valid=jnp.asarray(n_valid, jnp.int32))


# --- scoring -----------------------------------------------------------------


def quantized_dots(stats: MidxStats, h: Array) -> Array:
    """Stage-1 quantized logits for a batch of queries: (T, P).

    t[j] = <h, c1[a1_j] + c2[a2_j]> via TWO (T, K) codebook matmuls and an
    O(T P) gather over the codeword-pair grid — never a (T, P) @ d
    contraction, which is the sub-linear MIDX win."""
    hc1 = h.astype(jnp.float32) @ stats.c1.T    # (T, K1)
    hc2 = h.astype(jnp.float32) @ stats.c2.T    # (T, K2)
    return hc1[:, stats.codes[:, 0]] + hc2[:, stats.codes[:, 1]]


def list_log_masses(stats: MidxStats, kernel: SamplingKernel, h: Array,
                    use_kernels: bool | None = None) -> Array:
    """log of the stage-1 sampling masses for every list: (T, P).

    mass_j = cnt_j * K(t_j) with the QUANTIZED logit t_j; empty lists get
    -inf.  ``use_kernels`` routes the fused pair-mass computation through
    the ``midx_list_masses`` Pallas kernel (TPU default)."""
    if use_kernels is None:
        use_kernels = jax.default_backend() == "tpu"
    if use_kernels:
        from repro.kernels import ops
        mass = ops.midx_list_masses(h.astype(jnp.float32), stats.c1,
                                    stats.c2, stats.codes, stats.cnt,
                                    alpha=kernel.alpha)
    else:
        mass = stats.cnt[None, :] * kernel.of_dot(quantized_dots(stats, h))
    return jnp.where(mass > 0, jnp.log(jnp.maximum(mass, 1e-30)), -jnp.inf)


def member_log_scores(stats: MidxStats, kernel: SamplingKernel, h: Array,
                      lists: Array,
                      use_kernels: bool | None = None) -> Array:
    """Stage-2 EXACT within-list kernel log-scores.

    h: (T, d); lists: (T, m) drawn list ids -> (T, m, L) log K(<h, w_i>)
    with padding slots at -inf.  The (T*m, L, d) gathered-row dot + kernel
    hot loop routes through the ``leaf_scores`` Pallas kernel, which
    fetches each drawn list by block index (no (T, m, L, d) gather)."""
    if use_kernels is None:
        use_kernels = jax.default_backend() == "tpu"
    leaf = stats.list_size
    h32 = h.astype(jnp.float32)
    if use_kernels:
        from repro.kernels import ops
        scores = ops.leaf_scores(h32, stats.wq, lists, alpha=kernel.alpha)
    else:
        rows = stats.wq[lists]                   # (T, m, L, d)
        scores = kernel.of_dot(jnp.einsum("tmld,td->tml", rows, h32))
    pos = lists[..., None] * leaf + jnp.arange(leaf)    # packed positions
    scores = jnp.where(pos < stats.n_valid, scores, 0.0)
    return jnp.where(scores > 0, jnp.log(jnp.maximum(scores, 1e-30)),
                     -jnp.inf)


# --- sampling ----------------------------------------------------------------


def sample_batch(stats: MidxStats, kernel: SamplingKernel, h: Array, m: int,
                 key: Array,
                 use_kernels: bool | None = None) -> tuple[Array, Array]:
    """Natively batched two-stage draw: h (T, d) -> (ids (T, m) int32
    ORIGINAL local class ids, logq (T, m) exact composed log-probs)."""
    from repro.core.blocks import categorical_rows

    k_list, k_in = jax.random.split(key)
    list_logits = list_log_masses(stats, kernel, h, use_kernels)  # (T, P)
    log_p_list = jax.nn.log_softmax(list_logits, axis=-1)
    lists = categorical_rows(k_list, list_logits, m)              # (T, m)
    within_logits = member_log_scores(stats, kernel, h, lists, use_kernels)
    within = jax.random.categorical(k_in, within_logits, axis=-1)  # (T, m)
    log_p_within = jnp.take_along_axis(
        jax.nn.log_softmax(within_logits, axis=-1), within[..., None],
        axis=-1)[..., 0]
    packed = lists * stats.list_size + within
    ids = stats.perm[packed]
    logq = jnp.take_along_axis(log_p_list, lists, axis=1) + log_p_within
    return ids.astype(jnp.int32), logq


def sample(stats: MidxStats, kernel: SamplingKernel, h: Array, m: int,
           key: Array,
           use_kernels: bool | None = None) -> tuple[Array, Array]:
    """Single-query form: h (d,) -> (ids (m,), logq (m,))."""
    ids, logq = sample_batch(stats, kernel, h[None, :], m, key, use_kernels)
    return ids[0], logq[0]


def all_class_logq(stats: MidxStats, kernel: SamplingKernel,
                   h: Array) -> Array:
    """Exact log-probability of EVERY original local class id under the
    two-stage sampler (test oracle + the midx-oracle twin, O(n d)).

    Returns (n_pad,) indexed by ORIGINAL row id; padding rows are -inf."""
    list_logits = list_log_masses(stats, kernel, h[None, :],
                                  use_kernels=False)[0]          # (P,)
    log_p_list = jax.nn.log_softmax(list_logits)
    scores = kernel.of_dot(jnp.einsum("pld,d->pl", stats.wq,
                                      h.astype(jnp.float32)))
    pos = (jnp.arange(stats.num_lists)[:, None] * stats.list_size
           + jnp.arange(stats.list_size)[None, :])
    scores = jnp.where(pos < stats.n_valid, scores, 0.0)
    logit = jnp.where(scores > 0, jnp.log(jnp.maximum(scores, 1e-30)),
                      -jnp.inf)
    # Empty lists are all -inf rows; mask BEFORE log_softmax can NaN them.
    log_within = jnp.where(
        stats.cnt[:, None] > 0,
        jax.nn.log_softmax(jnp.where(stats.cnt[:, None] > 0, logit, 0.0),
                           axis=-1),
        -jnp.inf)
    log_within = jnp.where(logit == -jnp.inf, -jnp.inf, log_within)
    packed_logq = (log_p_list[:, None] + log_within).reshape(-1)
    return jnp.full((stats.n_pad,), -jnp.inf).at[stats.perm].set(packed_logq)
