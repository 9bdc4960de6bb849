"""Vocab-sharded kernel sampling + sampled-softmax loss (DESIGN.md §2.5).

The class-embedding table (LM head) is sharded over the tensor-parallel mesh
axis.  The paper's tree maps onto hardware: the top log2(tp) levels of the
divide & conquer hierarchy ARE the shard index.  We use the *stratified* form:
every shard draws m/tp negatives from its local kernel distribution, and the
expected-occurrence correction uses the exact global probabilities
q~_i = q_local(i) / tp — so E[count_i] = m * q~_i and eq. 2 applies verbatim.
Stratification removes all cross-shard sampling traffic and is a
variance-reduction over one global multinomial (documented beyond-paper
change; see EXPERIMENTS.md §Perf).

Two-stage (tapas) samplers use a different pattern — "sample → all-gather
pool → per-example re-score" (DESIGN.md §2.8): every shard draws pool/tp
candidates from its LOCAL base distribution, the pool's ids, inclusion
log-probabilities and embedding rows are all-gathered across the model axis
(the one place a (pool, d) tensor crosses shards; its transpose is the
gradient's psum_scatter back to the owning shard), every shard re-scores
the replicated pool against its tokens, and each shard then draws m/tp
slots from the SAME composed global q — so the eq. 2 correction uses
``logq + log m`` with no stratification factor.

All functions here are written to run INSIDE ``jax.shard_map`` with a named
tensor-parallel axis; apart from the tapas pool gather they only communicate
through psum/pmax of scalars or (T,)-vectors — never through gathered
logits.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.estimators import Estimator
from repro.core.sampled_softmax import transform_logits
from repro.core.samplers import (
    Sampler,
    categorical_rows,
    pool_log_inclusion,
)
from repro.kernels import ops

Array = jax.Array

# Every collective here takes ``axis_name: AxisName`` — a single mesh axis
# name or a TUPLE of names (multi-host promotion, DESIGN.md §7).  psum /
# pmax / pmin / all_gather accept tuples natively in jax; the two places
# that need composition by hand are the shard count (``axis_size``) and the
# row-major shard index (``axis_index``), so vocab-parallel heads laid out
# over e.g. ("host", "model") keep exact offsets and key folding.  The
# dryrun HLO gate asserts the resulting collective ops/shapes per estimator.
AxisName = Any  # str | tuple[str, ...]


def _axis_names(axis_name: AxisName) -> tuple[str, ...]:
    return (axis_name,) if isinstance(axis_name, str) else tuple(axis_name)


def axis_size(axis_name: AxisName) -> int:
    """Static total shard count across one or several named axes."""
    return int(lax.psum(1, axis_name))


def axis_index(axis_name: AxisName) -> Array:
    """Row-major composed shard index across one or several named axes.

    Matches the device order of ``lax.all_gather(..., axis_name)`` with the
    same tuple, so gathered-pool order and vocab offsets stay consistent."""
    names = _axis_names(axis_name)
    idx = lax.axis_index(names[0])
    for a in names[1:]:
        idx = idx * int(lax.psum(1, a)) + lax.axis_index(a)
    return idx


def local_vocab_offset(n_local: int, axis_name: AxisName) -> Array:
    return axis_index(axis_name) * n_local


def local_labels(w_local: Array, labels: Array, axis_name: AxisName) -> Array:
    """Global label ids -> this shard's local row ids (may be out of range
    on non-owner shards — only ever compared against LOCAL negative ids,
    which are in range, so a non-owner shard can never match).  The one
    implementation of the accidental-hit collision rule's label side."""
    return labels - local_vocab_offset(w_local.shape[0], axis_name)


def sharded_negative_sample(sampler: Sampler, state_local: Any, h: Array,
                            m: int, key: Array, axis_name: AxisName
                            ) -> tuple[Array, Array]:
    """Stratified sampling: each shard draws m/tp from its local distribution.

    Returns LOCAL ids (.., m_local) and the GLOBAL log q~ for them.
    """
    tp = axis_size(axis_name)
    assert m % tp == 0, f"m={m} must divide by the TP degree {tp}"
    m_local = m // tp
    key_local = jax.random.fold_in(key, axis_index(axis_name))
    with jax.named_scope("sampler_draw"):
        ids, logq_local = sampler.sample_batch(state_local, h, m_local,
                                               key_local)
    # q~_i = q_local(i) / tp  (global stratified probability)
    return ids, logq_local - jnp.log(jnp.asarray(tp, jnp.float32))


def _positive_logit(w_local: Array, h: Array, labels: Array, axis_name: AxisName,
                    bias_local: Array | None = None) -> Array:
    """Logit of each example's positive class, summed across shards.

    Exactly one shard owns each label; the others contribute zero."""
    n_local = w_local.shape[0]
    off = local_vocab_offset(n_local, axis_name)
    local = (labels >= off) & (labels < off + n_local)
    idx = jnp.clip(labels - off, 0, n_local - 1)
    w_pos = w_local[idx].astype(jnp.float32)  # (T, d)
    logit = jnp.einsum("td,td->t", h.astype(jnp.float32), w_pos)
    if bias_local is not None:
        logit = logit + bias_local[idx]
    logit = jnp.where(local, logit, 0.0)
    return lax.psum(logit, axis_name)


def sharded_sampled_softmax_loss(
    w_local: Array, h: Array, labels: Array, sampler: Sampler,
    state_local: Any, m: int, key: Array, *, axis_name: AxisName,
    abs_mode: bool = False, bias_local: Array | None = None,
    mask_accidental_hits: bool = True, impl: str = "auto") -> Array:
    """Sampled softmax over a vocab-sharded head, negatives sampled in place.

    w_local: (n/tp, d) local head shard.  h: (T, d) hidden states (replicated
    across the TP axis).  labels: (T,) GLOBAL class ids.  m: total negatives
    across shards (must divide by tp).  Returns per-example loss (T,).

    A negative that collided with the example's label (possible on exactly
    the shard owning the label row) is masked to zero mass after the eq. 2
    correction unless ``mask_accidental_hits=False`` (see
    core/sampled_softmax.py's module docstring for why).  Per-example
    negatives route the local corrected logsumexp through the fused head
    kernel (``kernels.ops.fused_head_lse`` — no (T, m/tp, d) gather in HBM)
    unless ``impl="einsum"``; the global combine is unchanged.

    No tensor of size (T, n) is ever materialized; cross-shard communication
    is two psums of (T,)-vectors and one pmax.
    """
    neg_ids, logq = sharded_negative_sample(sampler, state_local, h, m, key,
                                            axis_name)
    with jax.named_scope("head_loss"):
        h32 = h.astype(jnp.float32)
        pos = transform_logits(
            _positive_logit(w_local, h, labels, axis_name, bias_local),
            abs_mode)
        # local ids collide with the label iff label - shard offset matches.
        labels_local = local_labels(w_local, labels, axis_name)
        log_m = jnp.log(jnp.asarray(m, jnp.float32))

        if neg_ids.ndim == 2 and impl != "einsum":
            # eq. 2, stratified: E[count] = m_local*q_local = m*q~.
            corr = (logq + log_m).astype(jnp.float32)
            if mask_accidental_hits:
                corr = jnp.where(neg_ids == labels_local[:, None],
                                 ops.MASK_CORR, corr)
            biasg = bias_local[neg_ids] if bias_local is not None else None
            # per-token logsumexp over this shard's corrected negatives only.
            lse_local = ops.fused_head_lse(
                w_local, h32, neg_ids, corr, biasg, abs_mode=abs_mode,
                impl="auto" if impl == "fused" else impl)
            c = lax.pmax(jnp.maximum(lax.stop_gradient(lse_local),
                                     lax.stop_gradient(pos)), axis_name)
            sumexp = (lax.psum(jnp.exp(lse_local - c), axis_name)
                      + jnp.exp(pos - c))
            return jnp.log(sumexp) + c - pos

        o_adj = _corrected_neg_logits(
            w_local, h32, labels, neg_ids, logq, m, axis_name=axis_name,
            abs_mode=abs_mode, bias_local=bias_local,
            mask_hits=mask_accidental_hits)

        # Numerically stable global logsumexp over [pos, all shards'
        # negatives].  The shift constant needs no gradient (it cancels
        # analytically).
        local_max = lax.stop_gradient(jnp.max(o_adj, axis=-1))
        c = lax.pmax(jnp.maximum(local_max, lax.stop_gradient(pos)),
                     axis_name)
        sumexp_local = jnp.sum(jnp.exp(o_adj - c[:, None]), axis=-1)
        sumexp = lax.psum(sumexp_local, axis_name) + jnp.exp(pos - c)
        return jnp.log(sumexp) + c - pos


def _corrected_neg_logits(w_local: Array, h32: Array, labels: Array,
                          neg_ids: Array, logq: Array, m: int, *,
                          axis_name: AxisName, abs_mode: bool,
                          bias_local: Array | None,
                          mask_hits: bool) -> Array:
    """Shard-local eq.-2-corrected negative logits (T, m_local).

    The one implementation of gather + logit + bias + |.| transform +
    ``o - logq - ln m`` + accidental-hit masking shared by every estimator's
    einsum path (a fix to the correction or mask semantics lands here once).
    Masked slots are -inf: zero mass in the softmax partition AND zero
    value/gradient under softplus (logistic family).
    """
    w_neg = w_local[neg_ids].astype(jnp.float32)
    if neg_ids.ndim == 1:  # batch-shared negatives: (m_local, d)
        o_neg = jnp.einsum("td,md->tm", h32, w_neg)
        logq_b = jnp.broadcast_to(logq[None, :], o_neg.shape)
        nb = neg_ids[None, :]
    else:  # per-example negatives: (T, m_local, d)
        o_neg = jnp.einsum("td,tmd->tm", h32, w_neg)
        logq_b = logq
        nb = neg_ids
    if bias_local is not None:
        o_neg = o_neg + bias_local[nb]
    # eq. 2 with stratified correction: E[count] = m_local * q_local = m * q~.
    o_adj = (transform_logits(o_neg, abs_mode) - logq_b
             - jnp.log(jnp.asarray(m, jnp.float32)))
    if mask_hits:
        labels_local = local_labels(w_local, labels, axis_name)
        o_adj = jnp.where(nb == labels_local[:, None], -jnp.inf, o_adj)
    return o_adj


def sharded_tapas_negatives(sampler: Sampler, state_local: Any,
                            w_local: Array, h: Array, m: int, key: Array, *,
                            axis_name: AxisName,
                            bias_local: Array | None = None
                            ) -> tuple[Array, Array, Array, Array]:
    """The two-pass "sample → all-gather pool → re-score" pattern
    (DESIGN.md §2.8), shard-local view.

    Pass 1: this shard draws pool/tp candidates from its LOCAL base
    distribution (batch-shared bases use their native batch-summed draw,
    per-example bases the mean query — any fixed pool distribution keeps
    the composed q exact).  A class's global pool-inclusion probability is
    its inclusion on the one shard that owns it, so ``pool_log_inclusion``
    applies to the LOCAL per-draw log q1 with pool/tp draws — no /tp.

    All-gather (model axis): pool global ids, log pi, embedding rows
    (+ bias) — shard order = gather order, which the single-host
    reconstruction in tests/dist_scripts/check_tapas_train.py replays.

    Pass 2: re-score the replicated pool (one (T, pool) matmul — the pool
    is shared, so there is no (T, m, d) gather to avoid), then draw m/tp
    slots per shard from the SAME composed global q (keys folded by shard
    index), so the tp * m/tp = m draws are i.i.d. from q and the eq. 2
    correction is ``logq + log m`` with no stratification factor.

    Returns (pool_gids (pool,), o (T, pool) raw pool logits CARRYING
    GRADIENT through the embedding all-gather, slots (T, m/tp) pool slot
    indices, logq (T, m/tp) composed pool x resample log-probability,
    stop-gradiented).
    """
    tp = axis_size(axis_name)
    assert m % tp == 0, f"m={m} must divide by the TP degree {tp}"
    pool = sampler.pool
    assert pool % tp == 0, f"pool={pool} must divide by the TP degree {tp}"
    m_local, p_local = m // tp, pool // tp
    k_pool, k_draw = jax.random.split(key)
    k_pool_local = jax.random.fold_in(k_pool, axis_index(axis_name))
    base_rt = state_local["base"]
    with jax.named_scope("sampler_draw"):
        if sampler.base.shares_negatives:
            pids, lq1 = sampler.base.sample_batch(base_rt, h, p_local,
                                                  k_pool_local)
        else:
            pids, lq1 = sampler.base.sample(base_rt, jnp.mean(h, axis=0),
                                            p_local, k_pool_local)
    logpi_l = pool_log_inclusion(lq1, p_local)
    gids_l = pids + local_vocab_offset(w_local.shape[0], axis_name)
    pool_w = lax.all_gather(w_local[pids], axis_name, axis=0, tiled=True)
    pool_gids = lax.all_gather(gids_l, axis_name, axis=0, tiled=True)
    pool_logpi = lax.all_gather(logpi_l, axis_name, axis=0, tiled=True)
    o = jnp.einsum("td,pd->tp", h.astype(jnp.float32),
                   pool_w.astype(jnp.float32))
    if bias_local is not None:
        o = o + lax.all_gather(bias_local[pids], axis_name, axis=0,
                               tiled=True)[None, :]
    counts = jnp.zeros((w_local.shape[0] * tp,), jnp.int32
                       ).at[pool_gids].add(1)
    mult = counts[pool_gids]          # multiplicity via O(P) scatter, not P^2
    o_sg = lax.stop_gradient(o) / sampler.tau
    s = o_sg - (pool_logpi + jnp.log(mult.astype(jnp.float32)))[None, :]
    k_shard = jax.random.fold_in(k_draw, axis_index(axis_name))
    with jax.named_scope("sampler_draw"):
        slots = categorical_rows(k_shard, s, m_local)
    logq = (jnp.take_along_axis(o_sg, slots, axis=1)
            - jax.nn.logsumexp(s, axis=-1)[:, None])
    return pool_gids, o, slots, logq


def _sharded_tapas_loss(
    est: Estimator, w_local: Array, h: Array, labels: Array,
    sampler: Sampler, state_local: Any, m: int, key: Array, *,
    axis_name: AxisName, abs_mode: bool, bias_local: Array | None) -> Array:
    """Estimator loss over tapas negatives (per-example (T,)).

    The m/tp per-shard draws come from one GLOBAL q, so the corrected
    logits are ``o - logq - ln m`` on every shard and the estimators
    combine exactly as in the stratified path: pmax + psum logsumexp for
    sampled-softmax, a psum of softplus sums for the logistic family."""
    if est.name not in ("sampled-softmax", "nce", "sampled-logistic"):
        raise NotImplementedError(
            f"estimator '{est.name}' has no sharded tapas routing; add it "
            "to _sharded_tapas_loss")
    pos = transform_logits(
        _positive_logit(w_local, h, labels, axis_name, bias_local), abs_mode)
    pool_gids, o, slots, logq = sharded_tapas_negatives(
        sampler, state_local, w_local, h, m, key, axis_name=axis_name,
        bias_local=bias_local)
    o_sel = jnp.take_along_axis(o, slots, axis=1)          # (T, m/tp), grads
    o_adj = (transform_logits(o_sel, abs_mode) - logq
             - jnp.log(jnp.asarray(m, jnp.float32)))
    hit = pool_gids[slots] == labels[:, None]
    if est.masks_hits:
        # -inf: zero mass in the partition AND zero softplus value/grad.
        o_adj = jnp.where(hit, -jnp.inf, o_adj)
    if est.name == "sampled-softmax":
        local_max = lax.stop_gradient(jnp.max(o_adj, axis=-1))
        c = lax.pmax(jnp.maximum(local_max, lax.stop_gradient(pos)),
                     axis_name)
        sumexp = (lax.psum(jnp.sum(jnp.exp(o_adj - c[:, None]), axis=-1),
                           axis_name) + jnp.exp(pos - c))
        return jnp.log(sumexp) + c - pos
    neg_sum = lax.psum(jnp.sum(jax.nn.softplus(o_adj), axis=-1), axis_name)
    return jax.nn.softplus(-pos) + neg_sum


def sharded_estimator_loss(
    est: Estimator, w_local: Array, h: Array, labels: Array,
    sampler: Sampler, state_local: Any, m: int, key: Array, *,
    axis_name: AxisName, abs_mode: bool = False,
    bias_local: Array | None = None, impl: str = "auto") -> Array:
    """Estimator-routed vocab-sharded loss (DESIGN.md §6): the shard-local
    sampling + communication pattern each estimator needs, behind one call.

      sampled-softmax  -> ``sharded_sampled_softmax_loss`` (global corrected
                          logsumexp: one pmax + two psums of (T,)); the
                          fused Pallas head keeps the per-example path.
      nce / sampled-logistic -> the binary-logistic sum decomposes PER SHARD
                          (no global normalizer), so the only communication
                          is the positive-logit psum plus one psum of the
                          (T,) per-shard softplus sums.
      full             -> ``sharded_full_softmax_loss`` (dense oracle).

    Two-stage samplers (``sampler.two_stage``) divert to the tapas pool
    pattern (``_sharded_tapas_loss``) before the per-estimator routing —
    their negatives come from the all-gathered pool, not stratified
    per-shard draws.

    Same contract as sharded_sampled_softmax_loss: returns per-example (T,)
    losses, negatives drawn stratified m/tp per shard with exact global
    q~ = q_local / tp (module docstring).
    """
    if not est.needs_sampling:
        return sharded_full_softmax_loss(
            w_local, h, labels, axis_name=axis_name, abs_mode=abs_mode,
            bias_local=bias_local)
    if sampler.two_stage:
        return _sharded_tapas_loss(
            est, w_local, h, labels, sampler, state_local, m, key,
            axis_name=axis_name, abs_mode=abs_mode, bias_local=bias_local)
    if est.name == "sampled-softmax":
        return sharded_sampled_softmax_loss(
            w_local, h, labels, sampler, state_local, m, key,
            axis_name=axis_name, abs_mode=abs_mode, bias_local=bias_local,
            impl=impl)

    # Corrected-logistic family: additive across shards.  Explicit
    # allowlist — a future estimator with its own loss() must grow its own
    # sharded routing here, not silently inherit the logistic formula
    # (mesh and mesh=None runs would diverge without an error).
    if est.name not in ("nce", "sampled-logistic"):
        raise NotImplementedError(
            f"estimator '{est.name}' has no sharded routing; add it to "
            "sharded_estimator_loss")
    neg_ids, logq = sharded_negative_sample(sampler, state_local, h, m, key,
                                            axis_name)
    pos = transform_logits(
        _positive_logit(w_local, h, labels, axis_name, bias_local), abs_mode)
    o_adj = _corrected_neg_logits(
        w_local, h.astype(jnp.float32), labels, neg_ids, logq, m,
        axis_name=axis_name, abs_mode=abs_mode, bias_local=bias_local,
        mask_hits=est.masks_hits)
    neg_sum = lax.psum(jnp.sum(jax.nn.softplus(o_adj), axis=-1), axis_name)
    return jax.nn.softplus(-pos) + neg_sum


def sharded_full_softmax_loss(w_local: Array, h: Array, labels: Array, *,
                              axis_name: AxisName, abs_mode: bool = False,
                              bias_local: Array | None = None) -> Array:
    """Reference/eval loss: full softmax over the sharded vocab.

    Materializes only (T, n/tp) logits per shard."""
    logits = jnp.einsum("td,nd->tn", h.astype(jnp.float32),
                        w_local.astype(jnp.float32))
    if bias_local is not None:
        logits = logits + bias_local[None, :]
    logits = transform_logits(logits, abs_mode)
    local_max = lax.stop_gradient(jnp.max(logits, axis=-1))
    c = lax.pmax(local_max, axis_name)
    sumexp = lax.psum(jnp.sum(jnp.exp(logits - c[:, None]), axis=-1),
                      axis_name)
    pos = _positive_logit(w_local, h, labels, axis_name, bias_local)
    return jnp.log(sumexp) + c - transform_logits(pos, abs_mode)


def sharded_logits_argmax(w_local: Array, h: Array, *, axis_name: AxisName,
                          bias_local: Array | None = None
                          ) -> tuple[Array, Array]:
    """Greedy decode over a sharded head: global (argmax id, max logit).

    Communication: one pmax of (T,) + one psum of (T,) masked ids."""
    logits = jnp.einsum("td,nd->tn", h.astype(jnp.float32),
                        w_local.astype(jnp.float32))
    if bias_local is not None:
        logits = logits + bias_local[None, :]
    n_local = w_local.shape[0]
    off = local_vocab_offset(n_local, axis_name)
    local_best = jnp.max(logits, axis=-1)
    local_arg = jnp.argmax(logits, axis=-1).astype(jnp.int32) + off
    best = lax.pmax(local_best, axis_name)
    # Break ties toward the lowest shard by masking non-winners to 0 and
    # taking the min over winners via psum of one-hot-selected ids.
    is_winner = local_best >= best
    candidate = jnp.where(is_winner, local_arg, jnp.iinfo(jnp.int32).max)
    winner_id = lax.pmin(candidate, axis_name)
    return winner_id, best


def sharded_logits_topk(w_local: Array, h: Array, k: int, *,
                        axis_name: AxisName,
                        bias_local: Array | None = None
                        ) -> tuple[Array, Array]:
    """Dense top-k decode over a sharded head: global (ids, logits), sorted.

    The O(n d) fallback when no retrieval index is present (DESIGN.md §5).
    w_local: (n/tp, d) local head shard; h: (T, d) replicated across the TP
    axis -> ids (T, k) int32 GLOBAL class ids, logits (T, k) fp32.
    Communication: one all-gather of (T, k) per-shard candidates — never a
    gathered (T, n) logit tensor.  Ties resolve toward the lowest shard
    (matching ``sharded_logits_argmax`` at k = 1)."""
    logits = jnp.einsum("td,nd->tn", h.astype(jnp.float32),
                        w_local.astype(jnp.float32))
    if bias_local is not None:
        logits = logits + bias_local[None, :]
    n_local = w_local.shape[0]
    off = local_vocab_offset(n_local, axis_name)
    local_best, local_arg = lax.top_k(logits, min(k, n_local))
    local_ids = local_arg.astype(jnp.int32) + off
    all_best = lax.all_gather(local_best, axis_name, axis=1, tiled=True)
    all_ids = lax.all_gather(local_ids, axis_name, axis=1, tiled=True)
    best, sel = lax.top_k(all_best, k)
    return jnp.take_along_axis(all_ids, sel, axis=1), best


def sharded_partition_diagnostics(state_local: Any, sampler: Sampler,
                                  h: Array, *, axis_name: AxisName) -> Array:
    """Per-shard share of the global kernel mass (load-balance telemetry).

    Uses the root-level Gram statistics: rho_s = sum_b alpha h^T Z_b h + n_s,
    normalized across shards.  Works for both block statistics and the
    hierarchy form (whose per-shard root IS the shard's total mass — the top
    log2(tp) tree levels are the TP axis, DESIGN.md §2.5).
    Shape (T,) fraction owned by this shard."""
    stats = state_local["stats"]
    proj = state_local.get("proj")
    hq = h.astype(jnp.float32)
    if proj is not None:
        hq = hq @ proj.T
    if hasattr(stats, "levels_z"):  # hierarchy/tree statistics
        z, cnt = stats.levels_z[0], stats.levels_cnt[0]
    else:  # two-level block statistics
        z, cnt = stats.z, stats.cnt
    quad = jnp.einsum("nij,ti,tj->tn", z, hq, hq)
    mass = jnp.sum(sampler.kernel.alpha * quad + cnt[None, :], axis=-1)
    return mass / lax.psum(mass, axis_name)
