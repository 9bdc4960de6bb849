"""Pluggable loss estimators behind the sampled-softmax head (DESIGN.md §6).

The paper studies ONE estimator — the eq. 2/3 corrected sampled softmax —
but the surrounding literature treats the estimator as a free choice on top
of the same sampled negatives (Rawat et al. 2019's sampled-softmax variants;
NCE, Gutmann & Hyvarinen 2010).  This registry makes that choice a config
knob (``cfg.estimator``) without reopening the train island: every sampled
estimator consumes the SAME contract

    loss(pos_logit, neg_logits, logq, hit_mask, *, abs_mode) -> (...,)

where ``pos_logit``/(..., m) ``neg_logits`` are RAW logits, ``logq`` is the
sampler's exact log-probability for each negative (what the eq. 2
correction ``o - ln(m q)`` needs), and ``hit_mask`` marks negatives that
collided with the example's label.  The estimator decides what to do with
each ingredient:

  sampled-softmax   eq. 2/3: correct negatives by ln(m q), mask accidental
                    hits to zero mass, cross-entropy over the m+1 logits.
                    The paper's estimator; the default.
  nce               binary logistic "data vs noise": softplus(-pos) +
                    sum softplus(neg - ln(m q)).  Collided slots are KEPT —
                    every sampled candidate is noise-labelled, even one
                    that equals the label (as in TF's nce_loss).
  sampled-logistic  nce with collided slots REMOVED (hit-masked to zero
                    contribution) — TF's "Sampled Logistic" column; the
                    right choice when the label must never be pushed down
                    as noise.
  full              the dense oracle: no sampling, exact softmax cross
                    entropy over all n classes (eq. 1).  ``needs_sampling``
                    is False — the dispatch layer skips the sampler
                    entirely and never materializes (T, m) anything.

DELIBERATE DEVIATION from textbook NCE: the ln(m q) correction applies to
the NEGATIVES ONLY.  Full NCE also subtracts ln(m q(label|h)) from the
positive logit, but q(label) is not in this contract — for the adaptive
kernel samplers it would cost an extra all-class query (or hierarchy
descent) per example, for the exact quantity the sampled head exists to
avoid.  Consequence: under nce / sampled-logistic the learned positive
score absorbs a +ln(m q(label|h)) offset relative to true-NCE logits
(exactly zero-mean drift when q is uniform; input-dependent for adaptive
q).  The dense-oracle tests encode this same formula on purpose — they pin
the implementation, not the textbook estimator.

``loss_from_embeddings`` is the head-level seam: it routes the default
estimator through ``sampled_softmax_from_embeddings`` so the fused Pallas
head keeps serving the per-example path (DESIGN.md §4), computes plain
gathered logits for the logistic family, and short-circuits ``full`` to the
dense reference — the kernels stay behind this seam.
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp

from repro.core.sampled_softmax import (
    full_softmax_loss,
    gather_pos_neg_logits,
    sampled_softmax_from_embeddings,
    sampled_softmax_loss,
    transform_logits,
)

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class Estimator:
    """Base estimator; subclasses implement ``loss`` on the shared contract.

    ``needs_sampling`` False marks dense estimators: the dispatch layer
    must not sample and must route through ``dense_loss`` instead.
    ``masks_hits`` documents the accidental-hit policy (it is applied
    inside ``loss``; callers pass the raw mask either way).
    """

    name: str = "base"
    needs_sampling: bool = True
    masks_hits: bool = True

    def loss(self, pos_logit: Array, neg_logits: Array, logq: Array,
             hit_mask: Array | None, *, abs_mode: bool = False) -> Array:
        raise NotImplementedError

    def dense_loss(self, w: Array, h: Array, labels: Array, *,
                   abs_mode: bool = False,
                   bias: Array | None = None) -> Array:
        raise TypeError(f"estimator '{self.name}' needs sampled negatives")


@dataclasses.dataclass(frozen=True)
class SampledSoftmaxEstimator(Estimator):
    """The paper's eq. 2/3 estimator (module docstring)."""

    name: str = "sampled-softmax"

    def loss(self, pos_logit, neg_logits, logq, hit_mask, *,
             abs_mode=False):
        return sampled_softmax_loss(pos_logit, neg_logits, logq,
                                    abs_mode=abs_mode, hit_mask=hit_mask)


def _corrected_logistic(pos_logit, neg_logits, logq, hit_mask, abs_mode):
    """softplus(-pos) + sum softplus(neg - ln(m q)), hit slots zeroed when
    ``hit_mask`` is given.  Shared core of nce / sampled-logistic."""
    m = neg_logits.shape[-1]
    pos = transform_logits(pos_logit, abs_mode)
    neg = transform_logits(neg_logits, abs_mode) - (
        logq + jnp.log(jnp.asarray(m, neg_logits.dtype)))
    per_slot = jax.nn.softplus(neg)
    if hit_mask is not None:
        per_slot = jnp.where(hit_mask, 0.0, per_slot)
    return jax.nn.softplus(-pos) + jnp.sum(per_slot, axis=-1)


@dataclasses.dataclass(frozen=True)
class NCEEstimator(Estimator):
    """Noise-contrastive estimation, negatives eq.-2-corrected; the
    positive is deliberately UNCORRECTED (module docstring — q(label) is
    outside the contract).

    Collided slots stay in: a sampled candidate is noise-labelled even when
    it equals the example's label (as in TF's nce_loss) — so ``hit_mask``
    is deliberately ignored."""

    name: str = "nce"
    masks_hits: bool = False

    def loss(self, pos_logit, neg_logits, logq, hit_mask, *,
             abs_mode=False):
        return _corrected_logistic(pos_logit, neg_logits, logq, None,
                                   abs_mode)


@dataclasses.dataclass(frozen=True)
class SampledLogisticEstimator(Estimator):
    """NCE with accidental hits removed (zero mass AND zero gradient)."""

    name: str = "sampled-logistic"

    def loss(self, pos_logit, neg_logits, logq, hit_mask, *,
             abs_mode=False):
        return _corrected_logistic(pos_logit, neg_logits, logq, hit_mask,
                                   abs_mode)


@dataclasses.dataclass(frozen=True)
class FullSoftmaxEstimator(Estimator):
    """Dense oracle: exact eq. 1 cross entropy, no sampling at all."""

    name: str = "full"
    needs_sampling: bool = False

    def loss(self, pos_logit, neg_logits, logq, hit_mask, *,
             abs_mode=False):
        raise TypeError(
            "estimator 'full' is dense — route through dense_loss / "
            "loss_from_embeddings, not the sampled contract")

    def dense_loss(self, w, h, labels, *, abs_mode=False, bias=None):
        return full_softmax_loss(w, h, labels, abs_mode=abs_mode, bias=bias)


_REGISTRY: dict[str, Callable[[], Estimator]] = {
    "sampled-softmax": SampledSoftmaxEstimator,
    "nce": NCEEstimator,
    "sampled-logistic": SampledLogisticEstimator,
    "full": FullSoftmaxEstimator,
}


def estimator_names() -> list[str]:
    """Names accepted by make_estimator / cfg.estimator."""
    return sorted(_REGISTRY)


def make_estimator(name: str) -> Estimator:
    if name not in _REGISTRY:
        raise KeyError(
            f"unknown estimator '{name}'; have {sorted(_REGISTRY)}")
    return _REGISTRY[name]()


def local_sampled_loss(est: Estimator, sampler, w: Array, h: Array,
                       labels: Array, state, m: int, key: Array | None, *,
                       n_valid, abs_mode: bool = False,
                       bias: Array | None = None,
                       impl: str = "auto") -> Array:
    """The mesh=None head path, shared VERBATIM by the train island and
    ``repro.api.SoftmaxHead.loss``: hydrate (or rebuild-from-head) the
    sampler's runtime state, stop-gradient it, draw negatives, dispatch
    the estimator.  One copy — the golden-parity suite pins the numerics
    for both consumers (the sharded analogue is
    ``distributed.sharded_estimator_loss``)."""
    if not est.needs_sampling:
        return loss_from_embeddings(est, w, h, labels, None, None,
                                    abs_mode=abs_mode, bias=bias, impl=impl)
    with jax.named_scope("sampler_refresh"):
        runtime = sampler.island_runtime(state, jax.lax.stop_gradient(w),
                                         n_valid)
        runtime = jax.tree_util.tree_map(jax.lax.stop_gradient, runtime)
    with jax.named_scope("sampler_draw"):
        neg_ids, logq = sampler.sample_batch(runtime, h, m, key)
    return loss_from_embeddings(
        est, w, h, labels, jax.lax.stop_gradient(neg_ids),
        jax.lax.stop_gradient(logq), abs_mode=abs_mode, bias=bias,
        impl=impl)


@jax.named_scope("head_loss")
def loss_from_embeddings(
    est: Estimator, w: Array, h: Array, labels: Array,
    neg_ids: Array | None, logq: Array | None, *, abs_mode: bool = False,
    bias: Array | None = None, impl: str = "auto") -> Array:
    """Head-level dispatch: per-example loss (T,) from the embedding table.

    The default estimator keeps its fused-Pallas route (per-example
    negatives never materialize (T, m, d) in HBM — DESIGN.md §4); the
    logistic family gathers logits densely (elementwise losses have no LSE
    for the fused kernel to produce); ``full`` ignores the negatives."""
    if not est.needs_sampling:
        return est.dense_loss(w, h, labels, abs_mode=abs_mode, bias=bias)
    if neg_ids is None or logq is None:
        raise ValueError(
            f"estimator '{est.name}' needs sampled negatives: pass "
            "neg_ids and logq (or use estimator='full')")
    if est.name == "sampled-softmax":
        return sampled_softmax_from_embeddings(
            w, h, labels, neg_ids, logq, abs_mode=abs_mode, bias=bias,
            impl=impl)
    pos_logit, neg_logits, logq, hit = gather_pos_neg_logits(
        w, h, labels, neg_ids, logq, bias)
    return est.loss(pos_logit, neg_logits, logq, hit, abs_mode=abs_mode)
