"""The jitted train step: backbone (GSPMD) + sampled-softmax head (shard_map).

Data flow per step (LM example, production mesh):

  tokens (B,S) --DP--> backbone --> h (B,S,d)  [activations data-sharded]
  h flattened  --> shard_map island over the FULL mesh:
        head shard (vocab/tp, d/fsdp) --all-gather(fsdp)--> (vocab/tp, d)
        sampler-state refresh (one Gram/feature matmul)  |  or carried
            state (stale OK)
        stratified kernel sampling: m/tp negatives per shard   [paper §3.2,
            top tree levels = TP axis, DESIGN.md §2.5]
        estimator-routed corrected loss, global combine via psum  [eq. 2-3
            for the default sampled-softmax estimator; accidental hits
            masked, per-example negatives through the fused head kernel
            per cfg.head_impl — DESIGN.md §4/§6]
  loss --> value_and_grad --> optimizer (clip + AdamW/Adafactor)

Sampler statistics are carried in ``TrainState.sampler_state`` — ONE
self-describing ``SamplerState`` pytree whose array layout, abstract shapes
and sharding specs are declared by the sampler itself
(``Sampler.state_shapes`` / ``state_specs`` — DESIGN.md §6).  This module
never enumerates per-family arrays; adding a sampler family touches
``core/samplers.py`` only.  The state refreshes on a cadence
(cfg.sampler_refresh_every); the correction always uses the statistics that
were actually sampled from, so staleness costs bias-of-q only, never
correctness of the estimator (DESIGN.md §2.4).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.core import distributed, estimators
from repro.core.samplers import (
    SamplerState,
    empty_state,
    sampler_from_config,
)
from repro.models import api
from repro.models.transformer import padded_vocab
from repro.optim.transform import GradientTransform, apply_updates
from repro.sharding.rules import ShardCtx, param_specs_for

Array = jax.Array

#: kept name: the cfg-aware sampler constructor now lives in the registry
#: (core/samplers.py — one source of truth; this alias preserves the old
#: train-island spelling).
sampler_from_cfg = sampler_from_config


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class TrainState:
    """Carried training state.

    ``sampler_state`` is the sampler-owned ``SamplerState`` pytree
    (statistics + run-lifetime constants).  Its per-family array layout is
    documented where it is defined — ``core/samplers.py`` — not here; this
    struct, the checkpoint manager and the dry-run treat it as opaque.
    Statistics leaves ride sharded P('model') over their leading vocab-heap
    axis, constants replicated (``Sampler.state_specs``).
    """

    params: Any
    opt_state: Any
    sampler_state: SamplerState
    step: Array                  # () int32


def _merge_refresh(new: dict, keep: dict, refresh: Array) -> dict:
    return jax.tree_util.tree_map(
        lambda a_, b_: jnp.where(refresh, a_, b_), new, keep)


def make_refresh_fn(cfg: ArchConfig, ctx: ShardCtx
                    ) -> Callable[[Array, SamplerState], SamplerState]:
    """Unconditional sampler-stat rebuild from a head-table snapshot.

    The refresh-island half of ``refresh_mode="overlap"`` (DESIGN.md §7):
    the loop jits this once, dispatches it against SNAPSHOTS of the head
    and the carried sampler state (fresh buffers — donation of TrainState
    can never invalidate its inputs) without blocking the step stream,
    and swaps the result into
    the carried ``TrainState.sampler_state`` a fixed
    ``cfg.refresh_stale_steps`` steps later.  Mathematically identical to
    the in-step refresh at the same head; the only difference is WHICH
    head it saw (k optimizer updates stale — bias-of-q only, never
    estimator correctness, quantified in BENCH_grad_bias.json staleness
    rows).  A no-op (state passes through) for stateless samplers or
    dense estimators."""
    cfg.validate(tp=ctx.tp)
    sampler = sampler_from_config(cfg)
    estimator = estimators.make_estimator(cfg.estimator)
    mesh = ctx.mesh
    tp = ctx.tp
    head_fsdp = ctx.data_spec() if mesh is not None else None
    v_l = padded_vocab(cfg, tp) // tp
    carries_stats = sampler.carries_state and estimator.needs_sampling
    mdl = ctx.model_axis
    specs = (sampler.state_specs(cfg, tp, axis=mdl) if carries_stats
             else empty_state())

    def island(head, const):
        my = lax.axis_index(mdl)
        head_full = head  # gather the Fd-sharded feature dim
        for a in ctx.data_axes[::-1]:
            head_full = lax.all_gather(head_full, a, axis=1, tiled=True)
        n_valid = jnp.clip(cfg.vocab_size - my * v_l, 0, v_l)
        return sampler.build_stats(head_full, n_valid, const)

    @jax.named_scope("sampler_refresh")
    def refresh_fn(head: Array, sampler_state: SamplerState) -> SamplerState:
        if not carries_stats:
            return sampler_state
        head = lax.stop_gradient(head)
        if mesh is None:
            n_valid = jnp.asarray(cfg.vocab_size, jnp.int32)
            stats = sampler.build_stats(head, n_valid, sampler_state.const)
        else:
            stats = jax.shard_map(
                island, mesh=mesh, check_vma=False,
                in_specs=(P(mdl, head_fsdp), specs.const),
                out_specs=specs.stats,
            )(head, sampler_state.const)
        # Copy const so jitted callers never input→output-forward a buffer:
        # the swapped-in state must share NOTHING with the (donatable)
        # TrainState the loop passed at dispatch time.
        const = jax.tree_util.tree_map(jnp.copy, sampler_state.const)
        return SamplerState(stats=stats, const=const)

    refresh_fn.carries_stats = carries_stats
    return refresh_fn


def _make_head_loss(cfg: ArchConfig, ctx: ShardCtx
                    ) -> Callable[[Array, Array, Array, SamplerState, Array],
                                  Array]:
    """``head_loss(head, h2d, labels, sampler_state, key)`` -> the GLOBAL
    estimator loss SUM over all tokens: the head island of the train step
    (a ``shard_map`` over the full mesh, or the mesh=None local path)."""
    sampler = sampler_from_config(cfg)
    estimator = estimators.make_estimator(cfg.estimator)
    mesh = ctx.mesh
    tp = ctx.tp
    m = cfg.m_negatives
    dataspec = ctx.batch_spec() if mesh is not None else None
    head_fsdp = ctx.data_spec() if mesh is not None else None
    pure_fsdp = ctx.mode == "pure_fsdp"
    v_l = padded_vocab(cfg, tp) // tp  # head rows per vocab shard
    mdl = ctx.model_axis
    carries_stats = sampler.carries_state and estimator.needs_sampling
    specs = (sampler.state_specs(cfg, tp, axis=mdl) if carries_stats
             else empty_state())

    def _local_state(sampler_state: SamplerState, head_full, n_valid):
        """Runtime sampling state inside the island: ONE protocol call —
        the sampler hydrates its carried pytree, rebuilds from the gathered
        head, or (multi-stage families) keeps the head table for pool
        re-scoring (Sampler.island_runtime)."""
        with jax.named_scope("sampler_refresh"):
            return sampler.island_runtime(
                sampler_state, lax.stop_gradient(head_full), n_valid)

    def head_island(head, h2d, labels, stats, const, key):
        """Runs per-(data,model) shard.  head: (v_l, d_l) local;
        h2d: (T_l, d); labels: (T_l,).  Returns the GLOBAL loss sum (scalar,
        replicated) — tokens x vocab both stay sharded end to end."""
        my = lax.axis_index(mdl)
        head_full = head
        for a in ctx.data_axes[::-1]:
            head_full = lax.all_gather(head_full, a, axis=1, tiled=True)
        if pure_fsdp:
            # tokens are sharded over `model` too; the vocab-parallel loss
            # needs each model column to hold its data-row's full token set.
            h2d = lax.all_gather(h2d, mdl, axis=0, tiled=True)
            labels = lax.all_gather(labels, mdl, axis=0, tiled=True)
        n_valid = jnp.clip(cfg.vocab_size - my * v_l, 0, v_l)
        state_local = None
        if estimator.needs_sampling:
            state_local = jax.tree_util.tree_map(
                lax.stop_gradient,
                _local_state(SamplerState(stats, const), head_full, n_valid))
        # Distinct negatives per data shard: fold the data position in.
        for a in ctx.data_axes:
            key = jax.random.fold_in(key, lax.axis_index(a))
        losses = distributed.sharded_estimator_loss(
            estimator, head_full, h2d, labels, sampler, state_local,
            m, key, axis_name=mdl, abs_mode=cfg.abs_softmax,
            impl=cfg.head_impl)
        lsum = jnp.sum(losses)
        if pure_fsdp:
            # every model column computed the same row-sum; average the
            # replicas through a psum so the output is truly replicated.
            lsum = lax.psum(lsum / tp, mdl)
        for a in ctx.data_axes:
            lsum = lax.psum(lsum, a)
        return lsum

    def island_caller(head, h2d, labels, sampler_state: SamplerState, key):
        """Returns the global loss SUM over all tokens."""
        if mesh is None:
            return jnp.sum(estimators.local_sampled_loss(
                estimator, sampler, head, h2d, labels, sampler_state, m,
                key, n_valid=jnp.asarray(cfg.vocab_size, jnp.int32),
                abs_mode=cfg.abs_softmax, impl=cfg.head_impl))
        return jax.shard_map(
            head_island, mesh=mesh, check_vma=False,
            in_specs=(P(mdl, head_fsdp), P(dataspec, None), P(dataspec),
                      specs.stats, specs.const, P()),
            out_specs=P(),
        )(head, h2d, labels, sampler_state.stats, sampler_state.const, key)

    return island_caller


def make_eval_fn(cfg: ArchConfig, ctx: ShardCtx
                 ) -> Callable[[Any, dict], Array]:
    """``eval_fn(params, batch)`` -> mean full-softmax (eq. 1) loss.

    The exact loss the sampled estimators approximate, through the same
    backbone and head island as the train step (vocab-sharded on a mesh:
    per-shard logsumexp, combined across the model axis — no (T, n) logit
    tensor is gathered)."""
    cfg = dataclasses.replace(cfg, estimator="full")
    cfg.validate(tp=ctx.tp)
    head_loss = _make_head_loss(cfg, ctx)

    def eval_fn(params, batch):
        h2d, labels, _ = api.backbone_hidden(params, batch, cfg, ctx)
        head = api.head_table(params, cfg)
        lsum = head_loss(head, h2d, labels, empty_state(),
                         jax.random.PRNGKey(0))
        return lsum / h2d.shape[0]

    return eval_fn


def make_train_step(cfg: ArchConfig, ctx: ShardCtx, opt: GradientTransform,
                    aux_coef: float = 0.01
                    ) -> Callable[[TrainState, dict, Array],
                                  tuple[TrainState, dict]]:
    cfg.validate(tp=ctx.tp)
    sampler = sampler_from_config(cfg)
    estimator = estimators.make_estimator(cfg.estimator)
    mesh = ctx.mesh
    tp = ctx.tp
    head_fsdp = (ctx.data_spec() if ctx.mesh is not None else None)
    v_l = padded_vocab(cfg, tp) // tp  # head rows per vocab shard

    carries_stats = sampler.carries_state and estimator.needs_sampling
    mdl = ctx.model_axis
    # Specs must mirror the init gating: a dense estimator (estimator.
    # needs_sampling False) carries an EMPTY state even for a carrying
    # sampler, and the shard_map in_specs must match that empty pytree.
    specs = (sampler.state_specs(cfg, tp, axis=mdl) if carries_stats
             else empty_state())

    # --- stats refresh (no gradients; runs once per step, before the
    # microbatch loop, so all microbatches sample from the SAME q) ----------
    def refresh_island(head, stats, const, refresh):
        my = lax.axis_index(mdl)
        head_full = head  # gather the Fd-sharded feature dim
        for a in ctx.data_axes[::-1]:
            head_full = lax.all_gather(head_full, a, axis=1, tiled=True)
        n_valid = jnp.clip(cfg.vocab_size - my * v_l, 0, v_l)
        new = sampler.build_stats(head_full, n_valid, const)
        return _merge_refresh(new, stats, refresh)

    @jax.named_scope("sampler_refresh")
    def refresh_state(head, sampler_state: SamplerState, refresh
                      ) -> SamplerState:
        if not carries_stats:
            return sampler_state
        head = lax.stop_gradient(head)
        if mesh is None:
            n_valid = jnp.asarray(cfg.vocab_size, jnp.int32)
            new = sampler.build_stats(head, n_valid, sampler_state.const)
            return sampler_state.replace_stats(
                _merge_refresh(new, sampler_state.stats, refresh))
        stats = jax.shard_map(
            refresh_island, mesh=mesh, check_vma=False,
            in_specs=(P(mdl, head_fsdp), specs.stats, specs.const, P()),
            out_specs=specs.stats,
        )(head, sampler_state.stats, sampler_state.const, refresh)
        return sampler_state.replace_stats(stats)

    # --- loss (differentiable; consumes fixed stats) ------------------------
    island_caller = _make_head_loss(cfg, ctx)

    def loss_fn(params, mb, sampler_state, key):
        h2d, labels, aux = api.backbone_hidden(params, mb, cfg, ctx)
        head = api.head_table(params, cfg)
        lsum = island_caller(head, h2d, labels, sampler_state, key)
        loss = lsum / h2d.shape[0]
        return loss + aux_coef * aux, (loss, aux)

    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def _split_microbatches(batch, mu):
        """(B, ...) -> (mu, B/mu, ...) with shard-local interleaving, so the
        data-axis sharding of the batch dim is preserved (DESIGN.md §7)."""

        def one(x):
            b = x.shape[0]
            assert b % mu == 0, f"batch {b} % microbatches {mu} != 0"
            xr = x.reshape(b // mu, mu, *x.shape[1:])
            xr = jnp.moveaxis(xr, 1, 0)
            if ctx.mesh is not None:
                xr = ctx.act(xr, ".b" + "." * (x.ndim - 1))
            return xr

        return jax.tree_util.tree_map(one, batch)

    overlap = cfg.refresh_mode == "overlap"

    def train_step(state: TrainState, batch: dict, key: Array
                   ) -> tuple[TrainState, dict]:
        if overlap:
            # Refresh runs OUTSIDE the step (train/loop.py RefreshIsland
            # dispatches make_refresh_fn from a head snapshot and swaps
            # the result into the carried state k steps stale); the step
            # samples from whatever statistics it was handed.
            sstate = state.sampler_state
        else:
            refresh = (state.step % max(cfg.sampler_refresh_every, 1)) == 0
            head = api.head_table(state.params, cfg)
            sstate = refresh_state(head, state.sampler_state, refresh)
        mu = max(cfg.microbatches, 1)
        if mu == 1:
            (total, (loss, aux)), grads = grad_fn(
                state.params, batch, sstate, key)
        else:
            mbs = _split_microbatches(batch, mu)
            keys = jax.random.split(key, mu)
            g0 = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), state.params)
            acc0 = (jnp.zeros(()), jnp.zeros(()), jnp.zeros(()), g0)

            def body(acc, inp):
                mb, k_i = inp
                (tot_i, (loss_i, aux_i)), g_i = grad_fn(
                    state.params, mb, sstate, k_i)
                tot, lo, au, g = acc
                g = jax.tree_util.tree_map(
                    lambda a_, b_: a_ + b_.astype(jnp.float32), g, g_i)
                return (tot + tot_i, lo + loss_i, au + aux_i, g), None

            (total, loss, aux, grads), _ = jax.lax.scan(
                body, acc0, (mbs, keys))
            total, loss, aux = total / mu, loss / mu, aux / mu
            grads = jax.tree_util.tree_map(lambda g_: g_ / mu, grads)
        with jax.named_scope("optimizer"):
            updates, opt_state = opt.update(grads, state.opt_state,
                                            state.params)
            params = apply_updates(state.params, updates)
        new_state = TrainState(
            params=params,
            opt_state=opt_state,
            sampler_state=sstate if carries_stats else state.sampler_state,
            step=state.step + 1,
        )
        metrics = {"loss": loss, "aux_loss": aux, "total_loss": total}
        return new_state, metrics

    return train_step


def export_retrieval_index(state: TrainState, cfg: ArchConfig, ctx: ShardCtx,
                           leaf_size: int | None = None):
    """Packed serving index (DESIGN.md §5) from a trained state.

    Builds UNPROJECTED hierarchy statistics from the current head table —
    one Gram matmul, the same cost as a sampler refresh.  The carried
    ``sampler_state`` is deliberately NOT reused: it may be projected
    (useless for exact logits) and is at least one optimizer update stale
    (refresh ran before the step's gradient was applied), while serving
    decode must score with the embeddings actually being served.  The
    returned ``RetrievalIndex`` is a plain pytree — save it with the
    checkpoint (``CheckpointManager.save``) and a restarted server decodes
    without a rebuild."""
    from repro.serve import retrieval

    head = api.head_table(state.params, cfg)
    return retrieval.build_index(head, ctx, leaf_size=leaf_size,
                                 vocab_size=cfg.vocab_size)


def export_quantized_index(state: TrainState, cfg: ArchConfig, ctx: ShardCtx,
                           bits: int | None = None):
    """Quantized serving index (DESIGN.md §2.9) from a trained state.

    Same contract as ``export_retrieval_index`` — fresh UNPROJECTED head,
    never the carried sampler state — but packs the MIDX codebook
    structure with ``cfg.midx_bits``-wide member rows (int8 by default:
    ~4x smaller refresh payload over the train->serve seam).  The knobs
    ride ``ArchConfig`` (``midx_codewords`` / ``midx_codebooks`` /
    ``sampler_block`` / ``midx_bits``) so the serving index mirrors the
    training-time sampler's structure by construction."""
    from repro.serve import quantized_index

    head = api.head_table(state.params, cfg)
    return quantized_index.build_quantized_index(
        head, ctx, codewords=cfg.midx_codewords,
        codebooks=cfg.midx_codebooks, list_size=cfg.sampler_block,
        bits=bits if bits is not None else cfg.midx_bits,
        vocab_size=cfg.vocab_size)


def serving_index_source(checkpoint_dir: str, cfg: ArchConfig, ctx: ShardCtx,
                         opt: GradientTransform, *, max_len: int = 4096,
                         leaf_size: int | None = None,
                         quantized: bool = False):
    """The serving half of the train->serve refresh seam (DESIGN.md §5.1).

    Returns ``poll() -> (RetrievalIndex, step) | None``: probe the
    checkpoint directory, and when a step newer than the last one served
    has landed COMPLETE (the manager only lists renamed, manifest-bearing
    steps — the fsync/os.replace atomicity contract), restore it and
    export a fresh unprojected index from its head table.  Returns None
    when training hasn't advanced.  Built for the background
    ``serve.server.IndexRefresher``: the restore + hierarchy build (the
    expensive part) runs wherever ``poll`` is called — never on the decode
    path — and the engine swap that follows is O(1).

    The restore template is an ``eval_shape`` skeleton of the training
    state — the serving process never allocates a training state; arrays
    land straight from the npz.

    ``quantized=True`` exports the ``QuantizedRetrievalIndex`` (DESIGN.md
    §2.9, knobs from cfg) instead of the fp32 Gram index — the refresh
    payload the engine's ``index_payload_bytes`` gauge measures shrinks
    ~4x at ``midx_bits=8``.

    Partial-write race: the manifest rename makes COMPLETE checkpoints
    atomic, but a poll can still catch a directory mid-write (manifest
    landed, arrays not yet — e.g. a crashed writer, or a copy tool that
    replays the rename before the data).  A restore failure here must NOT
    kill the refresher (``IndexRefresher`` stops on source exceptions) and
    must NOT mark the step as served: report "nothing new" and leave
    ``last`` untouched so the next poll retries the same step once the
    writer finishes.
    """
    from repro.checkpoint.manager import CheckpointManager

    mgr = CheckpointManager(checkpoint_dir)
    like = jax.eval_shape(
        lambda _: init_train_state(jax.random.PRNGKey(0), cfg, ctx, opt,
                                   max_len=max_len), 0)
    last: dict[str, int | None] = {"step": None}

    def poll():
        step = mgr.latest_step()
        if step is None or step == last["step"]:
            return None
        try:
            state, _ = mgr.restore(like=like, step=step)
        except (OSError, KeyError, ValueError):
            return None  # torn read — retry this step on the next poll
        last["step"] = step
        if quantized:
            return export_quantized_index(state, cfg, ctx), step
        return export_retrieval_index(state, cfg, ctx,
                                      leaf_size=leaf_size), step

    return poll


def init_train_state(key, cfg: ArchConfig, ctx: ShardCtx,
                     opt: GradientTransform, max_len: int = 4096
                     ) -> TrainState:
    """Concrete (allocating) init — smoke tests / examples.  The dry-run uses
    abstract_train_state instead.

    On a mesh the state is built straight into the shardings
    ``abstract_train_state`` declares (params and optimizer state sharded
    by ``param_specs_for``, sampler statistics P('model')), so no device
    ever holds the whole state."""
    cfg.validate(tp=ctx.tp)
    if ctx.mesh is None:
        return _init_train_state(key, cfg, ctx, opt, max_len)
    shardings = jax.tree_util.tree_map(
        lambda s: s.sharding, abstract_train_state(cfg, ctx, opt, max_len))
    init = jax.jit(
        lambda k: _init_train_state(k, cfg, ctx, opt, max_len),
        out_shardings=shardings)
    return init(key)


def _init_train_state(key, cfg: ArchConfig, ctx: ShardCtx,
                      opt: GradientTransform, max_len: int) -> TrainState:
    sampler = sampler_from_config(cfg)
    estimator = estimators.make_estimator(cfg.estimator)
    params = api.init_params(key, cfg, ctx, max_len=max_len)
    opt_state = opt.init(params)
    head = api.head_table(params, cfg)
    sstate = empty_state()
    if sampler.carries_state and estimator.needs_sampling:
        if ctx.mesh is None:
            sstate = sampler.init_state(
                jax.random.fold_in(key, 7), head,
                n_valid=jnp.asarray(cfg.vocab_size, jnp.int32))
        else:
            # Mesh init allocates zeros by the sampler's declared shapes;
            # the first step's refresh (step 0) writes real statistics.
            # Constants are still drawn concretely — they never refresh.
            shapes = sampler.state_shapes(cfg, ctx.tp)
            sstate = SamplerState(
                stats=jax.tree_util.tree_map(
                    lambda s: jnp.zeros(s.shape, s.dtype), shapes.stats),
                const=sampler.init_const(jax.random.fold_in(key, 7),
                                         head.shape[1]))
    return TrainState(params=params, opt_state=opt_state,
                      sampler_state=sstate, step=jnp.zeros((), jnp.int32))


# --- abstract (dry-run) state ------------------------------------------------


def _spec_to_sharding(ctx: ShardCtx, spec: P):
    return NamedSharding(ctx.mesh, spec)


def abstract_train_state(cfg: ArchConfig, ctx: ShardCtx,
                         opt: GradientTransform, max_len: int = 4096
                         ) -> TrainState:
    """ShapeDtypeStruct TrainState with NamedShardings attached — zero
    allocation; feeds jit(...).lower() for the multi-pod dry-run."""
    cfg.validate(tp=ctx.tp)
    sampler = sampler_from_config(cfg)
    estimator = estimators.make_estimator(cfg.estimator)
    key = jax.random.PRNGKey(0)
    params_struct = jax.eval_shape(
        lambda k: api.init_params(k, cfg, ctx, max_len=max_len), key)
    specs = param_specs_for(params_struct, ctx)
    params_sds = jax.tree_util.tree_map(
        lambda s, sp: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=_spec_to_sharding(ctx, sp)),
        params_struct, specs)

    opt_struct = jax.eval_shape(opt.init, params_struct)
    opt_sds = _derive_opt_sds(opt_struct, params_struct, specs, ctx)

    sstate = empty_state()
    if sampler.carries_state and estimator.needs_sampling:
        shapes = sampler.state_shapes(cfg, ctx.tp)
        sspecs = sampler.state_specs(cfg, ctx.tp, axis=ctx.model_axis)
        sstate = jax.tree_util.tree_map(
            lambda s, sp: jax.ShapeDtypeStruct(
                s.shape, s.dtype, sharding=_spec_to_sharding(ctx, sp)),
            shapes, sspecs)
    step = jax.ShapeDtypeStruct((), jnp.int32,
                                sharding=_spec_to_sharding(ctx, P()))
    return TrainState(params=params_sds, opt_state=opt_sds,
                      sampler_state=sstate, step=step)


def _derive_opt_sds(opt_struct, params_struct, param_specs, ctx: ShardCtx):
    """Specs for optimizer state: same-shape leaves inherit the param spec;
    Adafactor's factored vr/vc drop the reduced axis."""
    by_path = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(params_struct)[0]:
        key = tuple(str(getattr(p, "key", getattr(p, "idx", p)))
                    for p in path)
        by_path[key] = leaf.shape
    spec_by_path = {}
    for path, sp in jax.tree_util.tree_flatten_with_path(param_specs)[0]:
        key = tuple(str(getattr(p, "key", getattr(p, "idx", p)))
                    for p in path)
        spec_by_path[key] = sp

    def leaf_sds(path, leaf):
        key = tuple(str(getattr(p, "key", getattr(p, "idx", p)))
                    for p in path)
        # try to find the param path inside the state path
        spec = P()
        for start in range(len(key)):
            for end in range(len(key), start, -1):
                sub = key[start:end]
                if sub in spec_by_path:
                    psp = spec_by_path[sub]
                    pshape = by_path[sub]
                    if leaf.shape == pshape:
                        spec = psp
                    elif leaf.shape == pshape[:-1]:      # adafactor vr
                        spec = P(*tuple(psp)[:-1])
                    elif leaf.shape == pshape[:-2] + pshape[-1:]:  # vc
                        spec = P(*(tuple(psp)[:-2] + tuple(psp)[-1:]))
                    break
            else:
                continue
            break
        return jax.ShapeDtypeStruct(leaf.shape, leaf.dtype,
                                    sharding=_spec_to_sharding(ctx, spec))

    flat = jax.tree_util.tree_flatten_with_path(opt_struct)[0]
    treedef = jax.tree_util.tree_structure(opt_struct)
    return jax.tree_util.tree_unflatten(
        treedef, [leaf_sds(p, l) for p, l in flat])
