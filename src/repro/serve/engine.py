"""Serving: prefill and decode steps (inference never samples the softmax —
the paper's technique is training-only, paper §5.2).

Two head paths exist at decode time:

  * **dense** — the full-head MIPS: every shard scores its (n/tp, d) vocab
    slice and the winners merge across the model axis
    (``distributed.sharded_logits_argmax`` / ``sharded_logits_topk``).
    O(n d) per token; always available.
  * **index** — hierarchy-backed beam retrieval over the packed Gram index
    (``serve/retrieval.py``, DESIGN.md §5): beam descent by kernel upper
    bound, exact scoring of ~beam * leaf_size surviving classes.  Sublinear
    in n; exact at full beam, recall-tunable below it.  ``make_topk_step``
    uses it whenever an index is passed and falls back to the dense path
    otherwise.  Index arrays ride the same vocab-sharded P('model') layout
    as the training statistics (DESIGN.md §2.5).

The decode path is the `decode_*` / `long_*` dry-run target: one new token
against a KV cache of seq_len.  KV caches are sequence-sharded over the
`model` axis (SP) so no head-count padding or KV duplication is needed and
the 500k-token hybrid cells fit; the softmax over the sharded seq dim lowers
to psum-style cross-shard reductions.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.core import distributed
from repro.models import api, encdec, transformer
from repro.serve import quantized_index, retrieval
from repro.sharding.rules import (
    ShardCtx,
    gather_head_fd,
    head_fd_axes,
    param_specs_for,
)

Array = jax.Array


def _argmax_island(cfg: ArchConfig, ctx: ShardCtx, head, h2d):
    """Greedy next token over the vocab-sharded head.

    head: (nvp, d) vocab-sharded P('model', Fd); h2d: (B, d) data-sharded
    -> (B,) int32 global argmax ids — the k=1 case of the dense
    ``decode_topk`` path (identical tie-breaking: lowest class id wins).
    """
    ids, _ = decode_topk(cfg, ctx, head, h2d, 1)
    return ids[:, 0]


def decode_topk(cfg: ArchConfig, ctx: ShardCtx, head, h2d, k: int, *,
                index: retrieval.RetrievalIndex
                | quantized_index.QuantizedRetrievalIndex | None = None,
                beam: int | None = None):
    """Top-k (ids, logits) for a batch of hidden states (DESIGN.md §5).

    head: (nvp, d) vocab-sharded head table (dense fallback only);
    h2d: (B, d) hidden states -> ids (B, k) int32 global class ids and
    logits (B, k) fp32, sorted descending.  With an ``index`` the beam
    retrieval path runs (exact at full beam, ``beam`` = recall knob);
    without one the dense sharded top-k head is the fallback.  Both index
    families dispatch here — the fp32 Gram ``RetrievalIndex`` and the
    ``QuantizedRetrievalIndex`` (DESIGN.md §2.9); the isinstance check
    resolves at trace time, so each treedef jit-compiles its own branch
    and the engine's double-buffered swap can flip between families
    without touching compiled code.
    """
    if isinstance(index, quantized_index.QuantizedRetrievalIndex):
        return quantized_index.decode_topk(index, h2d, k, beam, ctx)
    if index is not None:
        return retrieval.decode_topk(index, h2d, k, beam, ctx)
    if ctx.mesh is None:
        return retrieval.dense_topk(head, h2d, k, n_valid=cfg.vocab_size)
    dsp = ctx.data_spec()
    dataspec = None if h2d.shape[0] % ctx.dp else dsp
    mdl = ctx.model_axis
    v_l = head.shape[0] // ctx.tp

    def island(head_l, h_l):
        head_full = gather_head_fd(ctx, head_l)
        my = jax.lax.axis_index(mdl)
        n_valid = jnp.clip(cfg.vocab_size - my * v_l, 0, v_l)
        bias = jnp.where(jnp.arange(v_l) < n_valid, 0.0, -jnp.inf)
        return distributed.sharded_logits_topk(
            head_full, h_l, k, axis_name=mdl, bias_local=bias)

    return jax.shard_map(
        island, mesh=ctx.mesh, check_vma=False,
        in_specs=(P(mdl, head_fd_axes(ctx)), P(dataspec, None)),
        out_specs=(P(dataspec, None), P(dataspec, None)))(head, h2d)


def make_topk_step(cfg: ArchConfig, ctx: ShardCtx, k: int, *,
                   index: retrieval.RetrievalIndex
                   | quantized_index.QuantizedRetrievalIndex | None = None,
                   beam: int | None = None):
    """topk_step(params, token (B,1), caches, pos (B,)) ->
    (ids (B, k), logits (B, k), caches).

    The `decode_topk` serving path: one decoder step, then top-k over the
    vocab through the retrieval index (or the dense head when ``index`` is
    None).  ``ids[:, 0]`` equals ``make_decode_step``'s greedy token when
    the beam is full (or the index absent)."""

    def step(params, token, caches, pos):
        if cfg.family == "encdec":
            h, caches = encdec.decode_step(params, token, caches, pos, cfg,
                                           ctx)
        else:
            h, caches = transformer.decode_step(params, token, caches, pos,
                                                cfg, ctx)
        head = api.head_table(params, cfg)
        ids, logits = decode_topk(cfg, ctx, head, h[:, 0, :], k,
                                  index=index, beam=beam)
        return ids, logits, caches

    return step


def make_decode_fn(cfg: ArchConfig, ctx: ShardCtx, head, k: int, *,
                   beam: int | None = None):
    """``decode(index, h (B, d)) -> (ids, logits)`` for the async serving
    engine (``serve/server.py``): the index rides as a PYTREE ARGUMENT so
    the engine's double-buffered swap re-binds buffers without recompiling
    — only the microbatch bucket shapes (and the dense ``index=None``
    treedef) ever compile.  ``index=None`` serves the dense head path.

    The head table rides as a leaf of the returned ``jax.tree_util.Partial``
    and the engine passes it through jit as an argument: closed over, the
    (n, d) table would be baked into every compiled program as a constant
    (hundreds of MB at LM vocabularies)."""

    def decode(head_, index, h2d):
        return decode_topk(cfg, ctx, head_, h2d, k, index=index, beam=beam)

    return jax.tree_util.Partial(decode, head)


def make_decode_step(cfg: ArchConfig, ctx: ShardCtx):
    """decode_step(params, token (B,1), caches, pos (B,)) ->
    (next_token (B,), caches)."""

    def step(params, token, caches, pos):
        if cfg.family == "encdec":
            h, caches = encdec.decode_step(params, token, caches, pos, cfg,
                                           ctx)
        else:
            h, caches = transformer.decode_step(params, token, caches, pos,
                                                cfg, ctx)
        head = api.head_table(params, cfg)
        nxt = _argmax_island(cfg, ctx, head, h[:, 0, :])
        return nxt, caches

    return step


def make_prefill_step(cfg: ArchConfig, ctx: ShardCtx, max_len: int):
    """prefill(params, tokens/frames) -> (first generated token, caches)."""

    def step(params, batch):
        if cfg.family == "encdec":
            enc_out = encdec.encode(params, batch["frames"], cfg, ctx)
            cache = encdec.init_dec_cache(
                params, cfg, batch["frames"].shape[0], max_len, enc_out, ctx)
            tok0 = jnp.zeros((batch["frames"].shape[0], 1), jnp.int32)
            pos0 = jnp.zeros((batch["frames"].shape[0],), jnp.int32)
            h, cache = encdec.decode_step(params, tok0, cache, pos0, cfg, ctx)
        else:
            h, cache = transformer.prefill(params, batch["tokens"], cfg, ctx,
                                           max_len=max_len)
            h = h[:, -1:, :]
        head = api.head_table(params, cfg)
        nxt = _argmax_island(cfg, ctx, head, h[:, 0, :])
        return nxt, cache

    return step


# --- abstract inputs for the dry-run ----------------------------------------


def _sharded_sds(struct, specs, ctx: ShardCtx):
    return jax.tree_util.tree_map(
        lambda s, sp: jax.ShapeDtypeStruct(
            s.shape, s.dtype,
            sharding=NamedSharding(ctx.mesh, ctx.fit_spec(s.shape, sp))),
        struct, specs)


def abstract_params(cfg: ArchConfig, ctx: ShardCtx, max_len: int):
    struct = jax.eval_shape(
        lambda k: api.init_params(k, cfg, ctx, max_len=max_len),
        jax.random.PRNGKey(0))
    return _sharded_sds(struct, param_specs_for(struct, ctx), ctx)


def _cache_specs(cache_struct, ctx: ShardCtx, batch: int):
    """Sequence-sharded specs for KV caches, judged by array rank/width.

    When the batch can't shard over the data axes (long_500k: batch=1), the
    cache SEQUENCE dim is sharded over (data x model) jointly instead — the
    whole mesh then participates in the attention reduction."""
    small_batch = batch % ctx.dp != 0

    def spec_for(path, leaf):
        name = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                        for p in path)
        nd = len(leaf.shape)
        mdl = ctx.model_axis
        dsp = ctx.data_axes if len(ctx.data_axes) > 1 else ctx.data_axes[0]
        bsp = None if small_batch else dsp
        seq = (*ctx.data_axes, mdl) if small_batch else mdl
        if "conv" in name:       # (L, B, K-1, di): di over model
            return P(None, bsp, None, mdl)
        if "ssm" in name:        # (L, B, di, n): di over model
            return P(None, bsp, mdl, None)
        if nd == 5:              # (L, B, S, KV, hd): seq over model
            return P(None, bsp, seq, None, None)
        if nd == 3:
            return P(None, bsp, None)
        if nd == 4:              # mla latent (L, B, S, r)
            return P(None, bsp, seq, None)
        return P(*([None] * nd))

    flat = jax.tree_util.tree_flatten_with_path(cache_struct)[0]
    treedef = jax.tree_util.tree_structure(cache_struct)
    return jax.tree_util.tree_unflatten(
        treedef, [spec_for(p, l) for p, l in flat])


def abstract_decode_inputs(cfg: ArchConfig, ctx: ShardCtx, batch: int,
                           seq_len: int):
    """(params, token, caches, pos) ShapeDtypeStructs for decode lowering."""
    params = abstract_params(cfg, ctx, max_len=seq_len)
    if cfg.family == "encdec":
        def mk_cache(_):
            enc_sds = jnp.zeros((batch, seq_len, cfg.d_model),
                                jnp.dtype(cfg.dtype))
            p_dummy = api.init_params(jax.random.PRNGKey(0), cfg, ctx,
                                      max_len=seq_len)
            return encdec.init_dec_cache(p_dummy, cfg, batch, seq_len,
                                         enc_sds, ctx)

        cache_struct = jax.eval_shape(mk_cache, 0)
    else:
        cache_struct = jax.eval_shape(
            lambda _: transformer.init_cache(cfg, batch, seq_len, ctx), 0)
    caches = _sharded_sds(cache_struct,
                          _cache_specs(cache_struct, ctx, batch), ctx)
    dsp = ctx.data_axes if len(ctx.data_axes) > 1 else ctx.data_axes[0]
    bsp = None if batch % ctx.dp else dsp
    token = jax.ShapeDtypeStruct(
        (batch, 1), jnp.int32, sharding=NamedSharding(ctx.mesh, P(bsp, None)))
    pos = jax.ShapeDtypeStruct(
        (batch,), jnp.int32, sharding=NamedSharding(ctx.mesh, P(bsp)))
    return params, token, caches, pos


def abstract_prefill_inputs(cfg: ArchConfig, ctx: ShardCtx, batch: int,
                            seq_len: int):
    params = abstract_params(cfg, ctx, max_len=seq_len)
    dsp = ctx.data_axes if len(ctx.data_axes) > 1 else ctx.data_axes[0]
    mk = lambda shape, dt, spec: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=NamedSharding(ctx.mesh, spec))
    if cfg.family == "encdec":
        batch_in = {"frames": mk((batch, seq_len, cfg.d_model),
                                 jnp.dtype(cfg.dtype), P(dsp, None, None))}
    else:
        batch_in = {"tokens": mk((batch, seq_len), jnp.int32,
                                 P(dsp, None))}
    return params, batch_in
