"""Mixture-of-Experts layer with expert parallelism over the TP mesh axis.

Design (DESIGN.md §3): activations entering the FFN are sharded over the data
axes and replicated over `model`, so expert parallelism needs NO all-to-all —
each model shard owns E/tp experts, dispatches locally from the replicated
token set, and the per-token combine is a single psum over `model` (the same
collective a Megatron TP MLP pays).  Expert weights are additionally
FSDP-sharded over the data axes at rest and all-gathered per layer inside the
scan (ZeRO-3).

Dispatch is capacity-based (tokens above capacity drop, standard GShard
semantics) via cumsum slotting — no (T, E, C) one-hot is ever materialized.
Both the sharded path (shard_map) and a mesh-free local path (smoke tests)
run the same slotting math.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.models.layers import dense_init
from repro.sharding.rules import ShardCtx

Array = jax.Array
Params = dict


def init_moe(key, cfg: ArchConfig) -> Params:
    d, f, e = cfg.d_model, cfg.moe_d_ff, cfg.n_experts
    pd = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 6)
    p = {
        "router": dense_init(ks[0], (d, e), jnp.float32, scale=0.02),
        "w_gate": (jax.random.normal(ks[1], (e, d, f), jnp.float32)
                   / np.sqrt(d)).astype(pd),
        "w_up": (jax.random.normal(ks[2], (e, d, f), jnp.float32)
                 / np.sqrt(d)).astype(pd),
        "w_down": (jax.random.normal(ks[3], (e, f, d), jnp.float32)
                   / np.sqrt(f)).astype(pd),
    }
    if cfg.router_scale:  # deepseek-style sigmoid scoring bias
        p["router_bias"] = jnp.zeros((e,), jnp.float32)
    if cfg.n_shared_experts:
        fs = f * cfg.n_shared_experts
        p["shared"] = {
            "w_gate": dense_init(ks[4], (d, fs), pd),
            "w_up": dense_init(ks[5], (d, fs), pd),
            "w_down": dense_init(jax.random.fold_in(ks[5], 1), (fs, d), pd),
        }
    return p


def _route(p: Params, x2d: Array, cfg: ArchConfig) -> tuple[Array, Array, Array]:
    """Top-k routing.  Returns (expert_ids (T,k), weights (T,k), aux_loss)."""
    logits = x2d.astype(jnp.float32) @ p["router"]  # (T, E)
    if cfg.router_scale:
        scores = jax.nn.sigmoid(logits)
        gate_base = scores + p["router_bias"][None, :]
        topw, ids = lax.top_k(gate_base, cfg.moe_top_k)
        raw = jnp.take_along_axis(scores, ids, axis=-1)
        w = raw / jnp.maximum(raw.sum(-1, keepdims=True), 1e-9)
        probs = scores / jnp.maximum(scores.sum(-1, keepdims=True), 1e-9)
    else:
        probs = jax.nn.softmax(logits, axis=-1)
        topw, ids = lax.top_k(probs, cfg.moe_top_k)
        w = topw / jnp.maximum(topw.sum(-1, keepdims=True), 1e-9)
    # Switch-style load-balance auxiliary loss.
    e = logits.shape[-1]
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(
        jnp.sum(jax.nn.one_hot(ids[:, 0], e, dtype=jnp.float32), axis=0)
        / logits.shape[0])
    aux = e * jnp.sum(me * ce)
    return ids, w.astype(x2d.dtype), aux


def _expert_compute(xe: Array, wg: Array, wu: Array, wd: Array,
                    act: str) -> Array:
    """xe: (E_l, C, d) -> (E_l, C, d) through each expert's FFN."""
    up = jnp.einsum("ecd,edf->ecf", xe, wu)
    if act == "silu":
        gate = jnp.einsum("ecd,edf->ecf", xe, wg)
        h = jax.nn.silu(gate) * up
    else:
        h = jax.nn.gelu(up)
    return jnp.einsum("ecf,efd->ecd", h, wd)


def _dispatch_compute_combine(x2d: Array, ids: Array, w: Array, wg: Array,
                              wu: Array, wd: Array, cfg: ArchConfig,
                              e_lo, e_l: int, capacity: int) -> Array:
    """Slot tokens into this shard's e_l experts starting at (possibly
    traced) offset e_lo, run them, combine back.

    Returns this shard's additive contribution (T, d) — sum over shards (or
    identity when unsharded) yields the MoE output.
    """
    t, d = x2d.shape
    k = cfg.moe_top_k
    y = jnp.zeros((t, d), x2d.dtype)

    # Position of each (token, k) assignment within its expert, computed over
    # the flattened (k-major) order so ranks are unique.
    flat_ids = ids.reshape(-1)  # (T*k,)
    mine = (flat_ids >= e_lo) & (flat_ids < e_lo + e_l)
    local_e = jnp.clip(flat_ids - e_lo, 0, e_l - 1)
    onehot = jax.nn.one_hot(jnp.where(mine, local_e, e_l), e_l + 1,
                            dtype=jnp.int32)  # (T*k, E_l+1)
    pos = jnp.cumsum(onehot, axis=0) - 1
    pos = jnp.sum(pos * onehot, axis=-1)  # (T*k,)
    keep = mine & (pos < capacity)
    slot = jnp.where(keep, local_e * capacity + pos, e_l * capacity)

    # Dispatch one k-assignment at a time to bound the transient gather.
    xe = jnp.zeros((e_l * capacity + 1, d), x2d.dtype)
    slot_k = slot.reshape(t, k)
    for j in range(k):
        xe = xe.at[slot_k[:, j]].add(x2d, mode="drop",
                                     unique_indices=False)
    xe = xe[:-1].reshape(e_l, capacity, d)

    ye = _expert_compute(xe, wg, wu, wd, cfg.act)
    ye = ye.reshape(e_l * capacity, d)
    ye = jnp.concatenate([ye, jnp.zeros((1, d), ye.dtype)], axis=0)
    for j in range(k):
        contrib = ye[slot_k[:, j]] * w[:, j:j + 1]
        keep_j = keep.reshape(t, k)[:, j:j + 1]
        y = y + jnp.where(keep_j, contrib, 0.0)
    return y


def apply_moe(p: Params, x: Array, cfg: ArchConfig, ctx: ShardCtx
              ) -> tuple[Array, Array]:
    """MoE FFN.  x: (B, S, d).  Returns (y, aux_loss)."""
    b, s, d = x.shape
    dt = x.dtype
    e = cfg.n_experts

    if ctx.mesh is None:
        x2d = x.reshape(b * s, d)
        ids, w, aux = _route(p, x2d, cfg)
        capacity = int(max(cfg.moe_top_k, np.ceil(
            x2d.shape[0] * cfg.moe_top_k / e * cfg.capacity_factor)))
        y = _dispatch_compute_combine(
            x2d, ids, w, p["w_gate"].astype(dt), p["w_up"].astype(dt),
            p["w_down"].astype(dt), cfg, 0, e, capacity)
        y = y.reshape(b, s, d)
    else:
        mesh = ctx.mesh
        assert ctx.mode != "pure_fsdp", \
            "MoE archs must use tp_fsdp sharding (experts live on `model`)"
        tp = ctx.tp
        e_l = e // tp
        assert e % tp == 0, f"{e} experts must divide tp={tp}"
        wdsp = (None if ctx.mode == "tp" else
                (ctx.data_axes if len(ctx.data_axes) > 1
                 else ctx.data_axes[0]))  # mirrors the 'Fd' param rule
        dataspec = wdsp
        if b % ctx.dp:  # tiny batch (long-context decode): replicate tokens
            dataspec = None
            t_local = b * s
        else:
            t_local = (b // ctx.dp) * s
        capacity = int(max(cfg.moe_top_k, np.ceil(
            t_local * cfg.moe_top_k / e * cfg.capacity_factor)))

        router_bias = p.get("router_bias",
                            jnp.zeros((e,), jnp.float32))

        def sharded(x_loc, router, rbias, wg_loc, wu_loc, wd_loc):
            bl = x_loc.shape[0]
            x2d = x_loc.reshape(bl * s, d)
            rp = {"router": router}
            if cfg.router_scale:
                rp["router_bias"] = rbias
            ids, w, aux = _route(rp, x2d, cfg)
            # ZeRO-3: gather the fsdp-sharded reduction dim per layer.
            wg_f = _allgather_fsdp(wg_loc, ctx, axis=1).astype(dt)
            wu_f = _allgather_fsdp(wu_loc, ctx, axis=1).astype(dt)
            wd_f = _allgather_fsdp(wd_loc, ctx, axis=2).astype(dt)
            my = lax.axis_index(ctx.model_axis)
            lo = my * e_l
            y_part = _dispatch_compute_combine(
                x2d, ids, w, wg_f, wu_f, wd_f, cfg,
                e_lo=lo, e_l=e_l, capacity=capacity)
            y_loc = lax.psum(y_part, ctx.model_axis)
            for a in (ctx.model_axis, *ctx.data_axes):
                aux = lax.pmean(aux, a)
            return y_loc.reshape(bl, s, d), aux

        y, aux = jax.shard_map(
            sharded, mesh=mesh, check_vma=False,
            in_specs=(P(dataspec, None, None), P(None, None), P(None),
                      P(ctx.model_axis, wdsp, None),
                      P(ctx.model_axis, wdsp, None),
                      P(ctx.model_axis, None, wdsp)),
            out_specs=(P(dataspec, None, None), P()),
        )(x, p["router"], router_bias, p["w_gate"], p["w_up"], p["w_down"])

    if cfg.n_shared_experts:
        sh = p["shared"]
        up = ctx.act(x @ sh["w_up"].astype(dt), "bsf")
        if cfg.act == "silu":
            gate = ctx.act(x @ sh["w_gate"].astype(dt), "bsf")
            h = jax.nn.silu(gate) * up
        else:
            h = jax.nn.gelu(up)
        y = y + ctx.act(h @ sh["w_down"].astype(dt), "bs.")
    return ctx.act(y, "bO."), aux


def _allgather_fsdp(w: Array, ctx: ShardCtx, axis: int) -> Array:
    if ctx.mode == "tp":  # serving: weights already full along this dim
        return w
    out = w
    for a in ctx.data_axes[::-1]:
        out = lax.all_gather(out, a, axis=axis, tiled=True)
    return out
