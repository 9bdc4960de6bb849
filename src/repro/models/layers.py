"""Model primitives: norms, embeddings, RoPE, attention (GQA + MLA), MLP.

Conventions:
  * params are nested dicts of jnp arrays; init functions return them;
  * compute dtype = cfg.dtype; storage dtype = cfg.param_dtype; norms,
    softmax statistics and logits in fp32;
  * every apply function takes a ShardCtx for activation constraints; pass
    ``local_ctx()`` for single-device smoke use;
  * attention is chunked online-softmax (flash-style) in pure jnp — this is
    also the reference for the Pallas kernels in repro/kernels; on one TPU
    device, causal self-attention whose shapes the splash kernels take runs
    on them instead (`flash_applies`).
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.kernels import ops as kops
from repro.sharding.rules import ShardCtx

Array = jax.Array
Params = dict


def _dtype(cfg: ArchConfig):
    return jnp.dtype(cfg.dtype)


def _pdtype(cfg: ArchConfig):
    return jnp.dtype(cfg.param_dtype)


def dense_init(key, shape, dtype, scale: float | None = None):
    fan_in = shape[0] if len(shape) > 1 else 1
    scale = scale if scale is not None else 1.0 / np.sqrt(fan_in)
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


# --- norms -------------------------------------------------------------------


def init_norm(cfg: ArchConfig, dim: int | None = None) -> Params:
    dim = dim or cfg.d_model
    p = {"scale": jnp.ones((dim,), jnp.float32)}
    if cfg.norm == "layernorm":
        p["bias"] = jnp.zeros((dim,), jnp.float32)
    return p


def apply_norm(p: Params, x: Array, cfg: ArchConfig) -> Array:
    x32 = x.astype(jnp.float32)
    if cfg.norm == "layernorm" and "bias" in p:
        mean = jnp.mean(x32, axis=-1, keepdims=True)
        var = jnp.var(x32, axis=-1, keepdims=True)
        out = (x32 - mean) * jax.lax.rsqrt(var + 1e-5) * p["scale"] + p["bias"]
    else:
        ms = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
        out = x32 * jax.lax.rsqrt(ms + 1e-6) * p["scale"]
    return out.astype(x.dtype)


def rms_norm_only(x: Array, scale: Array, eps: float = 1e-6) -> Array:
    x32 = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(ms + eps) * scale).astype(x.dtype)


# --- embeddings / positions --------------------------------------------------


def init_embed(key, cfg: ArchConfig) -> Params:
    return {"table": dense_init(key, (cfg.vocab_size, cfg.d_model),
                                _pdtype(cfg), scale=0.02)}


def apply_embed(p: Params, ids: Array, cfg: ArchConfig,
                ctx: ShardCtx) -> Array:
    out = p["table"].astype(_dtype(cfg))[ids]
    return ctx.act(out, "bO.")


def init_pos_embed(key, cfg: ArchConfig, max_pos: int) -> Params:
    return {"table": dense_init(key, (max_pos, cfg.d_model), _pdtype(cfg),
                                scale=0.02)}


def rope(x: Array, positions: Array, theta: float) -> Array:
    """x: (..., S, H, hd) rotated pairwise; positions: (..., S)."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # (..,S,half)
    cos = jnp.cos(angles)[..., :, None, :]
    sin = jnp.sin(angles)[..., :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# --- attention (GQA) ---------------------------------------------------------


def padded_heads(cfg: ArchConfig, tp: int) -> tuple[int, int]:
    """Pad head counts up to TP divisibility (zero-weight heads; exactness is
    preserved — see DESIGN.md §Arch-applicability)."""
    def up(h):
        return max(tp, ((h + tp - 1) // tp) * tp)
    nh = up(cfg.n_heads)
    nkv = up(cfg.n_kv_heads) if cfg.n_kv_heads else nh
    # q heads per kv group must stay integral after padding
    while nh % nkv:
        nkv += tp
    return nh, nkv


def init_attention(key, cfg: ArchConfig, tp: int = 1,
                   d_model: int | None = None) -> Params:
    d = d_model or cfg.d_model
    hd = cfg.resolved_head_dim
    nh, nkv = padded_heads(cfg, tp)
    ks = jax.random.split(key, 4)
    pd = _pdtype(cfg)
    p = {
        "wq": dense_init(ks[0], (d, nh * hd), pd),
        "wk": dense_init(ks[1], (d, nkv * hd), pd),
        "wv": dense_init(ks[2], (d, nkv * hd), pd),
        "wo": dense_init(ks[3], (nh * hd, d), pd),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((nh * hd,), pd)
        p["bk"] = jnp.zeros((nkv * hd,), pd)
        p["bv"] = jnp.zeros((nkv * hd,), pd)
    if cfg.qk_norm:
        p["q_norm"] = {"scale": jnp.ones((hd,), jnp.float32)}
        p["k_norm"] = {"scale": jnp.ones((hd,), jnp.float32)}
    return p


def _qkv(p: Params, x: Array, cfg: ArchConfig, positions: Array,
         ctx: ShardCtx, rope_on: bool = True):
    dt = _dtype(cfg)
    hd = cfg.resolved_head_dim
    q = x @ p["wq"].astype(dt)
    k = x @ p["wk"].astype(dt)
    v = x @ p["wv"].astype(dt)
    if cfg.qkv_bias:
        q = q + p["bq"].astype(dt)
        k = k + p["bk"].astype(dt)
        v = v + p["bv"].astype(dt)
    B, S = x.shape[0], x.shape[1]
    q = q.reshape(B, S, -1, hd)
    k = k.reshape(B, S, -1, hd)
    v = v.reshape(B, S, -1, hd)
    if cfg.qk_norm:
        q = rms_norm_only(q, p["q_norm"]["scale"])
        k = rms_norm_only(k, p["k_norm"]["scale"])
    if rope_on and cfg.rope_theta > 0:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    q = ctx.act(q, "bsh.")
    k = ctx.act(k, "bsh.")
    v = ctx.act(v, "bsh.")
    return q, k, v


@jax.named_scope("attention")
def chunked_attention(q: Array, k: Array, v: Array, *, causal: bool,
                      chunk: int, q_offset: int = 0) -> Array:
    """Online-softmax attention over KV chunks (flash-style, pure jnp).

    q: (B, Sq, H, hd); k: (B, Sk, KV, hd); v: (B, Sk, KV, hv) with H a
    multiple of KV (GQA).  hv may differ from hd (MLA).
    Memory is O(Sq * chunk) per head instead of O(Sq * Sk).
    """
    B, Sq, H, hd = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    hv = v.shape[-1]
    group = H // KV
    scale = 1.0 / np.sqrt(hd)
    qf = (q * scale).astype(jnp.float32).reshape(B, Sq, KV, group, hd)
    n_chunks = -(-Sk // chunk)
    pad = n_chunks * chunk - Sk
    kf = jnp.pad(k.astype(jnp.float32), ((0, 0), (0, pad), (0, 0), (0, 0)))
    vf = jnp.pad(v.astype(jnp.float32), ((0, 0), (0, pad), (0, 0), (0, 0)))
    kf = kf.reshape(B, n_chunks, chunk, KV, hd)
    vf = vf.reshape(B, n_chunks, chunk, KV, hv)

    q_pos = q_offset + jnp.arange(Sq)

    def body(carry, inp):
        m, l, acc = carry
        kc, vc, c_idx = inp
        k_pos = c_idx * chunk + jnp.arange(chunk)
        s = jnp.einsum("bqkgh,bckh->bqkgc", qf, kc)  # (B,Sq,KV,group,chunk)
        valid = k_pos < Sk
        if causal:
            mask = (k_pos[None, :] <= q_pos[:, None]) & valid[None, :]
        else:
            mask = jnp.broadcast_to(valid[None, :], (Sq, chunk))
        s = jnp.where(mask[None, :, None, None, :], s, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
        p = jnp.exp(s - m_safe[..., None])
        p = jnp.where(mask[None, :, None, None, :], p, 0.0)
        corr = jnp.exp(jnp.where(jnp.isneginf(m), m_new, m - m_new))
        corr = jnp.where(jnp.isneginf(m_new), 1.0, corr)
        l_new = l * corr + jnp.sum(p, axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bqkgc,bckh->bqkgh", p, vc)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, Sq, KV, group), -jnp.inf)
    l0 = jnp.zeros((B, Sq, KV, group))
    a0 = jnp.zeros((B, Sq, KV, group, hv))
    ks = jnp.moveaxis(kf, 1, 0)
    vs = jnp.moveaxis(vf, 1, 0)
    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, a0), (ks, vs, jnp.arange(n_chunks)))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(B, Sq, H, hv).astype(q.dtype)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def flash_applies(q: Array, k: Array, v: Array, *, causal: bool,
                  ctx: ShardCtx) -> bool:
    """Whether self-attention over these q, k, v runs on the splash
    kernels (`flash_attention`): on a TPU, on one device, causal with
    Sq == Sk, v's head width equal to q's, and the head width and the
    sequence both multiples of 128.  Every other input takes
    `chunked_attention`."""
    s, hd = q.shape[1], q.shape[-1]
    return (causal and _on_tpu()
            and (ctx.mesh is None or ctx.mesh.size == 1)
            and k.shape[1] == s and v.shape[-1] == hd
            and hd % 128 == 0 and s % 128 == 0)


@jax.named_scope("attention")
def flash_attention(q: Array, k: Array, v: Array) -> Array:
    """Causal self-attention on the splash flash kernels, forward and
    backward (`kernels/ops.py::causal_attention`); the same shapes and
    result as `chunked_attention(q, k, v, causal=True, ...)`."""
    return kops.causal_attention(q, k, v)


def attn_forward(p: Params, x: Array, positions: Array, cfg: ArchConfig,
                 ctx: ShardCtx, *, causal: bool = True,
                 kv_override: tuple[Array, Array] | None = None) -> Array:
    """Full-sequence attention (train / prefill / encoder)."""
    q, k, v = _qkv(p, x, cfg, positions, ctx, rope_on=not cfg.learned_pos)
    if kv_override is not None:
        k, v = kv_override
    if kv_override is None and flash_applies(q, k, v, causal=causal,
                                             ctx=ctx):
        out = flash_attention(q, k, v)
    else:
        out = chunked_attention(q, k, v, causal=causal, chunk=cfg.attn_chunk)
    out = ctx.act(out, "bsh.")
    B, S = x.shape[0], x.shape[1]
    dt = _dtype(cfg)
    y = out.reshape(B, S, -1) @ p["wo"].astype(dt)
    return ctx.act(y, "bO.")


def cross_kv(p: Params, enc: Array, cfg: ArchConfig, ctx: ShardCtx):
    """K,V from encoder states for cross attention (no RoPE)."""
    dt = _dtype(cfg)
    hd = cfg.resolved_head_dim
    B, S = enc.shape[0], enc.shape[1]
    k = (enc @ p["wk"].astype(dt)).reshape(B, S, -1, hd)
    v = (enc @ p["wv"].astype(dt)).reshape(B, S, -1, hd)
    return ctx.act(k, "bsh."), ctx.act(v, "bsh.")


def attn_decode(p: Params, x: Array, cache_k: Array, cache_v: Array,
                pos: Array, cfg: ArchConfig, ctx: ShardCtx, *,
                update_cache: bool = True,
                rope_on: bool = True) -> tuple[Array, Array, Array]:
    """One-token decode against a (possibly seq-sharded) KV cache.

    x: (B, 1, d); cache_k/v: (B, S, KV, hd) laid out with seq over the model
    axis (SP) — the softmax reductions over seq become cross-shard psums that
    GSPMD inserts.  pos: (B,) current positions.  Returns (y, new_k, new_v).
    """
    dt = _dtype(cfg)
    hd = cfg.resolved_head_dim
    B = x.shape[0]
    q = (x @ p["wq"].astype(dt)).reshape(B, 1, -1, hd)
    k = (x @ p["wk"].astype(dt)).reshape(B, 1, -1, hd)
    v = (x @ p["wv"].astype(dt)).reshape(B, 1, -1, hd)
    if cfg.qkv_bias:
        q = q + p["bq"].astype(dt).reshape(1, 1, *q.shape[2:])
        k = k + p["bk"].astype(dt).reshape(1, 1, *k.shape[2:])
        v = v + p["bv"].astype(dt).reshape(1, 1, *v.shape[2:])
    if cfg.qk_norm:
        q = rms_norm_only(q, p["q_norm"]["scale"])
        k = rms_norm_only(k, p["k_norm"]["scale"])
    if rope_on and not cfg.learned_pos and cfg.rope_theta > 0:
        q = rope(q, pos[:, None], cfg.rope_theta)
        k = rope(k, pos[:, None], cfg.rope_theta)

    if update_cache:
        # Scatter the new token into the cache at its position (the cache may
        # store fewer KV heads than the TP-padded projection produces).
        nkv_c = cache_k.shape[2]
        bidx = jnp.arange(B)
        cache_k = cache_k.at[bidx, pos].set(
            k[:, 0, :nkv_c].astype(cache_k.dtype))
        cache_v = cache_v.at[bidx, pos].set(
            v[:, 0, :nkv_c].astype(cache_v.dtype))
        cache_k = ctx.act(cache_k, "bS..")
        cache_v = ctx.act(cache_v, "bS..")

    KV = cache_k.shape[2]
    H = q.shape[2]
    group = H // KV
    qf = q.astype(jnp.float32).reshape(B, KV, group, hd) / np.sqrt(hd)
    s = jnp.einsum("bkgh,bskh->bkgs", qf, cache_k.astype(jnp.float32))
    valid = jnp.arange(cache_k.shape[1])[None, :] <= pos[:, None]
    s = jnp.where(valid[:, None, None, :], s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgs,bskh->bkgh", w, cache_v.astype(jnp.float32))
    y = o.reshape(B, 1, H * hd).astype(dt) @ p["wo"].astype(dt)
    return ctx.act(y, "bs."), cache_k, cache_v


# --- MLP ----------------------------------------------------------------------


def init_mlp(key, cfg: ArchConfig, d_ff: int | None = None,
             d_model: int | None = None) -> Params:
    d = d_model or cfg.d_model
    f = d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    pd = _pdtype(cfg)
    p = {"w_up": dense_init(ks[1], (d, f), pd),
         "w_down": dense_init(ks[2], (f, d), pd)}
    if cfg.act == "silu":
        p["w_gate"] = dense_init(ks[0], (d, f), pd)
    return p


def apply_mlp(p: Params, x: Array, cfg: ArchConfig, ctx: ShardCtx) -> Array:
    dt = _dtype(cfg)
    up = ctx.act(x @ p["w_up"].astype(dt), "bsf")
    if "w_gate" in p:
        gate = ctx.act(x @ p["w_gate"].astype(dt), "bsf")
        h = jax.nn.silu(gate) * up
    else:
        h = jax.nn.gelu(up)
    y = h @ p["w_down"].astype(dt)
    return ctx.act(y, "bO.")
