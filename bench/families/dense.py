"""Dense decoder-only language models: a pre-norm decoder (layernorm, GQA
attention with RoPE and q/k/v biases, tanh-GELU MLP) over a Markov
language.

* ``ring``: the ``markov_lm`` generator, the ``SyntheticLM`` of
  ``src/repro/data/synthetic.py`` with its tables drawn from the seed.
* ``hidden``: ``lm_hidden``, written from the model's description in
  straightforward ``jax.numpy``; it imports nothing of the program.
* ``flops_per_target``: 6 x the backbone's matmul parameters, causal
  attention at 6 * L * S * d per token and the sampled head
  (``bench/flops.py``).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench import flops
from bench.reference import einsum, stored

GENERATORS = ("markov_lm",)


# --- traffic ----------------------------------------------------------------


def markov_lm_ring(key, *, vocab_size: int, batch: int, seq_len: int,
                   ring: int, rank: int = 16, temperature: float = 1.0):
    """``ring`` LM batches {tokens, labels: (batch, seq_len) int32}.

    An order-1 Markov language with low-rank transition logits,
    P(next | prev) ∝ exp(<E[next], C[prev]> * temperature / sqrt(rank)),
    the ``SyntheticLM`` of ``src/repro/data/synthetic.py`` with its tables
    drawn from ``key``; all ring batches are drawn in one scan."""

    def make(key):
        k_tab, k_first, k_seq = jax.random.split(key, 3)
        k1, k2 = jax.random.split(k_tab)
        e = jax.random.normal(k1, (vocab_size, rank))
        c = jax.random.normal(k2, (vocab_size, rank))
        scale = temperature / np.sqrt(rank)
        rows = ring * batch

        def step(prev, k):
            nxt = jax.random.categorical(k, (c[prev] @ e.T) * scale, axis=-1)
            return nxt, nxt

        first = jax.random.randint(k_first, (rows,), 0, vocab_size)
        _, seq = jax.lax.scan(step, first, jax.random.split(k_seq, seq_len))
        seq = jnp.moveaxis(seq, 0, 1)  # (rows, seq_len)
        tokens = jnp.concatenate([first[:, None], seq[:, :-1]], axis=1)
        shape = (ring, batch, seq_len)
        return [{"tokens": t, "labels": y} for t, y in zip(
            tokens.astype(jnp.int32).reshape(shape),
            seq.astype(jnp.int32).reshape(shape))]

    return jax.jit(make)(key)


def ring(cfg: dict, mix: dict, key):
    if mix["generator"] == "markov_lm":
        return markov_lm_ring(
            key, vocab_size=cfg["vocab_size"], batch=mix["batch"],
            seq_len=mix["seq_len"], ring=mix["ring"], rank=mix["markov_rank"],
            temperature=mix["temperature"])
    raise ValueError(f"unknown training generator {mix['generator']!r}")


def targets_per_batch(cfg: dict, mix: dict) -> int:
    return mix["batch"] * mix["seq_len"]


# --- the reference forward --------------------------------------------------


def layer_norm(x, p, eps: float = 1e-5):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def rope(x, theta: float):
    """x: (B, S, H, hd); rotate the two halves of each head by position."""
    s, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    inv = 1.0 / theta ** (np.arange(half, dtype=np.float32) / half)
    ang = np.arange(s, dtype=np.float32)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(ang))[None, :, None, :]
    sin = jnp.asarray(np.sin(ang))[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def lm_hidden(p, tokens, cfg: dict, mode: str = "fp32"):
    """tokens (B, S) -> last hidden states (B*S, d), float32."""
    b, s = tokens.shape
    d, nh, nkv = cfg["d_model"], cfg["n_heads"], cfg["n_kv_heads"]
    hd = cfg.get("head_dim") or d // nh
    group = nh // nkv
    if cfg.get("norm") != "layernorm" or cfg.get("act") != "gelu":
        raise ValueError("the LM reference covers layernorm + gelu models")
    if len(p["segments"]) != 1:
        raise ValueError("the LM reference covers one homogeneous segment")
    f32 = functools.partial(jax.tree_util.tree_map,
                            lambda a: a.astype(jnp.float32))

    def layer(x, lp):
        lp = f32(lp)
        a = lp["attn"]
        h = layer_norm(x, lp["norm1"])
        q = einsum(mode, "bsd,de->bse", h, a["wq"])
        k = einsum(mode, "bsd,de->bse", h, a["wk"])
        v = einsum(mode, "bsd,de->bse", h, a["wv"])
        if cfg.get("qkv_bias"):
            q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
        q = rope(q.reshape(b, s, nh, hd), cfg["rope_theta"])
        k = rope(k.reshape(b, s, nkv, hd), cfg["rope_theta"])
        v = v.reshape(b, s, nkv, hd)
        q = q.reshape(b, s, nkv, group, hd) / math.sqrt(hd)
        scores = einsum(mode, "bqkgh,bckh->bkgqc", q, k)
        causal = np.tril(np.ones((s, s), bool))
        scores = jnp.where(causal, scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        o = einsum(mode, "bkgqc,bckh->bqkgh", probs, v).reshape(b, s, nh * hd)
        x = stored(mode, x + einsum(mode, "bse,ed->bsd", o, a["wo"]))
        h2 = layer_norm(x, lp["norm2"])
        up = einsum(mode, "bsd,df->bsf", h2, lp["mlp"]["w_up"])
        x = stored(mode, x + einsum(mode, "bsf,fd->bsd", gelu_tanh(up),
                                    lp["mlp"]["w_down"]))
        return x, None

    x = stored(mode, p["embed"]["table"].astype(jnp.float32)[tokens])
    x, _ = jax.lax.scan(jax.checkpoint(layer), x, p["segments"][0])
    x = layer_norm(x, f32(p["final_norm"]))
    return x.reshape(b * s, d)


def hidden(p, batch, cfg: dict, mode: str = "fp32"):
    """(h (T, d), labels (T,)) of a batch."""
    return lm_hidden(p, batch["tokens"], cfg, mode), \
        batch["labels"].reshape(-1)


def head_table(p):
    """The class embeddings: the output head, or the embedding table where
    the two are tied (there is no ``head`` leaf)."""
    return p["head"]["w"] if "head" in p else p["embed"]["table"]


# --- model FLOPs ------------------------------------------------------------


def matmul_params(cfg: dict) -> int:
    """Matmul parameters of the backbone (no embedding, no head, no norms
    or biases)."""
    d = cfg["d_model"]
    hd = cfg.get("head_dim") or d // cfg["n_heads"]
    attn = (d * cfg["n_heads"] * hd + 2 * d * cfg["n_kv_heads"] * hd
            + cfg["n_heads"] * hd * d)
    mlp = (3 if cfg.get("act", "silu") == "silu" else 2) * d * cfg["d_ff"]
    return cfg["n_layers"] * (attn + mlp)


def flops_per_target(cfg: dict, mix: dict) -> float:
    """Model FLOPs (forward + backward) per softmax target."""
    d = cfg["d_model"]
    return (6.0 * matmul_params(cfg)
            + 6.0 * cfg["n_layers"] * mix.get("seq_len", 0) * d
            + flops.head(cfg["m_negatives"], d))
