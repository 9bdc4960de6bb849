"""Retrieval recommenders: the candidate-generation network of Covington,
Adams & Sargin, "Deep Neural Networks for YouTube Recommendations", RecSys
2016, §3.  The mean of the embeddings of a bag of recent watches,
concatenated with dense user features, goes through a tower of fully
connected layers to the user vector h; a separate output table holds one
row per item, and the softmax over the catalog is sampled.

* ``ring``: the ``zipf_recsys`` generator.  Watches and labels are item
  ids drawn i.i.d. from Zipf(s) over the catalog by inverse CDF (rank k,
  id k - 1, has probability k^-s / H); user features are N(0, 1).
* ``hidden``: the tower as the program computes it, ReLU after every layer
  but the last (a departure from the paper, which has one after the last
  too; the configuration file says so), in straightforward ``jax.numpy``;
  it imports nothing of the program.
* ``flops_per_target``: 6 x the tower's matmul parameters and the sampled
  head (``bench/flops.py``); the embedding bag is a lookup, not counted.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench import flops
from bench.reference import einsum, stores

GENERATORS = ("zipf_recsys",)


# --- traffic ----------------------------------------------------------------


def zipf_ids(key, shape, n: int, exponent: float):
    """Item ids of ``shape`` drawn i.i.d. from Zipf(exponent) over n items:
    one uniform each, searched in the normalised cumulative of k^-s.  The
    cumulative is float32, whose spacing near 1 (6e-8) is coarser than the
    rarest items' probabilities (7e-8 at n = 1M, s = 1): their mass falls
    to their neighbours, while the head of the distribution is exact."""
    ranks = jnp.arange(1, n + 1, dtype=jnp.float32)
    cdf = jnp.cumsum(ranks ** -exponent)
    cdf = cdf / cdf[-1]
    u = jax.random.uniform(key, shape, jnp.float32)
    idx = jnp.searchsorted(cdf, u, side="right")
    return jnp.minimum(idx, n - 1).astype(jnp.int32)


def zipf_recsys_ring(key, *, vocab_size: int, batch: int, history: int,
                     user_dim: int, ring: int, exponent: float):
    """``ring`` batches {history (batch, history) int32, user_feats (batch,
    user_dim) float32, labels (batch,) int32}, all made in one call."""

    def make(key):
        k_ids, k_feats = jax.random.split(key)
        ids = zipf_ids(k_ids, (ring, batch, history + 1), vocab_size,
                       exponent)
        feats = jax.random.normal(k_feats, (ring, batch, user_dim),
                                  jnp.float32)
        return [{"history": ids[i, :, :history], "user_feats": feats[i],
                 "labels": ids[i, :, history]} for i in range(ring)]

    return jax.jit(make)(key)


def ring(cfg: dict, mix: dict, key):
    if mix["generator"] == "zipf_recsys":
        return zipf_recsys_ring(
            key, vocab_size=cfg["vocab_size"], batch=mix["batch"],
            history=cfg["history_len"], user_dim=cfg["user_feature_dim"],
            ring=mix["ring"], exponent=mix["zipf_exponent"])
    raise ValueError(f"unknown training generator {mix['generator']!r}")


def targets_per_batch(cfg: dict, mix: dict) -> int:
    """One label per example."""
    return mix["batch"]


# --- the reference forward --------------------------------------------------


def hidden(p, batch, cfg: dict, mode: str = "fp32"):
    """(h (B, tower_dims[-1]), labels (B,)) of a batch."""
    store = stores(cfg)
    bag = p["embed"]["table"][batch["history"]].astype(jnp.float32)
    x = jnp.concatenate([jnp.mean(bag, axis=1),
                         batch["user_feats"].astype(jnp.float32)], axis=-1)
    tower, n = p["tower"], len(cfg["tower_dims"])
    for i in range(n):
        x = einsum(mode, "bi,io->bo", x, tower[f"w{i}"].astype(jnp.float32),
                   store=store) + tower[f"b{i}"].astype(jnp.float32)
        if i < n - 1:
            x = jax.nn.relu(x)
    return x, batch["labels"].reshape(-1)


def head_table(p):
    """The separate output table, one row per item."""
    return p["head"]["w"]


# --- model FLOPs ------------------------------------------------------------


def matmul_params(cfg: dict) -> int:
    """Matmul parameters of the tower (no tables, no biases)."""
    dims = [cfg["d_model"] + cfg["user_feature_dim"], *cfg["tower_dims"]]
    return sum(a * b for a, b in zip(dims, dims[1:]))


def flops_per_target(cfg: dict, mix: dict) -> float:
    """Model FLOPs (forward + backward) per softmax target: one per
    example."""
    return (6.0 * matmul_params(cfg)
            + flops.head(cfg["m_negatives"], cfg["tower_dims"][-1]))
