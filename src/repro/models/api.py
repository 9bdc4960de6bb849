"""Family dispatch: one API over all backbones.

The contract that makes the paper's technique portable (its §1 claim — "can
be applied to any model whose final layer is a dot product between a hidden
layer and class embeddings"): every backbone exposes

    init_params(key, cfg, ctx)                  -> params (with head table)
    backbone_hidden(params, batch, cfg, ctx)    -> (h (T, d_h), labels (T,), aux)

and the sampled-softmax head in repro/train/step.py consumes ONLY (h, labels,
head table).
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import encdec, lstm_lm, recsys, transformer
from repro.sharding.rules import ShardCtx

Array = jax.Array
Params = dict

LM_FAMILIES = ("dense", "moe", "ssm", "hybrid")


def init_params(key, cfg: ArchConfig, ctx: ShardCtx,
                max_len: int = 4096) -> Params:
    if cfg.family in LM_FAMILIES:
        return transformer.init_lm(key, cfg, ctx)
    if cfg.family == "encdec":
        return encdec.init_encdec(key, cfg, ctx, max_len=max_len)
    if cfg.family == "lstm":
        return lstm_lm.init_lstm_lm(key, cfg, ctx)
    if cfg.family == "recsys":
        return recsys.init_recsys(key, cfg, ctx)
    raise ValueError(f"unknown family {cfg.family}")


def head_table(params: Params, cfg: ArchConfig) -> Array:
    """The class-embedding table the sampler/loss operate on."""
    if cfg.tie_embeddings or "head" not in params:
        return params["embed"]["table"]
    return params["head"]["w"]


def hidden_width(cfg: ArchConfig) -> int:
    if cfg.family == "recsys":
        return cfg.tower_dims[-1]
    if cfg.family == "lstm":
        return cfg.lstm_units
    return cfg.d_model


@jax.named_scope("backbone")
def backbone_hidden(params: Params, batch: dict[str, Array], cfg: ArchConfig,
                    ctx: ShardCtx) -> tuple[Array, Array, Array]:
    """Forward to the last hidden layer; flatten (example, feature).

    batch keys by family:
      LM:      tokens (B, S), labels (B, S)
      encdec:  frames (B, S, d), tokens (B, S), labels (B, S)
      lstm:    tokens (B, S), labels (B, S)
      recsys:  history (B, H), user_feats (B, F), labels (B,)
    """
    if cfg.family in LM_FAMILIES:
        h, aux = transformer.hidden_states(params, batch["tokens"], cfg, ctx)
        d = h.shape[-1]
        hf = h.reshape(-1, d)
        labels = batch["labels"].reshape(-1)
        if cfg.mtp:
            h_mtp = transformer.mtp_hidden(params, h, batch["tokens"], cfg,
                                           ctx)
            # predict token t+2: labels shifted once more; last col dropped.
            hf = jnp.concatenate([hf, h_mtp[:, :-1].reshape(-1, d)], axis=0)
            mtp_labels = batch["labels"][:, 2:].reshape(-1)
            labels = jnp.concatenate([labels, mtp_labels], axis=0)
        return hf, labels, aux
    if cfg.family == "encdec":
        enc_out = encdec.encode(params, batch["frames"], cfg, ctx)
        h = encdec.decode_train(params, batch["tokens"], enc_out, cfg, ctx)
        return (h.reshape(-1, h.shape[-1]), batch["labels"].reshape(-1),
                jnp.zeros((), jnp.float32))
    if cfg.family == "lstm":
        h, aux = lstm_lm.hidden_states(params, batch["tokens"], cfg, ctx)
        return h.reshape(-1, h.shape[-1]), batch["labels"].reshape(-1), aux
    if cfg.family == "recsys":
        h, aux = recsys.hidden_states(params, batch["history"],
                                      batch["user_feats"], cfg, ctx)
        return h, batch["labels"].reshape(-1), aux
    raise ValueError(f"unknown family {cfg.family}")


def train_batch_specs(cfg: ArchConfig, global_batch: int, seq_len: int
                      ) -> dict[str, jax.ShapeDtypeStruct]:
    """ShapeDtypeStructs of one training batch (dry-run input stand-ins)."""
    i32 = jnp.int32
    if cfg.family in LM_FAMILIES or cfg.family == "lstm":
        return {
            "tokens": jax.ShapeDtypeStruct((global_batch, seq_len), i32),
            "labels": jax.ShapeDtypeStruct((global_batch, seq_len), i32),
        }
    if cfg.family == "encdec":
        return {
            "frames": jax.ShapeDtypeStruct(
                (global_batch, seq_len, cfg.d_model), jnp.dtype(cfg.dtype)),
            "tokens": jax.ShapeDtypeStruct((global_batch, seq_len), i32),
            "labels": jax.ShapeDtypeStruct((global_batch, seq_len), i32),
        }
    if cfg.family == "recsys":
        return {
            "history": jax.ShapeDtypeStruct(
                (global_batch, cfg.history_len), i32),
            "user_feats": jax.ShapeDtypeStruct(
                (global_batch, cfg.user_feature_dim), jnp.float32),
            "labels": jax.ShapeDtypeStruct((global_batch,), i32),
        }
    raise ValueError(cfg.family)
