"""The MFU counting rule: model FLOPs per softmax target.

One rule for every train cell (the arithmetic of
``benchmarks/roofline.py::active_params`` / ``model_flops``, extended with
attention and the sampled head):

* counted: 6 x the backbone's matmul parameters per target,
  causal attention at 6 * L * S * d per token, and the sampled head at
  6 * (1 + m) * d per target (the positive row plus the m negative rows it
  scores; with shared negatives each target scores the m shared rows);
* not counted: the sampler's statistics, the draws, the optimizer and any
  recomputation under remat.  Those are overhead, not model work, so a
  change that removes overhead raises the share and none can lift it
  above the chip's peak.

``cfg`` is a configuration file's dict (``bench/configs/<name>.json``).
"""
from __future__ import annotations


def backbone_matmul_params(cfg: dict) -> int:
    """Matmul parameters of the backbone (no embedding, no head, no norms
    or biases)."""
    d = cfg["d_model"]
    if cfg["family"] != "dense":
        raise ValueError(f"no FLOP rule for family {cfg['family']!r}")
    hd = cfg.get("head_dim") or d // cfg["n_heads"]
    attn = (d * cfg["n_heads"] * hd + 2 * d * cfg["n_kv_heads"] * hd
            + cfg["n_heads"] * hd * d)
    mlp = (3 if cfg.get("act", "silu") == "silu" else 2) * d * cfg["d_ff"]
    return cfg["n_layers"] * (attn + mlp)


def flops_per_target(cfg: dict, seq_len: int = 0) -> float:
    """Model FLOPs (forward + backward) per softmax target."""
    d = cfg["d_model"]
    return (6.0 * backbone_matmul_params(cfg)
            + 6.0 * cfg["n_layers"] * seq_len * d
            + 6.0 * (1 + cfg["m_negatives"]) * d)


def mfu_share(flops_per_step: float, seconds_per_step: float, chips: int,
              peak_flops: float) -> float:
    """Model FLOPs per step over (time per step x chips x peak), in %."""
    return 100.0 * flops_per_step / (seconds_per_step * chips * peak_flops)
