"""The MFU counting rule: model FLOPs per softmax target.

One rule for every train cell (the arithmetic of
``benchmarks/roofline.py::active_params`` / ``model_flops``, extended with
attention and the sampled head):

* counted: 6 x the backbone's matmul parameters per target, the
  backbone's attention where it has one (causal: 6 * L * S * d per token),
  and the sampled head at 6 * (1 + m) * d per target (the positive row
  plus the m negative rows it scores; with shared negatives each target
  scores the m shared rows);
* not counted: the sampler's statistics, the draws, embedding lookups and
  bags, the optimizer and any recomputation under remat.  Those are
  overhead, not model work, so a change that removes overhead raises the
  share and none can lift it above the chip's peak.

Each family gives its part, its matmul parameters and its attention, in
``bench/families/<family>.py``.  ``cfg`` is a configuration file's dict
(``bench/configs/<name>.json``).
"""
from __future__ import annotations


def head(m: int, width: int) -> float:
    """The sampled head's FLOPs per target: the positive and m negative
    rows of ``width``, forward and backward."""
    return 6.0 * (1 + m) * width


def backbone_matmul_params(cfg: dict) -> int:
    """Matmul parameters of the backbone (no embedding, no head, no norms
    or biases), by the configuration's family."""
    from bench import families

    return families.load(cfg).matmul_params(cfg)


def flops_per_target(cfg: dict, mix: dict | int = 0) -> float:
    """Model FLOPs (forward + backward) per softmax target of a train cell
    whose traffic is ``mix``, by the configuration's family; a bare number
    stands for an LM mix's ``seq_len``."""
    from bench import families

    if not isinstance(mix, dict):
        mix = {"seq_len": mix}
    return families.load(cfg).flops_per_target(cfg, mix)


def mfu_share(flops_per_step: float, seconds_per_step: float, chips: int,
              peak_flops: float) -> float:
    """Model FLOPs per step over (time per step x chips x peak), in %."""
    return 100.0 * flops_per_step / (seconds_per_step * chips * peak_flops)
