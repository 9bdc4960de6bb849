"""The MFU counting rule (bench/flops.py) against hand counts."""
import json
import os

import pytest

from bench import flops

ROOT = os.path.join(os.path.dirname(__file__), "..", "..")


def _config(name):
    with open(os.path.join(ROOT, "bench", "configs", f"{name}.json")) as f:
        return json.load(f)


def test_starcoder2_l7_by_hand():
    cfg = _config("starcoder2-3b.l7")
    d, f, layers = 3072, 12288, 7
    per_layer = (d * 24 * 128 + 2 * d * 2 * 128 + 24 * 128 * d  # q k v o
                 + 2 * d * f)                                 # up, down
    assert flops.backbone_matmul_params(cfg) == layers * per_layer
    want = (6 * layers * per_layer          # 4.03e9
            + 6 * layers * 2048 * d         # causal attention, 0.26e9
            + 6 * (1 + 2048) * d)           # positive + 2048 shared rows
    assert flops.flops_per_target(cfg, 2048) == pytest.approx(want)
    assert flops.flops_per_target(cfg, 2048) == pytest.approx(4.33e9,
                                                              rel=0.01)


def test_mfu_share():
    # 1e12 FLOP a step at 0.01 s a step on one 197 TFLOP/s chip
    assert flops.mfu_share(1e12, 0.01, 1, 197e12) == pytest.approx(
        100 * 1e14 / 197e12)
    assert flops.mfu_share(1e12, 0.01, 4, 197e12) == pytest.approx(
        100 * 1e14 / (4 * 197e12))
