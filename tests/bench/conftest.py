"""The benchmark's CPU tests import ``bench`` from the repository's root;
fixtures of cells cut to a size a test can hold."""
import argparse
import json
import os
import sys

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import harness  # noqa: E402

SEED = 2_147_483_777
LM = dict(vocab_size=512, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
          head_dim=16, d_ff=128, m_negatives=64, sampler_block=32,
          sampler_proj_rank=16, dtype="float32", param_dtype="float32")


def _json(*parts):
    with open(os.path.join(ROOT, "bench", *parts)) as f:
        return json.load(f)


def _cell(name, config, cut, traffic, mix_cut):
    """The cell ``name`` with its configuration and mix cut to a tiny
    size, under the limits of bench/checks/<name>.json."""
    cfg = dict(_json("configs", f"{config}.json"), **cut)
    mix = dict(_json("workloads", f"{traffic}.json"), **mix_cut)
    return harness.Cell(name, 1, cfg, mix, _json("checks", f"{name}.json"),
                        [], [], ROOT)


@pytest.fixture(scope="module")
def lm():
    return _cell("train.starcoder2-3b.l7", "starcoder2-3b.l7", LM,
                 "lm.b1s2048", dict(seq_len=64, ring=4))


class Env:
    """The harness's side of a run, without the look for a chip."""

    def __init__(self):
        self.compiles = harness.CompileCounter()

    log = staticmethod(lambda msg: None)
    memory_peak = staticmethod(lambda: 0)


@pytest.fixture
def env():
    return Env()


@pytest.fixture
def args():
    return argparse.Namespace(seed=SEED, seconds=0.3, trace=0)
