"""mfu.train: model FLOPs per step (bench/flops.py) over (time per step x
chips x the chip's bf16 peak), in %, over the traced window's steps
(host clock)."""
from bench.flops import mfu_share


def read(run):
    if run.get("kind") != "train" or not run.get("steps"):
        return None
    return mfu_share(run["flops_per_step"], run["seconds"] / run["steps"],
                     run["chips"], run["peak_flops"])
