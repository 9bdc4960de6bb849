"""The numbers that decide ``correct``, and the verdict against limits.

Training (per cell, two steps; ``reference.train_steps``):
  loss_gap    |first step's loss - reference| / reference
  loss_gap2   the same for the second step's loss.  Reported and not
              judged where the cell's checks file gives it no limit
              (PERF.md says why: Adam's first update is nearly a sign, so
              elements whose gradient is round-off in bfloat16 move by
              +-lr on either side at random, and the two sides' second
              losses part by more than a lower precision's first)
  grad_gap    worst leaf's |norm of the first clipped gradient - the
              reference's| / max(reference leaf norm, median leaf norm)
  grad_diff   worst leaf's norm of the first clipped gradient's
              difference from the reference's, on the same scale.  A
              leaf's norm is all but blind to rounding that is as often up
              as down, and this is not (PERF.md)
  grad_diff_med  the same, of the median leaf
  rows_gap    the share of the class table's rows whose first gradient is
              nought on one side and not on the other, over the rows the
              reference moves: a row moves where it is an input, a label or
              a drawn negative, so this reads how far the program's draws
              part from the reference's (PERF.md)
  change_gap  the same for the parameters' change after the two steps,
              over the leaves whose reference gradient is at least a
              thousandth of the median leaf's (a key bias under softmax
              has a gradient of nought to rounding and moves under Adam
              by round-off alone)
"""
from __future__ import annotations

import math

import numpy as np

#: a leaf counts for change_gap when its reference gradient norm is at
#: least this share of the median leaf's
MOVED_SHARE = 1e-3


def loss_gap(got, want) -> float:
    return max(abs(g - w) / abs(w) for g, w in zip(got, want))


def norm_gap(got, want, counted=None) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    if counted is None:
        counted = np.ones(want.shape, bool)
    scale = np.maximum(want, np.median(want[counted]))
    return float(np.max(np.abs(got - want)[counted] / scale[counted]))


def relative(diff_norm, want) -> np.ndarray:
    """Each leaf's diff_norm / max(want, median of want)."""
    want = np.asarray(want, np.float64)
    return (np.asarray(diff_norm, np.float64)
            / np.maximum(want, np.median(want)))


def moved_leaves(ref_grad_norm) -> np.ndarray:
    g = np.asarray(ref_grad_norm, np.float64)
    return g >= MOVED_SHARE * np.median(g)


def train_numbers(prog: dict, ref: dict) -> dict:
    """prog/ref: {"loss": [..], "grad_norm": (L,), "change_norm": (L,)};
    prog may carry "grad_diff_norm" (L,), its first gradient's difference
    from ref's, per leaf, and "rows_gap"."""
    counted = moved_leaves(ref["grad_norm"])
    out = {"loss_gap": loss_gap(prog["loss"][:1], ref["loss"][:1]),
           "loss_gap2": loss_gap(prog["loss"][1:2], ref["loss"][1:2]),
           "grad_gap": norm_gap(prog["grad_norm"], ref["grad_norm"]),
           "change_gap": norm_gap(prog["change_norm"], ref["change_norm"],
                                  counted)}
    if "grad_diff_norm" in prog:
        rel = relative(prog["grad_diff_norm"], ref["grad_norm"])
        out.update(grad_diff=float(np.max(rel)),
                   grad_diff_med=float(np.median(rel)))
    if "rows_gap" in prog:
        out["rows_gap"] = prog["rows_gap"]
    return out


def worst_leaves(prog: dict, ref: dict, names) -> dict:
    """For each per-leaf number, the leaf that sets it and its reading."""
    counted = moved_leaves(ref["grad_norm"])
    want_g = np.asarray(ref["grad_norm"], np.float64)
    want_c = np.asarray(ref["change_norm"], np.float64)
    per_leaf = {
        "grad_gap": np.abs(np.asarray(prog["grad_norm"]) - want_g)
        / np.maximum(want_g, np.median(want_g)),
        "change_gap": np.where(
            counted, np.abs(np.asarray(prog["change_norm"]) - want_c)
            / np.maximum(want_c, np.median(want_c[counted])), 0.0)}
    if "grad_diff_norm" in prog:
        per_leaf["grad_diff"] = relative(prog["grad_diff_norm"], want_g)
    return {k: (names[int(np.argmax(v))], float(np.max(v)))
            for k, v in per_leaf.items()}


def verdict(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """(all within their limits, {name: {"value", "limit"}}) over the
    numbers that ``limits`` names.  A number that is not finite fails."""
    checks, ok = {}, True
    for name, lim in limits.items():
        value, limit = numbers[name], lim["limit"]
        ok &= math.isfinite(value) and value <= limit
        checks[name] = {"value": value, "limit": limit}
    return bool(ok), checks
