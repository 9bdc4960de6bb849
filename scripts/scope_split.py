"""Device time per step of each layer scope of a benchmark train cell.

Usage (on a TPU, from the repository's root):

    python scripts/scope_split.py --workload train.starcoder2-3b.l7 \
        --seed 1234 --seconds 5

Builds the cell's compiled step from the seed as ``bench.run`` does,
profiles a window of back-to-back steps and splits the device's busy time
among the scopes the program names (``bench/scopes.py``): each op of the
trace is found by name among the instructions of the compiled step's
optimized HLO text, and charged its self time.  Prints one JSON line: ms
per step of each scope, of ``remat`` (recompute under ``jax.checkpoint``,
whatever its scope) and of the busy time, with the share of busy time
whose op was found in the text.  Exits non-zero off a TPU.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import shutil
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

from bench import harness, scopes, tracing  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    cell = harness.load_cell(ROOT, args.workload)
    harness.add_program_path(ROOT)
    try:
        device = harness.check_device(cell.chips)
    except harness.NoChip as e:
        print(f"scope_split: {e}", file=sys.stderr)
        return 3
    harness.enable_compile_cache(ROOT)
    from bench.kinds import train

    setup = train.Setup(train.Inputs(cell, args.seed), print)
    setup.first_steps()
    log_dir = str(ROOT / harness.TRACE_DIR)
    with tracing.capture(log_dir):
        w = train.window(setup, args.seconds)
    trace = tracing.load(log_dir)
    shutil.rmtree(log_dir, ignore_errors=True)
    lo, hi = trace.window()
    run = dict(kind="train", steps=w["steps"], trace=trace, lo=lo, hi=hi,
               hlo_text=setup.compiled.as_text())
    names = scopes.LAYER_SCOPES + (scopes.UNSCOPED, "remat")
    out = {name: scopes.device_ms(run, name) for name in names}
    chips = scopes.splits(run)
    busy = sum(s["busy"] for s in chips)
    out.update(
        busy=busy / len(chips) / w["steps"] / 1e6,
        mapped_share=sum(s["mapped"] for s in chips) / busy,
        steps=w["steps"], window_s=(hi - lo) / 1e9,
        targets_per_s=w["steps"] * train.targets_per_batch(cell.traffic)
        / w["seconds"], device=device)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
