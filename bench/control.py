"""Readings of the control and of planted faults, for setting limits.

    python3 -m bench.control --workload <cell> --seeds 1,2,3

For each seed, the cell's kind computes at the cell's own size the numbers
that decide ``correct`` for the program itself, for the reference in the
nearest lower precision put in the program's place, and for each fault the
cell can have, planted in the reference; each set also gets its verdict
under the cell's present limits.  Each printed line is one JSON object; a
limit lies above the program's readings and below the others.  The
benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import importlib
import json
import pathlib
import sys

from bench import compare, harness


def main(argv=None, root=None, platform: str = "tpu") -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated whole numbers")
    ap.add_argument("--dump", default=None,
                    help="a directory for each seed's per-leaf readings")
    args = ap.parse_args(argv)
    root = pathlib.Path(root or ".").resolve()
    cell = harness.load_cell(root, args.workload)
    harness.add_program_path(root)
    try:
        harness.check_device(cell.chips, platform)
    except harness.NoChip as e:
        print(f"bench.control: {e}", file=sys.stderr)
        return 3
    harness.enable_compile_cache(root)
    kind = importlib.import_module(f"bench.kinds.{cell.traffic['kind']}")
    cache = {}
    for seed in (int(s) for s in args.seeds.split(",")):
        detail = {} if args.dump else None
        readings = kind.control(cell, seed, lambda m: print(m, flush=True),
                                cache, detail)
        if args.dump:
            out = pathlib.Path(args.dump)
            out.mkdir(parents=True, exist_ok=True)
            (out / f"{cell.name}.{seed}.json").write_text(json.dumps(detail))
        verdicts = {name: compare.verdict(numbers, cell.checks)[0]
                    for name, numbers in readings.items()
                    if name not in ("witness", "draw_noise")}
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "readings": readings, "correct": verdicts}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
