"""Offered rates against what a serving cell sustains, to find its knee.

    python3 -m bench.sweep --workload <cell> --seed <n> --seconds <s> \
        --rates 2000,4000,8000

One set-up, then a window at each rate, each printed as one JSON line:
requests offered and completed, expired and failed, the served rate, the
latency percentiles from each request's scheduled arrival, how late the
client ran, the queue at the close and the microbatches' fill.  The knee is
the highest rate at which at least 99% of the offered requests complete,
none expires, and the queue at the close holds less than one largest
bucket.  A serving cell's mix fixes its rate at 0.8 x the knee; the
benchmark's own runs never sweep.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

from bench import harness


def main(argv=None, root=None, platform: str = "tpu") -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True,
                    help="comma-separated offered rates, requests/s")
    args = ap.parse_args(argv)
    root = pathlib.Path(root or ".").resolve()
    cell = harness.load_cell(root, args.workload)
    if cell.traffic["kind"] != "serve":
        print(f"bench.sweep: {cell.name} is not a serving cell",
              file=sys.stderr)
        return 2
    harness.add_program_path(root)
    try:
        harness.check_device(cell.chips, platform)
    except harness.NoChip as e:
        print(f"bench.sweep: {e}", file=sys.stderr)
        return 3
    harness.enable_compile_cache(root)
    from bench.kinds import serve

    rates = [float(r) for r in args.rates.split(",")]
    for s in serve.sweep(cell, args.seed, rates, args.seconds,
                         lambda m: print(m, file=sys.stderr, flush=True)):
        largest = max(cell.traffic["buckets"])
        s["sustained"] = (s["completed"] >= 0.99 * s["offered"]
                          and s["expired"] == 0
                          and s["queue_at_close"] < largest)
        print(json.dumps(dict(s, workload=cell.name)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
