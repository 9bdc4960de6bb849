"""Run one cell of the benchmark once.

  python3 -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout.  Exits non-zero, printing no result, when
JAX finds no TPU or fewer chips than the cell asks for.  The last line of
stdout is the result: ``correct``, ``attempted``, ``failed``, ``metrics``
(the cell's end-to-end metrics, or with ``--trace 1`` its per-layer ones)
and ``device``, then ``checks``, the numbers compared and their limits,
which are also the last lines of stderr.
"""
from __future__ import annotations

import argparse
import importlib
import pathlib
import shutil
import sys
import time

from bench import harness, tracing
from bench.peaks import peak_for


class Env:
    """What a kind's ``run`` may use from the harness."""

    def __init__(self, devices):
        self.devices = devices
        self.compiles = harness.CompileCounter()

    @staticmethod
    def log(msg: str) -> None:
        print(msg, flush=True)

    def memory_peak(self) -> int:
        return harness.memory_peak(self.devices)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def per_layer(cell, res: dict, device: dict) -> tuple:
    """(metrics, busy_s, window_s, breakdown) from the traced window."""
    trace_dir = str(cell.root / harness.TRACE_DIR)
    trace = tracing.load(trace_dir)
    lo, hi = trace.window()
    ops = list(trace.devices.values())
    busy = [tracing.busy_ns(o, lo, hi) / 1e9 for o in ops]
    inp = dict(res["layer_input"], trace=trace, lo=lo, hi=hi,
               chips=device["count"],
               peak_flops=peak_for(device["kind"])["flops_per_s"])
    metrics = {}
    for m in cell.per_layer:
        value = harness.metric_reader(cell.root, m["name"])(inp)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    first = ops[0] if ops else []
    breakdown = {"device_ops": tracing.top_ops(first, lo, hi),
                 "idle_gaps": tracing.idle_gaps(first, trace.spans, lo, hi)}
    shutil.rmtree(trace_dir, ignore_errors=True)
    window_s = (hi - lo) / 1e9
    return metrics, (sum(busy) / len(busy) if busy else 0.0), window_s, \
        breakdown


def main(argv=None, root=None, platform: str = "tpu") -> int:
    t_start = harness.process_start()
    args = parse(argv)
    root = pathlib.Path(root or ".").resolve()
    cell = harness.load_cell(root, args.workload)
    harness.add_program_path(root)
    import jax

    try:
        device = harness.check_device(cell.chips, platform)
    except harness.NoChip as e:
        print(f"bench.run: {e}", file=sys.stderr)
        return 3
    harness.enable_compile_cache(root)
    env = Env(jax.devices()[:cell.chips])
    print(f"[device] {device['platform']} {device['kind']} x"
          f"{device['count']}; peaks {peak_for(device['kind'])['source']}",
          flush=True)
    kind = importlib.import_module(f"bench.kinds.{cell.traffic['kind']}")
    res = kind.run(cell, args, env)
    device["memory_peak_bytes"] = res["peak"]
    setup_s = res["setup_end"] - t_start
    if args.trace:
        metrics, busy_s, window_s, breakdown = per_layer(cell, res, device)
        device.update(busy_s=busy_s, window_s=window_s)
        res["breakdown"] = breakdown
    else:
        units = {m["name"]: m["unit"] for m in cell.end_to_end}
        values = dict(res["e2e"], setup_s=setup_s)
        metrics = {k: {"value": values[k], "unit": units[k]}
                   for k in units}
    print(f"[run] setup_s {setup_s:.3f}; whole run "
          f"{time.time() - t_start:.1f} s", flush=True)
    harness.emit(dict(res, metrics=metrics, device=device))
    return 0


if __name__ == "__main__":
    sys.exit(main())
