"""From a profiler trace to device busy time, idle gaps and collectives.

The capture writes JAX's ``.xplane.pb``; ``load`` reduces it to plain
intervals, and every metric below is a pure function of those intervals,
so the reduction is tested on small synthetic traces
(``tests/bench/test_bench_tracing.py``).

Host spans: the benchmark wraps its own host work in
``jax.profiler.TraceAnnotation`` spans named ``bench.<what>``
(``bench.batch_fetch``, ``bench.step_dispatch``, ``bench.result_wait``).
The traced window is the span ``bench.window``; idle gaps on the device are
labelled by the host span that covers most of each gap.
"""
from __future__ import annotations

import contextlib
import dataclasses
import glob
import os
import shutil

#: op-name stems of cross-chip collectives in XLA's TPU trace
COLLECTIVE_STEMS = ("all-gather", "reduce-scatter", "all-reduce",
                    "all-to-all", "collective-permute")


@dataclasses.dataclass
class Trace:
    """devices: {device plane name: [(start_ns, end_ns, op name), ...]}
    spans: [(start_ns, end_ns, span name), ...] host spans named bench.*
    """

    devices: dict
    spans: list

    def window(self) -> tuple[float, float]:
        w = [s for s in self.spans if s[2] == "bench.window"]
        if not w:
            raise ValueError("the trace holds no bench.window span")
        return w[0][0], w[0][1]


@contextlib.contextmanager
def capture(log_dir: str):
    """Profile the body into ``log_dir`` (emptied first)."""
    import jax

    shutil.rmtree(log_dir, ignore_errors=True)
    os.makedirs(log_dir, exist_ok=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0  # host spans are TraceMe, not Python
    options.enable_hlo_proto = False
    jax.profiler.start_trace(log_dir, profiler_options=options)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def _op_line(plane):
    lines = {ln.name: ln for ln in plane.lines}
    for name in ("XLA Ops", "XLA Modules"):
        if name in lines:
            return lines[name]
    return None


def op_name(event_name: str) -> str:
    """The HLO op's own name from a trace event's name, which on the TPU is
    the whole instruction ("%fusion.357 = (bf16[...]) fusion(...)")."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


def load(log_dir: str) -> Trace:
    """Read the one ``.xplane.pb`` under ``log_dir``."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {log_dir}, "
                                f"found {len(paths)}")
    data = ProfileData.from_file(paths[0])
    devices, spans = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            line = _op_line(plane)
            if line is not None:
                devices[plane.name] = [
                    (e.start_ns, e.start_ns + e.duration_ns, op_name(e.name))
                    for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.start_ns, e.start_ns + e.duration_ns, e.name)
                             for e in line.events
                             if e.name.startswith("bench."))
    return Trace(devices, spans)


def clip(intervals, lo: float, hi: float) -> list:
    """Intervals cut to [lo, hi]; empty ones dropped."""
    out = []
    for s, e, *rest in intervals:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            out.append((s, e, *rest))
    return out


def union(intervals) -> list[tuple[float, float]]:
    """Merged, sorted (start, end) pairs covering the same time."""
    merged: list[list[float]] = []
    for s, e, *_ in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def covered(intervals) -> float:
    return sum(e - s for s, e in union(intervals))


def busy_ns(ops, lo: float, hi: float) -> float:
    """Time in [lo, hi] in which at least one op runs."""
    return covered(clip(ops, lo, hi))


def idle_share(ops, lo: float, hi: float) -> float:
    """1 - busy / window, in %."""
    return 100.0 * (1.0 - busy_ns(ops, lo, hi) / (hi - lo))


def is_collective(name: str) -> bool:
    return name.lower().startswith(COLLECTIVE_STEMS)


def exposed_collective_ns(ops, lo: float, hi: float) -> float:
    """Time in [lo, hi] in which a collective runs and no other op does."""
    ops = clip(ops, lo, hi)
    coll = union([o for o in ops if is_collective(o[2])])
    other = union([o for o in ops if not is_collective(o[2])])
    overlap, i, j = 0.0, 0, 0
    while i < len(coll) and j < len(other):
        s = max(coll[i][0], other[j][0])
        e = min(coll[i][1], other[j][1])
        overlap += max(0.0, e - s)
        if coll[i][1] < other[j][1]:
            i += 1
        else:
            j += 1
    return sum(e - s for s, e in coll) - overlap


def top_ops(ops, lo: float, hi: float, n: int = 10) -> list:
    """[[op name, seconds], ...]: the n ops with the most device time."""
    total: dict[str, float] = {}
    for s, e, name in clip(ops, lo, hi):
        total[name] = total.get(name, 0.0) + (e - s)
    best = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9] for name, ns in best]


def idle_gaps(ops, spans, lo: float, hi: float, n: int = 10) -> list:
    """[[host span, seconds], ...]: the n longest device-idle gaps in
    [lo, hi], each named by the bench.* host span (other than the window)
    that overlaps it most, or "untraced host work"."""
    busy = union(clip(ops, lo, hi))
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    host = [sp for sp in spans if sp[2] != "bench.window"]
    out = []
    for gs, ge in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        best, label = 0.0, "untraced host work"
        for s, e, name in host:
            ov = min(e, ge) - max(s, gs)
            if ov > best:
                best, label = ov, name
        out.append([label, (ge - gs) / 1e9])
    return out
