"""Finding cells by name, the device check, the compile cache, the set-up
clock and the result line."""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import pathlib
import sys
import time

SPEC_FILE = "BENCHMARK.json"
#: the compile cache inside the checkout, at a fixed path: the path is
#: part of the cache's key, so a directory that moved would never hit
CACHE_DIR = ".jax_cache"
#: scratch inside the checkout for profiler traces
TRACE_DIR = ".bench_trace"


class NoChip(RuntimeError):
    """JAX found no accelerator of the kind, or fewer chips than needed."""


@dataclasses.dataclass
class Cell:
    """One entry of BENCHMARK.json's workloads, with its files loaded."""

    name: str
    chips: int
    config: dict     # bench/configs/<config>.json
    traffic: dict    # bench/workloads/<traffic>.json
    checks: dict     # bench/checks/<cell>.json: {number: {"limit": x}}
    end_to_end: list  # the spec's end-to-end metrics this cell reports
    per_layer: list   # the spec's per-layer metrics this cell reports
    root: pathlib.Path


def load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _reports(metric: dict, cell: str, e2e_names: set) -> bool:
    """An end-to-end metric is reported where its ``workloads`` list the
    cell, or everywhere without the key; a per-layer one likewise, and
    without the key wherever the metric it moves is reported."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    return "moves" not in metric or metric["moves"] in e2e_names


def load_cell(root, name: str) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json``, found by name."""
    root = pathlib.Path(root)
    spec = load_json(root / SPEC_FILE)
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; have {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    config = load_json(root / configs[w["config"]]["file"])
    traffic = load_json(root / "bench" / "workloads" / f"{w['traffic']}.json")
    checks = load_json(root / "bench" / "checks" / f"{name}.json")
    e2e = [m for m in spec["end_to_end"] if _reports(m, name, set())]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if _reports(m, name, e2e_names)]
    return Cell(name, int(w["chips"]), config, traffic, checks, e2e,
                per_layer, root)


def metric_reader(root, name: str):
    """``read(run) -> float | None`` of ``bench/metrics/<name>.py``."""
    path = pathlib.Path(root) / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def add_program_path(root) -> None:
    """The program under test lives in ``<root>/src``."""
    src = str(pathlib.Path(root) / "src")
    if src not in sys.path:
        sys.path.insert(0, src)


def enable_compile_cache(root) -> str:
    """JAX's persistent compilation cache: ``$JAX_COMPILATION_CACHE_DIR``
    where the environment gives one, else ``<root>/.jax_cache``."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        pathlib.Path(root).resolve() / CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def check_device(chips: int, platform: str = "tpu") -> dict:
    """The device as JAX reports it; raises NoChip on another platform or
    too few chips.  There is no fallback."""
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != platform:
        raise NoChip(f"needs a {platform.upper()}; JAX found "
                     f"{dev.platform!r} ({dev.device_kind})")
    if len(devices) < chips:
        raise NoChip(f"needs {chips} chips; JAX found {len(devices)}")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": chips}


def process_start() -> float:
    """Wall-clock time at which this process started (Linux), else now."""
    try:
        with open("/proc/self/stat") as f:
            fields = f.read().rsplit(")", 1)[1].split()
        start_ticks = float(fields[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return time.time() - uptime + start_ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return time.time()


def memory_peak(devices) -> int:
    """Peak bytes in use on the fullest of ``devices``."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def emit(result: dict) -> None:
    """Compared numbers as the last lines of stderr, then the result as
    the last line of stdout, with ``checks`` as its last key."""
    for k, v in result["checks"].items():
        print(f"check {k}: {v['value']!r} (limit {v['limit']!r})",
              file=sys.stderr, flush=True)
    out = {k: result[k] for k in ("correct", "attempted", "failed",
                                  "metrics", "device")}
    if "breakdown" in result:
        out["breakdown"] = result["breakdown"]
    out["checks"] = result["checks"]
    print(json.dumps(out), flush=True)


class CompileCounter:
    """Counts the programs JAX compiles (or loads from its cache) while
    it is on: the measured window should count none."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.count = 0
        self.on = False
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, name, duration, **_):
        if self.on and name == self.EVENT:
            self.count += 1
