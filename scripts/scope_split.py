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
whose op was found in the text, and ``attention_kernel_share``: the
share of ``attention``'s time spent in Pallas kernels (the splash flash
kernels where the program takes them).  Exits non-zero off a TPU.

A Pallas kernel's custom call carries its ``kernel_metadata`` attribute
as JSON with line breaks, so the instruction spans several lines of the
HLO text and its ``op_name`` lies on a later line than its name; the text
is read with each instruction joined onto one line
(``one_line_instructions``), else every kernel would read as
``unscoped``.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import re
import shutil
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

from bench import harness, scopes, tracing  # noqa: E402

_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s")
_KERNEL = 'custom_call_target="tpu_custom_call"'


def _open_braces(text: str) -> int:
    """Braces opened less braces closed in ``text``, outside quotes."""
    depth, quoted, escaped = 0, False, False
    for ch in text:
        if escaped:
            escaped = False
        elif ch == "\\":
            escaped = True
        elif ch == '"':
            quoted = not quoted
        elif not quoted:
            depth += (ch == "{") - (ch == "}")
    return depth


def one_line_instructions(hlo_text: str) -> str:
    """``hlo_text`` with every instruction that spans several lines (its
    braces left open at the line's end) joined onto one line."""
    lines: list[str] = []
    open_ = False
    for line in hlo_text.splitlines():
        if open_:
            lines[-1] += line
        else:
            lines.append(line)
            if not _INSTRUCTION.match(line):
                continue
        open_ = _open_braces(lines[-1]) > 0
    return "\n".join(lines)


def kernel_names(hlo_text: str) -> set[str]:
    """The instructions of one-line HLO text that call a Pallas kernel."""
    return {m.group(1) for line in hlo_text.splitlines()
            if _KERNEL in line and (m := _INSTRUCTION.match(line))}


def kernel_share(run: dict, scope: str) -> float | None:
    """The share of ``scope``'s self time, over every chip, spent in Pallas
    kernel calls; None where the scope has no time."""
    text = run["hlo_text"]
    kernels = kernel_names(text)
    scope_of = {name: scopes.scope_of(path)
                for name, path in scopes.op_paths(text).items()}
    total = in_kernels = 0.0
    for ops in run["trace"].devices.values():
        ops = tracing.clip(ops, run["lo"], run["hi"])
        for (_, _, name), t in zip(ops, scopes.self_times(ops)):
            if scope_of.get(name, scopes.UNSCOPED) == scope:
                total += t
                in_kernels += t if name in kernels else 0.0
    return in_kernels / total if total else None


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
               hlo_text=one_line_instructions(setup.compiled.as_text()))
    names = scopes.LAYER_SCOPES + (scopes.UNSCOPED, "remat")
    out = {name: scopes.device_ms(run, name) for name in names}
    chips = scopes.splits(run)
    busy = sum(s["busy"] for s in chips)
    out.update(
        busy=busy / len(chips) / w["steps"] / 1e6,
        mapped_share=sum(s["mapped"] for s in chips) / busy,
        attention_kernel_share=kernel_share(run, "attention"),
        steps=w["steps"], window_s=(hi - lo) / 1e9,
        targets_per_s=w["steps"] * train.targets_per_batch(cell.traffic)
        / w["seconds"], device=device)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
