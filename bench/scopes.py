"""Device time per layer of the train step, from the program's scopes.

The program names each layer of its step with ``jax.named_scope``
(``backbone``, ``attention``, ``sampler_refresh``, ``sampler_draw``,
``head_loss``, ``optimizer``).  XLA keeps the names as each HLO
instruction's ``op_name`` metadata, a path such as
``jit(train_step)/transpose(jvp(backbone))/while/body/checkpoint/
rematted_computation/attention/dot_general``.  Each op of the device trace
is an instruction of the compiled step, so the trace's op name finds its
path, and the path its scope: the innermost component that names a layer
scope once transform wrappers (``jvp(...)``, ``transpose(...)``) are
stripped, else ``unscoped``.

Scopes are charged self time: an event's duration less the union of the
events nested inside it on the same line, so a ``while`` loop is charged
only for what its body's ops leave uncovered, and the self times of a
line's events add up to its busy time.  Everything here is a pure
function of intervals and text, tested on small synthetic traces and HLO
snippets (``tests/bench/test_bench_scopes.py``).

A run's readings need the compiled step's HLO text beside its trace
(``run["hlo_text"]``): a train cell's traced run carries it, and each
``bench/metrics/device_ms.train.<scope>.py`` reads one scope.  A Pallas
kernel's custom call carries its ``kernel_metadata`` attribute as JSON
with line breaks, so the instruction spans several lines of the text and
its ``op_name`` lies on a later line than its name; ``splits`` reads the
text with each instruction joined onto one line
(``one_line_instructions``), else every kernel would read as
``unscoped``.
"""
from __future__ import annotations

import re

from bench.tracing import clip, covered

#: the layer scopes the program names, as ``jax.named_scope`` gives them
LAYER_SCOPES = ("backbone", "attention", "sampler_refresh", "sampler_draw",
                "head_loss", "optimizer")
UNSCOPED = "unscoped"
#: ``jax.checkpoint``'s recompute carries this component in its path
REMAT_MARK = "rematted_computation"

_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="((?:[^"\\]|\\.)*)"')
_WRAPPER = re.compile(r"^[\w.\-]+\((.*)\)$")


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


def op_paths(hlo_text: str) -> dict[str, str]:
    """{instruction name: op_name path} over every computation of an HLO
    module's text; "" for an instruction without ``op_name``."""
    paths = {}
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m is None:
            continue
        p = _OP_NAME.search(line)
        paths[m.group(1)] = p.group(1) if p else ""
    return paths


def unwrap(component: str) -> str:
    """``transpose(jvp(head_loss))`` -> ``head_loss``."""
    m = _WRAPPER.match(component)
    while m is not None:
        component = m.group(1)
        m = _WRAPPER.match(component)
    return component


def scope_of(path: str) -> str:
    """The innermost layer scope named in ``path``, or ``unscoped``."""
    for component in reversed(path.split("/")):
        name = unwrap(component)
        if name in LAYER_SCOPES:
            return name
    return UNSCOPED


def self_times(ops) -> list[float]:
    """Each event's duration less the union of the events nested in it.

    ``ops`` are one line's ``(start, end, name)`` events.  An event's
    parent is the innermost earlier event still open at its start; the
    union of a parent's children is cut to the parent."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][0], -ops[i][1]))
    children: list[list] = [[] for _ in ops]
    open_: list[int] = []
    for i in order:
        while open_ and ops[open_[-1]][1] <= ops[i][0]:
            open_.pop()
        if open_:
            children[open_[-1]].append(ops[i])
        open_.append(i)
    return [(e - s) - covered(clip(children[i], s, e))
            for i, (s, e, *_) in enumerate(ops)]


def split_ns(ops, paths: dict, lo: float, hi: float) -> dict:
    """Self time in [lo, hi] by scope (every layer scope and ``unscoped``),
    with ``remat`` (ops whose path holds ``rematted_computation``, whatever
    their scope), ``mapped`` (ops whose name ``paths`` holds) and ``busy``
    (all ops), in ns."""
    ops = clip(ops, lo, hi)
    scope = {name: scope_of(path) for name, path in paths.items()}
    remat = {name for name, path in paths.items()
             if REMAT_MARK in path.split("/")}
    out = dict.fromkeys(LAYER_SCOPES + (UNSCOPED, "remat", "mapped"), 0.0)
    for (_, _, name), t in zip(ops, self_times(ops)):
        out[scope.get(name, UNSCOPED)] += t
        if name in scope:
            out["mapped"] += t
        if name in remat:
            out["remat"] += t
    out["busy"] = covered(ops)
    return out


def splits(run: dict) -> list[dict]:
    """``split_ns`` of each chip's ops in a traced run's window, mapped by
    the run's ``hlo_text``: the compiled step's optimized HLO text
    (``Compiled.as_text()``), its instructions joined onto one line each.
    Kept in ``run`` once computed, for the next scope's reading."""
    if "scope_splits" not in run:
        paths = op_paths(one_line_instructions(run["hlo_text"]))
        run["scope_splits"] = [
            split_ns(ops, paths, run["lo"], run["hi"])
            for ops in run["trace"].devices.values()]
    return run["scope_splits"]


def device_ms(run: dict, name: str) -> float | None:
    """Self time of scope ``name`` (a layer scope, ``unscoped`` or
    ``remat``) per window step, in ms, the mean over chips, of a traced
    train run with ``hlo_text`` (``splits``).  None where the run has no
    such text, where no op in the window is an instruction of the text (a
    lost map never reads as a gain), or where no op falls in any layer
    scope (a program without scopes)."""
    if (run.get("kind") != "train" or not run.get("steps")
            or not run.get("hlo_text")):
        return None
    chips = splits(run)
    if not any(s["mapped"] for s in chips):
        return None
    if not any(s[k] for s in chips for k in LAYER_SCOPES):
        return None
    return sum(s[name] for s in chips) / len(chips) / run["steps"] / 1e6
