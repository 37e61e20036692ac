"""The one reduction from the profiler's trace to device metrics.

``load_events`` reads an ``.xplane.pb`` with nothing but JAX
(``jax.profiler.ProfileData``) into a plain structure; ``reduce_events`` turns
that structure into busy time, the traced window, time by operation, the top
operations and the longest idle gaps. Every device metric of the benchmark is
read from this one result (``benchmark/readers/device_trace.py``).

What a TPU trace looks like (seen on a v5e, PR 24): one plane per chip named
``/device:TPU:<n>`` with the lines ``XLA Ops`` (one event per executed HLO
instruction, named by the instruction's text ``%name = shape op(...)``),
``XLA Modules`` (one event per executed program, ``jit_step(<hash>)``),
``Steps`` and ``Async XLA Ops`` (asynchronous copies and collectives, which
overlap the ops and are not counted as busy time); and a plane ``/host:CPU``
whose line ``python`` (or ``python3``: the command's name) holds the interpreter's calls, ``$file.py:line name``.
All planes share one clock, in nanoseconds.
"""

from __future__ import annotations

import re
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, MODULES_LINE, HOST_PLANE, PYTHON_LINE = (
    "XLA Ops", "XLA Modules", "/host:CPU", "python")

# "async-collective": XLA's name for the start/done pair of a combined
# asynchronous collective (seen on four chips: an all-gather)
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all", "async-collective")
NAMED_GAPS = 200  # the longest idle gaps are named by the host's call
PALLAS_TARGET = 'custom_call_target="tpu_custom_call"'
_LAYOUT = re.compile(r"\{[^{}]*\}")


def short_name(event_name: str) -> str:
    """``%fusion.109 = (bf16[4096]{0:T(1024)}, ...) fusion(...)`` -> ``fusion.109``;
    ``jit_step(12536509211202233264)`` -> ``jit_step``."""
    head = event_name.split(" = ", 1)[0].strip()
    return re.sub(r"\(\d+\)$", "", head.lstrip("%"))


def display_name(event_name: str) -> str:
    """The short name with the instruction's output shape, layouts dropped:
    what a reader of the ledger can recognise an operation by."""
    if " = " not in event_name:
        return short_name(event_name)
    head, rest = event_name.split(" = ", 1)
    rest = _LAYOUT.sub("", rest)
    if rest.startswith("("):  # a tuple of outputs: up to its closing bracket
        depth = 0
        for i, c in enumerate(rest):
            depth += (c == "(") - (c == ")")
            if depth == 0:
                break
        shape = rest[: i + 1]
    else:
        shape = rest.split(" ", 1)[0]
    return f"{head.lstrip('%')} {shape}"[:120].strip()


def stem(event_name: str) -> str:
    """The instruction's name without its number: ``copy.184`` -> ``copy``,
    ``paged_attention.31`` -> ``paged_attention``. Instances of one stem
    are the same operation at another place of the program."""
    return re.sub(r"\.\d+$", "", short_name(event_name))


def op_class(event_name: str) -> str:
    """``collective``; ``pallas:<stem>`` for a Pallas kernel (a custom call
    whose target is ``tpu_custom_call``), the stem being the instruction's
    name without its number: ``pallas:splash_mha_fwd...`` for the splash
    kernels, ``pallas:mixed`` for the paged kernel inside the engine's mixed
    program (it carries the program's name until it is given its own);
    ``other`` for everything else."""
    short = short_name(event_name)
    if any(c in short for c in COLLECTIVES):
        return "collective"
    if PALLAS_TARGET in event_name:
        return "pallas:" + stem(event_name)
    return "other"


def load_events(path: Path) -> dict:
    """``{"devices": {"0": {"ops": [[name, start_ns, dur_ns], ...],
    "modules": [...]}}, "host": [[name, start_ns, dur_ns], ...]}``."""
    from jax.profiler import ProfileData

    def rows(line):
        return [[e.name, float(e.start_ns), float(e.duration_ns)]
                for e in line.events]

    out = {"devices": {}, "host": []}
    for plane in ProfileData.from_file(str(path)).planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            lines = {line.name: line for line in plane.lines}
            out["devices"][m.group(1)] = {
                "ops": rows(lines[OPS_LINE]) if OPS_LINE in lines else [],
                "modules": rows(lines[MODULES_LINE]) if MODULES_LINE in lines else [],
            }
        elif plane.name == HOST_PLANE:
            # the interpreter's line carries the main thread's name, which is
            # the command's: "python" or "python3"
            for line in plane.lines:
                if line.name.startswith(PYTHON_LINE):
                    out["host"] = rows(line)
    return out


def union_intervals(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[List[float]] = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return [(a, b) for a, b in merged]


def name_gap(host: List[list], start: float, end: float) -> str:
    """What the host was doing in an idle gap: the innermost (shortest)
    interpreter call that spans the middle of the gap."""
    mid = (start + end) / 2.0
    best = None
    for name, s, d in host:
        if s <= mid <= s + d and (best is None or d < best[1]):
            best = (name, d)
    return best[0] if best else "(no host call recorded)"


def reduce_events(events: dict, chips: int) -> dict:
    devices = {k: v for k, v in events["devices"].items() if v["ops"]}
    if not devices:
        return None
    ids = sorted(devices, key=int)[:chips]
    n = len(ids)
    start = min(op[1] for d in ids for op in devices[d]["ops"])
    end = max(op[1] + op[2] for d in ids for op in devices[d]["ops"])
    busy_ns = 0.0
    op_ns: Dict[str, float] = defaultdict(float)  # by the instruction's text
    stem_ns: Dict[str, float] = defaultdict(float)
    stem_count: Dict[str, int] = defaultdict(int)
    class_ns: Dict[str, float] = defaultdict(float)
    for d in ids:
        ops = devices[d]["ops"]
        busy_ns += sum(b - a for a, b in union_intervals(
            [(s, s + dur) for _, s, dur in ops]))
        for name, _, dur in ops:
            op_ns[name] += dur
            of = stem(name)
            stem_ns[of] += dur
            stem_count[of] += 1
            class_ns[op_class(name)] += dur
    modules: Dict[str, dict] = {}
    for name, _, dur in devices[ids[0]]["modules"]:
        entry = modules.setdefault(short_name(name), {"count": 0, "seconds": 0.0})
        entry["count"] += 1
        entry["seconds"] += dur / 1e9
    # idle gaps of the first device, named by what the host was doing
    first = devices[ids[0]]["ops"]
    merged = union_intervals([(s, s + dur) for _, s, dur in first])
    gaps = sorted(((b_start - a_end, a_end, b_start) for (_, a_end), (b_start, _)
                   in zip(merged, merged[1:])), reverse=True)
    gap_ns: Dict[str, float] = defaultdict(float)
    for i, (length, a, b) in enumerate(gaps):
        if length < 1e3:
            break  # gaps under a microsecond are the device's own
        name = (name_gap(events["host"], a, b) if i < NAMED_GAPS
                else "(shorter gaps, not named)")
        gap_ns[name] += length
    top = sorted(op_ns.items(), key=lambda kv: -kv[1])[:10]
    # many small instances of one operation hide below a few large ones
    # (32 pool copies of 0.41 ms below 16 kernel calls, PR 25-26): the sums
    # by stem, each with the number of events it sums
    stems = sorted(stem_ns.items(), key=lambda kv: -kv[1])[:10]
    return {
        "busy_s": busy_ns / n / 1e9,
        "window_s": (end - start) / 1e9,
        "chips": n,
        "class_s": {k: v / n / 1e9 for k, v in class_ns.items()},
        "modules": modules,
        "top_ops": [[display_name(k), v / n / 1e9] for k, v in top],
        "top_stems": [[f"sum:{k} x{stem_count[k] // n}", v / n / 1e9]
                      for k, v in stems],
        "idle_gaps": [[k, v / 1e9] for k, v in
                      sorted(gap_ns.items(), key=lambda kv: -kv[1])[:10]],
    }
