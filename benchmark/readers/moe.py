"""Per-layer metrics of the routed MLP (``scaling_tpu/nn/moe.py``).

Two sources. The device time of the routed MLP is read from the profiler's
trace: the program puts router, dispatch, experts and combine under
``jax.named_scope("moe")``. ``trace_reduce.load_events`` keeps an event's
name only, a fusion's name does not carry its scope, and on a v5e the
events' own statistics do not either; the trace's metadata plane does hold
every compiled program's HLO, whose instructions name the scope path they
were compiled from (``benchmark/xplane_hlo.py``). So the ``.xplane.pb`` is
parsed here, as ``idle_in_spans_pct`` parses it: each operation of the first
chip's ``XLA Ops`` is looked up, by its instruction's name, in the program
(``XLA Modules``) it ran in. A fusion is the routed MLP's when its own
``op_name`` lies in the scope, or, having none, one fused into it does. The
load of the experts comes from the program's own spans, through
``obs.last_capture()``: on a routed model every ``serve.emit`` span carries
``load_max``, ``load_mean`` and ``experts_idle`` of its tick, from the (E,)
count of the REAL positions' assignments the mixed program returns.

Without a capture, without the scope in the trace (a dense model, a program
from before the scope existed) or without load fields a reader returns
nothing, not 0.
"""

from __future__ import annotations

import bisect
import functools
import re
import sys
from pathlib import Path
from statistics import mean

from benchmark import moe_ops_count, trace_reduce, xplane_hlo
from benchmark.readers.program_spans import last_capture

SCOPE = re.compile(r"(^|/)moe(/|$)")
EMIT = "serve.emit"
BF16_BYTES = 2


def scoped_ops(ops, modules, scopes):
    """``[[name, start_ns, dur_ns, scope], ...]``: each operation with the
    ``op_name`` of its instruction if that lies in the scope, else ''.
    ``ops`` and ``modules``: ``[name, start_ns, dur_ns]`` rows of one chip's
    ``XLA Ops`` and ``XLA Modules``; ``scopes``: ``{module's base name:
    {instruction name: op_name}}``. An operation belongs to the module whose
    interval holds its start."""
    modules = sorted(modules, key=lambda m: m[1])
    starts = [m[1] for m in modules]
    rows = []
    for name, start, dur in ops:
        scope = ""
        i = bisect.bisect_right(starts, start) - 1
        if i >= 0 and start < modules[i][1] + modules[i][2]:
            module = xplane_hlo.base_name(trace_reduce.short_name(modules[i][0]))
            scope = scopes.get(module, {}).get(trace_reduce.short_name(name), "")
        rows.append([name, start, dur, scope])
    return rows


@functools.lru_cache(maxsize=2)
def load_scoped_ops(path) -> list:
    """``scoped_ops`` of the first chip of a trace file."""
    events = trace_reduce.load_events(path)
    devices = {k: v for k, v in events["devices"].items() if v["ops"]}
    if not devices:
        return []
    first_chip = devices[min(devices, key=int)]
    hlo = xplane_hlo.hlo_modules(Path(path).read_bytes())
    scopes = {name: xplane_hlo.instruction_scopes(module, SCOPE)
              for name, module in hlo.items()}
    rows = scoped_ops(first_chip["ops"], first_chip["modules"], scopes)
    if not any(r[3] for r in rows):
        print(f"[moe] none of {len(rows)} operations lies in the scope; the trace "
              f"holds the HLO of {sorted(hlo)}", file=sys.stderr, flush=True)
    return rows


def scoped_seconds(ops):
    """Device seconds of the operations in the scope; None if none is."""
    inside = [dur for _, _, dur, scope in ops if scope]
    return sum(inside) / 1e9 if inside else None


def traced_ops():
    capture = last_capture()
    path = capture.trace_file() if capture else None
    return load_scoped_ops(path) if path is not None else []


def tick_loads(spans=None):
    """The ``serve.emit`` spans' load fields, one dict a traced tick.
    ``spans``: a capture's rows; the last capture's if not given."""
    if spans is None:
        capture = last_capture()
        spans = capture.spans if capture else []
    return [f for n, _, _, f in spans
            if n == EMIT and "load_mean" in f and "experts_idle" in f]


def report(ops, keep: int = 8) -> None:
    """stderr: the routed MLP's largest operations, summed by stem."""
    by_stem = {}
    for name, _, dur, scope in ops:
        if scope:
            entry = by_stem.setdefault(trace_reduce.stem(name), [0, 0.0])
            entry[0] += 1
            entry[1] += dur / 1e9
    for stem, (count, seconds) in sorted(by_stem.items(), key=lambda kv: -kv[1][1])[:keep]:
        print(f"[moe] {seconds:9.6f} s  sum:{stem} x{count}", file=sys.stderr)
    sys.stderr.flush()


def moe_time_pct(ctx, ops=None):
    """Device time of the routed MLP's operations over the device time of
    all operations of the traced ticks."""
    ops = traced_ops() if ops is None else ops
    inside = scoped_seconds(ops)
    total = sum(dur for _, _, dur, _ in ops) / 1e9
    if inside is None or total <= 0:
        return None
    report(ops)
    return 100.0 * inside / total


def moe_weights_roofline(ctx, ops=None, spans=None):
    """Bandwidth-bound: bytes of expert weights the traced ticks had to
    read over the routed MLP's device time, as a share of the chip's
    published HBM bandwidth. A tick reads, in every layer, the experts that
    a real position chose: layers x (experts - ``experts_idle``), the idle
    ones counted from the load summed over the layers (an expert idle in
    some layers only is counted as read in all of them: at ~45 real
    positions x 8 a layer over 64 experts that is under one expert in 200)."""
    loads = tick_loads(spans)
    peaks = ctx["device"]["peaks"]
    inside = scoped_seconds(traced_ops() if ops is None else ops)
    if not loads or inside is None or peaks is None:
        return None
    arch = ctx["config"]["transformer_architecture"]
    experts_read = sum(arch["num_layers"] * (arch["moe_num_experts"] - f["experts_idle"])
                       for f in loads)
    nbytes = moe_ops_count.expert_weight_bytes(
        experts_read, arch["hidden_size"], int(arch["hidden_size"] * arch["mlp_factor"]),
        BF16_BYTES)
    return 100.0 * nbytes / inside / peaks["hbm_bytes_per_s"]


def moe_load_max_over_mean(ctx, spans=None):
    """The fullest expert's real assignments over the mean expert's, mean
    over the traced ticks that routed anything."""
    ratios = [f["load_max"] / f["load_mean"] for f in tick_loads(spans)
              if f["load_mean"] > 0]
    return mean(ratios) if ratios else None
