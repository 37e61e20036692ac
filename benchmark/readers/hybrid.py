"""Per-layer metrics of a stack of single-mixer layers (``layer_pattern``:
``scaling_tpu/nn/mamba.py``, ``nn/moe.py`` with a share of the experts).

Two sources, as ``readers/moe.py`` has them. Device times are read from the
profiler's trace: the program puts a Mamba-2 mixer under
``jax.named_scope("ssm")`` and a routed MLP (router, dispatch, held experts,
combine, shared expert) under ``"moe"``; an executed operation is looked up,
by its instruction's name, in the HLO that the trace's metadata plane holds
(``benchmark/xplane_hlo.py``; ``moe.scoped_ops`` does the lookup). Times are
UNIONS of intervals, so nothing is counted twice. What the engine ran comes
from its own spans and counters, through ``obs.last_capture()``: every
``serve.mixed`` span of such a model carries ``ssm_rows`` (rows whose state
advanced) and ``ssm_lines`` (Mamba-2 layers), every ``serve.emit`` span
``experts_idle`` and ``absent_assign``; the counters
``serve_moe_assignments_total`` (the held experts') and
``serve_moe_absent_assignments_total``.

Without a capture, without the scope in the trace or without the span fields
(a model without such layers, a program from before they existed) a reader
returns nothing, not 0.
"""

from __future__ import annotations

import functools
import re
import sys
from pathlib import Path

from benchmark import hybrid_ops_count, trace_reduce, xplane_hlo
from benchmark.readers import moe
from benchmark.readers.program_spans import (
    OUTPUT_TOKENS, PREFILL_TOKENS, TICK, counter_moved, last_capture,
)

SCOPES = {"ssm": re.compile(r"(^|/)ssm(/|$)"), "moe": re.compile(r"(^|/)moe(/|$)")}
EITHER = re.compile(r"(^|/)(ssm|moe)(/|$)")
MIXED, EMIT = "serve.mixed", "serve.emit"
HELD_ASSIGNMENTS = "serve_moe_assignments_total"
ABSENT_ASSIGNMENTS = "serve_moe_absent_assignments_total"
BF16_BYTES = 2


@functools.lru_cache(maxsize=2)
def load_scoped_ops(path) -> list:
    """``moe.scoped_ops`` of the first chip of a trace file for the two
    scopes at once: ``[[name, start_ns, dur_ns, op_name or ''], ...]``."""
    events = trace_reduce.load_events(path)
    devices = {k: v for k, v in events["devices"].items() if v["ops"]}
    if not devices:
        return []
    first_chip = devices[min(devices, key=int)]
    hlo = xplane_hlo.hlo_modules(Path(path).read_bytes())
    scopes = {name: xplane_hlo.instruction_scopes(module, EITHER)
              for name, module in hlo.items()}
    rows = moe.scoped_ops(first_chip["ops"], first_chip["modules"], scopes)
    for scope, pattern in SCOPES.items():
        if not any(pattern.search(r[3]) for r in rows):
            print(f"[hybrid] none of {len(rows)} operations lies in the scope "
                  f"{scope!r}; the trace holds the HLO of {sorted(hlo)}",
                  file=sys.stderr, flush=True)
    return rows


def traced_ops():
    capture = last_capture()
    path = capture.trace_file() if capture else None
    return load_scoped_ops(path) if path is not None else []


def union_seconds(ops) -> float:
    return sum(b - a for a, b in trace_reduce.union_intervals(
        [(start, start + dur) for _, start, dur, *_ in ops])) / 1e9


def scope_seconds(ops, scope: str):
    """Device seconds inside ``scope``; None if no operation lies in it."""
    inside = [op for op in ops if SCOPES[scope].search(op[3])]
    return union_seconds(inside) if inside else None


def span_fields(name: str, field: str, spans=None):
    """The fields of every traced ``name`` span that carries ``field``."""
    if spans is None:
        capture = last_capture()
        spans = capture.spans if capture else []
    return [f for n, _, _, f in spans if n == name and field in f]


def counters_of(counters=None):
    if counters is None:
        capture = last_capture()
        counters = capture.counters if capture else {}
    return counters


def pattern_counts(arch: dict):
    pattern = arch.get("layer_pattern") or []
    return {kind: sum(k == kind for k in pattern)
            for kind in ("mamba", "moe", "attention")}


def mamba_shape(arch: dict):
    return (arch["mamba_num_heads"], arch["mamba_head_dim"], arch["ssm_state_size"],
            arch["n_groups"])


def report(ops, scope: str, keep: int = 30) -> None:
    """stderr: the scope's largest operations, summed by name (a name holds
    the result's shape: one line a distinct operation of a layer)."""
    by_name = {}
    for name, _, dur, op_name in ops:
        if SCOPES[scope].search(op_name):
            short = trace_reduce.short_name(name) + " " + name.split(" = ", 1)[-1][:60]
            entry = by_name.setdefault(short, [0, 0.0])
            entry[0] += 1
            entry[1] += dur / 1e9
    for name, (count, seconds) in sorted(by_name.items(), key=lambda kv: -kv[1][1])[:keep]:
        print(f"[{scope}] {seconds:9.6f} s  x{count}  {name}", file=sys.stderr)
    sys.stderr.flush()


def ssm_time_pct(ctx, ops=None):
    """Device time of the Mamba-2 mixers over the device time of all
    operations of the traced ticks."""
    ops = traced_ops() if ops is None else ops
    inside, total = scope_seconds(ops, "ssm"), union_seconds(ops)
    if inside is None or total <= 0:
        return None
    report(ops, "ssm")
    return 100.0 * inside / total


def ssm_state_roofline(ctx, ops=None, spans=None):
    """Bandwidth-bound: bytes the Mamba-2 layers had to move in the traced
    ticks (per tick and layer: each advancing row's state read and written
    once, the mixer's weights once) over the scope's device time, as a share
    of the chip's published HBM bandwidth."""
    ticks = span_fields(MIXED, "ssm_rows", spans)
    peaks = ctx["device"]["peaks"]
    inside = scope_seconds(traced_ops() if ops is None else ops, "ssm")
    if not ticks or inside is None or peaks is None:
        return None
    arch = ctx["config"]["transformer_architecture"]
    nbytes = sum(f["ssm_lines"] * hybrid_ops_count.ssm_layer_bytes(
        f["ssm_rows"], arch["hidden_size"], *mamba_shape(arch),
        arch["conv_kernel"], BF16_BYTES) for f in ticks)
    return 100.0 * nbytes / inside / peaks["hbm_bytes_per_s"]


def moe_held_roofline(ctx, ops=None, spans=None):
    """Bandwidth-bound: bytes of weights the routed layers had to read in the
    traced ticks (per tick and layer: the two matrices of each HELD expert a
    real position chose, the shared expert, the router) over the ``moe``
    scope's device time, as a share of the chip's published HBM bandwidth.
    The idle experts are counted from the load summed over the layers, as
    ``moe.moe_weights_roofline`` counts them."""
    loads = span_fields(EMIT, "absent_assign", spans)
    peaks = ctx["device"]["peaks"]
    inside = scope_seconds(traced_ops() if ops is None else ops, "moe")
    if not loads or inside is None or peaks is None:
        return None
    arch = ctx["config"]["transformer_architecture"]
    held = arch.get("moe_experts_held") or arch["moe_num_experts"]
    layers = pattern_counts(arch)["moe"]
    nbytes = sum(layers * hybrid_ops_count.moe_layer_bytes(
        held - f["experts_idle"], arch["hidden_size"], arch["moe_expert_width"],
        arch.get("moe_shared_expert_width") or 0, arch["moe_num_experts"],
        BF16_BYTES) for f in loads)
    return 100.0 * nbytes / inside / peaks["hbm_bytes_per_s"]


def moe_absent_assign_pct(ctx, counters=None):
    """Of the real positions' assignments in the traced ticks, the share that
    fell on experts this chip does not hold."""
    counters = counters_of(counters)
    absent = counter_moved(counters, ABSENT_ASSIGNMENTS)
    total = absent + counter_moved(counters, HELD_ASSIGNMENTS)
    if not total or not any(k.startswith(ABSENT_ASSIGNMENTS) for k in counters):
        return None
    return 100.0 * absent / total


def tick_mfu_pct(ctx, spans=None, counters=None):
    """The whole tick's share of the chip's bf16 peak: FLOPs the traced
    ticks' real tokens require (``hybrid_ops_count.serve_flops``) over the
    traced ticks' time (their ``serve.tick`` spans: host and device)."""
    if spans is None:
        capture = last_capture()
        spans = capture.spans if capture else []
    counters = counters_of(counters)
    peaks = ctx["device"]["peaks"]
    seconds = sum(dur for n, _, dur, _ in spans if n == TICK) / 1e9
    outputs = counter_moved(counters, OUTPUT_TOKENS)
    tokens = outputs + counter_moved(counters, PREFILL_TOKENS)
    if (not span_fields(MIXED, "ssm_rows", spans) or peaks is None
            or seconds <= 0 or not tokens):
        return None
    arch = ctx["config"]["transformer_architecture"]
    counts = pattern_counts(arch)
    heads = arch["num_attention_heads"]
    flops = hybrid_ops_count.serve_flops(
        tokens, outputs, counter_moved(counters, HELD_ASSIGNMENTS),
        ctx["host"].get("traced_context_tokens") or 0,
        mamba_layers=counts["mamba"], moe_layers=counts["moe"],
        attention_layers=counts["attention"], hidden=arch["hidden_size"],
        vocab=arch["vocab_size"], mamba=mamba_shape(arch),
        expert_width=arch["moe_expert_width"],
        shared_width=arch.get("moe_shared_expert_width") or 0,
        num_experts=arch["moe_num_experts"], heads=heads,
        kv_heads=arch.get("attention_num_kv_heads") or heads,
        head_dim=arch.get("attention_head_dim") or arch["hidden_size"] // heads)
    return 100.0 * flops / seconds / peaks["flops_per_s"]
