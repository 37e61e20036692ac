"""Per-layer metrics of a stack whose attention differs by LAYER (``layer_pattern``
with ``attention`` layers, full, beside ``window`` layers that keep a ring a
slot: ``scaling_tpu/nn/window_attention.py``; ``mlp`` / ``moe`` FFNs).

Sources as ``readers/sparse_latent.py`` has them. Device times from the
profiler's trace by scope, as UNIONS of intervals: the program puts a window
layer's mixer under ``jax.named_scope("window_attn")`` (inside it
``window_attend``, the walk over the rows' rings and its kernel, and ``gate``)
and, in a stack that has window layers, a full layer's under ``full_attn``; an
executed operation is looked up, by its instruction's name, in the HLO that the
trace's metadata plane holds (``benchmark/xplane_hlo.py``). What the engine ran
comes from its own spans and counters, through ``obs.last_capture()``: every
``serve.mixed`` span of such a model carries ``window_layers``,
``window_rows_past``, ``window_visible_lines``, ``window_pairs`` and
``full_pairs``; the counters ``serve_window_rows_total`` and
``serve_window_rows_past_window_total`` move a tick.

Without a capture, without the scopes in the trace or without the span fields
(any other model, a program from before they existed) a reader returns
nothing, not 0, and never raises.
"""

from __future__ import annotations

import functools
import re
import sys
from pathlib import Path

from benchmark import layered_gqa_ops_count as ops_count
from benchmark import trace_reduce, xplane_hlo
from benchmark.readers import moe
from benchmark.readers.hybrid import counters_of, span_fields, union_seconds
from benchmark.readers.program_spans import (
    OUTPUT_TOKENS, PREFILL_TOKENS, TICK, counter_moved, last_capture,
)

NAMES = ("window_attn", "full_attn", "window_attend", "gate")
SCOPES = {name: re.compile(rf"(^|/){name}(/|$)") for name in NAMES}
ANY = re.compile(rf"(^|/)({'|'.join(NAMES)})(/|$)")
MIXED = "serve.mixed"
ASSIGNMENTS = "serve_moe_assignments_total"
WINDOW_ROWS = "serve_window_rows_total"
WINDOW_ROWS_PAST = "serve_window_rows_past_window_total"
BF16_BYTES = 2


@functools.lru_cache(maxsize=2)
def load_scoped_ops(path) -> list:
    """``moe.scoped_ops`` of the first chip of a trace file for the scopes
    above: ``[[name, start_ns, dur_ns, op_name or ''], ...]``."""
    events = trace_reduce.load_events(path)
    devices = {k: v for k, v in events["devices"].items() if v["ops"]}
    if not devices:
        return []
    first_chip = devices[min(devices, key=int)]
    hlo = xplane_hlo.hlo_modules(Path(path).read_bytes())
    scopes = {name: xplane_hlo.instruction_scopes(module, ANY)
              for name, module in hlo.items()}
    return moe.scoped_ops(first_chip["ops"], first_chip["modules"], scopes)


def traced_ops():
    capture = last_capture()
    path = capture.trace_file() if capture else None
    return load_scoped_ops(path) if path is not None else []


def window_ticks(spans=None):
    """The fields of every traced ``serve.mixed`` span of a model with window
    layers."""
    return span_fields(MIXED, "window_pairs", spans)


def scope_seconds(scope: str, ops=None):
    """``(device seconds inside scope, of all operations)`` of the traced
    ticks; None without an operation in the scope."""
    ops = traced_ops() if ops is None else ops
    inside = [op for op in ops if SCOPES[scope].search(op[3])]
    total = union_seconds(ops)
    if not inside or total <= 0:
        return None
    return union_seconds(inside), total


def attention_shape(arch: dict):
    """``heads, window_heads, kv_heads, head_dim`` of a stack with window
    layers; None for any other."""
    if "window" not in (arch.get("layer_pattern") or []):
        return None
    heads = arch["num_attention_heads"]
    return {"heads": heads,
            "window_heads": arch.get("window_num_attention_heads") or heads,
            "kv_heads": arch["attention_num_kv_heads"],
            "head_dim": arch.get("attention_head_dim") or arch["hidden_size"] // heads}


def report(ops, keep: int = 12) -> None:
    """stderr: the two kinds' device time by what the operation was compiled
    from (its ``op_name`` from the kind's scope down)."""
    by_part = {}
    for _, _, dur, op_name in ops:
        found = ANY.search(op_name)
        if found:
            part = op_name[found.start():].lstrip("/")
            entry = by_part.setdefault(part, [0, 0.0])
            entry[0] += 1
            entry[1] += dur / 1e9
    for part, (count, seconds) in sorted(by_part.items(), key=lambda kv: -kv[1][1])[:keep]:
        print(f"[layered_gqa] {seconds:9.6f} s  {part} x{count}", file=sys.stderr)
    sys.stderr.flush()


def share_pct(scope: str, ops, spans, say: bool = False):
    if not window_ticks(spans):
        return None
    if ops is None:
        ops = traced_ops()
        if say:
            report(ops)
    seconds = scope_seconds(scope, ops)
    return None if seconds is None else 100.0 * seconds[0] / seconds[1]


def window_time_pct(ctx, ops=None, spans=None):
    """Device time of the window layers' mixers (projections, rotary, the
    ring's scatter, the walk, the gate, the output projection) over the device
    time of all operations of the traced ticks."""
    return share_pct("window_attn", ops, spans, say=True)


def full_attn_time_pct(ctx, ops=None, spans=None):
    """The same of the full layers' mixers (the paged kernel among them)."""
    return share_pct("full_attn", ops, spans)


def window_roofline(ctx, ops=None, spans=None):
    """The least time the chip could take for the traced ticks' attention
    under the window (a tick and a layer: the larger of the pairs' FLOPs over
    the bf16 peak and the visible lines' bytes over the HBM bandwidth) over
    the device time of what attends (``window_attend``: the walk over the
    rows' rings and its kernel)."""
    ticks, peaks = window_ticks(spans), ctx["device"]["peaks"]
    a = attention_shape(ctx["config"]["transformer_architecture"])
    if not ticks or peaks is None or a is None:
        return None
    seconds = scope_seconds("window_attend", ops)
    if seconds is None:
        return None
    least = sum(
        f["window_layers"] * max(
            ops_count.pair_flops(f["window_pairs"], a["window_heads"],
                                 a["head_dim"]) / peaks["flops_per_s"],
            ops_count.line_bytes(f["window_visible_lines"], a["kv_heads"],
                                 a["head_dim"], BF16_BYTES)
            / peaks["hbm_bytes_per_s"])
        for f in ticks)
    return 100.0 * least / seconds[0]


def window_active_row_pct(ctx, counters=None):
    """Of the rows the window layers attended for in the traced ticks, the
    share whose context was past the window: where the window cuts what a
    full layer would read."""
    counters = counters_of(counters)
    rows = counter_moved(counters, WINDOW_ROWS)
    if not rows:
        return None
    return 100.0 * counter_moved(counters, WINDOW_ROWS_PAST) / rows


def tick_mfu_pct(ctx, spans=None, counters=None):
    """The whole tick's share of the chip's bf16 peak: FLOPs the traced
    ticks' real tokens require (``layered_gqa_ops_count.serve_flops``) over
    the traced ticks' time (their ``serve.tick`` spans: host and device)."""
    if spans is None:
        capture = last_capture()
        spans = capture.spans if capture else []
    counters = counters_of(counters)
    peaks = ctx["device"]["peaks"]
    seconds = sum(dur for n, _, dur, _ in spans if n == TICK) / 1e9
    outputs = counter_moved(counters, OUTPUT_TOKENS)
    tokens = outputs + counter_moved(counters, PREFILL_TOKENS)
    ticks = window_ticks(spans)
    arch = ctx["config"]["transformer_architecture"]
    a = attention_shape(arch)
    if not ticks or peaks is None or seconds <= 0 or not tokens or a is None:
        return None
    pattern = arch["layer_pattern"]
    flops = ops_count.serve_flops(
        tokens, outputs, counter_moved(counters, ASSIGNMENTS),
        sum(f["full_pairs"] for f in ticks), sum(f["window_pairs"] for f in ticks),
        full_layers=pattern.count("attention"),
        window_layers=pattern.count("window"), dense_layers=pattern.count("mlp"),
        routed_layers=pattern.count("moe"), hidden=arch["hidden_size"],
        vocab=arch["vocab_size"], heads=a["heads"],
        window_heads=a["window_heads"], kv_heads=a["kv_heads"],
        head_dim=a["head_dim"],
        dense_width=int(arch["hidden_size"] * arch["mlp_factor"]),
        expert_width=arch["moe_expert_width"],
        shared_width=arch.get("moe_shared_expert_width") or 0,
        num_experts=arch["moe_num_experts"])
    return 100.0 * flops / seconds / peaks["flops_per_s"]
