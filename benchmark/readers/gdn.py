"""Per-layer metrics of a stack of gated delta-rule layers beside gated
full-attention layers (``layer_pattern`` with ``delta`` layers:
``scaling_tpu/nn/gated_delta.py``).

Sources as ``readers/hybrid.py`` has them. Device times from the profiler's
trace by scope, as UNIONS of intervals: the program puts a delta mixer
(projections, conv, recurrence, gated norm, output projection) under
``jax.named_scope("delta")`` (inside it ``delta_rule``, the recurrence alone,
and inside that ``delta_step``, the Pallas kernel of the single step) and, in a stack that has delta layers, a full-attention layer's mixer under
``gated_attn``; an executed operation is looked up, by its instruction's
name, in the HLO that the trace's metadata plane holds
(``benchmark/xplane_hlo.py``). What the engine ran comes from its own spans
and counters, through ``obs.last_capture()``: every ``serve.mixed`` span of
such a model carries ``delta_rows`` (rows whose state advanced),
``delta_lines`` (delta layers), ``delta_step_rows`` / ``delta_chunk_rows``
(rows that brought one token / more) and ``width`` (the token width it ran
at: at the full width every row runs the chunk form).

Without a capture, without the scopes in the trace or without the span fields
(any other model, a program from before they existed) a reader returns
nothing, not 0, and never raises.
"""

from __future__ import annotations

import functools
import re
import sys
from pathlib import Path

from benchmark import gdn_ops_count, trace_reduce, xplane_hlo
from benchmark.readers import moe
from benchmark.readers.hybrid import counters_of, span_fields, union_seconds
from benchmark.readers.program_spans import (
    OUTPUT_TOKENS, PREFILL_TOKENS, TICK, counter_moved, last_capture,
)

NAMES = ("delta", "delta_rule", "delta_step", "gated_attn")
SCOPES = {name: re.compile(rf"(^|/){name}(/|$)") for name in NAMES}
ANY = re.compile(rf"(^|/)({'|'.join(NAMES)})(/|$)")
MIXED = "serve.mixed"
ASSIGNMENTS = "serve_moe_assignments_total"
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


def delta_ticks(spans=None):
    """The fields of every traced ``serve.mixed`` span of a model with delta
    layers."""
    return span_fields(MIXED, "delta_lines", spans)


def delta_shape(arch: dict):
    """``(nk, nv, dk, dv)`` of a stack with delta layers; None for any other."""
    if "delta" not in (arch.get("layer_pattern") or []):
        return None
    return (arch["delta_num_key_heads"], arch["delta_num_value_heads"],
            arch["delta_key_head_dim"], arch["delta_value_head_dim"])


def report(ops, keep: int = 60) -> None:
    """stderr: the scopes' device time by what the operation was compiled
    from (its ``op_name`` from the scope down) and by operation."""
    by_part = {}
    for name, _, dur, op_name in ops:
        found = ANY.search(op_name)
        if found:
            part = (op_name[found.start():].lstrip("/")[-70:] + "  "
                    + trace_reduce.short_name(name))
            entry = by_part.setdefault(part, [0, 0.0])
            entry[0] += 1
            entry[1] += dur / 1e9
    for part, (count, seconds) in sorted(by_part.items(), key=lambda kv: -kv[1][1])[:keep]:
        print(f"[gdn] {seconds:9.6f} s  x{count}  {part}", file=sys.stderr)
    sys.stderr.flush()


def scope_seconds(scope: str, ops=None):
    """``(device seconds inside scope, of all operations)`` of the traced
    ticks; None without an operation in the scope."""
    ops = traced_ops() if ops is None else ops
    inside = [op for op in ops if SCOPES[scope].search(op[3])]
    total = union_seconds(ops)
    if not inside or total <= 0:
        return None
    return union_seconds(inside), total


def share_pct(scope: str, ops, spans, say: bool = False):
    if not delta_ticks(spans):
        return None
    if ops is None:
        ops = traced_ops()
        if say:
            report(ops)
    seconds = scope_seconds(scope, ops)
    return None if seconds is None else 100.0 * seconds[0] / seconds[1]


def delta_time_pct(ctx, ops=None, spans=None):
    """Device time of the delta mixers over the device time of all operations
    of the traced ticks."""
    return share_pct("delta", ops, spans, say=True)


def gated_attn_time_pct(ctx, ops=None, spans=None):
    """The same of the full-attention layers' mixers (projections, the q and
    k norms, rotary, the pool's scatter, the paged kernel, the gate, the
    output projection)."""
    return share_pct("gated_attn", ops, spans)


def delta_state_roofline(ctx, ops=None, spans=None):
    """Bandwidth-bound: bytes the delta layers had to move in the traced ticks
    (per tick and layer: each advancing row's state read and written once, the
    mixer's weights once) over the ``delta`` scope's device time, as a share
    of the chip's published HBM bandwidth."""
    ticks, peaks = delta_ticks(spans), ctx["device"]["peaks"]
    arch = ctx["config"]["transformer_architecture"]
    shape = delta_shape(arch)
    if not ticks or peaks is None or shape is None:
        return None
    seconds = scope_seconds("delta", ops)
    if seconds is None:
        return None
    nbytes = sum(f["delta_lines"] * gdn_ops_count.delta_layer_bytes(
        f["delta_rows"], arch["hidden_size"], *shape, arch["conv_kernel"],
        BF16_BYTES) for f in ticks)
    return 100.0 * nbytes / seconds[0] / peaks["hbm_bytes_per_s"]


def delta_step_roofline(ctx, ops=None, spans=None):
    """Bandwidth-bound, the step's kernel alone: bytes the rows that brought
    ONE token required in the traced ticks below the full width (per tick and
    layer: each such row's state read once and written once) over the device
    time of the kernel ``delta_step`` (by its scope), as a share of the chip's
    published HBM bandwidth. The kernel passes over every slot's line, so an
    engine with empty slots reads low."""
    ticks, peaks = span_fields(MIXED, "delta_step_rows", spans), ctx["device"]["peaks"]
    shape = delta_shape(ctx["config"]["transformer_architecture"])
    if not ticks or peaks is None or shape is None:
        return None
    seconds = scope_seconds("delta_step", ops)
    if seconds is None:
        return None
    full = full_width(ctx)
    nbytes = sum(f["delta_lines"] * f["delta_step_rows"] * 2.0
                 * gdn_ops_count.state_bytes(*shape[1:])
                 for f in ticks if full is None or f.get("width") != full)
    return 100.0 * nbytes / seconds[0] / peaks["hbm_bytes_per_s"]


def full_width(ctx):
    """The engine's full token width, ``num_slots x prefill_chunk``: a tick
    there runs every row through the chunk form."""
    engine = ctx["config"].get("engine") or {}
    if "num_slots" not in engine or "prefill_chunk" not in engine:
        return None
    return engine["num_slots"] * engine["prefill_chunk"]


def delta_chunk_row_pct(ctx, spans=None):
    """Of the rows whose delta lines advanced in the traced ticks, the share
    that ran the chunk form: the rows that brought more than one token, and
    every row of a tick at the full width."""
    ticks = span_fields(MIXED, "delta_chunk_rows", spans)
    full = full_width(ctx)
    rows = sum(f["delta_step_rows"] + f["delta_chunk_rows"] for f in ticks)
    if not rows:
        return None
    chunked = sum(
        f["delta_step_rows"] + f["delta_chunk_rows"]
        if full is not None and f.get("width") == full else f["delta_chunk_rows"]
        for f in ticks)
    return 100.0 * chunked / rows


def tick_mfu_pct(ctx, spans=None, counters=None):
    """The whole tick's share of the chip's bf16 peak: FLOPs the traced
    ticks' real tokens require (``gdn_ops_count.serve_flops``) over the traced
    ticks' time (their ``serve.tick`` spans: host and device)."""
    if spans is None:
        capture = last_capture()
        spans = capture.spans if capture else []
    counters = counters_of(counters)
    peaks = ctx["device"]["peaks"]
    seconds = sum(dur for n, _, dur, _ in spans if n == TICK) / 1e9
    outputs = counter_moved(counters, OUTPUT_TOKENS)
    tokens = outputs + counter_moved(counters, PREFILL_TOKENS)
    arch = ctx["config"]["transformer_architecture"]
    shape = delta_shape(arch)
    if (not delta_ticks(spans) or peaks is None or seconds <= 0 or not tokens
            or shape is None):
        return None
    pattern = arch["layer_pattern"]
    heads = arch["num_attention_heads"]
    flops = gdn_ops_count.serve_flops(
        tokens, outputs, counter_moved(counters, ASSIGNMENTS),
        ctx["host"].get("traced_context_tokens") or 0,
        delta_layers=pattern.count("delta"),
        attention_layers=pattern.count("attention"),
        moe_layers=pattern.count("moe"), hidden=arch["hidden_size"],
        vocab=arch["vocab_size"], delta=shape,
        expert_width=arch["moe_expert_width"],
        shared_width=arch.get("moe_shared_expert_width") or 0,
        num_experts=arch["moe_num_experts"], heads=heads,
        kv_heads=arch.get("attention_num_kv_heads") or heads,
        head_dim=arch.get("attention_head_dim") or arch["hidden_size"] // heads)
    return 100.0 * flops / seconds / peaks["flops_per_s"]
