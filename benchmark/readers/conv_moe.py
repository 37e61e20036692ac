"""Per-layer metrics of a stack of LFM2-MoE blocks (``layer_pattern`` with
``conv`` / ``attention`` operators and ``mlp`` / ``moe`` FFNs:
``scaling_tpu/nn/short_conv.py``, ``nn/moe.py`` with every expert held).

Two sources, as ``readers/hybrid.py`` has them. Device times are read from the
profiler's trace: the program puts a gated short convolution under
``jax.named_scope("conv")`` and a routed MLP (router, dispatch, experts,
combine) under ``"moe"``; an executed operation is looked up, by its
instruction's name, in the HLO that the trace's metadata plane holds
(``benchmark/xplane_hlo.py``; ``moe.scoped_ops`` does the lookup). Times are
UNIONS of intervals, so nothing is counted twice. What the engine ran comes
from its own spans and counters, through ``obs.last_capture()``: every
``serve.mixed`` span of such a model carries ``conv_rows`` (rows whose tail
advanced) and ``conv_lines`` (short-convolution layers), every ``serve.emit``
span ``experts_idle``; the counter ``serve_moe_assignments_total``.

Without a capture, without the scope in the trace or without the span fields
(a model without such layers, a program from before they existed) a reader
returns nothing, not 0.
"""

from __future__ import annotations

import functools
import re
import sys
from pathlib import Path

from benchmark import conv_moe_ops_count, trace_reduce, xplane_hlo
from benchmark.readers import moe
from benchmark.readers.hybrid import counters_of, span_fields, union_seconds
from benchmark.readers.program_spans import (
    OUTPUT_TOKENS, PREFILL_TOKENS, TICK, counter_moved, last_capture,
)

SCOPES = {"conv": re.compile(r"(^|/)conv(/|$)"), "moe": re.compile(r"(^|/)moe(/|$)")}
EITHER = re.compile(r"(^|/)(conv|moe)(/|$)")
MIXED = "serve.mixed"
ASSIGNMENTS = "serve_moe_assignments_total"
BF16_BYTES = 2
KINDS = ("conv", "attention", "mlp", "moe")


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
    return moe.scoped_ops(first_chip["ops"], first_chip["modules"], scopes)


def traced_ops():
    capture = last_capture()
    path = capture.trace_file() if capture else None
    return load_scoped_ops(path) if path is not None else []


def scope_seconds(ops, scope: str):
    """Device seconds inside ``scope``; None if no operation lies in it."""
    inside = [op for op in ops if SCOPES[scope].search(op[3])]
    return union_seconds(inside) if inside else None


def conv_ticks(spans=None):
    """The fields of every traced ``serve.mixed`` span of a model with
    short convolutions."""
    return span_fields(MIXED, "conv_rows", spans)


def pattern_counts(arch: dict):
    pattern = arch.get("layer_pattern") or []
    return {kind: sum(k == kind for k in pattern) for kind in KINDS}


def report(ops, scope: str, keep: int = 12) -> None:
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


def conv_time_pct(ctx, ops=None):
    """Device time of the gated short convolutions over the device time of
    all operations of the traced ticks."""
    ops = traced_ops() if ops is None else ops
    inside, total = scope_seconds(ops, "conv"), union_seconds(ops)
    if inside is None or total <= 0:
        return None
    report(ops, "conv")
    return 100.0 * inside / total


def conv_weights_roofline(ctx, ops=None, spans=None):
    """Bandwidth-bound: bytes the short-convolution layers had to move in the
    traced ticks (per tick and layer: the operator's two matrices and its
    filter once, each advancing row's tail read and written once) over the
    scope's device time, as a share of the chip's published HBM bandwidth."""
    ticks = conv_ticks(spans)
    peaks = ctx["device"]["peaks"]
    inside = scope_seconds(traced_ops() if ops is None else ops, "conv")
    if not ticks or inside is None or peaks is None:
        return None
    arch = ctx["config"]["transformer_architecture"]
    nbytes = sum(f["conv_lines"] * conv_moe_ops_count.conv_layer_bytes(
        f["conv_rows"], arch["hidden_size"], arch["conv_kernel"], BF16_BYTES)
        for f in ticks)
    return 100.0 * nbytes / inside / peaks["hbm_bytes_per_s"]


def moe_routed_roofline(ctx, ops=None, spans=None):
    """Bandwidth-bound: bytes of weights the ROUTED layers had to read in the
    traced ticks (per tick and routed layer: the three ``hidden x
    moe_expert_width`` matrices of each expert a real position chose, and the
    router) over the ``moe`` scope's device time, as a share of the chip's
    published HBM bandwidth. The idle experts are counted from the load
    summed over the layers, as ``moe.moe_weights_roofline`` counts them: an
    expert idle in some layers only is counted as read in all of them (at 64
    decode rows x 4 a layer over 64 experts, under 2 in 100)."""
    loads = moe.tick_loads(spans)
    peaks = ctx["device"]["peaks"]
    arch = ctx["config"]["transformer_architecture"]
    layers = pattern_counts(arch)["moe"]
    inside = scope_seconds(traced_ops() if ops is None else ops, "moe")
    if not loads or not layers or inside is None or peaks is None:
        return None
    experts = arch["moe_num_experts"]
    nbytes = sum(layers * conv_moe_ops_count.routed_layer_bytes(
        experts - f["experts_idle"], arch["hidden_size"], arch["moe_expert_width"],
        experts, BF16_BYTES) for f in loads)
    return 100.0 * nbytes / inside / peaks["hbm_bytes_per_s"]


def tick_mfu_pct(ctx, spans=None, counters=None):
    """The whole tick's share of the chip's bf16 peak: FLOPs the traced
    ticks' real tokens require (``conv_moe_ops_count.serve_flops``) over the
    traced ticks' time (their ``serve.tick`` spans: host and device)."""
    if spans is None:
        capture = last_capture()
        spans = capture.spans if capture else []
    counters = counters_of(counters)
    peaks = ctx["device"]["peaks"]
    seconds = sum(dur for n, _, dur, _ in spans if n == TICK) / 1e9
    outputs = counter_moved(counters, OUTPUT_TOKENS)
    tokens = outputs + counter_moved(counters, PREFILL_TOKENS)
    if not conv_ticks(spans) or peaks is None or seconds <= 0 or not tokens:
        return None
    arch = ctx["config"]["transformer_architecture"]
    counts = pattern_counts(arch)
    heads = arch["num_attention_heads"]
    flops = conv_moe_ops_count.serve_flops(
        tokens, outputs, counter_moved(counters, ASSIGNMENTS),
        ctx["host"].get("traced_context_tokens") or 0,
        conv_layers=counts["conv"], attention_layers=counts["attention"],
        dense_layers=counts["mlp"], routed_layers=counts["moe"],
        hidden=arch["hidden_size"], vocab=arch["vocab_size"],
        dense_width=int(arch["hidden_size"] * arch["mlp_factor"]),
        expert_width=arch["moe_expert_width"], num_experts=arch["moe_num_experts"],
        heads=heads, kv_heads=arch.get("attention_num_kv_heads") or heads,
        head_dim=arch.get("attention_head_dim") or arch["hidden_size"] // heads)
    return 100.0 * flops / seconds / peaks["flops_per_s"]
