"""Per-layer metrics of a looped trunk (``loop_steps > 1``: the program's
``_run_looped`` in ``scaling_tpu/models/transformer/inference.py``).

Two sources, as ``readers/moe.py`` has them. The device time of the trunk's
steps is read from the profiler's trace: the program runs them under
``jax.named_scope("loop")``, and an executed operation is looked up, by its
instruction's name, in the HLO that the trace's metadata plane holds
(``benchmark/xplane_hlo.py``; ``moe.scoped_ops`` does the lookup). The steps
are one rolled loop, so the trace may hold the ``while`` itself as an
operation that spans its body's: times here are UNIONS of intervals, never
sums, and neither layout counts a nanosecond twice. What the engine ran comes
from its own spans and counters, through ``obs.last_capture()``: every
``serve.mixed`` span of a looped model carries ``loop_steps``, the counter
``serve_loop_layer_passes_total`` counts the (step, layer) passes, and the
token counters say how many tokens the ticks processed.

Without a capture, without the scope in the trace (a plain model, a program
from before the scope existed) or without the span field a reader returns
nothing, not 0.
"""

from __future__ import annotations

import functools
import re
import sys
from pathlib import Path

from benchmark import looped_ops_count, ops_count, trace_reduce, xplane_hlo
from benchmark.readers import moe
from benchmark.readers.program_spans import (
    OUTPUT_TOKENS, PREFILL_TOKENS, TICK, counter_moved, last_capture,
)

SCOPE = re.compile(r"(^|/)loop(/|$)")
MIXED = "serve.mixed"
LAYER_PASSES = "serve_loop_layer_passes_total"
BF16_BYTES = 2


@functools.lru_cache(maxsize=2)
def load_scoped_ops(path) -> list:
    """``moe.scoped_ops`` of the first chip of a trace file, for the scope
    ``loop``: ``[[name, start_ns, dur_ns, scope or ''], ...]``."""
    events = trace_reduce.load_events(path)
    devices = {k: v for k, v in events["devices"].items() if v["ops"]}
    if not devices:
        return []
    first_chip = devices[min(devices, key=int)]
    hlo = xplane_hlo.hlo_modules(Path(path).read_bytes())
    scopes = {name: xplane_hlo.instruction_scopes(module, SCOPE)
              for name, module in hlo.items()}
    rows = moe.scoped_ops(first_chip["ops"], first_chip["modules"], scopes)
    if not any(r[3] for r in rows):
        print(f"[loop] none of {len(rows)} operations lies in the scope; the trace "
              f"holds the HLO of {sorted(hlo)}", file=sys.stderr, flush=True)
    return rows


def traced_ops():
    capture = last_capture()
    path = capture.trace_file() if capture else None
    return load_scoped_ops(path) if path is not None else []


def union_seconds(ops) -> float:
    """Seconds the device spent in ``ops``, overlapping ones counted once."""
    return sum(b - a for a, b in trace_reduce.union_intervals(
        [(start, start + dur) for _, start, dur, *_ in ops])) / 1e9


def loop_seconds(ops):
    """Device seconds inside the scope; None if no operation lies in it."""
    inside = [op for op in ops if op[3]]
    return union_seconds(inside) if inside else None


def kernel_seconds(ops) -> float:
    """Device seconds of the Pallas kernels inside the scope: the paged
    kernel, the one the engine's program holds."""
    return union_seconds([op for op in ops
                          if op[3] and trace_reduce.PALLAS_TARGET in op[0]])


def loop_ticks(spans=None):
    """``loop_steps`` of every traced tick of a looped model (its
    ``serve.mixed`` span's field). ``spans``: a capture's rows; the last
    capture's if not given."""
    if spans is None:
        capture = last_capture()
        spans = capture.spans if capture else []
    return [f["loop_steps"] for n, _, _, f in spans if n == MIXED and "loop_steps" in f]


def trunk_shape(arch: dict):
    """(layers, matmul parameters of a layer, heads, head size)."""
    heads = arch["num_attention_heads"]
    head_dim = arch["hidden_size"] // heads
    return (arch["num_layers"], looped_ops_count.layer_matmul_params(
        arch["hidden_size"], heads, arch.get("attention_num_kv_heads") or heads,
        head_dim, int(arch["hidden_size"] * arch["mlp_factor"])), heads, head_dim)


def loop_time_pct(ctx, ops=None):
    """Device time of the looped trunk over the device time of all
    operations of the traced ticks."""
    ops = traced_ops() if ops is None else ops
    inside = loop_seconds(ops)
    total = union_seconds(ops)
    if inside is None or total <= 0:
        return None
    return 100.0 * inside / total


def loop_weights_roofline(ctx, ops=None, spans=None):
    """Bandwidth-bound: bytes of trunk weights the traced ticks had to read
    (each tick every layer's matrices once a step) over the trunk's device
    time less the paged kernel's, as a share of the chip's published HBM
    bandwidth."""
    ticks = loop_ticks(spans)
    peaks = ctx["device"]["peaks"]
    ops = traced_ops() if ops is None else ops
    inside = loop_seconds(ops)
    if not ticks or inside is None or peaks is None:
        return None
    layers, layer_params, _, _ = trunk_shape(ctx["config"]["transformer_architecture"])
    nbytes = looped_ops_count.trunk_weight_bytes(
        sum(ticks) * layers, layer_params, BF16_BYTES)
    seconds = inside - kernel_seconds(ops)
    return 100.0 * nbytes / seconds / peaks["hbm_bytes_per_s"] if seconds > 0 else None


def paged_roofline_looped(ctx):
    """``device_trace.paged_roofline`` with the cache lines a looped model
    really reads: a row's context once a (step, layer), not once a layer."""
    trace, host, peaks = ctx["trace"], ctx["host"], ctx["device"]["peaks"]
    if not trace or peaks is None:
        return None
    kernel_s = sum(v for k, v in trace["class_s"].items()
                   if k.startswith("pallas:") and "splash" not in k)
    tokens = host.get("traced_context_tokens")
    arch = ctx["config"]["transformer_architecture"]
    steps = arch.get("loop_steps", 1)
    if kernel_s <= 0 or not tokens or steps < 2:
        return None
    heads = arch["num_attention_heads"]
    nbytes = steps * arch["num_layers"] * ops_count.paged_kv_bytes(
        tokens, arch.get("attention_num_kv_heads") or heads,
        arch["hidden_size"] // heads, BF16_BYTES)
    return 100.0 * nbytes / kernel_s / peaks["hbm_bytes_per_s"]


def loop_steps_run_mean(ctx, spans=None, counters=None):
    """Steps of the loop a traced tick really ran, mean over the ticks: the
    (step, layer) passes the program counted over ticks x layers. Equal to
    ``loop_steps`` while every token runs every step."""
    if counters is None:
        capture = last_capture()
        counters = capture.counters if capture else {}
    ticks = loop_ticks(spans)
    passes = counter_moved(counters, LAYER_PASSES)
    if not ticks or not passes:
        return None
    return passes / (len(ticks) * ctx["config"]["transformer_architecture"]["num_layers"])


def tick_mfu_pct(ctx, spans=None, counters=None):
    """The whole tick's share of the chip's bf16 peak: FLOPs the traced
    ticks' real tokens require (``looped_ops_count.serve_flops``) over the
    traced ticks' time (their ``serve.tick`` spans: host and device)."""
    capture = last_capture()
    if spans is None:
        spans = capture.spans if capture else []
    if counters is None:
        counters = capture.counters if capture else {}
    ticks = loop_ticks(spans)
    peaks = ctx["device"]["peaks"]
    seconds = sum(dur for n, _, dur, _ in spans if n == TICK) / 1e9
    outputs = counter_moved(counters, OUTPUT_TOKENS)
    tokens = outputs + counter_moved(counters, PREFILL_TOKENS)
    if not ticks or peaks is None or seconds <= 0 or not tokens:
        return None
    arch = ctx["config"]["transformer_architecture"]
    layers, layer_params, heads, head_dim = trunk_shape(arch)
    flops = looped_ops_count.serve_flops(
        tokens, outputs, ctx["host"].get("traced_context_tokens") or 0,
        max(ticks), layers, layer_params, arch["hidden_size"] * arch["vocab_size"],
        heads, head_dim)
    return 100.0 * flops / seconds / peaks["flops_per_s"]
