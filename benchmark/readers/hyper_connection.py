"""Per-layer metrics of a stack whose residual is ``hc_streams`` streams mixed
by hyper-connections (``scaling_tpu/nn/hyper_connection.py``; the blocks are
latent attention and ``mlp`` / ``moe`` FFNs: ``readers/latent.py``).

Two sources, as ``readers/hybrid.py`` has them. Device times are read from the
profiler's trace: the program puts every mapping's ``pre`` (the statistic,
``vec(X) phi``, the gates, the Sinkhorn kernel, ``u``), its ``post`` (``X' =
H_res X + H_post y``) and the readout under ``jax.named_scope("hc")``, beside
and never inside ``attn`` / ``mlp`` / ``moe``; an executed operation is looked
up, by its instruction's name, in the HLO that the trace's metadata plane holds
(``benchmark/xplane_hlo.py``; ``moe.scoped_ops`` does the lookup). Times are
UNIONS of intervals. What the engine ran comes from its own spans and
counters, through ``obs.last_capture()``: every ``serve.mixed`` span of such a
model carries ``hc_streams`` and ``hc_sublayers``, and the counter
``serve_hc_token_sublayers_total`` moves by real tokens x sub-layers a tick.

Without a capture, without the scope in the trace or without the span fields
(a model with the plain residual, a program from before they existed) a reader
returns nothing, not 0, and never raises.
"""

from __future__ import annotations

import functools
import re
import sys
from pathlib import Path

from benchmark import hc_latent_ops_count, trace_reduce, xplane_hlo
from benchmark.readers import moe
from benchmark.readers.hybrid import counters_of, span_fields, union_seconds
from benchmark.readers.latent import ASSIGNMENTS, BF16_BYTES, attention_shape, latent_ticks
from benchmark.readers.program_spans import (
    OUTPUT_TOKENS, PREFILL_TOKENS, TICK, counter_moved, last_capture,
)

HC = re.compile(r"(^|/)hc(/|$)")
MIXED = "serve.mixed"
TOKEN_SUBLAYERS = "serve_hc_token_sublayers_total"


@functools.lru_cache(maxsize=2)
def load_scoped_ops(path) -> list:
    """``moe.scoped_ops`` of the first chip of a trace file for the scope
    ``hc``: ``[[name, start_ns, dur_ns, op_name or ''], ...]``."""
    events = trace_reduce.load_events(path)
    devices = {k: v for k, v in events["devices"].items() if v["ops"]}
    if not devices:
        return []
    first_chip = devices[min(devices, key=int)]
    hlo = xplane_hlo.hlo_modules(Path(path).read_bytes())
    scopes = {name: xplane_hlo.instruction_scopes(module, HC)
              for name, module in hlo.items()}
    return moe.scoped_ops(first_chip["ops"], first_chip["modules"], scopes)


def traced_ops():
    capture = last_capture()
    path = capture.trace_file() if capture else None
    return load_scoped_ops(path) if path is not None else []


def hc_ticks(spans=None):
    """The fields of every traced ``serve.mixed`` span of a hyper-connected
    model."""
    return span_fields(MIXED, "hc_sublayers", spans)


def hc_seconds(ops=None):
    """``(device seconds under the scope, of all operations)`` of the traced
    ticks; None if no operation lies in the scope."""
    ops = traced_ops() if ops is None else ops
    inside = [op for op in ops if HC.search(op[3])]
    total = union_seconds(ops)
    if not inside or total <= 0:
        return None
    return union_seconds(inside), total


def report(ops, keep: int = 8) -> None:
    """stderr: the residual path's device time by what the operation was
    compiled from (its ``op_name`` below the scope)."""
    by_part = {}
    for _, _, dur, op_name in ops:
        if HC.search(op_name):
            part = re.split(r"(?:^|/)hc/", op_name, maxsplit=1)[-1]
            entry = by_part.setdefault(part, [0, 0.0])
            entry[0] += 1
            entry[1] += dur / 1e9
    for part, (count, seconds) in sorted(by_part.items(), key=lambda kv: -kv[1][1])[:keep]:
        print(f"[hc] {seconds:9.6f} s  {part} x{count}", file=sys.stderr)
    sys.stderr.flush()


def hc_time_pct(ctx, ops=None, spans=None):
    """Device time of the residual path (mappings and readout) over the device
    time of all operations of the traced ticks."""
    if not hc_ticks(spans):
        return None
    if ops is None:
        ops = traced_ops()
        report(ops)
    seconds = hc_seconds(ops)
    return None if seconds is None else 100.0 * seconds[0] / seconds[1]


def hc_stream_roofline(ctx, ops=None, spans=None, counters=None):
    """Bandwidth-bound: the least bytes the traced ticks' residual path moves
    (``hc_latent_ops_count.stream_bytes`` of the counter's real tokens x
    sub-layers) over the ``hc`` scope's device time, as a share of the chip's
    published HBM bandwidth."""
    ticks, peaks = hc_ticks(spans), ctx["device"]["peaks"]
    moved = counter_moved(counters_of(counters), TOKEN_SUBLAYERS)
    seconds = hc_seconds(ops) if ticks and peaks is not None and moved else None
    if seconds is None:
        return None
    nbytes = hc_latent_ops_count.stream_bytes(
        moved, ticks[0]["hc_streams"],
        ctx["config"]["transformer_architecture"]["hidden_size"], BF16_BYTES)
    return 100.0 * nbytes / seconds[0] / peaks["hbm_bytes_per_s"]


def tick_mfu_pct(ctx, spans=None, counters=None):
    """The whole tick's share of the chip's bf16 peak: FLOPs the traced
    ticks' real tokens require (``hc_latent_ops_count.serve_flops``: the
    latent blocks' and the mappings' matmuls) over the traced ticks' time
    (their ``serve.tick`` spans: host and device)."""
    if spans is None:
        capture = last_capture()
        spans = capture.spans if capture else []
    counters = counters_of(counters)
    peaks = ctx["device"]["peaks"]
    seconds = sum(dur for n, _, dur, _ in spans if n == TICK) / 1e9
    outputs = counter_moved(counters, OUTPUT_TOKENS)
    tokens = outputs + counter_moved(counters, PREFILL_TOKENS)
    ticks, latent = hc_ticks(spans), latent_ticks(spans)
    if not ticks or not latent or peaks is None or seconds <= 0 or not tokens:
        return None
    arch = ctx["config"]["transformer_architecture"]
    pattern = arch["layer_pattern"]
    flops = hc_latent_ops_count.serve_flops(
        tokens, outputs, counter_moved(counters, ASSIGNMENTS),
        sum(f["latent_pairs"] for f in latent),
        sum(f["latent_lines"] for f in latent),
        counter_moved(counters, TOKEN_SUBLAYERS), streams=ticks[0]["hc_streams"],
        latent_layers=pattern.count("latent"), dense_layers=pattern.count("mlp"),
        routed_layers=pattern.count("moe"), hidden=arch["hidden_size"],
        vocab=arch["vocab_size"],
        dense_width=int(arch["hidden_size"] * arch["mlp_factor"]),
        expert_width=arch["moe_expert_width"],
        shared_width=arch.get("moe_shared_expert_width") or 0,
        num_experts=arch["moe_num_experts"], attention=attention_shape(arch))
    return 100.0 * flops / seconds / peaks["flops_per_s"]
