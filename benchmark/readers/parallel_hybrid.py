"""Per-layer metrics of a stack of parallel blocks (``parallel_ssm``: a Mamba-2
mixer beside GQA attention on one normed input, ``scaling_tpu/nn/mamba.py`` and
``nn/attention.py``; a SwiGLU MLP; an untied head).

Two sources, as ``readers/hybrid.py`` has them. Device times are read from the
profiler's trace: the program puts a block's attention (projections, rotary,
the pool's scatter, the paged kernel, the output projection) under
``jax.named_scope("attn")``, its Mamba-2 mixer under ``"ssm"``, its MLP under
``"mlp"``, and the final norm, the head of the sampled positions and the
sampler under ``"head"``; an executed operation is looked up, by its
instruction's name, in the HLO that the trace's metadata plane holds
(``benchmark/xplane_hlo.py``; ``moe.scoped_ops`` does the lookup). Times are
UNIONS of intervals, so nothing is counted twice. What the engine ran comes
from its own spans and counters, through ``obs.last_capture()``: every
``serve.mixed`` span of such a model carries ``par_lines`` (layers whose two
mixers run side by side).

Without a capture, without the scope in the trace or without the span field (a
model without such layers, a program from before they existed) a reader
returns nothing, not 0.
"""

from __future__ import annotations

import functools
import re
from pathlib import Path

from benchmark import parallel_hybrid_ops_count, trace_reduce, xplane_hlo
from benchmark.readers import moe
from benchmark.readers.hybrid import (
    counters_of, mamba_shape, span_fields, union_seconds,
)
from benchmark.readers.program_spans import (
    OUTPUT_TOKENS, PREFILL_TOKENS, TICK, counter_moved, last_capture,
)

SCOPES = {name: re.compile(rf"(^|/){name}(/|$)")
          for name in ("attn", "ssm", "mlp", "head")}
ANY = re.compile(r"(^|/)(attn|ssm|mlp|head)(/|$)")
MIXED = "serve.mixed"


@functools.lru_cache(maxsize=2)
def load_scoped_ops(path) -> list:
    """``moe.scoped_ops`` of the first chip of a trace file for the four
    scopes at once: ``[[name, start_ns, dur_ns, op_name or ''], ...]``."""
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


def share_pct(ops, spans, *scopes):
    """Device time inside any of ``scopes`` over the device time of all
    operations, in percent; None unless the traced ticks are a parallel
    stack's and an operation lies in the scopes."""
    if not span_fields(MIXED, "par_lines", spans):
        return None
    ops = traced_ops() if ops is None else ops
    inside = [op for op in ops if any(SCOPES[s].search(op[3]) for s in scopes)]
    total = union_seconds(ops)
    if not inside or total <= 0:
        return None
    return 100.0 * union_seconds(inside) / total


def parmix_time_pct(ctx, ops=None, spans=None):
    """Device time of the blocks' two mixers, attention and Mamba-2, over the
    device time of all operations of the traced ticks."""
    return share_pct(ops, spans, "attn", "ssm")


def mlp_time_pct(ctx, ops=None, spans=None):
    """Device time of the blocks' MLPs over that of all operations."""
    return share_pct(ops, spans, "mlp")


def head_time_pct(ctx, ops=None, spans=None):
    """Device time of final norm, head and sampler over that of all
    operations."""
    return share_pct(ops, spans, "head")


def tick_mfu_pct(ctx, spans=None, counters=None):
    """The whole tick's share of the chip's bf16 peak: FLOPs the traced
    ticks' real tokens require (``parallel_hybrid_ops_count.serve_flops``)
    over the traced ticks' time (their ``serve.tick`` spans: host and
    device)."""
    if spans is None:
        capture = last_capture()
        spans = capture.spans if capture else []
    counters = counters_of(counters)
    peaks = ctx["device"]["peaks"]
    seconds = sum(dur for n, _, dur, _ in spans if n == TICK) / 1e9
    outputs = counter_moved(counters, OUTPUT_TOKENS)
    tokens = outputs + counter_moved(counters, PREFILL_TOKENS)
    if (not span_fields(MIXED, "par_lines", spans) or peaks is None
            or seconds <= 0 or not tokens):
        return None
    arch = ctx["config"]["transformer_architecture"]
    heads = arch["num_attention_heads"]
    flops = parallel_hybrid_ops_count.serve_flops(
        tokens, outputs, ctx["host"].get("traced_context_tokens") or 0,
        layers=arch["num_layers"], hidden=arch["hidden_size"],
        vocab=arch["vocab_size"],
        mlp_width=int(arch["hidden_size"] * arch["mlp_factor"]),
        mamba=mamba_shape(arch), heads=heads,
        kv_heads=arch.get("attention_num_kv_heads") or heads,
        head_dim=arch.get("attention_head_dim") or arch["hidden_size"] // heads)
    return 100.0 * flops / seconds / peaks["flops_per_s"]
