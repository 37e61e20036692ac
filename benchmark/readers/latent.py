"""Per-layer metrics of a stack of latent-attention blocks (``layer_pattern``
with ``latent`` layers: ``scaling_tpu/nn/latent_attention.py`` and its kernel
``nn/latent_paged_attention.py``; ``mlp`` / ``moe`` FFNs).

Two sources, as ``readers/hybrid.py`` has them. Device times are read from the
profiler's trace: the program puts a latent attention mixer (down and up
projections, the two norms, rotary, the pool's scatter, the absorption, the
kernel, W_UV and W_O) under ``jax.named_scope("attn")``; an executed operation
is looked up, by its instruction's name, in the HLO that the trace's metadata
plane holds (``benchmark/xplane_hlo.py``; ``readers/parallel_hybrid.py``
``traced_ops`` does the lookup, ``attn`` being one of its four scopes);
the kernel alone is the trace's ``pallas:latent_paged_attention`` class
(``benchmark/trace_reduce.py``). Times are UNIONS of intervals. What the engine
ran comes from its own spans and counters, through ``obs.last_capture()``:
every ``serve.mixed`` span of such a model carries ``latent_layers``,
``latent_lines`` (the lines a layer's attention reads: the rows' context + new
tokens) and ``latent_pairs`` (the (query, visible line) pairs).

Without a capture, without the scope or the kernel in the trace or without the
span fields (a model without such layers, a program from before they existed)
a reader returns nothing, not 0.
"""

from __future__ import annotations

from benchmark import latent_ops_count
from benchmark.readers.hybrid import counters_of, span_fields, union_seconds
# the lookup of an executed operation's scope in the HLO a trace holds, for
# `attn` among its four scopes: the reader that came with the first `attn`
from benchmark.readers.parallel_hybrid import SCOPES, traced_ops
from benchmark.readers.program_spans import (
    OUTPUT_TOKENS, PREFILL_TOKENS, TICK, counter_moved, last_capture,
)

ATTN = SCOPES["attn"]
MIXED = "serve.mixed"
KERNEL_CLASS = "pallas:latent_paged_attention"
ASSIGNMENTS = "serve_moe_assignments_total"
BF16_BYTES = 2


def latent_ticks(spans=None):
    """The fields of every traced ``serve.mixed`` span of a model with latent
    attention layers."""
    return span_fields(MIXED, "latent_pairs", spans)


def attention_shape(arch: dict) -> dict:
    return {"heads": arch["num_attention_heads"], "q_lora": arch["q_lora_rank"],
            "kv_lora": arch["kv_lora_rank"], "nope": arch["qk_nope_head_dim"],
            "rope": arch["qk_rope_head_dim"], "v": arch["v_head_dim"]}


def latent_time_pct(ctx, ops=None, spans=None):
    """Device time of the latent attention mixers over the device time of all
    operations of the traced ticks."""
    if not latent_ticks(spans):
        return None
    ops = traced_ops() if ops is None else ops
    inside = [op for op in ops if ATTN.search(op[3])]
    total = union_seconds(ops)
    if not inside or total <= 0:
        return None
    return 100.0 * union_seconds(inside) / total


def latent_roofline(ctx, spans=None):
    """The least time the chip could take for the traced ticks' attention
    over the latent cache (per tick and layer the larger of FLOPs over the
    bf16 peak and bytes over the HBM bandwidth:
    ``latent_ops_count.attention_flops``, ``line_bytes``) over the kernel's
    device time."""
    ticks = latent_ticks(spans)
    trace, peaks = ctx["trace"], ctx["device"]["peaks"]
    if not ticks or not trace or peaks is None:
        return None
    kernel_s = sum(v for k, v in trace["class_s"].items()
                   if k.startswith(KERNEL_CLASS))
    if kernel_s <= 0:
        return None
    a = attention_shape(ctx["config"]["transformer_architecture"])
    shape = {k: a[k] for k in ("heads", "kv_lora", "nope", "rope", "v")}
    nbytes = latent_ops_count.line_bytes(a["kv_lora"], a["rope"], BF16_BYTES)
    least = sum(f["latent_layers"] * max(
        latent_ops_count.attention_flops(
            f["latent_lines"], f["latent_pairs"], **shape) / peaks["flops_per_s"],
        f["latent_lines"] * nbytes / peaks["hbm_bytes_per_s"]) for f in ticks)
    return 100.0 * least / kernel_s


def tick_mfu_pct(ctx, spans=None, counters=None):
    """The whole tick's share of the chip's bf16 peak: FLOPs the traced
    ticks' real tokens require (``latent_ops_count.serve_flops``) over the
    traced ticks' time (their ``serve.tick`` spans: host and device)."""
    if spans is None:
        capture = last_capture()
        spans = capture.spans if capture else []
    counters = counters_of(counters)
    peaks = ctx["device"]["peaks"]
    seconds = sum(dur for n, _, dur, _ in spans if n == TICK) / 1e9
    outputs = counter_moved(counters, OUTPUT_TOKENS)
    tokens = outputs + counter_moved(counters, PREFILL_TOKENS)
    ticks = latent_ticks(spans)
    if not ticks or peaks is None or seconds <= 0 or not tokens:
        return None
    arch = ctx["config"]["transformer_architecture"]
    pattern = arch["layer_pattern"]
    flops = latent_ops_count.serve_flops(
        tokens, outputs, counter_moved(counters, ASSIGNMENTS),
        sum(f["latent_pairs"] for f in ticks),
        sum(f["latent_lines"] for f in ticks),
        latent_layers=pattern.count("latent"), dense_layers=pattern.count("mlp"),
        routed_layers=pattern.count("moe"), hidden=arch["hidden_size"],
        vocab=arch["vocab_size"],
        dense_width=int(arch["hidden_size"] * arch["mlp_factor"]),
        expert_width=arch["moe_expert_width"],
        shared_width=arch.get("moe_shared_expert_width") or 0,
        num_experts=arch["moe_num_experts"], attention=attention_shape(arch))
    return 100.0 * flops / seconds / peaks["flops_per_s"]
