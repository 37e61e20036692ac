"""Per-layer metrics of a stack of SPARSE latent-attention blocks
(``layer_pattern`` with ``latent`` layers that have an indexer:
``scaling_tpu/nn/sparse_latent_attention.py``; ``mlp`` / ``moe`` FFNs).

Two sources, as ``readers/latent.py`` has them. Device times are read from the
profiler's trace: the program puts a latent mixer under ``jax.named_scope(
"attn")`` and, inside it, everything the indexer adds (its three projections,
LayerNorm, rotary, the scatter of its key, scores and choice) under
``indexer``, the scores and the choice alone under ``index_select``, and the
gather of the chosen lines with the attention over them under
``sparse_attend``; an executed operation is looked up, by its instruction's
name, in the HLO that the trace's metadata plane holds
(``readers/parallel_hybrid.py`` ``traced_ops``: it keeps the ``op_name`` of
every operation inside ``attn``, and these scopes lie inside it). Times are
UNIONS of intervals. What the engine ran comes from its own spans and
counters, through ``obs.last_capture()``: every ``serve.mixed`` span of such a
model carries ``sparse_layers``, ``index_lines`` (index keys the indexer
reads: the rows' context + new tokens), ``index_pairs`` ((query, visible
line) pairs it scores), ``chosen_pairs`` ((query, chosen line) pairs the
attention multiplies) and ``chosen_lines`` (lines the attention must at least
read: ``min(visible, index_topk)`` a row).

Without a capture, without the scope in the trace or without the span fields
(a model without such layers, a program from before they existed) a reader
returns nothing, not 0, and never raises.
"""

from __future__ import annotations

import re

from benchmark import sparse_latent_ops_count as ops_count
from benchmark.readers.hybrid import counters_of, span_fields, union_seconds
from benchmark.readers.latent import ASSIGNMENTS, BF16_BYTES, attention_shape
from benchmark.readers.parallel_hybrid import traced_ops
from benchmark.readers.program_spans import (
    OUTPUT_TOKENS, PREFILL_TOKENS, TICK, counter_moved, last_capture,
)

MIXED = "serve.mixed"
SCOPES = {name: re.compile(rf"(^|/){name}(/|$)")
          for name in ("indexer", "index_select", "sparse_attend")}


def sparse_ticks(spans=None):
    """The fields of every traced ``serve.mixed`` span of a model with sparse
    latent attention layers."""
    return [f for f in span_fields(MIXED, "chosen_pairs", spans)
            if "index_pairs" in f and "sparse_layers" in f]


def scope_seconds(scope: str, ops=None):
    """``(device seconds inside scope, of all operations)`` of the traced
    ticks; None without an operation in the scope."""
    ops = traced_ops() if ops is None else ops
    inside = [op for op in ops if SCOPES[scope].search(op[3])]
    total = union_seconds(ops)
    if not inside or total <= 0:
        return None
    return union_seconds(inside), total


def least_seconds(ticks, peaks, flops_and_bytes) -> float:
    """Sum over ticks and layers of the larger of FLOPs over the bf16 peak
    and bytes over the HBM bandwidth."""
    least = 0.0
    for f in ticks:
        flops, nbytes = flops_and_bytes(f)
        least += f["sparse_layers"] * max(flops / peaks["flops_per_s"],
                                          nbytes / peaks["hbm_bytes_per_s"])
    return least


def sparse_latent_roofline(ctx, ops=None, spans=None):
    """The least time the chip could take for the traced ticks' attention
    over the CHOSEN lines over the device time of what attends over them
    (``sparse_attend``: the gather and the attention)."""
    ticks, peaks = sparse_ticks(spans), ctx["device"]["peaks"]
    if not ticks or peaks is None:
        return None
    seconds = scope_seconds("sparse_attend", ops)
    if seconds is None:
        return None
    a = attention_shape(ctx["config"]["transformer_architecture"])
    least = least_seconds(ticks, peaks, lambda f: (
        ops_count.chosen_flops(f["chosen_pairs"], a["heads"], a["kv_lora"], a["rope"]),
        ops_count.chosen_bytes(f.get("chosen_lines", 0), a["kv_lora"], a["rope"],
                               BF16_BYTES)))
    return 100.0 * least / seconds[0]


def indexer_roofline(ctx, ops=None, spans=None):
    """The least time the chip could take for the traced ticks' index scores
    over the device time of the scores AND the selection (``index_select``)."""
    ticks, peaks = sparse_ticks(spans), ctx["device"]["peaks"]
    if not ticks or peaks is None:
        return None
    seconds = scope_seconds("index_select", ops)
    if seconds is None:
        return None
    arch = ctx["config"]["transformer_architecture"]
    heads, dim = arch["index_n_heads"], arch["index_head_dim"]
    least = least_seconds(ticks, peaks, lambda f: (
        ops_count.index_flops(f["index_pairs"], heads, dim),
        ops_count.index_bytes(f["index_lines"], dim, BF16_BYTES)))
    return 100.0 * least / seconds[0]


def index_time_pct(ctx, ops=None, spans=None):
    """Device time of everything the indexers add over the device time of all
    operations of the traced ticks."""
    if not sparse_ticks(spans):
        return None
    seconds = scope_seconds("indexer", ops)
    return None if seconds is None else 100.0 * seconds[0] / seconds[1]


def sparse_chosen_pct(ctx, spans=None):
    """Chosen pairs over visible pairs of the traced ticks: how much of the
    cache the mechanism spared (100: it did nothing)."""
    ticks = sparse_ticks(spans)
    visible = sum(f["index_pairs"] for f in ticks)
    if not visible:
        return None
    return 100.0 * sum(f["chosen_pairs"] for f in ticks) / visible


def tick_mfu_pct(ctx, spans=None, counters=None):
    """The whole tick's share of the chip's bf16 peak: FLOPs the traced
    ticks' real tokens require UNDER SELECTION
    (``sparse_latent_ops_count.serve_flops``) over the traced ticks' time
    (their ``serve.tick`` spans: host and device)."""
    if spans is None:
        capture = last_capture()
        spans = capture.spans if capture else []
    counters = counters_of(counters)
    peaks = ctx["device"]["peaks"]
    seconds = sum(dur for n, _, dur, _ in spans if n == TICK) / 1e9
    outputs = counter_moved(counters, OUTPUT_TOKENS)
    tokens = outputs + counter_moved(counters, PREFILL_TOKENS)
    ticks = sparse_ticks(spans)
    if not ticks or peaks is None or seconds <= 0 or not tokens:
        return None
    arch = ctx["config"]["transformer_architecture"]
    pattern = arch["layer_pattern"]
    flops = ops_count.serve_flops(
        tokens, outputs, counter_moved(counters, ASSIGNMENTS),
        sum(f["chosen_pairs"] for f in ticks), sum(f["index_pairs"] for f in ticks),
        sparse_layers=pattern.count("latent"), dense_layers=pattern.count("mlp"),
        routed_layers=pattern.count("moe"), hidden=arch["hidden_size"],
        vocab=arch["vocab_size"],
        dense_width=int(arch["hidden_size"] * arch["mlp_factor"]),
        expert_width=arch["moe_expert_width"],
        shared_width=arch.get("moe_shared_expert_width") or 0,
        num_experts=arch["moe_num_experts"], attention=attention_shape(arch),
        index_heads=arch["index_n_heads"], index_dim=arch["index_head_dim"])
    return 100.0 * flops / seconds / peaks["flops_per_s"]
