"""Per-layer metrics of a stack of SPARSE grouped-query blocks
(``layer_pattern`` with ``attention`` layers that have an indexer:
``scaling_tpu/nn/sparse_attention.py``; ``moe`` FFNs).

Sources as ``readers/sparse_latent.py`` has them, whose span fields, scopes and
helpers these readers share (the engine annotates either sparse kind alike):
device times from the profiler's trace by scope (the sparse mixer under
``jax.named_scope("attn")``, inside it ``indexer`` / ``index_select`` /
``sparse_attend``), as UNIONS of intervals; what the engine ran from the
``serve.mixed`` spans' ``sparse_layers``, ``index_pairs``, ``chosen_pairs``,
``chosen_lines`` and the counters, through ``obs.last_capture()``. The three
metrics the two sparse kinds share (``indexer_roofline``, ``index_time_pct``,
``sparse_chosen_pct``) are ``readers/sparse_latent.py``'s, which read the
indexer's sizes from the architecture.

Without a capture, without the scope in the trace, without the span fields or
with a configuration whose sparse layers are latent, a reader returns nothing,
not 0, and never raises.
"""

from __future__ import annotations

from benchmark import sparse_gqa_ops_count as ops_count
from benchmark.readers.hybrid import counters_of, union_seconds
from benchmark.readers.latent import ASSIGNMENTS, ATTN, BF16_BYTES
from benchmark.readers.parallel_hybrid import traced_ops
from benchmark.readers.program_spans import (
    OUTPUT_TOKENS, PREFILL_TOKENS, TICK, counter_moved, last_capture,
)
from benchmark.readers.sparse_latent import least_seconds, scope_seconds, sparse_ticks


def attention_shape(arch: dict):
    """``heads, kv_heads, head_dim`` of a stack whose sparse layers are
    grouped-query ``attention`` layers; None for any other."""
    pattern = arch.get("layer_pattern") or []
    if "attention" not in pattern or "latent" in pattern or arch.get("index_topk") is None:
        return None
    heads = arch["num_attention_heads"]
    return {"heads": heads,
            "kv_heads": arch.get("attention_num_kv_heads") or heads,
            "head_dim": arch.get("attention_head_dim") or arch["hidden_size"] // heads}


def sparse_paged_roofline(ctx, ops=None, spans=None):
    """The least time the chip could take for the traced ticks' attention
    over the CHOSEN lines over the device time of what attends over them
    (``sparse_attend``: the gather of a row's window and the kernel)."""
    ticks, peaks = sparse_ticks(spans), ctx["device"]["peaks"]
    a = attention_shape(ctx["config"]["transformer_architecture"])
    if not ticks or peaks is None or a is None:
        return None
    seconds = scope_seconds("sparse_attend", ops)
    if seconds is None:
        return None
    least = least_seconds(ticks, peaks, lambda f: (
        ops_count.chosen_flops(f["chosen_pairs"], a["heads"], a["head_dim"]),
        ops_count.chosen_bytes(f.get("chosen_lines", 0), a["kv_heads"],
                               a["head_dim"], BF16_BYTES)))
    return 100.0 * least / seconds[0]


def sparse_gqa_time_pct(ctx, ops=None, spans=None):
    """Device time of the sparse attention mixers (the ``attn`` scope, their
    indexers included) over the device time of all operations of the traced
    ticks, as unions of intervals."""
    if (not sparse_ticks(spans)
            or attention_shape(ctx["config"]["transformer_architecture"]) is None):
        return None
    ops = traced_ops() if ops is None else ops
    inside = [op for op in ops if ATTN.search(op[3])]
    total = union_seconds(ops)
    if not inside or total <= 0:
        return None
    return 100.0 * union_seconds(inside) / total


def tick_mfu_pct(ctx, spans=None, counters=None):
    """The whole tick's share of the chip's bf16 peak: FLOPs the traced
    ticks' real tokens require UNDER SELECTION
    (``sparse_gqa_ops_count.serve_flops``) over the traced ticks' time (their
    ``serve.tick`` spans: host and device)."""
    if spans is None:
        capture = last_capture()
        spans = capture.spans if capture else []
    counters = counters_of(counters)
    peaks = ctx["device"]["peaks"]
    seconds = sum(dur for n, _, dur, _ in spans if n == TICK) / 1e9
    outputs = counter_moved(counters, OUTPUT_TOKENS)
    tokens = outputs + counter_moved(counters, PREFILL_TOKENS)
    ticks = sparse_ticks(spans)
    arch = ctx["config"]["transformer_architecture"]
    a = attention_shape(arch)
    if not ticks or peaks is None or seconds <= 0 or not tokens or a is None:
        return None
    flops = ops_count.serve_flops(
        tokens, outputs, counter_moved(counters, ASSIGNMENTS),
        sum(f["chosen_pairs"] for f in ticks), sum(f["index_pairs"] for f in ticks),
        layers=arch["layer_pattern"].count("attention"), hidden=arch["hidden_size"],
        vocab=arch["vocab_size"], heads=a["heads"], kv_heads=a["kv_heads"],
        head_dim=a["head_dim"], expert_width=arch["moe_expert_width"],
        num_experts=arch["moe_num_experts"], index_heads=arch["index_n_heads"],
        index_dim=arch["index_head_dim"])
    return 100.0 * flops / seconds / peaks["flops_per_s"]
