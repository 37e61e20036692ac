"""Per-layer metrics of a stack whose LATENT attention differs by layer
(``layer_pattern`` with sparse ``latent`` layers, paged, beside
``window_latent`` layers that keep a ring of latent lines a slot:
``scaling_tpu/nn/window_latent_attention.py``; ``mlp`` / ``moe`` FFNs).

Sources as ``readers/layered_gqa.py`` has them. Device times from the
profiler's trace by scope, as UNIONS of intervals: the program puts a windowed
latent layer's mixer under ``jax.named_scope("window_latent_attn")`` (inside it
``window_latent_attend``, the walk over the rows' rings and its kernel
``latent_ring_attention``, and ``gate``) and a full layer's under ``attn``
(indexer, choice, masked stream, gate); an executed operation is looked up, by
its instruction's name, in the HLO that the trace's metadata plane holds
(``benchmark/xplane_hlo.py``). What the engine ran comes from its own spans and
counters, through ``obs.last_capture()``: every ``serve.mixed`` span of such a
model carries ``window_latent_layers``, ``window_latent_visible_lines``,
``window_latent_pairs``, ``window_latent_single_rows`` and
``window_latent_chunk_rows`` beside the sparse layers' ``chosen_pairs`` and
``index_pairs``.

Without a capture, without the scopes in the trace or without the span fields
(any other model, a program from before they existed) a reader returns
nothing, not 0, and never raises.
"""

from __future__ import annotations

import functools
import re
from pathlib import Path

from benchmark import layered_latent_ops_count as ops_count
from benchmark import trace_reduce, xplane_hlo
from benchmark.readers import moe
from benchmark.readers.hybrid import counters_of, span_fields, union_seconds
from benchmark.readers.program_spans import (
    OUTPUT_TOKENS, PREFILL_TOKENS, TICK, counter_moved, last_capture,
)

NAMES = ("window_latent_attn", "window_latent_attend", "attn")
SCOPES = {name: re.compile(rf"(^|/){name}(/|$)") for name in NAMES}
ANY = re.compile(rf"(^|/)({'|'.join(NAMES)})(/|$)")
KERNEL = "latent_ring_attention"
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


def ring_ticks(spans=None):
    """The fields of every traced ``serve.mixed`` span of a model with
    windowed latent layers."""
    return span_fields(MIXED, "window_latent_pairs", spans)


def sizes(arch: dict, prefix: str = "") -> dict:
    """``heads, q_lora, kv_lora, nope, rope, v`` of a kind of latent layer."""
    return {"heads": arch[f"{prefix}num_attention_heads"],
            "q_lora": arch[f"{prefix}q_lora_rank"],
            "kv_lora": arch[f"{prefix}kv_lora_rank"],
            "nope": arch[f"{prefix}qk_nope_head_dim"],
            "rope": arch[f"{prefix}qk_rope_head_dim"],
            "v": arch[f"{prefix}v_head_dim"]}


def window_sizes(arch: dict):
    if "window_latent" not in (arch.get("layer_pattern") or []):
        return None
    return sizes(arch, "window_latent_")


def share_pct(scope: str, ops, spans):
    """Device time of the operations in ``scope`` over that of all operations
    of the traced ticks."""
    if not ring_ticks(spans):
        return None
    ops = traced_ops() if ops is None else ops
    inside = [op for op in ops if SCOPES[scope].search(op[3])]
    total = union_seconds(ops)
    if not inside or total <= 0:
        return None
    return 100.0 * union_seconds(inside) / total


def window_latent_time_pct(ctx, ops=None, spans=None):
    """The windowed latent layers' mixers (projections, rotary, the ring's
    scatter, the walk, the gate, the output projection)."""
    return share_pct("window_latent_attn", ops, spans)


def sparse_full_time_pct(ctx, ops=None, spans=None):
    """The full layers' mixers (projections, indexer, choice, masked stream,
    gate): what the shared sparse mechanism costs at these sizes."""
    return share_pct("attn", ops, spans)


def kernel_seconds(ops):
    """Device seconds of the ring kernel alone, by its name, among the
    operations of the walk's scope; None without one."""
    ops = traced_ops() if ops is None else ops
    walk = [op for op in ops if SCOPES["window_latent_attend"].search(op[3])]
    kernel = [op for op in walk if KERNEL in trace_reduce.stem(op[0])]
    return union_seconds(kernel) if kernel else None


def window_latent_roofline(ctx, ops=None, spans=None):
    """The least time the chip could take for the traced ticks' attention
    under the window (a tick and a layer: the larger of the pairs' FLOPs over
    the bf16 peak and the visible ring lines' bytes over the HBM bandwidth)
    over the device time of the kernel ``latent_ring_attention`` alone."""
    ticks, peaks = ring_ticks(spans), ctx["device"]["peaks"]
    a = window_sizes(ctx["config"]["transformer_architecture"])
    if not ticks or peaks is None or a is None:
        return None
    seconds = kernel_seconds(ops)
    if not seconds:
        return None
    least = sum(
        f["window_latent_layers"] * max(
            ops_count.window_flops(f["window_latent_visible_lines"],
                                   f["window_latent_pairs"], a)
            / peaks["flops_per_s"],
            ops_count.window_bytes(f["window_latent_visible_lines"], a, BF16_BYTES)
            / peaks["hbm_bytes_per_s"])
        for f in ticks)
    return 100.0 * least / seconds


def window_latent_chunk_row_pct(ctx, spans=None):
    """Of the rows the windowed latent layers advanced in the traced ticks,
    the share that brought a chunk (a call of the ring kernel of their own);
    the rest brought one token and went in one call together."""
    ticks = ring_ticks(spans)
    rows = sum(f["window_latent_chunk_rows"] + f["window_latent_single_rows"]
               for f in ticks)
    if not rows:
        return None
    return 100.0 * sum(f["window_latent_chunk_rows"] for f in ticks) / rows


def tick_mfu_pct(ctx, spans=None, counters=None):
    """The whole tick's share of the chip's bf16 peak: FLOPs the traced
    ticks' real tokens require under selection and window
    (``layered_latent_ops_count.serve_flops``) over the traced ticks' time
    (their ``serve.tick`` spans: host and device)."""
    if spans is None:
        capture = last_capture()
        spans = capture.spans if capture else []
    counters = counters_of(counters)
    peaks = ctx["device"]["peaks"]
    seconds = sum(dur for n, _, dur, _ in spans if n == TICK) / 1e9
    outputs = counter_moved(counters, OUTPUT_TOKENS)
    tokens = outputs + counter_moved(counters, PREFILL_TOKENS)
    ticks = [f for f in ring_ticks(spans) if "chosen_pairs" in f]
    arch = ctx["config"]["transformer_architecture"]
    a = window_sizes(arch)
    if not ticks or peaks is None or seconds <= 0 or not tokens or a is None:
        return None
    pattern = arch["layer_pattern"]
    flops = ops_count.serve_flops(
        tokens, outputs, counter_moved(counters, ASSIGNMENTS),
        sum(f["chosen_pairs"] for f in ticks), sum(f["index_pairs"] for f in ticks),
        sum(f["window_latent_pairs"] for f in ticks),
        sum(f["window_latent_visible_lines"] for f in ticks),
        full_layers=pattern.count("latent"),
        window_layers=pattern.count("window_latent"),
        dense_layers=pattern.count("mlp"), routed_layers=pattern.count("moe"),
        hidden=arch["hidden_size"], vocab=arch["vocab_size"],
        dense_width=int(arch["hidden_size"] * arch["mlp_factor"]),
        expert_width=arch["moe_expert_width"],
        shared_width=arch.get("moe_shared_expert_width") or 0,
        num_experts=arch["moe_num_experts"], full=sizes(arch), window=a,
        index_heads=arch["index_n_heads"], index_dim=arch["index_head_dim"])
    return 100.0 * flops / seconds / peaks["flops_per_s"]
