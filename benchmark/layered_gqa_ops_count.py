"""Operations and bytes a stack of Laguna blocks needs (grouped-query attention
that differs by LAYER: full layers over the whole context, window layers over
``window`` lines at most, each kind with its own head count and a per-head
gate; a dense FFN in the leading block, then softmax-routed SwiGLU experts with
one shared expert; an untied head), computed from the configuration's shapes
and from what the program counted. The benchmark's own counts (the yardstick):
a later PR that claims a gain cannot change them.

The window is counted as the WORK under the window, whatever implements it: a
(query, visible line) pair costs ``2 x 2 x heads x head_dim`` FLOP (its score
and its share of the value sum, every query head); the bytes are a LOWER
bound, so that no share can pass 100% by the count's fault: a row whose
queries together see ``lines`` lines (``min(context + new, window - 1 + new)``)
reads each of them once, K and V.
"""

from __future__ import annotations

MLP_MATRICES = 3   # a gated MLP: gate, up, down


def attention_matmul_params(hidden: int, heads: int, kv_heads: int,
                            head_dim: int) -> int:
    """W_Q, W_K, W_V, W_O and the per-head gate W_g of one layer."""
    return (2 * hidden * heads * head_dim + 2 * hidden * kv_heads * head_dim
            + hidden * heads)


def pair_flops(pairs: int, heads: int, head_dim: int) -> float:
    """Attention over (query, visible line) pairs: 36,864 FLOP a pair in a
    window layer of Laguna-S-2.1 (2 x 2 x 72 x 128), 24,576 in a full one."""
    return 4.0 * heads * head_dim * pairs


def line_bytes(lines: int, kv_heads: int, head_dim: int, itemsize: int) -> int:
    """The least the attention reads: ``lines`` lines of K and V (2 x 8 x 128
    values: 4,096 B in bf16)."""
    return lines * 2 * kv_heads * head_dim * itemsize


def serve_flops(tokens: int, sampled_tokens: int, held_assignments: int,
                full_pairs: int, window_pairs: int, *, full_layers: int,
                window_layers: int, dense_layers: int, routed_layers: int,
                hidden: int, vocab: int, heads: int, window_heads: int,
                kv_heads: int, head_dim: int, dense_width: int,
                expert_width: int, shared_width: int, num_experts: int) -> float:
    """FLOPs the ticks' real tokens REQUIRE, 2 a multiply-add: every token
    meets every attention layer's matrices (its kind's), the dense FFN, every
    routed layer's router and shared expert; an assignment that fell on a held
    expert its three matrices; a SAMPLED token the head; attention by the
    (query, visible line) pairs of each kind, summed over the ticks and
    counted a layer of the kind."""
    per_token = (
        full_layers * attention_matmul_params(hidden, heads, kv_heads, head_dim)
        + window_layers * attention_matmul_params(
            hidden, window_heads, kv_heads, head_dim)
        + dense_layers * MLP_MATRICES * hidden * dense_width
        + routed_layers * (hidden * num_experts
                           + MLP_MATRICES * hidden * shared_width))
    matrices = 2.0 * (tokens * per_token
                      + held_assignments * MLP_MATRICES * hidden * expert_width
                      + sampled_tokens * hidden * vocab)
    return (matrices + full_layers * pair_flops(full_pairs, heads, head_dim)
            + window_layers * pair_flops(window_pairs, window_heads, head_dim))
