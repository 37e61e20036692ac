"""Operations and bytes a stack of SPARSE grouped-query blocks (grouped-query
attention over each query's ``index_topk`` best lines, chosen by an indexer
that reads the hidden state; then softmax-routed SwiGLU experts; an untied
head) needs, computed from shapes and from what the program counted. The
benchmark's own counts (the yardstick): a later PR that claims a gain cannot
change them.

Everything is counted as the WORK under selection, whatever implements it (a
version that streams every visible line and masks reads low on these
rooflines; one that gathers reads what it gathers). A (query, CHOSEN line)
pair costs ``2 x 2 x heads x head_dim`` FLOP (its score and its share of the
value sum, every query head). A (query, visible line) pair costs the indexer
``2 index_heads index_dim`` FLOP (``sparse_latent_ops_count.index_flops``: the
indexer's arithmetic is the sparse latent layer's). Bytes are LOWER bounds, so
that no share can pass 100% by the count's fault: the attention reads at least
``min(visible, index_topk)`` lines of K and V a row (``chosen_lines``: the
union of a row's queries' choices is no smaller).
"""

from __future__ import annotations

from benchmark.sparse_latent_ops_count import index_bytes, index_flops  # noqa: F401

MLP_MATRICES = 3   # a gated expert: gate, up, down


def attention_matmul_params(hidden: int, heads: int, kv_heads: int,
                            head_dim: int) -> int:
    """W_Q, W_K, W_V, W_O of one layer; the QK-norms' vectors are not
    counted."""
    return 2 * hidden * heads * head_dim + 2 * hidden * kv_heads * head_dim


def indexer_matmul_params(hidden: int, index_heads: int, index_dim: int) -> int:
    """Parameters of ONE indexer that take part in a matrix multiplication:
    W_IQ (hidden x heads dim), W_IK (hidden x dim), W_Iw (hidden x heads); its
    LayerNorm's two vectors are not counted."""
    return hidden * index_heads * index_dim + hidden * index_dim + hidden * index_heads


def chosen_flops(chosen_pairs: int, heads: int, head_dim: int) -> float:
    """Attention over the chosen lines: 16,384 FLOP a pair at Keye-VL-2.0's
    sizes (2 x 2 x 32 x 128)."""
    return 4.0 * heads * head_dim * chosen_pairs


def chosen_bytes(chosen_lines: int, kv_heads: int, head_dim: int,
                 itemsize: int) -> int:
    """The least the attention reads: ``chosen_lines`` lines of K and V
    (2 x 4 x 128 values: 2,048 B in bf16)."""
    return chosen_lines * 2 * kv_heads * head_dim * itemsize


def serve_flops(tokens: int, sampled_tokens: int, held_assignments: int,
                chosen_pairs: int, index_pairs: int, *, layers: int,
                hidden: int, vocab: int, heads: int, kv_heads: int,
                head_dim: int, expert_width: int, num_experts: int,
                index_heads: int, index_dim: int) -> float:
    """FLOPs the ticks' real tokens REQUIRE under selection, 2 a
    multiply-add: every token meets, a layer, attention's four matrices, the
    indexer's three and the router; an assignment that fell on a held expert
    its three matrices; a SAMPLED token the head; attention by the CHOSEN
    pairs and the index scores by the (query, visible line) pairs, both summed
    over the ticks and counted a layer."""
    per_token = layers * (
        attention_matmul_params(hidden, heads, kv_heads, head_dim)
        + indexer_matmul_params(hidden, index_heads, index_dim)
        + hidden * num_experts)
    matrices = 2.0 * (tokens * per_token
                      + held_assignments * MLP_MATRICES * hidden * expert_width
                      + sampled_tokens * hidden * vocab)
    return matrices + layers * (chosen_flops(chosen_pairs, heads, head_dim)
                                + index_flops(index_pairs, index_heads, index_dim))
