"""Operations and bytes a stack of SPARSE latent-attention blocks (multi-head
latent attention over each query's ``index_topk`` best lines, chosen by a
lightning indexer; then a dense or a group-limited sigmoid-routed SwiGLU FFN
with a shared expert; an untied head) needs, computed from shapes and from
what the program counted. The benchmark's own counts (the yardstick): a later
PR that claims a gain cannot change them.

Everything is counted as the WORK under selection, whatever implements it (a
version that streams every visible line and masks reads low on these
rooflines; one that gathers reads what it gathers). A (query, CHOSEN line)
pair costs ``2 heads (2 kv_lora + rope)`` FLOP in the absorbed form, the only
one left: two queries of a row choose different lines, so an expanded form
would up-project every chosen line for every query. A (query, visible line)
pair costs the indexer ``2 index_heads index_dim`` FLOP. Bytes are LOWER
bounds, so that no share can pass 100% by the count's fault: the attention
reads at least ``min(visible, index_topk)`` lines a row (``chosen_lines``: the
union of a row's queries' choices is no smaller), the indexer every visible
index key once.
"""

from __future__ import annotations

from benchmark import latent_ops_count

MLP_MATRICES = latent_ops_count.MLP_MATRICES


def indexer_matmul_params(hidden: int, q_lora: int, index_heads: int,
                          index_dim: int) -> int:
    """Parameters of ONE indexer that take part in a matrix multiplication:
    W_IQ (q_lora x heads dim), W_IK (hidden x dim), W_Iw (hidden x heads); its
    LayerNorm's two vectors are not counted."""
    return q_lora * index_heads * index_dim + hidden * index_dim + hidden * index_heads


def chosen_flops(chosen_pairs: int, heads: int, kv_lora: int, rope: int) -> float:
    """Attention over the chosen lines: 278,528 FLOP a pair at
    DeepSeek-V3.2-Exp's sizes (2 x 128 x 1088)."""
    return latent_ops_count.absorbed_flops(chosen_pairs, heads, kv_lora, rope)


def chosen_bytes(chosen_lines: int, kv_lora: int, rope: int, itemsize: int) -> int:
    """The least the attention reads: ``chosen_lines`` lines of ``kv_lora +
    rope`` values (1,152 B in bf16)."""
    return chosen_lines * latent_ops_count.line_bytes(kv_lora, rope, itemsize)


def index_flops(index_pairs: int, index_heads: int, index_dim: int) -> float:
    """The index scores: 16,384 FLOP a (query, visible line) pair at
    DeepSeek-V3.2-Exp's sizes (2 x 64 x 128); relu, weights and the sum over
    the heads are not counted, nor is the selection."""
    return 2.0 * index_heads * index_dim * index_pairs


def index_bytes(index_lines: int, index_dim: int, itemsize: int) -> int:
    """Every visible index key once: 256 B a line in bf16."""
    return index_lines * index_dim * itemsize


def serve_flops(tokens: int, sampled_tokens: int, held_assignments: int,
                chosen_pairs: int, index_pairs: int, *, sparse_layers: int,
                dense_layers: int, routed_layers: int, hidden: int, vocab: int,
                dense_width: int, expert_width: int, shared_width: int,
                num_experts: int, attention: dict, index_heads: int,
                index_dim: int) -> float:
    """FLOPs the ticks' real tokens REQUIRE under selection, 2 a
    multiply-add: the matrices as ``latent_ops_count.serve_flops`` counts them
    (attention left out there: no pairs, no lines) plus every sparse layer's
    indexer's three; attention by the CHOSEN pairs; the index scores by the
    (query, visible line) pairs, both summed over the ticks and counted a
    layer. ``attention``: heads, q_lora, kv_lora, nope, rope, v."""
    a = attention
    matrices = latent_ops_count.serve_flops(
        tokens, sampled_tokens, held_assignments, 0, 0,
        latent_layers=sparse_layers, dense_layers=dense_layers,
        routed_layers=routed_layers, hidden=hidden, vocab=vocab,
        dense_width=dense_width, expert_width=expert_width,
        shared_width=shared_width, num_experts=num_experts, attention=a)
    indexer = 2.0 * tokens * sparse_layers * indexer_matmul_params(
        hidden, a["q_lora"], index_heads, index_dim)
    return matrices + indexer + sparse_layers * (
        chosen_flops(chosen_pairs, a["heads"], a["kv_lora"], a["rope"])
        + index_flops(index_pairs, index_heads, index_dim))
