"""Operations a stack of parallel blocks (a Mamba-2 mixer BESIDE GQA attention
on one normed input, then a SwiGLU MLP; an untied head) needs, computed from
shapes and from what the program counted. The benchmark's own counts (the
yardstick), beside ``ops_count.py`` and ``hybrid_ops_count.py`` (whose counts
of ONE Mamba-2 mixer and ONE attention layer are used as they are): a later PR
that claims a gain cannot change them."""

from __future__ import annotations

from benchmark.hybrid_ops_count import attention_matmul_params, mamba_matmul_params

MLP_MATRICES = 3   # gate, up, down: each hidden x width


def block_matmul_params(hidden: int, mlp_width: int, mamba: tuple, heads: int,
                        kv_heads: int, head_dim: int) -> int:
    """Parameters of ONE parallel block that take part in a matrix
    multiplication: attention's four projections, the mixer's in_proj and
    out_proj, the MLP's three matrices (conv, dt_bias, A, D and the norms'
    vectors are not counted). ``mamba``: (heads, head_dim, state, groups)."""
    return (attention_matmul_params(hidden, heads, kv_heads, head_dim)
            + mamba_matmul_params(hidden, *mamba)
            + MLP_MATRICES * hidden * mlp_width)


def serve_flops(tokens: int, sampled_tokens: int, row_context_tokens: int, *,
                layers: int, hidden: int, vocab: int, mlp_width: int,
                mamba: tuple, heads: int, kv_heads: int, head_dim: int) -> float:
    """FLOPs the ticks' real tokens require, 2 a multiply-add: every token
    works every block's matrices; every SAMPLED token the head. The
    recurrence: per token, head and layer the state update and its read-out
    (``2 x 2 x head_dim x state``). Attention: QK^T and PV over the context of
    ONE token a row a tick (``row_context_tokens``), which leaves out the
    further tokens of a prompt chunk: a lower bound, as a count of what is
    required should be. ``mamba``: (heads, head_dim, state, groups)."""
    m_heads, m_head_dim, m_state, _ = mamba
    per_token = layers * block_matmul_params(
        hidden, mlp_width, mamba, heads, kv_heads, head_dim)
    matmuls = 2.0 * (tokens * per_token + sampled_tokens * hidden * vocab)
    recurrence = 4.0 * tokens * layers * m_heads * m_head_dim * m_state
    attention = 4.0 * row_context_tokens * heads * head_dim * layers
    return matmuls + recurrence + attention
