"""Bytes and operations a stack of single-mixer layers (Mamba-2 / routed
un-gated experts with a shared expert / GQA attention) needs, computed from
shapes and from what the program counted. The benchmark's own counts (the
yardstick), beside ``ops_count.py``: a later PR that claims a gain cannot
change them."""

from __future__ import annotations

EXPERT_MATRICES = 2   # up and down: each hidden x expert_width, no gate matrix
STATE_BYTES = 4       # the recurrent state is float32


def mamba_dims(hidden: int, heads: int, head_dim: int, state: int, groups: int):
    """(inner width, conv channels, in_proj columns)."""
    inner = heads * head_dim
    conv_dim = inner + 2 * groups * state
    return inner, conv_dim, inner + conv_dim + heads


def mamba_matmul_params(hidden: int, heads: int, head_dim: int, state: int,
                        groups: int) -> int:
    """Parameters of ONE Mamba-2 mixer that take part in a matrix
    multiplication: in_proj and out_proj (conv, dt_bias, A, D and the norms'
    vectors are not counted)."""
    inner, _, in_width = mamba_dims(hidden, heads, head_dim, state, groups)
    return hidden * in_width + inner * hidden


def ssm_state_bytes(heads: int, head_dim: int, state: int) -> int:
    """Bytes of ONE (slot, layer) recurrent state."""
    return heads * head_dim * state * STATE_BYTES


def ssm_layer_bytes(rows: int, hidden: int, heads: int, head_dim: int,
                    state: int, groups: int, conv_kernel: int,
                    bytes_per_value: int) -> float:
    """Bytes ONE Mamba-2 layer has to move in a tick whose ``rows`` rows
    advance: each such row's state read once and written once, and the
    mixer's weights (in_proj, out_proj, conv) once. Activations and the conv
    tails (3 values a channel a row) are not counted."""
    _, conv_dim, _ = mamba_dims(hidden, heads, head_dim, state, groups)
    weights = (mamba_matmul_params(hidden, heads, head_dim, state, groups)
               + conv_dim * (conv_kernel + 1)) * bytes_per_value
    return 2.0 * rows * ssm_state_bytes(heads, head_dim, state) + weights


def moe_layer_bytes(experts_read: int, hidden: int, expert_width: int,
                    shared_width: int, num_experts: int,
                    bytes_per_value: int) -> float:
    """Bytes ONE routed layer has to read in a tick in which ``experts_read``
    of its HELD experts have at least one token: each such expert's two
    matrices once, the shared expert's two, and the float32 router (all
    ``num_experts`` columns)."""
    return (float(experts_read) * EXPERT_MATRICES * hidden * expert_width * bytes_per_value
            + EXPERT_MATRICES * hidden * shared_width * bytes_per_value
            + hidden * num_experts * 4)


def attention_matmul_params(hidden: int, heads: int, kv_heads: int, head_dim: int) -> int:
    """q and o (hidden x heads x head_dim each), k and v (hidden x kv_heads x
    head_dim each)."""
    return 2 * hidden * heads * head_dim + 2 * hidden * kv_heads * head_dim


def serve_flops(tokens: int, sampled_tokens: int, held_assignments: int,
                row_context_tokens: int, *, mamba_layers: int, moe_layers: int,
                attention_layers: int, hidden: int, vocab: int,
                mamba: tuple, expert_width: int, shared_width: int,
                num_experts: int, heads: int, kv_heads: int, head_dim: int) -> float:
    """FLOPs the ticks' real tokens require, 2 a multiply-add: every token
    works every Mamba-2 mixer's and attention layer's matrices, every routed
    layer's router and shared expert, and the experts its ASSIGNMENTS name
    among those held (``held_assignments``, the program's own count, summed
    over the layers: what fell on absent experts is another chip's work);
    every sampled token the head. The recurrence: per token, head and layer
    the state update and its read-out (``2 x 2 x head_dim x state``).
    Attention: QK^T and PV over the context of ONE token a row a tick
    (``row_context_tokens``), which leaves out the further tokens of a prompt
    chunk: a lower bound, as a count of what is required should be.
    ``mamba``: (heads, head_dim, state, groups)."""
    m_heads, m_head_dim, m_state, m_groups = mamba
    per_token = (
        mamba_layers * mamba_matmul_params(hidden, m_heads, m_head_dim, m_state, m_groups)
        + attention_layers * attention_matmul_params(hidden, heads, kv_heads, head_dim)
        + moe_layers * (hidden * num_experts + EXPERT_MATRICES * hidden * shared_width))
    matmuls = 2.0 * (tokens * per_token
                     + held_assignments * EXPERT_MATRICES * hidden * expert_width
                     + sampled_tokens * hidden * vocab)
    recurrence = 4.0 * tokens * mamba_layers * m_heads * m_head_dim * m_state
    attention = 4.0 * row_context_tokens * heads * head_dim * attention_layers
    return matmuls + recurrence + attention
