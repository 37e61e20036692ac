"""Bytes and operations a stack of LFM2-MoE blocks (gated short convolution or
GQA attention, then a dense or a routed SwiGLU FFN) needs, computed from
shapes and from what the program counted. The benchmark's own counts (the
yardstick), beside ``ops_count.py``: a later PR that claims a gain cannot
change them."""

from __future__ import annotations

FFN_MATRICES = 3   # gate, up, down: each hidden x width


def conv_matmul_params(hidden: int) -> int:
    """Parameters of ONE gated short convolution that take part in a matrix
    multiplication: in_proj (hidden x 3 hidden) and out_proj (hidden x
    hidden); the filter's ``hidden x taps`` are not."""
    return 4 * hidden * hidden


def conv_tail_bytes(hidden: int, taps: int, bytes_per_value: int) -> int:
    """Bytes of ONE (slot, layer) conv tail: the last ``taps - 1`` filter
    inputs of each channel."""
    return (taps - 1) * hidden * bytes_per_value


def conv_layer_bytes(rows: int, hidden: int, taps: int, bytes_per_value: int) -> float:
    """Bytes ONE short-convolution layer has to move in a tick whose ``rows``
    rows advance: the operator's two matrices and its filter once, each such
    row's tail read once and written once. Activations are not counted."""
    weights = (conv_matmul_params(hidden) + hidden * taps) * bytes_per_value
    return weights + 2.0 * rows * conv_tail_bytes(hidden, taps, bytes_per_value)


def routed_layer_bytes(experts_read: int, hidden: int, expert_width: int,
                       num_experts: int, bytes_per_value: int) -> float:
    """Bytes of weights ONE routed layer has to read in a tick in which
    ``experts_read`` of its experts have at least one token: each such
    expert's three ``hidden x expert_width`` matrices once, and the float32
    router (``num_experts`` columns)."""
    return (float(experts_read) * FFN_MATRICES * hidden * expert_width * bytes_per_value
            + hidden * num_experts * 4)


def attention_matmul_params(hidden: int, heads: int, kv_heads: int, head_dim: int) -> int:
    """q and o (hidden x heads x head_dim each), k and v (hidden x kv_heads x
    head_dim each)."""
    return 2 * hidden * heads * head_dim + 2 * hidden * kv_heads * head_dim


def serve_flops(tokens: int, sampled_tokens: int, assignments: int,
                row_context_tokens: int, *, conv_layers: int, attention_layers: int,
                dense_layers: int, routed_layers: int, hidden: int, vocab: int,
                dense_width: int, expert_width: int, num_experts: int,
                heads: int, kv_heads: int, head_dim: int) -> float:
    """FLOPs the ticks' real tokens require, 2 a multiply-add: every token
    works every short convolution's and attention layer's matrices, every
    dense FFN and every routed layer's router; the experts its ASSIGNMENTS
    name (``assignments``, the program's own count summed over the layers:
    ``top_k`` a token a routed layer); every SAMPLED token the head, which is
    the tied table. The short filter (``2 x taps x hidden`` a token a layer)
    is left out. Attention: QK^T and PV over the context of ONE token a row a
    tick (``row_context_tokens``), which leaves out the further tokens of a
    prompt chunk: a lower bound, as a count of what is required should be."""
    per_token = (
        conv_layers * conv_matmul_params(hidden)
        + attention_layers * attention_matmul_params(hidden, heads, kv_heads, head_dim)
        + dense_layers * FFN_MATRICES * hidden * dense_width
        + routed_layers * hidden * num_experts)
    matmuls = 2.0 * (tokens * per_token
                     + assignments * FFN_MATRICES * hidden * expert_width
                     + sampled_tokens * hidden * vocab)
    attention = 4.0 * row_context_tokens * heads * head_dim * attention_layers
    return matmuls + attention
