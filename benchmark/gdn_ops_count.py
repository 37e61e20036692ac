"""Bytes and operations a stack of gated delta-rule layers, gated
full-attention layers and softmax-routed GATED experts with a gated shared
expert needs, computed from shapes and from what the program counted. The
benchmark's own counts (the yardstick), beside ``ops_count.py`` and
``hybrid_ops_count.py``: a later PR that claims a gain cannot change them."""

from __future__ import annotations

EXPERT_MATRICES = 3   # gate, up and down: each hidden x expert_width
STATE_BYTES = 4       # the recurrent state is float32


def delta_dims(nk: int, nv: int, dk: int, dv: int):
    """(key width, value width, conv channels, in_proj columns)."""
    key_dim, value_dim = nk * dk, nv * dv
    return key_dim, value_dim, 2 * key_dim + value_dim, 2 * key_dim + 2 * value_dim


def delta_matmul_params(hidden: int, nk: int, nv: int, dk: int, dv: int) -> int:
    """Parameters of ONE delta mixer that take part in a matrix
    multiplication: the q | k | v | z projection, the b | a projection and the
    output projection (conv, dt_bias, A and the norm's vector are not
    counted)."""
    _, value_dim, _, in_width = delta_dims(nk, nv, dk, dv)
    return hidden * in_width + hidden * 2 * nv + value_dim * hidden


def state_bytes(nv: int, dk: int, dv: int) -> int:
    """Bytes of ONE (slot, layer) recurrent state."""
    return nv * dk * dv * STATE_BYTES


def delta_layer_bytes(rows: int, hidden: int, nk: int, nv: int, dk: int,
                      dv: int, conv_kernel: int, bytes_per_value: int) -> float:
    """Bytes ONE delta layer has to move in a tick whose ``rows`` rows
    advance: each such row's state read once and written once, and the
    mixer's weights (the three projections, the conv) once. Activations and
    the conv tails (3 values a channel a row) are not counted."""
    _, _, conv_dim, _ = delta_dims(nk, nv, dk, dv)
    weights = (delta_matmul_params(hidden, nk, nv, dk, dv)
               + conv_dim * conv_kernel) * bytes_per_value
    return 2.0 * rows * state_bytes(nv, dk, dv) + weights


def step_flops(nv: int, dk: int, dv: int) -> float:
    """FLOPs of the recurrence's ONE step a token and layer, 2 a
    multiply-add: the state's read-out for the key, the outer product written
    into it, the read-out for the query (``3 x 2 x dk x dv`` a value head)."""
    return 6.0 * nv * dk * dv


def chunk_flops(width: int, nk: int, nv: int, dk: int, dv: int) -> float:
    """FLOPs of the chunk form for ONE chunk of ``width`` positions of one
    row and layer, 2 a multiply-add: ``k k^T`` and ``q k^T`` a key head, a
    value head's triangular system against ``dv`` right-hand sides (``width^2
    dv``, half of a full product), the read-outs of ``S_0`` for keys and
    queries, the intra-chunk output and the state's update."""
    return (2.0 * 2 * width * width * dk * nk
            + nv * (width * width * dv          # the solve by substitution
                    + 2.0 * width * width * dv  # (D o q k^T) U
                    + 3 * 2.0 * width * dk * dv))


def attention_matmul_params(hidden: int, heads: int, kv_heads: int,
                            head_dim: int) -> int:
    """q WITH its gate (hidden x heads x 2 head_dim), o (heads x head_dim x
    hidden), k and v (hidden x kv_heads x head_dim each)."""
    return 3 * hidden * heads * head_dim + 2 * hidden * kv_heads * head_dim


def moe_layer_bytes(experts_read: int, hidden: int, expert_width: int,
                    shared_width: int, num_experts: int,
                    bytes_per_value: int) -> float:
    """Bytes ONE routed layer has to read in a tick in which ``experts_read``
    of its HELD experts have at least one token: each such expert's three
    matrices once, the shared expert's three and its gate's vector, and the
    float32 router (all ``num_experts`` columns)."""
    return (float(experts_read) * EXPERT_MATRICES * hidden * expert_width * bytes_per_value
            + (EXPERT_MATRICES * hidden * shared_width + hidden) * bytes_per_value
            + hidden * num_experts * 4)


def serve_flops(tokens: int, sampled_tokens: int, held_assignments: int,
                row_context_tokens: int, *, delta_layers: int,
                attention_layers: int, moe_layers: int, hidden: int, vocab: int,
                delta: tuple, expert_width: int, shared_width: int,
                num_experts: int, heads: int, kv_heads: int, head_dim: int) -> float:
    """FLOPs the ticks' real tokens require, 2 a multiply-add: every token
    works every delta mixer's and attention layer's matrices, every routed
    layer's router, shared expert and its gate, and the experts its
    ASSIGNMENTS name among those held (``held_assignments``, the program's own
    count, summed over the layers: what fell on absent experts is another
    chip's work); every sampled token the head. The recurrence: its step a
    token and delta layer (``step_flops``: the chunk form does more work for
    the same result, and that is not required). Attention: QK^T and PV over
    the context of ONE token a row a tick (``row_context_tokens``), which
    leaves out the further tokens of a prompt chunk: a lower bound, as a count
    of what is required should be. ``delta``: (nk, nv, dk, dv)."""
    nk, nv, dk, dv = delta
    per_token = (
        delta_layers * delta_matmul_params(hidden, nk, nv, dk, dv)
        + attention_layers * attention_matmul_params(hidden, heads, kv_heads, head_dim)
        + moe_layers * (hidden * num_experts + EXPERT_MATRICES * hidden * shared_width
                        + hidden))
    matmuls = 2.0 * (tokens * per_token
                     + held_assignments * EXPERT_MATRICES * hidden * expert_width
                     + sampled_tokens * hidden * vocab)
    recurrence = tokens * delta_layers * step_flops(nv, dk, dv)
    attention = 4.0 * row_context_tokens * heads * head_dim * attention_layers
    return matmuls + recurrence + attention
