"""Bytes and operations a looped trunk needs, computed from shapes and from
what the program counted. The benchmark's own counts (the yardstick), beside
``ops_count.py``: a later PR that claims a gain cannot change them."""

from __future__ import annotations

ATTENTION_MATRICES = 2  # q and o: hidden x (heads x head_dim); k and v apart
MLP_MATRICES = 3        # gate, up, down: each hidden x mlp_width


def layer_matmul_params(hidden: int, num_heads: int, num_kv_heads: int,
                        head_dim: int, mlp_width: int) -> int:
    """Parameters of ONE trunk layer that take part in a matrix
    multiplication: q, k, v, o and the three SwiGLU matrices, no bias; the
    norms' vectors are not counted."""
    attention = hidden * head_dim * (ATTENTION_MATRICES * num_heads + 2 * num_kv_heads)
    return attention + MLP_MATRICES * hidden * mlp_width


def trunk_weight_bytes(layer_passes: int, layer_params: int,
                       bytes_per_value: int) -> float:
    """Bytes of weights the trunk has to read for ``layer_passes`` (step,
    layer) passes, each reading its layer's matrices once: at serving batches
    a pass is bound by that read, and no chip holds a layer (103 MB here)
    from one step to the next."""
    return float(layer_passes) * layer_params * bytes_per_value


def serve_flops(tokens: int, sampled_tokens: int, row_context_tokens: int,
                loop_steps: int, num_layers: int, layer_params: int,
                head_params: int, num_heads: int, head_dim: int) -> float:
    """FLOPs the ticks' real tokens require: every token (prompt or output)
    works the trunk's matrices ``loop_steps`` times, every sampled token the
    head once (2 FLOPs a parameter); attention is QK^T and PV (2 x 2 FLOPs a
    key a head dimension) at every (step, layer) over the context of ONE token
    a row a tick (``row_context_tokens``: the rows' cached tokens summed over
    the ticks), which leaves out the further tokens of a prompt chunk: a
    lower bound, as a count of what is required should be."""
    matmuls = 2.0 * (tokens * loop_steps * num_layers * layer_params
                     + sampled_tokens * head_params)
    attention = 4.0 * row_context_tokens * num_heads * head_dim * loop_steps * num_layers
    return matmuls + attention
