"""Operations and bytes the algorithms need, computed from shapes.

These are the benchmark's own counts (the yardstick): a later PR that
claims a gain cannot change them. Recomputed operations never count.
"""

from __future__ import annotations


def train_flops_per_token(matmul_params: int, num_layers: int, num_heads: int,
                          head_dim: int, seq_len: int) -> float:
    """Forward + backward FLOPs one trained token requires.

    PaLM appendix B (``6 N + 12 L H Q T``, the formula of the repo's
    ``get_flops_per_token``) with two corrections that only lower the count:
    ``N`` is the parameters that take part in a matrix multiplication (the
    input embedding table is a lookup and is left out), and the attention
    term is halved because the mask is causal and the kernel skips the masked
    half: ``6 L H Q T`` = 3 x (QK^T and PV, 2 FLOPs per multiply-add,
    averaged over positions T/2 keys each)."""
    return 6.0 * matmul_params + 6.0 * num_layers * num_heads * head_dim * seq_len


def splash_flops(batch: int, seq_len: int, num_heads: int, head_dim: int,
                 backward: bool) -> float:
    """FLOPs causal attention needs for one call on (batch, seq, heads, d):
    the forward is QK^T and PV over the unmasked half (2 matmuls x 2 FLOPs x
    S^2/2 x d per head); the backward needs dV, dP, dQ and dK (4 matmuls; the
    recomputation of S = QK^T inside the kernel is not counted)."""
    half = batch * num_heads * (seq_len * seq_len / 2.0) * head_dim
    return 2 * 2 * half + (4 * 2 * half if backward else 0.0)


def paged_kv_bytes(context_tokens: int, num_kv_heads: int, head_dim: int,
                   bytes_per_value: int) -> float:
    """Bytes of keys and values one layer's paged attention has to read for
    rows whose contexts sum to ``context_tokens`` (each row reads its own
    context once; queries and outputs are negligible beside them)."""
    return 2.0 * context_tokens * num_kv_heads * head_dim * bytes_per_value
