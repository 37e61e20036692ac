"""Operations and bytes a stack of latent-attention blocks (multi-head latent
attention, then a dense or a sigmoid-routed SwiGLU FFN with a shared expert;
an untied head) needs, computed from shapes and from what the program counted.
The benchmark's own counts (the yardstick): a later PR that claims a gain
cannot change them.

Attention over the latent cache is counted as the WORK, whatever implements
it: of the two forms the mathematics has, the cheaper. Absorbed: a (query,
visible line) pair costs ``2 heads (2 kv_lora + rope)`` FLOP (scores over
``kv_lora + rope`` lanes, the value over ``kv_lora``). Expanded: every line a
row reads is first projected up to its heads' keys and values, ``2 kv_lora
heads (nope + v)`` FLOP a line, then a pair costs ``2 heads (nope + rope +
v)``. A line is read once: ``(kv_lora + rope)`` values.
"""

from __future__ import annotations

MLP_MATRICES = 3   # gate, up, down: each hidden x width


def attention_matmul_params(hidden: int, heads: int, q_lora: int, kv_lora: int,
                            nope: int, rope: int, v: int) -> int:
    """Parameters of ONE latent attention layer that take part in a matrix
    multiplication (the two latent norms' vectors are not counted): the down
    and up projections of the queries, the down projection of latent and
    rotary key, the up projection (applied as W_UK to the queries and W_UV to
    the outputs when absorbed: the same count), the output projection."""
    return (hidden * q_lora + q_lora * heads * (nope + rope)
            + hidden * (kv_lora + rope) + kv_lora * heads * (nope + v)
            + heads * v * hidden)


def absorbed_flops(pairs: int, heads: int, kv_lora: int, rope: int) -> float:
    return 2.0 * heads * (2 * kv_lora + rope) * pairs


def expanded_flops(lines: int, pairs: int, heads: int, kv_lora: int,
                   nope: int, rope: int, v: int) -> float:
    return (2.0 * kv_lora * heads * (nope + v) * lines
            + 2.0 * heads * (nope + rope + v) * pairs)


def attention_flops(lines: int, pairs: int, *, heads: int, kv_lora: int,
                    nope: int, rope: int, v: int) -> float:
    """FLOPs ONE layer's attention needs for a tick whose rows read ``lines``
    latent lines and hold ``pairs`` (query, visible line) pairs: the cheaper
    form. (Taken over the tick's sums, not row by row: the absorbed form is
    the cheaper one of every row of fewer than 171 queries at Kimi-K2's
    sizes, and the engine's rows bring at most ``prefill_chunk``.)"""
    return min(absorbed_flops(pairs, heads, kv_lora, rope),
               expanded_flops(lines, pairs, heads, kv_lora, nope, rope, v))


def line_bytes(kv_lora: int, rope: int, itemsize: int) -> int:
    """Bytes of ONE latent line: 1,152 at Kimi-K2's sizes in bf16."""
    return (kv_lora + rope) * itemsize


def serve_flops(tokens: int, sampled_tokens: int, held_assignments: int,
                pairs: int, lines: int, *, latent_layers: int,
                dense_layers: int, routed_layers: int, hidden: int, vocab: int,
                dense_width: int, expert_width: int, shared_width: int,
                num_experts: int, attention: dict) -> float:
    """FLOPs the ticks' real tokens require, 2 a multiply-add: every token
    works every latent layer's matrices, every dense FFN, every routed
    layer's router and shared expert; the routed experts by the assignments
    that fell on HELD ones (``held_assignments``, summed over the layers:
    ``serve_moe_assignments_total``); every SAMPLED token the head;
    attention by the (query, visible line) pairs and lines summed over the
    ticks (``attention_flops`` a layer). ``attention``: heads, q_lora,
    kv_lora, nope, rope, v."""
    a = attention
    per_token = (
        latent_layers * attention_matmul_params(
            hidden, a["heads"], a["q_lora"], a["kv_lora"], a["nope"],
            a["rope"], a["v"])
        + dense_layers * MLP_MATRICES * hidden * dense_width
        + routed_layers * (hidden * num_experts
                           + MLP_MATRICES * hidden * shared_width))
    matmuls = 2.0 * (tokens * per_token
                     + held_assignments * MLP_MATRICES * hidden * expert_width
                     + sampled_tokens * hidden * vocab)
    return matmuls + latent_layers * attention_flops(
        lines, pairs, heads=a["heads"], kv_lora=a["kv_lora"], nope=a["nope"],
        rope=a["rope"], v=a["v"])
