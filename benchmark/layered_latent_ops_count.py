"""Operations and bytes a stack of dots3-note blocks needs (multi-head latent
attention of TWO geometries: FULL layers under a lightning indexer's choice of
lines, kept in pages, WINDOW layers over ``window`` lines at most, kept as a
ring a slot; a head-wise gate on both; a dense FFN in the leading block, then
sigmoid-routed SwiGLU experts with one shared expert; an untied head),
computed from the configuration's shapes and from what the program counted.
The benchmark's own counts (the yardstick): a later PR that claims a gain
cannot change them.

A full layer is counted as ``sparse_latent_ops_count.py`` counts one, at these
sizes (the WORK under selection). A window layer is counted as the WORK under
the window, whatever implements it: of the two forms the mathematics has, the
cheaper (``latent_ops_count.attention_flops``: absorbed, a (query, visible
line) pair costs ``2 heads (2 kv_lora + rope)`` = 270,336 FLOP at these sizes;
expanded, ``2 kv_lora heads (nope + v)`` a line up-projected and ``2 heads
(nope + rope + v)`` a pair); the bytes are a LOWER bound, so that no share can
pass 100% by the count's fault: a row whose queries together see ``lines``
ring lines (``min(context + new, window - 1 + new)``) reads each of them once,
``kv_lora + rope`` values (2,176 B in bf16; the ring holds the rotary key in a
lane row of 128, 2,304 B a line).
"""

from __future__ import annotations

from benchmark import latent_ops_count, sparse_latent_ops_count

MLP_MATRICES = latent_ops_count.MLP_MATRICES


def layer_matmul_params(hidden: int, a: dict) -> int:
    """One latent attention layer's matrices (``latent_ops_count``'s five)
    and its head-wise gate ``W_g`` (hidden x heads). ``a``: heads, q_lora,
    kv_lora, nope, rope, v."""
    return latent_ops_count.attention_matmul_params(
        hidden, a["heads"], a["q_lora"], a["kv_lora"], a["nope"], a["rope"],
        a["v"]) + hidden * a["heads"]


def window_flops(lines: int, pairs: int, a: dict) -> float:
    """ONE window layer's attention for a tick whose rows see ``lines`` ring
    lines and hold ``pairs`` (query, visible line) pairs: the cheaper form."""
    return latent_ops_count.attention_flops(
        lines, pairs, heads=a["heads"], kv_lora=a["kv_lora"], nope=a["nope"],
        rope=a["rope"], v=a["v"])


def window_bytes(lines: int, a: dict, itemsize: int) -> int:
    """The least a window layer's attention reads: ``lines`` ring lines once."""
    return lines * latent_ops_count.line_bytes(a["kv_lora"], a["rope"], itemsize)


def serve_flops(tokens: int, sampled_tokens: int, held_assignments: int,
                chosen_pairs: int, index_pairs: int, window_pairs: int,
                window_lines: int, *, full_layers: int, window_layers: int,
                dense_layers: int, routed_layers: int, hidden: int, vocab: int,
                dense_width: int, expert_width: int, shared_width: int,
                num_experts: int, full: dict, window: dict, index_heads: int,
                index_dim: int) -> float:
    """FLOPs the ticks' real tokens REQUIRE, 2 a multiply-add: every token
    meets every attention layer's matrices and gate (its kind's), a full
    layer's indexer's three, the dense FFN, every routed layer's router and
    shared expert; an assignment that fell on a held expert its three
    matrices; a SAMPLED token the head over the vocabulary held; a full
    layer's attention by the CHOSEN pairs and its index scores by the (query,
    visible line) pairs; a window layer's by its pairs and lines under the
    window; all summed over the ticks and counted a layer of the kind."""
    per_token = (
        full_layers * (layer_matmul_params(hidden, full)
                       + sparse_latent_ops_count.indexer_matmul_params(
                           hidden, full["q_lora"], index_heads, index_dim))
        + window_layers * layer_matmul_params(hidden, window)
        + dense_layers * MLP_MATRICES * hidden * dense_width
        + routed_layers * (hidden * num_experts
                           + MLP_MATRICES * hidden * shared_width))
    matrices = 2.0 * (tokens * per_token
                      + held_assignments * MLP_MATRICES * hidden * expert_width
                      + sampled_tokens * hidden * vocab)
    return (matrices
            + full_layers * (
                sparse_latent_ops_count.chosen_flops(
                    chosen_pairs, full["heads"], full["kv_lora"], full["rope"])
                + sparse_latent_ops_count.index_flops(
                    index_pairs, index_heads, index_dim))
            + window_layers * window_flops(window_lines, window_pairs, window))
