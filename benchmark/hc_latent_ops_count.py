"""Operations and bytes a stack of latent-attention blocks on a residual path
of ``n`` hyper-connected STREAMS needs (``reference/hc_latent_moe_decoder.py``:
every sub-layer has a mapping of its own, the readout folds the streams),
computed from shapes and from what the program counted. The benchmark's own
counts (the yardstick): a later PR that claims a gain cannot change them. The
blocks themselves are ``latent_ops_count``'s.

The residual path is counted as the LEAST any implementation moves, so that no
fusion can read over 100% of the roofline: a sub-layer reads the ``n`` streams
``X`` and its own output ``y`` and writes the ``n`` streams ``X'`` and its
input ``u``, the next sub-layer's mapping sharing the pass that writes ``X'``:
``(2n + 2) hidden`` values a token a sub-layer (71,680 B at 4 streams of 3,584
in bf16). Its matmul is ``vec(X) phi``, ``2 n hidden (n^2 + 2n)`` FLOP a token
a sub-layer, the readout's ``2 n hidden n`` a sampled token; the Sinkhorn
steps and the mixes themselves (``~ 2 n^2 hidden`` FLOP a token a sub-layer on
the vector unit) are not matmuls and are left out of the share of the MXU's
peak.
"""

from __future__ import annotations

from benchmark import latent_ops_count


def stream_bytes(token_sublayers: int, streams: int, hidden: int,
                 itemsize: int) -> int:
    """The least bytes ``token_sublayers`` (real tokens x sub-layers) move."""
    return token_sublayers * (2 * streams + 2) * hidden * itemsize


def mapping_flops(token_sublayers: int, sampled_tokens: int, streams: int,
                  hidden: int) -> float:
    """The mappings' and the readout's matmuls, 2 a multiply-add."""
    width = streams * hidden
    return 2.0 * width * (token_sublayers * (streams * streams + 2 * streams)
                          + sampled_tokens * streams)


def serve_flops(tokens: int, sampled_tokens: int, held_assignments: int,
                pairs: int, lines: int, token_sublayers: int, *, streams: int,
                **shape) -> float:
    """``latent_ops_count.serve_flops`` of the blocks (``shape``: its keyword
    arguments) and the residual path's matmuls."""
    return (latent_ops_count.serve_flops(
        tokens, sampled_tokens, held_assignments, pairs, lines, **shape)
        + mapping_flops(token_sublayers, sampled_tokens, streams, shape["hidden"]))
