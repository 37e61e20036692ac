"""Plain reference of the dots3-note decoder: multi-head LATENT attention of
TWO geometries in one stack (full layers under a learned sparse choice of
lines, sliding layers under a window, each kind with sizes and a rotary base
of its own), a head-wise gate on both, the latents rescaled, over a dense then
sigmoid-routed SwiGLU stack, one rank's share of the routed experts.

Float32 ``jax.numpy`` under ``default_matmul_precision("highest")``, nothing of
``scaling_tpu``; RMSNorm, LayerNorm, linear and loss are ``dense_decoder``'s,
rotary, SwiGLU, the routed FFN (``noaux_tc`` with ONE group), the FFN block and
the head ``latent_moe_decoder``'s, the index scores and the exact choice
``sparse_latent_moe_decoder``'s. Written from the published configuration of
dots-studio/dots3-note-prev (``model_type: dots3_note``) and from the two
public families it is built from (the configuration file marks what is read
by a sibling's convention). Every layer is TWO pre-norm sub-blocks:

    h <- h + Attn_i(RMSNorm_attn(h))    Attn_i = full | window (``kinds``)
    h <- h + FFN_i(RMSNorm_ffn(h))      FFN_i = dense (i < num_dense) | routed

Latent attention at a kind's sizes ``(n, kv_lora, nope, rope, v, rope_base)``,
the EXPANDED form (the program serves the absorbed one, over pages and over
rings):

- ``c_q = a_q RMSNorm(x W_DQ)``; ``q_h = c_q W_UQ,h = [q_nope_h, q_rope_h]``;
  ``[c_kv, k_r] = x W_DKV``; ``c_kv <- a_kv RMSNorm(c_kv)``; with ``rescale``
  ``a_q = (hidden / q_lora) ** 0.5`` and ``a_kv = (hidden / kv_lora) ** 0.5``
  (else 1); the rotary key is not scaled; rotary (unscaled, the kind's base)
  on ``q_rope_h`` and on the ONE ``k_r``; ``[k_nope_h, v_h] = c_kv W_UKV,h``;
  ``k_h = [k_nope_h, k_r]``.
- ``o_h = softmax((nope + rope) ** -0.5 q_h k_h^T over what t may see) v_h``.
- the gate: ``g = sigmoid(x W_g)`` (one value a head, from the block's normed
  input), ``o_h <- g_h o_h``; ``y = concat_h(o_h) W_O``.
- what ``t`` may see, FULL: the indexer of ``sparse_latent_moe_decoder`` fed
  this block's (rescaled) ``c_q``, rotary on the first ``rope`` lanes with the
  full layers' table; the ``min(index_topk, t + 1)`` lines ``s <= t`` of
  largest ``I[t, s]``, a tie to the lower position. WINDOW: ``s`` iff ``t -
  window < s <= t``, a mask. Computed in blocks of ``QUERY_BLOCK`` queries,
  each against all keys, so that 8k positions at 128 heads fit beside the
  weights.
- routed FFN, dense FFN, final RMSNorm, untied head: ``latent_moe_decoder``'s.

Departures from the published model, each under ``assumed`` or ``reduced`` in
the configuration: one rank's share of the experts (absent experts' gates are
dropped after the renormalisation, their part of the sum left out), a slice of
the vocabulary, no multi-token-prediction module, no vision or audio tower;
index keys in the compute precision (the sibling release keeps them in FP8);
rotary pairs lane ``i`` with lane ``i + rope / 2``.

Weights: ``latent_moe_decoder``'s, every layer with ``head_gate`` (H, n), a
FULL layer with ``sparse_latent_moe_decoder``'s four index leaves. ``spec``:
``kinds`` (a tuple, ``"full"`` | ``"window"`` a block), ``sizes`` (two tuples
``(n, q_lora, kv_lora, nope, rope, v, rope_base)``, full then window),
``window``, ``hidden``, ``rescale``, ``index_heads``, ``index_dim``,
``index_topk``, ``num_dense``, ``eps``, ``top_k``, ``scale``, ``gate_eps``,
``experts_first``, ``shared``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark.reference.dense_decoder import (  # noqa: F401  (token_loss: the contract)
    F32, _f32, norm, token_loss,
)
from benchmark.reference.latent_moe_decoder import (  # noqa: F401  (the share test's)
    ffn_block, head_forward, rotary, routed_ffn, swiglu,
)
from benchmark.reference.sparse_latent_moe_decoder import chosen_lines, index_scores

QUERY_BLOCK = 128
KINDS = ("full", "window")


def masked_attention(q, k, v, scale: float, window, index, topk):
    """q, k (s, n, d), v (s, n, dv): the causal softmax over what each query
    may see, a block of ``QUERY_BLOCK`` queries at a time against all keys:
    under ``window`` (an int) the last ``window`` lines, under ``index``
    (``(index_q, index_k, index_w)``) its ``topk`` best. Returns ``(out (s, n,
    dv), seen (s, s) bool)``."""
    s = q.shape[0]
    block = min(QUERY_BLOCK, s)
    pad = -s % block
    padded = lambda a: jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
    q = padded(q)
    if index is not None:
        index_q, index_k, index_w = index
        index_q, index_w = padded(index_q), padded(index_w)
    keys = jnp.arange(s)

    def one(start):
        at = (start + jnp.arange(block))[:, None]
        seen = keys[None, :] <= at
        if window is not None:
            seen = seen & (keys[None, :] > at - window)
        if index is not None:
            iq, iw = (jax.lax.dynamic_slice_in_dim(a, start, block, 0)
                      for a in (index_q, index_w))
            seen = chosen_lines(index_scores(iq, index_k, iw), seen, topk)
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, 0)
        scores = jnp.einsum("qnd,knd->nqk", qb, k) * scale
        probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), axis=-1)
        # a padded query past the sequence sees keys of its own: finite, cut below
        return jnp.einsum("nqk,knd->qnd", probs, v), seen

    out, seen = jax.lax.map(one, jnp.arange(0, s + pad, block))
    return (out.reshape(s + pad, *out.shape[2:])[:s],
            seen.reshape(s + pad, s)[:s])


def attention_parts(x, p, kind: str, spec):
    """x (s, H) float32, one sequence: ``(y (s, H), seen (s, s))``."""
    s = x.shape[0]
    n, q_lora, lora, nope, rope, dv, base = spec["sizes"][KINDS.index(kind)]
    a_q = (spec["hidden"] / q_lora) ** 0.5 if spec["rescale"] else 1.0
    a_kv = (spec["hidden"] / lora) ** 0.5 if spec["rescale"] else 1.0
    turn = functools.partial(rotary, positions=jnp.arange(s), rope_base=base,
                             yarn=None)
    c_q = a_q * norm(x @ p["q_a"], p["q_a_norm"], "rms", spec["eps"])
    q = (c_q @ p["q_b"]).reshape(s, n, nope + rope)
    kv = x @ p["kv_a"]
    c_kv = a_kv * norm(kv[:, :lora], p["kv_a_norm"], "rms", spec["eps"])
    k_r = turn(kv[:, None, lora:])
    up = (c_kv @ p["kv_b"]).reshape(s, n, nope + dv)
    k = jnp.concatenate([up[..., :nope], jnp.broadcast_to(k_r, (s, n, rope))], -1)
    q = jnp.concatenate([q[..., :nope], turn(q[..., nope:])], -1)
    index, window = None, None
    if kind == "window":
        window = spec["window"]
    elif spec["index_topk"] is not None:
        # the indexer: its rope lanes come FIRST
        heads, dim = spec["index_heads"], spec["index_dim"]
        index_q = (c_q @ p["index_q"]).reshape(s, heads, dim)
        index_q = jnp.concatenate([turn(index_q[..., :rope]), index_q[..., rope:]], -1)
        index_k = norm(x @ p["index_k"], p["index_k_norm"], "layernorm", spec["eps"])
        index_k = jnp.concatenate(
            [turn(index_k[:, None, :rope])[:, 0], index_k[:, rope:]], -1)
        index = (index_q, index_k, (x @ p["index_w"]) * (heads ** -0.5 * dim ** -0.5))
    out, seen = masked_attention(q, k, up[..., nope:], (nope + rope) ** -0.5,
                                 window, index, spec["index_topk"])
    out = out * jax.nn.sigmoid(x @ p["head_gate"])[..., None]
    return out.reshape(s, n * dv) @ p["o"], seen


@functools.partial(jax.jit, static_argnames=("kind", "spec"))
def attention_block(h, layer, kind, spec):
    """``(h + Attn(RMSNorm(h)), seen)`` on one sequence: h (s, H) float32."""
    spec = dict(spec)
    with jax.default_matmul_precision("highest"):
        p = _f32(layer)
        y, seen = attention_parts(norm(h, p["attn_norm"], "rms", spec["eps"]),
                                  p, kind, spec)
        return h + y, seen


ATTENTION_LEAVES = ("attn_norm", "q_a", "q_a_norm", "q_b", "kv_a", "kv_a_norm", "kv_b", "o",
                    "head_gate", "index_q", "index_k", "index_k_norm", "index_w")


def forward(weights, tokens, spec, head_positions=None, chosen_out=None):
    """Logits (len(head_positions) or s, V) in float32 of one sequence of
    token ids; as ``dense_decoder.forward``. ``chosen_out``, a list, takes
    every layer's ``(s, s)`` bool of the lines each query attended over."""
    frozen = tuple(sorted(spec.items()))
    h = weights["embedding"][tokens].astype(F32)
    for i, layer in enumerate(weights["layers"]):
        h, seen = attention_block(
            h, {k: layer[k] for k in ATTENTION_LEAVES if k in layer},
            spec["kinds"][i], frozen)
        if chosen_out is not None:
            chosen_out.append(seen)
        h = ffn_block(h, {k: v for k, v in layer.items() if k not in ATTENTION_LEAVES},
                      i >= spec["num_dense"], frozen)
    if head_positions is not None:
        h = h[head_positions]
    return head_forward(h, weights["final_norm"], weights["head"], spec["eps"])
