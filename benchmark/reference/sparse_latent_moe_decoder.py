"""Plain reference of the DeepSeek-V3.2-Exp decoder: multi-head latent
attention over a LEARNED SPARSE choice of lines (a lightning indexer, then the
softmax over each query's ``index_topk`` best lines), over a dense then
group-limited sigmoid-routed SwiGLU stack, one rank's share of the experts.

Float32 ``jax.numpy`` under ``default_matmul_precision("highest")``, nothing of
``scaling_tpu``; RMSNorm, LayerNorm, linear and loss are ``dense_decoder``'s,
YaRN's tables and the SwiGLU are ``latent_moe_decoder``'s (the block around
the indexer is DeepSeek-V3's). Written from the published configuration of
deepseek-ai/DeepSeek-V3.2-Exp (``model_type: deepseek_v32``) and from the
release's ``inference/model.py`` as remembered (the configuration file marks
each such line). Every layer is TWO pre-norm sub-blocks:

    h <- h + Attn(RMSNorm_attn(h))
    h <- h + FFN_i(RMSNorm_ffn(h))      FFN_i = dense (i < num_dense) | routed

- latent attention, the EXPANDED form (the program serves the absorbed one
  over gathered lines): ``c_q = RMSNorm(x W_DQ)``; ``q_h = c_q W_UQ,h =
  [q_nope_h, q_rope_h]``; ``[c_kv, k_r] = x W_DKV``; ``c_kv <- RMSNorm(c_kv)``;
  rotary on ``q_rope_h`` and on the ONE ``k_r``; ``[k_nope_h, v_h] = c_kv
  W_UKV,h``; ``k_h = [k_nope_h, k_r]``.
- the indexer: ``q_I[t, j] = (c_q,t W_IQ)[j]`` (``index_heads`` heads of
  ``index_dim``); ``k_I[s] = LayerNorm(x_s W_IK)`` (weight and bias); rotary
  on the FIRST ``rope`` lanes of both (in a latent head the rope lanes come
  last); ``w[t, j] = (x_t W_Iw)[j] index_heads ** -0.5 index_dim ** -0.5``;
  ``I[t, s] = sum_j w[t, j] relu(q_I[t, j] . k_I[s])`` for ``s <= t``.
- the choice, EXACT: query ``t`` keeps the ``min(index_topk, t + 1)`` visible
  lines of largest ``I[t, s]``, a tie going to the lower position: a STABLE
  descending sort of the scores and the rank of every line in it (no
  ``top_k``: the program's is the thing compared).
- ``o_h = softmax(scale q_h k_h^T over the chosen lines) v_h``; ``y =
  concat_h(o_h) W_O``. Computed in blocks of ``QUERY_BLOCK`` queries, each
  against all keys, so that 8k positions at 128 heads fit beside the weights.
  ``index_topk`` None in the spec leaves the choice out (dense latent
  attention): what the tests and the builder's control read the choice's
  weight in the comparison from.
- routed FFN (``noaux_tc``, ``sigmoid``), GROUP-LIMITED: ``s = sigmoid(x
  W_r)`` over ALL experts, float32; with ``s' = s + b`` (the selection bias)
  the experts lie in ``n_group`` contiguous groups, a group's score is the sum
  of its two largest ``s'``, the ``topk_group`` best groups stay (a tie going
  to the lower group), the ``top_k`` largest ``s'`` inside them are chosen;
  ``g_e = scale * s_e / (sum of the chosen s + gate_eps)``; the experts HELD
  here are ``[experts_first, experts_first + held)``: the gates of absent
  experts are dropped, NOT renormalised; one shared expert, every token, added
  once (``shared: False`` leaves it out: the test that adds the shares up).
- after the last layer one RMSNorm, then an untied head.

Departures, each under ``assumed`` in the configuration: rotary pairs lane
``i`` with lane ``i + rope / 2`` in heads, key and indexer alike; index keys
in the compute precision (the release keeps them in FP8 after a Hadamard
rotation of query and key, orthogonal, so every ``q . k`` is as it is); no
multi-token-prediction module.

Weights: ``latent_moe_decoder``'s, a layer with four leaves more: ``index_q``
(q_lora, index_heads x index_dim), ``index_k`` (H, index_dim), ``index_k_norm``
(``{"weight", "bias"}``), ``index_w`` (H, index_heads). ``spec``:
``latent_moe_decoder``'s keys and ``index_heads``, ``index_dim``,
``index_topk``, ``n_group``, ``topk_group``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark.reference.dense_decoder import (  # noqa: F401  (token_loss: the contract)
    F32, _f32, linear, norm, token_loss,
)
from benchmark.reference.latent_moe_decoder import (
    EXPERT_BLOCK, EXPERT_LEAVES, rotary, softmax_scale, swiglu,
)

QUERY_BLOCK = 128


def index_scores(index_q, index_k, index_w):
    """``I[t, s]``: index_q (t, j, d), index_k (s, d), index_w (t, j)."""
    dots = jnp.einsum("tjd,sd->tjs", index_q, index_k)
    return jnp.einsum("tjs,tj->ts", jax.nn.relu(dots), index_w)


def chosen_lines(scores, visible, topk):
    """``(t, s)`` bool: each query's ``min(topk, seen)`` visible lines of
    largest score, a tie going to the lower position."""
    if topk is None:
        return visible
    order = jnp.argsort(-jnp.where(visible, scores, -jnp.inf), axis=-1, stable=True)
    rank = jnp.argsort(order, axis=-1)      # the inverse permutation
    return visible & (rank < topk)


def sparse_attention(q, k, v, index_q, index_k, index_w, scale: float, topk):
    """q, k (s, n, d), v (s, n, dv): the causal softmax over each query's
    chosen lines, a block of ``QUERY_BLOCK`` queries at a time against all
    keys. Returns ``(out (s, n, dv), chosen (s, s) bool)``."""
    s = q.shape[0]
    block = min(QUERY_BLOCK, s)
    pad = -s % block
    q, index_q, index_w = (jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
                           for a in (q, index_q, index_w))
    keys = jnp.arange(s)

    def one(start):
        qb, iq, iw = (jax.lax.dynamic_slice_in_dim(a, start, block, 0)
                      for a in (q, index_q, index_w))
        visible = keys[None, :] <= (start + jnp.arange(block))[:, None]
        chosen = chosen_lines(index_scores(iq, index_k, iw), visible, topk)
        scores = jnp.einsum("qnd,knd->nqk", qb, k) * scale
        probs = jax.nn.softmax(jnp.where(chosen[None], scores, -jnp.inf), axis=-1)
        # a padded query past the sequence sees every key: finite, cut below
        return jnp.einsum("nqk,knd->qnd", probs, v), chosen

    out, chosen = jax.lax.map(one, jnp.arange(0, s + pad, block))
    return (out.reshape(s + pad, *out.shape[2:])[:s],
            chosen.reshape(s + pad, s)[:s])


def attention_parts(x, p, spec):
    """x (s, H) float32, one sequence: ``(y (s, H), chosen (s, s))``."""
    s = x.shape[0]
    n, lora, nope, rope, dv = (spec[k] for k in ("num_heads", "kv_lora", "nope", "rope", "v"))
    heads, dim = spec["index_heads"], spec["index_dim"]
    positions = jnp.arange(s)
    turn = functools.partial(rotary, positions=positions, rope_base=spec["rope_base"],
                             yarn=spec["yarn"])
    c_q = norm(x @ p["q_a"], p["q_a_norm"], "rms", spec["eps"])
    q = (c_q @ p["q_b"]).reshape(s, n, nope + rope)
    kv = x @ p["kv_a"]
    c_kv = norm(kv[:, :lora], p["kv_a_norm"], "rms", spec["eps"])
    k_r = turn(kv[:, None, lora:])
    up = (c_kv @ p["kv_b"]).reshape(s, n, nope + dv)
    k = jnp.concatenate([up[..., :nope], jnp.broadcast_to(k_r, (s, n, rope))], -1)
    q = jnp.concatenate([q[..., :nope], turn(q[..., nope:])], -1)
    # the indexer: its rope lanes come FIRST
    index_q = (c_q @ p["index_q"]).reshape(s, heads, dim)
    index_q = jnp.concatenate([turn(index_q[..., :rope]), index_q[..., rope:]], -1)
    index_k = norm(x @ p["index_k"], p["index_k_norm"], "layernorm", spec["eps"])
    index_k = jnp.concatenate(
        [turn(index_k[:, None, :rope])[:, 0], index_k[:, rope:]], -1)
    index_w = (x @ p["index_w"]) * (heads ** -0.5 * dim ** -0.5)
    out, chosen = sparse_attention(
        q, k, up[..., nope:], index_q, index_k, index_w,
        softmax_scale(nope, rope, spec["yarn"]), spec["index_topk"])
    return out.reshape(s, n * dv) @ p["o"], chosen


def group_limited(choice, n_group: int, topk_group: int):
    """``choice`` (s, E) with every expert outside the token's ``topk_group``
    best groups at ``-inf``; a group's score is the sum of its two largest."""
    if n_group == 1:
        return choice
    s, E = choice.shape
    groups = choice.reshape(s, n_group, E // n_group)
    group_score = jnp.sort(groups, axis=-1)[..., -2:].sum(-1)        # (s, n_group)
    rank = jnp.argsort(jnp.argsort(-group_score, axis=-1, stable=True), axis=-1)
    return jnp.where((rank < topk_group)[..., None], groups, -jnp.inf).reshape(s, E)


def routed_ffn(x, p, experts, spec):
    """x (s, H) float32; ``experts``: the HELD experts' three stacked leaves
    in the dtype they came in. Each held expert on every token, weighted by
    the token's gate for it (zero for the experts it did not choose)."""
    s = x.shape[0]
    scores = jax.nn.sigmoid(x @ p["router"])                      # (s, E)
    _, idx = jax.lax.top_k(
        group_limited(scores + p["router_bias"], spec["n_group"], spec["topk_group"]),
        spec["top_k"])
    chosen = jnp.take_along_axis(scores, idx, axis=-1)
    gates = spec["scale"] * chosen / (chosen.sum(-1, keepdims=True) + spec["gate_eps"])
    weight = jnp.zeros_like(scores).at[jnp.arange(s)[:, None], idx].set(gates)
    held = experts["w_up"].shape[0]
    first = spec["experts_first"]
    weight = weight[:, first:first + held]        # absent experts: dropped
    block = min(EXPERT_BLOCK, held)
    assert held % block == 0, (held, block)

    def blocks(a):
        return a.reshape(held // block, block, *a.shape[1:])

    def add_block(y, part):
        gate, up, down, w = part                                   # w: (block, s)
        gate, up, down = (a.astype(F32) for a in (gate, up, down))
        hidden = jax.nn.silu(jnp.einsum("sh,ehf->esf", x, gate)) * jnp.einsum(
            "sh,ehf->esf", x, up)
        return y + jnp.einsum("esf,efh->sh", hidden * w[:, :, None], down), None

    y, _ = jax.lax.scan(add_block, jnp.zeros_like(x), (
        *(blocks(experts[name]) for name in EXPERT_LEAVES), blocks(weight.T)))
    if spec["shared"]:
        y = y + swiglu(x, p["shared_gate"], p["shared_up"], p["shared_down"])
    return y


@functools.partial(jax.jit, static_argnames=("spec",))
def attention_block(h, layer, spec):
    """``(h + Attn(RMSNorm(h)), chosen)`` on one sequence: h (s, H) float32."""
    spec = dict(spec)
    with jax.default_matmul_precision("highest"):
        p = _f32(layer)
        y, chosen = attention_parts(norm(h, p["attn_norm"], "rms", spec["eps"]), p, spec)
        return h + y, chosen


@functools.partial(jax.jit, static_argnames=("routed", "spec"))
def ffn_block(h, layer, routed, spec):
    """h <- h + FFN(RMSNorm(h)) on one sequence."""
    spec = dict(spec)
    with jax.default_matmul_precision("highest"):
        experts = {name: layer[name] for name in EXPERT_LEAVES if name in layer}
        p = _f32({k: v for k, v in layer.items() if k not in EXPERT_LEAVES})
        x = norm(h, p["ffn_norm"], "rms", spec["eps"])
        if routed:
            return h + routed_ffn(x, p, experts, spec)
        return h + linear(jax.nn.silu(linear(x, p["gate"])) * linear(x, p["up"]), p["down"])


ATTENTION_LEAVES = ("attn_norm", "q_a", "q_a_norm", "q_b", "kv_a", "kv_a_norm", "kv_b", "o",
                    "index_q", "index_k", "index_k_norm", "index_w")


@functools.partial(jax.jit, static_argnames=("eps",))
def head_forward(h, final_norm, head, eps):
    with jax.default_matmul_precision("highest"):
        return norm(h, _f32(final_norm), "rms", eps) @ head.astype(F32)


def forward(weights, tokens, spec, head_positions=None, chosen_out=None):
    """Logits (len(head_positions) or s, V) in float32 of one sequence of
    token ids; as ``dense_decoder.forward``. ``chosen_out``, a list, takes
    every layer's ``(s, s)`` bool of the lines each query attended over."""
    frozen = tuple(sorted(spec.items()))
    h = weights["embedding"][tokens].astype(F32)
    for i, layer in enumerate(weights["layers"]):
        h, chosen = attention_block(h, {k: layer[k] for k in ATTENTION_LEAVES}, frozen)
        if chosen_out is not None:
            chosen_out.append(chosen)
        h = ffn_block(h, {k: v for k, v in layer.items() if k not in ATTENTION_LEAVES},
                      i >= spec["num_dense"], frozen)
    if head_positions is not None:
        h = h[head_positions]
    return head_forward(h, weights["final_norm"], weights["head"], spec["eps"])
