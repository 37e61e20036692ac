"""Plain reference of Keye-VL-2.0-30B-A3B's decoder: grouped-query attention
over a LEARNED SPARSE choice of lines (an indexer scores every earlier
position, all heads of a query attend over its ``index_topk`` best), then
softmax-routed SwiGLU experts, every expert held.

Float32 ``jax.numpy`` under ``default_matmul_precision("highest")``, nothing of
``scaling_tpu``; RMSNorm, LayerNorm, linear, rotary, head and loss are
``dense_decoder``'s. Written from the catalog's row of Kwai-Keye/
Keye-VL-2.0-30B-A3B (``model_type: KeyeVL2``; ``described_as``: "GQA 32Q/4KV
with DeepSeek-Sparse-Attention indexer"); what its ``config`` leaves open is
under ``assumed`` in the configuration file. Every layer is TWO pre-norm
sub-blocks:

    h <- h + Attn(RMSNorm_attn(h))
    h <- h + MoE(RMSNorm_ffn(h))

- attention: ``q[t, i] = RMSNorm_d(x_t W_Q)[i]``, ``k[s, g] = RMSNorm_d(x_s
  W_K)[g]`` (one weight of ``head_dim`` each, per head), ``v[s, g] = (x_s
  W_V)[g]``; rotary on every lane of q and k (lane i with lane i + d / 2);
  query head ``i`` reads KV head ``i // (n / n_kv)``.
- the indexer: ``q_I[t, j] = (x_t W_IQ)[j]`` (``index_heads`` heads of
  ``index_dim``, from the hidden state); ``k_I[s] = LayerNorm(x_s W_IK)``
  (weight and bias; ONE key a token); rotary on every lane of both, at the
  block's base; ``w[t, j] = (x_t W_Iw)[j] index_heads ** -0.5 index_dim **
  -0.5``; ``I[t, s] = sum_j w[t, j] relu(q_I[t, j] . k_I[s])`` for ``s <= t``.
- the choice, EXACT and ONE a token (every head of the query shares it):
  query ``t`` keeps the ``min(index_topk, t + 1)`` visible lines of largest
  ``I[t, s]``, a tie going to the lower position: a STABLE descending sort of
  the scores and the rank of every line in it (no ``top_k``: the program's is
  the thing compared).
- ``o[t, i] = softmax(d ** -0.5 q[t, i] . k[s, i // group] over the chosen
  lines) v[s, i // group]``; ``y = concat_i(o[t, i]) W_O``. Computed in blocks
  of ``QUERY_BLOCK`` queries, each against all keys, so that 16k positions at
  32 heads fit beside the weights. ``index_topk`` None in the spec leaves the
  choice out (dense grouped-query attention): what the tests and the builder's
  control read the choice's weight in the comparison from.
- routed MLP: ``p = softmax(x W_r)`` in float32 over ALL experts; the
  ``top_k`` largest are chosen and their gates are ``p_e / sum of the chosen
  p`` (``norm_topk_prob`` true); ``MoE(x) = sum over them of g_e
  W_down_e(silu(W_gate_e x) * W_up_e x)``; no shared expert, no bias, no
  capacity. Every expert runs on every token, the unchosen weighted by zero,
  in blocks of ``EXPERT_BLOCK`` upcast as they are used.
- after the last layer one RMSNorm, then an untied head.

Weights: ``embedding`` (V, H), ``layers`` (each ``attn_norm``, ``ffn_norm``,
``q_norm``, ``k_norm``: ``{"weight"}``; ``q``, ``k``, ``v``, ``o``:
``{"weight"}``; ``index_q`` (H, index_heads x index_dim), ``index_k`` (H,
index_dim), ``index_k_norm`` ``{"weight", "bias"}``, ``index_w`` (H,
index_heads); ``router`` (H, E); ``gate``, ``up`` (E, H, F), ``down`` (E, F,
H)), ``final_norm``, ``head`` (H, V). ``spec``: ``num_heads``,
``num_kv_heads``, ``head_dim``, ``eps``, ``rope_base``, ``top_k``,
``index_heads``, ``index_dim``, ``index_topk``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark.reference.dense_decoder import (  # noqa: F401  (token_loss: the contract)
    F32, _f32, linear, norm, rotary, token_loss,
)

QUERY_BLOCK = 128
EXPERT_BLOCK = 8
EXPERT_LEAVES = ("gate", "up", "down")


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


def sparse_attention(q, k, v, index_q, index_k, index_w, topk):
    """q (s, n, d), k and v (s, n_kv, d): the causal softmax of every head
    over its query's chosen lines, a block of ``QUERY_BLOCK`` queries at a
    time against all keys. Returns ``(out (s, n, d), chosen (s, s) bool)``."""
    s, n, d = q.shape
    group = n // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
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
        scores = jnp.einsum("qnd,knd->nqk", qb, k) / jnp.sqrt(F32(d))
        probs = jax.nn.softmax(jnp.where(chosen[None], scores, -jnp.inf), axis=-1)
        # a padded query past the sequence sees every key: finite, cut below
        return jnp.einsum("nqk,knd->qnd", probs, v), chosen

    out, chosen = jax.lax.map(one, jnp.arange(0, s + pad, block))
    return (out.reshape(s + pad, n, d)[:s], chosen.reshape(s + pad, s)[:s])


def attention_parts(x, p, spec):
    """x (s, H) float32, one sequence: ``(y (s, H), chosen (s, s))``."""
    s = x.shape[0]
    n, n_kv, d = spec["num_heads"], spec["num_kv_heads"], spec["head_dim"]
    heads, dim = spec["index_heads"], spec["index_dim"]
    positions = jnp.arange(s)
    turn = functools.partial(rotary, positions=positions, base=spec["rope_base"])
    # one weight of head_dim, every head
    q = turn(norm(linear(x, p["q"]).reshape(s, n, d), p["q_norm"], "rms", spec["eps"]))
    k = turn(norm(linear(x, p["k"]).reshape(s, n_kv, d), p["k_norm"], "rms", spec["eps"]))
    v = linear(x, p["v"]).reshape(s, n_kv, d)
    # the indexer: from the hidden state, its whole head rotary
    index_q = turn((x @ p["index_q"]).reshape(s, heads, dim))
    index_k = turn(norm(x @ p["index_k"], p["index_k_norm"], "layernorm",
                        spec["eps"])[:, None, :])[:, 0]
    index_w = (x @ p["index_w"]) * (heads ** -0.5 * dim ** -0.5)
    out, chosen = sparse_attention(q, k, v, index_q, index_k, index_w,
                                   spec["index_topk"])
    return linear(out.reshape(s, n * d), p["o"]), chosen


def routed_mlp(x, router, experts, top_k: int):
    """x (s, H) float32; ``experts``: the three stacked leaves in the dtype
    they came in. Every expert on every token, weighted by the token's gate
    for it (zero for the experts it did not choose); the gates of the chosen
    sum to one."""
    s = x.shape[0]
    probs = jax.nn.softmax(x @ router, axis=-1)                    # (s, E), float32
    gate_vals, gate_idx = jax.lax.top_k(probs, top_k)
    gate_vals = gate_vals / gate_vals.sum(-1, keepdims=True)
    weight = jnp.zeros_like(probs).at[jnp.arange(s)[:, None], gate_idx].set(gate_vals)
    num_experts = router.shape[1]
    block = min(EXPERT_BLOCK, num_experts)
    assert num_experts % block == 0, (num_experts, block)

    def blocks(a):
        return a.reshape(num_experts // block, block, *a.shape[1:])

    def add_block(y, part):
        gate, up, down, w = part                                   # w: (block, s)
        gate, up, down = (a.astype(F32) for a in (gate, up, down))
        hidden = jax.nn.silu(jnp.einsum("sh,ehf->esf", x, gate)) * jnp.einsum(
            "sh,ehf->esf", x, up)
        return y + jnp.einsum("esf,efh->sh", hidden * w[:, :, None], down), None

    y, _ = jax.lax.scan(add_block, jnp.zeros_like(x), (
        *(blocks(experts[name]) for name in EXPERT_LEAVES), blocks(weight.T)))
    return y


FFN_LEAVES = ("ffn_norm", "router") + EXPERT_LEAVES


@functools.partial(jax.jit, static_argnames=("spec",))
def attention_block(h, layer, spec):
    """``(h + Attn(RMSNorm(h)), chosen)`` on one sequence: h (s, H) float32."""
    spec = dict(spec)
    with jax.default_matmul_precision("highest"):
        p = _f32(layer)
        y, chosen = attention_parts(norm(h, p["attn_norm"], "rms", spec["eps"]), p, spec)
        return h + y, chosen


@functools.partial(jax.jit, static_argnames=("spec",))
def ffn_block(h, layer, spec):
    """h <- h + MoE(RMSNorm(h)) on one sequence."""
    spec = dict(spec)
    with jax.default_matmul_precision("highest"):
        experts = {name: layer[name] for name in EXPERT_LEAVES}
        x = norm(h, _f32(layer["ffn_norm"]), "rms", spec["eps"])
        return h + routed_mlp(x, layer["router"].astype(F32), experts, spec["top_k"])


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
    for layer in weights["layers"]:
        h, chosen = attention_block(
            h, {k: v for k, v in layer.items() if k not in FFN_LEAVES}, frozen)
        if chosen_out is not None:
            chosen_out.append(chosen)
        h = ffn_block(h, {k: layer[k] for k in FFN_LEAVES}, frozen)
    if head_positions is not None:
        h = h[head_positions]
    return head_forward(h, weights["final_norm"], weights["head"], spec["eps"])
