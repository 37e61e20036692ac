"""Plain reference of the LFM2-MoE decoder: gated short convolutions and
grouped-query attention as operators, dense then routed SwiGLU as FFNs.

Float32 ``jax.numpy`` under ``default_matmul_precision("highest")``, nothing
of ``scaling_tpu``; rotary positions, causal attention, the RMSNorm and the
loss are ``dense_decoder``'s. Written from the published configuration of
LiquidAI/LFM2-24B-A2B (``model_type: lfm2_moe``) and the block as the family's
released modelling code has it. Every layer is TWO pre-norm sub-blocks:

    h <- h + Op_i (RMSNorm_op(h))       Op_i  = conv | attention, by ``ops[i]``
    h <- h + FFN_i(RMSNorm_ffn(h))      FFN_i = dense (i < num_dense) | routed

- ``conv``: ``[B | C | X] = x W_in`` (three times ``H`` columns, no bias);
  ``u = B * X``; ``v_t = sum_{j<K} w[:, j] * u_{t-K+1+j}`` with ``u = 0`` before
  the sequence (depthwise, causal, no bias, NO activation), written as a sum
  of ``K`` shifted products; ``y = (C * v) W_out``.
- ``attention``: ``q``, ``k`` each RMSNorm'd PER HEAD (one weight of
  ``head_dim``) BEFORE rotary; rotary on every dimension of each head; causal
  grouped-query softmax at ``1 / sqrt(head_dim)``; no bias; ``W_o``.
- dense FFN: ``W_2(silu(x W_1) * (x W_3))``.
- routed FFN: ``s = sigmoid(x W_r)`` over all experts, float32; the ``top_k``
  experts with the largest ``s_e + b_e`` (``b``: the expert bias, for the CHOICE
  only); ``g_e = scale * s_e / (sum of the chosen s + gate_eps)``; ``y = sum_e
  g_e W2_e(silu(x W1_e) * (x W3_e))``. No capacity: nothing is dropped.
- after the last layer one RMSNorm, then the head, TIED to the embedding:
  ``logits = x E^T``.

Departures: none from those equations. The plain form of the expert sum is
kept: a loop over the experts (in blocks of ``EXPERT_BLOCK``, each upcast as
it is used, so that a layer at the published widths, 64 experts of 3 x 2048 x
1536 = 2.4 GB in float32, fits on the chip beside the served weights), every
expert on every token, the unchosen ones weighted by zero: no dispatch.

Weights: ``{"embedding": (V, H), "layers": [layer, ...], "final_norm"}``; a
layer is ``{"op_norm", "ffn_norm": {"weight"}, ...}`` with, by operator,
``conv``: ``in_proj (H, 3 H)``, ``conv_w (H, K)``, ``out_proj (H, H)``;
``attention``: ``q``, ``k``, ``v``, ``o`` (``{"weight"}``), ``q_norm``, ``k_norm``
(``{"weight"}`` of ``head_dim``); and by FFN, dense: ``gate``, ``up``, ``down``
(``{"weight"}``); routed: ``router (H, E)``, ``router_bias (E,)``, ``w_gate``,
``w_up`` (E, H, F), ``w_down`` (E, F, H). ``spec``: ``ops`` (a tuple of
operator kinds, one a layer), ``num_dense``, ``num_heads``, ``num_kv_heads``,
``head_dim``, ``eps``, ``rope_base``, ``top_k``, ``scale``, ``gate_eps``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark.reference.dense_decoder import (  # noqa: F401  (token_loss: the contract)
    F32, _f32, attention, linear, norm, rotary, token_loss,
)

EXPERT_BLOCK = 8
EXPERT_LEAVES = ("w_gate", "w_up", "w_down")


def short_conv(x, p):
    """x (s, H) float32, one sequence from a zero history."""
    s = x.shape[0]
    B, C, X = jnp.split(x @ p["in_proj"], 3, axis=-1)
    u = B * X
    K = p["conv_w"].shape[1]
    padded = jnp.concatenate([jnp.zeros((K - 1, u.shape[1]), F32), u])
    v = sum(padded[j:j + s] * p["conv_w"][:, j] for j in range(K))
    return (C * v) @ p["out_proj"]


def attention_op(x, p, spec):
    s = x.shape[0]
    n, n_kv, d = spec["num_heads"], spec["num_kv_heads"], spec["head_dim"]
    positions = jnp.arange(s)
    # the norm sees one head at a time, before the rotation
    q = norm(linear(x, p["q"]).reshape(s, n, d), p["q_norm"], "rms", spec["eps"])
    k = norm(linear(x, p["k"]).reshape(s, n_kv, d), p["k_norm"], "rms", spec["eps"])
    q = rotary(q, positions, spec["rope_base"])
    k = rotary(k, positions, spec["rope_base"])
    v = linear(x, p["v"]).reshape(s, n_kv, d)
    return linear(attention(q, k, v).reshape(s, n * d), p["o"])


def dense_ffn(x, p):
    return linear(jax.nn.silu(linear(x, p["gate"])) * linear(x, p["up"]), p["down"])


def routed_ffn(x, p, experts, spec):
    """x (s, H) float32; ``experts``: the three stacked leaves in the dtype
    they came in. A loop over the experts: each on every token, weighted by
    the token's gate for it (zero for the experts it did not choose)."""
    s = x.shape[0]
    scores = jax.nn.sigmoid(x @ p["router"])                      # (s, E)
    _, idx = jax.lax.top_k(scores + p["router_bias"], spec["top_k"])
    chosen = jnp.take_along_axis(scores, idx, axis=-1)
    gates = spec["scale"] * chosen / (chosen.sum(-1, keepdims=True) + spec["gate_eps"])
    weight = jnp.zeros_like(scores).at[jnp.arange(s)[:, None], idx].set(gates)
    num_experts = scores.shape[1]
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


@functools.partial(jax.jit, static_argnames=("op", "routed", "spec"))
def layer_forward(h, layer, op, routed, spec):
    """One block (operator, then FFN) on one sequence: h (s, H) float32."""
    spec = dict(spec)
    with jax.default_matmul_precision("highest"):
        experts = {name: layer[name] for name in EXPERT_LEAVES if name in layer}
        p = _f32({k: v for k, v in layer.items() if k not in EXPERT_LEAVES})
        x = norm(h, p["op_norm"], "rms", spec["eps"])
        if op == "conv":
            h = h + short_conv(x, p)
        else:
            assert op == "attention", op
            h = h + attention_op(x, p, spec)
        x = norm(h, p["ffn_norm"], "rms", spec["eps"])
        if routed:
            return h + routed_ffn(x, p, experts, spec)
        return h + dense_ffn(x, p)


@functools.partial(jax.jit, static_argnames=("eps",))
def head_forward(h, final_norm, embedding, eps):
    """The final RMSNorm, then the head tied to the embedding table."""
    with jax.default_matmul_precision("highest"):
        x = norm(h, _f32(final_norm), "rms", eps)
        return jnp.einsum("sh,vh->sv", x, embedding.astype(F32))


def forward(weights, tokens, spec, head_positions=None):
    """Logits (len(head_positions) or s, V) in float32 of one sequence of
    token ids; as ``dense_decoder.forward``."""
    frozen = tuple(sorted((k, v) for k, v in spec.items() if k != "ops"))
    h = weights["embedding"][tokens].astype(F32)
    for i, (op, layer) in enumerate(zip(spec["ops"], weights["layers"])):
        h = layer_forward(h, layer, op, i >= spec["num_dense"], frozen)
    if head_positions is not None:
        h = h[head_positions]
    return head_forward(h, weights["final_norm"], weights["embedding"], spec["eps"])
