"""Plain reference of the DeepSeek-V3 / Kimi-K2 decoder: multi-head LATENT
attention over a dense then sigmoid-routed SwiGLU stack, one rank's share of
the routed experts.

Float32 ``jax.numpy`` under ``default_matmul_precision("highest")``, nothing of
``scaling_tpu``; the RMSNorm, the linear and the loss are ``dense_decoder``'s.
Written from the published configuration of moonshotai/Kimi-K2-Instruct
(``model_type: kimi_k2``: DeepSeek-V3's modelling code at Kimi's numbers).
Every layer is TWO pre-norm sub-blocks:

    h <- h + Attn(RMSNorm_attn(h))
    h <- h + FFN_i(RMSNorm_ffn(h))      FFN_i = dense (i < num_dense) | routed

- latent attention, the EXPANDED form only (the program serves the absorbed
  one, so the comparison tests the absorption): ``c_q = RMSNorm(x W_DQ)``;
  ``q_h = c_q W_UQ,h = [q_nope_h, q_rope_h]``; ``[c_kv, k_r] = x W_DKV``;
  ``c_kv <- RMSNorm(c_kv)``; rotary on ``q_rope_h`` and on the ONE ``k_r`` all
  heads share; ``[k_nope_h, v_h] = c_kv W_UKV,h``; ``k_h = [k_nope_h, k_r]``;
  ``o_h = softmax(scale q_h k_h^T + causal) v_h``; ``y = concat_h(o_h) W_O``.
  The full causal softmax is computed in blocks of ``QUERY_BLOCK`` queries,
  each against all keys, so that 8k-16k positions fit beside the weights.
- YaRN from the formula: ``f_i = base ** (-2i / rope)``; the correction
  index of ``r`` rotations is ``rope ln(original / (2 pi r)) / (2 ln base)``;
  ``low = floor(index(beta_fast))``, ``high = ceil(index(beta_slow))``;
  ``ramp_i = clip((i - low) / (high - low), 0, 1)``; ``inv_freq_i = f_i (1 -
  ramp_i) + (f_i / factor) ramp_i``, static; cos / sin times ``m(mscale) /
  m(mscale_all_dim)``, ``m(s) = 0.1 s ln(factor) + 1``; ``scale = (nope +
  rope) ** -0.5 * m(mscale_all_dim) ** 2``.
- dense FFN: ``W_2(silu(x W_1) * (x W_3))``.
- routed FFN (``noaux_tc``, ``sigmoid``): ``s = sigmoid(x W_r)`` over ALL
  experts, float32; the ``top_k`` with the largest ``s_e + b_e`` (``b``: the
  selection bias, for the CHOICE only; no group limit); ``g_e = scale * s_e /
  (sum of the chosen s + gate_eps)``; the experts HELD here are ``[experts_first,
  experts_first + held)``: the gates of absent experts are dropped, NOT
  renormalised; one shared expert, every token, added once (``shared: False``
  in the spec leaves it out: the test that adds the shares up counts it once).
- after the last layer one RMSNorm, then an untied head.

Departures, each under ``assumed`` in the configuration: rotary pairs lane
``i`` with lane ``i + rope / 2`` (the half-rotation layout; the released code
de-interleaves adjacent pairs first, which is this with the rope columns of
``W_UQ`` and ``W_DKV`` permuted: with seeded weights a convention). The plain
form of the expert sum is kept: every held expert on every token, the
unchosen ones weighted by zero.

Weights: ``{"embedding": (V, H), "layers": [layer, ...], "final_norm",
"head": (H, V)}``; a layer is ``{"attn_norm", "ffn_norm", "q_a_norm",
"kv_a_norm": {"weight"}, "q_a" (H, q_lora), "q_b" (q_lora, n (nope + rope)),
"kv_a" (H, kv_lora + rope), "kv_b" (kv_lora, n (nope + v)), "o" (n v, H)}``
with, by FFN, dense: ``gate``, ``up``, ``down`` (``{"weight"}``); routed:
``router (H, E)``, ``router_bias (E,)``, ``w_gate``, ``w_up`` (held, H, F),
``w_down`` (held, F, H), ``shared_gate``, ``shared_up`` (H, Fs), ``shared_down``
(Fs, H). ``spec``: ``num_dense``, ``num_heads``, ``kv_lora``, ``nope``,
``rope``, ``v``, ``eps``, ``rope_base``, ``yarn`` (a tuple: factor, original,
beta_fast, beta_slow, mscale, mscale_all_dim; or None), ``top_k``, ``scale``,
``gate_eps``, ``experts_first``, ``shared``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmark.reference.dense_decoder import (  # noqa: F401  (token_loss: the contract)
    F32, _f32, linear, norm, token_loss,
)

EXPERT_BLOCK = 4
EXPERT_LEAVES = ("w_gate", "w_up", "w_down")
QUERY_BLOCK = 256


def yarn_m(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_range(rope: int, base: float, yarn):
    """``(low, high)`` of the ramp (19, 20 at Kimi-K2's numbers)."""
    _, original, beta_fast, beta_slow, _, _ = yarn

    def index(rotations):
        return rope * math.log(original / (rotations * 2 * math.pi)) / (2 * math.log(base))

    return (max(math.floor(index(beta_fast)), 0),
            min(math.ceil(index(beta_slow)), rope - 1))


def inv_freq(rope: int, base: float, yarn):
    f = 1.0 / (base ** (jnp.arange(0, rope, 2, dtype=F32) / rope))
    if yarn is None:
        return f
    low, high = yarn_range(rope, base, yarn)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(rope // 2, dtype=F32) - low) / (high - low), 0.0, 1.0)
    return f * (1.0 - ramp) + (f / yarn[0]) * ramp


def softmax_scale(nope: int, rope: int, yarn) -> float:
    scale = (nope + rope) ** -0.5
    if yarn is not None and yarn[5]:
        scale *= yarn_m(yarn[0], yarn[5]) ** 2
    return scale


def rotary(x, positions, rope_base: float, yarn):
    """x (s, n, rope); rotates the pair (i, i + rope/2) by positions *
    inv_freq_i."""
    d = x.shape[-1]
    angle = positions.astype(F32)[:, None] * inv_freq(d, rope_base, yarn)[None, :]
    amplitude = 1.0 if yarn is None else yarn_m(yarn[0], yarn[4]) / yarn_m(yarn[0], yarn[5])
    cos = jnp.concatenate([jnp.cos(angle)] * 2, -1)[:, None, :] * amplitude
    sin = jnp.concatenate([jnp.sin(angle)] * 2, -1)[:, None, :] * amplitude
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def causal_attention(q, k, v, scale: float):
    """q, k (s, n, d), v (s, n, dv): the full causal softmax, a block of
    ``QUERY_BLOCK`` queries at a time against all keys."""
    s = q.shape[0]
    block = min(QUERY_BLOCK, s)
    pad = -s % block
    q = jnp.pad(q, ((0, pad), (0, 0), (0, 0)))
    keys = jnp.arange(s)

    def one(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, 0)
        scores = jnp.einsum("qnd,knd->nqk", qb, k) * scale
        visible = keys[None, :] <= (start + jnp.arange(block))[:, None]
        probs = jax.nn.softmax(jnp.where(visible[None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("nqk,knd->qnd", probs, v)

    out = jax.lax.map(one, jnp.arange(0, s + pad, block))
    return out.reshape(s + pad, *out.shape[2:])[:s]


def latent_attention(x, p, spec):
    """x (s, H) float32, one sequence."""
    s = x.shape[0]
    n, lora, nope, rope, dv = (spec[k] for k in ("num_heads", "kv_lora", "nope", "rope", "v"))
    positions = jnp.arange(s)
    c_q = norm(x @ p["q_a"], p["q_a_norm"], "rms", spec["eps"])
    q = (c_q @ p["q_b"]).reshape(s, n, nope + rope)
    kv = x @ p["kv_a"]
    c_kv = norm(kv[:, :lora], p["kv_a_norm"], "rms", spec["eps"])
    k_r = rotary(kv[:, None, lora:], positions, spec["rope_base"], spec["yarn"])
    q_r = rotary(q[..., nope:], positions, spec["rope_base"], spec["yarn"])
    up = (c_kv @ p["kv_b"]).reshape(s, n, nope + dv)
    k = jnp.concatenate([up[..., :nope], jnp.broadcast_to(k_r, (s, n, rope))], -1)
    q = jnp.concatenate([q[..., :nope], q_r], -1)
    out = causal_attention(q, k, up[..., nope:],
                           softmax_scale(nope, rope, spec["yarn"]))
    return out.reshape(s, n * dv) @ p["o"]


def swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def routed_ffn(x, p, experts, spec):
    """x (s, H) float32; ``experts``: the HELD experts' three stacked leaves
    in the dtype they came in. Each held expert on every token, weighted by
    the token's gate for it (zero for the experts it did not choose)."""
    s = x.shape[0]
    scores = jax.nn.sigmoid(x @ p["router"])                      # (s, E)
    _, idx = jax.lax.top_k(scores + p["router_bias"], spec["top_k"])
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
    """h <- h + Attn(RMSNorm(h)) on one sequence: h (s, H) float32."""
    spec = dict(spec)
    with jax.default_matmul_precision("highest"):
        p = _f32(layer)
        return h + latent_attention(norm(h, p["attn_norm"], "rms", spec["eps"]), p, spec)


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


ATTENTION_LEAVES = ("attn_norm", "q_a", "q_a_norm", "q_b", "kv_a", "kv_a_norm", "kv_b", "o")


@functools.partial(jax.jit, static_argnames=("eps",))
def head_forward(h, final_norm, head, eps):
    with jax.default_matmul_precision("highest"):
        return norm(h, _f32(final_norm), "rms", eps) @ head.astype(F32)


def forward(weights, tokens, spec, head_positions=None):
    """Logits (len(head_positions) or s, V) in float32 of one sequence of
    token ids; as ``dense_decoder.forward``."""
    frozen = tuple(sorted(spec.items()))
    h = weights["embedding"][tokens].astype(F32)
    for i, layer in enumerate(weights["layers"]):
        h = attention_block(h, {k: layer[k] for k in ATTENTION_LEAVES}, frozen)
        h = ffn_block(h, {k: v for k, v in layer.items() if k not in ATTENTION_LEAVES},
                      i >= spec["num_dense"], frozen)
    if head_positions is not None:
        h = h[head_positions]
    return head_forward(h, weights["final_norm"], weights["head"], spec["eps"])
