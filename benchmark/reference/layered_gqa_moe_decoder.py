"""Plain reference of poolside Laguna-S-2.1's decoder: grouped-query attention
that DIFFERS BY LAYER (full layers: their own head count, YaRN on the rotated
part of a head; sliding-window layers: more heads, a window, a plain rotary),
a per-head output gate on both, then a dense SwiGLU (the leading block) or
softmax-routed SwiGLU experts with one shared expert, one rank's share held.

Float32 ``jax.numpy`` under ``default_matmul_precision("highest")``, no cache,
no kernel, nothing of ``scaling_tpu``; RMSNorm, linear and loss are
``dense_decoder``'s. Written from the catalog's row of poolside/Laguna-S-2.1
(``model_type: laguna``); what its ``config`` leaves open is under ``assumed``
in the configuration file. Every block is TWO pre-norm sub-blocks:

    h <- h + Attn_l(RMSNorm_attn(h))
    h <- h + FFN_l(RMSNorm_ffn(h))

- ``Attn_l``, with ``n_l`` query heads (``heads[kind]``), ``n_kv`` KV heads of
  ``head_dim``: ``q = x W_q`` (``n_l`` heads), ``k = x W_k``, ``v = x W_v``;
  query head ``j`` reads KV head ``j // (n_l / n_kv)``.
  Rotary on the first ``dims`` lanes of every head of q and k (lane ``i`` with
  lane ``i + dims / 2``; the other lanes pass). FULL layers: YaRN's static
  frequencies (``f_i = base ** (-2i / dims)`` below ``low``, ``f_i / factor``
  from ``high`` on, a linear ramp between; ``low`` / ``high``: the indices
  that make ``beta_fast`` / ``beta_slow`` rotations over the original context,
  rounded down / up), cos and sin times ``attention_factor``. WINDOW layers:
  the base's frequencies, factor 1.
  Scores ``q . k head_dim ** -0.5``, causal; in a window layer query ``t``
  sees keys ``s`` with ``t - window < s <= t``: a mask over the WHOLE scores,
  computed ``QUERY_BLOCK`` queries at a time against all keys.
  The gate: ``g = sigmoid(x W_g)`` (``W_g``: H x ``n_l``, from the same normed
  input); head ``j``'s output times ``g_j``, then ``W_o``.
- dense FFN: ``(silu(x W_gate) * x W_up) W_down``.
- routed FFN: ``p = softmax(x W_r)`` in float32 over ALL experts; the
  ``top_k`` largest are chosen, gates ``scale p_e / sum of the chosen p``; the
  experts HELD here are ``[experts_first, experts_first + held)``: the gates
  of absent experts are dropped AFTER the renormalisation, not renormalised
  again; each held expert's OUTPUT is weighted; one shared expert of the same
  form runs on every token and is added once (``shared`` False leaves it out:
  the test that adds the ranks' shares counts it once). Every held expert runs
  on every token, the unchosen weighted by zero, ``EXPERT_BLOCK`` at a time,
  upcast as they are used.
- after the last block one RMSNorm, then an untied head.

Weights: ``embedding`` (V, H); ``layers``, each ``attn_norm``, ``ffn_norm``
(``{"weight"}``), ``q``, ``k``, ``v``, ``o`` (``{"weight"}``), ``head_gate``
(H, n_l), and dense: ``gate``, ``up``, ``down`` (``{"weight"}``) or routed:
``router`` (H, E), ``w_gate``, ``w_up`` (held, H, F), ``w_down`` (held, F, H),
``shared_gate``, ``shared_up`` (H, Fs), ``shared_down`` (Fs, H);
``final_norm``; ``head`` (H, V). ``spec``: ``kinds`` (a tuple, ``"full"`` |
``"window"`` a block), ``heads`` ((full, window)), ``num_kv_heads``,
``head_dim``, ``window``, ``eps``, ``rope`` ((full, window), each ``(base,
dims, yarn)`` with ``yarn`` None or ``(factor, original, beta_fast, beta_slow,
attention_factor)``), ``top_k``, ``scale``, ``experts_first``, ``shared``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmark.reference.dense_decoder import (  # noqa: F401  (token_loss: the contract)
    F32, _f32, linear, norm, token_loss,
)

QUERY_BLOCK = 128
EXPERT_BLOCK = 8
KINDS = ("full", "window")
EXPERT_LEAVES = ("w_gate", "w_up", "w_down")
SHARED_LEAVES = ("shared_gate", "shared_up", "shared_down")
ATTENTION_LEAVES = ("attn_norm", "q", "k", "v", "o", "head_gate")


def inv_freq(dims: int, base: float, yarn):
    """The ``dims / 2`` frequencies of a rotary over ``dims`` lanes."""
    f = 1.0 / (base ** (jnp.arange(0, dims, 2, dtype=F32) / dims))
    if yarn is None:
        return f
    factor, original, beta_fast, beta_slow, _ = yarn

    def index(rotations):
        return dims * math.log(original / (rotations * 2 * math.pi)) / (
            2 * math.log(base))

    low = max(math.floor(index(beta_fast)), 0)
    high = min(math.ceil(index(beta_slow)), dims - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dims // 2, dtype=F32) - low) / (high - low), 0.0, 1.0)
    return f * (1.0 - ramp) + (f / factor) * ramp


def rotary(x, positions, rope):
    """x (s, n, d): the first ``dims`` lanes of every head turned, lane ``i``
    with lane ``i + dims / 2``; the rest pass."""
    base, dims, yarn = rope
    angle = positions.astype(F32)[:, None] * inv_freq(dims, base, yarn)[None, :]
    amplitude = 1.0 if yarn is None else yarn[4]
    cos = amplitude * jnp.concatenate([jnp.cos(angle)] * 2, -1)[:, None, :]
    sin = amplitude * jnp.concatenate([jnp.sin(angle)] * 2, -1)[:, None, :]
    turned, rest = x[..., :dims], x[..., dims:]
    x1, x2 = turned[..., : dims // 2], turned[..., dims // 2:]
    turned = turned * cos + jnp.concatenate([-x2, x1], -1) * sin
    return jnp.concatenate([turned, rest], -1)


def attention(q, k, v, window):
    """q (s, n, d), k and v (s, n_kv, d): the causal softmax of every head,
    under ``window`` (None: none) a query ``t`` over the keys ``t - window < s
    <= t``; ``QUERY_BLOCK`` queries at a time against all keys."""
    s, n, d = q.shape
    group = n // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    block = min(QUERY_BLOCK, s)
    pad = -s % block
    q = jnp.pad(q, ((0, pad), (0, 0), (0, 0)))
    keys = jnp.arange(s)

    def one(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, 0)
        at = (start + jnp.arange(block))[:, None]
        seen = keys[None, :] <= at
        if window is not None:
            seen = seen & (keys[None, :] > at - window)
        scores = jnp.einsum("qnd,knd->nqk", qb, k) / jnp.sqrt(F32(d))
        probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("nqk,knd->qnd", probs, v)

    out = jax.lax.map(one, jnp.arange(0, s + pad, block))
    return out.reshape(s + pad, n, d)[:s]


def attention_parts(x, p, kind: str, spec, gated: bool = True):
    """x (s, H) float32, the block's normed input: ``Attn(x)`` (s, H)."""
    s = x.shape[0]
    i = KINDS.index(kind)
    n, n_kv, d = spec["heads"][i], spec["num_kv_heads"], spec["head_dim"]
    positions = jnp.arange(s)
    q = rotary(linear(x, p["q"]).reshape(s, n, d), positions, spec["rope"][i])
    k = rotary(linear(x, p["k"]).reshape(s, n_kv, d), positions, spec["rope"][i])
    v = linear(x, p["v"]).reshape(s, n_kv, d)
    out = attention(q, k, v, spec["window"] if kind == "window" else None)
    if gated:
        out = out * jax.nn.sigmoid(x @ p["head_gate"])[:, :, None]
    return linear(out.reshape(s, n * d), p["o"])


def swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def routed_ffn(x, p, experts, spec):
    """x (s, H) float32; ``experts``: the three stacked leaves of the experts
    HELD here, in the dtype they came in."""
    s = x.shape[0]
    probs = jax.nn.softmax(x @ p["router"], axis=-1)               # (s, E), float32
    chosen, idx = jax.lax.top_k(probs, spec["top_k"])
    gates = spec["scale"] * chosen / chosen.sum(-1, keepdims=True)
    weight = jnp.zeros_like(probs).at[jnp.arange(s)[:, None], idx].set(gates)
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


@functools.partial(jax.jit, static_argnames=("kind", "spec"))
def attention_block(h, layer, kind, spec):
    """h <- h + Attn(RMSNorm(h)) on one sequence: h (s, H) float32."""
    spec = dict(spec)
    with jax.default_matmul_precision("highest"):
        p = _f32(layer)
        return h + attention_parts(
            norm(h, p["attn_norm"], "rms", spec["eps"]), p, kind, spec)


@functools.partial(jax.jit, static_argnames=("spec",))
def ffn_block(h, layer, spec):
    """h <- h + FFN(RMSNorm(h)) on one sequence."""
    spec = dict(spec)
    with jax.default_matmul_precision("highest"):
        x = norm(h, _f32(layer["ffn_norm"]), "rms", spec["eps"])
        if "router" not in layer:
            p = _f32({k: layer[k] for k in ("gate", "up", "down")})
            return h + swiglu(x, p["gate"]["weight"], p["up"]["weight"],
                              p["down"]["weight"])
        experts = {name: layer[name] for name in EXPERT_LEAVES}
        p = _f32({k: layer[k] for k in ("router",) + SHARED_LEAVES})
        return h + routed_ffn(x, p, experts, spec)


@functools.partial(jax.jit, static_argnames=("eps",))
def head_forward(h, final_norm, head, eps):
    with jax.default_matmul_precision("highest"):
        return norm(h, _f32(final_norm), "rms", eps) @ head.astype(F32)


def forward(weights, tokens, spec, head_positions=None):
    """Logits (len(head_positions) or s, V) in float32 of one sequence of
    token ids; as ``dense_decoder.forward``."""
    frozen = tuple(sorted(spec.items()))
    h = weights["embedding"][tokens].astype(F32)
    for layer, kind in zip(weights["layers"], spec["kinds"], strict=True):
        h = attention_block(
            h, {k: layer[k] for k in ATTENTION_LEAVES}, kind, frozen)
        h = ffn_block(
            h, {k: v for k, v in layer.items() if k not in ATTENTION_LEAVES},
            frozen)
    if head_positions is not None:
        h = h[head_positions]
    return head_forward(h, weights["final_norm"], weights["head"], spec["eps"])
