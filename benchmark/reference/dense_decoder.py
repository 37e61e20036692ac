"""Plain reference of the dense decoder family the benchmark's cells run.

Float32 ``jax.numpy`` under ``default_matmul_precision("highest")``: no
kernel, no cache, no batching tricks, and nothing imported from
``scaling_tpu``. It follows the published descriptions (Mistral-7B-v0.3's
``modeling_mistral``; Pharia-1-LLM-7B's model card): pre-norm residual blocks,
grouped-query causal attention with rotary positions in the half-rotation
(GPT-NeoX / Hugging Face) layout, and either RMSNorm + SwiGLU without biases
or LayerNorm + a two-matrix GELU MLP with biases. Departures, each noted where
it is made: GELU is its tanh approximation (``assumed`` in the Pharia file).

Weights come in as the cell's own arrays (bf16) in a plain dict and are
upcast one layer at a time inside the jitted layer function, so a float32
copy of the whole model never exists:

    {"embedding": (V, H), "layers": [layer, ...], "final_norm": {...},
     "head": (H, V)}
    layer = {"norm1": {"weight"[, "bias"]}, "norm2": {...},
             "q" | "k" | "v" | "o": {"weight": (in, out)[, "bias"]},
             and "gate" | "up" | "down"  (SwiGLU)  or  "in" | "out"  (GELU)}

``spec`` holds what is not a weight: ``num_heads``, ``num_kv_heads``,
``head_dim``, ``norm`` ("rms" | "layernorm"), ``mlp`` ("swiglu" | "gelu"),
``eps`` and ``rope_base``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _f32(tree):
    return jax.tree.map(lambda x: x.astype(F32), tree)


def norm(x, p, kind: str, eps: float):
    if kind == "rms":
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * p["weight"]
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * p["weight"] + p["bias"]


def linear(x, p):
    y = x @ p["weight"]
    return y + p["bias"] if "bias" in p else y


def rotary(x, positions, base: float):
    """x (s, n, d); rotates the pair (i, i + d/2) by positions * base**(-2i/d)."""
    d = x.shape[-1]
    inv_freq = 1.0 / (base ** (jnp.arange(0, d, 2, dtype=F32) / d))
    angle = positions.astype(F32)[:, None] * inv_freq[None, :]  # (s, d/2)
    cos = jnp.concatenate([jnp.cos(angle)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(angle)] * 2, -1)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def attention(q, k, v):
    """Causal grouped-query attention of one sequence: q (s, n, d), k and v
    (s, n_kv, d); query head i reads KV head i // (n / n_kv)."""
    s, n, d = q.shape
    group = n // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("qnd,knd->nqk", q, k) / jnp.sqrt(F32(d))
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), axis=-1)
    return jnp.einsum("nqk,knd->qnd", probs, v)


@functools.partial(jax.jit, static_argnames=("spec",))
def layer_forward(h, layer, spec):
    """One pre-norm block on one sequence: h (s, H) float32."""
    spec = dict(spec)
    with jax.default_matmul_precision("highest"):
        p = _f32(layer)
        s = h.shape[0]
        n, n_kv, d = spec["num_heads"], spec["num_kv_heads"], spec["head_dim"]
        x = norm(h, p["norm1"], spec["norm"], spec["eps"])
        positions = jnp.arange(s)
        q = rotary(linear(x, p["q"]).reshape(s, n, d), positions, spec["rope_base"])
        k = rotary(linear(x, p["k"]).reshape(s, n_kv, d), positions, spec["rope_base"])
        v = linear(x, p["v"]).reshape(s, n_kv, d)
        h = h + linear(attention(q, k, v).reshape(s, n * d), p["o"])
        x = norm(h, p["norm2"], spec["norm"], spec["eps"])
        if spec["mlp"] == "swiglu":
            y = linear(jax.nn.silu(linear(x, p["gate"])) * linear(x, p["up"]), p["down"])
        else:
            # tanh approximation of GELU: the Scaling codebase's default
            y = linear(jax.nn.gelu(linear(x, p["in"]), approximate=True), p["out"])
        return h + y


@functools.partial(jax.jit, static_argnames=("spec",))
def head_forward(h, final_norm, head, spec):
    spec = dict(spec)
    with jax.default_matmul_precision("highest"):
        x = norm(h, _f32(final_norm), spec["norm"], spec["eps"])
        return x @ head.astype(F32)


def forward(weights, tokens, spec, head_positions=None):
    """Logits (len(head_positions) or s, V) in float32 of one sequence of
    token ids; the positions past the last one asked for may be padding,
    since attention is causal."""
    frozen = tuple(sorted(spec.items()))
    h = weights["embedding"][tokens].astype(F32)
    for layer in weights["layers"]:
        h = layer_forward(h, layer, frozen)
    if head_positions is not None:
        h = h[head_positions]
    return head_forward(h, weights["final_norm"], weights["head"], frozen)


def token_loss(logits, targets):
    """Cross entropy of each position, float32."""
    logz = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, targets[:, None].astype(jnp.int32), -1)[:, 0]
    return logz - picked
