"""Plain reference of the Falcon-H1 decoder: a block whose Mamba-2 mixer and
grouped-query attention run SIDE BY SIDE on one normed input and are summed.

Float32 ``jax.numpy`` under ``default_matmul_precision("highest")``, nothing
of ``scaling_tpu``; the RMSNorm, rotary, causal attention and the loss are
``dense_decoder``'s. Written from the equations of tiiuae/Falcon-H1-34B-Instruct
(its ``config.json`` gives every constant; WHERE each multiplier applies is the
released ``modeling_falcon_h1.py``'s, listed under ``assumed`` in the
configuration file). With the constants ``e`` (embedding), ``l`` (head),
``a_in`` / ``a_out`` (attention), ``k_m`` (keys), ``s_in`` / ``s_out`` (SSM),
``m = (m_z, m_x, m_B, m_C, m_dt)``, ``g_m`` / ``d_m`` (MLP):

    h_0 = e * Embed(tokens)
    u   = RMSNorm_in(h)                                 one norm, BOTH mixers read it
    h  <- h + s_out * SSM(u) + a_out * Attn(a_in * u)   summed, one residual
    h  <- h + MLP(RMSNorm_ff(h))
    logits = l * (RMSNorm_final(h_L) W_head)            untied head

- ``Attn(x)``: ``q = x W_q``, ``k = k_m * (x W_k)``, ``v = x W_v``; rotary over
  the whole head on q and k (half-rotation layout); causal grouped-query
  softmax at ``1 / sqrt(head_dim)``; ``W_o``. No bias.
- ``SSM(x)``, Mamba-2 (Dao & Gu 2024): ``p = (s_in * x) W_in``, ``[z | x' | B |
  C | dt] = p * [m_z | m_x | m_B | m_C | m_dt]`` by segment (``inner | inner |
  G N | G N | heads`` columns, ``inner = heads x head_dim``); ``[x' | B | C]
  <- silu(conv1d_K([x' | B | C]) + b)``, depthwise causal, written as a sum of
  ``K`` shifted products; ``dt = softplus(dt + dt_bias)``; ``A = -exp(A_log)``;
  ``S_t = exp(dt_t A) S_{t-1} + dt_t x'_t B_t^T`` (head ``h`` reads group ``h //
  (heads / G)``), ``y_t = S_t C_t + D x'_t``: a plain ``lax.scan`` over TIME,
  one position a step (the released kernel's chunks of 128 are no part of the
  mathematics); ``out = (w * GroupRMSNorm_G(y * silu(z))) W_out``: the gate
  BEFORE the norm (``mamba_norm_before_gate`` false), ``G`` groups.
- ``MLP(x) = d_m * ((silu(g_m * (x W_gate)) * (x W_up)) W_down)``.

Departures from the published description: none in the equations. Two in how
they are evaluated, so that a pass at the published widths (MLP 21,504, a head
of 261,120 columns: 5.35 GB in float32) fits on the chip beside the served
weights: each MLP runs in ``MLP_BLOCKS`` blocks of its columns, each block's
three matrices upcast as they are used and the partial outputs summed; the
head in ``HEAD_BLOCKS`` blocks of vocabulary columns, concatenated.

Weights: ``{"embedding": (V, H), "layers": [layer, ...], "final_norm",
"head": (H, V)}``; a layer is ``{"norm1", "norm2": {"weight"}; "q", "k", "v",
"o": {"weight"}; "in_proj" (H, 2 inner + 2 G N + heads), "conv_w" (conv_dim,
K), "conv_b", "dt_bias", "A_log", "D" (heads,), "gate_norm" (inner,),
"out_proj" (inner, H); "gate", "up" (H, F), "down" (F, H)}``. ``spec``:
``num_heads``, ``num_kv_heads``, ``head_dim``, ``eps``, ``rope_base``,
``mamba_heads``, ``mamba_head_dim``, ``state``, ``groups``, and the constants
``e``, ``l``, ``a_in``, ``a_out``, ``k_m``, ``s_in``, ``s_out``, ``ssm_m`` (a
5-tuple), ``g_m``, ``d_m``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark.reference.dense_decoder import (  # noqa: F401  (token_loss: the contract)
    F32, _f32, attention, linear, norm, rotary, token_loss,
)

MLP_BLOCKS = 8
HEAD_BLOCKS = 8
MLP_LEAVES = ("gate", "up", "down")


def ssm_mixer(u, p, spec):
    """u (s, H) float32, one sequence from a zero state."""
    s = u.shape[0]
    heads, P, N, G = (spec["mamba_heads"], spec["mamba_head_dim"], spec["state"],
                      spec["groups"])
    inner, GN = heads * P, G * N
    m_z, m_x, m_B, m_C, m_dt = spec["ssm_m"]
    proj = (spec["s_in"] * u) @ p["in_proj"]
    z = m_z * proj[:, :inner]
    xBC = jnp.concatenate([
        m_x * proj[:, inner:2 * inner],
        m_B * proj[:, 2 * inner:2 * inner + GN],
        m_C * proj[:, 2 * inner + GN:2 * inner + 2 * GN]], axis=-1)
    dt = m_dt * proj[:, 2 * inner + 2 * GN:]
    K = p["conv_w"].shape[1]
    padded = jnp.concatenate([jnp.zeros((K - 1, xBC.shape[1]), F32), xBC])
    xBC = jax.nn.silu(p["conv_b"] + sum(
        padded[j:j + s] * p["conv_w"][:, j] for j in range(K)))
    x = xBC[:, :inner].reshape(s, heads, P)
    B = jnp.repeat(xBC[:, inner:inner + GN].reshape(s, G, N), heads // G, axis=1)
    C = jnp.repeat(xBC[:, inner + GN:].reshape(s, G, N), heads // G, axis=1)
    dt = jax.nn.softplus(dt + p["dt_bias"])                       # (s, heads)
    A = -jnp.exp(p["A_log"])

    def step(S, t):
        x_t, B_t, C_t, dt_t = t                                   # (heads, ..)
        S = jnp.exp(dt_t * A)[:, None, None] * S + (
            dt_t[:, None, None] * x_t[:, :, None] * B_t[:, None, :])
        return S, jnp.einsum("hpn,hn->hp", S, C_t)

    _, y = jax.lax.scan(step, jnp.zeros((heads, P, N), F32), (x, B, C, dt))
    y = (y + p["D"][:, None] * x).reshape(s, inner)
    g = (y * jax.nn.silu(z)).reshape(s, G, inner // G)
    g = g * jax.lax.rsqrt(jnp.mean(g * g, -1, keepdims=True) + spec["eps"])
    return (g.reshape(s, inner) * p["gate_norm"]) @ p["out_proj"]


def attention_mixer(x, p, spec):
    s = x.shape[0]
    n, n_kv, d = spec["num_heads"], spec["num_kv_heads"], spec["head_dim"]
    positions = jnp.arange(s)
    q = rotary(linear(x, p["q"]).reshape(s, n, d), positions, spec["rope_base"])
    k = spec["k_m"] * linear(x, p["k"])           # before rotary
    k = rotary(k.reshape(s, n_kv, d), positions, spec["rope_base"])
    v = linear(x, p["v"]).reshape(s, n_kv, d)
    return linear(attention(q, k, v).reshape(s, n * d), p["o"])


def mlp(x, gate, up, down, spec):
    """``gate``, ``up`` (H, F) and ``down`` (F, H) in the dtype they came in:
    ``MLP_BLOCKS`` blocks of the ``F`` columns, each upcast as it is used."""
    F = gate.shape[1]
    blocks = MLP_BLOCKS if F % MLP_BLOCKS == 0 else 1

    def columns(w):                    # (H, F) -> (blocks, H, F / blocks)
        return jnp.moveaxis(w.reshape(w.shape[0], blocks, F // blocks), 1, 0)

    def add_block(y, part):
        g, u, d = (w.astype(F32) for w in part)
        hidden = jax.nn.silu(spec["g_m"] * (x @ g)) * (x @ u)
        return y + hidden @ d, None

    y, _ = jax.lax.scan(add_block, jnp.zeros_like(x), (
        columns(gate), columns(up), down.reshape(blocks, F // blocks, -1)))
    return spec["d_m"] * y


@functools.partial(jax.jit, static_argnames=("spec",))
def layer_forward(h, layer, spec):
    """One parallel block on one sequence: h (s, H) float32."""
    spec = dict(spec)
    with jax.default_matmul_precision("highest"):
        p = _f32({k: v for k, v in layer.items() if k not in MLP_LEAVES})
        u = norm(h, p["norm1"], "rms", spec["eps"])
        h = h + spec["s_out"] * ssm_mixer(u, p, spec) + (
            spec["a_out"] * attention_mixer(spec["a_in"] * u, p, spec))
        x = norm(h, p["norm2"], "rms", spec["eps"])
        return h + mlp(x, *(layer[name] for name in MLP_LEAVES), spec)


@functools.partial(jax.jit, static_argnames=("spec", "block", "blocks"))
def head_block(h, final_norm, head, spec, block, blocks):
    """The logits of vocabulary columns ``[block, block + 1) x V / blocks``."""
    spec = dict(spec)
    with jax.default_matmul_precision("highest"):
        x = norm(h, _f32(final_norm), "rms", spec["eps"])
        width = head.shape[1] // blocks
        columns = jax.lax.slice_in_dim(head, block * width, (block + 1) * width,
                                       axis=1)
        return spec["l"] * (x @ columns.astype(F32))


def forward(weights, tokens, spec, head_positions=None):
    """Logits (len(head_positions) or s, V) in float32 of one sequence of
    token ids; as ``dense_decoder.forward``."""
    frozen = tuple(sorted(spec.items()))
    h = spec["e"] * weights["embedding"][tokens].astype(F32)
    for layer in weights["layers"]:
        h = layer_forward(h, layer, frozen)
    if head_positions is not None:
        h = h[head_positions]
    head = weights["head"]
    blocks = HEAD_BLOCKS if head.shape[1] % HEAD_BLOCKS == 0 else 1
    return jnp.concatenate([
        head_block(h, weights["final_norm"], head, frozen, block, blocks)
        for block in range(blocks)], axis=-1)
