"""Plain reference of the Nemotron-H decoder: a stack of single-mixer layers.

Float32 ``jax.numpy`` under ``default_matmul_precision("highest")``, nothing
of ``scaling_tpu``; causal attention, the RMSNorm, the head and the loss are
``dense_decoder``'s. Every layer is ONE mixer: ``x <- x + Mixer_i(RMSNorm_i(x))``;
after the last layer a final RMSNorm, then an untied head. The kinds, as
NVIDIA-Nemotron-3-Nano-30B-A3B's ``hybrid_override_pattern`` names them:

- ``mamba`` (``M``), Mamba-2 (Dao & Gu 2024). ``[z | xBC | dt] = u W_in``
  (``inner | inner + 2 G N | heads`` columns, ``inner = heads x head_dim``);
  ``xBC_t = silu(b_c + sum_{j<K} w_c[:, j] * xBC_{t-K+1+j})``, written as a sum
  of ``K`` shifted products; ``xBC -> x | B | C``, head ``h`` using group ``h //
  (heads / G)``; ``dt = softplus(dt + dt_bias)``; ``A = -exp(A_log)``; ``S_t =
  exp(dt_t A) S_{t-1} + dt_t x_t B_t^T``; ``y_t = S_t C_t + D x_t``: the
  recurrence is a plain ``lax.scan`` over TIME, one position a step; ``y = w_n
  * GroupRMSNorm(y * silu(z))`` (the gate before the norm, ``G`` groups);
  ``out = y W_out``.
- ``moe`` (``E``): ``s = sigmoid(x W_r)`` over all ``num_experts``; the ``top_k``
  experts with the largest ``s_e + b_e`` (``b``: selection bias, for the choice
  only); ``g_e = scale * s_e / (sum of the chosen s + 1e-20)``; ``out = sum_e
  g_e relu(x W_up_e)^2 W_down_e + relu(x W_up_s)^2 W_down_s`` (one shared
  expert, un-gated like the routed ones).
- ``attention`` (``*``): grouped-query causal softmax at ``1 / sqrt(head_dim)``,
  no bias, NO positional embedding, ``W_o``.

**A share of the experts.** The reference is given the same share as the
program (model-configs guide, section 4): ``up`` and ``down`` hold the experts
``[experts_first, experts_first + held)`` only; the router scores all
``num_experts`` and takes its ``top_k``; the gates of absent experts are
dropped, NOT renormalised over those present. With ``shared: False`` in the
spec the shared expert is left out (the test that adds the shares up counts
it once).

Departures: none from those equations. The plain form of the expert sum is
kept: every held expert runs on every token and the unchosen ones are
weighted by zero; the experts are walked in blocks of ``EXPERT_BLOCK``, each
upcast as it is used, so that a layer at the published widths (64 experts of
2 x 2688 x 1856: 2.6 GB in float32) fits on the chip beside the served weights.

Weights: ``{"embedding": (V, H), "layers": [layer, ...], "final_norm",
"head": (H, V)}``; a layer is ``{"norm": {"weight"}, ...}`` with, by kind,
``mamba``: ``in_proj (H, inner + conv_dim + heads)``, ``conv_w (conv_dim, K)``,
``conv_b``, ``dt_bias``, ``A_log``, ``D`` (heads,), ``gate_norm (inner,)``,
``out_proj (inner, H)``; ``moe``: ``router (H, E)``, ``router_bias (E,)``,
``up (held, H, F)``, ``down (held, F, H)``, ``shared_up (H, Fs)``,
``shared_down (Fs, H)``; ``attention``: ``q``, ``k``, ``v``, ``o``
(``{"weight"}``). ``spec``: ``pattern`` (a tuple of kinds), ``num_heads``,
``num_kv_heads``, ``head_dim``, ``eps``, ``mamba_heads``, ``mamba_head_dim``,
``state``, ``groups``, ``top_k``, ``scale``, ``experts_first``, ``shared``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark.reference.dense_decoder import (  # noqa: F401  (token_loss: the contract)
    F32, _f32, attention, head_forward, linear, norm, token_loss,
)

EXPERT_BLOCK = 8
EXPERT_LEAVES = ("up", "down")


def relu2(x):
    return jnp.square(jax.nn.relu(x))


def mamba_mixer(u, p, spec):
    """u (s, H) float32, one sequence from a zero state."""
    s = u.shape[0]
    heads, P, N, G = (spec["mamba_heads"], spec["mamba_head_dim"], spec["state"],
                      spec["groups"])
    inner = heads * P
    conv_dim = inner + 2 * G * N
    proj = u @ p["in_proj"]
    z, xBC, dt = proj[:, :inner], proj[:, inner:inner + conv_dim], proj[:, inner + conv_dim:]
    K = p["conv_w"].shape[1]
    padded = jnp.concatenate([jnp.zeros((K - 1, conv_dim), F32), xBC])
    xBC = jax.nn.silu(p["conv_b"] + sum(
        padded[j:j + s] * p["conv_w"][:, j] for j in range(K)))
    x = xBC[:, :inner].reshape(s, heads, P)
    B = jnp.repeat(xBC[:, inner:inner + G * N].reshape(s, G, N), heads // G, axis=1)
    C = jnp.repeat(xBC[:, inner + G * N:].reshape(s, G, N), heads // G, axis=1)
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


def routed_mlp(x, p, experts, spec):
    """x (s, H) float32; ``experts``: the held experts' two stacked leaves in
    the dtype they came in. Every held expert on every token, weighted by the
    token's gate for it (zero for the experts it did not choose)."""
    s = x.shape[0]
    scores = jax.nn.sigmoid(x @ p["router"])                      # (s, E)
    _, idx = jax.lax.top_k(scores + p["router_bias"], spec["top_k"])
    chosen = jnp.take_along_axis(scores, idx, axis=-1)
    gates = spec["scale"] * chosen / (chosen.sum(-1, keepdims=True) + 1e-20)
    weight = jnp.zeros_like(scores).at[jnp.arange(s)[:, None], idx].set(gates)
    held = experts["up"].shape[0]
    first = spec["experts_first"]
    weight = weight[:, first:first + held]        # absent experts: dropped
    block = min(EXPERT_BLOCK, held)
    assert held % block == 0, (held, block)

    def blocks(a):
        return a.reshape(held // block, block, *a.shape[1:])

    def add_block(y, part):
        up, down, w = part                                         # w: (block, s)
        hidden = relu2(jnp.einsum("sh,ehf->esf", x, up.astype(F32)))
        return y + jnp.einsum("esf,efh->sh", hidden * w[:, :, None],
                              down.astype(F32)), None

    y, _ = jax.lax.scan(add_block, jnp.zeros_like(x), (
        blocks(experts["up"]), blocks(experts["down"]), blocks(weight.T)))
    if spec["shared"]:
        y = y + relu2(x @ p["shared_up"]) @ p["shared_down"]
    return y


def attention_mixer(x, p, spec):
    s = x.shape[0]
    n, n_kv, d = spec["num_heads"], spec["num_kv_heads"], spec["head_dim"]
    q = linear(x, p["q"]).reshape(s, n, d)        # no positional embedding
    k = linear(x, p["k"]).reshape(s, n_kv, d)
    v = linear(x, p["v"]).reshape(s, n_kv, d)
    return linear(attention(q, k, v).reshape(s, n * d), p["o"])


@functools.partial(jax.jit, static_argnames=("kind", "spec"))
def layer_forward(h, layer, kind, spec):
    """One single-mixer block on one sequence: h (s, H) float32."""
    spec = dict(spec)
    with jax.default_matmul_precision("highest"):
        experts = {name: layer[name] for name in EXPERT_LEAVES if name in layer}
        p = _f32({k: v for k, v in layer.items() if k not in EXPERT_LEAVES})
        x = norm(h, p["norm"], "rms", spec["eps"])
        if kind == "mamba":
            return h + mamba_mixer(x, p, spec)
        if kind == "moe":
            return h + routed_mlp(x, p, experts, spec)
        assert kind == "attention", kind
        return h + attention_mixer(x, p, spec)


def forward(weights, tokens, spec, head_positions=None):
    """Logits (len(head_positions) or s, V) in float32 of one sequence of
    token ids; as ``dense_decoder.forward``."""
    frozen = tuple(sorted((k, v) for k, v in spec.items() if k != "pattern"))
    head_spec = tuple(sorted({"norm": "rms", "eps": spec["eps"]}.items()))
    h = weights["embedding"][tokens].astype(F32)
    for kind, layer in zip(spec["pattern"], weights["layers"]):
        h = layer_forward(h, layer, kind, frozen)
    if head_positions is not None:
        h = h[head_positions]
    return head_forward(h, weights["final_norm"], weights["head"], head_spec)
