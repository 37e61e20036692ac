"""Plain reference of Qwen3-Next's decoder: gated delta-rule layers three to
every gated full-attention layer, each followed by softmax-routed SwiGLU
experts with a shared expert behind a gate of its own, one rank's share held.

Float32 ``jax.numpy`` under ``default_matmul_precision("highest")``, no cache,
no kernel, nothing of ``scaling_tpu``; linear and loss are ``dense_decoder``'s.
Written from the catalog's row of Qwen/Qwen3-Next-80B-A3B-Instruct and the
family's released modelling code (``modeling_qwen3_next.py``; the delta rule:
arXiv:2412.06464). Every layer is TWO pre-norm sub-blocks:

    h <- h + Mixer_l(N1(h)),  h <- h + MoE_l(N1(h)),
    N1(x; w) = x rsqrt(mean x^2 + eps) (1 + w)     (the weight an OFFSET from one)

- gated delta rule (``nk`` key heads, ``nv`` value heads, ``dk``, ``dv``):
  ``qkvz = x W_qkvz`` with the columns ordered BY KEY HEAD as the released
  checkpoint has them (for each key head: its q, its k, its ``nv / nk`` value
  heads' v, their z), ``ba = x W_ba`` likewise (b then a of a key head's value
  heads); ``c = silu(causal depthwise conv_K([q | k | v]))`` over the flat
  channels, no bias; ``beta = sigmoid(b)``, ``g = -exp(A_log) softplus(a +
  dt_bias)``; ``q <- q rsqrt(sum q^2 + 1e-6) dk^-0.5``, ``k <- k rsqrt(sum k^2
  + 1e-6)``; value head ``j`` uses key head ``j // (nv / nk)``; from a zero
  state ``S`` (dk x dv a head), position by position (a ``lax.scan``: the
  STEP, never a chunk form):
  ``S~ = exp(g_t) S``, ``u = beta_t (v_t - S~^T k_t)``, ``S = S~ + k_t u^T``,
  ``o_t = S^T q_t``; ``y = w_n (o rsqrt(mean o^2 + eps)) silu(z)`` over each
  head's ``dv`` lanes (a plain weight), then ``W_out``.
- gated attention (``n`` heads over ``n_kv``, ``head_dim``): ``[q | gate] = x
  W_q`` head by head, ``k``, ``v``; ``q <- N1(q; w_q)``, ``k <- N1(k; w_k)``
  over a head's lanes; rotary (lane ``i`` with lane ``i + dims / 2``) on the
  first ``rope_dims`` lanes; causal softmax at ``head_dim^-0.5``,
  ``QUERY_BLOCK`` queries at a time; ``o <- o sigmoid(gate)`` a lane; ``W_o``.
- routed MLP: ``p = softmax(x W_r)`` over ALL experts; the ``top_k`` largest,
  renormalised to sum to one; expert ``(silu(x W_g) (x W_u)) W_d``; the shared
  expert, the same form, times ``sigmoid(x w_s)``; the sum.
- one ``N1`` after the last layer, then an untied head.

Departures from the published code, each a statement of the configuration
file: the experts HELD here are ``[experts_first, experts_first + held)`` and
an assignment to an absent expert is left out AFTER the renormalisation over
the ten (``shared`` False leaves the shared expert out: the test that adds
the ranks' shares counts it once); the multi-token-prediction module is not
part of the model computed.

Weights: ``embedding`` (V, H); ``layers``, each ``mixer_norm``, ``ffn_norm``
(``{"weight"}``), and delta: ``qkvz`` (H, 2 nk dk + 2 nv dv), ``ba`` (H, 2
nv), ``conv`` (2 nk dk + nv dv, K), ``A_log``, ``dt_bias`` (nv,),
``gated_norm`` (dv,), ``out`` (nv dv, H); or attention: ``q`` (H, n 2
head_dim), ``k``, ``v``, ``o``, ``q_norm``, ``k_norm`` (head_dim,); then
``router`` (H, E), ``w_gate``, ``w_up`` (held, H, F), ``w_down`` (held, F, H),
``shared_gate``, ``shared_up`` (H, Fs), ``shared_down`` (Fs, H),
``shared_scale`` (H, 1); ``final_norm``; ``head`` (H, V). ``spec``: ``kinds``
(a tuple, ``"delta"`` | ``"attention"`` a layer), ``num_heads``,
``num_kv_heads``, ``head_dim``, ``rope_dims``, ``rope_base``, ``eps``,
``delta`` ((nk, nv, dk, dv)), ``top_k``, ``experts_first``, ``shared``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark.reference.dense_decoder import (  # noqa: F401  (token_loss: the contract)
    F32, _f32, token_loss,
)

QUERY_BLOCK = 128
EXPERT_BLOCK = 8
L2_EPS = 1e-6
EXPERT_LEAVES = ("w_gate", "w_up", "w_down")
SHARED_LEAVES = ("shared_gate", "shared_up", "shared_down", "shared_scale")
FFN_LEAVES = ("ffn_norm", "router") + EXPERT_LEAVES + SHARED_LEAVES


def norm1(x, weight, eps):
    """RMSNorm whose weight is an offset from one."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1.0 + weight)


# ------------------------------------------------------------ gated delta rule
def split_by_key_head(qkvz, ba, delta):
    """The released order undone: ``qkvz`` (s, nk (2 dk + 2 per dv)) and ``ba``
    (s, nk 2 per) to ``q``, ``k`` (s, nk, dk), ``v``, ``z`` (s, nv, dv), ``b``,
    ``a`` (s, nv)."""
    nk, nv, dk, dv = delta
    per = nv // nk
    s = qkvz.shape[0]
    qkvz = qkvz.reshape(s, nk, 2 * dk + 2 * per * dv)
    q, k = qkvz[..., :dk], qkvz[..., dk:2 * dk]
    v = qkvz[..., 2 * dk:2 * dk + per * dv].reshape(s, nv, dv)
    z = qkvz[..., 2 * dk + per * dv:].reshape(s, nv, dv)
    ba = ba.reshape(s, nk, 2 * per)
    return q, k, v, z, ba[..., :per].reshape(s, nv), ba[..., per:].reshape(s, nv)


def causal_conv(x, weight):
    """x (s, c), weight (c, K): silu of the depthwise causal convolution, the
    ``K - 1`` inputs before the sequence zeros."""
    K = weight.shape[1]
    s = x.shape[0]
    padded = jnp.pad(x, ((K - 1, 0), (0, 0)))
    out = sum(padded[j:j + s] * weight[:, j] for j in range(K))
    return jax.nn.silu(out)


def delta_rule(q, k, v, g, beta):
    """The recurrence, position by position from a zero state: q, k (s, nv,
    dk) normalised, v (s, nv, dv), g and beta (s, nv). Returns (s, nv, dv)."""
    nv, dk = q.shape[1:]
    dv = v.shape[2]

    def step(S, at):
        q_t, k_t, v_t, g_t, beta_t = at
        S = S * jnp.exp(g_t)[:, None, None]
        u = beta_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", S, k_t))
        S = S + k_t[:, :, None] * u[:, None, :]
        return S, jnp.einsum("hkv,hk->hv", S, q_t)

    _, o = jax.lax.scan(step, jnp.zeros((nv, dk, dv), F32), (q, k, v, g, beta))
    return o


def delta_parts(x, p, spec):
    """x (s, H) float32, the layer's normed input: the mixer's output."""
    nk, nv, dk, dv = spec["delta"]
    s = x.shape[0]
    q, k, v, z, b, a = split_by_key_head(x @ p["qkvz"], x @ p["ba"], spec["delta"])
    mixed = jnp.concatenate(
        [q.reshape(s, -1), k.reshape(s, -1), v.reshape(s, -1)], -1)
    mixed = causal_conv(mixed, p["conv"])
    q = mixed[:, :nk * dk].reshape(s, nk, dk)
    k = mixed[:, nk * dk:2 * nk * dk].reshape(s, nk, dk)
    v = mixed[:, 2 * nk * dk:].reshape(s, nv, dv)
    beta = jax.nn.sigmoid(b)
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(a + p["dt_bias"])
    q = q * jax.lax.rsqrt(jnp.sum(q * q, -1, keepdims=True) + L2_EPS) * dk ** -0.5
    k = k * jax.lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + L2_EPS)
    q, k = (jnp.repeat(t, nv // nk, axis=1) for t in (q, k))
    o = delta_rule(q, k, v, g, beta)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + spec["eps"])
    y = p["gated_norm"] * o * jax.nn.silu(z)
    return y.reshape(s, nv * dv) @ p["out"]


# ------------------------------------------------------------- gated attention
def rotary(x, positions, base: float, dims: int):
    """x (s, n, d): the first ``dims`` lanes of every head turned, lane ``i``
    with lane ``i + dims / 2``; the rest pass."""
    inv_freq = 1.0 / (base ** (jnp.arange(0, dims, 2, dtype=F32) / dims))
    angle = positions.astype(F32)[:, None] * inv_freq[None, :]
    cos = jnp.concatenate([jnp.cos(angle)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(angle)] * 2, -1)[:, None, :]
    turned, rest = x[..., :dims], x[..., dims:]
    x1, x2 = turned[..., : dims // 2], turned[..., dims // 2:]
    turned = turned * cos + jnp.concatenate([-x2, x1], -1) * sin
    return jnp.concatenate([turned, rest], -1)


def attention(q, k, v):
    """q (s, n, d), k and v (s, n_kv, d): the causal softmax of every head,
    ``QUERY_BLOCK`` queries at a time against all keys."""
    s, n, d = q.shape
    group = n // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    block = min(QUERY_BLOCK, s)
    pad = -s % block
    q = jnp.pad(q, ((0, pad), (0, 0), (0, 0)))
    keys = jnp.arange(s)

    def one(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, 0)
        seen = keys[None, :] <= (start + jnp.arange(block))[:, None]
        scores = jnp.einsum("qnd,knd->nqk", qb, k) / jnp.sqrt(F32(d))
        probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("nqk,knd->qnd", probs, v)

    out = jax.lax.map(one, jnp.arange(0, s + pad, block))
    return out.reshape(s + pad, n, d)[:s]


def attention_parts(x, p, spec, gated: bool = True):
    """x (s, H) float32, the layer's normed input: the mixer's output."""
    s = x.shape[0]
    n, n_kv, d = spec["num_heads"], spec["num_kv_heads"], spec["head_dim"]
    qg = (x @ p["q"]).reshape(s, n, 2 * d)
    q, gate = qg[..., :d], qg[..., d:]
    k = (x @ p["k"]).reshape(s, n_kv, d)
    v = (x @ p["v"]).reshape(s, n_kv, d)
    q, k = norm1(q, p["q_norm"], spec["eps"]), norm1(k, p["k_norm"], spec["eps"])
    positions = jnp.arange(s)
    q = rotary(q, positions, spec["rope_base"], spec["rope_dims"])
    k = rotary(k, positions, spec["rope_base"], spec["rope_dims"])
    out = attention(q, k, v)
    if gated:
        out = out * jax.nn.sigmoid(gate)
    return out.reshape(s, n * d) @ p["o"]


# ------------------------------------------------------------------ routed MLP
def swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def shared_expert(x, p):
    y = swiglu(x, p["shared_gate"], p["shared_up"], p["shared_down"])
    return y * jax.nn.sigmoid(x @ p["shared_scale"])


def routed_ffn(x, p, experts, spec):
    """x (s, H) float32; ``experts``: the three stacked leaves of the experts
    HELD here, in the dtype they came in."""
    s = x.shape[0]
    probs = jax.nn.softmax(x @ p["router"], axis=-1)               # (s, E), float32
    chosen, idx = jax.lax.top_k(probs, spec["top_k"])
    gates = chosen / chosen.sum(-1, keepdims=True)
    weight = jnp.zeros_like(probs).at[jnp.arange(s)[:, None], idx].set(gates)
    held = experts["w_up"].shape[0]
    first = spec["experts_first"]
    weight = weight[:, first:first + held]        # absent experts: left out
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
        y = y + shared_expert(x, p)
    return y


# ---------------------------------------------------------------------- blocks
@functools.partial(jax.jit, static_argnames=("kind", "spec"))
def mixer_block(h, layer, kind, spec):
    """h <- h + Mixer(N1(h)) on one sequence: h (s, H) float32."""
    spec = dict(spec)
    with jax.default_matmul_precision("highest"):
        p = _f32(layer)
        x = norm1(h, p["mixer_norm"]["weight"], spec["eps"])
        parts = delta_parts if kind == "delta" else attention_parts
        return h + parts(x, p, spec)


@functools.partial(jax.jit, static_argnames=("spec",))
def ffn_block(h, layer, spec):
    """h <- h + MoE(N1(h)) on one sequence."""
    spec = dict(spec)
    with jax.default_matmul_precision("highest"):
        x = norm1(h, layer["ffn_norm"]["weight"].astype(F32), spec["eps"])
        experts = {name: layer[name] for name in EXPERT_LEAVES}
        p = _f32({k: layer[k] for k in ("router",) + SHARED_LEAVES})
        return h + routed_ffn(x, p, experts, spec)


@functools.partial(jax.jit, static_argnames=("eps",))
def head_forward(h, final_norm, head, eps):
    with jax.default_matmul_precision("highest"):
        return norm1(h, final_norm["weight"].astype(F32), eps) @ head.astype(F32)


def forward(weights, tokens, spec, head_positions=None):
    """Logits (len(head_positions) or s, V) in float32 of one sequence of
    token ids; as ``dense_decoder.forward``."""
    frozen = tuple(sorted(spec.items()))
    h = weights["embedding"][tokens].astype(F32)
    for layer, kind in zip(weights["layers"], spec["kinds"], strict=True):
        h = mixer_block(
            h, {k: v for k, v in layer.items() if k not in FFN_LEAVES}, kind, frozen)
        h = ffn_block(h, {k: layer[k] for k in FFN_LEAVES}, frozen)
    if head_positions is not None:
        h = h[head_positions]
    return head_forward(h, weights["final_norm"], weights["head"], spec["eps"])
