"""Plain reference of the Xing4.0 decoder: DeepSeek-V3's blocks (multi-head
LATENT attention, a dense then sigmoid-routed SwiGLU stack) on a residual path
of ``n`` STREAMS mixed by manifold-constrained hyper-connections (mHC,
arXiv:2512.24880, on hyper-connections, arXiv:2409.19606).

Float32 ``jax.numpy`` under ``default_matmul_precision("highest")``, nothing of
``scaling_tpu``; the sub-layers themselves (latent attention in the EXPANDED
form, YaRN, the dense and the routed FFN) are ``latent_moe_decoder``'s, RMSNorm
and loss ``dense_decoder``'s. Written from the published configuration of
XingChen-AGI/Xing4.0-29B-A4B (``model_type: xing4_0``: ``hc_mult``,
``hc_sinkhorn_iters``, ``hc_eps``, ``mhc_h_res_clamp_min`` / ``_max``) and from
the mHC paper's equations as the configuration file's ``assumed`` words them.
A token's residual is ``X`` in ``R^{n x C}``; layer 0's input is the embedding
in every stream. Every SUB-LAYER ``l`` (each attention and each FFN has a
mapping of its own), per token:

    x      = vec(X)                                        in R^{nC}
    r      = 1 / sqrt(mean(x^2) + eps_norm)                no learned weight
    m      = (x phi_l) r                                   phi_l (nC, n^2 + 2n)
    H_pre  = sigmoid(a_pre m[0:n] + b_pre) + hc_eps                      (n,)
    H_post = 2 sigmoid(a_post m[n:2n] + b_post)                          (n,)
    S      = clip(a_res mat(m[2n:]) + b_res, clamp_min, clamp_max)    (n, n)
    M      = softmax over each row of S, + hc_eps;  M <- M / (column sums + hc_eps)
             then sinkhorn_iters - 1 times:  M <- M / (row sums + hc_eps)
                                             M <- M / (column sums + hc_eps)
    u      = sum_j H_pre[j] X[j]
    y      = F_l(RMSNorm_l(u))           F_l: latent attention | dense | routed FFN
    X'[i]  = sum_j M[i, j] X[j] + H_post[i] y

``mat`` is row-major. After the last block ``h = sum_j (sigmoid(a_h (x phi_h) r
+ b_h) + hc_eps)[j] X[j]``, then one RMSNorm and the untied head. ``M`` is a
plain ``(tokens, n, n)`` array and the Sinkhorn steps a Python loop.

Departures, each under ``assumed`` in the configuration: the first Sinkhorn
step is softmax-then-columns (the paper writes ``T_r(T_c(exp S))``); where
``hc_eps`` enters; the learned readout; and ``latent_moe_decoder``'s (rotary's
half-rotation layout, every held expert on every token).

Weights: ``latent_moe_decoder``'s, with in every layer ``attn_hc`` and
``ffn_hc`` (``{"phi" (nC, n^2 + 2n), "alpha" (3,): a_pre, a_post, a_res,
"bias" (n^2 + 2n,): b_pre, b_post, b_res row-major}``, float32) and at the top
``readout_hc`` (``{"phi" (nC, n), "alpha" (1,), "bias" (n,)}``). ``spec``:
``latent_moe_decoder``'s and ``hc_streams``, ``hc_sinkhorn_iters``, ``hc_eps``,
``hc_clamp`` (min, max).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark.reference.dense_decoder import (  # noqa: F401  (token_loss: the contract)
    F32, _f32, linear, norm, token_loss,
)
from benchmark.reference.latent_moe_decoder import (
    ATTENTION_LEAVES, EXPERT_LEAVES, head_forward, latent_attention, routed_ffn,
)


def scaled_projection(X, phi, eps_norm):
    """``m = (vec(X) phi) r``: X (s, n, C) float32 -> (s, k)."""
    x = X.reshape(X.shape[0], -1)
    r = 1.0 / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps_norm)
    return (x @ phi) * r


def doubly_stochastic(S, iters: int, eps: float):
    """S (s, n, n) -> the Sinkhorn projection after ``iters`` steps."""
    M = jax.nn.softmax(S, axis=-1) + eps
    M = M / (M.sum(axis=-2, keepdims=True) + eps)
    for _ in range(iters - 1):
        M = M / (M.sum(axis=-1, keepdims=True) + eps)
        M = M / (M.sum(axis=-2, keepdims=True) + eps)
    return M


def mapping(X, hc, spec):
    """``(H_pre (s, n), H_post (s, n), H_res (s, n, n))`` of one sub-layer."""
    n = spec["hc_streams"]
    m = scaled_projection(X, hc["phi"], spec["eps"])
    a_pre, a_post, a_res = hc["alpha"]
    b = hc["bias"]
    h_pre = jax.nn.sigmoid(a_pre * m[:, :n] + b[:n]) + spec["hc_eps"]
    h_post = 2.0 * jax.nn.sigmoid(a_post * m[:, n:2 * n] + b[n:2 * n])
    S = a_res * m[:, 2 * n:].reshape(-1, n, n) + b[2 * n:].reshape(n, n)
    S = jnp.clip(S, *spec["hc_clamp"])
    return h_pre, h_post, doubly_stochastic(S, spec["hc_sinkhorn_iters"], spec["hc_eps"])


def hyper_connected(X, hc, spec, branch):
    """``X' = H_res X + H_post branch(sum_j H_pre[j] X[j])``: X (s, n, C)."""
    h_pre, h_post, h_res = mapping(X, hc, spec)
    u = jnp.einsum("sj,sjc->sc", h_pre, X)
    y = branch(u)
    return jnp.einsum("sij,sjc->sic", h_res, X) + h_post[:, :, None] * y[:, None, :]


def readout(X, hc, spec):
    gate = jax.nn.sigmoid(
        hc["alpha"][0] * scaled_projection(X, hc["phi"], spec["eps"]) + hc["bias"])
    return jnp.einsum("sj,sjc->sc", gate + spec["hc_eps"], X)


@functools.partial(jax.jit, static_argnames=("spec",))
def attention_block(X, layer, spec):
    """One attention sub-layer on one sequence: X (s, n, C) float32."""
    spec = dict(spec)
    with jax.default_matmul_precision("highest"):
        p = _f32(layer)
        return hyper_connected(X, p["attn_hc"], spec, lambda u: latent_attention(
            norm(u, p["attn_norm"], "rms", spec["eps"]), p, spec))


@functools.partial(jax.jit, static_argnames=("routed", "spec"))
def ffn_block(X, layer, routed, spec):
    """One FFN sub-layer on one sequence."""
    spec = dict(spec)
    with jax.default_matmul_precision("highest"):
        experts = {name: layer[name] for name in EXPERT_LEAVES if name in layer}
        p = _f32({k: v for k, v in layer.items() if k not in EXPERT_LEAVES})

        def ffn(u):
            x = norm(u, p["ffn_norm"], "rms", spec["eps"])
            if routed:
                return routed_ffn(x, p, experts, spec)
            return linear(jax.nn.silu(linear(x, p["gate"])) * linear(x, p["up"]),
                          p["down"])

        return hyper_connected(X, p["ffn_hc"], spec, ffn)


@functools.partial(jax.jit, static_argnames=("spec",))
def fold(X, hc, spec):
    with jax.default_matmul_precision("highest"):
        return readout(X, _f32(hc), dict(spec))


HC_ATTENTION_LEAVES = ATTENTION_LEAVES + ("attn_hc",)


def forward(weights, tokens, spec, head_positions=None):
    """Logits (len(head_positions) or s, V) in float32 of one sequence of
    token ids; as ``dense_decoder.forward``."""
    frozen = tuple(sorted(spec.items()))
    h = weights["embedding"][tokens].astype(F32)
    X = jnp.broadcast_to(h[:, None, :], (h.shape[0], spec["hc_streams"], h.shape[1]))
    for i, layer in enumerate(weights["layers"]):
        X = attention_block(X, {k: layer[k] for k in HC_ATTENTION_LEAVES}, frozen)
        X = ffn_block(X, {k: v for k, v in layer.items() if k not in HC_ATTENTION_LEAVES},
                      i >= spec["num_dense"], frozen)
    if head_positions is not None:
        X = X[head_positions]
    h = fold(X, weights["readout_hc"], frozen)
    return head_forward(h, weights["final_norm"], weights["head"], spec["eps"])
