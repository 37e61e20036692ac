"""Plain reference of a looped decoder (Ouro-2.6B: ``model_type: ouro``).

Float32 ``jax.numpy`` under ``default_matmul_precision("highest")``: no
kernel, no cache, nothing of ``scaling_tpu``; rotary positions, causal
attention, RMSNorm, the linear map and the loss are ``dense_decoder``'s.
Written from the equations of ISSUE 40 (the row of the catalog, the released
``modeling_ouro.py`` and the paper's "sandwich normalization"). With ``x =
E[tokens]``, for step ``u = 0 .. steps - 1`` and layer ``l = 0 .. L - 1``, the
SAME weights at every ``u``:

    a = Attn_l(RMSNorm(x; g1_l))       rotary on every dimension of a head,
                                       causal, no bias
    x = x + RMSNorm(a; g2_l)           the sub-layer's OUTPUT is normed
    m = Wd_l(silu(Wg_l n) * Wu_l n),   n = RMSNorm(x; g3_l)
    x = x + RMSNorm(m; g4_l)
    after l = L - 1:  x = RMSNorm(x; g_final);  h_u = x   (what step u + 1
                      starts from)
                      lambda_u = sigmoid(w_exit . h_u + b_exit)
    p_0 = lambda_0;  p_u = lambda_u * prod_{j<u}(1 - lambda_j) for u < last;
    p_last = prod_{j<last}(1 - lambda_j)
    logits = W_head h_last             (``early_exit_threshold`` 1: every
                                       token runs every step)

Departures from the released code, each where it is made: the exit
distribution is read but never acted on (at threshold 1 the released code
exits no token early either); the rotary tables are computed in float32 for
the positions at hand (the released code caches them); nothing else.

With ``steps`` 1, no sandwich norms and no gate these are ``dense_decoder``'s
equations (RMSNorm, SwiGLU), which a test holds it to.

Weights as ``dense_decoder``'s (the cell's own bf16 arrays, upcast a layer at
a time inside the jitted layer function, so a float32 copy of the model never
exists: a layer at the published widths is 51 M parameters = 0.2 GB in
float32 beside 5.3 GB of served weights); a layer adds ``"norm_attn_out"``
and ``"norm_mlp_out"`` (``{"weight"}``) when ``spec["sandwich"]``; the tree
adds ``"exit"``: ``{"weight": (H, 1), "bias": (1,)}`` when ``spec["gate"]``.
``spec``: ``num_heads``, ``num_kv_heads``, ``head_dim``, ``eps``,
``rope_base``, ``steps``, ``sandwich``, ``gate``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark.reference.dense_decoder import (  # noqa: F401  (token_loss: the contract)
    F32, _f32, attention, linear, norm, rotary, token_loss,
)


@functools.partial(jax.jit, static_argnames=("spec",))
def layer_forward(h, layer, spec):
    """One block on one sequence: h (s, H) float32."""
    spec = dict(spec)
    with jax.default_matmul_precision("highest"):
        p = _f32(layer)
        s = h.shape[0]
        n, n_kv, d = spec["num_heads"], spec["num_kv_heads"], spec["head_dim"]
        eps = spec["eps"]
        x = norm(h, p["norm1"], "rms", eps)
        positions = jnp.arange(s)  # the same positions at every step
        q = rotary(linear(x, p["q"]).reshape(s, n, d), positions, spec["rope_base"])
        k = rotary(linear(x, p["k"]).reshape(s, n_kv, d), positions, spec["rope_base"])
        v = linear(x, p["v"]).reshape(s, n_kv, d)
        a = linear(attention(q, k, v).reshape(s, n * d), p["o"])
        if spec["sandwich"]:
            a = norm(a, p["norm_attn_out"], "rms", eps)
        h = h + a
        x = norm(h, p["norm2"], "rms", eps)
        m = linear(jax.nn.silu(linear(x, p["gate"])) * linear(x, p["up"]), p["down"])
        if spec["sandwich"]:
            m = norm(m, p["norm_mlp_out"], "rms", eps)
        return h + m


@functools.partial(jax.jit, static_argnames=("eps",))
def final_norm(h, weights, eps):
    return norm(h, _f32(weights), "rms", eps)


@jax.jit
def exit_gate(h, gate):
    """lambda of every position of h (s, H)."""
    with jax.default_matmul_precision("highest"):
        return jax.nn.sigmoid(linear(h, _f32(gate))[:, 0])


def exit_distribution(lambdas):
    """p_u over the steps from their gates ``lambdas`` (a list): the last
    step takes what the earlier ones left, so the p_u sum to 1."""
    stay, p = jnp.ones_like(lambdas[0]), []
    for lam in lambdas[:-1]:
        p.append(lam * stay)
        stay = stay * (1.0 - lam)
    return jnp.stack(p + [stay])


@jax.jit
def head_forward(h, head):
    with jax.default_matmul_precision("highest"):
        return h @ head.astype(F32)


def forward_with_exit(weights, tokens, spec, head_positions=None):
    """``(logits, p)``: the last step's logits as ``forward`` gives them, and
    the exit distribution ``(steps, len(head_positions) or s)`` (None
    without a gate)."""
    frozen = tuple(sorted(spec.items()))
    h = weights["embedding"][tokens].astype(F32)
    lambdas = []
    for _ in range(spec["steps"]):
        for layer in weights["layers"]:          # the SAME weights every step
            h = layer_forward(h, layer, frozen)
        h = final_norm(h, weights["final_norm"], spec["eps"])  # at EVERY step's end
        if spec["gate"]:
            at = h if head_positions is None else h[head_positions]
            lambdas.append(exit_gate(at, weights["exit"]))
    if head_positions is not None:
        h = h[head_positions]
    return (head_forward(h, weights["head"]),
            exit_distribution(lambdas) if lambdas else None)


def forward(weights, tokens, spec, head_positions=None):
    """Logits (len(head_positions) or s, V) in float32 of one sequence of
    token ids; the positions past the last one asked for may be padding,
    since attention is causal."""
    return forward_with_exit(weights, tokens, spec, head_positions)[0]
