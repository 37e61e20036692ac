"""A reference a later PR could add: a decoder whose MLP is routed.

Plain float32 ``jax.numpy``, nothing of ``scaling_tpu``; the attention half
of the block is ``dense_decoder``'s. The routed half follows what
``scaling_tpu/nn/moe.py`` computes by default, which is a harness proof and
not a published model: the reference of one, OLMoE's, with its gates as the
softmax leaves them, is ``benchmark/reference/moe_decoder.py`` (PR 28).
Departures from it, each where it is made: the top-k gate weights are
renormalised to sum to one (OLMoE's ``norm_topk_prob`` is false; the program's
``moe_norm_topk_prob`` defaults to true); there is no capacity here, so the
configuration must give the program one that drops nothing; the load-balance
term is left out of the loss (the configuration sets its coefficient to 0).

Weights as ``dense_decoder``'s, a layer's MLP being ``"router": (H, E)``,
``"w_gate"`` and ``"w_in"``: (E, H, F), ``"w_out"``: (E, F, H). ``spec`` adds
``top_k``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark.reference.dense_decoder import (  # noqa: F401  (token_loss: the contract)
    F32, _f32, attention, head_forward, linear, norm, rotary, token_loss,
)


def routed_mlp(x, p, top_k: int):
    """x (s, H): every expert on every token, then the top k of each token
    weighted by their renormalised router probabilities: the plain form of
    a sum over the k experts a token uses."""
    probs = jax.nn.softmax(x @ p["router"], axis=-1)              # (s, E)
    gate_vals, gate_idx = jax.lax.top_k(probs, top_k)
    # departure: renormalised over the chosen k, nn/moe.py's default
    gate_vals = gate_vals / gate_vals.sum(axis=-1, keepdims=True)
    weight = jnp.zeros_like(probs).at[
        jnp.arange(x.shape[0])[:, None], gate_idx].set(gate_vals)  # (s, E)
    hidden = jax.nn.silu(jnp.einsum("sh,ehf->esf", x, p["w_gate"])) * jnp.einsum(
        "sh,ehf->esf", x, p["w_in"])
    return jnp.einsum("se,esh->sh", weight, jnp.einsum("esf,efh->esh", hidden, p["w_out"]))


@functools.partial(jax.jit, static_argnames=("spec",))
def layer_forward(h, layer, spec):
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
        return h + routed_mlp(x, p, spec["top_k"])


def forward(weights, tokens, spec, head_positions=None):
    frozen = tuple(sorted(spec.items()))
    h = weights["embedding"][tokens].astype(F32)
    for layer in weights["layers"]:
        h = layer_forward(h, layer, frozen)
    if head_positions is not None:
        h = h[head_positions]
    return head_forward(h, weights["final_norm"], weights["head"], frozen)
