"""Plain reference of OLMoE's decoder: routed SwiGLU experts, QK-norm.

Float32 ``jax.numpy`` under ``default_matmul_precision("highest")``, nothing
of ``scaling_tpu``; rotary positions, causal attention, the norms, the head
and the loss are ``dense_decoder``'s. The layer equations are those of
``transformers``' ``modeling_olmoe.py`` (OLMoE-1B-7B-0125-Instruct):

- pre-norm block: ``h = x + Attn(RMSNorm(x))``, ``y = h + MoE(RMSNorm(h))``;
  final RMSNorm; untied head;
- attention: ``q = RMSNorm_q(W_q x)``, ``k = RMSNorm_k(W_k x)``, ``v = W_v x``,
  each of the two norms over the WHOLE projection (one learned weight of its
  full width) before the split into heads; then rotary on every dimension of
  each head, causal softmax, ``W_o``;
- routed MLP: ``p = softmax(W_r x)`` in float32 over ALL experts; the
  ``top_k`` largest ``p_e`` are the gates as they are, NOT renormalised
  (``norm_topk_prob`` false); ``MoE(x) = sum over them of p_e *
  W_down_e(silu(W_gate_e x) * W_up_e x)``. No capacity: nothing is dropped.

Departures: none from those equations. The plain form of the sum is kept:
every expert runs on every token and the unchosen ones are weighted by zero;
the experts are walked in blocks of ``EXPERT_BLOCK``, each upcast as it is
used, so that a layer at the published widths (64 experts of 3 x 2048 x 1024:
1.6 GB in float32) fits on the chip beside the served weights.

Weights as ``dense_decoder``'s; a layer adds ``"q_norm"`` and ``"k_norm"``
(``{"weight"}`` over the whole q and k projection), and its MLP is ``"router":
(H, E)``, ``"gate"`` and ``"up"``: (E, H, F), ``"down"``: (E, F, H). ``spec``:
``num_heads``, ``num_kv_heads``, ``head_dim``, ``eps``, ``rope_base``, ``top_k``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark.reference.dense_decoder import (  # noqa: F401  (token_loss: the contract)
    F32, _f32, attention, head_forward, linear, norm, rotary, token_loss,
)

EXPERT_BLOCK = 8
EXPERT_LEAVES = ("gate", "up", "down")


def routed_mlp(x, router, experts, top_k: int):
    """x (s, H) float32; ``experts``: the three stacked leaves in the dtype
    they came in. Every expert on every token, weighted by the token's gate
    for it (zero for the experts it did not choose)."""
    s = x.shape[0]
    probs = jax.nn.softmax(x @ router, axis=-1)                    # (s, E), float32
    gate_vals, gate_idx = jax.lax.top_k(probs, top_k)              # as they are
    weight = jnp.zeros_like(probs).at[jnp.arange(s)[:, None], gate_idx].set(gate_vals)
    num_experts = router.shape[1]
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


@functools.partial(jax.jit, static_argnames=("spec",))
def layer_forward(h, layer, spec):
    """One block on one sequence: h (s, H) float32."""
    spec = dict(spec)
    with jax.default_matmul_precision("highest"):
        experts = {name: layer[name] for name in EXPERT_LEAVES}
        p = _f32({k: v for k, v in layer.items() if k not in EXPERT_LEAVES})
        s = h.shape[0]
        n, n_kv, d = spec["num_heads"], spec["num_kv_heads"], spec["head_dim"]
        x = norm(h, p["norm1"], "rms", spec["eps"])
        positions = jnp.arange(s)
        # the norm sees the whole projection, all heads at once
        q = norm(linear(x, p["q"]), p["q_norm"], "rms", spec["eps"])
        k = norm(linear(x, p["k"]), p["k_norm"], "rms", spec["eps"])
        q = rotary(q.reshape(s, n, d), positions, spec["rope_base"])
        k = rotary(k.reshape(s, n_kv, d), positions, spec["rope_base"])
        v = linear(x, p["v"]).reshape(s, n_kv, d)
        h = h + linear(attention(q, k, v).reshape(s, n * d), p["o"])
        x = norm(h, p["norm2"], "rms", spec["eps"])
        return h + routed_mlp(x, p["router"], experts, spec["top_k"])


def forward(weights, tokens, spec, head_positions=None):
    """Logits (len(head_positions) or s, V) in float32 of one sequence of
    token ids; as ``dense_decoder.forward``."""
    frozen = tuple(sorted(spec.items()))
    # the head is dense_decoder's, which asks its spec for the norm's kind
    head_spec = tuple(sorted({**spec, "norm": "rms"}.items()))
    h = weights["embedding"][tokens].astype(F32)
    for layer in weights["layers"]:
        h = layer_forward(h, layer, frozen)
    if head_positions is not None:
        h = h[head_positions]
    return head_forward(h, weights["final_norm"], weights["head"], head_spec)
