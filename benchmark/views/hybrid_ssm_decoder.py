"""The program's pattern stack (``layer_pattern``: Mamba-2 / routed relu2
experts with a shared expert / GQA attention, one mixer a layer) as
``reference/hybrid_ssm_decoder.py`` wants it.

The only place the benchmark names fields of ``scaling_tpu``'s config or
leaves of its parameter tree for this architecture. ``arch`` is the
``transformer_architecture`` of the configuration file. The reference knows
one set of equations; a configuration that states others is refused here.
"""

from __future__ import annotations

import math

from benchmark import ops_count
from benchmark.views import dense_decoder

# what reference/hybrid_ssm_decoder.py computes, as the program's config says it
EQUATIONS = {
    "activation_function": "relu2", "norm_type": "rms", "moe_glu": False,
    "moe_router": "sigmoid_bias", "moe_norm_topk_prob": True,
    "relative_position_embedding_type": "none", "attention_bias": False,
    "mlp_bias": False, "weight_tying": False, "conv_kernel": 4,
}


def held_experts(arch: dict) -> int:
    return arch.get("moe_experts_held") or (
        arch["moe_num_experts"] - arch.get("moe_experts_first", 0))


def reference_spec(arch: dict) -> dict:
    other = {k: arch.get(k) for k, v in EQUATIONS.items() if arch.get(k) != v}
    if other or not arch.get("layer_pattern"):
        raise SystemExit(f"hybrid_ssm_decoder: the reference computes {EQUATIONS} "
                         f"over a layer_pattern; the configuration states {other}")
    heads = arch["num_attention_heads"]
    return {
        "pattern": tuple(arch["layer_pattern"]),
        "num_heads": heads,
        "num_kv_heads": arch.get("attention_num_kv_heads") or heads,
        "head_dim": arch.get("attention_head_dim") or arch["hidden_size"] // heads,
        "eps": arch.get("layernorm", {}).get("layernorm_epsilon", 1e-5),
        "mamba_heads": arch["mamba_num_heads"],
        "mamba_head_dim": arch["mamba_head_dim"],
        "state": arch["ssm_state_size"],
        "groups": arch["n_groups"],
        "top_k": arch["moe_top_k"],
        "scale": float(arch.get("moe_routed_scaling_factor", 1.0)),
        "experts_first": arch.get("moe_experts_first", 0),
        "shared": True,
    }


def reference_weights(params: dict, arch: dict) -> dict:
    """The program's parameter tree in the reference's plain layout (same
    arrays, no copy, no cast); ``layer_0`` embedding, ``layer_1..L`` blocks,
    ``layer_{L+1}`` final norm, ``layer_{L+2}`` head."""
    num_layers = arch["num_layers"]

    def block(p, kind):
        m = p["mixer"]
        if kind == "mamba":
            mixer = {"in_proj": m["in_proj"]["weight"], "conv_w": m["conv"]["weight"],
                     "conv_b": m["conv"]["bias"], "dt_bias": m["dt_bias"],
                     "A_log": m["A_log"], "D": m["D"],
                     "gate_norm": m["norm"]["weight"],
                     "out_proj": m["out_proj"]["weight"]}
        elif kind == "moe":
            mixer = {"router": m["router"]["weight"], "router_bias": m["router"]["bias"],
                     "up": m["w_in"], "down": m["w_out"],
                     "shared_up": m["shared_in"], "shared_down": m["shared_out"]}
        else:
            mixer = {"q": m["query"], "k": m["key"], "v": m["value"], "o": m["dense"]}
        return {"norm": p["norm"], **mixer}

    return {
        "embedding": params["layer_0"]["embedding"]["weight"],
        "layers": [block(params[f"layer_{i + 1}"], kind)
                   for i, kind in enumerate(arch["layer_pattern"])],
        "final_norm": params[f"layer_{num_layers + 1}"]["norm"],
        "head": params[f"layer_{num_layers + 2}"]["linear"]["weight"],
    }


def expert_param_count(arch: dict, param_shapes) -> int:
    """Parameters of the routed experts HELD here, all routed layers."""
    return sum(math.prod(param_shapes[f"layer_{i + 1}"]["mixer"][leaf].shape)
               for i, kind in enumerate(arch["layer_pattern"]) if kind == "moe"
               for leaf in ("w_in", "w_out"))


def train_flops_per_token(arch: dict, param_shapes, seq_len: int) -> float:
    """A token works every matrix but the embedding table and the routed
    experts it does not use: of the held experts' parameters the share
    ``moe_top_k / moe_num_experts`` (a token's choices fall on this share's
    experts in proportion to the share). Attention's term counts the attention
    layers only; the recurrence's (linear in the sequence) is left out, a
    lower bound. The program does not train this stack: the contract's
    function, used by no cell."""
    held = expert_param_count(arch, param_shapes)
    at_work = (dense_decoder.matmul_param_count(param_shapes) - held
               + held * arch["moe_top_k"] // arch["moe_num_experts"])
    heads = arch["num_attention_heads"]
    return ops_count.train_flops_per_token(
        at_work, sum(k == "attention" for k in arch["layer_pattern"]), heads,
        arch.get("attention_head_dim") or arch["hidden_size"] // heads, seq_len)
