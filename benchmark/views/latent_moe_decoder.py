"""The program's Kimi-K2 stack (``layer_pattern``: a block is TWO single-mixer
layers, a ``latent`` attention then an ``mlp`` or ``moe`` FFN) as
``reference/latent_moe_decoder.py`` wants it.

The only place the benchmark names fields of ``scaling_tpu``'s config or
leaves of its parameter tree for this architecture. ``arch`` is the
``transformer_architecture`` of the configuration file. The reference knows
one set of equations; a configuration that states others is refused here.
"""

from __future__ import annotations

import math

from benchmark import ops_count
from benchmark.views import dense_decoder

# the program's expert leaves, (held, H, F), (held, H, F), (held, F, H), under
# the reference's names
EXPERT_LEAVES = {"w_gate": "w_gate", "w_up": "w_in", "w_down": "w_out"}
SHARED_LEAVES = {"shared_gate": "shared_gate", "shared_up": "shared_in",
                 "shared_down": "shared_out"}

# what reference/latent_moe_decoder.py computes, as the program's config says it
EQUATIONS = {
    "mlp_type": "swiglu", "activation_function": "silu", "norm_type": "rms",
    "moe_glu": True, "moe_router": "sigmoid_bias", "moe_norm_topk_prob": True,
    "relative_position_embedding_type": "rotary", "attention_bias": False,
    "mlp_bias": False, "weight_tying": False,
}
# what the program's config need not state: its default is the reference's
DEFAULTS = {"moe_n_group": 1, "moe_topk_group": 1, "rotary_percentage": 1.0}
YARN_KEYS = ("factor", "original_max_position_embeddings", "beta_fast",
             "beta_slow", "mscale", "mscale_all_dim")
YARN_DEFAULTS = {"beta_fast": 32.0, "beta_slow": 1.0, "mscale": 1.0,
                 "mscale_all_dim": 0.0}


def blocks(arch: dict) -> int:
    """The dense blocks: the pattern is ``latent, FFN`` a block, the FFNs
    ``mlp`` in the leading blocks and ``moe`` after them."""
    pattern = list(arch.get("layer_pattern") or ())
    ops, ffns = pattern[0::2], pattern[1::2]
    dense = sum(k == "mlp" for k in ffns)
    if (not pattern or len(pattern) % 2 or set(ops) != {"latent"}
            or ffns != ["mlp"] * dense + ["moe"] * (len(ffns) - dense)):
        raise SystemExit(
            "latent_moe_decoder: layer_pattern is (latent, mlp | moe) a block, "
            f"the dense blocks leading; the configuration states {pattern}")
    return dense


def yarn(arch: dict):
    scaling = arch.get("rope_scaling")
    if scaling is None:
        return None
    if scaling.get("type", "yarn") != "yarn":
        raise SystemExit("latent_moe_decoder: the reference computes YaRN; the "
                         f"configuration states rope_scaling {scaling}")
    return tuple(float(scaling.get(k, YARN_DEFAULTS.get(k))) for k in YARN_KEYS)


def held_experts(arch: dict) -> int:
    return arch.get("moe_experts_held") or (
        arch["moe_num_experts"] - arch.get("moe_experts_first", 0))


def reference_spec(arch: dict) -> dict:
    other = {k: arch.get(k) for k, v in EQUATIONS.items() if arch.get(k) != v}
    other.update({k: arch[k] for k, v in DEFAULTS.items() if arch.get(k, v) != v})
    if other:
        raise SystemExit(f"latent_moe_decoder: the reference computes {EQUATIONS} "
                         f"and {DEFAULTS}; the configuration states {other}")
    return {
        "num_dense": blocks(arch),
        "num_heads": arch["num_attention_heads"],
        "kv_lora": arch["kv_lora_rank"],
        "nope": arch["qk_nope_head_dim"],
        "rope": arch["qk_rope_head_dim"],
        "v": arch["v_head_dim"],
        "eps": arch.get("layernorm", {}).get("layernorm_epsilon", 1e-5),
        "rope_base": float(arch.get("rotary_embedding_base", 10000)),
        "yarn": yarn(arch),
        "top_k": arch["moe_top_k"],
        "scale": float(arch.get("moe_routed_scaling_factor", 1.0)),
        "gate_eps": float(arch.get("moe_norm_topk_eps", 1e-20)),
        "experts_first": arch.get("moe_experts_first", 0),
        "shared": True,
    }


def reference_weights(params: dict, arch: dict) -> dict:
    """The program's parameter tree in the reference's plain layout (same
    arrays, no copy, no cast): ``layer_0`` embedding, ``layer_{2i+1}`` /
    ``layer_{2i+2}`` block ``i``'s attention / FFN, ``layer_{L+1}`` the final
    norm, ``layer_{L+2}`` the head."""
    blocks(arch)
    num_layers = arch["num_layers"]

    def attention(p):
        m = p["mixer"]
        return {"attn_norm": p["norm"], "q_a": m["q_a_proj"]["weight"],
                "q_a_norm": m["q_a_norm"], "q_b": m["q_b_proj"]["weight"],
                "kv_a": m["kv_a_proj"]["weight"], "kv_a_norm": m["kv_a_norm"],
                "kv_b": m["kv_b_proj"]["weight"], "o": m["dense"]["weight"]}

    def ffn(p):
        m = p["mixer"]
        if "router" not in m:
            return {"ffn_norm": p["norm"], "gate": m["gate_proj"],
                    "up": m["up_proj"], "down": m["down_proj"]}
        return {"ffn_norm": p["norm"], "router": m["router"]["weight"],
                "router_bias": m["router"]["bias"],
                **{name: m[leaf] for name, leaf in EXPERT_LEAVES.items()},
                **{name: m[leaf] for name, leaf in SHARED_LEAVES.items()}}

    return {
        "embedding": params["layer_0"]["embedding"]["weight"],
        "layers": [{**attention(params[f"layer_{2 * i + 1}"]),
                    **ffn(params[f"layer_{2 * i + 2}"])}
                   for i in range(num_layers // 2)],
        "final_norm": params[f"layer_{num_layers + 1}"]["norm"],
        "head": params[f"layer_{num_layers + 2}"]["linear"]["weight"],
    }


def expert_param_count(arch: dict, param_shapes) -> int:
    """Parameters of the routed experts HELD here, all routed layers."""
    return sum(math.prod(param_shapes[f"layer_{i + 1}"]["mixer"][leaf].shape)
               for i, kind in enumerate(arch["layer_pattern"]) if kind == "moe"
               for leaf in EXPERT_LEAVES.values())


def train_flops_per_token(arch: dict, param_shapes, seq_len: int) -> float:
    """A token works every matrix but the embedding table and the routed
    experts it does not use: of the held experts' parameters the share
    ``moe_top_k / moe_num_experts``. Attention's term counts the expanded
    heads (``nope + rope`` wide). The program does not train this stack: the
    contract's function, used by no cell."""
    held = expert_param_count(arch, param_shapes)
    at_work = (dense_decoder.matmul_param_count(param_shapes) - held
               + held * arch["moe_top_k"] // arch["moe_num_experts"])
    return ops_count.train_flops_per_token(
        at_work, sum(k == "latent" for k in arch["layer_pattern"]),
        arch["num_attention_heads"],
        arch["qk_nope_head_dim"] + arch["qk_rope_head_dim"], seq_len)
