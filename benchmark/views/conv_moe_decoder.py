"""The program's LFM2-MoE stack (``layer_pattern``: a block is TWO single-mixer
layers, a ``conv`` or ``attention`` operator then an ``mlp`` or ``moe`` FFN) as
``reference/conv_moe_decoder.py`` wants it.

The only place the benchmark names fields of ``scaling_tpu``'s config or
leaves of its parameter tree for this architecture. ``arch`` is the
``transformer_architecture`` of the configuration file. The reference knows
one set of equations; a configuration that states others is refused here.
"""

from __future__ import annotations

import math

from benchmark import ops_count
from benchmark.views import dense_decoder

# the program's expert leaves, (E, H, F), (E, H, F), (E, F, H), under the
# reference's names
EXPERT_LEAVES = {"w_gate": "w_gate", "w_up": "w_in", "w_down": "w_out"}
OPERATORS = ("conv", "attention")

# what reference/conv_moe_decoder.py computes, as the program's config says it
EQUATIONS = {
    "mlp_type": "swiglu", "activation_function": "silu", "norm_type": "rms",
    "moe_glu": True, "moe_router": "sigmoid_bias", "moe_norm_topk_prob": True,
    "key_query_norm": True, "relative_position_embedding_type": "rotary",
    "attention_bias": False, "mlp_bias": False, "weight_tying": True,
}
# what the program's config need not state: its default is the reference's
DEFAULTS = {"key_query_norm_scope": "head", "moe_shared_expert_width": None,
            "moe_experts_held": None, "moe_experts_first": 0,
            "rotary_percentage": 1.0}


def blocks(arch: dict):
    """``(operator kinds, dense blocks)``: the pattern is ``operator, FFN`` a
    block, the FFNs ``mlp`` in the leading blocks and ``moe`` after them."""
    pattern = list(arch.get("layer_pattern") or ())
    ops, ffns = pattern[0::2], pattern[1::2]
    dense = sum(k == "mlp" for k in ffns)
    if (not pattern or len(pattern) % 2 or set(ops) - set(OPERATORS)
            or ffns != ["mlp"] * dense + ["moe"] * (len(ffns) - dense)):
        raise SystemExit(
            "conv_moe_decoder: layer_pattern is (conv | attention, mlp | moe) a "
            f"block, the dense blocks leading; the configuration states {pattern}")
    return tuple(ops), dense


def reference_spec(arch: dict) -> dict:
    other = {k: arch.get(k) for k, v in EQUATIONS.items() if arch.get(k) != v}
    other.update({k: arch[k] for k, v in DEFAULTS.items() if arch.get(k, v) != v})
    if other:
        raise SystemExit(f"conv_moe_decoder: the reference computes {EQUATIONS} "
                         f"and {DEFAULTS}; the configuration states {other}")
    ops, dense = blocks(arch)
    heads = arch["num_attention_heads"]
    return {
        "ops": ops,
        "num_dense": dense,
        "num_heads": heads,
        "num_kv_heads": arch.get("attention_num_kv_heads") or heads,
        "head_dim": arch.get("attention_head_dim") or arch["hidden_size"] // heads,
        "eps": arch.get("layernorm", {}).get("layernorm_epsilon", 1e-5),
        "rope_base": float(arch.get("rotary_embedding_base", 10000)),
        "top_k": arch["moe_top_k"],
        "scale": float(arch.get("moe_routed_scaling_factor", 1.0)),
        "gate_eps": float(arch.get("moe_norm_topk_eps", 1e-20)),
    }


def reference_weights(params: dict, arch: dict) -> dict:
    """The program's parameter tree in the reference's plain layout (same
    arrays, no copy, no cast): ``layer_0`` embedding (the head's table too),
    ``layer_{2i+1}`` / ``layer_{2i+2}`` block ``i``'s operator / FFN,
    ``layer_{L+1}`` the final norm."""
    ops, _ = blocks(arch)

    def operator(p, kind):
        m = p["mixer"]
        if kind == "conv":
            return {"in_proj": m["in_proj"]["weight"], "conv_w": m["conv"]["weight"],
                    "out_proj": m["out_proj"]["weight"]}
        return {"q": m["query"], "k": m["key"], "v": m["value"], "o": m["dense"],
                "q_norm": m["norm_query"], "k_norm": m["norm_key"]}

    def ffn(p):
        m = p["mixer"]
        if "router" not in m:
            return {"gate": m["gate_proj"], "up": m["up_proj"], "down": m["down_proj"]}
        return {"router": m["router"]["weight"], "router_bias": m["router"]["bias"],
                **{name: m[leaf] for name, leaf in EXPERT_LEAVES.items()}}

    def block(i, kind):
        op, mlp = params[f"layer_{2 * i + 1}"], params[f"layer_{2 * i + 2}"]
        return {"op_norm": op["norm"], "ffn_norm": mlp["norm"],
                **operator(op, kind), **ffn(mlp)}

    return {
        "embedding": params["layer_0"]["embedding"]["weight"],
        "layers": [block(i, kind) for i, kind in enumerate(ops)],
        "final_norm": params[f"layer_{arch['num_layers'] + 1}"]["norm"],
    }


def expert_param_count(arch: dict, param_shapes) -> int:
    """Parameters of ALL routed experts of all routed layers."""
    return sum(math.prod(param_shapes[f"layer_{i + 1}"]["mixer"][leaf].shape)
               for i, kind in enumerate(arch["layer_pattern"]) if kind == "moe"
               for leaf in EXPERT_LEAVES.values())


def train_flops_per_token(arch: dict, param_shapes, seq_len: int) -> float:
    """A token works every matrix but the routed experts it does not use
    (``moe_top_k`` of ``moe_num_experts``); the tied table works once, as the
    head (its other use is a lookup): it lies in ``layer_0``, which
    ``matmul_param_count`` leaves out, so it is added back. Attention's term
    counts the attention layers only; the short filter's (``2 K H`` a token a
    layer) is left out, a lower bound. The program does not train this stack:
    the contract's function, used by no cell."""
    experts = expert_param_count(arch, param_shapes)
    at_work = (dense_decoder.matmul_param_count(param_shapes)
               + arch["vocab_size"] * arch["hidden_size"]
               - experts + experts * arch["moe_top_k"] // arch["moe_num_experts"])
    heads = arch["num_attention_heads"]
    return ops_count.train_flops_per_token(
        at_work, sum(k == "attention" for k in arch["layer_pattern"]), heads,
        arch.get("attention_head_dim") or arch["hidden_size"] // heads, seq_len)
