"""The program's routed decoder (``mlp_type: moe``, OLMoE's gates and QK-norm)
as ``reference/moe_decoder.py`` wants it.

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
EXPERT_LEAVES = {"gate": "w_gate", "up": "w_in", "down": "w_out"}

# what reference/moe_decoder.py computes, as the program's config says it
EQUATIONS = {
    "mlp_type": "moe", "activation_function": "silu", "norm_type": "rms",
    "moe_norm_topk_prob": False, "key_query_norm": True,
    "key_query_norm_scope": "projection", "attention_bias": False,
    "mlp_bias": False, "weight_tying": False,
}


def reference_spec(arch: dict) -> dict:
    other = {k: arch.get(k) for k, v in EQUATIONS.items() if arch.get(k) != v}
    if other:
        raise SystemExit(f"moe_decoder: the reference computes {EQUATIONS}; "
                         f"the configuration states {other}")
    heads = arch["num_attention_heads"]
    return {
        "num_heads": heads,
        "num_kv_heads": arch.get("attention_num_kv_heads") or heads,
        "head_dim": arch["hidden_size"] // heads,
        "eps": arch.get("layernorm", {}).get("layernorm_epsilon", 1e-5),
        "rope_base": float(arch.get("rotary_embedding_base", 10000)),
        "top_k": arch["moe_top_k"],
    }


def reference_weights(params: dict, arch: dict) -> dict:
    """The program's parameter tree in the reference's plain layout (same
    arrays, no copy, no cast); the layers lie as ``dense_decoder``'s do."""
    num_layers = arch["num_layers"]

    def block(p):
        attn, mlp = p["attention"], p["mlp"]
        return {"norm1": p["input_layernorm"], "norm2": p["post_attention_layernorm"],
                "q": attn["query"], "k": attn["key"], "v": attn["value"],
                "o": attn["dense"], "q_norm": attn["norm_query"],
                "k_norm": attn["norm_key"], "router": mlp["router"]["weight"],
                **{name: mlp[leaf] for name, leaf in EXPERT_LEAVES.items()}}

    return {
        "embedding": params["layer_0"]["embedding"]["weight"],
        "layers": [block(params[f"layer_{i}"]) for i in range(1, num_layers + 1)],
        "final_norm": params[f"layer_{num_layers + 1}"]["norm"],
        "head": params[f"layer_{num_layers + 2}"]["linear"]["weight"],
    }


def expert_param_count(arch: dict, param_shapes) -> int:
    """Parameters of ALL experts of all layers."""
    return sum(math.prod(param_shapes[f"layer_{i}"]["mlp"][leaf].shape)
               for i in range(1, arch["num_layers"] + 1)
               for leaf in EXPERT_LEAVES.values())


def train_flops_per_token(arch: dict, param_shapes, seq_len: int) -> float:
    """A token works the router and ``moe_top_k`` of the experts, not all of
    them: the matmul parameters it requires are all but the embedding table
    and the experts it does not use (+ the attention term, as dense)."""
    experts, top_k = arch["moe_num_experts"], arch["moe_top_k"]
    held = expert_param_count(arch, param_shapes)
    at_work = dense_decoder.matmul_param_count(param_shapes) - held + held * top_k // experts
    return ops_count.train_flops_per_token(
        at_work, arch["num_layers"], arch["num_attention_heads"],
        arch["hidden_size"] // arch["num_attention_heads"], seq_len)
