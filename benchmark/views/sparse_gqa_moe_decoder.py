"""The program's Keye-VL-2.0 decoder stack (``layer_pattern``: a block is TWO
single-mixer layers, a SPARSE ``attention`` then a ``moe`` FFN) as
``reference/sparse_gqa_moe_decoder.py`` wants it.

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
INDEX_KEYS = ("index_n_heads", "index_head_dim", "index_topk")
# the indexer's leaves in a sparse attention mixer, under the reference's names
INDEX_LEAVES = {"index_q": "index_q_proj", "index_k": "index_k_proj",
                "index_w": "index_w_proj"}

# what reference/sparse_gqa_moe_decoder.py computes, as the program's config
# says it
EQUATIONS = {
    "activation_function": "silu", "norm_type": "rms", "moe_glu": True,
    "moe_router": "softmax", "moe_norm_topk_prob": True, "key_query_norm": True,
    "relative_position_embedding_type": "rotary", "attention_bias": False,
    "mlp_bias": False, "weight_tying": False,
}
# what the program's config need not state: its default is the reference's
DEFAULTS = {"key_query_norm_scope": "head", "moe_shared_expert_width": None,
            "moe_experts_held": None, "moe_experts_first": 0,
            "moe_routed_scaling_factor": 1.0, "rotary_percentage": 1.0,
            "moe_n_group": 1, "moe_topk_group": 1}


def blocks(arch: dict) -> int:
    """Blocks of the stack: the pattern is ``attention, moe`` a block."""
    pattern = list(arch.get("layer_pattern") or ())
    if not pattern or pattern != ["attention", "moe"] * (len(pattern) // 2):
        raise SystemExit(
            "sparse_gqa_moe_decoder: layer_pattern is (attention, moe) a block; "
            f"the configuration states {pattern}")
    return len(pattern) // 2


def reference_spec(arch: dict) -> dict:
    other = {k: arch.get(k) for k, v in EQUATIONS.items() if arch.get(k) != v}
    other.update({k: arch[k] for k, v in DEFAULTS.items() if arch.get(k, v) != v})
    if other:
        raise SystemExit(f"sparse_gqa_moe_decoder: the reference computes "
                         f"{EQUATIONS} and {DEFAULTS}; the configuration states {other}")
    missing = [k for k in INDEX_KEYS if arch.get(k) is None]
    if missing:
        raise SystemExit("sparse_gqa_moe_decoder: the reference computes the "
                         f"indexer; the configuration lacks {missing}")
    blocks(arch)
    heads = arch["num_attention_heads"]
    return {
        "num_heads": heads,
        "num_kv_heads": arch.get("attention_num_kv_heads") or heads,
        "head_dim": arch.get("attention_head_dim") or arch["hidden_size"] // heads,
        "eps": arch.get("layernorm", {}).get("layernorm_epsilon", 1e-5),
        "rope_base": float(arch.get("rotary_embedding_base", 10000)),
        "top_k": arch["moe_top_k"],
        "index_heads": arch["index_n_heads"], "index_dim": arch["index_head_dim"],
        "index_topk": arch["index_topk"],
    }


def reference_weights(params: dict, arch: dict) -> dict:
    """The program's parameter tree in the reference's plain layout (same
    arrays, no copy, no cast): ``layer_0`` embedding, ``layer_{2i+1}`` /
    ``layer_{2i+2}`` block ``i``'s attention / routed MLP, ``layer_{L+1}`` the
    final norm, ``layer_{L+2}`` the head."""
    num_layers = arch["num_layers"]

    def block(i):
        op, mlp = params[f"layer_{2 * i + 1}"], params[f"layer_{2 * i + 2}"]
        a, m = op["mixer"], mlp["mixer"]
        return {"attn_norm": op["norm"], "ffn_norm": mlp["norm"],
                "q": a["query"], "k": a["key"], "v": a["value"], "o": a["dense"],
                "q_norm": a["norm_query"], "k_norm": a["norm_key"],
                **{name: a[leaf]["weight"] for name, leaf in INDEX_LEAVES.items()},
                "index_k_norm": a["index_k_norm"],
                "router": m["router"]["weight"],
                **{name: m[leaf] for name, leaf in EXPERT_LEAVES.items()}}

    return {
        "embedding": params["layer_0"]["embedding"]["weight"],
        "layers": [block(i) for i in range(blocks(arch))],
        "final_norm": params[f"layer_{num_layers + 1}"]["norm"],
        "head": params[f"layer_{num_layers + 2}"]["linear"]["weight"],
    }


def expert_param_count(arch: dict, param_shapes) -> int:
    """Parameters of ALL routed experts of all routed layers."""
    return sum(math.prod(param_shapes[f"layer_{i + 1}"]["mixer"][leaf].shape)
               for i, kind in enumerate(arch["layer_pattern"]) if kind == "moe"
               for leaf in EXPERT_LEAVES.values())


def train_flops_per_token(arch: dict, param_shapes, seq_len: int) -> float:
    """A token works every matrix, the indexer's three among them, but the
    routed experts it does not use (``moe_top_k`` of ``moe_num_experts``);
    attention's term over the lines a query CHOOSES: at most ``index_topk`` of
    a sequence. The program does not train this stack: the contract's
    function, used by no cell."""
    experts = expert_param_count(arch, param_shapes)
    at_work = (dense_decoder.matmul_param_count(param_shapes) - experts
               + experts * arch["moe_top_k"] // arch["moe_num_experts"])
    heads = arch["num_attention_heads"]
    return ops_count.train_flops_per_token(
        at_work, blocks(arch), heads,
        arch.get("attention_head_dim") or arch["hidden_size"] // heads,
        min(seq_len, 2 * arch["index_topk"]))
