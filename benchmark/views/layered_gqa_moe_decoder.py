"""The program's Laguna stack (``layer_pattern``: a block is TWO single-mixer
layers, an ``attention`` (full) or a ``window`` attention then an ``mlp`` or
``moe`` FFN) as ``reference/layered_gqa_moe_decoder.py`` wants it.

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
KINDS = {"attention": "full", "window": "window"}

# what reference/layered_gqa_moe_decoder.py computes, as the program's config
# says it
EQUATIONS = {
    "mlp_type": "swiglu", "activation_function": "silu", "norm_type": "rms",
    "moe_glu": True, "moe_router": "softmax", "moe_norm_topk_prob": True,
    "relative_position_embedding_type": "rotary", "attention_bias": False,
    "mlp_bias": False, "weight_tying": False, "attention_gate": "per_head",
}
# what the program's config need not state: its default is the reference's
DEFAULTS = {"key_query_norm": False, "moe_n_group": 1, "moe_topk_group": 1,
            "causal": True, "hc_streams": 1}
YARN_DEFAULTS = {"beta_fast": 32.0, "beta_slow": 1.0, "mscale": 1.0,
                 "mscale_all_dim": 0.0}


def blocks(arch: dict) -> tuple:
    """The kind of every block's attention: the pattern is ``attention |
    window``, then ``mlp | moe``, a block, the dense blocks leading."""
    pattern = list(arch.get("layer_pattern") or ())
    ops, ffns = pattern[0::2], pattern[1::2]
    dense = sum(k == "mlp" for k in ffns)
    if (not pattern or len(pattern) % 2 or not set(ops) <= set(KINDS)
            or ffns != ["mlp"] * dense + ["moe"] * (len(ffns) - dense)):
        raise SystemExit(
            "layered_gqa_moe_decoder: layer_pattern is (attention | window, "
            "mlp | moe) a block, the dense blocks leading; the configuration "
            f"states {pattern}")
    return tuple(KINDS[k] for k in ops)


def yarn(arch: dict):
    """``(factor, original, beta_fast, beta_slow, attention_factor)`` of the
    full layers' rotary, or None."""
    scaling = arch.get("rope_scaling")
    if scaling is None:
        return None
    scaling = {**YARN_DEFAULTS, **scaling}
    if scaling.get("type", "yarn") != "yarn" or scaling["mscale_all_dim"]:
        raise SystemExit(
            "layered_gqa_moe_decoder: the reference computes YaRN with cos and "
            "sin times attention_factor = 0.1 mscale ln(factor) + 1 and the "
            f"softmax scale untouched; the configuration states {scaling}")
    factor = float(scaling["factor"])
    attention_factor = (
        0.1 * scaling["mscale"] * math.log(factor) + 1.0 if factor > 1 else 1.0)
    return (factor, float(scaling["original_max_position_embeddings"]),
            float(scaling["beta_fast"]), float(scaling["beta_slow"]),
            attention_factor)


def reference_spec(arch: dict) -> dict:
    other = {k: arch.get(k) for k, v in EQUATIONS.items() if arch.get(k) != v}
    other.update({k: arch[k] for k, v in DEFAULTS.items() if arch.get(k, v) != v})
    if other:
        raise SystemExit(f"layered_gqa_moe_decoder: the reference computes "
                         f"{EQUATIONS} and {DEFAULTS}; the configuration states {other}")
    heads = arch["num_attention_heads"]
    head_dim = arch.get("attention_head_dim") or arch["hidden_size"] // heads

    def turned(share):
        return max(2, int(head_dim * share))

    return {
        "kinds": blocks(arch),
        "heads": (heads, arch.get("window_num_attention_heads") or heads),
        "num_kv_heads": arch["attention_num_kv_heads"],
        "head_dim": head_dim,
        "window": arch.get("window_size"),
        "eps": arch.get("layernorm", {}).get("layernorm_epsilon", 1e-5),
        "rope": (
            (float(arch.get("rotary_embedding_base", 10000)),
             turned(arch.get("rotary_percentage", 1.0)), yarn(arch)),
            (float(arch.get("window_rotary_embedding_base", 10000)),
             turned(arch.get("window_rotary_percentage", 1.0)), None)),
        "top_k": arch["moe_top_k"],
        "scale": float(arch.get("moe_routed_scaling_factor", 1.0)),
        "experts_first": arch.get("moe_experts_first", 0),
        "shared": True,
    }


def reference_weights(params: dict, arch: dict) -> dict:
    """The program's parameter tree in the reference's plain layout (same
    arrays, no copy, no cast): ``layer_0`` embedding, ``layer_{2i+1}`` /
    ``layer_{2i+2}`` block ``i``'s attention / FFN, ``layer_{L+1}`` the final
    norm, ``layer_{L+2}`` the head."""
    num_layers = arch["num_layers"]

    def attention(p):
        m = p["mixer"]
        return {"attn_norm": p["norm"], "q": m["query"], "k": m["key"],
                "v": m["value"], "o": m["dense"],
                "head_gate": m["gate"]["weight"]}

    def ffn(p):
        m = p["mixer"]
        if "router" not in m:
            return {"ffn_norm": p["norm"], "gate": m["gate_proj"],
                    "up": m["up_proj"], "down": m["down_proj"]}
        return {"ffn_norm": p["norm"], "router": m["router"]["weight"],
                **{name: m[leaf] for name, leaf in EXPERT_LEAVES.items()},
                **{name: m[leaf] for name, leaf in SHARED_LEAVES.items()}}

    return {
        "embedding": params["layer_0"]["embedding"]["weight"],
        "layers": [{**attention(params[f"layer_{2 * i + 1}"]),
                    **ffn(params[f"layer_{2 * i + 2}"])}
                   for i in range(len(blocks(arch)))],
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
    ``moe_top_k / moe_num_experts``; attention's term a full layer over the
    sequence, a window layer over its window at most. The program does not
    train this stack: the contract's function, used by no cell."""
    held = expert_param_count(arch, param_shapes)
    at_work = (dense_decoder.matmul_param_count(param_shapes) - held
               + held * arch["moe_top_k"] // arch["moe_num_experts"])
    kinds = blocks(arch)
    heads = arch["num_attention_heads"]
    head_dim = arch.get("attention_head_dim") or arch["hidden_size"] // heads
    window_heads = arch.get("window_num_attention_heads") or heads
    full = ops_count.train_flops_per_token(
        at_work, kinds.count("full"), heads, head_dim, seq_len)
    return full + ops_count.train_flops_per_token(
        0, kinds.count("window"), window_heads, head_dim,
        min(seq_len, 2 * (arch.get("window_size") or seq_len)))
