"""The program's parallel block (``parallel_ssm``: a Mamba-2 mixer beside GQA
attention on one normed input, summed; then a SwiGLU MLP; the published
multipliers) as ``reference/parallel_hybrid_decoder.py`` wants it.

The only place the benchmark names fields of ``scaling_tpu``'s config or
leaves of its parameter tree for this architecture. ``arch`` is the
``transformer_architecture`` of the configuration file. The reference knows
one set of equations; a configuration that states others is refused here.
"""

from __future__ import annotations

from benchmark import ops_count
from benchmark.views import dense_decoder

# what reference/parallel_hybrid_decoder.py computes, as the program's config
# says it
EQUATIONS = {
    "parallel_ssm": True, "mlp_type": "swiglu", "norm_type": "rms",
    "relative_position_embedding_type": "rotary", "attention_bias": False,
    "mlp_bias": False, "weight_tying": False, "attention_qkv_in_one": False,
}
# what the program's config need not state: its default is the reference's
DEFAULTS = {"key_query_norm": False, "rotary_percentage": 1.0,
            "layer_pattern": None, "loop_steps": 1, "sandwich_norm": False}
# the reference's constants <- the program's ``multipliers`` block
MULTIPLIERS = {"e": "embedding", "l": "lm_head", "a_in": "attention_in",
               "a_out": "attention_out", "k_m": "key", "s_in": "ssm_in",
               "s_out": "ssm_out", "g_m": "mlp_gate", "d_m": "mlp_down"}


def reference_spec(arch: dict) -> dict:
    other = {k: arch.get(k) for k, v in EQUATIONS.items() if arch.get(k) != v}
    other.update({k: arch[k] for k, v in DEFAULTS.items() if arch.get(k, v) != v})
    if other:
        raise SystemExit(f"parallel_hybrid_decoder: the reference computes {EQUATIONS} "
                         f"and {DEFAULTS}; the configuration states {other}")
    heads = arch["num_attention_heads"]
    mult = arch.get("multipliers", {})
    return {
        "num_heads": heads,
        "num_kv_heads": arch.get("attention_num_kv_heads") or heads,
        "head_dim": arch.get("attention_head_dim") or arch["hidden_size"] // heads,
        "eps": arch.get("layernorm", {}).get("layernorm_epsilon", 1e-5),
        "rope_base": float(arch.get("rotary_embedding_base", 10000)),
        "mamba_heads": arch["mamba_num_heads"],
        "mamba_head_dim": arch["mamba_head_dim"],
        "state": arch["ssm_state_size"],
        "groups": arch["n_groups"],
        "ssm_m": tuple(float(m) for m in mult.get("ssm", (1.0,) * 5)),
        **{name: float(mult.get(key, 1.0)) for name, key in MULTIPLIERS.items()},
    }


def reference_weights(params: dict, arch: dict) -> dict:
    """The program's parameter tree in the reference's plain layout (same
    arrays, no copy, no cast); ``layer_0`` embedding, ``layer_1..L`` blocks,
    ``layer_{L+1}`` final norm, ``layer_{L+2}`` head."""
    num_layers = arch["num_layers"]

    def block(p):
        attn, ssm, mlp = p["attention"], p["ssm"], p["mlp"]
        return {
            "norm1": p["input_layernorm"], "norm2": p["post_attention_layernorm"],
            "q": attn["query"], "k": attn["key"], "v": attn["value"],
            "o": attn["dense"],
            "in_proj": ssm["in_proj"]["weight"], "conv_w": ssm["conv"]["weight"],
            "conv_b": ssm["conv"]["bias"], "dt_bias": ssm["dt_bias"],
            "A_log": ssm["A_log"], "D": ssm["D"],
            "gate_norm": ssm["norm"]["weight"],
            "out_proj": ssm["out_proj"]["weight"],
            "gate": mlp["gate_proj"]["weight"], "up": mlp["up_proj"]["weight"],
            "down": mlp["down_proj"]["weight"],
        }

    return {
        "embedding": params["layer_0"]["embedding"]["weight"],
        "layers": [block(params[f"layer_{i}"]) for i in range(1, num_layers + 1)],
        "final_norm": params[f"layer_{num_layers + 1}"]["norm"],
        "head": params[f"layer_{num_layers + 2}"]["linear"]["weight"],
    }


def train_flops_per_token(arch: dict, param_shapes, seq_len: int) -> float:
    """A token works every matrix but the embedding table; attention's term
    counts every layer; the recurrence's (linear in the sequence) is left
    out, a lower bound. The program does not train this stack: the
    contract's function, used by no cell."""
    heads = arch["num_attention_heads"]
    return ops_count.train_flops_per_token(
        dense_decoder.matmul_param_count(param_shapes), arch["num_layers"], heads,
        arch.get("attention_head_dim") or arch["hidden_size"] // heads, seq_len)
