"""The program's dense decoders as ``reference/dense_decoder.py`` wants them.

The only place the benchmark names fields of ``scaling_tpu``'s config or
leaves of its parameter tree for this architecture. ``arch`` is the
``transformer_architecture`` of the configuration file.
"""

from __future__ import annotations

from benchmark import model, ops_count


def reference_spec(arch: dict) -> dict:
    return {
        "num_heads": arch["num_attention_heads"],
        "num_kv_heads": arch.get("attention_num_kv_heads") or arch["num_attention_heads"],
        "head_dim": arch["hidden_size"] // arch["num_attention_heads"],
        "norm": "rms" if arch["norm_type"] == "rms" else "layernorm",
        "mlp": "swiglu" if arch["mlp_type"] == "swiglu" else "gelu",
        "eps": arch.get("layernorm", {}).get("layernorm_epsilon", 1e-5),
        "rope_base": float(arch.get("rotary_embedding_base", 10000)),
    }


def reference_weights(params: dict, arch: dict) -> dict:
    """The program's parameter tree in the reference's plain layout (same
    arrays, no copy, no cast). Layout of the tree: ``layer_0`` embedding,
    ``layer_1..L`` blocks, ``layer_{L+1}`` final norm, ``layer_{L+2}`` head."""
    num_layers = arch["num_layers"]

    def block(p):
        attn, mlp = p["attention"], p["mlp"]
        out = {"norm1": p["input_layernorm"], "norm2": p["post_attention_layernorm"],
               "q": attn["query"], "k": attn["key"], "v": attn["value"],
               "o": attn["dense"]}
        if "gate_proj" in mlp:
            out.update(gate=mlp["gate_proj"], up=mlp["up_proj"], down=mlp["down_proj"])
        else:
            out.update({"in": mlp["dense_in"], "out": mlp["dense_out"]})
        return out

    return {
        "embedding": params["layer_0"]["embedding"]["weight"],
        "layers": [block(params[f"layer_{i}"]) for i in range(1, num_layers + 1)],
        "final_norm": params[f"layer_{num_layers + 1}"]["norm"],
        "head": params[f"layer_{num_layers + 2}"]["linear"]["weight"],
    }


def matmul_param_count(param_shapes) -> int:
    """Parameters that take part in a matrix multiplication: all but the
    input embedding table (a lookup)."""
    return model.count_params(param_shapes) - model.count_params(param_shapes["layer_0"])


def train_flops_per_token(arch: dict, param_shapes, seq_len: int) -> float:
    """Forward + backward FLOPs one trained token requires: every matmul
    parameter works on every token of a dense decoder."""
    return ops_count.train_flops_per_token(
        matmul_param_count(param_shapes), arch["num_layers"],
        arch["num_attention_heads"],
        arch["hidden_size"] // arch["num_attention_heads"], seq_len)
