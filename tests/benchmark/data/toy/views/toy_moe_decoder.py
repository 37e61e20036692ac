"""The program's ``mlp_type: moe`` decoder as ``toy_moe_decoder`` wants it:
a view a later PR could add, one new file."""

import math

from benchmark import ops_count
from benchmark.views import dense_decoder

EXPERT_LEAVES = ("w_gate", "w_in", "w_out")


def reference_spec(arch: dict) -> dict:
    if arch["activation_function"] != "silu":
        raise SystemExit("toy_moe_decoder: the reference's experts are SiLU-gated")
    spec = dense_decoder.reference_spec({**arch, "mlp_type": "swiglu"})
    del spec["mlp"]
    return {**spec, "top_k": arch["moe_top_k"]}


def reference_weights(params: dict, arch: dict) -> dict:
    num_layers = arch["num_layers"]

    def block(p):
        attn, mlp = p["attention"], p["mlp"]
        return {"norm1": p["input_layernorm"], "norm2": p["post_attention_layernorm"],
                "q": attn["query"], "k": attn["key"], "v": attn["value"],
                "o": attn["dense"], "router": mlp["router"]["weight"],
                **{name: mlp[name] for name in EXPERT_LEAVES}}

    return {
        "embedding": params["layer_0"]["embedding"]["weight"],
        "layers": [block(params[f"layer_{i}"]) for i in range(1, num_layers + 1)],
        "final_norm": params[f"layer_{num_layers + 1}"]["norm"],
        "head": params[f"layer_{num_layers + 2}"]["linear"]["weight"],
    }


def train_flops_per_token(arch: dict, param_shapes, seq_len: int) -> float:
    """A token works the router and ``top_k`` of the experts, not all of
    them: the matmul parameters it requires are all but the embedding table
    and the experts it does not use."""
    experts, top_k = arch["moe_num_experts"], arch["moe_top_k"]
    expert_params = sum(
        math.prod(param_shapes[f"layer_{i}"]["mlp"][name].shape)
        for i in range(1, arch["num_layers"] + 1) for name in EXPERT_LEAVES)
    at_work = (dense_decoder.matmul_param_count(param_shapes)
               - expert_params + expert_params * top_k // experts)
    return ops_count.train_flops_per_token(
        at_work, arch["num_layers"], arch["num_attention_heads"],
        arch["hidden_size"] // arch["num_attention_heads"], seq_len)
