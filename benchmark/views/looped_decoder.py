"""The program's looped decoder (``loop_steps``, ``sandwich_norm``,
``loop_exit_gate``: Ouro's block) as ``reference/looped_decoder.py`` wants it.

The only place the benchmark names fields of ``scaling_tpu``'s config or
leaves of its parameter tree for this architecture. ``arch`` is the
``transformer_architecture`` of the configuration file. The reference knows
one set of equations (RMSNorm, SwiGLU, rotary on the whole head, no bias, an
untied head; the loop, the sandwich norms and the gate each on or off); a
configuration that states others is refused here.
"""

from __future__ import annotations

from benchmark import model, ops_count

# what reference/looped_decoder.py computes, as the program's config says it;
# beside each, what the program takes a missing key for
EQUATIONS = {
    "mlp_type": ("swiglu", "default"), "norm_type": ("rms", "layernorm"),
    "relative_position_embedding_type": ("rotary", "rotary"),
    "rotary_percentage": (1.0, 1.0), "attention_bias": (False, True),
    "mlp_bias": (False, True), "weight_tying": (False, False),
    "key_query_norm": (False, False), "causal": (True, True),
    "loop_exit_threshold": (1.0, 1.0),
}
# the program's norms on the sub-layers' outputs, under the reference's names
OUTPUT_NORMS = {"norm_attn_out": "post_attention_output_layernorm",
                "norm_mlp_out": "post_mlp_output_layernorm"}


def loop_of(arch: dict):
    """(steps, sandwich norms, exit gate) as the architecture states them."""
    return (int(arch.get("loop_steps", 1)), bool(arch.get("sandwich_norm", False)),
            bool(arch.get("loop_exit_gate", False)))


def reference_spec(arch: dict) -> dict:
    other = {k: arch.get(k, default) for k, (want, default) in EQUATIONS.items()
             if arch.get(k, default) != want}
    if other:
        raise SystemExit("looped_decoder: the reference computes "
                         f"{ {k: v[0] for k, v in EQUATIONS.items()} }; "
                         f"the configuration states {other}")
    heads = arch["num_attention_heads"]
    steps, sandwich, gate = loop_of(arch)
    return {
        "num_heads": heads,
        "num_kv_heads": arch.get("attention_num_kv_heads") or heads,
        "head_dim": arch["hidden_size"] // heads,
        "eps": arch.get("layernorm", {}).get("layernorm_epsilon", 1e-5),
        "rope_base": float(arch.get("rotary_embedding_base", 10000)),
        "steps": steps, "sandwich": sandwich, "gate": gate,
    }


def reference_weights(params: dict, arch: dict) -> dict:
    """The program's parameter tree in the reference's plain layout (same
    arrays, no copy, no cast). Layout of the tree: ``layer_0`` embedding,
    ``layer_1..L`` the trunk's blocks (ONE set, whatever the steps),
    ``layer_{L+1}`` the final norm, then the exit gate if there is one, then
    the head."""
    num_layers = arch["num_layers"]
    _, sandwich, gate = loop_of(arch)

    def block(p):
        attn, mlp = p["attention"], p["mlp"]
        out = {"norm1": p["input_layernorm"], "norm2": p["post_attention_layernorm"],
               "q": attn["query"], "k": attn["key"], "v": attn["value"],
               "o": attn["dense"], "gate": mlp["gate_proj"], "up": mlp["up_proj"],
               "down": mlp["down_proj"]}
        if sandwich:
            out.update({name: p[leaf] for name, leaf in OUTPUT_NORMS.items()})
        return out

    weights = {
        "embedding": params["layer_0"]["embedding"]["weight"],
        "layers": [block(params[f"layer_{i}"]) for i in range(1, num_layers + 1)],
        "final_norm": params[f"layer_{num_layers + 1}"]["norm"],
        "head": params[f"layer_{num_layers + 2 + gate}"]["linear"]["weight"],
    }
    if gate:
        weights["exit"] = params[f"layer_{num_layers + 2}"]["linear"]
    return weights


def trunk_param_count(arch: dict, param_shapes) -> int:
    """Parameters of the trunk's layers, held once."""
    return sum(model.count_params(param_shapes[f"layer_{i}"])
               for i in range(1, arch["num_layers"] + 1))


def train_flops_per_token(arch: dict, param_shapes, seq_len: int) -> float:
    """A token works the trunk's parameters ``loop_steps`` times and attends
    at every (step, layer); final norm, gate and head once (the gate and the
    norm a step are vectors: not counted again)."""
    steps = loop_of(arch)[0]
    held = model.count_params(param_shapes) - model.count_params(param_shapes["layer_0"])
    at_work = held + (steps - 1) * trunk_param_count(arch, param_shapes)
    return ops_count.train_flops_per_token(
        at_work, steps * arch["num_layers"], arch["num_attention_heads"],
        arch["hidden_size"] // arch["num_attention_heads"], seq_len)
