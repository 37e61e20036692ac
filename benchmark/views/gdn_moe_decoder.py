"""The program's Qwen3-Next stack (``layer_pattern``: a published layer is TWO
single-mixer layers, a ``delta`` or an ``attention`` mixer then a ``moe``) as
``reference/gdn_moe_decoder.py`` wants it.

The only place the benchmark names fields of ``scaling_tpu``'s config or
leaves of its parameter tree for this architecture. ``arch`` is the
``transformer_architecture`` of the configuration file. The reference knows
one set of equations; a configuration that states others is refused here.

Where two orders meet: the program keeps a delta mixer's projection columns
kind by kind (``[q | k | v | z]``, ``[b | a]``), the reference keeps the
released checkpoint's, by key head (``reference/gdn_moe_decoder.py``
``split_by_key_head``); ``released_order`` is the permutation between them.
"""

from __future__ import annotations

import math

import numpy as np

from benchmark import gdn_ops_count, ops_count
from benchmark.views import dense_decoder

# the program's expert leaves, (held, H, F), (held, H, F), (held, F, H), under
# the reference's names
EXPERT_LEAVES = {"w_gate": "w_gate", "w_up": "w_in", "w_down": "w_out"}
SHARED_LEAVES = {"shared_gate": "shared_gate", "shared_up": "shared_in",
                 "shared_down": "shared_out", "shared_scale": "shared_scale"}

# what reference/gdn_moe_decoder.py computes, as the program's config says it
EQUATIONS = {
    "activation_function": "silu", "norm_type": "rms", "moe_glu": True,
    "moe_router": "softmax", "moe_norm_topk_prob": True,
    "moe_shared_expert_gate": True, "relative_position_embedding_type": "rotary",
    "attention_bias": False, "mlp_bias": False, "weight_tying": False,
    "attention_gate": "elementwise", "key_query_norm": True,
    "attention_qkv_in_one": False,
}
# what the program's config need not state: its default is the reference's
DEFAULTS = {"moe_routed_scaling_factor": 1.0, "moe_n_group": 1,
            "moe_topk_group": 1, "causal": True, "hc_streams": 1,
            "rope_scaling": None, "key_query_norm_scope": "head"}


def kinds(arch: dict) -> tuple:
    """The mixer of every published layer: the pattern is ``delta |
    attention``, then ``moe``, a layer."""
    pattern = list(arch.get("layer_pattern") or ())
    mixers, ffns = pattern[0::2], pattern[1::2]
    if (not pattern or len(pattern) % 2 or not set(mixers) <= {"delta", "attention"}
            or set(ffns) != {"moe"}):
        raise SystemExit(
            "gdn_moe_decoder: layer_pattern is (delta | attention, moe) a "
            f"layer; the configuration states {pattern}")
    return tuple(mixers)


def delta_shape(arch: dict) -> tuple:
    """(key heads, value heads, key head dim, value head dim)."""
    return (arch["delta_num_key_heads"], arch["delta_num_value_heads"],
            arch["delta_key_head_dim"], arch["delta_value_head_dim"])


def reference_spec(arch: dict) -> dict:
    other = {k: arch.get(k) for k, v in EQUATIONS.items() if arch.get(k) != v}
    other.update({k: arch[k] for k, v in DEFAULTS.items() if arch.get(k, v) != v})
    layernorm = arch.get("layernorm", {})
    if not layernorm.get("weight_offset"):
        other["layernorm.weight_offset"] = layernorm.get("weight_offset")
    if other:
        raise SystemExit(f"gdn_moe_decoder: the reference computes {EQUATIONS}, "
                         f"{DEFAULTS} and norms whose weight is an offset from "
                         f"one; the configuration states {other}")
    heads = arch["num_attention_heads"]
    head_dim = arch.get("attention_head_dim") or arch["hidden_size"] // heads
    return {
        "kinds": kinds(arch),
        "num_heads": heads,
        "num_kv_heads": arch["attention_num_kv_heads"],
        "head_dim": head_dim,
        "rope_dims": max(2, int(head_dim * arch.get("rotary_percentage", 1.0))),
        "rope_base": float(arch.get("rotary_embedding_base", 10000)),
        "eps": layernorm.get("layernorm_epsilon", 1e-5),
        "delta": delta_shape(arch),
        "top_k": arch["moe_top_k"],
        "experts_first": arch.get("moe_experts_first", 0),
        "shared": True,
    }


def released_order(nk: int, nv: int, dk: int, dv: int):
    """``(qkvz, ba)``: for each column of the released checkpoint's two
    projections (ordered by key head), the program's column that holds it
    (ordered kind by kind)."""
    per = nv // nk
    q0, k0, v0, z0 = 0, nk * dk, 2 * nk * dk, 2 * nk * dk + nv * dv
    qkvz, ba = [], []
    for h in range(nk):
        qkvz += [np.arange(q0 + h * dk, q0 + (h + 1) * dk),
                 np.arange(k0 + h * dk, k0 + (h + 1) * dk),
                 np.arange(v0 + h * per * dv, v0 + (h + 1) * per * dv),
                 np.arange(z0 + h * per * dv, z0 + (h + 1) * per * dv)]
        ba += [np.arange(h * per, (h + 1) * per),
               np.arange(nv + h * per, nv + (h + 1) * per)]
    return np.concatenate(qkvz), np.concatenate(ba)


def reference_weights(params: dict, arch: dict) -> dict:
    """The program's parameter tree in the reference's plain layout (no cast;
    the same arrays but a delta mixer's two projections, whose columns are
    gathered into the released order): ``layer_0`` embedding, ``layer_{2i+1}``
    / ``layer_{2i+2}`` layer ``i``'s mixer / routed MLP, ``layer_{L+1}`` the
    final norm, ``layer_{L+2}`` the head."""
    num_layers = arch["num_layers"]
    qkvz_order, ba_order = released_order(*delta_shape(arch))

    def mixer(p, kind):
        m = p["mixer"]
        if kind == "delta":
            return {"mixer_norm": p["norm"],
                    "qkvz": m["in_proj"]["weight"][:, qkvz_order],
                    "ba": m["ba_proj"]["weight"][:, ba_order],
                    "conv": m["conv"]["weight"], "A_log": m["A_log"],
                    "dt_bias": m["dt_bias"], "gated_norm": m["norm"]["weight"],
                    "out": m["out_proj"]["weight"]}
        return {"mixer_norm": p["norm"], "q": m["query"]["weight"],
                "k": m["key"]["weight"], "v": m["value"]["weight"],
                "o": m["dense"]["weight"], "q_norm": m["norm_query"]["weight"],
                "k_norm": m["norm_key"]["weight"]}

    def ffn(p):
        m = p["mixer"]
        return {"ffn_norm": p["norm"], "router": m["router"]["weight"],
                **{name: m[leaf] for name, leaf in EXPERT_LEAVES.items()},
                **{name: m[leaf] for name, leaf in SHARED_LEAVES.items()}}

    return {
        "embedding": params["layer_0"]["embedding"]["weight"],
        "layers": [{**mixer(params[f"layer_{2 * i + 1}"], kind),
                    **ffn(params[f"layer_{2 * i + 2}"])}
                   for i, kind in enumerate(kinds(arch))],
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
    experts it does not use (of the held experts' parameters the share
    ``moe_top_k / moe_num_experts``), the delta layers' recurrence and the
    attention layers' scores over the sequence. The program does not train
    this stack: the contract's function, used by no cell."""
    held = expert_param_count(arch, param_shapes)
    at_work = (dense_decoder.matmul_param_count(param_shapes) - held
               + held * arch["moe_top_k"] // arch["moe_num_experts"])
    mixers = kinds(arch)
    heads = arch["num_attention_heads"]
    head_dim = arch.get("attention_head_dim") or arch["hidden_size"] // heads
    _, nv, dk, dv = delta_shape(arch)
    return (ops_count.train_flops_per_token(
        at_work, mixers.count("attention"), heads, head_dim, seq_len)
        + 3.0 * mixers.count("delta") * gdn_ops_count.step_flops(nv, dk, dv))
