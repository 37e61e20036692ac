"""The program's Xing4.0 stack (``layer_pattern``: a block is TWO single-mixer
layers, a ``latent`` attention then an ``mlp`` or ``moe`` FFN, every one with a
hyper-connection mapping of its own; ``hc_streams`` residual streams) as
``reference/hc_latent_moe_decoder.py`` wants it.

The only place the benchmark names fields of ``scaling_tpu``'s config or
leaves of its parameter tree for this architecture; what the block shares with
Kimi-K2's is read through ``views/latent_moe_decoder.py``. ``arch`` is the
``transformer_architecture`` of the configuration file. The reference knows
one set of equations; a configuration that states others is refused.
"""

from __future__ import annotations

from benchmark.views import latent_moe_decoder as latent

HC_DEFAULTS = {"hc_sinkhorn_iters": 20, "hc_eps": 1e-6,
               "hc_res_clamp_min": -30.0, "hc_res_clamp_max": 30.0}


def reference_spec(arch: dict) -> dict:
    if arch.get("hc_streams", 1) < 2:
        raise SystemExit("hc_latent_moe_decoder: the reference mixes hc_streams "
                         "> 1 residual streams; the configuration states "
                         f"{arch.get('hc_streams')}")
    hc = {k: arch.get(k, v) for k, v in HC_DEFAULTS.items()}
    return {**latent.reference_spec(arch),
            "hc_streams": arch["hc_streams"],
            "hc_sinkhorn_iters": hc["hc_sinkhorn_iters"],
            "hc_eps": float(hc["hc_eps"]),
            "hc_clamp": (float(hc["hc_res_clamp_min"]),
                         float(hc["hc_res_clamp_max"]))}


def reference_weights(params: dict, arch: dict) -> dict:
    """``latent_moe_decoder``'s layout (same arrays, no copy, no cast), every
    layer with its two mappings, and the readout's leaves at the top."""
    weights = latent.reference_weights(params, arch)
    for i, layer in enumerate(weights["layers"]):
        layer["attn_hc"] = params[f"layer_{2 * i + 1}"]["hc"]
        layer["ffn_hc"] = params[f"layer_{2 * i + 2}"]["hc"]
    weights["readout_hc"] = params[f"layer_{arch['num_layers'] + 1}"]["hc"]
    return weights


def train_flops_per_token(arch: dict, param_shapes, seq_len: int) -> float:
    """``latent_moe_decoder``'s count and, a sub-layer, the mapping's one
    matmul (``phi``: its parameters are matrices of the tree like any other).
    The program does not train this stack: the contract's function, used by no
    cell."""
    return latent.train_flops_per_token(arch, param_shapes, seq_len)
