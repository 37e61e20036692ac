"""The program's dots3-note stack (``layer_pattern``: a block is TWO
single-mixer layers, a SPARSE ``latent`` (full) or a ``window_latent``
attention then an ``mlp`` or ``moe`` FFN) as
``reference/layered_latent_moe_decoder.py`` wants it.

The only place the benchmark names fields of ``scaling_tpu``'s config or
leaves of its parameter tree for this architecture; the FFNs' leaves are read
through ``views/latent_moe_decoder.py``. ``arch`` is the
``transformer_architecture`` of the configuration file. The reference knows
one set of equations; a configuration that states others is refused here.
"""

from __future__ import annotations

from benchmark import ops_count
from benchmark.views import dense_decoder, latent_moe_decoder as latent
from benchmark.views.sparse_latent_moe_decoder import INDEX_KEYS, INDEX_LEAVES

KINDS = {"latent": "full", "window_latent": "window"}
SIZES = ("num_attention_heads", "q_lora_rank", "kv_lora_rank",
         "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim")
# what reference/layered_latent_moe_decoder.py computes beyond
# latent_moe_decoder's block, as the program's config says it
EQUATIONS = {"attention_gate": "per_head", "rope_scaling": None,
             "moe_n_group": 1, "moe_topk_group": 1, "hc_streams": 1}
DEFAULTS = {"attention_gate": "none", "rope_scaling": None, "moe_n_group": 1,
            "moe_topk_group": 1, "hc_streams": 1}


def blocks(arch: dict) -> tuple:
    """The kind of every block's attention: the pattern is ``latent |
    window_latent``, then ``mlp | moe``, a block, the dense blocks leading."""
    pattern = list(arch.get("layer_pattern") or ())
    ops, ffns = pattern[0::2], pattern[1::2]
    dense = sum(k == "mlp" for k in ffns)
    if (not pattern or len(pattern) % 2 or not set(ops) <= set(KINDS)
            or ffns != ["mlp"] * dense + ["moe"] * (len(ffns) - dense)):
        raise SystemExit(
            "layered_latent_moe_decoder: layer_pattern is (latent | "
            "window_latent, mlp | moe) a block, the dense blocks leading; the "
            f"configuration states {pattern}")
    return tuple(KINDS[k] for k in ops)


def as_latent_blocks(arch: dict) -> dict:
    """``arch`` with every attention layer named ``latent``: what
    ``views/latent_moe_decoder.py`` checks and reads of a block is the same in
    both kinds."""
    return {**arch, "layer_pattern": ["latent" if i % 2 == 0 else kind
                                      for i, kind in enumerate(arch["layer_pattern"])]}


def sizes(arch: dict, prefix: str = "") -> tuple:
    """``(n, q_lora, kv_lora, nope, rope, v, rope_base)`` of a kind."""
    return (*(arch[f"{prefix}{k}"] for k in SIZES),
            float(arch.get(f"{prefix}rotary_embedding_base", 10000)))


def reference_spec(arch: dict) -> dict:
    other = {k: arch.get(k, DEFAULTS[k]) for k, v in EQUATIONS.items()
             if arch.get(k, DEFAULTS[k]) != v}
    missing = [k for k in INDEX_KEYS if arch.get(k) is None]
    if other or missing:
        raise SystemExit(
            f"layered_latent_moe_decoder: the reference computes {EQUATIONS} "
            f"and an indexer; the configuration states {other}, lacks {missing}")
    kinds = blocks(arch)
    # the FFNs and what the blocks share: latent_moe_decoder's check
    spec = latent.reference_spec(as_latent_blocks(arch))
    for key in ("num_heads", "kv_lora", "nope", "rope", "v", "rope_base", "yarn"):
        del spec[key]
    return {**spec, "kinds": kinds,
            "sizes": (sizes(arch), sizes(arch, "window_latent_")),
            "window": arch.get("window_size"), "hidden": arch["hidden_size"],
            "rescale": bool(arch.get("latent_lora_rescale", False)),
            "index_heads": arch["index_n_heads"],
            "index_dim": arch["index_head_dim"], "index_topk": arch["index_topk"]}


def reference_weights(params: dict, arch: dict) -> dict:
    """``latent_moe_decoder``'s layout (same arrays, no copy, no cast), every
    layer with its gate, a full layer with the indexer's four leaves."""
    kinds = blocks(arch)
    weights = latent.reference_weights(params, as_latent_blocks(arch))
    for i, (kind, layer) in enumerate(zip(kinds, weights["layers"])):
        mixer = params[f"layer_{2 * i + 1}"]["mixer"]
        layer["head_gate"] = mixer["gate"]["weight"]
        if kind == "full":
            layer.update({name: mixer[leaf]["weight"]
                          for name, leaf in INDEX_LEAVES.items()})
            layer["index_k_norm"] = mixer["index_k_norm"]
    return weights


def train_flops_per_token(arch: dict, param_shapes, seq_len: int) -> float:
    """A token works every matrix but the embedding table and the routed
    experts it does not use; attention's term a full layer over the lines a
    query chooses, a window layer over its window at most. The program does
    not train this stack: the contract's function, used by no cell."""
    held = latent.expert_param_count(arch, param_shapes)
    at_work = (dense_decoder.matmul_param_count(param_shapes) - held
               + held * arch["moe_top_k"] // arch["moe_num_experts"])
    kinds = blocks(arch)
    full = ops_count.train_flops_per_token(
        at_work, kinds.count("full"), arch["num_attention_heads"],
        arch["qk_nope_head_dim"] + arch["qk_rope_head_dim"],
        min(seq_len, 2 * arch["index_topk"]))
    return full + ops_count.train_flops_per_token(
        0, kinds.count("window"), arch["window_latent_num_attention_heads"],
        arch["window_latent_qk_nope_head_dim"] + arch["window_latent_qk_rope_head_dim"],
        min(seq_len, 2 * (arch.get("window_size") or seq_len)))
