"""The program's DeepSeek-V3.2-Exp stack (``layer_pattern``: a block is TWO
single-mixer layers, a SPARSE ``latent`` attention then an ``mlp`` or ``moe``
FFN) as ``reference/sparse_latent_moe_decoder.py`` wants it.

The only place the benchmark names fields of ``scaling_tpu``'s config or
leaves of its parameter tree for this architecture; what the block shares
with Kimi-K2's is read through ``views/latent_moe_decoder.py``. ``arch`` is
the ``transformer_architecture`` of the configuration file. The reference
knows one set of equations; a configuration that states others is refused.
"""

from __future__ import annotations

from benchmark import ops_count
from benchmark.views import dense_decoder, latent_moe_decoder as latent

INDEX_KEYS = ("index_n_heads", "index_head_dim", "index_topk")
# the indexer's leaves in a sparse latent mixer, under the reference's names
INDEX_LEAVES = {"index_q": "index_q_proj", "index_k": "index_k_proj",
                "index_w": "index_w_proj"}


def reference_spec(arch: dict) -> dict:
    missing = [k for k in INDEX_KEYS if arch.get(k) is None]
    if missing:
        raise SystemExit("sparse_latent_moe_decoder: the reference computes the "
                         f"indexer; the configuration lacks {missing}")
    # the group limit is this reference's own; everything else is the block
    # latent_moe_decoder checks
    spec = latent.reference_spec({**arch, "moe_n_group": 1, "moe_topk_group": 1})
    return {**spec, "index_heads": arch["index_n_heads"],
            "index_dim": arch["index_head_dim"], "index_topk": arch["index_topk"],
            "n_group": arch.get("moe_n_group", 1),
            "topk_group": arch.get("moe_topk_group", 1)}


def reference_weights(params: dict, arch: dict) -> dict:
    """``latent_moe_decoder``'s layout (same arrays, no copy, no cast), every
    layer with the indexer's four leaves."""
    weights = latent.reference_weights(params, arch)
    for i, layer in enumerate(weights["layers"]):
        mixer = params[f"layer_{2 * i + 1}"]["mixer"]
        layer.update({name: mixer[leaf]["weight"] for name, leaf in INDEX_LEAVES.items()})
        layer["index_k_norm"] = mixer["index_k_norm"]
    return weights


def train_flops_per_token(arch: dict, param_shapes, seq_len: int) -> float:
    """As ``latent_moe_decoder``'s (a token works every matrix, the indexer's
    three among them, and ``moe_top_k / moe_num_experts`` of the held
    experts), attention's term over the lines a query CHOOSES: at most
    ``index_topk`` of a sequence. The program does not train this stack: the
    contract's function, used by no cell."""
    held = latent.expert_param_count(arch, param_shapes)
    at_work = (dense_decoder.matmul_param_count(param_shapes) - held
               + held * arch["moe_top_k"] // arch["moe_num_experts"])
    return ops_count.train_flops_per_token(
        at_work, sum(k == "latent" for k in arch["layer_pattern"]),
        arch["num_attention_heads"],
        arch["qk_nope_head_dim"] + arch["qk_rope_head_dim"],
        min(seq_len, 2 * arch["index_topk"]))
