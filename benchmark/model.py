"""From a configuration file to the program's model: config, weights, the
reference's view of the same weights, and parameter counts.

This is the only place the benchmark names fields of ``scaling_tpu``'s
config or leaves of its parameter tree.
"""

from __future__ import annotations

import math
from typing import Optional


def prng_key(seed: int):
    """``--seed`` may exceed 32 signed bits; fold it in two halves."""
    import jax

    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)


def transformer_config(config: dict, traffic: dict, num_layers: Optional[int] = None):
    """The program's ``TransformerConfig`` for one cell: the architecture and
    topology of the configuration file, the optimizer settings of the
    traffic file (a training job), everything else the program's default."""
    from scaling_tpu.models.transformer import TransformerConfig

    arch = dict(config["transformer_architecture"])
    if num_layers is not None:
        arch["num_layers"] = num_layers
    if "sequence_length" in traffic:
        arch["sequence_length"] = traffic["sequence_length"]
    return TransformerConfig.from_dict({
        "topology": dict(config["topology"]),
        "transformer_architecture": arch,
        "optimizer": {
            "gradient_clipping": traffic.get("gradient_clipping", 1.0),
            "zero": bool(config.get("zero", False)),
            "loss_scaler": {"enable": False},
        },
        "learning_rate_scheduler": {
            "learning_rate": traffic.get("learning_rate", 3e-4),
            "learning_rate_warmup_steps": traffic.get("learning_rate_warmup_steps", 100),
            "learning_rate_decay_iters": traffic.get("learning_rate_decay_iters", 100000),
        },
        "trainer": {"train_iterations": 1, "seed": 0},
        "data": {},
        "logger": {"log_dir": None},
    })


def param_shardings(module):
    """The sharding ``module.shard_params`` would give each leaf, as a tree
    (None without a mesh), so that the weights can be made in place."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from scaling_tpu.nn import ParamMeta

    if module.topology is None:
        return None
    mesh = module.topology.mesh
    return jax.tree.map(
        lambda m: NamedSharding(mesh, P(*m.partition_spec)),
        module.param_metas(), is_leaf=lambda x: isinstance(x, ParamMeta))


def init_weights(module, seed: int):
    """The cell's weights, made on the device in ONE jitted call from the
    seed, in the type they are trained and served in (bf16), already placed
    where the mesh wants them."""
    import jax

    return jax.jit(module.init_params, out_shardings=param_shardings(module))(
        prng_key(seed))


def init_optimizer_state(optimizer, params):
    """The optimizer's fresh state (float32 masters, zero moments) made in
    ONE jitted call and placed as ``Optimizer.init_state`` places it: leaf by
    leaf on the host's clock it took 19 s of a four-chip run's set-up."""
    import jax

    shardings = jax.tree.map(
        lambda s: getattr(s, "sharding", None), optimizer.abstract_state(params))
    if optimizer.topology is None:
        shardings = None
    return jax.jit(optimizer.init_state, out_shardings=shardings)(params)


def param_shapes(module):
    import jax

    return jax.eval_shape(module.init_params, jax.random.PRNGKey(0))


def count_params(shapes) -> int:
    import jax

    return sum(math.prod(x.shape) for x in jax.tree.leaves(shapes))


def matmul_param_count(shapes) -> int:
    """Parameters that take part in a matrix multiplication: all but the
    input embedding table (a lookup)."""
    return count_params(shapes) - count_params(shapes["layer_0"])


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


def reference_weights(params: dict, num_layers: int) -> dict:
    """The program's parameter tree in the reference's plain layout (same
    arrays, no copy, no cast). Layout of the tree: ``layer_0`` embedding,
    ``layer_1..L`` blocks, ``layer_{L+1}`` final norm, ``layer_{L+2}`` head."""
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
