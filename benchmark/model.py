"""From a configuration file to the program's model: its config, its
weights and optimizer state made on the device, parameter shapes and counts.

What depends on the architecture (the reference's view of the weights, the
operations a trained token requires) is in ``views/``, one file a reference.
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


def engine_config(engine: dict):
    """The program's ``EngineConfig`` for a configuration's ``"engine"``
    object. ``num_slots`` and ``context`` (tokens a slot may hold) size the
    KV pool: ``context // block_size`` blocks a sequence, that times the
    slots plus the trash block in all. Every further key is a field of
    ``EngineConfig`` by name and overrides what was derived (a pool sized
    from the traffic, window pools, int8); one it does not have is an error
    that names it."""
    import dataclasses

    from scaling_tpu.serve.engine import EngineConfig

    given = dict(engine)
    slots, context = int(given.pop("num_slots")), int(given.pop("context"))
    fields = sorted(f.name for f in dataclasses.fields(EngineConfig))
    unknown = sorted(set(given) - set(fields))
    if unknown:
        raise SystemExit(
            f"benchmark: the configuration's \"engine\" has {unknown}, which "
            f"EngineConfig does not (known besides num_slots and context: {fields})")
    blocks_per_seq = context // int(given.get("block_size", EngineConfig().block_size))
    return EngineConfig(**{
        "num_slots": slots,
        "num_blocks": slots * blocks_per_seq + 1,  # + the trash block
        "max_blocks_per_seq": blocks_per_seq,
        **given,
    })
