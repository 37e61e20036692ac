"""sha256 of the lowered (StableHLO) text of every one-chip cell's model
program, on one CPU device: the train step of a train cell, the uncached
inference pass of a serve cell, over abstract weights (nothing runs).

    python benchmarks/lowered_hash.py     # from the root of a checkout

Run from two checkouts, equal lines say a change left those cells' programs
as they were (PR 58: the sequence-parallel region boundaries are taken only
on a mesh with a model axis). Lowering only: no TPU, no time measured, a few
GB of host memory for the text of the largest configuration.

What a serve cell's line does NOT cover: the mixed program its ticks run
(``ServeEngine``'s, over the paged pool). ROADMAP D22 is that gap, and this
script goes when the audit's goldens hold those programs.
"""

import hashlib
import json
import os
import sys
from pathlib import Path


def lowered_text(cell) -> tuple:
    """(what was lowered, its text) for one cell of BENCHMARK.json."""
    import jax
    import jax.numpy as jnp

    from benchmark import model
    from scaling_tpu.models.transformer.inference import TransformerInferenceModule
    from scaling_tpu.models.transformer.model import (
        init_model, init_optimizer, loss_function,
    )
    from scaling_tpu.topology import Topology

    if cell.traffic.get("kind") == "train":
        config = model.transformer_config(cell.config, cell.traffic)
        topology = Topology(config.topology, devices=jax.devices()[:1])
        module = init_model(config, topology)
        optimizer = init_optimizer(config, module, topology)
        params = jax.eval_shape(module.init_params, jax.random.PRNGKey(0))
        seq = config.transformer_architecture.sequence_length
        ids = jax.ShapeDtypeStruct(
            (1, config.topology.micro_batch_size, seq), jnp.int32)
        batch = {key: ids for key in ("token_ids", "target_token_ids",
                                      "position_ids", "segment_ids")}
        batch["loss_weights"] = jax.ShapeDtypeStruct(ids.shape, jnp.float32)
        step = module.build_train_step(optimizer, loss_function)
        return "train step", step.lower(
            params, optimizer.abstract_state(params), batch,
            jax.ShapeDtypeStruct((2,), jnp.uint32)).as_text()
    config = model.transformer_config(cell.config, {})
    module = init_model(config, None)
    params = jax.eval_shape(module.init_params, jax.random.PRNGKey(0))
    inf = TransformerInferenceModule(config, module, None)
    ids = jax.ShapeDtypeStruct((2, 128), jnp.int32)

    def run(p, tokens, positions):
        return inf._run_layers(p, inf._make_batch(tokens, positions), None, None)[0]

    return "inference pass (_run_layers, uncached)", jax.jit(run).lower(
        params, ids, ids).as_text()


def main() -> None:
    os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path.insert(0, os.getcwd())
    from benchmark import cells

    bench = json.loads(Path("BENCHMARK.json").read_text())
    for entry in bench["workloads"]:
        if entry["chips"] != 1:
            continue
        what, text = lowered_text(cells.load_cell(entry["name"]))
        print(entry["name"], what, len(text),
              hashlib.sha256(text.encode()).hexdigest()[:20], flush=True)


if __name__ == "__main__":
    main()
