"""Launch the transformer example:
``python -m examples.transformer_example.run examples/transformer_example/config.yml``

(reference: examples/transformer_example/run.py — config.yml -> runner;
single-host SPMD needs no launcher, so the config feeds main() directly.
For multi-host pods use ``scaling_tpu.runner.runner_main``.)

Generates a tiny synthetic token dataset next to the config on first run.
"""

import sys
from pathlib import Path

import numpy as np

from scaling_tpu.compile_cache import enable_compile_cache
from scaling_tpu.logging import logger
from scaling_tpu.models.transformer import TransformerConfig
from scaling_tpu.models.transformer.train import main


def ensure_example_data(config: TransformerConfig) -> None:
    """Synthesize a zipf-ish token stream if the data prefix is absent."""
    from scaling_tpu.data.memory_map import MemoryMapDatasetBuilder

    prefixes = config.data.data_prefixes or []
    for prefix in prefixes:
        prefix = Path(prefix)
        if prefix.with_suffix(".bin").exists():
            continue
        prefix.parent.mkdir(parents=True, exist_ok=True)
        logger.info(f"generating synthetic example data at {prefix}")
        rng = np.random.default_rng(0)
        vocab = config.transformer_architecture.vocab_size
        with MemoryMapDatasetBuilder(prefix, dtype=np.uint16) as builder:
            for _ in range(512):
                n = int(rng.integers(32, 256))
                doc = (rng.zipf(1.5, size=n) % (vocab - 1)) + 1
                builder.add(np.append(doc, 0).astype(np.uint16))


if __name__ == "__main__":
    config_path = (
        sys.argv[1] if len(sys.argv) > 1 else Path(__file__).parent / "config.yml"
    )
    config = TransformerConfig.from_yaml(config_path)
    ensure_example_data(config)
    enable_compile_cache()
    main(config)
