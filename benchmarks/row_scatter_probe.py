"""ROADMAP S21: the sum of cotangent rows into an embedding table
(``ops/row_scatter.py`` ``scatter_add_rows``) against XLA's ``scatter`` at a
cell's shape, by index set.

    python benchmarks/row_scatter_probe.py                  # on one chip
    python benchmarks/row_scatter_probe.py --rows 4096 --width 4096 --table 32000
    JAX_PLATFORMS=cpu python benchmarks/row_scatter_probe.py --smoke

The default shape is ``train-pharia7b-4chip``'s (both data ranks' 8,192 rows
of 2,304 columns into a model rank's ``[64000, 2304]`` shard; ids over the
128,000-word vocabulary, so about half fall outside); the second line is
``train-mistral7b-1chip``'s whole table, which XLA's scatter still sums (S21).
Times are the HOST's clock around ``block_until_ready`` over 20 calls of one
jitted program each (milliseconds; these calls are 0.7-9 ms, far above a
dispatch): not a baseline, a speed is a line of the ledger. Each kernel line
also says how far its answer lies from a float32 scatter-add, beside XLA's
bf16 scatter-add's distance. ``--smoke`` runs a toy shape with the kernel
interpreted and prints no time worth reading.
"""

import argparse
import functools
import math
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--rows", type=int, default=8192)
    parser.add_argument("--width", type=int, default=2304)
    parser.add_argument("--table", type=int, default=64000)
    parser.add_argument("--vocab", type=int, default=128000)
    parser.add_argument("--smoke", action="store_true")
    args = parser.parse_args()
    import jax
    import jax.numpy as jnp

    from scaling_tpu.ops import row_scatter
    from scaling_tpu.ops.row_scatter import _inside, scatter_add_rows

    n, h, v, vocab = args.rows, args.width, args.table, args.vocab
    tilings = ((256, 128), (512, 128), (128, 128), (256, 256))
    if args.smoke:
        n, h, v, vocab, tilings = 512, 128, 640, 1280, ((64, 128),)
    key = jax.random.PRNGKey(0)
    log_uniform = jnp.exp(
        jax.random.uniform(key, (n,)) * math.log(vocab - 1)).astype(jnp.int32)
    index_sets = {
        "log-uniform, the rank of the low ids": log_uniform,
        "log-uniform, the other rank": log_uniform - v,
        "uniform": jax.random.randint(key, (n,), 0, vocab),
        "one row": jnp.full((n,), 17, jnp.int32),
    }
    rows = jax.random.normal(jax.random.PRNGKey(1), (n, h), jnp.bfloat16)

    def xla(dtype):
        return jax.jit(lambda ids, rows: jnp.zeros((v, h), dtype).at[
            _inside(ids, v)].add(rows.astype(dtype), mode="drop"))

    def milliseconds(f, *operands, calls=20):
        f(*operands).block_until_ready()
        start = time.perf_counter()
        for _ in range(calls):
            out = f(*operands)
        out.block_until_ready()
        return (time.perf_counter() - start) / calls * 1e3

    print(f"{n} rows of {h} into [{v}, {h}] on {jax.devices()[0].device_kind}")
    for name, ids in index_sets.items():
        inside = int(((ids >= 0) & (ids < v)).sum())
        want = xla(jnp.float32)(ids, rows)
        bf16 = xla(jnp.bfloat16)
        off = float(jnp.abs(bf16(ids, rows).astype(jnp.float32) - want).max())
        print(f"{name} ({inside} inside): XLA scatter bf16 "
              f"{milliseconds(bf16, ids, rows):.3f} ms, float32 "
              f"{milliseconds(xla(jnp.float32), ids, rows):.3f} ms", flush=True)
        for tiles in tilings:
            row_scatter._TILES = tiles  # read when ``kernel`` is traced
            kernel = jax.jit(functools.partial(
                scatter_add_rows, num_rows=v, interpret=args.smoke))
            got = kernel(ids, rows).astype(jnp.float32)
            print(f"    kernel {tiles}: {milliseconds(kernel, ids, rows):.3f} ms,"
                  f" max |kernel - float32| {float(jnp.abs(got - want).max()):.4g}"
                  f" (XLA bf16's {off:.4g}) of {float(jnp.abs(want).max()):.4g}",
                  flush=True)


if __name__ == "__main__":
    main()
