"""The paged kernel ALONE at each cell's shape and at the shapes no cell times
(ROADMAP S11: what is left of ``nn/paged_attention.py`` after PR 69's sub-tiles;
chip only, ``--smoke`` with ``JAX_PLATFORMS=cpu`` rehearses it at a toy size):

    python benchmarks/paged_kernel_shapes.py [--root DIR] [--only NAME,..]
        [--held N,..] [--kinds decode,chunk] [--smoke]

For every shape (a cell's slots, heads, query block and table, or one that no
cell serves: an int8 pool, Pharia's group of 9) and every number of lines a row
holds (100 / 300 / 512 / 7,000 where a slot is that long; ``--held 620`` is what
``qwen3next``'s rows hold in its cell), one call of
``paged_decode_attention`` over rows that all hold that many lines, their blocks
scattered through the pool: ``decode`` rows of one token (the short path), and
``chunk`` rows that bring a whole query block (the full-width path). The call is
jitted alone, run ``CALLS`` times under ``jax.profiler.trace``, and its time is
the DEVICE's: the median duration of the trace's ``paged_attention`` events
(``benchmark/trace_reduce.py`` reads them), never the host's clock. One JSON line
a measurement: microseconds a call and a row, and the GB/s at which the rows' K
and V lines (and an int8 pool's scales) moved, of the chip's 819.

``--root DIR`` takes ``scaling_tpu`` from another checkout (the parent's, to set
two kernels side by side on one machine: run the script once a checkout, each
run its own process).
"""

import argparse
import json
import statistics
import sys
import tempfile
from pathlib import Path

HERE = Path(__file__).resolve().parent.parent
CALLS = 20
BLOCK = 16
# name: slots, query heads, KV heads, head width, query block, blocks a slot,
# pool dtype, masked. A cell's own engine shape unless it says otherwise
SHAPES = {
    "mistral7b": (16, 32, 8, 128, 32, 256, "bf16", False),     # both Mistral cells
    "olmoe": (16, 16, 16, 128, 32, 256, "bf16", False),
    "ouro-looped": (16, 16, 16, 128, 32, 40, "bf16", False),
    "nemotron3nano": (64, 32, 2, 128, 32, 40, "bf16", False),
    "qwen3next": (256, 16, 2, 256, 32, 128, "bf16", False),    # heads of 256 lanes
    "lfm2": (64, 32, 8, 64, 32, 40, "bf16", False),            # two heads a lane row
    "falconh1": (96, 20, 4, 128, 32, 40, "bf16", False),
    "keye-one-token": (8, 32, 4, 128, 1, 4096, "bf16", True),  # under its choice
    "laguna-full": (24, 48, 8, 128, 256, 2048, "bf16", False),
    # served by no cell
    "mistral7b-int8": (16, 32, 8, 128, 32, 256, "int8", False),
    "pharia-group9": (16, 36, 4, 128, 32, 256, "bf16", False),
}
HELD = (100, 300, 512, 7000)
SMOKE_SHAPES = {
    "toy": (3, 4, 2, 16, 4, 40, "bf16", False),
    "toy-int8-masked": (3, 4, 2, 16, 1, 40, "int8", True),
    "toy-head-major": (3, 4, 2, 256, 4, 40, "bf16", False),
}


def operands(shape, held, kind, seed=0):
    """The call's arguments: every row holds ``held`` lines and brings one token
    (``decode``) or a query block's (``chunk``); a pool of just the blocks held,
    scattered; a mask that keeps every other line."""
    import jax
    import jax.numpy as jnp

    from scaling_tpu.nn import paged_attention
    from scaling_tpu.nn.attention import kv_quantize_int8

    slots, n, n_kv, h, s, max_blocks, dtype, masked = shape
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    blocks = -(-held // BLOCK)
    pool_dims = (slots * blocks + 1, BLOCK, n_kv, h)
    pools = [jax.random.normal(k, pool_dims, jnp.bfloat16) for k in keys[:2]]
    scales = {}
    if dtype == "int8":
        (pools[0], sk), (pools[1], sv) = map(kv_quantize_int8, pools)
        scales = {"scale_k": sk, "scale_v": sv}
    else:   # as init_pools makes a native pool
        packed = paged_attention.packed_kv_dims(n_kv, h)
        pools = [p.reshape(*pool_dims[:2], *packed) for p in pools]
        # (a checkout from before PR 74 has token-major pools alone)
        dims = getattr(paged_attention, "kv_pool_dims", None)
        if dims and dims(BLOCK, n_kv, h, 2)[1] == 1:   # head-major blocks
            pools = [p.transpose(0, 2, 1, 3) for p in pools]
    table = 1 + jax.random.permutation(keys[2], slots * blocks).reshape(slots, blocks)
    table = jnp.pad(table.astype(jnp.int32), ((0, 0), (0, max_blocks - blocks)))
    new = min(held, s) if kind == "chunk" else 1
    valid = jnp.full((slots,), held, jnp.int32)
    if masked:
        scales["chosen"] = jnp.broadcast_to(
            jnp.arange(max_blocks * BLOCK) % 2 == 0, (slots, max_blocks * BLOCK))
    q = jax.random.normal(keys[3], (slots, s, n, h), jnp.bfloat16)
    return (q, *pools, table, valid, valid - new), scales


def device_us(trace_dir):
    """Durations of the trace's ``paged_attention`` events, microseconds."""
    from benchmark.trace_reduce import load_events, stem

    (path,) = Path(trace_dir).rglob("*.xplane.pb")
    return [dur / 1e3 for device in load_events(path)["devices"].values()
            for name, _, dur in device["ops"] if stem(name) == "paged_attention"]


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--root", default=str(HERE))
    parser.add_argument("--only", default="")
    parser.add_argument("--held", default="")
    parser.add_argument("--kinds", default="decode,chunk")
    parser.add_argument("--smoke", action="store_true")
    args = parser.parse_args()
    sys.path[:0] = [args.root, str(HERE)]
    import jax

    from scaling_tpu.nn.paged_attention import paged_decode_attention

    if not args.smoke and jax.default_backend() != "tpu":
        sys.exit("paged_kernel_shapes.py measures a TPU; JAX found "
                 f"{jax.default_backend()} (--smoke rehearses it)")
    shapes = SMOKE_SHAPES if args.smoke else SHAPES
    only = [name for name in args.only.split(",") if name] or list(shapes)
    helds = [int(n) for n in args.held.split(",") if n] or HELD
    for name in only:
        slots, n, n_kv, h, s, max_blocks, dtype, _ = shape = shapes[name]
        attend = jax.jit(lambda ops, kw: paged_decode_attention(
            *ops, sm_scale=h ** -0.5, num_repeat_kv=n // n_kv, **kw))
        for held in helds:
            if held > max_blocks * BLOCK:
                continue
            for kind in args.kinds.split(","):
                if kind == "chunk" and s == 1:
                    continue
                ops, kw = operands(shape, held, kind)
                jax.block_until_ready(attend(ops, kw))
                with tempfile.TemporaryDirectory() as trace_dir:
                    with jax.profiler.trace(trace_dir):
                        for _ in range(1 if args.smoke else CALLS):
                            out = attend(ops, kw)
                        jax.block_until_ready(out)
                    calls = device_us(trace_dir)
                line = {"device": jax.devices()[0].device_kind, "root": args.root,
                        "shape": name, "slots": slots, "group": n // n_kv,
                        "kv_heads": n_kv, "query_block": s, "pool": dtype,
                        "kind": kind, "held": held, "calls": len(calls)}
                if calls:   # the CPU's trace has no device plane: not measured
                    us = statistics.median(calls)
                    # K and V lines of the pool's dtype, an int8 pool's scales
                    line_bytes = 2 * n_kv * (h * (1 if dtype == "int8" else 2)
                                             + 4 * (dtype == "int8"))
                    line.update(us_a_call=us, us_a_row=us / slots,
                                gb_s=slots * held * line_bytes / us / 1e3)
                print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
