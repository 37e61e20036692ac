"""Rows of a table by index, with a gradient that is not XLA's scatter.

``take_rows(table (v, h), ids (...))`` is ``table[ids]`` with zeros for an
index outside ``[0, v)``; its gradient is ``scatter_add_rows``: ``out[r]`` =
the sum of the cotangent rows whose index is ``r``, indices outside dropped.
It is what ``parallel/sharding.py`` ``lookup_rows_on_data_shard`` reads a
vocabulary-parallel embedding table through.

Why a kernel: on a TPU v5e XLA's ``scatter`` of row updates is a serial loop
over the UPDATES, 0.72 us each whatever they hold: 8,192 rows of 2,304 into
``[64000, 2304]`` take 6.4 ms in bf16 and 6.8 in float32, the same with 497
or 7,695 of them inside the table, uniform or log-uniform
(``benchmarks/row_scatter_probe.py`` on one chip, PERF.md PR 72; the step's
``fusion bf16[64000,2304]`` read 6.3 ms on four chips). Here the updates are sorted
by index (XLA's sort of 8,192 keys: 7 us) and the sum is a one-hot matmul
over what is left of the sparsity: the table is cut into blocks of ``tr``
rows, the sorted updates into chunks of ``tk``, and a grid step multiplies
ONE chunk's one-hot ``(tr, tk)`` into ONE block's float32 accumulator.
Sorted, a chunk touches consecutive blocks and a block consecutive chunks, so
the (block, chunk) pairs that can hold anything are a staircase of exactly
``blocks + chunks - 1`` steps whatever the indices are (a block no update
falls in is met once and written as zeros; all updates on one row are
``chunks`` steps on one block). The staircase is two scalar-prefetched int32
vectors; the output block stays in VMEM while its index repeats and is
written once, in the cotangent's type, from the float32 sum (on the chip the
answers are XLA's bf16 scatter-add's to the last bit of the probe's four
index sets: it sums in float32 as well).

Off the chip (``row_scatter_interpret`` -> None) the sum is XLA's scatter-add
in float32; ``interpret=True`` runs the kernel's arithmetic under the
interpreter (tests).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..obs import count_kernel_build

# rows of the table a block holds, updates a chunk holds: a step's matmul is
# (tr, tk) x (tk, h), its one-hot built from one lane row of tk indices
_TILES = (256, 128)


def row_scatter_interpret(platform: Optional[str] = None) -> Optional[bool]:
    """None off the chip (XLA's scatter-add stands in); on a TPU the kernel
    is always compiled."""
    return False if (platform or jax.default_backend()) == "tpu" else None


def _inside(ids: jax.Array, num_rows: int) -> jax.Array:
    """``ids`` with every index outside the table at ``num_rows``: past the
    last row, where a read is filled, an update dropped, and a sort puts it
    last."""
    return jnp.where((ids >= 0) & (ids < num_rows), ids, num_rows)


def _kernel(block_ref, chunk_ref, ids_ref, rows_ref, out_ref, acc_ref):
    from jax.experimental import pallas as pl

    del chunk_ref  # the index maps read it
    step, last_step = pl.program_id(0), pl.num_programs(0) - 1
    block = block_ref[step]
    tr, tk = out_ref.shape[0], rows_ref.shape[0]

    @pl.when((step == 0) | (block_ref[jnp.maximum(step - 1, 0)] != block))
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # an index of another block, or past the table, matches no row here
    local = ids_ref[...] - block * tr  # (1, tk)
    onehot = jax.lax.broadcasted_iota(jnp.int32, (tr, tk), 0) == local
    acc_ref[...] += jnp.dot(
        onehot.astype(rows_ref.dtype), rows_ref[...],
        preferred_element_type=jnp.float32,
        precision=(jax.lax.Precision.HIGHEST
                   if rows_ref.dtype == jnp.float32 else None))

    @pl.when((step == last_step)
             | (block_ref[jnp.minimum(step + 1, last_step)] != block))
    def _():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def _staircase(sorted_ids: jax.Array, num_rows: int, tr: int, tk: int):
    """``(block, chunk)`` of each of the ``blocks + chunks - 1`` steps: chunk
    ``c`` meets the blocks from its first index's (block 0 for the first
    chunk) to the next chunk's first (the last block for the last chunk)."""
    chunks, blocks = sorted_ids.shape[0] // tk, -(-num_rows // tr)
    first = jnp.minimum(sorted_ids[::tk], num_rows - 1) // tr
    lo = first.at[0].set(0)
    hi = jnp.concatenate([first[1:], jnp.full((1,), blocks - 1, jnp.int32)])
    count = hi - lo + 1
    start = jnp.cumsum(count) - count
    step = jnp.arange(blocks + chunks - 1, dtype=jnp.int32)
    chunk = (jnp.searchsorted(start, step, side="right") - 1).astype(jnp.int32)
    return lo[chunk] + step - start[chunk], chunk


@functools.partial(jax.jit, static_argnames=("num_rows", "tiles", "interpret"))
def _kernel_call(ids, rows, *, num_rows, tiles, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    tr, tk = tiles
    n, h = rows.shape
    tr = min(tr, num_rows)  # a block of the whole table needs no alignment
    ids = _inside(ids, num_rows)
    pad = -n % tk
    if pad:  # updates that fall outside the table
        ids = jnp.pad(ids, (0, pad), constant_values=num_rows)
        rows = jnp.pad(rows, ((0, pad), (0, 0)))
    order = jnp.argsort(ids)
    ids, rows = ids[order], rows[order]
    block, chunk = _staircase(ids, num_rows, tr, tk)
    chunks = ids.shape[0] // tk
    item = rows.dtype.itemsize
    # the accumulator and a step's product in float32, two buffers each of
    # the output block and the chunk
    need = tr * h * (8 + 2 * item) + 2 * tk * h * item
    out_shape = jax.ShapeDtypeStruct(
        (num_rows, h), rows.dtype, vma=jax.typeof(rows).vma)
    return pl.pallas_call(
        _kernel,
        out_shape=out_shape,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(block.shape[0],),
            in_specs=[
                pl.BlockSpec((None, 1, tk), lambda s, blk, chk: (chk[s], 0, 0)),
                pl.BlockSpec((tk, h), lambda s, blk, chk: (chk[s], 0)),
            ],
            out_specs=pl.BlockSpec((tr, h), lambda s, blk, chk: (blk[s], 0)),
            scratch_shapes=[pltpu.VMEM((tr, h), jnp.float32)],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=max(16 << 20, need * 3 // 2)),
        interpret=interpret,
        name="scatter_add_rows",
    )(block, chunk, ids.reshape(chunks, 1, tk), rows)


def scatter_add_rows(
    ids: jax.Array,      # (n,) int32
    rows: jax.Array,     # (n, h)
    num_rows: int,
    *,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """``(num_rows, h)`` in ``rows.dtype``: ``out[r]`` is the float32 sum of
    ``rows[i]`` over ``ids[i] == r``; an index outside ``[0, num_rows)`` is
    dropped."""
    if interpret is None:
        interpret = row_scatter_interpret()
    if interpret is None:
        return jnp.zeros((num_rows, rows.shape[1]), jnp.float32).at[
            _inside(ids, num_rows)
        ].add(rows.astype(jnp.float32), mode="drop").astype(rows.dtype)
    count_kernel_build("scatter_add_rows", interpret)
    return _kernel_call(ids.astype(jnp.int32), rows, num_rows=num_rows,
                        tiles=_TILES,
                        interpret=interpret)


@jax.custom_vjp
def take_rows(table: jax.Array, ids: jax.Array) -> jax.Array:
    """``table[ids]`` ``(..., h)``, zeros where an index is outside the
    table; differentiable in ``table`` through :func:`scatter_add_rows`."""
    return table.at[_inside(ids, table.shape[0])].get(
        mode="fill", fill_value=0)


def _take_rows_fwd(table, ids):
    # the table's height rides on an array of no bytes: residuals are arrays
    return take_rows(table, ids), (ids, jnp.empty((table.shape[0], 0)))


def _take_rows_bwd(residuals, cotangent):
    ids, height = residuals
    grad = scatter_add_rows(
        ids.reshape(-1), cotangent.reshape(-1, cotangent.shape[-1]),
        height.shape[0])
    return grad, np.zeros(ids.shape, jax.dtypes.float0)


take_rows.defvjp(_take_rows_fwd, _take_rows_bwd)
