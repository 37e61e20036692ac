"""Pallas ABSORBED latent attention of rows' queries over their own RINGS of
latent lines under a sliding window (the windowed latent layer's served rows:
``nn/window_latent_attention.py``).

A windowed latent layer keeps, a slot, a ring of ``ring`` lines ``[c_kv
(lat), k_r, zeros]``, position ``p`` at line ``p % ring``. In the absorbed
form every head attends over such a line as over ONE shared KV head whose
value is the line's own first ``lat`` lanes ("the tile as key and value":
``nn/latent_paged_attention.py`` does it over pages), so a line is read once
for all the heads:

    scores[(p, j), k] = scale * q_line[p, j] . line[k]
    out[(p, j)]       = softmax_k(scores where visible[p, k]) @ line[k, :lat]

The ring is ``nn/window_ring_attention.py``'s: nothing is gathered and no mask
is built (a row's slot is a scalar-prefetched block index, the mask is
computed in the kernel from the position of every query row and the position
every line holds), only the tiles that hold a line of the row's ARC are
fetched and folded, the rows are a grid axis. What differs is the line: one
leaf, no head axis, the heads all in the matmul's rows (a query block is
``(positions x heads, line lanes)``, position-major, a plain reshape), and no
second operand for the values. A block of ``QUERY_POSITIONS`` positions x 64
heads x 1,152 lanes is 2.4 MB in bf16 and its float32 sum over 1,024 lanes
4.2 MB: four times the positions would not fit beside the tiles. A query that
sees nothing (padding, another row's token) gives zeros. Off-TPU the kernel
runs interpreted.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from . import paged_attention as _paged
from .window_ring_attention import NOBODY, line_positions

KERNEL_NAME = "latent_ring_attention"
# positions whose heads are one matmul's rows
QUERY_POSITIONS = 16
VMEM_LIMIT_BYTES = 48 << 20


def _kernel(slot_ref, first_ref, count_ref, q_ref, line_ref, at_ref, held_ref,
            o_ref, m_ref, l_ref, acc_ref, *, window: int, lat: int,
            sm_scale: float):
    pl = _paged.pl
    row, step = pl.program_id(0), pl.program_id(2)

    @pl.when(step == 0)
    def _clear():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(step < count_ref[row])
    def _fold():
        precision = (None if q_ref.dtype == jnp.float32
                     else jax.lax.Precision.DEFAULT)
        at = at_ref[...]                                    # (rows, 1)
        held = held_ref[...]                                # (1, tile)
        visible = (held >= 0) & (held <= at) & (held > at - window)
        lines = line_ref[...]                               # (tile, lanes)
        scores = jax.lax.dot_general(
            q_ref[...], lines, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=precision)
        scores = jnp.where(visible, scores * sm_scale, -jnp.inf)
        m_old = m_ref[...]
        m_new = jnp.maximum(m_old, scores.max(axis=-1, keepdims=True))
        m_safe = jnp.where(m_new == -jnp.inf, 0.0, m_new)
        p = jnp.exp(scores - m_safe)
        alpha = jnp.exp(m_old - m_safe)
        l_ref[...] = alpha * l_ref[...] + p.sum(axis=-1, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + jnp.dot(
            p.astype(lines.dtype), lines[:, :lat],
            preferred_element_type=jnp.float32, precision=precision)
        m_ref[...] = m_new

    @pl.when(step == pl.num_programs(2) - 1)
    def _finish():
        total = l_ref[...]
        o_ref[...] = (acc_ref[...] / jnp.where(total == 0.0, 1.0, total)
                      ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "window", "lat", "tile", "sm_scale", "interpret"))
def latent_ring_attention(
    q: jax.Array,         # (rows, positions, n, lanes) absorbed queries
    #                       [q_nope W_UK^T, q_rope after rotary, zeros]
    ring: jax.Array,      # (slots, ring, lanes): position p at line p % ring
    slot: jax.Array,      # (rows,) int32: the slot whose ring a row reads
    at: jax.Array,        # (rows, positions) int32: a query's position; NOBODY
    #                       for one that is nobody's
    last: jax.Array,      # (rows,) int32: the last position a row has written
    first: jax.Array,     # (rows,) int32: the first position a row's queries see
    live: jax.Array,      # (rows,) bool: rows that take part
    *,
    window: int,
    lat: int,             # the line's leading lanes that are its value
    tile: int,
    sm_scale: float,
    interpret: bool,
) -> jax.Array:
    """``(rows, positions, n, lat)``; a query that sees nothing gives zeros."""
    _paged._ensure_pallas()
    pl, pltpu = _paged.pl, _paged.pltpu
    num_rows, positions, n, lanes = q.shape
    slots, lines, width = ring.shape
    assert width == lanes and lat <= lanes and lines % tile == 0, (
        q.shape, ring.shape, lat, tile)
    tiles = lines // tile
    # whole sublane groups of matmul rows: any count of positions at heads
    # that are a multiple of 8
    unit = 8 // math.gcd(8, n)
    block = min(QUERY_POSITIONS, -(-positions // unit) * unit)
    pad = -positions % block
    padded = positions + pad
    rows = block * n
    folded = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0))).reshape(
        num_rows, padded * n, lanes)
    at = jnp.pad(at.astype(jnp.int32), ((0, 0), (0, pad)), constant_values=NOBODY)
    at = jnp.where(live[:, None], at, NOBODY)
    at_rows = jnp.repeat(at, n, axis=1)[:, :, None]        # (rows, padded x n, 1)
    last = last.astype(jnp.int32)
    held = line_positions(last, lines)[:, None, :]
    # the arc: positions [first, last], from line first % ring on
    first = jnp.maximum(first.astype(jnp.int32), 0)
    start = first % lines
    count = jnp.minimum((start % tile + (last - first)) // tile + 1, tiles)
    count = jnp.where(live & (last >= first), count, 0).astype(jnp.int32)
    first_tile = (start // tile).astype(jnp.int32)

    def tile_of(r, j, first_ref, count_ref):
        """The ring tile a step reads: the arc's tiles in ring order, past
        them the last of them again."""
        return (first_ref[r] + jnp.minimum(
            j, jnp.maximum(count_ref[r] - 1, 0))) % tiles

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(num_rows, padded // block, tiles),
        in_specs=[
            pl.BlockSpec((None, rows, lanes), lambda r, i, j, *_: (r, i, 0)),
            pl.BlockSpec((None, tile, lanes),
                         lambda r, i, j, s, f, c: (s[r], tile_of(r, j, f, c), 0)),
            pl.BlockSpec((None, rows, 1), lambda r, i, j, *_: (r, i, 0)),
            pl.BlockSpec((None, 1, tile),
                         lambda r, i, j, s, f, c: (r, 0, tile_of(r, j, f, c))),
        ],
        out_specs=pl.BlockSpec((None, rows, lat), lambda r, i, j, *_: (r, i, 0)),
        scratch_shapes=[
            pltpu.VMEM((rows, 1), jnp.float32),       # running max
            pltpu.VMEM((rows, 1), jnp.float32),       # normalizer
            pltpu.VMEM((rows, lat), jnp.float32),     # unnormalized sum
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, window=window, lat=lat, sm_scale=sm_scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((num_rows, padded * n, lat), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
        name=KERNEL_NAME,  # the trace's and the HLO's name for it
    )(slot.astype(jnp.int32), first_tile, count, folded, ring, at_rows, held)
    return out.reshape(num_rows, padded, n, lat)[:, :positions]
