"""Pallas grouped-query attention of rows' queries over their own RINGS of K
and V lines under a sliding window (the window attention layer's served rows:
``nn/window_attention.py``).

A window layer keeps, a slot, a ring of ``ring`` lines, position ``p`` at line
``p % ring``. A row's queries see the lines that hold positions ``(t - window,
t]``: an ARC of the ring, contiguous but for the wrap. The kernel is the dense
stream under a mask, flash-style, as ``nn/masked_gqa_attention.py``, with what
the ring allows on top:

- nothing is gathered and no mask is built: K and V are read from the rings
  where they lie (a row's slot is a scalar-prefetched block index), and the
  mask is computed in the kernel from two small operands, the position of
  every query row and the position every line holds (``(t - window < held <=
  t) and held >= 0``);
- only the tiles that hold a line of the row's arc are fetched and folded:
  the grid's key axis starts at the arc's first tile and goes round the ring,
  and past the arc's last tile the block index repeats (an unchanged block is
  not fetched twice) and nothing is folded;
- the rows are a grid axis: one call serves every row of a kind (all the
  tick's one-token rows at once; a chunk row alone), a row that takes no part
  folding nothing.

    scores[g, (p, j), k] = scale * q[p, g * group + j] . keys[k, g]
    out[g, (p, j)]       = softmax_k(scores where visible[p, k]) @ values[k, g]

The GQA group is folded into the matmul's rows (per KV head the queries of a
block are ``(positions x group, h)``, position-major), the KV heads are a loop
over lane-aligned column blocks of a key tile, a key tile is folded into a
float32 online softmax held in scratch across the tile axis. A query that sees
nothing (padding, another row's token) gives zeros. Off-TPU the kernel runs
interpreted.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import paged_attention as _paged

KERNEL_NAME = "window_ring_attention"
# positions whose heads are one matmul's rows (as masked_gqa_attention's)
QUERY_POSITIONS = 64
VMEM_LIMIT_BYTES = 48 << 20
# the position of a query row that is nobody's: it sees no line
NOBODY = -(1 << 30)


def _kernel(slot_ref, first_ref, count_ref, q_ref, k_ref, v_ref, at_ref,
            held_ref, o_ref, m_ref, l_ref, acc_ref, *, window: int,
            sm_scale: float):
    pl = _paged.pl
    row, step = pl.program_id(0), pl.program_id(2)
    n_kv, rows, h = q_ref.shape

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
        for g in range(n_kv):
            q = q_ref[g]
            k = k_ref[:, g * h:(g + 1) * h]
            v = v_ref[:, g * h:(g + 1) * h]
            scores = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32, precision=precision)
            scores = jnp.where(visible, scores * sm_scale, -jnp.inf)
            m_old = m_ref[g]
            m_new = jnp.maximum(m_old, scores.max(axis=-1, keepdims=True))
            m_safe = jnp.where(m_new == -jnp.inf, 0.0, m_new)
            p = jnp.exp(scores - m_safe)
            alpha = jnp.exp(m_old - m_safe)
            l_ref[g] = alpha * l_ref[g] + p.sum(axis=-1, keepdims=True)
            acc_ref[g] = alpha * acc_ref[g] + jnp.dot(
                p.astype(v.dtype), v,
                preferred_element_type=jnp.float32, precision=precision)
            m_ref[g] = m_new

    @pl.when(step == pl.num_programs(2) - 1)
    def _finish():
        total = l_ref[...]
        o_ref[...] = (acc_ref[...] / jnp.where(total == 0.0, 1.0, total)
                      ).astype(o_ref.dtype)


def line_positions(last: jax.Array, ring: int) -> jax.Array:
    """The position each of a ring's lines holds once the row's tokens up to
    position ``last`` (..., int32) are written: ``(..., ring)``; negative: not
    this row's."""
    lines = jnp.arange(ring, dtype=jnp.int32)
    last = last[..., None]
    return last - ((last - lines) % ring)


def ring_tile(ring: int, most: int) -> int:
    """The largest power of two of at most ``most`` lines that divides
    ``ring``."""
    tile = 1
    while tile * 2 <= most and ring % (tile * 2) == 0:
        tile *= 2
    return tile


@functools.partial(jax.jit, static_argnames=(
    "window", "tile", "sm_scale", "interpret"))
def window_ring_attention(
    q: jax.Array,         # (rows, positions, n, h) rotary-applied queries
    ring_k: jax.Array,    # (slots, ring, n_kv x h): position p at line p % ring,
    #                       a line's heads side by side (no reshape: a ring of
    #                       (.., n_kv, h) would be copied whole to be read so)
    ring_v: jax.Array,    # (slots, ring, n_kv x h)
    slot: jax.Array,      # (rows,) int32: the slot whose rings a row reads
    at: jax.Array,        # (rows, positions) int32: a query's position; NOBODY
    #                       for one that is nobody's
    last: jax.Array,      # (rows,) int32: the last position a row has written
    first: jax.Array,     # (rows,) int32: the first position a row's queries see
    #                       (at its first query: max(t - window + 1, 0))
    live: jax.Array,      # (rows,) bool: rows that take part
    *,
    window: int,
    tile: int,
    sm_scale: float,
    interpret: bool,
) -> jax.Array:
    """``(rows, positions, n, h)``; a query that sees nothing gives zeros."""
    _paged._ensure_pallas()
    pl, pltpu = _paged.pl, _paged.pltpu
    num_rows, positions, n, h = q.shape
    slots, ring, width = ring_k.shape
    n_kv = width // h
    group = n // n_kv
    assert n == n_kv * group and ring % tile == 0, (n, n_kv, ring, tile)
    tiles = ring // tile
    block = min(QUERY_POSITIONS, -(-positions // 8) * 8)
    pad = -positions % block
    padded = positions + pad
    rows = block * group
    # fold the GQA group into the matmul's rows: per KV head (positions x
    # group, h), position-major
    folded = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0))).reshape(
        num_rows, padded, n_kv, group, h).transpose(0, 2, 1, 3, 4).reshape(
            num_rows, n_kv, padded * group, h)
    at = jnp.pad(at.astype(jnp.int32), ((0, 0), (0, pad)), constant_values=NOBODY)
    at = jnp.where(live[:, None], at, NOBODY)
    at_rows = jnp.repeat(at, group, axis=1)[:, :, None]     # (rows, padded x group, 1)
    last = last.astype(jnp.int32)
    held = line_positions(last, ring)[:, None, :]
    # the arc: positions [first, last], from line first % ring on
    first = jnp.maximum(first.astype(jnp.int32), 0)
    start = first % ring
    count = jnp.minimum((start % tile + (last - first)) // tile + 1, tiles)
    count = jnp.where(live & (last >= first), count, 0).astype(jnp.int32)
    first_tile = (start // tile).astype(jnp.int32)

    def tile_of(r, j, first_ref, count_ref):
        """The ring tile a step reads: the arc's tiles in ring order, past
        them the last of them again."""
        return (first_ref[r] + jnp.minimum(
            j, jnp.maximum(count_ref[r] - 1, 0))) % tiles

    def kv_map(r, i, j, slot_ref, first_ref, count_ref):
        return slot_ref[r], tile_of(r, j, first_ref, count_ref), 0

    def q_map(r, i, j, *_):
        return r, 0, i, 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(num_rows, padded // block, tiles),
        in_specs=[
            pl.BlockSpec((None, n_kv, rows, h), q_map),
            pl.BlockSpec((None, tile, n_kv * h), kv_map),
            pl.BlockSpec((None, tile, n_kv * h), kv_map),
            pl.BlockSpec((None, rows, 1), lambda r, i, j, *_: (r, i, 0)),
            pl.BlockSpec((None, 1, tile),
                         lambda r, i, j, s, f, c: (r, 0, tile_of(r, j, f, c))),
        ],
        out_specs=pl.BlockSpec((None, n_kv, rows, h), q_map),
        scratch_shapes=[
            pltpu.VMEM((n_kv, rows, 1), jnp.float32),     # running max
            pltpu.VMEM((n_kv, rows, 1), jnp.float32),     # normalizer
            pltpu.VMEM((n_kv, rows, h), jnp.float32),     # unnormalized sum
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, window=window, sm_scale=sm_scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(
            (num_rows, n_kv, padded * group, h), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
        name=KERNEL_NAME,  # the trace's and the HLO's name for it
    )(slot.astype(jnp.int32), first_tile, count, folded,
      ring_k, ring_v, at_rows, held)
    out = out.reshape(num_rows, n_kv, padded, group, h).transpose(0, 2, 1, 3, 4)
    return out.reshape(num_rows, padded, n, h)[:, :positions]
