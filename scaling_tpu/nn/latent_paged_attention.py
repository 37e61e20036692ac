"""Pallas paged attention over LATENT cache lines (multi-head latent
attention, DeepSeek-V2/V3's MLA, in the absorbed form).

What a token leaves in the cache of a latent layer is no K and V a head but
ONE line: the normed latent ``c_kv`` (``kv_lora_rank`` values) and the ONE
rotary key ``k_r`` (``qk_rope_head_dim`` values) all heads share. With the
up-projections absorbed into the queries and the output (``nn/latent_attention
.py``), every head attends over that line as over one shared KV head whose
key is ``[c_kv, k_r]`` and whose value is ``c_kv`` itself:

    scores[n, q, k] = scale * (q_lat[q, n] . c_kv[k] + q_rope[q, n] . k_r[k])
    out[q, n]       = softmax_k(scores[n, q, :]) @ c_kv          (kv_lora_rank)

The kernel is ``nn/paged_attention.py``'s in what it streams and how: the grid
is the rows, block table and lengths are scalar-prefetched, the two pools stay
in HBM, a row loops over its TILES of ``tile_blocks`` table entries, fetched
block by block into one of two VMEM buffers while the other is folded into a
float32 online softmax, and the double buffer runs over the call's flat list
of (row, tile) steps (``_pipeline_carry``). Each latent tile moves HBM -> VMEM
ONCE and is used as key and as value.

What differs, because a latent line has no head axis and the heads' queries
are wide (``heads x kv_lora_rank`` a position: 64 KiB at Kimi-K2's sizes):

- The queries stay TOKEN-MAJOR, as the engine's mixed program packs them
  (``PagedTokenMap``): ``(tokens * heads, width)``, a row's positions back to
  back from ``starts[row]``. The kernel DMAs the row's ``new_len`` positions
  itself and writes exactly those positions of the output, so nothing is
  regrouped to ``(rows, row width)`` blocks around the call: at 32 rows x 32
  positions that block is 67 MB a layer, of which a decode tick uses 1/32.
  Output positions no row owns are never written: the caller selects them out.
- All heads of ``QUERY_POSITIONS`` positions are the rows of ONE matmul
  (``positions x heads`` against the tile), position-major. A row of one token
  (a decode row) folds its ``heads`` rows only; a chunk row loops over its
  ``ceil(new_len / QUERY_POSITIONS)`` query blocks inside every tile, so the
  float32 scores are ``(QUERY_POSITIONS * heads, tile)`` whatever the chunk.

Masking is the paged contract (``nn/attention.py``): slot ``k`` is visible to
the query at slot ``q`` iff ``k < valid_len`` and ``k <= q``.

Off-TPU the kernel runs interpreted, like ``paged_decode_attention``.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from ..obs import count_kernel_build
from . import paged_attention as _paged
from .paged_attention import _pipeline_carry, _round_up, paged_kernel_interpret

# KV tokens a tile holds (one step of a row's loop), as the paged kernel's
TILE_TOKENS = 512
# positions whose heads are one matmul's rows in a chunk row's fold
QUERY_POSITIONS = 8
# the queries of a whole chunk row, the float32 accumulator beside them and
# one query block's scores: more than Mosaic's default scoped 16 MiB
VMEM_LIMIT_BYTES = 64 << 20
KERNEL_NAME = "latent_paged_attention"
ROPE_LANES = 128


def rope_line_width(rope: int) -> int:
    """The width the rotary key's leaf of a latent line is MADE in: whole
    rows of ``ROPE_LANES`` lanes, the key first, zeros after it. On the chip
    an array whose minor dimension is 64 is tiled to 128 lanes anyway (twice
    its bytes, PR 48), and Mosaic refuses the kernel's DMA of a 64-wide slice
    of such a tile ("must be aligned to tiling (128)"); the zeros meet zeros
    of the queries."""
    return _round_up(rope, ROPE_LANES)


def latent_tile_tokens(block_size: int, max_blocks: int) -> int:
    """KV tokens one tile of the kernel holds at these shapes."""
    return block_size * max(1, min(max_blocks, TILE_TOKENS // block_size))


def _latent_kernel(
    # scalar prefetch (SMEM)
    tab_ref,      # (rows, max_blocks) int32 pool block ids
    valid_ref,    # (rows,) int32 visible slots per row (ctx + new real)
    base_ref,     # (rows,) int32 slot of each row's first query token
    start_ref,    # (rows,) int32 the row's first token in the packed batch
    before_ref,   # (rows,) int32 tiles of the rows before this one
    next_ref,     # (rows,) int32 next row that holds a visible slot, or -1
    # operands, all left in HBM
    q_lat_ref,    # (tokens * heads, lat) absorbed queries, token-major
    q_rope_ref,   # (tokens * heads, rope)
    pool_c_ref,   # (num_blocks, block_size, lat) the normed latents
    pool_r_ref,   # (num_blocks, block_size, rope) the rotary keys
    o_ref,        # (tokens * heads, lat)
    # scratch
    q_lat, q_rope, c_buf, r_buf, sems, io_sems, m_ref, l_ref, acc_ref,
    *,
    block_size: int,
    tile_blocks: int,
    heads: int,
    width: int,
    sm_scale: float,
):
    pl, pltpu = _paged.pl, _paged.pltpu
    pools = ((pool_c_ref, c_buf), (pool_r_ref, r_buf))
    tile = tile_blocks * block_size
    row = pl.program_id(0)
    valid_len = valid_ref[row]
    base = base_ref[row]
    new_len = valid_len - base
    start = start_ref[row]
    num_tiles = pl.cdiv(valid_len, tile)
    tiles_before = before_ref[row]
    next_row = next_ref[row]
    block_rows = QUERY_POSITIONS * heads

    @pl.when(row == 0)
    def _clear():
        # a tile's tail past the row's last block, and the query rows past a
        # row's last position, keep what an earlier step left there: masked
        # or never written out, but finite only once they start finite
        c_buf[...] = jnp.zeros_like(c_buf)
        r_buf[...] = jnp.zeros_like(r_buf)
        q_lat[...] = jnp.zeros_like(q_lat)
        q_rope[...] = jnp.zeros_like(q_rope)

    def blocks_held(t, of_row=row):
        return jnp.clip(
            pl.cdiv(valid_ref[of_row] - t * tile, block_size), 0, tile_blocks
        )

    def block_copies(block, i, slot):
        return [
            pltpu.make_async_copy(
                pool.at[block],
                buf.at[slot, pl.ds(i * block_size, block_size)],
                sems.at[slot, which],
            )
            for which, (pool, buf) in enumerate(pools)
        ]

    def start_tile(t, slot, of_row=row):
        def one(i, carry):
            block = tab_ref[of_row, t * tile_blocks + i]
            for copy in block_copies(block, i, slot):
                copy.start()
            return carry

        jax.lax.fori_loop(0, blocks_held(t, of_row), one, 0)

    def wait_tile(t, slot):
        def one(i, carry):
            for copy in block_copies(0, i, slot):
                copy.wait()
            return carry

        jax.lax.fori_loop(0, blocks_held(t), one, 0)

    def position_spans(p):
        """Position ``p`` of the row: its folded rows in the scratch and in
        the token-major operands."""
        return (pl.ds(pl.multiple_of(p * heads, heads), heads),
                pl.ds(pl.multiple_of((start + p) * heads, heads), heads))

    def query_copies(p):
        here, there = position_spans(p)
        return (
            pltpu.make_async_copy(q_lat_ref.at[there], q_lat.at[here],
                                  io_sems.at[0]),
            pltpu.make_async_copy(q_rope_ref.at[there], q_rope.at[here],
                                  io_sems.at[1]),
        )

    def output_copy(p):
        """The output leaves from ``q_lat``'s place (``finish``)."""
        here, there = position_spans(p)
        return (pltpu.make_async_copy(q_lat.at[here], o_ref.at[there],
                                      io_sems.at[2]),)

    def for_positions(copies, act):
        """``act`` (start or wait) every copy of every position the row
        brings."""
        def one(p, carry):
            for copy in copies(p):
                getattr(copy, act)()
            return carry

        jax.lax.fori_loop(0, jnp.minimum(new_len, width), one, 0)

    @pl.when((num_tiles > 0) & (tiles_before == 0))
    def _first():
        start_tile(0, 0)

    def over_query_rows(fn):
        """``fn(rows, first)`` over the folded query rows the row uses: the
        ``heads`` rows of a one-token row, or a chunk row's blocks of
        ``block_rows``."""
        @pl.when(new_len <= 1)
        def _one_token():
            fn(heads, 0)

        @pl.when(new_len > 1)
        def _chunk():
            def block(b, carry):
                fn(block_rows, pl.multiple_of(b * block_rows, block_rows))
                return carry

            jax.lax.fori_loop(
                0, pl.cdiv(jnp.minimum(new_len, width), QUERY_POSITIONS),
                block, 0)

    @pl.when(num_tiles > 0)
    def _row():
        for_positions(query_copies, "start")

        def clear(rows, first):
            span = pl.ds(first, rows)
            m_ref[span, :] = jnp.full((rows, 1), -jnp.inf, jnp.float32)
            l_ref[span, :] = jnp.zeros((rows, 1), jnp.float32)
            acc_ref[span, :] = jnp.zeros((rows, acc_ref.shape[1]), jnp.float32)

        over_query_rows(clear)
        for_positions(query_copies, "wait")

        precision = (
            None if q_lat.dtype == jnp.float32 else jax.lax.Precision.DEFAULT
        )

        def one_tile(t, carry):
            slot = (tiles_before + t) % 2
            last = t + 1 == num_tiles

            @pl.when(jnp.logical_not(last))
            def _prefetch():
                start_tile(t + 1, 1 - slot)

            @pl.when(last & (next_row >= 0))
            def _prefetch_next_row():
                start_tile(0, 1 - slot, next_row)

            wait_tile(t, slot)
            kv_slot = t * tile + jax.lax.broadcasted_iota(
                jnp.int32, (1, tile), 1)

            def fold(rows, first):
                """One query block against the latent tile, into its online
                softmax (running max, normaliser, accumulator: float32)."""
                span = pl.ds(first, rows)
                c_tile, r_tile = c_buf[slot], r_buf[slot]
                q_slot = base + (first + jax.lax.broadcasted_iota(
                    jnp.int32, (rows, 1), 0)) // heads
                allowed = (kv_slot < valid_len) & (kv_slot <= q_slot)
                dims = (((1,), (1,)), ((), ()))
                scores = jax.lax.dot_general(
                    q_lat[span, :], c_tile, dims,
                    preferred_element_type=jnp.float32, precision=precision,
                ) + jax.lax.dot_general(
                    q_rope[span, :], r_tile, dims,
                    preferred_element_type=jnp.float32, precision=precision,
                )
                scores = jnp.where(allowed, scores * sm_scale, -jnp.inf)
                m_old = m_ref[span, :]
                m_new = jnp.maximum(m_old, scores.max(axis=-1, keepdims=True))
                m_safe = jnp.where(m_new == -jnp.inf, 0.0, m_new)
                p = jnp.exp(scores - m_safe)
                alpha = jnp.exp(m_old - m_safe)
                l_ref[span, :] = alpha * l_ref[span, :] + p.sum(
                    axis=-1, keepdims=True)
                # the tile is the value too
                acc_ref[span, :] = alpha * acc_ref[span, :] + jnp.dot(
                    p.astype(c_tile.dtype), c_tile,
                    preferred_element_type=jnp.float32, precision=precision,
                )
                m_ref[span, :] = m_new

            over_query_rows(fold)
            return carry

        jax.lax.fori_loop(0, num_tiles, one_tile, 0)

        def finish(rows, first):
            # the output leaves from the queries' place: same shape, same
            # dtype, and the queries are spent
            span = pl.ds(first, rows)
            l = l_ref[span, :]
            q_lat[span, :] = (
                acc_ref[span, :] / jnp.where(l == 0.0, 1.0, l)
            ).astype(q_lat.dtype)

        over_query_rows(finish)
        for_positions(output_copy, "start")
        for_positions(output_copy, "wait")


def latent_paged_attention(
    q_lat: jax.Array,        # (tokens, heads, lat) absorbed queries
    q_rope: jax.Array,       # (tokens, heads, rope) rotary-applied
    pool_c: jax.Array,       # (num_blocks, block_size, lat)
    pool_r: jax.Array,       # (num_blocks, block_size, rope_line_width(rope))
    block_table: jax.Array,  # (rows, max_blocks) int32; 0 = trash
    valid_len: jax.Array,    # (rows,) int32 slots visible per row
    q_slot_base: jax.Array,  # (rows,) int32 slot of the row's first query
    starts: jax.Array,       # (rows,) int32 the row's first token in q
    *,
    width: int,              # the most positions one row brings
    sm_scale: float,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Absorbed latent attention over the paged latent pool; returns
    ``(tokens, heads, lat)``, the positions some row owns (``starts[r] + j``,
    ``j < valid_len[r] - q_slot_base[r]``) written, the others NOT (whatever
    the buffer held): the caller selects them out.

    The pool must already hold the query tokens' lines (the caller scatters
    through ``nn.attention.paged_scatter_kv`` first)."""
    _paged._ensure_pallas()
    if interpret is None:
        interpret = paged_kernel_interpret()
    # the stack's paged-attention kernel: counted under the name the paged
    # kernel's builds are, so that a run asserts it was compiled
    count_kernel_build("paged_attention", interpret)
    count_kernel_build(KERNEL_NAME, interpret)
    return _latent_call(
        q_lat, q_rope, pool_c, pool_r, block_table, valid_len, q_slot_base,
        starts, width=int(width), sm_scale=float(sm_scale),
        interpret=bool(interpret),
    )


@functools.partial(
    jax.jit, static_argnames=("width", "sm_scale", "interpret"))
def _latent_call(q_lat, q_rope, pool_c, pool_r, block_table, valid_len,
                 q_slot_base, starts, *, width: int, sm_scale: float,
                 interpret: bool):
    pl, pltpu = _paged.pl, _paged.pltpu
    tokens, heads, lat = q_lat.shape
    _, block_size, rope = pool_r.shape
    # the rotary key's leaf is as wide as the chip's lanes (ROPE_LANES)
    q_rope = jnp.pad(q_rope, ((0, 0), (0, 0), (0, rope - q_rope.shape[-1])))
    rows, max_blocks = block_table.shape
    tile = latent_tile_tokens(block_size, max_blocks)
    m_rows = _round_up(width, QUERY_POSITIONS) * heads
    valid_len = jnp.minimum(
        valid_len.astype(jnp.int32), max_blocks * block_size)
    tiles_before, next_row = _pipeline_carry(valid_len, tile)
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(rows,),
        in_specs=[in_hbm] * 4,
        out_specs=in_hbm,
        scratch_shapes=[
            pltpu.VMEM((m_rows, lat), q_lat.dtype),       # queries, then out
            pltpu.VMEM((m_rows, rope), q_rope.dtype),
            pltpu.VMEM((2, tile, lat), pool_c.dtype),
            pltpu.VMEM((2, tile, rope), pool_r.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SemaphoreType.DMA((3,)),
            pltpu.VMEM((m_rows, 1), jnp.float32),         # running max m
            pltpu.VMEM((m_rows, 1), jnp.float32),         # normalizer l
            pltpu.VMEM((m_rows, lat), jnp.float32),       # unnormalized acc
        ],
    )
    kernel = functools.partial(
        _latent_kernel, block_size=block_size, tile_blocks=tile // block_size,
        heads=heads, width=width, sm_scale=sm_scale,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((tokens * heads, lat), q_lat.dtype),
        # the rows run in order on one core: a row's first tile is started
        # by the row before it
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
        interpret=interpret,
        name=KERNEL_NAME,  # the trace's and the HLO's name for it
    )(
        block_table.astype(jnp.int32), valid_len,
        q_slot_base.astype(jnp.int32), starts.astype(jnp.int32),
        tiles_before, next_row,
        q_lat.reshape(tokens * heads, lat),
        q_rope.reshape(tokens * heads, rope),
        pool_c, pool_r,
    )
    return out.reshape(tokens, heads, lat)
