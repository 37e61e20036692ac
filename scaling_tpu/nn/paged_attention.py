"""Pallas paged-decode attention: stream KV blocks, never gather windows.

The serving hot path (serve/engine.py) decodes every slot each tick
through the block-paged KV pool. The original XLA path materializes each
row's FULL block window per layer — ``pool[block_table]`` gathers
``(slots, max_blocks * block_size, n_kv, h)`` into a fresh buffer before
a single token's attention runs. On a chip that is pure HBM traffic the
MXU never sees twice: once to build the window, once to read it.

This kernel removes the window. The grid is the rows; the block table is
scalar-prefetched, the pools stay in HBM, and each row loops over its own
TILES: ``_blocks_per_tile`` consecutive table entries (a function of the
shapes: 32 blocks = 512 tokens at the serving shapes), fetched block by
block with the kernel's own DMAs into one of two VMEM tile buffers, the
next tile in flight while this one is folded into a flash-style online
softmax (running max ``m``, normalizer ``l``, unnormalized accumulator in
f32 scratch — Dao et al., arxiv 2205.14135). Each KV byte moves HBM->VMEM
exactly once, no ``(rows, window)`` buffer ever exists, and what a row
cannot see costs nothing: the loop ends at the row's last tile, the last
tile fetches only the blocks that hold visible slots (an inactive row runs
no tile at all), and of a tile a row of few positions folds only the
SUB-TILES that hold a visible slot.

A sub-tile is ``_SUB_TOKENS`` = 128 tokens of a tile (``_blocks_per_sub``:
8 blocks of 16; a tile that is not whole sub-tiles is its own one). The
tile stays the unit of the DMAs' pipeline. How many of its sub-tiles hold a
slot is read from ``valid_len``, and each count is one static branch of the
kernel (ISSUE 69): its WAIT takes the whole sub-tiles at once, one wait a
pool by their bytes, and the part-held last one block by block, for every
row kind alike; and, for the rows of few positions, its FOLD has the static
width of that many sub-tiles, with one softmax update a tile, so a decode
row that holds 300 lines unpacks, multiplies and exponentiates 384 of its
tile's 512 slots. Those rows fold all KV heads TOGETHER: every head's QK^T
first, one softmax update over the stacked ``(n_kv, rows, width)`` scores,
then every head's PV. Head by head, as the chunk rows still fold, a head's
update is a chain of a dozen dependent steps on a register or two of scores
(16 query rows), and a call of 16 decode rows spent 84 us in those chains
where its DMAs take 54 (PERF.md, PR 69); together they pipeline, and the
fold is a third of that. Chunk rows wait the same way and fold the tile
whole, a head at a time: their scores are ``s * group`` rows deep already.

The double buffer runs over the CALL's flat list of (row, tile) steps, not
row by row: while a row's last tile is folded, the first tile of the next
row that holds a visible slot is already on its way into the other buffer,
and that row begins by waiting for it. Only the call's first active row
fetches its own first tile. A decode tick's rows are mostly one tile long,
so without this every row's whole fetch was waited for with nothing to
fold (ISSUE 41). Two things cross a grid step, both functions of
``valid_len`` alone and therefore computed in the wrapper and handed over
as two more scalar-prefetch vectors (``_pipeline_carry``), not carried in
scratch: the tiles of the rows before a row (its parity is the buffer the
row's first tile lies in; 0 says nobody has fetched it) and the next row
that runs a tile (-1: none). The buffers and their DMA semaphores are
scratch, which persists from one grid step to the next; the grid is
declared sequential (``dimension_semantics=("arbitrary",)``), since a row's
first tile is started by the row before it. A wait counts the blocks the
starter issued, ``blocks_held`` of the WAITING row's own ``valid_len``,
whoever started them; and the call's last active row prefetches nothing,
so every DMA started in a call is waited for in that call: none is still
writing VMEM, or holding a semaphore above zero, when the next kernel
(this one again, a layer on) takes the core.

Inside a tile the work is MXU-shaped and in the pool's own dtype. The GQA
group is folded into the matmul's rows: the caller-side wrapper lays the
queries out per KV head as ``(s * group, h)``, position-major, so per KV
head QK^T is ``(s * group, h) @ (h, tile)`` and PV is ``(s * group, tile)
@ (tile, h)``, both with float32 accumulation; K and V are never cast to
float32 nor repeated across the group. One head's ``(tile, h)`` matrix is
a sublane-strided read of the ``(tile, n_kv, h)`` buffer (``_heads``:
32-bit words, so bf16 and int8 heads are unpacked from the words that pack
them). A pool whose heads are wider than the 128 lanes, or that has one KV
head, lies HEAD-MAJOR, ``(num_blocks, n_kv, block_size, h)``
(``head_major_kv``: there the word view is a relayout of the tile or no
load Mosaic has; ISSUE 74): its VMEM tile is ``(n_kv, tile, h)``, a block's
DMA lands in every head's rows, and a head's matrix is a plain dense read.
Which layout a pool has the kernel reads off its shape
(``kv_block_layout``); the pipeline, the waits and the fold are the same
code for both. The probabilities meet V in the queries' dtype (bf16 when serving;
``l`` sums them in float32), as the splash kernel's do. Rows with at most
``_SHORT_QUERIES`` real positions — decode rows, a prompt's short tail —
run the loop over their first folded rows only, over the sub-tiles that
hold a slot and all heads together; prefill chunks take the full width, the
whole tile and a head at a time. Which path a row takes is read from
``valid_len - q_slot_base``, the row's ``new_len``.

Variants share one kernel body:

- native: pool blocks arrive in the pool dtype and are attended as-is;
- int8: pool blocks arrive quantized; the kernel dequantizes IN VMEM with
  the same per-slot-per-head ``kv_quantize_int8`` scales the pool writer
  produced (``nn.attention.paged_scatter_kv``). int8 is exact in bf16, so
  the scales go onto the products (``q . (k * scale) = (q . k) * scale``,
  ``p @ (v * scale) = (p * scale) @ v``); the wrapper gathers the rows'
  scales (1/h of the window's bytes) so a tile's lie along the lanes. The
  f32 window the XLA path materialized in HBM never exists here either;
- masked (``chosen``: the sparse grouped-query mixer's rows of ONE token,
  ``nn/sparse_attention.py``): each row brings a mask over its slots, handed
  over as the scales are, one lane-dense int32 strip a row, and ANDed into a
  tile's ``allowed``: a slot is visible iff the contract below admits it AND
  the row chose it. Every visible line is still fetched and multiplied (the
  choice is a mask, not a gather); what the kernel adds to the XLA fold it
  replaced is each row's OWN blocks by DMA up to its OWN length, and no tile
  for a row that sees nothing. A masked call of one position a row folds a
  row's ``group`` query rows alone (padded to 16: at 16 KiB blocks 7-9% of a
  call, PERF.md, PR 64); it waits as every call does. The mask's absence is
  a static branch: a maskless call has the operands it always had.

Masking follows the paged-decode contract exactly (``nn/attention.py``
``_paged_attention``): LOGICAL slot indices are the causal clock; slot
``k`` is visible to query slot ``q`` iff ``k < valid_len`` (written) and
``k <= q`` (causal). Queries may be a single decode token (s=1), a
prefill CHUNK (s=chunk), or the few tokens a prompt's last chunk is
left with (1 < s <= ``_SHORT_QUERIES``: the short path at one of its
static widths; a position ``valid_len`` does not admit is never
visible, whoever wrote it). K/V are scattered into
the pool by the caller before attending, and the same per-row
``valid_len``/``q_slot_base`` math serves every row kind, so one fused
program covers a whole mixed tick (serve/engine.py ``_build_mixed_fn``).
Positions past a row's real tokens (``new_len`` pads) come back finite —
garbage or zeros — and the host discards them; their writes land in the
trash block.

Off-TPU the kernel runs with ``interpret=True`` (the whole grid, the DMAs
and the semaphores execute as traced jax ops), so the CPU-mesh tests
exercise the REAL kernel body, not a stand-in.
"""

from __future__ import annotations

import functools
import itertools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from ..obs import count_kernel_build

# pallas resolves lazily on first kernel build so importing scaling_tpu.nn
# never pulls the pallas machinery on jax-light paths; the kernel body
# reads these globals at trace time, strictly after _ensure_pallas ran
pl = None  # type: ignore[assignment]
pltpu = None  # type: ignore[assignment]


def _ensure_pallas():
    global pl, pltpu
    if pl is None:
        from jax.experimental import pallas as _pl
        from jax.experimental.pallas import tpu as _pltpu

        pl, pltpu = _pl, _pltpu


def paged_kernel_interpret(platform: Optional[str] = None) -> bool:
    """Interpret mode off-TPU (CPU mesh tests run the real kernel body);
    on a TPU the kernel is always compiled."""
    return (platform or jax.default_backend()) != "tpu"


# KV tokens one tile (one step of a row's loop) aims to hold: enough bytes
# in flight to approach the HBM rate, few enough that the float32 scores of
# one KV head stay a few hundred KiB
_TILE_TOKENS = 512
# KV tokens one SUB-TILE holds, the unit a tile is waited for and folded in:
# one lane row of scores, so a row that holds 300 lines folds three of a
# tile's four and never waits for, unpacks or multiplies the fourth
_SUB_TOKENS = 128
# VMEM the two double-buffered pool tiles (K and V) may take together
_TILE_VMEM_BYTES = 8 << 20
# VMEM a kernel may take without asking (Mosaic's scoped default is 16 MiB);
# a call whose blocks need more (wide chunk rows at a large GQA group: every
# row's queries, output and softmax state are whole in VMEM) asks for what it
# needs, up to ``_VMEM_CEILING_BYTES`` of a v5e core's 128 MiB
_VMEM_DEFAULT_BYTES = 12 << 20
_VMEM_CEILING_BYTES = 100 << 20
# query positions of the short-query path: a decode row has 1 real
# position, the tail of a prompt's last chunk up to this many
_SHORT_QUERIES = 8


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _blocks_per_tile(block_size: int, max_blocks: int, n_kv: int, h: int,
                     itemsize: int, head_major: bool = False) -> int:
    """Consecutive table entries one tile takes: a function of the shapes.

    K and V, double-buffered, are four VMEM tiles. A head-major pool's
    (``head_major_kv``) is ``(n_kv, tokens, h)``: whole memory tiles, its own
    bytes. A token-major pool's keeps the pool's ``(tokens, n_kv, h)`` order
    and is reckoned with its ``(n_kv, h)`` minor dims padded to whole ``(8 * 4
    / itemsize, 128)`` memory tiles. That is an upper bound, not what Mosaic
    does for few heads: it keeps a bf16 ``(.., 2, 256)`` buffer in ``(2, 128)``
    memory tiles (a tile of 1,024 such tokens compiles inside the 16 MiB
    default, which 16 sublanes a token would not: PERF.md, PR 74), so the
    bound halved a 2 x 256 tile to 256 tokens for nothing; the shapes that
    still take this branch (4 KV heads and more, int8) keep the tiles they
    had."""
    if head_major:
        block_bytes = block_size * n_kv * _round_up(h, 128) * itemsize
    else:
        sublanes = 8 * max(1, 4 // itemsize)
        block_bytes = (
            block_size * _round_up(n_kv, sublanes) * _round_up(h, 128) * itemsize
        )
    by_vmem = _TILE_VMEM_BYTES // (4 * block_bytes)
    return max(1, min(max_blocks, _TILE_TOKENS // block_size, by_vmem))


def _blocks_per_sub(block_size: int, tile_blocks: int) -> int:
    """Consecutive blocks of a tile one sub-tile takes: a function of the
    shapes. ``_SUB_TOKENS`` tokens where whole blocks make them up and the
    tile is whole sub-tiles; any other tile is its own one sub-tile."""
    sub_blocks, rest = divmod(_SUB_TOKENS, block_size)
    if rest or tile_blocks % sub_blocks:
        return tile_blocks
    return sub_blocks


def kernel_tile_tokens(block_size: int, max_blocks: int, n_kv: int, h: int,
                       itemsize: int, head_major: bool = False) -> int:
    """KV tokens one tile of the kernel holds at these shapes."""
    return block_size * _blocks_per_tile(
        block_size, max_blocks, n_kv, h, itemsize, head_major
    )


def kernel_sub_tokens(block_size: int, max_blocks: int, n_kv: int, h: int,
                      itemsize: int, head_major: bool = False) -> int:
    """KV tokens one sub-tile of the kernel's tile holds at these shapes."""
    return block_size * _blocks_per_sub(
        block_size,
        _blocks_per_tile(block_size, max_blocks, n_kv, h, itemsize, head_major),
    )


def _unpack_head(words, i: int, packing: int):
    """Head ``i`` of the ``packing`` a 32-bit word holds, widened in place:
    bf16 to float32 (exact), int8 to int32."""
    if packing == 1:
        return words
    if packing == 2:
        bits = words << 16 if i == 0 else words & jnp.int32(-65536)
        return jax.lax.bitcast_convert_type(bits, jnp.float32)
    return (words << (24 - 8 * i)) >> 24


def _heads(tile_ref, width: int, head_major: bool):
    """``(g, matrix)`` for every KV head ``g`` of a VMEM tile, the ``(width,
    h)`` matrix of the head's first ``width`` tokens.

    A head-major tile ``(n_kv, tile, h)`` holds each head's matrix dense: a
    plain read. In a token-major ``(tile, n_kv, h)`` one heads are the tile's
    second-minor dim, so one head's rows lie ``n_kv``
    apart: a sublane-strided load, which Mosaic has for 32-bit words only.
    Narrower pools pack 2 (bf16) or 4 (int8) consecutive heads of a token
    into a word, so the tile is read as words, a column of words at a time,
    and each word's heads are unpacked with shifts. A head count the packing
    does not divide (an int8 pool sharded down to 2 heads) reads head by
    head."""
    if head_major:
        for g in range(tile_ref.shape[0]):
            yield g, tile_ref[g, pl.ds(0, width), :]
        return
    tile, n_kv, h = tile_ref.shape
    packing = 4 // tile_ref.dtype.itemsize
    if n_kv % packing:
        for g in range(n_kv):
            yield g, tile_ref[pl.ds(0, width), g, :]
        return
    words = tile_ref.reshape(tile * n_kv, h)
    if packing > 1:
        words = words.bitcast(jnp.int32)
    columns = n_kv // packing
    for j in range(columns):
        column = words[pl.ds(j, width, stride=columns), :]
        for i in range(packing):
            yield j * packing + i, _unpack_head(column, i, packing)


def _paged_attention_kernel(
    # scalar prefetch (SMEM)
    tab_ref,      # (rows, max_blocks) int32 pool block ids
    valid_ref,    # (rows,) int32 valid slot count per row (ctx + new real)
    base_ref,     # (rows,) int32 slot of each row's first query token
    before_ref,   # (rows,) int32 tiles of the rows before this one
    next_ref,     # (rows,) int32 next row that holds a visible slot, or -1
    # blocks
    q_ref,        # (1, n_kv, m, h) VMEM: queries folded per KV head
    pool_k_ref,   # (num_blocks, block_size, n_kv, h) left in HBM; a
    pool_v_ref,   # head-major pool (num_blocks, n_kv, block_size, h)
    *rest,        # [scale_k_ref, scale_v_ref,] [chosen_ref,] o_ref, the scratch
    block_size: int,
    tile_blocks: int,
    sub_blocks: int,
    sm_scale: float,
    group: int,
    m_short: int,
    quantized: bool,
    masked: bool,
    single: bool,
    head_major: bool,
):
    if quantized:
        # (1, n_kv, window) VMEM: the row's scales, one lane a slot
        scale_k_ref, scale_v_ref, *rest = rest
    if masked:
        # (1, 1, window) VMEM int32: the row's choice, one lane a slot
        chosen_ref, *rest = rest
    o_ref, k_buf, v_buf, sems, m_ref, l_ref, acc_ref = rest
    pools = ((pool_k_ref, k_buf), (pool_v_ref, v_buf))
    _, n_kv, m_full, _ = q_ref.shape
    tile, sub = tile_blocks * block_size, sub_blocks * block_size
    row = pl.program_id(0)
    valid_len = valid_ref[row]
    base = base_ref[row]
    num_tiles = pl.cdiv(valid_len, tile)
    # the call's tiles alternate between the two buffers across rows too
    tiles_before = before_ref[row]
    next_row = next_ref[row]

    @pl.when(row == 0)
    def _clear_v_tiles():
        # a tile's tail past the row's last block keeps what an earlier
        # tile left there. Its scores are masked whatever K holds; its
        # probabilities are 0, and 0 * v is 0 once v is finite
        v_buf[...] = jnp.zeros_like(v_buf)

    def blocks_held(t, of_row=row):
        """How many blocks of tile ``t`` hold slots ``of_row`` can see."""
        return jnp.clip(
            pl.cdiv(valid_ref[of_row] - t * tile, block_size), 0, tile_blocks
        )

    def tokens_of(buf, slot, first, count):
        """Tokens ``[first, first + count)`` of the tile in buffer ``slot``:
        the tile's major dim, or every head's rows of a head-major one."""
        if head_major:
            return buf.at[slot, :, pl.ds(first, count)]
        return buf.at[slot, pl.ds(first, count)]

    def block_copies(block, i, slot):
        """The DMAs of pool block ``block`` to place ``i`` of a tile."""
        return [
            pltpu.make_async_copy(
                pool.at[block],
                tokens_of(buf, slot, i * block_size, block_size),
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

    def wait_tile(t, slot, subs: int):
        """Wait for what tile ``t`` holds of the row's blocks when that is
        more than ``subs - 1`` and at most ``subs`` sub-tiles: the row's own
        count, whoever started them. A wait counts the bytes of its shape
        only: whole sub-tiles are ONE wait a pool by their bytes (a tenth off
        a call at 16 KiB blocks: PERF.md, PR 64), and any block stands for a
        block of the part-held last one."""
        def at_once(whole: int):
            for which, (_, buf) in enumerate(pools):
                part = tokens_of(buf, slot, 0, whole * sub)
                pltpu.make_async_copy(part, part, sems.at[slot, which]).wait()

        def one(i, carry):
            for copy in block_copies(0, i, slot):
                copy.wait()
            return carry

        rest = blocks_held(t) - (subs - 1) * sub_blocks
        pl.when(rest == sub_blocks)(lambda: at_once(subs))

        @pl.when(rest < sub_blocks)
        def _the_last_block_by_block():
            if subs > 1:
                at_once(subs - 1)
            jax.lax.fori_loop(0, rest, one, 0)

    m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when((num_tiles > 0) & (tiles_before == 0))
    def _first():
        # only the call's first active row fetches its own first tile: every
        # later one finds it in flight, started under the fold before it
        start_tile(0, 0)

    # operands narrower than float32 multiply exactly in one MXU pass; an
    # ambient jax.default_matmul_precision must not ask Mosaic for more
    precision = (
        None if q_ref.dtype == jnp.float32 else jax.lax.Precision.DEFAULT
    )

    def attend(m_run: int, by_sub: bool):
        """Fold every tile of the row into the first ``m_run`` folded query
        rows' online softmax (running max, normaliser, accumulator: float32).
        ``by_sub``: the rows of few positions' way. Of a tile only the
        sub-tiles that hold a visible slot are folded (one of the static
        widths 1 .. 4 sub-tiles, ONE softmax update a tile), and all KV heads
        are folded together: every head's scores first, one update over the
        stacked scores, then every head's values. Head by head the update is
        a chain of a dozen dependent steps on two registers of scores, and a
        call spent more time in those chains than in its DMAs (PERF.md, PR
        69). The full-width way folds the whole tile a head at a time: its
        scores are ``m_run`` rows deep already."""
        q_slot = base + jax.lax.broadcasted_iota(
            jnp.int32, (m_run, 1), 0
        ) // group

        def fold_span(slot, first, width: int):
            """The first ``width`` tokens of the tile in buffer ``slot``,
            the row's slots ``[first, first + width)``."""
            kv_slot = first + jax.lax.broadcasted_iota(
                jnp.int32, (1, width), 1
            )
            allowed = (kv_slot < valid_len) & (kv_slot <= q_slot)
            if quantized or masked:
                span = pl.ds(pl.multiple_of(first, tile), width)
            if masked:
                allowed = allowed & (chosen_ref[0, :, span] != 0)
            keys = _heads(k_buf.at[slot], width, head_major)
            values = _heads(v_buf.at[slot], width, head_major)
            together = n_kv if by_sub else 1
            for g0 in range(0, n_kv, together):
                scores = []
                for g, k in itertools.islice(keys, together):
                    # (s_q * group, h) @ (h, width): one MXU matmul a KV head
                    qk = jax.lax.dot_general(
                        q_ref[0, g, :m_run, :], k.astype(q_ref.dtype),
                        (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32,
                        precision=precision,
                    ) * sm_scale
                    if quantized:
                        # int8 is exact in the queries' dtype; the writer's
                        # kv_quantize_int8 scales, one a slot, go onto the
                        # products: q . (k * scale) = (q . k) * scale
                        qk = qk * scale_k_ref[0, pl.ds(g, 1), span]
                    scores.append(qk)
                # a head alone stays the matrix it is: stacked, a chunk
                # row's scores would be copied (Laguna's: 3 MiB a head)
                alone = together == 1
                at = g0 if alone else pl.ds(g0, together)
                scores = scores[0] if alone else jnp.stack(scores)
                scores = jnp.where(allowed, scores, -jnp.inf)
                # online softmax: all-masked spans keep m at -inf; the safe
                # shift avoids exp(-inf - -inf) = nan without branching
                m_old = m_ref[at, :m_run]
                m_new = jnp.maximum(
                    m_old, scores.max(axis=-1, keepdims=True)
                )
                m_safe = jnp.where(m_new == -jnp.inf, 0.0, m_new)
                p = jnp.exp(scores - m_safe)
                alpha = jnp.exp(m_old - m_safe)
                l_ref[at, :m_run] = (
                    alpha * l_ref[at, :m_run] + p.sum(axis=-1, keepdims=True)
                )
                m_ref[at, :m_run] = m_new
                for g, v in itertools.islice(values, together):
                    p_g, alpha_g = (
                        (p, alpha) if alone else (p[g - g0], alpha[g - g0])
                    )
                    if quantized:
                        p_g = p_g * scale_v_ref[0, pl.ds(g, 1), span]
                    acc_ref[g, :m_run] = (
                        alpha_g * acc_ref[g, :m_run] + jnp.dot(
                            p_g.astype(q_ref.dtype), v.astype(q_ref.dtype),
                            preferred_element_type=jnp.float32,
                            precision=precision,
                        )
                    )

        def one_tile(t, carry):
            slot = (tiles_before + t) % 2
            last = t + 1 == num_tiles

            # the next step of the call's flat (row, tile) list is in flight
            # while this one is folded: the row's next tile, or the first
            # tile of the next row that runs one. Two branches, not one with
            # the row and tile selected: measured 1% faster a call
            @pl.when(jnp.logical_not(last))
            def _prefetch():
                start_tile(t + 1, 1 - slot)

            @pl.when(last & (next_row >= 0))
            def _prefetch_next_row():
                start_tile(0, 1 - slot, next_row)

            # the sub-tiles that hold a slot the row can see: one branch a
            # count, its wait and its fold of a static width
            subs_held = pl.cdiv(jnp.minimum(valid_len - t * tile, tile), sub)
            for subs in range(1, tile // sub + 1):
                @pl.when(subs_held == subs)
                def _held():
                    wait_tile(t, slot, subs)
                    if by_sub:
                        fold_span(slot, t * tile, subs * sub)

            if not by_sub:
                fold_span(slot, t * tile, tile)
            return carry

        jax.lax.fori_loop(0, num_tiles, one_tile, 0)

    if single:
        # every row of the call brings ONE position: its group's rows
        attend(m_short, True)
    elif m_short < m_full:
        few = valid_len - base <= _SHORT_QUERIES
        pl.when(few)(lambda: attend(m_short, True))
        pl.when(jnp.logical_not(few))(lambda: attend(m_full, False))
    else:
        # a query block this narrow is the short path's own
        attend(m_full, True)

    l = l_ref[...]
    # folded rows that saw no slot (an inactive row, or the rows the short
    # path leaves out) emit zeros, not nan; the caller discards them
    o_ref[0] = (acc_ref[...] / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


def paged_decode_attention(
    q: jax.Array,               # (rows, s, n, h) rotary-applied queries
    pool_k: jax.Array,          # (num_blocks, block_size, n_kv, h), or
    pool_v: jax.Array,          # head-major (num_blocks, n_kv, block_size, h)
    block_table: jax.Array,     # (rows, max_blocks) int32; 0 = trash
    valid_len: jax.Array,       # (rows,) int32 slots visible per row
    q_slot_base: jax.Array,     # (rows,) int32 slot of first query token
    *,
    sm_scale: float,
    num_repeat_kv: int = 1,
    scale_k: Optional[jax.Array] = None,  # (num_blocks, block_size, n_kv)
    scale_v: Optional[jax.Array] = None,
    chosen: Optional[jax.Array] = None,   # (rows, slots) bool
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Flash-style paged attention over a block pool; returns (rows, s, n, h).

    The pool must already contain the query tokens' K/V (the caller
    scatters through ``nn.attention.paged_scatter_kv`` first — ONE pool
    writer, so kernel and XLA fallback read identical bytes).

    With ``chosen``, slot ``k`` of a row is visible to a query of the row
    iff the paged contract admits it AND ``chosen[row, k]`` (a slot past the
    mask's width is not chosen); a row that chose nothing gives zeros, as a
    row whose ``valid_len`` is 0 does. Without it the kernel built is the
    maskless one, operand for operand."""
    _ensure_pallas()
    h = pool_k.shape[-1]
    block_size, n_kv, head_major = kv_block_layout(
        pool_k, q.shape[2] // num_repeat_kv * q.shape[3])
    if interpret is None:
        interpret = paged_kernel_interpret()
    pack = h // q.shape[-1]
    if pack > 1:
        # a pool of heads narrower than the 128 lanes holds `pack` KV heads
        # a lane row (packed_kv_dims; LFM2's 64): see _pack_queries
        assert scale_k is None, "an int8 pool is not packed"
        rows, s, n, h_q = q.shape
        out = paged_decode_attention(
            _pack_queries(q, n_kv * pack, pack), pool_k, pool_v, block_table,
            valid_len, q_slot_base, sm_scale=sm_scale,
            num_repeat_kv=pack * num_repeat_kv, chosen=chosen,
            interpret=interpret)
        # a query head's output lies in the lanes of its own KV head
        out = out.reshape(rows, s, n_kv, pack, num_repeat_kv, pack, h_q)
        own = jnp.arange(pack)
        return out[:, :, :, own, :, own].transpose(1, 2, 3, 0, 4, 5).reshape(
            rows, s, n, h_q)
    count_kernel_build("paged_attention", interpret)
    return _paged_call(
        q, pool_k, pool_v, block_table, valid_len, q_slot_base,
        scale_k, scale_v, chosen, sm_scale=float(sm_scale),
        group=num_repeat_kv,
        tile_blocks=_blocks_per_tile(
            block_size, block_table.shape[1], n_kv, h, pool_k.dtype.itemsize,
            head_major,
        ),
        head_major=head_major, interpret=interpret,
    )


_LANES = 128


def packed_kv_dims(n_kv: int, h: int):
    """The ``(heads, width)`` a native pool keeps a token's K (or V) in: ``(n_kv,
    h)`` at the usual ``h = 128`` (or wider); for narrower heads whose count
    fills whole lane rows, ``128 / h`` consecutive KV heads side by side in
    one row of 128 lanes, ``(n_kv h / 128, 128)``. The kernel reads one head's
    ``(tile, width)`` matrix with a strided load that Mosaic has for rows of
    128 lanes only; and on the chip an array whose minor dimension is 64 is
    tiled to 128 lanes anyway (twice the bytes), so a pool made as ``(.., n_kv,
    64)`` and viewed as ``(.., n_kv / 2, 128)`` in the program costs a copy of
    the whole pool a call (my chip runs, PR 48: 2.6 ms of a 20.6 ms tick).
    The pool is therefore MADE in this shape (serve/kvcache.py) and written
    in it (``nn.attention.paged_scatter_kv``)."""
    pack = _LANES // h if h < _LANES and _LANES % h == 0 else 1
    if pack == 1 or n_kv % pack:
        return n_kv, h
    return n_kv // pack, pack * h


def head_major_kv(block_size: int, n_kv: int, h: int, itemsize: int) -> bool:
    """Whether a native pool whose line is ``(n_kv, h)`` (after
    ``packed_kv_dims``; a shard's heads) keeps its blocks HEAD-MAJOR, ``(n_kv,
    block_size, h)``, and not token-major, ``(block_size, n_kv, h)``: where a
    head is wider than the 128 lanes, or there is one KV head.

    The kernel reads a head's matrix out of a token-major VMEM tile through a
    view of it as 32-bit words, a row of 128 lanes each (``_heads``). At ``h =
    128`` that view is the tile's own bytes. At 256 lanes a token's memory
    tiles alternate between its lane halves, so the view is a relayout of the
    whole tile: at 2 KV heads the fold alone took 1.30 ms of a 1.77 ms call
    where the DMAs alone take 0.76 (Qwen3-Next's 256 rows of 620 lines;
    PERF.md, PR 74), and at any other head count Mosaic has no such strided
    load at all; a single head cannot be sliced out of a dim tiled by 2.
    Head-major, a head's ``(tile, h)`` matrix is dense in the VMEM tile: a
    plain read, the fold alone 0.42 ms (0.27 of it an empty body's), the call
    0.84, and every such shape compiles. (A head-major block is also whole
    ``(16, 128)`` memory tiles of 4 KiB where a token-major one of 2 heads is
    512 B tiles; the DMAs' rate turned out NOT to follow that: the DMAs alone
    take the same time from either.) Pools of 128-lane heads measured the same in
    both layouts and keep the one, and the lowered text, they had; blocks or
    heads that are no whole memory tiles stay token-major, whose tokens are a
    major dim any DMA may slice. As ``packed_kv_dims``, the pool is MADE so
    (serve/kvcache.py) and written so (``nn.attention.paged_scatter_kv``): a
    view of the other layout inside a program is a copy of the whole pool."""
    sublanes = 8 * max(1, 4 // itemsize)
    whole_tiles = block_size % sublanes == 0 and h % _LANES == 0
    return whole_tiles and (h > _LANES or n_kv == 1)


def kv_pool_dims(block_size: int, n_kv: int, h: int, itemsize: int,
                 shards: int = 1):
    """A native pool's dims past its blocks, for a token's ``(n_kv, h)`` K (or
    V) sharded ``shards`` ways over its heads, and the POOL's head axis:
    ``packed_kv_dims``' line, token-major ``((block_size, n_kv, h), 2)`` or
    head-major ``((n_kv, block_size, h), 1)`` as ``head_major_kv`` says of a
    shard's heads."""
    if shards == 1:
        n_kv, h = packed_kv_dims(n_kv, h)
    if head_major_kv(block_size, n_kv // shards, h, itemsize):
        return (n_kv, block_size, h), 1
    return (block_size, n_kv, h), 2


class KVBlockLayout(NamedTuple):
    """How a 4-d K (or V) pool's blocks lie, read off its shape."""

    block_size: int
    n_kv: int           # heads a line keeps (after packed_kv_dims)
    head_major: bool    # (num_blocks, n_kv, block_size, h)

    def lines(self, blocks: jax.Array) -> jax.Array:
        """Gathered blocks ``(.., b, *block dims)`` as the tokens they hold,
        in order: ``(.., b * block_size, n_kv, h)``."""
        if self.head_major:
            blocks = jnp.swapaxes(blocks, -3, -2)
        lead = blocks.shape[:-4]
        return blocks.reshape(*lead, -1, *blocks.shape[-2:])

    def scatter_rows(self, flat: jax.Array, width: int):
        """``(dims, index)`` for the pool's ONE row scatter: the pool seen as
        rows of ``dims`` and the row each of a batch's values goes to, for
        tokens at flat slots ``flat`` ``(n,)`` (block id x block_size +
        offset) whose K (or V) is ``(n, n_kv, width)``. Token-major a token's
        line is one row at its slot. Head-major it is ``n_kv`` rows of
        ``width``, one in each head's rows of the block: still ONE scatter,
        into the pool seen as rows of ``width`` (addressed as ``pool.at[block,
        :, offset]`` XLA copies the whole pool a call to scatter into a
        transposed one: 1.0 ms for a pool of 164 MB where this takes 0.12 and
        the token-major scatter 0.06; XLA's row scatter is serial in its
        updates: PERF.md, PR 74)."""
        if not self.head_major:
            return (self.n_kv, width), flat
        block, offset = jnp.divmod(flat, self.block_size)
        head = jnp.arange(self.n_kv, dtype=flat.dtype)
        index = (block[:, None] * self.n_kv + head) * self.block_size + offset[:, None]
        return (width,), index.reshape(-1)


def kv_block_layout(pool: jax.Array, line_width: int) -> KVBlockLayout:
    """The layout of a 4-d pool whose token's K (or V) is ``line_width`` values
    (its KV heads x their width): the pool's shape says which it is, dim 1
    holding the line's heads or dim 2. Where a block has as many tokens as a
    line has heads the shape cannot say, and the pool is taken to lie as
    ``init_pools`` would have made it (``head_major_kv``; an int8 pool is
    never head-major)."""
    n_kv = line_width // pool.shape[-1]
    if pool.shape[1] != pool.shape[2]:
        head_major = pool.shape[1] == n_kv
    else:
        head_major = pool.dtype != jnp.int8 and head_major_kv(
            pool.shape[1], n_kv, pool.shape[-1], pool.dtype.itemsize)
    return KVBlockLayout(pool.shape[2 if head_major else 1], n_kv, head_major)


def _pack_queries(q: jax.Array, n_kv: int, pack: int) -> jax.Array:
    """Queries for a pool that holds ``pack`` KV heads a lane row.

    A query of KV head ``a`` among the ``pack`` of a row is widened to 128
    lanes with zeros everywhere but in lanes ``[a h, (a + 1) h)``: its scores
    against the wide head are those against its own KV head (the others'
    lanes meet zeros), its softmax is its own row's, and lanes ``[a h, (a +
    1) h)`` of its output are its output. The ``pack`` KV heads' groups become
    one group of ``pack`` times the rows: the pool's bytes move once, the
    MXU's contraction is ``pack`` times as long as the mathematics needs."""
    rows, s, n, h = q.shape
    group = n // n_kv
    q = q.reshape(rows, s, n_kv // pack, pack, group, 1, h)
    own = jnp.eye(pack, dtype=q.dtype).reshape(1, 1, 1, pack, 1, pack, 1)
    return (q * own).reshape(rows, s, n, pack * h)


def _pipeline_carry(valid_len: jax.Array, tile: int):
    """What the DMA pipeline carries from one grid step to the next, both a
    function of ``valid_len`` alone: per row the tiles of the rows before
    it (their parity is the buffer the row's first tile lies in; 0: nobody
    has fetched it, the row starts it itself) and the next row that runs a
    tile at all (-1: none, the row's last tile prefetches nothing)."""
    rows = valid_len.shape[0]
    tiles = -(-valid_len // tile)
    tiles_before = jnp.cumsum(tiles) - tiles
    active = jnp.where(tiles > 0, jnp.arange(rows, dtype=jnp.int32), rows)
    following = jnp.append(jax.lax.cummin(active, reverse=True)[1:], rows)
    return (
        tiles_before.astype(jnp.int32),
        jnp.where(following == rows, -1, following).astype(jnp.int32),
    )


# jitted on its own: a model calls the kernel once a layer with the same
# shapes, and tracing the body (two query paths, the DMA loops) is slow
# Python — done once here, not once a layer, and lowered as one function
@functools.partial(
    jax.jit, static_argnames=("sm_scale", "group", "tile_blocks", "head_major",
                              "interpret")
)
def _paged_call(
    q, pool_k, pool_v, block_table, valid_len, q_slot_base, scale_k, scale_v,
    chosen, *, sm_scale: float, group: int, tile_blocks: int,
    head_major: bool, interpret: bool,
):
    rows, s, n, h = q.shape
    n_kv = n // group
    block_size = pool_k.shape[2 if head_major else 1]
    max_blocks = block_table.shape[1]
    assert pool_k.shape[1 if head_major else 2] == n_kv, (pool_k.shape, n, group)
    quantized, masked = scale_k is not None, chosen is not None
    tile = tile_blocks * block_size
    sub_blocks = _blocks_per_sub(block_size, tile_blocks)
    # fold the GQA group into the matmul's rows: per KV head the queries are
    # (s_pad * group, h), position-major, so the first positions of a row
    # are the first folded rows. 16 rows fill a packed bf16 register.
    s_pad = _round_up(s, 8)
    m_full = s_pad * group
    m_short = min(m_full, _round_up(_SHORT_QUERIES * group, 16))
    # under a mask, a call of ONE position a row folds a row's group alone
    # (8 x the rows cost Keye's shapes 7-9% of a call: PERF.md, PR 64)
    single = masked and s == 1
    if single:
        m_short = min(m_full, _round_up(group, 16))
    folded = jnp.pad(q, ((0, 0), (0, s_pad - s), (0, 0), (0, 0)))
    folded = folded.reshape(rows, s_pad, n_kv, group, h)
    folded = folded.transpose(0, 2, 1, 3, 4).reshape(rows, n_kv, m_full, h)

    def _row(bi, *_):  # the row's block; the prefetched scalars play no part
        return (bi, 0, 0, 0)

    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    in_specs = [pl.BlockSpec((1, n_kv, m_full, h), _row), in_hbm, in_hbm]
    tile_dims = (2, n_kv, tile, h) if head_major else (2, tile, n_kv, h)
    scratch = [
        pltpu.VMEM(tile_dims, pool_k.dtype),
        pltpu.VMEM(tile_dims, pool_v.dtype),
    ]
    operands = [folded, pool_k, pool_v]
    if quantized:
        # the rows' scales, gathered (1/h of the int8 window's bytes) and
        # turned slot-minor, so a tile's are one lane-dense (1, tile) strip
        window = pl.cdiv(max_blocks, tile_blocks) * tile
        for scale in (scale_k, scale_v):
            scale = scale[block_table].reshape(rows, -1, n_kv)
            scale = jnp.pad(
                scale, ((0, 0), (0, window - scale.shape[1]), (0, 0))
            )
            operands.append(scale.transpose(0, 2, 1))
            in_specs.append(
                pl.BlockSpec((1, n_kv, window), lambda bi, *_: (bi, 0, 0))
            )
    if masked:
        # the rows' choices as int32, as ``masked_gqa_attention`` takes its
        # mask (1/512 of a row's K and V bytes at Keye's line; int8 read the
        # same to 1-2%: PERF.md, PR 64), slot-minor and padded to whole tiles:
        # a tile's is one lane-dense (1, tile) strip
        window = pl.cdiv(max_blocks, tile_blocks) * tile
        strip = chosen[:, :window].astype(jnp.int32)
        strip = jnp.pad(strip, ((0, 0), (0, window - strip.shape[1])))
        operands.append(strip[:, None, :])
        in_specs.append(
            pl.BlockSpec((1, 1, window), lambda bi, *_: (bi, 0, 0))
        )
    scratch += [
        pltpu.SemaphoreType.DMA((2, 2)),
        pltpu.VMEM((n_kv, m_full, 1), jnp.float32),   # running max m
        pltpu.VMEM((n_kv, m_full, 1), jnp.float32),   # normalizer l
        pltpu.VMEM((n_kv, m_full, h), jnp.float32),   # unnormalized acc
    ]
    # a row sees no slot past its table
    valid_len = jnp.minimum(
        valid_len.astype(jnp.int32), max_blocks * block_size
    )
    tiles_before, next_row = _pipeline_carry(valid_len, tile)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(rows,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, n_kv, m_full, h), _row),
        scratch_shapes=scratch,
    )
    kernel = functools.partial(
        _paged_attention_kernel,
        block_size=block_size, tile_blocks=tile_blocks,
        sub_blocks=sub_blocks, sm_scale=sm_scale,
        group=group, m_short=m_short, quantized=quantized, masked=masked,
        single=single, head_major=head_major,
    )
    # what a row's blocks take in VMEM: queries and output (double-buffered),
    # the float32 accumulator, m and l (one value a row, a lane row each), the
    # K and V tile buffers, the scores of one KV head. The kernels the cells
    # had stay under the default and are built with the parameters they had.
    row = n_kv * m_full * h
    needed = (4 * row * q.dtype.itemsize + 4 * row + 2 * 4 * n_kv * m_full * 128
              + 4 * tile * (n_kv if head_major else
                            max(n_kv, 8 * max(1, 4 // pool_k.dtype.itemsize)))
              * h * pool_k.dtype.itemsize + 3 * 4 * m_full * tile)
    limit = {} if needed <= _VMEM_DEFAULT_BYTES else {
        "vmem_limit_bytes": min(_VMEM_CEILING_BYTES, needed + needed // 4)}
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(folded.shape, q.dtype),
        # the rows run in order on one core: a row's first tile is started
        # by the row before it
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), **limit
        ),
        interpret=interpret,
        name="paged_attention",  # the trace's and the HLO's name for it
    )(
        block_table.astype(jnp.int32),
        valid_len,
        q_slot_base.astype(jnp.int32),
        tiles_before,
        next_row,
        *operands,
    )
    out = out.reshape(rows, n_kv, s_pad, group, h).transpose(0, 2, 1, 3, 4)
    return out.reshape(rows, s_pad, n, h)[:, :s]
