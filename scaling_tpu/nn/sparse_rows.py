"""What the two sparse attention mixers share: the indexer's scores, the exact
choice of lines, and the walk over a tick's rows.

A sparse layer's query attends over the ``index_topk`` cached lines an indexer
scored highest for it. Whatever the line under the choice is (a latent line:
``nn/sparse_latent_attention.py``; the K and V of a grouped-query cache:
``nn/sparse_attention.py``), the arithmetic of the choice is one:

    I[t, s]   = sum_j w[t, j] relu(q_I[t, j] . k_I[s])         for s <= t
    S_t       = the min(index_topk, t + 1) lines s <= t of largest I[t, s]

``index_scores`` computes ``I`` in float32; ``choose_lines`` is the choice by
``jax.lax.top_k`` (the uncached forms', and the tests' reference of the
threshold); ``threshold_choice`` the same set as a mask, without a sort: the
``index_topk``-th largest visible score by bisection on the float's ordered
bits (``THRESHOLD_PASSES`` passes of compare-and-count) and, among the scores
equal to it, the lowest positions that fill the count. EXACT, a tie going to
the lower position. That fill (a prefix sum over the whole block of scores)
runs only in a call where some query has more visible scores at its threshold
than room for them; every other call's choice is the one compare ``score >=
threshold``, the same set. ``walk_rows`` counts the calls that filled
(``tie_breaks_heard``), over the queries whose result it keeps.

``walk_rows`` is the serving path's walk over a tick's rows, the same for
either line: the rows that bring ONE token (decode rows) are taken
``SINGLE_ROWS`` at a time, the rows that bring a chunk are then walked in
order, both rolled loops. Either way a row (1) gathers its index keys block by
block through its table and scores its queries key tile by key tile up to its
visible length, (2) finds each query's choice as a threshold, and (3) hands
its queries and the mask of what each chose to the mixer's own attention over
its own lines (``attend_single`` / ``attend_chunk``: what the line's layout
decides). Scores and masks span the smallest of ``_windows`` that holds what
is visible, and every loop over a row's tiles ends at its visible length. A
chunk row attends inside the branch of its window; a pass of one-token rows
AFTER it, their choice padded to the whole window, so that a mixer whose
``attend_single`` is a kernel (the grouped-query one's: the paged kernel under
a mask) builds it once a layer and not once a window.
"""

from __future__ import annotations

import contextlib
from typing import Callable

import jax
import jax.numpy as jnp

from .attention import paged_flat_slots

# index keys one step of a row's score loop multiplies
INDEX_TILE = 2048
# passes of the bisection that finds a query's threshold: one a bit of a
# float32's order
THRESHOLD_PASSES = 33
# rows of ONE token (decode rows) a pass of the row walk takes together: a
# pass scores the windows of all its rows up to the longest (and a mixer that
# folds in plain XLA streams them so), so a tick's few decode rows beside a
# prompt's chunk must not pay for every slot, and a walk row by row would pay
# the bisection's latency a row
SINGLE_ROWS = 4


def index_tile_tokens(block_size: int, max_blocks: int) -> int:
    """Index keys one step of a row's score loop holds at these shapes."""
    return block_size * max(1, min(max_blocks, INDEX_TILE // block_size))


def _windows(num_tiles: int, least: int):
    """The widths, in tiles, a row's scores and masks are computed at: the
    whole window, and its halves down to an eighth while they still hold
    ``least`` tiles (the lines a query keeps)."""
    widths = {num_tiles}
    for shift in (1, 2, 3):
        if num_tiles % (1 << shift) == 0 and (num_tiles >> shift) >= max(least, 1):
            widths.add(num_tiles >> shift)
    return sorted(widths)


def index_scores(q_i: jax.Array, k_i: jax.Array, w: jax.Array) -> jax.Array:
    """``I[t, s] = sum_j w[t, j] relu(q_i[t, j] . k_i[s])`` in float32:
    ``q_i`` (..., t, j, d), ``k_i`` (..., s, d), ``w`` (..., t, j) float32."""
    dots = jnp.einsum("...tjd,...sd->...tjs", q_i, k_i,
                      preferred_element_type=jnp.float32)
    return jnp.einsum("...tjs,...tj->...ts", jax.nn.relu(dots), w)


def choose_lines(scores: jax.Array, visible: jax.Array, topk: int):
    """The exact choice: ``(idx (..., k), held (..., k))`` with ``k =
    min(topk, lines)``, the positions of each query's ``k`` largest visible
    ``scores`` (..., lines) and which of them hold a line at all (a query
    that sees fewer than ``k``). Among equal scores the lower position wins
    (``jax.lax.top_k``'s order)."""
    k = min(topk, scores.shape[-1])
    _, idx = jax.lax.top_k(jnp.where(visible, scores, -jnp.inf), k)
    seen = jnp.sum(visible, axis=-1, keepdims=True)
    return idx, jnp.arange(k) < seen


def chosen_mask(scores: jax.Array, visible: jax.Array, topk: int) -> jax.Array:
    """``choose_lines``' set as a mask ``(..., lines)`` bool."""
    idx, held = choose_lines(scores, visible, topk)
    flat_idx = idx.reshape(-1, idx.shape[-1])
    mask = jnp.zeros((flat_idx.shape[0], scores.shape[-1]), bool).at[
        jnp.arange(flat_idx.shape[0])[:, None], flat_idx].max(
            held.reshape(flat_idx.shape))
    return mask.reshape(scores.shape)


def ordered_bits(x: jax.Array) -> jax.Array:
    """float32 -> int32 with the same order (``-inf`` lowest; NaN is no
    score)."""
    bits = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.int32)
    return jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)


# who listens for the calls of ``threshold_choice`` that filled ties by
# position (``tie_breaks_heard``): the function's result stays the mask alone
_hearing: list = []


@contextlib.contextmanager
def tie_breaks_heard():
    """The ``over`` (a bool scalar) of every ``threshold_choice`` called
    inside, in a list: whether that call filled ties by position. Read it at
    the level the calls were traced at."""
    heard: list = []
    _hearing.append(heard)
    try:
        yield heard
    finally:
        _hearing.remove(heard)


def kth_largest(bits: jax.Array, k: int) -> jax.Array:
    """The largest value that at least ``k`` of ``bits`` (..., lines) int32
    reach, by bisection: ``THRESHOLD_PASSES`` passes of compare-and-count."""
    low = jnp.full(bits.shape[:-1], jnp.iinfo(jnp.int32).min, jnp.int32)
    high = jnp.full(bits.shape[:-1], jnp.iinfo(jnp.int32).max, jnp.int32)

    def halve(_, bounds):
        # at least k scores reach `low`; fewer reach `high` (or it is the top)
        low, high = bounds
        mid = (low >> 1) + (high >> 1) + (low & high & 1)
        mid = jnp.where(mid == low, high, mid)
        enough = jnp.sum(bits >= mid[..., None], axis=-1) >= k
        return jnp.where(enough, mid, low), jnp.where(enough, high, mid)

    return jax.lax.fori_loop(0, THRESHOLD_PASSES, halve, (low, high))[0]


def threshold_choice(scores: jax.Array, visible: jax.Array, topk: int):
    """The exact choice as a mask ``(..., lines)`` bool: each query's
    ``min(topk, seen)`` visible lines of largest score, a tie going to the
    lower position; ``choose_lines``' set without a sort. The ``topk``-th
    largest visible score is found by bisection on the ordered bits
    (``kth_largest``). Where no query has more visible scores reaching it than
    ``topk``, those are the choice; in a call where some query has,
    everything above the threshold is chosen, and of the visible scores equal
    to it the first ``topk - (those above)`` by position: the same set either
    way."""
    bits = ordered_bits(jnp.where(visible, scores, -jnp.inf))
    low = kth_largest(bits, topk)
    # (what is invisible, all -inf, ties with itself under a query that sees
    # fewer than topk: only VISIBLE ties can be more than there is room for)
    reach = visible & (bits >= low[..., None])
    over = jnp.any(jnp.sum(reach, axis=-1) > topk)
    for heard in _hearing:
        heard.append(over)

    def lower_ties_first():
        above = reach & (bits > low[..., None])
        equal = reach & ~above
        room = topk - jnp.sum(above, axis=-1, keepdims=True)
        return above | (equal & (jnp.cumsum(equal, axis=-1) <= room))

    return jax.lax.cond(over, lower_ties_first, lambda: reach)


def tile_of(pool: jax.Array, tables: jax.Array, t, tile_blocks: int):
    """Tile ``t`` (``tile_blocks`` consecutive table entries) of each of
    ``tables``' rows, gathered from ``pool`` (blocks, block_size, ...):
    ``(rows, tile_blocks x block_size, lanes)``."""
    blocks = jax.lax.dynamic_slice_in_dim(tables, t * tile_blocks, tile_blocks, 1)
    return pool[blocks].reshape(tables.shape[0], tile_blocks * pool.shape[1], -1)


def row_addresses(view, batch_shape):
    """Where a paged batch ``(b, s)`` writes and how its rows lie: ``(ctx_len
    (rows,), new_len (rows,), row (b, s), offset (b, s), real (b, s), flat
    (b * s,), starts (rows,), width)``: the slots cached and the tokens
    brought a row; each position's row, its place among the row's new tokens
    and whether it holds a token; the pool slot each position writes (what is
    no token: the trash block); a row's first token in the batch and the most
    tokens a row may bring."""
    b, s = batch_shape
    rows = view.block_table.shape[0]
    ctx_len = view.context_len.astype(jnp.int32)
    if view.new_len is None:
        new_len = jnp.full((rows,), s, jnp.int32)
    else:
        new_len = view.new_len.astype(jnp.int32)
    row, offset, real = view.token_rows((b, s))
    # a leaf without a head axis says the block's size in its dim 1 (a 4-d K
    # pool may lie head-major: paged_attention.kv_block_layout)
    sized = view.pool_k if view.pool_i is None else view.pool_i
    flat = paged_flat_slots(
        view.block_table, ctx_len[row] + offset, sized.shape[1], row)
    flat = jnp.where(real, flat, 0).reshape(-1)
    if view.token_map is None:      # row-major: row r's tokens at r * s
        starts, width = jnp.arange(rows, dtype=jnp.int32) * s, s
    else:
        starts = view.token_map.row_tokens[:, 0]
        width = view.token_map.row_tokens.shape[1]
    return ctx_len, new_len, row, offset, real, flat, starts, width


def walk_rows(
    *,
    index_pool: jax.Array,    # (blocks, block_size, index_dim) the index keys
    block_table: jax.Array,   # (rows, max_blocks)
    ctx_len: jax.Array,       # (rows,) int32 slots cached before the tick
    new_len: jax.Array,       # (rows,) int32 tokens the row brings
    starts: jax.Array,        # (rows,) int32 a row's first token in the batch
    width: int,               # the most tokens a row may bring
    topk: int,
    q_i: jax.Array,           # (tokens, index_heads, index_dim)
    w: jax.Array,             # (tokens, index_heads) float32
    queries: jax.Array,       # (tokens, ...) what attends
    out: jax.Array,           # (tokens, ...) zeros: what no row owns stays so
    choice: Callable,         # (scores, visible, k) -> mask: threshold_choice
    attend_single: Callable,
    attend_chunk: Callable,
):
    """The attention of every token over the lines it chose, written into
    ``out``, and beside it one int32: the calls of ``choice`` that filled ties
    by position (``threshold_choice``'s slow branch), which only a query whose
    result is kept can cause.

    A tick pays for the rows' real shapes, not for ``rows x width`` padded
    queries against every window: the rows that bring ONE token are taken
    ``SINGLE_ROWS`` at a time (a walk row by row would pay the bisection's 33
    passes a row, one batch of every slot would stream the windows of the
    slots that decode nothing), the rows that bring more are walked in order
    (a rolled loop), each at its ``width`` positions.

    ``attend_single(tables (g, blocks), seen (g,), queries (g, 1, ...),
    chosen (g, 1, window) bool)`` -> ``(g, ...)`` and ``attend_chunk(table
    (blocks,), seen (), queries (width, ...), chosen (width, tiles x tile)
    bool, tiles)`` -> ``(width, ...)`` are the mixer's attention over its own
    lines under the mask (``tables`` are padded to whole tiles with the trash
    block; ``tiles`` is static). A chunk row's is traced once a window of
    ``_windows``; the one-token rows' ONCE, after their pass has chosen, at
    the whole window's width (a place past the count of such rows has ``seen``
    0 and chose nothing: it must cost nothing, or little). Both run under the
    scope ``sparse_attend``, the scores and the choice under ``indexer`` /
    ``index_select``."""
    tokens = queries.shape[0]
    rows, max_blocks = block_table.shape
    block_size = index_pool.shape[1]
    k = min(topk, max_blocks * block_size)
    tile = index_tile_tokens(block_size, max_blocks)
    tile_blocks = tile // block_size
    num_tiles = -(-max_blocks // tile_blocks)
    # a table's tail past its last whole tile addresses the trash block
    table = jnp.pad(block_table.astype(jnp.int32),
                    ((0, 0), (0, num_tiles * tile_blocks - max_blocks)))
    windows = _windows(num_tiles, -(-k // tile))
    valid = ctx_len + new_len

    def choose(tables, base, seen, owned, q_i, w, tiles: int):
        """What ``r`` rows of ``p`` consecutive queries from slot ``base``
        on attend to, each row over its own ``seen`` slots: q_i (r, p, j,
        d), w (r, p, j) -> (r, p, tiles * tile) bool, and whether the choice
        filled ties by position (int32 0 / 1). A query that is not ``owned``
        (r, p) sees nothing: its result is thrown away (another row's token,
        padding), so its ties must not cost the call the fill."""
        r, p = q_i.shape[:2]
        with jax.named_scope("indexer"), jax.named_scope("index_select"):
            scores = jax.lax.fori_loop(
                0, -(-jnp.max(seen) // tile),
                lambda t, scores: jax.lax.dynamic_update_slice_in_dim(
                    scores,
                    index_scores(
                        q_i, tile_of(index_pool, tables, t, tile_blocks), w),
                    t * tile, 2),
                jnp.zeros((r, p, tiles * tile), jnp.float32))
            slots = jnp.arange(tiles * tile, dtype=jnp.int32)
            at = base[:, None] + jnp.arange(p, dtype=jnp.int32)
            visible = ((slots < seen[:, None, None])
                       & (slots <= at[..., None]) & owned[..., None])
            with tie_breaks_heard() as heard:
                chosen = choice(scores, visible, k)
            return chosen, sum((over.astype(jnp.int32) for over in heard),
                               jnp.int32(0))

    def at_window(fn, slots_seen):
        """``fn(tiles)`` at the first of ``windows`` that holds
        ``slots_seen``."""
        return jax.lax.switch(
            jnp.sum(slots_seen > jnp.asarray(windows) * tile),
            [lambda tiles=tiles: fn(tiles) for tiles in windows])

    # ---- the rows of one token, ``group`` of them a pass, so that a tick
    # with few of them beside a prompt's chunk does not stream every
    # slot's window
    single = new_len == 1
    group = min(SINGLE_ROWS, rows)
    count = jnp.sum(single)
    # place g of a pass holds the g-th of them (no sort: a scatter by rank)
    order = jnp.zeros((rows + -rows % group,), jnp.int32).at[
        jnp.where(single, jnp.cumsum(single) - 1, rows + group)].set(
            jnp.arange(rows, dtype=jnp.int32), mode="drop")

    def one_group(g, carry):
        out, ties = carry
        mine = jax.lax.dynamic_slice_in_dim(order, g * group, group)
        live = g * group + jnp.arange(group) < count
        seen = jnp.where(live, valid[mine], 0)
        at = starts[mine]

        def first_tokens(tiles: int):
            chosen, filled = choose(table[mine], ctx_len[mine], seen,
                                    live[:, None], q_i[at][:, None],
                                    w[at][:, None], tiles)
            # at the whole window's width whichever window chose: what
            # attends is then traced once a walk, not once a window
            return jnp.pad(chosen, ((0, 0), (0, 0),
                                    (0, (num_tiles - tiles) * tile))), filled

        chosen, filled = at_window(first_tokens, jnp.max(seen))
        with jax.named_scope("sparse_attend"):
            first = attend_single(table[mine], seen, queries[at][:, None],
                                  chosen)
        # a place past the count writes nothing
        return (out.at[jnp.where(live, at, tokens)].set(first, mode="drop"),
                ties + filled)

    carry = jax.lax.fori_loop(
        0, -(-count // group), one_group, (out, jnp.int32(0)))
    if width == 1:
        return carry

    # ---- the rows that bring a chunk, one by one
    def one_row(carry, r):
        def chunk(carry):
            out, ties = carry
            # ``width`` places from the row's first token, or the batch's
            # last ``width`` where that would pass its end: the row's
            # tokens then lie ``shift`` places in
            first = jnp.minimum(starts[r], tokens - width)
            shift = starts[r] - first

            def of(a):
                return jax.lax.dynamic_slice_in_dim(a, first, width, 0)[None]

            # the row's own positions only: the places around them are
            # other rows' tokens
            place = jnp.arange(width) - shift
            keep = (place >= 0) & (place < new_len[r])

            def whole_chunk(tiles: int):
                chosen, filled = choose(
                    table[r][None], (ctx_len[r] - shift)[None],
                    valid[r][None], keep[None], of(q_i), of(w), tiles)
                with jax.named_scope("sparse_attend"):
                    return attend_chunk(table[r], valid[r], of(queries)[0],
                                        chosen[0], tiles), filled

            mine, filled = at_window(whole_chunk, valid[r])
            old = jax.lax.dynamic_slice_in_dim(out, first, width, 0)
            keep = keep.reshape((width,) + (1,) * (out.ndim - 1))
            return jax.lax.dynamic_update_slice_in_dim(
                out, jnp.where(keep, mine, old), first, 0), ties + filled

        return jax.lax.cond(new_len[r] > 1, chunk, lambda c: c, carry), None

    carry, _ = jax.lax.scan(one_row, carry, jnp.arange(rows, dtype=jnp.int32))
    return carry
