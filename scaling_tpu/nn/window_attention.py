"""Grouped-query attention over a SLIDING WINDOW, a kind of layer of its own
(a ``layer_pattern``'s ``window``: Laguna's ``sliding_attention`` layers beside
its ``full_attention`` ones).

The block is ``nn/attention.py``'s ``ParallelSelfAttention`` (separate Q / K /
V, rotary, the per-head output gate), with a head count and a rotary of its
own, and one more rule: query ``t`` sees key ``s`` iff ``t - window < s <= t``
(itself included, ``window`` lines at most).

**Uncached** (``prefill_forward``, the pool's probe, ``generate(
use_cache=False)``): the parent's unfused attention under the window's mask.

**Served: a RING a slot, not pages.** What a query may see never reaches
further back than ``window - 1`` lines, so a window layer keeps, for every
SLOT, a fixed number of lines whatever the context (:class:`WindowRingView`:
``k`` and ``v``, ``(slots, ring, n_kv x h)``: a line's heads side by side, the
lane-dense rows the kernel's plain tiles read; a line a slot in
``serve/kvcache.py``'s terms, as Mamba-2's and the short convolution's).
Position ``p`` lies at line ``p % ring``. The invariant that sizes the ring:

    ring >= window - 1 + the most tokens a row brings to a tick

A tick first WRITES its rows' new K and V (one scatter; what is no token is
dropped), then attends. A row that brings ``c`` tokens from position ``p0`` on
needs the lines of ``[p0 - (window - 1), p0 + c)``: ``window - 1 + c``
consecutive positions, which under the invariant fall on distinct lines, so
no write of the tick has landed on a line one of its queries still reads.
Which position a line holds follows from the row's last position alone
(``last - ((last - line) % ring)``; negative: the line is a former
occupant's, or empty, and is masked), so a slot that is reused or a row that
is evicted and prefilled again needs no reset by the host.

A row's ring is contiguous, so nothing is gathered: the rows attend through
``nn/window_ring_attention.py`` (K and V read from the rings where they lie;
the mask is the positions', computed in the kernel; only the tiles that hold a
line of the row's arc are fetched) at their real shapes: the rows of ONE token
all in one call, a chunk row at the row width, one by one (a rolled loop; a
slot without a chunk costs a branch). ``nn/masked_gqa_attention.py`` (Keye's
kernel: contiguous K and V under a mask operand) was tried first and computes
the same numbers, but at a GQA group of 9 its broadcast of a position's mask
row over the group is a relayout that Mosaic takes four minutes to compile
(PERF.md, PR 68).

``ctx.paged_kernel`` ``'xla'`` is the tests' reference of the walk: every
token gathers its row's whole ring and masks it.

Scopes (inside the layer's ``window_attn``): ``window_attend`` holds the walk
(the kernel's calls), ``gate`` the per-head gate. The scatter lies in neither.

Not built, refused by name (config validation, ``serve/engine.py``): int8
rings, model-parallel layers, rows that rewind (a ring line, once
overwritten, is gone), training, pipeline stages, context parallelism, the
prefix cache, a dense ``generate()`` cache, an indexer, LoRA.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from ..obs import count_kernel_build
from .attention import (
    PagedTokenMap,
    ParallelSelfAttention,
    multi_head_attention,
    repeat_kv,
)
from .base_layer import ForwardContext
from .masked_latent_attention import KEY_TILE
from .paged_attention import paged_kernel_interpret
from .short_conv import row_major_map
from .window_ring_attention import (
    KERNEL_NAME, NOBODY, line_positions, ring_tile, window_ring_attention,
)

# lines a tile of the one-token rows' call holds: such a row sees ``window``
# lines, an arc of the ring that finer tiles follow more closely
SINGLE_TILE = 256


class WindowRingView(NamedTuple):
    """One window layer's rings of the serving engine's state pool
    (serve/kvcache.py), plus the tick's addressing. Row ``r`` of the tick is
    slot ``r``'s ring."""

    # the fields the pool owns and the name in spans and counters
    LINES = ("k", "v")
    NAME = "window"

    k: jax.Array            # (slots, ring, n_kv x h) position p at line p % ring
    v: jax.Array            # (slots, ring, n_kv x h)
    context_len: jax.Array  # (slots,) int32 tokens the row has cached
    new_len: jax.Array      # (slots,) int32 real tokens the row brings
    token_map: Optional[PagedTokenMap] = None  # token-major batches


def ring_lines(window: int, row_width: int) -> int:
    """Lines of a ring: the smallest multiple of the kernel's tile that holds
    ``window - 1 + row_width`` lines (below one tile: the power of two that
    does, 8 at least)."""
    need = window - 1 + row_width
    if need >= KEY_TILE:
        return -(-need // KEY_TILE) * KEY_TILE
    return max(8, 1 << (need - 1).bit_length())


class RingRows(NamedTuple):
    """A tick's addressing of the slots' rings (``ring_rows``)."""

    ctx_len: jax.Array   # (slots,) int32 tokens a row has cached
    new_len: jax.Array   # (slots,) int32 real tokens it brings
    last: jax.Array      # (slots,) int32 the last position it will have written
    starts: jax.Array    # (slots,) int32 a row's first token in the batch
    width: int           # the most tokens a row brings
    row: jax.Array       # (tokens,) int32 a token's row
    at: jax.Array        # (tokens,) int32 its position
    real: jax.Array      # (tokens,) bool: it is a token
    line: jax.Array      # (tokens,) int32 its line of the flattened rings;
    #                      past them for what is no token (dropped)


def ring_rows(view, shape, window: int, batch_shape) -> RingRows:
    """Where a tick's tokens lie in the rings of a view that keeps a ring a
    slot (``shape``: its ``(slots, ring)``), and the invariant that lets a
    tick write before it attends: ``ring >= window - 1 + row width``."""
    slots, ring = shape
    tmap = view.token_map
    if tmap is None:
        tmap = row_major_map(*batch_shape)
    ctx_len = view.context_len.astype(jnp.int32)
    new_len = view.new_len.astype(jnp.int32)
    width = tmap.row_tokens.shape[1]
    if ring < window - 1 + width:
        raise ValueError(
            f"a ring of {ring} lines under rows of up to {width} tokens "
            f"and a window of {window}: a chunk's writes would "
            "land on lines its queries still read; the ring holds window "
            "- 1 + row width lines at least (serve/engine.py sizes it)")
    row, offset = tmap.row.reshape(-1), tmap.offset.reshape(-1)
    real = offset < new_len[row]
    at = ctx_len[row] + offset
    # what is no token is dropped: a ring has no trash line
    line = jnp.where(real, row * ring + at % ring, slots * ring)
    return RingRows(ctx_len, new_len, ctx_len + new_len - 1,
                    tmap.row_tokens[:, 0], width, row, at, real, line)


def ring_written(lines: jax.Array, line: jax.Array, new: jax.Array):
    """``lines`` (slots, ring, lanes) with the tick's ``new`` values, a token
    a row of them, at ``line`` (``RingRows.line``): the ONE scatter of a
    ring."""
    flat = lines.reshape(lines.shape[0] * lines.shape[1], -1)
    return flat.at[line].set(
        new.reshape(line.shape[0], -1).astype(lines.dtype),
        mode="drop").reshape(lines.shape)


def final_ring(lines: jax.Array, window: int, row_width: int):
    """What an uncached pass over ``lines`` (b, s, lanes) leaves in a ring
    sized for ticks whose rows bring ``row_width`` tokens: ``(b, ring,
    lanes)``, position ``p`` at line ``p % ring``, zeros where nothing is."""
    s = lines.shape[1]
    held = line_positions(jnp.int32(s - 1), ring_lines(window, row_width))
    return jnp.where((held >= 0)[None, :, None],
                     lines[:, jnp.maximum(held, 0)], 0)


def walk_ring_rows(attend, q, at: RingRows, window: int, ring: int):
    """Every row's queries ``q`` (tokens, n, lanes) over its own ring through
    a ring kernel, at the row's real shape: the rows of ONE token all in one
    call (a slot that decodes nothing folds nothing), the rows that bring a
    chunk one by one (a rolled loop; a slot without one costs a branch). What
    no row owns stays zero. ``attend(q (rows, positions, n, lanes), slot, at,
    last, first, live, tile=)`` is the kernel over the layer's rings."""
    tokens, n = q.shape[:2]
    ctx_len, new_len, last, starts, width = (
        at.ctx_len, at.new_len, at.last, at.starts, at.width)
    rows = new_len.shape[0]
    # the first position a row's first query sees
    first = ctx_len - (window - 1)
    single = new_len == 1
    ones = attend(
        q[starts][:, None], jnp.arange(rows, dtype=jnp.int32),
        ctx_len[:, None], last, first, single,
        tile=ring_tile(ring, SINGLE_TILE))
    out = jnp.zeros((tokens, n, ones.shape[-1]), q.dtype).at[
        jnp.where(single, starts, tokens)].set(ones[:, 0], mode="drop")
    if width == 1:
        return out

    def chunk(r, out):
        # ``width`` places from the row's first token, or the batch's
        # last ``width`` where that would pass its end: the row's tokens
        # then lie ``shift`` places in, among other rows'
        begin = jnp.minimum(starts[r], tokens - width)
        place = jnp.arange(width, dtype=jnp.int32) - (starts[r] - begin)
        keep = (place >= 0) & (place < new_len[r])
        mine = attend(
            jax.lax.dynamic_slice_in_dim(q, begin, width, 0)[None], r[None],
            jnp.where(keep, ctx_len[r] + place, NOBODY)[None],
            last[r][None], first[r][None], jnp.ones((1,), bool),
            tile=ring_tile(ring, KEY_TILE))[0]
        old = jax.lax.dynamic_slice_in_dim(out, begin, width, 0)
        return jax.lax.dynamic_update_slice_in_dim(
            out, jnp.where(keep[:, None, None], mine, old), begin, 0)

    def one_row(out, r):
        return jax.lax.cond(new_len[r] > 1, chunk, lambda r, out: out,
                            r, out), None

    out, _ = jax.lax.scan(one_row, out, jnp.arange(rows, dtype=jnp.int32))
    return out


class WindowSelfAttention(ParallelSelfAttention):
    STATE_VIEW = WindowRingView

    def __init__(self, *, window_size: int, **attention):
        super().__init__(**attention)
        assert not self.qkv_in_one and not self.lora_modules, (
            "a window attention layer has separate Q / K / V and no LoRA")
        assert self.num_local_attention_heads == 0 and self.causal, (
            "a window attention layer is causal, its window the layer's")
        self.window_size = window_size

    # --------------------------------------------------------------- forward
    def __call__(
        self,
        params: dict,
        x: jax.Array,  # (b, s, hidden)
        ctx: ForwardContext,
        segment_ids: Optional[jax.Array] = None,
        position_ids: Optional[jax.Array] = None,
        state: Optional[WindowRingView] = None,
        return_state: bool = False,
    ):
        """Without ``state`` each of the ``b`` sequences attends whole under
        the window's mask (``return_state``: also its final rings, sized for
        ticks whose rows bring ``ctx.serve_row_width`` tokens); with ``state``
        the batch is the tick's, and the second result is the view with its
        rings written."""
        b, s, _ = x.shape
        q, k, v = self._heads(params, x, ctx, position_ids)
        if state is not None:
            out, new_view = self._serve(q, k, v, state, ctx)
            return self._project_out(params, out, ctx, b, s, new_view, x)
        if segment_ids is None:
            segment_ids = jnp.zeros((b, s), dtype=jnp.int32)
        at = jnp.arange(s, dtype=jnp.int32)
        back = at[:, None] - at[None, :]
        allowed = ((back >= 0) & (back < self.window_size))[None] & (
            segment_ids[:, :, None] == segment_ids[:, None, :])
        out = multi_head_attention(
            q, repeat_kv(k, self.num_repeat_kv), repeat_kv(v, self.num_repeat_kv),
            ~allowed[:, None], self.scaling_factor, self.masked_softmax)
        rings = None
        if return_state:
            rings = tuple(
                final_ring(a.reshape(b, s, -1), self.window_size,
                           ctx.serve_row_width) for a in (k, v))
        return self._project_out(params, out, ctx, b, s, rings, x)

    # ---------------------------------------------------------------- served
    def _serve(self, q, k, v, view: WindowRingView, ctx: ForwardContext):
        """Write the batch's K and V to the rows' rings, then attend, row by
        row, under the window's mask: ``((g, s, n, h), the updated view)``."""
        g, s, n, h = q.shape
        tokens = g * s
        at = ring_rows(view, view.k.shape[:2], self.window_size, (g, s))
        view = view._replace(k=ring_written(view.k, at.line, k),
                             v=ring_written(view.v, at.line, v))
        q = q.reshape(tokens, n, h)
        with jax.named_scope("window_attend"):
            if ctx.paged_kernel == "pallas":
                out = self._walk_rows(q, view, at)
            else:
                assert ctx.paged_kernel == "xla", (
                    f"unknown paged_kernel {ctx.paged_kernel!r} (expected "
                    "'pallas' or 'xla')")
                out = self._attend_gathered_rings(
                    q, view, at.row, at.at, at.real, at.last)
        return out.reshape(g, s, n, h), view

    def _walk_rows(self, q, view: WindowRingView, at: RingRows):
        """Every row's queries over its own ring through the ring kernel
        (``walk_ring_rows``: the rows' real shapes)."""
        interpret = paged_kernel_interpret()
        count_kernel_build(KERNEL_NAME, interpret)
        attend = functools.partial(
            window_ring_attention, window=self.window_size,
            sm_scale=float(self.scaling_factor), interpret=interpret)
        return walk_ring_rows(
            lambda q, *rows, tile: attend(q, view.k, view.v, *rows, tile=tile),
            q, at, self.window_size, view.k.shape[1])

    def _attend_gathered_rings(self, q, view: WindowRingView, row, at, real,
                               last):
        """The same numbers with no walk and no kernel: every token gathers
        its row's WHOLE ring and masks it. The tests' reference of
        ``_walk_rows``."""
        tokens, n, h = q.shape
        n_kv, group = self.num_kv_heads, self.num_repeat_kv
        ring = view.k.shape[1]
        held = line_positions(last, ring)[row]                 # (tokens, ring)
        visible = (real[:, None] & (held >= 0) & (held <= at[:, None])
                   & (held > at[:, None] - self.window_size))
        keys, values = (a[row].reshape(tokens, ring, n_kv, h)
                        for a in (view.k, view.v))
        s = jnp.einsum("tgjh,twgh->tgjw", q.reshape(tokens, n_kv, group, h), keys,
                       preferred_element_type=jnp.float32)
        s = jnp.where(visible[:, None, None, :], s * self.scaling_factor, -jnp.inf)
        top = jnp.max(s, axis=-1, keepdims=True)
        e = jnp.exp(s - jnp.where(top == -jnp.inf, 0.0, top))
        e = e / jnp.maximum(e.sum(axis=-1, keepdims=True), 1e-30)
        return jnp.einsum("tgjw,twgh->tgjh", e.astype(values.dtype), values,
                          preferred_element_type=jnp.float32
                          ).astype(q.dtype).reshape(tokens, n, h)
