"""Multi-head LATENT attention over a SLIDING WINDOW, a kind of layer of its
own (a ``layer_pattern``'s ``window_latent``: dots3-note's ``sliding_attention``
layers beside its sparse ``full_attention`` ones).

The block is ``nn/latent_attention.py``'s ``LatentSelfAttention``, every
projection of it (and its two options, the head-wise gate and the latents'
rescale), at sizes of its own, with one more rule: query ``t`` sees line ``s``
iff ``t - window < s <= t`` (itself included, ``window`` lines at most).

**Uncached** (``prefill_forward``, the pool's probe, ``generate(
use_cache=False)``): the parent's expanded heads under the window's mask.

**Served: a RING of latent lines a slot, not pages**, under the rule of
``nn/window_attention.py`` (its ``ring_lines``, ``ring_rows``, ``ring_written``
and ``walk_ring_rows``: position ``p`` at line ``p % ring``, ``ring >= window
- 1 + the most tokens a row brings``, a tick writes its rows' lines and then
attends, which position a line holds follows from the row's last position
alone). :class:`LatentRingView` has ONE leaf, ``line``, ``(slots, ring,
kv_lora_rank + rope_line_width(rope))``: ``[c_kv after norm and rescale | k_r
after rotary | zeros]``, the rotary key in a lane row of 128 as every latent
line here. The rows attend in the ABSORBED form through
``nn/latent_ring_attention.py``: all heads over the line as ONE shared KV head
whose value is the line's first ``kv_lora_rank`` lanes, ``W_UK`` and ``W_UV``
applied around the kernel as two views of the one ``kv_b_proj`` leaf. The rows
of ONE token go in one call, a chunk row at the row width, one by one.

``ctx.paged_kernel`` ``'xla'`` is the tests' reference of the walk: every
token gathers its row's whole ring and masks it.

Scopes (inside the layer's ``window_latent_attn``): ``window_latent_attend``
holds the walk (the kernel's calls), ``gate`` the head-wise gate. The scatter
lies in neither.

Not built, refused by name (config validation, ``serve/engine.py``): int8
rings, model-parallel layers, training, pipeline stages,
context parallelism, the prefix cache, a dense ``generate()`` cache, an indexer
inside the window, GQA ``window`` layers in the same stack.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from ..obs import count_kernel_build
from .attention import PagedTokenMap
from .base_layer import ForwardContext
from .latent_attention import LatentSelfAttention
from .latent_ring_attention import KERNEL_NAME, latent_ring_attention
from .paged_attention import paged_kernel_interpret
from .window_attention import (
    RingRows, final_ring, ring_rows, ring_written, walk_ring_rows,
)
from .window_ring_attention import line_positions


class LatentRingView(NamedTuple):
    """One windowed latent layer's rings of the serving engine's state pool
    (serve/kvcache.py), plus the tick's addressing. Row ``r`` of the tick is
    slot ``r``'s ring."""

    # the field the pool owns and the name in spans and counters
    LINES = ("line",)
    NAME = "window_latent"

    line: jax.Array         # (slots, ring, kv_lora_rank + rope lane row)
    context_len: jax.Array  # (slots,) int32 tokens the row has cached
    new_len: jax.Array      # (slots,) int32 real tokens the row brings
    token_map: Optional[PagedTokenMap] = None  # token-major batches


class WindowLatentSelfAttention(LatentSelfAttention):
    STATE_VIEW = LatentRingView

    def __init__(self, *, window_size: int, **latent):
        super().__init__(**latent)
        self.window_size = window_size

    # --------------------------------------------------------------- forward
    def __call__(
        self,
        params: dict,
        x: jax.Array,  # (b, s, hidden)
        ctx: ForwardContext,
        segment_ids: Optional[jax.Array] = None,
        position_ids: Optional[jax.Array] = None,
        state: Optional[LatentRingView] = None,
        return_state: bool = False,
    ):
        """Without ``state`` each of the ``b`` sequences attends whole under
        the window's mask (``return_state``: also its final ring, sized for
        ticks whose rows bring ``ctx.serve_row_width`` tokens); with ``state``
        the batch is the tick's, and the second result is the view with its
        ring written."""
        b, s, _ = x.shape
        q_nope, q_rope, c_kv, k_r, _ = self._latents(
            params, x, ctx, position_ids)
        if state is not None:
            out, new_view = self._serve(params, q_nope, q_rope, c_kv, k_r,
                                        state, ctx)
            return self._project_out(params, out, x, ctx), new_view
        if segment_ids is None:
            segment_ids = jnp.zeros((b, s), dtype=jnp.int32)
        at = jnp.arange(s, dtype=jnp.int32)
        back = at[:, None] - at[None, :]
        allowed = ((back >= 0) & (back < self.window_size))[None] & (
            segment_ids[:, :, None] == segment_ids[:, None, :])
        y = self._expanded(params, x, q_nope, q_rope, c_kv, k_r,
                           ~allowed[:, None], ctx)
        if return_state:
            return y, (final_ring(self._whole_line(c_kv, k_r),
                                  self.window_size, ctx.serve_row_width),)
        return y

    # ---------------------------------------------------------------- served
    def _serve(self, params, q_nope, q_rope, c_kv, k_r, view: LatentRingView,
               ctx: ForwardContext):
        """Write the batch's lines to the rows' rings, then attend, row by
        row, in the absorbed form under the window's mask: ``((g, s, n * v),
        the updated view)``."""
        g, s, n = q_nope.shape[:3]
        at = ring_rows(view, view.line.shape[:2], self.window_size, (g, s))
        view = view._replace(line=ring_written(
            view.line, at.line, self._whole_line(c_kv, k_r)))
        q_line, w_uv = self._query_line(params, q_nope, q_rope)
        with jax.named_scope("window_latent_attend"):
            if ctx.paged_kernel == "pallas":
                out = self._walk_rows(q_line, view, at)
            else:
                assert ctx.paged_kernel == "xla", (
                    f"unknown paged_kernel {ctx.paged_kernel!r} (expected "
                    "'pallas' or 'xla')")
                out = self._attend_gathered_rings(q_line, view, at)
        out = jnp.einsum("tnc,cnv->tnv", out, w_uv)
        return out.reshape(g, s, n * self.v_dim), view

    def _walk_rows(self, q_line, view: LatentRingView, at: RingRows):
        """Every row's queries over its own ring through the latent ring
        kernel (``walk_ring_rows``: the rows' real shapes)."""
        interpret = paged_kernel_interpret()
        count_kernel_build(KERNEL_NAME, interpret)
        attend = functools.partial(
            latent_ring_attention, window=self.window_size,
            lat=self.kv_lora_rank, sm_scale=float(self.scaling_factor),
            interpret=interpret)
        return walk_ring_rows(
            lambda q, *rows, tile: attend(q, view.line, *rows, tile=tile),
            q_line, at, self.window_size, view.line.shape[1])

    def _attend_gathered_rings(self, q_line, view: LatentRingView,
                               at: RingRows):
        """The same numbers with no walk and no kernel: every token gathers
        its row's WHOLE ring and masks it. The tests' reference of
        ``_walk_rows``."""
        ring = view.line.shape[1]
        held = line_positions(at.last, ring)[at.row]          # (tokens, ring)
        visible = (at.real[:, None] & (held >= 0) & (held <= at.at[:, None])
                   & (held > at.at[:, None] - self.window_size))
        lines = view.line[at.row]                       # (tokens, ring, lanes)
        s = jnp.einsum("tnc,twc->tnw", q_line, lines,
                       preferred_element_type=jnp.float32)
        s = jnp.where(visible[:, None, :], s * self.scaling_factor, -jnp.inf)
        top = jnp.max(s, axis=-1, keepdims=True)
        e = jnp.exp(s - jnp.where(top == -jnp.inf, 0.0, top))
        e = e / jnp.maximum(e.sum(axis=-1, keepdims=True), 1e-30)
        return jnp.einsum("tnw,twc->tnc", e.astype(lines.dtype),
                          lines[..., :self.kv_lora_rank],
                          preferred_element_type=jnp.float32
                          ).astype(q_line.dtype)
