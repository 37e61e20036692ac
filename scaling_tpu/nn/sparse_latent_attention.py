"""Multi-head latent attention with a LEARNED SPARSE choice of lines
(DeepSeek-V3.2-Exp's sparse attention: a lightning indexer, then the MLA
softmax over each query's ``index_topk`` best lines only).

The block is ``nn/latent_attention.py``'s, every projection of it, plus an
indexer. With ``x_t`` the block's normed input and ``c_q,t = RMSNorm(x_t
W_DQ)`` the block's own query latent:

    q_I[t, j] = (c_q,t W_IQ)[j]           j < index_n_heads, index_head_dim wide
    k_I[s]    = LayerNorm(x_s W_IK)       ONE key a token; weight AND bias,
                                          float32 statistics
    rotary on the FIRST ``rope`` lanes of q_I[t, j] and of k_I[s]: the block's
    own tables (YaRN's with them); in a latent head the rope lanes come last
    w[t, j]   = (x_t W_Iw)[j] * index_n_heads ** -0.5 * index_head_dim ** -0.5
    I[t, s]   = sum_j w[t, j] relu(q_I[t, j] . k_I[s])         for s <= t
    S_t       = the min(index_topk, t + 1) lines s <= t of largest I[t, s]
    attention of query t: the latent softmax (same scale, same values) over
    s in S_t only

The choice is EXACT: ``jax.lax.top_k`` over the float32 index scores, a tie
going to the lower position (``choose_lines``). While a row's context is at
most ``index_topk`` every visible line is chosen and the layer is dense latent
attention.

**Expanded** (no cache: ``prefill_forward``, the pool's probe, ``generate(
use_cache=False)``): the parent's expanded heads under a mask that forbids
what a query did not choose.

**Absorbed over chosen lines** (serving, over the paged pool). What a token
leaves behind (``STATE_VIEW``: the paged rule; which leaf holds what is said
HERE, and differs from the parent's): ``pool_k`` holds the WHOLE latent line
``[c_kv after its norm (kv_lora_rank), k_r after rotary (rope), zeros]`` in
``kv_lora_rank + rope_line_width(rope)`` lanes (640 at DeepSeek-V3.2-Exp's
sizes), ``pool_v`` the indexer's key ``k_I`` after LayerNorm and rotary
(``index_head_dim`` lanes): 1,280 + 256 B a (token, layer) in bf16 for a line
of 1,408 B. Latent and rotary key share a leaf so that a line is ONE row to
whatever reads it: a query's score against a line is one dot product over the
leaf's lanes, a tile of lines is one gather of blocks, and a version that
gathers single chosen lines pays one gathered row a line, not two (a row costs
12-16 ns on a v5e whatever its width: PERF.md, PR 59). (The released checkpoint keeps ``k_I`` in FP8 after a Hadamard
rotation of query and key, which is orthogonal and leaves every ``q . k`` as
it is: left out with the quantisation it serves.)

A tick works row by row (``_attend_rows``, over ``sparse_rows.walk_rows``, the
walk this mixer shares with the sparse grouped-query one): the rows that bring ONE token
(decode rows) are taken ``SINGLE_ROWS`` at a time, the rows that bring a chunk
are then walked in order, both rolled loops. Either way a row (1) gathers its index keys block by
block through its table and scores its queries key tile by key tile up to its
visible length; (2) finds each query's choice as a THRESHOLD: the
``index_topk``-th largest visible score by bisection on the float's bits (33
passes of compare-and-count, no sort) and, among scores equal to it, the
lowest positions that fill the count (``threshold_choice``: the same set as
``choose_lines``' ``top_k``, which stays the uncached form's and the tests'
reference of it; the fill runs only in a call where a query whose result the
walk keeps has more visible ties than room, and the walk counts those calls);
(3) STREAMS its latent lines tile by tile, as dense latent attention would,
and folds each tile into a float32 online softmax under the
mask of what each query chose. This is exact and costs the DENSE attention's
FLOPs (every visible line is multiplied, most are masked): on a v5e the
alternative that multiplies only the chosen lines, a gather of ``index_topk``
single lines a query, is bound by 12-16 ns a gathered row whatever its width
(33 us a query a layer against ~3 us of arithmetic: PERF.md, PR 59), and
loses to the stream below ~12k visible lines. The metrics count the WORK under
selection, so this version reads low on them. ONE form serves chunk rows and
decode rows; the expanded form would up-project every line for every query
with nothing shared.

With ``output_gate`` (the parent's) the head-wise gate is applied after
``sparse_attend``, before the output projection, under ``gate``.

Scopes (inside the layer's ``attn``): ``indexer`` holds everything the
indexer adds (its three projections, LayerNorm, rotary, the scatter of its
key, scores and choice), ``index_select`` inside it the scores and the
choice, ``sparse_attend`` the gather of the chosen lines and the attention
over them.

Not built, refused by name (config validation, ``serve/kvcache.py``,
``serve/engine.py``): int8 lines, model-parallel layers,
training, the prefix cache (hits and copy-on-write over the third leaf have
not been held to the reference).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ..obs import count_kernel_build
from .attention import PagedKVCacheView, paged_scatter_kv
from .base_layer import ForwardContext
from .latent_attention import LatentSelfAttention
from .linear import ColumnParallelLinear
from .masked_latent_attention import (
    KERNEL_NAME, masked_latent_attention,
)
from .norm import NormType, get_norm
from .paged_attention import paged_kernel_interpret
from .seq_packing import segment_ids_to_mask
# the scores, the choice and the walk over a tick's rows are the two sparse
# mixers' (nn/sparse_attention.py is the other); named here as they were
from .sparse_rows import (  # noqa: F401
    INDEX_TILE, SINGLE_ROWS, THRESHOLD_PASSES, _windows, choose_lines,
    chosen_mask, index_scores, index_tile_tokens, ordered_bits, row_addresses,
    threshold_choice, tile_of, walk_rows,
)


class SparseLatentSelfAttention(LatentSelfAttention):
    def __init__(self, *, index_n_heads: int, index_head_dim: int,
                 index_topk: int, **latent):
        super().__init__(**latent)
        assert index_head_dim >= self.rope, (
            "the indexer's rotary lanes are the first qk_rope_head_dim of "
            "index_head_dim")
        self.index_heads, self.index_dim = index_n_heads, index_head_dim
        self.index_topk = index_topk
        self.index_scale = index_n_heads ** -0.5 * index_head_dim ** -0.5
        common = dict(bias=False, dtype=self.dtype,
                      init_method=latent.get("init_method",
                                             self.q_a_proj.init_method))
        self.index_q_proj = ColumnParallelLinear(
            self.q_lora_rank, index_n_heads * index_head_dim, **common)
        self.index_k_proj = ColumnParallelLinear(
            self.hidden_size, index_head_dim, **common)
        self.index_w_proj = ColumnParallelLinear(
            self.hidden_size, index_n_heads, **common)
        # weight and bias, float32 statistics (nn/norm.py)
        self.index_k_norm = get_norm(
            NormType.LAYERNORM, index_head_dim, latent.get("layernorm_config"),
            self.dtype)

    PARTS = LatentSelfAttention.PARTS + (
        "index_q_proj", "index_k_proj", "index_k_norm", "index_w_proj")

    # --------------------------------------------------------------- indexer
    def _indexer(self, params: dict, x: jax.Array, c_q: jax.Array,
                 ctx: ForwardContext, position_ids):
        """``(q_I (b, s, j, d), k_I (b, s, d), w (b, s, j) float32)``, rotary
        applied to the first ``rope`` lanes of queries and key."""
        b, s, _ = x.shape
        q_i = self.index_q_proj(params["index_q_proj"], c_q, ctx).reshape(
            b, s, self.index_heads, self.index_dim)
        k_i = self.index_k_norm(
            params["index_k_norm"],
            self.index_k_proj(params["index_k_proj"], x, ctx), ctx)
        q_i, k_i = self.rotary_embedding(
            q_i, k_i[:, :, None, :], position_ids, position_ids)
        w = self.index_w_proj(params["index_w_proj"], x, ctx).astype(
            jnp.float32) * self.index_scale
        return q_i, k_i[:, :, 0, :], w

    # --------------------------------------------------------------- forward
    def __call__(
        self,
        params: dict,
        x: jax.Array,  # (b, s, hidden)
        ctx: ForwardContext,
        segment_ids: Optional[jax.Array] = None,
        position_ids: Optional[jax.Array] = None,
        kv_cache=None,
        cache_offset=None,
        return_kv: bool = False,
    ):
        b, s, _ = x.shape
        q_nope, q_rope, c_kv, k_r, c_q = self._latents(
            params, x, ctx, position_ids)
        with jax.named_scope("indexer"):
            q_i, k_i, w = self._indexer(params, x, c_q, ctx, position_ids)
        if isinstance(kv_cache, PagedKVCacheView):
            out, new_view, tie_breaks = self._paged_sparse(
                params, q_nope, q_rope, c_kv, k_r, q_i, k_i, w, kv_cache, ctx)
            return (self._project_out(params, out, x, ctx), new_view,
                    tie_breaks)
        self._refuse_dense_cache(kv_cache)
        # --- expanded heads under the mask of the chosen lines
        if segment_ids is None:
            segment_ids = jnp.zeros((b, s), dtype=jnp.int32)
        forbidden = segment_ids_to_mask(segment_ids, None, causal=True,
                                        positions_q=None, positions_k=None)
        with jax.named_scope("indexer"), jax.named_scope("index_select"):
            visible = ~forbidden[:, 0]                          # (b, s, s)
            chosen = chosen_mask(
                index_scores(q_i, k_i, w), visible, self.index_topk)
        y = self._expanded(params, x, q_nope, q_rope, c_kv, k_r,
                           ~chosen[:, None], ctx)
        if return_kv:
            return y, self._sparse_line(c_kv, k_r, k_i)
        return y

    def _sparse_line(self, c_kv, k_r, k_i):
        """The two leaves of the line a token leaves behind: the latent with
        the rotary key (in its lane row) after it, and the index key."""
        return self._whole_line(c_kv, k_r), k_i

    # ----------------------------------------------------------------- paged
    def _paged_sparse(self, params, q_nope, q_rope, c_kv, k_r, q_i, k_i, w,
                      view: PagedKVCacheView, ctx: ForwardContext):
        """Write the batch's lines to the rows' blocks (``paged_scatter_kv``,
        the ONE pool writer), then attend, row by row, over what each query
        chose, in the absorbed form. Returns ``((b, s, n * v), the updated
        view, the walk's calls that filled ties by position: int32)``.

        ``ctx.paged_kernel``: ``'pallas'`` is what serves (``_attend_rows``:
        the rows' tiles streamed under each query's threshold, a chunk row's
        through ``nn/masked_latent_attention.py``); ``'xla'`` gathers each token's WHOLE window,
        chooses by ``top_k`` and masks: the tests' reference of it."""
        if view.quantized:
            raise ValueError(
                "a latent attention layer with an int8 pool: a latent line "
                "has no head axis for the per-head scales and its rounding "
                "is not measured; use kv_dtype='native'")
        b, s = q_nope.shape[:2]
        n = self.num_heads
        tokens = b * s
        ctx_len, new_len, row, offset, real, flat, starts, width = row_addresses(
            view, (b, s))
        line, key = self._sparse_line(c_kv, k_r, k_i)
        with jax.named_scope("indexer"):   # the scatter writes both leaves
            new_view = paged_scatter_kv(
                view, flat, line.reshape(tokens, -1), key.reshape(tokens, -1))
        q_line, w_uv = self._query_line(params, q_nope, q_rope)
        q_i = q_i.reshape(tokens, self.index_heads, self.index_dim)
        w = w.reshape(tokens, self.index_heads)
        if ctx.paged_kernel == "pallas":
            interpret = paged_kernel_interpret()
            # the stack's paged attention: counted under the name the paged
            # kernel's builds are, so that a run asserts it was built
            count_kernel_build("paged_attention", interpret)
            count_kernel_build(KERNEL_NAME, interpret)
            out, tie_breaks = self._attend_rows(
                q_i, w, q_line, new_view, ctx_len, new_len, starts, width,
                interpret)
        else:
            assert ctx.paged_kernel == "xla", (
                f"unknown paged_kernel {ctx.paged_kernel!r} (expected "
                "'pallas' or 'xla')")
            out = self._attend_gathered_windows(
                q_i, w, q_line, new_view, row.reshape(-1), offset.reshape(-1),
                ctx_len, ctx_len + new_len)
            # (the row walk leaves zeros where no row owns a token)
            out = jnp.where(real.reshape(tokens, 1, 1), out, 0)
            tie_breaks = jnp.int32(0)   # top_k's order breaks them
        out = jnp.einsum("tnc,cnv->tnv", out, w_uv)
        return out.reshape(b, s, n * self.v_dim), new_view, tie_breaks

    def _attend_rows(self, q_i, w, q_line, view, ctx_len, new_len, starts,
                     width: int, interpret: bool):
        """The absorbed attention of every token over the lines it chose:
        ``(tokens, n, kv_lora_rank)``; what no row owns gives zeros; and the
        walk's count of tie breaks. The walk over the rows, the scores and the
        choice are ``sparse_rows.walk_rows``' (shared with the sparse
        grouped-query mixer); what is this line's: the
        one-token rows' latent tiles folded into an online softmax in plain
        XLA, a chunk row's window through ``masked_latent_attention``."""
        pool_l = view.pool_k
        tokens, n, _ = q_line.shape
        block_size = pool_l.shape[1]
        tile = index_tile_tokens(block_size, view.block_table.shape[1])
        tile_blocks = tile // block_size

        def stream(tables, seen, q_line, chosen):
            """The rows' latent tiles folded into an online softmax under
            ``chosen``, in plain XLA (the batch of one-token rows: a tile of
            scores there is ``heads`` rows a row): q_line (r, 1, n, line) ->
            (r, n, kv_lora_rank)."""
            r, p = q_line.shape[:2]

            def fold(t, carry):
                top, total, acc = carry
                lines = tile_of(pool_l, tables, t, tile_blocks)
                s = jnp.einsum("rpnc,rkc->rpnk", q_line, lines,
                               preferred_element_type=jnp.float32)
                mask = jax.lax.dynamic_slice_in_dim(chosen, t * tile, tile, 2)
                s = jnp.where(mask[:, :, None, :],
                              s * self.scaling_factor, -jnp.inf)
                new_top = jnp.maximum(top, s.max(axis=-1))
                safe = jnp.where(new_top == -jnp.inf, 0.0, new_top)
                e = jnp.exp(s - safe[..., None])
                alpha = jnp.exp(top - safe)
                acc = alpha[..., None] * acc + jnp.einsum(
                    "rpnk,rkc->rpnc", e.astype(lines.dtype),
                    lines[..., :self.kv_lora_rank],
                    preferred_element_type=jnp.float32)
                return new_top, alpha * total + e.sum(axis=-1), acc

            _, total, acc = jax.lax.fori_loop(
                0, -(-jnp.max(seen) // tile), fold, (
                    jnp.full((r, p, n), -jnp.inf, jnp.float32),
                    jnp.zeros((r, p, n), jnp.float32),
                    jnp.zeros((r, p, n, self.kv_lora_rank), jnp.float32)))
            return (acc / jnp.where(total == 0.0, 1.0, total)[..., None]
                    ).astype(q_line.dtype)[:, 0]

        def whole_chunk(table, seen, q_line, chosen, tiles: int):
            # the row's window of lines, whole blocks through its table, for
            # the kernel's plain tiles
            lines = pool_l[table[:tiles * tile_blocks]]
            return masked_latent_attention(
                q_line, lines.reshape(tiles * tile, -1), chosen, seen,
                lat=self.kv_lora_rank, sm_scale=float(self.scaling_factor),
                interpret=interpret)

        return walk_rows(
            index_pool=view.pool_v, block_table=view.block_table,
            ctx_len=ctx_len, new_len=new_len, starts=starts, width=width,
            topk=self.index_topk, q_i=q_i, w=w, queries=q_line,
            out=jnp.zeros((tokens, n, self.kv_lora_rank), q_line.dtype),
            choice=self._chosen, attend_single=stream,
            attend_chunk=whole_chunk)

    def _chosen(self, scores, visible, k: int):
        """What each query attends over, as a mask over its row's slots."""
        return threshold_choice(scores, visible, k)

    def _attend_gathered_windows(self, q_i, w, q_line, view, row, offset,
                                 ctx_len, valid_len):
        """The same numbers with nothing streamed and no threshold: each
        token's WHOLE window of index keys and of lines is gathered, the
        choice is ``choose_lines``' ``top_k`` and the rest is masked.
        Independent of ``_attend_rows``; the tests' reference of it."""
        tokens = q_line.shape[0]
        window = view.block_table.shape[1] * view.pool_k.shape[1]
        keys = view.pool_v[view.block_table].reshape(
            -1, window, self.index_dim)[row]                   # (t, w, d)
        lines = view.pool_k[view.block_table].reshape(
            -1, window, q_line.shape[-1])[row]                 # (t, w, line)
        slots = jnp.arange(window, dtype=jnp.int32)[None, :]
        visible = (slots < valid_len[row][:, None]) & (
            slots <= (ctx_len[row] + offset)[:, None])
        scores = index_scores(q_i[:, None], keys, w[:, None])[:, 0]
        idx, held = choose_lines(scores, visible, self.index_topk)
        chosen = jnp.zeros((tokens, window), bool).at[
            jnp.arange(tokens)[:, None], idx].max(held)
        s = jnp.einsum("tnc,twc->tnw", q_line, lines,
                       preferred_element_type=jnp.float32)
        s = jnp.where(chosen[:, None, :], s * self.scaling_factor, -jnp.inf)
        top = jnp.max(s, axis=-1, keepdims=True)
        e = jnp.exp(s - jnp.where(top == -jnp.inf, 0.0, top))
        e = e / jnp.maximum(e.sum(axis=-1, keepdims=True), 1e-30)
        return jnp.einsum("tnw,twc->tnc", e.astype(lines.dtype),
                          lines[..., :self.kv_lora_rank],
                          preferred_element_type=jnp.float32
                          ).astype(q_line.dtype)
