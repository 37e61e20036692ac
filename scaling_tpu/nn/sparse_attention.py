"""Grouped-query attention with a LEARNED SPARSE choice of lines (Keye-VL-2.0's
decoder: a DeepSeek-Sparse-Attention indexer over a GQA cache).

The block is ``nn/attention.py``'s ``ParallelSelfAttention`` (separate Q / K /
V, per-head key/query norm, rotary on the whole head), every projection of it,
plus an indexer. With ``x_t`` the block's normed input:

    q_I[t, j] = (x_t W_IQ)[j]             j < index_n_heads, index_head_dim wide
                                          (from the hidden state: there is no
                                          query latent)
    k_I[s]    = LayerNorm(x_s W_IK)       ONE key a token; weight AND bias,
                                          float32 statistics
    rotary on ALL ``index_head_dim`` lanes of q_I[t, j] and of k_I[s], at the
    block's base (lane i paired with lane i + index_head_dim / 2)
    w[t, j]   = (x_t W_Iw)[j] * index_n_heads ** -0.5 * index_head_dim ** -0.5
    I[t, s]   = sum_j w[t, j] relu(q_I[t, j] . k_I[s])         for s <= t
    S_t       = the min(index_topk, t + 1) lines s <= t of largest I[t, s]
    o[t, i]   = softmax_{s in S_t}(scale q[t, i] . k[s, i // group]) v[s, i // group]

ONE choice a token, shared by every query head and every KV head. The scores,
the exact choice (``choose_lines`` / ``threshold_choice``) and the walk over a
tick's rows are ``nn/sparse_rows.py``'s, shared with the sparse latent mixer
(``nn/sparse_latent_attention.py``); what differs is the line under the choice
and what attends over it.

**Uncached** (``prefill_forward``, the pool's probe, ``generate(
use_cache=False)``): the parent's unfused attention under a mask that forbids
what a query did not choose.

**Over the paged pool** (serving). A token's line has THREE leaves
(``PagedKVCacheView``): ``pool_k`` and ``pool_v`` as every grouped-query
layer's, ``(n_kv, h)`` each, and ``pool_i``, the index key ``k_I`` after
LayerNorm and rotary, ``(index_head_dim,)`` with no head axis: at Keye's sizes
1,024 + 1,024 + 128 B a (token, layer) in bf16. All three are written by
``paged_scatter_kv`` through the same block table. A tick's rows are walked by
``sparse_rows.walk_rows``; under each query's mask

- a CHUNK row's window of K and V is gathered through its table (whole
  blocks) and STREAMED through ``nn/masked_gqa_attention.py``: every visible
  line is multiplied, what a query did not choose is dropped from its softmax
  (exact, at the dense attention's FLOPs: a gather of 2 x ``index_topk``
  single lines a query is bound by ~17 ns a gathered row of 1 KB on a v5e,
  22-24 ms a 320-query chunk against 3.0-4.5 ms of stream at 16k-48k visible
  lines: ``benchmarks/sparse_gqa_forms.py``; PERF.md, PR 61);
- the rows of ONE token attend through the paged kernel
  (``nn/paged_attention.py``) with the choice as its mask operand,
  ``SINGLE_ROWS`` rows a pass: each row's own blocks are fetched by DMA
  through its table up to its own length, a place of the pass that holds no
  row runs no tile, and the kernel is built once a layer (the walk pads every
  window's choice to the whole window). At Keye's 16 KiB blocks the kernel
  streams ~400 GB/s against ~210 for a gather of tiles through XLA, which it
  replaced (PERF.md, PR 64; gathering the chosen lines needs the choice as
  indices, which costs more than it saves: PR 61).

Scopes (inside the layer's ``attn``): ``indexer`` holds everything the indexer
adds (its three projections, LayerNorm, rotary, scores and choice),
``index_select`` inside it the scores and the choice, ``sparse_attend`` the
gather of a chunk row's window and the attention under the mask (the
one-token rows' ``paged_attention`` calls lie here). The scatter of the
line's three leaves is one call and lies in neither.

Not built, refused by name (here, config validation, ``serve/kvcache.py``,
``serve/engine.py``): int8 lines, model-parallel layers,
training, the prefix cache, a dense ``generate()`` cache, local-window heads,
LoRA, score manipulation.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ..obs import count_kernel_build
from .attention import (
    PagedKVCacheView,
    ParallelSelfAttention,
    multi_head_attention,
    paged_scatter_kv,
    repeat_kv,
)
from .base_layer import ForwardContext
from .linear import ColumnParallelLinear
from .masked_gqa_attention import KERNEL_NAME, masked_gqa_attention
from .norm import NormType, get_norm
from .paged_attention import (
    kv_block_layout, paged_decode_attention, paged_kernel_interpret,
)
from .param import tree_prefix
from .rotary import RotaryConfig, RotaryEmbedding
from .seq_packing import segment_ids_to_mask
from .sparse_rows import (
    chosen_mask, index_scores, index_tile_tokens, row_addresses,
    threshold_choice, walk_rows,
)

INDEX_PARTS = ("index_q_proj", "index_k_proj", "index_k_norm", "index_w_proj")


class SparseSelfAttention(ParallelSelfAttention):
    def __init__(self, *, index_n_heads: int, index_head_dim: int,
                 index_topk: int, rotary_config: RotaryConfig, **attention):
        super().__init__(rotary_config=rotary_config, **attention)
        assert not self.qkv_in_one and not self.lora_modules, (
            "a sparse attention layer has separate Q / K / V and no LoRA")
        assert self.num_local_attention_heads == 0 and self.causal, (
            "a sparse attention layer is causal, without local-window heads")
        assert index_head_dim % 2 == 0, "rotary turns pairs of lanes"
        self.index_heads, self.index_dim = index_n_heads, index_head_dim
        self.index_topk = index_topk
        self.index_scale = index_n_heads ** -0.5 * index_head_dim ** -0.5
        common = dict(bias=False, dtype=self.dtype,
                      init_method=self.query.init_method)
        self.index_q_proj = ColumnParallelLinear(
            self.hidden_size, index_n_heads * index_head_dim, **common)
        self.index_k_proj = ColumnParallelLinear(
            self.hidden_size, index_head_dim, **common)
        self.index_w_proj = ColumnParallelLinear(
            self.hidden_size, index_n_heads, **common)
        # weight and bias, float32 statistics (nn/norm.py)
        self.index_k_norm = get_norm(
            NormType.LAYERNORM, index_head_dim,
            attention.get("layernorm_config"), self.dtype)
        # the indexer's whole head turns, at the block's base
        self.index_rotary = RotaryEmbedding(RotaryConfig(
            dimensions=index_head_dim, base=rotary_config.base,
            max_seq_length=rotary_config.max_seq_length))

    def init(self, key: jax.Array) -> dict:
        params = super().init(key)
        keys = jax.random.split(jax.random.fold_in(key, 1), len(INDEX_PARTS))
        for name, k in zip(INDEX_PARTS, keys):
            params[name] = getattr(self, name).init(k)
        return params

    def param_metas(self) -> dict:
        metas = super().param_metas()
        for name in INDEX_PARTS:
            metas[name] = tree_prefix(getattr(self, name).param_metas(), name)
        return metas

    # --------------------------------------------------------------- indexer
    def _indexer(self, params: dict, x: jax.Array, ctx: ForwardContext,
                 position_ids):
        """``(q_I (b, s, j, d), k_I (b, s, d), w (b, s, j) float32)``, rotary
        applied to every lane of queries and key."""
        b, s, _ = x.shape
        q_i = self.index_q_proj(params["index_q_proj"], x, ctx).reshape(
            b, s, self.index_heads, self.index_dim)
        k_i = self.index_k_norm(
            params["index_k_norm"],
            self.index_k_proj(params["index_k_proj"], x, ctx), ctx)
        q_i, k_i = self.index_rotary(
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
        q, k, v = self._heads(params, x, ctx, position_ids)
        with jax.named_scope("indexer"):
            q_i, k_i, w = self._indexer(params, x, ctx, position_ids)
        if isinstance(kv_cache, PagedKVCacheView):
            out, new_view, tie_breaks = self._paged_sparse(
                q, k, v, q_i, k_i, w, kv_cache, ctx)
            return *self._project_out(params, out, ctx, b, s, new_view), tie_breaks
        if kv_cache is not None:
            raise ValueError(
                "a sparse attention layer takes a PagedKVCacheView (the "
                "serving engine's pool), not a dense cache: cached generate() "
                "is not built for it; use use_cache=False or ServeEngine")
        # --- the unfused attention under the mask of the chosen lines
        if segment_ids is None:
            segment_ids = jnp.zeros((b, s), dtype=jnp.int32)
        forbidden = segment_ids_to_mask(segment_ids, None, causal=True,
                                        positions_q=None, positions_k=None)
        with jax.named_scope("indexer"), jax.named_scope("index_select"):
            # top_k's choice: the reference of the served path's threshold
            chosen = chosen_mask(index_scores(q_i, k_i, w), ~forbidden[:, 0],
                                 self.index_topk)                  # (b, s, s)
        out = multi_head_attention(
            q, repeat_kv(k, self.num_repeat_kv), repeat_kv(v, self.num_repeat_kv),
            ~chosen[:, None], self.scaling_factor, self.masked_softmax)
        return self._project_out(
            params, out, ctx, b, s, (k, v, k_i) if return_kv else None)

    def _chosen(self, scores, visible, k: int):
        """What each query of a served row attends over, as a mask over its
        row's slots."""
        return threshold_choice(scores, visible, k)

    # ----------------------------------------------------------------- paged
    def _paged_sparse(self, q, k, v, q_i, k_i, w, view: PagedKVCacheView,
                      ctx: ForwardContext):
        """Write the batch's lines, three leaves each, to the rows' blocks
        (``paged_scatter_kv``, the ONE pool writer), then attend, row by row,
        over what each query chose. Returns ``((b, s, n, h), the updated
        view, the walk's calls that filled ties by position: int32)``.

        ``ctx.paged_kernel``: ``'pallas'`` is what serves
        (``sparse_rows.walk_rows``: a chunk row's window through
        ``nn/masked_gqa_attention.py`` under each query's threshold, the
        one-token rows' own blocks through ``nn/paged_attention.py`` under
        theirs); ``'xla'`` gathers each
        token's WHOLE window, chooses by ``top_k`` and masks: the tests'
        reference of it."""
        if view.quantized or view.pool_i is None:
            raise ValueError(
                "a sparse attention layer takes a native pool whose lines "
                "have an index key (PagedKVCacheView.pool_i): the rounding of "
                "index keys in an int8 pool is not measured; use "
                "kv_dtype='native'")
        b, s, n, h = q.shape
        tokens = b * s
        ctx_len, new_len, row, offset, real, flat, starts, width = row_addresses(
            view, (b, s))
        new_view = paged_scatter_kv(
            view, flat, k.reshape(tokens, *k.shape[2:]),
            v.reshape(tokens, *v.shape[2:]), k_i.reshape(tokens, -1))
        q = q.reshape(tokens, n, h)
        q_i = q_i.reshape(tokens, self.index_heads, self.index_dim)
        w = w.reshape(tokens, self.index_heads)
        if ctx.paged_kernel == "pallas":
            interpret = paged_kernel_interpret()
            count_kernel_build(KERNEL_NAME, interpret)
            out, tie_breaks = self._attend_rows(
                q_i, w, q, new_view, ctx_len, new_len, starts, width, interpret)
        else:
            assert ctx.paged_kernel == "xla", (
                f"unknown paged_kernel {ctx.paged_kernel!r} (expected "
                "'pallas' or 'xla')")
            out = self._attend_gathered_windows(
                q_i, w, q, new_view, row.reshape(-1), offset.reshape(-1),
                ctx_len, ctx_len + new_len)
            # (the row walk leaves zeros where no row owns a token)
            out = jnp.where(real.reshape(tokens, 1, 1), out, 0)
            tie_breaks = jnp.int32(0)   # top_k's order breaks them
        return out.reshape(b, s, n, h), new_view, tie_breaks

    def _attend_rows(self, q_i, w, q, view, ctx_len, new_len, starts,
                     width: int, interpret: bool):
        """The attention of every token over the lines it chose: ``(tokens,
        n, h)``; what no row owns gives zeros; and the walk's count of tie
        breaks. The walk, the scores and the choice are
        ``sparse_rows.walk_rows``'; what is this line's: K and V in two leaves
        with a head axis, the GQA group folded beside the positions."""
        tokens, n, h = q.shape
        n_kv, group = self.num_kv_heads, self.num_repeat_kv
        layout = kv_block_layout(view.pool_k, n_kv * h)
        block_size = layout.block_size
        tile = index_tile_tokens(block_size, view.block_table.shape[1])
        tile_blocks = tile // block_size

        def own_blocks(tables, seen, q, chosen):
            """The rows of ONE token through the paged kernel under their
            masks: each row's own blocks by DMA through its table, up to its
            own length, a place that sees nothing at no cost: q (r, 1, n, h)
            -> (r, n, h)."""
            return paged_decode_attention(
                q, view.pool_k, view.pool_v, tables, seen, seen - 1,
                sm_scale=float(self.scaling_factor), num_repeat_kv=group,
                chosen=chosen[:, 0], interpret=interpret)[:, 0]

        def whole_chunk(table, seen, q, chosen, tiles: int):
            # the row's window of K and of V, whole blocks through its table,
            # for the kernel's plain tiles
            blocks = table[:tiles * tile_blocks]
            return masked_gqa_attention(
                q, layout.lines(view.pool_k[blocks]),
                layout.lines(view.pool_v[blocks]), chosen,
                seen, sm_scale=float(self.scaling_factor), interpret=interpret)

        return walk_rows(
            index_pool=view.pool_i, block_table=view.block_table,
            ctx_len=ctx_len, new_len=new_len, starts=starts, width=width,
            topk=self.index_topk, q_i=q_i, w=w, queries=q,
            out=jnp.zeros((tokens, n, h), q.dtype), choice=self._chosen,
            attend_single=own_blocks, attend_chunk=whole_chunk)

    def _attend_gathered_windows(self, q_i, w, q, view, row, offset,
                                 ctx_len, valid_len):
        """The same numbers with nothing streamed and no threshold: each
        token's WHOLE window of index keys, K and V is gathered, the choice is
        ``choose_lines``' ``top_k`` and the rest is masked. Independent of
        ``_attend_rows``; the tests' reference of it."""
        tokens, n, h = q.shape
        n_kv, group = self.num_kv_heads, self.num_repeat_kv
        layout = kv_block_layout(view.pool_k, n_kv * h)
        window = view.block_table.shape[1] * layout.block_size

        def windows(pool, *tail):   # of a leaf without a head axis
            return pool[view.block_table].reshape(-1, window, *tail)[row]

        slots = jnp.arange(window, dtype=jnp.int32)[None, :]
        visible = (slots < valid_len[row][:, None]) & (
            slots <= (ctx_len[row] + offset)[:, None])
        scores = index_scores(
            q_i[:, None], windows(view.pool_i, self.index_dim), w[:, None])[:, 0]
        chosen = chosen_mask(scores, visible, self.index_topk)
        keys = layout.lines(view.pool_k[view.block_table])[row]
        values = layout.lines(view.pool_v[view.block_table])[row]
        s = jnp.einsum("tgjh,twgh->tgjw", q.reshape(tokens, n_kv, group, h), keys,
                       preferred_element_type=jnp.float32)
        s = jnp.where(chosen[:, None, None, :], s * self.scaling_factor, -jnp.inf)
        top = jnp.max(s, axis=-1, keepdims=True)
        e = jnp.exp(s - jnp.where(top == -jnp.inf, 0.0, top))
        e = e / jnp.maximum(e.sum(axis=-1, keepdims=True), 1e-30)
        return jnp.einsum("tgjw,twgh->tgjh", e.astype(values.dtype), values,
                          preferred_element_type=jnp.float32
                          ).astype(q.dtype).reshape(tokens, n, h)
