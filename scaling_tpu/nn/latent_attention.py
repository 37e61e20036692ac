"""Multi-head LATENT attention (MLA: DeepSeek-V2 / V3, Kimi-K2).

Queries and keys/values are projected DOWN to low-rank latents and up again a
head, and only the KV latent is kept (64 heads, hidden 7168 at Kimi-K2's
sizes):

    c_q = RMSNorm(x W_DQ)                     (q_lora_rank)
    q_h = c_q W_UQ,h = [q_nope_h (nope), q_rope_h (rope)]
    [c_kv (kv_lora_rank), k_r (rope)] = x W_DKV
    c_kv <- RMSNorm(c_kv);  k_r <- RoPE(k_r): ONE rotary key, shared by all
    heads;  q_rope_h <- RoPE(q_rope_h)

**Expanded** (no cache: ``prefill_forward``, the pool's probe, ``generate(
use_cache=False)``):

    [k_nope_h (nope), v_h (v)] = c_kv W_UKV,h;   k_h = [k_nope_h, k_r]
    o_h = softmax(scale q_h k_h^T + causal) v_h;  y = concat_h(o_h) W_O

**Absorbed** (serving, over the paged pool; the same numbers): with ``W_UKV,h``
split into ``W_UK,h`` (kv_lora_rank x nope) and ``W_UV,h`` (kv_lora_rank x v),

    q'_h = q_nope_h W_UK,h^T                  (kv_lora_rank)
    scores = scale (q'_h . c_kv + q_rope_h . k_r)
    o'_h = softmax(scores) c_kv               (kv_lora_rank)
    o_h = o'_h W_UV,h

so every head attends over the cached line ``[c_kv, k_r]`` as over ONE shared
KV head whose value is the line's own first ``kv_lora_rank`` lanes
(``nn/latent_paged_attention.py``). ``W_UK`` and ``W_UV`` are applied around
the kernel as two views of the ONE ``kv_b_proj`` leaf the checkpoint has: no
second copy of it lives in memory. The expanded form pays ``2 kv_lora_rank
heads (nope + v)`` FLOP a cached line a call, the absorbed ``2 heads (2
kv_lora_rank + rope)`` a (query, line) pair against the expanded ``2 heads (nope
+ rope + v)``: at Kimi-K2's sizes they break even at rows of 171 queries, and
the engine's rows bring at most ``prefill_chunk`` (32 by default, 160 in the
benchmark's Kimi-K2 configuration), so ONE form serves decode rows and chunk
rows.

**What a token leaves behind** (``STATE_VIEW``: the paged rule of
``serve/kvcache.py``; which leaf holds what is said HERE): ``pool_k`` holds
``c_kv`` after its norm, ``(num_blocks, block_size, kv_lora_rank)``;
``pool_v`` holds ``k_r`` after rotary, ``(num_blocks, block_size,
rope_line_width(rope))``: the key first, zeros after it to whole rows of 128
lanes (``latent_paged_attention.rope_line_width``: on the chip a 64-wide minor
dimension is tiled to 128 lanes whatever its shape says). No head axis. 512 +
64 values in bf16 are 1,152 B a (token, layer) against 40,960 B for Kimi-K2's
expanded heads; the pool holds 1,280 B of them.

YaRN (``nn/rotary.py``): the rotary tables take the static YaRN frequencies
and the softmax scale its factor, ``(nope + rope) ** -0.5 *
m(mscale_all_dim) ** 2``.

The sizes are the constructor's, so a stack may hold this mixer at two sets of
them (``nn/window_latent_attention.py`` is this class under a window, with a
ring of lines a slot). Two options, both off by default: ``output_gate``, the
head-wise gate of arXiv:2505.06708 (``g = sigmoid(x W_g)``, one value a head
from the layer's normed input, on ``o_h`` before ``W_O``; scope ``gate``), and
``lora_rescale``: ``c_q`` and ``c_kv`` leave their norms times ``(hidden /
rank) ** 0.5`` (the rotary key is not scaled).

Not built, refused by name where it is asked for (config validation,
``serve/kvcache.py``): int8 latent lines, model-parallel latent layers
(a latent line has no head axis to shard: a deployment replicates the
attention), training.
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp

from .attention import (
    PagedKVCacheView,
    multi_head_attention,
    paged_flat_slots,
    paged_scatter_kv,
)
from .base_layer import BaseLayer, ForwardContext
from .latent_paged_attention import latent_paged_attention, rope_line_width
from .linear import ColumnParallelLinear, RowParallelLinear, xavier_normal_init
from .masked_softmax import MaskedSoftmax, MaskedSoftmaxConfig
from .norm import LayerNormConfig, NormType, get_norm
from .param import tree_prefix
from .rotary import RotaryConfig, RotaryEmbedding, yarn_softmax_scale
from .seq_packing import segment_ids_to_mask


class LatentSelfAttention(BaseLayer):
    # the view of the serving state a layer with this mixer is handed: the
    # paged rule, its two leaves as the module docstring says
    STATE_VIEW = PagedKVCacheView

    def __init__(
        self,
        hidden_size: int,
        num_attention_heads: int,
        q_lora_rank: int,
        kv_lora_rank: int,
        qk_nope_head_dim: int,
        qk_rope_head_dim: int,
        v_head_dim: int,
        rotary_config: RotaryConfig,
        layernorm_config: Optional[LayerNormConfig] = None,
        masked_softmax_config: Optional[MaskedSoftmaxConfig] = None,
        dtype=jnp.float32,
        init_method: Callable = xavier_normal_init,
        output_gate: bool = False,
        lora_rescale: bool = False,
    ):
        assert rotary_config.dimensions == qk_rope_head_dim, (
            "the rotary slice of a latent head is qk_rope_head_dim wide")
        self.hidden_size = hidden_size
        self.num_heads = num_attention_heads
        self.q_lora_rank = q_lora_rank
        self.kv_lora_rank = kv_lora_rank
        self.nope, self.rope, self.v_dim = (
            qk_nope_head_dim, qk_rope_head_dim, v_head_dim)
        self.rope_line = rope_line_width(qk_rope_head_dim)
        self.dtype = dtype
        # YaRN's factor on the softmax scale (1 without rope scaling)
        self.scaling_factor = (
            (qk_nope_head_dim + qk_rope_head_dim) ** -0.5
            * yarn_softmax_scale(rotary_config.scaling))
        n = num_attention_heads
        common = dict(bias=False, dtype=dtype, init_method=init_method)
        self.q_a_proj = ColumnParallelLinear(hidden_size, q_lora_rank, **common)
        self.q_b_proj = ColumnParallelLinear(
            q_lora_rank, n * (self.nope + self.rope), **common)
        self.kv_a_proj = ColumnParallelLinear(
            hidden_size, kv_lora_rank + self.rope, **common)
        self.kv_b_proj = ColumnParallelLinear(
            kv_lora_rank, n * (self.nope + self.v_dim), **common)
        self.dense = RowParallelLinear(
            n * self.v_dim, hidden_size, parallel_input=True,
            parallel_output=True, **common)
        # RMSNorms with float32 statistics (nn/norm.py)
        self.q_a_norm = get_norm(NormType.RMS, q_lora_rank, layernorm_config, dtype)
        self.kv_a_norm = get_norm(NormType.RMS, kv_lora_rank, layernorm_config, dtype)
        self.rotary_embedding = RotaryEmbedding(rotary_config)
        self.masked_softmax = MaskedSoftmax(
            masked_softmax_config or MaskedSoftmaxConfig())
        # what the two latents leave their norms times (1: no rescale)
        self.q_scale = (hidden_size / q_lora_rank) ** 0.5 if lora_rescale else 1.0
        self.kv_scale = (hidden_size / kv_lora_rank) ** 0.5 if lora_rescale else 1.0
        # the leaves, in init's order: a gate is one more, the LAST (a mixer
        # without one splits its key as it always did)
        self.parts = self.PARTS
        if output_gate:
            self.gate = ColumnParallelLinear(
                hidden_size, n, parallel_output=True, **common)
            self.parts += ("gate",)

    PARTS = ("q_a_proj", "q_a_norm", "q_b_proj", "kv_a_proj", "kv_a_norm",
             "kv_b_proj", "dense")

    def init(self, key: jax.Array) -> dict:
        keys = jax.random.split(key, len(self.parts))
        return {name: getattr(self, name).init(k)
                for name, k in zip(self.parts, keys)}

    def param_metas(self) -> dict:
        return {name: tree_prefix(getattr(self, name).param_metas(), name)
                for name in self.parts}

    # --------------------------------------------------------------- forward
    def _latents(self, params: dict, x: jax.Array, ctx: ForwardContext,
                 position_ids):
        """``(q_nope (b, s, n, nope), q_rope (b, s, n, rope), c_kv (b, s,
        kv_lora_rank), k_r (b, s, rope), c_q (b, s, q_lora_rank))``, norms and
        rotary applied; ``c_q`` is the normed query latent the heads' queries
        were projected from (a sparse layer's indexer reads it too)."""
        b, s, _ = x.shape
        n = self.num_heads
        c_q = self._rescaled(self.q_a_norm(
            params["q_a_norm"], self.q_a_proj(params["q_a_proj"], x, ctx), ctx),
            self.q_scale)
        q = self.q_b_proj(params["q_b_proj"], c_q, ctx).reshape(
            b, s, n, self.nope + self.rope)
        q_nope, q_rope = q[..., :self.nope], q[..., self.nope:]
        kv = self.kv_a_proj(params["kv_a_proj"], x, ctx)
        c_kv = self._rescaled(self.kv_a_norm(
            params["kv_a_norm"], kv[..., :self.kv_lora_rank], ctx),
            self.kv_scale)
        k_r = kv[..., self.kv_lora_rank:][:, :, None, :]   # ONE key, no head
        q_rope, k_r = self.rotary_embedding(
            q_rope, k_r, position_ids, position_ids)
        return q_nope, q_rope, c_kv, k_r[:, :, 0, :], c_q

    @staticmethod
    def _rescaled(latent, scale: float):
        """A latent times ``lora_rescale``'s factor: a float32 product, the
        latent's dtype back."""
        if scale == 1.0:
            return latent
        return (latent.astype(jnp.float32) * scale).astype(latent.dtype)

    def _up_weights(self, params: dict, dtype):
        """``(W_UK (kv_lora_rank, n, nope), W_UV (kv_lora_rank, n, v))``: two
        views of the ONE ``kv_b_proj`` leaf, in its own order (no transpose of
        the weight a call)."""
        w = params["kv_b_proj"]["weight"].astype(dtype).reshape(
            self.kv_lora_rank, self.num_heads, self.nope + self.v_dim)
        return w[..., :self.nope], w[..., self.nope:]

    def _line(self, c_kv, k_r):
        """The two leaves of the line a token leaves behind."""
        pad = self.rope_line - self.rope
        return c_kv, jnp.pad(k_r, ((0, 0),) * (k_r.ndim - 1) + ((0, pad),))

    def _whole_line(self, c_kv, k_r):
        """The line as ONE leaf: the latent, the rotary key in its lane row
        after it."""
        return jnp.concatenate(self._line(c_kv, k_r), axis=-1)

    def _query_line(self, params, q_nope, q_rope):
        """A query against a whole line, absorbed: ``[q_nope W_UK^T, q_rope,
        zeros] . [c_kv, k_r, 0]``: ``((tokens, n, line lanes), W_UV)``."""
        b, s, n = q_nope.shape[:3]
        w_uk, w_uv = self._up_weights(params, q_nope.dtype)
        q_lat = jnp.einsum("bsnd,cnd->bsnc", q_nope, w_uk)
        q_line = jnp.concatenate([
            q_lat, q_rope,
            jnp.zeros((b, s, n, self.rope_line - self.rope), q_lat.dtype),
        ], axis=-1)
        return q_line.reshape(b * s, n, -1), w_uv

    def _project_out(self, params, out, x, ctx):
        """Heads -> hidden: ``out`` (b, s, n * v) through the output
        projection; with a gate, each head's output times its gate first
        (``x``: the layer's input, which the gate reads)."""
        if "gate" in self.parts:
            with jax.named_scope("gate"):
                g = jax.nn.sigmoid(
                    self.gate(params["gate"], x, ctx).astype(jnp.float32))
                heads = out.reshape(*out.shape[:2], self.num_heads, self.v_dim)
                out = (heads.astype(jnp.float32) * g[..., None]).astype(
                    out.dtype).reshape(out.shape)
        return self.dense(params["dense"], out, ctx)

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
        q_nope, q_rope, c_kv, k_r, _ = self._latents(
            params, x, ctx, position_ids)
        if isinstance(kv_cache, PagedKVCacheView):
            out, new_view = self._paged_attention(
                params, q_nope, q_rope, c_kv, k_r, kv_cache, ctx)
            return self._project_out(params, out, x, ctx), new_view
        self._refuse_dense_cache(kv_cache)
        if segment_ids is None:
            segment_ids = jnp.zeros((b, s), dtype=jnp.int32)
        mask = segment_ids_to_mask(segment_ids, None, causal=True,
                                   positions_q=None, positions_k=None)
        y = self._expanded(params, x, q_nope, q_rope, c_kv, k_r, mask, ctx)
        if return_kv:
            return y, self._line(c_kv, k_r)
        return y

    @staticmethod
    def _refuse_dense_cache(kv_cache):
        if kv_cache is not None:
            raise ValueError(
                "a latent attention layer takes a PagedKVCacheView (the "
                "serving engine's pool), not a dense cache: cached generate() "
                "is not built for it; use use_cache=False or ServeEngine")

    def _expanded(self, params, x, q_nope, q_rope, c_kv, k_r, forbidden, ctx):
        """The expanded form: every head's keys and values from the latent,
        the softmax under ``forbidden`` (b, 1, s, s), the gate (of the
        layer's input ``x``) and the output projection: ``(b, s, hidden)``."""
        b, s, n = *q_nope.shape[:2], self.num_heads
        kv = self.kv_b_proj(params["kv_b_proj"], c_kv, ctx).reshape(
            b, s, n, self.nope + self.v_dim)
        k = jnp.concatenate([
            kv[..., :self.nope],
            jnp.broadcast_to(k_r[:, :, None, :], (b, s, n, self.rope)),
        ], axis=-1)
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        out = multi_head_attention(
            q, k, kv[..., self.nope:], forbidden, self.scaling_factor,
            self.masked_softmax)
        return self._project_out(
            params, out.reshape(b, s, n * self.v_dim), x, ctx)

    def _paged_attention(self, params, q_nope, q_rope, c_kv, k_r,
                         view: PagedKVCacheView, ctx: ForwardContext):
        """Write the batch's lines to the rows' blocks (the paged path's slot
        addressing: ``paged_flat_slots``, the trash block for what is no
        token), then attend in the absorbed form, the queries token-major as
        the batch holds them. Returns ``((b, s, n * v), the updated view)``.

        ``ctx.paged_kernel``: ``'pallas'`` is what serves; ``'xla'`` gathers
        each row's window and is the tests' reference of the kernel."""
        if view.quantized:
            raise ValueError(
                "a latent attention layer with an int8 pool: a latent line "
                "has no head axis for the per-head scales and its rounding "
                "is not measured; use kv_dtype='native'")
        b, s = q_nope.shape[:2]
        n = self.num_heads
        block_size = view.pool_k.shape[1]
        rows, max_blocks = view.block_table.shape
        ctx_len = view.context_len.astype(jnp.int32)
        if view.new_len is None:
            new_len = jnp.full((rows,), s, jnp.int32)
        else:
            new_len = view.new_len.astype(jnp.int32)
        row, offset, real = view.token_rows((b, s))
        flat = paged_flat_slots(
            view.block_table, ctx_len[row] + offset, block_size, row)
        flat = jnp.where(real, flat, 0)
        line_c, line_r = self._line(c_kv, k_r)
        new_view = paged_scatter_kv(
            view, flat.reshape(-1),
            line_c.reshape(b * s, -1), line_r.reshape(b * s, -1))

        w_uk, w_uv = self._up_weights(params, q_nope.dtype)
        q_lat = jnp.einsum("bsnd,cnd->bsnc", q_nope, w_uk)
        tokens = b * s
        q_lat = q_lat.reshape(tokens, n, self.kv_lora_rank)
        q_rope = q_rope.reshape(tokens, n, self.rope)
        if view.token_map is None:      # row-major: row r's tokens at r * s
            starts = jnp.arange(rows, dtype=jnp.int32) * s
            width = s
        else:
            starts = view.token_map.row_tokens[:, 0]
            width = view.token_map.row_tokens.shape[1]
        valid_len = ctx_len + new_len
        if ctx.paged_kernel == "pallas":
            out = latent_paged_attention(
                q_lat, q_rope, new_view.pool_k, new_view.pool_v,
                view.block_table, valid_len, ctx_len, starts,
                width=width, sm_scale=self.scaling_factor)
        else:
            assert ctx.paged_kernel == "xla", (
                f"unknown paged_kernel {ctx.paged_kernel!r} (expected "
                "'pallas' or 'xla')")
            out = self._attend_gathered(
                q_lat, q_rope, new_view, row.reshape(-1), offset.reshape(-1),
                ctx_len, valid_len)
        # positions no row owns were never written (the kernel) or are a
        # masked row's (the gather form): zeros, not whatever the buffer held
        out = jnp.where(real.reshape(tokens, 1, 1), out, 0)
        out = jnp.einsum("tnc,cnv->tnv", out, w_uv)
        return out.reshape(b, s, n * self.v_dim), new_view

    def _attend_gathered(self, q_lat, q_rope, view, row, offset, ctx_len,
                         valid_len):
        """The absorbed form over each token's gathered window, float32
        softmax: independent of the kernel. ``(tokens, n, kv_lora_rank)``."""
        window = view.block_table.shape[1] * view.pool_k.shape[1]
        c = view.pool_k[view.block_table].reshape(
            -1, window, self.kv_lora_rank)[row]                 # (t, w, c)
        r = view.pool_v[view.block_table].reshape(
            -1, window, self.rope_line)[row][..., :self.rope]
        scores = (jnp.einsum("tnc,twc->tnw", q_lat, c,
                             preferred_element_type=jnp.float32)
                  + jnp.einsum("tnr,twr->tnw", q_rope, r,
                               preferred_element_type=jnp.float32))
        slots = jnp.arange(window, dtype=jnp.int32)[None, :]
        allowed = (slots < valid_len[row][:, None]) & (
            slots <= (ctx_len[row] + offset)[:, None])
        scores = jnp.where(allowed[:, None, :],
                           scores * self.scaling_factor, -jnp.inf)
        top = jnp.max(scores, axis=-1, keepdims=True)
        p = jnp.exp(scores - jnp.where(top == -jnp.inf, 0.0, top))
        p = p / jnp.maximum(p.sum(axis=-1, keepdims=True), 1e-30)
        return jnp.einsum("tnw,twc->tnc", p.astype(c.dtype), c,
                          preferred_element_type=jnp.float32).astype(q_lat.dtype)
