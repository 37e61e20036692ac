"""Tensor-parallel self attention.

Capability parity with the reference's ``ParallelSelfAttention``
(reference: src/scaling/core/nn/attention/attention.py:268-796): fused or
separate QKV (GQA via ``num_kv_heads``), rotary / rotary-complex, optional
key/query norm, sequence packing, causal + per-head local attention windows,
attention-probs dropout under MP-constant keys, LoRA injection on
query/key/value/dense, KV cache for incremental decode, row-parallel output
with sequence-parallel reduce-scatter.

TPU-first design choices:
- batch-major (b, s, n, h) instead of (s, b, n, h);
- sequence packing is carried as per-token segment ids (static shapes under
  jit) instead of varlen cu_seqlens; conversion helpers in seq_packing;
- the unfused path materialises the (b, n, s, s) scores through
  ``MaskedSoftmax`` (= reference 'torch' kernel); the fused path calls the
  Pallas flash-attention kernel with segment ids (= reference
  'flash_attention' kernel);
- head sharding over the model axis comes from GSPMD constraints on the
  column-parallel QKV outputs — no explicit head bookkeeping needed.
"""

from __future__ import annotations

import math
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from .base_layer import BaseLayer, ForwardContext, multiplied
from .linear import ColumnParallelLinear, RowParallelLinear, xavier_normal_init
from .lora import LoRAModuleType, LoRaConfig, ParallelLoRa
from .masked_softmax import MaskedSoftmax, MaskedSoftmaxConfig, MaskedSoftmaxKernel
from .norm import LayerNormConfig, NormType, get_norm
from .paged_attention import kv_block_layout
from .param import tree_prefix
from .rotary import (
    RelativePositionEmbeddingType,
    RotaryConfig,
    RotaryEmbedding,
    RotaryEmbeddingComplex,
)
from .seq_packing import segment_ids_to_mask


def repeat_kv(x: jax.Array, n_rep: int) -> jax.Array:
    """(b, s, n_kv, h) -> (b, s, n_kv * n_rep, h) for GQA."""
    if n_rep == 1:
        return x
    b, s, n_kv, h = x.shape
    x = jnp.broadcast_to(x[:, :, :, None, :], (b, s, n_kv, n_rep, h))
    return x.reshape(b, s, n_kv * n_rep, h)


class PagedTokenMap(NamedTuple):
    """Which pool row each position of a TOKEN-MAJOR batch belongs to.

    The serving engine packs a tick's real tokens back to back in row
    order (row 0's ``new_len[0]`` tokens, then row 1's, ...) into a batch
    of any shape ``(g, s)``, so that the trunk prices the tokens the tick
    holds and not ``rows x row width`` padded positions. The paged branch
    then needs, per position, the row whose block table and context
    length address it and its offset among that row's new tokens; and,
    per row, where its tokens lie, because the kernel attends row by row.
    All three follow from ``new_len`` alone (:func:`packed_token_map`).

    Positions past the tick's last real token carry the last row and an
    offset at or past its ``new_len``: not real, so written to the trash
    block and masked like a chunk's padding.
    """

    row: jax.Array         # (g, s) int32 pool row of each position
    offset: jax.Array      # (g, s) int32 place among the row's new tokens
    row_tokens: jax.Array  # (rows, w) int32 flat position (in g * s) of
    #                        row r's j-th new token, clipped into the batch


def packed_token_map(new_len: jax.Array, batch_shape: Tuple[int, int],
                     row_width: int) -> PagedTokenMap:
    """The :class:`PagedTokenMap` of a batch of shape ``batch_shape`` into
    which the rows' ``new_len`` (rows,) tokens were packed back to back in
    row order. ``row_width`` is the most tokens one row may bring: the
    width of the per-row query blocks the attention regroups to."""
    g, s = batch_shape
    total = g * s
    new_len = new_len.astype(jnp.int32)
    ends = jnp.cumsum(new_len)
    starts = ends - new_len
    t = jnp.arange(total, dtype=jnp.int32)
    # a token's row: how many rows end at or before it (empty rows end
    # where they start and are stepped over)
    row = jnp.sum(t[:, None] >= ends[None, :], axis=1, dtype=jnp.int32)
    row = jnp.minimum(row, new_len.shape[0] - 1)
    offset = t - starts[row]
    row_tokens = starts[:, None] + jnp.arange(row_width, dtype=jnp.int32)
    return PagedTokenMap(
        row=row.reshape(g, s), offset=offset.reshape(g, s),
        row_tokens=jnp.minimum(row_tokens, total - 1),
    )


class PagedKVCacheView(NamedTuple):
    """One layer's slice of the serving engine's block-paged KV pool
    (serve/kvcache.py), plus the batch's addressing state.

    The pool is a device-resident buffer of fixed-size blocks shared by
    every in-flight sequence (PagedAttention, SOSP '23); each decode row
    addresses its scattered blocks through ``block_table`` and its
    logical length through ``context_len``. Block 0 is the TRASH block:
    never allocated to content, it absorbs writes from inactive rows and
    padding so the jitted decode step needs no per-row branching.

    ``pool_k``/``pool_v`` are ``(num_blocks, block_size, n_kv, h)``, or
    HEAD-MAJOR ``(num_blocks, n_kv, block_size, h)`` where a head is wider
    than the 128 lanes or alone
    (``paged_attention.head_major_kv``; every reader asks
    ``paged_attention.kv_block_layout`` which, none reshapes a pool itself);
    float (dense) or int8 with per-slot-per-head ``scale_k``/``scale_v``
    of shape ``(num_blocks, block_size, n_kv)`` (quantized KV). A latent
    attention layer's line has no head axis and two leaves of unequal width:
    ``pool_k`` ``(num_blocks, block_size, kv_lora_rank)``, the normed KV
    latent, and ``pool_v`` ``(num_blocks, block_size, rope_line_width)``, the
    one rotary key (nn/latent_attention.py says which leaf holds what); the
    addressing state below is the same. A SPARSE grouped-query layer's line
    has a THIRD leaf beside K and V, ``pool_i`` ``(num_blocks, block_size,
    index_head_dim)``: the indexer's one key a token, no head axis
    (nn/sparse_attention.py); ``None`` for every other layer.

    ``new_len`` (per row, optional) is how many tokens the row REALLY
    brings: a prefill CHUNK shorter than its fixed program shape routes
    what is not a token to the trash block and excludes those slots from
    every mask, so one compiled program serves every chunk length
    (Sarathi-style chunked prefill, serve/engine.py). ``None`` means
    every presented position is real.

    ``token_map`` says which row each position of the batch belongs to.
    ``None`` is the ROW-MAJOR batch ``(rows, s)``: position ``(r, j)`` is
    row ``r``'s ``j``-th new token. The engine's mixed program packs the
    tick's real tokens token-major instead and hands the map along
    (:class:`PagedTokenMap`); both layouts go through the same scatter,
    kernel and masks.
    """

    pool_k: jax.Array
    pool_v: jax.Array
    block_table: jax.Array  # (rows, max_blocks) int32 block ids; 0 = trash
    context_len: jax.Array  # (rows,) int32 tokens already cached per row
    scale_k: Optional[jax.Array] = None
    scale_v: Optional[jax.Array] = None
    new_len: Optional[jax.Array] = None  # (rows,) int32 real new tokens
    token_map: Optional[PagedTokenMap] = None
    pool_i: Optional[jax.Array] = None   # a sparse layer's index keys

    @property
    def quantized(self) -> bool:
        return self.scale_k is not None

    def at_step(self, step, num_blocks: int) -> "PagedKVCacheView":
        """A looped model's view of loop step ``step`` (traced or not): its
        pools hold ``num_blocks`` blocks a step, step ``u``'s at ``[u *
        num_blocks, (u + 1) * num_blocks)``, so the same rows' tables
        shifted by ``step * num_blocks`` address the cache line of (step,
        this layer). Block 0 of every step's share is trash: an all-trash
        table stays one."""
        return self._replace(block_table=self.block_table + step * num_blocks)

    def token_rows(self, batch_shape: Tuple[int, int]):
        """``(row, offset, real)`` of every position of a batch of shape
        ``(b, s)``: its pool row, its place among the row's new tokens,
        and whether it holds a token at all."""
        if self.token_map is None:
            b, s = batch_shape
            row = jnp.broadcast_to(
                jnp.arange(b, dtype=jnp.int32)[:, None], (b, s))
            offset = jnp.broadcast_to(
                jnp.arange(s, dtype=jnp.int32)[None, :], (b, s))
        else:
            row, offset = self.token_map.row, self.token_map.offset
        if self.new_len is None:
            return row, offset, jnp.ones(batch_shape, bool)
        return row, offset, offset < self.new_len.astype(jnp.int32)[row]


def kv_quantize_int8(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Symmetric per-token-per-head int8: ``x`` (..., n_kv, h) -> (q, scale)
    with ``scale`` (..., n_kv). The ONE quantizer both the prefill pool
    writer (serve/kvcache.py) and the decode-step write below use, so the
    cache a prompt left behind and the cache decode appends to can never
    disagree about the rounding."""
    xf = x.astype(jnp.float32)
    scale = jnp.max(jnp.abs(xf), axis=-1) / 127.0
    scale = jnp.maximum(scale, 1e-8)
    q = jnp.clip(jnp.round(xf / scale[..., None]), -127, 127).astype(jnp.int8)
    return q, scale


def kv_dequantize_int8(q: jax.Array, scale: jax.Array, dtype) -> jax.Array:
    return (q.astype(jnp.float32) * scale[..., None].astype(jnp.float32)).astype(dtype)


def paged_flat_slots(block_table: jax.Array, positions: jax.Array,
                     block_size: int,
                     rows: Optional[jax.Array] = None) -> jax.Array:
    """Map logical token ``positions`` (b, s) to flat pool slots
    ``block_id * block_size + offset`` via their rows' block tables:
    ``rows`` (b, s) names each position's table row, by default the
    row-major batch's (position ``(r, j)`` reads table row ``r``).
    Positions past the table's reach route into the trash block (id 0 by
    convention sits at flat slots [0, block_size)) — NEVER into the
    row's last real block, where a clamped write would silently corrupt
    live cache."""
    max_blocks = block_table.shape[1]
    if rows is None:
        rows = jnp.arange(positions.shape[0], dtype=jnp.int32)[:, None]
    blk_idx = positions // block_size
    blocks = block_table[rows, jnp.clip(blk_idx, 0, max_blocks - 1)]
    blocks = jnp.where(blk_idx < max_blocks, blocks, 0)
    return blocks * block_size + positions % block_size


def paged_scatter_kv(view: PagedKVCacheView, flat: jax.Array,
                     k_rows: jax.Array, v_rows: jax.Array,
                     i_rows: Optional[jax.Array] = None) -> PagedKVCacheView:
    """Scatter new K/V rows (``(n, n_kv, h)``) into the pool at flat
    slots ``flat`` (``(n,)``), quantizing when the pool is int8 — the ONE
    pool writer (``_paged_attention`` calls it for chunk rows and decode
    rows alike), so the cache a prompt left behind and the cache decode
    appends to can never disagree about layout or rounding. ``i_rows``
    (``(n, index_head_dim)``): the third leaf of a sparse layer's line, its
    index keys, to the same slots of ``pool_i``. Returns the
    view with updated pools (tables/lengths untouched)."""
    # the pools as the rows ONE scatter addresses: a token's line at its slot,
    # or, of a pool that lies head-major, a row a head
    # (paged_attention.head_major_kv)
    k_dims, v_dims, at = view.pool_k.shape[2:], view.pool_v.shape[2:], flat
    if view.pool_k.ndim == 4 and not view.quantized:
        layout = kv_block_layout(view.pool_k, math.prod(k_rows.shape[1:]))
        k_dims, at = layout.scatter_rows(flat, view.pool_k.shape[-1])
        v_dims = k_dims
    pk = view.pool_k.reshape(-1, *k_dims)
    pv = view.pool_v.reshape(-1, *v_dims)
    scale_k, scale_v = view.scale_k, view.scale_v
    if view.quantized:
        qk, sk = kv_quantize_int8(k_rows)
        qv, sv = kv_quantize_int8(v_rows)
        pk = pk.at[flat].set(qk)
        pv = pv.at[flat].set(qv)
        scale_k = view.scale_k.reshape(pk.shape[0], -1)
        scale_v = view.scale_v.reshape(pv.shape[0], -1)
        scale_k = scale_k.at[flat].set(sk).reshape(view.scale_k.shape)
        scale_v = scale_v.at[flat].set(sv).reshape(view.scale_v.shape)
    else:
        # a pool of narrow heads keeps several a lane row
        # (paged_attention.packed_kv_dims): the same values, regrouped
        pk = pk.at[at].set(k_rows.reshape(-1, *k_dims).astype(pk.dtype))
        pv = pv.at[at].set(v_rows.reshape(-1, *v_dims).astype(pv.dtype))
    view = view._replace(
        pool_k=pk.reshape(view.pool_k.shape),
        pool_v=pv.reshape(view.pool_v.shape),
        scale_k=scale_k, scale_v=scale_v,
    )
    if i_rows is None:
        return view
    pi = view.pool_i.reshape(-1, view.pool_i.shape[-1])
    pi = pi.at[flat].set(i_rows.astype(pi.dtype))
    return view._replace(pool_i=pi.reshape(view.pool_i.shape))


def flash_path_active(
    *,
    kernel_is_flash: bool,
    causal: bool,
    dropout_attention_probs: float,
    deterministic: bool,
    context_parallel_size: int,
    seq_len: int,
    head_dim: int,
    has_kv_cache: bool = False,
    has_scores_manipulation: bool = False,
) -> bool:
    """Single source of truth for the flash-vs-XLA kernel gate.

    ``ParallelSelfAttention.__call__`` decides through this alone (mirrors
    the reference's kernel switch, masked_softmax_config.py:8-37); which
    path ran is read from ``obs.kernel_build_count``, never from a label."""
    if not kernel_is_flash or has_kv_cache or has_scores_manipulation:
        return False
    if not causal or context_parallel_size > 1:
        return False
    if dropout_attention_probs > 0.0 and not deterministic:
        return False
    from ..ops.flash_attention import flash_attention_supported

    return flash_attention_supported(seq_len, head_dim)


def multi_head_attention(
    query: jax.Array,  # (b, s_q, n, h)
    key: jax.Array,  # (b, s_k, n, h)
    value: jax.Array,  # (b, s_k, n, h)
    mask: jax.Array,  # (b, 1, s_q, s_k) True = forbidden
    scaling_factor: float,
    softmax: MaskedSoftmax,
    dropout_fn: Optional[Callable[[jax.Array], jax.Array]] = None,
    attention_scores_manipulation: Optional[jax.Array] = None,
    scores_manipulation_log_additive: bool = True,
) -> jax.Array:
    """Unfused attention: QK^T -> masked softmax -> PV. Returns (b, s_q, n, h)."""
    scores = jnp.einsum("bqnh,bknh->bnqk", query, key) * scaling_factor
    if attention_scores_manipulation is not None:
        m = attention_scores_manipulation.astype(scores.dtype)
        if scores_manipulation_log_additive:
            scores = scores + m
        else:
            # multiplicative variant (reference attention.py:166-170):
            # shift so the minimum UNMASKED score is 0, then scale — the
            # factors act on a non-negative score range
            filled = jnp.where(mask, jnp.asarray(10000.0, scores.dtype), scores)
            scores = (scores - jnp.min(filled, axis=-1, keepdims=True)) * m
    probs = softmax(scores, mask)
    if dropout_fn is not None:
        probs = dropout_fn(probs)
    out = jnp.einsum("bnqk,bknh->bqnh", probs.astype(value.dtype), value)
    return out


class ParallelSelfAttention(BaseLayer):
    # the view of the serving state a layer with this mixer is handed
    STATE_VIEW = PagedKVCacheView

    def __init__(
        self,
        hidden_size: int,
        num_attention_heads: int,
        masked_softmax_config: Optional[MaskedSoftmaxConfig] = None,
        causal: bool = True,
        num_local_attention_heads: int = 0,
        local_attention_window_size: Optional[int] = None,
        scaling_factor: Optional[float] = None,
        dropout_attention_probs: float = 0.0,
        rotary_config: Optional[RotaryConfig] = None,
        relative_position_embedding_type: str = RelativePositionEmbeddingType.ROTARY,
        bias: bool = True,
        dtype=jnp.float32,
        init_method: Callable = xavier_normal_init,
        bitfit_bias_name: Optional[str] = None,
        lora_config: Optional[LoRaConfig] = None,
        norm_type: NormType = NormType.LAYERNORM,
        key_query_norm: bool = False,
        key_query_norm_over_projection: bool = False,
        layernorm_config: Optional[LayerNormConfig] = None,
        qkv_in_one: bool = True,
        num_kv_heads: Optional[int] = None,
        head_dim: Optional[int] = None,
        key_multiplier: float = 1.0,
        output_gate: bool = False,
        lane_gate: bool = False,
    ):
        assert head_dim is not None or hidden_size % num_attention_heads == 0, (
            f"hidden size ({hidden_size}) must be divisible by "
            f"num_attention_heads ({num_attention_heads})"
        )
        self.hidden_size = hidden_size
        self.num_attention_heads = num_attention_heads
        # a head's size is hidden / heads unless the model states one of its
        # own (the projections' width, heads x head_dim, is then not hidden)
        self.head_dim = head_dim or hidden_size // num_attention_heads
        self.attention_width = num_attention_heads * self.head_dim
        self.causal = causal
        self.masked_softmax_config = masked_softmax_config or MaskedSoftmaxConfig()
        self.use_flash = self.masked_softmax_config.kernel == MaskedSoftmaxKernel.FLASH_ATTENTION
        self.num_local_attention_heads = num_local_attention_heads
        self.local_attention_window_size = local_attention_window_size
        if num_local_attention_heads > 0:
            assert local_attention_window_size is not None, (
                "local_attention_window_size needs to be set if num_local_attention_heads"
            )
        self.dropout_attention_probs = dropout_attention_probs
        self.scaling_factor = (
            scaling_factor if scaling_factor is not None else 1.0 / math.sqrt(self.head_dim)
        )
        self.dtype = dtype

        # a published constant on the keys, before rotary and the cache
        # (Falcon-H1's key_multiplier); the key projection's seeded init
        # starts that much higher
        self.key_multiplier = float(key_multiplier)
        assert key_multiplier == 1.0 or not qkv_in_one, (
            "a key multiplier needs a key projection of its own")
        self.qkv_in_one = qkv_in_one
        self.num_kv_heads = num_kv_heads
        if num_kv_heads:
            assert not qkv_in_one, "for a differing number of kv heads, qkv cannot be stored in one"
            assert num_attention_heads % num_kv_heads == 0
            self.num_repeat_kv = num_attention_heads // num_kv_heads
        else:
            self.num_kv_heads = num_attention_heads
            self.num_repeat_kv = 1

        common = dict(bias=bias, dtype=dtype, init_method=init_method,
                      bitfit_bias_name=bitfit_bias_name)
        width = self.attention_width
        if qkv_in_one:
            self.query_key_value = ColumnParallelLinear(
                hidden_size, width * 3, parallel_output=True, **common
            )
        else:
            kv_size = self.num_kv_heads * self.head_dim
            # with a gate a LANE the query projection is doubled: a head's
            # query, then its gate's logits (``lane_gate``, below)
            self.query = ColumnParallelLinear(
                hidden_size, width * (2 if lane_gate else 1),
                parallel_output=True, **common)
            self.key = ColumnParallelLinear(hidden_size, kv_size, parallel_output=True, **common)
            self.value = ColumnParallelLinear(hidden_size, kv_size, parallel_output=True, **common)

        self.dense = RowParallelLinear(
            width, hidden_size, parallel_input=True, parallel_output=True, **common
        )
        # a per-head gate on the heads' output, before ``dense``: g =
        # sigmoid(x W_g), one value a query head, from the layer's input (the
        # head-wise gate of arXiv:2505.06708); no bias
        # or a gate a LANE of every head's output, ``g = sigmoid(gate)`` with
        # ``[q | gate] = x W_q`` head by head (Qwen3-Next's attention): no leaf
        # of its own, the query projection is twice as wide
        self.lane_gate = lane_gate
        assert not lane_gate or not (output_gate or qkv_in_one), (
            "a gate a lane comes out of a query projection of its own and is "
            "the layer's one gate")
        self.gate = None
        if output_gate:
            self.gate = ColumnParallelLinear(
                hidden_size, num_attention_heads, parallel_output=True,
                bias=False, dtype=dtype, init_method=init_method)

        # rotary
        self.rotary_embedding: Any = None
        if relative_position_embedding_type == RelativePositionEmbeddingType.ROTARY:
            assert rotary_config is not None
            self.rotary_embedding = RotaryEmbedding(rotary_config)
        elif relative_position_embedding_type == RelativePositionEmbeddingType.ROTARY_COMPLEX:
            assert rotary_config is not None
            self.rotary_embedding = RotaryEmbeddingComplex(rotary_config)

        # key/query norm
        # per head (one weight of head_dim), or over the whole projection
        # before the split into heads (one weight of its full width:
        # OLMoE's q_norm / k_norm)
        self.key_query_norm = key_query_norm
        self.key_query_norm_over_projection = key_query_norm_over_projection
        if key_query_norm:
            heads_q, heads_k = (
                (num_attention_heads, self.num_kv_heads)
                if key_query_norm_over_projection else (1, 1)
            )
            self.norm_query = get_norm(norm_type, heads_q * self.head_dim, layernorm_config, dtype, bitfit_bias_name)
            self.norm_key = get_norm(norm_type, heads_k * self.head_dim, layernorm_config, dtype, bitfit_bias_name)

        self.masked_softmax = MaskedSoftmax(self.masked_softmax_config)

        # LoRA
        self.lora_config = lora_config
        self.lora_modules: Dict[str, ParallelLoRa] = {}
        if lora_config:
            for module_type in lora_config.parallel_modules:
                if module_type in (LoRAModuleType.DENSE, LoRAModuleType.QUERY):
                    out_features = hidden_size
                else:
                    out_features = self.num_kv_heads * self.head_dim
                self.lora_modules[f"{module_type.value}_{lora_config.name}"] = ParallelLoRa(
                    in_features=hidden_size,
                    out_features=out_features,
                    rank=lora_config.rank,
                    lora_module_type=module_type,
                    alpha=lora_config.alpha,
                    dropout=lora_config.dropout,
                    bias=lora_config.bias,
                    kaiming_a=lora_config.kaiming_a,
                    dtype=dtype,
                    name=lora_config.name,
                )

    # ------------------------------------------------------------------ init
    def init(self, key: jax.Array) -> dict:
        keys = jax.random.split(key, 8)
        params: dict = {}
        if self.qkv_in_one:
            params["query_key_value"] = self.query_key_value.init(keys[0])
        else:
            params["query"] = self.query.init(keys[0])
            params["key"] = self.key.init(keys[1])
            params["key"]["weight"] = multiplied(
                params["key"]["weight"], 1.0 / self.key_multiplier)
            params["value"] = self.value.init(keys[2])
        params["dense"] = self.dense.init(keys[3])
        if self.gate is not None:
            params["gate"] = self.gate.init(keys[7])
        if self.key_query_norm:
            params["norm_query"] = self.norm_query.init(keys[4])
            params["norm_key"] = self.norm_key.init(keys[5])
        for i, (name, mod) in enumerate(sorted(self.lora_modules.items())):
            params[name] = mod.init(jax.random.fold_in(keys[6], i))
        return params

    def param_metas(self) -> dict:
        metas: dict = {}
        if self.qkv_in_one:
            metas["query_key_value"] = tree_prefix(self.query_key_value.param_metas(), "query_key_value")
        else:
            metas["query"] = tree_prefix(self.query.param_metas(), "query")
            metas["key"] = tree_prefix(self.key.param_metas(), "key")
            metas["value"] = tree_prefix(self.value.param_metas(), "value")
        metas["dense"] = tree_prefix(self.dense.param_metas(), "dense")
        if self.gate is not None:
            metas["gate"] = tree_prefix(self.gate.param_metas(), "gate")
        if self.key_query_norm:
            metas["norm_query"] = tree_prefix(self.norm_query.param_metas(), "norm_query")
            metas["norm_key"] = tree_prefix(self.norm_key.param_metas(), "norm_key")
        for name, mod in sorted(self.lora_modules.items()):
            metas[name] = tree_prefix(mod.param_metas(), name)
        return metas

    # --------------------------------------------------------------- forward
    def _qkv(self, params: dict, x: jax.Array, ctx: ForwardContext):
        b, s, _ = x.shape
        if self.qkv_in_one:
            qkv = self.query_key_value(params["query_key_value"], x, ctx)
            qkv = qkv.reshape(b, s, self.num_attention_heads, 3 * self.head_dim)
            q, k, v = jnp.split(qkv, 3, axis=-1)
        else:
            q = self.query(params["query"], x, ctx).reshape(
                b, s, self.num_attention_heads, -1)  # with a lane gate: 2 head_dim
            k = self.key(params["key"], x, ctx).reshape(b, s, self.num_kv_heads, self.head_dim)
            v = self.value(params["value"], x, ctx).reshape(b, s, self.num_kv_heads, self.head_dim)
        # LoRA deltas
        if self.lora_config:
            lc = self.lora_config
            for mt, arr, nheads in (
                (LoRAModuleType.QUERY, "q", self.num_attention_heads),
                (LoRAModuleType.KEY, "k", self.num_kv_heads),
                (LoRAModuleType.VALUE, "v", self.num_kv_heads),
            ):
                name = f"{mt.value}_{lc.name}"
                if name in self.lora_modules:
                    delta = self.lora_modules[name](params[name], x, ctx)
                    delta = delta.reshape(b, s, nheads, self.head_dim)
                    if arr == "q":
                        q = q + delta
                    elif arr == "k":
                        k = k + delta
                    else:
                        v = v + delta
        return q, k, v

    def _heads(self, params: dict, x: jax.Array, ctx: ForwardContext,
               position_ids, lane_gate: bool = False):
        """``(q (b, s, n, h), k (b, s, n_kv, h), v (b, s, n_kv, h))`` as the
        attention meets them: projected, the key's multiplier, the key/query
        norm and rotary applied. ``lane_gate``: a fourth, the gate's logits
        (b, s, n, h) that came out of the query projection beside q."""
        b, s, _ = x.shape
        q, k, v = self._qkv(params, x, ctx)
        gate = None
        if self.lane_gate:
            q, gate = q[..., :self.head_dim], q[..., self.head_dim:]
        k = multiplied(k, self.key_multiplier)

        if self.key_query_norm and self.key_query_norm_over_projection:
            # the statistic runs over every head's values: under model
            # parallelism the heads are sharded, and GSPMD reduces the sum
            # of squares over the model axis (never a per-shard norm)
            q = self.norm_query(
                params["norm_query"], q.reshape(b, s, -1), ctx
            ).reshape(q.shape)
            k = self.norm_key(
                params["norm_key"], k.reshape(b, s, -1), ctx
            ).reshape(k.shape)
        elif self.key_query_norm:
            q = self.norm_query(params["norm_query"], q, ctx)
            k = self.norm_key(params["norm_key"], k, ctx)

        if self.rotary_embedding is not None:
            q, k = self.rotary_embedding(q, k, position_ids, position_ids)
        return (q, k, v, gate) if lane_gate else (q, k, v)

    def __call__(
        self,
        params: dict,
        x: jax.Array,  # (b, s, hidden)
        ctx: ForwardContext,
        segment_ids: Optional[jax.Array] = None,  # (b, s) packed-doc ids
        position_ids: Optional[jax.Array] = None,  # (b, s)
        kv_cache: Optional[Tuple[jax.Array, jax.Array]] = None,
        cache_offset: Optional[jax.Array] = None,
        attention_scores_manipulation: Optional[jax.Array] = None,
        attention_scores_manipulation_log_additive: bool = True,
        return_kv: bool = False,
    ):
        b, s, _ = x.shape
        if self.lane_gate:
            # what the epilogue's gate reads is then the logits, not the input
            q, k, v, x = self._heads(params, x, ctx, position_ids, lane_gate=True)
        else:
            q, k, v = self._heads(params, x, ctx, position_ids)

        new_kv = (k, v) if return_kv else None

        if isinstance(kv_cache, PagedKVCacheView):
            # block-paged decode (serve/): append the new tokens' K/V into
            # the shared block pool at each row's next slots, then attend
            # over the row's gathered blocks. position_ids stays the rotary
            # clock (applied above); context_len is the causal clock.
            assert attention_scores_manipulation is None, (
                "attention_scores_manipulation is unsupported on the paged "
                "decode path"
            )
            # (per-head local windows are refused before anything is traced:
            # config.py ``refuse_paged_serving``)
            out, new_view = self._paged_attention(q, k, v, kv_cache, b, s, ctx)
            return self._project_out(params, out, ctx, b, s, new_view, x)

        if kv_cache is not None:
            # incremental decode / token-slice pipelining: append new k/v at
            # cache_offset. A 3-tuple cache carries the cached slots'
            # segment ids too, so packed-document masking survives sequence
            # slicing (TeraPipe); the decode paths keep their 2-tuples and
            # the slots-only mask.
            cseg = None
            if len(kv_cache) == 3:
                ck, cv, cseg = kv_cache
            else:
                ck, cv = kv_cache
            assert cache_offset is not None
            ck = jax.lax.dynamic_update_slice_in_dim(ck, k.astype(ck.dtype), cache_offset, axis=1)
            cv = jax.lax.dynamic_update_slice_in_dim(cv, v.astype(cv.dtype), cache_offset, axis=1)
            k, v = ck, cv
            new_kv = (ck, cv)
            s_k = k.shape[1]
            # masking runs on CACHE SLOT indices, not on position_ids:
            # under left-padded (ragged) prompts a row's rotary positions
            # lag its slot indices by the pad width, and masking by rotary
            # position would forbid the most recent slots. position_ids
            # stays the rotary clock; slots are the causal clock.
            slots_k = jnp.broadcast_to(jnp.arange(s_k)[None, :], (b, s_k))
            slots_q = cache_offset + jnp.broadcast_to(
                jnp.arange(s)[None, :], (b, s)
            )
            # mask out unwritten cache slots + causal vs slot order
            valid_k = slots_k < (cache_offset + s)
            allowed = valid_k[:, None, :] & (slots_k[:, None, :] <= slots_q[:, :, None])
            if cseg is not None:
                seg_q = (
                    segment_ids
                    if segment_ids is not None
                    else jnp.zeros((b, s), jnp.int32)
                )
                cseg = jax.lax.dynamic_update_slice_in_dim(
                    cseg, seg_q.astype(cseg.dtype), cache_offset, axis=1
                )
                allowed = allowed & (cseg[:, None, :] == seg_q[:, :, None])
                new_kv = (ck, cv, cseg)
            mask = ~allowed[:, None, :, :]
        else:
            if segment_ids is None:
                segment_ids = jnp.zeros((b, s), dtype=jnp.int32)
            mask = segment_ids_to_mask(
                segment_ids, None, causal=self.causal,
                positions_q=None, positions_k=None,
            )

        dropout_fn = None
        if self.dropout_attention_probs > 0.0 and not ctx.deterministic:
            dropout_fn = lambda p: ctx.dropout(p, self.dropout_attention_probs)  # noqa: E731

        n_local = self.num_local_attention_heads

        # the flash (splash) kernel consumes UNREPEATED kv heads — the KV
        # bandwidth/memory win of GQA — and covers mixed local/global heads
        # via per-head masks; every other path repeats below
        use_flash_here = flash_path_active(
            kernel_is_flash=self.use_flash,
            causal=self.causal,
            dropout_attention_probs=self.dropout_attention_probs,
            deterministic=ctx.deterministic,
            context_parallel_size=ctx.context_parallel_size,
            seq_len=s,
            head_dim=self.head_dim,
            has_kv_cache=kv_cache is not None,
            has_scores_manipulation=attention_scores_manipulation is not None,
        )
        if use_flash_here:
            from ..ops.flash_attention import flash_attention_fused
            out = flash_attention_fused(
                q, k, v, segment_ids, causal=True, sm_scale=self.scaling_factor,
                num_local_heads=n_local,
                local_window=self.local_attention_window_size,
                mesh=ctx.mesh,
            )
            return self._project_out(params, out, ctx, b, s, new_kv, x)

        if ctx.context_parallel_size > 1 and kv_cache is None:
            # context parallelism: sequence sharded over the context mesh
            # axis. Two variants (topology.context_parallel_variant): 'ring'
            # rotates K/V blocks over ICI (ops/ring_attention.py); 'ulysses'
            # all-to-alls heads for sequence (ops/ulysses_attention.py).
            # Both are GQA-native — unrepeated KV cuts ICI traffic by the
            # group factor — but kv heads must still divide over the model
            # axis (and, for ulysses, over the context axis too); repeat
            # only as far as divisibility requires.
            assert attention_scores_manipulation is None, (
                "attention_scores_manipulation is unsupported under context "
                "parallelism"
            )
            assert n_local == 0, "local-window heads are unsupported under CP"
            assert dropout_fn is None, "attention-prob dropout unsupported under CP"
            from ..topology.topology import MODEL_AXIS

            assert ctx.context_parallel_variant in ("ring", "ulysses"), (
                f"unknown context_parallel_variant "
                f"{ctx.context_parallel_variant!r} (expected 'ring' or "
                "'ulysses') — refusing to silently pick a collective pattern"
            )
            ulysses = ctx.context_parallel_variant == "ulysses"
            mp = (
                ctx.mesh.shape[MODEL_AXIS]
                if ctx.mesh is not None and MODEL_AXIS in ctx.mesh.axis_names
                else 1
            )
            # kv heads must split cleanly over the model axis — and for
            # ulysses also over the context axis after the model split
            div = mp * (ctx.context_parallel_size if ulysses else 1)
            kr, vr = k, v
            n_kv = k.shape[2]
            if n_kv % div != 0:
                # repeat_kv's consecutive copies stay aligned with the
                # grouped-head reshape both variants use
                import math

                rep = div // math.gcd(n_kv, div)
                if self.num_repeat_kv % rep != 0:
                    rep = self.num_repeat_kv  # fallback: full repeat
                kr = repeat_kv(k, rep)
                vr = repeat_kv(v, rep)
            if ulysses:
                from ..ops.ulysses_attention import ulysses_attention

                out = ulysses_attention(
                    q, kr, vr, segment_ids, ctx.mesh,
                    causal=self.causal, sm_scale=self.scaling_factor,
                )
            else:
                from ..ops.ring_attention import ring_attention

                out = ring_attention(
                    q, kr, vr, segment_ids, ctx.mesh,
                    causal=self.causal, sm_scale=self.scaling_factor,
                )
            return self._project_out(params, out, ctx, b, s, new_kv, x)

        k = repeat_kv(k, self.num_repeat_kv)
        v = repeat_kv(v, self.num_repeat_kv)

        if n_local > 0 and kv_cache is None:
            # mixed local/global heads: first (n - n_local) heads global,
            # last n_local heads restricted to the window
            local_mask = segment_ids_to_mask(
                segment_ids, None, causal=self.causal,
                local_window=self.local_attention_window_size,
            )
            n_global = self.num_attention_heads - n_local
            out_g = multi_head_attention(
                q[:, :, :n_global], k[:, :, :n_global], v[:, :, :n_global],
                mask, self.scaling_factor, self.masked_softmax, dropout_fn,
                attention_scores_manipulation,
                attention_scores_manipulation_log_additive,
            ) if n_global > 0 else None
            out_l = multi_head_attention(
                q[:, :, n_global:], k[:, :, n_global:], v[:, :, n_global:],
                local_mask, self.scaling_factor, self.masked_softmax, dropout_fn,
                attention_scores_manipulation,
                attention_scores_manipulation_log_additive,
            )
            out = out_l if out_g is None else jnp.concatenate([out_g, out_l], axis=2)
        else:
            out = multi_head_attention(
                q, k, v, mask, self.scaling_factor, self.masked_softmax,
                dropout_fn, attention_scores_manipulation,
                attention_scores_manipulation_log_additive,
            )

        return self._project_out(params, out, ctx, b, s, new_kv, x)

    def _paged_attention(self, q, k, v, view: PagedKVCacheView, b: int, s: int,
                         ctx: ForwardContext):
        """Decode (or chunk-prefill) through the block-paged KV pool:
        scatter the batch's new tokens into the pool, then attend each
        row over its blocks with slot-validity + causal masking. One
        jitted program serves every mix of sequence lengths — raggedness
        lives entirely in ``block_table``/``context_len``/``new_len``
        (and ``token_map``), never in shapes.

        The batch ``(b, s)`` is any layout of the rows' new tokens:
        ``view.token_rows`` names each position's row and its offset
        among the row's tokens (row-major by default, token-major under a
        ``token_map``), and the scatter addresses the pool through that.
        Attention itself runs row by row over ``(rows, w)`` query blocks:
        a token-major batch is regrouped to them by one gather
        (``token_map.row_tokens``) and the output gathered back to the
        batch's order; the row-major batch is already in that shape.

        Two formulations behind one scatter (``ctx.paged_kernel``):

        - ``'pallas'`` — what serves: the flash-style streaming kernel
          (nn/paged_attention.py): KV blocks DMA from the pool per row
          into an online softmax; no gathered window is materialized.
          Runs interpreted off-TPU, so the CPU mesh tests the real body.
        - ``'xla'`` — the tests' reference, not a serving option: gather
          each row's blocks as one contiguous
          (rows, max_blocks*block_size, n_kv, h) window, then run the
          unfused attention. Independent of the kernel, and pure extra
          HBM traffic on a chip.
        """
        block_size = kv_block_layout(
            view.pool_k, k.shape[2] * k.shape[3]).block_size
        rows, max_blocks = view.block_table.shape
        window = max_blocks * block_size
        ctx_len = view.context_len.astype(jnp.int32)
        if view.new_len is None:
            assert view.token_map is None, "a token_map is made from new_len"
            new_len = jnp.full((rows,), s, jnp.int32)
        else:
            new_len = view.new_len.astype(jnp.int32)

        # --- write: each real token to its row's next slot (inactive
        # rows: table is all-trash); what is not a token routes to the
        # trash block — a clamped write into the row's own blocks would
        # corrupt the slots the NEXT chunk is about to fill
        row, offset, real = view.token_rows((b, s))
        flat = paged_flat_slots(
            view.block_table, ctx_len[row] + offset, block_size, row
        )
        flat = jnp.where(real, flat, 0)
        new_view = paged_scatter_kv(
            view, flat.reshape(-1),
            k.reshape(b * s, *k.shape[2:]), v.reshape(b * s, *v.shape[2:]),
        )

        # --- attend, row by row
        if view.token_map is None:
            return self._attend_rows(
                q, new_view, ctx_len, new_len, window, ctx), new_view
        q_rows = q.reshape(b * s, *q.shape[2:])[view.token_map.row_tokens]
        out = self._attend_rows(q_rows, new_view, ctx_len, new_len, window, ctx)
        return out[row, jnp.minimum(offset, q_rows.shape[1] - 1)], new_view

    def _attend_rows(self, q, view: PagedKVCacheView, ctx_len, new_len,
                     window: int, ctx: ForwardContext):
        """``q`` (rows, w, n, h), row ``r``'s new tokens in order, over
        the pool they were just scattered into; returns (rows, w, n, h)
        (finite garbage past a row's ``new_len``)."""
        rows, w = q.shape[:2]
        valid_len = ctx_len + new_len  # written slots per row
        kernel = ctx.paged_kernel
        if kernel == "pallas":
            import functools

            from .paged_attention import paged_decode_attention
            from ..topology.topology import MODEL_AXIS

            mp = (
                ctx.mesh.shape[MODEL_AXIS]
                if ctx.mesh is not None and MODEL_AXIS in ctx.mesh.axis_names
                else 1
            )
            call = functools.partial(
                paged_decode_attention,
                sm_scale=self.scaling_factor,
                num_repeat_kv=self.num_repeat_kv,
            )
            if mp == 1:
                return call(
                    q, view.pool_k, view.pool_v,
                    view.block_table, valid_len, ctx_len,
                    scale_k=view.scale_k, scale_v=view.scale_v,
                )
            # mp>1 sharded serving: pallas calls are opaque to GSPMD
            # (which would gather the whole pool to every device), so
            # partition the kernel itself — each model shard streams
            # its OWN (num_blocks, block_size, n_kv/mp, h) pool slice
            # under its n/mp query heads. Addressing state (tables,
            # lengths) is replicated; the GQA repeat factor is
            # unchanged per shard because q and kv heads divide mp
            # together (enforced at pool init, serve/kvcache.py).
            from jax.sharding import PartitionSpec as P

            heads = P(None, None, MODEL_AXIS, None)
            # a head-major pool's head axis is dim 1, the sharded one still
            pool = P(None, MODEL_AXIS, None, None) if kv_block_layout(
                view.pool_k, q.shape[2] // self.num_repeat_kv * q.shape[3]
            ).head_major else heads
            rep2, rep1 = P(None, None), P(None)
            quant = view.quantized
            in_specs = [heads, pool, pool, rep2, rep1, rep1]
            if quant:
                in_specs += [P(None, None, MODEL_AXIS)] * 2

            def run_shard(qq, pk, pv, tab, vl, qb, *scales):
                sk, sv = scales if quant else (None, None)
                return call(qq, pk, pv, tab, vl, qb,
                            scale_k=sk, scale_v=sv)

            operands = [
                q, view.pool_k, view.pool_v,
                view.block_table, valid_len, ctx_len,
            ]
            if quant:
                operands += [view.scale_k, view.scale_v]
            return jax.shard_map(
                run_shard, mesh=ctx.mesh, in_specs=tuple(in_specs),
                out_specs=heads, check_vma=False,
            )(*operands)
        assert kernel == "xla", (
            f"unknown paged_kernel {kernel!r} (expected 'pallas' or 'xla') "
            "— refusing to silently pick an attention path"
        )

        # --- gather: each row's blocks as one contiguous KV window
        layout = kv_block_layout(
            view.pool_k, q.shape[2] // self.num_repeat_kv * q.shape[3])
        # (rows, max_blocks, *a block's dims) -> (rows, window, n_kv, h)
        gk = layout.lines(view.pool_k[view.block_table])
        gv = layout.lines(view.pool_v[view.block_table])
        # (a pool of narrow heads keeps several a lane row: back to heads)
        gk = gk.reshape(rows, window, -1, q.shape[-1])
        gv = gv.reshape(rows, window, -1, q.shape[-1])
        if view.quantized:
            gsk = view.scale_k[view.block_table].reshape(rows, window, -1)
            gsv = view.scale_v[view.block_table].reshape(rows, window, -1)
            gk = kv_dequantize_int8(gk, gsk, q.dtype)
            gv = kv_dequantize_int8(gv, gsv, q.dtype)

        # masking runs on LOGICAL slot indices (the causal clock), exactly
        # like the dense cache path: unwritten slots are invalid, written
        # slots obey causal order against the query's slot
        slots_k = jnp.broadcast_to(
            jnp.arange(window, dtype=jnp.int32)[None, :], (rows, window)
        )
        slots_q = ctx_len[:, None] + jnp.arange(w, dtype=jnp.int32)[None, :]
        valid_k = slots_k < valid_len[:, None]
        allowed = valid_k[:, None, :] & (
            slots_k[:, None, :] <= slots_q[:, :, None]
        )
        mask = ~allowed[:, None, :, :]

        gk = repeat_kv(gk, self.num_repeat_kv)
        gv = repeat_kv(gv, self.num_repeat_kv)
        return multi_head_attention(
            q, gk, gv, mask, self.scaling_factor, self.masked_softmax, None
        )

    def _project_out(self, params, out, ctx, b, s, new_kv, x=None):
        """Shared epilogue: heads -> hidden, dense projection + LoRA delta;
        with a gate, each head's output times its gate first (``x``: the
        layer's input, which the gate reads; of a gate a lane its logits)."""
        if self.lane_gate:
            with jax.named_scope("gate"):
                g = jax.nn.sigmoid(x.astype(jnp.float32))
                out = (out.reshape(g.shape).astype(jnp.float32) * g).astype(out.dtype)
        if self.gate is not None:
            with jax.named_scope("gate"):
                g = jax.nn.sigmoid(
                    self.gate(params["gate"], x, ctx).astype(jnp.float32))
                out = (out.reshape(b, s, self.num_attention_heads, self.head_dim)
                       .astype(jnp.float32) * g[..., None]).astype(out.dtype)
        out = out.reshape(b, s, self.attention_width)
        y = self.dense(params["dense"], out, ctx)
        if self.lora_config:
            name = f"{LoRAModuleType.DENSE.value}_{self.lora_config.name}"
            if name in self.lora_modules:
                y = y + self.lora_modules[name](params[name], out, ctx)
        if new_kv is not None:
            return y, new_kv
        return y

    # ----------------------------------------------------------------- merge
    def merge_lora_weights(self, params: dict) -> dict:
        """Fold LoRA deltas into base weights; returns updated params tree.

        The reference mutates base weights and deletes the lora modules
        (attention.py:766-797). Functionally the same thing here: the delta
        is folded into the host weight and the lora_b factor is zeroed, so
        the still-present LoRA path contributes exactly nothing afterwards.
        A trained LoRA bias is folded into the host projection's bias (the
        reference silently drops it with the deleted module); merging raises
        if the host has no bias to absorb it rather than changing the model
        function silently.
        """
        if not self.lora_config:
            return params
        params = dict(params)
        lc = self.lora_config

        def fold_bias(host: dict, lora_bias, what: str) -> dict:
            if lora_bias is None or not jnp.asarray(lora_bias).size:
                return host
            if "bias" not in host:
                raise ValueError(
                    f"cannot merge LoRA bias on {what}: the host projection "
                    "has no bias parameter to absorb it (set lora bias=False "
                    "or keep the LoRA unmerged)"
                )
            host["bias"] = host["bias"] + lora_bias.astype(host["bias"].dtype)
            return host

        for mt in lc.parallel_modules:
            name = f"{mt.value}_{lc.name}"
            if name not in self.lora_modules:
                continue
            delta = self.lora_modules[name].get_delta_weights(params[name])
            lora_bias = params[name].get("bias")
            disabled = {
                **params[name],
                "lora_b": jnp.zeros_like(params[name]["lora_b"]),
            }
            if "bias" in disabled:
                disabled["bias"] = jnp.zeros_like(disabled["bias"])
            params[name] = disabled
            if mt == LoRAModuleType.DENSE:
                host = dict(params["dense"])
                host["weight"] = host["weight"] + delta.astype(host["weight"].dtype)
                params["dense"] = fold_bias(host, lora_bias, "dense")
            elif self.qkv_in_one:
                if lora_bias is not None:
                    raise NotImplementedError(
                        "LoRA bias merge is unsupported for the fused "
                        "query_key_value layout; set attention_qkv_in_one "
                        "false or lora bias=False"
                    )
                host = dict(params["query_key_value"])
                w = host["weight"].reshape(
                    self.hidden_size, self.num_attention_heads, 3 * self.head_dim
                )
                idx = {"query": 0, "key": 1, "value": 2}[mt.value]
                d = delta.reshape(self.hidden_size, self.num_attention_heads, self.head_dim)
                w = w.at[:, :, idx * self.head_dim : (idx + 1) * self.head_dim].add(
                    d.astype(w.dtype)
                )
                host["weight"] = w.reshape(self.hidden_size, 3 * self.hidden_size)
                params["query_key_value"] = host
            else:
                host = dict(params[mt.value])
                host["weight"] = host["weight"] + delta.astype(host["weight"].dtype)
                params[mt.value] = fold_bias(host, lora_bias, mt.value)
        return params
