"""Pallas paged-decode attention: stream KV blocks, never gather windows.

The serving hot path (serve/engine.py) decodes every slot each tick
through the block-paged KV pool. The original XLA path materializes each
row's FULL block window per layer — ``pool[block_table]`` gathers
``(slots, max_blocks * block_size, n_kv, h)`` into a fresh buffer before
a single token's attention runs. On a chip that is pure HBM traffic the
MXU never sees twice: once to build the window, once to read it.

This kernel removes the window. A ``PrefetchScalarGridSpec`` prefetches
the block table so the BlockSpec ``index_map`` can address the pool
directly: grid step ``(row, j)`` DMAs pool block ``table[row, j]`` into
VMEM and folds it into a flash-style online softmax (running max ``m``,
normalizer ``l``, unnormalized accumulator in f32 scratch — Dao et al.,
arxiv 2205.14135), so each KV byte moves HBM->VMEM exactly once and no
``(rows, window)`` buffer ever exists. Blocks past a row's context are
skipped with ``pl.when`` (their DMA still lands, but no FLOPs run).

Variants share one kernel body:

- native: pool blocks arrive in the pool dtype and are attended as-is;
- int8: pool blocks arrive quantized; the kernel dequantizes IN VMEM with
  the same per-slot-per-head ``kv_quantize_int8`` scales the pool writer
  produced (``nn.attention.paged_scatter_kv``) — the f32 window the XLA
  path materialized in HBM never exists here either.

Masking follows the paged-decode contract exactly (``nn/attention.py``
``_paged_attention``): LOGICAL slot indices are the causal clock; slot
``k`` is visible to query slot ``q`` iff ``k < valid_len`` (written) and
``k <= q`` (causal). Queries may be a single decode token (s=1), a
prefill CHUNK (s=chunk), or a decode token plus its speculative DRAFTS
(s=k+1 — the engine's mixed program scores all k candidates in this one
call; rejected candidates' writes are simply re-covered by the next
call because ``valid_len`` never admits them) — K/V are scattered into
the pool by the caller before attending, and the same per-row
``valid_len``/``q_slot_base`` math serves every row kind, so one fused
program covers a whole mixed tick (serve/engine.py ``_build_mixed_fn``).
Rows past their real tokens (``new_len`` pads) produce garbage query
outputs that the host discards; their writes land in the trash block.

Off-TPU the kernel runs with ``interpret=True`` (the whole grid executes
as traced jax ops), so the CPU-mesh tests exercise the REAL kernel body,
not a stand-in; the XLA gather branch stays config-selectable
(``EngineConfig.paged_kernel = 'xla'``) as the fallback.
"""

from __future__ import annotations

import functools
from typing import Optional

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


def _paged_attention_kernel(
    # scalar prefetch (available to the index_maps before the body runs)
    tab_ref,      # (rows, max_blocks) int32 pool block ids
    valid_ref,    # (rows,) int32 valid slot count per row (ctx + new real)
    base_ref,     # (rows,) int32 slot of each row's first query token
    # blocks (VMEM)
    q_ref,        # (1, s, n, h)
    k_ref,        # (1, block_size, n_kv, h) pool dtype (or int8)
    v_ref,
    *rest,        # [scale_k_ref, scale_v_ref,] o_ref, m_ref, l_ref, acc_ref
    block_size: int,
    sm_scale: float,
    num_repeat_kv: int,
    quantized: bool,
):
    if quantized:
        scale_k_ref, scale_v_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        scale_k_ref, scale_v_ref = None, None
        o_ref, m_ref, l_ref, acc_ref = rest
    row = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    valid_len = valid_ref[row]

    @pl.when(j * block_size < valid_len)
    def _block():
        q = q_ref[0].astype(jnp.float32)  # (s, n, h)
        k = k_ref[0].astype(jnp.float32)  # (bs, n_kv, h)
        v = v_ref[0].astype(jnp.float32)
        if quantized:
            # dequant-in-kernel: the same kv_quantize_int8 scales the pool
            # writer produced; the f32 window never round-trips HBM
            k = k * scale_k_ref[0].astype(jnp.float32)[..., None]
            v = v * scale_v_ref[0].astype(jnp.float32)[..., None]
        if num_repeat_kv > 1:
            bs, n_kv, h = k.shape
            k = jnp.broadcast_to(
                k[:, :, None, :], (bs, n_kv, num_repeat_kv, h)
            ).reshape(bs, n_kv * num_repeat_kv, h)
            v = jnp.broadcast_to(
                v[:, :, None, :], (bs, n_kv, num_repeat_kv, h)
            ).reshape(bs, n_kv * num_repeat_kv, h)
        s = q.shape[0]
        scores = jnp.einsum("snh,knh->snk", q, k) * sm_scale  # (s, n, bs)
        # logical slots this grid step covers, vs each query's slot
        slot = j * block_size + jax.lax.broadcasted_iota(
            jnp.int32, (1, 1, block_size), 2
        )
        q_slot = base_ref[row] + jax.lax.broadcasted_iota(
            jnp.int32, (s, 1, 1), 0
        )
        allowed = (slot < valid_len) & (slot <= q_slot)
        scores = jnp.where(allowed, scores, -jnp.inf)
        # online softmax: all-masked tails keep m at -inf; the safe shift
        # avoids exp(-inf - -inf) = nan without branching
        m_old = m_ref[...]  # (s, n)
        m_new = jnp.maximum(m_old, scores.max(axis=-1))
        m_safe = jnp.where(m_new == -jnp.inf, 0.0, m_new)
        p = jnp.where(allowed, jnp.exp(scores - m_safe[..., None]), 0.0)
        alpha = jnp.where(m_old == -jnp.inf, 0.0, jnp.exp(m_old - m_safe))
        l_ref[...] = alpha * l_ref[...] + p.sum(axis=-1)
        acc_ref[...] = (
            alpha[..., None] * acc_ref[...] + jnp.einsum("snk,knh->snh", p, v)
        )
        m_ref[...] = m_new

    @pl.when(j == pl.num_programs(1) - 1)
    def _finalize():
        l = l_ref[...]
        # rows with zero visible slots (fully-trash inactive rows can't
        # reach here, but keep the guard total) emit zeros, not nan
        o_ref[0] = (
            acc_ref[...] / jnp.where(l == 0.0, 1.0, l)[..., None]
        ).astype(o_ref.dtype)


def paged_decode_attention(
    q: jax.Array,               # (rows, s, n, h) rotary-applied queries
    pool_k: jax.Array,          # (num_blocks, block_size, n_kv, h)
    pool_v: jax.Array,
    block_table: jax.Array,     # (rows, max_blocks) int32; 0 = trash
    valid_len: jax.Array,       # (rows,) int32 slots visible per row
    q_slot_base: jax.Array,     # (rows,) int32 slot of first query token
    *,
    sm_scale: float,
    num_repeat_kv: int = 1,
    scale_k: Optional[jax.Array] = None,  # (num_blocks, block_size, n_kv)
    scale_v: Optional[jax.Array] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Flash-style paged attention over a block pool; returns (rows, s, n, h).

    The pool must already contain the query tokens' K/V (the caller
    scatters through ``nn.attention.paged_scatter_kv`` first — ONE pool
    writer, so kernel and XLA fallback read identical bytes)."""
    _ensure_pallas()
    rows, s, n, h = q.shape
    _, block_size, n_kv, _ = pool_k.shape
    max_blocks = block_table.shape[1]
    quantized = scale_k is not None
    if interpret is None:
        interpret = paged_kernel_interpret()
    count_kernel_build("paged_attention", interpret)

    def _row(bi, j, tab, valid, base):
        del j, tab, valid, base
        return (bi, 0, 0, 0)

    def _blk(bi, j, tab, valid, base):
        del valid, base
        return (tab[bi, j], 0, 0, 0)

    def _blk_scale(bi, j, tab, valid, base):
        del valid, base
        return (tab[bi, j], 0, 0)

    in_specs = [
        pl.BlockSpec((1, s, n, h), _row),
        pl.BlockSpec((1, block_size, n_kv, h), _blk),
        pl.BlockSpec((1, block_size, n_kv, h), _blk),
    ]
    if quantized:
        in_specs += [
            pl.BlockSpec((1, block_size, n_kv), _blk_scale),
            pl.BlockSpec((1, block_size, n_kv), _blk_scale),
        ]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(rows, max_blocks),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, s, n, h), _row),
        scratch_shapes=[
            pltpu.VMEM((s, n), jnp.float32),      # running max m
            pltpu.VMEM((s, n), jnp.float32),      # normalizer l
            pltpu.VMEM((s, n, h), jnp.float32),   # unnormalized accumulator
        ],
    )
    kernel = functools.partial(
        _paged_attention_kernel,
        block_size=block_size, sm_scale=sm_scale,
        num_repeat_kv=num_repeat_kv, quantized=quantized,
    )
    operands = [q, pool_k, pool_v]
    if quantized:
        operands += [scale_k, scale_v]
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
        name="paged_attention",  # the trace's and the HLO's name for it
    )(
        block_table.astype(jnp.int32),
        valid_len.astype(jnp.int32),
        q_slot_base.astype(jnp.int32),
        *operands,
    )
