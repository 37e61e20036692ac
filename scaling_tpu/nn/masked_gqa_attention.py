"""Pallas grouped-query attention of ONE row's queries over its K and V lines
under a per-query mask (the sparse grouped-query layer's chunk rows:
``nn/sparse_attention.py``).

A sparse layer's query attends over the lines an indexer chose for it, so two
queries of one row see different lines and the paged kernel
(``nn/paged_attention.py``: a row's tiles under ONE causal mask) does not
serve. This kernel is the dense stream under a mask, flash-style, the sibling
of ``nn/masked_latent_attention.py`` for a line with a head axis:

    scores[g, (p, j), k] = scale * q[p, g * group + j] . keys[k, g]
    out[g, (p, j)]       = softmax_k(scores where chosen[p, k]) @ values[k, g]

The mask is a (query, line) fact: ONE choice a token, shared by the ``group``
query heads of a KV head and by every KV head. The operands are the row's own,
contiguous: the caller gathers the row's window of K and of V through its
block table (whole blocks, cheap) as ``(lines, n_kv x h)`` and hands the mask
as ``(positions, lines)`` int32, so every operand is a plain ``BlockSpec``:
the grid is (blocks of ``QUERY_POSITIONS`` positions, key tiles); the GQA
group is folded into the matmul's rows (per KV head the queries of a block are
``(positions x group, h)``, position-major, as the paged kernel folds them),
the KV heads are a loop over lane-aligned column blocks of a key tile, a key
tile is folded into a float32 online softmax held in scratch across the tile
axis, and tiles past the row's visible length are neither fetched (their block
index repeats the last one that is) nor folded.

The mask is exact, not a price: every visible line is multiplied (the dense
attention's FLOPs), what a query did not choose is dropped from its softmax.
Off-TPU the kernel runs interpreted.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import paged_attention as _paged
from .masked_latent_attention import key_tile   # lines a step folds: 512

KERNEL_NAME = "masked_gqa_attention"
# positions whose heads are one matmul's rows: every block of them re-reads
# the row's whole window of K and V (2 x n_kv x h values a line), so a block
# must hold enough positions for the window's bytes to be worth their FLOPs
QUERY_POSITIONS = 64
# the blocks above in VMEM, double-buffered, with the scores of one KV head
VMEM_LIMIT_BYTES = 48 << 20


def _kernel(seen_ref, q_ref, k_ref, v_ref, mask_ref, o_ref, m_ref, l_ref,
            acc_ref, *, group: int, tile: int, sm_scale: float):
    pl = _paged.pl
    step = pl.program_id(1)
    n_kv, rows, h = q_ref.shape

    @pl.when(step == 0)
    def _clear():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(step * tile < seen_ref[0])
    def _fold():
        precision = (None if q_ref.dtype == jnp.float32
                     else jax.lax.Precision.DEFAULT)
        # a position's mask row serves the rows of its group, in every KV head
        chosen = mask_ref[...] != 0                         # (positions, tile)
        chosen = jnp.broadcast_to(
            chosen[:, None, :], (chosen.shape[0], group, tile)
        ).reshape(rows, tile)
        for g in range(n_kv):
            q = q_ref[g]
            k = k_ref[:, g * h:(g + 1) * h]
            v = v_ref[:, g * h:(g + 1) * h]
            scores = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32, precision=precision)
            scores = jnp.where(chosen, scores * sm_scale, -jnp.inf)
            m_old = m_ref[g]
            m_new = jnp.maximum(m_old, scores.max(axis=-1, keepdims=True))
            m_safe = jnp.where(m_new == -jnp.inf, 0.0, m_new)
            p = jnp.exp(scores - m_safe)
            alpha = jnp.exp(m_old - m_safe)
            l_ref[g] = alpha * l_ref[g] + p.sum(axis=-1, keepdims=True)
            acc_ref[g] = alpha * acc_ref[g] + jnp.dot(
                p.astype(v.dtype), v,
                preferred_element_type=jnp.float32, precision=precision)
            m_ref[g] = m_new

    @pl.when(step == pl.num_programs(1) - 1)
    def _finish():
        total = l_ref[...]
        o_ref[...] = (acc_ref[...] / jnp.where(total == 0.0, 1.0, total)
                      ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("sm_scale", "interpret"))
def masked_gqa_attention(
    q: jax.Array,         # (positions, n, h) rotary-applied queries
    keys: jax.Array,      # (lines, n_kv, h) the row's window of K, slot order
    values: jax.Array,    # (lines, n_kv, h) and of V
    chosen: jax.Array,    # (positions, lines) bool: what each query attends to
    seen: jax.Array,      # () int32: slots of the window that hold a line
    *,
    sm_scale: float,
    interpret: bool,
) -> jax.Array:
    """``(positions, n, h)``; a position that chose nothing gives zeros."""
    _paged._ensure_pallas()
    pl, pltpu = _paged.pl, _paged.pltpu
    positions, n, h = q.shape
    window, n_kv, _ = keys.shape
    group = n // n_kv
    assert n == n_kv * group, (n, n_kv)
    tile = key_tile(window)
    block = min(QUERY_POSITIONS, -(-positions // 8) * 8)
    pad = -positions % block
    padded = positions + pad
    rows = block * group
    # fold the GQA group into the matmul's rows: per KV head (positions x
    # group, h), position-major
    folded = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
        padded, n_kv, group, h).transpose(1, 0, 2, 3).reshape(
            n_kv, padded * group, h)
    mask = jnp.pad(chosen.astype(jnp.int32), ((0, pad), (0, 0)))
    seen = jnp.minimum(seen.astype(jnp.int32), window).reshape(1)

    def held(step, seen_ref):
        """The tile a step reads: past the visible ones, the last of them
        again (an unchanged block is not fetched twice)."""
        return jnp.minimum(step, jnp.maximum(seen_ref[0] - 1, 0) // tile)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(padded // block, window // tile),
        in_specs=[
            pl.BlockSpec((n_kv, rows, h), lambda i, j, seen: (0, i, 0)),
            pl.BlockSpec((tile, n_kv * h), lambda i, j, seen: (held(j, seen), 0)),
            pl.BlockSpec((tile, n_kv * h), lambda i, j, seen: (held(j, seen), 0)),
            pl.BlockSpec((block, tile), lambda i, j, seen: (i, held(j, seen))),
        ],
        out_specs=pl.BlockSpec((n_kv, rows, h), lambda i, j, seen: (0, i, 0)),
        scratch_shapes=[
            pltpu.VMEM((n_kv, rows, 1), jnp.float32),     # running max
            pltpu.VMEM((n_kv, rows, 1), jnp.float32),     # normalizer
            pltpu.VMEM((n_kv, rows, h), jnp.float32),     # unnormalized sum
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, group=group, tile=tile, sm_scale=sm_scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_kv, padded * group, h), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
        name=KERNEL_NAME,  # the trace's and the HLO's name for it
    )(seen, folded, keys.reshape(window, n_kv * h),
      values.reshape(window, n_kv * h), mask)
    out = out.reshape(n_kv, padded, group, h).transpose(1, 0, 2, 3)
    return out.reshape(padded, n, h)[:positions]
