"""Pallas attention of ONE row's queries over its latent lines under a
per-query mask (the sparse latent layer's chunk rows:
``nn/sparse_latent_attention.py``).

A sparse latent layer's query attends over the lines an indexer chose for it,
so two queries of one row see different lines and the paged latent kernel
(``nn/latent_paged_attention.py``: a row's tiles under ONE causal mask) does
not serve. This kernel is the dense stream under a mask, flash-style:

    scores[(p, n), k] = scale * q_line[p, n] . lines[k]      (all lanes)
    out[(p, n)]       = softmax_k(scores where chosen[p, k]) @ lines[k, :lat]

``q_line`` is ``[q', q_rope, zeros]`` against a line ``[c_kv, k_r, zeros]``
(one dot product over the leaf's lanes); the value is the line's first
``lat`` lanes. The operands are the row's own, contiguous: the caller gathers
the row's window of lines through its block table (whole blocks, cheap) and
hands the mask as ``(positions, lines)`` int32, so every operand is a plain
``BlockSpec``: the grid is (blocks of ``QUERY_POSITIONS`` positions, key
tiles), all heads of a block's positions are the rows of one matmul
(position-major), a key tile is folded into a float32 online softmax held in
scratch across the tile axis, and tiles past the row's visible length are
neither fetched (their block index repeats the last one that is) nor folded.
The scores never leave VMEM: in plain XLA the same fold writes and re-reads a
float32 ``(positions x heads, tile)`` score block per tile several times and
ran at a fifth of the matmuls' rate (PERF.md, PR 59).

The mask is exact, not a price: every visible line is multiplied (the dense
attention's FLOPs), what a query did not choose is dropped from its softmax.
Off-TPU the kernel runs interpreted.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import paged_attention as _paged

KERNEL_NAME = "masked_latent_attention"
# positions whose heads are one matmul's rows
QUERY_POSITIONS = 8
# lines a step folds
KEY_TILE = 512


def key_tile(lines: int) -> int:
    """The largest tile of at most ``KEY_TILE`` lines that divides ``lines``."""
    return next(t for t in (KEY_TILE, 256, 128, 64, 32, 16, 8, 4, 2, 1)
                if lines % t == 0)


def _kernel(seen_ref, q_ref, k_ref, mask_ref, o_ref, m_ref, l_ref, acc_ref, *,
            heads: int, lat: int, tile: int, sm_scale: float):
    pl = _paged.pl
    step = pl.program_id(1)

    @pl.when(step == 0)
    def _clear():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(step * tile < seen_ref[0])
    def _fold():
        q, k = q_ref[...], k_ref[...]
        precision = (None if q.dtype == jnp.float32
                     else jax.lax.Precision.DEFAULT)
        scores = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=precision)
        # a position's mask row serves its heads' rows
        chosen = mask_ref[...] != 0                         # (positions, tile)
        chosen = jnp.broadcast_to(
            chosen[:, None, :], (chosen.shape[0], heads, tile)
        ).reshape(scores.shape)
        scores = jnp.where(chosen, scores * sm_scale, -jnp.inf)
        m_old = m_ref[...]
        m_new = jnp.maximum(m_old, scores.max(axis=-1, keepdims=True))
        m_safe = jnp.where(m_new == -jnp.inf, 0.0, m_new)
        p = jnp.exp(scores - m_safe)
        alpha = jnp.exp(m_old - m_safe)
        l_ref[...] = alpha * l_ref[...] + p.sum(axis=-1, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + jnp.dot(
            p.astype(k.dtype), k[:, :lat],
            preferred_element_type=jnp.float32, precision=precision)
        m_ref[...] = m_new

    @pl.when(step == pl.num_programs(1) - 1)
    def _finish():
        total = l_ref[...]
        o_ref[...] = (acc_ref[...] / jnp.where(total == 0.0, 1.0, total)
                      ).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("lat", "sm_scale", "interpret"))
def masked_latent_attention(
    q_line: jax.Array,    # (positions, heads, width) queries against a line
    lines: jax.Array,     # (lines, width) the row's window, slot order
    chosen: jax.Array,    # (positions, lines) bool: what each query attends to
    seen: jax.Array,      # () int32: slots of the window that hold a line
    *,
    lat: int,             # the first lanes of a line are its value
    sm_scale: float,
    interpret: bool,
) -> jax.Array:
    """``(positions, heads, lat)``; a position that chose nothing gives
    zeros."""
    _paged._ensure_pallas()
    pl, pltpu = _paged.pl, _paged.pltpu
    positions, heads, width = q_line.shape
    window = lines.shape[0]
    tile = key_tile(window)
    pad = -positions % QUERY_POSITIONS
    q_line = jnp.pad(q_line, ((0, pad), (0, 0), (0, 0)))
    mask = jnp.pad(chosen.astype(jnp.int32), ((0, pad), (0, 0)))
    rows = QUERY_POSITIONS * heads
    seen = jnp.minimum(seen.astype(jnp.int32), window).reshape(1)

    def held(step, seen_ref):
        """The tile a step reads: past the visible ones, the last of them
        again (an unchanged block is not fetched twice)."""
        return jnp.minimum(step, jnp.maximum(seen_ref[0] - 1, 0) // tile)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=((positions + pad) // QUERY_POSITIONS, window // tile),
        in_specs=[
            pl.BlockSpec((rows, width), lambda i, j, seen: (i, 0)),
            pl.BlockSpec((tile, width), lambda i, j, seen: (held(j, seen), 0)),
            pl.BlockSpec((QUERY_POSITIONS, tile),
                         lambda i, j, seen: (i, held(j, seen))),
        ],
        out_specs=pl.BlockSpec((rows, lat), lambda i, j, seen: (i, 0)),
        scratch_shapes=[
            pltpu.VMEM((rows, 1), jnp.float32),       # running max
            pltpu.VMEM((rows, 1), jnp.float32),       # normalizer
            pltpu.VMEM((rows, lat), jnp.float32),     # unnormalized sum
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, heads=heads, lat=lat, tile=tile,
                          sm_scale=sm_scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(
            ((positions + pad) * heads, lat), q_line.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name=KERNEL_NAME,  # the trace's and the HLO's name for it
    )(seen, q_line.reshape(-1, width), lines, mask)
    return out.reshape(positions + pad, heads, lat)[:positions]
