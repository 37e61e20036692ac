"""Ulysses-style context parallelism: all-to-all over the ``context`` axis.

A capability beyond the reference (SURVEY §2.4: "Ulysses (attention head
all-to-all): absent — no all_to_all calls in repo"). The complementary
design to ``ops/ring_attention.py``:

- ring: every device keeps its sequence shard of Q resident and K/V blocks
  rotate — O(s/cp) activation memory, cp ppermute hops per layer;
- ulysses (DeepSpeed-Ulysses, Jacobs et al. 2023): one all-to-all trades
  the sequence shard for a head shard, each device then runs ordinary
  full-sequence attention for n/cp of the heads, and a second all-to-all
  restores sequence sharding — two collective hops per layer regardless of
  cp, but O(s^2) scores for the local heads.

Ring favours very long sequences (blockwise memory); ulysses favours
moderate sequences with enough heads (fewer, larger collectives that ride
ICI well). Both are selectable per run via
``topology.context_parallel_variant`` — the variant changes only the
attention internals, so loss parity with the single-device path holds for
either (tests/core/test_nn/test_ulysses_attention.py,
tests/transformer/test_training_context_parallel.py).

GQA stays unrepeated through the exchange: K/V travel with their n_kv/cp
head shard and the grouped-query einsum consumes them directly, so the
all-to-all moves 2·s·(n_kv/cp)·d elements, not the repeated 2·s·(n/cp)·d.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..topology.topology import CONTEXT_AXIS, DATA_AXIS, MODEL_AXIS

_NEG = -1e9


def _ulysses_local(
    q: jax.Array,  # (b, s_loc, n_loc, d) — this device's shards
    k: jax.Array,  # (b, s_loc, n_kv_loc, d) — UNREPEATED kv heads
    v: jax.Array,
    seg: jax.Array,  # (b, s_loc) int32 packed-doc ids
    *,
    axis_name: str,
    causal: bool,
    sm_scale: float,
) -> jax.Array:
    cp = jax.lax.psum(1, axis_name)
    b, s_loc, n, d = q.shape
    n_kv = k.shape[2]
    assert n % cp == 0, (
        f"ulysses needs local query heads ({n}) divisible by the context "
        f"axis ({cp}); lower cp or use the ring variant"
    )
    assert n_kv % cp == 0, (
        f"ulysses needs local kv heads ({n_kv}) divisible by the context "
        f"axis ({cp}); the caller repeats kv minimally to make this hold"
    )

    # all-to-all #1: scatter heads over the axis, gather the full sequence
    # (device i already holds sequence chunk i, so tiled concat along the
    # sequence axis reassembles global order)
    qg = jax.lax.all_to_all(q, axis_name, split_axis=2, concat_axis=1, tiled=True)
    kg = jax.lax.all_to_all(k, axis_name, split_axis=2, concat_axis=1, tiled=True)
    vg = jax.lax.all_to_all(v, axis_name, split_axis=2, concat_axis=1, tiled=True)
    seg_full = jax.lax.all_gather(seg, axis_name, axis=1, tiled=True)  # (b, s)

    s = s_loc * cp
    nh = n // cp
    n_kv_h = n_kv // cp
    g = nh // n_kv_h

    from .flash_attention import flash_attention_supported

    if causal and flash_attention_supported(s, d):
        # after the exchange each device holds the FULL sequence for its
        # head shard — ordinary causal attention, which is exactly the
        # splash kernel's job: O(s·block) score tiles instead of the
        # O(s^2) einsum below, and the same GQA-unrepeated contract
        from .flash_attention import flash_attention_fused

        out = flash_attention_fused(
            qg, kg, vg, seg_full, causal=True, sm_scale=sm_scale
        ).astype(q.dtype)
    else:
        # XLA fallback (non-causal, off-TPU, or unaligned shapes):
        # grouped-query attention with a stable softmax in f32
        qf = qg.astype(jnp.float32).reshape(b, s, n_kv_h, g, d) * sm_scale
        scores = jnp.einsum("bqhgd,bkhd->bhgqk", qf, kg.astype(jnp.float32))
        allowed = seg_full[:, :, None] == seg_full[:, None, :]  # (b, s_q, s_k)
        if causal:
            pos = jnp.arange(s)
            allowed = allowed & (pos[None, None, :] <= pos[None, :, None])
        masked = allowed[:, None, None, :, :]
        scores = jnp.where(masked, scores, _NEG)
        m = scores.max(axis=-1, keepdims=True)
        # fully-masked rows: exp(_NEG - _NEG) would be 1 — the mask kills them
        p = jnp.exp(scores - m) * masked
        l = jnp.maximum(p.sum(axis=-1, keepdims=True), 1e-20)
        out = jnp.einsum("bhgqk,bkhd->bqhgd", p / l, vg.astype(jnp.float32))
        out = out.reshape(b, s, nh, d).astype(q.dtype)

    # all-to-all #2: scatter the sequence back, gather this shard's heads
    return jax.lax.all_to_all(out, axis_name, split_axis=1, concat_axis=2, tiled=True)


def ulysses_attention(
    q: jax.Array,  # (b, s, n, d) GLOBAL logical shapes, context-sharded on s
    k: jax.Array,
    v: jax.Array,
    segment_ids: Optional[jax.Array],
    mesh: Mesh,
    causal: bool = True,
    sm_scale: float = 1.0,
) -> jax.Array:
    """shard_map entry mirroring ``ring_attention``'s contract: shards
    q/k/v over (data, context, model) and runs the head exchange."""
    if segment_ids is None:
        segment_ids = jnp.zeros(q.shape[:2], jnp.int32)

    qkv_spec = P(DATA_AXIS, CONTEXT_AXIS, MODEL_AXIS, None)
    seg_spec = P(DATA_AXIS, CONTEXT_AXIS)

    fn = jax.shard_map(
        partial(
            _ulysses_local,
            axis_name=CONTEXT_AXIS,
            causal=causal,
            sm_scale=sm_scale,
        ),
        mesh=mesh,
        in_specs=(qkv_spec, qkv_spec, qkv_spec, seg_spec),
        out_specs=qkv_spec,
        check_vma=False,
    )
    return fn(q, k, v, segment_ids)
