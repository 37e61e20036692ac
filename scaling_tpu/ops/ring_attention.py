"""Ring attention: context parallelism over the ``context`` mesh axis.

A capability beyond the reference, which bounds trained context by
per-device memory (SURVEY §5: "no ring attention, context parallelism,
blockwise attention, or Ulysses"). Design (Ring Attention with Blockwise
Transformers, Liu et al. 2023, expressed TPU-natively):

- activations are sharded along the sequence dim over the ``context`` axis;
- each device keeps its Q shard resident and computes attention against one
  K/V block at a time, merging with the online-softmax recurrence;
- K/V blocks (with their segment ids) rotate around the ring via
  ``lax.ppermute`` — ICI neighbour exchange — inside a ``lax.scan``;
- causal masking uses absolute sequence indices derived from each block's
  ring offset, so packing (segment ids) and causality behave exactly like
  the single-device path;
- within each ring step the K/V block is consumed in CHUNKS with the same
  online-softmax recurrence, so the materialized score tile is
  (s_loc x chunk), never (s_loc x s_loc);
- the backward pass is a CUSTOM VJP (the flash-attention recipe, not
  autodiff of the forward scan): forward saves only the output and the
  per-query logsumexp, and the gradient runs a second ring pass that
  recomputes each (s_loc x chunk) probability tile from them, with dK/dV
  accumulators rotating alongside their K/V blocks. Autodiff of the scan
  would stack per-chunk residuals — O(s_loc^2) per layer — exactly the
  memory the chunking removes.

Peak memory per device, forward AND backward: O(s/cp) for
Q/K/V/O/dQ/dK/dV + one rotating K/V (+dK/dV) block + one (s_loc x chunk)
score tile — sequence length scales linearly with the ring size.
"""

from __future__ import annotations

import functools
import warnings
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from ..topology.topology import CONTEXT_AXIS, DATA_AXIS, MODEL_AXIS

_NEG = -1e9
_DEFAULT_KV_CHUNK = 1024


def _kv_chunk(s_loc: int, requested: Optional[int] = None) -> int:
    """Largest divisor of ``s_loc`` at most the requested chunk (default
    _DEFAULT_KV_CHUNK): the score tile is (s_loc x chunk), so the chunk
    bounds per-step memory while the divisor constraint keeps the inner
    scan shape static. When the best divisor is a sliver (< 128 — e.g. a
    prime s_loc), one full tile wins: an s_loc-step scan of 1-wide
    einsums would blow up compile and step time by orders of magnitude
    for a memory bound nobody asked for."""
    cap = min(requested or _DEFAULT_KV_CHUNK, s_loc)
    for c in range(cap, 0, -1):
        if s_loc % c == 0:
            if c >= min(128, cap):
                return c
            break
    if s_loc > cap:
        # the memory bound the chunking exists for is silently gone: the
        # score tile regresses to (s_loc x s_loc). Long-context configs
        # must hear about it — pick a per-device sequence length with a
        # divisor in [128, chunk] to restore the bound.
        warnings.warn(
            f"ring attention: per-device sequence length {s_loc} has no "
            f"divisor in [{min(128, cap)}, {cap}]; falling back to one "
            f"full ({s_loc} x {s_loc}) score tile per step, losing the "
            f"chunked memory bound"
        )
    return s_loc


def _chunk_mask(seg_q, seg_c, q_pos, k_pos_c, causal):
    """(b, s_q, chunk) bool — packing + causality for one K/V chunk."""
    allowed = seg_q[:, :, None] == seg_c[:, None, :]
    if causal:
        allowed = allowed & (k_pos_c[None, None, :] <= q_pos[None, :, None])
    return allowed


def _split_chunks(x, n_chunks, chunk):
    """(b, s_loc, ...) -> (n_chunks, b, chunk, ...) for scan xs."""
    b = x.shape[0]
    return x.reshape(b, n_chunks, chunk, *x.shape[2:]).swapaxes(0, 1)


def _ring_fwd_pass(q, k, v, seg, axis_name, causal, sm_scale, kv_chunk):
    """Blockwise forward: returns (out, lse) with lse = m + log(l), the
    only residuals the backward needs."""
    ring = jax.lax.psum(1, axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    b, s_loc, n, d = q.shape
    n_kv = k.shape[2]
    g = n // n_kv  # query heads per kv head; rotating unrepeated K/V keeps
    # the ring's ICI traffic at 1/g of the repeated layout
    chunk = _kv_chunk(s_loc, kv_chunk)
    n_chunks = s_loc // chunk

    q_pos = my_idx * s_loc + jnp.arange(s_loc)
    qf = q.astype(jnp.float32).reshape(b, s_loc, n_kv, g, d) * sm_scale

    def step(carry, _):
        m, l, acc, k_blk, v_blk, seg_blk, owner = carry
        k_pos0 = owner * s_loc

        def inner(c2, xs):
            m, l, acc = c2
            k_c, v_c, seg_c, ci = xs
            k_pos_c = k_pos0 + ci * chunk + jnp.arange(chunk)
            s = jnp.einsum("bqhgd,bkhd->bhgqk", qf, k_c.astype(jnp.float32))
            allowed = _chunk_mask(seg, seg_c, q_pos, k_pos_c, causal)
            masked = allowed[:, None, None, :, :]
            s = jnp.where(masked, s, _NEG)
            m_new = jnp.maximum(m, s.max(axis=-1))  # (b, h, g, sq)
            # explicit zeroing: for a fully-masked chunk s == m_new == _NEG
            # and exp(0) would be 1 — the mask, not the exp, kills them
            p = jnp.exp(s - m_new[..., None]) * masked
            correction = jnp.exp(m - m_new)
            l_new = l * correction + p.sum(axis=-1)
            acc_new = (
                acc * jnp.moveaxis(correction, 3, 1)[..., None]
                + jnp.einsum("bhgqk,bkhd->bqhgd", p, v_c.astype(jnp.float32))
            )
            return (m_new, l_new, acc_new), None

        (m, l, acc), _ = jax.lax.scan(
            inner,
            (m, l, acc),
            (
                _split_chunks(k_blk, n_chunks, chunk),
                _split_chunks(v_blk, n_chunks, chunk),
                _split_chunks(seg_blk, n_chunks, chunk),
                jnp.arange(n_chunks),
            ),
        )
        # rotate the K/V block to the next ring neighbour
        perm = [(i, (i + 1) % ring) for i in range(ring)]
        k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
        v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
        seg_blk = jax.lax.ppermute(seg_blk, axis_name, perm)
        owner = jax.lax.ppermute(owner, axis_name, perm)
        return (m, l, acc, k_blk, v_blk, seg_blk, owner), None

    m0 = jnp.full((b, n_kv, g, s_loc), _NEG, jnp.float32)
    l0 = jnp.zeros((b, n_kv, g, s_loc), jnp.float32)
    acc0 = jnp.zeros((b, s_loc, n_kv, g, d), jnp.float32)
    carry = (m0, l0, acc0, k, v, seg, my_idx)
    (m, l, acc, *_), _ = jax.lax.scan(step, carry, None, length=ring)
    l_safe = jnp.maximum(l, 1e-20)
    out = acc / jnp.moveaxis(l_safe, 3, 1)[..., None]
    lse = m + jnp.log(l_safe)  # (b, h, g, sq)
    return out.reshape(b, s_loc, n, d).astype(q.dtype), lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _ring_core(q, k, v, seg, axis_name, causal, sm_scale, kv_chunk):
    out, _ = _ring_fwd_pass(q, k, v, seg, axis_name, causal, sm_scale, kv_chunk)
    return out


def _ring_core_fwd(q, k, v, seg, axis_name, causal, sm_scale, kv_chunk):
    out, lse = _ring_fwd_pass(q, k, v, seg, axis_name, causal, sm_scale, kv_chunk)
    return out, (q, k, v, seg, out, lse)


def _ring_core_bwd(axis_name, causal, sm_scale, kv_chunk, res, dout):
    """Second ring pass: probability tiles recompute from (q, k_blk, lse);
    dK/dV accumulators rotate WITH their K/V blocks, so after a full cycle
    every block arrives home carrying every device's contribution.

    Flash backward identities (P the normalized probs):
      dV_j  = sum_i P_ij dO_i
      dP_ij = dO_i · V_j
      dS_ij = P_ij (dP_ij - delta_i),  delta_i = dO_i · O_i
      dQ_i  = sm_scale * sum_j dS_ij K_j ;  dK_j = sum_i dS_ij Q_i*sm_scale
    """
    q, k, v, seg, out, lse = res
    ring = jax.lax.psum(1, axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    b, s_loc, n, d = q.shape
    n_kv = k.shape[2]
    g = n // n_kv
    chunk = _kv_chunk(s_loc, kv_chunk)
    n_chunks = s_loc // chunk

    q_pos = my_idx * s_loc + jnp.arange(s_loc)
    qf = q.astype(jnp.float32).reshape(b, s_loc, n_kv, g, d) * sm_scale
    do = dout.astype(jnp.float32).reshape(b, s_loc, n_kv, g, d)
    of = out.astype(jnp.float32).reshape(b, s_loc, n_kv, g, d)
    # delta_i = rowsum(dO * O), laid out like lse: (b, h, g, sq)
    delta = jnp.moveaxis(jnp.sum(do * of, axis=-1), 1, 3)

    def step(carry, _):
        dq, k_blk, v_blk, dk_blk, dv_blk, seg_blk, owner = carry
        k_pos0 = owner * s_loc

        def inner(c2, xs):
            dq = c2
            k_c, v_c, seg_c, ci = xs
            k_pos_c = k_pos0 + ci * chunk + jnp.arange(chunk)
            s = jnp.einsum("bqhgd,bkhd->bhgqk", qf, k_c.astype(jnp.float32))
            allowed = _chunk_mask(seg, seg_c, q_pos, k_pos_c, causal)
            masked = allowed[:, None, None, :, :]
            # lse is a true per-query constant, so P normalizes directly;
            # fully-masked rows have lse = NEG + log(eps) — the mask wins
            p = jnp.exp(jnp.where(masked, s, _NEG) - lse[..., None]) * masked
            dp = jnp.einsum("bqhgd,bkhd->bhgqk", do, v_c.astype(jnp.float32))
            ds = p * (dp - delta[..., None])
            dq = dq + jnp.einsum("bhgqk,bkhd->bqhgd", ds, k_c.astype(jnp.float32))
            dk_c = jnp.einsum("bhgqk,bqhgd->bkhd", ds, qf)
            dv_c = jnp.einsum("bhgqk,bqhgd->bkhd", p, do)
            return dq, (dk_c, dv_c)

        dq, (dk_cs, dv_cs) = jax.lax.scan(
            inner,
            dq,
            (
                _split_chunks(k_blk, n_chunks, chunk),
                _split_chunks(v_blk, n_chunks, chunk),
                _split_chunks(seg_blk, n_chunks, chunk),
                jnp.arange(n_chunks),
            ),
        )
        # (n_chunks, b, chunk, h, d) -> (b, s_loc, h, d)
        dk_blk = dk_blk + dk_cs.swapaxes(0, 1).reshape(b, s_loc, n_kv, d)
        dv_blk = dv_blk + dv_cs.swapaxes(0, 1).reshape(b, s_loc, n_kv, d)
        perm = [(i, (i + 1) % ring) for i in range(ring)]
        k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
        v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
        dk_blk = jax.lax.ppermute(dk_blk, axis_name, perm)
        dv_blk = jax.lax.ppermute(dv_blk, axis_name, perm)
        seg_blk = jax.lax.ppermute(seg_blk, axis_name, perm)
        owner = jax.lax.ppermute(owner, axis_name, perm)
        return (dq, k_blk, v_blk, dk_blk, dv_blk, seg_blk, owner), None

    dq0 = jnp.zeros((b, s_loc, n_kv, g, d), jnp.float32)
    dkv0 = jnp.zeros((b, s_loc, n_kv, d), jnp.float32)
    carry = (dq0, k, v, dkv0, dkv0, seg, my_idx)
    (dq, _, _, dk, dv, *_), _ = jax.lax.scan(step, carry, None, length=ring)
    dq = (dq * sm_scale).reshape(b, s_loc, n, d).astype(q.dtype)
    dseg = np.zeros(seg.shape, dtype=jax.dtypes.float0)
    return dq, dk.astype(k.dtype), dv.astype(v.dtype), dseg


_ring_core.defvjp(_ring_core_fwd, _ring_core_bwd)


def _ring_attention_local(
    q: jax.Array,  # (b, s_loc, n_loc, d) — this device's shards
    k: jax.Array,  # (b, s_loc, n_kv_loc, d) — UNREPEATED kv heads (GQA)
    v: jax.Array,
    seg: jax.Array,  # (b, s_loc) int32 packed-doc ids
    *,
    axis_name: str,
    causal: bool,
    sm_scale: float,
    kv_chunk: Optional[int] = None,
) -> jax.Array:
    return _ring_core(q, k, v, seg, axis_name, causal, sm_scale, kv_chunk)


def ring_attention(
    q: jax.Array,  # (b, s, n, d) GLOBAL logical shapes, context-sharded on s
    k: jax.Array,
    v: jax.Array,
    segment_ids: Optional[jax.Array],
    mesh: Mesh,
    causal: bool = True,
    sm_scale: float = 1.0,
    kv_chunk: Optional[int] = None,
) -> jax.Array:
    """shard_map entry: shards q/k/v over (data, context, model) and runs the
    ring. Requires seq divisible by the context axis size. ``kv_chunk``
    (STATIC — part of the trace, not a baked-in global) caps the inner
    score-tile width; default _DEFAULT_KV_CHUNK."""
    if segment_ids is None:
        segment_ids = jnp.zeros(q.shape[:2], jnp.int32)

    qkv_spec = P(DATA_AXIS, CONTEXT_AXIS, MODEL_AXIS, None)
    seg_spec = P(DATA_AXIS, CONTEXT_AXIS)

    fn = jax.shard_map(
        partial(
            _ring_attention_local,
            axis_name=CONTEXT_AXIS,
            causal=causal,
            sm_scale=sm_scale,
            kv_chunk=kv_chunk,
        ),
        mesh=mesh,
        in_specs=(qkv_spec, qkv_spec, qkv_spec, seg_spec),
        out_specs=qkv_spec,
        check_vma=False,
    )
    return fn(q, k, v, segment_ids)
