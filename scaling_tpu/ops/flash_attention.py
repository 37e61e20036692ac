"""Fused flash attention on TPU (Pallas splash-attention kernel).

Replaces the reference's flash-attn CUDA dependency
(reference: src/scaling/core/nn/attention/attention.py:29-36,204-259,
requirements/gpu_optimization.txt). The reference imports the flash-attn
package; the TPU-native equivalent is the splash-attention Pallas kernel
that ships with jax (jax.experimental.pallas.ops.tpu.splash_attention),
driven through this wrapper, which:

- feeds GQA **unrepeated**: q keeps all heads, k/v keep only the kv heads
  (the kernel groups queries internally) — preserving the KV bandwidth and
  memory win that is the point of grouped-query attention, where the
  reference's flash path repeats KV to full head count;
- maps the framework's (batch, seq, heads, head_dim) layout and packed-doc
  ``segment_ids`` (= the reference's ``cumulative_seq_lengths``,
  attention.py:245-258) onto the kernel's (heads, seq, head_dim) +
  SegmentIds API via vmap over batch;
- runs in interpreter mode off-TPU so the flash path stays testable on the
  CPU mesh harness.

Block sizes are 1024/1024 (fastest fwd+bwd in the v5e micro-sweep;
2048-wide blocks exceed VMEM) and snap down to sequence-length divisors.

Local-window heads are fused too (per-head LocalMask in the splash mask
set). Unsupported cases (KV cache decode, attention-score manipulation,
probability dropout, non-causal) stay on the XLA path in
``nn/attention.py`` — mirroring the reference's flash/torch kernel
switch (masked_softmax_config.py:8-37).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from ..obs import count_kernel_build

_MIN_BLOCK = 128


def _block_sizes():
    # 1024/1024 won the v5e fwd+bwd micro-sweep at seq 2048 (8.68ms vs 8.99
    # for 512/512; 2048-wide blocks exceed VMEM and fail to compile)
    return 1024, 1024


def flash_attention_supported(
    seq_len: int, head_dim: int, platform: Optional[str] = None
) -> bool:
    """The splash kernel needs lane-aligned shapes and a real TPU.

    Off-TPU the layer falls back to the XLA path (the reference likewise
    skips flash-attn without a GPU); interpreter-mode testing opts in via
    ``force_flash_interpret()`` around the whole computation.
    """
    if seq_len % _MIN_BLOCK != 0 or head_dim < 64:
        return False
    if _FORCE_INTERPRET:
        return True
    return (platform or jax.default_backend()) == "tpu"


_FORCE_INTERPRET = False


class force_flash_interpret:
    """Context manager: run the splash kernel in interpreter mode and make
    ``flash_attention_supported`` report True off-TPU (tests).

    The kernel is built with ``interpret=True`` directly rather than via
    ``pltpu.force_tpu_interpret_mode`` — the latter's randomized grid
    execution mishandles vmap-extended grids (dimension_semantics stays at
    the kernel's 3 entries while the grid grows a batch dim)."""

    def __enter__(self):
        global _FORCE_INTERPRET
        self._saved = _FORCE_INTERPRET
        _FORCE_INTERPRET = True
        return self

    def __exit__(self, *exc):
        global _FORCE_INTERPRET
        _FORCE_INTERPRET = self._saved
        return False


def _snap_block(block: int, seq_len: int) -> int:
    """Largest multiple of 128 that divides seq_len and is <= block.

    The splash kernel needs block sizes dividing the sequence length; the
    128-alignment gate in ``flash_attention_supported`` guarantees this
    terminates (at 128 in the worst case)."""
    b = min(block, seq_len)
    b -= b % _MIN_BLOCK
    while b > _MIN_BLOCK and seq_len % b != 0:
        b -= _MIN_BLOCK
    return max(b, _MIN_BLOCK)


@functools.lru_cache(maxsize=32)
def _make_kernel(num_q_heads: int, seq_len: int, block_q: int, block_kv: int,
                 interpret: bool, num_local_heads: int = 0,
                 local_window: Optional[int] = None):
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk,
        splash_attention_mask as sm,
    )

    count_kernel_build("splash_attention", interpret)
    bq = _snap_block(block_q, seq_len)
    bkv = _snap_block(block_kv, seq_len)
    # mixed-head masks: leading heads are fully causal, the trailing
    # num_local_heads attend within a backward window (the reference's
    # local-attention heads ride its flash sliding window,
    # attention.py:204-259); masks are per Q head, so GQA grouping is
    # unaffected
    shape = (seq_len, seq_len)
    head_masks = [
        sm.CausalMask(shape) for _ in range(num_q_heads - num_local_heads)
    ] + [
        sm.LocalMask(shape, window_size=(local_window, 0), offset=0)
        for _ in range(num_local_heads)
    ]
    mask = sm.MultiHeadMask(head_masks)
    sizes = sk.BlockSizes(
        block_q=bq, block_kv=bkv, block_kv_compute=bkv,
        block_q_dkv=bq, block_kv_dkv=bkv, block_kv_dkv_compute=bkv,
        block_q_dq=bq, block_kv_dq=bkv,
    )
    return sk.make_splash_mha(
        mask=mask, block_sizes=sizes, head_shards=1, q_seq_shards=1,
        interpret=interpret,
    )


def _tp_shardable(mesh, b: int, n: int, n_kv: int, num_local_heads: int) -> bool:
    """True when the kernel can be shard_map-partitioned over (data, model):
    uniform causal masks (no local heads), heads and batch divisible, and no
    pipe axis in play (inside the spatial pipeline the operands are already
    stage-local and shard_map's replication assumption would be wrong)."""
    from ..topology.topology import DATA_AXIS, MODEL_AXIS, PIPE_AXIS

    if num_local_heads > 0:
        return False
    names = mesh.axis_names
    if MODEL_AXIS not in names or mesh.shape[MODEL_AXIS] <= 1:
        return False
    if PIPE_AXIS in names and mesh.shape[PIPE_AXIS] > 1:
        return False
    mp = mesh.shape[MODEL_AXIS]
    dp = mesh.shape[DATA_AXIS] if DATA_AXIS in names else 1
    return n % mp == 0 and n_kv % mp == 0 and b % max(dp, 1) == 0


def flash_attention_fused(
    q: jax.Array,  # (b, s, n, d)
    k: jax.Array,  # (b, s, n_kv, d)  — UNREPEATED kv heads (GQA-native)
    v: jax.Array,  # (b, s, n_kv, d)
    segment_ids: Optional[jax.Array] = None,  # (b, s) int32 packed-doc ids
    causal: bool = True,
    sm_scale: float = 1.0,
    num_local_heads: int = 0,
    local_window: Optional[int] = None,
    mesh=None,
) -> jax.Array:
    """Block-wise causal attention, O(s) memory; returns (b, s, n, d).

    The trailing ``num_local_heads`` query heads attend only within
    ``local_window`` tokens back (mixed local/global heads)."""
    assert causal, "the flash path is causal-only; XLA handles the rest"
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk,
    )

    b, s, n, d = q.shape
    assert q.shape[1] == k.shape[1] and k.shape[2:] == v.shape[2:]
    block_q, block_kv = _block_sizes()
    # construct (and cache) the kernel outside the enclosing jit trace —
    # its mask-info constants must be concrete, not tracers
    with jax.ensure_compile_time_eval():
        kernel = _make_kernel(
            n, s, block_q, block_kv, _FORCE_INTERPRET,
            num_local_heads, local_window,
        )

    qt = jnp.swapaxes(q, 1, 2) * sm_scale  # (b, n, s, d) pre-scaled
    kt = jnp.swapaxes(k, 1, 2)  # (b, n_kv, s, d)
    vt = jnp.swapaxes(v, 1, 2)
    seg_i32 = (
        segment_ids.astype(jnp.int32)
        if segment_ids is not None
        else jnp.zeros((b, s), jnp.int32)
    )

    def run_local(qq, kk, vv, seg):
        def one(qi, ki, vi, si):
            return kernel(qi, ki, vi, segment_ids=sk.SegmentIds(q=si, kv=si))

        return jax.vmap(one)(qq, kk, vv, seg)

    if mesh is not None and _tp_shardable(mesh, b, n, k.shape[2], num_local_heads):
        # partition the kernel itself: pallas custom calls are opaque to
        # GSPMD, which would otherwise gather heads to every device. With
        # uniform causal masks each model shard runs an identical kernel on
        # its contiguous slice of q (and kv) heads; batch splits over data.
        from jax.sharding import PartitionSpec as P

        from ..topology.topology import DATA_AXIS, MODEL_AXIS

        mp = mesh.shape[MODEL_AXIS]
        with jax.ensure_compile_time_eval():
            shard_kernel = _make_kernel(
                n // mp, s, block_q, block_kv, _FORCE_INTERPRET, 0, None
            )

        def run_shard(qq, kk, vv, seg):
            def one(qi, ki, vi, si):
                return shard_kernel(
                    qi, ki, vi, segment_ids=sk.SegmentIds(q=si, kv=si)
                )

            return jax.vmap(one)(qq, kk, vv, seg)

        qkv_spec = P(DATA_AXIS, MODEL_AXIS, None, None)
        out = jax.shard_map(
            run_shard,
            mesh=mesh,
            in_specs=(qkv_spec, qkv_spec, qkv_spec, P(DATA_AXIS, None)),
            out_specs=qkv_spec,
            check_vma=False,
        )(qt, kt, vt, seg_i32)
    else:
        out = run_local(qt, kt, vt, seg_i32)
    return jnp.swapaxes(out, 1, 2).astype(q.dtype)  # (b, s, n, d)
