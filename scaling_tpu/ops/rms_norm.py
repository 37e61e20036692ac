"""Fused RMSNorm on TPU (Pallas).

The reference's ``fused`` LayerNormOptimizationType selects flash-attn's
CUDA fused rms_norm (reference: src/scaling/core/nn/norm/rms_norm.py:11-14,55,
layernorm_config.py). This is the TPU-native equivalent: one VMEM pass for
the forward (fp32 statistics computed in-register, bf16 in/out) and one for
the backward, with the weight gradient accumulated across the sequential
TPU grid instead of a separate reduction kernel.

Formulas (x, g row vectors, w the gain, r = rsqrt(mean(x^2) + eps)):
  y  = x * r * w
  gw = g * w
  dx = r * gw - x * r^3 * mean(gw * x)
  dw = sum_rows(g * x * r)

Off-TPU the layer keeps the plain XLA path; interpreter-mode testing opts
in via ``force_rms_interpret`` (same pattern as ops/flash_attention.py).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..obs import count_kernel_build

_LANES = 128
_DEFAULT_BLOCK_ROWS = 256

_FORCE_INTERPRET = False


class force_rms_interpret:
    """Context manager: run the fused RMSNorm in interpreter mode and make
    ``rms_norm_fused_supported`` report True off-TPU (tests)."""

    def __enter__(self):
        global _FORCE_INTERPRET
        self._saved = _FORCE_INTERPRET
        _FORCE_INTERPRET = True
        return self

    def __exit__(self, *exc):
        global _FORCE_INTERPRET
        _FORCE_INTERPRET = self._saved
        return False


def rms_norm_fused_supported(dim: int, platform: Optional[str] = None) -> bool:
    """Lane-aligned hidden dim on a real TPU (or forced interpreter mode)."""
    if dim % _LANES != 0:
        return False
    if _FORCE_INTERPRET:
        return True
    return (platform or jax.default_backend()) == "tpu"


def _block_rows(n: int) -> int:
    b = min(_DEFAULT_BLOCK_ROWS, n)
    while b > 8 and n % b != 0:
        b //= 2
    return b if n % b == 0 else 1


def _fwd_kernel(eps, x_ref, w_ref, y_ref):
    x = x_ref[:].astype(jnp.float32)
    r = jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    y = x * r * w_ref[:].astype(jnp.float32)
    y_ref[:] = y.astype(y_ref.dtype)


def _bwd_kernel(eps, x_ref, w_ref, g_ref, dx_ref, dw_ref):
    # r is recomputed rather than saved: a 1-D (n,) rstd residual blocked
    # (br,) trips Mosaic's layout verifier on real TPUs (XLA tiles the full
    # array, Mosaic the block — "XLA layout {0:T(512)} does not match
    # Mosaic layout {0:T(256)}"), and one fused mean-of-squares per row
    # block is cheaper than the HBM round-trip anyway
    x = x_ref[:].astype(jnp.float32)
    g = g_ref[:].astype(jnp.float32)
    w = w_ref[:].astype(jnp.float32)
    r = jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    gw = g * w
    mean_gwx = jnp.mean(gw * x, axis=-1, keepdims=True)
    dx = r * gw - x * (r**3) * mean_gwx
    dx_ref[:] = dx.astype(dx_ref.dtype)
    # dw accumulates across the sequential TPU grid
    @pl.when(pl.program_id(0) == 0)
    def _init():
        dw_ref[:] = jnp.zeros_like(dw_ref)

    dw_ref[:] += jnp.sum(g * x * r, axis=0).astype(dw_ref.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def rms_norm_fused(x: jax.Array, w: jax.Array, eps: float = 1e-5) -> jax.Array:
    """y = x * rsqrt(mean(x^2, -1) + eps) * w over the last dim, fused."""
    return _rms_fwd_impl(x, w, eps)


def _rows(x: jax.Array) -> jax.Array:
    return x.reshape(-1, x.shape[-1])


def _rms_fwd_impl(x: jax.Array, w: jax.Array, eps: float):
    orig_shape = x.shape
    x2 = _rows(x)
    n, d = x2.shape
    br = _block_rows(n)
    count_kernel_build("rms_norm", _FORCE_INTERPRET)
    y = pl.pallas_call(
        functools.partial(_fwd_kernel, eps),
        grid=(n // br,),
        in_specs=[
            pl.BlockSpec((br, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((br, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, d), x.dtype),
        interpret=_FORCE_INTERPRET,
    )(x2, w)
    return y.reshape(orig_shape)


def _rms_fwd(x, w, eps):
    return _rms_fwd_impl(x, w, eps), (x, w)


def _rms_bwd(eps, res, g):
    x, w = res
    orig_shape = x.shape
    x2, g2 = _rows(x), _rows(g)
    n, d = x2.shape
    br = _block_rows(n)
    dx, dw = pl.pallas_call(
        functools.partial(_bwd_kernel, eps),
        grid=(n // br,),
        in_specs=[
            pl.BlockSpec((br, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
            pl.BlockSpec((br, d), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((br, d), lambda i: (i, 0)),
            # every grid step maps the same (d,) block: sequential accumulate
            pl.BlockSpec((d,), lambda i: (0,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, d), x.dtype),
            jax.ShapeDtypeStruct((d,), jnp.float32),
        ],
        interpret=_FORCE_INTERPRET,
    )(x2, w, g2)
    return dx.reshape(orig_shape), dw.astype(w.dtype)


rms_norm_fused.defvjp(_rms_fwd, _rms_bwd)


def rms_norm_fused_shardable(mesh, x_shape) -> bool:
    """True when the kernel can be shard_map-partitioned on this mesh.

    The norm is row-independent, so a (b, s, h) activation partitions over
    batch (data axis) and sequence (context and model axes — the model-axis
    split IS sequence parallelism, matching where SP puts the norm anyway;
    reference: the SP layout notes in nn/norm.py). Not applicable inside a
    spatial pipeline (operands there are stage-local, same restriction as
    ops/flash_attention.py:_tp_shardable) or when dims don't divide."""
    from ..topology.topology import CONTEXT_AXIS, DATA_AXIS, MODEL_AXIS, PIPE_AXIS

    if len(x_shape) != 3:
        return False
    names = mesh.axis_names
    if PIPE_AXIS in names and mesh.shape[PIPE_AXIS] > 1:
        return False
    dp = mesh.shape[DATA_AXIS] if DATA_AXIS in names else 1
    seq_div = 1
    for a in (CONTEXT_AXIS, MODEL_AXIS):
        if a in names:
            seq_div *= mesh.shape[a]
    b, s, _ = x_shape
    return b % max(dp, 1) == 0 and s % seq_div == 0


def rms_norm_fused_sharded(
    x: jax.Array, w: jax.Array, eps: float, mesh
) -> jax.Array:
    """shard_map'd fused RMSNorm: every device runs the Pallas kernel on its
    local rows with the replicated gain; shard_map's transpose inserts the
    psum that reduces the per-shard weight grads (the manual analogue of
    GSPMD's backward collective for the XLA path)."""
    from jax.sharding import PartitionSpec as P

    from ..topology.topology import CONTEXT_AXIS, DATA_AXIS, MODEL_AXIS

    assert rms_norm_fused_shardable(mesh, x.shape)
    names = mesh.axis_names
    seq_axes = tuple(
        a for a in (CONTEXT_AXIS, MODEL_AXIS) if a in names and mesh.shape[a] > 1
    )
    spec = P(
        DATA_AXIS if DATA_AXIS in names and mesh.shape[DATA_AXIS] > 1 else None,
        seq_axes if seq_axes else None,
        None,
    )
    return jax.shard_map(
        lambda xx, ww: rms_norm_fused(xx, ww, eps),
        mesh=mesh,
        in_specs=(spec, P()),
        out_specs=spec,
        check_vma=False,
    )(x, w)
