"""Grouped matmul: rows sorted by group, each group against its own matrix.

``grouped_matmul(lhs (m, k), rhs (G, k, n), group_sizes (G,))`` multiplies
rows ``[offset[g], offset[g] + group_sizes[g])`` of ``lhs`` with ``rhs[g]``
(``offset`` the running sum of ``group_sizes``): the expert matrices of a
served routed MLP over the tick's assignments sorted by expert
(``nn/moe.py``, "Serving"). A group of no rows is not visited and its matrix
is not read; rows past ``sum(group_sizes)`` belong to no group and come back
UNWRITTEN on the chip (whatever the buffer held) and zero off it: the caller
selects them out, it does not multiply them by zero.

On a TPU this is a Pallas kernel, one grid step a (column tile, group): the
group's ``(k, tn)`` matrix tile arrives through the double-buffered pipeline
(so every matrix is read ONCE a call, in one DMA a step, the next group's in
flight while this one multiplies), ``lhs`` and the output's column tile stay
in VMEM for the whole call, and the group's rows are found through
scalar-prefetched offsets: a loop over windows of ``tm`` rows from the
group's first row (rounded down to the sublane tile), each multiplied whole
and stored under a mask of the rows that are the group's. The library's
kernel (``jax.experimental.pallas.ops.tpu.megablox.gmm``) tiles the ROWS
first and re-reads a matrix for every row tile its group touches; at a
decode tick's ~6 rows a group it reached 76% of the HBM rate (PERF.md, PR
50: step 0). A ``pallas_call`` keeps the caller's ``jax.named_scope`` in its
custom call's ``op_name``; ``jax.lax.ragged_dot`` also lowers to a Mosaic
kernel on the chip but the compiler renames its instructions
(``ragged-dot-none``) and the scope, which the benchmark's readers find the
routed MLP's device time by, is lost.

Off the chip it IS ``jax.lax.ragged_dot`` (a masked dense product on the
CPU: the tier-1 tests tick through it, so it must not be an interpreted
kernel a call); ``interpret=True`` runs the kernel's own arithmetic under the
interpreter. Operands as given, float32 accumulation.

The tiles come from the shapes (``grouped_tiles``), never from a model's
name: see there; a call whose buffers would pass VMEM (many rows of a narrow
``k`` into a wide ``n``) takes a narrower column tile (``fitting_columns``).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..obs import count_kernel_build

_LANES = 128
# rows a window starts on: a bf16 tile's 16 sublanes (a float32's 8 divide it)
_ROW_ALIGN = 16
# VMEM (a v5e has 128 MiB; the compiler's scoped default of 16 is raised to
# what a call's buffers need): what the two buffers of a matrix tile may take
_RHS_VMEM_BYTES = 28 << 20
# and the rows of lhs one call keeps resident (more are cut into calls)
_LHS_VMEM_BYTES = 16 << 20
# what a call's buffers may take of a v5e's 128 MiB in all (``lhs`` once, the
# matrix tile and the output's column tile twice), and what a call that would
# pass it is narrowed to
_CALL_VMEM_BYTES = 126 << 20
_NARROWED_VMEM_BYTES = 96 << 20


def grouped_matmul_interpret(platform: Optional[str] = None) -> Optional[bool]:
    """None off the chip (``ragged_dot`` stands in); on a TPU the kernel is
    always compiled."""
    return False if (platform or jax.default_backend()) == "tpu" else None


def grouped_tiles(m: int, k: int, n: int, groups: int,
                  itemsize: int = 2) -> Tuple[int, int]:
    """``(tm, tn)`` for ``(m, k) x (groups, k, n)``.

    The kernel is bound by the matrices it reads, each once: what a step
    costs beside its DMA is the MXU taking the matrix tile in, which a window
    of up to 128 rows pays once whatever it holds. So ``tm`` is the smallest
    power of two from 32 to 128 that holds twice the mean rows a group (a
    group then seldom needs a second window, and the mask and store of a
    window stay small), and the column tile is the whole width where two
    buffers of ``(k, n)`` fit their share of VMEM (one DMA a group, as long
    as it can be), else the widest lane multiple that does."""
    tm = 32
    while tm < 128 and tm < 2 * m // groups:
        tm *= 2
    tn = n
    if 2 * k * n * itemsize > _RHS_VMEM_BYTES:
        tn = max(_LANES,
                 _RHS_VMEM_BYTES // (2 * k * itemsize) // _LANES * _LANES)
    return tm, tn


def fitting_columns(rows: int, k: int, n: int, tn: int, itemsize: int) -> int:
    """``tn``, or, where ``rows`` of ``lhs``, two ``(k, tn)`` matrix tiles and
    two ``(rows, tn)`` output tiles would not fit VMEM (many rows of a narrow
    ``k`` into a wide ``n``: 5,376 x 1,536 into 5,120), the lane multiple that
    cuts ``n`` into the fewest equal column tiles that do. A matrix is still
    read once, a column tile a step."""
    def need(tn):
        return itemsize * (rows * k + 2 * tn * (k + rows))

    if need(tn) <= _CALL_VMEM_BYTES:
        return tn
    widest = (_NARROWED_VMEM_BYTES - itemsize * rows * k) // (
        2 * itemsize * (k + rows))
    tiles = -(-n // max(widest, _LANES))
    return -(-n // tiles // _LANES) * _LANES


def _kernel(gid_ref, start_ref, end_ref, lhs_ref, rhs_ref, out_ref, *,
            tm: int, transposed: bool):
    from jax.experimental import pallas as pl

    del gid_ref  # the matrix's index map reads it
    step = pl.program_id(1)
    start, end = start_ref[step], end_ref[step]
    m = lhs_ref.shape[0]
    first = start // _ROW_ALIGN * _ROW_ALIGN
    windows = jnp.where(end > start, (end - first + tm - 1) // tm, 0)
    dims = (((1,), (1,)), ((), ())) if transposed else (((1,), (0,)), ((), ()))

    def window(w, carry):
        # the last window is pulled back inside the buffer: a row is the
        # group's by its index, whichever window meets it
        base = pl.multiple_of(jnp.minimum(first + w * tm, m - tm), _ROW_ALIGN)
        rows = pl.ds(base, tm)
        acc = jax.lax.dot_general(
            lhs_ref[rows, :], rhs_ref[...], dims,
            preferred_element_type=jnp.float32)
        index = base + jax.lax.broadcasted_iota(jnp.int32, acc.shape, 0)
        mine = (index >= start) & (index < end)
        out_ref[rows, :] = jnp.where(
            mine, acc.astype(out_ref.dtype), out_ref[rows, :])
        return carry

    jax.lax.fori_loop(0, windows, window, 0)


@functools.partial(
    jax.jit, static_argnames=("tiles", "transposed", "interpret"))
def _grouped_call(lhs, rhs, sizes, *, tiles, transposed, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    tm, tn = tiles
    m, k = lhs.shape
    groups = rhs.shape[0]
    n = rhs.shape[1] if transposed else rhs.shape[2]
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    # a step's matrix: its group's, or, where that has no rows, the last
    # one's before it that has (the first that has, before any): the block
    # index then stays and the pipeline fetches nothing for the step
    gid = jax.lax.cummax(
        jnp.where(sizes > 0, jnp.arange(groups, dtype=jnp.int32), -1))
    gid = jnp.where(gid < 0, jnp.argmax(sizes > 0).astype(jnp.int32), gid)
    if transposed:
        rhs_spec = pl.BlockSpec(
            (None, tn, k), lambda j, s, gid, *_: (gid[s], j, 0))
    else:
        rhs_spec = pl.BlockSpec(
            (None, k, tn), lambda j, s, gid, *_: (gid[s], 0, j))
    # lhs once, the matrix tile and the output's column tile twice, a
    # window's float32 product and its stored copy; half as much again for
    # what the compiler keeps beside them
    need = (m * k * lhs.dtype.itemsize + 2 * k * tn * rhs.dtype.itemsize
            + 2 * m * tn * lhs.dtype.itemsize + 8 * tm * tn)
    return pl.pallas_call(
        functools.partial(_kernel, tm=tm, transposed=transposed),
        out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(pl.cdiv(n, tn), groups),
            in_specs=[
                pl.BlockSpec((m, k), lambda j, s, *_: (0, 0),
                             pipeline_mode=pl.Buffered(1)),
                rhs_spec,
            ],
            out_specs=pl.BlockSpec((m, tn), lambda j, s, *_: (0, j)),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=max(16 << 20, need * 3 // 2)),
        interpret=interpret,
        name="grouped_matmul",
    )(gid, starts, ends, lhs, rhs)


def grouped_matmul(
    lhs: jax.Array,            # (m, k), rows sorted by group
    rhs: jax.Array,            # (G, k, n)
    group_sizes: jax.Array,    # (G,) int32, sum <= m
    *,
    interpret: Optional[bool] = None,
    tiles: Optional[Tuple[int, int]] = None,
) -> jax.Array:
    """``(m, n)`` in ``lhs.dtype``: module docstring. ``tiles``: ``(tm,
    tn)`` in place of the shapes' (a sweep's, a test's)."""
    if interpret is None:
        interpret = grouped_matmul_interpret()
    if interpret is None:
        return jax.lax.ragged_dot(
            lhs, rhs, group_sizes, preferred_element_type=lhs.dtype)
    m, k = lhs.shape
    groups, _, n = rhs.shape
    count_kernel_build("grouped_matmul", interpret)
    tm, tn = tiles or grouped_tiles(m, k, n, groups, lhs.dtype.itemsize)
    pad = -m % tm  # none at a serving tick's widths
    # rows one call keeps in VMEM: all of a serving tick's; a longer buffer
    # (a whole prompt's assignments) is cut into calls, each with the rows
    # of every group that fall inside it
    block = max(tm, _LHS_VMEM_BYTES // (k * lhs.dtype.itemsize) // tm * tm)
    if tiles is None:
        tn = fitting_columns(min(m + pad, block), k, n, tn, lhs.dtype.itemsize)
    # the chip keeps an array whose minor dimension is no lane multiple with
    # a dimension that is one as its minor (Nemotron's (64, 2688, 1856): the
    # 2688 lie along the lanes), and a kernel takes its operands row-major:
    # handed as it is, such a matrix is copied whole every call (0.64 GB a
    # layer). Its transpose is that same memory read row-major, and the
    # kernel contracts the minor dimensions of both sides instead
    transposed = n % _LANES != 0 and k % _LANES == 0
    if transposed:
        rhs = rhs.swapaxes(1, 2)
    call = functools.partial(
        _grouped_call, tiles=(tm, tn),
        transposed=transposed, interpret=interpret)
    sizes = group_sizes.astype(jnp.int32)
    if pad:
        lhs = jnp.pad(lhs, ((0, pad), (0, 0)))
    if m + pad <= block:
        out = call(lhs, rhs, sizes)
    else:
        ends = jnp.cumsum(sizes)
        starts = ends - sizes
        out = jnp.concatenate([
            call(lhs[lo:lo + block], rhs,
                 jnp.clip(ends, lo, lo + block) - jnp.clip(starts, lo, lo + block))
            for lo in range(0, m + pad, block)])
    return out[:m] if pad else out
