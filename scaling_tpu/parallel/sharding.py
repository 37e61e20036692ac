"""Sharding helpers: constraint-driven tensor parallelism.

The reference implements TP with hand-written autograd collectives
(reference: src/scaling/core/nn/linear/utils.py:20-361). On TPU the idiomatic
equivalent is GSPMD: parameters and activations carry ``PartitionSpec``
annotations and XLA inserts the all-reduce/all-gather/reduce-scatter pairs —
including the transposed collectives for the backward pass — choosing
ICI-friendly schedules. These helpers apply constraints only when a mesh with
the named axis is active, so the same layer code runs on a single device.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops.row_scatter import take_rows
from ..topology.topology import CONTEXT_AXIS, DATA_AXIS, MODEL_AXIS, PIPE_AXIS


def _axis_in_mesh(mesh: Optional[Mesh], axis: str) -> bool:
    return mesh is not None and axis in mesh.axis_names


def constrain(x: jax.Array, mesh: Optional[Mesh], *spec) -> jax.Array:
    """with_sharding_constraint that degrades to identity without a mesh."""
    if mesh is None:
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(*spec)))


def spec_with_data_axis(spec, shape, dp: int):
    """Extend a partition spec with DATA_AXIS on the LAST free dim whose
    size is divisible by ``dp`` — the ZeRO/FSDP sharding rule shared by the
    optimizer's master/moment placement (stage 1) and the compute params
    themselves (stage 3). Returns the spec unchanged when the data axis is
    already consumed (e.g. expert-parallel params) or no dim divides.

    Last-free-dim (the innermost weight dim) keeps per-layer slices of
    stage-stacked pipeline bodies contiguous on their (pipe, layer)
    leading dims, so GSPMD's per-use all-gather stays a plain collective
    rather than a strided reshard."""
    spec = list(spec)
    while len(spec) < len(shape):
        spec.append(None)
    used = {
        a
        for entry in spec
        if entry is not None
        for a in (entry if isinstance(entry, tuple) else (entry,))
    }
    if dp <= 1 or DATA_AXIS in used:
        return tuple(spec)
    for d in reversed(range(len(shape))):
        if spec[d] is None and shape[d] % dp == 0 and shape[d] > 0:
            spec[d] = DATA_AXIS
            break
    return tuple(spec)


def _seq_axis(mesh: Optional[Mesh]):
    """Sequence dims shard over the context axis when it exists (ring
    attention context parallelism); None otherwise."""
    return CONTEXT_AXIS if _axis_in_mesh(mesh, CONTEXT_AXIS) else None


def shard_batch(x: jax.Array, mesh: Optional[Mesh]) -> jax.Array:
    """(b, s, ...) activation: batch over data, sequence over context."""
    if not _axis_in_mesh(mesh, DATA_AXIS):
        return x
    seq = [_seq_axis(mesh)] if x.ndim > 1 else []
    return constrain(x, mesh, DATA_AXIS, *seq, *([None] * (x.ndim - 1 - len(seq))))


def shard_activation_tp(x: jax.Array, mesh: Optional[Mesh]) -> jax.Array:
    """(b, s, h) activation inside a TP region: h sharded over model axis."""
    if not _axis_in_mesh(mesh, MODEL_AXIS):
        return x
    return constrain(x, mesh, DATA_AXIS, _seq_axis(mesh), MODEL_AXIS)


def _vocab_axes(mesh: Optional[Mesh]) -> tuple:
    """The mesh axes an untied head's vocabulary is split over: the model
    axis and, where stages exist, the pipe axis before it (``ParallelModule``
    lifts an edge layer's model-parallel dim over ``(pipe, model)``)."""
    if not _axis_in_mesh(mesh, MODEL_AXIS):
        return ()
    staged = _axis_in_mesh(mesh, PIPE_AXIS) and mesh.shape[PIPE_AXIS] > 1
    return (PIPE_AXIS, MODEL_AXIS) if staged else (MODEL_AXIS,)


def vocab_shards(mesh: Optional[Mesh]) -> int:
    """Over how many devices :func:`shard_logits` splits the vocabulary."""
    return math.prod(mesh.shape[axis] for axis in _vocab_axes(mesh))


def shard_logits(x: jax.Array, mesh: Optional[Mesh]) -> jax.Array:
    """(b, s, vocab) logits as the head's column-parallel matmul makes them:
    the vocabulary sharded as the head's weight has it, never gathered."""
    axes = _vocab_axes(mesh)
    if not axes:
        return x
    return constrain(x, mesh, DATA_AXIS, _seq_axis(mesh), axes)


def shard_activation_replicated_h(x: jax.Array, mesh: Optional[Mesh]) -> jax.Array:
    """(b, s, h) activation with h replicated (after TP all-reduce)."""
    if mesh is None:
        return x
    return constrain(x, mesh, DATA_AXIS, _seq_axis(mesh), None)


def shard_activation_sp(x: jax.Array, mesh: Optional[Mesh]) -> jax.Array:
    """(b, s, h) activation between TP regions under sequence parallelism:
    sequence sharded over the model axis (Megatron-style SP)."""
    if not _axis_in_mesh(mesh, MODEL_AXIS):
        return x
    seq = _seq_axis(mesh)
    sp_axes = (seq, MODEL_AXIS) if seq else MODEL_AXIS
    return constrain(x, mesh, DATA_AXIS, sp_axes, None)


def sp_boundary_is_manual(x_shape: tuple, mesh: Optional[Mesh]) -> bool:
    """Whether a ``(b, s, ...)`` activation crosses the boundary of a TP region
    under sequence parallelism through :func:`sp_enter` / :func:`sp_leave`:
    a model axis wider than 1 that alone shards the sequence (no context
    axis in play), no pipe axis in play (inside the spatial pipeline the
    operands are already stage-local, as for ops/flash_attention.py's
    ``_tp_shardable``), batch and sequence divisible. Everything else keeps
    :func:`shard_activation_sp`'s constraint: the layouts between regions are
    the same, so entering by one form and leaving by the other is legal."""
    if not _axis_in_mesh(mesh, MODEL_AXIS) or mesh.shape[MODEL_AXIS] <= 1:
        return False
    if any(_axis_in_mesh(mesh, axis) and mesh.shape[axis] > 1
           for axis in (PIPE_AXIS, CONTEXT_AXIS)):
        return False
    if len(x_shape) != 3:
        return False
    return (x_shape[0] % mesh.shape[DATA_AXIS] == 0
            and x_shape[1] % mesh.shape[MODEL_AXIS] == 0)


def _rows_first(x: jax.Array) -> jax.Array:
    """Local ``(b, s, h)`` -> ``(s, b * h)``: the sequence LEADS a 2-D array,
    the one form in which this TPU compiler keeps a reduce-scatter (and its
    transpose, the all-gather) what it is. Along dimension 1 of the 3-D
    array it rewrites a written-out ``psum_scatter`` to all-reduce + slice
    (tests/core/test_chip_compile.py,
    ``test_pharia_train_step_crosses_tp_regions_by_reduce_scatter``). At
    local batch 1 this is a reshape and moves nothing."""
    b, s, h = x.shape
    return jnp.swapaxes(x, 0, 1).reshape(s, b * h)


def _batch_first(rows: jax.Array, b: int) -> jax.Array:
    """``(s, b * h)`` -> ``(b, s, h)``, :func:`_rows_first`'s inverse."""
    s = rows.shape[0]
    return jnp.swapaxes(rows.reshape(s, b, -1), 0, 1)


def sp_enter(x: jax.Array, weight: jax.Array, mesh: Mesh) -> jax.Array:
    """ENTER a TP region under sequence parallelism: ``x`` ``(b, s, h)`` in
    the SP layout (sequence over the model axis) against the column-parallel
    ``weight`` ``(h, n)``; returns ``x @ weight`` ``(b, s, n)`` in the TP
    layout (:func:`shard_activation_tp`'s).

    The all-gather of the rows over the model axis and the matmul it feeds
    are ONE manual region, so that the backward is the local ``dy @ w^T`` and
    then a reduce-scatter. A gather in a region of its own, with the matmul
    left to GSPMD, keeps GSPMD's backward all-reduce of the whole activation
    and adds a reduce-scatter behind it. The gathered rows are a saved
    residual (the weight gradient reads them)."""
    assert sp_boundary_is_manual(x.shape, mesh), (x.shape, mesh)

    def region(x, weight):
        rows = jax.lax.all_gather(_rows_first(x), MODEL_AXIS, axis=0, tiled=True)
        return _batch_first(rows, x.shape[0]) @ weight

    return jax.shard_map(
        region, mesh=mesh,
        in_specs=(P(DATA_AXIS, MODEL_AXIS, None), P(None, MODEL_AXIS)),
        out_specs=P(DATA_AXIS, None, MODEL_AXIS),
    )(x, weight)


def sp_leave(x: jax.Array, weight: jax.Array, mesh: Mesh) -> jax.Array:
    """LEAVE a TP region under sequence parallelism: ``x`` ``(b, s, k)`` in the
    TP layout against the row-parallel ``weight`` ``(k, h)``; returns ``x @
    weight`` ``(b, s, h)`` in the SP layout. The local matmul's partial sums
    are reduce-scattered over the leading dimension of their 2-D rows, each
    rank keeping its share of the sequence: half an all-reduce's traffic,
    where a sharding constraint here compiles to the whole all-reduce and a
    slice (:func:`_rows_first`)."""
    assert sp_boundary_is_manual(x.shape, mesh), (x.shape, mesh)

    def region(x, weight):
        rows = jax.lax.psum_scatter(
            _rows_first(x @ weight), MODEL_AXIS, scatter_dimension=0, tiled=True)
        return _batch_first(rows, x.shape[0])

    return jax.shard_map(
        region, mesh=mesh,
        in_specs=(P(DATA_AXIS, None, MODEL_AXIS), P(MODEL_AXIS, None)),
        out_specs=P(DATA_AXIS, MODEL_AXIS, None),
    )(x, weight)


def lookup_on_data_shard(meta, shape: tuple, mesh: Optional[Mesh],
                         gathers_on_entry: bool) -> bool:
    """The ONE place that decides whether a leaf is consumed where ZeRO-1
    keeps it between steps (the masters' placement, the data axis on its
    columns) and never gathered: ``Optimizer.gather_params`` leaves such a
    leaf alone and ``VocabParallelEmbedding`` looks its tokens up through
    :func:`lookup_rows_on_data_shard`, both by this answer.

    All of: the step is entered with the compute copy on the masters' shards
    (``Optimizer.gathers_on_entry``: ZeRO-1 over a data axis wider than 1;
    False wherever no such optimizer built the pass: evaluation, inference,
    every serve program); the leaf is only ever read by row
    (``ParamMeta.row_lookup``: a table that is the head's matrix too still
    needs the gather); no pipe or context axis in play, as for
    :func:`sp_boundary_is_manual`; and the masters' rule really put the data
    axis on the columns of a ``(model, None)`` table (the data axis divides
    them)."""
    if not gathers_on_entry or not meta.row_lookup or mesh is None:
        return False
    if any(_axis_in_mesh(mesh, axis) and mesh.shape[axis] > 1
           for axis in (PIPE_AXIS, CONTEXT_AXIS)):
        return False
    return spec_with_data_axis(
        meta.partition_spec, shape, mesh.shape[DATA_AXIS]
    ) == (MODEL_AXIS, DATA_AXIS)


def lookup_rows_on_data_shard(token_ids: jax.Array, table: jax.Array,
                              mesh: Mesh, to_sp: bool) -> jax.Array:
    """``table[token_ids]`` for ``token_ids`` ``(b, s)`` (batch over the data
    axis) against a ``table`` ``(vocab, h)`` that lies as ZeRO-1's masters do:
    the vocabulary over the model axis, the COLUMNS over the data axis.
    Returns ``(b, s, h)`` with ``h`` whole: in the SP layout where ``to_sp``,
    else replicated over the model axis.

    One manual region. Every chip looks up the tokens of ALL data ranks (their
    ids are gathered: 4 bytes a token) in the columns it holds; a row that
    another model rank holds reads as zeros and its cotangent is dropped; the
    partial rows are summed over the model axis (a reduce-scatter on the
    leading dimension of 2-D rows where the result is sequence parallel:
    :func:`_rows_first`), and an all-to-all over the data axis hands each
    rank ITS tokens' other columns: a few MB of rows cross the links where
    the whole table did. The backward, by transposition: the exchange back,
    the all-gather over the model axis, and ONE sum of every data rank's
    rows into the shard (``ops/row_scatter.py``: a kernel on the chip, where
    XLA's scatter-add is a serial loop over the rows), which is the
    data-reduced gradient already in the masters' placement. A table that
    arrives gathered is sliced locally by ``in_specs`` and gives the same
    rows."""
    mp = mesh.shape[MODEL_AXIS]
    b, s = token_ids.shape
    assert b % mesh.shape[DATA_AXIS] == 0, (token_ids.shape, mesh)
    scatter = to_sp and mp > 1 and s % mp == 0

    def region(ids, shard):
        ids = jax.lax.all_gather(ids, DATA_AXIS, axis=0, tiled=True)
        # another model rank's row falls outside the shard: the lookup reads
        # zeros there and its gradient drops the row
        rows = take_rows(
            shard, ids - jax.lax.axis_index(MODEL_AXIS) * shard.shape[0])
        if scatter:
            rows = _batch_first(
                jax.lax.psum_scatter(_rows_first(rows), MODEL_AXIS,
                                     scatter_dimension=0, tiled=True), b)
        else:
            rows = jax.lax.psum(rows, MODEL_AXIS)
        return jax.lax.all_to_all(
            rows, DATA_AXIS, split_axis=0, concat_axis=2, tiled=True)

    y = jax.shard_map(
        region, mesh=mesh,
        in_specs=(P(DATA_AXIS, None), P(MODEL_AXIS, DATA_AXIS)),
        out_specs=P(DATA_AXIS, MODEL_AXIS if scatter else None, None),
    )(token_ids, table)
    return shard_activation_sp(y, mesh) if to_sp and not scatter else y


def shard_param(x: jax.Array, mesh: Optional[Mesh], spec: tuple) -> jax.Array:
    if mesh is None:
        return x
    return jax.device_put(x, NamedSharding(mesh, P(*spec)))
