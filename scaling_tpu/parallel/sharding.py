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
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

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


def shard_param(x: jax.Array, mesh: Optional[Mesh], spec: tuple) -> jax.Array:
    if mesh is None:
        return x
    return jax.device_put(x, NamedSharding(mesh, P(*spec)))
