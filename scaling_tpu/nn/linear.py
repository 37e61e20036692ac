"""Tensor-parallel linear layers and vocab-parallel embedding.

Capability parity with the reference's Column/Row/VocabParallel layers
(reference: src/scaling/core/nn/linear/column_parallel_linear.py:23,
row_parallel_linear.py:16, vocab_parallel_embedding.py:19), re-designed for
GSPMD: weights carry PartitionSpecs over the ``model`` mesh axis and
activation sharding constraints make XLA emit the collectives the reference
hand-rolls (copy-to-region, all-gather, all-reduce). Weight layout is
(in, out) — jnp convention — vs the reference's torch (out, in).

The one collective a constraint does NOT get is the reference's
reduce-scatter-to-sequence-parallel. Asked for the SP layout behind a
row-parallel matmul, this TPU compiler all-reduces the whole ``(b, s, h)``
activation over the model axis and slices the rank's share of the sequence
out of it, forward, and again for the cotangent of every column-parallel
input, backward: twice the traffic of the reduce-scatter it was asked for.
So under sequence parallelism a region's two boundaries are written out
(``parallel/sharding.py``: ``sp_enter``, an all-gather feeding the
column-parallel matmul inside one manual region; ``sp_leave``, the
row-parallel matmul and a reduce-scatter), over the leading dimension of the
2-D rows, the one form the compiler keeps; docs/PARALLELISM.md, "SP
(Megatron)", says what was measured. Everything else here is a constraint.

``parallel_output`` / ``parallel_input`` keep the reference's fusion
contract: a column-parallel with ``parallel_output=True`` feeds a
row-parallel with ``parallel_input=True`` without leaving the TP region.
"""

from __future__ import annotations

import math
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from ..parallel.sharding import (
    constrain,
    lookup_on_data_shard,
    lookup_rows_on_data_shard,
    shard_activation_replicated_h,
    shard_activation_sp,
    shard_activation_tp,
    sp_boundary_is_manual,
    sp_enter,
    sp_leave,
)
from ..topology.topology import DATA_AXIS, MODEL_AXIS
from .base_layer import BaseLayer, ForwardContext
from .param import ParamMeta, model_parallel_meta, replicated_meta


def xavier_normal_init(key: jax.Array, shape: tuple, dtype=jnp.float32) -> jax.Array:
    fan_in, fan_out = shape[0], shape[1]
    std = math.sqrt(2.0 / (fan_in + fan_out))
    return (jax.random.normal(key, shape) * std).astype(dtype)


def normal_init(std: float) -> Callable:
    def init(key: jax.Array, shape: tuple, dtype=jnp.float32) -> jax.Array:
        return (jax.random.normal(key, shape) * std).astype(dtype)

    return init


class ColumnParallelLinear(BaseLayer):
    """Y = X W + b with W's output dim sharded over the model axis."""

    def __init__(
        self,
        in_features: int,
        out_features: int,
        bias: bool = True,
        dtype=jnp.float32,
        init_method: Callable = xavier_normal_init,
        bitfit_bias_name: Optional[str] = None,
        parallel_output: bool = False,
    ):
        self.in_features = in_features
        self.out_features = out_features
        self.use_bias = bias
        self.dtype = dtype
        self.init_method = init_method
        self.bitfit_bias_name = bitfit_bias_name
        self.parallel_output = parallel_output

    @property
    def bias_name(self) -> str:
        return f"bias_{self.bitfit_bias_name}" if self.bitfit_bias_name else "bias"

    def init(self, key: jax.Array) -> dict:
        params = {"weight": self.init_method(key, (self.in_features, self.out_features), self.dtype)}
        if self.use_bias:
            params[self.bias_name] = jnp.zeros((self.out_features,), dtype=self.dtype)
        return params

    def param_metas(self) -> dict:
        metas = {
            "weight": model_parallel_meta(1, parameter_name="weight"),
        }
        if self.use_bias:
            metas[self.bias_name] = ParamMeta(
                parameter_name=self.bias_name,
                partition_spec=(MODEL_AXIS,),
                is_model_parallel=True,
                model_parallel_dimension=0,
            )
        return metas

    def __call__(self, params: dict, x: jax.Array, ctx: ForwardContext) -> jax.Array:
        y = column_parallel_matmul(x, params["weight"].astype(x.dtype), ctx)
        if self.use_bias:
            y = y + params[self.bias_name].astype(x.dtype)
        if y.ndim == 3:
            if self.parallel_output:
                y = shard_activation_tp(y, ctx.mesh)
            else:
                y = shard_activation_replicated_h(y, ctx.mesh)
        return y


def column_parallel_matmul(x: jax.Array, weight: jax.Array, ctx: ForwardContext) -> jax.Array:
    """``x @ weight`` for a column-parallel ``weight``: the matmul that ENTERS
    a TP region. Under sequence parallelism ``x`` arrives sequence-sharded:
    where the boundary can be written out (``sp_boundary_is_manual``) its
    rows are all-gathered inside the matmul's own manual region, and ``ctx``
    counts the region; elsewhere XLA gathers ``x`` where the matmul needs it
    (the reference skips its copy op under SP). Siblings that share ``x``
    (query, key and value; gate and up) each write the gather, and XLA keeps
    one of them forward and one reduce-scatter of their summed cotangents
    backward (tests/core/test_chip_compile.py pins both counts)."""
    if ctx.sequence_parallel and sp_boundary_is_manual(x.shape, ctx.mesh):
        ctx.note_sp_region(x)
        return sp_enter(x, weight, ctx.mesh)
    return x @ weight


class RowParallelLinear(BaseLayer):
    """Y = X W + b with W's input dim sharded over the model axis."""

    def __init__(
        self,
        in_features: int,
        out_features: int,
        bias: bool = True,
        dtype=jnp.float32,
        init_method: Callable = xavier_normal_init,
        bitfit_bias_name: Optional[str] = None,
        parallel_input: bool = True,
        parallel_output: bool = False,
    ):
        self.in_features = in_features
        self.out_features = out_features
        self.use_bias = bias
        self.dtype = dtype
        self.init_method = init_method
        self.bitfit_bias_name = bitfit_bias_name
        self.parallel_input = parallel_input
        self.parallel_output = parallel_output  # True => under SP, leave in the SP layout

    @property
    def bias_name(self) -> str:
        return f"bias_{self.bitfit_bias_name}" if self.bitfit_bias_name else "bias"

    def init(self, key: jax.Array) -> dict:
        params = {"weight": self.init_method(key, (self.in_features, self.out_features), self.dtype)}
        if self.use_bias:
            params[self.bias_name] = jnp.zeros((self.out_features,), dtype=self.dtype)
        return params

    def param_metas(self) -> dict:
        metas = {"weight": model_parallel_meta(0, parameter_name="weight")}
        if self.use_bias:
            # bias added after the reduce => replicated, mp-duplicate
            metas[self.bias_name] = replicated_meta(1, parameter_name=self.bias_name)
        return metas

    def __call__(self, params: dict, x: jax.Array, ctx: ForwardContext) -> jax.Array:
        weight = params["weight"].astype(x.dtype)
        to_sp = self.parallel_output and ctx.sequence_parallel
        if to_sp and sp_boundary_is_manual(x.shape, ctx.mesh):
            # leave the TP region into the sequence-parallel layout by a
            # reduce-scatter of the partial sums, written out
            y = sp_leave(x, weight, ctx.mesh)
        else:
            y = x @ weight
            if y.ndim == 3:
                if to_sp:
                    # the same layout by constraint, where a context or pipe
                    # axis is in play: the TPU compiler makes it an all-reduce
                    # of the whole activation and a slice (module docstring)
                    y = shard_activation_sp(y, ctx.mesh)
                else:
                    # all-reduce over the model axis (partial sums -> full)
                    y = shard_activation_replicated_h(y, ctx.mesh)
        if self.use_bias:
            y = y + params[self.bias_name].astype(x.dtype)
        return y


class VocabParallelEmbedding(BaseLayer):
    """Embedding with the vocabulary sharded over the model axis."""

    def __init__(
        self,
        num_embeddings: int,
        embedding_dim: int,
        dtype=jnp.float32,
        init_method: Callable = xavier_normal_init,
        finetunable_token_ids: Optional[list[int]] = None,
        row_lookup: bool = True,
    ):
        # every consumer reads the table by row (``ParamMeta.row_lookup``):
        # False where it is another layer's matrix too (the head's, through
        # a ``TiedLayerSpec``)
        self.row_lookup = row_lookup
        self.num_embeddings = num_embeddings
        self.embedding_dim = embedding_dim
        self.dtype = dtype
        self.init_method = init_method
        self.finetunable_token_ids = finetunable_token_ids or []

    def init(self, key: jax.Array) -> dict:
        return {
            "weight": self.init_method(key, (self.num_embeddings, self.embedding_dim), self.dtype)
        }

    def param_metas(self) -> dict:
        return {
            "weight": ParamMeta(
                parameter_name="weight",
                partition_spec=(MODEL_AXIS, None),
                is_model_parallel=True,
                model_parallel_dimension=0,
                lr_group="embedding",
                row_lookup=self.row_lookup,
            )
        }

    def __call__(self, params: dict, token_ids: jax.Array, ctx: ForwardContext) -> jax.Array:
        weight = params["weight"]
        if lookup_on_data_shard(self.param_metas()["weight"], weight.shape,
                                ctx.mesh, ctx.zero_gathers_on_entry):
            # the step left the table on ZeRO-1's shard: look up there and
            # exchange the rows
            return lookup_rows_on_data_shard(
                token_ids, weight.astype(self.dtype), ctx.mesh,
                ctx.sequence_parallel)
        # gather from the vocab-sharded table; XLA handles the out-of-shard
        # masking + psum that the reference hand-codes
        y = weight.astype(self.dtype)[token_ids]
        if ctx.sequence_parallel:
            y = shard_activation_sp(y, ctx.mesh)
        else:
            y = shard_activation_replicated_h(y, ctx.mesh)
        return y

    def finetunable_grad_mask(self) -> Optional[jax.Array]:
        """0/1 row mask for finetunable-token-only training; None if unused."""
        if not self.finetunable_token_ids:
            return None
        mask = jnp.zeros((self.num_embeddings, 1), dtype=jnp.float32)
        return mask.at[jnp.array(self.finetunable_token_ids)].set(1.0)
