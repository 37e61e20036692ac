"""Layer norms.

Parity with the reference's norm stack
(reference: src/scaling/core/nn/norm/layernorm.py:14-87, rms_norm.py:21-63,
get_norm.py): LayerNorm with optional bitfit bias, RMSNorm, a factory keyed
by ``NormType``. The reference's ``fused`` optimization type (flash-attn's
CUDA fused rms_norm) maps to the Pallas kernel in ``ops/rms_norm.py``;
``torch`` is the plain XLA path, which XLA fuses into neighbouring ops on
its own.

Sequence-parallel contract: norms sit *between* TP regions, so under SP
their input/output stay sequence-sharded; the surrounding linears change
layout. Norm params are replicated over the model axis and flagged
``is_sequence_parallel_norm`` so the optimizer knows their grads already
include every token's contribution only after a psum over the model axis —
with GSPMD the backward collective is emitted automatically, so the flag is
informational for grad-norm bookkeeping parity.
"""

from __future__ import annotations

from enum import Enum
from typing import Optional

import jax
import jax.numpy as jnp
from pydantic import Field

from ..config import BaseConfig
from .base_layer import BaseLayer, ForwardContext
from .param import ParamMeta


class NormType(Enum):
    LAYERNORM = "layernorm"
    RMS = "rms"


class LayerNormOptimizationType(Enum):
    TORCH = "torch"
    FUSED = "fused"


class LayerNormConfig(BaseConfig):
    optimization_type: LayerNormOptimizationType = Field(
        LayerNormOptimizationType.TORCH,
        description="norm implementation; 'torch' is the XLA-fused path, "
        "'fused' selects the Pallas kernel where available",
    )
    layernorm_epsilon: float = Field(
        1e-5, description="A value added to the denominator for numerical stability"
    )
    weight_offset: bool = Field(
        False,
        description="an RMS norm's learned weight is an OFFSET from one: y = "
        "x rsqrt(mean x^2 + eps) (1 + w), w starting at zeros (Qwen3-Next's "
        "and Gemma's RMSNorm); false: y = .. w, w starting at ones",
    )


def _norm_meta(name: str) -> ParamMeta:
    return ParamMeta(
        parameter_name=name,
        partition_spec=(None,),
        is_model_parallel=False,
        is_model_parallel_duplicate=True,
        no_weight_decay=True,
        is_sequence_parallel_norm=True,
    )


class LayerNorm(BaseLayer):
    def __init__(
        self,
        dimensions: int,
        config: Optional[LayerNormConfig] = None,
        dtype=jnp.float32,
        bitfit_bias_name: Optional[str] = None,
    ):
        self.dimensions = dimensions
        self.config = config or LayerNormConfig()
        self.dtype = dtype
        self.bitfit_bias_name = bitfit_bias_name

    @property
    def bias_name(self) -> str:
        return f"bias_{self.bitfit_bias_name}" if self.bitfit_bias_name else "bias"

    def init(self, key: jax.Array) -> dict:
        return {
            "weight": jnp.ones((self.dimensions,), dtype=self.dtype),
            self.bias_name: jnp.zeros((self.dimensions,), dtype=self.dtype),
        }

    def param_metas(self) -> dict:
        return {
            "weight": _norm_meta("weight"),
            self.bias_name: _norm_meta(self.bias_name),
        }

    def __call__(self, params: dict, x: jax.Array, ctx: ForwardContext) -> jax.Array:
        dtype = x.dtype
        x32 = x.astype(jnp.float32)
        mean = x32.mean(axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x32 - mean), axis=-1, keepdims=True)
        y = (x32 - mean) * jax.lax.rsqrt(var + self.config.layernorm_epsilon)
        y = y * params["weight"].astype(jnp.float32) + params[self.bias_name].astype(jnp.float32)
        return y.astype(dtype)


class RMSNorm(BaseLayer):
    def __init__(
        self,
        dimensions: int,
        config: Optional[LayerNormConfig] = None,
        dtype=jnp.float32,
        bitfit_bias_name: Optional[str] = None,
    ):
        self.dimensions = dimensions
        self.config = config or LayerNormConfig()
        self.dtype = dtype
        self.bitfit_bias_name = bitfit_bias_name  # rmsnorm has no bias; kept for API parity

    def init(self, key: jax.Array) -> dict:
        start = jnp.zeros if self.config.weight_offset else jnp.ones
        return {"weight": start((self.dimensions,), dtype=self.dtype)}

    def param_metas(self) -> dict:
        return {"weight": _norm_meta("weight")}

    def __call__(self, params: dict, x: jax.Array, ctx: ForwardContext) -> jax.Array:
        offset = self.config.weight_offset
        if (self.config.optimization_type == LayerNormOptimizationType.FUSED
                and not offset):
            from ..ops.rms_norm import (
                rms_norm_fused,
                rms_norm_fused_shardable,
                rms_norm_fused_sharded,
                rms_norm_fused_supported,
            )

            # pallas calls are opaque to GSPMD (see ops/flash_attention.py's
            # shard_map handling), so on a multi-device mesh the kernel is
            # partitioned explicitly: rows split over data x (context, model)
            # — the model-axis split IS sequence parallelism. Inside a
            # spatial pipeline (stage-local operands) or on indivisible
            # shapes the XLA path remains.
            if rms_norm_fused_supported(self.dimensions):
                if ctx.mesh is None or ctx.mesh.size <= 1:
                    return rms_norm_fused(
                        x, params["weight"], self.config.layernorm_epsilon
                    )
                if rms_norm_fused_shardable(ctx.mesh, x.shape):
                    return rms_norm_fused_sharded(
                        x,
                        params["weight"],
                        self.config.layernorm_epsilon,
                        ctx.mesh,
                    )
        dtype = x.dtype
        x32 = x.astype(jnp.float32)
        var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
        y = x32 * jax.lax.rsqrt(var + self.config.layernorm_epsilon)
        weight = params["weight"].astype(jnp.float32)
        return (y * (1.0 + weight if offset else weight)).astype(dtype)


def get_norm(
    norm_type: NormType,
    dimensions: int,
    layernorm_config: Optional[LayerNormConfig] = None,
    dtype=jnp.float32,
    bitfit_bias_name: Optional[str] = None,
) -> BaseLayer:
    if norm_type == NormType.LAYERNORM:
        return LayerNorm(dimensions, layernorm_config, dtype, bitfit_bias_name)
    if norm_type == NormType.RMS:
        return RMSNorm(dimensions, layernorm_config, dtype, bitfit_bias_name)
    raise NotImplementedError(f"norm type {norm_type}")
