"""Tensor-parallel MLPs.

(reference: src/scaling/core/nn/mlp.py:21-167) ``ParallelMLP`` is
column-parallel -> activation -> row-parallel; ``ParallelSwiGLUMLP`` gates a
silu branch against a linear branch before the row-parallel projection.
Under sequence parallelism both leave in the SP layout, as the attention
does: the norms, residuals and dropout between two regions sit in it, and
nothing there reads a hidden-replicated output.
``io_features * intermediate_feature_factor`` must be a natural number —
same contract as the reference, so configs produce identical shapes.
"""

from __future__ import annotations

from typing import Optional

import jax

from .activation_function import ActivationFunction, get_activation_function
from .base_layer import BaseLayer, ForwardContext, multiplied
from .linear import ColumnParallelLinear, RowParallelLinear, xavier_normal_init
from .param import tree_prefix


class ParallelMLP(BaseLayer):
    def __init__(
        self,
        io_features: int,
        intermediate_feature_factor: float = 4.0,
        activation: ActivationFunction = ActivationFunction.GELU,
        bias: bool = True,
        dtype=None,
        init_method=xavier_normal_init,
        bitfit_bias_name: Optional[str] = None,
    ):
        import jax.numpy as jnp

        dtype = dtype or jnp.float32
        assert float(int(io_features * intermediate_feature_factor)) == (
            io_features * intermediate_feature_factor
        ), "io_features * intermediate_feature_factor must be a natural number"
        intermediate = int(io_features * intermediate_feature_factor)
        self.activation_fn = get_activation_function(activation)
        self.dense_in = ColumnParallelLinear(
            io_features, intermediate, bias=bias, dtype=dtype,
            init_method=init_method, bitfit_bias_name=bitfit_bias_name,
            parallel_output=True,
        )
        self.dense_out = RowParallelLinear(
            intermediate, io_features, bias=bias, dtype=dtype,
            init_method=init_method, bitfit_bias_name=bitfit_bias_name,
            parallel_input=True, parallel_output=True,
        )

    def init(self, key: jax.Array) -> dict:
        k1, k2 = jax.random.split(key)
        return {"dense_in": self.dense_in.init(k1), "dense_out": self.dense_out.init(k2)}

    def param_metas(self) -> dict:
        return {
            "dense_in": tree_prefix(self.dense_in.param_metas(), "dense_in"),
            "dense_out": tree_prefix(self.dense_out.param_metas(), "dense_out"),
        }

    def __call__(self, params: dict, x: jax.Array, ctx: ForwardContext) -> jax.Array:
        h = self.dense_in(params["dense_in"], x, ctx)
        h = self.activation_fn(h)
        return self.dense_out(params["dense_out"], h, ctx)


class ParallelSwiGLUMLP(BaseLayer):
    """silu(x W_gate) * (x W_up) -> W_down, all tensor-parallel.

    ``gate_multiplier`` / ``down_multiplier``: published constants of a
    configuration (Falcon-H1's ``mlp_multipliers``), ``down * ((silu(gate * (x
    W_gate)) * (x W_up)) W_down)``; the seeded init starts ``W_gate`` and
    ``W_down`` at their usual scale over their multiplier. At 1 nothing is
    multiplied."""

    def __init__(
        self,
        io_features: int,
        intermediate_feature_factor: float = 8.0 / 3.0,
        bias: bool = False,
        dtype=None,
        init_method=xavier_normal_init,
        bitfit_bias_name: Optional[str] = None,
        gate_multiplier: float = 1.0,
        down_multiplier: float = 1.0,
    ):
        import jax.numpy as jnp

        dtype = dtype or jnp.float32
        assert float(int(io_features * intermediate_feature_factor)) == (
            io_features * intermediate_feature_factor
        ), "io_features * intermediate_feature_factor must be a natural number"
        intermediate = int(io_features * intermediate_feature_factor)
        self.intermediate = intermediate
        self.gate_multiplier = float(gate_multiplier)
        self.down_multiplier = float(down_multiplier)
        self.silu = get_activation_function(ActivationFunction.SILU)
        self.gate_proj = ColumnParallelLinear(
            io_features, intermediate, bias=bias, dtype=dtype,
            init_method=init_method, bitfit_bias_name=bitfit_bias_name,
            parallel_output=True,
        )
        self.up_proj = ColumnParallelLinear(
            io_features, intermediate, bias=bias, dtype=dtype,
            init_method=init_method, bitfit_bias_name=bitfit_bias_name,
            parallel_output=True,
        )
        self.down_proj = RowParallelLinear(
            intermediate, io_features, bias=bias, dtype=dtype,
            init_method=init_method, bitfit_bias_name=bitfit_bias_name,
            parallel_input=True, parallel_output=True,
        )

    def init(self, key: jax.Array) -> dict:
        k1, k2, k3 = jax.random.split(key, 3)
        params = {
            "gate_proj": self.gate_proj.init(k1),
            "up_proj": self.up_proj.init(k2),
            "down_proj": self.down_proj.init(k3),
        }
        for name, by in (("gate_proj", self.gate_multiplier),
                         ("down_proj", self.down_multiplier)):
            params[name]["weight"] = multiplied(params[name]["weight"], 1.0 / by)
        return params

    def param_metas(self) -> dict:
        return {
            "gate_proj": tree_prefix(self.gate_proj.param_metas(), "gate_proj"),
            "up_proj": tree_prefix(self.up_proj.param_metas(), "up_proj"),
            "down_proj": tree_prefix(self.down_proj.param_metas(), "down_proj"),
        }

    def __call__(self, params: dict, x: jax.Array, ctx: ForwardContext) -> jax.Array:
        gate = self.silu(multiplied(
            self.gate_proj(params["gate_proj"], x, ctx), self.gate_multiplier))
        up = self.up_proj(params["up_proj"], x, ctx)
        return multiplied(self.down_proj(params["down_proj"], gate * up, ctx),
                          self.down_multiplier)
