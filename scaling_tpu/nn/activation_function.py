"""Activation function registry.

(reference: src/scaling/core/nn/activation_function.py)
"""

from __future__ import annotations

from enum import Enum
from typing import Callable

import jax
import jax.numpy as jnp


class ActivationFunction(Enum):
    GELU = "gelu"
    SILU = "silu"
    RELU = "relu"
    TANH = "tanh"
    SIGMOID = "sigmoid"
    # relu(x) ** 2 (Primer, So et al. 2021; the experts of the Nemotron-H
    # family, ``mlp_hidden_act: relu2``)
    RELU2 = "relu2"


_FUNCTIONS: dict[ActivationFunction, Callable] = {
    ActivationFunction.GELU: jax.nn.gelu,
    ActivationFunction.SILU: jax.nn.silu,
    ActivationFunction.RELU: jax.nn.relu,
    ActivationFunction.TANH: jnp.tanh,
    ActivationFunction.SIGMOID: jax.nn.sigmoid,
    ActivationFunction.RELU2: lambda x: jnp.square(jax.nn.relu(x)),
}


def get_activation_function(activation: ActivationFunction) -> Callable:
    return _FUNCTIONS[activation]
