"""The control of ``correct``: the reference in the next lower precision.

``correct`` compares the timed path with the plain float32 reference under
a limit. The limit means something only if a computation that a later PR
might be tempted by fails it: for configurations that state bf16, weights
in fp8. ``--control fp8`` makes a run compute, beside its own comparison,
what that lower precision would read on the same inputs, and print it on
stderr and in the result's ``host``: the driver's runs never pass it.
PERF.md section 2 gives the readings each limit was set from.
"""

from __future__ import annotations

PRECISIONS = ("fp8",)


def lower_precision(weights, precision: str):
    """The reference's weights with every matrix rounded to ``precision``
    and kept in it (half the bytes of bf16; the reference upcasts a layer at
    a time). Vectors (norm weights, biases) stay as they are."""
    import jax
    import jax.numpy as jnp

    if precision not in PRECISIONS:
        raise SystemExit(f"benchmark: --control is one of {PRECISIONS}")
    return jax.tree.map(
        lambda x: x.astype(jnp.float8_e4m3fn) if x.ndim >= 2 else x, weights)
