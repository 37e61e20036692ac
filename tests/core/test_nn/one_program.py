"""A mixer call as ONE program: run eagerly, a mixer's ops (and a served row
walk's loops and branches) are compiled and dispatched one by one, anew at
every shape. Each helper traces anew on every call of it, so a test that
alters a part of the mixer sees the altered part traced."""

import jax
import jax.numpy as jnp

from scaling_tpu.nn.base_layer import ForwardContext


def jitted(mixer, ctx):
    """``(params, x, position_ids, view=None)`` -> what ``mixer`` returns under
    ``ctx``; one program a call shape."""
    return jax.jit(lambda params, x, position_ids, view=None: mixer(
        params, x, ctx, position_ids=position_ids, kv_cache=view))


def uncached(mixer, params, x):
    """The uncached form over the whole of ``x`` (1, s, hidden)."""
    return jitted(mixer, ForwardContext())(
        params, x, jnp.arange(x.shape[1], dtype=jnp.int32)[None])


def served(mixer, paged_kernel):
    """``(params, x, position_ids, view) -> (y, view, tie breaks)``: the mixer
    over a paged view."""
    return jitted(mixer, ForwardContext(serving=True, paged_kernel=paged_kernel))
