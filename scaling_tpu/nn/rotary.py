"""Rotary position embeddings, both variants.

Parity with the reference (reference: src/scaling/core/nn/rotary.py:142-255):

- ``RotaryEmbedding``: GPT-NeoX-style half-rotation with precomputed cos/sin
  tables, partial application via ``rotary_percentage`` (dimensions < head
  dim), position-id gather;
- ``RotaryEmbeddingComplex``: llama-style pairwise complex multiplication
  (``freqs_cis``), which pairs adjacent dims instead of split halves.

Layout is batch-major (b, s, n_heads, head_dim), vs the reference's
(s, b, n, h). Tables are computed in fp32 and applied in the activation
dtype (neox path) / fp32 (complex path), matching reference numerics.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from pydantic import Field, model_validator

from ..config import BaseConfig


class RopeScalingConfig(BaseConfig):
    """A checkpoint's ``rope_scaling`` object. One type is built: YaRN (Peng
    et al. 2023, arXiv:2309.00071) as DeepSeek-V3's modelling code applies it:
    STATIC frequencies (``yarn_inv_freq``), whatever the context, cos / sin
    times ``m(mscale) / m(mscale_all_dim)`` and the softmax scale times
    ``m(mscale_all_dim) ** 2`` (``yarn_mscale``)."""

    type: str = Field("yarn", description="only 'yarn' is built")
    factor: float = Field(description="context extension factor", ge=1.0)
    original_max_position_embeddings: int = Field(
        description="positions the base frequencies were trained at", gt=0)
    beta_fast: float = Field(32.0, description="rotations above which a "
                             "frequency keeps its base value", gt=0)
    beta_slow: float = Field(1.0, description="rotations below which a "
                             "frequency is divided by factor", gt=0)
    mscale: float = Field(1.0, description="m(mscale) multiplies cos / sin", ge=0)
    mscale_all_dim: float = Field(
        0.0, description="m(mscale_all_dim) divides cos / sin and, squared, "
        "multiplies the softmax scale", ge=0)

    @model_validator(mode="after")
    def _validate(self):
        if self.type != "yarn":
            raise ValueError(
                f"rope_scaling type {self.type!r}: only 'yarn' is built "
                "(static frequencies and a softmax scale; 'linear', "
                "'dynamic', 'longrope' and 'llama3' are not)")
        return self


class RotaryConfig(BaseConfig):
    dimensions: int = Field(0, description="number of leading head dims to rotate")
    base: int = Field(10000, description="rotary frequency base")
    max_seq_length: int = Field(2048, description="table length")
    scaling: Optional[RopeScalingConfig] = Field(
        None, description="YaRN frequencies in place of the base's")


def yarn_mscale(factor: float, mscale: float) -> float:
    """``m = 0.1 mscale ln(factor) + 1`` (1 at ``factor <= 1``)."""
    if factor <= 1.0:
        return 1.0
    return 0.1 * mscale * math.log(factor) + 1.0


def yarn_correction_range(scaling: RopeScalingConfig, dimensions: int,
                          base: float) -> tuple[int, int]:
    """``(low, high)``: the frequency indices between which YaRN's ramp
    runs. Index ``i`` makes ``r`` full rotations over the original context at
    ``i = dimensions ln(original / (2 pi r)) / (2 ln base)``; ``low`` is that
    of ``beta_fast`` rounded down, ``high`` that of ``beta_slow`` rounded up,
    both clipped into the table."""
    def index(rotations: float) -> float:
        return (dimensions * math.log(
            scaling.original_max_position_embeddings
            / (rotations * 2 * math.pi))) / (2 * math.log(base))

    low = math.floor(index(scaling.beta_fast))
    high = math.ceil(index(scaling.beta_slow))
    return max(low, 0), min(high, dimensions - 1)


def yarn_inv_freq(scaling: RopeScalingConfig, dimensions: int,
                  base: float) -> np.ndarray:
    """The ``dimensions / 2`` static YaRN frequencies: ``f_i = base ** (-2i /
    dimensions)`` below ``low``, ``f_i / factor`` from ``high`` on, the linear
    ramp between them."""
    f = 1.0 / (base ** (np.arange(0, dimensions, 2, dtype=np.float32) / dimensions))
    low, high = yarn_correction_range(scaling, dimensions, base)
    if low == high:
        high += 0.001  # the released code's guard against a zero-width ramp
    ramp = np.clip((np.arange(dimensions // 2, dtype=np.float32) - low)
                   / (high - low), 0.0, 1.0)
    return (f * (1.0 - ramp) + (f / scaling.factor) * ramp).astype(np.float32)


def yarn_softmax_scale(scaling: Optional[RopeScalingConfig]) -> float:
    """What YaRN multiplies the attention's ``1 / sqrt(d)`` by:
    ``m(mscale_all_dim) ** 2`` (1 without scaling or at ``mscale_all_dim``
    0)."""
    if scaling is None or not scaling.mscale_all_dim:
        return 1.0
    return yarn_mscale(scaling.factor, scaling.mscale_all_dim) ** 2


def _cos_sin_tables(dimensions: int, max_seq_length: int, base: float,
                    scaling: Optional[RopeScalingConfig] = None):
    # host-side numpy: the tables embed into jitted programs as constants,
    # which must not require a device->host fetch at trace time
    amplitude = 1.0
    if scaling is None:
        inv_freq = 1.0 / (base ** (np.arange(0, dimensions, 2, dtype=np.float32) / dimensions))
    else:
        inv_freq = yarn_inv_freq(scaling, dimensions, base)
        amplitude = (yarn_mscale(scaling.factor, scaling.mscale)
                     / yarn_mscale(scaling.factor, scaling.mscale_all_dim))
    t = np.arange(max_seq_length, dtype=np.float32)
    freqs = np.outer(t, inv_freq)  # (s, d/2)
    emb = np.concatenate([freqs, freqs], axis=-1)  # (s, d)
    if amplitude == 1.0:
        return np.cos(emb), np.sin(emb)
    return np.cos(emb) * amplitude, np.sin(emb) * amplitude


def rotate_half(x: jax.Array) -> jax.Array:
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([-x2, x1], axis=-1)


def apply_rotary_pos_emb(
    x: jax.Array,  # (b, s, n, d_rot)
    cos: jax.Array,  # (s_table, d_rot)
    sin: jax.Array,
    position_ids: Optional[jax.Array],  # (b, s) or None
) -> jax.Array:
    cos, sin = jnp.asarray(cos), jnp.asarray(sin)
    if position_ids is None:
        s = x.shape[1]
        cos_g = cos[None, :s, None, :]
        sin_g = sin[None, :s, None, :]
    else:
        cos_g = cos[position_ids][:, :, None, :]  # (b, s, 1, d)
        sin_g = sin[position_ids][:, :, None, :]
    return x * cos_g.astype(x.dtype) + rotate_half(x) * sin_g.astype(x.dtype)


class RotaryEmbedding:
    """Half-rotation rotary, optionally applied to a leading slice of dims."""

    def __init__(self, config: RotaryConfig):
        assert config.dimensions > 1, "RotaryEmbedding cannot use dimensions <= 1"
        self.dimensions = config.dimensions
        self.cos, self.sin = _cos_sin_tables(
            config.dimensions, config.max_seq_length, config.base, config.scaling)

    def __call__(
        self,
        query: jax.Array,  # (b, s, n, h)
        key: jax.Array,  # (b, s, n_kv, h)
        query_position_ids: Optional[jax.Array] = None,
        key_position_ids: Optional[jax.Array] = None,
    ) -> tuple[jax.Array, jax.Array]:
        d = self.dimensions
        if query.shape[-1] != d:
            assert query.shape[-1] > d, f"query dims {query.shape[-1]} < rotary dims {d}"
            q_rot = apply_rotary_pos_emb(query[..., :d], self.cos, self.sin, query_position_ids)
            k_rot = apply_rotary_pos_emb(key[..., :d], self.cos, self.sin, key_position_ids)
            query = jnp.concatenate([q_rot, query[..., d:]], axis=-1)
            key = jnp.concatenate([k_rot, key[..., d:]], axis=-1)
            return query, key
        return (
            apply_rotary_pos_emb(query, self.cos, self.sin, query_position_ids),
            apply_rotary_pos_emb(key, self.cos, self.sin, key_position_ids),
        )


def precompute_freqs_cis(dim: int, end: int, theta: float) -> np.ndarray:
    """Complex rotation factors e^{i t f} as a (end, dim/2) complex64 array.

    Host-side numpy (see _cos_sin_tables); stored as cos/sin would be too,
    but complex64 keeps the llama pairing arithmetic one multiply.
    """
    freqs = 1.0 / (theta ** (np.arange(0, dim, 2, dtype=np.float32)[: dim // 2] / dim))
    t = np.arange(end, dtype=np.float32)
    angles = np.outer(t, freqs)
    return (np.cos(angles) + 1j * np.sin(angles)).astype(np.complex64)


def apply_complex_rotary_emb(
    x: jax.Array,  # (b, s, n, h)
    freqs_cis: jax.Array,  # (s_table, h/2) complex
    position_ids: Optional[jax.Array],
) -> jax.Array:
    """Llama-style adjacent-pair rotation, in real arithmetic: complex64 is
    software-emulated on TPU and measured ~8% slower end-to-end."""
    b, s, n, h = x.shape
    xf = x.astype(jnp.float32)
    x_even, x_odd = xf[..., 0::2], xf[..., 1::2]  # (b, s, n, h/2)
    # split host-side: complex never reaches the device
    freqs_np = np.asarray(freqs_cis)
    cos_t = jnp.asarray(np.real(freqs_np).astype(np.float32))
    sin_t = jnp.asarray(np.imag(freqs_np).astype(np.float32))
    if position_ids is None:
        cos = cos_t[None, :s, None, :]
        sin = sin_t[None, :s, None, :]
    else:
        cos = cos_t[position_ids][:, :, None, :]
        sin = sin_t[position_ids][:, :, None, :]
    r_even = x_even * cos - x_odd * sin
    r_odd = x_even * sin + x_odd * cos
    out = jnp.stack([r_even, r_odd], axis=-1).reshape(b, s, n, h)
    return out.astype(x.dtype)


class RotaryEmbeddingComplex:
    """Llama-style rotary via complex multiplication (adjacent-dim pairs)."""

    def __init__(self, config: RotaryConfig):
        assert config.dimensions > 1, "RotaryEmbedding cannot use dimensions <= 1"
        self.freqs_cis = precompute_freqs_cis(
            config.dimensions, config.max_seq_length, float(config.base)
        )

    def __call__(
        self,
        query: jax.Array,
        key: jax.Array,
        query_position_ids: Optional[jax.Array] = None,
        key_position_ids: Optional[jax.Array] = None,
    ) -> tuple[jax.Array, jax.Array]:
        return (
            apply_complex_rotary_emb(query, self.freqs_cis, query_position_ids),
            apply_complex_rotary_emb(key, self.freqs_cis, key_position_ids),
        )


class RelativePositionEmbeddingType:
    NONE = "none"
    ROTARY = "rotary"
    ROTARY_COMPLEX = "rotary_complex"
