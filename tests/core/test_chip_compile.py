"""The main path's Pallas kernels, compiled for a TPU v5e that is described
and not attached (on-chip-measurement guide, section 2): what Mosaic refuses
at the real head shapes fails here, on the CPU, before it costs chip time.

Nothing runs, so these say nothing about results or speed; ``chip_smoke.py``
checks both on the chip. The topology is described only inside the
module-scoped fixture: the TPU library belongs to one process at a time, so
it must not load while a module is imported, and the compiles happen in the
test's own process. The persistent compile cache is off around them: an
executable compiled for a described chip is written to it but cannot be
read back without one.
"""

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from scaling_tpu.nn.paged_attention import paged_decode_attention
from scaling_tpu.ops.flash_attention import flash_attention_fused
from scaling_tpu.topology.topology import DATA_AXIS, MODEL_AXIS

HEAD_DIM = 128
# the serve phase of chip_smoke.py: 8 slots x 4k context in blocks of 16,
# plus the trash block; 4 KV heads under 16 query heads
SLOTS, BLOCK_SIZE, MAX_BLOCKS, KV_HEADS, Q_HEADS = 8, 16, 256, 4, 16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was_enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was_enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def splash_fwd_bwd(q, k, v, mesh=None):
    def loss(q, k, v):
        out = flash_attention_fused(
            q, k, v, sm_scale=HEAD_DIM ** -0.5, mesh=mesh
        )
        return out.astype(jnp.float32).sum()

    return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)


def qkv_shapes(batch, seq, q_heads, kv_heads, sharding):
    return tuple(
        jax.ShapeDtypeStruct(
            (batch, seq, heads, HEAD_DIM), jnp.bfloat16, sharding=sharding
        )
        for heads in (q_heads, kv_heads, kv_heads)
    )


@pytest.mark.parametrize(
    "batch,seq,q_heads,kv_heads",
    [(4, 2048, 16, 4), (2, 4096, 32, 8)],
    ids=["0.5b-s2048-16q4kv", "1b-s4096-32q8kv"],
)
def test_splash_fwd_bwd_compiles(one_chip, batch, seq, q_heads, kv_heads):
    compiled = jax.jit(splash_fwd_bwd).lower(
        *qkv_shapes(batch, seq, q_heads, kv_heads, one_chip)
    ).compile()
    # forward, dq and dkv kernels
    assert compiled.as_text().count("tpu_custom_call") >= 3


def test_splash_survives_shard_map_on_2x2(topo):
    """TP=2 x DP=2 (chip_smoke.py --chips 4): the kernel is partitioned by
    shard_map over (data, model), each chip running its own heads."""
    mesh = Mesh(
        np.array(topo.devices).reshape(2, 2), (DATA_AXIS, MODEL_AXIS)
    )
    sharding = NamedSharding(mesh, P(DATA_AXIS, None, MODEL_AXIS, None))
    compiled = jax.jit(
        functools.partial(splash_fwd_bwd, mesh=mesh)
    ).lower(*qkv_shapes(4, 2048, Q_HEADS, KV_HEADS, sharding)).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 3


@pytest.mark.parametrize("kv_dtype", ["native", "int8"])
@pytest.mark.parametrize(
    "s", [1, 32, 5], ids=["decode-s1", "prefill-chunk-s32", "spec-s5"]
)
@pytest.mark.parametrize(
    "slots,q_heads,kv_heads",
    [(SLOTS, Q_HEADS, KV_HEADS), (16, 32, 8), (16, 36, 4), (16, 16, 16)],
    ids=["smoke-16q4kv", "serve-cell-32q8kv", "pharia-36q4kv-group9",
         "olmoe-16q16kv-group1"],
)
def test_paged_kernel_compiles(one_chip, slots, q_heads, kv_heads, s, kv_dtype):
    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    quantized = kv_dtype == "int8"
    pool_dims = (slots * MAX_BLOCKS + 1, BLOCK_SIZE, kv_heads, HEAD_DIM)
    pool = shape(pool_dims, jnp.int8 if quantized else jnp.bfloat16)
    scales = (
        {"scale_k": shape(pool_dims[:3], jnp.float32),
         "scale_v": shape(pool_dims[:3], jnp.float32)}
        if quantized else {}
    )

    def attend(q, pool_k, pool_v, table, valid_len, base, scales):
        return paged_decode_attention(
            q, pool_k, pool_v, table, valid_len, base,
            sm_scale=HEAD_DIM ** -0.5, num_repeat_kv=q_heads // kv_heads,
            interpret=False, **scales,
        )

    compiled = jax.jit(attend).lower(
        shape((slots, s, q_heads, HEAD_DIM), jnp.bfloat16), pool, pool,
        shape((slots, MAX_BLOCKS), jnp.int32), shape((slots,), jnp.int32),
        shape((slots,), jnp.int32), scales,
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
