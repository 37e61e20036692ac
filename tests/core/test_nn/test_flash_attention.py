"""Flash (splash) vs XLA attention parity — forward, gradients, GQA
(reference: tests/core/test_nn/test_flash_attention.py flash-vs-torch)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from scaling_tpu.nn.attention import (
    multi_head_attention,
    repeat_kv,
    segment_ids_to_mask,
)
from scaling_tpu.nn.masked_softmax import MaskedSoftmax, MaskedSoftmaxConfig
from scaling_tpu.ops.flash_attention import (
    flash_attention_fused,
    flash_attention_supported,
    force_flash_interpret,
)

B, S, N, D = 1, 128, 2, 64


def make_qkv(seed=0, n=N, n_kv=N, d=D):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (
        jax.random.normal(ks[0], (B, S, n, d), jnp.float32) * 0.3,
        jax.random.normal(ks[1], (B, S, n_kv, d), jnp.float32) * 0.3,
        jax.random.normal(ks[2], (B, S, n_kv, d), jnp.float32) * 0.3,
    )


def xla_attention(q, k, v, segment_ids, d=D):
    if k.shape[2] != q.shape[2]:
        rep = q.shape[2] // k.shape[2]
        k, v = repeat_kv(k, rep), repeat_kv(v, rep)
    mask = segment_ids_to_mask(segment_ids, None, causal=True)
    softmax = MaskedSoftmax(MaskedSoftmaxConfig(softmax_in_fp32=True))
    return multi_head_attention(q, k, v, mask, 1.0 / np.sqrt(d), softmax, None, None)


def test_supported_gates_on_platform_without_interpret():
    """Outside force_flash_interpret the platform gate must hold (the layer
    falls back to XLA off-TPU)."""
    assert flash_attention_supported(S, D, platform="tpu")
    assert not flash_attention_supported(S - 1, D, platform="tpu")  # unaligned
    assert not flash_attention_supported(S, 32, platform="tpu")  # narrow head
    assert not flash_attention_supported(S, D, platform="cpu")


def test_block_sizes_snap_to_seq_divisors():
    """128-aligned lengths the default blocks don't divide (1536, 640) must
    snap instead of crashing at kernel construction."""
    from scaling_tpu.ops.flash_attention import _snap_block

    assert _snap_block(1024, 1536) == 768
    assert _snap_block(512, 1536) == 512
    assert _snap_block(512, 640) == 128
    assert _snap_block(1024, 2048) == 1024
    assert _snap_block(512, 128) == 128


def test_block_sizes_are_constants_no_environment_reaches(monkeypatch):
    """1024 / 1024 is the v5e sweep's winner; the knob that overrode it had
    one writer, a script no record ran, and went with it."""
    from scaling_tpu.ops.flash_attention import _block_sizes

    monkeypatch.setenv("SCALING_TPU_FLASH_BLOCK_Q", "512")
    assert _block_sizes() == (1024, 1024)


@pytest.fixture()
def interpret_pallas():
    """Run TPU Pallas kernels interpreted on the CPU harness; the context
    must span grad tracing too (bwd kernels trace lazily)."""
    with force_flash_interpret():
        yield


def test_supported_opts_in_under_interpret(interpret_pallas):
    # inside force_flash_interpret the CPU harness opts in
    assert flash_attention_supported(S, D, platform="cpu")


@pytest.mark.parametrize("packed", [False, True], ids=["single-doc", "packed"])
def test_flash_matches_xla_forward(packed, interpret_pallas):
    q, k, v = make_qkv()
    if packed:
        segment_ids = jnp.concatenate(
            [jnp.zeros((B, S // 2), jnp.int32), jnp.ones((B, S - S // 2), jnp.int32)],
            axis=1,
        )
    else:
        segment_ids = jnp.zeros((B, S), jnp.int32)
    ref = xla_attention(q, k, v, segment_ids)
    out = flash_attention_fused(q, k, v, segment_ids, causal=True,
                                sm_scale=1.0 / np.sqrt(D))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_flash_gqa_unrepeated_kv(interpret_pallas):
    """The kernel consumes unrepeated KV heads (the GQA bandwidth win the
    r1 VERDICT flagged) and matches the repeat-kv XLA reference."""
    q, k, v = make_qkv(2, n=4, n_kv=2, d=64)
    segment_ids = jnp.zeros((B, S), jnp.int32)
    ref = xla_attention(q, k, v, segment_ids, d=64)
    out = flash_attention_fused(q, k, v, segment_ids, causal=True,
                                sm_scale=1.0 / np.sqrt(64))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("gqa", [False, True], ids=["mha", "gqa"])
def test_flash_matches_xla_grads(gqa, interpret_pallas):
    n_kv = N // 2 if gqa else N
    q, k, v = make_qkv(1, n=N, n_kv=n_kv)
    segment_ids = jnp.concatenate(
        [jnp.zeros((B, S // 2), jnp.int32), jnp.ones((B, S - S // 2), jnp.int32)],
        axis=1,
    )

    def loss_flash(q, k, v):
        o = flash_attention_fused(q, k, v, segment_ids, causal=True,
                                  sm_scale=1.0 / np.sqrt(D))
        return (o * o).sum()

    def loss_ref(q, k, v):
        o = xla_attention(q, k, v, segment_ids)
        return (o * o).sum()

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gf, gr, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(
            np.asarray(gf), np.asarray(gr), atol=5e-4, rtol=5e-4, err_msg=name
        )


def test_flash_mixed_local_global_heads(interpret_pallas):
    """Trailing local-window heads get a LocalMask inside the kernel and
    match the XLA mixed-head reference (reference: flash sliding window,
    attention.py:204-259)."""
    n, n_local, window = 4, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(ks[0], (B, S, n, D), jnp.float32) * 0.3
    k = jax.random.normal(ks[1], (B, S, n, D), jnp.float32) * 0.3
    v = jax.random.normal(ks[2], (B, S, n, D), jnp.float32) * 0.3
    segment_ids = jnp.zeros((B, S), jnp.int32)

    softmax = MaskedSoftmax(MaskedSoftmaxConfig(softmax_in_fp32=True))
    global_mask = segment_ids_to_mask(segment_ids, None, causal=True)
    local_mask = segment_ids_to_mask(
        segment_ids, None, causal=True, local_window=window
    )
    out_g = multi_head_attention(
        q[:, :, : n - n_local], k[:, :, : n - n_local], v[:, :, : n - n_local],
        global_mask, 1.0 / np.sqrt(D), softmax, None, None,
    )
    out_l = multi_head_attention(
        q[:, :, n - n_local :], k[:, :, n - n_local :], v[:, :, n - n_local :],
        local_mask, 1.0 / np.sqrt(D), softmax, None, None,
    )
    ref = jnp.concatenate([out_g, out_l], axis=2)

    out = flash_attention_fused(
        q, k, v, segment_ids, causal=True, sm_scale=1.0 / np.sqrt(D),
        num_local_heads=n_local, local_window=window,
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_flash_shard_map_tp_parity(interpret_pallas, devices):
    """Under mp>1 the kernel partitions via shard_map (contiguous head
    slices per model shard, batch over data) and matches the unsharded
    kernel — GSPMD alone would replicate the opaque pallas call."""
    from scaling_tpu.topology import Topology, TopologyConfig

    topo = Topology(
        TopologyConfig.from_dict(
            {
                "model_parallel_size": 2,
                "pipe_parallel_size": 1,
                "data_parallel_size": 4,
                "micro_batch_size": 1,
                "gradient_accumulation_steps": 1,
            }
        )
    )
    n, n_kv = 4, 2
    ks = jax.random.split(jax.random.PRNGKey(9), 3)
    q = jax.random.normal(ks[0], (4, S, n, D), jnp.float32) * 0.3
    k = jax.random.normal(ks[1], (4, S, n_kv, D), jnp.float32) * 0.3
    v = jax.random.normal(ks[2], (4, S, n_kv, D), jnp.float32) * 0.3
    seg = jnp.concatenate(
        [jnp.zeros((4, S // 2), jnp.int32), jnp.ones((4, S - S // 2), jnp.int32)],
        axis=1,
    )
    scale = 1.0 / np.sqrt(D)
    ref = flash_attention_fused(q, k, v, seg, causal=True, sm_scale=scale)
    out = jax.jit(
        lambda q, k, v, s: flash_attention_fused(
            q, k, v, s, causal=True, sm_scale=scale, mesh=topo.mesh
        )
    )(q, k, v, seg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)
