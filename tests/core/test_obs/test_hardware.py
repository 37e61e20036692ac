"""Hardware gauge + MFU math units. The MFU check is hand-computed from
a small transformer config so a regression in any constant (6N, the
12LHS attention term, the peak table) trips it (ISSUE 5 satellite)."""

import pytest

from scaling_tpu.models.transformer.utils.get_tflops import (
    HardwareType,
    get_flops_per_token,
    get_model_parameter_count,
    get_palm_mfu,
)
from scaling_tpu.obs import (
    StepTimeEMA,
    achieved_tflops,
    device_memory_snapshot,
    mfu,
    update_hardware_gauges,
)
from scaling_tpu.obs.registry import MetricsRegistry

# hand-computed reference config: H=512, L=4, V=1000, S=128, mlp_factor=4
H, L, V, S = 512, 4, 1000, 128
# per layer: 4*H^2 (qkv+dense) + 2*4*H^2 (mlp) = 12*H^2 = 3145728
# total: 4 * 3145728 + 1000*512 = 12582912 + 512000 = 13094912
PARAMS = 13094912
# 6N + 12*L*H*S = 78569472 + 3145728 = 81715200
FLOPS_PER_TOKEN = 81715200.0


def test_parameter_count_hand_computed():
    assert get_model_parameter_count(H, L, V, 4.0, glu=False) == PARAMS


def test_flops_per_token_hand_computed():
    assert get_flops_per_token(PARAMS, L, H, S) == FLOPS_PER_TOKEN


def test_achieved_tflops_and_mfu_hand_computed():
    tokens_per_step = 8 * S  # global batch 8
    step_time = 0.5
    ach = achieved_tflops(FLOPS_PER_TOKEN, tokens_per_step, step_time)
    assert ach == pytest.approx(
        FLOPS_PER_TOKEN * tokens_per_step / 0.5 / 1e12
    )
    u = mfu(ach, world_size=4, peak_tflops_per_device=275.0)
    assert u == pytest.approx(ach / (4 * 275.0))


def test_mfu_matches_palm_reference_estimator():
    """Our decomposed (flops_per_token, achieved, mfu) pipeline must land
    on the same number as the monolithic get_palm_mfu the transformer
    entrypoint logs — one accounting, two call paths."""
    tokens_per_step = 8 * S
    step_time = 0.5
    tokens_per_second = tokens_per_step / step_time
    reference = get_palm_mfu(
        PARAMS, L, H, S, tokens_per_second, world_size=4,
        hardware=HardwareType.TPU_V4,
    )
    ours = mfu(
        achieved_tflops(FLOPS_PER_TOKEN, tokens_per_step, step_time),
        world_size=4, peak_tflops_per_device=HardwareType.TPU_V4.max_tflops,
    )
    assert ours == pytest.approx(reference)


def test_step_time_ema():
    ema = StepTimeEMA(alpha=0.5)
    assert ema.update(1.0) == 1.0  # first sample seeds
    assert ema.update(2.0) == pytest.approx(1.5)
    assert ema.update(2.0) == pytest.approx(1.75)


def test_device_memory_snapshot_cpu_safe():
    snap = device_memory_snapshot()
    assert snap, "no local devices?"
    for rec in snap:
        assert rec["bytes_in_use"] >= 0
        assert rec["peak_bytes_in_use"] >= 0
        assert rec["peak_bytes_reserved"] >= 0
        assert "platform" in rec


def test_update_hardware_gauges_sets_registry():
    reg = MetricsRegistry()
    summary = update_hardware_gauges(reg)
    assert set(summary) == {
        "device_bytes_in_use", "device_peak_bytes_in_use",
        "device_peak_bytes_reserved", "live_arrays"
    }
    snap = reg.snapshot()["gauges"]
    assert "live_arrays" in snap
    assert any(k.startswith("device_bytes_in_use{") for k in snap)
    assert any(k.startswith("device_peak_bytes_reserved{") for k in snap)


def test_hardware_gauges_carry_the_reserved_high_water_mark(monkeypatch):
    """The TPU runtime keeps ``peak_bytes_reserved`` (a running program's
    temporaries) apart from ``peak_bytes_in_use`` (live arrays): a gauge
    of the second alone understates the peak (PERF.md, Findings PR 24)."""
    import jax

    class Device:
        id, platform = 0, "tpu"

        def memory_stats(self):
            return {"bytes_in_use": 5, "peak_bytes_in_use": 7,
                    "peak_bytes_reserved": 11, "bytes_limit": 16}

    monkeypatch.setattr(jax, "local_devices", lambda: [Device()])
    (rec,) = device_memory_snapshot()
    assert (rec["peak_bytes_in_use"], rec["peak_bytes_reserved"]) == (7, 11)
    reg = MetricsRegistry()
    assert update_hardware_gauges(reg)["device_peak_bytes_reserved"] == 11
    assert reg.snapshot()["gauges"][
        "device_peak_bytes_reserved{device=0}"] == 11


def test_peak_table_v5e_published_values():
    """Google Cloud "TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM. The v5e
    reports itself as "TPU v5 lite"."""
    hw = HardwareType.from_device_kind("TPU v5 lite")
    assert hw is HardwareType.TPU_V5E
    assert (hw.max_tflops, hw.hbm_gbps) == (197.0, 819.0)


def test_peak_table_unknown_device_kind_raises():
    """An assumed peak makes every MFU wrong without saying so."""
    with pytest.raises(ValueError, match="no published peak"):
        HardwareType.from_device_kind("TPU v9 mega")


def test_no_mfu_peak_on_the_cpu_backend():
    """The CPU has no published matmul peak: the trainer logs no MFU there
    (train.py reads detect_hardware) rather than one against a TPU's."""
    from scaling_tpu.models.transformer.utils.get_tflops import detect_hardware

    assert detect_hardware() is None
