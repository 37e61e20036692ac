"""TFLOPs and MFU estimators.

(reference: src/scaling/transformer/utils/get_tflops.py:12-401) — the same
five estimator families, with the hardware peak table swapped from GPUs to
TPU generations (bf16 peak per chip; public cloud.google.com figures).
"""

from __future__ import annotations

from enum import Enum
from typing import Optional


class HardwareType(Enum):
    TPU_V4 = "tpu_v4"
    TPU_V5E = "tpu_v5e"
    TPU_V5P = "tpu_v5p"
    TPU_V6E = "tpu_v6e"
    A100 = "a100"
    H100 = "h100"

    @property
    def max_tflops(self) -> float:
        """Published dense bf16 peak per chip."""
        return _PEAKS[self][0]

    @property
    def hbm_gbps(self) -> float:
        """Published HBM bandwidth per chip, GB/s."""
        return _PEAKS[self][1]

    @classmethod
    def from_device_kind(cls, device_kind: str) -> "HardwareType":
        """The chip behind ``jax.devices()[0].device_kind``. A kind that
        is not in the table raises: an assumed peak makes every MFU
        computed from it wrong without saying so."""
        try:
            return _DEVICE_KINDS[device_kind]
        except KeyError:
            raise ValueError(
                f"no published peak for device_kind {device_kind!r}; add it "
                f"to the table in {__name__} (known: {sorted(_DEVICE_KINDS)})"
            ) from None


# (bf16 TFLOP/s, HBM GB/s) per chip. TPUs: Google Cloud documentation, the
# "TPU v4" / "TPU v5e" / "TPU v5p" / "TPU v6e" system-architecture pages;
# GPUs: NVIDIA A100 / H100 SXM datasheets (dense, no sparsity).
_PEAKS = {
    HardwareType.TPU_V4: (275.0, 1200.0),
    HardwareType.TPU_V5E: (197.0, 819.0),
    HardwareType.TPU_V5P: (459.0, 2765.0),
    HardwareType.TPU_V6E: (918.0, 1640.0),
    HardwareType.A100: (312.0, 2039.0),
    HardwareType.H100: (989.4, 3350.0),
}

# ``device_kind`` as the JAX TPU runtime spells it
_DEVICE_KINDS = {
    "TPU v4": HardwareType.TPU_V4,
    "TPU v5 lite": HardwareType.TPU_V5E,
    "TPU v5e": HardwareType.TPU_V5E,
    "TPU v5p": HardwareType.TPU_V5P,
    "TPU v5": HardwareType.TPU_V5P,
    "TPU v6 lite": HardwareType.TPU_V6E,
    "TPU v6e": HardwareType.TPU_V6E,
}


def detect_hardware() -> Optional[HardwareType]:
    """The attached accelerator's row of the peak table, or None on the
    CPU backend (a host CPU has no published matmul peak, so no MFU is
    computed there). An accelerator of unknown kind raises."""
    import jax

    device = jax.devices()[0]
    if device.platform == "cpu":
        return None
    return HardwareType.from_device_kind(device.device_kind)


def get_model_parameter_count(
    hidden_size: int, num_layers: int, vocab_size: int,
    mlp_factor: float = 4.0, glu: bool = False,
) -> int:
    per_layer = 4 * hidden_size * hidden_size + (3 if glu else 2) * int(
        hidden_size * hidden_size * mlp_factor
    )
    return num_layers * per_layer + vocab_size * hidden_size


def get_tflops_megatron(
    parameter_count: int,
    iter_time_s: float,
    global_batch_size: int,
    sequence_length: int,
) -> float:
    """6 * N * tokens (reference: get_tflops.py:319-334)."""
    flops = 6.0 * parameter_count * global_batch_size * sequence_length
    return flops / iter_time_s / 1e12


def get_tflops_bloom(
    hidden_size: int,
    num_layers: int,
    vocab_size: int,
    iter_time_s: float,
    global_batch_size: int,
    sequence_length: int,
    activation_checkpointing: bool = False,
) -> float:
    """Megatron-paper Appendix formula with the 4/3 recompute factor
    (reference: get_tflops.py:245-316)."""
    coeff = 4.0 if activation_checkpointing else 3.0
    flops = (
        24.0 * coeff * global_batch_size * sequence_length * num_layers * hidden_size**2
        * (
            1.0
            + sequence_length / (6.0 * hidden_size)
            + vocab_size / (16.0 * num_layers * hidden_size)
        )
    )
    return flops / iter_time_s / 1e12


def get_tflops_electra(
    hidden_size: int,
    num_layers: int,
    num_attention_heads: int,
    vocab_size: int,
    sequence_length: int,
    iter_time_s: float,
    global_batch_size: int,
    mlp_factor: float = 4.0,
) -> float:
    """Per-op forward count x3 for fwd+bwd (reference: get_tflops.py:128-242)."""
    head_dim = hidden_size // num_attention_heads
    attn = (
        3 * 2 * hidden_size * hidden_size  # qkv
        + 2 * num_attention_heads * sequence_length * head_dim  # scores
        + 2 * num_attention_heads * sequence_length * head_dim  # context
        + 2 * hidden_size * hidden_size  # dense
    )
    mlp = 2 * 2 * int(hidden_size * hidden_size * mlp_factor)
    per_token = num_layers * (attn + mlp) + 2 * hidden_size * vocab_size
    flops = 3.0 * per_token * global_batch_size * sequence_length
    return flops / iter_time_s / 1e12


def get_tflops_aleph_alpha(
    hidden_size: int,
    num_layers: int,
    num_attention_heads: int,
    vocab_size: int,
    sequence_length: int,
    iter_time_s: float,
    global_batch_size: int,
    mlp_factor: float = 4.0,
) -> float:
    """House estimator incl. attention quadratic term
    (reference: get_tflops.py:12-125)."""
    qkv = 6 * hidden_size * hidden_size
    scores = 2 * sequence_length * hidden_size
    ctx = 2 * sequence_length * hidden_size
    dense = 2 * hidden_size * hidden_size
    mlp = 4 * int(hidden_size * hidden_size * mlp_factor)
    lm_head = 2 * hidden_size * vocab_size
    per_token = num_layers * (qkv + scores + ctx + dense + mlp) + lm_head
    flops = 3.0 * per_token * global_batch_size * sequence_length
    return flops / iter_time_s / 1e12


def get_flops_per_token(
    parameter_count: int,
    num_layers: int,
    hidden_size: int,
    sequence_length: int,
) -> float:
    """PaLM appendix-B train FLOPs per token: ``6N`` matmul plus the
    ``12 L H S`` attention quadratic term. This is the single number the
    obs telemetry layer needs from a model to turn step time into
    achieved-TFLOPs/MFU gauges (docs/OBSERVABILITY.md)."""
    return (
        6.0 * parameter_count
        + 12.0 * num_layers * hidden_size * sequence_length
    )


def get_palm_mfu(
    parameter_count: int,
    num_layers: int,
    hidden_size: int,
    sequence_length: int,
    tokens_per_second: float,
    world_size: int,
    hardware: HardwareType,
) -> float:
    """PaLM appendix-B MFU: observed tokens/s over peak-flop token rate
    (reference: get_tflops.py:337-401)."""
    flops_per_token = get_flops_per_token(
        parameter_count, num_layers, hidden_size, sequence_length
    )
    peak_tokens_per_second = hardware.max_tflops * 1e12 * world_size / flops_per_token
    return tokens_per_second / peak_tokens_per_second
