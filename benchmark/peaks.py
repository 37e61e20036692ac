"""Published peaks per chip, keyed by ``device_kind`` as JAX spells it.

Copied from ``scaling_tpu/models/transformer/utils/get_tflops.py`` (``_PEAKS``
and ``_DEVICE_KINDS``) so that no later PR can move the yardstick. Source:
Google Cloud documentation, system-architecture pages "TPU v4", "TPU v5e",
"TPU v5p", "TPU v6e" (dense bf16 TFLOP/s and HBM GB/s per chip). A device
that is not in the table is an error, not a default.
"""

from __future__ import annotations

# device_kind -> (bf16 FLOP/s, HBM bytes/s, HBM bytes)
PEAKS = {
    "TPU v4": (275e12, 1200e9, 32e9),
    "TPU v5 lite": (197e12, 819e9, 16e9),
    "TPU v5e": (197e12, 819e9, 16e9),
    "TPU v5p": (459e12, 2765e9, 95e9),
    "TPU v5": (459e12, 2765e9, 95e9),
    "TPU v6 lite": (918e12, 1640e9, 32e9),
    "TPU v6e": (918e12, 1640e9, 32e9),
}


def peaks_of(device_kind: str) -> dict:
    try:
        flops, hbm_bw, hbm = PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peak for device_kind {device_kind!r}; add it to "
            f"benchmark/peaks.py with its source (known: {sorted(PEAKS)})"
        ) from None
    return {"flops_per_s": flops, "hbm_bytes_per_s": hbm_bw, "hbm_bytes": hbm}
