"""Hardware gauges: device memory, live arrays, step-time EMA, MFU.

Everything here is host-side bookkeeping — ``memory_stats()`` is a
runtime query against the allocator and ``jax.live_arrays()`` walks the
client's tracking table; neither blocks on device work, so the per-step
gauge update adds NO device syncs (unit-asserted in
tests/core/test_obs/test_step_path.py).

MFU follows the PaLM appendix-B accounting the transformer entrypoint
already logs (models/transformer/utils/get_tflops.py): the model
declares its FLOPs-per-token estimate once, the trainer divides achieved
token throughput by the hardware's peak-flop token rate. jax imports
stay inside functions so the analyzer CLI never pays backend init.
"""

from __future__ import annotations

from typing import Dict, List, Optional

from .registry import MetricsRegistry, get_registry


def device_memory_snapshot() -> List[Dict]:
    """Per-local-device allocator stats; zeros where the backend keeps
    none (CPU)."""
    import jax

    out: List[Dict] = []
    for d in jax.local_devices():
        try:
            stats = d.memory_stats()
        except (RuntimeError, NotImplementedError):
            # some backends raise rather than returning None
            stats = None
        stats = stats or {}
        out.append({
            "device": d.id,
            "platform": d.platform,
            "bytes_in_use": int(stats.get("bytes_in_use", 0)),
            "peak_bytes_in_use": int(stats.get("peak_bytes_in_use", 0)),
            # the most the runtime reserved for a running program's
            # temporaries: its own high-water mark, of another moment
            # than peak_bytes_in_use (docs/OBSERVABILITY.md "Metric names")
            "peak_bytes_reserved": int(stats.get("peak_bytes_reserved", 0)),
            "bytes_limit": int(stats.get("bytes_limit", 0)),
        })
    return out


def update_hardware_gauges(registry: Optional[MetricsRegistry] = None) -> Dict:
    """Refresh device-memory and live-array gauges; returns an aggregate
    summary (max across local devices) for merging into step metrics."""
    import jax

    reg = registry if registry is not None else get_registry()
    max_in_use = 0
    max_peak = 0
    max_reserved = 0
    for rec in device_memory_snapshot():
        labels = {"device": str(rec["device"])}
        reg.gauge("device_bytes_in_use", labels).set(rec["bytes_in_use"])
        reg.gauge("device_peak_bytes_in_use", labels).set(
            rec["peak_bytes_in_use"]
        )
        reg.gauge("device_peak_bytes_reserved", labels).set(
            rec["peak_bytes_reserved"]
        )
        max_in_use = max(max_in_use, rec["bytes_in_use"])
        max_peak = max(max_peak, rec["peak_bytes_in_use"])
        max_reserved = max(max_reserved, rec["peak_bytes_reserved"])
    live = len(jax.live_arrays())
    reg.gauge("live_arrays").set(live)
    return {
        "device_bytes_in_use": max_in_use,
        "device_peak_bytes_in_use": max_peak,
        "device_peak_bytes_reserved": max_reserved,
        "live_arrays": live,
    }


def _kernel_builds(kernel: str, interpret: bool):
    return get_registry().counter(
        "kernel_builds",
        {"kernel": kernel, "interpret": str(bool(interpret)).lower()},
    )


def count_kernel_build(kernel: str, interpret: bool) -> None:
    """Count one Pallas kernel build (trace), by kernel name and by whether
    it was built for the interpreter, so that a run on the chip asserts
    the path it took instead of inferring it from the configuration."""
    _kernel_builds(kernel, interpret).inc()


def kernel_build_count(kernel: str, interpret: bool) -> int:
    """How often ``kernel`` was built so far, compiled (``interpret=False``)
    or for the interpreter (chip_smoke.py and the benchmark's ``correct``
    assert on it)."""
    return int(_kernel_builds(kernel, interpret).value)


class StepTimeEMA:
    """Exponential moving average of fetched step durations — the smooth
    signal regression gates and dashboards want, next to the raw
    per-step value."""

    def __init__(self, alpha: float = 0.1):
        assert 0 < alpha <= 1
        self.alpha = alpha
        self.value: Optional[float] = None

    def update(self, duration_s: float) -> float:
        if self.value is None:
            self.value = float(duration_s)
        else:
            self.value = (
                self.alpha * float(duration_s) + (1 - self.alpha) * self.value
            )
        return self.value


def achieved_tflops(flops_per_token: float, tokens_per_step: float,
                    step_time_s: float) -> float:
    """Model-FLOPs throughput actually sustained, pod-wide."""
    return flops_per_token * tokens_per_step / step_time_s / 1e12


def mfu(achieved_tflops_total: float, world_size: int,
        peak_tflops_per_device: float) -> float:
    """Model FLOPs Utilization: achieved over the pod's peak."""
    return achieved_tflops_total / (world_size * peak_tflops_per_device)
