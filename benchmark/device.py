"""The device the run is on: claimed once, named in every result."""

from __future__ import annotations

import sys
from typing import List


class CompileCounter:
    """Counts the programs JAX lowers (each new jitted shape lowers once,
    whether or not the persistent cache then serves its executable), so a
    run can show that nothing compiled inside its measured window."""

    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        from jax._src import monitoring

        self.count = 0
        monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name, duration, **kwargs):
        if name == self.EVENT:
            self.count += 1


def claim_device(chips: int, rehearse: bool) -> dict:
    """First contact with JAX. Without a TPU, or with fewer chips than the
    cell asks for, the run ends here with no result line; ``rehearse`` is
    the CPU walk-through, which never prints one either."""
    import jax

    from scaling_tpu.compile_cache import enable_compile_cache

    devices = jax.devices()
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    if rehearse:
        if device["platform"] != "cpu":
            sys.exit("benchmark: --rehearse is the CPU walk-through "
                     "(JAX_PLATFORMS=cpu)")
    elif device["platform"] != "tpu":
        sys.exit(f"benchmark: no TPU (JAX found {device}); a cell is measured "
                 "on the chip only (see --rehearse)")
    if len(devices) < chips:
        sys.exit(f"benchmark: the cell needs {chips} chip(s), JAX found "
                 f"{len(devices)}")
    cache = enable_compile_cache()
    print(f"device: {device} used={chips} compile_cache={cache}",
          file=sys.stderr, flush=True)
    return device


def live_bytes(devices) -> List[int]:
    """Bytes of live arrays on each device now (the kinds call this as the
    window opens, when what is live is what the window keeps)."""
    return [int((d.memory_stats() or {}).get("bytes_in_use", 0)) for d in devices]


def memory_peaks(devices, live_at_window: List[int]) -> List[int]:
    """Peak HBM per device. The TPU runtime counts live arrays
    (``peak_bytes_in_use``) and the most it reserved for a running program's
    temporaries (``peak_bytes_reserved``) apart, each with its own high-water
    mark. In the window the two coincide: the peak there is the arrays live
    as the window opens plus the largest reservation (PR 22: that sum matched
    the compiler's buffer assignment; ``peak_bytes_in_use`` alone, which the
    trainer's gauge reports, understates it). Set-up can hold more live
    arrays than the window does (weights being made, the reference's float32
    head), at a time when little is reserved, so the two high-water marks may
    not be added: on four chips their sum read 21 GB on a 16 GB chip. The
    peak is the larger of the window's peak and set-up's live high-water."""
    peaks = []
    for d, live in zip(devices, live_at_window):
        stats = d.memory_stats() or {}
        print(f"memory_stats[{d.id}]: { {k: v for k, v in stats.items() if 'bytes' in k} } "
              f"live as the window opened: {live}", file=sys.stderr)
        peaks.append(max(int(stats.get("peak_bytes_in_use", 0)),
                         live + int(stats.get("peak_bytes_reserved", 0))))
    return peaks
