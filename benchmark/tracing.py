"""The traced run's profiler slice: a short steady part of the window
(``--trace 1``), or a few seconds of the same traffic after the measured
window has closed (``--trace 2``)."""

from __future__ import annotations

import shutil
import time
from pathlib import Path
from typing import Optional


class Tracer:
    """Starts a capture (``scaling_tpu.obs.start_capture``: the profiler,
    and the program's spans onto its clock) once ``start_after_s`` of the
    window have passed and stops it at the first chunk or tick boundary
    ``min_s`` later. With ``enabled`` false every call is a no-op."""

    def __init__(self, enabled: bool, out_dir: Path, start_after_s: float,
                 min_s: float, after_window: bool = False):
        self.enabled, self.out_dir = enabled, Path(out_dir)
        self.start_after_s, self.min_s = start_after_s, min_s
        self.after_window = after_window
        self.started_at: Optional[float] = None
        self.stopped_at: Optional[float] = None

    def open_after_window(self) -> None:
        """``--trace 2``: the measured window has closed untraced and its
        numbers are taken. Starts and stops the profiler once and throws
        that trace away, so that what its first start costs falls into no
        number, then arms this tracer: the kind goes on with the same
        traffic, calls ``maybe_start(0)`` when the traced part shall begin
        and ``maybe_stop()`` at each boundary until ``active`` is false
        again (``stopped_at`` is set)."""
        from scaling_tpu.obs import start_capture, stop_capture

        shutil.rmtree(self.out_dir, ignore_errors=True)
        start_capture(self.out_dir)
        stop_capture()
        self.enabled, self.start_after_s = True, 0.0

    @property
    def active(self) -> bool:
        return self.started_at is not None and self.stopped_at is None

    def maybe_start(self, t_in_window: float) -> None:
        if (not self.enabled or self.started_at is not None
                or t_in_window < self.start_after_s):
            return
        from scaling_tpu.obs import start_capture

        shutil.rmtree(self.out_dir, ignore_errors=True)
        start_capture(self.out_dir)
        self.started_at = time.monotonic()

    def maybe_stop(self, force: bool = False) -> None:
        if not self.active:
            return
        if force or time.monotonic() - self.started_at >= self.min_s:
            from scaling_tpu.obs import stop_capture

            stop_capture()
            self.stopped_at = time.monotonic()

    def trace_file(self) -> Optional[Path]:
        from scaling_tpu.obs import last_capture

        capture = last_capture() if self.started_at is not None else None
        return capture.trace_file() if capture else None

    def discard(self) -> None:
        """The trace is reduced: delete it."""
        shutil.rmtree(self.out_dir, ignore_errors=True)
