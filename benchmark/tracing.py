"""The traced run's profiler slice: a short steady part of the window."""

from __future__ import annotations

import shutil
import time
from pathlib import Path
from typing import Optional


class Tracer:
    """Starts ``jax.profiler`` once ``start_after_s`` of the window have
    passed and stops it at the first chunk or tick boundary ``min_s`` later.
    With ``enabled`` false every call is a no-op."""

    def __init__(self, enabled: bool, out_dir: Path, start_after_s: float,
                 min_s: float):
        self.enabled, self.out_dir = enabled, Path(out_dir)
        self.start_after_s, self.min_s = start_after_s, min_s
        self.started_at: Optional[float] = None
        self.stopped_at: Optional[float] = None

    @property
    def active(self) -> bool:
        return self.started_at is not None and self.stopped_at is None

    def maybe_start(self, t_in_window: float) -> None:
        if (not self.enabled or self.started_at is not None
                or t_in_window < self.start_after_s):
            return
        import jax

        shutil.rmtree(self.out_dir, ignore_errors=True)
        self.out_dir.mkdir(parents=True)
        jax.profiler.start_trace(str(self.out_dir))
        self.started_at = time.monotonic()

    def maybe_stop(self, force: bool = False) -> None:
        if not self.active:
            return
        if force or time.monotonic() - self.started_at >= self.min_s:
            import jax

            jax.profiler.stop_trace()
            self.stopped_at = time.monotonic()

    def trace_file(self) -> Optional[Path]:
        files = sorted(self.out_dir.glob("**/*.xplane.pb"))
        return files[-1] if files else None
