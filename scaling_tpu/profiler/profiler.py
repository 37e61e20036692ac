"""Profiling: synchronized timers, a step-windowed collector, XLA traces.

(reference: src/scaling/core/profiler/ — ``SynchronizedTimer`` brackets with
``torch.cuda.synchronize`` (timer.py:16-23); ``Profiler`` collects
per-instruction observations inside a configured step window and gathers
them to rank 0 as JSON (profiler.py:79-104)). The TPU equivalents:

- ``SynchronizedTimer`` brackets with ``jax.block_until_ready`` — the
  single-controller analogue of a device sync;
- the instruction loop is one fused XLA program, so per-instruction timers
  become per-step phase timers (data load / step / sync) plus an optional
  ``jax.profiler`` trace of the window (started and stopped through
  ``obs.start_capture`` / ``stop_capture``, so the trainer's ``step.*``
  spans lie on its host plane), which exposes the true per-op schedule
  in TensorBoard / Perfetto — strictly more detail than the reference's
  hand-rolled instruction timers;
- observations are written as one JSON, feeding the pipeline schedule
  simulator (parallel/pipeline_schedule.py) exactly like the reference's
  profile JSON feeds its SimulationEngine (base.py:276-595).
"""

from __future__ import annotations

import json
import time
from pathlib import Path
from typing import Any, Dict, List, Optional

import jax
from pydantic import Field

from ..config import BaseConfig
from ..logging import logger
from ..obs.capture import start_capture, stop_capture


class ProfilerConfig(BaseConfig):
    profile_steps: int = Field(0, description="number of steps to profile; 0 disables")
    profile_start_at_step: int = Field(
        10, description="first profiled step (skips compile/warmup)"
    )
    profiler_output: Optional[Path] = Field(
        None, description="where the observations JSON (and XLA trace dir) go"
    )
    capture_xla_trace: bool = Field(
        False, description="also capture a jax.profiler trace of the window "
        "(TensorBoard/Perfetto-compatible)"
    )


class SynchronizedTimer:
    """Wall clock around device work; stop() drains outstanding computation
    so the measured span covers it (reference: timer.py:7-35)."""

    def __init__(self, name: str = ""):
        self.name = name
        self._start: Optional[float] = None
        self.durations: List[float] = []

    def start(self) -> None:
        self._start = time.perf_counter()

    def stop(self, wait_for: Any = None) -> float:
        if wait_for is not None:
            jax.block_until_ready(wait_for)
        assert self._start is not None, "timer not started"
        d = time.perf_counter() - self._start
        self.durations.append(d)
        self._start = None
        return d

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.stop()


class Profiler:
    """Collects per-step phase timings inside the configured window."""

    def __init__(self, config: Optional[ProfilerConfig] = None):
        self.config = config or ProfilerConfig()
        self.observations: List[Dict[str, Any]] = []
        self._tracing = False

    def enabled_at(self, step: int) -> bool:
        c = self.config
        return (
            c.profile_steps > 0
            and c.profile_start_at_step <= step < c.profile_start_at_step + c.profile_steps
        )

    def begin_step(self, step: int) -> None:
        c = self.config
        if (
            c.capture_xla_trace
            and c.profiler_output is not None
            and step == c.profile_start_at_step
            and not self._tracing
        ):
            # through the one control (obs/capture.py): the step spans
            # of the window land on the profiler's clock with it
            start_capture(Path(c.profiler_output).parent / "xla_trace")
            self._tracing = True

    def record(self, step: int, durations: Dict[str, float]) -> None:
        if not self.enabled_at(step):
            return
        self.observations.append({"step": step, **durations})

    def end_step(self, step: int) -> None:
        c = self.config
        last = c.profile_start_at_step + c.profile_steps - 1
        if step == last:
            if self._tracing:
                self._tracing = False
                stop_capture()
            self.flush()

    def close(self) -> None:
        """Abort-safe drain: stop an active XLA trace and flush whatever
        the window collected so far. A run that dies mid-window
        (NonFiniteLossError, SIGTERM drain, watchdog stall) previously
        lost EVERY observation and left the trace running; the trainer
        calls this from its ``finally`` so partial observations land.
        Idempotent — flush rewrites the same JSON on a clean exit."""
        if self._tracing:
            self._tracing = False
            try:
                stop_capture()
            except RuntimeError as e:
                logger.warning(f"could not stop in-flight XLA trace: {e!r}")
        self.flush()

    def flush(self) -> None:
        if self.config.profiler_output is None or not self.observations:
            return
        out = Path(self.config.profiler_output)
        out.parent.mkdir(parents=True, exist_ok=True)
        out.write_text(json.dumps(self.observations, indent=2))
        logger.info(f"profiler: wrote {len(self.observations)} observations to {out}")
