"""Unified telemetry (ISSUE 5, docs/OBSERVABILITY.md).

- :mod:`.registry` — process-wide metrics registry (counters, gauges,
  histograms with labels), per-step JSONL flush + Prometheus textfile.
- :mod:`.spans` — ``with obs.span("ckpt.commit", step=N)`` phase
  tracing, emitted through ``logger.log_event`` into the same stream as
  the supervision events.
- :mod:`.recorder` — the bounded ring every closed span lands in,
  exactly, capture or not: ``recorded_spans`` / ``record_span``.
- :mod:`.compile_events` — JAX's trace / lower / compile / cache-load
  events as rows of that ring and counters of the registry, by program
  name: the account of a process's set-up.
- :mod:`.capture` — the one start/stop control for all tracing of a
  running process: the profiler, the spans' annotations on its clock,
  and the recorder's rows between its two markers.
- :mod:`.hardware` — device memory / live-array gauges, step-time EMA,
  achieved-TFLOPs and MFU math.
- :mod:`.telemetry` — the per-step driver the trainer owns.
- :mod:`.report` / ``python -m scaling_tpu.obs`` — run-dir analyzer
  turning events + metrics JSONL into a health report.

jax-free at import time (functions import it lazily): the analyzer CLI
and the supervisor's relaunch path must not pay backend init.
"""

from .capture import (
    Capture,
    capturing,
    last_capture,
    settle_at_capture_edges,
    start_capture,
    stop_capture,
)
from .hardware import (
    StepTimeEMA,
    achieved_tflops,
    count_kernel_build,
    device_memory_snapshot,
    kernel_build_count,
    mfu,
    update_hardware_gauges,
)
from .recorder import (
    Row,
    process_start_s,
    record_span,
    recorded_spans,
    recorded_tail,
)
from .registry import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    get_registry,
    host_id,
)
from .spans import (
    Span,
    current_span,
    current_trace,
    current_trace_id,
    derive_trace_id,
    new_trace_id,
    span,
    trace_context,
)
from .telemetry import StepTelemetry

__all__ = [
    "Capture",
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "Row",
    "Span",
    "StepTelemetry",
    "StepTimeEMA",
    "achieved_tflops",
    "capturing",
    "count_kernel_build",
    "current_span",
    "current_trace",
    "current_trace_id",
    "derive_trace_id",
    "device_memory_snapshot",
    "get_registry",
    "host_id",
    "kernel_build_count",
    "last_capture",
    "mfu",
    "new_trace_id",
    "process_start_s",
    "record_span",
    "recorded_spans",
    "recorded_tail",
    "settle_at_capture_edges",
    "span",
    "start_capture",
    "stop_capture",
    "trace_context",
    "update_hardware_gauges",
]
