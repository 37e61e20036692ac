"""The one control that starts and stops tracing in a running process.

``start_capture(out_dir)`` starts ``jax.profiler`` into ``out_dir``,
switches the program's spans onto it and snapshots the registry's
counters; ``stop_capture()`` stops the profiler and returns a
:class:`Capture`. Nothing else in ``scaling_tpu`` or ``benchmark`` calls
``jax.profiler.start_trace`` / ``stop_trace``: the trainer's
``Profiler`` (by step number) and the benchmark's ``Tracer`` (by window
time) both go through here, so a trace can be taken from a process that
is already running, more than once, and the spans always know.

While a capture is on, every :func:`obs.span` also opens a
``jax.profiler.TraceAnnotation`` of its name. That puts it on the host
plane of the ``.xplane.pb`` (the line of the thread that ran it), on the
clock the device's ``XLA Ops`` are on, so an idle gap of the chip can be
laid beside the phase of the host it fell into. The span is also kept in
the capture's own list, exactly (the ``span_seconds`` histogram is
bucketed: no median can be read back from it). While none is on a span
does neither and pays one global read.

jax is imported lazily, as everywhere in ``obs``.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from pathlib import Path
from typing import Any, Dict, List, Optional, Tuple

from .registry import MetricsRegistry, get_registry

# (name, start_ns, duration_ns, fields): start_ns counts from the start
# of the capture, which is the origin the profiler gives its planes to
# within the time start_trace takes to return; fields holds the span's
# step, its parent's name and its scalar annotations (and lists of
# numbers: a looped model's exit distribution)
SpanRow = Tuple[str, int, int, Dict[str, Any]]


@dataclasses.dataclass
class Capture:
    """What one capture held."""

    trace_dir: str              # where the profiler wrote (plugins/profile/...)
    seconds: float              # start_capture's return to stop_capture's call
    spans: List[SpanRow]        # every span opened and closed inside, in closing order
    counters: Dict[str, float]  # registry counters that moved: rendered name -> difference

    def trace_file(self) -> Optional[Path]:
        """The newest ``.xplane.pb`` under ``trace_dir``."""
        files = sorted(Path(self.trace_dir).glob("**/*.xplane.pb"))
        return files[-1] if files else None


class _Active:
    """A capture in progress: what a span needs at entry and exit."""

    __slots__ = ("trace_dir", "origin", "started", "spans",
                 "counters_before", "registry", "annotation")

    def __init__(self, trace_dir: str, registry: MetricsRegistry, annotation):
        self.trace_dir = trace_dir
        self.registry = registry
        self.annotation = annotation  # jax.profiler.TraceAnnotation
        self.spans: List[SpanRow] = []
        self.counters_before = registry.snapshot()["counters"]
        self.origin = time.perf_counter()
        self.started = 0.0

    def close_span(self, sp, parent: Optional[str], step: Optional[int],
                   start: float, duration: float) -> None:
        """Keep a span that was opened under this capture, if it is
        still on (``start``: the span's ``time.perf_counter()``)."""
        if _active is not self:
            return
        fields = {k: v for k, v in sp.fields.items()
                  if isinstance(v, (bool, int, float, str))
                  or (isinstance(v, list) and v
                      and all(isinstance(x, (int, float)) for x in v))}
        if step is not None:
            fields["step"] = step
        if parent is not None:
            fields["parent"] = parent
        self.spans.append((sp.name, int((start - self.origin) * 1e9),
                           int(duration * 1e9), fields))


_lock = threading.Lock()  # start/stop only; a span reads _active without it
_active: Optional[_Active] = None
_last: Optional[Capture] = None


def capturing() -> bool:
    return _active is not None


def active() -> Optional[_Active]:
    """The capture in progress, for :func:`obs.span`."""
    return _active


def last_capture() -> Optional[Capture]:
    """The record of the newest finished capture of this process."""
    return _last


def start_capture(out_dir, registry: Optional[MetricsRegistry] = None) -> None:
    """Start the profiler into ``out_dir`` (created if missing) and turn
    the spans' third sink on. One capture at a time."""
    global _active
    import jax

    with _lock:
        if _active is not None:
            raise RuntimeError(
                f"start_capture: a capture into {_active.trace_dir} is "
                "already on; stop_capture() it first")
        Path(out_dir).mkdir(parents=True, exist_ok=True)
        cap = _Active(str(out_dir),
                      registry if registry is not None else get_registry(),
                      jax.profiler.TraceAnnotation)
        jax.profiler.start_trace(str(out_dir))
        cap.started = time.perf_counter()
        _active = cap


def stop_capture() -> Capture:
    """Stop the profiler and return the capture's record (kept for
    :func:`last_capture`). Safe in a ``finally``: the control is off and
    the record kept before the profiler is asked to stop, so a profiler
    that fails to write leaves the next ``start_capture`` free."""
    global _active, _last
    import jax

    with _lock:
        cap = _active
        if cap is None:
            raise RuntimeError("stop_capture: no capture is on")
        seconds = time.perf_counter() - cap.started
        _active = None
        after = cap.registry.snapshot()["counters"]
        moved = {k: v - cap.counters_before.get(k, 0.0) for k, v in after.items()
                 if v != cap.counters_before.get(k, 0.0)}
        _last = Capture(trace_dir=cap.trace_dir, seconds=seconds,
                        spans=cap.spans, counters=moved)
        jax.profiler.stop_trace()
        return _last
