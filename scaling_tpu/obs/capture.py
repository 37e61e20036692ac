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
``jax.profiler.TraceAnnotation`` of its name, with its ``step`` (where
it has one) as the event's metadata. That puts it on the host plane of
the ``.xplane.pb`` (the line of the thread that ran it), on the clock
the device's ``XLA Ops`` are on, so an idle gap of the chip can be laid
beside the phase of the host it fell into, and a row of the recorder is
joined to its event by ``(name, step)``: :attr:`Capture.clock_offset_ns`
is what that join gives, the trace's clock minus the capture's. While
none is on a span does not and pays one global read.

A capture keeps no spans of its own. Every closed span lands in the
process's bounded recorder (``obs/recorder.py``) whether a capture is on
or not; ``start_capture`` and ``stop_capture`` each append a marker row
there (``obs.capture``, with the trace dir and its ``edge``), and
:attr:`Capture.spans` is the recorder's rows between the two.

The profiler runs with the interpreter's tracer OFF
(``python_tracer_level`` 0) unless ``python_frames=True``: that tracer
hooks every Python call, and on a serving tick's host code it made the
scheduler read 18 times what it costs (PERF.md, Findings PR 42). The
host tracer stays on, so the spans' annotations and the runtime's own
``PjitFunction(...)`` events still lie on the thread's line.

jax is imported lazily, as everywhere in ``obs``.
"""

from __future__ import annotations

import statistics
import threading
import weakref
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional, Tuple

from .recorder import CAPTURE_MARKER, _recorder, clock
from .registry import MetricsRegistry, get_registry

# (name, start_ns, duration_ns, fields): start_ns counts from the start
# of the capture (``Capture.origin_ns`` on the recorder's clock), which is
# the origin the profiler gives its planes to within the time start_trace
# takes to return: ``Capture.clock_offset_ns`` says how far within;
# fields holds the span's step, its parent's name and its scalar
# annotations (and lists of numbers: a looped model's exit distribution)
SpanRow = Tuple[str, int, int, Dict[str, Any]]
HOST_PLANE = "/host:CPU"


class Capture:
    """What one capture held."""

    def __init__(self, trace_dir: str, seconds: float,
                 counters: Dict[str, float],
                 spans: Optional[List[SpanRow]] = None,
                 markers: Optional[Tuple[tuple, tuple]] = None):
        self.trace_dir = trace_dir  # where the profiler wrote (plugins/profile/...)
        self.seconds = seconds      # start_capture's return to stop_capture's call
        self.counters = counters    # registry counters that moved: rendered name -> difference
        # a capture of THIS process is its two marker rows in the
        # recorder; ``spans=`` is for one rebuilt from a file (a test's
        # recorded capture), which has no recorder to cut from
        self._markers, self._given = markers, spans
        self._clock_offset_ns: Optional[float] = None

    @property
    def origin_ns(self) -> Optional[int]:
        """The capture's start on the recorder's clock: what a
        ``recorded_spans()`` row's ``start_ns`` is counted down by to lie
        beside :attr:`spans` (None for a capture rebuilt from a file)."""
        return None if self._markers is None else round(
            self._markers[0][1] * 1e9)

    @property
    def spans(self) -> List[SpanRow]:
        """Every span opened and closed inside, in closing order: cut
        from the recorder on each read, so read before ``RING_ROWS``
        more spans have closed."""
        if self._markers is None:
            return self._given if self._given is not None else []
        return _recorder.between(*self._markers)

    def trace_file(self) -> Optional[Path]:
        """The newest ``.xplane.pb`` under ``trace_dir``."""
        files = sorted(Path(self.trace_dir).glob("**/*.xplane.pb"))
        return files[-1] if files else None

    def annotations(self) -> List[Tuple[str, Optional[int], float, float]]:
        """The spans' annotations as the trace holds them, ``(name, step,
        start_ns, duration_ns)`` on the TRACE's clock (the device lines'),
        in order of time: the events of the host plane that bear a name
        of :attr:`spans`. Parses the ``.xplane.pb``; [] without one."""
        path = self.trace_file()
        names = {row[0] for row in self.spans}
        if path is None or not names:
            return []
        from jax.profiler import ProfileData

        out = []
        for plane in ProfileData.from_file(str(path)).planes:
            if plane.name != HOST_PLANE:
                continue
            for line in plane.lines:
                for event in line.events:
                    if event.name in names:
                        step = dict(event.stats).get("step")
                        out.append((event.name, step, float(event.start_ns),
                                    float(event.duration_ns)))
        return sorted(out, key=lambda a: a[2])

    @property
    def clock_offset_ns(self) -> Optional[float]:
        """The trace's clock minus the capture's: the median, over the
        spans that carry a ``step``, of the annotation's start minus the
        row's (joined by ``(name, step)``; a pair that occurs twice, as
        in a fleet whose replicas' steps collide, is left out). A
        ``spans`` row lies on the device's clock at ``start_ns +
        clock_offset_ns``, any row of the recorder at ``start_ns -
        origin_ns + clock_offset_ns``, to microseconds (an annotation
        opens just before its span reads the clock). None where nothing
        joins. Read once, then kept."""
        if self._clock_offset_ns is None:
            rows, seen = {}, {}
            for name, start, _, fields in self.spans:
                key = (name, fields.get("step"))
                rows[key] = None if key in rows else start
            for name, step, start, _ in self.annotations():
                seen[(name, step)] = None if (name, step) in seen else start
            diffs = [seen[key] - start for key, start in rows.items()
                     if key[1] is not None and start is not None
                     and seen.get(key) is not None]
            if diffs:
                self._clock_offset_ns = statistics.median(diffs)
        return self._clock_offset_ns


class _Active:
    """A capture in progress: what a span needs at entry."""

    __slots__ = ("trace_dir", "started", "marker", "counters_before",
                 "registry", "annotation")

    def __init__(self, trace_dir: str, registry: MetricsRegistry, annotation):
        self.trace_dir = trace_dir
        self.registry = registry
        self.annotation = annotation  # jax.profiler.TraceAnnotation
        self.counters_before = registry.snapshot()["counters"]
        self.started = 0.0       # start_trace's return, on the recorder's clock
        self.marker: tuple = ()  # the start marker's row


def _marker(trace_dir: str, edge: str, start: float, duration: float) -> tuple:
    row = (CAPTURE_MARKER, start, duration, None, None,
           {"trace_dir": trace_dir, "edge": edge})
    _recorder.append(row)
    return row


_lock = threading.Lock()  # start/stop only; a span reads _active without it
_active: Optional[_Active] = None
_last: Optional[Capture] = None
# bound methods called before a capture starts and before it stops, so that
# work a program has issued and not yet accounted for lands on the right side
# of the edge (:func:`settle_at_capture_edges`); dead owners fall out
_settlers: List["weakref.WeakMethod"] = []


def settle_at_capture_edges(method: Callable[[], None]) -> None:
    """Have ``method`` (a bound method, held weakly) called on the thread
    that starts or stops a capture, before the edge is cut: a capture then
    holds whole units of work, each with all its spans and counts. The serve
    engine settles the tick whose program it has issued and not yet read."""
    _settlers.append(weakref.WeakMethod(method))


def _settle() -> None:
    live = []
    for ref in list(_settlers):
        method = ref()
        if method is not None:
            live.append(ref)
            method()
    _settlers[:] = live


def capturing() -> bool:
    return _active is not None


def active() -> Optional[_Active]:
    """The capture in progress, for :func:`obs.span`."""
    return _active


def last_capture() -> Optional[Capture]:
    """The record of the newest finished capture of this process."""
    return _last


def start_capture(out_dir, registry: Optional[MetricsRegistry] = None,
                  python_frames: bool = False) -> None:
    """Start the profiler into ``out_dir`` (created if missing) and turn
    the spans' annotations on. One capture at a time. ``python_frames``
    turns the interpreter's tracer on as well: every Python call becomes
    an event of the host line, for someone who hunts a frame by hand, at
    several times the host's cost (docs/OBSERVABILITY.md says when)."""
    global _active
    import jax

    _settle()
    with _lock:
        if _active is not None:
            raise RuntimeError(
                f"start_capture: a capture into {_active.trace_dir} is "
                "already on; stop_capture() it first")
        Path(out_dir).mkdir(parents=True, exist_ok=True)
        cap = _Active(str(out_dir),
                      registry if registry is not None else get_registry(),
                      jax.profiler.TraceAnnotation)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 1 if python_frames else 0
        origin = clock()
        jax.profiler.start_trace(str(out_dir), profiler_options=options)
        cap.started = clock()
        # start = the origin, duration = what start_trace took: a span
        # opened before its end was opened with no capture on
        cap.marker = _marker(cap.trace_dir, "start", origin,
                             cap.started - origin)
        _active = cap


def stop_capture() -> Capture:
    """Stop the profiler and return the capture's record (kept for
    :func:`last_capture`). Safe in a ``finally``: the control is off and
    the record kept before the profiler is asked to stop, so a profiler
    that fails to write leaves the next ``start_capture`` free."""
    global _active, _last
    import jax

    if _active is not None:
        _settle()
    with _lock:
        cap = _active
        if cap is None:
            raise RuntimeError("stop_capture: no capture is on")
        now = clock()
        _active = None
        markers = (cap.marker, _marker(cap.trace_dir, "stop", now, 0.0))
        after = cap.registry.snapshot()["counters"]
        moved = {k: v - cap.counters_before.get(k, 0.0) for k, v in after.items()
                 if v != cap.counters_before.get(k, 0.0)}
        _last = Capture(trace_dir=cap.trace_dir, seconds=now - cap.started,
                        counters=moved, markers=markers)
        jax.profiler.stop_trace()
        return _last
