"""Phase tracing: ``with obs.span("ckpt.commit", step=N): ...``.

Every span lands three times, and a fourth while a capture is on:

- as an exact row ``(name, start_ns, duration_ns, step, parent,
  fields)`` of the process's bounded recorder (``obs/recorder.py``),
  appended as the span closes: what a median, a window's cut or a
  capture's ``spans`` is read from, capture or not;
- as an observation in the default registry's ``span_seconds`` histogram
  (labelled by span name) — cheap, in-memory, flushed with the per-step
  registry snapshot;
- as a structured ``span`` event through :meth:`logger.log_event`, so
  the PR 4 supervision events and the new telemetry share ONE stream and
  the run-dir analyzer (``python -m scaling_tpu.obs report``) can
  attribute barrier waits and checkpoint commits per host without a
  second file format. The record is built and serialised only when
  something takes it: an events path is configured, or the logger
  mirrors at the span's level;
- while :func:`obs.start_capture` is on (``obs/capture.py``), as a
  ``jax.profiler.TraceAnnotation`` of its name (its ``step``, where it
  has one, as the event's metadata), which puts it on the host plane of
  the profiler's trace on the device's clock. With no capture it does
  not.

Spans nest (thread-local stack; the parent's name is recorded on the
child) and are exception-safe: a body that raises still emits the span,
marked ``ok=false`` with the exception type, and the exception
propagates untouched.

Distributed tracing rides the same stream (docs/OBSERVABILITY.md
"Tracing"): a per-thread trace context — adopted via
:func:`trace_context` or inherited from the enclosing span — stamps
``trace`` / ``span_id`` / ``parent_span_id`` onto span events, and a
provider hook registered with :func:`logger.set_trace_provider` stamps
``trace`` onto every OTHER ``log_event`` record emitted under an active
context. Trace-less code paths emit byte-identical records to before:
no ids are allocated and no trace fields appear unless a context is
active, which is also what keeps warmup traffic out of the trace
coverage denominator.

Device-drain semantics reuse :class:`SynchronizedTimer`'s contract
without forcing a sync: a span measures host wall time unless the caller
hands it device work via ``sp.wait_for(x)``, in which case the exit
drains ``x`` first so the measured time covers the device work. The
default is drain-free — the step path must not gain device syncs outside
profiler windows (unit-asserted).

No jax at module level; the drain imports it lazily.
"""

from __future__ import annotations

import hashlib
import os
import threading
import uuid
from contextlib import contextmanager
from typing import Any, Iterator, Optional

from ..logging import logger
from ..logging.logger import set_trace_provider
from . import capture as _capture
from .recorder import _recorder, clock as _clock
from .registry import OVERFLOW_LABELS, get_registry

_record = _recorder.append

_local = threading.local()


def _stack() -> list:
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = []
        _local.stack = stack
    return stack


# ------------------------------------------------------------- trace ids
def new_trace_id() -> str:
    """A fresh 16-hex trace id (one per originating request)."""
    return uuid.uuid4().hex[:16]


def new_span_id() -> str:
    """A fresh 8-hex span id (allocated only for traced spans)."""
    return uuid.uuid4().hex[:8]


def derive_trace_id(*parts: Any) -> str:
    """Deterministic trace id from identity parts. Cross-host work that
    shares an identity but never an RPC envelope — a capacity lease
    ``(host, epoch)``, a checkpoint ``commit:step-N`` — derives the SAME
    trace id independently on every host, so the analyzer reassembles
    one fleet-wide trace without any context having crossed the wire."""
    raw = "\x1f".join(str(p) for p in parts)
    return hashlib.sha1(raw.encode("utf-8")).hexdigest()[:16]


@contextmanager
def trace_context(trace_id: Optional[str],
                  parent_span_id: Optional[str] = None) -> Iterator[None]:
    """Adopt an inbound trace context for this thread.

    Spans opened in the body (and ``log_event`` records emitted in it)
    carry ``trace_id``; the previous context is restored on exit, so
    nested adoption — a worker dispatching one request per envelope —
    composes. ``trace_id=None`` adopts the empty context (explicitly
    masking any ambient trace, which the warmup path relies on)."""
    prev = getattr(_local, "trace", None)
    _local.trace = (trace_id, parent_span_id) if trace_id else None
    try:
        yield
    finally:
        _local.trace = prev


def current_trace() -> Optional[dict]:
    """The active trace context as a JSON-safe dict — the exact value an
    RPC envelope's ``trace`` key carries (``{"trace_id": ...,
    "parent_span_id": ...}``), or ``None`` outside any context. The
    innermost traced span wins over an adopted context so the receiver
    links to the sender's actual span."""
    stack = _stack()
    if stack and stack[-1].trace_id:
        return {"trace_id": stack[-1].trace_id,
                "parent_span_id": stack[-1].span_id}
    ctx = getattr(_local, "trace", None)
    if ctx is not None and ctx[0]:
        return {"trace_id": ctx[0], "parent_span_id": ctx[1]}
    return None


def current_trace_id() -> Optional[str]:
    """Just the active ``trace_id`` (what journal records store)."""
    t = current_trace()
    return t["trace_id"] if t else None


def _trace_event_fields() -> Optional[dict]:
    """Provider for :func:`logger.set_trace_provider`: the ``trace``
    field to stamp onto non-span ``log_event`` records. Explicit fields
    win over the provider in ``log_event``, and the provider returns
    ``None`` outside any context so trace-less records stay
    byte-identical to the pre-tracing stream."""
    tid = current_trace_id()
    return {"trace": tid} if tid else None


set_trace_provider(_trace_event_fields)


class Span:
    """One traced phase: what :func:`span` returns, a context manager
    that yields itself; mutate it in the body to enrich the record."""

    __slots__ = ("name", "fields", "_wait_for", "duration_s", "trace_id",
                 "span_id", "parent_span_id", "_step", "_level", "_registry",
                 "_parent", "_capture", "_annotation", "_start")

    def __init__(self, name: str, fields: dict, step: Optional[int] = None,
                 level: str = "debug", registry=None):
        self.name = name
        self.fields = fields
        self._wait_for: Any = None
        self.duration_s: Optional[float] = None
        self.trace_id: Optional[str] = None
        self.span_id: Optional[str] = None
        self.parent_span_id: Optional[str] = None
        self._step, self._level, self._registry = step, level, registry

    def wait_for(self, x: Any) -> Any:
        """Drain ``x`` (``jax.block_until_ready``) before the span closes,
        so the measured time covers its device work. Returns ``x``."""
        self._wait_for = x
        return x

    def annotate(self, **fields: Any) -> None:
        """Attach extra fields to the emitted span event."""
        self.fields.update(fields)

    def __enter__(self) -> "Span":
        stack = _stack()
        self._parent = stack[-1].name if stack else None
        # resolve the trace lineage at entry, per thread: the enclosing
        # span wins (its span_id becomes the parent link), else the
        # adopted context; with neither the span stays trace-less and
        # allocates no ids at all — the pre-tracing fast path,
        # byte-identical records
        if stack and stack[-1].trace_id:
            self.trace_id = stack[-1].trace_id
            self.parent_span_id = stack[-1].span_id
        else:
            ctx = getattr(_local, "trace", None)
            if ctx is not None and ctx[0]:
                self.trace_id, self.parent_span_id = ctx
        if self.trace_id:
            self.span_id = new_span_id()
        stack.append(self)
        self._capture = cap = _capture.active()
        if cap is not None:
            # the span's step rides the annotation: a row of the recorder
            # and its event of the trace are joined by (name, step)
            self._annotation = (
                cap.annotation(self.name) if self._step is None
                else cap.annotation(self.name, step=self._step))
            self._annotation.__enter__()
        self._start = _clock()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        error = exc_type.__name__ if exc_type is not None else None
        try:
            if exc_type is None and self._wait_for is not None:
                # drain INSIDE the measured window: the caller explicitly
                # asked for SynchronizedTimer semantics on this span —
                # opt-in via sp.wait_for(x), never the default
                import jax

                jax.block_until_ready(self._wait_for)
        except BaseException as e:
            error = type(e).__name__
            raise
        finally:
            duration = _clock() - self._start
            if self._capture is not None:
                self._annotation.__exit__(None, None, None)
            _record((self.name, self._start, duration, self._step,
                     self._parent, self.fields))
            self.duration_s = duration
            _stack().pop()
            _emit(self, self._parent, duration, error is None, error,
                  self._step, self._level, self._registry)
        return False  # the body's exception propagates untouched


def current_span() -> Optional[Span]:
    stack = _stack()
    return stack[-1] if stack else None


def span(name: str, *, step: Optional[int] = None, level: str = "debug",
         registry=None, **fields: Any) -> Span:
    """Trace one phase. ``level`` controls only the console mirror of the
    event (per-step phases default to ``debug`` so steady-state training
    does not quadruple its console output); the events file — when
    configured — receives every span regardless."""
    return Span(name, fields, step, level, registry)


def _emit(sp: Span, parent: Optional[str], duration: float, ok: bool,
          error: Optional[str], step: Optional[int], level: str,
          registry) -> None:
    reg = registry if registry is not None else get_registry()
    # the handle is kept per (registry, span name): a lookup through
    # the registry's lock on every span was most of what a span cost
    hist = reg.span_handles.get(sp.name)
    if hist is None:
        hist = reg.histogram("span_seconds", labels={"span": sp.name})
        if hist.labels != OVERFLOW_LABELS:  # a leaking name must not grow the dict
            reg.span_handles[sp.name] = hist
    hist.observe(duration)
    if not logger.takes_events(level):
        return  # no events file and no mirror at this level: nobody reads it
    event_fields = dict(sp.fields)
    event_fields.update(span=sp.name, dur_s=round(duration, 6), ok=ok)
    if parent is not None:
        event_fields["parent"] = parent
    if step is not None:
        event_fields["step"] = step
    if error is not None:
        event_fields["error"] = error
    # trace lineage (explicit annotate() fields win, like host below):
    # only traced spans carry the columns, so trace-less runs emit the
    # exact records they always did
    if sp.trace_id is not None:
        event_fields.setdefault("trace", sp.trace_id)
        event_fields.setdefault("span_id", sp.span_id)
        if sp.parent_span_id is not None:
            event_fields.setdefault("parent_span_id", sp.parent_span_id)
    # host + relaunch epoch ride every span so the analyzer can attribute
    # per host AND per supervisor epoch — the same step gets re-saved and
    # the same barrier re-waited after a relaunch, and merging those
    # incidents would corrupt the arrived-last verdict
    for env_var, field in (("SCALING_TPU_HOST_ID", "host"),
                           ("SCALING_TPU_COORD_EPOCH", "epoch")):
        raw = os.environ.get(env_var)
        if raw is not None and field not in event_fields:
            try:
                event_fields[field] = int(raw)
            except ValueError:
                logger.warning(f"non-integer {env_var} {raw!r} ignored")
    # spans skip the per-record fsync: 3-4 of them land per training
    # step, and the durability contract belongs to lifecycle events
    logger.log_event("span", _level=level, _fsync=False, **event_fields)
