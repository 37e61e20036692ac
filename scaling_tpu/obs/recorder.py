"""The bounded recorder: every closed span of the process, exactly.

One process-wide ring (``collections.deque(maxlen=RING_ROWS)``) that
:func:`obs.span` appends one row to at ``Span.__exit__``, whether a
capture is on or not. The ``span_seconds`` histogram is bucketed, so no
median comes back out of it; a row here is the span itself:
``(name, start_ns, duration_ns, step, parent, fields)``.

**The clock.** Every row is on :data:`clock`, ``time.monotonic``: the
clock the serving stack stamps a request on (``Request.arrival_s``,
``Sequence.admitted_s`` / ``first_token_s`` / ``token_stamps``), so a
span's start can be laid beside an arrival with no conversion. A span
reads it here (``obs/spans.py``), a capture takes its origin from it
(``obs/capture.py``), and :func:`record_span` takes its ``start`` on it.

``fields`` is kept by reference, as the span's own dict (so an
``annotate()`` after the row was written would show; nothing does that),
and filtered when READ: scalars and non-empty lists of numbers survive,
anything else (a list of trace ids) does not. Nothing is computed when a
row is written but the tuple.

A capture is a view of the ring: ``start_capture`` / ``stop_capture``
each append a marker row named :data:`CAPTURE_MARKER` and the capture's
spans are the rows between its two markers (:meth:`Recorder.between`).

Bound: ``RING_ROWS`` rows, the oldest dropped first. A serving tick
closes 8 spans, so the ring holds the last ~16,000 ticks (four minutes
at 15 ms a tick) at a few hundred bytes a row (~50 MB when full); a reader that wants a window reads
it before that many more spans have closed.

No jax, no logger: ``obs/spans.py`` and ``obs/capture.py`` import this.
"""

from __future__ import annotations

import collections
import os
import time
from typing import Any, Dict, List, NamedTuple, Optional

RING_ROWS = 131_072
CAPTURE_MARKER = "obs.capture"

clock = time.monotonic  # THE clock of every row, in seconds (see above)
_IMPORTED = clock()     # what ``process_start_s`` falls back to
_process_start: Optional[float] = None


def process_start_s() -> float:
    """When this process started, in seconds on :data:`clock`: the kernel's
    own stamp (``/proc/self/stat`` field 22, ticks of 10 ms since boot)
    laid onto the monotonic clock through ``CLOCK_BOOTTIME``, so that the
    interpreter's start and the imports before this module lie inside what
    is counted from it. Where the stamp cannot be read, the moment this
    module was imported. Read once a process, then kept."""
    global _process_start
    if _process_start is None:
        try:
            with open("/proc/self/stat", "rb") as f:
                # the command's name may hold spaces and brackets: the
                # fields are counted from the last ")", state (3) first
                ticks = int(f.read().rsplit(b")", 1)[1].split()[19])
            age = (time.clock_gettime(time.CLOCK_BOOTTIME)
                   - ticks / os.sysconf("SC_CLK_TCK"))
            start = clock() - age
        except (OSError, ValueError, IndexError, AttributeError):
            start = _IMPORTED
        _process_start = min(start, _IMPORTED)
    return _process_start


class Row(NamedTuple):
    """One closed span, or one phase written through ``record_span``."""

    name: str
    start_ns: int           # on ``clock``
    duration_ns: int
    step: Optional[int]
    parent: Optional[str]   # the enclosing span's name
    fields: Dict[str, Any]  # filtered: scalars and lists of numbers


def kept_fields(fields: Dict[str, Any]) -> Dict[str, Any]:
    """What of a span's annotations a row shows: scalars, and non-empty
    lists of numbers (a looped model's exit distribution)."""
    return {k: v for k, v in fields.items()
            if isinstance(v, (bool, int, float, str))
            or (isinstance(v, list) and v
                and all(isinstance(x, (int, float)) for x in v))}


def _row(raw: tuple) -> Row:
    name, start, duration, step, parent, fields = raw
    return Row(name, round(start * 1e9), round(duration * 1e9), step, parent,
               kept_fields(fields))


class Recorder:
    """A ring of raw rows ``(name, start_s, duration_s, step, parent,
    fields)``; ``append`` is the deque's own (atomic under the GIL, so
    any thread may call it without a lock)."""

    def __init__(self, maxlen: int = RING_ROWS):
        self.ring: collections.deque = collections.deque(maxlen=maxlen)
        self.append = self.ring.append

    def rows(self, since_ns: Optional[int] = None,
             name: Optional[str] = None) -> List[Row]:
        """The ring's rows in closing order, oldest first: those that
        START at or after ``since_ns`` and bear ``name``, where given."""
        since = None if since_ns is None else since_ns / 1e9
        return [_row(raw) for raw in list(self.ring)
                if (name is None or raw[0] == name)
                and (since is None or raw[1] >= since)]

    def tail(self, name: str, count: int) -> List[Row]:
        """The rows that closed after the ``count + 1``-th last row named
        ``name``, in closing order. A parent closes after its children,
        so for ``serve.tick`` these are the last ``count`` ticks whole.
        Converts only the rows it returns (one copy of the ring's
        pointers apart), so a poll does not pay for the whole ring."""
        raws, seen = [], 0
        for raw in reversed(list(self.ring)):
            if raw[0] == name:
                seen += 1
                if seen > count:
                    break
            raws.append(raw)
        raws.reverse()
        return [_row(raw) for raw in raws]

    def between(self, start_marker: tuple, stop_marker: tuple) -> List[tuple]:
        """A capture's spans: the rows that closed between its two
        markers and were opened after the capture was on (the start
        marker's start + duration), as ``(name, start_ns, duration_ns,
        fields)`` with ``step`` and ``parent`` inside ``fields`` and
        ``start_ns`` counted from the start marker's start. Marker rows
        are left out. If the ring has dropped the start marker, what is
        left of the capture."""
        origin = start_marker[1]
        opened = origin + start_marker[2]
        out: List[tuple] = []
        inside = False
        for raw in reversed(list(self.ring)):  # the newest capture is near the end
            if raw is start_marker:
                break
            if raw is stop_marker:
                inside = True
            elif inside and raw[0] != CAPTURE_MARKER and raw[1] >= opened:
                name, start, duration, step, parent, fields = raw
                fields = kept_fields(fields)
                if step is not None:
                    fields["step"] = step
                if parent is not None:
                    fields["parent"] = parent
                out.append((name, round((start - origin) * 1e9),
                            round(duration * 1e9), fields))
        out.reverse()
        return out


_recorder = Recorder()


def recorded_spans(since_ns: Optional[int] = None,
                   name: Optional[str] = None) -> List[Row]:
    """Rows of the process's recorder, in closing order
    (:meth:`Recorder.rows`)."""
    return _recorder.rows(since_ns, name)


def recorded_tail(name: str, count: int) -> List[Row]:
    """The newest rows of the process's recorder, back to the last
    ``count`` rows named ``name`` (:meth:`Recorder.tail`)."""
    return _recorder.tail(name, count)


def record_span(name: str, start: float, duration: float,
                **fields: Any) -> None:
    """Write a row for a phase that is no code region (a request's time
    to its first token): ``start`` in seconds on :data:`clock`,
    ``duration`` in seconds. No histogram, no event, no annotation."""
    _recorder.append((name, start, duration, None, None, fields))
