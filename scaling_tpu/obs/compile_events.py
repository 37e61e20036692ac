"""The program's account of its own set-up: JAX's compile events as rows
of the recorder and counters of the registry, by program name.

``install()`` registers ONE duration listener and ONE event listener with
``jax.monitoring`` (once a process) and writes a ``process.start`` row (at
every call). ``compile_cache.enable_compile_cache()`` calls it, which every
entry point passes before its first compile. From then on every program
JAX traces, lowers and compiles (or loads from the persistent cache) leaves
rows on ``recorder.clock``, each with ``fun_name`` as JAX gives it
(``jit(step)``, ``jit(mixed_128)``, ``jit(init_params)``; an eager operation
is a program of its own, ``jit(convert_element_type)``):

- ``compile.trace``: the program's OUTERMOST trace. JAX fires
  ``jaxpr_trace_duration`` for every jitted function traced inside the
  outer trace too, one a ``jnp`` call, thousands in a model's trace, each
  inside the outer one's seconds; and again for what a LOWERING rule traces
  (the interpreted Pallas kernels: 1,280 events inside ``mixed_128``'s
  lowering on the CPU), so the outermost is not simply the last before the
  lower event. The newest trace event of each NAME is held (an inner event
  costs one dict write) and the lower event ``jit(X)`` takes ``X``'s, the
  newest of that name, which the outermost is; what else was held is
  dropped there.
- ``compile.lower``: jaxpr to MLIR module (``jaxpr_to_mlir_module_duration``).
- ``compile.backend``: ``backend_compile_duration``, which wraps
  ``compile_or_get_cached``: on a hit of the persistent cache it CONTAINS
  the retrieval. The row carries ``cache_hit`` (true / false; absent where
  the cache was not asked: it is off, or the program has a host callback)
  and, on a hit, ``retrieval_s``, so a reader can take those seconds out.
- ``compile.cache_load``: the retrieval alone (``cache_retrieval_time_sec``,
  which comes without a name: the row is written when the enclosing
  backend event brings one).

A row's ``start`` is ``clock() - duration`` at the callback: JAX stamps with
``time.time()``, a row must not. A reader takes the UNION of a kind's
intervals, not their sum: a program traced inside another's trace (a jitted
``make_batch`` under ``eval_shape`` inside a traced function) is the
outermost of ITS lower event and lies inside the other's row.

Counters: ``jax_programs_lowered_total`` (one a ``compile.lower`` row: a
retrace of a jitted function at a new shape moves it, which is what a
recompile in serving is), ``jax_compile_cache_hits_total``,
``jax_compile_cache_misses_total``, and ``jax_compile_seconds_total{phase=
trace|lower|backend|cache_load}``, the seconds of the rows written (the
outermost traces only; ``backend`` net of the retrieval, so the four add up).

No jax at import (``install`` is called by code that has it); nothing here
runs while nothing compiles.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

from .recorder import clock, process_start_s, record_span
from .registry import get_registry

TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"
RETRIEVAL_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
HIT_EVENT = "/jax/compilation_cache/cache_hits"
MISS_EVENT = "/jax/compilation_cache/cache_misses"

LOWERED = "jax_programs_lowered_total"

_installed = False
# the newest trace event of each name since the last lower event:
# fun_name -> (end on the clock, seconds)
_traces: Dict[str, Tuple[float, float]] = {}
# since the last backend event: what the cache said, and a hit's
# retrieval as (end on the clock, seconds)
_cache_hit: Optional[bool] = None
_retrieval: Optional[Tuple[float, float]] = None


def _row(phase: str, end: float, seconds: float, counted: Optional[float] = None,
         **fields) -> None:
    """One ``compile.<phase>`` row ending at ``end``, and its seconds
    (``counted`` where they differ from the row's) on the phase's counter."""
    record_span(f"compile.{phase}", end - seconds, seconds, **fields)
    get_registry().counter("jax_compile_seconds_total", {"phase": phase}).inc(
        max(seconds if counted is None else counted, 0.0))


def _write_retrieval(**fields) -> float:
    """The held retrieval as a ``compile.cache_load`` row; its seconds."""
    global _retrieval
    if _retrieval is None:
        return 0.0
    (end, seconds), _retrieval = _retrieval, None
    _row("cache_load", end, seconds, **fields)
    return seconds


def _on_duration(event: str, duration: float, fun_name: str = "", **_) -> None:
    global _cache_hit, _retrieval
    if event == TRACE_EVENT:
        _traces[fun_name] = (clock(), duration)
    elif event == LOWER_EVENT:
        now = clock()
        # ``jit(X)``, ``pmap(X)``: the module's name wraps the function's
        held = _traces.get(fun_name[fun_name.find("(") + 1:].rstrip(")"))
        _traces.clear()
        if held is not None:
            _row("trace", *held, fun_name=fun_name)
        _row("lower", now, duration, fun_name=fun_name)
        get_registry().counter(LOWERED).inc()
    elif event == BACKEND_EVENT:
        now = clock()
        fields = {"fun_name": fun_name}
        if _cache_hit is not None:
            fields["cache_hit"], _cache_hit = _cache_hit, None
        retrieved = _write_retrieval(fun_name=fun_name)
        if retrieved:
            fields["retrieval_s"] = retrieved
        # counted net of the retrieval, which has its own phase
        _row("backend", now, duration, counted=duration - retrieved, **fields)
    elif event == RETRIEVAL_EVENT:
        _write_retrieval()  # one no backend event claimed: nameless
        _retrieval = (clock(), duration)


def _on_event(event: str, **_) -> None:
    global _cache_hit
    if event == HIT_EVENT:
        _cache_hit = True
        get_registry().counter("jax_compile_cache_hits_total").inc()
    elif event == MISS_EVENT:
        _cache_hit = False
        get_registry().counter("jax_compile_cache_misses_total").inc()


def programs_lowered() -> int:
    """``jax_programs_lowered_total`` now (0 before ``install``)."""
    return int(get_registry().counter(LOWERED).value)


def install() -> None:
    """Listen to JAX's compile events (registered once a process) and write
    the ``process.start`` row: from the process's start
    (``process_start_s``) to this call, which is the interpreter, the
    imports and JAX reaching the device. The ROW is written at every call,
    one an entry point reaching its first compile: a reader takes the last
    before its cut."""
    global _installed
    start = process_start_s()
    record_span("process.start", start, clock() - start)
    if _installed:
        return
    from jax import monitoring

    monitoring.register_event_duration_secs_listener(_on_duration)
    monitoring.register_event_listener(_on_event)
    _installed = True
