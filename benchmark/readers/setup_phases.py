"""Where ``setup_s`` went, phase by phase, from the program's own account.

The program listens to JAX's compile events and writes one row a program and
phase into its span recorder (``scaling_tpu/obs/compile_events.py``), on the
clock every other row is on:

- ``process.start``: the process's own start to the moment it switched its
  compile cache on (``benchmark/device.py`` ``claim_device``: the interpreter,
  the imports, JAX reaching the chip);
- ``compile.trace`` (a program's OUTERMOST trace), ``compile.lower`` (jaxpr to
  MLIR), ``compile.backend`` (XLA's and Mosaic's compile, or on a hit of the
  persistent cache the retrieval, which it then CONTAINS: ``cache_hit``,
  ``retrieval_s``) and ``compile.cache_load`` (the retrieval alone), each with
  ``fun_name`` (``jit(step)``, ``jit(mixed_128)``, ``jit(init_params)``; an
  eager operation is a program of its own).

Set-up's rows are those that CLOSED before the process's first ``obs.capture``
marker: ``Tracer.open_after_window`` writes it as the window closes, and no
program is lowered inside a ``correct`` window (the harness's
``compiles_in_window == 0``), so every ``compile.*`` row before the marker is
set-up's. ``process.start`` is written at every call of the program's
``install()``: the LAST before the cut is this run's. A duration is the UNION
of its rows' intervals, never their sum: a program traced inside another's
trace lies inside that one's row.

The four durations and ``setup_unaccounted_s`` add up to the line's ``setup_s``
by construction. ``process.start`` is counted from the process's start, the
harness's ``setup_s`` from ``benchmark/run.py``'s ``T0``, its first statement:
the remainder is short by the interpreter's own start (``phases`` prints how
many ms where it can find ``T0``).

With a program that has no such rows (no ``process.start`` left before the
cut, or no ``compile.*`` row) every reader here returns nothing.

``phases`` also prints to stderr, under ``[phases]``: the seven numbers, the
programs that took the most ``compile.*`` seconds, and whether any
``compile.lower`` row STARTS after the window opened (``T0 + setup_s``; where
``T0`` cannot be found the process's start ``+ setup_s``, some ms earlier) and
before the marker: the program's rows and the harness's count must agree that nothing
was lowered in the window.
"""

import sys

from benchmark.readers.window_spans import MARKER, recorded_spans

START = "process.start"
TRACE, LOWER, BACKEND, CACHE_LOAD = (
    "compile.trace", "compile.lower", "compile.backend", "compile.cache_load")
S = 1e9  # ns


def intervals(rows):
    return [(r.start_ns, r.start_ns + r.duration_ns) for r in rows]


def union_ns(spans) -> int:
    """The length of the union of ``(start, end)`` intervals."""
    total, reach = 0, None
    for start, end in sorted(spans):
        if reach is None or start > reach:
            total, reach = total + end - start, end
        elif end > reach:
            total, reach = total + end - reach, end
    return total


def harness_t0_ns():
    """``benchmark/run.py``'s ``T0`` where this process runs it (as
    ``__main__`` or as a module), else None: for one line of stderr."""
    for name in ("__main__", "benchmark.run"):
        t0 = getattr(sys.modules.get(name), "T0", None)
        if isinstance(t0, float):
            return round(t0 * S)
    return None


def cut_setup(rows, setup_s, t0_ns=None):
    """The phases of set-up from the recorder's ``rows`` (closing order), or
    None: see the module's docstring. ``setup_s``: the line's, seconds;
    ``t0_ns``: what it was counted from, where known (else the process's
    start, which is earlier)."""
    first = next((i for i, r in enumerate(rows) if r.name == MARKER), len(rows))
    before = rows[:first]
    starts = [r for r in before if r.name == START]
    by_kind = {kind: [r for r in before if r.name == kind]
               for kind in (TRACE, LOWER, BACKEND, CACHE_LOAD)}
    if not starts or not (by_kind[LOWER] or by_kind[BACKEND]):
        return None
    start = starts[-1]
    loads = intervals(by_kind[CACHE_LOAD])
    load_ns = union_ns(loads)
    programs = {}
    for kind, kind_rows in by_kind.items():
        for r in kind_rows:
            # a hit's backend row holds its retrieval: count it once
            ns = r.duration_ns - (round(r.fields.get("retrieval_s", 0.0) * S)
                                  if kind == BACKEND else 0)
            name = r.fields.get("fun_name", "?")
            programs[name] = programs.get(name, 0) + ns
    window_opens = (start.start_ns if t0_ns is None else t0_ns) + round(setup_s * S)
    return {
        "until_device_s": start.duration_ns / S,
        "trace_lower_s": union_ns(
            intervals(by_kind[TRACE]) + intervals(by_kind[LOWER])) / S,
        "cache_load_s": load_ns / S,
        # net of the retrievals: what of the backend rows no cache_load covers
        "backend_compile_s": (
            union_ns(intervals(by_kind[BACKEND]) + loads) - load_ns) / S,
        "cache_misses": sum(r.fields.get("cache_hit") is False
                            for r in by_kind[BACKEND]),
        "programs_lowered": len(by_kind[LOWER]),
        "process_start_ns": start.start_ns,
        "programs": sorted(programs.items(), key=lambda kv: -kv[1]),
        "lowered_in_window": [r.fields.get("fun_name", "?")
                              for r in by_kind[LOWER]
                              if r.start_ns >= window_opens],
    }


DURATIONS = ("until_device_s", "trace_lower_s", "cache_load_s",
             "backend_compile_s")


def phases(ctx):
    """``cut_setup`` of this run, cut once: the seven readers share ``ctx``."""
    if "setup_phases" not in ctx:
        rows = recorded_spans()
        setup_s = ctx["end_to_end"]["setup_s"]
        t0 = harness_t0_ns()
        cut = ctx["setup_phases"] = (
            cut_setup(rows, setup_s, t0) if rows else None)
        if cut is not None:
            cut["unaccounted_s"] = setup_s - sum(cut[k] for k in DURATIONS)
            report(cut, setup_s, t0)
    return ctx["setup_phases"]


def report(cut, setup_s, t0) -> None:
    def say(line):
        print(f"[phases] {line}", file=sys.stderr, flush=True)

    say(f"setup_s {setup_s:.3f} = until the device {cut['until_device_s']:.3f} "
        f"+ trace and lower {cut['trace_lower_s']:.3f} + cache load "
        f"{cut['cache_load_s']:.3f} + backend compile "
        f"{cut['backend_compile_s']:.3f} + unaccounted "
        f"{cut['unaccounted_s']:.3f}; {cut['programs_lowered']} program(s) "
        f"lowered, {cut['cache_misses']} missed the compile cache")
    if t0 is not None:
        say(f"the process started {(t0 - cut['process_start_ns']) / 1e6:.1f} ms "
            "before the harness's T0")
    for name, ns in cut["programs"][:8]:
        say(f"{ns / S:9.3f} s of compile.* rows  {name}")
    late = cut["lowered_in_window"]
    say("no compile.lower row starts between the window's opening and the "
        "first obs.capture marker" if not late else
        f"LOWERED IN THE WINDOW, by the program's rows: {late}")


def value(ctx, key):
    cut = phases(ctx)
    return None if cut is None else cut[key]


def setup_until_device_s(ctx):
    """``process.start``'s duration: interpreter, imports, first contact
    with the chip."""
    return value(ctx, "until_device_s")


def setup_trace_lower_s(ctx):
    """Union of the ``compile.trace`` and ``compile.lower`` rows: the host's
    Python, paid warm or cold, and what grows with the model's code."""
    return value(ctx, "trace_lower_s")


def setup_cache_load_s(ctx):
    """Union of the ``compile.cache_load`` rows: executables read back from
    the persistent cache."""
    return value(ctx, "cache_load_s")


def setup_backend_compile_s(ctx):
    """Union of the ``compile.backend`` rows net of the retrievals: XLA's
    and Mosaic's own compile, about 0 in a warm run."""
    return value(ctx, "backend_compile_s")


def setup_cache_misses(ctx):
    """``compile.backend`` rows whose ``cache_hit`` is false: a "warm" run
    that reads above 0 compared cache states, not trees."""
    return value(ctx, "cache_misses")


def setup_programs_lowered(ctx):
    """``compile.lower`` rows: the programs set-up lowered, eager
    operations included."""
    return value(ctx, "programs_lowered")


def setup_unaccounted_s(ctx):
    """The line's ``setup_s`` minus the four durations: weights made on the
    device, the engine's pools, the reference loss (train), the warm-up
    traffic (serve)."""
    return value(ctx, "unaccounted_s")
