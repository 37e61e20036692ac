"""Per-layer metrics of the MEASURED WINDOW, from the program's span recorder.

The readers of ``program_spans.py`` see the few seconds traced after the
window, under the profiler. These see the window itself, untraced: the program
keeps every closed span exactly in one bounded recorder whether a capture is on
or not (``scaling_tpu/obs/recorder.py``), and ``obs.recorded_spans()`` hands
the rows over, in closing order, as ``(name, start_ns, duration_ns, step,
parent, fields)`` on one monotonic clock.

Which rows are the window's: ``Tracer.open_after_window`` starts and stops a
capture for nothing as the window closes, before any further tick, and each
of the two leaves a marker row ``obs.capture``. The window's ticks are the
N = ``len(ctx["host"]["tick_s"])`` ``serve.tick`` rows that closed last before
the process's FIRST marker. They are accepted only if that first capture held
no tick (under ``--trace 1`` it holds the traced slice, and what closed before
it is not the window) and if their durations agree with the harness's
``tick_s`` one for one: the span lies inside the harness's two clock reads, so
the median difference is under ``AGREE_MS``. Otherwise, and with a program that
has no recorder, every reader here returns nothing.

The rows of a tick share its ``step``; a request's ``serve.first_token`` row
starts at its arrival, lasts to its first token and carries ``queue_s``, its
wait for a slot.
"""

from statistics import median

TICK, MIXED, WAIT = "serve.tick", "serve.mixed", "serve.mixed.wait"
SCHEDULE, FIRST_TOKEN, MARKER = "serve.schedule", "serve.first_token", "obs.capture"
AGREE_MS = 0.1


def recorded_spans():
    try:
        from scaling_tpu.obs import recorded_spans as program_rows
    except ImportError:  # a program without the recorder
        return None
    return program_rows()


def cut_window(rows, tick_s):
    """``(ticks, rows of the window)`` or None: see the module's docstring.
    ``rows``: the recorder's; ``tick_s``: the harness's tick times, seconds."""
    markers = [i for i, r in enumerate(rows) if r.name == MARKER]
    if len(markers) < 2 or not tick_s:
        return None
    if any(r.name == TICK for r in rows[markers[0]:markers[1]]):
        return None
    before = rows[:markers[0]]
    at = [i for i, r in enumerate(before) if r.name == TICK]
    if len(at) < len(tick_s):
        return None
    first = len(at) - len(tick_s)
    ticks = [before[i] for i in at[first:]]
    if median(abs(r.duration_ns / 1e6 - 1e3 * s)
              for r, s in zip(ticks, tick_s)) >= AGREE_MS:
        return None
    # a tick's children close before it: the window's rows start after the
    # tick that closed last before the window's first
    return ticks, before[at[first - 1] + 1 if first else 0:]


def window(ctx):
    """``cut_window`` of this run, cut once: the seven readers share ``ctx``."""
    if "window_spans" not in ctx:
        rows = recorded_spans()
        ctx["window_spans"] = cut_window(
            rows, (ctx.get("host") or {}).get("tick_s")) if rows else None
    return ctx["window_spans"]


def named(ctx, name):
    """``(ticks, the window's rows called name)``, or None."""
    cut = window(ctx)
    if cut is None:
        return None
    steps = {t.step for t in cut[0]}
    return cut[0], [r for r in cut[1] if r.name == name and r.step in steps]


def tick_host_ms_p50(ctx):
    """Per tick of the window, ``serve.tick`` minus its ``serve.mixed.wait``
    (the one place the host waits for the chip), median: what the host adds
    to a tick, with no profiler on."""
    cut = named(ctx, WAIT)
    if cut is None:
        return None
    waits = {r.step: r.duration_ns for r in cut[1]}
    host = [(t.duration_ns - waits[t.step]) / 1e6 for t in cut[0] if t.step in waits]
    return median(host) if host else None


def sched_ms_mean(ctx):
    """``serve.schedule``, MEAN over the window's ticks: eviction lives in
    the ticks that allocate, not in the median tick."""
    cut = named(ctx, SCHEDULE)
    return sum(r.duration_ns for r in cut[1]) / 1e6 / len(cut[0]) if cut else None


def evict_ms_mean(ctx):
    """The scheduler's ``evict_ms`` (inside ``PrefixCache.evict``), summed
    over the window and divided by its ticks; 0 where nothing was evicted."""
    cut = named(ctx, SCHEDULE)
    return (sum(r.fields.get("evict_ms", 0.0) for r in cut[1]) / len(cut[0])
            if cut else None)


def tick_fill_pct(ctx):
    """Real tokens the window's ticks held over the token widths they ran at."""
    cut = named(ctx, MIXED)
    if not cut or not cut[1]:
        return None
    return 100.0 * sum(r.fields["tokens"] for r in cut[1]) / sum(
        r.fields["width"] for r in cut[1])


def wide_tick_pct(ctx):
    """Share of the window's ticks that ran at the engine's LARGEST token
    width (``EngineConfig.mixed_widths``): the ticks that make ``itl_p95_ms``."""
    from benchmark import model

    cut = named(ctx, MIXED)
    if not cut or not cut[1]:
        return None
    largest = model.engine_config(ctx["config"]["engine"]).mixed_widths[-1]
    return 100.0 * sum(r.fields["width"] == largest for r in cut[1]) / len(cut[1])


def first_tokens(ctx):
    """The ``serve.first_token`` rows whose arrival and first token both
    lie inside the window's ticks."""
    cut = window(ctx)
    if cut is None:
        return []
    opens = cut[0][0].start_ns
    closes = cut[0][-1].start_ns + cut[0][-1].duration_ns
    return [r for r in cut[1] if r.name == FIRST_TOKEN
            and opens <= r.start_ns and r.start_ns + r.duration_ns <= closes]


def ttft_queue_pct(ctx):
    """Of the time to first token of the window's own requests, the share
    spent waiting for a slot (arrival to ``admitted_s``): the rest is the
    prompt streaming in."""
    rows = first_tokens(ctx)
    total = sum(r.duration_ns for r in rows)
    return 100.0 * sum(r.fields["queue_s"] for r in rows) * 1e9 / total if total else None


def prefill_ms_p50(ctx):
    """Same rows: a slot given to the first token out, median."""
    rows = first_tokens(ctx)
    return median(r.duration_ns / 1e6 - 1e3 * r.fields["queue_s"]
                  for r in rows) if rows else None
