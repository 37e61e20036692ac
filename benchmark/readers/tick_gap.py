"""The gap between two of the chip's programs in a serve cell, part by part.

Between the end of one tick's mixed program and the start of the next the
chip waits for the host. In the order they happen, a gap is made of:

1. the **wake-up**: the program has ended and ``np.asarray(sampled)`` has
   not yet returned (the end of ``serve.mixed.wait``);
2. ``serve.emit`` and 3. ``serve.retire`` of that tick;
4. **between**: that ``serve.tick``'s end to the next one's start, the
   harness's loop around ``engine.tick()``;
5. ``serve.schedule`` and 6. ``serve.mixed.build`` of the next tick;
7. the **launch**: the start of its ``serve.mixed.dispatch`` to the first
   operation of its program;
8. what a tick spends in no leaf span (**unspanned**).

Seven readers take their part from the MEASURED window, untraced, out of the
program's span recorder: the window is ``window_spans.window(ctx)``, cut once
a run and shared through ``ctx``, and where it cannot be cut they return
nothing. Three take theirs from the traced slice on the device's clock: the
first chip's ``XLA Modules`` line (one event a program, ``jit_mixed_<width>``)
beside the spans' annotations on the host plane, which since PR 57 carry their
span's ``step``; the trace is loaded once between them (``ctx["tick_gap"]``).
Where an annotation has no step (a program from before PR 57) a tick's
annotations are joined by their order in time; where the trace has no modules
line a program is what the device ran from one ``serve.mixed.dispatch``
annotation's start to the next one's, first operation to last. Without a
capture, or a trace that holds neither, they return nothing.

``traced`` also prints to stderr, one line a part, the medians of the traced
gaps' parts as the annotations bound them, and what of a gap no part covers.
"""

import sys
import time
from bisect import bisect_left, bisect_right
from statistics import median

from benchmark import trace_reduce
from benchmark.readers import program_spans, window_spans

TICK, SCHEDULE, BUILD, DISPATCH, WAIT, EMIT, RETIRE = (
    "serve.tick", "serve.schedule", "serve.mixed.build", "serve.mixed.dispatch",
    "serve.mixed.wait", "serve.emit", "serve.retire")
# the spans that tile a tick: serve.tick and serve.mixed only hold them, and
# serve.draft / .preempt / .cow nest in serve.schedule
LEAVES = (SCHEDULE, BUILD, DISPATCH, WAIT, EMIT, RETIRE)
PROGRAM = "jit_mixed_"  # engine._build_mixed_fn names a width's program so
MS = 1e6  # ns


# ------------------------------------------------- the window, untraced
def span_ms_mean(ctx, name):
    """A span's time summed over the window and divided by its ticks."""
    cut = window_spans.named(ctx, name)
    return sum(r.duration_ns for r in cut[1]) / MS / len(cut[0]) if cut else None


def emit_ms_mean(ctx):
    """``serve.emit``: the new pool state absorbed, acceptance, a token
    appended for every row, the tick's counters."""
    return span_ms_mean(ctx, EMIT)


def retire_ms_mean(ctx):
    return span_ms_mean(ctx, RETIRE)


def build_ms_mean(ctx):
    return span_ms_mean(ctx, BUILD)


def dispatch_ms_mean(ctx):
    """``serve.mixed.dispatch``, the jitted call until it returns: the
    untraced twin of ``tick_dispatch_ms_p50*``."""
    return span_ms_mean(ctx, DISPATCH)


def emit_us_per_row(ctx):
    """``serve.emit`` over the rows it emitted for (its ``rows``: chunk rows
    that finished their prompt + decode rows), microseconds: nothing where
    the program's spans do not say (before PR 57)."""
    cut = window_spans.named(ctx, EMIT)
    rows = sum(r.fields.get("rows", 0) for r in cut[1]) if cut else 0
    return sum(r.duration_ns for r in cut[1]) / 1e3 / rows if rows else None


def unspanned_ms_mean(ctx):
    """``serve.tick`` minus its leaf spans, mean over the window's ticks:
    the tick's time that no part of the account names."""
    cut = window_spans.window(ctx)
    if cut is None:
        return None
    steps = {t.step for t in cut[0]}
    leaves = sum(r.duration_ns for r in cut[1]
                 if r.name in LEAVES and r.step in steps)
    return (sum(t.duration_ns for t in cut[0]) - leaves) / MS / len(cut[0])


def rows_of(tick):
    return tick.fields.get("decodes", 0) + tick.fields.get("chunks", 0)


def between_ticks_ms_mean(ctx):
    """One window tick's end to the next one's start, mean over the pairs
    in which the engine had work all through: the later tick ran a program
    and the earlier one left rows running (it retired fewer requests than
    it had rows, by ``serve.retire``'s ``finished``; where the program does
    not say, every pair whose later tick ran a program). The harness's loop
    between two ``engine.tick()`` calls, not the wait for an arrival."""
    cut = window_spans.named(ctx, RETIRE)
    if cut is None:
        return None
    finished = {r.step: r.fields.get("finished", 0) for r in cut[1]}
    gaps = [b.start_ns - a.start_ns - a.duration_ns
            for a, b in zip(cut[0], cut[0][1:])
            if rows_of(b) and rows_of(a) > finished.get(a.step, 0)]
    return sum(gaps) / MS / len(gaps) if gaps else None


# ------------------------------------- the traced slice, the device's clock
def load_events(path):
    """``trace_reduce.load_events``'s structure, of what these readers need:
    the first chip's ``modules`` (its ``ops`` only where it has no program
    of ours on that line) and the host's ``serve.*`` annotations, each with
    its ``step`` as a fourth element (None where it carries none)."""
    from jax.profiler import ProfileData

    def rows(line):
        return [[e.name, float(e.start_ns), float(e.duration_ns)]
                for e in line.events]

    chips, host = {}, []
    for plane in ProfileData.from_file(str(path)).planes:
        m = trace_reduce.DEVICE_PLANE.match(plane.name)
        if m:
            chips[int(m.group(1))] = {line.name: line for line in plane.lines}
        elif plane.name == trace_reduce.HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("serve."):
                        step = next((v for k, v in e.stats if k == "step"), None)
                        host.append([e.name, float(e.start_ns),
                                     float(e.duration_ns), step])
    devices = {}
    if chips:
        first = min(chips)
        lines = chips[first]
        modules = (rows(lines[trace_reduce.MODULES_LINE])
                   if trace_reduce.MODULES_LINE in lines else [])
        ours = any(name.startswith(PROGRAM) for name, _, _ in modules)
        devices[str(first)] = {
            "modules": modules,
            "ops": [] if ours or trace_reduce.OPS_LINE not in lines
            else rows(lines[trace_reduce.OPS_LINE])}
    return {"devices": devices, "host": host}


def annotations(events, name):
    """``(start, end, step)`` of the host's events called ``name``, by time."""
    return sorted((row[1], row[1] + row[2], row[3] if len(row) > 3 else None)
                  for row in events["host"] if row[0] == name)


def windows(starts, dispatches):
    """For each dispatch, ``(i, j)``: ``starts[i:j]`` (sorted) lie at or after
    its annotation's start and before the next dispatch's."""
    edges = [start for start, _, _ in dispatches] + [float("inf")]
    return [(bisect_left(starts, opens), bisect_left(starts, closes))
            for opens, closes in zip(edges, edges[1:])]


def programs(events, dispatches):
    """``(start, end)`` of each mixed program the first chip ran, by time."""
    devices = events["devices"]
    if not devices:
        return []
    chip = devices[min(devices, key=int)]
    ours = sorted((s, s + d) for name, s, d in chip["modules"]
                  if name.startswith(PROGRAM))
    if ours or not dispatches:
        return ours
    # no modules line: what ran from one dispatch's start to the next one's
    ops = sorted((s, s + d) for _, s, d in chip["ops"])
    return [(ops[i][0], max(end for _, end in ops[i:j]))
            for i, j in windows([s for s, _ in ops], dispatches) if j > i]


def tick_annotations(events, dispatches):
    """For each dispatch, its tick's annotations ``{name: (start, end)}``:
    joined by ``step`` where every annotation of a name carries one of its
    own, else by order in time (a tick's spans lie between its ``serve.tick``
    annotation's start and the next one's)."""
    by_name = {name: annotations(events, name) for name in (TICK,) + LEAVES}
    stepped = all(None not in steps and len(steps) == len(rows)
                  for rows in by_name.values()
                  for steps in [{step for _, _, step in rows}])
    if stepped:
        at = {name: {step: (start, end) for start, end, step in rows}
              for name, rows in by_name.items()}
        return [{name: at[name][step] for name in at if step in at[name]}
                for _, _, step in dispatches]
    opens = [start for start, _, _ in by_name[TICK]]
    out = []
    for d_start, _, _ in dispatches:
        k = bisect_right(opens, d_start)
        mine = opens[k - 1] if k else float("-inf")
        later = opens[k] if k < len(opens) else float("inf")
        out.append({name: (start, end) for name, rows in by_name.items()
                    for start, end, _ in rows if mine <= start < later})
    return out


def gaps(events):
    """The traced slice's gaps, each ``{part: ns}``: ``gap`` (one program's
    end to the next one's start), ``wake``, ``launch``, and the host's parts
    as their annotations bound them; ``early`` marks a wait that returned
    before its program's last operation had ended. None without programs."""
    dispatches = annotations(events, DISPATCH)
    ran = programs(events, dispatches)
    if len(ran) < 2:
        return None
    ticks = tick_annotations(events, dispatches)
    # a dispatch's program: the first that starts after the call was entered
    # and before the next call is
    of_program = {i: k for k, (i, j) in enumerate(
        windows([start for start, _ in ran], dispatches)) if j > i}
    out = []
    for i, ((_, a_end), (b_start, _)) in enumerate(zip(ran, ran[1:])):
        gap = {"gap": b_start - a_end}
        a = ticks[of_program[i]] if i in of_program else {}
        b = ticks[of_program[i + 1]] if i + 1 in of_program else {}
        if WAIT in a:
            gap["wake"] = max(0.0, a[WAIT][1] - a_end)
            gap["early"] = a[WAIT][1] < a_end
        if DISPATCH in b:
            gap["launch"] = b_start - b[DISPATCH][0]
        for name, tick in ((EMIT, a), (RETIRE, a), (SCHEDULE, b), (BUILD, b)):
            if name in tick:
                gap[name] = tick[name][1] - tick[name][0]
        if TICK in a and TICK in b:
            gap["between"] = b[TICK][0] - a[TICK][1]
        out.append(gap)
    return out


PARTS = ("wake", EMIT, RETIRE, "between", SCHEDULE, BUILD, "launch")


def table(found):
    """stderr: the median of each part over the gaps that have every part,
    and of what is left of those gaps."""
    whole = [g for g in found if all(p in g for p in PARTS)]
    total = sum(g["gap"] for g in found)
    print(f"[gap] {len(found)} gaps between two mixed programs, {len(whole)} "
          f"with every part; median gap "
          f"{median(g['gap'] for g in found) / MS:.3f} ms (mean "
          f"{total / len(found) / MS:.3f}, {total / 1e9:.4f} s in all); "
          f"{sum(g.get('early', False) for g in found)} wait(s) returned "
          "before the program's last operation ended", file=sys.stderr)
    if whole:
        for part in PARTS:
            print(f"[gap] {part:<22} median "
                  f"{median(g[part] for g in whole) / MS:8.3f} ms",
                  file=sys.stderr)
        rest = [g["gap"] - sum(g[p] for p in PARTS) for g in whole]
        print(f"[gap] {'in no part':<22} median {median(rest) / MS:8.3f} ms "
              f"(sum of the parts' medians "
              f"{sum(median(g[p] for g in whole) for p in PARTS) / MS:.3f})",
              file=sys.stderr)


def traced(ctx):
    """``gaps`` of this run's capture, read once: the three readers share
    ``ctx``."""
    if "tick_gap" not in ctx:
        capture = program_spans.last_capture()
        path = capture.trace_file() if capture else None
        started = time.monotonic()
        ctx["tick_gap"] = gaps(load_events(path)) if path else None
        if ctx["tick_gap"]:
            table(ctx["tick_gap"])
            print(f"[gap] the trace read in {time.monotonic() - started:.2f} s",
                  file=sys.stderr, flush=True)
    return ctx["tick_gap"]


def part_ms_p50(ctx, part):
    values = [g[part] for g in traced(ctx) or () if part in g]
    return median(values) / MS if values else None


def gap_ms_p50(ctx):
    """End of one mixed program to the start of the next, median: the time
    the chip waits for the host a tick."""
    return part_ms_p50(ctx, "gap")


def gap_wake_ms_p50(ctx):
    """A program's end to the end of its tick's ``serve.mixed.wait``: the
    host hearing that the chip is done (0 where the wait returned first)."""
    return part_ms_p50(ctx, "wake")


def gap_launch_ms_p50(ctx):
    """The start of ``serve.mixed.dispatch`` to the program's start."""
    return part_ms_p50(ctx, "launch")
