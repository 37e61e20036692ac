"""Per-layer metrics read from the program's own spans and counters.

While a capture is on (``scaling_tpu/obs/capture.py``; the benchmark's
``Tracer`` starts and stops one around its traced slice) every ``obs.span``
of the program is kept exactly, the registry's counters are differenced, and
each span lies as a ``TraceAnnotation`` on the host plane of the profiler's
trace, on the clock of the device's ``XLA Ops``. These readers take all of
that from the program, in the process, through ``obs.last_capture()``: they
need nothing of ``ctx``. Without a capture (a ``--trace 0`` run, a program
from before the control existed) each returns nothing.

``phase_table`` also prints to stderr, one line a phase, the idle seconds of
the traced slice by the engine's leaf span and the median of each span: what
PERF.md section 5 says of the host's share of a tick.
"""

import sys
from statistics import median

from benchmark import trace_reduce

TICK, MIXED, WAIT = "serve.tick", "serve.mixed", "serve.mixed.wait"
PREFILL_TOKENS = "serve_prefill_tokens_total"
OUTPUT_TOKENS = "serve_tokens_generated_total"


def last_capture():
    try:
        from scaling_tpu.obs import last_capture as program_last_capture
    except ImportError:  # a program without the control
        return None
    return program_last_capture()


def durations_ms(capture, name):
    return [dur / 1e6 for n, _, dur, _ in capture.spans if n == name]


def median_ms(name):
    capture = last_capture()
    values = durations_ms(capture, name) if capture else []
    return median(values) if values else None


def tick_host_ms(spans):
    """Per traced tick, ``serve.tick`` minus its ``serve.mixed.wait`` (the
    one place the host waits for the chip): what the host adds to a tick.
    ``spans``: the capture's rows; a tick and its wait share their ``step``."""
    waits = {f.get("step"): dur for n, _, dur, f in spans if n == WAIT}
    return [(dur - waits[f.get("step")]) / 1e6
            for n, _, dur, f in spans if n == TICK and f.get("step") in waits]


def tick_host_ms_p50(ctx):
    capture = last_capture()
    values = tick_host_ms(capture.spans) if capture else []
    return median(values) if values else None


def sched_ms_p50(ctx):
    return median_ms("serve.schedule")


def tick_dispatch_ms_p50(ctx):
    return median_ms("serve.mixed.dispatch")


def counter_moved(counters, name):
    """A counter's difference over the capture, summed over its label sets
    (an engine in a fleet labels its counters ``{replica=N}``)."""
    return sum(v for k, v in counters.items()
               if k == name or k.startswith(name + "{"))


def prefill_token_pct(ctx):
    """Prompt tokens prefilled over all tokens the traced ticks processed."""
    capture = last_capture()
    if capture is None:
        return None
    prefill = counter_moved(capture.counters, PREFILL_TOKENS)
    total = prefill + counter_moved(capture.counters, OUTPUT_TOKENS)
    return 100.0 * prefill / total if total else None


def leaf_segments(host):
    """The host's line cut into stretches that do not overlap, each under
    the name of its leaf span: any ``serve.*`` annotation but ``serve.tick``
    and ``serve.mixed``, which only hold the others. Where leaves nest (a
    child of ``serve.schedule``) the stretch goes to the shortest."""
    leaves = sorted((d, s, s + d, n) for n, s, d in host
                    if n.startswith("serve.") and n not in (TICK, MIXED))
    segments, claimed = [], []
    for _, start, end, name in leaves:
        at = start
        for x, y in claimed:
            if y <= at:
                continue
            if x >= end:
                break
            if x > at:
                segments.append((at, x, name))
            at = max(at, y)
        if at < end:
            segments.append((at, end, name))
        claimed = trace_reduce.union_intervals(claimed + [(start, end)])
    return sorted(segments)


def idle_by_span(events):
    """``(idle ns, {leaf span: idle ns inside it})`` of the first chip: its
    idle time is what lies between its ``XLA Ops``, from the first to the
    last. ``events``: what ``trace_reduce.load_events`` gives."""
    devices = {k: v for k, v in events["devices"].items() if v["ops"]}
    if not devices:
        return None
    ops = devices[min(devices, key=int)]["ops"]
    busy = trace_reduce.union_intervals([(s, s + d) for _, s, d in ops])
    gaps = [(a_end, b_start) for (_, a_end), (b_start, _) in zip(busy, busy[1:])]
    segments = leaf_segments(events["host"])
    inside, i = {}, 0
    for a, b in gaps:  # both in order of time: one sweep
        while i < len(segments) and segments[i][1] <= a:
            i += 1
        j = i
        while j < len(segments) and segments[j][0] < b:
            start, end, name = segments[j]
            inside[name] = inside.get(name, 0.0) + min(b, end) - max(a, start)
            j += 1
    return sum(b - a for a, b in gaps), inside


def phase_table(capture, idle):
    """stderr: one line a phase."""
    idle_ns, inside = idle if idle else (0.0, {})
    names = sorted({n for n, _, _, _ in capture.spans if n.startswith("serve.")})
    host = tick_host_ms(capture.spans)
    print(f"[spans] traced {capture.seconds:.2f} s, "
          f"{len(durations_ms(capture, TICK))} ticks; host adds "
          f"{median(host) if host else float('nan'):.3f} ms a tick (median); "
          f"first chip idle {idle_ns / 1e9:.4f} s, in leaf spans "
          f"{sum(inside.values()) / 1e9:.4f} s", file=sys.stderr)
    for name in names:
        values = durations_ms(capture, name)
        print(f"[spans] {name:<22} n={len(values):<4} median "
              f"{median(values):8.3f} ms  idle inside "
              f"{inside.get(name, 0.0) / 1e9:.4f} s", file=sys.stderr)
    sys.stderr.flush()


def idle_in_spans_pct(ctx):
    """Share of the first chip's idle time in the traced slice that lies
    inside a leaf span of the engine: whether the spans explain the gaps."""
    capture = last_capture()
    path = capture.trace_file() if capture else None
    if path is None:
        return None
    idle = idle_by_span(trace_reduce.load_events(path))
    phase_table(capture, idle)
    if not idle or idle[0] <= 0:
        return None
    return 100.0 * sum(idle[1].values()) / idle[0]
