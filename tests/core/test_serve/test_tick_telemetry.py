"""A tick's own telemetry, once a tick (ISSUE 57): what the engine counted a
token or a row at a time it counts once a tick through handles it keeps,
and every ``serve_*`` counter, gauge and histogram reads after a fixed run
what a per-token count made here gives (the same numbers on the parent
commit); ``serve.emit`` / ``serve.retire`` say how many rows, tokens and
requests they handled; the block that annotates ``serve.mixed`` runs once
the program is issued; under a capture an annotation carries its span's
``step`` and ``Capture.clock_offset_ns`` lays a row on its annotation."""

import bisect
import time

import pytest

from scaling_tpu import obs
from scaling_tpu.obs.registry import DEFAULT_BUCKETS, MetricsRegistry

# (prompt, new tokens): two slots, so requests queue; chunks of 4, so the
# longest prompt streams in over three ticks beside a decoding row
WORK = [([1, 2, 3, 4, 5], 6), ([7, 8, 9], 4),
        ([11, 12, 13, 14, 15, 16, 17, 18, 19], 5), ([3, 1, 4], 3)]
TOKENS = sum(n for _, n in WORK)
PROMPT_TOKENS = sum(len(p) for p, _ in WORK)
# tick() calls for WORK on two slots: 12 programs (a finished request's slot
# is free a tick later than when every tick was read as it was issued, ISSUE
# 60: one program more for each of the two requests that waited for a slot)
# and the call that only reads the last
TICKS = 13
PHASES = ("serve.schedule", "serve.mixed.build", "serve.mixed.dispatch",
          "serve.mixed.wait", "serve.mixed", "serve.emit", "serve.retire",
          "serve.tick")
# the order in which the spans of one tick() call close (ISSUE 60): the
# program of its own step is issued, then the one before it is read
ISSUE = ("serve.schedule", "serve.mixed.build", "serve.mixed.dispatch",
         "serve.mixed")
READ = ("serve.mixed.wait", "serve.emit", "serve.retire")


def closing_order(first, calls):
    """``(name, step)`` of every span that ``calls`` tick() calls close, the
    first at step ``first``, when only the first has nothing to read and
    only the last nothing to issue."""
    out = []
    for i in range(calls):
        step = first + i
        out += [(name, step) for name in (
            ISSUE if i < calls - 1 else ISSUE[:1])]
        if i:
            out += [(name, step - 1) for name in READ]
        if i == calls - 1:  # no program of its own: an empty retire says so
            out.append(("serve.retire", step))
        out.append(("serve.tick", step))
    return out
# what serve.mixed's row held at the parent of ISSUE 57, for a dense model,
# and the sub-tiles its rows hold (ISSUE 69)
MIXED_FIELDS = {"decodes", "chunks", "width", "tokens", "sampled_rows",
                "kv_rows", "kv_tiles", "kv_subtiles"}


@pytest.fixture(scope="module")
def toy_inference():
    from scaling_tpu.serve.bench import build_toy_inference

    return build_toy_inference(hidden=32, layers=2, vocab=64, heads=4)


def make_engine(toy_inference, **kw):
    from scaling_tpu.serve.engine import EngineConfig, ServeEngine

    defaults = dict(num_slots=2, block_size=4, num_blocks=64,
                    max_blocks_per_seq=8, token_budget=64, prefill_chunk=4,
                    enable_prefix_cache=False)
    defaults.update(kw)
    engine = ServeEngine(toy_inference, EngineConfig(**defaults))
    engine.warmup_mode = True  # compile off the books: counts nothing
    engine.submit([1, 2], 2)
    engine.run_until_done()
    engine.warmup_mode = False
    engine.finished.clear()
    return engine


def moved(before, after):
    """What a run added to the registry: counters by difference, gauges as
    they stand, histograms' count / sum / per-bucket contents by difference."""
    counters = {k: v - before["counters"].get(k, 0.0)
                for k, v in after["counters"].items()
                if k.startswith("serve_") and v != before["counters"].get(k, 0.0)}
    hists = {}
    for k, h in after["histograms"].items():
        b = before["histograms"].get(k, {"count": 0, "sum": 0.0, "buckets": {}})
        if k.startswith("serve_") and h["count"] != b["count"]:
            hists[k] = {"count": h["count"] - b["count"], "sum": h["sum"] - b["sum"],
                        "buckets": {le: n - b["buckets"].get(le, 0)
                                    for le, n in h["buckets"].items()}}
    gauges = {k: v for k, v in after["gauges"].items() if k.startswith("serve_")}
    return counters, gauges, hists


def by_hand(values):
    """A histogram's cumulative buckets of ``values``, counted here."""
    counts = [0] * (len(DEFAULT_BUCKETS) + 1)
    for v in values:
        counts[bisect.bisect_left(DEFAULT_BUCKETS, v)] += 1
    out, running = {}, 0
    for bound, n in zip(DEFAULT_BUCKETS, counts):
        running += n
        out[f"{bound:g}"] = running
    out["+Inf"] = running + counts[-1]
    return out


@pytest.mark.parametrize("replica", [None, 57])
def test_after_a_fixed_run_every_serve_metric_reads_a_per_token_count(
        toy_inference, replica):
    """The literal numbers below are what the parent of ISSUE 57 reads for
    this run (held there before the change was written); the histograms'
    contents are timings, so they are counted here from the sequences' own
    stamps, a token at a time, and must agree bucket by bucket."""
    engine = make_engine(toy_inference, replica_id=replica)
    reg = obs.get_registry()
    since = time.monotonic_ns()
    before = reg.snapshot()
    arrival = time.monotonic()
    seqs = [engine.submit(p, n, arrival_s=arrival) for p, n in WORK]
    engine.run_until_done()
    counters, gauges, hists = moved(before, reg.snapshot())
    assert [len(s.generated) for s in seqs] == [n for _, n in WORK]

    def named(name, **labels):
        inner = ",".join(f"{k}={v}" for k, v in sorted(
            {**labels, **({"replica": replica} if replica is not None else {})}.items()))
        return f"{name}{{{inner}}}" if inner else name

    rows = [r for r in obs.recorded_spans(since_ns=since)
            if r.name == "serve.tick" and r.fields.get("replica") == replica]
    assert len(rows) == TICKS
    assert counters == {
        named("serve_requests_admitted_total"): 4.0,
        named("serve_requests_completed_total"): 4.0,
        named("serve_tokens_generated_total"): float(TOKENS),
        named("serve_prefill_tokens_total"): float(PROMPT_TOKENS),
        named("serve_sampler_ticks_total", path="greedy"): float(TICKS - 1),
        named("serve_mixed_ticks_total", width=8): float(TICKS - 1),
        # every call but the first (nothing to run ahead of) and the last
        # (nothing left to issue) issued its program before it read
        named("serve_ticks_overlapped_total"): float(TICKS - 2),
        named("serve_ticks_synchronous_total", reason="first"): 1.0,
        named("serve_ticks_synchronous_total", reason="drained"): 1.0,
    }
    sched = engine.scheduler
    assert gauges.items() >= {
        named(k): v for k, v in sched.gauges().items()}.items()
    assert gauges[named("serve_running_seqs")] == 0.0
    assert gauges[named("serve_free_blocks")] == 63.0
    # the two latency histograms and the queue wait: one sample a token
    # after a request's first, one a request, one a request
    itl = [b - a for s in seqs for a, b in zip(s.token_stamps, s.token_stamps[1:])]
    ttft = [s.first_token_s - arrival for s in seqs]
    waits = [s.admitted_s - arrival for s in seqs]
    assert len(itl) == TOKENS - len(WORK)
    for name, values in (("serve_itl_seconds", itl), ("serve_ttft_seconds", ttft),
                         ("serve_queue_wait_seconds", waits)):
        got = hists.pop(named(name))
        assert got["count"] == len(values), name
        assert got["buckets"] == by_hand(values), name
        assert got["sum"] == pytest.approx(sum(values)), name
    assert hists == {}


def test_emit_and_retire_say_what_they_handled_and_a_tick_closes_8_spans(
        toy_inference):
    engine = make_engine(toy_inference)
    since = time.monotonic_ns()
    for p, n in WORK:
        engine.submit(p, n)
    engine.run_until_done()
    rows = obs.recorded_spans(since_ns=since)
    spans = [r for r in rows if r.name != "serve.first_token"]
    ticks = [r for r in spans if r.name == "serve.tick"]
    assert len(ticks) == TICKS
    assert [(r.name, r.step) for r in spans] == closing_order(
        ticks[0].step, TICKS)
    emits = [r for r in spans if r.name == "serve.emit"]
    assert sum(r.fields["tokens"] for r in emits) == TOKENS
    by_step = {r.step: r for r in ticks}
    for r in emits:
        tick = by_step[r.step].fields
        # decode rows, and the chunk rows whose prompt this tick finished
        assert tick["decodes"] <= r.fields["rows"] <= tick["decodes"] + tick["chunks"]
        assert r.fields["tokens"] == r.fields["rows"]  # no drafts: a token a row
    assert sum(r.fields["rows"] for r in emits) == TOKENS
    retires = [r for r in spans if r.name == "serve.retire"]
    assert sum(r.fields["finished"] for r in retires) == len(WORK)
    assert {r.fields["finished"] for r in retires} <= {0, 1, 2}
    # serve.mixed's row holds every field it held, and no other
    for r in spans:
        if r.name == "serve.mixed":
            assert set(r.fields) == MIXED_FIELDS
            assert r.fields["width"] == 8 and 0 < r.fields["tokens"] <= 8


def test_a_tick_looks_nothing_up_in_the_registry_after_a_label_sets_first_use(
        toy_inference, monkeypatch):
    engine = make_engine(toy_inference)
    lookups = []
    real = MetricsRegistry._get
    monkeypatch.setattr(MetricsRegistry, "_get", lambda self, cls, name, labels, **kw: (
        lookups.append((name, *sorted((labels or {}).items())))
        or real(self, cls, name, labels, **kw)))
    engine.submit(*WORK[0])
    engine.run_until_done()
    first = list(lookups)
    # once a label set: serve_ticks_synchronous_total under its two reasons
    assert ("serve_tokens_generated_total",) in first
    assert len(set(first)) == len(first)
    engine.submit(*WORK[1])
    engine.run_until_done()
    assert lookups == first  # the second request's ticks: handles only


def test_serve_mixed_is_annotated_once_the_program_is_issued(toy_inference,
                                                             monkeypatch):
    """The counters and fields of ``serve.mixed`` need nothing of the call:
    they are written after ``serve.mixed.dispatch`` has closed and before
    ``serve.mixed.wait`` opens, where the chip is busy."""
    engine = make_engine(toy_inference)
    seen = []
    real = engine._annotate_mixed

    def spy(*args):
        seen.append(obs.recorded_spans()[-1].name)
        real(*args)
        seen.append(obs.current_span().name)

    monkeypatch.setattr(engine, "_annotate_mixed", spy)
    engine.submit(*WORK[0])
    engine.run_until_done()
    assert seen and set(seen[0::2]) == {"serve.mixed.dispatch"}
    assert set(seen[1::2]) == {"serve.mixed"}  # no leaf span open around it


def test_under_a_capture_an_annotation_carries_its_step_and_the_offset_joins(
        toy_inference, tmp_path):
    engine = make_engine(toy_inference)
    first = engine.tick_index
    obs.start_capture(tmp_path / "trace")
    try:
        for p, n in WORK[:2]:
            engine.submit(p, n)
        engine.run_until_done()
    finally:
        capture = obs.stop_capture()
    spans = [s for s in capture.spans if s[0] != "serve.first_token"]
    annotations = capture.annotations()
    expected = closing_order(first, engine.tick_index - first)
    assert [(name, fields["step"]) for name, _, _, fields in spans] == expected
    assert len(annotations) == len(expected)
    assert {(name, step) for name, step, _, _ in annotations} == set(expected)
    assert {name for name, _ in expected} == set(PHASES)
    # the offset lays every row on its annotation, to microseconds (nine in
    # ten: a row whose thread lost the CPU between the annotation's start
    # and the span's clock read lies off by that pause, on any clock)
    offset = capture.clock_offset_ns
    assert offset is not None
    at = {(name, step): (start, dur) for name, step, start, dur in annotations}
    off = sorted(abs(start + offset - at[(name, fields["step"])][0])
                 for name, start, _, fields in spans)
    assert off[len(off) * 9 // 10] < 50_000 and off[-1] < 5_000_000
    # and the recorder's own rows, whose start is absolute: counted down by
    # the capture's origin
    rows = [r for r in obs.recorded_spans(since_ns=capture.origin_ns)
            if (r.name, r.step) in at]
    assert len(rows) == len(spans)
    off = sorted(abs(r.start_ns - capture.origin_ns + offset - at[(r.name, r.step)][0])
                 for r in rows)
    assert off[len(off) * 9 // 10] < 50_000
    # a request's first-token row has no annotation and no step: it is laid
    # on the trace's clock by the offset alone, inside its tick's emit
    token = [s for s in capture.spans if s[0] == "serve.first_token"][0]
    ends = token[1] + token[2] + offset
    assert any(start <= ends <= start + dur for (name, _), (start, dur)
               in at.items() if name == "serve.emit")


def test_a_capture_with_no_trace_or_no_step_has_no_offset(tmp_path):
    from scaling_tpu.obs import Capture

    assert Capture(str(tmp_path), 1.0, {}, spans=[]).clock_offset_ns is None
    assert Capture(str(tmp_path), 1.0, {}, spans=[
        ("serve.tick", 0, 10, {"step": 1})]).clock_offset_ns is None  # no file
    obs.start_capture(tmp_path / "trace")
    try:
        with obs.span("phase.without.step"):
            pass
    finally:
        capture = obs.stop_capture()
    assert [a[:2] for a in capture.annotations()] == [("phase.without.step", None)]
    assert capture.clock_offset_ns is None
