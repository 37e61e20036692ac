"""The bounded recorder (obs/recorder.py): every closed span lands in it
exactly and in closing order, capture or not; the ring drops its oldest;
a capture is the rows between its two markers; ``record_span`` writes a
phase that is no code region; and a span pays no registry lookup after
its first."""

import threading
import time

import pytest

from scaling_tpu import obs
from scaling_tpu.obs import recorder as recorder_module
from scaling_tpu.obs import spans as spans_module
from scaling_tpu.obs.recorder import CAPTURE_MARKER, Recorder, Row
from scaling_tpu.obs.registry import MetricsRegistry


@pytest.fixture(autouse=True)
def _no_capture_left_on():
    yield
    if obs.capturing():
        obs.stop_capture()


@pytest.fixture
def ring_restored():
    """The process-wide ring as it was found: a test that writes rows at
    made-up times leaves none behind (every row of the running program lies
    after ``process.start``, and a later test may say so)."""
    ring = recorder_module._recorder.ring
    held = list(ring)
    yield
    ring.clear()
    ring.extend(held)


def test_rows_are_exact_and_in_closing_order(monkeypatch, ring_restored):
    """Four clock reads, two nested spans: each row is the span itself, to
    the nanosecond, the child first."""
    monkeypatch.setattr(spans_module, "_clock",
                        iter([100.0, 100.001, 100.0035, 100.01]).__next__)
    reg = MetricsRegistry()
    with obs.span("outer", step=5, registry=reg, rows=2) as outer:
        with obs.span("inner", registry=reg, traces=["not", "numbers"],
                      exit_p=[0.25, 0.75], empty=[]) as inner:
            inner.annotate(tokens=7, ok=True, note="x", blob=object())
    child, parent = obs.recorded_spans()[-2:]
    assert child == Row("inner", 100_001_000_000, 2_500_000, None, "outer",
                        {"exit_p": [0.25, 0.75], "tokens": 7, "ok": True,
                         "note": "x"})
    assert parent == Row("outer", 100_000_000_000, 10_000_000, 5, None,
                         {"rows": 2})
    assert outer.duration_s == pytest.approx(0.01)
    # fields are kept by reference and filtered when read, not when written
    raw = recorder_module._recorder.ring[-2]
    assert raw[5] is inner.fields and "blob" in raw[5]


def test_recorded_spans_filters_by_name_and_start():
    reg = MetricsRegistry()
    with obs.span("early", registry=reg):
        pass
    since = time.monotonic_ns()
    for step in range(3):
        with obs.span("late.a", step=step, registry=reg):
            with obs.span("late.b", step=step, registry=reg):
                pass
    rows = obs.recorded_spans(since_ns=since)
    assert [r.name for r in rows] == ["late.b", "late.a"] * 3
    assert [r.step for r in obs.recorded_spans(since_ns=since, name="late.a")] == [
        0, 1, 2]
    assert all(r.start_ns >= since for r in rows)
    assert "early" in [r.name for r in obs.recorded_spans()]
    assert "early" not in [r.name for r in rows]


def test_the_ring_drops_the_oldest_at_maxlen():
    assert recorder_module._recorder.ring.maxlen == recorder_module.RING_ROWS == 131_072
    ring = Recorder(maxlen=4)
    for i in range(6):
        ring.append((f"s{i}", float(i), 0.5, i, None, {}))
    assert [r.name for r in ring.rows()] == ["s2", "s3", "s4", "s5"]
    # the tail: back to the last `count` rows of a name, children included
    ticks = Recorder(maxlen=16)
    for step in range(4):
        ticks.append(("child", float(step), 0.25, step, "tick", {}))
        ticks.append(("tick", float(step), 0.5, step, None, {}))
    assert [(r.name, r.step) for r in ticks.tail("tick", 2)] == [
        ("child", 2), ("tick", 2), ("child", 3), ("tick", 3)]
    assert ticks.tail("tick", 0) == []
    assert ticks.tail("tick", 9) == ticks.rows()  # fewer than asked for: all there is


def test_a_capture_is_the_rows_between_its_two_markers(tmp_path):
    """Taken twice in one process: each capture's ``spans`` are the ring's
    rows between ITS markers, as the 4-tuples they always were (``step`` and
    ``parent`` inside the fields, the fields filtered, ``start_ns`` from the
    capture's origin), the markers themselves left out."""
    reg = MetricsRegistry()
    since = time.monotonic_ns()
    captures = []
    for i, name in enumerate(("first", "second")):
        with obs.span("before", registry=reg):
            pass
        obs.start_capture(tmp_path / name, registry=reg)
        for step in range(i + 1):
            with obs.span("phase.outer", step=step, registry=reg):
                with obs.span("phase.inner", registry=reg, rows=3,
                              traces=["not", "a", "scalar"]):
                    pass
        obs.record_span("request.first", time.monotonic(), 0.25, req=i)
        captures.append(obs.stop_capture())
    rows = obs.recorded_spans(since_ns=since)
    markers = [k for k, r in enumerate(rows) if r.name == CAPTURE_MARKER]
    assert len(markers) == 4
    assert [rows[k].fields for k in markers] == [
        {"trace_dir": str(tmp_path / name), "edge": edge}
        for name in ("first", "second") for edge in ("start", "stop")]
    for i, capture in enumerate(captures):
        start, stop = rows[markers[2 * i]], rows[markers[2 * i + 1]]
        inside = rows[markers[2 * i] + 1:markers[2 * i + 1]]
        assert [s[0] for s in capture.spans] == [r.name for r in inside] == \
            ["phase.inner", "phase.outer"] * (i + 1) + ["request.first"]
        for got, row in zip(capture.spans, inside):
            name, start_ns, duration_ns, fields = got
            want = dict(row.fields)
            if row.step is not None:
                want["step"] = row.step
            if row.parent is not None:
                want["parent"] = row.parent
            assert (name, duration_ns, fields) == (row.name, row.duration_ns, want)
            assert abs(start_ns - (row.start_ns - start.start_ns)) <= 1
            assert 0 <= start_ns <= stop.start_ns - start.start_ns
        assert capture.spans[0][3] == {"rows": 3, "parent": "phase.outer"}
        # the pair's span is the capture
        assert (stop.start_ns - start.start_ns - start.duration_ns) / 1e9 == \
            pytest.approx(capture.seconds, abs=1e-6)
    # a capture keeps no list: the same rows on every read, cut anew
    assert captures[0].spans == captures[0].spans
    assert captures[0].spans is not captures[0].spans
    assert not hasattr(obs.capture._Active, "spans")
    assert not hasattr(obs.capture._Active, "close_span")


def test_a_capture_whose_markers_left_the_ring_holds_what_is_left():
    ring = Recorder(maxlen=5)
    start = ("obs.capture", 10.0, 0.5, None, None, {"edge": "start"})
    stop = ("obs.capture", 20.0, 0.0, None, None, {"edge": "stop"})
    ring.append(start)
    for i in range(3):
        ring.append((f"s{i}", 11.0 + i, 0.25, i, None, {}))
    ring.append(stop)
    assert [s[0] for s in ring.between(start, stop)] == ["s0", "s1", "s2"]
    assert ring.between(start, stop)[0] == ("s0", 1_000_000_000, 250_000_000,
                                            {"step": 0})
    ring.append(("after", 21.0, 0.1, None, None, {}))  # drops the start marker
    assert [s[0] for s in ring.between(start, stop)] == ["s0", "s1", "s2"]
    for _ in range(5):
        ring.append(("later", 22.0, 0.1, None, None, {}))
    assert ring.between(start, stop) == []


def test_record_span_writes_a_row_and_nothing_else(monkeypatch, ring_restored):
    def boom(*a, **k):  # pragma: no cover - firing IS the failure
        raise AssertionError("record_span reached the registry")

    monkeypatch.setattr(MetricsRegistry, "_get", boom)
    obs.record_span("serve.first_token", 12.5, 0.75, queue_s=0.5, req=3,
                    traces=["x"])
    assert obs.recorded_spans()[-1] == Row(
        "serve.first_token", 12_500_000_000, 750_000_000, None, None,
        {"queue_s": 0.5, "req": 3})


def test_two_threads_record_into_the_one_ring():
    """More spans than a switch interval lets one thread finish: every row
    of both threads is there, each thread's own in its closing order, each
    child under its own thread's parent."""
    import sys

    reg = MetricsRegistry()
    since = time.monotonic_ns()
    n = 2000

    def work(tag):
        for step in range(n):
            with obs.span(f"{tag}.outer", step=step, registry=reg):
                with obs.span(f"{tag}.inner", step=step, registry=reg):
                    pass

    threads = [threading.Thread(target=work, args=(tag,)) for tag in ("a", "b")]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    rows = obs.recorded_spans(since_ns=since)
    for tag in ("a", "b"):
        mine = [r for r in rows if r.name.startswith(tag + ".")]
        assert [(r.name, r.step) for r in mine] == [
            (f"{tag}.{part}", step) for step in range(n)
            for part in ("inner", "outer")]
        assert all(r.parent == f"{tag}.outer" for r in mine
                   if r.name.endswith(".inner"))
    assert len(rows) == 4 * n


def test_a_span_does_no_registry_lookup_after_its_first(monkeypatch):
    """Counted, not timed: the ``span_seconds`` handle is kept per
    (registry, span name), so the second span of a name never reaches
    ``MetricsRegistry._get`` (its lock, its label key)."""
    lookups = []
    real = MetricsRegistry._get

    def counting(self, cls, name, labels, **kw):
        lookups.append((name, dict(labels or {})))
        return real(self, cls, name, labels, **kw)

    monkeypatch.setattr(MetricsRegistry, "_get", counting)
    reg, other = MetricsRegistry(), MetricsRegistry()
    for _ in range(50):
        with obs.span("serve.tick", registry=reg):
            with obs.span("serve.schedule", registry=reg):
                pass
    assert lookups == [("span_seconds", {"span": "serve.schedule"}),
                       ("span_seconds", {"span": "serve.tick"})]
    hists = reg.snapshot()["histograms"]
    assert hists["span_seconds{span=serve.tick}"]["count"] == 50
    assert hists["span_seconds{span=serve.schedule}"]["count"] == 50
    with obs.span("serve.tick", registry=other):  # a handle is its registry's
        pass
    assert len(lookups) == 3
    assert other.snapshot()["histograms"]["span_seconds{span=serve.tick}"]["count"] == 1
    # reset() drops the handles with the metrics: no span observes into a
    # histogram the registry no longer holds
    reg.reset()
    with obs.span("serve.tick", registry=reg):
        pass
    assert len(lookups) == 4
    assert reg.snapshot()["histograms"]["span_seconds{span=serve.tick}"]["count"] == 1


def test_span_seconds_keeps_the_default_buckets_and_the_recorder_the_phase():
    """``span_seconds`` is bucketed like every other histogram (first bound
    1 ms: its four finer buckets went with ISSUE 57); what resolves a
    tick's phases is the recorder's row, which is the span itself."""
    from scaling_tpu.obs.registry import DEFAULT_BUCKETS

    reg = MetricsRegistry()
    since = time.monotonic_ns()
    with obs.span("phase", registry=reg) as sp:
        pass
    assert reg.span_handles["phase"].buckets == DEFAULT_BUCKETS
    buckets = reg.snapshot()["histograms"]["span_seconds{span=phase}"]["buckets"]
    assert list(buckets)[0] == "0.001" and buckets["0.001"] == 1
    row, = obs.recorded_spans(since_ns=since, name="phase")
    assert row.duration_ns == round(sp.duration_s * 1e9) < 1_000_000


def test_past_the_series_cap_a_leaking_span_name_keeps_no_handle():
    from scaling_tpu.obs.registry import MAX_SERIES_PER_METRIC

    reg = MetricsRegistry()
    for i in range(MAX_SERIES_PER_METRIC + 5):
        with obs.span(f"leak.{i}", registry=reg):
            pass
    assert len(reg.span_handles) == MAX_SERIES_PER_METRIC
    overflow = reg.snapshot()["histograms"]["span_seconds{__overflow__=true}"]
    assert overflow["count"] == 5
