"""The one start/stop control for tracing (obs/capture.py): captures can
be taken more than once in a process, the spans' annotations are on exactly
while one is, the interpreter's tracer only when asked for, and a span with
no sink builds no event record."""

import json

import pytest

from scaling_tpu import obs
from scaling_tpu.obs import spans as spans_module
from scaling_tpu.obs.registry import MetricsRegistry


def host_events(trace_file):
    """name -> count of the events on the host plane's lines."""
    from jax.profiler import ProfileData

    counts = {}
    for plane in ProfileData.from_file(str(trace_file)).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for event in line.events:
                counts[event.name] = counts.get(event.name, 0) + 1
    return counts


@pytest.fixture(autouse=True)
def _no_capture_left_on():
    yield
    if obs.capturing():
        obs.stop_capture()


def test_two_captures_in_one_process_give_two_traces_and_two_records(tmp_path):
    reg = MetricsRegistry()
    records = []
    for i, name in enumerate(("first", "second")):
        assert not obs.capturing()
        obs.start_capture(tmp_path / name, registry=reg)
        assert obs.capturing()
        reg.counter("work_total").inc(i + 1)
        for step in range(i + 2):
            with obs.span("phase.outer", step=step, registry=reg):
                with obs.span("phase.inner", registry=reg, rows=3,
                              traces=["not", "a", "scalar"]):
                    pass
        records.append(obs.stop_capture())
        assert obs.last_capture() is records[-1]
    first, second = records
    assert first.trace_dir != second.trace_dir
    for i, rec in enumerate(records):
        assert rec.trace_file() is not None and rec.seconds >= 0
        # every span closed during the capture, in closing order
        assert [s[0] for s in rec.spans] == ["phase.inner", "phase.outer"] * (i + 2)
        inner, outer = rec.spans[0], rec.spans[1]
        assert inner[3] == {"rows": 3, "parent": "phase.outer"}
        assert outer[3] == {"step": 0}
        assert outer[1] <= inner[1] and inner[2] <= outer[2]  # nested in time
        # the counters' differences over THIS capture
        assert rec.counters == {"work_total": i + 1}
        # and the spans lie on the profiler's host plane under their names
        events = host_events(rec.trace_file())
        assert events["phase.outer"] == events["phase.inner"] == i + 2


def test_start_while_on_and_stop_while_off_raise(tmp_path):
    with pytest.raises(RuntimeError, match="no capture is on"):
        obs.stop_capture()
    obs.start_capture(tmp_path / "t")
    with pytest.raises(RuntimeError, match="already on"):
        obs.start_capture(tmp_path / "u")
    assert obs.capturing()  # the refused start left the first one alone
    obs.stop_capture()
    with pytest.raises(RuntimeError, match="no capture is on"):
        obs.stop_capture()


def test_stop_is_safe_in_a_finally_when_the_profiler_fails(tmp_path, monkeypatch):
    """A profiler that cannot write its trace must not leave the control
    on: the record is kept and the next capture starts."""
    import jax

    obs.start_capture(tmp_path / "t")
    with obs.span("kept"):
        pass
    real_stop = jax.profiler.stop_trace

    def failing_stop():
        real_stop()
        raise RuntimeError("disk full")

    monkeypatch.setattr(jax.profiler, "stop_trace", failing_stop)
    with pytest.raises(RuntimeError, match="disk full"):
        obs.stop_capture()
    assert not obs.capturing()
    assert [s[0] for s in obs.last_capture().spans] == ["kept"]
    monkeypatch.undo()
    obs.start_capture(tmp_path / "again")
    obs.stop_capture()


def test_without_a_capture_a_span_annotates_nothing_and_keeps_nothing(monkeypatch):
    """Booby-trap, as test_step_path.py does for syncs: with no capture
    on, a span must not touch the profiler; it lands in the recorder (and
    the histogram) alone, and no capture holds it."""
    import jax

    def boom(*a, **k):  # pragma: no cover - firing IS the failure
        raise AssertionError("a span reached the profiler with no capture on")

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", boom)
    last = obs.last_capture()
    held = len(last.spans) if last else 0
    reg = MetricsRegistry()
    with obs.span("serve.tick", step=1, registry=reg) as sp:
        with obs.span("serve.schedule", registry=reg):
            pass
    assert sp.duration_s is not None
    assert [r.name for r in obs.recorded_spans()[-2:]] == [
        "serve.schedule", "serve.tick"]
    assert obs.last_capture() is last and (
        last is None or len(last.spans) == held)
    assert reg.snapshot()["histograms"]["span_seconds{span=serve.tick}"]["count"] == 1


def test_a_span_opened_before_the_capture_is_not_kept(tmp_path):
    with obs.span("straddles"):
        obs.start_capture(tmp_path / "t")
        with obs.span("inside"):
            pass
    rec = obs.stop_capture()
    assert [s[0] for s in rec.spans] == ["inside"]


@pytest.mark.parametrize("python_frames, level", [(None, 0), (False, 0), (True, 1)])
def test_the_interpreters_tracer_is_on_only_when_asked_for(
        python_frames, level, tmp_path, monkeypatch):
    """``start_capture`` hands the profiler its options: the host tracer
    as it comes (the spans' annotations), the interpreter's tracer off
    unless ``python_frames=True``. The profiler is stubbed."""
    import jax

    seen = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda log_dir, **kw: seen.append((log_dir, kw)))
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    kwargs = {} if python_frames is None else {"python_frames": python_frames}
    obs.start_capture(tmp_path / "t", **kwargs)
    obs.stop_capture()
    (log_dir, kw), = seen
    assert log_dir == str(tmp_path / "t") and set(kw) == {"profiler_options"}
    options = kw["profiler_options"]
    assert options.python_tracer_level == level
    assert options.host_tracer_level == jax.profiler.ProfileOptions().host_tracer_level == 2


EVENT_CASES = {
    "traceless": (None, dict(step=7, backend="npz")),
    "traced": ("feedc0de00000001", dict(step=7, rows=2)),
}


@pytest.mark.parametrize("case", sorted(EVENT_CASES))
def test_event_record_is_byte_for_byte_what_it_was(case, tmp_path, monkeypatch):
    """With an events path the span's record is exactly the one the
    pre-capture ``_emit`` wrote: same keys, same order, same rounding
    (``ts`` is the clock's and ``span_id`` random, so both are pinned)."""
    trace_id, fields = EVENT_CASES[case]
    path = tmp_path / "events.jsonl"
    monkeypatch.setenv("SCALING_TPU_EVENTS_PATH", str(path))
    monkeypatch.setenv("SCALING_TPU_HOST_ID", "3")
    monkeypatch.setattr(spans_module, "_clock", iter([10.0, 10.25]).__next__)
    # a row at a made-up time stays out of the process-wide ring, where every
    # row lies after ``process.start``
    monkeypatch.setattr(spans_module, "_record", [].append)
    monkeypatch.setattr("time.time", lambda: 1234.5)
    monkeypatch.setattr(spans_module, "new_span_id", lambda: "abcd1234")
    with obs.trace_context(trace_id):
        with obs.span("ckpt.stage", registry=MetricsRegistry(), **fields) as sp:
            sp.annotate(extra="x")
    expected = {"event": "span", "ts": 1234.5, "span": "ckpt.stage",
                "dur_s": 0.25, "ok": True, "host": 3, "extra": "x", **fields}
    if trace_id:
        expected.update(trace=trace_id, span_id="abcd1234")
    assert path.read_text() == json.dumps(expected, sort_keys=True) + "\n"


def test_with_no_sink_emit_serialises_nothing(monkeypatch):
    """No events path, no mirror at the span's level: no record is built,
    nothing is serialised, nothing is logged. The histogram still counts."""
    from scaling_tpu.logging import logger

    def boom(*a, **k):  # pragma: no cover - firing IS the failure
        raise AssertionError("a span with no sink built an event record")

    monkeypatch.delenv("SCALING_TPU_EVENTS_PATH", raising=False)
    assert not logger.takes_events("debug")
    monkeypatch.setattr(logger, "log_event", boom)
    monkeypatch.setattr(json, "dumps", boom)
    reg = MetricsRegistry()
    with obs.span("serve.tick", step=1, registry=reg, decodes=3):
        pass
    assert reg.snapshot()["histograms"]["span_seconds{span=serve.tick}"]["count"] == 1
    # a mirror at the span's level is a sink again
    seen = []
    monkeypatch.setattr(logger, "log_event", lambda *a, **k: seen.append(k))
    with obs.span("lifecycle", level="info", registry=reg):
        pass
    assert [k["span"] for k in seen] == ["lifecycle"]
