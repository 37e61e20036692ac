"""The engine's tick by phase (ISSUE 25): every phase a span at the place
of the work, ``serve.tick`` opened by ``tick()`` itself and so exactly
once a tick whoever drives the engine, all of them on the profiler's
host plane while a capture is on; and the scheduler's ``admitted_s``
stamp behind ``queue_wait_s``."""

import json
import time

import pytest

from scaling_tpu import obs
from scaling_tpu.obs.report import load_run_dir
from scaling_tpu.obs.trace import analyze

PROMPTS = [[1, 2, 3, 4, 5], [7, 8, 9], [11, 12, 13, 14, 15, 16, 17, 18],
           [3, 1, 4]]
PHASES = ("serve.schedule", "serve.mixed.build", "serve.mixed.dispatch",
          "serve.mixed.wait", "serve.mixed", "serve.emit", "serve.retire",
          "serve.tick")  # the order in which one tick's spans close


@pytest.fixture(scope="module")
def toy_inference():
    from scaling_tpu.serve.bench import build_toy_inference

    return build_toy_inference(hidden=32, layers=2, vocab=64, heads=4)


def make_engine(toy_inference, **kw):
    from scaling_tpu.serve.engine import EngineConfig, ServeEngine

    defaults = dict(num_slots=2, block_size=4, num_blocks=64,
                    max_blocks_per_seq=8, token_budget=64, prefill_chunk=4)
    defaults.update(kw)
    return ServeEngine(toy_inference, EngineConfig(**defaults))


@pytest.fixture()
def events(tmp_path, monkeypatch):
    path = tmp_path / "events.jsonl"
    monkeypatch.setenv("SCALING_TPU_EVENTS_PATH", str(path))
    return path


def read(path):
    return [json.loads(line) for line in path.read_text().splitlines()]


def test_under_a_capture_every_tick_lies_on_the_host_plane_by_phase(
        toy_inference, tmp_path):
    from jax.profiler import ProfileData

    e = make_engine(toy_inference)
    e.warmup_mode = True  # compile off the books, and emit no span
    e.submit([1, 2], 2)
    e.run_until_done()
    e.warmup_mode = False
    first_tick = e.tick_index
    obs.start_capture(tmp_path / "trace")
    try:
        for p in PROMPTS[:3]:
            e.submit(p, 4)
        e.run_until_done()
    finally:
        rec = obs.stop_capture()
    ticks = e.tick_index - first_tick
    assert ticks > 4
    # the capture's list: one tick after the other, each phase once, in
    # the order the spans close, all of one tick under its step
    assert [s[0] for s in rec.spans] == list(PHASES) * ticks
    for i in range(ticks):
        row = {s[0]: s for s in rec.spans[i * len(PHASES):(i + 1) * len(PHASES)]}
        assert {s[3]["step"] for s in row.values()} == {first_tick + i}
        tick = row["serve.tick"]
        assert tick[3]["decodes"] + tick[3]["chunks"] > 0
        children = [row[n] for n in ("serve.schedule", "serve.mixed",
                                     "serve.emit", "serve.retire")]
        assert all(c[3]["parent"] == "serve.tick" for c in children)
        assert tick[2] >= sum(c[2] for c in children)
        mixed = [row[n] for n in ("serve.mixed.build", "serve.mixed.dispatch",
                                  "serve.mixed.wait")]
        assert all(c[3]["parent"] == "serve.mixed" for c in mixed)
        assert row["serve.mixed"][2] >= sum(c[2] for c in mixed)
        # in time too: each child inside its tick
        assert all(tick[1] <= c[1] and c[1] + c[2] <= tick[1] + tick[2]
                   for c in children + mixed)
    # the same spans on the host plane of the .xplane.pb, by name
    names = {}
    for plane in ProfileData.from_file(str(rec.trace_file())).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                for event in line.events:
                    names[event.name] = names.get(event.name, 0) + 1
    for phase in PHASES:
        assert names.get(phase) == ticks, phase
    # and the counters the capture differenced are the traced ticks'
    assert rec.counters["serve_prefill_tokens_total"] == sum(
        len(p) for p in PROMPTS[:3])
    assert rec.counters["serve_tokens_generated_total"] == 12


def test_warmup_ticks_emit_no_phase_span(toy_inference, events):
    e = make_engine(toy_inference)
    e.warmup_mode = True
    e.submit([1, 2], 2)
    e.run_until_done()
    assert not events.exists() or not [
        r for r in read(events) if r.get("event") == "span"]


def drive_single(engine, tmp_path):
    from scaling_tpu.serve.bench import run_bench

    run_bench(engine, [(0.0, p, 3) for p in PROMPTS[:2]])
    return [engine]


def drive_fleet(engine, tmp_path):
    from scaling_tpu.serve.bench import run_fleet_bench
    from scaling_tpu.serve.engine import EngineConfig, ServeEngine
    from scaling_tpu.serve.router import FleetRouter

    engines = [
        ServeEngine(engine.inf, EngineConfig(
            replica_id=r, num_slots=2, block_size=4, num_blocks=64,
            max_blocks_per_seq=8, token_budget=64, prefill_chunk=4))
        for r in range(2)
    ]
    run_fleet_bench(FleetRouter(engines), [(0.0, p, 3) for p in PROMPTS])
    return engines


@pytest.mark.parametrize("drive", [drive_single, drive_fleet])
def test_serve_tick_is_emitted_exactly_once_a_tick_by_the_bench_loops(
        drive, toy_inference, events, tmp_path):
    """``tick()`` opens ``serve.tick`` itself; the wrappers the two loops
    of serve/bench.py had around it are gone, so no tick reads twice."""
    engines = drive(make_engine(toy_inference), tmp_path)
    spans = [r for r in read(events) if r.get("event") == "span"]
    for e in engines:
        mine = [r for r in spans if r.get("replica") == e.replica_id
                and r["span"] == "serve.tick"]
        assert [r["step"] for r in mine] == list(range(e.tick_index))
        assert all("decodes" in r and "chunks" in r for r in mine)
    assert sum(r["span"] == "serve.tick" for r in spans) == sum(
        e.tick_index for e in engines)
    children = [r for r in spans if r["span"] == "serve.schedule"]
    assert len(children) == sum(e.tick_index for e in engines)
    assert all(r["parent"] == "serve.tick" for r in children)


def test_a_request_that_waited_for_a_slot_carries_its_queue_wait(
        toy_inference, events):
    """One slot, two requests: the second is admitted only after the
    first has finished. The scheduler stamps when each first got its
    slot, the engine observes arrival-to-stamp and writes it beside
    ``ttft_s``, and ``obs trace`` reads it in place of its inference."""
    e = make_engine(toy_inference, num_slots=1)
    before = obs.get_registry().snapshot()["histograms"].get(
        "serve_queue_wait_seconds", {"count": 0, "sum": 0.0})
    arrival = time.monotonic()
    with obs.trace_context("aaaa000000000001"):
        first = e.submit(PROMPTS[0], 3, arrival_s=arrival)
    with obs.trace_context("aaaa000000000002"):
        second = e.submit(PROMPTS[1], 3, arrival_s=arrival)
    assert first.admitted_s is None and second.admitted_s is None
    e.tick()
    assert first.admitted_s is not None and second.admitted_s is None
    stamp = first.admitted_s
    e.run_until_done()
    assert first.admitted_s == stamp  # stamped once
    assert second.admitted_s >= first.finished_s > first.admitted_s
    after = obs.get_registry().snapshot()["histograms"][
        "serve_queue_wait_seconds"]
    assert after["count"] - before["count"] == 2
    waited = second.admitted_s - arrival
    assert after["sum"] - before["sum"] == pytest.approx(
        (first.admitted_s - arrival) + waited)
    by_req = {r["req"]: r for r in read(events)
              if r.get("event") == "serve-request"}
    assert by_req[second.request.req_id]["queue_wait_s"] == round(waited, 6)
    assert by_req[second.request.req_id]["queue_wait_s"] > \
        by_req[first.request.req_id]["queue_wait_s"] >= 0
    assert by_req[second.request.req_id]["ttft_s"] >= \
        by_req[second.request.req_id]["queue_wait_s"]
    # obs trace: the stamp, not the inference from the first compute span
    data = load_run_dir(events.parent)
    per_trace = analyze(data)["per_trace"]
    assert per_trace["aaaa000000000002"]["phases"]["queue_wait"] == \
        by_req[second.request.req_id]["queue_wait_s"]
    # a run dir from before the field: the inference still answers
    stripped = events.parent / "old" / "events.jsonl"
    stripped.parent.mkdir()
    with open(stripped, "w") as f:
        for rec in read(events):
            rec.pop("queue_wait_s", None)
            f.write(json.dumps(rec) + "\n")
    old = analyze(load_run_dir(stripped.parent))["per_trace"]
    inferred = old["aaaa000000000002"]["phases"]["queue_wait"]
    assert inferred > 0 and inferred != \
        by_req[second.request.req_id]["queue_wait_s"]
