"""The engine's tick by phase (ISSUE 25): every phase a span at the place
of the work, ``serve.tick`` opened by ``tick()`` itself and so exactly
once a tick whoever drives the engine, all of them on the profiler's
host plane while a capture is on; the scheduler's ``admitted_s``
stamp behind ``queue_wait_s``; and what ISSUE 42 records where the work
happens: the eviction's cost on ``serve.schedule``, one
``serve.first_token`` row a request, ``tick_phases_ms``."""

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
# what serve.schedule says of a tick that evicted, and of no other
EVICT_FIELDS = {"evict_ms", "evicted"}


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
    # the capture's list: one tick() call after the other, each phase once
    # a program, in the order the spans close (ISSUE 60): a call issues the
    # program of ITS step, then reads the program of the step before it,
    # whose wait, emit and retire carry that step. Only the first call has
    # nothing to read and only the last nothing to issue.
    # (a request's serve.first_token row is no phase of a tick: below)
    spans = [s for s in rec.spans if s[0] != "serve.first_token"]
    assert len(rec.spans) - len(spans) == 3
    from tests.core.test_serve.test_tick_telemetry import (
        READ as read_phases, closing_order)

    expected = closing_order(first_tick, ticks)
    assert [(s[0], s[3]["step"]) for s in spans] == expected
    assert set(PHASES) == {name for name, _ in expected}
    row = {(s[0], s[3]["step"]): s for s in spans}
    for i in range(ticks):
        step = first_tick + i
        tick = row["serve.tick", step]
        issued = i < ticks - 1
        assert (tick[3]["decodes"] + tick[3]["chunks"] > 0) == issued
        assert tick[3]["overlapped"] == (0 < i < ticks - 1)
        children = [row["serve.schedule", step]]
        mixed = []
        if issued:
            children.append(row["serve.mixed", step])
            mixed = [row[n, step] for n in ("serve.mixed.build",
                                            "serve.mixed.dispatch")]
            assert all(c[3]["parent"] == "serve.mixed" for c in mixed)
            assert row["serve.mixed", step][2] >= sum(c[2] for c in mixed)
        if i:  # the read of the program before, inside THIS call
            children += [row[n, step - 1] for n in read_phases]
        if not issued:
            children.append(row["serve.retire", step])
        assert all(c[3]["parent"] == "serve.tick" for c in children)
        assert tick[2] >= sum(c[2] for c in children)
        # in time too: each child inside its tick() call, the program
        # issued before the one before it is waited for
        assert all(tick[1] <= c[1] and c[1] + c[2] <= tick[1] + tick[2]
                   for c in children + mixed)
        if i and issued:
            wait = row["serve.mixed.wait", step - 1]
            assert row["serve.mixed", step][1] + row[
                "serve.mixed", step][2] <= wait[1]
    # the same spans on the host plane of the .xplane.pb, by name
    names = {}
    for plane in ProfileData.from_file(str(rec.trace_file())).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                for event in line.events:
                    names[event.name] = names.get(event.name, 0) + 1
    for phase in PHASES:
        assert names.get(phase) == sum(n == phase for n, _ in expected), phase
    # and the counters the capture differenced are the traced ticks'
    assert rec.counters["serve_prefill_tokens_total"] == sum(
        len(p) for p in PROMPTS[:3])
    assert rec.counters["serve_tokens_generated_total"] == 12


def test_warmup_ticks_emit_no_phase_span(toy_inference, events):
    e = make_engine(toy_inference)
    e.warmup_mode = True
    e.submit([1, 2], 2)
    e.run_until_done()
    # the engine's set-up is spanned, warm-up or not (once an engine, never
    # in a tick: the analyzer's tick attribution names its spans and does
    # not read these); the tick's phases are silent
    spans = [r["span"] for r in read(events)
             if r.get("event") == "span"] if events.exists() else []
    assert sorted(spans) == ["serve.init", "serve.lower"]


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


def test_eviction_is_on_the_schedule_span_only_in_the_ticks_that_evict(
        toy_inference):
    """A pool of 7 blocks, prompts of two whole blocks each: the first
    requests leave their prompt blocks in the prefix cache, a later one
    finds the free list short and the scheduler's one call of
    ``PrefixCache.evict`` runs. That tick's ``serve.schedule`` row carries
    ``evict_ms`` and ``evicted``; the others carry neither, and paid no
    clock read for it. The second prompt extends the first, so the first's
    last block is pushed as a leaf, matched again and given a child: the
    stale entry that ``evict`` has to skip, a lifetime total of
    ``stats_snapshot()`` and no field of the span (ISSUE 57)."""
    e = make_engine(toy_inference, num_slots=1, num_blocks=8,
                    max_blocks_per_seq=4)
    since = time.monotonic_ns()
    prompts = [[10 * i + j for j in range(8)] for i in range(1, 5)]
    prompts.insert(1, prompts[0] + [7, 7, 7, 7, 7])
    for p in prompts:
        e.submit(p, 3)
        e.run_until_done()
    sched = e.scheduler
    assert sched.evicted_blocks > 0 and sched.evict_seconds > 0
    assert sched.prefix_cache.stale_skipped > 0
    rows = obs.recorded_spans(since_ns=since, name="serve.schedule")
    assert len(rows) == e.tick_index
    evicting = [r for r in rows if "evict_ms" in r.fields]
    assert evicting and len(evicting) < len(rows)
    assert all(EVICT_FIELDS <= set(r.fields) for r in evicting)
    assert all(EVICT_FIELDS.isdisjoint(r.fields)
               for r in rows if r not in evicting)
    assert sum(r.fields["evicted"] for r in evicting) == sched.evicted_blocks
    assert not any("evict_stale" in r.fields for r in rows)
    assert sum(r.fields["evict_ms"] for r in evicting) == pytest.approx(
        1e3 * sched.evict_seconds, abs=1e-5 * len(evicting))
    # the eviction lies inside the span that reports it
    assert all(0 < r.fields["evict_ms"] <= r.duration_ns / 1e6 for r in evicting)
    assert e.stats_snapshot()["evict_stale"] == sched.prefix_cache.stale_skipped


def test_no_pressure_no_eviction_no_field_no_clock_read(toy_inference, monkeypatch):
    from scaling_tpu.serve import scheduler as scheduler_module

    e = make_engine(toy_inference)
    calls = []
    real = scheduler_module.PrefixCache.evict
    monkeypatch.setattr(scheduler_module.PrefixCache, "evict",
                        lambda self, n: calls.append(n) or real(self, n))
    since = time.monotonic_ns()
    for p in PROMPTS:
        e.submit(p, 3)
    e.run_until_done()
    assert calls == [] and e.scheduler.evict_seconds == 0.0
    rows = obs.recorded_spans(since_ns=since, name="serve.schedule")
    assert len(rows) == e.tick_index
    assert all(EVICT_FIELDS.isdisjoint(r.fields) for r in rows)
    assert e.stats_snapshot()["evict_stale"] == 0


def test_one_first_token_row_a_request_none_in_warm_up(toy_inference):
    """One slot, three requests: each leaves one ``serve.first_token`` row
    when its first token is stamped, from its arrival, as long as its time
    to first token, with the queue wait that ``serve_queue_wait_seconds``
    observed. Warm-up traffic leaves none."""
    e = make_engine(toy_inference, num_slots=1)
    since = time.monotonic_ns()
    e.warmup_mode = True
    e.submit([1, 2], 2)
    e.run_until_done()
    e.warmup_mode = False
    assert obs.recorded_spans(since_ns=since, name="serve.first_token") == []

    def hist(name):
        return obs.get_registry().snapshot()["histograms"].get(
            name, {"count": 0, "sum": 0.0})

    before = hist("serve_queue_wait_seconds")
    arrival = time.monotonic()
    seqs = [e.submit(p, 3, arrival_s=arrival) for p in PROMPTS[:3]]
    e.run_until_done()
    rows = obs.recorded_spans(since_ns=since, name="serve.first_token")
    assert [r.fields["req"] for r in rows] == [s.request.req_id for s in seqs]
    for r, seq in zip(rows, seqs):
        assert r.start_ns == round(arrival * 1e9) and r.step is None
        assert r.duration_ns == round((seq.first_token_s - arrival) * 1e9)
        assert r.fields == {
            "queue_s": seq.admitted_s - arrival,
            "prompt_tokens": len(seq.request.prompt),
            "req": seq.request.req_id}
        assert 0 <= r.fields["queue_s"] <= r.duration_ns / 1e9
    after = hist("serve_queue_wait_seconds")
    assert after["count"] - before["count"] == 3
    assert sum(r.fields["queue_s"] for r in rows) == pytest.approx(
        after["sum"] - before["sum"])
    # the later ones waited for the slot: their time to first token is queue
    assert rows[2].fields["queue_s"] > rows[1].fields["queue_s"] > \
        rows[0].fields["queue_s"]


def test_tick_phases_ms_is_the_median_of_each_phase_over_the_last_ticks(
        toy_inference, monkeypatch):
    from statistics import median

    from scaling_tpu.serve import engine as engine_module

    e = make_engine(toy_inference)
    assert e.stats_snapshot()["tick_phases_ms"] == {}  # no tick yet
    since = time.monotonic_ns()
    for p in PROMPTS:
        e.submit(p, 4)
    e.run_until_done()
    phases = e.stats_snapshot()["tick_phases_ms"]
    # every span of a tick, and the parts of the account that no span holds:
    # a tick minus its leaves and tick to tick (ISSUE 57), and the share of
    # the ticks that issued their program ahead of a read (ISSUE 60)
    assert sorted(phases) == sorted(
        PHASES + ("between", "unspanned", "overlapped_pct"))
    json.dumps(phases)  # the replica's stats RPC carries it
    rows = obs.recorded_spans(since_ns=since)
    ticks = [r for r in rows if r.name == "serve.tick"]
    assert len(ticks) == e.tick_index
    for name in PHASES:
        mine = [r.duration_ns for r in rows if r.name == name]
        # the last call only reads: one program fewer than tick() calls
        assert len(mine) == e.tick_index - (
            name not in ("serve.tick", "serve.schedule", "serve.retire"))
        assert phases[name] == pytest.approx(median(mine) / 1e6, abs=1e-6)
    assert phases["overlapped_pct"] == pytest.approx(
        100.0 * (len(ticks) - 2) / len(ticks))
    leaves = set(PHASES) - {"serve.tick", "serve.mixed"}
    assert leaves == engine_module.LEAF_PHASES
    # a call's leaves by TIME: its own schedule, build and dispatch, and the
    # wait, emit and retire of the step before it
    unspanned = [t.duration_ns - sum(
        r.duration_ns for r in rows if r.name in leaves
        and t.start_ns <= r.start_ns
        and r.start_ns + r.duration_ns <= t.start_ns + t.duration_ns)
        for t in ticks]
    assert all(u >= 0 for u in unspanned)
    assert phases["unspanned"] == pytest.approx(median(unspanned) / 1e6, abs=1e-6)
    # run_until_done calls tick() back to back, and the engine has work
    # from each tick to the next: a program is in flight until the last
    # call, which reads it and retires the last request
    retired = {r.step: r.fields["finished"] for r in rows if r.name == "serve.retire"}
    assert sum(retired.values()) == len(PROMPTS)
    assert retired[ticks[-2].step] > 0 and retired[ticks[-1].step] == 0
    between = [b.start_ns - a.start_ns - a.duration_ns
               for a, b in zip(ticks, ticks[1:])
               if a.fields["decodes"] + a.fields["chunks"]
               and b.fields["decodes"] + b.fields["chunks"]]
    assert len(between) == len(ticks) - 2 and min(between) >= 0
    assert phases["between"] == pytest.approx(median(between) / 1e6, abs=1e-6)
    assert phases["between"] + phases["unspanned"] < phases["serve.tick"]
    # the wait lies outside serve.mixed now: the program is issued, and
    # the call goes on to read the one before it
    assert phases["serve.tick"] >= phases["serve.mixed"] >= phases["serve.mixed.dispatch"]
    assert phases["serve.tick"] >= phases["serve.mixed.wait"]
    # over the LAST ticks only: with room for 3, the first ticks fall out
    monkeypatch.setattr(engine_module, "TICK_PHASES_TICKS", 3)
    last = [r.duration_ns for r in rows if r.name == "serve.tick"][-3:]
    assert e.stats_snapshot()["tick_phases_ms"]["serve.tick"] == pytest.approx(
        median(last) / 1e6, abs=1e-6)
    # another engine of the process (an in-process fleet's other replica)
    # reads its own ticks, not these
    other = make_engine(toy_inference, replica_id=7)
    assert other.stats_snapshot()["tick_phases_ms"] == {}
