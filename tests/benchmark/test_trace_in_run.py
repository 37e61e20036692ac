"""``--trace 2`` (measure first, trace afterwards in the same process) and
the per-layer metrics that read the program's own spans and counters."""

import json
import types
from pathlib import Path

import pytest

from benchmark import cells, serve_kind, traffic_gen

DATA = Path(__file__).parent / "data"
TOY = DATA / "toy"
CHAT = cells.load_json(cells.ROOT / "traffic" / "chat-steady.json")
NEW_METRICS = ("tick_host_ms_p50", "sched_ms_p50", "tick_dispatch_ms_p50",
               "prefill_token_pct", "idle_in_spans_pct")


@pytest.fixture()
def no_capture(monkeypatch):
    from scaling_tpu.obs import capture

    monkeypatch.setattr(capture, "_last", None)


@pytest.mark.parametrize("workload, end_to_end, per_layer", [
    ("toy-train", {"setup_s", "train_tokens_per_s"}, {"toy_steps", "step_ms_p50"}),
    ("toy-serve", {"setup_s", "serve_tokens_per_s", "itl_p95_ms"}, {"tick_ms_p50"}),
])
def test_trace_2_walks_the_whole_path_on_the_cpu(run, grown, capsys, monkeypatch,
                                                 workload, end_to_end, per_layer):
    """The window runs with no capture on, its numbers are taken, the
    profiler is started and stopped once for nothing, then a capture holds
    the traced part; the line carries both kinds of metric and the trace is
    deleted once read."""
    from scaling_tpu import obs
    from scaling_tpu.obs import capture

    log = []
    real_start, real_stop = capture.start_capture, capture.stop_capture

    def start(out_dir, registry=None):
        log.append(("start", len(log)))
        return real_start(out_dir, registry)

    def stop():
        rec = real_stop()
        log.append(("stop", [s[0] for s in rec.spans]))
        return rec

    monkeypatch.setattr(obs, "start_capture", start)
    monkeypatch.setattr(obs, "stop_capture", stop)
    if workload == "toy-serve":
        real_numbers = serve_kind.window_numbers

        def numbers(*a):
            log.append(("window_numbers", obs.capturing()))
            return real_numbers(*a)

        monkeypatch.setattr(serve_kind, "window_numbers", numbers)
    result = run.main(["--workload", workload, "--seed", "3000000019",
                       "--seconds", "1.5", "--trace", "2", "--rehearse",
                       "--root", str(grown),
                       "--benchmark-json", str(TOY / "BENCHMARK.json")])
    out = capsys.readouterr().out.strip().splitlines()
    assert json.loads(out[-1]) == {"rehearsal": True, "workload": workload}
    assert result["correct"] and result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == end_to_end | per_layer
    assert all(m["value"] > 0 for m in result["metrics"].values())
    # one start/stop thrown away, then the traced part; and for serve the
    # window's numbers were taken before either, with no capture on
    kinds = [k for k, _ in log]
    assert kinds[-4:] == ["start", "stop", "start", "stop"]
    assert kinds.count("start") == 2
    if workload == "toy-serve":
        assert log[0] == ("window_numbers", False)
        assert log[2][1] == []  # the throwaway capture held no span
        assert "serve.tick" in log[4][1]  # the traced part's ticks
    assert not (grown.parent / ".bench_trace" / workload).exists()


@pytest.mark.parametrize("seed", [0, 1, 7, 24, 1234567, 2147483659,
                                  3000000019, 4294967291])
def test_traced_arrivals_change_nothing_before_them(seed):
    """The arrivals of the traced part are a further phase with a generator
    of its own and their token ids are drawn last: the replayed trace of the
    window is the same request for request and token for token."""
    plain = traffic_gen.generate(CHAT, seed, 51, 32768)
    more = traffic_gen.generate(CHAT, seed, 51, 32768, traced_seconds=5.0)
    assert more[:len(plain)] == plain
    assert not any(r.traced for r in plain)
    traced = more[len(plain):]
    assert traced and all(r.traced and not r.counted for r in traced)
    assert traced[0].due_s == 0.0 and all(0 <= r.due_s < 5.0 for r in traced)
    assert [r.due_s for r in traced] == sorted(r.due_s for r in traced)
    assert len(plain) == 22 + 138
    for r in traced:
        assert CHAT["prompt"]["min"] <= len(r.prompt) <= CHAT["prompt"]["max"]
        assert len(r.prompt) + r.output_len <= CHAT["max_total"]


def test_numbers_of_the_window_are_fixed_when_they_are_taken():
    """Under --trace 2 a sequence that was still decoding as the window
    closed decodes on through the traced part: what was taken of it (its
    tokens for the check of outputs, its gaps, that it was cut) stays."""
    request = traffic_gen.Request(due_s=1.0, prompt=[5, 6, 7], output_len=8)
    warm = traffic_gen.Request(due_s=-1.0, prompt=[5], output_len=2)
    live = types.SimpleNamespace(
        first_token_s=12.0, finished_s=None, finish_status="completed",
        generated=[3, 4, 5], token_stamps=[12.0, 12.1, 12.3])
    old = types.SimpleNamespace(
        first_token_s=9.5, finished_s=9.6, finish_status="completed",
        generated=[1, 2], token_stamps=[9.5, 9.6])
    submitted = [(warm, old), (request, live)]
    taken = serve_kind.window_numbers(submitted, 10.0, 51.0)
    tokens, done, failed, finished, cut, ttft, itl, unserved = taken
    assert (tokens, failed, finished, cut, unserved) == (3, 0, 0, 1, 0)
    assert done == [(request, [3, 4, 5])]
    assert ttft == [pytest.approx(1.0)] and itl == [
        pytest.approx(0.1), pytest.approx(0.2)]
    # the traced part: two more tokens, and the sequence finishes
    live.generated += [6, 7]
    live.token_stamps += [70.0, 70.1]
    live.finished_s = 70.1
    assert (tokens, done, failed, finished, cut, ttft, itl, unserved) == taken
    assert done[0][1] == [3, 4, 5] and len(itl) == 2
    again = serve_kind.window_numbers(submitted, 10.0, 51.0)
    assert again[1] != done  # taken later, it would have read otherwise


def test_every_new_metric_reads_nothing_without_a_capture(no_capture):
    bench = cells.load_json(cells.REPO / "BENCHMARK.json")
    assert bench["trace_in_run"] is True
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW_METRICS:
        assert by_name[name]["workloads"] == ["serve-mistral7b-chat-steady"]
        spec = cells.load_json(cells.ROOT / "metrics" / f"{name}.json")
        assert spec["reader"] == f"program_spans:{name}"
        assert cells.load_reader(name)({}) is None


def test_program_spans_on_a_recorded_capture(monkeypatch, tmp_path, capsys):
    """Three ticks as a capture holds them, two of them as the trace does,
    in whole milliseconds: every value below is computed by hand in the
    fixture's ``expected``."""
    from scaling_tpu.obs import capture

    fixture = cells.load_json(DATA / "capture_three_ticks.json")
    want = fixture["expected"]
    spans = [tuple(row) for row in fixture["spans"]]
    trace_dir = tmp_path / "trace"
    trace_dir.mkdir()
    (trace_dir / "recorded.xplane.pb").write_bytes(b"")
    monkeypatch.setattr(capture, "_last", capture.Capture(
        trace_dir=str(trace_dir), seconds=0.302, spans=spans,
        counters=fixture["counters"]))
    reader = cells.load_reader
    assert reader("tick_host_ms_p50")({}) == pytest.approx(want["tick_host_ms_p50"])
    assert reader("sched_ms_p50")({}) == pytest.approx(want["sched_ms_p50"])
    assert reader("tick_dispatch_ms_p50")({}) == pytest.approx(
        want["tick_dispatch_ms_p50"])
    assert reader("prefill_token_pct")({}) == pytest.approx(want["prefill_token_pct"])
    # the trace's part: the recorded events stand in for the .xplane.pb
    idle_reader = reader("idle_in_spans_pct")
    monkeypatch.setattr(idle_reader.__globals__["trace_reduce"], "load_events",
                        lambda path: fixture["events"])
    idle_ns, inside = idle_reader.__globals__["idle_by_span"](fixture["events"])
    assert idle_ns / 1e6 == pytest.approx(want["idle_ms"])
    assert {k: v / 1e6 for k, v in inside.items()} == pytest.approx(
        want["idle_inside_ms"])
    assert idle_reader({}) == pytest.approx(want["idle_in_spans_pct"])
    # and the phase table, one line a phase, on stderr
    table = [line for line in capsys.readouterr().err.splitlines()
             if line.startswith("[spans]")]
    assert "host adds 10.000 ms a tick" in table[0]
    assert [line.split()[1] for line in table[1:]] == [
        "serve.mixed.dispatch", "serve.mixed.wait", "serve.schedule", "serve.tick"]
