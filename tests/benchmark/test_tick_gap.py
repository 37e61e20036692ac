"""What PR 57 brings as files: ``readers/tick_gap.py`` and ten
``metrics/*.json``, the account of the gap between two of the chip's
programs. The seven window readers on a synthetic recorder and the three
traced ones on a synthetic ``load_events`` structure, every value by hand;
nothing without a window, a capture, or a device line to read; the ten
entries in ``BENCHMARK.json`` by membership; and the files walked through
``--trace 2`` on the CPU in a copy of ``benchmark/``."""

import json
import re
from pathlib import Path

import pytest

from benchmark import cells
from benchmark.readers import program_spans, window_spans

TOY = Path(__file__).parent / "data" / "toy"
SERVE_CELLS = {
    "serve-mistral7b-chat-steady", "serve-mistral7b-chat-burst",
    "serve-olmoe-chat-burst", "serve-ouro2.6b-reason-burst",
    "serve-nemotron3nano-reason-burst", "serve-lfm2-24b-reason-burst",
    "serve-falconh1-34b-reason-burst", "serve-kimik2-longdoc-burst"}
# metric -> (reader's function, unit, layer)
WINDOW = {
    "emit_ms_mean.window": ("emit_ms_mean", "ms", "engine tick"),
    "emit_us_per_row.window": ("emit_us_per_row", "us", "engine tick"),
    "retire_ms_mean.window": ("retire_ms_mean", "ms", "engine tick"),
    "build_ms_mean.window": ("build_ms_mean", "ms", "engine tick"),
    "dispatch_ms_mean.window": ("dispatch_ms_mean", "ms", "engine tick"),
    "unspanned_ms_mean.window": ("unspanned_ms_mean", "ms", "engine tick"),
    "between_ticks_ms_mean.window": ("between_ticks_ms_mean", "ms", "load generator"),
}
TRACED = {
    "gap_ms_p50.traced": ("gap_ms_p50", "ms", "engine tick"),
    "gap_wake_ms_p50.traced": ("gap_wake_ms_p50", "ms", "engine tick"),
    "gap_launch_ms_p50.traced": ("gap_launch_ms_p50", "ms", "engine tick"),
}
METRICS = {**WINDOW, **TRACED}
MS = 1_000_000  # ns


# ------------------------------------------------ a synthetic recorder
def row(name, start_ms, dur_ms, step=None, parent=None, **fields):
    from scaling_tpu.obs import Row

    return Row(name, round(start_ms * MS), round(dur_ms * MS), step, parent, fields)


def tick_rows(step, start, sched, build, dispatch, wait, emit, retire, slack,
              decodes, chunks, rows=None, finished=None):
    """One tick as the engine closes it, children first; ``slack`` is what
    the tick spends in no leaf. ``rows`` / ``finished`` None: a program from
    before PR 57, whose spans do not carry them."""
    at = start + slack / 2
    out = [row("serve.schedule", at, sched, step, "serve.tick")]
    mixed = at + sched
    parts = []
    for name, dur in (("serve.mixed.build", build),
                      ("serve.mixed.dispatch", dispatch),
                      ("serve.mixed.wait", wait)):
        parts.append(row(name, mixed + sum(r.duration_ns for r in parts) / MS,
                         dur, step, "serve.mixed"))
    out += parts + [row("serve.mixed", mixed, build + dispatch + wait, step,
                        "serve.tick", width=128, tokens=9)]
    emits = mixed + build + dispatch + wait
    said = {} if rows is None else {"rows": rows, "tokens": rows}
    out.append(row("serve.emit", emits, emit, step, "serve.tick", **said))
    said = {} if finished is None else {"finished": finished}
    out.append(row("serve.retire", emits + emit, retire, step, "serve.tick", **said))
    total = sched + build + dispatch + wait + emit + retire + slack
    return out + [row("serve.tick", start, total, step, None,
                      decodes=decodes, chunks=chunks)]


def marker(at_ms, edge):
    return row("obs.capture", at_ms, 0.0, trace_dir="/t", edge=edge)


def window_rows(said=True):
    """Four ticks under steps 10-13. Tick 11 retires all four of its rows
    and the engine then waits 71.8 ms for an arrival: no pair of the loop."""
    def maybe(value):
        return value if said else None

    return (
        tick_rows(10, 1000.0, .2, .1, .5, 12.0, .4, .1, .2, 3, 1,
                  maybe(4), maybe(0))            # 13.5 ms, ends 1013.5
        + tick_rows(11, 1013.8, .3, .2, .6, 12.5, .6, .1, .1, 4, 0,
                    maybe(4), maybe(4))          # 14.4 ms, ends 1028.2
        + tick_rows(12, 1100.0, .1, .1, .4, 13.0, .2, .2, .3, 1, 1,
                    maybe(2), maybe(0))          # 14.3 ms, ends 1114.3
        + tick_rows(13, 1114.5, .4, .2, .5, 13.0, .8, .2, .2, 6, 0,
                    maybe(6), maybe(1)))         # 15.3 ms


TICK_S = [0.01353, 0.01442, 0.01433, 0.01532]  # the harness's two clock reads
WARM_UP = tick_rows(9, 900.0, 5.0, 1.0, 3.0, 25.0, 9.0, 2.0, 1.0, 1, 1, 9, 9)
AFTER = ([marker(1200, "start"), marker(1201, "stop")]
         + tick_rows(14, 1210.0, 9.0, 1.0, 3.0, 20.0, 7.0, 2.0, 1.0, 1, 1, 9, 0)
         + [marker(1300, "start")]
         + tick_rows(15, 1310.0, 9.0, 1.0, 3.0, 20.0, 7.0, 2.0, 1.0, 1, 1, 9, 0)
         + [marker(1400, "stop")])
RECORDER = WARM_UP + window_rows() + AFTER
WINDOW_BY_HAND = {
    "emit_ms_mean.window": 0.5,            # (.4 + .6 + .2 + .8) / 4 ticks
    "emit_us_per_row.window": 125.0,       # 2,000 us over 4 + 4 + 2 + 6 rows
    "retire_ms_mean.window": 0.15,         # (.1 + .1 + .2 + .2) / 4
    "build_ms_mean.window": 0.15,          # (.1 + .2 + .1 + .2) / 4
    "dispatch_ms_mean.window": 0.5,        # (.5 + .6 + .4 + .5) / 4
    "unspanned_ms_mean.window": 0.2,       # (.2 + .1 + .3 + .2) / 4
    "between_ticks_ms_mean.window": 0.25,  # .3 and .2; 71.8 is no pair
}


def read_window(monkeypatch, name, rows, tick_s=TICK_S):
    monkeypatch.setattr(window_spans, "recorded_spans", lambda: rows)
    return cells.load_reader(name)({"host": {"tick_s": tick_s}})


@pytest.mark.parametrize("name", sorted(WINDOW))
def test_each_window_reader_on_a_synthetic_recorder_by_hand(name, monkeypatch):
    """Warm-up before the window, the throwaway capture's markers, a lead
    tick and the traced slice after it: only the window's four ticks count."""
    assert read_window(monkeypatch, name, RECORDER) == pytest.approx(
        WINDOW_BY_HAND[name])


def test_a_program_whose_spans_say_neither_rows_nor_finished(monkeypatch):
    """The parent of PR 57: the spans are there, their new fields are not.
    The row metric reads nothing, the pair rule falls back to "the later
    tick ran a program", the five plain means read as before."""
    rows = window_rows(said=False) + AFTER
    assert read_window(monkeypatch, "emit_us_per_row.window", rows) is None
    assert read_window(monkeypatch, "between_ticks_ms_mean.window", rows) == \
        pytest.approx((0.3 + 71.8 + 0.2) / 3)
    for name in ("emit_ms_mean.window", "retire_ms_mean.window",
                 "build_ms_mean.window", "dispatch_ms_mean.window",
                 "unspanned_ms_mean.window"):
        assert read_window(monkeypatch, name, rows) == pytest.approx(
            WINDOW_BY_HAND[name])


def test_ticks_that_ran_no_program_are_ticks_of_the_mean_and_no_pair(monkeypatch):
    """A tick with nothing to run closes schedule and retire alone: it
    counts among the window's ticks, and no pair ends in it."""
    empty = [row("serve.schedule", 1030.0, .1, 14, "serve.tick"),
             row("serve.retire", 1030.1, .1, 14, "serve.tick", finished=0),
             row("serve.tick", 1030.0, .3, 14, None, decodes=0, chunks=0)]
    rows = window_rows()[:16] + empty + [marker(1200, "start"), marker(1201, "stop")]
    tick_s = TICK_S[:2] + [0.00031]
    assert read_window(monkeypatch, "emit_ms_mean.window", rows, tick_s) == \
        pytest.approx((.4 + .6) / 3)
    assert read_window(monkeypatch, "unspanned_ms_mean.window", rows, tick_s) == \
        pytest.approx((.2 + .1 + .1) / 3)
    assert read_window(monkeypatch, "between_ticks_ms_mean.window", rows, tick_s) == \
        pytest.approx(0.3)


NO_WINDOW = {
    "no marker (a run that took no capture)": (WARM_UP + window_rows(), TICK_S),
    "durations do not line up": (RECORDER, [TICK_S[1], TICK_S[0]] + TICK_S[2:]),
    "an empty recorder": ([], TICK_S),
    "a harness that ran no tick": (RECORDER, []),
}


@pytest.mark.parametrize("case", sorted(NO_WINDOW))
def test_without_a_window_every_window_reader_returns_nothing(case, monkeypatch):
    rows, tick_s = NO_WINDOW[case]
    for name in WINDOW:
        assert read_window(monkeypatch, name, rows, tick_s) is None, name


def test_the_window_is_cut_once_a_run_and_shared_with_window_spans(monkeypatch):
    """``tick_gap`` imports ``window_spans.window``: the ten readers and
    PR 42's seven read ONE cut, kept in ``ctx``."""
    calls = []
    monkeypatch.setattr(window_spans, "recorded_spans",
                        lambda: calls.append(1) or RECORDER)
    ctx = {"host": {"tick_s": TICK_S}}
    for name in list(WINDOW) + ["sched_ms_mean.window", "tick_host_ms_p50.window"]:
        assert cells.load_reader(name)(ctx) is not None
    assert len(calls) == 1


# --------------------------- a synthetic trace, on the device's clock (ms)
def ev(name, start_ms, end_ms, *step):
    return [name, start_ms * MS, (end_ms - start_ms) * MS, *step]


# three ticks; tick 21's wait returns 0.1 ms BEFORE its program has ended
TICKS = {
    20: {"serve.tick": (100.0, 114.5), "serve.schedule": (100.05, 100.25),
         "serve.mixed.build": (100.30, 100.40),
         "serve.mixed.dispatch": (100.45, 101.05),
         "serve.mixed.wait": (101.10, 113.70), "serve.emit": (113.75, 114.15),
         "serve.retire": (114.20, 114.40), "program": (100.95, 113.00)},
    21: {"serve.tick": (114.8, 129.0), "serve.schedule": (114.85, 115.15),
         "serve.mixed.build": (115.20, 115.40),
         "serve.mixed.dispatch": (115.45, 116.15),
         "serve.mixed.wait": (116.20, 127.90), "serve.emit": (127.95, 128.45),
         "serve.retire": (128.50, 128.60), "program": (116.05, 128.00)},
    22: {"serve.tick": (129.2, 143.0), "serve.schedule": (129.25, 129.35),
         "serve.mixed.build": (129.40, 129.50),
         "serve.mixed.dispatch": (129.55, 130.05),
         "serve.mixed.wait": (130.10, 142.50), "serve.emit": (142.55, 142.75),
         "serve.retire": (142.80, 142.90), "program": (129.95, 142.00)},
}
TRACED_BY_HAND = {
    "gap_ms_p50.traced": 2.5,         # 116.05 - 113.00 = 3.05, 129.95 - 128.00 = 1.95
    "gap_wake_ms_p50.traced": 0.35,   # 113.70 - 113.00 = 0.70, and 0: returned early
    "gap_launch_ms_p50.traced": 0.5,  # 116.05 - 115.45 = 0.60, 129.95 - 129.55 = 0.40
}


def trace_events(steps=True, modules=True, ops=True):
    host = [ev(name, *span, *([step] if steps else []))
            for step, tick in TICKS.items()
            for name, span in tick.items() if name != "program"]
    host.append(ev("serve.cow", 100.10, 100.20, *([20] if steps else [])))
    mine = [ev(f"jit_mixed_128({7 + step})", *TICKS[step]["program"]) for step in TICKS]
    # a fork's eager scatter between two programs is no program of a tick
    other = [ev("jit_scatter(99)", 114.0, 114.01)]
    first = {"modules": mine + other if modules else [],
             "ops": [ev("%fusion.1 = bf16[8] fusion()", start, (start + end) / 2)
                     for start, end in (t["program"] for t in TICKS.values())]
             + [ev("%copy.2 = bf16[8] copy()", (start + end) / 2, end)
                for start, end in (t["program"] for t in TICKS.values())]
             if ops else []}
    # a second chip, a little late: never read
    second = {"modules": [ev(f"jit_mixed_128({step})", 1 + TICKS[step]["program"][0],
                             2 + TICKS[step]["program"][1]) for step in TICKS],
              "ops": []}
    return {"devices": {"1": second, "0": first}, "host": host}


class FakeCapture:
    def trace_file(self):
        return Path("/nowhere/t.xplane.pb")


def read_traced(monkeypatch, name, events, capture=FakeCapture()):
    reader = cells.load_reader(name)
    monkeypatch.setattr(program_spans, "last_capture", lambda: capture)
    monkeypatch.setitem(reader.__globals__, "load_events", lambda path: events)
    return reader({})


CASES = {
    "joined by step": trace_events(),
    "no step on the annotations: joined by order": trace_events(steps=False),
    "no modules line: first to last operation across a dispatch":
        trace_events(modules=False),
}


@pytest.mark.parametrize("case", sorted(CASES))
@pytest.mark.parametrize("name", sorted(TRACED))
def test_each_traced_reader_on_a_synthetic_trace_by_hand(name, case, monkeypatch,
                                                         capsys):
    assert read_traced(monkeypatch, name, CASES[case]) == pytest.approx(
        TRACED_BY_HAND[name])
    # the table on stderr: every part of the first gap, and what is left
    err = capsys.readouterr().err
    assert "2 gaps between two mixed programs, 2 with every part" in err
    assert "1 wait(s) returned before the program's last operation ended" in err
    said = {m.group(1): float(m.group(2)) for m in (
        re.match(r"\[gap\] (\S+)\s+median\s+([\d.]+) ms", line)
        for line in err.splitlines()) if m}
    assert said["wake"] == pytest.approx(0.35) and said["launch"] == pytest.approx(0.5)
    assert said["serve.emit"] == pytest.approx(0.45)        # .4 and .5
    assert said["serve.retire"] == pytest.approx(0.15)      # .2 and .1
    assert said["between"] == pytest.approx(0.25)           # .3 and .2
    assert said["serve.schedule"] == pytest.approx(0.2)     # .3 and .1
    assert said["serve.mixed.build"] == pytest.approx(0.15)  # .2 and .1


def test_the_parts_and_what_is_left_make_the_gap(monkeypatch):
    reader = cells.load_reader("gap_ms_p50.traced")
    found = reader.__globals__["gaps"](trace_events())
    parts = reader.__globals__["PARTS"]
    assert [g["early"] for g in found] == [False, True]
    first = found[0]
    assert first["gap"] / MS == pytest.approx(3.05)
    # wake .7 emit .4 retire .2 between .3 schedule .3 build .2 launch .6
    assert sum(first[p] for p in parts) / MS == pytest.approx(2.7)


NOTHING = {
    "no capture (--trace 0)": (trace_events(), None),
    "neither a modules line nor an operation": (
        trace_events(modules=False, ops=False), FakeCapture()),
    "no device plane (the CPU)": ({"devices": {}, "host": trace_events()["host"]},
                                  FakeCapture()),
    "one program: no gap": (
        {"devices": {"0": {"modules": [ev("jit_mixed_128(1)", 1.0, 2.0)], "ops": []}},
         "host": []}, FakeCapture()),
}


@pytest.mark.parametrize("case", sorted(NOTHING))
def test_without_a_capture_or_a_device_line_the_traced_readers_return_nothing(
        case, monkeypatch):
    events, capture = NOTHING[case]
    for name in TRACED:
        assert read_traced(monkeypatch, name, events, capture) is None, name


def test_a_capture_without_a_trace_file_and_a_program_without_the_control(
        monkeypatch):
    class NoFile:
        def trace_file(self):
            return None

    for name in TRACED:
        assert read_traced(monkeypatch, name, trace_events(), NoFile()) is None
    from scaling_tpu import obs

    monkeypatch.undo()
    monkeypatch.delattr(obs, "last_capture")
    for name in TRACED:
        assert cells.load_reader(name)({}) is None


def test_the_trace_is_loaded_once_between_the_three(monkeypatch):
    loads = []
    ctx = {}
    monkeypatch.setattr(program_spans, "last_capture", FakeCapture)
    for name in TRACED:
        reader = cells.load_reader(name)
        monkeypatch.setitem(reader.__globals__, "load_events",
                            lambda path: loads.append(path) or trace_events())
        assert reader(ctx) == pytest.approx(TRACED_BY_HAND[name])
    assert len(loads) == 1


def test_load_events_reads_the_annotations_steps_from_a_real_trace(tmp_path):
    """On the CPU a trace has the host plane alone: the loader hands over
    the ``serve.*`` annotations with their steps and no device, and no gap
    is read from it."""
    from scaling_tpu import obs

    obs.start_capture(tmp_path / "trace")
    try:
        for step in (3, 4):
            with obs.span("serve.tick", step=step):
                with obs.span("serve.emit", step=step):
                    pass
        with obs.span("serve.admit"):
            pass
        with obs.span("ckpt.stage", step=9):
            pass
    finally:
        capture = obs.stop_capture()
    tick_gap = cells.load_reader("gap_ms_p50.traced").__globals__
    events = tick_gap["load_events"](capture.trace_file())
    assert events["devices"] == {}
    assert sorted((name, step) for name, _, _, step in events["host"]) == [
        ("serve.admit", None), ("serve.emit", 3), ("serve.emit", 4),
        ("serve.tick", 3), ("serve.tick", 4)]
    assert tick_gap["gaps"](events) is None


# ------------------------------------------------ the entries and the files
def test_the_ten_entries_are_in_the_benchmark_and_name_their_files():
    bench = cells.load_json(cells.REPO / "BENCHMARK.json")
    entries = {m["name"]: m for m in bench["per_layer"]}
    end_to_end = {m["name"]: m for m in bench["end_to_end"]}
    layers = {m["layer"] for m in bench["per_layer"] if m["name"] not in METRICS}
    for name, (function, unit, layer) in METRICS.items():
        assert entries[name] == {
            "name": name, "unit": unit, "better": "lower", "source": "program_span",
            "layer": layer, "moves": "serve_tokens_per_s",
            "workloads": entries[name]["workloads"]}
        assert set(entries[name]["workloads"]) == SERVE_CELLS
        assert SERVE_CELLS <= set(end_to_end["serve_tokens_per_s"]["workloads"])
        assert layer in layers  # a layer the benchmark already names
        spec = cells.load_json(cells.ROOT / "metrics" / f"{name}.json")
        assert spec["reader"] == f"tick_gap:{function}" and spec["unit"] == unit
        assert callable(cells.load_reader(name))


@pytest.fixture(scope="module")
def toy_bench(grown):
    """The toy benchmark with the ten entries appended for its serve cell,
    beside a copy of ``benchmark/`` that holds this PR's files."""
    bench = cells.load_json(TOY / "BENCHMARK.json")
    real = cells.load_json(cells.REPO / "BENCHMARK.json")
    bench["per_layer"] += [{**m, "workloads": ["toy-serve"]}
                           for m in real["per_layer"] if m["name"] in METRICS]
    bench_file = grown.parent / "BENCHMARK.json"
    bench_file.write_text(json.dumps(bench, indent=1))
    return grown, bench_file


def test_trace_2_reads_the_seven_from_the_recorder_on_the_cpu(
        run, toy_bench, capsys, monkeypatch):
    """The whole path on the CPU: the window runs untraced and the seven
    window metrics are on the line, each what the recorder's rows give by
    hand; the traced slice has no device plane here, so the three traced
    ones are left out of the line and nothing raises."""
    from benchmark import serve_kind
    from scaling_tpu import obs
    from scaling_tpu.obs import recorder

    root, bench_file = toy_bench
    recorder._recorder.ring.clear()  # the process's FIRST marker cuts the window
    seen = {}
    real = serve_kind.run
    monkeypatch.setattr(serve_kind, "run", lambda cell, args, env: seen.setdefault(
        "outcome", real(cell, args, env)))
    result = run.main(["--workload", "toy-serve", "--seed", "3000000057",
                       "--seconds", "1.5", "--trace", "2", "--rehearse",
                       "--root", str(root), "--benchmark-json", str(bench_file)])
    assert result["correct"] and result["failed"] == 0
    capsys.readouterr()
    metrics = {name: m["value"] for name, m in result["metrics"].items()}
    assert set(WINDOW) <= set(metrics) and not set(TRACED) & set(metrics)
    assert {name: result["metrics"][name]["unit"] for name in WINDOW} == {
        name: spec[1] for name, spec in WINDOW.items()}

    # by hand, from the same recorder and the harness's own ticks
    tick_s = seen["outcome"]["host"]["tick_s"]
    rows = obs.recorded_spans()
    first_marker = next(i for i, r in enumerate(rows) if r.name == "obs.capture")
    ticks = [r for r in rows[:first_marker] if r.name == "serve.tick"][-len(tick_s):]
    assert len(ticks) == len(tick_s) > 10
    steps = {t.step for t in ticks}

    def mine(name):
        return [r for r in rows[:first_marker] if r.name == name and r.step in steps]

    def mean_ms(name):
        return sum(r.duration_ns for r in mine(name)) / MS / len(ticks)

    assert metrics["emit_ms_mean.window"] == pytest.approx(mean_ms("serve.emit"))
    assert metrics["retire_ms_mean.window"] == pytest.approx(mean_ms("serve.retire"))
    assert metrics["build_ms_mean.window"] == pytest.approx(mean_ms("serve.mixed.build"))
    assert metrics["dispatch_ms_mean.window"] == pytest.approx(
        mean_ms("serve.mixed.dispatch"))
    emitted_for = sum(r.fields["rows"] for r in mine("serve.emit"))
    assert emitted_for > 0
    assert metrics["emit_us_per_row.window"] == pytest.approx(
        1e3 * mean_ms("serve.emit") * len(ticks) / emitted_for)
    leaves = ("serve.schedule", "serve.mixed.build", "serve.mixed.dispatch",
              "serve.mixed.wait", "serve.emit", "serve.retire")
    assert metrics["unspanned_ms_mean.window"] == pytest.approx(
        sum(t.duration_ns for t in ticks) / MS / len(ticks)
        - sum(mean_ms(name) for name in leaves))
    assert 0 < metrics["unspanned_ms_mean.window"] < metrics["tick_ms_p50"]
    finished = {r.step: r.fields["finished"] for r in mine("serve.retire")}
    pairs = [b.start_ns - a.start_ns - a.duration_ns for a, b in zip(ticks, ticks[1:])
             if b.fields["decodes"] + b.fields["chunks"]
             and a.fields["decodes"] + a.fields["chunks"] > finished[a.step]]
    assert pairs and metrics["between_ticks_ms_mean.window"] == pytest.approx(
        sum(pairs) / MS / len(pairs))
