"""What PR 42 brings as files: ``readers/window_spans.py`` and seven
``metrics/*.json``, which read the MEASURED window's own spans from the
program's span recorder. Each reader on a synthetic recorder, every value
by hand; nothing where the rows do not line up with the harness's ticks;
and the files added to a copy of ``benchmark/`` into which only files go,
walked through ``--trace 2`` on the CPU."""

import filecmp
import json
import shutil
from pathlib import Path
from statistics import median

import pytest

from benchmark import cells, serve_kind

TOY = Path(__file__).parent / "data" / "toy"
SERVE_CELLS = ["serve-mistral7b-chat-steady", "serve-mistral7b-chat-burst",
               "serve-olmoe-chat-burst", "serve-ouro2.6b-reason-burst"]
# metric -> (reader's function, unit, better, layer)
WINDOW_METRICS = {
    "tick_host_ms_p50.window": ("tick_host_ms_p50", "ms", "lower", "engine tick"),
    "sched_ms_mean.window": ("sched_ms_mean", "ms", "lower", "scheduler"),
    "evict_ms_mean.window": ("evict_ms_mean", "ms", "lower", "scheduler"),
    "tick_fill_pct.window": ("tick_fill_pct", "%", "higher", "scheduler"),
    "wide_tick_pct.window": ("wide_tick_pct", "%", "lower", "scheduler"),
    "ttft_queue_pct": ("ttft_queue_pct", "%", "lower", "scheduler"),
    "prefill_ms_p50": ("prefill_ms_p50", "ms", "lower", "engine tick"),
}
ADDED = ["readers/window_spans.py"] + [f"metrics/{m}.json" for m in WINDOW_METRICS]
MS = 1_000_000  # ns


def row(name, start_ms, dur_ms, step=None, parent=None, **fields):
    from scaling_tpu.obs import Row

    return Row(name, round(start_ms * MS), round(dur_ms * MS), step, parent, fields)


def tick_rows(step, start_ms, tick_ms, wait_ms, sched_ms, width, tokens, **sched):
    """One tick as the engine closes it: children first."""
    return [
        row("serve.schedule", start_ms, sched_ms, step, "serve.tick", **sched),
        row("serve.mixed.wait", start_ms + sched_ms + 1, wait_ms, step, "serve.mixed"),
        row("serve.mixed", start_ms + sched_ms, wait_ms + 2, step, "serve.tick",
            width=width, tokens=tokens, decodes=3, chunks=1),
        row("serve.tick", start_ms, tick_ms, step, None, decodes=3, chunks=1),
    ]


def marker(at_ms, edge):
    return row("obs.capture", at_ms, 0.0, trace_dir="/t", edge=edge)


# the window: four ticks from 1000 ms on, under steps 10-13
WINDOW = (
    tick_rows(10, 1000, 16.0, 12.0, 0.2, 128, 20)
    + tick_rows(11, 1020, 15.0, 12.5, 0.3, 128, 100)
    # arrived at 1005, a slot 30 ms later, first token out of tick 12
    + tick_rows(12, 1040, 17.0, 13.0, 1.5, 512, 300, evict_ms=1.0, evicted=3)[:3]
    + [row("serve.first_token", 1005, 50.0, queue_s=0.030, prompt_tokens=40, req=5)]
    + tick_rows(12, 1040, 17.0, 13.0, 1.5, 512, 300)[3:]
    + tick_rows(13, 1060, 20.0, 13.0, 0.4, 128, 64)[:3]
    + [row("serve.first_token", 1030, 48.0, queue_s=0.010, prompt_tokens=64, req=6),
       # arrived before the window opened: not the window's request
       row("serve.first_token", 990, 88.0, queue_s=0.080, prompt_tokens=9, req=4)]
    + tick_rows(13, 1060, 20.0, 13.0, 0.4, 128, 64)[3:]
)
TICK_S = [0.01603, 0.01502, 0.01704, 0.02001]  # the harness's two clock reads
WARM_UP = (tick_rows(8, 900, 30.0, 25.0, 5.0, 512, 500)
           + [row("serve.first_token", 890, 40.0, queue_s=0.039, prompt_tokens=7, req=1)]
           + tick_rows(9, 950, 31.0, 25.0, 5.0, 512, 500, evict_ms=4.0, evicted=9))
THROWAWAY = [marker(1100, "start"), marker(1101, "stop")]
TRACED = (tick_rows(14, 1110, 40.0, 20.0, 9.0, 512, 1)   # the lead ticks
          + [marker(1200, "start")]
          + tick_rows(15, 1210, 41.0, 20.0, 9.0, 512, 1)
          + [marker(1300, "stop")])
RECORDER = WARM_UP + WINDOW + THROWAWAY + TRACED
# 16 slots x chunk 32: the engine builds its program at 128 and at 512 tokens
CONFIG = {"engine": {"num_slots": 16, "context": 4096}}
BY_HAND = {
    "tick_host_ms_p50.window": 4.0,    # 16-12, 15-12.5, 17-13, 20-13 -> 2.5 4 4 7
    "sched_ms_mean.window": 0.6,       # (0.2 + 0.3 + 1.5 + 0.4) / 4
    "evict_ms_mean.window": 0.25,      # 1.0 / 4 ticks
    "tick_fill_pct.window": 100 * 484 / 896,  # 20+100+300+64 over 3 x 128 + 512
    "wide_tick_pct.window": 25.0,      # one tick of four at 512
    "ttft_queue_pct": 100 * 40 / 98,   # 30 + 10 ms of 50 + 48
    "prefill_ms_p50": 29.0,            # 50 - 30 = 20, 48 - 10 = 38
}


def read(monkeypatch, name, rows, tick_s=TICK_S, root=cells.ROOT):
    reader = cells.load_reader(name, root)
    monkeypatch.setitem(reader.__globals__, "recorded_spans", lambda: rows)
    return reader({"host": {"tick_s": tick_s}, "config": CONFIG})


@pytest.mark.parametrize("name", sorted(WINDOW_METRICS))
def test_each_reader_on_a_synthetic_recorder_by_hand(name, monkeypatch):
    """Warm-up ticks before the window, the throwaway capture's markers
    after it, lead ticks and the traced slice after those: only the window's
    four ticks, and the two requests that arrived inside them, are read."""
    assert read(monkeypatch, name, RECORDER) == pytest.approx(BY_HAND[name])
    # the recorder of a window that evicted nothing reads 0, not nothing
    plain = [r._replace(fields={k: v for k, v in r.fields.items()
                                if k not in ("evict_ms", "evicted")})
             for r in RECORDER]
    want = 0.0 if name == "evict_ms_mean.window" else BY_HAND[name]
    assert read(monkeypatch, name, plain) == pytest.approx(want)


NOTHING = {
    # one tick's time swapped: the rows are not the harness's ticks
    "durations do not line up": (RECORDER, [TICK_S[1], TICK_S[0]] + TICK_S[2:]),
    "fewer ticks than the harness counted": (RECORDER, [0.03, 0.031] + TICK_S + [0.04]),
    "no marker (a run that took no capture)": (WARM_UP + WINDOW, TICK_S),
    # --trace 1: the process's first capture holds the traced ticks
    "the first capture holds ticks": (WARM_UP + WINDOW + TRACED + THROWAWAY, TICK_S),
    "an empty recorder": ([], TICK_S),
    "a harness that ran no tick": (RECORDER, []),
}


@pytest.mark.parametrize("case", sorted(NOTHING))
def test_every_reader_returns_nothing_when_the_window_cannot_be_cut(case, monkeypatch):
    rows, tick_s = NOTHING[case]
    for name in WINDOW_METRICS:
        assert read(monkeypatch, name, rows, tick_s) is None, name


def test_without_the_recorder_every_reader_returns_nothing(monkeypatch):
    """The parent's program: ``scaling_tpu.obs`` has no ``recorded_spans``."""
    from scaling_tpu import obs

    monkeypatch.delattr(obs, "recorded_spans")
    for name in WINDOW_METRICS:
        reader = cells.load_reader(name)
        assert reader({"host": {"tick_s": TICK_S}, "config": CONFIG}) is None


def test_the_seven_entries_are_appended_and_name_their_files():
    bench = cells.load_json(cells.REPO / "BENCHMARK.json")
    last = bench["per_layer"][-len(WINDOW_METRICS):]
    assert [m["name"] for m in last] == list(WINDOW_METRICS)
    end_to_end = {m["name"]: m for m in bench["end_to_end"]}
    for m in last:
        function, unit, better, layer = WINDOW_METRICS[m["name"]]
        assert m == {"name": m["name"], "unit": unit, "better": better,
                     "source": "program_span", "layer": layer,
                     "moves": "serve_tokens_per_s", "workloads": SERVE_CELLS}
        # every listed cell reports the end-to-end metric it moves
        assert set(SERVE_CELLS) <= set(end_to_end[m["moves"]]["workloads"])
        spec = cells.load_json(cells.ROOT / "metrics" / f"{m['name']}.json")
        assert spec["reader"] == f"window_spans:{function}" and spec["unit"] == unit
        assert layer in {x["layer"] for x in bench["per_layer"][:-len(WINDOW_METRICS)]}


@pytest.fixture(scope="module")
def grown_by_files(tmp_path_factory):
    """A copy of ``benchmark/`` as it was before this PR (the eight files left
    out) plus the toy files; then the eight, ADDED as files. Nothing the
    benchmark had is touched."""
    root = tmp_path_factory.mktemp("checkout") / "benchmark"
    shutil.copytree(cells.ROOT, root, ignore=shutil.ignore_patterns(
        "__pycache__", "window_spans.py", *(f"{m}.json" for m in WINDOW_METRICS)))
    for part in ("configs", "traffic", "metrics", "readers", "reference", "views"):
        for f in (TOY / part).iterdir():
            shutil.copy(f, root / part / f.name)
    before = {p.relative_to(root): p.read_bytes()
              for p in root.rglob("*") if p.is_file()}
    assert not any(Path(rel) in before for rel in ADDED)
    for rel in ADDED:
        shutil.copy(cells.ROOT / rel, root / rel)
    after = {p.relative_to(root) for p in root.rglob("*") if p.is_file()}
    assert after - set(before) == {Path(rel) for rel in ADDED}
    assert all((root / rel).read_bytes() == data for rel, data in before.items())
    assert all(filecmp.cmp(root / rel, cells.ROOT / rel, shallow=False)
               for rel in before if (cells.ROOT / rel).is_file())
    # the toy benchmark with the seven entries appended, for its serve cell
    bench = cells.load_json(TOY / "BENCHMARK.json")
    real = cells.load_json(cells.REPO / "BENCHMARK.json")
    bench["per_layer"] += [{**m, "workloads": ["toy-serve"]}
                           for m in real["per_layer"] if m["name"] in WINDOW_METRICS]
    bench_file = root.parent / "BENCHMARK.json"
    bench_file.write_text(json.dumps(bench, indent=1))
    return root, bench_file


def test_trace_2_reads_the_window_from_the_recorder_on_the_cpu(
        run, grown_by_files, capsys, monkeypatch):
    """The whole path: the toy cell's window runs untraced, the throwaway
    capture leaves the marker, the traced part follows, and all seven
    metrics are on the line, each what the recorder's rows give by hand."""
    from scaling_tpu import obs
    from scaling_tpu.obs import recorder

    root, bench_file = grown_by_files
    # the process's FIRST marker cuts the window: a worker that ran another
    # file's captures before this test starts from an empty ring, as a run does
    recorder._recorder.ring.clear()
    seen = {}
    real = serve_kind.run
    monkeypatch.setattr(serve_kind, "run", lambda cell, args, env: seen.setdefault(
        "outcome", real(cell, args, env)))
    result = run.main(["--workload", "toy-serve", "--seed", "3000000019",
                       "--seconds", "1.5", "--trace", "2", "--rehearse",
                       "--root", str(root), "--benchmark-json", str(bench_file)])
    assert result["correct"] and result["failed"] == 0
    assert json.loads(capsys.readouterr().out.strip().splitlines()[-1]) == {
        "rehearsal": True, "workload": "toy-serve"}
    metrics = {name: m["value"] for name, m in result["metrics"].items()}
    assert set(WINDOW_METRICS) <= set(metrics)
    assert {name: result["metrics"][name]["unit"] for name in WINDOW_METRICS} == {
        name: spec[1] for name, spec in WINDOW_METRICS.items()}

    # by hand, from the same recorder and the harness's own ticks
    tick_s = seen["outcome"]["host"]["tick_s"]
    rows = obs.recorded_spans()
    first_marker = next(i for i, r in enumerate(rows) if r.name == "obs.capture")
    assert [r.fields["edge"] for r in rows if r.name == "obs.capture"] == [
        "start", "stop", "start", "stop"]
    ticks = [r for r in rows[:first_marker] if r.name == "serve.tick"][-len(tick_s):]
    assert len(ticks) == len(tick_s) > 10
    # the span lies inside the harness's two clock reads
    assert all(0 <= 1e9 * s - r.duration_ns < 0.5 * MS for r, s in zip(ticks, tick_s))
    steps = {t.step for t in ticks}

    def mine(name):
        return [r for r in rows[:first_marker] if r.name == name and r.step in steps]

    waits = {r.step: r.duration_ns for r in mine("serve.mixed.wait")}
    assert metrics["tick_host_ms_p50.window"] == pytest.approx(median(
        (t.duration_ns - waits[t.step]) / 1e6 for t in ticks if t.step in waits))
    assert 0 < metrics["tick_host_ms_p50.window"] < metrics["tick_ms_p50"]
    assert metrics["sched_ms_mean.window"] == pytest.approx(
        sum(r.duration_ns for r in mine("serve.schedule")) / 1e6 / len(ticks))
    # 4 slots x 256 tokens for this traffic: the pool is never short
    assert metrics["evict_ms_mean.window"] == 0.0
    mixed = mine("serve.mixed")
    assert metrics["tick_fill_pct.window"] == pytest.approx(
        100 * sum(r.fields["tokens"] for r in mixed)
        / sum(r.fields["width"] for r in mixed))
    assert 0 < metrics["tick_fill_pct.window"] <= 100
    # 4 slots x chunk 32: one program, at 128 tokens, so every tick is wide
    assert {r.fields["width"] for r in mixed} == {128}
    assert metrics["wide_tick_pct.window"] == 100.0
    opens, closes = ticks[0].start_ns, ticks[-1].start_ns + ticks[-1].duration_ns
    firsts = [r for r in rows[:first_marker] if r.name == "serve.first_token"
              and opens <= r.start_ns and r.start_ns + r.duration_ns <= closes]
    assert firsts
    assert metrics["ttft_queue_pct"] == pytest.approx(
        100 * sum(r.fields["queue_s"] for r in firsts) * 1e9
        / sum(r.duration_ns for r in firsts))
    assert 0 <= metrics["ttft_queue_pct"] < 100
    assert metrics["prefill_ms_p50"] == pytest.approx(median(
        r.duration_ns / 1e6 - 1e3 * r.fields["queue_s"] for r in firsts))
    # the traced slice's rows came after the marker and were not read
    assert any(r.name == "serve.tick" for r in rows[first_marker:])
