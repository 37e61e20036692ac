"""What PR 73 brings as files: ``readers/setup_phases.py`` and seven
``metrics/setup_*.json``, which read where ``setup_s`` went from the rows the
program writes for JAX's compile events. The reader over hand-made rows, every
value by hand; nothing where the program has no such rows; and the toy cells
walked through ``--trace 2`` on the CPU with the seven entries appended."""

import json
from pathlib import Path

import pytest

from benchmark import cells

TOY = Path(__file__).parent / "data" / "toy"
# metric -> unit
METRICS = {
    "setup_until_device_s": "s",
    "setup_trace_lower_s": "s",
    "setup_cache_load_s": "s",
    "setup_backend_compile_s": "s",
    "setup_cache_misses": "programs",
    "setup_programs_lowered": "programs",
    "setup_unaccounted_s": "s",
}
DURATIONS = ["setup_until_device_s", "setup_trace_lower_s", "setup_cache_load_s",
             "setup_backend_compile_s", "setup_unaccounted_s"]
MS = 1_000_000  # ns


@pytest.fixture(scope="module")
def reader():
    return cells.load_module(cells.ROOT, "readers", "setup_phases")


def row(name, start_ms, dur_ms, **fields):
    from scaling_tpu.obs import Row

    return Row(name, round(start_ms * MS), round(dur_ms * MS), None, None, fields)


def program(name, at_ms, trace_ms, lower_ms, backend_ms, hit=None, load_ms=0.0):
    """One program's rows as the listener writes them: trace, lower, then
    (on a hit) the retrieval just before the backend row that holds it."""
    rows = [row("compile.trace", at_ms, trace_ms, fun_name=name),
            row("compile.lower", at_ms + trace_ms, lower_ms, fun_name=name)]
    at = at_ms + trace_ms + lower_ms
    fields = {"fun_name": name}
    if hit is not None:
        fields["cache_hit"] = hit
    if load_ms:
        rows.append(row("compile.cache_load", at + 0.5, load_ms, fun_name=name))
        fields["retrieval_s"] = load_ms / 1e3
    return rows + [row("compile.backend", at, backend_ms, **fields)]


def marker(at_ms, edge):
    return row("obs.capture", at_ms, 0.0, trace_dir="/t", edge=edge)


# a process that started at 100 ms on the clock and had its device at 9,100
SETUP = (
    [row("process.start", 100, 9000)]
    # an eager operation: compiled (a miss), 3 + 7 + 400 ms
    + program("jit(convert_element_type)", 9200, 3, 7, 400, hit=False)
    # make_batch, traced under eval_shape INSIDE init_params' trace: its
    # trace and lower rows (15 + 10 ms) lie inside that trace of 1,000 ms
    + program("jit(make_batch)", 10100, 15, 10, 30, hit=True, load_ms=20)[:2]
    + program("jit(init_params)", 10000, 1000, 500, 130, hit=True, load_ms=100)
    # the step: 4 s of trace, 2 s of lowering, read back in 900 of 950 ms
    + program("jit(step)", 12000, 4000, 2000, 950, hit=True, load_ms=900)
    # a program with a host callback: the cache was not asked
    + program("jit(debug)", 19000, 1, 2, 50)
)
THROWAWAY = [marker(30000, "start"), marker(30001, "stop")]
# what the check compiles afterwards is not set-up's
AFTER = program("jit(largest_gap)", 31000, 5, 5, 800, hit=False)
RECORDER = SETUP + THROWAWAY + AFTER
SETUP_S = 25.0


# ------------------------------------------------------- the reader, by hand
def test_the_four_durations_and_the_remainder_sum_to_setup_s(
        reader, capsys, monkeypatch):
    ctx = {"end_to_end": {"setup_s": SETUP_S}}
    monkeypatch.setattr(reader, "recorded_spans", lambda: RECORDER)
    got = {name: getattr(reader, name)(ctx) for name in METRICS}
    assert got["setup_until_device_s"] == pytest.approx(9.0)
    # traces and lowerings: 10 + 1,500 (make_batch's 25 inside it) + 6,000 + 3
    assert got["setup_trace_lower_s"] == pytest.approx(7.513)
    assert got["setup_cache_load_s"] == pytest.approx(1.0)
    # 400 + (130 - 100) + (950 - 900) + 50
    assert got["setup_backend_compile_s"] == pytest.approx(0.530)
    assert got["setup_cache_misses"] == 1
    assert got["setup_programs_lowered"] == 5
    assert sum(got[name] for name in DURATIONS) == pytest.approx(SETUP_S, abs=1e-9)
    assert got["setup_unaccounted_s"] == pytest.approx(25.0 - 9.0 - 7.513 - 1.0 - 0.530)
    err = capsys.readouterr().err
    # once a run, whichever reader came first; the step leads the programs
    assert err.count("[phases] setup_s 25.000 = until the device 9.000") == 1
    assert err.index("jit(step)") < err.index("jit(init_params)")
    assert "no compile.lower row starts between the window's opening" in err


def test_nested_trace_rows_are_counted_once(reader):
    outer = program("jit(outer)", 1000, 100, 10, 10)
    inner = program("jit(inner)", 1020, 30, 5, 5)[:2]   # inside outer's trace
    cut = reader.cut_setup([row("process.start", 0, 500)] + inner + outer, 2.0)
    assert cut["trace_lower_s"] == pytest.approx(0.110)
    assert cut["programs_lowered"] == 2
    # the sum by program is a sum: it names who took the seconds
    assert dict(cut["programs"])["jit(inner)"] == 35 * MS


def test_rows_that_closed_after_the_first_marker_are_left_out(reader):
    with_after = reader.cut_setup(RECORDER, SETUP_S)
    without = reader.cut_setup(SETUP + THROWAWAY, SETUP_S)
    assert with_after == without
    assert "jit(largest_gap)" not in dict(with_after["programs"])
    # with no marker at all every row is read
    assert reader.cut_setup(SETUP + AFTER, SETUP_S)["cache_misses"] == 2


def test_the_last_process_start_before_the_cut_is_this_runs(reader):
    older = [row("process.start", 100, 400)] + program("jit(a)", 600, 1, 1, 1)
    cut = reader.cut_setup(older + SETUP + THROWAWAY
                           + [row("process.start", 100, 40000)], SETUP_S)
    assert cut["until_device_s"] == pytest.approx(9.0)


@pytest.mark.parametrize("rows", [
    [], SETUP[1:], THROWAWAY + SETUP, [row("process.start", 100, 9000)],
    [row("serve.tick", 5, 1)] + THROWAWAY,
], ids=["empty", "no-process-start", "start-after-the-cut", "no-compile-rows",
        "a-program-without-the-account"])
def test_a_ring_without_the_account_yields_nothing(reader, rows, monkeypatch):
    assert reader.cut_setup(rows, SETUP_S) is None
    monkeypatch.setattr(reader, "recorded_spans", lambda: rows)
    assert [getattr(reader, name)({"end_to_end": {"setup_s": SETUP_S}})
            for name in METRICS] == [None] * 7


def test_a_program_lowered_in_the_window_is_named_on_stderr(reader, capsys):
    # set-up took 25 s from T0 = 150 ms: the window opened at 25,150
    late = program("jit(mixed_512)", 26000, 5, 5, 5, hit=True, load_ms=2)
    cut = reader.cut_setup(SETUP + late + THROWAWAY, SETUP_S, t0_ns=150 * MS)
    assert cut["lowered_in_window"] == ["jit(mixed_512)"]
    # without T0 the process's start stands in, 50 ms earlier
    assert reader.cut_setup(SETUP + late + THROWAWAY, SETUP_S)[
        "lowered_in_window"] == ["jit(mixed_512)"]
    cut["unaccounted_s"] = 0.0
    reader.report(cut, SETUP_S, 150 * MS)
    err = capsys.readouterr().err
    assert "LOWERED IN THE WINDOW, by the program's rows: ['jit(mixed_512)']" in err
    assert "the process started 50.0 ms before the harness's T0" in err


def test_union_of_intervals(reader):
    assert reader.union_ns([]) == 0
    assert reader.union_ns([(0, 10), (5, 12), (20, 30), (21, 22), (30, 31)]) == 23


# ------------------------------------------------ the entries and the files
@pytest.mark.parametrize("name", sorted(METRICS))
def test_the_entry_is_in_the_benchmark_and_names_its_file(name):
    bench = cells.load_json(cells.REPO / "BENCHMARK.json")
    entries = {m["name"]: m for m in bench["per_layer"]}   # by membership
    all_cells = [w["name"] for w in bench["workloads"]]
    assert entries[name] == {
        "name": name, "unit": METRICS[name], "better": "lower",
        "source": "program_span", "layer": "set-up", "moves": "setup_s",
        "workloads": entries[name]["workloads"]}
    # every cell reports setup_s, so every cell lists the metric
    assert set(all_cells[:15]) <= set(entries[name]["workloads"]) <= set(all_cells)
    assert "workloads" not in next(
        m for m in bench["end_to_end"] if m["name"] == "setup_s")
    spec = cells.load_json(cells.ROOT / "metrics" / f"{name}.json")
    assert spec["reader"] == f"setup_phases:{name}" and spec["unit"] == METRICS[name]
    assert callable(cells.load_reader(name))


# ------------------------------------------------------ the walk, on the CPU
@pytest.fixture(scope="module")
def toy_bench(grown):
    """The toy benchmark with the seven entries appended for its train and
    its serve cell, beside a copy of ``benchmark/`` that holds the files."""
    bench = cells.load_json(TOY / "BENCHMARK.json")
    real = cells.load_json(cells.REPO / "BENCHMARK.json")
    bench["per_layer"] += [{**m, "workloads": ["toy-train", "toy-serve"]}
                           for m in real["per_layer"] if m["name"] in METRICS]
    bench_file = grown.parent / "BENCHMARK.json"
    bench_file.write_text(json.dumps(bench, indent=1))
    return grown, bench_file


@pytest.mark.parametrize("workload,programs", [
    ("toy-train", {"jit(step)", "jit(make_batch)"}),
    ("toy-serve", {"jit(mixed_128)"})])
def test_trace_2_reads_the_seven_from_the_recorder_on_the_cpu(
        run, toy_bench, capsys, workload, programs):
    """The whole path: the cell's set-up compiles its programs, the window
    runs, the throwaway capture leaves the marker, and all seven metrics are
    on the line, adding up to its ``setup_s``."""
    from scaling_tpu import obs
    from scaling_tpu.obs import recorder

    root, bench_file = toy_bench
    # the process's FIRST marker is the cut, and a worker minutes old has
    # other tests' rows: start from an empty ring, as a run does
    recorder._recorder.ring.clear()
    result = run.main(["--workload", workload, "--seed", "3000000073",
                       "--seconds", "1.5", "--trace", "2", "--rehearse",
                       "--root", str(root), "--benchmark-json", str(bench_file)])
    assert result["correct"] and result["failed"] == 0
    err = capsys.readouterr().err
    metrics = {name: m["value"] for name, m in result["metrics"].items()}
    assert set(METRICS) <= set(metrics)
    assert {name: result["metrics"][name]["unit"] for name in METRICS} == METRICS
    assert sum(metrics[name] for name in DURATIONS) == pytest.approx(
        metrics["setup_s"], abs=1e-3)
    assert all(metrics[name] >= 0 for name in DURATIONS[:4])
    assert metrics["setup_programs_lowered"] >= 2
    assert 0 <= metrics["setup_cache_misses"] <= metrics["setup_programs_lowered"]

    # by hand, from the same recorder
    rows = obs.recorded_spans()
    first_marker = next(i for i, r in enumerate(rows) if r.name == "obs.capture")
    before = rows[:first_marker]
    lowered = [r for r in before if r.name == "compile.lower"]
    assert metrics["setup_programs_lowered"] == len(lowered)
    assert programs <= {r.fields["fun_name"] for r in lowered}
    start = [r for r in before if r.name == "process.start"][-1]
    assert metrics["setup_until_device_s"] == start.duration_ns / 1e9
    # the program's rows and the harness's count agree: nothing was lowered
    # between the window's opening and the marker
    assert "no compile.lower row starts between the window's opening" in err
    assert "LOWERED IN THE WINDOW" not in err
