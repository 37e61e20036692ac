"""The harness is driven by data: a configuration, a traffic mix, a per-layer
metric and a cell are added as new files plus one entry, with no file of the
benchmark edited; and what the contract asks of a run without a TPU, of the
traffic generator and of the timing holds. Everything runs in this process
on the CPU at toy size."""

import json
from pathlib import Path

import numpy as np
import pytest

from benchmark import cells, traffic_gen

TOY = Path(__file__).parent / "data" / "toy"


def rehearse(run, grown, capsys, workload, trace):
    result = run.main(["--workload", workload, "--seed", "3000000019",
                       "--seconds", "1.5", "--trace", str(trace), "--rehearse",
                       "--root", str(grown),
                       "--benchmark-json", str(TOY / "BENCHMARK.json")])
    out = capsys.readouterr().out.strip().splitlines()
    # a rehearsal never prints a result line
    assert json.loads(out[-1]) == {"rehearsal": True, "workload": workload}
    assert not any('"metrics"' in line for line in out)
    return result


@pytest.mark.parametrize("workload", ["toy-train", "toy-train-4chip"])
def test_new_train_cell_is_found_by_name_and_runs(run, grown, capsys, workload):
    """``toy-train``: the Mistral branch on one device; ``toy-train-4chip``:
    the Pharia branch (LayerNorm, GELU, biases) at TP=2 x DP=2 with ZeRO-1
    and sequence parallelism on four of the suite's virtual CPU devices."""
    result = rehearse(run, grown, capsys, workload, trace=1)
    assert result["correct"] and result["failed"] == 0 and result["attempted"] > 0
    # per-layer metrics of the traced run: the new reader (a new file) and
    # one the benchmark already had; the CPU has no device plane, so the
    # trace's readers found nothing to read and are left out of the line
    assert result["metrics"]["toy_steps"] == {
        "value": result["attempted"], "unit": "steps"}
    assert result["metrics"]["step_ms_p50"]["value"] > 0
    assert set(result["metrics"]) == {"toy_steps", "step_ms_p50"}
    assert result["device"]["platform"] == "cpu"
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(result)


def test_new_serve_cell_is_found_by_name_and_runs(run, grown, capsys):
    result = rehearse(run, grown, capsys, "toy-serve", trace=0)
    assert result["correct"] and result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == {
        "setup_s", "serve_tokens_per_s", "itl_p95_ms"}
    assert all(m["value"] > 0 for m in result["metrics"].values())


def test_ttft_is_timed_from_when_the_request_was_due(run, grown, capsys, monkeypatch):
    """Every request's time to first token contains how late the generator
    submitted it: the clock starts when it was due, not at the submit."""
    from benchmark import serve_kind

    seen = {}
    real = serve_kind.run

    def spy(cell, args, env):
        seen["outcome"] = real(cell, args, env)
        return seen["outcome"]

    monkeypatch.setattr(serve_kind, "run", spy)
    rehearse(run, grown, capsys, "toy-serve", trace=0)
    host = seen["outcome"]["host"]
    assert len(host["ttft_s"]) == len(host["submit_late_s"]) > 0
    assert all(late >= 0 for late in host["submit_late_s"])
    assert all(t >= late for t, late in zip(host["ttft_s"], host["submit_late_s"]))


def test_without_a_tpu_there_is_no_result_line(run, grown, capsys):
    with pytest.raises(SystemExit) as stop:
        run.main(["--workload", "toy-train", "--seed", "1", "--seconds", "1",
                  "--trace", "0", "--root", str(grown),
                  "--benchmark-json", str(TOY / "BENCHMARK.json")])
    assert stop.value.code not in (0, None)
    assert "no TPU" in str(stop.value.code)
    assert capsys.readouterr().out.strip() == ""


def test_unknown_cell_and_unknown_device_kind_fail():
    from benchmark.peaks import peaks_of

    with pytest.raises(SystemExit):
        cells.load_cell("no-such-cell")
    with pytest.raises(ValueError, match="no published peak"):
        peaks_of("TPU v9")
    assert peaks_of("TPU v5 lite") == {
        "flops_per_s": 197e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9}


CHAT = cells.load_json(cells.ROOT / "traffic" / "chat-steady.json")


def test_traffic_is_a_pure_function_of_the_seed():
    a = traffic_gen.generate(CHAT, 2147483659, 51, 32768)
    b = traffic_gen.generate(CHAT, 2147483659, 51, 32768)
    assert a == b
    c = traffic_gen.generate(CHAT, 7, 51, 32768)
    assert [r.prompt for r in a] != [r.prompt for r in c]
    # another seed replays the same schedule with other token ids: the order
    # of long and short requests decides who queues behind whom, and is not
    # left to the seed
    assert [(r.due_s, len(r.prompt), r.output_len) for r in a] == [
        (r.due_s, len(r.prompt), r.output_len) for r in c]
    # another window length draws its own schedule at the same rate
    d = traffic_gen.generate(CHAT, 7, 20, 32768)
    assert all(r.due_s < 20 for r in d) and len(d) < len(a)


@pytest.mark.parametrize("seed", [0, 7, 2147483659])
def test_traffic_keeps_inside_its_clips(seed):
    reqs = traffic_gen.generate(CHAT, seed, 51, 32768)
    counted = [r for r in reqs if r.counted]
    assert [r.due_s for r in reqs] == sorted(r.due_s for r in reqs)
    assert all(-CHAT["warm_seconds"] <= r.due_s < 0 for r in reqs if not r.counted)
    assert all(0 <= r.due_s < 51 for r in counted) and counted[0].due_s == 0.0
    for r in reqs:
        # a request out of the history carries what it had generated in its
        # prompt, so only a fresh one is held to the prompt's clip
        longest = CHAT["prompt"]["max"] if r.counted else CHAT["max_total"] - 1
        assert CHAT["prompt"]["min"] <= len(r.prompt) <= longest
        assert 1 <= r.output_len <= CHAT["output"]["max"]
        assert len(r.prompt) + r.output_len <= CHAT["max_total"]
        assert all(1 <= t < 32768 for t in r.prompt)
    # the offered rate is the file's
    assert abs(len(counted) / 51 - CHAT["rate"]) < 0.25 * CHAT["rate"]


NOMINAL = {"seconds": 100, "tick_s": 0.1, "prefill_tokens_per_tick": 32}


@pytest.mark.parametrize("age_s, prompt, output, left", [
    (0.05, 64, 10, (64, 10)),   # no tick yet
    (0.15, 64, 10, (64, 10)),   # 1 tick: half the prompt streamed in
    (0.25, 64, 10, (65, 9)),    # 2 ticks: the prompt's last chunk yields a token
    (0.55, 65, 10, (68, 7)),    # 5 ticks, 3 of them the prompt's (65 = 2 x 32 + 1)
    (1.05, 64, 10, (73, 1)),    # 10 ticks: 9 tokens out, the last to come
    (1.15, 64, 10, None),       # 11 ticks: finished, gone
])
def test_history_ages_a_request_by_the_nominal_engine(age_s, prompt, output, left):
    assert traffic_gen._aged(NOMINAL, age_s, prompt, output) == left


def test_window_opens_on_the_requests_the_history_left_running():
    """Those out of the history are all due as the warm-up starts, are as
    many as rate x a request's mean life (Little's law) within what a Poisson
    count of that mean varies by, and each is in mid-life: what the nominal
    engine left of a request the history drew, less of its answer to come
    than it asked for and as much more prompt. (Not less than a FRESH request
    asks for: the requests a moment finds running are the long ones.)"""
    reqs = traffic_gen.generate(CHAT, 7, 51, 32768)
    warm_start = -CHAT["warm_seconds"]
    old = [r for r in reqs if r.due_s == warm_start][:-1]  # the last is the warm-up's first
    h = CHAT["history"]
    mean_life_s = h["tick_s"] * (560 / h["prefill_tokens_per_tick"] + 200)
    running_mean = CHAT["rate"] * mean_life_s
    assert abs(len(old) - running_mean) < 2 * running_mean ** 0.5
    drawn = zip(*traffic_gen._shapes(
        np.random.default_rng([CHAT["shape_seed"], 0]), CHAT, float(h["seconds"])))
    aged = [(int(p), int(o), traffic_gen._aged(h, h["seconds"] - off, int(p), int(o)))
            for off, p, o in drawn]
    running = [(p, o, *left) for p, o, left in aged if left is not None]
    assert [(p_now, o_left) for _, _, p_now, o_left in running] == [
        (len(r.prompt), r.output_len) for r in old]
    assert all(0 <= o - o_left == p_now - p for p, o, p_now, o_left in running)
    # most have generated something; one may still be streaming its prompt in
    assert sum(o_left < o for _, o, _, o_left in running) >= len(running) - 2


def test_every_metric_of_benchmark_json_has_its_file_and_reader():
    bench = cells.load_json(cells.REPO / "BENCHMARK.json")
    end_to_end = {m["name"] for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        spec = cells.load_json(cells.ROOT / "metrics" / f"{m['name']}.json")
        assert spec["unit"] == m["unit"]
        assert callable(cells.load_reader(m["name"]))
        assert m["moves"] in end_to_end
    for w in bench["workloads"]:
        cell = cells.load_cell(w["name"])
        assert cell.kind in ("train", "serve") and cell.chips == w["chips"]
        names = {m["name"] for m in cell.metrics("end_to_end")}
        assert "setup_s" in names and len(names) >= 2
        for m in cell.metrics("per_layer"):
            assert m["moves"] in names, (w["name"], m["name"])
