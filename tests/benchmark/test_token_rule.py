"""Whose output tokens ``serve_tokens_per_s`` counts is the traffic file's
(``"tokens"``, PR 30), and below the knee the count may not punish speed.

The cells' own schedules are replayed through a nominal engine written here
(nothing of ``scaling_tpu``), at tick times from 27.5 ms (PR 31's) down to
2.5 ms, and the stamped sequences go to ``serve_kind.window_numbers`` as a
run's do. Counting every stamp, the retired ``serve-mistral7b-chat`` read 85.5
tokens/s at a 45 ms tick and 79.7 at 27.5 ms (ledger, PR 29: refused for it);
the schedule that replaced it (``chat-steady``, PR 39) shows the same fault,
weaker, from a 14 ms tick down. Everything on the CPU, no JAX.
"""

import functools
import json
import re
import types

import pytest

from benchmark import cells, serve_kind, traffic_gen

TOY = cells.REPO / "tests" / "benchmark" / "data" / "toy"
BENCH = json.loads((cells.REPO / "BENCHMARK.json").read_text())
SERVE_CELLS = [w for w in BENCH["workloads"]
               if cells.load_json(cells.ROOT / "traffic" / f"{w['traffic']}.json")["kind"] == "serve"]
WINDOW_S = 51.0
T0 = 1000.0  # the host clock's reading as the window opens
# PR 31's tick, PR 33's neighbourhood, the traffic file's nominal tick (its
# history: 15.6 ms), and beyond
TICKS_MS = (27.5, 22, 20, 17, 15.6, 14, 12, 10, 7.5, 5, 2.5)


def load_traffic(name):
    return cells.load_json(cells.ROOT / "traffic" / f"{name}.json")


def generator_of(traffic):
    """The traffic file's generator, found as ``cells.Cell.generate`` finds it."""
    if "generator" not in traffic:
        return traffic_gen.generate
    return cells.load_module(cells.ROOT, "generators", traffic["generator"],
                             cells.GENERATOR_CONTRACT).generate


@functools.lru_cache(maxsize=None)
def replay(traffic_name: str, tick_ms: float, slots: int = 16, chunk: int = 32):
    """``(request, sequence)`` pairs as ``serve_kind.run`` leaves them, from
    a nominal engine: a request is submitted when it is due and takes the
    first free of ``slots`` slots, first come first served; every tick lasts
    ``tick_ms`` and gives each running request the next ``chunk`` tokens of
    its prompt or, once that is in (the tick that takes in the last of it
    too), one output token, stamped as the tick ends. The engine ticks
    while it has work and the run ends as ``serve_kind.run`` ends it."""
    traffic = load_traffic(traffic_name)
    requests = generator_of(traffic)(traffic, 7, WINDOW_S, 32768)
    cut = traffic.get("backlog", "fail") == "cut"
    tick_s = tick_ms / 1e3
    seqs = [types.SimpleNamespace(
        first_token_s=None, finished_s=None, finish_status="completed",
        generated=[], token_stamps=[], admitted_s=None, prompt_left=len(r.prompt))
        for r in requests]
    now, idx, waiting, running = requests[0].due_s, 0, [], []
    while True:
        while idx < len(requests) and requests[idx].due_s <= now:
            waiting.append(idx)
            idx += 1
        while waiting and len(running) < slots:
            running.append(waiting.pop(0))
            seqs[running[-1]].admitted_s = T0 + now
        if not running:
            if idx >= len(requests):
                break
            now = requests[idx].due_s
            continue
        now += tick_s
        for i in list(running):
            seq = seqs[i]
            seq.prompt_left -= min(chunk, seq.prompt_left)
            if seq.prompt_left:
                continue
            seq.generated.append(1)
            seq.token_stamps.append(T0 + now)
            if seq.first_token_s is None:
                seq.first_token_s = T0 + now
            if len(seq.generated) == requests[i].output_len:
                seq.finished_s = T0 + now
                running.remove(i)
        if now > WINDOW_S and idx >= len(requests) and (
                now > WINDOW_S + serve_kind.DRAIN_CAP_S
                or all(seq.first_token_s is not None
                       for r, seq in zip(requests, seqs) if r.counted and (
                           not cut or serve_kind.taken_by(seq, T0 + WINDOW_S)))):
            break
    return list(zip(requests, seqs))


def tokens_in_window(traffic_name, tick_ms, rule=None):
    """The cell's count: by its traffic file's rule unless ``rule`` is given."""
    traffic = load_traffic(traffic_name)
    got = serve_kind.window_numbers(
        replay(traffic_name, tick_ms), T0, WINDOW_S, traffic.get("backlog", "fail"),
        rule or traffic.get("tokens", "all"))
    assert got[2] == 0  # no counted request failed in the nominal engine
    return got[0]


def every_stamp(traffic_name, tick_ms):
    """The count as it was before PR 30, written out."""
    return sum(T0 <= s < T0 + WINDOW_S
               for _, seq in replay(traffic_name, tick_ms) for s in seq.token_stamps)


# ---- below the knee: the window's own requests ---------------------------

@pytest.mark.parametrize("slower, faster", list(zip(TICKS_MS, TICKS_MS[1:])),
                         ids=lambda ms: f"{ms}ms")
def test_the_chat_cells_count_never_falls_as_the_tick_shortens(slower, faster):
    slow = tokens_in_window("chat-steady", slower)
    fast = tokens_in_window("chat-steady", faster)
    assert fast >= slow
    # and it is bounded by what the window's 138 requests ask for
    offered = sum(r.output_len for r, _ in replay("chat-steady", faster) if r.counted)
    assert 0 < slow <= fast <= offered == 26825


def test_counting_every_stamp_punishes_speed_in_the_chat_cell():
    """The fault of PR 29-30 on today's schedule, pinned: the 22 requests
    submitted before the window hold 4,239 output tokens, and the faster the
    tick the more of them are stamped before it opens, so the count of every
    stamp falls from a 14 ms tick to a 2.5 ms one while the cell's own rises."""
    before = [r for r, _ in replay("chat-steady", 14) if not r.counted]
    assert (len(before), sum(r.output_len for r in before)) == (22, 4239)
    at_14, at_2 = every_stamp("chat-steady", 14), every_stamp("chat-steady", 2.5)
    assert at_14 == tokens_in_window("chat-steady", 14, "all")
    assert (at_14, at_2) == (26985, 26781)  # a faster engine reads 0.8% LESS
    # what falls is the share of the requests submitted before the window
    assert at_14 - tokens_in_window("chat-steady", 14) == 1154
    assert at_2 - tokens_in_window("chat-steady", 2.5) == 0
    # the same two replays by the cell's rule
    assert tokens_in_window("chat-steady", 2.5) > tokens_in_window("chat-steady", 14)
    # at the file's own nominal tick; PERF.md has the chip's reading beside it
    assert tokens_in_window("chat-steady", 15.6) / WINDOW_S == pytest.approx(502.41, abs=0.01)


def test_only_the_counted_requests_stamps_are_counted():
    request = lambda due: traffic_gen.Request(due_s=due, prompt=[5, 6], output_len=4)
    stamps = lambda *at: types.SimpleNamespace(
        first_token_s=at[0], finished_s=at[-1], finish_status="completed",
        generated=[1] * len(at), token_stamps=list(at), admitted_s=at[0] - 1)
    submitted = [(request(-5.0), stamps(9.0, 9.9, 10.0, 10.1)),  # of the warm-up
                 (request(0.0), stamps(10.0, 20.0, 60.9, 61.0)),
                 (request(50.0), stamps(60.5, 61.5, 62.5, 63.5)),
                 (request(1.0), object())]                       # refused at submit
    assert serve_kind.tokens_stamped(submitted, 10.0, 51.0) == 2 + 3 + 1
    assert serve_kind.tokens_stamped(submitted, 10.0, 51.0, "counted") == 3 + 1
    for rule in serve_kind.TOKEN_RULES:
        assert serve_kind.window_numbers(submitted, 10.0, 51.0, "fail", rule)[0] == \
            serve_kind.tokens_stamped(submitted, 10.0, 51.0, rule)
    assert serve_kind.window_numbers(submitted, 10.0, 51.0) == \
        serve_kind.window_numbers(submitted, 10.0, 51.0, "fail", "all")  # the defaults
    # nothing else of the window moves with the rule
    assert serve_kind.window_numbers(submitted, 10.0, 51.0, "fail", "all")[1:] == \
        serve_kind.window_numbers(submitted, 10.0, 51.0, "fail", "counted")[1:]


# ---- above the knee: what the engine completes, as before ----------------

# (every stamp in the window, unserved) by the parent's ``window_numbers``
# (commit 528f16a) on these same replays, at the ticks of the OLMoE and the
# Mistral burst cell and of PR 29's change: the burst cells' level does not
# reset. The chip reads 253.3-254.0 and 317.8-318.5 tokens/s, 180 and 166
# unserved (PERF.md, PR 27-28): 12968 / 51 = 254.3, 16257 / 51 = 318.8.
BURST_BEFORE = {58.4: (12968, 180), 46.7: (16257, 166), 29.9: (25248, 123)}


@pytest.mark.parametrize("tick_ms", sorted(BURST_BEFORE))
def test_the_burst_traffic_counts_every_stamp_as_before(tick_ms):
    got = tokens_in_window("chat-burst32", tick_ms)
    unserved = serve_kind.window_numbers(
        replay("chat-burst32", tick_ms), T0, WINDOW_S, "cut")[7]
    assert (got, unserved) == BURST_BEFORE[tick_ms]
    assert got == every_stamp("chat-burst32", tick_ms)
    # its warm-up burst's tokens are the engine's work too: the other rule
    # would leave them out
    assert tokens_in_window("chat-burst32", tick_ms, "counted") < got


def test_the_burst_count_rises_with_speed():
    assert tokens_in_window("chat-burst32", 29.9) > 1.5 * tokens_in_window("chat-burst32", 46.7)


# ---- the rule is the traffic file's, by name -----------------------------

@pytest.mark.parametrize("entry", SERVE_CELLS, ids=lambda w: w["name"])
def test_each_serve_cells_traffic_names_its_rule(entry):
    traffic = load_traffic(entry["traffic"])
    below_the_knee = traffic.get("backlog", "fail") == "fail"
    # below the knee the window's own requests; above it (the backlog is
    # cut) whatever the engine completes, the key left out
    assert traffic.get("tokens") == ("counted" if below_the_knee else None)
    assert traffic.get("tokens", "all") in serve_kind.TOKEN_RULES


@pytest.mark.parametrize("name", ["toy-chat", "toy-burst"])
def test_the_toy_traffic_names_no_rule(name):
    assert "tokens" not in cells.load_json(TOY / "traffic" / f"{name}.json")


def toy_cell(grown, traffic_name: str, **keys):
    """``toy-serve`` under a copy of its traffic with ``keys`` added: the
    BENCHMARK.json to run it from."""
    traffic = cells.load_json(TOY / "traffic" / "toy-chat.json")
    (grown / "traffic" / f"{traffic_name}.json").write_text(json.dumps({**traffic, **keys}))
    bench = json.loads((TOY / "BENCHMARK.json").read_text())
    bench["workloads"].append({**next(w for w in bench["workloads"] if w["name"] == "toy-serve"),
                               "name": traffic_name, "traffic": traffic_name})
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if "toy-serve" in metric.get("workloads", ()):
            metric["workloads"].append(traffic_name)
    bench_file = grown.parent / f"{traffic_name}.json"
    bench_file.write_text(json.dumps(bench))
    return ["--workload", traffic_name, "--seed", "3000000019", "--seconds", "1.5",
            "--trace", "0", "--rehearse", "--root", str(grown),
            "--benchmark-json", str(bench_file)]


def test_a_run_counts_by_its_traffic_files_rule(run, grown, capsys):
    # a short warm-up and long answers: its requests decode on into the window
    result = run.main(toy_cell(grown, "toy-chat-counted", tokens="counted", warm_seconds=0.05,
                               output={"median": 12, "sigma": 0.1, "min": 10, "max": 12}))
    assert result["correct"] and result["failed"] == 0
    said = re.search(r"stamped in the window: (\d+) by \"tokens\": 'all', (\d+) by "
                     r"\"tokens\": 'counted'; this traffic counts 'counted'",
                     capsys.readouterr().err)
    every, counted = int(said.group(1)), int(said.group(2))
    assert 0 < counted < every  # the toy's warm-up requests decode into its window
    assert result["metrics"]["serve_tokens_per_s"]["value"] == counted / 1.5


def test_an_unknown_token_rule_is_refused(run, grown):
    with pytest.raises(SystemExit, match="'mine'"):
        run.main(toy_cell(grown, "toy-chat-mine", tokens="mine"))
