"""What every serve traffic file of the benchmark promises, file by file:
ONE replayed schedule whatever the seed, as many counted requests as its
``why`` says, and, for traffic the engine is meant to keep up with (PR 39:
``chat-steady`` replaced a cell whose window had idle gaps of 1-2 s in which
the check machines' host changed state), arrivals and work with no hole in
them. And ``BENCHMARK.json`` names no cell that is gone. CPU, no JAX."""

import re

import pytest

from benchmark import cells

from .test_token_rule import BENCH, T0, WINDOW_S, generator_of, replay

CELLS = {w["name"] for w in BENCH["workloads"]}
TRAFFIC = {p.stem: cells.load_json(p) for p in sorted((cells.ROOT / "traffic").glob("*.json"))}
SERVE = {name: traffic for name, traffic in TRAFFIC.items() if traffic["kind"] == "serve"}
# the longest the engine may be without work inside a window before the
# host of a check machine may change state in it (PERF.md, section 7 h)
LONGEST_IDLE_S = 0.25
LONGEST_BETWEEN_ARRIVALS_S = 2.5


def generate(traffic, seed):
    return generator_of(traffic)(traffic, seed, WINDOW_S, 32768)


def the_schedule_is_the_same_for_two_seeds_and_the_token_ids_are_not(name, traffic):
    a, b = generate(traffic, 2147483659), generate(traffic, 39)
    shape = lambda reqs: [(r.due_s, len(r.prompt), r.output_len) for r in reqs]
    assert shape(a) == shape(b)
    assert all(x.prompt != y.prompt for x, y in zip(a, b) if len(x.prompt) >= 32)


def the_counted_requests_number_what_the_why_says(name, traffic):
    said = re.search(r"(\d+) counted requests", traffic["why"])
    assert said, f"{name}: the why gives no count"
    counted = [r for r in generate(traffic, 7) if r.counted]
    assert len(counted) == int(said.group(1))
    assert all(0 <= r.due_s < WINDOW_S for r in counted)


def no_two_arrivals_lie_far_apart(name, traffic):
    due = [r.due_s for r in generate(traffic, 7) if r.counted] + [WINDOW_S]
    assert due[0] == 0.0
    assert max(b - a for a, b in zip(due, due[1:])) <= LONGEST_BETWEEN_ARRIVALS_S


def its_own_nominal_engine_is_never_without_work(name, traffic):
    """Replayed at the file's ``history.tick_s``: the stretches of the window
    in which no request holds a slot."""
    held = sorted((seq.admitted_s - T0, (seq.finished_s or float("inf")) - T0)
                  for _, seq in replay(name, 1e3 * traffic["history"]["tick_s"])
                  if seq.admitted_s is not None)
    idle, busy_until = [], 0.0
    for start, end in held:
        if start > busy_until:
            idle.append((max(busy_until, 0.0), min(start, WINDOW_S)))
        busy_until = max(busy_until, end)
    idle.append((busy_until, WINDOW_S))
    assert max((b - a for a, b in idle), default=0.0) <= LONGEST_IDLE_S, idle


EVERY_FILE = (the_schedule_is_the_same_for_two_seeds_and_the_token_ids_are_not,
              the_counted_requests_number_what_the_why_says)
# only of traffic below the knee, which draws a history for its opening
# state: a burst file's arrivals are ``burst_every_s`` apart by design
BELOW_THE_KNEE = (no_two_arrivals_lie_far_apart, its_own_nominal_engine_is_never_without_work)
CASES = [(name, check) for name, traffic in SERVE.items()
         for check in EVERY_FILE + (BELOW_THE_KNEE if "history" in traffic else ())]


@pytest.mark.parametrize("name, check", CASES,
                         ids=[f"{name}-{check.__name__}" for name, check in CASES])
def test_serve_traffic_file(name, check):
    check(name, SERVE[name])


def test_every_serve_file_is_covered_and_chat_steady_by_all_four():
    assert {"chat-steady", "chat-burst32"} <= set(SERVE)
    assert sum(name == "chat-steady" for name, _ in CASES) == 4
    assert "chat-0.8knee" not in SERVE  # went with its cell (PR 39)


LISTED = [m for m in BENCH["end_to_end"] + BENCH["per_layer"] if "workloads" in m]


@pytest.mark.parametrize("entry", LISTED, ids=lambda m: m["name"])
def test_every_workloads_list_names_cells_that_exist(entry):
    assert entry["workloads"], "a metric is left with an empty list"
    assert set(entry["workloads"]) <= CELLS
    assert len(set(entry["workloads"])) == len(entry["workloads"])
    assert "serve-mistral7b-chat" not in entry["workloads"]
