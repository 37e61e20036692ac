"""What PR 27 made a matter of files: a configuration names its reference
and view, its ``"engine"`` is passed through, a traffic file names its
generator and how its window ends. Everything on the CPU."""

import json
import types
from pathlib import Path

import pytest

from benchmark import cells, model, serve_kind, traffic_gen

TOY = Path(__file__).parent / "data" / "toy"
BENCH = json.loads((cells.REPO / "BENCHMARK.json").read_text())
BURST = cells.load_json(cells.ROOT / "traffic" / "chat-burst32.json")
bursts = cells.load_module(cells.ROOT, "generators", "bursts",
                           cells.GENERATOR_CONTRACT).generate


def rehearse(run, grown, workload, trace=0, seconds="1.5"):
    return run.main(["--workload", workload, "--seed", "3000000019",
                     "--seconds", seconds, "--trace", str(trace), "--rehearse",
                     "--root", str(grown),
                     "--benchmark-json", str(TOY / "BENCHMARK.json")])


# ---- the generator ``bursts`` ------------------------------------------

def shapes(requests):
    return [(r.due_s, len(r.prompt), r.output_len, r.traced) for r in requests]


def test_bursts_are_a_pure_function_of_parameters_and_seed():
    a = bursts(BURST, 2147483659, 51, 32768)
    assert a == bursts(BURST, 2147483659, 51, 32768)
    c = bursts(BURST, 7, 51, 32768)
    # another seed: the same schedule, order inside a burst included, with
    # other token ids
    assert shapes(a) == shapes(c)
    assert [r.prompt for r in a] != [r.prompt for r in c]
    # the cell's own figures: one uncounted burst at -5 s, seven counted
    assert sorted({r.due_s for r in a}) == [-5.0, 0.0, 8.0, 16.0, 24.0, 32.0, 40.0, 48.0]
    assert len(a) == 256 and sum(r.counted for r in a) == 224
    assert all(sum(r.due_s == t for r in a) == 32 for t in {r.due_s for r in a})
    # a shorter window is a prefix of a longer one, and another shape_seed
    # is another schedule
    short = bursts(BURST, 7, 20, 32768)
    assert shapes(short) == shapes(c)[:len(short)] and len(short) == 32 * 4
    assert shapes(bursts({**BURST, "shape_seed": 28}, 7, 51, 32768)) != shapes(c)


@pytest.mark.parametrize("seed", [0, 7, 2147483659, 4294967291])
def test_bursts_keep_inside_their_clips(seed):
    reqs = bursts(BURST, seed, 51, 32768, traced_seconds=5.0)
    plain = bursts(BURST, seed, 51, 32768)
    # the traced part follows and changes nothing before it
    assert reqs[:len(plain)] == plain and not any(r.traced for r in plain)
    traced = reqs[len(plain):]
    assert len(traced) == 32 and all(r.traced and not r.counted and r.due_s == 0.0
                                     for r in traced)
    assert [r.due_s for r in plain] == sorted(r.due_s for r in plain)
    for r in reqs:
        assert BURST["prompt"]["min"] <= len(r.prompt) <= BURST["prompt"]["max"]
        assert 1 <= r.output_len <= BURST["output"]["max"]
        assert len(r.prompt) + r.output_len <= BURST["max_total"]
        assert all(1 <= t < 32768 for t in r.prompt)
    # lengths are chat-steady's
    chat = cells.load_json(cells.ROOT / "traffic" / "chat-steady.json")
    assert all(BURST[k] == chat[k] for k in ("prompt", "output", "max_total"))
    # no burst before the window when there is no warm-up
    cold = bursts({**BURST, "warm_seconds": 0.0}, seed, 51, 32768)
    assert shapes(cold) != [] and min(r.due_s for r in cold) == 0.0
    assert len(cold) == 224


# ---- how the window ends -----------------------------------------------

def seq(first=None, finished=None, generated=(), admitted=None,
        status="completed"):
    stamps = [] if first is None else [first + 0.1 * i for i in range(len(generated))]
    return types.SimpleNamespace(
        first_token_s=first, finished_s=finished, finish_status=status,
        generated=list(generated), token_stamps=stamps, admitted_s=admitted)


def hand_made():
    """The window is [10, 61). Six counted requests and one of the warm-up."""
    request = lambda due, out: traffic_gen.Request(due_s=due, prompt=[5, 6], output_len=out)
    return [
        (request(-1.0, 2), seq(9.5, 9.6, [1, 2], admitted=9.0)),       # warm-up: not counted
        (request(0.0, 3), seq(11.0, 11.2, [1, 2, 3], admitted=10.5)),  # finished
        (request(0.0, 9), seq(60.0, None, [1, 2], admitted=59.0)),     # cut while decoding
        (request(8.0, 4), seq(admitted=None)),                         # waiting for a slot
        (request(8.0, 4), seq(admitted=62.0)),                         # taken during the drain
        (request(8.0, 4), seq(admitted=60.5)),                         # taken in time, no token
        (request(8.0, 4), seq(12.0, 12.1, [1, 2], admitted=11.5)),     # finished at the wrong length
        (request(8.0, 4), object()),                                   # refused at submit
    ]


@pytest.mark.parametrize("backlog, failed, unserved", [("fail", 5, 0), ("cut", 3, 2)])
def test_backlog_cut_against_fail(backlog, failed, unserved):
    """``cut`` takes out of ``failed`` only the requests the engine had not
    taken when arrivals stopped; what it took is held to the same rule."""
    got = serve_kind.window_numbers(hand_made(), 10.0, 51.0, backlog)
    tokens, done, n_failed, finished, cut, ttft, itl, n_unserved = got
    assert (n_failed, n_unserved) == (failed, unserved)
    assert (finished, cut) == (2, 1)  # the wrong length did finish; it failed
    assert [generated for _, generated in done] == [[1, 2, 3], [1, 2]]
    assert tokens == 3 + 2 + 2  # the wrong-length one's tokens were made in the window
    assert ttft == [pytest.approx(1.0), pytest.approx(50.0)]
    assert len(itl) == 2 + 1


def test_backlog_fail_is_the_default_and_an_unknown_rule_is_refused(run, grown, capsys):
    assert serve_kind.window_numbers(hand_made(), 10.0, 51.0) == \
        serve_kind.window_numbers(hand_made(), 10.0, 51.0, "fail")
    traffic = cells.load_json(TOY / "traffic" / "toy-burst.json")
    (grown / "traffic" / "toy-burst-drop.json").write_text(
        json.dumps({**traffic, "backlog": "drop"}))
    bench = json.loads((TOY / "BENCHMARK.json").read_text())
    bench["workloads"].append({**next(w for w in bench["workloads"]
                                      if w["name"] == "toy-serve-burst"),
                               "name": "toy-drop", "traffic": "toy-burst-drop"})
    (grown.parent / "BENCHMARK.json").write_text(json.dumps(bench))
    with pytest.raises(SystemExit, match="'drop'"):
        run.main(["--workload", "toy-drop", "--seed", "1", "--seconds", "1",
                  "--trace", "0", "--rehearse", "--root", str(grown),
                  "--benchmark-json", str(grown.parent / "BENCHMARK.json")])


def test_burst_cell_runs_through_a_generator_file_and_cuts_its_backlog(run, grown, capsys):
    """``toy-serve-burst``: the real ``generators/bursts.py`` at toy size,
    offered faster than the CPU walk-through drains it."""
    result = rehearse(run, grown, "toy-serve-burst", trace=2)
    assert result["correct"] and result["failed"] == 0
    assert result["unserved"] > 0 and result["cut"] >= 0
    assert result["attempted"] == 16 * 6
    assert set(result["metrics"]) == {"setup_s", "serve_tokens_per_s",
                                      "batch_occupancy_pct"}
    assert result["metrics"]["batch_occupancy_pct"]["value"] > 50
    # the chat cell's result line has no such note
    assert "unserved" not in rehearse(run, grown, "toy-serve")


def test_a_token_altered_where_it_is_produced_is_not_correct(run, grown, capsys, monkeypatch):
    """The rest of a run with the timed path broken underneath: every
    emitted token of the engine is moved by one, the requests finish at the
    lengths asked for, and the check against the reference says no."""
    from scaling_tpu.serve import engine as engine_module

    real_tick = engine_module.ServeEngine.tick

    def tick(self):
        out = real_tick(self)
        for s in list(self.scheduler.running.values()) + list(self.finished):
            if s.generated and not getattr(s, "_moved", 0) == len(s.generated):
                s.generated[-1] = s.generated[-1] % 500 + 1
                s._moved = len(s.generated)
        return out

    monkeypatch.setattr(engine_module.ServeEngine, "tick", tick)
    result = rehearse(run, grown, "toy-serve-burst")
    assert result["failed"] == 0 and result["unserved"] > 0
    assert result["correct"] is False


# ---- the configuration's files -----------------------------------------

# a configuration that names no "reference" gets the dense decoder's; the
# routed one names its own (PR 28)
REFERENCES = {"olmoe-1b-7b-serve": "moe_decoder"}


@pytest.mark.parametrize("entry", BENCH["workloads"], ids=lambda w: w["name"])
def test_every_cell_resolves_to_a_reference_a_view_and_a_generator(entry):
    cell = cells.load_cell(entry["name"])
    for name in cells.REFERENCE_CONTRACT:
        assert callable(getattr(cell.reference, name))
    for name in cells.VIEW_CONTRACT:
        assert callable(getattr(cell.view, name))
    named = REFERENCES.get(entry["config"])
    assert cell.reference_name == (named or cells.DEFAULT_REFERENCE)
    assert cell.config.get("reference") == named  # the dense files name none
    assert Path(cell.reference.__file__).stem == Path(cell.view.__file__).stem \
        == cell.reference_name
    if cell.kind == "serve":
        assert callable(cell.generate)
        want = traffic_gen.generate if "generator" not in cell.traffic else bursts
        assert cell.generate.__code__.co_code == want.__code__.co_code
    # the reference takes nothing of the program
    source = Path(cell.reference.__file__).read_text()
    assert "scaling_tpu" not in source.split('"""', 2)[2]


# today's count, PR 26's tree: ops_count.train_flops_per_token(
# model.matmul_param_count(shapes), L, H, Q, 4096), to the digit
FLOPS_PER_TOKEN = {"mistral-7b-v0.3": 5033336832.0,
                   "mistral-7b-v0.3-serve": 23354695680.0,
                   "pharia-1-7b": 13450214400.0}


@pytest.mark.parametrize("name", sorted(FLOPS_PER_TOKEN))
def test_dense_flops_per_token_are_todays_to_the_digit(name):
    from scaling_tpu.models.transformer.model import init_model

    config = cells.load_json(cells.ROOT / "configs" / f"{name}.json")
    view = cells.load_module(cells.ROOT, "views", "dense_decoder", cells.VIEW_CONTRACT)
    cfg = model.transformer_config(
        {**config, "topology": {**config["topology"], "model_parallel_size": 1,
                                "data_parallel_size": 1, "sequence_parallel": False}},
        {"sequence_length": 4096})
    got = view.train_flops_per_token(
        config["transformer_architecture"], model.param_shapes(init_model(cfg, None)), 4096)
    assert got == FLOPS_PER_TOKEN[name]


def test_a_missing_reference_file_fails_with_its_name(grown):
    (grown / "configs" / "toy-lost.json").write_text(json.dumps(
        {**cells.load_json(TOY / "configs" / "toy-rms.json"),
         "name": "toy-lost", "reference": "no_such_decoder"}))
    bench = json.loads((TOY / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "toy-lost", "file": "benchmark/configs/toy-lost.json"})
    bench["workloads"].append({"name": "toy-lost", "config": "toy-lost",
                               "traffic": "toy-train", "chips": 1})
    (grown.parent / "lost.json").write_text(json.dumps(bench))
    cell = cells.load_cell("toy-lost", grown.parent / "lost.json", grown)
    with pytest.raises(SystemExit, match=r"reference/no_such_decoder\.py"):
        cell.reference
    with pytest.raises(SystemExit, match=r"views/no_such_decoder\.py"):
        cell.view
    # a file that is there and lacks a function of the contract names it
    (grown / "views" / "no_such_decoder.py").write_text("def reference_spec(arch): ...\n")
    with pytest.raises(SystemExit, match="train_flops_per_token"):
        cell.view
    # and a traffic file that is not there names itself before JAX is touched
    bench["workloads"].append({"name": "toy-nowhere", "config": "toy-rms",
                               "traffic": "no-such-traffic", "chips": 1})
    (grown.parent / "lost.json").write_text(json.dumps(bench))
    with pytest.raises(SystemExit, match=r"traffic/no-such-traffic\.json"):
        cells.load_cell("toy-nowhere", grown.parent / "lost.json", grown)


def test_engine_keys_are_passed_through_and_an_unknown_one_is_named():
    from scaling_tpu.serve.engine import EngineConfig

    derived = model.engine_config({"num_slots": 16, "context": 4096})
    assert derived == EngineConfig(num_slots=16, num_blocks=16 * 256 + 1,
                                   max_blocks_per_seq=256)
    given = model.engine_config({"num_slots": 4, "context": 1024, "block_size": 32,
                                 "kv_dtype": "int8", "num_blocks": 65})
    assert (given.block_size, given.kv_dtype, given.max_blocks_per_seq) == (32, "int8", 32)
    assert given.num_blocks == 65  # a pool sized from the traffic, not derived
    with pytest.raises(SystemExit, match="window_pools"):
        model.engine_config({"num_slots": 4, "context": 1024, "window_pools": 2})
    for entry in BENCH["configs"]:
        engine = cells.load_json(cells.REPO / entry["file"]).get("engine")
        if engine is not None:
            assert model.engine_config(engine).num_slots == engine["num_slots"]


# ---- an architecture as files ------------------------------------------

def test_routed_cell_brings_its_reference_view_and_flop_count(run, grown, capsys, monkeypatch):
    """``toy-train-moe``: a ``mlp_type: moe`` configuration whose reference,
    view and operation count are all new files; its first step's loss
    agrees with its reference and a token is priced at ``top_k`` experts."""
    from benchmark import train_kind

    seen = {}
    real = train_kind.run
    monkeypatch.setattr(train_kind, "run", lambda cell, args, env: seen.setdefault(
        "outcome", real(cell, args, env)))
    for part, name in (("reference", "toy_moe_decoder.py"), ("views", "toy_moe_decoder.py"),
                       ("configs", "toy-moe.json")):
        assert not (cells.ROOT / part / name).exists()
        assert (grown / part / name).exists()
    result = rehearse(run, grown, "toy-train-moe", trace=1)
    assert result["correct"] and result["failed"] == 0 and result["attempted"] > 0
    host = seen["outcome"]["host"]
    assert abs(host["first_loss"] - host["reference_loss"]) <= train_kind.LOSS_TOL
    # by hand: hidden 128, 2 q / 1 KV heads of 64, 4 experts of width 256
    # of which a token uses 2, 2 layers, vocabulary 512, sequence 256
    attention = 2 * 128 * 128 + 2 * 128 * 64          # q, o; k, v
    router, expert = 128 * 4, 3 * 128 * 256
    norms, head = 2 * 128, 128 + 128 * 512            # a layer's; final norm + head
    at_work = 2 * (attention + router + 2 * expert + norms) + head
    held = 2 * (attention + router + 4 * expert + norms) + head
    assert host["flops_per_token"] == 6.0 * at_work + 6.0 * 2 * 2 * 64 * 256
    assert host["flops_per_token"] < 6.0 * held
    # the dense view, asked about the same tree, would price all four experts
    dense = cells.load_module(cells.ROOT, "views", "dense_decoder")
    assert dense.matmul_param_count(
        model.param_shapes(seen_module(grown))) == held


def seen_module(grown):
    from scaling_tpu.models.transformer.model import init_model

    config = cells.load_json(grown / "configs" / "toy-moe.json")
    return init_model(model.transformer_config(config, {}), None)


# ---- the control: the reference in the next lower precision -------------

@pytest.mark.parametrize("workload", ["toy-train", "toy-train-moe", "toy-serve"])
def test_the_control_fails_the_limit_the_program_keeps(run, grown, capsys, monkeypatch,
                                                       workload):
    """``--control fp8`` at a size a test can hold: with fp8 weights the
    reference itself misses the limit that the program, in the precision its
    configuration states, keeps with room. (The limits were set from the
    chip's readings at the cells' own sizes: PERF.md section 2.)"""
    from benchmark import train_kind

    kind = train_kind if "train" in workload else serve_kind
    seen = {}
    real = kind.run
    monkeypatch.setattr(kind, "run", lambda cell, args, env: seen.setdefault(
        "outcome", real(cell, args, env)))
    result = run.main(["--workload", workload, "--seed", "3000000019", "--seconds", "1.5",
                       "--trace", "0", "--rehearse", "--control", "fp8", "--root", str(grown),
                       "--benchmark-json", str(TOY / "BENCHMARK.json")])
    assert result["correct"]
    host = seen["outcome"]["host"]
    if kind is train_kind:
        sound = abs(host["first_loss"] - host["reference_loss"])
        control, limit = abs(host["control_loss"] - host["reference_loss"]), kind.LOSS_TOL
    else:
        sound, control, limit = (host["worst_logit_gap"], host["control_logit_gap"],
                                 kind.LOGIT_TOL)
    assert sound < limit / 2 and control > limit and control > 3 * sound
    # the benchmark's own runs do not run it
    assert "control" not in json.dumps(result)
