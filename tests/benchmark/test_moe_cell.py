"""What PR 28 brings for ``olmoe-1b-7b-serve`` as files (``reference/`` and
``views/moe_decoder.py``, ``readers/moe.py``, ``moe_ops_count.py``, three
metrics), rehearsed on the CPU at a toy width through a copy of
``benchmark/`` into which only a toy configuration is added; and the readers
on recorded events."""

import json
import shutil
from pathlib import Path

import pytest

from benchmark import cells, moe_ops_count, serve_kind
from benchmark.readers import moe

TOY_MOE = Path(__file__).parent / "data" / "toy_moe"
BENCH = TOY_MOE / "BENCHMARK.json"
MOE_METRICS = ("moe_time_pct.saturated", "moe_weights_roofline.saturated",
               "moe_load_max_over_mean.saturated")


@pytest.fixture(scope="module")
def grown_moe(grown):
    """``grown`` (the toy traffic is there) plus the one toy configuration;
    reference, view, readers and metrics are the benchmark's own."""
    shutil.copy(TOY_MOE / "configs" / "toy-olmoe.json", grown / "configs")
    shutil.copy(TOY_MOE / "traffic" / "toy-moe-chat.json", grown / "traffic")
    for part, name in (("reference", "moe_decoder.py"), ("views", "moe_decoder.py"),
                       ("readers", "moe.py")):
        assert (cells.ROOT / part / name).is_file() and (grown / part / name).is_file()
    return grown


def rehearse(run, root, trace=0, *more, workload="toy-serve-moe", seconds="1.5"):
    return run.main(["--workload", workload, "--seed", "3000000019",
                     "--seconds", seconds, "--trace", str(trace), "--rehearse",
                     "--root", str(root), "--benchmark-json", str(BENCH), *more])


def spy_on_the_kind(monkeypatch):
    seen = {}
    real = serve_kind.run
    monkeypatch.setattr(serve_kind, "run", lambda cell, args, env: seen.setdefault(
        "outcome", real(cell, args, env)))
    return seen


def test_routed_serve_cell_is_correct_and_reads_its_load(run, grown_moe, capsys,
                                                         monkeypatch):
    """The engine serves OLMoE's equations through the paged cache, every
    checked token on the reference's best logit (float32 on both sides at
    this width: the configuration says why); the traced part's ticks carry
    their load."""
    from scaling_tpu import obs

    seen = spy_on_the_kind(monkeypatch)
    result = rehearse(run, grown_moe, trace=2)
    assert result["correct"] and result["failed"] == 0 and result["unserved"] > 0
    assert seen["outcome"]["host"]["worst_logit_gap"] < 1e-3
    # the CPU has no device plane: the two readers of the trace find nothing
    # and are left out; the spans' reader has the ticks' load fields
    assert set(result["metrics"]) == {"setup_s", "serve_tokens_per_s",
                                      "batch_occupancy_pct", MOE_METRICS[2]}
    assert 1.0 <= result["metrics"][MOE_METRICS[2]]["value"] <= 8.0
    capture = obs.last_capture()
    loads = moe.tick_loads(capture.spans)
    assert loads and all(0 <= f["experts_idle"] <= 8 and f["load_max"] >= f["load_mean"]
                         for f in loads)
    # every real position of the traced ticks: 2 experts in each of 2 layers
    assigned = sum(round(f["load_mean"] * 8) for f in loads)
    assert assigned == capture.counters["serve_moe_assignments_total"]
    tokens = (capture.counters.get("serve_prefill_tokens_total", 0)
              + sum(f.get("decodes", 0) for n, _, _, f in capture.spans
                    if n == "serve.mixed"))
    assert assigned == 2 * 2 * tokens


def test_a_dense_cell_reads_none_of_the_routed_metrics(run, grown, capsys):
    """A dense model's spans carry no load: the readers return nothing."""
    from scaling_tpu import obs

    toy = Path(__file__).parent / "data" / "toy" / "BENCHMARK.json"
    run.main(["--workload", "toy-serve-burst", "--seed", "5", "--seconds", "1.5",
              "--trace", "2", "--rehearse", "--root", str(grown),
              "--benchmark-json", str(toy)])
    capture = obs.last_capture()
    assert any(n == "serve.emit" for n, _, _, _ in capture.spans)
    assert moe.tick_loads(capture.spans) == []
    assert "serve_moe_assignments_total" not in capture.counters
    ctx = {"device": {"peaks": None}, "config": {}}
    assert moe.moe_load_max_over_mean(ctx) is None
    assert moe.moe_weights_roofline(ctx) is None and moe.moe_time_pct(ctx) is None


def test_a_token_altered_where_it_is_produced_is_not_correct(run, grown_moe, capsys,
                                                             monkeypatch):
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
    result = rehearse(run, grown_moe)
    assert result["failed"] == 0 and result["correct"] is False


def test_the_control_fails_the_limit_the_program_keeps(run, grown_moe, capsys, monkeypatch):
    """``--control fp8``: the routed reference with fp8 weights misses the
    limit that the program keeps with room."""
    seen = spy_on_the_kind(monkeypatch)
    # chat traffic the CPU keeps up with: its requests finish, so the gaps
    # are maxima over a few hundred positions and not over a dozen
    result = rehearse(run, grown_moe, 0, "--control", "fp8",
                      workload="toy-serve-moe-chat", seconds="3")
    assert result["correct"]
    host = seen["outcome"]["host"]
    sound, control = host["worst_logit_gap"], host["control_logit_gap"]
    assert sound < serve_kind.LOGIT_TOL / 2 < serve_kind.LOGIT_TOL < control
    assert control > 3 * sound


# ---- the readers on recorded events --------------------------------------

# name, start_ns, dur_ns, scope: what ``load_scoped_ops`` gives; the names
# and the scope paths as a v5e's trace has them
OPS = [
    ["%fusion.101 = bf16[16,32,2048] fusion(...)", 0.0, 400e3, ""],
    ["%fusion.102 = f32[16,32,64] fusion(...)", 400e3, 100e3,
     "jit(mixed)/moe/bsh,he->bse/dot_general"],
    ["%fusion.103 = bf16[64,16,32,1024] fusion(...)", 500e3, 2000e3,
     "jit(mixed)/moe/ebch,ehf->ebcf/dot_general"],
    ["%paged_attention.3 = bf16[16,16,32,128] custom-call(...)", 2500e3, 300e3, ""],
    ["%fusion.103 = bf16[64,16,32,1024] fusion(...)", 2800e3, 2100e3,
     "jit(mixed)/moe/ebch,ehf->ebcf/dot_general"],
    ["%copy.7 = bf16[4097,16,16,128] copy(...)", 4900e3, 100e3, ""],
]
SPANS = [
    ("serve.emit", 0, 10, {"step": 1, "load_max": 30, "load_mean": 12.0, "experts_idle": 0}),
    ("serve.mixed", 0, 10, {"step": 1}),
    ("serve.emit", 20, 10, {"step": 2, "load_max": 8, "load_mean": 2.0, "experts_idle": 24}),
    ("serve.emit", 40, 10, {"step": 3}),  # a tick of the warm-up: no fields
]
CTX = {"device": {"peaks": {"hbm_bytes_per_s": 819e9}},
       "config": {"transformer_architecture": {
           "num_layers": 8, "moe_num_experts": 64, "hidden_size": 2048, "mlp_factor": 0.5}}}


def test_readers_give_the_three_values_by_hand(capsys):
    inside_s = (100e3 + 2000e3 + 2100e3) / 1e9
    assert moe.scoped_seconds(OPS) == pytest.approx(inside_s)
    assert moe.moe_time_pct(CTX, ops=OPS) == pytest.approx(100 * 4.2 / 5.0)
    # two ticks: 8 layers x 64 experts, then 8 x 40; an expert is 3 x 2048 x 1024 x 2 B
    nbytes = 8 * (64 + 40) * 3 * 2048 * 1024 * 2
    assert moe_ops_count.expert_weight_bytes(8 * 104, 2048, 1024, 2) == nbytes
    assert moe.moe_weights_roofline(CTX, ops=OPS, spans=SPANS) == pytest.approx(
        100 * nbytes / inside_s / 819e9)
    assert moe.moe_load_max_over_mean(CTX, spans=SPANS) == pytest.approx((2.5 + 4.0) / 2)
    assert "sum:fusion x3" in capsys.readouterr().err


def test_a_trace_without_the_scope_gives_none_not_zero():
    bare = [[name, start, dur, ""] for name, start, dur, _ in OPS]
    assert moe.scoped_seconds(bare) is None
    assert moe.moe_time_pct(CTX, ops=bare) is None
    assert moe.moe_weights_roofline(CTX, ops=bare, spans=SPANS) is None
    assert moe.moe_time_pct(CTX, ops=[]) is None
    # no load fields (a dense model's spans): nothing, whatever the trace
    assert moe.moe_weights_roofline(CTX, ops=OPS, spans=SPANS[3:]) is None
    assert moe.moe_load_max_over_mean(CTX, spans=SPANS[3:]) is None


def test_ops_take_the_scope_of_their_instruction_in_their_module():
    """An operation is looked up by its instruction's name in the module
    whose interval holds its start: the same name in another program, or
    outside every program, is not the routed MLP's."""
    scopes = {"jit_mixed": {"fusion.103": "jit(mixed)/moe/ebch,ehf->ebcf/dot_general",
                            "fusion.102": "jit(mixed)/moe/bsh,he->bse/dot_general"},
              "jit_other": {}}
    modules = [["jit_mixed(12536509211202233264)", 0.0, 5000e3],
               ["jit_other(77)", 6000e3, 1000e3]]
    bare = [row[:3] for row in OPS] + [
        ["%fusion.103 = f32[8] fusion(...)", 6100e3, 50e3],     # jit_other's fusion.103
        ["%fusion.102 = f32[8] fusion(...)", 5500e3, 50e3]]     # between programs
    rows = moe.scoped_ops(bare, modules, scopes)
    assert [bool(r[3]) for r in rows] == [False, True, True, False, True, False,
                                          False, False]
    assert rows[:6] == OPS
    assert moe.scoped_ops(bare, [], scopes)[1][3] == ""


def test_scopes_are_read_from_the_hlo_a_trace_carries(tmp_path):
    """A trace taken here, on the CPU, of a jitted function with a
    ``jax.named_scope("moe")``: its metadata plane holds the program's HLO,
    and the instructions compiled from inside the scope, fused or not, are
    found by name; the others are not."""
    import jax
    import jax.numpy as jnp

    from benchmark import xplane_hlo

    @jax.jit
    def routed(x, w):
        with jax.named_scope("moe"):
            y = jax.nn.silu(jnp.einsum("ab,bc->ac", x, w))
        with jax.named_scope("remoe"):
            return jnp.tanh(y).sum()

    x = jnp.ones((64, 64))
    routed(x, x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    routed(x, x).block_until_ready()
    jax.profiler.stop_trace()
    trace = sorted(tmp_path.glob("**/*.xplane.pb"))[-1]
    modules = xplane_hlo.hlo_modules(trace.read_bytes())
    assert "jit_routed" in modules
    scopes = xplane_hlo.instruction_scopes(modules["jit_routed"], moe.SCOPE)
    assert any(op.endswith("dot_general") for op in scopes.values())
    assert all("/moe/" in op for op in scopes.values())
    everything = xplane_hlo.instruction_scopes(modules["jit_routed"], moe.re.compile("."))
    assert any("remoe" in op for op in everything.values())
    assert len(scopes) < len(everything)
    # a file with no metadata plane, and bytes that are no HLO
    assert xplane_hlo.hlo_modules(b"") == {}
    assert xplane_hlo.base_name("jit_mixed(12536509211202233264)") == "jit_mixed"


def test_recorded_tick_of_the_chip_is_attributed_as_the_trace_says():
    """One tick recorded on the v5e (PR 28): the expert matmuls' fusions are
    found in the scope by their instruction's name, the whole-pool copies
    and the paged kernel are not."""
    recorded = cells.load_json(Path(__file__).parent / "data" / "moe_trace_events.json")
    rows = moe.scoped_ops(recorded["ops"], [recorded["module"]], recorded["scopes"])
    assert [bool(r[3]) for r in rows] == recorded["want_scope"]
    assert sum(recorded["want_scope"]) == 6 == len(rows) - sum(recorded["want_scope"])
    inside = [r for r in rows if r[3]]
    assert all(r[3].startswith("jit(mixed)/moe/") for r in inside)
    assert any("mlp____w_in" in r[0] for r in inside)
    assert not any("copy" in r[0].split(" = ")[0] for r in inside)
    assert moe.scoped_seconds(rows) == pytest.approx(sum(r[2] for r in inside) / 1e9)


def test_metric_files_name_the_readers():
    bench = json.loads((cells.REPO / "BENCHMARK.json").read_text())
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name in MOE_METRICS:
        spec = cells.load_json(cells.ROOT / "metrics" / f"{name}.json")
        assert spec["reader"].split(":")[0] == "moe" and spec["unit"] == entries[name]["unit"]
        assert entries[name]["layer"] == "routed MLP"
        assert entries[name]["workloads"] == ["serve-olmoe-chat-burst"]
        assert callable(cells.load_reader(name))
