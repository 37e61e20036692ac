"""What PR 46 brings for ``nemotron3-nano-30b-a3b-serve`` as files
(``reference/`` and ``views/hybrid_ssm_decoder.py``, ``readers/hybrid.py``,
``hybrid_ops_count.py``, five metrics, ``traffic/reason-burst64.json``),
rehearsed on the CPU at a toy width through a copy of ``benchmark/`` into
which only a toy configuration is added; and the readers on recorded rows."""

import json
import shutil
from pathlib import Path

import pytest

from benchmark import cells, hybrid_ops_count, serve_kind
from benchmark.readers import hybrid

TOY_HYBRID = Path(__file__).parent / "data" / "toy_hybrid"
BENCH = TOY_HYBRID / "BENCHMARK.json"
CELL = "serve-nemotron3nano-reason-burst"
CONFIG = "nemotron3-nano-30b-a3b-serve"
HYBRID_METRICS = {
    "ssm_time_pct.saturated": "state-space mixer",
    "ssm_state_roofline.saturated": "state-space mixer",
    "moe_held_roofline.saturated": "routed MLP",
    "moe_absent_assign_pct.saturated": "routed MLP",
    "tick_mfu_pct.hybrid": "engine tick",
}


@pytest.fixture(scope="module")
def grown_hybrid(grown):
    """``grown`` (the toy burst traffic is there) plus the one toy
    configuration and its chat traffic; reference, view, readers and
    metrics are the benchmark's own."""
    shutil.copy(TOY_HYBRID / "configs" / "toy-nemotron.json", grown / "configs")
    shutil.copy(TOY_HYBRID / "traffic" / "toy-hybrid-chat.json", grown / "traffic")
    for part, name in (("reference", "hybrid_ssm_decoder.py"),
                       ("views", "hybrid_ssm_decoder.py"), ("readers", "hybrid.py")):
        assert (cells.ROOT / part / name).is_file() and (grown / part / name).is_file()
    return grown


def rehearse(run, root, trace=0, *more, workload="toy-serve-hybrid", seconds="1.5"):
    return run.main(["--workload", workload, "--seed", "3000000019",
                     "--seconds", seconds, "--trace", str(trace), "--rehearse",
                     "--root", str(root), "--benchmark-json", str(BENCH), *more])


def spy_on_the_kind(monkeypatch):
    seen = {}
    real = serve_kind.run
    monkeypatch.setattr(serve_kind, "run", lambda cell, args, env: seen.setdefault(
        "outcome", real(cell, args, env)))
    return seen


def test_hybrid_serve_cell_is_correct_and_reads_its_state_and_its_share(
        run, grown_hybrid, capsys, monkeypatch):
    """The engine serves the stack through the paged cache and the state pool,
    every checked token on the reference's best logit (float32 on both sides
    at this width: the configuration says why); the traced part's ticks carry
    the rows whose state advanced and the assignments that fell on absent
    experts."""
    from scaling_tpu import obs

    seen = spy_on_the_kind(monkeypatch)
    result = rehearse(run, grown_hybrid, trace=2)
    assert result["correct"] and result["failed"] == 0 and result["unserved"] > 0
    assert seen["outcome"]["host"]["worst_logit_gap"] < 1e-3
    # the CPU has no device plane and no published peak: the readers of the
    # trace and of the peak find nothing and are left out; the counters' and
    # the spans' have the ticks' numbers
    assert set(result["metrics"]) == {
        "setup_s", "serve_tokens_per_s", "batch_occupancy_pct",
        "moe_load_max_over_mean.saturated", "moe_absent_assign_pct.saturated"}
    # 4 of 8 experts held, fresh weights: about half of the assignments
    assert 25 < result["metrics"]["moe_absent_assign_pct.saturated"]["value"] < 75
    capture = obs.last_capture()
    mixed = hybrid.span_fields("serve.mixed", "ssm_rows", capture.spans)
    assert mixed and all(f["ssm_lines"] == 3 and 0 < f["ssm_rows"] <= 4 for f in mixed)
    assert capture.counters["serve_ssm_state_updates_total"] == 3 * sum(
        f["ssm_rows"] for f in mixed)
    emits = hybrid.span_fields("serve.emit", "absent_assign", capture.spans)
    assert len(emits) == len(mixed)
    # with a described peak the whole tick's share of it reads a small
    # number, from the counters and the spans alone
    ctx = {"device": {"peaks": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}},
           "config": cells.load_json(grown_hybrid / "configs" / "toy-nemotron.json"),
           "host": {"traced_context_tokens": 100}}
    assert 0 < hybrid.tick_mfu_pct(ctx) < 1.0


def test_a_plain_cell_reads_none_of_the_hybrid_metrics(run, grown, capsys):
    """A plain model's spans carry neither ``ssm_rows`` nor ``absent_assign``
    and its counters no absent assignments: the readers return nothing. What
    the parent commit's program gives under this PR's benchmark files."""
    from scaling_tpu import obs

    toy = Path(__file__).parent / "data" / "toy" / "BENCHMARK.json"
    run.main(["--workload", "toy-serve-burst", "--seed", "5", "--seconds", "1.5",
              "--trace", "2", "--rehearse", "--root", str(grown),
              "--benchmark-json", str(toy)])
    capture = obs.last_capture()
    assert any(n == "serve.mixed" for n, _, _, _ in capture.spans)
    ctx = {"device": {"peaks": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}},
           "config": {"transformer_architecture": {"num_layers": 2}},
           "host": {}, "trace": None}
    assert hybrid.tick_mfu_pct(ctx) is None and hybrid.moe_absent_assign_pct(ctx) is None
    assert hybrid.ssm_time_pct(ctx) is None and hybrid.ssm_state_roofline(ctx) is None
    assert hybrid.moe_held_roofline(ctx) is None


def test_a_token_altered_where_it_is_produced_is_not_correct(run, grown_hybrid, capsys,
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
    result = rehearse(run, grown_hybrid)
    assert result["failed"] == 0 and result["correct"] is False


def test_a_state_that_is_never_written_is_not_correct(run, grown_hybrid, capsys,
                                                     monkeypatch):
    """The program that drops the recurrent lines it computed (every tick
    starts from the lines as they were) serves tokens the harness refuses:
    the comparison sees the mechanism."""
    from scaling_tpu.nn import mamba

    real = mamba.Mamba2Mixer._serve
    monkeypatch.setattr(
        mamba.Mamba2Mixer, "_serve",
        lambda self, params, z, xBC, dt, view: (
            real(self, params, z, xBC, dt, view)[0], view))
    result = rehearse(run, grown_hybrid, workload="toy-serve-hybrid-chat", seconds="3")
    assert result["failed"] == 0 and result["correct"] is False


def test_the_control_fails_the_limit_the_program_keeps(run, grown_hybrid, capsys,
                                                       monkeypatch):
    """``--control fp8``: the reference with fp8 weights misses the limit that
    the program keeps with room."""
    seen = spy_on_the_kind(monkeypatch)
    result = rehearse(run, grown_hybrid, 0, "--control", "fp8",
                      workload="toy-serve-hybrid-chat", seconds="3")
    assert result["correct"]
    host = seen["outcome"]["host"]
    sound, control = host["worst_logit_gap"], host["control_logit_gap"]
    assert sound < serve_kind.LOGIT_TOL / 2 < serve_kind.LOGIT_TOL < control
    assert control > 3 * sound


# ---- the readers on recorded rows -----------------------------------------

SSM = "jit(mixed)/jit(_lambda_)/ssm"
MOE = "jit(mixed)/jit(_lambda_)/moe"
KERNEL = ('%paged_attention.3 = bf16[64,32,32,128] custom-call(...), '
          'custom_call_target="tpu_custom_call"')
# name, start_ns, dur_ns, op_name: what ``load_scoped_ops`` gives
OPS = [
    ["%fusion.1 = bf16[8,32,2688] fusion(...)", 0.0, 100e3, ""],            # embedding
    ["%fusion.11 = bf16[8,32,10304] fusion(...)", 100e3, 400e3, SSM + "/dot_general"],
    ["%fusion.12 = f32[64,64,64,128] fusion(...)", 500e3, 600e3, SSM + "/dot_general"],
    ["%fusion.13 = f32[64,64,64,128] fusion(...)", 900e3, 300e3, SSM + "/add"],  # overlaps
    ["%fusion.21 = bf16[64,8,32,1856] fusion(...)", 1200e3, 2500e3, MOE + "/bsec,bsh->ebch"],
    ["%fusion.22 = bf16[8,32,2688] fusion(...)", 3700e3, 300e3, MOE + "/dot_general"],
    [KERNEL, 4000e3, 200e3, "jit(mixed)/jit(_lambda_)/pallas_call"],
    ["%fusion.40 = f32[64,1,65536] fusion(...)", 4200e3, 500e3, ""],          # head
    ["%sort.5 = f32[64,65536] sort(...)", 4700e3, 300e3, ""],
]
SPANS = [
    ("serve.tick", 0, 30e6, {"step": 1}),
    ("serve.mixed", 0, 25e6, {"step": 1, "ssm_rows": 64, "ssm_lines": 7}),
    ("serve.emit", 26e6, 1e6, {"step": 1, "load_max": 12, "load_mean": 6.0,
                               "experts_idle": 2, "absent_assign": 1300}),
    ("serve.tick", 40e6, 50e6, {"step": 2}),
    ("serve.mixed", 40e6, 45e6, {"step": 2, "ssm_rows": 60, "ssm_lines": 7}),
    ("serve.emit", 86e6, 1e6, {"step": 2, "load_max": 9, "load_mean": 5.0,
                               "experts_idle": 0, "absent_assign": 1200}),
    ("serve.mixed", 95e6, 5e6, {"step": 3}),  # a tick of the warm-up: no field
]
COUNTERS = {"serve_moe_assignments_total": 2600,
            "serve_moe_absent_assignments_total": 2500,
            "serve_prefill_tokens_total": 64, "serve_tokens_generated_total": 60}
ARCH = cells.load_json(cells.ROOT / "configs" / f"{CONFIG}.json")["transformer_architecture"]
CTX = {"device": {"peaks": {"hbm_bytes_per_s": 819e9, "flops_per_s": 197e12}},
       "config": {"transformer_architecture": ARCH},
       "host": {"traced_context_tokens": 5000}}
H, F, FS = 2688, 1856, 3712
MAMBA_MATMULS = H * 10304 + 4096 * H


def test_readers_give_the_five_values_by_hand():
    assert hybrid_ops_count.mamba_dims(H, 64, 64, 128, 8) == (4096, 6144, 10304)
    assert hybrid_ops_count.mamba_matmul_params(H, 64, 64, 128, 8) == MAMBA_MATMULS
    # a Mamba-2 layer without its vectors: conv, conv bias, dt_bias, A, D, 2 norms
    assert MAMBA_MATMULS == 38_744_896 - (6144 * 4 + 6144 + 3 * 64 + 4096 + H)
    assert hybrid_ops_count.ssm_state_bytes(64, 64, 128) == 2 * 2**20
    assert hybrid.pattern_counts(ARCH) == {"mamba": 7, "moe": 7, "attention": 2}
    # times are unions: the two overlapping operations count 700 us, not 900
    assert hybrid.union_seconds(OPS) == pytest.approx(5.0e-3)
    assert hybrid.scope_seconds(OPS, "ssm") == pytest.approx(1.1e-3)
    assert hybrid.scope_seconds(OPS, "moe") == pytest.approx(2.8e-3)
    assert hybrid.ssm_time_pct(CTX, ops=OPS) == pytest.approx(100 * 1.1 / 5.0)
    # per tick 7 layers x (rows x 2 x 2 MiB + in_proj, out_proj and conv in bf16)
    weights = (MAMBA_MATMULS + 6144 * 5) * 2
    nbytes = 7 * ((64 + 60) * 2 * 2 * 2**20 + 2 * weights)
    assert hybrid.ssm_state_roofline(CTX, ops=OPS, spans=SPANS) == pytest.approx(
        100 * nbytes / 1.1e-3 / 819e9)
    # per tick 7 layers x (the held experts that had a token x 2 matrices +
    # the shared expert's 2 + the float32 router)
    layer = lambda read: read * 2 * H * F * 2 + 2 * H * FS * 2 + H * 128 * 4
    assert hybrid_ops_count.moe_layer_bytes(62, H, F, FS, 128, 2) == layer(62)
    assert hybrid.moe_held_roofline(CTX, ops=OPS, spans=SPANS) == pytest.approx(
        100 * 7 * (layer(62) + layer(64)) / 2.8e-3 / 819e9)
    assert hybrid.moe_absent_assign_pct(CTX, counters=COUNTERS) == pytest.approx(
        100 * 2500 / 5100)
    per_token = (7 * MAMBA_MATMULS + 2 * (2 * H * 4096 + 2 * H * 256)
                 + 7 * (H * 128 + 2 * H * FS))
    flops = (2.0 * (124 * per_token + 2600 * 2 * H * F + 60 * H * 65536)
             + 4.0 * 124 * 7 * 64 * 64 * 128 + 4.0 * 5000 * 32 * 128 * 2)
    assert hybrid_ops_count.serve_flops(
        124, 60, 2600, 5000, mamba_layers=7, moe_layers=7, attention_layers=2,
        hidden=H, vocab=65536, mamba=(64, 64, 128, 8), expert_width=F, shared_width=FS,
        num_experts=128, heads=32, kv_heads=2, head_dim=128) == flops
    assert hybrid.tick_mfu_pct(CTX, spans=SPANS, counters=COUNTERS) == pytest.approx(
        100 * flops / 0.080 / 197e12)


def test_without_the_scope_or_the_field_a_reader_gives_none_not_zero():
    bare = [[name, start, dur, ""] for name, start, dur, _ in OPS]
    assert hybrid.scope_seconds(bare, "ssm") is None
    assert hybrid.ssm_time_pct(CTX, ops=bare) is None and hybrid.ssm_time_pct(CTX, ops=[]) is None
    assert hybrid.ssm_state_roofline(CTX, ops=bare, spans=SPANS) is None
    assert hybrid.ssm_state_roofline(CTX, ops=OPS, spans=SPANS[6:]) is None
    assert hybrid.moe_held_roofline(CTX, ops=bare, spans=SPANS) is None
    # a model that holds all its experts has no absent_assign field
    whole = [(n, s, d, {k: v for k, v in f.items() if k != "absent_assign"})
             for n, s, d, f in SPANS]
    assert hybrid.moe_held_roofline(CTX, ops=OPS, spans=whole) is None
    assert hybrid.moe_absent_assign_pct(CTX, counters={}) is None
    assert hybrid.moe_absent_assign_pct(
        CTX, counters={"serve_moe_assignments_total": 9}) is None
    assert hybrid.tick_mfu_pct(CTX, spans=SPANS[6:], counters=COUNTERS) is None
    assert hybrid.tick_mfu_pct(CTX, spans=SPANS, counters={}) is None
    no_peak = {**CTX, "device": {"peaks": None}}
    assert hybrid.tick_mfu_pct(no_peak, spans=SPANS, counters=COUNTERS) is None
    assert hybrid.ssm_state_roofline(no_peak, ops=OPS, spans=SPANS) is None


def test_the_scopes_are_read_from_the_hlo_a_trace_carries(tmp_path):
    """A trace taken here, on the CPU, of a jitted function with an ``ssm``
    and a ``moe`` scope: the instructions compiled from inside either are
    found by name and told apart; the others not."""
    import jax
    import jax.numpy as jnp

    from benchmark import xplane_hlo

    @jax.jit
    def mixed(x, w):
        with jax.named_scope("ssm"):
            x = jnp.tanh(x @ w)
        with jax.named_scope("moe"):
            x = jnp.sin(x @ w)
        with jax.named_scope("neither"):
            return jnp.cos(x).sum()

    x = jnp.ones((64, 64))
    mixed(x, x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    mixed(x, x).block_until_ready()
    jax.profiler.stop_trace()
    trace = sorted(tmp_path.glob("**/*.xplane.pb"))[-1]
    modules = xplane_hlo.hlo_modules(trace.read_bytes())
    scopes = xplane_hlo.instruction_scopes(modules["jit_mixed"], hybrid.EITHER)
    assert any(hybrid.SCOPES["ssm"].search(op) for op in scopes.values())
    assert any(hybrid.SCOPES["moe"].search(op) for op in scopes.values())
    assert not any("neither" in op for op in scopes.values())
    assert not any(hybrid.SCOPES["ssm"].search(op) and hybrid.SCOPES["moe"].search(op)
                   for op in scopes.values())


def test_metric_files_name_the_readers_and_the_cell_lists_them():
    bench = json.loads((cells.REPO / "BENCHMARK.json").read_text())
    entries = {m["name"]: m for m in bench["per_layer"]}
    assert [m["name"] for m in bench["per_layer"][-len(HYBRID_METRICS):]] == list(
        HYBRID_METRICS)   # appended, in this order
    for name, layer in HYBRID_METRICS.items():
        spec = cells.load_json(cells.ROOT / "metrics" / f"{name}.json")
        assert spec["reader"].split(":")[0] == "hybrid" and spec["unit"] == entries[name]["unit"]
        assert entries[name]["layer"] == layer
        assert entries[name]["moves"] == "serve_tokens_per_s"
        assert entries[name]["workloads"] == [CELL]
        assert callable(cells.load_reader(name))
    # the cell reports what the Ouro burst cell reports but the looped trunk's
    # metrics, + the two readers of the routed MLP whose counts hold for a
    # share of un-gated experts, + its own five; NOT the paged kernel's share
    # (its reader counts num_layers cache lines, 16 where 2 exist) nor the
    # three-matrix expert roofline
    listed = {m["name"] for m in bench["per_layer"] if CELL in m["workloads"]}
    ouro = {m["name"] for m in bench["per_layer"]
            if "serve-ouro2.6b-reason-burst" in m["workloads"]}
    looped = {"paged_roofline.looped", "loop_time_pct.saturated",
              "loop_weights_roofline.saturated", "loop_steps_run_mean.saturated",
              "tick_mfu_pct.saturated"}
    assert listed == (ouro - looped) | set(HYBRID_METRICS) | {
        "moe_time_pct.saturated", "moe_load_max_over_mean.saturated"}
    assert not any("paged_roofline" in name for name in listed)
    assert "moe_weights_roofline.saturated" not in listed
    for m in bench["end_to_end"] + bench["per_layer"]:
        if CELL in m.get("workloads", ()):
            assert m["workloads"][-1] == CELL   # appended
    cell = cells.load_cell(CELL)
    assert cell.reference_name == "hybrid_ssm_decoder" and cell.chips == 1
    assert [m["name"] for m in cell.metrics("end_to_end")] == ["setup_s", "serve_tokens_per_s"]
    assert bench["workloads"][-1]["name"] == CELL and bench["configs"][-1]["name"] == CONFIG


def test_the_cell_resolves_to_its_reference_view_and_generator():
    """What ``test_files_by_name.py`` asks of every cell (its table of
    references is from before this configuration)."""
    cell = cells.load_cell(CELL)
    for name in cells.REFERENCE_CONTRACT:
        assert callable(getattr(cell.reference, name))
    for name in cells.VIEW_CONTRACT:
        assert callable(getattr(cell.view, name))
    assert Path(cell.reference.__file__).stem == Path(cell.view.__file__).stem \
        == cell.config["reference"] == "hybrid_ssm_decoder"
    bursts = cells.load_module(cells.ROOT, "generators", "bursts",
                               cells.GENERATOR_CONTRACT).generate
    assert cell.generate.__code__.co_code == bursts.__code__.co_code
    # the reference takes nothing of the program
    source = Path(cell.reference.__file__).read_text()
    assert "scaling_tpu" not in source.split('"""', 2)[2]


def test_the_configuration_names_every_key_it_changed_and_cuts_no_width():
    """What ``test_configs.py`` asks of every configuration, for one whose
    keys are config.json's own (a share of the experts and of the vocabulary
    are cuts its table does not know)."""
    config = cells.load_json(cells.ROOT / "configs" / f"{CONFIG}.json")
    entry = next(c for c in json.loads((cells.REPO / "BENCHMARK.json").read_text())["configs"]
                 if c["name"] == CONFIG)
    published, reduced, arch = config["published"], config["reduced"], ARCH
    assert sorted(entry["reduced"]) == sorted(reduced) == sorted(
        ["num_hidden_layers", "n_routed_experts", "vocab_size", "max_position_embeddings"])
    for key, value in published.items():
        if key == "parameter_count":
            continue
        if key in reduced:
            assert reduced[key]["published"] == value and reduced[key]["run"] == config[key] != value
        else:
            assert config[key] == value, f"{key} differs and is not in reduced"
    for key in reduced:   # a width is never cut
        assert not key.endswith(("_dim", "_rank")) and "intermediate" not in key
        assert key not in ("hidden_size", "ssm_state_size", "num_experts_per_tok")
    # the program runs what the file states, width for width
    as_run = {
        "hidden_size": arch["hidden_size"], "num_hidden_layers": arch["num_layers"],
        "num_attention_heads": arch["num_attention_heads"],
        "num_key_value_heads": arch["attention_num_kv_heads"],
        "head_dim": arch["attention_head_dim"], "mamba_num_heads": arch["mamba_num_heads"],
        "mamba_head_dim": arch["mamba_head_dim"], "ssm_state_size": arch["ssm_state_size"],
        "n_groups": arch["n_groups"], "conv_kernel": arch["conv_kernel"],
        "moe_intermediate_size": arch["moe_expert_width"],
        "moe_shared_expert_intermediate_size": arch["moe_shared_expert_width"],
        "n_routed_experts": arch["moe_experts_held"],
        "num_experts_per_tok": arch["moe_top_k"],
        "routed_scaling_factor": arch["moe_routed_scaling_factor"],
        "norm_topk_prob": arch["moe_norm_topk_prob"], "vocab_size": arch["vocab_size"],
        "max_position_embeddings": arch["sequence_length"],
        "norm_eps": arch["layernorm"]["layernorm_epsilon"],
        "mlp_hidden_act": arch["activation_function"],
        "tie_word_embeddings": arch["weight_tying"],
        "time_step_min": arch["time_step_min"], "time_step_max": arch["time_step_max"],
        "time_step_floor": arch["time_step_floor"],
    }
    assert {key: config[key] for key in as_run} == as_run
    assert arch["moe_num_experts"] == published["n_routed_experts"] == 128   # the router
    kinds = {"M": "mamba", "E": "moe", "*": "attention"}
    assert arch["layer_pattern"] == [kinds[c] for c in published["hybrid_override_pattern"][:16]]
    assert "2 chips" in config["stands_for"]
    assert {"positions", "block", "mamba_inner", "gated_norm", "router", "state",
            "init"} <= set(config["assumed"])
    assert config["engine"] == {"num_slots": 64, "context": 640,
                                "enable_prefix_cache": False}


def test_the_traffic_is_the_issues_and_fits_the_slots():
    """``reason-burst64``: 64 at once every whole second the rate rule gives,
    ``reason-burst16``'s lengths to the digit; no request asks for more than a
    slot's 640 positions or names a token outside the held vocabulary."""
    traffic = cells.load_json(cells.ROOT / "traffic" / "reason-burst64.json")
    ouro = cells.load_json(cells.ROOT / "traffic" / "reason-burst16.json")
    assert (traffic["generator"], traffic["backlog"], traffic["burst_size"],
            traffic["shape_seed"]) == ("bursts", "cut", 64, 46)
    assert traffic["burst_every_s"] == int(traffic["burst_every_s"]) <= 8
    for key in ("prompt", "output", "max_total", "warm_seconds", "check_requests",
                "check_max_tokens", "trace_seconds"):
        assert traffic[key] == ouro[key]
    assert "tokens" not in traffic   # above the knee: whatever the engine completes
    config = cells.load_json(cells.ROOT / "configs" / f"{CONFIG}.json")
    context = config["engine"]["context"]
    assert traffic["max_total"] == traffic["check_max_tokens"] == context == 640
    vocab = config["transformer_architecture"]["vocab_size"]
    requests = cells.load_cell(CELL).generate(traffic, 2**31 + 5, 51.0, vocab)
    counted = [r for r in requests if r.due_s >= 0]
    assert len(counted) == 64 * len({r.due_s for r in counted})
    assert sum(r.due_s < 0 for r in requests) == 64     # one uncounted burst
    assert max(len(r.prompt) + r.output_len for r in requests) <= context
    assert min(r.output_len for r in requests) >= 64
    assert all(1 <= t < vocab for r in requests[:64] for t in r.prompt)
