"""What PR 52 brings for ``falcon-h1-34b-serve`` as files (``reference/`` and
``views/parallel_hybrid_decoder.py``, ``readers/parallel_hybrid.py``,
``parallel_hybrid_ops_count.py``, four metrics, ``traffic/reason-burst96.json``),
rehearsed on the CPU at a toy width through a copy of ``benchmark/`` into
which only a toy configuration is added; and the readers on recorded rows.
Membership is pinned, never position: the next configuration's PR appends
after these entries."""

import json
import shutil
from pathlib import Path

import pytest

from benchmark import cells, hybrid_ops_count, parallel_hybrid_ops_count, serve_kind
from benchmark.readers import hybrid, parallel_hybrid

TOY = Path(__file__).parent / "data" / "toy_parhybrid"
BENCH = TOY / "BENCHMARK.json"
CELL = "serve-falconh1-34b-reason-burst"
CONFIG = "falcon-h1-34b-serve"
TRAFFIC = "reason-burst96"
NEMOTRON, LFM2 = "serve-nemotron3nano-reason-burst", "serve-lfm2-24b-reason-burst"
METRICS = {
    "parmix_time_pct.saturated": ("parallel mixers", "device_trace", "parmix_time_pct"),
    "mlp_time_pct.saturated": ("dense MLP", "device_trace", "mlp_time_pct"),
    "head_time_pct.saturated": ("head and sampler", "device_trace", "head_time_pct"),
    "tick_mfu_pct.parhybrid": ("engine tick", "program_counter", "tick_mfu_pct"),
}
SSM = {"ssm_time_pct.saturated", "ssm_state_roofline.saturated"}


@pytest.fixture(scope="module")
def grown_parhybrid(grown):
    """``grown`` (the toy burst traffic is there) plus the one toy
    configuration and its chat traffic; reference, view, readers and
    metrics are the benchmark's own."""
    shutil.copy(TOY / "configs" / "toy-falconh1.json", grown / "configs")
    shutil.copy(TOY / "traffic" / "toy-parhybrid-chat.json", grown / "traffic")
    for part, name in (("reference", "parallel_hybrid_decoder.py"),
                       ("views", "parallel_hybrid_decoder.py"),
                       ("readers", "parallel_hybrid.py")):
        assert (cells.ROOT / part / name).is_file() and (grown / part / name).is_file()
    return grown


def rehearse(run, root, trace=0, *more, workload="toy-serve-parhybrid", seconds="1.5"):
    return run.main(["--workload", workload, "--seed", "3000000019",
                     "--seconds", seconds, "--trace", str(trace), "--rehearse",
                     "--root", str(root), "--benchmark-json", str(BENCH), *more])


def spy_on_the_kind(monkeypatch):
    seen = {}
    real = serve_kind.run
    monkeypatch.setattr(serve_kind, "run", lambda cell, args, env: seen.setdefault(
        "outcome", real(cell, args, env)))
    return seen


def test_the_toy_states_the_published_constants():
    toy = cells.load_json(TOY / "configs" / "toy-falconh1.json")["transformer_architecture"]
    real = cells.load_json(cells.ROOT / "configs" / f"{CONFIG}.json")["transformer_architecture"]
    assert toy["multipliers"] == real["multipliers"] and toy["parallel_ssm"] is True
    assert toy["num_attention_heads"] // toy["attention_num_kv_heads"] == 5 == (
        real["num_attention_heads"] // real["attention_num_kv_heads"])
    assert toy["rotary_embedding_base"] == real["rotary_embedding_base"] == 10 ** 11


def test_parallel_hybrid_serve_cell_is_correct_and_reads_both_kinds_of_lines(
        run, grown_parhybrid, capsys, monkeypatch):
    """The engine serves the stack through the paged line and the recurrent
    line of every layer, every checked token on the reference's best logit
    (float32 on both sides at this width: the configuration says why); the
    traced part's ticks carry ``par_lines`` and the rows whose state advanced."""
    from scaling_tpu import obs

    seen = spy_on_the_kind(monkeypatch)
    result = rehearse(run, grown_parhybrid, trace=2)
    assert result["correct"] and result["failed"] == 0 and result["unserved"] > 0
    assert seen["outcome"]["host"]["worst_logit_gap"] < 1e-3
    # the CPU has no device plane and no published peak: the readers of the
    # trace and of the peak find nothing and are left out
    assert set(result["metrics"]) == {"setup_s", "serve_tokens_per_s",
                                      "batch_occupancy_pct"}
    capture = obs.last_capture()
    mixed = hybrid.span_fields("serve.mixed", "par_lines", capture.spans)
    assert mixed and all(f["par_lines"] == f["ssm_lines"] == 3 for f in mixed)
    assert all(0 < f["ssm_rows"] <= 4 for f in mixed)
    assert capture.counters["serve_parallel_mixer_passes_total"] == 3 * len(mixed)
    assert capture.counters["serve_ssm_state_updates_total"] == 3 * sum(
        f["ssm_rows"] for f in mixed)
    # with a described peak the whole tick's share of it reads a small
    # number, from the counters and the spans alone
    ctx = {"device": {"peaks": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}},
           "config": cells.load_json(grown_parhybrid / "configs" / "toy-falconh1.json"),
           "host": {"traced_context_tokens": 100}}
    assert 0 < parallel_hybrid.tick_mfu_pct(ctx) < 1.0


def test_a_plain_cell_reads_none_of_the_new_metrics(run, grown, capsys):
    """A plain model's spans carry no ``par_lines``: the readers return
    nothing, whatever its trace's scopes. What the parent commit's program
    gives under this PR's benchmark files."""
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
    for reader in METRICS.values():
        assert getattr(parallel_hybrid, reader[2])(ctx) is None
    assert parallel_hybrid.parmix_time_pct(ctx, ops=OPS) is None   # scopes, no field


def test_a_token_altered_where_the_engine_produces_it_is_not_correct(
        run, grown_parhybrid, capsys, monkeypatch):
    """The trap of this model's multipliers: over plain Xavier weights
    ``lm_head_multiplier`` alone would put every gap between logits under the
    harness's 0.05 and ANY token would pass. With this configuration's init
    an engine that emits the token beside the best one every 7th position is
    refused."""
    import jax.numpy as jnp

    from scaling_tpu.serve.engine import ServeEngine

    real = ServeEngine._sample_grid

    def off_by_one(self, logits, *rest):
        sampled = real(self, logits, *rest)
        rows = jnp.arange(sampled.shape[0])[:, None]
        return jnp.where(rows % 7 == 3, (sampled + 1) % logits.shape[-1], sampled)

    monkeypatch.setattr(ServeEngine, "_sample_grid", off_by_one)
    seen = spy_on_the_kind(monkeypatch)
    result = rehearse(run, grown_parhybrid, workload="toy-serve-parhybrid-chat",
                      seconds="3")
    assert result["failed"] == 0 and result["correct"] is False
    assert seen["outcome"]["host"]["worst_logit_gap"] > 4 * serve_kind.LOGIT_TOL


def test_the_control_fails_the_limit_the_program_keeps(run, grown_parhybrid, capsys,
                                                       monkeypatch):
    """``--control fp8``: the reference with fp8 weights misses the limit that
    the program keeps with room."""
    seen = spy_on_the_kind(monkeypatch)
    result = rehearse(run, grown_parhybrid, 0, "--control", "fp8",
                      workload="toy-serve-parhybrid-chat", seconds="3")
    assert result["correct"]
    host = seen["outcome"]["host"]
    sound, control = host["worst_logit_gap"], host["control_logit_gap"]
    assert sound < serve_kind.LOGIT_TOL / 2 < serve_kind.LOGIT_TOL < control
    assert control > 3 * sound


# ---- the readers on recorded rows -----------------------------------------

LAYER = "jit(mixed)/jit(_lambda_)/"
KERNEL = ('%paged_attention.3 = bf16[96,4,40,128] custom-call(...), '
          'custom_call_target="tpu_custom_call"')
# name, start_ns, dur_ns, op_name: what ``load_scoped_ops`` gives
OPS = [
    ["%fusion.1 = bf16[8,32,5120] fusion(...)", 0.0, 100e3, ""],                 # embedding
    ["%fusion.11 = bf16[8,32,2560] fusion(...)", 100e3, 300e3, LAYER + "attn/dot_general"],
    [KERNEL, 400e3, 200e3, LAYER + "attn/pallas_call"],
    ["%fusion.12 = bf16[8,32,9248] fusion(...)", 600e3, 400e3, LAYER + "ssm/dot_general"],
    ["%multiply_reduce_fusion = (f32[96,32,128], f32[96,32,128,256]) fusion(...)",
     900e3, 1300e3, LAYER + "ssm/mul"],                                          # overlaps
    ["%fusion.15 = bf16[8,32,21504] fusion(...)", 2200e3, 1000e3, LAYER + "mlp/dot_general"],
    ["%fusion.40 = bf16[96,261120] fusion(...)", 3200e3, 700e3, "jit(mixed)/head/dot_general"],
    ["%fusion.41 = s32[96] fusion(...)", 3900e3, 100e3, "jit(mixed)/head/cond/argmax"],
    ["%copy.3 = s32[96] copy(...)", 4000e3, 200e3, ""],
]
SPANS = [
    ("serve.tick", 0, 15e6, {"step": 1}),
    ("serve.mixed", 0, 12e6, {"step": 1, "par_lines": 5, "ssm_rows": 96, "ssm_lines": 5}),
    ("serve.tick", 20e6, 25e6, {"step": 2}),
    ("serve.mixed", 20e6, 22e6, {"step": 2, "par_lines": 5, "ssm_rows": 90, "ssm_lines": 5}),
    ("serve.mixed", 50e6, 5e6, {"step": 3}),  # a tick of the warm-up: no field
]
COUNTERS = {"serve_prefill_tokens_total": 64, "serve_tokens_generated_total": 186}
ARCH = cells.load_json(cells.ROOT / "configs" / f"{CONFIG}.json")["transformer_architecture"]
CTX = {"device": {"peaks": {"hbm_bytes_per_s": 819e9, "flops_per_s": 197e12}},
       "config": {"transformer_architecture": ARCH},
       "host": {"traced_context_tokens": 30000}}
H, F, V, L = 5120, 21504, 261120, 5
MAMBA = (32, 128, 256, 2)


def test_readers_give_the_four_values_by_hand():
    # times are unions: the two overlapping ssm operations count 1.6 ms, not 1.7
    assert parallel_hybrid.union_seconds(OPS) == pytest.approx(4.2e-3)
    assert parallel_hybrid.parmix_time_pct(CTX, ops=OPS, spans=SPANS) == pytest.approx(
        100 * (0.5 + 1.6) / 4.2)
    assert parallel_hybrid.mlp_time_pct(CTX, ops=OPS, spans=SPANS) == pytest.approx(
        100 * 1.0 / 4.2)
    assert parallel_hybrid.head_time_pct(CTX, ops=OPS, spans=SPANS) == pytest.approx(
        100 * 0.8 / 4.2)
    # the same yardstick as Nemotron's cell reads the state at THIS shape
    assert hybrid.mamba_shape(ARCH) == MAMBA
    assert hybrid_ops_count.ssm_state_bytes(32, 128, 256) == 4 * 1024 * 1024
    assert hybrid.ssm_time_pct(CTX, ops=OPS) == pytest.approx(100 * 1.6 / 4.2)
    weights = (H * 9248 + 4096 * H + 5120 * 5) * 2
    nbytes = 5 * (2 * weights + (96 + 90) * 2 * 4 * 1024 * 1024)
    assert hybrid.ssm_state_roofline(CTX, ops=OPS, spans=SPANS) == pytest.approx(
        100 * nbytes / 1.6e-3 / 819e9)
    # a block's matrices: the per-kind counts of the configuration's file
    block = parallel_hybrid_ops_count.block_matmul_params(H, F, MAMBA, 20, 4, 128)
    assert block == 31_457_280 + (47_349_760 + 20_971_520) + 330_301_440
    flops = (2.0 * (250 * L * block + 186 * H * V) + 4.0 * 250 * L * 32 * 128 * 256
             + 4.0 * 30000 * 20 * 128 * L)
    assert parallel_hybrid_ops_count.serve_flops(
        250, 186, 30000, layers=L, hidden=H, vocab=V, mlp_width=F, mamba=MAMBA,
        heads=20, kv_heads=4, head_dim=128) == flops
    assert parallel_hybrid.tick_mfu_pct(CTX, spans=SPANS, counters=COUNTERS) == pytest.approx(
        100 * flops / 0.040 / 197e12)


def test_no_share_can_pass_one_hundred_on_what_the_chip_can_do():
    """A time share is a union inside a union; the tick's FLOPs are counted so
    that a tick whose whole time is those FLOPs at the peak reads 100; the
    state's bytes so that a scope moving them at the published rate does."""
    everything = [[n, s, d, LAYER + "ssm/x"] for n, s, d, _ in OPS]
    assert parallel_hybrid.parmix_time_pct(CTX, ops=everything, spans=SPANS) == pytest.approx(100)
    for reader in (parallel_hybrid.parmix_time_pct, parallel_hybrid.mlp_time_pct,
                   parallel_hybrid.head_time_pct):
        assert 0 < reader(CTX, ops=OPS, spans=SPANS) < 100
    flops = parallel_hybrid_ops_count.serve_flops(
        96, 96, 96 * 400, layers=L, hidden=H, vocab=V, mlp_width=F, mamba=MAMBA,
        heads=20, kv_heads=4, head_dim=128)
    tick = [("serve.tick", 0, 1e9 * flops / 197e12, {"step": 1}), SPANS[1]]
    ctx = {**CTX, "host": {"traced_context_tokens": 96 * 400}}
    assert parallel_hybrid.tick_mfu_pct(
        ctx, spans=tick, counters={"serve_tokens_generated_total": 96}) == pytest.approx(100.0)
    # a decode token's required FLOPs: twice the 2.15 B block parameters and
    # the 1.34 B of the head it meets in the cut
    assert 2 * 3.4e9 < flops / 96 < 2 * 3.6e9
    ticks = [SPANS[1]]
    nbytes = 5 * hybrid_ops_count.ssm_layer_bytes(96, H, *MAMBA, 4, 2)
    assert nbytes == pytest.approx(4.03e9 + 0.68e9, rel=2e-3)   # lines + the mixers' weights
    at_the_rate = [["%fusion.1 = ...", 0.0, 1e9 * nbytes / 819e9, LAYER + "ssm/x"]]
    assert hybrid.ssm_state_roofline(CTX, ops=at_the_rate, spans=ticks) == pytest.approx(100.0)


def test_without_the_scope_or_the_field_a_reader_gives_none_not_zero():
    bare = [[name, start, dur, ""] for name, start, dur, _ in OPS]
    no_field = SPANS[4:]
    for name in ("parmix_time_pct", "mlp_time_pct", "head_time_pct"):
        reader = getattr(parallel_hybrid, name)
        assert reader(CTX, ops=bare, spans=SPANS) is None
        assert reader(CTX, ops=[], spans=SPANS) is None
        assert reader(CTX, ops=OPS, spans=no_field) is None
    assert parallel_hybrid.tick_mfu_pct(CTX, spans=no_field, counters=COUNTERS) is None
    assert parallel_hybrid.tick_mfu_pct(CTX, spans=SPANS, counters={}) is None
    no_peak = {**CTX, "device": {"peaks": None}}
    assert parallel_hybrid.tick_mfu_pct(no_peak, spans=SPANS, counters=COUNTERS) is None
    # an `attn` that is part of a longer name is no scope
    near = [["%f = ...", 0.0, 1e3, "jit(mixed)/attn_out/mul"], ["%g = ...", 1e3, 1e3, ""]]
    assert parallel_hybrid.parmix_time_pct(CTX, ops=near, spans=SPANS) is None


def test_the_scopes_are_read_from_the_hlo_a_trace_carries(tmp_path):
    """A trace taken here, on the CPU, of a jitted function with the four
    scopes and one more: the instructions compiled from inside each are found
    by name and told apart."""
    import jax
    import jax.numpy as jnp

    from benchmark import xplane_hlo

    @jax.jit
    def mixed(x, w):
        with jax.named_scope("attn"):
            a = jnp.tanh(x @ w)
        with jax.named_scope("ssm"):
            s = jnp.sin(x @ w)
        x = a + s
        with jax.named_scope("mlp"):
            x = jnp.cos(x @ w)
        with jax.named_scope("conv"):
            x = jnp.exp(-x @ w)
        with jax.named_scope("head"):
            return jnp.argmax(x @ w, axis=-1)

    x = jnp.ones((64, 64))
    mixed(x, x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    mixed(x, x).block_until_ready()
    jax.profiler.stop_trace()
    trace = sorted(tmp_path.glob("**/*.xplane.pb"))[-1]
    modules = xplane_hlo.hlo_modules(trace.read_bytes())
    scopes = xplane_hlo.instruction_scopes(modules["jit_mixed"], parallel_hybrid.ANY)
    for pattern in parallel_hybrid.SCOPES.values():
        assert any(pattern.search(op) for op in scopes.values())
    assert not any("conv" in op for op in scopes.values())
    assert not any(sum(bool(p.search(op)) for p in parallel_hybrid.SCOPES.values()) > 1
                   for op in scopes.values())


# ---- the files, by name and by membership ---------------------------------

def test_metric_files_name_the_readers_and_the_cell_lists_them():
    bench = json.loads((cells.REPO / "BENCHMARK.json").read_text())
    entries = {m["name"]: m for m in bench["per_layer"]}
    assert set(METRICS) <= set(entries)
    for name, (layer, source, reader) in METRICS.items():
        spec = cells.load_json(cells.ROOT / "metrics" / f"{name}.json")
        assert spec["reader"] == f"parallel_hybrid:{reader}"
        assert spec["unit"] == entries[name]["unit"] == "%"
        assert (entries[name]["layer"], entries[name]["source"]) == (layer, source)
        assert entries[name]["moves"] == "serve_tokens_per_s"
        assert entries[name]["workloads"] == [CELL]
        assert callable(cells.load_reader(name))
    # the cell reports what BOTH other state-keeping cells report but the
    # routed MLP's, + the two readers of the Mamba-2 state (the SAME yardstick
    # as Nemotron's cell, at this shape), + its own four; NOT the paged
    # kernel's share (its reader takes a head as hidden / heads = 256 where
    # this model's is 128), nor any `moe_*`, `conv_*`, `loop_*` list or
    # another model's `tick_mfu_pct.*`
    listed = {m["name"] for m in bench["per_layer"] if CELL in m["workloads"]}
    both = {m["name"] for m in bench["per_layer"]
            if NEMOTRON in m["workloads"] and LFM2 in m["workloads"]}
    shared = {name for name in both if not name.startswith("moe_")}
    assert len(shared) == 22
    assert listed == shared | SSM | set(METRICS)
    assert not any(name.startswith(("moe_", "conv_", "loop_", "paged_roofline"))
                   for name in listed)
    assert {n for n in listed if n.startswith("tick_mfu_pct")} == {"tick_mfu_pct.parhybrid"}
    # appended: wherever this cell and Nemotron's are listed, this one comes after
    for m in bench["end_to_end"] + bench["per_layer"]:
        cells_of = m.get("workloads", [])
        if CELL in cells_of and NEMOTRON in cells_of:
            assert cells_of.index(CELL) > cells_of.index(NEMOTRON)
    cell = cells.load_cell(CELL)
    assert cell.reference_name == "parallel_hybrid_decoder" and cell.chips == 1
    assert [m["name"] for m in cell.metrics("end_to_end")] == ["setup_s", "serve_tokens_per_s"]
    names = [w["name"] for w in bench["workloads"]]
    configs = [c["name"] for c in bench["configs"]]
    assert names.index(CELL) > names.index(LFM2)
    assert configs.index(CONFIG) > configs.index("lfm2-24b-a2b-serve")
    entry = bench["workloads"][names.index(CELL)]
    assert (entry["config"], entry["traffic"], entry["chips"]) == (CONFIG, TRAFFIC, 1)
    assert len(entry["why"]) <= 200 and "head" in entry["why"]
    assert len(names) <= 24 and sum(w["chips"] == 4 for w in bench["workloads"]) == 1


def test_the_cell_resolves_to_its_reference_view_and_generator():
    """What ``test_files_by_name.py`` asks of every cell (its table of
    references is from before this configuration)."""
    cell = cells.load_cell(CELL)
    for name in cells.REFERENCE_CONTRACT:
        assert callable(getattr(cell.reference, name))
    for name in cells.VIEW_CONTRACT:
        assert callable(getattr(cell.view, name))
    assert Path(cell.reference.__file__).stem == Path(cell.view.__file__).stem \
        == cell.config["reference"] == "parallel_hybrid_decoder"
    bursts = cells.load_module(cells.ROOT, "generators", "bursts",
                               cells.GENERATOR_CONTRACT).generate
    assert cell.generate.__code__.co_code == bursts.__code__.co_code
    spec = cell.view.reference_spec(ARCH)
    assert (spec["num_heads"], spec["num_kv_heads"], spec["head_dim"]) == (20, 4, 128)
    with pytest.raises(SystemExit, match="the configuration states {'parallel_ssm': None"):
        cell.view.reference_spec({k: v for k, v in ARCH.items() if k != "parallel_ssm"})


def test_the_configuration_names_every_key_it_changed_and_cuts_no_width():
    """What ``test_configs.py`` asks of every configuration, for one whose
    keys are config.json's own (its table knows dense keys only)."""
    config = cells.load_json(cells.ROOT / "configs" / f"{CONFIG}.json")
    bench = json.loads((cells.REPO / "BENCHMARK.json").read_text())
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert entry["source"] == config["source"] == (
        "https://huggingface.co/tiiuae/Falcon-H1-34B-Instruct/blob/main/config.json")
    published, reduced, arch = config["published"], config["reduced"], ARCH
    assert sorted(entry["reduced"]) == sorted(reduced) == sorted(
        ["num_hidden_layers", "max_position_embeddings"])
    for key, value in published.items():
        if key == "parameter_count":
            continue
        if key in reduced:
            assert reduced[key]["published"] == value and reduced[key]["run"] == config[key] != value
        else:
            assert config[key] == value, f"{key} differs and is not in reduced"
    # the program runs what the file states, width for width and constant for
    # constant: every head, group and vocabulary row
    mult = arch["multipliers"]
    as_run = {
        "hidden_size": arch["hidden_size"], "num_hidden_layers": arch["num_layers"],
        "num_attention_heads": arch["num_attention_heads"],
        "num_key_value_heads": arch["attention_num_kv_heads"],
        "head_dim": arch["attention_head_dim"],
        "intermediate_size": int(arch["hidden_size"] * arch["mlp_factor"]),
        "vocab_size": arch["vocab_size"],
        "max_position_embeddings": arch["sequence_length"],
        "mamba_n_heads": arch["mamba_num_heads"], "mamba_d_head": arch["mamba_head_dim"],
        "mamba_d_ssm": arch["mamba_num_heads"] * arch["mamba_head_dim"],
        "mamba_d_state": arch["ssm_state_size"], "mamba_n_groups": arch["n_groups"],
        "mamba_d_conv": arch["conv_kernel"],
        "rms_norm_eps": arch["layernorm"]["layernorm_epsilon"],
        "rope_theta": arch["rotary_embedding_base"],
        "tie_word_embeddings": arch["weight_tying"],
        "attention_bias": arch["attention_bias"], "mlp_bias": arch["mlp_bias"],
        "embedding_multiplier": mult["embedding"], "lm_head_multiplier": mult["lm_head"],
        "attention_in_multiplier": mult["attention_in"],
        "attention_out_multiplier": mult["attention_out"],
        "key_multiplier": mult["key"], "ssm_in_multiplier": mult["ssm_in"],
        "ssm_out_multiplier": mult["ssm_out"], "ssm_multipliers": mult["ssm"],
        "mlp_multipliers": [mult["mlp_gate"], mult["mlp_down"]],
    }
    assert {key: config[key] for key in as_run} == as_run
    assert arch["parallel_ssm"] is True and arch.get("layer_pattern") is None
    assert published["parameter_count"] == 33_642_516_224 == (
        72 * 430_120_032 + 2 * 261_120 * 5120 + 5120)
    assert "4,824,474,080" in reduced["num_hidden_layers"]["why"]
    assert "leading 5 of the 72 layers" in config["stands_for"]
    assert {"multipliers", "block", "mamba_inner", "gated_norm", "time_step", "state",
            "precision", "chunk_size", "init", "parameter_count"} <= set(config["assumed"])
    assert config["engine"] == {"num_slots": 96, "context": 640,
                                "enable_prefix_cache": False}
    assert config["chips"] == 1


def test_the_traffic_is_the_issues_and_fits_the_slots():
    """``reason-burst96``: 96 at once every whole second the rate rule gives,
    ``reason-burst64``'s lengths to the digit; no request asks for more than a
    slot's 640 positions or names a token outside the vocabulary."""
    traffic = cells.load_json(cells.ROOT / "traffic" / f"{TRAFFIC}.json")
    other = cells.load_json(cells.ROOT / "traffic" / "reason-burst64.json")
    assert (traffic["generator"], traffic["backlog"], traffic["burst_size"],
            traffic["shape_seed"]) == ("bursts", "cut", 96, 52)
    assert traffic["burst_every_s"] == int(traffic["burst_every_s"]) >= 2
    for key in ("prompt", "output", "max_total", "warm_seconds", "check_requests",
                "check_max_tokens", "trace_seconds", "kind"):
        assert traffic[key] == other[key]
    assert set(traffic) == set(other)
    assert "tokens" not in traffic   # above the knee: whatever the engine completes
    config = cells.load_json(cells.ROOT / "configs" / f"{CONFIG}.json")
    context = config["engine"]["context"]
    assert traffic["max_total"] == traffic["check_max_tokens"] == context == 640
    vocab = config["transformer_architecture"]["vocab_size"]
    requests = cells.load_cell(CELL).generate(traffic, 2**31 + 5, 51.0, vocab)
    counted = [r for r in requests if r.due_s >= 0]
    assert len(counted) == 96 * len({r.due_s for r in counted})
    assert sum(r.due_s < 0 for r in requests) == 96     # one uncounted burst
    assert max(len(r.prompt) + r.output_len for r in requests) <= context
    assert min(r.output_len for r in requests) >= 64
    assert all(1 <= t < vocab for r in requests[:96] for t in r.prompt)
    assert max(t for r in requests for t in r.prompt) > 65536   # the whole vocabulary
    # the steady state's offer, as the file's `why` reckons it
    offered = sum(r.output_len for r in counted) / 51.0
    assert offered > 0.9 * 96 * 269.7 / traffic["burst_every_s"]
