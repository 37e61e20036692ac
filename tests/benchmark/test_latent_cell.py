"""What PR 55 brings for ``kimi-k2-instruct-serve`` as files (``reference/``
and ``views/latent_moe_decoder.py``, ``readers/latent.py``,
``latent_ops_count.py``, three metrics, ``traffic/longdoc-burst32.json``),
rehearsed on the CPU at a toy width through a copy of ``benchmark/`` into
which only a toy configuration is added; and the readers on recorded rows.
Membership is pinned, never position: the next configuration's PR appends
after these entries."""

import json
import shutil
from pathlib import Path

import pytest

from benchmark import cells, latent_ops_count, serve_kind
from benchmark.readers import hybrid, latent

TOY = Path(__file__).parent / "data" / "toy_latent"
BENCH = TOY / "BENCHMARK.json"
CELL = "serve-kimik2-longdoc-burst"
CONFIG = "kimi-k2-instruct-serve"
TRAFFIC = "longdoc-burst32"
FALCON = "serve-falconh1-34b-reason-burst"
METRICS = {
    "latent_time_pct.saturated": ("latent attention", "device_trace", "latent_time_pct"),
    "latent_roofline.saturated": ("latent kernel", "device_trace", "latent_roofline"),
    "tick_mfu_pct.latent": ("engine tick", "program_counter", "tick_mfu_pct"),
}
MOE = {"moe_time_pct.saturated", "moe_load_max_over_mean.saturated"}


@pytest.fixture(scope="module")
def grown_latent(grown):
    """``grown`` (the toy burst traffic is there) plus the one toy
    configuration and its chat traffic; reference, view, readers and
    metrics are the benchmark's own."""
    shutil.copy(TOY / "configs" / "toy-kimik2.json", grown / "configs")
    shutil.copy(TOY / "traffic" / "toy-latent-chat.json", grown / "traffic")
    for part, name in (("reference", "latent_moe_decoder.py"),
                       ("views", "latent_moe_decoder.py"), ("readers", "latent.py")):
        assert (cells.ROOT / part / name).is_file() and (grown / part / name).is_file()
    return grown


def rehearse(run, root, trace=0, *more, workload="toy-serve-latent", seconds="1.5"):
    return run.main(["--workload", workload, "--seed", "3000000019",
                     "--seconds", seconds, "--trace", str(trace), "--rehearse",
                     "--root", str(root), "--benchmark-json", str(BENCH), *more])


def spy_on_the_kind(monkeypatch):
    seen = {}
    real = serve_kind.run
    monkeypatch.setattr(serve_kind, "run", lambda cell, args, env: seen.setdefault(
        "outcome", real(cell, args, env)))
    return seen


def test_the_toy_states_the_published_equations():
    toy = cells.load_json(TOY / "configs" / "toy-kimik2.json")["transformer_architecture"]
    real = cells.load_json(cells.ROOT / "configs" / f"{CONFIG}.json")["transformer_architecture"]
    for key in ("moe_router", "moe_routed_scaling_factor", "moe_norm_topk_eps",
                "rotary_embedding_base", "moe_glu", "mlp_type", "weight_tying"):
        assert toy[key] == real[key], key
    assert toy["layer_pattern"][:4] == real["layer_pattern"][:4] == [
        "latent", "mlp", "latent", "moe"]
    assert {k: v for k, v in toy["rope_scaling"].items()
            if k not in ("factor", "original_max_position_embeddings")} == {
        k: v for k, v in real["rope_scaling"].items()
        if k not in ("factor", "original_max_position_embeddings")}
    assert toy["moe_experts_held"] < toy["moe_num_experts"]


def test_latent_serve_cell_is_correct_and_its_ticks_carry_lines_and_pairs(
        run, grown_latent, capsys, monkeypatch):
    """The engine serves the stack through the latent pool (absorbed, the
    kernel interpreted), every checked token on the reference's (expanded)
    best logit; the traced part's ticks carry ``latent_layers``,
    ``latent_lines`` and ``latent_pairs``."""
    from scaling_tpu import obs

    seen = spy_on_the_kind(monkeypatch)
    result = rehearse(run, grown_latent, trace=2)
    assert result["correct"] and result["failed"] == 0 and result["unserved"] > 0
    assert seen["outcome"]["host"]["worst_logit_gap"] < 1e-3
    # the CPU has no device plane and no published peak: the readers of the
    # trace and of the peak find nothing and are left out
    assert set(result["metrics"]) == {"setup_s", "serve_tokens_per_s",
                                      "batch_occupancy_pct"}
    capture = obs.last_capture()
    mixed = hybrid.span_fields("serve.mixed", "latent_pairs", capture.spans)
    assert mixed and all(f["latent_layers"] == 3 for f in mixed)
    # a row of n new tokens over c cached lines reads c + n lines and holds
    # n c + n (n + 1) / 2 pairs: at least one pair a line, at most 32 (a chunk)
    assert all(0 < f["latent_lines"] <= f["latent_pairs"] <= 32 * f["latent_lines"]
               for f in mixed)
    assert capture.counters["serve_latent_lines_read_total"] == 3 * sum(
        f["latent_lines"] for f in mixed)
    ctx = {"device": {"peaks": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}},
           "config": cells.load_json(grown_latent / "configs" / "toy-kimik2.json"),
           "host": {}}
    assert 0 < latent.tick_mfu_pct(ctx) < 1.0


def test_a_plain_cell_reads_none_of_the_new_metrics(run, grown, capsys):
    """A plain model's spans carry no ``latent_pairs``: the readers return
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
        assert getattr(latent, reader[2])(ctx) is None
    assert latent.latent_time_pct(ctx, ops=OPS) is None   # scopes, no field


def test_a_token_altered_where_the_engine_produces_it_is_not_correct(
        run, grown_latent, capsys, monkeypatch):
    """An engine that emits the token beside the best one every 7th position
    is refused: the logits of this init are far enough apart."""
    import jax.numpy as jnp

    from scaling_tpu.serve.engine import ServeEngine

    real = ServeEngine._sample_grid

    def off_by_one(self, logits, *rest):
        sampled = real(self, logits, *rest)
        rows = jnp.arange(sampled.shape[0])[:, None]
        return jnp.where(rows % 7 == 3, (sampled + 1) % logits.shape[-1], sampled)

    monkeypatch.setattr(ServeEngine, "_sample_grid", off_by_one)
    seen = spy_on_the_kind(monkeypatch)
    result = rehearse(run, grown_latent, workload="toy-serve-latent-chat", seconds="3")
    assert result["failed"] == 0 and result["correct"] is False
    assert seen["outcome"]["host"]["worst_logit_gap"] > 4 * serve_kind.LOGIT_TOL


def test_the_control_fails_the_limit_the_program_keeps(run, grown_latent, capsys,
                                                       monkeypatch):
    """``--control fp8``: the reference with fp8 weights misses the limit that
    the program keeps with room."""
    seen = spy_on_the_kind(monkeypatch)
    result = rehearse(run, grown_latent, 0, "--control", "fp8",
                      workload="toy-serve-latent-chat", seconds="3")
    assert result["correct"]
    host = seen["outcome"]["host"]
    sound, control = host["worst_logit_gap"], host["control_logit_gap"]
    assert sound < serve_kind.LOGIT_TOL / 2 < serve_kind.LOGIT_TOL < control
    assert control > 3 * sound


# ---- the readers on recorded rows -----------------------------------------

LAYER = "jit(mixed_128)/jit(_lambda_)/"
KERNEL = ('%latent_paged_attention.3 = bf16[8192,512] custom-call(...), '
          'custom_call_target="tpu_custom_call"')
# name, start_ns, dur_ns, op_name: what ``load_scoped_ops`` gives
OPS = [
    ["%fusion.1 = bf16[128,7168] fusion(...)", 0.0, 100e3, ""],                  # embedding
    ["%fusion.11 = bf16[128,12288] fusion(...)", 100e3, 300e3, LAYER + "attn/dot_general"],
    [KERNEL, 400e3, 1200e3, LAYER + "attn/pallas_call"],
    ["%fusion.12 = bf16[128,7168] fusion(...)", 1500e3, 500e3, LAYER + "attn/dot_general"],
    ["%fusion.13 = f32[128,384] fusion(...)", 1900e3, 600e3, LAYER + "moe/dot_general"],  # overlaps
    ["%fusion.40 = bf16[32,20480] fusion(...)", 2500e3, 300e3, "jit(mixed_128)/head/dot_general"],
    ["%copy.3 = s32[32] copy(...)", 2800e3, 200e3, ""],
]
SPANS = [
    ("serve.tick", 0, 15e6, {"step": 1}),
    ("serve.mixed", 0, 12e6, {"step": 1, "latent_layers": 6, "latent_lines": 240_000,
                              "latent_pairs": 240_000}),          # 32 decode rows
    ("serve.tick", 20e6, 45e6, {"step": 2}),
    ("serve.mixed", 20e6, 42e6, {"step": 2, "latent_layers": 6, "latent_lines": 60_000,
                                 "latent_pairs": 1_900_000}),     # 16 chunk rows
    ("serve.mixed", 70e6, 5e6, {"step": 3}),  # a tick of the warm-up: no field
]
COUNTERS = {"serve_prefill_tokens_total": 512, "serve_tokens_generated_total": 32,
            "serve_moe_assignments_total": 700}
ARCH = cells.load_json(cells.ROOT / "configs" / f"{CONFIG}.json")["transformer_architecture"]
CTX = {"device": {"peaks": {"hbm_bytes_per_s": 819e9, "flops_per_s": 197e12}},
       "config": {"transformer_architecture": ARCH}, "host": {},
       "trace": {"class_s": {"pallas:latent_paged_attention": 2.4e-3,
                             "pallas:grouped_matmul": 1.0e-3, "other": 9.0}}}
SHAPE = dict(heads=64, kv_lora=512, nope=128, rope=64, v=128)


def test_the_counts_are_the_issues_by_hand():
    assert latent_ops_count.line_bytes(512, 64, 2) == 1152
    assert latent_ops_count.absorbed_flops(1, 64, 512, 64) == 2 * 64 * 1088 == 139_264
    assert latent_ops_count.expanded_flops(1, 0, **SHAPE) == 2 * 512 * 16_384
    assert latent_ops_count.expanded_flops(0, 1, **SHAPE) == 2 * 64 * 320
    # the two forms break even at rows of 171 queries: one line, q pairs
    def cheaper(q):
        return (latent_ops_count.absorbed_flops(q, 64, 512, 64)
                < latent_ops_count.expanded_flops(1, q, **SHAPE))
    assert cheaper(170) and not cheaper(171)
    assert latent_ops_count.attention_flops(10, 10 * 32, **SHAPE) == 139_264 * 320
    assert latent_ops_count.attention_matmul_params(7168, 64, 1536, 512, 128, 64, 128) \
        == 101_124_096 - 1536 - 512


def test_readers_give_the_three_values_by_hand():
    assert latent.union_seconds(OPS) == pytest.approx(3.0e-3)
    # the attn scope holds 0.3 + 1.2 + 0.5 ms; the kernel is inside it
    assert latent.latent_time_pct(CTX, ops=OPS, spans=SPANS) == pytest.approx(
        100 * 1.9 / 3.0)
    # tick 1 (decode rows) is bound by its bytes, tick 2 (chunks) by its FLOPs
    decode = max(139_264 * 240_000 / 197e12, 240_000 * 1152 / 819e9)
    chunks = max(139_264 * 1_900_000 / 197e12, 60_000 * 1152 / 819e9)
    assert decode == 240_000 * 1152 / 819e9 and chunks == 139_264 * 1_900_000 / 197e12
    assert latent.latent_roofline(CTX, spans=SPANS) == pytest.approx(
        100 * 6 * (decode + chunks) / 2.4e-3)
    per_token = (6 * (101_124_096 - 2048) + 3 * 7168 * 18_432
                 + 5 * (7168 * 384 + 3 * 7168 * 2048))
    flops = (2.0 * (544 * per_token + 700 * 3 * 7168 * 2048 + 32 * 7168 * 20_480)
             + 6 * 139_264 * 2_140_000)
    assert latent.tick_mfu_pct(CTX, spans=SPANS, counters=COUNTERS) == pytest.approx(
        100 * flops / 0.060 / 197e12)


def test_no_share_can_pass_one_hundred_on_what_the_chip_can_do():
    everything = [[n, s, d, LAYER + "attn/x"] for n, s, d, _ in OPS]
    assert latent.latent_time_pct(CTX, ops=everything, spans=SPANS) == pytest.approx(100)
    # a kernel that moves a decode tick's lines at the published rate reads 100
    tick = [SPANS[1]]
    at_the_rate = {**CTX, "trace": {"class_s": {
        "pallas:latent_paged_attention": 6 * 240_000 * 1152 / 819e9}}}
    assert latent.latent_roofline(at_the_rate, spans=tick) == pytest.approx(100.0)
    # and one that multiplies a chunk tick's pairs at the peak, in the cheaper form
    tick = [SPANS[3]]
    at_the_peak = {**CTX, "trace": {"class_s": {
        "pallas:latent_paged_attention": 6 * 139_264 * 1_900_000 / 197e12}}}
    assert latent.latent_roofline(at_the_peak, spans=tick) == pytest.approx(100.0)
    flops = latent_ops_count.serve_flops(
        32, 32, 20, 240_000, 240_000, latent_layers=6, dense_layers=1, routed_layers=5,
        hidden=7168, vocab=20_480, dense_width=18_432, expert_width=2048,
        shared_width=2048, num_experts=384,
        attention=dict(SHAPE, q_lora=1536))
    spans = [("serve.tick", 0, 1e9 * flops / 197e12, {"step": 1}), SPANS[1]]
    assert latent.tick_mfu_pct(CTX, spans=spans, counters={
        "serve_tokens_generated_total": 32,
        "serve_moe_assignments_total": 20}) == pytest.approx(100.0)


def test_without_the_scope_the_kernel_or_the_field_a_reader_gives_none_not_zero():
    bare = [[name, start, dur, ""] for name, start, dur, _ in OPS]
    no_field = SPANS[4:]
    assert latent.latent_time_pct(CTX, ops=bare, spans=SPANS) is None
    assert latent.latent_time_pct(CTX, ops=[], spans=SPANS) is None
    assert latent.latent_time_pct(CTX, ops=OPS, spans=no_field) is None
    assert latent.latent_roofline(CTX, spans=no_field) is None
    assert latent.latent_roofline({**CTX, "trace": None}, spans=SPANS) is None
    other_kernel = {**CTX, "trace": {"class_s": {"pallas:paged_attention": 1.0}}}
    assert latent.latent_roofline(other_kernel, spans=SPANS) is None
    assert latent.tick_mfu_pct(CTX, spans=no_field, counters=COUNTERS) is None
    assert latent.tick_mfu_pct(CTX, spans=SPANS, counters={}) is None
    no_peak = {**CTX, "device": {"peaks": None}}
    assert latent.tick_mfu_pct(no_peak, spans=SPANS, counters=COUNTERS) is None
    assert latent.latent_roofline(no_peak, spans=SPANS) is None
    near = [["%f = ...", 0.0, 1e3, "jit(mixed)/attn_out/mul"], ["%g = ...", 1e3, 1e3, ""]]
    assert latent.latent_time_pct(CTX, ops=near, spans=SPANS) is None


# ---- the files, by name and by membership ---------------------------------

def test_metric_files_name_the_readers_and_the_cell_lists_them():
    bench = json.loads((cells.REPO / "BENCHMARK.json").read_text())
    entries = {m["name"]: m for m in bench["per_layer"]}
    assert set(METRICS) <= set(entries)
    for name, (layer, source, reader) in METRICS.items():
        spec = cells.load_json(cells.ROOT / "metrics" / f"{name}.json")
        assert spec["reader"] == f"latent:{reader}"
        assert spec["unit"] == entries[name]["unit"] == "%"
        assert (entries[name]["layer"], entries[name]["source"]) == (layer, source)
        assert entries[name]["moves"] == "serve_tokens_per_s"
        assert entries[name]["workloads"] == [CELL]
        assert callable(cells.load_reader(name))
    # the cell reports what Falcon-H1's cell reports but that cell's own and
    # the state-space mixer's, + the routed MLP's time and load, + its own
    # three; NOT the paged kernel's share (no such kernel runs here), nor
    # another model's `tick_mfu_pct.*`
    listed = {m["name"] for m in bench["per_layer"] if CELL in m["workloads"]}
    falcon = {m["name"] for m in bench["per_layer"] if FALCON in m["workloads"]}
    shared = {n for n in falcon if not n.startswith(
        ("ssm_", "parmix_", "mlp_time", "head_time", "tick_mfu_pct"))}
    assert len(shared) == 22
    assert listed == shared | MOE | set(METRICS)
    assert not any(n.startswith(("paged_roofline", "conv_", "loop_", "ssm_")) for n in listed)
    assert {n for n in listed if n.startswith("tick_mfu_pct")} == {"tick_mfu_pct.latent"}
    # appended: wherever this cell and Falcon-H1's are listed, this one comes after
    for m in bench["end_to_end"] + bench["per_layer"]:
        cells_of = m.get("workloads", [])
        if CELL in cells_of and FALCON in cells_of:
            assert cells_of.index(CELL) > cells_of.index(FALCON)
    cell = cells.load_cell(CELL)
    assert cell.reference_name == "latent_moe_decoder" and cell.chips == 1
    assert [m["name"] for m in cell.metrics("end_to_end")] == ["setup_s", "serve_tokens_per_s"]
    names = [w["name"] for w in bench["workloads"]]
    configs = [c["name"] for c in bench["configs"]]
    assert names.index(CELL) > names.index(FALCON)
    assert configs.index(CONFIG) > configs.index("falcon-h1-34b-serve")
    entry = bench["workloads"][names.index(CELL)]
    assert (entry["config"], entry["traffic"], entry["chips"]) == (CONFIG, TRAFFIC, 1)
    assert len(entry["why"]) <= 200
    for word in ("32 slots x 16,384", "1,152 B", "1/32", "dense"):
        assert word in entry["why"], word
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
        == cell.config["reference"] == "latent_moe_decoder"
    bursts = cells.load_module(cells.ROOT, "generators", "bursts",
                               cells.GENERATOR_CONTRACT).generate
    assert cell.generate.__code__.co_code == bursts.__code__.co_code
    spec = cell.view.reference_spec(ARCH)
    assert (spec["num_heads"], spec["kv_lora"], spec["nope"], spec["rope"], spec["v"]) == (
        64, 512, 128, 64, 128)
    assert spec["yarn"] == (32.0, 4096.0, 1.0, 1.0, 1.0, 1.0)
    assert (spec["num_dense"], spec["top_k"], spec["scale"], spec["gate_eps"]) == (
        1, 8, 2.827, 1e-20)
    with pytest.raises(SystemExit, match="the configuration states {'moe_router': 'softmax'"):
        cell.view.reference_spec({**ARCH, "moe_router": "softmax"})
    with pytest.raises(SystemExit, match="layer_pattern is"):
        cell.view.reference_spec({**ARCH, "layer_pattern": ["attention", "mlp"]})


def test_the_configuration_names_every_key_it_changed_and_cuts_no_width():
    """What ``test_configs.py`` asks of every configuration, for one whose
    keys are config.json's own (its table knows dense keys only)."""
    config = cells.load_json(cells.ROOT / "configs" / f"{CONFIG}.json")
    bench = json.loads((cells.REPO / "BENCHMARK.json").read_text())
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert entry["source"] == config["source"] == (
        "https://huggingface.co/moonshotai/Kimi-K2-Instruct/blob/main/config.json")
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
    # the program runs what the file states, width for width
    as_run = {
        "hidden_size": arch["hidden_size"], "num_hidden_layers": arch["num_layers"] // 2,
        "num_attention_heads": arch["num_attention_heads"],
        "q_lora_rank": arch["q_lora_rank"], "kv_lora_rank": arch["kv_lora_rank"],
        "qk_nope_head_dim": arch["qk_nope_head_dim"],
        "qk_rope_head_dim": arch["qk_rope_head_dim"], "v_head_dim": arch["v_head_dim"],
        "intermediate_size": int(arch["hidden_size"] * arch["mlp_factor"]),
        "moe_intermediate_size": arch["moe_expert_width"],
        "n_routed_experts": arch["moe_experts_held"],
        "num_experts_per_tok": arch["moe_top_k"],
        "n_shared_experts": arch["moe_shared_expert_width"] // arch["moe_expert_width"],
        "first_k_dense_replace": arch["layer_pattern"].count("mlp"),
        "routed_scaling_factor": arch["moe_routed_scaling_factor"],
        "norm_topk_prob": arch["moe_norm_topk_prob"],
        "n_group": arch["moe_n_group"], "topk_group": arch["moe_topk_group"],
        "vocab_size": arch["vocab_size"],
        "max_position_embeddings": arch["sequence_length"],
        "rms_norm_eps": arch["layernorm"]["layernorm_epsilon"],
        "rope_theta": arch["rotary_embedding_base"],
        "rope_scaling": arch["rope_scaling"],
        "tie_word_embeddings": arch["weight_tying"],
        "attention_bias": arch["attention_bias"],
    }
    assert {key: config[key] for key in as_run} == as_run
    assert arch["moe_num_experts"] == published["n_routed_experts"] == 384
    assert arch["layer_pattern"] == ["latent", "mlp"] + ["latent", "moe"] * 5
    assert published["parameter_count"] == 1_026_408_232_448 == (
        497_500_160 + 60 * (147_931_520 + 384 * 44_040_192) + 2 * 163_840 * 7168 + 7168)
    assert "32 chips" in config["stands_for"] and "4,173,177,728" in config["stands_for"]
    assert {"block", "attention", "forms", "rotary", "router", "experts", "state",
            "precision", "init", "traffic_means", "parameter_count"} <= set(config["assumed"])
    # the ISSUE's slots and context; chunk and budget with their reason
    assert config["engine"] == {"num_slots": 32, "context": 16384,
                                "enable_prefix_cache": False,
                                "prefill_chunk": 160, "token_budget": 480}
    assert "break even at rows of 171" in config["assumed"]["forms"]
    assert "0 failed" in config["assumed"]["forms"]
    assert config["chips"] == 1


def test_the_traffic_is_the_issues_and_fits_the_slots():
    """``longdoc-burst32``: 32 at once every whole second the rate rule
    gives; no request asks for more than a slot's 16,384 positions or names a
    token outside the held vocabulary."""
    traffic = cells.load_json(cells.ROOT / "traffic" / f"{TRAFFIC}.json")
    assert (traffic["generator"], traffic["backlog"], traffic["burst_size"],
            traffic["shape_seed"], traffic["warm_seconds"]) == ("bursts", "cut", 32, 55, 20)
    assert traffic["burst_every_s"] == int(traffic["burst_every_s"]) >= 2
    assert traffic["prompt"] == {"median": 6144, "sigma": 0.6, "min": 1024, "max": 15360}
    assert traffic["output"] == {"median": 160, "sigma": 0.5, "min": 32, "max": 512}
    assert "tokens" not in traffic   # above the knee: whatever the engine completes
    assert traffic["check_requests"] == 4 and traffic["check_max_tokens"] >= 8192
    assert traffic["trace_seconds"] == 3
    config = cells.load_json(cells.ROOT / "configs" / f"{CONFIG}.json")
    context = config["engine"]["context"]
    assert traffic["max_total"] == context == 16384
    vocab = config["transformer_architecture"]["vocab_size"]
    requests = cells.load_cell(CELL).generate(traffic, 2**31 + 5, 51.0, vocab)
    counted = [r for r in requests if r.due_s >= 0]
    assert len(counted) == 32 * len({r.due_s for r in counted})
    assert sum(r.due_s < 0 for r in requests) == 32     # one uncounted burst
    assert {r.due_s for r in requests if r.due_s < 0} == {-20.0}
    assert max(len(r.prompt) + r.output_len for r in requests) <= context
    assert min(r.output_len for r in requests) >= 32
    assert all(1 <= t < vocab for r in requests[:8] for t in r.prompt)
    # the check teacher-forces requests whose cache spans >= 384 blocks
    assert sum(6144 <= len(r.prompt) + r.output_len <= traffic["check_max_tokens"]
               for r in counted) >= 8
    mean_prompt = sum(len(r.prompt) for r in counted) / len(counted)
    mean_output = sum(r.output_len for r in counted) / len(counted)
    assert 6500 < mean_prompt < 8200 and 160 < mean_output < 200
