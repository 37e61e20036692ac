"""What PR 65 brings for ``xing4.0-29b-a4b-serve`` as files (``reference/`` and
``views/hc_latent_moe_decoder.py``, ``readers/hyper_connection.py``,
``hc_latent_ops_count.py``, three metrics, ``traffic/rag2k-burst32.json``),
rehearsed on the CPU at a toy width through a copy of ``benchmark/`` into
which only a toy configuration is added; and the readers on recorded rows.
Membership is pinned, never position or a literal list: the next
configuration's PR appends after these entries."""

import json
import shutil
from pathlib import Path

import pytest

from benchmark import cells, hc_latent_ops_count, latent_ops_count, serve_kind
from benchmark.readers import hybrid, hyper_connection as hc

TOY = Path(__file__).parent / "data" / "toy_hc_latent"
BENCH = TOY / "BENCHMARK.json"
CELL = "serve-xing29b-rag-burst"
CONFIG = "xing4.0-29b-a4b-serve"
TRAFFIC = "rag2k-burst32"
KIMI = "serve-kimik2-longdoc-burst"
METRICS = {
    "hc_time_pct.saturated": ("hyper-connection", "device_trace", "lower", "hc_time_pct"),
    "hc_stream_roofline.saturated": ("hyper-connection", "device_trace", "higher",
                                     "hc_stream_roofline"),
    "tick_mfu_pct.hc_latent": ("engine tick", "program_counter", "higher", "tick_mfu_pct"),
}
# the accepted metrics whose readers read this configuration unchanged
READ_UNCHANGED = {"latent_time_pct.saturated", "latent_roofline.saturated",
                  "moe_time_pct.saturated", "moe_load_max_over_mean.saturated",
                  "moe_routed_roofline.saturated"}


@pytest.fixture(scope="module")
def grown_hc(grown):
    """``grown`` (the toy burst traffic is there) plus the one toy
    configuration and its chat traffic; reference, view, readers and metrics
    are the benchmark's own."""
    shutil.copy(TOY / "configs" / "toy-xing.json", grown / "configs")
    shutil.copy(TOY / "traffic" / "toy-hc-chat.json", grown / "traffic")
    for part, name in (("reference", "hc_latent_moe_decoder.py"),
                       ("views", "hc_latent_moe_decoder.py"),
                       ("readers", "hyper_connection.py")):
        assert (cells.ROOT / part / name).is_file() and (grown / part / name).is_file()
    return grown


def rehearse(run, root, trace=0, *more, workload="toy-serve-hc", seconds="1.5"):
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
    toy = cells.load_json(TOY / "configs" / "toy-xing.json")["transformer_architecture"]
    real = cells.load_json(cells.ROOT / "configs" / f"{CONFIG}.json")["transformer_architecture"]
    for key in ("hc_streams", "hc_sinkhorn_iters", "hc_eps", "hc_res_clamp_min",
                "hc_res_clamp_max", "moe_router", "moe_routed_scaling_factor",
                "moe_norm_topk_eps", "rotary_embedding_base", "moe_glu", "mlp_type",
                "weight_tying", "moe_top_k"):
        assert toy[key] == real[key], key
    assert toy["layer_pattern"][:4] == real["layer_pattern"][:4] == [
        "latent", "mlp", "latent", "moe"]
    assert {k: v for k, v in toy["rope_scaling"].items()
            if k not in ("factor", "original_max_position_embeddings")} == {
        k: v for k, v in real["rope_scaling"].items()
        if k not in ("factor", "original_max_position_embeddings")}
    # every expert is held, as in the cell
    assert toy["moe_experts_held"] == toy["moe_num_experts"]
    assert real["moe_experts_held"] == real["moe_num_experts"] == 64


def test_hc_serve_cell_is_correct_and_its_ticks_carry_streams_and_sublayers(
        run, grown_hc, capsys, monkeypatch):
    """The engine serves the stack through the latent pool, the residual's
    four streams mixed a sub-layer (the Sinkhorn kernel interpreted), every
    checked token on the reference's best logit; the traced part's ticks
    carry ``hc_streams`` and ``hc_sublayers`` and the counter moves by real
    tokens x sub-layers."""
    from scaling_tpu import obs

    seen = spy_on_the_kind(monkeypatch)
    result = rehearse(run, grown_hc, trace=2)
    assert result["correct"] and result["failed"] == 0 and result["unserved"] > 0
    assert seen["outcome"]["host"]["worst_logit_gap"] < 1e-3
    # the CPU has no device plane and no published peak: the readers of the
    # trace and of the peak find nothing and are left out
    assert set(result["metrics"]) == {"setup_s", "serve_tokens_per_s",
                                      "batch_occupancy_pct"}
    capture = obs.last_capture()
    mixed = hc.hc_ticks(capture.spans)
    assert mixed and all((f["hc_streams"], f["hc_sublayers"]) == (4, 6) for f in mixed)
    assert capture.counters[hc.TOKEN_SUBLAYERS] == 6 * sum(f["tokens"] for f in mixed)
    assert mixed == hybrid.span_fields("serve.mixed", "latent_pairs", capture.spans)
    ctx = {"device": {"peaks": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}},
           "config": cells.load_json(grown_hc / "configs" / "toy-xing.json"),
           "host": {}}
    assert 0 < hc.tick_mfu_pct(ctx) < 1.0
    # no device plane: nothing under the scope, so nothing, not 0
    assert hc.hc_time_pct(ctx) is None and hc.hc_stream_roofline(ctx) is None


def test_a_plain_cell_reads_none_of_the_new_metrics(run, grown, capsys):
    """A plain model's spans carry no ``hc_sublayers`` and its counters no
    ``serve_hc_token_sublayers_total``: the readers return nothing and do not
    raise, whatever its trace's scopes. What the parent commit's program gives
    under this PR's benchmark files."""
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
    for *_, reader in METRICS.values():
        assert getattr(hc, reader)(ctx) is None
    assert hc.hc_time_pct(ctx, ops=OPS) is None   # scopes, no field
    assert hc.hc_stream_roofline(ctx, ops=OPS) is None


def test_the_control_fails_the_limit_the_program_keeps(run, grown_hc, capsys,
                                                       monkeypatch):
    """``--control fp8``: the reference with fp8 matrices (``phi`` among
    them) misses the limit that the program keeps with room."""
    seen = spy_on_the_kind(monkeypatch)
    result = rehearse(run, grown_hc, 0, "--control", "fp8",
                      workload="toy-serve-hc-chat", seconds="3")
    assert result["correct"]
    host = seen["outcome"]["host"]
    sound, control = host["worst_logit_gap"], host["control_logit_gap"]
    assert sound < serve_kind.LOGIT_TOL / 2 < serve_kind.LOGIT_TOL < control
    assert control > 3 * sound


# ---- the readers on recorded rows -----------------------------------------

LAYER = "jit(mixed_896)/jit(_lambda_)/"
KERNEL = ('%hc_sinkhorn.3 = f32[16,8,128] custom-call(...), '
          'custom_call_target="tpu_custom_call"')
# name, start_ns, dur_ns, op_name: what ``load_scoped_ops`` gives
OPS = [
    ["%fusion.1 = bf16[896,14336] fusion(...)", 0.0, 100e3, ""],                  # embedding
    ["%fusion.5 = f32[72,896] fusion(...)", 100e3, 200e3, LAYER + "hc/td,dk->kt/dot_general"],
    [KERNEL, 300e3, 50e3, LAYER + "hc/jit(sinkhorn_tokens)/hc_sinkhorn/pallas_call"],
    ["%fusion.11 = bf16[896,6144] fusion(...)", 350e3, 650e3, LAYER + "attn/dot_general"],
    ["%fusion.12 = bf16[896,14336] fusion(...)", 1000e3, 250e3, LAYER + "hc/concatenate"],
    ["%fusion.13 = f32[896,64] fusion(...)", 1200e3, 600e3, LAYER + "moe/dot_general"],  # overlaps
    ["%fusion.14 = bf16[32,3584] fusion(...)", 1800e3, 100e3, "jit(mixed_896)/head/hc/mul"],
    ["%fusion.40 = bf16[32,131072] fusion(...)", 1900e3, 100e3, "jit(mixed_896)/head/dot_general"],
]
SPANS = [
    ("serve.tick", 0, 25e6, {"step": 1}),
    ("serve.mixed", 0, 24e6, {"step": 1, "tokens": 500, "hc_streams": 4, "hc_sublayers": 12,
                              "latent_layers": 6, "latent_lines": 90_000,
                              "latent_pairs": 600_000}),
    ("serve.tick", 30e6, 25e6, {"step": 2}),
    ("serve.mixed", 30e6, 24e6, {"step": 2, "tokens": 32, "hc_streams": 4, "hc_sublayers": 12,
                                 "latent_layers": 6, "latent_lines": 70_000,
                                 "latent_pairs": 70_000}),
    ("serve.mixed", 70e6, 5e6, {"step": 3}),  # a tick of the warm-up: no field
]
COUNTERS = {"serve_prefill_tokens_total": 470, "serve_tokens_generated_total": 62,
            "serve_moe_assignments_total": 5 * 4 * 532,
            "serve_hc_token_sublayers_total": 12 * 532}
ARCH = cells.load_json(cells.ROOT / "configs" / f"{CONFIG}.json")["transformer_architecture"]
CTX = {"device": {"peaks": {"hbm_bytes_per_s": 819e9, "flops_per_s": 197e12}},
       "config": {"transformer_architecture": ARCH}, "host": {}, "trace": None}
SHAPE = dict(latent_layers=6, dense_layers=1, routed_layers=5, hidden=3584, vocab=131_072,
             dense_width=9216, expert_width=1024, shared_width=1024, num_experts=64,
             attention=dict(heads=32, q_lora=768, kv_lora=512, nope=128, rope=64, v=128))


def test_the_counts_are_the_issues_by_hand():
    # X and y in, X' and u out: (2 n + 2) C values a token a sub-layer
    assert hc_latent_ops_count.stream_bytes(1, 4, 3584, 2) == 71_680
    assert hc_latent_ops_count.stream_bytes(12 * 500, 4, 3584, 2) == 430_080_000
    # vec(X) phi: 2 x 14,336 x 24 a token a sub-layer; the readout 2 x 14,336 x 4
    assert hc_latent_ops_count.mapping_flops(1, 0, 4, 3584) == 2 * 14_336 * 24
    assert hc_latent_ops_count.mapping_flops(0, 1, 4, 3584) == 2 * 14_336 * 4
    blocks = latent_ops_count.serve_flops(532, 62, 10_640, 670_000, 160_000, **SHAPE)
    assert hc_latent_ops_count.serve_flops(
        532, 62, 10_640, 670_000, 160_000, 12 * 532, streams=4, **SHAPE) == (
        blocks + 2.0 * 14_336 * (12 * 532 * 24 + 62 * 4))
    assert latent_ops_count.attention_matmul_params(3584, 32, 768, 512, 128, 64, 128) \
        == 28_411_136 - 768 - 512


def test_readers_give_the_three_values_by_hand():
    assert hc.union_seconds(OPS) == pytest.approx(2.0e-3)
    # under `hc`: 0.2 + 0.05 + 0.25 + 0.1 ms, the readout's under `head` too
    assert hc.hc_time_pct(CTX, ops=OPS, spans=SPANS) == pytest.approx(100 * 0.6 / 2.0)
    assert hc.hc_stream_roofline(CTX, ops=OPS, spans=SPANS, counters=COUNTERS) == \
        pytest.approx(100 * 12 * 532 * 71_680 / 0.6e-3 / 819e9)
    flops = hc_latent_ops_count.serve_flops(
        532, 62, 10_640, 670_000, 160_000, 12 * 532, streams=4, **SHAPE)
    assert hc.tick_mfu_pct(CTX, spans=SPANS, counters=COUNTERS) == pytest.approx(
        100 * flops / 0.050 / 197e12)


def test_no_share_can_pass_one_hundred_on_what_the_chip_can_do():
    everything = [[n, s, d, LAYER + "hc/x"] for n, s, d, _ in OPS]
    assert hc.hc_time_pct(CTX, ops=everything, spans=SPANS) == pytest.approx(100)
    # a residual path that moves its least bytes at the published rate reads 100
    at_the_rate = [["%fusion = ...", 0.0, 1e9 * 12 * 532 * 71_680 / 819e9, LAYER + "hc/x"]]
    assert hc.hc_stream_roofline(CTX, ops=at_the_rate, spans=SPANS,
                                 counters=COUNTERS) == pytest.approx(100.0)
    flops = hc_latent_ops_count.serve_flops(
        32, 32, 640, 70_000, 70_000, 12 * 32, streams=4, **SHAPE)
    spans = [("serve.tick", 0, 1e9 * flops / 197e12, {"step": 1}), SPANS[3]]
    assert hc.tick_mfu_pct(CTX, spans=spans, counters={
        "serve_tokens_generated_total": 32, "serve_moe_assignments_total": 640,
        "serve_hc_token_sublayers_total": 12 * 32}) == pytest.approx(100.0)


def test_without_the_scope_the_counter_or_the_field_a_reader_gives_none_not_zero():
    bare = [[name, start, dur, ""] for name, start, dur, _ in OPS]
    no_field = SPANS[4:]
    assert hc.hc_time_pct(CTX, ops=bare, spans=SPANS) is None
    assert hc.hc_time_pct(CTX, ops=[], spans=SPANS) is None
    assert hc.hc_time_pct(CTX, ops=OPS, spans=no_field) is None
    assert hc.hc_stream_roofline(CTX, ops=OPS, spans=no_field, counters=COUNTERS) is None
    assert hc.hc_stream_roofline(CTX, ops=bare, spans=SPANS, counters=COUNTERS) is None
    assert hc.hc_stream_roofline(CTX, ops=OPS, spans=SPANS, counters={}) is None
    assert hc.tick_mfu_pct(CTX, spans=no_field, counters=COUNTERS) is None
    assert hc.tick_mfu_pct(CTX, spans=SPANS, counters={}) is None
    no_peak = {**CTX, "device": {"peaks": None}}
    assert hc.tick_mfu_pct(no_peak, spans=SPANS, counters=COUNTERS) is None
    assert hc.hc_stream_roofline(no_peak, ops=OPS, spans=SPANS, counters=COUNTERS) is None
    near = [["%f = ...", 0.0, 1e3, "jit(mixed)/hc_out/mul"], ["%g = ...", 1e3, 1e3, ""]]
    assert hc.hc_time_pct(CTX, ops=near, spans=SPANS) is None


# ---- the files, by name and by membership ---------------------------------

def test_metric_files_name_the_readers_and_the_cell_is_listed():
    bench = json.loads((cells.REPO / "BENCHMARK.json").read_text())
    entries = {m["name"]: m for m in bench["per_layer"]}
    assert set(METRICS) <= set(entries)
    for name, (layer, source, better, reader) in METRICS.items():
        spec = cells.load_json(cells.ROOT / "metrics" / f"{name}.json")
        assert spec["reader"] == f"hyper_connection:{reader}"
        assert spec["unit"] == entries[name]["unit"] == "%"
        assert (entries[name]["layer"], entries[name]["source"],
                entries[name]["better"]) == (layer, source, better)
        assert entries[name]["moves"] == "serve_tokens_per_s"
        assert CELL in entries[name]["workloads"]
        assert callable(cells.load_reader(name))
    # the cell reports every metric that Kimi-K2's cell reports but that
    # cell's own `tick_mfu_pct.*`, + the routed roofline LFM2's cell has, + its
    # own three
    listed = {m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", [])}
    kimi = {m["name"] for m in bench["per_layer"] if KIMI in m.get("workloads", [])}
    assert {n for n in kimi if not n.startswith("tick_mfu_pct")} <= listed
    assert READ_UNCHANGED <= listed
    assert {n for n in listed if n.startswith("tick_mfu_pct")} == {"tick_mfu_pct.hc_latent"}
    assert not any(n.startswith(("paged_roofline", "conv_", "loop_", "ssm_", "sparse_"))
                   for n in listed)
    # appended: wherever this cell and Kimi-K2's are listed, this one comes after
    for m in bench["end_to_end"] + bench["per_layer"]:
        cells_of = m.get("workloads", [])
        if CELL in cells_of and KIMI in cells_of:
            assert cells_of.index(CELL) > cells_of.index(KIMI)
    cell = cells.load_cell(CELL)
    assert cell.reference_name == "hc_latent_moe_decoder" and cell.chips == 1
    assert [m["name"] for m in cell.metrics("end_to_end")] == ["setup_s", "serve_tokens_per_s"]
    names = [w["name"] for w in bench["workloads"]]
    configs = [c["name"] for c in bench["configs"]]
    assert CELL in names and names.index(CELL) > names.index(KIMI)
    assert CONFIG in configs and configs.index(CONFIG) > configs.index("kimi-k2-instruct-serve")
    entry = bench["workloads"][names.index(CELL)]
    assert (entry["config"], entry["traffic"], entry["chips"]) == (CONFIG, TRAFFIC, 1)
    assert len(entry["why"]) <= 200
    for word in ("32 slots x 8,192", "4 streams", "64 experts"):
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
        == cell.config["reference"] == "hc_latent_moe_decoder"
    bursts = cells.load_module(cells.ROOT, "generators", "bursts",
                               cells.GENERATOR_CONTRACT).generate
    assert cell.generate.__code__.co_code == bursts.__code__.co_code
    spec = cell.view.reference_spec(ARCH)
    assert (spec["hc_streams"], spec["hc_sinkhorn_iters"], spec["hc_eps"],
            spec["hc_clamp"]) == (4, 20, 1e-6, (-30.0, 30.0))
    assert (spec["num_heads"], spec["kv_lora"], spec["nope"], spec["rope"], spec["v"]) == (
        32, 512, 128, 64, 128)
    assert spec["yarn"] == (64.0, 4096.0, 32.0, 1.0, 1.0, 1.0)
    assert (spec["num_dense"], spec["top_k"], spec["scale"], spec["gate_eps"]) == (
        1, 4, 2.0, 1e-20)
    # YaRN with a real ramp: between frequency indices 10 and 23; the softmax
    # scale 192 ** -0.5 x (0.1 ln 64 + 1) ** 2
    from benchmark.reference import latent_moe_decoder as latent
    assert latent.yarn_range(64, 10000.0, spec["yarn"]) == (10, 23)
    assert latent.softmax_scale(128, 64, spec["yarn"]) == pytest.approx(0.14468, abs=1e-5)
    with pytest.raises(SystemExit, match="mixes hc_streams > 1"):
        cell.view.reference_spec({**ARCH, "hc_streams": 1})
    with pytest.raises(SystemExit, match="the configuration states {'moe_router': 'softmax'"):
        cell.view.reference_spec({**ARCH, "moe_router": "softmax"})


def test_the_configuration_names_every_key_it_changed_and_cuts_no_width():
    """What ``test_configs.py`` asks of every configuration, for one whose
    keys are config.json's own (its table knows dense keys only): every number
    of the catalog row under the same key, depth and positions alone
    reduced."""
    config = cells.load_json(cells.ROOT / "configs" / f"{CONFIG}.json")
    bench = json.loads((cells.REPO / "BENCHMARK.json").read_text())
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert entry["source"] == config["source"] == (
        "https://huggingface.co/XingChen-AGI/Xing4.0-29B-A4B/blob/main/config.json")
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
        "hc_mult": arch["hc_streams"], "hc_sinkhorn_iters": arch["hc_sinkhorn_iters"],
        "hc_eps": arch["hc_eps"], "mhc_h_res_clamp_min": arch["hc_res_clamp_min"],
        "mhc_h_res_clamp_max": arch["hc_res_clamp_max"],
    }
    assert {key: config[key] for key in as_run} == as_run
    assert arch["moe_num_experts"] == arch["moe_experts_held"] == published["n_routed_experts"]
    # the two leading dense layers count once: one dense block, five routed
    assert published["first_k_dense_replace"] == 2
    assert arch["layer_pattern"] == ["latent", "mlp"] + ["latent", "moe"] * 5
    assert published["parameter_count"] == 29_505_562_613 == (
        2 * 128_196_918 + 38 * 744_989_046 + 2 * 131_072 * 3584 + 3584 + 57_349)
    assert "4,792,727,177" in config["stands_for"] and "9.59 GB" in config["stands_for"]
    assert {"mapping", "rms", "sinkhorn", "eps", "readout", "stream", "mtp", "block",
            "attention", "rotary", "router", "experts", "precision", "init",
            "engine"} <= set(config["assumed"])
    assert config["engine"]["num_slots"] == 32 and config["engine"]["context"] == 8192
    assert config["engine"]["enable_prefix_cache"] is False
    assert config["chips"] == 1


def test_the_traffic_is_the_issues_and_fits_the_slots():
    """``rag2k-burst32``: 32 at once every whole second the rate rule gives;
    no request asks for more than a slot's 8,192 positions or names a token
    outside the vocabulary."""
    traffic = cells.load_json(cells.ROOT / "traffic" / f"{TRAFFIC}.json")
    assert (traffic["generator"], traffic["backlog"], traffic["burst_size"],
            traffic["shape_seed"]) == ("bursts", "cut", 32, 65)
    assert traffic["burst_every_s"] == int(traffic["burst_every_s"]) >= 1
    assert traffic["prompt"] == {"median": 1792, "sigma": 0.7, "min": 256, "max": 7680}
    assert traffic["output"] == {"median": 128, "sigma": 0.5, "min": 16, "max": 512}
    assert "tokens" not in traffic   # above the knee: whatever the engine completes
    assert traffic["check_requests"] == 4 and traffic["check_max_tokens"] == 4096
    assert traffic["trace_seconds"] == 3
    config = cells.load_json(cells.ROOT / "configs" / f"{CONFIG}.json")
    context = config["engine"]["context"]
    assert traffic["max_total"] == context == 8192
    vocab = config["transformer_architecture"]["vocab_size"]
    requests = cells.load_cell(CELL).generate(traffic, 2**31 + 5, 51.0, vocab)
    counted = [r for r in requests if r.due_s >= 0]
    assert len(counted) == 32 * len({r.due_s for r in counted})
    assert sum(r.due_s < 0 for r in requests) == 32     # one uncounted burst
    assert max(len(r.prompt) + r.output_len for r in requests) <= context
    assert min(r.output_len for r in requests) >= 16
    assert all(1 <= t < vocab for r in requests[:8] for t in r.prompt)
    assert max(t for r in requests[:64] for t in r.prompt) > vocab // 2
    mean_prompt = sum(len(r.prompt) for r in counted) / len(counted)
    mean_output = sum(r.output_len for r in counted) / len(counted)
    assert 2000 < mean_prompt < 2600 and 130 < mean_output < 160
