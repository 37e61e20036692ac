"""What PR 68 brings for ``laguna-s-2.1-serve`` as files (``reference/`` and
``views/layered_gqa_moe_decoder.py``, ``readers/layered_gqa.py``,
``layered_gqa_ops_count.py``, five metrics, ``traffic/mixedlen32k-burst24.json``),
rehearsed on the CPU at a toy width through a copy of ``benchmark/`` into
which only a toy configuration is added; and the readers on recorded rows.
Membership is pinned, never position or a literal list: the next
configuration's PR appends after these entries."""

import json
import math
import shutil
from pathlib import Path

import pytest

from benchmark import cells, layered_gqa_ops_count as ops_count, model, serve_kind
from benchmark.readers import layered_gqa as lg

DATA = Path(__file__).parent / "data"
TOY = DATA / "toy_layered_gqa"
BENCH = TOY / "BENCHMARK.json"
CELL = "serve-laguna-s-mixedlen-burst"
CONFIG = "laguna-s-2.1-serve"
TRAFFIC = "mixedlen32k-burst24"
XING = "serve-xing29b-rag-burst"
METRICS = {
    "window_time_pct.saturated": ("window attention", "device_trace", "lower",
                                  "window_time_pct"),
    "full_attn_time_pct.saturated": ("full attention", "device_trace", "lower",
                                     "full_attn_time_pct"),
    "window_roofline.saturated": ("window attention", "device_trace", "higher",
                                  "window_roofline"),
    "window_active_row_pct.saturated": ("window attention", "program_counter",
                                        "higher", "window_active_row_pct"),
    "tick_mfu_pct.layered_gqa": ("engine tick", "program_counter", "higher",
                                 "tick_mfu_pct"),
}
# the accepted metrics whose readers read this configuration unchanged
READ_UNCHANGED = {"moe_time_pct.saturated", "moe_load_max_over_mean.saturated"}


@pytest.fixture(scope="module")
def grown_laguna(grown):
    """``grown`` (the toy burst traffic is there) plus the one toy
    configuration and a chat traffic; reference, view, readers and metrics are
    the benchmark's own."""
    shutil.copy(TOY / "configs" / "toy-laguna.json", grown / "configs")
    shutil.copy(DATA / "toy_hc_latent" / "traffic" / "toy-hc-chat.json",
                grown / "traffic")
    for part, name in (("reference", "layered_gqa_moe_decoder.py"),
                       ("views", "layered_gqa_moe_decoder.py"),
                       ("readers", "layered_gqa.py")):
        assert (cells.ROOT / part / name).is_file() and (grown / part / name).is_file()
    return grown


def rehearse(run, root, trace=0, *more, workload="toy-serve-laguna", seconds="1.5"):
    return run.main(["--workload", workload, "--seed", "3000000019",
                     "--seconds", seconds, "--trace", str(trace), "--rehearse",
                     "--root", str(root), "--benchmark-json", str(BENCH), *more])


def spy_on_the_kind(monkeypatch):
    seen = {}
    real = serve_kind.run
    monkeypatch.setattr(serve_kind, "run", lambda cell, args, env: seen.setdefault(
        "outcome", real(cell, args, env)))
    return seen


ARCH = cells.load_json(cells.ROOT / "configs" / f"{CONFIG}.json")["transformer_architecture"]


def test_the_toy_states_the_published_equations():
    toy = cells.load_json(TOY / "configs" / "toy-laguna.json")["transformer_architecture"]
    for key in ("attention_gate", "moe_router", "moe_routed_scaling_factor",
                "moe_norm_topk_prob", "rotary_embedding_base", "rotary_percentage",
                "window_rotary_embedding_base", "window_rotary_percentage",
                "moe_glu", "mlp_type", "mlp_factor", "weight_tying", "moe_top_k",
                "key_query_norm", "layer_pattern", "moe_experts_first"):
        assert toy[key] == ARCH[key], key
    assert {k: v for k, v in toy["rope_scaling"].items()
            if k not in ("factor", "original_max_position_embeddings")} == {
        k: v for k, v in ARCH["rope_scaling"].items()
        if k not in ("factor", "original_max_position_embeddings")}
    # two head counts over the same KV heads, a share of the experts held
    assert toy["window_num_attention_heads"] * 2 == toy["num_attention_heads"] * 3
    assert ARCH["window_num_attention_heads"] * 2 == ARCH["num_attention_heads"] * 3
    assert toy["moe_experts_held"] < toy["moe_num_experts"]
    assert (ARCH["moe_experts_held"], ARCH["moe_num_experts"]) == (32, 256)


def test_laguna_serve_cell_is_correct_and_its_ticks_say_what_the_window_did(
        run, grown_laguna, capsys, monkeypatch):
    """The engine serves the stack through the pool (the full layers) and the
    rings (the window layers; the masked kernel interpreted), every checked
    token within the tolerance of the reference's best logit; the traced
    part's ticks carry the window's fields and the counters move."""
    from scaling_tpu import obs

    seen = spy_on_the_kind(monkeypatch)
    result = rehearse(run, grown_laguna, trace=2)
    assert result["correct"] and result["failed"] == 0 and result["unserved"] > 0
    assert seen["outcome"]["host"]["worst_logit_gap"] < 1e-3
    # the CPU has no device plane and no published peak: the readers of the
    # trace and of the peak find nothing and are left out
    assert set(result["metrics"]) == {
        "setup_s", "serve_tokens_per_s", "batch_occupancy_pct",
        "window_active_row_pct.saturated"}
    capture = obs.last_capture()
    mixed = lg.window_ticks(capture.spans)
    assert mixed and all(f["window_layers"] == 6 for f in mixed)
    assert capture.counters[lg.WINDOW_ROWS] == 6 * sum(f["window_rows"] for f in mixed)
    assert capture.counters[lg.WINDOW_ROWS_PAST] == 6 * sum(
        f["window_rows_past"] for f in mixed) > 0
    assert result["metrics"]["window_active_row_pct.saturated"]["value"] == pytest.approx(
        100 * capture.counters[lg.WINDOW_ROWS_PAST] / capture.counters[lg.WINDOW_ROWS])
    # under the window a query sees 16 lines at most; a full layer's pairs
    # are no fewer
    assert all(f["window_pairs"] <= 16 * f["tokens"] and f["full_pairs"] >=
               f["window_pairs"] for f in mixed)
    ctx = {"device": {"peaks": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}},
           "config": cells.load_json(grown_laguna / "configs" / "toy-laguna.json"),
           "host": {}}
    assert 0 < lg.tick_mfu_pct(ctx) < 1.0
    # no device plane: nothing under the scopes, so nothing, not 0
    assert lg.window_time_pct(ctx) is None and lg.window_roofline(ctx) is None
    assert lg.full_attn_time_pct(ctx) is None


def test_a_plain_cell_reads_none_of_the_new_metrics(run, grown, capsys):
    """A plain model's spans carry no ``window_pairs`` and its counters no
    ``serve_window_rows_total``: the readers return nothing and do not raise,
    whatever its trace's scopes. What the parent commit's program gives under
    this PR's benchmark files."""
    from scaling_tpu import obs

    toy = DATA / "toy" / "BENCHMARK.json"
    run.main(["--workload", "toy-serve-burst", "--seed", "5", "--seconds", "1.5",
              "--trace", "2", "--rehearse", "--root", str(grown),
              "--benchmark-json", str(toy)])
    capture = obs.last_capture()
    assert any(n == "serve.mixed" for n, _, _, _ in capture.spans)
    ctx = {"device": {"peaks": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}},
           "config": {"transformer_architecture": {"num_layers": 2}},
           "host": {}, "trace": None}
    for *_, reader in METRICS.values():
        assert getattr(lg, reader)(ctx) is None
    assert lg.window_time_pct(ctx, ops=OPS) is None   # scopes, no field
    assert lg.window_roofline(ctx, ops=OPS) is None


def test_the_control_fails_the_limit_the_program_keeps(run, grown_laguna, capsys,
                                                       monkeypatch):
    """``--control fp8``: the reference with fp8 matrices misses the limit
    that the program keeps with room."""
    seen = spy_on_the_kind(monkeypatch)
    result = rehearse(run, grown_laguna, 0, "--control", "fp8",
                      workload="toy-serve-laguna-chat", seconds="3")
    assert result["correct"]
    host = seen["outcome"]["host"]
    sound, control = host["worst_logit_gap"], host["control_logit_gap"]
    assert sound < serve_kind.LOGIT_TOL / 2 < serve_kind.LOGIT_TOL < control
    assert control > 3 * sound


# ---- the readers on recorded rows -----------------------------------------

LAYER = "jit(mixed_1664)/jit(_lambda_)/"
KERNEL = ('%masked_gqa_attention.3 = bf16[8,4608,128] custom-call(...), '
          'custom_call_target="tpu_custom_call"')
# name, start_ns, dur_ns, op_name: what ``load_scoped_ops`` gives
OPS = [
    ["%fusion.1 = bf16[1664,3072] fusion(...)", 0.0, 100e3, ""],                # embedding
    ["%fusion.5 = bf16[1664,9216] fusion(...)", 100e3, 200e3,
     LAYER + "window_attn/dot_general"],
    [KERNEL, 300e3, 150e3,
     LAYER + "window_attn/window_attend/while/body/cond/branch_2_fun/"
     "jit(masked_gqa_attention)/masked_gqa_attention/pallas_call"],
    ["%fusion.7 = bf16[1664,72,128] fusion(...)", 450e3, 50e3,
     LAYER + "window_attn/gate/mul"],
    ["%fusion.11 = bf16[1664,6144] fusion(...)", 500e3, 500e3,
     LAYER + "full_attn/dot_general"],
    ["%fusion.12 = bf16[1664,48,128] fusion(...)", 1000e3, 100e3,
     LAYER + "full_attn/gate/mul"],
    ["%fusion.13 = f32[1664,256] fusion(...)", 1050e3, 650e3, LAYER + "moe/dot_general"],
    ["%fusion.40 = bf16[24,12544] fusion(...)", 1700e3, 300e3,
     "jit(mixed_1664)/head/dot_general"],
]
SPANS = [
    ("serve.tick", 0, 40e6, {"step": 1}),
    ("serve.mixed", 0, 39e6, {"step": 1, "tokens": 1500, "window_layers": 6,
                              "window_rows": 24, "window_rows_past": 20,
                              "window_visible_lines": 12_000,
                              "window_pairs": 700_000, "full_pairs": 9_000_000}),
    ("serve.tick", 50e6, 40e6, {"step": 2}),
    ("serve.mixed", 50e6, 39e6, {"step": 2, "tokens": 24, "window_layers": 6,
                                 "window_rows": 24, "window_rows_past": 22,
                                 "window_visible_lines": 11_500,
                                 "window_pairs": 11_500, "full_pairs": 150_000}),
    ("serve.mixed", 100e6, 5e6, {"step": 3}),  # a tick of the warm-up: no field
]
COUNTERS = {"serve_prefill_tokens_total": 1476, "serve_tokens_generated_total": 48,
            "serve_moe_assignments_total": 7 * 1905,
            "serve_window_rows_total": 6 * 48,
            "serve_window_rows_past_window_total": 6 * 42}
CTX = {"device": {"peaks": {"hbm_bytes_per_s": 819e9, "flops_per_s": 197e12}},
       "config": {"transformer_architecture": ARCH}, "host": {}, "trace": None}
SHAPE = dict(full_layers=2, window_layers=6, dense_layers=1, routed_layers=7,
             hidden=3072, vocab=12_544, heads=48, window_heads=72, kv_heads=8,
             head_dim=128, dense_width=12_288, expert_width=1024, shared_width=1024,
             num_experts=256)


def test_the_counts_are_the_issues_by_hand():
    # Q and O, K and V, the gate: the ISSUE's 44,187,648 and 63,135,744
    assert ops_count.attention_matmul_params(3072, 48, 8, 128) == 44_187_648
    assert ops_count.attention_matmul_params(3072, 72, 8, 128) == 63_135_744
    assert ops_count.pair_flops(1, 72, 128) == 36_864
    assert ops_count.pair_flops(1, 48, 128) == 24_576
    assert ops_count.line_bytes(1, 8, 128, 2) == 4096
    per_token = (2 * 44_187_648 + 6 * 63_135_744 + 113_246_208
                 + 7 * (786_432 + 9_437_184))
    assert ops_count.serve_flops(1, 0, 0, 0, 0, **SHAPE) == 2.0 * per_token
    assert ops_count.serve_flops(0, 1, 0, 0, 0, **SHAPE) == 2.0 * 3072 * 12_544
    assert ops_count.serve_flops(0, 0, 1, 0, 0, **SHAPE) == 2.0 * 9_437_184
    assert ops_count.serve_flops(0, 0, 0, 1, 1, **SHAPE) == 2 * 24_576 + 6 * 36_864
    # ~2 GFLOP a prompt token at a context of 4k, 1.25 held assignments a
    # token and layer: the ISSUE's reckoning
    assert 1.7e9 < ops_count.serve_flops(1, 0, 9, 4096, 512, **SHAPE) < 2.1e9


def test_readers_give_the_five_values_by_hand():
    assert lg.union_seconds(OPS) == pytest.approx(2.0e-3)
    # window_attn: 0.2 + 0.15 + 0.05 ms; full_attn: 0.5 + 0.1 ms
    assert lg.window_time_pct(CTX, ops=OPS, spans=SPANS) == pytest.approx(100 * 0.4 / 2.0)
    assert lg.full_attn_time_pct(CTX, ops=OPS, spans=SPANS) == pytest.approx(
        100 * 0.6 / 2.0)
    # tick 1 is bound by its pairs' FLOPs, tick 2 by its lines' bytes
    least = 6 * (700_000 * 36_864 / 197e12 + 11_500 * 4096 / 819e9)
    assert 700_000 * 36_864 / 197e12 > 12_000 * 4096 / 819e9
    assert 11_500 * 36_864 / 197e12 < 11_500 * 4096 / 819e9
    assert lg.window_roofline(CTX, ops=OPS, spans=SPANS) == pytest.approx(
        100 * least / 0.15e-3)
    assert lg.window_active_row_pct(CTX, counters=COUNTERS) == pytest.approx(100 * 42 / 48)
    flops = ops_count.serve_flops(1524, 48, 7 * 1905, 9_150_000, 711_500, **SHAPE)
    assert lg.tick_mfu_pct(CTX, spans=SPANS, counters=COUNTERS) == pytest.approx(
        100 * flops / 0.080 / 197e12)


def test_no_share_can_pass_one_hundred_on_what_the_chip_can_do():
    everything = [[n, s, d, LAYER + "window_attn/x"] for n, s, d, _ in OPS]
    assert lg.window_time_pct(CTX, ops=everything, spans=SPANS) == pytest.approx(100)
    # a walk that takes the least its pairs and its lines allow reads 100
    least = 6 * (700_000 * 36_864 / 197e12 + 11_500 * 4096 / 819e9)
    at_the_rate = [["%k = ...", 0.0, 1e9 * least, LAYER + "window_attn/window_attend/x"]]
    assert lg.window_roofline(CTX, ops=at_the_rate, spans=SPANS) == pytest.approx(100.0)
    assert lg.window_active_row_pct(CTX, counters={
        lg.WINDOW_ROWS: 6, lg.WINDOW_ROWS_PAST: 6}) == 100.0
    flops = ops_count.serve_flops(24, 24, 200, 150_000, 11_500, **SHAPE)
    spans = [("serve.tick", 0, 1e9 * flops / 197e12, {"step": 1}), SPANS[3]]
    assert lg.tick_mfu_pct(CTX, spans=spans, counters={
        "serve_tokens_generated_total": 24,
        "serve_moe_assignments_total": 200}) == pytest.approx(100.0)


def test_without_the_scope_the_counter_or_the_field_a_reader_gives_none_not_zero():
    bare = [[name, start, dur, ""] for name, start, dur, _ in OPS]
    no_field = SPANS[4:]
    for reader in (lg.window_time_pct, lg.full_attn_time_pct, lg.window_roofline):
        assert reader(CTX, ops=bare, spans=SPANS) is None
        assert reader(CTX, ops=[], spans=SPANS) is None
        assert reader(CTX, ops=OPS, spans=no_field) is None
    assert lg.window_active_row_pct(CTX, counters={}) is None
    assert lg.tick_mfu_pct(CTX, spans=no_field, counters=COUNTERS) is None
    assert lg.tick_mfu_pct(CTX, spans=SPANS, counters={}) is None
    no_peak = {**CTX, "device": {"peaks": None}}
    assert lg.tick_mfu_pct(no_peak, spans=SPANS, counters=COUNTERS) is None
    assert lg.window_roofline(no_peak, ops=OPS, spans=SPANS) is None
    other = {**CTX, "config": {"transformer_architecture": {
        **ARCH, "layer_pattern": ["attention", "moe"]}}}
    assert lg.window_roofline(other, ops=OPS, spans=SPANS) is None
    assert lg.tick_mfu_pct(other, spans=SPANS, counters=COUNTERS) is None
    near = [["%f = ...", 0.0, 1e3, "jit(mixed)/window_attn_out/mul"],
            ["%g = ...", 1e3, 1e3, ""]]
    assert lg.window_time_pct(CTX, ops=near, spans=SPANS) is None


# ---- the files, by name and by membership ---------------------------------

def test_metric_files_name_the_readers_and_the_cell_is_in_each_list():
    bench = json.loads((cells.REPO / "BENCHMARK.json").read_text())
    entries = {m["name"]: m for m in bench["per_layer"]}
    assert set(METRICS) <= set(entries)
    for name, (layer, source, better, reader) in METRICS.items():
        spec = cells.load_json(cells.ROOT / "metrics" / f"{name}.json")
        assert spec["reader"] == f"layered_gqa:{reader}"
        assert spec["unit"] == entries[name]["unit"] == "%"
        assert (entries[name]["layer"], entries[name]["source"],
                entries[name]["better"]) == (layer, source, better)
        assert entries[name]["moves"] == "serve_tokens_per_s"
        assert CELL in entries[name]["workloads"]
        assert callable(cells.load_reader(name))
    # the cell is in every list the serve burst cells share (those Xing4.0's
    # cell and Mistral's burst cell are both in), and in the routed MLP's two
    listed = {m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", [])}
    shared = {m["name"] for m in bench["per_layer"]
              if {XING, "serve-mistral7b-chat-burst"} <= set(m.get("workloads", []))}
    assert len(shared) >= 32 and shared <= listed
    assert READ_UNCHANGED <= listed
    assert {n for n in listed if n.startswith("tick_mfu_pct")} == {"tick_mfu_pct.layered_gqa"}
    # whose readers do not read this configuration unchanged: left off
    assert not listed & {"paged_roofline.saturated", "head_time_pct.saturated"}
    serve = next(m for m in bench["end_to_end"] if m["name"] == "serve_tokens_per_s")
    assert CELL in serve["workloads"]
    # appended: wherever this cell and Xing4.0's are listed, this one comes after
    for m in bench["end_to_end"] + bench["per_layer"]:
        cells_of = m.get("workloads", [])
        if CELL in cells_of and XING in cells_of:
            assert cells_of.index(CELL) > cells_of.index(XING)
    cell = cells.load_cell(CELL)
    assert cell.reference_name == "layered_gqa_moe_decoder" and cell.chips == 1
    assert [m["name"] for m in cell.metrics("end_to_end")] == ["setup_s", "serve_tokens_per_s"]
    names = [w["name"] for w in bench["workloads"]]
    configs = [c["name"] for c in bench["configs"]]
    assert CELL in names and names.index(CELL) > names.index(XING)
    assert CONFIG in configs and configs.index(CONFIG) > configs.index("xing4.0-29b-a4b-serve")
    entry = bench["workloads"][names.index(CELL)]
    assert (entry["config"], entry["traffic"], entry["chips"]) == (CONFIG, TRAFFIC, 1)
    assert len(entry["why"]) <= 200
    for word in ("24 slots x 32,768", "6 window layers", "32 of 256 experts"):
        assert word in entry["why"], word
    assert len(names) <= 24 and sum(w["chips"] == 4 for w in bench["workloads"]) == 1


def test_the_cell_resolves_to_its_reference_view_and_generator():
    cell = cells.load_cell(CELL)
    for name in cells.REFERENCE_CONTRACT:
        assert callable(getattr(cell.reference, name))
    for name in cells.VIEW_CONTRACT:
        assert callable(getattr(cell.view, name))
    assert Path(cell.reference.__file__).stem == Path(cell.view.__file__).stem \
        == cell.config["reference"] == "layered_gqa_moe_decoder"
    bursts = cells.load_module(cells.ROOT, "generators", "bursts",
                               cells.GENERATOR_CONTRACT).generate
    assert cell.generate.__code__.co_code == bursts.__code__.co_code
    spec = cell.view.reference_spec(ARCH)
    assert spec["kinds"] == ("full", "window", "window", "window") * 2
    assert (spec["heads"], spec["num_kv_heads"], spec["head_dim"], spec["window"]) == (
        (48, 72), 8, 128, 512)
    full, window = spec["rope"]
    assert full[:2] == (500_000.0, 64) and window == (10_000.0, 128, None)
    # the published attention_factor, 0.1 ln 128 + 1
    published = cell.config["published"]["rope_parameters"]["full_attention"]
    assert full[2] == (128.0, 8192.0, 32.0, 1.0, pytest.approx(
        published["attention_factor"], rel=1e-12))
    assert published["attention_factor"] == 1.4852030263919618
    assert (spec["top_k"], spec["scale"], spec["experts_first"], spec["shared"]) == (
        10, 2.5, 0, True)
    # YaRN with a real ramp: between frequency indices 9 and 18 of 32
    freqs = cell.reference.inv_freq(64, 500_000.0, full[2])
    base = cell.reference.inv_freq(64, 500_000.0, None)
    assert freqs[:10] == pytest.approx(base[:10]) and freqs[18:] == pytest.approx(
        base[18:] / 128)
    assert base[12] / 128 < freqs[12] < base[12]
    with pytest.raises(SystemExit, match="the configuration states {'attention_gate': None"):
        cell.view.reference_spec({k: v for k, v in ARCH.items() if k != "attention_gate"})
    with pytest.raises(SystemExit, match="the configuration states {'moe_router': 'sigmoid_bias'"):
        cell.view.reference_spec({**ARCH, "moe_router": "sigmoid_bias"})
    with pytest.raises(SystemExit, match="layer_pattern is \\(attention \\| window"):
        cell.view.reference_spec({**ARCH, "layer_pattern": ["latent", "moe"]})


def test_the_configuration_names_every_key_it_changed_and_cuts_no_width():
    """Every number of the catalog row under the same key; depth, the experts
    held, the vocabulary's slice and the positions alone reduced; every item
    the ISSUE lists under ``assumed``; the parameter count from the program's
    own tree."""
    config = cells.load_json(cells.ROOT / "configs" / f"{CONFIG}.json")
    bench = json.loads((cells.REPO / "BENCHMARK.json").read_text())
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert entry["source"] == config["source"] == (
        "https://huggingface.co/poolside/Laguna-S-2.1/blob/main/config.json")
    published, reduced, arch = config["published"], config["reduced"], ARCH
    assert sorted(entry["reduced"]) == sorted(reduced) == sorted(
        ["num_hidden_layers", "num_experts", "vocab_size", "max_position_embeddings"])
    for key, value in published.items():
        if key == "parameter_count":
            continue
        if key in reduced:
            assert reduced[key]["published"] == value and reduced[key]["run"] == config[key] != value
        else:
            assert config[key] == value, f"{key} differs and is not in reduced"
    blocks = arch["num_layers"] // 2
    as_run = {
        "hidden_size": arch["hidden_size"], "num_hidden_layers": blocks,
        "num_attention_heads": arch["num_attention_heads"],
        "num_key_value_heads": arch["attention_num_kv_heads"],
        "head_dim": arch["attention_head_dim"],
        "intermediate_size": int(arch["hidden_size"] * arch["mlp_factor"]),
        "moe_intermediate_size": arch["moe_expert_width"],
        "shared_expert_intermediate_size": arch["moe_shared_expert_width"],
        "num_experts": arch["moe_experts_held"],
        "num_experts_per_tok": arch["moe_top_k"],
        "moe_routed_scaling_factor": arch["moe_routed_scaling_factor"],
        "norm_topk_prob": arch["moe_norm_topk_prob"],
        "vocab_size": arch["vocab_size"],
        "max_position_embeddings": arch["sequence_length"],
        "rms_norm_eps": arch["layernorm"]["layernorm_epsilon"],
        "sliding_window": arch["window_size"],
        "tie_word_embeddings": arch["weight_tying"],
        "attention_bias": arch["attention_bias"],
    }
    assert {key: config[key] for key in as_run} == as_run
    assert arch["moe_num_experts"] == published["num_experts"] == 256
    # the pattern and the head counts are the published lists' first eight
    kinds = {"full_attention": "attention", "sliding_attention": "window"}
    ffns = {"dense": "mlp", "sparse": "moe"}
    assert arch["layer_pattern"] == [
        name for l in range(blocks)
        for name in (kinds[published["layer_types"][l]],
                     ffns[published["mlp_layer_types"][l]])]
    heads = {"attention": arch["num_attention_heads"],
             "window": arch["window_num_attention_heads"]}
    assert [heads[k] for k in arch["layer_pattern"][0::2]] == \
        published["num_attention_heads_per_layer"][:blocks]
    assert set(published["gating_types"]) == {"per_head"} and arch["attention_gate"] == "per_head"
    rope = published["rope_parameters"]
    full, window = rope["full_attention"], rope["sliding_attention"]
    assert (arch["rotary_embedding_base"], arch["rotary_percentage"]) == (
        full["rope_theta"], full["partial_rotary_factor"])
    assert (arch["window_rotary_embedding_base"], arch["window_rotary_percentage"]) == (
        window["rope_theta"], window["partial_rotary_factor"])
    assert {k: arch["rope_scaling"][k] for k in (
        "factor", "original_max_position_embeddings", "beta_fast", "beta_slow")} == {
        k: full[k] for k in ("factor", "original_max_position_embeddings",
                             "beta_fast", "beta_slow")}
    assert published["parameter_count"] == 117_561_953_280 == (
        157_440_000 + 11 * 2_470_336_512 + 36 * 2_489_284_608
        + 2 * 100_352 * 3072 + 3072)
    assert "2,843,053,056" in config["stands_for"] and "5.69 GB" in config["stands_for"]
    assert {"gate", "qk_norm", "router", "shared_expert", "rotary", "window", "cache",
            "init", "block", "engine_shape", "precision"} <= set(config["assumed"])
    assert config["engine"]["num_slots"] == 24 and config["engine"]["context"] == 32_768
    assert config["engine"]["enable_prefix_cache"] is False
    assert config["chips"] == 1


def test_the_parameter_count_is_the_programs_own_tree():
    from scaling_tpu.models.transformer.model import init_model

    config = cells.load_json(cells.ROOT / "configs" / f"{CONFIG}.json")
    module = init_model(model.transformer_config(config, {}), None)
    shapes = model.param_shapes(module)
    assert model.count_params(shapes) == 2_843_053_056
    mixer = lambda i: model.count_params(shapes[f"layer_{i}"]["mixer"])
    assert (mixer(1), mixer(3), mixer(2)) == (44_187_648, 63_135_744, 113_246_208)
    assert mixer(4) == 786_432 + 33 * 9_437_184
    assert model.count_params(shapes["layer_3"]["mixer"]["gate"]) == 3072 * 72


def test_the_rings_and_the_pools_are_the_bytes_the_configuration_states():
    """24 slots x 32,768 tokens of pool for the two full layers, a ring of
    1,024 lines a slot for each of the six window layers, whatever the
    context (shapes alone: nothing is allocated here)."""
    from scaling_tpu.nn.window_attention import ring_lines

    config = cells.load_json(cells.ROOT / "configs" / f"{CONFIG}.json")
    engine = model.engine_config(config["engine"])
    arch = config["transformer_architecture"]
    ring = ring_lines(arch["window_size"], engine.mixed_width)
    assert ring == 1024 >= arch["window_size"] - 1 + engine.prefill_chunk
    line = 2 * arch["attention_num_kv_heads"] * arch["attention_head_dim"] * 2
    assert line == 4096
    assert 6 * engine.num_slots * ring * line == 603_979_776           # 0.60 GB
    assert 2 * (engine.num_blocks - 1) * engine.block_size * line == 6_442_450_944
    assert engine.mixed_widths == (896, 24 * 256)
    assert engine.token_budget == 3 * 256 + 24


def test_the_traffic_is_the_issues_and_fits_the_slots():
    """``mixedlen32k-burst24``: 24 at once every whole second the rate rule
    gives; no request asks for more than a slot's 32,768 positions or names a
    token outside the 12,544 rows held."""
    traffic = cells.load_json(cells.ROOT / "traffic" / f"{TRAFFIC}.json")
    assert (traffic["generator"], traffic["backlog"], traffic["burst_size"],
            traffic["shape_seed"], traffic["warm_seconds"]) == (
        "bursts", "cut", 24, 68, 20)
    assert traffic["burst_every_s"] == int(traffic["burst_every_s"]) >= 1
    assert traffic["prompt"] == {"median": 4096, "sigma": 1.2, "min": 256, "max": 30720}
    assert traffic["output"] == {"median": 256, "sigma": 0.6, "min": 32, "max": 1024}
    assert "tokens" not in traffic   # above the knee: whatever the engine completes
    assert traffic["check_requests"] == 4 and traffic["check_max_tokens"] == 8192
    config = cells.load_json(cells.ROOT / "configs" / f"{CONFIG}.json")
    context = config["engine"]["context"]
    assert traffic["max_total"] == context == 32_768
    vocab = config["transformer_architecture"]["vocab_size"]
    requests = cells.load_cell(CELL).generate(traffic, 2**31 + 5, 51.0, vocab)
    counted = [r for r in requests if r.due_s >= 0]
    assert len(counted) == 24 * len({r.due_s for r in counted})
    assert sum(r.due_s < 0 for r in requests) == 24     # one uncounted burst
    assert max(len(r.prompt) + r.output_len for r in requests) <= context
    assert min(r.output_len for r in requests) >= 32
    assert all(1 <= t < vocab for r in requests[:4] for t in r.prompt)
    assert max(t for r in requests[:8] for t in r.prompt) > vocab // 2
    prompts = [len(r.prompt) for r in counted]
    mean_prompt = sum(prompts) / len(prompts)
    mean_output = sum(r.output_len for r in counted) / len(counted)
    assert 6200 < mean_prompt < 7700 and 280 < mean_output < 330
    # short and long in ONE queue: some dense in every layer, some past 16k
    assert 0.02 < sum(p <= 512 for p in prompts) / len(prompts) < 0.08
    assert 0.08 < sum(p > 16_384 for p in prompts) / len(prompts) < 0.16
    # the rows the check draws from wrap a ring of 1,024 lines
    assert sum(2304 <= p + r.output_len <= 8192
               for p, r in zip(prompts, counted)) / len(prompts) > 0.35
    assert math.isclose(traffic["trace_seconds"], 3.0)
