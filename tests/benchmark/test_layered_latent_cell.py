"""What PR 75 brings for ``dots3-note-prev-serve`` as files (``reference/`` and
``views/layered_latent_moe_decoder.py``, ``readers/layered_latent.py``,
``layered_latent_ops_count.py``, five metrics,
``traffic/mixedlen64k-burst16.json``), rehearsed on the CPU at a toy width
through a copy of ``benchmark/`` into which only a toy configuration is added;
and the readers on recorded rows. Membership is pinned, never position or a
literal list: the next configuration's PR appends after these entries."""

import json
import shutil
from pathlib import Path

import pytest

from benchmark import cells, layered_latent_ops_count as ops_count, model, serve_kind
from benchmark.readers import layered_latent as ll

DATA = Path(__file__).parent / "data"
TOY = DATA / "toy_layered_latent"
BENCH = TOY / "BENCHMARK.json"
CELL = "serve-dots3-mixedlen64k-burst"
CONFIG = "dots3-note-prev-serve"
TRAFFIC = "mixedlen64k-burst16"
REFERENCE = "layered_latent_moe_decoder"
LAGUNA, DSV32 = "serve-laguna-s-mixedlen-burst", "serve-dsv32-longdoc-burst"
METRICS = {
    "window_latent_time_pct.saturated": (
        "windowed latent attention", "device_trace", "lower", "window_latent_time_pct"),
    "window_latent_roofline.saturated": (
        "windowed latent attention", "device_trace", "higher", "window_latent_roofline"),
    "sparse_full_time_pct.saturated": (
        "sparse latent attention", "device_trace", "lower", "sparse_full_time_pct"),
    "window_latent_chunk_row_pct.saturated": (
        "windowed latent attention", "program_counter", "higher",
        "window_latent_chunk_row_pct"),
    "tick_mfu_pct.layered_latent": (
        "engine tick", "program_counter", "higher", "tick_mfu_pct"),
}
CONFIG_FILE = cells.load_json(cells.ROOT / "configs" / f"{CONFIG}.json")
ARCH = CONFIG_FILE["transformer_architecture"]
FULL = {"heads": 128, "q_lora": 1024, "kv_lora": 512, "nope": 128, "rope": 64, "v": 128}
WINDOW = {"heads": 64, "q_lora": 1024, "kv_lora": 1024, "nope": 192, "rope": 64, "v": 128}


@pytest.fixture(scope="module")
def grown_dots3(grown):
    """``grown`` (the toy burst traffic is there) plus the one toy
    configuration and a chat traffic; reference, view, readers and metrics are
    the benchmark's own."""
    shutil.copy(TOY / "configs" / "toy-dots3.json", grown / "configs")
    shutil.copy(DATA / "toy_hc_latent" / "traffic" / "toy-hc-chat.json",
                grown / "traffic")
    for part, name in (("reference", f"{REFERENCE}.py"), ("views", f"{REFERENCE}.py"),
                       ("readers", "layered_latent.py")):
        assert (cells.ROOT / part / name).is_file() and (grown / part / name).is_file()
    return grown


def rehearse(run, root, trace=0, *more, workload="toy-serve-dots3", seconds="1.5"):
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
    toy = cells.load_json(TOY / "configs" / "toy-dots3.json")["transformer_architecture"]
    for key in ("attention_gate", "latent_lora_rescale", "moe_router",
                "moe_routed_scaling_factor", "moe_norm_topk_prob", "moe_norm_topk_eps",
                "rotary_embedding_base", "window_latent_rotary_embedding_base",
                "moe_glu", "mlp_type", "weight_tying", "moe_top_k",
                "layer_pattern", "moe_experts_first"):
        assert toy[key] == ARCH[key], key
    # two geometries: the windowed kind has half the heads, a wider latent and
    # a wider key; the window is odd; a share of the experts held
    for arch in (toy, ARCH):
        assert arch["window_latent_num_attention_heads"] * 2 == arch["num_attention_heads"]
        assert arch["window_latent_kv_lora_rank"] == 2 * arch["kv_lora_rank"]
        assert arch["window_latent_qk_nope_head_dim"] * 2 == 3 * arch["qk_nope_head_dim"]
        assert arch["window_size"] % 2 == 1
        assert arch["moe_experts_held"] < arch["moe_num_experts"]
    assert (ARCH["moe_experts_held"], ARCH["moe_num_experts"]) == (32, 256)


def test_the_toy_cell_is_correct_and_its_ticks_say_what_both_attentions_did(
        run, grown_dots3, capsys, monkeypatch):
    """The engine serves the stack through the pool (the sparse full layers)
    and the rings (the windowed latent layers; the ring kernel interpreted),
    every checked token within the tolerance of the reference's best logit; the
    traced part's ticks carry both kinds' fields and the counters move."""
    from scaling_tpu import obs

    seen = spy_on_the_kind(monkeypatch)
    result = rehearse(run, grown_dots3, trace=2)
    assert result["correct"] and result["failed"] == 0 and result["unserved"] > 0
    assert seen["outcome"]["host"]["worst_logit_gap"] < 1e-3
    # the CPU has no device plane and no published peak: the readers of the
    # trace and of the peak find nothing and are left out
    assert set(result["metrics"]) == {
        "setup_s", "serve_tokens_per_s", "batch_occupancy_pct",
        "sparse_chosen_pct.saturated", "window_latent_chunk_row_pct.saturated"}
    assert 0 < result["metrics"]["window_latent_chunk_row_pct.saturated"]["value"] < 100
    capture = obs.last_capture()
    mixed = ll.ring_ticks(capture.spans)
    assert mixed and all(f["window_latent_layers"] == 3 and f["sparse_layers"] == 2
                         for f in mixed)
    rows = sum(f["window_latent_single_rows"] + f["window_latent_chunk_rows"]
               for f in mixed)
    moved = {path: sum(v for k, v in capture.counters.items()
                       if k.startswith("serve_window_latent_rows_total") and path in k)
             for path in ("single", "chunk")}
    assert sum(moved.values()) == 3 * rows and min(moved.values()) > 0
    # under the window a query sees 17 lines at most
    assert all(f["window_latent_pairs"] <= 17 * f["tokens"] for f in mixed)
    ctx = {"device": {"peaks": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}},
           "config": cells.load_json(grown_dots3 / "configs" / "toy-dots3.json"),
           "host": {}}
    assert 0 < ll.tick_mfu_pct(ctx) < 1.0
    # no device plane: nothing under the scopes, so nothing, not 0
    assert ll.window_latent_time_pct(ctx) is None and ll.sparse_full_time_pct(ctx) is None
    assert ll.window_latent_roofline(ctx) is None


def test_a_plain_cell_reads_none_of_the_new_metrics(run, grown, capsys):
    """A plain model's spans carry no ``window_latent_pairs``: the readers
    return nothing and do not raise, whatever its trace's scopes. What the
    parent commit's program gives under this PR's benchmark files."""
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
        assert getattr(ll, reader)(ctx) is None
    assert ll.window_latent_time_pct(ctx, ops=OPS) is None   # scopes, no field
    assert ll.window_latent_roofline(ctx, ops=OPS) is None


def test_the_control_fails_the_limit_the_program_keeps(run, grown_dots3, capsys,
                                                       monkeypatch):
    """``--control fp8``: the reference with fp8 matrices misses the limit
    that the program keeps with room."""
    seen = spy_on_the_kind(monkeypatch)
    result = rehearse(run, grown_dots3, 0, "--control", "fp8",
                      workload="toy-serve-dots3-chat", seconds="3")
    assert result["correct"]
    host = seen["outcome"]["host"]
    sound, control = host["worst_logit_gap"], host["control_logit_gap"]
    assert sound < serve_kind.LOGIT_TOL / 2 < serve_kind.LOGIT_TOL < control
    assert control > 3 * sound


# ---- the readers on recorded rows -----------------------------------------

LAYER = "jit(mixed_896)/jit(_lambda_)/"
KERNEL = ('%latent_ring_attention.3 = bf16[1,16384,1024] custom-call(...), '
          'custom_call_target="tpu_custom_call"')
# name, start_ns, dur_ns, op_name: what ``load_scoped_ops`` gives
OPS = [
    ["%fusion.1 = bf16[896,5120] fusion(...)", 0.0, 100e3, ""],                 # embedding
    ["%fusion.5 = bf16[896,16384] fusion(...)", 100e3, 200e3,
     LAYER + "window_latent_attn/dot_general"],
    [KERNEL, 300e3, 150e3,
     LAYER + "window_latent_attn/window_latent_attend/while/body/cond/branch_1_fun/"
     "jit(latent_ring_attention)/latent_ring_attention/pallas_call"],
    ["%fusion.6 = bf16[896,64,1152] fusion(...)", 450e3, 25e3,
     LAYER + "window_latent_attn/window_latent_attend/scatter"],
    ["%fusion.7 = bf16[896,64,128] fusion(...)", 475e3, 25e3,
     LAYER + "window_latent_attn/gate/mul"],
    ["%fusion.11 = bf16[896,24576] fusion(...)", 500e3, 500e3,
     LAYER + "attn/indexer/dot_general"],
    ["%fusion.12 = bf16[896,128,128] fusion(...)", 1000e3, 100e3,
     LAYER + "attn/gate/mul"],
    ["%fusion.13 = f32[896,256] fusion(...)", 1100e3, 600e3, LAYER + "moe/dot_general"],
    ["%fusion.40 = bf16[16,19008] fusion(...)", 1700e3, 300e3,
     "jit(mixed_896)/head/dot_general"],
]
RING = {"window_latent_layers": 3, "window_latent_rows_past": 10}
SPANS = [
    ("serve.tick", 0, 40e6, {"step": 1}),
    ("serve.mixed", 0, 39e6, {
        "step": 1, "tokens": 780, **RING, "window_latent_visible_lines": 8_000,
        "window_latent_pairs": 400_000, "window_latent_single_rows": 12,
        "window_latent_chunk_rows": 3, "sparse_layers": 2, "index_pairs": 9_000_000,
        "chosen_pairs": 1_500_000}),
    ("serve.tick", 50e6, 40e6, {"step": 2}),
    ("serve.mixed", 50e6, 39e6, {
        "step": 2, "tokens": 16, **RING, "window_latent_visible_lines": 8_208,
        "window_latent_pairs": 8_208, "window_latent_single_rows": 16,
        "window_latent_chunk_rows": 0, "sparse_layers": 2, "index_pairs": 200_000,
        "chosen_pairs": 32_768}),
    ("serve.mixed", 100e6, 5e6, {"step": 3}),  # a tick of the warm-up: no field
]
COUNTERS = {"serve_prefill_tokens_total": 768, "serve_tokens_generated_total": 28,
            "serve_moe_assignments_total": 4 * 796}
CTX = {"device": {"peaks": {"hbm_bytes_per_s": 819e9, "flops_per_s": 197e12}},
       "config": {"transformer_architecture": ARCH}, "host": {}, "trace": None}
SHAPE = dict(full_layers=2, window_layers=3, dense_layers=1, routed_layers=4,
             hidden=5120, vocab=19_008, dense_width=13_824, expert_width=1536,
             shared_width=1536, num_experts=256, full=FULL, window=WINDOW,
             index_heads=64, index_dim=128)
ABSORBED = 2 * 64 * (2 * 1024 + 64)


def test_the_counts_are_the_issues_by_hand():
    assert ll.sizes(ARCH) == FULL and ll.window_sizes(ARCH) == WINDOW
    # the ISSUE's 144,048,384 less the indexer's 9,371,904, and 90,832,896
    # (the norms' vectors are not matrices)
    assert ops_count.layer_matmul_params(5120, FULL) == 144_048_384 - 9_371_904
    assert ops_count.layer_matmul_params(5120, WINDOW) == 90_832_896
    assert ABSORBED == 270_336
    # one query over 513 lines: absorbed; a chunk of 256 over 768: expanded
    assert ops_count.window_flops(513, 513, WINDOW) == 513 * ABSORBED
    expanded = 2 * 1024 * 64 * (192 + 128) * 768 + 2 * 64 * (192 + 64 + 128) * 256 * 640
    assert ops_count.window_flops(768, 256 * 640, WINDOW) == expanded < 256 * 640 * ABSORBED
    assert ops_count.window_bytes(1, WINDOW, 2) == 2176
    per_token = (2 * (144_048_384 - 256) + 3 * 90_832_896 + 212_336_640
                 + 4 * (1_310_720 + 23_592_960))
    assert ops_count.serve_flops(1, 0, 0, 0, 0, 0, 0, **SHAPE) == 2.0 * per_token
    assert ops_count.serve_flops(0, 1, 0, 0, 0, 0, 0, **SHAPE) == 2.0 * 5120 * 19_008
    assert ops_count.serve_flops(0, 0, 1, 0, 0, 0, 0, **SHAPE) == 2.0 * 23_592_960
    assert ops_count.serve_flops(0, 0, 0, 1, 1, 0, 0, **SHAPE) == 2 * (
        2 * 128 * (2 * 512 + 64) + 2 * 64 * 128)
    assert ops_count.serve_flops(0, 0, 0, 0, 0, 1, 1, **SHAPE) == 3 * ABSORBED


def test_readers_give_the_five_values_by_hand():
    assert ll.union_seconds(OPS) == pytest.approx(2.0e-3)
    # window_latent_attn: 0.2 + 0.15 + 0.025 + 0.025 ms; attn: 0.5 + 0.1 ms
    assert ll.window_latent_time_pct(CTX, ops=OPS, spans=SPANS) == pytest.approx(
        100 * 0.4 / 2.0)
    assert ll.sparse_full_time_pct(CTX, ops=OPS, spans=SPANS) == pytest.approx(
        100 * 0.6 / 2.0)
    # tick 1 is bound by its pairs' FLOPs (50 pairs a line: absorbed is the
    # cheaper form), tick 2 by its lines' bytes; the time is the kernel's
    # alone, by its name
    flops = ops_count.window_flops(8_000, 400_000, WINDOW)
    assert flops == 400_000 * ABSORBED and flops / 197e12 > 8_000 * 2176 / 819e9
    assert 8_208 * ABSORBED / 197e12 < 8_208 * 2176 / 819e9
    least = 3 * (flops / 197e12 + 8_208 * 2176 / 819e9)
    assert ll.window_latent_roofline(CTX, ops=OPS, spans=SPANS) == pytest.approx(
        100 * least / 0.15e-3)
    total = ops_count.serve_flops(796, 28, 4 * 796, 1_532_768, 9_200_000, 408_208,
                                  16_208, **SHAPE)
    assert ll.tick_mfu_pct(CTX, spans=SPANS, counters=COUNTERS) == pytest.approx(
        100 * total / 0.080 / 197e12)
    assert ll.window_latent_chunk_row_pct(CTX, spans=SPANS) == pytest.approx(100 * 3 / 31)
    assert ll.window_latent_chunk_row_pct(CTX, spans=SPANS[4:]) is None


def test_no_share_can_pass_one_hundred_on_what_the_chip_can_do():
    everything = [[n, s, d, LAYER + "window_latent_attn/x"] for n, s, d, _ in OPS]
    assert ll.window_latent_time_pct(CTX, ops=everything, spans=SPANS) == pytest.approx(100)
    # a kernel that takes the least its pairs and its lines allow reads 100
    least = 3 * (ops_count.window_flops(8_000, 400_000, WINDOW) / 197e12
                 + 8_208 * 2176 / 819e9)
    at_the_rate = [[KERNEL, 0.0, 1e9 * least,
                    LAYER + "window_latent_attn/window_latent_attend/x"]]
    assert ll.window_latent_roofline(CTX, ops=at_the_rate, spans=SPANS) == pytest.approx(100.0)
    flops = ops_count.serve_flops(16, 16, 100, 32_768, 200_000, 8_208, 8_208, **SHAPE)
    spans = [("serve.tick", 0, 1e9 * flops / 197e12, {"step": 1}), SPANS[3]]
    assert ll.tick_mfu_pct(CTX, spans=spans, counters={
        "serve_tokens_generated_total": 16,
        "serve_moe_assignments_total": 100}) == pytest.approx(100.0)


def test_without_the_scope_the_kernel_or_the_field_a_reader_gives_none_not_zero():
    bare = [[name, start, dur, ""] for name, start, dur, _ in OPS]
    no_field = SPANS[4:]
    for reader in (ll.window_latent_time_pct, ll.sparse_full_time_pct,
                   ll.window_latent_roofline):
        assert reader(CTX, ops=bare, spans=SPANS) is None
        assert reader(CTX, ops=[], spans=SPANS) is None
        assert reader(CTX, ops=OPS, spans=no_field) is None
    # the walk's scope without the kernel's name in it: no roofline
    unnamed = [op for op in OPS if op[0] != KERNEL]
    assert ll.window_latent_roofline(CTX, ops=unnamed, spans=SPANS) is None
    assert ll.tick_mfu_pct(CTX, spans=no_field, counters=COUNTERS) is None
    assert ll.tick_mfu_pct(CTX, spans=SPANS, counters={}) is None
    no_peak = {**CTX, "device": {"peaks": None}}
    assert ll.tick_mfu_pct(no_peak, spans=SPANS, counters=COUNTERS) is None
    assert ll.window_latent_roofline(no_peak, ops=OPS, spans=SPANS) is None
    other = {**CTX, "config": {"transformer_architecture": {
        **ARCH, "layer_pattern": ["latent", "moe"]}}}
    assert ll.window_latent_roofline(other, ops=OPS, spans=SPANS) is None
    assert ll.tick_mfu_pct(other, spans=SPANS, counters=COUNTERS) is None
    near = [["%f = ...", 0.0, 1e3, "jit(mixed)/window_latent_attn_out/mul"],
            ["%g = ...", 1e3, 1e3, "jit(mixed)/full_attn/mul"]]
    assert ll.window_latent_time_pct(CTX, ops=near, spans=SPANS) is None
    assert ll.sparse_full_time_pct(CTX, ops=near, spans=SPANS) is None


# ---- the files, by name and by membership ---------------------------------

def test_metric_files_name_the_readers_and_the_cell_is_in_each_list():
    bench = json.loads((cells.REPO / "BENCHMARK.json").read_text())
    entries = {m["name"]: m for m in bench["per_layer"]}
    assert set(METRICS) <= set(entries)
    for name, (layer, source, better, reader) in METRICS.items():
        spec = cells.load_json(cells.ROOT / "metrics" / f"{name}.json")
        assert spec["reader"] == f"layered_latent:{reader}"
        assert spec["unit"] == entries[name]["unit"] == "%"
        assert (entries[name]["layer"], entries[name]["source"],
                entries[name]["better"]) == (layer, source, better)
        assert entries[name]["moves"] == "serve_tokens_per_s"
        assert CELL in entries[name]["workloads"]
        assert callable(cells.load_reader(name))
    # the cell is in every list Laguna's and DeepSeek-V3.2-Exp's cells share
    # (the routed MLP's two among them)
    listed = {m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", [])}
    shared = {m["name"] for m in bench["per_layer"]
              if {LAGUNA, DSV32} <= set(m.get("workloads", []))}
    assert len(shared) >= 41 and shared <= listed
    assert {"moe_time_pct.saturated", "moe_load_max_over_mean.saturated"} <= listed
    assert {n for n in listed if n.startswith("tick_mfu_pct")} == {
        "tick_mfu_pct.layered_latent"}
    serve = next(m for m in bench["end_to_end"] if m["name"] == "serve_tokens_per_s")
    assert CELL in serve["workloads"]
    # appended: wherever this cell and Laguna's are listed, this one comes after
    for m in bench["end_to_end"] + bench["per_layer"]:
        cells_of = m.get("workloads", [])
        if CELL in cells_of and LAGUNA in cells_of:
            assert cells_of.index(CELL) > cells_of.index(LAGUNA)
    cell = cells.load_cell(CELL)
    assert cell.reference_name == REFERENCE and cell.chips == 1
    assert [m["name"] for m in cell.metrics("end_to_end")] == ["setup_s", "serve_tokens_per_s"]
    names = [w["name"] for w in bench["workloads"]]
    configs = [c["name"] for c in bench["configs"]]
    assert CELL in names and names.index(CELL) > names.index(LAGUNA)
    assert CONFIG in configs and configs.index(CONFIG) > configs.index("laguna-s-2.1-serve")
    entry = bench["workloads"][names.index(CELL)]
    assert (entry["config"], entry["traffic"], entry["chips"]) == (CONFIG, TRAFFIC, 1)
    assert len(entry["why"]) <= 200
    for word in ("16 slots x 65,536", "1 of 8 ranks", "2 : 3", "13 : 33", "5 of 46"):
        assert word in entry["why"], word
    assert len(names) <= 24 and sum(w["chips"] == 4 for w in bench["workloads"]) == 1


def test_the_cell_resolves_to_its_reference_view_and_generator():
    cell = cells.load_cell(CELL)
    for name in cells.REFERENCE_CONTRACT:
        assert callable(getattr(cell.reference, name))
    for name in cells.VIEW_CONTRACT:
        assert callable(getattr(cell.view, name))
    assert Path(cell.reference.__file__).stem == Path(cell.view.__file__).stem \
        == cell.config["reference"] == REFERENCE
    bursts = cells.load_module(cells.ROOT, "generators", "bursts",
                               cells.GENERATOR_CONTRACT).generate
    assert cell.generate.__code__.co_code == bursts.__code__.co_code
    spec = cell.view.reference_spec(ARCH)
    assert spec["kinds"] == ("full", "full", "window", "window", "window")
    assert spec["sizes"] == ((128, 1024, 512, 128, 64, 128, 80_000_000.0),
                             (64, 1024, 1024, 192, 64, 128, 50_000.0))
    assert (spec["window"], spec["hidden"], spec["rescale"], spec["num_dense"]) == (
        513, 5120, True, 1)
    assert (spec["index_heads"], spec["index_dim"], spec["index_topk"]) == (64, 128, 2048)
    assert (spec["top_k"], spec["scale"], spec["gate_eps"], spec["experts_first"],
            spec["shared"], spec["eps"]) == (8, 1.0, 1e-20, 0, True, 1e-5)
    with pytest.raises(SystemExit, match="the configuration states {'attention_gate': 'none'"):
        cell.view.reference_spec({k: v for k, v in ARCH.items() if k != "attention_gate"})
    with pytest.raises(SystemExit, match="lacks \\['index_topk'\\]"):
        cell.view.reference_spec({**ARCH, "index_topk": None})
    with pytest.raises(SystemExit, match="the configuration states {'moe_router': 'softmax'"):
        cell.view.reference_spec({**ARCH, "moe_router": "softmax"})
    with pytest.raises(SystemExit, match="layer_pattern is \\(latent \\| window_latent"):
        cell.view.reference_spec({**ARCH, "layer_pattern": ["attention", "moe"]})


def test_the_reference_imports_nothing_of_the_program():
    source = (cells.ROOT / "reference" / f"{REFERENCE}.py").read_text()
    imports = [line for line in source.splitlines()
               if line.startswith(("import ", "from "))]
    assert imports and not any("scaling_tpu" in line for line in imports)
    assert all(line.startswith(("from __future__", "import functools", "import jax",
                                "from benchmark.reference.")) for line in imports)
    assert 'jax.default_matmul_precision("highest")' in source
    for departure in ("one rank's share", "multi-token-prediction", "vision or audio",
                      "FP8"):
        assert departure in source, departure


def test_the_configuration_names_every_key_it_changed_and_cuts_no_width():
    """Every number of the catalog row under the same key; depth, the experts
    held, the vocabulary's slice and the positions alone reduced, each with
    published beside run; every item the ISSUE lists under ``assumed``, the
    rescale's reading first; what is left out named."""
    config = CONFIG_FILE
    bench = json.loads((cells.REPO / "BENCHMARK.json").read_text())
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json" and len(entry["why"]) <= 200
    assert entry["source"] == config["source"] == (
        "https://huggingface.co/dots-studio/dots3-note-prev/blob/main/config.json")
    published, reduced, arch = config["published"], config["reduced"], ARCH
    assert sorted(entry["reduced"]) == sorted(reduced) == sorted(
        ["num_hidden_layers", "n_routed_experts", "vocab_size", "max_position_embeddings"])
    assert len(published) >= 45
    for key, value in published.items():
        if key in reduced:
            assert reduced[key]["published"] == value and reduced[key]["run"] == config[key] != value
            assert reduced[key]["why"]
        else:
            assert config[key] == value, f"{key} differs and is not in reduced"
    assert {k: reduced[k]["run"] for k in reduced} == {
        "num_hidden_layers": 5, "n_routed_experts": 32, "vocab_size": 19_008,
        "max_position_embeddings": 65_536}
    blocks = arch["num_layers"] // 2
    as_run = {
        "hidden_size": arch["hidden_size"], "num_hidden_layers": blocks,
        "num_attention_heads": arch["num_attention_heads"],
        "q_lora_rank": arch["q_lora_rank"], "kv_lora_rank": arch["kv_lora_rank"],
        "qk_nope_head_dim": arch["qk_nope_head_dim"],
        "qk_rope_head_dim": arch["qk_rope_head_dim"], "v_head_dim": arch["v_head_dim"],
        "swa_num_attention_heads": arch["window_latent_num_attention_heads"],
        "swa_q_lora_rank": arch["window_latent_q_lora_rank"],
        "swa_kv_lora_rank": arch["window_latent_kv_lora_rank"],
        "swa_qk_nope_head_dim": arch["window_latent_qk_nope_head_dim"],
        "swa_qk_rope_head_dim": arch["window_latent_qk_rope_head_dim"],
        "swa_v_head_dim": arch["window_latent_v_head_dim"],
        "swa_rope_theta": arch["window_latent_rotary_embedding_base"],
        "rope_theta": arch["rotary_embedding_base"],
        "sliding_window_size": arch["window_size"],
        "index_n_heads": arch["index_n_heads"], "index_head_dim": arch["index_head_dim"],
        "index_topk": arch["index_topk"],
        "intermediate_size": int(arch["hidden_size"] * arch["mlp_factor"]),
        "moe_intermediate_size": arch["moe_expert_width"],
        "n_routed_experts": arch["moe_experts_held"],
        "num_experts_per_tok": arch["moe_top_k"],
        "routed_scaling_factor": arch["moe_routed_scaling_factor"],
        "norm_topk_prob": arch["moe_norm_topk_prob"],
        "apply_mla_qkv_lora_rescale": arch["latent_lora_rescale"],
        "vocab_size": arch["vocab_size"],
        "max_position_embeddings": arch["sequence_length"],
        "rms_norm_eps": arch["layernorm"]["layernorm_epsilon"],
        "tie_word_embeddings": arch["weight_tying"],
        "attention_bias": arch["attention_bias"],
    }
    assert {key: config[key] for key in as_run} == as_run
    assert arch["moe_num_experts"] == published["n_routed_experts"] == 256
    assert arch["moe_shared_expert_width"] == (
        published["n_shared_experts"] * published["moe_intermediate_size"])
    assert published["rope_scaling"] is None and "rope_scaling" not in arch
    assert (published["attention_gate_type"], published["swa_attention_gate_type"],
            arch["attention_gate"]) == ("headwise", "headwise", "per_head")
    assert (published["scoring_func"], published["topk_method"], arch["moe_router"]) == (
        "sigmoid", "noaux_tc", "sigmoid_bias")
    # the pattern is the published list's first five, the leading FFN dense
    kinds = {"full_attention": "latent", "sliding_attention": "window_latent"}
    assert arch["layer_pattern"] == [
        name for l in range(blocks)
        for name in (kinds[published["layer_types"][l]],
                     "mlp" if l < published["first_k_dense_replace"] else "moe")]
    types = published["layer_types"]
    assert (len(types), types.count("full_attention")) == (46, 13)
    assert types[2:6] == ["sliding_attention"] * 3 + ["full_attention"]
    assert list(config["assumed"])[:6] == [
        "rescale", "gate", "window", "indexer", "layout", "init"]
    assert "LongCat-Flash" in config["assumed"]["rescale"]
    for left_out in ("multi-token-prediction", "vision encoder", "audio encoder"):
        assert left_out in config["assumed"]["left_out"] and left_out in config["stands_for"]
    for said in ("ONE of the 8 chips", "2 : 3", "13 : 33", "4,087,154,176", "8.17 GB",
                 "279,551,726,592"):
        assert said in config["stands_for"], said
    assert config["engine"] == {"num_slots": 16, "context": 65_536,
                                "enable_prefix_cache": False, "prefill_chunk": 256,
                                "token_budget": 784}
    assert config["chips"] == 1


def program_shapes(arch):
    from scaling_tpu.models.transformer.model import init_model

    module = init_model(model.transformer_config(
        {**CONFIG_FILE, "transformer_architecture": arch}, {}), None)
    return model.param_shapes(module)


def test_the_parameter_count_is_the_programs_own_tree():
    shapes = program_shapes(ARCH)
    assert model.count_params(shapes) == CONFIG_FILE["parameters"] == 4_087_154_176
    mixer = lambda i: model.count_params(shapes[f"layer_{i}"]["mixer"])
    # the ISSUE's figures and the two latent norms' vectors
    assert (mixer(1), mixer(5), mixer(2)) == (
        144_048_384 + 1536, 90_832_896 + 2048, 212_336_640)
    assert mixer(4) == 1_310_720 + 256 + 33 * 23_592_960 == 779_878_656
    assert model.count_params(shapes["layer_5"]["mixer"]["gate"]) == 5120 * 64
    assert model.count_params(shapes["layer_1"]["mixer"]["gate"]) == 5120 * 128
    assert "index_q_proj" in shapes["layer_3"]["mixer"]
    assert "index_q_proj" not in shapes["layer_5"]["mixer"]


def test_at_the_published_depth_experts_and_vocabulary_the_tree_is_the_language_model():
    """46 blocks by the published ``layer_types``, all 256 experts, the whole
    vocabulary: 279.55 B parameters (``described_as``'s 288 B counts the MTP
    module and the towers, which the row's config does not hold)."""
    published = CONFIG_FILE["published"]
    kinds = {"full_attention": "latent", "sliding_attention": "window_latent"}
    pattern = [name for l, kind in enumerate(published["layer_types"])
               for name in (kinds[kind], "mlp" if l < 1 else "moe")]
    shapes = program_shapes({
        **ARCH, "layer_pattern": pattern, "num_layers": len(pattern),
        "vocab_size": published["vocab_size"], "moe_experts_held": None})
    count = model.count_params(shapes)
    routed = 1_310_720 + 256 + 257 * 23_592_960 + 5120
    assert count == CONFIG_FILE["published_parameters"] == 279_551_726_592 == (
        13 * (144_049_920 + 5120) + 33 * (90_834_944 + 5120)
        + 212_336_640 + 5120 + 45 * routed + 2 * 152_064 * 5120 + 5120)
    assert 279.5e9 < count < 279.6e9


def test_the_rings_and_the_pools_are_the_bytes_the_configuration_states():
    """16 slots x 65,536 tokens of pool for the two full layers (two leaves,
    640 + 128 lanes), a ring of 1,024 lines of 1,152 lanes a slot for each of
    the three sliding layers, whatever the context (shapes alone)."""
    from scaling_tpu.nn.latent_paged_attention import rope_line_width
    from scaling_tpu.nn.window_attention import ring_lines

    engine = model.engine_config(CONFIG_FILE["engine"])
    ring = ring_lines(ARCH["window_size"], engine.mixed_width)
    assert ring == 1024 >= ARCH["window_size"] - 1 + engine.prefill_chunk
    lanes = ARCH["window_latent_kv_lora_rank"] + rope_line_width(
        ARCH["window_latent_qk_rope_head_dim"])
    assert lanes == 1152
    assert 3 * engine.num_slots * ring * lanes * 2 == 113_246_208          # 0.11 GB
    paged = ARCH["kv_lora_rank"] + rope_line_width(ARCH["qk_rope_head_dim"]) \
        + ARCH["index_head_dim"]
    assert paged * 2 == 1536
    assert 2 * (engine.num_blocks - 1) * engine.block_size * paged * 2 == 3_221_225_472
    assert engine.mixed_widths == (896, 16 * 256)
    assert engine.token_budget == 3 * 256 + 16


def test_the_traffic_is_the_issues_and_fits_the_slots():
    """``mixedlen64k-burst16``: 16 at once every whole second the rate rule
    gives; no request asks for more than a slot's 65,536 positions or names a
    token outside the 19,008 rows held."""
    traffic = cells.load_json(cells.ROOT / "traffic" / f"{TRAFFIC}.json")
    assert (traffic["generator"], traffic["backlog"], traffic["burst_size"],
            traffic["shape_seed"], traffic["warm_seconds"]) == (
        "bursts", "cut", 16, 75, 20)
    assert traffic["burst_every_s"] == int(traffic["burst_every_s"]) >= 1
    assert traffic["prompt"] == {"median": 6144, "sigma": 1.2, "min": 256, "max": 61440}
    assert traffic["output"] == {"median": 256, "sigma": 0.6, "min": 32, "max": 1024}
    assert "tokens" not in traffic   # above the knee: whatever the engine completes
    assert traffic["check_requests"] == 4 and traffic["check_max_tokens"] == 8192
    assert traffic["trace_seconds"] == 3.0
    for said in ("sweep", "2.0 x", "note-taking"):
        assert said in traffic["why"], said
    context = CONFIG_FILE["engine"]["context"]
    assert traffic["max_total"] == context == 65_536
    vocab = ARCH["vocab_size"]
    requests = cells.load_cell(CELL).generate(traffic, 2**31 + 5, 51.0, vocab)
    counted = [r for r in requests if r.due_s >= 0]
    assert len(counted) == 16 * len({r.due_s for r in counted})
    assert sum(r.due_s < 0 for r in requests) == 16     # one uncounted burst
    assert max(len(r.prompt) + r.output_len for r in requests) <= context
    assert min(r.output_len for r in requests) >= 32
    assert all(1 <= t < vocab for r in requests[:4] for t in r.prompt)
    assert max(t for r in requests[:8] for t in r.prompt) > vocab // 2
    prompts = [len(r.prompt) for r in counted]
    # short and long in ONE queue: some dense in a full layer, some past 16k
    assert sum(p <= 2048 for p in prompts) and sum(p > 16_384 for p in prompts)
    # the rows the check draws from pass the window and the indexer's 2,048
    assert sum(2048 < p + r.output_len <= 8192
               for p, r in zip(prompts, counted)) / len(prompts) > 0.2
