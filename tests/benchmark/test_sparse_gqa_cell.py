"""What PR 61 brings for ``keye-vl-2.0-30b-a3b-serve`` as files (``reference/``
and ``views/sparse_gqa_moe_decoder.py``, ``readers/sparse_gqa.py``,
``sparse_gqa_ops_count.py``, three metrics, ``traffic/longctx64k-burst8.json``),
rehearsed on the CPU at a toy width through a copy of ``benchmark/`` into which
only a toy configuration is added; and the readers, the new ones and the three
shared with the sparse latent cell, on recorded rows. Membership is pinned,
never position: the next configuration's PR appends after these entries. This
file also holds, for this configuration and cell, the facts ``test_configs.py``,
``test_files_by_name.py``, ``test_tick_gap.py`` and ``test_window_spans.py`` ask
of every configuration and cell (their tables are from before it)."""

import json
import shutil
from pathlib import Path

import pytest

from benchmark import cells, serve_kind, sparse_gqa_ops_count as ops_count
from benchmark.readers import sparse_gqa, sparse_latent

TOY = Path(__file__).parent / "data" / "toy_sparse_gqa"
BENCH = TOY / "BENCHMARK.json"
CELL = "serve-keye30b-longctx-burst"
CONFIG = "keye-vl-2.0-30b-a3b-serve"
TRAFFIC = "longctx64k-burst8"
DSV32 = "serve-dsv32-longdoc-burst"
REFERENCE = "sparse_gqa_moe_decoder"
LAYER_NAME = "sparse grouped-query attention"
METRICS = {
    "sparse_paged_roofline.saturated": (LAYER_NAME, "device_trace",
                                        "sparse_paged_roofline", "higher"),
    "sparse_gqa_time_pct.saturated": (LAYER_NAME, "device_trace",
                                      "sparse_gqa_time_pct", "lower"),
    "tick_mfu_pct.sparse_gqa": ("engine tick", "program_counter", "tick_mfu_pct", "higher"),
}
# PR 59's three that read either sparse kind
SHARED = ("indexer_roofline.saturated", "index_time_pct.saturated",
          "sparse_chosen_pct.saturated")


@pytest.fixture(scope="module")
def grown_sparse(grown):
    """``grown`` plus the one toy configuration and its traffic; reference,
    view, readers and metrics are the benchmark's own."""
    shutil.copy(TOY / "configs" / "toy-keye.json", grown / "configs")
    shutil.copy(TOY / "traffic" / "toy-sparse-gqa-burst.json", grown / "traffic")
    for part, name in (("reference", f"{REFERENCE}.py"), ("views", f"{REFERENCE}.py"),
                       ("readers", "sparse_gqa.py")):
        assert (cells.ROOT / part / name).is_file() and (grown / part / name).is_file()
    return grown


def rehearse(run, root, trace=0, *more, seconds="1.5"):
    return run.main(["--workload", "toy-serve-sparse-gqa", "--seed", "3000000019",
                     "--seconds", seconds, "--trace", str(trace), "--rehearse",
                     "--root", str(root), "--benchmark-json", str(BENCH), *more])


def spy_on_the_kind(monkeypatch):
    seen = {}
    real = serve_kind.run
    monkeypatch.setattr(serve_kind, "run", lambda cell, args, env: seen.setdefault(
        "outcome", real(cell, args, env)))
    return seen


def test_the_toy_states_the_configurations_equations():
    toy = cells.load_json(TOY / "configs" / "toy-keye.json")["transformer_architecture"]
    real = cells.load_json(cells.ROOT / "configs" / f"{CONFIG}.json")["transformer_architecture"]
    for key in ("moe_router", "moe_norm_topk_prob", "rotary_embedding_base", "moe_glu",
                "mlp_type", "weight_tying", "key_query_norm", "key_query_norm_scope",
                "attention_qkv_in_one", "attention_bias", "norm_type"):
        assert toy[key] == real[key], key
    assert toy["layer_pattern"][:2] == real["layer_pattern"][:2] == ["attention", "moe"]
    assert toy["num_attention_heads"] // toy["attention_num_kv_heads"] > 1   # grouped
    assert toy["index_topk"] == 16
    traffic = cells.load_json(TOY / "traffic" / "toy-sparse-gqa-burst.json")
    assert traffic["prompt"]["min"] > toy["index_topk"]


def test_sparse_gqa_serve_cell_is_correct_and_its_ticks_carry_what_was_chosen(
        run, grown_sparse, capsys, monkeypatch):
    """The engine serves the stack through the three-leaf pool, every checked
    token on the reference's best logit; the traced part's ticks carry
    ``sparse_layers``, ``index_lines``, ``index_pairs``, ``chosen_pairs`` and
    ``chosen_lines``, and no latent field."""
    from scaling_tpu import obs

    seen = spy_on_the_kind(monkeypatch)
    result = rehearse(run, grown_sparse, trace=2)
    assert result["correct"] and result["failed"] == 0 and result["unserved"] > 0
    assert seen["outcome"]["host"]["worst_logit_gap"] < 1e-3
    # the CPU has no device plane and no published peak: the readers of the
    # trace and of the peak find nothing and are left out; what the spans and
    # counters alone give is there
    assert set(result["metrics"]) == {
        "setup_s", "serve_tokens_per_s", "batch_occupancy_pct",
        "sparse_chosen_pct.saturated", "moe_load_max_over_mean.saturated"}
    assert 0 < result["metrics"]["sparse_chosen_pct.saturated"]["value"] < 100
    capture = obs.last_capture()
    mixed = sparse_latent.sparse_ticks(capture.spans)
    assert mixed and all(f["sparse_layers"] == 3 for f in mixed)
    for f in mixed:
        assert "latent_pairs" not in f and "latent_lines" not in f
        assert 0 < f["chosen_lines"] <= f["index_lines"]
        assert 0 < f["chosen_pairs"] <= f["index_pairs"]
        assert f["chosen_lines"] <= f["chosen_pairs"] <= 16 * f["tokens"]
    assert any(f["chosen_pairs"] < f["index_pairs"] for f in mixed)
    assert capture.counters["serve_index_lines_read_total"] == 3 * sum(
        f["index_lines"] for f in mixed)
    assert capture.counters["serve_sparse_chosen_pairs_total"] == 3 * sum(
        f["chosen_pairs"] for f in mixed)
    ctx = {"device": {"peaks": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}},
           "config": cells.load_json(grown_sparse / "configs" / "toy-keye.json"),
           "host": {}}
    assert 0 < sparse_gqa.tick_mfu_pct(ctx) < 1.0
    # the sparse latent cell's whole-tick share takes another line as attended
    # and its architecture keys are not this stack's
    with pytest.raises(KeyError):
        sparse_latent.tick_mfu_pct(ctx)


def test_the_control_fails_the_limit_the_program_keeps(run, grown_sparse, capsys,
                                                       monkeypatch):
    """``--control fp8``: the reference with fp8 weights misses the limit that
    the program keeps with room."""
    seen = spy_on_the_kind(monkeypatch)
    result = rehearse(run, grown_sparse, 0, "--control", "fp8", seconds="2")
    assert result["correct"]
    host = seen["outcome"]["host"]
    sound, control = host["worst_logit_gap"], host["control_logit_gap"]
    assert sound < serve_kind.LOGIT_TOL / 2 < serve_kind.LOGIT_TOL < control
    assert control > 3 * sound


def test_an_engine_that_leaves_the_selection_out_is_not_correct(
        run, grown_sparse, capsys, monkeypatch):
    """What ``correct`` sees of the mechanism at the toy's widths: an engine
    whose queries keep every visible line (dense grouped-query attention) is
    held to the reference's choice of 16 and fails the limit."""
    from scaling_tpu.nn.sparse_attention import SparseSelfAttention

    init = SparseSelfAttention.__init__

    def keeps_everything(self, **sizes):
        init(self, **sizes)
        self.index_topk = 256      # a slot's whole context

    monkeypatch.setattr(SparseSelfAttention, "__init__", keeps_everything)
    seen = spy_on_the_kind(monkeypatch)
    result = rehearse(run, grown_sparse, seconds="2")
    assert result["failed"] == 0 and result["correct"] is False
    assert seen["outcome"]["host"]["worst_logit_gap"] > serve_kind.LOGIT_TOL


# ---- the readers on recorded rows -----------------------------------------

LAYER = "jit(mixed_1024)/jit(_lambda_)/"
# name, start_ns, dur_ns, op_name: what ``load_scoped_ops`` gives
OPS = [
    ["%fusion.1 = bf16[1024,2048] fusion(...)", 0.0, 100e3, ""],                   # embedding
    ["%fusion.11 = bf16[1024,4096] fusion(...)", 100e3, 300e3, LAYER + "attn/dot_general"],
    ["%fusion.12 = bf16[1024,1024] fusion(...)", 400e3, 200e3, LAYER + "attn/indexer/dot_general"],
    ["%scatter.3 = bf16[32769,16,64] scatter(...)", 600e3, 100e3, LAYER + "attn/scatter"],
    ["%fusion.20 = f32[320,2048] fusion(...)", 700e3, 500e3,
     LAYER + "attn/while/body/cond/branch_1_fun/indexer/index_select/while/body/dot_general"],
    ["%fusion.21 = s32[320] fusion(...)", 1200e3, 900e3,
     LAYER + "attn/while/body/cond/branch_1_fun/indexer/index_select/while/body/reduce_sum"],
    ["%gather.7 = bf16[4096,16,4,128] gather(...)", 2100e3, 1500e3,
     LAYER + "attn/while/body/cond/branch_1_fun/sparse_attend/gather"],
    ["%masked_gqa_attention.1 = bf16[4,2560,128] custom-call(...)", 3500e3, 600e3,
     LAYER + "attn/while/body/cond/branch_1_fun/sparse_attend/pallas_call"],  # overlaps
    ["%fusion.13 = f32[1024,128] fusion(...)", 4100e3, 600e3, LAYER + "moe/dot_general"],
    ["%fusion.40 = bf16[8,151936] fusion(...)", 4700e3, 200e3, "jit(mixed_1024)/head/dot_general"],
    ["%copy.3 = s32[8] copy(...)", 4900e3, 100e3, ""],
]
SPANS = [
    ("serve.tick", 0, 15e6, {"step": 1}),
    ("serve.mixed", 0, 12e6, {                    # 8 decode rows at 32k lines each
        "step": 1, "sparse_layers": 4, "index_lines": 262_144,
        "index_pairs": 262_144, "chosen_pairs": 16_384, "chosen_lines": 16_384}),
    ("serve.tick", 20e6, 45e6, {"step": 2}),
    ("serve.mixed", 20e6, 42e6, {                 # 3 chunk rows of 320 at ~20k lines
        "step": 2, "sparse_layers": 4, "index_lines": 60_960,
        "index_pairs": 19_353_600, "chosen_pairs": 1_966_080, "chosen_lines": 6_144}),
    ("serve.mixed", 110e6, 5e6, {"step": 3}),     # a tick of the warm-up: no field
]
COUNTERS = {"serve_prefill_tokens_total": 960, "serve_tokens_generated_total": 8,
            "serve_moe_assignments_total": 7744}
ARCH = cells.load_json(cells.ROOT / "configs" / f"{CONFIG}.json")["transformer_architecture"]
CTX = {"device": {"peaks": {"hbm_bytes_per_s": 819e9, "flops_per_s": 197e12}},
       "config": {"transformer_architecture": ARCH}, "host": {}, "trace": {"class_s": {}}}
DSV32_ARCH = cells.load_json(
    cells.ROOT / "configs" / "deepseek-v3.2-exp-serve.json")["transformer_architecture"]
PAIR, INDEX_PAIR = 2 * 2 * 32 * 128, 2 * 16 * 64
SHAPE = dict(layers=4, hidden=2048, vocab=151_936, heads=32, kv_heads=4, head_dim=128,
             expert_width=768, num_experts=128, index_heads=16, index_dim=64)
PER_TOKEN = 4 * (18_874_368 + (2_261_120 - 128) + 262_144)


def test_the_counts_are_the_issues_by_hand():
    assert ops_count.chosen_flops(1, 32, 128) == PAIR == 16_384
    assert ops_count.chosen_bytes(2048, 4, 128, 2) == 2048 * 2048
    assert ops_count.index_flops(1, 16, 64) == INDEX_PAIR == 2_048
    assert ops_count.index_bytes(1, 64, 2) == 128
    assert ops_count.attention_matmul_params(2048, 32, 4, 128) == 18_874_368
    assert ops_count.indexer_matmul_params(2048, 16, 64) == 2_261_120 - 128
    # a query past index_topk: 33.6 MFLOP a layer whatever the context; at 20k
    # visible lines the index scores are 41 MFLOP
    assert ops_count.chosen_flops(2048, 32, 128) == pytest.approx(33.55e6, rel=1e-3)
    assert ops_count.index_flops(20_000, 16, 64) == pytest.approx(40.96e6, rel=1e-3)
    # the matrices a token meets a layer with its 8 experts: the ISSUE's 118 MFLOP
    # (attention 37.7, indexer 4.5, router 0.5, experts 75.5)
    assert ops_count.serve_flops(1, 0, 8 * 4, 0, 0, **SHAPE) / 4 == pytest.approx(
        118.3e6, rel=2e-3)
    assert 2.0 * 18_874_368 == pytest.approx(37.7e6, rel=2e-3)
    assert 2.0 * (2_261_120 - 128) == pytest.approx(4.5e6, rel=1e-2)
    assert 2.0 * 8 * 3 * 2048 * 768 == pytest.approx(75.5e6, rel=1e-3)
    base = ops_count.serve_flops(1, 0, 0, 0, 0, **SHAPE)
    assert base == 2.0 * PER_TOKEN
    # an assignment on a held expert, a sampled token, a chosen and an index pair
    assert ops_count.serve_flops(1, 0, 1, 0, 0, **SHAPE) - base == 2.0 * 3 * 2048 * 768
    assert ops_count.serve_flops(1, 1, 0, 0, 0, **SHAPE) - base == 2.0 * 2048 * 151_936
    assert ops_count.serve_flops(1, 0, 0, 1, 0, **SHAPE) - base == 4 * PAIR
    assert ops_count.serve_flops(1, 0, 0, 0, 1, **SHAPE) - base == 4 * INDEX_PAIR


def test_readers_give_the_six_values_by_hand():
    assert sparse_latent.union_seconds(OPS) == pytest.approx(5.0e-3)
    # the attn scope: 0.1 - 4.1 ms, its indexers included
    assert sparse_gqa.sparse_gqa_time_pct(CTX, ops=OPS, spans=SPANS) == pytest.approx(
        100 * 4.0 / 5.0)
    # PR 59's three read this configuration's sizes from its architecture: the
    # indexer's scope holds 0.2 + 0.5 + 0.9 ms; scores and choice 1.4
    assert sparse_latent.index_time_pct(CTX, ops=OPS, spans=SPANS) == pytest.approx(
        100 * 1.6 / 5.0)
    decode_index = max(INDEX_PAIR * 262_144 / 197e12, 262_144 * 128 / 819e9)
    chunk_index = max(INDEX_PAIR * 19_353_600 / 197e12, 60_960 * 128 / 819e9)
    assert decode_index == 262_144 * 128 / 819e9
    assert chunk_index == INDEX_PAIR * 19_353_600 / 197e12
    assert sparse_latent.indexer_roofline(CTX, ops=OPS, spans=SPANS) == pytest.approx(
        100 * 4 * (decode_index + chunk_index) / 1.4e-3)
    assert sparse_latent.sparse_chosen_pct(CTX, spans=SPANS) == pytest.approx(
        100 * (16_384 + 1_966_080) / (262_144 + 19_353_600))
    # tick 1 (decode rows): the attention is bound by its bytes (2,048 B a
    # line against 16,384 FLOP a pair, one query a line); tick 2 by its FLOPs
    decode = max(PAIR * 16_384 / 197e12, 16_384 * 2048 / 819e9)
    chunks = max(PAIR * 1_966_080 / 197e12, 6_144 * 2048 / 819e9)
    assert decode == 16_384 * 2048 / 819e9 and chunks == PAIR * 1_966_080 / 197e12
    # gather and kernel overlap in 3.5-3.6 ms: a union, 2.0 ms
    assert sparse_gqa.sparse_paged_roofline(CTX, ops=OPS, spans=SPANS) == pytest.approx(
        100 * 4 * (decode + chunks) / 2.0e-3)
    flops = (2.0 * (968 * PER_TOKEN + 7744 * 3 * 2048 * 768 + 8 * 2048 * 151_936)
             + 4 * (PAIR * (16_384 + 1_966_080) + INDEX_PAIR * (262_144 + 19_353_600)))
    assert sparse_gqa.tick_mfu_pct(CTX, spans=SPANS, counters=COUNTERS) == pytest.approx(
        100 * flops / 0.060 / 197e12)


def test_no_share_can_pass_one_hundred_on_what_the_chip_can_do():
    def only(scope, seconds):
        return [["%op = ...", 0.0, 1e9 * seconds, LAYER + f"attn/{scope}/x"]]

    # an attention that multiplies a chunk tick's chosen pairs at the peak, and
    # one that reads a decode tick's chosen lines at the published rate
    at_peak = only("sparse_attend", 4 * PAIR * 1_966_080 / 197e12)
    assert sparse_gqa.sparse_paged_roofline(CTX, ops=at_peak, spans=[SPANS[3]]) == \
        pytest.approx(100.0)
    at_rate = only("sparse_attend", 4 * 16_384 * 2048 / 819e9)
    assert sparse_gqa.sparse_paged_roofline(CTX, ops=at_rate, spans=[SPANS[1]]) == \
        pytest.approx(100.0)
    everything = [[n, s, d, LAYER + "attn/x"] for n, s, d, _ in OPS]
    assert sparse_gqa.sparse_gqa_time_pct(CTX, ops=everything, spans=SPANS) == pytest.approx(100)
    flops = ops_count.serve_flops(8, 8, 64, 16_384, 262_144, **SHAPE)
    spans = [("serve.tick", 0, 1e9 * flops / 197e12, {"step": 1}), SPANS[1]]
    assert sparse_gqa.tick_mfu_pct(CTX, spans=spans, counters={
        "serve_tokens_generated_total": 8,
        "serve_moe_assignments_total": 64}) == pytest.approx(100.0)


def test_without_the_scope_the_field_or_the_kind_a_reader_gives_none_not_zero():
    bare = [[name, start, dur, ""] for name, start, dur, _ in OPS]
    no_field = SPANS[4:]
    dense = [(n, s, d, {k: v for k, v in f.items() if not k.startswith(
        ("sparse", "index", "chosen"))}) for n, s, d, f in SPANS]
    for reader in (sparse_gqa.sparse_paged_roofline, sparse_gqa.sparse_gqa_time_pct):
        assert reader(CTX, ops=bare, spans=SPANS) is None
        assert reader(CTX, ops=[], spans=SPANS) is None
        assert reader(CTX, ops=OPS, spans=no_field) is None
        assert reader(CTX, ops=OPS, spans=dense) is None
    no_peak = {**CTX, "device": {"peaks": None}}
    assert sparse_gqa.sparse_paged_roofline(no_peak, ops=OPS, spans=SPANS) is None
    assert sparse_gqa.tick_mfu_pct(CTX, spans=no_field, counters=COUNTERS) is None
    assert sparse_gqa.tick_mfu_pct(CTX, spans=SPANS, counters={}) is None
    assert sparse_gqa.tick_mfu_pct(no_peak, spans=SPANS, counters=COUNTERS) is None
    # a sparse LATENT configuration (the other sparse kind) is not this reader's
    latent = {**CTX, "config": {"transformer_architecture": DSV32_ARCH}}
    assert sparse_gqa.attention_shape(DSV32_ARCH) is None
    assert sparse_gqa.sparse_paged_roofline(latent, ops=OPS, spans=SPANS) is None
    assert sparse_gqa.sparse_gqa_time_pct(latent, ops=OPS, spans=SPANS) is None
    assert sparse_gqa.tick_mfu_pct(latent, spans=SPANS, counters=COUNTERS) is None
    near = [["%g = ...", 1e3, 1e3, "jit(mixed)/attn/my_sparse_attend/mul"]]
    assert sparse_gqa.sparse_paged_roofline(CTX, ops=near, spans=SPANS) is None


# ---- the files, by name and by membership ---------------------------------

def test_metric_files_name_the_readers_and_the_cell_lists_them():
    bench = json.loads((cells.REPO / "BENCHMARK.json").read_text())
    entries = {m["name"]: m for m in bench["per_layer"]}
    assert set(METRICS) <= set(entries)
    for name, (layer, source, reader, better) in METRICS.items():
        spec = cells.load_json(cells.ROOT / "metrics" / f"{name}.json")
        assert spec["reader"] == f"sparse_gqa:{reader}"
        assert spec["unit"] == entries[name]["unit"] == "%"
        assert (entries[name]["layer"], entries[name]["source"]) == (layer, source)
        assert (entries[name]["moves"], entries[name]["better"]) == (
            "serve_tokens_per_s", better)
        assert entries[name]["workloads"] == [CELL]
        assert callable(cells.load_reader(name))
    # the cell reports what the sparse latent cell reports but the three whose
    # counts take another line or every visible line as attended, + its own
    listed = {m["name"] for m in bench["per_layer"] if CELL in m["workloads"]}
    dsv32 = {m["name"] for m in bench["per_layer"] if DSV32 in m["workloads"]}
    assert dsv32 - listed == {"latent_time_pct.saturated", "sparse_latent_roofline.saturated",
                              "tick_mfu_pct.sparse_latent"}
    assert listed - dsv32 == set(METRICS)
    assert set(SHARED) <= listed and all(
        entries[name]["workloads"] == [DSV32, CELL] for name in SHARED)
    assert {"moe_time_pct.saturated", "moe_load_max_over_mean.saturated", "peak_hbm_gb.serve",
            "tick_ms_p50.saturated", "device_idle_pct.saturated",
            "gap_ms_p50.traced", "tick_fill_pct.window"} <= listed
    assert not {n for n in listed if n.startswith(("paged_roofline", "latent_"))}
    assert {n for n in listed if n.startswith("tick_mfu_pct")} == {"tick_mfu_pct.sparse_gqa"}
    # appended: wherever this cell is listed it comes last
    for m in bench["end_to_end"] + bench["per_layer"]:
        if CELL in m.get("workloads", []):
            assert m["workloads"][-1] == CELL
    cell = cells.load_cell(CELL)
    assert cell.reference_name == REFERENCE and cell.chips == 1
    assert [m["name"] for m in cell.metrics("end_to_end")] == ["setup_s", "serve_tokens_per_s"]
    names = [w["name"] for w in bench["workloads"]]
    configs = [c["name"] for c in bench["configs"]]
    assert names[-1] == CELL or names.index(CELL) > names.index(DSV32)
    assert configs.index(CONFIG) > configs.index("deepseek-v3.2-exp-serve")
    entry = bench["workloads"][names.index(CELL)]
    assert (entry["config"], entry["traffic"], entry["chips"]) == (CONFIG, TRAFFIC, 1)
    assert len(entry["why"]) <= 200
    for word in ("8 slots x 65,536", "2,048", "depth 4"):
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
        == cell.config["reference"] == REFERENCE
    bursts = cells.load_module(cells.ROOT, "generators", "bursts",
                               cells.GENERATOR_CONTRACT).generate
    assert cell.generate.__code__.co_code == bursts.__code__.co_code
    spec = cell.view.reference_spec(ARCH)
    assert (spec["num_heads"], spec["num_kv_heads"], spec["head_dim"]) == (32, 4, 128)
    assert (spec["index_heads"], spec["index_dim"], spec["index_topk"]) == (16, 64, 2048)
    assert (spec["top_k"], spec["rope_base"], spec["eps"]) == (8, 1e7, 1e-6)
    with pytest.raises(SystemExit, match="the configuration states {'moe_router': "
                                         "'sigmoid_bias'"):
        cell.view.reference_spec({**ARCH, "moe_router": "sigmoid_bias"})
    with pytest.raises(SystemExit, match="the configuration lacks \\['index_topk'\\]"):
        cell.view.reference_spec({k: v for k, v in ARCH.items() if k != "index_topk"})
    # the window's and the gap's metrics (test_window_spans.py and
    # test_tick_gap.py list the cells of before) name this cell too
    bench = json.loads((cells.REPO / "BENCHMARK.json").read_text())
    for m in bench["per_layer"]:
        if m["name"].endswith((".window", ".traced")):
            assert CELL in m["workloads"], m["name"]


def test_the_configuration_names_every_key_it_changed_and_cuts_no_width():
    """What ``test_configs.py`` asks of every configuration, for one whose
    keys are config.json's own (its table knows two cuts)."""
    config = cells.load_json(cells.ROOT / "configs" / f"{CONFIG}.json")
    bench = json.loads((cells.REPO / "BENCHMARK.json").read_text())
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert 1 <= len(entry["why"]) <= 200 and entry["why"].isprintable()
    assert entry["source"] == config["source"] == (
        "https://huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B/blob/main/config.json")
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
    assert published["sa_config"] == config["sa_config"] == {
        "indexer_head_dim": 64, "indexer_num_heads": 16, "indexer_num_kv_heads": 1,
        "kv_chunk_size": 512, "q_chunk_size": 512, "topk": 2048}
    assert published["rope_scaling"]["mrope_section"] == [16, 24, 24]
    # the program runs what the file states, width for width
    as_run = {
        "hidden_size": arch["hidden_size"], "num_hidden_layers": arch["num_layers"] // 2,
        "num_attention_heads": arch["num_attention_heads"],
        "num_key_value_heads": arch["attention_num_kv_heads"],
        "head_dim": arch["attention_head_dim"],
        "intermediate_size": int(arch["hidden_size"] * arch["mlp_factor"]),
        "moe_intermediate_size": arch["moe_expert_width"],
        "num_experts": arch["moe_num_experts"], "num_local_experts": arch["moe_num_experts"],
        "num_experts_per_tok": arch["moe_top_k"],
        "norm_topk_prob": arch["moe_norm_topk_prob"],
        "vocab_size": arch["vocab_size"],
        "max_position_embeddings": arch["sequence_length"],
        "rms_norm_eps": arch["layernorm"]["layernorm_epsilon"],
        "rope_theta": arch["rotary_embedding_base"],
        "tie_word_embeddings": arch["weight_tying"],
        "attention_bias": arch["attention_bias"],
    }
    assert {key: config[key] for key in as_run} == as_run
    sa = config["sa_config"]
    assert (arch["index_n_heads"], arch["index_head_dim"], arch["index_topk"]) == (
        sa["indexer_num_heads"], sa["indexer_head_dim"], sa["topk"])
    assert arch.get("moe_experts_held") is None    # every expert held
    assert arch["layer_pattern"] == ["attention", "moe"] * 4
    assert config["mlp_only_layers"] == [] and config["decoder_sparse_step"] == 1
    layer = 625_381_760
    assert published["parameter_count"] == 30_640_656_384 == 48 * layer + 622_331_904
    assert 4 * layer + 622_331_904 == 3_123_858_944
    for words in ("ONE chip a layer", "twelve chips", "ALL 128 experts",
                  "all 151,936 rows", "3,123,858,944", "vision tower"):
        assert words in config["stands_for"], words
    assumed = config["assumed"]
    assert {"block", "qk_norm", "indexer", "indexer_rotary", "chunk_sizes", "rotary",
            "index_keys", "init", "router", "tower", "forms", "engine_shape", "precision",
            "parameter_count"} <= set(assumed)
    for n, key in enumerate(("qk_norm", "indexer", "indexer_rotary", "chunk_sizes",
                             "rotary", "index_keys", "init"), start=1):
        assert assumed[key].startswith(f"ASSUMED ({n})"), key
    engine = config["engine"]
    assert (engine["num_slots"], engine["context"], engine["enable_prefix_cache"]) == (
        8, 65536, False)
    # three chunk rows beside a decode row in every slot: the scheduler charges
    # the decode rows to the budget first
    assert engine["token_budget"] == 3 * engine["prefill_chunk"] + engine["num_slots"]
    assert config["chips"] == 1


def test_the_traffic_is_the_issues_and_fits_the_slots():
    """``longctx64k-burst8``: 8 at once every whole second the rate rule
    gives; no request asks for more than a slot's 65,536 positions or names a
    token outside the vocabulary; every request passes ``index_topk`` within
    its first chunks."""
    traffic = cells.load_json(cells.ROOT / "traffic" / f"{TRAFFIC}.json")
    assert (traffic["generator"], traffic["backlog"], traffic["burst_size"],
            traffic["shape_seed"]) == ("bursts", "cut", 8, 61)
    assert traffic["warm_seconds"] >= 20 and traffic["warm_seconds"] % 5 == 0
    assert traffic["burst_every_s"] == int(traffic["burst_every_s"]) >= 2
    assert traffic["prompt"] == {"median": 24576, "sigma": 0.6, "min": 8192, "max": 61440}
    assert traffic["output"] == {"median": 160, "sigma": 0.5, "min": 32, "max": 512}
    assert "tokens" not in traffic   # above the knee: whatever the engine completes
    assert traffic["check_requests"] == 4 and traffic["check_max_tokens"] == 16384
    assert traffic["trace_seconds"] == 3
    config = cells.load_json(cells.ROOT / "configs" / f"{CONFIG}.json")
    context = config["engine"]["context"]
    assert traffic["max_total"] == context == 65536
    vocab = config["transformer_architecture"]["vocab_size"]
    requests = cells.load_cell(CELL).generate(traffic, 2**31 + 5, 51.0, vocab)
    counted = [r for r in requests if r.due_s >= 0]
    assert len(counted) == 8 * len({r.due_s for r in counted})
    assert sum(r.due_s < 0 for r in requests) == 8      # one uncounted burst
    assert {r.due_s for r in requests if r.due_s < 0} == {-float(traffic["warm_seconds"])}
    assert max(len(r.prompt) + r.output_len for r in requests) <= context
    assert min(len(r.prompt) for r in requests) >= 8192 > 2048
    assert min(r.output_len for r in requests) >= 32
    assert all(1 <= t < vocab for r in requests[:2] for t in r.prompt)
    assert max(t for r in requests[:2] for t in r.prompt) > 2**16   # all 151,936 rows
    # the check teacher-forces requests of 8,192-16,384 tokens: every checked
    # position past its 2,048th chose 2,048 of up to 16,384 lines
    assert sum(len(r.prompt) + r.output_len <= traffic["check_max_tokens"]
               for r in counted) >= 4
    mean_output = sum(r.output_len for r in counted) / len(counted)
    assert 150 < mean_output < 230
