"""What PR 71 brings for ``qwen3-next-80b-a3b-serve`` as files (``reference/``
and ``views/gdn_moe_decoder.py``, ``readers/gdn.py``, ``gdn_ops_count.py``,
six metrics, ``traffic/extract-burst256.json``), rehearsed on the CPU at a
toy width through a copy of ``benchmark/`` into which only a toy configuration
is added; and the readers on recorded rows. Membership is pinned, never
position or a literal list: the next configuration's PR appends after these
entries."""

import json
import shutil
from pathlib import Path

import pytest

from benchmark import cells, gdn_ops_count as ops_count, model, serve_kind
from benchmark.readers import gdn

DATA = Path(__file__).parent / "data"
TOY = DATA / "toy_gdn"
BENCH = TOY / "BENCHMARK.json"
CELL = "serve-qwen3next-80b-extract-burst"
CONFIG = "qwen3-next-80b-a3b-serve"
TRAFFIC = "extract-burst256"
LAGUNA = "serve-laguna-s-mixedlen-burst"
METRICS = {
    "delta_time_pct.saturated": ("delta mixer", "device_trace", "lower",
                                 "delta_time_pct"),
    "delta_state_roofline.saturated": ("delta mixer", "device_trace", "higher",
                                       "delta_state_roofline"),
    "delta_step_roofline.saturated": ("delta mixer", "device_trace", "higher",
                                      "delta_step_roofline"),
    "delta_chunk_row_pct.saturated": ("delta mixer", "program_counter", "lower",
                                   "delta_chunk_row_pct"),
    "gated_attn_time_pct.saturated": ("full attention", "device_trace", "lower",
                                      "gated_attn_time_pct"),
    "tick_mfu_pct.gdn": ("engine tick", "program_counter", "higher",
                         "tick_mfu_pct"),
}
# the accepted metrics whose readers read this configuration unchanged
READ_UNCHANGED = {"moe_time_pct.saturated", "moe_load_max_over_mean.saturated"}


@pytest.fixture(scope="module")
def grown_gdn(grown):
    """``grown`` (the toy burst traffic is there) plus the one toy
    configuration and a chat traffic; reference, view, readers and metrics are
    the benchmark's own."""
    shutil.copy(TOY / "configs" / "toy-qwen3next.json", grown / "configs")
    shutil.copy(DATA / "toy_hc_latent" / "traffic" / "toy-hc-chat.json",
                grown / "traffic")
    for part, name in (("reference", "gdn_moe_decoder.py"),
                       ("views", "gdn_moe_decoder.py"), ("readers", "gdn.py")):
        assert (cells.ROOT / part / name).is_file() and (grown / part / name).is_file()
    return grown


def rehearse(run, root, trace=0, *more, workload="toy-serve-qwen3next", seconds="1.5"):
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
    toy = cells.load_json(TOY / "configs" / "toy-qwen3next.json")["transformer_architecture"]
    for key in ("attention_gate", "key_query_norm", "moe_router", "moe_norm_topk_prob",
                "moe_shared_expert_gate", "rotary_embedding_base", "rotary_percentage",
                "moe_glu", "mlp_type", "weight_tying", "layer_pattern", "layernorm",
                "conv_kernel", "moe_experts_first", "norm_type"):
        assert toy[key] == ARCH[key], key
    # two value heads a key head, a share of the experts held
    assert toy["delta_num_value_heads"] == 2 * toy["delta_num_key_heads"]
    assert ARCH["delta_num_value_heads"] == 2 * ARCH["delta_num_key_heads"] == 32
    assert toy["moe_experts_held"] < toy["moe_num_experts"]
    assert (ARCH["moe_experts_held"], ARCH["moe_num_experts"]) == (64, 512)


def test_the_serve_cell_is_correct_and_its_ticks_say_which_form_ran(
        run, grown_gdn, capsys, monkeypatch):
    """The engine serves the stack through the pool (the attention layers) and
    the lines a slot (the delta layers), every checked token within the
    tolerance of the reference's best logit; the traced part's ticks carry the
    delta fields and the counters move."""
    from scaling_tpu import obs

    seen = spy_on_the_kind(monkeypatch)
    result = rehearse(run, grown_gdn, trace=2)
    assert result["correct"] and result["failed"] == 0
    assert seen["outcome"]["host"]["worst_logit_gap"] < 1e-3
    # the CPU has no device plane and no published peak: the readers of the
    # trace and of the peak find nothing and are left out
    assert set(result["metrics"]) == {
        "setup_s", "serve_tokens_per_s", "batch_occupancy_pct",
        "delta_chunk_row_pct.saturated"}
    capture = obs.last_capture()
    mixed = gdn.delta_ticks(capture.spans)
    assert mixed and all(f["delta_lines"] == 6 for f in mixed)
    assert all(f["delta_step_rows"] + f["delta_chunk_rows"] == f["delta_rows"] <= 4
               for f in mixed)
    assert sum(v for k, v in capture.counters.items()
               if k.startswith("serve_delta_rows_total")) == 6 * sum(
        f["delta_rows"] for f in mixed)
    # the toy engine (4 slots x chunks of 16) builds ONE program, the full
    # width: whole rows, so every row runs the chunk form
    assert cells.load_json(grown_gdn / "configs" / "toy-qwen3next.json")[
        "engine"]["prefill_chunk"] * 4 == mixed[0]["width"]
    assert result["metrics"]["delta_chunk_row_pct.saturated"]["value"] == 100.0
    ctx = {"device": {"peaks": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}},
           "config": cells.load_json(grown_gdn / "configs" / "toy-qwen3next.json"),
           "host": {}}
    assert 0 < gdn.tick_mfu_pct(ctx) < 1.0
    # no device plane: nothing under the scopes, so nothing, not 0
    assert gdn.delta_time_pct(ctx) is None and gdn.delta_state_roofline(ctx) is None
    assert gdn.gated_attn_time_pct(ctx) is None
    assert gdn.delta_step_roofline(ctx) is None


def test_a_plain_cell_reads_none_of_the_new_metrics(run, grown, capsys):
    """A plain model's spans carry no ``delta_lines``: the readers return
    nothing and do not raise, whatever its trace's scopes. What the parent
    commit's program gives under this PR's benchmark files."""
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
        assert getattr(gdn, reader)(ctx) is None
    assert gdn.delta_time_pct(ctx, ops=OPS) is None   # scopes, no field
    assert gdn.delta_state_roofline(ctx, ops=OPS) is None


def test_the_control_fails_the_limit_the_program_keeps(run, grown_gdn, capsys,
                                                       monkeypatch):
    """``--control fp8``: the reference with fp8 matrices misses the limit
    that the program keeps with room."""
    seen = spy_on_the_kind(monkeypatch)
    result = rehearse(run, grown_gdn, 0, "--control", "fp8",
                      workload="toy-serve-qwen3next-chat", seconds="3")
    assert result["correct"]
    host = seen["outcome"]["host"]
    sound, control = host["worst_logit_gap"], host["control_logit_gap"]
    assert sound < serve_kind.LOGIT_TOL / 2 < serve_kind.LOGIT_TOL < control
    assert control > 3 * sound


# ---- the readers on recorded rows -----------------------------------------

LAYER = "jit(mixed_768)/jit(_lambda_)/"
# name, start_ns, dur_ns, op_name: what ``load_scoped_ops`` gives
OPS = [
    ["%fusion.1 = bf16[768,2048] fusion(...)", 0.0, 100e3, ""],                # embedding
    ["%fusion.5 = bf16[768,12288] fusion(...)", 100e3, 200e3,
     LAYER + "delta/dot_general"],
    ['%delta_step.1 = (f32[256,32,128,128], f32[256,32,128]) custom-call(...), '
     'custom_call_target="tpu_custom_call"', 300e3, 700e3,
     LAYER + "delta/delta_rule/delta_step/pallas_call"],
    ["%fusion.7 = f32[768,32,128] fusion(...)", 1000e3, 100e3,
     LAYER + "delta/mul"],
    ["%fusion.11 = bf16[768,8192] fusion(...)", 1100e3, 150e3,
     LAYER + "gated_attn/dot_general"],
    ["%fusion.12 = bf16[768,16,256] fusion(...)", 1250e3, 50e3,
     LAYER + "gated_attn/gate/mul"],
    ["%fusion.13 = f32[768,512] fusion(...)", 1300e3, 400e3, LAYER + "moe/dot_general"],
    ["%fusion.40 = bf16[256,18992] fusion(...)", 1700e3, 300e3,
     "jit(mixed_768)/head/dot_general"],
]
SPANS = [
    ("serve.tick", 0, 30e6, {"step": 1}),
    ("serve.mixed", 0, 29e6, {"step": 1, "tokens": 700, "width": 768,
                              "delta_rows": 256, "delta_lines": 6,
                              "delta_step_rows": 242, "delta_chunk_rows": 14}),
    ("serve.tick", 40e6, 30e6, {"step": 2}),
    ("serve.mixed", 40e6, 29e6, {"step": 2, "tokens": 2000, "width": 8192,
                                 "delta_rows": 250, "delta_lines": 6,
                                 "delta_step_rows": 180, "delta_chunk_rows": 70}),
    ("serve.mixed", 100e6, 5e6, {"step": 3}),  # a tick of the warm-up: no field
]
COUNTERS = {"serve_prefill_tokens_total": 2200, "serve_tokens_generated_total": 500,
            "serve_moe_assignments_total": 8 * 3375}
CTX = {"device": {"peaks": {"hbm_bytes_per_s": 819e9, "flops_per_s": 197e12}},
       "config": {"transformer_architecture": ARCH,
                  "engine": {"num_slots": 256, "prefill_chunk": 32}},
       "host": {"traced_context_tokens": 250_000}, "trace": None}
SHAPE = dict(delta_layers=6, attention_layers=2, moe_layers=8, hidden=2048,
             vocab=18_992, delta=(16, 32, 128, 128), expert_width=512,
             shared_width=512, num_experts=512, heads=16, kv_heads=2, head_dim=256)
DELTA = (16, 32, 128, 128)


def test_the_counts_are_the_issues_by_hand():
    # the ISSUE's 33.7 M and 27.3 M: the matrices of a delta mixer (the conv,
    # 8,192 x 4, is beside them) and of an attention mixer
    assert ops_count.delta_matmul_params(2048, *DELTA) == (
        2048 * 12_288 + 2048 * 64 + 4096 * 2048) == 33_685_504
    assert ops_count.attention_matmul_params(2048, 16, 2, 256) == 27_262_976
    assert ops_count.delta_dims(*DELTA) == (2048, 4096, 8192, 12_288)
    assert ops_count.state_bytes(32, 128, 128) == 2 * 1024 * 1024
    # a layer's bytes a tick: rows x 2 x the state + the mixer's weights once
    weights = (33_685_504 + 8192 * 4) * 2
    assert ops_count.delta_layer_bytes(256, 2048, *DELTA, 4, 2) == (
        256 * 2 * 2 * 1024 * 1024 + weights)
    assert ops_count.delta_layer_bytes(0, 2048, *DELTA, 4, 2) == weights
    # THREE expert matrices and the shared gate
    assert ops_count.moe_layer_bytes(64, 2048, 512, 512, 512, 2) == (
        64 * 3 * 2048 * 512 * 2 + (3 * 2048 * 512 + 2048) * 2 + 2048 * 512 * 4)
    assert ops_count.step_flops(32, 128, 128) == 6 * 32 * 128 * 128
    per_token = (6 * 33_685_504 + 2 * 27_262_976
                 + 8 * (2048 * 512 + 3 * 2048 * 512 + 2048))
    assert ops_count.serve_flops(1, 0, 0, 0, **SHAPE) == (
        2.0 * per_token + 6 * 6 * 32 * 128 * 128)
    assert ops_count.serve_flops(0, 1, 0, 0, **SHAPE) == 2.0 * 2048 * 18_992
    assert ops_count.serve_flops(0, 0, 1, 0, **SHAPE) == 2.0 * 3 * 2048 * 512
    assert ops_count.serve_flops(0, 0, 0, 1, **SHAPE) == 4.0 * 16 * 256 * 2
    # the chunk form does more work than the step for the same positions
    assert ops_count.chunk_flops(32, *DELTA) > 32 * ops_count.step_flops(32, 128, 128)


def test_readers_give_the_six_values_by_hand():
    assert gdn.union_seconds(OPS) == pytest.approx(2.0e-3)
    # delta: 0.2 + 0.7 + 0.1 ms; gated_attn: 0.15 + 0.05 ms
    assert gdn.delta_time_pct(CTX, ops=OPS, spans=SPANS) == pytest.approx(100 * 1.0 / 2.0)
    assert gdn.gated_attn_time_pct(CTX, ops=OPS, spans=SPANS) == pytest.approx(
        100 * 0.2 / 2.0)
    nbytes = 6 * (ops_count.delta_layer_bytes(256, 2048, *DELTA, 4, 2)
                  + ops_count.delta_layer_bytes(250, 2048, *DELTA, 4, 2))
    assert gdn.delta_state_roofline(CTX, ops=OPS, spans=SPANS) == pytest.approx(
        100 * nbytes / 1.0e-3 / 819e9)
    # the kernel alone: tick 1's 242 rows that stepped (tick 2 ran at the full
    # width, where whole rows run the chunk form and the kernel does not run)
    assert gdn.delta_step_roofline(CTX, ops=OPS, spans=SPANS) == pytest.approx(
        100 * 6 * 242 * 2 * 2 * 1024 * 1024 / 0.7e-3 / 819e9)
    # tick 2 ran at the full width: every one of its rows ran the chunk form
    assert gdn.full_width(CTX) == 8192
    assert gdn.delta_chunk_row_pct(CTX, spans=SPANS) == pytest.approx(
        100 * (14 + 250) / (256 + 250))
    flops = ops_count.serve_flops(2700, 500, 8 * 3375, 250_000, **SHAPE)
    assert gdn.tick_mfu_pct(CTX, spans=SPANS, counters=COUNTERS) == pytest.approx(
        100 * flops / 0.060 / 197e12)


def test_no_share_can_pass_one_hundred_on_what_the_chip_can_do():
    everything = [[n, s, d, LAYER + "delta/x"] for n, s, d, _ in OPS]
    assert gdn.delta_time_pct(CTX, ops=everything, spans=SPANS) == pytest.approx(100)
    # a scope that takes the least its bytes allow reads 100
    nbytes = 6 * (ops_count.delta_layer_bytes(256, 2048, *DELTA, 4, 2)
                  + ops_count.delta_layer_bytes(250, 2048, *DELTA, 4, 2))
    at_the_rate = [["%k = ...", 0.0, 1e9 * nbytes / 819e9, LAYER + "delta/delta_rule/x"]]
    assert gdn.delta_state_roofline(CTX, ops=at_the_rate, spans=SPANS) == pytest.approx(100.0)
    # a kernel that moves nothing but the stepping rows' states at the rate
    step = 6 * 242 * 2 * 2 * 1024 * 1024
    kernel = [["%delta_step.1 = ...", 0.0, 1e9 * step / 819e9,
               LAYER + "delta/delta_rule/delta_step/pallas_call"]]
    assert gdn.delta_step_roofline(CTX, ops=kernel, spans=SPANS) == pytest.approx(100.0)
    all_chunks = [("serve.mixed", 0, 1e6, {"delta_lines": 6, "delta_rows": 5, "width": 8192,
                                           "delta_step_rows": 5, "delta_chunk_rows": 0})]
    assert gdn.delta_chunk_row_pct(CTX, spans=all_chunks) == 100.0
    flops = ops_count.serve_flops(256, 256, 200, 100_000, **SHAPE)
    spans = [("serve.tick", 0, 1e9 * flops / 197e12, {"step": 1}), SPANS[1]]
    assert gdn.tick_mfu_pct(
        {**CTX, "host": {"traced_context_tokens": 100_000}}, spans=spans, counters={
            "serve_tokens_generated_total": 256,
            "serve_moe_assignments_total": 200}) == pytest.approx(100.0)


def test_without_the_scope_the_counter_or_the_field_a_reader_gives_none_not_zero():
    bare = [[name, start, dur, ""] for name, start, dur, _ in OPS]
    no_field = SPANS[4:]
    for reader in (gdn.delta_time_pct, gdn.gated_attn_time_pct,
                   gdn.delta_state_roofline, gdn.delta_step_roofline):
        assert reader(CTX, ops=bare, spans=SPANS) is None
        assert reader(CTX, ops=[], spans=SPANS) is None
        assert reader(CTX, ops=OPS, spans=no_field) is None
    assert gdn.delta_chunk_row_pct(CTX, spans=no_field) is None
    assert gdn.tick_mfu_pct(CTX, spans=no_field, counters=COUNTERS) is None
    assert gdn.tick_mfu_pct(CTX, spans=SPANS, counters={}) is None
    no_peak = {**CTX, "device": {"peaks": None}}
    assert gdn.tick_mfu_pct(no_peak, spans=SPANS, counters=COUNTERS) is None
    assert gdn.delta_state_roofline(no_peak, ops=OPS, spans=SPANS) is None
    other = {**CTX, "config": {"transformer_architecture": {
        **ARCH, "layer_pattern": ["attention", "moe"]}}}
    assert gdn.delta_state_roofline(other, ops=OPS, spans=SPANS) is None
    assert gdn.tick_mfu_pct(other, spans=SPANS, counters=COUNTERS) is None
    near = [["%f = ...", 0.0, 1e3, "jit(mixed)/delta_out/mul"],
            ["%g = ...", 1e3, 1e3, ""]]
    assert gdn.delta_time_pct(CTX, ops=near, spans=SPANS) is None


# ---- the files, by name and by membership ---------------------------------

def test_metric_files_name_the_readers_and_the_cell_is_in_each_list():
    bench = json.loads((cells.REPO / "BENCHMARK.json").read_text())
    entries = {m["name"]: m for m in bench["per_layer"]}
    assert set(METRICS) <= set(entries)
    for name, (layer, source, better, reader) in METRICS.items():
        spec = cells.load_json(cells.ROOT / "metrics" / f"{name}.json")
        assert spec["reader"] == f"gdn:{reader}"
        assert spec["unit"] == entries[name]["unit"] == "%"
        assert (entries[name]["layer"], entries[name]["source"],
                entries[name]["better"]) == (layer, source, better)
        assert entries[name]["moves"] == "serve_tokens_per_s"
        assert CELL in entries[name]["workloads"]
        assert callable(cells.load_reader(name))
    # the cell is in every list the serve burst cells share (those Laguna's
    # cell and Mistral's burst cell are both in), and in the routed MLP's two
    listed = {m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", [])}
    shared = {m["name"] for m in bench["per_layer"]
              if {LAGUNA, "serve-mistral7b-chat-burst"} <= set(m.get("workloads", []))}
    assert len(shared) >= 32 and shared <= listed
    assert READ_UNCHANGED <= listed
    assert {n for n in listed if n.startswith("tick_mfu_pct")} == {"tick_mfu_pct.gdn"}
    serve = next(m for m in bench["end_to_end"] if m["name"] == "serve_tokens_per_s")
    assert CELL in serve["workloads"]
    # appended: wherever this cell and Laguna's are listed, this one comes after
    for m in bench["end_to_end"] + bench["per_layer"]:
        cells_of = m.get("workloads", [])
        if CELL in cells_of and LAGUNA in cells_of:
            assert cells_of.index(CELL) > cells_of.index(LAGUNA)
    cell = cells.load_cell(CELL)
    assert cell.reference_name == "gdn_moe_decoder" and cell.chips == 1
    assert [m["name"] for m in cell.metrics("end_to_end")] == ["setup_s", "serve_tokens_per_s"]
    names = [w["name"] for w in bench["workloads"]]
    configs = [c["name"] for c in bench["configs"]]
    assert CELL in names and names.index(CELL) > names.index(LAGUNA)
    assert CONFIG in configs and configs.index(CONFIG) > configs.index("laguna-s-2.1-serve")
    entry = bench["workloads"][names.index(CELL)]
    assert (entry["config"], entry["traffic"], entry["chips"]) == (CONFIG, TRAFFIC, 1)
    assert len(entry["why"]) <= 200
    for word in ("256 slots", "1 of 8 ranks", "depth 8"):
        assert word in entry["why"], word
    assert len(names) <= 24 and sum(w["chips"] == 4 for w in bench["workloads"]) == 1


def test_the_cell_resolves_to_its_reference_view_and_generator():
    cell = cells.load_cell(CELL)
    for name in cells.REFERENCE_CONTRACT:
        assert callable(getattr(cell.reference, name))
    for name in cells.VIEW_CONTRACT:
        assert callable(getattr(cell.view, name))
    assert Path(cell.reference.__file__).stem == Path(cell.view.__file__).stem \
        == cell.config["reference"] == "gdn_moe_decoder"
    bursts = cells.load_module(cells.ROOT, "generators", "bursts",
                               cells.GENERATOR_CONTRACT).generate
    assert cell.generate.__code__.co_code == bursts.__code__.co_code
    spec = cell.view.reference_spec(ARCH)
    assert spec["kinds"] == ("delta", "delta", "delta", "attention") * 2
    assert (spec["num_heads"], spec["num_kv_heads"], spec["head_dim"],
            spec["rope_dims"], spec["rope_base"]) == (16, 2, 256, 64, 1e7)
    assert spec["delta"] == DELTA and spec["eps"] == 1e-6
    assert (spec["top_k"], spec["experts_first"], spec["shared"]) == (10, 0, True)
    # the reference is independent of the program and runs the STEP
    source = Path(cell.reference.__file__).read_text()
    assert "scaling_tpu" not in source.replace("``scaling_tpu``", "")
    assert 'default_matmul_precision("highest")' in source and "lax.scan(step" in source
    with pytest.raises(SystemExit, match="the configuration states {'attention_gate': 'per_head'"):
        cell.view.reference_spec({**ARCH, "attention_gate": "per_head"})
    with pytest.raises(SystemExit, match="the configuration states {'moe_router': 'sigmoid_bias'"):
        cell.view.reference_spec({**ARCH, "moe_router": "sigmoid_bias"})
    with pytest.raises(SystemExit, match="layernorm.weight_offset"):
        cell.view.reference_spec({**ARCH, "layernorm": {"layernorm_epsilon": 1e-6}})
    with pytest.raises(SystemExit, match="layer_pattern is \\(delta \\| attention"):
        cell.view.reference_spec({**ARCH, "layer_pattern": ["mamba", "moe"]})


def test_the_released_order_is_a_permutation_by_key_head():
    """The view's two index vectors: every program column once, and the
    released layout's first key head is q_0, k_0, its two value heads' v, their
    z."""
    cell = cells.load_cell(CELL)
    qkvz, ba = cell.view.released_order(*DELTA)
    assert sorted(qkvz.tolist()) == list(range(12_288))
    assert sorted(ba.tolist()) == list(range(64))
    assert qkvz[:128].tolist() == list(range(128))                    # q of key head 0
    assert qkvz[128:256].tolist() == list(range(2048, 2176))          # its k
    assert qkvz[256:512].tolist() == list(range(4096, 4352))          # v of value heads 0, 1
    assert qkvz[512:768].tolist() == list(range(8192, 8448))          # their z
    assert qkvz[768] == 128                                           # q of key head 1
    assert ba[:4].tolist() == [0, 1, 32, 33] and ba[4:8].tolist() == [2, 3, 34, 35]


def test_the_configuration_names_every_key_it_changed_and_cuts_no_width():
    """Every number of the catalog row under the same key; depth, the experts
    held, the vocabulary's slice and the positions alone reduced, each with
    its published value beside what runs; what is left out is named; the
    parameter count is the program's own tree's."""
    config = cells.load_json(cells.ROOT / "configs" / f"{CONFIG}.json")
    bench = json.loads((cells.REPO / "BENCHMARK.json").read_text())
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert entry["source"] == config["source"] == (
        "https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct/blob/main/config.json")
    assert len(entry["why"]) <= 200
    published, reduced, arch = config["published"], config["reduced"], ARCH
    assert sorted(entry["reduced"]) == sorted(reduced) == sorted(
        ["num_hidden_layers", "num_experts", "vocab_size", "max_position_embeddings"])
    for key, value in published.items():
        if key == "parameter_count":
            continue
        if key in reduced:
            assert reduced[key]["published"] == value and reduced[key]["run"] == config[key] != value
            assert reduced[key]["why"]
        else:
            assert config[key] == value, f"{key} differs and is not in reduced"
    assert {k: reduced[k]["run"] for k in reduced} == {
        "num_hidden_layers": 8, "num_experts": 64, "vocab_size": 18_992,
        "max_position_embeddings": 2048}
    layers = arch["num_layers"] // 2
    as_run = {
        "hidden_size": arch["hidden_size"], "num_hidden_layers": layers,
        "num_attention_heads": arch["num_attention_heads"],
        "num_key_value_heads": arch["attention_num_kv_heads"],
        "head_dim": arch["attention_head_dim"],
        "partial_rotary_factor": arch["rotary_percentage"],
        "rope_theta": arch["rotary_embedding_base"],
        "linear_num_key_heads": arch["delta_num_key_heads"],
        "linear_num_value_heads": arch["delta_num_value_heads"],
        "linear_key_head_dim": arch["delta_key_head_dim"],
        "linear_value_head_dim": arch["delta_value_head_dim"],
        "linear_conv_kernel_dim": arch["conv_kernel"],
        "moe_intermediate_size": arch["moe_expert_width"],
        "shared_expert_intermediate_size": arch["moe_shared_expert_width"],
        "num_experts": arch["moe_experts_held"],
        "num_experts_per_tok": arch["moe_top_k"],
        "norm_topk_prob": arch["moe_norm_topk_prob"],
        "vocab_size": arch["vocab_size"],
        "max_position_embeddings": arch["sequence_length"],
        "rms_norm_eps": arch["layernorm"]["layernorm_epsilon"],
        "tie_word_embeddings": arch["weight_tying"],
    }
    assert {key: config[key] for key in as_run} == as_run
    assert arch["moe_num_experts"] == published["num_experts"] == 512
    # full_attention_interval 4: layer l is full attention where (l + 1) % 4 == 0
    interval = published["full_attention_interval"]
    assert arch["layer_pattern"] == [
        name for l in range(layers)
        for name in ("attention" if (l + 1) % interval == 0 else "delta", "moe")]
    assert "8 chips" in config["stands_for"] or "8 ranks" in config["stands_for"]
    assert "multi-token" in config["left_out"]
    assert f"{config['parameter_count']:,}" in config["stands_for"]
    assert "init" in config["assumed"] and "engine_shape" in config["assumed"]
    assert config["engine"]["num_slots"] == 256 and config["engine"]["context"] == 2048
    assert config["engine"]["enable_prefix_cache"] is False
    assert config["chips"] == 1


def test_the_parameter_count_is_the_programs_own_tree():
    from scaling_tpu.models.transformer.model import init_model

    config = cells.load_json(cells.ROOT / "configs" / f"{CONFIG}.json")
    module = init_model(model.transformer_config(config, {}), None)
    shapes = model.param_shapes(module)
    assert model.count_params(shapes) == config["parameter_count"] == 1_978_847_360
    mixer = lambda i: model.count_params(shapes[f"layer_{i}"]["mixer"])
    assert (mixer(1), mixer(7)) == (33_718_464, 27_263_488)
    assert mixer(2) == 2048 * 512 + 65 * 3 * 2048 * 512 + 2048
    assert model.count_params(shapes["layer_0"]) == 18_992 * 2048


def test_the_lines_and_the_pool_are_the_bytes_the_configuration_states():
    """256 slots: a float32 state and a conv tail a (slot, delta layer),
    2 MiB + 48 KiB, and 2,048 tokens of pool a slot for the two attention
    layers at 2 KiB a token and layer (shapes alone: nothing is allocated)."""
    config = cells.load_json(cells.ROOT / "configs" / f"{CONFIG}.json")
    engine = model.engine_config(config["engine"])
    state = ops_count.state_bytes(32, 128, 128)
    tail = 8192 * 3 * 2
    assert (state, tail) == (2 * 1024 * 1024, 48 * 1024)
    assert 6 * engine.num_slots * (state + tail) == 3_296_722_944       # 3.30 GB
    line = 2 * ARCH["attention_num_kv_heads"] * ARCH["attention_head_dim"] * 2
    assert line == 2048
    assert 2 * (engine.num_blocks - 1) * engine.block_size * line == 2_147_483_648
    assert engine.mixed_widths == (768, 256 * 32)
    assert engine.token_budget == 768 and engine.small_bucket_chunks == 16


def test_the_traffic_is_the_issues_and_fits_the_slots():
    """``extract-burst256``: 256 at once every whole second the rate rule
    gives; no request asks for more than a slot's 2,048 positions or names a
    token outside the 18,992 rows held."""
    traffic = cells.load_json(cells.ROOT / "traffic" / f"{TRAFFIC}.json")
    assert (traffic["generator"], traffic["backlog"], traffic["burst_size"],
            traffic["shape_seed"], traffic["warm_seconds"]) == (
        "bursts", "cut", 256, 71, 5)
    assert traffic["burst_every_s"] == int(traffic["burst_every_s"]) >= 1
    assert traffic["prompt"] == {"median": 384, "sigma": 0.7, "min": 64, "max": 1408}
    assert traffic["output"] == {"median": 256, "sigma": 0.5, "min": 64, "max": 640}
    assert "tokens" not in traffic   # above the knee: whatever the engine completes
    assert traffic["check_requests"] == 4 and traffic["check_max_tokens"] == 2048
    assert traffic["trace_seconds"] == 3.0
    config = cells.load_json(cells.ROOT / "configs" / f"{CONFIG}.json")
    context = config["engine"]["context"]
    assert traffic["max_total"] == context == 2048
    vocab = config["transformer_architecture"]["vocab_size"]
    requests = cells.load_cell(CELL).generate(traffic, 2**31 + 5, 51.0, vocab)
    counted = [r for r in requests if r.due_s >= 0]
    assert len(counted) == 256 * len({r.due_s for r in counted})
    assert sum(r.due_s < 0 for r in requests) == 256     # one uncounted burst
    assert max(len(r.prompt) + r.output_len for r in requests) <= context
    assert min(r.output_len for r in requests) >= 64
    assert all(1 <= t < vocab for r in requests[:4] for t in r.prompt)
    assert max(t for r in requests[:8] for t in r.prompt) > vocab // 2
    prompts = [len(r.prompt) for r in counted]
    mean_prompt = sum(prompts) / len(prompts)
    mean_output = sum(r.output_len for r in counted) / len(counted)
    assert 420 < mean_prompt < 520 and 260 < mean_output < 310
