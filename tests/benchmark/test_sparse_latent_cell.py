"""What PR 59 brings for ``deepseek-v3.2-exp-serve`` as files (``reference/``
and ``views/sparse_latent_moe_decoder.py``, ``readers/sparse_latent.py``,
``sparse_latent_ops_count.py``, five metrics, ``traffic/longdoc32k-burst16
.json``), rehearsed on the CPU at a toy width through a copy of ``benchmark/``
into which only a toy configuration is added; and the readers on recorded
rows. Membership is pinned, never position: the next configuration's PR
appends after these entries. This file also holds, for this configuration, the
facts ``test_configs.py`` and ``test_files_by_name.py`` ask of every
configuration (their tables are from before it)."""

import json
import shutil
from pathlib import Path

import pytest

from benchmark import cells, serve_kind, sparse_latent_ops_count as ops_count
from benchmark.readers import hybrid, latent, sparse_latent

TOY = Path(__file__).parent / "data" / "toy_sparse_latent"
BENCH = TOY / "BENCHMARK.json"
CELL = "serve-dsv32-longdoc-burst"
CONFIG = "deepseek-v3.2-exp-serve"
TRAFFIC = "longdoc32k-burst16"
KIMI = "serve-kimik2-longdoc-burst"
REFERENCE = "sparse_latent_moe_decoder"
METRICS = {
    "sparse_latent_roofline.saturated": ("sparse latent attention", "device_trace",
                                         "sparse_latent_roofline", "higher"),
    "indexer_roofline.saturated": ("sparse latent attention", "device_trace",
                                   "indexer_roofline", "higher"),
    "index_time_pct.saturated": ("sparse latent attention", "device_trace",
                                 "index_time_pct", "higher"),
    "sparse_chosen_pct.saturated": ("sparse latent attention", "program_span",
                                    "sparse_chosen_pct", "lower"),
    "tick_mfu_pct.sparse_latent": ("engine tick", "program_counter", "tick_mfu_pct", "higher"),
}


@pytest.fixture(scope="module")
def grown_sparse(grown):
    """``grown`` plus the one toy configuration and its two traffics;
    reference, view, readers and metrics are the benchmark's own."""
    shutil.copy(TOY / "configs" / "toy-dsv32.json", grown / "configs")
    for name in ("toy-sparse-burst.json", "toy-sparse-latent-chat.json"):
        shutil.copy(TOY / "traffic" / name, grown / "traffic")
    for part, name in (("reference", f"{REFERENCE}.py"), ("views", f"{REFERENCE}.py"),
                       ("readers", "sparse_latent.py")):
        assert (cells.ROOT / part / name).is_file() and (grown / part / name).is_file()
    return grown


def rehearse(run, root, trace=0, *more, workload="toy-serve-sparse", seconds="1.5"):
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
    toy = cells.load_json(TOY / "configs" / "toy-dsv32.json")["transformer_architecture"]
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
    # a group limit that limits, and an indexer that leaves lines out
    assert 1 < toy["moe_topk_group"] < toy["moe_n_group"]
    assert toy["index_topk"] == 16 and toy["index_head_dim"] >= toy["qk_rope_head_dim"]
    traffic = cells.load_json(TOY / "traffic" / "toy-sparse-burst.json")
    assert traffic["prompt"]["min"] > toy["index_topk"]


def test_sparse_serve_cell_is_correct_and_its_ticks_carry_what_was_chosen(
        run, grown_sparse, capsys, monkeypatch):
    """The engine serves the stack through the three-leaf pool (the chosen
    lines gathered), every checked token on the reference's (expanded, masked)
    best logit; the traced part's ticks carry ``sparse_layers``,
    ``index_lines``, ``index_pairs``, ``chosen_pairs`` and ``chosen_lines``."""
    from scaling_tpu import obs

    seen = spy_on_the_kind(monkeypatch)
    result = rehearse(run, grown_sparse, trace=2)
    assert result["correct"] and result["failed"] == 0 and result["unserved"] > 0
    assert seen["outcome"]["host"]["worst_logit_gap"] < 1e-3
    # the CPU has no device plane and no published peak: the readers of the
    # trace and of the peak find nothing and are left out; the share of the
    # pairs that were chosen comes from the spans alone
    assert set(result["metrics"]) == {"setup_s", "serve_tokens_per_s",
                                      "batch_occupancy_pct", "sparse_chosen_pct.saturated"}
    assert 0 < result["metrics"]["sparse_chosen_pct.saturated"]["value"] < 100
    capture = obs.last_capture()
    mixed = sparse_latent.sparse_ticks(capture.spans)
    assert mixed and all(f["sparse_layers"] == 3 for f in mixed)
    for f in mixed:
        assert 0 < f["chosen_lines"] <= f["index_lines"] == f["latent_lines"]
        assert 0 < f["chosen_pairs"] <= f["index_pairs"] == f["latent_pairs"]
        assert f["chosen_lines"] <= f["chosen_pairs"] <= 16 * f["tokens"]
    assert any(f["chosen_pairs"] < f["index_pairs"] for f in mixed)
    assert capture.counters["serve_index_lines_read_total"] == 3 * sum(
        f["index_lines"] for f in mixed)
    assert capture.counters["serve_sparse_chosen_pairs_total"] == 3 * sum(
        f["chosen_pairs"] for f in mixed)
    ctx = {"device": {"peaks": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}},
           "config": cells.load_json(grown_sparse / "configs" / "toy-dsv32.json"),
           "host": {}}
    assert 0 < sparse_latent.tick_mfu_pct(ctx) < 1.0
    # Kimi's readers read this model too: its spans keep latent_* as visible
    assert 0 < latent.tick_mfu_pct(ctx) < 1.0


def test_a_dense_latent_cell_reads_none_of_the_new_metrics(run, grown, capsys):
    """A latent model without an indexer (Kimi-K2's toy) carries no
    ``chosen_pairs``: the readers return nothing, whatever its trace's scopes.
    What the parent commit's program gives under this PR's benchmark files."""
    from scaling_tpu import obs

    toy = Path(__file__).parent / "data" / "toy_latent"
    shutil.copy(toy / "configs" / "toy-kimik2.json", grown / "configs")
    run.main(["--workload", "toy-serve-latent", "--seed", "5", "--seconds", "1.5",
              "--trace", "2", "--rehearse", "--root", str(grown),
              "--benchmark-json", str(toy / "BENCHMARK.json")])
    capture = obs.last_capture()
    assert hybrid.span_fields("serve.mixed", "latent_pairs", capture.spans)
    ctx = {"device": {"peaks": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}},
           "config": cells.load_json(grown / "configs" / "toy-kimik2.json"),
           "host": {}, "trace": None}
    for _, _, reader, _ in METRICS.values():
        assert getattr(sparse_latent, reader)(ctx) is None
    for reader in ("sparse_latent_roofline", "indexer_roofline", "index_time_pct"):
        assert getattr(sparse_latent, reader)(ctx, ops=OPS) is None   # scopes, no field


def test_a_token_altered_where_the_engine_produces_it_is_not_correct(
        run, grown_sparse, capsys, monkeypatch):
    import jax.numpy as jnp

    from scaling_tpu.serve.engine import ServeEngine

    real = ServeEngine._sample_grid

    def off_by_one(self, logits, *rest):
        sampled = real(self, logits, *rest)
        rows = jnp.arange(sampled.shape[0])[:, None]
        return jnp.where(rows % 7 == 3, (sampled + 1) % logits.shape[-1], sampled)

    monkeypatch.setattr(ServeEngine, "_sample_grid", off_by_one)
    seen = spy_on_the_kind(monkeypatch)
    result = rehearse(run, grown_sparse, workload="toy-serve-sparse-chat", seconds="3")
    assert result["failed"] == 0 and result["correct"] is False
    assert seen["outcome"]["host"]["worst_logit_gap"] > 4 * serve_kind.LOGIT_TOL


def test_the_control_fails_the_limit_the_program_keeps(run, grown_sparse, capsys,
                                                       monkeypatch):
    """``--control fp8``: the reference with fp8 weights misses the limit that
    the program keeps with room."""
    seen = spy_on_the_kind(monkeypatch)
    result = rehearse(run, grown_sparse, 0, "--control", "fp8",
                      workload="toy-serve-sparse-chat", seconds="3")
    assert result["correct"]
    host = seen["outcome"]["host"]
    sound, control = host["worst_logit_gap"], host["control_logit_gap"]
    assert sound < serve_kind.LOGIT_TOL / 2 < serve_kind.LOGIT_TOL < control
    assert control > 3 * sound


def test_an_engine_that_leaves_the_selection_out_is_not_correct(
        run, grown_sparse, capsys, monkeypatch):
    """What ``correct`` sees of the mechanism at the toy's widths: an engine
    whose queries keep every visible line (dense latent attention) is held to
    the reference's choice of 16 and fails the limit."""
    from scaling_tpu.nn.sparse_latent_attention import SparseLatentSelfAttention

    init = SparseLatentSelfAttention.__init__

    def keeps_everything(self, **sizes):
        init(self, **sizes)
        self.index_topk = 256      # a slot's whole context

    monkeypatch.setattr(SparseLatentSelfAttention, "__init__", keeps_everything)
    seen = spy_on_the_kind(monkeypatch)
    result = rehearse(run, grown_sparse, workload="toy-serve-sparse-chat", seconds="3")
    assert result["failed"] == 0 and result["correct"] is False
    assert seen["outcome"]["host"]["worst_logit_gap"] > serve_kind.LOGIT_TOL


# ---- the readers on recorded rows -----------------------------------------

LAYER = "jit(mixed_512)/jit(_lambda_)/"
# name, start_ns, dur_ns, op_name: what ``load_scoped_ops`` gives
OPS = [
    ["%fusion.1 = bf16[512,7168] fusion(...)", 0.0, 100e3, ""],                   # embedding
    ["%fusion.11 = bf16[512,24576] fusion(...)", 100e3, 300e3, LAYER + "attn/dot_general"],
    ["%fusion.12 = bf16[512,8192] fusion(...)", 400e3, 200e3, LAYER + "attn/indexer/dot_general"],
    ["%scatter.3 = bf16[32769,16,128] scatter(...)", 600e3, 100e3,
     LAYER + "attn/indexer/scatter"],
    ["%fusion.20 = f32[160,2048] fusion(...)", 700e3, 500e3,
     LAYER + "attn/indexer/index_select/while/body/cond/branch_1_fun/dot_general"],
    ["%sort.4 = (f32[160,32768], s32[160,32768]) sort(...)", 1200e3, 900e3,
     LAYER + "attn/indexer/index_select/while/body/cond/branch_1_fun/top_k"],
    ["%gather.7 = bf16[32,2048,512] gather(...)", 2100e3, 1500e3,
     LAYER + "attn/sparse_attend/while/body/gather"],
    ["%fusion.30 = bf16[32,128,512] fusion(...)", 3500e3, 600e3,     # overlaps the gather
     LAYER + "attn/sparse_attend/while/body/dot_general"],
    ["%fusion.13 = f32[512,256] fusion(...)", 4100e3, 600e3, LAYER + "moe/dot_general"],
    ["%fusion.40 = bf16[16,16160] fusion(...)", 4700e3, 200e3, "jit(mixed_512)/head/dot_general"],
    ["%copy.3 = s32[16] copy(...)", 4900e3, 100e3, ""],
]
SPANS = [
    ("serve.tick", 0, 15e6, {"step": 1}),
    ("serve.mixed", 0, 12e6, {                    # 16 decode rows at 16k lines each
        "step": 1, "sparse_layers": 6, "latent_layers": 6, "index_lines": 256_000,
        "index_pairs": 256_000, "chosen_pairs": 32_768, "chosen_lines": 32_768,
        "latent_lines": 256_000, "latent_pairs": 256_000}),
    ("serve.tick", 20e6, 85e6, {"step": 2}),
    ("serve.mixed", 20e6, 82e6, {                 # 3 chunk rows of 160 at ~10k lines
        "step": 2, "sparse_layers": 6, "latent_layers": 6, "index_lines": 30_480,
        "index_pairs": 4_838_640, "chosen_pairs": 983_040, "chosen_lines": 6_144,
        "latent_lines": 30_480, "latent_pairs": 4_838_640}),
    ("serve.mixed", 110e6, 5e6, {"step": 3}),     # a tick of the warm-up: no field
]
COUNTERS = {"serve_prefill_tokens_total": 480, "serve_tokens_generated_total": 16,
            "serve_moe_assignments_total": 500}
ARCH = cells.load_json(cells.ROOT / "configs" / f"{CONFIG}.json")["transformer_architecture"]
CTX = {"device": {"peaks": {"hbm_bytes_per_s": 819e9, "flops_per_s": 197e12}},
       "config": {"transformer_architecture": ARCH}, "host": {}, "trace": {"class_s": {}}}
PAIR, INDEX_PAIR = 2 * 128 * 1088, 2 * 64 * 128


def test_the_counts_are_the_issues_by_hand():
    assert ops_count.chosen_flops(1, 128, 512, 64) == PAIR == 278_528
    assert ops_count.chosen_bytes(2048, 512, 64, 2) == 2048 * 1152
    assert ops_count.index_flops(1, 64, 128) == INDEX_PAIR == 16_384
    assert ops_count.index_bytes(1, 128, 2) == 256
    assert ops_count.indexer_matmul_params(7168, 1536, 64, 128) == 13_959_424 - 256
    # a query past index_topk: 570 MFLOP a layer whatever the context; at 16k
    # visible lines the index scores are 268 MFLOP, at 32k 537 MFLOP
    assert ops_count.chosen_flops(2048, 128, 512, 64) == pytest.approx(570.4e6, rel=1e-3)
    assert ops_count.index_flops(16_384, 64, 128) == pytest.approx(268.4e6, rel=1e-3)
    assert ops_count.index_flops(32_768, 64, 128) == pytest.approx(536.9e6, rel=1e-3)
    # the matrices a prompt token meets in the six layers, the indexers' among
    # them, with the 8 x 8 / 256 assignments a routed layer's held experts
    # expect of it: the ISSUE's 3.77 GFLOP
    shape = dict(heads=128, q_lora=1536, kv_lora=512, nope=128, rope=64, v=128)
    common = dict(sparse_layers=6, dense_layers=1, routed_layers=5, hidden=7168,
                  vocab=16_160, dense_width=18_432, expert_width=2048, shared_width=2048,
                  num_experts=256, attention=shape, index_heads=64, index_dim=128)
    per_token = (6 * (187_107_328 - 2048) + 3 * 7168 * 18_432
                 + 5 * (7168 * 256 + 3 * 7168 * 2048))
    assert ops_count.serve_flops(1, 0, 0, 0, 0, **common) == 2.0 * (
        per_token + 6 * (13_959_424 - 256))
    assert ops_count.serve_flops(4, 0, 5, 0, 0, **common) / 4 == pytest.approx(
        3.77e9, rel=5e-3)
    # an assignment on a held expert, a sampled token, a chosen and an index pair
    base = ops_count.serve_flops(1, 0, 0, 0, 0, **common)
    assert ops_count.serve_flops(1, 0, 1, 0, 0, **common) - base == 2.0 * 3 * 7168 * 2048
    assert ops_count.serve_flops(1, 1, 0, 0, 0, **common) - base == 2.0 * 7168 * 16_160
    assert ops_count.serve_flops(1, 0, 0, 1, 0, **common) - base == 6 * PAIR
    assert ops_count.serve_flops(1, 0, 0, 0, 1, **common) - base == 6 * INDEX_PAIR


def test_readers_give_the_five_values_by_hand():
    assert sparse_latent.union_seconds(OPS) == pytest.approx(5.0e-3)
    # the indexer's scope holds 0.2 + 0.1 + 0.5 + 0.9 ms; scores and choice 1.4
    assert sparse_latent.index_time_pct(CTX, ops=OPS, spans=SPANS) == pytest.approx(
        100 * 1.7 / 5.0)
    # Kimi's reader of the attn scope covers the mixers WITH their indexers
    assert latent.latent_time_pct(CTX, ops=OPS, spans=SPANS) == pytest.approx(
        100 * 4.0 / 5.0)
    # tick 1 (decode rows): the indexer is bound by its bytes, the attention by
    # its bytes too; tick 2 (chunks): both by their FLOPs
    decode_index = max(INDEX_PAIR * 256_000 / 197e12, 256_000 * 256 / 819e9)
    chunk_index = max(INDEX_PAIR * 4_838_640 / 197e12, 30_480 * 256 / 819e9)
    assert decode_index == 256_000 * 256 / 819e9
    assert chunk_index == INDEX_PAIR * 4_838_640 / 197e12
    assert sparse_latent.indexer_roofline(CTX, ops=OPS, spans=SPANS) == pytest.approx(
        100 * 6 * (decode_index + chunk_index) / 1.4e-3)
    decode = max(PAIR * 32_768 / 197e12, 32_768 * 1152 / 819e9)
    chunks = max(PAIR * 983_040 / 197e12, 6_144 * 1152 / 819e9)
    assert decode == PAIR * 32_768 / 197e12 and chunks == PAIR * 983_040 / 197e12
    # gather and attention overlap in 3.5-3.6 ms: a union, 2.0 ms
    assert sparse_latent.sparse_latent_roofline(CTX, ops=OPS, spans=SPANS) == pytest.approx(
        100 * 6 * (decode + chunks) / 2.0e-3)
    assert sparse_latent.sparse_chosen_pct(CTX, spans=SPANS) == pytest.approx(
        100 * (32_768 + 983_040) / (256_000 + 4_838_640))
    per_token = (6 * (187_107_328 - 2048 + 13_959_424 - 256) + 3 * 7168 * 18_432
                 + 5 * (7168 * 256 + 3 * 7168 * 2048))
    flops = (2.0 * (496 * per_token + 500 * 3 * 7168 * 2048 + 16 * 7168 * 16_160)
             + 6 * (PAIR * (32_768 + 983_040) + INDEX_PAIR * (256_000 + 4_838_640)))
    assert sparse_latent.tick_mfu_pct(CTX, spans=SPANS, counters=COUNTERS) == pytest.approx(
        100 * flops / 0.100 / 197e12)


def test_no_share_can_pass_one_hundred_on_what_the_chip_can_do():
    def only(scope, seconds):
        return [["%op = ...", 0.0, 1e9 * seconds, LAYER + f"attn/{scope}/x"]]

    # an attention that multiplies a chunk tick's chosen pairs at the peak, and
    # one that reads a decode tick's chosen lines at the published rate
    tick = [SPANS[3]]
    at_peak = only("sparse_attend", 6 * PAIR * 983_040 / 197e12)
    assert sparse_latent.sparse_latent_roofline(CTX, ops=at_peak, spans=tick) == \
        pytest.approx(100.0)
    wide = [("serve.mixed", 0, 1e6, {**SPANS[1][3], "chosen_pairs": 16, "chosen_lines": 32_768})]
    at_rate = only("sparse_attend", 6 * 32_768 * 1152 / 819e9)
    assert sparse_latent.sparse_latent_roofline(CTX, ops=at_rate, spans=wide) == \
        pytest.approx(100.0)
    # an indexer that reads a decode tick's keys at the published rate
    at_rate = only("indexer/index_select", 6 * 256_000 * 256 / 819e9)
    assert sparse_latent.indexer_roofline(CTX, ops=at_rate, spans=[SPANS[1]]) == \
        pytest.approx(100.0)
    everything = [[n, s, d, LAYER + "attn/indexer/x"] for n, s, d, _ in OPS]
    assert sparse_latent.index_time_pct(CTX, ops=everything, spans=SPANS) == pytest.approx(100)
    # every query under index_topk: nothing was spared
    dense = [("serve.mixed", 0, 1e6, {**SPANS[1][3], "chosen_pairs": 256_000})]
    assert sparse_latent.sparse_chosen_pct(CTX, spans=dense) == pytest.approx(100.0)
    shape = dict(heads=128, q_lora=1536, kv_lora=512, nope=128, rope=64, v=128)
    flops = ops_count.serve_flops(
        16, 16, 20, 32_768, 256_000, sparse_layers=6, dense_layers=1, routed_layers=5,
        hidden=7168, vocab=16_160, dense_width=18_432, expert_width=2048, shared_width=2048,
        num_experts=256, attention=shape, index_heads=64, index_dim=128)
    spans = [("serve.tick", 0, 1e9 * flops / 197e12, {"step": 1}), SPANS[1]]
    assert sparse_latent.tick_mfu_pct(CTX, spans=spans, counters={
        "serve_tokens_generated_total": 16,
        "serve_moe_assignments_total": 20}) == pytest.approx(100.0)


def test_without_the_scope_or_the_field_a_reader_gives_none_not_zero():
    bare = [[name, start, dur, ""] for name, start, dur, _ in OPS]
    no_field = SPANS[4:]
    kimi = [(n, s, d, {k: v for k, v in f.items() if not k.startswith(
        ("sparse", "index", "chosen"))}) for n, s, d, f in SPANS]
    for reader in (sparse_latent.sparse_latent_roofline, sparse_latent.indexer_roofline,
                   sparse_latent.index_time_pct):
        assert reader(CTX, ops=bare, spans=SPANS) is None
        assert reader(CTX, ops=[], spans=SPANS) is None
        assert reader(CTX, ops=OPS, spans=no_field) is None
        assert reader(CTX, ops=OPS, spans=kimi) is None
    no_peak = {**CTX, "device": {"peaks": None}}
    assert sparse_latent.sparse_latent_roofline(no_peak, ops=OPS, spans=SPANS) is None
    assert sparse_latent.indexer_roofline(no_peak, ops=OPS, spans=SPANS) is None
    assert sparse_latent.sparse_chosen_pct(CTX, spans=no_field) is None
    assert sparse_latent.sparse_chosen_pct(CTX, spans=kimi) is None
    assert sparse_latent.tick_mfu_pct(CTX, spans=no_field, counters=COUNTERS) is None
    assert sparse_latent.tick_mfu_pct(CTX, spans=kimi, counters=COUNTERS) is None
    assert sparse_latent.tick_mfu_pct(CTX, spans=SPANS, counters={}) is None
    assert sparse_latent.tick_mfu_pct(no_peak, spans=SPANS, counters=COUNTERS) is None
    near = [["%f = ...", 0.0, 1e3, "jit(mixed)/attn/indexer_out/mul"],
            ["%g = ...", 1e3, 1e3, "jit(mixed)/attn/my_sparse_attend/mul"]]
    assert sparse_latent.index_time_pct(CTX, ops=near, spans=SPANS) is None
    assert sparse_latent.sparse_latent_roofline(CTX, ops=near, spans=SPANS) is None


# ---- the files, by name and by membership ---------------------------------

def test_metric_files_name_the_readers_and_the_cell_lists_them():
    bench = json.loads((cells.REPO / "BENCHMARK.json").read_text())
    entries = {m["name"]: m for m in bench["per_layer"]}
    assert set(METRICS) <= set(entries)
    for name, (layer, source, reader, better) in METRICS.items():
        spec = cells.load_json(cells.ROOT / "metrics" / f"{name}.json")
        assert spec["reader"] == f"sparse_latent:{reader}"
        assert spec["unit"] == entries[name]["unit"] == "%"
        assert (entries[name]["layer"], entries[name]["source"]) == (layer, source)
        assert (entries[name]["moves"], entries[name]["better"]) == (
            "serve_tokens_per_s", better)
        assert entries[name]["workloads"] == [CELL]
        assert callable(cells.load_reader(name))
    # the cell reports what Kimi-K2's cell reports but the two whose counts
    # take every visible line as attended, + its own five
    listed = {m["name"] for m in bench["per_layer"] if CELL in m["workloads"]}
    kimi = {m["name"] for m in bench["per_layer"] if KIMI in m["workloads"]}
    assert kimi - listed == {"latent_roofline.saturated", "tick_mfu_pct.latent"}
    assert listed - kimi == set(METRICS)
    assert {"latent_time_pct.saturated", "moe_time_pct.saturated", "peak_hbm_gb.serve",
            "tick_ms_p50.saturated", "device_idle_pct.saturated"} <= listed
    assert {n for n in listed if n.startswith("tick_mfu_pct")} == {"tick_mfu_pct.sparse_latent"}
    # appended: wherever this cell and Kimi-K2's are listed, this one comes after
    for m in bench["end_to_end"] + bench["per_layer"]:
        cells_of = m.get("workloads", [])
        if CELL in cells_of:
            assert KIMI in cells_of or m["name"] in METRICS
            assert cells_of[-1] == CELL or cells_of.index(CELL) > cells_of.index(KIMI)
    cell = cells.load_cell(CELL)
    assert cell.reference_name == REFERENCE and cell.chips == 1
    assert [m["name"] for m in cell.metrics("end_to_end")] == ["setup_s", "serve_tokens_per_s"]
    names = [w["name"] for w in bench["workloads"]]
    configs = [c["name"] for c in bench["configs"]]
    assert names.index(CELL) > names.index(KIMI)
    assert configs.index(CONFIG) > configs.index("kimi-k2-instruct-serve")
    entry = bench["workloads"][names.index(CELL)]
    assert (entry["config"], entry["traffic"], entry["chips"]) == (CONFIG, TRAFFIC, 1)
    assert len(entry["why"]) <= 200
    for word in ("16 slots x 32,768", "2,048", "1/32", "depth 6"):
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
    assert (spec["num_heads"], spec["kv_lora"], spec["nope"], spec["rope"], spec["v"]) == (
        128, 512, 128, 64, 128)
    assert (spec["index_heads"], spec["index_dim"], spec["index_topk"]) == (64, 128, 2048)
    assert (spec["n_group"], spec["topk_group"]) == (8, 4)
    assert spec["yarn"] == (40.0, 4096.0, 32.0, 1.0, 1.0, 1.0)
    assert (spec["num_dense"], spec["top_k"], spec["scale"], spec["gate_eps"]) == (
        1, 8, 2.5, 1e-20)
    with pytest.raises(SystemExit, match="the configuration states {'moe_router': 'softmax'"):
        cell.view.reference_spec({**ARCH, "moe_router": "softmax"})
    with pytest.raises(SystemExit, match="the configuration lacks \\['index_topk'\\]"):
        cell.view.reference_spec({k: v for k, v in ARCH.items() if k != "index_topk"})


def test_the_configuration_names_every_key_it_changed_and_cuts_no_width():
    """What ``test_configs.py`` asks of every configuration, for one whose
    keys are config.json's own (its table knows dense keys only)."""
    config = cells.load_json(cells.ROOT / "configs" / f"{CONFIG}.json")
    bench = json.loads((cells.REPO / "BENCHMARK.json").read_text())
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert entry["source"] == config["source"] == (
        "https://huggingface.co/deepseek-ai/DeepSeek-V3.2-Exp/blob/main/config.json")
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
        "index_n_heads": arch["index_n_heads"], "index_head_dim": arch["index_head_dim"],
        "index_topk": arch["index_topk"],
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
    }
    assert {key: config[key] for key in as_run} == as_run
    assert arch["moe_num_experts"] == published["n_routed_experts"] == 256
    # the three leading dense layers count once
    assert published["first_k_dense_replace"] == config["first_k_dense_replace"] == 3
    assert arch["layer_pattern"] == ["latent", "mlp"] + ["latent", "moe"] * 5
    assert published["parameter_count"] == 671_877_944_064 == (
        3 * 597_442_816 + 58 * (246_956_544 + 256 * 44_040_192) + 2 * 129_280 * 7168 + 7168)
    assert "32 chips" in config["stands_for"] and "3,825,510,144" in config["stands_for"]
    assert {"block", "attention", "indexer", "index_keys", "mtp", "forms", "engine_shape",
            "rotary", "router", "experts", "state", "precision", "init",
            "parameter_count"} <= set(config["assumed"])
    assert config["assumed"]["indexer"].startswith("FROM MEMORY")
    assert "Hadamard" in config["assumed"]["index_keys"]
    assert config["num_nextn_predict_layers"] == 1 and "NOT served" in config["assumed"]["mtp"]
    engine = config["engine"]
    assert (engine["num_slots"], engine["context"], engine["enable_prefix_cache"]) == (
        16, 32768, False)
    # three chunk rows beside a decode row in every slot: the scheduler charges
    # the decode rows to the budget first
    assert engine["token_budget"] == 3 * engine["prefill_chunk"] + engine["num_slots"]
    assert config["chips"] == 1


def test_the_traffic_is_the_issues_and_fits_the_slots():
    """``longdoc32k-burst16``: 16 at once every whole second the rate rule
    gives; no request asks for more than a slot's 32,768 positions or names a
    token outside the held vocabulary; every request passes ``index_topk``
    within its first chunks."""
    traffic = cells.load_json(cells.ROOT / "traffic" / f"{TRAFFIC}.json")
    assert (traffic["generator"], traffic["backlog"], traffic["burst_size"],
            traffic["shape_seed"], traffic["warm_seconds"]) == ("bursts", "cut", 16, 59, 20)
    assert traffic["burst_every_s"] == int(traffic["burst_every_s"]) >= 2
    assert traffic["prompt"] == {"median": 12288, "sigma": 0.6, "min": 4096, "max": 28672}
    assert traffic["output"] == {"median": 160, "sigma": 0.5, "min": 32, "max": 512}
    assert "tokens" not in traffic   # above the knee: whatever the engine completes
    assert traffic["check_requests"] == 4 and traffic["check_max_tokens"] == 8192
    assert traffic["trace_seconds"] == 3
    config = cells.load_json(cells.ROOT / "configs" / f"{CONFIG}.json")
    context = config["engine"]["context"]
    assert traffic["max_total"] == context == 32768
    vocab = config["transformer_architecture"]["vocab_size"]
    requests = cells.load_cell(CELL).generate(traffic, 2**31 + 5, 51.0, vocab)
    counted = [r for r in requests if r.due_s >= 0]
    assert len(counted) == 16 * len({r.due_s for r in counted})
    assert sum(r.due_s < 0 for r in requests) == 16     # one uncounted burst
    assert {r.due_s for r in requests if r.due_s < 0} == {-20.0}
    assert max(len(r.prompt) + r.output_len for r in requests) <= context
    assert min(len(r.prompt) for r in requests) >= 4096 > 2048
    assert min(r.output_len for r in requests) >= 32
    assert all(1 <= t < vocab for r in requests[:4] for t in r.prompt)
    # the check teacher-forces requests of 4,096-8,192 tokens: every checked
    # position chose 2,048 of 2,049-8,192 lines
    assert sum(len(r.prompt) + r.output_len <= traffic["check_max_tokens"]
               for r in counted) >= 4
    mean_prompt = sum(len(r.prompt) for r in counted) / len(counted)
    mean_output = sum(r.output_len for r in counted) / len(counted)
    assert 12_000 < mean_prompt < 15_000 and 160 < mean_output < 200
