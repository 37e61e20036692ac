"""What PR 48 brings for ``lfm2-24b-a2b-serve`` as files (``reference/`` and
``views/conv_moe_decoder.py``, ``readers/conv_moe.py``,
``conv_moe_ops_count.py``, four metrics, ``traffic/reason-burst64-fast.json``),
rehearsed on the CPU at a toy width through a copy of ``benchmark/`` into
which only a toy configuration is added; and the readers on recorded rows.
Membership is pinned, never position: the next configuration's PR appends
after these entries."""

import json
import shutil
from pathlib import Path

import pytest

from benchmark import cells, conv_moe_ops_count, serve_kind
from benchmark.readers import conv_moe

TOY_CONVMOE = Path(__file__).parent / "data" / "toy_convmoe"
BENCH = TOY_CONVMOE / "BENCHMARK.json"
CELL = "serve-lfm2-24b-reason-burst"
CONFIG = "lfm2-24b-a2b-serve"
TRAFFIC = "reason-burst64-fast"
NEMOTRON = "serve-nemotron3nano-reason-burst"
METRICS = {
    "conv_time_pct.saturated": ("short convolution", "device_trace"),
    "conv_weights_roofline.saturated": ("short convolution", "device_trace"),
    "moe_routed_roofline.saturated": ("routed MLP", "device_trace"),
    "tick_mfu_pct.convmoe": ("engine tick", "program_counter"),
}


@pytest.fixture(scope="module")
def grown_convmoe(grown):
    """``grown`` (the toy burst traffic is there) plus the one toy
    configuration and its chat traffic; reference, view, readers and
    metrics are the benchmark's own."""
    shutil.copy(TOY_CONVMOE / "configs" / "toy-lfm2.json", grown / "configs")
    shutil.copy(TOY_CONVMOE / "traffic" / "toy-convmoe-chat.json", grown / "traffic")
    for part, name in (("reference", "conv_moe_decoder.py"),
                       ("views", "conv_moe_decoder.py"), ("readers", "conv_moe.py")):
        assert (cells.ROOT / part / name).is_file() and (grown / part / name).is_file()
    return grown


def rehearse(run, root, trace=0, *more, workload="toy-serve-convmoe", seconds="1.5"):
    return run.main(["--workload", workload, "--seed", "3000000019",
                     "--seconds", seconds, "--trace", str(trace), "--rehearse",
                     "--root", str(root), "--benchmark-json", str(BENCH), *more])


def spy_on_the_kind(monkeypatch):
    seen = {}
    real = serve_kind.run
    monkeypatch.setattr(serve_kind, "run", lambda cell, args, env: seen.setdefault(
        "outcome", real(cell, args, env)))
    return seen


def test_conv_moe_serve_cell_is_correct_and_reads_its_tails_and_its_load(
        run, grown_convmoe, capsys, monkeypatch):
    """The engine serves the stack through the paged cache and the conv-tail
    lines, every checked token on the reference's best logit (float32 on both
    sides at this width: the configuration says why); the traced part's ticks
    carry the rows whose tails advanced, and the load of all the experts."""
    from scaling_tpu import obs

    seen = spy_on_the_kind(monkeypatch)
    result = rehearse(run, grown_convmoe, trace=2)
    assert result["correct"] and result["failed"] == 0 and result["unserved"] > 0
    assert seen["outcome"]["host"]["worst_logit_gap"] < 1e-3
    # the CPU has no device plane and no published peak: the readers of the
    # trace and of the peak find nothing and are left out; the counters' and
    # the spans' have the ticks' numbers
    assert set(result["metrics"]) == {
        "setup_s", "serve_tokens_per_s", "batch_occupancy_pct",
        "moe_load_max_over_mean.saturated"}
    capture = obs.last_capture()
    mixed = conv_moe.conv_ticks(capture.spans)
    assert mixed and all(f["conv_lines"] == 3 and 0 < f["conv_rows"] <= 4 for f in mixed)
    assert capture.counters["serve_conv_state_updates_total"] == 3 * sum(
        f["conv_rows"] for f in mixed)
    # with a described peak the whole tick's share of it reads a small
    # number, from the counters and the spans alone
    ctx = {"device": {"peaks": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}},
           "config": cells.load_json(grown_convmoe / "configs" / "toy-lfm2.json"),
           "host": {"traced_context_tokens": 100}}
    assert 0 < conv_moe.tick_mfu_pct(ctx) < 1.0


def test_a_plain_cell_reads_none_of_the_new_metrics(run, grown, capsys):
    """A plain model's spans carry no ``conv_rows``, its trace no ``conv``
    scope and its configuration no routed layer of a pattern: the readers
    return nothing. What the parent commit's program gives under this PR's
    benchmark files."""
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
    assert conv_moe.tick_mfu_pct(ctx) is None and conv_moe.conv_time_pct(ctx) is None
    assert conv_moe.conv_weights_roofline(ctx) is None
    assert conv_moe.moe_routed_roofline(ctx) is None


def test_a_tail_that_is_never_written_is_not_correct(run, grown_convmoe, capsys,
                                                    monkeypatch):
    """The program that drops the tails it computed (every tick starts from
    the lines as they were) serves tokens the harness refuses: the comparison
    sees the mechanism."""
    from scaling_tpu.nn import short_conv

    real = short_conv.GatedShortConv._serve
    monkeypatch.setattr(
        short_conv.GatedShortConv, "_serve",
        lambda self, weight, u, view: (real(self, weight, u, view)[0], view))
    result = rehearse(run, grown_convmoe, workload="toy-serve-convmoe-chat", seconds="3")
    assert result["failed"] == 0 and result["correct"] is False


def test_the_control_fails_the_limit_the_program_keeps(run, grown_convmoe, capsys,
                                                       monkeypatch):
    """``--control fp8``: the reference with fp8 weights misses the limit that
    the program keeps with room."""
    seen = spy_on_the_kind(monkeypatch)
    result = rehearse(run, grown_convmoe, 0, "--control", "fp8",
                      workload="toy-serve-convmoe-chat", seconds="3")
    assert result["correct"]
    host = seen["outcome"]["host"]
    sound, control = host["worst_logit_gap"], host["control_logit_gap"]
    assert sound < serve_kind.LOGIT_TOL / 2 < serve_kind.LOGIT_TOL < control
    assert control > 3 * sound


# ---- the readers on recorded rows -----------------------------------------

CONV = "jit(mixed)/jit(_lambda_)/conv"
MOE = "jit(mixed)/jit(_lambda_)/moe"
MLP = "jit(mixed)/jit(_lambda_)/mlp"
KERNEL = ('%paged_attention.3 = bf16[64,4,256,128] custom-call(...), '
          'custom_call_target="tpu_custom_call"')
# name, start_ns, dur_ns, op_name: what ``load_scoped_ops`` gives
OPS = [
    ["%fusion.1 = bf16[8,32,2048] fusion(...)", 0.0, 100e3, ""],            # embedding
    ["%fusion.11 = bf16[8,32,6144] fusion(...)", 100e3, 300e3, CONV + "/dot_general"],
    ["%fusion.12 = f32[8,32,2048] fusion(...)", 400e3, 100e3, CONV + "/mul"],
    ["%fusion.13 = bf16[8,32,2048] fusion(...)", 450e3, 150e3, CONV + "/dot_general"],  # overlaps
    ["%fusion.15 = bf16[8,32,11776] fusion(...)", 600e3, 600e3, MLP + "/dot_general"],
    ["%fusion.21 = bf16[64,8,32,1536] fusion(...)", 1200e3, 2500e3, MOE + "/ebch,ehf->ebcf"],
    ["%fusion.22 = bf16[8,32,2048] fusion(...)", 3700e3, 300e3, MOE + "/dot_general"],
    [KERNEL, 4000e3, 200e3, "jit(mixed)/jit(_lambda_)/pallas_call"],
    ["%fusion.40 = f32[64,1,65536] fusion(...)", 4200e3, 500e3, ""],          # head
    ["%fusion.41 = s32[64] fusion(...)", 4700e3, 300e3, ""],
]
SPANS = [
    ("serve.tick", 0, 15e6, {"step": 1}),
    ("serve.mixed", 0, 12e6, {"step": 1, "conv_rows": 64, "conv_lines": 6}),
    ("serve.emit", 13e6, 1e6, {"step": 1, "load_max": 11, "load_mean": 4.0,
                               "experts_idle": 2}),
    ("serve.tick", 20e6, 25e6, {"step": 2}),
    ("serve.mixed", 20e6, 22e6, {"step": 2, "conv_rows": 60, "conv_lines": 6}),
    ("serve.emit", 43e6, 1e6, {"step": 2, "load_max": 9, "load_mean": 3.75,
                               "experts_idle": 0}),
    ("serve.mixed", 50e6, 5e6, {"step": 3}),  # a tick of the warm-up: no field
]
COUNTERS = {"serve_moe_assignments_total": 2976,
            "serve_prefill_tokens_total": 64, "serve_tokens_generated_total": 60}
ARCH = cells.load_json(cells.ROOT / "configs" / f"{CONFIG}.json")["transformer_architecture"]
CTX = {"device": {"peaks": {"hbm_bytes_per_s": 819e9, "flops_per_s": 197e12}},
       "config": {"transformer_architecture": ARCH},
       "host": {"traced_context_tokens": 5000}}
H, F, I, E, V = 2048, 1536, 11776, 64, 65536


def test_readers_give_the_four_values_by_hand():
    assert conv_moe_ops_count.conv_matmul_params(H) == H * 3 * H + H * H
    # a conv operator without its filter
    assert conv_moe_ops_count.conv_matmul_params(H) == 16_783_360 - 3 * H
    assert conv_moe_ops_count.conv_tail_bytes(H, 3, 2) == 8 * 1024
    assert conv_moe.pattern_counts(ARCH) == {"conv": 6, "attention": 2, "mlp": 2, "moe": 6}
    # times are unions: the two overlapping operations count 200 us, not 250
    assert conv_moe.union_seconds(OPS) == pytest.approx(5.0e-3)
    assert conv_moe.scope_seconds(OPS, "conv") == pytest.approx(0.5e-3)
    assert conv_moe.scope_seconds(OPS, "moe") == pytest.approx(2.8e-3)
    assert conv_moe.conv_time_pct(CTX, ops=OPS) == pytest.approx(100 * 0.5 / 5.0)
    # per tick 6 layers x (in_proj, out_proj and the filter in bf16 + rows x 2
    # x 8 KiB of tail)
    weights = (4 * H * H + 3 * H) * 2
    nbytes = 6 * (2 * weights + (64 + 60) * 2 * 8 * 1024)
    assert conv_moe_ops_count.conv_layer_bytes(64, H, 3, 2) == weights + 64 * 2 * 8192
    assert conv_moe.conv_weights_roofline(CTX, ops=OPS, spans=SPANS) == pytest.approx(
        100 * nbytes / 0.5e-3 / 819e9)
    # per tick 6 routed layers x (the experts that had a token x 3 matrices +
    # the float32 router)
    layer = lambda read: read * 3 * H * F * 2 + H * E * 4
    assert conv_moe_ops_count.routed_layer_bytes(62, H, F, E, 2) == layer(62)
    assert conv_moe.moe_routed_roofline(CTX, ops=OPS, spans=SPANS) == pytest.approx(
        100 * 6 * (layer(62) + layer(64)) / 2.8e-3 / 819e9)
    per_token = (6 * 4 * H * H + 2 * (2 * H * 2048 + 2 * H * 512)
                 + 2 * 3 * H * I + 6 * H * E)
    flops = (2.0 * (124 * per_token + 2976 * 3 * H * F + 60 * H * V)
             + 4.0 * 5000 * 32 * 64 * 2)
    assert conv_moe_ops_count.serve_flops(
        124, 60, 2976, 5000, conv_layers=6, attention_layers=2, dense_layers=2,
        routed_layers=6, hidden=H, vocab=V, dense_width=I, expert_width=F,
        num_experts=E, heads=32, kv_heads=8, head_dim=64) == flops
    assert conv_moe.tick_mfu_pct(CTX, spans=SPANS, counters=COUNTERS) == pytest.approx(
        100 * flops / 0.040 / 197e12)


def test_no_share_can_pass_one_hundred_on_what_the_chip_can_do():
    """The bytes and FLOPs are counted so that a scope running AT the chip's
    published rate reads 100 and no faster program is possible: every expert
    read, every row advancing, at the time the bytes alone take."""
    ticks = [("serve.mixed", 0, 1, {"conv_rows": 64, "conv_lines": 6}),
             ("serve.emit", 0, 1, {"load_max": 9, "load_mean": 4.0, "experts_idle": 0})]
    conv_bytes = 6 * conv_moe_ops_count.conv_layer_bytes(64, H, 3, 2)
    moe_bytes = 6 * conv_moe_ops_count.routed_layer_bytes(64, H, F, E, 2)
    # 6 x 64 experts x 3 matrices in bf16: the 7.25 GB a tick ISSUE 48 counts
    assert moe_bytes == pytest.approx(7.25e9, rel=1e-3)
    for scope, nbytes, reader in (("conv", conv_bytes, conv_moe.conv_weights_roofline),
                                  ("moe", moe_bytes, conv_moe.moe_routed_roofline)):
        at_the_rate = [["%fusion.1 = ...", 0.0, 1e9 * nbytes / 819e9, f"jit(mixed)/{scope}/x"]]
        assert reader(CTX, ops=at_the_rate, spans=ticks) == pytest.approx(100.0)
    # a tick of 64 decode rows whose device time is the FLOPs at the peak
    flops = conv_moe_ops_count.serve_flops(
        64, 64, 64 * 4 * 6, 64 * 320, conv_layers=6, attention_layers=2, dense_layers=2,
        routed_layers=6, hidden=H, vocab=V, dense_width=I, expert_width=F,
        num_experts=E, heads=32, kv_heads=8, head_dim=64)
    tick = [("serve.tick", 0, 1e9 * flops / 197e12, {"step": 1}), *ticks]
    counters = {"serve_moe_assignments_total": 64 * 4 * 6,
                "serve_tokens_generated_total": 64}
    ctx = {**CTX, "host": {"traced_context_tokens": 64 * 320}}
    assert conv_moe.tick_mfu_pct(ctx, spans=tick, counters=counters) == pytest.approx(100.0)
    # a token's required FLOPs: twice the parameters it meets in the cut (6 conv
    # operators 100 M, 2 attention 21 M, 2 dense FFNs 145 M, 6 x 4 experts 226 M,
    # the tied head 134 M)
    per_token = flops / 64
    assert 2 * 0.62e9 < per_token < 2 * 0.64e9


def test_without_the_scope_or_the_field_a_reader_gives_none_not_zero():
    bare = [[name, start, dur, ""] for name, start, dur, _ in OPS]
    assert conv_moe.scope_seconds(bare, "conv") is None
    assert conv_moe.conv_time_pct(CTX, ops=bare) is None
    assert conv_moe.conv_time_pct(CTX, ops=[]) is None
    assert conv_moe.conv_weights_roofline(CTX, ops=bare, spans=SPANS) is None
    assert conv_moe.conv_weights_roofline(CTX, ops=OPS, spans=SPANS[6:]) is None
    assert conv_moe.moe_routed_roofline(CTX, ops=bare, spans=SPANS) is None
    assert conv_moe.moe_routed_roofline(CTX, ops=OPS, spans=SPANS[6:]) is None
    # a configuration without a pattern's routed layers (OLMoE's): nothing
    plain = {**CTX, "config": {"transformer_architecture": {
        **ARCH, "layer_pattern": None}}}
    assert conv_moe.moe_routed_roofline(plain, ops=OPS, spans=SPANS) is None
    assert conv_moe.tick_mfu_pct(CTX, spans=SPANS[6:], counters=COUNTERS) is None
    assert conv_moe.tick_mfu_pct(CTX, spans=SPANS, counters={}) is None
    no_peak = {**CTX, "device": {"peaks": None}}
    assert conv_moe.tick_mfu_pct(no_peak, spans=SPANS, counters=COUNTERS) is None
    assert conv_moe.conv_weights_roofline(no_peak, ops=OPS, spans=SPANS) is None
    assert conv_moe.moe_routed_roofline(no_peak, ops=OPS, spans=SPANS) is None


def test_the_scopes_are_read_from_the_hlo_a_trace_carries(tmp_path):
    """A trace taken here, on the CPU, of a jitted function with a ``conv``, an
    ``mlp`` and a ``moe`` scope: the instructions compiled from inside ``conv``
    and ``moe`` are found by name and told apart; the others not (an XLA
    ``conv_general_dilated`` is no ``conv`` scope)."""
    import jax
    import jax.numpy as jnp

    from benchmark import xplane_hlo

    @jax.jit
    def mixed(x, w):
        with jax.named_scope("conv"):
            x = jnp.tanh(x @ w)
        with jax.named_scope("moe"):
            x = jnp.sin(x @ w)
        with jax.named_scope("mlp"):
            x = jnp.cos(x @ w)
        return jax.lax.conv_general_dilated(
            x[None, None], w[None, None, :3, :3], (1, 1), "SAME").sum()

    x = jnp.ones((64, 64))
    mixed(x, x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    mixed(x, x).block_until_ready()
    jax.profiler.stop_trace()
    trace = sorted(tmp_path.glob("**/*.xplane.pb"))[-1]
    modules = xplane_hlo.hlo_modules(trace.read_bytes())
    scopes = xplane_hlo.instruction_scopes(modules["jit_mixed"], conv_moe.EITHER)
    assert any(conv_moe.SCOPES["conv"].search(op) for op in scopes.values())
    assert any(conv_moe.SCOPES["moe"].search(op) for op in scopes.values())
    assert not any("mlp" in op or "conv_general_dilated" in op for op in scopes.values())
    assert not any(conv_moe.SCOPES["conv"].search(op) and conv_moe.SCOPES["moe"].search(op)
                   for op in scopes.values())


# ---- the files, by name and by membership ---------------------------------

def test_metric_files_name_the_readers_and_the_cell_lists_them():
    bench = json.loads((cells.REPO / "BENCHMARK.json").read_text())
    entries = {m["name"]: m for m in bench["per_layer"]}
    assert set(METRICS) <= set(entries)
    for name, (layer, source) in METRICS.items():
        spec = cells.load_json(cells.ROOT / "metrics" / f"{name}.json")
        assert spec["reader"].split(":")[0] == "conv_moe"
        assert spec["unit"] == entries[name]["unit"] == "%"
        assert (entries[name]["layer"], entries[name]["source"]) == (layer, source)
        assert entries[name]["moves"] == "serve_tokens_per_s"
        assert entries[name]["workloads"] == [CELL]
        assert callable(cells.load_reader(name))
    # the cell reports what Nemotron's cell reports but its five hybrid
    # metrics (so also the two readers of the routed MLP whose counts hold for
    # any expert shape), + its own four; NOT the paged kernel's share (its
    # reader counts num_layers cache lines, 16 where 2 exist) nor the two
    # expert rooflines of other shapes
    listed = {m["name"] for m in bench["per_layer"] if CELL in m["workloads"]}
    nemotron = {m["name"] for m in bench["per_layer"] if NEMOTRON in m["workloads"]}
    hybrid = {"ssm_time_pct.saturated", "ssm_state_roofline.saturated",
              "moe_held_roofline.saturated", "moe_absent_assign_pct.saturated",
              "tick_mfu_pct.hybrid"}
    assert hybrid <= nemotron
    assert listed == (nemotron - hybrid) | set(METRICS)
    assert {"moe_time_pct.saturated", "moe_load_max_over_mean.saturated"} <= listed
    assert not any("paged_roofline" in name for name in listed)
    assert not listed & {"moe_weights_roofline.saturated", "moe_held_roofline.saturated"}
    # appended: wherever both cells are listed, this one comes after
    for m in bench["end_to_end"] + bench["per_layer"]:
        cells_of = m.get("workloads", [])
        if CELL in cells_of and NEMOTRON in cells_of:
            assert cells_of.index(CELL) > cells_of.index(NEMOTRON)
    cell = cells.load_cell(CELL)
    assert cell.reference_name == "conv_moe_decoder" and cell.chips == 1
    assert [m["name"] for m in cell.metrics("end_to_end")] == ["setup_s", "serve_tokens_per_s"]
    names = [w["name"] for w in bench["workloads"]]
    configs = [c["name"] for c in bench["configs"]]
    assert names.index(CELL) > names.index(NEMOTRON)
    assert configs.index(CONFIG) > configs.index("nemotron3-nano-30b-a3b-serve")
    entry = bench["workloads"][names.index(CELL)]
    assert (entry["config"], entry["traffic"], entry["chips"]) == (CONFIG, TRAFFIC, 1)
    assert len(entry["why"]) <= 200 and "dense" in entry["why"]
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
        == cell.config["reference"] == "conv_moe_decoder"
    bursts = cells.load_module(cells.ROOT, "generators", "bursts",
                               cells.GENERATOR_CONTRACT).generate
    assert cell.generate.__code__.co_code == bursts.__code__.co_code
    # the reference takes nothing of the program
    source = Path(cell.reference.__file__).read_text()
    assert "scaling_tpu" not in source.split('"""', 2)[2]


def test_the_configuration_names_every_key_it_changed_and_cuts_no_width():
    """What ``test_configs.py`` asks of every configuration, for one whose
    keys are config.json's own (its table knows dense keys only)."""
    config = cells.load_json(cells.ROOT / "configs" / f"{CONFIG}.json")
    bench = json.loads((cells.REPO / "BENCHMARK.json").read_text())
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert entry["source"] == config["source"] == (
        "https://huggingface.co/LiquidAI/LFM2-24B-A2B/blob/main/config.json")
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
    for key in reduced:   # a width is never cut
        assert not key.endswith(("_dim", "_rank")) and "intermediate" not in key
        assert key not in ("hidden_size", "num_experts_per_tok", "num_experts", "vocab_size")
    # the program runs what the file states, width for width: EVERY expert,
    # the whole vocabulary
    as_run = {
        "hidden_size": arch["hidden_size"],
        "num_hidden_layers": arch["num_layers"] // 2,   # a block is two mixer layers
        "num_attention_heads": arch["num_attention_heads"],
        "num_key_value_heads": arch["attention_num_kv_heads"],
        "conv_L_cache": arch["conv_kernel"],
        "intermediate_size": int(arch["hidden_size"] * arch["mlp_factor"]),
        "moe_intermediate_size": arch["moe_expert_width"],
        "num_experts": arch["moe_num_experts"],
        "num_experts_per_tok": arch["moe_top_k"],
        "routed_scaling_factor": arch["moe_routed_scaling_factor"],
        "norm_topk_prob": arch["moe_norm_topk_prob"], "vocab_size": arch["vocab_size"],
        "max_position_embeddings": arch["sequence_length"],
        "norm_eps": arch["layernorm"]["layernorm_epsilon"],
        "use_expert_bias": arch["moe_router"] == "sigmoid_bias",
        "conv_bias": arch["attention_bias"] or arch["mlp_bias"],
    }
    assert {key: config[key] for key in as_run} == as_run
    assert arch.get("moe_experts_held") is None and arch.get("moe_shared_expert_width") is None
    assert arch["rotary_embedding_base"] == published["rope_parameters"]["rope_theta"] == 1e6
    assert arch["attention_head_dim"] == 64 and arch["weight_tying"] is True
    assert arch["moe_norm_topk_eps"] == 1e-6 and arch["key_query_norm"] is True
    # the cut: the leading 8 of layer_types, two whole periods, both dense layers
    kinds = {"conv": "conv", "full_attention": "attention"}
    ops = [kinds[k] for k in published["layer_types"]]
    assert len(ops) == 40 == published["num_hidden_layers"] == len(config["layer_types"])
    assert ops[:4] * 10 == ops                      # the period
    dense = published["num_dense_layers"]
    assert arch["layer_pattern"] == [
        kind for i, op in enumerate(ops[:8]) for kind in (op, "mlp" if i < dense else "moe")]
    assert published["parameter_count"] == 23_843_661_440
    assert "4,025,293,440" in reduced["num_hidden_layers"]["why"]
    assert "8 of the 40 layers" in config["stands_for"]
    assert {"tied_head", "head_dim", "block", "conv", "router", "expert_bias", "state",
            "precision", "init"} <= set(config["assumed"])
    assert config["engine"] == {"num_slots": 64, "context": 640,
                                "enable_prefix_cache": False}
    assert config["chips"] == 1


def test_the_traffic_is_the_issues_and_fits_the_slots():
    """``reason-burst64-fast``: 64 at once every whole second the rate rule
    gives, ``reason-burst64``'s lengths to the digit; no request asks for more
    than a slot's 640 positions or names a token outside the vocabulary."""
    traffic = cells.load_json(cells.ROOT / "traffic" / f"{TRAFFIC}.json")
    slow = cells.load_json(cells.ROOT / "traffic" / "reason-burst64.json")
    assert (traffic["generator"], traffic["backlog"], traffic["burst_size"],
            traffic["shape_seed"]) == ("bursts", "cut", 64, 48)
    assert traffic["burst_every_s"] == int(traffic["burst_every_s"]) < slow["burst_every_s"]
    for key in ("prompt", "output", "max_total", "warm_seconds", "check_requests",
                "check_max_tokens", "trace_seconds", "kind"):
        assert traffic[key] == slow[key]
    assert set(traffic) == set(slow)
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
    # the rate rule: the output tokens offered are at least twice what the
    # file says the engine completes, and one whole second more would not be
    offered = sum(r.output_len for r in counted) / 51.0
    steady = 64 * 269.7 / traffic["burst_every_s"]
    assert offered > 0.9 * steady
