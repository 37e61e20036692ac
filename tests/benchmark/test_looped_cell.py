"""What PR 40 brings for ``ouro-2.6b-serve`` as files (``reference/`` and
``views/looped_decoder.py``, ``readers/looped.py``, ``looped_ops_count.py``,
five metrics), rehearsed on the CPU at a toy width through a copy of
``benchmark/`` into which only a toy configuration is added; and the readers
on recorded rows."""

import json
import shutil
from pathlib import Path

import pytest

from benchmark import cells, looped_ops_count, serve_kind
from benchmark.readers import looped

TOY_LOOPED = Path(__file__).parent / "data" / "toy_looped"
BENCH = TOY_LOOPED / "BENCHMARK.json"
CELL = "serve-ouro2.6b-reason-burst"
LOOPED_METRICS = {
    "loop_time_pct.saturated": "looped trunk",
    "loop_weights_roofline.saturated": "looped trunk",
    "paged_roofline.looped": "paged kernel",
    "loop_steps_run_mean.saturated": "looped trunk",
    "tick_mfu_pct.saturated": "engine tick",
}


@pytest.fixture(scope="module")
def grown_looped(grown):
    """``grown`` (the toy burst traffic is there) plus the one toy
    configuration and its chat traffic; reference, view, readers and
    metrics are the benchmark's own."""
    shutil.copy(TOY_LOOPED / "configs" / "toy-ouro.json", grown / "configs")
    shutil.copy(TOY_LOOPED / "traffic" / "toy-looped-chat.json", grown / "traffic")
    for part, name in (("reference", "looped_decoder.py"), ("views", "looped_decoder.py"),
                       ("readers", "looped.py")):
        assert (cells.ROOT / part / name).is_file() and (grown / part / name).is_file()
    return grown


def rehearse(run, root, trace=0, *more, workload="toy-serve-looped", seconds="1.5"):
    return run.main(["--workload", workload, "--seed", "3000000019",
                     "--seconds", seconds, "--trace", str(trace), "--rehearse",
                     "--root", str(root), "--benchmark-json", str(BENCH), *more])


def spy_on_the_kind(monkeypatch):
    seen = {}
    real = serve_kind.run
    monkeypatch.setattr(serve_kind, "run", lambda cell, args, env: seen.setdefault(
        "outcome", real(cell, args, env)))
    return seen


def test_looped_serve_cell_is_correct_and_reads_its_loop(run, grown_looped, capsys,
                                                         monkeypatch):
    """The engine serves Ouro's equations through the paged cache, one line
    per (step, layer), every checked token on the reference's best logit
    (float32 on both sides at this width: the configuration says why); the
    traced part's ticks carry the loop's steps and the exit distribution."""
    from scaling_tpu import obs

    seen = spy_on_the_kind(monkeypatch)
    result = rehearse(run, grown_looped, trace=2)
    assert result["correct"] and result["failed"] == 0 and result["unserved"] > 0
    assert seen["outcome"]["host"]["worst_logit_gap"] < 1e-3
    # the CPU has no device plane and no published peak: the readers of the
    # trace and of the peak find nothing and are left out; the counter's has
    # the ticks' passes
    assert set(result["metrics"]) == {"setup_s", "serve_tokens_per_s",
                                      "batch_occupancy_pct",
                                      "loop_steps_run_mean.saturated"}
    assert result["metrics"]["loop_steps_run_mean.saturated"]["value"] == 4.0
    capture = obs.last_capture()
    assert looped.loop_ticks(capture.spans) == [4] * len(
        [1 for n, *_ in capture.spans if n == "serve.mixed"])
    assert capture.counters["serve_loop_layer_passes_total"] == 4 * 2 * len(
        looped.loop_ticks(capture.spans))
    emits = [f for n, _, _, f in capture.spans if n == "serve.emit"]
    assert emits and all(abs(sum(f["exit_p"]) - 1.0) < 1e-4 for f in emits)
    # with a described peak the whole tick's share of it reads a small
    # number, from the counters and the spans alone
    ctx = {"device": {"peaks": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}},
           "config": cells.load_json(grown_looped / "configs" / "toy-ouro.json"),
           "host": {"traced_context_tokens": 100}}
    assert 0 < looped.tick_mfu_pct(ctx) < 1.0


def test_a_plain_cell_reads_none_of_the_looped_metrics(run, grown, capsys):
    """A plain model's spans carry no ``loop_steps``: the readers return
    nothing."""
    from scaling_tpu import obs

    toy = Path(__file__).parent / "data" / "toy" / "BENCHMARK.json"
    run.main(["--workload", "toy-serve-burst", "--seed", "5", "--seconds", "1.5",
              "--trace", "2", "--rehearse", "--root", str(grown),
              "--benchmark-json", str(toy)])
    capture = obs.last_capture()
    assert any(n == "serve.mixed" for n, _, _, _ in capture.spans)
    assert looped.loop_ticks(capture.spans) == []
    assert "serve_loop_layer_passes_total" not in capture.counters
    ctx = {"device": {"peaks": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}},
           "config": {"transformer_architecture": {"num_layers": 2}},
           "host": {}, "trace": None}
    assert looped.loop_steps_run_mean(ctx) is None and looped.tick_mfu_pct(ctx) is None
    assert looped.loop_weights_roofline(ctx) is None and looped.loop_time_pct(ctx) is None
    assert looped.paged_roofline_looped(ctx) is None


def test_a_token_altered_where_it_is_produced_is_not_correct(run, grown_looped, capsys,
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
    result = rehearse(run, grown_looped)
    assert result["failed"] == 0 and result["correct"] is False


def test_steps_that_share_a_cache_line_are_not_correct(run, grown_looped, capsys,
                                                      monkeypatch):
    """The program with every step of a layer on ONE cache line serves
    tokens the harness refuses: the comparison sees the mechanism."""
    from scaling_tpu.nn.attention import PagedKVCacheView

    monkeypatch.setattr(PagedKVCacheView, "at_step", lambda self, step, num_blocks: self)
    result = rehearse(run, grown_looped, workload="toy-serve-looped-chat", seconds="3")
    assert result["failed"] == 0 and result["correct"] is False


def test_the_control_fails_the_limit_the_program_keeps(run, grown_looped, capsys,
                                                       monkeypatch):
    """``--control fp8``: the looped reference with fp8 weights misses the
    limit that the program keeps with room."""
    seen = spy_on_the_kind(monkeypatch)
    # chat traffic the CPU keeps up with: its requests finish, so the gaps
    # are maxima over a few hundred positions and not over a dozen
    result = rehearse(run, grown_looped, 0, "--control", "fp8",
                      workload="toy-serve-looped-chat", seconds="3")
    assert result["correct"]
    host = seen["outcome"]["host"]
    sound, control = host["worst_logit_gap"], host["control_logit_gap"]
    assert sound < serve_kind.LOGIT_TOL / 2 < serve_kind.LOGIT_TOL < control
    assert control > 3 * sound


# ---- the readers on recorded rows -----------------------------------------

# name, start_ns, dur_ns, scope: what ``load_scoped_ops`` gives. The rolled
# loop as a trace may hold it: the ``while`` itself spans its body's
# operations (the second step's here), and the kernel lies inside
LOOP = "jit(mixed)/loop/while"
KERNEL = ('%paged_attention.3 = bf16[8,32,16,128] custom-call(...), '
          'custom_call_target="tpu_custom_call"')
OPS = [
    ["%fusion.1 = bf16[4,32,2048] fusion(...)", 0.0, 100e3, ""],          # embedding
    ["%while.2 = (...) while(...)", 100e3, 4000e3, LOOP],
    ["%fusion.11 = bf16[4,32,5632] fusion(...)", 200e3, 1500e3,
     LOOP + "/body/jit(_lambda_)/dot_general"],
    [KERNEL, 1700e3, 300e3, LOOP + "/body/jit(_lambda_)/pallas_call"],
    ["%fusion.11 = bf16[4,32,5632] fusion(...)", 2100e3, 1500e3,
     LOOP + "/body/jit(_lambda_)/dot_general"],
    [KERNEL, 3600e3, 300e3, LOOP + "/body/jit(_lambda_)/pallas_call"],
    ["%fusion.40 = f32[8,1,49152] fusion(...)", 4100e3, 400e3, ""],        # head
    ["%sort.5 = f32[8,49152] sort(...)", 4500e3, 500e3, ""],
]
SPANS = [
    ("serve.tick", 0, 30e6, {"step": 1}),
    ("serve.mixed", 0, 25e6, {"step": 1, "loop_steps": 4}),
    ("serve.tick", 40e6, 50e6, {"step": 2}),
    ("serve.mixed", 40e6, 45e6, {"step": 2, "loop_steps": 4}),
    ("serve.mixed", 95e6, 5e6, {"step": 3}),  # a tick of the warm-up: no field
]
COUNTERS = {"serve_loop_layer_passes_total": 2 * 4 * 48,
            "serve_prefill_tokens_total": 32, "serve_tokens_generated_total": 16}
ARCH = {"num_layers": 48, "hidden_size": 2048, "num_attention_heads": 16,
        "attention_num_kv_heads": 16, "mlp_factor": 2.75, "vocab_size": 49152,
        "loop_steps": 4}
CTX = {"device": {"peaks": {"hbm_bytes_per_s": 819e9, "flops_per_s": 197e12}},
       "config": {"transformer_architecture": ARCH},
       "host": {"traced_context_tokens": 5000},
       "trace": {"class_s": {"pallas:paged_attention": 0.0006, "other": 0.004}}}
LAYER_PARAMS = 4 * 2048 * 2048 + 3 * 2048 * 5632


def test_readers_give_the_five_values_by_hand():
    assert looped_ops_count.layer_matmul_params(2048, 16, 16, 128, 5632) == LAYER_PARAMS
    assert LAYER_PARAMS == 51_388_416 - 4 * 2048  # a layer without its four norms
    # times are unions: the while spans its body, nothing is counted twice
    assert looped.union_seconds(OPS) == pytest.approx(5.0e-3)
    assert looped.loop_seconds(OPS) == pytest.approx(4.0e-3)
    assert looped.kernel_seconds(OPS) == pytest.approx(0.6e-3)
    assert looped.loop_time_pct(CTX, ops=OPS) == pytest.approx(100 * 4.0 / 5.0)
    # two ticks x 4 steps x 48 layers, a layer's matrices once each, in bf16
    nbytes = 2 * 4 * 48 * LAYER_PARAMS * 2
    assert looped_ops_count.trunk_weight_bytes(2 * 4 * 48, LAYER_PARAMS, 2) == nbytes
    assert looped.loop_weights_roofline(CTX, ops=OPS, spans=SPANS) == pytest.approx(
        100 * nbytes / (4.0e-3 - 0.6e-3) / 819e9)
    # a token's K and V once a (step, layer): four times device_trace's count
    kv = 4 * 48 * 2 * 5000 * 16 * 128 * 2
    assert looped.paged_roofline_looped(CTX) == pytest.approx(100 * kv / 0.0006 / 819e9)
    assert looped.loop_steps_run_mean(CTX, spans=SPANS, counters=COUNTERS) == 4.0
    flops = (2.0 * (48 * 4 * 48 * LAYER_PARAMS + 16 * 2048 * 49152)
             + 4.0 * 5000 * 16 * 128 * 4 * 48)
    assert looped_ops_count.serve_flops(
        48, 16, 5000, 4, 48, LAYER_PARAMS, 2048 * 49152, 16, 128) == flops
    assert looped.tick_mfu_pct(CTX, spans=SPANS, counters=COUNTERS) == pytest.approx(
        100 * flops / 0.080 / 197e12)


def test_a_trace_in_which_the_body_lies_flat_reads_the_same():
    """Without the ``while`` as an operation of its own the scope's time is
    its body's operations', gaps between them left out."""
    flat = [op for op in OPS if " while(" not in op[0]]
    assert looped.loop_seconds(flat) == pytest.approx(3.6e-3)
    assert looped.loop_time_pct(CTX, ops=flat) == pytest.approx(100 * 3.6 / 4.6)


def test_without_the_scope_or_the_field_a_reader_gives_none_not_zero():
    bare = [[name, start, dur, ""] for name, start, dur, _ in OPS]
    assert looped.loop_seconds(bare) is None
    assert looped.loop_time_pct(CTX, ops=bare) is None and looped.loop_time_pct(CTX, ops=[]) is None
    assert looped.loop_weights_roofline(CTX, ops=bare, spans=SPANS) is None
    assert looped.loop_weights_roofline(CTX, ops=OPS, spans=SPANS[4:]) is None
    assert looped.loop_steps_run_mean(CTX, spans=SPANS[4:], counters=COUNTERS) is None
    assert looped.loop_steps_run_mean(CTX, spans=SPANS, counters={}) is None
    assert looped.tick_mfu_pct(CTX, spans=SPANS[4:], counters=COUNTERS) is None
    plain = {**CTX, "config": {"transformer_architecture": {**ARCH, "loop_steps": 1}}}
    assert looped.paged_roofline_looped(plain) is None
    assert looped.paged_roofline_looped({**CTX, "trace": None}) is None


def test_the_scope_is_read_from_the_hlo_a_trace_carries(tmp_path):
    """A trace taken here, on the CPU, of a jitted function whose rolled loop
    lies under ``jax.named_scope("loop")``: the instructions compiled from
    inside it, the loop's body included, are found by name; the others not."""
    import jax
    import jax.numpy as jnp

    from benchmark import xplane_hlo

    @jax.jit
    def stepped(x, w):
        with jax.named_scope("loop"):
            x, _ = jax.lax.scan(lambda h, _: (jnp.tanh(h @ w), None), x, None, length=4)
        with jax.named_scope("unlooped"):
            return jnp.sin(x).sum()

    x = jnp.ones((64, 64))
    stepped(x, x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    stepped(x, x).block_until_ready()
    jax.profiler.stop_trace()
    trace = sorted(tmp_path.glob("**/*.xplane.pb"))[-1]
    modules = xplane_hlo.hlo_modules(trace.read_bytes())
    scopes = xplane_hlo.instruction_scopes(modules["jit_stepped"], looped.SCOPE)
    assert any("while" in op for op in scopes.values())
    assert all("/loop/" in op or op.endswith("/loop") for op in scopes.values())
    assert not any("unlooped" in op for op in scopes.values())


def test_metric_files_name_the_readers_and_the_cell_lists_them():
    bench = json.loads((cells.REPO / "BENCHMARK.json").read_text())
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name, layer in LOOPED_METRICS.items():
        spec = cells.load_json(cells.ROOT / "metrics" / f"{name}.json")
        assert spec["reader"].split(":")[0] == "looped" and spec["unit"] == entries[name]["unit"]
        assert entries[name]["layer"] == layer
        assert entries[name]["moves"] == "serve_tokens_per_s"
        assert entries[name]["workloads"] == [CELL]
        assert callable(cells.load_reader(name))
    # the cell reports what the Mistral burst cell reports, but the paged
    # kernel's share under the reader that counts its real lines
    listed = {m["name"] for m in bench["per_layer"] if CELL in m["workloads"]}
    mistral = {m["name"] for m in bench["per_layer"]
               if "serve-mistral7b-chat-burst" in m["workloads"]}
    assert listed == (mistral - {"paged_roofline.saturated"}) | set(LOOPED_METRICS)
    cell = cells.load_cell(CELL)
    assert cell.reference_name == "looped_decoder" and cell.chips == 1
    assert [m["name"] for m in cell.metrics("end_to_end")] == ["setup_s", "serve_tokens_per_s"]


def test_the_traffic_is_the_issues_and_fits_the_slots():
    """``reason-burst16``: 16 at once every whole second the rate rule gives,
    the lengths of ISSUE 40; no request asks for more than a slot's 640
    positions, whatever the seed."""
    traffic = cells.load_json(cells.ROOT / "traffic" / "reason-burst16.json")
    assert (traffic["generator"], traffic["backlog"], traffic["burst_size"]) == (
        "bursts", "cut", 16)
    assert traffic["burst_every_s"] == int(traffic["burst_every_s"]) <= 8
    assert traffic["prompt"] == {"median": 128, "sigma": 0.6, "min": 32, "max": 320}
    assert traffic["output"] == {"median": 256, "sigma": 0.5, "min": 64, "max": 512}
    config = cells.load_json(cells.ROOT / "configs" / "ouro-2.6b-serve.json")
    context = config["engine"]["context"]
    assert traffic["max_total"] == traffic["check_max_tokens"] == context == 640
    requests = cells.load_cell(CELL).generate(traffic, 2**31 + 5, 51.0, 49152)
    counted = [r for r in requests if r.due_s >= 0]
    assert len(counted) == 16 * len({r.due_s for r in counted})
    assert max(len(r.prompt) + r.output_len for r in requests) <= context
    assert min(r.output_len for r in requests) >= 64
