"""A routed model (``mlp_type: moe``) through ``ServeEngine``: the mixed
program's load vector rides the tick's one host read, feeds the counter and
the emit span's fields; a dense model's program is the parent's."""

import jax
import numpy as np
import pytest

from scaling_tpu import obs
from scaling_tpu.models.transformer import TransformerConfig
from scaling_tpu.models.transformer.inference import TransformerInferenceModule
from scaling_tpu.models.transformer.model import init_model
from scaling_tpu.serve.engine import EngineConfig, ServeEngine
from tests.transformer.test_serving import jitted_programs

LAYERS, EXPERTS, TOP_K = 2, 8, 2


def make_engine(engine=None, **arch):
    config = TransformerConfig.from_dict({
        "topology": {"model_parallel_size": 1, "pipe_parallel_size": 1,
                     "data_parallel_size": 1, "micro_batch_size": 1,
                     "gradient_accumulation_steps": 1},
        "transformer_architecture": {
            "vocab_size": 96, "hidden_size": 64, "num_layers": LAYERS,
            "num_attention_heads": 4, "attention_num_kv_heads": 4,
            "attention_qkv_in_one": False, "attention_bias": False,
            "mlp_bias": False, "norm_type": "rms", "sequence_length": 128,
            "activation_function": "silu", "precision": "float32", **arch},
        "optimizer": {"loss_scaler": {"enable": False}},
        "learning_rate_scheduler": {"learning_rate": 1e-3},
        "trainer": {"train_iterations": 1, "seed": 0}, "data": {},
        "logger": {"log_dir": None},
    })
    module = init_model(config, None)
    inf = TransformerInferenceModule(
        config, module, module.init_params(jax.random.PRNGKey(0)))
    return ServeEngine(inf, EngineConfig(**{
        "num_slots": 4, "num_blocks": 4 * 8 + 1, "max_blocks_per_seq": 8,
        "block_size": 16, "prefill_chunk": 8, **(engine or {})}))


ROUTED = dict(mlp_type="moe", mlp_factor=0.5, moe_num_experts=EXPERTS,
              moe_top_k=TOP_K, moe_norm_topk_prob=False, key_query_norm=True,
              key_query_norm_scope="projection")


def serve_three(engine):
    rng = np.random.default_rng(0)
    lengths = (13, 30, 7)
    seqs = [engine.submit(list(rng.integers(1, 90, n)), 5) for n in lengths]
    engine.run_until_done()
    assert all(len(s.generated) == 5 for s in seqs)
    # every prompt position, and every generated token but a request's last
    return sum(n + 5 - 1 for n in lengths)


def test_load_feeds_the_counter_and_the_emit_span(tmp_path):
    engine = make_engine(**ROUTED)
    assert engine.num_experts == EXPERTS
    before = obs.get_registry().snapshot()["counters"].get(
        "serve_moe_assignments_total", 0)
    obs.start_capture(tmp_path / "trace")
    try:
        positions = serve_three(engine)
    finally:
        capture = obs.stop_capture()
    moved = obs.get_registry().snapshot()["counters"][
        "serve_moe_assignments_total"] - before
    assert moved == positions * TOP_K * LAYERS
    emits = [f for n, _, _, f in capture.spans if n == "serve.emit"]
    assert emits and all({"load_max", "load_mean", "experts_idle"} <= set(f)
                         for f in emits)
    assert sum(round(f["load_mean"] * EXPERTS) for f in emits) == moved
    assert all(0 <= f["experts_idle"] <= EXPERTS and f["load_max"] >= f["load_mean"]
               for f in emits)
    # one program, whose first result is ONE vector: the grid, then the load
    assert list(engine._mixed_fns) == list(engine.config.mixed_widths)
    # the rows the experts' matmuls were given: the tick's width x top_k a
    # layer (the grouped form: nn/moe.py), whatever the tick holds; with the
    # assignments above, the share of them that was real work
    mixed = [f for n, _, _, f in capture.spans if n == "serve.mixed"]
    assert all(f["moe_rows"] == f["width"] * TOP_K * LAYERS for f in mixed)
    assert capture.counters["serve_moe_rows_total{path=grouped}"] == sum(
        f["moe_rows"] for f in mixed) >= moved
    assert "serve_moe_rows_total{path=dense}" not in capture.counters


def test_a_dense_model_pays_nothing(tmp_path):
    engine = make_engine(mlp_type="swiglu", mlp_factor=2.0)
    assert engine.num_experts == 0
    obs.start_capture(tmp_path / "trace")
    try:
        serve_three(engine)
    finally:
        capture = obs.stop_capture()
    assert "serve_moe_assignments_total" not in capture.counters
    emits = [f for n, _, _, f in capture.spans if n == "serve.emit"]
    assert emits and not any("load_max" in f for f in emits)
    assert not any(k.startswith("serve_moe_rows_total") for k in capture.counters)
    assert not any("moe_rows" in f for n, _, _, f in capture.spans
                   if n == "serve.mixed")


def test_one_program_serves_a_routed_model_whatever_the_tick_holds():
    """A prompt shorter than a chunk, one many chunks long and a
    preemption: the routed engine compiles its mixed program once and
    holds no other jitted callable."""
    engine = make_engine({"num_blocks": 7}, **ROUTED)
    cycle = [(i % 5) + 1 for i in range(40)]
    seqs = [engine.submit(p, 6) for p in (cycle, cycle[3:], [9, 8, 7])]
    engine.run_until_done()
    assert all(len(s.generated) == 6 for s in seqs)
    assert engine.scheduler.preemption_count > 0
    width, = engine.config.mixed_widths  # a toy engine has one bucket
    assert jitted_programs(engine) == {"_mixed_fns": 1}
    assert list(engine._mixed_fns) == [width]
    assert engine._mixed_fns[width]._cache_size() == 1


def test_packed_ticks_drop_no_assignment_and_count_real_positions_only():
    """A routed engine with both token widths (8 slots x 32: 128 under
    256), through ticks of either: every request's tokens are those of the
    plain cached forward pass (which has room for everything, so nothing
    was dropped by packing rows into shared groups), and the load counts
    each real position's top_k choices once a layer — not the width's
    empty tail."""
    engine = make_engine({"num_slots": 8, "prefill_chunk": 32,
                          "num_blocks": 8 * 8 + 1,
                          "enable_prefix_cache": False}, **ROUTED)
    assert engine.config.mixed_widths == (128, 256)
    rng = np.random.default_rng(2)
    lengths = (70, 40, 33, 90, 64, 5, 1)  # 5+ prompts stream at once: 256
    prompts = [[int(t) for t in rng.integers(1, 90, n)] for n in lengths]
    before = obs.get_registry().snapshot()["counters"].get(
        "serve_moe_assignments_total", 0)
    seqs = [engine.submit(p, 4) for p in prompts]
    engine.run_until_done()
    assert set(engine.mixed_ticks) == {128, 256}
    moved = obs.get_registry().snapshot()["counters"][
        "serve_moe_assignments_total"] - before
    assert moved == sum(n + 4 - 1 for n in lengths) * TOP_K * LAYERS
    # (ONE left-padded batch: a prompt a call would compile the pass a length)
    want = engine.inf.generate(prompts, max_tokens=4, use_cache=True)
    assert [seq.generated for seq in seqs] == [w.completion_ids for w in want]


def test_projection_scope_needs_the_norm_switched_on():
    with pytest.raises(ValueError, match="key_query_norm_scope"):
        make_engine(**{**ROUTED, "key_query_norm": False})
