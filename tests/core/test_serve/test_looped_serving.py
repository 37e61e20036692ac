"""A looped model (``loop_steps > 1``: the trunk run several times over the
same weights, sandwich norms, an exit gate) through ``ServeEngine``: one KV
cache line per (step, layer) behind the scheduler's logical blocks, so that a
preemption, a prefix hit and a copy-on-write fork each cover every step; one
rolled program whatever the tick holds; what is refused, by name; the spans'
new fields and the counter."""

import jax
import numpy as np
import pytest

from scaling_tpu import obs
from scaling_tpu.models.transformer import TransformerConfig
from scaling_tpu.models.transformer.inference import TransformerInferenceModule
from scaling_tpu.models.transformer.model import init_model
from scaling_tpu.serve.engine import EngineConfig, ServeEngine

STEPS, LAYERS, VOCAB = 4, 2, 96
TOPOLOGY = {"model_parallel_size": 1, "pipe_parallel_size": 1,
            "data_parallel_size": 1, "micro_batch_size": 1,
            "gradient_accumulation_steps": 1}
ARCH = {"vocab_size": VOCAB, "hidden_size": 64, "num_layers": LAYERS,
        "num_attention_heads": 4, "attention_num_kv_heads": 4,
        "attention_qkv_in_one": False, "attention_bias": False,
        "mlp_type": "swiglu", "mlp_factor": 2.0, "mlp_bias": False,
        "norm_type": "rms", "sequence_length": 128, "precision": "float32",
        "weight_tying": False, "loop_steps": STEPS, "sandwich_norm": True,
        "loop_exit_gate": True}


def looped_config(topology=None, **arch):
    return TransformerConfig.from_dict({
        "topology": {**TOPOLOGY, **(topology or {})},
        "transformer_architecture": {**ARCH, **arch},
        "data": {}, "logger": {"log_dir": None}})


@pytest.fixture(scope="module")
def looped():
    config = looped_config()
    module = init_model(config, None)
    params = module.init_params(jax.random.PRNGKey(3))
    # seeded weights away from their initial ones (norms of one, a gate
    # with no bias), so that greedy tokens vary with what the cache holds
    leaves, treedef = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(4), len(leaves))
    params = jax.tree.unflatten(treedef, [
        x + 0.3 * jax.random.normal(k, x.shape) for x, k in zip(leaves, keys)])
    return TransformerInferenceModule(config, module, params)


def prompts(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, VOCAB, size=n).tolist() for n in lengths]


def engine_of(inf, **config):
    return ServeEngine(inf, EngineConfig(**{
        "num_slots": 4, "block_size": 4, "num_blocks": 64,
        "max_blocks_per_seq": 12, "token_budget": 64, "prefill_chunk": 8,
        **config}))


def served(engine, requests, max_new):
    for p in requests:
        engine.submit(p, max_new_tokens=max_new)
    return {s.request.req_id: s.generated for s in engine.run_until_done()}


@pytest.fixture(scope="module")
def undisturbed(looped):
    """What ``generate`` (dense caches, one a line, steps unrolled) gives
    for each prompt, as ONE left-padded batch (a prompt a call would compile
    the passes a length)."""
    requests = prompts((9, 21, 14, 30, 17))
    return requests, [out.completion_ids
                      for out in looped.generate(requests, max_tokens=10)]


def test_a_tokens_cache_is_one_line_per_step_and_layer(looped):
    engine = engine_of(looped)
    pools = engine.pools
    assert pools.kv_lines == STEPS * LAYERS == engine.stats_snapshot()["kv_lines"]
    assert pools.num_layers == LAYERS and pools.num_blocks == 64
    # a layer's pool holds every step's blocks; the scheduler counts 64
    assert pools.pool_k[0].shape == (STEPS * 64, 4, 4, 16)
    assert engine.scheduler.allocator.num_blocks == 64
    assert engine.stats_snapshot()["kv_pool_bytes"] == pools.device_bytes() == (
        2 * LAYERS * STEPS * 64 * 4 * 4 * 16 * 4)
    assert list(pools.line_blocks(5)) == [5, 69, 133, 197]


def test_the_engine_serves_what_generate_gives(looped, undisturbed):
    requests, want = undisturbed
    got = served(engine_of(looped), requests, 10)
    assert [got[i] for i in range(len(requests))] == want
    assert len({tuple(w) for w in want}) > 1  # the weights say something


def test_a_preempted_and_resumed_sequence_reproduces_its_tokens(looped, undisturbed):
    """A pool too small for the rows forces recompute-style preemption: the
    freed logical blocks are free in every line, and the resumed sequence
    refills every step's."""
    requests, want = undisturbed
    engine = engine_of(looped, num_blocks=17, enable_prefix_cache=False)
    got = served(engine, requests, 10)
    assert engine.scheduler.preemption_count > 0
    assert any(s.preemptions for s in engine.finished)
    assert [got[i] for i in range(len(requests))] == want


def test_a_prefix_hit_covers_every_step(looped):
    """A follower maps the leader's cached blocks and prefills its tail
    only: the mapped blocks hold the prefix at EVERY step, or steps 1-3 would
    attend to nothing."""
    prefix = prompts((16,), seed=3)[0]  # 4 full blocks
    family = [prefix + tail for tail in prompts((3, 2, 5), seed=4)]
    want = [looped.generate(p, max_tokens=8).completion_ids for p in family]
    engine = engine_of(looped)
    first = served(engine, family[:1], 8)
    rest = served(engine, family[1:], 8)
    assert engine.scheduler.prefix_hit_tokens == 2 * len(prefix)
    assert [first[0], rest[1], rest[2]] == want


def test_a_copy_on_write_fork_copies_the_block_in_every_line(looped):
    """A block shared when its owner is about to write into it is forked:
    the copy must hold the block's tokens at every step."""
    prompt = prompts((10,), seed=6)[0]
    want = looped.generate(prompt, max_tokens=8).completion_ids
    engine = engine_of(looped, enable_prefix_cache=False)
    seq = engine.submit(prompt, max_new_tokens=8)
    while len(seq.generated) < 3:
        engine.tick()
    # a fourth token is in flight, its row's write counted in num_cached;
    # someone else now references the block the next token is written into
    assert seq.in_flight == 1 and seq.num_cached == len(prompt) + 3
    filled = seq.num_cached % 4
    assert filled, "the next write must land inside a block"
    target = seq.blocks[seq.num_cached // 4]
    engine.scheduler.allocator.incref(target)
    (src, dst), = engine.tick().cow_pairs
    assert src == target and seq.blocks[(seq.num_cached - 1) // 4] == dst
    # what the block held before this tick's write, at every step
    for pool in engine.pools.pool_k + engine.pools.pool_v:
        for u in range(STEPS):
            copied = np.asarray(pool[dst + 64 * u])[:filled]
            np.testing.assert_array_equal(
                copied, np.asarray(pool[src + 64 * u])[:filled])
            assert np.abs(copied).max() > 0
    engine.run_until_done()
    engine.scheduler.allocator.free([target])
    assert seq.generated == want


def test_one_rolled_program_whatever_the_tick_holds(looped, undisturbed):
    """Prompts shorter and longer than a chunk, decode rows, a preemption:
    ONE jitted program a token width, compiled once, and its lowered text
    holds the matmuls of ONE step (the loop is rolled)."""
    requests, _ = undisturbed
    engine = engine_of(looped, num_blocks=17)
    served(engine, requests, 10)
    assert engine.scheduler.preemption_count > 0
    assert engine.prefill_program_count == len(engine.config.mixed_widths) == 1
    (width, fn), = engine._mixed_fns.items()
    assert fn._cache_size() == 1
    packed, _ = engine._layout.host(width)
    text = fn.lower(looped.params, engine._pool_state(), packed,
                    engine._base_key, engine._prev).as_text()
    plain = plain_inference()
    plain_engine = engine_of(plain, num_blocks=17)
    plain_text = plain_engine._build_mixed_fn(width).lower(
        plain.params, plain_engine._pool_state(), packed,
        plain_engine._base_key, plain_engine._prev).as_text()
    assert "stablehlo.while" in text
    assert text.count("stablehlo.dot_general") == plain_text.count(
        "stablehlo.dot_general") > 0


def plain_inference():
    """The same block walked once, without a gate."""
    config = looped_config(loop_steps=1, loop_exit_gate=False)
    module = init_model(config, None)
    return TransformerInferenceModule(
        config, module, module.init_params(jax.random.PRNGKey(3)))


# ---- what is refused, by name

def test_training_a_looped_model_is_refused_by_name():
    from scaling_tpu.models.transformer import train
    from scaling_tpu.models.transformer.model import init_optimizer, loss_function

    config = looped_config()
    with pytest.raises(NotImplementedError, match="looped model.*served, not trained"):
        train.main(config)
    module = init_model(config, None)
    optimizer = init_optimizer(config, module, None)
    with pytest.raises(NotImplementedError, match="looped model.*served, not trained"):
        module.build_train_step(optimizer, loss_function)
    with pytest.raises(NotImplementedError, match="run the trunk once"):
        module.forward({}, {}, None)


def test_a_pipelined_looped_trunk_is_refused_by_name():
    from scaling_tpu.topology import Topology

    config = looped_config(topology={"pipe_parallel_size": 2})
    with pytest.raises(ValueError, match="pipe_parallel_size 2 with loop_steps 4"):
        init_model(config, Topology(config.topology))


@pytest.mark.parametrize("fields,message", [
    ({"loop_exit_threshold": 0.9}, "loop_exit_threshold 0.9: only 1 is served"),
    ({"loop_steps": 1}, "loop_exit_gate reads the exit distribution"),
    ({"mlp_type": "moe", "mlp_factor": 0.5, "activation_function": "silu"},
     "loop_steps > 1 with mlp_type 'moe'"),
], ids=["threshold-under-1", "gate-without-a-loop", "routed-and-looped"])
def test_a_configuration_the_loop_cannot_run_is_refused_by_name(fields, message):
    with pytest.raises(ValueError, match=message):
        looped_config(**fields)


def test_a_plain_model_has_no_exit_distribution(looped):
    plain = plain_inference()
    with pytest.raises(ValueError, match="loop_steps 1"):
        plain.exit_probabilities([1, 2, 3])
    nogate = looped_config(loop_exit_gate=False)
    module = init_model(nogate, None)
    inf = TransformerInferenceModule(nogate, module,
                                     module.init_params(jax.random.PRNGKey(0)))
    with pytest.raises(ValueError, match="set loop_exit_gate"):
        inf.exit_probabilities([1, 2, 3])
    assert inf.logits([1, 2, 3]).shape == (1, 3, VOCAB)


# ---- spans, counter, parameter groups

def test_spans_carry_the_loop_and_the_exit_distribution(looped, tmp_path):
    engine = engine_of(looped)
    requests = prompts((9, 12), seed=8)
    obs.start_capture(str(tmp_path))
    try:
        served(engine, requests, 6)
    finally:
        capture = obs.stop_capture()
    mixed = [f for n, _, _, f in capture.spans if n == "serve.mixed"]
    emits = [f for n, _, _, f in capture.spans if n == "serve.emit"]
    assert mixed and all(f["loop_steps"] == STEPS for f in mixed)
    assert capture.counters["serve_loop_layer_passes_total"] == (
        len(mixed) * STEPS * LAYERS)
    assert len(emits) == len(mixed)
    for f in emits:
        assert len(f["exit_p"]) == STEPS
        assert sum(f["exit_p"]) == pytest.approx(1.0, abs=1e-4)
        assert f["exit_expected_steps"] == pytest.approx(
            sum((u + 1) * p for u, p in enumerate(f["exit_p"])), abs=1e-4)
        assert 1.0 <= f["exit_expected_steps"] <= STEPS
    # the tick's mean is the model's own distribution at the sampled
    # positions: a decode-only tick of one row reads that row's position
    tick = next(i for i, f in enumerate(mixed) if f["chunks"] == 0)
    assert emits[tick]["exit_p"] != emits[0]["exit_p"]


def test_a_plain_models_spans_are_what_they_were(tmp_path):
    engine = engine_of(plain_inference())
    obs.start_capture(str(tmp_path))
    try:
        served(engine, prompts((9,), seed=8), 4)
    finally:
        capture = obs.stop_capture()
    fields = [f for n, _, _, f in capture.spans if n in ("serve.mixed", "serve.emit")]
    assert fields and not any(
        key in f for f in fields for key in ("loop_steps", "exit_p", "exit_expected_steps"))
    assert "serve_loop_layer_passes_total" not in capture.counters
    assert engine.stats_snapshot()["kv_lines"] == LAYERS


def test_the_new_norms_and_the_gates_bias_take_no_weight_decay():
    from scaling_tpu.models.transformer.model import get_parameter_groups

    config = looped_config()
    groups = {g.name: g.keys for g in get_parameter_groups(
        config, init_model(config, None))}
    no_decay = groups["no_weight_decay_params"]
    for name in ("post_attention_output_layernorm", "post_mlp_output_layernorm"):
        assert sum(name in key for key in no_decay) == LAYERS
    assert any(key.endswith("linear.bias") for key in no_decay)
    assert any(key.endswith("linear.weight") and "layer_4" in key
               for key in groups["weight_decay_params"])
