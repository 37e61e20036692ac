"""A stack of LFM2-MoE blocks (``layer_pattern``: a gated short convolution or
GQA attention with per-head QK norm, then a dense or a routed SwiGLU FFN; a
tied head) through ``ServeEngine``: conv-tail lines beside the paged KV pools,
donated and aliased like them; the engine's logits against the plain
reference's full forward; a slot reused, a sequence preempted and recomputed;
what is refused, by name; the router's selection bias and ``+ 1e-6``; the
spans' new fields, the counter and the stats."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import cells
from scaling_tpu import obs
from scaling_tpu.models.transformer import TransformerConfig
from scaling_tpu.models.transformer.inference import TransformerInferenceModule
from scaling_tpu.models.transformer.model import init_model
from scaling_tpu.nn.attention import PagedKVCacheView
from scaling_tpu.nn.moe import ParallelMoEMLP
from scaling_tpu.nn.short_conv import ConvTailView
from scaling_tpu.serve.engine import EngineConfig, ServeEngine

from . import reference_walk

VOCAB = 96
OPS = ["conv", "conv", "attention", "conv"]
FFNS = ["mlp", "moe", "moe", "moe"]
PATTERN = [kind for block in zip(OPS, FFNS) for kind in block]
CONV_LAYERS = OPS.count("conv")
TOPOLOGY = {"model_parallel_size": 1, "pipe_parallel_size": 1,
            "data_parallel_size": 1, "micro_batch_size": 1,
            "gradient_accumulation_steps": 1}
ARCH = {"vocab_size": VOCAB, "hidden_size": 48, "num_layers": len(PATTERN),
        "layer_pattern": PATTERN,
        "num_attention_heads": 4, "attention_num_kv_heads": 2,
        "attention_head_dim": 16, "attention_qkv_in_one": False,
        "attention_bias": False, "key_query_norm": True,
        "mlp_type": "swiglu", "mlp_factor": 2.5, "mlp_bias": False,
        "moe_num_experts": 8, "moe_top_k": 3, "moe_expert_width": 40,
        "moe_glu": True, "moe_router": "sigmoid_bias", "moe_norm_topk_prob": True,
        "moe_norm_topk_eps": 1e-6, "activation_function": "silu",
        "norm_type": "rms", "conv_kernel": 3,
        "relative_position_embedding_type": "rotary",
        "rotary_embedding_base": 1000000, "sequence_length": 128,
        "precision": "float32", "weight_tying": True}


def lfm2_config(topology=None, **arch):
    return TransformerConfig.from_dict({
        "topology": {**TOPOLOGY, **(topology or {})},
        "transformer_architecture": {**ARCH, **arch},
        "data": {}, "logger": {"log_dir": None}})


@pytest.fixture(scope="module")
def lfm2():
    config = lfm2_config()
    module = init_model(config, None)
    params = module.init_params(jax.random.PRNGKey(3))
    # away from the init: norms off one, a selection bias that says something
    leaves, treedef = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(4), len(leaves))
    params = jax.tree.unflatten(treedef, [
        x + 0.3 * jax.random.normal(k, x.shape) for x, k in zip(leaves, keys)])
    return TransformerInferenceModule(config, module, params)


@pytest.fixture(scope="module")
def reference():
    ref = cells.load_module(cells.ROOT, "reference", "conv_moe_decoder",
                            cells.REFERENCE_CONTRACT)
    view = cells.load_module(cells.ROOT, "views", "conv_moe_decoder",
                             cells.VIEW_CONTRACT)
    return ref, view


def prompts(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, VOCAB, size=n).tolist() for n in lengths]


def engine_of(inf, **config):
    return ServeEngine(inf, EngineConfig(**{
        "num_slots": 4, "block_size": 4, "num_blocks": 64,
        "max_blocks_per_seq": 12, "token_budget": 64, "prefill_chunk": 8,
        "enable_prefix_cache": False, **config}))


def served(engine, requests, max_new):
    for p in requests:
        engine.submit(p, max_new_tokens=max_new)
    return {s.request.req_id: s.generated for s in engine.run_until_done()}


@pytest.fixture(scope="module")
def undisturbed(lfm2, reference):
    """Each prompt alone, greedy, by the plain REFERENCE's full forward (no
    cache, no state pool, no batching, nothing of the program): the tokens,
    and how far the runner-up lies below each."""
    ref, view = reference
    weights = view.reference_weights(lfm2.params, ARCH)
    spec = view.reference_spec(ARCH)
    requests = prompts((9, 21, 14, 30, 17))
    return requests, reference_walk.greedy_by_reference(
        lambda tokens: ref.forward(weights, jnp.asarray(tokens), spec), requests, 10,
        least_margin=1e-3)


def test_the_state_pool_is_one_tail_per_slot_and_conv_layer(lfm2):
    engine = engine_of(lfm2)
    pools, stats = engine.pools, engine.stats_snapshot()
    # the view each consuming layer's mixer declares, in layer order
    assert pools.kinds == [ConvTailView] * 2 + [PagedKVCacheView, ConvTailView]
    assert pools.kv_lines == stats["kv_lines"] == 1        # KV for the attention layer only
    assert pools.state_lines == stats["state_lines"] == CONV_LAYERS
    assert engine.line_layers == {"conv": CONV_LAYERS} and engine.ssm_lines == 0
    # ONE sequence, in state order: the one list of the view's one field
    assert ConvTailView.LINES == ("tail",)
    (tails,) = pools.lines
    assert [(a.shape, a.dtype) for a in tails] == [((4, 2, 48), jnp.float32)] * CONV_LAYERS
    assert stats["state_pool_bytes"] == pools.state_bytes() == CONV_LAYERS * 4 * 2 * 48 * 4
    assert pools.pool_k[0].shape == (64, 4, 2, 16)
    # ONE donated structure: the four of the pools, then the tails' list
    state = engine._pool_state()
    assert len(state) == 5 and state[4] is tails


def test_the_program_is_the_reference_at_every_position(lfm2, reference):
    """The uncached forward: both operators, both FFNs, per-head QK norm
    before rotary, the tied head."""
    ref, view = reference
    tokens = prompts((40,), seed=5)[0]
    want = ref.forward(view.reference_weights(lfm2.params, ARCH), jnp.asarray(tokens),
                       view.reference_spec(ARCH))
    got = lfm2.logits(jnp.asarray([tokens]))[0]
    assert want.shape == (40, VOCAB)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-4)
    # the head is the embedding table: no leaf of its own
    assert lfm2.params[f"layer_{len(PATTERN) + 2}"] == {"embedding": {}}


def test_the_engine_serves_what_the_references_full_forward_gives(lfm2, undisturbed):
    """Prefill in chunks of 8 whose edges fall mid-prompt (9, 21, 14, 30, 17),
    four rows at once and a fifth in a reused slot, then decode: ticks mix
    chunk rows and decode rows."""
    requests, want = undisturbed
    engine = engine_of(lfm2)
    got = served(engine, requests, 10)
    assert [got[i] for i in range(len(requests))] == want
    assert len({tuple(w) for w in want}) > 1  # the weights say something
    assert len({t for w in want for t in w}) > 5   # and not the input back


def test_a_reused_slot_does_not_inherit_its_old_occupants_tail(lfm2, undisturbed):
    """One slot: five sequences follow one another through the same lines of
    the state pool, no reset by the host in between."""
    requests, want = undisturbed
    engine = engine_of(lfm2, num_slots=1)
    got = served(engine, requests, 10)
    assert [got[i] for i in range(len(requests))] == want
    assert float(jnp.abs(engine.pools.lines[0][0]).max()) > 0


def test_a_preempted_and_resumed_sequence_reproduces_its_tokens(lfm2, undisturbed):
    """A pool too small for the rows forces recompute-style preemption: the
    resumed sequence re-enters at context 0, so the program starts its tails
    from zeros and the recompute regenerates token for token."""
    requests, want = undisturbed
    engine = engine_of(lfm2, num_blocks=17)
    got = served(engine, requests, 10)
    assert engine.scheduler.preemption_count > 0
    assert any(s.preemptions for s in engine.finished)
    assert [got[i] for i in range(len(requests))] == want


def test_a_tail_that_is_never_written_serves_other_tokens(lfm2, undisturbed, monkeypatch):
    """The comparison sees the mechanism: with the lines dropped (every tick
    starts from the lines as they were) the engine's tokens differ."""
    from scaling_tpu.nn import short_conv

    real = short_conv.GatedShortConv._serve
    monkeypatch.setattr(
        short_conv.GatedShortConv, "_serve",
        lambda self, weight, u, view: (real(self, weight, u, view)[0], view))
    requests, want = undisturbed
    got = served(engine_of(lfm2), requests, 10)
    assert [got[i] for i in range(len(requests))] != want


@pytest.mark.parametrize("config,message", [
    ({"enable_prefix_cache": True},
     "keep a line a slot \\({'conv': 3}\\): a prefix hit .* lines never saw"),
])
def test_what_would_skip_the_tail_is_refused_by_name(lfm2, config, message):
    with pytest.raises(ValueError, match=message):
        engine_of(lfm2, **config)
    # the default EngineConfig has the prefix cache on: refused too, not
    # silently turned off
    with pytest.raises(ValueError, match="enable_prefix_cache"):
        ServeEngine(lfm2, EngineConfig())


@pytest.mark.parametrize("topology,arch,message", [
    ({"pipe_parallel_size": 2}, {}, "layer_pattern with pipe_parallel_size 2"),
    ({"model_parallel_size": 2}, {}, "layer_pattern with model_parallel_size 2"),
    ({}, {"key_query_norm_scope": "projection"}, "key_query_norm_scope 'projection'"),
    ({}, {"mlp_type": "moe"}, "'mlp' layers and mlp_type 'moe'"),
    ({}, {"sandwich_norm": True}, "layer_pattern with sandwich_norm"),
    ({}, {"layer_pattern": ["conv"]}, "names 1 layers, num_layers is 8"),
    ({}, {"conv_kernel": 1}, "conv_kernel"),
])
def test_a_layout_the_stack_does_not_build_is_refused_by_name(topology, arch, message):
    with pytest.raises(ValueError, match=message):
        lfm2_config(topology, **arch)


def test_training_and_cached_generate_are_refused_by_name(lfm2):
    from scaling_tpu.nn.base_layer import ForwardContext
    from scaling_tpu.serve.kvcache import build_layer_views

    with pytest.raises(NotImplementedError, match="layer_pattern stack is served"):
        lfm2.module.forward(lfm2.params, {}, ForwardContext())
    with pytest.raises(ValueError, match="cached generate\\(\\) keeps dense KV"):
        lfm2.generate([1, 2, 3], max_tokens=2)
    # and a state of the wrong kind, handed to a layer, by name
    engine = engine_of(lfm2)
    views = build_layer_views(
        engine._pool_state(), jnp.zeros((4, 12), jnp.int32), jnp.zeros((4,), jnp.int32),
        jnp.ones((4,), jnp.int32), kinds=[PagedKVCacheView] + [ConvTailView] * 3)
    batch = lfm2._make_batch(jnp.ones((4, 8), jnp.int32), jnp.zeros((4, 8), jnp.int32))
    with pytest.raises(ValueError, match="consumes a ConvTailView and was handed a Paged"):
        lfm2._run_layers(lfm2.params, batch, views, None, paged_kernel="xla")
    ctx = lfm2._make_ctx()
    embedded = lfm2.module.layers[0](lfm2.params["layer_0"], batch, ctx)
    with pytest.raises(ValueError, match="a conv layer takes a ConvTailView"):
        lfm2.module.layers[1](lfm2.params["layer_1"], embedded, ctx,
                              kv_cache=(jnp.zeros((4, 8, 2, 16)),) * 2, cache_offset=0)


def router(**kw):
    layer = ParallelMoEMLP(io_features=16, intermediate_feature_factor=1.0,
                           num_experts=6, top_k=2, router="sigmoid_bias", **kw)
    return layer, layer.init(jax.random.PRNGKey(0))


def test_the_selection_bias_moves_the_choice_and_not_the_gates():
    layer, params = router(norm_topk_eps=1e-6)
    params["router"]["weight"] = jax.random.normal(jax.random.PRNGKey(1), (16, 6))
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 5, 16))
    scores, gates, chosen = layer._route(params, x)
    # a bias that lifts the expert with the LOWEST score over all the others
    worst = jnp.argmin(scores, axis=-1)                       # (1, 5)
    lifted = dict(params, router=dict(params["router"], bias=jnp.zeros((6,)).at[
        worst[0, 0]].set(10.0)))
    scores2, gates2, chosen2 = layer._route(lifted, x)
    np.testing.assert_array_equal(np.asarray(scores2), np.asarray(scores))
    assert int(worst[0, 0]) in np.asarray(chosen2[0, 0])       # the choice moved
    assert int(worst[0, 0]) not in np.asarray(chosen[0, 0])
    # the gates are the chosen SCORES over their sum + 1e-6, the bias nowhere
    s = np.take_along_axis(np.asarray(scores2, np.float64), np.asarray(chosen2), -1)
    np.testing.assert_allclose(np.asarray(gates2), s / (s.sum(-1, keepdims=True) + 1e-6),
                               rtol=1e-6)


def test_the_renormalisations_epsilon_is_the_configurations():
    """Scores of ~1e-6 (a router far in the negative): ``+ 1e-6`` halves the
    gates where Nemotron's ``+ 1e-20`` leaves them summing to one."""
    x = jnp.ones((1, 1, 16))
    sums = {}
    for eps in (1e-6, 1e-20):
        layer, params = router(norm_topk_eps=eps)
        # every logit is log(5e-7): s = 5e-7 each, the two chosen sum to 1e-6
        params["router"]["weight"] = jnp.full((16, 6), float(np.log(5e-7)) / 16)
        sums[eps] = float(layer._route(params, x)[1].sum())
    assert sums[1e-6] == pytest.approx(0.5, rel=1e-3)
    assert sums[1e-20] == pytest.approx(1.0, rel=1e-6)
    assert lfm2_config().transformer_architecture.moe_norm_topk_eps == 1e-6
    assert TransformerConfig.from_dict({
        "topology": TOPOLOGY, "transformer_architecture": {
            k: v for k, v in ARCH.items() if k != "moe_norm_topk_eps"},
        "data": {}, "logger": {"log_dir": None},
    }).transformer_architecture.moe_norm_topk_eps == 1e-20


def test_spans_counters_and_load_of_a_model_that_holds_every_expert(lfm2, tmp_path):
    engine = engine_of(lfm2)
    assert engine.num_experts == 8 and not engine.moe_partial
    requests = prompts((9, 12), seed=8)
    obs.start_capture(str(tmp_path))
    try:
        served(engine, requests, 6)
    finally:
        capture = obs.stop_capture()
    mixed = [f for n, _, _, f in capture.spans if n == "serve.mixed"]
    emits = [f for n, _, _, f in capture.spans if n == "serve.emit"]
    assert mixed and len(emits) == len(mixed)
    assert all(f["conv_lines"] == CONV_LAYERS for f in mixed)
    assert [f["conv_rows"] for f in mixed] == [f["decodes"] + f["chunks"] for f in mixed]
    assert capture.counters["serve_conv_state_updates_total"] == CONV_LAYERS * sum(
        f["conv_rows"] for f in mixed)
    assert not any("ssm_rows" in f for f in mixed)
    assert "serve_ssm_state_updates_total" not in capture.counters
    # every real position's 3 assignments in each of the 3 routed layers
    routed = FFNS.count("moe")
    assert capture.counters["serve_moe_assignments_total"] == 3 * routed * sum(
        f["tokens"] for f in mixed)
    assert "serve_moe_absent_assignments_total" not in capture.counters
    for f in emits:   # all held: the load fields as OLMoE's, no absent_assign
        assert "absent_assign" not in f
        assert 0 <= f["experts_idle"] <= 8 and f["load_max"] >= f["load_mean"] >= 0
    assert all(f["moe_rows"] == f["width"] * 3 * routed for f in mixed)
    assert capture.counters["serve_moe_rows_total{path=grouped}"] == sum(
        f["moe_rows"] for f in mixed)


def test_the_operator_and_the_dense_ffn_lie_in_scopes_of_their_own(lfm2):
    """``conv``, ``mlp`` and ``moe`` name the instructions compiled from
    inside each: what the benchmark's readers look up in a trace's HLO."""
    tokens = jnp.asarray(prompts((8,))[0])[None]
    batch = lfm2._make_batch(tokens, jnp.arange(8)[None])
    hlo = jax.jit(lambda p: lfm2._run_layers(p, batch, None, None)[0]).lower(
        lfm2.params).as_text(debug_info=True)
    for scope in ("conv", "mlp", "moe"):
        assert re.search(rf'/{scope}/', hlo), scope
