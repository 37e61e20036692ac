"""A stack of single-mixer layers (``layer_pattern``: Mamba-2 / routed experts
with a share held / attention) through ``ServeEngine``: a recurrent-state pool
beside the paged KV pools, donated and aliased like them; a slot reused, a
sequence preempted and recomputed; what is refused, by name; the spans' new
fields, the counters and the stats."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from scaling_tpu import obs
from scaling_tpu.models.transformer import TransformerConfig
from scaling_tpu.models.transformer.inference import TransformerInferenceModule
from scaling_tpu.models.transformer.model import init_model
from scaling_tpu.nn.attention import PagedKVCacheView
from scaling_tpu.nn.mamba import RecurrentStateView
from scaling_tpu.serve.engine import EngineConfig, ServeEngine

from . import reference_walk

VOCAB = 96
KINDS = {"M": "mamba", "E": "moe", "*": "attention"}
PATTERN = "MEM*EM"
TOPOLOGY = {"model_parallel_size": 1, "pipe_parallel_size": 1,
            "data_parallel_size": 1, "micro_batch_size": 1,
            "gradient_accumulation_steps": 1}
ARCH = {"vocab_size": VOCAB, "hidden_size": 48, "num_layers": len(PATTERN),
        "layer_pattern": [KINDS[c] for c in PATTERN],
        "num_attention_heads": 4, "attention_num_kv_heads": 2,
        "attention_head_dim": 16, "attention_qkv_in_one": False,
        "attention_bias": False, "mlp_type": "moe", "mlp_bias": False,
        "moe_num_experts": 8, "moe_top_k": 3, "moe_expert_width": 40,
        "moe_glu": False, "moe_router": "sigmoid_bias",
        "moe_routed_scaling_factor": 2.5, "moe_shared_expert_width": 56,
        "moe_experts_first": 0, "moe_experts_held": 4,
        "activation_function": "relu2", "norm_type": "rms",
        "mamba_num_heads": 4, "mamba_head_dim": 8, "ssm_state_size": 16,
        "n_groups": 2, "conv_kernel": 4,
        "relative_position_embedding_type": "none", "sequence_length": 128,
        "precision": "float32", "weight_tying": False}
M_LAYERS = PATTERN.count("M")


def hybrid_config(topology=None, **arch):
    return TransformerConfig.from_dict({
        "topology": {**TOPOLOGY, **(topology or {})},
        "transformer_architecture": {**ARCH, **arch},
        "data": {}, "logger": {"log_dir": None}})


@pytest.fixture(scope="module")
def hybrid():
    config = hybrid_config()
    module = init_model(config, None)
    params = module.init_params(jax.random.PRNGKey(3))
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
        "enable_prefix_cache": False, **config}))


def served(engine, requests, max_new):
    for p in requests:
        engine.submit(p, max_new_tokens=max_new)
    return {s.request.req_id: s.generated for s in engine.run_until_done()}


@pytest.fixture(scope="module")
def undisturbed(hybrid):
    """Each prompt alone through the UNCACHED forward, greedy: what no cache,
    no state pool and no batching can have touched. Beside each token, how far
    the runner-up lies below it."""
    requests = prompts((9, 21, 14, 30, 17))
    return requests, reference_walk.greedy_by_reference(
        lambda tokens: hybrid.logits(jnp.asarray(tokens))[0], requests, 10)


def test_the_state_pool_is_one_line_per_slot_and_mamba_layer(hybrid):
    engine = engine_of(hybrid)
    pools, stats = engine.pools, engine.stats_snapshot()
    # the view each consuming layer's mixer declares, in layer order
    assert pools.kinds == [RecurrentStateView] * 2 + [PagedKVCacheView, RecurrentStateView]
    assert pools.kv_lines == stats["kv_lines"] == 1        # KV for the * layer only
    assert pools.state_lines == stats["state_lines"] == M_LAYERS == engine.ssm_lines
    assert engine.line_layers == {"ssm": M_LAYERS}
    # ONE sequence, in state order: a list a field of the view's LINES
    ssm, conv = pools.lines
    assert RecurrentStateView.LINES == ("ssm", "conv")
    assert [a.shape for a in ssm] == [(4, 4, 8, 16)] * M_LAYERS
    assert [a.shape for a in conv] == [(4, 4 * 8 + 2 * 2 * 16, 3)] * M_LAYERS
    assert all(a.dtype == jnp.float32 for a in ssm + conv)
    assert stats["state_pool_bytes"] == pools.state_bytes() == M_LAYERS * 4 * (
        4 * 8 * 16 * 4 + 96 * 3 * 4)
    assert pools.pool_k[0].shape == (64, 4, 2, 16)
    # ONE donated structure: the four of the pools, then the lines' lists
    state = engine._pool_state()
    assert len(state) == 6 and state[4] is ssm and state[5] is conv
    # a model without recurrent layers keeps the four-entry state
    from scaling_tpu.serve.bench import build_toy_inference

    plain = engine_of(build_toy_inference(hidden=32, layers=2, vocab=64, heads=4))
    assert len(plain._pool_state()) == 4 and plain.pools.kinds is None
    assert plain.stats_snapshot()["state_lines"] == 0
    assert plain.stats_snapshot()["state_pool_bytes"] == 0


def test_the_engine_serves_what_the_uncached_forward_gives(hybrid, undisturbed):
    """Prefill in chunks of 8 whose edges fall mid-prompt (9, 21, 14, 30, 17),
    four rows at once and a fifth in a reused slot, then decode."""
    requests, want = undisturbed
    got = served(engine_of(hybrid), requests, 10)
    assert [got[i] for i in range(len(requests))] == want
    assert len({tuple(w) for w in want}) > 1  # the weights say something


def test_a_reused_slot_does_not_inherit_its_old_occupants_state(hybrid, undisturbed):
    """One slot: five sequences follow one another through the same lines of
    the state pool, no reset by the host in between."""
    requests, want = undisturbed
    engine = engine_of(hybrid, num_slots=1)
    got = served(engine, requests, 10)
    assert [got[i] for i in range(len(requests))] == want
    assert float(jnp.abs(engine.pools.lines[0][0]).max()) > 0


def test_a_preempted_and_resumed_sequence_reproduces_its_tokens(hybrid, undisturbed):
    """A pool too small for the rows forces recompute-style preemption: the
    resumed sequence re-enters at context 0, so the program zeroes its state
    and the recompute regenerates token for token."""
    requests, want = undisturbed
    engine = engine_of(hybrid, num_blocks=17)
    got = served(engine, requests, 10)
    assert engine.scheduler.preemption_count > 0
    assert any(s.preemptions for s in engine.finished)
    assert [got[i] for i in range(len(requests))] == want


@pytest.mark.parametrize("config,message", [
    ({"enable_prefix_cache": True},
     "keep a line a slot \\({'ssm': 3}\\): a prefix hit .* lines never saw"),
])
def test_what_would_skip_the_state_is_refused_by_name(hybrid, config, message):
    with pytest.raises(ValueError, match=message):
        engine_of(hybrid, **config)
    # the default EngineConfig has the prefix cache on: refused too, not
    # silently turned off
    with pytest.raises(ValueError, match="enable_prefix_cache"):
        ServeEngine(hybrid, EngineConfig())


@pytest.mark.parametrize("topology,arch,message", [
    ({"pipe_parallel_size": 2}, {}, "layer_pattern with pipe_parallel_size 2"),
    ({"model_parallel_size": 2}, {}, "layer_pattern with model_parallel_size 2"),
    ({}, {"loop_steps": 2}, "layer_pattern with loop_steps"),
    ({}, {"layer_pattern": ["mamba"]}, "names 1 layers, num_layers is 6"),
    ({}, {"sandwich_norm": True}, "layer_pattern with sandwich_norm"),
    ({}, {"n_groups": 3}, "not a multiple of n_groups"),
    ({}, {"moe_experts_first": 6, "moe_experts_held": 4}, "do not lie in moe_num_experts"),
    ({}, {"adapter_config": {"attention_downsampling_factor": 0.25}}, "adapter_config"),
    ({}, {"layer_pattern": None, "num_layers": 2}, "attention_head_dim without layer_pattern"),
])
def test_a_layout_the_pattern_stack_does_not_build_is_refused_by_name(
        topology, arch, message):
    with pytest.raises(ValueError, match=message):
        hybrid_config(topology, **arch)


def test_training_and_cached_generate_are_refused_by_name(hybrid):
    from scaling_tpu.nn.base_layer import ForwardContext

    with pytest.raises(NotImplementedError, match="layer_pattern stack is served"):
        hybrid.module.forward(hybrid.params, {}, ForwardContext())
    with pytest.raises(ValueError, match="cached generate\\(\\) keeps dense KV"):
        hybrid.generate([1, 2, 3], max_tokens=2)
    # and a cache of the wrong kind, handed to a layer, by name
    from scaling_tpu.serve.kvcache import build_layer_views

    engine = engine_of(hybrid)
    views = build_layer_views(
        engine._pool_state(), jnp.zeros((4, 12), jnp.int32), jnp.zeros((4,), jnp.int32),
        jnp.ones((4,), jnp.int32), kinds=[PagedKVCacheView] + [RecurrentStateView] * 3)
    batch = hybrid._make_batch(jnp.ones((4, 8), jnp.int32), jnp.zeros((4, 8), jnp.int32))
    with pytest.raises(ValueError, match="consumes a RecurrentStateView and was handed a Paged"):
        hybrid._run_layers(hybrid.params, batch, views, None, paged_kernel="xla")
    with pytest.raises(ValueError, match="consumed 4 KV cache"):
        hybrid._run_layers(hybrid.params, batch, build_layer_views(
            engine._pool_state(), jnp.zeros((4, 12), jnp.int32),
            jnp.zeros((4,), jnp.int32), jnp.ones((4,), jnp.int32),
            kinds=engine.pools.kinds) + views[:1], None, paged_kernel="xla")


def test_spans_counters_and_load_of_a_share(hybrid, tmp_path):
    engine = engine_of(hybrid)
    assert engine.num_experts == 4 and engine.moe_partial
    requests = prompts((9, 12), seed=8)
    obs.start_capture(str(tmp_path))
    try:
        served(engine, requests, 6)
    finally:
        capture = obs.stop_capture()
    mixed = [f for n, _, _, f in capture.spans if n == "serve.mixed"]
    emits = [f for n, _, _, f in capture.spans if n == "serve.emit"]
    assert mixed and len(emits) == len(mixed)
    assert all(f["ssm_lines"] == M_LAYERS for f in mixed)
    assert [f["ssm_rows"] for f in mixed] == [f["decodes"] + f["chunks"] for f in mixed]
    assert [f["ssm_step_rows"] + f["ssm_chunk_rows"] for f in mixed] == [
        f["ssm_rows"] for f in mixed]
    # a decode row brings one token; so does the 9-token prompt's last chunk
    assert [f["ssm_chunk_rows"] for f in mixed[:3]] == [2, 1, 0]
    assert [f["chunks"] for f in mixed[:3]] == [2, 2, 0]
    assert capture.counters["serve_ssm_state_updates_total"] == M_LAYERS * sum(
        f["ssm_rows"] for f in mixed)
    # 4 slots x chunk 8 build the one program, of the full width: whole rows
    assert engine.config.mixed_widths == (32,)
    assert {k: v for k, v in capture.counters.items()
            if k.startswith("serve_ssm_rows_total")} == {
        "serve_ssm_rows_total{path=whole}":
            capture.counters["serve_ssm_state_updates_total"]}
    # every real position's 3 assignments in each of the 2 routed layers fell
    # on a held expert or on an absent one
    routed = PATTERN.count("E")
    held = capture.counters["serve_moe_assignments_total"]
    absent = capture.counters["serve_moe_absent_assignments_total"]
    assert held + absent == 3 * routed * sum(f["tokens"] for f in mixed)
    assert held > 0 and absent > 0
    assert absent == sum(f["absent_assign"] for f in emits)
    for f in emits:   # the load is over the four HELD experts
        assert 0 <= f["experts_idle"] <= 4 and f["load_max"] >= f["load_mean"] >= 0
    # the experts' matmuls are given the width's assignments (held, absent
    # and padding alike: the last two in the group no matmul visits)
    assert all(f["moe_rows"] == f["width"] * 3 * routed for f in mixed)
    assert capture.counters["serve_moe_rows_total{path=grouped}"] == sum(
        f["moe_rows"] for f in mixed) >= held + absent


def test_a_tick_with_more_chunk_rows_than_the_small_width_gathers_runs_whole(
        hybrid, tmp_path):
    """16 slots x chunk 32 build two programs; the one of 128 places advances
    one-token rows by the single step and gathers up to 4 chunk rows. Six
    short prompts and a long one arrive at once: the first tick holds 65
    tokens, which fit 128 places, and 7 chunk rows, which do not fit 4, so it
    runs whole rows at the full width; the long prompt's second chunk then
    rides beside six decode rows in the small program. Every request gets the
    uncached forward's tokens."""
    requests = prompts((3, 4, 5, 6, 7, 8, 40), seed=5)
    want = reference_walk.greedy_by_reference(
        lambda tokens: hybrid.logits(jnp.asarray(tokens))[0], requests, 6,
        least_margin=0)
    engine = engine_of(hybrid, num_slots=16, prefill_chunk=32, token_budget=128,
                       max_blocks_per_seq=16, num_blocks=16 * 16 + 1)
    assert engine.config.mixed_widths == (128, 512)
    obs.start_capture(str(tmp_path))
    try:
        got = served(engine, requests, 6)
    finally:
        capture = obs.stop_capture()
    assert [got[i] for i in range(len(requests))] == want
    mixed = [f for n, _, _, f in capture.spans if n == "serve.mixed"]
    assert [(f["width"], f["tokens"], f["ssm_step_rows"], f["ssm_chunk_rows"])
            for f in mixed[:3]] == [(512, 65, 0, 7), (128, 14, 6, 1), (128, 7, 7, 0)]
    assert all(f["width"] == 128 for f in mixed[1:])
    by_path = {path: capture.counters[f"serve_ssm_rows_total{{path={path}}}"]
               for path in ("step", "chunk", "whole")}
    assert by_path == {
        "whole": M_LAYERS * 7, "chunk": M_LAYERS * 1,
        "step": M_LAYERS * sum(f["ssm_step_rows"] for f in mixed[1:])}
    assert sum(by_path.values()) == capture.counters["serve_ssm_state_updates_total"]


def test_a_model_that_holds_all_its_experts_counts_what_it_counted(tmp_path):
    """OLMoE-shaped: ``serve_moe_assignments_total`` is every assignment, no
    absent count, no new span field."""
    config = hybrid_config(moe_experts_held=None)
    module = init_model(config, None)
    inf = TransformerInferenceModule(
        config, module, module.init_params(jax.random.PRNGKey(0)))
    engine = engine_of(inf)
    assert engine.num_experts == 8 and not engine.moe_partial
    obs.start_capture(str(tmp_path))
    try:
        served(engine, prompts((9,), seed=2), 4)
    finally:
        capture = obs.stop_capture()
    tokens = sum(f["tokens"] for n, _, _, f in capture.spans if n == "serve.mixed")
    assert capture.counters["serve_moe_assignments_total"] == 3 * 2 * tokens
    assert "serve_moe_absent_assignments_total" not in capture.counters
    assert not any("absent_assign" in f for n, _, _, f in capture.spans
                   if n == "serve.emit")


def test_a_plain_models_spans_are_what_they_were(tmp_path):
    from scaling_tpu.serve.bench import build_toy_inference

    engine = engine_of(build_toy_inference(hidden=32, layers=2, vocab=64, heads=4))
    obs.start_capture(str(tmp_path))
    try:
        served(engine, [[1, 2, 3, 4, 5]], 3)
    finally:
        capture = obs.stop_capture()
    fields = set().union(*(f for n, _, _, f in capture.spans if n == "serve.mixed"))
    assert not fields & {"ssm_rows", "ssm_lines"}
    assert "serve_ssm_state_updates_total" not in capture.counters


def small_share(bias=None):
    """The toy holding 2 of 64 experts: ``serve_bound`` bites (ISSUE 56). With
    ``bias`` the sigmoid router's selection bias of the two held experts: far
    above the scores, every position's 3 choices hold both."""
    config = hybrid_config(moe_num_experts=64, moe_experts_held=2)
    module = init_model(config, None)
    params = module.init_params(jax.random.PRNGKey(3))
    if bias is not None:
        for layer in params.values():
            router = layer.get("mixer", {}).get("router")
            if router is not None:
                router["bias"] = router["bias"].at[:2].set(bias)
    return TransformerInferenceModule(config, module, params)


@pytest.mark.parametrize("routing", ["balanced", "skewed"])
def test_a_small_share_of_the_experts_is_given_the_bounds_rows(
        routing, tmp_path, monkeypatch):
    """2 of 64 experts held, 3 a token: a width of 128 / 512 places brings 384
    / 1,536 assignments and the routed layers' matmuls are given 128 / 256 rows
    a pass (``moe_rows``). A balanced router never exceeds them; one forced
    onto the held experts does (two 32-token chunks beside eight decode rows:
    144 held assignments a layer against 128 rows), the passes beyond the
    first are counted, and the tokens are the one-hot form's."""
    from scaling_tpu.nn.moe import ParallelMoEMLP

    inf = small_share(10.0 if routing == "skewed" else None)
    requests = prompts((3, 4, 5, 6, 7, 8, 32, 32, 32, 32), seed=6)
    config = dict(num_slots=16, prefill_chunk=32, token_budget=128,
                  max_blocks_per_seq=16, num_blocks=16 * 16 + 1)
    engine = engine_of(inf, **config)
    assert engine.config.mixed_widths == (128, 512) and engine.moe_partial
    routed = PATTERN.count("E")
    assert engine._moe_rows == {128: ("grouped", 128 * routed, True),
                                512: ("grouped", 256 * routed, True)}
    obs.start_capture(str(tmp_path))
    try:
        got = served(engine, requests, 6)
    finally:
        capture = obs.stop_capture()
    mixed = [f for n, _, _, f in capture.spans if n == "serve.mixed"]
    emits = [f for n, _, _, f in capture.spans if n == "serve.emit"]
    assert {f["width"] for f in mixed} == {128, 512}  # eight chunk rows, then two
    assert all(f["moe_rows"] == {128: 128, 512: 256}[f["width"]] * routed
               for f in mixed)
    assert capture.counters["serve_moe_rows_total{path=grouped}"] == sum(
        f["moe_rows"] for f in mixed)
    # a capture keeps the counters that moved
    extra = capture.counters.get("serve_moe_extra_passes_total", 0)
    assert extra == sum(f["moe_extra_passes"] for f in emits)
    held = capture.counters["serve_moe_assignments_total"]
    tokens = sum(f["tokens"] for f in mixed)
    assert held + capture.counters["serve_moe_absent_assignments_total"] == (
        3 * routed * tokens)
    if routing == "balanced":
        assert extra == 0 and 0 < held < 3 * routed * tokens // 8
    else:
        assert held == 2 * routed * tokens
        # 2 x the tick's tokens a layer over the width's rows, less the first
        assert extra == sum(
            routed * max(-(-2 * f["tokens"] // (f["moe_rows"] // routed)) - 1, 0)
            for f in mixed) > 0
    # the one-hot form at room for the whole row, through the same engine
    monkeypatch.setattr(
        ParallelMoEMLP, "serve_rows",
        lambda self, places, mesh=None: ("dense", self.experts_held * places))
    dense = engine_of(inf, **config)
    assert dense._moe_rows[128] == ("dense", 2 * 128 * routed, False)
    assert served(dense, requests, 6) == got
