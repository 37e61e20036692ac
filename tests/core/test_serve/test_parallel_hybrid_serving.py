"""A stack of parallel blocks (``parallel_ssm``: a Mamba-2 mixer BESIDE GQA
attention at group 5 on one normed input, summed into one residual, then a
SwiGLU MLP; Falcon-H1's published multipliers; an untied head) through
``ServeEngine``: every layer keeps a paged KV line AND a recurrent line a
slot, donated and aliased alike; the engine's tokens against the plain
reference's full forward; a slot reused over dirty lines, a sequence preempted
and recomputed; what is refused, by name; the spans' new field, the counter,
the scopes and the stats."""

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
from scaling_tpu.nn.base_layer import ForwardContext, state_views
from scaling_tpu.nn.mamba import RecurrentStateView
from scaling_tpu.serve.engine import EngineConfig, ServeEngine
from scaling_tpu.serve.kvcache import build_layer_views, line_layers

from . import reference_walk

VOCAB, LAYERS = 96, 3
TOPOLOGY = {"model_parallel_size": 1, "pipe_parallel_size": 1,
            "data_parallel_size": 1, "micro_batch_size": 1,
            "gradient_accumulation_steps": 1}
# Falcon-H1-34B's constants (benchmark/configs/falcon-h1-34b-serve.json)
MULTIPLIERS = {
    "embedding": 5.656854249492381, "lm_head": 0.0078125, "attention_in": 1.0,
    "attention_out": 0.0375, "key": 0.011048543456039804, "ssm_in": 0.25,
    "ssm_out": 0.08838834764831845,
    "ssm": [0.3535533905932738, 0.25, 0.1767766952966369, 0.5, 0.3535533905932738],
    "mlp_gate": 0.1767766952966369, "mlp_down": 0.011160714285714284}
ARCH = {"vocab_size": VOCAB, "hidden_size": 40, "num_layers": LAYERS,
        "parallel_ssm": True,
        # 10 query heads over 2 KV heads: a group of 5, as the 34B's 20 over 4
        "num_attention_heads": 10, "attention_num_kv_heads": 2,
        "attention_head_dim": 8, "attention_qkv_in_one": False,
        "attention_bias": False,
        "mlp_type": "swiglu", "mlp_factor": 2.4, "mlp_bias": False,
        "mamba_num_heads": 4, "mamba_head_dim": 8, "ssm_state_size": 16,
        "n_groups": 2, "conv_kernel": 4, "norm_type": "rms",
        "relative_position_embedding_type": "rotary",
        "rotary_embedding_base": 100000000000, "sequence_length": 128,
        "precision": "float32", "weight_tying": False,
        "multipliers": MULTIPLIERS}


def falcon_config(topology=None, **arch):
    return TransformerConfig.from_dict({
        "topology": {**TOPOLOGY, **(topology or {})},
        "transformer_architecture": {**ARCH, **arch},
        "data": {}, "logger": {"log_dir": None}})


def away_from_init(params, key):
    """Norms off one, a conv bias that says something, D off ones."""
    leaves, treedef = jax.tree.flatten(params)
    keys = jax.random.split(key, len(leaves))
    return jax.tree.unflatten(treedef, [
        x + 0.3 * jnp.std(x) * jax.random.normal(k, x.shape) + (
            0.1 * jax.random.normal(k, x.shape) if x.ndim == 1 else 0.0)
        for x, k in zip(leaves, keys)])


@pytest.fixture(scope="module")
def falcon():
    config = falcon_config()
    module = init_model(config, None)
    params = away_from_init(module.init_params(jax.random.PRNGKey(3)),
                            jax.random.PRNGKey(4))
    return TransformerInferenceModule(config, module, params)


@pytest.fixture(scope="module")
def reference():
    ref = cells.load_module(cells.ROOT, "reference", "parallel_hybrid_decoder",
                            cells.REFERENCE_CONTRACT)
    view = cells.load_module(cells.ROOT, "views", "parallel_hybrid_decoder",
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
def undisturbed(falcon, reference):
    """Each prompt alone, greedy, by the plain REFERENCE's full forward (no
    cache, no state pool, no batching, nothing of the program): the tokens,
    and how far the runner-up lies below each."""
    ref, view = reference
    weights = view.reference_weights(falcon.params, ARCH)
    spec = view.reference_spec(ARCH)
    requests = prompts((9, 21, 14, 30, 17))
    return requests, reference_walk.greedy_by_reference(
        lambda tokens: ref.forward(weights, jnp.asarray(tokens), spec), requests, 10,
        least_margin=1e-4)


def test_every_layer_keeps_a_paged_line_and_a_recurrent_line_a_slot(falcon):
    engine = engine_of(falcon)
    pools, stats = engine.pools, engine.stats_snapshot()
    layers = [l for l in falcon.module.layers if state_views(l)]
    assert len(layers) == LAYERS
    assert all(l.consumes == (PagedKVCacheView, RecurrentStateView) for l in layers)
    # an entry a consuming MIXER, in layer order: the layer's paged one first
    assert pools.kinds == [PagedKVCacheView, RecurrentStateView] * LAYERS
    assert line_layers(pools.kinds) == {RecurrentStateView: LAYERS}
    assert pools.kv_lines == stats["kv_lines"] == LAYERS
    assert pools.state_lines == stats["state_lines"] == LAYERS
    assert engine.line_layers == {"ssm": LAYERS} and engine.ssm_lines == LAYERS
    assert engine.par_lines == LAYERS
    ssm, conv = pools.lines
    assert [(a.shape, a.dtype) for a in ssm] == [((4, 4, 8, 16), jnp.float32)] * LAYERS
    assert [a.shape for a in conv] == [(4, 4 * 8 + 2 * 2 * 16, 3)] * LAYERS
    assert stats["state_pool_bytes"] == pools.state_bytes() == LAYERS * 4 * (
        4 * 8 * 16 * 4 + 96 * 3 * 4)
    assert pools.pool_k[0].shape == (64, 4, 2, 8) and len(pools.pool_k) == LAYERS
    # ONE donated structure: the four of the pools, then the two lists
    state = engine._pool_state()
    assert len(state) == 6 and state[4] is ssm and state[5] is conv
    # the views are one an entry, and the state comes back as it went in
    views = build_layer_views(state, jnp.zeros((4, 12), jnp.int32),
                              jnp.zeros((4,), jnp.int32), jnp.ones((4,), jnp.int32),
                              kinds=pools.kinds)
    assert [type(v) for v in views] == pools.kinds


def test_every_donated_leaf_is_aliased_to_the_output_computed_from_it(falcon):
    """K, V, ssm and conv of every layer: the alias table of the lowered mixed
    program pairs each with the output in its own place (PR 51's rule)."""
    engine = engine_of(falcon)
    width = engine.config.mixed_widths[0]
    state = engine._pool_state()
    packed = jnp.zeros((engine._layout.size(width),), jnp.int32)
    text = jax.jit(engine._build_mixed_fn(width).__wrapped__, donate_argnums=(1,),
                   keep_unused=True).lower(
        falcon.params, state, packed, engine._base_key, engine._prev).as_text()
    leaves = len(jax.tree.leaves(state))
    assert leaves == 4 * LAYERS
    aliased = re.findall(r"tf\.aliasing_output = (\d+)", text)
    # behind the host's read and the grid the next program is fed
    assert sorted(map(int, aliased)) == list(range(2, leaves + 2))


def test_the_program_is_the_reference_at_every_position(falcon, reference):
    """The uncached forward: both mixers on the one normed input, summed,
    the MLP, every multiplier, the untied head."""
    ref, view = reference
    tokens = prompts((40,), seed=5)[0]
    want = ref.forward(view.reference_weights(falcon.params, ARCH),
                       jnp.asarray(tokens), view.reference_spec(ARCH))
    got = falcon.logits(jnp.asarray([tokens]))[0]
    assert want.shape == (40, VOCAB)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-5)
    assert float(jnp.std(want)) > 0.1      # the init's work: logits of a size


def test_the_engine_serves_what_the_references_full_forward_gives(falcon, undisturbed):
    """Prefill in chunks of 8 whose edges fall mid-prompt (9, 21, 14, 30, 17),
    four rows at once and a fifth in a reused slot, then decode: ticks mix
    chunk rows and decode rows, through the paged line AND the recurrent line
    of every layer."""
    requests, want = undisturbed
    engine = engine_of(falcon)
    got = served(engine, requests, 10)
    assert [got[i] for i in range(len(requests))] == want
    assert len({tuple(w) for w in want}) > 1  # the weights say something
    assert len({t for w in want for t in w}) > 5
    # both forms of the recurrence ran: single steps and gathered chunks
    assert engine.mixed_ticks.keys() >= {engine.config.mixed_widths[0]}


def test_a_reused_slot_does_not_inherit_its_old_occupants_lines(falcon, undisturbed):
    """One slot: five sequences follow one another through the same lines of
    both pools, no reset by the host in between; the lines start dirty."""
    requests, want = undisturbed
    engine = engine_of(falcon, num_slots=1)
    engine.pools.lines = tuple(
        [jnp.full_like(a, 7.0) for a in field] for field in engine.pools.lines)
    got = served(engine, requests, 10)
    assert [got[i] for i in range(len(requests))] == want
    assert float(jnp.abs(engine.pools.lines[0][0]).max()) > 0


def test_a_preempted_and_resumed_sequence_reproduces_its_tokens(falcon, undisturbed):
    """A pool too small for the rows forces recompute-style preemption: the
    resumed sequence re-enters at context 0, so the program starts its
    recurrent lines from zeros and the recompute regenerates token for token."""
    requests, want = undisturbed
    engine = engine_of(falcon, num_blocks=17)
    got = served(engine, requests, 10)
    assert engine.scheduler.preemption_count > 0
    assert any(s.preemptions for s in engine.finished)
    assert [got[i] for i in range(len(requests))] == want


@pytest.mark.parametrize("dropped", ["recurrent lines", "keys' multiplier"])
def test_the_comparison_sees_each_mixer(falcon, undisturbed, monkeypatch, dropped):
    """With the recurrent lines never written back, or the key multiplier left
    out of the served path, the engine's tokens differ from the reference's."""
    from scaling_tpu.nn import attention, mamba

    if dropped == "recurrent lines":
        real = mamba.Mamba2Mixer._serve
        monkeypatch.setattr(
            mamba.Mamba2Mixer, "_serve",
            lambda self, params, z, xBC, dt, view: (
                real(self, params, z, xBC, dt, view)[0], view))
    else:
        monkeypatch.setattr(attention, "multiplied", lambda x, by: x)
    requests, want = undisturbed
    got = served(engine_of(falcon), requests, 10)
    assert [got[i] for i in range(len(requests))] != want


@pytest.mark.parametrize("config,message", [
    ({"enable_prefix_cache": True},
     "keep a line a slot \\({'ssm': 3}\\): a prefix hit .* lines never saw"),
])
def test_what_would_skip_the_lines_is_refused_by_name(falcon, config, message):
    with pytest.raises(ValueError, match=message):
        engine_of(falcon, **config)
    with pytest.raises(ValueError, match="enable_prefix_cache"):
        ServeEngine(falcon, EngineConfig())


@pytest.mark.parametrize("topology,arch,message", [
    ({"pipe_parallel_size": 2}, {}, "parallel_ssm with pipe_parallel_size 2"),
    ({"model_parallel_size": 2}, {}, "parallel_ssm with model_parallel_size 2"),
    ({}, {"loop_steps": 2}, "parallel_ssm with loop_steps > 1"),
    ({}, {"layer_pattern": ["mamba"] * LAYERS, "parallel_ssm": False},
     "multipliers with layer_pattern"),
    ({}, {"layer_pattern": ["mamba"] * LAYERS}, "parallel_ssm with layer_pattern"),
    ({}, {"sandwich_norm": True}, "parallel_ssm with sandwich_norm"),
    ({}, {"mlp_type": "moe", "multipliers": {}}, "parallel_ssm with mlp_type 'moe'"),
    ({}, {"lora_config": {"name": "lora"}}, "parallel_ssm with lora_config"),
    ({}, {"n_groups": 3}, "not a multiple of n_groups 3"),
    ({}, {"mlp_type": "default"}, "mlp_gate / mlp_down are a SwiGLU MLP's"),
    ({}, {"parallel_ssm": False}, "attention_head_dim without layer_pattern or parallel_ssm"),
    ({}, {"multipliers": {"ssm": [1.0, 1.0]}}, "ssm"),
    ({}, {"multipliers": {"key": 0.0}}, "key"),
])
def test_a_layout_the_block_does_not_build_is_refused_by_name(topology, arch, message):
    with pytest.raises(ValueError, match=message):
        falcon_config(topology, **arch)


def test_training_and_cached_generate_are_refused_by_name(falcon):
    with pytest.raises(NotImplementedError, match="parallel_ssm stack is served"):
        falcon.module.forward(falcon.params, {}, ForwardContext())
    with pytest.raises(ValueError, match="cached generate\\(\\) keeps dense KV"):
        falcon.generate([1, 2, 3], max_tokens=2)
    # the uncached truth stays reachable
    out = falcon.generate([1, 2, 3], max_tokens=2, use_cache=False)
    assert len(out.token_ids if hasattr(out, "token_ids") else out) >= 2
    # a state of the wrong kind, handed to a layer, by both names
    engine = engine_of(falcon)
    views = build_layer_views(
        engine._pool_state(), jnp.zeros((4, 12), jnp.int32), jnp.zeros((4,), jnp.int32),
        jnp.ones((4,), jnp.int32), kinds=engine.pools.kinds)
    batch = falcon._make_batch(jnp.ones((4, 8), jnp.int32), jnp.zeros((4, 8), jnp.int32))
    with pytest.raises(ValueError, match="consumes a PagedKVCacheView and a "
                                         "RecurrentStateView and was handed a Recurrent"):
        falcon._run_layers(falcon.params, batch, views[1:] + views[:1], None,
                           paged_kernel="xla")
    with pytest.raises(ValueError, match="but only 5 were provided"):
        falcon._run_layers(falcon.params, batch, views[:5], None, paged_kernel="xla")
    ctx = falcon._make_ctx()
    embedded = falcon.module.layers[0](falcon.params["layer_0"], batch, ctx)
    with pytest.raises(ValueError, match="a parallel block takes a PagedKVCacheView and"):
        falcon.module.layers[1](falcon.params["layer_1"], embedded, ctx,
                                kv_cache=((jnp.zeros((4, 8, 2, 8)),) * 2,) * 2,
                                cache_offset=0)


def test_multipliers_of_one_and_no_ssm_are_the_dense_decoder(reference):
    """The same stack with every multiplier at 1 and the second mixer removed
    is the dense decoder the benchmark's other reference computes, and its
    lowered forward is that of a configuration that never named either."""
    arch = {k: v for k, v in ARCH.items() if k not in (
        "parallel_ssm", "multipliers", "attention_head_dim")}
    arch.update(num_attention_heads=10, hidden_size=80)
    named = falcon_config(**{**arch, "parallel_ssm": False, "attention_head_dim": None,
                             "multipliers": {"ssm": [1.0] * 5}})
    plain = TransformerConfig.from_dict({
        "topology": TOPOLOGY, "transformer_architecture": arch,
        "data": {}, "logger": {"log_dir": None}})
    tokens = jnp.asarray([prompts((12,), seed=2)[0]])
    texts, outs = [], []
    for config in (named, plain):
        module = init_model(config, None)
        params = module.init_params(jax.random.PRNGKey(1))
        inf = TransformerInferenceModule(config, module, params)
        assert all(layer.consumes is PagedKVCacheView
                   for layer in module.layers if state_views(layer))
        outs.append(np.asarray(inf.logits(tokens)))
        batch = inf._make_batch(tokens, jnp.arange(12)[None])
        texts.append(jax.jit(lambda p: inf._run_layers(p, batch, None, None)[0]).lower(
            params).as_text())
    assert np.array_equal(outs[0], outs[1]) and texts[0] == texts[1]
    dense_ref = cells.load_module(cells.ROOT, "reference", "dense_decoder",
                                  cells.REFERENCE_CONTRACT)
    dense_view = cells.load_module(cells.ROOT, "views", "dense_decoder",
                                   cells.VIEW_CONTRACT)
    want = dense_ref.forward(dense_view.reference_weights(params, arch), tokens[0],
                             dense_view.reference_spec(arch))
    np.testing.assert_allclose(outs[1][0], np.asarray(want), atol=3e-5)


def test_spans_counters_and_stats_count_both_kinds_of_lines(falcon, tmp_path):
    engine = engine_of(falcon)
    requests = prompts((9, 12), seed=8)
    obs.start_capture(str(tmp_path))
    try:
        served(engine, requests, 6)
    finally:
        capture = obs.stop_capture()
    mixed = [f for n, _, _, f in capture.spans if n == "serve.mixed"]
    assert mixed and all(f["par_lines"] == LAYERS for f in mixed)
    assert all(f["ssm_lines"] == LAYERS for f in mixed)
    assert [f["ssm_rows"] for f in mixed] == [f["decodes"] + f["chunks"] for f in mixed]
    assert all(f["ssm_step_rows"] + f["ssm_chunk_rows"] == f["ssm_rows"] for f in mixed)
    assert all("kv_rows" in f and "kv_tiles" in f for f in mixed)
    counters = capture.counters
    assert counters["serve_parallel_mixer_passes_total"] == LAYERS * len(mixed)
    assert counters["serve_ssm_state_updates_total"] == LAYERS * sum(
        f["ssm_rows"] for f in mixed)
    by_path = {k: v for k, v in counters.items() if k.startswith("serve_ssm_rows_total")}
    assert sum(by_path.values()) == counters["serve_ssm_state_updates_total"]
    assert not any("moe_rows" in f or "conv_rows" in f for f in mixed)


def test_a_short_mixed_run_advances_its_rows_in_the_forms_it_did(falcon, tmp_path):
    """16 slots x chunk 32 build two programs, 128 and 512 places; six short
    prompts and a long one arrive at once. Which form advanced which rows is
    what it was before the chunk rows' lines were fetched row by row (ISSUE
    53: the same rows gathered, the same tick at the full width), tick by
    tick and in the counters, and every request gets the uncached forward's
    tokens."""
    requests = prompts((3, 4, 5, 6, 7, 8, 40), seed=5)
    want = reference_walk.greedy_by_reference(
        lambda tokens: falcon.logits(jnp.asarray(tokens))[0], requests, 6,
        least_margin=0)
    engine = engine_of(falcon, num_slots=16, prefill_chunk=32, token_budget=128,
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
            for f in mixed] == [
        (512, 65, 0, 7), (128, 14, 6, 1), (128, 7, 7, 0), (128, 7, 7, 0),
        (128, 7, 7, 0), (128, 7, 7, 0), (128, 1, 1, 0)]
    assert {path: capture.counters[f"serve_ssm_rows_total{{path={path}}}"]
            for path in ("step", "chunk", "whole")} == {
        "whole": LAYERS * 7, "chunk": LAYERS * 1, "step": LAYERS * 35}
    assert capture.counters["serve_ssm_state_updates_total"] == LAYERS * 43
    # both programs ran under the capture, each under a name of its own: the
    # benchmark's readers find an operation's scope by its module's name and
    # its own (under ONE name a full-width tick in a traced slice made them
    # read the small program's operations in the wide program's table)
    from benchmark import xplane_hlo
    modules = xplane_hlo.hlo_modules(capture.trace_file().read_bytes())
    assert {"jit_mixed_128", "jit_mixed_512"} <= set(modules)


def test_the_two_mixers_the_mlp_and_the_head_lie_in_scopes_of_their_own(falcon):
    """``attn``, ``ssm``, ``mlp`` and ``head`` name the instructions compiled
    from inside each: what the benchmark's readers look up in a trace's HLO.
    The Mamba-2 mixer is no part of ``attn``."""
    engine = engine_of(falcon)
    width = engine.config.mixed_widths[0]
    packed = jnp.zeros((engine._layout.size(width),), jnp.int32)
    hlo = jax.jit(engine._build_mixed_fn(width).__wrapped__).lower(
        falcon.params, engine._pool_state(), packed, engine._base_key,
        engine._prev).compile().as_text()
    from benchmark.readers.parallel_hybrid import SCOPES

    names = set(re.findall(r'op_name="([^"]+)"', hlo))
    inside = {scope: {n for n in names if pattern.search(n)}
              for scope, pattern in SCOPES.items()}
    assert all(inside.values()), {k: len(v) for k, v in inside.items()}
    assert not inside["attn"] & inside["ssm"]
    assert any("head/cond" in n for n in inside["head"])   # the sampler too
    assert any("paged_attention" in n or "pallas" in n or "gather" in n
               for n in inside["attn"])                    # the cache's read
