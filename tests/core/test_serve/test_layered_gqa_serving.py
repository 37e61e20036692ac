"""A stack of Laguna blocks (``layer_pattern``: FULL grouped-query attention or
WINDOW attention with a head count and a rotary of its own, a per-head output
gate on both, then a dense or a softmax-routed FFN with a shared expert)
through ``ServeEngine``: a window layer keeps a RING of lines a slot where a
full layer keeps pages; prefill in chunks then decode until every ring has
wrapped twice against the plain reference's full forward, on logits, through
the walk's kernel and through its gather form; a chunk that straddles the
ring's end; a slot reused after a longer row; the engine's greedy tokens; the
three deliberate faults (no gate, one rotary for both kinds, no window) seen by
the comparison of logits; the eight shares of the routed layer; what is
refused, by name; the rings' bytes whatever the context, the gauges and the
counters."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import cells
from scaling_tpu import obs
from scaling_tpu.models.transformer import TransformerConfig
from scaling_tpu.models.transformer.inference import TransformerInferenceModule
from scaling_tpu.models.transformer.model import init_model
from scaling_tpu.nn.window_attention import (
    WindowRingView, WindowSelfAttention, line_positions, ring_lines,
)
from scaling_tpu.serve.engine import EngineConfig, ServeEngine

from . import reference_walk

VOCAB, HIDDEN, WINDOW, CHUNK = 96, 64, 8, 8
BRANCH_SCALE = 4.0
# two periods of the published pattern: full, window x 3; the leading FFN dense
PATTERN = ["attention", "mlp"] + ["window", "moe"] * 3 + (
    ["attention", "moe"] + ["window", "moe"] * 3)
TOPOLOGY = {"model_parallel_size": 1, "pipe_parallel_size": 1,
            "data_parallel_size": 1, "micro_batch_size": 1,
            "gradient_accumulation_steps": 1}
ARCH = {"vocab_size": VOCAB, "hidden_size": HIDDEN, "num_layers": len(PATTERN),
        "layer_pattern": PATTERN,
        "num_attention_heads": 6, "window_num_attention_heads": 9,
        "attention_num_kv_heads": 3, "attention_head_dim": 16,
        "attention_qkv_in_one": False, "attention_bias": False,
        "attention_gate": "per_head", "window_size": WINDOW,
        "rotary_embedding_base": 500000, "rotary_percentage": 0.5,
        "rope_scaling": {"type": "yarn", "factor": 8,
                         "original_max_position_embeddings": 16,
                         "beta_fast": 32, "beta_slow": 1},
        "window_rotary_embedding_base": 10000,
        "mlp_type": "swiglu", "mlp_factor": 4.0, "mlp_bias": False,
        "moe_num_experts": 16, "moe_top_k": 3, "moe_expert_width": 32,
        "moe_glu": True, "moe_router": "softmax", "moe_norm_topk_prob": True,
        "moe_routed_scaling_factor": 2.5, "moe_shared_expert_width": 32,
        "moe_experts_first": 0, "moe_experts_held": 4,
        "activation_function": "silu", "norm_type": "rms",
        "layernorm": {"layernorm_epsilon": 1e-6},
        "relative_position_embedding_type": "rotary", "sequence_length": 128,
        "precision": "float32", "weight_tying": False}


def laguna_config(topology=None, **arch):
    return TransformerConfig.from_dict({
        "topology": {**TOPOLOGY, **(topology or {})},
        "transformer_architecture": {**ARCH, **arch},
        "data": {}, "logger": {"log_dir": None}})


@pytest.fixture(scope="module")
def laguna():
    config = laguna_config()
    module = init_model(config, None)
    params = module.init_params(jax.random.PRNGKey(3))
    # away from the init: norms off one; branches that are no small steps (the
    # seeded init starts every mixer's output projection at 1 / (2
    # sqrt(layers)) of its Xavier scale), a router that chooses, and gates
    # that differ by head and by token
    leaves, treedef = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(4), len(leaves))
    params = jax.tree.unflatten(treedef, [
        x + (0.2 * jax.random.normal(k, x.shape) if x.ndim == 1 else 0.0)
        for x, k in zip(leaves, keys)])
    for i in range(1, len(PATTERN) + 1):
        mixer = params[f"layer_{i}"]["mixer"]
        for out in ("dense", "down_proj"):
            if out in mixer:
                mixer[out]["weight"] = BRANCH_SCALE * mixer[out]["weight"]
        for out in ("w_out", "shared_out"):
            if out in mixer:
                mixer[out] = BRANCH_SCALE * mixer[out]
        if "router" in mixer:
            mixer["router"]["weight"] = 20 * mixer["router"]["weight"]
        if "gate" in mixer:
            mixer["gate"]["weight"] = 4 * mixer["gate"]["weight"]
    return TransformerInferenceModule(config, module, params)


@pytest.fixture(scope="module")
def reference():
    return (cells.load_module(cells.ROOT, "reference", "layered_gqa_moe_decoder",
                              cells.REFERENCE_CONTRACT),
            cells.load_module(cells.ROOT, "views", "layered_gqa_moe_decoder",
                              cells.VIEW_CONTRACT))


def prompts(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, VOCAB, size=n).tolist() for n in lengths]


# the ring is 16 lines (window 8, chunks of 8): 56 positions wrap it thrice
TOKENS = prompts((56,), seed=5)[0]
RING = ring_lines(WINDOW, CHUNK)


def by_reference(inf, reference, tokens):
    ref, view = reference
    return np.asarray(ref.forward(view.reference_weights(inf.params, ARCH),
                                  jnp.asarray(tokens), view.reference_spec(ARCH)))


@pytest.fixture(scope="module")
def wanted(laguna, reference):
    """The reference's full forward over ``TOKENS``: logits at every position."""
    return by_reference(laguna, reference, TOKENS)


def engine_of(inf, **config):
    return ServeEngine(inf, EngineConfig(**{
        "num_slots": 4, "block_size": 4, "num_blocks": 4 * 16 + 1,
        "max_blocks_per_seq": 16, "token_budget": 64, "prefill_chunk": CHUNK,
        "enable_prefix_cache": False, **config}))


def one_row_engine(inf):
    return engine_of(inf, num_slots=1, num_blocks=64 // 4 + 1,
                     max_blocks_per_seq=64 // 4)


walk = reference_walk.paged_walk


# float32 on both sides: what separates the served form from the reference's
# full forward is the order of float32 sums
LOGIT_ATOL = 2e-4
# two chunks of prefill, then decode one by one: 40 decode rows wrap the ring
# of 16 twice and more
PREFILL_THEN_DECODE = [CHUNK] * 2 + [1] * (len(TOKENS) - 2 * CHUNK)


@pytest.mark.parametrize("paged_kernel", ["xla", "pallas"])
def test_chunks_then_decode_through_ring_and_pool_are_the_references_full_forward(
        laguna, wanted, paged_kernel):
    got, state = walk(laguna, one_row_engine(laguna), TOKENS,
                      PREFILL_THEN_DECODE, paged_kernel)
    assert got.shape == wanted.shape == (len(TOKENS), VOCAB)
    np.testing.assert_allclose(got, wanted, atol=LOGIT_ATOL)
    assert wanted.std() > 0.3    # the logits say something
    # the state: two pools (the full layers), six rings of 16 lines a slot
    assert len(state[0]) == 2 and [a.shape for a in state[4]] == [
        (1, RING, 3 * 16)] * 6 and RING == 16


def test_a_chunk_that_straddles_the_rings_end_reads_what_it_must(laguna, wanted):
    """Chunks whose edges fall at 4, 12, 20, ...: the second covers positions
    12-19, lines 12-15 then 0-3, and its first query still reads positions
    5-11; then chunks all the way, each over lines the one before wrote."""
    sizes = [4] + [CHUNK] * 6 + [1] * 4
    for kernel in ("xla", "pallas"):
        got, _ = walk(laguna, one_row_engine(laguna), TOKENS, sizes, kernel)
        np.testing.assert_allclose(got, wanted, atol=LOGIT_ATOL)


def test_a_slot_reused_after_a_longer_row_sees_nothing_of_it(laguna, reference):
    """A row of 56 tokens leaves every line of its slot's rings written; the
    next row of the same slot starts at position 0 and its 11 tokens see their
    own lines alone (a line's position follows from the row's last position;
    what is not this row's is masked: no reset by the host)."""
    engine = one_row_engine(laguna)
    _, state = walk(laguna, engine, TOKENS, PREFILL_THEN_DECODE, "pallas")
    short = prompts((11,), seed=9)[0]
    got, _ = walk(laguna, engine, short, [CHUNK, 1, 1, 1], "pallas", state=state)
    np.testing.assert_allclose(got, by_reference(laguna, reference, short),
                               atol=LOGIT_ATOL)


def uncached_logits(inf):
    """The uncached pass over ``TOKENS``, traced anew on every call."""
    ids = jnp.asarray(TOKENS, jnp.int32)[None]
    batch = inf._make_batch(ids, jnp.arange(len(TOKENS), dtype=jnp.int32)[None])
    return np.asarray(jax.jit(
        lambda p: inf._run_layers(p, batch, None, None)[0])(inf.params)[0])


def test_the_uncached_pass_is_the_references_too(laguna, wanted):
    np.testing.assert_allclose(uncached_logits(laguna), wanted, atol=LOGIT_ATOL)


def mixers(inf, kind):
    return [layer.mixer for layer in inf.module.layers
            if isinstance(getattr(layer, "mixer", None), kind)]


@pytest.mark.parametrize("fault", [
    "the gate skipped", "one rotary for both kinds", "nothing windowed"])
def test_each_deliberate_fault_moves_the_logits_past_the_tolerance(
        laguna, wanted, monkeypatch, fault):
    """The comparison of logits sees each mechanism: a run that skips the
    gate, turns the window layers' heads by the full layers' rotary, or
    windows nothing moves a logit by more than the benchmark's 0.05."""
    window = mixers(laguna, WindowSelfAttention)
    full = [m for m in mixers(laguna, type(window[0]).__mro__[1])
            if not isinstance(m, WindowSelfAttention)]
    assert (len(full), len(window)) == (2, 6)
    if fault == "the gate skipped":
        for m in full + window:
            monkeypatch.setattr(m, "gate", None)
    elif fault == "one rotary for both kinds":
        for m in window:
            monkeypatch.setattr(m, "rotary_embedding", full[0].rotary_embedding)
    else:
        for m in window:
            monkeypatch.setattr(m, "window_size", 10 ** 6)
    got = uncached_logits(laguna)
    assert np.abs(got - wanted).max() > 0.05 > 100 * LOGIT_ATOL


REQUESTS = prompts((9, 37, 14, 3), seed=2)
NEW_TOKENS = 24


@pytest.fixture(scope="module")
def served(laguna, tmp_path_factory):
    """ONE engine serving ``REQUESTS`` under a capture: prefill in chunks of 8
    whose edges fall mid-prompt, four rows at once, then decode past the
    window and around the ring."""
    engine = engine_of(laguna)
    obs.start_capture(str(tmp_path_factory.mktemp("capture")))
    try:
        for p in REQUESTS:
            engine.submit(p, max_new_tokens=NEW_TOKENS)
        got = {s.request.req_id: s.generated for s in engine.run_until_done()}
    finally:
        capture = obs.stop_capture()
    return engine, [got[i] for i in range(len(REQUESTS))], capture


def test_the_engine_serves_what_the_references_full_forward_gives(
        laguna, reference, served):
    """Ticks mix chunk rows and decode rows, token-major: every token the
    engine emitted is within the tolerance of the reference's best at its
    position, teacher-forced through the reference's full forward."""
    ref, view = reference
    engine, got, _ = served
    weights = view.reference_weights(laguna.params, ARCH)
    spec = view.reference_spec(ARCH)
    longest = max(map(len, REQUESTS)) + NEW_TOKENS
    for p, out in zip(REQUESTS, got):
        assert len(out) == NEW_TOKENS
        tokens = np.zeros((longest,), np.int32)
        tokens[:len(p) + NEW_TOKENS - 1] = list(p) + out[:-1]
        at = np.arange(len(p) - 1, len(p) - 1 + NEW_TOKENS)
        logits = np.asarray(ref.forward(weights, jnp.asarray(tokens), spec,
                                        head_positions=jnp.asarray(at)))
        picked = logits[np.arange(NEW_TOKENS), out]
        assert (logits.max(-1) - picked).max() < LOGIT_ATOL
    assert len({tuple(out) for out in got}) > 1     # the weights say something
    stats = engine.stats_snapshot()
    assert stats["window_layers"] == 6 and stats["state_lines"] == 6
    assert stats["kv_lines"] == 2


def test_the_rings_bytes_do_not_depend_on_the_context(laguna):
    """What the engine allocates for the six window layers is a fixed number
    of lines a slot: the same at four times the context, where the full
    layers' pools are four times as large; the gauges say how many."""
    small = engine_of(laguna)
    large = engine_of(laguna, num_blocks=4 * 64 + 1, max_blocks_per_seq=64)
    assert small.pools.state_bytes() == large.pools.state_bytes() == (
        6 * 2 * 4 * RING * 3 * 16 * 4)
    assert large.pools.device_bytes() > 3.9 * small.pools.device_bytes()
    gauges = obs.get_registry().snapshot()["gauges"]
    assert gauges["serve_window_ring_lines"] == RING
    assert gauges["serve_window_ring_gb"] == small.pools.state_bytes() / 1e9
    # a wider row needs a longer ring: window - 1 + row width lines at least
    assert engine_of(laguna, prefill_chunk=32, token_budget=160
                     ).window_ring_lines == 64 >= WINDOW - 1 + 32
    assert [ring_lines(512, 512), ring_lines(512, 1), ring_lines(8, 8)] == [
        1024, 512, 16]


def test_a_lines_position_follows_from_the_rows_last_position():
    held = np.asarray(line_positions(jnp.asarray([5, 37], jnp.int32), 16))
    assert held[0].tolist() == [0, 1, 2, 3, 4, 5] + list(range(-10, 0))
    assert sorted(held[1].tolist()) == list(range(22, 38))
    assert all(p % 16 == line for line, p in enumerate(held[1].tolist()))


def test_the_ticks_say_what_the_window_did(served):
    """``serve.mixed`` carries the window layers' rows, those past the window,
    the ring lines their queries see and the (query, line) pairs; the counters
    sum them over the layers."""
    engine, _, capture = served
    ticks = [s for s in obs.recorded_spans() if s.name == "serve.mixed"
             and "window_layers" in s.fields
             and s.start_ns >= engine._created_ns]
    assert ticks and all(t.fields["window_layers"] == 6 for t in ticks)
    # a tick of four decode rows deep in their sequences: each sees a full
    # window of 8 lines, all are past the window
    deep = [t for t in ticks if t.fields["window_rows"] == 4
            and t.fields["tokens"] == 4 and t.fields["window_rows_past"] == 4]
    assert deep and all(t.fields["window_pairs"] == 4 * WINDOW
                        and t.fields["window_visible_lines"] == 4 * WINDOW
                        for t in deep)
    # the first tick prefills from nothing: no row is past the window yet
    assert ticks[0].fields["window_rows_past"] == 0
    counters = obs.get_registry().snapshot()["counters"]
    assert counters["serve_window_rows_total"] >= 6 * sum(
        t.fields["window_rows"] for t in ticks)
    assert counters["serve_window_rows_past_window_total"] >= 6 * sum(
        t.fields["window_rows_past"] for t in ticks) > 0


def test_eight_shares_of_the_routed_layer_add_up_to_the_uncut_layer(reference):
    """The published layer's form at a small size: 64 softmax-routed experts,
    10 a token, gates renormalised over the chosen and times 2.5, held whole
    against the same layer as 8 ranks of 8 experts each (the router keeps its
    64 outputs and its 10 a token; absent experts' gates are dropped AFTER the
    renormalisation): the ranks' routed parts plus the shared expert ONCE are
    the whole layer. In the reference, and in the program's ``serve``."""
    from scaling_tpu.nn.moe import ParallelMoEMLP

    ref, _ = reference
    H, F, E, K, HELD = 64, 32, 64, 10, 8
    make = lambda first, held: ParallelMoEMLP(
        io_features=H, intermediate_feature_factor=1.0, num_experts=E, top_k=K,
        norm_topk_prob=True, glu=True, intermediate=F, router="softmax",
        routed_scaling_factor=2.5, shared_expert_width=F, experts_first=first,
        experts_held=held)
    whole = make(0, E)
    params = whole.init(jax.random.PRNGKey(0))
    params["router"]["weight"] = 20 * params["router"]["weight"]
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 24, H))

    def rank_params(first, held):
        p = dict(params)
        for leaf in ("w_in", "w_out", "w_gate"):
            p[leaf] = params[leaf][first:first + held]
        return p

    def as_reference(p):
        return {"router": p["router"]["weight"], "shared_gate": p["shared_gate"],
                "shared_up": p["shared_in"], "shared_down": p["shared_out"]}, {
                "w_gate": p["w_gate"], "w_up": p["w_in"], "w_down": p["w_out"]}

    spec = {"top_k": K, "scale": 2.5, "experts_first": 0, "shared": True}
    with jax.default_matmul_precision("highest"):
        p, experts = as_reference(params)
        want = ref.routed_ffn(x[0], p, experts, spec)
        shared = ref.swiglu(x[0], p["shared_gate"], p["shared_up"], p["shared_down"])
        parts = []
        for first in range(0, E, HELD):
            p, experts = as_reference(rank_params(first, HELD))
            parts.append(ref.routed_ffn(
                x[0], p, experts, {**spec, "experts_first": first, "shared": False}))
        assert len(parts) == 8
        np.testing.assert_allclose(sum(parts) + shared, want, atol=5e-6)
        # no share is the whole: a rank alone leaves most of the layer out
        assert np.abs(parts[0] + shared - want).max() > 0.05
        # the program: each rank's serve() holds the shared expert, so the
        # eight outputs count it eight times
        got_whole, _ = whole.serve(params, x)
        np.testing.assert_allclose(got_whole[0], want, atol=3e-5)
        ranks = [make(first, HELD).serve(rank_params(first, HELD), x)[0][0]
                 for first in range(0, E, HELD)]
        np.testing.assert_allclose(sum(ranks) - 7 * shared, want, atol=2e-4)


@pytest.mark.parametrize("arch, message", [
    ({"window_size": None}, "needs window_size"),
    ({"num_local_attention_heads": 2, "local_attention_window_size": 4},
     "'window' layers with num_local_attention_heads"),
    ({"index_n_heads": 2, "index_head_dim": 16, "index_topk": 4},
     "without ONE kind of attention layer|'window' layers with index_"),
    ({"relative_position_embedding_type": "none"},
     "'window' layers and relative_position_embedding_type"),
    ({"attention_num_kv_heads": None, "attention_qkv_in_one": True},
     "needs attention_num_kv_heads"),
    ({"window_num_attention_heads": 8}, "is not a multiple of"),
    ({"causal": False}, "'window' layers and causal false"),
    ({"key_query_norm": True}, "'window' layers with key_query_norm"),
    ({"layer_pattern": ["attention", "mlp"] * 8}, "without 'window' layers"),
    ({"layer_pattern": ["window", "mlp"] * 8},
     "rope_scaling without 'latent' or 'attention' layers"),
])
def test_what_the_configuration_refuses_is_refused_by_name(arch, message):
    with pytest.raises(ValueError, match=message):
        laguna_config(**arch)


@pytest.mark.parametrize("topology, message", [
    ({"model_parallel_size": 2}, "layer_pattern with model_parallel_size 2"),
    ({"pipe_parallel_size": 2}, "layer_pattern with pipe_parallel_size 2"),
    ({"context_parallel_size": 2, "data_parallel_size": 1},
     "'window' layers with context_parallel_size 2"),
])
def test_what_the_layout_refuses_is_refused_by_name(topology, message):
    with pytest.raises(ValueError, match=message):
        laguna_config(topology=topology)


def test_a_gate_or_a_scaled_rotary_elsewhere_is_refused_by_name():
    plain = {k: v for k, v in ARCH.items() if k not in (
        "layer_pattern", "window_size", "window_num_attention_heads",
        "attention_head_dim", "rope_scaling")}
    with pytest.raises(ValueError, match="attention_gate without layer_pattern"):
        laguna_config(**{**plain, "layer_pattern": None, "window_size": None,
                         "window_num_attention_heads": None,
                         "attention_head_dim": None, "rope_scaling": None,
                         "num_layers": 2})


@pytest.mark.parametrize("engine, message", [
    ({"enable_prefix_cache": True},
     "enable_prefix_cache with layers that keep a line a slot"),
    ({"kv_dtype": "int8"}, "kv_dtype 'int8' with window attention layers"),
])
def test_what_the_engine_refuses_is_refused_by_name(laguna, engine, message):
    with pytest.raises(ValueError, match=message):
        engine_of(laguna, **engine)


def test_training_and_a_dense_cache_are_refused_by_name(laguna):
    ids = jnp.asarray([TOKENS[:8]], jnp.int32)
    with pytest.raises(NotImplementedError,
                       match="layer_pattern stack is served, not trained"):
        laguna.module.forward(laguna.params, {"token_ids": ids}, laguna._make_ctx())
    with pytest.raises(ValueError, match="cached generate\\(\\) keeps dense KV"):
        laguna.generate(ids, max_tokens=2)
    layer = next(l for l in laguna.module.layers
                 if isinstance(getattr(l, "mixer", None), WindowSelfAttention))
    dense = (jnp.zeros((1, 8, 3, 16)),) * 2
    with pytest.raises(ValueError, match="a window layer takes a WindowRingView"):
        layer(laguna.module._layer_params(laguna.params, 3),
              {"activations": jnp.zeros((1, 8, HIDDEN))}, laguna._make_ctx(),
              kv_cache=dense)


def test_a_ring_too_short_for_the_rows_width_is_refused(laguna):
    """The invariant where it would break: a view whose rings hold fewer than
    window - 1 + row width lines raises when the layer is traced."""
    mixer = mixers(laguna, WindowSelfAttention)[0]
    lines = jnp.zeros((1, 8, 3 * 16))
    view = WindowRingView(k=lines, v=lines, context_len=jnp.zeros((1,), jnp.int32),
                          new_len=jnp.full((1,), 8, jnp.int32))
    q = jnp.zeros((1, 8, 9, 16))
    with pytest.raises(ValueError, match="a ring of 8 lines under rows of up to 8"):
        mixer._serve(q, jnp.zeros((1, 8, 3, 16)), jnp.zeros((1, 8, 3, 16)), view,
                     laguna._make_ctx())


def test_per_head_windows_are_refused_before_the_engine_traces_anything():
    """The homogeneous stack's per-head local windows are not built on the
    paged kernel: the engine refuses the configuration by name, where it used
    to assert inside the trace."""
    config = TransformerConfig.from_dict({
        "topology": TOPOLOGY,
        "transformer_architecture": {
            "vocab_size": VOCAB, "hidden_size": 32, "num_layers": 1,
            "num_attention_heads": 4, "num_local_attention_heads": 2,
            "local_attention_window_size": 4, "sequence_length": 32},
        "data": {}, "logger": {"log_dir": None}})
    module = init_model(config, None)
    inf = TransformerInferenceModule(
        config, module, module.init_params(jax.random.PRNGKey(0)))
    with pytest.raises(ValueError, match="num_local_attention_heads with the "
                       "paged serving engine"):
        ServeEngine(inf, EngineConfig(num_slots=1))
