"""A stack of Qwen3-Next layers (``layer_pattern``: a gated delta-rule mixer or
gated full attention with heads of 256 lanes, rotary on 64 of them, a gate a
lane and a q / k norm whose weight is an offset from one; then softmax-routed
experts with a shared expert behind a gate) through ``ServeEngine`` against
the benchmark's plain reference (``benchmark/reference/gdn_moe_decoder.py``,
whose recurrence is the STEP): prefill in chunks then decode on LOGITS, rows
that step beside rows that chunk, a slot reused, a preempted row recomputed;
a bfloat16 state fails the comparison; the attention layer, the shared gate
and the eight shares of a routed layer alone; the published parameter count;
what is refused, by name; the spans' fields, the counters and the stats."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import cells
from scaling_tpu import obs
from scaling_tpu.models.transformer import TransformerConfig
from scaling_tpu.models.transformer.inference import TransformerInferenceModule
from scaling_tpu.models.transformer.model import init_model
from scaling_tpu.nn.base_layer import ForwardContext
from scaling_tpu.nn.gated_delta import DeltaStateView
from scaling_tpu.serve.engine import EngineConfig, ServeEngine

from . import reference_walk

VOCAB, HIDDEN, CHUNK = 96, 64, 8
BRANCH_SCALE = 4.0
# one period of the published pattern: three delta layers to one attention
PATTERN = ["delta", "moe"] * 3 + ["attention", "moe"]
NK, NV, DK, DV = 2, 4, 16, 16
TOPOLOGY = {"model_parallel_size": 1, "pipe_parallel_size": 1,
            "data_parallel_size": 1, "micro_batch_size": 1,
            "gradient_accumulation_steps": 1}
ARCH = {"vocab_size": VOCAB, "hidden_size": HIDDEN, "num_layers": len(PATTERN),
        "layer_pattern": PATTERN,
        # the published attention head: 256 lanes, rotary on the first 64
        "num_attention_heads": 4, "attention_num_kv_heads": 2,
        "attention_head_dim": 256, "attention_qkv_in_one": False,
        "attention_bias": False, "attention_gate": "elementwise",
        "key_query_norm": True, "rotary_embedding_base": 10000000,
        "rotary_percentage": 0.25,
        "delta_num_key_heads": NK, "delta_num_value_heads": NV,
        "delta_key_head_dim": DK, "delta_value_head_dim": DV, "conv_kernel": 4,
        "mlp_type": "swiglu", "mlp_bias": False,
        "moe_num_experts": 16, "moe_top_k": 3, "moe_expert_width": 32,
        "moe_glu": True, "moe_router": "softmax", "moe_norm_topk_prob": True,
        "moe_shared_expert_width": 32, "moe_shared_expert_gate": True,
        "moe_experts_first": 0, "moe_experts_held": 4,
        "activation_function": "silu", "norm_type": "rms",
        "layernorm": {"layernorm_epsilon": 1e-6, "weight_offset": True},
        "relative_position_embedding_type": "rotary", "sequence_length": 128,
        "precision": "float32", "weight_tying": False}
DELTA_LAYERS = PATTERN.count("delta")


def gdn_config(topology=None, **arch):
    return TransformerConfig.from_dict({
        "topology": {**TOPOLOGY, **(topology or {})},
        "transformer_architecture": {**ARCH, **arch},
        "data": {}, "logger": {"log_dir": None}})


@pytest.fixture(scope="module")
def gdn():
    config = gdn_config()
    module = init_model(config, None)
    params = module.init_params(jax.random.PRNGKey(3))
    # away from the init: norm weights off their start (zeros: the offset from
    # one), decays and time steps that differ by head; branches that are no
    # small steps, a router that chooses, a shared gate that differs by token
    leaves, treedef = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(4), len(leaves))
    params = jax.tree.unflatten(treedef, [
        x + (0.2 * jax.random.normal(k, x.shape) if x.ndim == 1 else 0.0)
        for x, k in zip(leaves, keys)])
    for i in range(1, len(PATTERN) + 1):
        mixer = params[f"layer_{i}"]["mixer"]
        for out in ("dense", "out_proj"):
            if out in mixer:
                mixer[out]["weight"] = BRANCH_SCALE * mixer[out]["weight"]
        for out in ("w_out", "shared_out"):
            if out in mixer:
                mixer[out] = BRANCH_SCALE * mixer[out]
        if "router" in mixer:
            mixer["router"]["weight"] = 20 * mixer["router"]["weight"]
            mixer["shared_scale"] = 8 * mixer["shared_scale"]
    return TransformerInferenceModule(config, module, params)


@pytest.fixture(scope="module")
def reference():
    return (cells.load_module(cells.ROOT, "reference", "gdn_moe_decoder",
                              cells.REFERENCE_CONTRACT),
            cells.load_module(cells.ROOT, "views", "gdn_moe_decoder",
                              cells.VIEW_CONTRACT))


def prompts(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, VOCAB, size=n).tolist() for n in lengths]


TOKENS = prompts((44,), seed=5)[0]


def by_reference(inf, reference, tokens):
    ref, view = reference
    return np.asarray(ref.forward(view.reference_weights(inf.params, ARCH),
                                  jnp.asarray(tokens), view.reference_spec(ARCH)))


@pytest.fixture(scope="module")
def wanted(gdn, reference):
    """The reference's full forward over ``TOKENS``: logits at every position."""
    return by_reference(gdn, reference, TOKENS)


def engine_of(inf, **config):
    return ServeEngine(inf, EngineConfig(**{
        "num_slots": 4, "block_size": 4, "num_blocks": 4 * 16 + 1,
        "max_blocks_per_seq": 16, "token_budget": 64, "prefill_chunk": CHUNK,
        "enable_prefix_cache": False, **config}))


def one_row_engine(inf):
    return engine_of(inf, num_slots=1, num_blocks=64 // 4 + 1,
                     max_blocks_per_seq=64 // 4)


walk = reference_walk.paged_walk

# float32 on both sides, and two forms of one recurrence: the served rows run
# the chunk form (a triangular system a head) and the single step with its
# read-out taken before the write, the reference a scan of the plain step.
# What separates them is the order of float32 sums through 8 layers whose
# branches are BRANCH_SCALE times their init's: a few 1e-5 of logits whose
# deviation is ~1. A state kept in bfloat16 moves them by 100 times this
# (test_a_bfloat16_state_fails_the_comparison)
LOGIT_ATOL = 3e-4
# two chunks of prefill, a ragged one, then decode one by one
PREFILL_THEN_DECODE = [CHUNK] * 2 + [5] + [1] * (len(TOKENS) - 2 * CHUNK - 5)


@pytest.mark.parametrize("paged_kernel", ["xla", "pallas"])
def test_chunks_then_decode_are_the_references_full_forward(
        gdn, wanted, paged_kernel):
    got, state = walk(gdn, one_row_engine(gdn), TOKENS, PREFILL_THEN_DECODE,
                      paged_kernel)
    assert got.shape == wanted.shape == (len(TOKENS), VOCAB)
    np.testing.assert_allclose(got, wanted, atol=LOGIT_ATOL)
    assert wanted.std() > 0.3    # the logits say something
    # the state: one pool (the attention layer, lines of 2 x 256), three
    # float32 states and three conv tails a slot, a tap a plane of 128 lanes
    assert len(state[0]) == 1 and state[0][0].shape[2:] == (2, 256)
    assert [a.shape for a in state[4]] == [(1, NV, DK, DV)] * DELTA_LAYERS
    assert [a.shape for a in state[5]] == [
        (1, 3, (2 * NK * DK + NV * DV) // 128, 128)] * DELTA_LAYERS
    assert all(a.dtype == jnp.float32 for a in state[4])


def test_the_uncached_pass_is_the_references_too(gdn, wanted):
    ids = jnp.asarray(TOKENS, jnp.int32)[None]
    batch = gdn._make_batch(ids, jnp.arange(len(TOKENS), dtype=jnp.int32)[None])
    got = np.asarray(jax.jit(
        lambda p: gdn._run_layers(p, batch, None, None)[0])(gdn.params)[0])
    np.testing.assert_allclose(got, wanted, atol=LOGIT_ATOL)


def test_a_slot_reused_after_a_longer_row_sees_nothing_of_it(gdn, reference):
    """A row of 44 tokens leaves its slot's states and tails written; the next
    row of the same slot starts at context 0 and the program starts it from
    zeros: no reset by the host."""
    engine = one_row_engine(gdn)
    _, state = walk(gdn, engine, TOKENS, PREFILL_THEN_DECODE, "pallas")
    assert float(jnp.abs(state[4][0]).max()) > 0
    short = prompts((11,), seed=9)[0]
    got, _ = walk(gdn, engine, short, [CHUNK, 1, 1, 1], "pallas", state=state)
    np.testing.assert_allclose(got, by_reference(gdn, reference, short),
                               atol=LOGIT_ATOL)


def test_a_bfloat16_state_fails_the_comparison(gdn, wanted):
    """What the configuration forbids (the recurrent state is float32): the
    same walk over lines kept in bfloat16 moves the logits far past the
    tolerance; the benchmark's own check at its limit of 0.05 may not see it
    (PERF.md section 7), this one must."""
    engine = one_row_engine(gdn)
    state = list(engine._pool_state())
    state[4] = [a.astype(jnp.bfloat16) for a in state[4]]
    got, after = walk(gdn, engine, TOKENS, PREFILL_THEN_DECODE, "pallas",
                      state=tuple(state))
    assert all(a.dtype == jnp.bfloat16 for a in after[4])
    assert np.abs(got - wanted).max() > 30 * LOGIT_ATOL


REQUESTS = prompts((9, 37, 14, 3, 21, 6), seed=2)
NEW_TOKENS = 20


def served_by(engine, requests, new_tokens):
    """Every request the engine was given, these after any before, in the
    order of submission."""
    for p in requests:
        engine.submit(p, max_new_tokens=new_tokens)
    got = {s.request.req_id: s.generated for s in engine.run_until_done()}
    return [got[i] for i in range(len(got))]


def assert_tokens_are_the_references(inf, reference, requests, got):
    """Every token the engine emitted is within the tolerance of the
    reference's best at its position, teacher-forced through the reference's
    full forward."""
    ref, view = reference
    weights = view.reference_weights(inf.params, ARCH)
    spec = view.reference_spec(ARCH)
    longest = max(map(len, requests)) + NEW_TOKENS
    for p, out in zip(requests, got):
        assert len(out) == NEW_TOKENS
        tokens = np.zeros((longest,), np.int32)
        tokens[:len(p) + NEW_TOKENS - 1] = list(p) + out[:-1]
        at = np.arange(len(p) - 1, len(p) - 1 + NEW_TOKENS)
        logits = np.asarray(ref.forward(weights, jnp.asarray(tokens), spec,
                                        head_positions=jnp.asarray(at)))
        picked = logits[np.arange(NEW_TOKENS), out]
        assert (logits.max(-1) - picked).max() < LOGIT_ATOL


@pytest.fixture(scope="module")
def served(gdn, tmp_path_factory):
    """ONE engine of four slots serving six ``REQUESTS`` under a capture:
    prefill in chunks of 8 whose edges fall mid-prompt beside rows that
    decode, token-major at both widths; two requests wait for a slot and
    reuse one."""
    engine = engine_of(gdn)
    obs.start_capture(str(tmp_path_factory.mktemp("capture")))
    try:
        got = served_by(engine, REQUESTS, NEW_TOKENS)
    finally:
        capture = obs.stop_capture()
    return engine, got, capture


def test_the_engine_serves_what_the_references_full_forward_gives(
        gdn, reference, served):
    engine, got, _ = served
    assert_tokens_are_the_references(gdn, reference, REQUESTS, got)
    assert len({tuple(out) for out in got}) > 1     # the weights say something
    stats = engine.stats_snapshot()
    assert stats["state_lines"] == DELTA_LAYERS and stats["kv_lines"] == 1
    assert stats["line_layers"] == {"delta": DELTA_LAYERS}
    assert stats["state_pool_bytes"] == DELTA_LAYERS * 4 * (
        NV * DK * DV * 4 + (2 * NK * DK + NV * DV) * 3 * 4)
    assert engine.line_layers == engine.split_lines == {"delta": DELTA_LAYERS}
    assert engine.ssm_lines == 0


def test_a_preempted_row_is_recomputed_from_zeros(gdn, reference, served):
    """A pool too small for the rows forces recompute-style preemption: the
    resumed sequence re-enters at context 0, the program starts its lines
    from zeros, and its tokens are the ones an undisturbed engine gave."""
    _, want, _ = served
    engine = engine_of(gdn, num_blocks=25)
    got = served_by(engine, REQUESTS, NEW_TOKENS)
    assert engine.scheduler.preemption_count > 0
    assert got == want


def test_rows_that_step_beside_rows_that_chunk_and_the_ticks_say_so(
        gdn, reference, tmp_path):
    """16 slots x chunk 32 build two programs; the one of 128 places advances
    one-token rows by the single step and gathers up to 4 chunk rows. Six
    short prompts and a long one arrive at once: the first tick holds 7 chunk
    rows, which do not fit 4, so it runs whole rows at the full width; the
    long prompt's second chunk then rides beside six rows that step in the
    small program. Every token is the reference's; ``serve.mixed`` carries the
    delta layers, the rows whose lines advanced and how many brought one token
    or more, and the counters sum them over the layers by the form that ran."""
    requests = prompts((3, 4, 5, 6, 7, 8, 40), seed=5)
    engine = engine_of(gdn, num_slots=16, prefill_chunk=32, token_budget=128,
                       max_blocks_per_seq=16, num_blocks=16 * 16 + 1)
    assert engine.config.mixed_widths == (128, 512)
    obs.start_capture(str(tmp_path))
    try:
        got = served_by(engine, requests, NEW_TOKENS)
    finally:
        capture = obs.stop_capture()
    assert_tokens_are_the_references(gdn, reference, requests, got)
    mixed = [f for n, _, _, f in capture.spans if n == "serve.mixed"]
    assert all(f["delta_lines"] == DELTA_LAYERS for f in mixed)
    assert all(f["delta_step_rows"] + f["delta_chunk_rows"] == f["delta_rows"]
               for f in mixed)
    assert not any("ssm_rows" in f for f in mixed)
    assert [(f["width"], f["tokens"], f["delta_step_rows"], f["delta_chunk_rows"])
            for f in mixed[:3]] == [(512, 65, 0, 7), (128, 14, 6, 1), (128, 7, 7, 0)]
    by_path = {path: capture.counters[f"serve_delta_rows_total{{path={path}}}"]
               for path in ("step", "chunk", "whole")}
    assert by_path == {
        "whole": DELTA_LAYERS * 7, "chunk": DELTA_LAYERS * 1,
        "step": DELTA_LAYERS * sum(f["delta_step_rows"] for f in mixed[1:])}
    assert sum(by_path.values()) == capture.counters[
        "serve_delta_state_updates_total"]
    assert not any(k.startswith("serve_ssm_rows_total") for k in capture.counters)


def test_step_rows_beside_several_chunk_rows_and_empty_slots_are_the_references(
        gdn, reference, tmp_path):
    """The program of 128 places lists up to 4 chunk rows for the delta
    layers' kernel (``delta_chunk_rows``). Three short prompts fill three of
    its places, the fourth and 13 slots empty; while they decode, two long
    prompts arrive and bring two chunks a tick beside three rows that step:
    more chunk rows than one, fewer than the list holds, step rows and empty
    slots in one tick. Every token is the reference's."""
    requests = prompts((3, 5, 4, 44, 51), seed=7)
    engine = engine_of(gdn, num_slots=16, prefill_chunk=32, token_budget=128,
                       max_blocks_per_seq=24, num_blocks=16 * 24 + 1)
    assert engine.config.mixed_widths == (128, 512)
    obs.start_capture(str(tmp_path))
    try:
        for p in requests[:3]:
            engine.submit(p, max_new_tokens=NEW_TOKENS)
        for _ in range(3):
            engine.tick()
        got = served_by(engine, requests[3:], NEW_TOKENS)     # all five
    finally:
        capture = obs.stop_capture()
    mixed = [f for n, _, _, f in capture.spans if n == "serve.mixed"]
    assert {f["width"] for f in mixed} == {128}
    shapes = [(f["delta_step_rows"], f["delta_chunk_rows"]) for f in mixed]
    assert shapes[0] == (0, 3)
    assert (3, 2) in shapes
    assert_tokens_are_the_references(gdn, reference, requests, got)


def test_the_attention_layer_is_the_references(gdn, reference):
    """Heads of 256 lanes, rotary on the first 64, q and k normed with an
    offset-from-one weight, a gate a lane out of the doubled query
    projection: the program's mixer against the reference's layer, and the
    gate skipped is seen."""
    ref, view = reference
    layer = gdn.module.layers[7]
    p = gdn.module._layer_params(gdn.params, 7)["mixer"]
    x = jax.random.normal(jax.random.PRNGKey(6), (1, 24, HIDDEN))
    pos = jnp.arange(24, dtype=jnp.int32)[None]
    got = jax.jit(lambda p, x: layer.mixer(
        p, x, ForwardContext(), position_ids=pos))(p, x)
    weights = {"q": p["query"]["weight"], "k": p["key"]["weight"],
               "v": p["value"]["weight"], "o": p["dense"]["weight"],
               "q_norm": p["norm_query"]["weight"],
               "k_norm": p["norm_key"]["weight"]}
    spec = view.reference_spec(ARCH)
    assert (spec["head_dim"], spec["rope_dims"]) == (256, 64)
    with jax.default_matmul_precision("highest"):
        want = ref.attention_parts(x[0], weights, spec)
        ungated = ref.attention_parts(x[0], weights, spec, gated=False)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want), atol=2e-5)
    assert np.abs(np.asarray(ungated) - np.asarray(want)).max() > 0.05
    assert p["query"]["weight"].shape == (HIDDEN, 4 * 2 * 256)
    assert float(jnp.abs(p["norm_query"]["weight"]).max()) < 1.0  # an offset


def routed(first, held, E=64, K=10, H=64, F=32):
    from scaling_tpu.nn.moe import ParallelMoEMLP

    return ParallelMoEMLP(
        io_features=H, intermediate_feature_factor=1.0, num_experts=E, top_k=K,
        norm_topk_prob=True, glu=True, intermediate=F, router="softmax",
        shared_expert_width=F, shared_expert_gate=True, experts_first=first,
        experts_held=held)


def test_eight_shares_of_the_routed_layer_add_up_to_the_uncut_layer(reference):
    """The published layer's form at a small size: 64 softmax-routed experts,
    10 a token, the ten renormalised, a shared expert times sigmoid(x w_s),
    held whole against the same layer as 8 ranks of 8 experts each (the router
    keeps its 64 outputs and its 10 a token; absent experts' gates are left
    out AFTER the renormalisation): the ranks' routed parts plus the gated
    shared expert ONCE are the whole layer. In the reference, and in the
    program's ``serve``."""
    ref, _ = reference
    E, HELD = 64, 8
    whole = routed(0, E)
    params = whole.init(jax.random.PRNGKey(0))
    params["router"]["weight"] = 20 * params["router"]["weight"]
    params["shared_scale"] = 8 * params["shared_scale"]
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 24, 64))

    def rank_params(first, held):
        p = dict(params)
        for leaf in ("w_in", "w_out", "w_gate"):
            p[leaf] = params[leaf][first:first + held]
        return p

    def as_reference(p):
        return {"router": p["router"]["weight"], "shared_gate": p["shared_gate"],
                "shared_up": p["shared_in"], "shared_down": p["shared_out"],
                "shared_scale": p["shared_scale"]}, {
                "w_gate": p["w_gate"], "w_up": p["w_in"], "w_down": p["w_out"]}

    spec = {"top_k": 10, "experts_first": 0, "shared": True}
    with jax.default_matmul_precision("highest"):
        p, experts = as_reference(params)
        want = ref.routed_ffn(x[0], p, experts, spec)
        shared = ref.shared_expert(x[0], p)
        # the gate differs by token, and it matters
        scale = jax.nn.sigmoid(x[0] @ p["shared_scale"])
        assert float(scale.max() - scale.min()) > 0.3
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
        ranks = [routed(first, HELD).serve(rank_params(first, HELD), x)[0][0]
                 for first in range(0, E, HELD)]
        np.testing.assert_allclose(sum(ranks) - 7 * shared, want, atol=2e-4)


def test_the_shared_gate_is_a_flag_and_off_by_default():
    from scaling_tpu.nn.moe import ParallelMoEMLP

    plain = ParallelMoEMLP(io_features=16, intermediate_feature_factor=1.0,
                           num_experts=4, intermediate=8, shared_expert_width=8)
    assert "shared_scale" not in plain.init(jax.random.PRNGKey(0))
    gated = routed(0, 4, E=4, K=2, H=16, F=8)
    params = gated.init(jax.random.PRNGKey(0))
    assert params["shared_scale"].shape == (16, 1)
    assert jax.tree.structure(params) == jax.tree.structure(gated.param_metas())
    # the leaves both have are the same values: the gate's key is its own
    for name, leaf in plain.init(jax.random.PRNGKey(0)).items():
        if name != "router":
            assert leaf.shape == params[name].shape


def test_the_published_sizes_count_the_published_parameters():
    """Depth 48, 512 experts held whole and the whole vocabulary: the tree
    counts 79,674,391,296 parameters, the released ~80B with the
    multi-token-prediction module left out. Reckoned from the widths: a delta
    mixer 2,048 x 12,288 + 2,048 x 64 + 8,192 x 4 + 32 + 32 + 128 + 4,096 x
    2,048 = 33,718,464; an attention mixer 2,048 x 8,192 + 2 x 2,048 x 512 +
    2 x 256 + 4,096 x 2,048 = 27,263,488; a routed MLP 512 x 3 x 2,048 x 512 +
    2,048 x 512 (router) + 3 x 2,048 x 512 + 2,048 (shared and its gate) =
    1,614,809,088; two norms a layer 4,096; 36 delta layers and 12 attention
    layers; embedding and head 2 x 151,936 x 2,048 and the final norm."""
    from benchmark import model as bench_model

    config = cells.load_json(
        cells.ROOT / "configs" / "qwen3-next-80b-a3b-serve.json")
    arch = dict(config["transformer_architecture"])
    published = config["published"]
    layers = published["num_hidden_layers"]
    period = arch["layer_pattern"][:8]
    arch.update(
        num_layers=2 * layers, layer_pattern=period * (layers // 4),
        vocab_size=published["vocab_size"], moe_experts_first=0,
        moe_experts_held=published["num_experts"],
        sequence_length=2048)
    module = init_model(bench_model.transformer_config(
        {**config, "transformer_architecture": arch}, {}), None)
    total = bench_model.count_params(bench_model.param_shapes(module))
    delta = 2048 * 12288 + 2048 * 64 + 8192 * 4 + 32 + 32 + 128 + 4096 * 2048
    attention = 2048 * 8192 + 2 * 2048 * 512 + 2 * 256 + 4096 * 2048
    moe = 512 * 3 * 2048 * 512 + 2048 * 512 + 3 * 2048 * 512 + 2048
    want = (36 * delta + 12 * attention + 48 * (moe + 2 * 2048)
            + 2 * 151936 * 2048 + 2048)
    assert total == want == published["parameter_count"] == 79_674_391_296
    # and the cell's own tree is the count its file states
    cell = init_model(bench_model.transformer_config(config, {}), None)
    assert bench_model.count_params(bench_model.param_shapes(cell)) == (
        config["parameter_count"])


@pytest.mark.parametrize("arch, message", [
    ({"delta_num_key_heads": None}, "needs \\['delta_num_key_heads'\\]"),
    ({"delta_num_value_heads": 3}, "is not a multiple of delta_num_key_heads"),
    ({"layer_pattern": ["delta", "moe"] * 3 + ["mamba", "moe"]},
     "'delta' layers beside 'mamba' layers"),
    ({"layer_pattern": ["delta", "moe"] * 3 + ["conv", "moe"]},
     "'delta' layers beside 'conv' layers"),
    ({"layer_pattern": ["attention", "moe"] * 4}, "without 'delta' layers"),
    ({"layer_pattern": ["delta", "moe"] * 3 + ["window", "moe"],
      "window_size": 8}, "'delta' layers beside 'window' layers"),
    ({"hc_streams": 2}, "hc_streams"),
    ({"norm_type": "layernorm"}, "layernorm.weight_offset with norm_type"),
    ({"moe_shared_expert_width": None},
     "moe_shared_expert_gate without moe_shared_expert_width"),
    ({"attention_num_kv_heads": None, "attention_qkv_in_one": True},
     "attention_gate 'elementwise' with"),
])
def test_what_the_configuration_refuses_is_refused_by_name(arch, message):
    with pytest.raises(ValueError, match=message):
        gdn_config(**arch)


@pytest.mark.parametrize("topology, message", [
    ({"model_parallel_size": 2}, "layer_pattern with model_parallel_size 2"),
    ({"pipe_parallel_size": 2}, "layer_pattern with pipe_parallel_size 2"),
])
def test_what_the_layout_refuses_is_refused_by_name(topology, message):
    with pytest.raises(ValueError, match=message):
        gdn_config(topology=topology)


@pytest.mark.parametrize("engine, message", [
    ({"enable_prefix_cache": True},
     "keep a line a slot \\({'delta': 3}\\): a prefix hit .* lines never saw"),
    ({"kv_dtype": "int8"}, "kv_dtype 'int8' with 'delta' layers"),
])
def test_what_the_engine_refuses_is_refused_by_name(gdn, engine, message):
    with pytest.raises(ValueError, match=message):
        engine_of(gdn, **engine)


def test_training_and_a_dense_cache_are_refused_by_name(gdn):
    ids = jnp.asarray([TOKENS[:8]], jnp.int32)
    with pytest.raises(NotImplementedError,
                       match="layer_pattern stack is served, not trained"):
        gdn.module.forward(gdn.params, {"token_ids": ids}, gdn._make_ctx())
    with pytest.raises(ValueError, match="cached generate\\(\\) keeps dense KV"):
        gdn.generate(ids, max_tokens=2)
    layer = gdn.module.layers[1]
    assert layer.consumes is DeltaStateView
    with pytest.raises(ValueError, match="a delta layer takes a DeltaStateView"):
        layer(gdn.module._layer_params(gdn.params, 1),
              {"activations": jnp.zeros((1, 8, HIDDEN))}, gdn._make_ctx(),
              kv_cache=(jnp.zeros((1, 8, 2, 16)),) * 2)


def test_the_small_width_is_the_configurations_to_size():
    """``small_bucket_chunks``: how many prompts streaming a chunk each the
    small program holds beside a decode row a slot; 3 unless stated."""
    shape = dict(num_slots=256, prefill_chunk=32, enable_prefix_cache=False)
    assert EngineConfig(**shape).mixed_widths == (384, 8192)
    assert EngineConfig(**shape, small_bucket_chunks=16).mixed_widths == (768, 8192)
