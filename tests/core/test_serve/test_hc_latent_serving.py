"""A stack of Xing4.0 blocks (``layer_pattern``: latent attention, then a dense
or a sigmoid-routed FFN with every expert held, on a residual of FOUR streams
mixed by manifold-constrained hyper-connections, ``nn/hyper_connection.py``)
through ``ServeEngine``: the streams are activations and are never cached, a
token still leaves one latent line a layer; prefill in chunks then decode
through the paged pool against the plain reference's full forward, on logits;
the engine's greedy tokens; each part of the mapping seen by the comparison of
logits; the gather of the sampled positions; what is refused, by name; the
spans' new fields and the counter; the published model's parameter counts."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import cells, model as bench_model
from scaling_tpu import obs
from scaling_tpu.models.transformer import TransformerConfig
from scaling_tpu.models.transformer.inference import (
    TransformerInferenceModule, gather_positions,
)
from scaling_tpu.models.transformer.model import init_model
from scaling_tpu.nn import hyper_connection
from scaling_tpu.serve.engine import EngineConfig, ServeEngine

from . import reference_walk

VOCAB, HIDDEN, STREAMS = 128, 128, 4
BRANCH_SCALE = 4.0
PATTERN = ["latent", "mlp", "latent", "moe"]
TOPOLOGY = {"model_parallel_size": 1, "pipe_parallel_size": 1,
            "data_parallel_size": 1, "micro_batch_size": 1,
            "gradient_accumulation_steps": 1}
ARCH = {"vocab_size": VOCAB, "hidden_size": HIDDEN, "num_layers": len(PATTERN),
        "layer_pattern": PATTERN, "hc_streams": STREAMS, "hc_sinkhorn_iters": 20,
        "hc_eps": 1e-6, "hc_res_clamp_min": -30, "hc_res_clamp_max": 30,
        "num_attention_heads": 4, "q_lora_rank": 48, "kv_lora_rank": 64,
        "qk_nope_head_dim": 32, "qk_rope_head_dim": 16, "v_head_dim": 32,
        "rope_scaling": {"type": "yarn", "factor": 8,
                         "original_max_position_embeddings": 32, "beta_fast": 32,
                         "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1},
        "rotary_embedding_base": 10000, "attention_bias": False,
        "mlp_type": "swiglu", "mlp_factor": 2.5, "mlp_bias": False,
        "moe_num_experts": 8, "moe_top_k": 4, "moe_expert_width": 32,
        "moe_glu": True, "moe_router": "sigmoid_bias", "moe_norm_topk_prob": True,
        "moe_norm_topk_eps": 1e-20, "moe_routed_scaling_factor": 2.0,
        "moe_shared_expert_width": 32, "moe_experts_first": 0, "moe_experts_held": 8,
        "activation_function": "silu", "norm_type": "rms",
        "layernorm": {"layernorm_epsilon": 1e-6},
        "relative_position_embedding_type": "rotary", "sequence_length": 128,
        "precision": "float32", "weight_tying": False}


def xing_config(topology=None, **arch):
    return TransformerConfig.from_dict({
        "topology": {**TOPOLOGY, **(topology or {})},
        "transformer_architecture": {**ARCH, **arch},
        "data": {}, "logger": {"log_dir": None}})


@pytest.fixture(scope="module")
def xing():
    config = xing_config()
    module = init_model(config, None)
    params = module.init_params(jax.random.PRNGKey(3))
    # away from the init: norms off one, a selection bias that says something
    # (the mappings' leaves are float32 vectors and matrices of their own init)
    leaves, treedef = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(4), len(leaves))
    params = jax.tree.unflatten(treedef, [
        x + (0.2 * jax.random.normal(k, x.shape) if x.ndim == 1 and x.size > 3 else 0.0)
        for x, k in zip(leaves, keys)])
    # and branches that are no small steps (the seeded init starts every
    # mixer's output projection at 1 / (2 sqrt(layers)) of its Xavier scale):
    # the streams then differ by as much as they hold, and how H_res mixes
    # them shows in the logits of a stack of four sub-layers
    for i in range(1, len(PATTERN) + 1):
        mixer = params[f"layer_{i}"]["mixer"]
        for out in ("dense", "down_proj"):
            if out in mixer:
                mixer[out]["weight"] = BRANCH_SCALE * mixer[out]["weight"]
        for out in ("w_out", "shared_out"):
            if out in mixer:
                mixer[out] = BRANCH_SCALE * mixer[out]
        # a row of S with TWO entries beyond the clamp in every mapping: the
        # clamp ties them (the seeded draw has such a row in 7 of 10 mappings)
        hc = params[f"layer_{i}"]["hc"]
        hc["bias"] = hc["bias"].at[2 * STREAMS:3 * STREAMS].set(
            jnp.array([45.0, 38.0, -10.0, -20.0]))
    return TransformerInferenceModule(config, module, params)


@pytest.fixture(scope="module")
def reference():
    return (cells.load_module(cells.ROOT, "reference", "hc_latent_moe_decoder",
                              cells.REFERENCE_CONTRACT),
            cells.load_module(cells.ROOT, "views", "hc_latent_moe_decoder",
                              cells.VIEW_CONTRACT))


def prompts(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, VOCAB, size=n).tolist() for n in lengths]


TOKENS = prompts((28,), seed=5)[0]


@pytest.fixture(scope="module")
def wanted(xing, reference):
    """The reference's full forward over ``TOKENS``: logits at every position."""
    ref, view = reference
    return np.asarray(ref.forward(view.reference_weights(xing.params, ARCH),
                                  jnp.asarray(TOKENS), view.reference_spec(ARCH)))


def engine_of(inf, **config):
    return ServeEngine(inf, EngineConfig(**{
        "num_slots": 4, "block_size": 4, "num_blocks": 4 * 16 + 1,
        "max_blocks_per_seq": 16, "token_budget": 64, "prefill_chunk": 8,
        "enable_prefix_cache": False, **config}))


def paged_logits(inf, tokens, chunk, paged_kernel="xla"):
    """``reference_walk.paged_logits`` through a pool of one row of 64 slots."""
    engine = engine_of(inf, num_slots=1, num_blocks=64 // 4 + 1,
                       max_blocks_per_seq=64 // 4)
    return reference_walk.paged_logits(inf, engine, tokens, chunk, paged_kernel)


# float32 on both sides: what separates the served form (absorbed attention
# over the pool, the token axis minor in the mappings) from the reference's
# full forward is the order of float32 sums
LOGIT_ATOL = 1e-4


def test_chunks_then_decode_through_the_pool_are_the_references_full_forward(
        xing, wanted):
    got = paged_logits(xing, TOKENS, 8)
    assert got.shape == wanted.shape == (len(TOKENS), VOCAB)
    np.testing.assert_allclose(got, wanted, atol=LOGIT_ATOL)
    assert wanted.std() > 0.3    # the logits say something


sinkhorn_tokens = hyper_connection.sinkhorn_tokens
real_post = hyper_connection.HyperConnection.post


def one_step(s, *, n, iters, eps, interpret):
    return sinkhorn_tokens(s, n=n, iters=1, eps=eps, interpret=interpret)


def transposed(self, x, y, mix):
    n = self.n
    mix = jnp.concatenate([
        mix[:, :n * n].reshape(-1, n, n).swapaxes(1, 2).reshape(-1, n * n),
        mix[:, n * n:]], axis=1)
    return real_post(self, x, y, mix)


def without_the_factor(self, x, y, mix):
    return real_post(self, x, y, mix.at[:, self.n ** 2:].multiply(0.5))


@pytest.mark.parametrize("mutation", [
    "one Sinkhorn step for 20", "a transposed H_res", "a dropped clamp",
    "H_post without its factor 2"])
def test_each_part_of_the_mapping_moves_the_logits_past_the_tolerance(
        xing, wanted, monkeypatch, mutation):
    """The comparison of logits sees the mechanism: at the seeded mappings
    (``b_res`` over +-60: the clamp acts, 20 steps are far from the limit)
    each wrong form moves a logit by orders of magnitude more than the
    tolerance, and by more than the benchmark's 0.05."""
    if mutation == "one Sinkhorn step for 20":
        monkeypatch.setattr(hyper_connection, "sinkhorn_tokens", one_step)
    elif mutation == "a transposed H_res":
        monkeypatch.setattr(hyper_connection.HyperConnection, "post", transposed)
    elif mutation == "a dropped clamp":
        for layer in xing.module.layers:
            if getattr(layer, "hc", None) is not None:
                monkeypatch.setattr(layer.hc, "clamp", (-1e9, 1e9))
    else:
        monkeypatch.setattr(hyper_connection.HyperConnection, "post",
                            without_the_factor)
    got = uncached_logits(xing)
    assert np.abs(got - wanted).max() > 0.05 > 100 * LOGIT_ATOL


def uncached_logits(inf):
    """The uncached pass over ``TOKENS``, traced anew on every call (the
    module's own ``logits`` keeps its first trace)."""
    ids = jnp.asarray(TOKENS, jnp.int32)[None]
    batch = inf._make_batch(ids, jnp.arange(len(TOKENS), dtype=jnp.int32)[None])
    return np.asarray(jax.jit(
        lambda p: inf._run_layers(p, batch, None, None)[0])(inf.params)[0])


def test_the_uncached_pass_is_the_references_too(xing, wanted):
    np.testing.assert_allclose(uncached_logits(xing), wanted, atol=LOGIT_ATOL)


REQUESTS = prompts((9, 21, 14), seed=2)
NEW_TOKENS = 4


@pytest.fixture(scope="module")
def served(xing, tmp_path_factory):
    """ONE engine serving ``REQUESTS`` under a capture: prefill in chunks of 8
    whose edges fall mid-prompt, three rows at once, then decode."""
    engine = engine_of(xing)
    obs.start_capture(str(tmp_path_factory.mktemp("capture")))
    try:
        for p in REQUESTS:
            engine.submit(p, max_new_tokens=NEW_TOKENS)
        got = {s.request.req_id: s.generated for s in engine.run_until_done()}
    finally:
        capture = obs.stop_capture()
    return engine, [got[i] for i in range(len(REQUESTS))], capture


def test_the_engine_serves_what_the_references_full_forward_gives(
        xing, reference, served):
    """Ticks mix chunk rows and decode rows, token-major, and the readout runs
    on the gathered positions: every token the engine emitted is the
    reference's best at its position, teacher-forced through the reference's
    full forward (one padded length: attention is causal), by a margin."""
    ref, view = reference
    engine, got, _ = served
    weights = view.reference_weights(xing.params, ARCH)
    spec = view.reference_spec(ARCH)
    longest = max(map(len, REQUESTS)) + NEW_TOKENS
    for p, out in zip(REQUESTS, got):
        assert len(out) == NEW_TOKENS
        tokens = np.zeros((longest,), np.int32)
        tokens[:len(p) + NEW_TOKENS - 1] = list(p) + out[:-1]
        at = np.arange(len(p) - 1, len(p) - 1 + NEW_TOKENS)
        logits = np.asarray(ref.forward(weights, jnp.asarray(tokens), spec,
                                        head_positions=jnp.asarray(at)))
        assert logits.argmax(-1).tolist() == out
        top2 = np.sort(logits, axis=-1)[:, -2:]
        assert (top2[:, 1] - top2[:, 0]).min() > 1e-3
    assert len({tuple(out) for out in got}) > 1     # the weights say something
    # the streams are activations: the pool is one latent line a token a layer
    pools, stats = engine.pools, engine.stats_snapshot()
    assert pools.kv_lines == stats["kv_lines"] == PATTERN.count("latent")
    assert [a.shape for a in pools.pool_k] == [(65, 4, 64)] * 2
    assert (stats["hc_streams"], stats["hc_sublayers"]) == (STREAMS, len(PATTERN))


def test_the_gather_of_the_sampled_positions_keeps_what_follows_the_tokens():
    """``gather_positions`` flattens the two token axes alone. The served
    stream is ``(batch, seq, streams * hidden)``, one width; were a trunk
    ``(batch, seq, streams, hidden)``, flattening down to the last axis would
    give rows of OTHER positions without an error."""
    a = jnp.arange(2 * 3 * 4 * 5).reshape(2, 3, 4, 5)
    index = jnp.array([[4, 1], [5, 0]])
    got = gather_positions(a, index)
    assert got.shape == (2, 2, 4, 5)
    np.testing.assert_array_equal(got[0, 0], a[1, 1])
    np.testing.assert_array_equal(got[1, 0], a[1, 2])
    flattened = a.reshape(-1, a.shape[-1])[index]
    assert flattened.shape == (2, 2, 5)      # a stream of another position
    np.testing.assert_array_equal(flattened[0, 0], a[0, 1, 0])
    # one width: what it always was
    plain = a.reshape(2, 3, 20)
    np.testing.assert_array_equal(
        gather_positions(plain, index), plain.reshape(-1, 20)[index])


# ---- refused by name -------------------------------------------------------

@pytest.mark.parametrize("arch,message", [
    ({"layer_pattern": None, "rope_scaling": None, "num_layers": 2},
     "hc_streams > 1 without layer_pattern"),
    ({"loop_steps": 2}, "hc_streams > 1 with loop_steps > 1"),
    ({"hc_res_clamp_min": 30, "hc_res_clamp_max": -30},
     "hc_res_clamp_min 30.0 is not under hc_res_clamp_max -30.0"),
    ({"hc_streams": 0}, "hc_streams"),
    ({"hc_sinkhorn_iters": 0}, "hc_sinkhorn_iters"),
])
def test_a_stack_the_mapping_is_not_built_for_is_refused_by_name(arch, message):
    with pytest.raises(ValueError, match=message):
        xing_config(**arch)


def test_training_and_pipeline_stages_are_refused_by_name(xing):
    from scaling_tpu.nn.base_layer import ForwardContext

    with pytest.raises(NotImplementedError, match="layer_pattern stack is served"):
        xing.module.forward(xing.params, {}, ForwardContext())
    with pytest.raises(ValueError, match="layer_pattern with pipe_parallel_size 2"):
        xing_config({"pipe_parallel_size": 2})


def test_a_stack_without_streams_builds_no_mapping():
    """``hc_streams`` 1 (the default) is the plain residual: no leaf, no
    field, the layer's two branches of old."""
    config = xing_config(hc_streams=1)
    module = init_model(config, None)
    shapes = jax.eval_shape(module.init_params, jax.random.PRNGKey(0))
    assert all(set(shapes[f"layer_{i + 1}"]) == {"norm", "mixer"}
               for i in range(len(PATTERN)))
    assert set(shapes[f"layer_{len(PATTERN) + 1}"]) == {"norm"}
    assert all(layer.hc is None for layer in module.layers[1:len(PATTERN) + 1])


# ---- spans, counters, scopes -----------------------------------------------

def test_spans_and_the_counter_of_a_hyper_connected_model(served):
    _, got, capture = served
    mixed = [f for n, _, _, f in capture.spans if n == "serve.mixed"]
    assert mixed and all((f["hc_streams"], f["hc_sublayers"]) == (4, 4) for f in mixed)
    # every prompt token and every emitted token but a request's last is a
    # token of some tick: real tokens x sub-layers
    tokens = sum(map(len, REQUESTS)) + len(REQUESTS) * (NEW_TOKENS - 1)
    assert capture.counters["serve_hc_token_sublayers_total"] == 4 * sum(
        f["tokens"] for f in mixed) == 4 * tokens


def test_the_mappings_lie_in_the_hc_scope_beside_the_mixers(xing):
    """``hc`` names the instructions compiled from a mapping's ``pre`` and
    ``post`` and from the readout, the Sinkhorn kernel among them, beside and
    never inside ``attn`` / ``mlp`` / ``moe``: what the benchmark's readers
    look up, and why ``latent_time_pct`` and ``moe_time_pct`` keep their
    meaning."""
    batch = xing._make_batch(jnp.ones((1, 8), jnp.int32), jnp.arange(8)[None])
    text = jax.jit(lambda p: xing._run_layers(p, batch, None, None)[0]).lower(
        xing.params).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', text))
    inside = {n for n in names if re.search(r"(^|/)hc(/|$)", n)}
    assert any("dot_general" in n for n in inside)
    assert any("hc_sinkhorn" in n for n in inside)
    assert not any(re.search(r"/(attn|mlp|moe)/", n) for n in inside)
    assert any("/attn/" in n for n in names) and any("/moe/" in n for n in names)


# ---- the published model, by its shapes ------------------------------------

def test_the_published_widths_give_the_models_parameters():
    """29.5 B at depth 40 (two dense blocks, 38 routed), 4,792,727,177 at the
    cell's cut (one dense block, five routed); abstract shapes, nothing is
    made."""
    cell = cells.load_json(cells.ROOT / "configs" / "xing4.0-29b-a4b-serve.json")

    def count(blocks, dense):
        pattern = ["latent", "mlp"] * dense + ["latent", "moe"] * (blocks - dense)
        config = dict(cell, transformer_architecture={
            **cell["transformer_architecture"], "layer_pattern": pattern,
            "num_layers": len(pattern)})
        module = init_model(bench_model.transformer_config(config, {}), None)
        return bench_model.count_params(bench_model.param_shapes(module))

    mapping, readout = 344_091, 57_349
    attention = 28_411_136
    dense = attention + 2 * 3584 + 2 * mapping + 3 * 3584 * 9216
    routed = (attention + 2 * 3584 + 2 * mapping + 3584 * 64 + 64
              + 65 * 3 * 3584 * 1024)
    ends = 2 * 131_072 * 3584 + 3584 + readout
    assert (dense, routed) == (128_196_918, 744_989_046)
    assert count(6, 1) == dense + 5 * routed + ends == 4_792_727_177
    whole = count(40, 2)
    assert whole == 2 * dense + 38 * routed + ends == cell["published"]["parameter_count"]
    assert 29.4e9 < whole < 29.6e9
