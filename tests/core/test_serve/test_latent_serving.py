"""A stack of Kimi-K2 blocks (``layer_pattern``: multi-head LATENT attention
with YaRN, then a dense or a sigmoid-routed SwiGLU FFN with a shared expert,
4 of 16 experts held; an untied head) through ``ServeEngine``: one latent line
a token a layer in the paged pool, two leaves of unequal width and no head
axis; prefill chunks then decode in the ABSORBED form against the plain
reference's full forward in the EXPANDED form, on logits; a sequence preempted
and recomputed; what is refused, by name; the spans' new fields, the counter,
the gauge and the stats."""

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
from scaling_tpu.serve.engine import EngineConfig, ServeEngine
from scaling_tpu.serve.kvcache import build_layer_views

from . import reference_walk

VOCAB = 128
PATTERN = ["latent", "mlp", "latent", "moe", "latent", "moe"]
LATENT_LAYERS = PATTERN.count("latent")
TOPOLOGY = {"model_parallel_size": 1, "pipe_parallel_size": 1,
            "data_parallel_size": 1, "micro_batch_size": 1,
            "gradient_accumulation_steps": 1}
ARCH = {"vocab_size": VOCAB, "hidden_size": 256, "num_layers": len(PATTERN),
        "layer_pattern": PATTERN, "num_attention_heads": 4,
        "q_lora_rank": 96, "kv_lora_rank": 64, "qk_nope_head_dim": 32,
        "qk_rope_head_dim": 16, "v_head_dim": 32,
        "rope_scaling": {"type": "yarn", "factor": 8,
                         "original_max_position_embeddings": 32, "beta_fast": 1,
                         "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1},
        "rotary_embedding_base": 50000, "attention_bias": False,
        "mlp_type": "swiglu", "mlp_factor": 2.5, "mlp_bias": False,
        "moe_num_experts": 16, "moe_top_k": 4, "moe_expert_width": 64,
        "moe_glu": True, "moe_router": "sigmoid_bias", "moe_norm_topk_prob": True,
        "moe_norm_topk_eps": 1e-20, "moe_routed_scaling_factor": 2.827,
        "moe_shared_expert_width": 64, "moe_experts_first": 0, "moe_experts_held": 4,
        "activation_function": "silu", "norm_type": "rms",
        "layernorm": {"layernorm_epsilon": 1e-6},
        "relative_position_embedding_type": "rotary", "sequence_length": 128,
        "precision": "float32", "weight_tying": False}


def kimi_config(topology=None, **arch):
    return TransformerConfig.from_dict({
        "topology": {**TOPOLOGY, **(topology or {})},
        "transformer_architecture": {**ARCH, **arch},
        "data": {}, "logger": {"log_dir": None}})


@pytest.fixture(scope="module")
def kimi():
    config = kimi_config()
    module = init_model(config, None)
    params = module.init_params(jax.random.PRNGKey(3))
    # away from the init: norms off one, a selection bias that says something
    leaves, treedef = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(4), len(leaves))
    params = jax.tree.unflatten(treedef, [
        x + (0.2 * jax.random.normal(k, x.shape) if x.ndim == 1 else 0.0)
        for x, k in zip(leaves, keys)])
    return TransformerInferenceModule(config, module, params)


@pytest.fixture(scope="module")
def reference():
    return (cells.load_module(cells.ROOT, "reference", "latent_moe_decoder",
                              cells.REFERENCE_CONTRACT),
            cells.load_module(cells.ROOT, "views", "latent_moe_decoder",
                              cells.VIEW_CONTRACT))


def prompts(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, VOCAB, size=n).tolist() for n in lengths]


def engine_of(inf, **config):
    return ServeEngine(inf, EngineConfig(**{
        "num_slots": 4, "block_size": 4, "num_blocks": 4 * 24 + 1,
        "max_blocks_per_seq": 24, "token_budget": 64, "prefill_chunk": 8,
        "enable_prefix_cache": False, **config}))


def served(engine, requests, max_new):
    for p in requests:
        engine.submit(p, max_new_tokens=max_new)
    return {s.request.req_id: s.generated for s in engine.run_until_done()}


@pytest.fixture(scope="module")
def undisturbed(kimi, reference):
    """Each prompt alone, greedy, by the plain REFERENCE's full forward (the
    expanded form, no cache, nothing of the program): the tokens, and how far
    the runner-up lies below each."""
    ref, view = reference
    weights = view.reference_weights(kimi.params, ARCH)
    spec = view.reference_spec(ARCH)
    requests = prompts((9, 37, 14, 50, 21), seed=2)
    return requests, reference_walk.greedy_by_reference(
        lambda tokens: ref.forward(weights, jnp.asarray(tokens), spec), requests, 8)


def paged_logits(inf, tokens, chunk, paged_kernel):
    """``reference_walk.paged_logits`` through a pool of one row of 128 slots."""
    engine = engine_of(inf, num_slots=1, num_blocks=128 // 4 + 1,
                       max_blocks_per_seq=128 // 4)
    return reference_walk.paged_logits(inf, engine, tokens, chunk, paged_kernel)


# float32 on both sides: what separates the absorbed form over the pool from
# the expanded full forward is the order of float32 sums (the largest
# difference seen is 2e-5 at logits of deviation ~0.7)
LOGIT_ATOL = 1e-4


@pytest.mark.parametrize("paged_kernel", ["pallas", "xla"])
def test_chunks_then_decode_through_the_pool_are_the_references_full_forward(
        kimi, reference, paged_kernel):
    """Prefill in chunks of 8, then decode, through the latent pool in the
    absorbed form (the kernel interpreted, and the gather form) == the
    reference's expanded full forward, on logits at every position."""
    ref, view = reference
    tokens = prompts((44,), seed=5)[0]
    want = np.asarray(ref.forward(view.reference_weights(kimi.params, ARCH),
                                  jnp.asarray(tokens), view.reference_spec(ARCH)))
    got = paged_logits(kimi, tokens, 8, paged_kernel)
    assert got.shape == want.shape == (44, VOCAB)
    np.testing.assert_allclose(got, want, atol=LOGIT_ATOL)


def test_bfloat16_in_place_of_the_float32_statistics_fails_the_tolerance(
        kimi, reference, monkeypatch):
    """The tolerance is tight enough to see a lower precision: with the
    RMSNorms' statistics (the block's, the two latent norms') computed in
    bfloat16 the same comparison fails by two orders of magnitude."""
    from scaling_tpu.nn import norm

    def bf16_statistics(self, params, x, ctx):
        xb = x.astype(jnp.bfloat16)
        var = jnp.mean(jnp.square(xb), axis=-1, keepdims=True)
        y = xb * jax.lax.rsqrt(var + jnp.bfloat16(self.config.layernorm_epsilon))
        return (y * params["weight"].astype(jnp.bfloat16)).astype(x.dtype)

    ref, view = reference
    tokens = prompts((44,), seed=5)[0]
    want = np.asarray(ref.forward(view.reference_weights(kimi.params, ARCH),
                                  jnp.asarray(tokens), view.reference_spec(ARCH)))
    monkeypatch.setattr(norm.RMSNorm, "__call__", bf16_statistics)
    got = paged_logits(kimi, tokens, 8, "xla")
    assert np.abs(got - want).max() > 30 * LOGIT_ATOL


def test_the_pool_is_one_latent_line_a_token_a_layer(kimi):
    engine = engine_of(kimi)
    pools, stats = engine.pools, engine.stats_snapshot()
    assert pools.kinds is None   # paged layers alone: the four lists of the pools
    assert pools.kv_lines == stats["kv_lines"] == LATENT_LAYERS
    assert pools.state_lines == 0 and engine.latent_layers == stats["latent_layers"] == 3
    # two leaves, no head axis: the latent, and the rotary key's lane row
    assert [a.shape for a in pools.pool_k] == [(97, 4, 64)] * LATENT_LAYERS
    assert [a.shape for a in pools.pool_v] == [(97, 4, 128)] * LATENT_LAYERS
    # a token's line: (kv_lora_rank + rope_line_width) values a layer; the
    # line itself is (64 + 16) values, the rotary key's lane row holds zeros
    # after it. At Kimi-K2's sizes (512 + 128) x 2 B = 1,280 B held for a
    # line of (512 + 64) x 2 B = 1,152 B
    assert stats["kv_line_bytes"] == pools.line_bytes == LATENT_LAYERS * (64 + 128) * 4
    assert stats["kv_pool_bytes"] == 97 * 4 * pools.line_bytes
    from scaling_tpu.nn.latent_paged_attention import rope_line_width
    assert rope_line_width(64) == rope_line_width(16) == 128
    assert (512 + 64) * 2 == 1152 and (512 + rope_line_width(64)) * 2 == 1280


def test_the_engine_serves_what_the_references_full_forward_gives(kimi, undisturbed):
    """Prefill in chunks of 8 whose edges fall mid-prompt, four rows at once
    and a fifth in a reused slot, then decode: ticks mix chunk rows and
    decode rows, token-major, through the interpreted kernel."""
    requests, want = undisturbed
    engine = engine_of(kimi)
    got = served(engine, requests, 8)
    assert [got[i] for i in range(len(requests))] == want
    assert len({tuple(w) for w in want}) > 1  # the weights say something
    # the rotary key's lane row: the key, then zeros
    pool_r = np.asarray(engine.pools.pool_v[0])
    assert np.abs(pool_r[1:, :, :16]).max() > 0 and not pool_r[..., 16:].any()


def test_a_preempted_and_recomputed_row_is_an_undisturbed_one(kimi, undisturbed):
    """A pool too small for the rows forces recompute-style preemption: the
    resumed sequence re-enters at context 0 and rewrites its latent lines."""
    requests, want = undisturbed
    engine = engine_of(kimi, num_blocks=26)
    got = served(engine, requests, 8)
    assert engine.scheduler.preemption_count > 0
    assert any(s.preemptions for s in engine.finished)
    assert [got[i] for i in range(len(requests))] == want


def test_lines_that_are_never_written_serve_other_tokens(kimi, undisturbed, monkeypatch):
    """The comparison sees the mechanism: with the scatter dropped the
    engine's tokens differ."""
    from scaling_tpu.nn import latent_attention

    monkeypatch.setattr(latent_attention, "paged_scatter_kv",
                        lambda view, flat, k_rows, v_rows: view)
    requests, want = undisturbed
    got = served(engine_of(kimi), requests, 8)
    assert [got[i] for i in range(len(requests))] != want


# ---- refused by name -------------------------------------------------------

def test_an_int8_pool_is_refused_by_name(kimi):
    with pytest.raises(ValueError, match="latent attention layer's cache line has no "
                                         "head axis.*kv_dtype='native'"):
        engine_of(kimi, kv_dtype="int8")


@pytest.mark.parametrize("topology,arch,message", [
    ({"model_parallel_size": 2}, {}, "layer_pattern with model_parallel_size 2"),
    ({"pipe_parallel_size": 2}, {}, "layer_pattern with pipe_parallel_size 2"),
    ({}, {"rope_scaling": {**ARCH["rope_scaling"], "type": "linear"}},
     "rope_scaling type 'linear': only 'yarn' is built"),
    ({}, {"rope_scaling": {**ARCH["rope_scaling"], "type": "dynamic"}},
     "rope_scaling type 'dynamic'"),
    ({}, {"moe_n_group": 8, "moe_topk_group": 4, "moe_num_experts": 12},
     "moe_n_group 8 / moe_topk_group 4: the group-limited choice"),
    ({}, {"moe_topk_group": 2}, "group-limited choice"),
    ({}, {"kv_lora_rank": None}, "'latent' layers needs \\['kv_lora_rank'\\]"),
    ({}, {"qk_rope_head_dim": 15}, "qk_rope_head_dim 15 is odd"),
    ({}, {"relative_position_embedding_type": "none"},
     "a latent head's position is its rotary slice"),
    # (since PR 68 a pattern's 'attention' layers apply YaRN too)
    ({}, {"layer_pattern": ["conv", "mlp"] * 3, "num_attention_heads": 4},
     "rope_scaling without 'latent' or 'attention' layers"),
])
def test_a_layout_the_stack_does_not_build_is_refused_by_name(topology, arch, message):
    with pytest.raises(ValueError, match=message):
        kimi_config(topology, **arch)


def test_rope_scaling_without_a_pattern_is_refused_by_name():
    arch = {k: v for k, v in ARCH.items() if k != "layer_pattern"}
    with pytest.raises(ValueError, match="rope_scaling without layer_pattern"):
        kimi_config(**{**arch, "layer_pattern": None})


def test_training_and_cached_generate_are_refused_by_name(kimi):
    from scaling_tpu.nn.base_layer import ForwardContext

    with pytest.raises(NotImplementedError, match="layer_pattern stack is served"):
        kimi.module.forward(kimi.params, {}, ForwardContext())
    with pytest.raises(ValueError, match="cached generate\\(\\) keeps dense KV"):
        kimi.generate([1, 2, 3], max_tokens=2)
    batch = kimi._make_batch(jnp.ones((1, 8), jnp.int32), jnp.arange(8)[None])
    ctx = kimi._make_ctx()
    embedded = kimi.module.layers[0](kimi.params["layer_0"], batch, ctx)
    with pytest.raises(ValueError, match="latent attention layer takes a PagedKVCacheView"):
        kimi.module.layers[1](kimi.params["layer_1"], embedded, ctx,
                              kv_cache=(jnp.zeros((1, 8, 64)), jnp.zeros((1, 8, 128))),
                              cache_offset=0)


# ---- spans, counters, scopes -----------------------------------------------

def test_spans_counters_and_gauge_of_a_latent_model(kimi, tmp_path):
    engine = engine_of(kimi)
    requests = prompts((9, 21), seed=8)
    obs.start_capture(str(tmp_path))
    try:
        served(engine, requests, 6)
    finally:
        capture = obs.stop_capture()
    mixed = [f for n, _, _, f in capture.spans if n == "serve.mixed"]
    assert mixed and all(f["latent_layers"] == LATENT_LAYERS for f in mixed)
    # the first tick: two chunk rows of 8 at context 0: 16 lines, 2 x 36 pairs
    assert (mixed[0]["latent_lines"], mixed[0]["latent_pairs"]) == (16, 72)
    # the second: the 9-token prompt's last token (context 8: 9 lines, 9
    # pairs) beside the other's second chunk (context 8: 16 lines, 64 + 36)
    assert (mixed[1]["latent_lines"], mixed[1]["latent_pairs"]) == (25, 109)
    # a decode tick: a row of one token over c lines reads c + 1, pairs c + 1
    decode = [f for f in mixed if f["chunks"] == 0]
    assert decode and all(f["latent_lines"] == f["latent_pairs"] for f in decode)
    assert capture.counters["serve_latent_lines_read_total"] == LATENT_LAYERS * sum(
        f["latent_lines"] for f in mixed)
    # a share of the experts: the absent assignments are counted
    assert engine.moe_partial and engine.num_experts == 4
    total = 4 * PATTERN.count("moe") * sum(f["tokens"] for f in mixed)
    assert (capture.counters["serve_moe_assignments_total"]
            + capture.counters["serve_moe_absent_assignments_total"]) == total
    # a constant of the pools: stats_snapshot() has it, no gauge (ISSUE 57)
    assert engine.stats_snapshot()["kv_line_bytes"] == engine.pools.line_bytes


def test_the_mixer_lies_in_the_attn_scope_and_its_kernel_has_its_own_name(kimi):
    """``attn`` names the instructions compiled from inside a latent mixer,
    the kernel among them: what the benchmark's readers look up."""
    engine = engine_of(kimi, num_slots=1)
    views = build_layer_views(
        engine._pool_state(), jnp.arange(1, 25, dtype=jnp.int32)[None],
        jnp.zeros((1,), jnp.int32), jnp.full((1,), 8, jnp.int32),
        kinds=engine.pools.kinds)
    batch = kimi._make_batch(jnp.ones((1, 8), jnp.int32), jnp.arange(8)[None])
    text = jax.jit(lambda p, v: kimi._run_layers(p, batch, v, None)[0]).lower(
        kimi.params, views).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', text))
    assert any(re.search(r"/attn/dot_general", n) for n in names)
    assert any(re.search(r"/attn/.*latent_paged_attention/", n) for n in names)
    assert any("/moe/" in n for n in names)
    assert not any(re.search(r"/attn/.*/moe/|/moe/.*/attn/", n) for n in names)
