"""A stack of DeepSeek-V3.2-Exp blocks (``layer_pattern``: SPARSE latent
attention, an indexer choosing ``index_topk`` 16 lines a query, then a dense
or a group-limited sigmoid-routed SwiGLU FFN, 4 of 16 experts held) through
``ServeEngine`` at contexts of 40-200: a line of two leaves in the paged pool
(latent + rotary key, index key);
prefill chunks then decode over GATHERED chosen lines against the plain
reference's full forward, on logits and on the chosen sets themselves; mixed
ticks, eviction and re-admission; what leaves the comparison when the
selection is left out, off by one, or its rope lanes misplaced; what is
refused, by name; the spans' fields and the counters."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import cells
from scaling_tpu import obs
from scaling_tpu.models.transformer import TransformerConfig
from scaling_tpu.models.transformer.inference import TransformerInferenceModule
from scaling_tpu.models.transformer.model import init_model
from scaling_tpu.nn import sparse_latent_attention
from scaling_tpu.serve.engine import EngineConfig, ServeEngine
from scaling_tpu.serve.kvcache import build_layer_views

from . import reference_walk

VOCAB = 128
TOPK = 16
PATTERN = ["latent", "mlp", "latent", "moe", "latent", "moe"]
SPARSE_LAYERS = PATTERN.count("latent")
TOPOLOGY = {"model_parallel_size": 1, "pipe_parallel_size": 1,
            "data_parallel_size": 1, "micro_batch_size": 1,
            "gradient_accumulation_steps": 1}
ARCH = {"vocab_size": VOCAB, "hidden_size": 256, "num_layers": len(PATTERN),
        "layer_pattern": PATTERN, "num_attention_heads": 4,
        "q_lora_rank": 96, "kv_lora_rank": 64, "qk_nope_head_dim": 32,
        "qk_rope_head_dim": 16, "v_head_dim": 32,
        "index_n_heads": 4, "index_head_dim": 32, "index_topk": TOPK,
        "rope_scaling": {"type": "yarn", "factor": 8,
                         "original_max_position_embeddings": 32, "beta_fast": 32,
                         "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1},
        "rotary_embedding_base": 10000, "attention_bias": False,
        "mlp_type": "swiglu", "mlp_factor": 2.5, "mlp_bias": False,
        "moe_num_experts": 16, "moe_top_k": 4, "moe_expert_width": 64,
        "moe_glu": True, "moe_router": "sigmoid_bias", "moe_norm_topk_prob": True,
        "moe_norm_topk_eps": 1e-20, "moe_routed_scaling_factor": 2.5,
        "moe_shared_expert_width": 64, "moe_experts_first": 0, "moe_experts_held": 4,
        "moe_n_group": 4, "moe_topk_group": 2,
        "activation_function": "silu", "norm_type": "rms",
        "layernorm": {"layernorm_epsilon": 1e-6},
        "relative_position_embedding_type": "rotary", "sequence_length": 256,
        "precision": "float32", "weight_tying": False}
WINDOW = 256


def dsv32_config(topology=None, **arch):
    return TransformerConfig.from_dict({
        "topology": {**TOPOLOGY, **(topology or {})},
        "transformer_architecture": {**ARCH, **arch},
        "data": {}, "logger": {"log_dir": None}})


@pytest.fixture(scope="module")
def dsv32():
    config = dsv32_config()
    module = init_model(config, None)
    params = module.init_params(jax.random.PRNGKey(3))
    # away from the init: norms off one, biases that say something
    leaves, treedef = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(4), len(leaves))
    params = jax.tree.unflatten(treedef, [
        x + (0.2 * jax.random.normal(k, x.shape) if x.ndim == 1 else 0.0)
        for x, k in zip(leaves, keys)])
    return TransformerInferenceModule(config, module, params)


@pytest.fixture(scope="module")
def reference():
    return (cells.load_module(cells.ROOT, "reference", "sparse_latent_moe_decoder",
                              cells.REFERENCE_CONTRACT),
            cells.load_module(cells.ROOT, "views", "sparse_latent_moe_decoder",
                              cells.VIEW_CONTRACT))


def prompts(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, VOCAB, size=n).tolist() for n in lengths]


def engine_of(inf, **config):
    return ServeEngine(inf, EngineConfig(**{
        "num_slots": 4, "block_size": 4, "num_blocks": 4 * 64 + 1,
        "max_blocks_per_seq": 64, "token_budget": 64, "prefill_chunk": 8,
        "enable_prefix_cache": False, **config}))


def served(engine, requests, max_new):
    for p in requests:
        engine.submit(p, max_new_tokens=max_new)
    return {s.request.req_id: s.generated for s in engine.run_until_done()}


def reference_logits(dsv32, reference, tokens, chosen_out=None, **spec):
    ref, view = reference
    return np.asarray(ref.forward(
        view.reference_weights(dsv32.params, ARCH), jnp.asarray(tokens),
        {**view.reference_spec(ARCH), **spec}, chosen_out=chosen_out))


@pytest.fixture(scope="module")
def undisturbed(dsv32, reference):
    """Each prompt alone, greedy, by the plain REFERENCE's full forward: the
    tokens, and how far the runner-up lies below each."""
    requests = prompts((40, 97, 61, 200, 130), seed=2)
    return requests, reference_walk.greedy_by_reference(
        lambda tokens: reference_logits(dsv32, reference, tokens), requests, 6)


def paged_logits(inf, tokens, chunk, paged_kernel):
    """``reference_walk.paged_logits`` through a pool of one row's window."""
    engine = engine_of(inf, num_slots=1, num_blocks=WINDOW // 4 + 1,
                       max_blocks_per_seq=WINDOW // 4)
    return reference_walk.paged_logits(inf, engine, tokens, chunk, paged_kernel)


# float32 on both sides: what separates the absorbed form over gathered lines
# from the expanded full forward is the order of float32 sums
LOGIT_ATOL = 2e-4


@pytest.fixture(scope="module")
def sequence(dsv32, reference):
    tokens = prompts((76,), seed=5)[0]
    chosen = []
    want = reference_logits(dsv32, reference, tokens, chosen_out=chosen)
    return tokens, want, [np.asarray(c) for c in chosen]


@pytest.mark.parametrize("paged_kernel", ["pallas", "xla"])
def test_chunks_then_decode_through_the_pool_are_the_references_full_forward(
        dsv32, sequence, paged_kernel):
    """Prefill in chunks of 8, then decode, over the chosen lines (the gather
    that serves, and the mask-everything form) == the reference's expanded
    full forward under its own choice, on logits at every position."""
    tokens, want, chosen = sequence
    assert all(c.sum(axis=1).tolist() == [min(TOPK, t + 1) for t in range(76)]
               for c in chosen)
    got = paged_logits(dsv32, tokens, 8, paged_kernel)
    assert got.shape == want.shape == (76, VOCAB)
    np.testing.assert_allclose(got, want, atol=LOGIT_ATOL)


def test_the_program_chooses_the_references_lines(dsv32, sequence, monkeypatch):
    """The chosen SETS themselves: the masks the served path attends under (a
    threshold found by bisection), a call and layer at a time, are the
    reference's choice (a stable sort's ranks) for those queries."""
    tokens, _, chosen = sequence
    masks = []
    choose = sparse_latent_attention.SparseLatentSelfAttention._chosen

    def recording(self, scores, visible, k):
        mask = choose(self, scores, visible, k)
        # in the order the program runs them: a call, then a layer
        jax.debug.callback(lambda m: masks.append(np.asarray(m)), mask, ordered=True)
        return mask

    monkeypatch.setattr(
        sparse_latent_attention.SparseLatentSelfAttention, "_chosen", recording)
    sizes = [8] * 5 + [1] * 4
    paged_logits(dsv32, tokens[:44], 8, "pallas")
    # a call chooses once a layer: a token's in a pass over the one-token
    # rows, a chunk's in its own walk (no pass is made for a call without
    # one-token rows)
    assert len(masks) == SPARSE_LAYERS * len(sizes)
    done = 0
    for call, n in enumerate(sizes):
        for layer in range(SPARSE_LAYERS):
            mask = masks[call * SPARSE_LAYERS + layer][0]      # (n, window)
            for j in range(n):
                want = np.flatnonzero(chosen[layer][done + j])
                assert np.flatnonzero(mask[j]).tolist() == want.tolist(), (done, layer, j)
        done += n
    assert done == 44


@pytest.mark.parametrize("what,spec", [
    ("the selection left out", {"index_topk": None}),
    ("index_topk off by one", {"index_topk": TOPK - 1}),
])
def test_a_reference_that_chooses_otherwise_is_told_apart(dsv32, reference, sequence,
                                                          what, spec):
    """The comparison sees the mechanism: against a reference without the
    choice, or with one line fewer, the served logits are off by far more
    than the tolerance."""
    tokens, want, _ = sequence
    other = reference_logits(dsv32, reference, tokens, **spec)
    np.testing.assert_allclose(other[:TOPK - 1], want[:TOPK - 1], atol=LOGIT_ATOL)
    assert np.abs(other - want).max() > 50 * LOGIT_ATOL, what


def test_rope_lanes_that_are_the_last_and_not_the_first_are_told_apart(
        dsv32, sequence, monkeypatch):
    """The indexer's rotary lanes are the FIRST of a head: an indexer that
    turns the last ones chooses other lines, and the logits show it."""
    tokens, want, _ = sequence
    indexer = sparse_latent_attention.SparseLatentSelfAttention._indexer

    def last_lanes(self, params, x, c_q, ctx, position_ids):
        flipped = lambda a: a[..., ::-1]

        class Rotary:
            def __call__(_, q, k, qp, kp):
                q, k = self_rotary(flipped(q), flipped(k), qp, kp)
                return flipped(q), flipped(k)

        self_rotary = self.rotary_embedding
        self.rotary_embedding = Rotary()
        try:
            return indexer(self, params, x, c_q, ctx, position_ids)
        finally:
            self.rotary_embedding = self_rotary

    monkeypatch.setattr(
        sparse_latent_attention.SparseLatentSelfAttention, "_indexer", last_lanes)
    got = paged_logits(dsv32, tokens, 8, "pallas")
    assert np.abs(got - want).max() > 50 * LOGIT_ATOL


def test_the_pool_is_one_line_of_two_leaves_a_token_a_layer(dsv32):
    engine = engine_of(dsv32)
    pools, stats = engine.pools, engine.stats_snapshot()
    assert pools.kinds is None and pools.kv_lines == SPARSE_LAYERS
    assert engine.sparse_layers == stats["sparse_layers"] == SPARSE_LAYERS
    assert engine.latent_layers == SPARSE_LAYERS
    # the latent with the rotary key's lane row after it (gathered as ONE
    # row a chosen line), and the indexer's key; no head axis
    assert [a.shape for a in pools.pool_k] == [(257, 4, 64 + 128)] * SPARSE_LAYERS
    assert [a.shape for a in pools.pool_v] == [(257, 4, 32)] * SPARSE_LAYERS
    assert len(pools.state()) == 4    # the paged rule, as every latent stack
    assert stats["kv_line_bytes"] == pools.line_bytes == SPARSE_LAYERS * (64 + 128 + 32) * 4
    # at DeepSeek-V3.2-Exp's sizes in bf16: 1,280 + 256 B held a (token,
    # layer) for a line of 512 + 64 + 128 values = 1,408 B
    assert (512 + 128 + 128) * 2 == 1536 and (512 + 64 + 128) * 2 == 1408


def test_the_engine_serves_what_the_references_full_forward_gives(dsv32, undisturbed):
    """Prefill in chunks of 8 whose edges fall mid-prompt, four rows at once
    and a fifth in a reused slot, then decode: ticks mix chunk rows and
    decode rows, token-major, contexts of 40-206 against index_topk 16."""
    requests, want = undisturbed
    engine = engine_of(dsv32)
    got = served(engine, requests, 6)
    assert [got[i] for i in range(len(requests))] == want
    assert len({tuple(w) for w in want}) > 1  # the weights say something
    # the index keys' leaf was written where the latents were; the rotary
    # key's lane row: the key, then zeros
    assert np.abs(np.asarray(engine.pools.pool_v[0])[1:]).max() > 0
    line = np.asarray(engine.pools.pool_k[0])
    assert np.abs(line[1:, :, :80]).max() > 0 and not line[..., 80:].any()


def test_a_preempted_and_recomputed_row_is_an_undisturbed_one(dsv32, undisturbed):
    """A pool too small for the rows forces recompute-style preemption: the
    evicted sequence re-enters at context 0 and rewrites its lines."""
    requests, want = undisturbed
    engine = engine_of(dsv32, num_blocks=70)
    got = served(engine, requests, 6)
    assert engine.scheduler.preemption_count > 0
    assert any(s.preemptions for s in engine.finished)
    assert [got[i] for i in range(len(requests))] == want


def test_index_keys_that_are_never_written_serve_other_tokens(dsv32, undisturbed,
                                                              monkeypatch):
    """With the indexer's choice replaced by the FIRST lines of a row the
    engine's tokens differ: the served tokens depend on the choice."""
    choose = sparse_latent_attention.threshold_choice

    def first_lines(scores, visible, topk):
        return choose(-jnp.cumsum(jnp.ones_like(scores), axis=-1), visible, topk)

    monkeypatch.setattr(sparse_latent_attention, "threshold_choice", first_lines)
    requests, want = undisturbed
    got = served(engine_of(dsv32), requests, 6)
    assert [got[i] for i in range(len(requests))] != want


def test_a_tie_over_room_is_filled_by_position_and_counted(dsv32, reference, tmp_path):
    """The fill of ties by position runs only in a call whose kept queries
    have more visible scores at their threshold than room, and the engine
    counts those calls on the tick's one host read. A first sparse layer whose
    index weights are zero scores every line 0.0: its rows tie everywhere, and
    from the 17th line on (index_topk 16) a query's ties are more than its
    room. The engine serves the reference's tokens (a tie goes to the lower
    position in both), ``serve.emit`` carries ``tie_breaks`` 1 from the tick
    whose chunk passes 16 lines on, the counter adds them up; the model as it
    is counts none."""
    first = next(name for name in sorted(dsv32.params)
                 if "index_w_proj" in dsv32.params[name].get("mixer", {}))
    layer = dsv32.params[first]
    weight = layer["mixer"]["index_w_proj"]["weight"]
    tied = TransformerInferenceModule(dsv32.config, dsv32.module, {
        **dsv32.params, first: {**layer, "mixer": {
            **layer["mixer"], "index_w_proj": {"weight": jnp.zeros_like(weight)}}}})
    prompt = prompts((20,), seed=11)[0]
    want = prompt + reference_walk.greedy_by_reference(
        lambda tokens: reference_logits(tied, reference, tokens), [prompt], 3,
        least_margin=0)[0]
    for inf, tie_breaks in ((tied, [0, 0, 1, 1, 1]), (dsv32, [0] * 5)):
        engine = engine_of(inf, num_slots=1)
        obs.start_capture(str(tmp_path / str(sum(tie_breaks))))
        try:
            got = served(engine, [prompt], 3)
        finally:
            capture = obs.stop_capture()
        # chunks of 8, 8 and 4 (the last one's queries see 17-20 lines), then
        # two decode ticks
        emits = [f for n, _, _, f in capture.spans if n == "serve.emit"]
        assert [f["tie_breaks"] for f in emits] == tie_breaks
        assert capture.counters.get("serve_sparse_tie_breaks_total", 0) == sum(tie_breaks)
        if inf is tied:
            assert got[0] == want[len(prompt):]
        # the load behind it is the experts' alone
        assert all(f["load_max"] >= f["load_mean"] > 0 for f in emits)


def test_the_spans_say_what_was_scored_chosen_and_read(dsv32, tmp_path):
    """``serve.mixed`` of a sparse model: ``sparse_layers``, ``index_lines``,
    ``index_pairs``, ``chosen_pairs``, counted on the host from the tick's
    row lengths; the two counters add them up over the layers."""
    engine = engine_of(dsv32, num_slots=1)
    obs.start_capture(str(tmp_path))
    try:
        served(engine, prompts((40,), seed=7), 3)
    finally:
        capture = obs.stop_capture()
    spans = [f for n, _, _, f in capture.spans if n == "serve.mixed"]
    # 5 chunks of 8, then 2 decode ticks (the first token comes off the last chunk)
    assert [f["tokens"] for f in spans] == [8] * 5 + [1, 1]
    assert all(f["sparse_layers"] == SPARSE_LAYERS for f in spans)
    assert [f["index_lines"] for f in spans] == [8, 16, 24, 32, 40, 41, 42]
    # a chunk at context c: sum over its 8 queries of c + p + 1 visible lines
    assert [f["index_pairs"] for f in spans] == [
        8 * c + 36 for c in (0, 8, 16, 24, 32)] + [41, 42]
    assert [f["index_pairs"] for f in spans] == [f["latent_pairs"] for f in spans]
    # ... of which a query keeps min(16, what it sees)
    # ... of which a query keeps min(16, what it sees): the second chunk's
    # queries see 9..16 lines, every later query 16 of more
    assert [f["chosen_pairs"] for f in spans] == [36, 100, 128, 128, 128, 16, 16]
    assert capture.counters["serve_index_lines_read_total"] == SPARSE_LAYERS * sum(
        f["index_lines"] for f in spans)
    assert capture.counters["serve_sparse_chosen_pairs_total"] == SPARSE_LAYERS * sum(
        f["chosen_pairs"] for f in spans)
    # a latent line's one-token rows do not go through the paged kernel
    assert not any("sparse_single_rows" in f for f in spans)
    assert "serve_sparse_single_rows_total" not in capture.counters
    assert obs.kernel_build_count("masked_latent_attention", interpret=True) > 0
    assert obs.kernel_build_count("masked_latent_attention", interpret=False) == 0


def test_indexer_and_attention_over_the_chosen_lines_have_scopes_of_their_own(dsv32):
    """``attn`` names the mixer; inside it ``indexer`` (projections, LayerNorm,
    rotary, the scatter of the key, scores and choice), ``index_select`` (scores
    and choice) and ``sparse_attend`` (gather and attention): what the
    benchmark's readers look up."""
    import re

    engine = engine_of(dsv32, num_slots=1)
    views = build_layer_views(
        engine._pool_state(), jnp.arange(1, 65, dtype=jnp.int32)[None],
        jnp.zeros((1,), jnp.int32), jnp.full((1,), 8, jnp.int32),
        kinds=engine.pools.kinds)
    batch = dsv32._make_batch(jnp.ones((1, 8), jnp.int32), jnp.arange(8)[None])
    text = jax.jit(lambda p, v: dsv32._run_layers(p, batch, v, None)[0]).lower(
        dsv32.params, views).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', text))
    # (the row walk's loops and branches put their own names in between)
    for scope in (r"/attn/indexer/", r"/attn/(\S+/)?indexer/index_select/",
                  r"/attn/(\S+/)?sparse_attend/"):
        assert any(re.search(scope, n) for n in names), scope
    assert not any(re.search(r"/moe/.*/indexer/|/indexer/.*/sparse_attend/", n)
                   for n in names)


# ---- refused by name -------------------------------------------------------

def test_an_int8_pool_is_refused_by_name(dsv32):
    with pytest.raises(ValueError, match="latent attention layer's cache line has no "
                                         "head axis.*kv_dtype='native'"):
        engine_of(dsv32, kv_dtype="int8")


def test_the_prefix_cache_is_refused_by_name(dsv32):
    with pytest.raises(ValueError, match="enable_prefix_cache with sparse latent "
                                         "attention layers"):
        engine_of(dsv32, enable_prefix_cache=True)


@pytest.mark.parametrize("topology,arch,message", [
    ({"model_parallel_size": 2}, {}, "layer_pattern with model_parallel_size 2"),
    ({}, {"index_topk": None}, "\\['index_n_heads', 'index_head_dim'\\] without "
                               "\\['index_topk'\\]"),
    ({}, {"index_head_dim": 8}, "index_head_dim 8 is narrower than qk_rope_head_dim 16"),
    # (since PR 61 a pattern's 'attention' layers may have the indexer
    # instead: nn/sparse_attention.py; a pattern with neither, or both, may not)
    ({}, {"layer_pattern": ["mamba", "mlp"] * 3, "rope_scaling": None},
     "without ONE kind of attention layer to make sparse: the indexer"),
    ({}, {"layer_pattern": ["latent", "attention"] * 3},
     "without ONE kind of attention layer to make sparse: the indexer"),
    ({}, {"moe_n_group": 3}, "moe_n_group 3 / moe_topk_group 2: the group-limited choice"),
    ({}, {"moe_topk_group": 5}, "moe_n_group 4 / moe_topk_group 5"),
    ({}, {"moe_top_k": 9, "moe_topk_group": 2}, "group-limited choice"),
    ({}, {"moe_router": "softmax"}, "the group-limited choice is the 'sigmoid_bias'"),
])
def test_a_layout_the_stack_does_not_build_is_refused_by_name(topology, arch, message):
    with pytest.raises(ValueError, match=message):
        dsv32_config(topology, **arch)


def test_training_and_cached_generate_are_refused_by_name(dsv32):
    from scaling_tpu.nn.base_layer import ForwardContext

    with pytest.raises(NotImplementedError, match="layer_pattern stack is served"):
        dsv32.module.forward(dsv32.params, {}, ForwardContext())
    with pytest.raises(ValueError, match="cached generate\\(\\) keeps dense KV"):
        dsv32.generate([1, 2, 3], max_tokens=2)


def test_uncached_generate_is_the_references_full_forward(dsv32, reference):
    """``generate(use_cache=False)``: the expanded heads under the mask of
    the chosen lines, the program's own uncached truth."""
    tokens = prompts((50,), seed=9)[0]
    want = reference_logits(dsv32, reference, tokens)
    batch = dsv32._make_batch(jnp.asarray(tokens, jnp.int32)[None],
                              jnp.arange(50, dtype=jnp.int32)[None])
    got = np.asarray(jax.jit(
        lambda p: dsv32._run_layers(p, batch, None, None)[0])(dsv32.params)[0])
    np.testing.assert_allclose(got, want, atol=LOGIT_ATOL)
