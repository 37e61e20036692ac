"""A stack of Keye-VL-2.0 decoder blocks (``layer_pattern``: SPARSE
grouped-query attention, an indexer that reads the hidden state choosing
``index_topk`` 16 lines a token for ALL heads, then softmax-routed SwiGLU
experts, every expert held) through ``ServeEngine`` at contexts of 40-200: a
line of THREE leaves in the paged pool (K, V, index key); prefill chunks then
decode, one tick ahead, against the plain reference's full forward, on logits
and on the chosen sets themselves; mixed ticks, eviction and re-admission;
what leaves the comparison when the selection is left out, off by one, its
index key not LayerNorm'd or not rotated, or the choice made a head and not a
token; the pool's shapes and bytes, the scatter, copy-on-write; what is
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
from scaling_tpu.nn import sparse_attention, sparse_rows
from scaling_tpu.nn.attention import PagedKVCacheView, paged_scatter_kv
from scaling_tpu.serve.engine import EngineConfig, ServeEngine
from scaling_tpu.serve.kvcache import build_layer_views, state_from_views

from . import reference_walk

VOCAB = 128
TOPK = 16
PATTERN = ["attention", "moe"] * 3
SPARSE_LAYERS = PATTERN.count("attention")
KV_HEADS, HEAD_DIM, INDEX_DIM = 2, 32, 16
TOPOLOGY = {"model_parallel_size": 1, "pipe_parallel_size": 1,
            "data_parallel_size": 1, "micro_batch_size": 1,
            "gradient_accumulation_steps": 1}
ARCH = {"vocab_size": VOCAB, "hidden_size": 128, "num_layers": len(PATTERN),
        "layer_pattern": PATTERN, "num_attention_heads": 8,
        "attention_num_kv_heads": KV_HEADS, "attention_head_dim": HEAD_DIM,
        "attention_qkv_in_one": False, "attention_bias": False,
        "key_query_norm": True, "key_query_norm_scope": "head",
        "index_n_heads": 4, "index_head_dim": INDEX_DIM, "index_topk": TOPK,
        "rotary_embedding_base": 10000,
        "mlp_type": "swiglu", "mlp_factor": 2.0, "mlp_bias": False,
        "moe_num_experts": 8, "moe_top_k": 2, "moe_expert_width": 64,
        "moe_glu": True, "moe_router": "softmax", "moe_norm_topk_prob": True,
        "activation_function": "silu", "norm_type": "rms",
        "layernorm": {"layernorm_epsilon": 1e-6},
        "relative_position_embedding_type": "rotary", "sequence_length": 256,
        "precision": "float32", "weight_tying": False}
WINDOW = 256
# a line: K and V (2 heads of 32) and the index key (16), float32
LINE_BYTES = (2 * KV_HEADS * HEAD_DIM + INDEX_DIM) * 4


def keye_config(topology=None, **arch):
    return TransformerConfig.from_dict({
        "topology": {**TOPOLOGY, **(topology or {})},
        "transformer_architecture": {**ARCH, **arch},
        "data": {}, "logger": {"log_dir": None}})


def inference_of(config):
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
def keye():
    return inference_of(keye_config())


@pytest.fixture(scope="module")
def reference():
    return (cells.load_module(cells.ROOT, "reference", "sparse_gqa_moe_decoder",
                              cells.REFERENCE_CONTRACT),
            cells.load_module(cells.ROOT, "views", "sparse_gqa_moe_decoder",
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


def reference_logits(keye, reference, tokens, chosen_out=None, **spec):
    ref, view = reference
    return np.asarray(ref.forward(
        view.reference_weights(keye.params, ARCH), jnp.asarray(tokens),
        {**view.reference_spec(ARCH), **spec}, chosen_out=chosen_out))


@pytest.fixture(scope="module")
def undisturbed(keye, reference):
    """Each prompt alone, greedy, by the plain REFERENCE's full forward: the
    tokens, and how far the runner-up lies below each."""
    requests = prompts((40, 97, 61, 200, 130), seed=2)
    return requests, reference_walk.greedy_by_reference(
        lambda tokens: reference_logits(keye, reference, tokens), requests, 6)


def paged_logits(inf, tokens, chunk, paged_kernel):
    """``reference_walk.paged_logits`` through a pool of one row's window."""
    engine = engine_of(inf, num_slots=1, num_blocks=WINDOW // 4 + 1,
                       max_blocks_per_seq=WINDOW // 4)
    return reference_walk.paged_logits(inf, engine, tokens, chunk, paged_kernel)


# float32 on both sides: what separates the streamed form from the full
# forward is the order of float32 sums
LOGIT_ATOL = 2e-4


@pytest.fixture(scope="module")
def sequence(keye, reference):
    tokens = prompts((76,), seed=5)[0]
    chosen = []
    want = reference_logits(keye, reference, tokens, chosen_out=chosen)
    return tokens, want, [np.asarray(c) for c in chosen]


@pytest.mark.parametrize("paged_kernel", ["pallas", "xla"])
def test_chunked_prefill_then_decode_is_the_references_full_forward(
        keye, sequence, paged_kernel):
    """Prefill in chunks of 8, then decode, over the chosen lines (the stream
    under the mask that serves, and the mask-everything form) == the
    reference's full forward under its own choice, on logits at every
    position."""
    tokens, want, chosen = sequence
    assert all(c.sum(axis=1).tolist() == [min(TOPK, t + 1) for t in range(76)]
               for c in chosen)
    got = paged_logits(keye, tokens, 8, paged_kernel)
    assert got.shape == want.shape == (76, VOCAB)
    np.testing.assert_allclose(got, want, atol=LOGIT_ATOL)


def test_the_program_chooses_the_references_lines(keye, sequence, monkeypatch):
    """The chosen SETS themselves: the masks the served path attends under (a
    threshold found by bisection), a call and layer at a time, are the
    reference's choice (a stable sort's ranks) for those queries: ONE mask a
    token, no head axis."""
    tokens, _, chosen = sequence
    masks = []
    choose = sparse_attention.SparseSelfAttention._chosen

    def recording(self, scores, visible, k):
        mask = choose(self, scores, visible, k)
        # in the order the program runs them: a call, then a layer
        jax.debug.callback(lambda m: masks.append(np.asarray(m)), mask, ordered=True)
        return mask

    monkeypatch.setattr(sparse_attention.SparseSelfAttention, "_chosen", recording)
    sizes = [8] * 3 + [1] * 4
    paged_logits(keye, tokens[:28], 8, "pallas")
    assert len(masks) == SPARSE_LAYERS * len(sizes)
    done = 0
    for call, n in enumerate(sizes):
        for layer in range(SPARSE_LAYERS):
            mask = masks[call * SPARSE_LAYERS + layer]
            assert mask.ndim == 3 and mask.shape[:2] == (1, n)   # (rows, queries, slots)
            for j in range(n):
                want = np.flatnonzero(chosen[layer][done + j])
                assert np.flatnonzero(mask[0, j]).tolist() == want.tolist(), (done, layer, j)
        done += n
    assert done == 28


@pytest.mark.parametrize("what,spec", [
    ("the selection left out", {"index_topk": None}),
    ("index_topk off by one", {"index_topk": TOPK - 1}),
])
def test_a_reference_that_chooses_otherwise_is_told_apart(keye, reference, sequence,
                                                          what, spec):
    """The comparison sees the mechanism: against a reference without the
    choice, or with one line fewer, the served logits are off by far more
    than the tolerance."""
    tokens, want, _ = sequence
    other = reference_logits(keye, reference, tokens, **spec)
    np.testing.assert_allclose(other[:TOPK - 1], want[:TOPK - 1], atol=LOGIT_ATOL)
    assert np.abs(other - want).max() > 50 * LOGIT_ATOL, what


def broken_indexer(how):
    """``SparseSelfAttention._indexer`` with one step of the index key's
    making left out."""
    indexer = sparse_attention.SparseSelfAttention._indexer

    def broken(self, params, x, ctx, position_ids):
        kept = self.index_k_norm, self.index_rotary
        if how == "not LayerNorm'd":
            self.index_k_norm = lambda p, k, ctx: k
        else:   # not rotated: queries and key at position 0
            rotary = self.index_rotary
            self.index_rotary = lambda q, k, qp, kp: rotary(
                q, k, jnp.zeros_like(qp), jnp.zeros_like(kp))
        try:
            return indexer(self, params, x, ctx, position_ids)
        finally:
            self.index_k_norm, self.index_rotary = kept

    return broken


@pytest.mark.parametrize("how", ["not LayerNorm'd", "not rotated"])
def test_an_index_key_made_otherwise_is_told_apart(keye, sequence, monkeypatch, how):
    """The index key is LayerNorm'd (weight and bias) and rotated at its
    position: an indexer that leaves either out chooses other lines, and the
    logits show it."""
    tokens, want, _ = sequence
    monkeypatch.setattr(sparse_attention.SparseSelfAttention, "_indexer",
                        broken_indexer(how))
    got = paged_logits(keye, tokens, 8, "pallas")
    assert np.abs(got - want).max() > 50 * LOGIT_ATOL, how


def test_a_choice_made_a_head_and_not_a_token_is_told_apart(keye, sequence, monkeypatch):
    """ONE choice a token serves all 8 heads. A served path in which the heads
    of the second KV head attend over the lines chosen for the token BEFORE
    (their own choice) gives other logits."""
    tokens, want, _ = sequence
    walk = sparse_rows.walk_rows

    def per_head(*, attend_single, attend_chunk, queries, **rest):
        n = queries.shape[1]

        def split(attend):
            def twice(tables, seen, q, chosen, *tiles):
                shifted = jnp.roll(chosen, 1, axis=-2).at[..., 0, :].set(chosen[..., 0, :])
                own = attend(tables, seen, q, chosen, *tiles)
                other = attend(tables, seen, q, shifted, *tiles)
                return jnp.concatenate([own[..., :n // 2, :], other[..., n // 2:, :]], -2)
            return twice

        return walk(attend_single=split(attend_single), attend_chunk=split(attend_chunk),
                    queries=queries, **rest)

    monkeypatch.setattr(sparse_attention, "walk_rows", per_head)
    got = paged_logits(keye, tokens, 8, "pallas")
    assert np.abs(got - want).max() > 50 * LOGIT_ATOL


# ---- the line of three leaves -------------------------------------------

def test_the_pool_is_one_line_of_three_leaves_a_token_a_layer(keye):
    engine = engine_of(keye)
    pools, stats = engine.pools, engine.stats_snapshot()
    assert pools.kinds is None or not pools.lines
    assert pools.kv_lines == SPARSE_LAYERS
    assert engine.sparse_layers == stats["sparse_layers"] == SPARSE_LAYERS
    assert engine.latent_layers == 0
    # K and V as the probe gave them, and the indexer's key with no head axis
    assert [a.shape for a in pools.pool_k] == [(257, 4, KV_HEADS, HEAD_DIM)] * SPARSE_LAYERS
    assert [a.shape for a in pools.pool_v] == [(257, 4, KV_HEADS, HEAD_DIM)] * SPARSE_LAYERS
    assert [a.shape for a in pools.pool_i] == [(257, 4, INDEX_DIM)] * SPARSE_LAYERS
    # the state: the four of the pools, then the third leaf
    state = pools.state()
    assert len(state) == 5 and state[2] is None and state[3] is None
    assert state[4] is pools.pool_i
    assert stats["kv_line_bytes"] == pools.line_bytes == SPARSE_LAYERS * LINE_BYTES
    assert pools.device_bytes() == 257 * 4 * SPARSE_LAYERS * LINE_BYTES
    # at Keye-VL-2.0's sizes in bf16: 1,024 + 1,024 + 128 B a (token, layer),
    # 8,704 B a token over the four layers held
    assert (2 * 4 * 128 + 64) * 2 == 2176 and 4 * 2176 == 8704


def test_a_two_leaf_stacks_state_is_what_it_was():
    """A stack without an indexer: four entries, no third leaf, its views
    carry none; the round trip through the views gives the state back."""
    dense = inference_of(keye_config(
        index_n_heads=None, index_head_dim=None, index_topk=None))
    engine = engine_of(dense)
    pools = engine.pools
    assert pools.pool_i is None and len(pools.state()) == 4
    assert engine.sparse_layers == 0
    assert pools.line_bytes == SPARSE_LAYERS * 2 * KV_HEADS * HEAD_DIM * 4
    views = build_layer_views(
        pools.state(), jnp.zeros((4, 64), jnp.int32), jnp.zeros((4,), jnp.int32),
        kinds=pools.kinds)
    assert all(v.pool_i is None for v in views)
    state = state_from_views(views)
    assert len(state) == 4 and state[0][0] is pools.pool_k[0]


def test_the_views_carry_the_third_leaf_and_give_it_back_last(keye):
    engine = engine_of(keye)
    state = engine._pool_state()
    views = build_layer_views(
        state, jnp.zeros((4, 64), jnp.int32), jnp.zeros((4,), jnp.int32),
        jnp.ones((4,), jnp.int32), kinds=engine.pools.kinds)
    assert len(views) == SPARSE_LAYERS
    assert all(v.pool_i is state[4][i] for i, v in enumerate(views))
    back = state_from_views(views)
    assert len(back) == 5 and all(a is b for a, b in zip(back[4], state[4]))
    engine.pools.absorb_state(back)
    assert engine.pools.pool_i is back[4] and engine.pools.lines == ()


def test_the_one_scatter_writes_all_three_leaves_through_the_same_slots():
    view = PagedKVCacheView(
        pool_k=jnp.zeros((3, 4, 2, 8)), pool_v=jnp.zeros((3, 4, 2, 8)),
        pool_i=jnp.zeros((3, 4, 5)), block_table=jnp.zeros((1, 2), jnp.int32),
        context_len=jnp.zeros((1,), jnp.int32))
    flat = jnp.asarray([5, 9])
    new = paged_scatter_kv(view, flat, jnp.ones((2, 2, 8)), 2 * jnp.ones((2, 2, 8)),
                           3 * jnp.ones((2, 5)))
    for pool, value in ((new.pool_k, 1), (new.pool_v, 2), (new.pool_i, 3)):
        flat_pool = np.asarray(pool).reshape(12, -1)
        assert (flat_pool[[5, 9]] == value).all()
        assert not np.delete(flat_pool, [5, 9], axis=0).any()
    # without index keys the third leaf is left alone (every other mixer)
    assert paged_scatter_kv(view, flat, jnp.ones((2, 2, 8)), jnp.ones((2, 2, 8))
                            ).pool_i is view.pool_i


def test_copy_on_write_forks_all_three_leaves(keye):
    engine = engine_of(keye)
    pools = engine.pools
    for arrs in (pools.pool_k, pools.pool_v, pools.pool_i):
        for i in range(len(arrs)):
            arrs[i] = arrs[i].at[3].set(1.5 + i)
    engine._apply_cow([(3, 7)])
    for arrs in (pools.pool_k, pools.pool_v, pools.pool_i):
        for i, pool in enumerate(arrs):
            assert (np.asarray(pool[7]) == 1.5 + i).all() and not np.asarray(pool[8]).any()


# ---- through the engine --------------------------------------------------

def test_the_engine_serves_what_the_references_full_forward_gives(keye, undisturbed):
    """Prefill in chunks of 8 whose edges fall mid-prompt, four rows at once
    and a fifth in a reused slot, then decode, one tick ahead: ticks mix chunk
    rows and decode rows, token-major, contexts of 40-206 against index_topk
    16."""
    requests, want = undisturbed
    engine = engine_of(keye)
    got = served(engine, requests, 6)
    assert [got[i] for i in range(len(requests))] == want
    assert len({tuple(w) for w in want}) > 1  # the weights say something
    # one tick ahead: most ticks were issued before the last one was read
    assert engine.ticks_overlapped > sum(engine.ticks_synchronous.values())
    # the index keys' leaf was written where K and V were
    assert np.abs(np.asarray(engine.pools.pool_i[0])[1:]).max() > 0


def test_a_preempted_and_recomputed_row_is_an_undisturbed_one(keye, undisturbed):
    """A pool too small for the rows forces recompute-style preemption: the
    evicted sequence re-enters at context 0 and rewrites its lines."""
    requests, want = undisturbed
    engine = engine_of(keye, num_blocks=70)
    got = served(engine, requests, 6)
    assert engine.scheduler.preemption_count > 0
    assert any(s.preemptions for s in engine.finished)
    assert [got[i] for i in range(len(requests))] == want


def test_a_choice_of_other_lines_serves_other_tokens(keye, undisturbed, monkeypatch):
    """With the indexer's choice replaced by the FIRST lines of a row the
    engine's tokens differ: the served tokens depend on the choice."""
    choose = sparse_attention.threshold_choice

    def first_lines(scores, visible, topk):
        return choose(-jnp.cumsum(jnp.ones_like(scores), axis=-1), visible, topk)

    monkeypatch.setattr(sparse_attention, "threshold_choice", first_lines)
    requests, want = undisturbed
    got = served(engine_of(keye), requests, 6)
    assert [got[i] for i in range(len(requests))] != want


def test_a_tie_over_room_is_filled_by_position_and_counted(keye, reference, tmp_path):
    """The fill of ties by position runs only in a call whose kept queries
    have more visible scores at their threshold than room, and the engine
    counts those calls on the tick's one host read. A first sparse layer whose
    index weights are zero scores every line 0.0: its rows tie everywhere, and
    from the 17th line on (index_topk 16) a query's ties are more than its
    room. The engine serves the reference's tokens (a tie goes to the lower
    position in both), ``serve.emit`` carries ``tie_breaks`` 1 from the tick
    whose chunk passes 16 lines on, the counter adds them up; the model as it
    is counts none."""
    first = next(name for name in sorted(keye.params)
                 if "index_w_proj" in keye.params[name].get("mixer", {}))
    layer = keye.params[first]
    weight = layer["mixer"]["index_w_proj"]["weight"]
    tied = TransformerInferenceModule(keye.config, keye.module, {
        **keye.params, first: {**layer, "mixer": {
            **layer["mixer"], "index_w_proj": {"weight": jnp.zeros_like(weight)}}}})
    prompt = prompts((20,), seed=11)[0]
    want = prompt + reference_walk.greedy_by_reference(
        lambda tokens: reference_logits(tied, reference, tokens), [prompt], 3,
        least_margin=0)[0]
    for inf, tie_breaks in ((tied, [0, 0, 1, 1, 1]), (keye, [0] * 5)):
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


def test_the_spans_say_what_was_scored_chosen_and_read(keye, tmp_path):
    """``serve.mixed`` of a sparse model of either kind: ``sparse_layers``,
    ``index_lines``, ``index_pairs``, ``chosen_pairs``, counted on the host
    from the tick's row lengths, and ``sparse_single_rows``, the rows of one
    token (the paged kernel's, under their masks); the three counters add them
    up over the layers; no latent field."""
    engine = engine_of(keye, num_slots=1)
    obs.start_capture(str(tmp_path))
    try:
        served(engine, prompts((40,), seed=7), 3)
    finally:
        capture = obs.stop_capture()
    spans = [f for n, _, _, f in capture.spans if n == "serve.mixed"]
    # 5 chunks of 8, then 2 decode ticks (the first token comes off the last chunk)
    assert [f["tokens"] for f in spans] == [8] * 5 + [1, 1]
    assert all(f["sparse_layers"] == SPARSE_LAYERS for f in spans)
    assert not any("latent_pairs" in f or "latent_layers" in f for f in spans)
    assert [f["index_lines"] for f in spans] == [8, 16, 24, 32, 40, 41, 42]
    # a chunk at context c: sum over its 8 queries of c + p + 1 visible lines
    assert [f["index_pairs"] for f in spans] == [
        8 * c + 36 for c in (0, 8, 16, 24, 32)] + [41, 42]
    # ... of which a query keeps min(16, what it sees)
    assert [f["chosen_pairs"] for f in spans] == [36, 100, 128, 128, 128, 16, 16]
    assert [f["chosen_lines"] for f in spans] == [8, 16, 16, 16, 16, 16, 16]
    assert capture.counters["serve_index_lines_read_total"] == SPARSE_LAYERS * sum(
        f["index_lines"] for f in spans)
    assert capture.counters["serve_sparse_chosen_pairs_total"] == SPARSE_LAYERS * sum(
        f["chosen_pairs"] for f in spans)
    # the two decode ticks' rows went through the paged kernel, a layer each
    assert [f["sparse_single_rows"] for f in spans] == [0] * 5 + [1, 1]
    assert capture.counters["serve_sparse_single_rows_total"] == SPARSE_LAYERS * 2
    assert obs.kernel_build_count("masked_gqa_attention", interpret=True) > 0
    assert obs.kernel_build_count("masked_gqa_attention", interpret=False) == 0
    assert obs.kernel_build_count("paged_attention", interpret=True) > 0


def test_indexer_and_attention_over_the_chosen_lines_have_scopes_of_their_own(keye):
    """``attn`` names the mixer; inside it ``indexer`` (projections, LayerNorm,
    rotary, scores and choice), ``index_select`` (scores and choice) and
    ``sparse_attend`` (the window's gather and the attention): what the
    benchmark's readers look up. A stack without an indexer has no ``attn``
    scope, as before."""
    import re

    def op_names(inf):
        engine = engine_of(inf, num_slots=1)
        views = build_layer_views(
            engine._pool_state(), jnp.arange(1, 65, dtype=jnp.int32)[None],
            jnp.zeros((1,), jnp.int32), jnp.full((1,), 8, jnp.int32),
            kinds=engine.pools.kinds)
        batch = inf._make_batch(jnp.ones((1, 8), jnp.int32), jnp.arange(8)[None])
        text = jax.jit(lambda p, v: inf._run_layers(p, batch, v, None)[0]).lower(
            inf.params, views).compile().as_text()
        return set(re.findall(r'op_name="([^"]*)"', text))

    names = op_names(keye)
    # (the row walk's loops and branches put their own names in between)
    for scope in (r"/attn/indexer/", r"/attn/(\S+/)?indexer/index_select/",
                  r"/attn/(\S+/)?sparse_attend/"):
        assert any(re.search(scope, n) for n in names), scope
    assert not any(re.search(r"/moe/.*/indexer/|/indexer/.*/sparse_attend/", n)
                   for n in names)
    dense = inference_of(keye_config(
        index_n_heads=None, index_head_dim=None, index_topk=None))
    assert not any(re.search(r"/(attn|indexer|sparse_attend)/", n)
                   for n in op_names(dense))


# ---- refused by name -------------------------------------------------------

def test_an_int8_pool_is_refused_by_name(keye):
    with pytest.raises(ValueError, match="sparse attention layer's cache line has an "
                                         "index key.*kv_dtype='native'"):
        engine_of(keye, kv_dtype="int8")


def test_the_prefix_cache_is_refused_by_name(keye):
    with pytest.raises(ValueError, match="enable_prefix_cache with sparse attention "
                                         "layers"):
        engine_of(keye, enable_prefix_cache=True)


@pytest.mark.parametrize("topology,arch,message", [
    ({"model_parallel_size": 2}, {}, "layer_pattern with model_parallel_size 2"),
    ({}, {"index_topk": None}, "\\['index_n_heads', 'index_head_dim'\\] without "
                               "\\['index_topk'\\]"),
    ({}, {"layer_pattern": ["mamba", "moe"] * 3}, "without ONE kind of attention layer"),
    ({}, {"attention_qkv_in_one": True, "attention_num_kv_heads": None},
     "index_\\* with attention_qkv_in_one"),
    ({}, {"relative_position_embedding_type": "none"}, "indexer's whole head is rotary"),
    ({}, {"index_head_dim": 15}, "index_head_dim 15 is odd"),
])
def test_a_layout_the_stack_does_not_build_is_refused_by_name(topology, arch, message):
    with pytest.raises(ValueError, match=message):
        keye_config(topology, **arch)


def test_training_and_cached_generate_are_refused_by_name(keye):
    from scaling_tpu.nn.base_layer import ForwardContext

    with pytest.raises(NotImplementedError, match="layer_pattern stack is served"):
        keye.module.forward(keye.params, {}, ForwardContext())
    with pytest.raises(ValueError, match="cached generate\\(\\) keeps dense KV"):
        keye.generate([1, 2, 3], max_tokens=2)


def test_uncached_generate_is_the_references_full_forward(keye, reference):
    """The uncached pass: the unfused attention under the mask of the chosen
    lines, the program's own uncached truth."""
    tokens = prompts((50,), seed=9)[0]
    want = reference_logits(keye, reference, tokens)
    batch = keye._make_batch(jnp.asarray(tokens, jnp.int32)[None],
                             jnp.arange(50, dtype=jnp.int32)[None])
    got = np.asarray(jax.jit(
        lambda p: keye._run_layers(p, batch, None, None)[0])(keye.params)[0])
    np.testing.assert_allclose(got, want, atol=LOGIT_ATOL)
