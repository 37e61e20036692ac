"""A stack of dots3-note blocks (``layer_pattern``: SPARSE ``latent`` attention
kept in pages beside ``window_latent`` attention of other sizes kept as a ring
of latent lines a slot, a head-wise gate on both, the latents rescaled, then a
dense or a sigmoid-routed FFN with a shared expert) through ``ServeEngine``:
prefill in chunks then decode past the window, past ``index_topk`` and round
the ring against the plain reference's full forward, on logits, through the
walk's kernel and through its gather form; the engine's tokens with rows that
step beside rows that chunk; a preempted row recomputed; bfloat16 statistics
and each deliberate fault (a window one line short, no gate, no rescale, one
rotary base for both kinds) seen by the comparison of logits; the eight shares
of the routed layer; what is refused, by name; pages and rings in one state,
the gauges, the span fields and the counter."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import cells
from scaling_tpu import obs
from scaling_tpu.models.transformer import TransformerConfig
from scaling_tpu.models.transformer.inference import TransformerInferenceModule
from scaling_tpu.models.transformer.model import init_model
from scaling_tpu.nn.latent_attention import LatentSelfAttention
from scaling_tpu.nn.sparse_latent_attention import SparseLatentSelfAttention
from scaling_tpu.nn.window_attention import ring_lines
from scaling_tpu.nn.window_latent_attention import (
    LatentRingView, WindowLatentSelfAttention,
)
from scaling_tpu.serve.engine import EngineConfig, ServeEngine
from scaling_tpu.serve.kvcache import build_layer_views

from . import reference_walk

VOCAB, HIDDEN, WINDOW, CHUNK, TOPK = 96, 64, 9, 8, 12
BRANCH_SCALE = 4.0
# the published pattern's head: a dense full block, then one period F S S S
PATTERN = ["latent", "mlp", "latent", "moe"] + ["window_latent", "moe"] * 3
TOPOLOGY = {"model_parallel_size": 1, "pipe_parallel_size": 1,
            "data_parallel_size": 1, "micro_batch_size": 1,
            "gradient_accumulation_steps": 1}
ARCH = {"vocab_size": VOCAB, "hidden_size": HIDDEN, "num_layers": len(PATTERN),
        "layer_pattern": PATTERN,
        "num_attention_heads": 8, "q_lora_rank": 32, "kv_lora_rank": 16,
        "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
        "index_n_heads": 4, "index_head_dim": 16, "index_topk": TOPK,
        "window_size": WINDOW,
        "window_latent_num_attention_heads": 4, "window_latent_q_lora_rank": 32,
        "window_latent_kv_lora_rank": 32, "window_latent_qk_nope_head_dim": 24,
        "window_latent_qk_rope_head_dim": 8, "window_latent_v_head_dim": 16,
        "window_latent_rotary_embedding_base": 50000,
        "rotary_embedding_base": 80000000,
        "latent_lora_rescale": True, "attention_gate": "per_head",
        "attention_bias": False,
        "mlp_type": "swiglu", "mlp_factor": 2.0, "mlp_bias": False,
        "moe_num_experts": 16, "moe_top_k": 3, "moe_expert_width": 32,
        "moe_glu": True, "moe_router": "sigmoid_bias", "moe_norm_topk_prob": True,
        "moe_norm_topk_eps": 1e-20, "moe_routed_scaling_factor": 1.0,
        "moe_shared_expert_width": 32, "moe_experts_first": 0, "moe_experts_held": 4,
        "activation_function": "silu", "norm_type": "rms",
        "layernorm": {"layernorm_epsilon": 1e-5},
        "relative_position_embedding_type": "rotary", "sequence_length": 128,
        "precision": "float32", "weight_tying": False}


def dots3_config(topology=None, **arch):
    return TransformerConfig.from_dict({
        "topology": {**TOPOLOGY, **(topology or {})},
        "transformer_architecture": {**ARCH, **arch},
        "data": {}, "logger": {"log_dir": None}})


@pytest.fixture(scope="module")
def dots3():
    config = dots3_config()
    module = init_model(config, None)
    params = module.init_params(jax.random.PRNGKey(3))
    # away from the init: norms off one; branches that are no small steps, a
    # router that chooses, and gates that differ by head and by token
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
    name = "layered_latent_moe_decoder"
    return (cells.load_module(cells.ROOT, "reference", name, cells.REFERENCE_CONTRACT),
            cells.load_module(cells.ROOT, "views", name, cells.VIEW_CONTRACT))


def prompts(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, VOCAB, size=n).tolist() for n in lengths]


# the ring is 16 lines (window 9, chunks of 8): 56 positions wrap it thrice,
# and pass index_topk 12 after the second chunk
TOKENS = prompts((56,), seed=5)[0]
RING = ring_lines(WINDOW, CHUNK)


def by_reference(inf, reference, tokens, chosen_out=None):
    ref, view = reference
    return np.asarray(ref.forward(
        view.reference_weights(inf.params, ARCH), jnp.asarray(tokens),
        view.reference_spec(ARCH), chosen_out=chosen_out))


@pytest.fixture(scope="module")
def wanted(dots3, reference):
    """The reference's full forward over ``TOKENS``: logits at every position."""
    return by_reference(dots3, reference, TOKENS)


def engine_of(inf, **config):
    return ServeEngine(inf, EngineConfig(**{
        "num_slots": 4, "block_size": 4, "num_blocks": 4 * 16 + 1,
        "max_blocks_per_seq": 16, "token_budget": 64, "prefill_chunk": CHUNK,
        "enable_prefix_cache": False, **config}))


def one_row_engine(inf):
    return engine_of(inf, num_slots=1, num_blocks=64 // 4 + 1,
                     max_blocks_per_seq=64 // 4)


walk = reference_walk.paged_walk


# float32 on both sides: what separates the absorbed forms over pages and
# rings from the reference's expanded full forward is the order of float32
# sums (the largest difference seen is 2e-6 at logits of deviation ~0.9)
LOGIT_ATOL = 1e-4
# two chunks of prefill, then decode one by one: 40 decode rows wrap the ring
# of 16 twice and more
PREFILL_THEN_DECODE = [CHUNK] * 2 + [1] * (len(TOKENS) - 2 * CHUNK)


@pytest.mark.parametrize("paged_kernel", ["xla", "pallas"])
def test_chunks_then_decode_through_pages_and_rings_are_the_references_full_forward(
        dots3, reference, wanted, paged_kernel):
    got, state = walk(dots3, one_row_engine(dots3), TOKENS,
                      PREFILL_THEN_DECODE, paged_kernel)
    assert got.shape == wanted.shape == (len(TOKENS), VOCAB)
    np.testing.assert_allclose(got, wanted, atol=LOGIT_ATOL)
    assert wanted.std() > 0.3    # the logits say something
    # the state: two pools of two leaves (the sparse full layers: the whole
    # latent line, the index key), then three rings of 16 lines a slot, one leaf
    assert [a.shape[1:] for a in state[0]] == [(4, 16 + 128)] * 2
    assert [a.shape[1:] for a in state[1]] == [(4, 16)] * 2
    assert len(state) == 5 and [a.shape for a in state[4]] == [
        (1, RING, 32 + 128)] * 3 and RING == 16
    # both mechanisms cut: past 12 lines a full layer's query chooses, past 9
    # a windowed layer's sees its window alone
    seen = []
    by_reference(dots3, reference, TOKENS, chosen_out=seen)
    assert [int(s[-1].sum()) for s in seen] == [TOPK, TOPK, WINDOW, WINDOW, WINDOW]


def test_a_chunk_that_straddles_the_rings_end_reads_what_it_must(dots3, wanted):
    """Chunks whose edges fall at 4, 12, 20, ...: the second covers positions
    12-19, lines 12-15 then 0-3, and its first query still reads positions
    4-11; then chunks all the way, each over lines the one before wrote."""
    sizes = [4] + [CHUNK] * 6 + [1] * 4
    for kernel in ("xla", "pallas"):
        got, _ = walk(dots3, one_row_engine(dots3), TOKENS, sizes, kernel)
        np.testing.assert_allclose(got, wanted, atol=LOGIT_ATOL)


def test_a_slot_reused_after_a_longer_row_sees_nothing_of_it(dots3, reference):
    engine = one_row_engine(dots3)
    _, state = walk(dots3, engine, TOKENS, PREFILL_THEN_DECODE, "pallas")
    short = prompts((11,), seed=9)[0]
    got, _ = walk(dots3, engine, short, [CHUNK, 1, 1, 1], "pallas", state=state)
    np.testing.assert_allclose(got, by_reference(dots3, reference, short),
                               atol=LOGIT_ATOL)


def uncached_logits(inf):
    """The uncached pass over ``TOKENS``, traced anew on every call."""
    ids = jnp.asarray(TOKENS, jnp.int32)[None]
    batch = inf._make_batch(ids, jnp.arange(len(TOKENS), dtype=jnp.int32)[None])
    return np.asarray(jax.jit(
        lambda p: inf._run_layers(p, batch, None, None)[0])(inf.params)[0])


def test_the_uncached_pass_is_the_references_too(dots3, wanted):
    np.testing.assert_allclose(uncached_logits(dots3), wanted, atol=LOGIT_ATOL)


def mixers(inf, kind):
    return [layer.mixer for layer in inf.module.layers
            if isinstance(getattr(layer, "mixer", None), kind)]


def test_one_mixer_class_serves_both_kinds(dots3):
    full = mixers(dots3, SparseLatentSelfAttention)
    window = mixers(dots3, WindowLatentSelfAttention)
    assert (len(full), len(window)) == (2, 3)
    assert all(isinstance(m, LatentSelfAttention) for m in full + window)
    for name in ("_latents", "_up_weights", "_expanded", "_query_line", "_project_out"):
        assert getattr(WindowLatentSelfAttention, name) is getattr(LatentSelfAttention, name)
    assert (full[0].num_heads, full[0].kv_lora_rank, full[0].kv_scale) == (8, 16, 2.0)
    assert (window[0].num_heads, window[0].kv_lora_rank) == (4, 32)
    assert window[0].kv_scale == window[0].q_scale == full[0].q_scale == 2 ** 0.5
    assert full[0].rotary_embedding is not window[0].rotary_embedding


@pytest.mark.parametrize("fault", [
    "a window of 8 lines", "the gate skipped", "the rescale skipped",
    "one rotary base for both kinds"])
def test_each_deliberate_fault_moves_the_logits_past_the_tolerance(
        dots3, wanted, monkeypatch, fault):
    """The comparison of logits sees each mechanism: a window one line short
    (the off-by-one reading of ``sliding_window_size``), a run that skips the
    gate or the latents' rescale, or turns the windowed layers' heads by the
    full layers' base moves a logit by more than the benchmark's 0.05."""
    full = mixers(dots3, SparseLatentSelfAttention)
    window = mixers(dots3, WindowLatentSelfAttention)
    if fault == "a window of 8 lines":
        for m in window:
            monkeypatch.setattr(m, "window_size", WINDOW - 1)
    elif fault == "the gate skipped":
        for m in full + window:
            monkeypatch.setattr(m, "parts", m.parts[:-1])
    elif fault == "the rescale skipped":
        for m in full + window:
            monkeypatch.setattr(m, "q_scale", 1.0)
            monkeypatch.setattr(m, "kv_scale", 1.0)
    else:
        for m in window:
            monkeypatch.setattr(m, "rotary_embedding", full[0].rotary_embedding)
    got = uncached_logits(dots3)
    assert np.abs(got - wanted).max() > 0.05 > 100 * LOGIT_ATOL


def test_bfloat16_statistics_fail_the_tolerance(dots3, wanted, monkeypatch):
    """The tolerance is tight enough to see a lower precision: with the
    softmax's statistics of the windowed layers' served walk (scores, maximum,
    sum) in bfloat16, or the RMSNorms' (the blocks', the latent norms'), the
    same comparison fails by more than an order of magnitude."""
    from scaling_tpu.nn import norm, window_latent_attention

    def bf16_softmax(self, q_line, view, at):
        held = window_latent_attention.line_positions(at.last, view.line.shape[1])[at.row]
        visible = (at.real[:, None] & (held >= 0) & (held <= at.at[:, None])
                   & (held > at.at[:, None] - self.window_size))
        lines = view.line[at.row]
        s = jnp.einsum("tnc,twc->tnw", q_line, lines).astype(jnp.bfloat16)
        s = jnp.where(visible[:, None, :], s * self.scaling_factor, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("tnw,twc->tnc", p.astype(lines.dtype),
                          lines[..., :self.kv_lora_rank])

    with monkeypatch.context() as patch:
        patch.setattr(WindowLatentSelfAttention, "_attend_gathered_rings", bf16_softmax)
        got, _ = walk(dots3, one_row_engine(dots3), TOKENS, PREFILL_THEN_DECODE, "xla")
    assert np.abs(got - wanted).max() > 30 * LOGIT_ATOL

    def bf16_statistics(self, params, x, ctx):
        xb = x.astype(jnp.bfloat16)
        var = jnp.mean(jnp.square(xb), axis=-1, keepdims=True)
        y = xb * jax.lax.rsqrt(var + jnp.bfloat16(self.config.layernorm_epsilon))
        return (y * params["weight"].astype(jnp.bfloat16)).astype(x.dtype)

    monkeypatch.setattr(norm.RMSNorm, "__call__", bf16_statistics)
    got, _ = walk(dots3, one_row_engine(dots3), TOKENS, PREFILL_THEN_DECODE, "xla")
    assert np.abs(got - wanted).max() > 30 * LOGIT_ATOL


REQUESTS = prompts((9, 37, 14, 3, 30), seed=2)
NEW_TOKENS = 24


def served_by(engine):
    for p in REQUESTS:
        engine.submit(p, max_new_tokens=NEW_TOKENS)
    got = {s.request.req_id: s.generated for s in engine.run_until_done()}
    return [got[i] for i in range(len(REQUESTS))]


@pytest.fixture(scope="module")
def served(dots3, tmp_path_factory):
    """ONE engine serving ``REQUESTS`` under a capture: prefill in chunks of 8
    whose edges fall mid-prompt, four rows at once and a fifth in a reused
    slot, then decode past the window, past ``index_topk`` and round the
    ring."""
    engine = engine_of(dots3)
    obs.start_capture(str(tmp_path_factory.mktemp("capture")))
    try:
        got = served_by(engine)
    finally:
        capture = obs.stop_capture()
    return engine, got, capture


def test_the_engine_serves_what_the_references_full_forward_gives(
        dots3, reference, served):
    """Ticks mix chunk rows and decode rows, token-major: every token the
    engine emitted is within the tolerance of the reference's best at its
    position, teacher-forced through the reference's full forward."""
    ref, view = reference
    engine, got, _ = served
    weights = view.reference_weights(dots3.params, ARCH)
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
    assert (stats["window_latent_layers"], stats["state_lines"], stats["kv_lines"],
            stats["sparse_layers"], stats["latent_layers"]) == (3, 3, 2, 2, 2)
    assert stats["line_layers"] == {"window_latent": 3}


def test_a_preempted_and_recomputed_row_is_an_undisturbed_one(dots3, served):
    """A pool too small for the rows forces recompute-style preemption: the
    evicted sequence re-enters at context 0, rewrites its pages and writes its
    ring lines over what it had left there."""
    engine = engine_of(dots3, num_blocks=30)
    got = served_by(engine)
    assert engine.scheduler.preemption_count > 0
    assert any(s.preemptions for s in engine.finished)
    assert got == served[1]


def test_the_rings_bytes_do_not_depend_on_the_context(dots3):
    """What the engine allocates for the three windowed layers is a fixed
    number of lines a slot: the same at four times the context, where the full
    layers' pools are four times as large; the gauges say how many."""
    small = engine_of(dots3)
    large = engine_of(dots3, num_blocks=4 * 64 + 1, max_blocks_per_seq=64)
    assert small.pools.state_bytes() == large.pools.state_bytes() == (
        3 * 4 * RING * (32 + 128) * 4)
    assert large.pools.device_bytes() > 3.9 * small.pools.device_bytes()
    assert [k.__name__ for k in small.pools.kinds] == (
        ["PagedKVCacheView"] * 2 + ["LatentRingView"] * 3)
    gauges = obs.get_registry().snapshot()["gauges"]
    assert gauges["serve_window_latent_ring_lines"] == RING == small.window_latent_ring_lines
    assert gauges["serve_window_latent_ring_gb"] == small.pools.state_bytes() / 1e9
    # a wider row needs a longer ring: window - 1 + row width lines at least
    assert engine_of(dots3, prefill_chunk=32, token_budget=160
                     ).window_latent_ring_lines == 64 >= WINDOW - 1 + 32
    # the cell's: 513 lines back under chunks of up to 512
    assert [ring_lines(513, 256), ring_lines(513, 512), ring_lines(513, 513)] == [
        1024, 1024, 1536]


def test_the_ticks_say_what_both_attentions_did(served):
    """``serve.mixed`` carries the windowed latent layers' rows by the form
    that attended, the ring lines their queries see and the (query, line)
    pairs, beside the sparse layers' fields; the counter sums the rows over
    the layers, by path."""
    engine, _, capture = served
    from types import SimpleNamespace

    ticks = [SimpleNamespace(fields=f) for n, _, _, f in capture.spans
             if n == "serve.mixed" and "window_latent_layers" in f]
    assert ticks and all(t.fields["window_latent_layers"] == 3
                         and t.fields["sparse_layers"] == 2
                         and t.fields["window_latent_lines"] == 3 for t in ticks)
    assert all(t.fields["window_latent_single_rows"] + t.fields["window_latent_chunk_rows"]
               == t.fields["window_latent_rows"] for t in ticks)
    # rows that step beside rows that chunk, in one tick
    assert any(t.fields["window_latent_single_rows"] and t.fields["window_latent_chunk_rows"]
               for t in ticks)
    # a tick of four decode rows deep in their sequences: each sees a full
    # window of 9 lines, and chooses 12 in a full layer
    deep = [t for t in ticks if t.fields["window_latent_single_rows"] == 4
            and t.fields["tokens"] == 4 and t.fields["window_latent_rows_past"] == 4]
    assert deep and all(t.fields["window_latent_pairs"] == 4 * WINDOW
                        and t.fields["window_latent_visible_lines"] == 4 * WINDOW
                        for t in deep)
    assert any(t.fields["chosen_pairs"] == 4 * TOPK < t.fields["index_pairs"] for t in deep)
    moved = {path: sum(v for k, v in capture.counters.items()
                       if k.startswith("serve_window_latent_rows_total") and path in k)
             for path in ("single", "chunk")}
    assert moved["single"] == 3 * sum(t.fields["window_latent_single_rows"] for t in ticks)
    assert moved["chunk"] == 3 * sum(t.fields["window_latent_chunk_rows"] for t in ticks) > 0


def test_both_kinds_have_scopes_of_their_own(dots3):
    """The lowered tick names ``window_latent_attn`` with ``window_latent_attend``
    and ``gate`` inside it, and the full layers' ``attn`` with ``indexer``,
    and ``gate``."""
    engine = one_row_engine(dots3)
    text = jax.jit(lambda p, state: dots3._run_layers(
        p, dots3._make_batch(jnp.zeros((1, CHUNK), jnp.int32),
                             jnp.arange(CHUNK, dtype=jnp.int32)[None]),
        build_layer_views(
            state, jnp.arange(1, 17, dtype=jnp.int32)[None],
            jnp.zeros((1,), jnp.int32), jnp.full((1,), CHUNK, jnp.int32),
            kinds=engine.pools.kinds), None, paged_kernel="xla")[0]).lower(
        dots3.params, engine._pool_state()).as_text(debug_info=True)
    for scope in ("window_latent_attn/window_latent_attend", "window_latent_attn/gate",
                  "attn/indexer", "attn/gate"):
        assert scope in text, scope


def test_eight_shares_of_the_routed_layer_add_up_to_the_uncut_layer(reference):
    """The published layer's form at a small size: 64 sigmoid-routed experts,
    8 a token by ``p + b`` (one group), gates ``p / (sum of the chosen p +
    1e-20)`` times 1, held whole against the same layer as 8 ranks of 8
    experts each (the router keeps its 64 outputs and its 8 a token; absent
    experts' gates are dropped AFTER the renormalisation): the ranks' routed
    parts plus the shared expert ONCE are the whole layer. In the reference,
    and in the program's ``serve``."""
    from scaling_tpu.nn.moe import ParallelMoEMLP

    ref, _ = reference
    H, F, E, K, HELD = 64, 32, 64, 8, 8
    make = lambda first, held: ParallelMoEMLP(
        io_features=H, intermediate_feature_factor=1.0, num_experts=E, top_k=K,
        norm_topk_prob=True, norm_topk_eps=1e-20, glu=True, intermediate=F,
        router="sigmoid_bias", routed_scaling_factor=1.0, shared_expert_width=F,
        experts_first=first, experts_held=held)
    whole = make(0, E)
    params = whole.init(jax.random.PRNGKey(0))
    params["router"]["weight"] = 20 * params["router"]["weight"]
    params["router"]["bias"] = 0.1 * jax.random.normal(jax.random.PRNGKey(1), (E,))
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 24, H))

    def rank_params(first, held):
        p = dict(params)
        for leaf in ("w_in", "w_out", "w_gate"):
            p[leaf] = params[leaf][first:first + held]
        return p

    def as_reference(p):
        return {"router": p["router"]["weight"], "router_bias": p["router"]["bias"],
                "shared_gate": p["shared_gate"], "shared_up": p["shared_in"],
                "shared_down": p["shared_out"]}, {
                "w_gate": p["w_gate"], "w_up": p["w_in"], "w_down": p["w_out"]}

    spec = {"top_k": K, "scale": 1.0, "gate_eps": 1e-20, "experts_first": 0,
            "shared": True}
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


WITHOUT_WINDOWED = {"layer_pattern": ["latent", "mlp"] * 5, "window_size": None,
                    **{k: None for k in ARCH if k.startswith("window_latent_")
                       and not k.endswith("base")}}


@pytest.mark.parametrize("arch, message", [
    ({"window_size": None}, "needs \\['window_size'\\]"),
    ({"window_latent_kv_lora_rank": None}, "needs \\['window_latent_kv_lora_rank'\\]"),
    ({"window_latent_qk_rope_head_dim": 7}, "is odd: rotary turns pairs"),
    ({"relative_position_embedding_type": "none"},
     "'latent' layers and relative_position_embedding_type"),
    ({"causal": False}, "'window_latent' layers and causal false"),
    ({"attention_gate": "elementwise"}, "attention_gate 'elementwise' with 'window'"),
    ({"hc_streams": 2}, "'window_latent' layers with hc_streams > 1"),
    ({"layer_pattern": ["latent", "mlp", "window", "moe"] + PATTERN[4:],
      "attention_num_kv_heads": 2, "attention_qkv_in_one": False},
     "'window_latent' layers beside 'window' layers|'window' layers with index_"),
    ({"layer_pattern": ["latent", "mlp", "mamba", "moe"] + PATTERN[4:]},
     "'window_latent' layers beside 'mamba' layers"),
    ({**WITHOUT_WINDOWED, "window_latent_v_head_dim": 16},
     "without 'window_latent' layers"),
    ({**WITHOUT_WINDOWED, "window_size": 9}, "without 'window' layers"),
    ({"layer_pattern": ["attention", "mlp"] * 5, "index_topk": None,
      "index_n_heads": None, "index_head_dim": None, "window_size": None,
      **{k: None for k in WITHOUT_WINDOWED if k.startswith("window_latent_")}},
     "latent_lora_rescale without 'latent' or 'window_latent' layers"),
])
def test_what_the_configuration_refuses_is_refused_by_name(arch, message):
    with pytest.raises(ValueError, match=message):
        dots3_config(**arch)


@pytest.mark.parametrize("topology, message", [
    ({"model_parallel_size": 2}, "layer_pattern with model_parallel_size 2"),
    ({"pipe_parallel_size": 2}, "layer_pattern with pipe_parallel_size 2"),
    ({"context_parallel_size": 2, "data_parallel_size": 1},
     "'window_latent' layers with context_parallel_size 2"),
])
def test_what_the_layout_refuses_is_refused_by_name(topology, message):
    with pytest.raises(ValueError, match=message):
        dots3_config(topology=topology)


@pytest.mark.parametrize("engine, message", [
    ({"enable_prefix_cache": True},
     "enable_prefix_cache with layers that keep a line a slot"),
    ({"kv_dtype": "int8"}, "kv_dtype 'int8' with window attention layers"),
])
def test_what_the_engine_refuses_is_refused_by_name(dots3, engine, message):
    with pytest.raises(ValueError, match=message):
        engine_of(dots3, **engine)


def test_training_and_a_dense_cache_are_refused_by_name(dots3):
    ids = jnp.asarray([TOKENS[:8]], jnp.int32)
    with pytest.raises(NotImplementedError,
                       match="layer_pattern stack is served, not trained"):
        dots3.module.forward(dots3.params, {"token_ids": ids}, dots3._make_ctx())
    with pytest.raises(ValueError, match="cached generate\\(\\) keeps dense KV"):
        dots3.generate(ids, max_tokens=2)
    layer = next(l for l in dots3.module.layers
                 if isinstance(getattr(l, "mixer", None), WindowLatentSelfAttention))
    assert layer.consumes is LatentRingView
    with pytest.raises(ValueError, match="a window_latent layer takes a LatentRingView"):
        layer(dots3.module._layer_params(dots3.params, 5),
              {"activations": jnp.zeros((1, 8, HIDDEN))}, dots3._make_ctx(),
              kv_cache=(jnp.zeros((1, 8, 32)),) * 2)
