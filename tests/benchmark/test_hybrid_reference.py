"""The program's stack of single-mixer layers (Nemotron-H's equations: Mamba-2
/ routed relu2 experts with a shared expert / GQA attention without positions)
against the plain reference ``benchmark/reference/hybrid_ssm_decoder.py`` on
seeded random weights, at a small size on the CPU: the full forward pass,
prefill in chunks and decoding through the paged cache and the recurrent-state
pool, a mixed tick, padding, the router, the shares of the experts, the
counts. Logits, never tokens."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import cells, model
from benchmark.reference import hybrid_ssm_decoder as ref
from benchmark.views import hybrid_ssm_decoder as view

KINDS = {"M": "mamba", "E": "moe", "*": "attention"}
ARCH = dict(
    vocab_size=96, hidden_size=48, num_layers=7,
    layer_pattern=[KINDS[c] for c in "MEMEM*E"],
    num_attention_heads=4, attention_num_kv_heads=2, attention_head_dim=16,
    attention_qkv_in_one=False, attention_bias=False, mlp_type="moe", mlp_bias=False,
    moe_num_experts=8, moe_top_k=3, moe_expert_width=40, moe_glu=False,
    moe_router="sigmoid_bias", moe_norm_topk_prob=True, moe_routed_scaling_factor=2.5,
    moe_shared_expert_width=56, activation_function="relu2",
    mamba_num_heads=4, mamba_head_dim=8, ssm_state_size=16, n_groups=2, conv_kernel=4,
    norm_type="rms", layernorm={"layernorm_epsilon": 1e-5},
    relative_position_embedding_type="none", sequence_length=128,
    precision="float32", causal=True, weight_tying=False)
TOPOLOGY = dict(model_parallel_size=1, pipe_parallel_size=1, data_parallel_size=1,
                micro_batch_size=1, gradient_accumulation_steps=1)
# float32 on both sides, the same mathematics in another order of summation
# (the program's one-chunk form of the recurrence against the reference's
# scan over time, its capacity buffers against every-expert-on-every-token,
# a paged cache and a state pool against none): logits of magnitude ~1-3
# agree to a few float32 roundings a layer. 3e-4 would already fail a bf16
# computation (2**-9 = 2e-3 a rounding), a renormalised or unscaled gate and
# a state that leaked from one sequence into the next (all below).
LOGIT_ATOL = 3e-4
CHUNK = 32


def build(**changes):
    from scaling_tpu.models.transformer.inference import TransformerInferenceModule
    from scaling_tpu.models.transformer.model import init_model

    arch = {**ARCH, **changes}
    config = model.transformer_config(
        {"transformer_architecture": arch, "topology": TOPOLOGY}, {})
    module = init_model(config, None)
    params = module.init_params(jax.random.PRNGKey(3))
    # norm weights start at one, D at one, the selection bias at zero: perturb
    # every leaf so that each takes part
    leaves, treedef = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(4), len(leaves))
    params = jax.tree.unflatten(treedef, [
        (x.astype(jnp.float32) + 0.1 * jax.random.normal(k, x.shape)).astype(x.dtype)
        for x, k in zip(leaves, keys)])
    return arch, TransformerInferenceModule(config, module, params)


def reference_logits(arch, params, tokens, **spec):
    return np.asarray(ref.forward(
        view.reference_weights(params, arch), jnp.asarray(tokens),
        {**view.reference_spec(arch), **spec}))


class Served:
    """The engine's state (paged KV pools + recurrent lines) and one jitted
    call of the stack over it, as ``ServeEngine``'s mixed program makes it:
    the tick's tokens packed token-major, ``packed_token_map`` from the rows'
    ``new_len``."""

    BLOCK, MAX_BLOCKS = 16, 8

    def __init__(self, inf, slots=1, row_width=CHUNK, kernel="pallas"):
        from scaling_tpu.nn.attention import packed_token_map
        from scaling_tpu.serve.engine import packed_batch_shape
        from scaling_tpu.serve.kvcache import (
            build_layer_views, init_pools, state_from_views)

        self.inf, self.slots, self.row_width = inf, slots, row_width
        self.pools = init_pools(inf, slots * self.MAX_BLOCKS + 1, self.BLOCK,
                                num_slots=slots)
        self.state = self.pools.state()
        self.table = 1 + jnp.arange(slots * self.MAX_BLOCKS, dtype=jnp.int32).reshape(
            slots, self.MAX_BLOCKS)
        kinds = self.pools.kinds

        def step(state, tokens, ctx, new_len):
            shape = packed_batch_shape(tokens.shape[0], row_width)
            tmap = packed_token_map(new_len, shape, row_width)
            pos = jnp.where(tmap.offset < new_len[tmap.row],
                            ctx[tmap.row] + tmap.offset, 0)
            views = build_layer_views(state, self.table, ctx, new_len, tmap, kinds=kinds)
            logits, new_views, load = inf._run_layers(
                inf.params, inf._make_batch(tokens.reshape(shape), pos), views, None,
                paged_kernel=kernel, moe_load=True)
            return logits.reshape(tokens.shape[0], -1), state_from_views(new_views), load

        self._step = jax.jit(step)

    def tick(self, rows, ctx):
        """``rows``: a list of token lists, one a slot ([]: an empty slot);
        ``ctx``: the tokens each slot's state has seen. Returns each row's
        logits (new_len, V) and the load."""
        new_len = [len(r) for r in rows]
        width = -(-max(sum(new_len), 1) // self.row_width) * self.row_width
        packed = np.zeros((width,), np.int32)
        flat = [t for r in rows for t in r]
        packed[:len(flat)] = flat
        logits, self.state, load = self._step(
            self.state, jnp.asarray(packed), jnp.asarray(ctx, jnp.int32),
            jnp.asarray(new_len, jnp.int32))
        logits, out, at = np.asarray(logits), [], 0
        for n in new_len:
            out.append(logits[at:at + n])
            at += n
        return out, np.asarray(load)


def served_logits(inf, tokens, prompt_len, chunk, kernel="pallas"):
    """Prefill ``tokens[:prompt_len]`` in chunks of ``chunk`` (their edges
    fall mid-prompt, the last one ragged) and decode the rest one token at a
    time through the cache and the state pool: the logits of every position."""
    served = Served(inf, kernel=kernel)
    logits, loads, done = [], [], 0
    while done < len(tokens):
        n = min(chunk, prompt_len - done) if done < prompt_len else 1
        out, load = served.tick([list(tokens[done:done + n])], [done])
        logits.append(out[0])
        loads.append(load)
        done += n
    return np.concatenate(logits), loads


# ---- (a) the system against the reference ---------------------------------

def test_full_forward_agrees_with_the_reference():
    arch, inf = build()
    tokens = np.random.default_rng(0).integers(1, arch["vocab_size"], 75)
    got = np.asarray(inf.logits(tokens)[0])   # 75 positions: two chunks of the scan
    want = reference_logits(arch, inf.params, tokens)
    np.testing.assert_allclose(got, want, atol=LOGIT_ATOL, rtol=0)
    assert np.abs(want).max() > 0.5  # the agreement is not that of zeros


@pytest.mark.parametrize("chunk", [32, 8, 5])
def test_chunked_prefill_then_decode_is_the_full_forward_pass(chunk):
    """Chunk edges fall mid-prompt (43 = 32 + 11 = 5 x 8 + 3 = 8 x 5 + 3); then
    four decode ticks; equal to the reference's full forward at EVERY position."""
    arch, inf = build()
    tokens = np.random.default_rng(2).integers(1, arch["vocab_size"], 47).astype(np.int32)
    got, loads = served_logits(inf, tokens, 43, chunk)
    np.testing.assert_allclose(got, reference_logits(arch, inf.params, tokens),
                               atol=LOGIT_ATOL, rtol=0)
    # every real position's top_k assignments a routed layer, held or not
    routed = arch["layer_pattern"].count("moe")
    assert sum(int(l.sum()) for l in loads) == 47 * arch["moe_top_k"] * routed


def test_the_kernel_and_the_gather_formulation_agree_on_the_stack():
    arch, inf = build()
    tokens = np.random.default_rng(3).integers(1, arch["vocab_size"], 40).astype(np.int32)
    a, _ = served_logits(inf, tokens, 37, 8, kernel="pallas")
    b, _ = served_logits(inf, tokens, 37, 8, kernel="xla")
    np.testing.assert_allclose(a, b, atol=LOGIT_ATOL, rtol=0)


def test_a_tick_that_mixes_decode_rows_chunk_rows_and_empty_slots():
    """Four slots: slot 0 decodes, slot 1 is empty, slot 2 streams a chunk
    mid-prompt, slot 3 starts a prompt; each row equals its own sequence's
    full forward, and the empty slot's lines are untouched bit for bit."""
    arch, inf = build()
    rng = np.random.default_rng(5)
    a, c, d = (rng.integers(1, arch["vocab_size"], n).astype(np.int32)
               for n in (20, 30, 7))
    served = Served(inf, slots=4, row_width=8)
    # bring slots 0 and 2 to where the mixed tick finds them; slot 1 keeps
    # what an earlier occupant left there
    served.tick([list(a[:8]), list(d[:5]), list(c[:8]), []], [0, 0, 0, 0])
    served.tick([list(a[8:16]), [], list(c[8:16]), []], [8, 5, 8, 0])
    served.tick([list(a[16:19]), [], [], []], [16, 5, 16, 0])
    before = jax.tree.map(np.asarray, served.state)
    out, _ = served.tick([[int(a[19])], [], list(c[16:22]), list(d)], [19, 5, 16, 0])
    after = jax.tree.map(np.asarray, served.state)
    want = {0: reference_logits(arch, inf.params, a)[19:20],
            2: reference_logits(arch, inf.params, c)[16:22],
            3: reference_logits(arch, inf.params, d)}
    for slot, logits in want.items():
        np.testing.assert_allclose(out[slot], logits, atol=LOGIT_ATOL, rtol=0)
    assert out[1].shape[0] == 0
    for lines_before, lines_after in zip(before[4] + before[5], after[4] + after[5]):
        assert np.array_equal(lines_before[1], lines_after[1])   # slot 1: bit for bit
        assert not np.array_equal(lines_before[0], lines_after[0])


def test_padding_takes_no_part_in_state_or_conv_tail():
    """A chunk of 5 real tokens in a row 8 wide leaves what a row exactly 5
    wide leaves, to a float32 rounding: the conv tail is the last 3 REAL
    inputs (a gather; the inputs themselves come out of a matmul of another
    shape), and what is no token has dt = 0: it decays nothing and adds
    nothing, but the sum runs over 8 terms, three of them zero, in another
    order. The mixed tick above holds the other half: a row that brings NO
    token keeps its lines bit for bit."""
    arch, inf = build()
    tokens = np.random.default_rng(6).integers(1, arch["vocab_size"], 5).astype(np.int32)
    states = []
    for row_width in (8, 5):
        served = Served(inf, row_width=row_width, kernel="xla")
        served.tick([list(tokens)], [0])             # 5 real (+ 3 padding)
        states.append(jax.tree.map(np.asarray, served.state))
    for wide, narrow in zip(states[0][5], states[1][5]):
        np.testing.assert_allclose(wide, narrow, rtol=1e-5, atol=1e-6)
        assert np.abs(wide).max() > 0
    for wide, narrow in zip(states[0][4], states[1][4]):
        np.testing.assert_allclose(wide, narrow, rtol=1e-5, atol=1e-8)
        assert np.abs(wide).max() > 0


def test_a_state_that_leaks_into_the_next_sequence_is_another_model(monkeypatch):
    """What the zero-at-context-0 rule guards: with the rule taken out, a slot
    reused by another sequence starts from its old occupant's state and misses
    the limit by far."""
    from scaling_tpu.nn import mamba

    arch, inf = build()
    rng = np.random.default_rng(7)
    old, new = (rng.integers(1, arch["vocab_size"], 24).astype(np.int32) for _ in range(2))
    want = reference_logits(arch, inf.params, new)

    def reuse():
        served = Served(inf, row_width=8, kernel="xla")
        served.tick([list(old[:8])], [0])
        return np.concatenate([served.tick([list(new[i:i + 8])], [i])[0][0]
                               for i in (0, 8, 16)])

    np.testing.assert_allclose(reuse(), want, atol=LOGIT_ATOL, rtol=0)
    real_where = mamba.jnp.where
    monkeypatch.setattr(mamba.Mamba2Mixer, "_serve", _serve_without_the_reset(
        mamba.Mamba2Mixer._serve))
    assert np.abs(reuse() - want).max() > 100 * LOGIT_ATOL
    del real_where


def _serve_without_the_reset(real):
    def serve(self, params, z, xBC, dt, view):
        # a context that is never 0: no row is fresh
        return real(self, params, z, xBC, dt, view._replace(
            context_len=view.context_len + 1))
    return serve


# ---- (c) the router ---------------------------------------------------------

def test_a_bias_that_changes_the_choice_leaves_the_gates_the_chosen_scores():
    """``b`` moves which experts are taken; a taken expert's gate stays
    ``2.5 x s_e / sum of the chosen s``: the bias never enters a gate."""
    from scaling_tpu.nn.moe import ParallelMoEMLP

    layer = ParallelMoEMLP(48, 1.0, 8, top_k=3, norm_topk_prob=True, glu=False,
                           intermediate=40, router="sigmoid_bias",
                           routed_scaling_factor=2.5)
    params = layer.init(jax.random.PRNGKey(0))
    params["router"]["weight"] = jax.random.normal(jax.random.PRNGKey(1), (48, 8))
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 16, 48))
    scores, gates, idx = layer._route(params, x)
    biased = {**params, "router": {**params["router"], "bias": jnp.asarray(
        [3.0, -3.0, 0, 0, 0, 0, 0, 3.0], jnp.float32)}}
    scores_b, gates_b, idx_b = layer._route(biased, x)
    np.testing.assert_array_equal(np.asarray(scores), np.asarray(scores_b))
    assert not np.array_equal(np.sort(idx, -1), np.sort(idx_b, -1))   # the choice moved
    assert (np.asarray(idx_b) == 0).any(-1).all() and not (np.asarray(idx_b) == 1).any()
    for s, g, i in ((scores, gates, idx), (scores_b, gates_b, idx_b)):
        chosen = np.take_along_axis(np.asarray(s), np.asarray(i), -1)
        np.testing.assert_allclose(
            np.asarray(g), 2.5 * chosen / chosen.sum(-1, keepdims=True), rtol=1e-6)
    # and the reference, given the same bias, makes the same choice
    arch, inf = build()
    tokens = np.random.default_rng(8).integers(1, arch["vocab_size"], 33)
    for i, kind in enumerate(arch["layer_pattern"]):
        if kind == "moe":
            inf.params[f"layer_{i + 1}"]["mixer"]["router"]["bias"] = jnp.asarray(
                [2.0, -2.0, 0, 1.0, 0, 0, -1.0, 0], jnp.float32)
    inf._logits_fn = None
    np.testing.assert_allclose(
        np.asarray(inf.logits(tokens)[0]), reference_logits(arch, inf.params, tokens),
        atol=LOGIT_ATOL, rtol=0)


@pytest.mark.parametrize("changed", [{"moe_routed_scaling_factor": 1.0},
                                     {"moe_norm_topk_prob": False}],
                         ids=["unscaled", "not-renormalised"])
def test_other_gates_are_another_model(changed):
    arch, inf = build()
    _, other = build(**changed)
    other.params = inf.params
    tokens = np.random.default_rng(1).integers(1, arch["vocab_size"], 40)
    want = reference_logits(arch, inf.params, tokens)
    assert np.abs(np.asarray(other.logits(tokens)[0]) - want).max() > 10 * LOGIT_ATOL


def test_the_view_refuses_equations_the_reference_does_not_compute():
    for key, value in (("moe_router", "softmax"), ("moe_glu", True),
                       ("relative_position_embedding_type", "rotary"),
                       ("activation_function", "silu")):
        with pytest.raises(SystemExit, match=key):
            view.reference_spec({**ARCH, key: value})
    with pytest.raises(SystemExit, match="layer_pattern"):
        view.reference_spec({**ARCH, "layer_pattern": None})


# ---- (d) the share ties to the model ---------------------------------------

def test_the_two_shares_add_up_to_the_uncut_layer_and_count_every_assignment():
    """One routed layer: the outputs of the shares [0, E/2) and [E/2, E), the
    shared expert counted once, add up to the uncut REFERENCE's layer; the
    held and the absent assignments make top_k a real position."""
    from scaling_tpu.nn import ActivationFunction
    from scaling_tpu.nn.moe import ParallelMoEMLP

    E, k, H, F, Fs = 8, 3, 48, 40, 56
    common = dict(io_features=H, intermediate_feature_factor=1.0, num_experts=E,
                  top_k=k, norm_topk_prob=True, glu=False, intermediate=F,
                  activation=ActivationFunction.RELU2,
                  router="sigmoid_bias", routed_scaling_factor=2.5,
                  shared_expert_width=Fs)
    whole = ParallelMoEMLP(**common)
    params = whole.init(jax.random.PRNGKey(0))
    params["router"]["weight"] = jax.random.normal(jax.random.PRNGKey(1), (H, E))
    params["router"]["bias"] = 0.3 * jax.random.normal(jax.random.PRNGKey(2), (E,))
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 16, H))
    real = jnp.arange(16)[None, :] < jnp.asarray([16, 9])[:, None]
    spec = {"top_k": k, "scale": 2.5, "experts_first": 0, "shared": True}
    p = {"router": params["router"]["weight"], "router_bias": params["router"]["bias"],
         "shared_up": params["shared_in"], "shared_down": params["shared_out"]}
    with jax.default_matmul_precision("highest"):
        want = np.stack([np.asarray(ref.routed_mlp(
            row, p, {"up": params["w_in"], "down": params["w_out"]}, spec)) for row in x])
        shared = np.asarray(ref.relu2(x @ p["shared_up"]) @ p["shared_down"])
    got_whole, load_whole = whole.serve(params, x, real)
    np.testing.assert_allclose(np.asarray(got_whole), want, atol=2e-5, rtol=0)
    assert load_whole.shape == (E,) and int(load_whole.sum()) == k * 25
    parts, loads = [], []
    for first in (0, E // 2):
        share = ParallelMoEMLP(**common, experts_first=first, experts_held=E // 2)
        assert jax.tree.map(jnp.shape, share.init(jax.random.PRNGKey(0)))["w_in"] == (
            E // 2, H, F)
        held = {**params, "w_in": params["w_in"][first:first + E // 2],
                "w_out": params["w_out"][first:first + E // 2]}
        y, load = share.serve(held, x, real)
        parts.append(np.asarray(y))
        loads.append(np.asarray(load))
        # the reference, given the same share, gives the same part
        part = np.stack([np.asarray(ref.routed_mlp(
            row, p, {"up": held["w_in"], "down": held["w_out"]},
            {**spec, "experts_first": first})) for row in x])
        np.testing.assert_allclose(parts[-1], part, atol=2e-5, rtol=0)
    # the shared expert is in both parts: counted once
    np.testing.assert_allclose(parts[0] + parts[1] - shared, want, atol=4e-5, rtol=0)
    for load, other in zip(loads, loads[::-1]):
        assert load.shape == (E // 2 + 1,)
        assert int(load.sum()) == k * 25           # held + absent = top_k a real position
        assert int(load[-1]) == int(other[:-1].sum())   # my absent are the other's held
    np.testing.assert_array_equal(np.concatenate([loads[0][:-1], loads[1][:-1]]),
                                  np.asarray(load_whole))
    # gates of absent experts are dropped, NOT renormalised over those present
    assert np.abs(parts[0] - shared).max() < np.abs(want - shared).max() * 1.5


def test_a_share_through_the_whole_stack_agrees_with_the_reference_given_the_same_share():
    arch, inf = build(moe_experts_first=4, moe_experts_held=4)
    assert inf.params["layer_2"]["mixer"]["w_in"].shape[0] == 4
    tokens = np.random.default_rng(9).integers(1, arch["vocab_size"], 47).astype(np.int32)
    got, loads = served_logits(inf, tokens, 43, 8)
    np.testing.assert_allclose(got, reference_logits(arch, inf.params, tokens),
                               atol=LOGIT_ATOL, rtol=0)
    routed = arch["layer_pattern"].count("moe")
    assert all(l.shape == (4 + 1,) for l in loads)
    assert sum(int(l.sum()) for l in loads) == 47 * arch["moe_top_k"] * routed
    assert sum(int(l[-1]) for l in loads) > 0      # some fell on absent experts


# ---- (e) the published counts ----------------------------------------------

def test_published_depth_experts_and_vocabulary_count_the_published_parameters():
    from scaling_tpu.models.transformer.model import init_model

    config = cells.load_json(cells.ROOT / "configs" / "nemotron3-nano-30b-a3b-serve.json")
    published = config["published"]
    pattern = [KINDS[c] for c in published["hybrid_override_pattern"]]
    assert (len(pattern), pattern.count("mamba"), pattern.count("moe"),
            pattern.count("attention")) == (52, 23, 23, 6)
    arch = {**config["transformer_architecture"], "layer_pattern": pattern,
            "num_layers": 52, "moe_experts_held": None,
            "vocab_size": published["vocab_size"]}
    shapes = model.param_shapes(init_model(model.transformer_config(
        {**config, "transformer_architecture": arch}, {}), None))
    assert model.count_params(shapes) == published["parameter_count"] == 31_577_940_288
    by_kind = {kind: model.count_params(shapes[f"layer_{pattern.index(kind) + 1}"])
               for kind in KINDS.values()}
    assert by_kind == {"mamba": 38_744_896, "moe": 1_297_468_160, "attention": 23_399_040}
    # as run: the leading 16, 64 experts held, half the vocabulary
    run = model.param_shapes(init_model(model.transformer_config(config, {}), None))
    assert model.count_params(run) == 5_282_534_208
    assert model.count_params(run["layer_2"]) == 658_885_376
    assert config["transformer_architecture"]["layer_pattern"] == pattern[:16]
    assert view.expert_param_count(config["transformer_architecture"], run) == \
        7 * 64 * 2 * 2688 * 1856
