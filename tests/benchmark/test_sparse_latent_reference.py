"""``reference/sparse_latent_moe_decoder.py`` and its view against the program
on seeded weights at a toy width: the program (expanded heads under the mask of
its own exact top-k where there is no cache) is the reference, logits and
chosen sets; YaRN's numbers at DeepSeek-V3.2-Exp's sizes (a real ramp); each
constant perturbed in the reference alone moves the logits, the choice left
out, off by one and the indexer's rope lanes misplaced among them; ties go to
the lower position; the 32 shares of a group-limited layer (8 groups x 4
ranks a group), the shared expert counted once, add up to the uncut layer; the
blocks in which the reference evaluates the softmax change nothing; the
published parameter counts by ``jax.eval_shape``."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import cells, model
from benchmark.control import lower_precision
from scaling_tpu.models.transformer import TransformerConfig
from scaling_tpu.models.transformer.inference import TransformerInferenceModule
from scaling_tpu.models.transformer.model import init_model

CONFIG = "deepseek-v3.2-exp-serve"
VOCAB = 128
TOPK = 16
TOPOLOGY = {"model_parallel_size": 1, "pipe_parallel_size": 1,
            "data_parallel_size": 1, "micro_batch_size": 1,
            "gradient_accumulation_steps": 1}
PUBLISHED = cells.load_json(cells.ROOT / "configs" / f"{CONFIG}.json")
# the published equations and constants at a toy width: hidden 256, 4 heads,
# latents of 96 / 64, heads of 32 + 16 / 32, an indexer of 4 heads x 32 that
# keeps 16 lines, 16 experts in 4 groups of which a token keeps 2, 4 a token,
# 4 held, YaRN at factor 8 over an original context of 32
ARCH = {**PUBLISHED["transformer_architecture"],
        "vocab_size": VOCAB, "hidden_size": 256, "num_layers": 6,
        "layer_pattern": ["latent", "mlp", "latent", "moe", "latent", "moe"],
        "num_attention_heads": 4, "q_lora_rank": 96, "kv_lora_rank": 64,
        "qk_nope_head_dim": 32, "qk_rope_head_dim": 16, "v_head_dim": 32,
        "index_n_heads": 4, "index_head_dim": 32, "index_topk": TOPK,
        "rope_scaling": {**PUBLISHED["transformer_architecture"]["rope_scaling"],
                         "factor": 8, "original_max_position_embeddings": 32},
        "mlp_factor": 2.5, "moe_num_experts": 16, "moe_top_k": 4,
        "moe_expert_width": 64, "moe_shared_expert_width": 64,
        "moe_experts_first": 0, "moe_experts_held": 4,
        "moe_n_group": 4, "moe_topk_group": 2,
        "sequence_length": 128, "precision": "float32"}


@pytest.fixture(scope="module")
def files():
    return (cells.load_module(cells.ROOT, "reference", "sparse_latent_moe_decoder",
                              cells.REFERENCE_CONTRACT),
            cells.load_module(cells.ROOT, "views", "sparse_latent_moe_decoder",
                              cells.VIEW_CONTRACT))


def build(arch, key=11):
    config = TransformerConfig.from_dict({
        "topology": TOPOLOGY, "transformer_architecture": arch,
        "data": {}, "logger": {"log_dir": None}})
    module = init_model(config, None)
    params = module.init_params(jax.random.PRNGKey(key))
    # away from the init: norms off one, biases that change choices
    leaves, treedef = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(key + 1), len(leaves))
    params = jax.tree.unflatten(treedef, [
        x + (0.2 * jax.random.normal(k, x.shape) if x.ndim == 1 else 0.0)
        for x, k in zip(leaves, keys)])
    return TransformerInferenceModule(config, module, params)


@pytest.fixture(scope="module")
def dsv32():
    return build(ARCH)


@pytest.fixture(scope="module")
def tokens():
    return jnp.asarray(np.random.default_rng(3).integers(1, VOCAB, size=80))


@pytest.fixture(scope="module")
def sound(files, dsv32, tokens):
    ref, view = files
    weights = view.reference_weights(dsv32.params, ARCH)
    spec = view.reference_spec(ARCH)
    chosen = []
    logits = np.asarray(ref.forward(weights, tokens, spec, chosen_out=chosen))
    return weights, spec, logits, [np.asarray(c) for c in chosen]


def test_the_program_is_the_reference(dsv32, tokens, sound):
    """The program's uncached pass (expanded heads under the mask of its own
    ``top_k``, its own rotary tables and norms) against the reference (a
    stable sort's ranks), float32 on both sides."""
    _, spec, want, chosen = sound
    assert spec["yarn"][:2] == (8.0, 32.0) and spec["experts_first"] == 0
    assert (spec["index_heads"], spec["index_dim"], spec["index_topk"]) == (4, 32, TOPK)
    assert (spec["n_group"], spec["topk_group"]) == (4, 2)
    got = np.asarray(dsv32.logits(tokens[None])[0])
    np.testing.assert_allclose(got, want, atol=5e-5)
    assert 0.2 < want.std() < 2.0   # the init's work: fresh logits of a size
    # every query chose min(16, what it sees), causal, and not just the last 16
    assert len(chosen) == 3
    for c in chosen:
        assert c.sum(axis=1).tolist() == [min(TOPK, t + 1) for t in range(80)]
        assert not np.triu(c, 1).any()
        assert any(not c[t, t - TOPK + 1:t + 1].all() for t in range(TOPK, 80))


def test_yarn_at_the_published_numbers(files):
    """DeepSeek-V3.2-Exp's rope_scaling: beta_fast 32 / beta_slow 1 give a
    real ramp from index 10 to 23 (Kimi's 1 / 1 gave a step); the softmax
    scale is 192^-0.5 x (0.1 ln 40 + 1)^2 = 0.135234. The program's tables and
    the reference's formula agree."""
    from benchmark.reference import latent_moe_decoder as block
    from scaling_tpu.nn import rotary

    _, view = files
    from benchmark.views import latent_moe_decoder as latent_view
    yarn = latent_view.yarn(PUBLISHED["transformer_architecture"])
    assert yarn == (40.0, 4096.0, 32.0, 1.0, 1.0, 1.0)
    assert block.yarn_range(64, 10000.0, yarn) == (10, 23)
    base = 10000.0 ** (-np.arange(0, 64, 2) / 64)
    got = np.asarray(block.inv_freq(64, 10000.0, yarn))
    np.testing.assert_allclose(got[:11], base[:11], rtol=1e-6)
    np.testing.assert_allclose(got[23:], base[23:] / 40, rtol=1e-6)
    ramp = (np.arange(11, 23) - 10) / 13
    np.testing.assert_allclose(got[11:23], base[11:23] * (1 - ramp) + base[11:23] / 40 * ramp,
                               rtol=1e-5)
    assert block.softmax_scale(128, 64, yarn) == pytest.approx(0.135234, abs=5e-7)
    assert block.yarn_m(40.0, 1.0) == pytest.approx(1.36889, abs=5e-6)
    scaling = rotary.RopeScalingConfig(**PUBLISHED["transformer_architecture"]["rope_scaling"])
    assert rotary.yarn_correction_range(scaling, 64, 10000.0) == (10, 23)
    np.testing.assert_allclose(rotary.yarn_inv_freq(scaling, 64, 10000.0), got, rtol=1e-6)
    assert 192 ** -0.5 * rotary.yarn_softmax_scale(scaling) == pytest.approx(
        0.135234, abs=5e-7)


@pytest.mark.parametrize("name,off", [
    ("index_topk", None), ("index_topk", TOPK - 1), ("index_topk", TOPK + 1),
    ("index_heads", 2), ("n_group", 1), ("topk_group", 1), ("topk_group", 3),
    ("scale", 2.5 * 1.25), ("gate_eps", 0.5), ("top_k", 3), ("eps", 1e-2),
    ("rope_base", 50000.0), ("yarn", None), ("yarn", (8.0, 32.0, 1.0, 1.0, 1.0, 1.0)),
    ("experts_first", 4), ("shared", False)])
def test_each_constant_perturbed_in_the_reference_alone_moves_the_logits(
        files, tokens, sound, name, off):
    """None is dropped "because the result stays inside the tolerance": the
    choice left out (dense latent attention), one line fewer or more, half
    the indexer's heads, no group limit, another one."""
    ref, _ = files
    weights, spec, want, _ = sound
    if name == "index_heads":   # W_IQ no longer reshapes to the heads it has
        with pytest.raises(TypeError):
            ref.forward(weights, tokens, {**spec, name: off})
        return
    got = np.asarray(ref.forward(weights, tokens, {**spec, name: off}))
    assert np.abs(got - want).max() > 1e-3, name
    if name == "index_topk":    # while a query sees no more than it may keep
        np.testing.assert_allclose(got[:TOPK - 1], want[:TOPK - 1], atol=1e-5)


def test_rope_lanes_that_are_the_last_of_an_indexer_head_move_the_logits(
        files, tokens, sound, monkeypatch):
    """In the indexer the rope lanes come FIRST: a reference that turns the
    last 16 of a head's 32 lanes chooses other lines."""
    ref, _ = files
    weights, spec, want, _ = sound
    mirrored = dict(weights, layers=[
        {**layer, "index_q": layer["index_q"].reshape(96, 4, 32)[..., ::-1].reshape(96, 128),
         "index_k": layer["index_k"][:, ::-1],
         "index_k_norm": {k: v[::-1] for k, v in layer["index_k_norm"].items()}}
        for layer in weights["layers"]])
    # the same projections with every head's lanes mirrored: the dot products
    # are the same numbers, the rotary now meets what were the last lanes
    got = np.asarray(ref.forward(mirrored, tokens, spec))
    assert np.abs(got - want).max() > 1e-3


def test_ties_go_to_the_lower_position(files):
    """Program and reference alike: among equal index scores the lower
    position is kept, with the context above and below ``index_topk``."""
    from scaling_tpu.nn.sparse_latent_attention import choose_lines

    ref, _ = files
    scores = jnp.asarray([[1.0, 3.0, 3.0, 0.5, 3.0, 3.0, 2.0, 3.0],
                          [5.0, 5.0, 5.0, 5.0, 5.0, 5.0, 5.0, 5.0],
                          [0.0, 0.0, 0.0, 0.0, 0.0, 0.0, 0.0, 0.0]])
    visible = jnp.asarray([[True] * 8, [True] * 8, [True] * 2 + [False] * 6])
    want = ref.chosen_lines(scores, visible, 3)
    assert np.flatnonzero(want[0]).tolist() == [1, 2, 4]
    assert np.flatnonzero(want[1]).tolist() == [0, 1, 2]
    assert np.flatnonzero(want[2]).tolist() == [0, 1]
    idx, held = choose_lines(scores, visible, 3)
    for row in range(3):
        assert sorted(np.asarray(idx[row])[np.asarray(held[row])].tolist()) == \
            np.flatnonzero(want[row]).tolist()
    assert ref.chosen_lines(scores, visible, None) is visible


def test_thirty_two_shares_add_up_to_the_uncut_layer(files):
    """The shares test under GROUP-LIMITED routing: a 64-expert layer in 8
    groups of 8 (a token keeps 4 groups, 6 experts) held whole against the
    same layer as 32 ranks of 2 experts each, a quarter of a group a rank as
    the configuration's chip holds a quarter of group 0 (the router keeps its
    64 outputs, its groups and its 6 a token; absent experts' gates are
    dropped, not renormalised): the ranks' routed parts plus the shared expert
    ONCE are the whole layer. In the reference, and in the program's
    ``serve``; without the group limit the layer is another one."""
    from scaling_tpu.nn.moe import ParallelMoEMLP

    ref, _ = files
    H, F, E, K, G, TG, HELD = 64, 32, 64, 6, 8, 4, 2
    make = lambda first, held, n_group=G: ParallelMoEMLP(
        io_features=H, intermediate_feature_factor=1.0, num_experts=E, top_k=K,
        norm_topk_prob=True, norm_topk_eps=1e-20, glu=True, intermediate=F,
        router="sigmoid_bias", routed_scaling_factor=2.5,
        shared_expert_width=F, experts_first=first, experts_held=held,
        n_group=n_group, topk_group=TG if n_group > 1 else 1)
    whole = make(0, E)
    params = whole.init(jax.random.PRNGKey(0))
    params["router"]["bias"] = 0.1 * jax.random.normal(jax.random.PRNGKey(1), (E,))
    params["router"]["weight"] = 20 * params["router"]["weight"]
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

    spec = {"top_k": K, "scale": 2.5, "gate_eps": 1e-20, "experts_first": 0,
            "shared": True, "n_group": G, "topk_group": TG}
    with jax.default_matmul_precision("highest"):
        p, experts = as_reference(params)
        want = ref.routed_ffn(x[0], p, experts, spec)
        shared = ref.swiglu(x[0], p["shared_gate"], p["shared_up"], p["shared_down"])
        parts = []
        for first in range(0, E, HELD):
            p, experts = as_reference(rank_params(first, HELD))
            parts.append(ref.routed_ffn(
                x[0], p, experts, {**spec, "experts_first": first, "shared": False}))
        assert len(parts) == 32
        np.testing.assert_allclose(sum(parts) + shared, want, atol=5e-6)
        # the program: each rank's serve() holds the shared expert, so the
        # 32 outputs count it 32 times
        got_whole, _ = whole.serve(params, x)
        np.testing.assert_allclose(got_whole[0], want, atol=3e-5)
        ranks = [make(first, HELD).serve(rank_params(first, HELD), x)[0][0]
                 for first in range(0, E, HELD)]
        np.testing.assert_allclose(sum(ranks) - 31 * shared, want, atol=2e-4)
        # the limit does something: the ungrouped layer chooses otherwise
        ungrouped, _ = make(0, E, n_group=1).serve(params, x)
        assert float(jnp.abs(ungrouped[0] - want).max()) > 1e-2
        plain = ref.routed_ffn(x[0], *as_reference(params),
                               {**spec, "n_group": 1, "topk_group": 1})
        np.testing.assert_allclose(ungrouped[0], plain, atol=3e-5)
    assert float(jnp.abs(parts[0]).max()) > 1e-3   # a share is not nothing


def test_the_blocks_of_the_softmax_change_nothing(files, tokens, sound, monkeypatch):
    """The one departure in how the equations are evaluated: index scores,
    choice and softmax a block of queries at a time; a block of 16 (several,
    the last one ragged) gives the same logits and the same chosen sets as one
    block, and so do the gathered head positions."""
    ref, _ = files
    weights, spec, want, chosen = sound
    monkeypatch.setattr(ref, "QUERY_BLOCK", 16)
    ref.attention_block.clear_cache()
    again = []
    split = np.asarray(ref.forward(weights, tokens[:70], spec, chosen_out=again))
    ref.attention_block.clear_cache()
    np.testing.assert_allclose(split, want[:70], atol=5e-6)
    assert all((np.asarray(a) == c[:70, :70]).all() for a, c in zip(again, chosen))
    positions = jnp.asarray([79, 3, 20])
    picked = np.asarray(ref.forward(weights, tokens, spec, head_positions=positions))
    np.testing.assert_allclose(picked, want[np.asarray(positions)], atol=2e-6)


def test_the_fp8_control_moves_the_logits_and_keeps_the_vectors(files, tokens, sound):
    ref, _ = files
    weights, spec, want, _ = sound
    lowered = lower_precision(weights, "fp8")
    assert lowered["layers"][0]["index_q"].dtype == jnp.float8_e4m3fn
    assert lowered["layers"][1]["w_up"].dtype == jnp.float8_e4m3fn
    assert lowered["layers"][1]["index_k_norm"]["bias"].dtype == jnp.float32
    got = np.asarray(ref.forward(lowered, tokens, spec))
    assert np.abs(got - want).max() > 0.02


def test_the_reference_takes_nothing_of_the_program(files):
    ref, _ = files
    source = open(ref.__file__).read().split('"""', 2)[2]
    assert "scaling_tpu" not in source
    assert "W_UK" not in source and "absorb" not in source   # the expanded form only
    assert "top_k(" in source and source.count("top_k(") == 1   # the router's alone


@pytest.mark.parametrize("blocks,count", [(61, 671_877_944_064), (6, 3_825_510_144)])
def test_the_tree_counts_the_published_parameters(blocks, count):
    """``jax.eval_shape`` of the program's own tree: the cut as it is run, and
    the whole model without its multi-token-prediction module (61 blocks, three
    of them dense, 256 experts held, the whole vocabulary)."""
    config = PUBLISHED
    if blocks == 61:
        arch = {**PUBLISHED["transformer_architecture"], "num_layers": 122,
                "layer_pattern": ["latent", "mlp"] * 3 + ["latent", "moe"] * 58,
                "moe_experts_held": 256, "vocab_size": 129_280}
        config = {**PUBLISHED, "transformer_architecture": arch}
        assert count == PUBLISHED["published"]["parameter_count"]
    shapes = model.param_shapes(init_model(model.transformer_config(config, {}), None))
    assert model.count_params(shapes) == count
    if blocks == 61:
        return

    def size(tree):
        return sum(math.prod(x.shape) for x in jax.tree.leaves(tree))

    mixer = shapes["layer_1"]["mixer"]
    indexer = {k: v for k, v in mixer.items() if k.startswith("index_")}
    assert size(indexer) == 13_959_424 == 1536 * 8192 + 7168 * 128 + 2 * 128 + 7168 * 64
    assert size(mixer) - size(indexer) == 187_107_328
    assert size(shapes["layer_2"]["mixer"]) == 3 * 7168 * 18_432
    routed = shapes["layer_4"]["mixer"]
    assert routed["w_in"].shape == (8, 7168, 2048)
    assert routed["router"]["weight"].shape == (7168, 256)
    assert size(routed) == 7168 * 256 + 256 + 9 * 44_040_192
    assert size(shapes["layer_1"]) + size(shapes["layer_2"]) == 597_442_816
    assert size(shapes["layer_3"]) + size(shapes["layer_4"]) == 599_278_080
    assert size(shapes["layer_0"]) == size(shapes["layer_14"]) == 16_160 * 7168
    assert size(shapes["layer_13"]) == 7168
