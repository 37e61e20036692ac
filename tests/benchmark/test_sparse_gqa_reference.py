"""``reference/sparse_gqa_moe_decoder.py`` and its view against the program on
seeded weights at a toy width: the program (the unfused attention under the
mask of its own exact top-k where there is no cache) is the reference, logits
and chosen sets; each constant perturbed in the reference alone moves the
logits, the choice left out or off by one, the index key's LayerNorm and
rotary among them; ties go to the lower position; ONE choice a token whatever
the head; the blocks in which the reference evaluates the softmax change
nothing; the fp8 control; the published parameter counts by
``jax.eval_shape``."""

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

CONFIG = "keye-vl-2.0-30b-a3b-serve"
VOCAB = 128
TOPK = 16
TOPOLOGY = {"model_parallel_size": 1, "pipe_parallel_size": 1,
            "data_parallel_size": 1, "micro_batch_size": 1,
            "gradient_accumulation_steps": 1}
PUBLISHED = cells.load_json(cells.ROOT / "configs" / f"{CONFIG}.json")
# the configuration's equations and constants at a toy width: hidden 128, 8
# query heads over 2 KV heads of 32, an indexer of 4 heads x 16 that keeps 16
# lines, 8 experts of 64 of which a token keeps 2
ARCH = {**PUBLISHED["transformer_architecture"],
        "vocab_size": VOCAB, "hidden_size": 128, "num_layers": 6,
        "layer_pattern": ["attention", "moe"] * 3,
        "num_attention_heads": 8, "attention_num_kv_heads": 2,
        "attention_head_dim": 32, "index_n_heads": 4, "index_head_dim": 16,
        "index_topk": TOPK, "moe_num_experts": 8, "moe_top_k": 2,
        "moe_expert_width": 64, "sequence_length": 128, "precision": "float32"}


@pytest.fixture(scope="module")
def files():
    return (cells.load_module(cells.ROOT, "reference", "sparse_gqa_moe_decoder",
                              cells.REFERENCE_CONTRACT),
            cells.load_module(cells.ROOT, "views", "sparse_gqa_moe_decoder",
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
def keye():
    return build(ARCH)


@pytest.fixture(scope="module")
def tokens():
    return jnp.asarray(np.random.default_rng(3).integers(1, VOCAB, size=80))


@pytest.fixture(scope="module")
def sound(files, keye, tokens):
    ref, view = files
    weights = view.reference_weights(keye.params, ARCH)
    spec = view.reference_spec(ARCH)
    chosen = []
    logits = np.asarray(ref.forward(weights, tokens, spec, chosen_out=chosen))
    return weights, spec, logits, [np.asarray(c) for c in chosen]


def test_the_program_is_the_reference(keye, tokens, sound):
    """The program's uncached pass (the unfused attention under the mask of
    its own ``top_k``, its own rotary tables and norms) against the reference
    (a stable sort's ranks), float32 on both sides."""
    _, spec, want, chosen = sound
    assert (spec["num_heads"], spec["num_kv_heads"], spec["head_dim"]) == (8, 2, 32)
    assert (spec["index_heads"], spec["index_dim"], spec["index_topk"]) == (4, 16, TOPK)
    assert spec["rope_base"] == 1e7 and spec["top_k"] == 2
    got = np.asarray(keye.logits(tokens[None])[0])
    np.testing.assert_allclose(got, want, atol=5e-5)
    assert 0.2 < want.std() < 4.0   # the init's work: fresh logits of a size
    # every query chose min(16, what it sees), causal, and not just the last 16
    assert len(chosen) == 3
    for c in chosen:
        assert c.shape == (80, 80)            # ONE choice a token: no head axis
        assert c.sum(axis=1).tolist() == [min(TOPK, t + 1) for t in range(80)]
        assert not np.triu(c, 1).any()
        assert any(not c[t, t - TOPK + 1:t + 1].all() for t in range(TOPK, 80))


@pytest.mark.parametrize("name,off", [
    ("index_topk", None), ("index_topk", TOPK - 1), ("index_topk", TOPK + 1),
    ("index_heads", 2), ("top_k", 3), ("eps", 1e-2), ("rope_base", 50000.0),
    ("num_kv_heads", 4)])
def test_each_constant_perturbed_in_the_reference_alone_moves_the_logits(
        files, tokens, sound, name, off):
    """None is dropped "because the result stays inside the tolerance": the
    choice left out (dense grouped-query attention), one line fewer or more."""
    ref, _ = files
    weights, spec, want, _ = sound
    if name in ("index_heads", "num_kv_heads"):   # a projection no longer reshapes
        with pytest.raises(TypeError):
            ref.forward(weights, tokens, {**spec, name: off})
        return
    got = np.asarray(ref.forward(weights, tokens, {**spec, name: off}))
    assert np.abs(got - want).max() > 1e-3, name
    if name == "index_topk":    # while a query sees no more than it may keep
        np.testing.assert_allclose(got[:TOPK - 1], want[:TOPK - 1], atol=1e-5)


@pytest.mark.parametrize("what", ["no LayerNorm bias", "no LayerNorm weight",
                                  "a QK-norm of ones"])
def test_each_assumed_equation_left_out_moves_the_logits(files, tokens, sound, what):
    """The index key's LayerNorm has a weight AND a bias, q and k a per-head
    norm: a reference given ones or zeros for them computes other logits."""
    ref, _ = files
    weights, spec, want, _ = sound

    def changed(layer):
        layer = dict(layer)
        if what == "no LayerNorm bias":
            layer["index_k_norm"] = {**layer["index_k_norm"],
                                     "bias": jnp.zeros_like(layer["index_k_norm"]["bias"])}
        elif what == "no LayerNorm weight":
            layer["index_k_norm"] = {**layer["index_k_norm"],
                                     "weight": jnp.ones_like(layer["index_k_norm"]["weight"])}
        elif what == "a QK-norm of ones":
            layer["k_norm"] = {"weight": jnp.ones_like(layer["k_norm"]["weight"])}
        return layer

    other = dict(weights, layers=[changed(l) for l in weights["layers"]])
    got = np.asarray(ref.forward(other, tokens, spec))
    assert np.abs(got - want).max() > 1e-3, what


def test_an_index_key_that_is_not_rotated_chooses_other_lines(files, tokens, sound,
                                                              monkeypatch):
    """The indexer's whole head is rotary: with the index key and queries left
    at position 0 the chosen sets differ, and the logits with them."""
    ref, _ = files
    weights, spec, want, chosen = sound
    rotary = ref.rotary

    def only_wide_heads(x, positions, base):
        if x.shape[-1] == spec["index_dim"]:       # the indexer's heads
            return x
        return rotary(x, positions, base)

    monkeypatch.setattr(ref, "rotary", only_wide_heads)
    ref.attention_block.clear_cache()
    other = []
    got = np.asarray(ref.forward(weights, tokens, spec, chosen_out=other))
    ref.attention_block.clear_cache()
    assert any((np.asarray(a) != c).any() for a, c in zip(other, chosen))
    assert np.abs(got - want).max() > 1e-3


def test_ties_go_to_the_lower_position(files):
    """Program and reference alike: among equal index scores the lower
    position is kept, with the context above and below ``index_topk``."""
    from scaling_tpu.nn.sparse_rows import choose_lines, threshold_choice

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
    mask = np.asarray(threshold_choice(scores, visible, 3))
    for row in range(3):
        assert sorted(np.asarray(idx[row])[np.asarray(held[row])].tolist()) == \
            np.flatnonzero(want[row]).tolist() == np.flatnonzero(mask[row]).tolist()
    assert ref.chosen_lines(scores, visible, None) is visible


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
    assert lowered["layers"][1]["up"].dtype == jnp.float8_e4m3fn
    assert lowered["layers"][1]["index_k_norm"]["bias"].dtype == jnp.float32
    got = np.asarray(ref.forward(lowered, tokens, spec))
    assert np.abs(got - want).max() > 0.02


def test_the_reference_takes_nothing_of_the_program(files):
    ref, view = files
    source = open(ref.__file__).read().split('"""', 2)[2]
    assert "scaling_tpu" not in source and "pallas" not in source
    assert "top_k(" in source and source.count("top_k(") == 1   # the router's alone
    with pytest.raises(SystemExit, match="layer_pattern is \\(attention, moe\\)"):
        view.reference_spec({**ARCH, "layer_pattern": ["attention", "mlp"] * 3})
    with pytest.raises(SystemExit, match="the configuration states"):
        view.reference_spec({**ARCH, "moe_norm_topk_prob": False})
    with pytest.raises(SystemExit, match="lacks \\['index_topk'\\]"):
        view.reference_spec({**ARCH, "index_topk": None})


def test_the_tree_counts_the_published_parameters():
    """``jax.eval_shape`` of the program's own tree: the cut as it is run, and
    from its blocks the whole decoder (48 blocks, every expert, the whole
    vocabulary; the vision tower apart)."""
    shapes = model.param_shapes(init_model(model.transformer_config(PUBLISHED, {}), None))
    assert model.count_params(shapes) == 3_123_858_944

    def size(tree):
        return sum(math.prod(x.shape) for x in jax.tree.leaves(tree))

    mixer = shapes["layer_1"]["mixer"]
    indexer = {k: v for k, v in mixer.items() if k.startswith("index_")}
    assert size(indexer) == 2_261_120 == 2048 * 1024 + 2048 * 64 + 2048 * 16 + 2 * 64
    assert size(mixer) - size(indexer) == 18_874_368 + 256
    routed = shapes["layer_2"]["mixer"]
    assert routed["w_in"].shape == (128, 2048, 768)
    assert routed["router"]["weight"].shape == (2048, 128)
    assert size(routed) == 262_144 + 128 * 4_718_592
    block = size(shapes["layer_1"]) + size(shapes["layer_2"])
    assert block == 625_381_760
    assert all(size(shapes[f"layer_{2 * i + 1}"]) + size(shapes[f"layer_{2 * i + 2}"]) == block
               for i in range(4))
    assert size(shapes["layer_0"]) == size(shapes["layer_10"]) == 151_936 * 2048
    assert size(shapes["layer_9"]) == 2048
    # every block is the one kind (no dense layer, every expert held), so the
    # published 48 are 48 of these beside the same embedding, norm and head
    outside = size(shapes["layer_0"]) + size(shapes["layer_9"]) + size(shapes["layer_10"])
    assert 48 * block + outside == 30_640_656_384 == PUBLISHED["published"]["parameter_count"]
    assert PUBLISHED["published"]["num_hidden_layers"] == 48
