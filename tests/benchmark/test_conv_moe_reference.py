"""The program's stack of LFM2-MoE blocks (a gated short convolution or GQA
attention with per-head QK norm, then a dense or a routed SwiGLU FFN, a tied
head) against the plain reference ``benchmark/reference/conv_moe_decoder.py``
on seeded random weights, at a small size on the CPU: the full forward pass,
prefill in chunks and decoding through the paged cache and the conv-tail
lines, a mixed tick, the router, the view's refusals, the counts. Logits,
never tokens."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import cells, model
from benchmark.reference import conv_moe_decoder as ref
from benchmark.views import conv_moe_decoder as view

# the engine's state and one jitted call of the stack over it, token-major,
# as ServeEngine's mixed program makes it: any pattern stack's, by its kinds
from .test_hybrid_reference import Served, served_logits

OPS = ["conv", "conv", "attention", "conv", "conv"]
FFNS = ["mlp", "mlp", "moe", "moe", "moe"]
ARCH = dict(
    vocab_size=96, hidden_size=48, num_layers=2 * len(OPS),
    layer_pattern=[kind for block in zip(OPS, FFNS) for kind in block],
    num_attention_heads=4, attention_num_kv_heads=2, attention_head_dim=16,
    attention_qkv_in_one=False, attention_bias=False, key_query_norm=True,
    mlp_type="swiglu", mlp_factor=2.5, mlp_bias=False,
    moe_num_experts=8, moe_top_k=3, moe_expert_width=40, moe_glu=True,
    moe_router="sigmoid_bias", moe_norm_topk_prob=True, moe_norm_topk_eps=1e-6,
    activation_function="silu", conv_kernel=3,
    norm_type="rms", layernorm={"layernorm_epsilon": 1e-5},
    relative_position_embedding_type="rotary", rotary_embedding_base=1000000,
    sequence_length=128, precision="float32", causal=True, weight_tying=True)
TOPOLOGY = dict(model_parallel_size=1, pipe_parallel_size=1, data_parallel_size=1,
                micro_batch_size=1, gradient_accumulation_steps=1)
# float32 on both sides, the same mathematics in another order of summation
# (the program's token-major filter over a carried tail against the
# reference's shifted sums over the whole sequence, its capacity buffers
# against every-expert-on-every-token, a paged cache and a line pool against
# none): logits of magnitude ~1-3 agree to a few float32 roundings a layer.
# 3e-4 would already fail a bf16 computation (2**-9 = 2e-3 a rounding), a
# gate renormalised with another epsilon, a norm after the rotation and a tail
# that leaked from one sequence into the next (all below).
LOGIT_ATOL = 3e-4


def build(**changes):
    from scaling_tpu.models.transformer.inference import TransformerInferenceModule
    from scaling_tpu.models.transformer.model import init_model

    arch = {**ARCH, **changes}
    config = model.transformer_config(
        {"transformer_architecture": arch, "topology": TOPOLOGY}, {})
    module = init_model(config, None)
    params = module.init_params(jax.random.PRNGKey(3))
    # the table starts small, norm weights at one (or at signs), the
    # selection bias at zero: lift the table to unit size and perturb every
    # leaf, so that each takes part
    leaves, treedef = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(4), len(leaves))
    params = jax.tree.unflatten(treedef, [
        (x.astype(jnp.float32) + 0.1 * jax.random.normal(k, x.shape)).astype(x.dtype)
        for x, k in zip(leaves, keys)])
    table = params["layer_0"]["embedding"]["weight"]
    params["layer_0"]["embedding"]["weight"] = table * 10.0
    return arch, TransformerInferenceModule(config, module, params)


def reference_logits(arch, params, tokens, **spec):
    return np.asarray(ref.forward(
        view.reference_weights(params, arch), jnp.asarray(tokens),
        {**view.reference_spec(arch), **spec}))


# ---- (a) the system against the reference ---------------------------------

def test_full_forward_agrees_with_the_reference():
    arch, inf = build()
    tokens = np.random.default_rng(0).integers(1, arch["vocab_size"], 75)
    got = np.asarray(inf.logits(tokens)[0])
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
    # every real position's top_k assignments a routed layer, all held
    routed = arch["layer_pattern"].count("moe")
    assert loads[0].shape == (arch["moe_num_experts"],)
    assert sum(int(l.sum()) for l in loads) == 47 * arch["moe_top_k"] * routed


def test_the_kernel_and_the_gather_formulation_agree_on_the_stack():
    arch, inf = build()
    tokens = np.random.default_rng(3).integers(1, arch["vocab_size"], 40).astype(np.int32)
    a, _ = served_logits(inf, tokens, 37, 8, kernel="pallas")
    b, _ = served_logits(inf, tokens, 37, 8, kernel="xla")
    np.testing.assert_allclose(a, b, atol=LOGIT_ATOL, rtol=0)


def test_a_tick_that_mixes_decode_rows_chunk_rows_and_empty_slots():
    """Four slots: slot 0 decodes, slot 1 is empty, slot 2 streams a chunk
    mid-prompt, slot 3 starts a prompt in a slot whose lines are dirty; each
    row equals its own sequence's full forward, and the empty slot's lines
    are untouched bit for bit."""
    arch, inf = build()
    rng = np.random.default_rng(5)
    a, c, d = (rng.integers(1, arch["vocab_size"], n).astype(np.int32)
               for n in (20, 30, 7))
    served = Served(inf, slots=4, row_width=8)
    # bring slots 0 and 2 to where the mixed tick finds them; slots 1 and 3
    # keep what an earlier occupant left there
    served.tick([list(a[:8]), list(d[:5]), list(c[:8]), list(c[:3])], [0, 0, 0, 0])
    served.tick([list(a[8:16]), [], list(c[8:16]), []], [8, 5, 8, 3])
    served.tick([list(a[16:19]), [], [], []], [16, 5, 16, 3])
    before = jax.tree.map(np.asarray, served.state)
    out, _ = served.tick([[int(a[19])], [], list(c[16:22]), list(d)], [19, 5, 16, 0])
    after = jax.tree.map(np.asarray, served.state)
    want = {0: reference_logits(arch, inf.params, a)[19:20],
            2: reference_logits(arch, inf.params, c)[16:22],
            3: reference_logits(arch, inf.params, d)}
    for slot, logits in want.items():
        np.testing.assert_allclose(out[slot], logits, atol=LOGIT_ATOL, rtol=0)
    assert out[1].shape[0] == 0
    assert len(before) == 5 and len(before[4]) == OPS.count("conv")
    for lines_before, lines_after in zip(before[4], after[4]):
        assert np.array_equal(lines_before[1], lines_after[1])   # slot 1: bit for bit
        assert not np.array_equal(lines_before[0], lines_after[0])


def test_a_tail_that_leaks_into_the_next_sequence_is_another_model(monkeypatch):
    """Without the zero start at context 0 a sequence that follows another
    through the same slot reads its predecessor's last inputs: the tolerance
    sees it."""
    from scaling_tpu.nn import short_conv

    arch, inf = build()
    rng = np.random.default_rng(7)
    first, second = (rng.integers(1, arch["vocab_size"], 12).astype(np.int32)
                     for _ in range(2))
    want = reference_logits(arch, inf.params, second)

    def follow():
        served = Served(inf, row_width=16)
        served.tick([list(first)], [0])
        return served.tick([list(second)], [0])[0][0]

    np.testing.assert_allclose(follow(), want, atol=LOGIT_ATOL, rtol=0)
    real = short_conv.GatedShortConv._serve
    monkeypatch.setattr(
        short_conv.GatedShortConv, "_serve",
        lambda self, weight, u, v: real(
            self, weight, u, v._replace(context_len=jnp.ones_like(v.context_len))))
    assert np.abs(follow() - want).max() > 10 * LOGIT_ATOL


# ---- (b) the router and the view ------------------------------------------

@pytest.mark.parametrize("changed", [{"gate_eps": 1e-2}, {"scale": 2.0}, {"top_k": 2}],
                         ids=["another-epsilon", "another-scale", "another-k"])
def test_other_gates_are_another_model(changed):
    arch, inf = build()
    tokens = np.random.default_rng(8).integers(1, arch["vocab_size"], 30)
    got = np.asarray(inf.logits(tokens)[0])
    other = reference_logits(arch, inf.params, tokens, **changed)
    assert np.abs(got - other).max() > 10 * LOGIT_ATOL


def test_a_norm_after_the_rotation_is_another_model(monkeypatch):
    """Per-head QK norm BEFORE rotary: with learned weights the two orders
    differ (the rotation mixes the dimensions a weight scales apart)."""
    arch, inf = build()
    tokens = np.random.default_rng(9).integers(1, arch["vocab_size"], 30)
    got = np.asarray(inf.logits(tokens)[0])
    real_rotary, real_norm = ref.rotary, ref.norm
    pending = []

    def norm_later(x, p, kind, eps):
        if x.ndim == 3:          # a head norm: remember it, apply after rotary
            pending.append(p)
            return x
        return real_norm(x, p, kind, eps)

    def rotary_then_norm(x, positions, base):
        return real_norm(real_rotary(x, positions, base), pending.pop(0), "rms", 1e-5)

    monkeypatch.setattr(ref, "norm", norm_later)
    monkeypatch.setattr(ref, "rotary", rotary_then_norm)
    ref.layer_forward.clear_cache()
    try:
        other = reference_logits(arch, inf.params, tokens)
    finally:
        monkeypatch.undo()
        ref.layer_forward.clear_cache()
    assert np.abs(got - other).max() > 10 * LOGIT_ATOL


def test_the_view_refuses_equations_the_reference_does_not_compute():
    for changed in ({"weight_tying": False}, {"moe_glu": False},
                    {"moe_router": "softmax"}, {"key_query_norm": False},
                    {"key_query_norm_scope": "projection"},
                    {"moe_shared_expert_width": 64}, {"moe_experts_held": 4},
                    {"relative_position_embedding_type": "none"}):
        with pytest.raises(SystemExit, match="conv_moe_decoder: the reference computes"):
            view.reference_spec({**ARCH, **changed})
    for pattern in (["conv", "moe", "conv", "mlp"], ["mlp", "conv"], ["conv"],
                    ["mamba", "moe"]):
        with pytest.raises(SystemExit, match="layer_pattern is"):
            view.reference_spec({**ARCH, "layer_pattern": pattern})
    spec = view.reference_spec(ARCH)
    assert spec["ops"] == tuple(OPS) and spec["num_dense"] == 2
    assert spec["gate_eps"] == 1e-6 and spec["rope_base"] == 1e6 and spec["scale"] == 1.0
    # the reference takes nothing of the program
    source = cells.ROOT / "reference" / "conv_moe_decoder.py"
    assert "scaling_tpu" not in source.read_text().split('"""', 2)[2]


# ---- (c) the published counts ----------------------------------------------

KINDS = {"conv": "conv", "full_attention": "attention"}


def test_published_depth_counts_the_published_parameters():
    from scaling_tpu.models.transformer.model import init_model

    config = cells.load_json(cells.ROOT / "configs" / "lfm2-24b-a2b-serve.json")
    published = config["published"]
    ops = [KINDS[kind] for kind in published["layer_types"]]
    assert (len(ops), ops.count("conv"), ops.count("attention")) == (40, 30, 10)
    dense = published["num_dense_layers"]
    pattern = [kind for i, op in enumerate(ops)
               for kind in (op, "mlp" if i < dense else "moe")]
    arch = {**config["transformer_architecture"], "layer_pattern": pattern,
            "num_layers": len(pattern)}
    shapes = model.param_shapes(init_model(model.transformer_config(
        {**config, "transformer_architecture": arch}, {}), None))
    assert model.count_params(shapes) == published["parameter_count"] == 23_843_661_440
    by_kind = {kind: model.count_params(shapes[f"layer_{pattern.index(kind) + 1}"])
               for kind in ("conv", "attention", "mlp", "moe")}
    # each with its one norm of 2048
    assert by_kind == {"conv": 16_783_360 + 2048, "attention": 10_485_888 + 2048,
                       "mlp": 72_351_744 + 2048,
                       "moe": 603_979_776 + 131_072 + 64 + 2048}
    # tied: the table counts once, the head holds no leaf
    assert model.count_params(shapes["layer_0"]) == 65_536 * 2048
    assert model.count_params(shapes[f"layer_{len(pattern) + 2}"]) == 0
    # as run: the leading 8 blocks, every expert, the whole vocabulary
    run = model.param_shapes(init_model(model.transformer_config(config, {}), None))
    assert model.count_params(run) == 4_025_293_440
    assert config["transformer_architecture"]["layer_pattern"] == pattern[:16]
    assert view.expert_param_count(config["transformer_architecture"], run) == \
        6 * 64 * 3 * 2048 * 1536
    assert view.blocks(config["transformer_architecture"]) == (tuple(ops[:8]), 2)
