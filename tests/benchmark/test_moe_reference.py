"""The program's routed decoder (OLMoE's equations) against the plain
reference ``benchmark/reference/moe_decoder.py`` on seeded random weights, at
a small size on the CPU: the full forward pass, prefill in chunks and decoding
through the paged cache, the gates, the QK-norm, the load vector, the counts."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import cells, model
from benchmark.reference import moe_decoder as ref
from benchmark.views import moe_decoder as view

ARCH = dict(
    vocab_size=96, hidden_size=64, num_layers=2, num_attention_heads=4,
    attention_num_kv_heads=4, attention_qkv_in_one=False, attention_bias=False,
    key_query_norm=True, key_query_norm_scope="projection",
    mlp_type="moe", mlp_factor=0.5, mlp_bias=False, moe_num_experts=8,
    moe_top_k=2, moe_norm_topk_prob=False, activation_function="silu",
    norm_type="rms", layernorm={"layernorm_epsilon": 1e-5},
    relative_position_embedding_type="rotary", rotary_embedding_base=10000,
    sequence_length=128, precision="float32", causal=True, weight_tying=False)
TOPOLOGY = dict(model_parallel_size=1, pipe_parallel_size=1, data_parallel_size=1,
                micro_batch_size=1, gradient_accumulation_steps=1)
# float32 on both sides, the same mathematics in another order of summation
# (the program's three einsums over capacity buffers against the reference's
# every-expert-on-every-token sum; a paged cache against none): logits of
# magnitude ~1 agree to a few float32 roundings per layer, as for the dense
# reference. 2e-4 would already fail a bf16 computation (2**-9 = 2e-3 a
# rounding), a renormalised gate (below) and one dropped assignment.
LOGIT_ATOL = 2e-4
CHUNK = 32
# what nn/moe.py's training capacity would give a 32-position row at the
# DEFAULT moe_capacity_factor, which ARCH leaves alone
CAPACITY_AT_DEFAULT = int(1.25 * ARCH["moe_top_k"] * CHUNK / ARCH["moe_num_experts"])


def build(num_layers=2, topology=TOPOLOGY, **changes):
    from scaling_tpu.models.transformer.inference import TransformerInferenceModule
    from scaling_tpu.models.transformer.model import init_model
    from scaling_tpu.topology import Topology

    arch = {**ARCH, "num_layers": num_layers, **changes}
    config = model.transformer_config(
        {"transformer_architecture": arch, "topology": topology}, {})
    topo = Topology(config.topology) if topology["model_parallel_size"] > 1 else None
    module = init_model(config, topo)
    params = module.init_params(jax.random.PRNGKey(3))
    # norm weights start at one (a q_norm of ones cannot tell a per-head
    # weight from a whole-projection one): perturb every leaf
    leaves, treedef = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(4), len(leaves))
    params = jax.tree.unflatten(treedef, [
        (x.astype(jnp.float32) + 0.1 * jax.random.normal(k, x.shape)).astype(x.dtype)
        for x, k in zip(leaves, keys)])
    if topo is not None:
        params = module.shard_params(params)
    return arch, TransformerInferenceModule(config, module, params)


def reference_logits(arch, params, tokens):
    return np.asarray(ref.forward(view.reference_weights(params, arch),
                                  jnp.asarray(tokens), view.reference_spec(arch)))


def prompt_with_a_crowded_chunk(rng, length):
    """Tokens whose first chunk sends one expert more than the default
    capacity holds: 24 copies of one token (equal inputs, equal routing in
    the first layer) among random ones."""
    tokens = rng.integers(1, ARCH["vocab_size"], length)
    tokens[rng.permutation(CHUNK)[:24]] = 7
    return tokens.astype(np.int32)


def served_logits(inf, tokens, prompt_len, chunk, kernel="pallas"):
    """Prefill ``tokens[:prompt_len]`` in chunks of ``chunk`` (the last one
    ragged, padded to the program's width) and decode the rest one token at
    a time, all through the paged cache, as the engine's programs do:
    (logits of every real position, the (E,) load of every call)."""
    from scaling_tpu.serve.kvcache import build_layer_views, init_pools

    block_size, max_blocks = 16, 8
    pools = init_pools(inf, max_blocks + 1, block_size)
    state = (pools.pool_k, pools.pool_v, pools.scale_k, pools.scale_v)
    table = jnp.arange(1, max_blocks + 1, dtype=jnp.int32)[None]

    @jax.jit
    def step(state, row, ctx, new_len):
        pos = ctx[:, None] + jnp.arange(row.shape[1], dtype=jnp.int32)[None]
        views = build_layer_views(state, table, ctx, new_len)
        logits, new_views, load = inf._run_layers(
            inf.params, inf._make_batch(row, pos), views, None,
            paged_kernel=kernel, moe_load=True)
        return logits, (
            [v.pool_k for v in new_views], [v.pool_v for v in new_views], None, None), load

    logits, loads, done = [], [], 0
    while done < len(tokens):
        width = chunk if done < prompt_len else 1
        n = min(width, prompt_len - done) if done < prompt_len else 1
        row = np.zeros((1, width), np.int32)
        row[0, :n] = tokens[done:done + n]
        out, state, load = step(state, jnp.asarray(row), jnp.asarray([done], jnp.int32),
                                jnp.asarray([n], jnp.int32))
        logits.append(np.asarray(out[0, :n]))
        loads.append(np.asarray(load))
        done += n
    return np.concatenate(logits), loads


# ---- (1) one layer and the whole model, QK-norm over the whole projection

@pytest.mark.parametrize("num_layers", [1, 2])
def test_full_forward_agrees_with_the_reference(num_layers):
    arch, inf = build(num_layers)
    tokens = np.random.default_rng(0).integers(1, arch["vocab_size"], 40)
    got = np.asarray(inf.logits(tokens)[0])
    want = reference_logits(arch, inf.params, tokens)
    np.testing.assert_allclose(got, want, atol=LOGIT_ATOL, rtol=0)
    assert np.abs(want).max() > 0.5  # the agreement is not that of zeros


def test_a_per_head_norm_is_another_model():
    """The same weights under ``key_query_norm_scope: head`` (each head's
    slice of the learned weight cannot be given: one head's worth of it is):
    the statistic runs over 16 values instead of 64, and the limit fails."""
    arch, inf = build(1)
    _, per_head = build(1, key_query_norm_scope="head")
    params = jax.tree.map(lambda x: x, inf.params)
    attn = params["layer_1"]["attention"]
    head_dim = arch["hidden_size"] // arch["num_attention_heads"]
    for name in ("norm_query", "norm_key"):
        assert attn[name]["weight"].shape == (arch["hidden_size"],)
        assert per_head.params["layer_1"]["attention"][name]["weight"].shape == (head_dim,)
        attn[name] = {"weight": attn[name]["weight"][:head_dim]}
    per_head.params = params
    tokens = np.random.default_rng(0).integers(1, arch["vocab_size"], 40)
    gap = np.abs(np.asarray(per_head.logits(tokens)[0])
                 - reference_logits(arch, inf.params, tokens)).max()
    assert gap > 10 * LOGIT_ATOL


# ---- (2) the gates are the softmax's, as they are

def test_renormalised_gates_are_another_model():
    arch, inf = build()
    _, renormed = build(moe_norm_topk_prob=True)
    renormed.params = inf.params
    tokens = np.random.default_rng(1).integers(1, arch["vocab_size"], 40)
    want = reference_logits(arch, inf.params, tokens)
    np.testing.assert_allclose(np.asarray(inf.logits(tokens)[0]), want,
                               atol=LOGIT_ATOL, rtol=0)
    # two gates of eight experts sum to well under one: renormalised, the
    # routed half of every block is scaled up by 1 / their sum
    assert np.abs(np.asarray(renormed.logits(tokens)[0]) - want).max() > 10 * LOGIT_ATOL
    with pytest.raises(SystemExit, match="moe_norm_topk_prob"):
        view.reference_spec({**arch, "moe_norm_topk_prob": True})


# ---- (3) prefill in chunks, then decode, through the paged cache, at the
# DEFAULT capacity factor

def test_chunked_prefill_then_decode_is_the_full_forward_pass():
    arch, inf = build()
    assert "moe_capacity_factor" not in arch  # the program's default, 1.25
    prompt_len, total = 75, 80
    tokens = prompt_with_a_crowded_chunk(np.random.default_rng(2), total)
    got, loads = served_logits(inf, tokens, prompt_len, CHUNK)
    # the first chunk is crowded: summed over two layers, one expert took
    # more than both layers' default capacity together
    assert loads[0].max() > arch["num_layers"] * CAPACITY_AT_DEFAULT
    np.testing.assert_allclose(got, reference_logits(arch, inf.params, tokens),
                               atol=LOGIT_ATOL, rtol=0)


def test_the_training_capacity_would_have_dropped_that_chunk():
    """What (3) rests on: the same layer, the same crowded row, under the
    capacity training keeps at the default factor, is not the mixture."""
    from scaling_tpu.nn.base_layer import ForwardContext

    _, inf = build(1)
    layer = inf.module.layers[1]
    params = inf.params["layer_1"]["mlp"]
    x = jnp.tile(jax.random.normal(jax.random.PRNGKey(5), (1, 1, ARCH["hidden_size"])),
                 (1, CHUNK, 1))
    kept, _ = layer.mlp.serve(params, x)
    dropped, _ = layer.mlp(params, x, ForwardContext())
    np.testing.assert_allclose(np.asarray(kept[0, 0]), np.asarray(dropped[0, 0]), atol=1e-6)
    assert np.abs(np.asarray(dropped[0, CAPACITY_AT_DEFAULT:])).max() == 0.0
    assert np.abs(np.asarray(kept[0, -1] - kept[0, 0])).max() < 1e-6


# ---- (4) how the prompt was cut into chunks does not show

def test_chunk_32_and_chunk_8_give_the_same_logits():
    arch, inf = build()
    tokens = prompt_with_a_crowded_chunk(np.random.default_rng(3), 70)
    wide, _ = served_logits(inf, tokens, 66, 32, kernel="xla")
    narrow, _ = served_logits(inf, tokens, 66, 8, kernel="xla")
    np.testing.assert_allclose(wide, narrow, atol=LOGIT_ATOL, rtol=0)


# ---- (5) the load vector counts real positions only

def test_the_load_counts_real_positions_times_top_k_times_layers():
    arch, inf = build()
    tokens = np.random.default_rng(4).integers(1, arch["vocab_size"], 45).astype(np.int32)
    _, loads = served_logits(inf, tokens, 43, CHUNK, kernel="xla")
    per_position = arch["moe_top_k"] * arch["num_layers"]
    # chunks of 32 and 11 (21 padded positions), then two decode tokens
    assert [int(l.sum()) for l in loads] == [32 * per_position, 11 * per_position,
                                             per_position, per_position]
    assert all(l.shape == (arch["moe_num_experts"],) and l.dtype == np.int32
               for l in loads)
    assert all(l.min() >= 0 for l in loads) and loads[2].max() <= arch["num_layers"]


# ---- (6) the published counts

def test_published_depth_counts_the_published_parameters_and_eight_experts():
    from scaling_tpu.models.transformer.model import init_model

    config = cells.load_json(cells.ROOT / "configs" / "olmoe-1b-7b-serve.json")
    arch = config["transformer_architecture"]
    cfg = model.transformer_config(config, {}, num_layers=16)
    shapes = model.param_shapes(init_model(cfg, None))
    assert model.count_params(shapes) == 6_919_161_856
    layer = model.count_params(shapes["layer_1"])
    experts = view.expert_param_count({**arch, "num_layers": 16}, shapes)
    assert (layer, experts) == (419_569_664, 16 * 402_653_184)
    # by hand, a trained token: attention 4 x 2048^2, the router, 8 experts
    # of 3 x 2048 x 1024, the head; norms and QK-norms weigh nothing here
    # but are matmul-free parameters the dense count carries too
    at_work = 16 * (4 * 2048 * 2048 + 2048 * 64 + 8 * 3 * 2048 * 1024
                    + 4 * 2048) + 2048 + 2048 * 50304
    flops = view.train_flops_per_token({**arch, "num_layers": 16}, shapes, 4096)
    assert flops == 6.0 * at_work + 6.0 * 16 * 16 * 128 * 4096
    held = at_work + 16 * 56 * 3 * 2048 * 1024
    assert flops < 6.0 * held / 4  # 64 experts priced would be 5 x as much


# ---- (7) the whole-projection norm across model-parallel shards

def test_whole_projection_norm_under_model_parallel_2_equals_1(devices):
    arch, one = build()
    _, two = build(topology={**TOPOLOGY, "model_parallel_size": 2})
    tokens = np.random.default_rng(6).integers(1, arch["vocab_size"], 24)
    np.testing.assert_allclose(
        np.asarray(two.logits(tokens)[0]), np.asarray(one.logits(tokens)[0]),
        atol=LOGIT_ATOL, rtol=0)
    np.testing.assert_allclose(
        np.asarray(two.logits(tokens)[0]), reference_logits(arch, one.params, tokens),
        atol=LOGIT_ATOL, rtol=0)
