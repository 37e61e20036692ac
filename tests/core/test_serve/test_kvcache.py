"""Paged KV pool mechanics (ISSUE 9): flat-slot addressing, chunk
scatter + block gather round-trips, int8 quantization accuracy on
hand-built pools; then the donated pool state through the engine's
program, dense, routed, sharded over two devices, looped, and a pattern stack
whose state carries recurrent lines beside the pools."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from scaling_tpu.nn.attention import (
    PagedKVCacheView,
    kv_dequantize_int8,
    kv_quantize_int8,
    paged_flat_slots,
)
from scaling_tpu.nn.base_layer import ForwardContext


def test_paged_flat_slots_maps_through_block_table():
    table = jnp.asarray([[3, 1, 4, 0]], jnp.int32)  # logical block j -> pool block
    pos = jnp.asarray([[0, 1, 2, 3, 4, 5]], jnp.int32)
    flat = np.asarray(paged_flat_slots(table, pos, block_size=2))
    # logical slot 0,1 live in pool block 3; 2,3 in block 1; 4,5 in block 4
    assert flat.tolist() == [[6, 7, 2, 3, 8, 9]]


def test_paged_flat_slots_routes_past_table_into_trash():
    # a FULLY-allocated table: out-of-range positions must go to the
    # trash block, never clamp into the row's last REAL block (which
    # would silently overwrite live cache)
    table = jnp.asarray([[2, 3]], jnp.int32)
    pos = jnp.asarray([[5]], jnp.int32)  # block index 2 >= table width 2
    flat = np.asarray(paged_flat_slots(table, pos, block_size=2))
    assert flat[0, 0] == 1  # trash block 0, offset 5 % 2


def test_int8_roundtrip_error_bounded():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(5, 3, 16)).astype(np.float32))
    q, scale = kv_quantize_int8(x)
    assert q.dtype == jnp.int8 and scale.shape == (5, 3)
    back = kv_dequantize_int8(q, scale, jnp.float32)
    err = np.abs(np.asarray(back) - np.asarray(x)).max()
    # max-abs/127 symmetric quantization: error <= scale/2 per element
    assert err <= float(np.asarray(scale).max()) / 2 + 1e-7


def _empty_view(ctx_len, new_len, table, n_kv, h, quantized=False,
                num_blocks=6, block_size=2):
    """One row's view of an all-zero pool: ``ctx_len`` tokens cached,
    ``new_len`` of the presented tokens real."""
    pool = jnp.zeros((num_blocks, block_size, n_kv, h), jnp.float32)
    scale = (
        jnp.zeros((num_blocks, block_size, n_kv), jnp.float32)
        if quantized else None
    )
    if quantized:
        pool = pool.astype(jnp.int8)
    return PagedKVCacheView(
        pool_k=pool, pool_v=pool,
        block_table=jnp.asarray([table], jnp.int32),
        context_len=jnp.asarray([ctx_len], jnp.int32),
        scale_k=scale, scale_v=scale,
        new_len=jnp.asarray([new_len], jnp.int32),
    )


@pytest.fixture(scope="module")
def toy_inference():
    from scaling_tpu.serve.bench import build_toy_inference

    return build_toy_inference(hidden=32, layers=3, vocab=64, heads=4)


def _write_chunk(toy_inference, ctx_len, new_len, table, quantized, seed):
    """One chunk row of width 8 through the pool's writer as the mixed
    program reaches it (``_paged_attention``: flat slots from the row's
    table, pads to trash, ``paged_scatter_kv``). Returns (k, new view)."""
    attn = toy_inference.module.layers[1].attention
    n, n_kv, h = attn.num_attention_heads, attn.num_kv_heads, attn.head_dim
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(1, 8, n, h)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(1, 8, n_kv, h)).astype(np.float32))
    view = _empty_view(ctx_len, new_len, table, n_kv, h, quantized)
    out, new = attn._paged_attention(q, k, k, view, 1, 8, ForwardContext())
    assert np.isfinite(np.asarray(out)).all()
    return np.asarray(k)[0], new


@pytest.mark.parametrize("quantized", [False, True], ids=["native", "int8"])
def test_what_a_chunk_wrote_is_what_a_gather_reads_back(toy_inference,
                                                        quantized):
    # a ragged chunk in the middle of a prompt: 5 real tokens of 8 behind
    # 3 cached ones, starting inside a block; blocks scattered on purpose
    block_row = [3, 1, 4, 2]
    k, new = _write_chunk(toy_inference, 3, 5, block_row, quantized, seed=1)
    row = jnp.asarray(block_row)
    # gather the row back through the block table: logical order restored
    gk = new.pool_k[row].reshape(8, *k.shape[1:])
    if quantized:
        gs = new.scale_k[row].reshape(8, k.shape[1])
        gk = kv_dequantize_int8(gk, gs, jnp.float32)
    got = np.asarray(gk)
    tol = 0.02 if quantized else 0.0
    assert np.abs(got[3:8] - k[:5]).max() <= tol
    assert not got[:3].any()  # the cached tokens' slots were not touched


def test_chunk_padding_lands_in_trash_not_blocks(toy_inference):
    k, new = _write_chunk(toy_inference, 0, 3, [3, 1, 4, 0], False, seed=2)
    pool = np.asarray(new.pool_k)
    # real blocks 3 and 1 hold tokens 0..2; token 3 is a pad: its slot in
    # block 1 and the row's next block (4) stay untouched
    assert np.allclose(pool[3], k[0:2])
    assert np.allclose(pool[1, 0], k[2])
    assert not pool[1, 1].any()
    assert not pool[4].any() and not pool[2].any() and not pool[5].any()
    # pads went somewhere in trash block 0 (content irrelevant, only that
    # no REAL block got them)
    assert pool[0].any()


# --- the donated pool state leaves the program as it entered (ISSUE 31) ---

# 8 slots x chunk 32: the smallest engine with BOTH token buckets (8 decode
# tokens + 3 chunks round up to 128, under the full 256)
SLOTS, MAX_BLOCKS, CHUNK = 8, 8, 32
WIDTHS = (128, 256)
LOOP_STEPS = 4
MODELS = ["dense", "routed", "mp2", "looped", "hybrid"]


@pytest.fixture(scope="module")
def inference_modules(toy_inference):
    """The model kinds whose mixed programs differ in what they return or
    where their pools live: dense; routed (its first output is the grid
    flattened + the (E,) load); dense on a 2-device model-parallel
    serving mesh (pools sharded over ``model``); looped (three layers'
    pools of 4 x the blocks ride a rolled loop's carry, and its first
    output is the grid flattened + the exit distribution's four numbers);
    a pattern stack (three layers: Mamba-2, attention, routed with 2 of 4
    experts held: ONE KV pool, one recurrent line a slot, and its first
    output the grid flattened + the held load + the absent count)."""
    from scaling_tpu.models.transformer import TransformerConfig
    from scaling_tpu.models.transformer.inference import (
        TransformerInferenceModule,
    )
    from scaling_tpu.models.transformer.model import init_model
    from scaling_tpu.serve.bench import build_toy_inference

    routed = TransformerConfig.from_dict({
        "topology": {"model_parallel_size": 1, "pipe_parallel_size": 1,
                     "data_parallel_size": 1, "micro_batch_size": 1,
                     "gradient_accumulation_steps": 1},
        "transformer_architecture": {
            "vocab_size": 64, "hidden_size": 32, "num_layers": 3,
            "num_attention_heads": 4, "sequence_length": 256,
            "mlp_type": "moe", "mlp_factor": 0.5, "moe_num_experts": 4,
            "moe_top_k": 2, "norm_type": "rms", "weight_tying": False,
            "activation_function": "silu", "mlp_bias": False},
        "optimizer": {"gradient_clipping": 1.0},
        "learning_rate_scheduler": {"learning_rate": 3e-4},
        "trainer": {"train_iterations": 1, "seed": 0},
        "data": {}, "logger": {"log_dir": None},
    })
    module = init_model(routed, None)
    looped = TransformerConfig.from_dict({
        **routed.as_dict(), "transformer_architecture": {
            "vocab_size": 64, "hidden_size": 32, "num_layers": 3,
            "num_attention_heads": 4, "sequence_length": 256,
            "mlp_type": "swiglu", "mlp_factor": 2.0, "norm_type": "rms",
            "weight_tying": False, "mlp_bias": False, "loop_steps": LOOP_STEPS,
            "sandwich_norm": True, "loop_exit_gate": True}})
    looped_module = init_model(looped, None)
    hybrid = TransformerConfig.from_dict({
        **routed.as_dict(), "transformer_architecture": {
            "vocab_size": 64, "hidden_size": 32, "num_layers": 3,
            "layer_pattern": ["mamba", "attention", "moe"],
            "num_attention_heads": 4, "sequence_length": 256,
            "mlp_type": "moe", "moe_expert_width": 16, "moe_num_experts": 4,
            "moe_top_k": 2, "moe_glu": False, "moe_router": "sigmoid_bias",
            "moe_experts_held": 2, "activation_function": "relu2",
            "mamba_num_heads": 4, "mamba_head_dim": 8, "ssm_state_size": 8,
            "n_groups": 2, "norm_type": "rms", "weight_tying": False,
            "relative_position_embedding_type": "none", "mlp_bias": False}})
    hybrid_module = init_model(hybrid, None)
    return {
        "hybrid": TransformerInferenceModule(
            hybrid, hybrid_module,
            hybrid_module.init_params(jax.random.PRNGKey(0))),
        "looped": TransformerInferenceModule(
            looped, looped_module,
            looped_module.init_params(jax.random.PRNGKey(0))),
        "dense": toy_inference,
        "routed": TransformerInferenceModule(
            routed, module, module.init_params(jax.random.PRNGKey(0))),
        "mp2": build_toy_inference(hidden=32, layers=3, vocab=64, heads=4,
                                   mp=2),
    }


def _program_and_args(inf, kv_dtype, spec_k, width=WIDTHS[0]):
    """The engine's program at one of its token widths, as the plain
    function under its ``jax.jit``, with toy arguments in its signature."""
    from scaling_tpu.serve.engine import EngineConfig, ServeEngine

    engine = ServeEngine(inf, EngineConfig(
        num_slots=SLOTS, block_size=4, num_blocks=2 * MAX_BLOCKS + 1,
        max_blocks_per_seq=MAX_BLOCKS, token_budget=64, prefill_chunk=CHUNK,
        kv_dtype=kv_dtype, spec_k=spec_k,
        # refused for a stack with recurrent layers (a hit would skip
        # tokens the state never saw)
        enable_prefix_cache=not inf.architecture.recurrent_layers,
    ))
    assert engine.config.mixed_widths == WIDTHS

    packed, tick = engine._layout.host(width)
    tick.new_lens[:] = 1
    args = (inf.params, engine._pool_state(), engine._dev(packed),
            engine._base_key)
    return engine, engine._build_mixed_fn(width).__wrapped__, args


def _aliases(lowered):
    """{argument number: the output it aliases}. On one device JAX pairs
    them itself and writes ``tf.aliasing_output`` on ``main``'s
    signature; on a mesh it only marks the donors (``jax.buffer_donor``)
    and XLA pairs them when it compiles, so the table is read off the
    compiled module's ``input_output_alias``."""
    text = lowered.as_text()
    signature = text.split("@main(", 1)[1].split(") -> ", 1)[0]
    if "jax.buffer_donor" in signature:
        table = re.search(r"input_output_alias=\{(.*?)\}, entry",
                          lowered.compile().as_text()).group(1)
        return {int(arg): int(out) for out, arg in
                re.findall(r"\{(\d+)\}: \((\d+), \{\}, \S+-alias\)", table)}
    aliases = {}
    for arg in signature.split("%arg")[1:]:
        m = re.search(r"tf\.aliasing_output = (\d+)", arg)
        if m:
            aliases[int(arg.split(":", 1)[0])] = int(m.group(1))
    return aliases


@pytest.mark.parametrize("width", WIDTHS, ids=["small", "full"])
@pytest.mark.parametrize("kv_dtype", ["native", "int8"])
@pytest.mark.parametrize(
    "model,spec_k",
    [("dense", 0), ("dense", 2), ("routed", 0), ("mp2", 0), ("looped", 0),
     ("hybrid", 0)],
    ids=["mixed", "mixed-spec2", "routed", "mp2", "looped", "hybrid"],
)
def test_donated_pool_aliases_the_output_computed_from_it(
        inference_modules, model, spec_k, kv_dtype, width):
    """JAX pairs a donated buffer with an output of its shape and dtype
    in flattened order, so ``pool_v[3]`` is updated in place only if the
    program's lowered ``main`` says its argument aliases the output leaf
    at ``pool_v[3]``'s place in the returned state. Lowered with donation
    forced (the CPU engine never donates; lowering alone warns of
    nothing). Returning the per-layer views instead (k0, v0, table, ctx,
    k1, v1, ...) fails this: three layers' six pools were paired with
    outputs 1, 2, 4, 5, 7, 8 where 1, 2, 3, 4, 5, 6 compute from them,
    and XLA copied every pool but the first on every call. A routed
    model's first output is one vector (grid + load), still ONE leaf
    ahead of the state; on the serving mesh every pool is sharded over
    ``model`` and XLA does the pairing. Both token widths' programs
    donate and return the same state. A looped model's pools pass through
    its rolled loop's carry on their way from argument to output: still
    one pool a LAYER, each aliased to the output computed from it. A
    pattern stack's state carries, after the pools of its ONE attention
    layer, the ssm and conv lines of its Mamba-2 layer: donated and aliased
    like them (a copy of the cell's 0.96 GB of lines would cost ~2.3 ms a
    tick)."""
    _, fn, args = _program_and_args(
        inference_modules[model], kv_dtype, spec_k, width)
    lowered = jax.jit(fn, donate_argnums=(1,), keep_unused=True).lower(
        *args
    )
    first = len(jax.tree_util.tree_leaves(args[0]))  # params come first
    donated = jax.tree_util.tree_leaves(args[1])
    if model == "hybrid":  # 1 K + 1 V pool (+ 2 scales), 1 ssm + 1 conv line
        assert len(donated) == (6 if kv_dtype == "int8" else 4)
    else:
        assert len(donated) == (12 if kv_dtype == "int8" else 6)
    # outputs flatten as (tokens, *state): state leaf j is output 1 + j
    want = {first + j: 1 + j for j in range(len(donated))}
    assert _aliases(lowered) == want


@pytest.mark.parametrize("width", WIDTHS, ids=["small", "full"])
@pytest.mark.parametrize("kv_dtype", ["native", "int8"])
@pytest.mark.parametrize("model", MODELS,
                         ids=["mixed", "routed", "mp2", "looped", "hybrid"])
def test_programs_return_the_state_in_pool_state_structure(
        inference_modules, model, kv_dtype, width):
    engine, fn, args = _program_and_args(
        inference_modules[model], kv_dtype, 0, width)
    sampled, state = jax.eval_shape(fn, *args)
    sw = engine.config.sample_width
    tail = {"routed": engine.num_experts, "looped": LOOP_STEPS,
            "hybrid": 2 + 1}  # the held experts' load + the absent count
    assert sampled.shape == (
        (SLOTS * sw + tail[model],) if model in tail else (SLOTS, sw)
    )
    structure = jax.tree_util.tree_structure
    assert structure(state) == structure(engine._pool_state())
    assert (state[2] is None) == (kv_dtype == "native")
    for got, held in zip(jax.tree_util.tree_leaves(state),
                         jax.tree_util.tree_leaves(engine._pool_state())):
        assert (got.shape, got.dtype) == (held.shape, held.dtype)
    # one pool a layer; a looped model's holds every step's blocks; a
    # pattern stack has pools for its attention layers only, and lines of
    # recurrent state, one a slot, for its Mamba-2 layers
    steps = LOOP_STEPS if model == "looped" else 1
    if model == "hybrid":
        assert len(state) == 6 and len(state[0]) == 1 == engine.pools.kv_lines
        assert [a.shape[0] for a in state[4] + state[5]] == [SLOTS, SLOTS]
        return
    assert len(state) == 4
    assert len(state[0]) == 3 and engine.pools.kv_lines == 3 * steps
    assert state[0][0].shape[0] == steps * (2 * MAX_BLOCKS + 1)


def test_run_layers_on_paged_views_defaults_to_the_kernel(toy_inference):
    """``_run_layers`` given block-paged views and NO ``paged_kernel``
    traces the Pallas call: the back-end's default is written once, on
    ``ForwardContext``, and a caller that names none cannot get the
    gather (which stays reachable by name, as the tests' reference)."""
    from scaling_tpu.serve.kvcache import build_layer_views

    engine, _, args = _program_and_args(toy_inference, "native", 0)
    params, state, packed = args[:3]
    tick = engine._layout.split(packed)
    tables, ctx_lens, new_lens = tick.tables, tick.ctx_lens, tick.new_lens
    tokens = jnp.zeros((SLOTS, CHUNK), jnp.int32)  # a row-major batch
    pos = ctx_lens[:, None] + jnp.arange(CHUNK)[None, :]
    batch = toy_inference._make_batch(tokens, pos)

    def pallas_calls(**named):
        views = build_layer_views(state, tables, ctx_lens, new_lens)
        jaxpr = jax.make_jaxpr(
            lambda p: toy_inference._run_layers(p, batch, views, None,
                                                **named)[0]
        )(params)
        return str(jaxpr).count("pallas_call")

    assert pallas_calls() > 0
    assert pallas_calls(paged_kernel="xla") == 0
