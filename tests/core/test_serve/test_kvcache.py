"""Paged KV pool mechanics (ISSUE 9): flat-slot addressing, chunk
scatter + block gather round-trips, int8 quantization accuracy on
hand-built pools; then the donated pool state through the engine's
program, dense, routed, sharded over two devices, looped, and pattern stacks
whose state carries lines a slot beside the pools (Mamba-2's, a short
convolution's, and a kind this file defines: what a kind declares beside its
mixer is all the pool reads)."""

import re
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from scaling_tpu.nn.attention import (
    PagedKVCacheView,
    PagedTokenMap,
    kv_dequantize_int8,
    kv_quantize_int8,
    paged_flat_slots,
)
from scaling_tpu.nn.base_layer import BaseLayer, ForwardContext
from scaling_tpu.nn.mamba import RecurrentStateView
from scaling_tpu.nn.short_conv import ConvTailView
from scaling_tpu.serve.kvcache import (
    build_layer_views,
    init_pools,
    line_layers,
    state_from_views,
)


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
# the stacks of test_hybrid_serving.py and test_conv_moe_serving.py, in an
# engine of 16 slots whose buckets are 128 and 512 places
WIDE = {"num_slots": 16, "max_blocks_per_seq": 12, "num_blocks": 16 * 12 + 1}
WIDE_WIDTHS = (128, 512)


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
    output the grid flattened + the held load + the absent count); and the
    two serving tests' own stacks, Mamba-2 ``MEM*EM`` and LFM2's blocks."""
    from scaling_tpu.models.transformer import TransformerConfig
    from scaling_tpu.models.transformer.inference import (
        TransformerInferenceModule,
    )
    from scaling_tpu.models.transformer.model import init_model
    from scaling_tpu.serve.bench import build_toy_inference
    from tests.core.test_serve.test_conv_moe_serving import lfm2_config
    from tests.core.test_serve.test_hybrid_serving import hybrid_config

    def seeded(config):
        module = init_model(config, None)
        return TransformerInferenceModule(
            config, module, module.init_params(jax.random.PRNGKey(0)))

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
        "mamba2": seeded(hybrid_config()),
        "lfm2": seeded(lfm2_config()),
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


def _program_and_args(inf, kv_dtype, width=WIDTHS[0], **config):
    """The engine's program at one of its token widths, as the plain
    function under its ``jax.jit``, with toy arguments in its signature."""
    from scaling_tpu.serve.engine import EngineConfig, ServeEngine

    engine = ServeEngine(inf, EngineConfig(**{
        "num_slots": SLOTS, "block_size": 4, "num_blocks": 2 * MAX_BLOCKS + 1,
        "max_blocks_per_seq": MAX_BLOCKS, "token_budget": 64,
        "prefill_chunk": CHUNK, "kv_dtype": kv_dtype,
        # refused for a stack that keeps a line a slot (a hit would skip
        # tokens the lines never saw)
        "enable_prefix_cache": inf.architecture.layer_pattern is None,
        **config}))
    assert width in engine.config.mixed_widths

    packed, tick = engine._layout.host(width)
    tick.new_lens[:] = 1
    args = (inf.params, engine._pool_state(), engine._dev(packed),
            engine._base_key, engine._prev)
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


# (stack, kv_dtype, KV layers, lists of lines, what follows the grid in the
# first output): every stack whose state is donated
ALIAS_CASES = [
    pytest.param(model, kv_dtype, kv_layers, lines, None,
                 id=f"{name}-{kv_dtype}")
    for name, model, kv_layers, lines in [
        ("mixed", "dense", 3, 0), ("routed", "routed", 3, 0),
        ("mp2", "mp2", 3, 0), ("looped", "looped", 3, 0),
        ("hybrid", "hybrid", 1, 2)]  # 1 ssm + 1 conv line
    for kv_dtype in ("native", "int8")
] + [
    # 3 ssm + 3 conv lines; the 4 held experts' load and the absent count
    pytest.param("mamba2", "native", 1, 6, 4 + 1, id="mamba2-wide"),
    # 3 conv tails; the 8 experts' load
    pytest.param("lfm2", "native", 1, 3, 8, id="lfm2-wide"),
]


@pytest.mark.parametrize("bucket", [0, 1], ids=["small", "full"])
@pytest.mark.parametrize("model,kv_dtype,kv_layers,lines,tail", ALIAS_CASES)
def test_donated_state_aliases_the_output_computed_from_it(
        inference_modules, model, kv_dtype, kv_layers, lines, tail, bucket):
    """JAX pairs a donated buffer with an output of its shape and dtype
    in flattened order, so ``pool_v[3]`` is updated in place only if the
    program's lowered ``main`` says its argument aliases the output leaf
    at ``pool_v[3]``'s place in the returned state. Lowered with donation
    forced (the CPU engine never donates; lowering alone warns of
    nothing). Returning the per-layer views instead (k0, v0, table, ctx,
    k1, v1, ...) fails this: three layers' six pools were paired with
    outputs 1, 2, 4, 5, 7, 8 where 1, 2, 3, 4, 5, 6 compute from them,
    and XLA copied every pool but the first on every call. A routed
    model's first output is one vector (grid + load), still ONE leaf, and
    with the grid the next program is fed TWO leaves lie ahead of the state; on the serving mesh every pool is sharded over
    ``model`` and XLA does the pairing. Both token widths' programs
    donate and return the same state. A looped model's pools pass through
    its rolled loop's carry on their way from argument to output: still
    one pool a LAYER, each aliased to the output computed from it. A
    pattern stack's state carries, after the pools of its ONE attention
    layer, the ssm and conv lines of its Mamba-2 layer: donated and aliased
    like them (a copy of the cell's 0.96 GB of lines would cost ~2.3 ms a
    tick); the two serving tests' stacks (three Mamba-2 layers' six lists of
    lines, three short convolutions' tails) in their wider engine too, each
    also held to the structure, shapes and dtypes it was handed."""
    wide = tail is not None
    engine, fn, args = _program_and_args(
        inference_modules[model], kv_dtype,
        (WIDE_WIDTHS if wide else WIDTHS)[bucket], **(WIDE if wide else {}))
    if wide:
        sampled, _, state = jax.eval_shape(fn, *args)
        assert (jax.tree_util.tree_structure(state)
                == jax.tree_util.tree_structure(args[1]))
        for got, held in zip(jax.tree_util.tree_leaves(state),
                             jax.tree_util.tree_leaves(args[1])):
            assert (got.shape, got.dtype) == (held.shape, held.dtype)
        assert sampled.shape == (16 + tail,)  # a slot's one sample, then the tail
    lowered = jax.jit(fn, donate_argnums=(1,), keep_unused=True).lower(
        *args
    )
    first = len(jax.tree_util.tree_leaves(args[0]))  # params come first
    donated = jax.tree_util.tree_leaves(args[1])
    # a K and a V pool (+ 2 scales) an attention layer, then the lines
    assert len(donated) == kv_layers * (4 if kv_dtype == "int8" else 2) + lines
    # outputs flatten as (tokens, the grid the next program is fed, *state):
    # state leaf j is output 2 + j
    want = {first + j: 2 + j for j in range(len(donated))}
    assert _aliases(lowered) == want


@pytest.mark.parametrize("width", WIDTHS, ids=["small", "full"])
@pytest.mark.parametrize("kv_dtype", ["native", "int8"])
@pytest.mark.parametrize("model", MODELS,
                         ids=["mixed", "routed", "mp2", "looped", "hybrid"])
def test_programs_return_the_state_in_pool_state_structure(
        inference_modules, model, kv_dtype, width):
    engine, fn, args = _program_and_args(
        inference_modules[model], kv_dtype, width)
    sampled, feed, state = jax.eval_shape(fn, *args)
    tail = {"routed": engine.num_experts, "looped": LOOP_STEPS,
            "hybrid": 2 + 1}  # the held experts' load + the absent count
    assert sampled.shape == (
        (SLOTS + tail[model],) if model in tail else (SLOTS, 1)
    )
    # what the next program takes as ``prev``: the grid alone, one shape
    # whatever follows it in the host's read
    assert (feed.shape, feed.dtype) == (engine._prev.shape, engine._prev.dtype)
    assert feed.shape == (SLOTS, 1)
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


# --- what a kind declares beside its mixer is all the pool reads (ISSUE 51) ---

def _addressing(slots, max_blocks=2):
    """A tick's addressing for ``slots`` rows: every row brings one token."""
    table = 1 + jnp.arange(slots * max_blocks, dtype=jnp.int32).reshape(
        slots, max_blocks)
    return table, jnp.zeros((slots,), jnp.int32), jnp.ones((slots,), jnp.int32)


@pytest.mark.parametrize("model,kinds", [
    ("dense", None),
    ("mamba2", [RecurrentStateView] * 2 + [PagedKVCacheView, RecurrentStateView]),
    ("lfm2", [ConvTailView] * 2 + [PagedKVCacheView, ConvTailView]),
], ids=["dense", "mamba2", "conv-tail"])
def test_views_built_from_a_state_give_that_state_back(inference_modules, model,
                                                       kinds):
    """``state_from_views`` undoes ``build_layer_views`` for every kind that
    exists: the same structure, the same buffers at the same places."""
    pools = init_pools(inference_modules[model], 5, 4, num_slots=2)
    assert pools.kinds == kinds
    state = pools.state()
    views = build_layer_views(state, *_addressing(2), kinds=pools.kinds)
    assert [type(v) for v in views] == (kinds or [PagedKVCacheView] * 3)
    back = state_from_views(views)
    assert (jax.tree_util.tree_structure(back)
            == jax.tree_util.tree_structure(state))
    assert all(a is b for a, b in zip(jax.tree_util.tree_leaves(back),
                                      jax.tree_util.tree_leaves(state)))


class RunningSumView(NamedTuple):
    """A fourth kind of per-slot state, known to this file alone: the sum of
    the mixer's inputs over the tokens a slot has seen."""

    LINES = ("total",)
    NAME = "sum"

    total: jax.Array        # (slots, hidden) float32
    context_len: jax.Array
    new_len: jax.Array
    token_map: Optional[PagedTokenMap] = None


class RunningSum(BaseLayer):
    """``y_t = sum of x up to t``: a stub mixer that names its view, called as
    the per-slot mixers are (``state``, ``return_state``)."""

    STATE_VIEW = RunningSumView

    def __call__(self, params, x, ctx, state=None, return_state=False):
        if state is None:  # uncached: the whole sequence from zeros
            y = jnp.cumsum(x, axis=1)
            return (y, y[:, -1].astype(jnp.float32)) if return_state else y
        # served, a row-major batch: row r is slot r, its first new_len
        # positions real; a row at context 0 starts from zeros
        real = jnp.arange(x.shape[1])[None, :] < state.new_len[:, None]
        start = jnp.where(state.context_len[:, None] == 0, 0.0, state.total)
        y = start[:, None] + jnp.cumsum(x * real[..., None], axis=1)
        last = jnp.take_along_axis(
            y, jnp.maximum(state.new_len - 1, 0)[:, None, None], axis=1)[:, 0]
        total = jnp.where(state.new_len[:, None] > 0, last, state.total)
        return y, state._replace(total=total.astype(state.total.dtype))


def test_a_kind_defined_here_goes_through_the_pool_and_the_walk():
    """A stack of all four kinds: Mamba-2, a short convolution, attention, and
    in place of the second convolution's mixer the stub above. Its line is
    allocated by the rule (the probe's final state, a line a slot), lies in
    the state where the stack first meets its kind, is handed to its layer as
    its own view, comes back updated in the structure it entered in, and is
    counted; ``serve/kvcache.py`` and the walk name no kind."""
    from scaling_tpu.models.transformer.inference import (
        TransformerInferenceModule,
    )
    from scaling_tpu.models.transformer.model import init_model
    from tests.core.test_serve.test_hybrid_serving import hybrid_config

    config = hybrid_config(
        num_layers=4, layer_pattern=["mamba", "conv", "attention", "conv"])
    module = init_model(config, None)
    inf = TransformerInferenceModule(
        config, module, module.init_params(jax.random.PRNGKey(0)))
    stub = [l for l in module.layers if getattr(l, "consumes", None)][-1]
    stub.mixer = RunningSum()
    assert stub.consumes is RunningSumView

    slots, hidden = 3, 48
    pools = init_pools(inf, 7, 4, num_slots=slots)
    assert pools.kinds == [RecurrentStateView, ConvTailView, PagedKVCacheView,
                           RunningSumView]
    assert line_layers(pools.kinds) == {
        RecurrentStateView: 1, ConvTailView: 1, RunningSumView: 1}
    assert pools.state_lines == 3 and pools.kv_lines == 1
    # the four of the pools, then a list a field in the order the stack meets
    # the kinds: ssm, conv; tail; total
    state = pools.state()
    assert [len(part) for part in state[4:]] == [1, 1, 1, 1]
    ssm, conv, tail, total = (part[0] for part in state[4:])
    assert (total.shape, total.dtype) == ((slots, hidden), jnp.float32)
    assert tail.shape == (slots, 3, hidden)
    assert pools.state_bytes() == sum(
        a.size * a.dtype.itemsize for a in (ssm, conv, tail, total))

    table, ctx_len, new_len = _addressing(slots)
    new_len = new_len.at[2].set(0)  # the last slot is empty
    views = build_layer_views(state, table, ctx_len, new_len, kinds=pools.kinds)
    assert [type(v) for v in views] == pools.kinds
    assert views[3].total is total and views[3].new_len is new_len
    back = state_from_views(views)
    assert (jax.tree_util.tree_structure(back)
            == jax.tree_util.tree_structure(state))
    assert all(a is b for a, b in zip(jax.tree_util.tree_leaves(back),
                                      jax.tree_util.tree_leaves(state)))

    # one tick of the walk over those views: every layer is handed its own
    batch = inf._make_batch(jnp.ones((slots, 2), jnp.int32),
                            jnp.zeros((slots, 2), jnp.int32))
    _, new_views = inf._run_layers(inf.params, batch, views, None,
                                   paged_kernel="xla")
    assert [type(v) for v in new_views] == pools.kinds
    new_state = state_from_views(new_views)
    assert (jax.tree_util.tree_structure(new_state)
            == jax.tree_util.tree_structure(state))
    pools.absorb_state(new_state)
    assert jax.tree_util.tree_structure(pools.state()) == (
        jax.tree_util.tree_structure(state))
    advanced = np.asarray(pools.lines[-1][0])
    assert np.abs(advanced[:2]).min() > 0 and not advanced[2].any()
    # a view handed to a layer of another kind is refused by both names
    with pytest.raises(ValueError, match="consumes a RunningSumView and was "
                                         "handed a ConvTailView"):
        inf._run_layers(inf.params, batch, views[:3] + views[1:2], None,
                        paged_kernel="xla")


def test_run_layers_on_paged_views_defaults_to_the_kernel(toy_inference):
    """``_run_layers`` given block-paged views and NO ``paged_kernel``
    traces the Pallas call: the back-end's default is written once, on
    ``ForwardContext``, and a caller that names none cannot get the
    gather (which stays reachable by name, as the tests' reference)."""
    engine, _, args = _program_and_args(toy_inference, "native")
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
