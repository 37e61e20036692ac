"""The mixed program packs a tick's real tokens token-major (ISSUE 33).

(a) What it computes is what the row-major formulation computes (one batch
row a slot, ``mixed_width`` positions each, the gather back-end): the same
sampled tokens, the same bytes in the pool, on ticks that mix decode rows,
drafted rows, a mid-prompt chunk, a finishing chunk and empty slots; native
and int8 pools, dense and routed, one device and the mp = 2 serving mesh.
(b) A tick runs at the smallest token width that holds it, and the span
fields and the counter say so. (c) Every width's program is lowered by the
engine's first tick and none afterwards.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from scaling_tpu import obs
from scaling_tpu.nn.attention import packed_token_map
from scaling_tpu.serve.engine import (
    EngineConfig,
    ServeEngine,
    packed_batch_shape,
)
from scaling_tpu.serve.kvcache import build_layer_views, state_from_views
from tests.transformer.test_serving import jitted_programs

# the smallest engine with both token buckets: 8 decode tokens + 3 chunks of
# 32 round up to 128, under the full 8 x 32 = 256
SLOTS, CHUNK, BLOCK, MAX_BLOCKS = 8, 32, 16, 8
SMALL, FULL = 128, 256


def make_engine(inf, **overrides):
    return ServeEngine(inf, EngineConfig(**{
        "num_slots": SLOTS, "block_size": BLOCK, "prefill_chunk": CHUNK,
        "num_blocks": SLOTS * MAX_BLOCKS + 1, "max_blocks_per_seq": MAX_BLOCKS,
        "enable_prefix_cache": False, **overrides}))


# ------------------------------------------------- the widths and the map
# (num_slots, prefill_chunk, small_bucket_chunks) -> the widths: a decode row
# in every slot and so many chunks, in whole lanes, under slots x chunk. One
# row for every serve configuration of the benchmark, named: the pin that no
# cell's widths move
@pytest.mark.parametrize("slots,chunk,small_chunks,want", [
    (16, 32, 3, (128, 512)),     # Mistral, OLMoE, Ouro
    (64, 32, 3, (256, 2048)),    # Nemotron, LFM2
    (96, 32, 3, (256, 3072)),    # Falcon-H1
    (32, 160, 3, (512, 5120)),   # Kimi-K2
    (16, 320, 3, (1024, 5120)),  # DeepSeek-V3.2-Exp
    (8, 320, 3, (1024, 2560)),   # Keye
    (32, 256, 3, (896, 8192)),   # Xing
    (24, 256, 3, (896, 6144)),   # Laguna
    (256, 32, 16, (768, 8192)),  # Qwen3-Next: many slots, short chunks
    (256, 32, 3, (384, 8192)),   # (at the default its slots alone are 256)
    (16, 256, 3, (896, 4096)),   # dots3
    (8, 32, 3, (128, 256)),
    (4, 32, 3, (128,)),          # the full width is no larger: one program
    (4, 4, 3, (16,)),            # nor need it be whole lanes
], ids=lambda v: str(v))
def test_token_widths_follow_from_the_configuration(slots, chunk, small_chunks,
                                                    want):
    cfg = EngineConfig(num_slots=slots, prefill_chunk=chunk,
                       small_bucket_chunks=small_chunks)
    assert cfg.mixed_widths == want
    assert cfg.mixed_width == chunk
    assert all(w % 128 == 0 for w in want[:-1])


@pytest.mark.parametrize("width,row_width,shape", [
    (128, 32, (4, 32)), (512, 32, (16, 32)),  # the full width: row-major's
    (16, 4, (4, 4)), (128, 48, (4, 32)), (256, 4, (64, 4)),
], ids=lambda v: str(v))
def test_a_width_runs_in_groups_of_a_row_s_width(width, row_width, shape):
    assert packed_batch_shape(width, row_width) == shape


def test_the_map_is_the_host_s_packing_read_back():
    """Rows of 3, 0, 1, 0, 4 tokens packed into 12 places: each token's
    row and offset, where each row's tokens lie, and what is no token."""
    new_len = jnp.asarray([3, 0, 1, 0, 4], jnp.int32)
    m = packed_token_map(new_len, (2, 6), row_width=4)
    row, offset = np.asarray(m.row).ravel(), np.asarray(m.offset).ravel()
    assert row[:8].tolist() == [0, 0, 0, 2, 4, 4, 4, 4]
    assert offset[:8].tolist() == [0, 1, 2, 0, 0, 1, 2, 3]
    # past the last token: the last row, past its length (so not real)
    assert (row[8:] == 4).all() and (offset[8:] >= 4).all()
    assert np.asarray(m.row_tokens).tolist() == [
        [0, 1, 2, 3], [3, 4, 5, 6], [3, 4, 5, 6], [4, 5, 6, 7], [4, 5, 6, 7]]


# ------------------------------------- (a) packed == row-major, per tick
@pytest.fixture(scope="module")
def models():
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
            "vocab_size": 64, "hidden_size": 32, "num_layers": 2,
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
    toy = dict(hidden=32, layers=2, vocab=64, heads=4)
    return {
        "dense": build_toy_inference(**toy),
        "routed": TransformerInferenceModule(
            routed, module, module.init_params(jax.random.PRNGKey(0))),
        "mp2": build_toy_inference(**toy, mp=2),
    }


# (context length, real new tokens) by slot; a decode row brings one
TICKS = {
    # 66 tokens: the small width
    "common": [(37, "decode"), None, (32, 32), (64, 11), (5, "decode"),
               None, (90, "decode"), (0, 20)],
    # 5 whole chunks and more: only the full width holds them
    "crowded": [(0, 32), (32, 32), (17, "decode"), (64, 32), (0, 32),
                None, (96, 32), (3, 7)],
}


def row_major_program(engine):
    """The tick in the layout it had before tokens were packed: one batch
    row a slot, ``mixed_width`` positions each, attention by the gather
    formulation, the sampled position, its last, picked out of each row."""
    inf, cfg = engine.inf, engine.config
    width = cfg.mixed_width

    def mixed(params, state, packed, base_key, prev):
        (tables, ctx_lens, new_lens, topks, reqids, gen0, temps, topps,
         tokens) = engine._layout.split(packed)
        tokens = tokens.reshape(cfg.num_slots, width)
        pos = ctx_lens[:, None] + jnp.arange(width)[None, :]
        views = build_layer_views(state, tables, ctx_lens, new_lens)
        last = jnp.clip(new_lens - 1, 0, width - 1)
        index = (jnp.arange(cfg.num_slots) * width + last)[:, None]
        logits, new_views, *load = inf._run_layers(
            params, inf._make_batch(tokens, pos), views, None,
            paged_kernel="xla", gather_index=index,
            moe_load=engine.num_experts > 0)
        sampled = engine._sample_grid(
            logits, temps, topps, topks, reqids, gen0 + last, base_key)
        return sampled, state_from_views(new_views), load

    return jax.jit(mixed)


def random_tick(engine, rows, seed):
    """Pools full of random history, and one tick's operands in both
    layouts: (state, shared operands, row-major tokens, packed tokens)."""
    cfg = engine.config
    rng = np.random.default_rng(seed)

    def like(held):
        if held.dtype == jnp.int8:
            x = rng.integers(-127, 128, held.shape).astype(np.int8)
        elif held.ndim == 3:  # an int8 pool's scales
            x = rng.uniform(0.004, 0.02, held.shape).astype(np.float32)
        else:
            x = rng.normal(size=held.shape).astype(np.float32)
        return jax.device_put(jnp.asarray(x, held.dtype), held.sharding)

    state = jax.tree_util.tree_map(like, engine._pool_state())
    n = cfg.num_slots
    tables = np.zeros((n, cfg.max_blocks_per_seq), np.int32)
    ctx, new_lens = np.zeros((n,), np.int32), np.zeros((n,), np.int32)
    tokens = np.zeros((n, cfg.mixed_width), np.int32)
    blocks = rng.permutation(np.arange(1, cfg.num_blocks))  # never trash
    for slot, row in enumerate(rows):
        if row is None:
            continue
        ctx[slot] = row[0]
        new_lens[slot] = 1 if row[1] == "decode" else row[1]
        tables[slot] = blocks[slot * MAX_BLOCKS:(slot + 1) * MAX_BLOCKS]
        tokens[slot, :new_lens[slot]] = rng.integers(1, 60, new_lens[slot])
    packed = np.concatenate([tokens[s, :new_lens[s]] for s in range(n)])
    temps = np.where(np.arange(n) % 3 == 0, 0.8, 0.0).astype(np.float32)
    shared = dict(
        tables=tables, ctx=ctx, new_lens=new_lens, temps=temps,
        topps=np.zeros((n,), np.float32), topks=np.zeros((n,), np.int32),
        reqids=np.arange(n, dtype=np.int32) + 7,
        gen0=rng.integers(0, 9, n).astype(np.int32))
    return state, shared, tokens, packed


def pack(engine, shared, tokens):
    """The tick as the engine hands it over: ONE int32 vector, written
    through the layout's own fields."""
    packed, tick = engine._layout.host(len(tokens))
    tick.tables[:], tick.ctx_lens[:] = shared["tables"], shared["ctx"]
    tick.new_lens[:], tick.gen0[:] = shared["new_lens"], shared["gen0"]
    tick.temps[:], tick.topps[:] = shared["temps"], shared["topps"]
    tick.topks[:], tick.reqids[:] = shared["topks"], shared["reqids"]
    tick.tokens[:] = tokens
    return engine._dev(packed)


def call(engine, fn, state, shared, tokens):
    return fn(engine.inf.params, state, pack(engine, shared, tokens),
              engine._base_key, engine._prev)


# Every stack under both ticks at native pools. At int8 pools the assertion is
# the same at a second dtype: the dense stack's two ticks guard it in tier-1,
# the other six are ``slow`` (tests/conftest.py says what that means).
PACKED_TICKS = [
    pytest.param(
        model, kv_dtype, tick, id=f"{model}-{kv_dtype}-{tick}",
        marks=[pytest.mark.slow] if kv_dtype == "int8" and model != "dense" else [])
    for model in ("dense", "routed", "mp2")
    for kv_dtype in ("native", "int8")
    for tick in TICKS
]


@pytest.mark.parametrize("model,kv_dtype,tick", PACKED_TICKS)
def test_packed_tick_is_the_row_major_tick(models, model, kv_dtype, tick):
    engine = make_engine(models[model], kv_dtype=kv_dtype)
    assert engine.config.mixed_widths == (SMALL, FULL)
    state, shared, tokens, packed = random_tick(engine, TICKS[tick], seed=3)
    width = SMALL if len(packed) <= SMALL else FULL
    assert width == {"common": SMALL, "crowded": FULL}[tick]
    padded = np.zeros((width,), np.int32)
    padded[:len(packed)] = packed

    want, want_state, want_load = call(
        engine, row_major_program(engine), state, shared, tokens.ravel())
    got, feed, got_state = call(
        engine, engine._build_mixed_fn(width), state, shared, padded)

    n, new_lens = SLOTS, shared["new_lens"]
    got, want = np.asarray(got), np.asarray(want)
    # what the next program is fed is the grid itself
    assert np.asarray(feed).tolist() == got[:n].reshape(n, 1).tolist()
    if engine.num_experts:
        # no assignment dropped, and only real positions counted: every
        # real token's top_k choices, in every layer
        load, got = got[n:], got[:n].reshape(n, 1)
        assert load.tolist() == np.asarray(want_load[0]).tolist()
        assert load.sum() == len(packed) * 2 * 2
    # what the host reads of the grid: the one sample of a row that
    # brought a token
    for slot in np.flatnonzero(new_lens):
        assert got[slot].tolist() == want[slot].tolist(), slot
    # the pool: every block but the trash block, byte for byte (int8: the
    # same roundings), scales and all
    for g, w in zip(jax.tree_util.tree_leaves(got_state),
                    jax.tree_util.tree_leaves(want_state)):
        g, w = np.asarray(g)[1:], np.asarray(w)[1:]
        if g.dtype == np.int8:
            assert np.abs(g.astype(int) - w.astype(int)).max() <= 1
            assert (g != w).mean() < 1e-3
        else:
            np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-6)


# ------------------------- (b) bucket choice, (c) lowered at the first tick
@pytest.fixture(scope="module")
def lowerings():
    """A running count of the programs JAX lowers in this process."""
    from jax._src import monitoring

    seen = []

    def on_event(name, duration, **kwargs):
        if name == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            seen.append(name)

    monitoring.register_event_duration_secs_listener(on_event)
    return seen


@pytest.fixture(scope="module")
def served(models, lowerings, tmp_path_factory):
    """One engine through ticks of chosen sizes: a fresh batch of prompts
    (one token to generate each, so a request is done when its prompt is
    in) and the engine's next tick, for each case in turn; everything
    under one capture. Returns (engine, {case: the tick's serve.mixed
    span fields}, lowerings after the first tick / at the end)."""
    engine = make_engine(models["dense"])
    rng = np.random.default_rng(5)
    cases = {
        "at": [32, 32, 32, 32],           # 128: the small width, full
        "one-under": [32, 32, 32, 31],
        "one-over": [32, 32, 32, 32, 1],  # 129
        "all-chunks": [40] * SLOTS,       # every slot a whole chunk: 256
        "one-token": [1],
    }
    ticks = {}
    obs.start_capture(tmp_path_factory.mktemp("packed") / "trace")
    try:
        # the first tick: one decode-sized prompt, as a warm-up sends
        engine.submit([5], 2)
        engine.tick()
        after_first = len(lowerings)
        engine.run_until_done()
        for case, lengths in cases.items():
            for n in lengths:
                engine.submit(list(rng.integers(1, 60, n)), 1)
            step = engine.tick_index
            engine.tick()
            ticks[case] = step
            engine.run_until_done()
    finally:
        capture = obs.stop_capture()
    fields = {f["step"]: f for name, _, _, f in capture.spans
              if name == "serve.mixed"}
    return (engine, {case: fields[step] for case, step in ticks.items()},
            capture, (after_first, len(lowerings)))


@pytest.mark.parametrize("case,tokens,width", [
    ("at", 128, SMALL), ("one-under", 127, SMALL), ("one-over", 129, FULL),
    ("all-chunks", 256, FULL), ("one-token", 1, SMALL),
])
def test_a_tick_runs_at_the_smallest_width_that_holds_it(served, case,
                                                         tokens, width):
    _, spans, _, _ = served
    assert (spans[case]["tokens"], spans[case]["width"]) == (tokens, width)


def test_ticks_and_tokens_are_counted_by_width(served):
    engine, _, capture, _ = served
    spans = [f for name, _, _, f in capture.spans if name == "serve.mixed"]
    for width in (SMALL, FULL):
        mine = [f for f in spans if f["width"] == width]
        assert mine and engine.mixed_ticks[width] == len(mine)
        assert capture.counters[
            f"serve_mixed_ticks_total{{width={width}}}"] == len(mine)
    stats = engine.stats_snapshot()
    assert stats["mixed_ticks"] == {
        str(w): c for w, c in engine.mixed_ticks.items()}
    # the tokens by width are the spans' own (ISSUE 57): no second tally
    assert "mixed_tokens" not in stats and not hasattr(engine, "mixed_tokens")
    assert stats["prefill_compiles"] == 2


def test_both_programs_are_lowered_by_the_first_tick_and_none_after(served):
    """The first tick held one token, so only the small width RAN it; the
    full width's program was lowered there all the same, and a run that
    then visits both widths lowers nothing. The engine holds exactly the
    two bucket programs, each compiled once."""
    engine, spans, _, (after_first, at_end) = served
    assert {f["width"] for f in spans.values()} == {SMALL, FULL}
    assert at_end == after_first
    assert jitted_programs(engine) == {"_mixed_fns": 2}
    assert list(engine._mixed_fns) == [SMALL, FULL]
    for fn in engine._mixed_fns.values():
        assert fn._cache_size() == 1


def test_warm_up_ticks_are_not_counted(models):
    engine = make_engine(models["dense"])
    engine.warmup_mode = True
    engine.submit([1], 2)
    engine.run_until_done()
    engine.warmup_mode = False
    assert list(engine._mixed_fns) == [SMALL, FULL]  # lowered all the same
    assert engine.mixed_ticks == {}


def test_the_program_holds_one_layer_function_however_deep_the_stack():
    """Layers built from one architecture are one function of (params,
    activations, cache), jitted on its own (``_run_layers``): the program
    is traced and lowered at one layer's cost, which is what lets an
    engine lower a program a token width at its first tick."""
    from scaling_tpu.serve.bench import build_toy_inference

    dots = []
    for layers in (2, 4):
        engine = make_engine(build_toy_inference(
            hidden=32, layers=layers, vocab=64, heads=4))
        state, shared, _, packed = random_tick(engine, TICKS["common"], seed=1)
        padded = np.zeros((SMALL,), np.int32)
        padded[:len(packed)] = packed
        text = engine._build_mixed_fn(SMALL).lower(
            engine.inf.params, state, pack(engine, shared, padded),
            engine._base_key, engine._prev).as_text()
        dots.append(text.count("stablehlo.dot_general"))
    assert dots[0] == dots[1] > 0


# ------------------- the rows and kernel tiles a tick's KV reads span (ISSUE 41)
@pytest.fixture(scope="module")
def tiled_ticks(models, tmp_path_factory):
    """A prompt of 70 tokens streaming in beside a short request, the paged
    kernel's tile shrunk to 32 tokens (2 blocks) so that rows grow from one
    tile to three: the ``serve.mixed`` span fields of the first four ticks."""
    from scaling_tpu.nn import paged_attention

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(paged_attention, "_TILE_TOKENS", 32)
        engine = make_engine(models["dense"])
        rng = np.random.default_rng(41)
        obs.start_capture(tmp_path_factory.mktemp("tiled") / "trace")
        try:
            engine.submit(list(rng.integers(1, 60, 70)), 3)
            engine.submit(list(rng.integers(1, 60, 5)), 6)
            for _ in range(4):
                engine.tick()
        finally:
            capture = obs.stop_capture()
    return [f for name, _, _, f in capture.spans if name == "serve.mixed"]


@pytest.mark.parametrize("tick,held", [
    (0, (32, 5)),   # both first chunks: one tile each
    (1, (64, 6)),   # the prompt's second chunk fills its second tile
    (2, (70, 7)),   # its last chunk reaches into a third
    (3, (71, 8)),   # both rows decode
])
def test_the_span_counts_the_rows_and_tiles_the_kernel_reads(
    tiled_ticks, tick, held
):
    fields = tiled_ticks[tick]
    assert (fields["kv_rows"], fields["kv_tiles"]) == (
        len(held), sum(-(-h // 32) for h in held))


# ---------- the sub-tiles of those tiles that a tick's rows hold (ISSUE 69)
@pytest.fixture(scope="module")
def sub_tiled_ticks(models, tmp_path_factory):
    """A prompt of 130 tokens streaming in beside a short request, the paged
    kernel's tile shrunk to 64 tokens (4 blocks) and its sub-tile to one block
    of 16, so that a row grows from one sub-tile to nine while the other keeps
    its one: the ``serve.mixed`` span fields of the first six ticks."""
    from scaling_tpu.nn import paged_attention

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(paged_attention, "_TILE_TOKENS", 64)
        patch.setattr(paged_attention, "_SUB_TOKENS", 16)
        engine = make_engine(models["dense"], max_blocks_per_seq=12,
                             num_blocks=SLOTS * 12 + 1)
        assert (engine._kv_tile, engine._kv_sub) == (64, 16)
        rng = np.random.default_rng(69)
        obs.start_capture(tmp_path_factory.mktemp("subtiled") / "trace")
        try:
            engine.submit(list(rng.integers(1, 60, 130)), 3)
            engine.submit(list(rng.integers(1, 60, 5)), 8)
            for _ in range(6):
                engine.tick()
        finally:
            capture = obs.stop_capture()
    return [f for name, _, _, f in capture.spans if name == "serve.mixed"]


@pytest.mark.parametrize("tick,held", [
    (0, (32, 5)),     # two sub-tiles of the long row's first tile, one of the short's
    (1, (64, 6)),     # its first tile whole
    (2, (96, 7)),     # half of its second
    (3, (128, 8)),    # two whole tiles: nothing of them is left out
    (4, (130, 9)),    # the ninth sub-tile: a quarter of a third tile
    (5, (131, 10)),   # both rows decode
])
def test_the_span_counts_the_sub_tiles_the_kernel_folds(
    sub_tiled_ticks, tick, held
):
    """``kv_subtiles`` over 4 x ``kv_tiles`` is the share of the tiles' fold
    that the kernel's sub-tiles leave."""
    fields = sub_tiled_ticks[tick]
    assert fields["kv_tiles"] == sum(-(-h // 64) for h in held)
    assert fields["kv_subtiles"] == sum(-(-h // 16) for h in held)
    assert fields["kv_subtiles"] <= 4 * fields["kv_tiles"]


# ------------- the tick says which branch its sampler takes (ISSUE 47)
@pytest.fixture(scope="module")
def sampler_ticks(models, tmp_path_factory):
    """A greedy request of 8 tokens beside two sampling ones of 3 and 2:
    rows with a temperature in the first three ticks, none afterwards."""
    engine = make_engine(models["dense"])
    obs.start_capture(tmp_path_factory.mktemp("sampler") / "trace")
    try:
        engine.submit([5, 6, 7], 8)
        engine.submit([1, 2], 3, temperature=0.8, top_k=4)
        engine.submit([3], 2, temperature=1.2, top_p=0.9)
        engine.run_until_done()
    finally:
        capture = obs.stop_capture()
    return engine, capture, [
        f for name, _, _, f in capture.spans if name == "serve.mixed"]


def test_the_span_counts_the_rows_that_sample(sampler_ticks):
    """``sampled_rows`` = the slots whose request has a temperature > 0;
    a finished request's slot stops counting with the tick after."""
    _, _, spans = sampler_ticks
    assert [f["sampled_rows"] for f in spans] == [2, 2, 1, 0, 0, 0, 0, 0]


def test_ticks_are_counted_by_the_sampler_s_branch(sampler_ticks):
    engine, capture, spans = sampler_ticks
    assert capture.counters[
        "serve_sampler_ticks_total{path=sampled}"] == 3
    assert capture.counters[
        "serve_sampler_ticks_total{path=greedy}"] == 5
    assert engine.sampled_ticks == 3
    assert engine.stats_snapshot()["sampled_tick_share"] == 3 / len(spans)


def test_a_greedy_engine_never_counts_a_sampled_tick(served):
    engine, _, capture, _ = served
    spans = [f for name, _, _, f in capture.spans if name == "serve.mixed"]
    assert {f["sampled_rows"] for f in spans} == {0}
    assert capture.counters[
        "serve_sampler_ticks_total{path=greedy}"] == len(spans)
    assert "serve_sampler_ticks_total{path=sampled}" not in capture.counters
    assert engine.stats_snapshot()["sampled_tick_share"] == 0.0


def test_warm_up_and_untraced_ticks_of_a_sampling_request(models):
    """Warm-up ticks write no field and move no counter, sampling or not;
    with no capture running a counted tick's row holds the field all the
    same, and no trace list when no request is traced."""
    def counted():
        return {k: v for k, v in obs.get_registry().snapshot()[
            "counters"].items() if k.startswith("serve_sampler_ticks_total")}

    engine = make_engine(models["dense"])
    before, mark = counted(), obs.recorded_spans()[-1].start_ns + 1
    engine.warmup_mode = True
    engine.submit([1, 2], 3, temperature=0.8)
    engine.run_until_done()
    engine.warmup_mode = False
    assert engine.sampled_ticks == 0 and counted() == before
    assert engine.stats_snapshot()["sampled_tick_share"] is None
    engine.submit([1, 2], 3, temperature=0.8)
    engine.run_until_done()
    rows = obs.recorded_spans(since_ns=mark, name="serve.mixed")
    assert [r.fields["sampled_rows"] for r in rows] == [1, 1, 1]
    assert all("traces" not in r.fields for r in rows)
    assert engine.stats_snapshot()["sampled_tick_share"] == 1.0
    moved = {k: v - before.get(k, 0) for k, v in counted().items()
             if v != before.get(k, 0)}
    assert moved == {"serve_sampler_ticks_total{path=sampled}": 3}

