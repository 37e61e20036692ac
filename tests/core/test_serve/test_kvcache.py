"""Paged KV pool mechanics (ISSUE 9): flat-slot addressing, prompt
scatter + block gather round-trips, int8 quantization accuracy — all on
hand-built pools, no model."""

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
from scaling_tpu.serve.kvcache import write_prompt_kv


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


def _empty_view(num_blocks=6, block_size=2, n_kv=2, h=4, quantized=False):
    pool = jnp.zeros((num_blocks, block_size, n_kv, h), jnp.float32)
    scale = (
        jnp.zeros((num_blocks, block_size, n_kv), jnp.float32)
        if quantized else None
    )
    if quantized:
        pool = pool.astype(jnp.int8)
    return PagedKVCacheView(
        pool_k=pool, pool_v=pool, block_table=jnp.zeros((1, 4), jnp.int32),
        context_len=jnp.zeros((1,), jnp.int32),
        scale_k=scale, scale_v=scale,
    )


@pytest.mark.parametrize("quantized", [False, True], ids=["native", "int8"])
def test_write_prompt_then_gather_roundtrips(quantized):
    rng = np.random.default_rng(1)
    block_size, prompt_len, bucket = 2, 5, 8
    k = jnp.asarray(rng.normal(size=(1, bucket, 2, 4)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(1, bucket, 2, 4)).astype(np.float32))
    view = _empty_view(quantized=quantized)
    block_row = jnp.asarray([3, 1, 4, 0], jnp.int32)  # scattered on purpose
    new = write_prompt_kv(view, k, v, block_row, jnp.int32(prompt_len),
                          block_size)
    # gather the row back through the block table: logical order restored
    gk = new.pool_k[block_row].reshape(8, 2, 4)
    if quantized:
        gs = new.scale_k[block_row].reshape(8, 2)
        gk = kv_dequantize_int8(gk, gs, jnp.float32)
    got = np.asarray(gk)[:prompt_len]
    want = np.asarray(k)[0, :prompt_len]
    tol = 0.02 if quantized else 0.0
    assert np.abs(got - want).max() <= tol


def test_prompt_padding_lands_in_trash_not_blocks():
    rng = np.random.default_rng(2)
    k = jnp.asarray(rng.normal(size=(1, 8, 2, 4)).astype(np.float32))
    view = _empty_view()
    block_row = jnp.asarray([3, 1, 0, 0], jnp.int32)
    new = write_prompt_kv(view, k, k, block_row, jnp.int32(3), block_size=2)
    pool = np.asarray(new.pool_k)
    # real blocks 3 and 1 hold tokens 0..2; block 4 untouched (token 3 is pad)
    assert np.allclose(pool[3], np.asarray(k)[0, 0:2])
    assert np.allclose(pool[1, 0], np.asarray(k)[0, 2])
    assert np.allclose(pool[1, 1], 0.0)  # slot for token 3 never written
    assert np.allclose(pool[4], 0.0)
    # pads went somewhere in trash block 0 (content irrelevant, only that
    # no REAL block got them)
    assert not np.allclose(pool[0], 0.0)


# --- the donated pool state leaves each program as it entered (ISSUE 31) ---

SLOTS, MAX_BLOCKS, CHUNK = 2, 8, 4
PROGRAMS = [("prefill", 0), ("chunk", 0), ("decode", 0), ("mixed", 0),
            ("mixed", 2)]


@pytest.fixture(scope="module")
def toy_inference():
    from scaling_tpu.serve.bench import build_toy_inference

    return build_toy_inference(hidden=32, layers=3, vocab=64, heads=4)


def _program_and_args(toy_inference, program, kv_dtype, spec_k):
    """One of the engine's four programs as the plain function under its
    ``jax.jit``, with toy arguments in its signature."""
    from scaling_tpu.serve.engine import EngineConfig, ServeEngine

    engine = ServeEngine(toy_inference, EngineConfig(
        num_slots=SLOTS, block_size=4, num_blocks=2 * MAX_BLOCKS + 1,
        max_blocks_per_seq=MAX_BLOCKS, token_budget=64, prefill_chunk=CHUNK,
        kv_dtype=kv_dtype, spec_k=spec_k,
    ))
    width = engine.config.mixed_width

    def z(*shape, dt=np.int32):
        return np.zeros(shape, dt)

    def sampler(n):
        return (z(n, dt=np.float32), z(n, dt=np.float32), z(n), z(n), z(n))

    built, operands = {
        "prefill": (lambda: engine._build_prefill_fn(8),
                    (z(1, 8), z(MAX_BLOCKS), np.int32(5), *sampler(1))),
        "chunk": (lambda: engine._build_chunk_fn(CHUNK),
                  (z(1, CHUNK), z(MAX_BLOCKS), z(1), np.ones(1, np.int32),
                   *sampler(1))),
        "decode": (engine._build_decode_fn,
                   (z(SLOTS, MAX_BLOCKS), z(SLOTS), z(SLOTS),
                    *sampler(SLOTS))),
        "mixed": (lambda: engine._build_mixed_fn(width),
                  (z(SLOTS, MAX_BLOCKS), z(SLOTS), z(SLOTS, width),
                   np.ones(SLOTS, np.int32), *sampler(SLOTS))),
    }[program]
    args = (toy_inference.params, engine._pool_state(), *operands,
            engine._base_key)
    return engine, built().__wrapped__, args


def _main_aliases(lowered_text):
    """{argument number: its ``tf.aliasing_output``} off ``main``'s
    signature in a lowered module's text."""
    signature = lowered_text.split("@main(", 1)[1].split(") -> ", 1)[0]
    aliases = {}
    for arg in signature.split("%arg")[1:]:
        m = re.search(r"tf\.aliasing_output = (\d+)", arg)
        if m:
            aliases[int(arg.split(":", 1)[0])] = int(m.group(1))
    return aliases


@pytest.mark.parametrize("kv_dtype", ["native", "int8"])
@pytest.mark.parametrize(
    "program,spec_k", PROGRAMS,
    ids=[f"{p}-spec{k}" if k else p for p, k in PROGRAMS],
)
def test_donated_pool_aliases_the_output_computed_from_it(
        toy_inference, program, spec_k, kv_dtype):
    """JAX pairs a donated buffer with an output of its shape and dtype
    in flattened order, so ``pool_v[3]`` is updated in place only if the
    program's lowered ``main`` says its argument aliases the output leaf
    at ``pool_v[3]``'s place in the returned state. Lowered with donation
    forced (the CPU engine never donates; lowering alone warns of
    nothing). Returning the per-layer views instead (k0, v0, table, ctx,
    k1, v1, ...), as every program did before ISSUE 31, fails this: three
    layers' six pools were paired with outputs 1, 2, 4, 5, 7, 8 where
    1, 2, 3, 4, 5, 6 compute from them, and XLA copied every pool but
    the first on every call."""
    _, fn, args = _program_and_args(toy_inference, program, kv_dtype, spec_k)
    text = jax.jit(fn, donate_argnums=(1,), keep_unused=True).lower(
        *args
    ).as_text()
    first = len(jax.tree_util.tree_leaves(args[0]))  # params come first
    donated = jax.tree_util.tree_leaves(args[1])
    assert len(donated) == (12 if kv_dtype == "int8" else 6)
    # outputs flatten as (tokens, *state): state leaf j is output 1 + j
    want = {first + j: 1 + j for j in range(len(donated))}
    assert _main_aliases(text) == want


@pytest.mark.parametrize("kv_dtype", ["native", "int8"])
@pytest.mark.parametrize("program", ["prefill", "chunk", "decode", "mixed"])
def test_programs_return_the_state_in_pool_state_structure(
        toy_inference, program, kv_dtype):
    engine, fn, args = _program_and_args(toy_inference, program, kv_dtype, 0)
    _, state = jax.eval_shape(fn, *args)
    structure = jax.tree_util.tree_structure
    assert structure(state) == structure(engine._pool_state())
    assert (state[2] is None) == (kv_dtype == "native")
    for got, held in zip(jax.tree_util.tree_leaves(state),
                         jax.tree_util.tree_leaves(engine._pool_state())):
        assert (got.shape, got.dtype) == (held.shape, held.dtype)
