"""Multi-head latent attention (``nn/latent_attention.py``) and its paged
kernel (``nn/latent_paged_attention.py``): the absorbed form over the pool is
the expanded form without a cache; the kernel interpreted is the gather form
for decode rows, chunk rows, ragged ``new_len``, a row that crosses a tile, an
empty row, token-major and row-major batches; what a token leaves behind."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from scaling_tpu.nn import latent_paged_attention as lpa
from scaling_tpu.nn.attention import PagedKVCacheView, packed_token_map
from scaling_tpu.nn.base_layer import ForwardContext
from scaling_tpu.nn.latent_attention import LatentSelfAttention
from scaling_tpu.nn.norm import LayerNormConfig
from scaling_tpu.nn.rotary import RopeScalingConfig, RotaryConfig

from .one_program import jitted

H, HEADS, Q_LORA, KV_LORA, NOPE, ROPE, V = 256, 4, 96, 64, 32, 16, 32
BLOCK, MAX_BLOCKS = 4, 40


def mixer(dtype=jnp.float32):
    return LatentSelfAttention(
        hidden_size=H, num_attention_heads=HEADS, q_lora_rank=Q_LORA,
        kv_lora_rank=KV_LORA, qk_nope_head_dim=NOPE, qk_rope_head_dim=ROPE,
        v_head_dim=V, dtype=dtype,
        layernorm_config=LayerNormConfig(layernorm_epsilon=1e-6),
        rotary_config=RotaryConfig(
            dimensions=ROPE, base=50000, max_seq_length=256,
            scaling=RopeScalingConfig(
                factor=8, original_max_position_embeddings=32, beta_fast=1,
                beta_slow=1, mscale=1, mscale_all_dim=1)))


def empty_view(rows, **kw):
    blocks = rows * MAX_BLOCKS + 1
    table = 1 + jnp.arange(rows * MAX_BLOCKS, dtype=jnp.int32).reshape(rows, MAX_BLOCKS)
    return PagedKVCacheView(
        pool_k=jnp.zeros((blocks, BLOCK, KV_LORA)),
        pool_v=jnp.zeros((blocks, BLOCK, lpa.rope_line_width(ROPE))),
        block_table=table, context_len=jnp.zeros((rows,), jnp.int32), **kw)


def test_the_scale_is_yarns_and_the_line_is_latent_plus_one_key():
    m = mixer()
    assert m.scaling_factor == pytest.approx(
        (NOPE + ROPE) ** -0.5 * (0.1 * np.log(8) + 1) ** 2)
    params = m.init(jax.random.PRNGKey(0))
    assert set(params) == set(m.PARTS)
    assert params["kv_a_proj"]["weight"].shape == (H, KV_LORA + ROPE)
    assert params["kv_b_proj"]["weight"].shape == (KV_LORA, HEADS * (NOPE + V))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 10, H))
    pos = jnp.broadcast_to(jnp.arange(10), (2, 10))
    ctx = ForwardContext()
    y, (c_kv, k_r) = m(params, x, ctx, position_ids=pos, return_kv=True)
    assert y.shape == (2, 10, H)
    # no head axis; the rotary key in a lane row, zeros after it
    assert c_kv.shape == (2, 10, KV_LORA) and k_r.shape == (2, 10, 128)
    assert not np.asarray(k_r[..., ROPE:]).any() and np.abs(np.asarray(k_r[..., :ROPE])).max() > 0


@pytest.mark.parametrize("paged_kernel", ["pallas", "xla"])
def test_absorbed_over_the_pool_is_expanded_without_a_cache(paged_kernel):
    """One layer: a prompt in three calls (a chunk of 12, a ragged chunk of 7
    of 12, then single tokens) through the pool in the absorbed form == the
    expanded form over the whole sequence."""
    m = mixer()
    params = m.init(jax.random.PRNGKey(0))
    s = 24
    x = jax.random.normal(jax.random.PRNGKey(1), (1, s, H))
    call = jitted(m, ForwardContext(paged_kernel=paged_kernel))
    want = call(params, x, jnp.arange(s)[None])
    view, done, got = empty_view(1), 0, []
    for real, width in ((12, 12), (7, 12), (1, 1), (1, 1), (1, 1), (1, 1), (1, 1)):
        chunk = jnp.zeros((1, width, H)).at[:, :real].set(x[:, done:done + real])
        pos = (done + jnp.arange(width))[None]
        y, view = call(params, chunk, pos, view._replace(
            context_len=jnp.asarray([done], jnp.int32),
            new_len=jnp.asarray([real], jnp.int32)))
        got.append(y[:, :real])
        done += real
    np.testing.assert_allclose(np.concatenate(got, axis=1), want, atol=2e-5)


def random_call(rng, ctx, new, width, tokens):
    rows = len(ctx)
    ctx, new = np.asarray(ctx, np.int32), np.asarray(new, np.int32)
    blocks = rows * MAX_BLOCKS + 1
    pool_c = jnp.asarray(rng.normal(size=(blocks, BLOCK, KV_LORA)), jnp.float32)
    pool_r = jnp.zeros((blocks, BLOCK, 128)).at[..., :ROPE].set(
        rng.normal(size=(blocks, BLOCK, ROPE)))
    table = 1 + np.arange(rows * MAX_BLOCKS, dtype=np.int32).reshape(rows, MAX_BLOCKS)
    table[new + ctx == 0] = 0        # an empty row: all trash
    q_lat = jnp.asarray(rng.normal(size=(tokens, HEADS, KV_LORA)), jnp.float32)
    q_rope = jnp.asarray(rng.normal(size=(tokens, HEADS, ROPE)), jnp.float32)
    return q_lat, q_rope, pool_c, pool_r, jnp.asarray(table), jnp.asarray(ctx), jnp.asarray(new)


@pytest.mark.parametrize("ctx,new", [
    ([0, 37, 150, 0, 155], [12, 1, 9, 0, 5]),       # chunks, a decode row, an empty row
    ([63, 64, 65, 127], [1, 1, 1, 1]),              # decode rows at a tile's edge
    ([60, 0, 100, 3], [12, 12, 12, 12]),            # full chunks crossing tiles
    ([0, 0, 0, 159], [0, 0, 0, 1]),                 # only the last row is active
    ([5, 0, 9, 0], [3, 0, 10, 0]),                  # ragged, empty rows between
])
def test_the_kernel_interpreted_is_the_gather_form(monkeypatch, ctx, new):
    """Token-major queries as the engine packs them, tiles of 64 tokens (a
    row of 160 slots crosses two tile edges)."""
    monkeypatch.setattr(lpa, "TILE_TOKENS", 64)
    rng = np.random.default_rng(sum(ctx) + sum(new))
    width, tokens = 12, 64
    q_lat, q_rope, pool_c, pool_r, table, ctx_len, new_len = random_call(
        rng, ctx, new, width, tokens)
    token_map = packed_token_map(new_len, (4, 16), width)
    starts = token_map.row_tokens[:, 0]
    got = lpa.latent_paged_attention(
        q_lat, q_rope, pool_c, pool_r, table, ctx_len + new_len, ctx_len, starts,
        width=width, sm_scale=0.11)
    m = mixer()
    view = PagedKVCacheView(pool_k=pool_c, pool_v=pool_r, block_table=table,
                            context_len=ctx_len, new_len=new_len, token_map=token_map)
    row, offset, real = view.token_rows((4, 16))
    m.scaling_factor = 0.11
    want = m._attend_gathered(q_lat, q_rope, view, row.reshape(-1), offset.reshape(-1),
                              ctx_len, ctx_len + new_len)
    real = np.asarray(real).reshape(-1)
    assert real.sum() == sum(new)
    np.testing.assert_allclose(np.asarray(got)[real], np.asarray(want)[real], atol=2e-5)


def test_a_row_major_batch_and_a_token_major_one_attend_alike():
    """The mixer under both layouts of the same tick: rows of (5, 1, 8) new
    tokens as a (3, 8) row-major batch and packed into (2, 8) token-major."""
    m = mixer()
    params = m.init(jax.random.PRNGKey(0))
    new = np.array([5, 1, 8], np.int32)
    ctx_len = jnp.asarray([3, 20, 0], jnp.int32)
    rng = np.random.default_rng(0)
    rows_x = jnp.asarray(rng.normal(size=(3, 8, H)), jnp.float32)
    view = empty_view(3)
    # some context for the rows to attend over
    view = view._replace(
        pool_k=jnp.asarray(rng.normal(size=view.pool_k.shape), jnp.float32),
        pool_v=view.pool_v.at[..., :ROPE].set(rng.normal(size=(*view.pool_v.shape[:2], ROPE))),
        context_len=ctx_len, new_len=jnp.asarray(new))
    call = jitted(m, ForwardContext())
    pos = ctx_len[:, None] + jnp.arange(8)[None]
    y_rows, _ = call(params, rows_x, pos, view)
    packed = jnp.concatenate([rows_x[r, :n] for r, n in enumerate(new)]
                             + [jnp.zeros((2, H))]).reshape(2, 8, H)
    token_map = packed_token_map(jnp.asarray(new), (2, 8), 8)
    ppos = jnp.where(token_map.offset < jnp.asarray(new)[token_map.row],
                     ctx_len[token_map.row] + token_map.offset, 0)
    y_packed, _ = call(params, packed, ppos, view._replace(token_map=token_map))
    flat = y_packed.reshape(16, H)
    at = 0
    for r, n in enumerate(new):
        np.testing.assert_allclose(flat[at:at + n], y_rows[r, :n], atol=2e-5)
        at += n
    # what is no token comes back as W_O of zeros: finite, and nobody's
    assert np.isfinite(np.asarray(flat)).all()


def test_an_int8_view_and_a_dense_cache_are_refused_by_name():
    m = mixer()
    params = m.init(jax.random.PRNGKey(0))
    x = jnp.zeros((1, 4, H))
    pos = jnp.arange(4)[None]
    view = empty_view(1)._replace(scale_k=jnp.ones((41, 4, 1)), scale_v=jnp.ones((41, 4, 1)))
    with pytest.raises(ValueError, match="latent attention layer with an int8 pool"):
        m(params, x, ForwardContext(), position_ids=pos, kv_cache=view)
    with pytest.raises(ValueError, match="takes a PagedKVCacheView"):
        m(params, x, ForwardContext(), position_ids=pos,
          kv_cache=(jnp.zeros((1, 8, 64)), jnp.zeros((1, 8, 128))), cache_offset=0)
