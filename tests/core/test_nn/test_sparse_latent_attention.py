"""``nn/sparse_latent_attention.py``: the index scores against loops; the
exact choice with a context above and below ``index_topk`` and under ties; the
mixer's cached form (a line of two leaves in a paged pool: latent + rotary
key, index key; the rows walked one by one, their tiles streamed under each query's threshold) against its uncached
form (expanded heads under a mask of ``top_k``'s choice), row-major chunks and a
token-major tick of chunk rows and decode rows; the stream that serves against
the gather-everything form."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from scaling_tpu.nn.attention import PagedKVCacheView, packed_token_map
from scaling_tpu.nn.rotary import RopeScalingConfig, RotaryConfig
from scaling_tpu.nn.sparse_latent_attention import (
    SINGLE_ROWS, SparseLatentSelfAttention, choose_lines, index_scores, index_tile_tokens,
    threshold_choice,
)

from .one_program import served, uncached

HIDDEN, HEADS, TOPK, BLOCK = 64, 4, 8, 4
MAX_BLOCKS = 16               # a row's window: 64 slots


LATENT = dict(
    hidden_size=HIDDEN, num_attention_heads=HEADS, q_lora_rank=32,
    kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8,
    rotary_config=RotaryConfig(
        dimensions=8, base=10000, max_seq_length=64,
        scaling=RopeScalingConfig(
            type="yarn", factor=4, original_max_position_embeddings=16,
            beta_fast=32, beta_slow=1, mscale=1, mscale_all_dim=1)))


def mixer_of(topk=TOPK):
    return SparseLatentSelfAttention(
        index_n_heads=3, index_head_dim=24, index_topk=topk, **LATENT)


@pytest.fixture(scope="module")
def mixer():
    return mixer_of()


@pytest.fixture(scope="module")
def params(mixer):
    params = mixer.init(jax.random.PRNGKey(0))
    params["index_k_norm"]["bias"] = 0.3 * jax.random.normal(jax.random.PRNGKey(1), (24,))
    return params


def pools(rows, mixer):
    """The two leaves: [c_kv (16), k_r (8), zeros] in 16 + 128 lanes, and the
    index key (24)."""
    blocks = rows * MAX_BLOCKS + 1
    return (jnp.zeros((blocks, BLOCK, 16 + mixer.rope_line)),
            jnp.zeros((blocks, BLOCK, 24)))


def tables(rows):
    return 1 + jnp.arange(rows * MAX_BLOCKS, dtype=jnp.int32).reshape(rows, MAX_BLOCKS)


def test_index_scores_are_the_sum_over_heads_of_weighted_relus():
    rng = np.random.default_rng(0)
    q, k, w = rng.normal(size=(5, 3, 6)), rng.normal(size=(7, 6)), rng.normal(size=(5, 3))
    want = np.zeros((5, 7))
    for t in range(5):
        for s in range(7):
            want[t, s] = sum(w[t, j] * max(0.0, float(q[t, j] @ k[s])) for j in range(3))
    got = index_scores(jnp.asarray(q, jnp.float32), jnp.asarray(k, jnp.float32),
                       jnp.asarray(w, jnp.float32))
    np.testing.assert_allclose(got, want, atol=1e-5)
    assert (want < 0).any()   # negative head weights: the scores have both signs


@pytest.mark.parametrize("seen", [3, 8, 9, 40])
def test_the_choice_is_exact_above_and_below_index_topk(seen):
    """A query that sees ``seen`` lines keeps its min(8, seen) largest, none
    of them invisible, whatever the window holds past what it sees."""
    rng = np.random.default_rng(seen)
    scores = jnp.asarray(rng.normal(size=(2, 64)), jnp.float32)
    visible = jnp.arange(64)[None, :] < jnp.asarray([[seen], [max(seen - 2, 1)]])
    idx, held = choose_lines(scores, visible, TOPK)
    assert idx.shape == held.shape == (2, TOPK)
    for row, n in enumerate((seen, max(seen - 2, 1))):
        got = np.asarray(idx[row])[np.asarray(held[row])]
        want = np.argsort(-np.asarray(scores[row, :n]), kind="stable")[:TOPK]
        assert sorted(got.tolist()) == sorted(want.tolist())
        assert int(held[row].sum()) == min(TOPK, n)
    # the threshold's set (bisection, no sort) is top_k's
    mask = np.asarray(threshold_choice(scores, visible, TOPK))
    for row in range(2):
        assert np.flatnonzero(mask[row]).tolist() == sorted(
            np.asarray(idx[row])[np.asarray(held[row])].tolist())


def test_equal_scores_keep_the_lower_positions():
    scores = jnp.asarray([[2.0, 7.0, 7.0, 7.0, 1.0, 7.0, 7.0, 7.0, 7.0, 7.0]])
    idx, held = choose_lines(scores, jnp.ones((1, 10), bool), 4)
    assert sorted(np.asarray(idx[0]).tolist()) == [1, 2, 3, 5] and bool(held.all())
    flat, held = choose_lines(jnp.zeros((1, 10)), jnp.arange(10)[None] < 6, 4)
    assert sorted(np.asarray(flat[0]).tolist()) == [0, 1, 2, 3]
    assert np.flatnonzero(threshold_choice(scores, jnp.ones((1, 10), bool), 4)[0]).tolist() \
        == [1, 2, 3, 5]
    assert np.flatnonzero(threshold_choice(
        jnp.zeros((1, 10)), jnp.arange(10)[None] < 6, 4)[0]).tolist() == [0, 1, 2, 3]
    # scores of both signs, the extremes of float32, a query that sees nothing
    wild = jnp.asarray([[-3e38, 3e38, -0.0, 0.0, 1e-45, -1e-45, 5.0, -5.0]])
    assert np.flatnonzero(threshold_choice(wild, jnp.ones((1, 8), bool), 3)[0]).tolist() \
        == [1, 4, 6]
    assert np.flatnonzero(threshold_choice(wild, jnp.ones((1, 8), bool), 5)[0]).tolist() \
        == [1, 2, 3, 4, 6]
    assert not threshold_choice(wild, jnp.zeros((1, 8), bool), 3).any()


def chunked(mixer, params, x, sizes, paged_kernel):
    """One sequence through a pool of its own, ``sizes`` positions a call,
    row-major batches of one row."""
    pool_c, pool_i = pools(1, mixer)
    step = served(mixer, paged_kernel)
    out, done = [], 0
    for n in sizes:
        view = PagedKVCacheView(
            pool_k=pool_c, pool_v=pool_i, block_table=tables(1),
            context_len=jnp.asarray([done], jnp.int32),
            new_len=jnp.asarray([n], jnp.int32))
        y, view, _ = step(params, x[:, done:done + n],
                          done + jnp.arange(n, dtype=jnp.int32)[None], view)
        pool_c, pool_i = view.pool_k, view.pool_v
        out.append(y)
        done += n
    return jnp.concatenate(out, axis=1), (pool_c, pool_i)


@pytest.mark.parametrize("paged_kernel", ["pallas", "xla"])
def test_the_cached_form_is_the_uncached_form(mixer, params, paged_kernel):
    """Chunks of 7 then single tokens over the pool, the chosen lines gathered
    (and the mask-everything form) == the expanded heads under the mask, at a
    context that passes ``index_topk`` 8 inside the second chunk."""
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 40, HIDDEN))
    want = uncached(mixer, params, x)
    got, (pool_c, pool_i) = chunked(
        mixer, params, x, [7] * 5 + [1] * 5, paged_kernel)
    np.testing.assert_allclose(got, want, atol=2e-5)
    # the line's two leaves: 40 slots written; latent, rotary key, then zeros
    assert np.abs(np.asarray(pool_i[1:11])).min(axis=-1).max() > 0
    assert not np.asarray(pool_i[11:]).any() and not np.asarray(pool_c[11:]).any()
    assert np.abs(np.asarray(pool_c[1:11, :, :24])).min(axis=-1).max() > 0
    assert not np.asarray(pool_c[..., 24:]).any()


def test_a_choice_of_everything_is_dense_latent_attention(params):
    """``index_topk`` past the context: every visible line is chosen, and the
    layer is the parent's dense latent attention on the same weights."""
    from scaling_tpu.nn.latent_attention import LatentSelfAttention

    x = jax.random.normal(jax.random.PRNGKey(3), (1, 24, HIDDEN))
    dense = LatentSelfAttention(**LATENT)
    want = uncached(dense, params, x)
    np.testing.assert_allclose(uncached(mixer_of(64), params, x), want, atol=1e-5)
    got, _ = chunked(mixer_of(64), params, x, [8, 8, 8], "pallas")
    np.testing.assert_allclose(got, want, atol=2e-5)
    assert float(jnp.abs(uncached(mixer_of(TOPK), params, x) - want).max()) > 1e-3


@pytest.mark.parametrize("contexts, news, idle, shape", [
    ([30, 12, 0, 0], [1, 6, 0, 3], 2, (2, 6)),
    # more rows of one token than a pass takes (SINGLE_ROWS), and not a whole
    # number of passes: 6 of 8 rows, a chunk row and an idle row among them
    ([30, 9, 17, 12, 0, 33, 21, 5], [1, 1, 1, 6, 0, 1, 1, 1], 4, (2, 6)),
], ids=["one-decode-row", "six-decode-rows"])
def test_a_token_major_tick_of_chunk_rows_and_decode_rows(
        mixer, params, contexts, news, idle, shape):
    """Rows in one packed batch, the first case: a decode row at context 30, a
    chunk row of 6 at context 12 (its queries see 13-18 lines: all past
    index_topk), an idle row, a chunk row of 3 at context 0 (dense), padding
    after them; every row's output is its own sequence's uncached output at
    those positions, and only its own lines were written."""
    rows, width = len(news), 6
    assert sum(n == 1 for n in news) in (1, SINGLE_ROWS + 2)
    ctx_len = jnp.asarray(contexts, jnp.int32)
    new_len = jnp.asarray(news, jnp.int32)
    seqs = [jax.random.normal(jax.random.PRNGKey(10 + r), (1, 40, HIDDEN))
            for r in range(rows)]
    pool_c, pool_i = pools(rows, mixer)
    table = tables(rows)
    # each row's context, written by a row-major call of its own: the whole
    # sequence's places, of which the row owns its context's (one call shape)
    write = served(mixer, "xla")
    for r in range(rows):
        if not int(ctx_len[r]):
            continue
        view = PagedKVCacheView(
            pool_k=pool_c, pool_v=pool_i, block_table=table[r:r + 1],
            context_len=jnp.zeros((1,), jnp.int32), new_len=ctx_len[r:r + 1])
        _, view, _ = write(params, seqs[r], jnp.arange(40, dtype=jnp.int32)[None], view)
        pool_c, pool_i = view.pool_k, view.pool_v
    token_map = packed_token_map(new_len, shape, width)
    row, offset = np.asarray(token_map.row).reshape(-1), np.asarray(token_map.offset).reshape(-1)
    real = offset < np.asarray(new_len)[row]
    x = jnp.stack([seqs[r][0, int(ctx_len[r]) + o] if ok else jnp.zeros((HIDDEN,))
                   for r, o, ok in zip(row, offset, real)]).reshape(*shape, HIDDEN)
    pos = jnp.asarray(np.where(real, np.asarray(ctx_len)[row] + offset, 0)).reshape(shape)
    outs = {}
    for kernel in ("pallas", "xla"):
        view = PagedKVCacheView(
            pool_k=pool_c, pool_v=pool_i, block_table=table,
            context_len=ctx_len, new_len=new_len, token_map=token_map)
        y, new, _ = served(mixer, kernel)(params, x, pos, view)
        outs[kernel] = np.asarray(y).reshape(-1, HIDDEN)
    for r in range(rows):
        n, c = int(new_len[r]), int(ctx_len[r])
        if not n:
            continue
        # (causal: a position's output is what it is whatever follows it)
        want = np.asarray(uncached(mixer, params, seqs[r])[0, c:c + n])
        for kernel, got in outs.items():
            np.testing.assert_allclose(got[(row == r) & real], want, atol=3e-5,
                                       err_msg=f"row {r} {kernel}")
    # the idle row's blocks stay untouched; padding went to the trash block
    idle = np.asarray(table[idle])
    assert not np.asarray(new.pool_v)[idle].any() and not np.asarray(new.pool_k)[idle].any()


def test_the_row_walk_pays_for_real_shapes(mixer, params):
    """The rows of one token are taken a few a pass and a chunk row is walked
    at its chunk: the lowered program holds a rolled loop over the rows, a branch a
    window, loops over a row's tiles, and no sort: the choice is a threshold."""
    view = PagedKVCacheView(
        pool_k=pools(4, mixer)[0], pool_v=pools(4, mixer)[1], block_table=tables(4),
        context_len=jnp.zeros((4,), jnp.int32))
    text = jax.jit(lambda *a: mixer._attend_rows(*a, 6, True)).lower(
        jnp.zeros((12, 3, 24)), jnp.zeros((12, 3)), jnp.zeros((12, HEADS, 16 + 128)),
        view, jnp.zeros((4,), jnp.int32), jnp.zeros((4,), jnp.int32),
        jnp.zeros((4,), jnp.int32)).as_text()
    assert "stablehlo.while" in text and "stablehlo.case" in text
    assert "stablehlo.sort" not in text and "top_k" not in text
    assert index_tile_tokens(BLOCK, MAX_BLOCKS) == 64
    assert index_tile_tokens(16, 2048) == 2048 and index_tile_tokens(16, 64) == 1024
