"""``nn/sparse_attention.py``: the sparse grouped-query mixer's cached form
(the shared row walk over a line of THREE leaves, the masked Pallas kernel
interpreted) against its uncached form, at contexts above and below
``index_topk``; ties; the kernel against the mask-everything form; ONE choice a
token shared by every head and group; a token-major tick of chunk rows and
decode rows; and that the scores, the choice and the walk are the sparse latent
mixer's own (``nn/sparse_rows.py``), not a copy."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from scaling_tpu.nn import (
    paged_attention, sparse_attention, sparse_latent_attention, sparse_rows,
)
from scaling_tpu.nn.attention import (
    PagedKVCacheView, ParallelSelfAttention, packed_token_map,
)
from scaling_tpu.nn.base_layer import ForwardContext
from scaling_tpu.nn.masked_gqa_attention import masked_gqa_attention
from scaling_tpu.nn.norm import NormType
from scaling_tpu.nn.rotary import RotaryConfig
from scaling_tpu.nn.sparse_attention import SparseSelfAttention
from scaling_tpu.nn.sparse_rows import (
    SINGLE_ROWS, chosen_mask, index_scores, threshold_choice, tie_breaks_heard,
    walk_rows,
)

from .one_program import served, uncached

HIDDEN, HEADS, KV_HEADS, HEAD_DIM, TOPK, BLOCK = 64, 8, 2, 16, 8, 4
INDEX_HEADS, INDEX_DIM = 3, 12
MAX_BLOCKS = 16               # a row's window: 64 slots

GQA = dict(
    hidden_size=HIDDEN, num_attention_heads=HEADS, num_kv_heads=KV_HEADS,
    head_dim=HEAD_DIM, qkv_in_one=False, bias=False, key_query_norm=True,
    norm_type=NormType.RMS,
    rotary_config=RotaryConfig(dimensions=HEAD_DIM, base=10000, max_seq_length=64))


def mixer_of(topk=TOPK):
    return SparseSelfAttention(index_n_heads=INDEX_HEADS, index_head_dim=INDEX_DIM,
                               index_topk=topk, **GQA)


@pytest.fixture(scope="module")
def mixer():
    return mixer_of()


@pytest.fixture(scope="module")
def params(mixer):
    params = mixer.init(jax.random.PRNGKey(0))
    params["index_k_norm"]["bias"] = 0.3 * jax.random.normal(
        jax.random.PRNGKey(1), (INDEX_DIM,))
    for name in ("norm_query", "norm_key"):
        params[name]["weight"] = 1 + 0.2 * jax.random.normal(
            jax.random.PRNGKey(2), (HEAD_DIM,))
    return params


def pools(rows):
    """The three leaves of a line: K and V (2 KV heads of 16) and the index
    key (12), no head axis."""
    blocks = rows * MAX_BLOCKS + 1
    return (jnp.zeros((blocks, BLOCK, KV_HEADS, HEAD_DIM)),
            jnp.zeros((blocks, BLOCK, KV_HEADS, HEAD_DIM)),
            jnp.zeros((blocks, BLOCK, INDEX_DIM)))


def tables(rows):
    return 1 + jnp.arange(rows * MAX_BLOCKS, dtype=jnp.int32).reshape(rows, MAX_BLOCKS)


def view_of(leaves, table, ctx_len, new_len, token_map=None):
    pool_k, pool_v, pool_i = leaves
    return PagedKVCacheView(
        pool_k=pool_k, pool_v=pool_v, pool_i=pool_i, block_table=table,
        context_len=jnp.asarray(ctx_len, jnp.int32),
        new_len=jnp.asarray(new_len, jnp.int32), token_map=token_map)


def leaves_of(view):
    return view.pool_k, view.pool_v, view.pool_i


def chunked(mixer, params, x, sizes, paged_kernel):
    """One sequence through a pool of its own, ``sizes`` positions a call,
    row-major batches of one row."""
    leaves = pools(1)
    step = served(mixer, paged_kernel)
    out, done = [], 0
    for n in sizes:
        y, view, _ = step(
            params, x[:, done:done + n],
            done + jnp.arange(n, dtype=jnp.int32)[None],
            view_of(leaves, tables(1), [done], [n]))
        leaves = leaves_of(view)
        out.append(y)
        done += n
    return jnp.concatenate(out, axis=1), leaves


def test_the_two_sparse_mixers_share_one_copy_of_scores_choice_and_walk():
    for name in ("index_scores", "choose_lines", "ordered_bits", "threshold_choice",
                 "_windows", "index_tile_tokens", "walk_rows"):
        shared = getattr(sparse_rows, name)
        assert getattr(sparse_latent_attention, name) is shared, name
    for name in ("index_scores", "threshold_choice", "walk_rows", "chosen_mask"):
        assert getattr(sparse_attention, name) is getattr(sparse_rows, name), name


@pytest.mark.parametrize("paged_kernel", ["pallas", "xla"])
def test_the_cached_form_is_the_uncached_form(mixer, params, paged_kernel):
    """Chunks of 7 then single tokens over the pool, the row's window streamed
    under the mask (and the mask-everything form) == the unfused attention
    under the mask, at a context that passes ``index_topk`` 8 inside the
    second chunk."""
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 40, HIDDEN))
    want = uncached(mixer, params, x)
    got, (pool_k, pool_v, pool_i) = chunked(
        mixer, params, x, [7] * 5 + [1] * 5, paged_kernel)
    np.testing.assert_allclose(got, want, atol=2e-5)
    # the line's three leaves: 40 slots written, nothing past them
    for pool in (pool_k, pool_v, pool_i):
        flat = np.asarray(pool).reshape(pool.shape[0], BLOCK, -1)
        assert np.abs(flat[1:11]).max(axis=-1).min() > 0
        assert not flat[11:].any()


def test_a_choice_of_everything_is_dense_grouped_query_attention(params):
    """``index_topk`` past the context: every visible line is chosen, and the
    layer is the parent's dense attention on the same weights."""
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 24, HIDDEN))
    dense = ParallelSelfAttention(**GQA)
    want = uncached(dense, params, x)
    np.testing.assert_allclose(uncached(mixer_of(64), params, x), want, atol=1e-5)
    got, _ = chunked(mixer_of(64), params, x, [12, 12], "pallas")
    np.testing.assert_allclose(got, want, atol=2e-5)
    assert float(jnp.abs(uncached(mixer_of(TOPK), params, x) - want).max()) > 1e-3


def test_equal_scores_keep_the_lower_positions_in_both_forms(mixer, params, monkeypatch):
    """An indexer whose scores are all equal chooses the FIRST lines a query
    sees, by ``top_k`` (uncached) and by the threshold (served) alike."""
    flat = lambda q_i, k_i, w: jnp.zeros((*q_i.shape[:-2], k_i.shape[-2]), jnp.float32)
    monkeypatch.setattr(sparse_attention, "index_scores", flat)
    monkeypatch.setattr(sparse_rows, "index_scores", flat)
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 30, HIDDEN))
    want = uncached(mixer, params, x)
    got, _ = chunked(mixer, params, x, [6] * 4 + [1] * 6, "pallas")
    np.testing.assert_allclose(got, want, atol=2e-5)
    visible = jnp.tril(jnp.ones((30, 30), bool))
    first = np.asarray(chosen_mask(jnp.zeros((30, 30)), visible, TOPK))
    assert all(np.flatnonzero(first[t]).tolist() == list(range(min(TOPK, t + 1)))
               for t in range(30))


# ---- where the choice fills ties by position -----------------------------

LINES = 32


def _distinct(seen):
    """Three queries over 32 lines, no two scores of a query equal; query
    ``q`` sees its first ``seen[q]`` lines."""
    rng = np.random.default_rng(11)
    scores = np.stack([rng.permutation(LINES) for _ in seen]).astype(np.float32)
    return scores - 7.5, np.arange(LINES)[None] < np.asarray(seen)[:, None]


def _tied_at_the_threshold(below):
    """Query 0's TOPK-th largest score shared by the line ranked above it and
    by the ``below`` ranked next below it, wherever they lie."""
    scores, visible = _distinct((LINES, 20, 9))
    ranked = np.argsort(-scores[0])
    scores[0, ranked[TOPK - 2:TOPK + below]] = scores[0, ranked[TOPK - 1]]
    return scores, visible


def _rounded():
    rng = np.random.default_rng(12)
    return (np.round(rng.normal(size=(3, LINES)) * 2) / 2).astype(np.float32), \
        np.arange(LINES)[None] < np.asarray([[LINES], [20], [9]])


CHOICES = {
    # name: (scores (3, 32), visible (3, 32)), whether the call fills ties
    "no ties": (lambda: _distinct((LINES, 20, 9)), False),
    "ties within room": (lambda: _tied_at_the_threshold(0), False),
    "ties over room": (lambda: _tied_at_the_threshold(2), True),
    "a query that sees fewer than topk": (
        lambda: (np.zeros((3, LINES), np.float32),
                 np.arange(LINES)[None] < np.asarray([[5], [3], [TOPK]])), False),
    "a query that sees nothing": (lambda: _distinct((LINES, 0, 9)), False),
    "rounded scores": (_rounded, True),
    "all-zero scores": (
        lambda: (np.zeros((3, LINES), np.float32),
                 np.arange(LINES)[None] < np.asarray([[LINES], [20], [9]])), True),
}


def _more_at_the_threshold_than_room(scores, visible):
    """By a sort, in numpy: does some query have more visible scores at or
    above its TOPK-th largest than TOPK?"""
    for row, sees in zip(scores, visible):
        v = row[sees]
        if len(v) > TOPK and (v >= np.sort(v)[-TOPK]).sum() > TOPK:
            return True
    return False


def _walked_chunk(new_len):
    """The walk over ONE chunk row of 6 places at context 20 of which
    ``new_len`` are its tokens; places 3-5 bring an index query of zeros, so
    all their scores tie, over 24-26 visible lines. The attention is the mask
    itself. Returns the masks (6, 64), top_k's masks, the count, what the
    choice's calls said."""
    rng = np.random.default_rng(13)
    width, ctx = 6, 20
    # (of one sign: no score is the 0.0 of an index query no key agrees with)
    index_pool = jnp.asarray(np.abs(rng.normal(
        size=(MAX_BLOCKS + 1, BLOCK, INDEX_DIM))), jnp.float32)
    q_i = np.abs(rng.normal(size=(width, INDEX_HEADS, INDEX_DIM))).astype(np.float32)
    q_i[3:] = 0.0
    w = jnp.asarray(rng.normal(size=(width, INDEX_HEADS)), jnp.float32)
    window = MAX_BLOCKS * BLOCK
    said = []

    def choice(scores, visible, k):
        with tie_breaks_heard() as heard:
            mask = threshold_choice(scores, visible, k)
        said.extend(heard)
        return mask

    out, ties = walk_rows(
        index_pool=index_pool, block_table=tables(1),
        ctx_len=jnp.asarray([ctx], jnp.int32), new_len=jnp.asarray([new_len], jnp.int32),
        starts=jnp.zeros((1,), jnp.int32), width=width, topk=TOPK,
        q_i=jnp.asarray(q_i), w=w, queries=jnp.zeros((width, window)),
        out=jnp.zeros((width, window)), choice=choice,
        attend_single=lambda tables, seen, q, chosen: chosen[:, 0].astype(
            jnp.float32),
        attend_chunk=lambda table, seen, q, chosen, tiles: chosen.astype(jnp.float32))
    scores = index_scores(jnp.asarray(q_i), index_pool[tables(1)[0]].reshape(
        window, INDEX_DIM), w)
    slots = np.arange(window)[None]
    visible = (slots < ctx + new_len) & (slots <= ctx + np.arange(width)[:, None])
    want = np.asarray(chosen_mask(scores, jnp.asarray(visible), TOPK))
    return np.asarray(out) > 0, want, int(ties), [bool(x) for x in said]


UNOWNED, OWNED = "an unowned place whose scores all tie", "that place owned"


@pytest.mark.parametrize("case, beside_a_query_that_fills", [
    *((case, beside) for case in CHOICES for beside in (False, True)),
    (UNOWNED, False), (OWNED, False),
], ids=lambda v: {False: "alone", True: "beside a query that fills"}.get(v, v))
def test_the_choice_is_top_ks_set_and_fills_ties_only_over_room(
        case, beside_a_query_that_fills):
    """``threshold_choice``'s mask is ``chosen_mask``'s set (``jax.lax.top_k``,
    a tie to the lower position) in every case, in the branch the case names
    (the fill by position only where a query has more VISIBLE scores at its
    threshold than room) and, beside a query that forces the fill on the
    whole call, in the other branch too. In the walk a place whose result is
    thrown away sends no call down the fill."""
    if case in (UNOWNED, OWNED):
        owned = case == OWNED
        with jax.disable_jit():
            got, want, ties, said = _walked_chunk(6 if owned else 3)
        kept = 6 if owned else 3
        assert (got[:kept] == want[:kept]).all() and not got[kept:].any()
        assert want[:kept].sum(axis=-1).tolist() == [TOPK] * kept
        assert ties == int(owned) and said == [owned]
        if owned:   # the first lines: a tie goes to the lower position
            assert np.flatnonzero(got[5]).tolist() == list(range(TOPK))
        return
    make, fills = CHOICES[case]
    scores, visible = make()
    assert _more_at_the_threshold_than_room(scores, visible) == fills
    if beside_a_query_that_fills:
        scores = np.concatenate([scores, np.zeros((1, LINES), np.float32)])
        visible = np.concatenate([visible, np.ones((1, LINES), bool)])
        fills = True
    with tie_breaks_heard() as heard:
        got = np.asarray(threshold_choice(
            jnp.asarray(scores), jnp.asarray(visible), TOPK))
    assert [bool(over) for over in heard] == [fills]
    want = np.asarray(chosen_mask(jnp.asarray(scores), jnp.asarray(visible), TOPK))
    assert (got == want).all()
    assert got.sum(axis=-1).tolist() == np.minimum(visible.sum(axis=-1), TOPK).tolist()
    assert not (got & ~visible).any()


def test_the_kernel_is_the_mask_everything_form():
    """``masked_gqa_attention`` (interpreted) against a plain masked softmax:
    positions that are no multiple of the kernel's block, a window of several
    tiles of which the last ones are past what the row sees, a position that
    chose nothing."""
    rng = np.random.default_rng(0)
    positions, window, seen = 11, 24, 17
    q = jnp.asarray(rng.normal(size=(positions, HEADS, HEAD_DIM)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(window, KV_HEADS, HEAD_DIM)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(window, KV_HEADS, HEAD_DIM)), jnp.float32)
    chosen = rng.random((positions, window)) < 0.4
    chosen[:, seen:] = False
    chosen[3] = False
    got = masked_gqa_attention(q, k, v, jnp.asarray(chosen), jnp.int32(seen),
                               sm_scale=0.25, interpret=True)
    group = HEADS // KV_HEADS
    s = jnp.einsum("pgjh,kgh->pgjk", q.reshape(positions, KV_HEADS, group, HEAD_DIM), k)
    s = jnp.where(jnp.asarray(chosen)[:, None, None, :], 0.25 * s, -jnp.inf)
    e = jnp.exp(s - jnp.where(jnp.isinf(s.max(-1, keepdims=True)), 0.0,
                              s.max(-1, keepdims=True)))
    want = jnp.einsum("pgjk,kgh->pgjh", e / jnp.maximum(e.sum(-1, keepdims=True), 1e-30), v)
    np.testing.assert_allclose(got, want.reshape(positions, HEADS, HEAD_DIM), atol=1e-5)
    assert not np.asarray(got[3]).any()


def test_one_choice_a_token_is_shared_by_every_head_and_group(mixer, params, monkeypatch):
    """The mask the served path attends under is ``(queries, lines)``: no head
    axis, so the 8 query heads of both KV heads read the same lines. A mixer
    whose heads of the SECOND group attended over other lines gives another
    output."""
    shapes = []
    choose = SparseSelfAttention._chosen

    def recording(self, scores, visible, k):
        mask = choose(self, scores, visible, k)
        shapes.append(mask.shape)
        return mask

    monkeypatch.setattr(SparseSelfAttention, "_chosen", recording)
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 14, HIDDEN))
    got, _ = chunked(mixer, params, x, [6] * 2 + [1] * 2, "pallas")   # (traced: shapes)
    assert shapes and all(len(shape) == 3 and shape[:2] in ((1, 6), (1, 1))
                          for shape in shapes)
    monkeypatch.undo()
    want = uncached(mixer, params, x)
    np.testing.assert_allclose(got, want, atol=2e-5)

    # a choice made a HEAD (each KV head's group by its own indexer head) is
    # another result
    real_scores = sparse_attention.index_scores
    calls = []

    def first_head_only(q_i, k_i, w):
        calls.append(1)
        return real_scores(q_i[..., :1, :], k_i, w[..., :1])

    monkeypatch.setattr(sparse_attention, "index_scores", first_head_only)
    other = uncached(mixer, params, x)
    assert calls and float(jnp.abs(other - want).max()) > 1e-3


@pytest.mark.parametrize("contexts, news, idle, shape", [
    ([30, 12, 0, 0], [1, 6, 0, 3], 2, (2, 6)),
    # more rows of one token than a pass takes (SINGLE_ROWS), and not a whole
    # number of passes: 6 of 8 rows, a chunk row and an idle row among them
    ([30, 9, 17, 12, 0, 33, 21, 5], [1, 1, 1, 6, 0, 1, 1, 1], 4, (2, 6)),
    # one pass: two decode rows that see fewer lines than ``index_topk`` (3 and
    # 7 of 8), two whose contexts end mid-block and mid-tile (38 and 30 lines
    # in blocks of 4 and the kernel's tiles of 8)
    ([2, 12, 0, 37, 29, 6], [1, 6, 0, 1, 1, 1], 2, (2, 6)),
], ids=["one-decode-row", "six-decode-rows", "short-and-mid-tile-decode-rows"])
def test_a_token_major_tick_of_chunk_rows_and_decode_rows(
        mixer, params, monkeypatch, contexts, news, idle, shape):
    """Rows in one packed batch: every row's output is its own sequence's
    uncached output at those positions, and only its own lines were written,
    in all three leaves. The one-token rows' kernel runs tiles of 8 slots, so
    that their contexts span several."""
    monkeypatch.setattr(paged_attention, "_TILE_TOKENS", 8)
    rows, width = len(news), 6
    assert sum(n == 1 for n in news) in (1, SINGLE_ROWS, SINGLE_ROWS + 2)
    ctx_len = jnp.asarray(contexts, jnp.int32)
    new_len = jnp.asarray(news, jnp.int32)
    seqs = [jax.random.normal(jax.random.PRNGKey(10 + r), (1, 40, HIDDEN))
            for r in range(rows)]
    leaves = pools(rows)
    table = tables(rows)
    # each row's context, written by a row-major call of its own: the whole
    # sequence's places, of which the row owns its context's (one call shape)
    write = served(mixer, "xla")
    for r in range(rows):
        if int(ctx_len[r]):
            _, view, _ = write(params, seqs[r], jnp.arange(40, dtype=jnp.int32)[None],
                               view_of(leaves, table[r:r + 1], [0], ctx_len[r:r + 1]))
            leaves = leaves_of(view)
    token_map = packed_token_map(new_len, shape, width)
    row, offset = np.asarray(token_map.row).reshape(-1), np.asarray(token_map.offset).reshape(-1)
    real = offset < np.asarray(new_len)[row]
    x = jnp.stack([seqs[r][0, int(ctx_len[r]) + o] if ok else jnp.zeros((HIDDEN,))
                   for r, o, ok in zip(row, offset, real)]).reshape(*shape, HIDDEN)
    pos = jnp.asarray(np.where(real, np.asarray(ctx_len)[row] + offset, 0)).reshape(shape)
    outs = {}
    for kernel in ("pallas", "xla"):
        y, new, _ = served(mixer, kernel)(
            params, x, pos, view_of(leaves, table, ctx_len, new_len, token_map))
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
    assert not any(np.asarray(pool)[idle].any() for pool in leaves_of(new))


def test_the_row_walk_pays_for_real_shapes(mixer):
    """The lowered walk holds a rolled loop over the rows, a branch a window,
    loops over a row's tiles, and no sort: the choice is a threshold. The
    one-token rows attend through the paged kernel, called ONCE (not once a
    window), and no pass of four gathers a ``(rows, tile, n_kv, h)`` tile of
    K or V through the table."""
    view = view_of(pools(4), tables(4), [0] * 4, [0] * 4)
    text = jax.jit(lambda *a: mixer._attend_rows(*a, 6, True)).lower(
        jnp.zeros((12, INDEX_HEADS, INDEX_DIM)), jnp.zeros((12, INDEX_HEADS)),
        jnp.zeros((12, HEADS, HEAD_DIM)), view, jnp.zeros((4,), jnp.int32),
        jnp.zeros((4,), jnp.int32), jnp.zeros((4,), jnp.int32)).as_text()
    assert "stablehlo.while" in text and "stablehlo.case" in text
    assert "stablehlo.sort" not in text and "top_k" not in text
    assert text.count("call @_paged_call(") == 1
    # a pass's tile of K or V as XLA would gather it: 4 rows x 16 blocks of
    # (4, 2, 16); of the index keys (4, 12), which ARE gathered so
    assert "tensor<4x16x4x12xf32>" in text
    assert "tensor<4x16x4x2x16xf32>" not in text


def test_what_the_mixer_does_not_build_is_refused_by_name(mixer, params):
    x = jnp.zeros((1, 4, HIDDEN))
    pos = jnp.arange(4, dtype=jnp.int32)[None]
    dense_cache = (jnp.zeros((1, 8, KV_HEADS, HEAD_DIM)),) * 2
    with pytest.raises(ValueError, match="PagedKVCacheView.*not a dense cache"):
        mixer(params, x, ForwardContext(), position_ids=pos, kv_cache=dense_cache,
              cache_offset=0)
    two_leaves = view_of(pools(1), tables(1), [0], [4])._replace(pool_i=None)
    with pytest.raises(ValueError, match="index key"):
        mixer(params, x, ForwardContext(serving=True), position_ids=pos,
              kv_cache=two_leaves)
    with pytest.raises(AssertionError, match="separate Q / K / V"):
        SparseSelfAttention(index_n_heads=2, index_head_dim=8, index_topk=4,
                            **{**GQA, "qkv_in_one": True, "num_kv_heads": None})
    # the threshold of the shared module is the one the mixer calls
    assert SparseSelfAttention._chosen(mixer, jnp.zeros((1, 4)), jnp.ones((1, 4), bool), 2
                                       ).tolist() == threshold_choice(
        jnp.zeros((1, 4)), jnp.ones((1, 4), bool), 2).tolist()
