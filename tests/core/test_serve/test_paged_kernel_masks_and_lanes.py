"""The Pallas paged-decode kernel (``test_paged_kernel.py``, whose cases and
dense reference these use) under what later issues gave it. ISSUE 64: an
optional mask operand (a row's choice of slots: the sparse grouped-query
mixer's one-token rows); its cases hold the masked call to the dense reference
under the mask and the maskless call to the operands it had. A group of 16
over 2 KV heads against the gather formulation; heads narrower than the lanes
sharing a lane row, and the pool of narrow heads made and written packed.
ISSUE 74: pools of heads wider than the lanes (or of one head) laid head-major:
the kernel against the float32 reference, the writer, the rule."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from scaling_tpu.nn import paged_attention  # noqa: E402
from scaling_tpu.nn.attention import kv_quantize_int8  # noqa: E402
from scaling_tpu.nn.paged_attention import (  # noqa: E402
    paged_decode_attention,
)

from .test_paged_kernel import (  # noqa: E402
    BS, assert_real_positions, check_rows, dense_reference, tiled_case,
)


def masked_case(group, s):
    """Four rows over tiles of 8 tokens (2 blocks of 4): a row in its first
    tile, one that ends mid-block and mid-tile, one on its table's last slot,
    an inactive one; ``chosen`` keeps about half of every row's slots, the
    last row's first position among them."""
    rng = np.random.default_rng(64)
    case = tiled_case(
        rng, block_size=4, max_blocks=7, n_kv=2, group=group, s=s,
        ctx=[3, 18 - s, 28 - s, 0], new_len=[s, s, s, 0])
    chosen = rng.random((4, 28)) < 0.5
    chosen[:3, 0] = True
    return case, jnp.asarray(chosen)


@pytest.mark.parametrize("group", [1, 4, 8])
@pytest.mark.parametrize("s", [1, 5], ids=["one-position", "five-positions"])
@pytest.mark.parametrize("mask", ["all-true", "random", "empty", "narrower"])
def test_a_rows_choice_masks_its_slots(monkeypatch, group, s, mask):
    """Slot ``k`` is visible iff the paged contract admits it AND the row
    chose it: a choice of everything is the maskless call; a random one is
    the dense reference under it; a row that chose nothing, or sees nothing,
    gives zeros; a mask narrower than the table's window chooses nothing past
    its width."""
    monkeypatch.setattr(paged_attention, "_TILE_TOKENS", 8)
    (q, pk, pv, tab, ctx, new), chosen = masked_case(group, s)
    if mask == "all-true":
        out = check_rows(q, pk, pv, tab, ctx, new, group,
                         chosen=jnp.ones_like(chosen))
        want = check_rows(q, pk, pv, tab, ctx, new, group)
        assert_real_positions(out, want, new, 1e-6)
        return
    if mask == "empty":
        chosen = chosen.at[1].set(False)
    if mask == "narrower":      # 13 slots of 28: the rest is not chosen
        out = check_rows(q, pk, pv, tab, ctx, new, group, chosen=chosen[:, :13])
        chosen = chosen.at[:, 13:].set(False)
    else:
        out = check_rows(q, pk, pv, tab, ctx, new, group, chosen=chosen)
    ref = dense_reference(q, pk, pv, tab, ctx + new, ctx, group, chosen)
    live = [0, 2] if mask == "empty" else [0, 1, 2]
    for row in live:
        np.testing.assert_allclose(
            np.asarray(out[row]), np.asarray(ref[row]), atol=1e-5,
            err_msg=f"row {row}")
    # the inactive row, and the row that chose nothing
    assert not np.asarray(out[3]).any()
    if mask == "empty":
        assert not np.asarray(out[1]).any()


def pallas_operands(**kwargs):
    """``(prefetched scalars, all operands)`` of the call's ``pallas_call``,
    read from its jaxpr (no kernel is built: nothing is lowered)."""
    rng = np.random.default_rng(0)
    q, pk, pv, tab, ctx, new = tiled_case(
        rng, block_size=4, max_blocks=4, n_kv=2, group=2, s=1,
        ctx=[5, 0], new_len=[1, 1])
    if kwargs.pop("int8", False):
        (pk, sk), (pv, sv) = kv_quantize_int8(pk), kv_quantize_int8(pv)
        kwargs.update(scale_k=sk, scale_v=sv)
    jaxpr = jax.make_jaxpr(lambda *a: paged_decode_attention(
        *a, sm_scale=0.25, num_repeat_kv=2, interpret=True, **kwargs))(
            q, pk, pv, tab, ctx + new, ctx)
    calls = []

    def find(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                calls.append(eqn)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                find(sub)

    find(jaxpr.jaxpr)
    (call,) = calls
    return call.params["grid_mapping"].num_index_operands, len(call.invars)


def test_a_maskless_call_has_the_operands_it_had():
    """Five prefetched scalars (table, valid_len, base, tiles before, next
    row), the queries and two pools; an int8 pool adds its two strips of
    scales, a mask ONE strip more, and nothing else."""
    assert pallas_operands() == (5, 8)
    assert pallas_operands(int8=True) == (5, 10)
    assert pallas_operands(chosen=jnp.ones((2, 16), bool)) == (5, 9)


def test_group_16_over_2_kv_heads_agrees_with_the_gather_formulation():
    """Nemotron-3-Nano's attention: 32 query heads over 2 KV heads x 128 (GQA
    group 16; 2 KV heads are under a sublane tile, one packed word a token),
    through ``ParallelSelfAttention._paged_attention`` on a mixed tick (a
    decode row, a chunk row, an empty one): the Pallas kernel against the
    ``'xla'`` gather formulation it is held to. bf16 inputs, float32
    accumulation on both sides: the outputs differ by the rounding of the
    probabilities to bf16 before PV (2**-9 of values under 1 in magnitude)."""
    from scaling_tpu.nn.attention import PagedKVCacheView, ParallelSelfAttention
    from scaling_tpu.nn.base_layer import ForwardContext

    hidden, heads, kv_heads, head_dim = 96, 32, 2, 128
    attn = ParallelSelfAttention(
        hidden, heads, num_kv_heads=kv_heads, head_dim=head_dim, qkv_in_one=False,
        bias=False, dtype=jnp.bfloat16, relative_position_embedding_type="none")
    params = attn.init(jax.random.PRNGKey(0))
    assert params["query"]["weight"].shape == (hidden, heads * head_dim)
    assert params["key"]["weight"].shape == (hidden, kv_heads * head_dim)
    assert params["dense"]["weight"].shape == (heads * head_dim, hidden)
    rows, s, block, max_blocks = 3, 8, 16, 4
    x = jax.random.normal(jax.random.PRNGKey(1), (rows, s, hidden), jnp.bfloat16)
    pool = jax.random.normal(
        jax.random.PRNGKey(2), (rows * max_blocks + 1, block, kv_heads, head_dim),
        jnp.bfloat16)
    view = PagedKVCacheView(
        pool_k=pool, pool_v=pool[::-1],
        block_table=1 + jnp.arange(rows * max_blocks, dtype=jnp.int32).reshape(rows, -1),
        context_len=jnp.asarray([37, 16, 0], jnp.int32),
        new_len=jnp.asarray([1, 8, 0], jnp.int32))
    outs = {}
    for kernel in ("pallas", "xla"):
        out, new_view = attn(params, x, ForwardContext(paged_kernel=kernel),
                             kv_cache=view)
        outs[kernel] = np.asarray(out.astype(jnp.float32))
        assert new_view.pool_k.shape == pool.shape
    for row, n in ((0, 1), (1, 8)):
        np.testing.assert_allclose(outs["pallas"][row, :n], outs["xla"][row, :n],
                                   atol=2e-2, rtol=2e-2)
        assert np.abs(outs["xla"][row, :n]).max() > 0.1


@pytest.mark.parametrize("h,n_kv,n_rep", [(64, 8, 4), (64, 2, 1), (32, 4, 2)],
                         ids=["lfm2-32q8kv-h64", "h64-group1", "h32-four-a-row"])
@pytest.mark.parametrize("s", [1, 8])
def test_heads_narrower_than_the_lanes_share_a_lane_row(h, n_kv, n_rep, s):
    """LFM2's attention has heads of 64 where the kernel's strided read wants
    rows of 128 lanes: the pool is MADE with two KV heads side by side in a
    lane row (``packed_kv_dims``), each query widened with zeros outside its
    own KV head's lanes. A decode row, a chunk row and an empty one against
    the dense window over the same values head by head."""
    rng = np.random.default_rng(h + s)
    rows, blocks = 3, 6
    pool_k = jnp.asarray(rng.normal(size=(rows * blocks + 1, BS, n_kv, h)), jnp.float32)
    pool_v = jnp.asarray(rng.normal(size=(rows * blocks + 1, BS, n_kv, h)), jnp.float32)
    tab = 1 + jnp.arange(rows * blocks, dtype=jnp.int32).reshape(rows, blocks)
    q = jnp.asarray(rng.normal(size=(rows, s, n_kv * n_rep, h)), jnp.float32)
    valid = jnp.asarray([21, s, 0], jnp.int32)
    base = jnp.asarray([21 - s, 0, 0], jnp.int32)
    packed = paged_attention.packed_kv_dims(n_kv, h)
    assert packed == (n_kv * h // 128, 128)
    assert paged_attention.packed_kv_dims(8, 128) == (8, 128)
    assert paged_attention.packed_kv_dims(3, 64) == (3, 64)    # no whole lane rows
    assert paged_attention.packed_kv_dims(4, 48) == (4, 48)
    got = paged_decode_attention(
        q, pool_k.reshape(*pool_k.shape[:2], *packed),
        pool_v.reshape(*pool_v.shape[:2], *packed), tab, valid, base,
        sm_scale=h ** -0.5, num_repeat_kv=n_rep)
    want = dense_reference(q, pool_k, pool_v, tab, valid, base, n_rep)
    np.testing.assert_allclose(np.asarray(got[:2]), np.asarray(want[:2]), atol=2e-5)
    assert np.isfinite(np.asarray(got)).all()
    # the widened queries: a head's own lanes hold it, the others zeros
    wide = paged_attention._pack_queries(q, n_kv, 128 // h)
    assert wide.shape == (rows, s, n_kv * n_rep, 128)
    assert int((wide != 0).sum()) == int((q != 0).sum())


def test_a_pool_of_narrow_heads_is_made_and_written_packed():
    """``init_pools`` makes a native pool of 64-wide heads two a lane row, the
    ONE writer regroups a token's K and V to it, and both formulations of
    the paged branch read it (``ParallelSelfAttention._paged_attention``)."""
    from scaling_tpu.nn.attention import PagedKVCacheView, ParallelSelfAttention
    from scaling_tpu.nn.base_layer import ForwardContext
    from scaling_tpu.nn.norm import NormType

    hidden, heads, kv_heads, head_dim = 96, 8, 4, 64
    attn = ParallelSelfAttention(
        hidden, heads, num_kv_heads=kv_heads, head_dim=head_dim, qkv_in_one=False,
        bias=False, key_query_norm=True, norm_type=NormType.RMS,
        relative_position_embedding_type="none")
    params = attn.init(jax.random.PRNGKey(0))
    rows, s, block, max_blocks = 3, 8, 4, 6
    x = jax.random.normal(jax.random.PRNGKey(1), (rows, s, hidden))
    pool = jax.random.normal(
        jax.random.PRNGKey(2), (rows * max_blocks + 1, block, kv_heads, head_dim))
    table = 1 + jnp.arange(rows * max_blocks, dtype=jnp.int32).reshape(rows, -1)
    lens = dict(context_len=jnp.asarray([13, 4, 0], jnp.int32),
                new_len=jnp.asarray([1, 8, 0], jnp.int32))
    plain = PagedKVCacheView(pool_k=pool, pool_v=pool[::-1], block_table=table, **lens)
    packed_dims = (*pool.shape[:2], 2, 128)
    packed = plain._replace(pool_k=pool.reshape(packed_dims),
                            pool_v=pool[::-1].reshape(packed_dims))
    outs = {}
    for name, view, kernel in (("plain-xla", plain, "xla"), ("packed-xla", packed, "xla"),
                               ("packed-pallas", packed, "pallas")):
        out, new_view = attn(params, x, ForwardContext(paged_kernel=kernel), kv_cache=view)
        assert new_view.pool_k.shape == view.pool_k.shape
        outs[name] = (np.asarray(out), np.asarray(new_view.pool_k).reshape(pool.shape))
    for name in ("packed-xla", "packed-pallas"):
        for row, n in ((0, 1), (1, 8)):
            np.testing.assert_allclose(outs[name][0][row, :n],
                                       outs["plain-xla"][0][row, :n], atol=2e-5)
        np.testing.assert_array_equal(outs[name][1], outs["plain-xla"][1])


# ---- ISSUE 74: a pool of few KV heads lies HEAD-MAJOR, (num_blocks, n_kv,
# block_size, h): a block is whole memory tiles and a head's matrix in the
# kernel's VMEM tile is dense. The kernel reads which layout a pool has off its
# shape (``kv_block_layout``); the cases hold it to the float32 reference at 2
# KV heads of 128 and of 256 lanes.

def head_major(pool):
    return jnp.swapaxes(pool, 1, 2)


HEAD_MAJOR_ROWS = {
    # what the rows hold, of tiles of 512 tokens (40 blocks of 16 a row): none,
    # one line, a part of a sub-tile, whole sub-tiles, a whole tile, a tile and
    # a line, a tile and a part
    "decode": (1, [0, 1, 100, 256, 512, 513, 640], "one"),
    "chunk": (16, [0, 16, 100, 512, 513, 640], "all"),
    # a decode row, a row with drafts, chunk rows, an inactive row in one call
    "mixed": (16, [300, 5, 640, 16, 0, 513], [1, 5, 16, 13, 0, 16]),
}


@pytest.mark.parametrize("mask", ["maskless", "masked"])
@pytest.mark.parametrize("rows", list(HEAD_MAJOR_ROWS))
@pytest.mark.parametrize("h", [128, 256])
def test_a_head_major_pool_of_two_kv_heads_matches_the_float32_reference(
    h, rows, mask
):
    """bf16 K and V, float32 scores and softmax state: the kernel over a
    head-major pool against the dense window over the same values in float32,
    for decode rows, chunk rows and a mixed call, rows of 0 / 1 / part-held /
    whole tiles, with and without a mask; and bit for bit what it gives over
    the token-major pool of the same values where both take the same tiles."""
    s, valid, new = HEAD_MAJOR_ROWS[rows]
    valid = np.asarray(valid, np.int32)
    new = {"one": 1, "all": s}[new] if isinstance(new, str) else new
    new = np.minimum(valid, new).astype(np.int32)
    group = 8
    rng = np.random.default_rng(74)
    q, pk, pv, tab, ctx, new = tiled_case(
        rng, block_size=16, max_blocks=40, n_kv=2, group=group, s=s,
        ctx=valid - new, new_len=new, dtype=jnp.bfloat16, h=h)
    kwargs, chosen = {}, None
    if mask == "masked":
        chosen = rng.random((len(valid), 640)) < 0.5
        chosen |= np.arange(640) >= np.asarray(ctx)[:, None]
        chosen = kwargs["chosen"] = jnp.asarray(chosen)
    assert paged_attention.kv_block_layout(head_major(pk), 2 * h) == (16, 2, True)
    assert paged_attention.kv_block_layout(pk, 2 * h) == (16, 2, False)
    out = check_rows(q, head_major(pk), head_major(pv), tab, ctx, new, group,
                     **kwargs)
    f32 = [a.astype(jnp.float32) for a in (q, pk, pv)]
    ref = dense_reference(*f32, tab, ctx + new, ctx, group, chosen)
    assert_real_positions(out, ref, new, 2e-2)
    assert not bool(jnp.any(out[np.asarray(valid) == 0]))
    if h == 128:    # the token-major tile is 512 tokens too: the same folds
        same = check_rows(q, pk, pv, tab, ctx, new, group, **kwargs)
        np.testing.assert_array_equal(np.asarray(out, np.float32),
                                      np.asarray(same, np.float32))


@pytest.mark.parametrize("n_kv,h", [(1, 128), (4, 256)],
                         ids=["one-kv-head", "four-heads-of-256"])
def test_lines_mosaic_cannot_read_token_major_read_head_major(n_kv, h):
    """One KV head, and heads of 256 lanes at a count other than 2: the lines
    no cell serves that ``head_major_kv`` also lays head-major (token-major the
    chip's compiler refuses them). A decode row, a row with drafts, a chunk row
    and an empty one against the float32 reference."""
    rng = np.random.default_rng(n_kv + h)
    valid, new = np.asarray([300, 5, 530, 0], np.int32), [1, 5, 16, 0]
    new = np.minimum(valid, new).astype(np.int32)
    q, pk, pv, tab, ctx, new = tiled_case(
        rng, block_size=16, max_blocks=40, n_kv=n_kv, group=4, s=16,
        ctx=valid - new, new_len=new, dtype=jnp.bfloat16, h=h)
    assert paged_attention.kv_pool_dims(16, n_kv, h, 2) == ((n_kv, 16, h), 1)
    out = check_rows(q, head_major(pk), head_major(pv), tab, ctx, new, 4)
    f32 = [a.astype(jnp.float32) for a in (q, pk, pv)]
    ref = dense_reference(*f32, tab, ctx + new, ctx, 4)
    assert_real_positions(out, ref, new, 2e-2)
    assert not bool(jnp.any(out[3]))


def test_a_pool_of_wide_heads_is_written_and_read_head_major():
    """The ONE writer gives a token's K and V to a head-major pool head by
    head, and both formulations of the paged branch read it back
    (``ParallelSelfAttention._paged_attention``): the pool after the write is
    the token-major pool after the same write bit for bit, and so is the XLA
    fallback's output; the kernel's agrees with it."""
    from scaling_tpu.nn.attention import PagedKVCacheView, ParallelSelfAttention
    from scaling_tpu.nn.base_layer import ForwardContext
    from scaling_tpu.nn.norm import NormType

    hidden, heads, kv_heads, head_dim = 96, 4, 2, 256
    attn = ParallelSelfAttention(
        hidden, heads, num_kv_heads=kv_heads, head_dim=head_dim, qkv_in_one=False,
        bias=False, key_query_norm=True, norm_type=NormType.RMS,
        relative_position_embedding_type="none")
    params = attn.init(jax.random.PRNGKey(0))
    rows, s, block, max_blocks = 3, 8, 8, 4
    assert paged_attention.kv_pool_dims(block, kv_heads, head_dim, 4) == (
        (kv_heads, block, head_dim), 1)
    x = jax.random.normal(jax.random.PRNGKey(1), (rows, s, hidden))
    pool = jax.random.normal(
        jax.random.PRNGKey(2), (rows * max_blocks + 1, block, kv_heads, head_dim))
    table = 1 + jnp.arange(rows * max_blocks, dtype=jnp.int32).reshape(rows, -1)
    lens = dict(context_len=jnp.asarray([19, 4, 0], jnp.int32),
                new_len=jnp.asarray([1, 8, 0], jnp.int32))
    plain = PagedKVCacheView(pool_k=pool, pool_v=pool[::-1], block_table=table, **lens)
    major = plain._replace(pool_k=head_major(pool), pool_v=head_major(pool[::-1]))
    outs = {}
    for name, view, kernel in (("plain-xla", plain, "xla"), ("major-xla", major, "xla"),
                               ("major-pallas", major, "pallas")):
        out, new_view = attn(params, x, ForwardContext(paged_kernel=kernel), kv_cache=view)
        assert new_view.pool_k.shape == view.pool_k.shape
        written = new_view.pool_k if view is plain else head_major(new_view.pool_k)
        outs[name] = (np.asarray(out), np.asarray(written))
    assert (outs["plain-xla"][1] != np.asarray(pool)).any()     # something was written
    for name in ("major-xla", "major-pallas"):
        np.testing.assert_array_equal(outs[name][1], outs["plain-xla"][1])
    for row, n in ((0, 1), (1, 8)):
        np.testing.assert_array_equal(outs["major-xla"][0][row, :n],
                                      outs["plain-xla"][0][row, :n])
        np.testing.assert_allclose(outs["major-pallas"][0][row, :n],
                                   outs["plain-xla"][0][row, :n], atol=2e-5)
        assert np.abs(outs["plain-xla"][0][row, :n]).max() > 0.1


# every serve cell's line: KV heads and head width as its config gives them,
# the dims ``init_pools`` makes a block of 16 bf16 tokens in, head-major or not
CELL_LINES = {
    "mistral-7b": ((8, 128), (16, 8, 128), False),
    "olmoe-1b-7b": ((16, 128), (16, 16, 128), False),
    "ouro-2.6b": ((16, 128), (16, 16, 128), False),
    "nemotron3-nano": ((2, 128), (16, 2, 128), False),
    "lfm2-24b (two heads a lane row)": ((8, 64), (16, 4, 128), False),
    "falcon-h1-34b": ((4, 128), (16, 4, 128), False),
    "keye-vl-2.0": ((4, 128), (16, 4, 128), False),
    "laguna-s-2.1": ((8, 128), (16, 8, 128), False),
    "qwen3-next-80b": ((2, 256), (2, 16, 256), True),
    # served by no cell: what Mosaic cannot read token-major at all
    "one KV head": ((1, 128), (1, 16, 128), True),
    "eight heads of 256 lanes": ((8, 256), (8, 16, 256), True),
    "sixteen heads of 256 lanes": ((16, 256), (16, 16, 256), True),
}


@pytest.mark.parametrize("cell", list(CELL_LINES))
def test_the_layout_is_a_function_of_the_line(cell):
    """Head-major where a head is wider than the lanes or alone; every pool of
    128-lane heads keeps the dims it had. The pool's shape then says which
    layout it is to every reader, also where heads and tokens are as many."""
    (n_kv, h), dims, major = CELL_LINES[cell]
    head_axis = 1 if major else 2
    assert paged_attention.kv_pool_dims(16, n_kv, h, 2) == (dims, head_axis)
    pool = jnp.zeros((3, *dims), jnp.bfloat16)
    layout = paged_attention.kv_block_layout(pool, n_kv * h)
    assert layout == (16, dims[head_axis - 1], major)
    # blocks or heads of no whole memory tiles stay token-major; so does a
    # shard of several heads; an int8 pool is never head-major
    assert paged_attention.kv_pool_dims(4, n_kv, h, 2)[1] == 2
    assert not paged_attention.head_major_kv(16, n_kv, 48, 2)
    assert not paged_attention.kv_block_layout(
        jnp.zeros((3, 16, 16, 256), jnp.int8), 16 * 256).head_major


def test_a_shards_heads_decide_the_layout_of_a_sharded_pool():
    """Under a model axis the pool keeps its GLOBAL heads, unpacked, and lies
    as a shard's count says: two 128-lane heads over two shards are one a
    shard."""
    assert paged_attention.kv_pool_dims(16, 2, 128, 2, shards=2) == ((2, 16, 128), 1)
    assert paged_attention.kv_pool_dims(16, 4, 128, 2, shards=2) == ((16, 4, 128), 2)
    assert paged_attention.kv_pool_dims(16, 8, 64, 2, shards=2) == ((16, 8, 64), 2)
    assert paged_attention.kv_pool_dims(16, 4, 256, 2, shards=2) == ((4, 16, 256), 1)
