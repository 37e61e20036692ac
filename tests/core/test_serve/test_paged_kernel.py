"""Pallas paged-decode kernel parity (ISSUE 10), array-level and fast:
the streaming online-softmax kernel (nn/paged_attention.py, interpret
mode on the CPU mesh) against a straight dense reference that gathers
the block window and softmaxes it whole — native and int8-dequant-in-
kernel, single decode tokens and multi-token prefill chunks, GQA
repeat, and the all-trash inactive row. ISSUE 26 re-tiled the kernel
(several pool blocks a tile, the GQA group folded into the matmul's
rows, a short-query path); its cases force several tiles a row by
shrinking the tile the shapes would derive. ISSUE 41 ran the kernel's
double buffer across rows (a row's first tile is fetched under the last
fold of the active row before it); its cases mix rows of 1, 2 and 3
tiles with inactive rows anywhere, and permute the rows of a call. The mask
operand (ISSUE 64), groups of 16 and heads narrower than the lanes:
``test_paged_kernel_masks_and_lanes.py``."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from scaling_tpu.nn import paged_attention  # noqa: E402
from scaling_tpu.nn.attention import kv_quantize_int8  # noqa: E402
from scaling_tpu.nn.paged_attention import (  # noqa: E402
    paged_decode_attention,
)

BS, MAXB, NB, H = 4, 4, 9, 16


def dense_reference(q, pool_k, pool_v, tab, valid_len, base, n_rep,
                    chosen=None):
    """Gather-the-window attention, mirroring the XLA fallback's masking
    discipline (slot < valid_len, slot <= q_slot), under a row's ``chosen``
    (rows, window) where given."""
    b, s, n, h = q.shape
    window = tab.shape[1] * pool_k.shape[1]
    gk = pool_k[tab].reshape(b, window, -1, h)
    gv = pool_v[tab].reshape(b, window, -1, h)
    if n_rep > 1:
        n_kv = gk.shape[2]
        gk = jnp.broadcast_to(
            gk[:, :, :, None, :], (b, window, n_kv, n_rep, h)
        ).reshape(b, window, n, h)
        gv = jnp.broadcast_to(
            gv[:, :, :, None, :], (b, window, n_kv, n_rep, h)
        ).reshape(b, window, n, h)
    slots_k = jnp.arange(window)[None, :]
    slots_q = base[:, None] + jnp.arange(s)[None, :]
    allowed = (slots_k[:, None, :] < valid_len[:, None, None]) & (
        slots_k[:, None, :] <= slots_q[:, :, None]
    )
    if chosen is not None:
        allowed = allowed & chosen[:, None, :]
    scores = jnp.einsum("bqnh,bknh->bnqk", q, gk) * h ** -0.5
    scores = jnp.where(allowed[:, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bnqk,bknh->bqnh", probs, gv)


def make_case(rng, n_kv, s):
    pool_k = jnp.asarray(rng.normal(size=(NB, BS, n_kv, H)), jnp.float32)
    pool_v = jnp.asarray(rng.normal(size=(NB, BS, n_kv, H)), jnp.float32)
    tab = jnp.asarray([[1, 2, 0, 0], [3, 0, 0, 0], [4, 5, 6, 7]], jnp.int32)
    ctx = jnp.asarray([5, 2, 11], jnp.int32)
    q = jnp.asarray(rng.normal(size=(3, s, 4, H)), jnp.float32)
    return q, pool_k, pool_v, tab, ctx


@pytest.mark.parametrize("n_kv,n_rep", [(4, 1), (2, 2)])
@pytest.mark.parametrize("s", [1, 4])
def test_kernel_matches_dense_window(n_kv, n_rep, s):
    rng = np.random.default_rng(0)
    q, pool_k, pool_v, tab, ctx = make_case(rng, n_kv, s)
    out = paged_decode_attention(
        q, pool_k, pool_v, tab, ctx + s, ctx,
        sm_scale=H ** -0.5, num_repeat_kv=n_rep, interpret=True,
    )
    ref = dense_reference(q, pool_k, pool_v, tab, ctx + s, ctx, n_rep)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_kernel_int8_dequant_in_kernel_matches_dense_dequant():
    """The int8 variant dequantizes inside the kernel with the SAME
    kv_quantize_int8 scales the pool writer produced; it must equal the
    reference computed over host-dequantized pools (same scales, same
    math — just never materializing the f32 window)."""
    rng = np.random.default_rng(1)
    q, pool_k, pool_v, tab, ctx = make_case(rng, 2, 1)
    qk, sk = kv_quantize_int8(pool_k)
    qv, sv = kv_quantize_int8(pool_v)
    out = paged_decode_attention(
        q, qk, qv, tab, ctx + 1, ctx,
        sm_scale=H ** -0.5, num_repeat_kv=2,
        scale_k=sk, scale_v=sv, interpret=True,
    )
    deq_k = qk.astype(jnp.float32) * sk[..., None]
    deq_v = qv.astype(jnp.float32) * sv[..., None]
    ref = dense_reference(q, deq_k, deq_v, tab, ctx + 1, ctx, 2)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_kernel_inactive_row_is_finite():
    """An inactive slot (all-trash table, zero context) must come back
    finite — its output is discarded, but a NaN would poison the batched
    program's donation/debug paths."""
    rng = np.random.default_rng(2)
    q, pool_k, pool_v, _, _ = make_case(rng, 4, 1)
    tab = jnp.zeros((3, MAXB), jnp.int32)
    ctx = jnp.zeros((3,), jnp.int32)
    out = paged_decode_attention(
        q, pool_k, pool_v, tab, ctx + 1, ctx,
        sm_scale=H ** -0.5, num_repeat_kv=1, interpret=True,
    )
    assert bool(jnp.all(jnp.isfinite(out)))


def test_kernel_respects_new_token_visibility():
    """Causality at the slot level: with two new tokens (s=2), token 0
    must not see token 1's slot. Flip token 1's K/V; token 0's output
    must not move."""
    rng = np.random.default_rng(3)
    q, pool_k, pool_v, tab, ctx = make_case(rng, 4, 2)
    out1 = paged_decode_attention(
        q, pool_k, pool_v, tab, ctx + 2, ctx,
        sm_scale=H ** -0.5, num_repeat_kv=1, interpret=True,
    )
    # perturb the pool at each row's LAST new slot (ctx+1)
    pk = np.array(pool_k)  # writable copy
    for row in range(3):
        slot = int(ctx[row]) + 1
        blk = int(tab[row, slot // BS])
        pk[blk, slot % BS] += 100.0
    out2 = paged_decode_attention(
        q, jnp.asarray(pk), pool_v, tab, ctx + 2, ctx,
        sm_scale=H ** -0.5, num_repeat_kv=1, interpret=True,
    )
    np.testing.assert_allclose(
        np.asarray(out1[:, 0]), np.asarray(out2[:, 0]), atol=1e-5
    )


# ---- ISSUE 26: the re-tiled kernel. Rows own consecutive pool blocks; a
# tile of `tile_tokens` is forced through the module's target, so a few
# dozen tokens of context already span several tiles.

def tiled_case(rng, *, block_size, max_blocks, n_kv, group, s, ctx, new_len,
               dtype=jnp.float32, h=H):
    rows = len(ctx)
    pool_shape = (rows * max_blocks + 1, block_size, n_kv, h)
    pool_k = jnp.asarray(rng.normal(size=pool_shape), dtype)
    pool_v = jnp.asarray(rng.normal(size=pool_shape), dtype)
    ctx, new_len = np.asarray(ctx, np.int32), np.asarray(new_len, np.int32)
    tab = 1 + np.arange(rows * max_blocks, dtype=np.int32).reshape(
        rows, max_blocks)
    for r in range(rows):  # blocks past the row's slots are trash
        tab[r, -(-int(ctx[r] + new_len[r]) // block_size):] = 0
    q = jnp.asarray(rng.normal(size=(rows, s, n_kv * group, h)), dtype)
    return (q, pool_k, pool_v, jnp.asarray(tab), jnp.asarray(ctx),
            jnp.asarray(new_len))


def check_rows(q, pool_k, pool_v, tab, ctx, new_len, group, **kernel_kwargs):
    """Run the kernel; every position, padded ones too, must be finite."""
    h = q.shape[-1]
    out = paged_decode_attention(
        q, pool_k, pool_v, tab, ctx + new_len, ctx,
        sm_scale=h ** -0.5, num_repeat_kv=group, interpret=True,
        **kernel_kwargs,
    )
    assert out.shape == q.shape and out.dtype == q.dtype
    assert bool(jnp.all(jnp.isfinite(out.astype(jnp.float32))))
    return out


def assert_real_positions(out, ref, new_len, atol):
    for row, real in enumerate(np.asarray(new_len)):
        np.testing.assert_allclose(
            np.asarray(out[row, :real], np.float32),
            np.asarray(ref[row, :real], np.float32), atol=atol,
            err_msg=f"row {row} ({real} real positions)",
        )


@pytest.mark.parametrize("group", [4, 9])
@pytest.mark.parametrize("s", [1, 5, 16])
def test_gqa_group_folds_into_the_matmul_rows(monkeypatch, group, s):
    """Group 9 (Pharia: 36 q / 4 KV heads) makes the folded rows no
    multiple of the sublane count; s = 5 (drafts) pads the positions."""
    monkeypatch.setattr(paged_attention, "_TILE_TOKENS", 8)
    rng = np.random.default_rng(10)
    q, pk, pv, tab, ctx, new = tiled_case(
        rng, block_size=4, max_blocks=6, n_kv=2, group=group, s=s,
        ctx=[0, 7, 24 - s], new_len=[s, s, s],
    )
    out = check_rows(q, pk, pv, tab, ctx, new, group)
    ref = dense_reference(q, pk, pv, tab, ctx + new, ctx, group)
    assert_real_positions(out, ref, new, 1e-5)


@pytest.mark.parametrize(
    "valid", [6, 8, 9, 15, 16, 17],
    ids=["mid-block", "on-tile-boundary", "one-past-boundary",
         "last-slot-of-tile-2", "on-boundary-2", "one-past-boundary-2"],
)
def test_context_ends_around_a_tile_boundary(monkeypatch, valid):
    """Tiles of 8 tokens (2 blocks of 4): the decode token's slot is the
    context's last, so `valid` walks it across the tile's edge."""
    monkeypatch.setattr(paged_attention, "_TILE_TOKENS", 8)
    rng = np.random.default_rng(11)
    q, pk, pv, tab, ctx, new = tiled_case(
        rng, block_size=4, max_blocks=5, n_kv=2, group=2, s=1,
        ctx=[valid - 1, 0], new_len=[1, 1],
    )
    out = check_rows(q, pk, pv, tab, ctx, new, 2)
    ref = dense_reference(q, pk, pv, tab, ctx + new, ctx, 2)
    assert_real_positions(out, ref, new, 1e-5)


@pytest.mark.parametrize("kv_dtype", ["native", "int8"])
@pytest.mark.parametrize(
    "block_size,max_blocks,tile_tokens",
    [(4, 5, 8), (16, 3, 32), (4, 7, 12)],
    ids=["bs4-5blocks-by2", "bs16-3blocks-by2", "bs4-7blocks-by3"],
)
def test_one_call_mixes_every_row_kind(
    monkeypatch, block_size, max_blocks, tile_tokens, kv_dtype
):
    """One mixed tick: a decode row (1 real position), a draft row (5), a
    chunk row (the full width), a chunk row shorter than the width, and
    an all-trash row; `max_blocks` is no multiple of the blocks a tile
    takes, so the last tile of a full row is cut short by the table."""
    monkeypatch.setattr(paged_attention, "_TILE_TOKENS", tile_tokens)
    assert max_blocks % paged_attention._blocks_per_tile(
        block_size, max_blocks, 2, H, 4) != 0
    s, window = 16, block_size * max_blocks
    rng = np.random.default_rng(12)
    new = [1, 5, s, s - 3, 0]
    ctx = [window - 1, window // 2, window - s, 3, 0]
    q, pk, pv, tab, ctx, new = tiled_case(
        rng, block_size=block_size, max_blocks=max_blocks, n_kv=2, group=2,
        s=s, ctx=ctx, new_len=new,
    )
    if kv_dtype == "int8":
        qk, sk = kv_quantize_int8(pk)
        qv, sv = kv_quantize_int8(pv)
        out = check_rows(q, qk, qv, tab, ctx, new, 2, scale_k=sk, scale_v=sv)
        pk = qk.astype(jnp.float32) * sk[..., None]
        pv = qv.astype(jnp.float32) * sv[..., None]
    else:
        out = check_rows(q, pk, pv, tab, ctx, new, 2)
    ref = dense_reference(q, pk, pv, tab, ctx + new, ctx, 2)
    assert_real_positions(out, ref, new, 1e-5)
    # the all-trash row comes back as zeros, and so does a decode row past
    # its first positions: that row took the short-query path
    assert not bool(jnp.any(out[4]))
    assert not bool(jnp.any(out[0, paged_attention._SHORT_QUERIES:]))


@pytest.mark.parametrize("dtype,n_kv", [
    (jnp.bfloat16, 2), (jnp.bfloat16, 4), (jnp.bfloat16, 3),
    (jnp.int8, 4), (jnp.int8, 8), (jnp.int8, 2),
], ids=["bf16-2kv", "bf16-4kv", "bf16-3kv-unpacked",
        "int8-4kv", "int8-8kv", "int8-2kv-unpacked"])
def test_narrow_pools_unpack_their_heads_from_words(monkeypatch, dtype, n_kv):
    """bf16 and int8 pools are read as 32-bit words holding 2 or 4
    consecutive heads of a token and unpacked with shifts; a head count
    the packing does not divide takes the plain per-head read."""
    monkeypatch.setattr(paged_attention, "_TILE_TOKENS", 8)
    rng = np.random.default_rng(13)
    q, pk, pv, tab, ctx, new = tiled_case(
        rng, block_size=4, max_blocks=5, n_kv=n_kv, group=2, s=4,
        ctx=[13, 2, 0], new_len=[4, 1, 0], dtype=jnp.bfloat16,
    )
    if dtype == jnp.int8:
        qk, sk = kv_quantize_int8(pk)
        qv, sv = kv_quantize_int8(pv)
        out = check_rows(q, qk, qv, tab, ctx, new, 2, scale_k=sk, scale_v=sv)
        pk = qk.astype(jnp.float32) * sk[..., None]
        pv = qv.astype(jnp.float32) * sv[..., None]
    else:
        out = check_rows(q, pk, pv, tab, ctx, new, 2)
    ref = dense_reference(
        q.astype(jnp.float32), pk.astype(jnp.float32),
        pv.astype(jnp.float32), tab, ctx + new, ctx, 2,
    )
    # bf16 queries, probabilities and output: 2**-8 of the values' scale
    assert_real_positions(out, ref, new, 3e-2)


@pytest.mark.parametrize(
    "block_size,max_blocks,n_kv,h,itemsize,expect",
    [
        (16, 256, 8, 128, 2, 32),   # the serve cell: 512 tokens a tile
        (16, 256, 4, 128, 2, 32),   # Pharia: 4 KV heads pad to 8 sublanes
        (16, 256, 8, 128, 1, 32),   # int8 pools
        (16, 256, 32, 128, 4, 8),   # float32, 32 KV heads: VMEM decides
        (4, 4, 2, 16, 4, 4),        # the CPU tests' pool: the whole table
        (16, 3, 8, 128, 2, 3),
        (1024, 8, 8, 128, 2, 1),    # a block larger than the target
    ],
)
def test_blocks_per_tile_is_a_function_of_the_shapes(
    block_size, max_blocks, n_kv, h, itemsize, expect
):
    assert paged_attention._blocks_per_tile(
        block_size, max_blocks, n_kv, h, itemsize) == expect


@pytest.mark.parametrize(
    "block_size,tile_blocks,expect",
    [
        (16, 32, 8),     # every cell: four sub-tiles of 128 tokens a tile
        (16, 8, 8),      # a slot of 128 tokens: the tile is one sub-tile
        (16, 12, 12),    # 8 does not divide 12: the tile is its own sub-tile
        (16, 3, 3),
        (32, 16, 4),
        (128, 4, 1),
        (256, 2, 2),     # a block larger than a sub-tile
        (48, 8, 8),      # blocks that do not make up 128 tokens
        (4, 2, 2),       # the CPU tests' tiles of 8 tokens
    ],
)
def test_blocks_per_sub_is_a_function_of_the_shapes(
    block_size, tile_blocks, expect
):
    assert paged_attention._blocks_per_sub(block_size, tile_blocks) == expect


# ---- ISSUE 41: the double buffer runs over the call's flat list of (row,
# tile) steps. Tiles of 8 tokens (2 blocks of 4), so contexts of 1-8 / 9-16
# / 17-24 tokens are rows of 1 / 2 / 3 tiles; a row's first tile lies in
# the buffer the parity of the tiles before it says, and is fetched by the
# last active row before it.

CROSS_ROW_CASES = {
    # valid_len per row (0: an inactive row, all-trash table)
    "1-2-3-tiles-alternate": [5, 12, 20, 7, 16, 24],
    "inactive-rows-first": [0, 0, 6, 13, 19],
    "inactive-rows-between": [7, 0, 18, 0, 0, 11, 3],
    "inactive-rows-last": [17, 4, 9, 0, 0],
    "a-single-active-row": [0, 0, 21, 0],
    "only-the-first-row-active": [14, 0, 0],
    "an-all-inactive-call": [0, 0, 0],
    "one-tile-after-three": [23, 2, 8, 1],
    "three-tiles-after-one": [3, 22, 6, 24],
    "every-row-starts-in-the-second-buffer": [8, 16, 24, 8, 16],
    "one-tile-rows-only": [1, 8, 4, 7, 2, 5],
}


def cross_row_case(valid, kv_dtype, seed=14):
    """Decode rows (one real position, the context's last slot) over
    contexts of ``valid`` tokens; returns the kernel's arguments, the pools
    the reference reads and the rows' ``new_len``."""
    rng = np.random.default_rng(seed)
    valid = np.asarray(valid, np.int32)
    new = (valid > 0).astype(np.int32)
    q, pk, pv, tab, ctx, new = tiled_case(
        rng, block_size=4, max_blocks=6, n_kv=2, group=2, s=1,
        ctx=valid - new, new_len=new,
    )
    kwargs = {}
    ref_k, ref_v = pk, pv
    if kv_dtype == "int8":
        pk, sk = kv_quantize_int8(pk)
        pv, sv = kv_quantize_int8(pv)
        kwargs = {"scale_k": sk, "scale_v": sv}
        ref_k = pk.astype(jnp.float32) * sk[..., None]
        ref_v = pv.astype(jnp.float32) * sv[..., None]
    return (q, pk, pv, tab, ctx, new), kwargs, (ref_k, ref_v)


@pytest.mark.parametrize("kv_dtype", ["native", "int8"])
@pytest.mark.parametrize("case", list(CROSS_ROW_CASES))
def test_the_pipeline_crosses_rows(monkeypatch, case, kv_dtype):
    """Whatever rows lie between two active ones, and whichever buffer a
    row starts in, every row reads its own tiles whole."""
    monkeypatch.setattr(paged_attention, "_TILE_TOKENS", 8)
    valid = CROSS_ROW_CASES[case]
    (q, pk, pv, tab, ctx, new), kwargs, (ref_k, ref_v) = cross_row_case(
        valid, kv_dtype)
    tiles = [-(-v // 8) for v in valid]
    assert max(tiles) <= 3 and tab.shape[1] * 4 == 24
    out = check_rows(q, pk, pv, tab, ctx, new, 2, **kwargs)
    ref = dense_reference(q, ref_k, ref_v, tab, ctx + new, ctx, 2)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref) * (np.asarray(new) > 0)[
            :, None, None, None],
        atol=1e-5,  # float32 pools: far inside chip_smoke's PAGED_RTOL
    )
    # an inactive row emits zeros
    assert not bool(jnp.any(out[np.asarray(valid) == 0]))


@pytest.mark.parametrize("kv_dtype", ["native", "int8"])
@pytest.mark.parametrize("order", [
    [5, 4, 3, 2, 1, 0], [1, 0, 3, 2, 5, 4], [2, 5, 0, 3, 1, 4],
], ids=["reversed", "swapped-pairs", "shuffled"])
def test_permuting_the_rows_permutes_the_output_bit_for_bit(
    monkeypatch, order, kv_dtype
):
    """A row's output is a function of the row alone: what another row left
    in a buffer, or a tile that had not landed, would show as a difference
    once the rows change places (and with them their starting buffers and
    who fetches whose first tile)."""
    monkeypatch.setattr(paged_attention, "_TILE_TOKENS", 8)
    (q, pk, pv, tab, ctx, new), kwargs, _ = cross_row_case(
        [20, 0, 6, 13, 24, 2], kv_dtype, seed=15)
    out = check_rows(q, pk, pv, tab, ctx, new, 2, **kwargs)
    order = np.asarray(order)
    moved = check_rows(
        q[order], pk, pv, tab[order], ctx[order], new[order], 2, **kwargs)
    np.testing.assert_array_equal(np.asarray(moved), np.asarray(out)[order])


@pytest.mark.parametrize("valid,before,following", [
    ([5, 12, 20, 7], [0, 1, 3, 6], [1, 2, 3, -1]),
    ([0, 0, 6, 13], [0, 0, 0, 1], [2, 2, 3, -1]),
    ([7, 0, 18, 0], [0, 1, 1, 4], [2, 2, -1, -1]),
    ([0, 0, 0], [0, 0, 0], [-1, -1, -1]),
    ([30, 9], [0, 3], [1, -1]),  # clipped to the table's 24 slots
], ids=["all-active", "inactive-first", "inactive-between-and-last",
        "all-inactive", "past-the-table"])
def test_what_crosses_a_grid_step_is_a_function_of_valid_len(
    valid, before, following
):
    got_before, got_next = paged_attention._pipeline_carry(
        jnp.minimum(jnp.asarray(valid, jnp.int32), 24), 8)
    assert got_before.tolist() == before
    assert got_next.tolist() == following


# ---- ISSUE 64: a mask operand. A row's choice of slots, one lane-dense strip
# a row; the rows of ONE position fold their group's rows alone
# (``test_paged_kernel_masks_and_lanes.py``)


# ---- ISSUE 69: a tile is waited for and folded in SUB-TILES of 128 tokens, up
# to the last one that holds a slot the row can see. Blocks of 16 tokens at the
# tile the shapes derive (no monkeypatch): 40 blocks a row are tiles of 32
# blocks = 512 tokens = four sub-tiles of 8 blocks; 12 blocks a row are ONE tile
# of 12 blocks, which 8 does not divide: that tile is its own one sub-tile.

SUB_TILE_ROWS = {
    # max_blocks, valid_len per row (0: inactive); the SECOND row's K and V
    # are large, and it leaves them in both buffers' tails for the rows after
    "sub-tiles-of-128": (40, [
        0, 640, 127, 128, 129, 0, 255, 256, 257, 383, 384, 385, 0, 511, 512,
        513, 640, 0]),
    "a-tile-of-12-blocks-is-its-own-sub-tile": (12, [
        0, 192, 127, 128, 129, 0, 191, 192, 1, 0]),
}
LARGE = 1e4


@pytest.mark.parametrize("kv_dtype", ["native", "int8"])
@pytest.mark.parametrize("mask", ["maskless", "masked"])
@pytest.mark.parametrize("rows", ["decode-s1", "decode-in-a-mixed-call",
                                  "chunk"])
@pytest.mark.parametrize("case", list(SUB_TILE_ROWS))
def test_rows_end_at_and_around_every_sub_tile_boundary(
    case, rows, mask, kv_dtype
):
    """A row that ends one slot before, on and one slot past each sub-tile's
    edge reads every slot it holds and none it does not, on the short path
    (alone in a call of one position, which under a mask folds its group's rows
    alone, and beside chunk rows) and on the chunk rows' full-width path; what
    the row before left in a buffer's unheld tail, large as it may be, meets a
    probability of exactly 0."""
    max_blocks, valid = SUB_TILE_ROWS[case]
    tile_blocks = paged_attention._blocks_per_tile(16, max_blocks, 2, H, 4)
    sub_blocks = paged_attention._blocks_per_sub(16, tile_blocks)
    assert (tile_blocks, sub_blocks) == ((32, 8) if max_blocks == 40
                                         else (12, 12))
    s = 1 if rows == "decode-s1" else 16
    valid = np.asarray(valid, np.int32)
    new = np.minimum(valid, s if rows == "chunk" else 1)
    # only the chunk rows are past the short path's positions
    assert (rows == "chunk") == bool(
        (new > paged_attention._SHORT_QUERIES).any())
    rng = np.random.default_rng(69)
    q, pk, pv, tab, ctx, new = tiled_case(
        rng, block_size=16, max_blocks=max_blocks, n_kv=2, group=2, s=s,
        ctx=valid - new, new_len=new,
    )
    big = slice(1 + max_blocks, 1 + 2 * max_blocks)   # the second row's blocks
    pk, pv = pk.at[big].multiply(LARGE), pv.at[big].multiply(LARGE)
    q = q.at[1].divide(LARGE)       # its own scores stay of order one
    kwargs, ref_k, ref_v = {}, pk, pv
    if kv_dtype == "int8":
        pk, sk = kv_quantize_int8(pk)
        pv, sv = kv_quantize_int8(pv)
        kwargs = {"scale_k": sk, "scale_v": sv}
        ref_k = pk.astype(jnp.float32) * sk[..., None]
        ref_v = pv.astype(jnp.float32) * sv[..., None]
    chosen = None
    if mask == "masked":
        chosen = rng.random((len(valid), max_blocks * 16)) < 0.5
        # every query keeps its own slot, so no row chose nothing
        chosen |= np.arange(max_blocks * 16) >= (np.asarray(ctx))[:, None]
        chosen = kwargs["chosen"] = jnp.asarray(chosen)
    out = check_rows(q, pk, pv, tab, ctx, new, 2, **kwargs)
    ref = dense_reference(q, ref_k, ref_v, tab, ctx + new, ctx, 2, chosen)
    for row, real in enumerate(np.asarray(new)):
        scale = LARGE if row == 1 else 1.0
        np.testing.assert_allclose(
            np.asarray(out[row, :real]) / scale,
            np.asarray(ref[row, :real]) / scale, atol=1e-5,
            err_msg=f"row {row} holds {valid[row]} slots",
        )
    assert not bool(jnp.any(out[valid == 0]))
