"""Which form attends over a sparse grouped-query layer's chosen lines, by row
kind, at ``serve-keye30b-longctx-burst``'s shapes (PR 61; chip only, ``--smoke``
with ``JAX_PLATFORMS=cpu`` rehearses it at a toy size):

    python benchmarks/sparse_gqa_forms.py [--smoke] [--choice]

For one 320-query CHUNK row and for a pass of four ONE-token rows, at two
visible lengths: the index scores, the choice as a threshold (bisection) and by
``jax.lax.top_k``, then attention over the choice in three forms. (a) STREAMED,
every visible line multiplied under the mask: the chunk row through
``nn/masked_gqa_attention.py`` with its window gathered through the table
(WHAT SERVES a chunk row), the one-token rows folded tile by tile in plain XLA
with their tiles gathered through the table (what served them until PR 64;
kept here, and only here, as the yardstick). (b) GATHERED, each query's
``index_topk`` single K and V lines fetched through the table and attended
densely (never served: it needs the choice as indices). (c) PAGED, the
one-token rows only: ``nn/paged_attention.py``'s kernel with the choice as its
mask operand, each row's own blocks by DMA through its whole table up to its
own length (WHAT SERVES the one-token rows since PR 64). Milliseconds on the
host's clock around ``block_until_ready`` (best of five after a warm call; the
window is the visible length), one JSON line a measurement.

The choice's parts apart (PR 62): the 33 passes of the bisection alone; the
choice as it serves (random scores: no query has more ties than room, the one
compare); the same call beside ONE query whose scores all tie (the whole call
fills ties by position: the prefix sum); and the form before PR 62, which
filled in every call. Measured only, never wired into the program: the
threshold found two and three bits of the float's order at a time (3 or 7
thresholds compared in one read of the bits, 16 or 11 reads for the 33).
``--choice`` stops after the choice's lines.
"""

import json
import sys
import time

import jax
import jax.numpy as jnp


def best_ms(fn, *args, reps=5):
    jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(reps):
        t = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t)
    return 1e3 * best


def digits_threshold(bits, k: int, width: int):
    """``sparse_rows.kth_largest``'s value, found ``width`` bits of the order a
    read: the ``2 ** width - 1`` thresholds that differ in the next digit are
    compared in one pass over ``bits`` and the largest that ``k`` scores still
    reach is kept. 16 reads at 2 bits, 11 at 3."""
    u = jax.lax.bitcast_convert_type(bits, jnp.uint32) ^ jnp.uint32(1 << 31)
    found = jnp.zeros(bits.shape[:-1], jnp.uint32)
    top = 32
    while top > 0:
        step = top % width or width
        top -= step
        digits = jnp.arange(1, 1 << step, dtype=jnp.uint32) << top
        tried = found[..., None] | digits
        enough = jnp.sum(u[..., None, :] >= tried[..., None], axis=-1) >= k
        found = found | (jnp.sum(enough, axis=-1).astype(jnp.uint32) << top)
    return jax.lax.bitcast_convert_type(found ^ jnp.uint32(1 << 31), jnp.int32)


def main():
    smoke, choice_only = "--smoke" in sys.argv, "--choice" in sys.argv
    from scaling_tpu.nn.masked_gqa_attention import masked_gqa_attention
    from scaling_tpu.nn.paged_attention import (
        paged_decode_attention, paged_kernel_interpret,
    )
    from scaling_tpu.nn.sparse_rows import (
        choose_lines, index_scores, kth_largest, ordered_bits,
        threshold_choice, tile_of,
    )

    if not smoke and jax.default_backend() != "tpu":
        sys.exit("sparse_gqa_forms.py measures a TPU; JAX found "
                 f"{jax.default_backend()} (--smoke rehearses it)")
    interpret = paged_kernel_interpret()
    if smoke:
        n, n_kv, h, ih, idim, topk, bs, max_blocks, chunk = 8, 2, 32, 4, 16, 16, 4, 64, 8
        lengths, dtype = (100, 200), jnp.float32
    else:
        n, n_kv, h, ih, idim, topk, bs, max_blocks, chunk = 32, 4, 128, 16, 64, 2048, 16, 4096, 320
        lengths, dtype = (16384, 49152), jnp.bfloat16
    group = n // n_kv
    blocks = 4 * max_blocks + 1
    keys = jax.random.split(jax.random.PRNGKey(0), 8)
    pool_k = jax.random.normal(keys[0], (blocks, bs, n_kv, h), dtype)
    pool_v = jax.random.normal(keys[1], (blocks, bs, n_kv, h), dtype)
    pool_i = jax.random.normal(keys[2], (blocks, bs, idim), dtype)
    # four rows' tables, scattered through the pool
    tables = jax.random.permutation(keys[3], blocks - 1)[:4 * max_blocks].reshape(
        4, max_blocks).astype(jnp.int32) + 1
    scale = h ** -0.5

    def say(**fields):
        print(json.dumps({"device": jax.devices()[0].device_kind, **fields}), flush=True)

    for seen in lengths:
        for rows, p in ((1, chunk), (4, 1)):
            kind = "chunk" if p > 1 else "one-token x4"
            q = jax.random.normal(keys[4], (rows, p, n, h), dtype)
            q_i = jax.random.normal(keys[5], (rows, p, ih, idim), dtype)
            w = jax.random.normal(keys[6], (rows, p, ih), jnp.float32)
            # the smallest window that holds what is visible
            window = seen
            table = tables[:rows, :seen // bs]
            slots = jnp.arange(window, dtype=jnp.int32)
            at = seen - p + jnp.arange(p, dtype=jnp.int32)
            visible = jnp.broadcast_to(
                (slots < seen) & (slots <= at[:, None]), (rows, p, window))

            @jax.jit
            def scores_of(q_i, w):
                keys_i = pool_i[table].reshape(rows, window, idim)
                return index_scores(q_i, keys_i, w)

            scores = scores_of(q_i, w)
            say(kind=kind, seen=seen, what="index scores (the visible keys gathered)",
                ms=best_ms(scores_of, q_i, w))
            by_threshold = jax.jit(lambda s: threshold_choice(s, visible, topk))
            say(kind=kind, seen=seen, what="choice: threshold by bisection",
                ms=best_ms(by_threshold, scores))
            bits_of = lambda s: ordered_bits(jnp.where(visible, s, -jnp.inf))
            passes = jax.jit(lambda s: kth_largest(bits_of(s), topk))
            say(kind=kind, seen=seen, what="choice: the 33 passes alone",
                ms=best_ms(passes, scores))
            # one query of the call scores every line alike
            tied = scores.at[0, 0].set(0.0)
            say(kind=kind, seen=seen,
                what="choice: a call that fills ties by position",
                ms=best_ms(by_threshold, tied))

            @jax.jit
            def always_filling(s):      # the form before PR 62
                bits = bits_of(s)
                low = kth_largest(bits, topk)[..., None]
                above, equal = bits > low, bits == low
                room = topk - jnp.sum(above, axis=-1, keepdims=True)
                return visible & (above | (
                    equal & (jnp.cumsum(equal, axis=-1) <= room)))

            say(kind=kind, seen=seen,
                what="choice: the fill in every call (before PR 62)",
                ms=best_ms(always_filling, scores),
                same_set=bool(jnp.all(always_filling(tied) == by_threshold(tied))
                              & jnp.all(always_filling(scores) == by_threshold(scores))))
            for width in (2, 3):
                by_digits = jax.jit(
                    lambda s: digits_threshold(bits_of(s), topk, width))
                say(kind=kind, seen=seen,
                    what=f"threshold alone, {width} bits a read "
                         f"({-(-32 // width)} reads; not wired in)",
                    ms=best_ms(by_digits, scores),
                    same_threshold=bool(jnp.all(by_digits(tied) == passes(tied))
                                        & jnp.all(by_digits(scores) == passes(scores))))
            by_top_k = jax.jit(lambda s: choose_lines(s, visible, topk)[0])
            say(kind=kind, seen=seen, what="choice: jax.lax.top_k",
                ms=best_ms(by_top_k, scores))
            if choice_only:
                continue
            chosen, idx = by_threshold(scores), by_top_k(scores)

            # (b) GATHERED: each query's chosen single lines through the table
            @jax.jit
            def gathered(q, idx):
                flat = (jnp.take_along_axis(
                    table[:, None, :], idx // bs, axis=2) * bs + idx % bs)
                k = pool_k.reshape(-1, n_kv, h)[flat]      # (rows, p, topk, n_kv, h)
                v = pool_v.reshape(-1, n_kv, h)[flat]
                held = jnp.arange(idx.shape[-1]) < jnp.minimum(
                    at + 1, idx.shape[-1])[:, None]
                s = jnp.einsum("rpgjh,rpkgh->rpgjk",
                               q.reshape(rows, p, n_kv, group, h), k,
                               preferred_element_type=jnp.float32) * scale
                s = jnp.where(held[None, :, None, None, :], s, -jnp.inf)
                e = jax.nn.softmax(s, axis=-1)
                return jnp.einsum("rpgjk,rpkgh->rpgjh", e.astype(v.dtype), v,
                                  preferred_element_type=jnp.float32
                                  ).astype(q.dtype).reshape(rows, p, n, h)

            say(kind=kind, seen=seen, what="attend: GATHERED chosen lines",
                ms=best_ms(gathered, q, idx))

            # (a) STREAMED under the mask
            if p > 1:
                @jax.jit
                def streamed(q, chosen):
                    return masked_gqa_attention(
                        q[0], pool_k[table[0]].reshape(window, n_kv, h),
                        pool_v[table[0]].reshape(window, n_kv, h), chosen[0],
                        jnp.int32(seen), sm_scale=scale, interpret=interpret)
            else:
                tile_blocks = min(seen // bs, 2048 // bs)
                tile = tile_blocks * bs

                @jax.jit
                def streamed(q, chosen):
                    qg = q.reshape(rows, n_kv, group, h)

                    def fold(t, carry):
                        top, total, acc = carry
                        k = tile_of(pool_k, table, t, tile_blocks).reshape(
                            rows, tile, n_kv, h)
                        v = tile_of(pool_v, table, t, tile_blocks).reshape(
                            rows, tile, n_kv, h)
                        s = jnp.einsum("rgjh,rkgh->rgjk", qg, k,
                                       preferred_element_type=jnp.float32)
                        mask = jax.lax.dynamic_slice_in_dim(
                            chosen[:, 0], t * tile, tile, 1)
                        s = jnp.where(mask[:, None, None, :], s * scale, -jnp.inf)
                        new_top = jnp.maximum(top, s.max(axis=-1))
                        safe = jnp.where(new_top == -jnp.inf, 0.0, new_top)
                        e = jnp.exp(s - safe[..., None])
                        alpha = jnp.exp(top - safe)
                        acc = alpha[..., None] * acc + jnp.einsum(
                            "rgjk,rkgh->rgjh", e.astype(v.dtype), v,
                            preferred_element_type=jnp.float32)
                        return new_top, alpha * total + e.sum(axis=-1), acc

                    _, total, acc = jax.lax.fori_loop(
                        0, -(-seen // tile), fold, (
                            jnp.full((rows, n_kv, group), -jnp.inf, jnp.float32),
                            jnp.zeros((rows, n_kv, group), jnp.float32),
                            jnp.zeros((rows, n_kv, group, h), jnp.float32)))
                    return (acc / total[..., None]).astype(q.dtype).reshape(
                        rows, 1, n, h)

            say(kind=kind, seen=seen, what="attend: STREAMED under the mask",
                ms=best_ms(streamed, q, chosen))
            a, b = streamed(q, chosen), gathered(q, idx)
            a = a if a.ndim == 4 else a[None]
            say(kind=kind, seen=seen, what="largest difference of the two forms",
                value=float(jnp.max(jnp.abs(
                    a.astype(jnp.float32) - b.astype(jnp.float32)))))
            if p > 1:
                continue

            # (c) PAGED: the rows' own blocks through the paged kernel, the
            # choice its mask operand; the rows' WHOLE tables, as the walk
            # hands them over (the kernel ends at a row's own length)
            valid = jnp.full((rows,), seen, jnp.int32)

            @jax.jit
            def paged(q, chosen, pool_k, pool_v):
                return paged_decode_attention(
                    q, pool_k, pool_v, tables[:rows], valid, valid - 1,
                    sm_scale=scale, num_repeat_kv=group, chosen=chosen[:, 0],
                    interpret=interpret)

            say(kind=kind, seen=seen, what="attend: PAGED kernel under the mask",
                ms=best_ms(paged, q, chosen, pool_k, pool_v))
            say(kind=kind, seen=seen,
                what="largest difference of PAGED and STREAMED",
                value=float(jnp.max(jnp.abs(
                    paged(q, chosen, pool_k, pool_v).astype(jnp.float32)
                    - a.astype(jnp.float32)))))


if __name__ == "__main__":
    sys.path.insert(0, ".")
    main()
