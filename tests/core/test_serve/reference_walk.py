"""What the serving files of the ``layer_pattern`` stacks share: the greedy
walk by a plain reference, and one sequence's logits through the paged pool.

Both are built so that a program is compiled ONCE a shape. The references'
blocks are jitted a shape, and a served pass that runs eagerly compiles and
dispatches its loops and branches one by one: a walk that grows a sequence a
token at a time, or a pass a call, otherwise pays a compile a length.
"""

import jax
import jax.numpy as jnp
import numpy as np

from scaling_tpu.serve.kvcache import build_layer_views, state_from_views


def greedy_by_reference(logits_of, requests, new_tokens, least_margin=1e-3):
    """Each prompt alone, greedy, ``new_tokens`` tokens by ``logits_of(tokens)
    -> (len(tokens), vocab)``, a plain reference's full forward (no cache, no
    pool, no batching, nothing of the program). Every call has ONE length, the
    longest sequence's, padded with token 0 behind what is there: the forward
    is causal, so a position's logits are what they are whatever follows it.

    Greedy tokens compare exactly only where no near-tie can break the other
    way under another order of summation (float32: ~1e-5): every token leads
    its runner-up by more than ``least_margin``."""
    length = max(map(len, requests)) + new_tokens
    want, margins = [], []
    for p in requests:
        tokens = list(p)
        for _ in range(new_tokens):
            padded = tokens + [0] * (length - len(tokens))
            logits = np.asarray(logits_of(padded))[len(tokens) - 1]
            top2 = np.sort(logits)[-2:]
            margins.append(float(top2[1] - top2[0]))
            tokens.append(int(logits.argmax()))
        want.append(tokens[len(p):])
    assert min(margins) > least_margin
    return want


def paged_logits(inf, engine, tokens, chunk, paged_kernel):
    """Logits of every position of ONE sequence served through ``engine``'s
    pool: ``chunk`` positions a call, the last four or more one by one (decode
    rows); ``paged_walk`` with that schedule."""
    sizes = [chunk] * ((len(tokens) - 4) // chunk)
    sizes += [1] * (len(tokens) - sum(sizes))
    return paged_walk(inf, engine, tokens, sizes, paged_kernel)[0]


def paged_walk(inf, engine, tokens, sizes, paged_kernel, state=None):
    """``(logits of every position, the state after)`` of ONE sequence served
    through ``engine``'s state (an engine of one slot whose row owns blocks 1,
    2, ...): ``sizes`` positions a call from position 0 on, row-major batches
    of one row, the row's lines written by the calls before; one jitted pass a
    call shape, traced anew on every call of this function (a test that alters
    a part of the model sees it traced). ``state``: what an earlier walk left
    (a reused slot), the engine's fresh state by default."""
    block_size = engine.config.block_size
    blocks = engine.config.max_blocks_per_seq
    assert sum(sizes) == len(tokens) <= blocks * block_size
    table = jnp.arange(1, blocks + 1, dtype=jnp.int32)[None]

    @jax.jit
    def step(params, state, ids, done):
        n = ids.shape[1]
        pos = done + jnp.arange(n, dtype=jnp.int32)[None]
        views = build_layer_views(
            state, table, done[None], jnp.asarray([n], jnp.int32),
            kinds=engine.pools.kinds)
        logits, new_views = inf._run_layers(
            params, inf._make_batch(ids, pos), views, None,
            paged_kernel=paged_kernel)
        return logits[0], state_from_views(new_views)

    state = engine._pool_state() if state is None else state
    out, done = [], 0
    for n in sizes:
        ids = jnp.asarray(tokens[done:done + n], jnp.int32)[None]
        logits, state = step(inf.params, state, ids, jnp.int32(done))
        out.append(np.asarray(logits))
        done += n
    return np.concatenate(out), state
