"""Flash vs XLA attention fwd+bwd at the bench shape (one chip).

Importable by chip_session.py; run directly for just the micro-bench:
    cd /root/repo && python benchmarks/attn_bench.py
"""

import time

import jax
import jax.numpy as jnp

from scaling_tpu.ops.flash_attention import flash_attention_fused

B, S, N, NKV, D = 4, 2048, 16, 4, 128
SCALE = D**-0.5


def timeit(fn, *args, iters=10):
    """Median of 3 windows, each ending in block_until_ready; milliseconds
    per call."""
    out = fn(*args)
    jax.block_until_ready(out)
    times = []
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(*args)
        jax.block_until_ready(out)
        times.append((time.perf_counter() - t0) / iters)
    return sorted(times)[1] * 1e3  # ms


def make_qkv(key=None):
    key = key if key is not None else jax.random.PRNGKey(0)
    q = jax.random.normal(key, (B, S, N, D), jnp.bfloat16)
    k = jax.random.normal(key, (B, S, NKV, D), jnp.bfloat16)
    v = jax.random.normal(key, (B, S, NKV, D), jnp.bfloat16)
    seg = jnp.zeros((B, S), jnp.int32)
    return q, k, v, seg


def flash(q, k, v, seg):
    return flash_attention_fused(q, k, v, segment_ids=seg, sm_scale=SCALE)


def xla_attn(q, k, v, seg):
    del seg  # single doc: the causal mask below covers it
    rep = N // NKV
    kk = jnp.repeat(k, rep, axis=2)
    vv = jnp.repeat(v, rep, axis=2)
    logits = jnp.einsum("bsnd,btnd->bnst", q, kk) * SCALE
    mask = jnp.tril(jnp.ones((S, S), bool))
    logits = jnp.where(mask[None, None], logits.astype(jnp.float32), -1e9)
    p = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bnst,btnd->bsnd", p, vv)


def xla_long(q, k, v, seg):
    """xla_attn with shapes derived from the inputs (the long-context sweep
    feeds arbitrary seq lengths; the fixed-S version above keeps the exact
    program the original A/B measured)."""
    del seg
    b, s, n, d = q.shape
    rep = n // k.shape[2]
    kk = jnp.repeat(k, rep, axis=2)
    vv = jnp.repeat(v, rep, axis=2)
    logits = jnp.einsum("bsnd,btnd->bnst", q, kk) * (d**-0.5)
    mask = jnp.tril(jnp.ones((s, s), bool))
    logits = jnp.where(mask[None, None], logits.astype(jnp.float32), -1e9)
    p = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bnst,btnd->bsnd", p, vv)


def fwd_bwd(fn):
    """fwd+bwd closure: grads of sum(fn) wrt q/k/v, jitted."""
    return jax.jit(
        jax.grad(
            lambda q, k, v, seg: fn(q, k, v, seg).astype(jnp.float32).sum(),
            argnums=(0, 1, 2),
        )
    )


def main():
    q, k, v, seg = make_qkv()
    print(f"flash fwd : {timeit(jax.jit(flash), q, k, v, seg):8.2f} ms")
    print(f"xla   fwd : {timeit(jax.jit(xla_attn), q, k, v, seg):8.2f} ms")
    print(f"flash f+b : {timeit(fwd_bwd(flash), q, k, v, seg):8.2f} ms")
    print(f"xla   f+b : {timeit(fwd_bwd(xla_attn), q, k, v, seg):8.2f} ms")


if __name__ == "__main__":
    main()
