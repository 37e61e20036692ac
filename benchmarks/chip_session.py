"""One serial on-chip measurement session.

Every section runs in its OWN subprocess, and this parent never touches
JAX: the chip belongs to one process at a time, and a fresh process per
section returns all HBM to the backend between sections, so an OOM (often
an *informative* result, e.g. XLA attention at seq 32k, or the XLA full
step duplicating ~9G of state on a 16G v5e) costs exactly one measurement
instead of poisoning every later one.

Sections (labels are stable):
  1. attention micro-bench: flash vs XLA fwd+bwd at the bench shape
  2. flash block-size sweep
  3/4. full train step A/B: flash vs XLA kernel vs flash+fused-norm
       (one arm per process; identical params from the same PRNGKey)
  5. trace capture for benchmarks/analyze_trace.py
  6. micro-batch sweep (4/8/16); winner feeds bench.py's BENCH_MBS
  7. long-context attention sweep, seq 8k/16k/32k (splash vs the ring's
     blockwise kernel vs XLA full attention — XLA OOM near 32k expected)
  8. 1B single-chip attempt (BASELINE #3 shape, every-layer remat, mbs 1)
  9. decode throughput (batched KV-cache generate)

Usage: python benchmarks/chip_session.py             # every section
       python benchmarks/chip_session.py <section>   # one section, in-process

CHIP_SESSION_SMOKE=1 shrinks every arm to CPU-rehearsable shapes so the
whole session's plumbing — including the subprocess fan-out — can be
validated without the chip under JAX_PLATFORMS=cpu (numbers are then
meaningless; sections that need the TPU print FAIL and move on).
"""
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
os.chdir(REPO)

SMOKE = bool(os.environ.get("CHIP_SESSION_SMOKE"))
# (seq, hidden, layers, mbs) of the full-step arms; long-context seqs;
# 1b-arm layer count
if SMOKE:
    STEP_SHAPE, LONG_SEQS, LAYERS_1B = (256, 256, 2, 2), (512, 1024), 3
    MBS_SWEEP = (2,)
else:
    STEP_SHAPE, LONG_SEQS, LAYERS_1B = (2048, 2048, 8, 4), (8192, 16384, 32768), 20
    MBS_SWEEP = (4, 8, 16)
SEQ, HIDDEN, LAYERS, MBS = STEP_SHAPE


# ------------------------------------------------------------ child plumbing
def _init_backend():
    """First device contact of a section's process; every section shares
    the one placeable compile cache (scaling_tpu/compile_cache.py)."""
    import jax

    from scaling_tpu.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.devices()


def _build_step(mbs, layers=None, remat=False, kernel="flash_attention",
                norm=None):
    """Fresh model+optimizer+jitted step at the bench shape.

    Each section process builds its own copy from PRNGKey(0), so A/B arms
    in different processes still measure identical parameter values.
    """
    import jax
    import numpy as np

    import bench

    os.environ["BENCH_KERNEL"] = kernel
    if norm is None:
        os.environ.pop("BENCH_NORM", None)
    else:
        os.environ["BENCH_NORM"] = norm
    key = jax.random.PRNGKey(0)
    cfg, _, module, optimizer = bench.build(
        SEQ, mbs, HIDDEN, layers if layers is not None else LAYERS, remat=remat
    )
    step = module.build_train_step(optimizer, bench.loss_function, donate=False)
    params = module.shard_params(module.init_params(key))
    opt_state = optimizer.init_state(params)
    batch = module.shard_batch(
        bench.synth_batch(np.random.default_rng(0), mbs, SEQ,
                          cfg.transformer_architecture.vocab_size, 1),
        stacked=True,
    )

    def f(pp, ss):
        _, _, loss, _, _ = step(pp, ss, batch, key)
        return loss

    return cfg, f, params, opt_state


# ---------------------------------------------------------------- sections
def sec_attn():
    from benchmarks import attn_bench

    q, k, v, seg = attn_bench.make_qkv()
    for name, fn in (("flash", attn_bench.flash), ("xla", attn_bench.xla_attn)):
        try:
            t = attn_bench.timeit(attn_bench.fwd_bwd(fn), q, k, v, seg)
            print(f"1. attn {name} f+b: {t:8.2f} ms", flush=True)
        except Exception as e:
            print(f"1. attn {name} f+b: FAIL {type(e).__name__}", flush=True)


def sec_blocks():
    from benchmarks import attn_bench

    q, k, v, seg = attn_bench.make_qkv()
    for bq, bkv in ((512, 512), (1024, 1024), (2048, 1024), (1024, 2048)):
        os.environ["SCALING_TPU_FLASH_BLOCK_Q"] = str(bq)
        os.environ["SCALING_TPU_FLASH_BLOCK_KV"] = str(bkv)
        try:
            t = attn_bench.timeit(attn_bench.fwd_bwd(attn_bench.flash),
                                  q, k, v, seg)
            print(f"2. flash blocks q={bq} kv={bkv}: {t:8.2f} ms", flush=True)
        except Exception as e:
            print(f"2. flash blocks q={bq} kv={bkv}: FAIL {type(e).__name__}",
                  flush=True)


def sec_step(label, kernel, norm=None):
    from benchmarks import attn_bench

    try:
        _, f, params, opt_state = _build_step(MBS, kernel=kernel, norm=norm)
        t = attn_bench.timeit(f, params, opt_state, iters=3)
        print(f"3/4. step {label}: {t:8.1f} ms", flush=True)
    except Exception as e:
        print(f"3/4. step {label}: FAIL {type(e).__name__}: {e}", flush=True)


def sec_trace():
    import jax

    outdir = os.path.join(REPO, "chiprun_out", "bench_trace")
    _tracing = False
    try:
        _, f, params, opt_state = _build_step(MBS)
        loss = f(params, opt_state)  # compile OUTSIDE the trace window
        jax.block_until_ready(loss)
        jax.profiler.start_trace(outdir)
        _tracing = True
        for _ in range(2):
            loss = f(params, opt_state)
        jax.block_until_ready(loss)
        jax.profiler.stop_trace()
        _tracing = False
        print(
            f"5. trace written to {outdir}; analyze with "
            f"python benchmarks/analyze_trace.py {outdir}",
            flush=True,
        )
    except Exception as e:
        print(f"5. trace capture: FAIL {type(e).__name__}: {e}", flush=True)
    finally:
        if _tracing:
            # a failure mid-trace must not leave the profiler running
            try:
                jax.profiler.stop_trace()
            except Exception:
                pass


def sec_mbs(mbs):
    # bigger per-step batch amortizes per-step overheads and widens MXU
    # tiles; memory-bound upward (fp32 masters dominate). Winner feeds
    # bench.py's BENCH_MBS. BENCH_NORM stays cleared so the sweep measures
    # the exact configuration bench.py runs.
    from benchmarks import attn_bench

    try:
        _, f, params, opt_state = _build_step(mbs)
        t = attn_bench.timeit(f, params, opt_state, iters=3)
        print(f"6. step mbs={mbs}: {t:8.1f} ms "
              f"({mbs * SEQ / t * 1000:.0f} tok/s)", flush=True)
    except Exception as e:
        print(f"6. step mbs={mbs}: FAIL {type(e).__name__}: {e}", flush=True)


def sec_long(s_long):
    # The no-O(s^2) story at wall-clock (VERDICT r3 #8): splash flash kernel
    # vs the ring's blockwise kernel (cp=1: one ring step IS the blockwise
    # inner loop with its chunked score tiles) vs XLA full attention,
    # fwd+bwd. XLA is EXPECTED to fail near 32k (the 16*s^2 score tensor
    # alone is ~34G) — that failure is the point of the comparison, and the
    # per-section process means it cannot poison the other arms.
    import jax
    import jax.numpy as jnp

    from benchmarks import attn_bench
    from scaling_tpu.ops.ring_attention import ring_attention
    from scaling_tpu.topology import Topology, TopologyConfig

    _topo1 = Topology(TopologyConfig.from_dict({
        "model_parallel_size": 1, "pipe_parallel_size": 1,
        "data_parallel_size": 1, "context_parallel_size": 1,
        "micro_batch_size": 1, "gradient_accumulation_steps": 1,
    }))

    def _ring_op(q, k, v, seg):
        return ring_attention(q, k, v, seg, _topo1.mesh, causal=True,
                              sm_scale=attn_bench.SCALE)

    kq = jax.random.PRNGKey(1)
    q_l = jax.random.normal(kq, (1, s_long, 16, 128), jnp.bfloat16)
    k_l = jax.random.normal(kq, (1, s_long, 4, 128), jnp.bfloat16)
    v_l = jax.random.normal(kq, (1, s_long, 4, 128), jnp.bfloat16)
    seg_l = jnp.zeros((1, s_long), jnp.int32)
    for name, op in (("splash", attn_bench.flash), ("ring-blockwise", _ring_op),
                     ("xla", attn_bench.xla_long)):
        try:
            t = attn_bench.timeit(attn_bench.fwd_bwd(op), q_l, k_l, v_l, seg_l,
                                  iters=3)
            print(f"7. seq={s_long} {name}: {t:8.1f} ms", flush=True)
        except Exception as e:
            print(f"7. seq={s_long} {name}: FAIL {type(e).__name__}", flush=True)


def sec_1b():
    # BASELINE #3's shape with every-layer remat at mbs 1 (bench.py's
    # BENCH_MODEL=1b arm). fp32 master+moments + bf16 params are 15.3G of
    # the 16G v5e, so an OOM here is a legitimate, informative outcome.
    from benchmarks import attn_bench

    try:
        _, f, params, opt_state = _build_step(1, layers=LAYERS_1B, remat=True)
        t = attn_bench.timeit(f, params, opt_state, iters=3)
        print(f"8. 1b step mbs=1: {t:8.1f} ms ({SEQ / t * 1000:.0f} tok/s)",
              flush=True)
    except Exception as e:
        print(f"8. 1b step: FAIL {type(e).__name__}: {e}", flush=True)


def sec_decode():
    # Batched KV-cache generate at the bench model size: decode is
    # HBM-bandwidth-bound (each new token re-reads the weights), so this
    # number tracks a different ceiling than the training MFU.
    try:
        import time as _time

        import jax
        import numpy as np

        import bench
        from scaling_tpu.models.transformer.inference import (
            TransformerInferenceModule,
        )

        os.environ["BENCH_KERNEL"] = "flash_attention"
        os.environ.pop("BENCH_NORM", None)  # measure the bench-default norm
        cfg_i, _, mod_i, _ = bench.build(SEQ, 1, HIDDEN, LAYERS)
        p_i = mod_i.shard_params(mod_i.init_params(jax.random.PRNGKey(0)))
        im = TransformerInferenceModule(cfg_i, mod_i, p_i)
        gen_b, prompt_len = 8, 128
        gen_tokens = 8 if SMOKE else 128
        prompt = np.random.default_rng(0).integers(
            1, 1000, size=(gen_b, prompt_len)
        )
        # warm-up at the MEASURED length: the fused decode loop's compile
        # is keyed on the step count (and prefill on cache length), so a
        # shorter warm-up would leave the real compile inside the window
        im.generate(prompt, max_tokens=gen_tokens)
        t0 = _time.perf_counter()
        im.generate(prompt, max_tokens=gen_tokens)
        dt = _time.perf_counter() - t0
        print(f"9. decode: {gen_b * gen_tokens / dt:8.0f} tok/s "
              f"(batch {gen_b}, {gen_tokens} new tokens, cached)", flush=True)
    except Exception as e:
        print(f"9. decode: FAIL {type(e).__name__}: {e}", flush=True)


def _sections():
    """(name, thunk, timeout_s) in run order. Timeouts bound a wedged
    section instead of letting one hang eat the session."""
    secs = [
        ("attn", sec_attn, 900),
        ("blocks", sec_blocks, 900),
        ("step-flash", lambda: sec_step("flash", "flash_attention"), 900),
        ("step-xla", lambda: sec_step("xla", "torch"), 900),
        ("step-fusednorm",
         lambda: sec_step("flash+fusednorm", "flash_attention", norm="fused"),
         900),
        ("trace", sec_trace, 900),
    ]
    secs += [(f"mbs-{m}", (lambda m=m: sec_mbs(m)), 900) for m in MBS_SWEEP]
    secs += [(f"long-{s}", (lambda s=s: sec_long(s)), 1200) for s in LONG_SEQS]
    secs += [("1b", sec_1b, 1500), ("decode", sec_decode, 900)]
    return secs


def run_section(name):
    for n, thunk, _ in _sections():
        if n == name:
            _init_backend()
            thunk()
            return
    sys.exit(f"unknown section {name!r}")


def main():
    """Dispatcher: one subprocess per section, output streamed to this
    stdout; crash/timeout/OOM in a section costs only that section."""
    import subprocess

    for name, _, timeout_s in _sections():
        try:
            p = subprocess.run(
                [sys.executable, os.path.abspath(__file__), name],
                timeout=timeout_s,
            )
            if p.returncode != 0:
                print(f"-- section {name}: exited rc={p.returncode}",
                      flush=True)
        except subprocess.TimeoutExpired:
            print(f"-- section {name}: FAIL timeout after {timeout_s}s",
                  flush=True)
    print("session complete", flush=True)


if __name__ == "__main__":
    if len(sys.argv) > 1:
        run_section(sys.argv[1])
    else:
        # child processes re-read these; the parent never touches jax
        main()
