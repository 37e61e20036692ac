"""First proof that the tree starts on the chip: train -> checkpoint -> serve.

``python chip_smoke.py`` needs one TPU and drives the main path once, through
the entry points a user calls, at the full width of the dense 0.5B decoder
(hidden 2048, 16 query / 4 KV heads x 128, SwiGLU x2.75, RMSNorm, rotary,
vocab 32768, sequence 2048, bf16 compute with fp32 masters; 8 layers):

- ``kernels``: each Pallas kernel of the two paths against its ``jax.numpy``
  reference at the real head shapes (splash fwd+bwd; paged decode at s=1,
  one prefill chunk and s=k+1, native and int8 pools).
- ``train``: ``scaling_tpu.models.transformer.train.main(config)`` on a token
  memory map made from ``--seed``, twenty steps and one checkpoint.
- ``serve``: that checkpoint through ``TransformerInferenceModule
  .from_checkpoint`` and the real ``ServeEngine`` (its defaults; the KV pool
  sized for 8 slots x 4k context), checked against ``generate``.

``python chip_smoke.py --chips 4`` runs ONLY the sharded-training check: the
same model at TP=2 x DP=2 with ZeRO-1 and sequence parallelism, and the same
steps at mp=dp=1 on one of the four chips at the same global batch.

The chip belongs to one process at a time and the trainer's ~9 GB of state
does not fit beside the engine, so this parent never imports JAX: every
phase is a child process that takes the chip, prints its findings, writes
its verdict to ``.scratch/chip_smoke/<phase>.json`` and exits. Any phase
that fails, and any run without a TPU, is a non-zero exit with no result
line. The last line of a passing run is exactly
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.

``--rehearse`` walks the same control flow on the CPU at a toy size with
the kernels interpreted (on-chip-measurement guide, rehearsal 1). It never
prints ``"ok": true``: a rehearsal is not a chip run.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent
WORK = REPO / ".scratch" / "chip_smoke"

# the 0.5B dense decoder bench.py measures, and the toy the rehearsal walks
FULL = dict(
    hidden=2048, layers=8, heads=16, kv_heads=4, vocab=32768, seq=2048,
    micro_batch=4, steps=20, sharded_steps=8,
    slots=8, context=4096, requests=12, prompt_len=(128, 1024),
    output_len=(32, 128),
)
TOY = dict(
    hidden=512, layers=2, heads=4, kv_heads=2, vocab=512, seq=256,
    micro_batch=4, steps=6, sharded_steps=3,
    slots=4, context=512, requests=4, prompt_len=(16, 96),
    output_len=(4, 12),
)
BLOCK_SIZE = 16      # EngineConfig default
PREFILL_CHUNK = 32   # EngineConfig default
TAIL_ROWS = 5        # the tokens a prompt's last chunk may be left with

# bf16 keeps 8 significant bits: a value of magnitude m is rounded by up to
# m * 2**-9. The splash kernel rounds the probabilities to bf16 before the
# second matmul and the reference does not, and the backward chains three
# such matmuls, so outputs and gradients are held to 2% of the reference's
# largest magnitude (~5 roundings), not to float32 agreement.
SPLASH_RTOL = 2e-2
# the paged kernel multiplies in the pool's dtype (bf16, or int8 unpacked to
# bf16 with the f32 scales on the products) and accumulates in float32; the
# reference computes in float32 throughout. They differ by the probabilities'
# rounding to bf16 before PV (2**-9 relative each, averaging down over the
# context), the summation order over tiles (online softmax) and the final
# cast of the output to bf16 (2**-9 relative): measured 2.6e-3 of the largest
# magnitude on the chip (PERF.md, PR 26), held to 1e-2.
PAGED_RTOL = 1e-2
# logits at the 0.5B width reach magnitude ~16, where bf16's step is 2**-4;
# the engine's paged path and generate's dense cache sum in different orders,
# so two logits within two steps (0.125) are a tie no path is bound to break
# the same way. Tokens must agree wherever the reference's margin is larger.
LOGIT_TOL = 0.125
# TP=2 x DP=2 and one chip compute the same bf16 step with different
# reduction orders (partial products summed across two chips, gradients
# across two replicas); at a loss of ~10 the per-step values are held to
# 0.05 (0.5%) over the first few steps, before the trajectories part.
SHARDED_LOSS_TOL = 0.05


# ----------------------------------------------------------------- parent
def run_phase(name: str, args, extra_env=None) -> dict:
    """One child owns the chip for one phase; its verdict comes back in
    ``<WORK>/<name>.json``. A failed child ends the run with its code."""
    cmd = [sys.executable, str(Path(__file__).resolve()), "--phase", name,
           "--seed", str(args.seed)]
    if args.rehearse:
        cmd.append("--rehearse")
    sys.stdout.flush()
    t0 = time.monotonic()
    try:
        proc = subprocess.run(
            cmd, cwd=REPO, env={**os.environ, **(extra_env or {})},
            timeout=900,
        )
    except subprocess.TimeoutExpired:
        # subprocess.run has already killed and reaped the child
        sys.exit(f"chip_smoke: phase {name} exceeded 900 s")
    if proc.returncode != 0:
        print(f"chip_smoke: phase {name} failed (exit {proc.returncode})",
              file=sys.stderr)
        sys.exit(proc.returncode if proc.returncode > 0 else 1)
    verdict = json.loads((WORK / f"{name}.json").read_text())
    print(f"[{name}] passed in {time.monotonic() - t0:.0f} s", flush=True)
    return verdict


def parent(args) -> int:
    shutil.rmtree(WORK, ignore_errors=True)
    WORK.mkdir(parents=True)
    if args.chips == 4:
        phases = ["train_sharded", "train_single"]
    else:
        phases = ["kernels", "train", "serve"]
    verdicts = {}
    for name in phases:
        env = None
        if args.rehearse and args.chips == 4:
            env = {"XLA_FLAGS": (os.environ.get("XLA_FLAGS", "")
                                 + " --xla_force_host_platform_device_count=4"
                                 ).strip()}
        verdicts[name] = run_phase(name, args, env)
    if args.chips == 4:
        compare_sharded(verdicts["train_sharded"], verdicts["train_single"])
    devices = {json.dumps(v["device"], sort_keys=True)
               for v in verdicts.values()}
    if len(devices) != 1:
        sys.exit(f"chip_smoke: phases disagree on the device: {devices}")
    device = next(iter(verdicts.values()))["device"]
    if args.rehearse:
        print(json.dumps({"rehearsal": True, "device": device}))
        return 0
    if device["platform"] != "tpu" or device["count"] != args.chips:
        sys.exit(f"chip_smoke: expected {args.chips} TPU chip(s), ran on "
                 f"{device}")
    print(json.dumps({"ok": True, "device": device}))
    return 0


def compare_sharded(sharded: dict, single: dict) -> None:
    a, b = sharded["losses"], single["losses"]
    if len(a) != len(b):
        sys.exit(f"chip_smoke: {len(a)} sharded steps vs {len(b)} single")
    worst = max(abs(x - y) for x, y in zip(a, b))
    print(f"[sharded] per-step loss, TP2xDP2+ZeRO-1+SP vs one chip "
          f"(tolerance {SHARDED_LOSS_TOL}):")
    for i, (x, y) in enumerate(zip(a, b), 1):
        print(f"[sharded]   step {i}: {x:.5f} vs {y:.5f}  diff {x - y:+.5f}")
    print(f"[sharded] largest difference {worst:.5f}")
    if worst > SHARDED_LOSS_TOL:
        sys.exit("chip_smoke: sharded and single-chip losses disagree")


# ------------------------------------------------------------ child: setup
def claim_device(rehearse: bool, count: int) -> dict:
    """First contact with JAX. Without a TPU the phase dies here: nothing
    of this script carries on on the CPU unless it was asked to rehearse."""
    import jax

    from scaling_tpu.compile_cache import enable_compile_cache

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    if rehearse:
        if device["platform"] != "cpu":
            sys.exit("chip_smoke: --rehearse is the CPU walk-through; run "
                     "it with JAX_PLATFORMS=cpu")
    elif device["platform"] != "tpu":
        sys.exit(f"chip_smoke: no TPU (JAX found {device}); this script "
                 "does not run on the CPU (see --rehearse)")
    if len(devices) < count:
        sys.exit(f"chip_smoke: need {count} devices, JAX found {len(devices)}")
    print(f"device: platform={device['platform']} kind={device['kind']} "
          f"count={device['count']} compile_cache={enable_compile_cache()}",
          flush=True)
    return device


def require_compiled_kernel(kernel: str, rehearse: bool) -> int:
    """The kernel was built, as it counted itself when it was traced (obs
    ``kernel_builds``), and for the device the run is on: compiled on the
    chip, interpreted only in the CPU rehearsal."""
    from scaling_tpu.obs import kernel_build_count

    wanted = kernel_build_count(kernel, interpret=rehearse)
    other = kernel_build_count(kernel, interpret=not rehearse)
    if wanted < 1 or other:
        sys.exit(f"chip_smoke: {kernel} was built {wanted}x with interpret="
                 f"{rehearse} and {other}x with interpret={not rehearse}")
    return wanted


def model_config(size: dict, work: Path, *, mp: int = 1, dp: int = 1,
                 micro_batch: int, steps: int, save: bool):
    from scaling_tpu.models.transformer import TransformerConfig

    return TransformerConfig.from_dict({
        "topology": {
            "model_parallel_size": mp, "pipe_parallel_size": 1,
            "data_parallel_size": dp, "micro_batch_size": micro_batch,
            "gradient_accumulation_steps": 1,
            "sequence_parallel": mp > 1,
        },
        "transformer_architecture": {
            "vocab_size": size["vocab"], "hidden_size": size["hidden"],
            "num_layers": size["layers"],
            "num_attention_heads": size["heads"],
            "attention_num_kv_heads": size["kv_heads"],
            "sequence_length": size["seq"], "precision": "bfloat16",
            "mlp_type": "swiglu", "mlp_factor": 2.75, "norm_type": "rms",
            "relative_position_embedding_type": "rotary", "causal": True,
            "masked_softmax": {"kernel": "flash_attention"},
            "weight_tying": False, "attention_qkv_in_one": False,
            "dropout_embedding": 0.0, "dropout_attention_probs": 0.0,
            "dropout_after_attention": 0.0, "dropout_after_mlp": 0.0,
        },
        "optimizer": {"gradient_clipping": 1.0, "zero": mp * dp > 1,
                      "loss_scaler": {"enable": False}},
        "learning_rate_scheduler": {
            "learning_rate": 6e-4, "learning_rate_warmup_steps": 5,
            "learning_rate_decay_iters": 1000,
        },
        "trainer": {
            "train_iterations": steps, "seed": 0,
            **({"save_dir": str(work / "checkpoint"),
                "save_interval": steps} if save else {}),
        },
        "data": {"data_prefixes": [str(work / "data" / "tokens")],
                 "eod_token_id": 0},
        "logger": {"log_dir": str(work / "logs")},
    })


def write_token_memory_map(prefix: Path, vocab: int, tokens: int,
                           seed: int) -> None:
    """A zipf-skewed stream (so the loss can fall), made in bulk from the
    seed, in the layout ``examples/transformer_example/run.py`` writes."""
    import numpy as np

    from scaling_tpu.data.memory_map import MemoryMapDatasetBuilder

    prefix.parent.mkdir(parents=True, exist_ok=True)
    rng = np.random.default_rng(seed)
    stream = (rng.zipf(1.5, size=tokens) % (vocab - 1) + 1).astype(np.uint16)
    cuts = np.cumsum(rng.integers(256, 2048, size=tokens // 256))
    with MemoryMapDatasetBuilder(prefix, dtype=np.uint16) as builder:
        for doc in np.split(stream, cuts[cuts < tokens]):
            if len(doc):
                builder.add(np.append(doc, 0).astype(np.uint16))


def step_records(log_dir: Path) -> list:
    """The per-step records the trainer itself logged."""
    path = log_dir / "metrics_rank_0.jsonl"
    records = [json.loads(line) for line in path.read_text().splitlines()]
    return [r for r in records if r.get("kind") == "step"]


def peak_bytes(devices) -> list:
    """Peak HBM per device: the allocator's high-water mark of live arrays
    plus the most the runtime reserved for a running program's temporaries
    (the TPU runtime counts the two apart; their sum is what the compiler's
    buffer assignment predicts for the train step)."""
    stats = [d.memory_stats() or {} for d in devices]
    return [int(s.get("peak_bytes_in_use", 0))
            + int(s.get("peak_bytes_reserved", 0)) for s in stats]


def run_training(tag: str, size: dict, config, seed: int, rehearse: bool):
    """``train.main`` through its normal entry, then the facts about it."""
    import contextlib
    import math

    import jax

    from scaling_tpu import native
    from scaling_tpu.models.transformer.train import main
    from scaling_tpu.ops.flash_attention import force_flash_interpret

    topo = config.topology
    steps = config.trainer.train_iterations
    write_token_memory_map(
        Path(config.data.data_prefixes[0]), size["vocab"],
        tokens=2 * steps * topo.global_batch_size * (size["seq"] + 1),
        seed=seed,
    )
    t0 = time.monotonic()
    with force_flash_interpret() if rehearse else contextlib.nullcontext():
        trainer = main(config)
        lowered = trainer._train_step.lower(
            trainer.params, trainer.opt_state,
            trainer._assemble_micro_batches(), jax.random.PRNGKey(0),
        )
    print(f"[{tag}] train.main returned after {time.monotonic() - t0:.1f} s; "
          f"pack index: {'native C++' if native.native_available() else 'Python'}")

    records = step_records(Path(config.logger.log_dir))
    losses = [r["metrics"]["loss"] for r in records]
    if len(losses) != steps:
        sys.exit(f"chip_smoke: {len(losses)} logged steps, expected {steps}")
    if not all(x is not None and math.isfinite(x) for x in losses):
        sys.exit(f"chip_smoke: non-finite loss in {losses}")
    print(f"[{tag}] loss by step: " + " ".join(f"{x:.4f}" for x in losses))
    if not losses[-1] < losses[0]:
        sys.exit(f"chip_smoke: loss did not fall ({losses[0]} -> {losses[-1]})")

    calls = lowered.as_text().count("tpu_custom_call")
    builds = require_compiled_kernel("splash_attention", rehearse)
    if calls < 1 and not rehearse:
        sys.exit("chip_smoke: the lowered train step holds no tpu_custom_call")
    print(f"[{tag}] splash kernel: {builds} build(s) interpret={rehearse}, "
          f"{calls} tpu_custom_call(s) in the lowered step")

    # the first step carries the compile; steady state is the rest
    steady = records[2:]
    med = lambda key: sorted(r["metrics"][key] for r in steady)[len(steady) // 2]
    mfu = med("mfu") if "mfu" in steady[0]["metrics"] else None
    device = jax.devices()[0]
    print(f"[{tag}] trainer-logged medians over steps 3..{steps} on "
          f"{device.platform}/{device.device_kind}: "
          f"step {med('step_duration') * 1e3:.1f} ms, "
          f"{med('tokens_per_second'):.0f} tokens/s, mfu "
          f"{'not computed (no published peak)' if mfu is None else f'{mfu:.4f}'}")
    return trainer, lowered, losses


# ----------------------------------------------------------- child: phases
def phase_train(size, args, device) -> dict:
    import jax

    config = model_config(size, WORK, micro_batch=size["micro_batch"],
                          steps=size["steps"], save=True)
    _, _, losses = run_training("train", size, config, args.seed,
                                args.rehearse)
    peak = peak_bytes(jax.devices()[:1])[0]
    print(f"[train] peak HBM {peak} bytes ({peak / 2**30:.2f} GiB) = "
          f"peak_bytes_in_use + peak_bytes_reserved of memory_stats "
          f"{jax.devices()[0].memory_stats()}")
    latest = (WORK / "checkpoint" / "latest")
    if not latest.is_file():
        sys.exit("chip_smoke: the trainer left no checkpoint")
    print(f"[train] checkpoint {latest.read_text().strip()} written")
    return {"losses": losses}


def phase_train_sharded(size, args, device) -> dict:
    """TP=2 x DP=2, ZeRO-1, sequence parallelism, one process on four chips."""
    import jax

    work = WORK / "sharded"
    config = model_config(size, work, mp=2, dp=2,
                          micro_batch=size["micro_batch"] // 2,
                          steps=size["sharded_steps"], save=False)
    trainer, lowered, losses = run_training(
        "sharded", size, config, args.seed, args.rehearse)
    devices = jax.devices()[:4]
    holders = {d.id: 0 for d in devices}
    for leaf in jax.tree.leaves((trainer.params, trainer.opt_state)):
        for shard in leaf.addressable_shards:
            holders[shard.device.id] += shard.data.nbytes
    print(f"[sharded] parameter+optimizer bytes by device: {holders}")
    if min(holders.values()) == 0 or max(holders.values()) > 1.5 * min(
            holders.values()):
        sys.exit("chip_smoke: state is not spread over the four devices")
    peaks = peak_bytes(devices)
    print(f"[sharded] peak HBM bytes by device (in use + reserved): {peaks}")
    if not args.rehearse and min(peaks) == 0:
        sys.exit("chip_smoke: a device reports no memory in use")
    text = lowered.compile().as_text()
    counts = {op: text.count(f" {op}(") + text.count(f" {op}-start(")
              for op in ("all-reduce", "reduce-scatter", "all-gather")}
    print(f"[sharded] collectives in the compiled step: {counts}")
    if counts["all-reduce"] < 1 or (
            counts["reduce-scatter"] + counts["all-gather"]) < 1:
        sys.exit("chip_smoke: the compiled step lacks the expected "
                 "all-reduce / reduce-scatter / all-gather")
    return {"losses": losses}


def phase_train_single(size, args, device) -> dict:
    """The comparison: same model, same global batch, one of the chips."""
    work = WORK / "single"
    config = model_config(size, work, micro_batch=size["micro_batch"],
                          steps=size["sharded_steps"], save=False)
    _, _, losses = run_training("single", size, config, args.seed,
                                args.rehearse)
    return {"losses": losses}


def phase_serve(size, args, device) -> dict:
    import numpy as np

    from scaling_tpu.models.transformer.inference import (
        TransformerInferenceModule,
    )
    from scaling_tpu.serve.bench import run_bench, sample_workload
    from scaling_tpu.serve.engine import EngineConfig, ServeEngine

    os.environ["SCALING_TPU_EVENTS_PATH"] = str(WORK / "serve_events.jsonl")
    inf = TransformerInferenceModule.from_checkpoint(WORK / "checkpoint")
    blocks_per_seq = size["context"] // BLOCK_SIZE
    engine = ServeEngine(inf, EngineConfig(
        num_slots=size["slots"],
        num_blocks=size["slots"] * blocks_per_seq + 1,  # + the trash block
        max_blocks_per_seq=blocks_per_seq,
    ))
    cfg = engine.config
    if (cfg.prefill_chunk, cfg.enable_prefix_cache, cfg.block_size) != (
            PREFILL_CHUNK, True, BLOCK_SIZE):
        sys.exit(f"chip_smoke: the engine's defaults moved: {cfg}")
    workload = sample_workload(
        size["requests"], rate=4.0, prompt_len=size["prompt_len"],
        output_len=size["output_len"], vocab=size["vocab"], seed=args.seed,
    )
    # compile the tick program off the clock, as `serve bench --warmup` does
    t0 = time.monotonic()
    engine.warmup_mode = True
    engine.submit([1], 2)
    engine.run_until_done()
    engine.warmup_mode = False
    engine.finished.clear()
    print(f"[serve] engine warm-up (compile) {time.monotonic() - t0:.1f} s")

    stats = run_bench(engine, workload, max_wall_s=600.0)
    by_prompt = {tuple(s.request.prompt): s for s in engine.finished}
    for _, prompt, want in workload:
        seq = by_prompt[tuple(prompt)]
        if seq.finish_status != "completed" or len(seq.generated) != want:
            sys.exit(f"chip_smoke: request of {len(prompt)} tokens finished "
                     f"{seq.finish_status} with {len(seq.generated)} of "
                     f"{want} tokens")
    # many ticks ran over donated pools; a deleted buffer would have raised
    print(f"[serve] {stats['requests']} requests answered in full over "
          f"{stats['ticks']} ticks (donated pool state reused every tick)")
    print(f"[serve] on {device['platform']}/{device['kind']}: ttft p50 "
          f"{stats['ttft_p50_s']:.4f} s p99 {stats['ttft_p99_s']:.4f} s, "
          f"{stats['tokens_per_s']:.1f} output tokens/s "
          f"({stats['output_tokens']} tokens, wall {stats['wall_s']:.2f} s), "
          f"itl p50 {stats['itl_p50_s']:.4f} s")

    builds = require_compiled_kernel("paged_attention", args.rehearse)
    width = cfg.mixed_widths[0]  # the token width nearly every tick runs
    empty, _ = engine._layout.host(width)
    text = engine._mixed_fns[width].lower(
        inf.params, engine._pool_state(), engine._dev(empty),
        engine._base_key, engine._prev,
    ).as_text()
    calls = text.count("tpu_custom_call")
    if calls < 1 and not args.rehearse:
        sys.exit("chip_smoke: the mixed program holds no tpu_custom_call")
    print(f"[serve] paged kernel: {builds} build(s) interpret="
          f"{args.rehearse}, {calls} tpu_custom_call(s) in the lowered mixed "
          f"program (width {width})")

    # reference: the plain KV-cache path on the same prompts, one ragged batch
    prompts = [prompt for _, prompt, _ in workload]
    outs = inf.generate(prompts, max_tokens=max(w for _, _, w in workload))
    compared = ties = 0
    worst = 0.0
    for (_, prompt, want), ref in zip(workload, outs):
        got = by_prompt[tuple(prompt)].generated
        logits = np.asarray(ref.logits[:want], np.float32)
        for t in range(want):
            ref_tok = ref.completion_ids[t]
            gap = float(logits[t, ref_tok] - logits[t, got[t]])
            compared += 1
            worst = max(worst, gap)
            if got[t] != ref_tok:
                if gap > LOGIT_TOL:
                    sys.exit(
                        f"chip_smoke: engine token {got[t]} vs generate "
                        f"{ref_tok} at step {t} of a {len(prompt)}-token "
                        f"prompt; reference logits differ by {gap:.4f} > "
                        f"{LOGIT_TOL}")
                ties += 1
                break  # contexts differ from here on
    print(f"[serve] engine vs generate: {compared} positions compared, "
          f"{ties} request(s) parted at a tie within {LOGIT_TOL}; largest "
          f"reference-logit gap of an engine token {worst:.4f}")
    return {}


def dense_attention(q, k, v, scale):
    """Causal GQA attention in float32: the splash kernel's reference."""
    import jax
    import jax.numpy as jnp

    b, s, n, d = q.shape
    rep = n // k.shape[2]
    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    scores = jnp.einsum("bqnd,bknd->bnqk", q, k) * scale
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -1e30), axis=-1)
    return jnp.einsum("bnqk,bknd->bqnd", probs, v)


def dense_paged(q, pool_k, pool_v, table, valid_len, base, rep, scale):
    """Gather the block window and softmax it whole, in float32: the paged
    kernel's reference (the masking of nn/attention.py's XLA branch)."""
    import jax
    import jax.numpy as jnp

    b, s, n, h = q.shape
    window = table.shape[1] * pool_k.shape[1]
    gk = jnp.repeat(pool_k[table].reshape(b, window, -1, h), rep, axis=2)
    gv = jnp.repeat(pool_v[table].reshape(b, window, -1, h), rep, axis=2)
    slots_k = jnp.arange(window)[None, None, :]
    slots_q = (base[:, None] + jnp.arange(s)[None, :])[:, :, None]
    allowed = (slots_k < valid_len[:, None, None]) & (slots_k <= slots_q)
    scores = jnp.einsum("bqnh,bknh->bnqk", q.astype(jnp.float32),
                        gk.astype(jnp.float32)) * scale
    probs = jax.nn.softmax(jnp.where(allowed[:, None], scores, -1e30), -1)
    return jnp.einsum("bnqk,bknh->bqnh", probs, gv.astype(jnp.float32))


def check_close(tag, got, ref, rtol) -> None:
    import numpy as np

    got, ref = np.asarray(got, np.float32), np.asarray(ref, np.float32)
    err, scale = float(np.abs(got - ref).max()), float(np.abs(ref).max())
    ok = np.isfinite(got).all() and err <= rtol * scale
    print(f"[kernels] {tag}: max |err| {err:.3e} vs max |ref| {scale:.3e} "
          f"(allowed {rtol:.0e} of it) {'ok' if ok else 'FAILED'}")
    if not ok:
        sys.exit(f"chip_smoke: kernel check failed: {tag}")


def phase_kernels(size, args, device) -> dict:
    import contextlib

    import jax
    import jax.numpy as jnp
    import numpy as np

    from scaling_tpu.nn.attention import kv_quantize_int8
    from scaling_tpu.nn.paged_attention import paged_decode_attention
    from scaling_tpu.ops.flash_attention import (
        flash_attention_fused,
        force_flash_interpret,
    )

    n, n_kv = size["heads"], size["kv_heads"]
    d = size["hidden"] // n
    scale = d ** -0.5
    keys = jax.random.split(jax.random.PRNGKey(args.seed), 8)

    # splash forward + backward at the train step's shape
    shape = (size["micro_batch"], size["seq"])
    q = jax.random.normal(keys[0], (*shape, n, d), jnp.bfloat16)
    k = jax.random.normal(keys[1], (*shape, n_kv, d), jnp.bfloat16)
    v = jax.random.normal(keys[2], (*shape, n_kv, d), jnp.bfloat16)
    w = jax.random.normal(keys[3], (*shape, n, d), jnp.float32)

    def loss(fn):
        return lambda q, k, v: (fn(q, k, v).astype(jnp.float32) * w).sum()

    flash = lambda q, k, v: flash_attention_fused(q, k, v, sm_scale=scale)
    dense = lambda q, k, v: dense_attention(q, k, v, scale)
    with force_flash_interpret() if args.rehearse else contextlib.nullcontext():
        out = jax.jit(flash)(q, k, v)
        grads = jax.jit(jax.grad(loss(flash), argnums=(0, 1, 2)))(q, k, v)
    # the references multiply in full float32: the TPU's default for a
    # float32 matmul is one bf16 pass, which would make them as coarse as
    # what they judge
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(dense)(q, k, v)
        ref_grads = jax.jit(jax.grad(loss(dense), argnums=(0, 1, 2)))(q, k, v)
    check_close("splash fwd", out, ref, SPLASH_RTOL)
    for name, g, r in zip(("dq", "dk", "dv"), grads, ref_grads):
        check_close(f"splash bwd {name}", g, r, SPLASH_RTOL)
    require_compiled_kernel("splash_attention", args.rehearse)

    # paged decode over the serve phase's real pool
    rows = size["slots"]
    max_blocks = size["context"] // BLOCK_SIZE
    num_blocks = rows * max_blocks + 1
    pool_shape = (num_blocks, BLOCK_SIZE, n_kv, d)
    pool_k = jax.random.normal(keys[4], pool_shape, jnp.bfloat16)
    pool_v = jax.random.normal(keys[5], pool_shape, jnp.bfloat16)
    qk, sk = kv_quantize_int8(pool_k)
    qv, sv = kv_quantize_int8(pool_v)
    deq_k = qk.astype(jnp.float32) * sk[..., None]
    deq_v = qv.astype(jnp.float32) * sv[..., None]
    rng = np.random.default_rng(args.seed)
    # every row owns its own blocks; contexts from empty to nearly full,
    # one row inactive (all-trash table, nothing visible)
    table = 1 + np.arange(rows * max_blocks, dtype=np.int32).reshape(
        rows, max_blocks)
    rng.shuffle(table.reshape(-1))
    for s in (1, PREFILL_CHUNK, TAIL_ROWS):
        ctx = rng.integers(0, size["context"] - s, size=rows).astype(np.int32)
        ctx[0], ctx[1] = 0, size["context"] - s
        tab = table.copy()
        for r in range(rows):  # blocks past the row's context are trash
            tab[r, -(-(int(ctx[r]) + s) // BLOCK_SIZE):] = 0
        tab[2], ctx[2] = 0, 0
        tab, ctx = jnp.asarray(tab), jnp.asarray(ctx)
        valid = ctx + s
        valid = valid.at[2].set(0)
        qq = jax.random.normal(keys[6], (rows, s, n, d), jnp.bfloat16)
        for name, pk, pv, scales, rk, rv in (
            ("native", pool_k, pool_v, {}, pool_k, pool_v),
            ("int8", qk, qv, {"scale_k": sk, "scale_v": sv}, deq_k, deq_v),
        ):
            got = jax.jit(lambda q, pk, pv, scales: paged_decode_attention(
                q, pk, pv, tab, valid, ctx, sm_scale=scale,
                num_repeat_kv=n // n_kv, **scales))(qq, pk, pv, scales)
            with jax.default_matmul_precision("highest"):
                ref = jax.jit(lambda q, rk, rv: dense_paged(
                    q, rk, rv, tab, valid, ctx, n // n_kv, scale))(qq, rk, rv)
            live = np.array([r for r in range(rows) if r != 2])
            check_close(f"paged {name} s={s}", got[live], ref[live],
                        PAGED_RTOL)
            if not bool(jnp.isfinite(got[2].astype(jnp.float32)).all()):
                sys.exit("chip_smoke: the inactive paged row is not finite")
    require_compiled_kernel("paged_attention", args.rehearse)
    return {}


PHASES = {
    "kernels": (phase_kernels, 1),
    "train": (phase_train, 1),
    "serve": (phase_serve, 1),
    "train_sharded": (phase_train_sharded, 4),
    "train_single": (phase_train_single, 1),
}


def child(args) -> int:
    fn, need = PHASES[args.phase]
    device = claim_device(args.rehearse, need)
    verdict = fn(TOY if args.rehearse else FULL, args, device)
    (WORK / f"{args.phase}.json").write_text(
        json.dumps({**verdict, "device": device}))
    return 0


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1,
                        help="4: only the TP=2 x DP=2 training comparison")
    parser.add_argument("--seed", type=int, default=0,
                        help="seed of the synthetic data and requests")
    parser.add_argument("--rehearse", action="store_true",
                        help="CPU walk-through at a toy size, kernels "
                        "interpreted; never reports a chip run")
    parser.add_argument("--phase", choices=sorted(PHASES),
                        help=argparse.SUPPRESS)  # the parent's children
    args = parser.parse_args()
    return child(args) if args.phase else parent(args)


if __name__ == "__main__":
    sys.exit(main())
