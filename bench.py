"""Benchmark: train-step throughput of the flagship transformer on one TPU chip.

Runs a GQA + RoPE + SwiGLU decoder (the BASELINE.md config-#3 shape scaled
to one chip) through the real jitted train step — forward, backward, AdamW —
and prints ONE JSON line with tokens/sec/chip and MFU, naming the device it
ran on (``platform`` / ``device_kind`` / ``device_count``). ``vs_baseline``
is MFU against the 45% target from BASELINE.json (the reference publishes no
numbers of its own — BASELINE.md "Reference-published numbers").

A measurement path that finds no chip fails: without a TPU, or when anything
raises, the process exits non-zero and prints no result line. The only
exception that is part of the measurement is an out-of-memory error while
climbing the micro-batch ladder, which means "this arm does not fit".

``BENCH_MODEL`` (0.5b | 1b | 0.5b-lora), ``BENCH_MBS`` (pin the micro-batch)
and ``BENCH_REMAT`` (1b arm's checkpointing policy) select the arm.
"""

from __future__ import annotations

import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from scaling_tpu.compile_cache import enable_compile_cache
from scaling_tpu.models.transformer import TransformerConfig
from scaling_tpu.models.transformer.model import (
    init_model,
    init_optimizer,
    loss_function,
)
from scaling_tpu.models.transformer.utils.get_tflops import (
    detect_hardware,
    get_model_parameter_count,
    get_palm_mfu,
)
from scaling_tpu.obs import kernel_build_count
from scaling_tpu.topology import Topology

MFU_TARGET = 0.45  # BASELINE.json: ">=45% MFU on a 7B on v5p-128"
REMAT_POLICIES = ("every_layer", "every_layer_save_dots", "every_pipe_stage",
                  "disabled")


def build(seq_len: int, micro_batch_size: int, hidden: int, layers: int,
          remat=False, lora: bool = False):
    """``remat``: False (off), True (every_layer), or an explicit
    activation_checkpointing_type string (e.g. every_layer_save_dots)."""
    arch: dict = {
        "vocab_size": 32768,
        "hidden_size": hidden,
        "num_layers": layers,
        "num_attention_heads": hidden // 128,
        "attention_num_kv_heads": max(1, hidden // 512),
        "sequence_length": seq_len,
        "precision": "bfloat16",
        "mlp_type": "swiglu",
        "mlp_factor": 2.75,  # llama-style 8/3 rounded to an integer width
        "norm_type": "rms",
        "relative_position_embedding_type": os.environ.get("BENCH_ROTARY", "rotary"),
        "causal": True,
        # the splash flash kernel (GQA-native, unrepeated KV) beats
        # XLA attention ~10x at seq 2048 in the fwd+bwd micro-bench;
        # BENCH_KERNEL=torch selects the XLA path for comparison
        "masked_softmax": {"kernel": os.environ.get("BENCH_KERNEL", "flash_attention")},
        # BENCH_NORM=fused selects the Pallas fused RMSNorm for A/B
        # against the XLA-fused default
        "layernorm": {"optimization_type": os.environ.get("BENCH_NORM", "torch")},
        "weight_tying": False,
        # fused QKV is layout-incompatible with GQA (differing kv
        # heads), and GQA's KV-bandwidth win matters more here
        "attention_qkv_in_one": False,
        "dropout_embedding": 0.0,
        "dropout_attention_probs": 0.0,
        "dropout_after_attention": 0.0,
        "dropout_after_mlp": 0.0,
    }
    if lora:
        # BASELINE #5's PEFT arm: LoRA on the attention projections, the
        # backbone frozen (stop-gradient'd inside the loss — see PERF.md
        # "PEFT step economics").
        arch["lora_config"] = {"name": "lo", "rank": 16, "alpha": 32}
    config = TransformerConfig.from_dict(
        {
            **(
                {"training": {"finetune": True, "finetunable_parameters": []}}
                if lora
                else {}
            ),
            "topology": {
                "model_parallel_size": 1,
                "pipe_parallel_size": 1,
                "data_parallel_size": 1,
                "micro_batch_size": micro_batch_size,
                "gradient_accumulation_steps": 1,
                **(
                    {
                        "activation_checkpointing_type": (
                            remat if isinstance(remat, str) else "every_layer"
                        )
                    }
                    if remat
                    else {}
                ),
            },
            "transformer_architecture": arch,
            "optimizer": {"gradient_clipping": 1.0, "loss_scaler": {"enable": False}},
            "learning_rate_scheduler": {
                "learning_rate": 3e-4,
                "learning_rate_warmup_steps": 10,
                "learning_rate_decay_iters": 1000,
            },
            "trainer": {"train_iterations": 10, "seed": 0},
            "data": {},
            "logger": {"log_dir": None},
        }
    )
    topology = Topology(config.topology)
    module = init_model(config, topology)
    optimizer = init_optimizer(config, module, topology)
    return config, topology, module, optimizer


def synth_batch(rng: np.random.Generator, batch: int, seq_len: int, vocab: int, gas: int):
    tokens = rng.integers(1, vocab, size=(gas, batch, seq_len), dtype=np.int64)
    pos = np.broadcast_to(np.arange(seq_len, dtype=np.int32), (gas, batch, seq_len))
    return {
        "token_ids": jnp.asarray(tokens, jnp.int32),
        "target_token_ids": jnp.asarray(np.roll(tokens, -1, axis=-1), jnp.int32),
        "position_ids": jnp.asarray(pos),
        "segment_ids": jnp.zeros((gas, batch, seq_len), jnp.int32),
        "loss_weights": jnp.ones((gas, batch, seq_len), jnp.float32),
    }


def is_out_of_memory(error: Exception) -> bool:
    """XLA reports a program or allocation that does not fit the chip's HBM
    as RESOURCE_EXHAUSTED."""
    return "RESOURCE_EXHAUSTED" in str(error)


def climb_mbs_ladder(measure, mbs_plan, arch, dt):
    """Self-tune the micro-batch: keep climbing the plan while each rung is
    faster PER TOKEN than the last kept one. A rung that does not fit the
    chip's memory, or stops winning, keeps the recorded winner; any other
    failure of a rung is a failure of the bench. ``measure(mbs) -> (arch,
    step_seconds)``; returns the winning ``(arch, step_seconds, mbs)``."""
    mbs = mbs_plan[0]
    for trial in mbs_plan[1:]:
        try:
            arch_t, dt_t = measure(trial)
        except Exception as e:
            if not is_out_of_memory(e):
                raise
            print(f"# mbs={trial} does not fit; keeping mbs={mbs}",
                  file=sys.stderr)
            break
        if trial / dt_t > mbs / dt:
            arch, dt, mbs = arch_t, dt_t, trial
        else:
            break
    return arch, dt, mbs


def main() -> None:
    seq_len = 2048
    # default ~0.5B: params bf16 + fp32 master/moments + fp32 grads ~ 9G,
    # inside the 16G HBM of the smallest current chip (v5e)
    hidden, layers, remat = 2048, 8, False
    # the ladder stops at the first arm that isn't faster per token (and an
    # arm that does not fit keeps the last recorded winner), so the tail
    # only runs while each rung keeps winning
    mbs_plan = [4, 8, 16, 32]
    bench_model = os.environ.get("BENCH_MODEL", "0.5b")
    lora = False
    if bench_model not in ("0.5b", "1b", "0.5b-lora"):
        sys.exit(f"unknown BENCH_MODEL {bench_model!r} (0.5b|1b|0.5b-lora)")
    if bench_model == "1b":
        # BASELINE #3's 1B GQA+RoPE+SwiGLU shape. Single-chip this is an
        # HBM long shot on v5e: fp32 master+moments + bf16 params alone
        # are 14 bytes/param = 15.3G of the 16G — remat + mbs 1 give it
        # its best chance. (Per-chip fit of the ACTUAL BASELINE #3 layout,
        # TP=2 x DP=4 with ZeRO-1, is pinned in
        # tests/transformer/test_hlo_cost_pins.)
        remat_env = os.environ.get("BENCH_REMAT", "every_layer")
        if remat_env not in REMAT_POLICIES:
            sys.exit(f"unknown BENCH_REMAT {remat_env!r} "
                     f"({'|'.join(REMAT_POLICIES)})")
        hidden, layers = 2048, 20
        remat = False if remat_env == "disabled" else remat_env
        mbs_plan = [1, 2, 4]
    elif bench_model == "0.5b-lora":
        # BASELINE #5's PEFT arm: frozen backbone + rank-16 LoRA on the
        # attention projections. Optimizer state is ~0.4% of full, so
        # bigger micro-batches fit than the pretraining arm allows.
        lora = True
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"bench.py measures a TPU; JAX found {devices[0].platform} "
                 f"({devices[0].device_kind})")
    hardware = detect_hardware()  # an unknown device_kind raises
    enable_compile_cache()
    # BENCH_MBS pins the micro-batch; unset, the bench self-tunes: measure
    # at the smallest plan entry, then try the next — a bigger per-step
    # batch amortizes overheads and widens MXU tiles — and keep whichever
    # is faster per token
    if os.environ.get("BENCH_MBS"):
        mbs_plan = [int(os.environ["BENCH_MBS"])]

    def measure(mbs):
        """Warm up, then the median of 3 windows of 10 steps; each window
        ends in block_until_ready on the final loss, which chains on all
        prior steps."""
        config, topology, module, optimizer = build(
            seq_len, mbs, hidden, layers, remat=remat, lora=lora
        )
        arch = config.transformer_architecture
        key = jax.random.PRNGKey(0)
        params = module.shard_params(module.init_params(key))
        opt_state = optimizer.init_state(params)
        step = module.build_train_step(optimizer, loss_function)
        batch = module.shard_batch(
            synth_batch(np.random.default_rng(0), mbs, seq_len,
                        arch.vocab_size, 1),
            stacked=True,
        )
        params, opt_state, loss, _, _ = step(params, opt_state, batch, key)
        if not np.isfinite(float(loss)):
            raise RuntimeError(f"non-finite warmup loss {float(loss)}")
        iters, windows = 10, []
        for _ in range(3):
            t0 = time.perf_counter()
            for i in range(iters):
                params, opt_state, loss, _, _ = step(
                    params, opt_state, batch, jax.random.fold_in(key, i)
                )
            jax.block_until_ready(loss)
            windows.append((time.perf_counter() - t0) / iters)
        # device state is frame-local: it frees on return, before any next arm
        return arch, sorted(windows)[1]

    arch, dt = measure(mbs_plan[0])
    arch, dt, mbs = climb_mbs_ladder(measure, mbs_plan, arch, dt)

    # the attention path that ran, as the kernel counted itself when built
    kernel = os.environ.get("BENCH_KERNEL", "flash_attention")
    splash_builds = kernel_build_count("splash_attention", interpret=False)
    if (kernel == "flash_attention") != (splash_builds > 0):
        raise RuntimeError(
            f"BENCH_KERNEL={kernel} but {splash_builds} compiled splash "
            "kernel(s) were built: the attention path that ran is not the "
            "one that was asked for"
        )
    tokens_per_sec = mbs * seq_len / dt
    param_count = get_model_parameter_count(
        arch.hidden_size, arch.num_layers, arch.vocab_size, arch.mlp_factor, glu=True
    )
    mfu = get_palm_mfu(
        param_count, arch.num_layers, arch.hidden_size, arch.sequence_length,
        tokens_per_sec, world_size=1, hardware=hardware,
    )
    print(json.dumps({
        "metric": "tokens_per_sec_per_chip",
        "value": round(tokens_per_sec, 1),
        "unit": "tokens/s",
        "vs_baseline": round(mfu / MFU_TARGET, 4),
        "mfu": round(mfu, 4),
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
        "hardware": hardware.value,
        "peak_tflops": hardware.max_tflops,
        "params": param_count,
        "step_ms": round(dt * 1000, 2),
        "micro_batch_size": mbs,
        "model": bench_model,
        "remat": remat or None,
        "kernel": kernel,
    }))


if __name__ == "__main__":
    main()
