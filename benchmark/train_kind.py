"""Kind ``train``: the program's jitted train step in the benchmark's loop.

The harness calls what ``scaling_tpu.models.transformer.train.main`` calls
(``init_model``, ``init_optimizer``, ``build_train_step``, the batch placement
of ``shard_batch``) and times chunks of k steps that each end in
``block_until_ready``, the loop of ``bench.py`` ``measure``. The trainer's data
loader, logging and checkpoints are outside this kind. Under ``--trace 2`` the
window runs untraced and a traced second of the same chunks follows it.
"""

from __future__ import annotations

import contextlib
import math
import sys
import time

# The train step computes in bf16 and the reference in float32 on the same
# bf16 weights. The loss is a mean over 512 positions of values ~ln(vocab) ~
# 10-12, so roundings average out: over 14 seeds on the chip the two differed
# by at most 2.3e-4 (Mistral widths; 1.0e-4 at Pharia's; PERF.md, Findings PR
# 24). The tolerance is nine times that: a step that computes in a lower
# precision than the configuration states (fp8 rounds 32 times coarser than
# bf16, about 7e-3 on this mean) fails it, as does a wrong position, mask,
# norm or a dropped bias (more than 0.1).
LOSS_TOL = 2e-3


def log(msg: str) -> None:
    print(f"[train] {msg}", file=sys.stderr, flush=True)


def run(cell, args, env) -> dict:
    import jax
    import jax.numpy as jnp

    from scaling_tpu.models.transformer.model import (
        init_model, init_optimizer, loss_function,
    )
    from scaling_tpu.obs import kernel_build_count
    from scaling_tpu.ops.flash_attention import force_flash_interpret
    from scaling_tpu.topology import Topology

    from . import model
    from .device import live_bytes, memory_peaks

    traffic, arch_json = cell.traffic, cell.config["transformer_architecture"]
    config = model.transformer_config(cell.config, traffic)
    arch, topo = config.transformer_architecture, config.topology
    if topo.world_size != cell.chips:
        sys.exit(f"benchmark: configuration {cell.config['name']} lays out "
                 f"{topo.world_size} chips, the cell asks for {cell.chips}")
    topology = Topology(config.topology, devices=jax.devices()[:cell.chips])
    module = init_model(config, topology)
    optimizer = init_optimizer(config, module, topology)
    seq, vocab = arch.sequence_length, arch.vocab_size
    batch_rows = topo.micro_batch_size * topo.data_parallel_size
    check_positions = min(int(traffic["check_positions"]), seq)
    key = model.prng_key(args.seed)
    # the program counts its kernel builds for the whole process, so a
    # build in the wrong mode is one made during THIS run: an earlier one (a
    # test that compiled the kernel for a described chip) is not its path
    kernel = traffic.get("kernel")
    wrong_before = kernel_build_count(
        kernel, interpret=not args.rehearse) if kernel else 0

    def make_batch(key, check: bool):
        """A fresh batch of log-uniform token ids; ``check`` keeps the loss
        of the leading positions of the first sequence only."""
        u = jax.random.uniform(key, (1, batch_rows, seq + 1))
        ids = jnp.exp(u * math.log(vocab - 1)).astype(jnp.int32)  # 1..vocab-1
        weights = jnp.ones((1, batch_rows, seq), jnp.float32)
        if check:
            keep = (jnp.arange(seq) < check_positions)[None, None, :]
            first = (jnp.arange(batch_rows) == 0)[None, :, None]
            weights = (keep & first).astype(jnp.float32)
        return {
            "token_ids": ids[..., :-1],
            "target_token_ids": ids[..., 1:],
            "position_ids": jnp.broadcast_to(
                jnp.arange(seq, dtype=jnp.int32), (1, batch_rows, seq)),
            "segment_ids": jnp.zeros((1, batch_rows, seq), jnp.int32),
            "loss_weights": weights,
        }

    batch_shapes = jax.eval_shape(lambda k: make_batch(k, False), key)
    zeros = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), batch_shapes)
    batch_shardings = jax.tree.map(
        lambda x: x.sharding, module.shard_batch(zeros, stacked=True))
    make_batch = jax.jit(make_batch, static_argnums=1,
                         out_shardings=batch_shardings)

    interpret = (force_flash_interpret() if args.rehearse
                 else contextlib.nullcontext())
    with interpret:
        params = model.init_weights(module, args.seed)
        jax.block_until_ready(params)
        env["mark"]("weights made on the device")
        # -- correct: the reference's loss on the check batch, before the
        # step donates the weights
        check = make_batch(jax.random.fold_in(key, 1_000_003), True)
        tokens = check["token_ids"][0, 0, :check_positions]
        targets = check["target_token_ids"][0, 0, :check_positions]
        ref, view = cell.reference, cell.view
        weights, spec = (view.reference_weights(params, arch_json),
                         view.reference_spec(arch_json))
        logits = ref.forward(weights, tokens, spec)
        ref_loss = float(ref.token_loss(logits, targets).mean())
        del logits
        control_loss = None
        if env.get("control"):  # benchmark/control.py: never in the driver's runs
            from .control import lower_precision

            control_loss = float(ref.token_loss(ref.forward(
                lower_precision(weights, env["control"]), tokens, spec),
                targets).mean())
            log(f"control ({env['control']} weights in the reference): loss "
                f"{control_loss:.5f}, {abs(control_loss - ref_loss):.2e} from the "
                f"reference's (tolerance {LOSS_TOL})")
        env["mark"]("reference loss computed")

        opt_state = model.init_optimizer_state(optimizer, params)
        step = module.build_train_step(optimizer, loss_function)
        t = time.monotonic()
        params, opt_state, loss, _, _ = step(params, opt_state, check, key)
        first_loss = float(loss)
        log(f"first step (compiles or loads from the cache) "
            f"{time.monotonic() - t:.1f} s; loss {first_loss:.5f} vs reference "
            f"{ref_loss:.5f} on {check_positions} positions (tolerance {LOSS_TOL})")
        loss_ok = abs(first_loss - ref_loss) <= LOSS_TOL

        # -- warm-up: every program the window uses, then the chunk length
        counter = 0

        def run_steps(n, params, opt_state):
            nonlocal counter
            for _ in range(n):
                batch = make_batch(jax.random.fold_in(key, counter), False)
                params, opt_state, loss, _, _ = step(
                    params, opt_state, batch, jax.random.fold_in(key, counter))
                counter += 1
            return params, opt_state, loss

        params, opt_state, loss = run_steps(1, params, opt_state)
        jax.block_until_ready(loss)
        t = time.monotonic()
        params, opt_state, loss = run_steps(2, params, opt_state)
        jax.block_until_ready(loss)
        step_s = (time.monotonic() - t) / 2
        k = max(1, round(float(traffic["chunk_seconds"]) / step_s))
        log(f"warm step {step_s * 1e3:.1f} ms -> chunks of {k} steps")
        env["mark"]("warm-up done, the window opens")

        devices = list(topology.mesh.devices.flatten())
        live = live_bytes(devices)
        setup_s = time.monotonic() - env["t0"]
        compiles_before = env["compiles"].count
        tracer = env["tracer"]
        chunks, losses = [], []
        t0 = time.monotonic()
        while True:
            now = time.monotonic()
            if now - t0 >= args.seconds:
                break
            tracer.maybe_start(now - t0)
            params, opt_state, loss = run_steps(k, params, opt_state)
            losses.append(float(loss))  # ends the chunk: a host read
            chunks.append(time.monotonic() - now)
            tracer.maybe_stop()
        elapsed = time.monotonic() - t0
        env["mark"]("window over")
        compiles_in_window = env["compiles"].count - compiles_before
        # -- --trace 2: more chunks on the same weights with fresh batches,
        # traced; nothing below reads them. The peak is read before any
        # capture starts, as far into the run as --trace 0's window goes
        window_peaks = None
        if tracer.after_window:
            window_peaks = memory_peaks(devices, live)
            tracer.open_after_window()
            while tracer.stopped_at is None:
                tracer.maybe_start(0.0)
                params, opt_state, loss = run_steps(k, params, opt_state)
                jax.block_until_ready(loss)
                tracer.maybe_stop()
            env["mark"]("traced part over")

    steps = k * len(chunks)
    tokens_per_step = batch_rows * seq
    tokens_per_s = steps * tokens_per_step / elapsed
    finite = all(math.isfinite(x) for x in losses)
    builds = kernel_build_count(kernel, interpret=args.rehearse) if kernel else 1
    wrong_builds = (kernel_build_count(kernel, interpret=not args.rehearse)
                    - wrong_before) if kernel else 0
    log(f"{steps} steps in {elapsed:.2f} s, loss {losses[0]:.4f} -> "
        f"{losses[-1]:.4f}; {kernel}: {builds} build(s), {wrong_builds} in "
        f"the wrong mode; {compiles_in_window} program(s) lowered in the window")
    flops_per_token = cell.view.train_flops_per_token(
        arch_json, model.param_shapes(module), seq)
    return {
        "correct": bool(loss_ok and finite and builds > 0 and wrong_builds == 0
                        and compiles_in_window == 0),
        "attempted": steps,
        "failed": 0 if finite else sum(not math.isfinite(x) for x in losses),
        "setup_s": setup_s,
        "end_to_end": {"train_tokens_per_s": tokens_per_s / cell.chips},
        "host": {
            "chunk_step_s": [c / k for c in chunks],
            "steps": steps, "steps_per_chunk": k,
            "tokens_per_s": tokens_per_s, "tokens_per_step": tokens_per_step,
            "flops_per_token": flops_per_token,
            "first_loss": first_loss, "reference_loss": ref_loss,
            "control_loss": control_loss,
            "micro_batch": topo.micro_batch_size,
            "batch_rows": batch_rows, "seq": seq,
        },
        "devices": [d.id for d in devices], "live_bytes": live,
        "window_peaks": window_peaks,
    }
