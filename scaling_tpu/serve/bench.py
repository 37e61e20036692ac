"""Serving load generator: Poisson arrivals -> engine -> obs telemetry.

``python -m scaling_tpu.serve bench`` drives the continuous-batching
engine with an open-loop Poisson arrival process (exponential
inter-arrival gaps at ``--rate`` req/s) and prompt/output lengths sampled
uniformly from ``--prompt-len``/``--output-len`` ranges, then reports
tokens/s, p50/p99 time-to-first-token and inter-token latency.

Telemetry rides the SAME rails training uses (docs/OBSERVABILITY.md):
metrics through ``obs.get_registry()`` (flushed to ``<run-dir>/
metrics.jsonl``), per-request ``serve-request`` + final ``serve-summary``
events through ``logger.log_event`` — so ``python -m scaling_tpu.obs
report <run-dir>`` grows a serving section, and the
``--assert-serve-throughput`` / ``--assert-ttft`` gates work both here
(self-gating) and on the analyzer over
the run dir (CI reads the artifacts, not the console).

The model is a randomly initialised toy transformer by default (the
benchmark measures the ENGINE: scheduling, paging, recompile hygiene);
``--checkpoint`` serves a real one.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path
from typing import List, Optional

# NOTE: keep this module jax-light at import — the engine (and so jax)
# loads inside main() AFTER _ensure_devices has set the virtual device
# count for --replicas/--mp; an eager engine import would pin the
# process to however many devices the environment happened to have
from .scheduler import Backpressure


def build_toy_inference(hidden: int = 64, layers: int = 2, vocab: int = 128,
                        heads: int = 4, seq_len: int = 256, mp: int = 1,
                        device_offset: int = 0):
    """Random-init tiny model wrapped for inference (no checkpoint).

    ``mp > 1`` builds the model on a model-parallel serving mesh (needs
    that many jax devices): params shard over the model axis, and the
    engine's pools and programs follow (docs/SERVING.md "The fleet").
    Weights are init-key deterministic, so the mp=1 and mp=2 builds of
    the same shape hold the SAME weights — the mp parity tests rely on
    that.

    ``device_offset`` places this instance's params (and mesh, at
    mp > 1) starting at that jax device: fleet replica ``r`` builds at
    offset ``r * mp``, so every replica owns its own device group and
    their tick programs genuinely run concurrently instead of queueing
    on device 0."""
    import jax

    from ..models.transformer import TransformerConfig
    from ..models.transformer.inference import TransformerInferenceModule
    from ..models.transformer.model import init_model

    config = TransformerConfig.from_dict({
        "topology": {
            "model_parallel_size": mp, "pipe_parallel_size": 1,
            "data_parallel_size": 1, "micro_batch_size": 1,
            "gradient_accumulation_steps": 1,
        },
        "transformer_architecture": {
            "vocab_size": vocab, "hidden_size": hidden, "num_layers": layers,
            "num_attention_heads": heads, "sequence_length": seq_len,
            "mlp_type": "swiglu", "mlp_factor": 2.0, "norm_type": "rms",
            "weight_tying": False,
        },
        "optimizer": {"gradient_clipping": 1.0},
        "learning_rate_scheduler": {
            "learning_rate": 3e-4, "learning_rate_warmup_steps": 10,
            "learning_rate_decay_iters": 100,
        },
        "trainer": {"train_iterations": 1, "seed": 0},
        "data": {}, "logger": {"log_dir": None},
    })
    topo = None
    if mp > 1 or device_offset > 0:
        if len(jax.devices()) < device_offset + mp:
            raise RuntimeError(
                f"mp={mp} at device offset {device_offset} needs "
                f"{device_offset + mp} jax devices, found "
                f"{len(jax.devices())} (off-TPU: set XLA_FLAGS="
                f"--xla_force_host_platform_device_count=N)"
            )
    if mp > 1:
        from ..topology import Topology

        topo = Topology(
            config.topology,
            devices=jax.devices()[device_offset:device_offset + mp],
        )
    module = init_model(config, topo)
    params = module.init_params(jax.random.PRNGKey(0))
    if topo is not None:
        params = module.shard_params(params)
    elif device_offset > 0:
        params = jax.device_put(params, jax.devices()[device_offset])
    return TransformerInferenceModule(config, module, params)


def sample_workload(n_requests: int, rate: float, prompt_len, output_len,
                    vocab: int, seed: int, shared_prefix_len: int = 0,
                    prefix_families: int = 1):
    """Poisson arrival offsets + per-request prompts/output budgets.

    ``shared_prefix_len > 0`` models the dominant real-traffic shape:
    requests draw one of ``prefix_families`` fixed system prompts of
    that length and append a random tail sampled from ``prompt_len`` —
    the prefix-cache arm of the benchmark (``--shared-prefix-len``)."""
    import numpy as np

    rng = np.random.default_rng(seed)
    prefixes = [
        rng.integers(1, vocab, size=shared_prefix_len).tolist()
        for _ in range(prefix_families)
    ] if shared_prefix_len > 0 else []
    gaps = rng.exponential(1.0 / rate, size=n_requests)
    arrivals = np.cumsum(gaps)
    arrivals[0] = 0.0  # the first request opens the run
    work = []
    for i in range(n_requests):
        plen = int(rng.integers(prompt_len[0], prompt_len[1] + 1))
        olen = int(rng.integers(output_len[0], output_len[1] + 1))
        tail = rng.integers(1, vocab, size=plen).tolist()
        prompt = (prefixes[i % prefix_families] + tail) if prefixes else tail
        work.append((float(arrivals[i]), prompt, olen))
    return work


def run_bench(engine, workload, time_scale: float = 1.0,
              max_wall_s: float = 600.0, tick_timeout_s: float = 0.0,
              extra_stats: Optional[dict] = None,
              carry: Optional[dict] = None) -> dict:
    """Open-loop drive: submit each request when the wall clock crosses
    its arrival offset, tick the engine continuously, drain. Returns the
    summary stats dict (also emitted as the ``serve-summary`` event).

    Resilience rails (docs/SERVING.md "Resilience"): a submission the
    engine sheds (watermark backpressure) is counted, not retried — the
    open-loop client models a router that took the hint elsewhere. When
    the engine flips to ``draining`` (SIGTERM), submission stops,
    in-flight requests run to completion or their deadlines, and the
    loop exits cleanly with the unsubmitted tail counted.
    ``tick_timeout_s > 0`` arms a tick-stall watchdog (the resilience
    ``StepStallWatchdog``): a tick that stops beating dumps thread
    stacks, logs a ``serve-stall`` event, and then SIGKILLs the process
    — a wedged tick (hung device, dead mount) is unrecoverable
    in-process, and dying loudly is what lets a ``--restarts``
    supervisor replay the journal instead of hanging forever behind a
    silent child. ``carry`` folds a crashed predecessor's terminal
    tallies (completed/timeouts/shed, from the journal replay) into
    the summary so the FINAL summary — the one the shed/timeout gates
    read — describes the whole run dir, not just the last process."""
    import os
    import signal as _signal

    from ..logging import logger
    from ..obs import get_registry, new_trace_id, span, trace_context

    watchdog = None
    if tick_timeout_s > 0:
        from ..resilience import StepStallWatchdog

        def _on_stall(tick, elapsed):
            from ..resilience.faults import get_fault_plan

            logger.log_event(
                "serve-stall", tick=tick, stalled_s=round(elapsed, 3)
            )
            get_fault_plan().fire("serve.stall.kill")
            with span("serve.stall.kill", tick=tick):
                os.kill(os.getpid(), _signal.SIGKILL)

        watchdog = StepStallWatchdog(tick_timeout_s, on_stall=_on_stall)
        watchdog.start()

    t0 = time.monotonic()
    start_ticks = engine.tick_index  # warmup ticks stay off the books
    pending = sorted(workload, key=lambda w: w[0])
    idx = 0
    try:
        while True:
            now = time.monotonic() - t0
            if now > max_wall_s:
                raise RuntimeError(
                    f"bench exceeded --max-wall-s={max_wall_s}: "
                    f"{idx}/{len(pending)} submitted, "
                    f"{len(engine.finished)} finished"
                )
            while not engine.draining and idx < len(pending) and \
                    pending[idx][0] * time_scale <= now:
                arrival, prompt, olen = pending[idx]
                # one fresh trace id per measured request at submit —
                # the origin of the distributed trace every downstream
                # span/event/journal record inherits (warmup traffic
                # runs outside any context and stays untraced)
                with trace_context(new_trace_id()):
                    res = engine.submit(
                        prompt, olen, arrival_s=t0 + arrival * time_scale
                    )
                if isinstance(res, Backpressure) and res.draining:
                    # SIGTERM raced this submission: it was never
                    # offered to a live engine — unsubmitted, not shed
                    break
                idx += 1
            if watchdog is not None:
                # beat every loop pass, idle waits included — the
                # watchdog watches for a WEDGED tick (the loop stuck
                # inside engine.tick() stops beating), not for a
                # healthy bench sleeping between Poisson arrivals
                watchdog.beat(engine.tick_index)
            if engine.scheduler.has_work:
                engine.tick()
            elif engine.draining or idx >= len(pending):
                break
            else:
                # idle until the next arrival (clamped: stay responsive)
                wait = pending[idx][0] * time_scale - (time.monotonic() - t0)
                if wait > 0:
                    time.sleep(min(wait, 0.05))
    finally:
        if watchdog is not None:
            watchdog.stop()

    wall_s = time.monotonic() - t0
    seqs = engine.finished
    completed = [s for s in seqs if s.finish_status == "completed"]
    ttfts = sorted(
        s.first_token_s - s.request.arrival_s for s in seqs
        if s.first_token_s is not None
    )
    itls: List[float] = []
    for s in seqs:
        itls.extend(b - a for a, b in zip(s.token_stamps, s.token_stamps[1:]))
    itls.sort()
    total_tokens = sum(len(s.generated) for s in seqs)

    # the SAME nearest-rank percentile `obs report` uses over the run
    # dir, so the self-gate here and the CI gate there can never
    # disagree about the same run's p99
    from ..obs.report import percentile

    def pct(vals, q):
        return percentile(vals, q) if vals else None

    prompt_tokens = sum(len(s.request.prompt) for s in seqs)
    # hits count every (re-)admission match (a preempted sequence
    # re-matching its own cached blocks included), so the rate is
    # work-avoided / work-demanded: hit / (hit + actually-prefilled) —
    # bounded [0, 1] even when preemptions force re-prefills
    hit = engine.scheduler.prefix_hit_tokens
    prefilled = engine.prefilled_tokens
    # cumulative across supervised relaunches: `carry` holds the
    # crashed predecessor runs' terminal tallies from the journal
    # replay, so the final summary — the one the shed/timeout gates
    # read — describes the WHOLE run dir, not just this process
    carry = carry or {}
    c_completed = int(carry.get("completed", 0))
    c_timeouts = int(carry.get("timeouts", 0))
    c_shed = int(carry.get("shed", 0))
    total_shed = engine.shed_count + c_shed
    total_timeouts = engine.timeout_count + c_timeouts
    attempts = total_shed + total_timeouts + len(completed) + c_completed
    stats = {
        "requests": len(completed) + c_completed,
        "requests_timeout": total_timeouts,
        "requests_shed": total_shed,
        "shed_rate": (
            round(total_shed / attempts, 4) if attempts else 0.0
        ),
        "drained": engine.draining,
        "unsubmitted": len(pending) - idx,
        "wall_s": round(wall_s, 6),
        "output_tokens": total_tokens,
        "prompt_tokens": prompt_tokens,
        "tokens_per_s": round(total_tokens / wall_s, 3) if wall_s > 0 else 0.0,
        "ttft_p50_s": pct(ttfts, 50),
        "ttft_p99_s": pct(ttfts, 99),
        "itl_p50_s": pct(itls, 50),
        "itl_p99_s": pct(itls, 99),
        "preemptions": engine.scheduler.preemption_count,
        "ticks": engine.tick_index - start_ticks,
        "prefill_compiles": engine.prefill_program_count,
        "programs_lowered_since_ready": engine.programs_lowered_since_ready,
        "max_concurrent_prefills": engine.max_concurrent_prefills,
        # prefill work actually paid after shared-prefix reuse
        "prefix_hit_tokens": hit,
        "prefix_hit_rate": (
            round(hit / (hit + prefilled), 4) if hit + prefilled else 0.0
        ),
        "prefilled_tokens": prefilled,
        "engine": engine_shape_stats(engine),
    }
    if extra_stats:
        stats.update(extra_stats)
    logger.log_event("serve-summary", **stats)
    get_registry().flush_step(engine.tick_index)
    return stats


def engine_shape_stats(engine, replicas: int = 1) -> dict:
    """The engine-shape facts the serve-summary carries so the tuner's
    serving cost model can calibrate against this run's measured spans
    (tune/serving.py ``ServeCalibration``)."""
    cfg = engine.config
    return {
        "mp": engine.model_parallel,
        "replicas": replicas,
        "num_slots": cfg.num_slots,
        "block_size": cfg.block_size,
        "num_blocks": cfg.num_blocks,
        "token_budget": cfg.token_budget,
        "prefill_chunk": cfg.prefill_chunk,
    }


def run_fleet_bench(router, workload, time_scale: float = 1.0,
                    max_wall_s: float = 600.0,
                    extra_stats: Optional[dict] = None,
                    carry: Optional[dict] = None,
                    fleet_journal=None) -> dict:
    """Open-loop drive of the FLEET (docs/SERVING.md "The fleet"): one
    Poisson arrival stream submits through the router (prefix-affinity /
    least-loaded / retry-elsewhere), while one tick thread per replica
    runs its engine's event loop — replicas tick CONCURRENTLY (each owns
    its own device group; the jitted tick releases the GIL), which is
    what makes fleet tokens/s scale with replicas instead of queueing N
    engines on one device.

    A submission the WHOLE fleet sheds is counted (and journaled into
    the fleet-level journal — replica journals only see their own
    admissions) and not retried; SIGTERM drains every replica and the
    loop exits cleanly once the last in-flight request finishes."""
    import threading

    from ..logging import logger
    from ..obs import get_registry, new_trace_id, trace_context
    from ..obs.report import percentile

    handles = list(router.replicas)
    engines = [h.engine for h in handles]
    start_ticks = {h.replica_id: h.engine.tick_index for h in handles}
    stop = threading.Event()
    # a replica thread dying must surface as THE bench error, not as a
    # silent hang until --max-wall-s (the survivors keep router.has_work
    # true forever for the dead replica's stranded requests)
    errors: List[BaseException] = []

    def tick_loop(handle):
        eng = handle.engine
        try:
            while not stop.is_set():
                if eng.scheduler.has_work:
                    with handle.lock:
                        if not eng.scheduler.has_work:
                            continue
                        eng.tick()
                else:
                    time.sleep(0.001)
        except BaseException as e:  # noqa: BLE001 — re-raised below
            errors.append(e)
            stop.set()

    threads = [
        threading.Thread(target=tick_loop, args=(h,), daemon=True,
                         name=f"serve-replica-{h.replica_id}")
        for h in handles if h.alive
    ]
    t0 = time.monotonic()
    for t in threads:
        t.start()
    pending = sorted(workload, key=lambda w: w[0])
    idx = 0
    shed = 0
    try:
        while True:
            if errors:
                raise RuntimeError(
                    "a replica tick thread died"
                ) from errors[0]
            now = time.monotonic() - t0
            if now > max_wall_s:
                raise RuntimeError(
                    f"fleet bench exceeded --max-wall-s={max_wall_s}: "
                    f"{idx}/{len(pending)} submitted, "
                    f"{sum(len(e.finished) for e in engines)} finished"
                )
            draining = any(h.engine.draining for h in handles if h.alive)
            while not draining and idx < len(pending) and \
                    pending[idx][0] * time_scale <= now:
                arrival, prompt, olen = pending[idx]
                # per-request trace origin (same contract as run_bench)
                with trace_context(new_trace_id()):
                    res = router.submit(
                        prompt, olen, arrival_s=t0 + arrival * time_scale
                    )
                if isinstance(res, Backpressure):
                    if res.draining:
                        # SIGTERM raced this submission: unsubmitted
                        draining = True
                        break
                    # the WHOLE fleet shed this offer: consumed,
                    # journaled at fleet level (so --resume skip math
                    # maps 1:1 onto workload items), AND counted on the
                    # unlabeled serve_requests_shed_total counter — the
                    # documented overload signal dashboards watch
                    # (replicas skip their counters via count_shed)
                    shed += 1
                    get_registry().counter(
                        "serve_requests_shed_total"
                    ).inc()
                    if fleet_journal is not None:
                        fleet_journal.record_shed(res.reason)
                idx += 1
            if (draining or idx >= len(pending)) and not router.has_work:
                break
            time.sleep(0.002)
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=60)

    wall_s = time.monotonic() - t0
    seqs = [s for e in engines for s in e.finished]
    completed = [s for s in seqs if s.finish_status == "completed"]
    ttfts = sorted(
        s.first_token_s - s.request.arrival_s for s in seqs
        if s.first_token_s is not None
    )
    itls: List[float] = []
    for s in seqs:
        itls.extend(b - a for a, b in zip(s.token_stamps, s.token_stamps[1:]))
    itls.sort()
    total_tokens = sum(len(s.generated) for s in seqs)

    def pct(vals, q):
        return percentile(vals, q) if vals else None

    carry = carry or {}
    c_completed = int(carry.get("completed", 0))
    c_timeouts = int(carry.get("timeouts", 0))
    c_shed = int(carry.get("shed", 0))
    total_shed = shed + c_shed
    total_timeouts = sum(e.timeout_count for e in engines) + c_timeouts
    attempts = total_shed + total_timeouts + len(completed) + c_completed
    hit = sum(e.scheduler.prefix_hit_tokens for e in engines)
    prefilled = sum(e.prefilled_tokens for e in engines)
    rstats = router.stats()
    replica_rows = []
    for h in handles:
        e = h.engine
        per = rstats["per_replica"].get(h.replica_id, {})
        replica_rows.append({
            "replica": h.replica_id,
            "alive": h.alive,
            "requests": sum(
                1 for s in e.finished if s.finish_status == "completed"
            ),
            "output_tokens": sum(len(s.generated) for s in e.finished),
            "timeouts": e.timeout_count,
            "ticks": e.tick_index - start_ticks[h.replica_id],
            "preemptions": e.scheduler.preemption_count,
            "pool_pressure": round(e.scheduler.pool_pressure(), 4),
            **per,
        })
    stats = {
        "requests": len(completed) + c_completed,
        "requests_timeout": total_timeouts,
        "requests_shed": total_shed,
        "shed_rate": (
            round(total_shed / attempts, 4) if attempts else 0.0
        ),
        "drained": any(e.draining for e in engines),
        "unsubmitted": len(pending) - idx,
        "wall_s": round(wall_s, 6),
        "output_tokens": total_tokens,
        "prompt_tokens": sum(len(s.request.prompt) for s in seqs),
        "tokens_per_s": round(total_tokens / wall_s, 3) if wall_s > 0 else 0.0,
        "ttft_p50_s": pct(ttfts, 50),
        "ttft_p99_s": pct(ttfts, 99),
        "itl_p50_s": pct(itls, 50),
        "itl_p99_s": pct(itls, 99),
        "preemptions": sum(e.scheduler.preemption_count for e in engines),
        "ticks": sum(
            e.tick_index - start_ticks[h.replica_id]
            for h, e in zip(handles, engines)
        ),
        "prefill_compiles": sum(e.prefill_program_count for e in engines),
        "max_concurrent_prefills": max(
            e.max_concurrent_prefills for e in engines
        ),
        "prefix_hit_tokens": hit,
        "prefix_hit_rate": (
            round(hit / (hit + prefilled), 4) if hit + prefilled else 0.0
        ),
        "prefilled_tokens": prefilled,
        "replicas": len(handles),
        "replica_stats": replica_rows,
        "router": rstats,
        "engine": engine_shape_stats(engines[0], replicas=len(handles)),
    }
    if extra_stats:
        stats.update(extra_stats)
    logger.log_event("serve-summary", **stats)
    get_registry().flush_step(max(e.tick_index for e in engines))
    return stats


def run_supervised(argv: List[str], args) -> int:
    """``--restarts N``: the serving counterpart of
    ``resilience.run_with_resume`` — run the bench as a child process
    and, when it dies (a ``serve.tick`` kill, an OOM, a wedged tick),
    relaunch it with ``--resume`` so the request journal replays: every
    incomplete request re-enqueues with its original id and regenerates
    token-for-token. Exits 0 the moment a child drains cleanly;
    re-raises the child's exit code once the budget is spent.

    A ``SCALING_TPU_FAULTS`` chaos plan arms the FIRST launch only:
    hit counters are per-process, so a persistent plan would kill every
    replay at the same tick and turn a bounded-restart drill into
    guaranteed budget exhaustion.

    SIGTERM to the supervisor is RELAYED to the running child (whose
    own drain handler finishes in-flight work and exits 0) and ends
    the supervision loop — the graceful-drain contract holds in the
    supervised deployment mode too, and no orphan keeps writing to the
    run dir. A child that dies mid-drain is not relaunched (mirroring
    the trainer supervisor's preemption rule)."""
    import os
    import signal
    import subprocess

    from ..logging import logger
    from ..obs import span
    from ..resilience.faults import get_fault_plan

    child_argv: List[str] = []
    skip = False
    for a in argv:
        if skip:
            skip = False
            continue
        if a == "--restarts":
            skip = True
            continue
        if a.startswith("--restarts="):
            continue
        child_argv.append(a)
    run_dir = Path(args.run_dir)
    run_dir.mkdir(parents=True, exist_ok=True)
    # the supervisor's own lifecycle events (serve-restart / give-up)
    # land in the same run dir the children write to
    os.environ.setdefault(
        "SCALING_TPU_EVENTS_PATH", str(run_dir / "events.jsonl")
    )
    env = dict(os.environ)
    state = {"child": None, "draining": False}

    def _relay(signum, frame):
        state["draining"] = True
        child = state["child"]
        if child is not None and child.poll() is None:
            child.send_signal(signal.SIGTERM)

    prev = signal.getsignal(signal.SIGTERM)
    signal.signal(signal.SIGTERM, _relay)
    attempts = 0
    try:
        while True:
            if state["draining"]:
                # SIGTERM landed while no child was running (e.g.
                # between a crash and the relaunch): relaunching would
                # serve the whole remaining workload with the drain
                # request silently ignored — stop here instead
                logger.log_event("serve-drain", supervisor=True)
                return 0
            cmd = [sys.executable, "-m", "scaling_tpu.serve", "bench",
                   *child_argv]
            if attempts > 0 and "--resume" not in child_argv:
                cmd.append("--resume")
            get_fault_plan().fire("serve.supervisor.spawn")
            with span("serve.supervisor.spawn", attempt=attempts):
                state["child"] = subprocess.Popen(cmd, env=env)
            if state["draining"]:
                # the signal raced the launch: the handler saw no child
                state["child"].send_signal(signal.SIGTERM)
            rc = state["child"].wait()
            state["child"] = None
            if rc == 0:
                return 0
            if state["draining"]:
                logger.log_event("serve-drain-failed", rc=rc)
                return rc if rc > 0 else 1
            attempts += 1
            if attempts > args.restarts:
                logger.log_event(
                    "serve-give-up", attempts=attempts - 1, rc=rc,
                )
                return rc if rc > 0 else 1
            logger.log_event("serve-restart", attempt=attempts, rc=rc)
            env.pop("SCALING_TPU_FAULTS", None)
    finally:
        signal.signal(signal.SIGTERM, prev)


def _run_fleet(args, infs, workload, journal_base, make_engine,
               warmup_engine) -> dict:
    """Fleet mode (``--replicas N``): N engines behind the
    prefix-affinity router, per-replica journal namespaces, SIGTERM
    drain fan-out, one Poisson stream through ``run_fleet_bench``."""
    from ..logging import logger
    from .journal import journal_path, open_journal, replay_journal
    from .router import FleetRouter, install_fleet_drain_handler

    engines = [
        make_engine(replica_id=r, inf_override=infs[r])
        for r in range(args.replicas)
    ]
    router = FleetRouter(engines)
    install_fleet_drain_handler(router)
    fleet_journal = None
    fleet_replay = None
    replays = {}
    if not args.no_journal:
        for r, eng in enumerate(engines):
            jr, rep = open_journal(journal_base, args.resume, replica_id=r)
            eng.attach_journal(jr)
            replays[r] = rep
        # the fleet-level journal records only whole-fleet sheds (every
        # replica said Backpressure): the resume skip math needs one
        # record per CONSUMED workload item, and a shed offer produced
        # no submit record in any replica journal
        fleet_journal, fleet_replay = open_journal(journal_base, args.resume)
    elif args.resume:
        for r in range(args.replicas):
            replays[r] = replay_journal(journal_path(journal_base, r))
        fleet_replay = replay_journal(journal_base)
    if args.warmup > 0:
        for eng in engines:
            warmup_engine(eng)
    extra_stats = None
    carry = None
    offered = sum(
        rep.offered_count for rep in replays.values() if rep is not None
    ) + (fleet_replay.shed_count if fleet_replay is not None else 0)
    if args.resume and offered:
        incomplete_total = completed_total = timeout_total = 0
        for r in sorted(replays):
            rep = replays[r]
            if rep is None:
                continue
            eng = engines[r]
            eng._next_req_id = rep.next_req_id
            # each replica replays its OWN journal namespace: original
            # req_ids keep the sampler-key fold, so the regenerated
            # tokens are the ones the crashed replica would have emitted
            for rec in rep.incomplete:
                # a journaled request resumes its pre-crash trace
                # (None for legacy journals — stays untraced)
                eng.submit(
                    rec["prompt"], rec["max_new_tokens"],
                    eos_token_id=rec.get("eos_token_id"),
                    temperature=rec.get("temperature", 0.0),
                    top_k=rec.get("top_k"), top_p=rec.get("top_p"),
                    deadline_ms=rec.get("deadline_ms"),
                    ttft_deadline_ms=rec.get("ttft_deadline_ms"),
                    req_id=int(rec["req"]), force=True,
                    trace=rec.get("trace"),
                )
            incomplete_total += len(rep.incomplete)
            completed_total += len(rep.completed)
            timeout_total += rep.timeout_count
        router.sync_next_req_id()
        workload = sorted(workload, key=lambda w: w[0])[offered:]
        if workload:
            base = workload[0][0]
            workload = [(a - base, p, o) for a, p, o in workload]
        extra_stats = {
            "resumed": True,
            "replayed_incomplete": incomplete_total,
            "replayed_completed": completed_total,
        }
        carry = {
            "completed": completed_total,
            "timeouts": timeout_total,
            "shed": (
                fleet_replay.shed_count if fleet_replay is not None else 0
            ),
        }
        logger.log_event(
            "serve-resume", incomplete=incomplete_total,
            completed=completed_total, remaining_workload=len(workload),
        )
    return run_fleet_bench(
        router, workload, max_wall_s=args.max_wall_s,
        extra_stats=extra_stats, carry=carry, fleet_journal=fleet_journal,
    )


def _fleet_capacity_tick(client, sup, router, plan, host_of,
                         leases, counters) -> None:
    """One arbitration pass on the elastic capacity channel
    (``--capacity-dir``, ``resilience.capacity``): heartbeat the
    fleet's demand, take delivery of granted leases, give reclaimed
    hosts back.

    - **demand**: max pool pressure across alive replicas + total queue
      depth — the signal the training-side ``CapacityManager`` sustains
      over before borrowing or reclaiming a host.
    - **granted**: admit the leased host into the placement plan, spawn
      one replica pinned there, then mark the lease ``active``. A
      failed spawn leaves the lease ``granted`` — retried next tick,
      and expired back to training by the manager if the fleet dies.
    - **reclaiming**: drain the host's replicas through the supervisor
      (clean retire, journal harvested); once every one has actually
      exited, write ``released`` and drop the host from the plan —
      training upsizes back over it.
    """
    from ..logging import logger

    alive = [h for h in router.replicas if h.alive and not h.retired]
    pressure = max(
        (float(h.last_stats.get("pool_pressure", 0.0)) for h in alive),
        default=0.0,
    )
    queue = sum(int(h.last_stats.get("waiting", 0)) for h in alive)
    client.publish(pressure=pressure, queue=queue, replicas=len(alive))
    for lease in client.granted():
        if lease.host in leases:
            continue  # already spawning/active for this grant
        hid = None
        if plan is not None:
            hid = plan.add_host(lease.host, lease.slots).host_id
            # pin BEFORE the spawn so the placement closure lands the
            # new replica on the leased host, not the least-loaded one
            host_of[max(h.replica_id for h in router.replicas) + 1] = hid
        rid = sup.spawn_replica()
        if rid is None:
            if plan is not None:
                plan.remove_host(lease.host, lease.slots)
            continue  # lease stays granted; retried next tick
        try:
            active = client.activate(lease)
        except Exception as e:
            # activation write failed (injected capacity.lease fault or
            # sick channel): the replica must not squat on a host the
            # manager will expire back to training — retire it now
            logger.warning(
                f"lease activation for {lease.host} failed ({e!r}); "
                "draining the replica"
            )
            sup.drain_replica(rid, reason="capacity-activate-failed")
            if plan is not None:
                plan.remove_host(lease.host, lease.slots)
            continue
        leases[lease.host] = {"lease": active, "replicas": [rid]}
        counters["activated"] += 1
    for lease in client.reclaiming():
        rec = leases.get(lease.host)
        rids = list(rec["replicas"]) if rec else []
        still_running = []
        for rid in rids:
            try:
                h = router.replica(rid)
            except (KeyError, ValueError):
                continue
            if h.alive and not h.retired:
                sup.drain_replica(rid, reason="capacity-reclaim")
            if h.proc.poll() is None:
                still_running.append(rid)
        if still_running:
            continue  # release only after the host is actually clear
        client.release(lease)
        if plan is not None:
            plan.remove_host(lease.host, lease.slots)
        leases.pop(lease.host, None)
        counters["released"] += 1


def _run_fleet_proc(args, workload, run_dir, journal_base) -> dict:
    """Process-isolated fleet mode (``--replicas-proc N``,
    docs/SERVING.md "Process mode"): every replica is a SUBPROCESS
    behind the same router policy, supervised by
    ``replica_proc.FleetSupervisor`` — a SIGKILLed replica's journal is
    harvested, its incomplete requests re-dispatch to survivors
    token-exactly, and the process relaunches on budgeted backoff; with
    ``--autoscale`` the supervisor also spawns under sustained pressure
    and drains at sustained idle.

    The HOST stays jax-free and single-threaded: submissions, polling,
    and supervision all run on this loop (each worker process owns its
    own devices, so nothing here needs the threaded fleet's per-replica
    tick threads or their lock discipline). Finished requests ship back
    via cursor-based ``poll`` RPCs; the summary's ``outputs`` map
    (req_id -> tokens) is what the chaos drill diffs against a
    fault-free run."""
    import os
    import signal
    import subprocess
    from concurrent.futures import ThreadPoolExecutor

    from ..logging import logger
    from ..obs import get_registry, new_trace_id, span, trace_context
    from ..obs.report import percentile
    from ..resilience.faults import get_fault_plan
    from .journal import RequestJournal
    from .replica_proc import (
        FleetSupervisor,
        read_rendezvous,
        rendezvous_file,
        spawn_replica_proc,
    )
    from .router import AutoscalePolicy, FleetRouter, ReplicaUnreachable

    # fresh run: stale journals from a previous drill in this dir (ANY
    # replica id — an earlier run may have autoscaled further) would
    # poison failover harvests
    for stale in run_dir.glob(f"{journal_base.stem}*{journal_base.suffix}"):
        stale.unlink()
    fleet_journal = RequestJournal(journal_base)

    # ---- host mode (--hostsfile, docs/SERVING.md "Host mode") ----
    # replicas spawn across the hostsfile's machines (ssh for remote
    # hosts, local exec for localhost entries), publish their host:port
    # into the run dir's rendezvous file, and the control plane's flag
    # files carry drain/abort to workers a partition has cut off from
    # RPC. Placement rides the tuner's PlacementPlan: relaunches pin
    # their recorded host, autoscale spawns go to the least-loaded
    # feasible host.
    plan = None
    control = None
    host_of: dict = {}  # replica_id -> host_id (sticky across relaunch)
    if args.hostsfile:
        from ..resilience.controlplane import (
            FileControlPlane,
            log_clock_offset,
        )
        from ..runner.config import RunnerConfig
        from ..runner.runner import get_resource_pool
        from ..tune.serving import PlacementPlan

        pool = get_resource_pool(RunnerConfig(
            hostsfile=args.hostsfile, default_gpu_count=1,
        ))
        plan = PlacementPlan.from_pool(pool)
        control = FileControlPlane(
            run_dir / "control", host_id=0, num_hosts=len(plan.hosts),
        )
        # the router host's skew stamp (workers stamp their own): the
        # pair is what obs trace aligns cross-host timelines with
        log_clock_offset(control)
        rdv = rendezvous_file(run_dir)
        if rdv.exists():
            # a previous drill's entries would satisfy ready-waits with
            # dead addresses
            rdv.unlink()
    worker_cfg = {
        "journal_base": str(journal_base),
        "metrics_path": str(run_dir / "metrics.jsonl"),
        "warmup": args.warmup,
        "toy": {"hidden": args.hidden, "layers": args.layers,
                "vocab": args.vocab, "heads": args.heads},
        "engine": {
            "num_slots": args.num_slots, "block_size": args.block_size,
            "num_blocks": args.num_blocks,
            "max_blocks_per_seq": args.max_blocks_per_seq,
            "token_budget": args.token_budget, "kv_dtype": args.kv_dtype,
            "prefill_chunk": args.prefill_chunk,
            "enable_prefix_cache": not args.no_prefix_cache,
            "default_deadline_ms": args.deadline_ms,
            "default_ttft_deadline_ms": args.ttft_deadline_ms,
            "shed_high_watermark": args.shed_high_watermark,
            "shed_low_watermark": args.shed_low_watermark,
            "max_waiting": args.max_waiting,
        },
    }
    if plan is not None:
        worker_cfg["control_dir"] = str(run_dir / "control")
        worker_cfg["num_hosts"] = len(plan.hosts)
    chaos_env = dict(os.environ)
    clean_env = dict(os.environ)
    # a chaos plan arms the INITIAL spawns only: hit counters are
    # per-process, so a relaunched or autoscaled worker re-armed with
    # the same plan would die at the same hit forever
    # (run_supervised's rule)
    clean_env.pop("SCALING_TPU_FAULTS", None)

    def spawn(replica_id, env=None):
        kw = {}
        if plan is not None:
            hid = host_of.get(replica_id)
            if hid is None:
                # a NEW replica (autoscale): least-loaded feasible host;
                # a relaunch found its pin above and never re-places
                counts: dict = {}
                for hh in host_of.values():
                    counts[hh] = counts.get(hh, 0) + 1
                hid = plan.next_host(counts)
                if hid is None:
                    # every host is slot-full: land on the least loaded
                    # rather than refuse the spawn (oversubscription
                    # beats a stranded relaunch)
                    hid = min(
                        plan.hosts,
                        key=lambda h: (counts.get(h.host_id, 0),
                                       h.host_id),
                    ).host_id
                host_of[replica_id] = hid
            kw = {"hostname": plan.hostname(hid), "host_id": hid}
        return spawn_replica_proc(
            replica_id, worker_cfg, run_dir,
            env=clean_env if env is None else env, **kw,
        )

    drain_req = {"flag": False}

    def _drain_sig(signum, frame):
        # flag only: RPC fan-out happens on the loop, not in the handler
        drain_req["flag"] = True

    # Install before spawning: workers log serve-replica-ready the
    # moment they publish their addr, which is before spawn() returns
    # on the host — a drain signal sent at first-ready must not hit the
    # default SIGTERM disposition and kill the bench under its workers.
    prev = signal.signal(signal.SIGTERM, _drain_sig)

    if plan is not None:
        # place the initial fleet up front (same least-loaded rule the
        # autoscale spawn uses) — infeasible fleets fail loudly here
        for r, hid in enumerate(plan.initial_assignment(args.replicas_proc)):
            host_of[r] = hid
    # parallel launch: every worker pays its cold jit warmup at once
    with ThreadPoolExecutor(max_workers=args.replicas_proc) as ex:
        handles = list(ex.map(
            lambda r: spawn(r, chaos_env), range(args.replicas_proc)
        ))
    router = FleetRouter(handles=handles, block_size=args.block_size)
    policy = None
    if args.autoscale:
        policy = AutoscalePolicy(
            min_replicas=args.min_replicas,
            max_replicas=args.max_replicas,
            sustain_s=args.autoscale_sustain_s,
            idle_sustain_s=args.autoscale_idle_s,
            cooldown_s=args.autoscale_sustain_s,
        )
    recs: dict = {}  # req_id -> finished record, newest wins

    def harvest(handle):
        try:
            for rec in handle.poll_finished():
                recs[int(rec["req"])] = rec
        except ReplicaUnreachable:
            pass  # dead replica: the journal harvest owns its outputs

    sup = FleetSupervisor(
        router, spawn, journal_base,
        restart_budget=args.restart_budget,
        policy=policy, on_drain=harvest,
    )
    # ---- elastic capacity (--capacity-dir, docs/RESILIENCE.md
    # "Elastic capacity") ---- the fleet joins the training
    # supervisor's capacity channel: demand heartbeats feed the
    # arbitration manager, granted leases spawn replicas on the
    # borrowed host, reclaims drain them and hand the host back.
    cap_client = None
    cap_leases: dict = {}  # host -> {"lease": Lease, "replicas": [id]}
    cap_counters = {"activated": 0, "released": 0}
    if args.capacity_dir:
        from ..resilience.capacity import CapacityChannel, FleetCapacityClient

        cap_client = FleetCapacityClient(
            CapacityChannel(Path(args.capacity_dir)),
            publish_interval_s=args.capacity_publish_s,
        )
    pending = sorted(workload, key=lambda w: w[0])
    idx = 0
    shed = 0
    draining = False
    t0 = time.monotonic()
    last_sup = -1.0
    try:
        while True:
            now = time.monotonic() - t0
            if now > args.max_wall_s:
                raise RuntimeError(
                    f"proc fleet bench exceeded --max-wall-s="
                    f"{args.max_wall_s}: {idx}/{len(pending)} submitted, "
                    f"{len(recs)} finished"
                )
            if drain_req["flag"] and not draining:
                draining = True
                logger.log_event(
                    "serve-drain", fleet=True, replicas=len(router.live),
                )
                if control is not None:
                    # the control-plane flag reaches workers a partition
                    # has cut off from the RPC fan-out below
                    control.set_flag("serve-drain")
                router.begin_drain()
            if now - last_sup >= 0.05:
                last_sup = now
                sup.tick()
                if cap_client is not None and not draining:
                    _fleet_capacity_tick(
                        cap_client, sup, router, plan, host_of,
                        cap_leases, cap_counters,
                    )
                for h in router.replicas:
                    if h.alive and not h.retired:
                        harvest(h)
            if sup.gave_up and not router.live:
                raise RuntimeError(
                    "every replica exhausted its restart budget; "
                    f"{len(sup.orphans)} request(s) stranded"
                )
            while not draining and idx < len(pending) \
                    and pending[idx][0] <= now:
                arrival, prompt, olen = pending[idx]
                # per-request trace origin: the RPC envelope carries it
                # to the worker, whose dispatch adopts it (one trace per
                # request across every process in the fleet)
                with trace_context(new_trace_id()):
                    res = router.submit(prompt, olen)
                if isinstance(res, Backpressure):
                    if res.draining:
                        draining = True  # SIGTERM raced this submission
                        break
                    shed += 1
                    get_registry().counter(
                        "serve_requests_shed_total"
                    ).inc()
                    fleet_journal.record_shed(res.reason)
                idx += 1
            if (draining or idx >= len(pending)) and not router.has_work \
                    and not sup.pending_recovery():
                break
            time.sleep(0.002)
        # autoscale settle: hold the fleet at idle long enough for the
        # policy's idle-drain to fire (the drill pins "drains at idle
        # within budget") — bounded by the wall clock
        if policy is not None and not draining:
            deadline = min(
                time.monotonic()
                + (policy.idle_sustain_s + policy.cooldown_s) * 2 + 1.0,
                t0 + args.max_wall_s,
            )
            while (sum(1 for h in router.replicas
                       if h.alive and not h.retired) > policy.min_replicas
                   and policy.drains < policy.drain_budget
                   and time.monotonic() < deadline):
                sup.tick()
                time.sleep(0.02)
        wall_s = time.monotonic() - t0
        for h in router.replicas:
            if h.alive and not h.retired:
                try:
                    h.refresh()
                except ReplicaUnreachable:
                    pass
                harvest(h)
                h.request_shutdown()
        get_fault_plan().fire("serve.fleet.teardown")
        with span("serve.fleet.teardown"):
            for h in router.replicas:
                if h.proc.poll() is None:
                    try:
                        h.proc.wait(timeout=30)
                    except subprocess.TimeoutExpired:
                        logger.warning(
                            f"replica {h.replica_id} ignored shutdown; "
                            "killing"
                        )
                        h.proc.kill()
    except BaseException:
        if control is not None:
            # abort rides the control-plane rails: workers a partition
            # (or a dead ssh channel) cut off from RPC still see the
            # flag file and exit instead of orphaning on their host
            try:
                control.set_flag("serve-abort")
            except OSError:
                pass
        raise
    finally:
        signal.signal(signal.SIGTERM, prev)
        with span("serve.fleet.teardown", phase="finally"):
            for h in router.replicas:
                if h.proc.poll() is None:
                    # no orphan keeps writing to the run dir — kill()
                    # reaches through ssh for remote-host replicas
                    h.kill()

    completed = {
        r: rec for r, rec in recs.items() if rec["status"] == "completed"
    }
    # outputs = polled records, with the failover harvest filling in
    # requests whose serving replica died after finishing them
    outputs = {int(r): list(t) for r, t in sup.recovered.items()}
    outputs.update({r: list(rec["toks"]) for r, rec in completed.items()})
    timeouts = sup.recovered_timeouts + sum(
        1 for rec in recs.values() if rec["status"] == "timeout"
    )
    attempts = shed + timeouts + len(outputs)
    output_tokens = sum(len(rec["toks"]) for rec in recs.values()) + sum(
        len(t) for r, t in sup.recovered.items() if r not in recs
    )
    ttfts = sorted(
        rec["ttft_s"] for rec in recs.values()
        if rec.get("ttft_s") is not None
    )
    itls = sorted(g for rec in recs.values() for g in rec.get("itls", ()))

    def pct(vals, q):
        return percentile(vals, q) if vals else None

    rstats = router.stats()
    agg_keys = ("preemptions", "prefix_hit_tokens", "prefilled_tokens",
                "prefill_compiles")
    agg = dict.fromkeys(agg_keys, 0)
    ticks = 0
    max_prefills = 0
    submit_dups = 0
    rpc_retries = 0
    replica_rows = []
    for h in router.replicas:
        s = h.last_stats
        h_ticks = h.ticks_banked + int(s.get("tick", 0))
        ticks += h_ticks
        for k in agg:
            agg[k] += int(s.get(k, 0))
        max_prefills = max(
            max_prefills, int(s.get("max_concurrent_prefills", 0))
        )
        submit_dups += h.last_dups
        rpc_retries += h.rpc_retries
        replica_rows.append({
            "replica": h.replica_id,
            "host": h.host_id,
            "alive": h.alive,
            "retired": h.retired,
            "restarts": h.restarts,
            "dups": h.last_dups,
            "rpc_retries": h.rpc_retries,
            "requests": int(s.get("completed", 0)),
            "output_tokens": int(s.get("output_tokens", 0)),
            "timeouts": int(s.get("timeout_count", 0)),
            "ticks": h_ticks,
            "preemptions": int(s.get("preemptions", 0)),
            "pool_pressure": round(float(s.get("pool_pressure", 0.0)), 4),
            **rstats["per_replica"].get(h.replica_id, {}),
        })
    hit = agg["prefix_hit_tokens"]
    prefilled = agg["prefilled_tokens"]
    stats = {
        "requests": len(outputs),
        "requests_timeout": timeouts,
        "requests_shed": shed,
        "shed_rate": round(shed / attempts, 4) if attempts else 0.0,
        "drained": draining,
        "unsubmitted": len(pending) - idx,
        "wall_s": round(wall_s, 6),
        "output_tokens": output_tokens,
        "prompt_tokens": sum(
            int(rec.get("prompt_len", 0)) for rec in recs.values()
        ),
        "tokens_per_s": (
            round(output_tokens / wall_s, 3) if wall_s > 0 else 0.0
        ),
        "ttft_p50_s": pct(ttfts, 50),
        "ttft_p99_s": pct(ttfts, 99),
        "itl_p50_s": pct(itls, 50),
        "itl_p99_s": pct(itls, 99),
        "preemptions": agg["preemptions"],
        "ticks": ticks,
        "prefill_compiles": agg["prefill_compiles"],
        "max_concurrent_prefills": max_prefills,
        "prefix_hit_tokens": hit,
        "prefix_hit_rate": (
            round(hit / (hit + prefilled), 4) if hit + prefilled else 0.0
        ),
        "prefilled_tokens": prefilled,
        "replicas": len(router.replicas),
        "replica_stats": replica_rows,
        "router": rstats,
        "engine": {
            "mp": 1, "replicas": len(router.replicas),
            "num_slots": args.num_slots, "block_size": args.block_size,
            "num_blocks": args.num_blocks,
            "token_budget": args.token_budget,
            "prefill_chunk": args.prefill_chunk,
        },
        # the process-fleet story (obs report's fleet section + the
        # --assert-max-replica-restarts gate read these)
        "proc_fleet": True,
        "replica_restarts": sup.restarts,
        "replica_spawns": policy.spawns if policy else 0,
        "replica_drains": policy.drains if policy else 0,
        "recovered_requests": len(sup.recovered),
        "redispatched_requests": sup.redispatched,
        "replicas_gave_up": len(sup.gave_up),
        # partition-drill counters: worker-side dedup hits (an RPC retry
        # or in-doubt re-offer the engine had already admitted) and
        # client-side transport retries
        "submit_dups": submit_dups,
        "rpc_retries": rpc_retries,
    }
    if cap_client is not None:
        # the arbitration story: borrowed-host leases this fleet
        # activated and handed back (docs/RESILIENCE.md)
        stats["capacity_leases_activated"] = cap_counters["activated"]
        stats["capacity_leases_released"] = cap_counters["released"]
        stats["capacity_leases_open"] = len(cap_leases)
    if plan is not None:
        # the host-mode story: which hosts the plan expected vs which
        # actually rendezvoused (obs report's never-reported gate)
        stats["fleet_hosts"] = [h.host_id for h in plan.hosts]
        try:
            reported = read_rendezvous(rendezvous_file(run_dir))
        except OSError:
            reported = {}
        stats["hosts_reported"] = sorted({
            int(rec["host"]) for rec in reported.values()
            if rec.get("host") is not None
        })
    # the event rides WITHOUT the raw outputs map (events.jsonl is for
    # telemetry, not payloads); the returned stats / --json carry it for
    # the chaos drill's token-exact diff
    logger.log_event("serve-summary", **stats)
    stats["outputs"] = {str(r): outputs[r] for r in sorted(outputs)}
    get_registry().flush_step(ticks)
    return stats


def _apply_serving_config(args, argv: List[str], parser) -> None:
    """Fold a tuner-emitted serving config (``tune --serve
    --emit-config``) into the parsed args as DEFAULTS: any knob the user
    passed explicitly on the command line wins over the file."""
    from ..resilience.guards import retry_io

    try:
        cfg = json.loads(retry_io(
            Path(args.config).read_text, what="serving config read"
        ))
    except (OSError, ValueError) as e:
        parser.error(f"--config {args.config}: unreadable ({e})")
    passed = {
        a[2:].split("=", 1)[0].replace("-", "_")
        for a in argv if a.startswith("--")
    }
    for key in ("mp", "replicas", "block_size", "token_budget",
                "num_slots", "num_blocks", "max_blocks_per_seq"):
        if key in cfg and key not in passed:
            setattr(args, key, int(cfg[key]))


def _ensure_devices(need: int) -> None:
    """The fleet needs ``replicas * mp`` jax devices. Off-TPU, force the
    virtual host-platform device count BEFORE the first jax import (the
    flag is inert after backend init — if jax is already up with too few
    devices, fail actionably instead of queueing every replica on
    device 0)."""
    import os

    if need <= 1:
        return
    flags = os.environ.get("XLA_FLAGS", "")
    if ("jax" not in sys.modules
            and "--xla_force_host_platform_device_count" not in flags):
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={need}"
        ).strip()
    import jax

    if len(jax.devices()) < need:
        raise SystemExit(
            f"error: --replicas x --mp needs {need} devices, found "
            f"{len(jax.devices())}; off-TPU set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={need} before launch"
        )


def main(argv: Optional[List[str]] = None) -> int:
    """The bench, with SIGTERM's handler put back as it was found: the
    single engine chains a drain handler onto it
    (``install_drain_handler``), and a caller IN PROCESS (a test, a
    notebook) must not keep a handler that drains an engine long gone."""
    import signal

    found = signal.getsignal(signal.SIGTERM)
    try:
        return _main(argv)
    finally:
        if found is not None and signal.getsignal(signal.SIGTERM) is not found:
            signal.signal(signal.SIGTERM, found)


def _main(argv: Optional[List[str]]) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m scaling_tpu.serve bench",
        description="continuous-batching serving benchmark (docs/SERVING.md)",
        # no prefix abbreviations: _apply_serving_config decides which
        # knobs the user passed explicitly by scanning argv, and an
        # abbreviated flag would dodge the scan and lose to --config
        allow_abbrev=False,
    )
    parser.add_argument("--requests", type=int, default=16)
    parser.add_argument("--rate", type=float, default=8.0,
                        help="Poisson arrival rate, requests/second")
    parser.add_argument("--prompt-len", type=int, nargs=2, default=(4, 24),
                        metavar=("MIN", "MAX"))
    parser.add_argument("--output-len", type=int, nargs=2, default=(4, 16),
                        metavar=("MIN", "MAX"))
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--run-dir", default="runs/serve_bench",
                        help="telemetry output dir (events + metrics jsonl)")
    # engine shape knobs (all land in the jitted programs' signatures)
    parser.add_argument("--num-slots", type=int, default=8)
    parser.add_argument("--block-size", type=int, default=16)
    parser.add_argument("--num-blocks", type=int, default=128)
    parser.add_argument("--max-blocks-per-seq", type=int, default=16)
    parser.add_argument("--token-budget", type=int, default=512)
    parser.add_argument("--kv-dtype", choices=["native", "int8"],
                        default="native")
    parser.add_argument("--prefill-chunk", type=int, default=32,
                        help="Sarathi-style chunked prefill: tokens per "
                        "chunk (prompts stream into the pool sharing the "
                        "tick budget with decodes)")
    # ---- the fleet (docs/SERVING.md "The fleet") ----
    parser.add_argument("--replicas", type=int, default=1,
                        help="data-parallel engine replicas behind the "
                        "prefix-affinity router; ONE Poisson stream "
                        "drives the fleet, each replica ticks on its own "
                        "device group (toy model only off-chip)")
    parser.add_argument("--mp", type=int, default=1,
                        help="model-parallel shards per replica: KV "
                        "pools shard over the model axis (each chip "
                        "holds n_kv/mp heads) and the tick programs run "
                        "SPMD; needs replicas*mp devices")
    # ---- process mode (docs/SERVING.md "Process mode") ----
    parser.add_argument("--replicas-proc", type=int, default=0,
                        metavar="N",
                        help="process-isolated fleet: N replica "
                        "SUBPROCESSES behind the router, supervised "
                        "in-run (SIGKILL a replica -> journal-exact "
                        "failover to survivors + budgeted relaunch); "
                        "replaces --replicas, toy model only, mp=1")
    parser.add_argument("--hostsfile", metavar="FILE",
                        help="with --replicas-proc: span the fleet over "
                        "the hosts listed here (runner hostsfile syntax, "
                        "slots= caps replicas per host). Remote hosts "
                        "spawn over ssh, workers publish host:port into "
                        "<run-dir>/rendezvous.jsonl, drain/abort ride "
                        "the control-plane flag files, and relaunches "
                        "pin their recorded host (docs/SERVING.md "
                        "\"Host mode\")")
    parser.add_argument("--autoscale", action="store_true",
                        help="with --replicas-proc: spawn a replica "
                        "under sustained fleet-wide pressure, drain one "
                        "at sustained idle (budgeted, never below "
                        "--min-replicas)")
    parser.add_argument("--min-replicas", type=int, default=1,
                        help="autoscale floor (drains stop here)")
    parser.add_argument("--max-replicas", type=int, default=4,
                        help="autoscale ceiling (spawns stop here)")
    parser.add_argument("--autoscale-sustain-s", type=float, default=2.0,
                        help="seconds the whole fleet must stay above "
                        "the high watermark before a spawn (also the "
                        "action cooldown)")
    parser.add_argument("--autoscale-idle-s", type=float, default=5.0,
                        help="seconds the whole fleet must stay idle "
                        "before a drain")
    parser.add_argument("--restart-budget", type=int, default=3,
                        help="with --replicas-proc: supervised "
                        "relaunches allowed per replica before the "
                        "supervisor gives it up")
    parser.add_argument("--capacity-dir", metavar="DIR",
                        help="with --replicas-proc: join the elastic "
                        "capacity channel at DIR (the training "
                        "supervisor's <control_dir>/capacity — "
                        "docs/RESILIENCE.md \"Elastic capacity\"). The "
                        "fleet heartbeats its pool pressure there; the "
                        "training-side arbiter answers sustained "
                        "pressure by LEASING a training host (the fleet "
                        "spawns a replica on it and activates the "
                        "lease) and reclaims it at sustained idle (the "
                        "fleet drains that host's replicas, then "
                        "releases)")
    parser.add_argument("--capacity-publish-s", type=float, default=0.5,
                        help="demand-heartbeat period on the capacity "
                        "channel")
    parser.add_argument("--config", metavar="FILE",
                        help="tuner-emitted serving config (python -m "
                        "scaling_tpu.tune --serve --emit-config): its "
                        "mp/replicas/block_size/token_budget/num_slots/"
                        "num_blocks become defaults; explicit flags win")
    parser.add_argument("--shared-prefix-len", type=int, default=0,
                        help="prefix-cache arm: every request shares one "
                        "of --prefix-families system prompts of this "
                        "length (0 = fully random prompts)")
    parser.add_argument("--prefix-families", type=int, default=1,
                        help="number of distinct shared prefixes for "
                        "--shared-prefix-len")
    parser.add_argument("--no-prefix-cache", action="store_true",
                        help="disable shared-prefix block reuse (the A/B "
                        "for --shared-prefix-len)")
    parser.add_argument("--warmup", type=int, default=0,
                        help="serve N throwaway requests (excluded from "
                        "stats) before the open-loop clock starts, so "
                        "first-tick jit compiles don't distort arrival "
                        "timing")
    # resilience knobs (docs/SERVING.md "Resilience")
    parser.add_argument("--deadline-ms", type=float, default=None,
                        help="per-request total deadline (ms from "
                        "arrival); expired requests are cancelled at the "
                        "next tick boundary with status 'timeout'")
    parser.add_argument("--ttft-deadline-ms", type=float, default=None,
                        help="per-request first-token deadline (ms)")
    parser.add_argument("--shed-high-watermark", type=float, default=None,
                        help="pool-pressure fraction above which new "
                        "submissions are shed with structured "
                        "backpressure (hysteresis down to "
                        "--shed-low-watermark); default: no shedding")
    parser.add_argument("--shed-low-watermark", type=float, default=None,
                        help="pool-pressure fraction at which shedding "
                        "stops again (defaults to the high watermark)")
    parser.add_argument("--max-waiting", type=int, default=None,
                        help="hard waiting-queue depth cap; submissions "
                        "beyond it are shed (default: unbounded)")
    parser.add_argument("--no-journal", action="store_true",
                        help="disable the crash-replay request journal "
                        "(<run-dir>/journal.jsonl)")
    parser.add_argument("--resume", action="store_true",
                        help="replay <run-dir>/journal.jsonl first: "
                        "re-enqueue incomplete requests (same req ids -> "
                        "token-identical continuations) and skip the "
                        "workload items already submitted")
    parser.add_argument("--restarts", type=int, default=0,
                        help="supervised mode: run the bench as child "
                        "processes, relaunching with --resume after a "
                        "crash, up to N restarts (the serving "
                        "run_with_resume)")
    parser.add_argument("--tick-timeout-s", type=float, default=0.0,
                        help="tick-stall watchdog: dump thread stacks + "
                        "log a serve-stall event when no tick completes "
                        "for this long (0 = off)")
    # toy model knobs / real checkpoint
    parser.add_argument("--hidden", type=int, default=64)
    parser.add_argument("--layers", type=int, default=2)
    parser.add_argument("--vocab", type=int, default=128)
    parser.add_argument("--heads", type=int, default=4)
    parser.add_argument("--checkpoint", help="serve a real checkpoint dir "
                        "instead of the random toy model")
    parser.add_argument("--max-wall-s", type=float, default=600.0)
    parser.add_argument("--json", metavar="FILE",
                        help="also write the summary stats as JSON")
    parser.add_argument("--assert-serve-throughput", type=float,
                        metavar="FLOOR",
                        help="fail (exit 1) when output tokens/s is below "
                        "FLOOR (same gate `obs report` applies to the "
                        "run dir)")
    parser.add_argument("--assert-ttft", type=float, metavar="CEIL",
                        help="fail (exit 1) when p99 time-to-first-token "
                        "exceeds CEIL seconds")
    argv = list(sys.argv[1:] if argv is None else argv)
    args = parser.parse_args(argv)
    if args.config:
        _apply_serving_config(args, argv, parser)
    if args.replicas_proc and args.restarts:
        parser.error("--replicas-proc supervises its replicas in-run "
                     "(relaunch + journal failover); --restarts "
                     "supervises the in-process bench — pick one")
    if args.restarts > 0:
        return run_supervised(argv, args)
    if args.requests < 1:
        parser.error("--requests must be >= 1")
    if args.rate <= 0:
        parser.error("--rate must be > 0")
    for flag, (lo, hi), floor in (("--prompt-len", args.prompt_len, 1),
                                  ("--output-len", args.output_len, 1)):
        if lo < floor or hi < lo:
            parser.error(f"{flag} needs {floor} <= MIN <= MAX, got {lo} {hi}")
    if args.replicas < 1 or args.mp < 1:
        parser.error("--replicas and --mp must be >= 1")
    fleet = args.replicas > 1
    if args.checkpoint and fleet:
        parser.error(
            "--replicas > 1 serves the toy model only (an in-process "
            "fleet of checkpoint-sized replicas is a dev harness, not a "
            "deployment; production runs one process per replica)"
        )
    proc_fleet = args.replicas_proc > 0
    if proc_fleet:
        if args.replicas_proc < 1:
            parser.error("--replicas-proc must be >= 1")
        if fleet:
            parser.error("--replicas-proc IS the fleet (subprocess "
                         "replicas); drop --replicas")
        if args.mp > 1:
            parser.error("--replicas-proc serves mp=1 replicas (each "
                         "worker process owns its own devices)")
        if args.checkpoint:
            parser.error("--replicas-proc serves the toy model only "
                         "(workers rebuild the model from the config "
                         "they are handed)")
        if args.resume:
            parser.error("--replicas-proc recovers in-run (the "
                         "supervisor harvests dead replicas' journals); "
                         "--resume is the in-process replay path")
        if args.no_journal:
            parser.error("--replicas-proc needs the journal — failover "
                         "replays it")
        if args.autoscale and args.min_replicas > args.replicas_proc:
            parser.error("--min-replicas exceeds --replicas-proc")
        if args.autoscale and args.max_replicas < args.min_replicas:
            parser.error("--max-replicas < --min-replicas")
    else:
        if args.hostsfile:
            parser.error("--hostsfile spans the PROCESS fleet over "
                         "machines; it needs --replicas-proc")
        _ensure_devices(args.replicas * args.mp)
    # the proc-fleet HOST never builds an engine: the jax-importing
    # modules load only in the worker subprocesses
    if not proc_fleet:
        from ..compile_cache import enable_compile_cache
        from .engine import EngineConfig, ServeEngine, install_drain_handler

        enable_compile_cache()

    import os

    run_dir = Path(args.run_dir)
    run_dir.mkdir(parents=True, exist_ok=True)
    # telemetry rails: events via the logger's env hook, metrics via the
    # registry's explicit sink (mirrors how the supervisor wires hosts)
    os.environ.setdefault(
        "SCALING_TPU_EVENTS_PATH", str(run_dir / "events.jsonl")
    )
    from ..obs import get_registry

    get_registry().configure(metrics_path=str(run_dir / "metrics.jsonl"))

    if proc_fleet:
        # workers build their own toy model from the handed config
        infs = []
        inf = None
        vocab = args.vocab
    elif args.checkpoint:
        from ..models.transformer.inference import TransformerInferenceModule

        topology = (
            {"model_parallel_size": args.mp} if args.mp > 1 else None
        )
        inf = TransformerInferenceModule.from_checkpoint(
            args.checkpoint, topology=topology
        )
        infs = [inf]
        vocab = inf.architecture.vocab_size
    else:
        # one model instance per replica, each on its own device group
        # (offset r*mp): deterministic init keys mean every replica holds
        # the SAME weights — a data-parallel serving fleet
        infs = [
            build_toy_inference(
                hidden=args.hidden, layers=args.layers, vocab=args.vocab,
                heads=args.heads, mp=args.mp, device_offset=r * args.mp,
            )
            for r in range(args.replicas)
        ]
        inf = infs[0]
        vocab = args.vocab

    cap = args.max_blocks_per_seq * args.block_size
    longest = (args.prompt_len[1] + args.shared_prefix_len
               + args.output_len[1])
    if longest > cap:
        print(
            f"error: prompt+output can reach {longest} tokens but the "
            f"block table holds {cap}; raise --max-blocks-per-seq or "
            "--block-size", file=sys.stderr,
        )
        return 2
    if args.shared_prefix_len > 0 and args.prefix_families < 1:
        parser.error("--prefix-families must be >= 1")

    def make_engine(replica_id=None, inf_override=None):
        return ServeEngine(inf_override or inf, EngineConfig(
            num_slots=args.num_slots, block_size=args.block_size,
            num_blocks=args.num_blocks,
            max_blocks_per_seq=args.max_blocks_per_seq,
            token_budget=args.token_budget, kv_dtype=args.kv_dtype,
            prefill_chunk=args.prefill_chunk,
            enable_prefix_cache=not args.no_prefix_cache,
            default_deadline_ms=args.deadline_ms,
            default_ttft_deadline_ms=args.ttft_deadline_ms,
            shed_high_watermark=args.shed_high_watermark,
            shed_low_watermark=args.shed_low_watermark,
            max_waiting=args.max_waiting,
            replica_id=replica_id,
        ))

    def warmup_engine(engine):
        # compile the tick programs off the clock: the first mixed-step
        # call jit-compiles for seconds, and an open-loop workload that
        # arrives during it measures the compiler, not the engine
        engine.warmup_mode = True
        for _ in range(args.warmup):
            engine.submit([1], 2)
        engine.run_until_done()
        engine.warmup_mode = False
        engine.finished.clear()

    workload = sample_workload(
        args.requests, args.rate, tuple(args.prompt_len),
        tuple(args.output_len), vocab, args.seed,
        shared_prefix_len=args.shared_prefix_len,
        prefix_families=args.prefix_families,
    )
    journal_base = run_dir / "journal.jsonl"

    if proc_fleet:
        stats = _run_fleet_proc(args, workload, run_dir, journal_base)
    elif fleet:
        stats = _run_fleet(args, infs, workload, journal_base, make_engine,
                           warmup_engine)
    else:
        engine = make_engine()
        # SIGTERM -> graceful drain: stop admitting, finish in-flight,
        # flush telemetry, exit 0 with a parseable run dir
        install_drain_handler(engine)
        replay = None
        if not args.no_journal:
            from .journal import open_journal

            # --resume folds the crashed run's journal first; a fresh run
            # truncates any stale one from a previous drill in this dir
            journal, replay = open_journal(journal_base, args.resume)
            engine.attach_journal(journal)
        elif args.resume:
            from .journal import replay_journal

            replay = replay_journal(journal_base)
        if args.warmup > 0:
            warmup_engine(engine)
        extra_stats = None
        carry = None
        if replay is not None and replay.offered_count:
            from ..logging import logger

            # crash-replay: re-enqueue every request without a terminal
            # status under its ORIGINAL id (the sampler keys fold the id,
            # so the regenerated tokens are the ones the crashed run would
            # have emitted), then serve the workload tail the crashed run
            # never reached. force=True: recovery work is never shed.
            incomplete = replay.incomplete
            engine._next_req_id = replay.next_req_id
            for rec in incomplete:
                engine.submit(
                    rec["prompt"], rec["max_new_tokens"],
                    eos_token_id=rec.get("eos_token_id"),
                    temperature=rec.get("temperature", 0.0),
                    top_k=rec.get("top_k"), top_p=rec.get("top_p"),
                    deadline_ms=rec.get("deadline_ms"),
                    ttft_deadline_ms=rec.get("ttft_deadline_ms"),
                    req_id=int(rec["req"]), force=True,
                    trace=rec.get("trace"),
                )
            # skip every workload item the crashed run(s) CONSUMED — both
            # admitted submissions and overload sheds (a shed offer was
            # answered with Backpressure; re-offering it would double-serve
            # the tail behind it)
            done = replay.offered_count
            workload = sorted(workload, key=lambda w: w[0])[done:]
            if workload:
                base = workload[0][0]  # the tail arrives from t=0 again
                workload = [(a - base, p, o) for a, p, o in workload]
            extra_stats = {
                "resumed": True,
                "replayed_incomplete": len(incomplete),
                "replayed_completed": len(replay.completed),
            }
            # the crashed run(s)' terminal tallies fold into this run's
            # summary so the gates judge the whole run dir
            carry = {
                "completed": len(replay.completed),
                "timeouts": replay.timeout_count,
                "shed": replay.shed_count,
            }
            logger.log_event(
                "serve-resume", incomplete=len(incomplete),
                completed=len(replay.completed),
                remaining_workload=len(workload),
            )
        stats = run_bench(
            engine, workload, max_wall_s=args.max_wall_s,
            tick_timeout_s=args.tick_timeout_s, extra_stats=extra_stats,
            carry=carry,
        )

    print("== serve bench ==")
    print(f"  requests={stats['requests']} wall={stats['wall_s']:.3f}s "
          f"ticks={stats['ticks']} preemptions={stats['preemptions']} "
          f"prefill_compiles={stats['prefill_compiles']}")
    if (stats["requests_shed"] or stats["requests_timeout"]
            or stats["drained"]):
        print(f"  resilience: shed={stats['requests_shed']} "
              f"(rate {stats['shed_rate']:.1%}) "
              f"timeouts={stats['requests_timeout']} "
              f"drained={stats['drained']} "
              f"unsubmitted={stats['unsubmitted']}")
    print(f"  hot path: prefill_chunk={args.prefill_chunk} "
          f"max_concurrent_prefills={stats['max_concurrent_prefills']}")
    if args.mp > 1:
        print(f"  sharding: mp={args.mp} (KV pools sharded over the "
              f"model axis, {args.mp}x less pool memory per chip)")
    if stats.get("replicas", 1) > 1:
        r = stats["router"]
        print(f"  fleet: replicas={stats['replicas']} "
              f"affinity_hits={r['affinity_dispatches']}/{r['dispatches']} "
              f"({r['affinity_hit_rate']:.1%}) "
              f"retries_elsewhere={r['retries_elsewhere']} "
              f"rejected={r['rejected']}")
        for row in stats["replica_stats"]:
            if row.get("retired"):
                mark = " [drained]"
            elif not row.get("alive", True):
                mark = " [FAILED]"
            else:
                mark = ""
            if row.get("restarts"):
                mark = f" restarts={row['restarts']}" + mark
            if row.get("host") is not None:
                mark = f" host={row['host']}" + mark
            print(f"    replica {row['replica']}: "
                  f"requests={row['requests']} "
                  f"tokens={row['output_tokens']} "
                  f"dispatches={row.get('dispatches', 0)} "
                  f"ticks={row['ticks']} "
                  f"pressure={row['pool_pressure']:.2f}" + mark)
    if stats.get("proc_fleet"):
        print(f"  supervision: restarts={stats['replica_restarts']} "
              f"spawns={stats['replica_spawns']} "
              f"drains={stats['replica_drains']} "
              f"recovered={stats['recovered_requests']} "
              f"redispatched={stats['redispatched_requests']}")
        if stats.get("fleet_hosts") is not None:
            print(f"  hosts: planned={stats['fleet_hosts']} "
                  f"reported={stats['hosts_reported']} "
                  f"submit_dups={stats['submit_dups']} "
                  f"rpc_retries={stats['rpc_retries']}")
    if stats["prefix_hit_tokens"]:
        print(f"  prefix cache: {stats['prefix_hit_tokens']} tokens hit, "
              f"{stats['prefilled_tokens']} prefilled "
              f"({stats['prompt_tokens']} prompt tokens submitted; "
              f"hit rate {stats['prefix_hit_rate']:.1%})")
    print(f"  output tokens/s: {stats['tokens_per_s']:.1f} "
          f"({stats['output_tokens']} tokens)")
    if stats["ttft_p50_s"] is not None:
        print(f"  ttft: p50={stats['ttft_p50_s']:.4f}s "
              f"p99={stats['ttft_p99_s']:.4f}s")
    if stats["itl_p50_s"] is not None:
        print(f"  itl:  p50={stats['itl_p50_s']:.4f}s "
              f"p99={stats['itl_p99_s']:.4f}s")
    print(f"  run dir: {run_dir} (analyze: python -m scaling_tpu.obs "
          f"report {run_dir})")

    if args.json:
        from ..resilience.guards import retry_io

        stats_text = json.dumps(stats, indent=1) + "\n"
        retry_io(
            lambda: Path(args.json).write_text(stats_text),
            what="bench stats write",
        )

    failures = []
    if (args.assert_serve_throughput is not None
            and stats["tokens_per_s"] < args.assert_serve_throughput):
        failures.append(
            f"assert-serve-throughput: {stats['tokens_per_s']:.1f} tokens/s "
            f"< floor {args.assert_serve_throughput:.1f}"
        )
    if args.assert_ttft is not None and (
            stats["ttft_p99_s"] is None
            or stats["ttft_p99_s"] > args.assert_ttft):
        failures.append(
            f"assert-ttft: p99 TTFT {stats['ttft_p99_s']}s "
            f"> ceiling {args.assert_ttft}s"
        )
    if args.assert_serve_throughput is not None or args.assert_ttft is not None:
        print("== gates ==")
        for f in failures:
            print(f"  FAIL {f}")
        if not failures:
            print("  PASS")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
