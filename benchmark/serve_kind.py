"""Kind ``serve``: the program's ``ServeEngine`` under an open loop.

Copied from ``scaling_tpu/serve/bench.py`` ``run_bench`` (submit each request
when the clock crosses its due time, tick while there is work, drain) and
``chip_smoke.py`` ``phase_serve`` (the engine sized from slots x context, one
warm-up request off the clock, the path-taken asserts), with what those
measure wrongly repaired: every request is timed FROM WHEN IT WAS DUE, the
generator's lateness is reported, and the window is long enough for its tails.
Under ``--trace 2`` the window and its drain run untraced, every number of the
window is taken, and only then are a few seconds of the same traffic traced.

Two choices are the traffic file's, by name: ``"backlog"`` (``BACKLOGS``: how
the window ends) and ``"tokens"`` (``TOKEN_RULES``: whose output tokens
``serve_tokens_per_s`` counts).
"""

from __future__ import annotations

import gc
import sys
import time

# The engine computes in bf16 through the paged cache; the reference in
# float32 with no cache, on the same bf16 weights, teacher-forced with the
# engine's tokens. With seeded random weights the logits at these widths are
# small (|logit| < 2) and come out of the engine's bf16 head in steps of
# 2**-7 = 0.008, after 16 layers of bf16 roundings, so near-ties break either
# way: each engine token must be within LOGIT_TOL of the reference's best
# logit at its position. The largest gap seen on the chip over ~11,000
# teacher-forced positions was 0.019 (PERF.md, Findings PR 24); the
# tolerance is 2.6 times that. A wrong position, block or mask picks a
# token far below the best; an int8 cache or fp8 matmul shifts every logit
# by more than the tolerance and fails within a few positions.
LOGIT_TOL = 0.05
# After the last arrival the run goes on until every counted request has had
# its first token, at most this long (a run is allowed run_seconds + 60 s
# in all). A request still decoding then is cut, not failed: its first token
# and its gaps so far count, and its tokens so far are checked like any
# other's. One that has no first token by then has failed.
DRAIN_CAP_S = 20.0
# How the window ends is the traffic's: ``"backlog": "fail"`` (the default)
# is the rule above, for traffic the engine is meant to keep up with.
# ``"cut"`` is for traffic offered above what the engine sustains, where a
# growing queue is the design: a counted request that the engine had not
# TAKEN (given a slot) when arrivals stopped is ``unserved``, neither failed
# nor checked, and the run goes on only until every request taken by then
# has its first token. Everything the engine took is held to the rule above.
BACKLOGS = ("fail", "cut")
# Whose output tokens ``serve_tokens_per_s`` counts is the traffic's too.
# ``"tokens": "all"`` (the default): every token stamped inside the window,
# whoever's. That is what the ENGINE completes, the metric of traffic offered
# above the knee, where every slot is full all through the window and a
# faster tick reads higher. ``"counted"``: the tokens of the counted requests
# (those due inside the window) alone, for traffic BELOW the knee: what the
# users who sent a request in the window got in the window. It is bounded by
# what the trace offers, and a faster engine can only raise it. Under "all"
# such a cell also counts the requests submitted before the window (the drawn
# history and the warm-up), which decode one token a tick whatever the load:
# the faster the tick, the more of their tokens are stamped BEFORE the window
# opens, and the reading fell as the engine got faster (PERF.md, Findings
# PR 29-30: 85.5 at a 45 ms tick, 79.7 at 27.5 ms).
TOKEN_RULES = ("all", "counted")
# --trace 2: when the window's numbers are taken, arrivals resume at the
# cell's rate and the capture starts once a tick carries a prefill chunk,
# or after this long at the latest: the traced part is then the cell's mix
# of prefill and decode, and not the decode-only residue of the drain.
TRACE_LEAD_S = 2.0


def log(msg: str) -> None:
    print(f"[serve] {msg}", file=sys.stderr, flush=True)


def build_engine(cell, seed: int):
    import jax

    from scaling_tpu.models.transformer.inference import TransformerInferenceModule
    from scaling_tpu.models.transformer.model import init_model
    from scaling_tpu.serve.engine import ServeEngine

    from . import model

    engine_config = model.engine_config(cell.config["engine"])
    config = model.transformer_config(cell.config, {})
    module = init_model(config, None)
    params = model.init_weights(module, seed)
    inf = TransformerInferenceModule(config, module, params)
    engine = ServeEngine(inf, engine_config)
    jax.block_until_ready(params)
    return config, inf, engine


def check_against_reference(cell, inf, done, seed: int, count: int,
                            max_tokens: int, max_outputs: int, control=None):
    """A seeded sample of ``done`` (request, its tokens as the window
    closed), the engine's tokens teacher-forced through the plain reference:
    (positions compared, largest gap of an engine token below the reference's
    best logit, the control's largest gap). Every request is padded to the
    same ``max_tokens`` and ``max_outputs`` (attention is causal, so padding
    after the last token changes nothing), so the check is two programs
    whatever the sample. With ``control`` (``benchmark/control.py``) the
    reference runs once more over the same prompts and tokens in that lower
    precision, and the gap read is that of the token IT puts first at each
    position; without, that gap is None."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from .control import lower_precision

    ref, view = cell.reference, cell.view
    arch = cell.config["transformer_architecture"]
    weights = view.reference_weights(inf.params, arch)
    spec = view.reference_spec(arch)
    lowered = lower_precision(weights, control) if control else None

    @jax.jit
    def largest_gap(logits, got, valid):
        picked = jnp.take_along_axis(logits, got[:, None], axis=-1)[:, 0]
        return jnp.max(jnp.where(valid, logits.max(axis=-1) - picked, 0.0))

    pool = [(r, generated) for r, generated in done
            if generated and len(r.prompt) + len(generated) <= max_tokens]
    picks = np.random.default_rng(seed).permutation(len(pool))[:count]
    compared, worst, control_worst = 0, 0.0, 0.0 if control else None
    for i in picks:
        request, generated = pool[int(i)]
        prompt, got = request.prompt, generated[:max_outputs]
        tokens = np.zeros((max_tokens,), np.int32)
        tokens[:len(prompt)] = prompt
        tokens[len(prompt):len(prompt) + len(got) - 1] = got[:-1]
        positions = np.full((max_outputs,), len(prompt) - 1, np.int32)
        positions[:len(got)] = np.arange(len(prompt) - 1, len(prompt) + len(got) - 1)
        padded = np.zeros((max_outputs,), np.int32)
        padded[:len(got)] = got
        logits = ref.forward(weights, jnp.asarray(tokens), spec,
                             head_positions=jnp.asarray(positions))
        valid = jnp.arange(max_outputs) < len(got)
        gap = largest_gap(logits, jnp.asarray(padded), valid)
        compared += len(got)
        worst = max(worst, float(gap))
        if control:
            first = ref.forward(lowered, jnp.asarray(tokens), spec,
                                head_positions=jnp.asarray(positions)).argmax(-1)
            control_worst = max(control_worst, float(largest_gap(logits, first, valid)))
    return compared, worst, control_worst


def taken_by(seq, when: float) -> bool:
    """Whether the engine had given ``seq`` a slot by ``when`` (the
    scheduler stamps ``admitted_s`` the first time it does)."""
    admitted = getattr(seq, "admitted_s", None)
    return admitted is not None and admitted < when


def tokens_stamped(submitted, t0: float, seconds: float, tokens: str = "all") -> int:
    """Output tokens stamped in ``[t0, t0 + seconds)``: of every submitted
    sequence, or with ``tokens`` ``"counted"`` of the counted requests'
    alone (``TOKEN_RULES``)."""
    return sum(t0 <= s < t0 + seconds
               for r, seq in submitted if tokens == "all" or r.counted
               for s in getattr(seq, "token_stamps", ()))


def window_numbers(submitted, t0: float, seconds: float, backlog: str = "fail",
                   tokens: str = "all"):
    """What the result says of the window, taken as the run leaves it, from
    copies where the objects go on living (under ``--trace 2`` the sequences
    decode on through the traced part): output tokens stamped inside the
    window, those ``tokens`` names (``TOKEN_RULES``); ``done``, the counted
    requests that had their first token, each with its tokens so far; how
    many failed, finished, were cut while decoding; the time-to-first-token
    and inter-token samples; and, with ``backlog`` ``"cut"``, how many the
    engine had not taken when arrivals stopped and that never had a token
    (``unserved``; else 0)."""
    window_end = t0 + seconds
    tokens_in_window = tokens_stamped(submitted, t0, seconds, tokens)
    done, failed, finished, cut, ttft, itl, unserved = [], 0, 0, 0, [], [], 0
    for r, seq in submitted:
        if not r.counted:
            continue
        # refused at submit (no sequence), no first token by the end of the
        # run, or finished otherwise than completed at the length asked for
        ok = getattr(seq, "first_token_s", None) is not None
        if ok and seq.finished_s is not None:
            finished += 1
            ok = (seq.finish_status == "completed"
                  and len(seq.generated) == r.output_len)
        if not ok:
            waiting = (backlog == "cut" and hasattr(seq, "first_token_s")
                       and seq.first_token_s is None
                       and not taken_by(seq, window_end))
            unserved += waiting
            failed += not waiting
            continue
        done.append((r, list(seq.generated)))
        cut += seq.finished_s is None
        ttft.append(seq.first_token_s - (t0 + r.due_s))
        itl.extend(b - a for a, b in zip(seq.token_stamps, seq.token_stamps[1:]))
    return tokens_in_window, done, failed, finished, cut, ttft, itl, unserved


def run(cell, args, env) -> dict:
    import jax
    import numpy as np

    from scaling_tpu.obs import kernel_build_count

    from .device import live_bytes, memory_peaks
    from .stats import percentile

    traffic = cell.traffic
    if cell.chips != 1:
        sys.exit("benchmark: kind serve drives one engine on one chip")
    backlog = traffic.get("backlog", "fail")
    if backlog not in BACKLOGS:
        sys.exit(f"benchmark: \"backlog\" is one of {BACKLOGS}, not {backlog!r}")
    cut_backlog = backlog == "cut"
    token_rule = traffic.get("tokens", "all")
    if token_rule not in TOKEN_RULES:
        sys.exit(f"benchmark: \"tokens\" is one of {TOKEN_RULES}, not {token_rule!r}")
    # the program counts its kernel builds for the whole process, so a
    # build in the wrong mode is one made during THIS run: an earlier one (a
    # test that compiled the kernel for a described chip) is not its path
    wrong_before = kernel_build_count("paged_attention", interpret=not args.rehearse)
    config, inf, engine = build_engine(cell, args.seed)
    env["mark"]("weights and KV pool on the device")
    arch = config.transformer_architecture
    tracer = env["tracer"]
    trace_s = float(traffic.get("trace_seconds", 1.0))
    requests = cell.generate(
        traffic, args.seed, args.seconds, arch.vocab_size,
        traced_seconds=TRACE_LEAD_S + trace_s if tracer.after_window else 0.0)
    after_window = [r for r in requests if r.traced]
    requests = [r for r in requests if not r.traced]
    counted = [r for r in requests if r.counted]
    log(f"{len(requests)} requests ({len(counted)} counted), "
        f"{sum(len(r.prompt) for r in counted)} prompt and "
        f"{sum(r.output_len for r in counted)} output tokens in the window")

    # warm-up: the one program a tick runs, off the clock
    t = time.monotonic()
    engine.warmup_mode = True
    engine.submit([1], 2)
    engine.run_until_done()
    engine.warmup_mode = False
    engine.finished.clear()
    log(f"engine warm-up (compiles or loads from the cache) "
        f"{time.monotonic() - t:.1f} s")
    env["mark"]("engine warm, pre-window traffic starts")
    gc.collect()
    gc.freeze()

    warm_s = float(traffic["warm_seconds"])
    submitted, late = [], []
    tick_s, tick_at, decode_rows, prefill_rows, context_tokens = [], [], [], [], []
    traced_context_tokens = 0
    idx = 0
    t0 = time.monotonic() + warm_s            # the window opens at t0
    setup_s = None
    compiles_before = 0
    end_of_arrivals = t0 + args.seconds
    while True:
        now = time.monotonic()
        if setup_s is None and now >= t0:
            live = live_bytes(jax.devices()[:1])
            setup_s = now - env["t0"]
            compiles_before = env["compiles"].count
        while idx < len(requests) and t0 + requests[idx].due_s <= now:
            r = requests[idx]
            seq = engine.submit(r.prompt, r.output_len, arrival_s=t0 + r.due_s)
            submitted.append((r, seq))
            if r.counted:
                late.append(time.monotonic() - (t0 + r.due_s))
            idx += 1
        if engine.scheduler.has_work:
            if now >= t0:
                tracer.maybe_start(now - t0)
            a = time.monotonic()
            tick = engine.tick()
            b = time.monotonic()
            if a >= t0:
                tick_s.append(b - a)
                tick_at.append(a - t0)
                prefill_rows.append(len(tick.prefills))
                decode_rows.append(len(tick.decodes))
                context_tokens.append(sum(
                    s.num_cached for s in tick.decodes + tick.prefills))
                if tracer.active:
                    traced_context_tokens += context_tokens[-1]
            tracer.maybe_stop()
            if b > end_of_arrivals and idx >= len(requests) and (
                    b > end_of_arrivals + DRAIN_CAP_S
                    or all(seq.first_token_s is not None
                           for r, seq in submitted if r.counted and (
                               not cut_backlog
                               or taken_by(seq, end_of_arrivals)))):
                break
        elif idx >= len(requests):
            break
        else:
            wait = t0 + requests[idx].due_s - time.monotonic()
            if wait > 0:
                time.sleep(min(wait, 0.001))
    tracer.maybe_stop(force=True)
    env["mark"]("window and drain over")
    drained_s = time.monotonic() - end_of_arrivals
    compiles_in_window = env["compiles"].count - compiles_before

    # -- the window's numbers, fixed here whatever runs afterwards
    (tokens_in_window, done, failed, finished, cut, ttft, itl,
     unserved) = window_numbers(submitted, t0, args.seconds, backlog, token_rule)
    log("output tokens stamped in the window: " + ", ".join(
        f"{tokens_stamped(submitted, t0, args.seconds, rule)} by \"tokens\": {rule!r}"
        for rule in TOKEN_RULES) + f"; this traffic counts {token_rule!r}")
    log(f"{len(done)} of {len(counted)} counted requests had their first token "
        f"({finished} finished, {cut} cut while decoding, {failed} failed, "
        f"{unserved} unserved), ran "
        f"{drained_s:.1f} s past the last arrival with "
        f"{len(engine.scheduler.waiting)} waiting; ttft p50 "
        f"{1e3 * percentile(ttft, 50) if ttft else -1:.0f} ms, p95 "
        f"{1e3 * percentile(ttft, 95) if ttft else -1:.0f} ms; "
        f"{len(ttft)} time-to-first-token and {len(itl)} inter-token samples; "
        f"{len(tick_s)} ticks; preemptions {engine.scheduler.preemption_count}; "
        f"{compiles_in_window} program(s) lowered in the window")
    if tick_s:  # what a run-to-run difference of the tokens completed is made of
        p50 = percentile(tick_s, 50)
        slow = sorted((i for i, x in enumerate(tick_s) if x > 1.5 * p50),
                      key=lambda i: -tick_s[i])
        log(f"ticks: mean {1e3 * sum(tick_s) / len(tick_s):.3f} ms, p50 {1e3 * p50:.3f}, "
            f"p99 {1e3 * percentile(tick_s, 99):.3f}; {sum(tick_s):.2f} s inside ticks, "
            f"{tick_at[-1] + tick_s[-1] - sum(tick_s):.2f} s between them; decode rows a "
            f"tick {sum(decode_rows) / len(decode_rows):.3f}; {len(slow)} tick(s) over 1.5 "
            f"x p50 holding {sum(tick_s[i] - p50 for i in slow):.3f} s more than p50, the "
            "longest as (at s, ms, decode rows, prefill rows): " + ", ".join(
                f"({tick_at[i]:.2f}, {1e3 * tick_s[i]:.1f}, {decode_rows[i]}, "
                f"{prefill_rows[i]})" for i in slow[:8]))

    # -- --trace 2: the window's numbers are taken; the same traffic goes
    # on, uncounted, and a few seconds of it are traced. The peak is read
    # before any capture starts.
    window_peaks = None
    if tracer.after_window:
        window_peaks = memory_peaks(jax.devices()[:1], live)
        tracer.open_after_window()
        resumed, idx, prefilling = time.monotonic(), 0, False
        while tracer.stopped_at is None:
            now = time.monotonic()
            while idx < len(after_window) and resumed + after_window[idx].due_s <= now:
                r = after_window[idx]
                engine.submit(r.prompt, r.output_len, arrival_s=resumed + r.due_s)
                idx += 1
            if prefilling or now - resumed >= TRACE_LEAD_S:
                tracer.maybe_start(0.0)
            if engine.scheduler.has_work:
                tick = engine.tick()
                prefilling = bool(tick.prefills)
                if tracer.active:
                    traced_context_tokens += sum(
                        s.num_cached for s in tick.decodes + tick.prefills)
            else:
                time.sleep(0.001)
            tracer.maybe_stop()
        env["mark"]("traced part over")

    builds = kernel_build_count("paged_attention", interpret=args.rehearse)
    wrong_builds = kernel_build_count(
        "paged_attention", interpret=not args.rehearse) - wrong_before
    num_slots = engine.config.num_slots
    pool_tokens = (engine.config.num_blocks - 1) * engine.config.block_size
    # -- correct: outside the window, with the pools' memory given back
    engine.pools = None
    del engine
    gc.unfreeze()
    gc.collect()
    compared, worst, control_worst = check_against_reference(
        cell, inf, done, args.seed, int(traffic.get("check_requests", 4)),
        int(traffic.get("check_max_tokens", 2048)), int(traffic["output"]["max"]),
        control=env.get("control"))
    env["mark"]("checked against the reference")
    log(f"engine vs reference: {compared} positions teacher-forced, largest "
        f"gap of an engine token below the reference's best logit {worst:.4f} "
        f"(tolerance {LOGIT_TOL}); paged_attention: {builds} build(s), "
        f"{wrong_builds} in the wrong mode")
    if control_worst is not None:
        log(f"control ({env['control']} weights in the reference, same prompts "
            f"and tokens): largest gap of the token it puts first {control_worst:.4f}")
    correct = (failed == 0 and compared > 0 and worst <= LOGIT_TOL
               and builds > 0 and wrong_builds == 0 and compiles_in_window == 0
               # a backlog that is cut must still have served someone to the end
               and (finished > 0 or not cut_backlog))
    end_to_end = {"serve_tokens_per_s": tokens_in_window / args.seconds}
    if ttft:
        end_to_end["ttft_p95_ms"] = 1e3 * percentile(ttft, 95)
    if itl:
        end_to_end["itl_p95_ms"] = 1e3 * percentile(itl, 95)
    return {
        "correct": bool(correct),
        "attempted": len(counted),
        "failed": failed,
        "notes": {"cut": cut, **({"unserved": unserved} if cut_backlog else {})},
        "setup_s": setup_s,
        "end_to_end": end_to_end,
        "host": {
            "tick_s": tick_s, "decode_rows": decode_rows, "num_slots": num_slots,
            "context_tokens": context_tokens, "pool_tokens": pool_tokens,
            "submit_late_s": late, "traced_context_tokens": traced_context_tokens,
            "ttft_s": ttft, "ttft_samples": len(ttft), "itl_samples": len(itl),
            "ttft_p50_ms": 1e3 * percentile(ttft, 50) if ttft else None,
            "itl_p50_ms": 1e3 * percentile(itl, 50) if itl else None,
            "worst_logit_gap": worst, "drained_s": drained_s,
            "control_logit_gap": control_worst,
            "counted": len(counted),
            "unserved": unserved if cut_backlog else None,
        },
        "devices": [jax.devices()[0].id], "live_bytes": live,
        "window_peaks": window_peaks,
    }
