"""The engine runs ONE TICK AHEAD of the tokens the host has read (ISSUE 60):
``tick()`` issues program N+1 and only then reads program N, a decode row's
last token fed to the program on the device from the samples of the one
before it. Token for token what the synchronous path gives (the same code
with the read moved ahead of the schedule) and what ``generate()`` gives;
``seq.generated`` and ``seq.token_stamps`` hold only read tokens after every
``tick()``; where the scheduler needs a token's VALUE the read comes first,
and the counters say how often."""

import jax
import pytest

from scaling_tpu import obs
from scaling_tpu.models.transformer.inference import TransformerInferenceModule
from scaling_tpu.models.transformer.model import init_model
from scaling_tpu.serve.engine import (
    IN_FLIGHT,
    SYNC_REASONS,
    EngineConfig,
    ServeEngine,
)
from scaling_tpu.serve.scheduler import SequenceState

PROMPTS = [[1, 2, 3, 4, 5], [7, 8, 9], [11, 12, 13, 14, 15, 16, 17, 18, 19],
           [3, 1, 4], [21, 22, 23, 24, 25, 26], [2, 7, 1, 8, 2, 8]]
MAX_NEW = 7
ENGINE = dict(num_slots=4, block_size=4, num_blocks=64, max_blocks_per_seq=8,
              token_budget=64, prefill_chunk=4)


@pytest.fixture(scope="module")
def toy():
    from scaling_tpu.serve.bench import build_toy_inference

    return build_toy_inference(hidden=32, layers=2, vocab=64, heads=4)


@pytest.fixture(scope="module")
def want(toy):
    """``generate()`` on the prompts (ONE left-padded batch: a prompt a call
    would compile the passes a length), two tokens past MAX_NEW."""
    return [out.completion_ids for out in toy.generate(
        PROMPTS, max_tokens=MAX_NEW + 2, use_cache=True)]


def make_engine(inf, synchronous=False, **config):
    engine = ServeEngine(inf, EngineConfig(**{**ENGINE, **config}))
    if synchronous:
        # the synchronous path IS the overlapped one with the read first
        engine._read_first = lambda now: "preempt"
    return engine


def holds_only_read_tokens(seqs):
    for seq in seqs:
        assert len(seq.generated) == len(seq.token_stamps)
        assert all(tok >= 0 for tok in seq.generated)
        assert seq.token_stamps == sorted(seq.token_stamps)
        assert len(seq.generated) + seq.in_flight <= seq.request.max_new_tokens
        assert seq.in_flight in (0, 1)


def drive(engine, seqs, max_ticks=500):
    """``tick()`` until the scheduler has no work, the invariant checked
    after EVERY call; returns the Ticks."""
    ticks = []
    while engine.scheduler.has_work:
        ticks.append(engine.tick())
        holds_only_read_tokens(seqs)
        assert len(ticks) < max_ticks
    return ticks


def nothing_in_flight(engine, seqs):
    assert engine._issued is None
    assert all(s.in_flight == 0 and s.state is SequenceState.FINISHED
               for s in seqs)
    # every slot and block given back, once (a double free raises)
    sched = engine.scheduler
    assert sorted(sched._free_slots) == list(range(engine.config.num_slots))
    assert not sched.running and not sched.waiting
    assert sched.available_blocks() == engine.config.num_blocks - 1


def tokens(seqs):
    return [list(s.generated) for s in seqs]


# ------------------------- (i) chunks that finish beside decoding rows
@pytest.mark.parametrize("chunk,slots", [(4, 4), (8, 2), (32, 6), (4, 1)])
def test_overlapped_ticks_emit_what_the_synchronous_path_and_generate_emit(
        toy, want, chunk, slots):
    """Prompts of several chunks stream in beside decoding rows, finish
    their prompt mid-run (a chunk row's first token is fed on the device
    like a decode row's), slots are reused (``slots`` < requests)."""
    runs = {}
    for synchronous in (False, True):
        engine = make_engine(toy, synchronous, prefill_chunk=chunk,
                             num_slots=slots)
        seqs = [engine.submit(p, MAX_NEW) for p in PROMPTS]
        drive(engine, seqs)
        nothing_in_flight(engine, seqs)
        runs[synchronous] = engine, tokens(seqs)
    assert runs[False][1] == runs[True][1] == [w[:MAX_NEW] for w in want]
    over, sync = runs[False][0], runs[True][0]
    assert sync.ticks_overlapped == 0 and over.ticks_overlapped > 0
    # a finished request's slot is free a tick later (its last token is
    # read a tick later): at most a program more for each that waited
    extra = sum(over.mixed_ticks.values()) - sum(sync.mixed_ticks.values())
    assert 0 <= extra <= max(0, len(PROMPTS) - slots)


def test_a_decode_row_brings_the_sentinel_and_never_the_token(toy, want):
    """What the host hands the program for a row whose token is in flight
    is ``IN_FLIGHT``: the value never visits the host on its way."""
    engine = make_engine(toy)
    handed = []
    engine._lower_mixed_programs()
    for width, fn in list(engine._mixed_fns.items()):
        def spy(params, state, packed, key, prev, _fn=fn, _width=width):
            handed.append(engine._layout.split(packed).tokens.copy())
            return _fn(params, state, packed, key, prev)
        engine._mixed_fns[width] = spy
    seq = engine.submit(PROMPTS[0], MAX_NEW)
    drive(engine, [seq])
    assert seq.generated == want[0][:MAX_NEW]
    # two chunks of the prompt, then MAX_NEW - 1 decode rows of one token
    assert [t[t != 0].tolist() for t in handed] == (
        [PROMPTS[0][:4], PROMPTS[0][4:]] + [[IN_FLIGHT]] * (MAX_NEW - 1))


# ----------------------------------------- (ii) an EOS read a tick late
@pytest.mark.parametrize("position", [0, 1, 3, MAX_NEW - 1])
def test_an_eos_read_a_tick_late_drops_the_row_issued_behind_it(
        toy, want, position):
    """The EOS is known a tick after the row behind it was issued: that
    row's sample is dropped, ``generated`` ends at the EOS, the slot and
    the blocks are freed once, the neighbours are untouched."""
    eos = want[2][position]
    first = want[2].index(eos)  # its first appearance ends the request
    engine = make_engine(toy)
    seqs = [engine.submit(PROMPTS[0], MAX_NEW),
            engine.submit(PROMPTS[2], MAX_NEW, eos_token_id=eos),
            engine.submit(PROMPTS[3], MAX_NEW)]
    drive(engine, seqs)
    nothing_in_flight(engine, seqs)
    assert seqs[1].generated == want[2][:first + 1]
    assert seqs[1].generated[-1] == eos
    assert seqs[0].generated == want[0][:MAX_NEW]
    assert seqs[2].generated == want[3][:MAX_NEW]
    assert [s.finish_status for s in seqs] == ["completed"] * 3
    assert engine.ticks_synchronous.keys() <= {"first", "drained"}


def test_an_eos_on_the_only_sequence_leaves_nothing_in_flight(toy, want):
    """Every row of the tick in flight was issued behind an EOS: nobody
    would call ``tick()`` again for it, so it is read where it is found."""
    eos = want[1][2]
    first = want[1].index(eos)
    engine = make_engine(toy)
    seq = engine.submit(PROMPTS[1], MAX_NEW, eos_token_id=eos)
    assert engine.run_until_done() == [seq]
    nothing_in_flight(engine, [seq])
    assert seq.generated == want[1][:first + 1]


# --------------------------------------------------- (iii) a preemption
@pytest.mark.parametrize("synchronous", [False, True],
                         ids=["overlapped", "synchronous"])
def test_a_preempted_sequence_resumes_with_every_token_it_was_given(
        toy, want, synchronous):
    """A pool too small for the rows: the engine reads the tick in flight
    BEFORE a schedule that might preempt, so a victim's resume prompt holds
    the token that was in flight, and the output is ``generate()``'s."""
    engine = make_engine(toy, synchronous, num_blocks=11)
    seqs = [engine.submit(p, MAX_NEW) for p in PROMPTS]
    drive(engine, seqs)
    nothing_in_flight(engine, seqs)
    assert engine.scheduler.preemption_count > 0
    assert tokens(seqs) == [w[:MAX_NEW] for w in want]
    if not synchronous:
        assert engine.ticks_synchronous["preempt"] > 0
        assert engine.ticks_overlapped > 0  # and only those ticks pay for it


def test_may_preempt_is_a_bound_on_what_schedule_does(toy):
    """Never a miss: over a run under pool pressure, no ``schedule()``
    preempts unless ``may_preempt()`` said it might just before."""
    engine = make_engine(toy, num_blocks=11)
    sched = engine.scheduler
    said = []
    schedule = sched.schedule

    def watched():
        said.append(sched.may_preempt())
        t = schedule()
        assert said[-1] or not t.preempted
        return t

    sched.schedule = watched
    seqs = [engine.submit(p, MAX_NEW) for p in PROMPTS]
    drive(engine, seqs)
    assert sched.preemption_count > 0 and not all(said)


# ---------------------------------------------- (iv) a deadline cancel
@pytest.mark.parametrize("after", [1, 3])
def test_a_deadline_cancel_keeps_the_token_that_was_in_flight(
        toy, want, after):
    """A running request runs out of time with a token in flight: the tick
    is read first (``reason=deadline``), the token is the request's, and
    the cancellation frees the slot and the blocks once."""
    engine = make_engine(toy)
    late = engine.submit(PROMPTS[0], MAX_NEW, deadline_ms=1e9)
    other = engine.submit(PROMPTS[2], MAX_NEW)
    while len(late.generated) < after:
        engine.tick()
        holds_only_read_tokens([late, other])
    assert late.in_flight == 1
    late.request.deadline_ms = 0.0
    engine.tick()
    assert late.finish_status == "timeout" and late.in_flight == 0
    assert late.generated == want[0][:after + 1]
    assert len(late.token_stamps) == after + 1
    assert engine.ticks_synchronous["deadline"] == 1
    drive(engine, [late, other])
    nothing_in_flight(engine, [late, other])
    assert other.generated == want[2][:MAX_NEW]
    assert engine.timeout_count == 1


def test_a_first_token_in_flight_meets_its_deadline(toy, want):
    """The first token is computed but unread when the TTFT deadline runs
    out: the read comes first, and the request is not cancelled for a
    token it already has."""
    engine = make_engine(toy)
    seq = engine.submit(PROMPTS[1], MAX_NEW, ttft_deadline_ms=1e9)
    engine.tick()
    assert seq.in_flight == 1 and seq.first_token_s is None
    seq.request.ttft_deadline_ms = 0.0
    drive(engine, [seq])
    assert seq.finish_status == "completed"
    assert seq.generated == want[1][:MAX_NEW]
    assert engine.ticks_synchronous["deadline"] == 1


# ----------- (vi) the load vector, exit_p and the state lines a tick late
def perturbed(config, seed=3):
    module = init_model(config, None)
    params = module.init_params(jax.random.PRNGKey(seed))
    leaves, treedef = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(leaves))
    params = jax.tree.unflatten(treedef, [
        x + 0.3 * jax.random.normal(k, x.shape) for x, k in zip(leaves, keys)])
    return TransformerInferenceModule(config, module, params)


def routed_model():
    from tests.core.test_serve.test_looped_serving import looped_config

    return perturbed(looped_config(
        loop_steps=1, loop_exit_gate=False, sandwich_norm=False,
        mlp_type="moe", mlp_factor=0.5, moe_num_experts=8, moe_top_k=2))


def looped_model():
    from tests.core.test_serve.test_looped_serving import looped_config

    return perturbed(looped_config())


def recurrent_model():
    from tests.core.test_serve.test_hybrid_serving import hybrid_config

    return perturbed(hybrid_config())


@pytest.mark.parametrize("build,emit_field", [
    (routed_model, "load_max"), (looped_model, "exit_p"),
    (recurrent_model, "absent_assign")],
    ids=["routed", "looped", "recurrent"])
def test_a_model_whose_read_carries_more_than_tokens_is_served_a_tick_ahead(
        build, emit_field, tmp_path):
    """A routed model's load vector, a looped model's exit distribution and
    a recurrent model's state lines: what rides the host's read arrives a
    tick late with it, on ``serve.emit`` under the step it belongs to, and
    the tokens are the synchronous path's."""
    from tests.core.test_serve.test_hybrid_serving import prompts

    inf = build()
    requests = prompts((9, 21, 14, 30, 17))
    config = dict(block_size=4, num_blocks=64, max_blocks_per_seq=12,
                  prefill_chunk=8, enable_prefix_cache=False)
    runs = {}
    for synchronous in (False, True):
        engine = make_engine(inf, synchronous, **config)
        seqs = [engine.submit(p, 10) for p in requests]
        obs.start_capture(tmp_path / f"trace{synchronous:d}")
        try:
            drive(engine, seqs)
        finally:
            capture = obs.stop_capture()
        nothing_in_flight(engine, seqs)
        runs[synchronous] = engine, tokens(seqs), capture
    over, got, capture = runs[False]
    assert got == runs[True][1] and all(len(g) == 10 for g in got)
    assert over.ticks_overlapped >= over.tick_index - 2
    emits = {f["step"]: f for name, _, _, f in capture.spans
             if name == "serve.emit"}
    issued = [f["step"] for name, _, _, f in capture.spans
              if name == "serve.mixed"]
    assert sorted(emits) == issued and all(emit_field in f
                                           for f in emits.values())
    # and the counters a tick's read feeds are the synchronous run's
    fed = [k for k in capture.counters if k.startswith((
        "serve_moe_assignments", "serve_moe_absent", "serve_tokens_",
        "serve_prefill_"))]
    assert fed and all(capture.counters[k] == runs[True][2].counters[k]
                       for k in fed)


# ------------------------------------- the counters, spans and snapshots
def test_all_but_the_first_and_the_last_call_are_overlapped(toy, tmp_path):
    """Without preemption or deadlines: the first ``tick()``
    has nothing to run ahead of (``first``), the last only reads
    (``drained``), every other one issues a program ahead of the read of
    the one before; ``serve.tick`` says so, span by span."""
    engine = make_engine(toy, num_slots=8)
    seqs = [engine.submit(p, MAX_NEW + i) for i, p in enumerate(PROMPTS)]
    obs.start_capture(tmp_path / "trace")
    try:
        ticks = drive(engine, seqs)
    finally:
        capture = obs.stop_capture()
    assert engine.scheduler.preemption_count == 0
    assert engine.tick_index == len(ticks)
    counters = capture.counters
    assert counters["serve_ticks_overlapped_total"] == len(ticks) - 2
    assert counters["serve_ticks_synchronous_total{reason=first}"] == 1
    assert counters["serve_ticks_synchronous_total{reason=drained}"] == 1
    assert {k.split("=")[1].rstrip("}") for k in counters
            if k.startswith("serve_ticks_synchronous")} <= set(SYNC_REASONS)
    flags = [f["overlapped"] for name, _, _, f in capture.spans
             if name == "serve.tick"]
    assert flags == [False] + [True] * (len(ticks) - 2) + [False]
    # the last call issued nothing and returns an empty Tick
    assert not ticks[-1].prefills and not ticks[-1].decodes
    snapshot = engine.stats_snapshot()
    assert snapshot["ticks_overlapped"] == len(ticks) - 2
    assert snapshot["ticks_synchronous"] == {"first": 1, "drained": 1}
    assert snapshot["tick_phases_ms"]["overlapped_pct"] == pytest.approx(
        100.0 * (len(ticks) - 2) / len(ticks))


def test_a_ticks_read_closes_inside_the_next_tick_under_its_own_step(
        toy, tmp_path):
    """``serve.mixed.wait``, ``serve.emit`` and ``serve.retire`` of step N
    lie inside ``serve.tick`` of step N + 1, after its ``serve.mixed``: the
    program of N + 1 is issued before N is read."""
    engine = make_engine(toy)
    seqs = [engine.submit(p, MAX_NEW) for p in PROMPTS[:3]]
    obs.start_capture(tmp_path / "trace")
    try:
        drive(engine, seqs)
    finally:
        capture = obs.stop_capture()
    by = {}
    for name, start, duration, fields in capture.spans:
        if "step" in fields:
            by[name, fields["step"]] = (start, start + duration)
    last = engine.tick_index - 1
    for step in range(last):
        inside = by["serve.tick", step + 1]
        for name in ("serve.mixed.wait", "serve.emit", "serve.retire"):
            start, end = by[name, step]
            assert inside[0] <= start and end <= inside[1], (name, step)
        if step + 1 < last:  # the last call issues nothing
            assert by["serve.mixed", step + 1][1] <= by[
                "serve.mixed.wait", step][0]
    assert ("serve.mixed", last) not in by
    assert ("serve.mixed.wait", last) not in by


def test_warm_up_counts_no_tick_and_a_late_submit_restarts_the_overlap(toy):
    engine = make_engine(toy)
    engine.warmup_mode = True
    engine.submit([1], 2)
    engine.run_until_done()
    engine.warmup_mode = False
    engine.finished.clear()
    assert engine.ticks_overlapped == 0 and engine.ticks_synchronous == {}
    assert engine._issued is None
    for fn in engine._mixed_fns.values():
        assert fn._cache_size() == 1
    a = engine.submit(PROMPTS[0], 3)
    engine.run_until_done()
    b = engine.submit(PROMPTS[1], 3)  # after an idle spell
    engine.run_until_done()
    nothing_in_flight(engine, [a, b])
    assert engine.ticks_synchronous == {"first": 2, "drained": 2}
    # the first call of a program with a program's own samples for `prev`
    # found the executable its warm-up built
    for fn in engine._mixed_fns.values():
        assert fn._cache_size() == 1


# ------------------------------------------- a capture holds whole ticks
def test_a_capture_started_and_stopped_mid_run_holds_whole_ticks(toy, tmp_path):
    """``obs`` has the engine settle the tick in flight before a capture
    starts and before it stops (``settle_at_capture_edges``): every tick
    issued inside the capture is read inside it, so its rows and counters
    are those of the same ticks, and none from outside."""
    engine = make_engine(toy)
    seqs = [engine.submit(p, MAX_NEW) for p in PROMPTS[:4]]
    for _ in range(3):
        engine.tick()
    assert engine._issued is not None
    before = engine._issued.step
    obs.start_capture(tmp_path / "trace")
    try:
        assert engine._issued is None  # read before the edge
        for _ in range(4):
            engine.tick()
            holds_only_read_tokens(seqs)
        last = engine._issued.step
    finally:
        capture = obs.stop_capture()
    assert engine._issued is None  # and the last one before the other edge
    steps = {name: [f["step"] for n, _, _, f in capture.spans if n == name]
             for name in ("serve.mixed", "serve.mixed.wait", "serve.emit")}
    assert steps["serve.mixed"] == list(range(before + 1, last + 1))
    assert steps["serve.mixed.wait"] == steps["serve.emit"] == steps["serve.mixed"]
    emitted = sum(f["tokens"] for n, _, _, f in capture.spans if n == "serve.emit")
    assert capture.counters["serve_tokens_generated_total"] == emitted > 0
    assert capture.counters["serve_mixed_ticks_total{width=16}"] == 4
    # a tick issued with nothing in flight: the capture's first
    assert capture.counters["serve_ticks_synchronous_total{reason=first}"] == 1
    drive(engine, seqs)
    nothing_in_flight(engine, seqs)


def test_settle_reads_the_tick_in_flight_on_the_thread_that_ticks_only(toy, want):
    import threading

    engine = make_engine(toy)
    seq = engine.submit(PROMPTS[0], MAX_NEW)
    engine.tick()
    engine.tick()
    assert seq.in_flight == 1 and len(seq.generated) == 0
    other = threading.Thread(target=engine.settle)
    other.start()
    other.join()
    assert seq.in_flight == 1  # another thread leaves the tick to its own
    engine.settle()
    assert seq.in_flight == 0 and seq.generated == want[0][:1]
    holds_only_read_tokens([seq])
    engine.settle()  # nothing in flight: nothing to do
    drive(engine, [seq])
    assert seq.generated == want[0][:MAX_NEW]
