"""Continuous-batching scheduler policy units (ISSUE 9) — jax-free:
admission order, token-budget mixing, incremental block growth,
preemption on pool exhaustion, slot/block recycling."""

import pytest

from scaling_tpu.serve.scheduler import (
    BlockAllocator,
    ContinuousBatchingScheduler,
    Request,
    SchedulerConfig,
    SequenceState,
)


def make_sched(num_slots=4, block_size=2, num_blocks=16,
               max_blocks_per_seq=8, token_budget=64):
    """A chunk as long as the longest prompt a table holds, so every
    prompt here enters in one row; no prefix trie (these prompts share
    their heads, and block counts are what the tests assert)."""
    return ContinuousBatchingScheduler(SchedulerConfig(
        num_slots=num_slots, block_size=block_size, num_blocks=num_blocks,
        max_blocks_per_seq=max_blocks_per_seq, token_budget=token_budget,
        prefill_chunk=max_blocks_per_seq * block_size, prefix_cache=False,
    ))


def submit(sched, req_id, prompt_len=4, max_new=4):
    return sched.add_request(Request(
        req_id=req_id, prompt=list(range(1, prompt_len + 1)),
        max_new_tokens=max_new,
    ))


def settle_prefills(tick):
    """What the engine does after running a prefill: the prompt's KV is
    now cached."""
    for seq in tick.prefills:
        seq.num_cached = len(seq.resume_prompt)
        seq.generated.append(1)  # the prefill emits the first token


def settle_decodes(tick):
    for seq in tick.decodes:
        seq.num_cached += 1
        seq.generated.append(1)


# ------------------------------------------------------------- allocator
def test_allocator_never_hands_out_trash_block():
    alloc = BlockAllocator(8)
    got = alloc.alloc(7)
    assert 0 not in got
    assert sorted(got) == list(range(1, 8))


def test_allocator_exhaustion_and_double_free():
    alloc = BlockAllocator(4)
    blocks = alloc.alloc(3)
    with pytest.raises(RuntimeError, match="exhausted"):
        alloc.alloc(1)
    alloc.free(blocks[:1])
    assert alloc.free_blocks == 1
    with pytest.raises(ValueError, match="double free"):
        alloc.free(blocks[:1])
    with pytest.raises(ValueError):
        alloc.free([0])  # the trash block is never freeable


# ------------------------------------------------------------- admission
def test_admission_fifo_and_slot_assignment():
    sched = make_sched()
    a, b = submit(sched, 0), submit(sched, 1)
    tick = sched.schedule()
    assert tick.prefills == [a, b]
    assert a.state is SequenceState.RUNNING and a.slot is not None
    assert a.slot != b.slot
    assert not tick.decodes  # just-admitted sequences prefill, not decode


def test_token_budget_limits_prefills_per_tick():
    sched = make_sched(token_budget=10)
    seqs = [submit(sched, i, prompt_len=4) for i in range(4)]
    tick = sched.schedule()
    # 4+4 fits the budget of 10; the third prompt would cross it
    assert tick.prefills == seqs[:2]
    settle_prefills(tick)
    tick2 = sched.schedule()
    # the 2 running decodes charge the budget; 4+4 still fits alongside
    assert tick2.prefills == seqs[2:]
    assert tick2.decodes == seqs[:2]


def test_degenerate_requests_rejected():
    """A 0-token budget would still receive prefill's unconditional first
    token; an empty prompt has nothing to prefill. Both reject at intake."""
    sched = make_sched()
    with pytest.raises(ValueError, match="max_new_tokens"):
        sched.add_request(Request(req_id=0, prompt=[1, 2], max_new_tokens=0))
    with pytest.raises(ValueError, match="empty prompt"):
        sched.add_request(Request(req_id=1, prompt=[], max_new_tokens=4))


def test_request_too_big_for_table_or_pool_rejected():
    sched = make_sched(block_size=2, max_blocks_per_seq=4)  # cap 8 tokens
    with pytest.raises(ValueError, match="block table"):
        submit(sched, 0, prompt_len=6, max_new=4)
    sched2 = make_sched(block_size=2, num_blocks=3, max_blocks_per_seq=8)
    with pytest.raises(ValueError, match="could never finish"):
        submit(sched2, 0, prompt_len=3, max_new=3)  # 3 blocks > 2 usable


# ------------------------------------------------------ growth/preemption
def test_incremental_block_growth():
    sched = make_sched(block_size=2)
    a = submit(sched, 0, prompt_len=4, max_new=4)
    settle_prefills(sched.schedule())
    assert len(a.blocks) == 2  # prompt only: 4 tokens / 2 per block
    settle_decodes(sched.schedule())  # grows for the decode token (slot 4)
    assert len(a.blocks) == 3


def test_preemption_on_pool_exhaustion_evicts_youngest():
    # 4 usable blocks, block_size 2: two 4-token prompts fill the pool
    sched = make_sched(block_size=2, num_blocks=5)
    a = submit(sched, 0, prompt_len=4, max_new=4)
    b = submit(sched, 1, prompt_len=4, max_new=4)
    settle_prefills(sched.schedule())
    assert sched.allocator.free_blocks == 0
    tick = sched.schedule()  # a needs a growth block -> b must go
    assert tick.preempted == [b]
    assert b.state is SequenceState.WAITING
    assert b.slot is None and b.blocks == [] and b.num_cached == 0
    assert b.preemptions == 1 and sched.preemption_count == 1
    assert tick.decodes == [a]
    # the engine must zero the vacated decode row before the next step
    assert len(sched.drain_freed_slots()) == 1


def test_preempted_sequence_resumes_with_generated_tokens():
    sched = make_sched(block_size=2, num_blocks=5)
    a = submit(sched, 0, prompt_len=4, max_new=4)
    b = submit(sched, 1, prompt_len=4, max_new=4)
    settle_prefills(sched.schedule())
    b_generated_before = list(b.generated)
    settle_decodes(sched.schedule())  # preempts b
    assert b.state is SequenceState.WAITING
    # b resumes with prompt + already-generated as its new prompt
    assert b.resume_prompt == list(b.request.prompt) + b_generated_before
    # drain a to completion; b re-admits once blocks free up
    for _ in range(20):
        tick = sched.schedule()
        settle_prefills(tick)
        settle_decodes(tick)
        for seq in list(tick.prefills) + list(tick.decodes):
            if seq.done and seq.slot is not None:
                sched.finish(seq)
        if b.state is SequenceState.RUNNING and a.state is SequenceState.FINISHED:
            break
    assert a.state is SequenceState.FINISHED
    assert b.state in (SequenceState.RUNNING, SequenceState.FINISHED)


def test_oldest_never_preempted_for_younger():
    sched = make_sched(block_size=2, num_blocks=5)
    a = submit(sched, 0, prompt_len=4, max_new=4)
    settle_prefills(sched.schedule())
    b = submit(sched, 1, prompt_len=4, max_new=4)
    tick = sched.schedule()
    # b's admission cannot evict the older a; b waits for capacity
    assert tick.prefills == [] and a.state is SequenceState.RUNNING
    assert b.state is SequenceState.WAITING


# ------------------------------------------------------------- recycling
def test_finish_recycles_slot_and_blocks():
    sched = make_sched(num_slots=1, block_size=2, num_blocks=5)
    a = submit(sched, 0, prompt_len=4, max_new=1)
    b = submit(sched, 1, prompt_len=4, max_new=1)
    tick = sched.schedule()
    assert tick.prefills == [a]  # one slot
    settle_prefills(tick)
    assert a.done
    slot = a.slot
    sched.finish(a)
    assert a.state is SequenceState.FINISHED
    assert sched.drain_freed_slots() == [slot]
    tick2 = sched.schedule()
    assert tick2.prefills == [b] and b.slot == slot  # recycled


# ------------------------------------------------------- chunked prefill
def make_chunked(num_slots=4, block_size=2, num_blocks=32,
                 max_blocks_per_seq=16, token_budget=8, prefill_chunk=4,
                 prefix_cache=True):
    return ContinuousBatchingScheduler(SchedulerConfig(
        num_slots=num_slots, block_size=block_size, num_blocks=num_blocks,
        max_blocks_per_seq=max_blocks_per_seq, token_budget=token_budget,
        prefill_chunk=prefill_chunk, prefix_cache=prefix_cache,
    ))


def settle_chunks(sched, tick):
    """What the engine does after running one chunk per prefill entry."""
    chunk = sched.config.prefill_chunk
    for seq in tick.prefills:
        n = min(chunk, seq.prefill_len - seq.num_cached)
        seq.num_cached += n
        if seq.num_cached == seq.prefill_len:
            seq.generated.append(1)  # the final chunk emits token one


def test_chunked_prompt_streams_across_ticks():
    sched = make_chunked()
    a = submit(sched, 0, prompt_len=10, max_new=2)
    tick = sched.schedule()
    assert tick.prefills == [a] and a.state is SequenceState.RUNNING
    settle_chunks(sched, tick)
    assert a.num_cached == 4 and a.prefilling
    # only first-chunk blocks were allocated, not the whole prompt's
    assert len(a.blocks) == 2
    for expected in (8, 10):
        tick = sched.schedule()
        assert tick.prefills == [a] and tick.decodes == []
        settle_chunks(sched, tick)
        assert a.num_cached == expected
    assert not a.prefilling and a.generated == [1]
    tick = sched.schedule()  # prefill done -> decodes from here on
    assert tick.prefills == [] and tick.decodes == [a]


def test_over_budget_prompt_streams_and_decodes_never_starve():
    """The ISSUE 10 scheduler fix: a prompt bigger than the whole token
    budget no longer admits as a monopolizing sole prefill — it streams
    one chunk per tick while every running decode row still advances."""
    sched = make_chunked(token_budget=6, prefill_chunk=4)
    small = submit(sched, 0, prompt_len=2, max_new=8)
    settle_prefills_chunked_first_tick = sched.schedule()
    settle_chunks(sched, settle_prefills_chunked_first_tick)
    assert not small.prefilling  # 2-token prompt = one chunk
    big = submit(sched, 1, prompt_len=20, max_new=2)  # >> budget of 6
    while big.prefilling or big.slot is None:
        tick = sched.schedule()
        # the decode row advances EVERY tick the big prompt streams
        assert small in tick.decodes
        assert len(tick.prefills) <= 1 and (
            not tick.prefills or tick.prefills[0] is big
        )
        settle_chunks(sched, tick)
        settle_decodes(tick)
        if small.done:
            break
    assert big.num_cached == 20 and big.generated == [1]
    # 20 tokens at chunk 4 took 5 ticks, never one monopolized tick
    assert len(small.generated) >= 5


def test_chunked_admission_shares_tick_across_prompts():
    """Several prompts prefill together under one tick's budget — the
    'one prompt per tick' serialization is gone."""
    sched = make_chunked(token_budget=16, prefill_chunk=4)
    seqs = [submit(sched, i, prompt_len=8, max_new=2) for i in range(3)]
    tick = sched.schedule()
    assert tick.prefills == seqs  # 3 first chunks of 4 <= budget 16
    settle_chunks(sched, tick)
    assert all(s.prefilling and s.num_cached == 4 for s in seqs)
    tick2 = sched.schedule()
    assert tick2.prefills == seqs and tick2.decodes == []
    settle_chunks(sched, tick2)
    assert all(not s.prefilling for s in seqs)


def test_chunked_budget_defers_excess_chunks_but_oldest_progresses():
    sched = make_chunked(token_budget=5, prefill_chunk=4)
    a = submit(sched, 0, prompt_len=8, max_new=2)
    b = submit(sched, 1, prompt_len=8, max_new=2)
    tick = sched.schedule()
    # budget 5: a's first chunk (4) fits, b's would cross -> next tick
    assert tick.prefills == [a]
    settle_chunks(sched, tick)
    tick2 = sched.schedule()
    # a streams its second chunk (oldest first); b's admission waits
    assert tick2.prefills[0] is a
    settle_chunks(sched, tick2)
    for _ in range(6):
        t = sched.schedule()
        settle_chunks(sched, t)
        if not b.prefilling and b.slot is not None:
            break
    assert b.num_cached == 8  # b still got there


def test_mid_prefill_preemption_restarts_prompt():
    """A mid-prefill sequence that cannot grow its next chunk re-enters
    the queue with zero progress (its blocks are gone) and later
    re-streams the whole prompt; the older peer always progresses.
    (Prefix cache off: WITH it, the preempted sequence's registered
    blocks survive eviction and it resumes mid-prompt instead —
    test_prefix_cache.py pins that path.)"""
    sched = make_chunked(block_size=2, num_blocks=7, token_budget=32,
                         prefill_chunk=4, max_blocks_per_seq=8,
                         prefix_cache=False)
    a = submit(sched, 0, prompt_len=8, max_new=2)
    b = submit(sched, 1, prompt_len=8, max_new=2)
    t = sched.schedule()  # both admit first chunks: 2+2 of 6 usable blocks
    assert t.prefills == [a, b]
    settle_chunks(sched, t)
    # second chunks need 2 blocks each; a (oldest) takes the last 2 free,
    # b cannot grow and self-preempts — dropping ALL its progress — then
    # re-admits from the queue front in the same tick's ADMIT phase (its
    # own freed blocks cover a fresh first chunk: no wasted tick)
    t2 = sched.schedule()
    assert t2.preempted == [b]
    assert b.preemptions == 1
    assert b.num_cached == 0  # restarts the prompt from token zero
    assert t2.prefills == [a, b]
    settle_chunks(sched, t2)
    assert a.num_cached == 8 and not a.prefilling  # oldest progressed
    # drain a; b must re-admit and re-stream its prompt from token zero
    for _ in range(20):
        tick = sched.schedule()
        settle_chunks(sched, tick)
        settle_decodes(tick)
        for seq in list(tick.prefills) + list(tick.decodes):
            if seq.done and seq.slot is not None:
                sched.finish(seq)
        if b.state is SequenceState.FINISHED:
            break
    assert b.state is SequenceState.FINISHED
    # the full prompt re-streamed after the restart(s) and decode ran to
    # its budget (finish() recycles blocks, so num_cached is 0 again here)
    assert len(b.generated) == 2


@pytest.mark.parametrize("chunk", [0, None, 4.0])
def test_prefill_chunk_validation(chunk):
    with pytest.raises(ValueError, match="prefill_chunk"):
        SchedulerConfig(prefill_chunk=chunk)


def test_gauges_track_occupancy():
    sched = make_sched(block_size=2, num_blocks=9)
    submit(sched, 0, prompt_len=4, max_new=2)
    submit(sched, 1, prompt_len=4, max_new=2)
    sched.schedule()
    g = sched.gauges()
    assert g["serve_running_seqs"] == 2.0
    assert g["serve_waiting_seqs"] == 0.0
    assert g["serve_free_blocks"] == 4.0
    assert g["serve_pool_utilization"] == pytest.approx(0.5)


# ------------------- a tick ahead of the tokens the host has read (ISSUE 60)
def issue(tick):
    """What the engine does as it ISSUES a tick: the rows' sequences are
    projected past it, the tokens they will produce counted in flight."""
    for seq in tick.prefills:
        seq.num_cached = len(seq.resume_prompt)
        seq.in_flight += 1
    for seq in tick.decodes:
        seq.num_cached += 1
        seq.in_flight += 1


def read(tick):
    for seq in tick.prefills + tick.decodes:
        seq.in_flight -= 1
        seq.generated.append(1)


@pytest.mark.parametrize("read_tokens,in_flight,remaining,done", [
    (0, 0, 3, False), (1, 1, 1, False), (2, 1, 0, True), (3, 0, 0, True)])
def test_a_token_in_flight_counts_wherever_a_length_decides(
        read_tokens, in_flight, remaining, done):
    sched = make_sched()
    seq = submit(sched, 0, max_new=3)
    seq.generated += [5] * read_tokens
    seq.in_flight = in_flight
    assert (seq.remaining_tokens, seq.done) == (remaining, done)
    assert 5 in seq.generated or not read_tokens  # never a placeholder


def test_a_sequence_whose_last_token_is_in_flight_is_not_scheduled_again():
    """Its budget is spent counting what is in flight: it keeps its slot
    and its blocks until the engine has read that token, asks for no block
    and brings no row; its neighbour decodes on."""
    sched = make_sched()
    short, long = submit(sched, 0, max_new=2), submit(sched, 1, max_new=5)
    first = sched.schedule()
    issue(first)                       # both first tokens in flight
    second = sched.schedule()          # scheduled a tick ahead of the read
    assert second.decodes == [short, long]
    issue(second)
    read(first)
    assert short.done and short.in_flight == 1 and not long.done
    blocks = list(short.blocks)
    third = sched.schedule()
    assert third.decodes == [long] and not third.preempted
    assert short.slot is not None and short.blocks == blocks
    issue(third)
    read(second)
    assert short.done and short.in_flight == 0   # the engine finishes it now
    sched.finish(short)
    assert short.state is SequenceState.FINISHED


def test_may_preempt_is_false_with_room_and_true_before_a_grow_preempts():
    sched = make_sched(num_slots=2, num_blocks=6)   # 5 usable blocks of 2
    a, b = submit(sched, 0, prompt_len=3, max_new=6), submit(
        sched, 1, prompt_len=3, max_new=6)
    assert not sched.may_preempt()
    first = sched.schedule()           # 2 blocks each: 1 left
    issue(first)
    read(first)
    assert not sched.may_preempt()     # both rows write inside their blocks
    issue(sched.schedule())
    assert sched.may_preempt()         # each needs a third block: one is left
    tick = sched.schedule()
    assert tick.preempted == [b] and tick.decodes == [a]


def test_may_preempt_counts_a_waiting_head_older_than_a_running_sequence():
    """ADMIT preempts only on behalf of an OLDER request (a victim resuming,
    ids pinned by a replay): a younger head never makes the bound fire."""
    sched = make_sched(num_slots=3, num_blocks=8)   # 7 usable blocks of 2
    submit(sched, 5, prompt_len=3, max_new=2)
    young = submit(sched, 7, prompt_len=3, max_new=2)
    issue(sched.schedule())            # 2 blocks each, 3 left
    submit(sched, 9, prompt_len=8, max_new=2)     # younger: waits its turn
    assert not sched.may_preempt()
    assert not sched.schedule().preempted
    sched.waiting.clear()
    submit(sched, 3, prompt_len=8, max_new=2)     # older: it would preempt
    assert sched.may_preempt()
    assert sched.schedule().preempted == [young]
