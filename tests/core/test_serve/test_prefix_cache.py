"""Shared-prefix block reuse invariants (ISSUE 11 rung (a)) — jax-free:
allocator refcount/copy-on-write semantics, trie admission at full-block
granularity, LRU eviction rules, preemption releasing only private
blocks."""

import random

import pytest

from scaling_tpu.serve import scheduler as scheduler_module
from scaling_tpu.serve.scheduler import (
    BlockAllocator,
    ContinuousBatchingScheduler,
    PrefixCache,
    Request,
    SchedulerConfig,
    SequenceState,
)


def make_sched(num_slots=4, block_size=4, num_blocks=32,
               max_blocks_per_seq=8, token_budget=64, prefill_chunk=4):
    return ContinuousBatchingScheduler(SchedulerConfig(
        num_slots=num_slots, block_size=block_size, num_blocks=num_blocks,
        max_blocks_per_seq=max_blocks_per_seq, token_budget=token_budget,
        prefill_chunk=prefill_chunk,
    ))


def submit(sched, req_id, prompt, max_new=4):
    return sched.add_request(Request(
        req_id=req_id, prompt=list(prompt), max_new_tokens=max_new,
    ))


def settle_chunks(sched, tick):
    chunk = sched.config.prefill_chunk
    for seq in tick.prefills:
        n = min(chunk, seq.prefill_len - seq.num_cached)
        seq.num_cached += n
        if seq.num_cached == seq.prefill_len:
            seq.generated.append(1)


def drive_prefill(sched, seq, max_ticks=20):
    for _ in range(max_ticks):
        if not seq.prefilling and seq.slot is not None:
            return
        settle_chunks(sched, sched.schedule())
    raise AssertionError("prefill did not complete")


def dfs_evictable(cache):
    """Cached blocks only the trie references, counted by brute force."""
    count, stack = 0, list(cache._root.children.values())
    while stack:
        node = stack.pop()
        count += cache.allocator.refcount(node.block) == 1
        stack.extend(node.children.values())
    return count


# --------------------------------------------------- allocator refcounts
def test_allocator_refcounts_and_free_list_discipline():
    alloc = BlockAllocator(8)
    (b,) = alloc.alloc(1)
    assert alloc.refcount(b) == 1
    alloc.incref(b)
    assert alloc.refcount(b) == 2
    alloc.free([b])  # one user gone; block still held
    assert alloc.refcount(b) == 1
    assert b not in list(alloc._free)
    alloc.free([b])  # last user gone -> free list
    assert alloc.refcount(b) == 0
    assert b in list(alloc._free)
    with pytest.raises(ValueError, match="double free"):
        alloc.free([b])
    with pytest.raises(ValueError):
        alloc.incref(b)  # can't re-reference a freed block


# --------------------------------------------------------- trie matching
def test_trie_shares_only_full_blocks_at_partial_boundary():
    """A shared prefix that is not a block multiple shares only its FULL
    blocks — the partial tail block is never mapped (its slots would be
    written by the extending sequence)."""
    alloc = BlockAllocator(16)
    cache = PrefixCache(alloc, block_size=4)
    blocks = alloc.alloc(3)
    prompt = list(range(1, 11))  # 10 tokens: 2 full blocks + 2 spare
    cache.insert(prompt[:4], blocks[0])
    cache.insert(prompt[:8], blocks[1])
    got, matched = cache.match(prompt + [99, 98])
    assert matched == 8 and got == blocks[:2]
    assert alloc.refcount(blocks[0]) == 3  # owner + cache + matcher
    # a full-block-multiple prompt still leaves >= 1 token to prefill
    got2, matched2 = cache.match(prompt[:8])
    assert matched2 == 4 and got2 == [blocks[0]]


def test_trie_insert_requires_cached_parent_and_dedups():
    alloc = BlockAllocator(16)
    cache = PrefixCache(alloc, block_size=2)
    b = alloc.alloc(3)
    # orphan: parent path [1, 2] was never cached
    assert not cache.insert([1, 2, 3, 4], b[0])
    assert cache.insert([1, 2], b[0])
    assert cache.insert([1, 2, 3, 4], b[1])
    # duplicate path: the second block stays private, no cache ref taken
    assert not cache.insert([1, 2], b[2])
    assert alloc.refcount(b[2]) == 1


# ------------------------------------------------------------- eviction
def test_eviction_refuses_refcounted_blocks_and_is_lru():
    alloc = BlockAllocator(16)
    cache = PrefixCache(alloc, block_size=2)
    b = alloc.alloc(2)
    cache.insert([1, 2], b[0])
    cache.insert([7, 8], b[1])
    alloc.free([b[0]])
    alloc.free([b[1]])  # both now cache-only (refcount 1)
    # [1, 2] was inserted first (older last_used) -> evicted first
    assert cache.evictable_count() == 2
    assert cache.evict(1) == 1
    assert b[0] in list(alloc._free) and b[1] not in list(alloc._free)
    # a matcher's reference pins the survivor against eviction
    got, matched = cache.match([7, 8, 9])
    assert got == [b[1]] and matched == 2
    assert cache.evictable_count() == 0
    assert cache.evict(1) == 0  # refuses: refcount > 1
    assert alloc.refcount(b[1]) == 2


def test_divergent_chain_insert_refused_so_evictable_is_deliverable():
    """The eviction invariant (in-use descendant => in-use ancestors)
    must survive concurrent duplicate prefills: a sequence holding a
    PRIVATE duplicate of an ancestor block may not hang its next block
    under the canonical node — otherwise that ancestor counts evictable
    while leaf-only eviction can never deliver it, and the allocator
    raises mid-schedule on the over-promised capacity."""
    alloc = BlockAllocator(16)
    cache = PrefixCache(alloc, block_size=2)
    a1, b1, b2 = alloc.alloc(3)
    # sequence A cached the canonical first block...
    assert cache.insert([1, 2], a1, parent_blocks=[a1])
    # ...sequence B prefilled a private duplicate (insert dedups) and
    # must NOT register its second block under A's node
    assert not cache.insert([1, 2], b1, parent_blocks=[b1, b2])
    assert not cache.insert([1, 2, 3, 4], b2, parent_blocks=[b1, b2])
    # A finishes: its node drops to cache-only and IS deliverable
    alloc.free([a1])
    assert cache.evictable_count() == 1
    assert cache.evict(1) == 1  # every promised block can be delivered
    alloc.free([b1])
    alloc.free([b2])


def test_evictable_count_is_incremental_and_matches_dfs():
    """evictable_count() is O(1) set bookkeeping driven by the
    allocator's refcount hook — pin it against a brute-force DFS across
    a mixed insert/match/free/evict history."""
    alloc = BlockAllocator(32)
    cache = PrefixCache(alloc, block_size=2)

    def dfs_count():
        return dfs_evictable(cache)

    blocks = alloc.alloc(4)
    cache.insert([1, 2], blocks[0], parent_blocks=blocks)
    cache.insert([1, 2, 3, 4], blocks[1], parent_blocks=blocks)
    cache.insert([7, 8], blocks[2], parent_blocks=[blocks[2]])
    assert cache.evictable_count() == dfs_count() == 0
    alloc.free(blocks[:2])  # chain [1,2]->[3,4] now cache-only
    assert cache.evictable_count() == dfs_count() == 2
    got, matched = cache.match([1, 2, 3, 4, 5])
    assert matched == 4
    assert cache.evictable_count() == dfs_count() == 0  # pinned by match
    alloc.free(got)
    assert cache.evictable_count() == dfs_count() == 2
    assert cache.evict(2) == 2
    assert cache.evictable_count() == dfs_count() == 0


def test_eviction_is_leaf_first_cascading():
    alloc = BlockAllocator(16)
    cache = PrefixCache(alloc, block_size=2)
    b = alloc.alloc(2)
    cache.insert([1, 2], b[0])
    cache.insert([1, 2, 3, 4], b[1])
    alloc.free([b[0]])
    alloc.free([b[1]])
    assert cache.evict(2) == 2  # child first, then the exposed parent
    assert sorted([b[0], b[1]]) == sorted(
        x for x in alloc._free if x in (b[0], b[1])
    )


# ------------------------------------------- the LRU order, kept not searched
class WalkCache(PrefixCache):
    """The plain reference: ``evict`` as it was before the heap, a walk of
    the whole trie for the oldest evictable leaf, once a block freed."""

    def evict(self, n):
        freed = 0
        while freed < n and self._evictable:
            victim = None
            stack = list(self._root.children.values())
            while stack:
                node = stack.pop()
                if node.children:
                    stack.extend(node.children.values())
                elif node.block in self._evictable and (
                        victim is None or node.last_used < victim.last_used):
                    victim = node
            if victim is None:
                break
            del victim.parent.children[victim.key]
            self._nodes -= 1
            self.allocator.free([victim.block])
            del self._cached_blocks[victim.block]
            freed += 1
        return freed


class Pool:
    """A pool driven as the scheduler drives its own: admission maps the
    matched prefix and allocates the rest (evicting when the free list is
    short), prompt blocks enter the trie a few at a time under the
    sequence's own table, a sequence that leaves frees its table."""

    BS = 2

    def __init__(self, cache_cls, num_blocks):
        self.alloc = BlockAllocator(num_blocks)
        self.cache = cache_cls(self.alloc, self.BS)
        self.live = {}  # id -> [prompt, table, blocks of it inserted so far]
        self.evictions = []  # every block eviction freed, in order

    def evict(self, n):
        before = len(self.alloc._free)
        got = self.cache.evict(n)
        freed = list(self.alloc._free)[before:]
        assert got == len(freed)
        self.evictions += freed
        return got

    def take(self, n):
        short = n - self.alloc.free_blocks
        if short > 0 and self.evict(short) < short:
            return None
        return self.alloc.alloc(n)

    def admit(self, sid, prompt, insert_now):
        mapped, matched = self.cache.match(prompt)
        fresh = self.take(-(-len(prompt) // self.BS) - len(mapped))
        if fresh is None:
            self.alloc.free(mapped)  # admission abandoned
            return
        self.live[sid] = [prompt, mapped + fresh, len(mapped)]
        self.advance(sid, insert_now)

    def advance(self, sid, blocks):
        prompt, table, done = self.live[sid]
        upto = min(done + blocks, len(prompt) // self.BS)
        for k in range(done, upto):
            self.cache.insert(prompt[:(k + 1) * self.BS], table[k],
                              parent_blocks=table)
        self.live[sid][2] = upto

    def grow(self, sid):
        fresh = self.take(1)  # a decoded token's block: never cached
        if fresh is not None:
            self.live[sid][1] += fresh

    def leave(self, sid, leaf_first):
        table = self.live.pop(sid)[1]
        self.alloc.free(table[::-1] if leaf_first else table)

    def state(self):
        c = self.cache
        return (list(self.alloc._free), sorted(c._evictable), c.cached_blocks,
                c.evictable_count(), len(self.evictions))


@pytest.mark.parametrize("seed", range(10))
def test_heap_evicts_the_blocks_the_walk_would_in_its_order(seed):
    """Random schedules of match / insert / free / evict over prompts that
    SHARE prefixes (three choices a block, so the trie branches at every
    depth), with prompts prefilled twice at once (private duplicates whose
    inserts are refused), preempted sequences coming back through their own
    cached blocks, and tables freed root first or leaf first. Two pools in
    lockstep, the heap's and the walk's: every eviction frees the same
    blocks in the same order, and after every step the free lists, the
    evictable sets and the O(1) count against a DFS agree."""
    rng = random.Random(seed)
    heap, walk = Pool(PrefixCache, 40), Pool(WalkCache, 40)
    parked = []  # prompts of preempted sequences, to be admitted again

    def both(op, *args):
        for pool in (heap, walk):
            getattr(pool, op)(*args)
        assert heap.state() == walk.state()
        assert heap.evictions == walk.evictions
        assert heap.cache.evictable_count() == dfs_evictable(heap.cache)

    for sid in range(1500):
        roll = rng.random()
        live = sorted(heap.live)
        if roll < 0.40 or not live:
            if parked and rng.random() < 0.5:
                prompt = parked.pop(rng.randrange(len(parked)))
            else:
                prompt = [t for _ in range(rng.randint(1, 7))
                          for t in [rng.randrange(3)] * Pool.BS]
                prompt += [9] * rng.randint(0, 1)
            both("admit", sid, prompt, rng.randint(0, 7))
        elif roll < 0.55:
            both("advance", rng.choice(live), rng.randint(1, 7))
        elif roll < 0.65:
            both("grow", rng.choice(live))
        elif roll < 0.90:
            victim = rng.choice(live)
            if rng.random() < 0.3:  # preempted: it will ask again, longer
                parked.append(heap.live[victim][0] + [rng.randrange(3)])
            both("leave", victim, rng.random() < 0.5)
        else:
            both("evict", rng.randint(1, 4))
    assert len(heap.evictions) > 200  # the schedule did press on the pool
    assert heap.cache.stale_skipped > 0  # and re-stamped leaves on the way
    assert walk.cache.stale_skipped == 0


@pytest.mark.parametrize("stale", ["child", "mapped", "restamped", "gone"])
def test_evict_skips_an_entry_whose_node_is_no_evictable_leaf_any_more(stale):
    """Each way an entry goes stale, alone: the one cached block was pushed
    as a leaf when its prefill's reference fell; then it gets a child (by
    an insert that maps no ancestor, as ``parent_blocks=None`` allows), is
    mapped with no ``match`` behind it, is matched and freed (pushed again
    under the new stamp), or is evicted and its block cached anew."""
    alloc = BlockAllocator(8)
    cache = PrefixCache(alloc, block_size=2)
    (b,) = alloc.alloc(1)
    cache.insert([1, 2], b)
    alloc.free([b])
    assert cache._lru == [(1, b)]
    if stale == "child":
        (c,) = alloc.alloc(1)
        cache.insert([1, 2, 3, 4], c)  # in use: rc 2, and no entry
        assert cache.evict(1) == 0  # not the parent from under it
        alloc.free([c])
        assert cache.evict(2) == 2 and list(alloc._free)[-2:] == [c, b]
    elif stale == "mapped":
        alloc.incref(b)
        assert cache.evict(1) == 0 and alloc.refcount(b) == 2
        alloc.free([b])  # pushed again
        assert cache.evict(1) == 1
    elif stale == "restamped":
        alloc.free(cache.match([1, 2, 3])[0])
        assert cache._lru == [(1, b), (2, b)]
        assert cache.evict(1) == 1 and cache._lru == []
    else:
        cache._lru.append((1, b))  # as a second free had pushed it
        assert cache.evict(1) == 1
        assert alloc.alloc(7)[-1] == b
        cache.insert([5, 6], b)
        alloc.free([b])
        assert cache.evict(1) == 1 and cache.cached_blocks == 0
    assert cache.stale_skipped == 1 and cache._lru == []


def test_heap_stays_bounded_without_pool_pressure():
    """One cached prompt matched and freed 10,000 times in a roomy pool:
    each free pushes its last block again and nothing pops, so the heap is
    rebuilt from the live leaves whenever it passes twice the trie's size.
    The next ``evict`` still frees the least recently used leaf."""
    alloc = BlockAllocator(64)
    cache = PrefixCache(alloc, block_size=2)
    old, hot = [5, 6, 7, 8], [1, 2, 3, 4, 1, 2]
    for prompt in (old, hot):
        blocks = alloc.alloc(len(prompt) // 2)
        for k, b in enumerate(blocks):
            cache.insert(prompt[:2 * (k + 1)], b, parent_blocks=blocks)
        alloc.free(blocks)
    assert cache.cached_blocks == 5
    longest = 0
    for _ in range(10_000):
        got, matched = cache.match(hot + [0])
        assert matched == len(hot)
        alloc.free(got)
        longest = max(longest, len(cache._lru))
    assert longest <= 2 * cache.cached_blocks
    assert cache.stale_skipped == 0  # rebuilt, not popped
    old_leaf = cache._root.children[(5, 6)].children[(7, 8)].block
    assert cache.evict(1) == 1 and alloc._free[-1] == old_leaf
    # then the old chain's exposed root, and only then the hot prompt,
    # leaf first
    hot_leaf = cache._root.children[(1, 2)].children[(3, 4)].children[(1, 2)]
    assert cache.evict(2) == 2 and alloc._free[-1] == hot_leaf.block


def test_evict_pops_its_victims_and_walks_nothing(monkeypatch):
    """The cost's shape, counted and not timed: in a trie of 4,000 nodes
    (100 finished prompts of 40 blocks, as a burst of chats leaves them)
    ``evict(4)`` pops four heap entries, one a block, and never iterates
    over a node's children: no walk from the root, or from anywhere."""
    class Counted(dict):
        walked = 0

        def values(self):
            Counted.walked += 1
            return super().values()

        __iter__ = items = keys = values

    alloc = BlockAllocator(4200)
    cache = PrefixCache(alloc, block_size=2)
    cache._root.children = Counted()
    for chain in range(100):
        prompt = [t for k in range(40) for t in (chain, k)]
        blocks = alloc.alloc(40)
        for k, b in enumerate(blocks):
            cache.insert(prompt[:2 * (k + 1)], b, parent_blocks=blocks)
        alloc.free(blocks)
    assert cache.cached_blocks == cache.evictable_count() == 4000
    assert len(cache._lru) == 100  # the leaves, not the nodes
    pops = []
    real = scheduler_module.heapq.heappop
    monkeypatch.setattr(scheduler_module.heapq, "heappop",
                        lambda heap: pops.append(1) or real(heap))
    oldest = cache._root.children[(0, 0)]
    for _ in range(39):
        (oldest,) = oldest.children.values()
    Counted.walked = 0
    assert cache.evict(4) == 4
    assert len(pops) == 4 and cache.stale_skipped == 0
    assert Counted.walked == 0
    # the oldest chain's last four blocks, leaf first
    want = [oldest.block, oldest.parent.block, oldest.parent.parent.block,
            oldest.parent.parent.parent.block]
    assert list(alloc._free)[-4:] == want
    assert len(cache._lru) == 100 and cache.cached_blocks == 3996


# --------------------------------------------------------- copy-on-write
def test_fork_on_write_at_shared_block():
    """A sequence about to write into a block with refcount > 1 forks it
    first: the tick carries the (src, dst) copy pair and the sequence's
    table swaps to the private copy; the shared original keeps its other
    users."""
    sched = make_sched(block_size=4, prefill_chunk=4)
    a = submit(sched, 0, range(1, 9), max_new=4)  # 8 tokens: 2 full blocks
    drive_prefill(sched, a)
    # simulate a shared LAST block (trie sharing never produces this —
    # the invariant is enforced, not assumed): someone else references
    # the block a's next decode token will be written into
    target = a.blocks[1]
    sched.allocator.incref(target)
    # a's prompt is 8 tokens (block-aligned) + first generated token ->
    # num_cached == 8; next write lands in a NEW block, so force the
    # mid-block case: pretend one slot of block 1 is still unwritten
    a.num_cached = 7
    tick = sched.schedule()
    assert len(tick.cow_pairs) == 1
    src, dst = tick.cow_pairs[0]
    assert src == target and dst != target
    assert a.blocks[1] == dst
    assert sched.allocator.refcount(target) == 1  # only the other user
    assert sched.allocator.refcount(dst) == 1
    sched.allocator.free([target])


def test_preemption_releases_only_private_blocks():
    """Preempting a prefix-sharing sequence drops its references; blocks
    the trie still caches stay resident (evictable), private blocks
    return to the free list."""
    sched = make_sched(block_size=4, num_blocks=32, prefill_chunk=4)
    a = submit(sched, 0, range(1, 10), max_new=4)  # 9 tokens: 2 full + tail
    drive_prefill(sched, a)
    # a's 2 full prompt blocks are registered in the trie
    assert sched.prefix_cache.cached_blocks == 2
    shared = list(a.blocks[:2])
    private = list(a.blocks[2:])
    assert all(sched.allocator.refcount(b) == 2 for b in shared)
    free_before = sched.allocator.free_blocks
    sched._preempt(a, [])
    # shared blocks: cache ref survives, nothing hit the free list
    assert all(sched.allocator.refcount(b) == 1 for b in shared)
    assert all(b not in list(sched.allocator._free) for b in shared)
    # private blocks: fully released
    assert all(sched.allocator.refcount(b) == 0 for b in private)
    assert sched.allocator.free_blocks == free_before + len(private)
    assert sched.prefix_cache.evictable_count() == 2


# -------------------------------------------------- admission via trie
def test_admission_maps_cached_prefix_and_prefills_only_tail():
    sched = make_sched(block_size=4, prefill_chunk=4, token_budget=8)
    a = submit(sched, 0, range(1, 13), max_new=2)  # 12 tokens: 3 full blocks
    drive_prefill(sched, a)
    prefix_blocks = list(a.blocks[:3])
    b = submit(sched, 1, list(range(1, 13)) + [50, 51], max_new=2)
    tick = sched.schedule()
    assert b in tick.prefills
    assert b.num_cached == 12 and b.prefix_cached == 12
    assert b.blocks[:3] == prefix_blocks  # SAME pool blocks, refcounted
    assert all(sched.allocator.refcount(bl) >= 2 for bl in prefix_blocks)
    assert sched.prefix_hit_tokens == 12
    # only the 2-token tail is budget-charged and streamed
    assert b.prefill_len - b.num_cached == 2


def test_block_multiple_prompt_leaves_final_block_to_prefill():
    """A prompt entirely covered by cached blocks still re-prefills its
    last block — the completing chunk must run to emit token one."""
    sched = make_sched(block_size=4, prefill_chunk=4)
    a = submit(sched, 0, range(1, 9), max_new=2)  # exactly 2 blocks
    drive_prefill(sched, a)
    b = submit(sched, 1, range(1, 9), max_new=2)  # identical prompt
    tick = sched.schedule()
    assert b in tick.prefills
    assert b.num_cached == 4 and b.prefill_len == 8


def test_preempted_sequence_resumes_through_its_own_cached_blocks():
    """Recompute-style preemption + prefix cache: the victim's
    registered blocks survive (trie refs), so its re-admission matches
    them and resumes mid-prompt instead of restarting at token zero."""
    sched = make_sched(block_size=4, num_blocks=32, prefill_chunk=4)
    a = submit(sched, 0, range(1, 10), max_new=4)
    drive_prefill(sched, a)
    sched._preempt(a, [])
    assert a.state is SequenceState.WAITING and a.num_cached == 0
    tick = sched.schedule()
    assert a in tick.prefills
    assert a.num_cached == 8  # matched its own 2 cached blocks
