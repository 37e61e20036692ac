"""mp>1 sharded serving + fleet integration (ISSUE 14, tier-1).

Rung 1 acceptance: the mp=2 engine — KV pools sharded over the model
axis, one SPMD mixed program — is token-for-token identical to mp=1
with the prefix cache on and off, with
greedy AND temperature>0 rows in every run (the per-(request, position)
sampler keys make sampled rows exact too, up to fp reassociation the
argmax/categorical comparisons absorb). The conftest's 8-device virtual
CPU mesh hosts the mp=2 serving mesh; weights are init-key
deterministic so both builds hold identical parameters.

Rung 2 chaos: a fleet replica "killed" mid-flight leaves dispatch, the
router serves on with the survivors, and a journal replay into a fresh
engine restores the lost replica's requests token-exactly — the
in-process mirror of the single-engine crash-replay e2e.
"""

import pytest

from scaling_tpu.serve.engine import EngineConfig, ServeEngine
from scaling_tpu.serve.journal import open_journal
from scaling_tpu.serve.router import FleetRouter

# greedy, sampled, top-k, top-p rows in one batch — every parity run
# exercises all four sampler shapes
PROMPTS = [
    ([3, 4, 5, 6, 7, 8, 9, 10, 11, 12], dict()),
    ([5, 6, 7], dict(temperature=0.9)),
    ([9, 10, 11, 12, 13, 14, 15], dict(temperature=0.7, top_k=8)),
    ([2, 3, 4, 5, 6], dict(temperature=0.8, top_p=0.9)),
]
MAX_NEW = 6


@pytest.fixture(scope="module")
def toy_infs():
    """The SAME toy weights at mp=1 and on the mp=2 serving mesh."""
    from scaling_tpu.serve.bench import build_toy_inference

    kw = dict(hidden=32, layers=2, vocab=64, heads=4)
    return {
        1: build_toy_inference(**kw),
        2: build_toy_inference(mp=2, **kw),
    }


def run_engine(inf, prompts=PROMPTS, **overrides):
    cfg = dict(num_slots=4, block_size=4, num_blocks=32,
               max_blocks_per_seq=8, token_budget=64, prefill_chunk=4)
    cfg.update(overrides)
    engine = ServeEngine(inf, EngineConfig(**cfg))
    for prompt, kw in prompts:
        engine.submit(prompt, max_new_tokens=MAX_NEW, **kw)
    finished = engine.run_until_done()
    return engine, {s.request.req_id: list(s.generated) for s in finished}


@pytest.mark.parametrize("prefix_cache", [True, False])
def test_mp2_token_exact_vs_mp1_matrix(toy_infs, prefix_cache):
    """The rung-1 acceptance matrix: mp=2 == mp=1 token-for-token with
    the prefix cache on and off, greedy and temp>0 rows."""
    _, mp1 = run_engine(toy_infs[1], enable_prefix_cache=prefix_cache)
    e2, mp2 = run_engine(toy_infs[2], enable_prefix_cache=prefix_cache)
    assert e2.model_parallel == 2 and e2.mesh is not None
    assert mp2 == mp1, f"prefix={prefix_cache}"


def test_mp2_pools_are_sharded_over_kv_heads(toy_infs):
    """Each mp shard owns its kv-head slice — per-chip pool memory
    halves (the big-models-fit point of rung 1)."""
    engine = ServeEngine(toy_infs[2], EngineConfig(
        num_slots=4, block_size=4, num_blocks=32, max_blocks_per_seq=8,
        token_budget=64, prefill_chunk=4,
    ))
    pool = engine.pools.pool_k[0]
    n_kv = pool.shape[2]
    shards = pool.addressable_shards
    assert len(shards) == 2
    devices = set()
    for sh in shards:
        assert sh.data.shape[2] == n_kv // 2  # the kv-head slice
        devices.add(sh.device)
    assert len(devices) == 2


def build_fleet(inf, n=2, tmp_path=None, **overrides):
    cfg = dict(num_slots=4, block_size=4, num_blocks=64,
               max_blocks_per_seq=8, token_budget=64, prefill_chunk=4)
    cfg.update(overrides)
    engines = [
        ServeEngine(inf, EngineConfig(replica_id=r, **cfg))
        for r in range(n)
    ]
    if tmp_path is not None:
        for r, e in enumerate(engines):
            journal, _ = open_journal(
                tmp_path / "journal.jsonl", resume=False, replica_id=r
            )
            e.attach_journal(journal)
    return FleetRouter(engines), engines


def drain_fleet(router, max_ticks=500):
    ticks = 0
    while router.has_work:
        for handle in router.live:
            if handle.engine.scheduler.has_work:
                handle.engine.tick()
        ticks += 1
        assert ticks < max_ticks, "fleet made no progress"


def fleet_outputs(engines):
    return {
        s.request.req_id: list(s.generated)
        for e in engines for s in e.finished
    }


def test_fleet_prefix_affinity_hits_warm_replica_trie(toy_infs):
    """Integration of router policy with REAL engines: a prompt family
    dispatched by affinity actually HITS the warm replica's prefix trie
    (prefill work skipped), instead of re-prefilling on a cold one."""
    router, engines = build_fleet(toy_infs[1])
    family = list(range(1, 13))  # 3 full blocks at bs=4
    router.submit(family + [50, 51], MAX_NEW)
    drain_fleet(router)  # prefill completes -> blocks enter the trie
    router.submit(family + [52, 53, 54], MAX_NEW)
    router.submit([40, 41, 42, 43, 44], MAX_NEW)  # unrelated
    drain_fleet(router)
    stats = router.stats()
    assert stats["affinity_dispatches"] == 1
    warm = [e for e in engines if e.scheduler.prefix_hit_tokens > 0]
    assert len(warm) == 1 and warm[0].scheduler.prefix_hit_tokens >= 12
    # both replicas served something (the unrelated prompt went cold)
    assert all(e.finished for e in engines)


def test_fleet_retry_elsewhere_on_real_backpressure(toy_infs):
    """A replica at its max_waiting cap sheds; the router lands the
    request on the other replica instead of surfacing Backpressure."""
    from scaling_tpu.serve.scheduler import Backpressure

    router, engines = build_fleet(toy_infs[1], max_waiting=1)
    # fill replica 0's waiting queue (no ticks -> nothing admitted)
    for i in range(2):
        res = router.submit([10 + i, 11, 12, 13, 14], MAX_NEW)
        assert not isinstance(res, Backpressure)
    # both replicas now hold one waiting seq each; next submissions shed
    # from whichever is tried and retry over — until the whole fleet is
    # at cap, when the client finally sees Backpressure
    res = router.submit([30, 31, 32, 33], MAX_NEW)
    assert isinstance(res, Backpressure)
    assert router.stats()["rejected"] == 1
    drain_fleet(router)
    assert len(fleet_outputs(engines)) == 2


def test_replica_kill_and_journal_resume_is_token_exact(toy_infs,
                                                        tmp_path):
    """The chaos arm: run the same workload (a) fault-free and (b) with
    replica 1 killed mid-flight — the router sheds new work to the
    survivor, and a journal replay into a fresh engine regenerates the
    dead replica's incomplete requests token-for-token. Final outputs
    across the fleet match the fault-free run EXACTLY (the sampler keys
    fold (request, position), so replay is recompute, not approximation).
    """
    inf = toy_infs[1]
    # DISTINCT leading blocks per request: prefix affinity must not
    # collapse the whole workload onto one replica (that policy has its
    # own test above)
    work = [
        (list(range(1 + i, 9 + i)) + [40 + i],
         dict(temperature=0.8 if i % 2 else 0.0))
        for i in range(6)
    ]
    # (a) fault-free reference
    ref_dir = tmp_path / "ref"
    ref_dir.mkdir()
    router, engines = build_fleet(inf, tmp_path=ref_dir)
    for prompt, kw in work:
        router.submit(prompt, MAX_NEW, **kw)
    drain_fleet(router)
    reference = fleet_outputs(engines)
    assert len(reference) == 6

    # (b) chaos: same workload, replica 1 dies after a few ticks
    chaos_dir = tmp_path / "chaos"
    chaos_dir.mkdir()
    router, engines = build_fleet(inf, tmp_path=chaos_dir)
    for prompt, kw in work[:4]:
        router.submit(prompt, MAX_NEW, **kw)
    for _ in range(3):  # a few ticks: some tokens emitted, none finished
        for handle in router.live:
            handle.engine.tick()
    victim = router.replica(1).engine
    lost = {
        s.request.req_id for s in victim.scheduler.running.values()
    } | {s.request.req_id for s in victim.scheduler.waiting}
    assert lost, "replica 1 held no work — the kill would prove nothing"
    router.fail_replica(1)
    # the survivors keep serving: the remaining workload dispatches to
    # the live replica only
    for prompt, kw in work[4:]:
        router.submit(prompt, MAX_NEW, **kw)
    drain_fleet(router)
    assert router.replica(0).engine.finished

    # journal-resume the dead replica: fresh engine, force-admit its
    # incomplete requests under their ORIGINAL ids
    fresh = ServeEngine(inf, EngineConfig(
        num_slots=4, block_size=4, num_blocks=64, max_blocks_per_seq=8,
        token_budget=64, prefill_chunk=4, replica_id=1,
    ))
    journal, replay = open_journal(
        chaos_dir / "journal.jsonl", resume=True, replica_id=1
    )
    fresh.attach_journal(journal)
    assert {r["req"] for r in replay.incomplete} == lost
    for rec in replay.incomplete:
        fresh.submit(
            rec["prompt"], rec["max_new_tokens"],
            temperature=rec.get("temperature", 0.0),
            top_k=rec.get("top_k"), top_p=rec.get("top_p"),
            req_id=int(rec["req"]), force=True,
        )
    router.restore_replica(1, fresh)
    drain_fleet(router)
    outputs = fleet_outputs([router.replica(0).engine, fresh])
    # every surviving + replayed request matches the fault-free run
    assert outputs == reference
