"""Serving-layout tuner units (ISSUE 14, docs/TUNING.md "Serving
layouts"): enumeration rules, scoring physics, the HBM feasibility gate,
the measured serve-span calibration, and the pinned ranking golden —
all pure host-side python (no jax), mirroring the training tuner's test
conventions."""

import json

import pytest

from scaling_tpu.tune.costmodel import Calibration, SliceTopology
from scaling_tpu.tune.layouts import BENCH_MODELS, ModelSpec
from scaling_tpu.tune.serving import (
    HBM_GB,
    ServeCalibration,
    ServingPoint,
    check_serve_golden,
    enumerate_serving_points,
    predict_tick_seconds,
    rank_serving_points,
    score_serving_point,
    serve_golden_path,
)

MODEL = BENCH_MODELS["0.5b"]  # 16 heads, 4 kv heads
TOPO = SliceTopology(chips=8)


def labels(scores):
    return [s.point.label for s in scores]


def test_enumeration_respects_head_divisibility():
    points = enumerate_serving_points(8, MODEL, block_sizes=(16,),
                                      token_budgets=(256,))
    mps = sorted({p.mp for p in points})
    # kv=4 heads exclude mp=8 even though 16 q heads would divide it
    assert mps == [1, 2, 4]
    assert all(p.mp * p.replicas == 8 for p in points)


def test_enumeration_uses_only_dividing_mp():
    model = ModelSpec(hidden_size=256, num_layers=2,
                      num_attention_heads=4, num_kv_heads=4,
                      sequence_length=128, vocab_size=512)
    points = enumerate_serving_points(6, model, block_sizes=(16,),
                                      token_budgets=(128,))
    assert sorted({p.mp for p in points}) == [1, 2]  # 3 divides 6, not 4


def test_replication_beats_sharding_on_throughput():
    """mp shards the compute but pays activation all-reduces; pure
    replication at equal world is always at least as fast — the tuner
    must rank mpN·r1 below mp1·rN for a model that fits one chip (mp's
    win is MEMORY, priced separately)."""
    ranked = rank_serving_points(
        MODEL,
        enumerate_serving_points(8, MODEL, block_sizes=(16,),
                                 token_budgets=(256,)),
        TOPO,
    )
    by_mp = {s.point.mp: s for s in ranked}
    assert by_mp[1].tokens_per_s > by_mp[2].tokens_per_s
    assert by_mp[2].tokens_per_s > by_mp[4].tokens_per_s
    # and mp halves the per-chip memory footprint
    assert by_mp[2].memory_gb < by_mp[1].memory_gb


def test_mp_comm_prices_dcn_when_shards_cross_domains():
    """An mp group that crosses the ICI domain pays DCN rates — the
    same link rule training placement uses. mp is the fastest-varying
    axis (stride 1), so mp=2 fits a 2-chip domain but mp=4 crosses."""
    split = SliceTopology(chips=8, ici_domain=2)
    p2 = score_serving_point(MODEL, ServingPoint(2, 4, 16, 256), split)
    p4 = score_serving_point(MODEL, ServingPoint(4, 2, 16, 256), split)
    assert p2.link == "ici" and p4.link == "dcn"
    assert p4.comm_s > 10 * p2.comm_s


def test_hbm_gate_drops_infeasible_points():
    """A model too big for one v5e chip unsharded: mp=1 points must be
    DROPPED (not ranked slow), and a dividing mp that fits must
    survive."""
    big = ModelSpec(hidden_size=8192, num_layers=48,
                    num_attention_heads=64, num_kv_heads=8,
                    sequence_length=2048, vocab_size=128000)
    assert big.parameter_count * 2 / 1e9 > HBM_GB["tpu_v5e"]
    points = enumerate_serving_points(8, big, block_sizes=(16,),
                                      token_budgets=(256,))
    ranked = rank_serving_points(big, points, TOPO)
    assert ranked, "no feasible point at all — the gate over-fired"
    assert all(s.point.mp >= 4 for s in ranked)
    assert all(s.memory_gb <= HBM_GB["tpu_v5e"] for s in ranked)


def test_block_size_trades_kernel_overhead_for_memory():
    """Smaller blocks pay the paged kernel's per-block streaming
    overhead (slower); bigger blocks pay fragmentation (more memory)."""
    small = score_serving_point(MODEL, ServingPoint(1, 8, 8, 256), TOPO)
    large = score_serving_point(MODEL, ServingPoint(1, 8, 32, 256), TOPO)
    assert large.tokens_per_s > small.tokens_per_s
    assert large.memory_gb > small.memory_gb


def test_serving_point_config_is_runnable_shape():
    cfg = ServingPoint(2, 4, 16, 256, num_slots=8).to_config(MODEL)
    assert cfg["mp"] == 2 and cfg["replicas"] == 4
    assert cfg["num_blocks"] * cfg["block_size"] >= 256 * 16
    assert cfg["model"]["num_kv_heads"] % cfg["mp"] == 0


def test_a_looped_model_pays_its_trunk_and_its_cache_once_a_step():
    """``loop_steps`` (ISSUE 40): parameters are held once, so the weights'
    share of the memory does not move; the pool holds a cache line per
    (step, layer) and a tick works the trunk's parameters every step."""
    import dataclasses

    from scaling_tpu.tune.serving import serve_flops_per_token, serving_memory_gb

    looped = dataclasses.replace(MODEL, loop_steps=4)
    assert ModelSpec.from_arch({
        "hidden_size": 2048, "num_layers": 8, "num_attention_heads": 16,
        "attention_num_kv_heads": 4, "sequence_length": 2048,
        "vocab_size": 32768, "mlp_factor": 2.75, "mlp_type": "swiglu",
        "loop_steps": 4}) == looped
    assert looped.parameter_count == MODEL.parameter_count
    assert looped.kv_lines == 4 * MODEL.kv_lines == 32
    point = ServingPoint(1, 8, 16, 256)
    weights = MODEL.parameter_count * 2 / 1e9
    assert serving_memory_gb(looped, point) - weights == pytest.approx(
        4 * (serving_memory_gb(MODEL, point) - weights))
    head = MODEL.vocab_size * MODEL.hidden_size
    assert serve_flops_per_token(looped, 100.0) - 2.0 * head == pytest.approx(
        4 * (serve_flops_per_token(MODEL, 100.0) - 2.0 * head))
    plain = predict_tick_seconds(MODEL, point, TOPO)["tick_s"]
    assert 3.0 < predict_tick_seconds(looped, point, TOPO)["tick_s"] / plain < 4.0
    assert point.to_config(looped)["model"]["loop_steps"] == 4
    assert "loop_steps" not in point.to_config(MODEL)["model"]
    # the training cost model refuses it by name
    with pytest.raises(ValueError, match="served, not trained"):
        looped.flops_per_token


def test_serve_calibration_scales_predictions(tmp_path):
    """A canned run dir with serve.mixed spans + a serve-summary
    carrying engine facts yields a measured/predicted factor that
    scales every candidate's tick time."""
    point = ServingPoint(1, 2, 4, 48, num_slots=12)
    predicted = predict_tick_seconds(MODEL, point, TOPO)["tick_s"]
    measured = 4.0 * predicted
    events = [
        {"event": "span", "span": "serve.mixed", "dur_s": measured,
         "ts": float(i), "step": i}
        for i in range(5)
    ]
    events.append({
        "event": "serve-summary", "ts": 99.0, "tokens_per_s": 1.0,
        "engine": {"mp": 1, "replicas": 2, "num_slots": 12,
                   "block_size": 4, "token_budget": 48},
    })
    (tmp_path / "events.jsonl").write_text(
        "\n".join(json.dumps(e) for e in events) + "\n"
    )
    cal = ServeCalibration.from_run_dir(tmp_path, MODEL, TOPO)
    assert cal is not None and cal.ticks == 5
    assert cal.factor == pytest.approx(4.0, rel=1e-6)
    base = score_serving_point(MODEL, point, TOPO)
    scaled = score_serving_point(MODEL, point, TOPO,
                                 serve_calibration=cal)
    assert scaled.tick_s == pytest.approx(4.0 * base.tick_s, rel=1e-6)
    assert scaled.tokens_per_s == pytest.approx(
        base.tokens_per_s / 4.0, rel=1e-6
    )


def test_serve_calibration_missing_data_returns_none(tmp_path):
    (tmp_path / "events.jsonl").write_text(json.dumps(
        {"event": "serve-summary", "ts": 1.0, "tokens_per_s": 1.0}
    ) + "\n")  # no spans, no engine facts
    assert ServeCalibration.from_run_dir(tmp_path, MODEL, TOPO) is None


def test_serving_golden_pinned_and_detects_drift():
    """The tier-1 pin: the default-calibration ranking of the 8-dev
    0.5b serving space reproduces the committed golden, and a doctored
    golden is flagged as drift (the gate bites)."""
    ranked = rank_serving_points(
        MODEL, enumerate_serving_points(8, MODEL), TOPO,
        Calibration.default(),
    )
    payload = {"ranked": [s.to_dict() for s in ranked]}
    path = serve_golden_path(8, "0.5b")
    assert path.is_file(), "commit tune_serve_8dev_0.5b.json"
    assert check_serve_golden(payload, path) == []
    doctored = dict(payload)
    doctored["ranked"] = list(reversed(payload["ranked"]))
    assert check_serve_golden(doctored, path)


# ===================================================== placement axis
def plan2x2(per_gb=0.0, hbm=float("inf")):
    from scaling_tpu.tune.serving import HostCapacity, PlacementPlan
    return PlacementPlan(
        [HostCapacity(0, "tpu-a", 2, hbm), HostCapacity(1, "tpu-b", 2, hbm)],
        per_replica_gb=per_gb,
    )


def test_placement_round_robins_least_loaded_lowest_id_ties():
    plan = plan2x2()
    assert plan.initial_assignment(3) == [0, 1, 0]
    assert plan.next_host({0: 2, 1: 1}) == 1
    assert plan.next_host({0: 2, 1: 2}) is None  # slot-bound full


def test_placement_hbm_gate_binds_before_slots():
    # 2 slots/host but only one 10GB replica fits in 15GB of HBM
    plan = plan2x2(per_gb=10.0, hbm=15.0)
    assert plan.feasible(0, 0) and not plan.feasible(0, 1)
    assert plan.initial_assignment(2) == [0, 1]
    with pytest.raises(ValueError, match="placement infeasible"):
        plan.initial_assignment(3)


def test_placement_from_pool_follows_hostsfile_order():
    from scaling_tpu.tune.serving import PlacementPlan
    plan = PlacementPlan.from_pool({"h0": 1, "h1": 3})
    assert [(h.host_id, h.hostname, h.slots) for h in plan.hosts] \
        == [(0, "h0", 1), (1, "h1", 3)]


def test_placement_payload_reports_both_capacity_bounds():
    rows = plan2x2(per_gb=10.0, hbm=15.0).to_payload()
    assert rows[0]["max_replicas_by_memory"] == 1
    assert rows[0]["max_replicas"] == 1  # min(slots=2, memory=1)
    unbounded = plan2x2().to_payload()
    assert unbounded[1]["hbm_gb"] is None
    assert unbounded[1]["max_replicas"] == 2
