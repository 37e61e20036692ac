"""Cost model: ordering pins on known 8-device layouts, link-class
topology awareness, artifact-fed scoring from committed audit goldens,
and calibration source behavior (ISSUE 8 satellite)."""

import math

import pytest

from scaling_tpu.tune import best_layout
from scaling_tpu.tune.costmodel import (
    Calibration,
    SliceTopology,
    analytic_collectives,
    link_for_axis,
    score_layout,
)
from scaling_tpu.tune.layouts import BENCH_MODELS, Layout

MODEL = BENCH_MODELS["0.5b"]


def _layout(pp=1, dp=1, cp=1, mp=1, **kw):
    world_free = 8 // (pp * dp * cp * mp)
    assert world_free == 1, "tests build full 8-device layouts"
    gas = 64 // (8 * dp)
    defaults = dict(micro_batch_size=8, gradient_accumulation_steps=gas,
                    sp=mp > 1 and cp == 1)
    defaults.update(kw)
    return Layout(pp=pp, dp=dp, cp=cp, mp=mp, **defaults)


@pytest.fixture(scope="module")
def ranked():
    _, scores = best_layout(MODEL, SliceTopology(chips=8))
    return scores


def by_label(scores):
    return {s.layout.label: s for s in scores}


# --------------------------------------------------------- ordering pins
def test_known_8dev_layout_ordering(ranked):
    """Pins on the MULTICHIP-arm family the dryrun grid runs: ZeRO-3's
    extra parameter all-gathers cost over plain ZeRO-1 at equal layout;
    interleaved virtual stages beat fill-drain (less bubble at thin-tick
    permute cost); token slices land between (bubble shrink minus the
    cache-path attention penalty)."""
    t = by_label(ranked)
    assert (
        t["pp1·dp8·mp1·z1"].predicted_step_s
        < t["pp1·dp8·mp1·z3"].predicted_step_s
    )
    fd = t["pp2·dp2·mp2·sp·z1"]
    vpp = t["pp2·dp2·mp2·sp·z1·v2"]
    ts = t["pp2·dp2·mp2·sp·z1·ts2"]
    assert vpp.predicted_step_s < fd.predicted_step_s
    assert vpp.bubble_fraction < fd.bubble_fraction
    assert ts.predicted_step_s < fd.predicted_step_s
    assert vpp.predicted_step_s < ts.predicted_step_s


def test_top_pick_beats_hand_picked_multichip_arm(ranked):
    """ISSUE 8 acceptance: the tuner's top pick matches or beats the
    hand-picked MULTICHIP arm (pp=2 x dp=2 x mp=2 + SP + ZeRO-1) by the
    simulator+FLOPs score."""
    hand_picked = by_label(ranked)["pp2·dp2·mp2·sp·z1"]
    assert ranked[0].predicted_step_s <= hand_picked.predicted_step_s


# ---------------------------------------------------- topology awareness
def test_link_classes_follow_ici_domain():
    """Inner axes (model) ride ICI; the outermost axis crosses DCN as
    soon as the ICI domain is smaller than the slice."""
    L = _layout(pp=2, dp=2, mp=2)
    one_slice = SliceTopology(chips=8)
    split = SliceTopology(chips=8, ici_domain=4)
    assert link_for_axis(L, one_slice, "pipe").name == "ici"
    assert link_for_axis(L, split, "pipe").name == "dcn"
    assert link_for_axis(L, split, "model").name == "ici"
    assert link_for_axis(L, split, "data").name == "ici"
    # fused axis takes the slowest member
    assert link_for_axis(L, split, "pipe+model").name == "dcn"


def test_dcn_crossing_worsens_predictions_monotonically():
    """Shrinking the ICI domain can only slow layouts down, and it slows
    the DP-heavy layout (whole-gradient all-reduce across the boundary)
    far more than the PP-outer layout (thin boundary activations)."""
    dp8 = _layout(dp=8, mp=1)
    pp2 = _layout(pp=2, dp=2, mp=2)
    one = SliceTopology(chips=8)
    split = SliceTopology(chips=8, ici_domain=4)
    dp8_one = score_layout(MODEL, dp8, one).predicted_step_s
    dp8_split = score_layout(MODEL, dp8, split).predicted_step_s
    pp2_one = score_layout(MODEL, pp2, one).predicted_step_s
    pp2_split = score_layout(MODEL, pp2, split).predicted_step_s
    assert dp8_split > dp8_one
    assert pp2_split >= pp2_one
    assert (dp8_split - dp8_one) > (pp2_split - pp2_one)


def test_calibration_efficiency_scales_compute():
    L = _layout(dp=8, mp=1)
    topo = SliceTopology(chips=8)
    slow = score_layout(MODEL, L, topo, Calibration.from_mfu(0.25, "t"))
    fast = score_layout(MODEL, L, topo, Calibration.from_mfu(0.75, "t"))
    assert slow.compute_s == pytest.approx(3 * fast.compute_s, rel=1e-9)


# ------------------------------------------------------- artifact feeding
def test_score_from_committed_audit_golden():
    """The artifact-fed path: per-axis collective bytes from a REAL
    lowered program (the committed train_pp2_mp2 audit golden) drop into
    the scorer in place of the analytic volumes — scoring stays finite
    and carries its source label."""
    from scaling_tpu.analysis.hlo_audit import golden_cost_summary
    from scaling_tpu.tune.layouts import ModelSpec

    summary = golden_cost_summary("train_pp2_mp2")
    assert summary["per_axis"] and summary["flops"]
    tiny = ModelSpec(hidden_size=128, num_layers=2, num_attention_heads=2,
                     num_kv_heads=2, sequence_length=64, vocab_size=512,
                     mlp_factor=2.0)
    layout = Layout(pp=2, dp=2, cp=1, mp=2, micro_batch_size=2,
                    gradient_accumulation_steps=1, sp=True)
    score = score_layout(
        tiny, layout, SliceTopology(chips=8),
        collectives=summary["collectives"],
        collectives_source="hlo:train_pp2_mp2",
    )
    assert math.isfinite(score.predicted_step_s)
    assert score.collectives_source == "hlo:train_pp2_mp2"
    # the golden's axes carry model- and pipe-axis traffic
    assert "model" in score.comm_by_axis
    assert any("pipe" in a for a in score.comm_by_axis)


def test_analytic_inventory_schema_matches_hlo_inventory():
    """Analytic records use the exact (op, axis, count, bytes) schema of
    ``hlo_audit.collective_inventory`` so artifact summaries substitute
    without translation."""
    recs = analytic_collectives(MODEL, _layout(pp=2, dp=2, mp=2))
    assert recs
    for rec in recs:
        assert set(rec) == {"op", "axis", "count", "bytes"}
        assert rec["axis"] in ("pipe", "data", "context", "model")


@pytest.mark.parametrize("stage,want", [
    (1, {"reduce-scatter": (1, 2), "all-gather": (1, 2)}),
    (3, {"reduce-scatter": (1, 4), "all-gather": (2, 4)}),
])
def test_data_axis_records_are_what_the_zero_step_emits(stage, want):
    """ZeRO-1 crosses the data axis as a reduce-scatter of the gradients
    and ONE gather of the updated compute copy, both bf16 (2 bytes a
    parameter of the TP / PP shard): never the float32 all-reduce of a
    replicated optimizer. Stage 3 gathers twice (forward and backward)."""
    layout = _layout(pp=1, dp=4, mp=2, zero_stage=stage)
    recs = [r for r in analytic_collectives(MODEL, layout) if r["axis"] == "data"]
    shard = MODEL.parameter_count // 2
    assert {r["op"]: (r["count"], r["bytes"] // shard) for r in recs} == want


def test_calibration_from_run_dir_reads_mfu(tmp_path):
    run = tmp_path / "run"
    run.mkdir()
    (run / "metrics_rank_0.jsonl").write_text(
        '{"kind": "step", "step": 1, "host": 0, "metrics": '
        '{"mfu": 0.62, "step_duration": 0.5}}\n'
        '{"kind": "step", "step": 2, "host": 0, "metrics": '
        '{"mfu": 0.58, "step_duration": 0.5}}\n'
    )
    cal = Calibration.from_run_dir(run)
    assert cal is not None
    assert cal.compute_efficiency == pytest.approx(0.60)
    assert str(run) in cal.source
    empty = tmp_path / "empty"
    empty.mkdir()
    assert Calibration.from_run_dir(empty) is None


def test_memory_estimate_orders_sharded_below_replicated(ranked):
    t = by_label(ranked)
    assert t["pp1·dp8·mp1·z3"].memory_gb < t["pp1·dp8·mp1·z1"].memory_gb


# ------------------------------------------------- per-axis correction
def test_axis_correction_from_pairs_and_reranking():
    """Accumulated prediction-vs-measured pairs correct the ranking per
    axis: runs whose dp-dominant layouts measured 2x the prediction push
    every dp-active candidate down by exactly that factor, pp-only
    candidates stay untouched, and garbage pairs are dropped."""
    from scaling_tpu.tune.costmodel import (
        AxisCorrection,
        SliceTopology,
        score_layout,
    )
    from scaling_tpu.tune.layouts import BENCH_MODELS, Layout

    corr = AxisCorrection.from_pairs([
        {"label": "pp1·dp8·mp1·z1", "predicted_step_s": 1.0,
         "measured_step_s": 2.0},
        {"label": "pp1·dp8·mp1·z1", "predicted_step_s": 1.0,
         "measured_step_s": 8.0},
        {"label": "bogus", "predicted_step_s": float("nan"),
         "measured_step_s": 1.0},  # dropped, never fatal
        {"label": "no-numbers"},  # dropped
    ])
    assert corr.pairs == 2
    assert corr.factors == {"data": 4.0}  # geomean(2, 8)

    model = BENCH_MODELS["0.5b"]
    topo = SliceTopology(chips=8)
    dp_layout = Layout(pp=1, dp=8, cp=1, mp=1, micro_batch_size=8,
                       gradient_accumulation_steps=1)
    pp_layout = Layout(pp=2, dp=4, cp=1, mp=1, micro_batch_size=8,
                       gradient_accumulation_steps=2)
    base_dp = score_layout(model, dp_layout, topo).predicted_step_s
    corr_dp = score_layout(model, dp_layout, topo,
                           correction=corr).predicted_step_s
    assert corr_dp == pytest.approx(base_dp * 4.0)
    # the pp2 layout is also dp-active (dp=4): geomean over {data} only
    # (pipe has no telemetry) is still the data factor
    base_pp = score_layout(model, pp_layout, topo).predicted_step_s
    corr_pp = score_layout(model, pp_layout, topo,
                           correction=corr).predicted_step_s
    assert corr_pp == pytest.approx(base_pp * 4.0)
    # identity leaves everything untouched
    ident = AxisCorrection.identity()
    assert ident.factor_for(dp_layout) == 1.0


def test_axis_correction_from_run_dirs(tmp_path):
    """Pairs accumulate across run dirs: each dir's tuner-prediction
    event + step records yield one (predicted, measured) pair tagged by
    the layout label; dirs without usable telemetry are skipped."""
    import json

    from scaling_tpu.tune.costmodel import AxisCorrection

    def write_run(d, label, predicted, measured):
        d.mkdir(parents=True, exist_ok=True)
        (d / "events.jsonl").write_text(json.dumps({
            "event": "tuner-prediction", "ts": 1.0, "label": label,
            "predicted_step_s": predicted,
        }) + "\n")
        recs = [json.dumps({
            "kind": "step", "step": s, "host": 0,
            "metrics": {"step_duration": measured},
        }) for s in range(1, 4)]
        (d / "metrics.jsonl").write_text("\n".join(recs) + "\n")

    root = tmp_path / "runs"
    write_run(root / "epoch0", "pp1·dp2·mp1·z1", 1.0, 3.0)
    write_run(root / "epoch1", "pp2·dp1·mp1·z1", 2.0, 1.0)
    (root / "empty").mkdir()

    corr = AxisCorrection.from_run_dirs(root)
    assert corr is not None and corr.pairs == 2
    assert corr.factors["data"] == pytest.approx(3.0)
    assert corr.factors["pipe"] == pytest.approx(0.5)
    # no telemetry at all -> None (callers fall back to uncorrected)
    assert AxisCorrection.from_run_dirs(tmp_path / "nothing") is None
    # a FLAT telemetry dir with an incidental subdirectory (checkpoints,
    # a control dir) must still contribute its own direct files — once
    flat = tmp_path / "flat"
    write_run(flat, "pp1·dp4·mp1·z1", 1.0, 2.0)
    (flat / "ckpt").mkdir()
    corr_flat = AxisCorrection.from_run_dirs(flat)
    assert corr_flat is not None and corr_flat.pairs == 1
    assert corr_flat.factors["data"] == pytest.approx(2.0)
