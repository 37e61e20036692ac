"""Tier-1 CI gate: shell the analysis CLI exactly as an operator would.

Fails on new lint findings or golden-report drift, so the gate runs
inside the existing tier-1 command with no new infra (ISSUE 2). The
fast tier audits the train sections (the whole three-section compile
measures ~41 s cold on the 2-core CI host, seconds warm via the shared
compile cache); the full `all` invocation rides the slow tier.
"""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[3]
FIXTURES = Path(__file__).resolve().parent / "fixtures"


def run_cli(*args, timeout=120):
    return subprocess.run(
        [sys.executable, "-m", "scaling_tpu.analysis", *args],
        cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True,
        text=True,
        timeout=timeout,
    )


def test_lint_gate_clean_tree_exits_zero(tmp_path):
    """The clean tree is the enforced baseline — INCLUDING the
    whole-program rules (ISSUE 15) and the protocol rules (ISSUE 17):
    the JSON report carries its schema version and a stable per-rule
    summary the gate diffs structurally, with STA009-STA015 present and
    pinned at zero unsuppressed."""
    out = tmp_path / "lint.json"
    p = run_cli("lint", "--json", str(out))
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    assert "lint: 0 finding(s)" in p.stdout
    payload = json.loads(out.read_text())
    assert payload["schema_version"] == 3
    summary = payload["lint"]["rules"]
    ids = [r["rule"] for r in summary]
    # stable ordering: sorted rule ids, every known rule exactly once
    assert ids == sorted(ids) and len(ids) == len(set(ids))
    assert {"STA009", "STA010", "STA011", "STA012", "STA013", "STA014",
            "STA015"} <= set(ids)
    for rec in summary:
        assert rec["unsuppressed"] == 0, rec
        assert rec["severity"] in ("error", "warning")


def test_lint_gate_seeded_violations_exit_nonzero(tmp_path):
    out = tmp_path / "lint.json"
    p = run_cli("lint", "--paths", str(FIXTURES), "--json", str(out),
                timeout=120)
    assert p.returncode != 0
    payload = json.loads(out.read_text())
    assert payload["schema_version"] == 3
    rules = {f["rule"] for f in payload["lint"]["findings"]}
    assert {"STA001", "STA002", "STA003", "STA004", "STA005", "STA006",
            "STA007", "STA008", "STA009", "STA010", "STA011", "STA012",
            "STA013", "STA014", "STA015"} <= rules
    assert payload["lint"]["unsuppressed"] > 0
    assert payload["exit_code"] != 0
    # the per-rule summary counts agree with the findings list
    by_rule = {r["rule"]: r for r in payload["lint"]["rules"]}
    for rule in ("STA009", "STA010", "STA011", "STA012", "STA013",
                 "STA014", "STA015"):
        assert by_rule[rule]["findings"] == sum(
            1 for f in payload["lint"]["findings"] if f["rule"] == rule
        )
        assert by_rule[rule]["unsuppressed"] >= 1


def test_protocol_gate_matches_golden(tmp_path):
    """ISSUE 17: the clean tree reproduces the committed protocol
    inventory — barrier name templates with their participants, and the
    per-module RPC op tables. The serving fleet's submit/poll/drain/
    stats/shutdown ops and the control plane's barrier/heartbeat ops
    must all be present with their reply keys."""
    out = tmp_path / "protocol.json"
    p = run_cli("protocol", "--json", str(out))
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-2000:]
    payload = json.loads(out.read_text())
    assert payload["schema_version"] == 3
    assert payload["protocol"]["drift"] == []
    inv = payload["protocol"]["inventory"]
    assert "step-{}" in inv["barriers"]
    assert inv["barriers"]["step-{}"]["waits"]  # trainer check-in waits
    assert inv["barriers"]["step-{}"]["arrives"]  # preempt broadcast arrives
    replica_ops = inv["rpc"]["scaling_tpu.serve.replica_proc"]["ops"]
    assert {"submit", "poll", "drain", "stats", "shutdown"} <= set(replica_ops)
    assert "stats" in replica_ops["stats"]["reply_keys"]
    cp_ops = inv["rpc"]["scaling_tpu.resilience.controlplane"]["ops"]
    assert {"arrive", "hb", "set_flag", "get_flag", "count",
            "peers", "prune"} <= set(cp_ops)
    # every op in the table has a handler on the server side — STA013
    # pins this too, but the golden makes the drift diff structural
    for op, rec in replica_ops.items():
        assert rec["handler"], op
        assert rec["clients"], op


def test_protocol_gate_detects_seeded_drift(tmp_path):
    """A doctored protocol golden (a handler deleted from the table, a
    barrier renamed) must make the same invocation exit non-zero — a
    removed dispatch arm or a skipped barrier fails CI structurally,
    not just at runtime under fault drills."""
    from scaling_tpu.analysis.protocol import golden_path

    gdir = tmp_path / "goldens"
    gdir.mkdir()
    golden = json.loads(golden_path().read_text())
    del golden["rpc"]["scaling_tpu.serve.replica_proc"]["ops"]["drain"]
    golden["barriers"]["renamed-{}"] = golden["barriers"].pop("step-{}")
    (gdir / "protocol.json").write_text(json.dumps(golden))
    p = run_cli("protocol", "--goldens", str(gdir))
    assert p.returncode != 0
    assert "DRIFT" in p.stdout
    assert "drain" in p.stdout and "renamed-{}" in p.stdout


def test_audit_gate_matches_golden(tmp_path):
    """The enforced baseline: today's clean tree reproduces the committed
    goldens (collective inventory, precision audit, recompile keys) for
    the single-device, the pp=2/mp=2 mesh, and the interleaved
    virtual-stage train steps."""
    out = tmp_path / "audit.json"
    p = run_cli(
        "audit", "--sections", "train_single,train_pp2_mp2,train_pp2_vpp2",
        "--json", str(out),
    )
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-2000:]
    payload = json.loads(out.read_text())
    assert payload["audit"]["drift"] == []
    sec = payload["audit"]["sections"]["train_single"]
    assert sec["host_callbacks"] == 0
    assert sec["bf16_to_f32_dot_upcasts"] == 0
    pp2 = payload["audit"]["sections"]["train_pp2_mp2"]
    axes = {r["axis"] for r in pp2["collectives"]}
    # the layout's signature collectives, attributed to their mesh axes
    assert "model" in axes and any("pipe" in a for a in axes), axes

    # the interleaved step's stage shift still lowers to pipe-axis
    # collective-permutes (the circular roll did not silently degrade to
    # an all-gather); the v x per-STEP multiplicity lives in the tick
    # scan's trip count, so the static op count pins the program shape
    # and the golden pins its drift
    vpp2 = payload["audit"]["sections"]["train_pp2_vpp2"]
    assert any(
        r["op"] == "collective-permute" and r["axis"] == "pipe"
        for r in vpp2["collectives"]
    ), vpp2["collectives"]


def test_audit_gate_serve_decode_matches_golden(tmp_path):
    """The serving engine's MIXED program reproduces its pinned golden
    (ISSUE 9; repinned for ISSUE 11's fused tick): ONE program per tick
    covers one-token decode rows and prefill chunks —
    its signature carries no per-request shapes, no host callbacks, and
    a stable recompile key baking the chunk width — the
    no-recompile-storm contract for the continuous-batching scheduler's
    shape bucketing."""
    out = tmp_path / "serve.json"
    p = run_cli("audit", "--sections", "serve_decode,serve_decode_mp2",
                "--json", str(out))
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-2000:]
    payload = json.loads(out.read_text())
    assert payload["audit"]["drift"] == []
    sec = payload["audit"]["sections"]["serve_decode"]
    assert sec["host_callbacks"] == 0
    assert sec["infeed_outfeed"] == 0
    static = sec["recompile_key"]["static"]
    assert static["kind"] == "serve_mixed_step"
    # shapes in the signature come from engine CONFIG, never per request:
    # the chunk and the widths it makes, and no selector of a program or
    # a back-end (there is one of each)
    assert set(static) == {
        "kind", "num_slots", "block_size", "max_blocks_per_seq", "kv_dtype",
        "prefill_chunk", "mixed_width", "token_widths", "token_width"}
    assert static["mixed_width"] == static["prefill_chunk"]
    # the engine's two token widths (ISSUE 33): the section is the small
    # width's program, the full width's is pinned beside it — the same
    # function at another T: as many dots, no other collective, about
    # twice the work — and there is no third
    small, full = static["token_widths"]
    assert static["token_width"] == small < full
    assert full == static["num_slots"] * static["mixed_width"]
    at_full = sec["full_width"]
    assert at_full["hash"] != sec["recompile_key"]["hash"]
    assert at_full["dot_general_count"] == sec["dot_general_count"]
    assert 1.5 * sec["flops"] < at_full["flops"] < 2.0 * sec["flops"]
    assert sec.get("chunk_program") is None
    # off-TPU the paged kernel runs interpreted (inlined HLO, 0 custom
    # calls); an on-chip repin records the real custom-call count
    assert sec["pallas_custom_calls"] == 0

    # the mp=2 SHARDED section (ISSUE 14): same program family, now
    # SPMD over the serving mesh — model-axis activation all-reduces in
    # the inventory, mp in the recompile key, per-shard flops roughly
    # halved; and the mp=1 section's key hash must be UNCHANGED by the
    # sharding work (its static config never grew an mp entry)
    mp2 = payload["audit"]["sections"]["serve_decode_mp2"]
    assert mp2["recompile_key"]["static"]["mp"] == 2
    assert "mp" not in static
    assert mp2["mesh"] == {"pipe": 1, "data": 1, "context": 1, "model": 2}
    assert any(
        r["op"] == "all-reduce" and r["axis"] == "model"
        for r in mp2["collectives"]
    ), mp2["collectives"]
    assert mp2["host_callbacks"] == 0
    assert mp2["flops"] < sec["flops"]  # compute genuinely sharded
    # the full width pays the same collectives, on twice the positions
    counts = {(r["op"], r["count"]) for r in mp2["collectives"]}
    assert counts == {(r["op"], r["count"])
                      for r in mp2["full_width"]["collectives"]}
    # since PR 54 the head's logits stay sharded over the vocabulary: the
    # ONE all-gather the tick paid until then was the head's whole WEIGHT
    # (hidden x vocab, every tick: f32[128,512] here, 268 MB at Mistral-7B's
    # widths). In its place the greedy pick gathers a (maximum, column) pair
    # a shard of each of the 32 sampled positions (2 gathers of [32,2]), and
    # the branch that runs only when a row samples sorts and masks its 32
    # rows of logits across the two shards (2 all-to-alls, the cumulative
    # sum's gather of [32,512], a permute, 5 all-reduces of [32]): rows x
    # vocab at most, never hidden x vocab. The 5 all-reduces of the layers'
    # activations are as before.
    assert counts == {("all-gather", 5), ("all-reduce", 10),
                      ("all-to-all", 2), ("collective-permute", 1)}
    gathered = sum(r["bytes"] for r in mp2["collectives"]
                   if r["op"] == "all-gather")
    assert gathered < 128 * 512 * 4  # all five: a quarter of that weight


def test_audit_gate_detects_seeded_drift(tmp_path):
    """A doctored golden (one extra all-gather, a flipped recompile key)
    must make the same CLI invocation exit non-zero — proving the gate
    bites, not just agrees with itself."""
    from scaling_tpu.analysis.hlo_audit import GOLDEN_DIR

    gdir = tmp_path / "goldens"
    gdir.mkdir()
    golden = json.loads((GOLDEN_DIR / "train_single.json").read_text())
    golden["collectives"].append(
        {"op": "all-gather", "axis": "model", "count": 1, "bytes": 4096}
    )
    golden["recompile_key"]["hash"] = "sha256:0000000000000000"
    (gdir / "train_single.json").write_text(json.dumps(golden))
    p = run_cli("audit", "--sections", "train_single", "--goldens", str(gdir))
    assert p.returncode != 0
    assert "DRIFT" in p.stdout


@pytest.mark.slow
def test_full_cli_all_clean(tmp_path):
    """The acceptance-criteria invocation: `all` (lint + every audit
    section, including the pp=2/mp=2 mesh step and the fused decode
    loop) exits 0 on the clean tree with a parseable JSON report."""
    out = tmp_path / "all.json"
    p = run_cli("all", "--json", str(out), timeout=1500)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-2000:]
    payload = json.loads(out.read_text())
    assert payload["exit_code"] == 0
    assert set(payload["audit"]["sections"]) == {
        "train_single", "train_pp2_mp2", "train_pp2_vpp2",
        "train_pp2_tokenslice", "decode_fused", "serve_decode",
        "serve_decode_mp2",
    }
    pp2 = payload["audit"]["sections"]["train_pp2_mp2"]
    axes = {(r["op"], r["axis"]) for r in pp2["collectives"]}
    # the mesh layout's signature collectives: TP activation reductions on
    # the model axis, pipe-edge transfers on the pipe axis
    assert any(ax == "model" for _, ax in axes), axes
    assert any("pipe" in ax for _, ax in axes), axes
