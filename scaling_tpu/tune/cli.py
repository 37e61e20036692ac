"""CLI: ``python -m scaling_tpu.tune`` — rank layouts, emit a config.

Exit codes: 0 clean, 1 golden drift (``--check-golden``), 2 usage error.

Calibration resolution (printed with the report — the tuner NEVER uses
the legacy step-time/3.2 fudge):

1. ``--run-dir DIR``: mean MFU of that obs run dir's step records.
2. ``--obs-root ROOT``: the newest obs run dir under it.
3. An explicit default (efficiency 0.5) that says it is uncalibrated.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path
from typing import Optional, Tuple

GOLDEN_DIR = Path(__file__).resolve().parent / "goldens"

# golden scores compare within this band (pure-python floats are
# deterministic; the band absorbs deliberate small constant tweaks
# without re-pinning the world)
GOLDEN_RTOL = 0.02


def _newest_run_dir(obs_root: Path) -> Optional[Path]:
    """The run dir under ``obs_root`` whose telemetry is newest: the
    directory holding the most recently modified ``*.jsonl``."""
    newest: Tuple[float, Optional[Path]] = (-1.0, None)
    try:
        for p in obs_root.rglob("*.jsonl"):
            try:
                mtime = p.stat().st_mtime
            except OSError:
                continue
            if mtime > newest[0]:
                newest = (mtime, p.parent)
    except OSError:
        return None
    return newest[1]


def resolve_calibration(run_dir: Optional[str], obs_root: Optional[str]):
    from .costmodel import Calibration

    candidates = [run_dir] if run_dir else []
    if obs_root:
        newest = _newest_run_dir(Path(obs_root))
        if newest is not None:
            candidates.append(str(newest))
    for candidate in candidates:
        cal = Calibration.from_run_dir(candidate)
        if cal is not None:
            return cal
        print(f"# tune: {candidate} has no MFU step records; falling back",
              file=sys.stderr)
    return None  # the uncalibrated default, which labels itself


def golden_path(devices: int, model_name: str) -> Path:
    return GOLDEN_DIR / f"tune_{devices}dev_{model_name}.json"


def check_golden(payload: dict, path: Path) -> list:
    if not path.is_file():
        return [f"no golden at {path} (run --repin-golden)"]
    golden = json.loads(path.read_text())
    drift = []
    g_rank = [(r["label"], r["predicted_step_s"]) for r in golden["ranked"]]
    c_rank = [
        (r["label"], r["predicted_step_s"]) for r in payload["ranked"]
    ]
    if [l for l, _ in g_rank] != [l for l, _ in c_rank]:
        drift.append(
            f"ranking order changed: golden {[l for l, _ in g_rank][:5]}... "
            f"!= current {[l for l, _ in c_rank][:5]}..."
        )
    for (gl, gs), (cl, cs) in zip(g_rank, c_rank):
        if gl == cl and gs and abs(cs - gs) > GOLDEN_RTOL * gs:
            drift.append(
                f"{gl}: predicted {gs:.6f}s -> {cs:.6f}s "
                f"(> {GOLDEN_RTOL:.0%} band)"
            )
    return drift


def _lowered_crosscheck(scores, top: int) -> list:
    """Lower the real train step (tiny audit shapes) for the top layouts
    and return their per-axis inventories next to the analytic estimate
    at the SAME tiny shape — a structural check that the analytic model
    puts traffic on the right axes. cp>1 layouts are skipped (the audit
    section builder has no context-parallel arm)."""
    import dataclasses

    from ..analysis.hlo_audit import layout_cost_summary
    from .costmodel import analytic_collectives
    from .layouts import ModelSpec

    out = []
    for s in scores[:top]:
        L = s.layout
        if L.cp > 1:
            out.append({"label": L.label, "skipped": "cp>1 not lowerable "
                        "via the audit section builder"})
            continue
        layers = 2 * L.pp * L.vpp  # audit convention: 2 layers per chunk
        tiny = ModelSpec(hidden_size=128, num_layers=layers,
                         num_attention_heads=2, num_kv_heads=2,
                         sequence_length=64, vocab_size=512,
                         mlp_factor=2.0, glu=True)
        summary = layout_cost_summary(
            pp=L.pp, dp=L.dp, mp=L.mp,
            gas=L.gradient_accumulation_steps, zero=True,
            vpp=L.vpp, slices=L.token_slices, layers=layers,
        )
        # the audit section builder only expresses ZeRO-1 (zero=True);
        # pin the analytic side to the same stage so the two inventories
        # describe the SAME program, whatever stage the ranked layout ran
        tiny_layout = dataclasses.replace(
            L, micro_batch_size=2, zero_stage=1
        )
        analytic_axis: dict = {}
        for r in analytic_collectives(tiny, tiny_layout):
            # sum same-axis records (zero-3 layouts emit several per axis)
            analytic_axis[r["axis"]] = (
                analytic_axis.get(r["axis"], 0) + r["bytes"]
            )
        out.append({
            "label": L.label,
            "lowered_per_axis": summary["per_axis"],
            "analytic_per_axis": analytic_axis,
            "flops": summary["flops"],
        })
    return out


def _parse_model(name: str):
    """Resolve --model to a ModelSpec (shared by the training and
    serving modes); returns (model, model_name) or (None, error)."""
    from .layouts import BENCH_MODELS, ModelSpec

    if name in BENCH_MODELS:
        return BENCH_MODELS[name], name
    try:
        parts = [float(x) for x in name.split(",")]
        model = ModelSpec(
            hidden_size=int(parts[0]), num_layers=int(parts[1]),
            num_attention_heads=int(parts[2]), num_kv_heads=int(parts[3]),
            sequence_length=int(parts[4]), vocab_size=int(parts[5]),
            mlp_factor=parts[6] if len(parts) > 6 else 2.75,
        )
        return model, "custom"
    except (ValueError, IndexError):
        return None, name


def serve_main(args) -> int:
    """``--serve``: rank (mp, replicas, block_size, token_budget) serving
    points by predicted fleet tokens/s; golden-pinned like the training
    ranking, ``--emit-config`` writes a dict ``serve bench --config``
    runs directly (docs/TUNING.md "Serving layouts")."""
    from .costmodel import Calibration, SliceTopology
    from .serving import (
        ServeCalibration,
        check_serve_golden,
        enumerate_serving_points,
        rank_serving_points,
        serve_golden_path,
    )

    model, model_name = _parse_model(args.model)
    if model is None:
        print(f"error: unknown --model {model_name!r}", file=sys.stderr)
        return 2
    try:
        block_sizes = [
            int(x) for x in args.serve_block_sizes.split(",") if x.strip()
        ]
        budgets = [
            int(x) for x in args.serve_token_budgets.split(",") if x.strip()
        ]
    except ValueError:
        block_sizes = budgets = []
    if (not block_sizes or not budgets
            or any(v < 1 for v in block_sizes + budgets)):
        print("error: bad --serve-block-sizes / --serve-token-budgets "
              "(want comma lists of ints >= 1)", file=sys.stderr)
        return 2
    topo = SliceTopology(
        chips=args.devices, ici_domain=args.ici_domain,
        generation=args.generation,
    )
    pinning = args.check_golden or args.repin_golden
    calibration = (
        Calibration.default() if pinning
        else resolve_calibration(args.run_dir, args.obs_root)
    )
    serve_cal = None
    if args.serve_calibrate_from and not pinning:
        serve_cal = ServeCalibration.from_run_dir(
            args.serve_calibrate_from, model, topo, calibration
        )
        if serve_cal is None:
            print(
                f"# tune: {args.serve_calibrate_from} has no serve spans "
                "or engine facts; predictions uncalibrated",
                file=sys.stderr,
            )
    points = enumerate_serving_points(
        args.devices, model, block_sizes=block_sizes,
        token_budgets=budgets, num_slots=args.serve_num_slots,
    )
    if not points:
        print("error: no valid serving point (does any mp divide both "
              "the chip count and the q/kv heads?)", file=sys.stderr)
        return 2
    ranked = rank_serving_points(model, points, topo, calibration,
                                 serve_cal)
    if not ranked:
        print(f"error: no serving point fits {args.generation} HBM for "
              "this model", file=sys.stderr)
        return 2
    cal = calibration or Calibration.default()
    best = ranked[0]
    payload = {
        "mode": "serve",
        "devices": args.devices,
        "model": model_name,
        "slice_topology": topo.to_dict(),
        "calibration": cal.to_dict(),
        "serve_calibration": serve_cal.to_dict() if serve_cal else None,
        "ranked": [s.to_dict() for s in ranked],
        "serving_config": best.point.to_config(model),
        "dropped_over_hbm": len(points) - len(ranked),
    }
    if args.serve_hostsfile:
        # the placement axis (docs/SERVING.md "Host mode"): WHERE the
        # best point's replicas may spawn — per-host slot and HBM
        # feasibility over the deployment's hostsfile, plus the
        # least-loaded initial assignment `serve bench --hostsfile`
        # would make. Golden-safe: the pin compares only "ranked".
        from ..runner.config import RunnerConfig
        from ..runner.runner import get_resource_pool
        from .serving import (
            HBM_GB,
            HostCapacity,
            PlacementPlan,
            serving_memory_gb,
        )

        pool = get_resource_pool(RunnerConfig(
            hostsfile=args.serve_hostsfile, default_gpu_count=1,
        ))
        per_gb = serving_memory_gb(model, best.point) * best.point.mp
        chip_gb = HBM_GB.get(topo.generation, float("inf"))
        plan = PlacementPlan(
            [
                HostCapacity(i, hn, max(int(s), 1),
                             chip_gb * max(int(s), 1))
                for i, (hn, s) in enumerate(pool.items())
            ],
            per_replica_gb=per_gb,
        )
        try:
            assignment = plan.initial_assignment(best.point.replicas)
        except ValueError as e:
            assignment = None
            print(f"# tune: placement infeasible for best point: {e}",
                  file=sys.stderr)
        payload["placement"] = {
            "hostsfile": str(args.serve_hostsfile),
            "per_replica_gb": round(per_gb, 3),
            "hosts": plan.to_payload(),
            "assignment": assignment,
        }
    print(f"tune --serve: {len(ranked)} feasible serving point(s) of "
          f"{model_name} on {args.devices} chip(s) [{topo.generation}, "
          f"ici_domain={topo.domain}; {payload['dropped_over_hbm']} "
          f"dropped over HBM]")
    print(f"calibration: efficiency={cal.compute_efficiency:.3f} "
          f"({cal.source})"
          + (f"; serve tick factor {serve_cal.factor:.3f} "
             f"({serve_cal.source})" if serve_cal else ""))
    header = (f"{'rank':>4} {'layout':<24} {'tokens/s':>10} {'tick_s':>9} "
              f"{'comm_s':>9} {'mem_GB':>7} link")
    print(header)
    for i, s in enumerate(ranked[: args.top]):
        print(
            f"{i + 1:>4} {s.point.label:<24} {s.tokens_per_s:>10.0f} "
            f"{s.tick_s:>9.5f} {s.comm_s:>9.5f} {s.memory_gb:>7.2f} "
            f"{s.link}"
        )
    print(f"best: {best.point.label} predicted {best.tokens_per_s:.0f} "
          f"fleet tokens/s (run: python -m scaling_tpu.serve bench "
          f"--config <emitted>)")
    if payload.get("placement"):
        pl = payload["placement"]
        print(f"placement: {len(pl['hosts'])} host(s), "
              f"{pl['per_replica_gb']:.2f} GB/replica, "
              f"assignment={pl['assignment']}")
        for row in pl["hosts"]:
            print(f"    host {row['host_id']} ({row['hostname']}): "
                  f"slots={row['slots']} "
                  f"max_replicas={row['max_replicas']}")
    if args.emit_config:
        Path(args.emit_config).write_text(
            json.dumps(payload["serving_config"], indent=1) + "\n"
        )
    if args.json:
        Path(args.json).write_text(json.dumps(payload, indent=1) + "\n")
    gpath = serve_golden_path(args.devices, model_name)
    if args.repin_golden:
        gpath.parent.mkdir(parents=True, exist_ok=True)
        gpath.write_text(json.dumps(
            {
                "calibration": "pinned-default",
                "ranked": [
                    {"label": s.to_dict()["label"],
                     "tokens_per_s": s.to_dict()["tokens_per_s"]}
                    for s in ranked
                ],
            },
            indent=1,
        ) + "\n")
        print(f"serving golden repinned -> {gpath}")
    elif args.check_golden:
        drift = check_serve_golden(payload, gpath)
        for line in drift:
            print(f"DRIFT: {line}")
        print(f"golden: {'OK' if not drift else 'DRIFT'}")
        return 1 if drift else 0
    return 0


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m scaling_tpu.tune",
        description="topology-aware auto-sharding tuner (docs/TUNING.md)",
    )
    parser.add_argument("--devices", type=int, default=8)
    parser.add_argument("--model", default="0.5b",
                        help="bench model name (0.5b|1b) or "
                        "hidden,layers,heads,kv,seq,vocab[,mlp_factor]")
    parser.add_argument("--global-batch", type=int, default=64,
                        help="global batch size in sequences")
    parser.add_argument("--mbs", type=int, default=8,
                        help="micro batch size (bench self-tunes this "
                        "per chip; the tuner searches layouts at a fixed "
                        "one unless --mbs-ladder widens the search)")
    parser.add_argument("--mbs-ladder", metavar="LIST",
                        help="comma list of additional micro-batch sizes "
                        "to enumerate and score alongside --mbs (global "
                        "batch fixed, so gas scales inversely: smaller "
                        "mbs buys thinner pipeline bubbles and less "
                        "activation memory). Ignored under golden "
                        "pinning so the pinned ranking stays single-mbs")
    parser.add_argument("--generation", default="tpu_v5e",
                        choices=["tpu_v4", "tpu_v5e", "tpu_v5p", "tpu_v6e"])
    parser.add_argument("--ici-domain", type=int, default=None,
                        help="chips per ICI domain (default: all chips on "
                        "one slice; smaller values model DCN crossings)")
    parser.add_argument("--top", type=int, default=10,
                        help="rows to print (the JSON always carries all)")
    parser.add_argument("--json", metavar="FILE",
                        help="write the machine-readable report")
    parser.add_argument("--run-dir", help="obs run dir to calibrate "
                        "compute efficiency from (mean MFU)")
    parser.add_argument("--obs-root",
                        help="calibrate from the newest obs run dir "
                        "under this root")
    parser.add_argument("--correct-from-runs", metavar="ROOT",
                        help="accumulate tuner-prediction vs span-measured "
                        "pairs from every run dir under ROOT and apply the "
                        "per-axis multiplicative correction to the ranking "
                        "(docs/TUNING.md calibration loop)")
    parser.add_argument("--emit-config", metavar="FILE",
                        help="write the best layout's TopologyConfig dict")
    parser.add_argument("--record-events", metavar="FILE",
                        help="append a tuner-prediction event for the best "
                        "layout to this events JSONL (an obs run dir file)")
    parser.add_argument("--lower", type=int, metavar="K", default=0,
                        help="cross-check the top K layouts' analytic axis "
                        "attribution against the really-lowered step "
                        "(tiny shapes; needs the 8-device CPU mesh)")
    parser.add_argument("--check-golden", action="store_true",
                        help="compare against the pinned ranking (forces "
                        "the default calibration)")
    parser.add_argument("--repin-golden", action="store_true",
                        help="rewrite the pinned ranking from this run "
                        "(forces the default calibration)")
    # ---- serving layouts (docs/TUNING.md "Serving layouts") ----
    parser.add_argument("--serve", action="store_true",
                        help="rank SERVING layouts instead of training "
                        "ones: (mp, replicas=devices/mp, block_size, "
                        "token_budget) points scored by fleet tokens/s — "
                        "mp activation all-reduces priced ICI-vs-DCN like "
                        "training, KV pool memory per chip gated against "
                        "the generation's HBM")
    parser.add_argument("--serve-block-sizes", default="8,16,32",
                        metavar="LIST", help="KV block sizes to sweep")
    parser.add_argument("--serve-token-budgets", default="128,256,512",
                        metavar="LIST",
                        help="per-tick token budgets to sweep")
    parser.add_argument("--serve-num-slots", type=int, default=8,
                        help="decode slots per replica (fixed across the "
                        "sweep; the jitted batch size)")
    parser.add_argument("--serve-hostsfile", metavar="FILE",
                        help="with --serve: plan WHERE the best point's "
                        "replicas spawn — per-host slot/HBM feasibility "
                        "over this runner hostsfile, published as the "
                        "payload's 'placement' table (the same "
                        "least-loaded rule serve bench --hostsfile "
                        "applies at spawn time)")
    parser.add_argument("--serve-calibrate-from", metavar="RUN_DIR",
                        help="scale predicted tick time by the measured "
                        "serve.mixed spans of this serve "
                        "bench run dir (its serve-summary must carry the "
                        "engine shape facts)")
    args = parser.parse_args(argv)
    if args.serve:
        return serve_main(args)

    from .costmodel import (
        AxisCorrection,
        Calibration,
        SliceTopology,
        rank_layouts,
    )
    from .layouts import BENCH_MODELS, enumerate_layouts

    model, model_name = _parse_model(args.model)
    if model is None:
        print(f"error: unknown --model {model_name!r} "
              f"(names: {sorted(BENCH_MODELS)})", file=sys.stderr)
        return 2

    topo = SliceTopology(
        chips=args.devices, ici_domain=args.ici_domain,
        generation=args.generation,
    )
    pinning = args.check_golden or args.repin_golden
    calibration = (
        Calibration.default() if pinning
        else resolve_calibration(args.run_dir, args.obs_root)
    )
    ladder = None
    if args.mbs_ladder and not pinning:
        try:
            ladder = [int(x) for x in args.mbs_ladder.split(",") if x.strip()]
        except ValueError:
            ladder = None
        if not ladder or any(m < 1 for m in ladder):
            print(f"error: bad --mbs-ladder {args.mbs_ladder!r} "
                  "(want a comma list of ints >= 1)", file=sys.stderr)
            return 2
    layouts = enumerate_layouts(
        args.devices, model, global_batch_size=args.global_batch,
        micro_batch_size=args.mbs, mbs_ladder=ladder,
    )
    if not layouts:
        print("error: no valid layouts for this model/device count",
              file=sys.stderr)
        return 2
    correction = None
    if args.correct_from_runs and not pinning:
        correction = AxisCorrection.from_run_dirs(args.correct_from_runs)
        if correction is None:
            print(
                f"correction: no tuner prediction/measured pairs under "
                f"{args.correct_from_runs}; ranking uncorrected",
                file=sys.stderr,
            )
    ranked = rank_layouts(model, layouts, topo, calibration,
                          correction=correction)
    cal = calibration or Calibration.default()

    best = ranked[0]
    prediction = {
        "label": best.layout.label,
        "predicted_step_s": round(best.predicted_step_s, 6),
        "world_size": best.layout.world,
        "source": cal.source,
        "collectives_source": best.collectives_source,
    }
    payload = {
        "devices": args.devices,
        "model": model_name,
        "model_spec": {
            "hidden_size": model.hidden_size,
            "num_layers": model.num_layers,
            "num_attention_heads": model.num_attention_heads,
            "num_kv_heads": model.num_kv_heads,
            "sequence_length": model.sequence_length,
            "vocab_size": model.vocab_size,
            "mlp_factor": model.mlp_factor,
            "parameter_count": model.parameter_count,
        },
        "global_batch_size": args.global_batch,
        "micro_batch_size": args.mbs,
        "slice_topology": topo.to_dict(),
        "calibration": cal.to_dict(),
        "axis_correction": correction.to_dict() if correction else None,
        "ranked": [s.to_dict() for s in ranked],
        "topology_config": best.layout.topology_dict(),
        "prediction": prediction,
    }
    if args.lower:
        from ..analysis.cli import _ensure_virtual_mesh

        _ensure_virtual_mesh()  # lowering needs the 8-device CPU mesh
        payload["lowered_crosscheck"] = _lowered_crosscheck(ranked, args.lower)

    print(f"tune: {len(ranked)} valid layout(s) of {model_name} on "
          f"{args.devices} device(s) [{topo.generation}, ici_domain="
          f"{topo.domain}]")
    print(f"calibration: efficiency={cal.compute_efficiency:.3f} "
          f"({cal.source})")
    if correction is not None:
        facs = " ".join(
            f"{a}={f:.3f}" for a, f in sorted(correction.factors.items())
        )
        print(f"axis correction: {facs or '(none)'} "
              f"[{correction.pairs} pair(s), {correction.source}]")
    header = (f"{'rank':>4} {'layout':<28} {'step_s':>9} {'tok/s':>10} "
              f"{'bubble':>7} {'comm_s':>8} {'mem_GB':>7} links")
    print(header)
    for i, s in enumerate(ranked[: args.top]):
        links = ",".join(
            f"{ax}:{rec['link']}" for ax, rec in sorted(s.comm_by_axis.items())
        )
        print(
            f"{i + 1:>4} {s.layout.label:<28} {s.predicted_step_s:>9.4f} "
            f"{s.tokens_per_s:>10.0f} {s.bubble_fraction:>6.1%} "
            f"{s.comm_s:>8.4f} {s.memory_gb:>7.2f} {links}"
        )
    print(f"best: {best.layout.label} predicted {best.predicted_step_s:.4f}"
          f"s/step ({best.tokens_per_s:.0f} tokens/s)")
    print("export " + "SCALING_TPU_TUNER_PREDICTION='"
          + json.dumps(prediction) + "'")

    if args.emit_config:
        Path(args.emit_config).write_text(
            json.dumps(payload["topology_config"], indent=1) + "\n"
        )
    if args.record_events:
        from ..logging.logger import append_jsonl_line

        append_jsonl_line(
            args.record_events,
            json.dumps(
                {"event": "tuner-prediction", "ts": time.time(), **prediction},
                sort_keys=True,
            ),
        )
    if args.json:
        Path(args.json).write_text(json.dumps(payload, indent=1) + "\n")

    gpath = golden_path(args.devices, model_name)
    if args.repin_golden:
        gpath.parent.mkdir(parents=True, exist_ok=True)
        gpath.write_text(json.dumps(
            {
                "calibration": "pinned-default",
                "ranked": [
                    {"label": s.to_dict()["label"],
                     "predicted_step_s": s.to_dict()["predicted_step_s"]}
                    for s in ranked
                ],
            },
            indent=1,
        ) + "\n")
        print(f"golden repinned -> {gpath}")
    elif args.check_golden:
        drift = check_golden(payload, gpath)
        for line in drift:
            print(f"DRIFT: {line}")
        print(f"golden: {'OK' if not drift else 'DRIFT'}")
        return 1 if drift else 0
    return 0


if __name__ == "__main__":
    sys.exit(main())
