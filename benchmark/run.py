"""Run one cell of BENCHMARK.json once.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1|2>

Loads the cell's configuration and traffic by name, makes the weights from
the seed, warms up every shape the window uses (all of that is ``setup_s``),
measures for ``--seconds``, checks the outputs against the plain reference
and prints one JSON object as the last line of stdout: with ``--trace 0`` the
cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics from a
profiled slice of the window, with ``--trace 2`` both: the window runs as
under ``--trace 0``, its numbers are taken, and only then are a few seconds
more of the same traffic traced, in the same process. Without a TPU, or with
fewer chips than the cell asks for, it exits non-zero and prints no result.
``--rehearse`` walks the same code on the CPU (kernels interpreted) and never
prints a result line. ``--control fp8`` (never the driver's) also reads what
the reference in that lower precision would give: ``benchmark/control.py``.
"""

from __future__ import annotations

import time

T0 = time.monotonic()  # set-up is counted from the start of the process

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

REPO = Path(__file__).resolve().parent.parent
# the traced run profiles a slice that starts this far into the window
TRACE_AFTER_SHARE = 1 / 3
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))


def kinds() -> dict:
    from benchmark import serve_kind, train_kind

    return {"train": train_kind.run, "serve": serve_kind.run}


def parse(argv):
    from benchmark import cells

    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--seconds", type=float, default=None,
                   help="length of the measured window (default: run_seconds)")
    p.add_argument("--trace", type=int, choices=(0, 1, 2), default=0)
    p.add_argument("--rehearse", action="store_true",
                   help="CPU walk-through, kernels interpreted; prints no result")
    p.add_argument("--control", default=None,
                   help="also read what the reference in this lower precision "
                        "would give (benchmark/control.py); never the driver's")
    p.add_argument("--benchmark-json", type=Path,
                   default=cells.REPO / "BENCHMARK.json", help=argparse.SUPPRESS)
    p.add_argument("--root", type=Path, default=cells.ROOT,
                   help=argparse.SUPPRESS)
    return p.parse_args(argv)


def run_cell(args) -> dict:
    """The result object of one run (the last line, before it is printed)."""
    from benchmark import cells, device as dev, trace_reduce
    from benchmark.peaks import peaks_of
    from benchmark.tracing import Tracer

    cell = cells.load_cell(args.workload, args.benchmark_json, args.root)
    if args.seconds is None:
        args.seconds = float(cell.bench["run_seconds"])
    device = dev.claim_device(cell.chips, args.rehearse)
    import jax

    traffic = cell.traffic
    tracer = Tracer(
        enabled=args.trace == 1,
        out_dir=args.root.resolve().parent / ".bench_trace" / cell.name,
        start_after_s=TRACE_AFTER_SHARE * args.seconds,
        min_s=min(float(traffic.get("trace_seconds", 1.0)), args.seconds / 3),
        after_window=args.trace == 2,
    )
    def mark(label: str) -> None:
        """Where set-up time goes: seconds since the process started."""
        print(f"[setup] {time.monotonic() - T0:6.1f} s  {label}",
              file=sys.stderr, flush=True)

    mark("JAX has the device")
    env = {"t0": T0, "compiles": dev.CompileCounter(), "tracer": tracer,
           "mark": mark, "control": args.control}
    outcome = kinds()[cell.kind](cell, args, env)
    tracer.maybe_stop(force=True)

    used_ids = set(outcome["devices"])
    used = [d for d in jax.devices() if d.id in used_ids]
    peaks = dev.memory_peaks(used, outcome["live_bytes"])
    device_out = {"platform": device["platform"], "kind": device["kind"],
                  "count": device["count"], "memory_peak_bytes": max(peaks)}
    values = {"setup_s": outcome["setup_s"], **outcome["end_to_end"]}
    result = {"correct": outcome["correct"], "attempted": outcome["attempted"],
              "failed": outcome["failed"], **outcome.get("notes", {})}
    # --trace 0: end-to-end; 1: per-layer; 2: both, side by side
    metrics = {} if args.trace == 1 else {
        m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
        for m in cell.metrics("end_to_end")}
    if args.trace:
        reduction = None
        trace_file = tracer.trace_file()
        if trace_file is not None:
            reduction = trace_reduce.reduce_events(
                trace_reduce.load_events(trace_file), chips=cell.chips)
        if reduction is None:
            # the CPU walk-through has no device plane; on the chip a traced
            # run in which no operation ran on the device is no run
            if not args.rehearse:
                sys.exit("benchmark: the trace holds no device operation")
        else:
            device_out["busy_s"] = reduction["busy_s"]
            device_out["window_s"] = reduction["window_s"]
            # a list holds ten entries: the five largest sums by stem, then
            # the five largest single operations; stderr has ten of each
            for name, seconds in reduction["top_stems"] + reduction["top_ops"]:
                print(f"[trace] {seconds:9.6f} s  {name}", file=sys.stderr)
            result["breakdown"] = {
                "device_ops": reduction["top_stems"][:5] + reduction["top_ops"][:5],
                "idle_gaps": reduction["idle_gaps"]}
        ctx = {
            "cell": cell.name, "kind": cell.kind, "chips": cell.chips,
            "config": cell.config, "traffic": traffic, "host": outcome["host"],
            "trace": reduction, "end_to_end": values,
            "device": {**device_out, "memory_peaks": peaks,
                       "peaks": None if args.rehearse else peaks_of(device["kind"])},
        }
        if args.trace == 2:
            # peak_hbm_gb.* reads what was taken as the window closed, before
            # any capture; the line's memory_peak_bytes is the whole run's
            ctx["device"].update(memory_peaks=outcome["window_peaks"],
                                 memory_peak_bytes=max(outcome["window_peaks"]))
        for m in cell.metrics("per_layer"):
            value = cells.load_reader(m["name"], args.root)(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        if args.trace == 2:
            tracer.discard()  # reduced and read: the trace goes
    result.update(metrics=metrics, device=device_out)
    return result


def main(argv=None) -> dict:
    args = parse(argv)
    result = run_cell(args)
    if args.rehearse:
        print(json.dumps({"rehearsal": True, "workload": args.workload}))
    else:
        print(json.dumps(result), flush=True)
    return result


if __name__ == "__main__":
    main()
