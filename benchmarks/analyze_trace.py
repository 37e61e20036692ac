"""Schema-free xplane trace parser -> top ops by self time per trace line.

No external tooling: the installed tensorboard profile plugin's generated
protos are incompatible with the installed protobuf, so this walks the
wire format directly. Field numbers (verified empirically via
``protoc --decode_raw``):
  XSpace.planes=1; XPlane: name=2, lines=3, event_metadata=4 (map k=1 v=2);
  XEventMetadata: id=1, name=2; XLine: id=1, name=2, timestamp=3, events=4;
  XEvent: metadata_id=1, offset_ps=2, duration_ps=3.

Usage: python benchmarks/analyze_trace.py <trace_dir> [line-filter]
"""
import glob
import sys
from collections import defaultdict


def read_varint(buf, i):
    shift = val = 0
    while True:
        b = buf[i]
        i += 1
        val |= (b & 0x7F) << shift
        if not b & 0x80:
            return val, i
        shift += 7


def walk(buf):
    """Yield (field_number, wire_type, value) for one message buffer."""
    i, n = 0, len(buf)
    while i < n:
        tag, i = read_varint(buf, i)
        fn, wt = tag >> 3, tag & 7
        if wt == 0:
            v, i = read_varint(buf, i)
        elif wt == 1:
            v, i = buf[i : i + 8], i + 8
        elif wt == 2:
            ln, i = read_varint(buf, i)
            v, i = buf[i : i + ln], i + ln
        elif wt == 5:
            v, i = buf[i : i + 4], i + 4
        else:
            raise ValueError(f"wire type {wt}")
        yield fn, wt, v


def fields(buf, fn_want):
    return [v for fn, _, v in walk(buf) if fn == fn_want]


def first_varint(buf, fn_want, default=0):
    for fn, wt, v in walk(buf):
        if fn == fn_want and wt == 0:
            return v
    return default


def main():
    path = sys.argv[1] if len(sys.argv) > 1 else "chiprun_out/bench_trace"
    line_filter = sys.argv[2] if len(sys.argv) > 2 else ""
    files = glob.glob(f"{path}/**/*.xplane.pb", recursive=True)
    if not files:
        sys.exit(f"no xplane files under {path}")
    for f in files:
        space = open(f, "rb").read()
        for plane in fields(space, 1):
            pname = b"".join(fields(plane, 2)).decode(errors="replace")
            ev_names = {}
            for entry in fields(plane, 4):
                key = first_varint(entry, 1)
                for meta in fields(entry, 2):
                    nm = b"".join(
                        v for fn, wt, v in walk(meta) if fn == 2 and wt == 2
                    ).decode(errors="replace")
                    ev_names[key] = nm
            for line in fields(plane, 3):
                lname = b"".join(
                    v for fn, wt, v in walk(line) if fn == 2 and wt == 2
                ).decode(errors="replace")
                if line_filter and line_filter not in lname:
                    continue
                totals = defaultdict(int)
                counts = defaultdict(int)
                for ev in fields(line, 4):
                    mid = first_varint(ev, 1)
                    dur = first_varint(ev, 3)
                    totals[ev_names.get(mid, str(mid))] += dur
                    counts[ev_names.get(mid, str(mid))] += 1
                tot = sum(totals.values())
                if tot < 1e6:  # skip sub-microsecond lines
                    continue
                print(f"== {pname} :: {lname}: {tot/1e9:.2f} ms total")
                for name, d in sorted(totals.items(), key=lambda kv: -kv[1])[:25]:
                    print(
                        f"   {d/1e9:9.3f} ms {100*d/tot:5.1f}% "
                        f"x{counts[name]:<5} {name[:100]}"
                    )
                buckets = defaultdict(int)
                for name, d in totals.items():
                    buckets[classify(name)] += d
                summary = "  ".join(
                    f"{b}={100*d/tot:.1f}%"
                    for b, d in sorted(buckets.items(), key=lambda kv: -kv[1])
                )
                print(f"   buckets: {summary}")


_BUCKETS = (
    # substring -> bucket; first match wins, so collectives beat the
    # generic 'fusion' and pallas custom-calls beat 'copy' inside names
    (("all-reduce", "all-gather", "reduce-scatter", "collective-permute",
      "all-to-all"), "collective"),
    (("custom-call", "tpu_custom_call", "splash", "flash", "mosaic"), "pallas"),
    (("dot", "convolution", "cublas", "matmul"), "matmul"),
    (("copy", "transpose", "bitcast", "reshape", "slice",
      "concatenate"), "layout"),
    (("fusion", "loop_"), "fused-elementwise"),
)


def classify(name: str) -> str:
    """Coarse MFU-attribution buckets by op-name substring. 'matmul' +
    'pallas' is the useful-FLOPs share; 'layout' + 'collective' is the
    overhead to attack. XLA names fusions after their root op
    ('loop_dot_fusion', 'loop_slice_fusion'), so a named root wins the
    bucket — that root dominates the fusion's time — and only anonymous
    fusions fall to the catch-all 'fused-elementwise' bucket."""
    low = name.lower()
    for subs, bucket in _BUCKETS:
        if any(s in low for s in subs):
            return bucket
    return "other"


if __name__ == "__main__":
    main()
