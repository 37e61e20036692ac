"""The one trace reduction, on a small trace recorded on the chip, against
numbers worked out by hand and by brute force; and the benchmark's own
counts of operations and bytes against hand counts at one shape."""

import json
from pathlib import Path

import numpy as np
import pytest

from benchmark import cells, model, ops_count, trace_reduce
from benchmark.views import dense_decoder as view

SAMPLE = Path(__file__).parent / "data" / "tpu_trace_events.json"


@pytest.fixture(scope="module")
def events():
    return json.loads(SAMPLE.read_text())


def test_names_and_classes():
    fusion = ("%fusion.446 = (bf16[32768,4096]{1,0:T(8,128)(2,1)}, f32[32768,4096]{1,0}) "
              "fusion(bf16[4096]{0} %p), kind=kLoop, calls=%fused_computation.3")
    kernel = ('%mixed.16 = bf16[16,32,32,128]{3,2,1,0:T(8,128)(2,1)} custom-call(s32[16,256]{1,0} '
              '%tables.1), custom_call_target="tpu_custom_call", operand_layout_constraints={}')
    concat = '%custom-call.62 = bf16[4096,1024]{1,0} custom-call(bf16[1024,1024]{1,0} %s), custom_call_target="ConcatBitcast"'
    assert trace_reduce.short_name(fusion) == "fusion.446"
    assert trace_reduce.short_name("jit_step(12536509211202233264)") == "jit_step"
    assert trace_reduce.display_name(fusion) == "fusion.446 (bf16[32768,4096], f32[32768,4096])"
    assert trace_reduce.display_name(kernel) == "mixed.16 bf16[16,32,32,128]"
    assert trace_reduce.op_class(fusion) == "other"
    assert trace_reduce.op_class(kernel) == "pallas:mixed"
    assert trace_reduce.op_class(concat) == "other"
    assert trace_reduce.op_class("%all-gather-start.3 = bf16[8]{0} all-gather-start(bf16[4]{0} %x)") == "collective"
    # XLA's name for a combined asynchronous collective, seen on four chips
    assert trace_reduce.op_class("%async-collective-done = bf16[4608,128000]{1,0} fusion(bf16[4608,64000]{1,0} %g)") == "collective"


def test_recorded_sample_by_hand(events):
    r = trace_reduce.reduce_events(events, chips=1)
    ops = events["devices"]["0"]["ops"]
    t0 = min(s for _, s, _ in ops)
    # the window: first operation's start to the last one's end. By hand:
    # the last operation, copy.146, starts 32,696,467 ns after the first
    # and lasts 406,148 ns.
    assert r["window_s"] == pytest.approx(33_102_615e-9, abs=1e-12)
    # busy: nanoseconds in which some operation ran, marked one by one
    grid = np.zeros(33_102_615, bool)
    for _, s, d in ops:
        grid[int(s - t0): int(s - t0 + d)] = True
    assert r["busy_s"] == pytest.approx(grid.sum() * 1e-9, abs=2e-9 * len(ops))
    assert 0.87 < r["busy_s"] / r["window_s"] < 0.89  # 12% idle: the cut-out parts
    # per class and per operation: one splash kernel of 2,690,683 ns
    assert r["class_s"]["pallas:splash_mha_fwd_segmented_residuals"] == pytest.approx(2_690_683e-9)
    assert sum(r["class_s"].values()) == pytest.approx(sum(d for _, _, d in ops) * 1e-9)
    assert r["top_ops"][0] == [
        "fusion.446 (bf16[32768,4096], f32[32768,4096], f32[32768,4096], f32[32768,4096])",
        pytest.approx(5_715_231e-9)]
    assert [n for n, _ in r["top_ops"][:3]][2].startswith("splash_mha_fwd_segmented_residuals.2 ")
    assert len(r["top_ops"]) == 10
    assert r["modules"]["jit_step"]["count"] == 2
    assert r["modules"]["jit_make_batch"] == {"count": 1, "seconds": pytest.approx(4_602e-9)}


def test_idle_gaps_are_named_by_the_innermost_host_call(events):
    r = trace_reduce.reduce_events(events, chips=1)
    ops = sorted(events["devices"]["0"]["ops"], key=lambda o: o[1])
    host = sorted(events["host"], key=lambda h: h[2])  # innermost = shortest first
    want = {}
    for (_, s1, d1), (_, s2, _) in zip(ops, ops[1:]):
        gap = s2 - (s1 + d1)
        if gap >= 1e3:
            mid = s1 + d1 + gap / 2
            name = next((n for n, s, d in host if s <= mid <= s + d),
                        "(no host call recorded)")
            want[name] = want.get(name, 0.0) + gap * 1e-9
    got = dict(r["idle_gaps"])
    assert got.keys() == want.keys() and len(got) >= 1
    for name, seconds in want.items():
        assert got[name] == pytest.approx(seconds)
    # the longest: between one train step's last operation and the next
    # program's first, the host was inside the read of the loss
    assert r["idle_gaps"][0][0] == "$array.py:631 _value"


def test_busy_time_is_averaged_over_chips(events):
    two = {"devices": {"0": events["devices"]["0"],
                       "1": {"ops": events["devices"]["0"]["ops"][:8], "modules": []}},
           "host": events["host"]}
    one = trace_reduce.reduce_events(events, chips=1)
    first8 = sum(d for _, _, d in events["devices"]["0"]["ops"][:8]) * 1e-9
    both = trace_reduce.reduce_events(two, chips=2)
    assert both["chips"] == 2 and both["window_s"] == one["window_s"]
    assert both["busy_s"] == pytest.approx((one["busy_s"] + first8) / 2, rel=1e-6)
    assert trace_reduce.reduce_events({"devices": {}, "host": []}, chips=1) is None


def test_operation_and_byte_counts_by_hand():
    # causal attention, batch 2, sequence 4096, 32 heads of 128: the unmasked
    # half is 2*32*(4096*4096/2)*128 = 68,719,476,736 multiply-adds per matmul;
    # forward 2 matmuls, backward 4, 2 FLOPs each
    assert ops_count.splash_flops(2, 4096, 32, 128, backward=False) == 4 * 68_719_476_736
    assert ops_count.splash_flops(2, 4096, 32, 128, backward=True) == 12 * 68_719_476_736
    # keys and values of 1000 cached tokens, 8 KV heads of 128, bf16
    assert ops_count.paged_kv_bytes(1000, 8, 128, 2) == 4_096_000
    # Mistral-7B-v0.3 at depth 3: per layer 2*4096*4096 (q, o) + 2*4096*1024
    # (k, v) + 3*4096*14336 (MLP) + 2*4096 (norms) = 218,112,000; + final norm
    # 4096 + head 4096*32768; the embedding table is left out
    config = cells.load_json(cells.ROOT / "configs" / "mistral-7b-v0.3.json")
    from scaling_tpu.models.transformer.model import init_model

    shapes = model.param_shapes(init_model(
        model.transformer_config(config, {}, num_layers=3), None))
    n = view.matmul_param_count(shapes)
    assert n == 3 * 218_112_000 + 4096 + 4096 * 32768 == 788_557_824
    assert ops_count.train_flops_per_token(n, 3, 32, 128, 4096) == (
        6 * 788_557_824 + 6 * 3 * 32 * 128 * 4096)


def test_operations_are_summed_by_stem_beside_the_largest_instances(events):
    """Many small instances of one operation must not hide below a few large
    ones: the sums by stem (the instruction's name without its number), each
    with the number of events it sums, against a sum made by hand here."""
    import re

    r = trace_reduce.reduce_events(events, chips=1)
    ops = events["devices"]["0"]["ops"]
    by_hand = {}
    for name, _, dur in ops:
        stem = re.sub(r"\.\d+$", "", name.split(" = ", 1)[0].lstrip("%"))
        total, count = by_hand.get(stem, (0.0, 0))
        by_hand[stem] = (total + dur * 1e-9, count + 1)
    assert trace_reduce.stem("%copy.184 = bf16[4097,16,8,128]{3,2,1,0} copy(%p)") == "copy"
    assert trace_reduce.stem("%paged_attention.31 = bf16[16,32,32,128] custom-call()") == "paged_attention"
    assert len(r["top_stems"]) == min(10, len(by_hand))
    assert [s for _, s in r["top_stems"]] == sorted((s for _, s in r["top_stems"]), reverse=True)
    for label, seconds in r["top_stems"]:
        stem, count = re.fullmatch(r"sum:(.+) x(\d+)", label).groups()
        assert by_hand[stem] == (pytest.approx(seconds), int(count))
    # the stems' largest is at least the largest instance: a sum holds it
    assert r["top_stems"][0][1] >= r["top_ops"][0][1]
    # over two chips a stem's count and seconds are a chip's share
    two = {"devices": {"0": events["devices"]["0"], "1": events["devices"]["0"]},
           "host": events["host"]}
    assert trace_reduce.reduce_events(two, chips=2)["top_stems"] == [
        [label, pytest.approx(seconds)] for label, seconds in r["top_stems"]]
