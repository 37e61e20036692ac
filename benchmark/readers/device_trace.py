"""Per-layer metrics read from the one reduction of the profiler's trace
(benchmark/trace_reduce.py). Without a trace a reader returns nothing."""

from benchmark import ops_count


def device_idle_pct(ctx):
    trace = ctx["trace"]
    if not trace or trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])


def collective_time_pct(ctx):
    """Device time inside collectives (total, not only the exposed part)
    over the traced window, averaged over the chips."""
    trace = ctx["trace"]
    if not trace or trace["window_s"] <= 0:
        return None
    return 100.0 * trace["class_s"].get("collective", 0.0) / trace["window_s"]


def splash_roofline(ctx):
    """Compute-bound: FLOPs causal attention needs for the traced steps'
    forward and backward calls over the splash kernels' device time, as a
    share of the chip's published bf16 peak."""
    trace, host, peaks = ctx["trace"], ctx["host"], ctx["device"]["peaks"]
    if not trace or peaks is None:
        return None
    kernel_s = sum(v for k, v in trace["class_s"].items()
                   if k.startswith("pallas:splash_mha"))
    steps = trace["modules"].get("jit_step", {}).get("count")
    if kernel_s <= 0 or not steps:
        return None
    arch = ctx["config"]["transformer_architecture"]
    topo = ctx["config"]["topology"]
    heads = arch["num_attention_heads"] // topo["model_parallel_size"]
    flops = steps * arch["num_layers"] * ops_count.splash_flops(
        host["micro_batch"], host["seq"], heads,
        arch["hidden_size"] // arch["num_attention_heads"], backward=True)
    return 100.0 * flops / kernel_s / peaks["flops_per_s"]


def paged_roofline(ctx):
    """Bandwidth-bound: bytes of keys and values the traced ticks' rows had
    to read over the paged kernel's device time, as a share of the chip's
    published HBM bandwidth."""
    trace, host, peaks = ctx["trace"], ctx["host"], ctx["device"]["peaks"]
    if not trace or peaks is None:
        return None
    # the engine's mixed program holds one Pallas kernel, the paged one
    kernel_s = sum(v for k, v in trace["class_s"].items()
                   if k.startswith("pallas:") and "splash" not in k)
    tokens = host.get("traced_context_tokens")
    if kernel_s <= 0 or not tokens:
        return None
    arch = ctx["config"]["transformer_architecture"]
    nbytes = arch["num_layers"] * ops_count.paged_kv_bytes(
        tokens, arch["attention_num_kv_heads"],
        arch["hidden_size"] // arch["num_attention_heads"], 2)
    return 100.0 * nbytes / kernel_s / peaks["hbm_bytes_per_s"]
