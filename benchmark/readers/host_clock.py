"""Per-layer metrics read from the harness's own host clocks and counts."""

from statistics import median

from benchmark.stats import percentile


def step_ms_p50(ctx):
    """Median over the window's chunks of chunk time / steps in the chunk."""
    steps = ctx["host"].get("chunk_step_s")
    return 1e3 * median(steps) if steps else None


def mfu_pct(ctx):
    """FLOPs the trained tokens require (benchmark/ops_count.py) over the
    published peak of the chips used."""
    host, peaks = ctx["host"], ctx["device"]["peaks"]
    if peaks is None or "flops_per_token" not in host:
        return None
    # from the median step, so that the profiler's own stalls in a traced
    # run do not read as a slower model
    tokens_per_s = host["tokens_per_step"] / median(host["chunk_step_s"])
    return 100.0 * host["flops_per_token"] * tokens_per_s / (
        ctx["chips"] * peaks["flops_per_s"])


def peak_hbm_gb(ctx):
    """Fullest chip: the larger of peak_bytes_in_use and the bytes live as
    the window opened + peak_bytes_reserved (benchmark/device.py)."""
    return ctx["device"]["memory_peak_bytes"] / 1e9


def tick_ms_p50(ctx):
    ticks = ctx["host"].get("tick_s")
    return 1e3 * median(ticks) if ticks else None


def gen_late_ms_p95(ctx):
    """How late the load generator submitted, against when each request
    was due: a starved generator must not read as a fast server."""
    late = ctx["host"].get("submit_late_s")
    return 1e3 * percentile(late, 95) if late else None


def batch_occupancy_pct(ctx):
    """Decode rows per tick over the engine's slots, mean over the window."""
    rows = ctx["host"].get("decode_rows")
    return 100.0 * sum(rows) / (len(rows) * ctx["host"]["num_slots"]) if rows else None


def kv_pool_fill_pct(ctx):
    """Tokens the ticks' rows held in the KV pool over the tokens the pool
    was reserved for, mean over the window's ticks: a reserved pool that the
    traffic never fills is not fill."""
    held = ctx["host"].get("context_tokens")
    return 100.0 * sum(held) / (len(held) * ctx["host"]["pool_tokens"]) if held else None


def ttft_p50_ms(ctx):
    """Time from when a request was due to its first token, median."""
    ttft = ctx["host"].get("ttft_s")
    return 1e3 * percentile(ttft, 50) if ttft else None

