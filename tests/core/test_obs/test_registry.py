"""Metrics registry units: labels, histogram buckets, textfile render,
JSONL flush (ISSUE 5 satellite: registry test coverage)."""

import json

import pytest

from scaling_tpu.obs.registry import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
)


def test_counter_inc_and_monotonicity():
    reg = MetricsRegistry()
    c = reg.counter("restarts_total")
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    with pytest.raises(ValueError):
        c.inc(-1)


def test_counter_coerces_numpy_scalars_for_json():
    """inc() must coerce like Gauge.set: a numpy scalar surviving to
    flush_step's json.dumps would abort the training step."""
    np = pytest.importorskip("numpy")
    reg = MetricsRegistry()
    reg.counter("x").inc(np.float32(2))
    reg.gauge("g").set(np.float64(1.5))
    snap = reg.snapshot()
    assert type(snap["counters"]["x"]) is float
    json.dumps(snap)  # must not raise


def test_labels_create_distinct_children_and_get_or_create():
    reg = MetricsRegistry()
    a = reg.gauge("mem", {"device": "0"})
    b = reg.gauge("mem", {"device": "1"})
    assert a is not b
    # same labels (any ordering/value types) -> the same child
    assert reg.gauge("mem", {"device": 0}) is a


def test_kind_conflict_raises():
    reg = MetricsRegistry()
    reg.counter("x")
    with pytest.raises(TypeError):
        reg.gauge("x")


def test_observe_many_leaves_what_observing_each_in_order_leaves():
    """One take of the lock a batch; buckets, count and the sum, to the
    last bit (added in the same order), as after a call a value."""
    values = [0.05, 0.5, 1e-9, 0.1, 5.0, 50.0, 0.3333333333333333, 0.1]
    one, many = (MetricsRegistry().histogram("lat", buckets=(0.1, 1.0, 10.0))
                 for _ in range(2))
    for v in values:
        one.observe(v)
    many.observe_many(values[:3])
    many.observe_many(iter(values[3:]))  # any iterable
    many.observe_many([])
    assert many.bucket_counts() == one.bucket_counts()
    assert (many.sum, many.count) == (one.sum, one.count)  # ==, not approx
    assert one.count == 8 and one.sum == pytest.approx(sum(values))


def test_histogram_buckets_cumulative():
    reg = MetricsRegistry()
    h = reg.histogram("lat", buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.5, 0.5, 5.0, 50.0):
        h.observe(v)
    counts = h.bucket_counts()
    assert counts == {"0.1": 1, "1": 3, "10": 4, "+Inf": 5}
    assert h.count == 5
    assert h.sum == pytest.approx(56.05)


def test_histogram_boundary_lands_in_its_bucket():
    reg = MetricsRegistry()
    h = reg.histogram("lat", buckets=(1.0,))
    h.observe(1.0)  # le="1" is inclusive, Prometheus-style
    assert h.bucket_counts() == {"1": 1, "+Inf": 1}


def test_snapshot_shape():
    reg = MetricsRegistry()
    reg.counter("steps").inc(3)
    reg.gauge("mfu").set(0.41)
    reg.gauge("unset_gauge")  # never set -> omitted
    reg.histogram("span_seconds", {"span": "step.data"}).observe(0.2)
    snap = reg.snapshot()
    assert snap["counters"] == {"steps": 3.0}
    assert snap["gauges"] == {"mfu": 0.41}
    hist = snap["histograms"]["span_seconds{span=step.data}"]
    assert hist["count"] == 1 and hist["sum"] == pytest.approx(0.2)


def test_textfile_render_prometheus_format():
    reg = MetricsRegistry()
    reg.counter("train_steps_total").inc(7)
    reg.gauge("device_bytes_in_use", {"device": "0"}).set(1024)
    reg.histogram("lat", buckets=(1.0,)).observe(0.5)
    text = reg.render_textfile()
    assert "# TYPE train_steps_total counter" in text
    assert "train_steps_total 7" in text
    assert 'device_bytes_in_use{device="0"} 1024' in text
    assert '# TYPE lat histogram' in text
    assert 'lat_bucket{le="1"} 1' in text
    assert 'lat_bucket{le="+Inf"} 1' in text
    assert "lat_sum 0.5" in text and "lat_count 1" in text


def test_write_textfile_atomic(tmp_path):
    reg = MetricsRegistry()
    reg.gauge("g").set(1)
    out = tmp_path / "metrics.prom"
    reg.write_textfile(out)
    assert "g 1" in out.read_text()
    # no temp debris left behind
    assert list(tmp_path.iterdir()) == [out]


def test_flush_step_appends_jsonl(tmp_path):
    reg = MetricsRegistry()
    path = tmp_path / "metrics.jsonl"
    prom = tmp_path / "metrics.prom"
    reg.configure(metrics_path=str(path), textfile_path=str(prom))
    reg.counter("steps").inc()
    reg.flush_step(1)
    reg.counter("steps").inc()
    reg.flush_step(2)
    recs = [json.loads(l) for l in path.read_text().splitlines()]
    assert [r["step"] for r in recs] == [1, 2]
    assert [r["kind"] for r in recs] == ["registry", "registry"]
    assert recs[1]["counters"]["steps"] == 2.0
    assert "ts" in recs[0] and "host" in recs[0]
    assert "steps 2" in prom.read_text()


def test_flush_step_nan_gauge_lands_as_null(tmp_path):
    reg = MetricsRegistry()
    path = tmp_path / "metrics.jsonl"
    reg.configure(metrics_path=str(path))
    reg.gauge("mfu").set(float("nan"))
    reg.flush_step(1)
    raw = path.read_text()
    assert "NaN" not in raw  # bare NaN is not JSON outside Python
    assert json.loads(raw)["gauges"]["mfu"] is None


def test_flush_step_textfile_via_env(tmp_path, monkeypatch):
    """SCALING_TPU_METRICS_TEXTFILE turns on the Prometheus textfile
    render without any code-level configure() — node-exporter scraping
    is a deployment decision, not a model-config one."""
    prom = tmp_path / "node" / "scaling_tpu.prom"
    monkeypatch.setenv("SCALING_TPU_METRICS_TEXTFILE", str(prom))
    reg = MetricsRegistry()
    reg.configure(metrics_path=str(tmp_path / "metrics.jsonl"))
    reg.counter("steps").inc(3)
    reg.flush_step(1)
    assert "steps 3" in prom.read_text()


def test_flush_step_without_sink_is_noop(monkeypatch):
    from scaling_tpu.logging import logger

    monkeypatch.delenv("SCALING_TPU_METRICS_PATH", raising=False)
    monkeypatch.setattr(logger, "_config", None)
    reg = MetricsRegistry()
    reg.counter("steps").inc()
    reg.flush_step(1)  # must not raise, must not write anywhere


def test_metric_classes_exported():
    assert Counter.kind == "counter"
    assert Gauge.kind == "gauge"
    assert Histogram.kind == "histogram"


def test_cardinality_cap_folds_overflow_series():
    """Past MAX_SERIES_PER_METRIC distinct label sets, NEW series fold
    into one __overflow__ series — aggregate totals stay right, and the
    registry (snapshot, textfile render) stops growing."""
    from scaling_tpu.obs.registry import (
        MAX_SERIES_PER_METRIC,
        OVERFLOW_LABELS,
    )

    reg = MetricsRegistry()
    n = MAX_SERIES_PER_METRIC + 25
    for i in range(n):
        reg.counter("leaky_total", labels={"req": i}).inc()
    counters = reg.snapshot()["counters"]
    series = [k for k in counters if k.startswith("leaky_total")]
    assert len(series) == MAX_SERIES_PER_METRIC + 1
    overflow_key = "leaky_total{__overflow__=true}"
    assert overflow_key in counters
    assert counters[overflow_key] == n - MAX_SERIES_PER_METRIC
    # the overflow series is shared: a second novel label set lands in
    # the SAME metric object
    m1 = reg.counter("leaky_total", labels={"req": "novel-a"})
    m2 = reg.counter("leaky_total", labels={"req": "novel-b"})
    assert m1 is m2 and m1.labels == OVERFLOW_LABELS
    # existing (pre-cap) series still resolve to their own objects
    early = reg.counter("leaky_total", labels={"req": 0})
    assert early.labels != OVERFLOW_LABELS
    # other metric names are unaffected by leaky_total's overflow
    other = reg.counter("fine_total", labels={"x": 1})
    assert other.labels == (("x", "1"),)
    # reset clears the guard state too
    reg.reset()
    fresh = reg.counter("leaky_total", labels={"req": "post-reset"})
    assert fresh.labels != OVERFLOW_LABELS


def test_cardinality_cap_ignores_unlabeled_metrics():
    """Unlabeled metrics never fold: there is exactly one series per
    name, which is the point of the cap."""
    from scaling_tpu.obs.registry import MAX_SERIES_PER_METRIC

    reg = MetricsRegistry()
    for i in range(MAX_SERIES_PER_METRIC + 5):
        reg.gauge("g", labels={"k": i}).set(float(i))
    plain = reg.gauge("plain")
    plain.set(1.0)
    assert plain.labels == ()
    assert reg.gauge("plain") is plain
