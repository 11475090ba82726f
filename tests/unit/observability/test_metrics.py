"""Metrics registry: log-bucket histogram math vs numpy ground truth,
Prometheus text golden output, registry semantics (get-or-create, type
conflicts, reset-in-place), monitor-bridge events, interval deltas."""

import numpy as np
import pytest

from deepspeed_tpu.observability import (Counter, Gauge, Histogram,
                                         MetricsRegistry, get_registry,
                                         quantiles_from_counts)


# ------------------------------------------------------------ histogram


def test_histogram_quantiles_vs_numpy():
    """Log-bucketed estimates must land within one bucket ratio
    (10**(1/buckets_per_decade)) of numpy's exact quantiles — the
    documented accuracy contract — across a lognormal latency-like
    sample."""
    rng = np.random.default_rng(7)
    samples = rng.lognormal(mean=-4.0, sigma=1.2, size=5000)
    h = Histogram("t", buckets_per_decade=10)
    for s in samples:
        h.record(float(s))
    ratio = 10 ** (1 / 10) * 1.0001  # one bucket of slack + fp dust
    for q in (0.1, 0.5, 0.9, 0.99):
        est, true = h.quantile(q), float(np.quantile(samples, q))
        assert true / ratio <= est <= true * ratio, (q, est, true)
    assert h.count == len(samples)
    assert h.sum == pytest.approx(samples.sum(), rel=1e-9)
    assert h.mean == pytest.approx(samples.mean(), rel=1e-9)


def test_histogram_edge_cases():
    h = Histogram("t", lo=1e-3, hi=1e2, buckets_per_decade=2)
    assert h.quantile(0.5) is None  # empty
    h.record(-1.0)            # clamps to 0 → first bucket
    h.record(0.0)
    h.record(1e9)             # overflow bucket
    assert h.count == 3
    assert h.quantile(0.0) == h.edges[0]
    assert h.quantile(1.0) == h.edges[-1]  # overflow reports the last edge


def test_quantiles_from_counts_empty_and_single():
    edges = [1.0, 2.0, 4.0]
    assert quantiles_from_counts(edges, [0, 0, 0, 0], (0.5,)) == [None]
    qs = quantiles_from_counts(edges, [0, 1, 0, 0], (0.0, 0.5, 1.0))
    mid = float(np.sqrt(1.0 * 2.0))  # geometric midpoint of (1, 2]
    assert qs == [1.0, mid, mid]  # q=0 resolves to the underflow edge


# ----------------------------------------------------------- prometheus


def test_prometheus_golden():
    """Exact text-format golden: HELP/TYPE lines, cumulative le buckets
    with +Inf, _sum/_count, counters and gauges, trailing newline."""
    reg = MetricsRegistry()
    reg.counter("ds_reqs_total", "Requests").inc(3)
    reg.gauge("ds_depth", "Queue depth").set(2.5)
    h = reg.histogram("ds_lat_seconds", "Latency", lo=0.1, hi=10.0,
                      buckets_per_decade=1)
    h.record(0.05)   # below lo → first bucket
    h.record(0.5)
    h.record(100.0)  # overflow
    text = reg.render_prometheus()
    assert text == (
        "# HELP ds_depth Queue depth\n"
        "# TYPE ds_depth gauge\n"
        "ds_depth 2.5\n"
        "# HELP ds_lat_seconds Latency\n"
        "# TYPE ds_lat_seconds histogram\n"
        'ds_lat_seconds_bucket{le="0.1"} 1\n'
        'ds_lat_seconds_bucket{le="1"} 2\n'
        'ds_lat_seconds_bucket{le="10"} 2\n'
        'ds_lat_seconds_bucket{le="100"} 3\n'
        'ds_lat_seconds_bucket{le="+Inf"} 3\n'
        "ds_lat_seconds_sum 100.55\n"
        "ds_lat_seconds_count 3\n"
        "# HELP ds_reqs_total Requests\n"
        "# TYPE ds_reqs_total counter\n"
        "ds_reqs_total 3\n")


def test_prometheus_parses_line_by_line():
    """Every non-comment line of a populated registry must be
    ``name{labels} value`` with a float-parseable value."""
    import re
    reg = MetricsRegistry()
    reg.counter("a_total").inc()
    reg.histogram("b_seconds").record(0.25)
    reg.gauge("c").set(-1)
    pat = re.compile(r'^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^}]*\})?\s+\S+$')
    for line in reg.render_prometheus().splitlines():
        if line.startswith("#"):
            continue
        assert pat.match(line), line
        float(line.rsplit(" ", 1)[1])


# ------------------------------------------------------------- registry


def test_registry_get_or_create_and_type_conflict():
    reg = MetricsRegistry()
    c1 = reg.counter("x_total")
    assert reg.counter("x_total") is c1
    with pytest.raises(TypeError):
        reg.gauge("x_total")
    assert isinstance(reg.get("x_total"), Counter)
    assert reg.get("nope") is None
    assert "x_total" in reg.names()


def test_registry_reset_keeps_handles_valid():
    reg = MetricsRegistry()
    c = reg.counter("n_total")
    h = reg.histogram("h_seconds")
    c.inc(5)
    h.record(1.0)
    reg.reset()
    assert c.value == 0 and h.count == 0
    c.inc()  # pre-reset handle still feeds the same registry
    assert reg.get("n_total").value == 1


def test_counter_rejects_negative():
    c = Counter("n_total")
    with pytest.raises(ValueError):
        c.inc(-1)


def test_global_registry_is_a_singleton():
    assert get_registry() is get_registry()


# ------------------------------------------------- bridge + delta views


def test_to_events_shapes():
    reg = MetricsRegistry()
    reg.counter("c_total").inc(2)
    reg.gauge("g").set(7)
    reg.histogram("h_seconds")  # empty → skipped entirely
    h = reg.histogram("lat_seconds")
    for v in (0.1, 0.2, 0.4):
        h.record(v)
    events = reg.to_events(step=42, prefix="serve/")
    d = {name: v for name, v, _ in events}
    assert all(step == 42 for _, _, step in events)
    assert d["serve/c_total"] == 2.0 and d["serve/g"] == 7.0
    assert d["serve/lat_seconds_count"] == 3.0
    assert d["serve/lat_seconds_mean"] == pytest.approx(0.7 / 3)
    assert "serve/lat_seconds_p50" in d and "serve/lat_seconds_p99" in d
    assert not any(n.startswith("serve/h_seconds") for n in d)


