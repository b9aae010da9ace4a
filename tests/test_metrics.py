"""Unit tests for the observability primitives (repro.serving.metrics):
registry get-or-create semantics, histogram bucket math, Prometheus
exposition, the bounded time-series ring, and the Chrome tracer.

These are pure host-side tests — no engine, no device work."""
import json
import threading

import pytest

from repro.serving.metrics import (
    DEFAULT_TIMESERIES_LEN,
    NULL_TRACER,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    PoolObservability,
    TimeSeries,
    Tracer,
)


# ---------------------------------------------------------------- registry

def test_registry_get_or_create_identity():
    r = MetricsRegistry()
    c1 = r.counter("spartus_x_total", "help one")
    c2 = r.counter("spartus_x_total", "different help, same metric")
    assert c1 is c2
    # distinct labels are distinct metrics:
    c3 = r.counter("spartus_x_total", labels={"shard": "0"})
    assert c3 is not c1


def test_registry_type_conflict_raises():
    r = MetricsRegistry()
    r.counter("spartus_y_total")
    with pytest.raises(ValueError, match="already registered"):
        r.gauge("spartus_y_total")
    with pytest.raises(ValueError, match="already registered"):
        r.histogram("spartus_y_total")


def test_counter_rejects_negative():
    r = MetricsRegistry()
    c = r.counter("c_total")
    c.inc(3)
    c.inc(0)
    with pytest.raises(ValueError, match="negative"):
        c.inc(-1)
    assert c.value == 3.0


def test_gauge_set_and_inc():
    g = MetricsRegistry().gauge("g")
    g.set(2.5)
    g.inc(-0.5)          # gauges may go down
    assert g.value == 2.0


def test_histogram_cumulative_buckets():
    h = MetricsRegistry().histogram("h_seconds", buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.1, 0.5, 5.0, 100.0):
        h.observe(v)
    cum = dict(h.cumulative())
    # le-semantics: 0.1 counts the two observations <= 0.1
    assert cum[0.1] == 2
    assert cum[1.0] == 3
    assert cum[10.0] == 4
    assert cum[float("inf")] == 5
    assert h.count == 5
    assert h.sum == pytest.approx(105.65)


def test_snapshot_shapes():
    r = MetricsRegistry()
    r.counter("a_total").inc(2)
    r.gauge("b").set(7)
    r.histogram("c_seconds", buckets=(1.0,)).observe(0.5)
    snap = r.snapshot()
    assert snap["a_total"] == {"type": "counter", "value": 2.0}
    assert snap["b"] == {"type": "gauge", "value": 7.0}
    assert snap["c_seconds"]["type"] == "histogram"
    assert snap["c_seconds"]["count"] == 1
    # snapshot must be JSON-serializable as-is (admin endpoint contract):
    json.dumps(snap)


def test_render_prometheus_format():
    r = MetricsRegistry()
    r.counter("spartus_frames_total", "frames").inc(42)
    r.gauge("spartus_occupancy").set(3)
    r.gauge("spartus_shard_load", labels={"shard": "1"}).set(2)
    h = r.histogram("spartus_chunk_seconds", buckets=(0.1, 1.0))
    h.observe(0.05)
    text = r.render_prometheus()
    assert "# TYPE spartus_frames_total counter" in text
    assert "spartus_frames_total 42" in text
    assert 'spartus_shard_load{shard="1"} 2' in text
    assert 'spartus_chunk_seconds_bucket{le="0.1"} 1' in text
    assert 'spartus_chunk_seconds_bucket{le="+Inf"} 1' in text
    assert "spartus_chunk_seconds_count 1" in text
    assert text.endswith("\n")


# ------------------------------------------------------------- time series

def test_timeseries_ring_bound_and_drop_count():
    ts = TimeSeries(maxlen=4)
    for i in range(10):
        ts.append({"chunk": i})
    assert len(ts) == 4
    assert ts.n_appended == 10
    assert ts.n_dropped == 6
    assert [s["chunk"] for s in ts.snapshot()] == [6, 7, 8, 9]
    assert [s["chunk"] for s in ts.snapshot(last=2)] == [8, 9]


def test_timeseries_update_last_merges():
    ts = TimeSeries(maxlen=8)
    ts.append({"chunk": 1, "lagging": 0})
    ts.update_last({"lagging": 3, "partial_queue_depth_max": 5})
    (s,) = ts.snapshot()
    assert s["lagging"] == 3
    assert s["partial_queue_depth_max"] == 5
    # snapshot returns copies — mutating them must not touch the ring:
    s["lagging"] = 99
    assert ts.snapshot()[0]["lagging"] == 3


def test_timeseries_update_last_on_empty_is_noop():
    ts = TimeSeries(maxlen=2)
    ts.update_last({"x": 1})
    assert ts.snapshot() == []


def test_timeseries_rejects_zero_len():
    with pytest.raises(ValueError):
        TimeSeries(maxlen=0)


# ------------------------------------------------------------------ tracer

def test_tracer_records_loadable_chrome_json():
    tr = Tracer(enabled=True)
    with tr.span("dispatch"):
        pass
    with tr.span("snapshot_fetch"):
        pass
    doc = json.loads(tr.to_json())
    assert set(doc) == {"traceEvents", "displayTimeUnit"}
    names = {e["name"] for e in doc["traceEvents"]}
    assert names == {"dispatch", "snapshot_fetch"}
    for e in doc["traceEvents"]:
        assert e["ph"] == "X"
        assert e["ts"] >= 0
        assert e["dur"] >= 0


def test_tracer_bounded_events():
    tr = Tracer(enabled=True, max_events=3)
    for i in range(10):
        with tr.span(f"s{i}"):
            pass
    assert tr.n_events == 3
    assert tr.phase_names() == ["s7", "s8", "s9"]


def test_disabled_tracer_records_nothing():
    tr = Tracer(enabled=False)
    with tr.span("dispatch"):
        pass
    assert tr.n_events == 0
    assert NULL_TRACER.n_events == 0
    assert json.loads(NULL_TRACER.to_json())["traceEvents"] == []


def test_span_sums_seconds_into_and_keeps_disabled_ring_empty():
    """A span feeds ``into[<phase>_s]`` whether or not the Chrome ring
    records: the disabled tracer adds seconds and records no event."""
    into = {}
    for tr in (NULL_TRACER, Tracer(enabled=False)):
        for _ in range(2):
            with tr.span("snapshot_fetch", into) as sp:
                pass
            assert sp.t1 >= sp.t0 and sp.seconds >= 0
        assert tr.n_events == 0
    assert set(into) == {"snapshot_fetch_s"}
    assert into["snapshot_fetch_s"] >= 0
    rec = Tracer(enabled=True)
    with rec.span("dispatch", into):
        pass
    assert rec.phase_names() == ["dispatch"]
    assert set(into) == {"snapshot_fetch_s", "dispatch_s"}


def test_tracer_dump(tmp_path):
    tr = Tracer(enabled=True)
    with tr.span("pacing_idle"):
        pass
    path = tmp_path / "trace.json"
    tr.dump(str(path))
    doc = json.loads(path.read_text())
    assert doc["traceEvents"][0]["name"] == "pacing_idle"


# ------------------------------------------------------- PoolObservability

def test_fold_chunk_counters_and_sample():
    obs = PoolObservability(timeseries_len=8)
    s = obs.fold_chunk(occupancy=3, capacity=4, n_active=2,
                       frames_advanced=64, dispatch_s=1e-3, chunk_s=2e-3,
                       host_overlap_frac=0.5, admissions=3, retirements=1,
                       shard_loads=[2, 1])
    assert obs.c_dispatches.value == 1.0
    assert obs.c_frames.value == 64.0
    assert obs.g_occupancy.value == 3.0
    assert obs.g_active_frac.value == pytest.approx(0.5)
    assert s["chunk"] == 1
    assert s["shard_loads"] == [2, 1]
    assert s["temporal_sparsity_inc"] == 0.0      # no totals yet
    snap = obs.registry.snapshot()
    assert snap['spartus_shard_load{shard="0"}']["value"] == 2.0
    assert snap['spartus_shard_load{shard="1"}']["value"] == 1.0


def test_fold_chunk_diffs_totals_one_boundary_later():
    import numpy as np
    obs = PoolObservability()
    # boundary 1 enqueues totals [nnz/cols, overflow, steps] = [5, 0, 10]
    obs.fold_chunk(occupancy=1, capacity=1, n_active=1, frames_advanced=10,
                   dispatch_s=0.0, chunk_s=0.0, host_overlap_frac=0.0,
                   admissions=0, retirements=0,
                   telemetry_totals=np.array([5.0, 0.0, 10.0]))
    # boundary 2 fetches them: window sparsity = 1 - 5/10
    s2 = obs.fold_chunk(occupancy=1, capacity=1, n_active=1,
                        frames_advanced=10, dispatch_s=0.0, chunk_s=0.0,
                        host_overlap_frac=0.0, admissions=0, retirements=0,
                        telemetry_totals=np.array([8.0, 1.0, 20.0]))
    assert s2["temporal_sparsity_inc"] == pytest.approx(0.5)
    assert s2["samples_inc"] == 10.0
    assert obs.g_sparsity.value == pytest.approx(0.5)
    # end of run resolves the second window: (8-5)/(20-10)
    obs.flush_totals()
    assert obs._last_totals[2] == 20.0


def test_fold_results_classifies_truncated():
    class R:
        def __init__(self, truncated):
            self.truncated = truncated

    obs = PoolObservability()
    obs.fold_results([R(False), R(True), R(False)])
    assert obs.c_completed.value == 2.0
    assert obs.c_truncated.value == 1.0


def test_timeseries_drop_counter_wired():
    obs = PoolObservability(timeseries_len=2)
    for _ in range(5):
        obs.fold_chunk(occupancy=1, capacity=1, n_active=1,
                       frames_advanced=1, dispatch_s=0.0, chunk_s=0.0,
                       host_overlap_frac=0.0, admissions=0, retirements=0)
    assert len(obs.timeseries) == 2
    assert obs.c_ts_dropped.value == 3.0


def test_shared_registry_across_bundles():
    r = MetricsRegistry()
    a = PoolObservability(registry=r)
    b = PoolObservability(registry=r)
    a.c_dispatches.inc()
    b.c_dispatches.inc()
    assert r.snapshot()["spartus_dispatches_total"]["value"] == 2.0


def test_default_timeseries_len():
    assert PoolObservability().timeseries.maxlen == DEFAULT_TIMESERIES_LEN


def test_concurrent_folds_are_consistent():
    """The async driver folds from a worker thread while the admin
    endpoint scrapes — hammer both sides and check totals."""
    obs = PoolObservability(timeseries_len=64)
    N, T = 200, 4

    def fold():
        for _ in range(N):
            obs.fold_chunk(occupancy=1, capacity=2, n_active=1,
                           frames_advanced=2, dispatch_s=1e-4, chunk_s=2e-4,
                           host_overlap_frac=0.1, admissions=0,
                           retirements=0)

    threads = [threading.Thread(target=fold) for _ in range(T)]
    for t in threads:
        t.start()
    for _ in range(50):
        obs.registry.snapshot()
        obs.registry.render_prometheus()
        obs.timeseries.snapshot(last=8)
    for t in threads:
        t.join()
    assert obs.c_dispatches.value == N * T
    assert obs.c_frames.value == 2 * N * T
    assert obs.timeseries.n_appended == N * T
