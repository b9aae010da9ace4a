"""Integration tests for the serving observability layer: the compiled
step must be bit-identical (and host-transfer-free) with observability on
or off, the live counters must agree exactly with `ServeStats`, the
tick-loop tracer must cover all five driver phases, and the admin
endpoint must answer every command against a live async pool under load.
"""
import asyncio
import contextlib
import json
import re
import threading
import time

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.analysis import hlo as hlolib
from repro.analysis.cases import lower_pool_chunk
from repro.models import lstm_am
from repro.serving import (
    AsyncSpartusServer,
    BatchedSpartusEngine,
    EngineConfig,
    PoolObservability,
    StreamRequest,
    Tracer,
    serve_requests,
)
from repro.serving.scheduler import (
    RETIRE_BLOCK,
    UPLOAD_BLOCK_FRAMES,
    SessionPool,
)

INPUT_DIM, HIDDEN, CLASSES = 20, 32, 11
GAMMA, M, THETA = 0.75, 4, 0.05
LENS = [5, 9, 3, 12, 1, 7, 8, 2]


def _make_engine():
    cfg = lstm_am.LSTMAMConfig(input_dim=INPUT_DIM, hidden_dim=HIDDEN,
                               n_layers=2, n_classes=CLASSES)
    params = lstm_am.cbtd_prune_stacks(
        lstm_am.init_params(jax.random.key(0), cfg), gamma=GAMMA, m=M)
    ecfg = EngineConfig(theta=THETA, gamma=GAMMA, m=M, capacity_frac=1.0)
    return BatchedSpartusEngine(params, cfg, ecfg)


@pytest.fixture(scope="module")
def engine():
    return _make_engine()


@pytest.fixture(scope="module")
def workload():
    return [np.asarray(
        jax.random.normal(jax.random.key(900 + i), (t, INPUT_DIM)),
        np.float32) for i, t in enumerate(LENS)]


def _requests(feats):
    return [StreamRequest(i, 0, f) for i, f in enumerate(feats)]


# ------------------------------------------- zero-added-host-transfer pin
# The chunk-lowering recipe and the forbidden-token scan live in
# repro.analysis (cases.lower_pool_chunk / hlo.host_transfer_lines): the
# same code the contract checker and `python -m tools.lint --contracts`
# run, so this pin and CI can never drift apart.


def test_compiled_chunk_identical_with_and_without_obs(engine, workload):
    """The boundary-fold rule, pinned at the HLO level: attaching
    observability must not change the compiled scan by one byte — every
    metric source folds host-side at chunk boundaries, never inside the
    step — and the scan itself must contain no host-transfer ops
    (outfeed/infeed/callback), i.e. zero added host syncs per scan
    iteration."""
    hlo_off = lower_pool_chunk(engine, workload, observability=None)
    hlo_on = lower_pool_chunk(engine, workload,
                              observability=PoolObservability())
    assert hlo_on == hlo_off
    hits = hlolib.host_transfer_lines(hlo_on)
    assert hits == [], f"host-transfer ops in compiled chunk: {hits[:5]}"


DEVICE_SCOPES = ("reset", "step_scan", "bank_rows", "delta_encode",
                 "matvec", "gates", "state_update", "telemetry", "head")


def test_device_scopes_are_metadata_only(engine, workload, monkeypatch):
    """The named scopes name the chunk program's work (the ``op_name``
    metadata a profiler trace carries) and change none of its ops: the
    compiled chunk with every scope turned into a no-op differs only in
    metadata."""
    scoped = lower_pool_chunk(engine, workload)
    for name in DEVICE_SCOPES:
        assert f"/{name}/" in scoped, name
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare = lower_pool_chunk(_make_engine(), workload)
    assert "/bank_rows/" not in bare
    assert hlolib.op_histogram(scoped) == hlolib.op_histogram(bare)
    assert hlolib.strip_metadata(scoped) == hlolib.strip_metadata(bare)


def test_telemetry_totals_reduction_is_transfer_free(engine):
    """The one device-side observability signal — the [3] totals the
    boundary fold diffs — must itself lower without host callbacks."""
    txt = engine._tel_totals.lower(engine.init_state(4).telemetry) \
        .compile().as_text()
    assert hlolib.host_transfer_lines(txt) == []


# ----------------------------------------------- counter/ServeStats parity

@pytest.mark.parametrize("cap,chunk,max_steps", [
    (3, 4, None),     # chunked, multiple admission waves
    (2, 2, None),     # chunked, tiny chunks
    (4, 8, None),     # chunked, whole-utterance chunks
    (3, 1, None),     # one-frame chunks
    (2, 4, 6),        # truncated by max_steps mid-run
])
def test_counters_match_servestats(engine, workload, cap, chunk, max_steps):
    """The live counters and `ServeStats` are two views of one run and
    must agree EXACTLY: dispatches, frames, and delivered results split
    by the same `truncated` flag."""
    obs = PoolObservability()
    results, stats = serve_requests(engine, _requests(workload),
                                    capacity=cap, chunk_frames=chunk,
                                    max_steps=max_steps, observability=obs)
    n_trunc = sum(1 for r in results if r.truncated)
    assert obs.c_dispatches.value == stats.n_dispatches
    assert obs.c_frames.value == stats.total_frames
    assert obs.c_completed.value == len(results) - n_trunc
    assert obs.c_truncated.value == n_trunc
    assert obs.c_admissions.value == len(results)
    if max_steps is not None:
        assert stats.truncated and n_trunc > 0
    # one time-series sample per dispatch boundary:
    assert obs.timeseries.n_appended == stats.n_dispatches
    samples = obs.timeseries.snapshot()
    assert sum(s["frames"] for s in samples) == stats.total_frames
    assert sum(s["admissions"] for s in samples) == len(results)
    # retirements land in the boundary that RESOLVED them; results still
    # pending at the final flush() surface outside any dispatch boundary:
    assert sum(s["retirements"] for s in samples) <= len(results)


def test_observability_does_not_change_results(engine, workload):
    """Logits with observability attached are bit-identical to without."""
    res_off, _ = serve_requests(engine, _requests(workload), capacity=3,
                                chunk_frames=4)
    res_on, _ = serve_requests(engine, _requests(workload), capacity=3,
                               chunk_frames=4,
                               observability=PoolObservability())
    for a, b in zip(sorted(res_off, key=lambda r: r.req_id),
                    sorted(res_on, key=lambda r: r.req_id)):
        np.testing.assert_array_equal(a.logits, b.logits)


def test_incremental_sparsity_converges_to_measured(engine, workload):
    """The boundary-diffed running totals telescope to the run's
    cumulative measured sparsity: after `flush_totals` resolves the tail
    window, the accumulated [nnz/cols, overflow, steps] must reproduce
    `stats.sparsity` exactly — and every per-window increment in the
    time series is a valid sparsity with sample weights that sum to at
    most the run total (the tail window resolves after the last
    boundary, outside the ring)."""
    obs = PoolObservability()
    _, stats = serve_requests(engine, _requests(workload), capacity=4,
                              chunk_frames=4, observability=obs)
    tot = obs._last_totals          # flushed by serve_requests
    assert tot[2] > 0
    assert 1.0 - tot[0] / tot[2] == pytest.approx(
        stats.sparsity["temporal_sparsity"], abs=1e-9)
    assert tot[1] / tot[2] == pytest.approx(
        stats.sparsity["capacity_overflow_rate"], abs=1e-9)
    samples = obs.timeseries.snapshot()
    w = np.array([s["samples_inc"] for s in samples])
    sp = np.array([s["temporal_sparsity_inc"] for s in samples])
    assert w.sum() > 0
    assert w.sum() <= tot[2]
    assert ((0.0 <= sp) & (sp <= 1.0)).all()


def test_idle_pool_sparsity_summary(engine):
    """Satellite regression at the pool level: a pool that never stepped
    reports the full zeroed sparsity key set, not {}."""
    from repro.serving.telemetry import measured_sparsity
    state = engine.init_state(4)
    summ = measured_sparsity(state.telemetry, engine.n_cols)
    assert summ == {"temporal_sparsity": 0.0,
                    "capacity_overflow_rate": 0.0,
                    "mean_active_columns": 0.0}


# ------------------------------------------- bench report schema stamping

def _load_bench_module():
    import importlib.util
    import os
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "serving_bench", os.path.join(root, "benchmarks",
                                      "serving_bench.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_bench_writer_stamps_and_refuses_mixed_schemas(tmp_path):
    """BENCH_serving.json carries one schema_version on the report and on
    every row; a row from a different schema refuses to write rather
    than producing a half-old, half-new file."""
    sb = _load_bench_module()
    path = tmp_path / "BENCH.json"
    sb._write_report(str(path), {"leg": {"frames_per_s": 1.0}})
    doc = json.loads(path.read_text())
    assert doc["schema_version"] == sb.SCHEMA_VERSION
    assert doc["leg"]["schema_version"] == sb.SCHEMA_VERSION

    stale_row = {"leg": {"schema_version": sb.SCHEMA_VERSION - 1}}
    with pytest.raises(ValueError, match="refusing to mix"):
        sb._write_report(str(path), stale_row)
    stale_top = {"schema_version": sb.SCHEMA_VERSION + 1}
    with pytest.raises(ValueError, match="refusing to mix"):
        sb._write_report(str(path), stale_top)
    # current-version stamps pass through idempotently:
    sb._write_report(str(path), doc)


# --------------------------------------------- tracer + admin end-to-end

PHASES = {"client_pump", "admission_upload", "dispatch", "retire_snapshot",
          "snapshot_fetch", "fetch_wait", "fetch_copy", "delivery_pump",
          "pacing_idle"}


async def _admin_query(reader, writer, msg):
    writer.write((json.dumps(msg) + "\n").encode())
    await writer.drain()
    return json.loads(await reader.readline())


def test_async_trace_and_admin_endpoint(engine, workload):
    """One live async run, under client load, covering the tentpole's
    operator surface end to end: the tracer records every tick-loop
    phase as loadable Chrome trace JSON, and the admin endpoint answers
    healthz/stats/metrics/timeseries (plus in-band errors) while the
    pool is actively serving."""
    from repro.launch.serve import start_admin_server

    obs = PoolObservability(tracer=Tracer(enabled=True))

    async def client(server, feats):
        handle = await server.stream(want_partials=True)
        for j in range(0, len(feats), 3):
            await handle.send(feats[j:j + 3])
            await asyncio.sleep(0)
        handle.close()
        async for _ in handle:
            pass
        return await handle.result()

    async def run():
        async with AsyncSpartusServer(engine, capacity=3, chunk_frames=4,
                                      observability=obs) as server:
            admin = await start_admin_server(server, obs, port=0)
            port = admin.sockets[0].getsockname()[1]
            tasks = [asyncio.ensure_future(client(server, f))
                     for f in workload[:6]]
            reader, writer = await asyncio.open_connection("127.0.0.1", port)
            # query every command while clients are mid-stream:
            health = await _admin_query(reader, writer, {"cmd": "healthz"})
            stats = await _admin_query(reader, writer, {"cmd": "stats"})
            await _admin_query(reader, writer, {"cmd": "metrics"})
            results = await asyncio.gather(*tasks)
            # re-scrape after the load completes, so counter assertions
            # below see the whole run:
            metrics = await _admin_query(reader, writer, {"cmd": "metrics"})
            ts = await _admin_query(reader, writer,
                                    {"cmd": "timeseries", "last": 4})
            bad = await _admin_query(reader, writer, {"cmd": "nope"})
            not_obj = await _admin_query(reader, writer, [1, 2])
            writer.close()
            admin.close()
            await admin.wait_closed()
            return health, stats, metrics, ts, bad, not_obj, results

    health, stats, metrics, ts, bad, not_obj, results = asyncio.run(run())

    assert health["ok"] is True and health["capacity"] == 3
    assert "n_dispatches" in stats["stats"]
    assert metrics["metrics"]["spartus_dispatches_total"]["value"] > 0
    assert "# TYPE spartus_frames_total counter" in metrics["prometheus"]
    assert len(ts["timeseries"]) <= 4 and ts["n_appended"] > 0
    for s in ts["timeseries"]:
        assert {"chunk", "occupancy", "frames", "dispatch_s",
                "temporal_sparsity_inc", "snapshot_fetch_s", "fetch_rows",
                "upload_frame_slots", "client_pump_s", "delivery_pump_s",
                "pacing_idle_s"} <= set(s)
    assert "error" in bad and "error" in not_obj
    assert len(results) == 6 and all(r.logits.size for r in results)
    # the delivered-result counters agree with what the clients saw:
    assert obs.c_completed.value == 6.0
    # every driver phase traced, and the trace round-trips as JSON:
    doc = json.loads(obs.tracer.to_json())
    names = {e["name"] for e in doc["traceEvents"]}
    assert PHASES <= names, f"missing phases: {PHASES - names}"
    assert all(e["ph"] in ("X", "i") for e in doc["traceEvents"])


# ------------------------------- spans on the profiler clock, boundary counts

def _host_event_names(log_dir):
    import glob
    import os

    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return {e.name for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events}


def test_spans_land_on_the_profiler_clock(engine, workload, tmp_path):
    """With no observability attached (the tracer is NULL_TRACER) the
    pool's and the driver's phases still appear as ``spartus.*`` host
    events of a ``jax.profiler`` trace, on the device trace's clock."""
    async def run():
        async with AsyncSpartusServer(engine, capacity=3,
                                      chunk_frames=4) as server:
            return await asyncio.gather(*(server.submit(f)
                                          for f in workload[:4]))

    jax.profiler.start_trace(str(tmp_path))
    try:
        results = asyncio.run(run())
    finally:
        jax.profiler.stop_trace()
    assert len(results) == 4
    names = _host_event_names(str(tmp_path))
    want = {"spartus.snapshot_fetch", "spartus.fetch_wait",
            "spartus.fetch_copy", "spartus.client_pump",
            "spartus.dispatch", "spartus.delivery_pump"}
    assert want <= names, f"missing: {want - names}"
    assert not any(n.startswith("bench.") for n in names)


@pytest.mark.parametrize("stream_partials", [False, True])
def test_boundary_counts_match_what_crossed(engine, workload,
                                            stream_partials):
    """The boundary samples count what crossed between host and device:
    one admission wave of ``nb x K`` frame slots (nb blocks of K frames)
    holding the real frames, one retirement gather of ``R x T_pad`` rows
    per retiring boundary (R = RETIRE_BLOCK slots, clipped to the
    capacity), and of the fetched rows exactly those delivered (each
    session's rows, and with partials each chunk's rows once more).
    Work resolved after the last dispatch waits in ``obs.boundary``."""
    cap, chunk = 3, 4
    feats = workload[:cap]          # 5, 9, 3 frames: each retires alone
    obs = PoolObservability()
    pool = SessionPool(engine, capacity=cap, max_frames=16,
                       chunk_frames=chunk, stream_partials=stream_partials,
                       observability=obs)
    for i, f in enumerate(feats):
        pool.admit(StreamRequest(i, 0, f), 0)
    results, now = [], 0
    while len(results) < cap:
        done, adv = pool.tick(now)
        results += done
        now += max(adv, 1)
    partial_rows = sum(p.rows.shape[0] for p in pool.take_partials())
    samples = obs.timeseries.snapshot()

    def total(key):
        return sum(s[key] for s in samples) + obs.boundary.get(key, 0)

    t_buf, t_pad = pool._frames.shape[1], pool._out.shape[1]
    n_frames = sum(f.shape[0] for f in feats)
    k = min(UPLOAD_BLOCK_FRAMES, t_buf)     # one block per utterance here
    assert samples[0]["upload_blocks"] == cap
    assert samples[0]["upload_frame_slots"] == 4 * k       # nb = 4 >= 3
    assert samples[0]["upload_frames"] == n_frames
    assert samples[0]["upload_bytes"] == 4 * k * INPUT_DIM * 4
    assert total("upload_frame_slots") == 4 * k
    delivered = sum(r.logits.shape[0] for r in results)
    assert delivered == n_frames
    assert partial_rows == (n_frames if stream_partials else 0)
    assert total("fetch_rows_kept") == delivered + partial_rows
    # scans of 4, 4 and 1 frames (the 9-frame session sets each length);
    # a partials snapshot holds every slot's rows of one scan:
    chunk_rows = cap * (4 + 4 + 1) if stream_partials else 0
    assert total("retire_gathers") == len(results)
    r = min(RETIRE_BLOCK, cap)
    assert total("fetch_rows") == len(results) * r * t_pad + chunk_rows
    assert total("fetch_bytes") == total("fetch_rows") * CLASSES * 4
    # a lone retiree of a 64-slot pool fetches one block of R slots'
    # rows, not the whole 64-slot bank:
    big_obs = PoolObservability()
    big = SessionPool(engine, capacity=64, max_frames=16, chunk_frames=chunk,
                      observability=big_obs)
    big.admit(StreamRequest(0, 0, feats[0]), 0)
    now = 0
    while big.n_active or big.has_pending:
        big.tick(now)
        now += chunk
    big_rows = (sum(s["fetch_rows"] for s in big_obs.timeseries.snapshot())
                + big_obs.boundary.get("fetch_rows", 0))
    assert big_rows == RETIRE_BLOCK * big._out.shape[1]
    for key in ("snapshot_fetch_s", "fetch_wait_s", "fetch_copy_s",
                "retire_snapshot_s", "admission_upload_s"):
        assert total(key) > 0


# ----------------------------------------- scrape-vs-update thread safety

_BUCKET_RE = re.compile(r"^(\w+)_bucket\{(.*)\} (\d+)$")
_COUNT_RE = re.compile(r"^(\w+)_count(?:\{(.*)\})? (\d+)$")


def _assert_prometheus_consistent(text):
    """Every histogram family in one exposition must be self-consistent:
    the +Inf bucket equals ``_count`` and cumulative buckets are
    monotone.  A scrape interleaved with an ``observe`` used to tear
    (buckets, sum and count were read under separate lock
    acquisitions)."""
    inf_buckets, buckets = {}, {}
    for line in text.splitlines():
        m = _BUCKET_RE.match(line)
        if m:
            name, labels, v = m.group(1), m.group(2), int(m.group(3))
            rest = ",".join(p for p in labels.split(",")
                            if not p.startswith('le="'))
            buckets.setdefault((name, rest), []).append(v)
            if 'le="+Inf"' in labels:
                inf_buckets[(name, rest)] = v
            continue
        m = _COUNT_RE.match(line)
        if m:
            key = (m.group(1), m.group(2) or "")
            assert inf_buckets[key] == int(m.group(3)), \
                f"torn scrape: {key} +Inf bucket != count in\n{line}"
    for key, vals in buckets.items():
        assert vals == sorted(vals), f"non-monotone buckets for {key}"
    return len(inf_buckets)


def test_metrics_scrape_consistency_under_hammer():
    """Pure-registry stress: observer threads hammer one histogram (plus
    a counter) while scraper threads render/snapshot concurrently; every
    single scrape must be internally consistent."""
    from repro.serving.metrics import MetricsRegistry

    reg = MetricsRegistry()
    hist = reg.histogram("stress_seconds", "stress", buckets=(0.1, 1.0, 10.0))
    ctr = reg.counter("stress_total", "stress")
    stop = threading.Event()
    errors = []

    def observer():
        i = 0
        while not stop.is_set():
            hist.observe(0.01 * (i % 400))   # spans all buckets + overflow
            ctr.inc()
            i += 1

    def scraper():
        try:
            while not stop.is_set():
                _assert_prometheus_consistent(reg.render_prometheus())
                snap = reg.snapshot()["stress_seconds"]
                cum = [snap["buckets"][k] for k in ("0.1", "1.0", "10.0")]
                assert cum == sorted(cum)
                assert snap["count"] >= cum[-1]
        except AssertionError as e:   # surfaced after join
            errors.append(e)

    threads = ([threading.Thread(target=observer) for _ in range(3)]
               + [threading.Thread(target=scraper) for _ in range(3)])
    for t in threads:
        t.start()
    time.sleep(1.0)
    stop.set()
    for t in threads:
        t.join()
    assert not errors, errors[0]
    # quiescent ground truth: totals survived the concurrency intact
    count, total, cum = hist.stats()
    assert count == hist.count == cum[-1][1]
    assert total == pytest.approx(hist.sum)


def test_metrics_scrape_consistency_against_ticking_pool(engine, workload):
    """End-to-end stress: scrape the live registry while a real pool
    run folds metrics at every chunk boundary."""
    obs = PoolObservability()
    done = threading.Event()
    errors = []

    def scraper():
        n_scrapes = 0
        try:
            while not done.is_set() or n_scrapes == 0:
                _assert_prometheus_consistent(obs.registry.render_prometheus())
                obs.registry.snapshot()
                n_scrapes += 1
        except AssertionError as e:
            errors.append(e)

    threads = [threading.Thread(target=scraper) for _ in range(2)]
    for t in threads:
        t.start()
    try:
        results, _ = serve_requests(engine, _requests(workload), capacity=3,
                                    chunk_frames=4, observability=obs)
    finally:
        done.set()
        for t in threads:
            t.join()
    assert not errors, errors[0]
    assert len(results) == len(workload)
    # a final quiescent scrape sees the full run:
    n_hist = _assert_prometheus_consistent(obs.registry.render_prometheus())
    assert n_hist >= 2      # dispatch_seconds, chunk_seconds, ...
