"""Load-generator benchmark for the continuous-batching serving subsystem.

Compares aggregate throughput (frames/s) and per-request latency (p50/p95)
of the `SessionPool` scheduler at several batch capacities against the
baseline of running the same requests *sequentially* through the batch-1
`SpartusEngine`, and verifies that the pooled per-request logits are
identical (atol 1e-5) to the batch-1 engine's.

    PYTHONPATH=src python benchmarks/serving_bench.py
    PYTHONPATH=src python benchmarks/serving_bench.py --chunk-frames 32
    PYTHONPATH=src python benchmarks/serving_bench.py --async-load  # open-loop
        Poisson-arrival load generator against the asyncio front-end:
        latency (p50/p95/p99), time-to-first-logit and queue wait vs
        offered load, plus sustained throughput vs the synchronous
        chunked pool at the same chunk size
    PYTHONPATH=src python benchmarks/serving_bench.py --check   # CI gate:
        fail unless capacity-16 aggregate frames/s >= 4x sequential
    PYTHONPATH=src python benchmarks/serving_bench.py --sweep   # slow CI gate:
        hidden in {128, 512} at m=16 / capacity 16 plus a forced-scatter
        leg, emits BENCH_serving.json, fails if the pool ever drops below
        the batch-1 engine (the crossover that regressed before the
        scatter/dense-gather SpMV paths); also runs the chunked tick loop
        at chunk_frames in {1, 8, 32} vs the per-frame pool at hidden=128
        and fails if chunk_frames=32 is slower than per-frame (the
        dispatch-amortisation gate), and the async open-loop leg, failing
        if the async front-end's sustained (saturated) throughput drops
        below ASYNC_FLOOR x the synchronous chunked pool, and the
        quantized leg (int8 weights + Q8.8 delta thresholds): parity vs
        the quantized batch-1 engine, max-abs logit divergence vs the
        fp32 pool under QUANT_DIVERGENCE_BOUND, and the 4x int8 weight-
        payload shrink (QUANT_PAYLOAD_FLOOR)
    PYTHONPATH=src python benchmarks/serving_bench.py --quant  # that
        quantized leg alone, at the CLI's model config

Runs on CPU: the batch-1 engine pays ~8 XLA dispatches + 3 host syncs per
(frame, layer) while the pool amortises one dispatch + one logits fetch
across all slots per tick — the speedup below is that dispatch economy,
before any accelerator parallelism.  The chunked tick loop compounds it:
one lax.scan dispatch advances all slots up to C frames and logits leave
the device once per session (at retirement), so the per-tick Python /
dispatch / fetch overhead is amortised C-fold on top.
"""
from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys
import time
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import use_compile_cache
from repro.models import lstm_am
from repro.serving import (
    AsyncSpartusServer, BatchedSpartusEngine, EngineConfig,
    PoolObservability, SpartusEngine, StreamRequest, serve_requests,
)

#: BENCH_serving.json schema version.  Stamped on the report and on every
#: top-level row by `_write_report`, which refuses to mix versions —
#: downstream consumers (CI artifact diffing, dashboards) can trust that
#: one file means one schema.  v2 added the observability rows
#: (`obs_overhead`) and the per-row stamp itself.  v3 added the quantized
#: leg (`quant_*` rows: divergence vs fp32, weight-payload ratio,
#: bytes-per-slot and the equal-bytes capacity) and `bytes_per_slot`
#: inside every ServeStats dict.
SCHEMA_VERSION = 3


def _write_report(path: str, report: dict) -> None:
    """Stamp the schema version on the report and every row, then write.

    Refuses to *silently mix* schemas: a row already carrying a different
    ``schema_version`` (say, merged in from an older BENCH_serving.json)
    raises instead of producing a file that is half old shape, half new."""
    stamped = {}
    top = report.get("schema_version", SCHEMA_VERSION)
    if top != SCHEMA_VERSION:
        raise ValueError(
            f"refusing to mix schemas: report carries schema_version={top}, "
            f"writer is {SCHEMA_VERSION}")
    for key, row in report.items():
        if isinstance(row, dict):
            v = row.get("schema_version", SCHEMA_VERSION)
            if v != SCHEMA_VERSION:
                raise ValueError(
                    f"refusing to mix schemas: row {key!r} carries "
                    f"schema_version={v}, writer is {SCHEMA_VERSION}")
            row = dict(row, schema_version=SCHEMA_VERSION)
        stamped[key] = row
    stamped["schema_version"] = SCHEMA_VERSION
    with open(path, "w") as f:
        json.dump(stamped, f, indent=2)
    print(f"[bench] wrote {path} (schema v{SCHEMA_VERSION})")


def build_model(hidden: int, n_layers: int, input_dim: int, n_classes: int,
                gamma: float, m: int, seed: int = 0):
    cfg = lstm_am.LSTMAMConfig(input_dim=input_dim, hidden_dim=hidden,
                               n_layers=n_layers, n_classes=n_classes)
    params = lstm_am.init_params(jax.random.key(seed), cfg)
    return lstm_am.cbtd_prune_stacks(params, gamma=gamma, m=m), cfg


def make_requests(n: int, frames: int, input_dim: int,
                  arrival_stride: int = 0) -> List[StreamRequest]:
    return [
        StreamRequest(
            req_id=i, arrival_step=i * arrival_stride,
            feats=np.asarray(
                jax.random.normal(jax.random.key(100 + i), (frames, input_dim)),
                np.float32))
        for i in range(n)
    ]


def bench_config(hidden: int, layers: int, input_dim: int, classes: int,
                 frames: int, n_requests: int, caps: List[int], theta: float,
                 gamma: float, m: int, capacity_frac: float,
                 spmv_path: str = "auto", chunk_frames: int = 0):
    """One model configuration: sequential batch-1 baseline + the pool at
    each capacity, with per-request logits parity checked against the
    batch-1 engine.  Returns (report dict, parity_ok)."""
    params, cfg = build_model(hidden, layers, input_dim, classes, gamma, m)
    ecfg = EngineConfig(theta=theta, gamma=gamma, m=m,
                        capacity_frac=capacity_frac, spmv_path=spmv_path)
    e1 = SpartusEngine(params, cfg, ecfg)
    eb = BatchedSpartusEngine(params, cfg, ecfg)
    reqs = make_requests(n_requests, frames, input_dim)
    total_frames = n_requests * frames

    # -- sequential batch-1 baseline ----------------------------------------
    warm = jnp.asarray(reqs[0].feats[:2])
    e1.run_utterance(warm)  # compile
    e1.telemetry.clear()
    t0 = time.perf_counter()
    seq_logits = [np.asarray(e1.run_utterance(jnp.asarray(r.feats)))
                  for r in reqs]
    t_seq = time.perf_counter() - t0
    seq_fps = total_frames / t_seq
    report = {"hidden": hidden, "m": m, "spmv_path": spmv_path,
              "chunk_frames": chunk_frames,
              "sequential": {"frames_per_s": seq_fps, "wall_s": t_seq}}
    print(f"[bench] hidden={hidden} ({spmv_path}) sequential batch-1: "
          f"{n_requests} x {frames} frames in {t_seq:.2f}s -> "
          f"{seq_fps:.0f} frames/s")

    # -- pooled, per capacity ------------------------------------------------
    parity_ok = True
    for cap in caps:
        # warm-up compiles the step for this capacity outside the timing;
        # full-length feats so the warm-up hits the same frame-buffer bucket
        # as the timed run (a [:2] slice would bucket differently past 64
        # frames and hide a recompile inside the timing), and a full
        # admission wave so the batched-upload variant is compiled too:
        serve_requests(eb, [StreamRequest(i, 0, reqs[0].feats)
                            for i in range(cap)], cap,
                       chunk_frames=chunk_frames)
        results, stats = serve_requests(eb, reqs, capacity=cap,
                                        chunk_frames=chunk_frames)
        for r in results:
            if not np.allclose(r.logits, seq_logits[r.req_id], atol=1e-5):
                parity_ok = False
                print(f"[bench] PARITY FAIL req {r.req_id} at capacity {cap}")
        speedup = stats.frames_per_s / seq_fps
        report[f"capacity_{cap}"] = dict(stats.to_dict(), speedup=speedup)
        print(f"[bench] capacity {cap:3d}: {stats.frames_per_s:8.0f} frames/s "
              f"({speedup:4.1f}x)  p50 {stats.p50_latency_s*1e3:7.1f} ms  "
              f"p95 {stats.p95_latency_s*1e3:7.1f} ms")
    return report, parity_ok


def bench_chunked(hidden: int, layers: int, input_dim: int, classes: int,
                  frames: int, n_requests: int, cap: int, theta: float,
                  gamma: float, m: int, capacity_frac: float,
                  chunk_grid=(1, 8, 32)):
    """Chunked tick loop vs the per-frame pool at one capacity: same
    requests, logits parity pinned against the per-frame results, speedup
    and dispatch amortisation reported per chunk_frames.  Returns
    (report dict, parity_ok)."""
    params, cfg = build_model(hidden, layers, input_dim, classes, gamma, m)
    ecfg = EngineConfig(theta=theta, gamma=gamma, m=m,
                        capacity_frac=capacity_frac)
    eb = BatchedSpartusEngine(params, cfg, ecfg)
    reqs = make_requests(n_requests, frames, input_dim)

    def warm(chunk):
        # full admission wave at full length: compiles the step, the
        # batched upload and the retirement snapshot for the timed shapes
        serve_requests(eb, [StreamRequest(i, 0, reqs[0].feats)
                            for i in range(cap)], cap, chunk_frames=chunk)

    warm(0)
    base_results, base = serve_requests(eb, reqs, capacity=cap)
    report = {"hidden": hidden, "m": m, "capacity": cap,
              "per_frame": base.to_dict()}
    print(f"[bench] hidden={hidden} capacity={cap} per-frame pool: "
          f"{base.frames_per_s:8.0f} frames/s  "
          f"({base.dispatches_per_frame:.3f} dispatches/frame)")

    parity_ok = True
    for chunk in chunk_grid:
        warm(chunk)
        results, stats = serve_requests(eb, reqs, capacity=cap,
                                        chunk_frames=chunk)
        for r in results:
            if not np.allclose(r.logits, base_results[r.req_id].logits,
                               atol=1e-5):
                parity_ok = False
                print(f"[bench] PARITY FAIL req {r.req_id} at "
                      f"chunk_frames {chunk}")
        speedup = stats.frames_per_s / base.frames_per_s
        report[f"chunk_{chunk}"] = dict(stats.to_dict(),
                                        speedup_vs_per_frame=speedup)
        print(f"[bench] chunk_frames {chunk:3d}: {stats.frames_per_s:8.0f} "
              f"frames/s ({speedup:4.1f}x per-frame)  "
              f"{stats.dispatches_per_frame:.3f} dispatches/frame  "
              f"host overlap {stats.host_overlap_frac:.0%}")
    return report, parity_ok


def bench_obs_overhead(hidden: int, layers: int, input_dim: int,
                       classes: int, frames: int, n_requests: int, cap: int,
                       theta: float, gamma: float, m: int,
                       capacity_frac: float, chunk: int, repeats: int = 5):
    """Observability-overhead leg: the same chunked workload with live
    metrics + time-series folding enabled vs fully disabled.

    The fold happens at chunk boundaries only, on host values the pool
    already computed, so the expected cost is a few dict/lock operations
    per boundary — the gate (enabled >= OBS_FLOOR x disabled) pins that
    the observability layer never grows a hot-path cost.

    A sub-3% effect needs signal discipline on a shared runner whose
    speed drifts ~10% over seconds: the workload is floored (each timed
    run covers at least OBS_MIN_FRAMES total frames, whatever
    --frames/--requests say), the two sides run INTERLEAVED off/on
    pairs so each pair shares one drift regime, and the gate takes the
    BEST pair ratio — a systematic observability cost slows the on-side
    of every pair, while drift hits pairs at random, so max-over-pairs
    rejects the former and forgives the latter.  Returns
    (report dict with an ``obs_overhead`` shape, gate_ok)."""
    frames = max(frames, OBS_MIN_FRAMES // max(n_requests, 1), 1)
    if n_requests * frames < OBS_MIN_FRAMES:
        n_requests = -(-OBS_MIN_FRAMES // frames)
    params, cfg = build_model(hidden, layers, input_dim, classes, gamma, m)
    ecfg = EngineConfig(theta=theta, gamma=gamma, m=m,
                        capacity_frac=capacity_frac)
    eb = BatchedSpartusEngine(params, cfg, ecfg)
    reqs = make_requests(n_requests, frames, input_dim)
    # warm: compiles the step/upload/snapshot AND the telemetry-totals
    # reduction the enabled side dispatches per boundary
    serve_requests(eb, [StreamRequest(i, 0, reqs[0].feats)
                        for i in range(cap)], cap, chunk_frames=chunk,
                   observability=PoolObservability())

    off = on = obs = None
    pair_ratios = []
    for _ in range(repeats):
        _, s_off = serve_requests(eb, reqs, capacity=cap, chunk_frames=chunk)
        if off is None or s_off.frames_per_s > off.frames_per_s:
            off = s_off
        o = PoolObservability()
        _, s_on = serve_requests(eb, reqs, capacity=cap, chunk_frames=chunk,
                                 observability=o)
        if on is None or s_on.frames_per_s > on.frames_per_s:
            on, obs = s_on, o
        if s_off.frames_per_s:
            pair_ratios.append(s_on.frames_per_s / s_off.frames_per_s)
    ratio = max(pair_ratios) if pair_ratios else 0.0
    snap = obs.registry.snapshot()
    row = {
        "hidden": hidden, "m": m, "capacity": cap, "chunk_frames": chunk,
        "repeats": repeats, "n_requests": n_requests, "frames": frames,
        "disabled_frames_per_s": off.frames_per_s,
        "enabled_frames_per_s": on.frames_per_s,
        "pair_ratios": [round(r, 4) for r in pair_ratios],
        "ratio": ratio,
        "n_timeseries_samples": len(obs.timeseries),
        "dispatches_counted": snap["spartus_dispatches_total"]["value"],
        "frames_counted": snap["spartus_frames_total"]["value"],
    }
    ok = ratio >= OBS_FLOOR
    print(f"[bench] obs overhead hidden={hidden} chunk={chunk} "
          f"({n_requests}x{frames} frames, {repeats} interleaved pairs): "
          f"enabled {on.frames_per_s:8.0f} / disabled "
          f"{off.frames_per_s:8.0f} frames/s, best pair ratio "
          f"{ratio:.3f}x (floor {OBS_FLOOR}) -> {'PASS' if ok else 'FAIL'}")
    return row, ok


def bench_async_load(hidden: int, layers: int, input_dim: int, classes: int,
                     frames: int, n_requests: int, cap: int, theta: float,
                     gamma: float, m: int, capacity_frac: float, chunk: int,
                     loads=(0.5, 1.0, 2.0)):
    """Open-loop Poisson-arrival load generator against the asyncio
    front-end (`AsyncSpartusServer`), at offered loads relative to the
    synchronous chunked pool's saturated throughput.

    Open-loop means arrivals are scheduled by the wall clock, independent
    of completions (the admission queue is unbounded), so past saturation
    the latency percentiles grow while sustained throughput plateaus at
    the server's capacity — the classic latency-vs-offered-load curve.
    Each load row records achieved frames/s, p50/p95/p99 latency,
    time-to-first-logit and queue wait.

    The ``saturated`` row is the curve's limit point — every arrival at
    t=0 — which is exactly the workload the synchronous chunked drain
    loop (`serve_requests`) measures, so the report's
    ``throughput_ratio`` = saturated async frames/s / sync chunked
    frames/s isolates the front-end's event-loop overhead (~0.9x on a
    2-core CPU box; the finite-load rows are additionally depressed by
    chunk under-fill while staggered sessions wait for boundaries, which
    is a property of chunked scheduling itself, not of the async front
    end).  Per-request logits are parity-checked against the synchronous
    results at every load.  Returns (report dict, parity_ok)."""
    params, cfg = build_model(hidden, layers, input_dim, classes, gamma, m)
    ecfg = EngineConfig(theta=theta, gamma=gamma, m=m,
                        capacity_frac=capacity_frac)
    eb = BatchedSpartusEngine(params, cfg, ecfg)
    reqs = make_requests(n_requests, frames, input_dim)
    total_frames = n_requests * frames

    # -- synchronous chunked baseline (same chunk size) ----------------------
    serve_requests(eb, [StreamRequest(i, 0, reqs[0].feats)
                        for i in range(cap)], cap, chunk_frames=chunk)  # warm
    base_results, base = serve_requests(eb, reqs, capacity=cap,
                                        chunk_frames=chunk)
    sync_fps = base.frames_per_s
    print(f"[bench] hidden={hidden} capacity={cap} chunk={chunk} sync "
          f"chunked pool: {sync_fps:8.0f} frames/s")

    async def run_async(arrivals):
        async with AsyncSpartusServer(
                eb, cap, chunk_frames=chunk, max_frames=frames,
                offload_ticks=False) as srv:
            t0 = time.perf_counter()

            async def client(i):
                await asyncio.sleep(arrivals[i])
                return await srv.submit(reqs[i].feats)

            results = await asyncio.gather(
                *[client(i) for i in range(n_requests)])
            wall = time.perf_counter() - t0
        return results, wall

    # warm the async-only shapes outside the timed runs.  One all-at-once
    # pass is NOT enough: staggered arrivals hit small pow2 admission-wave
    # upload buckets that an aligned pass never compiles (a stray ~100 ms
    # compile mid-leg wrecks a 100 ms measurement), so every bucket is
    # compiled deterministically here, and each load leg below also runs
    # once unmeasured with the SAME arrival schedule before its timed pass.
    from repro.serving import SessionPool
    wpool = SessionPool(eb, cap, max_frames=frames, chunk_frames=chunk)
    rid = 0
    r = 1
    while r <= cap:
        for _ in range(r):
            wpool.admit(StreamRequest(10 ** 6 + rid, 0, reqs[0].feats), 0)
            rid += 1
        wpool.step_chunk(now=0)
        wpool.drain(now=0)
        r *= 2
    asyncio.run(run_async([0.0] * n_requests))

    report = {"hidden": hidden, "m": m, "capacity": cap,
              "chunk_frames": chunk, "n_requests": n_requests,
              "frames_per_request": frames,
              "sync_chunked": base.to_dict()}
    parity_ok = True

    def one_leg(label, mult, arrivals):
        nonlocal parity_ok
        asyncio.run(run_async(arrivals))            # compile-warm pass
        results, wall = asyncio.run(run_async(arrivals))
        for rr in results:
            if not np.allclose(rr.logits, base_results[rr.req_id].logits,
                               atol=1e-5):
                parity_ok = False
                print(f"[bench] ASYNC PARITY FAIL req {rr.req_id} at "
                      f"load {label}")
        achieved = total_frames / wall
        lat = np.array([rr.wall_latency_s for rr in results])
        ttfl = np.array([rr.ttfl_s for rr in results])
        qw = np.array([rr.queue_wait_s for rr in results])
        row = {
            "offered_x": mult,
            "offered_frames_per_s": (mult * sync_fps
                                     if np.isfinite(mult) else None),
            "achieved_frames_per_s": achieved,
            "p50_latency_s": float(np.percentile(lat, 50)),
            "p95_latency_s": float(np.percentile(lat, 95)),
            "p99_latency_s": float(np.percentile(lat, 99)),
            "p50_ttfl_s": float(np.percentile(ttfl, 50)),
            "p95_queue_wait_s": float(np.percentile(qw, 95)),
        }
        print(f"[bench] async load {label:>5}: {achieved:8.0f} frames/s  "
              f"p50 {row['p50_latency_s']*1e3:7.1f} ms  "
              f"p99 {row['p99_latency_s']*1e3:7.1f} ms  "
              f"queue p95 {row['p95_queue_wait_s']*1e3:7.1f} ms")
        return row

    rng = np.random.default_rng(0)
    for mult in loads:
        # Poisson process: exponential inter-arrival gaps at a mean rate
        # of offered_fps / frames utterances per second.
        gaps = rng.exponential(frames / (mult * sync_fps), n_requests)
        arrivals = np.cumsum(gaps) - gaps[0]
        report[f"load_{mult}"] = one_leg(f"{mult:.1f}x", mult,
                                         list(arrivals))
    sat = one_leg("sat", float("inf"), [0.0] * n_requests)
    report["saturated"] = sat
    sustained = sat["achieved_frames_per_s"]
    report["sustained_frames_per_s"] = sustained
    report["throughput_ratio"] = sustained / sync_fps if sync_fps else 0.0
    print(f"[bench] async saturated throughput: {sustained:.0f} frames/s = "
          f"{report['throughput_ratio']:.2f}x the sync chunked pool")
    return report, parity_ok


def bench_sharded(layers: int, input_dim: int, classes: int, frames: int,
                  theta: float, gamma: float, capacity_frac: float,
                  hidden: int, cap: int, chunk: int, grid=(1, 2, 4)):
    """Slot-sharded pool scaling: the same request burst through
    ``SessionPool(n_devices=n)`` for each n in ``grid``, logits pinned
    against the shard_1 run at 1e-5, frames/s per row.

    Runs on emulated host devices
    (``XLA_FLAGS=--xla_force_host_platform_device_count=8``): the SPMD
    partitioning, placement and admission paths are identical to real
    multi-device, and because the sharded steady state contains zero
    cross-device communication, per-device wall time shrinks with the
    shard count — bounded by physical cores, since the emulated devices
    share them.  Rows a machine cannot host (n > visible devices) are
    recorded as skipped.  Returns (report dict, parity_ok,
    shard4_speedup or None)."""
    params, cfg = build_model(hidden, layers, input_dim, classes, gamma, m=16)
    ecfg = EngineConfig(theta=theta, gamma=gamma, m=16,
                        capacity_frac=capacity_frac)
    eb = BatchedSpartusEngine(params, cfg, ecfg)
    reqs = make_requests(cap, frames, input_dim)   # one request per slot
    report = {"hidden": hidden, "m": 16, "capacity": cap,
              "chunk_frames": chunk, "n_cpus": os.cpu_count(),
              "n_devices_visible": jax.device_count()}
    parity_ok = True
    base_results = None
    fps = {}
    for n_dev in grid:
        if n_dev > jax.device_count():
            print(f"[bench] shard_{n_dev}: skipped "
                  f"({jax.device_count()} device(s) visible; set XLA_FLAGS="
                  f"--xla_force_host_platform_device_count=8)")
            report[f"shard_{n_dev}"] = {
                "skipped": f"needs {n_dev} devices, "
                           f"{jax.device_count()} visible"}
            continue
        # warm: compiles the sharded step/upload/snapshot for this mesh
        serve_requests(eb, [StreamRequest(i, 0, reqs[0].feats)
                            for i in range(cap)], cap, chunk_frames=chunk,
                       n_devices=n_dev)
        results, stats = serve_requests(eb, reqs, capacity=cap,
                                        chunk_frames=chunk, n_devices=n_dev)
        if base_results is None:
            base_results = results
        for r in results:
            if not np.allclose(r.logits, base_results[r.req_id].logits,
                               atol=1e-5):
                parity_ok = False
                print(f"[bench] SHARD PARITY FAIL req {r.req_id} at "
                      f"n_devices {n_dev}")
        fps[n_dev] = stats.frames_per_s
        speedup = stats.frames_per_s / fps[min(fps)]
        report[f"shard_{n_dev}"] = dict(stats.to_dict(), n_devices=n_dev,
                                        speedup_vs_shard_1=speedup)
        print(f"[bench] shard_{n_dev}: {stats.frames_per_s:8.0f} frames/s "
              f"({speedup:4.2f}x shard_1)")
    shard4 = (fps[4] / fps[1]) if (1 in fps and 4 in fps) else None
    report["shard4_speedup"] = shard4
    return report, parity_ok, shard4


def bench_quant(hidden: int, layers: int, input_dim: int, classes: int,
                frames: int, n_requests: int, cap: int, theta: float,
                gamma: float, m: int, capacity_frac: float, chunk: int):
    """Quantized-serving leg: the same pooled workload with int8 CBCSC
    weight payloads + Q8.8 delta thresholds vs the fp32 pool.

    Three gates ride on one pair of runs (docs/quantization.md):

    - **parity**: the quantized pool must match the quantized batch-1
      engine (the scale-epilogue dequant is the same arithmetic in both,
      so pooling may not perturb quantized logits any more than fp32);
    - **divergence**: max-abs logit difference between the quantized and
      fp32 pools stays under ``QUANT_DIVERGENCE_BOUND`` — the only
      quant-mode divergence source is the Q8.8 activation snap in the
      delta threshold (measured ~5e-4 at this config; the bound leaves
      two orders of headroom so model-seed drift cannot flake CI);
    - **memory**: the int8 weight *payload* (CBCSC values + 8-bit LIDX +
      the dense mirrors) must shrink by at least ``QUANT_PAYLOAD_FLOOR``
      (exactly 4.0x by construction; total weight bytes shrink less
      because the fp32 head, biases and valid masks do not quantize).

    The report also prices the saving as capacity: ``equal_bytes_
    capacity`` is the slot count the quantized pool could host in the
    fp32 pool's device-byte budget (weight saving divided by the
    per-slot state cost).  Returns (report dict, gate_ok)."""
    from repro.core.quantization import QuantConfig

    params, cfg = build_model(hidden, layers, input_dim, classes, gamma, m)
    ecfg_f = EngineConfig(theta=theta, gamma=gamma, m=m,
                          capacity_frac=capacity_frac)
    ecfg_q = EngineConfig(theta=theta, gamma=gamma, m=m,
                          capacity_frac=capacity_frac, quant=QuantConfig())
    eb_f = BatchedSpartusEngine(params, cfg, ecfg_f)
    eb_q = BatchedSpartusEngine(params, cfg, ecfg_q)
    e1_q = SpartusEngine(params, cfg, ecfg_q)
    reqs = make_requests(n_requests, frames, input_dim)

    for eb in (eb_f, eb_q):     # warm: full admission wave, full length
        serve_requests(eb, [StreamRequest(i, 0, reqs[0].feats)
                            for i in range(cap)], cap, chunk_frames=chunk)
    f_results, f_stats = serve_requests(eb_f, reqs, capacity=cap,
                                        chunk_frames=chunk)
    q_results, q_stats = serve_requests(eb_q, reqs, capacity=cap,
                                        chunk_frames=chunk)

    # parity: quantized pool vs the quantized batch-1 oracle
    e1_q.run_utterance(jnp.asarray(reqs[0].feats[:2]))  # compile
    parity_ok = True
    for r in q_results:
        ref = np.asarray(e1_q.run_utterance(jnp.asarray(reqs[r.req_id].feats)))
        if not np.allclose(r.logits, ref, atol=1e-5):
            parity_ok = False
            print(f"[bench] QUANT PARITY FAIL req {r.req_id}")

    # divergence: quantized pool vs the fp32 pool, same requests
    f_by_id = {r.req_id: r for r in f_results}
    divergence = max(
        float(np.max(np.abs(np.asarray(r.logits, np.float32)
                            - np.asarray(f_by_id[r.req_id].logits,
                                         np.float32))))
        for r in q_results)

    w_f, w_q = eb_f.weight_bytes(), eb_q.weight_bytes()
    p_f, p_q = eb_f.weight_payload_bytes(), eb_q.weight_payload_bytes()
    payload_ratio = p_f / p_q if p_q else 0.0
    total_ratio = w_f / w_q if w_q else 0.0
    # price the weight saving as extra capacity at the fp32 byte budget:
    state_per_slot = q_stats.bytes_per_slot - w_q / cap
    equal_bytes_cap = (int(cap + (w_f - w_q) / state_per_slot)
                       if state_per_slot > 0 else cap)

    row = {
        "hidden": hidden, "m": m, "capacity": cap, "chunk_frames": chunk,
        "fp32_frames_per_s": f_stats.frames_per_s,
        "quant_frames_per_s": q_stats.frames_per_s,
        "fp32_bytes_per_slot": f_stats.bytes_per_slot,
        "quant_bytes_per_slot": q_stats.bytes_per_slot,
        "fp32_weight_bytes": w_f, "quant_weight_bytes": w_q,
        "fp32_weight_payload_bytes": p_f, "quant_weight_payload_bytes": p_q,
        "weight_payload_ratio": payload_ratio,
        "weight_total_ratio": total_ratio,
        "equal_bytes_capacity": equal_bytes_cap,
        "max_abs_logit_divergence": divergence,
        "divergence_bound": QUANT_DIVERGENCE_BOUND,
    }
    diverged = divergence > QUANT_DIVERGENCE_BOUND
    shrunk = payload_ratio >= QUANT_PAYLOAD_FLOOR
    ok = parity_ok and not diverged and shrunk
    print(f"[bench] quant hidden={hidden} cap={cap} chunk={chunk}: "
          f"{q_stats.frames_per_s:8.0f} frames/s "
          f"(fp32 {f_stats.frames_per_s:8.0f}), divergence "
          f"{divergence:.2e} (bound {QUANT_DIVERGENCE_BOUND}), payload "
          f"{payload_ratio:.2f}x / total {total_ratio:.2f}x smaller, "
          f"slot {q_stats.bytes_per_slot/1e3:.0f} kB vs "
          f"{f_stats.bytes_per_slot/1e3:.0f} kB "
          f"(equal-bytes capacity {equal_bytes_cap}) -> "
          f"{'PASS' if ok else 'FAIL'}")
    return row, ok


# sweep legs: (hidden, spmv_path).  The auto legs pin the dense-mirror route
# (every gated config has S*(1-gamma) >= 1); the forced-scatter leg pins the
# scatter kernels, which auto would otherwise never exercise here.
SWEEP_LEGS = ((128, "auto"), (512, "auto"), (128, "scatter"))
SWEEP_CAP = 16
# chunked-vs-per-frame leg: hidden for the chunked tick-loop gate and the
# chunk_frames grid recorded in BENCH_serving.json.  The gate requires the
# largest chunk to be at least as fast as the per-frame pool; measured CPU
# speedup at hidden=128 / capacity 16 is >= 3x (dispatch amortisation).
SWEEP_CHUNK_HIDDEN = 128
SWEEP_CHUNK_GRID = (1, 8, 32)
# async open-loop leg: offered-load multipliers (x the sync chunked pool's
# throughput) for the latency-vs-load rows, and the CI floor on the
# saturated-throughput ratio.  The async front-end runs the identical
# chunked dispatch loop, so the saturated ratio measures pure event-loop
# overhead (client-task wakeups, admission pumping between chunks):
# ~0.85-0.9x at hidden=128 / ~10 ms chunks on a 2-core CPU box, and
# closer to 1x as per-chunk device time grows.  The floor is set low
# enough that shared-runner noise cannot flake the job:
ASYNC_LOADS = (0.5, 1.0, 2.0)
# raised 0.75 -> 0.85 with the batched-wakeup driver (dirty-set pump, one
# delivery pass per boundary, no per-send event-loop pokes): measured
# 0.93-1.0x on the 2-core dev box at hidden=128 / 32-frame chunks.
ASYNC_FLOOR = 0.85
# sharded leg: slot-dimension data parallelism at the big-model config
# (hidden=512, a 64-slot pool, 32-frame chunks), shard_{1,2,4} rows.  The
# scaling gate — shard_4 >= SHARD_FLOOR x shard_1 — is enforced on the
# emulated-device CI run (the multi-device job), where >= 4 cores back
# the 4 emulated devices; on smaller hosts the rows are still written
# but the gate only warns, since emulated devices cannot scale past the
# physical core count.
SHARD_HIDDEN = 512
SHARD_CAP = 64
SHARD_CHUNK = 32
SHARD_GRID = (1, 2, 4)
SHARD_FLOOR = 2.0
SHARD_MIN_CPUS = 4
# observability-overhead leg: live metrics + time-series folding may cost
# at most 3% of chunked throughput (measured ~30-40us per chunk boundary:
# the fold is a few dict/lock ops, and the incremental-sparsity totals
# ride the existing one-boundary-later fetch cadence).  Shared-runner
# noise swamps a sub-3% effect on short runs, so the leg floors the
# workload (OBS_MIN_FRAMES total frames per timed run) and interleaves
# best-of-N off/on pairs:
OBS_FLOOR = 0.97
OBS_MIN_FRAMES = 16384
# quantized leg: max-abs logit divergence of the int8/Q8.8 pool vs the
# fp32 pool (sole source: the Q8.8 activation snap in the delta
# threshold; measured ~5e-4 at hidden=128 / m=16 / gamma=0.9375 — the
# bound leaves ~100x headroom), and the floor on the weight-payload
# shrink (CBCSC values + LIDX + dense mirrors quantize 4.0x exactly;
# 3.5 tolerates any future payload bookkeeping change):
QUANT_DIVERGENCE_BOUND = 0.05
QUANT_PAYLOAD_FLOOR = 3.5


def _sharded_gate(shard4, parity_ok) -> bool:
    """PASS/FAIL for the sharded leg: parity always gates; the 2x scaling
    floor gates only where the hardware can express it (>= SHARD_MIN_CPUS
    physical cores behind >= 4 emulated devices)."""
    if not parity_ok:
        return False
    if shard4 is None:
        return True                       # leg skipped: too few devices
    if (os.cpu_count() or 1) < SHARD_MIN_CPUS:
        if shard4 < SHARD_FLOOR:
            print(f"[bench] sharded scaling {shard4:.2f}x below the "
                  f"{SHARD_FLOOR}x floor, NOT gating: only "
                  f"{os.cpu_count()} physical core(s) behind the emulated "
                  f"devices")
        return True
    return shard4 >= SHARD_FLOOR


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--hidden", type=int, default=64)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--input-dim", type=int, default=40)
    ap.add_argument("--classes", type=int, default=41)
    ap.add_argument("--frames", type=int, default=40)
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--capacities", default="1,4,16")
    ap.add_argument("--theta", type=float, default=0.05)
    ap.add_argument("--gamma", type=float, default=0.9375)
    ap.add_argument("--m", type=int, default=4)
    ap.add_argument("--capacity-frac", type=float, default=0.5)
    ap.add_argument("--chunk-frames", type=int, default=0,
                    help="chunked tick loop: frames advanced per dispatch "
                         "(0 = per-frame path)")
    ap.add_argument("--check", action="store_true",
                    help="exit 1 unless capacity-16 (or max capacity) hits "
                         ">=4x sequential frames/s with matching logits")
    ap.add_argument("--sweep", action="store_true",
                    help="crossover gate: hidden in {128, 512} at m=16, "
                         "capacity 16; exit 1 if the pool is ever slower "
                         "than batch-1 or parity fails")
    ap.add_argument("--async-load", action="store_true",
                    help="open-loop Poisson load generator against the "
                         "asyncio front-end: latency vs offered load plus "
                         "sustained-throughput ratio vs the sync chunked "
                         "pool (exit 1 on parity failure)")
    ap.add_argument("--obs-overhead", action="store_true",
                    help="observability-overhead leg only: chunked "
                         "throughput with live metrics + time-series "
                         "enabled vs disabled, exit 1 if enabled < "
                         f"{OBS_FLOOR}x disabled")
    ap.add_argument("--sharded", action="store_true",
                    help="sharded-pool leg only: shard_{1,2,4} rows at "
                         "hidden=512 / capacity=64 / 32-frame chunks, "
                         "parity-pinned, with the 2x shard_4 scaling gate "
                         "(run under XLA_FLAGS="
                         "--xla_force_host_platform_device_count=8; the "
                         "multi-device CI job does)")
    ap.add_argument("--quant", action="store_true",
                    help="quantized leg only: int8 weight payloads + Q8.8 "
                         "delta thresholds vs the fp32 pool; exit 1 on "
                         "parity failure, logit divergence > "
                         f"{QUANT_DIVERGENCE_BOUND}, or a weight-payload "
                         f"shrink under {QUANT_PAYLOAD_FLOOR}x")
    ap.add_argument("--json", action="store_true")
    ap.add_argument("--emit-json", metavar="PATH", default=None,
                    help="write the report as JSON (--sweep defaults to "
                         "BENCH_serving.json)")
    args = ap.parse_args()
    use_compile_cache()

    if args.sweep:
        if args.check or args.sharded or args.quant:
            ap.error("--sweep already includes the other gates; drop "
                     "--check/--sharded/--quant")
        if args.m != ap.get_default("m") or \
                args.capacities != ap.get_default("capacities") or \
                args.chunk_frames != ap.get_default("chunk_frames"):
            ap.error("--sweep fixes m=16, capacity 16 and its own "
                     "chunk_frames grid; drop --m/--capacities/"
                     "--chunk-frames or run without --sweep")
        emit = args.emit_json or "BENCH_serving.json"
        report = {}
        ok = True
        for hidden, path in SWEEP_LEGS:
            rep, parity = bench_config(
                hidden, args.layers, args.input_dim, args.classes,
                args.frames, args.requests, [SWEEP_CAP], args.theta,
                args.gamma, m=16, capacity_frac=args.capacity_frac,
                spmv_path=path)
            speedup = rep[f"capacity_{SWEEP_CAP}"]["speedup"]
            crossed = speedup >= 1.0
            print(f"[bench] sweep hidden={hidden} path={path}: parity="
                  f"{'ok' if parity else 'FAIL'} speedup={speedup:.1f}x -> "
                  f"{'PASS' if (parity and crossed) else 'FAIL'}")
            ok = ok and parity and crossed
            report[f"hidden_{hidden}_{path}"] = dict(
                rep, parity=parity,
                frames_per_s=rep[f"capacity_{SWEEP_CAP}"]["frames_per_s"])
        # chunked tick-loop gate: the biggest chunk must never be slower
        # than the per-frame pool (it measures >= 3x on CPU; the CI floor
        # is 1x so a noisy shared runner cannot flake the job):
        crep, cparity = bench_chunked(
            SWEEP_CHUNK_HIDDEN, args.layers, args.input_dim, args.classes,
            args.frames, args.requests, SWEEP_CAP, args.theta, args.gamma,
            m=16, capacity_frac=args.capacity_frac,
            chunk_grid=SWEEP_CHUNK_GRID)
        cmax = max(SWEEP_CHUNK_GRID)
        cspeed = crep[f"chunk_{cmax}"]["speedup_vs_per_frame"]
        cfast = cspeed >= 1.0
        print(f"[bench] sweep chunked hidden={SWEEP_CHUNK_HIDDEN}: parity="
              f"{'ok' if cparity else 'FAIL'} chunk_{cmax}="
              f"{cspeed:.1f}x per-frame -> "
              f"{'PASS' if (cparity and cfast) else 'FAIL'}")
        ok = ok and cparity and cfast
        report[f"chunked_hidden_{SWEEP_CHUNK_HIDDEN}"] = dict(
            crep, parity=cparity)
        # async open-loop leg: the asyncio front-end must sustain at least
        # ASYNC_FLOOR x the sync chunked pool at the same chunk size
        # (measured ~1x: it runs the identical chunked dispatch loop):
        arep, aparity = bench_async_load(
            SWEEP_CHUNK_HIDDEN, args.layers, args.input_dim, args.classes,
            args.frames, 3 * args.requests, SWEEP_CAP, args.theta,
            args.gamma, m=16, capacity_frac=args.capacity_frac,
            chunk=cmax, loads=ASYNC_LOADS)
        aratio = arep["throughput_ratio"]
        afast = aratio >= ASYNC_FLOOR
        print(f"[bench] sweep async hidden={SWEEP_CHUNK_HIDDEN}: parity="
              f"{'ok' if aparity else 'FAIL'} saturated={aratio:.2f}x sync "
              f"chunked (floor {ASYNC_FLOOR}) -> "
              f"{'PASS' if (aparity and afast) else 'FAIL'}")
        ok = ok and aparity and afast
        report[f"async_hidden_{SWEEP_CHUNK_HIDDEN}_chunk_{cmax}"] = dict(
            arep, parity=aparity)
        # sharded leg: shard_{1,2,4} rows; the 2x gate binds where the
        # host can express it (multi-device CI job), rows always land:
        srep, sparity, shard4 = bench_sharded(
            args.layers, args.input_dim, args.classes, args.frames,
            args.theta, args.gamma, args.capacity_frac,
            hidden=SHARD_HIDDEN, cap=SHARD_CAP, chunk=SHARD_CHUNK,
            grid=SHARD_GRID)
        sgate = _sharded_gate(shard4, sparity)
        print(f"[bench] sweep sharded hidden={SHARD_HIDDEN}: parity="
              f"{'ok' if sparity else 'FAIL'} shard_4="
              f"{'skipped' if shard4 is None else f'{shard4:.2f}x'} "
              f"shard_1 (floor {SHARD_FLOOR}x) -> "
              f"{'PASS' if sgate else 'FAIL'}")
        ok = ok and sgate
        report[f"sharded_hidden_{SHARD_HIDDEN}"] = dict(srep, parity=sparity)
        # observability-overhead leg: live metrics + time-series must stay
        # within OBS_FLOOR of the bare chunked pool (same config as the
        # chunked leg, so the two rows are directly comparable):
        orow, ook = bench_obs_overhead(
            SWEEP_CHUNK_HIDDEN, args.layers, args.input_dim, args.classes,
            args.frames, args.requests, SWEEP_CAP, args.theta, args.gamma,
            m=16, capacity_frac=args.capacity_frac, chunk=cmax)
        ok = ok and ook
        report["obs_overhead"] = orow
        # quantized leg: int8 weights + Q8.8 activations at the chunked
        # config — divergence-gated vs the fp32 pool, payload-ratio gated:
        qrow, qok = bench_quant(
            SWEEP_CHUNK_HIDDEN, args.layers, args.input_dim, args.classes,
            args.frames, args.requests, SWEEP_CAP, args.theta, args.gamma,
            m=16, capacity_frac=args.capacity_frac, chunk=cmax)
        ok = ok and qok
        report[f"quant_hidden_{SWEEP_CHUNK_HIDDEN}"] = qrow
        if args.json:
            print(json.dumps(report, indent=2))
        _write_report(emit, report)
        return 0 if ok else 1

    if args.sharded:
        emit = args.emit_json or "BENCH_serving.json"
        srep, sparity, shard4 = bench_sharded(
            args.layers, args.input_dim, args.classes, args.frames,
            args.theta, args.gamma, args.capacity_frac,
            hidden=SHARD_HIDDEN, cap=SHARD_CAP, chunk=SHARD_CHUNK,
            grid=SHARD_GRID)
        sgate = _sharded_gate(shard4, sparity)
        print(f"[bench] sharded hidden={SHARD_HIDDEN}: parity="
              f"{'ok' if sparity else 'FAIL'} shard_4="
              f"{'skipped' if shard4 is None else f'{shard4:.2f}x'} "
              f"shard_1 (floor {SHARD_FLOOR}x) -> "
              f"{'PASS' if sgate else 'FAIL'}")
        report = {f"sharded_hidden_{SHARD_HIDDEN}": dict(srep,
                                                         parity=sparity)}
        if args.json:
            print(json.dumps(report, indent=2))
        _write_report(emit, report)
        return 0 if sgate else 1

    if args.quant:
        chunk = args.chunk_frames or 32
        cap = max(int(c) for c in args.capacities.split(","))
        row, ok = bench_quant(
            args.hidden, args.layers, args.input_dim, args.classes,
            args.frames, args.requests, cap, args.theta, args.gamma,
            args.m, args.capacity_frac, chunk=chunk)
        report = {f"quant_hidden_{args.hidden}": row}
        if args.json:
            print(json.dumps(report, indent=2))
        if args.emit_json:
            _write_report(args.emit_json, report)
        return 0 if ok else 1

    if args.obs_overhead:
        chunk = args.chunk_frames or 32
        cap = max(int(c) for c in args.capacities.split(","))
        row, ok = bench_obs_overhead(
            args.hidden, args.layers, args.input_dim, args.classes,
            args.frames, args.requests, cap, args.theta, args.gamma,
            args.m, args.capacity_frac, chunk=chunk)
        report = {"obs_overhead": row}
        if args.json:
            print(json.dumps(report, indent=2))
        if args.emit_json:
            _write_report(args.emit_json, report)
        return 0 if ok else 1

    if args.async_load:
        chunk = args.chunk_frames or 32
        report, parity_ok = bench_async_load(
            args.hidden, args.layers, args.input_dim, args.classes,
            args.frames, args.requests, max(
                int(c) for c in args.capacities.split(",")), args.theta,
            args.gamma, args.m, args.capacity_frac, chunk=chunk,
            loads=ASYNC_LOADS)
        if args.json:
            print(json.dumps(report, indent=2))
        if args.emit_json:
            _write_report(args.emit_json, report)
        return 0 if parity_ok else 1

    caps = [int(c) for c in args.capacities.split(",")]
    report, parity_ok = bench_config(
        args.hidden, args.layers, args.input_dim, args.classes, args.frames,
        args.requests, caps, args.theta, args.gamma, args.m,
        args.capacity_frac, chunk_frames=args.chunk_frames)

    if args.json:
        print(json.dumps(report, indent=2))
    if args.emit_json:
        _write_report(args.emit_json, report)

    if args.check:
        cap = max(caps)
        speedup = report[f"capacity_{cap}"]["speedup"]
        ok = parity_ok and speedup >= 4.0
        print(f"[bench] check: parity={'ok' if parity_ok else 'FAIL'} "
              f"speedup@{cap}={speedup:.1f}x -> {'PASS' if ok else 'FAIL'}")
        return 0 if ok else 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
