"""Observability smoke for CI: a live async pool under client load, the
admin endpoint answering every command, the tracer covering every
tick-loop phase, and the counters agreeing with the delivered results.

Spins up an in-process `AsyncSpartusServer` (tiny untrained CBTD model —
this exercises plumbing, not accuracy) with observability + tracing
attached, streams concurrent clients through it, queries the admin
listener (``healthz`` / ``stats`` / ``metrics`` / ``timeseries``) while
the pool is serving, and writes the artifacts CI uploads:

* ``<outdir>/trace.json``    — Chrome trace (load it in Perfetto)
* ``<outdir>/metrics.json``  — final registry snapshot + time series

Exit code 0 = every check passed.  Run directly::

    PYTHONPATH=src python tools/obs_smoke.py --outdir /tmp/obs
"""
from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys

import numpy as np

REQUIRED_PHASES = {"client_pump", "admission_upload", "dispatch",
                   "retire_snapshot", "snapshot_fetch", "fetch_wait",
                   "fetch_copy", "delivery_pump", "pacing_idle"}
ADMIN_COMMANDS = ("healthz", "stats", "metrics", "timeseries")


def _fail(msg: str) -> None:
    print(f"[obs-smoke] FAIL: {msg}")
    sys.exit(1)


async def _query(reader, writer, msg):
    writer.write((json.dumps(msg) + "\n").encode())
    await writer.drain()
    return json.loads(await reader.readline())


async def _client(server, feats, block=3):
    handle = await server.stream(want_partials=True)
    for j in range(0, len(feats), block):
        await handle.send(feats[j:j + block])
        await asyncio.sleep(0)
    handle.close()
    async for _ in handle:
        pass
    return await handle.result()


async def _run(args):
    import jax

    from repro.launch.serve import start_admin_server
    from repro.models import lstm_am
    from repro.serving import (AsyncSpartusServer, BatchedSpartusEngine,
                               EngineConfig, PoolObservability, Tracer)

    cfg = lstm_am.LSTMAMConfig(input_dim=20, hidden_dim=args.hidden,
                               n_layers=2, n_classes=11)
    params = lstm_am.cbtd_prune_stacks(
        lstm_am.init_params(jax.random.key(0), cfg), gamma=0.75, m=4)
    engine = BatchedSpartusEngine(
        params, cfg, EngineConfig(theta=0.05, gamma=0.75, m=4))
    rng = np.random.default_rng(0)
    feats = [rng.standard_normal((t, 20)).astype(np.float32)
             for t in (12, 7, 19, 4, 15, 9, 11, 6)[:args.clients]]

    obs = PoolObservability(tracer=Tracer(enabled=True))
    replies = {}
    async with AsyncSpartusServer(engine, capacity=args.capacity,
                                  chunk_frames=4,
                                  observability=obs) as server:
        admin = await start_admin_server(server, obs, port=0)
        port = admin.sockets[0].getsockname()[1]
        print(f"[obs-smoke] admin listening on 127.0.0.1:{port}")
        tasks = [asyncio.ensure_future(_client(server, f)) for f in feats]
        reader, writer = await asyncio.open_connection("127.0.0.1", port)
        # first sweep races the load on purpose — the endpoint must answer
        # mid-serve; the post-load sweep is what we assert counters on:
        for cmd in ADMIN_COMMANDS:
            replies[f"live_{cmd}"] = await _query(reader, writer,
                                                  {"cmd": cmd})
        results = await asyncio.gather(*tasks)
        for cmd in ADMIN_COMMANDS:
            replies[cmd] = await _query(reader, writer, {"cmd": cmd})
        replies["bad"] = await _query(reader, writer, {"cmd": "bogus"})
        writer.close()
        admin.close()
        await admin.wait_closed()
    return obs, replies, results


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--outdir", default="obs_smoke_out")
    ap.add_argument("--hidden", type=int, default=32)
    ap.add_argument("--capacity", type=int, default=3)
    ap.add_argument("--clients", type=int, default=6)
    args = ap.parse_args()

    obs, replies, results = asyncio.run(_run(args))

    for cmd in ADMIN_COMMANDS:
        for key in (f"live_{cmd}", cmd):
            if "error" in replies[key]:
                _fail(f"admin {key!r} answered error: {replies[key]}")
    if replies["healthz"].get("ok") is not True:
        _fail(f"healthz not ok: {replies['healthz']}")
    if "error" not in replies["bad"]:
        _fail("unknown command did not answer in-band error")

    if len(results) != args.clients:
        _fail(f"{len(results)}/{args.clients} clients finished")
    snap = replies["metrics"]["metrics"]
    n_done = snap["spartus_completed_total"]["value"]
    if n_done != args.clients:
        _fail(f"completed counter {n_done} != {args.clients} clients")
    if snap["spartus_dispatches_total"]["value"] <= 0:
        _fail("no dispatches counted")
    if not replies["timeseries"]["timeseries"]:
        _fail("empty time series after a served load")
    if "# TYPE spartus_frames_total counter" not in \
            replies["metrics"]["prometheus"]:
        _fail("prometheus exposition missing the frames counter")

    trace = json.loads(obs.tracer.to_json())
    names = {e["name"] for e in trace["traceEvents"]}
    if not REQUIRED_PHASES <= names:
        _fail(f"trace missing phases: {sorted(REQUIRED_PHASES - names)}")

    os.makedirs(args.outdir, exist_ok=True)
    trace_path = os.path.join(args.outdir, "trace.json")
    obs.tracer.dump(trace_path)
    metrics_path = os.path.join(args.outdir, "metrics.json")
    with open(metrics_path, "w") as f:
        json.dump({"metrics": snap,
                   "prometheus": replies["metrics"]["prometheus"],
                   "timeseries": obs.timeseries.snapshot()}, f, indent=2)
    print(f"[obs-smoke] {len(results)} clients served, "
          f"{int(snap['spartus_frames_total']['value'])} frames, "
          f"{len(trace['traceEvents'])} trace events "
          f"({', '.join(sorted(names))})")
    print(f"[obs-smoke] wrote {trace_path} and {metrics_path}")
    print("[obs-smoke] PASS")
    return 0


if __name__ == "__main__":
    sys.exit(main())
