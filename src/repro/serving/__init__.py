"""Serving: the Spartus datapath as an inference service.

- `engine`         — paper-faithful batch-1 streaming engine (SpartusEngine)
- `batched_engine` — continuous-batching multi-session engine (step_batch /
                     step_frames / chunked step_chunk + output snapshots)
- `scheduler`      — SessionPool admission/eviction (incl. incremental
                     streaming admission + partial-logits snapshots) and
                     the synchronous serve_requests driver
- `async_server`   — asyncio streaming front-end (AsyncSpartusServer):
                     admission-while-running, wall-clock-paced chunks,
                     per-chunk partial logits to bounded per-session
                     queues (lagging/backfill slow-consumer policy)
- `sharding`       — slot-dimension data parallelism: NamedSharding
                     placement of every pool slab over a 1-D ("data",)
                     mesh (SessionPool(n_devices=N))
- `telemetry`      — device-resident per-(layer, slot) sparsity counters
                     + the shared latency percentile reduction
- `metrics`        — live observability: metrics registry (Prometheus
                     text + JSON snapshot), per-chunk time-series ring,
                     driver-phase Chrome tracing (PoolObservability,
                     folded at chunk boundaries only)
- `checkpoint`     — session checkpoint/restore: per-slot snapshots of
                     the full recurrent state (h/c, delta memories, frame
                     cursor, logits-bank rows), whole-pool save/restore
                     through training/checkpoint.py's atomic writer, and
                     cross-shard-count migration (bit-identical resume)
- `faults`         — the robustness vocabulary: typed retriable-vs-fatal
                     serving errors (wire codes), the seeded deterministic
                     fault-injection harness, and full-jitter backoff

See docs/serving.md for the architecture, docs/robustness.md for the
failure model, and docs/architecture.md for how serving fits the full
pipeline.
"""
from repro.serving.async_server import (
    AsyncSpartusServer,
    StreamClosed,
    StreamHandle,
)
from repro.serving.checkpoint import (
    PoolCheckpoint,
    SessionSnapshot,
    engine_fingerprint,
    load_checkpoint,
    restore_into,
    save_pool,
    snapshot_pool,
    snapshot_session,
)
from repro.serving.faults import (
    AdmissionShed,
    Backoff,
    BadRequest,
    DriverRecovered,
    FaultEvent,
    FaultInjector,
    FaultPlan,
    InjectedFault,
    ProtocolError,
    ServingError,
    SessionTimeout,
    error_payload,
)
from repro.serving.batched_engine import (
    BatchedGRULayerState,
    BatchedLayerState,
    BatchedSpartusEngine,
    PoolState,
)
from repro.serving.engine import EngineConfig, PackedLayer, SpartusEngine
from repro.serving.metrics import (
    MetricsRegistry,
    PoolObservability,
    TimeSeries,
    Tracer,
)
from repro.serving.scheduler import (
    PartialLogits,
    RequestResult,
    ServeStats,
    SessionPool,
    StreamRequest,
    serve_requests,
)
from repro.serving.telemetry import (
    TelemetryState,
    init_telemetry,
    measured_sparsity,
    percentile_summary,
)
