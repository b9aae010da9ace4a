"""Continuous-batching session scheduler for streaming DeltaLSTM serving.

The datacenter serving pattern (ESE's channel-multiplexed multi-voice
engine, SHARP's adaptive RNN scheduler) translated to software: one
weight-resident `BatchedSpartusEngine` and a `SessionPool` that
multiplexes many independent streaming requests across its fixed-capacity
slot dimension.

Lifecycle of a request:

  queued ──admit──> active(slot k) ──per-frame steps──> finished
            ^                                              │
            └── backpressure: waits while no slot is free ─┘

* `admit` attaches a request to a free slot and uploads its *whole*
  utterance `[T, D]` into the slot's device-resident feature buffer once;
  the slot's device state is re-initialised by the `reset` mask *inside*
  the next `step_frames`, so admission never triggers an extra dispatch
  or a recompile.  `admit_stream` admits a session whose utterance is
  still arriving: frames are appended incrementally (`append_frames`),
  the session simply idles ("starved") whenever it has consumed
  everything received so far, and `finish_stream` marks the end of the
  utterance.  A starved session costs nothing: it rides the chunk
  masked out, exactly like a free slot.
* `step` advances all active slots one frame in ONE jitted call
  (`step_frames`): each slot's current frame is gathered **on device** by
  the cursor carried in `PoolState` — the tick moves zero frame bytes
  host -> device — then the `[B, n_classes]` logits are fetched once,
  each active slot's row appended to its request, and slots whose
  utterance is exhausted retire.
* `step_chunk` (``chunk_frames >= 1``) amortises that dispatch over up to
  C frames: ONE `lax.scan`-backed dispatch advances every slot by up to C
  frames, banking logits in a per-slot device output buffer, and the pool
  runs **double-buffered**: while chunk t executes on device, the host
  does chunk t's retirement bookkeeping and the next admissions, and the
  device->host logits fetch of chunk t-1's retired sessions.  A finished
  session's logits leave the device once, at retirement, instead of one
  `[B, n_classes]` row fetch per tick.  Admission happens at chunk
  boundaries only.
* ``stream_partials=True`` additionally snapshots **each chunk's** rows
  for every live slot (`engine.snapshot_chunk`, a `[B, C, n_classes]`
  device copy — not the whole output buffer) and surfaces them one chunk
  later as `PartialLogits`, so a streaming consumer sees logits per
  chunk instead of only at retirement.  This is what the asyncio
  front-end (`serving/async_server.py`) feeds to its per-session queues.
* `tick` is the non-blocking driver entry point: one call does at most
  one dispatch (chunk or frame), retires sessions that finished without
  needing another dispatch, and returns `(finished_results,
  frames_advanced)` without waiting for the device (JAX async dispatch;
  the only sync is the previous chunk's one-copy logits fetch).
* Idle slots ride along masked-out for free; the pool never reshapes (the
  frame buffer length is bucketed to powers of two), so the step function
  compiles once per (capacity, bucket).  Growth past
  ``max_buffer_frames`` is refused at admission time with a clear error
  instead of silently truncating.

`serve_requests` is the batteries-included synchronous driver: feed it an
iterable of requests with arrival times (in scheduler ticks), get
per-request logits plus queue/service/latency metrics back;
``chunk_frames=C`` selects the chunked path (0 keeps the per-frame oracle
path).  It is also the parity oracle the async front-end is pinned
against in tests.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from collections import deque
from typing import Dict, Iterable, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis import lockorder
from repro.serving.batched_engine import BatchedSpartusEngine, PoolState
from repro.serving.faults import FaultInjector
from repro.serving.metrics import NULL_TRACER, PoolObservability
from repro.serving import sharding as shardlib
from repro.serving import telemetry as tele

#: default ceiling on the per-slot frame-buffer length (frames).  The device
#: buffers grow by pow2 buckets up to this; an utterance that could not fit
#: is rejected at admission with a ValueError instead of being truncated at
#: some later chunk boundary.
DEFAULT_MAX_BUFFER_FRAMES = 4096


@functools.partial(jax.jit, donate_argnums=(0, 1))
def _device_upload(
    frames: jax.Array, lengths: jax.Array, rows: jax.Array,
    slots: jax.Array, ts: jax.Array,
) -> Tuple[jax.Array, jax.Array]:
    """Scatter one admission wave's (bucket-padded) utterances + lengths
    into the pool's device buffers at DYNAMIC slot indices.

    rows [R, T_buf, D], slots/ts [R] int32; padding entries carry an
    out-of-bounds slot and are dropped.  Jitted with traced indices so it
    compiles once per (buffer shape, R-bucket): an eagerly dispatched
    ``frames.at[slot, :t].set(...)`` re-lowers per (slot, t) pair and
    cost ~2 ms PER ADMISSION on the CPU backend — an admission storm of
    16 requests used to spend longer staging frames than the device
    spends computing a 32-frame chunk.  The buffers are donated, so the
    scatter updates them in place instead of copying the whole slab (the
    runtime serializes the write against any in-flight chunk still
    reading the old frames)."""
    frames = frames.at[slots].set(rows, mode="drop")
    lengths = lengths.at[slots].set(ts, mode="drop")
    return frames, lengths


@functools.partial(jax.jit, donate_argnums=(0, 1))
def _device_append(
    frames: jax.Array, lengths: jax.Array, rows: jax.Array,
    slots: jax.Array, starts: jax.Array, ts: jax.Array,
) -> Tuple[jax.Array, jax.Array]:
    """Append one wave of mid-stream frame blocks into live slots' buffers.

    rows [R, A, D] (A = pow2 bucket of the wave's longest block), slots
    [R] int32 (out-of-bounds = padding, dropped), starts [R] int32 (frame
    offset of each block = frames received so far), ts [R] int32 new total
    length.  One gather + vmapped ``dynamic_update_slice`` + one scatter,
    jitted so incremental streaming admission costs one dispatch per wave
    like the full-utterance upload.  The caller guarantees
    ``start + A <= T_buf`` (growing the buffer first if needed) so the
    slice never clamps into earlier frames."""
    safe = jnp.minimum(slots, frames.shape[0] - 1)
    cur = frames[safe]                                     # [R, T_buf, D]
    upd = jax.vmap(
        lambda b, r, st: jax.lax.dynamic_update_slice(b, r, (st, 0))
    )(cur, rows, starts)
    frames = frames.at[slots].set(upd, mode="drop")
    lengths = lengths.at[slots].set(ts, mode="drop")
    return frames, lengths


def validated_frames(feats, req_id: int,
                     input_dim: Optional[int] = None) -> np.ndarray:
    """Admission-time payload validation (shared by ``admit``,
    ``append_frames`` and the async server): reject non-numeric dtypes
    and NaN/Inf values with a clear ValueError BEFORE the frames reach
    the shared device batch — one poisoned utterance must never corrupt
    neighbour sessions' logits.  Returns the float32 frame array.

    Host-side and admission-only: the isfinite scan runs once per
    received frame block, never per tick, so the hot path is untouched.
    """
    arr = np.asarray(feats)
    if arr.dtype.kind not in "fiu":
        raise ValueError(
            f"request {req_id}: frames have unsupported dtype {arr.dtype} "
            f"(expected a float or integer array)")
    arr = np.asarray(arr, np.float32)
    if input_dim is not None and arr.size and arr.shape[-1] != input_dim:
        raise ValueError(
            f"request {req_id}: feature dim {arr.shape[-1]} != "
            f"engine input dim {input_dim}")
    if not np.isfinite(arr).all():
        raise ValueError(
            f"request {req_id}: frames contain NaN/Inf values")
    return arr


@dataclasses.dataclass
class StreamRequest:
    """One streaming utterance: `feats [T, D]` arriving at `arrival_step`."""

    req_id: int
    arrival_step: int
    feats: np.ndarray

    @property
    def n_frames(self) -> int:
        return int(self.feats.shape[0])


@dataclasses.dataclass
class RequestResult:
    req_id: int
    arrival_step: int
    admit_step: int       # tick the request got a slot
    finish_step: int      # tick its last frame was produced
    logits: np.ndarray    # [T, n_classes]
    wall_latency_s: float  # wall time from eligibility to last frame
    truncated: bool = False  # stopped by max_steps with frames still pending
    #                          (logits holds the frames produced so far)
    queue_wait_s: float = 0.0  # wall time from eligibility to slot admission
    ttfl_s: float = 0.0        # time to first logit: wall time from
    #                            eligibility until the first logits row was
    #                            available host-side (== wall_latency_s when
    #                            logits only surface at retirement)

    @property
    def queue_steps(self) -> int:
        return self.admit_step - self.arrival_step

    @property
    def service_steps(self) -> int:
        return self.finish_step - self.admit_step + 1

    @property
    def turnaround_steps(self) -> int:
        return self.finish_step - self.arrival_step + 1


@dataclasses.dataclass
class PartialLogits:
    """One streamed block of logits for a live session (``stream_partials``):
    rows ``[n, n_classes]`` covering frames ``[t0, t0 + n)``."""

    req_id: int
    t0: int
    rows: np.ndarray


@dataclasses.dataclass
class _Session:
    req_id: int
    arrival_step: int
    admit_step: int
    arrival_wall: float
    admit_wall: float
    total: Optional[int]   # utterance length; None while the client streams
    n_recv: int = 0        # frames received (staged for device upload)
    cursor: int = 0        # frames consumed by the engine
    last_step: int = 0     # tick of the most recent consumed frame
    needs_reset: bool = True
    cancelled: bool = False
    partials_paused: bool = False  # slow consumer: skip snapshot_chunk
    #                                entries for this slot until resumed
    first_logit_wall: float = 0.0  # 0.0 = no logits surfaced yet
    rows: List[np.ndarray] = dataclasses.field(default_factory=list)

    @property
    def done(self) -> bool:
        """Every frame of a finished utterance has been consumed."""
        return self.total is not None and self.cursor >= self.total

    @property
    def available(self) -> int:
        """Frames received but not yet consumed."""
        return self.n_recv - self.cursor

    def result(self, logits: np.ndarray, *, truncated: bool = False,
               finish_step: Optional[int] = None) -> RequestResult:
        t_done = time.perf_counter()
        first = self.first_logit_wall if self.first_logit_wall else t_done
        return RequestResult(
            req_id=self.req_id,
            arrival_step=self.arrival_step,
            admit_step=self.admit_step,
            finish_step=self.last_step if finish_step is None else finish_step,
            logits=logits,
            wall_latency_s=t_done - self.arrival_wall,
            truncated=truncated,
            queue_wait_s=self.admit_wall - self.arrival_wall,
            ttfl_s=first - self.arrival_wall,
        )


#: slots per retirement gather (clipped to the pool's capacity): one
#: block shape, so a pool compiles one retirement program however many
#: sessions retire at a boundary; more retirees take more blocks.
RETIRE_BLOCK = 32


@dataclasses.dataclass
class _PendingChunk:
    """Sessions that finished inside an in-flight chunk: their slots' rows
    were gathered out of the device output buffer in blocks of
    ``RETIRE_BLOCK`` slots (async, BEFORE the next chunk donates that
    buffer away) and are fetched to host one chunk later — one D2H copy
    per block — overlapped with the next chunk's device execution."""

    sessions: List[_Session]
    where: List[Tuple[int, int]]  # (block, row) holding each session's rows
    blocks: List[jax.Array]       # [R, T_pad, n_classes] device gathers


@dataclasses.dataclass
class _PendingPartials:
    """One chunk's per-slot logits rows (``engine.snapshot_chunk``),
    snapshotted device-side before the next dispatch donates the output
    buffer and fetched one chunk later, overlapped like retirements."""

    entries: List[Tuple[_Session, int, int, int]]  # (session, slot, t0, n)
    rows: jax.Array                                # [B, C, n_classes]


@dataclasses.dataclass
class ServeStats:
    capacity: int
    n_requests: int
    total_frames: int
    total_steps: int      # ticks that advanced >= 1 slot (idle ticks excluded)
    wall_s: float
    frames_per_s: float
    p50_latency_s: float
    p95_latency_s: float
    p50_turnaround_steps: float
    p95_turnaround_steps: float
    # aggregated device-side telemetry (telemetry.measured_sparsity output),
    # the input to hwsim.spartus_model.evaluate_from_telemetry:
    sparsity: Dict[str, float] = dataclasses.field(default_factory=dict)
    # True when max_steps stopped the run before every request completed;
    # in-flight sessions were drained into truncated RequestResults:
    truncated: bool = False
    # dispatch amortisation: jitted device dispatches issued and their
    # ratio to frames served — the per-frame path pays ~1/B dispatches per
    # frame, the chunked path ~1/(B*C):
    chunk_frames: int = 0            # 0 = per-frame path
    n_dispatches: int = 0
    dispatches_per_frame: float = 0.0
    # mean fraction of each step_chunk call's wall time the host spent on
    # useful work after the dispatch returned (retirement bookkeeping, the
    # device-side snapshot, the previous chunk's logits fetch) — all
    # concurrent with the in-flight device chunk; 0.0 on the per-frame
    # path, which syncs on its logits every tick:
    host_overlap_frac: float = 0.0
    # tail latency + streaming responsiveness under concurrency:
    p99_latency_s: float = 0.0
    # queue wait: wall time from request eligibility to slot admission
    # (the backpressure component of the latency):
    p50_queue_wait_s: float = 0.0
    p95_queue_wait_s: float = 0.0
    p99_queue_wait_s: float = 0.0
    # time-to-first-logit: how long a client waits before logits start
    # streaming back (== full latency when logits only surface at
    # retirement, i.e. the sync chunked path without stream_partials):
    p50_ttfl_s: float = 0.0
    p95_ttfl_s: float = 0.0
    p99_ttfl_s: float = 0.0
    # device bytes per resident session (SessionPool.bytes_per_slot):
    # per-slot state slabs + frame/logits rows + the slot's share of the
    # shared packed weights — the capacity currency the int8 quantized
    # pack (EngineConfig.quant) buys back:
    bytes_per_slot: float = 0.0

    def to_dict(self) -> Dict[str, float]:
        return dataclasses.asdict(self)


def aggregate_stats(
    results: Sequence[RequestResult],
    *,
    capacity: int,
    n_requests: int,
    total_steps: int,
    wall_s: float,
    sparsity: Dict[str, float],
    truncated: bool = False,
    chunk_frames: int = 0,
    n_dispatches: int = 0,
    host_overlap_frac: float = 0.0,
    bytes_per_slot: float = 0.0,
) -> ServeStats:
    """Reduce per-request results to the aggregate `ServeStats` (shared by
    the synchronous `serve_requests` driver and the asyncio front-end)."""
    frames = int(sum(r.logits.shape[0] for r in results))
    lat = [r.wall_latency_s for r in results]
    tas = np.array([r.turnaround_steps for r in results], np.float64)
    pl = tele.percentile_summary(lat, "latency_s")
    pq = tele.percentile_summary([r.queue_wait_s for r in results],
                                 "queue_wait_s")
    pt = tele.percentile_summary([r.ttfl_s for r in results], "ttfl_s")
    return ServeStats(
        capacity=capacity,
        n_requests=n_requests,
        total_frames=frames,
        total_steps=total_steps,
        wall_s=wall_s,
        frames_per_s=frames / wall_s if wall_s > 0 else float("inf"),
        p50_latency_s=pl["p50_latency_s"],
        p95_latency_s=pl["p95_latency_s"],
        p99_latency_s=pl["p99_latency_s"],
        p50_turnaround_steps=float(np.percentile(tas, 50)) if len(tas) else 0.0,
        p95_turnaround_steps=float(np.percentile(tas, 95)) if len(tas) else 0.0,
        sparsity=sparsity,
        truncated=truncated,
        chunk_frames=chunk_frames,
        n_dispatches=n_dispatches,
        dispatches_per_frame=n_dispatches / frames if frames else 0.0,
        host_overlap_frac=host_overlap_frac,
        p50_queue_wait_s=pq["p50_queue_wait_s"],
        p95_queue_wait_s=pq["p95_queue_wait_s"],
        p99_queue_wait_s=pq["p99_queue_wait_s"],
        p50_ttfl_s=pt["p50_ttfl_s"],
        p95_ttfl_s=pt["p95_ttfl_s"],
        p99_ttfl_s=pt["p99_ttfl_s"],
        bytes_per_slot=bytes_per_slot,
    )


def _frame_bucket(n: int, floor: int = 64) -> int:
    """Frame-buffer length bucket: next power of two, >= ``floor``.  Keeps
    the device buffer shape (and thus the compiled step) stable across
    utterance lengths; growth past the bucket recompiles once."""
    b = floor
    while b < n:
        b *= 2
    return b


class SessionPool:
    """Fixed-capacity pool of device-resident streaming sessions.

    Request features live on device: ``admit`` uploads the whole utterance
    `[T, D]` into the slot's row of a `[B, T_buf, D]` buffer once, and every
    tick gathers each slot's current frame by the device cursor in
    ``PoolState`` — the steady state issues zero per-tick host staging
    copies (the old `step_batch` path re-staged every slot's frame on host
    each tick, which at large hidden sizes cost more than the math).

    ``admit_stream`` admits a session before its utterance is complete:
    `append_frames` stages further frame blocks (uploaded one jitted wave
    per boundary, like admissions), `finish_stream` closes the utterance,
    and `cancel` abandons it (the slot frees at the next boundary).  A
    session that has consumed everything received so far simply idles.

    With ``chunk_frames=C >= 1`` the pool runs the chunked tick loop:
    ``step_chunk`` advances every active slot up to C frames in ONE
    dispatch and banks logits in a per-slot device output buffer
    `[B, T_buf, n_classes]`; retired sessions' logits are fetched once, at
    retirement, double-buffered one chunk behind the in-flight dispatch.
    ``stream_partials=True`` also snapshots each chunk's `[B, C,
    n_classes]` rows so live sessions stream partial logits per chunk
    (``take_partials``).  A chunked pool steps with
    ``step_chunk``/``flush``/``tick`` only (``step`` raises: the two modes
    account logits differently).

    An utterance longer than ``max_buffer_frames`` (whether declared at
    admission or accumulated by appends) is rejected with a ValueError:
    the device frame buffers grow in pow2 buckets up to that ceiling and
    nothing in the pool ever truncates silently.

    ``n_devices=N >= 1`` shards the pool's slot dimension over a 1-D
    ``("data",)`` mesh (`serving/sharding.py`): every per-slot device
    slab — layer state, frame buffers, cursors, lengths, the logits
    bank, telemetry — is partitioned into contiguous slot blocks, one
    per device, and the same jitted step/chunk dispatch runs SPMD with
    zero cross-device communication in the steady state (slots are
    independent).  Admission places each session on the least-loaded
    shard; a capacity not divisible by N falls back to replication (the
    never-invalid rule), which is correct but not parallel.  The public
    API is unchanged — only placement differs.
    """

    # Machine-checked lock discipline (repro.analysis.concurrency; see
    # docs/concurrency.md).  Every listed field is rebound at dispatch
    # boundaries by jitted calls that DONATE the old buffers, while
    # cross-thread readers — the async server's ``stats()``, the admin
    # endpoint, checkpoint snapshots — may hold stale references; an
    # unlocked read can fetch a deleted buffer.  Host bookkeeping
    # (``_slots``, ``_by_req``, ``_staged``, ``_staged_appends``,
    # ``_partials``) is tick/driver-thread-only and deliberately absent.
    _guarded_by_ = {
        "state": "_state_lock",
        "_frames": "_state_lock",
        "_lengths": "_state_lock",
        "_out": "_state_lock",
        "_pending": "_state_lock",
        "_pending_partials": "_state_lock",
    }

    def __init__(self, engine: BatchedSpartusEngine, capacity: int,
                 max_frames: int = 64, chunk_frames: int = 0,
                 max_buffer_frames: Optional[int] = None,
                 stream_partials: bool = False,
                 n_devices: Optional[int] = None,
                 observability: Optional[PoolObservability] = None,
                 faults: Optional[FaultInjector] = None):
        if capacity < 1:
            raise ValueError("capacity must be >= 1")
        if chunk_frames < 0:
            raise ValueError("chunk_frames must be >= 0 (0 = per-frame)")
        self.engine = engine
        self.capacity = capacity
        self.chunk_frames = chunk_frames
        self.stream_partials = stream_partials
        self.max_buffer_frames = (DEFAULT_MAX_BUFFER_FRAMES
                                  if max_buffer_frames is None
                                  else int(max_buffer_frames))
        if max_frames > self.max_buffer_frames:
            raise ValueError(
                f"max_frames={max_frames} exceeds max_buffer_frames="
                f"{self.max_buffer_frames}")
        # slot-dimension data parallelism (None = single-device layout,
        # bit-for-bit the pre-sharding pool):
        self._n_devices = n_devices
        # seeded fault-injection hook (serving/faults.py): `_fire(site)`
        # raises InjectedFault at the scheduled invocations; None = off,
        # zero cost (one attribute check per boundary, nothing compiled)
        self.faults = faults
        self._mesh = (shardlib.make_pool_mesh(int(n_devices))
                      if n_devices is not None else None)
        self.n_shards = (shardlib.n_pool_shards(self._mesh, capacity)
                         if self._mesh is not None else 1)
        self.state: PoolState = engine.init_state(capacity)
        self._slots: List[Optional[_Session]] = [None] * capacity
        self._by_req: Dict[int, int] = {}
        # device-resident per-slot feature buffers, uploaded at admission:
        self._t_buf = _frame_bucket(max_frames)
        self._frames = jnp.zeros((capacity, self._t_buf, engine.input_dim),
                                 jnp.float32)
        # per-slot utterance lengths (device) — the chunk masks a slot off
        # once its cursor reaches its length:
        self._lengths = jnp.zeros((capacity,), jnp.int32)
        # chunked mode: device logits buffer + retirements pending their
        # (overlapped) host fetch.  The time axis is padded by
        # chunk_frames so the chunk's banking slice never clamps: rows
        # past a session's length are scratch no reader consumes.
        self._out: Optional[jax.Array] = (
            engine.init_out_buf(capacity, self._t_buf + chunk_frames)
            if chunk_frames else None)
        if self._mesh is not None:
            # one placement pass at construction; the step functions
            # donate every slab, so the sharding persists tick over tick.
            self.state = shardlib.shard_pool_state(self.state, self._mesh)
            self._frames = shardlib.shard_slot_array(self._frames, self._mesh)
            self._lengths = shardlib.shard_slot_array(self._lengths,
                                                      self._mesh)
            if self._out is not None:
                self._out = shardlib.shard_slot_array(self._out, self._mesh)
        self._pending: List[_PendingChunk] = []
        self._pending_partials: List[_PendingPartials] = []
        self._partials: List[PartialLogits] = []
        # admissions staged host-side, flushed to device in ONE batched
        # upload at the next step/chunk boundary; appends staged likewise:
        self._staged: List[Tuple[int, np.ndarray]] = []
        self._staged_appends: List[Tuple[int, int, np.ndarray]] = []
        # observability: buffer growths (should be 0 when pre-sized),
        # dispatches issued, and the running sum and count of per-chunk
        # host-overlap fractions:
        self.n_frame_grows = 0
        self.n_dispatches = 0
        self._overlap_sum = 0.0
        self._overlap_n = 0
        # live observability (metrics.PoolObservability): all sources are
        # folded at dispatch boundaries only, on host values the pool
        # already computed — the one device-derived signal (incremental
        # sparsity) is a [3] reduction enqueued here and fetched one
        # boundary later, so observability never syncs on the in-flight
        # chunk and never changes the compiled step (pinned in
        # tests/test_observability.py).  None = fully off; the tracer
        # falls back to NULL_TRACER, whose spans are profiler annotations
        # only.  ``_boundary`` collects span seconds and fetch / upload
        # counts for the next boundary sample (None = not collected).
        self.obs = observability
        self._tracer = (observability.tracer if observability is not None
                        else NULL_TRACER)
        self._boundary = (observability.boundary
                          if observability is not None else None)
        self._adm_since_fold = 0
        # Guards the dispatch-and-rebind of ``self.state`` against readers
        # on other threads (the async server's ``stats()`` / the admin
        # endpoint call ``measured_sparsity()`` from the event loop while
        # ``offload_ticks`` runs the tick in a worker).  Dispatch donates
        # the old state's buffers the instant it is issued, so a reader
        # holding a stale reference would fetch a deleted buffer; making
        # (dispatch + rebind) atomic and reading under the same lock means
        # readers only ever see the live (possibly in-flight) state.
        # Created through the lock-order factory so the chaos job's
        # recorder (repro.analysis.lockorder) sees every acquisition; a
        # plain threading.Lock when no recorder is installed.
        self._state_lock = lockorder.make_lock("SessionPool._state_lock")

    def _fire(self, site: str) -> None:
        """Fault-injection hook: raise if the plan scheduled a failure at
        this invocation of ``site``.  A ``"poison"`` payload additionally
        invalidates the device state first — modelling a crash *after* a
        dispatch donated the buffers away, so per-slot salvage must fail
        and the watchdog's lost-session path is exercised."""
        if self.faults is None:
            return
        try:
            self.faults.fire(site)
        except Exception as exc:
            if self.obs is not None:
                self.obs.fold_fault(site)
            if getattr(exc, "payload", None) == "poison":
                with self._state_lock:
                    for leaf in jax.tree_util.tree_leaves(self.state):
                        leaf.delete()
            raise

    def _tally(self, **counts: int) -> None:
        """Add host-known counts to the next boundary sample."""
        if self._boundary is not None:
            for key, n in counts.items():
                self._boundary[key] = self._boundary.get(key, 0) + n

    def _dev1d(self, arr: np.ndarray) -> jax.Array:
        """Place a per-slot host vector (active/reset masks, chunk-start
        cursors) to match the pool's slot sharding.  Identity-cost when
        unsharded (the jitted step converts host arrays itself); in
        sharded mode an explicit placement keeps every dispatch input on
        the agreed layout so GSPMD never has to guess (a differently
        placed mask would recompile the step)."""
        if self._mesh is None:
            return arr
        return shardlib.shard_slot_array(jnp.asarray(arr), self._mesh)

    def _ensure_slot_sharding(self) -> None:
        """Re-pin the frame/length buffers to the slot sharding if an
        upload scatter's output landed elsewhere (GSPMD usually preserves
        the operand sharding; this is the cheap invariant check that
        makes it a guarantee).  No-op when unsharded."""
        if self._mesh is None:
            return
        fs = shardlib.slot_sharding(self._frames.shape, self._mesh)
        if self._frames.sharding != fs:
            self._frames = jax.device_put(self._frames, fs)
        ls = shardlib.slot_sharding(self._lengths.shape, self._mesh)
        if self._lengths.sharding != ls:
            self._lengths = jax.device_put(self._lengths, ls)

    def shard_loads(self) -> List[int]:
        """Occupied-slot count per shard (admission placement telemetry)."""
        per = self.capacity // self.n_shards
        return [sum(self._slots[k] is not None
                    for k in range(s * per, (s + 1) * per))
                for s in range(self.n_shards)]

    @property
    def n_active(self) -> int:
        return sum(s is not None for s in self._slots)

    @property
    def n_free(self) -> int:
        return self.capacity - self.n_active

    @property
    def has_pending(self) -> bool:
        """Chunked mode: retired sessions (or streamed chunks) whose host
        fetch is still outstanding (resolved by the next ``step_chunk``,
        ``tick`` or ``flush``)."""
        with self._state_lock:
            return bool(self._pending or self._pending_partials
                        or self._partials)

    @property
    def has_retirable(self) -> bool:
        """Sessions that can retire (or be reaped) without another
        dispatch: finished-and-fully-consumed streams, and cancellations
        awaiting their boundary."""
        return any(s is not None and (s.done or s.cancelled)
                   for s in self._slots)

    # -- admission -----------------------------------------------------------

    def admit(self, request: StreamRequest, now: int,
              arrival_wall: Optional[float] = None) -> bool:
        """Attach `request` (a complete utterance) to a free slot; False if
        the pool is full.  Raises ValueError if the utterance could never
        fit the frame buffers (``max_buffer_frames``)."""
        if request.n_frames == 0:
            raise ValueError(f"request {request.req_id} has no frames")
        feats = validated_frames(request.feats, request.req_id)
        return self._bind(request.req_id, request.arrival_step, now, feats,
                          total=request.n_frames, arrival_wall=arrival_wall)

    def admit_stream(self, req_id: int, now: int,
                     feats: Optional[np.ndarray] = None,
                     arrival_step: Optional[int] = None,
                     arrival_wall: Optional[float] = None) -> bool:
        """Admit a session whose utterance is still arriving; False if the
        pool is full.  ``feats`` optionally carries the frames received so
        far; more arrive via ``append_frames`` and ``finish_stream`` closes
        the utterance.  The session idles (masked out, free) whenever it
        has consumed everything received."""
        feats = (np.zeros((0, self.engine.input_dim), np.float32)
                 if feats is None else validated_frames(feats, req_id))
        return self._bind(req_id, now if arrival_step is None else
                          arrival_step, now, feats, total=None,
                          arrival_wall=arrival_wall)

    def _bind(self, req_id: int, arrival_step: int, now: int,
              feats: np.ndarray, total: Optional[int],
              arrival_wall: Optional[float]) -> bool:
        if req_id in self._by_req:
            raise ValueError(f"request {req_id} is already in the pool")
        if feats.size and feats.shape[-1] != self.engine.input_dim:
            raise ValueError(
                f"request {req_id}: feature dim {feats.shape[-1]} != "
                f"engine input dim {self.engine.input_dim}")
        n = int(feats.shape[0])
        if max(n, total or 0) > self.max_buffer_frames:
            raise ValueError(
                f"request {req_id}: utterance of {max(n, total or 0)} frames "
                f"exceeds the frame-buffer growth limit "
                f"(max_buffer_frames={self.max_buffer_frames}); split the "
                f"stream or build the pool with a larger limit")
        k = self._pick_slot()
        if k is None:
            return False
        wall = (time.perf_counter() if arrival_wall is None
                else arrival_wall)
        self._slots[k] = _Session(
            req_id=req_id, arrival_step=arrival_step,
            admit_step=now, arrival_wall=wall,
            admit_wall=time.perf_counter(), total=total,
            n_recv=n, last_step=now - 1)
        self._by_req[req_id] = k
        # host-side staging only; the device upload happens once
        # per admission wave, at the next step/chunk boundary.
        # Zero-length stagings still clear the slot's stale device
        # length from its previous occupant.
        self._staged.append((k, feats))
        self._adm_since_fold += 1
        if self.obs is not None:
            self.obs.fold_admissions(1)
        return True

    def _pick_slot(self) -> Optional[int]:
        """Device-aware slot placement: the first free slot on the
        least-loaded shard (ties toward the lower shard index), so
        admissions spread evenly across devices instead of filling shard
        0 first and leaving the others' slot blocks masked idle.
        Unsharded pools (n_shards == 1) keep the first-free policy —
        identical slot assignment to the pre-sharding pool."""
        if self.n_shards <= 1:
            for k, s in enumerate(self._slots):
                if s is None:
                    return k
            return None
        per = self.capacity // self.n_shards
        best_k, best_load = None, per + 1
        for s in range(self.n_shards):
            free_k, load = None, 0
            for k in range(s * per, (s + 1) * per):
                if self._slots[k] is None:
                    if free_k is None:
                        free_k = k
                else:
                    load += 1
            if free_k is not None and load < best_load:
                best_k, best_load = free_k, load
        return best_k

    def _live(self, req_id: int) -> _Session:
        if req_id not in self._by_req:
            raise KeyError(f"request {req_id} is not in the pool")
        sess = self._slots[self._by_req[req_id]]
        assert sess is not None
        return sess

    def append_frames(self, req_id: int, feats: np.ndarray) -> None:
        """Stage additional frames for a live streaming session (uploaded
        in one jitted wave at the next boundary)."""
        sess = self._live(req_id)
        if sess.total is not None:
            raise ValueError(f"request {req_id} is already finished")
        if sess.cancelled:
            raise ValueError(f"request {req_id} was cancelled")
        feats = validated_frames(feats, req_id)
        if feats.ndim != 2 or feats.shape[-1] != self.engine.input_dim:
            raise ValueError(
                f"request {req_id}: appended frames must be [n, "
                f"{self.engine.input_dim}], got {feats.shape}")
        if feats.shape[0] == 0:
            return
        new_total = sess.n_recv + int(feats.shape[0])
        if new_total > self.max_buffer_frames:
            raise ValueError(
                f"request {req_id}: appending {feats.shape[0]} frames would "
                f"reach {new_total} frames, past the frame-buffer growth "
                f"limit (max_buffer_frames={self.max_buffer_frames})")
        self._staged_appends.append(
            (self._by_req[req_id], sess.n_recv, feats))
        sess.n_recv = new_total

    def finish_stream(self, req_id: int) -> None:
        """No more frames: the session retires once it has consumed
        everything received (possibly without another dispatch)."""
        sess = self._live(req_id)
        if sess.total is None:
            sess.total = sess.n_recv

    def cancel(self, req_id: int) -> None:
        """Abandon a session: its slot frees at the next boundary and no
        result is produced.  Also covers the retirement window — a
        session that already finished inside an in-flight chunk (its
        device-side snapshot taken, the one-chunk-later host fetch still
        outstanding) is suppressed at resolve time, so a cancel can never
        race the double buffer into delivering a dead session's logits.
        Raises KeyError only for a request the pool has no trace of."""
        if req_id in self._by_req:
            sess = self._slots[self._by_req[req_id]]
            assert sess is not None
            if not sess.cancelled and self.obs is not None:
                self.obs.fold_cancelled(1)
            sess.cancelled = True
            return
        with self._state_lock:
            pending = list(self._pending)
        for p in pending:
            for sess in p.sessions:
                if sess.req_id == req_id:
                    if not sess.cancelled and self.obs is not None:
                        self.obs.fold_cancelled(1)
                    sess.cancelled = True
                    return
        raise KeyError(f"request {req_id} is not in the pool")

    def pause_partials(self, req_id: int) -> None:
        """Stop snapshotting partial-logit chunks for one live session (a
        lagging consumer): its frames keep advancing and its logits keep
        banking in the device output buffer, but no further per-chunk
        host copies are made for it until ``resume_partials``.  The
        missed range stays recoverable via ``peek_rows`` (or the final
        ``RequestResult``) — this is the pool half of the async server's
        bounded-queue slow-consumer policy.  Chunked pools only: the
        per-frame path has no logits bank to backfill from, so pausing
        there would silently drop rows."""
        if not self.chunk_frames:
            raise RuntimeError("pause_partials requires a chunked pool "
                               "(chunk_frames >= 1)")
        self._live(req_id).partials_paused = True

    def resume_partials(self, req_id: int) -> None:
        """Re-enable per-chunk partial snapshots for a live session (the
        consumer drained; the caller backfills the gap via ``peek_rows``)."""
        if not self.chunk_frames:
            raise RuntimeError("resume_partials requires a chunked pool "
                               "(chunk_frames >= 1)")
        self._live(req_id).partials_paused = False

    def peek_rows(self, req_id: int, t0: int = 0) -> np.ndarray:
        """Fetch a live session's banked logits rows ``[t0, cursor)`` from
        the device output buffer (chunked mode only).

        This is the slow-consumer backfill path: rows the partial stream
        skipped while the session was paused are still in the logits bank
        (it holds the whole utterance until retirement), so a consumer
        that drains late pays one catch-up fetch instead of the server
        having buffered every skipped chunk host-side.  The fetch syncs
        on the in-flight chunk (the rows include frames it is writing) —
        an explicitly rare, caller-initiated sync, not a steady-state one.
        """
        if not self.chunk_frames:
            raise RuntimeError("peek_rows requires a chunked pool "
                               "(chunk_frames >= 1)")
        sess = self._live(req_id)
        hi = sess.cursor
        if t0 >= hi:
            return np.zeros((0, self.engine.n_classes), np.float32)
        # Same discipline as ``measured_sparsity``: the lock keeps an
        # offloaded tick from donating ``self._out`` away mid-fetch (the
        # PR 6 deleted-buffer race, this time on the logits bank).
        with self._state_lock:
            return np.asarray(self._out[self._by_req[req_id], t0:hi])

    def _reap_cancelled(self) -> None:
        """Free cancelled sessions' slots and drop their staged uploads
        (called at every boundary, before masks are computed)."""
        dead = [k for k, s in enumerate(self._slots)
                if s is not None and s.cancelled]
        if not dead:
            return
        gone = set(dead)
        for k in dead:
            sess = self._slots[k]
            del self._by_req[sess.req_id]
            self._slots[k] = None
        self._staged = [(k, f) for k, f in self._staged if k not in gone]
        self._staged_appends = [(k, st, f) for k, st, f in
                                self._staged_appends if k not in gone]

    # -- device upload staging ----------------------------------------------

    def _merged_appends(self) -> List[Tuple[int, int, np.ndarray]]:
        """Coalesce staged append blocks per slot (they are contiguous by
        construction) so the wave carries one entry per slot."""
        merged: Dict[int, Tuple[int, List[np.ndarray]]] = {}
        for k, start, feats in self._staged_appends:
            if k in merged:
                merged[k][1].append(feats)
            else:
                merged[k] = (start, [feats])
        return [(k, start, np.concatenate(blocks) if len(blocks) > 1
                 else blocks[0]) for k, (start, blocks) in merged.items()]

    def _grow_buffers(self, t_need: int) -> None:
        """ONE device-side realloc straight to ``t_need``'s pow2 bucket;
        resident slots' frames are copied device->device, never re-staged
        from host (regression-tested in tests/test_chunked_serving.py)."""
        old_t = self._t_buf
        new_t = _frame_bucket(t_need, floor=old_t)
        grown = jnp.zeros((self.capacity, new_t, self.engine.input_dim),
                          jnp.float32)
        if self._mesh is not None:
            grown = shardlib.shard_slot_array(grown, self._mesh)
        # lint: allow(eager-scatter) one-time realloc
        self._frames = grown.at[:, :old_t, :].set(self._frames)
        if self._out is not None:
            out = jnp.zeros((self.capacity, new_t + self.chunk_frames,
                             self.engine.n_classes), jnp.float32)
            if self._mesh is not None:
                out = shardlib.shard_slot_array(out, self._mesh)
            self._out = out.at[  # lint: allow(eager-scatter) one-time realloc
                :, :old_t + self.chunk_frames, :].set(self._out)
        self._t_buf = new_t
        self.n_frame_grows += 1

    def _flush_uploads(self) -> None:
        """One batched H2D copy of every utterance admitted — and every
        frame block appended — since the last step (the whole admission
        wave: [R, T_buf, D] in one ``device_put`` + one jitted scatter,
        with R bucketed to a power of two so at most log2(capacity)
        variants ever compile; appends go in a second [R, A, D] wave).

        The only host->device bytes are the new frames themselves: when a
        long utterance outgrows the bucket, the frame slab is reallocated
        ONCE, straight to the needed bucket, and the resident slots'
        frames are copied device->device — never re-staged from host.
        Growth recompiles the step for the new bucket, so drivers pre-size
        ``max_frames`` to the longest known utterance."""
        self._fire("admission_upload")
        appends = self._merged_appends()
        a_pad = (_frame_bucket(max(f.shape[0] for _, _, f in appends),
                               floor=1) if appends else 0)
        t_need = max(
            [f.shape[0] for _, f in self._staged] +
            [start + a_pad for _, start, _ in appends] + [0])
        # The upload scatters DONATE ``self._frames``/``self._lengths``
        # (and a growth rebinds them): the same deleted-buffer hazard as
        # the step dispatch, against a concurrent checkpoint snapshot or
        # admin scrape holding a stale reference — so the whole
        # rebind sequence holds the state lock (the guarded-by checker
        # enforces this; the staged host lists stay driver-thread-only).
        with self._state_lock:
            if t_need > self._t_buf:
                self._grow_buffers(t_need)
            if self._staged:
                rb = _frame_bucket(len(self._staged), floor=1)
                rows = np.zeros((rb, self._t_buf, self.engine.input_dim),
                                np.float32)
                slots = np.full((rb,), self.capacity, np.int32)  # OOB: drop
                ts = np.zeros((rb,), np.int32)
                for i, (k, feats) in enumerate(self._staged):
                    rows[i, :feats.shape[0]] = feats  # zero tail clears stale
                    slots[i] = k
                    ts[i] = feats.shape[0]
                self._staged.clear()
                self._tally(upload_frames=int(ts.sum()),
                            upload_frame_slots=rb * self._t_buf,
                            upload_bytes=rows.nbytes)
                self._frames, self._lengths = _device_upload(
                    self._frames, self._lengths, jax.device_put(rows),
                    slots, ts)
            if appends:
                rb = _frame_bucket(len(appends), floor=1)
                rows = np.zeros((rb, a_pad, self.engine.input_dim),
                                np.float32)
                slots = np.full((rb,), self.capacity, np.int32)
                starts = np.zeros((rb,), np.int32)
                ts = np.zeros((rb,), np.int32)
                for i, (k, start, feats) in enumerate(appends):
                    rows[i, :feats.shape[0]] = feats
                    slots[i] = k
                    starts[i] = start
                    ts[i] = start + feats.shape[0]
                self._staged_appends.clear()
                self._tally(upload_frames=int((ts - starts).sum()),
                            upload_frame_slots=rb * a_pad,
                            upload_bytes=rows.nbytes)
                self._frames, self._lengths = _device_append(
                    self._frames, self._lengths, jax.device_put(rows), slots,
                    starts, ts)
            self._ensure_slot_sharding()

    def _masks(self) -> Tuple[np.ndarray, np.ndarray]:
        """active = occupied AND has unconsumed frames (a starved streaming
        session rides along masked out); reset = admitted since the last
        dispatch (applied even if the slot starts starved)."""
        active = np.zeros((self.capacity,), bool)
        reset = np.zeros((self.capacity,), bool)
        for k, sess in enumerate(self._slots):
            if sess is None:
                continue
            active[k] = sess.available > 0
            reset[k] = sess.needs_reset
        return active, reset

    # -- per-frame tick loop -------------------------------------------------

    def step(self, now: int) -> List[RequestResult]:
        """Advance every active session one frame (one jitted call).
        Returns the requests that finished on this tick."""
        if self.chunk_frames:
            raise RuntimeError(
                "this pool was built with chunk_frames >= 1; "
                "drive it with step_chunk()/flush(), not step()")
        self._reap_cancelled()
        active, reset = self._masks()
        if not active.any():
            return []
        with self._tracer.span("admission_upload", self._boundary):
            self._flush_uploads()
        self._fire("dispatch")

        with self._tracer.span("dispatch") as disp, self._state_lock:
            self.state, logits = self.engine.step_frames(
                self.state, self._frames, self._dev1d(active),
                self._dev1d(reset))
        self.n_dispatches += 1
        with self._tracer.span("snapshot_fetch", self._boundary):
            logits_np = np.asarray(logits)      # ONE device->host fetch/tick
        self._tally(fetch_rows=logits_np.shape[0],
                    fetch_rows_kept=int(active.sum()),
                    fetch_bytes=logits_np.nbytes)

        finished: List[RequestResult] = []
        for k, sess in enumerate(self._slots):
            if sess is None:
                continue
            sess.needs_reset = False
            if not active[k]:
                continue                        # starved: rode along masked
            row = logits_np[k].copy()           # detach from the batch row
            sess.rows.append(row)
            if not sess.first_logit_wall:
                sess.first_logit_wall = time.perf_counter()
            if self.stream_partials:
                self._partials.append(PartialLogits(
                    req_id=sess.req_id, t0=sess.cursor, rows=row[None]))
            sess.cursor += 1
            sess.last_step = now
            if sess.done:
                finished.append(sess.result(np.stack(sess.rows)))
                self._free(k)
        if self.obs is not None:
            self.obs.fold_results(finished)
            self._fold_boundary(
                n_active=int(active.sum()), frames=int(active.sum()),
                dispatch_s=disp.seconds,
                chunk_s=time.perf_counter() - disp.t0,
                overlap=0.0, retirements=len(finished))
        return finished

    def _free(self, k: int) -> None:
        sess = self._slots[k]
        if sess is not None:
            del self._by_req[sess.req_id]
        self._slots[k] = None

    # -- chunked tick loop ---------------------------------------------------

    def max_chunk_advance(self) -> int:
        """Ticks the next ``step_chunk`` will consume: min(chunk_frames,
        most unconsumed frames any session holds).  0 when every session
        is starved (or none is active)."""
        rem = [s.available for s in self._slots if s is not None]
        return min(self.chunk_frames, max(rem)) if rem else 0

    def _chunk_len(self) -> int:
        """Scan length for the next chunk dispatch: the pow2 bucket of the
        actual advance, capped at chunk_frames.  Tail chunks therefore run
        a shorter scan instead of C mostly-masked iterations, and the jit
        compiles at most log2(C) variants."""
        adv = self.max_chunk_advance()
        return min(self.chunk_frames, _frame_bucket(adv, floor=1))

    def step_chunk(self, now: int) -> List[RequestResult]:
        """Advance every active session up to ``chunk_frames`` frames in
        ONE device dispatch, double-buffered.

        Returns the results of sessions that retired in the PREVIOUS
        chunk: their device->host logits fetch happens here, overlapped
        with the chunk just dispatched (JAX async dispatch returns before
        the device finishes).  Sessions finishing in THIS chunk have their
        output-buffer rows sliced off device-side now — before the next
        dispatch donates the buffer away — and surface on the next
        ``step_chunk``/``flush`` call.  With ``stream_partials`` every
        advancing session's chunk rows are snapshotted and surface as
        ``PartialLogits`` (``take_partials``) on the same one-chunk-later
        cadence.  Call ``flush()`` after the last chunk to collect the
        tail."""
        if not self.chunk_frames:
            raise RuntimeError(
                "this pool was built with chunk_frames=0; use step()")
        self._reap_cancelled()
        self._queue_done_retirements()
        active, reset = self._masks()
        if not active.any():
            return self.flush()
        n = self._chunk_len()
        starts = np.array([0 if s is None else s.cursor
                           for s in self._slots], np.int32)
        with self._tracer.span("admission_upload", self._boundary):
            self._flush_uploads()
        self._fire("dispatch")

        with self._tracer.span("dispatch") as disp, self._state_lock:
            self.state, self._out = self.engine.step_chunk(
                self.state, self._frames, self._lengths, self._dev1d(active),
                self._dev1d(reset), self._out, n_frames=n)
        self.n_dispatches += 1

        # ---- everything below overlaps the in-flight device chunk ----
        retiring: List[_Session] = []
        slots: List[int] = []
        partial_entries: List[Tuple[_Session, int, int, int]] = []
        frames_this = 0
        for k, sess in enumerate(self._slots):
            if sess is None:
                continue
            sess.needs_reset = False
            adv = min(n, sess.available)
            if adv <= 0:
                continue
            frames_this += adv
            sess.cursor += adv
            sess.last_step = now + adv - 1
            if self.stream_partials and not sess.partials_paused:
                partial_entries.append((sess, k, int(starts[k]), adv))
            if sess.done:
                retiring.append(sess)
                slots.append(k)
                self._free(k)
        newly: List[_PendingChunk] = []
        newly_partials: List[_PendingPartials] = []
        if retiring or partial_entries:
            with self._tracer.span("retire_snapshot", self._boundary), \
                    self._state_lock:
                if retiring:
                    # gather the retirees' rows NOW: dispatched against
                    # this chunk's output before the next step_chunk
                    # donates it, detaching the rows device-side; the
                    # host fetch waits one more chunk.
                    newly.append(self._gather_retirees(retiring, slots))
                if partial_entries:
                    # likewise for the streamed chunk rows — but only this
                    # chunk's [B, n, n_classes] window, not the whole
                    # buffer:
                    newly_partials.append(_PendingPartials(
                        entries=partial_entries,
                        rows=self.engine.snapshot_chunk(self._out,
                                                        self._dev1d(starts),
                                                        n_frames=n)))
        with self._tracer.span("snapshot_fetch", self._boundary) as fetch:
            finished = self._resolve()       # syncs on the PREVIOUS chunk
        with self._state_lock:
            self._pending.extend(newly)
            self._pending_partials.extend(newly_partials)

        wall = fetch.t1 - disp.t0
        overlap = 0.0
        if wall > 0:
            # fraction of this call's wall time spent doing useful host
            # work AFTER the dispatch returned — retirement bookkeeping,
            # the snapshot dispatch, and the previous chunk's logits
            # fetch — all concurrent with the device executing this chunk.
            overlap = (fetch.t1 - disp.t1) / wall
            self._overlap_sum += overlap
            self._overlap_n += 1
        if self.obs is not None:
            self._fold_boundary(
                n_active=int(active.sum()), frames=frames_this,
                dispatch_s=disp.seconds, chunk_s=wall,
                overlap=overlap, retirements=len(finished))
        return finished

    def _queue_done_retirements(self) -> None:
        """Retire sessions that are already done WITHOUT another dispatch
        (a stream finished after its last received frame was consumed, or
        finished with zero frames): gather their banked rows now; the
        results surface at the next resolve like any other retirement."""
        retiring: List[_Session] = []
        slots: List[int] = []
        for k, sess in enumerate(self._slots):
            if sess is not None and sess.done:
                retiring.append(sess)
                slots.append(k)
                self._free(k)
        if retiring:
            with self._tracer.span("retire_snapshot", self._boundary), \
                    self._state_lock:
                self._pending.append(self._gather_retirees(retiring, slots))

    def _gather_retirees(self, retiring: List[_Session],
                         slots: List[int]) -> _PendingChunk:
        """Dispatch ``ceil(len(slots) / R)`` gathers of the retiring slots'
        rows out of ``self._out``, R slots each; the last block's spare
        indices repeat its first slot, and those rows are never read.
        Caller holds ``_state_lock``."""
        r = min(RETIRE_BLOCK, self.capacity)
        blocks: List[jax.Array] = []
        where: List[Tuple[int, int]] = []
        for lo in range(0, len(slots), r):
            block = slots[lo:lo + r]
            idx = np.full((r,), block[0], np.int32)
            idx[:len(block)] = block
            where += [(len(blocks), j) for j in range(len(block))]
            blocks.append(self.engine.retire_rows(self._out, idx))
        self._tally(retire_gathers=len(blocks))
        return _PendingChunk(sessions=retiring, where=where, blocks=blocks)

    def flush(self) -> List[RequestResult]:
        """Resolve retirements (and streamed partials) still pending from
        the last dispatched chunk (the double-buffer tail)."""
        if self.chunk_frames:
            self._reap_cancelled()
            self._queue_done_retirements()
        with self._tracer.span("snapshot_fetch", self._boundary):
            return self._resolve()

    def tick(self, now: int) -> Tuple[List[RequestResult], int]:
        """Non-blocking driver entry: at most one dispatch, in either mode.

        Returns ``(finished_results, frames_advanced)``.  Safe to call
        with nothing to do (returns ``([], 0)``); handles cancellations,
        dispatch-free retirements and the double-buffer tail.  The call
        does not wait for the device — the only host sync is the previous
        chunk's one-copy logits fetch (per-frame mode syncs on its own
        logits, as always)."""
        if self.chunk_frames:
            adv = self.max_chunk_advance()
            if adv:
                return self.step_chunk(now), adv
            return self.flush(), 0
        self._reap_cancelled()
        finished: List[RequestResult] = []
        # dispatch-free retirements (finished streams with nothing left):
        for k, sess in enumerate(self._slots):
            if sess is not None and sess.done:
                finished.append(sess.result(
                    np.stack(sess.rows) if sess.rows else np.zeros(
                        (0, self.engine.n_classes), np.float32)))
                self._free(k)
        if self.obs is not None:
            self.obs.fold_results(finished)
        active, _ = self._masks()
        if active.any():
            return finished + self.step(now), 1
        return finished, 0

    def take_partials(self) -> List[PartialLogits]:
        """Drain the streamed per-chunk logits resolved so far (in frame
        order per session; ``stream_partials`` only)."""
        out, self._partials = self._partials, []
        return out

    def _resolve(self) -> List[RequestResult]:
        self._resolve_partials()
        return self._resolve_pending()

    def _resolve_partials(self) -> None:
        with self._state_lock:
            pend, self._pending_partials = self._pending_partials, []
        if not pend:
            return
        for p in pend:
            rows = self._fetch(p.rows)         # ONE fetch per chunk
            kept = 0
            for sess, k, t0, adv in p.entries:
                if sess.cancelled:
                    continue                   # cancelled mid-window
                if not sess.first_logit_wall:
                    sess.first_logit_wall = time.perf_counter()
                self._partials.append(PartialLogits(
                    req_id=sess.req_id, t0=t0, rows=rows[k, :adv].copy()))
                kept += adv
            self._tally(fetch_rows_kept=kept)

    def _fetch(self, rows: jax.Array) -> np.ndarray:
        """Device-to-host copy of one snapshot, timed in two spans: the
        wait for the device to finish it, then the copy itself."""
        with self._tracer.span("fetch_wait", self._boundary):
            jax.block_until_ready(rows)
        with self._tracer.span("fetch_copy", self._boundary):
            host = np.asarray(rows)
        self._tally(fetch_rows=host.shape[0] * host.shape[1],
                    fetch_bytes=host.nbytes)
        return host

    def _resolve_pending(self) -> List[RequestResult]:
        with self._state_lock:
            pend, self._pending = self._pending, []
        if not pend:
            return []
        out: List[RequestResult] = []
        for p in pend:
            blocks = [self._fetch(b) for b in p.blocks]  # ONE fetch a block
            kept = 0
            for sess, (b, j) in zip(p.sessions, p.where):
                if sess.cancelled:
                    continue   # cancelled inside the retirement window:
                    #            the rows are dropped, never delivered
                out.append(sess.result(blocks[b][j, :sess.cursor].copy()))
                kept += sess.cursor
            self._tally(fetch_rows_kept=kept)
        if self.obs is not None:
            self.obs.fold_results(out)
        return out

    def _fold_boundary(self, *, n_active: int, frames: int,
                       dispatch_s: float, chunk_s: float, overlap: float,
                       retirements: int) -> None:
        """One dispatch boundary's fold into the observability layer —
        host values only, plus the (device, un-fetched) telemetry-totals
        dispatch that the NEXT boundary's fold will diff."""
        adm, self._adm_since_fold = self._adm_since_fold, 0
        # the totals reduction reads ``self.state``: take the lock so an
        # interleaved reader/dispatch cannot hand it a deleted buffer.
        with self._state_lock:
            totals = self.engine.telemetry_totals(self.state)
        self.obs.fold_chunk(
            occupancy=self.n_active,
            capacity=self.capacity,
            n_active=n_active,
            frames_advanced=frames,
            dispatch_s=dispatch_s,
            chunk_s=chunk_s,
            host_overlap_frac=overlap,
            admissions=adm,
            retirements=retirements,
            shard_loads=self.shard_loads(),
            telemetry_totals=totals,
        )

    def mean_host_overlap_frac(self) -> float:
        return self._overlap_sum / self._overlap_n if self._overlap_n \
            else 0.0

    def drain(self, now: int) -> List[RequestResult]:
        """Evict every in-flight session, returning truncated
        ``RequestResult``s with the logits produced so far (used when
        ``serve_requests`` hits ``max_steps`` mid-stream, so partial work is
        surfaced instead of silently dropped).  In chunked mode the
        already-finished (pending-fetch) sessions are resolved first, then
        partial sessions' rows are read from the device output buffer —
        truncation granularity is the chunk."""
        n_classes = self.engine.n_classes
        self._staged.clear()    # evicted sessions' uploads must not land
        self._staged_appends.clear()
        self._reap_cancelled()
        out: List[RequestResult] = self._resolve()
        drained: List[RequestResult] = []
        with self._state_lock:
            for k, sess in enumerate(self._slots):
                if sess is None:
                    continue
                if self.chunk_frames:
                    logits = (np.asarray(self._out[k, :sess.cursor])
                              if sess.cursor
                              else np.zeros((0, n_classes), np.float32))
                else:
                    logits = (np.stack(sess.rows) if sess.rows
                              else np.zeros((0, n_classes), np.float32))
                drained.append(sess.result(logits, truncated=not sess.done,
                                           finish_step=now))
                self._free(k)
        if self.obs is not None:
            self.obs.fold_results(drained)
        return out + drained

    def measured_sparsity(self) -> Dict[str, float]:
        # Thread-safe against an in-flight offloaded tick: holding the
        # lock keeps the next dispatch from donating ``self.state`` out
        # from under the host fetch (the fetch itself may block until the
        # current chunk completes, which is the intended sync point).
        with self._state_lock:
            return self.engine.measured_sparsity(self.state)

    def bytes_per_slot(self) -> float:
        """Device bytes held per resident session: the slot's share of the
        recurrent-state slabs (incl. telemetry and cursors), its frame
        buffer row, its logits-bank row, and the per-slot share of the
        shared packed weights (``engine.weight_bytes() / capacity``).
        Pure shape arithmetic — no device sync.  Quantized packing
        (``EngineConfig.quant``) shrinks the weight term ~4x; the fp32
        session state is format-independent.  Folds the
        ``spartus_slot_bytes`` gauge when observability is attached."""
        def nbytes(a) -> int:
            return int(np.prod(a.shape)) * jnp.dtype(a.dtype).itemsize

        # Shape arithmetic only — but reading the slab references while a
        # concurrent dispatch donates-and-rebinds them can hand this loop
        # a deleted buffer whose ``.shape`` access throws (the PR 6 race,
        # audited here for the admin endpoint's ``stats()`` path).
        with self._state_lock:
            total = sum(nbytes(l)
                        for l in jax.tree_util.tree_leaves(self.state))
            total += nbytes(self._frames) + nbytes(self._lengths)
            if self._out is not None:
                total += nbytes(self._out)
        total += self.engine.weight_bytes()
        per_slot = total / self.capacity
        if self.obs is not None:
            self.obs.fold_slot_bytes(per_slot)
        return float(per_slot)

    # -- checkpoint / restore (serving/checkpoint.py) ------------------------

    def pool_config(self) -> Dict[str, object]:
        """Constructor kwargs that rebuild an equivalent (empty) pool —
        the watchdog's recovery recipe.  ``max_frames`` reports the
        CURRENT buffer bucket so the rebuilt pool needs no regrow (and
        therefore no step recompile) to receive the restored sessions."""
        return dict(
            capacity=self.capacity,
            max_frames=self._t_buf,
            chunk_frames=self.chunk_frames,
            max_buffer_frames=self.max_buffer_frames,
            stream_partials=self.stream_partials,
            n_devices=self._n_devices,
        )

    def snapshot(self):
        """In-memory whole-pool snapshot (``PoolCheckpoint``): every live
        session in one gathered D2H fetch.  Call ``flush()`` first if the
        double-buffer tail must be resolved rather than dropped."""
        from repro.serving import checkpoint as ckptlib

        return ckptlib.snapshot_pool(self)

    def snapshot_session(self, req_id: int):
        """Serialize one live session (``SessionSnapshot``) in a single
        gathered fetch of its slot's rows."""
        from repro.serving import checkpoint as ckptlib

        return ckptlib.snapshot_session(self, req_id)

    def restore_session(self, snap) -> bool:
        """Restore one ``SessionSnapshot`` into a free slot; False when
        the pool is full.  The session continues bit-identically — slot
        index, capacity and shard count are placement, not semantics."""
        from repro.serving import checkpoint as ckptlib

        return ckptlib.restore_session(self, snap)

    def checkpoint(self, path: str) -> List[RequestResult]:
        """Write the whole pool to a checkpoint directory (atomic,
        committed, retained — `training.checkpoint.CheckpointManager`).
        Flushes the double-buffer tail first and returns those finished
        results: completed sessions belong to the caller, not the file."""
        from repro.serving import checkpoint as ckptlib

        return ckptlib.save_pool(self, path)

    def restore(self, path: str, step: Optional[int] = None) -> None:
        """Load a pool checkpoint into THIS (fresh, empty) pool.  The
        shard count and capacity may differ from the writer's — this is
        the migration primitive for rebalancing and preemption recovery."""
        from repro.serving import checkpoint as ckptlib

        ckptlib.restore_into(self, ckptlib.load_checkpoint(path, step))


RequestLike = Union[StreamRequest, Tuple[int, np.ndarray]]


def _normalize(requests: Iterable[RequestLike]) -> List[StreamRequest]:
    out: List[StreamRequest] = []
    for i, r in enumerate(requests):
        if isinstance(r, StreamRequest):
            out.append(r)
        else:
            arrival, feats = r
            out.append(StreamRequest(req_id=i, arrival_step=int(arrival),
                                     feats=np.asarray(feats, np.float32)))
    return sorted(out, key=lambda r: (r.arrival_step, r.req_id))


def serve_requests(
    engine: BatchedSpartusEngine,
    requests: Iterable[RequestLike],
    capacity: int,
    max_steps: Optional[int] = None,
    chunk_frames: int = 0,
    n_devices: Optional[int] = None,
    observability: Optional[PoolObservability] = None,
) -> Tuple[List[RequestResult], ServeStats]:
    """Drive a request stream through a `SessionPool` to completion.

    requests: iterable of StreamRequest or `(arrival_step, feats [T, D])`.
    Admission is FIFO in arrival order; a request that finds the pool full
    waits (backpressure) and is admitted as soon as a slot frees.  Returns
    per-request results (logits + latency) and aggregate throughput stats.

    ``chunk_frames=C >= 1`` selects the chunked tick loop: one device
    dispatch advances all active sessions up to C frames, logits are
    banked on device and fetched once per session at retirement
    (double-buffered behind the next chunk), and admission happens at
    chunk boundaries — higher throughput (fewer dispatches/frame), up to
    C-1 ticks of extra queueing latency.  ``chunk_frames=0`` (default)
    keeps the per-frame path, which is the chunked path's parity oracle.

    If ``max_steps`` stops the run early, in-flight sessions are drained
    into ``RequestResult``s with ``truncated=True`` holding their partial
    logits (never-admitted requests have no partial logits and are simply
    absent from the results); ``stats.truncated`` flags the cut — in
    chunked mode the cut lands on the first chunk boundary at or past
    ``max_steps``, so partial logits come in chunk granularity.
    ``total_steps`` counts only ticks that advanced at least one slot, so
    frames/step utilisation is not diluted by idle fast-forward ticks.

    ``n_devices=N`` shards the pool's slot dimension over N devices
    (`SessionPool(n_devices=...)`): same API, same results, one SPMD
    dispatch per tick across all devices.

    ``observability=PoolObservability(...)`` attaches the live metrics /
    time-series / tracing layer (serving/metrics.py): every dispatch
    boundary is folded into its registry and ring buffer, at zero added
    host syncs.  Results and throughput are identical with it on or off.
    """
    pending = deque(_normalize(requests))
    n_requests = len(pending)
    # pre-size the device frame buffers to the longest utterance so no
    # mid-run bucket growth (= recompile) can happen:
    max_frames = max((r.n_frames for r in pending), default=1)
    pool = SessionPool(
        engine, capacity, max_frames=max_frames, chunk_frames=chunk_frames,
        max_buffer_frames=max(max_frames, DEFAULT_MAX_BUFFER_FRAMES),
        n_devices=n_devices, observability=observability)
    waiting: deque[Tuple[StreamRequest, float]] = deque()
    results: List[RequestResult] = []
    now = 0
    total_steps = 0
    truncated = False
    t0 = time.perf_counter()

    while pending or waiting or pool.n_active or pool.has_pending:
        # fast-forward over idle time to the next arrival:
        if not waiting and not pool.n_active and pending:
            now = max(now, pending[0].arrival_step)
        while pending and pending[0].arrival_step <= now:
            waiting.append((pending.popleft(), time.perf_counter()))
        while waiting and pool.n_free:
            req, arr_wall = waiting.popleft()
            pool.admit(req, now, arrival_wall=arr_wall)
        # count only ticks that advance >= 1 slot: the arrival fast-forward
        # above makes idle iterations rare, but total_steps feeds per-step
        # utilisation metrics and must stay exact if the loop ever changes
        # (e.g. wall-clock-paced ticking instead of fast-forward).
        if chunk_frames:
            adv = pool.max_chunk_advance()
            results.extend(pool.step_chunk(now) if adv else pool.flush())
            total_steps += adv
            now += max(adv, 1)
        else:
            dispatched = pool.n_active > 0
            results.extend(pool.step(now))
            if dispatched:
                total_steps += 1
            now += 1
        if max_steps is not None and total_steps >= max_steps:
            truncated = bool(pending or waiting or pool.n_active)
            results.extend(pool.drain(now - 1))
            break

    wall = time.perf_counter() - t0
    if observability is not None:
        observability.flush_totals()
    results.sort(key=lambda r: r.req_id)
    stats = aggregate_stats(
        results,
        capacity=capacity,
        n_requests=n_requests,
        total_steps=total_steps,
        wall_s=wall,
        sparsity=pool.measured_sparsity(),
        truncated=truncated,
        chunk_frames=chunk_frames,
        n_dispatches=pool.n_dispatches,
        host_overlap_frac=pool.mean_host_overlap_frac(),
        bytes_per_slot=pool.bytes_per_slot(),
    )
    return results, stats
