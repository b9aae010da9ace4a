"""Robustness suite: checkpoint/restore, fault injection, degradation.

Three pillars, pinned to BIT-identical logits (``np.array_equal``, not
allclose) wherever before/after run the SAME compiled program on the
same f32 state — then there is nothing to round.  The two documented
exceptions fall back to the repo's 1e-5 oracle tolerance: capacity-1
pools (a different XLA program than the batch-1 engine) and cross-
shard-count migration (rows straddle two differently-partitioned
programs; the transferred *state* is still checked byte-for-byte):

* **Checkpoint/restore** (serving/checkpoint.py): a pool killed at a
  chunk boundary and restored — same shape, different capacity, or a
  different shard count — finishes every in-flight session with exactly
  the logits of an uninterrupted run.
* **Fault injection** (serving/faults.py): seeded deterministic
  `FaultPlan` s fire at named pool sites; every session that survives a
  fault bit-matches the fault-free run (no cross-session contamination).
* **Graceful degradation** (async_server.py): the driver watchdog
  rebuilds the pool after a crashed tick and resumes the salvageable
  sessions; overload sheds with a typed retriable error; idle sessions
  reap; the JSON-lines transport answers malformed traffic in-band with
  typed codes and never takes down a neighbouring stream.

Run sharded cases under
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` (the CI chaos
job does).
"""
import asyncio
import json
from collections import deque

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.launch.serve import MAX_LINE_BYTES, demo_client, handle_conn, jline
from repro.models import lstm_am
from repro.serving import (
    AdmissionShed,
    AsyncSpartusServer,
    Backoff,
    BadRequest,
    BatchedSpartusEngine,
    DriverRecovered,
    EngineConfig,
    FaultEvent,
    FaultInjector,
    FaultPlan,
    InjectedFault,
    PoolObservability,
    ProtocolError,
    ServingError,
    SessionTimeout,
    SpartusEngine,
    StreamRequest,
    error_payload,
)
from repro.serving import checkpoint as ckptlib
from repro.serving.scheduler import SessionPool, validated_frames

INPUT_DIM, HIDDEN, CLASSES = 20, 32, 11
GAMMA, M, THETA = 0.75, 4, 0.05
LENS = [5, 9, 3, 12, 1, 7]
N_DEV = jax.device_count()


@pytest.fixture(scope="module")
def model():
    cfg = lstm_am.LSTMAMConfig(input_dim=INPUT_DIM, hidden_dim=HIDDEN,
                               n_layers=2, n_classes=CLASSES)
    params = lstm_am.init_params(jax.random.key(0), cfg)
    return lstm_am.cbtd_prune_stacks(params, gamma=GAMMA, m=M), cfg


@pytest.fixture(scope="module")
def engines(model):
    params, cfg = model
    ecfg = EngineConfig(theta=THETA, gamma=GAMMA, m=M, capacity_frac=1.0)
    return (SpartusEngine(params, cfg, ecfg),
            BatchedSpartusEngine(params, cfg, ecfg))


def _utterance(key, t):
    return np.asarray(
        jax.random.normal(jax.random.key(key), (t, INPUT_DIM)), np.float32)


@pytest.fixture(scope="module")
def workload(engines):
    e1, _ = engines
    feats = [_utterance(300 + i, t) for i, t in enumerate(LENS)]
    refs = [np.asarray(e1.run_utterance(jnp.asarray(f))) for f in feats]
    return feats, refs


def _reqs(feats):
    return [StreamRequest(100 + i, 0, f) for i, f in enumerate(feats)]


def _drain(pool, pending, *, now=0, collected=None, max_iters=10_000):
    """Drive a pool to completion, retrying ticks that raise injected
    faults (the transient-infrastructure model: state is intact, the
    driver simply tries again).  Returns {req_id: logits}."""
    out = dict(collected or {})
    pending = deque(pending)
    for _ in range(max_iters):
        while pending and pool.n_free and pool.admit(pending[0], now):
            pending.popleft()
        if not (pending or pool.n_active or pool.has_pending):
            break
        try:
            finished, adv = pool.tick(now)
        except InjectedFault:
            continue
        for r in finished:
            out[r.req_id] = r.logits
        now += max(adv, 1)
    else:
        raise AssertionError("pool did not drain")
    for r in pool.flush():
        out[r.req_id] = r.logits
    return out


# -- the harness itself -------------------------------------------------------


def test_fault_plan_deterministic():
    a, b = FaultPlan.seeded(7), FaultPlan.seeded(7)
    assert a == b and len(a.events) == 4
    assert FaultPlan.seeded(8) != a
    plan = FaultPlan(events=(FaultEvent("dispatch", 5),
                             FaultEvent("dispatch", 1),
                             FaultEvent("preempt", 0)))
    assert [e.at for e in plan.events_for("dispatch")] == [1, 5]
    assert plan.with_events(FaultEvent("dispatch", 9)).events[-1].at == 9


def test_fault_injector_fires_once_at_scheduled_invocations():
    inj = FaultInjector(FaultPlan(events=(FaultEvent("dispatch", 2),)))
    inj.fire("dispatch")
    inj.fire("dispatch")
    with pytest.raises(InjectedFault) as ei:
        inj.fire("dispatch")
    assert ei.value.site == "dispatch" and ei.value.invocation == 2
    assert ei.value.retriable and ei.value.code == "injected"
    inj.fire("dispatch")                     # each event fires exactly once
    assert inj.count("dispatch") == 4 and len(inj.fired) == 1


def test_backoff_deterministic_and_bounded():
    a, b = Backoff(seed=3), Backoff(seed=3)
    delays = [a.delay(k) for k in range(8)]
    assert delays == [b.delay(k) for k in range(8)]
    for k, d in enumerate(delays):
        assert 0.0 <= d <= a.ceiling(k) <= a.cap_s
    assert a.ceiling(50) == a.cap_s          # capped, no overflow
    assert Backoff(seed=4).delay(3) != a.delay(3)


def test_error_payload_taxonomy():
    cases = [
        (BadRequest("nope"), "bad_request", False),
        (AdmissionShed(), "shed", True),
        (SessionTimeout("idle"), "timeout", True),
        (DriverRecovered("lost"), "retriable_internal", True),
        (ProtocolError("bad_json", "junk"), "bad_json", False),
        (InjectedFault("dispatch", 3), "injected", True),
        (ValueError("plain"), "bad_request", False),
        (RuntimeError("boom"), "internal", False),
    ]
    for exc, code, retriable in cases:
        p = error_payload(exc)
        assert p["code"] == code and p["retriable"] is retriable
        assert p["message"]
    assert error_payload(AdmissionShed(retry_after_ms=80))[
        "retry_after_ms"] == 80.0
    assert isinstance(BadRequest("x"), ValueError)   # pre-taxonomy callers
    assert isinstance(BadRequest("x"), ServingError)


# -- admission validation -----------------------------------------------------


def test_validated_frames_rejects_garbage():
    good = validated_frames(np.zeros((3, INPUT_DIM), np.float32), 1)
    assert good.dtype == np.float32 and good.shape == (3, INPUT_DIM)
    with pytest.raises(ValueError, match="NaN/Inf"):
        validated_frames(np.full((2, INPUT_DIM), np.nan), 1)
    with pytest.raises(ValueError, match="NaN/Inf"):
        validated_frames(np.full((2, INPUT_DIM), np.inf), 1)
    with pytest.raises(ValueError, match="dtype"):
        validated_frames(np.array([["a"] * INPUT_DIM]), 1)
    with pytest.raises(ValueError, match="feature dim"):
        validated_frames(np.zeros((2, 3), np.float32), 1,
                         input_dim=INPUT_DIM)


def test_rejected_admission_leaves_neighbours_bit_identical(
        engines, workload):
    """A poisoned admission fails ITS request; sessions admitted before
    and after produce exactly the fault-free logits."""
    _, eb = engines
    feats, refs = workload
    pool = SessionPool(eb, 2, max_frames=16, chunk_frames=4)
    assert pool.admit(StreamRequest(100, 0, feats[0]), 0)
    with pytest.raises(ValueError, match="NaN/Inf"):
        pool.admit(StreamRequest(999, 0,
                                 np.full((4, INPUT_DIM), np.nan)), 0)
    with pytest.raises(ValueError, match="dtype"):
        pool.admit(StreamRequest(998, 0, np.array([["x"] * INPUT_DIM])), 0)
    got = _drain(pool, [StreamRequest(101, 0, feats[1])])
    assert np.array_equal(got[100], refs[0])
    assert np.array_equal(got[101], refs[1])
    # incremental path: a bad append also fails cleanly
    pool2 = SessionPool(eb, 2, max_frames=16, chunk_frames=4)
    assert pool2.admit_stream(200, 0, feats=feats[2][:1])
    with pytest.raises(ValueError, match="NaN/Inf"):
        pool2.append_frames(200, np.full((2, INPUT_DIM), np.nan))


# -- checkpoint / restore -----------------------------------------------------


@pytest.mark.parametrize("capacity,chunk", [(2, 4), (4, 8), (3, 1)])
def test_checkpoint_restore_roundtrip_bit_identical(
        engines, workload, tmp_path, capacity, chunk):
    """Kill the pool mid-flight at a chunk boundary, restore from disk
    into a fresh pool, finish: every session's logits are bit-identical
    to the uninterrupted run — over 4-, 8- and one-frame chunks."""
    _, eb = engines
    feats, refs = workload
    pool = SessionPool(eb, capacity, max_frames=16, chunk_frames=chunk)
    got = {}
    pending = deque(_reqs(feats))
    now = 0
    for _ in range(3):                     # run a few boundaries...
        while pending and pool.n_free and pool.admit(pending[0], now):
            pending.popleft()
        finished, adv = pool.tick(now)
        for r in finished:                 # collect — retirements during
            got[r.req_id] = r.logits       # warm-up are results too
        now += max(adv, 1)
    # ...then "die": checkpoint returns the flushed double-buffer tail
    for r in pool.checkpoint(str(tmp_path / "ckpt")):
        got[r.req_id] = r.logits
    n_live = pool.n_active
    del pool                               # the process is gone
    pool2 = SessionPool(eb, capacity, max_frames=16, chunk_frames=chunk)
    pool2.restore(str(tmp_path / "ckpt"))
    assert pool2.n_active == n_live
    got = _drain(pool2, pending, now=now, collected=got)
    assert sorted(got) == [100 + i for i in range(len(feats))]
    for i in range(len(feats)):
        assert np.array_equal(got[100 + i], refs[i]), f"req {100 + i}"


def test_restore_into_different_capacity(engines, workload, tmp_path):
    """Capacity is placement, not semantics: restoring a 2-slot pool's
    checkpoint into a 5-slot pool continues bit-identically."""
    _, eb = engines
    feats, refs = workload
    pool = SessionPool(eb, 2, max_frames=16, chunk_frames=4)
    pending = deque(_reqs(feats[:4]))
    while pending and pool.n_free and pool.admit(pending[0], 0):
        pending.popleft()
    got = {r.req_id: r.logits for r in pool.tick(0)[0]}
    for r in pool.checkpoint(str(tmp_path / "ck")):
        got[r.req_id] = r.logits
    big = SessionPool(eb, 5, max_frames=16, chunk_frames=4)
    big.restore(str(tmp_path / "ck"))
    got = _drain(big, pending, now=4, collected=got)
    for i in range(4):
        assert np.array_equal(got[100 + i], refs[i])


@pytest.mark.skipif(N_DEV < 4, reason="needs 4 (emulated) devices")
@pytest.mark.parametrize("src_dev,dst_dev", [(None, 4), (4, None), (2, 4)])
def test_restore_across_shard_counts(engines, workload, tmp_path,
                                     src_dev, dst_dev):
    """The migration primitive: a checkpoint written at one shard count
    restores at another.  The state transfer is byte-identical — every
    array the destination pool holds after restore equals the file
    bit-for-bit — but end-to-end logits straddle two differently
    partitioned XLA programs (src's first chunk, dst's rest), so the
    numeric bar is the repo's 1e-5 oracle tolerance, same as
    test_sharded_serving.py."""
    _, eb = engines
    feats, refs = workload
    pool = SessionPool(eb, 4, max_frames=16, chunk_frames=4,
                       n_devices=src_dev)
    pending = deque(_reqs(feats[:4]))
    while pending and pool.n_free and pool.admit(pending[0], 0):
        pending.popleft()
    got = {r.req_id: r.logits for r in pool.tick(0)[0]}
    for r in pool.checkpoint(str(tmp_path / "mig")):
        got[r.req_id] = r.logits
    dst = SessionPool(eb, 4, max_frames=16, chunk_frames=4,
                      n_devices=dst_dev)
    dst.restore(str(tmp_path / "mig"))
    saved = {s.req_id: s for s in
             ckptlib.load_checkpoint(str(tmp_path / "mig")).sessions}
    for snap in ckptlib.snapshot_pool(dst).sessions:
        ref_snap = saved.pop(snap.req_id)
        assert snap.meta["cursor"] == ref_snap.meta["cursor"]
        for key, arr in ref_snap.arrays.items():
            assert np.array_equal(snap.arrays[key], arr), (snap.req_id, key)
    assert not saved
    got = _drain(dst, pending, now=4, collected=got)
    for i in range(4):
        np.testing.assert_allclose(got[100 + i], refs[i], atol=1e-5)


def test_single_session_snapshot_migrates(engines, workload):
    """One session snapshotted out of a busy pool and restored into a
    different pool (different capacity, different neighbours) continues
    bit-identically — per-slot computational independence."""
    _, eb = engines
    feats, refs = workload
    pool = SessionPool(eb, 4, max_frames=16, chunk_frames=4)
    for i in range(4):
        assert pool.admit(StreamRequest(100 + i, 0, feats[i]), 0)
    got = {r.req_id: r.logits for r in pool.tick(0)[0]}
    snap = pool.snapshot_session(101)
    assert snap.req_id == 101
    other = SessionPool(eb, 2, max_frames=16, chunk_frames=4)
    assert other.admit(StreamRequest(500, 0, feats[4]), 0)
    assert other.restore_session(snap)
    got.update(_drain(other, [], now=4))
    assert np.array_equal(got[101], refs[1])
    assert np.array_equal(got[500], refs[4])


def test_restore_guards(engines, model, workload, tmp_path):
    """Engine fingerprint mismatches and non-empty targets are refused
    loudly — a checkpoint is only valid against the weights/config that
    wrote it, and restore never silently merges into live sessions."""
    params, cfg = model
    _, eb = engines
    feats, _ = workload
    pool = SessionPool(eb, 2, max_frames=16, chunk_frames=4)
    assert pool.admit(StreamRequest(100, 0, feats[0]), 0)
    ckpt = pool.snapshot()
    assert ckpt.meta["engine"] == ckptlib.engine_fingerprint(eb)
    # duplicate req_id: single-session restore into a pool that already
    # serves it is refused
    with pytest.raises(ValueError, match="already in the pool"):
        pool.restore_session(pool.snapshot_session(100))
    # non-empty target
    with pytest.raises(ValueError, match="empty pool"):
        ckptlib.restore_into(pool, ckpt)
    # different engine config -> different fingerprint
    other = BatchedSpartusEngine(
        params, cfg, EngineConfig(theta=0.2, gamma=GAMMA, m=M,
                                  capacity_frac=1.0))
    mism = SessionPool(other, 2, max_frames=16, chunk_frames=4)
    with pytest.raises(ValueError, match="fingerprint"):
        ckptlib.restore_into(mism, ckpt)
    # nothing on disk
    with pytest.raises(FileNotFoundError):
        ckptlib.load_checkpoint(str(tmp_path / "nope"))


def test_preemption_cycles(engines, workload, tmp_path):
    """The 'preempt' site end-to-end, twice: kill the pool at a boundary,
    restore from the latest committed checkpoint, keep going.  Two
    preemptions deep, every session is still bit-identical."""
    _, eb = engines
    feats, refs = workload
    path = str(tmp_path / "preempt")
    pool = SessionPool(eb, 3, max_frames=16, chunk_frames=4)
    pending = deque(_reqs(feats))
    got = {}
    now = 0
    for cycle in range(2):
        for _ in range(2):
            while pending and pool.n_free and pool.admit(pending[0], now):
                pending.popleft()
            finished, adv = pool.tick(now)
            for r in finished:
                got[r.req_id] = r.logits
            now += max(adv, 1)
        for r in pool.checkpoint(path):
            got[r.req_id] = r.logits
        del pool                          # preempted
        pool = SessionPool(eb, 3, max_frames=16, chunk_frames=4)
        pool.restore(path)                # latest committed step
    got = _drain(pool, pending, now=now, collected=got)
    for i in range(len(feats)):
        assert np.array_equal(got[100 + i], refs[i]), f"req {100 + i}"


# -- chaos: injected pool faults ----------------------------------------------


@pytest.mark.parametrize("site,ats", [
    ("dispatch", (1, 3)),
    ("admission_upload", (0, 2)),
    ("dispatch", (0,)),
])
def test_pool_fault_retry_bit_identical(engines, workload, site, ats):
    """A plain injected fault at a pool site leaves device state intact
    (it fires BEFORE the dispatch donates); the driver retries the tick
    and every session finishes bit-identical to the fault-free run."""
    _, eb = engines
    feats, refs = workload
    inj = FaultInjector(FaultPlan(
        events=tuple(FaultEvent(site, at) for at in ats)))
    pool = SessionPool(eb, 3, max_frames=16, chunk_frames=4, faults=inj)
    got = _drain(pool, _reqs(feats))
    assert len(inj.fired) == len(ats)
    for i in range(len(feats)):
        assert np.array_equal(got[100 + i], refs[i]), f"req {100 + i}"


# -- chaos: async server degradation ------------------------------------------


@pytest.mark.parametrize("ats,n_devices", [
    ((1,), None),
    ((1, 3), None),
    ((2,), 4),
])
def test_watchdog_recovers_bit_identical(engines, workload, ats, n_devices):
    """The driver watchdog: an injected dispatch crash mid-service is
    absorbed — the pool is rebuilt from snapshots and EVERY session
    completes with exactly the fault-free logits."""
    if n_devices and N_DEV < n_devices:
        pytest.skip("needs emulated devices")
    _, eb = engines
    feats, refs = workload
    inj = FaultInjector(FaultPlan(
        events=tuple(FaultEvent("dispatch", at) for at in ats)))
    obs = PoolObservability()

    async def run():
        async with AsyncSpartusServer(
                eb, 4, chunk_frames=4, max_frames=16, offload_ticks=False,
                watchdog=True, faults=inj, n_devices=n_devices,
                observability=obs) as srv:
            res = await asyncio.gather(
                *[srv.submit(f) for f in feats])
            assert srv.n_recoveries == len(ats)
            return res

    for r in asyncio.run(run()):
        if n_devices:
            # sharded pools are 1e-5 vs the batch-1 oracle (different
            # XLA partitioning); the rebuild itself is same-program.
            np.testing.assert_allclose(r.logits, refs[r.req_id], atol=1e-5)
        else:
            assert np.array_equal(r.logits, refs[r.req_id]), r.req_id
    assert obs.c_recoveries.value == len(ats)
    assert obs.c_salvaged.value > 0 and obs.c_lost.value == 0
    assert obs.registry.counter(
        "spartus_faults_total", labels={"site": "dispatch"}).value == len(ats)


def test_watchdog_poison_fails_only_unsalvageable(engines, workload):
    """A poison fault models a crash AFTER donation: the device state is
    gone, so mid-flight sessions fail — each with a retriable
    `DriverRecovered` — but the server survives and a fresh submission
    afterwards is served bit-identically."""
    _, eb = engines
    feats, refs = workload
    inj = FaultInjector(FaultPlan(
        events=(FaultEvent("dispatch", 1, payload="poison"),)))

    async def run():
        async with AsyncSpartusServer(
                eb, 4, chunk_frames=4, max_frames=16, offload_ticks=False,
                watchdog=True, faults=inj) as srv:
            handles = [await srv.stream(feats[i]) for i in range(4)]
            for h in handles:
                h.close()
            ok = lost = 0
            for h in handles:
                try:
                    r = await h.result()
                    assert np.array_equal(r.logits, refs[r.req_id])
                    ok += 1
                except ServingError as e:
                    assert e.retriable and e.code == "retriable_internal"
                    lost += 1
            assert srv.n_recoveries == 1 and lost >= 1
            # the server is alive: retry one lost utterance, then a new one
            r = await srv.submit(feats[0])
            assert np.array_equal(r.logits, refs[0])
            r = await srv.submit(feats[5])
            assert np.array_equal(r.logits, refs[5])

    asyncio.run(run())


def test_watchdog_disabled_fails_loudly(engines, workload):
    """Without the watchdog the old contract holds: a crashed tick fails
    every connected client with the driver's error."""
    _, eb = engines
    feats, _ = workload
    inj = FaultInjector(FaultPlan(events=(FaultEvent("dispatch", 0),)))

    async def run():
        srv = AsyncSpartusServer(eb, 2, chunk_frames=4, max_frames=16,
                                 offload_ticks=False, faults=inj)
        await srv.start()
        with pytest.raises(InjectedFault):
            await srv.submit(feats[0])
        with pytest.raises(InjectedFault):
            await srv.stop()              # the driver re-raises on join

    asyncio.run(run())


def test_idle_reaper_times_out_silent_sessions(engines, workload):
    """A client that opens and goes silent is reaped after
    ``idle_timeout_s`` with a retriable `SessionTimeout`; a busy
    neighbour is untouched and bit-identical."""
    _, eb = engines
    feats, refs = workload
    obs = PoolObservability()

    async def run():
        async with AsyncSpartusServer(
                eb, 2, chunk_frames=4, max_frames=16, offload_ticks=False,
                idle_timeout_s=0.15, observability=obs) as srv:
            silent = await srv.stream(feats[1][:2])   # never closes
            r = await srv.submit(feats[3])
            assert np.array_equal(r.logits, refs[3])
            with pytest.raises(SessionTimeout):
                await silent.result()

    asyncio.run(run())
    assert obs.c_timeouts.value >= 1


def test_shed_policy_and_idempotent_tokens(engines, workload):
    """Overload with policy='shed': admission past max_pending raises a
    typed retriable `AdmissionShed` with a retry hint instead of
    queueing; a token re-open returns the SAME handle (no double
    admission) while the stream lives, and a backoff retry eventually
    lands."""
    _, eb = engines
    feats, refs = workload
    obs = PoolObservability()

    async def run():
        async with AsyncSpartusServer(
                eb, 1, chunk_frames=4, max_frames=16, offload_ticks=False,
                max_pending=1, overload_policy="shed",
                target_chunk_ms=15.0, observability=obs) as srv:
            h = await srv.stream(feats[0], token="tok")
            assert (await srv.stream(token="tok")) is h   # idempotent
            shed = None
            others = []
            try:
                for i in range(8):
                    others.append(await srv.stream(feats[1][:3]))
            except AdmissionShed as e:
                shed = e
            assert shed is not None and shed.retriable
            assert shed.code == "shed" and shed.retry_after_ms >= 15.0
            h.close()
            for o in others:
                o.close()
            r = await h.result()
            # capacity-1 compiles a different program than the batch-1
            # oracle: oracle parity is 1e-5, like the serving suite pins
            np.testing.assert_allclose(r.logits, refs[0], atol=1e-5)
            for o in others:
                await o.result()
            # the slot freed: a backoff retry now succeeds
            bo = Backoff(seed=1)
            for attempt in range(6):
                try:
                    h2 = await srv.stream(feats[2], token="tok2")
                    break
                except AdmissionShed:
                    await asyncio.sleep(bo.delay(attempt))
            else:
                raise AssertionError("retry never admitted")
            h2.close()
            r2 = await h2.result()
            np.testing.assert_allclose(r2.logits, refs[2], atol=1e-5)
            # settled stream released its token: a re-open is a NEW stream
            h3 = await srv.stream(feats[0], token="tok")
            assert h3 is not h
            h3.close()
            await h3.result()
    asyncio.run(run())
    assert obs.c_shed.value >= 1


def test_async_bad_request_is_typed_and_isolated(engines, workload):
    """Malformed payloads at the async boundary raise `BadRequest`
    (typed, non-retriable) in the offending call; the pool and its other
    sessions never see them."""
    _, eb = engines
    feats, refs = workload
    obs = PoolObservability()

    async def run():
        async with AsyncSpartusServer(
                eb, 2, chunk_frames=4, max_frames=16, offload_ticks=False,
                observability=obs) as srv:
            with pytest.raises(BadRequest, match="NaN/Inf"):
                await srv.stream(np.full((3, INPUT_DIM), np.nan))
            with pytest.raises(BadRequest, match="dtype"):
                await srv.stream(np.array([["z"] * INPUT_DIM]))
            with pytest.raises(BadRequest, match="feature dim"):
                await srv.stream(np.zeros((2, 7), np.float32))
            h = await srv.stream(feats[0][:2])
            with pytest.raises(BadRequest, match="NaN/Inf"):
                await h.send(np.full((1, INPUT_DIM), -np.inf))
            await h.send(feats[0][2:])
            h.close()
            r = await h.result()
            # capacity-1 compiles a different program than the batch-1
            # oracle: oracle parity is 1e-5, like the serving suite pins
            np.testing.assert_allclose(r.logits, refs[0], atol=1e-5)

    asyncio.run(run())
    assert obs.c_bad_requests.value == 4


# -- the JSON-lines transport under fuzzed traffic ----------------------------


async def _jsonl_roundtrip(reader, writer, obj):
    jline(writer, obj)
    await writer.drain()
    return json.loads(await reader.readline())


def test_protocol_hardening_fuzz(engines, workload):
    """Malformed JSON-lines traffic answers typed in-band errors without
    killing the connection; an oversized line closes only ITS connection;
    a well-behaved stream on another connection is bit-identical
    throughout the abuse."""
    _, eb = engines
    feats, refs = workload

    async def run():
        async with AsyncSpartusServer(
                eb, 2, chunk_frames=4, max_frames=16,
                offload_ticks=False) as srv:
            tcp = await asyncio.start_server(
                lambda r, w: handle_conn(srv, r, w), "127.0.0.1", 0,
                limit=MAX_LINE_BYTES)
            port = tcp.sockets[0].getsockname()[1]
            good = asyncio.create_task(demo_client(port, 7, feats[0]))

            r, w = await asyncio.open_connection("127.0.0.1", port)
            corpus = [
                (b"this is not json\n", "bad_json"),
                (b"[1, 2, 3]\n", "bad_json"),
                (b'{"no_op": true}\n', "bad_json"),
                (b'{"op": "detonate", "id": 1}\n', "unknown_op"),
                (b'{"op": "frames", "id": 1, "frames": [[0.0]]}\n',
                 "no_such_stream"),
                (b'{"op": "close", "id": 1}\n', "no_such_stream"),
                (b'{"op": "frames"}\n', "no_such_stream"),
            ]
            for raw, code in corpus:
                w.write(raw)
                await w.drain()
                msg = json.loads(await r.readline())
                assert msg["event"] == "error", (raw, msg)
                assert msg["code"] == code and msg["retriable"] is False
            # the connection survived all of that: open a real stream
            msg = await _jsonl_roundtrip(r, w, {"op": "open", "id": 5})
            assert msg == {"event": "open_ok", "id": 5}
            msg = await _jsonl_roundtrip(r, w, {"op": "open", "id": 5})
            assert msg["code"] == "duplicate_id"
            # bad payloads fail the op, not the stream or connection:
            msg = await _jsonl_roundtrip(
                r, w, {"op": "frames", "id": 5,
                       "frames": [[float("nan")] * INPUT_DIM]})
            assert msg["code"] == "bad_request" and not msg["retriable"]
            msg = await _jsonl_roundtrip(
                r, w, {"op": "frames", "id": 5, "frames": ["junk"]})
            assert msg["code"] == "bad_request"
            # stream 5 still works end to end
            for j in range(0, len(feats[1]), 4):
                jline(w, {"op": "frames", "id": 5,
                          "frames": feats[1][j:j + 4].tolist()})
            jline(w, {"op": "close", "id": 5})
            await w.drain()
            rows = []
            while True:
                msg = json.loads(await r.readline())
                if msg["event"] == "done":
                    break
                assert msg["event"] == "partial"
                rows.append(np.asarray(msg["logits"], np.float32))
            assert np.array_equal(np.concatenate(rows), refs[1])
            # transport violation: an over-long line drops the connection
            w.write(b'{"op": "open", "id": 9, "pad": "'
                    + b"x" * (MAX_LINE_BYTES + 64) + b'"}\n')
            await w.drain()
            msg = json.loads(await r.readline())
            assert msg["code"] == "line_too_long"
            assert await r.readline() == b""        # closed
            w.close()
            # ...and the neighbour never noticed
            cid, streamed, done = await good
            assert cid == 7 and done["event"] == "done"
            assert np.array_equal(streamed, refs[0])
            tcp.close()
            await tcp.wait_closed()

    asyncio.run(run())


def test_demo_client_retries_through_shed(engines, workload):
    """The launcher's demo client rides out 'shed' answers with seeded
    backoff + token and still gets bit-identical logits."""
    _, eb = engines
    feats, refs = workload

    async def run():
        async with AsyncSpartusServer(
                eb, 1, chunk_frames=4, max_frames=16, offload_ticks=False,
                max_pending=1, overload_policy="shed") as srv:
            tcp = await asyncio.start_server(
                lambda r, w: handle_conn(srv, r, w), "127.0.0.1", 0,
                limit=MAX_LINE_BYTES)
            port = tcp.sockets[0].getsockname()[1]
            out = await asyncio.gather(
                *[demo_client(port, i, feats[i]) for i in range(4)])
            tcp.close()
            await tcp.wait_closed()
            return out

    for cid, streamed, done in asyncio.run(run()):
        assert done["event"] == "done"
        np.testing.assert_allclose(streamed, refs[cid], atol=1e-5)
