"""Chunked device tick loop + double-buffered serving.

`step_chunk` advances every active slot up to C frames in ONE dispatch
(`lax.scan` over the per-frame core) and banks logits in a per-slot
device output buffer; `SessionPool`/`serve_requests` overlap retirement
fetches and admission bookkeeping with the in-flight chunk.  One-frame
chunks (``chunk_frames=1``) and the batch-1 engine are the parity
oracles: every test here pins C-frame logits/state/telemetry against
them at 1e-5.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.models import lstm_am
from repro.serving import telemetry as tele
from repro.serving import (
    AsyncSpartusServer,
    BatchedSpartusEngine,
    EngineConfig,
    SpartusEngine,
    StreamRequest,
    serve_requests,
)
from repro.serving.metrics import PoolObservability
from repro.serving import scheduler
from repro.serving.scheduler import (
    RETIRE_BLOCK,
    UPLOAD_BLOCK_FRAMES,
    SessionPool,
)

INPUT_DIM, HIDDEN, CLASSES = 20, 32, 11
GAMMA, M, THETA = 0.75, 4, 0.05


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


# -- engine level ------------------------------------------------------------


@pytest.mark.parametrize("c", [4, 8])
def test_step_chunk_matches_single_frame_chunks(engines, c):
    """One C-frame chunk dispatch == the same frames through C one-frame
    chunks: identical logits in the output buffer, identical final layer
    state, cursor and telemetry — including slots that go inactive
    mid-chunk."""
    _, eb = engines
    lens = np.array([c - 1, c // 2, c], np.int32)
    feats = [_utterance(200 + i, int(t)) for i, t in enumerate(lens)]
    frames = np.zeros((3, 8, INPUT_DIM), np.float32)
    for i, f in enumerate(feats):
        frames[i, :lens[i]] = f
    frames = jnp.asarray(frames)

    s_ref = eb.init_state(3)
    ref = eb.init_out_buf(3, 9)
    for t in range(c):
        s_ref, ref = eb.step_chunk(s_ref, frames, lens, np.ones(3, bool),
                                   np.full(3, t == 0), ref, n_frames=1)
    ref = np.asarray(ref)

    s = eb.init_state(3)
    out = eb.init_out_buf(3, 8 + c)
    s, out = eb.step_chunk(s, frames, lens, np.ones(3, bool),
                           np.ones(3, bool), out, n_frames=c)
    out = np.asarray(out)
    for b in range(3):
        # rows past lens[b] are scratch (never consumed by any reader)
        np.testing.assert_allclose(out[b, :lens[b]], ref[b, :lens[b]],
                                   atol=1e-5)
    np.testing.assert_array_equal(np.asarray(s.cursor), lens)
    np.testing.assert_array_equal(np.asarray(s_ref.cursor), lens)
    for a, b in zip(jax.tree.leaves(s_ref.layers), jax.tree.leaves(s.layers)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)
    np.testing.assert_array_equal(np.asarray(s_ref.telemetry.steps),
                                  np.asarray(s.telemetry.steps))
    np.testing.assert_array_equal(np.asarray(s_ref.telemetry.nnz_sum),
                                  np.asarray(s.telemetry.nnz_sum))


def test_step_chunk_donates_state_and_out_buf(engines):
    """The chunk dispatch consumes (donates) the incoming PoolState and
    output buffer: the old device buffers are deleted, not copied."""
    _, eb = engines
    frames = jnp.asarray(np.stack([_utterance(210, 6), _utterance(211, 6)]))
    state = eb.init_state(2)
    out = eb.init_out_buf(2, 6)
    old_cursor, old_out = state.cursor, out
    state, out = eb.step_chunk(state, frames, np.array([6, 6]),
                               np.ones(2, bool), np.ones(2, bool), out,
                               n_frames=4)
    with pytest.raises(RuntimeError, match="deleted"):
        np.asarray(old_cursor)
    with pytest.raises(RuntimeError, match="deleted"):
        np.asarray(old_out)
    # the returned arrays are live and correct-shaped:
    assert np.asarray(out).shape == (2, 6, CLASSES)
    np.testing.assert_array_equal(np.asarray(state.cursor), [4, 4])


# -- scheduler level ---------------------------------------------------------


def test_chunk_sizes_parity_grid(engines):
    """C-frame chunks == one-frame chunks == batch-1 engine over a grid
    of (capacity, chunk_frames) with ragged utterance lengths, staggered
    arrivals, mid-chunk retirements and chunk-boundary admissions."""
    e1, eb = engines
    lens = [5, 9, 3, 12, 1, 7]
    feats = [_utterance(220 + i, t) for i, t in enumerate(lens)]
    refs = [np.asarray(e1.run_utterance(jnp.asarray(f))) for f in feats]
    reqs = [StreamRequest(i, arrival_step=2 * i, feats=feats[i])
            for i in range(len(lens))]

    for capacity in (2, 4):
        base, _ = serve_requests(eb, reqs, capacity=capacity, chunk_frames=1)
        for chunk in (1, 3, 8, 32):
            results, stats = serve_requests(eb, reqs, capacity=capacity,
                                            chunk_frames=chunk)
            assert [r.req_id for r in results] == list(range(len(lens)))
            for r in results:
                np.testing.assert_allclose(r.logits, refs[r.req_id],
                                           atol=1e-5)
                # and against one-frame chunks:
                np.testing.assert_allclose(
                    r.logits, base[r.req_id].logits, atol=1e-5)
            assert stats.total_frames == sum(lens)
            assert not stats.truncated
            assert stats.chunk_frames == chunk


def test_midchunk_retirement_and_boundary_admission(engines):
    """capacity 1, chunk 4: a 3-frame request retires mid-chunk (the slot's
    scan iterations past its length are masked no-ops), and the queued
    request is admitted at the next chunk boundary — tick 3, not 4."""
    e1, eb = engines
    feats = [_utterance(230, 3), _utterance(231, 5)]
    reqs = [StreamRequest(0, 0, feats[0]), StreamRequest(1, 0, feats[1])]
    results, stats = serve_requests(eb, reqs, capacity=1, chunk_frames=4)

    assert [r.req_id for r in results] == [0, 1]
    for r in results:
        ref = np.asarray(e1.run_utterance(jnp.asarray(feats[r.req_id])))
        np.testing.assert_allclose(r.logits, ref, atol=1e-5)
    # request 0: 3 frames, finishes at tick 2 inside a 3-tick chunk
    assert results[0].admit_step == 0 and results[0].finish_step == 2
    # request 1 waited for the boundary: admitted at tick 3, not 4
    assert results[1].admit_step == 3
    assert results[1].finish_step == 7
    assert stats.total_steps == 3 + 5


def test_chunked_max_steps_drains_partial(engines):
    """max_steps truncates at a chunk boundary: partial
    logits (chunk granularity) still match the batch-1 prefix."""
    e1, eb = engines
    feats = [_utterance(240, 8), _utterance(241, 8)]
    reqs = [StreamRequest(0, 0, feats[0]), StreamRequest(1, 0, feats[1])]
    results, stats = serve_requests(eb, reqs, capacity=2, chunk_frames=4,
                                    max_steps=4)
    assert stats.truncated
    assert [r.req_id for r in results] == [0, 1]
    for r in results:
        assert r.truncated and r.logits.shape[0] == 4
        ref = np.asarray(e1.run_utterance(jnp.asarray(feats[r.req_id])))
        np.testing.assert_allclose(r.logits, ref[:4], atol=1e-5)


def test_cancel_during_retirement_window_suppresses_result(engines):
    """The double-buffer audit: a session that finished inside an
    in-flight chunk sits in the retirement window (device snapshot taken,
    host fetch one chunk away).  cancel() during that window must be
    accepted (the session is not 'unknown' — no result was delivered
    yet), must suppress the stale result at resolve time, and the freed
    slot must serve a new session with clean numerics."""
    e1, eb = engines
    feats = [_utterance(280, 3), _utterance(281, 5)]
    pool = SessionPool(eb, capacity=1, max_frames=16, chunk_frames=4)

    assert pool.admit(StreamRequest(0, 0, feats[0]), 0)
    done_now = pool.step_chunk(now=0)
    assert done_now == []                 # double-buffered: fetch pending
    assert 0 not in pool._by_req          # retired from the live set...
    assert pool.has_pending               # ...but the fetch is outstanding
    pool.cancel(0)                        # <- inside the window

    # the freed slot serves the next session; the cancelled session's
    # pending snapshot resolves to NOTHING:
    assert pool.admit(StreamRequest(1, 1, feats[1]), 1)
    results = []
    for t in (1, 5):
        results.extend(pool.step_chunk(now=t))
    results.extend(pool.flush())
    assert [r.req_id for r in results] == [1]
    ref = np.asarray(e1.run_utterance(jnp.asarray(feats[1])))
    np.testing.assert_allclose(results[0].logits, ref, atol=1e-5)
    # a request the pool has never seen still raises:
    with pytest.raises(KeyError):
        pool.cancel(99)


def test_pool_rejects_chunk_frames_below_one(engines):
    """A chunk advances at least one frame: the pool, the synchronous
    driver and the async front end (through its pool) refuse
    ``chunk_frames=0`` up front."""
    _, eb = engines
    with pytest.raises(ValueError, match="chunk_frames"):
        SessionPool(eb, capacity=2, chunk_frames=0)
    with pytest.raises(ValueError, match="chunk_frames"):
        serve_requests(eb, [StreamRequest(0, 0, _utterance(290, 3))],
                       capacity=2, chunk_frames=0)
    with pytest.raises(ValueError, match="chunk_frames"):
        AsyncSpartusServer(eb, capacity=2, chunk_frames=0)


def test_upload_growth_single_realloc_no_host_recopy(engines, monkeypatch):
    """Regression: a long utterance used to rebuild the whole frame slab.
    Growth must now (a) reallocate ONCE, straight to the new bucket,
    (b) stage only the new utterance's bytes host->device — the other
    slots' frames are copied device->device, bit-identically."""
    _, eb = engines
    staged = []
    real_device_put = jax.device_put
    real_asarray = jnp.asarray

    def counting_device_put(x, *a, **kw):
        if isinstance(x, np.ndarray):
            staged.append(x.nbytes)
        return real_device_put(x, *a, **kw)

    def counting_asarray(x, *a, **kw):
        if isinstance(x, np.ndarray):
            staged.append(x.nbytes)
        return real_asarray(x, *a, **kw)

    pool = SessionPool(eb, capacity=3, max_frames=16, chunk_frames=4)
    short = _utterance(250, 8)
    assert pool.admit(StreamRequest(0, 0, short), 0)
    pool._flush_uploads()
    resident_before = np.asarray(pool._frames[0, :8])

    monkeypatch.setattr(jax, "device_put", counting_device_put)
    monkeypatch.setattr(jnp, "asarray", counting_asarray)
    long = _utterance(251, 150)                      # 16 -> 256 bucket
    assert pool.admit(StreamRequest(1, 0, long), 0)
    pool._flush_uploads()
    monkeypatch.setattr(jax, "device_put", real_device_put)
    monkeypatch.setattr(jnp, "asarray", real_asarray)

    # one realloc, straight to the final bucket:
    assert pool.n_frame_grows == 1
    assert pool._t_buf == 256
    # only the new utterance (padded to its bucket) crossed host->device —
    # in particular NOT the other slots' frames (capacity x bucket = the
    # slab the old jnp.pad growth rebuilt).  Small slack for the [R] slot
    # and length index vectors of the batched upload:
    bucket_bytes = 256 * INPUT_DIM * 4
    assert bucket_bytes <= sum(staged) <= bucket_bytes + 64
    assert sum(staged) < 3 * bucket_bytes        # capacity x bucket = slab
    # the resident slot's frames were carried over device-side, bit-exact:
    np.testing.assert_array_equal(np.asarray(pool._frames[0, :8]),
                                  resident_before)
    np.testing.assert_array_equal(np.asarray(pool._frames[1, :150]), long)
    # a later utterance within the bucket never grows again:
    assert pool.admit(StreamRequest(2, 0, _utterance(252, 100)), 0)
    pool._flush_uploads()
    assert pool.n_frame_grows == 1


def _admit_wave(pool, lengths, key0):
    """Admit one utterance per length (0 = a stream with no frames yet)
    and return each slot's frames."""
    sent = {}
    for i, n in enumerate(lengths):
        rid = key0 + i
        if n:
            feats = _utterance(rid, n)
            assert pool.admit(StreamRequest(rid, 0, feats), 0)
        else:
            feats = np.zeros((0, INPUT_DIM), np.float32)
            assert pool.admit_stream(rid, 0)
        sent[pool._by_req[rid]] = feats
    return sent


def test_block_placement_lands_each_utterance_bit_for_bit(engines):
    """An admission wave ships K-frame blocks of real frames: each
    utterance lands bit for bit below its length, the tail of its last
    block is zero, frames past that keep the slot's earlier occupant, and
    the padding blocks of the pow2 bucket touch no slot at all."""
    _, eb = engines
    obs = PoolObservability()
    pool = SessionPool(eb, capacity=8, max_frames=1024, chunk_frames=4,
                       observability=obs)
    t_buf = pool._t_buf
    k = min(UPLOAD_BLOCK_FRAMES, t_buf)
    assert (t_buf, k) == (1024, 256)
    _admit_wave(pool, [t_buf] * 8, 300)              # earlier occupants
    pool._flush_uploads()
    prior = np.asarray(pool._frames)
    for rid in range(300, 307):
        pool.cancel(rid)
    pool._reap_cancelled()
    obs.boundary.clear()

    lengths = [1, k - 1, k, k + 1, 2 * k + 3, t_buf, 0]
    sent = _admit_wave(pool, lengths, 400)
    pool._flush_uploads()
    frames = np.asarray(pool._frames)
    want_len = np.full((8,), t_buf, np.int32)
    for slot, feats in sent.items():
        n = feats.shape[0]
        end = max(1, -(-n // k)) * k                 # end of its last block
        np.testing.assert_array_equal(frames[slot, :n], feats)
        assert not frames[slot, n:end].any()
        np.testing.assert_array_equal(frames[slot, end:], prior[slot, end:])
        want_len[slot] = n
    np.testing.assert_array_equal(frames[7], prior[7])   # not in the wave
    np.testing.assert_array_equal(np.asarray(pool._lengths), want_len)
    n_blocks = 1 + 1 + 1 + 2 + 3 + 4 + 1             # 13 real: nb = 16
    assert obs.boundary["upload_blocks"] == n_blocks
    assert obs.boundary["upload_frame_slots"] == 16 * k
    assert obs.boundary["upload_frames"] == sum(lengths)
    assert obs.boundary["upload_bytes"] == 16 * k * INPUT_DIM * 4


def test_block_placement_keyed_by_block_count_alone(engines):
    """Waves of different sizes with the same bucketed block count run
    one compiled placement program."""
    _, eb = engines
    pool = SessionPool(eb, capacity=5, max_frames=1024, chunk_frames=4)
    _admit_wave(pool, [700], 500)                    # 1 utterance: nb = 4
    pool._flush_uploads()
    n0 = scheduler._device_place._cache_size()
    pool.cancel(500)
    pool._reap_cancelled()
    _admit_wave(pool, [10, 20, 30, 0], 510)          # 4 utterances: nb = 4
    pool._flush_uploads()
    assert scheduler._device_place._cache_size() == n0
    for rid in range(510, 514):
        pool.cancel(rid)
    pool._reap_cancelled()
    _admit_wave(pool, [600, 300], 520)               # 5 blocks: nb = 8
    pool._flush_uploads()
    assert scheduler._device_place._cache_size() == n0 + 1


def test_block_placement_ships_only_the_blocks(engines, monkeypatch):
    """What crosses to the device for a wave is nb x K x D floats plus
    the three [nb] int32 vectors (slot, block offset, length)."""
    _, eb = engines
    shipped = []
    real_place = scheduler._device_place

    def recording_place(frames, lengths, *wave):
        shipped.append([(np.shape(a), np.asarray(a).nbytes) for a in wave])
        return real_place(frames, lengths, *wave)

    monkeypatch.setattr(scheduler, "_device_place", recording_place)
    pool = SessionPool(eb, capacity=4, max_frames=1024, chunk_frames=4)
    _admit_wave(pool, [300, 5, 0], 600)              # 2 + 1 + 1 blocks
    pool._flush_uploads()
    k, nb = UPLOAD_BLOCK_FRAMES, 4
    (wave,) = shipped
    assert wave[0][0] == (nb, k, INPUT_DIM)
    assert sum(b for _, b in wave) == nb * k * INPUT_DIM * 4 + 3 * nb * 4


def test_restaged_slot_keeps_its_latest_staging(engines):
    """A stream that finished with no frames retires without a dispatch;
    its slot is re-admitted before the next wave, and the wave carries
    only the later staging for that slot."""
    _, eb = engines
    pool = SessionPool(eb, capacity=1, max_frames=64, chunk_frames=4)
    assert pool.admit_stream(700, 0)
    pool.finish_stream(700)
    assert pool.tick(0)[1] == 0                      # retired, not uploaded
    feats = _utterance(701, 9)
    assert pool.admit(StreamRequest(701, 0, feats), 0)
    assert [k for k, _ in pool._staged] == [0, 0]
    pool._flush_uploads()
    assert int(pool._lengths[0]) == 9
    np.testing.assert_array_equal(np.asarray(pool._frames[0, :9]), feats)


def test_no_per_tick_reallocation(engines):
    """Steady-state chunked ticking reuses the donated state/output slabs:
    the number of live device arrays does not grow tick over tick."""
    _, eb = engines
    pool = SessionPool(eb, capacity=2, max_frames=64, chunk_frames=4)
    for i in range(2):
        pool.admit(StreamRequest(i, 0, _utterance(260 + i, 64)), 0)
    pool.step_chunk(now=0)                  # compile + first tick
    jax.block_until_ready(pool.state.cursor)
    n0 = len(jax.live_arrays())
    for t in range(3):
        pool.step_chunk(now=4 * (t + 1))
        jax.block_until_ready(pool.state.cursor)
        assert len(jax.live_arrays()) <= n0
    assert pool.n_active == 2               # nobody retired mid-measurement


def test_accumulate_layers_matches_per_layer_accumulate():
    """The vectorised whole-step telemetry fold equals L sequential
    per-layer accumulate() calls (the oracle it replaced in the step).
    Accumulators are per-(layer, slot) — the slot dim is reduced only in
    measured_sparsity, never in the step (the sharded pool depends on
    that: a per-step slot reduction would be a per-frame all-reduce)."""
    L, B = 3, 5
    rng = np.random.default_rng(0)
    nnz = jnp.asarray(rng.integers(0, 50, (L, B)), jnp.int32)
    dropped = jnp.asarray(rng.integers(0, 3, (L, B)), jnp.int32)
    active = jnp.asarray(rng.random(B) < 0.6)

    stacked = tele.accumulate_layers(tele.init_telemetry(L, B), nnz, dropped,
                                     active)
    looped = tele.init_telemetry(L, B)
    for li in range(L):
        looped = tele.accumulate(looped, li, nnz[li], dropped[li], active)
    for a, b in zip(stacked, looped):
        assert a.shape == (L, B)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_dispatch_amortisation_metrics(engines):
    """ServeStats surfaces the dispatch economy: C-frame chunks issue
    ~1/C the dispatches of one-frame chunks, and the overlap fraction
    is a sane [0, 1) number."""
    _, eb = engines
    reqs = [StreamRequest(i, 0, _utterance(270 + i, 16)) for i in range(4)]
    _, single = serve_requests(eb, reqs, capacity=4, chunk_frames=1)
    _, chunked = serve_requests(eb, reqs, capacity=4, chunk_frames=8)

    assert single.n_dispatches == 16         # one per tick
    assert single.dispatches_per_frame == pytest.approx(16 / 64)
    assert single.total_steps == 16
    assert chunked.n_dispatches == 2         # 16 frames / 8-frame chunks
    assert chunked.dispatches_per_frame == pytest.approx(2 / 64)
    assert chunked.total_steps == 16
    for stats in (single, chunked):
        assert 0.0 <= stats.host_overlap_frac < 1.0


# -- retirement gather -------------------------------------------------------


def _single_frame_oracle(eb, feats, batch):
    """Each utterance's logits from one-frame ``step_chunk`` calls on a
    ``batch``-slot state, ``batch`` utterances at a time: bit-identical
    to what the pool banks in 4-frame chunks at the same capacity."""
    out = []
    for lo in range(0, len(feats), batch):
        group = feats[lo:lo + batch]
        t_max = max(f.shape[0] for f in group)
        frames = np.zeros((batch, t_max, INPUT_DIM), np.float32)
        lens = np.zeros(batch, np.int32)
        for b, f in enumerate(group):
            frames[b, :f.shape[0]], lens[b] = f, f.shape[0]
        frames = jnp.asarray(frames)
        state, bank = eb.init_state(batch), eb.init_out_buf(batch, t_max + 1)
        for t in range(t_max):
            state, bank = eb.step_chunk(state, frames, lens, lens > 0,
                                        np.full(batch, t == 0), bank,
                                        n_frames=1)
        bank = np.asarray(bank)
        out += [bank[b, :f.shape[0]] for b, f in enumerate(group)]
    return out


def _drive(pool, queue, now=0):
    """Admit queued requests as slots free, tick until everything has
    resolved; returns the results."""
    queue, results = list(queue), []
    while queue or pool.n_active or pool.has_pending:
        while queue and pool.admit(queue[0], now):
            queue.pop(0)
        done, adv = pool.tick(now)
        results += done
        now += max(adv, 1)
    return results


def _retire_fewer(pool, feats):
    return _drive(pool, [StreamRequest(i, 0, f) for i, f in enumerate(feats)])


def _retire_readmit(pool, feats):
    """The first 40 sessions fill the pool and retire together; the
    queued ones take their slots at the very next boundary, while the
    retirement gathers are still to be fetched."""
    results = _retire_fewer(pool, feats)
    first = [r for r in results if r.req_id < pool.capacity]
    later = [r for r in results if r.req_id >= pool.capacity]
    assert later and all(r.admit_step == max(f.finish_step for f in first)
                         + 1 for r in later)
    return results


def _retire_cancel(pool, feats):
    """Two sessions, one in each gathered block, are cancelled inside the
    retirement window: their rows are fetched but never delivered."""
    for i, f in enumerate(feats):
        assert pool.admit(StreamRequest(i, 0, f), 0)
    assert pool.step_chunk(0) == [] and pool.step_chunk(4) == []
    assert pool.has_pending and pool.n_active == 0
    for req_id in (3, 35):
        pool.cancel(req_id)
    return pool.flush()


def _retire_flush(pool, feats):
    """Streams that have consumed every frame and then finish retire
    without another dispatch (``_queue_done_retirements``)."""
    for i, f in enumerate(feats):
        assert pool.admit_stream(i, 0, f)
    now = 0
    while pool.max_chunk_advance():
        assert pool.step_chunk(now) == []
        now += 4
    dispatches = pool.n_dispatches
    for i in range(len(feats)):
        pool.finish_stream(i)
    results = pool.flush()
    assert pool.n_dispatches == dispatches and not pool.has_pending
    return results


# (driver, capacity, lengths, cancelled ids, retirement gathers): R is 32
# and a 6-frame session retires at the second 4-frame chunk boundary.
RETIRE_CASES = {
    "fewer_than_block": (_retire_fewer, 64, [6] * 5, (), 1),
    "exactly_block": (_retire_fewer, 64, [6] * 32, (), 1),
    "more_than_block": (_retire_fewer, 64, [6] * 40 + [10] * 5, (), 3),
    "readmit_next_boundary": (_retire_readmit, 40, [6] * 40 + [5] * 8, (),
                              3),
    "cancel_in_window": (_retire_cancel, 64, [6] * 40, (3, 35), 2),
    "flush_path": (_retire_flush, 64, [6] * 40, (), 2),
}


@pytest.mark.parametrize("case", sorted(RETIRE_CASES))
def test_retirement_gather_serves_bank_rows(engines, case):
    """Retiring sessions' rows leave the device in blocks of RETIRE_BLOCK
    gathered slots, the last block padded with a slot already in it.
    Every served utterance equals the one-frame-chunk oracle bit for bit,
    cancelled ones are never served, the boundary samples count the
    gathers and their R x T_pad rows, and every block count runs the one
    retirement program."""
    _, eb = engines
    drive, capacity, lens, cancelled, gathers = RETIRE_CASES[case]
    feats = [_utterance(900 + i, t) for i, t in enumerate(lens)]
    obs = PoolObservability()
    pool = SessionPool(eb, capacity=capacity, max_frames=16,
                       chunk_frames=4, observability=obs)
    compiled = eb._retire_rows._cache_size()
    results = drive(pool, feats)

    assert sorted(r.req_id for r in results) == [
        i for i in range(len(feats)) if i not in cancelled]
    refs = _single_frame_oracle(eb, feats, capacity)
    for r in results:
        np.testing.assert_array_equal(r.logits, refs[r.req_id])

    def total(key):
        return (sum(s[key] for s in obs.timeseries.snapshot())
                + obs.boundary.get(key, 0))

    t_pad = pool._out.shape[1]
    assert min(RETIRE_BLOCK, capacity) == 32
    assert total("retire_gathers") == gathers
    assert total("fetch_rows") == gathers * RETIRE_BLOCK * t_pad
    assert total("fetch_rows_kept") == sum(r.logits.shape[0]
                                           for r in results)
    assert eb._retire_rows._cache_size() - compiled <= 1
