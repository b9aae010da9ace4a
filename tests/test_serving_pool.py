"""Continuous-batching serving subsystem: batched engine == batch-1 engine
numerically, scheduler lifecycle (staggered arrivals, early finish,
backpressure), device-resident telemetry == per-step telemetry."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.hwsim import spartus_model as hw
from repro.kernels import ops
from repro.models import lstm_am
from repro.serving import (
    BatchedSpartusEngine,
    EngineConfig,
    SpartusEngine,
    StreamRequest,
    serve_requests,
)

INPUT_DIM, HIDDEN, CLASSES = 20, 32, 11
GAMMA, M, THETA = 0.75, 4, 0.05


@pytest.fixture(scope="module")
def model():
    """Small CBTD-pruned AM (no training needed for engine equivalence)."""
    cfg = lstm_am.LSTMAMConfig(input_dim=INPUT_DIM, hidden_dim=HIDDEN,
                               n_layers=2, n_classes=CLASSES)
    params = lstm_am.init_params(jax.random.key(0), cfg)
    return lstm_am.cbtd_prune_stacks(params, gamma=GAMMA, m=M), cfg


@pytest.fixture(scope="module")
def engines(model):
    params, cfg = model
    ecfg = EngineConfig(theta=THETA, gamma=GAMMA, m=M, capacity_frac=1.0)
    return SpartusEngine(params, cfg, ecfg), BatchedSpartusEngine(params, cfg, ecfg)


def _utterance(key, t):
    return np.asarray(jax.random.normal(jax.random.key(key), (t, INPUT_DIM)),
                      np.float32)


def _one_frame_chunks(eb, state, frames, lengths, active, resets):
    """Step ``state`` through ``len(resets)`` one-frame ``step_chunk``
    dispatches over the ``[B, T, D]`` frame buffers, slots masked by
    ``active`` and re-initialised where ``resets[t]`` says; returns
    ``(state, out_buf)``."""
    frames = jnp.asarray(frames)
    b, t_buf = frames.shape[:2]
    out = eb.init_out_buf(b, t_buf + 1)
    for reset in resets:
        state, out = eb.step_chunk(state, frames, lengths, active, reset,
                                   out, n_frames=1)
    return state, out


def test_step_chunk_matches_batch1(engines):
    """All slots active with different utterances: each slot's logits are
    identical to running that utterance alone through SpartusEngine."""
    e1, eb = engines
    feats = [_utterance(i + 1, 10) for i in range(3)]
    ref = [np.asarray(e1.run_utterance(jnp.asarray(f))) for f in feats]

    _, out = _one_frame_chunks(eb, eb.init_state(3), np.stack(feats),
                               np.full(3, 10), np.ones(3, bool),
                               [np.full(3, t == 0) for t in range(10)])
    out = np.asarray(out)
    for b in range(3):
        np.testing.assert_allclose(out[b, :10], ref[b], atol=1e-5)


def test_scheduler_staggered_and_early_finish(engines):
    """Mixed lengths + staggered arrivals through a capacity-2 pool: every
    request's logits match the batch-1 engine; short sessions retire early
    and free their slot for the queued request (backpressure)."""
    e1, eb = engines
    feats = [_utterance(10, 8), _utterance(11, 3), _utterance(12, 6)]
    reqs = [StreamRequest(0, 0, feats[0]), StreamRequest(1, 0, feats[1]),
            StreamRequest(2, 1, feats[2])]
    results, stats = serve_requests(eb, reqs, capacity=2, chunk_frames=1)

    assert [r.req_id for r in results] == [0, 1, 2]
    for r in results:
        ref = np.asarray(e1.run_utterance(jnp.asarray(feats[r.req_id])))
        np.testing.assert_allclose(r.logits, ref, atol=1e-5)
    # request 2 arrived at t=1 into a full pool; request 1 (3 frames)
    # finishes at t=2, so 2 is admitted at t=3 after queueing:
    r2 = results[2]
    assert r2.admit_step == 3 and r2.queue_steps == 2
    assert results[1].finish_step == 2
    assert stats.n_requests == 3
    assert stats.total_frames == 8 + 3 + 6


def test_full_pool_serializes(engines):
    """capacity=1: simultaneous arrivals are served strictly one at a time."""
    _, eb = engines
    feats = [_utterance(20 + i, 4) for i in range(3)]
    results, _ = serve_requests(
        eb, [(0, f) for f in feats], capacity=1)
    admits = [r.admit_step for r in results]
    finishes = [r.finish_step for r in results]
    assert admits == [0, 4, 8]
    assert finishes == [3, 7, 11]


def test_telemetry_matches_batch1(model):
    """Device-aggregated counters reduce to the same summary statistics the
    batch-1 per-step dicts produce, for the identical workload."""
    params, cfg = model
    ecfg = EngineConfig(theta=0.2, gamma=GAMMA, m=M, capacity_frac=0.5)
    e1 = SpartusEngine(params, cfg, ecfg)
    eb = BatchedSpartusEngine(params, cfg, ecfg)
    feats = _utterance(30, 12)

    e1.run_utterance(jnp.asarray(feats))
    sp1 = e1.measured_sparsity()

    # same utterance in slot 0 of a capacity-2 pool, slot 1 idle:
    frames = np.zeros((2, 12, INPUT_DIM), np.float32)
    frames[0] = feats
    state, _ = _one_frame_chunks(
        eb, eb.init_state(2), frames, np.array([12, 0]),
        np.array([True, False]),
        [np.array([t == 0, False]) for t in range(12)])
    spb = eb.measured_sparsity(state)

    assert spb["temporal_sparsity"] == pytest.approx(sp1["temporal_sparsity"],
                                                     abs=1e-9)
    assert spb["capacity_overflow_rate"] == pytest.approx(
        sp1["capacity_overflow_rate"], abs=1e-9)
    assert spb["mean_active_columns"] == pytest.approx(
        sp1["mean_active_columns"], abs=1e-9)
    # and the hwsim consumes the aggregate directly:
    rep = hw.evaluate_from_telemetry(hw.SPARTUS, hw.TEST_LAYER, GAMMA, spb)
    assert rep.latency_us > 0


def test_idle_slots_frozen(engines):
    """Inactive slots must not change state or contribute telemetry."""
    _, eb = engines
    frames = np.zeros((2, 2, INPUT_DIM), np.float32)
    frames[0] = _utterance(40, 2)
    lengths = np.array([2, 0])
    state, _ = _one_frame_chunks(eb, eb.init_state(2), frames, lengths,
                                 np.array([True, False]),
                                 [np.array([True, False])])
    before = jax.device_get(state.layers)
    state2, _ = _one_frame_chunks(eb, state, frames, lengths,
                                  np.array([False, False]),
                                  [np.zeros(2, bool)])
    after = jax.device_get(state2.layers)
    for a, b in zip(jax.tree.leaves(before), jax.tree.leaves(after)):
        np.testing.assert_array_equal(a, b)
    # telemetry only counted the one active (slot, frame) sample per
    # layer — in the active slot's own [L, B] column (the idle slot's
    # column stays zero; slot columns reduce only in measured_sparsity,
    # which is what keeps a sharded pool free of per-step all-reduces):
    steps = np.asarray(jax.device_get(state2.telemetry.steps))
    np.testing.assert_array_equal(steps, [[1, 0], [1, 0]])


def test_step_chunk_cursor_resets_midstream(engines):
    """Re-admitting a new session into a used slot restarts its device
    cursor at frame 0 (reset mask), without touching the neighbour slot."""
    _, eb = engines
    frames = np.stack([_utterance(60, 5), _utterance(61, 5)])
    # three frames, then slot 0 re-admitted (reset), slot 1 streaming on:
    resets = [np.full(2, t == 0) for t in range(3)] + [np.array([True,
                                                                 False])]
    state, _ = _one_frame_chunks(eb, eb.init_state(2), frames,
                                 np.full(2, 5), np.ones(2, bool), resets)
    np.testing.assert_array_equal(np.asarray(state.cursor), [1, 4])


def test_weight_sparsity_enforced_on_unpruned_model():
    """Regression: packing an UNpruned (or partially pruned) model used to
    derive BLEN from max occupancy, voiding the format and reporting ~0
    weight sparsity.  Pack time now enforces blen_for(gamma) by clipping
    and reports the clipped count."""
    cfg = lstm_am.LSTMAMConfig(input_dim=INPUT_DIM, hidden_dim=HIDDEN,
                               n_layers=2, n_classes=CLASSES)
    params = lstm_am.init_params(jax.random.key(3), cfg)   # no pruning
    ecfg = EngineConfig(theta=THETA, gamma=GAMMA, m=M)
    engine = SpartusEngine(params, cfg, ecfg)
    # BLEN/S = 1 - gamma, so structural sparsity can no longer collapse:
    assert engine.weight_sparsity() >= GAMMA - 0.01
    assert engine.pack_overflow_count() > 0
    for layer in engine.layers:
        assert layer.enc.blen == layer.enc.s - int(layer.enc.s * GAMMA)


def test_pack_overflow_zero_for_pruned_model(engines):
    """A properly CBTD-pruned model fits blen_for(gamma) exactly — the
    clip must be a no-op."""
    e1, _ = engines
    assert e1.pack_overflow_count() == 0
    assert e1.weight_sparsity() == pytest.approx(GAMMA, abs=0.03)


def test_max_steps_drains_partial_results(engines):
    """Regression: max_steps used to silently drop all logits of unfinished
    sessions.  They now surface as truncated RequestResults holding the
    frames produced so far, and the stats carry a truncated flag."""
    e1, eb = engines
    feats = [_utterance(70, 8), _utterance(71, 8)]
    reqs = [StreamRequest(0, 0, feats[0]), StreamRequest(1, 0, feats[1])]
    results, stats = serve_requests(eb, reqs, capacity=2, max_steps=3,
                                    chunk_frames=1)

    assert stats.truncated
    assert stats.total_steps == 3
    assert [r.req_id for r in results] == [0, 1]
    for r in results:
        assert r.truncated
        assert r.logits.shape[0] == 3           # partial: 3 of 8 frames
        ref = np.asarray(e1.run_utterance(jnp.asarray(feats[r.req_id])))
        np.testing.assert_allclose(r.logits, ref[:3], atol=1e-5)

    # a run that completes is not truncated:
    results2, stats2 = serve_requests(eb, reqs, capacity=2)
    assert not stats2.truncated
    assert all(not r.truncated for r in results2)
    assert stats2.total_frames == 16


def test_total_steps_counts_only_dispatching_ticks(engines):
    """Regression: total_steps must count ticks that advanced >= 1 slot,
    never idle time between arrival bursts — whether the gap is skipped by
    the fast-forward or (in a future scheduler) ticked through idle."""
    _, eb = engines
    reqs = [StreamRequest(0, 0, _utterance(80, 3)),
            StreamRequest(1, 10, _utterance(81, 3))]
    results, stats = serve_requests(eb, reqs, capacity=1)
    assert len(results) == 2
    assert results[1].admit_step == 10          # idle gap fast-forwarded
    assert stats.total_steps == 6               # 3 + 3 dispatching ticks
    assert stats.total_frames == 6
    # utilisation identity the old wall-tick counting broke: with capacity 1
    # every counted step serves exactly one frame.
    assert stats.total_frames == stats.total_steps

    # and the pool-level invariant behind it: a tick with no active session
    # dispatches nothing (the driver must not count it as a step).
    from repro.serving.scheduler import SessionPool
    pool = SessionPool(eb, capacity=2)
    assert pool.tick(now=0) == ([], 0)
    assert pool.n_active == 0 and pool.n_dispatches == 0


def test_admit_rejects_utterance_past_growth_limit(engines):
    """Satellite fix: an utterance longer than the frame-buffer growth
    limit is rejected at admission with a clear error — not silently
    truncated at some later chunk boundary.  The pool stays usable."""
    from repro.serving.scheduler import SessionPool
    _, eb = engines
    pool = SessionPool(eb, capacity=2, max_frames=16, chunk_frames=4,
                       max_buffer_frames=64)
    with pytest.raises(ValueError, match="growth limit"):
        pool.admit(StreamRequest(0, 0, _utterance(400, 100)), 0)
    assert pool.n_active == 0                    # nothing half-admitted
    # a fitting request still admits and serves normally:
    assert pool.admit(StreamRequest(1, 0, _utterance(401, 10)), 0)
    results, now = [], 0
    while len(results) < 1:
        fin, adv = pool.tick(now)
        results += fin
        now += max(adv, 1)
    assert results[0].req_id == 1 and results[0].logits.shape[0] == 10

    # pre-sizing beyond the limit is a configuration error, caught early:
    with pytest.raises(ValueError, match="max_buffer_frames"):
        SessionPool(eb, capacity=1, max_frames=128, max_buffer_frames=64)


def test_append_rejects_frames_past_growth_limit(engines):
    """Incremental admission enforces the same ceiling: an append that
    would push a stream past max_buffer_frames raises, and the already-
    received frames still serve to completion."""
    from repro.serving.scheduler import SessionPool
    _, eb = engines
    pool = SessionPool(eb, capacity=1, max_frames=16, chunk_frames=4,
                       max_buffer_frames=32)
    feats = _utterance(410, 30)
    assert pool.admit_stream(5, 0, feats=feats)
    with pytest.raises(ValueError, match="growth limit"):
        pool.append_frames(5, _utterance(411, 8))
    pool.finish_stream(5)                        # the 30 frames stand
    results, now = [], 0
    while len(results) < 1:
        fin, adv = pool.tick(now)
        results += fin
        now += max(adv, 1)
    assert results[0].logits.shape[0] == 30


@pytest.mark.parametrize("chunk", [1, 4])
def test_incremental_admission_matches_full_admission(engines, chunk):
    """admit_stream + append_frames + finish_stream produces the same
    logits as admitting the complete utterance (one-frame and 4-frame
    chunks) — the contract the async front-end is built on."""
    e1, eb = engines
    feats = _utterance(420, 11)
    ref = np.asarray(e1.run_utterance(jnp.asarray(feats)))
    from repro.serving.scheduler import SessionPool
    pool = SessionPool(eb, capacity=2, max_frames=16, chunk_frames=chunk)
    assert pool.admit_stream(0, 0, feats=feats[:3])
    results, now, fed = [], 0, 3
    while len(results) < 1:
        if fed < 11:
            pool.append_frames(0, feats[fed:fed + 4])
            fed += 4
            if fed >= 11:
                pool.finish_stream(0)
        fin, adv = pool.tick(now)
        results += fin
        now += max(adv, 1)
    np.testing.assert_allclose(results[0].logits, ref, atol=1e-5)


def test_spmv_path_selection_parity(model):
    """Forcing the scatter path and the dense-mirror path over the same
    packed weights must agree (batch-1 and pooled)."""
    params, cfg = model
    outs = {}
    for path in ("scatter", "dense"):
        ecfg = EngineConfig(theta=THETA, gamma=GAMMA, m=M,
                            capacity_frac=1.0, spmv_path=path)
        e1 = SpartusEngine(params, cfg, ecfg)
        eb = BatchedSpartusEngine(params, cfg, ecfg)
        assert (e1.layers[0].w_dense_t is not None) == (path == "dense")
        feats = _utterance(90, 6)
        ref = np.asarray(e1.run_utterance(jnp.asarray(feats)))
        results, _ = serve_requests(eb, [StreamRequest(0, 0, feats)],
                                    capacity=2)
        np.testing.assert_allclose(results[0].logits, ref, atol=1e-5)
        outs[path] = ref
    np.testing.assert_allclose(outs["scatter"], outs["dense"], atol=1e-4)

    with pytest.raises(ValueError, match="spmv_path"):
        SpartusEngine(params, cfg,
                      EngineConfig(gamma=GAMMA, m=M, spmv_path="gather"))


def test_batched_ops_match_unbatched():
    """kernels.ops *_batch entry points == per-row loop of the scalar ops."""
    key = jax.random.key(7)
    b, f, cap = 4, 24, 8
    x = jax.random.normal(key, (b, f))
    x_hat = jax.random.normal(jax.random.key(8), (b, f)) * 0.1
    d_b, xh_b, nnz_b = ops.delta_encode_batch(x, x_hat, 0.1)
    idx_b, val_b, drop_b = ops.select_active_columns_batch(d_b, cap)
    for i in range(b):
        d, xh, nnz = ops.delta_encode(x[i], x_hat[i], 0.1)
        idx, val, drop = ops.select_active_columns(d, cap)
        np.testing.assert_array_equal(np.asarray(d_b[i]), np.asarray(d))
        np.testing.assert_array_equal(np.asarray(xh_b[i]), np.asarray(xh))
        assert int(nnz_b[i]) == int(nnz)
        np.testing.assert_array_equal(np.asarray(idx_b[i]), np.asarray(idx))
        np.testing.assert_array_equal(np.asarray(val_b[i]), np.asarray(val))
        assert int(drop_b[i]) == int(drop)

    dm = jax.random.normal(jax.random.key(9), (b, 4, 16))
    c = jax.random.normal(jax.random.key(10), (b, 16))
    h_b, c_b = ops.lstm_pointwise_batch(dm, c)
    for i in range(b):
        h, cn = ops.lstm_pointwise(dm[i], c[i])
        np.testing.assert_allclose(np.asarray(h_b[i]), np.asarray(h),
                                   atol=1e-7)
        np.testing.assert_allclose(np.asarray(c_b[i]), np.asarray(cn),
                                   atol=1e-7)
