"""DeltaGRU through the session pool: the pack, the chunked pool against
the plain reference (core/delta_gru.py with the FCL and logit head),
per-slot independence, snapshots, and the matvec MAC counters.

The weights are drawn on the int8 grid of the pack's power-of-two scale,
so the pack stores them exactly and the reference computes from the same
numbers as the pool.
"""
from collections import deque

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import delta_gru, int8_pack
from repro.core.quantization import pow2_scale_for
from repro.kernels import ops
from repro.kernels import ref as kref
from repro.models import lstm_am
from repro.serving import (
    BatchedSpartusEngine,
    EngineConfig,
    PoolObservability,
    SpartusEngine,
    StreamRequest,
    serve_requests,
)
from repro.serving import checkpoint as ckptlib
from repro.serving.batched_engine import BatchedGRULayerState
from repro.serving.scheduler import SessionPool

INPUT_DIM, HIDDEN, CLASSES, M, THETA = 20, 64, 11, 8, 0.1
LENS = [5, 40, 17, 70, 3, 33, 1, 48]


def _gru_cfg():
    return lstm_am.LSTMAMConfig(input_dim=INPUT_DIM, hidden_dim=HIDDEN,
                                n_layers=2, n_classes=CLASSES, cell="gru")


def _on_grid(lp):
    """One layer's weights on the int8 grid of its stack's scale, and
    nonzero biases (so the initial delta memories are exercised)."""
    scale = pow2_scale_for(delta_gru.stacked_weight_matrix(lp), 8)
    snap = lambda w: int8_pack(w, scale)[0].astype(jnp.float32) * scale  # noqa: E731
    kb = jax.random.split(jax.random.key(int(lp["w_x"].shape[1])), 2)
    return {"w_x": snap(lp["w_x"]), "w_h": snap(lp["w_h"]),
            "b_x": 0.1 * jax.random.normal(kb[0], lp["b_x"].shape),
            "b_h": 0.1 * jax.random.normal(kb[1], lp["b_h"].shape)}


@pytest.fixture(scope="module")
def params():
    p = lstm_am.init_params(jax.random.key(3), _gru_cfg())
    return {**p, "gru": [_on_grid(lp) for lp in p["gru"]]}


def _engine(params, theta=THETA):
    return BatchedSpartusEngine(
        params, _gru_cfg(),
        EngineConfig(theta=theta, gamma=0.0, m=M, capacity_frac=1.0))


@pytest.fixture(scope="module")
def engine(params):
    return _engine(params)


def _utterance(key, t):
    return np.asarray(jax.random.normal(jax.random.key(key), (t, INPUT_DIM)),
                      np.float32)


@pytest.fixture(scope="module")
def feats():
    return [_utterance(500 + i, t) for i, t in enumerate(LENS)]


def _head(params, hs):
    y = jax.nn.relu(hs @ params["fcl"]["w"].T + params["fcl"]["b"])
    return np.asarray(y @ params["logit"]["w"].T + params["logit"]["b"])


def _reference(params, x, theta):
    """core/delta_gru.py layer by layer, then the head (plain GRU at
    theta None)."""
    hs = jnp.asarray(x)
    for lp in params["gru"]:
        if theta is None:
            hs = delta_gru.gru_layer(lp, hs)
        else:
            hs, _, _ = delta_gru.delta_gru_layer(lp, hs, theta)
    return _head(params, hs)


def _reqs(feats):
    return [StreamRequest(100 + i, i % 3, f) for i, f in enumerate(feats)]


# -- the pack ----------------------------------------------------------------


def test_gru_pack_has_the_zero_blocks_and_no_overflow(engine, params):
    """gamma 0 gives BLEN = S, so a dense stack packs with nothing
    clipped; the xc rows are zero on the h-columns and the hc rows on the
    x-columns; the packed stack and delta memories are the GRU's."""
    h = HIDDEN
    assert engine.cell == "gru" and engine.pack_overflow_count() == 0
    for layer, lp in zip(engine.layers, params["gru"]):
        d = layer.input_dim
        assert layer.cell == "gru"
        assert layer.enc.blen == layer.enc.s == 4 * h // M
        assert layer.w_dense_t is not None        # S * (1 - gamma) >= 1
        w = np.asarray(layer.w_dense_t).T         # [4H, D+H]
        np.testing.assert_array_equal(
            w, np.asarray(delta_gru.stacked_weight_matrix(lp)))
        assert not w[2 * h:3 * h, d:].any() and not w[3 * h:, :d].any()
        assert np.all(w[:2 * h] != 0) or np.mean(w[:2 * h] != 0) > 0.95
        np.testing.assert_array_equal(
            np.asarray(layer.bias),
            np.stack([lp["b_x"][0] + lp["b_h"][0],
                      lp["b_x"][1] + lp["b_h"][1],
                      lp["b_x"][2], lp["b_h"][2]]))
        # the dense mirror multiplies 4H x (D+H), the model holds 3H a column
        assert layer.matvec_macs == 4 * h * (d + h)
        assert layer.matvec_macs_held == 3 * h * (d + h)


def test_gru_refuses_the_lstm_only_routes(params):
    with pytest.raises(ValueError, match="Pallas"):
        BatchedSpartusEngine(params, _gru_cfg(), EngineConfig(
            theta=THETA, gamma=0.0, m=M, use_pallas=True))
    e1 = SpartusEngine(params, _gru_cfg(), EngineConfig(
        theta=THETA, gamma=0.0, m=M, capacity_frac=1.0))
    with pytest.raises(NotImplementedError, match="DeltaLSTM"):
        e1.run_utterance(jnp.asarray(_utterance(1, 2)))


def test_gru_pointwise_matches_the_cell_equations():
    dm = jax.random.normal(jax.random.key(1), (3, 4, HIDDEN))
    hp = jax.random.normal(jax.random.key(2), (3, HIDDEN))
    got = np.asarray(ops.gru_pointwise_batch(dm, hp))
    r, u = jax.nn.sigmoid(dm[:, 0]), jax.nn.sigmoid(dm[:, 1])
    want = (1 - u) * jnp.tanh(dm[:, 2] + r * dm[:, 3]) + u * hp
    # 1e-6: the jitted batch fuses the expression, the eager one does not
    # (an ulp apart)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(
        got[1], kref.gru_pointwise_ref(dm[1], hp[1]), rtol=1e-6, atol=1e-6)


# -- the pool against the plain reference ------------------------------------


@pytest.mark.parametrize("chunk", [1, 32])
def test_chunked_pool_matches_the_plain_reference(engine, params, feats,
                                                  chunk):
    """Eight utterances through three slots, arriving over three steps:
    slots retire mid-chunk and are re-admitted (reset) at boundaries.
    Tolerance 1e-5: the pool forms W_x dx + W_h dh as one stacked [D+H]
    contraction, the reference as two, so sums differ by reassociation
    (~1e-7 relative), and no delta lies that close to theta here."""
    results, _ = serve_requests(engine, _reqs(feats), capacity=3,
                                chunk_frames=chunk)
    assert sorted(r.req_id for r in results) == [100 + i
                                                 for i in range(len(feats))]
    for r in results:
        want = _reference(params, feats[r.req_id - 100], THETA)
        np.testing.assert_allclose(r.logits, want, rtol=1e-5, atol=1e-5)


def test_pool_at_theta_zero_is_a_plain_gru(params, feats):
    """At theta 0 every delta fires and the delta memories telescope to
    the GRU's pre-activations.  Tolerance 1e-4: the pool sums T frames of
    deltas where the GRU multiplies once, so rounding grows with the
    length (70 frames here); core/test_delta_lstm holds 2e-5 over 20."""
    results, _ = serve_requests(_engine(params, theta=0.0), _reqs(feats),
                                capacity=4, chunk_frames=8)
    for r in results:
        want = _reference(params, feats[r.req_id - 100], None)
        np.testing.assert_allclose(r.logits, want, rtol=1e-4, atol=1e-4)


def test_a_slots_logits_do_not_depend_on_the_other_slots(engine, feats):
    """The same utterance in slot 0 of a 4-slot pool beside two different
    sets of neighbours: the same compiled program, so bit for bit."""
    def serve(neighbours):
        reqs = [StreamRequest(0, 0, feats[3])] + [
            StreamRequest(i + 1, 0, f) for i, f in enumerate(neighbours)]
        results, _ = serve_requests(engine, reqs, capacity=4,
                                    chunk_frames=16)
        return {r.req_id: r.logits for r in results}[0]

    a = serve([feats[0], feats[1], feats[2]])
    b = serve([feats[5], feats[7], _utterance(9, 64)])
    np.testing.assert_array_equal(a, b)


# -- snapshots ---------------------------------------------------------------


def _drain(pool, pending, now=0, collected=None):
    out = dict(collected or {})
    pending = deque(pending)
    for _ in range(10_000):
        while pending and pool.n_free and pool.admit(pending[0], now):
            pending.popleft()
        if not (pending or pool.n_active or pool.has_pending):
            break
        finished, adv = pool.tick(now)
        out.update((r.req_id, r.logits) for r in finished)
        now += max(adv, 1)
    else:
        raise AssertionError("pool did not drain")
    out.update((r.req_id, r.logits) for r in pool.flush())
    return out


def test_gru_pool_snapshot_restores_bit_for_bit(engine, feats, tmp_path):
    """A GRU pool checkpointed after one chunk and restored into a fresh
    pool of another capacity finishes every session with the logits of
    an uninterrupted run; a single session migrates likewise."""
    reqs = [StreamRequest(100 + i, 0, f) for i, f in enumerate(feats[:4])]
    refs = _drain(SessionPool(engine, 3, max_frames=128, chunk_frames=8),
                  reqs)
    pool = SessionPool(engine, 3, max_frames=128, chunk_frames=8)
    pending = deque(reqs)
    while pending and pool.n_free and pool.admit(pending[0], 0):
        pending.popleft()
    got = {r.req_id: r.logits for r in pool.tick(0)[0]}
    snap = pool.snapshot_session(101)
    assert {"layer0/s_hat", "layer0/h", "layer0/dm"} <= set(snap.arrays)
    assert "layer0/c" not in snap.arrays
    for r in pool.checkpoint(str(tmp_path / "ck")):
        got[r.req_id] = r.logits
    big = SessionPool(engine, 5, max_frames=128, chunk_frames=8)
    big.restore(str(tmp_path / "ck"))
    assert all(isinstance(st, BatchedGRULayerState)
               for st in big.state.layers)
    got = _drain(big, pending, now=8, collected=got)
    assert sorted(got) == sorted(refs)
    for k in refs:
        np.testing.assert_array_equal(got[k], refs[k])

    other = SessionPool(engine, 2, max_frames=128, chunk_frames=8)
    assert other.restore_session(snap)
    np.testing.assert_array_equal(_drain(other, [], now=8)[101], refs[101])


def test_lstm_snapshot_is_refused_by_a_gru_pool(engine, feats):
    """The fingerprint names the cell: a DeltaLSTM pool's checkpoint does
    not restore into a DeltaGRU pool of the same shapes, nor the reverse,
    and a DeltaLSTM session snapshot is refused before a slot is used."""
    cfg = lstm_am.LSTMAMConfig(input_dim=INPUT_DIM, hidden_dim=HIDDEN,
                               n_layers=2, n_classes=CLASSES)
    lstm = BatchedSpartusEngine(
        lstm_am.init_params(jax.random.key(0), cfg), cfg,
        EngineConfig(theta=THETA, gamma=0.0, m=M, capacity_frac=1.0))
    assert ckptlib.engine_fingerprint(lstm)["cell"] == "lstm"
    assert ckptlib.engine_fingerprint(engine)["cell"] == "gru"
    src = SessionPool(lstm, 2, max_frames=64, chunk_frames=8)
    assert src.admit(StreamRequest(7, 0, feats[1]), 0)
    src.tick(0)
    lstm_ckpt, lstm_snap = src.snapshot(), src.snapshot_session(7)
    gru_pool = SessionPool(engine, 2, max_frames=64, chunk_frames=8)
    with pytest.raises(ValueError, match="fingerprint"):
        ckptlib.restore_into(gru_pool, lstm_ckpt)
    with pytest.raises(ValueError, match="gru"):
        gru_pool.restore_session(lstm_snap)
    assert gru_pool.n_active == 0
    gsrc = SessionPool(engine, 2, max_frames=64, chunk_frames=8)
    assert gsrc.admit(StreamRequest(8, 0, feats[1]), 0)
    gsrc.tick(0)
    with pytest.raises(ValueError, match="fingerprint"):
        ckptlib.restore_into(SessionPool(lstm, 2, max_frames=64,
                                         chunk_frames=8), gsrc.snapshot())


# -- the matvec MAC counters ---------------------------------------------------


def test_matvec_counters_read_the_held_share(engine, feats):
    """Every dispatch tallies slots x frames x the layers' route sizes:
    the GRU's dense mirror holds 3 of every 4 rows; the CBTD-pruned
    LSTM's holds m x BLEN of 4H (BLEN / S)."""
    obs = PoolObservability()
    serve_requests(engine, _reqs(feats), capacity=3, chunk_frames=8,
                   observability=obs)
    samples = obs.timeseries.snapshot()
    macs = sum(s["matvec_macs"] for s in samples)
    held = sum(s["matvec_macs_held"] for s in samples)
    frames = sum(s["frames"] for s in samples)
    per_frame = sum(4 * HIDDEN * (l.input_dim + HIDDEN)
                    for l in engine.layers)
    assert macs >= frames * per_frame and macs % (3 * per_frame) == 0
    assert held / macs == 0.75

    cfg = lstm_am.LSTMAMConfig(input_dim=INPUT_DIM, hidden_dim=32,
                               n_layers=2, n_classes=CLASSES)
    lstm = BatchedSpartusEngine(
        lstm_am.cbtd_prune_stacks(lstm_am.init_params(jax.random.key(0),
                                                      cfg), 0.75, 4),
        cfg, EngineConfig(theta=0.05, gamma=0.75, m=4, capacity_frac=1.0))
    obs = PoolObservability()
    serve_requests(lstm, _reqs(feats[:3]), capacity=2, chunk_frames=4,
                   observability=obs)
    samples = obs.timeseries.snapshot()
    assert (sum(s["matvec_macs_held"] for s in samples) /
            sum(s["matvec_macs"] for s in samples)) == 0.25
