"""Quantized serving end-to-end: int8 weights, Q8.8 activations.

The quant mode (``EngineConfig.quant = QuantConfig()``) stores the CBCSC
weight payloads — values, 8-bit LIDX, and the dense mirrors — as int8 at
rest and dequantizes in the SpMV epilogue (``y * scale``, a power-of-two
per-tensor scale), while the delta threshold compares Q8.8-quantized
activations.  This suite pins the mode's three load-bearing claims:

* **parity**: the quantized pool equals the quantized batch-1 engine at
  the repo's 1e-5 oracle tolerance across (capacity, chunk, spmv_path,
  shard count) — pooling adds no quantization error;
* **divergence**: quantized logits differ from fp32 logits only through
  the Q8.8 activation snap, bounded well under any decodable margin;
* **off means off**: ``quant=None`` and ``QuantConfig(enabled=False)``
  are BIT-identical to the fp32 default — same logits, same compiled
  HLO text — so the flag cannot tax the default path.

Plus the memory story (int8 operands visible in the optimized HLO, no
fp32 mirror constant baked into the module, the 4x payload shrink in
``weight_payload_bytes`` / ``ServeStats.bytes_per_slot``) and the
checkpoint fingerprint that refuses cross-format restores.
"""
from collections import deque

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.analysis import hlo
from repro.analysis.cases import lower_pool_chunk
from repro.core.quantization import QuantConfig
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
from repro.serving.scheduler import SessionPool

INPUT_DIM, HIDDEN, CLASSES = 20, 32, 11
GAMMA, M, THETA = 0.75, 4, 0.05
LENS = [5, 9, 3, 12, 1, 7]
N_DEV = jax.device_count()

multi_device = pytest.mark.skipif(
    N_DEV < 4, reason="needs 4 (emulated) devices; run under "
    "XLA_FLAGS=--xla_force_host_platform_device_count=8")


@pytest.fixture(scope="module")
def model():
    cfg = lstm_am.LSTMAMConfig(input_dim=INPUT_DIM, hidden_dim=HIDDEN,
                               n_layers=2, n_classes=CLASSES)
    params = lstm_am.init_params(jax.random.key(0), cfg)
    return lstm_am.cbtd_prune_stacks(params, gamma=GAMMA, m=M), cfg


def _ecfg(spmv_path="auto", quant=QuantConfig()):
    return EngineConfig(theta=THETA, gamma=GAMMA, m=M, capacity_frac=1.0,
                        spmv_path=spmv_path, quant=quant)


@pytest.fixture(scope="module")
def qengines(model):
    params, cfg = model
    return (SpartusEngine(params, cfg, _ecfg()),
            BatchedSpartusEngine(params, cfg, _ecfg()))


@pytest.fixture(scope="module")
def fengines(model):
    params, cfg = model
    ecfg = _ecfg(quant=None)
    return (SpartusEngine(params, cfg, ecfg),
            BatchedSpartusEngine(params, cfg, ecfg))


def _utterance(key, t):
    return np.asarray(
        jax.random.normal(jax.random.key(key), (t, INPUT_DIM)), np.float32)


@pytest.fixture(scope="module")
def workload(qengines):
    e1q, _ = qengines
    feats = [_utterance(500 + i, t) for i, t in enumerate(LENS)]
    refs = [np.asarray(e1q.run_utterance(jnp.asarray(f))) for f in feats]
    return feats, refs


def _reqs(feats):
    return [StreamRequest(100 + i, 0, f) for i, f in enumerate(feats)]


def _drain(pool, pending, *, now=0, collected=None, max_iters=10_000):
    out = dict(collected or {})
    pending = deque(pending)
    for _ in range(max_iters):
        while pending and pool.n_free and pool.admit(pending[0], now):
            pending.popleft()
        if not (pending or pool.n_active or pool.has_pending):
            break
        finished, adv = pool.tick(now)
        for r in finished:
            out[r.req_id] = r.logits
        now += max(adv, 1)
    else:
        raise AssertionError("pool did not drain")
    for r in pool.flush():
        out[r.req_id] = r.logits
    return out


# -- weights at rest ----------------------------------------------------------


def test_quant_weights_are_int8_at_rest(qengines, fengines):
    _, ebq = qengines
    _, ebf = fengines
    for lq, lf in zip(ebq.layers, ebf.layers):
        assert lq.enc.val.dtype == jnp.int8
        assert lq.enc.lidx.dtype == jnp.int8      # the paper's 8-bit LIDX
        assert lf.enc.val.dtype == jnp.float32
        if lq.w_dense_t is not None:
            assert lq.w_dense_t.dtype == jnp.int8
            assert lf.w_dense_t.dtype == jnp.float32
        # pow2 scale: the dequant multiply is an exact FPGA shift
        s = float(lq.scale)
        assert s == 2.0 ** round(np.log2(s))


def test_quant_payload_shrinks_4x(qengines, fengines):
    _, ebq = qengines
    _, ebf = fengines
    # the quantized payload terms (values + lidx + mirrors) shrink 4x
    # exactly: every element goes f32 -> int8
    assert ebf.weight_payload_bytes() == 4 * ebq.weight_payload_bytes()
    # total weight bytes shrink less (fp32 head / biases / valid masks):
    assert ebf.weight_bytes() > ebq.weight_bytes()


def test_bytes_per_slot_accounting(qengines, fengines):
    _, ebq = qengines
    _, ebf = fengines
    feats = [_utterance(520 + i, t) for i, t in enumerate(LENS[:4])]
    obs = PoolObservability()
    _, qstats = serve_requests(ebq, _reqs(feats), capacity=4,
                               chunk_frames=4, observability=obs)
    _, fstats = serve_requests(ebf, _reqs(feats), capacity=4, chunk_frames=4)
    assert 0 < qstats.bytes_per_slot < fstats.bytes_per_slot
    # the stats row carries it, and the gauge mirrors the last fold:
    assert qstats.to_dict()["bytes_per_slot"] == qstats.bytes_per_slot
    snap = obs.registry.snapshot()
    assert snap["spartus_slot_bytes"]["value"] == pytest.approx(
        qstats.bytes_per_slot)


# -- parity: quantized pool vs quantized batch-1 oracle -----------------------


@pytest.mark.parametrize("spmv_path", ["auto", "scatter"])
def test_quant_pool_vs_batch1_parity_grid(model, spmv_path):
    """Quantized serving equals the quantized batch-1 engine over the
    same (capacity, chunk_frames) x ragged-lengths grid the fp32 chunked
    suite pins — on both SpMV routes."""
    params, cfg = model
    e1 = SpartusEngine(params, cfg, _ecfg(spmv_path))
    eb = BatchedSpartusEngine(params, cfg, _ecfg(spmv_path))
    feats = [_utterance(540 + i, t) for i, t in enumerate(LENS)]
    refs = [np.asarray(e1.run_utterance(jnp.asarray(f))) for f in feats]
    reqs = [StreamRequest(i, arrival_step=2 * i, feats=feats[i])
            for i in range(len(LENS))]
    for capacity in (2, 4):
        for chunk in (1, 3, 8, 32):
            results, stats = serve_requests(eb, reqs, capacity=capacity,
                                            chunk_frames=chunk)
            assert [r.req_id for r in results] == list(range(len(LENS)))
            for r in results:
                np.testing.assert_allclose(r.logits, refs[r.req_id],
                                           atol=1e-5)
            assert stats.total_frames == sum(LENS)


@multi_device
def test_quant_sharded_pool_parity(qengines, workload):
    """Slot-sharding a quantized pool changes placement, not numerics."""
    _, eb = qengines
    feats, refs = workload
    results, _ = serve_requests(eb, _reqs(feats), capacity=4,
                                chunk_frames=4, n_devices=4)
    for r in results:
        np.testing.assert_allclose(r.logits, refs[r.req_id - 100],
                                   atol=1e-5)


# -- divergence vs fp32 -------------------------------------------------------

#: The only quant-mode divergence source is the Q8.8 activation snap in
#: the delta threshold (the int8 weight grid is what fp32 packing already
#: uses).  Measured max-abs logit difference at this scale is ~5e-4; the
#: bound leaves two orders of headroom.
DIVERGENCE_BOUND = 0.05


def test_quant_vs_fp32_divergence_bounded(qengines, fengines, workload):
    _, ebq = qengines
    e1f, ebf = fengines
    feats, qrefs = workload
    fres, _ = serve_requests(ebf, _reqs(feats), capacity=4, chunk_frames=8)
    qres, _ = serve_requests(ebq, _reqs(feats), capacity=4, chunk_frames=8)
    fby = {r.req_id: r.logits for r in fres}
    div = max(float(np.max(np.abs(r.logits - fby[r.req_id]))) for r in qres)
    assert div <= DIVERGENCE_BOUND
    # and the batch-1 engines diverge by the same mechanism and bound:
    for f, qr in zip(feats, qrefs):
        fr = np.asarray(e1f.run_utterance(jnp.asarray(f)))
        assert float(np.max(np.abs(qr - fr))) <= DIVERGENCE_BOUND


# -- off means off: bit-identity of the disabled modes ------------------------


def test_quant_disabled_is_bit_identical_to_fp32(model, fengines, workload):
    """``QuantConfig(enabled=False)`` and ``quant=None`` are the same
    fp32 path: byte-identical compiled HLO, bit-identical logits."""
    params, cfg = model
    _, ebf = fengines
    eb_off = BatchedSpartusEngine(
        params, cfg, _ecfg(quant=QuantConfig(enabled=False)))
    feats, _ = workload
    base, _ = serve_requests(ebf, _reqs(feats), capacity=4, chunk_frames=4)
    off, _ = serve_requests(eb_off, _reqs(feats), capacity=4, chunk_frames=4)
    for a, b in zip(base, off):
        assert a.req_id == b.req_id
        np.testing.assert_array_equal(a.logits, b.logits)
    assert hlo.strip_metadata(lower_pool_chunk(eb_off, feats[:4])) == \
        hlo.strip_metadata(lower_pool_chunk(ebf, feats[:4]))


# -- the compiled module: int8 operands, no baked fp32 mirror ----------------


def test_quant_hlo_keeps_int8_operands(qengines, fengines, workload):
    feats, _ = workload
    _, ebq = qengines
    _, ebf = fengines
    txt_q = lower_pool_chunk(ebq, feats[:4])
    txt_f = lower_pool_chunk(ebf, feats[:4])
    assert "s8[" in txt_q          # int8 payloads survive optimization
    assert "s8[" not in txt_f      # and never leak into the fp32 module
    for layer in ebq.layers:
        if layer.w_dense_t is None:
            continue
        r, c = layer.w_dense_t.shape
        # the mirror is an s8 constant; the ONLY f32 producer of its
        # shape is the runtime convert feeding the GEMM — a baked
        # f32 constant would mean XLA folded the dequant back in:
        assert any(f"s8[{r},{c}]" in ln and " constant(" in ln
                   for ln in txt_q.splitlines())
        assert not any(f"= f32[{r},{c}]" in ln and " constant(" in ln
                       for ln in txt_q.splitlines())


def test_quant_obs_on_off_hlo_identical(qengines, workload):
    """Observability folds stay host-side in quant mode too: attaching
    them changes not one byte of the compiled chunk step."""
    feats, _ = workload
    _, ebq = qengines
    assert lower_pool_chunk(ebq, feats[:4], PoolObservability()) == \
        lower_pool_chunk(ebq, feats[:4])


# -- checkpoint/restore -------------------------------------------------------


def test_quant_checkpoint_restore_capacity_migration(
        qengines, workload, tmp_path):
    """A quantized pool checkpointed mid-flight restores into a LARGER
    quantized pool and finishes with the uninterrupted run's logits —
    the recurrent state lives on the quantized grid, so migration has
    nothing to re-quantize."""
    _, eb = qengines
    feats, refs = workload
    pool = SessionPool(eb, 2, max_frames=16, chunk_frames=4)
    pending = deque(_reqs(feats[:4]))
    while pending and pool.n_free and pool.admit(pending[0], 0):
        pending.popleft()
    got = {r.req_id: r.logits for r in pool.tick(0)[0]}
    for r in pool.checkpoint(str(tmp_path / "qck")):
        got[r.req_id] = r.logits
    big = SessionPool(eb, 5, max_frames=16, chunk_frames=4)
    big.restore(str(tmp_path / "qck"))
    got = _drain(big, pending, now=4, collected=got)
    for i in range(4):
        assert np.array_equal(got[100 + i], refs[i])


def test_quant_fp32_restore_refusal(qengines, fengines, workload, tmp_path):
    """The engine fingerprint carries the quant format: a quantized
    checkpoint will not restore into an fp32 pool (or vice versa) — the
    recurrent state evolves on a different numeric grid, so resuming
    across formats would silently diverge rather than fail."""
    _, ebq = qengines
    _, ebf = fengines
    feats, _ = workload

    qpool = SessionPool(ebq, 2, max_frames=16, chunk_frames=4)
    assert qpool.admit(StreamRequest(0, 0, feats[1]), 0)
    qpool.tick(0)
    qpool.checkpoint(str(tmp_path / "q"))
    fpool = SessionPool(ebf, 2, max_frames=16, chunk_frames=4)
    with pytest.raises(ValueError, match="fingerprint"):
        fpool.restore(str(tmp_path / "q"))

    fpool2 = SessionPool(ebf, 2, max_frames=16, chunk_frames=4)
    assert fpool2.admit(StreamRequest(0, 0, feats[1]), 0)
    fpool2.tick(0)
    fpool2.checkpoint(str(tmp_path / "f"))
    qpool2 = SessionPool(ebq, 2, max_frames=16, chunk_frames=4)
    with pytest.raises(ValueError, match="fingerprint"):
        qpool2.restore(str(tmp_path / "f"))
    # and the fingerprints themselves disagree only on the quant entry:
    fq = ckptlib.engine_fingerprint(ebq)
    ff = ckptlib.engine_fingerprint(ebf)
    assert fq["quant"] == [8, 16, 8] and ff["quant"] is None
    assert {k: v for k, v in fq.items() if k != "quant"} == \
        {k: v for k, v in ff.items() if k != "quant"}
