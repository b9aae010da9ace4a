"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps.  Under
JAX_PLATFORMS=cpu the kernels lower to Pallas' interpreter (kernel bodies
execute on CPU; BlockSpec tiling semantics fully exercised); that they
compile for a TPU is pinned by tests/test_tpu_compile.py.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import apply_cbtd, blen_for, cbcsc_decode, cbcsc_encode
from repro.kernels import ops, ref, stsp_spmv
from repro.kernels.delta_encode import delta_encode_pallas
from repro.kernels.lstm_pointwise import lstm_pointwise_pallas
from repro.kernels.stsp_spmv import stsp_spmv_scatter_batch_pallas

TOL = {jnp.float32: dict(rtol=1e-6, atol=1e-6),
       jnp.bfloat16: dict(rtol=2e-2, atol=2e-2)}


# -- delta_encode -----------------------------------------------------------


@pytest.mark.parametrize("f", [1024, 2048, 8192])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("theta", [0.0, 0.1, 0.5])
def test_delta_encode_kernel(f, dtype, theta):
    k1, k2 = jax.random.split(jax.random.key(f + int(theta * 10)))
    x = jax.random.normal(k1, (3, f), dtype)          # 3 pool slots
    x_hat = x + jax.random.normal(k2, (3, f), dtype) * 0.2
    d, xh, nnz = delta_encode_pallas(x, x_hat, theta)
    d_ref, xh_ref, nnz_ref = jax.vmap(
        lambda a, b: ref.delta_encode_ref(a, b, theta))(x, x_hat)
    np.testing.assert_allclose(np.asarray(d, np.float32),
                               np.asarray(d_ref, np.float32), **TOL[dtype])
    np.testing.assert_allclose(np.asarray(xh, np.float32),
                               np.asarray(xh_ref, np.float32), **TOL[dtype])
    np.testing.assert_array_equal(np.asarray(nnz), np.asarray(nnz_ref))


def test_delta_encode_wrapper_pads_ragged():
    x = jax.random.normal(jax.random.key(0), (1147,))
    x_hat = jnp.zeros((1147,))
    d, xh, nnz = ops.delta_encode(x, x_hat, 0.3, use_pallas=True)
    d_ref, xh_ref, nnz_ref = ref.delta_encode_ref(x, x_hat, 0.3)
    np.testing.assert_allclose(np.asarray(d), np.asarray(d_ref), rtol=1e-6)
    assert int(nnz) == int(nnz_ref)
    assert d.shape == (1147,)


# -- lstm_pointwise ---------------------------------------------------------


@pytest.mark.parametrize("h", [512, 1024, 4096])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_lstm_pointwise_kernel(h, dtype):
    k1, k2 = jax.random.split(jax.random.key(h))
    dm = jax.random.normal(k1, (2, 4, h), dtype)      # 2 pool slots
    c = jax.random.normal(k2, (2, h), dtype)
    hh, cc = lstm_pointwise_pallas(dm, c)
    h_ref, c_ref = jax.vmap(ref.lstm_pointwise_ref)(dm, c)
    np.testing.assert_allclose(np.asarray(hh, np.float32),
                               np.asarray(h_ref, np.float32), **TOL[dtype])
    np.testing.assert_allclose(np.asarray(cc, np.float32),
                               np.asarray(c_ref, np.float32), **TOL[dtype])


def test_lstm_pointwise_wrapper_ragged():
    dm = jax.random.normal(jax.random.key(1), (4, 700))
    c = jax.random.normal(jax.random.key(2), (700,))
    hh, cc = ops.lstm_pointwise(dm, c, use_pallas=True)
    h_ref, c_ref = ref.lstm_pointwise_ref(dm, c)
    np.testing.assert_allclose(np.asarray(hh), np.asarray(h_ref), rtol=1e-5,
                               atol=1e-6)


# -- stsp_spmv --------------------------------------------------------------


def _cbcsc_case(seed, h, q, m, gamma):
    w = apply_cbtd(
        jax.random.normal(jax.random.key(seed), (h, q)) + 0.01, gamma, m, 1.0
    )
    return w, cbcsc_encode(w, m, blen=blen_for(h, m, gamma))


@pytest.mark.parametrize("h,q,m,gamma,k", [
    (64, 32, 8, 0.75, 8),
    (128, 96, 16, 0.9, 16),
    (256, 128, 32, 0.5, 32),
    (512, 256, 64, 0.94, 24),
])
def test_stsp_spmv_kernel_vs_dense(h, q, m, gamma, k):
    w, enc = _cbcsc_case(h + q, h, q, m, gamma)
    kd, kv = jax.random.split(jax.random.key(k))
    idx = jax.random.permutation(kd, q)[:k].astype(jnp.int32)
    ds_vals = jax.random.normal(kv, (k,))
    y = ops.stsp_spmv(enc.val, enc.lidx, idx, ds_vals, s=enc.s, use_pallas=True)
    # dense oracle: sparse delta vector through the dense pruned matrix
    ds = jnp.zeros((q,)).at[idx].set(ds_vals)
    y_dense = w @ ds
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_dense),
                               rtol=1e-4, atol=1e-4)
    # and vs the jnp oracle of the kernel math:
    y_ref = ref.stsp_spmv_ref(enc.val, enc.lidx, idx, ds_vals, enc.s)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-5)


def test_stsp_spmv_padding_is_noop():
    w, enc = _cbcsc_case(7, 64, 32, 8, 0.75)
    idx = jnp.array([3, 10, 0, 0], jnp.int32)   # 2 padded slots pointing at col 0
    ds_vals = jnp.array([1.0, -2.0, 0.0, 0.0])
    y = ops.stsp_spmv(enc.val, enc.lidx, idx, ds_vals, s=enc.s, use_pallas=True)
    y_expect = w[:, 3] * 1.0 + w[:, 10] * (-2.0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_expect),
                               rtol=1e-5, atol=1e-5)


def test_stsp_spmv_duplicate_indices_accumulate():
    w, enc = _cbcsc_case(9, 64, 32, 8, 0.5)
    idx = jnp.array([5, 5], jnp.int32)
    ds_vals = jnp.array([1.0, 1.0])
    y = ops.stsp_spmv(enc.val, enc.lidx, idx, ds_vals, s=enc.s, use_pallas=True)
    np.testing.assert_allclose(np.asarray(y), np.asarray(2.0 * w[:, 5]),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_stsp_spmv_dtypes(dtype):
    w, enc = _cbcsc_case(11, 128, 64, 16, 0.75)
    enc_t = type(enc)(val=enc.val.astype(dtype), lidx=enc.lidx, valid=enc.valid,
                      h=enc.h, m=enc.m, blen=enc.blen)
    idx = jnp.arange(12, dtype=jnp.int32)
    ds_vals = jax.random.normal(jax.random.key(1), (12,), dtype)
    y = ops.stsp_spmv(enc_t.val, enc_t.lidx, idx, ds_vals, s=enc.s,
                      use_pallas=True)
    y_ref = ref.stsp_spmv_ref(enc_t.val, enc_t.lidx, idx, ds_vals, enc.s)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), **TOL[dtype])


# -- batched scatter kernel + dense-gather fallback -------------------------
#
# Parity sweep of every batched SpMV implementation against the per-row
# one-hot oracle (ref.stsp_spmv_ref) over (S, M, BLEN, K, B) shapes spanning
# both regimes of the path heuristic: S*(1-gamma) < 1 (scatter wins) and
# >= 1 (dense-gather mirror wins).


def _batched_case(seed, h, q, m, gamma, k, b):
    w, enc = _cbcsc_case(seed, h, q, m, gamma)
    keys = jax.random.split(jax.random.key(seed + 1), b)
    idx = jax.vmap(lambda kk: jax.random.permutation(kk, q)[:k])(keys)
    idx = idx.astype(jnp.int32)
    ds = jax.random.normal(jax.random.key(seed + 2), (b, k))
    y_ref = jnp.stack([ref.stsp_spmv_ref(enc.val, enc.lidx, idx[i], ds[i],
                                         enc.s) for i in range(b)])
    return w, enc, idx, ds, y_ref


# (h, q, m, gamma, k, b): s = h/m in {4, 8, 16, 32, 128}, blen in {1..8}
BATCH_SWEEP = [
    (32, 16, 8, 0.75, 4, 1),        # s=4,  blen=1, single slot
    (64, 32, 8, 0.75, 8, 3),        # s=8,  blen=2
    (128, 96, 16, 0.9, 16, 4),      # s=8,  blen=1
    (256, 128, 16, 0.9375, 24, 5),  # s=16, blen=1 (paper's gamma)
    (256, 128, 8, 0.5, 32, 2),      # s=32, blen=16, half-dense
    (2048, 256, 16, 0.9375, 48, 8), # s=128: the old one-hot cliff regime
]


@pytest.mark.parametrize("h,q,m,gamma,k,b", BATCH_SWEEP)
def test_scatter_batch_kernel_parity_sweep(h, q, m, gamma, k, b):
    _, enc, idx, ds, y_ref = _batched_case(h + q + b, h, q, m, gamma, k, b)
    y = stsp_spmv_scatter_batch_pallas(enc.val, enc.lidx, idx, ds, s=enc.s)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("h,q,m,gamma,k,b", BATCH_SWEEP)
def test_dense_gather_batch_parity_sweep(h, q, m, gamma, k, b):
    _, enc, idx, ds, y_ref = _batched_case(h + q + b, h, q, m, gamma, k, b)
    w_dense = cbcsc_decode(enc, jnp.float32)
    y = ops.delta_spmv_dense_gather_batch(w_dense, idx, ds)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-5)
    # and through the public batched entry point's w_dense route:
    y2 = ops.stsp_spmv_batch(enc.val, enc.lidx, idx, ds, s=enc.s,
                             w_dense=w_dense)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(y2))


@pytest.mark.parametrize("h,q,m,gamma,k,b", BATCH_SWEEP)
def test_scatter_ref_matches_onehot_ref(h, q, m, gamma, k, b):
    _, enc, idx, ds, y_ref = _batched_case(h + q + b, h, q, m, gamma, k, b)
    y = jnp.stack([ref.stsp_spmv_scatter_ref(enc.val, enc.lidx, idx[i],
                                             ds[i], enc.s) for i in range(b)])
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-5)


def test_scatter_batch_kernel_slot_groups(monkeypatch):
    """A pool whose NZI/NZV tables outgrow SMEM_TABLE_BYTES is served in
    slot groups, one pallas_call each (here 2 + 2 + 1 slots), with the
    same rows as the reference."""
    _, enc, idx, ds, y_ref = _batched_case(11, 256, 128, 16, 0.9375, 24, 5)
    monkeypatch.setattr(stsp_spmv, "SMEM_TABLE_BYTES", 2 * 8 * 24)
    # the unjitted body: a cached trace would keep the old grouping
    fn = functools.partial(stsp_spmv_scatter_batch_pallas.__wrapped__,
                           s=enc.s)
    jaxpr = str(jax.make_jaxpr(fn)(enc.val, enc.lidx, idx, ds))
    assert jaxpr.count("platform_index") == 3
    np.testing.assert_allclose(
        np.asarray(fn(enc.val, enc.lidx, idx, ds)), np.asarray(y_ref),
        rtol=1e-5, atol=1e-5)


def test_scatter_batch_kernel_duplicate_columns_accumulate():
    """A column listed twice in one slot's NZI list contributes twice —
    the scatter-add must accumulate, not overwrite."""
    w, enc = _cbcsc_case(9, 64, 32, 8, 0.5)
    idx = jnp.array([[5, 5, 7], [7, 5, 5]], jnp.int32)
    ds = jnp.array([[1.0, 1.0, 0.5], [0.5, 1.0, 1.0]])
    y = stsp_spmv_scatter_batch_pallas(enc.val, enc.lidx, idx, ds, s=enc.s)
    expect = 2.0 * w[:, 5] + 0.5 * w[:, 7]
    np.testing.assert_allclose(np.asarray(y[0]), np.asarray(expect),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(y[1]), np.asarray(expect),
                               rtol=1e-5, atol=1e-5)
    y_d = ops.delta_spmv_dense_gather_batch(cbcsc_decode(enc, jnp.float32),
                                            idx, ds)
    np.testing.assert_allclose(np.asarray(y_d), np.asarray(y),
                               rtol=1e-5, atol=1e-5)


def test_scatter_batch_kernel_padding_is_noop():
    """Padded NZI slots (idx=0, ds=0) must not perturb the accumulator even
    though their CBCSC slab is fetched and scattered."""
    w, enc = _cbcsc_case(7, 64, 32, 8, 0.75)
    idx = jnp.array([[3, 10, 0, 0]], jnp.int32)
    ds = jnp.array([[1.0, -2.0, 0.0, 0.0]])
    y = stsp_spmv_scatter_batch_pallas(enc.val, enc.lidx, idx, ds, s=enc.s)
    expect = w[:, 3] * 1.0 + w[:, 10] * (-2.0)
    np.testing.assert_allclose(np.asarray(y[0]), np.asarray(expect),
                               rtol=1e-5, atol=1e-5)


def test_scatter_batch_kernel_padded_lidx_duplicates():
    """BLEN-padding entries all carry lidx=0 (duplicate local indices with
    val=0): the scatter must add exact zeros at row 0, not corrupt it.
    Forced by encoding with blen > max occupancy."""
    h, q, m = 64, 24, 8
    w = apply_cbtd(jax.random.normal(jax.random.key(3), (h, q)) + 0.01,
                   0.75, m, 1.0)
    enc = cbcsc_encode(w, m, blen=blen_for(h, m, 0.75) + 3)  # extra padding
    idx = jnp.array([[1, 4, 9]], jnp.int32)
    ds = jnp.array([[0.3, -1.2, 2.0]])
    y = stsp_spmv_scatter_batch_pallas(enc.val, enc.lidx, idx, ds, s=enc.s)
    expect = 0.3 * w[:, 1] - 1.2 * w[:, 4] + 2.0 * w[:, 9]
    np.testing.assert_allclose(np.asarray(y[0]), np.asarray(expect),
                               rtol=1e-5, atol=1e-5)


def test_stsp_spmv_batch_all_paths_agree():
    """Public batched entry point: XLA scatter, Pallas scatter and dense
    mirror must agree on the same inputs."""
    _, enc, idx, ds, y_ref = _batched_case(77, 128, 64, 16, 0.875, 12, 4)
    y_xla = ops.stsp_spmv_batch(enc.val, enc.lidx, idx, ds, s=enc.s)
    y_pal = ops.stsp_spmv_batch(enc.val, enc.lidx, idx, ds, s=enc.s,
                                use_pallas=True)
    y_den = ops.stsp_spmv_batch(enc.val, enc.lidx, idx, ds, s=enc.s,
                                w_dense=cbcsc_decode(enc, jnp.float32))
    for y in (y_xla, y_pal, y_den):
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   rtol=1e-5, atol=1e-5)


def test_spmv_path_heuristic():
    """Large-S models must route to the dense mirror (never the O(S) path);
    small-S CBCSC stays on the scatter kernel."""
    assert ops.spmv_use_dense_gather(s=128, gamma=0.9375)   # 8 >= 1
    assert ops.spmv_use_dense_gather(s=32, gamma=0.75)      # 8 >= 1
    assert not ops.spmv_use_dense_gather(s=8, gamma=0.9375)  # 0.5 < 1
    assert not ops.spmv_use_dense_gather(s=15, gamma=0.9375)


# -- CBCSC pack-time BLEN enforcement (clip mode) ----------------------------
# (lives here, not in test_cbcsc.py, because that module importorskips on
# hypothesis and these regressions must always run)


def test_cbcsc_overflow_clip_keeps_largest():
    """on_overflow='clip' enforces BLEN by dropping the smallest-|w|
    nonzeros per subcolumn; survivors decode exactly, dropped become 0."""
    # one column, M=1, S=4: subcolumn [1, -3, 2, -0.5], BLEN=2
    w = jnp.array([[1.0], [-3.0], [2.0], [-0.5]])
    enc = cbcsc_encode(w, m=1, blen=2, on_overflow="clip")
    dec = np.asarray(cbcsc_decode(enc))
    np.testing.assert_allclose(dec[:, 0], [0.0, -3.0, 2.0, 0.0])
    assert int(np.asarray(enc.valid).sum()) == 2


def test_cbcsc_overflow_clip_is_lossless_when_balanced():
    """Clip mode on an already-balanced matrix == raise-mode encoding."""
    w = apply_cbtd(jax.random.normal(jax.random.key(5), (32, 8)) + 0.01,
                   0.75, 4, 1.0)
    blen = blen_for(32, 4, 0.75)
    a = cbcsc_encode(w, 4, blen=blen)
    b = cbcsc_encode(w, 4, blen=blen, on_overflow="clip")
    np.testing.assert_array_equal(np.asarray(a.val), np.asarray(b.val))
    np.testing.assert_array_equal(np.asarray(a.lidx), np.asarray(b.lidx))
    np.testing.assert_array_equal(np.asarray(a.valid), np.asarray(b.valid))


def test_cbcsc_overflow_clip_preserves_stream_order():
    """Survivors keep Alg. 3's ascending-k order inside each subcolumn
    even though selection is by magnitude."""
    w = jnp.array([[0.5], [0.0], [3.0], [-2.0]])   # M=1, S=4, k order
    enc = cbcsc_encode(w, m=1, blen=2, on_overflow="clip")
    np.testing.assert_array_equal(np.asarray(enc.lidx).ravel(), [2, 3])
    np.testing.assert_allclose(np.asarray(enc.val).ravel(), [3.0, -2.0])


# -- wrapper-level integration ----------------------------------------------


def test_select_active_columns_basic():
    delta = jnp.array([0.0, 0.5, 0.0, -2.0, 0.1, 0.0])
    idx, vals, dropped = ops.select_active_columns(delta, capacity=4)
    got = sorted((int(i), float(v)) for i, v in zip(idx, vals) if v != 0)
    assert [g[0] for g in got] == [1, 3, 4]
    assert [g[1] for g in got] == pytest.approx([0.5, -2.0, 0.1])
    assert int(dropped) == 0


def test_select_active_columns_overflow_keeps_largest():
    delta = jnp.array([0.1, -0.9, 0.5, 0.0, 0.3])
    idx, vals, dropped = ops.select_active_columns(delta, capacity=2)
    kept = {int(i) for i, v in zip(idx, vals) if v != 0}
    assert kept == {1, 2}          # two largest magnitudes
    assert int(dropped) == 2       # 0.1 and 0.3 dropped


def test_dense_topk_fused_matches_select_plus_gather():
    """The fused dense-mirror SpMV (capacity clip in the dense domain,
    lax.cond-guarded) must reproduce select_active_columns_batch +
    delta_spmv_dense_gather_batch BIT-exactly — including boundary ties
    (broken toward the lower index), rows that overflow capacity, rows
    that don't, and all-zero rows."""
    b, q, h, k = 6, 48, 32, 12
    w = jnp.asarray(
        np.asarray(jax.random.normal(jax.random.key(0), (h, q))))
    rng = np.random.default_rng(1)
    cases = []
    dense = rng.standard_normal((b, q)).astype(np.float32)       # overflow
    cases.append(dense)
    sparse = dense * (rng.random((b, q)) < 0.1)                  # underflow
    cases.append(sparse.astype(np.float32))
    tied = np.zeros((b, q), np.float32)                          # boundary tie
    tied[:, : k + 4] = 0.5
    tied[:, 1] = -0.5                                            # sign-tie too
    cases.append(tied)
    cases.append(np.zeros((b, q), np.float32))                   # nothing fired
    mixed = np.zeros((b, q), np.float32)                         # per-row mix
    mixed[0] = dense[0]
    mixed[2, :3] = 1.0
    cases.append(mixed)
    for delta in cases:
        delta = jnp.asarray(delta)
        idx, vals, dropped_ref = ops.select_active_columns_batch(delta, k)
        y_ref = ops.delta_spmv_dense_gather_batch(w, idx, vals)
        y, dropped = ops.delta_spmv_dense_topk_batch(
            jnp.asarray(w.T), delta, k)
        np.testing.assert_array_equal(np.asarray(y), np.asarray(y_ref))
        np.testing.assert_array_equal(np.asarray(dropped),
                                      np.asarray(dropped_ref))
    # capacity >= Q short-circuit: nothing can drop, delta flows through
    y, dropped = ops.delta_spmv_dense_topk_batch(
        jnp.asarray(w.T), jnp.asarray(cases[0]), q)
    np.testing.assert_array_equal(np.asarray(dropped), 0)
    np.testing.assert_allclose(
        np.asarray(y), np.asarray(cases[0]) @ np.asarray(w).T, atol=1e-5)


def test_full_delta_step_via_kernels_matches_dense():
    """End-to-end single DeltaLinear step through the kernel trio equals the
    dense masked computation: encode -> select -> stsp_spmv."""
    h, q, m, gamma = 128, 96, 16, 0.75
    w, enc = _cbcsc_case(21, h, q, m, gamma)
    x = jax.random.normal(jax.random.key(22), (q,))
    x_hat = x + jax.random.normal(jax.random.key(23), (q,)) * 0.3
    theta = 0.2

    delta, new_xh, nnz = ops.delta_encode(x, x_hat, theta, use_pallas=True)
    idx, vals, dropped = ops.select_active_columns(delta, capacity=q)
    assert int(dropped) == 0
    y = ops.stsp_spmv(enc.val, enc.lidx, idx, vals, s=enc.s, use_pallas=True)

    d_ref, _, _ = ref.delta_encode_ref(x, x_hat, theta)
    np.testing.assert_allclose(np.asarray(y), np.asarray(w @ d_ref),
                               rtol=1e-4, atol=1e-4)


def test_xla_and_pallas_paths_agree():
    w, enc = _cbcsc_case(31, 256, 128, 32, 0.9)
    idx = jnp.arange(20, dtype=jnp.int32) * 3
    vals = jax.random.normal(jax.random.key(3), (20,))
    y_p = ops.stsp_spmv(enc.val, enc.lidx, idx, vals, s=enc.s, use_pallas=True)
    y_x = ops.stsp_spmv(enc.val, enc.lidx, idx, vals, s=enc.s, use_pallas=False)
    np.testing.assert_allclose(np.asarray(y_p), np.asarray(y_x), rtol=1e-5,
                               atol=1e-5)


def test_dense_gather_path():
    w = jax.random.normal(jax.random.key(5), (64, 32))
    idx = jnp.array([1, 5, 9], jnp.int32)
    vals = jnp.array([0.5, -1.0, 2.0])
    y = ops.delta_spmv_dense_gather(w, idx, vals)
    ds = jnp.zeros((32,)).at[idx].set(vals)
    np.testing.assert_allclose(np.asarray(y), np.asarray(w @ ds), rtol=1e-6)
