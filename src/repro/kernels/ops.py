"""jit'd public wrappers for the Pallas kernels + XLA fallback paths.

``use_pallas`` selects the Pallas implementation (compiled by Mosaic on a
TPU, interpreted only where the program is lowered for the CPU — see
kernels/lowering.py); the default XLA path implements identical math with
gather/einsum.  The Pallas kernels are batched over a leading slot
dimension; the single-session wrappers call them with one slot.

Also hosts ``select_active_columns`` — the fixed-capacity NZI list builder
(the static-shape translation of the Spartus DPE's NZV/NZI streams).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.analysis.contracts import hotpath_contract
from repro.kernels.delta_encode import delta_encode_pallas
from repro.kernels.lstm_pointwise import lstm_pointwise_pallas
from repro.kernels.stsp_spmv import stsp_spmv_scatter_batch_pallas
from repro.kernels import ref as _ref


@functools.partial(jax.jit, static_argnames=("use_pallas", "act_bits",
                                             "act_frac_bits"))
def delta_encode(
    x: jax.Array, x_hat: jax.Array, theta,
    *, use_pallas: bool = False,
    act_bits: int | None = None, act_frac_bits: int = 8,
):
    """Eqs. (4)-(5). x, x_hat: [F] any length (padded internally).
    Returns (delta [F], new_x_hat [F], nnz scalar int32).

    ``act_bits`` (static) quantizes the threshold comparison to the Qm.n
    activation grid (Q8.8 by default): x and theta are snapped to the
    grid and the reference state stores the quantized x, so temporal
    sparsity is computed on the same values the fixed-point arithmetic
    sees.  None (default) keeps the fp32 comparison bit-identical to
    before."""
    if not use_pallas:
        return _ref.delta_encode_ref(x, x_hat, theta, act_bits, act_frac_bits)
    delta, new_xh, nnz = delta_encode_pallas(
        x[None], x_hat[None], theta,
        act_bits=act_bits, act_frac_bits=act_frac_bits)
    return delta[0], new_xh[0], nnz[0]


@functools.partial(jax.jit, static_argnames=("use_pallas",))
def lstm_pointwise(dm: jax.Array, c: jax.Array, *, use_pallas: bool = False):
    """HPE gate math. dm: [4, H], c: [H] -> (h, c')."""
    if not use_pallas:
        return _ref.lstm_pointwise_ref(dm, c)
    h, c_new = lstm_pointwise_pallas(dm[None], c[None])
    return h[0], c_new[0]


@functools.partial(jax.jit, static_argnames=("capacity",))
def select_active_columns(
    delta: jax.Array, capacity: int
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Build the fixed-capacity NZI/NZV lists from a (sparse) delta vector.

    Deterministic policy: if more than ``capacity`` deltas fired, keep the
    largest |delta| (drop-smallest overflow, DESIGN.md §9); padding slots
    get idx=0, val=0.  Returns (idx [K] int32, vals [K], n_dropped).

    Implemented with ``lax.top_k`` on the magnitudes (un-fired slots
    masked to -1): ~5x faster than the full argsort it replaces, and
    bit-identical — top_k orders descending and breaks ties toward the
    lower index, exactly like the old stable ascending argsort of the
    negated magnitudes (the per-frame serving hot path spent more time in
    this sort than in the SpMV itself)."""
    idx, vals, n_dropped = _select_active_columns_batch(delta[None], capacity)
    return idx[0], vals[0], n_dropped[0]


def _select_active_columns_batch(
    delta: jax.Array, capacity: int
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Batched NZI/NZV core shared by the scalar and _batch wrappers.
    delta [B, F] -> (idx [B, K] int32, vals [B, K], n_dropped [B])."""
    k = min(capacity, delta.shape[-1])
    mag = jnp.abs(delta)
    fired = delta != 0
    masked = jnp.where(fired, mag, -1.0)         # fired mags are > 0
    top_mag, top_idx = jax.lax.top_k(masked, k)
    valid = top_mag > 0
    idx = jnp.where(valid, top_idx, 0).astype(jnp.int32)
    vals = jnp.where(valid, jnp.take_along_axis(delta, top_idx, axis=-1),
                     0).astype(delta.dtype)
    n_dropped = jnp.maximum(
        jnp.sum(fired.astype(jnp.int32), axis=-1) - capacity, 0)
    return idx, vals, n_dropped


def stsp_spmv_xla(
    val: jax.Array, lidx: jax.Array, idx: jax.Array, ds_vals: jax.Array, s: int
) -> jax.Array:
    """XLA gather+scatter-add path (identical math to the Pallas kernel).

    Historically this decompressed CBCSC with an S-wide one-hot einsum —
    O(S) work per stored nonzero, which cratered the batched pool at large
    subcolumn lengths (hidden>=256 / m=16).  The scatter-add formulation
    (``ref.stsp_spmv_scatter_ref``) touches each fetched (value, lidx) pair
    exactly once."""
    return _ref.stsp_spmv_scatter_ref(val, lidx, idx, ds_vals, s)


@functools.partial(jax.jit, static_argnames=("s", "use_pallas"))
def stsp_spmv(
    val: jax.Array,
    lidx: jax.Array,
    idx: jax.Array,
    ds_vals: jax.Array,
    *,
    s: int,
    use_pallas: bool = False,
    scale: jax.Array | None = None,
) -> jax.Array:
    """y [H] = sum_k ds_vals[k] * W_cbcsc[:, idx[k]]  (fp32).

    ``scale`` dequantizes int8 payloads in the epilogue: the kernels cast
    ``val`` to fp32 internally, so y*scale with a power-of-two per-tensor
    scale is exactly the fp32 result on pre-scaled weights (the multiply
    is exact and commutes with the adds)."""
    if not use_pallas:
        y = stsp_spmv_xla(val, lidx, idx, ds_vals, s)
    else:
        y = stsp_spmv_scatter_batch_pallas(val, lidx, idx[None], ds_vals[None],
                                           s=s)[0]
    if scale is not None:
        y = y * scale
    return y


# -- batched (slot-dimension) entry points ---------------------------------
#
# The serving scheduler advances a whole pool of independent streaming
# sessions per frame (serving/batched_engine.py).  One jitted call covers
# the entire pool: the XLA routes vmap the single-session math over a
# leading slot dimension B (weights broadcast, per-slot state maps), and
# the Pallas kernels carry B in their grid.  Numerics per row are identical
# to the unbatched calls, which is what makes the batched engine
# bit-comparable to `SpartusEngine`.


@functools.partial(jax.jit, static_argnames=("use_pallas", "act_bits",
                                             "act_frac_bits"))
def delta_encode_batch(
    x: jax.Array, x_hat: jax.Array, theta,
    *, use_pallas: bool = False,
    act_bits: int | None = None, act_frac_bits: int = 8,
):
    """Batched eqs. (4)-(5).  x, x_hat: [B, F] -> (delta [B, F],
    new_x_hat [B, F], nnz [B] int32).  ``act_bits`` as in delta_encode."""
    if use_pallas:
        return delta_encode_pallas(x, x_hat, theta, act_bits=act_bits,
                                   act_frac_bits=act_frac_bits)
    fn = functools.partial(_ref.delta_encode_ref, act_bits=act_bits,
                           act_frac_bits=act_frac_bits)
    return jax.vmap(fn, in_axes=(0, 0, None))(x, x_hat, theta)


@functools.partial(jax.jit, static_argnames=("capacity",))
def select_active_columns_batch(
    delta: jax.Array, capacity: int
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Batched NZI/NZV list builder.  delta: [B, F] ->
    (idx [B, K] int32, vals [B, K], n_dropped [B]).  Runs the batched
    top_k directly (not a vmap of the scalar op) so the one sort covers
    the whole pool."""
    return _select_active_columns_batch(delta, capacity)


def spmv_use_dense_gather(s: int, gamma: float) -> bool:
    """Path heuristic for the batched SpMV: the CBCSC scatter path does
    BLEN ~= S*(1-gamma) adds per (PE, active column), the dense-gather path
    does S multiply-adds but on the MXU with no index traffic.  Once
    ``S*(1-gamma) >= 1`` the scatter path has no arithmetic advantage left
    per lane, so large-S models route to the dense mirror and never touch
    the O(S)-per-nonzero decompression that caused the hidden>=256 / m=16
    performance cliff."""
    return s * (1.0 - gamma) >= 1.0


@hotpath_contract("stsp_spmv_batch")
@functools.partial(jax.jit, static_argnames=("s", "use_pallas"))
def stsp_spmv_batch(
    val: jax.Array,
    lidx: jax.Array,
    idx: jax.Array,
    ds_vals: jax.Array,
    *,
    s: int,
    use_pallas: bool = False,
    w_dense: jax.Array | None = None,
    scale: jax.Array | None = None,
) -> jax.Array:
    """Batched STSP SpMxSpV: shared CBCSC weights, per-slot active lists.
    idx, ds_vals: [B, K] -> y [B, H].

    Three implementations, selected at pack time (serving/engine.py applies
    ``spmv_use_dense_gather``):
      * ``w_dense`` given — dense-gather fallback: one [B, K] panel gather
        from the pack-time dense mirror + an MXU matmul (no CBCSC decode in
        the hot loop at all);
      * ``use_pallas`` — single batched Pallas scatter kernel over grid
        (B, K) (one pallas_call for the whole pool, not a vmap of B calls);
      * otherwise — vmap of the XLA scatter-add path.

    ``scale`` dequantizes int8 payloads (CBCSC val or dense mirror) in the
    epilogue — one fp32 multiply on the [B, H] result, exact for the
    power-of-two per-tensor scales the pack emits, so weight memory stays
    int8 at rest on every route.
    """
    if w_dense is not None:
        y = delta_spmv_dense_gather_batch(w_dense, idx, ds_vals)
    elif use_pallas:
        y = stsp_spmv_scatter_batch_pallas(val, lidx, idx, ds_vals, s=s)
    else:
        fn = functools.partial(stsp_spmv, s=s, use_pallas=False)
        y = jax.vmap(fn, in_axes=(None, None, 0, 0))(val, lidx, idx, ds_vals)
    if scale is not None:
        y = y * scale
    return y


@functools.partial(jax.jit, static_argnames=("use_pallas",))
def lstm_pointwise_batch(
    dm: jax.Array, c: jax.Array, *, use_pallas: bool = False
):
    """Batched HPE gate math.  dm: [B, 4, H], c: [B, H] -> (h, c') [B, H]."""
    if use_pallas:
        return lstm_pointwise_pallas(dm, c)
    return jax.vmap(_ref.lstm_pointwise_ref)(dm, c)


@hotpath_contract("gather_frames", op_budget={"gather": 1})
def gather_frames(frames: jax.Array, cursor: jax.Array) -> jax.Array:
    """Gather each slot's current frame from its device-resident buffer.

    frames [B, T_buf, D], cursor [B] int32 -> x [B, D].  The cursor is
    clamped to the buffer (slots whose cursor ran past their utterance are
    masked inactive by the caller, so the clamped garbage row is never
    consumed).  Deliberately not jit-wrapped: it is traced inline by the
    serving step/chunk functions — including inside `jax.lax.scan`, where
    the chunked tick loop (batched_engine.step_chunk) calls it once per
    scan iteration with the carried cursor.

    Implemented as ``take_along_axis`` over the time axis (batch dims
    aligned) rather than ``frames[arange(B), cursor]``: identical rows,
    but the aligned-batch form partitions cleanly when the slot dimension
    is sharded across devices — GSPMD keeps the gather local per shard,
    where the iota-indexed form inserted an all-gather of the indices
    plus an all-reduce of the result on EVERY scan iteration (measured on
    the emulated-device mesh; the sharded pool's zero-communication
    steady state depends on this)."""
    t_buf = frames.shape[1]
    idx = jnp.minimum(cursor, t_buf - 1).astype(jnp.int32)[:, None, None]
    return jnp.take_along_axis(frames, idx, axis=1)[:, 0]


@hotpath_contract("bank_rows", forbid_ops=("scatter",),
                  op_budget={"dynamic-update-slice": 1})
def bank_rows(
    buf: jax.Array, rows: jax.Array, start: jax.Array
) -> jax.Array:
    """Bank one chunk's stacked logits into the per-slot output buffers.

    buf [B, T_pad, C], rows [N, B, C] (a lax.scan's stacked per-iteration
    outputs), start [B] int32 -> updated buf, where slot b's rows land at
    ``buf[b, start[b] : start[b]+N]``.  One vmapped dynamic_update_slice
    per chunk — far cheaper on CPU than a scatter per scan iteration.
    The caller guarantees ``start[b] + N <= T_pad`` (the serving pool pads
    the buffer's time axis by chunk_frames), so the slice never clamps;
    rows written past a session's utterance length are scratch that no
    reader ever consumes (retirement fetches ``[:n_frames]``)."""
    per_slot = jnp.swapaxes(rows, 0, 1)          # [B, N, C]

    def one(buf_b, rows_b, start_b):
        return jax.lax.dynamic_update_slice(buf_b, rows_b, (start_b, 0))

    return jax.vmap(one)(buf, per_slot, start)


@hotpath_contract("gather_rows",
                  forbid_ops=("scatter", "dynamic-update-slice"))
def gather_rows(buf: jax.Array, start: jax.Array, n: int) -> jax.Array:
    """Inverse of ``bank_rows``: slice each slot's last-banked chunk back out.

    buf [B, T_pad, C], start [B] int32, static n -> rows [B, n, C], where
    row b is ``buf[b, start[b] : start[b]+n]``.  One vmapped dynamic_slice
    — the partial-logits streaming path (`SessionPool.stream_partials`)
    uses it to snapshot ONLY the chunk's rows for every live slot, so a
    streamed chunk costs a [B, n, C] copy + fetch instead of re-copying
    the whole [B, T_pad, C] output buffer.  The caller guarantees
    ``start[b] + n <= T_pad`` (the serving pool pads the buffer's time
    axis by chunk_frames), so the slice never clamps."""
    def one(buf_b, st):
        return jax.lax.dynamic_slice(buf_b, (st, 0), (n, buf_b.shape[-1]))

    return jax.vmap(one)(buf, start)


def delta_spmv_dense_gather(
    w: jax.Array, idx: jax.Array, ds_vals: jax.Array
) -> jax.Array:
    """Temporal-sparsity-only path: gather dense columns of w [H, Q] by the
    active index list and run one [H, K] x [K] MXU matmul.  Used when the
    weights are not CBCSC-packed (e.g. unpruned baselines) and as the
    batch-1 leg of the large-S dense mirror path (spmv_use_dense_gather)."""
    panel = jnp.take(w, idx, axis=1)             # [H, K]
    return panel @ ds_vals


@hotpath_contract("delta_spmv_dense_topk", forbid_ops=("transpose",),
                  op_budget={"dot": 1, "sort": 1})
@functools.partial(jax.jit, static_argnames=("capacity",))
def delta_spmv_dense_topk_batch(
    wt: jax.Array, delta: jax.Array, capacity: int,
    scale: jax.Array | None = None,
) -> Tuple[jax.Array, jax.Array]:
    """Fused capacity enforcement + dense-mirror SpMV: wt [Q, H]
    (pre-transposed mirror), delta [B, Q] -> (y [B, H], n_dropped [B]).

    The dense-mirror path never consumes the NZI/NZV *lists* — only the
    dense delta slab with the over-capacity tail zeroed.  So instead of
    top_k -> gather -> scatter-back-to-dense (the scatter dominated the
    serving step at hidden=128), enforce capacity directly in the dense
    domain: keep a column iff its |delta| beats the K-th largest, with
    boundary ties broken toward the lower index via a cumulative tie
    rank.  That reproduces ``select_active_columns_batch`` +
    ``delta_spmv_dense_gather_batch`` BIT-EXACTLY (same kept set, same
    GEMM contraction).  Two more CPU-motivated savings:

      * the clip runs under a ``lax.cond`` on "did ANY row overflow" —
        at serving sparsity the NZI capacity almost never binds, so the
        steady state pays one reduction instead of a top_k + cumsum
        (whose XLA CPU lowering costs more than the GEMM itself);
      * the mirror is stored pre-transposed [Q, H]: XLA does not hoist
        the transpose of `w.T` out of the per-tick dot on CPU, which
        made the un-transposed GEMM ~3x slower.

    ``capacity >= Q`` (nothing can ever drop) skips the cond too.

    ``scale`` dequantizes an int8 mirror in the GEMM epilogue (y*scale,
    exact for power-of-two per-tensor scales): the mirror stays int8 at
    rest and is only widened inside the GEMM fusion."""
    b, q = delta.shape
    k = min(capacity, q)
    fired = delta != 0
    n_fired = jnp.sum(fired.astype(jnp.int32), axis=-1)
    n_dropped = jnp.maximum(n_fired - capacity, 0)

    def clip(d):
        mag = jnp.abs(d)
        masked = jnp.where(d != 0, mag, -1.0)
        top_mag, _ = jax.lax.top_k(masked, k)
        thresh = top_mag[:, -1:]                  # K-th largest (or -1)
        above = (d != 0) & (mag > thresh)
        ties = (d != 0) & (mag == thresh)
        n_above = jnp.sum(above.astype(jnp.int32), axis=-1, keepdims=True)
        tie_rank = jnp.cumsum(ties.astype(jnp.int32), axis=-1)
        keep = above | (ties & (tie_rank <= k - n_above))
        return jnp.where(keep, d, 0.0)

    if k >= q:
        ds_dense = delta                          # un-fired entries are 0
    else:
        ds_dense = jax.lax.cond(
            jnp.any(n_dropped > 0), clip, lambda d: d, delta)
    y = ds_dense.astype(jnp.float32) @ wt.astype(jnp.float32)
    if scale is not None:
        y = y * scale
    return y, n_dropped


def delta_spmv_dense_gather_batch(
    w: jax.Array, idx: jax.Array, ds_vals: jax.Array
) -> jax.Array:
    """Batched dense-mirror SpMV: w [H, Q], idx/ds_vals [B, K] -> y [B, H].

    The [B, K] active lists are scattered back to a dense [B, Q] delta
    slab (one cheap gather-free scatter-add; duplicate indices accumulate,
    padding slots carry 0.0) and contracted against the mirror in a single
    [B, Q] x [Q, H] MXU matmul.  Unlike a per-slot [B, K, H] column-panel
    gather — whose weight traffic grows with B — the GEMM reads the mirror
    ONCE per tick regardless of pool size, which is exactly the
    weight-fetch amortisation continuous batching exists for.  Exploits
    temporal sparsity only; spatial sparsity is already priced into the
    pack-time mirror's zeros."""
    b, k = idx.shape
    slot = jnp.arange(b, dtype=idx.dtype)[:, None]
    ds_dense = jnp.zeros((b, w.shape[1]), jnp.float32).at[
        jnp.broadcast_to(slot, (b, k)), idx
    ].add(ds_vals.astype(jnp.float32))
    return ds_dense @ w.T.astype(jnp.float32)
