"""Pallas TPU kernel: spatio-temporal sparse matrix-vector product — the
heart of the Spartus accelerator (Fig. 2/4/9), adapted for TPU.

Semantics (one DeltaLSTM/DeltaLinear step, for every slot b of a pool):

    y[b, H] = sum_{k < K} ds_vals[b, k] * W[:, idx[b, k]]

where W is stored in CBCSC (core/cbcsc.py): ``val/lidx [Q, M, BLEN]``,
row r = lidx*M + pe.  Only the K *active* columns (nonzero deltas) are
touched — temporal sparsity — and only BLEN nonzeros per subcolumn are
stored/fetched — spatial sparsity.

TPU adaptation of the FPGA dataflow (DESIGN.md §2):
  * NZI list -> scalar-prefetched ``[B, K]`` index table in SMEM: the
    grid's DMA engine fetches exactly the CBCSC slabs of active columns
    from HBM (``index_map`` reads ``idx_ref[b, k]``) — the "CTRL
    generates physical WMEM addresses from NZIs" step of Sec. IV-A.  The
    matching NZV values ride along as a second SMEM table, and a pool
    whose tables outgrow SMEM is split into slot groups;
  * per-PE LUTRAM write -> BLEN unrolled selects into an ``[S, M]`` fp32
    VMEM accumulator: pair j of every PE adds ``ds*val[:, j]`` to the row
    its ``lidx[:, j]`` names (a compare against a sublane iota), which is
    the scatter-add Mosaic cannot lower, written as BLEN vector passes;
  * MAC-array partial sums -> the accumulator block is revisited across
    the K grid steps of a slot ("arbitrary" dimension semantics) and
    written back once per slot; slots are independent ("parallel").

Workload balance: CBCSC guarantees every "PE" (lane) sees exactly BLEN
pairs per active column — the same argument as the paper's Sec. III-C,
with the memory-interface arbitration replaced by a fixed-shape DMA.

The XLA routes (ops.stsp_spmv_xla, the pack-time dense mirror
ops.delta_spmv_dense_topk_batch) implement the identical math.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.lowering import pallas_call

# Bytes of SMEM one call's scalar-prefetched NZI/NZV tables may take.  All
# of SMEM is 1 MiB on a v5e, and Mosaic keeps its own scalars there: a
# 64-slot pool with every column in its NZI list (two [64, 2048] 4-byte
# tables, 1 MiB) does not fit, so the pool is served in slot groups.
SMEM_TABLE_BYTES = 256 * 1024


def _stsp_scatter_batch_kernel(idx_ref, ds_ref, val_ref, lidx_ref, y_ref):
    """One (slot, active-column) pair per grid step."""
    b, k = pl.program_id(0), pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)

    _, s, m = y_ref.shape
    val = ds_ref[b, k] * val_ref[0].astype(jnp.float32)   # [M, BLEN]
    lidx = lidx_ref[0].astype(jnp.int32)                   # [M, BLEN]
    row = jax.lax.broadcasted_iota(jnp.int32, (s, m), 0)
    acc = y_ref[0]
    for j in range(val.shape[1]):
        acc += jnp.where(row == lidx[:, j][None, :], val[:, j][None, :], 0.0)
    y_ref[0] = acc


@functools.partial(jax.jit, static_argnames=("s",))
def stsp_spmv_scatter_batch_pallas(
    val: jax.Array,      # [Q, M, BLEN]
    lidx: jax.Array,     # [Q, M, BLEN] int32 (or int8-packed)
    idx: jax.Array,      # [B, K] int32 active columns per slot (pad: any id)
    ds_vals: jax.Array,  # [B, K] float (pad: 0.0)
    *,
    s: int,
) -> jax.Array:
    """Batched STSP SpMxSpV: y [B, S*M] = sum_k ds[b, k] * W[:, idx[b, k]].

    One pallas_call per group of slots whose NZI/NZV tables fit
    SMEM_TABLE_BYTES (the whole pool when they do): grid (slots, K), slots
    parallel, the K active columns of each slot revisiting that slot's
    [S, M] accumulator.  Only active columns' CBCSC slabs are fetched from
    HBM — the weight-fetch economy of the paper's NZI dataflow, kept intact
    under batching (no one-hot materialisation, no [K, M, BLEN, S]
    temporaries).
    """
    _, m, blen = val.shape
    b, k_total = idx.shape
    idx, ds_vals = idx.astype(jnp.int32), ds_vals.astype(jnp.float32)
    group = max(1, SMEM_TABLE_BYTES // (8 * k_total))
    slab = pl.BlockSpec((1, m, blen),
                        lambda bb, kk, idx_ref, ds_ref: (idx_ref[bb, kk], 0, 0))
    ys = []
    for lo in range(0, b, group):
        n = min(group, b - lo)
        ys.append(pallas_call(
            _stsp_scatter_batch_kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=(n, k_total),
                in_specs=[slab, slab],
                out_specs=pl.BlockSpec(
                    (1, s, m), lambda bb, kk, idx_ref, ds_ref: (bb, 0, 0)),
            ),
            out_shape=jax.ShapeDtypeStruct((n, s, m), jnp.float32),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary")),
        )(idx[lo:lo + n], ds_vals[lo:lo + n], val, lidx))
    return jnp.concatenate(ys).reshape(b, s * m)
