"""Pallas TPU kernel: fused delta encoding (the Spartus DPE, Fig. 6).

Computes eqs. (4)-(5) in one pass over every slot's state vector:
thresholded delta, reference-state update, and the per-slot count of
fired entries (the NZV occupancy used for capacity selection and
sparsity telemetry).

TPU mapping: the state is a ``[B, F]`` slab with F padded to whole
128-lane rows; the grid walks blocks of slot rows (one sublane tile), so
each step owns a ``[rows, F]`` VMEM block and the threshold/select runs
entirely on the VPU.  θ is one f32 scalar in SMEM.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.lowering import pallas_call

LANES = 128
SUBLANE_BYTES = 32  # one sublane tile: 8 rows of f32, 16 of bf16


def slot_rows(n_slots: int, dtype) -> int:
    """Slot rows per grid step: one sublane tile when it divides the pool,
    else the whole pool in one block (a block dim equal to the array dim
    is always a legal Mosaic tile)."""
    rows = SUBLANE_BYTES // jnp.dtype(dtype).itemsize
    return rows if n_slots % rows == 0 else n_slots


def _delta_encode_kernel(
    theta_ref, x_ref, xh_ref, delta_ref, xh_out_ref, nnz_ref,
    *, act_bits: Optional[int] = None, act_frac_bits: int = 8,
):
    x = x_ref[...]
    xh = xh_ref[...]
    if act_bits is not None:
        # Snap the incoming state to the Qm.n grid in-register (the DPE's
        # fixed-point view); xh is already on-grid by induction because
        # xh_out below stores the quantized x.  Saturating clip, matching
        # core.quantization.quantize_act (wrapper pre-quantizes theta).
        scale = 2.0 ** (-act_frac_bits)
        qmax = 2.0 ** (act_bits - 1) - 1
        x = jnp.clip(jnp.round(x / scale), -qmax - 1, qmax) * scale
    raw = x - xh
    fired = jnp.abs(raw).astype(jnp.float32) > theta_ref[0]
    delta_ref[...] = jnp.where(fired, raw, jnp.zeros_like(raw))
    xh_out_ref[...] = jnp.where(fired, x, xh)
    nnz_ref[...] = jnp.sum(fired.astype(jnp.int32), axis=1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("act_bits", "act_frac_bits"))
def delta_encode_pallas(
    x: jax.Array, x_hat: jax.Array, theta, *,
    act_bits: Optional[int] = None, act_frac_bits: int = 8,
):
    """x, x_hat: [B, F] (any F; padded to whole lane rows here).

    Returns (delta [B, F], new_x_hat [B, F], nnz [B] int32).  With
    ``act_bits`` the threshold comparison runs on the Qm.n grid (see
    ops.delta_encode); theta is snapped here, x inside the kernel.
    """
    b, f = x.shape
    pad = (-f) % LANES
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad)))
        x_hat = jnp.pad(x_hat, ((0, 0), (0, pad)))
    fp = f + pad
    rows = slot_rows(b, x.dtype)
    # θ rounded to the state dtype first, so the f32 comparison in SMEM
    # decides exactly as a comparison in that dtype would.
    theta_arr = jnp.asarray(theta, x.dtype).reshape(1)
    if act_bits is not None:
        from repro.core.quantization import quantize_act
        theta_arr = quantize_act(theta_arr, act_bits, act_frac_bits)
    block = pl.BlockSpec((rows, fp), lambda i: (i, 0))

    delta, new_xh, nnz = pallas_call(
        functools.partial(_delta_encode_kernel, act_bits=act_bits,
                          act_frac_bits=act_frac_bits),
        grid=(b // rows,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), block, block],
        out_specs=[block, block, pl.BlockSpec((rows, 1), lambda i: (i, 0))],
        out_shape=[
            jax.ShapeDtypeStruct((b, fp), x.dtype),
            jax.ShapeDtypeStruct((b, fp), x.dtype),
            jax.ShapeDtypeStruct((b, 1), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
    )(theta_arr.astype(jnp.float32), x, x_hat)
    return delta[:, :f], new_xh[:, :f], nnz[:, 0]
