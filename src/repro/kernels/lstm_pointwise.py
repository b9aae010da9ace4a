"""Pallas TPU kernel: fused LSTM gate pointwise math (the Spartus HPE,
Fig. 8 — sigmoid/tanh units + pointwise multiply-add after the adder
trees).

Input is every slot's delta-memory row DM ``[B, 4, H]`` (gate order
i, g, f, o per eq. 8) and cell state c ``[B, H]``; outputs are (h, c').
DM is viewed as ``[B, 4H]`` with each gate a whole number of 128-lane
rows, so a grid step holds one sublane tile of slots with all four gate
rows resident in VMEM: the whole cell update is a single VPU pass with
no HBM round-trips between gates.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.delta_encode import LANES, slot_rows
from repro.kernels.lowering import pallas_call


def _lstm_pointwise_kernel(dm_ref, c_ref, h_ref, c_out_ref):
    h = c_ref.shape[-1]
    i = jax.nn.sigmoid(dm_ref[:, 0 * h:1 * h])
    g = jnp.tanh(dm_ref[:, 1 * h:2 * h])
    f = jax.nn.sigmoid(dm_ref[:, 2 * h:3 * h])
    o = jax.nn.sigmoid(dm_ref[:, 3 * h:4 * h])
    c_new = f * c_ref[...] + i * g
    h_ref[...] = o * jnp.tanh(c_new)
    c_out_ref[...] = c_new


@jax.jit
def lstm_pointwise_pallas(dm: jax.Array, c: jax.Array):
    """dm: [B, 4, H], c: [B, H] (any H; padded to lane rows here)
    -> (h [B, H], c' [B, H])."""
    b, h_dim = c.shape
    assert dm.shape == (b, 4, h_dim)
    pad = (-h_dim) % LANES
    if pad:
        dm = jnp.pad(dm, ((0, 0), (0, 0), (0, pad)))
        c = jnp.pad(c, ((0, 0), (0, pad)))
    hp = h_dim + pad
    rows = slot_rows(b, c.dtype)
    block = pl.BlockSpec((rows, hp), lambda i: (i, 0))

    h, c_new = pallas_call(
        _lstm_pointwise_kernel,
        grid=(b // rows,),
        in_specs=[pl.BlockSpec((rows, 4 * hp), lambda i: (i, 0)), block],
        out_specs=[block, block],
        out_shape=[
            jax.ShapeDtypeStruct((b, hp), dm.dtype),
            jax.ShapeDtypeStruct((b, hp), dm.dtype),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
    )(dm.reshape(b, 4 * hp), c)
    return h[:, :h_dim], c_new[:, :h_dim]
