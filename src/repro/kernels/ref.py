"""Pure-jnp oracles for every Pallas kernel (the correctness contract).

Each function is the mathematical spec; kernels must match to float
tolerance across the shape/dtype sweeps in tests/test_kernels.py.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.quantization import quantize_act


def delta_encode_ref(
    x: jax.Array, x_hat: jax.Array, theta: float,
    act_bits: Optional[int] = None, act_frac_bits: int = 8,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Eqs. (4)-(5): (delta, new_x_hat, nnz). x, x_hat: [F].

    With ``act_bits`` set, the threshold comparison runs on the Qm.n
    activation grid: x and theta are snapped to the grid first, and the
    updated reference state stores the *quantized* x — so x_hat stays
    on-grid by induction and every delta is an exact difference of grid
    points (what the fixed-point DPE hardware compares).
    """
    if act_bits is not None:
        x = quantize_act(x, act_bits, act_frac_bits)
        theta = quantize_act(jnp.asarray(theta, x.dtype), act_bits,
                             act_frac_bits)
    raw = x - x_hat
    fired = jnp.abs(raw) > theta
    delta = jnp.where(fired, raw, jnp.zeros_like(raw))
    new_x_hat = jnp.where(fired, x, x_hat)
    return delta, new_x_hat, jnp.sum(fired.astype(jnp.int32))


def lstm_pointwise_ref(
    dm: jax.Array, c: jax.Array
) -> Tuple[jax.Array, jax.Array]:
    """HPE post-MxV math (Sec. IV-D): dm [4, H] (i,g,f,o), c [H] -> (h, c')."""
    i = jax.nn.sigmoid(dm[0])
    g = jnp.tanh(dm[1])
    f = jax.nn.sigmoid(dm[2])
    o = jax.nn.sigmoid(dm[3])
    c_new = f * c + i * g
    h = o * jnp.tanh(c_new)
    return h, c_new


def gru_pointwise_ref(dm: jax.Array, h_prev: jax.Array) -> jax.Array:
    """DeltaGRU post-MxV math: dm [4, H] (M_r, M_u, M_xc, M_hc), h_prev
    [H] -> h.  The reset gate scales the recurrent candidate stream."""
    r = jax.nn.sigmoid(dm[0])
    u = jax.nn.sigmoid(dm[1])
    c = jnp.tanh(dm[2] + r * dm[3])
    return (1.0 - u) * c + u * h_prev


def stsp_spmv_ref(
    val: jax.Array,      # [Q, M, BLEN] CBCSC values (0-padded)
    lidx: jax.Array,     # [Q, M, BLEN] local indices
    idx: jax.Array,      # [K] active column ids (padded entries arbitrary)
    ds_vals: jax.Array,  # [K] delta values (0.0 for padding)
    s: int,              # subcolumn length H/M
) -> jax.Array:
    """y[H] = sum_k ds_vals[k] * column(idx[k]), column scattered from
    CBCSC: row r = lidx*M + pe.  The spec of the Spartus MAC arrays."""
    q, m, blen = val.shape
    v = val[idx]                                   # [K, M, BLEN]
    li = lidx[idx].astype(jnp.int32)               # [K, M, BLEN] (lidx may
    #                                                be int8-packed)
    onehot = li[..., None] == jnp.arange(s, dtype=li.dtype)   # [K,M,BLEN,S]
    contrib = jnp.einsum(
        "kmb,kmbs->ksm", v.astype(jnp.float32) * ds_vals[:, None, None],
        onehot.astype(jnp.float32),
    )                                              # [K, S, M]
    return jnp.sum(contrib, axis=0).reshape(s * m)  # row r = s*M + m


def stsp_spmv_scatter_ref(
    val: jax.Array,      # [Q, M, BLEN] CBCSC values (0-padded)
    lidx: jax.Array,     # [Q, M, BLEN] local indices
    idx: jax.Array,      # [K] active column ids (padded entries arbitrary)
    ds_vals: jax.Array,  # [K] delta values (0.0 for padding)
    s: int,              # subcolumn length H/M
) -> jax.Array:
    """Scatter-add formulation of ``stsp_spmv_ref`` — the oracle of the
    batched Pallas scatter kernel and the XLA serving path.  Each fetched
    (value, lidx) pair lands at global row r = lidx*M + pe via one
    scatter-add, O(1) per nonzero instead of the one-hot's O(S).  Must be
    numerically identical (same fp32 adds, different order) to the one-hot
    spec above."""
    q, m, blen = val.shape
    v = val[idx].astype(jnp.float32) * ds_vals[:, None, None].astype(jnp.float32)
    pe = jnp.arange(m, dtype=jnp.int32)[None, :, None]        # [1, M, 1]
    # int32 row math: an int8-packed lidx would overflow at lidx*m
    rows = lidx[idx].astype(jnp.int32) * m + pe                # [K, M, BLEN]
    return jnp.zeros((s * m,), jnp.float32).at[rows.reshape(-1)].add(
        v.reshape(-1))
