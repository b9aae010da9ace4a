"""DeltaGRU — the prior Delta Network RNN (Neil et al. 2017; DeltaRNN FPGA'18).

Implemented as the baseline the paper extends (Sec. II: "The DN algorithm
was only studied and implemented as DeltaGRU. The DeltaLSTM extends the DN
algorithm to LSTM RNNs").  Used in benchmarks to compare DeltaLSTM against
the prior art's algorithmic behaviour.

GRU formulation (cuDNN variant, as used by DeltaGRU so that the reset gate
applies to the *recurrent matmul output* — this is what makes the delta
memory decomposition exact):

    r_t = σ(W_xr x_t + W_hr h_{t-1} + b_r)
    u_t = σ(W_xu x_t + W_hu h_{t-1} + b_u)
    c_t = tanh(W_xc x_t + r_t ⊙ (W_hc h_{t-1} + b_hc) + b_xc)
    h_t = (1-u_t) ⊙ c_t + u_t ⊙ h_{t-1}

Delta memories: M_r, M_u accumulate both matmul streams; the candidate gate
needs the recurrent stream kept separate (M_hc) because of the r_t gating.

The session pool serves this cell (serving/engine.py ``pack_gru_layer``):
``stacked_weight_matrix`` lays the four accumulators out as one
[4H, D+H] matrix, so the pool's delta encoder and SpMV routes run on the
concatenated ``[x, h]`` exactly as for the DeltaLSTM, and
``initial_delta_memories`` gives the accumulators' values at t = 0.
"""
from __future__ import annotations

from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.delta_lstm import delta_threshold

Params = Dict[str, Any]


class DeltaGRUState(NamedTuple):
    h: jax.Array       # [H]
    x_hat: jax.Array   # [D]
    h_hat: jax.Array   # [H]
    m_r: jax.Array     # [H]
    m_u: jax.Array     # [H]
    m_xc: jax.Array    # [H]
    m_hc: jax.Array    # [H]


def init_gru_params(
    key: jax.Array, input_dim: int, hidden_dim: int, dtype=jnp.float32
) -> Params:
    k1, k2 = jax.random.split(key)
    bound = 1.0 / jnp.sqrt(hidden_dim)
    # stacked (r, u, c) along the first axis
    w_x = jax.random.uniform(k1, (3 * hidden_dim, input_dim), dtype, -bound, bound)
    w_h = jax.random.uniform(k2, (3 * hidden_dim, hidden_dim), dtype, -bound, bound)
    b_x = jnp.zeros((3, hidden_dim), dtype)
    b_h = jnp.zeros((3, hidden_dim), dtype)
    return {"w_x": w_x, "w_h": w_h, "b_x": b_x, "b_h": b_h}


def gru_step(params: Params, h: jax.Array, x: jax.Array) -> jax.Array:
    hdim = h.shape[-1]
    px = (params["w_x"] @ x).reshape(3, hdim) + params["b_x"]
    ph = (params["w_h"] @ h).reshape(3, hdim) + params["b_h"]
    r = jax.nn.sigmoid(px[0] + ph[0])
    u = jax.nn.sigmoid(px[1] + ph[1])
    c = jnp.tanh(px[2] + r * ph[2])
    return (1.0 - u) * c + u * h


def init_delta_gru_state(
    input_dim: int, hidden_dim: int, params: Optional[Params] = None, dtype=jnp.float32
) -> DeltaGRUState:
    # one buffer per field: leaves sharing a buffer reject donation if the
    # state is ever passed through a donating entry point
    def z() -> jnp.ndarray:
        return jnp.zeros((hidden_dim,), dtype)

    if params is not None:
        b_x, b_h = params["b_x"].astype(dtype), params["b_h"].astype(dtype)
        m_r, m_u = b_x[0] + b_h[0], b_x[1] + b_h[1]
        m_xc, m_hc = b_x[2], b_h[2]
    else:
        m_r, m_u, m_xc, m_hc = z(), z(), z(), z()
    return DeltaGRUState(
        h=z(), x_hat=jnp.zeros((input_dim,), dtype), h_hat=z(),
        m_r=m_r, m_u=m_u, m_xc=m_xc, m_hc=m_hc,
    )


def delta_gru_step(
    params: Params, state: DeltaGRUState, x: jax.Array, theta: float | jax.Array
) -> Tuple[DeltaGRUState, jax.Array, Dict[str, jax.Array]]:
    hdim = state.h.shape[-1]
    dx, x_hat = delta_threshold(x, state.x_hat, theta)
    dh, h_hat = delta_threshold(state.h, state.h_hat, theta)

    px = (params["w_x"] @ dx).reshape(3, hdim)
    ph = (params["w_h"] @ dh).reshape(3, hdim)
    m_r = state.m_r + px[0] + ph[0]
    m_u = state.m_u + px[1] + ph[1]
    m_xc = state.m_xc + px[2]
    m_hc = state.m_hc + ph[2]

    r = jax.nn.sigmoid(m_r)
    u = jax.nn.sigmoid(m_u)
    c = jnp.tanh(m_xc + r * m_hc)
    h = (1.0 - u) * c + u * state.h

    aux = {
        "nnz_dx": jnp.sum(dx != 0).astype(jnp.int32),
        "nnz_dh": jnp.sum(dh != 0).astype(jnp.int32),
    }
    new = DeltaGRUState(h=h, x_hat=x_hat, h_hat=h_hat,
                        m_r=m_r, m_u=m_u, m_xc=m_xc, m_hc=m_hc)
    return new, h, aux


def gru_layer(params: Params, xs: jax.Array) -> jax.Array:
    hdim = params["w_h"].shape[-1]

    def step(h, x):
        h = gru_step(params, h, x)
        return h, h

    _, hs = jax.lax.scan(step, jnp.zeros((hdim,), xs.dtype), xs)
    return hs


def delta_gru_layer(
    params: Params, xs: jax.Array, theta: float | jax.Array,
    state: Optional[DeltaGRUState] = None,
) -> Tuple[jax.Array, DeltaGRUState, Dict[str, jax.Array]]:
    input_dim = params["w_x"].shape[-1]
    hdim = params["w_h"].shape[-1]
    if state is None:
        state = init_delta_gru_state(input_dim, hdim, params, xs.dtype)

    def step(carry, x):
        carry, h, aux = delta_gru_step(params, carry, x, theta)
        return carry, (h, aux["nnz_dx"], aux["nnz_dh"])

    state, (hs, nnz_dx, nnz_dh) = jax.lax.scan(step, state, xs)
    return hs, state, {"nnz_dx": nnz_dx, "nnz_dh": nnz_dh}


def stacked_weight_matrix(params: Params) -> jax.Array:
    """The [4H, D+H] matrix whose product with the concatenated delta
    ``[Δx, Δh]`` updates the four delta memories in one matvec.  Row
    blocks (r, u, xc, hc): ``[W_xr W_hr]``, ``[W_xu W_hu]``, ``[W_xc 0]``
    and ``[0 W_hc]`` — the xc block is zero on the h-columns and the hc
    block on the x-columns, so a quarter of a dense [4H, D+H] product
    multiplies structural zeros."""
    hdim, d = params["w_h"].shape[1], params["w_x"].shape[1]
    w_x, w_h = params["w_x"], params["w_h"]
    top = jnp.concatenate([w_x[:2 * hdim], w_h[:2 * hdim]], axis=1)
    xc = jnp.concatenate(
        [w_x[2 * hdim:], jnp.zeros((hdim, hdim), w_x.dtype)], axis=1)
    hc = jnp.concatenate(
        [jnp.zeros((hdim, d), w_h.dtype), w_h[2 * hdim:]], axis=1)
    return jnp.concatenate([top, xc, hc], axis=0)


def initial_delta_memories(params: Params) -> jax.Array:
    """``[4, H]`` delta memories at t = 0, in the stack's row order:
    (b_xr + b_hr, b_xu + b_hu, b_xc, b_hc) — what ``init_delta_gru_state``
    puts in ``m_r, m_u, m_xc, m_hc``."""
    b_x, b_h = params["b_x"], params["b_h"]
    return jnp.stack([b_x[0] + b_h[0], b_x[1] + b_h[1], b_x[2], b_h[2]])
