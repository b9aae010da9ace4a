"""Weights from the seed and the plain reference of a DeltaLSTM acoustic model.

Nothing here imports the program.  ``make_params`` draws one model from a
seed in one jitted call on the device: the LSTM stacks on the int8 grid
(what the paper's accelerator stores), CBTD-pruned column-balanced
(Alg. 1 at alpha = 1), and a dense float32 FCL and logit layer.  The
program packs exactly these arrays; the grid and the power-of-two scale
make its int8 pack an identity, so the reference and the program compute
from the same numbers.

``forward`` is the model in straightforward ``jax.numpy`` (paper eqs.
3-8): per layer, the thresholded delta of the concatenated
``[input, h]`` state against its reference ``s_hat``, delta memories
``dm += W @ delta``, the (i, g, f, o) gates, then ReLU(FCL) and the logit
layer.  ``reference_logits`` runs it over utterances in blocks of rows.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, List, Sequence, Tuple

import numpy as np


def seed_words(seed: int, n: int) -> List[int]:
    """``n`` independent 31-bit integers from a seed of any size."""
    words = np.random.SeedSequence(int(seed)).generate_state(n, np.uint32)
    return [int(w >> 1) for w in words]


def grid(bound: float) -> Tuple[float, int]:
    """Power-of-two step and largest code of a signed int8 grid spanning
    ``[-bound, bound]``: the step is the one the program's int8 pack
    derives from the largest weight, so the pack is exact."""
    step = 2.0 ** math.ceil(math.log2(bound / 127.0))
    qmax = int(math.floor(bound / step))
    if not 64 <= qmax <= 127:
        raise ValueError(f"bound {bound} gives an int8 grid of {qmax} codes")
    return step, qmax


def cbtd_prune(w, gamma: float, m: int):
    """Alg. 1 at alpha = 1: in every subcolumn (rows r with r % m == i of
    one column) keep the ``S - floor(S * gamma)`` largest magnitudes."""
    import jax.numpy as jnp

    h, q = w.shape
    s = h // m
    sub = w.reshape(s, m, q).transpose(1, 0, 2)             # [M, S, Q]
    ranks = jnp.argsort(jnp.argsort(jnp.abs(sub), axis=1), axis=1)
    keep = ranks >= int(s * gamma)
    return (sub * keep).transpose(1, 0, 2).reshape(h, q)


def layer_dims(cfg: dict) -> List[Tuple[int, int]]:
    d, h = cfg["input_dim"], cfg["hidden_dim"]
    return [(d if i == 0 else h, h) for i in range(cfg["n_layers"])]


@functools.lru_cache(maxsize=None)
def _params_fn(dims: Tuple[Tuple[int, int], ...], n_classes: int,
               gamma: float, m: int, lstm_scale: float):
    import jax
    import jax.numpy as jnp

    def make(key):
        keys = jax.random.split(key, len(dims) + 2)
        lstm = []
        for k, (d, h) in zip(keys, dims):
            step, qmax = grid(lstm_scale / math.sqrt(h))
            q = jax.random.randint(k, (4 * h, d + h), -qmax, qmax + 1)
            w = cbtd_prune(q.astype(jnp.float32) * step, gamma, m)
            b = jnp.zeros((4, h), jnp.float32).at[2].set(1.0)  # forget bias
            lstm.append({"w_x": w[:, :d], "w_h": w[:, d:], "b": b})
        h = dims[-1][1]
        bound = 1.0 / math.sqrt(h)
        fcl = {"w": jax.random.uniform(keys[-2], (h, h), jnp.float32,
                                       -bound, bound),
               "b": jnp.zeros((h,), jnp.float32)}
        logit = {"w": jax.random.uniform(keys[-1], (n_classes, h),
                                         jnp.float32, -bound, bound),
                 "b": jnp.zeros((n_classes,), jnp.float32)}
        return {"lstm": lstm, "fcl": fcl, "logit": logit}

    return jax.jit(make)


def make_params(key_int: int, cfg: dict) -> Dict:
    import jax

    fn = _params_fn(tuple(layer_dims(cfg)), cfg["n_classes"],
                    float(cfg["gamma"]), int(cfg["m"]),
                    float(cfg["weights"]["lstm_scale"]))
    return fn(jax.random.key(key_int))


def forward(params, feats, lengths, theta: float, dtype, precision):
    """feats [N, T, D], lengths [N] -> (logits [N, T, C], fired [L, N, T]
    active columns per layer-step, h_absmax [L] max |h| of each layer over
    the valid frames)."""
    import jax
    import jax.numpy as jnp

    def mm(a, b):
        return jnp.matmul(a, b, precision=precision)

    x = feats.astype(dtype)
    n, t = x.shape[:2]
    valid = (jnp.arange(t)[None, :] < lengths[:, None])[..., None]
    fired_all, hmax = [], []
    for lp in params["lstm"]:
        wt = jnp.concatenate([lp["w_x"], lp["w_h"]], axis=1).T.astype(dtype)
        h_dim = lp["w_h"].shape[1]
        q = wt.shape[0]
        dm0 = jnp.broadcast_to(lp["b"].reshape(-1).astype(dtype),
                               (n, 4 * h_dim))
        carry = (jnp.zeros((n, q), dtype), jnp.zeros((n, h_dim), dtype),
                 jnp.zeros((n, h_dim), dtype), dm0)

        def step(carry, xt, wt=wt, h_dim=h_dim):
            s_hat, c, h, dm = carry
            s = jnp.concatenate([xt, h], axis=-1)
            raw = s - s_hat
            fired = jnp.abs(raw) > theta
            delta = jnp.where(fired, raw, jnp.zeros_like(raw))
            s_hat = jnp.where(fired, s, s_hat)
            dm = dm + mm(delta, wt).astype(dtype)
            g = dm.reshape(n, 4, h_dim)
            i, gg = jax.nn.sigmoid(g[:, 0]), jnp.tanh(g[:, 1])
            f, o = jax.nn.sigmoid(g[:, 2]), jax.nn.sigmoid(g[:, 3])
            c = f * c + i * gg
            h = o * jnp.tanh(c)
            return (s_hat, c, h, dm), (h, jnp.sum(fired, axis=-1))

        _, (hs, fired) = jax.lax.scan(step, carry, jnp.swapaxes(x, 0, 1))
        x = jnp.swapaxes(hs, 0, 1)                           # [N, T, H]
        fired_all.append(jnp.swapaxes(fired, 0, 1))
        hmax.append(jnp.max(jnp.where(valid, jnp.abs(x), 0)))
    y = jax.nn.relu(mm(x, params["fcl"]["w"].T.astype(dtype))
                    + params["fcl"]["b"].astype(dtype))
    logits = mm(y, params["logit"]["w"].T.astype(dtype)) \
        + params["logit"]["b"].astype(dtype)
    return logits.astype(jnp.float32), jnp.stack(fired_all), jnp.stack(hmax)


@functools.lru_cache(maxsize=None)
def _forward_fn(theta: float, dtype_name: str, precision: str):
    import jax
    import jax.numpy as jnp

    return jax.jit(functools.partial(
        forward, theta=theta, dtype=jnp.dtype(dtype_name),
        precision=precision))


def reference_logits(params, utts: Sequence[np.ndarray], cfg: dict,
                     dtype: str = "float32", precision: str = "highest",
                     block: int = 16):
    """The reference over each utterance, ``block`` utterances at a time
    (rows sorted by length so that each block pads little).  Returns
    (list of [T_i, C] logits, per-layer stats dict)."""
    import jax.numpy as jnp

    fn = _forward_fn(float(cfg["theta"]), dtype, precision)
    order = sorted(range(len(utts)), key=lambda i: utts[i].shape[0])
    out: List[np.ndarray] = [None] * len(utts)
    n_layers = cfg["n_layers"]
    fired_sum = np.zeros(n_layers)
    steps = 0
    hmax = np.zeros(n_layers)
    for b0 in range(0, len(order), block):
        idx = order[b0:b0 + block]
        t_max = max(utts[i].shape[0] for i in idx)
        feats = np.zeros((len(idx), t_max, utts[idx[0]].shape[1]),
                         np.float32)
        for r, i in enumerate(idx):
            feats[r, :utts[i].shape[0]] = utts[i]
        lens = np.array([utts[i].shape[0] for i in idx], np.int32)
        logits, fired, hm = fn(params, jnp.asarray(feats), jnp.asarray(lens))
        logits, fired = np.asarray(logits), np.asarray(fired)
        hmax = np.maximum(hmax, np.asarray(hm))
        for r, i in enumerate(idx):
            t = utts[i].shape[0]
            out[i] = logits[r, :t]
            fired_sum += fired[:, r, :t].sum(axis=1)
            steps += t
    cols = np.array([d + h for d, h in layer_dims(cfg)], np.float64)
    stats = {"active_columns": (fired_sum / steps).tolist(),
             "temporal_sparsity": (1.0 - fired_sum / steps / cols).tolist(),
             "h_absmax": hmax.tolist()}
    return out, stats
