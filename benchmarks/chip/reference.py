"""Weights from the seed and the plain reference, for every model family.

Nothing here imports the program.  This module holds what the families
share: seed words, the int8 grid and CBTD pruning (Alg. 1 of
arXiv:2108.02297) that a family's ``make_params`` draws its weights with,
and ``reference_logits``, which runs a family's plain ``forward`` over
utterances in blocks of rows.  Each family's weights and equations are in
``models/<family>.py``.
"""
from __future__ import annotations

import functools
import math
from typing import List, Sequence, Tuple

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


@functools.lru_cache(maxsize=None)
def _forward_fn(model, theta: float, dtype_name: str, precision: str):
    import jax
    import jax.numpy as jnp

    return jax.jit(functools.partial(
        model.forward, theta=theta, dtype=jnp.dtype(dtype_name),
        precision=precision))


def reference_logits(model, params, utts: Sequence[np.ndarray], cfg: dict,
                     dtype: str = "float32", precision: str = "highest",
                     block: int = 16):
    """``model.forward`` (a family module) over each utterance, ``block``
    utterances at a time (rows sorted by length so that each block pads
    little).  Returns (list of [T_i, C] logits, per-layer stats dict)."""
    import jax.numpy as jnp

    fn = _forward_fn(model, float(cfg["theta"]), dtype, precision)
    order = sorted(range(len(utts)), key=lambda i: utts[i].shape[0])
    out: List[np.ndarray] = [None] * len(utts)
    dims = model.layer_dims(cfg)
    n_layers = len(dims)
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
    cols = np.array([d + h for d, h in dims], np.float64)
    stats = {"active_columns": (fired_sum / steps).tolist(),
             "temporal_sparsity": (1.0 - fired_sum / steps / cols).tolist(),
             "h_absmax": hmax.tolist()}
    return out, stats
