"""DeltaLSTM acoustic model (arXiv:2108.02297): one model family of the
chip benchmark, named by a configuration's ``"model": "delta_lstm"``.

A family module gives the harness five functions:

``layer_dims(cfg)``      ``[(d_in, h)]`` per recurrent layer.
``make_params(key_int, cfg)``  the weights from a seed, in one jitted call
                         on the device.
``forward(params, feats, lengths, theta, dtype, precision)``  the plain
                         reference: ``(logits [N, T, C], fired [L, N, T],
                         h_absmax [L])``.
``weights_held(cfg)``    nonzero weights the model holds (``work.py``
                         credits two operations to each, once per frame).
``engine(params, cfg)``  the program's engine for this configuration; the
                         only function that imports the program.

``make_params`` draws the LSTM stacks on the int8 grid (what the paper's
accelerator stores), CBTD-pruned column-balanced (Alg. 1 at alpha = 1),
and a dense float32 FCL and logit layer.  The program packs exactly these
arrays; the grid and the power-of-two scale make its int8 pack an
identity, so the reference and the program compute from the same numbers.

``forward`` is the model in straightforward ``jax.numpy`` (paper eqs.
3-8): per layer, the thresholded delta of the concatenated ``[input, h]``
state against its reference ``s_hat``, delta memories ``dm += W @ delta``,
the (i, g, f, o) gates, then ReLU(FCL) and the logit layer.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, List, Tuple

from reference import cbtd_prune, grid


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


def weights_held(cfg: dict) -> int:
    """The CBTD-pruned LSTM stacks (every subcolumn of ``4H / m`` rows
    keeps ``S - floor(S * gamma)`` weights) plus the dense FCL and logit
    layer."""
    total = 0
    for d, h in layer_dims(cfg):
        s = 4 * h // cfg["m"]
        keep = s - int(s * cfg["gamma"])
        total += (d + h) * cfg["m"] * keep
    h = cfg["hidden_dim"]
    return total + h * h + cfg["n_classes"] * h


def engine(params, cfg: dict):
    from repro.models.lstm_am import LSTMAMConfig
    from repro.serving import BatchedSpartusEngine, EngineConfig

    return BatchedSpartusEngine(
        params, LSTMAMConfig(input_dim=cfg["input_dim"],
                             hidden_dim=cfg["hidden_dim"],
                             n_layers=cfg["n_layers"],
                             n_classes=cfg["n_classes"]),
        EngineConfig(theta=cfg["theta"], gamma=cfg["gamma"], m=cfg["m"],
                     capacity_frac=cfg["capacity_frac"]))
