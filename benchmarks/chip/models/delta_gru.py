"""DeltaGRU acoustic model (EdgeDRNN, Gao et al., IEEE JETCAS 2020; the
Delta Network cell of Neil et al. 2017 that Spartus extends): one model
family of the chip benchmark, named by ``"model": "delta_gru"``.

The five functions of a family are those of ``models/delta_lstm.py``.

``make_params`` draws each layer's GRU weights dense on the int8 grid,
uniform over ``+-gru_scale/sqrt(H)`` with a power-of-two step (EdgeDRNN
exploits temporal sparsity only, so nothing is pruned), biases 0, and a
dense float32 FCL and logit layer.  The program packs exactly these
arrays (the pack is an identity on the grid).

``forward`` is the cell in straightforward ``jax.numpy`` (the cuDNN-style
GRU of ``repro.core.delta_gru``, the reset gate applied to the recurrent
product): per layer, the thresholded delta of the concatenated
``[input, h]`` against its reference ``s_hat``; the four delta memories
``(M_r, M_u, M_xc, M_hc) += delta @ W.T`` with W the explicit [4H, D+H]
stack ``[[W_xr W_hr], [W_xu W_hu], [W_xc 0], [0 W_hc]]``; then
``r = sigmoid(M_r)``, ``u = sigmoid(M_u)``, ``c = tanh(M_xc + r * M_hc)``,
``h = (1 - u) * c + u * h_prev``; then ReLU(FCL) and the logit layer.
One product with the stack is W_x dx + W_h dh written as a single sum,
a departure from the two products of the equations: it sums in the order
the served dense mirror does, so that rounding does not flip threshold
decisions over 1000-frame utterances.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, List, Tuple

from reference import grid


def layer_dims(cfg: dict) -> List[Tuple[int, int]]:
    d, h = cfg["input_dim"], cfg["hidden_dim"]
    return [(d if i == 0 else h, h) for i in range(cfg["n_layers"])]


def _stack(lp):
    """The [4H, D+H] matrix of (r, u, xc, hc) rows."""
    import jax.numpy as jnp

    h, d = lp["w_h"].shape[1], lp["w_x"].shape[1]
    w_x, w_h = lp["w_x"], lp["w_h"]
    return jnp.concatenate([
        jnp.concatenate([w_x[:2 * h], w_h[:2 * h]], axis=1),
        jnp.concatenate([w_x[2 * h:], jnp.zeros((h, h), w_x.dtype)], axis=1),
        jnp.concatenate([jnp.zeros((h, d), w_h.dtype), w_h[2 * h:]], axis=1),
    ], axis=0)


@functools.lru_cache(maxsize=None)
def _params_fn(dims: Tuple[Tuple[int, int], ...], n_classes: int,
               gru_scale: float):
    import jax
    import jax.numpy as jnp

    def make(key):
        keys = jax.random.split(key, len(dims) + 2)
        gru = []
        for k, (d, h) in zip(keys, dims):
            step, qmax = grid(gru_scale / math.sqrt(h))
            kx, kh = jax.random.split(k)
            q_x = jax.random.randint(kx, (3 * h, d), -qmax, qmax + 1)
            q_h = jax.random.randint(kh, (3 * h, h), -qmax, qmax + 1)
            gru.append({"w_x": q_x.astype(jnp.float32) * step,
                        "w_h": q_h.astype(jnp.float32) * step,
                        "b_x": jnp.zeros((3, h), jnp.float32),
                        "b_h": jnp.zeros((3, h), jnp.float32)})
        h = dims[-1][1]
        bound = 1.0 / math.sqrt(h)
        fcl = {"w": jax.random.uniform(keys[-2], (h, h), jnp.float32,
                                       -bound, bound),
               "b": jnp.zeros((h,), jnp.float32)}
        logit = {"w": jax.random.uniform(keys[-1], (n_classes, h),
                                         jnp.float32, -bound, bound),
                 "b": jnp.zeros((n_classes,), jnp.float32)}
        return {"gru": gru, "fcl": fcl, "logit": logit}

    return jax.jit(make)


def make_params(key_int: int, cfg: dict) -> Dict:
    import jax

    fn = _params_fn(tuple(layer_dims(cfg)), cfg["n_classes"],
                    float(cfg["weights"]["gru_scale"]))
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
    for lp in params["gru"]:
        wt = _stack(lp).T.astype(dtype)                      # [D+H, 4H]
        h_dim = lp["w_h"].shape[1]
        q = wt.shape[0]
        b_x, b_h = lp["b_x"], lp["b_h"]
        dm0 = jnp.concatenate([b_x[0] + b_h[0], b_x[1] + b_h[1], b_x[2],
                               b_h[2]]).astype(dtype)
        carry = (jnp.zeros((n, q), dtype), jnp.zeros((n, h_dim), dtype),
                 jnp.broadcast_to(dm0, (n, 4 * h_dim)))

        def step(carry, xt, wt=wt, h_dim=h_dim):
            s_hat, h, dm = carry
            s = jnp.concatenate([xt, h], axis=-1)
            raw = s - s_hat
            fired = jnp.abs(raw) > theta
            delta = jnp.where(fired, raw, jnp.zeros_like(raw))
            s_hat = jnp.where(fired, s, s_hat)
            dm = dm + mm(delta, wt).astype(dtype)
            g = dm.reshape(n, 4, h_dim)
            r, u = jax.nn.sigmoid(g[:, 0]), jax.nn.sigmoid(g[:, 1])
            c = jnp.tanh(g[:, 2] + r * g[:, 3])
            h = (1.0 - u) * c + u * h
            return (s_hat, h, dm), (h, jnp.sum(fired, axis=-1))

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
    """The dense GRU layers (3H x (d + H) each: the stack's two zero
    blocks are not held) plus the dense FCL and logit layer."""
    total = sum(3 * h * (d + h) for d, h in layer_dims(cfg))
    h = cfg["hidden_dim"]
    return total + h * h + cfg["n_classes"] * h


def engine(params, cfg: dict):
    from repro.models.lstm_am import LSTMAMConfig
    from repro.serving import BatchedSpartusEngine, EngineConfig

    return BatchedSpartusEngine(
        params, LSTMAMConfig(input_dim=cfg["input_dim"],
                             hidden_dim=cfg["hidden_dim"],
                             n_layers=cfg["n_layers"],
                             n_classes=cfg["n_classes"], cell="gru"),
        EngineConfig(theta=cfg["theta"], gamma=cfg["gamma"], m=cfg["m"],
                     capacity_frac=cfg["capacity_frac"]))
