"""Synthetic speech features for the chip benchmark, made from a seed.

A copy of the program's synthesiser (piecewise-stationary phone segments,
Ornstein-Uhlenbeck trajectories toward per-phone targets, 41 static
features plus first and second temporal differences = 123 dims), kept
here so that no later change to the program can change the traffic.  Two
things differ from the program's copy: each utterance has the length the
traffic mix gives it (not a uniform draw), and the whole set is
CMVN-normalised per dimension over its valid frames, as TIMIT front ends
do.  Everything runs in one jitted call on the default device.
"""
from __future__ import annotations

import functools
from typing import List, Sequence

import numpy as np


def _synth(key, t_max: int, n_static: int, n_phones: int,
           avg_segment: float, tau: float, noise: float, means):
    import jax
    import jax.numpy as jnp

    k_seg, k_cls, k_ou = jax.random.split(key, 3)
    change = jax.random.bernoulli(k_seg, 1.0 / avg_segment, (t_max,))
    change = change.at[0].set(True)
    seg_id = jnp.cumsum(change.astype(jnp.int32)) - 1
    seg_class = jax.random.randint(k_cls, (t_max,), 0, n_phones)
    target = means[seg_class[seg_id]]                       # [T, F]
    eps = jax.random.normal(k_ou, (t_max, n_static)) * noise

    def step(x, inp):
        mu, e = inp
        x = tau * x + (1.0 - tau) * mu + e * jnp.sqrt(1 - tau ** 2)
        return x, x

    _, traj = jax.lax.scan(step, target[0], (target, eps))
    d1 = jnp.diff(traj, axis=0, prepend=traj[:1])
    d2 = jnp.diff(d1, axis=0, prepend=d1[:1])
    return jnp.concatenate([traj, d1, d2], axis=-1)          # [T, 3F]


@functools.lru_cache(maxsize=None)
def _make_fn(n: int, t_max: int, n_static: int, n_phones: int,
             avg_segment: float, tau: float, noise: float):
    import jax
    import jax.numpy as jnp

    def make(key, lengths):
        k_means, k_utt = jax.random.split(key)
        means = jax.random.normal(k_means, (n_phones, n_static)) * 1.5
        keys = jax.random.split(k_utt, n)
        feats = jax.vmap(lambda k: _synth(
            k, t_max, n_static, n_phones, avg_segment, tau, noise,
            means))(keys)                                   # [N, T, D]
        valid = (jnp.arange(t_max)[None, :] < lengths[:, None])[..., None]
        count = jnp.sum(valid)
        mu = jnp.sum(jnp.where(valid, feats, 0.0), axis=(0, 1)) / count
        var = jnp.sum(jnp.where(valid, (feats - mu) ** 2, 0.0),
                      axis=(0, 1)) / count
        return jnp.where(valid, (feats - mu) * jax.lax.rsqrt(var + 1e-8),
                         0.0)

    return jax.jit(make)


def utterances(key_int: int, lengths: Sequence[int],
               speech: dict) -> List[np.ndarray]:
    """One float32 ``[T_i, 3 * n_static]`` array per requested length."""
    import jax
    import jax.numpy as jnp

    lengths = np.asarray(lengths, np.int32)
    fn = _make_fn(len(lengths), int(lengths.max()), int(speech["n_static"]),
                  int(speech["n_phones"]), float(speech["avg_segment"]),
                  float(speech["tau"]), float(speech["noise"]))
    feats = np.asarray(fn(jax.random.key(key_int), jnp.asarray(lengths)))
    return [feats[i, :n].copy() for i, n in enumerate(lengths)]
