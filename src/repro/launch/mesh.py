"""Production mesh builders.

A FUNCTION (not module-level constant) so importing this module never
touches jax device state.  Shapes follow the assignment:
  single-pod: (16, 16)        -> ("data", "model")      = 256 chips
  multi-pod:  (2, 16, 16)     -> ("pod", "data", "model") = 512 chips

``data_axes()`` returns the axes a global batch shards over (pod folds
into data parallelism); ``model_axis()`` the tensor-parallel axis.
"""
from __future__ import annotations

from typing import Tuple

import jax


AUTO = jax.sharding.AxisType.Auto


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AUTO,) * len(axes))


def make_host_mesh(n_data: int = 2, n_model: int = 2):
    """Small mesh over host devices (tests; needs XLA_FLAGS device count)."""
    return jax.make_mesh((n_data, n_model), ("data", "model"),
                         axis_types=(AUTO, AUTO))


def make_data_mesh(n_data: int = 1):
    """1-D ``("data",)`` mesh over the first ``n_data`` local devices —
    the serving pool's slot-dimension data parallelism (each device owns
    a contiguous block of pool slots; no model axis, the CBCSC weights
    replicate).  Raises with a clear message when the host exposes fewer
    devices (CI emulates them with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8``)."""
    avail = len(jax.devices())
    if n_data < 1:
        raise ValueError(f"n_data must be >= 1, got {n_data}")
    if n_data > avail:
        raise ValueError(
            f"requested a {n_data}-device data mesh but only {avail} "
            f"device(s) are visible; set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={n_data} (before "
            f"importing jax) to emulate host devices")
    return jax.make_mesh((n_data,), ("data",), axis_types=(AUTO,))


def data_axes(mesh) -> Tuple[str, ...]:
    names = mesh.axis_names
    return tuple(a for a in ("pod", "data") if a in names)


def model_axis(mesh) -> str:
    return "model"


def axis_size(mesh, *names: str) -> int:
    out = 1
    for n in names:
        if n in mesh.axis_names:
            out *= mesh.shape[n]
    return out
