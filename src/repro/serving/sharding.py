"""Slot-dimension data parallelism for the serving pool.

Spartus scales by replicating sparse PEs across a bigger FPGA with a
balanced workload; the serving-pool analogue is to partition the pool's
*slot* dimension across devices.  Every per-slot slab the pool owns —
layer state, delta memories, frame buffers, cursors, lengths, the logits
bank, telemetry — is placed with a `NamedSharding` over a 1-D
``("data",)`` mesh, so the existing jitted `step_chunk` dispatch runs
SPMD across all devices: each device advances its own block of slots
and, because slots are fully independent (the batched
kernels are vmaps of per-session ops and telemetry is kept per-slot),
the steady-state chunk contains **zero cross-device communication** —
the partitioned program is the single-device program, n_devices times in
parallel.  Only admission (the host-staged upload scatter) and
retirement (the one-copy D2H fetch) touch per-shard rows.

Placement follows `distributed/sharding.py`'s never-invalid rule
(`slot_spec`): a slot dimension not divisible by the mesh's data-axis
size falls back to replication, so any (capacity, n_devices) pair is
valid — it just stops being parallel.  `SessionPool(n_devices=N)` is the
public knob; everything here is the plumbing underneath it.

CI has no multi-device hardware: the mesh is emulated with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (set before jax
import), which exercises the identical GSPMD partitioning path on CPU.
"""
from __future__ import annotations

from typing import List, Tuple

import jax
from jax.sharding import NamedSharding

from repro.distributed.sharding import slot_spec
from repro.launch.mesh import axis_size, data_axes, make_data_mesh
from repro.serving.batched_engine import PoolState


def make_pool_mesh(n_devices: int):
    """1-D ``("data",)`` mesh over ``n_devices`` local devices."""
    return make_data_mesh(n_devices)


def mesh_data_size(mesh) -> int:
    """Number of shards the mesh's data axes provide."""
    return axis_size(mesh, *data_axes(mesh))


def n_pool_shards(mesh, capacity: int) -> int:
    """Effective shard count for a ``capacity``-slot pool on ``mesh``:
    the data-axis size when it divides capacity, else 1 (the pool slabs
    replicate — `slot_spec`'s never-invalid fallback)."""
    size = mesh_data_size(mesh)
    return size if size > 1 and capacity % size == 0 else 1


def shard_bounds(capacity: int, n_shards: int) -> List[Tuple[int, int]]:
    """``[lo, hi)`` slot ranges owned by each shard (contiguous blocks:
    `NamedSharding` over dim 0 splits the slot axis into equal runs)."""
    per = capacity // n_shards
    return [(s * per, (s + 1) * per) for s in range(n_shards)]


def slot_sharding(shape, mesh, dim: int = 0) -> NamedSharding:
    """`NamedSharding` for one per-slot slab (``dim`` = the slot axis)."""
    return NamedSharding(mesh, slot_spec(tuple(shape), mesh, dim=dim))


def shard_slot_array(x: jax.Array, mesh, dim: int = 0) -> jax.Array:
    """Place one per-slot slab; replicates when the dim doesn't divide."""
    return jax.device_put(x, slot_sharding(x.shape, mesh, dim=dim))


def pool_state_shardings(state: PoolState, mesh) -> PoolState:
    """`NamedSharding` pytree matching a `PoolState`: layer slabs and the
    cursor shard the slot axis at dim 0; the `[L, B]` telemetry
    accumulators shard it at dim 1."""
    dim0 = lambda leaf: slot_sharding(leaf.shape, mesh, dim=0)  # noqa: E731
    dim1 = lambda leaf: slot_sharding(leaf.shape, mesh, dim=1)  # noqa: E731
    return PoolState(
        layers=jax.tree.map(dim0, state.layers),
        telemetry=jax.tree.map(dim1, state.telemetry),
        cursor=dim0(state.cursor),
    )


def shard_pool_state(state: PoolState, mesh) -> PoolState:
    """Place every `PoolState` slab on the mesh (one `device_put` of the
    whole pytree).  Done once at pool construction; the step functions
    donate the state, so the placement persists tick over tick."""
    return jax.device_put(state, pool_state_shardings(state, mesh))
