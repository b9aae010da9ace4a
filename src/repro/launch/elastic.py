"""Elastic scaling: re-shard a checkpoint onto a different device count.

Checkpoints store full (host) arrays keyed by tree path, so elasticity is
a pure re-layout problem: build the new mesh from whatever devices exist,
recompute PartitionSpecs with the same rules (they degrade gracefully —
any non-divisible dim falls back to replication), and device_put.

Straggler/failure policy at the job level (launch/train.py):
  * deterministic (process, step)->data mapping means a restarted/rescaled
    job replays the exact stream — no sample loss, no duplication;
  * checkpoint cadence bounds lost work; COMMIT markers make partial
    writes invisible;
  * on shrink, the global batch is preserved by raising per-host batch
    (grad-accumulation) so optimization hyperparameters stay valid.
"""
from __future__ import annotations

import math
from typing import Tuple

import jax

from repro.distributed.sharding import param_specs
from repro.launch.mesh import AUTO


def best_mesh_for(n_devices: int):
    """Largest (data, model) grid <= n_devices with model <= 16 (TP island
    bounded by ICI domain) and data maximal."""
    model = min(16, n_devices)
    while n_devices % model:
        model //= 2
    data = n_devices // model
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(AUTO, AUTO))


def reshard(tree, mesh, cfg=None):
    """device_put a host pytree onto ``mesh`` with the standard rules."""
    from jax.sharding import NamedSharding

    specs = param_specs(tree, mesh, cfg)
    shardings = jax.tree.map(lambda s: NamedSharding(mesh, s), specs)
    return jax.device_put(tree, shardings)


def rescale_batch(global_batch: int, old_hosts: int, new_hosts: int,
                  per_host: int) -> Tuple[int, int]:
    """(new per-host batch, grad-accum factor) preserving the global batch."""
    assert global_batch == old_hosts * per_host
    new_per_host = math.ceil(global_batch / new_hosts)
    accum = 1
    while new_per_host > 2 * per_host:
        new_per_host = math.ceil(new_per_host / 2)
        accum *= 2
    return new_per_host, accum
