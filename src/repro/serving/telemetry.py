"""Device-resident sparsity telemetry + latency summaries for the serving
pool and the asyncio front-end.

The batch-1 `SpartusEngine` appends a Python dict per (step, layer) with
`int()` host syncs on every frame — fine for one utterance, fatal for a
server.  Here telemetry is three `[L, B]` accumulators (layer x slot)
that live on device and ride the `lax.scan` carry of
`BatchedSpartusEngine.step_chunk`, so the steady state does zero host
round-trips and one chunk dispatch folds in L x C (layer, frame)
samples — only frames a slot actually consumed count, since
`accumulate` masks by the per-iteration active mask.  Keeping the slot
dimension (rather than summing over it per step) is what lets the
sharded pool (docs/serving.md, slot-dimension data parallelism) carry
telemetry with ZERO cross-device traffic: each device accumulates its
own slots' columns and the reduction over B happens once, host-side, in
`measured_sparsity` — which fetches the accumulators on demand and
reduces them to the same summary statistics the batch-1 engine reports:

  temporal_sparsity      = 1 - mean over (active step, layer) of nnz/n_cols
  capacity_overflow_rate = fraction of samples where the NZI list dropped
  mean_active_columns    = mean nnz per sample

Because the per-layer column count is static, the mean-of-ratios reduces
exactly to sums:  mean(nnz/cols) = (sum_l nnz_sum_l / n_cols_l) / sum_l steps_l,
so the aggregate numbers equal what the per-step dict path would report.

``percentile_summary`` is the shared latency reduction: every serving
surface (sync `serve_requests`, the async front-end, the load benchmark)
reports wall latency, queue wait and time-to-first-logit through it so
p50/p95/p99 mean the same thing everywhere.
"""
from __future__ import annotations

from typing import Dict, NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.contracts import hotpath_contract


class TelemetryState(NamedTuple):
    """Per-(layer, slot) accumulators over (active slot, frame) samples.

    float32, not int32: a long-running server would wrap an int32 counter
    (garbage statistics), whereas float32 sums stay exact up to 2^24 and
    then round — the reported *ratios* keep ~1e-7 relative accuracy for
    the life of the process (int64/float64 need jax x64, off by default).

    The slot dimension is kept unreduced on purpose: it makes every
    accumulator a `[.., B]` slab that shards over the pool's slot axis
    exactly like the layer state, so the sharded pool's step needs no
    cross-device reduction (a per-step ``sum(axis=-1)`` would be an
    all-reduce per scan iteration).
    """

    nnz_sum: jax.Array         # [L, B] float32: total fired deltas
    overflow_steps: jax.Array  # [L, B] float32: samples where capacity
    #                            dropped deltas
    steps: jax.Array           # [L, B] float32: number of samples


def init_telemetry(n_layers: int, n_slots: int) -> TelemetryState:
    # three DISTINCT buffers: the serving step/chunk functions donate the
    # whole PoolState, and donating one buffer aliased into three leaves
    # fails with "attempt to donate the same buffer twice"
    def z() -> jax.Array:
        return jnp.zeros((n_layers, n_slots), jnp.float32)

    return TelemetryState(nnz_sum=z(), overflow_steps=z(), steps=z())


def accumulate(
    tel: TelemetryState,
    layer: int,
    nnz: jax.Array,      # [B] int32 fired-delta counts
    dropped: jax.Array,  # [B] int32 overflow drop counts
    active: jax.Array,   # [B] bool slot mask
) -> TelemetryState:
    """Fold one layer-step of one batch into the accumulators (traced)."""
    act = active.astype(jnp.float32)
    # traced-only helper: called from inside the jitted step, never eagerly
    return TelemetryState(
        nnz_sum=tel.nnz_sum.at[layer].add(  # lint: allow(eager-scatter)
            nnz.astype(jnp.float32) * act),
        # lint: allow(eager-scatter)
        overflow_steps=tel.overflow_steps.at[layer].add(
            (dropped > 0).astype(jnp.float32) * act),
        steps=tel.steps.at[layer].add(act),  # lint: allow(eager-scatter)
    )


def accumulate_layers(
    tel: TelemetryState,
    nnz: jax.Array,      # [L, B] int32 fired-delta counts, all layers
    dropped: jax.Array,  # [L, B] int32 overflow drop counts
    active: jax.Array,   # [B] bool slot mask
) -> TelemetryState:
    """Fold one whole step (all layers at once) into the accumulators.

    Same math as L calls to ``accumulate``, but as three [L, B] slab adds
    instead of 3L row scatters — the scatters were measurable per-tick
    overhead on the CPU backend, and inside the chunked ``lax.scan`` this
    runs once per frame.  Purely elementwise over the slot dimension, so
    a slot-sharded pool accumulates with zero cross-device traffic."""
    act = active.astype(jnp.float32)
    return TelemetryState(
        nnz_sum=tel.nnz_sum + nnz.astype(jnp.float32) * act,
        overflow_steps=tel.overflow_steps
        + (dropped > 0).astype(jnp.float32) * act,
        steps=tel.steps + act,
    )


def percentile_summary(
    values: Sequence[float], name: str, qs: Sequence[int] = (50, 95, 99),
) -> Dict[str, float]:
    """Reduce a latency sample list to ``{"p<q>_<name>": value}`` entries
    (0.0 for an empty sample, so stats stay well-formed on empty runs)."""
    arr = np.asarray(list(values), np.float64)
    if arr.size == 0:
        return {f"p{q}_{name}": 0.0 for q in qs}
    return {f"p{q}_{name}": float(np.percentile(arr, q)) for q in qs}


@hotpath_contract("fold_totals",
                  forbid_ops=("dot", "gather", "scatter",
                              "dynamic-update-slice"))
def fold_totals(tel: TelemetryState, n_cols: Sequence[int]) -> jax.Array:
    """Reduce the `[L, B]` accumulators to the three running totals that
    `measured_sparsity` is built from, ON DEVICE (traced / jittable):

        [sum_l nnz_sum_l / n_cols_l,  overflow.sum(),  steps.sum()]

    This is what the observability layer diffs between chunk boundaries
    to report *incremental* sparsity: the `[3]` result is dispatched at
    one boundary and fetched at the next, so the live metrics never add
    a host sync against an in-flight chunk (metrics.PoolObservability).
    Host-side, ``measured_sparsity(tel, cols)`` equals the summary
    computed from ``fold_totals(tel, cols)``'s three numbers."""
    cols = jnp.asarray(n_cols, jnp.float32)[:, None]   # [L, 1] vs [L, B]
    return jnp.stack([
        (tel.nnz_sum / cols).sum(),
        tel.overflow_steps.sum(),
        tel.steps.sum(),
    ])


def measured_sparsity(
    tel: TelemetryState, n_cols: Sequence[int]
) -> Dict[str, float]:
    """Reduce the accumulators to the engine's summary dict.  This is the
    only host fetch in the telemetry path — and, for a sharded pool, the
    only place the per-slot columns are ever reduced across devices.

    An idle pool (no samples yet) returns the full key set zeroed, like
    ``percentile_summary`` on an empty sample — callers can always index
    the summary without guarding for `KeyError`."""
    nnz, ovf, steps = (np.asarray(jax.device_get(a), np.float64) for a in tel)
    total = steps.sum()
    if total == 0:
        return {
            "temporal_sparsity": 0.0,
            "capacity_overflow_rate": 0.0,
            "mean_active_columns": 0.0,
        }
    cols = np.asarray(n_cols, np.float64)[:, None]   # [L, 1] vs [L, B]
    return {
        "temporal_sparsity": float(1.0 - (nnz / cols).sum() / total),
        "capacity_overflow_rate": float(ovf.sum() / total),
        "mean_active_columns": float(nnz.sum() / total),
    }
