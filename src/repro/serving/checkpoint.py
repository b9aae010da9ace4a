"""Session checkpoint/restore for the serving pool — the recovery and
migration primitive of the robustness layer (docs/robustness.md).

What a session *is*, for checkpoint purposes, is exactly the state the
chunked tick loop threads through `engine.step_chunk` plus the host-side
bookkeeping the scheduler keeps per slot:

  * per-layer recurrent slabs — the rows of each layer state's fields:
    ``s_hat`` (delta references), ``c`` (DeltaLSTM only), ``h``, ``dm``
    (delta memories);
  * the per-slot telemetry columns (sparsity accumulators);
  * the device frame cursor and the frames received so far (device
    feature buffer row, with any *staged-but-not-yet-uploaded* host
    blocks overlaid — a snapshot never has to force an upload flush);
  * the banked logits rows ``[0, cursor)`` of the device output buffer
    (chunked mode) or the host row list (per-frame mode) — the rows a
    client may not have consumed yet;
  * the `_Session` metadata (req id, totals, needs_reset, ...).

Because every slot is computationally independent (the batched kernels
are vmaps of per-session ops — the zero-collectives property the sharded
pool is built on), a session restored into *any* slot of *any* pool with
the same engine weights continues bit-identically: slot index, pool
capacity and shard count are placement, not semantics.  That is what
makes the whole-pool checkpoint double as the **migration primitive**:
``SessionPool.restore`` works into a pool with a different ``n_devices``
(or capacity) than the one that wrote the checkpoint.

Fetch discipline: `snapshot_pool` performs ONE gathered device->host
fetch of the whole pool pytree (state, frames, lengths, out) under the
pool's state lock — it syncs on the in-flight chunk (checkpoints happen
at boundaries) and adds nothing to the compiled step, which is pinned by
the ``step_chunk/post-restore`` hot-path contract (analysis/cases.py).

File IO rides `training/checkpoint.py`: the flattened array dict *is* a
pytree, so `CheckpointManager` provides the atomic tmp-dir + ``os.replace``
+ COMMIT-marker write, retention and restore machinery unchanged.
"""
from __future__ import annotations

import dataclasses
import functools
import json
import time
from typing import Any, Dict, List, Optional, TYPE_CHECKING

import jax
import jax.numpy as jnp
import numpy as np

from repro.serving import sharding as shardlib
from repro.serving import telemetry as tele
from repro.serving.batched_engine import STATE_TYPES, PoolState
from repro.training.checkpoint import CheckpointManager

if TYPE_CHECKING:  # import cycle: scheduler imports this module's consumers
    from repro.serving.scheduler import RequestResult, SessionPool

FORMAT = "spartus-pool"
VERSION = 1


# -- snapshot containers ------------------------------------------------------


@dataclasses.dataclass
class SessionSnapshot:
    """One session's full state: JSON-able ``meta`` + named host arrays.

    Array keys: ``layer{i}/<field>`` for each field of the layer's state
    type (``s_hat, c, h, dm`` for DeltaLSTM, ``s_hat, h, dm`` for
    DeltaGRU), ``telemetry`` ``[3, L]``
    (nnz_sum / overflow_steps / steps columns), ``frames`` ``[n_recv, D]``
    and ``rows`` ``[cursor, n_classes]`` (the banked logits)."""

    meta: Dict[str, Any]
    arrays: Dict[str, np.ndarray]

    @property
    def req_id(self) -> int:
        return int(self.meta["req_id"])


@dataclasses.dataclass
class PoolCheckpoint:
    """A whole pool's live sessions plus the engine fingerprint that
    guards restore compatibility."""

    meta: Dict[str, Any]
    sessions: List[SessionSnapshot]


def engine_fingerprint(engine) -> Dict[str, Any]:
    """The engine identity a checkpoint is only valid against: layer
    shapes and the sparsity/quantization parameters that change the
    computed numbers.  (Weight *values* are assumed managed by the model
    checkpoint path — serving snapshots carry state, not parameters.)

    The quantization entry keeps a quantized pool from restoring an fp32
    pool's sessions (and vice versa): the recurrent state evolves on a
    different numeric grid, so resuming across formats would silently
    diverge rather than fail.  The cell entry keeps a DeltaLSTM pool from
    restoring a DeltaGRU pool's sessions and the reverse."""
    from repro.serving.engine import active_quant

    quant = active_quant(engine.cfg)
    return {
        "cell": engine.cell,
        "input_dim": int(engine.input_dim),
        "n_classes": int(engine.n_classes),
        "layers": [[int(l.input_dim), int(l.hidden_dim)]
                   for l in engine.layers],
        "theta": float(engine.cfg.theta),
        "gamma": float(engine.cfg.gamma),
        "quant": (None if quant is None else
                  [int(quant.weight_bits), int(quant.act_bits),
                   int(quant.act_frac_bits)]),
    }


def _fp_key(fp: Dict[str, Any]) -> str:
    return json.dumps(fp, sort_keys=True)


def _check_engine(pool: "SessionPool", meta: Dict[str, Any]) -> None:
    have = engine_fingerprint(pool.engine)
    want = meta.get("engine")
    if want is not None and "cell" not in want:
        want = {**want, "cell": "lstm"}   # written before the GRU cell
    if want is None or _fp_key(have) != _fp_key(want):
        raise ValueError(
            f"checkpoint engine fingerprint {want} does not match the "
            f"pool's engine {have}; restore requires the same cell, model "
            f"shapes and sparsity config (theta/gamma)")


def _layer_rows(state: PoolState, k: int):
    """Slot ``k``'s row of every field of every layer state, by name."""
    return tuple({f: getattr(st, f)[k] for f in st._fields}
                 for st in state.layers)


# -- session snapshot ---------------------------------------------------------


def _session_meta(sess) -> Dict[str, Any]:
    return {
        "req_id": int(sess.req_id),
        "arrival_step": int(sess.arrival_step),
        "admit_step": int(sess.admit_step),
        "total": None if sess.total is None else int(sess.total),
        "n_recv": int(sess.n_recv),
        "cursor": int(sess.cursor),
        "last_step": int(sess.last_step),
        "needs_reset": bool(sess.needs_reset),
        "partials_paused": bool(sess.partials_paused),
        "had_first_logit": bool(sess.first_logit_wall),
    }


def _overlay_frames(pool: "SessionPool", sess, k: int,
                    dev_row: Optional[np.ndarray]) -> np.ndarray:
    """The session's frames ``[n_recv, D]``: the device buffer row
    overlaid with any host-staged blocks not yet uploaded.  Host-side
    ``n_recv`` is authoritative (the device length can lag a staged
    admission/append by one boundary), so a snapshot never needs to
    force an upload flush first."""
    fr = np.zeros((sess.n_recv, pool.engine.input_dim), np.float32)
    if dev_row is not None and sess.n_recv:
        n_dev = min(sess.n_recv, dev_row.shape[0])
        fr[:n_dev] = dev_row[:n_dev]
    for slot, feats in pool._staged:
        if slot == k:
            fr[:feats.shape[0]] = feats
    for slot, start, feats in pool._staged_appends:
        if slot == k:
            fr[start:start + feats.shape[0]] = feats
    return fr


def _session_rows(pool: "SessionPool", sess, k: int,
                  out_row: Optional[np.ndarray]) -> np.ndarray:
    """The banked logits rows ``[0, cursor)`` — from the device output
    bank (chunked) or the host row list (per-frame)."""
    n_classes = pool.engine.n_classes
    if pool.chunk_frames:
        if out_row is None or not sess.cursor:
            return np.zeros((0, n_classes), np.float32)
        return np.asarray(out_row[:sess.cursor], np.float32).copy()
    if not sess.rows:
        return np.zeros((0, n_classes), np.float32)
    return np.stack(sess.rows).astype(np.float32)


def _snap(pool: "SessionPool", sess, k: int, layer_rows, tel_col,
          frames_row, out_row) -> SessionSnapshot:
    arrays: Dict[str, np.ndarray] = {}
    for i, row in enumerate(layer_rows):
        for name, val in row.items():
            arrays[f"layer{i}/{name}"] = np.asarray(val, np.float32).copy()
    arrays["telemetry"] = np.asarray(np.stack(tel_col), np.float32)
    arrays["frames"] = _overlay_frames(pool, sess, k, frames_row)
    arrays["rows"] = _session_rows(pool, sess, k, out_row)
    return SessionSnapshot(meta=_session_meta(sess), arrays=arrays)


def snapshot_session(pool: "SessionPool", req_id: int) -> SessionSnapshot:
    """Serialize ONE live session (one gathered D2H fetch of its rows).

    Raises KeyError for a request the pool has no live slot for — a
    session inside the retirement window is already past snapshotting
    (its result is in flight; resolve it with ``flush()``)."""
    if req_id not in pool._by_req:
        raise KeyError(f"request {req_id} is not live in the pool")
    k = pool._by_req[req_id]
    sess = pool._slots[k]
    with pool._state_lock:
        state = pool.state
        layer_rows, tel_col, frames_row, out_row = jax.device_get((
            _layer_rows(state, k),
            (state.telemetry.nnz_sum[:, k],
             state.telemetry.overflow_steps[:, k],
             state.telemetry.steps[:, k]),
            pool._frames[k],
            pool._out[k] if pool._out is not None else None,
        ))
    return _snap(pool, sess, k, layer_rows, tel_col, frames_row, out_row)


def snapshot_pool(pool: "SessionPool") -> PoolCheckpoint:
    """Serialize every live session in ONE gathered device->host fetch
    of the pool pytree (state, frames, out) — the single-sync snapshot
    the whole-pool checkpoint and the watchdog are built on.  Sessions
    inside the retirement window are NOT included (their logits are in
    flight to the host); call ``flush()`` first to resolve them."""
    with pool._state_lock:
        state, frames, out = jax.device_get(
            (pool.state, pool._frames, pool._out))
    sessions: List[SessionSnapshot] = []
    for k, sess in enumerate(pool._slots):
        if sess is None:
            continue
        layer_rows = _layer_rows(state, k)
        tel_col = (state.telemetry.nnz_sum[:, k],
                   state.telemetry.overflow_steps[:, k],
                   state.telemetry.steps[:, k])
        sessions.append(_snap(pool, sess, k, layer_rows, tel_col,
                              frames[k], out[k] if out is not None else None))
    meta = {
        "format": FORMAT,
        "version": VERSION,
        "engine": engine_fingerprint(pool.engine),
        "chunk_frames": int(pool.chunk_frames),
        "capacity": int(pool.capacity),
        "n_sessions": len(sessions),
    }
    return PoolCheckpoint(meta=meta, sessions=sessions)


# -- restore ------------------------------------------------------------------


@functools.partial(jax.jit, donate_argnums=(0,))
def _write_row(slab: jax.Array, row: jax.Array, k: jax.Array) -> jax.Array:
    """Scatter one session's row into a per-slot slab at a traced index
    (compiles once per slab shape, like the admission upload)."""
    return slab.at[k].set(row, mode="drop")


@functools.partial(jax.jit, donate_argnums=(0,))
def _write_col(slab: jax.Array, col: jax.Array, k: jax.Array) -> jax.Array:
    """Scatter one telemetry column ``[L]`` into a ``[L, B]`` slab."""
    return slab.at[:, k].set(col, mode="drop")


def _make_session(pool: "SessionPool", snap: SessionSnapshot, k: int,
                  now_wall: float):
    from repro.serving.scheduler import _Session

    m = snap.meta
    sess = _Session(
        req_id=int(m["req_id"]),
        arrival_step=int(m["arrival_step"]),
        admit_step=int(m["admit_step"]),
        arrival_wall=now_wall,
        admit_wall=now_wall,
        total=None if m["total"] is None else int(m["total"]),
        n_recv=int(m["n_recv"]),
        cursor=int(m["cursor"]),
        last_step=int(m["last_step"]),
        needs_reset=bool(m["needs_reset"]),
        partials_paused=bool(m["partials_paused"]),
        # wall clocks re-base to restore time: latency numbers measure
        # this process's service, not the epoch of the dead one
        first_logit_wall=now_wall if m["had_first_logit"] else 0.0,
    )
    if not pool.chunk_frames:
        sess.rows = [np.array(r) for r in snap.arrays["rows"]]
    pool._slots[k] = sess
    pool._by_req[sess.req_id] = k
    # frames go through the standard staged-upload wave at the next
    # boundary — one jitted H2D scatter, no eager per-slot writes; a
    # zero-length staging still clears the slot's stale device length
    pool._staged.append((k, np.asarray(snap.arrays["frames"], np.float32)))
    return sess


def _check_layer_fields(pool: "SessionPool", snap: SessionSnapshot) -> None:
    """A session snapshot carries no engine fingerprint: refuse one whose
    layer arrays are not the pool's state fields (another cell kind or
    depth) before any slot is touched."""
    want = {f"layer{i}/{f}" for i, layer in enumerate(pool.engine.layers)
            for f in STATE_TYPES[layer.cell]._fields}
    have = {k for k in snap.arrays if k.startswith("layer")}
    if have != want:
        raise ValueError(
            f"request {snap.meta['req_id']}: snapshot layer state "
            f"{sorted(have)} does not match the pool's {pool.engine.cell} "
            f"layers {sorted(want)}")


def restore_session(pool: "SessionPool", snap: SessionSnapshot) -> bool:
    """Restore ONE session into a free slot of a live pool (the
    single-session migration primitive).  Returns False if the pool is
    full; raises on an incompatible engine or duplicate request id.

    Device writes are jitted donated scatters at a traced slot index, so
    repeated restores compile once per slab shape — and the compiled
    ``step_chunk`` itself is untouched (the post-restore contract pin)."""
    m = snap.meta
    if int(m["req_id"]) in pool._by_req:
        raise ValueError(f"request {m['req_id']} is already in the pool")
    _check_layer_fields(pool, snap)
    if int(m["n_recv"]) > pool.max_buffer_frames:
        raise ValueError(
            f"request {m['req_id']}: snapshot holds {m['n_recv']} frames, "
            f"past this pool's max_buffer_frames={pool.max_buffer_frames}")
    k = pool._pick_slot()
    if k is None:
        return False
    if int(m["n_recv"]) > pool._t_buf:
        pool._grow_buffers(int(m["n_recv"]))
    sess = _make_session(pool, snap, k, time.perf_counter())
    kk = np.int32(k)
    with pool._state_lock:
        state = pool.state
        layers = []
        for i, st in enumerate(state.layers):
            layers.append(type(st)(**{
                f: _write_row(getattr(st, f),
                              jnp.asarray(snap.arrays[f"layer{i}/{f}"]), kk)
                for f in st._fields}))
        telemetry = tele.TelemetryState(
            nnz_sum=_write_col(state.telemetry.nnz_sum,
                               jnp.asarray(snap.arrays["telemetry"][0]), kk),
            overflow_steps=_write_col(
                state.telemetry.overflow_steps,
                jnp.asarray(snap.arrays["telemetry"][1]), kk),
            steps=_write_col(state.telemetry.steps,
                             jnp.asarray(snap.arrays["telemetry"][2]), kk),
        )
        cursor = _write_row(state.cursor, jnp.int32(sess.cursor), kk)
        new_state = PoolState(tuple(layers), telemetry, cursor)
        if pool._mesh is not None:
            new_state = shardlib.shard_pool_state(new_state, pool._mesh)
        pool.state = new_state
        if pool.chunk_frames:
            rows = snap.arrays["rows"]
            row_full = np.zeros((pool._out.shape[1], pool.engine.n_classes),
                                np.float32)
            row_full[:rows.shape[0]] = rows
            out = _write_row(pool._out, jnp.asarray(row_full), kk)
            if pool._mesh is not None:
                out = shardlib.shard_slot_array(out, pool._mesh)
            pool._out = out
    return True


def restore_into(pool: "SessionPool", ckpt: PoolCheckpoint) -> None:
    """Restore every session of a checkpoint into a FRESH, empty pool.

    The target pool may have a different capacity and a different shard
    count (``n_devices``) than the writer — slot placement is re-derived
    by the pool's own admission policy, and per-slot independence makes
    the continued logits bit-identical either way.  The new `PoolState`
    is assembled host-side in one pass and placed (sharded) in one
    ``device_put`` per slab; frames ride the standard staged-upload wave
    at the first boundary.  Nothing here touches the compiled step."""
    t0 = time.perf_counter()
    _check_engine(pool, ckpt.meta)
    if (pool.n_active or pool._staged or pool._staged_appends
            or pool.has_pending):
        raise ValueError("restore_into requires an empty pool with no "
                         "staged or pending work")
    if len(ckpt.sessions) > pool.capacity:
        raise ValueError(
            f"checkpoint holds {len(ckpt.sessions)} sessions, pool "
            f"capacity is {pool.capacity}")
    t_need = max((int(s.meta["n_recv"]) for s in ckpt.sessions), default=0)
    if t_need > pool.max_buffer_frames:
        raise ValueError(
            f"checkpoint session holds {t_need} frames, past this pool's "
            f"max_buffer_frames={pool.max_buffer_frames}")
    if t_need > pool._t_buf:
        pool._grow_buffers(t_need)

    # host-side assembly on top of the fresh-init values (so untouched
    # slots keep the exact fresh state, dm bias rows included):
    base = jax.device_get(pool.state)
    layer_types = [type(st) for st in base.layers]
    layers = [{f: np.array(getattr(st, f)) for f in st._fields}
              for st in base.layers]
    # three DISTINCT arrays: the step donates the whole state and aliased
    # telemetry leaves reject donation (the init_telemetry bug)
    tel_n = np.array(base.telemetry.nnz_sum)
    tel_o = np.array(base.telemetry.overflow_steps)
    tel_s = np.array(base.telemetry.steps)
    cursor = np.array(base.cursor)
    out_np = (np.zeros((pool.capacity, pool._t_buf + pool.chunk_frames,
                        pool.engine.n_classes), np.float32)
              if pool.chunk_frames else None)

    now_wall = time.perf_counter()
    for snap in ckpt.sessions:
        if int(snap.meta["req_id"]) in pool._by_req:
            raise ValueError(f"duplicate request {snap.meta['req_id']} "
                             "in checkpoint")
        k = pool._pick_slot()
        assert k is not None  # capacity checked above
        sess = _make_session(pool, snap, k, now_wall)
        for i, fields in enumerate(layers):
            for f, slab in fields.items():
                slab[k] = snap.arrays[f"layer{i}/{f}"]
        tel_n[:, k] = snap.arrays["telemetry"][0]
        tel_o[:, k] = snap.arrays["telemetry"][1]
        tel_s[:, k] = snap.arrays["telemetry"][2]
        cursor[k] = sess.cursor
        if out_np is not None:
            rows = snap.arrays["rows"]
            out_np[k, :rows.shape[0]] = rows

    new_state = PoolState(
        layers=tuple(t(**{f: jnp.asarray(a) for f, a in d.items()})
                     for t, d in zip(layer_types, layers)),
        telemetry=tele.TelemetryState(nnz_sum=jnp.asarray(tel_n),
                                      overflow_steps=jnp.asarray(tel_o),
                                      steps=jnp.asarray(tel_s)),
        cursor=jnp.asarray(cursor),
    )
    with pool._state_lock:
        if pool._mesh is not None:
            new_state = shardlib.shard_pool_state(new_state, pool._mesh)
        pool.state = new_state
        if out_np is not None:
            out = jnp.asarray(out_np)
            if pool._mesh is not None:
                out = shardlib.shard_slot_array(out, pool._mesh)
            pool._out = out
    if pool.obs is not None:
        pool.obs.fold_restore(n_sessions=len(ckpt.sessions),
                              seconds=time.perf_counter() - t0)


# -- file IO (rides training/checkpoint.py) -----------------------------------


def _flatten_ckpt(ckpt: PoolCheckpoint):
    arrays: Dict[str, np.ndarray] = {}
    metas: List[Dict[str, Any]] = []
    for i, snap in enumerate(ckpt.sessions):
        metas.append(snap.meta)
        for key, arr in snap.arrays.items():
            arrays[f"s{i}/{key}"] = arr
    meta = dict(ckpt.meta)
    meta["sessions"] = metas
    return arrays, meta


def _unflatten_ckpt(arrays: Dict[str, np.ndarray],
                    meta: Dict[str, Any]) -> PoolCheckpoint:
    if meta.get("format") != FORMAT:
        raise ValueError(f"not a {FORMAT} checkpoint: {meta.get('format')!r}")
    if int(meta.get("version", -1)) > VERSION:
        raise ValueError(f"checkpoint version {meta['version']} is newer "
                         f"than this code ({VERSION})")
    sessions = []
    for i, smeta in enumerate(meta["sessions"]):
        prefix = f"s{i}/"
        sarr = {k[len(prefix):]: np.asarray(v)
                for k, v in arrays.items() if k.startswith(prefix)}
        sessions.append(SessionSnapshot(meta=dict(smeta), arrays=sarr))
    pmeta = {k: v for k, v in meta.items() if k != "sessions"}
    return PoolCheckpoint(meta=pmeta, sessions=sessions)


def save_pool(pool: "SessionPool", path: str, *,
              keep_last: int = 3,
              async_save: bool = False) -> List["RequestResult"]:
    """Checkpoint the whole pool to ``path`` (a checkpoint *directory*:
    atomic write, COMMIT marker, retention — `CheckpointManager`).

    Flushes the double-buffer tail first and RETURNS those finished
    results: sessions in the retirement window at checkpoint time have
    completed — their logits belong to the caller, not the checkpoint.
    The checkpoint step number is the pool's dispatch count."""
    results = pool.flush()
    t0 = time.perf_counter()
    ckpt = snapshot_pool(pool)
    arrays, meta = _flatten_ckpt(ckpt)
    mgr = CheckpointManager(path, keep_last=keep_last, process_index=0,
                            async_save=async_save)
    mgr.save(pool.n_dispatches, arrays, metadata=meta)
    mgr.wait()
    if pool.obs is not None:
        pool.obs.fold_checkpoint(n_sessions=len(ckpt.sessions),
                                 seconds=time.perf_counter() - t0)
    return results


def load_checkpoint(path: str, step: Optional[int] = None) -> PoolCheckpoint:
    """Read a committed pool checkpoint back (latest step by default).
    Incomplete checkpoints (no COMMIT marker) are never offered — the
    kill -9 safety property inherited from `CheckpointManager`."""
    mgr = CheckpointManager(path, process_index=0, async_save=False)
    if step is None:
        step = mgr.latest_step()
        if step is None:
            raise FileNotFoundError(f"no committed checkpoint under {path}")
    arrays, meta = mgr.restore_arrays(step)
    return _unflatten_ckpt(arrays, meta)
