"""Continuous-batching Spartus engine: all pool slots advance up to C
frames in a single jitted call.

`SpartusEngine` (engine.py) is the paper-faithful batch-1 datapath: a
Python loop per frame and per layer with host syncs for telemetry.  This
module is its server-grade twin: the per-layer state of every session in
a fixed-capacity pool is stored as stacked device slabs
(`BatchedLayerState`, shapes `[B, ...]`), and each frame of
`step_chunk` runs

    IPU   delta_encode_batch            (slots batched)
    CTRL  select_active_columns_batch   (scatter route; the dense-mirror
    MACs  stsp_spmv_batch                route fuses both into
                                         delta_spmv_dense_topk_batch)
    HPE   lstm_pointwise_batch | gru_pointwise_batch

for every layer, plus the FCL/logit head, inside one jit.  The cell kind
is the pack's (`PackedLayer.cell`): a DeltaLSTM layer carries
`BatchedLayerState` (with its cell state ``c``), a DeltaGRU layer
`BatchedGRULayerState` (no cell state; ``dm`` holds M_r, M_u, M_xc,
M_hc), and only the `gates` / `state_update` steps differ, chosen per
layer at trace time.  An `active`
mask freezes idle slots (their state is carried through unchanged), and
a `reset` mask re-initialises slots at admission time so attach/detach
never recompiles.  Telemetry is accumulated on device (telemetry.py) and
fetched only when `measured_sparsity` is called.

`step_chunk` is the one step entry point: it reads each slot's frames
from pre-uploaded per-slot feature buffers `[B, T_buf, D]` by the
device cursor in `PoolState` (a tick moves no frame bytes host ->
device), advances every active slot up to `n_frames` frames in ONE
dispatch via `jax.lax.scan` over the per-frame core, and banks each
frame's logits in a per-slot device output buffer `[B, T_buf,
n_classes]` instead of returning them per tick — a finished slot's
logits leave the device once, at retirement.  It donates the incoming
`PoolState` and the output buffer, so the slabs are reused in place
chunk over chunk instead of reallocating.

Because the output buffer is donated, anything that must outlive the
next chunk is detached device-side first: `retire_rows` gathers just
the retiring slots' rows (`[R, T_pad, n_classes]`), and `snapshot_chunk`
slices just one chunk's `[B, C, n_classes]` window (the partial-logits
stream for live sessions — `SessionPool.stream_partials` / the async
front-end).
Both are dispatched before the next `step_chunk` and fetched one chunk
later, overlapped with the in-flight dispatch.

Per-slot numerics are identical to `SpartusEngine`: every batched op
computes each slot's row with the very same per-session math (a vmap on
the XLA routes, a slot grid axis in the Pallas kernels), so a session's
logits do not depend on what the other slots are doing (verified in
tests/test_serving_pool.py).
"""
from __future__ import annotations

from typing import Any, Dict, NamedTuple, Tuple, Union

import jax
import jax.numpy as jnp

from repro.analysis.contracts import hotpath_contract
from repro.kernels import ops
from repro.models.lstm_am import LSTMAMConfig
from repro.serving import telemetry as tele
from repro.serving.engine import (
    EngineConfig, PackedLayer, PackedSpartusModel, active_quant,
)


class BatchedLayerState(NamedTuple):
    """Stacked per-slot state of one DeltaLSTM layer."""

    s_hat: jax.Array  # [B, D+H] concatenated x̂ / ĥ references
    c: jax.Array      # [B, H] cell state
    h: jax.Array      # [B, H] hidden state
    dm: jax.Array     # [B, 4H] delta memories


class BatchedGRULayerState(NamedTuple):
    """Stacked per-slot state of one DeltaGRU layer."""

    s_hat: jax.Array  # [B, D+H] concatenated x̂ / ĥ references
    h: jax.Array      # [B, H] hidden state
    dm: jax.Array     # [B, 4H] delta memories (M_r, M_u, M_xc, M_hc)


LayerStates = Union[BatchedLayerState, BatchedGRULayerState]

#: per-slot state type of each cell kind (`PackedLayer.cell`)
STATE_TYPES = {"lstm": BatchedLayerState, "gru": BatchedGRULayerState}


class PoolState(NamedTuple):
    """Full device-resident state of the session pool."""

    layers: Tuple[LayerStates, ...]
    telemetry: tele.TelemetryState
    cursor: jax.Array  # [B] int32 per-slot frame cursor into the pool's
    #                    device-resident feature buffers


def _fresh_layer_state(layer: PackedLayer, n_slots: int) -> LayerStates:
    d, h = layer.input_dim, layer.hidden_dim
    dm0 = jnp.broadcast_to(layer.bias.astype(jnp.float32).reshape(-1),
                           (n_slots, 4 * h))
    fresh = {"s_hat": jnp.zeros((n_slots, d + h), jnp.float32),
             "c": jnp.zeros((n_slots, h), jnp.float32),
             "h": jnp.zeros((n_slots, h), jnp.float32),
             "dm": dm0}
    state_type = STATE_TYPES[layer.cell]
    return state_type(*(fresh[f] for f in state_type._fields))


@hotpath_contract("retire_rows", forbid_ops=("transpose",))
def retire_rows(out_buf: jax.Array, slot_idx: jax.Array) -> jax.Array:
    """Device-side gather of whole slots' rows out of the chunk output
    buffer (jitted per engine): ``out_buf [B, T_pad, C]``, ``slot_idx
    [R] int32`` -> ``[R, T_pad, C]``.  Indices are pool slots, always in
    range, so ``clip`` adds no masking."""
    return jnp.take(out_buf, slot_idx, axis=0, mode="clip")


class BatchedSpartusEngine(PackedSpartusModel):
    """Weight-resident multi-session engine: one CBCSC weight set, B
    independent streaming sessions multiplexed across it."""

    def __init__(self, am_params: Dict[str, Any], am_cfg: LSTMAMConfig,
                 cfg: EngineConfig = EngineConfig()):
        super().__init__(am_params, am_cfg, cfg)
        # the chunk donates the incoming PoolState and output buffer, so
        # the slabs are reused in place, never reallocated per chunk.
        self._step_chunk = jax.jit(self._step_chunk_impl,
                                   static_argnames=("n_frames",),
                                   donate_argnums=(0, 5))
        # output-buffer snapshots (chunked serving): a gather of the
        # retiring slots' rows, and a chunk-window slice for streamed
        # partial logits.  Both are dispatched BEFORE the next step_chunk
        # donates the buffer away, detaching the rows device-side; the
        # host fetch happens one chunk later, overlapped with the next
        # dispatch.
        self._retire_rows = jax.jit(retire_rows)
        self._snapshot_chunk = jax.jit(ops.gather_rows,
                                       static_argnames=("n",))
        # observability: [3] device reduction of the telemetry slabs
        # (nnz/cols, overflow, steps totals).  Non-donating — it reads
        # the accumulators the chunk just produced, and is dispatched at
        # one boundary / fetched at the next, same detach-now/fetch-
        # later cadence as the output-buffer snapshots above.
        n_cols = self.n_cols

        def telemetry_totals(t: tele.TelemetryState) -> jax.Array:
            return tele.fold_totals(t, n_cols)

        self._tel_totals = jax.jit(telemetry_totals)

    # -- state management ----------------------------------------------------

    def init_state(self, n_slots: int) -> PoolState:
        return PoolState(
            layers=tuple(_fresh_layer_state(l, n_slots) for l in self.layers),
            telemetry=tele.init_telemetry(len(self.layers), n_slots),
            cursor=jnp.zeros((n_slots,), jnp.int32),
        )

    def init_out_buf(self, n_slots: int, t_buf: int) -> jax.Array:
        """Per-slot device logits buffer for the chunked tick loop."""
        return jnp.zeros((n_slots, t_buf, self.n_classes), jnp.float32)

    def _apply_reset(self, state: PoolState, reset: jax.Array) -> PoolState:
        """Re-initialise reset slots' layer state and device cursor —
        admission, fused into the chunk dispatch so attach never costs an
        extra dispatch or recompiles.  Applied ONCE per dispatch, at the
        boundary: inside a chunk no slot resets."""
        n_slots = state.cursor.shape[0]
        rm = reset[:, None]
        layers = []
        for layer, st in zip(self.layers, state.layers):
            fresh = _fresh_layer_state(layer, n_slots)
            layers.append(type(st)(*(
                jnp.where(rm, f, old) for f, old in zip(fresh, st))))
        cursor = jnp.where(reset, 0, state.cursor)
        return PoolState(tuple(layers), state.telemetry, cursor)

    # -- the batched step ----------------------------------------------------

    def _step_core(
        self, state: PoolState, x: jax.Array, active: jax.Array,
        cursor: jax.Array,
    ) -> Tuple[PoolState, jax.Array]:
        cfg = self.cfg
        quant = active_quant(cfg)
        act_kw = (
            {"act_bits": quant.act_bits, "act_frac_bits": quant.act_frac_bits}
            if quant is not None else {}
        )
        n_slots = x.shape[0]
        new_layers = []
        nnz_layers, dropped_layers = [], []
        h = x
        for layer, st in zip(self.layers, state.layers):
            wscale = layer.scale if quant is not None else None
            val, lidx, mirror = layer.enc.val, layer.enc.lidx, layer.w_dense_t
            if quant is not None:
                # int8 at rest inside the compiled module: without the
                # barrier XLA folds convert(s8 const) into a baked f32
                # constant, restoring the fp32 footprint at rest.
                if mirror is not None:
                    mirror = jax.lax.optimization_barrier(mirror)
                else:
                    val, lidx = jax.lax.optimization_barrier((val, lidx))
            with jax.named_scope("delta_encode"):
                s = jnp.concatenate([h, st.h], axis=-1)       # [B, D+H]
                delta, s_hat, nnz = ops.delta_encode_batch(
                    s, st.s_hat, cfg.theta, use_pallas=cfg.use_pallas,
                    **act_kw)
            with jax.named_scope("matvec"):
                if mirror is not None:
                    # dense-mirror route: capacity enforced in the dense
                    # domain (no NZI list, no scatter) — bit-identical to
                    # the select + dense-gather chain, measurably faster
                    # on CPU.
                    y, dropped = ops.delta_spmv_dense_topk_batch(
                        mirror, delta, layer.capacity, scale=wscale)
                else:
                    idx, vals, dropped = ops.select_active_columns_batch(
                        delta, layer.capacity
                    )
                    y = ops.stsp_spmv_batch(
                        val, lidx, idx, vals,
                        s=layer.enc.s, use_pallas=cfg.use_pallas,
                        scale=wscale,
                    )
                dm = st.dm + y.astype(st.dm.dtype)
            with jax.named_scope("gates"):
                dm4 = dm.reshape(n_slots, 4, layer.hidden_dim)
                if layer.cell == "gru":
                    h_new = ops.gru_pointwise_batch(dm4, st.h)
                    stepped = {"s_hat": s_hat, "h": h_new, "dm": dm}
                else:
                    h_new, c_new = ops.lstm_pointwise_batch(
                        dm4, st.c, use_pallas=cfg.use_pallas)
                    stepped = {"s_hat": s_hat, "c": c_new, "h": h_new,
                               "dm": dm}
            with jax.named_scope("state_update"):
                am = active[:, None]
                new_layers.append(type(st)(*(
                    jnp.where(am, stepped[f], old)
                    for f, old in zip(st._fields, st))))
            nnz_layers.append(nnz)
            dropped_layers.append(dropped)
            h = h_new
        with jax.named_scope("telemetry"):
            tel = tele.accumulate_layers(
                state.telemetry, jnp.stack(nnz_layers),
                jnp.stack(dropped_layers), active)
        with jax.named_scope("head"):
            h = jax.nn.relu(h @ self.fcl["w"].T + self.fcl["b"])
            logits = h @ self.logit["w"].T + self.logit["b"]
        return PoolState(tuple(new_layers), tel, cursor), logits

    @hotpath_contract("step_chunk", donates=("state", "out_buf"),
                      op_budget={"transpose": 0, "dynamic-update-slice": 8})
    def _step_chunk_impl(
        self, state: PoolState, frames: jax.Array, lengths: jax.Array,
        active: jax.Array, reset: jax.Array, out_buf: jax.Array,
        *, n_frames: int,
    ) -> Tuple[PoolState, jax.Array]:
        # admission resets happen once at the chunk boundary, then
        # lax.scan advances every slot up to n_frames frames with zero
        # host involvement.  A slot whose cursor reaches
        # its utterance length mid-chunk goes inactive for the remaining
        # iterations: its state freezes and it contributes no telemetry —
        # exactly as if the host had masked it.  The scan stacks each
        # iteration's logits (static-offset writes), and ONE vmapped
        # dynamic-slice banks the whole [C, B, n_classes] block into the
        # per-slot output buffers at the chunk-start cursors; rows past a
        # session's length are scratch no reader consumes.  The named
        # scopes (reset, step_scan, bank_rows, and per layer in
        # _step_core) name the device work in a profiler trace.
        with jax.named_scope("reset"):
            state = self._apply_reset(state, reset)
        start = state.cursor

        def body(st, _):
            act = jnp.logical_and(active, st.cursor < lengths)
            x = ops.gather_frames(frames, st.cursor)
            new_st, logits = self._step_core(
                st, x, act, st.cursor + act.astype(st.cursor.dtype))
            return new_st, logits

        with jax.named_scope("step_scan"):
            state, ys = jax.lax.scan(body, state, None, length=n_frames)
        with jax.named_scope("bank_rows"):
            return state, ops.bank_rows(out_buf, ys, start)

    def step_chunk(
        self, state: PoolState, frames: jax.Array, lengths: jax.Array,
        active: jax.Array, reset: jax.Array, out_buf: jax.Array,
        *, n_frames: int,
    ) -> Tuple[PoolState, jax.Array]:
        """Advance every active slot up to ``n_frames`` frames in ONE
        dispatch (`jax.lax.scan` over the per-frame core).

        frames  [B, T_buf, D]          device-resident feature buffers
        lengths [B] int32              per-slot utterance length; a slot
                                       stops (state frozen, no logits, no
                                       telemetry) once its cursor reaches it
        active  [B] bool               occupied slots
        reset   [B] bool               slots admitted at this chunk boundary
                                       (layer state + cursor re-initialised
                                       before the first frame)
        out_buf [B, T_pad, n_classes]  device logits buffer; frame t of slot
                                       b lands in ``out_buf[b, t]``.  T_pad
                                       must be >= T_buf + n_frames: the
                                       chunk banks its stacked logits with
                                       one dynamic slice per slot, and rows
                                       past a session's length are scratch
                                       (never read — retirement fetches
                                       ``[:n_frames]``)

        Returns ``(new_state, new_out_buf)``.  Both the incoming ``state``
        and ``out_buf`` are DONATED: the caller must drop its references
        and use the returned arrays (slice a retiring slot's rows *before*
        the next call).  Logits never leave the device here — fetch a
        finished slot's rows from the output buffer once, at retirement.
        Numerics per consumed frame do not depend on ``n_frames``: C
        one-frame chunks bank the rows of one C-frame chunk (pinned at
        1e-5 in tests/test_chunked_serving.py).
        """
        return self._step_chunk(
            state, frames, jnp.asarray(lengths, jnp.int32),
            jnp.asarray(active, bool), jnp.asarray(reset, bool), out_buf,
            n_frames=int(n_frames))

    def retire_rows(self, out_buf: jax.Array,
                    slot_idx: jax.Array) -> jax.Array:
        """Device-side gather of the retiring slots' rows: ``out_buf [B,
        T_pad, n_classes]``, ``slot_idx [R] int32`` -> ``[R, T_pad,
        n_classes]``.  The pool calls it with a fixed ``R`` (padding the
        indices), so it compiles once per pool however many sessions
        retire.  Used to detach retiring sessions' rows before the next
        ``step_chunk`` donates the buffer away; each block is then
        fetched in one D2H copy one chunk later."""
        return self._retire_rows(out_buf, jnp.asarray(slot_idx, jnp.int32))

    def snapshot_chunk(self, out_buf: jax.Array, starts: jax.Array,
                       *, n_frames: int) -> jax.Array:
        """Device-side slice of ONE chunk's rows for every slot:
        ``out_buf [B, T_pad, n_classes]``, per-slot chunk-start cursors
        ``starts [B]`` -> ``[B, n_frames, n_classes]``.

        This is the live-slot counterpart of ``retire_rows``: partial-
        logits streaming needs every chunk's rows for every advancing
        session, and copying the whole output buffer per chunk would
        scale with utterance length — the window slice scales with the
        chunk only.  Same detach-before-donation contract."""
        return self._snapshot_chunk(out_buf, jnp.asarray(starts, jnp.int32),
                                    n=int(n_frames))

    # -- telemetry -----------------------------------------------------------

    def measured_sparsity(self, state: PoolState) -> Dict[str, float]:
        """Single host fetch of the device-resident accumulators."""
        return tele.measured_sparsity(state.telemetry, self.n_cols)

    def telemetry_totals(self, state: PoolState) -> jax.Array:
        """Dispatch (NOT fetch) the `[3]` running-totals reduction of the
        telemetry accumulators: ``[sum nnz/cols, sum overflow, sum
        steps]``.  The observability fold enqueues this each chunk
        boundary and reads the value one boundary later, so live
        incremental-sparsity reporting never syncs on the in-flight
        chunk (see telemetry.fold_totals)."""
        return self._tel_totals(state.telemetry)
