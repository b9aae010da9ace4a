"""Spartus serving engine: streaming DeltaLSTM inference over CBCSC
weights — the software twin of the accelerator datapath (Fig. 4).

Per step and per layer:
  IPU   -> kernels.ops.delta_encode   (thresholded Δ, reference update)
  CTRL  -> kernels.ops.select_active_columns (fixed-capacity NZI list)
  MACs  -> kernels.ops.stsp_spmv      (CBCSC spatio-temporal SpMxSpV)
  HPE   -> kernels.ops.lstm_pointwise (gates + cell update)

The cell kind is fixed at pack time (`PackedLayer.cell`): a DeltaLSTM
layer stacks its (i, g, f, o) gates, a DeltaGRU layer (core/delta_gru.py)
its four delta memories (M_r, M_u, M_xc, M_hc); both are one [4H, D+H]
matrix, so everything up to the gate math is shared.  The batched engine
serves both; the batch-1 `SpartusEngine` and the Pallas route are
DeltaLSTM-only.

The engine exports any trained LSTM AM (models/lstm_am.py) into packed
CBCSC + int8 form, runs batched streaming sessions, and records the
per-step NZI occupancies that drive the hwsim performance model.

``use_pallas`` switches to the Pallas kernels (compiled by Mosaic on a
TPU, interpreted only where lowered for the CPU: kernels/lowering.py);
the XLA path is numerically identical.

`SpartusEngine` is deliberately slow and simple — a Python loop per
frame with host syncs for telemetry — because it is the parity oracle:
the batched pool, the chunked tick loop and the async front-end are all
pinned against its logits at 1e-5 (see docs/serving.md).  The shared
CBCSC export (`pack_lstm_layer`, via `PackedSpartusModel`) enforces
`blen_for(gamma)` at pack time and fixes each layer's SpMV route —
scatter kernels vs the pack-time dense mirror (docs/kernels.md).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (
    CBCSC, blen_for, cbcsc_decode, cbcsc_encode, int8_pack,
)
from repro.core import delta_gru
from repro.core.delta_lstm import stacked_weight_matrix
from repro.core.quantization import QuantConfig
from repro.kernels import ops
from repro.models.lstm_am import LSTMAMConfig


@dataclasses.dataclass
class PackedLayer:
    enc: CBCSC                 # CBCSC arrays (values already int8-dequantized)
    scale: jax.Array           # int8 weight scale
    bias: jax.Array            # [4, H] initial delta memories
    input_dim: int
    hidden_dim: int
    capacity: int              # NZI list capacity
    pack_overflow: int = 0     # nonzeros clipped enforcing BLEN at pack time
    # [D+H, 4H] PRE-TRANSPOSED dense mirror (dense SpMV path): stored in
    # GEMM-contraction layout because XLA CPU re-transposes `w.T` on every
    # tick otherwise (~3x the dot cost at hidden=128)
    w_dense_t: Optional[jax.Array] = None
    cell: str = "lstm"         # "lstm" (i, g, f, o) | "gru" (r, u, xc, hc)
    # MACs one slot-frame of this layer's matvec route issues, and the part
    # of them that multiplies weights the model holds (host-side counters)
    matvec_macs: int = 0
    matvec_macs_held: int = 0


@dataclasses.dataclass
class EngineConfig:
    theta: float = 0.1
    gamma: float = 0.9375
    m: int = 64                # PEs per column (CBCSC granularity)
    capacity_frac: float = 0.5  # NZI capacity as fraction of columns
    use_pallas: bool = False
    quant_bits: int = 8
    # SpMV implementation: "auto" routes layers with S*(1-gamma) >= 1 to the
    # dense-gather mirror (ops.spmv_use_dense_gather); "scatter" forces the
    # CBCSC scatter path, "dense" forces the mirror.
    spmv_path: str = "auto"
    # Quantized serving (docs/quantization.md): None keeps the fp32 path
    # byte-identical to before; a QuantConfig with enabled=True stores the
    # CBCSC payload and dense mirror as int8 at rest (dequantized in the
    # SpMV epilogue) and runs the delta threshold on the Qm.n activation
    # grid.  enabled=False behaves exactly like None.
    quant: Optional[QuantConfig] = None


def active_quant(cfg: EngineConfig) -> Optional[QuantConfig]:
    """The engine's quantization config iff quantization is actually on."""
    q = cfg.quant
    return q if (q is not None and q.enabled) else None


def _pack_stack(w: jax.Array, bias: jax.Array, hidden_dim: int,
                cfg: EngineConfig, cell: str,
                held_per_col: Optional[int] = None) -> PackedLayer:
    """Export one stacked [4H, D+H] matrix to the serving format.

    BLEN is *enforced* at ``blen_for(gamma)`` (Alg. 3), clipping the
    smallest-magnitude overflow nonzeros per subcolumn, rather than derived
    from max occupancy: an untrained or partially-pruned matrix used to
    inflate BLEN to S, silently voiding the format's bandwidth contract
    (and making ``weight_sparsity()`` report near 0).  The clipped count is
    recorded as ``pack_overflow`` — 0 for any properly CBTD-pruned model.

    ``held_per_col`` is how many weights of a column the model holds
    (counted from shapes; default every stored slot, m x BLEN): the
    ``matvec_macs_held`` counter credits that many MACs per column.
    """
    if cfg.spmv_path not in ("auto", "scatter", "dense"):
        raise ValueError(f"spmv_path must be 'auto', 'scatter' or 'dense', "
                         f"got {cfg.spmv_path!r}")
    q8, scale = int8_pack(w)
    wq = q8.astype(jnp.float32) * scale            # dequantized int8 grid
    wq = wq * (w != 0)                             # keep pruned zeros exact
    h4, n_cols = wq.shape
    m = cfg.m
    while h4 % m:
        m //= 2
    blen = blen_for(h4, m, cfg.gamma)
    enc = cbcsc_encode(wq, m, blen=blen, on_overflow="clip")
    overflow = int(jax.device_get(jnp.sum(wq != 0) - jnp.sum(enc.valid)))
    s = enc.s
    if cfg.spmv_path == "dense" or (
        cfg.spmv_path == "auto" and ops.spmv_use_dense_gather(s, cfg.gamma)
    ):
        # pack-time dense mirror: decoded from the (clipped) CBCSC arrays so
        # every SpMV path computes from identical weights; materialised
        # transposed, in the per-tick GEMM's contraction layout.
        w_dense_t = jnp.asarray(cbcsc_decode(enc, jnp.float32).T)
    else:
        w_dense_t = None
    if active_quant(cfg) is not None:
        # Int8 at rest: the fp32 payload above is already on the int8 grid
        # (wq = q8 * scale with a pow2 per-tensor scale), so dividing back
        # by the scale is exact and y*scale in the SpMV epilogue reproduces
        # the fp32 path bit for bit.  Weight memory drops 4x per element.
        # The local indices pack to the paper's 8-bit LIDX when they fit
        # (S <= 128; the kernels widen to int32 before any row math).
        lidx = enc.lidx.astype(jnp.int8) if s <= 128 else enc.lidx
        enc = dataclasses.replace(
            enc, val=jnp.round(enc.val / scale).astype(jnp.int8), lidx=lidx)
        if w_dense_t is not None:
            w_dense_t = jnp.round(w_dense_t / scale).astype(jnp.int8)
    capacity = max(int(n_cols * cfg.capacity_frac), 8)
    stored = m * blen
    held = stored if held_per_col is None else held_per_col
    # the dense mirror multiplies every column by all 4H rows; the scatter
    # route fetches m x BLEN stored slots for each of its K list entries
    cols = n_cols if w_dense_t is not None else min(capacity, n_cols)
    row_macs = h4 if w_dense_t is not None else stored
    return PackedLayer(
        enc=enc, scale=scale, bias=bias,
        input_dim=n_cols - hidden_dim, hidden_dim=hidden_dim,
        capacity=capacity, pack_overflow=overflow, w_dense_t=w_dense_t,
        cell=cell, matvec_macs=cols * row_macs,
        matvec_macs_held=cols * min(held, row_macs),
    )


def pack_lstm_layer(params: Dict[str, Any], cfg: EngineConfig) -> PackedLayer:
    """Export one (CBTD-pruned) LSTM layer: the (i, g, f, o) stack of eq.
    (8), biases as the initial delta memories."""
    return _pack_stack(stacked_weight_matrix(params), params["b"],
                       params["w_h"].shape[1], cfg, "lstm")


def pack_gru_layer(params: Dict[str, Any], cfg: EngineConfig) -> PackedLayer:
    """Export one DeltaGRU layer (core/delta_gru.py params): the
    (r, u, xc, hc) stack with its two structural-zero blocks.  Each column
    holds 3H weights of the model (r, u and one of xc / hc)."""
    if cfg.use_pallas:
        raise ValueError("use_pallas=True serves DeltaLSTM layers only: "
                         "there is no Pallas DeltaGRU kernel")
    h = params["w_h"].shape[1]
    return _pack_stack(delta_gru.stacked_weight_matrix(params),
                       delta_gru.initial_delta_memories(params), h, cfg,
                       "gru", held_per_col=3 * h)


_PACKS = {"lstm": pack_lstm_layer, "gru": pack_gru_layer}


class LayerState:
    """Mutable per-session state of one DeltaLSTM layer (x̂/ĥ/c/h/DM)."""

    def __init__(self, layer: PackedLayer, dtype=jnp.float32):
        d, h = layer.input_dim, layer.hidden_dim
        self.s_hat = jnp.zeros((d + h,), dtype)    # concatenated x̂ / ĥ
        self.c = jnp.zeros((h,), dtype)
        self.h = jnp.zeros((h,), dtype)
        self.dm = layer.bias.astype(dtype).reshape(-1)  # [4H]


def _step_layer(
    layer: PackedLayer, state: LayerState, x: jax.Array, cfg: EngineConfig
) -> Tuple[jax.Array, Dict[str, int]]:
    """One streaming step of one layer.  x: [D] -> h: [H]."""
    if layer.cell != "lstm":
        raise NotImplementedError(
            f"the batch-1 SpartusEngine steps DeltaLSTM layers only, not "
            f"{layer.cell!r}; serve other cells with BatchedSpartusEngine")
    quant = active_quant(cfg)
    act_kw = (
        {"act_bits": quant.act_bits, "act_frac_bits": quant.act_frac_bits}
        if quant is not None else {}
    )
    wscale = layer.scale if quant is not None else None
    val, lidx, mirror = layer.enc.val, layer.enc.lidx, layer.w_dense_t
    if quant is not None:
        # int8 at rest INSIDE the compiled module too: the weights are
        # closed-over constants, and without a barrier XLA folds
        # convert(s8 const) into a baked f32 constant — silently
        # restoring the fp32 footprint the quant mode exists to shed.
        if mirror is not None:
            mirror = jax.lax.optimization_barrier(mirror)
        else:
            val, lidx = jax.lax.optimization_barrier((val, lidx))
    s = jnp.concatenate([x, state.h])
    delta, s_hat, nnz = ops.delta_encode(
        s, state.s_hat, cfg.theta, use_pallas=cfg.use_pallas, **act_kw
    )
    if mirror is not None:
        # B=1 leg of the exact batched dense-mirror computation, so pooled
        # and batch-1 logits stay bit-comparable on the dense path:
        y, dropped = ops.delta_spmv_dense_topk_batch(
            mirror, delta[None], layer.capacity, scale=wscale)
        y, dropped = y[0], dropped[0]
    else:
        idx, vals, dropped = ops.select_active_columns(delta, layer.capacity)
        y = ops.stsp_spmv(
            val, lidx, idx, vals, s=layer.enc.s,
            use_pallas=cfg.use_pallas, scale=wscale,
        )
    dm = state.dm + y.astype(state.dm.dtype)
    h_new, c_new = ops.lstm_pointwise(
        dm.reshape(4, layer.hidden_dim), state.c, use_pallas=cfg.use_pallas
    )
    state.s_hat = s_hat
    state.c = c_new
    state.h = h_new
    state.dm = dm
    stats = {"nnz": int(nnz), "dropped": int(dropped),
             "n_cols": int(s.shape[0])}
    return h_new, stats


class PackedSpartusModel:
    """CBCSC export + weight accounting shared by the batch-1 engine and
    the continuous-batching engine (serving/batched_engine.py)."""

    def __init__(self, am_params: Dict[str, Any], am_cfg: LSTMAMConfig,
                 cfg: EngineConfig = EngineConfig()):
        self.cfg = cfg
        cell = am_cfg.cell
        if cell not in _PACKS:
            raise ValueError(f"cell must be one of {sorted(_PACKS)}, got "
                             f"{cell!r}")
        self.layers = [_PACKS[cell](lp, cfg) for lp in am_params[cell]]
        self.fcl = am_params["fcl"]
        self.logit = am_params["logit"]
        self.am_cfg = am_cfg

    @property
    def input_dim(self) -> int:
        return self.layers[0].input_dim

    @property
    def n_classes(self) -> int:
        return self.logit["w"].shape[0]

    @property
    def cell(self) -> str:
        return self.am_cfg.cell

    @property
    def matvec_macs(self) -> Tuple[int, int]:
        """(MACs, MACs on held weights) of one slot-frame's matvecs over
        every layer, from the pack's shapes."""
        return (sum(l.matvec_macs for l in self.layers),
                sum(l.matvec_macs_held for l in self.layers))

    @property
    def n_cols(self) -> List[int]:
        """Stacked-matrix column count per layer (telemetry reduction)."""
        return [l.input_dim + l.hidden_dim for l in self.layers]

    def weight_sparsity(self) -> float:
        """Fraction of zero weights in the packed layers.  Because pack time
        enforces BLEN = blen_for(gamma), this is >= 1 - BLEN/S even for an
        unpruned matrix (overflow is clipped, see ``pack_overflow_count``)
        instead of collapsing to ~0 when BLEN used to track max occupancy."""
        dense = sum(l.enc.h * l.enc.q for l in self.layers)
        nnz = sum(float(jnp.sum(l.enc.valid)) for l in self.layers)
        return 1.0 - nnz / dense

    def pack_overflow_count(self) -> int:
        """Total nonzeros clipped across layers enforcing BLEN at pack time
        (0 for a properly CBTD-pruned model; > 0 flags that the exported
        weights deviate from the training-time matrix)."""
        return sum(l.pack_overflow for l in self.layers)

    def weight_bytes(self) -> int:
        """Bytes of packed weight memory at rest: CBCSC payloads (val +
        lidx + valid), dense mirrors, biases, and the fc/logit head.  This
        is the model's share of a pool's device footprint — with
        ``cfg.quant`` enabled the val/mirror terms are int8 (4x smaller),
        while the int32 lidx bookkeeping and the fp32 head are unchanged
        (docs/quantization.md has the per-term table)."""
        def nbytes(a) -> int:
            return int(np.prod(a.shape)) * jnp.dtype(a.dtype).itemsize

        total = 0
        for l in self.layers:
            total += nbytes(l.enc.val) + nbytes(l.enc.lidx)
            total += nbytes(l.enc.valid) + nbytes(l.bias)
            total += nbytes(l.scale)
            if l.w_dense_t is not None:
                total += nbytes(l.w_dense_t)
        for p in (self.fcl, self.logit):
            total += sum(nbytes(a) for a in p.values())
        return total

    def weight_payload_bytes(self) -> int:
        """CBCSC val/lidx streams + dense mirrors only — the weight memory
        the paper's WMEM actually stores per layer (excludes the validity
        mask, biases and the fp32 fc/logit head, which are O(H) or
        amortised).  The ~4x int8 reduction applies to this term."""
        def nbytes(a) -> int:
            return int(np.prod(a.shape)) * jnp.dtype(a.dtype).itemsize

        total = 0
        for l in self.layers:
            total += nbytes(l.enc.val) + nbytes(l.enc.lidx)
            if l.w_dense_t is not None:
                total += nbytes(l.w_dense_t)
        return total


class SpartusEngine(PackedSpartusModel):
    """Multi-layer streaming engine with per-step sparsity telemetry."""

    def __init__(self, am_params: Dict[str, Any], am_cfg: LSTMAMConfig,
                 cfg: EngineConfig = EngineConfig()):
        super().__init__(am_params, am_cfg, cfg)
        self.telemetry: List[Dict[str, int]] = []

    def new_session(self) -> List[LayerState]:
        return [LayerState(l) for l in self.layers]

    def step(self, session: List[LayerState], x: jax.Array) -> jax.Array:
        """One frame through the whole AM -> logits [n_classes]."""
        h = x
        for li, (layer, st) in enumerate(zip(self.layers, session)):
            h, stats = _step_layer(layer, st, h, self.cfg)
            stats["layer"] = li
            self.telemetry.append(stats)
        h = jax.nn.relu(h @ self.fcl["w"].T + self.fcl["b"])
        return h @ self.logit["w"].T + self.logit["b"]

    def run_utterance(self, feats: jax.Array) -> jax.Array:
        """feats: [T, D] -> logits [T, n_classes] (batch-1 streaming)."""
        session = self.new_session()
        return jnp.stack([self.step(session, feats[t])
                          for t in range(feats.shape[0])])

    # -- telemetry -> hardware model -----------------------------------------

    def measured_sparsity(self) -> Dict[str, float]:
        if not self.telemetry:
            return {}
        nnz = np.array([t["nnz"] for t in self.telemetry], np.float64)
        cols = np.array([t["n_cols"] for t in self.telemetry], np.float64)
        dropped = np.array([t["dropped"] for t in self.telemetry], np.float64)
        return {
            "temporal_sparsity": float(1.0 - (nnz / cols).mean()),
            "capacity_overflow_rate": float((dropped > 0).mean()),
            "mean_active_columns": float(nnz.mean()),
        }
