"""Representative lowering cases for the hot-path contracts.

A :class:`ContractCase` binds a registered contract name to a recipe
that builds a jitted callable plus concrete arguments — the same shapes,
engine configuration and pool wiring the serving tests use (hidden=32,
gamma=0.75, m=4, a 4-slot pool, 4-frame chunks) — so the checker
inspects the HLO that actually ships, not a toy.  ``build_cases()``
returns every case runnable on the current device topology; the sharded
``step_chunk`` case appears only when the interpreter was started with
enough emulated devices (``XLA_FLAGS=--xla_force_host_platform_device_count=4``,
as the CI lint job and the sharded subprocess tests do).

The pool-chunk lowering helper here is also the shared replacement for
the ad-hoc ``_lower_chunk_hlo`` helpers that used to live in
``tests/test_observability.py`` and the sharded subprocess script.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, List, Mapping, Optional, Sequence, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from . import hlo

# Test-scale model constants, matching tests/test_observability.py and
# tests/test_sharded_serving.py so case HLO is the HLO those suites pin.
INPUT_DIM = 20
HIDDEN = 32
CLASSES = 11
GAMMA = 0.75
M = 4
THETA = 0.05
LENS = (5, 9, 3, 12, 1, 7, 8, 2)


@dataclasses.dataclass
class BuiltCase:
    """A jitted callable plus concrete arguments, ready to lower."""

    fn: Any
    args: Tuple[Any, ...]
    kwargs: Dict[str, Any]
    donate_argnums: Tuple[int, ...] = ()

    def donated_args(self) -> List[Any]:
        return [self.args[i] for i in self.donate_argnums]

    def donated_leaf_count(self) -> int:
        return len(jax.tree_util.tree_leaves(self.donated_args()))


@dataclasses.dataclass
class ContractCase:
    """One (contract, representative arguments) pair for the checker.

    ``build`` must return fresh arguments on every call: the donation
    probe executes the function once, consuming the donated buffers.
    ``op_budget_override`` tightens/relaxes the contract's op budgets for
    this case only (e.g. the scatter-route chunk legitimately contains
    the top-k sort that the dense-mirror route must not).
    """

    name: str
    contract: str
    build: Callable[[], BuiltCase]
    run_donation_probe: bool = True
    min_devices: int = 1
    op_budget_override: Mapping[str, int] = dataclasses.field(
        default_factory=dict)


# -- engines (cached: packing is the expensive part) --------------------------


@functools.lru_cache(maxsize=None)
def _engine(spmv_path: str = "auto", use_pallas: bool = False,
            quant: bool = False):
    from repro.core.quantization import QuantConfig
    from repro.models import lstm_am
    from repro.serving import BatchedSpartusEngine, EngineConfig

    cfg = lstm_am.LSTMAMConfig(input_dim=INPUT_DIM, hidden_dim=HIDDEN,
                               n_layers=2, n_classes=CLASSES)
    params = lstm_am.cbtd_prune_stacks(
        lstm_am.init_params(jax.random.key(0), cfg), gamma=GAMMA, m=M)
    ecfg = EngineConfig(theta=THETA, gamma=GAMMA, m=M, capacity_frac=1.0,
                        use_pallas=use_pallas, spmv_path=spmv_path,
                        quant=QuantConfig() if quant else None)
    return BatchedSpartusEngine(params, cfg, ecfg)


@functools.lru_cache(maxsize=None)
def _gru_engine():
    """The DeltaGRU build of the same test-scale model: dense weights
    (gamma 0), so every layer takes the dense-mirror route."""
    from repro.models import lstm_am
    from repro.serving import BatchedSpartusEngine, EngineConfig

    cfg = lstm_am.LSTMAMConfig(input_dim=INPUT_DIM, hidden_dim=HIDDEN,
                               n_layers=2, n_classes=CLASSES, cell="gru")
    params = lstm_am.init_params(jax.random.key(0), cfg)
    ecfg = EngineConfig(theta=THETA, gamma=0.0, m=M, capacity_frac=1.0)
    return BatchedSpartusEngine(params, cfg, ecfg)


def _feats(n: int = 4) -> List[np.ndarray]:
    return [np.asarray(
        jax.random.normal(jax.random.key(800 + i), (t, INPUT_DIM)),
        np.float32) for i, t in enumerate(LENS[:n])]


# -- the pool-chunk recipe (shared with the test suites) ----------------------


def built_pool_chunk(
    engine: Any,
    feats: Sequence[np.ndarray],
    *,
    capacity: int = 4,
    max_frames: int = 16,
    chunk_frames: int = 4,
    n_devices: Optional[int] = None,
    observability: Any = None,
) -> BuiltCase:
    """Admit ``feats`` into a fresh SessionPool and stage the chunk step
    exactly as a serving run would, returning it ready to lower."""
    from repro.serving import StreamRequest
    from repro.serving.scheduler import SessionPool

    kwargs: Dict[str, Any] = {}
    if n_devices is not None:
        kwargs["n_devices"] = n_devices
    if observability is not None:
        kwargs["observability"] = observability
    pool = SessionPool(engine, capacity=capacity, max_frames=max_frames,
                       chunk_frames=chunk_frames, **kwargs)
    for i in range(capacity):
        pool.admit(StreamRequest(100 + i, 0, feats[i % len(feats)]), 0)
    pool._reap_cancelled()
    active, reset = pool._masks()
    pool._flush_uploads()
    return BuiltCase(
        fn=engine._step_chunk,
        args=(pool.state, pool._frames, pool._lengths, pool._dev1d(active),
              pool._dev1d(reset), pool._out),
        kwargs={"n_frames": chunk_frames},
        donate_argnums=(0, 5),
    )


def lower_pool_chunk(
    engine: Any,
    feats: Sequence[np.ndarray],
    observability: Any = None,
    *,
    capacity: int = 4,
    max_frames: int = 16,
    chunk_frames: int = 4,
    n_devices: Optional[int] = None,
) -> str:
    """Optimized HLO text of the pool's compiled chunk step.

    This is the shared form of the ``_lower_chunk_hlo`` helper the
    observability tests and the sharded-serving subprocess pin both use.
    """
    built = built_pool_chunk(
        engine, feats, capacity=capacity, max_frames=max_frames,
        chunk_frames=chunk_frames, n_devices=n_devices,
        observability=observability)
    return hlo.compiled_text(built.fn, *built.args, **built.kwargs)


# -- per-contract case builders ----------------------------------------------


def _built_step_chunk(spmv_path: str, quant: bool = False) -> BuiltCase:
    return built_pool_chunk(_engine(spmv_path, quant=quant), _feats())


def _built_step_chunk_sharded() -> BuiltCase:
    return built_pool_chunk(_engine(), _feats(8), capacity=8, n_devices=4)


def _built_step_chunk_restored() -> BuiltCase:
    """The chunk step as staged by a pool REBUILT from a checkpoint — the
    watchdog-recovery / preemption-resume path (serving/checkpoint.py).

    Restore is host-side assembly plus the standard upload wave, so the
    dispatch a restored pool stages must be the very same compiled
    ``_step_chunk`` with the same shapes, donation and op budgets as a
    fresh pool's — zero ops added by having been through a checkpoint."""
    from repro.serving import StreamRequest
    from repro.serving import checkpoint as ckptlib
    from repro.serving.scheduler import SessionPool

    engine = _engine()
    feats = _feats()
    pool = SessionPool(engine, capacity=4, max_frames=16, chunk_frames=4)
    for i in range(4):
        pool.admit(StreamRequest(100 + i, 0, feats[i]), 0)
    pool.step_chunk(0)                      # mid-flight recurrent state
    ckpt = ckptlib.snapshot_pool(pool)
    pool2 = SessionPool(engine, capacity=4, max_frames=16, chunk_frames=4)
    ckptlib.restore_into(pool2, ckpt)
    pool2._reap_cancelled()
    active, reset = pool2._masks()
    pool2._flush_uploads()
    return BuiltCase(
        fn=engine._step_chunk,
        args=(pool2.state, pool2._frames, pool2._lengths,
              pool2._dev1d(active), pool2._dev1d(reset), pool2._out),
        kwargs={"n_frames": 4},
        donate_argnums=(0, 5),
    )


def _spmv_args(spmv_path: str, quant: bool = False) -> Tuple[Any, ...]:
    layer = _engine(spmv_path, quant=quant).layers[0]
    k = layer.capacity
    idx = jnp.tile(jnp.arange(k, dtype=jnp.int32) %
                   (layer.input_dim + layer.hidden_dim), (4, 1))
    vals = jax.random.normal(jax.random.key(5), (4, k), jnp.float32)
    return layer, idx, vals


def _built_spmv_scatter(use_pallas: bool, quant: bool = False) -> BuiltCase:
    from repro.kernels import ops

    layer, idx, vals = _spmv_args("scatter", quant=quant)
    kwargs: Dict[str, Any] = {"s": layer.enc.s, "use_pallas": use_pallas}
    if quant:
        kwargs["scale"] = layer.scale   # int8 payload + epilogue dequant
    return BuiltCase(
        fn=ops.stsp_spmv_batch,
        args=(layer.enc.val, layer.enc.lidx, idx, vals),
        kwargs=kwargs,
        donate_argnums=(),
    )


def _built_spmv_dense(quant: bool = False) -> BuiltCase:
    from repro.kernels import ops

    layer, _, _ = _spmv_args("dense", quant=quant)
    delta = jax.random.normal(jax.random.key(7),
                              (4, layer.w_dense_t.shape[0]), jnp.float32)
    kwargs: Dict[str, Any] = {"capacity": layer.capacity}
    if quant:
        kwargs["scale"] = layer.scale
    return BuiltCase(
        fn=ops.delta_spmv_dense_topk_batch,
        args=(layer.w_dense_t, delta),
        kwargs=kwargs,
        donate_argnums=(),
    )


def _built_fold_totals() -> BuiltCase:
    engine = _engine()
    return BuiltCase(fn=engine._tel_totals,
                     args=(engine.init_state(4).telemetry,),
                     kwargs={}, donate_argnums=())


def _built_bank_rows() -> BuiltCase:
    from repro.kernels import ops

    buf = jnp.zeros((4, 16, CLASSES), jnp.float32)
    rows = jax.random.normal(jax.random.key(9), (4, 4, CLASSES), jnp.float32)
    start = jnp.asarray([0, 4, 8, 2], jnp.int32)
    return BuiltCase(fn=jax.jit(ops.bank_rows), args=(buf, rows, start),
                     kwargs={}, donate_argnums=())


def _built_gather_rows() -> BuiltCase:
    from repro.kernels import ops

    buf = jax.random.normal(jax.random.key(11), (4, 16, CLASSES), jnp.float32)
    start = jnp.asarray([0, 4, 8, 2], jnp.int32)
    return BuiltCase(fn=jax.jit(ops.gather_rows, static_argnames=("n",)),
                     args=(buf, start), kwargs={"n": 4}, donate_argnums=())


def _built_retire_rows() -> BuiltCase:
    engine = _engine()
    buf = jax.random.normal(jax.random.key(12), (4, 16, CLASSES), jnp.float32)
    slot_idx = jnp.asarray([2, 0, 2], jnp.int32)   # last index: padding
    return BuiltCase(fn=engine._retire_rows, args=(buf, slot_idx),
                     kwargs={}, donate_argnums=())


def _built_gather_frames() -> BuiltCase:
    from repro.kernels import ops

    frames = jax.random.normal(jax.random.key(13), (4, 8, INPUT_DIM),
                               jnp.float32)
    cursor = jnp.asarray([0, 3, 7, 2], jnp.int32)
    return BuiltCase(fn=jax.jit(ops.gather_frames), args=(frames, cursor),
                     kwargs={}, donate_argnums=())


def _built_place_blocks() -> BuiltCase:
    """One admission wave as ``SessionPool._place_staged`` ships it into
    a 4-slot pool at T_buf = 2K: a 300-frame utterance in two blocks, a
    5-frame one in one, and a padding block (slot 4) that must drop."""
    from repro.serving.scheduler import UPLOAD_BLOCK_FRAMES, _device_place

    k = UPLOAD_BLOCK_FRAMES
    frames = jax.random.normal(jax.random.key(14), (4, 2 * k, INPUT_DIM),
                               jnp.float32)
    blocks = jax.random.normal(jax.random.key(15), (4, k, INPUT_DIM),
                               jnp.float32)
    return BuiltCase(
        fn=_device_place,
        args=(frames, jnp.zeros((4,), jnp.int32), blocks,
              np.asarray([1, 1, 3, 4], np.int32),
              np.asarray([0, k, 0, 0], np.int32),
              np.asarray([300, 300, 5, 0], np.int32)),
        kwargs={}, donate_argnums=(0, 1))


def build_cases(*, include_sharded: Optional[bool] = None) -> List[ContractCase]:
    """Every contract case runnable on the current device topology.

    Importing the annotated modules registers the contracts themselves,
    so do that before any lookup.
    """
    from repro.kernels import ops  # noqa: F401  (registers contracts)
    from repro.serving import batched_engine, scheduler, telemetry  # noqa: F401

    if include_sharded is None:
        include_sharded = jax.device_count() >= 4
    cases = [
        ContractCase("step_chunk/dense-mirror", "step_chunk",
                     lambda: _built_step_chunk("auto"),
                     op_budget_override={"sort": 0}),
        ContractCase("step_chunk/scatter", "step_chunk",
                     lambda: _built_step_chunk("scatter")),
        ContractCase("step_chunk/gru-dense-mirror", "step_chunk",
                     lambda: built_pool_chunk(_gru_engine(), _feats()),
                     op_budget_override={"sort": 0}),
        ContractCase("step_chunk/post-restore", "step_chunk",
                     _built_step_chunk_restored,
                     op_budget_override={"sort": 0}),
        ContractCase("stsp_spmv_batch/xla-scatter", "stsp_spmv_batch",
                     lambda: _built_spmv_scatter(False)),
        ContractCase("stsp_spmv_batch/pallas", "stsp_spmv_batch",
                     lambda: _built_spmv_scatter(True)),
        ContractCase("stsp_spmv_batch/dense-mirror", "delta_spmv_dense_topk",
                     _built_spmv_dense),
        # quantized builds of the same hot paths: int8 weight payloads with
        # the scale-epilogue dequant must honour every fp32 clause —
        # donation, zero collectives, op budgets (docs/quantization.md):
        ContractCase("step_chunk/quant-int8", "step_chunk",
                     lambda: _built_step_chunk("auto", quant=True),
                     op_budget_override={"sort": 0}),
        ContractCase("stsp_spmv_batch/quant-scatter", "stsp_spmv_batch",
                     lambda: _built_spmv_scatter(False, quant=True)),
        ContractCase("stsp_spmv_batch/quant-dense-mirror",
                     "delta_spmv_dense_topk",
                     lambda: _built_spmv_dense(quant=True)),
        ContractCase("fold_totals", "fold_totals", _built_fold_totals),
        ContractCase("bank_rows", "bank_rows", _built_bank_rows),
        ContractCase("gather_rows", "gather_rows", _built_gather_rows),
        ContractCase("retire_rows", "retire_rows", _built_retire_rows),
        ContractCase("gather_frames", "gather_frames", _built_gather_frames),
        ContractCase("place_blocks", "place_blocks", _built_place_blocks),
    ]
    if include_sharded:
        cases.append(
            ContractCase("step_chunk/sharded-4dev", "step_chunk",
                         _built_step_chunk_sharded, min_devices=4))
    return cases
