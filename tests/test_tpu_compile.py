"""Compiles for a TPU v5e that is described, not attached.

Every Pallas kernel that ``use_pallas=True`` reaches is compiled by Mosaic
at the paper's widths, and the pool's chunk step at ``LSTM_2L_1024H`` by
XLA's TPU backend, one chip and four (and at EdgeDRNN's 2x768 DeltaGRU on
one).  Nothing runs: these tests catch what
the chip's compilers refuse (tile-misaligned blocks, unlowerable
primitives, a sharded step that communicates) at no chip time.  The
topology is described inside a fixture, so a worker that cannot load the
TPU compiler skips these tests and every worker collects the same ones.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, SingleDeviceSharding

from repro.analysis import hlo
from repro.configs.spartus_lstm import LSTM_2L_1024H
from repro.kernels.delta_encode import delta_encode_pallas
from repro.kernels.lstm_pointwise import lstm_pointwise_pallas
from repro.kernels.stsp_spmv import stsp_spmv_scatter_batch_pallas
from repro.models import lstm_am
from repro.serving import BatchedSpartusEngine, EngineConfig
from repro.serving import sharding as shardlib
from repro.serving.scheduler import UPLOAD_BLOCK_FRAMES, _device_place

# the paper's widths: Q columns of the layer-2 stack [x, h], M PEs, BLEN
# nonzeros per subcolumn at gamma=0.9375, S = 4H/M rows per subcolumn,
# B pool slots, K NZI capacity, H hidden
Q, M, BLEN, S, B, K, H = 2048, 64, 4, 64, 16, 1024, 1024
# chip_smoke.py's pool: 64 slots, every column in the NZI list
POOL, FULL_K = 64, Q
GAMMA, THETA = 0.9375, 0.3
T_BUF, CHUNK = 256, 32
KERNEL = "tpu_custom_call"


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip is written to the persistent
        # cache but cannot be read back without one: keep the cache out
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def params():
    cfg = LSTM_2L_1024H
    return lstm_am.cbtd_prune_stacks(
        lstm_am.init_params(jax.random.key(0), cfg), gamma=GAMMA, m=M)


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("f,act_bits", [(123 + H, None), (2 * H, None),
                                        (2 * H, 16)])
def test_delta_encode_compiles(one_chip, f, act_bits):
    x = _sds((B, f), jnp.float32, one_chip)
    txt = hlo.compiled_text(delta_encode_pallas, x, x, THETA,
                            act_bits=act_bits)
    assert KERNEL in txt


def test_lstm_pointwise_compiles(one_chip):
    txt = hlo.compiled_text(lstm_pointwise_pallas,
                            _sds((B, 4, H), jnp.float32, one_chip),
                            _sds((B, H), jnp.float32, one_chip))
    assert KERNEL in txt


@pytest.mark.parametrize("val_dtype,lidx_dtype,b,k", [
    (jnp.float32, jnp.int32, B, K),
    (jnp.int8, jnp.int8, B, K),    # quantized serving's int8 payloads
    # two [64, 2048] NZI/NZV tables overflow SMEM in one call
    (jnp.float32, jnp.int32, POOL, FULL_K),
])
def test_stsp_scatter_compiles(one_chip, val_dtype, lidx_dtype, b, k):
    txt = hlo.compiled_text(
        stsp_spmv_scatter_batch_pallas,
        _sds((Q, M, BLEN), val_dtype, one_chip),
        _sds((Q, M, BLEN), lidx_dtype, one_chip),
        _sds((b, k), jnp.int32, one_chip),
        _sds((b, k), jnp.float32, one_chip), s=S)
    assert KERNEL in txt


def _chunk_text(engine, capacity, state_shardings, slot_sharding):
    """Optimized HLO of the pool's chunk step for ``capacity`` slots."""
    state = jax.eval_shape(lambda: engine.init_state(capacity))
    state = jax.tree.map(lambda s, sh: _sds(s.shape, s.dtype, sh), state,
                         state_shardings(state))

    def slab(shape, dtype):
        return _sds(shape, dtype, slot_sharding(shape))

    return hlo.compiled_text(
        engine._step_chunk, state,
        slab((capacity, T_BUF, engine.input_dim), jnp.float32),
        slab((capacity,), jnp.int32), slab((capacity,), jnp.bool_),
        slab((capacity,), jnp.bool_),
        slab((capacity, T_BUF + CHUNK, engine.n_classes), jnp.float32),
        n_frames=CHUNK)


@pytest.mark.parametrize("use_pallas,spmv_path", [(False, "auto"),
                                                  (True, "scatter")])
def test_step_chunk_compiles(one_chip, params, use_pallas, spmv_path):
    engine = BatchedSpartusEngine(params, LSTM_2L_1024H, EngineConfig(
        theta=THETA, gamma=GAMMA, m=M, use_pallas=use_pallas,
        spmv_path=spmv_path, capacity_frac=1.0))
    txt = _chunk_text(engine, POOL,
                      lambda st: jax.tree.map(lambda _: one_chip, st),
                      lambda _: one_chip)
    assert (KERNEL in txt) == use_pallas


def test_gru_step_chunk_compiles(one_chip):
    """EdgeDRNN's dense 2x768 DeltaGRU (gamma 0) on the dense-mirror
    route: the GRU gate math fuses into the chunk program, no kernel."""
    cfg = lstm_am.LSTMAMConfig(input_dim=123, hidden_dim=768, n_layers=2,
                               n_classes=41, cell="gru")
    engine = BatchedSpartusEngine(
        lstm_am.init_params(jax.random.key(0), cfg), cfg,
        EngineConfig(theta=THETA, gamma=0.0, m=M, capacity_frac=1.0))
    txt = _chunk_text(engine, POOL,
                      lambda st: jax.tree.map(lambda _: one_chip, st),
                      lambda _: one_chip)
    assert KERNEL not in txt


def test_sharded_step_chunk_compiles_without_collectives(topo, params):
    mesh = Mesh(np.asarray(topo.devices), ("data",))
    engine = BatchedSpartusEngine(params, LSTM_2L_1024H, EngineConfig(
        theta=THETA, gamma=GAMMA, m=M, capacity_frac=1.0))
    txt = _chunk_text(
        engine, 256,
        lambda st: shardlib.pool_state_shardings(st, mesh),
        lambda shape: shardlib.slot_sharding(shape, mesh))
    assert hlo.count_collectives(txt) == 0, hlo.collective_lines(txt)[:4]


def test_admission_placement_moves_only_the_wave(one_chip):
    """At the offline cells' slab ([1024, 4096, 123] f32) and a wave of
    256 blocks, the placement program updates the donated slab in place:
    no slab-sized temporary.  The padded-row scatter it replaced is
    compiled beside it to show the pin fires: the TPU compiler relays out
    the whole slab for it, twice."""
    cap, t_buf, d, nb = 1024, 4096, 123, 256
    frames = _sds((cap, t_buf, d), jnp.float32, one_chip)
    lengths = _sds((cap,), jnp.int32, one_chip)
    vec = _sds((nb,), jnp.int32, one_chip)
    placed = _device_place.lower(
        frames, lengths,
        _sds((nb, UPLOAD_BLOCK_FRAMES, d), jnp.float32, one_chip),
        vec, vec, vec).compile()
    slab_bytes = cap * t_buf * d * 4
    assert placed.memory_analysis().temp_size_in_bytes < slab_bytes // 100
    assert hlo.alias_count(placed.as_text()) == 2

    def padded_rows(frames, lengths, rows, slots, ts):
        return (frames.at[slots].set(rows, mode="drop"),
                lengths.at[slots].set(ts, mode="drop"))

    rb = 32
    padded = jax.jit(padded_rows, donate_argnums=(0, 1)).lower(
        frames, lengths, _sds((rb, t_buf, d), jnp.float32, one_chip),
        _sds((rb,), jnp.int32, one_chip),
        _sds((rb,), jnp.int32, one_chip)).compile()
    assert padded.memory_analysis().temp_size_in_bytes >= slab_bytes
