"""Slot-dimension data parallelism for the serving pool
(`SessionPool(n_devices=N)`, serving/sharding.py).

Three layers of coverage:

* pure spec logic (`slot_spec`, shard bounds) on abstract meshes — no
  devices needed;
* in-process parity: ``n_devices=1`` always runs; the multi-device grid
  (n_devices in {2, 4} x capacity x chunk_frames x ragged lengths,
  non-divisible-capacity fallback, mid-chunk retirement on a non-zero
  shard, admission skew) runs when the interpreter was started with
  ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the
  multi-device CI job does);
* a subprocess leg (slow) that sets the flag itself, so the tier-1 suite
  exercises the multi-device path on any machine — including the pin
  that the compiled sharded chunk contains ZERO collective ops (the
  steady state must not communicate; an iota-indexed frame gather once
  put an all-gather + all-reduce in every scan iteration).
"""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.distributed.sharding import slot_spec
from repro.models import lstm_am
from repro.serving import (
    AsyncSpartusServer,
    BatchedSpartusEngine,
    EngineConfig,
    SpartusEngine,
    StreamRequest,
    serve_requests,
)
from repro.serving import sharding as shardlib
from repro.serving.scheduler import SessionPool

INPUT_DIM, HIDDEN, CLASSES = 20, 32, 11
GAMMA, M, THETA = 0.75, 4, 0.05
LENS = [5, 9, 3, 12, 1, 7, 8, 2]

N_DEV = jax.device_count()
multi_device = pytest.mark.skipif(
    N_DEV < 4, reason="needs >= 4 devices; run with "
    "XLA_FLAGS=--xla_force_host_platform_device_count=8")


@pytest.fixture(scope="module")
def model():
    cfg = lstm_am.LSTMAMConfig(input_dim=INPUT_DIM, hidden_dim=HIDDEN,
                               n_layers=2, n_classes=CLASSES)
    params = lstm_am.init_params(jax.random.key(0), cfg)
    return lstm_am.cbtd_prune_stacks(params, gamma=GAMMA, m=M), cfg


@pytest.fixture(scope="module")
def engines(model):
    params, cfg = model
    ecfg = EngineConfig(theta=THETA, gamma=GAMMA, m=M, capacity_frac=1.0)
    return (SpartusEngine(params, cfg, ecfg),
            BatchedSpartusEngine(params, cfg, ecfg))


def _utterance(key, t):
    return np.asarray(
        jax.random.normal(jax.random.key(key), (t, INPUT_DIM)), np.float32)


@pytest.fixture(scope="module")
def workload(engines):
    e1, _ = engines
    feats = [_utterance(500 + i, t) for i, t in enumerate(LENS)]
    refs = [np.asarray(e1.run_utterance(jnp.asarray(f))) for f in feats]
    reqs = [StreamRequest(i, 2 * i, feats[i]) for i in range(len(LENS))]
    return feats, refs, reqs


# -- spec logic (no devices) --------------------------------------------------


MESH4 = jax.sharding.AbstractMesh((4,), ("data",))
MESH1 = jax.sharding.AbstractMesh((1,), ("data",))


def test_slot_spec_divisible_shards_dim():
    assert slot_spec((8, 3), MESH4) == P("data", None)
    assert slot_spec((8,), MESH4) == P("data")
    assert slot_spec((2, 8, 5), MESH4, dim=1) == P(None, "data", None)


def test_slot_spec_never_invalid():
    # non-divisible slot dim, or a trivial mesh: replicate, never error
    assert slot_spec((6, 3), MESH4) == P(None, None)
    assert slot_spec((8, 3), MESH1) == P(None, None)
    assert slot_spec((2, 6, 5), MESH4, dim=1) == P(None, None, None)


def test_shard_bounds_and_counts():
    assert shardlib.shard_bounds(8, 4) == [(0, 2), (2, 4), (4, 6), (6, 8)]
    assert shardlib.shard_bounds(8, 1) == [(0, 8)]
    assert shardlib.n_pool_shards(MESH4, 8) == 4
    assert shardlib.n_pool_shards(MESH4, 6) == 1   # fallback: replicate
    assert shardlib.n_pool_shards(MESH1, 8) == 1


# -- single-device mesh (always runs) ----------------------------------------


def test_sharded_pool_n_devices_1_parity(engines, workload):
    """n_devices=1 builds the mesh/placement path end to end (trivially
    replicated) and must be bit-comparable to the unsharded pool."""
    _, eb = engines
    feats, refs, reqs = workload
    for chunk in (1, 4):
        base, _ = serve_requests(eb, reqs, capacity=4, chunk_frames=chunk)
        res, stats = serve_requests(eb, reqs, capacity=4, chunk_frames=chunk,
                                    n_devices=1)
        for r in res:
            np.testing.assert_allclose(r.logits, refs[r.req_id], atol=1e-5)
            np.testing.assert_allclose(r.logits, base[r.req_id].logits,
                                       atol=1e-5)
        assert stats.sparsity      # telemetry survived the mesh path


def test_n_devices_overcommit_raises():
    with pytest.raises(ValueError, match="device"):
        shardlib.make_pool_mesh(max(N_DEV * 2, 1024))


# -- multi-device grid (emulated-device CI job) -------------------------------


@multi_device
def test_sharded_parity_grid(engines, workload):
    """Sharded pools (2 and 4 devices) reproduce the single-device logits
    at 1e-5 over (capacity, chunk_frames) with ragged lengths and
    staggered arrivals — including a capacity NOT divisible by the
    device count, which must fall back to replication (never-invalid)
    and still be correct."""
    _, eb = engines
    feats, refs, reqs = workload
    for n_dev in (2, 4):
        for capacity, chunk in ((4, 1), (4, 4), (8, 8), (6, 4)):
            res, _ = serve_requests(eb, reqs, capacity=capacity,
                                    chunk_frames=chunk, n_devices=n_dev)
            assert [r.req_id for r in res] == list(range(len(LENS)))
            for r in res:
                np.testing.assert_allclose(
                    r.logits, refs[r.req_id], atol=1e-5,
                    err_msg=f"n_dev={n_dev} cap={capacity} chunk={chunk} "
                            f"req={r.req_id}")


@multi_device
def test_least_loaded_shard_admission_and_skew(engines):
    """Admissions spread across shards (least-loaded placement), and a
    deliberately skewed occupancy re-balances as new sessions arrive."""
    _, eb = engines
    pool = SessionPool(eb, capacity=8, max_frames=16, chunk_frames=4,
                       n_devices=4)
    assert pool.n_shards == 4
    for i in range(4):
        assert pool.admit(StreamRequest(i, 0, _utterance(600 + i, 8)), 0)
    assert pool.shard_loads() == [1, 1, 1, 1]      # one per shard
    # skew: free shards 1..3 by cancelling their sessions, keep shard 0
    for i in range(1, 4):
        pool.cancel(i)
    pool.step_chunk(now=0)
    assert pool.shard_loads() == [1, 0, 0, 0]
    # the next admissions go to the empty shards, not next to slot 0:
    for i in range(10, 13):
        assert pool.admit(StreamRequest(i, 1, _utterance(610 + i, 8)), 1)
    assert pool.shard_loads() == [1, 1, 1, 1]
    pool.drain(now=2)


@multi_device
def test_sharded_midchunk_retirement_on_nonzero_shard(engines):
    """A session living on a non-zero shard retires mid-chunk; its slot
    is reused; logits parity holds throughout."""
    e1, eb = engines
    pool = SessionPool(eb, capacity=4, max_frames=16, chunk_frames=4,
                       n_devices=4)
    lens = [8, 3, 8, 8]                  # slot 1 (shard 1) dies mid-chunk
    feats = [_utterance(620 + i, t) for i, t in enumerate(lens)]
    for i in range(4):
        assert pool.admit(StreamRequest(i, 0, feats[i]), 0)
    assert pool.shard_loads() == [1, 1, 1, 1]
    results = []
    results.extend(pool.step_chunk(0))     # session 1 retires mid-chunk
    assert pool.shard_loads() == [1, 0, 1, 1]
    # the freed shard-1 slot is the least-loaded choice for the next
    # admission (slot reuse while its old snapshot is still in flight):
    assert pool.admit(StreamRequest(9, 4, _utterance(630, 5)), 4)
    assert pool.shard_loads() == [1, 1, 1, 1]
    now = 4
    for _ in range(3):
        results.extend(pool.step_chunk(now))
        now += 4
    results.extend(pool.flush())
    got = {r.req_id: r.logits for r in results}
    for i, f in enumerate(feats):
        ref = np.asarray(e1.run_utterance(jnp.asarray(f)))
        np.testing.assert_allclose(got[i], ref, atol=1e-5)
    ref9 = np.asarray(e1.run_utterance(jnp.asarray(_utterance(630, 5))))
    np.testing.assert_allclose(got[9], ref9, atol=1e-5)


@multi_device
def test_sharded_async_server_parity(engines, workload):
    """The asyncio front-end over a 4-device sharded pool streams the
    oracle logits (admission-while-running exercises per-shard placement
    and per-shard retirement fetches)."""
    import asyncio
    _, eb = engines
    feats, refs, _ = workload

    async def run():
        async with AsyncSpartusServer(eb, capacity=4, chunk_frames=4,
                                      max_frames=16, offload_ticks=False,
                                      n_devices=4) as srv:
            return await asyncio.gather(
                *[srv.submit(feats[i], want_partials=True)
                  for i in range(len(feats))])

    results = asyncio.run(run())
    for i, r in enumerate(results):
        np.testing.assert_allclose(r.logits, refs[i], atol=1e-5)


# -- subprocess leg: multi-device on ANY machine (tier-1) ---------------------


SUBPROCESS_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import json
    import numpy as np
    import jax, jax.numpy as jnp
    from repro.models import lstm_am
    from repro.serving import (BatchedSpartusEngine, EngineConfig,
                               SpartusEngine, StreamRequest, serve_requests)

    cfg = lstm_am.LSTMAMConfig(input_dim=20, hidden_dim=32, n_layers=2,
                               n_classes=11)
    params = lstm_am.cbtd_prune_stacks(
        lstm_am.init_params(jax.random.key(0), cfg), gamma=0.75, m=4)
    ecfg = EngineConfig(theta=0.05, gamma=0.75, m=4, capacity_frac=1.0)
    e1 = SpartusEngine(params, cfg, ecfg)
    eb = BatchedSpartusEngine(params, cfg, ecfg)
    lens = [5, 9, 3, 12, 1, 7, 8, 2]
    feats = [np.asarray(jax.random.normal(jax.random.key(700 + i), (t, 20)),
                        np.float32) for i, t in enumerate(lens)]
    refs = [np.asarray(e1.run_utterance(jnp.asarray(f))) for f in feats]
    reqs = [StreamRequest(i, 2 * i, feats[i]) for i in range(len(lens))]

    # compact grid: one sharded config plus the non-divisible replication
    # fallback — this test exists so EVERY tier-1 run exercises the
    # multi-device path; the full grid (n_devices in {1, 2, 4}, one-frame
    # chunks, 8-way, admission skew, async) runs in-process in the
    # multi-device CI job where the flag is set for the whole suite:
    max_err = 0.0
    for n_dev, cap, chunk in ((4, 8, 4), (4, 6, 4)):
        res, _ = serve_requests(eb, reqs, capacity=cap, chunk_frames=chunk,
                                n_devices=n_dev)
        for r in res:
            max_err = max(max_err, float(np.max(np.abs(
                r.logits - refs[r.req_id]))))

    # zero-communication pin: compile the sharded chunk and count
    # collective ops (the steady state must not communicate).  The
    # lowering recipe and the token scan are the shared analyzer's —
    # the same code `python -m tools.lint --contracts` runs in CI:
    from repro.analysis.cases import lower_pool_chunk
    from repro.analysis.hlo import count_collectives
    txt = lower_pool_chunk(eb, feats, capacity=8, n_devices=4)
    colls = count_collectives(txt)
    print(json.dumps({"devices": len(jax.devices()), "max_err": max_err,
                      "collectives": colls}))
""")


@pytest.mark.slow
def test_sharded_serving_subprocess_4dev():
    """4 emulated host devices in a subprocess: the sharded pool matches
    the batch-1 oracle at 1e-5 (including the non-divisible replication
    fallback), and the compiled sharded chunk contains no collective ops
    at all."""
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run(
        [sys.executable, "-c", SUBPROCESS_SCRIPT],
        capture_output=True, text=True, timeout=900,
        # JAX_PLATFORMS=cpu: the emulated host devices ARE the cpu
        # platform, and without the pin a box with a TPU plugin installed
        # burns ~8 minutes of metadata-probe timeouts before falling back
        env={"PYTHONPATH": os.path.join(repo_root, "src"),
             "PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu"},
        cwd=repo_root,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    data = json.loads(out.stdout.strip().splitlines()[-1])
    assert data["devices"] == 4
    assert data["max_err"] <= 1e-5
    assert data["collectives"] == 0


RETIRE_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import json
    import numpy as np
    import jax, jax.numpy as jnp
    from repro.analysis import hlo
    from repro.models import lstm_am
    from repro.serving import (BatchedSpartusEngine, EngineConfig,
                               StreamRequest, serve_requests)
    from repro.serving.scheduler import RETIRE_BLOCK, SessionPool

    cfg = lstm_am.LSTMAMConfig(input_dim=20, hidden_dim=32, n_layers=2,
                               n_classes=11)
    params = lstm_am.cbtd_prune_stacks(
        lstm_am.init_params(jax.random.key(0), cfg), gamma=0.75, m=4)
    eb = BatchedSpartusEngine(
        params, cfg, EngineConfig(theta=0.05, gamma=0.75, m=4,
                                  capacity_frac=1.0))
    # 40 sessions retire at one boundary (two blocks, the second padded),
    # five more at a later one:
    lens = [6] * 40 + [10] * 5
    reqs = [StreamRequest(i, 0, np.asarray(
        jax.random.normal(jax.random.key(800 + i), (t, 20)), np.float32))
        for i, t in enumerate(lens)]
    base, _ = serve_requests(eb, reqs, capacity=64, chunk_frames=4)
    compiled = eb._retire_rows._cache_size()
    sharded, _ = serve_requests(eb, reqs, capacity=64, chunk_frames=4,
                                n_devices=4)
    same_ids = [r.req_id for r in sharded] == [r.req_id for r in base]
    max_err = max(float(np.max(np.abs(a.logits - b.logits)))
                  for a, b in zip(sharded, base))

    # each device steps its own 16 slots, so the banked rows are those of
    # a 16-slot pool stepped in one-frame chunks, bit for bit; the gather
    # must not change them
    def oracle(feats, batch=16):
        t = feats.shape[0]
        frames = np.zeros((batch, t, 20), np.float32)
        frames[0] = feats
        frames = jnp.asarray(frames)
        lens = np.zeros(batch, np.int32)
        lens[0] = t
        state, bank = eb.init_state(batch), eb.init_out_buf(batch, t + 1)
        for i in range(t):
            state, bank = eb.step_chunk(state, frames, lens,
                                        np.arange(batch) == 0,
                                        np.full(batch, i == 0), bank,
                                        n_frames=1)
        return np.asarray(bank)[0, :t]

    exact = all(np.array_equal(r.logits, oracle(reqs[r.req_id].feats))
                for r in sharded[::4])

    # the retirement program of the slot-sharded bank: every collective
    # in it moves the [R, T_pad, C] block, none the bank or a shard of it
    pool = SessionPool(eb, capacity=64, max_frames=16, chunk_frames=4,
                       n_devices=4)
    b, t_pad, c = pool._out.shape
    txt = hlo.compiled_text(eb._retire_rows, pool._out,
                            jnp.arange(RETIRE_BLOCK, dtype=jnp.int32))
    colls = hlo.collective_lines(txt)
    bank = [f"f32[{n},{t_pad},{c}]" for n in (b, b // pool.n_shards)]
    print(json.dumps({
        "devices": len(jax.devices()), "n_shards": pool.n_shards,
        "same_ids": same_ids, "max_err": max_err, "exact": exact,
        "new_programs":
        eb._retire_rows._cache_size() - compiled,
        "collectives": hlo.count_collectives(txt),
        "bank_collectives": sum(any(s in l for s in bank) for l in colls),
        "block_collectives": sum(f"f32[{RETIRE_BLOCK},{t_pad},{c}]" in l
                                 for l in colls)}))
""")


@pytest.mark.slow
def test_sharded_retirement_gather_subprocess_4dev():
    """4 emulated host devices: through the retirement gather the
    slot-sharded pool serves what it banked bit for bit (the one-frame
    chunk oracle at its 16 slots per device) and matches the unsharded
    pool at 1e-5, compiles one retirement program for the pool, and that program
    moves no copy of the bank between devices (GSPMD's masked local
    gather: at most a reduction of the gathered block)."""
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run(
        [sys.executable, "-c", RETIRE_SCRIPT],
        capture_output=True, text=True, timeout=900,
        env={"PYTHONPATH": os.path.join(repo_root, "src"),
             "PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu"},
        cwd=repo_root,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    data = json.loads(out.stdout.strip().splitlines()[-1])
    assert data["devices"] == 4 and data["n_shards"] == 4
    assert data["same_ids"] and data["max_err"] <= 1e-5
    assert data["exact"]
    assert data["new_programs"] == 1
    assert data["bank_collectives"] == 0
    assert data["collectives"] == data["block_collectives"]
