"""Bring-up check of the DeltaLSTM serving path on a TPU.

Serves seeded synthetic-speech sessions through the normal entry points
at the paper's hardware-test width (``LSTM_2L_1024H``: 123-dim input,
2x1024 hidden, 41 classes; random CBTD-pruned weights, no training) and
holds every session's logits to a plain float32 reference computed on
the same chip.

    python chip_smoke.py             # one chip: phases A, B and C
    python chip_smoke.py --chips 4   # four chips: the slot-sharded pool
                                     # against the one-chip pool, only

Phases on one chip:

  A  ``serve_requests`` on ``BatchedSpartusEngine`` (the default XLA route)
     against ``lstm_am.forward`` on the packed weights at "highest" matmul
     precision;
  B  ``AsyncSpartusServer`` (``watchdog=False``) serving drip-fed streams
     through the same engine; the streamed partial logits must match
     phase A's, and no recovery, shed or error may occur;
  C  the same requests with the Pallas kernels compiled
     (``use_pallas=True, spmv_path="scatter"``) against the reference.

The script refuses to run anywhere but on a TPU: it exits non-zero before
any phase when JAX's first device is not a TPU.  Its last line is one JSON
object, ``{"ok": true, "device": {...}}``; any failed phase raises, so the
script then exits non-zero without printing it.  It runs in one process
and starts no other.
"""
from __future__ import annotations

import argparse
import asyncio
import dataclasses
import json
import os
import sys
import time
from typing import Callable, Dict, List

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

GAMMA, M, THETA = 0.9375, 64, 0.3
# The reference has no NZI capacity, so the engine's NZI list holds every
# column: no delta can be dropped, and a difference from the reference is
# an error of the route, never a different computation.
CAPACITY_FRAC = 1.0

# How far the served logits may sit from the float32 reference.  On a TPU,
# XLA runs an f32 matmul at its default precision as one bf16 pass, so the
# serving path's dense-mirror GEMM and logit head round their operands to
# 8 significant bits, and a delta that lands within that rounding of θ can
# fire on one side and not the other, which moves the trajectory.  The
# plain reference itself shows how large this is: the same forward pass
# run at default precision instead of "highest" (the precision floor,
# measured in every run).  A route is held to FLOOR_FACTOR times that
# floor, and never to less than ABS_TOL, the float32 summation-order noise
# that remains where the matmuls are exact (the CPU).  The frame-wise
# argmax must agree with the reference's at least as often as the
# default-precision reference does, less ARGMAX_SLACK.
FLOOR_FACTOR = 4.0
ABS_TOL = 1e-4
ARGMAX_SLACK = 0.02


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Model and traffic sizes; the defaults are the chip run's."""

    hidden: int = 1024        # LSTM_2L_1024H
    n_static: int = 41        # 41 static + deltas + delta-deltas = 123 dims
    m: int = M
    n_sessions: int = 48
    max_frames: int = 256     # lengths uniform in [max_frames/2, max_frames]
    capacity: int = 64
    chunk_frames: int = 32
    n_streams: int = 8        # phase B's drip-fed clients
    sharded_sessions: int = 128
    sharded_capacity: int = 256


class CompileCounter:
    """Counts XLA backend compiles and persistent-cache hits."""

    def __init__(self):
        import jax

        self.compiles = 0
        self.cache_hits = 0

        def on_duration(event, _secs, **_kw):
            if event == "/jax/core/compile/backend_compile_duration":
                self.compiles += 1

        def on_event(event, **_kw):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)


def _model(sizes: Sizes, seed: int):
    import jax
    from repro.configs.spartus_lstm import LSTM_2L_1024H
    from repro.data.speech import SpeechConfig
    from repro.models import lstm_am

    data_cfg = SpeechConfig(n_static=sizes.n_static,
                            max_frames=sizes.max_frames, seed=seed)
    cfg = dataclasses.replace(LSTM_2L_1024H, hidden_dim=sizes.hidden,
                              input_dim=data_cfg.feat_dim,
                              n_classes=data_cfg.vocab)
    params = lstm_am.cbtd_prune_stacks(
        lstm_am.init_params(jax.random.key(seed), cfg), gamma=GAMMA,
        m=sizes.m)
    return data_cfg, cfg, params


def _utterances(data_cfg, n: int):
    import numpy as np
    from repro.data.speech import SpeechDataset

    feats, n_frames, *_ = next(SpeechDataset(data_cfg, n))
    feats = np.asarray(feats, np.float32)
    n_frames = np.maximum(np.asarray(n_frames), 1)
    return feats, [feats[i, :int(n_frames[i])] for i in range(n)]


def reference_logits(engine, cfg, feats, precision: str):
    """``lstm_am.forward`` with delta on at θ, on the engine's packed
    (int8-grid, BLEN-clipped) weights: feats [N, T, D] -> [N, T, C]."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import cbcsc_decode
    from repro.models import lstm_am

    layers = []
    for layer in engine.layers:
        w = cbcsc_decode(layer.enc, jnp.float32)
        d = layer.input_dim
        layers.append({"w_x": w[:, :d], "w_h": w[:, d:], "b": layer.bias})
    params = {"lstm": layers, "fcl": engine.fcl, "logit": engine.logit}
    rcfg = dataclasses.replace(cfg, delta=True, theta=engine.cfg.theta)
    with jax.default_matmul_precision(precision):
        out, _ = jax.jit(lambda p, f: lstm_am.forward(p, rcfg, f))(
            params, jnp.asarray(feats))
    return np.asarray(out)


def _references(engine, cfg, feats, lens):
    """The reference logits per session, and the bound they set."""
    ref = reference_logits(engine, cfg, feats, "highest")
    ref = [ref[i, :n] for i, n in enumerate(lens)]
    floor = reference_logits(engine, cfg, feats, "default")
    floor = [floor[i, :n] for i, n in enumerate(lens)]
    return ref, Bound(_compare(floor, ref))


def _compare(got: List, want: List) -> Dict[str, float]:
    import numpy as np

    err = max(float(np.max(np.abs(g - w))) for g, w in zip(got, want))
    agree = np.concatenate([np.argmax(g, -1) == np.argmax(w, -1)
                            for g, w in zip(got, want)])
    return {"max_abs": err, "argmax_agree": float(agree.mean())}


class Bound:
    """The comparison every route is held to (see FLOOR_FACTOR)."""

    def __init__(self, floor: Dict[str, float]):
        self.floor = floor
        self.atol = max(FLOOR_FACTOR * floor["max_abs"], ABS_TOL)
        self.min_agree = floor["argmax_agree"] - ARGMAX_SLACK

    def check(self, what: str, cmp: Dict[str, float], log) -> None:
        ok = (cmp["max_abs"] <= self.atol
              and cmp["argmax_agree"] >= self.min_agree)
        log(f"{what}: max-abs logit error {cmp['max_abs']:.6g} (bound "
            f"{self.atol:.6g}), argmax agreement {cmp['argmax_agree']:.6f} "
            f"(bound >= {self.min_agree:.6f}) -> {'ok' if ok else 'FAIL'}")
        if not ok:
            raise AssertionError(f"{what} is outside the bound")


def _requests(utts):
    from repro.serving import StreamRequest

    return [StreamRequest(req_id=i, arrival_step=2 * i, feats=u)
            for i, u in enumerate(utts)]


def _serve(engine, utts, counter, log, what: str, **kw):
    """Warm-up run (set-up: compiles), then the timed run on the same
    requests.  Returns the timed run's per-session logits and stats."""
    import numpy as np
    from repro.serving import serve_requests

    t0 = time.perf_counter()
    warm, _ = serve_requests(engine, _requests(utts), **kw)
    t_setup = time.perf_counter() - t0
    c0 = counter.compiles
    t0 = time.perf_counter()
    res, stats = serve_requests(engine, _requests(utts), **kw)
    t_window = time.perf_counter() - t0
    assert not stats.truncated and len(res) == len(utts)
    logits = [r.logits for r in res]
    for lg, u in zip(logits, utts):
        assert lg.shape == (u.shape[0], engine.n_classes), lg.shape
        assert np.all(np.isfinite(lg)), f"{what}: non-finite logits"
    rerun = max(float(np.max(np.abs(a.logits - b.logits)))
                for a, b in zip(warm, res))
    sp = stats.sparsity
    log(f"{what}: served {stats.total_frames} frames of {len(utts)} "
        f"sessions; set-up (warm-up run, compiles included) {t_setup:.3f} s, "
        f"timed window {t_window:.3f} s, compiles in the window "
        f"{counter.compiles - c0}; temporal sparsity "
        f"{sp['temporal_sparsity']:.6f}, NZI overflow rate "
        f"{sp['capacity_overflow_rate']:.6g}; warm-up vs timed run max-abs "
        f"difference {rerun:.6g}")
    assert sp["capacity_overflow_rate"] == 0.0, f"{what}: NZI overflow"
    return logits, stats


async def _stream_clients(engine, utts, sizes: Sizes, seed: int):
    import numpy as np
    from repro.serving import AsyncSpartusServer

    async def client(server, feats, rng):
        handle = await server.stream(want_partials=True)
        j = 0
        while j < len(feats):
            n = int(rng.integers(2, 7))
            await handle.send(feats[j:j + n])
            j += n
            await asyncio.sleep(float(rng.random()) * 0.002)
        handle.close()
        partials = [p async for p in handle]
        result = await handle.result()
        return np.concatenate([p.rows for p in partials]), result

    server = AsyncSpartusServer(
        engine, sizes.capacity, chunk_frames=sizes.chunk_frames,
        max_frames=sizes.max_frames, overload_policy="wait",
        watchdog=False)
    async with server:
        out = await asyncio.gather(*[
            client(server, u, np.random.default_rng(seed + 100 + i))
            for i, u in enumerate(utts)])
    return out, server


def run_phases(sizes: Sizes = Sizes(), seed: int = 0,
               log: Callable[[str], None] = print) -> Dict[str, object]:
    """Phases A, B and C on the first device.  Raises on any failure."""
    import jax
    from repro.serving import BatchedSpartusEngine, EngineConfig

    counter = CompileCounter()
    t_start = time.perf_counter()
    data_cfg, cfg, params = _model(sizes, seed)
    feats, utts = _utterances(data_cfg, sizes.n_sessions)
    lens = [u.shape[0] for u in utts]
    log(f"model {cfg.name}: input {cfg.input_dim}, hidden {cfg.hidden_dim}, "
        f"{cfg.n_layers} layers, {cfg.n_classes} classes; theta {THETA}, "
        f"gamma {GAMMA}, m {sizes.m}; {len(utts)} sessions of "
        f"{min(lens)}-{max(lens)} frames ({sum(lens)} frames)")

    t_model = time.perf_counter()
    ecfg = EngineConfig(theta=THETA, gamma=GAMMA, m=sizes.m,
                        capacity_frac=CAPACITY_FRAC)
    engine = BatchedSpartusEngine(params, cfg, ecfg)
    t_pack = time.perf_counter()
    ref, bound = _references(engine, cfg, feats, lens)
    t_ref = time.perf_counter()
    log(f"set-up {t_ref - t_start:.3f} s (model and data "
        f"{t_model - t_start:.3f} s, pack {t_pack - t_model:.3f} s, "
        f"references {t_ref - t_pack:.3f} s); precision floor (reference "
        f"at default vs highest matmul precision): max-abs "
        f"{bound.floor['max_abs']:.6g}, argmax agreement "
        f"{bound.floor['argmax_agree']:.6f}; max |logit| "
        f"{max(float(abs(r).max()) for r in ref):.6g}")

    kw = dict(capacity=sizes.capacity, chunk_frames=sizes.chunk_frames)
    a_logits, a_stats = _serve(engine, utts, counter, log,
                               "phase A (XLA route)", **kw)
    bound.check("phase A vs reference", _compare(a_logits, ref), log)

    out, server = asyncio.run(_stream_clients(
        engine, utts[:sizes.n_streams], sizes, seed))
    streamed = [s for s, _ in out]
    for (s, r), u in zip(out, utts):
        assert s.shape == (u.shape[0], engine.n_classes), s.shape
        assert r.logits.shape == s.shape and not r.truncated
    b_stats = server.stats()
    assert server.n_recoveries == 0, "phase B: watchdog recovery"
    assert b_stats.n_requests == sizes.n_streams
    log(f"phase B (async front end): {sizes.n_streams} drip-fed streams, "
        f"{b_stats.total_frames} frames, {server.n_recoveries} recoveries, "
        f"0 sheds, 0 errors; streamed vs phase A max-abs "
        f"{_compare(streamed, a_logits[:sizes.n_streams])['max_abs']:.6g}")
    bound.check("phase B streamed vs reference",
                _compare(streamed, ref[:sizes.n_streams]), log)

    pallas = BatchedSpartusEngine(params, cfg, dataclasses.replace(
        ecfg, use_pallas=True, spmv_path="scatter"))
    c_logits, _ = _serve(pallas, utts, counter, log,
                         "phase C (compiled Pallas kernels)", **kw)
    bound.check("phase C vs reference", _compare(c_logits, ref), log)
    log(f"compiles {counter.compiles}, persistent-cache hits "
        f"{counter.cache_hits}; total {time.perf_counter() - t_start:.3f} s "
        f"on {jax.devices()[0].device_kind}")
    return {"bound": bound, "a": a_stats, "b": b_stats, "server": server}


def run_sharded(sizes: Sizes = Sizes(), n_devices: int = 4, seed: int = 0,
                log: Callable[[str], None] = print) -> None:
    """The slot-sharded pool on ``n_devices`` chips against the one-chip
    pool on the same requests.  Raises on any failure."""
    import jax
    from repro.analysis import hlo
    from repro.analysis.cases import built_pool_chunk
    from repro.serving import BatchedSpartusEngine, EngineConfig

    counter = CompileCounter()
    data_cfg, cfg, params = _model(sizes, seed)
    feats, utts = _utterances(data_cfg, sizes.sharded_sessions)
    ecfg = EngineConfig(theta=THETA, gamma=GAMMA, m=sizes.m,
                        capacity_frac=CAPACITY_FRAC)
    engine = BatchedSpartusEngine(params, cfg, ecfg)
    ref, bound = _references(engine, cfg, feats,
                             [u.shape[0] for u in utts])

    # the pool's own placement: every per-slot slab split, none replicated
    per = sizes.sharded_capacity // n_devices
    pool_kw = dict(capacity=sizes.sharded_capacity,
                   max_frames=sizes.max_frames,
                   chunk_frames=sizes.chunk_frames, n_devices=n_devices)
    built = built_pool_chunk(engine, utts, **pool_kw)
    state = built.args[0]
    slabs = [(leaf, 0) for leaf in jax.tree.leaves(state.layers)]
    slabs += [(leaf, 1) for leaf in jax.tree.leaves(state.telemetry)]
    slabs += [(a, 0) for a in (state.cursor, *built.args[1:])]
    for leaf, dim in slabs:
        shards = leaf.addressable_shards
        assert len({s.device for s in shards}) == n_devices, leaf.sharding
        assert all(s.data.shape[dim] == per for s in shards), \
            (leaf.shape, [s.data.shape for s in shards])
    n_coll = hlo.count_collectives(
        hlo.compiled_text(built.fn, *built.args, **built.kwargs))
    log(f"sharded pool: {len(slabs)} per-slot slabs, each split {per} "
        f"slots x {n_devices} devices; collectives in the compiled chunk: "
        f"{n_coll}")
    assert n_coll == 0, "the sharded chunk communicates"

    kw = dict(capacity=sizes.sharded_capacity,
              chunk_frames=sizes.chunk_frames)
    one, _ = _serve(engine, utts, counter, log, "one-chip pool", **kw)
    shd, _ = _serve(engine, utts, counter, log,
                    f"{n_devices}-chip pool", n_devices=n_devices, **kw)
    bound.check(f"{n_devices}-chip pool vs one-chip pool",
                _compare(shd, one), log)
    bound.check("one-chip pool vs reference", _compare(one, ref), log)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the slot-sharded pool on four chips "
                         "against the one-chip pool")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    print(f"[smoke] platform {dev.platform}, device_kind {dev.device_kind}, "
          f"device count {len(devices)}", flush=True)
    if dev.platform != "tpu":
        print("[smoke] no TPU: this check runs only on the chip",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"[smoke] --chips {args.chips} needs {args.chips} devices",
              file=sys.stderr)
        return 2

    from repro.compile_cache import use_compile_cache

    print(f"[smoke] compile cache {use_compile_cache()}", flush=True)

    def log(msg: str) -> None:
        print(f"[smoke] {msg}", flush=True)

    if args.chips == 4:
        run_sharded(n_devices=4, seed=args.seed, log=log)
    else:
        run_phases(seed=args.seed, log=log)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
