"""The one traffic generator: every mix under ``traffic/`` is data for it.

A mix file names its ``kind`` and the parameters of that kind:

``offline``   batch transcription with a standing backlog.  A closed loop:
              ``clients_per_slot * capacity`` clients each ``submit()`` one
              whole utterance after another, with no partial logits.  The
              server free-runs (``target_chunk_ms`` 0).

Every seed gets the same multiset of utterance lengths (stratified
quantiles of the mix's length distribution); the seed only orders them
and makes the features.  The server is driven in-process through its
public client API (``stream``/``close``/``cancel``, ``submit``).
"""
from __future__ import annotations

import asyncio
import dataclasses
import itertools
import math
import time
from statistics import NormalDist
from typing import Any, Callable, Dict, List, Tuple

import numpy as np


def stratified_lengths(spec: Dict[str, Any], n: int) -> np.ndarray:
    """``n`` lengths at the mid-quantiles of a clipped lognormal."""
    nd = NormalDist()
    qs = [(i + 0.5) / n for i in range(n)]
    raw = [spec["median"] * math.exp(spec["sigma"] * nd.inv_cdf(q))
           for q in qs]
    return np.clip(np.rint(raw), spec["min"], spec["max"]).astype(np.int32)


@dataclasses.dataclass
class Outcome:
    """What a drive returns to the harness."""

    e2e: Dict[str, float]
    attempted: int
    failed: int
    window: Tuple[float, float]              # perf_counter bounds
    sample: Dict[int, np.ndarray]            # utterance id -> served logits
    notes: Dict[str, Any]


class Hooks:
    """Called by a drive at the window's edges (tracing, compile counts)
    and at the end of each phase of its set-up."""

    def mark(self, phase: str) -> None:
        pass

    def window_start(self) -> None:
        pass

    def window_end(self) -> None:
        pass


def server_shape(mix: Dict[str, Any]) -> Dict[str, Any]:
    """The server's constructor arguments that this mix fixes."""
    return {"capacity": int(mix["capacity"]),
            "chunk_frames": int(mix["chunk_frames"]),
            "target_chunk_ms": float(mix["target_chunk_ms"]),
            "max_frames": int(mix["max_frames"])}


def sample_ids(lengths: np.ndarray, n: int,
               rng: np.random.Generator) -> List[int]:
    """A seeded sample of utterance ids with the longest one in it."""
    longest = int(np.argmax(lengths))
    rest = [int(i) for i in rng.permutation(len(lengths)) if i != longest]
    return [longest] + rest[:max(n - 1, 0)]


async def drive(mix: Dict[str, Any], make_server: Callable[..., Any],
                utts: List[np.ndarray], seconds: float,
                rng: np.random.Generator, hooks: Hooks,
                log: Callable[[str], None]) -> Outcome:
    kinds = {"offline": _offline}
    if mix["kind"] not in kinds:
        raise ValueError(f"unknown traffic kind {mix['kind']!r}")
    return await kinds[mix["kind"]](mix, make_server, utts, seconds, rng,
                                    hooks, log)


# -- offline -----------------------------------------------------------------

async def _offline(mix, make_server, utts, seconds, rng, hooks, log):
    shape = server_shape(mix)
    cap, chunk = shape["capacity"], shape["chunk_frames"]
    lengths = np.array([u.shape[0] for u in utts])
    keep = set(sample_ids(lengths, int(mix["sample"]), rng))
    order = itertools.chain.from_iterable(
        rng.permutation(len(utts)) for _ in itertools.count())
    server = make_server(**shape)
    done: List[Tuple[float, int, bool]] = []      # (t, frames, failed)
    sample: Dict[int, Tuple[float, np.ndarray]] = {}
    live: Dict[int, Any] = {}                     # client -> open handle
    state = {"stop": False, "t0": math.inf}
    ramped = asyncio.Event()
    n_clients = int(round(mix["clients_per_slot"] * cap))

    async def client(c: int) -> None:
        while not state["stop"]:
            uid = int(next(order))
            try:
                live[c] = handle = await server.stream(utts[uid],
                                                       want_partials=False)
                handle.close()
                res = await handle.result()
            except asyncio.CancelledError:
                if state["stop"] and not asyncio.current_task().cancelling():
                    return                  # the harness ended the request
                raise
            except Exception as exc:        # counted; then this client stops
                done.append((time.perf_counter(), 0, True))
                log(f"request failed: {type(exc).__name__}: {exc}")
                return
            t = time.perf_counter()
            bad = bool(res.truncated) or res.logits.shape[0] != \
                utts[uid].shape[0]
            done.append((t, int(res.logits.shape[0]), bad))
            if uid in keep and uid not in sample and t >= state["t0"]:
                sample[uid] = (t, res.logits)
            if len(done) >= mix["ramp_completions"]:
                ramped.set()

    async with server:
        # warm-up: one wave of each admission size up to half the pool,
        # on an idle pool so that each wave is admitted at one boundary;
        # one chunk long, as every chunk of the backlogged window is
        for r in (1 << k for k in range(int(math.log2(cap // 2)) + 1)):
            await asyncio.gather(*(server.submit(utts[j % len(utts)][:chunk])
                                   for j in range(r)))
            await asyncio.sleep(0.05)
        hooks.mark("warm-up waves")
        tasks = [asyncio.create_task(client(c)) for c in range(n_clients)]
        await ramped.wait()
        log(f"ramp: {len(done)} completions, {n_clients} clients")
        t0 = time.perf_counter()
        state["t0"] = t0
        hooks.window_start()
        await asyncio.sleep(seconds)
        t1 = time.perf_counter()
        state["stop"] = True
        hooks.window_end()
        for handle in live.values():        # requests still in flight end
            handle.cancel()
        await asyncio.gather(*tasks)
    in_win = [(f, bad) for t, f, bad in done if t0 <= t < t1]
    frames = sum(f for f, bad in in_win if not bad)
    return Outcome(
        e2e={"frames_per_s": frames / (t1 - t0)},
        attempted=len(in_win), failed=sum(bad for _, bad in in_win),
        window=(t0, t1),
        sample={uid: lg for uid, (t, lg) in sample.items() if t < t1},
        notes={"completions_in_window": len(in_win),
               "frames_in_window": frames})
