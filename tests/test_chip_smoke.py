"""``chip_smoke.py`` on the CPU: its phases at a tiny width, with the
Pallas kernels interpreted, and its refusal to report a result off-chip.

The script itself runs only on a TPU; these tests import it and call the
phase functions directly, so the CPU suite covers the same code path the
chip run takes (the v5e compiles are pinned by tests/test_tpu_compile.py).
"""
import asyncio
import importlib.util
import pathlib
import sys

import pytest

from repro.serving import BatchedSpartusEngine, EngineConfig
from repro.serving.scheduler import SessionPool

ROOT = pathlib.Path(__file__).resolve().parents[1]
TINY = dict(hidden=32, n_static=8, m=8, n_sessions=6, max_frames=16,
            capacity=8, chunk_frames=4, n_streams=3)


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod      # its dataclasses look it up
    spec.loader.exec_module(mod)
    return mod


def test_phases_match_reference_at_tiny_width(smoke):
    lines = []
    out = smoke.run_phases(smoke.Sizes(**TINY), log=lines.append)
    # CPU matmuls are exact f32: the precision floor is nil, so every
    # route is held to the f32 summation-order tolerance
    assert out["bound"].floor["max_abs"] < smoke.ABS_TOL
    assert out["bound"].atol == smoke.ABS_TOL
    checks = [line for line in lines if "vs reference" in line]
    assert len(checks) == 3 and all(c.endswith("-> ok") for c in checks)
    assert out["server"].watchdog is False
    assert out["server"].n_recoveries == 0
    assert out["b"].n_requests == TINY["n_streams"]
    assert out["a"].total_frames > 0


def test_phase_b_fails_on_a_crashed_tick(smoke, monkeypatch):
    """With the watchdog off, a tick that raises fails phase B's clients
    instead of being recovered: a device fault cannot end in a pass."""
    sizes = smoke.Sizes(**TINY)
    data_cfg, cfg, params = smoke._model(sizes, 0)
    _, utts = smoke._utterances(data_cfg, sizes.n_streams)
    engine = BatchedSpartusEngine(params, cfg, EngineConfig(
        theta=smoke.THETA, gamma=smoke.GAMMA, m=sizes.m))
    real_tick = SessionPool.tick
    calls = []

    def tick(self, now):
        calls.append(now)
        if len(calls) == 2:
            raise RuntimeError("device fault")
        return real_tick(self, now)

    monkeypatch.setattr(SessionPool, "tick", tick)
    with pytest.raises(RuntimeError, match="device fault"):
        asyncio.run(smoke._stream_clients(engine, utts, sizes, 0))


def test_entry_point_refuses_cpu(smoke, capsys):
    assert smoke.main([]) != 0
    out = capsys.readouterr().out
    assert "platform cpu" in out
    assert '"ok"' not in out
