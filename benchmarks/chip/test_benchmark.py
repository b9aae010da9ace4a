"""CPU checks of the chip benchmark: ``pytest benchmarks/chip``.

Nothing here needs a chip.  The harness is driven end to end on the CPU
at a tiny width, sound and with the served path broken, and the
lower-precision control is held to each configuration's limits.
"""
from __future__ import annotations

import json
import sys
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import compare  # noqa: E402
import control  # noqa: E402
import reference  # noqa: E402
import run  # noqa: E402
import speech  # noqa: E402
import trace_reduce  # noqa: E402
import traffic  # noqa: E402
import work  # noqa: E402

BENCH = json.loads((run.ROOT / "BENCHMARK.json").read_text())
CONFIGS = {c["name"]: json.loads((run.ROOT / c["file"]).read_text())
           for c in BENCH["configs"]}


# -- work per frame against hand counts --------------------------------------

@pytest.mark.parametrize("name,ops", [
    # 2x1024: stacks (123+1024)*64*4 + 2048*64*4 nonzeros, FCL 1024^2,
    # logit 41*1024
    ("lstm_2l_1024h", 2 * 1_908_480),
    # 3x512: stacks (123+512)*64*2 + 2*1024*64*2, FCL 512^2, logit 41*512
    ("lstm_3l_512h", 2 * 626_560),
])
def test_work_matches_hand_counts(name, ops):
    assert work.ops_per_frame(CONFIGS[name]) == ops


def test_reference_weights_prune_to_the_column_balance():
    cfg = dict(CONFIGS["lstm_3l_512h"], hidden_dim=64, m=8, input_dim=20)
    params = reference.make_params(7, cfg)
    for lp in params["lstm"]:
        w = np.concatenate([lp["w_x"], lp["w_h"]], axis=1)
        s = w.shape[0] // cfg["m"]
        sub = w.reshape(s, cfg["m"], -1)
        assert np.all((sub != 0).sum(axis=0) <= s - int(s * cfg["gamma"]))
        step, _ = reference.grid(cfg["weights"]["lstm_scale"] /
                                 np.sqrt(cfg["hidden_dim"]))
        assert np.all(np.abs(w / step - np.rint(w / step)) == 0)


# -- the trace reduction on a small recorded trace ---------------------------

def _ev(meta, start_us, dur_us):
    return (f"events {{ metadata_id: {meta} offset_ps: {start_us * 10**6} "
            f"duration_ps: {dur_us * 10**6} }}")


def _trace():
    """Device ops at [0,20) [30,40) [35,50) [80,90) us, one module
    execution [0,50) and one [80,90); the window is [10, 100) us; the
    host was in ``bench.fetch`` during [55, 75) inside ``bench.tick``
    [52, 95)."""
    dev = " ".join([_ev(1, 0, 20), _ev(2, 30, 10), _ev(1, 35, 15),
                    _ev(2, 80, 10)])
    mods = " ".join([_ev(3, 0, 50), _ev(3, 80, 10)])
    host = " ".join([_ev(1, 10, 90), _ev(2, 52, 43), _ev(3, 55, 20)])
    return f"""
planes {{ id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0 {dev} }}
  lines {{ id: 2 name: "XLA Modules" timestamp_ns: 0 {mods} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "fusion.1" }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "copy.2" }} }}
  event_metadata {{ key: 3 value {{ id: 3 name: "jit__step_chunk_impl" }} }}
}}
planes {{ id: 2 name: "/host:CPU"
  lines {{ id: 1 name: "python" timestamp_ns: 0 {host} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "bench.window" }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "bench.tick" }} }}
  event_metadata {{ key: 3 value {{ id: 3 name: "bench.fetch" }} }}
}}"""


def test_trace_reduce_on_a_small_trace():
    from jax.profiler import ProfileData

    red = trace_reduce.reduce(ProfileData.from_text_proto(_trace()))
    assert red.n_devices == 1
    assert red.window_s == pytest.approx(90e-6)
    # busy inside the window: [10,20) + [30,50) + [80,90) = 40 us
    assert red.busy_s == pytest.approx(40e-6)
    assert red.idle_share == pytest.approx(50 / 90)
    # the module that starts before the window is not counted
    assert trace_reduce.module_ms(red, "step_chunk") == pytest.approx(0.01)
    assert dict(red.top_ops) == pytest.approx(
        {"fusion.1": 25e-6, "copy.2": 20e-6})
    # gaps [20,30) untraced, [50,80) mid 65 in bench.fetch, [90,100) mid 95
    # past bench.tick's end: untraced
    assert dict(red.idle_gaps) == pytest.approx(
        {"untraced": 20e-6, "bench.fetch": 30e-6})
    assert trace_reduce.module_ms(None, "step_chunk") is None


# -- files found by name -----------------------------------------------------

@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_finds_its_files(cell):
    c = run.load_cell(cell)
    assert c.config["name"] == cell.split(".")[0]
    assert c.mix["kind"] in ("offline",)
    assert {"setup_s"} < {m["name"] for m in c.end_to_end}
    assert c.per_layer
    for m in c.per_layer:
        assert callable(run.load_reader(m["name"]))


def test_unknown_device_kind_is_refused():
    assert run.load_peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(run.NoChip):
        run.load_peaks("TPU v9 imaginary")


def test_off_the_chip_the_run_is_refused():
    with pytest.raises(run.NoChip):
        run.run_cell(run.load_cell("lstm_2l_1024h.offline"), 1, 1.0, False,
                     use_cache=False, log=lambda _m: None)


# -- the control: the reference in bfloat16 fails the limits -----------------

@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_lower_precision_control_fails_the_limits(name):
    cfg = CONFIGS[name]
    mix = json.loads((HERE / "traffic" / "offline.json").read_text())
    lengths = traffic.stratified_lengths(
        dict(mix["length"], median=300, max=800), 4)
    utts = speech.utterances(11, lengths, mix["speech"])
    params = reference.make_params(5, cfg)
    ref, st = reference.reference_logits(
        params, utts, cfg, precision=cfg["matmul_precision"])
    ctrl, _ = reference.reference_logits(params, utts, cfg,
                                         dtype="bfloat16",
                                         precision="default")
    assert min(st["h_absmax"]) > 0
    assert max(st["temporal_sparsity"]) < 1
    ok, _ = compare.judge(compare.numbers(ctrl, ref, st["h_absmax"]),
                          cfg["limits"])
    assert not ok
    same, _ = compare.judge(compare.numbers(ref, ref, st["h_absmax"]),
                            cfg["limits"])
    assert same


# -- the whole run on the CPU, sound and with the served path broken ---------

def _tiny_cell() -> run.Cell:
    """The 2x1024 offline cell at hidden 64 and a pool of 8."""
    cell = run.load_cell("lstm_2l_1024h.offline")
    cell.config = dict(cell.config, hidden_dim=64, m=8)
    cell.mix = dict(cell.mix, capacity=8, chunk_frames=8, max_frames=128,
                    n_distinct=16, ramp_completions=8, sample=4)
    cell.mix["length"] = dict(cell.mix["length"], median=40, min=10,
                              max=80)
    return cell


def _run_tiny(trace: bool = False):
    return run.run_cell(_tiny_cell(), 2**31 + 99, 1.0, trace,
                        require_chip=False, use_cache=False,
                        log=lambda _m: None)


@pytest.mark.parametrize("trace", [False, True])
def test_sound_run_is_correct(trace):
    res = _run_tiny(trace)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert list(res)[-1] == "checks"
    expect = {"slot_occupancy"} if trace else {"frames_per_s", "setup_s"}
    assert expect <= set(res["metrics"])


@pytest.mark.parametrize("fault", sorted(control.FAULTS))
def test_broken_served_path_is_not_correct(monkeypatch, fault):
    from repro.serving.batched_engine import BatchedSpartusEngine

    monkeypatch.setattr(BatchedSpartusEngine, "_step_core",
                        control.FAULTS[fault](BatchedSpartusEngine._step_core))
    assert not _run_tiny()["correct"]
