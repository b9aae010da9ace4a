"""CPU checks of the chip benchmark: ``pytest benchmarks/chip``.

Nothing here needs a chip.  The harness is driven end to end on the CPU
at a tiny width, sound and with the served path broken, and the
lower-precision control is held to each configuration's limits.
"""
from __future__ import annotations

import hashlib
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
LSTM = run.load_model("delta_lstm")
FAMILY = ("layer_dims", "make_params", "forward", "weights_held", "engine")


# -- work per frame against hand counts --------------------------------------

@pytest.mark.parametrize("name,ops", [
    # 2x1024: stacks (123+1024)*64*4 + 2048*64*4 nonzeros, FCL 1024^2,
    # logit 41*1024
    ("lstm_2l_1024h", 2 * 1_908_480),
    # 3x512: stacks (123+512)*64*2 + 2*1024*64*2, FCL 512^2, logit 41*512
    ("lstm_3l_512h", 2 * 626_560),
])
def test_work_matches_hand_counts(name, ops):
    assert work.ops_per_frame(LSTM, CONFIGS[name]) == ops


def test_reference_weights_prune_to_the_column_balance():
    cfg = dict(CONFIGS["lstm_3l_512h"], hidden_dim=64, m=8, input_dim=20)
    params = LSTM.make_params(7, cfg)
    for lp in params["lstm"]:
        w = np.concatenate([lp["w_x"], lp["w_h"]], axis=1)
        s = w.shape[0] // cfg["m"]
        sub = w.reshape(s, cfg["m"], -1)
        assert np.all((sub != 0).sum(axis=0) <= s - int(s * cfg["gamma"]))
        step, _ = reference.grid(cfg["weights"]["lstm_scale"] /
                                 np.sqrt(cfg["hidden_dim"]))
        assert np.all(np.abs(w / step - np.rint(w / step)) == 0)


def _digest(arrays) -> str:
    h = hashlib.sha256()
    for a in arrays:
        h.update(np.ascontiguousarray(np.asarray(a)).tobytes())
    return h.hexdigest()


def test_family_module_reproduces_the_pinned_digests():
    """Weights and reference logits of the DeltaLSTM family, pinned on the
    CPU before its code moved into ``models/delta_lstm.py``: the same seed
    gives the same numbers bit for bit."""
    import jax

    cfg = dict(CONFIGS["lstm_2l_1024h"], hidden_dim=64, m=8)
    params = LSTM.make_params(7, cfg)
    assert _digest(jax.tree_util.tree_leaves(params)) == (
        "478e46e37bc388130d61fde52ebc4a0a8baf63f47c02eae50497b9f5bfa30bb5")
    mix = json.loads((HERE / "traffic" / "offline.json").read_text())
    utts = speech.utterances(11, np.array([40, 60, 80]), mix["speech"])
    logits, _ = reference.reference_logits(LSTM, params, utts, cfg,
                                           precision="highest")
    assert _digest(logits) == (
        "8d4b197485366f5af7654fe56713d4f3d2b862c4caa7afb060f3deb123e61a0c")


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
    assert c.model.__name__ == "bench_model_" + c.config["model"]
    for fn in FAMILY:
        assert callable(getattr(c.model, fn)), fn


def _bench_with_family(tmp_path: Path, family: str) -> Path:
    """A benchmark in ``tmp_path`` that adds one configuration of the
    model family ``family`` and its offline cell to ``BENCHMARK.json``:
    new entries and new files only."""
    (tmp_path / "configs").mkdir()
    cfg = dict(CONFIGS["lstm_2l_1024h"], name="added_2l", model=family)
    (tmp_path / "configs" / "added_2l.json").write_text(json.dumps(cfg))
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append({"name": "added_2l", "source": "test",
                             "file": "configs/added_2l.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "added_2l.offline",
                               "config": "added_2l", "traffic": "offline",
                               "chips": 1, "why": "test"})
    for m in bench["per_layer"]:
        m["workloads"].append("added_2l.offline")
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(bench))
    return path


def test_a_family_added_as_files_only_runs_correct(tmp_path):
    models = tmp_path / "models"
    models.mkdir()
    (models / "thin_lstm.py").write_text(
        "import run\n"
        "_base = run.load_model('delta_lstm')\n"
        "layer_dims, make_params, forward, weights_held, engine = (\n"
        "    _base.layer_dims, _base.make_params, _base.forward,\n"
        "    _base.weights_held, _base.engine)\n")
    cell = run.load_cell("added_2l.offline",
                         _bench_with_family(tmp_path, "thin_lstm"), models)
    assert Path(cell.model.__file__) == models / "thin_lstm.py"
    res = _run_tiny(cell=cell)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0


def test_unknown_model_family_is_refused(tmp_path):
    bench = _bench_with_family(tmp_path, "no_such_family")
    with pytest.raises(FileNotFoundError, match="no_such_family.py"):
        run.load_cell("added_2l.offline", bench, tmp_path / "models")


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
    model = run.load_model(cfg["model"])
    params = model.make_params(5, cfg)
    ref, st = reference.reference_logits(
        model, params, utts, cfg, precision=cfg["matmul_precision"])
    ctrl, _ = reference.reference_logits(model, params, utts, cfg,
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

def _tiny_cell(cell: run.Cell) -> run.Cell:
    """A 2x1024 offline cell at hidden 64 and a pool of 8."""
    cell.config = dict(cell.config, hidden_dim=64, m=8)
    cell.mix = dict(cell.mix, capacity=8, chunk_frames=8, max_frames=128,
                    n_distinct=16, ramp_completions=8, sample=4)
    cell.mix["length"] = dict(cell.mix["length"], median=40, min=10,
                              max=80)
    return cell


def _run_tiny(trace: bool = False, cell: run.Cell = None):
    cell = cell or run.load_cell("lstm_2l_1024h.offline")
    return run.run_cell(_tiny_cell(cell), 2**31 + 99, 1.0, trace,
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
