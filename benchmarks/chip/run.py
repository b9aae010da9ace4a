"""Chip benchmark of the Spartus streaming server.

    python benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` (a configuration under a traffic mix)
in this process, on the first chip: weights and traffic from the seed,
the program's ``AsyncSpartusServer`` (``watchdog=False``) over the engine
of the configuration's model family, driven in-process by ``traffic.py``,
a measured window of ``--seconds``, then the served logits of a seeded
sample of finished requests against the plain reference (``compare.py``).

Everything a cell needs is found by name: ``configs/<config>.json``,
the model family that the configuration's ``"model"`` names,
``models/<family>.py`` (its weights, plain reference, weight count and
engine: see ``models/delta_lstm.py``), ``traffic/<mix>.json`` and, for
each per-layer metric, ``metrics/<name>.py`` (a ``read(run)`` function
over the run's collected sources; ``None`` when it finds nothing to
read).  Peaks are in ``peaks.json``, keyed by ``device_kind``.

With ``--trace 0`` the result's metrics are the cell's end-to-end metrics;
with ``--trace 1`` the profiler records the window and the metrics are the
cell's per-layer metrics.  The last line on standard output is one JSON
object (``correct``, ``attempted``, ``failed``, ``metrics``, ``device``,
with ``--trace 1`` ``breakdown``, and last ``checks``: each number compared
beside its limit, which also end standard error).  Earlier lines carry the
set-up split, compile counts, memory in use and per-layer temporal
sparsity.

Exit codes: 0 with a result; 2 without a TPU, with fewer chips than the
cell asks for, or on a ``device_kind`` missing from ``peaks.json``; 3 when
a program compiled (or was loaded from the compilation cache) inside the
measured window.
"""
from __future__ import annotations

import time

_T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from types import ModuleType  # noqa: E402
from typing import Any, Callable, Dict, List, Optional  # noqa: E402

import numpy as np  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
for _p in (str(HERE), str(ROOT / "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import compare  # noqa: E402
import reference  # noqa: E402
import speech  # noqa: E402
import trace_reduce  # noqa: E402
import traffic  # noqa: E402
import work  # noqa: E402


class NoChip(RuntimeError):
    """No TPU, too few chips, or a device kind without peaks."""


class CompileInWindow(RuntimeError):
    """A program was compiled or loaded inside the measured window."""


@dataclasses.dataclass
class Cell:
    name: str
    config: Dict[str, Any]
    model: ModuleType
    mix: Dict[str, Any]
    chips: int
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]


def load_cell(name: str, bench_path: Path = ROOT / "BENCHMARK.json",
              models: Path = HERE / "models") -> Cell:
    """The cell ``name`` of ``bench_path``; configuration files are found
    from the directory that holds it, model families under ``models``."""
    bench = json.loads(bench_path.read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in {bench_path}")
    w = cells[name]
    cfg_file = {c["name"]: c["file"] for c in bench["configs"]}[w["config"]]
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    moved = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in moved)]
    config = json.loads((bench_path.parent / cfg_file).read_text())
    return Cell(name=name, config=config,
                model=load_model(config["model"], models),
                mix=json.loads((HERE / "traffic" / f"{w['traffic']}.json")
                               .read_text()),
                chips=int(w["chips"]), end_to_end=e2e, per_layer=per_layer)


def _load_file(path: Path, module_name: str) -> ModuleType:
    spec = importlib.util.spec_from_file_location(module_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(metric: str) -> Callable[[Any], Optional[float]]:
    return _load_file(HERE / "metrics" / f"{metric}.py",
                      "bench_metric_" + metric.replace(".", "_")).read


def load_model(name: str, root: Path = HERE / "models") -> ModuleType:
    """The family module ``<root>/<name>.py``: ``layer_dims``,
    ``make_params``, ``forward``, ``weights_held`` and ``engine``."""
    path = root / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"model family {name!r}: no {path}")
    return _load_file(path, "bench_model_" + name.replace(".", "_"))


def load_peaks(kind: str) -> Dict[str, float]:
    table = json.loads((HERE / "peaks.json").read_text())["devices"]
    if kind not in table:
        raise NoChip(f"device_kind {kind!r} has no entry in peaks.json")
    return table[kind]


class CompileCounter:
    """Counts programs that were compiled or loaded from the persistent
    compilation cache (JAX reports both as a backend compile), and the
    cache hits among them."""

    def __init__(self):
        import jax

        self.compiles = 0
        self.cache_hits = 0
        self.names: List[str] = []

        def on_duration(event, _secs, **kw):
            if event == "/jax/core/compile/backend_compile_duration":
                self.compiles += 1
                self.names.append(str(kw.get("fun_name", "?")))

        def on_event(event, **_kw):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)


@dataclasses.dataclass
class RunView:
    """What a per-layer metric reader may read."""

    trace: Optional[trace_reduce.Reduction]
    timeseries: List[Dict[str, Any]]
    frames_per_chunk: int             # slots x frames of one chunk program
    ops_per_frame: float
    peaks: Optional[Dict[str, float]]

    def module_ms(self, part: str) -> Optional[float]:
        return trace_reduce.module_ms(self.trace, part)


class _Annotated:
    """Wraps a callable in a profiler host annotation."""

    def __init__(self, fn, label: str):
        import jax

        self._fn, self._ann = fn, jax.profiler.TraceAnnotation
        self._label = label

    def __call__(self, *a, **kw):
        with self._ann(self._label):
            return self._fn(*a, **kw)


def annotate(server) -> None:
    """Put the server's phases on the profiler's clock (where the names
    exist), so that idle gaps can be put under what the host was doing."""
    sites = [(server, "_pump", "bench.pump"),
             (server, "_deliver", "bench.deliver"),
             (server.pool, "tick", "bench.tick"),
             (server.pool, "_flush_uploads", "bench.upload"),
             (server.pool, "_resolve", "bench.fetch"),
             (server.pool.engine, "step_chunk", "bench.dispatch")]
    for obj, attr, label in sites:
        fn = getattr(obj, attr, None)
        if callable(fn) and not isinstance(fn, _Annotated):
            setattr(obj, attr, _Annotated(fn, label))


class _Hooks(traffic.Hooks):
    def __init__(self, counter: CompileCounter, trace_dir: Optional[str],
                 log: Callable[[str], None]):
        self.counter, self.trace_dir, self.log = counter, trace_dir, log
        self.c0 = self.c1 = 0
        self.wall = (0.0, 0.0)
        self._ann = None
        self._last = (time.perf_counter(), counter.compiles)

    def mark(self, phase: str) -> None:
        t, c = time.perf_counter(), self.counter.compiles
        self.log(f"{phase}: {t - self._last[0]:.3f} s, {c - self._last[1]} "
                 f"programs compiled or loaded")
        self._last = (t, c)

    def window_start(self) -> None:
        import jax

        self.mark("ramp")
        self.c0 = self.counter.compiles
        self.wall = (time.time(), 0.0)
        if self.trace_dir is not None:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
            self._ann = jax.profiler.TraceAnnotation(trace_reduce.WINDOW)
            self._ann.__enter__()

    def window_end(self) -> None:
        self.c1 = self.counter.compiles
        self.wall = (self.wall[0], time.time())
        if self._ann is not None:
            self._ann.__exit__(None, None, None)

    def stop(self) -> None:
        """Ends the trace once the drive is over, so that writing it never
        stalls the server while sessions still owe rows."""
        import jax

        if self._ann is not None:
            jax.profiler.stop_trace()
            self._ann = None


def _program_sparsity(server, model, cfg) -> Dict[str, List[float]]:
    """Per-layer temporal sparsity and active columns from the program's
    own device counters (over every frame the pool served)."""
    tel = server.pool.state.telemetry
    nnz = np.asarray(tel.nnz_sum, np.float64).sum(axis=1)
    steps = np.asarray(tel.steps, np.float64).sum(axis=1)
    cols = np.array([d + h for d, h in model.layer_dims(cfg)])
    act = nnz / np.maximum(steps, 1)
    return {"temporal_sparsity": (1 - act / cols).tolist(),
            "active_columns": act.tolist()}


def make_inputs(cell: Cell, seed: int):
    """Weights (on the device) and the traffic's utterances from the seed,
    and the generator the traffic goes on to draw its order from."""
    import jax

    words = reference.seed_words(seed, 3)
    params = jax.block_until_ready(
        cell.model.make_params(words[0], cell.config))
    rng = np.random.default_rng(words[2])
    lengths = traffic.stratified_lengths(cell.mix["length"],
                                         cell.mix["n_distinct"])
    lengths = lengths[rng.permutation(len(lengths))]
    return params, speech.utterances(words[1], lengths,
                                     cell.mix["speech"]), rng


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             require_chip: bool = True, use_cache: bool = True,
             log: Callable[[str], None] = print) -> Dict[str, Any]:
    import jax

    devs = jax.devices()
    dev = devs[0]
    log(f"platform {dev.platform}, device_kind {dev.device_kind}, "
        f"devices {len(devs)}")
    peaks = None
    if require_chip:
        if dev.platform != "tpu":
            raise NoChip(f"no TPU: JAX found {dev.platform}")
        if len(devs) < cell.chips:
            raise NoChip(f"{cell.name} needs {cell.chips} chips, JAX found "
                         f"{len(devs)}")
        peaks = load_peaks(dev.device_kind)
    if use_cache:
        from repro.compile_cache import use_compile_cache

        log(f"compile cache {use_compile_cache()}")
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    counter = CompileCounter()
    cfg, mix, model = cell.config, cell.mix, cell.model
    t0 = time.perf_counter()
    params, utts, rng = make_inputs(cell, seed)
    t_inputs = time.perf_counter()
    from repro.serving import AsyncSpartusServer, PoolObservability

    engine = model.engine(params, cfg)
    t_pack = time.perf_counter()
    lengths = np.array([u.shape[0] for u in utts])
    log(f"set-up before serving: weights and traffic data "
        f"{t_inputs - t0:.3f} s ({len(utts)} utterances, "
        f"{int(lengths.sum())} frames, {int(lengths.min())}-"
        f"{int(lengths.max())} frames each), program pack "
        f"{t_pack - t_inputs:.3f} s; compiles {counter.compiles} "
        f"({counter.cache_hits} from the cache)")
    tmp = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    obs = PoolObservability() if trace else None
    made = []

    def make_server(**shape):
        server = AsyncSpartusServer(
            engine, shape["capacity"], chunk_frames=shape["chunk_frames"],
            target_chunk_ms=shape["target_chunk_ms"],
            max_frames=shape["max_frames"], watchdog=False,
            observability=obs)
        if trace:
            annotate(server)
        made[:] = [server]          # a warm-up server's buffers go with it
        return server

    hooks = _Hooks(counter, tmp, log)
    c_setup = counter.compiles
    try:
        out = asyncio.run(traffic.drive(mix, make_server, utts, seconds, rng,
                                        hooks, log))
    finally:
        hooks.stop()
    setup_s = out.window[0] - _T_PROCESS
    window_compiles = hooks.c1 - hooks.c0
    log(f"set-up {setup_s:.3f} s (process start to window start); "
        f"compiles before serving {c_setup}, in set-up "
        f"{hooks.c0 - c_setup}, persistent-cache hits {counter.cache_hits}"
        f", in the window {window_compiles}")
    if window_compiles:
        raise CompileInWindow(
            f"{window_compiles} programs compiled or loaded in the window: "
            f"{counter.names[hooks.c0:hooks.c1]}")
    stats = dev.memory_stats() or {}
    peak_bytes = int(stats.get("peak_bytes_in_use", 0))
    log(f"device memory: peak {peak_bytes} bytes, in use "
        f"{int(stats.get('bytes_in_use', 0))} bytes")
    prog_sp = _program_sparsity(made[-1], model, cfg)
    log(f"program counters: temporal sparsity per layer "
        f"{prog_sp['temporal_sparsity']}, active columns per layer-step "
        f"{prog_sp['active_columns']}")
    log(f"window {out.window[1] - out.window[0]:.3f} s: "
        f"{json.dumps(out.notes)}; attempted {out.attempted}, failed "
        f"{out.failed}")

    red = None
    series: List[Dict[str, Any]] = []
    if trace:
        red = trace_reduce.reduce(trace_reduce.find_xplane(tmp))
        shutil.rmtree(tmp, ignore_errors=True)
        series = [s for s in obs.timeseries.snapshot()
                  if hooks.wall[0] <= s["t_wall"] < hooks.wall[1]]
        log(f"trace: window {red.window_s:.6f} s, device busy "
            f"{red.busy_s:.6f} s on {red.n_devices} devices, modules "
            f"{json.dumps(red.modules)}")

    # the program's state goes before the reference runs
    del made, engine, obs
    gc.collect()
    t_ref = time.perf_counter()
    ids = sorted(out.sample)
    ref, ref_sp = reference.reference_logits(
        model, params, [utts[i] for i in ids], cfg,
        precision=cfg["matmul_precision"])
    nums = compare.numbers([out.sample[i] for i in ids], ref,
                           ref_sp["h_absmax"])
    correct, checks = compare.judge(nums, cfg["limits"])
    log(f"reference over {len(ids)} requests "
        f"({int(nums['sampled_frames'])} frames) "
        f"{time.perf_counter() - t_ref:.3f} s: temporal sparsity per layer "
        f"{ref_sp['temporal_sparsity']}, active columns per layer-step "
        f"{ref_sp['active_columns']}, max |h| per layer "
        f"{ref_sp['h_absmax']}; {json.dumps(nums)}")

    shape = traffic.server_shape(mix)
    view = RunView(trace=red, timeseries=series,
                   frames_per_chunk=shape["capacity"] * shape["chunk_frames"],
                   ops_per_frame=work.ops_per_frame(model, cfg), peaks=peaks)
    metrics: Dict[str, Dict[str, Any]] = {}
    if trace:
        for m in cell.per_layer:
            value = load_reader(m["name"])(view)
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
    else:
        values = {**out.e2e, "setup_s": setup_s}
        for m in cell.end_to_end:
            value = values.get(m["name"])
            if value is None or not math.isfinite(value):
                raise RuntimeError(f"{m['name']} was not measured: {value}")
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": peak_bytes}
    result: Dict[str, Any] = {"correct": bool(correct),
                              "attempted": out.attempted,
                              "failed": out.failed, "metrics": metrics,
                              "device": device}
    if red is not None:
        device["busy_s"] = red.busy_s
        device["window_s"] = red.window_s
        result["breakdown"] = {"device_ops": [list(x) for x in red.top_ops],
                               "idle_gaps": [list(x) for x in red.idle_gaps]}
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)

    def log(msg: str) -> None:
        print(f"[bench] {msg}", flush=True)

    try:
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                          log=log)
    except NoChip as exc:
        print(f"[bench] {exc}", file=sys.stderr)
        return 2
    except CompileInWindow as exc:
        print(f"[bench] {exc}", file=sys.stderr)
        return 3
    for line in compare.lines(result["checks"]):
        print(line, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
