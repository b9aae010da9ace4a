import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture x input-shape)
cell on the production meshes, prove memory fit, and extract roofline
terms.  MUST be run as its own process (the XLA_FLAGS line above has to
execute before jax initialises — do not import this module from tests).

    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both
    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-1.7b --shape train_4k

Methodology note (measured, see EXPERIMENTS.md §Dry-run): XLA's
``cost_analysis`` counts a while-loop body ONCE, so the scanned layer
stack undercounts flops/bytes by ~n_layers.  Each cell therefore runs
  1. the PRODUCTION compile (scan-over-layers): proves sharding coherence
     + per-device memory fit (memory_analysis is per-device);
  2. two reduced-depth UNROLLED cost probes (1 and 2 layer-stacks):
     exact per-layer flops/bytes/collective-bytes by finite difference,
     extrapolated to full depth for the roofline terms.

Artifacts: experiments/dryrun/{arch}__{shape}__{mesh}.json
"""
import argparse
import dataclasses
import json
import time
import traceback
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs import REGISTRY, get_arch
from repro.distributed.sharding import batch_specs, cache_specs, param_specs
from repro.launch.mesh import AUTO, make_production_mesh
from repro.launch import roofline as RL
from repro.launch.steps import (
    abstract_cache, abstract_opt_state, abstract_params, make_prefill_step,
    make_serve_step, make_train_step, n_params_of,
)
from repro.models import api, scan
from repro.models.config import SHAPES, shape_applicable
from repro.training.optimizer import AdamWConfig

OUT_DIR = os.path.join(os.path.dirname(__file__), "../../../experiments/dryrun")

HBM_LIMIT_BYTES = 16 * 1024**3  # v5e HBM per chip


def _shardings(mesh, spec_tree):
    from jax.sharding import NamedSharding
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree)


def _probe_cfgs(cfg) -> Tuple[object, object, int, int, int]:
    """(cfg1, cfg2, L1, L2, L_full) reduced-depth same-width configs."""
    if cfg.family == "audio":
        c1 = dataclasses.replace(cfg, n_enc_layers=1, n_dec_layers=1, n_layers=2)
        c2 = dataclasses.replace(cfg, n_enc_layers=2, n_dec_layers=2, n_layers=4)
        return c1, c2, 2, 4, cfg.n_enc_layers + cfg.n_dec_layers
    if cfg.family == "hybrid":
        u = len(cfg.block_pattern)
        c1 = dataclasses.replace(cfg, n_layers=u)
        c2 = dataclasses.replace(cfg, n_layers=2 * u)
        return c1, c2, u, 2 * u, cfg.n_layers
    c1 = dataclasses.replace(cfg, n_layers=1)
    c2 = dataclasses.replace(cfg, n_layers=2)
    return c1, c2, 1, 2, cfg.n_layers


def _lower_cell(cfg, cell, mesh, *, donate: bool = True):
    """Build + lower the cell's step (abstract args, current scan mode)."""
    params_abs = abstract_params(cfg, jnp.bfloat16)
    p_sh = _shardings(mesh, param_specs(params_abs, mesh, cfg))
    if cell.kind == "train":
        opt_abs = abstract_opt_state(params_abs)
        o_sh = _shardings(mesh, param_specs(opt_abs, mesh, cfg))
        batch_abs = api.input_specs(cfg, cell)
        b_sh = _shardings(mesh, batch_specs(batch_abs, mesh))
        from repro.launch.steps import pick_microbatches
        step = make_train_step(cfg, AdamWConfig(), cell.seq_len,
                               microbatches=pick_microbatches(cfg, cell))
        return jax.jit(
            step, in_shardings=(p_sh, o_sh, b_sh),
            out_shardings=(p_sh, o_sh, None),
            donate_argnums=(0, 1) if donate else (),
        ).lower(params_abs, opt_abs, batch_abs), params_abs
    if cell.kind == "prefill":
        in_abs = api.input_specs(cfg, cell)["inputs"]
        in_sh = _shardings(mesh, batch_specs({"x": in_abs}, mesh))["x"]
        step = make_prefill_step(cfg, cell.seq_len)
        return jax.jit(
            step, in_shardings=(p_sh, in_sh), out_shardings=None
        ).lower(params_abs, in_abs), params_abs
    # decode
    from repro import perf
    from repro.launch.steps import quantize_params_abstract

    cache_abs = abstract_cache(cfg, cell, jnp.bfloat16)
    c_sh = _shardings(mesh, cache_specs(cache_abs, mesh))
    in_abs = api.input_specs(cfg, cell)["inputs"]
    in_sh = _shardings(mesh, batch_specs({"x": in_abs}, mesh))["x"]
    step = make_serve_step(cfg)
    arg0 = params_abs
    a0_sh = p_sh
    if perf.current().int8_weights:
        arg0 = quantize_params_abstract(params_abs)
        a0_sh = {"q": _shardings(mesh, param_specs(arg0["q"], mesh, cfg)),
                 "scales": _shardings(mesh, param_specs(arg0["scales"], mesh, cfg))}
    return jax.jit(
        step, in_shardings=(a0_sh, c_sh, in_sh),
        out_shardings=(None, c_sh), donate_argnums=(1,) if donate else (),
    ).lower(arg0, cache_abs, in_abs), params_abs


def _probe_costs(cfg, cell, mesh, n_dev: int):
    """Unrolled finite-difference probe -> extrapolated per-device
    (flops, hbm_bytes, coll_bytes, coll_breakdown)."""
    c1, c2, l1, l2, l_full = _probe_cfgs(cfg)
    vals = []
    for c in (c1, c2):
        with scan.unrolled():
            lowered, _ = _lower_cell(c, cell, mesh, donate=False)
        compiled = lowered.compile()
        ca = compiled.cost_analysis() or {}
        coll, breakdown = RL.collective_bytes(compiled.as_text(), n_dev)
        vals.append((float(ca.get("flops", 0.0)),
                     float(ca.get("bytes accessed", 0.0)), coll, breakdown))
    (f1, b1, c1b, bd1), (f2, b2, c2b, bd2) = vals
    per_layer = ((f2 - f1) / (l2 - l1), (b2 - b1) / (l2 - l1),
                 (c2b - c1b) / (l2 - l1))
    extra = l_full - l1
    flops = f1 + per_layer[0] * extra
    hbm = b1 + per_layer[1] * extra
    coll = c1b + per_layer[2] * extra
    kinds = set(bd1) | set(bd2)
    breakdown = {
        k: bd1.get(k, 0.0)
        + (bd2.get(k, 0.0) - bd1.get(k, 0.0)) / (l2 - l1) * extra
        for k in kinds
    }
    return flops, hbm, coll, breakdown


def run_cell(arch_name: str, shape_name: str, multi_pod: bool,
             out_dir: Optional[str] = None, verbose: bool = True,
             probe: bool = True) -> dict:
    cfg = get_arch(arch_name)
    cell = {c.name: c for c in SHAPES}[shape_name]
    mesh_name = "multi" if multi_pod else "single"
    record = {
        "arch": arch_name, "shape": shape_name, "mesh": mesh_name,
        "kind": cell.kind, "status": "?",
    }

    ok, reason = shape_applicable(cfg, cell)
    if not ok:
        record.update(status="skipped", reason=reason)
        _emit(record, out_dir, verbose)
        return record

    t0 = time.time()
    from repro import perf
    mo = perf.current().mesh_override
    if mo is not None:
        mesh = jax.make_mesh(mo[0], mo[1], axis_types=(AUTO,) * len(mo[1]))
    else:
        mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = mesh.size

    with jax.set_mesh(mesh):
        # 1. production compile: sharding + memory proof
        lowered, params_abs = _lower_cell(cfg, cell, mesh)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
        record["n_params"] = n_params_of(params_abs)

        ma = compiled.memory_analysis()
        mem = {
            "argument_bytes": int(ma.argument_size_in_bytes),
            "output_bytes": int(ma.output_size_in_bytes),
            "temp_bytes": int(ma.temp_size_in_bytes),
            "alias_bytes": int(ma.alias_size_in_bytes),
        }
        peak = (mem["argument_bytes"] + mem["temp_bytes"]
                + max(mem["output_bytes"] - mem["alias_bytes"], 0))
        mem["peak_bytes"] = int(peak)
        mem["fits_16gb"] = bool(peak < HBM_LIMIT_BYTES)

        # raw (scan-once) costs, kept for reference
        ca = compiled.cost_analysis() or {}
        raw_coll, _ = RL.collective_bytes(compiled.as_text(), n_dev)
        record["raw_scanned_costs"] = {
            "flops": float(ca.get("flops", 0.0)),
            "hbm_bytes": float(ca.get("bytes accessed", 0.0)),
            "coll_bytes": raw_coll,
        }

        # 2. cost probe (unrolled finite difference)
        if probe:
            flops, hbm, coll, breakdown = _probe_costs(cfg, cell, mesh, n_dev)
            n_active = RL.active_params(cfg, params_abs)
            mf = RL.model_flops(cfg, cell, n_active)
            roof = RL.Roofline(
                flops=flops, hbm_bytes=hbm, coll_bytes=coll,
                coll_breakdown=breakdown,
                compute_s=flops / RL.PEAK_FLOPS,
                memory_s=hbm / RL.HBM_BW,
                collective_s=coll / RL.ICI_BW,
                bottleneck="", model_flops_total=mf,
                useful_ratio=mf / max(flops * n_dev, 1.0), n_devices=n_dev,
            )
            terms = {"compute": roof.compute_s, "memory": roof.memory_s,
                     "collective": roof.collective_s}
            roof.bottleneck = max(terms, key=terms.get)
            record["n_active_params"] = n_active
            record["roofline"] = roof.to_dict()

    record.update(status="ok", lower_s=round(t_lower, 1),
                  compile_s=round(t_compile, 1), memory=mem)
    _emit(record, out_dir, verbose)
    return record


def _emit(record: dict, out_dir: Optional[str], verbose: bool):
    out_dir = out_dir or os.path.abspath(OUT_DIR)
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(
        out_dir, f"{record['arch']}__{record['shape']}__{record['mesh']}.json"
    )
    with open(path, "w") as f:
        json.dump(record, f, indent=1)
    if not verbose:
        return
    if record["status"] == "ok":
        m = record["memory"]
        msg = (f"[dryrun] {record['arch']:24s} {record['shape']:12s} "
               f"{record['mesh']:6s} OK  peak={m['peak_bytes']/2**30:7.2f}GiB"
               f"{'' if m['fits_16gb'] else ' OVER'}")
        if "roofline" in record:
            r = record["roofline"]
            msg += (f" compute={r['compute_s']*1e3:9.2f}ms"
                    f" mem={r['memory_s']*1e3:9.2f}ms"
                    f" coll={r['collective_s']*1e3:9.2f}ms"
                    f" -> {r['bottleneck']}  useful={r['useful_ratio']:.2f}")
        print(msg, flush=True)
    else:
        print(f"[dryrun] {record['arch']:24s} {record['shape']:12s} "
              f"{record['mesh']:6s} {record['status'].upper()}: "
              f"{record.get('reason','')}", flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"], default="both")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--no-probe", action="store_true",
                    help="skip the unrolled cost probe (multi-pod pass only "
                         "needs the compile+memory proof)")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    archs = [args.arch] if args.arch else sorted(REGISTRY)
    shapes = [args.shape] if args.shape else [c.name for c in SHAPES]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    failures = []
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                try:
                    rec = run_cell(arch, shape, mp, args.out,
                                   probe=not args.no_probe and not mp)
                    if rec["status"] not in ("ok", "skipped"):
                        failures.append((arch, shape, mp))
                except Exception as e:
                    traceback.print_exc()
                    failures.append((arch, shape, mp))
                    _emit({"arch": arch, "shape": shape,
                           "mesh": "multi" if mp else "single",
                           "kind": "?", "status": "error",
                           "reason": repr(e)[:500]}, args.out, True)
    if failures:
        print(f"FAILURES: {failures}", flush=True)
        raise SystemExit(1)
    print("dry-run complete: all cells OK", flush=True)


if __name__ == "__main__":
    main()
