"""Readings for the limits of ``compare.py``: the lower-precision control
and the faults a served cell can have.

    python benchmarks/chip/control.py --workload <cell> --seeds 1,2,3
    python benchmarks/chip/control.py --workload <cell> --seeds 1,2,3 \
        --fault state_unchanged --seconds 5

Without ``--fault``, for each seed the cell's weights, utterances and
sample of requests are made exactly as a benchmark run makes them; then
the plain reference at the configuration's precision is compared with the
same reference put in the program's place in the nearest precision below
(bfloat16 weights, activations and state), and, for the record, with the
reference at "highest" matmul precision.  With ``--fault``, a whole run of
the cell at its own size (``run.run_cell``, a window of ``--seconds``) is
made with that fault planted in the program's chunk step.  One JSON line
per seed, with ``correct`` as ``compare.judge`` decides it under the
configuration's limits.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

import compare
import reference
import run
import traffic


def alter_answer(core):
    """A served token altered where it is produced: +1 on class 0."""
    def broken(self, state, x, active, cursor):
        new, logits = core(self, state, x, active, cursor)
        return new, logits.at[:, 0].add(1.0)
    return broken


def state_unchanged(core):
    """A step that returns its recurrent state unchanged."""
    def broken(self, state, x, active, cursor):
        _, logits = core(self, state, x, active, cursor)
        return state._replace(cursor=cursor), logits
    return broken


FAULTS = {"alter_answer": alter_answer, "state_unchanged": state_unchanged}


def plant(fault: str) -> None:
    """Replace the program's per-frame step by its broken copy."""
    from repro.serving.batched_engine import BatchedSpartusEngine

    BatchedSpartusEngine._step_core = FAULTS[fault](
        BatchedSpartusEngine._step_core)


def readings(cell: run.Cell, seed: int) -> dict:
    cfg, model = cell.config, cell.model
    params, utts, rng = run.make_inputs(cell, seed)
    ids = traffic.sample_ids(np.array([u.shape[0] for u in utts]),
                             int(cell.mix["sample"]), rng)
    sample = [utts[i] for i in ids]
    t = time.perf_counter()
    ref, st = reference.reference_logits(model, params, sample, cfg,
                                         precision=cfg["matmul_precision"])
    t_ref = time.perf_counter() - t
    out = {"seed": seed, "reference_s": t_ref, "reference_stats": st}
    for name, dtype, prec in (("control_bfloat16", "bfloat16", "default"),
                              ("float32_highest", "float32", "highest")):
        got, _ = reference.reference_logits(model, params, sample, cfg,
                                            dtype=dtype, precision=prec)
        nums = compare.numbers(got, ref, st["h_absmax"])
        out[name] = {**nums,
                     "correct": compare.judge(nums, cfg["limits"])[0]}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--fault", choices=sorted(FAULTS))
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    cell = run.load_cell(args.workload)
    if args.fault:
        plant(args.fault)
    for seed in (int(s) for s in args.seeds.split(",")):
        if args.fault:
            res = run.run_cell(cell, seed, args.seconds, False,
                               log=lambda _m: None)
            line = {"seed": seed, "fault": args.fault,
                    "correct": res["correct"], "checks": res["checks"]}
        else:
            line = readings(cell, seed)
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
