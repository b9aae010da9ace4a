"""The comparison that decides ``correct``.

The served logits of a sample of finished requests are compared, frame by
frame, with the plain reference (``reference.py``) computed at the matmul
precision that the configuration states (``matmul_precision``: "default"
in both configurations, the precision of the program's own dots).  The
numbers:

``logit_gap_max``    widest gap, over every sampled frame, by which the
                     reference's logit of the class the served logits put
                     first lies below the reference's best (0 where they
                     agree): what a greedy decoder would lose.
``logit_err_max``    widest |served - reference| over every sampled logit.
``argmax_mismatch``  share of sampled frames whose served argmax differs
                     from the reference's.
``dead_layers``      layers whose hidden state stayed identically zero in
                     the reference over the sample: there the comparison
                     would check nothing.
``shape_errors``     sampled requests whose served logits do not have one
                     finite row per frame.

A configuration's file gives a limit to each number it holds the run to;
the others are printed for the record.  A run is correct when every
limited number is at or below its limit and the sample is not empty.
"""
from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import numpy as np


def numbers(served: Sequence[np.ndarray], ref: Sequence[np.ndarray],
            h_absmax: Sequence[float]) -> Dict[str, float]:
    bad = sum(s.shape != r.shape or not np.all(np.isfinite(s))
              for s, r in zip(served, ref))
    pairs = [(s, r) for s, r in zip(served, ref)
             if s.shape == r.shape and np.all(np.isfinite(s))]
    gap = err = 0.0
    mismatch = frames = 0
    for s, r in pairs:
        pick = np.argmax(s, axis=-1)
        best = np.max(r, axis=-1)
        got = np.take_along_axis(r, pick[:, None], axis=-1)[:, 0]
        gap = max(gap, float(np.max(best - got)))
        err = max(err, float(np.max(np.abs(s - r))))
        mismatch += int(np.sum(pick != np.argmax(r, axis=-1)))
        frames += s.shape[0]
    return {"logit_gap_max": gap, "logit_err_max": err,
            "argmax_mismatch": mismatch / frames if frames else 1.0,
            "dead_layers": float(sum(h == 0.0 for h in h_absmax)),
            "shape_errors": float(bad),
            "sampled_requests": float(len(served)),
            "sampled_frames": float(frames)}


ALWAYS = {"dead_layers": 0.0, "shape_errors": 0.0}


def judge(nums: Dict[str, float], limits: Dict[str, float]
          ) -> Tuple[bool, Dict[str, Dict[str, float]]]:
    """(correct, {name: {"value", "limit"}}) for every limited number."""
    held = {**ALWAYS, **limits}
    checks = {k: {"value": nums[k], "limit": float(v)}
              for k, v in held.items()}
    ok = nums["sampled_requests"] > 0 and all(
        c["value"] <= c["limit"] for c in checks.values())
    return ok, checks


def lines(checks: Dict[str, Dict[str, float]]) -> List[str]:
    return [f"{k} {c['value']!r} limit {c['limit']!r}"
            for k, c in checks.items()]
