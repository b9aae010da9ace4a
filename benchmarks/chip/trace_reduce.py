"""Reduce a profiler trace (``.xplane.pb``) to the benchmark's device numbers.

Read with ``jax.profiler.ProfileData``.  A device is a plane named
``/device:<KIND>:<n>``; its ``XLA Ops`` line holds one event per operation
that ran and its ``XLA Modules`` line one event per program execution.
The traced window is the host event ``bench.window`` that the harness
opens at the window's start and closes at its end (the whole trace where
it is missing).  From these:

* busy time: the union of the operation intervals inside the window,
  averaged over the devices; the idle share is 1 - busy / window;
* per-module device time: executions and seconds per XLA module;
* the operations that took most device time, by HLO name and opcode (a
  ``while`` op's time contains the ops of its body);
* idle gaps: each gap between busy intervals on the first device, put
  under the innermost ``bench.*`` host annotation that covers the gap's
  midpoint ("untraced" where none does), summed per label.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

WINDOW = "bench.window"
_DEVICE = re.compile(r"^/device:(?!CPU)[A-Za-z_]+:\d+$")


@dataclasses.dataclass
class Reduction:
    window_s: float
    busy_s: float
    n_devices: int
    modules: Dict[str, Tuple[int, float]]     # name -> (executions, s)
    top_ops: List[Tuple[str, float]]
    idle_gaps: List[Tuple[str, float]]

    @property
    def idle_share(self) -> Optional[float]:
        return 1.0 - self.busy_s / self.window_s if self.window_s > 0 \
            else None


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"{len(paths)} traces under {log_dir}")
    return paths[0]


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def _events(line):
    for e in line.events:
        yield e.name, int(e.start_ns), int(e.start_ns + e.duration_ns)


_OPCODE = re.compile(r"[\]})] ([a-z][\w\-]*)\(")


def short_name(op: str) -> str:
    """``%while.4 = (s32[], ...) while(...)`` -> ``%while.4 while``: the
    trace names each operation by its whole HLO text."""
    name, _, rest = op.partition(" = ")
    m = _OPCODE.search(rest)
    return f"{name} {m.group(1)}" if m else name


def reduce(data, top: int = 10) -> Reduction:
    """``data``: a ``jax.profiler.ProfileData`` or a path to a trace."""
    if isinstance(data, str):
        from jax.profiler import ProfileData
        data = ProfileData.from_file(data)
    host = []                                    # (name, start, end)
    devices = []
    for plane in data.planes:
        if _DEVICE.match(plane.name):
            devices.append(plane)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(ev for ev in _events(line)
                            if ev[0].startswith("bench."))
    windows = [(a, b) for n, a, b in host if n == WINDOW]
    labels = sorted(((b - a, a, b, n) for n, a, b in host if n != WINDOW))
    ops_by_dev, modules = [], defaultdict(lambda: [0, 0])
    for plane in devices:
        ops = []
        for line in plane.lines:
            if line.name == "XLA Modules":
                for n, a, b in _events(line):
                    if not windows or windows[0][0] <= a < windows[0][1]:
                        modules[n][0] += 1
                        modules[n][1] += b - a
            elif line.name == "XLA Ops":
                ops.extend(_events(line))
        ops_by_dev.append(ops)
    if windows:
        w0, w1 = windows[0]
    else:
        spans = [(a, b) for ops in ops_by_dev for _, a, b in ops]
        w0 = min((a for a, _ in spans), default=0)
        w1 = max((b for _, b in spans), default=0)
    busy, op_time = [], defaultdict(int)
    first_busy: List[Tuple[int, int]] = []
    for i, ops in enumerate(ops_by_dev):
        clipped = [(n, max(a, w0), min(b, w1)) for n, a, b in ops
                   if b > w0 and a < w1]
        for n, a, b in clipped:
            op_time[short_name(n)] += b - a
        merged = _union([(a, b) for _, a, b in clipped])
        busy.append(sum(b - a for a, b in merged))
        if i == 0:
            first_busy = merged
    n_dev = max(len(ops_by_dev), 1)
    gaps, prev = defaultdict(int), w0
    for a, b in first_busy + [(w1, w1)]:
        if a > prev:
            mid = (prev + a) // 2
            label = next((n for _, s, e, n in labels if s <= mid < e),
                         "untraced")
            gaps[label] += a - prev
        prev = max(prev, b)
    ns = 1e-9
    return Reduction(
        window_s=(w1 - w0) * ns,
        busy_s=sum(busy) / n_dev * ns,
        n_devices=len(ops_by_dev),
        modules={k: (v[0], v[1] * ns) for k, v in modules.items()},
        top_ops=sorted(((k, v / n_dev * ns) for k, v in op_time.items()),
                       key=lambda kv: -kv[1])[:top],
        idle_gaps=sorted(((k, v * ns) for k, v in gaps.items()),
                         key=lambda kv: -kv[1])[:top])


def module_ms(red: Optional[Reduction], part: str) -> Optional[float]:
    """Mean device milliseconds per execution of the modules whose name
    contains ``part`` (None where the trace has none)."""
    if red is None:
        return None
    hits = [v for k, v in red.modules.items() if part in k]
    n = sum(c for c, _ in hits)
    return sum(s for _, s in hits) / n * 1e3 if n else None
