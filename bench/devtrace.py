"""Reduction of a profiler trace to the numbers the per-layer metrics read.

The benchmark brackets its measured window with the host span
``bench.window`` and each call into a layer with a host span
``bench.<layer>`` (``jax.profiler.TraceAnnotation``). From the device
planes it takes, inside the window:

* busy time: the union of the intervals in which an XLA operation ran;
* device time and executions per program: the ``XLA Modules`` events,
  by module name (``jit_<function>``, the trailing id dropped);
* top device operations by summed duration (nested operations, such as a
  loop and its body, each count);
* idle gaps: each stretch in which no operation ran, attributed to the
  innermost benchmark span open at its midpoint.

On a TPU v5e the device plane's clock runs about a millisecond apart from
the host's (a program's device start precedes its host launch by ~1.2 ms
in a probe trace), so the attribution of gaps to host spans is good to
about a millisecond; busy time and program times use the device clock
alone.
"""
from __future__ import annotations

import dataclasses
import glob
import heapq
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
OUTSIDE = "(no span)"
MODULE_ID = re.compile(r"\(\d+\)$")

Interval = Tuple[float, float]


@dataclasses.dataclass
class Reduction:
    window_s: float
    n_devices: int
    busy_s: float                          # mean over devices
    program_s: Dict[str, float]            # module -> device s (mean)
    program_calls: Dict[str, float]        # module -> executions (mean)
    top_ops: List[Tuple[str, float]]       # (op, s), mean over devices
    idle_gaps: List[Tuple[str, float]]     # (span name, s), mean


def load(trace_dir: str):
    """The newest ``*.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return ProfileData.from_file(files[-1])


def merge(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out



def _clip(a: float, b: float, w: Interval) -> Optional[Interval]:
    lo, hi = max(a, w[0]), min(b, w[1])
    return (lo, hi) if hi > lo else None


def _events(line):
    for e in line.events:
        yield e.name, float(e.start_ns), float(e.end_ns)


def reduce(pd) -> Reduction:
    host_spans: List[Tuple[float, float, str]] = []
    window: Optional[Interval] = None
    devices = []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            devices.append(plane)
            continue
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for name, a, b in _events(line):
                if name == WINDOW_SPAN:
                    window = (a, b)
                elif name.startswith(SPAN_PREFIX):
                    host_spans.append((a, b, name[len(SPAN_PREFIX):]))
    if window is None:
        raise ValueError(f"trace has no {WINDOW_SPAN!r} host span")
    if not devices:
        raise ValueError("trace has no device plane")
    nd = len(devices)
    busy = 0.0
    prog: Dict[str, float] = defaultdict(float)
    calls: Dict[str, float] = defaultdict(float)
    ops: Dict[str, float] = defaultdict(float)
    gaps: Dict[str, float] = defaultdict(float)
    for plane in devices:
        lines = {line.name: line for line in plane.lines}
        line = lines.get(OPS_LINE) or lines.get(MODULES_LINE)
        ivs = []
        if line is not None:
            for name, a, b in _events(line):
                c = _clip(a, b, window)
                if c:
                    ivs.append(c)
                    ops[name.split(" = ")[0]] += (c[1] - c[0]) / nd
        if MODULES_LINE in lines:
            for name, a, b in _events(lines[MODULES_LINE]):
                c = _clip(a, b, window)
                if c:
                    key = MODULE_ID.sub("", name)
                    prog[key] += (c[1] - c[0]) / nd
                    calls[key] += 1.0 / nd
        u = merge(ivs)
        busy += sum(b - a for a, b in u) / nd
        edges = [window[0]] + [x for iv in u for x in iv] + [window[1]]
        idle = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        for name, s in _attribute(idle, host_spans).items():
            gaps[name] += s / nd
    w_s = (window[1] - window[0]) * 1e-9
    top = lambda d: sorted(((k, v * 1e-9) for k, v in d.items()),
                           key=lambda kv: -kv[1])[:10]
    return Reduction(window_s=w_s, n_devices=nd, busy_s=busy * 1e-9,
                     program_s={k: v * 1e-9 for k, v in prog.items()},
                     program_calls=dict(calls),
                     top_ops=top(ops), idle_gaps=top(gaps))


def _attribute(gaps: Sequence[Interval],
               spans: Sequence[Tuple[float, float, str]]) -> Dict[str, float]:
    """Each gap's length to the innermost span (latest start) open at its
    midpoint, or to ``OUTSIDE``."""
    out: Dict[str, float] = defaultdict(float)
    order = sorted(spans)
    active: List[Tuple[float, float, str]] = []    # heap by end
    k = 0
    for a, b in sorted(gaps):
        m = 0.5 * (a + b)
        while k < len(order) and order[k][0] <= m:
            s = order[k]
            heapq.heappush(active, (s[1], s[0], s[2]))
            k += 1
        while active and active[0][0] <= m:
            heapq.heappop(active)
        if active:
            out[max(active, key=lambda s: s[1])[2]] += b - a
        else:
            out[OUTSIDE] += b - a
    return out
