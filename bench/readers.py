"""Reductions shared by the per-layer metric readers in ``metrics/``.

Each returns None where the run has nothing to read, and the harness then
leaves the metric out of the result line.
"""
from __future__ import annotations

import re
from typing import Optional

import flops
import system

# act_batch's jitted q_values/logits forward, by module name in the trace
FORWARD = re.compile(r"^jit__lambda$")


def forward_least_s(run) -> float:
    """Roofline time of every forward call of the window, by batch size."""
    a, h = run.agent, system.head(run.agent)
    return sum(n * flops.least_seconds(flops.forward_flops(a, h, b),
                                       flops.forward_bytes(a, b), run.peak)
               for b, n in run.batch_sizes.items())


def program_s(run, pattern) -> Optional[float]:
    """Device seconds of the traced window's executions of the programs
    whose module name matches ``pattern``; None where none ran."""
    r = run.reduction
    if r is None:
        return None
    s = sum(v for k, v in r.program_s.items() if pattern.match(k))
    return s if s > 0 else None


def roofline_pct(least_s: float, device_s: Optional[float]):
    if device_s is None or least_s <= 0:
        return None
    return 100.0 * least_s / device_s


def forward_roofline(run):
    """Roofline share of the forward program: the least time of the
    window's calls over its device time in the trace."""
    return roofline_pct(forward_least_s(run), program_s(run, FORWARD))


def forward_mfu(run):
    """Model operations of the decisions answered (live rows), over the
    window's seconds times the chip's peak."""
    a = run.agent
    per = flops.forward_flops(a, system.head(a), 1)
    if not run.counts["attempted"]:
        return None
    return (100.0 * run.counts["attempted"] * per
            / (run.window_s * run.peak["flops_per_s"]))


def idle_pct(run):
    r = run.reduction
    if r is None or r.window_s <= 0:
        return None
    return 100.0 * (1.0 - r.busy_s / r.window_s)
