"""The comparison that decides ``correct``.

During the window a seeded reservoir keeps
a uniform sample of the decisions the timed path served: the observation
as the policy received it, the program's two outputs for it (Q-values or
P-head logits) and the action it answered. After the window the float32
reference (``reference.py``) computes its own outputs for those
observations, from weights it makes itself, and two numbers are compared,
each against a limit of the configuration:

* ``out_err``: the largest gap between a program output and the
  reference's;
* ``gap``: the widest gap by which the served action's reference output
  lies below the reference's best.

Both are in the outputs' own units (a Q-value, or a logit): a fixed scale,
so that neither grows where the sample's outputs happen to be small, as
a scale taken from the sample would. The limit is on ``answer_err``, the
larger of the two: how far the answer served departs from the reference,
in its values or in the action taken. ``gap`` alone does not separate the program from the
float8 control (the agent's margins are wider than either's error, so
neither flips an action), and ``out_err`` alone does not see an action
altered after the forward; together they catch both.

Besides these, a run is correct only with no failed decision (fallback,
degraded, shed, non-finite output); with no decision to compare, the
numbers read None and the run is not correct.
"""
from __future__ import annotations

from typing import Dict, Optional

import numpy as np

import reference


class Reservoir:
    """A uniform sample of ``size`` rows from a stream (Algorithm R), drawn
    from ``rng``."""

    def __init__(self, size: int, rng: np.random.Generator):
        self.size = size
        self.rng = rng
        self.seen = 0
        self.obs = []
        self.out = []
        self.act = []

    def offer(self, obs: np.ndarray, out, act: np.ndarray,
              rows: Optional[np.ndarray] = None) -> None:
        """Offer rows ``rows`` (default all) of one served batch; ``out`` is
        the program's output array, read only when a row is kept."""
        rows = np.arange(len(act)) if rows is None else rows
        n = len(rows)
        if not n:
            return
        idx = np.arange(self.seen, self.seen + n)
        j = np.where(idx < self.size, idx, self.rng.integers(0, idx + 1))
        keep = np.flatnonzero(j < self.size)
        self.seen += n
        if not keep.size:
            return
        out = np.asarray(out, np.float64)
        for i in keep:
            r, slot = int(rows[i]), int(j[i])
            item = (np.array(obs[r], np.float32), out[r].copy(), int(act[r]))
            if slot < len(self.obs):
                self.obs[slot], self.out[slot], self.act[slot] = item
            else:
                self.obs.append(item[0])
                self.out.append(item[1])
                self.act.append(item[2])


def forward_numbers(agent: Dict, sample: Reservoir, head: str,
                    precision: str = "float32",
                    weights: Optional[Dict] = None) -> Dict[str, float]:
    """``out_err`` and ``gap`` of the sampled decisions against the float32
    reference. With ``precision="float8"`` the control stands in for the
    program: its own outputs and its own first choice are compared."""
    if not sample.obs:
        return {"out_err": None, "gap": None, "answer_err": None}
    w = weights if weights is not None else reference.init_weights(agent)
    obs = np.stack(sample.obs)
    ref = reference.outputs(w, obs, head)
    if precision == "float32":
        out, act = np.stack(sample.out), np.asarray(sample.act)
    else:
        out = reference.outputs(w, obs, head, precision)
        act = out.argmax(-1)
    served = ref[np.arange(len(act)), act]
    out_err = float(np.abs(out - ref).max())
    gap = float((ref.max(-1) - served).max())
    return {"out_err": out_err, "gap": gap, "answer_err": max(out_err, gap)}


def verdict(numbers: Dict[str, float], limits: Dict[str, float]) -> Dict:
    """{name: {"value", "limit"}} for every number that has a limit."""
    return {k: {"value": numbers[k], "limit": v} for k, v in limits.items()}


def passed(checks: Dict) -> bool:
    return all(c["value"] is not None and np.isfinite(c["value"])
               and c["value"] <= c["limit"] for c in checks.values())
