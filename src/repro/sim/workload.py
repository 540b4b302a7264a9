"""Chained sub-job workloads: the unit Mirage provisions (§4.1, §4.5).

A long-running service (training or inference) is split into a chain of
wall-clock-limited sub-jobs J1..Jk. The provisioner controls WHEN each
successor is submitted; the outcome per consecutive pair is either an
INTERRUPTION (successor starts after the predecessor ends) or an OVERLAP
(successor starts while the predecessor still runs).

The chain-link protocol's rules live here, once, for every engine that
runs it (the scalar and vector envs, the co-tenant env, the serving
lanes and ``MultiTenantSim``): a link's projected end (``link_end``),
the reactive fallback that turns a wait into a submit (``forced_submit``),
the successor's submit instant (``submit_instant``) and the predecessor's
end once its successor has gone in (``settle_pred_end``). The N=1 co-sim
and vector-equals-scalar identities rest on every engine evaluating the
same float expressions, so change them here or nowhere.
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import numpy as np

from .trace import Job

HOUR = 3600.0
INF = float("inf")


@dataclasses.dataclass
class SubJobChain:
    """A service of ``k`` sub-jobs, each with the same size and limit."""
    user_id: int
    n_nodes: int
    sub_limit: float = 48 * HOUR
    k: int = 2
    next_id: int = 900_000

    def make_sub(self, idx: int, submit_time: float) -> Job:
        return Job(job_id=self.next_id + idx, user_id=self.user_id,
                   submit_time=submit_time, runtime=self.sub_limit,
                   time_limit=self.sub_limit, n_nodes=self.n_nodes,
                   job_name=f"chain_{self.user_id}.sub_{idx}")


def pair_outcome(pred: Job, succ: Job) -> Tuple[str, float]:
    """('interrupt'|'overlap', seconds). Interrupt: succ starts after pred
    ends; overlap: succ starts (holds nodes) before pred ends."""
    assert pred.end_time >= 0 and succ.start_time >= 0
    gap = succ.start_time - pred.end_time
    if gap >= 0:
        return "interrupt", gap
    return "overlap", -gap


def link_end(start, runtime, limit):
    """A link's projected end: its start plus the smaller of its runtime
    and limit, or inf while it is not running (not yet started, or
    fault-killed and queued again — it has no known end). Floats, or
    numpy arrays over a lane axis."""
    if isinstance(start, np.ndarray):
        return np.where(start >= 0, start + np.minimum(runtime, limit),
                        np.inf)
    return start + min(runtime, limit) if start >= 0 else INF


def forced_submit(action, now, interval, pred_end):
    """Whether a decision is a wait (``action == 0``) whose next interval
    reaches the predecessor's end ``pred_end``: the reactive fallback
    submits the successor instead. A predecessor with no known end (inf)
    never forces. Scalars, or numpy arrays over a lane axis."""
    return (action == 0) & (now + interval >= pred_end)


def submit_instant(now: float, pred_end: float, forced: bool) -> float:
    """The successor's submit instant: ``now`` for a voluntary submit,
    the predecessor's end (never before ``now``) for a forced one; a
    predecessor with no known end (inf) lets it go in ``now``."""
    return max(now, pred_end) if forced and pred_end < INF else now


def settle_pred_end(pred: Job, t_sub: float) -> None:
    """Fill in the predecessor's end once its successor, submitted at
    ``t_sub``, has started: a running predecessor (the original or a
    fault-requeued restart) runs to its limit from its current start; one
    killed and still queued has been down since the submission. An end
    the simulator already recorded stands."""
    if pred.end_time < 0:
        pred.end_time = (link_end(pred.start_time, pred.runtime,
                                  pred.time_limit)
                         if pred.start_time >= 0 else t_sub)
