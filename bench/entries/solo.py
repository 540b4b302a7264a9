"""Entry: the multi-tenant ``ProvisionService`` in its default solo mode
(each tenant forks its own copy of the cluster at its own start instant),
closed loop.

Traffic parameters: ``tenants`` (the service's tenant slots),
``max_batch``, ``links`` per tenant (enough that no chain finishes),
``max_queue`` and ``slo_s`` (the service's admission: a queue bound and a
per-decision limit, None for none), ``start_days`` (the cycle's base
instants, in days after the trace's warm-up), ``stagger_h`` (slot k's
tenants start k x ``stagger_h`` hours after a base), ``horizon_days``
(simulated days a tenant serves from its first decision) and ``sample``
(decisions kept for the comparison).

One service serves through the window. Each slot holds one tenant at a
time: the slot's j-th tenant starts at base ``j mod len(start_days)`` plus
the slot's stagger, forking the cluster there and queueing its
predecessor until it starts, and serves while its next decision ends
inside its horizon; then the slot's next tenant starts, at the top of a
round whose decisions wait for it. Slot k's first tenant serves (k + 1) /
``tenants`` of a horizon, so the slots' starts come evenly through the
rounds, as in a service whose tenants come and go, and not all at once.
The sequence is fixed by the traffic, so every run does the same work
whatever its seed, which draws only the sample; none leaves the trace.
Set-up warms the forward batch sizes the rounds offer, replays the
cluster past the cycle's last fork point (every later start forks a
checkpoint) and starts the first tenant of every slot.

Decisions are timed as in the co-sim entry (``serve.py``, whose learner
and lane wrappers and guard this entry reuses): due when the service asks
``live_tenants`` at the top of a round, done when the lane's ``apply``
returns. The round's starts come after the first of these, so a start
counts in the latency of every decision it holds up. The turnover is the
entry's: ``ProvisionService`` begins its tenants once, in ``start``, and
the entry begins a slot's next tenant through the lane's own ``begin``
when the service asks for the round's tenants.
"""
from __future__ import annotations

import importlib.util
import os

import numpy as np

import check
import system

_spec = importlib.util.spec_from_file_location(
    "bench_entry_serve", os.path.join(os.path.dirname(__file__), "serve.py"))
serve = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(serve)

HOUR = 3600.0


class Session(serve.Session):
    def __init__(self, run):
        from repro.core.agent import LearnerPolicy
        from repro.serve import ProvisionService, ServiceConfig
        t, a = run.traffic, run.agent
        self.head = system.head(a)
        self.trace, self.cfg, self.cache = system.build_world(run.config)
        self.learner, self.fn_attr = system.build_learner(a, 0)
        self.policy = LearnerPolicy(a["method"], self.learner)
        T = t["tenants"]
        lo = self.trace[0].submit_time + self.cfg.warmup
        self.bases = [lo + d * system.DAY for d in t["start_days"]]
        self.stagger = t["stagger_h"] * HOUR
        self.horizon = t["horizon_days"] * system.DAY
        self.trace_end = self.trace[-1].submit_time
        last = max(self.bases) + (T - 1) * self.stagger
        if last + 2 * self.horizon > self.trace_end:
            raise ValueError("the tenants' starts and horizon do not fit "
                             "inside the trace")
        if self.horizon / T <= self.cfg.interval:
            raise ValueError("a slot's first tenant would serve no decision")
        self.sample = check.Reservoir(t["sample"], run.rng(2))
        self.due = [0.0]
        self.turns = []         # (slot, j, start, first, last, decisions)
        self.last_error = None
        run.say(f"{T} tenant slots (solo), {len(self.trace)} jobs, "
                f"{self.cfg.n_nodes} nodes; {a['method']} {a['kind']} "
                f"d={a['d_model']}; starts at days {t['start_days']} after "
                f"the warm-up in turn, slots {t['stagger_h']} h apart, "
                f"{t['horizon_days']} simulated days each")
        self._wrap_learner(run)
        # every round serves every slot (a slot's next tenant starts in the
        # call that retires its tenant, and none sheds), in chunks of
        # max_batch and the rest
        for b in {min(T, t["max_batch"]), T % t["max_batch"]} - {0}:
            self.learner.act_batch(
                np.zeros((b, a["history"], 40), np.float32), explore=False)
        guard = serve.EpisodeGuard(run, float("inf"))
        self.svc = svc = ProvisionService(
            self.trace, self.cfg, self.policy,
            svc=ServiceConfig(tenants=T, links=t["links"],
                              max_batch=t["max_batch"],
                              max_queue=t["max_queue"], slo_s=t["slo_s"]),
            cache=self.cache, guard=guard)
        self.cache.fork_at(svc.lanes[0].env.warmup_point(last))
        self.n_begun = [1] * T
        svc.start(t_starts=[self._t_start(k, 0) for k in range(T)])
        guard.sim = svc.lanes[0].env.sim
        self.firsts = [lane.env.sim.now for lane in svc.lanes]
        self.ends = [f + self.horizon * (k + 1) / T
                     for k, f in enumerate(self.firsts)]
        self._wrap_service(run, svc)

    def _t_start(self, k: int, j: int) -> float:
        """Start instant of slot ``k``'s ``j``-th tenant."""
        return self.bases[j % len(self.bases)] + k * self.stagger

    def _wrap_service(self, run, svc):
        """Time each decision from its round's start, then start a slot's
        next tenant once its tenant's next decision would end past the
        horizon (not after the window's end): a start holds up the round's
        decisions, and counts in their latency."""
        due, lanes, ends = self.due, svc.lanes, self.ends
        live_tenants, step = svc.live_tenants, self.cfg.interval

        def live():
            due[0] = run.clock()
            if not run.past_end():
                for k, lane in enumerate(lanes):
                    if lane.env.sim.now + step >= ends[k]:
                        self._next_tenant(run, k)
            return [k for k in live_tenants()
                    if lanes[k].env.sim.now + step < ends[k]]

        svc.live_tenants = live
        for lane in lanes:
            self._wrap_lane(run, lane, due)

    def _next_tenant(self, run, k: int) -> None:
        lane, j = self.svc.lanes[k], self.n_begun[k]
        self.turns.append((k, j - 1, self._t_start(k, j - 1), self.firsts[k],
                           lane.env.sim.now, lane.n_decisions))
        lane.begin(t_start=self._t_start(k, j))
        self.n_begun[k] += 1
        self.firsts[k] = lane.env.sim.now
        self.ends[k] = self.firsts[k] + self.horizon
        if run.open:
            run.counts["starts"] += 1

    def window(self, run):
        svc, c = self.svc, run.counts
        before = (svc.n_rounds, svc.n_degraded, svc.n_shed,
                  svc.policy.n_fallbacks,
                  sum(len(lane.outcomes) for lane in svc.lanes))
        svc.run()
        c["rounds"] = svc.n_rounds - before[0]
        c["degraded"] = svc.n_degraded - before[1]
        c["shed"] = svc.n_shed - before[2]
        c["fallbacks"] = svc.policy.n_fallbacks - before[3]
        c["links"] = sum(len(lane.outcomes) for lane in svc.lanes) - before[4]
        c["failed"] = (c["fallbacks"] + c["degraded"] + c["shed"]
                       + c["nonfinite"])
        c["attempted"] += c["degraded"] + c["shed"]
        self.last_error = svc.policy.last_error

    def report(self, run):
        c = run.counts
        full = [n for k, j, _, _, _, n in self.turns if j > 0]
        furthest = max(lane.env.sim.now for lane in self.svc.lanes)
        run.say(f"{c['decisions']} decisions in {c['rounds']} rounds, "
                f"{c['starts']} tenant starts, {c['calls']} forward calls; "
                f"submit share {c['submits'] / max(c['decisions'], 1):.4f}; "
                f"{c['links']} links done; decisions of the "
                f"{len(full)} whole turns ended: {min(full, default=0)}-"
                f"{max(full, default=0)}; the furthest "
                f"{(self.trace_end - furthest) / system.DAY:.1f} days before "
                f"the trace's last arrival; failed {c['failed']} (fallbacks "
                f"{c['fallbacks']}, degraded {c['degraded']}, shed "
                f"{c['shed']}, non-finite {c['nonfinite']})")
        if self.last_error:
            run.say(f"last fallback error: {self.last_error}")
        run.sample = self.sample
        run.head = self.head
        run.turns = self.turns
        run.bases, run.stagger = self.bases, self.stagger
        run.trace_start, run.trace_end = (self.trace[0].submit_time,
                                          self.trace_end)
        run.warmup = self.cfg.warmup
        run.horizon, run.interval = self.horizon, self.cfg.interval

    def close(self):
        self.svc = None
        super().close()


def setup(run) -> Session:
    return Session(run)


checks = serve.checks
