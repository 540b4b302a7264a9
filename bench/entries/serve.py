"""Entry: the multi-tenant ``ProvisionService`` in co-simulation mode (its
tenants share one simulated cluster), closed loop.

Traffic parameters: ``tenants``, ``max_batch``, ``links`` per tenant (enough that no chain
finishes), ``start_days`` (the episode start instants, in days after the
trace's warm-up), ``horizon_days`` (simulated days an episode serves) and
``sample`` (decisions kept for the comparison).

The traffic is a cycle of episodes, one for each start instant: a fresh
service takes on the tenants at that instant and serves them until its
simulated clock has advanced ``horizon_days``; then the next episode
starts. Every episode does the same work whatever the run's seed, which
sets only the order of the cycle, and no episode leaves the trace, so the
cluster runs at the configuration's load through the whole window. Set-up
warms every forward batch size and runs the whole cycle once.

The harness times each decision from its own wrappers: a decision is due
when its round begins (the service asks ``live_tenants`` at the top of
every round) and done when the lane's ``apply`` returns. The window ends
through the public ``guard``: once it is over, ``should_stop`` answers
True at the next poll and the in-flight batch drains.
"""
from __future__ import annotations

import numpy as np

import check
import system
from repro.train.fault import PreemptionGuard


class EpisodeGuard(PreemptionGuard):
    """Reports "stop" once the run's window is over, or once the episode's
    simulated clock has passed its horizon."""

    def __init__(self, run, end: float):
        super().__init__(install_signals=False)
        self.run = run
        self.end = end
        self.sim = None

    def should_stop(self) -> bool:
        return self.run.past_end() or self.sim.now >= self.end


class Session:
    def __init__(self, run):
        from repro.core.agent import LearnerPolicy
        t, a = run.traffic, run.agent
        self.head = system.head(a)
        self.trace, self.cfg, self.cache = system.build_world(run.config)
        self.learner, self.fn_attr = system.build_learner(a, 0)
        self.policy = LearnerPolicy(a["method"], self.learner)
        lo = self.trace[0].submit_time + self.cfg.warmup
        self.starts = [lo + d * system.DAY for d in t["start_days"]]
        self.horizon = t["horizon_days"] * system.DAY
        self.trace_end = self.trace[-1].submit_time
        if max(self.starts) + 2 * self.horizon > self.trace_end:
            raise ValueError("the episodes' starts and horizon do not fit "
                             "inside the trace")
        self.order = run.rng(1).permutation(len(self.starts))
        self.sample = check.Reservoir(t["sample"], run.rng(2))
        self.due = [0.0]
        self.episodes = []
        self.last_error = None
        run.say(f"{t['tenants']} tenants (co-sim), {len(self.trace)} jobs, "
                f"{self.cfg.n_nodes} nodes; {a['method']} {a['kind']} d={a['d_model']}; episodes at "
                f"days {t['start_days']} after the warm-up, "
                f"{t['horizon_days']} simulated days each; order "
                f"{self.order.tolist()}")
        self._wrap_learner(run)
        # every forward batch size a round can offer, then the whole cycle
        for b in range(1, min(t["tenants"], t["max_batch"]) + 1):
            self.learner.act_batch(
                np.zeros((b, a["history"], 40), np.float32), explore=False)
        for i in range(len(self.starts)):
            self._episode(run, i)

    # ------------------------------------------------------------ wrappers
    def _wrap_learner(self, run):
        learner, spans = self.learner, run.spans
        out = system.capture_outputs(learner, self.fn_attr)
        act_batch = learner.act_batch

        def act(mats, explore=True):
            with spans("forward"):
                acts = act_batch(mats, explore=explore)
            if run.open:
                n = len(acts)
                run.counts["attempted"] += n
                run.counts["calls"] += 1
                run.batch_sizes[n] += 1
                bad = ~np.isfinite(np.asarray(out[0])).all(-1)
                run.counts["nonfinite"] += int(bad.sum())
                self.sample.offer(mats, out[0], acts)
            return acts

        learner.act_batch = act

    def _wrap_service(self, run, svc):
        due, spans = self.due, run.spans
        live_tenants = svc.live_tenants

        def live():
            due[0] = run.clock()
            return live_tenants()

        svc.live_tenants = live
        for lane in svc.lanes:
            self._wrap_lane(run, lane, due)
        advance = svc.cosim.advance_round

        def advance_round():
            with spans("advance"):
                advance()

        svc.cosim.advance_round = advance_round

    @staticmethod
    def _wrap_lane(run, lane, due):
        apply = lane.apply

        def wrapped(action, fell_back=False):
            with run.spans("apply"):
                apply(action, fell_back=fell_back)
            if run.open:
                run.latencies.append(run.clock() - due[0])
                run.counts["decisions"] += 1
                run.counts["submits"] += int(action == 1)

        lane.apply = wrapped

    # ------------------------------------------------------------ episodes
    def _episode(self, run, i: int) -> None:
        """One episode: a fresh service takes on the tenants at start
        instant ``i`` and serves them to the horizon (or the window's
        end). Its seed is ``i``, so an episode is the same in every run."""
        from repro.serve import ProvisionService, ServiceConfig
        t = run.traffic
        t0 = self.starts[i]
        guard = EpisodeGuard(run, t0 + self.horizon)
        svc = ProvisionService(
            self.trace, self.cfg, self.policy,
            svc=ServiceConfig(tenants=t["tenants"], links=t["links"],
                              max_batch=t["max_batch"], co_sim=True),
            seed=i, cache=self.cache, guard=guard)
        svc.start(t_starts=[t0])
        guard.sim = svc.lanes[0].env.sim
        self._wrap_service(run, svc)
        counting, n0 = run.open, run.counts["decisions"]
        svc.run()
        if counting:
            c = run.counts
            c["episodes"] += 1
            c["rounds"] += svc.n_rounds
            c["degraded"] += svc.n_degraded
            c["shed"] += svc.n_shed
            c["fallbacks"] += svc.policy.n_fallbacks
            c["links"] += sum(len(lane.outcomes) for lane in svc.lanes)
            self.episodes.append((i, guard.sim.now - t0,
                                  c["decisions"] - n0))
        self.last_error = svc.policy.last_error or self.last_error

    # -------------------------------------------------------------- window
    def window(self, run):
        n = 0
        while not run.past_end():
            self._episode(run, int(self.order[n % len(self.order)]))
            n += 1
        c = run.counts
        c["failed"] = (c["fallbacks"] + c["degraded"] + c["shed"]
                       + c["nonfinite"])
        c["attempted"] += c["degraded"] + c["shed"]

    def report(self, run):
        c = run.counts
        days = [s / system.DAY for _, s, _ in self.episodes]
        furthest = max((self.starts[i] + s for i, s, _ in self.episodes),
                       default=self.starts[0])
        run.say(f"{c['decisions']} decisions in {c['rounds']} rounds of "
                f"{c['episodes']} episodes, {c['calls']} forward calls; "
                f"submit share {c['submits'] / max(c['decisions'], 1):.4f}; "
                f"{c['links']} links done; simulated days per episode "
                f"{[round(d, 2) for d in days]}, the furthest "
                f"{(self.trace_end - furthest) / system.DAY:.1f} days before "
                f"the trace's last arrival; failed {c['failed']} (fallbacks "
                f"{c['fallbacks']}, degraded {c['degraded']}, shed "
                f"{c['shed']}, non-finite {c['nonfinite']})")
        if self.last_error:
            run.say(f"last fallback error: {self.last_error}")
        run.sample = self.sample
        run.head = self.head
        run.episodes = self.episodes
        run.order = self.order
        run.starts, run.trace_end = self.starts, self.trace_end

    def close(self):
        self.learner = self.policy = self.cache = None


def setup(run) -> Session:
    return Session(run)


def checks(run):
    numbers = check.forward_numbers(run.agent, run.sample, run.head)
    run.say(f"compared {len(run.sample.obs)} sampled decisions: out_err "
            f"{numbers['out_err']}, gap {numbers['gap']}")
    out = check.verdict(numbers, run.config["limits"])
    out["failed"] = {"value": run.counts["failed"], "limit": 0}
    return out
