"""Seeded fault injection (ISSUE 7): plan determinism, hand-computed
requeue/interruption accounting, FaultPlan.none() bit-identity with the
fault-free engine (fast paths enabled AND disabled), fork/CoW fault
state, cancel semantics, faulted scenarios through evaluate_batch, and
vector/scalar lane equivalence under faults.
"""
import copy
import dataclasses

import numpy as np
import pytest

import repro.sim.simulator as sim_mod
from repro.core import (AvgWaitPolicy, DQNConfig, DQNLearner, EnvConfig,
                        FoundationConfig, LearnerPolicy, PGConfig, PGLearner,
                        ProvisionEnv, ReactivePolicy, ReplayCheckpointCache,
                        TreePolicy, VectorProvisionEnv, evaluate_batch)
from repro.core.agent import ALL_METHODS
from repro.core.trees import GradientBoosting, RandomForest
from repro.sim import (FAULT_PROFILES, FaultPlan, SlurmSimulator,
                       get_scenario, replay, synthesize_trace)
from repro.sim.faults import FAIL, REPAIR
from repro.sim.trace import V100, Job

from _fork_path import ForkPathCache

HOUR = 3600.0
DAY = 24 * HOUR
HISTORY = 12


def _results(sim):
    return [(j.job_id, j.start_time, j.end_time) for j in sim.finished]


# ------------------------------------------------------------- the plan
def test_fault_plan_deterministic_and_immutable():
    a = FaultPlan.generate(30 * DAY, 88, seed=5)
    b = FaultPlan.generate(30 * DAY, 88, seed=5)
    np.testing.assert_array_equal(a.times, b.times)
    np.testing.assert_array_equal(a.kinds, b.kinds)
    np.testing.assert_array_equal(a.nodes, b.nodes)
    c = FaultPlan.generate(30 * DAY, 88, seed=6)
    assert len(c) != len(a) or not np.array_equal(a.times, c.times)
    # arrays are frozen: a shared plan cannot be mutated by any consumer
    with pytest.raises(ValueError):
        a.times[0] = 0.0
    # every failure is paired with a repair; times sorted; net down = 0
    assert (np.sort(a.times) == a.times).all()
    assert (a.nodes[a.kinds == FAIL].sum()
            == a.nodes[a.kinds == REPAIR].sum())
    # control-plane errors are a pure function of (seed, op index)
    assert [a.ctrl_failures(k) for k in range(20)] == [0] * 20  # rate 0
    e = FaultPlan.none(ctrl_seed=3, ctrl_error_rate=0.9)
    seq = [e.ctrl_failures(k) for k in range(40)]
    assert seq == [e.ctrl_failures(k) for k in range(40)]
    assert max(seq) > 0


def test_fault_spec_scales_blast_radius():
    spec = FAULT_PROFILES["faulty"]
    plan = spec.make_plan(30 * DAY, 88, seed=0)
    assert plan.nodes.max() <= max(1, round(0.05 * 88))
    assert plan.ctrl_error_rate == spec.ctrl_error_rate


# --------------------------------------------- hand-computed accounting
def test_requeue_accounting_hand_computed():
    """4-node cluster, two 2-node 10h jobs started at t=0. A 2-node
    failure at t=1h must kill exactly the newer job (newest-start-first,
    tie broken toward the larger index), charge 2 nodes x 1h of lost
    work, and requeue it; the repair at t=2h restarts it to finish at
    t=12h. The survivor is untouched."""
    plan = FaultPlan(np.array([1 * HOUR, 2 * HOUR]),
                     np.array([FAIL, REPAIR]), np.array([2, 2]))
    j1 = Job(job_id=1, user_id=1, submit_time=0.0, runtime=10 * HOUR,
             time_limit=12 * HOUR, n_nodes=2)
    j2 = Job(job_id=2, user_id=1, submit_time=0.0, runtime=10 * HOUR,
             time_limit=12 * HOUR, n_nodes=2)
    sim = replay([j1, j2], n_nodes=4, mode="fast", faults=plan)
    got = {j.job_id: (j.start_time, j.end_time) for j in sim.finished}
    assert got[1] == (0.0, 10 * HOUR)            # survivor runs through
    assert got[2] == (2 * HOUR, 12 * HOUR)       # requeued, restarted
    assert sim.n_node_failures == 1
    assert sim.n_requeues == 1
    assert sim.lost_node_s == 2 * 1 * HOUR       # 2 nodes x 1h discarded
    # the requeued job kept its original submit time (age priority)
    assert j2.submit_time == 0.0


def test_capacity_shrinks_and_recovers():
    """A failure with no kill still shrinks schedulable capacity until
    the repair: a 4-node job cannot start while 1 of 4 nodes is down."""
    plan = FaultPlan(np.array([1 * HOUR, 5 * HOUR]),
                     np.array([FAIL, REPAIR]), np.array([1, 1]))
    j = Job(job_id=1, user_id=1, submit_time=2 * HOUR, runtime=HOUR,
            time_limit=2 * HOUR, n_nodes=4)
    sim = SlurmSimulator(4, mode="fast", faults=plan)
    sim.load([j])
    sim.run_until_started(j)
    assert j.start_time == 5 * HOUR              # waits for the repair
    assert sim.cluster.down_nodes == 0


# ----------------------------------------------------- none() identity
def test_fault_plan_none_bit_identical():
    """FaultPlan.none() must be bit-identical to faults=None over a heavy
    month — same finished set, same exact start/end times."""
    jobs = synthesize_trace(V100, months=1, seed=3, load_scale=1.05)
    base = replay([copy.copy(j) for j in jobs], V100.n_nodes, mode="fast")
    none = replay([copy.copy(j) for j in jobs], V100.n_nodes, mode="fast",
                  faults=FaultPlan.none())
    assert _results(base) == _results(none)
    assert none.n_node_failures == 0 and none.n_requeues == 0
    assert none.lost_node_s == 0.0


def test_fast_paths_decision_identical_under_faults():
    """The no-op scheduling cache and arrival fast-forward must not
    change any decision when faults are active: a faulted replay matches
    a reference engine with both optimizations disabled (the same
    harness that pins the fault-free engine)."""
    jobs = synthesize_trace(V100, months=1, seed=3, load_scale=1.0)
    plan = FaultPlan.generate(jobs[-1].submit_time + 3 * DAY, V100.n_nodes,
                              seed=7, mtbf_s=2 * DAY, max_nodes=4)
    opt = replay([copy.copy(j) for j in jobs], V100.n_nodes, mode="fast",
                 faults=plan)

    rec = sim_mod.SlurmSimulator._record_noop
    ru = sim_mod.SlurmSimulator.run_until
    sim_mod.SlurmSimulator._record_noop = (
        lambda self, q, free, st, sp: None)

    def run_until_ref(self, t, _stop_idx=None):
        t = max(t, self.now)
        exact = self.mode == "exact"
        while True:
            tn = self._next_event_time()
            if exact and self._next_sched <= t and self._next_sched < tn:
                self.now = self._next_sched
                self._schedule()
                self._next_sched += self.sched_interval
                if _stop_idx is not None and self._start[_stop_idx] >= 0:
                    return
                continue
            if tn > t:
                break
            if _stop_idx is not None and tn == float("inf") and not exact:
                return
            self.now = tn
            self._absorb_events(tn)
            if not exact:
                self._schedule()
            if _stop_idx is not None and self._start[_stop_idx] >= 0:
                return
        self.now = t

    sim_mod.SlurmSimulator.run_until = run_until_ref
    try:
        ref = replay([copy.copy(j) for j in jobs], V100.n_nodes,
                     mode="fast", faults=plan)
    finally:
        sim_mod.SlurmSimulator.run_until = ru
        sim_mod.SlurmSimulator._record_noop = rec
    assert opt.n_node_failures == ref.n_node_failures > 0
    assert opt.n_requeues == ref.n_requeues
    assert opt.lost_node_s == ref.lost_node_s
    assert _results(opt) == _results(ref)


# ------------------------------------------------------------ fork/CoW
def test_fork_carries_fault_state():
    jobs = synthesize_trace(V100, months=1, seed=3, load_scale=1.0)
    plan = FaultPlan.generate(jobs[-1].submit_time + 3 * DAY, V100.n_nodes,
                              seed=7, mtbf_s=2 * DAY, max_nodes=4)
    base = SlurmSimulator(V100.n_nodes, mode="fast", faults=plan)
    base.load([copy.copy(j) for j in jobs])
    mid = jobs[0].submit_time + 10 * DAY
    base.run_until(mid)
    f = base.fork()
    assert f._faults is base._faults          # plan shared (immutable)
    assert f._fault_ptr == base._fault_ptr
    assert (f.n_node_failures, f.n_requeues, f.lost_node_s) == (
        base.n_node_failures, base.n_requeues, base.lost_node_s)
    end = jobs[-1].submit_time + 2 * DAY
    f.run_until(end)
    base.run_until(end)
    assert _results(f) == _results(base)
    assert f.n_requeues == base.n_requeues


def test_cancel_semantics():
    """cancel() removes a queued job, kills a running one WITHOUT requeue
    or lost-work charging, and drops a not-yet-arrived one."""
    mk = lambda jid, sub: Job(job_id=jid, user_id=1, submit_time=sub,
                              runtime=4 * HOUR, time_limit=5 * HOUR,
                              n_nodes=1)
    sim = SlurmSimulator(1, mode="fast")
    sim.load([mk(1, 0.0), mk(2, 0.0), mk(3, 10 * HOUR)])
    sim.run_until(HOUR)
    # j1 running, j2 queued (1 node), j3 pending arrival
    assert sim.cancel(2) is True               # queued -> gone
    assert sim.cancel(3) is True               # pending arrival -> gone
    assert sim.cancel(1) is True               # running -> killed, no requeue
    assert sim.cancel(99) is False
    sim.run_until(30 * HOUR)
    assert sim.n_requeues == 0 and sim.lost_node_s == 0.0
    assert [j.job_id for j in sim.finished] == []


# ------------------------------------------- scenarios + evaluate_batch
@pytest.fixture(scope="module")
def faulty_world():
    sc = get_scenario("V100", "heavy", "single", fault="faulty")
    jobs = sc.make_trace(months=1, seed=5)
    plan = sc.make_fault_plan(jobs, seed=5)
    cfg = sc.env_config(history=HISTORY, interval=1800.0, faults=plan)
    cache = ReplayCheckpointCache(jobs, cfg.n_nodes, faults=plan)
    return jobs, cfg, plan, cache


def _all_policies():
    """All eight methods, training-free (the test_policy_eval recipe)."""
    rng = np.random.default_rng(0)
    X = rng.normal(size=(48, 4 * 40)).astype(np.float32)
    y = np.abs(rng.normal(size=48)) * HOUR
    out = {"reactive": ReactivePolicy(), "avg": AvgWaitPolicy()}
    out["avg"].waits = [2 * HOUR, 5 * HOUR, HOUR]
    for m, model in (("random_forest", RandomForest(n_trees=4, seed=0)),
                     ("xgboost", GradientBoosting(n_rounds=6, seed=0))):
        out[m] = TreePolicy(model.fit(X, y), m)
    for m in ("transformer+dqn", "transformer+pg", "moe+dqn", "moe+pg"):
        kind = "moe" if m.startswith("moe") else "transformer"
        fc = dataclasses.replace(FoundationConfig(kind=kind).reduced(),
                                 kind=kind, history=HISTORY)
        learner = (DQNLearner(fc, DQNConfig(), seed=0) if m.endswith("dqn")
                   else PGLearner(fc, PGConfig(), seed=0))
        out[m] = LearnerPolicy(m, learner)
    return out


def test_faulted_cell_all_methods_through_evaluate_batch(faulty_world):
    """Every §6 method runs on a faulted cell via evaluate_batch, with
    per-lane fault/requeue counters surfaced in the result."""
    jobs, cfg, plan, cache = faulty_world
    assert len(plan) > 0
    policies = _all_policies()
    any_faults = 0
    for method in ALL_METHODS:
        venv = VectorProvisionEnv(jobs, cfg, 2, seed=100, cache=cache)
        res = evaluate_batch(venv, policies[method], episodes=2, seed=7)
        assert res.method == method
        assert res.summary()["n_episodes"] == 2
        assert len(res.fault_counts) == 2 == len(res.requeue_counts)
        assert all(c >= 0 for c in res.fault_counts)
        any_faults += sum(res.fault_counts)
    # the counters are live wiring, not dead zeros: with every method
    # seeing the same faulted windows, at least one episode overlaps a
    # failure (the plan is dense enough by construction at this seed)
    assert any_faults > 0


def test_vector_matches_scalar_under_faults(faulty_world):
    """Lane i of a faulted vector env stays bit-identical to a scalar
    env seeded seed+i — including fault-mutated predecessor state."""
    jobs, cfg, plan, cache = faulty_world
    B = 3
    venv = VectorProvisionEnv(jobs, cfg, B, seed=50, cache=cache)
    lo, hi = venv._t_start_range
    t0s = np.random.default_rng(11).uniform(lo, hi, B)
    obs = venv.reset(t_starts=t0s)
    vec = [{k: np.array(v) for k, v in obs.items()}]
    vr = np.zeros(B)
    vinfos = [{}] * B
    while not venv.dones.all():
        was = venv.dones.copy()
        obs, r, dones, inf = venv.step([0] * B)
        vec.append({k: np.array(v) for k, v in obs.items()})
        for i in range(B):
            if not was[i] and dones[i]:
                vr[i] = r[i]
                vinfos[i] = inf[i]
    # the reference is the scalar fork path (no timeline)
    fork_cache = ForkPathCache(jobs, cfg.n_nodes, faults=plan)
    for i in range(B):
        env = ProvisionEnv(jobs, cfg, seed=50 + i, cache=fork_cache)
        sobs = env.reset(t_start=float(t0s[i]))
        step = 0
        np.testing.assert_array_equal(vec[step]["matrix"][i],
                                      sobs["matrix"])
        done = False
        while not done:
            sobs, sr, done, sinfo = env.step(0)
            step += 1
            if step < len(vec) and not done:
                np.testing.assert_array_equal(vec[step]["matrix"][i],
                                              sobs["matrix"])
                assert vec[step]["pred_remaining"][i] == \
                    sobs["pred_remaining"]
        assert sr == vr[i]
        assert sinfo == vinfos[i]
        assert "n_faults" in sinfo and "n_requeues" in sinfo
