"""Self-healing control plane (ISSUE 7): seeded-jitter retries, the
crash-safe decision journal, graceful policy degradation, and the chain
driver's kill-and-resume contract (final schedule identical to an
uninterrupted run).
"""
import os

import numpy as np
import pytest

from repro.core import (ChainDriver, CircuitBreaker, ControlPlane,
                        DecisionJournal, EnvConfig, FallbackPolicy,
                        JournalCorruptionError, ReactivePolicy,
                        ReplayCheckpointCache, RetryExhaustedError,
                        RetryPolicy, TransientControlError)
from repro.sim import FaultPlan, get_fault_spec, synthesize_trace
from repro.sim.trace import V100
from repro.train.fault import PreemptionGuard

HOUR = 3600.0
DAY = 24 * HOUR
SEED = 2


@pytest.fixture(scope="module")
def faulty_chain_world():
    jobs = synthesize_trace(V100, months=1, seed=5, load_scale=1.0)
    plan = get_fault_spec("faulty").make_plan(
        jobs[-1].submit_time + 3 * DAY, V100.n_nodes, seed=3)
    cfg = EnvConfig(n_nodes=V100.n_nodes, history=12, interval=1800.0,
                    faults=plan)
    cache = ReplayCheckpointCache(jobs, cfg.n_nodes, faults=plan)
    return jobs, cfg, cache


def _driver(jobs, cfg, cache, **kw):
    kw.setdefault("policy", FallbackPolicy(ReactivePolicy()))
    kw.setdefault("retry", RetryPolicy(seed=1, sleep=lambda s: None))
    return ChainDriver(jobs, cfg, links=3, seed=SEED, cache=cache, **kw)


# --------------------------------------------------------------- retry
def test_retry_policy_recovers_and_gives_up():
    slept = []
    rp = RetryPolicy(max_attempts=4, base_delay_s=0.1, max_delay_s=1.0,
                     seed=0, sleep=slept.append, clock=lambda: 0.0)
    state = {"left": 2}

    def flaky():
        if state["left"] > 0:
            state["left"] -= 1
            raise TransientControlError("flap")
        return "ok"

    assert rp.call(flaky) == ("ok", 2)
    assert len(slept) == 2
    # seeded jitter: delay_k in [0.5, 1.5] * base * 2^k, deterministic
    assert 0.05 <= slept[0] <= 0.15 and 0.1 <= slept[1] <= 0.3
    assert slept == [s for s in slept]          # reproducible values
    calls = []

    def always():
        calls.append(1)
        raise TransientControlError("down")

    with pytest.raises(TransientControlError):
        rp.call(always)
    assert len(calls) == 4                      # max_attempts bound

    # the wall-clock deadline bounds retrying even under max_attempts
    t = {"now": 0.0}

    def clock():
        return t["now"]

    def sleep(d):
        t["now"] += d

    rp2 = RetryPolicy(max_attempts=100, base_delay_s=10.0,
                      max_delay_s=10.0, deadline_s=25.0,
                      seed=0, sleep=sleep, clock=clock)
    calls.clear()
    with pytest.raises(TransientControlError):
        rp2.call(always)
    assert len(calls) < 10


def test_retry_give_up_names_op_attempts_elapsed():
    """Final give-up raises RetryExhaustedError naming the op, attempt
    count and elapsed wall time (chained from the transient error), on
    both the max-attempts and the deadline paths."""
    t = {"now": 0.0}
    rp = RetryPolicy(max_attempts=3, base_delay_s=0.1, seed=0,
                     sleep=lambda d: t.__setitem__("now", t["now"] + d),
                     clock=lambda: t["now"])

    def always():
        raise TransientControlError("down")

    with pytest.raises(RetryExhaustedError) as ei:
        rp.call(always, op_name="submit")
    msg = str(ei.value)
    assert "submit" in msg and "3 attempts" in msg and "elapsed" in msg
    assert isinstance(ei.value.__cause__, TransientControlError)
    # RetryExhaustedError IS-A TransientControlError (compat contract)
    assert isinstance(ei.value, TransientControlError)

    t["now"] = 0.0
    rp2 = RetryPolicy(max_attempts=100, base_delay_s=10.0, max_delay_s=10.0,
                      deadline_s=5.0, seed=0,
                      sleep=lambda d: t.__setitem__("now", t["now"] + d),
                      clock=lambda: t["now"])
    with pytest.raises(RetryExhaustedError) as ei2:
        rp2.call(always, op_name="cancel")
    assert "cancel" in str(ei2.value) and "deadline" in str(ei2.value)


def test_retry_deadline_exact_edge():
    """A delay landing *exactly* on the deadline is still taken (the
    deadline is inclusive); only strict overrun gives up."""
    # reproduce the first jittered delay from the seeded stream
    d0 = min(0.1 * 2.0 ** 0, 1.0) * (
        0.5 + float(np.random.default_rng(7).random()))
    t = {"now": 0.0}
    slept = []

    def sleep(d):
        slept.append(d)
        t["now"] += d

    rp = RetryPolicy(max_attempts=10, base_delay_s=0.1, max_delay_s=1.0,
                     deadline_s=d0, seed=7, sleep=sleep,
                     clock=lambda: t["now"])
    state = {"left": 1}

    def once():
        if state["left"] > 0:
            state["left"] -= 1
            raise TransientControlError("flap")
        return "ok"

    # first retry's delay == deadline exactly -> allowed, op succeeds
    assert rp.call(once) == ("ok", 1)
    assert slept == [d0]

    # but the very next delay after that would overrun -> give up
    state["left"] = 5
    t["now"] = 0.0
    rp2 = RetryPolicy(max_attempts=10, base_delay_s=0.1, max_delay_s=1.0,
                      deadline_s=d0, seed=7, sleep=sleep,
                      clock=lambda: t["now"])
    with pytest.raises(RetryExhaustedError):
        rp2.call(once)


def test_control_plane_replays_same_errors():
    """Ctrl errors are a pure function of (ctrl_seed, op index): two
    control planes over the same plan see identical error sequences."""
    plan = FaultPlan.none(ctrl_seed=9, ctrl_error_rate=0.5)

    class FakeSim:
        def __init__(self):
            self.submitted = []

        def submit(self, job):
            self.submitted.append(job)

    logs = []
    for _ in range(2):
        cp = ControlPlane(plan, retry=RetryPolicy(seed=0,
                                                  sleep=lambda s: None))
        sim = FakeSim()
        for k in range(20):
            cp.submit(sim, k)
        assert sim.submitted == list(range(20))  # every op lands once
        logs.append((cp.n_errors, cp.n_retries))
    assert logs[0] == logs[1]
    assert logs[0][0] > 0


# ------------------------------------------------------------- journal
def test_decision_journal_torn_tail(tmp_path):
    """A crash mid-append leaves a partial trailing frame — replay drops
    exactly that and keeps the durable prefix."""
    p = str(tmp_path / "journal.msgpack")
    j = DecisionJournal(p)
    recs = [{"i": k, "a": k % 2, "fb": False} for k in range(5)]
    for r in recs:
        j.append(r)
    assert j.replay() == recs
    size = os.path.getsize(p)
    with open(p, "ab") as f:
        f.write(b"\x85\xa1")         # partial frame header (< 8 bytes)
    assert j.replay() == recs        # torn tail dropped, prefix intact
    # truncation mid-body (frame header durable, body short) is torn too
    with open(p, "rb+") as f:
        f.truncate(size - 3)
    assert j.replay() == recs[:4]
    assert DecisionJournal(str(tmp_path / "missing")).replay() == []


def test_decision_journal_raises_on_mid_file_corruption(tmp_path):
    """Corrupt bytes *before* the end of the journal (a bit flip inside a
    complete record) raise instead of silently truncating — a silently
    shortened journal would resume divergently."""
    p = str(tmp_path / "journal.msgpack")
    j = DecisionJournal(p)
    sizes = []
    for k in range(6):
        j.append({"i": k, "a": k % 2, "fb": False})
        sizes.append(os.path.getsize(p))
    blob = open(p, "rb").read()
    # flip one byte inside the SECOND record's CRC-protected body
    # (past its 8-byte frame header)
    off = sizes[0] + 8
    corrupted = blob[:off] + bytes([blob[off] ^ 0xFF]) + blob[off + 1:]
    open(p, "wb").write(corrupted)
    with pytest.raises(JournalCorruptionError):
        j.replay()


# ------------------------------------------------------------- breaker
def test_circuit_breaker_trips_cools_down_and_probes():
    """closed -> open at `threshold` failures in the sliding window;
    half-open after the cooldown; one probe closes or re-opens it."""
    t = {"now": 0.0}
    br = CircuitBreaker(window=8, threshold=3, cooldown_s=5.0,
                        clock=lambda: t["now"])
    assert br.state == CircuitBreaker.CLOSED and br.allow()
    # failures interleaved with successes: trips on the 3rd failure
    # inside the 8-outcome window
    for ok in (False, True, False):
        br.record(ok)
        assert br.state == CircuitBreaker.CLOSED
    br.record(False)
    assert br.state == CircuitBreaker.OPEN
    assert br.n_trips == 1
    assert not br.allow()                        # still cooling down
    t["now"] = 4.99
    assert not br.allow()
    t["now"] = 5.0                               # cooldown elapsed
    assert br.allow()                            # admits the probe...
    assert br.state == CircuitBreaker.HALF_OPEN
    br.record(False)                             # ...probe fails: re-open
    assert br.state == CircuitBreaker.OPEN and br.n_trips == 2
    assert not br.allow()                        # fresh cooldown from now
    t["now"] = 10.0
    assert br.allow()
    br.record(True)                              # probe succeeds: close
    assert br.state == CircuitBreaker.CLOSED
    # recovery cleared the window: old failures don't linger
    br.record(False)
    br.record(False)
    assert br.state == CircuitBreaker.CLOSED


def test_circuit_breaker_window_slides_and_forced_trip():
    t = {"now": 0.0}
    br = CircuitBreaker(window=4, threshold=3, cooldown_s=1.0,
                        clock=lambda: t["now"])
    # 2 failures then enough successes to push them out of the window
    for ok in (False, False, True, True, True, False, False):
        br.record(ok)
    assert br.state == CircuitBreaker.CLOSED    # never 3 in any window of 4
    br.trip()                                   # chaos/bench force-open
    assert br.state == CircuitBreaker.OPEN and not br.allow()
    assert br.n_trips == 1


# ------------------------------------------------------------ fallback
def test_fallback_policy_on_exception_and_deadline():
    class Exploding(ReactivePolicy):
        def act_batch(self, obs):
            raise RuntimeError("learner OOM")

    obs = {"pred_remaining": np.array([0.0, 4 * HOUR])}
    pol = FallbackPolicy(Exploding())
    acts = pol.act_batch(obs)
    np.testing.assert_array_equal(acts, [1, 0])   # reactive rule
    assert pol.n_fallbacks == 1 and pol.n_decisions == 1
    assert pol.method == "reactive+fallback"

    t = {"now": 0.0}

    class Slow(ReactivePolicy):
        def act_batch(self, inner_obs):
            t["now"] += 5.0                        # overruns the deadline
            return np.zeros(2, np.int64)

    pol2 = FallbackPolicy(Slow(), deadline_s=1.0, clock=lambda: t["now"])
    np.testing.assert_array_equal(pol2.act_batch(obs), [1, 0])
    assert pol2.n_fallbacks == 1
    # within the deadline the inner decision passes through
    pol3 = FallbackPolicy(ReactivePolicy(), deadline_s=60.0)
    np.testing.assert_array_equal(pol3.act_batch(obs), [1, 0])
    assert pol3.n_fallbacks == 0 and pol3.n_decisions == 1


def test_fallback_policy_keeps_last_error():
    class Exploding(ReactivePolicy):
        def act_batch(self, obs):
            raise RuntimeError("RESOURCE_EXHAUSTED: out of HBM")

    obs = {"pred_remaining": np.array([0.0])}
    pol = FallbackPolicy(Exploding())
    assert pol.last_error is None
    pol.act_batch(obs)
    assert pol.last_error == "RuntimeError: RESOURCE_EXHAUSTED: out of HBM"
    healthy = FallbackPolicy(ReactivePolicy())
    healthy.act_batch(obs)
    assert healthy.n_fallbacks == 0 and healthy.last_error is None


# -------------------------------------------------------- chain driver
def test_chain_driver_completes_with_retries(faulty_chain_world):
    jobs, cfg, cache = faulty_chain_world
    res = _driver(jobs, cfg, cache).run()
    assert res.reason == "completed"
    assert len(res.outcomes) == 3
    assert res.n_decisions > 3 and res.n_replayed == 0
    assert len(res.schedule) == 4               # pred + 3 links
    assert all(k in res.outcomes[0] for k in
               ("kind", "amount_s", "wait_s", "n_retries"))
    # deterministic: a second identical driver reproduces the schedule
    assert _driver(jobs, cfg, cache).run().schedule == res.schedule


def test_chain_driver_kill_and_resume_identical(faulty_chain_world,
                                                tmp_path):
    """The acceptance test: a driver killed mid-chain by
    PreemptionGuard.trigger(), restarted against its decision journal,
    replays the journalled prefix without consulting the policy and
    finishes with a schedule identical to an uninterrupted run."""
    jobs, cfg, cache = faulty_chain_world
    ref = _driver(jobs, cfg, cache,
                  journal=DecisionJournal(str(tmp_path / "ref"))).run()
    assert ref.reason == "completed"

    guard = PreemptionGuard(install_signals=False)
    consulted = {"n": 0}

    class TriggerMidway(FallbackPolicy):
        def act_batch(self, obs):
            consulted["n"] += 1
            if consulted["n"] >= ref.n_decisions // 2:
                guard.trigger()                  # preempt mid-chain
            return super().act_batch(obs)

    jp = str(tmp_path / "chain")
    first = _driver(jobs, cfg, cache, policy=TriggerMidway(ReactivePolicy()),
                    journal=DecisionJournal(jp), guard=guard).run()
    assert first.reason == "preempted"
    assert first.n_decisions < ref.n_decisions

    consulted["n"] = 0
    resumed = _driver(jobs, cfg, cache,
                      journal=DecisionJournal(jp)).run()
    assert resumed.reason == "completed"
    assert resumed.n_replayed == first.n_decisions
    # only the post-crash suffix consulted the policy
    assert resumed.n_decisions == ref.n_decisions
    assert resumed.schedule == ref.schedule
    assert [(o["kind"], o["amount_s"]) for o in resumed.outcomes] == \
        [(o["kind"], o["amount_s"]) for o in ref.outcomes]
    # ... and the journal now drives a full no-policy replay
    replay_only = _driver(jobs, cfg, cache,
                          journal=DecisionJournal(jp)).run()
    assert replay_only.n_replayed == ref.n_decisions
    assert replay_only.schedule == ref.schedule


def test_chain_driver_rejects_mismatched_journal(faulty_chain_world,
                                                 tmp_path):
    jobs, cfg, cache = faulty_chain_world
    jp = str(tmp_path / "j")
    _driver(jobs, cfg, cache, journal=DecisionJournal(jp)).run()
    bad = ChainDriver(jobs, cfg, FallbackPolicy(ReactivePolicy()), links=3,
                      seed=SEED + 1, cache=cache,
                      journal=DecisionJournal(jp))
    with pytest.raises(ValueError):
        bad.run()


def test_chained_trainer_accepts_external_guard(tmp_path):
    """The data plane accepts a control-plane-owned guard: triggering it
    preempts the sub-job."""
    from repro.data import DataConfig, data_iterator
    from repro.models import registry
    from repro.train import ChainConfig, ChainedTrainer, OptimizerConfig

    cfg = registry.get_config("tinyllama-1.1b", smoke=True)
    ocfg = OptimizerConfig(lr=1e-3, warmup_steps=1, total_steps=100)
    t = ChainedTrainer(cfg, ocfg, ChainConfig(ckpt_dir=str(tmp_path)),
                       data_iterator(cfg, DataConfig(batch=2, seq_len=16)),
                       seed=0)
    guard = PreemptionGuard(install_signals=False)
    guard.trigger()
    info = t.run_subjob(10, guard=guard)
    assert info["reason"] == "preempted"
    assert info["steps_done"] == 0
    assert t.guard is guard
