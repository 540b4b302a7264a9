"""Multi-tenant provisioning service (ISSUE 8 tentpole): dynamic
batching equivalence, kill-at-arbitrary-point recovery, circuit-breaker
degradation, deadline-aware load shedding and graceful drain. All chaos
is seeded and clocks/sleeps are injected — no wall-clock waits.
"""
import dataclasses
import time
import zlib

import numpy as np
import pytest

from repro.core import (ChainDriver, CircuitBreaker, EnvConfig,
                        FallbackPolicy, ReactivePolicy,
                        ReplayCheckpointCache, RetryPolicy)
from repro.core.state import encode_snapshot
from repro.serve import ProvisionService, ServiceConfig
from repro.serve.cosim import CoSimChainLane, CoSimWorld
from repro.sim import FaultPlan, get_fault_spec, synthesize_trace
from repro.sim.faults import FAIL, REPAIR
from repro.sim.trace import V100
from repro.train.fault import PreemptionGuard

HOUR = 3600.0
DAY = 24 * HOUR
SEED = 11
TENANTS = 6
LINKS = 2


class Kill(BaseException):
    """Abrupt process death: NOT an Exception, so FallbackPolicy cannot
    catch it — it rips straight through the serving loop like SIGKILL."""


class Ticker:
    """Injectable monotonic clock: every read advances it a little."""

    def __init__(self, tick=0.001):
        self.now = 0.0
        self.tick = tick

    def __call__(self):
        self.now += self.tick
        return self.now


def _retry_factory(i):
    return RetryPolicy(seed=100 + i, sleep=lambda s: None)


@pytest.fixture(scope="module")
def world():
    jobs = synthesize_trace(V100, months=1, seed=5, load_scale=1.0)
    plan = get_fault_spec("faulty").make_plan(
        jobs[-1].submit_time + 3 * DAY, V100.n_nodes, seed=3)
    cfg = EnvConfig(n_nodes=V100.n_nodes, history=12, interval=1800.0,
                    sub_limit=8 * HOUR, faults=plan)
    cache = ReplayCheckpointCache(jobs, cfg.n_nodes, faults=plan)
    return jobs, cfg, cache


def _service(world, policy=None, svc=None, journal_dir=None, **kw):
    jobs, cfg, cache = world
    kw.setdefault("retry_factory", _retry_factory)
    return ProvisionService(
        jobs, cfg, policy or FallbackPolicy(ReactivePolicy()),
        svc=svc or ServiceConfig(tenants=TENANTS, links=LINKS, max_batch=4),
        seed=SEED, journal_dir=journal_dir, cache=cache, **kw)


@pytest.fixture(scope="module")
def reference(world):
    """Uninterrupted run — the identity target for every chaos variant."""
    res = _service(world).run()
    assert res.reason == "completed"
    return res


def _schedules(res):
    return [t.schedule for t in res.tenants]


# ------------------------------------------------------- batching == solo
def test_batched_service_matches_independent_drivers(world, reference):
    """Multiplexing N lanes behind one act_batch call changes nothing:
    each tenant's schedule is bit-identical to a solo ChainDriver run
    with the same (seed, cache, retry stream)."""
    jobs, cfg, cache = world
    for i, t in enumerate(reference.tenants):
        solo = ChainDriver(jobs, cfg, FallbackPolicy(ReactivePolicy()),
                           links=LINKS, seed=SEED + i, cache=cache,
                           retry=_retry_factory(i)).run()
        assert solo.schedule == t.schedule
        assert t.reason == "completed"
    assert reference.n_decisions == sum(t.n_decisions
                                        for t in reference.tenants)
    assert reference.n_replayed == 0 and reference.n_shed == 0
    assert len(reference.latencies_s) == reference.n_decisions
    assert reference.p99_latency_s >= 0.0


# -------------------------------------------------------- kill & restart
@pytest.mark.parametrize("kill_after_batches", [1, 7])
def test_kill_at_arbitrary_point_restart_identical(world, reference,
                                                   tmp_path,
                                                   kill_after_batches):
    """The acceptance test: a service killed abruptly (uncatchable
    exception mid-batch, plus a torn journal tail) and restarted against
    its journals finishes with per-tenant schedules bit-identical to the
    uninterrupted run — no lost, no double-applied decisions."""
    jdir = str(tmp_path / f"j{kill_after_batches}")

    class Dying(ReactivePolicy):
        def __init__(self):
            super().__init__()
            self.batches = 0

        def act_batch(self, obs):
            if self.batches >= kill_after_batches:
                raise Kill()
            self.batches += 1
            return super().act_batch(obs)

    first = _service(world, policy=FallbackPolicy(Dying()),
                     journal_dir=jdir)
    with pytest.raises(Kill):
        first.run()
    applied = first.n_decisions
    assert 0 < applied < reference.n_decisions

    # the crash also tore the tail of one tenant's journal mid-append
    with open(f"{jdir}/tenant_00000.journal", "ab") as f:
        f.write(b"\x00\x01\x02")

    resumed = _service(world, journal_dir=jdir)
    res = resumed.run()
    assert res.reason == "completed"
    assert res.n_replayed == applied          # every journaled decision
    assert res.n_replayed + res.n_decisions == reference.n_decisions
    assert _schedules(res) == _schedules(reference)

    # a second rehydrate replays everything and applies nothing new
    replay_only = _service(world, journal_dir=jdir).run()
    assert replay_only.n_replayed == reference.n_decisions
    assert replay_only.n_decisions == 0
    assert _schedules(replay_only) == _schedules(reference)


# ------------------------------------------------------- circuit breaker
def test_breaker_trips_on_sick_learner_and_keeps_answering(world,
                                                           reference):
    """A persistently failing learner trips the fleet-wide breaker: the
    service stops consulting it and keeps answering reactively, with the
    schedule unchanged (the fallback IS the reactive rule)."""
    calls = {"n": 0}

    class Sick(ReactivePolicy):
        def act_batch(self, obs):
            calls["n"] += 1
            raise RuntimeError("learner OOM")

    svc = ServiceConfig(tenants=TENANTS, links=LINKS, max_batch=4,
                        breaker_window=8, breaker_threshold=3,
                        breaker_cooldown_s=float("inf"))
    s = _service(world, policy=FallbackPolicy(Sick()), svc=svc)
    res = s.run()
    assert res.reason == "completed"
    assert res.breaker_trips == 1
    assert calls["n"] == 3                    # consults stop at the trip
    # only the pre-trip batches (possibly ragged) consulted the learner
    assert 0 < res.n_decisions - res.n_degraded <= 3 * svc.max_batch
    assert res.n_degraded > 0
    assert _schedules(res) == _schedules(reference)


def test_breaker_forced_open_serves_reactive(world, reference):
    """Chaos/ops can force the breaker open: the learner is never
    consulted, every decision is degraded, nothing stalls."""
    calls = {"n": 0}

    class Counting(ReactivePolicy):
        def act_batch(self, obs):
            calls["n"] += 1
            return super().act_batch(obs)

    br = CircuitBreaker(cooldown_s=float("inf"))
    br.trip()
    s = _service(world, policy=FallbackPolicy(Counting()), breaker=br)
    res = s.run()
    assert res.reason == "completed"
    assert calls["n"] == 0
    assert res.n_degraded == res.n_decisions > 0
    assert _schedules(res) == _schedules(reference)


def test_breaker_half_open_probe_recovers(world, reference):
    """After the cooldown a half-open probe reaches the (recovered)
    learner and closes the breaker — degradation is temporary."""
    clock = Ticker(tick=0.01)
    state = {"failures_left": 3, "consults": 0}

    class Flaky(ReactivePolicy):
        def act_batch(self, obs):
            state["consults"] += 1
            if state["failures_left"] > 0:
                state["failures_left"] -= 1
                raise RuntimeError("transient learner brownout")
            return super().act_batch(obs)

    svc = ServiceConfig(tenants=TENANTS, links=LINKS, max_batch=4,
                        breaker_window=8, breaker_threshold=3,
                        breaker_cooldown_s=0.5)
    s = _service(world, policy=FallbackPolicy(Flaky(), clock=clock),
                 svc=svc, clock=clock)
    res = s.run()
    assert res.reason == "completed"
    assert res.breaker_trips == 1             # tripped once, then healed
    assert res.n_degraded > 0                 # served through the outage
    assert s.breaker.state == CircuitBreaker.CLOSED
    assert state["consults"] > 4              # probed and kept consulting
    assert _schedules(res) == _schedules(reference)


# ---------------------------------------------------------- load shedding
def test_overload_sheds_bounded_with_hints(world, reference):
    """A slow policy under a tight SLO sheds the tail of every round —
    bounded per-tenant counts with retry-after hints — while the
    head-of-line batch always proceeds, and shedding (a wall-clock
    delay) leaves every schedule untouched."""
    clock = Ticker(tick=0.001)

    class Slow(ReactivePolicy):
        def act_batch(self, obs):
            clock.now += 10.0                 # one batch costs ~10s
            return super().act_batch(obs)

    svc = ServiceConfig(tenants=TENANTS, links=LINKS, max_batch=2,
                        max_queue=4, slo_s=15.0)
    s = _service(world, policy=FallbackPolicy(Slow()), svc=svc,
                 clock=clock)
    res = s.run()
    assert res.reason == "completed"
    assert res.n_shed > 0
    assert sum(res.shed_per_tenant) == res.n_shed
    # bounded: nobody is shed more than once per service round
    assert max(res.shed_per_tenant) <= res.n_rounds
    shed_tenants = [i for i, n in enumerate(res.shed_per_tenant) if n]
    assert shed_tenants
    assert all(s.retry_after_s[i] > 0.0 for i in shed_tenants)
    # wall-clock shedding never leaks into simulated time
    assert _schedules(res) == _schedules(reference)
    assert res.n_decisions == reference.n_decisions


# ------------------------------------------------------- drain & health
def test_graceful_drain_health_and_rehydrate(world, reference, tmp_path):
    jdir = str(tmp_path / "drain")
    guard = PreemptionGuard(install_signals=False)

    class TripsGuard(ReactivePolicy):
        def __init__(self):
            super().__init__()
            self.batches = 0

        def act_batch(self, obs):
            self.batches += 1
            if self.batches == 3:
                guard.trigger()               # preemption notice mid-round
            return super().act_batch(obs)

    s = _service(world, policy=FallbackPolicy(TripsGuard()),
                 journal_dir=jdir, guard=guard)
    h0 = s.health()
    assert not h0.ready and h0.tenants == TENANTS
    res = s.run()
    assert res.reason == "drained"
    assert 0 < res.n_decisions < reference.n_decisions
    assert any(t.reason == "drained" for t in res.tenants)
    h1 = s.health()
    assert h1.draining and not h1.ready
    assert h1.n_decisions == res.n_decisions
    assert h1.tenants_live > 0 and h1.breaker_state == "closed"

    s2 = _service(world, journal_dir=jdir)
    res2 = s2.run()
    assert res2.reason == "completed"
    assert res2.n_replayed == res.n_decisions
    assert _schedules(res2) == _schedules(reference)
    h2 = s2.health()
    assert h2.tenants_live == 0 and h2.queue_depth == 0
    assert h2.max_lag_rounds == 0


# ---------------------------------------------- co-simulation (ISSUE 10)
def _co_service(world, policy=None, journal_dir=None, **kw):
    jobs, cfg, cache = world
    kw.setdefault("retry_factory", _retry_factory)
    return ProvisionService(
        jobs, cfg, policy or FallbackPolicy(ReactivePolicy()),
        svc=ServiceConfig(tenants=TENANTS, links=LINKS, max_batch=4,
                          co_sim=True),
        seed=SEED, journal_dir=journal_dir, cache=cache, **kw)


@pytest.fixture(scope="module")
def co_reference(world):
    """Uninterrupted co-sim run — the identity target for co chaos."""
    res = _co_service(world).run()
    assert res.reason == "completed"
    assert all(t.reason == "completed" for t in res.tenants)
    return res


@pytest.mark.parametrize("kill_after_batches", [1, 5])
def test_cosim_kill_midround_restart_identical(world, co_reference,
                                               tmp_path,
                                               kill_after_batches):
    """The co-sim acceptance test: killed abruptly mid-round (6 tenants
    x max_batch=4 means the shared round is two chunks, so the kill
    lands with a partial round journaled, plus a torn tail) and
    restarted against its journals, the service replays the shared
    schedule exactly — every tenant's schedule bit-identical to the
    uninterrupted co run."""
    jdir = str(tmp_path / f"co{kill_after_batches}")

    class Dying(ReactivePolicy):
        def __init__(self):
            super().__init__()
            self.batches = 0

        def act_batch(self, obs):
            if self.batches >= kill_after_batches:
                raise Kill()
            self.batches += 1
            return super().act_batch(obs)

    first = _co_service(world, policy=FallbackPolicy(Dying()),
                        journal_dir=jdir)
    with pytest.raises(Kill):
        first.run()
    applied = first.n_decisions
    assert 0 < applied < co_reference.n_decisions

    # the crash also tore the tail of one tenant's journal mid-append
    with open(f"{jdir}/tenant_00000.journal", "ab") as f:
        f.write(b"\x00\x01\x02")

    res = _co_service(world, journal_dir=jdir).run()
    assert res.reason == "completed"
    assert res.n_replayed == applied          # every journaled decision
    assert res.n_replayed + res.n_decisions == co_reference.n_decisions
    assert _schedules(res) == _schedules(co_reference)

    # a second rehydrate replays everything and applies nothing new
    replay_only = _co_service(world, journal_dir=jdir).run()
    assert replay_only.n_replayed == co_reference.n_decisions
    assert replay_only.n_decisions == 0
    assert _schedules(replay_only) == _schedules(co_reference)


def test_cosim_rejects_cross_mode_journals(world, tmp_path):
    """Journals are mode-stamped: a co-sim service refuses journals
    written by the per-fork service and vice versa — silently replaying
    a decision stream against the wrong engine would corrupt schedules."""
    solo_dir, co_dir = str(tmp_path / "solo"), str(tmp_path / "co")
    assert _service(world, journal_dir=solo_dir).run().reason == "completed"
    with pytest.raises(ValueError, match="co"):
        _co_service(world, journal_dir=solo_dir).run()

    assert _co_service(world, journal_dir=co_dir).run().reason == "completed"
    with pytest.raises(ValueError, match="co-sim"):
        _service(world, journal_dir=co_dir).run()


def test_cosim_faults_attributed_to_owning_tenant(world, co_reference):
    """Satellite regression: on a faulted co-sim cell each tenant's
    reported fault/requeue counts are its OWNED counts (the tenant whose
    job the fault killed), not the fleet-window totals every tenant
    would otherwise share."""
    s = _co_service(world)
    res = s.run()
    assert res.reason == "completed"
    w = s.cosim.world
    for i, t in enumerate(res.tenants):
        assert t.n_faults == int(w.fault_counts[i])
        assert t.n_requeues == int(w.requeue_counts[i])
    # the shared background DID fault during the serving window, yet only
    # tenants whose jobs were hit carry counts — owned <= fleet, and the
    # background's own kills are nobody's interruption
    assert w.sim.n_node_failures > 0
    assert sum(t.n_faults for t in res.tenants) <= w.sim.n_node_failures
    assert sum(t.n_requeues for t in res.tenants) <= w.sim.n_requeues


# --------------------------------------------------------- program spans
def test_cosim_spans_count_the_service_and_change_nothing(world, tmp_path):
    """Recording the program's spans counts one ``service.round`` per
    round, one ``forward.wait`` per batch and one ``lane.apply`` per
    decision, and changes nothing served: the actions and the per-tenant
    schedules match a run with recording off bit for bit."""
    import dataclasses

    from repro import telemetry
    from repro.core import (DQNConfig, DQNLearner, FoundationConfig,
                            LearnerPolicy)
    fc = dataclasses.replace(FoundationConfig(kind="transformer").reduced(),
                             history=world[1].history)
    learner = DQNLearner(fc, DQNConfig(), seed=0)

    class Logged(LearnerPolicy):
        def __init__(self):
            super().__init__("transformer+dqn", learner)
            self.actions = []

        def act_batch(self, obs):
            acts = super().act_batch(obs)
            self.actions.append(acts.tolist())
            return acts

    def serve(name):
        policy = Logged()
        svc = _co_service(world, policy=policy,
                          journal_dir=str(tmp_path / name))
        return svc.run(), policy.actions

    telemetry.reset()
    off, off_actions = serve("off")
    with telemetry.capture():
        t0 = time.perf_counter()
        on, on_actions = serve("on")
        t1 = time.perf_counter()
    assert telemetry.totals(0.0, t0) == {}        # nothing while off
    tot = telemetry.totals(t0, t1)
    assert on.reason == "completed" and on.n_batches > on.n_rounds > 0
    assert tot["service.round"].count == on.n_rounds
    assert tot["forward.wait"].count == tot["forward.launch"].count \
        == tot["forward.fetch"].count == on.n_batches
    assert tot["lane.apply"].count == on.n_decisions
    # one header per tenant, then one record per decision
    assert tot["journal.append"].count == on.n_decisions + TENANTS
    assert tot["service.start"].count == tot["service.build"].count == 1
    assert tot["cosim.advance"].count == tot["sim.advance"].count \
        == tot["state.encode"].count
    assert on_actions == off_actions and on_actions
    assert _schedules(on) == _schedules(off)
    telemetry.reset()


# ------------------------------------------- shared snapshot encoding
def _pair_row(sim, cfg, pred):
    """One lane's snapshot row built on its own, as the scalar
    environment builds it: the full encode of the simulator's sample
    with the lane's predecessor and successor infos."""
    info = None
    if pred is not None:
        info = {"size": pred.n_nodes, "limit": pred.time_limit,
                "queue_time": max(pred.wait_time, 0.0),
                "elapsed": (max(sim.now - pred.start_time, 0.0)
                            if pred.start_time >= 0 else 0.0)}
    return encode_snapshot(sim.sample(), cfg.n_nodes, cfg.sub_limit, info,
                           {"size": cfg.chain_nodes, "limit": cfg.sub_limit})


class _PerLaneWorld(CoSimWorld):
    """The reference: every lane encodes the whole snapshot itself, and
    counts the pushes made while its predecessor was requeued."""
    requeued_pushes = 0

    def _push_snapshots(self, lanes):
        for lane in lanes:
            pred = lane.env.pred
            _PerLaneWorld.requeued_pushes += int(
                pred is not None and pred.start_time < 0)
            lane.env.hist.push(_pair_row(lane.env.sim, self.cfg, pred))


@pytest.fixture(scope="module", params=["fault-free", "faulty", "outage"])
def snap_world(request, world):
    """The fault-free cluster, the ``faulty`` profile, and an outage of
    every node from 1 h to 4 h after the episodes' start, which requeues
    every tenant's running predecessor."""
    if request.param == "faulty":
        return ("faulty",) + world
    jobs, cfg, _ = world
    cfg = dataclasses.replace(cfg, faults=None)
    if request.param == "outage":
        probe = CoSimWorld(jobs, cfg, 1, seed=SEED,
                           cache=ReplayCheckpointCache(jobs, cfg.n_nodes))
        CoSimChainLane(jobs, cfg, probe, 0, links=LINKS, seed=SEED)
        probe.begin()
        n = cfg.n_nodes
        cfg = dataclasses.replace(cfg, faults=FaultPlan(
            np.array([probe.t0 + 1 * HOUR, probe.t0 + 4 * HOUR]),
            np.array([FAIL, REPAIR]), np.array([n, n])))
    return (request.param, jobs, cfg,
            ReplayCheckpointCache(jobs, cfg.n_nodes, faults=cfg.faults))


def _lanes_equal(a, b):
    for la, lb in zip(a.lanes, b.lanes):
        np.testing.assert_array_equal(la.env.hist.matrix(),
                                      lb.env.hist.matrix())
        assert (la.obs is None) == (lb.obs is None)
        if la.obs is not None:
            for key in la.obs:
                np.testing.assert_array_equal(la.obs[key], lb.obs[key], key)


@pytest.mark.parametrize("tenants", [1, 3, 8])
def test_cosim_shared_snapshot_matches_per_lane_encode(snap_world, tenants):
    """The world encodes the cluster once per instant and each lane adds
    its pair columns: after ``begin`` and after every ``advance_round``
    each lane's history and observation equal, bit for bit, those of a
    world whose lanes each encode ``sim.sample()`` with their own
    predecessor and successor infos."""
    name, jobs, cfg, cache = snap_world

    def build(cls):
        w = cls(jobs, cfg, tenants, seed=SEED, cache=cache)
        for i in range(tenants):
            CoSimChainLane(jobs, cfg, w, i, links=LINKS, seed=SEED + i,
                           retry=_retry_factory(i), cache=cache)
        w.begin()
        return w

    _PerLaneWorld.requeued_pushes = 0
    new, ref = build(CoSimWorld), build(_PerLaneWorld)
    _lanes_equal(new, ref)
    rng = np.random.default_rng(tenants)
    rounds = 0
    while not all(lane.done for lane in new.lanes):
        for ln, lr in zip(new.lanes, ref.lanes):
            assert ln.awaiting == lr.awaiting
            if ln.awaiting:
                a = int(rng.random() < 0.1)
                ln.apply(a)
                lr.apply(a)
        new.advance_round()
        ref.advance_round()
        _lanes_equal(new, ref)
        rounds += 1
        assert rounds < 10_000
    assert all(lane.done for lane in ref.lanes)
    assert [lane.outcomes for lane in new.lanes] == \
        [lane.outcomes for lane in ref.lanes]
    if name == "outage":
        # the pair columns of a requeued predecessor were exercised
        assert _PerLaneWorld.requeued_pushes > 0


class _Hashing(ReactivePolicy):
    """Submits on a hash of each observation's bytes: a change of any
    bit of a lane's observation changes its actions."""

    def __init__(self):
        super().__init__()
        self.actions = []

    def act_batch(self, obs):
        acts = np.array([zlib.crc32(m.tobytes() + r.tobytes()) % 6 == 0
                         for m, r in zip(obs["matrix"],
                                         np.asarray(obs["pred_remaining"],
                                                    np.float64))], np.int64)
        self.actions.append(acts.tolist())
        return acts


@pytest.mark.parametrize("tenants", [1, 3, 8])
def test_cosim_shared_snapshot_serves_the_same_service(snap_world, tenants,
                                                      tmp_path,
                                                      monkeypatch):
    """A short co-sim service run serves the same actions, writes the same
    journals and ends with the same schedules as one whose lanes each
    encode their whole snapshot."""
    _, jobs, cfg, cache = snap_world

    def serve(name):
        policy = _Hashing()
        jdir = tmp_path / name
        res = ProvisionService(
            jobs, cfg, policy,
            svc=ServiceConfig(tenants=tenants, links=LINKS, max_batch=4,
                              co_sim=True),
            seed=SEED, journal_dir=str(jdir), cache=cache,
            retry_factory=_retry_factory).run()
        assert res.reason == "completed"
        journals = {p.name: p.read_bytes() for p in sorted(jdir.iterdir())}
        return res, policy.actions, journals

    new, new_actions, new_journals = serve("new")
    monkeypatch.setattr(CoSimWorld, "_push_snapshots",
                        _PerLaneWorld._push_snapshots)
    ref, ref_actions, ref_journals = serve("ref")
    assert new_actions == ref_actions
    assert any(1 in batch for batch in new_actions)
    assert len(new_journals) == tenants
    assert new_journals == ref_journals
    assert _schedules(new) == _schedules(ref)


def test_cosim_one_snapshot_span_per_shared_instant(world):
    """``state.snapshot`` is recorded once per round whose advance pushed
    a history row, plus once for the start's inject, and recording
    changes nothing served."""
    from repro import telemetry

    def serve():
        policy = _Hashing()
        svc = _co_service(world, policy=policy)
        advance = svc.cosim.advance_round
        pushed = []

        def advance_round():
            before = [lane.env.hist._pos for lane in svc.cosim.lanes]
            advance()
            pushed.append(before != [lane.env.hist._pos
                                     for lane in svc.cosim.lanes])

        svc.cosim.advance_round = advance_round
        return svc.run(), policy.actions, pushed

    telemetry.reset()
    off, off_actions, _ = serve()
    with telemetry.capture():
        t0 = time.perf_counter()
        on, on_actions, pushed = serve()
        t1 = time.perf_counter()
    tot = telemetry.totals(t0, t1)
    telemetry.reset()
    assert on.reason == "completed" and any(pushed) and not all(pushed)
    assert tot["cosim.advance"].count == len(pushed)
    assert tot["state.snapshot"].count == sum(pushed) + 1
    assert on_actions == off_actions
    assert _schedules(on) == _schedules(off)


# ------------------------------------------------ solo environment spans
@pytest.mark.parametrize("tenants", [1, 3, 6])
def test_solo_env_spans_count_per_tenant(world, tenants):
    """In the solo service a start records one ``env.reset`` (with one
    ``env.fork`` and one ``env.warmup``) per tenant and no step of its
    warm-up; a round in which every tenant waits records one
    ``env.advance`` and one history-push ``env.encode`` per tenant, and
    each decision one ``env.encode`` for its observation refresh.
    Recording changes nothing served."""
    from repro import telemetry
    svc_cfg = ServiceConfig(tenants=tenants, links=LINKS, max_batch=4)

    def serve(max_rounds=None):
        policy = _Hashing()
        svc = _service(world, policy=FallbackPolicy(ReactivePolicy()) if
                       max_rounds else policy, svc=svc_cfg)
        t0 = time.perf_counter()
        svc.start()
        t1 = time.perf_counter()
        res = svc.run(max_rounds=max_rounds)
        return svc, res, policy.actions, (t0, t1, time.perf_counter())

    telemetry.reset()
    with telemetry.capture():
        svc, res, _, (t0, t1, t2) = serve(max_rounds=1)
    start, round_ = telemetry.totals(t0, t1), telemetry.totals(t1, t2)
    assert res.n_rounds == 1 and res.n_decisions == tenants
    assert all(not lane.outcomes for lane in svc.lanes)     # every one waited
    for name in ("env.reset", "env.fork", "env.warmup"):
        assert start[name].count == tenants and round_[name].count == 0
    assert start["env.advance"].count == start["env.encode"].count == 0
    assert round_["env.advance"].count == tenants
    assert round_["env.encode"].count == 2 * tenants
    assert round_["lane.apply"].count == tenants

    off_svc, off, off_actions, _ = serve()
    with telemetry.capture():
        on_svc, on, on_actions, (t0, _, t2) = serve()
    tot = telemetry.totals(t0, t2)
    telemetry.reset()
    submits = sum(len(lane.outcomes) for lane in on_svc.lanes)
    assert on.reason == "completed" and 0 < submits < on.n_decisions
    assert tot["env.advance"].count == on.n_decisions - submits
    assert tot["env.encode"].count == 2 * on.n_decisions - submits
    assert on_actions == off_actions and on_actions
    assert _schedules(on) == _schedules(off)


def test_cosim_records_no_env_spans(world):
    """The co-sim round passes none of the solo environment's spans, and
    its own spans keep their counts: one ``state.encode`` and one
    ``sim.advance`` per ``cosim.advance``."""
    from repro import telemetry
    telemetry.reset()
    with telemetry.capture():
        t0 = time.perf_counter()
        res = _co_service(world, policy=_Hashing()).run()
        t1 = time.perf_counter()
    tot = telemetry.totals(t0, t1)
    telemetry.reset()
    assert res.reason == "completed" and tot["cosim.advance"].count > 0
    for name in ("env.reset", "env.fork", "env.warmup", "env.advance",
                 "env.encode"):
        assert tot[name].count == 0, name
    assert tot["state.encode"].count == tot["sim.advance"].count \
        == tot["cosim.advance"].count
    assert tot["cosim.inject"].count == 1
    assert tot["state.snapshot"].count >= 1
