"""Differential episode engine: the immutable ``BackgroundTimeline``
must serve resets start/end-**bit-identically** to the classic
fork-per-lane path — on fault-free and faulted cells, on proved-start
lanes and on provable-cascade fallback lanes alike — and the new API
surface (``make_env``/``make_vector_env`` factories, ``schedule_view``,
``resized``) must uphold its contracts.
"""
import copy
import time

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

import repro.core.provisioner as provisioner
from repro import telemetry
from repro.core import EnvConfig, ProvisionEnv
from repro.core.provisioner import ReplayCheckpointCache
from repro.sim import (FaultPlan, SlurmSimulator, get_fault_spec, make_env,
                       make_vector_env, synthesize_trace)
from repro.sim.faults import FAIL, REPAIR
from repro.sim.trace import V100, Job

from _fork_path import ForkPathCache

HOUR = 3600.0
DAY = 24 * HOUR


@pytest.fixture(scope="module")
def trace_cfg():
    jobs = synthesize_trace(V100, months=1, seed=5, load_scale=1.0)
    return jobs, EnvConfig(n_nodes=V100.n_nodes, history=12, interval=1800.0)


def run_episode(venv, t_starts, policy):
    obs = venv.reset(t_starts=t_starts)
    traj = [{k: np.array(v) for k, v in obs.items()}]
    rewards, infos = np.zeros(venv.batch), [{}] * venv.batch
    t = 0
    while not venv.dones.all():
        was = venv.dones.copy()
        obs, r, dones, inf = venv.step([policy(t)] * venv.batch)
        traj.append({k: np.array(v) for k, v in obs.items()})
        for i in range(venv.batch):
            if not was[i] and dones[i]:
                rewards[i] = r[i]
                infos[i] = inf[i]
        t += 1
    return traj, rewards, infos


def assert_trajs_equal(a, b):
    ta, ra, ia = a
    tb, rb, ib = b
    assert len(ta) == len(tb)
    for sa, sb in zip(ta, tb):
        for k in sa:
            np.testing.assert_array_equal(sa[k], sb[k], err_msg=k)
    np.testing.assert_array_equal(ra, rb)
    assert ia == ib


def _pred_times(venv):
    """(start, end) per lane after a reset — the episode's ground truth."""
    return [(e.pred.start_time, e.pred.end_time) for e in venv.envs]


# --------------------------------------------------- differential == fork
def test_differential_engine_bit_identical_fault_free(trace_cfg):
    """Full-trajectory equality: the differential reset (timeline place +
    adopt) and the classic fork-per-lane reset produce bit-identical
    observations, rewards and infos — and the engine actually engaged."""
    jobs, cfg = trace_cfg
    B = 4
    lo, hi = ProvisionEnv(jobs, cfg, seed=0)._t_start_range
    ts = [lo + f * (hi - lo) for f in (0.1, 0.35, 0.6, 0.85)]
    policy = (lambda t: 1 if t >= 3 else 0)

    venv_d = make_vector_env(jobs, cfg, B, seed=0)
    venv_f = make_vector_env(jobs, cfg, B, seed=0,
                             cache=ForkPathCache(jobs, cfg.n_nodes))
    a = run_episode(venv_d, ts, policy)
    b = run_episode(venv_f, ts, policy)
    # the engine served every lane (fault-free: timeline covers the trace)
    assert venv_d.reset_stats["diff_lanes"] == B
    assert venv_d.reset_stats["fallback_lanes"] == 0
    assert venv_f.reset_stats["diff_lanes"] == 0
    assert 0.0 < venv_d.differential_hit_rate <= 1.0
    assert _pred_times(venv_d) == _pred_times(venv_f)
    assert_trajs_equal(a, b)


def test_differential_covers_both_placement_kinds(trace_cfg):
    """Across a spread of start instants on a heavy-load month, the
    engine exercises BOTH materialization paths — proved-inert starts and
    provable-cascade fallbacks — and every lane still matches the
    full-fork engine start/end-exactly."""
    jobs, cfg = trace_cfg
    B = 8
    lo, hi = ProvisionEnv(jobs, cfg, seed=0)._t_start_range
    ts = [lo + (i + 0.5) / B * (hi - lo) for i in range(B)]
    venv_d = make_vector_env(jobs, cfg, B, seed=0)
    venv_f = make_vector_env(jobs, cfg, B, seed=0,
                             cache=ForkPathCache(jobs, cfg.n_nodes))
    venv_d.reset(t_starts=ts)
    venv_f.reset(t_starts=ts)
    st_ = venv_d.reset_stats
    assert st_["starts"] + st_["cascades"] == B
    assert st_["cascades"] > 0     # heavy load: displacements do occur
    assert _pred_times(venv_d) == _pred_times(venv_f)


def test_differential_faulted_lanes_fall_back(trace_cfg):
    """On a faulted cell the timeline is only the truth before the first
    fault event: lanes past ``valid_until`` must fall back to real forks,
    lanes before it may stay differential — and both populations must be
    bit-identical to the fork-only engine."""
    jobs, cfg_ff = trace_cfg
    lo, hi = ProvisionEnv(jobs, cfg_ff, seed=0)._t_start_range
    # one mid-trace fail/repair pair: early lanes differential, late
    # lanes (after the fault) forced onto the fork path
    t_fault = lo + 0.5 * (hi - lo)
    plan = FaultPlan(np.array([t_fault, t_fault + 6 * HOUR]),
                     np.array([FAIL, REPAIR]), np.array([4, 4]))
    cfg = EnvConfig(n_nodes=cfg_ff.n_nodes, history=cfg_ff.history,
                    interval=cfg_ff.interval, faults=plan)
    ts = [lo + 0.1 * (hi - lo), lo + 0.8 * (hi - lo)]
    policy = (lambda t: 1 if t >= 3 else 0)
    venv_d = make_vector_env(jobs, cfg, 2, seed=0)
    venv_f = make_vector_env(jobs, cfg, 2, seed=0,
                             cache=ForkPathCache(jobs, cfg.n_nodes,
                                                 faults=plan))
    a = run_episode(venv_d, ts, policy)
    b = run_episode(venv_f, ts, policy)
    assert venv_d.reset_stats["diff_lanes"] == 1       # pre-fault lane
    assert venv_d.reset_stats["fallback_lanes"] == 1   # post-fault lane
    assert _pred_times(venv_d) == _pred_times(venv_f)
    assert_trajs_equal(a, b)


@settings(max_examples=6, deadline=None)
@given(st.integers(min_value=0, max_value=10**6),
       st.lists(st.floats(min_value=0.02, max_value=0.98),
                min_size=2, max_size=3))
def test_differential_matches_fork_under_faults_property(seed, fracs):
    """Property: for any fault plan drawn from the registered profile and
    any episode start instants, differential and full-fork resets agree
    on every predecessor start/end — including lanes whose episodes
    straddle kills/requeues and lanes past ``valid_until``."""
    jobs = synthesize_trace(V100, months=1, seed=5, load_scale=1.0)
    plan = get_fault_spec("faulty").make_plan(
        jobs[-1].submit_time + 3 * DAY, V100.n_nodes, seed=seed)
    cfg = EnvConfig(n_nodes=V100.n_nodes, history=12, interval=1800.0,
                    faults=plan)
    lo, hi = ProvisionEnv(jobs, cfg, seed=0)._t_start_range
    ts = [lo + f * (hi - lo) for f in fracs]
    venv_d = make_vector_env(jobs, cfg, len(ts), seed=seed % 97)
    venv_f = make_vector_env(jobs, cfg, len(ts), seed=seed % 97,
                             cache=ForkPathCache(jobs, cfg.n_nodes,
                                                 faults=plan))
    venv_d.reset(t_starts=ts)
    venv_f.reset(t_starts=ts)
    assert _pred_times(venv_d) == _pred_times(venv_f)


# ------------------------------------------------------------ API surface
def test_schedule_view_read_only(trace_cfg):
    """``schedule_view()`` is the one supported cross-module read: its
    arrays mirror the schedule exactly and are frozen unconditionally
    (no sanitizer needed) — writes raise at the write site."""
    jobs, cfg = trace_cfg
    sim = SlurmSimulator(cfg.n_nodes, mode="fast")
    sim.load([copy.copy(j) for j in jobs])
    sim.run_until(jobs[0].submit_time + 5 * DAY)
    view = sim.schedule_view()
    assert view.n == sim._n and view.now == sim.now
    np.testing.assert_array_equal(view.start, sim._start[:sim._n])
    np.testing.assert_array_equal(view.end, sim._end[:sim._n])
    np.testing.assert_array_equal(view.ids, sim._ids[:sim._n])
    for name in ("sub", "runtime", "limit", "nodes", "ids", "start", "end"):
        arr = getattr(view, name)
        assert not arr.flags.writeable, name
        with pytest.raises(ValueError):
            arr[0] = 0
    # the freeze is a view property: the simulator's own buffers stay
    # writeable (freezing them would break the engine itself)
    assert sim._start.flags.writeable


def test_factory_overrides_do_not_mutate_cfg(trace_cfg):
    jobs, cfg = trace_cfg
    venv = make_vector_env(jobs, cfg, 1, seed=0, interval=900.0)
    assert venv.cfg.interval == 900.0
    assert cfg.interval == 1800.0              # replace(), not mutation
    env = make_env(jobs, cfg, seed=3, history=7)
    assert env.cfg.history == 7 and cfg.history != 7
    assert isinstance(env, ProvisionEnv)


def test_factory_lane_identity_and_resized(trace_cfg):
    """Factory-built lane i == factory-built scalar seeded seed+i, and
    ``resized`` shares trace/cfg/seed/cache (same object) so tail chunks
    reuse the warm ring."""
    jobs, cfg = trace_cfg
    cache = ReplayCheckpointCache(jobs, cfg.n_nodes)
    venv = make_vector_env(jobs, cfg, 2, seed=11, cache=cache)
    assert venv.cache is cache
    small = venv.resized(1)
    assert small.batch == 1 and small.cache is cache
    assert small.trace is venv.trace and small.cfg is venv.cfg
    assert venv.resized(2) is venv             # no-op resize: same object
    lo, hi = venv._t_start_range
    ts = lo + 0.4 * (hi - lo)
    vobs = venv.reset(t_starts=[ts, ts])
    sobs = make_env(jobs, cfg, seed=12, cache=cache).reset(t_start=ts)
    np.testing.assert_allclose(vobs["matrix"][1], sobs["matrix"], atol=1e-7)


# ------------------------------------------- scalar differential resets
def _cascade_at_t0_world():
    """Four nodes; two running jobs leave one free. The blocked head (3
    nodes) reserves the 5-h end, so the 1-node, 8-h job behind it cannot
    backfill — until a 4-node predecessor outranks the head at t0 = 1 h
    and moves the reservation to the 10-h end: the predecessor's
    submission itself starts a background job."""
    jobs = [Job(job_id=1, user_id=1, submit_time=0.0, runtime=5 * HOUR,
                time_limit=5 * HOUR, n_nodes=2),
            Job(job_id=2, user_id=1, submit_time=0.0, runtime=10 * HOUR,
                time_limit=10 * HOUR, n_nodes=1),
            Job(job_id=3, user_id=1, submit_time=60.0, runtime=HOUR,
                time_limit=HOUR, n_nodes=3),
            Job(job_id=4, user_id=1, submit_time=120.0, runtime=8 * HOUR,
                time_limit=8 * HOUR, n_nodes=1),
            Job(job_id=5, user_id=1, submit_time=30 * HOUR, runtime=HOUR,
                time_limit=HOUR, n_nodes=1)]
    cfg = EnvConfig(n_nodes=4, chain_nodes=4, sub_limit=12 * HOUR, history=2,
                    interval=1800.0, warmup=HOUR)
    return jobs, cfg, HOUR


def _world(case):
    """(trace, cfg, t_start) of one scalar-reset case."""
    if case == "cascade-at-t0":
        return _cascade_at_t0_world()
    load = 0.5 if case == "start" else 1.0
    jobs = synthesize_trace(V100, months=1, seed=5, load_scale=load)
    plan = None
    if case.startswith("faulty"):
        # this seed's first fault falls 15.2 days in: starts on both
        # sides of it, and one whose predecessor waits across it
        plan = get_fault_spec("faulty").make_plan(
            jobs[-1].submit_time + 3 * DAY, V100.n_nodes, seed=4)
        assert 14 * DAY < plan.times[0] < 16 * DAY
    cfg = EnvConfig(n_nodes=V100.n_nodes, history=12, interval=1800.0,
                    faults=plan)
    frac = {"fault-free": 0.3, "faulty-before": 0.2, "faulty-across": 0.55,
            "faulty-after": 0.8, "extension": 0.7, "start": 0.02}[case]
    lo, hi = ProvisionEnv(jobs, cfg, seed=0)._t_start_range
    return jobs, cfg, lo + frac * (hi - lo)


SCALAR_CASES = ["fault-free", "faulty-before", "faulty-across",
                "faulty-after", "extension", "cascade-at-t0", "start"]


@pytest.mark.parametrize("case", SCALAR_CASES)
def test_scalar_differential_reset_matches_fork(case, monkeypatch):
    """``ProvisionEnv.reset`` served off the timeline is bit-identical to
    the fork path (a ``ForkPathCache``): history, obs, the
    predecessor's start and wait, ``sim.now``, and 50 further steps —
    on a fault-free world, on both sides of the first fault, where the
    placement runs past the recorded reach (the frontier extends), on a
    cascade at t0 and on a proved start."""
    jobs, cfg, t0 = _world(case)
    if case == "extension":
        monkeypatch.setattr(provisioner, "TIMELINE_CHUNK", 12 * HOUR)
    cache = ReplayCheckpointCache(jobs, cfg.n_nodes, faults=cfg.faults)
    diff = make_env(jobs, cfg, seed=7, cache=cache)
    fork = make_env(jobs, cfg, seed=7,
                    cache=ForkPathCache(jobs, cfg.n_nodes,
                                        faults=cfg.faults))
    telemetry.reset()
    with telemetry.capture():
        a = time.perf_counter()
        od = diff.reset(t_start=t0)
        spans = telemetry.totals(a, time.perf_counter())
    telemetry.reset()
    of = fork.reset(t_start=t0)
    np.testing.assert_array_equal(diff.hist.matrix(), fork.hist.matrix())
    for k in od:
        np.testing.assert_array_equal(od[k], of[k], err_msg=k)
    assert (diff.pred.start_time, diff.pred.wait_time) == \
        (fork.pred.start_time, fork.pred.wait_time)
    assert diff.sim.now == fork.sim.now
    # what the reset took: the timeline's placement, or the fork path
    placed = case != "faulty-after"
    assert spans["env.place"].count == int(placed)
    assert spans["env.fork"].count == spans["env.warmup"].count == 1
    if placed:
        pl = provisioner.place_predecessor(
            cache, cache.timeline(until=diff.pred.submit_time), diff.pred,
            cfg.interval)
        assert spans["env.replay"].count == int(case == "cascade-at-t0")
        assert provisioner.served(pl, diff.pred.submit_time) == \
            (case != "cascade-at-t0")
        if case == "start":
            assert pl.kind == "start"
        if case == "extension":
            assert cache.misses > 2     # the reach moved more than once
    for n in range(50):
        step = [env.step(int(n == 49)) for env in (diff, fork)]
        (o1, r1, d1, i1), (o2, r2, d2, i2) = step
        for k in o1:
            np.testing.assert_array_equal(o1[k], o2[k], err_msg=k)
        assert (r1, d1, i1) == (r2, d2, i2)
        if d1:
            break
    assert d1


# ------------------------------------------ frontier-bounded timeline
def test_bounded_timeline_answers_as_the_whole_trace(trace_cfg):
    """Below its reach a frontier-bounded timeline samples and places as
    the whole-trace timeline of the same replay does, and it certifies
    nothing at or past ``valid_until``."""
    jobs, cfg = trace_cfg
    cache = ReplayCheckpointCache(jobs, cfg.n_nodes)
    lo, hi = ProvisionEnv(jobs, cfg, seed=0)._t_start_range
    part = cache.timeline(until=lo + 0.3 * (hi - lo))
    reach = part.valid_until
    assert np.isfinite(reach) and part.open_ended
    whole = cache.timeline()
    assert whole is not part and whole.valid_until == float("inf")
    ts = np.linspace(lo - DAY, reach, 97)[:-1]
    a, b = part.sample_lanes(ts), whole.sample_lanes(ts)
    for f in a.__dataclass_fields__:
        np.testing.assert_array_equal(getattr(a, f), getattr(b, f), f)
    n_open = 0
    for t0 in np.linspace(lo, reach + 2 * DAY, 60):
        args = (float(t0), 1, cfg.sub_limit, cfg.sub_limit, 5_000_001,
                cfg.interval)
        p, w = part.place(*args), whole.place(*args)
        if t0 >= reach:
            assert p.kind == "fallback"
            continue
        assert p.t < reach
        if p.exhausted:
            n_open += 1
            assert p.kind == "cascade" and w.t >= p.t
        else:
            assert (p.kind, p.t, p.pass_pos, p.pass_size) == \
                (w.kind, w.t, w.pass_pos, w.pass_size)
    assert n_open > 0


def test_advanced_frontier_records_the_pristine_timeline(trace_cfg):
    """The frontier records from t=0 whatever forks moved it first: a
    cache forked ahead before its first ``timeline()`` holds the same
    background as a pristine drain — job arrays, start log, the passes
    that start jobs and every sample. (Passes that start nothing may
    fall at other replay boundaries.)"""
    jobs, cfg = trace_cfg
    pristine = ReplayCheckpointCache(jobs, cfg.n_nodes).timeline()
    cache = ReplayCheckpointCache(jobs, cfg.n_nodes)
    cache.fork_at(10.3 * DAY + 17.0)
    cache.fork_at(3.1 * DAY)
    moved = cache.timeline()
    for name in ("sub", "rt", "lim", "nn", "ids", "log_idx", "log_t",
                 "log_end", "first_start", "_rsnap", "_qsnap"):
        np.testing.assert_array_equal(getattr(pristine, name),
                                      getattr(moved, name), name)
    m, k = pristine.rec_nstart > 0, moved.rec_nstart > 0
    for name in ("rec_t", "rec_kind", "rec_free_entry", "rec_free_exit",
                 "rec_free_bf", "rec_shadow", "rec_spare", "rec_head",
                 "rec_nstart"):
        np.testing.assert_array_equal(getattr(pristine, name)[m],
                                      getattr(moved, name)[k], name)
    ts = np.linspace(jobs[0].submit_time, jobs[-1].submit_time, 101)
    a, b = pristine.sample_lanes(ts), moved.sample_lanes(ts)
    for f in a.__dataclass_fields__:
        np.testing.assert_array_equal(getattr(a, f), getattr(b, f), f)


def test_reset_inside_the_reach_does_not_rebuild(trace_cfg):
    """A scalar reset inside the recorded reach reuses the timeline (a
    hit) and moves no frontier; one past it rebuilds once."""
    jobs, cfg = trace_cfg
    cache = ReplayCheckpointCache(jobs, cfg.n_nodes)
    lo, hi = ProvisionEnv(jobs, cfg, seed=0)._t_start_range
    make_env(jobs, cfg, seed=1, cache=cache).reset(
        t_start=lo + 0.5 * (hi - lo))
    tl, misses = cache._timeline, cache.misses
    assert np.isfinite(tl.valid_until)
    hits = cache.hits
    make_env(jobs, cfg, seed=2, cache=cache).reset(
        t_start=lo + 0.3 * (hi - lo))
    assert cache._timeline is tl and cache.misses == misses
    assert cache.hits > hits
    make_env(jobs, cfg, seed=3, cache=cache).reset(t_start=tl.valid_until)
    assert cache._timeline is not tl and cache.misses > misses
