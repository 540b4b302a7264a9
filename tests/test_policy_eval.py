"""Batched Policy protocol + vectorized evaluation (ISSUE 5).

The contract: ``evaluate_batch`` with B lanes produces an EvalResult
identical to B single-lane (B=1) evaluations at the same seeds and start
instants, for every method in ALL_METHODS — lane ``i`` of the vector env
is bit-identical to a scalar env seeded ``seed + i``, and every policy
acts through one batched code path. (The scalar ``evaluate`` shim and
the pre-protocol act-only adapter were retired after their one-release
window; B=1 ``evaluate_batch`` is the scalar path now.)
"""
import dataclasses

import numpy as np
import pytest

from repro.core import (DQNConfig, DQNLearner, EnvConfig, FoundationConfig,
                        LearnerPolicy, PGConfig, PGLearner, ProvisionEnv,
                        ReactivePolicy, ReplayCheckpointCache, TreePolicy,
                        VectorProvisionEnv, evaluate_batch)
from repro.core.agent import ALL_METHODS
from repro.core.baselines import AvgWaitPolicy
from repro.core.trees import GradientBoosting, RandomForest
from repro.sim import synthesize_trace
from repro.sim.trace import V100

HOUR = 3600.0
HISTORY = 12
SEED = 100
B = 3
WARM_WAITS = [2 * HOUR, 5 * HOUR, HOUR]


@pytest.fixture(scope="module")
def world():
    jobs = synthesize_trace(V100, months=1, seed=5, load_scale=1.0)
    cfg = EnvConfig(n_nodes=V100.n_nodes, history=HISTORY, interval=1800.0)
    cache = ReplayCheckpointCache(jobs, cfg.n_nodes)
    return jobs, cfg, cache


@pytest.fixture(scope="module")
def stateless_policies():
    """Deterministic, stateless-under-evaluation policies, built once:
    trees fit on random summary blocks, learners init-only (explore off
    during evaluation, so no RNG is consumed)."""
    rng = np.random.default_rng(0)
    X = rng.normal(size=(48, 4 * 40)).astype(np.float32)
    y = np.abs(rng.normal(size=48)) * HOUR
    out = {"reactive": ReactivePolicy()}
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


def make_policy(method, stateless):
    if method == "avg":
        pol = AvgWaitPolicy()
        pol.waits = WARM_WAITS           # same warm state every instance
        return pol
    return stateless[method]


@pytest.mark.parametrize("method", ALL_METHODS)
def test_evaluate_batch_matches_scalar(world, stateless_policies, method):
    jobs, cfg, cache = world
    venv = VectorProvisionEnv(jobs, cfg, B, seed=SEED, cache=cache)
    lo, hi = venv._t_start_range
    t0s = np.random.default_rng(7).uniform(lo, hi, B)

    bres = evaluate_batch(venv, make_policy(method, stateless_policies),
                          t_starts=t0s)
    waits, ints, ovls = [], [], []
    for i in range(B):
        venv1 = VectorProvisionEnv(jobs, cfg, 1, seed=SEED + i, cache=cache)
        sres = evaluate_batch(venv1, make_policy(method, stateless_policies),
                              t_starts=[t0s[i]])
        waits += sres.waits_h
        ints += sres.interruptions_h
        ovls += sres.overlaps_h

    assert bres.method == method
    assert bres.waits_h == waits                       # exact, lane order
    assert sorted(bres.interruptions_h) == sorted(ints)
    assert sorted(bres.overlaps_h) == sorted(ovls)
    assert len(bres.waits_h) == B


def test_evaluate_batch_tail_chunk(world, stateless_policies):
    """episodes > B runs a tail chunk on a tail-sized env sharing the
    cache; accounting still one row per episode."""
    jobs, cfg, cache = world
    venv = VectorProvisionEnv(jobs, cfg, 2, seed=SEED, cache=cache)
    res = evaluate_batch(venv, stateless_policies["reactive"], episodes=3,
                         seed=7)
    assert res.summary()["n_episodes"] == 3


def test_evaluate_b1_observe_cadence(world):
    """A B=1 env must feed the avg policy one episode at a time (each
    episode is its own chunk, the legacy observe_wait cadence): after k
    episodes the window holds the warm start plus k observed waits."""
    jobs, cfg, cache = world
    venv = VectorProvisionEnv(jobs, cfg, 1, seed=SEED, cache=cache)
    pol = AvgWaitPolicy()
    pol.waits = WARM_WAITS
    res = evaluate_batch(venv, pol, episodes=2, seed=7)
    assert len(pol.waits) == len(WARM_WAITS) + 2
    assert pol.waits[-2:] == [w * HOUR for w in res.waits_h]


def test_avg_wait_deque_matches_list_window():
    """O(1) deque + running sum == the legacy list-slice window."""
    rng = np.random.default_rng(3)
    pol = AvgWaitPolicy(window=5)
    ref = []
    for w in rng.uniform(0, 10 * HOUR, 23):
        pol.observe_wait(float(w))
        ref = (ref + [float(w)])[-5:]
        assert pol.waits == ref
        assert pol.t_avg == pytest.approx(float(np.mean(ref)))


def test_scalar_env_cache_bit_identical(world):
    """ProvisionEnv(cache=...) resets are served from the shared replay
    (its timeline and checkpoints) instead of re-replaying the trace
    head — observations and outcomes unchanged."""
    jobs, cfg, cache = world
    cold = ProvisionEnv(jobs, cfg, seed=3)
    warm = ProvisionEnv(jobs, cfg, seed=3, cache=cache)
    hits0 = cache.hits + cache.misses
    obs_c = cold.reset()
    obs_w = warm.reset()
    assert cache.hits + cache.misses > hits0
    np.testing.assert_array_equal(obs_c["matrix"], obs_w["matrix"])
    done_c = done_w = False
    while not (done_c or done_w):
        _, rc, done_c, ic = cold.step(1)
        _, rw, done_w, iw = warm.step(1)
    assert done_c and done_w and rc == rw
    assert ic["kind"] == iw["kind"] and ic["wait_s"] == iw["wait_s"]


def test_evaluate_cacheless_matches_cached(world, stateless_policies):
    """A checkpoint-free stand-in cache (interval=inf: per-episode
    trace-head replays, the legacy scalar cost model) must produce
    results identical to a warm checkpointed cache — checkpoint forks
    are bit-identical to fresh replays."""
    jobs, cfg, cache = world
    pol = stateless_policies["reactive"]
    cold = ReplayCheckpointCache(jobs, cfg.n_nodes, interval=float("inf"))
    r_cold = evaluate_batch(
        VectorProvisionEnv(jobs, cfg, 1, seed=SEED, cache=cold), pol,
        episodes=2, seed=7)
    r_warm = evaluate_batch(
        VectorProvisionEnv(jobs, cfg, 1, seed=SEED, cache=cache), pol,
        episodes=2, seed=7)
    assert r_cold.waits_h == r_warm.waits_h
    assert r_cold.interruptions_h == r_warm.interruptions_h
    assert r_cold.overlaps_h == r_warm.overlaps_h


def test_offline_samples_reuse_env_cache(world):
    """collect_offline_samples must fork from an attached env.cache
    instead of building (and re-replaying) its own."""
    from repro.core.provisioner import collect_offline_samples
    jobs, cfg, cache = world
    env = ProvisionEnv(jobs, cfg, seed=0, cache=cache)
    before = cache.hits + cache.misses
    samples = collect_offline_samples(env, n_episodes=1, n_points=2, seed=0)
    assert len(samples) == 2
    assert cache.hits + cache.misses > before


def test_build_policy_pg_passes_seed(world, monkeypatch):
    """Regression: the PG online-training call used to drop seed=."""
    import repro.core.agent as agent_mod
    jobs, cfg, cache = world
    seen = {}

    def fake_train(env, learner, episodes=30, seed=0, batch=None):
        seen["seed"] = seed
        return []

    monkeypatch.setattr(agent_mod, "train_online_pg", fake_train)
    rng = np.random.default_rng(0)
    samples = [{"matrix": rng.normal(size=(HISTORY, 40)).astype(np.float32),
                "summary": rng.normal(size=4 * 40).astype(np.float32),
                "reward": -1.0, "wait_s": HOUR, "time_pos": 0.5}
               for _ in range(4)]
    env = ProvisionEnv(jobs, cfg, seed=0, cache=cache)
    agent_mod.build_policy("transformer+pg", env, offline_samples=samples,
                           pretrain_epochs=1, history=HISTORY, reduced=True,
                           seed=11)
    assert seen["seed"] == 11


def test_scenario_registry():
    from repro.sim import (CHAIN_SHAPES, CO_TENANTS, FAULT_PROFILES,
                           LOAD_LEVELS, SCENARIOS, get_scenario,
                           iter_scenarios)
    # every cell has a /co<N> co-simulation twin (the trailing x2)
    assert len(SCENARIOS) == (3 * len(LOAD_LEVELS) * len(CHAIN_SHAPES)
                              * (1 + len(FAULT_PROFILES)) * 2)
    s = get_scenario("V100", "heavy", "single")
    assert s is get_scenario("V100/heavy/single")
    assert s is get_scenario("V100", "heavy", 1)      # node-count lookup
    assert s.load_scale == LOAD_LEVELS["heavy"]
    assert s.chain_nodes == 1
    assert s.fault == "" and s.fault_spec is None
    multi = list(iter_scenarios(clusters=["RTX"], chains=["multi"],
                                faults=[""]))
    assert [m.name for m in multi] == ["RTX/light/multi", "RTX/medium/multi",
                                       "RTX/heavy/multi"]
    cfg = s.env_config(history=12, interval=1800.0)
    assert cfg.n_nodes == s.profile.n_nodes and cfg.history == 12
    # arbitrary chain sizes: registered shapes resolve to their cell,
    # unregistered ones get an ad-hoc variant
    assert s.with_chain_nodes(8) is get_scenario("V100", "heavy", "multi")
    ad_hoc = s.with_chain_nodes(2)
    assert ad_hoc.name == "V100/heavy/2n" and ad_hoc.chain_nodes == 2
    assert ad_hoc.env_config().chain_nodes == 2
    # faulted cells: every fault-free cell has a named faulted variant
    f = get_scenario("V100", "heavy", "single", fault="faulty")
    assert f is get_scenario("V100/heavy/single/faulty")
    assert f.fault == "faulty" and f.fault_spec is FAULT_PROFILES["faulty"]
    assert f.with_chain_nodes(8) is get_scenario("V100/heavy/multi/faulty")
    faulted = list(iter_scenarios(clusters=["RTX"], chains=["multi"],
                                  faults=["faulty"]))
    assert [m.name for m in faulted] == [
        "RTX/light/multi/faulty", "RTX/medium/multi/faulty",
        "RTX/heavy/multi/faulty"]
    # co-simulation cells: a registered /co<N> twin per cell, an ad-hoc
    # variant for any other tenant count, and with_tenants round-trips
    co = get_scenario("V100", "heavy", "single", tenants=CO_TENANTS)
    assert co is get_scenario(f"V100/heavy/single/co{CO_TENANTS}")
    assert co.tenants == CO_TENANTS and co.load_scale == s.load_scale
    assert s.with_tenants(CO_TENANTS) is co
    assert co.with_tenants(1) is s and s.with_tenants(1) is s
    ad_hoc_co = get_scenario("V100/heavy/single/co1024")
    assert ad_hoc_co.tenants == 1024
    assert ad_hoc_co.name == "V100/heavy/single/co1024"
    assert co.with_chain_nodes(8).tenants == CO_TENANTS
    # the tenants filter defaults to the solo grid (sweep stability)
    solo_only = list(iter_scenarios(clusters=["RTX"], chains=["multi"],
                                    faults=[""]))
    assert all(m.tenants == 1 for m in solo_only)
    co_cells = list(iter_scenarios(clusters=["RTX"], chains=["multi"],
                                   faults=[""], tenants=[CO_TENANTS]))
    assert [m.name for m in co_cells] == [
        f"RTX/light/multi/co{CO_TENANTS}",
        f"RTX/medium/multi/co{CO_TENANTS}",
        f"RTX/heavy/multi/co{CO_TENANTS}"]
