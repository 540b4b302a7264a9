"""The system under test, built from a configuration.

The world (trace, fault plan, checkpoint cache) comes from the
configuration's fixed ``trace_seed``, and the agent's weights from its fixed
``weight_key`` in one jitted ``init_foundation`` call, as one deployment's
history and one deployed checkpoint would. A run's ``--seed`` changes only
the order in which the traffic visits the configuration's start instants,
and the decisions sampled for the comparison: every seed does the same
work.
"""
from __future__ import annotations

from typing import Dict

DAY = 86400.0


def scenario(config: Dict):
    from repro.sim import get_scenario
    w = config["world"]
    return get_scenario(f"{w['cluster']}/{w['load']}/{w['chain']}")


def build_world(config: Dict):
    """(trace, env config, checkpoint cache) of the configuration's
    cluster: a trace of ``trace_months`` drawn from ``trace_seed``."""
    from repro.core import ReplayCheckpointCache
    a, w = config["agent"], config["world"]
    sc = scenario(config)
    stated = (w["nodes"], w["jobs_per_month"], w["load_scale"],
              w["chain_nodes"])
    built = (sc.profile.n_nodes, sc.profile.jobs_per_month, sc.load_scale,
             sc.chain_nodes)
    if stated != built:
        raise ValueError(f"configuration states {stated}, the program's "
                         f"scenario has {built}")
    trace = sc.make_trace(months=w["trace_months"], seed=w["trace_seed"])
    cfg = sc.env_config(a["history"], float(a["interval_s"]),
                        faults=sc.make_fault_plan(trace, w["trace_seed"]))
    cache = ReplayCheckpointCache(trace, cfg.n_nodes, faults=cfg.faults)
    return trace, cfg, cache


def foundation_config(a: Dict):
    from repro.configs import mirage_agent
    from repro.core.foundation import FoundationConfig
    trunk = mirage_agent.CONFIG.replace(
        n_layers=a["n_layers"], d_model=a["d_model"], n_heads=a["n_heads"],
        n_kv_heads=a["n_heads"], d_ff=a["d_ff"],
        compute_dtype=a["compute_dtype"], remat=a["remat"])
    return FoundationConfig(kind=a["kind"], n_experts=a["n_experts"],
                            history=a["history"], trunk=trunk,
                            gate_time_feature=a["gate_time_feature"])


def head(a: Dict) -> str:
    """``q`` for a DQN agent (V-head), ``p`` for a policy-gradient one."""
    return "q" if a["learner"] == "dqn" else "p"


def build_learner(a: Dict, seed: int):
    """The configuration's learner with weights from its fixed key. Returns
    (learner, the name of its jitted forward attribute)."""
    import jax
    from repro.core import DQNConfig, DQNLearner, PGConfig, PGLearner
    from repro.core.foundation import init_foundation
    fc = foundation_config(a)
    params = jax.jit(lambda k: init_foundation(k, fc))(
        jax.random.PRNGKey(a["weight_key"]))
    if a["learner"] == "dqn":
        return DQNLearner(fc, DQNConfig(), seed=seed, params=params), "_q_fn"
    return PGLearner(fc, PGConfig(), seed=seed, params=params), "_logits_fn"


def capture_outputs(learner, attr: str) -> list:
    """Wrap the learner's jitted forward (``attr``) so that its latest
    output stays readable in the returned one-slot list: the Q-values or
    logits the timed path computed, for the comparison."""
    fn = getattr(learner, attr)
    out = [None]

    def forward(params, states):
        out[0] = fn(params, states)
        return out[0]

    setattr(learner, attr, forward)
    return out
