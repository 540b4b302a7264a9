"""The float8 control against the configurations' limits, at the
configured widths and depth with two experts (a size the CPU holds):
the bfloat16 program stays inside every limit, the control falls outside
one. The same comparison runs at the cells' own size on the chip in
``bench/control.py``."""
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

import check
import reference
import system

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _config(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        c = json.load(f)
    c["agent"].update(n_experts=min(c["agent"]["n_experts"], 2))
    c["world"]["trace_months"] = 1
    return c


@pytest.fixture(scope="module")
def observations():
    """32 observations of 8 lanes of the V100 cluster at history 144."""
    from repro.core.baselines import ReactivePolicy
    from repro.sim import make_vector_env
    c = _config("v100-medium-moe-dqn")
    trace, cfg, cache = system.build_world(c)
    venv = make_vector_env(trace, cfg, 8, seed=11, cache=cache)
    obs = venv.reset()
    mats = [obs["matrix"].copy()]
    for _ in range(3):
        obs, *_ = venv.step(ReactivePolicy().act_batch(obs))
        mats.append(obs["matrix"].copy())
    return np.concatenate(mats)


@pytest.mark.parametrize("name", ["v100-medium-moe-dqn"])
def test_control_fails_where_the_program_passes(name, observations):
    c = _config(name)
    a = c["agent"]
    learner, attr = system.build_learner(a, 0)
    out = np.asarray(getattr(learner, attr)(learner.params,
                                            jnp.asarray(observations)))
    sample = check.Reservoir(len(observations), np.random.default_rng(0))
    sample.offer(observations, out, out.argmax(-1))
    head = system.head(a)
    w = reference.init_weights(a)
    prog = check.forward_numbers(a, sample, head, weights=w)
    ctl = check.forward_numbers(a, sample, head, "float8", weights=w)
    limits = c["limits"]
    assert all(prog[k] <= limits[k] for k in limits), (prog, limits)
    assert any(ctl[k] > limits[k] for k in limits), (ctl, limits)
