"""The P-head limit of ``rtx-medium-transformer-pg`` against precision.

``answer_err`` is in logit units here (the P-head's two action logits),
not in Q-value units. At the configured width (on 32 observations of the
RTX cluster, a size the CPU holds) the bfloat16 program stays inside the
configuration's limit and the float8 control falls outside it; at the
``--smoke`` width, with the limit scaled by the ratio of the reference's
largest logit there to its largest at the configured width, the float32
program stays inside and the float8 control falls outside. So the limit
would catch a lower precision than the configuration states."""
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

import check
import reference
import system

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = "rtx-medium-transformer-pg"


def _config():
    with open(os.path.join(BENCH, "configs", NAME + ".json")) as f:
        c = json.load(f)
    c["world"]["trace_months"] = 1
    return c


@pytest.fixture(scope="module")
def observations():
    """32 observations of 8 lanes of the RTX cluster at history 144."""
    from repro.core.baselines import ReactivePolicy
    from repro.sim import make_vector_env
    trace, cfg, cache = system.build_world(_config())
    venv = make_vector_env(trace, cfg, 8, seed=11, cache=cache)
    obs = venv.reset()
    mats = [obs["matrix"].copy()]
    for _ in range(3):
        obs, *_ = venv.step(ReactivePolicy().act_batch(obs))
        mats.append(obs["matrix"].copy())
    return np.concatenate(mats)


def _numbers(a, obs):
    """(program's, float8 control's, reference's largest |logit|)."""
    learner, attr = system.build_learner(a, 0)
    out = np.asarray(getattr(learner, attr)(learner.params,
                                            jnp.asarray(obs)))
    sample = check.Reservoir(len(obs), np.random.default_rng(0))
    sample.offer(obs, out, out.argmax(-1))
    w = reference.init_weights(a)
    scale = float(np.abs(reference.outputs(w, obs, "p")).max())
    return (check.forward_numbers(a, sample, "p", weights=w),
            check.forward_numbers(a, sample, "p", "float8", weights=w),
            scale)


@pytest.fixture(scope="module")
def configured(observations):
    c = _config()
    assert system.head(c["agent"]) == "p"
    return c, _numbers(c["agent"], observations)


def test_pg_control_fails_where_the_program_passes(configured):
    c, (prog, ctl, _) = configured
    limit = c["limits"]["answer_err"]
    assert prog["answer_err"] <= limit, (prog, limit)
    assert ctl["answer_err"] > limit, (ctl, limit)


def test_pg_scaled_limit_catches_float8_at_the_smoke_width(configured,
                                                           observations):
    from repro.configs import mirage_agent
    c, (_, _, scale) = configured
    s = mirage_agent.SMOKE
    a = dict(c["agent"], n_layers=s.n_layers, d_model=s.d_model,
             n_heads=s.n_heads, d_ff=s.d_ff, history=24,
             compute_dtype="float32")
    prog, ctl, smoke_scale = _numbers(a, observations[:, -24:])
    limit = c["limits"]["answer_err"] * smoke_scale / scale
    assert prog["answer_err"] <= limit, (prog, limit)
    assert ctl["answer_err"] > limit, (ctl, limit)
    # float32 end to end, the program differs from the reference by
    # summation order alone
    assert prog["answer_err"] <= 1e-5 * smoke_scale
