"""Compile the agent's jitted programs for a described v5e chip.

Nothing runs: each test lowers one program at the configured width
(configs/mirage_agent.py, 144-snapshot history, 10 experts) and compiles it
with the TPU compiler for a chip that is described, not attached. That
catches what the chip's compiler refuses, and programs that do not fit its
16 GiB of HBM, without chip time. The topology is described inside a fixture,
never at import, so only the test worker that runs this file loads libtpu.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.agent import make_pretrain_step
from repro.core.dqn import DQNConfig, learner_opt_config
from repro.core.dqn import make_update as make_dqn_update
from repro.core.foundation import FoundationConfig, init_foundation, q_values
from repro.core.pg import PGConfig
from repro.core.pg import make_update as make_pg_update
from repro.core.state import STATE_DIM
from repro.train.optimizer import OptimizerConfig, init_opt_state

GiB = 2 ** 30
HISTORY = 144
FC = FoundationConfig(kind="moe", history=HISTORY)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off meanwhile
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def params(one_chip):
    shapes = jax.eval_shape(lambda: init_foundation(jax.random.PRNGKey(0),
                                                    FC))
    return _on(one_chip, shapes)


def _on(sharding, tree):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _states(sharding, b):
    return jax.ShapeDtypeStruct((b, HISTORY, STATE_DIM), jnp.float32,
                                sharding=sharding)


def _vec(sharding, b, dtype=jnp.float32):
    return jax.ShapeDtypeStruct((b,), dtype, sharding=sharding)


def _opt_state(sharding, params, ocfg):
    return _on(sharding, jax.eval_shape(lambda p: init_opt_state(p, ocfg),
                                        params))


def _footprint(compiled) -> int:
    m = compiled.memory_analysis()
    return m.temp_size_in_bytes + m.argument_size_in_bytes


def test_moe_q_values_forward_compiles(one_chip, params):
    compiled = jax.jit(lambda p, s: q_values(p, FC, s)).lower(
        params, _states(one_chip, 32)).compile()
    assert _footprint(compiled) < 8 * GiB


def test_moe_dqn_update_fits_one_chip(one_chip, params):
    """Without the trunk's remat this update needs ~19 GiB at batch 32."""
    B = 32
    dc = DQNConfig()
    batch = {"s": _states(one_chip, B), "a": _vec(one_chip, B, jnp.int32),
             "r": _vec(one_chip, B), "s2": _states(one_chip, B),
             "done": _vec(one_chip, B, jnp.bool_)}
    opt = _opt_state(one_chip, params, learner_opt_config(dc.lr))
    compiled = jax.jit(make_dqn_update(FC, dc)).lower(
        params, params, opt, batch).compile()
    assert _footprint(compiled) < 8 * GiB


def test_moe_pg_update_compiles(one_chip, params):
    T = 32
    pc = PGConfig()
    opt = _opt_state(one_chip, params, learner_opt_config(pc.lr))
    compiled = jax.jit(make_pg_update(FC, pc)).lower(
        params, opt, _states(one_chip, T), _vec(one_chip, T, jnp.int32),
        _vec(one_chip, T), _vec(one_chip, T)).compile()
    assert _footprint(compiled) < 8 * GiB


def test_moe_pretrain_step_compiles(one_chip, params):
    B = 16
    ocfg = OptimizerConfig(lr=3e-4, warmup_steps=10, total_steps=100,
                           weight_decay=0.0)
    opt = _opt_state(one_chip, params, ocfg)
    compiled = jax.jit(make_pretrain_step(FC, ocfg)).lower(
        params, opt, _states(one_chip, B), _vec(one_chip, B),
        _vec(one_chip, B)).compile()
    assert _footprint(compiled) < 8 * GiB
