"""Deep Q-learning for the provisioner (§2.2, §4.9.2; Eqs. 2-4).

Online on-policy training with experience replay and ε-greedy exploration.
Two credit modes:

* ``paper_credit=True`` (default, Eq. 8): the observed outcome penalty is
  assigned to every action of the episode — Q regression toward the
  episode return (Monte-Carlo-style targets, no bootstrap).
* ``paper_credit=False``: standard one-step TD with a target network,
  ``R + γ·max_a' Q_target(s', a')``.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import telemetry
from repro.train.optimizer import OptimizerConfig, adamw_update, init_opt_state
from .foundation import FoundationConfig, init_foundation, q_values


@dataclasses.dataclass
class DQNConfig:
    gamma: float = 0.99
    epsilon: float = 0.1
    paper_credit: bool = True
    target_update_every: int = 50
    lr: float = 1e-4
    batch_size: int = 32


def learner_opt_config(lr: float) -> OptimizerConfig:
    """AdamW settings of the online learners (DQN and PG)."""
    return OptimizerConfig(lr=lr, warmup_steps=10, total_steps=100_000,
                           weight_decay=0.0, grad_clip=1.0)


def make_update(fc: FoundationConfig, dc: DQNConfig):
    """The pure DQN update ``(params, target_params, opt_state, batch) ->
    (params, opt_state, loss)`` that ``DQNLearner`` jits."""
    ocfg = learner_opt_config(dc.lr)

    def loss_fn(params, target_params, batch):
        q = q_values(params, fc, batch["s"])                     # (B,2)
        qa = jnp.take_along_axis(q, batch["a"][:, None], 1)[:, 0]
        if dc.paper_credit:
            target = batch["r"]
        else:
            q_next = q_values(target_params, fc, batch["s2"])
            target = batch["r"] + dc.gamma * jnp.max(q_next, -1) * (
                1.0 - batch["done"].astype(jnp.float32))
        target = jax.lax.stop_gradient(target)
        return jnp.mean(jnp.square(qa - target))

    def dqn_update(params, target_params, opt_state, batch):
        with jax.named_scope("dqn_update"):
            loss, grads = jax.value_and_grad(loss_fn)(params, target_params,
                                                      batch)
            params, opt_state, _ = adamw_update(grads, params, opt_state,
                                                ocfg)
        return params, opt_state, loss

    return dqn_update


class DQNLearner:
    def __init__(self, fc: FoundationConfig, dc: DQNConfig, seed: int = 0,
                 params: Dict = None):
        self.fc, self.dc = fc, dc
        key = jax.random.PRNGKey(seed)
        self.params = params if params is not None else init_foundation(key, fc)
        self.target_params = jax.tree.map(jnp.copy, self.params)
        self.opt_state = init_opt_state(self.params,
                                        learner_opt_config(dc.lr))
        self.rng = np.random.default_rng(seed)
        self._steps = 0
        self._update = jax.jit(make_update(fc, dc))
        self._q_fn = jax.jit(lambda p, s: q_values(p, self.fc, s))

    # ----------------------------------------------------------- serving
    def act(self, state_matrix: np.ndarray, explore: bool = True) -> int:
        """Deterministic policy (§4.4): submit iff Q(submit) > Q(no-submit);
        ε-greedy exploration during online training. B=1 view of
        ``act_batch`` — one code path serves both."""
        return int(self.act_batch(state_matrix[None], explore=explore)[0])

    def act_batch(self, state_matrices: np.ndarray,
                  explore: bool = True) -> np.ndarray:
        """Vectorized policy over a (B, k, 40) stack -> (B,) actions.
        One jitted forward serves the whole batch (the vector-env path).
        The host waits once, for the outputs' copy: waiting for the
        forward and then copying would cost it a second wake-up."""
        with telemetry.span("forward.launch"):
            q = self._q_fn(self.params, jnp.asarray(state_matrices))
        with telemetry.span("forward.wait"):
            q = np.asarray(q)
        with telemetry.span("forward.fetch"):
            a = np.argmax(q, axis=-1)
            if explore:
                b = len(a)
                flip = self.rng.random(b) < self.dc.epsilon
                a = np.where(flip, self.rng.integers(0, 2, b), a)
        return a.astype(np.int64)

    # ----------------------------------------------------------- learning
    def train_on(self, batch: Dict[str, np.ndarray]) -> float:
        jb = {k: jnp.asarray(v) for k, v in batch.items()}
        self.params, self.opt_state, loss = self._update(
            self.params, self.target_params, self.opt_state, jb)
        self._steps += 1
        if self._steps % self.dc.target_update_every == 0:
            self.target_params = jax.tree.map(jnp.copy, self.params)
        return float(loss)
