"""Policy gradient (REINFORCE) for the provisioner (§2.3, Eqs. 5-6).

The P-head outputs submit/no-submit probabilities; actions are sampled
(non-deterministic policy, §4.4). The Monte-Carlo gradient uses whole
episodes with the shaped episode return (Eq. 8) and a running-mean
baseline for variance reduction.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import telemetry
from repro.train.optimizer import adamw_update, init_opt_state
from .dqn import learner_opt_config
from .foundation import FoundationConfig, init_foundation, policy_logits


@dataclasses.dataclass
class PGConfig:
    lr: float = 1e-4
    entropy_coef: float = 0.01
    baseline_momentum: float = 0.9


def make_update(fc: FoundationConfig, pc: PGConfig):
    """The pure REINFORCE update ``(params, opt_state, states, actions,
    advantage, mask) -> (params, opt_state, loss)`` that ``PGLearner``
    jits."""
    ocfg = learner_opt_config(pc.lr)

    def loss_fn(params, states, actions, advantage, mask):
        logits = policy_logits(params, fc, states)               # (T,2)
        logp = jax.nn.log_softmax(logits, -1)
        lp_a = jnp.take_along_axis(logp, actions[:, None], 1)[:, 0]
        denom = jnp.maximum(mask.sum(), 1.0)
        entropy = (-jnp.sum(jnp.exp(logp) * logp, -1) * mask).sum() / denom
        return (-(lp_a * advantage * mask).sum() / denom
                - pc.entropy_coef * entropy)

    def pg_update(params, opt_state, states, actions, advantage, mask):
        with jax.named_scope("pg_update"):
            loss, grads = jax.value_and_grad(loss_fn)(
                params, states, actions, advantage, mask)
            params, opt_state, _ = adamw_update(grads, params, opt_state,
                                                ocfg)
        return params, opt_state, loss

    return pg_update


class PGLearner:
    def __init__(self, fc: FoundationConfig, pc: PGConfig, seed: int = 0,
                 params: Dict = None):
        self.fc, self.pc = fc, pc
        key = jax.random.PRNGKey(seed)
        self.params = params if params is not None else init_foundation(key, fc)
        self.opt_state = init_opt_state(self.params,
                                        learner_opt_config(pc.lr))
        self.rng = np.random.default_rng(seed)
        self.baseline = 0.0
        self._update = jax.jit(make_update(fc, pc))
        self._logits_fn = jax.jit(lambda p, s: policy_logits(p, self.fc, s))

    # ----------------------------------------------------------- serving
    def act(self, state_matrix: np.ndarray, explore: bool = True) -> int:
        """Sample from the output binomial distribution (§4.4). B=1 view
        of ``act_batch`` — one code path serves both."""
        return int(self.act_batch(state_matrix[None], explore=explore)[0])

    def act_batch(self, state_matrices: np.ndarray,
                  explore: bool = True) -> np.ndarray:
        """Vectorized sampling over a (B, k, 40) stack -> (B,) actions.
        The host waits once, for the probabilities' copy."""
        with telemetry.span("forward.launch"):
            logits = self._logits_fn(self.params,
                                     jnp.asarray(state_matrices))
            p = jax.nn.softmax(logits, -1)
        with telemetry.span("forward.wait"):
            p = np.asarray(p)
        with telemetry.span("forward.fetch"):
            if explore:
                u = self.rng.random(len(p))
                return (u < p[:, 1]).astype(np.int64)
            return np.argmax(p, axis=-1).astype(np.int64)

    # ----------------------------------------------------------- learning
    def train_on_episode(self, states: np.ndarray, actions: np.ndarray,
                         episode_return: float, pad_to: int = 32) -> float:
        """states: (T, k, 40); actions: (T,); the shaped return credits
        every action of the trajectory (Eq. 6 with r(tau)). Episodes are
        padded to multiples of ``pad_to`` so the jitted update doesn't
        retrace on every new episode length."""
        self.baseline = (self.pc.baseline_momentum * self.baseline
                         + (1 - self.pc.baseline_momentum) * episode_return)
        adv = episode_return - self.baseline
        T = len(actions)
        Tp = max(-(-T // pad_to) * pad_to, pad_to)
        sp = np.zeros((Tp,) + states.shape[1:], np.float32)
        sp[:T] = states
        ap = np.zeros((Tp,), np.int32)
        ap[:T] = actions
        mask = np.zeros((Tp,), np.float32)
        mask[:T] = 1.0
        self.params, self.opt_state, loss = self._update(
            self.params, self.opt_state, jnp.asarray(sp), jnp.asarray(ap),
            jnp.full((Tp,), adv, jnp.float32), jnp.asarray(mask))
        return float(loss)
