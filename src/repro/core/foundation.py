"""Dual-head foundation models (§4.6-4.7, Figs. 5-6), pure JAX.

* ``transformer`` trunk: per-snapshot embedding of the 40 state variables
  (+ the ordinal action variable broadcast to every snapshot token), learned
  positions, bidirectional transformer encoder (built on the same
  repro.models substrate the payload archs use), mean-pool.
* V-head: trunk -> scalar Q(s, a).
* P-head: trunk (action variable zeroed) -> 2-way action logits.
* ``moe`` trunk (Eq. 7): E expert transformers under a *dense* softmax
  gate; Q-values / logits are the gate-weighted average of per-expert head
  outputs. Experts specialize temporally (§4.7) via the gate's time
  feature and per-expert sample weighting during offline pretraining.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs import mirage_agent
from repro.models import transformer as tf
from repro.models.common import ModelConfig
from repro.models.layers import dense_init
from .state import STATE_DIM


@dataclasses.dataclass(frozen=True)
class FoundationConfig:
    kind: str = "transformer"        # transformer | moe
    n_experts: int = mirage_agent.N_EXPERTS
    history: int = 144
    trunk: ModelConfig = mirage_agent.CONFIG
    gate_time_feature: bool = True   # gate sees the episode's time position
    gate_top1: bool = False          # §4.7 ablation: sparse top-1 gating
                                     # (paper found it inferior to the dense
                                     # weighted average; kept for the bench)

    def reduced(self) -> "FoundationConfig":
        return dataclasses.replace(self, trunk=mirage_agent.SMOKE, history=24,
                                   n_experts=4)


def _init_trunk(key, fc: FoundationConfig) -> Dict:
    cfg = fc.trunk
    ks = jax.random.split(key, 4)
    return {
        "embed_in": dense_init(ks[0], STATE_DIM + 1, cfg.d_model, jnp.float32),
        "pos": jax.random.normal(ks[1], (fc.history, cfg.d_model),
                                 jnp.float32) * 0.02,
        "trunk": tf.init(ks[2], cfg),
        "v_head": dense_init(ks[3], cfg.d_model, 1, jnp.float32),
        "p_head": dense_init(jax.random.fold_in(ks[3], 1), cfg.d_model, 2,
                             jnp.float32),
    }


def init_foundation(key, fc: FoundationConfig) -> Dict:
    if fc.kind == "transformer":
        return _init_trunk(key, fc)
    ks = jax.random.split(key, fc.n_experts + 1)
    experts = [_init_trunk(ks[i], fc) for i in range(fc.n_experts)]
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *experts)
    gate_in = STATE_DIM + (1 if fc.gate_time_feature else 0)
    return {"experts": stacked,
            "gate": dense_init(ks[-1], gate_in, fc.n_experts, jnp.float32)}


def _trunk_apply(params: Dict, fc: FoundationConfig, states: jnp.ndarray,
                 action: jnp.ndarray) -> jnp.ndarray:
    """states: (B, k, 40); action: (B,) in {-1, 0, +1}. Returns (B, d)."""
    cfg = fc.trunk
    B, k, m = states.shape
    act = jnp.broadcast_to(action[:, None, None].astype(jnp.float32),
                           (B, k, 1))
    x = jnp.concatenate([states, act], axis=-1)
    h = jnp.einsum("bkm,md->bkd", x, params["embed_in"]) + params["pos"][None]
    pos = jnp.broadcast_to(jnp.arange(k)[None], (B, k))
    h, _, _ = tf.apply_trunk(params["trunk"], cfg, h.astype(cfg.cdtype), pos)
    return h.mean(axis=1).astype(jnp.float32)


def _heads(params: Dict, feats: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    q = jnp.einsum("bd,do->bo", feats, params["v_head"])[:, 0]
    logits = jnp.einsum("bd,do->bo", feats, params["p_head"])
    return q, logits


def _gate(params: Dict, fc: FoundationConfig, states: jnp.ndarray,
          time_pos: Optional[jnp.ndarray]) -> jnp.ndarray:
    """Dense softmax gate over experts (Eq. 7). Gate input: current snapshot
    (+ normalized time position for temporal specialization)."""
    cur = states[:, -1, :]
    if fc.gate_time_feature:
        tp = (time_pos if time_pos is not None
              else jnp.zeros((states.shape[0],), jnp.float32))
        cur = jnp.concatenate([cur, tp[:, None]], axis=-1)
    g = jax.nn.softmax(jnp.einsum("bm,me->be", cur, params["gate"]), -1)
    if fc.gate_top1:
        # straight-through top-1: hard routing fwd, soft gradient
        hard = jax.nn.one_hot(jnp.argmax(g, -1), g.shape[-1], dtype=g.dtype)
        g = hard + g - jax.lax.stop_gradient(g)
    return g


def q_values(params: Dict, fc: FoundationConfig, states: jnp.ndarray,
             time_pos: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Q(s, a) for both actions. Returns (B, 2): [:,0]=no-submit, [:,1]=submit.
    The two trunk passes carry the names ``action_wait`` and
    ``action_submit`` in the device's trace."""
    B = states.shape[0]

    def both(trunk_params):
        qs = []
        for name, a in (("action_wait", -1.0), ("action_submit", 1.0)):
            with jax.named_scope(name):
                feats = _trunk_apply(trunk_params, fc,
                                     states, jnp.full((B,), a))
                qs.append(_heads(trunk_params, feats)[0])
        return jnp.stack(qs, axis=-1)                      # (B, 2)

    with jax.named_scope("q_values"):
        if fc.kind == "transformer":
            return both(params)
        per_exp = jax.vmap(both, in_axes=(0,))(params["experts"])  # (E,B,2)
        g = _gate(params, fc, states, time_pos)                     # (B, E)
        return jnp.einsum("ebq,be->bq", per_exp, g)


def policy_logits(params: Dict, fc: FoundationConfig, states: jnp.ndarray,
                  time_pos: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """P-head action logits (B, 2); action input is the 0 placeholder."""
    B = states.shape[0]

    def one(trunk_params):
        feats = _trunk_apply(trunk_params, fc, states, jnp.zeros((B,)))
        return _heads(trunk_params, feats)[1]

    with jax.named_scope("policy_logits"):
        if fc.kind == "transformer":
            return one(params)
        per_exp = jax.vmap(one, in_axes=(0,))(params["experts"])  # (E,B,2)
        g = _gate(params, fc, states, time_pos)
        return jnp.einsum("ebq,be->bq", per_exp, g)


def reward_prediction(params: Dict, fc: FoundationConfig, states: jnp.ndarray,
                      time_pos: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Offline-pretraining output: predicted reward of submitting now
    (= Q(s, submit)); (B,)."""
    return q_values(params, fc, states, time_pos)[:, 1]
