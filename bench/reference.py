"""Plain float32 reference of the Mirage agent (paper §4.6-4.7, Figs. 5-6).

Written from the description, independent of ``repro``: it imports nothing
of the program and makes its own weights from the configuration's fixed key,
drawing them in the order the published initialiser draws them (fan-in
scaled truncated normals, learned positions at 0.02), so that the same key
gives the same agent.

The agent reads a (k, 40) state matrix: each of the k snapshots is embedded
with the ordinal action variable appended (-1 no-submit, +1 submit, 0 for
the P-head), a learned position is added, and a bidirectional pre-norm
transformer encoder (LayerNorm, multi-head softmax attention, tanh-GELU
MLP, final LayerNorm) is mean-pooled. The V-head gives Q(s, a); the P-head
gives two action logits. The ``moe`` kind averages E such experts under a
dense softmax gate over the current snapshot and the time position (Eq. 7);
the served path passes time position 0.

``matmul`` selects the precision of every matrix product: ``"float32"`` at
the highest precision is the reference; ``"float8"`` rounds both operands
to float8_e4m3 first and is the lower-precision control.
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

STATE_DIM = 40
LN_EPS = 1e-6


# ------------------------------------------------------------------ weights
def _dense(key, n_in: int, out_shape) -> jnp.ndarray:
    shape = (n_in,) + tuple(out_shape)
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
            * (1.0 / math.sqrt(n_in)))


def _layer(key, d: int, heads: int, d_ff: int) -> Dict:
    k_attn, k_mlp, _, _ = jax.random.split(key, 4)
    kq, kk, kv, ko, _, _ = jax.random.split(k_attn, 6)
    k_in, _, k_out = jax.random.split(k_mlp, 3)
    hd = d // heads
    one, zero = jnp.ones((d,), jnp.float32), jnp.zeros((d,), jnp.float32)
    return {"ln1": (one, zero), "ln2": (one, zero),
            "wq": _dense(kq, d, (heads, hd)), "wk": _dense(kk, d, (heads, hd)),
            "wv": _dense(kv, d, (heads, hd)),
            "wo": _dense(ko, heads * hd, (d,)).reshape(heads, hd, d),
            "w_in": _dense(k_in, d, (d_ff,)), "w_out": _dense(k_out, d_ff, (d,))}


def _expert(key, a: Dict) -> Dict:
    d, L = a["d_model"], a["n_layers"]
    ks = jax.random.split(key, 4)
    # the encoder stack draws from the fourth of its own four-way split
    # (slots for embedding and output head it does not use come first)
    stack_key = jax.random.split(jax.random.split(ks[2], 4)[3], 1)[0]
    layer_keys = [stack_key] if L == 1 else list(jax.random.split(stack_key, L))
    return {
        "embed": _dense(ks[0], STATE_DIM + 1, (d,)),
        "pos": jax.random.normal(ks[1], (a["history"], d), jnp.float32) * 0.02,
        "layers": [_layer(k, d, a["n_heads"], a["d_ff"]) for k in layer_keys],
        "final": (jnp.ones((d,), jnp.float32), jnp.zeros((d,), jnp.float32)),
        "v_head": _dense(ks[3], d, (1,)),
        "p_head": _dense(jax.random.fold_in(ks[3], 1), d, (2,)),
    }


def init_weights(a: Dict) -> Dict:
    """The agent of configuration ``a`` (its ``agent`` block) from its key."""
    key = jax.random.PRNGKey(a["weight_key"])
    if a["kind"] == "transformer":
        return {"experts": [_expert(key, a)]}
    ks = jax.random.split(key, a["n_experts"] + 1)
    return {"experts": [_expert(ks[i], a) for i in range(a["n_experts"])],
            "gate": _dense(ks[-1], STATE_DIM + 1, (a["n_experts"],))}


# ------------------------------------------------------------------ forward
def _matmul(precision: str):
    if precision == "float32":
        return lambda spec, x, w: jnp.einsum(
            spec, x, w, precision=jax.lax.Precision.HIGHEST)
    if precision == "float8":
        f8 = jnp.float8_e4m3fn
        return lambda spec, x, w: jnp.einsum(
            spec, x.astype(f8), w.astype(f8),
            preferred_element_type=jnp.float32)
    raise ValueError(f"unknown precision {precision!r}")


def _layer_norm(x, gb):
    g, b = gb
    mu = x.mean(-1, keepdims=True)
    var = jnp.square(x - mu).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + LN_EPS) * g + b


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def _pooled(p: Dict, states, action, mm) -> jnp.ndarray:
    """One expert's encoder over (B, k, 40) states -> (B, d) features;
    ``action`` is one value or one per row."""
    B, k, _ = states.shape
    act = jnp.broadcast_to(jnp.reshape(jnp.asarray(action, jnp.float32),
                                       (-1, 1, 1)), (B, k, 1))
    x = jnp.concatenate([states, act], -1)
    h = mm("bkm,md->bkd", x, p["embed"]) + p["pos"][None]
    for lp in p["layers"]:
        a = _layer_norm(h, lp["ln1"])
        q = mm("bkd,dhe->bkhe", a, lp["wq"])
        kk = mm("bkd,dhe->bkhe", a, lp["wk"])
        v = mm("bkd,dhe->bkhe", a, lp["wv"])
        s = mm("bqhe,bkhe->bhqk", q, kk) / math.sqrt(q.shape[-1])
        o = mm("bhqk,bkhe->bqhe", jax.nn.softmax(s, axis=-1), v)
        h = h + mm("bqhe,hed->bqd", o, lp["wo"])
        a = _layer_norm(h, lp["ln2"])
        h = h + mm("bkf,fd->bkd", _gelu(mm("bkd,df->bkf", a, lp["w_in"])),
                   lp["w_out"])
    return _layer_norm(h, p["final"]).mean(axis=1)


def _expert_out(p: Dict, states, head: str, precision: str) -> jnp.ndarray:
    """(B, 2): Q for (no-submit, submit) with ``head="q"``, else logits."""
    mm = _matmul(precision)
    if head == "q":
        return jnp.stack([mm("bd,do->bo", _pooled(p, states, a, mm),
                             p["v_head"])[:, 0] for a in (-1.0, 1.0)], -1)
    return mm("bd,do->bo", _pooled(p, states, 0.0, mm), p["p_head"])


_expert_jit = jax.jit(_expert_out, static_argnums=(2, 3))


def outputs(w: Dict, states: np.ndarray, head: str, precision: str = "float32",
            block: int = 32) -> np.ndarray:
    """The agent's (N, 2) outputs over (N, k, 40) states, ``block`` rows and
    one expert at a time, in float64 on the host."""
    out = []
    for b0 in range(0, len(states), block):
        s = jnp.asarray(states[b0:b0 + block], jnp.float32)
        per = [np.asarray(_expert_jit(e, s, head, precision), np.float64)
               for e in w["experts"]]
        if "gate" not in w:
            out.append(per[0])
            continue
        cur = np.concatenate([np.asarray(s[:, -1, :], np.float64),
                              np.zeros((len(s), 1))], -1)
        z = cur @ np.asarray(w["gate"], np.float64)
        g = np.exp(z - z.max(-1, keepdims=True))
        g /= g.sum(-1, keepdims=True)
        out.append(np.einsum("ebq,be->bq", np.stack(per), g))
    return np.concatenate(out)
