"""The float32 reference against the program's agent, on the CPU at the
reduced width, and the lower-precision control against the limits."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference
from repro.core.foundation import (FoundationConfig, init_foundation,
                                   policy_logits, q_values)


def _reduced(kind, dtype="bfloat16"):
    fc = dataclasses.replace(FoundationConfig(kind=kind).reduced(), kind=kind)
    fc = dataclasses.replace(fc, trunk=fc.trunk.replace(compute_dtype=dtype))
    a = dict(kind=kind, n_experts=fc.n_experts, history=fc.history,
             d_model=fc.trunk.d_model, n_layers=fc.trunk.n_layers,
             n_heads=fc.trunk.n_heads, d_ff=fc.trunk.d_ff, weight_key=7)
    return fc, a


def _states(k, n=16, seed=0):
    return np.random.default_rng(seed).normal(size=(n, k, 40)).astype(
        np.float32)


@pytest.mark.parametrize("kind", ["transformer", "moe"])
def test_weights_are_the_programs_from_the_same_key(kind):
    fc, a = _reduced(kind)
    p = jax.jit(lambda k: init_foundation(k, fc))(jax.random.PRNGKey(7))
    w = reference.init_weights(a)
    experts = ([p] if kind == "transformer" else
               [jax.tree.map(lambda x, i=i: x[i], p["experts"])
                for i in range(fc.n_experts)])
    for e, r in zip(experts, w["experts"]):
        seg = e["trunk"]["segments"][0]["b0"]
        pairs = [(e["embed_in"], r["embed"]), (e["pos"], r["pos"]),
                 (e["v_head"], r["v_head"]), (e["p_head"], r["p_head"])]
        for li, lr in enumerate(r["layers"]):
            pairs += [(seg["attn"][n][li], lr[n]) for n in ("wq", "wk", "wv",
                                                           "wo")]
            pairs += [(seg["ffn"]["wi"][li], lr["w_in"]),
                      (seg["ffn"]["wo"][li], lr["w_out"])]
        for x, y in pairs:
            np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                       rtol=0, atol=1e-7)
    if kind == "moe":
        np.testing.assert_array_equal(np.asarray(p["gate"]),
                                      np.asarray(w["gate"]))


@pytest.mark.parametrize("kind", ["transformer", "moe"])
@pytest.mark.parametrize("head", ["q", "p"])
def test_reference_matches_float32_program(kind, head):
    fc, a = _reduced(kind, "float32")
    p = init_foundation(jax.random.PRNGKey(7), fc)
    s = _states(fc.history)
    fn = q_values if head == "q" else policy_logits
    prog = np.asarray(fn(p, fc, jnp.asarray(s)), np.float64)
    ref = reference.outputs(reference.init_weights(a), s, head, block=5)
    scale = np.abs(ref).max()
    # float32 end to end: the two differ by summation order alone
    assert np.abs(prog - ref).max() <= 1e-5 * scale


@pytest.mark.parametrize("kind", ["transformer", "moe"])
def test_bfloat16_forward_lies_outside_the_float32_tolerance(kind):
    fc, a = _reduced(kind, "bfloat16")
    p = init_foundation(jax.random.PRNGKey(7), fc)
    s = _states(fc.history)
    prog = np.asarray(q_values(p, fc, jnp.asarray(s)), np.float64)
    ref = reference.outputs(reference.init_weights(a), s, "q")
    err = np.abs(prog - ref).max() / np.abs(ref).max()
    assert err > 1e-5 * 10
