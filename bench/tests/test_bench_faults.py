"""A run with the timed path broken underneath comes out not correct: the
harness's own comparison, driven as in a chip run (without the look for a
chip), at a tiny size on the CPU."""
import jax.numpy as jnp
import pytest

import _tiny
from repro.core import dqn, pg

CELLS = {
    "serve-co8": dict(tenants=3, sample=64),
}


def _altered_answer(monkeypatch):
    """Every Q-value or logit scaled by 1.25 where the forward makes it."""
    q, lg = dqn.q_values, pg.policy_logits
    monkeypatch.setattr(dqn, "q_values", lambda *a, **k: 1.25 * q(*a, **k))
    monkeypatch.setattr(pg, "policy_logits",
                        lambda *a, **k: 1.25 * lg(*a, **k))


def _flipped_action(monkeypatch):
    """The forward is right, the action it answers is the other one."""
    for cls in (dqn.DQNLearner, pg.PGLearner):
        act = cls.act_batch
        monkeypatch.setattr(cls, "act_batch",
                            lambda self, m, explore=True, _a=act:
                            1 - _a(self, m, explore=explore))


def _half_batch(monkeypatch):
    """Only the first half of each batch is computed; the rest repeats it."""
    def halved(fn):
        def f(params, fc, s, *a, **k):
            h = max(s.shape[0] // 2, 1)
            out = fn(params, fc, s[:h], *a, **k)
            reps = -(-s.shape[0] // h)
            return jnp.concatenate([out] * reps)[:s.shape[0]]
        return f
    monkeypatch.setattr(dqn, "q_values", halved(dqn.q_values))
    monkeypatch.setattr(pg, "policy_logits", halved(pg.policy_logits))


FAULTS = {"altered_answer": _altered_answer,
          "flipped_action": _flipped_action,
          "half_batch": _half_batch}


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_sound_run_is_correct(cell):
    res, _ = _tiny.execute(_tiny.spec(**CELLS[cell]))
    assert res["correct"], res["checks"]


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("cell", sorted(CELLS))
def test_broken_path_is_not_correct(cell, fault, monkeypatch):
    FAULTS[fault](monkeypatch)
    res, _ = _tiny.execute(_tiny.spec(**CELLS[cell]))
    assert not res["correct"], res["checks"]
