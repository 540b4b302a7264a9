"""The chain-link rules of ``repro.sim.workload``: every engine (scalar,
vector and co-tenant envs, the serving lanes, ``MultiTenantSim``) applies
these, so their edges are pinned here once."""
import numpy as np
import pytest

from repro.sim.trace import Job
from repro.sim.workload import (forced_submit, link_end, settle_pred_end,
                                submit_instant)

NOW = 86_400.0 + 1 / 3      # a non-round instant: exact float edges
IV = 600.0
INF = float("inf")


def _pred(start, runtime=5_000.0, limit=7_200.0, end=-1.0):
    return Job(job_id=1, user_id=1, submit_time=0.0, runtime=runtime,
               time_limit=limit, n_nodes=1, start_time=start, end_time=end)


def _forced_exactly_at_the_end():
    assert forced_submit(0, NOW, IV, NOW + IV) is True


def _not_forced_one_ulp_before():
    end = np.nextafter(NOW + IV, INF)
    assert NOW + IV < end
    assert not forced_submit(0, NOW, IV, end)


def _submit_is_never_forced():
    assert forced_submit(1, NOW, IV, NOW) is False


def _killed_queued_never_forces():
    end = link_end(-1.0, 5_000.0, 7_200.0)
    assert end == INF
    assert forced_submit(0, 1e15, IV, end) is False


def _scalar_and_array_agree():
    rng = np.random.default_rng(0)
    n = 64
    starts = np.where(rng.random(n) < 0.2, -1.0,
                      NOW - rng.uniform(0, 7_200.0, n))
    runtimes = rng.uniform(1.0, 9_000.0, n)
    limits = rng.choice([3_600.0, 7_200.0], n)
    ends = link_end(starts, runtimes, limits)
    actions = rng.integers(0, 2, n)
    nows = NOW + rng.choice([0.0, 300.0, 3_000.0], n)
    # a few lanes exactly on the forced edge
    nows[:4] = np.where(np.isfinite(ends[:4]), ends[:4] - IV, nows[:4])
    actions[:4] = 0
    forced = forced_submit(actions, nows, IV, ends)
    assert forced[:4][np.isfinite(ends[:4])].all()
    for i in range(n):
        end = link_end(float(starts[i]), float(runtimes[i]),
                       float(limits[i]))
        assert end == ends[i]
        assert forced_submit(int(actions[i]), float(nows[i]), IV, end) \
            == forced[i]


def _submit_instants():
    end = NOW + 250.0
    assert submit_instant(NOW, end, False) == NOW
    assert submit_instant(NOW, end, True) == end
    # a forced submit never goes in before now
    assert submit_instant(NOW, NOW - 1.0, True) == NOW
    # nor waits on a killed, queued predecessor's unknown end
    assert submit_instant(NOW, INF, True) == NOW


def _pred_end_when_started():
    p = _pred(NOW)
    settle_pred_end(p, NOW + 50.0)
    assert p.end_time == NOW + 5_000.0           # runtime under the limit
    p = _pred(NOW, runtime=9_000.0)
    settle_pred_end(p, NOW + 50.0)
    assert p.end_time == NOW + 7_200.0           # runs to its limit
    p = _pred(NOW, end=NOW + 10.0)
    settle_pred_end(p, NOW + 50.0)
    assert p.end_time == NOW + 10.0              # a recorded end stands


def _pred_end_when_killed_and_queued():
    p = _pred(-1.0)
    settle_pred_end(p, NOW + 50.0)
    assert p.end_time == NOW + 50.0              # down since the submit


CASES = {f.__name__.strip("_"): f for f in (
    _forced_exactly_at_the_end, _not_forced_one_ulp_before,
    _submit_is_never_forced, _killed_queued_never_forces,
    _scalar_and_array_agree, _submit_instants, _pred_end_when_started,
    _pred_end_when_killed_and_queued)}


@pytest.mark.parametrize("case", sorted(CASES))
def test_chain_link_rules(case):
    CASES[case]()
