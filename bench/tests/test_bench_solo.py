"""CPU rehearsals of the solo serve cell at a tiny size: the smoke agent's
widths in float32 over a one-month RTX trace and three tenant slots six
hours apart. Each tenant starts where the traffic says, inside the trace;
each serves to its horizon, and the slots' first tenants stagger the
starts through the rounds; two seeds serve the same work; nothing is shed
or degraded; a start counts in the latency of the decisions it holds
up; set-up warms the one batch size the rounds offer; and
``bench/run.py`` refuses to run the cell without a chip."""
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from _tiny_solo import CELL, execute, spec

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DAY = 86400.0


@pytest.fixture(scope="module")
def served():
    """Two runs that see every base of the cycle, under different seeds."""
    return [execute(spec(), seconds=6.0, seed=seed)
            for seed in (2**33 + 5, 23)]


def test_solo_window_and_counts(served):
    res, run = served[0]
    assert res["correct"], res["checks"]
    assert res["failed"] == 0
    assert res["checks"]["compiles_in_window"]["value"] == 0
    c = run.counts
    assert c["decisions"] == len(run.latencies) == res["attempted"] > 0
    assert c["shed"] == c["degraded"] == c["fallbacks"] == 0
    assert c["nonfinite"] == 0
    assert c["starts"] >= 2 * len(spec()["traffic"]["start_days"])
    # every round serves every slot: set-up warms that one batch size
    assert set(run.batch_sizes) == {3}
    assert sum(b * n for b, n in run.batch_sizes.items()) == c["decisions"]
    assert len(run.sample.obs) == min(32, c["decisions"])
    m = res["metrics"]
    assert set(m) == {"decisions_per_s", "decision_p95_ms", "setup_s"}
    assert m["decisions_per_s"]["value"] == pytest.approx(
        c["decisions"] / run.window_s)
    assert m["decision_p95_ms"]["value"] == pytest.approx(
        1e3 * np.quantile(run.latencies, 0.95))


def test_solo_tenants_start_where_the_traffic_says(served):
    _, run = served[0]
    t = spec()["traffic"]
    bases = t["start_days"]
    assert {j % len(bases) for _, j, *_ in run.turns} == set(
        range(len(bases))), "the runs see the whole cycle"
    for k, j, start, first, last, _ in run.turns:
        # slot k's j-th tenant starts k x stagger_h after the cycle's
        # j-th base, which lies start_days after the first arrival and
        # the warm-up
        want = (run.trace_start + run.warmup + bases[j % len(bases)] * DAY
                + k * t["stagger_h"] * 3600.0)
        assert start == pytest.approx(want)
        assert run.trace_start < start <= first < last < run.trace_end


def test_solo_tenants_serve_to_their_horizon(served):
    _, run = served[0]
    T = spec()["traffic"]["tenants"]
    firsts = {}
    for k, j, _, first, last, n in run.turns:
        # a tenant serves while its next decision ends inside its horizon
        # (slot k's first tenant, (k + 1) / T of one)
        horizon = run.horizon * ((k + 1) / T if j == 0 else 1.0)
        assert last + run.interval >= first + horizon
        assert n > 0
        if j == 0:
            firsts[k] = n
    # so the slots' first tenants hand over in turn
    assert [firsts[k] for k in sorted(firsts)] == sorted(set(firsts.values()))
    assert len(firsts) == T


def test_solo_every_seed_serves_the_same_work(served):
    (_, a), (_, b) = served
    n = min(len(a.turns), len(b.turns))
    assert n >= 6
    assert a.turns[:n] == b.turns[:n]
    assert a.sample.obs and b.sample.obs


def test_solo_a_start_counts_in_the_decisions_it_holds_up(monkeypatch):
    """A tenant start lengthened by 0.1 s shows in the latency of every
    decision of its round, the started tenant's and the others'."""
    from repro.core.control import ChainLane
    begin = ChainLane.begin

    def slow_begin(self, *a, **kw):
        out = begin(self, *a, **kw)
        time.sleep(0.1)
        return out

    monkeypatch.setattr(ChainLane, "begin", slow_begin)
    res, run = execute(spec(), seconds=3.0, seed=41)
    assert res["correct"], res["checks"]
    T, c = spec()["traffic"]["tenants"], run.counts
    held = sum(lat >= 0.1 for lat in run.latencies)
    # the window's last start may open a round that the window's end
    # abandons
    assert c["starts"] >= 2
    assert T * (c["starts"] - 1) <= held <= T * c["starts"]



def _has_result(stdout):
    for line in stdout.strip().splitlines():
        try:
            if isinstance(json.loads(line), dict):
                return True
        except ValueError:
            pass
    return False


def test_solo_run_without_a_chip_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELL, "--seed", "5",
         "--seconds", "1", "--trace", "0"], cwd=REPO, env=env,
        capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert not _has_result(p.stdout)
    assert "TPU" in p.stderr
