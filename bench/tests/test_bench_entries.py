"""CPU rehearsals of each entry at a tiny size: the window ends
where the harness says, the counts agree, and ``bench/run.py`` refuses to
run without a chip or without the program."""
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

import _tiny

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _common(res, run, seconds):
    assert res["correct"], res["checks"]
    assert res["failed"] == 0
    assert set(res) >= {"correct", "attempted", "failed", "metrics",
                        "device"}
    assert list(res)[-1] == "checks"
    assert res["checks"]["compiles_in_window"]["value"] == 0
    assert run.window_s >= seconds
    assert sum(b * n for b, n in run.batch_sizes.items()) >= res["attempted"]
    assert res["metrics"]["setup_s"]["value"] == pytest.approx(run.setup_s)


def test_serve_cosim_window_and_counts():
    s = _tiny.spec(tenants=3, sample=64)
    res, run = _tiny.execute(s, seconds=1.0)
    _common(res, run, 1.0)
    c = run.counts
    assert c["decisions"] == len(run.latencies) == res["attempted"] > 0
    assert set(run.batch_sizes) <= {1, 2, 3}
    assert sum(b * n for b, n in run.batch_sizes.items()) == c["decisions"]
    assert len(run.sample.obs) == min(64, c["decisions"])
    # the in-flight round drains after the end, and no more
    assert run.window_s < 1.0 + 2.0
    m = res["metrics"]
    assert m["decisions_per_s"]["value"] == pytest.approx(
        c["decisions"] / run.window_s)
    assert m["decision_p95_ms"]["value"] == pytest.approx(
        1e3 * np.quantile(run.latencies, 0.95))
    assert set(m) == {"decisions_per_s", "decision_p95_ms", "setup_s"}


def _episodes(seed, seconds=4.0):
    """The decisions of each whole episode of a run, by start instant."""
    s = _tiny.spec(tenants=3, sample=16)
    _, run = _tiny.execute(s, seconds=seconds, seed=seed)
    assert run.counts["episodes"] >= 4, "the window holds more than a cycle"
    return run


def test_episodes_keep_to_their_horizon_and_the_trace():
    run = _episodes(2**33 + 5)
    horizon = _tiny.EPISODES["horizon_days"] * 86400.0
    # every whole episode served its horizon (a round that fast-forwards to
    # a successor's start may carry it past), the same span each time it
    # came round, and none left the trace
    spans = {}
    for i, span, _ in run.episodes[:-1]:
        assert span >= horizon
        spans.setdefault(i, set()).add(span)
    assert len(spans) == 3 and all(len(v) == 1 for v in spans.values())
    ends = [run.starts[i] + span for i, span, _ in run.episodes]
    assert max(ends) < run.trace_end


def test_every_seed_serves_the_same_cycle():
    a, b = _episodes(11), _episodes(2**32 + 3)
    # the seed orders the cycle; an episode's work is the same in any order
    for run in (a, b):
        assert sorted(run.order) == [0, 1, 2]
    work_a = {i: n for i, _, n in a.episodes[:-1]}
    work_b = {i: n for i, _, n in b.episodes[:-1]}
    common = set(work_a) & set(work_b)
    assert common and all(work_a[i] == work_b[i] for i in common)


def _bench_run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         _tiny.CELL, "--seed", "5", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def _has_result(stdout):
    for line in stdout.strip().splitlines():
        try:
            if isinstance(json.loads(line), dict):
                return True
        except ValueError:
            pass
    return False


def test_run_without_a_chip_prints_no_result():
    p = _bench_run(REPO)
    assert p.returncode != 0
    assert not _has_result(p.stdout)
    assert "TPU" in p.stderr


def test_run_without_the_program_prints_no_result(tmp_path):
    shutil.copytree(os.path.join(REPO, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", ".trace"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    p = _bench_run(str(tmp_path))
    assert p.returncode != 0
    assert not _has_result(p.stdout)
