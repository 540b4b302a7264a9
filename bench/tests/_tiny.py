"""Tiny specs of the benchmark's cells for CPU rehearsals: the cells' own
entries, traffic and harness at a few-thousand-parameter agent over a
one-month trace (episodes of eight simulated days), computed in float32 so
that sound runs compare at rounding and the limits here can be tight."""
import time

import harness

LIMITS = {"answer_err": 1e-4}

CELL = "serve-co8.v100-medium-moe-dqn"
# inside the tiny one-month trace
EPISODES = dict(start_days=[2, 4, 6], horizon_days=8)


def spec(cell=CELL, **traffic):
    s = harness.load_cell(cell)
    a = s["config"]["agent"]
    a.update(n_layers=1, d_model=32, n_heads=2, d_ff=64, history=12,
             interval_s=1800, n_experts=min(a["n_experts"], 2),
             compute_dtype="float32")
    s["config"]["world"]["trace_months"] = 1
    s["config"]["limits"] = dict(LIMITS)
    s["traffic"].update(EPISODES)
    s["traffic"].update(traffic)
    return s


def execute(s, seconds=1.0, seed=2**31 + 17):
    """One run without the look for a chip; returns (result, run)."""
    return harness.measure(s, seed, seconds, False, time.perf_counter(),
                           require_tpu=False)
