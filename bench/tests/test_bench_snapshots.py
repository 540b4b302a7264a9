"""CPU rehearsal of ``state.snapshots_per_round.serve``: the tiny co-sim
cell, run inside ``telemetry.capture()``, encodes the shared cluster
snapshot at most once per round however many tenants wait, and the
reader reads None where the program records no ``state.snapshot``
spans."""
import importlib.util
import os
import sys
import time

import pytest

import _tiny
from repro import telemetry

NAME = "state.snapshots_per_round.serve"
PATH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "metrics", NAME + ".py")


def _read(run):
    spec = importlib.util.spec_from_file_location("m_snapshots", PATH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(run)


@pytest.fixture(scope="module")
def recorded():
    spec = _tiny.spec(tenants=3, sample=16)
    # a tiny agent that mostly waits, so that most rounds push histories
    spec["config"]["agent"]["weight_key"] = 2
    telemetry.reset()
    with telemetry.capture():
        res, run = _tiny.execute(spec, seconds=1.0)
        value = _read(run)
        tot = telemetry.totals(run.t_open, run.t_close)
    yield res, run, value, tot
    telemetry.reset()


def test_one_snapshot_per_round_at_most(recorded):
    res, _, value, tot = recorded
    assert res["correct"], res["checks"]
    rounds = tot["cosim.advance"].count
    snaps = tot["state.snapshot"].count
    assert rounds > tot["cosim.inject"].count > 0
    # each round encodes once for its waiting tenants and each start once
    # for its inject, never once per tenant
    assert snaps <= rounds + tot["cosim.inject"].count
    assert value == (snaps - tot["cosim.inject"].count) / rounds
    assert 0.5 < value <= 1.0


def test_snapshot_spans_nest_in_the_encode(recorded):
    _, _, _, tot = recorded
    # one span per encode, inside ``state.encode`` or ``cosim.inject``,
    # which keep wrapping the same work as before
    assert tot["state.snapshot"].count <= (tot["state.encode"].count
                                           + tot["cosim.inject"].count)
    assert tot["state.snapshot"].seconds <= (tot["state.encode"].seconds
                                             + tot["cosim.inject"].seconds)


def test_reader_reads_none_without_the_spans(recorded, monkeypatch):
    _, run, _, _ = recorded

    class Later:
        t_open, t_close, window_s = run.t_close + 1.0, run.t_close + 2.0, 1.0

    assert _read(Later) is None
    # a program that records its rounds but encodes no shared snapshot
    telemetry.reset()
    with telemetry.capture():
        t0 = time.perf_counter()
        for _ in range(3):
            with telemetry.span("cosim.advance"):
                with telemetry.span("state.encode"):
                    pass
        t1 = time.perf_counter()

    class PerTenant:
        t_open, t_close, window_s = t0, t1, t1 - t0

    assert _read(PerTenant) is None
    # a program without spans at all
    monkeypatch.setitem(sys.modules, "repro.telemetry", None)
    monkeypatch.delattr(sys.modules["repro"], "telemetry")
    assert _read(run) is None
