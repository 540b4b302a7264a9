"""CPU rehearsal of the solo cell's nine per-layer readers: the tiny solo
cell, run inside ``telemetry.capture()`` (on the chip the profiler of a
traced run switches the spans on), gives each span reader a finite number
that fits the harness's own clock; the trace readers read a recorded
reduction; and without the program's spans, as at a program that lacks
the ``env.*`` spans, or without a trace, each reads None."""
import importlib.util
import math
import os
import sys
import time

import pytest

import devtrace
import flops
from _tiny_solo import execute, spec
from repro import telemetry

METRICS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "metrics")
SPANS = ("lane.apply_ms.solo", "env.advance_ms.solo", "env.encode_ms.solo",
         "env.reset_ms.solo", "forward.host_ms.solo", "forward.wait_ms.solo")
TRACE = ("forward_roofline.solo", "device.idle.solo")


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        "m_" + name.replace(".", "_"), os.path.join(METRICS, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@pytest.fixture(scope="module")
def recorded():
    telemetry.reset()
    with telemetry.capture():
        res, run = execute(spec(), seconds=3.0)
    yield res, run, {n: _reader(n)(run) for n in SPANS}
    telemetry.reset()


@pytest.mark.parametrize("name", SPANS)
def test_solo_reader_finds_a_finite_number(recorded, name):
    res, _, values = recorded
    assert res["correct"], res["checks"]
    assert values[name] is not None and math.isfinite(values[name])
    assert values[name] >= 0


def test_solo_lane_parts_lie_inside_the_apply(recorded):
    _, run, v = recorded
    parts = v["env.advance_ms.solo"] + v["env.encode_ms.solo"]
    assert parts <= v["lane.apply_ms.solo"]
    # the benchmark's own wrapper times the same applies from outside
    assert v["lane.apply_ms.solo"] <= run.spans.mean_ms("apply")


def test_solo_forward_split_lies_inside_the_harness_call(recorded):
    _, run, v = recorded
    split = v["forward.host_ms.solo"] + v["forward.wait_ms.solo"]
    whole = run.spans.mean_ms("forward")
    assert whole / 2 <= split <= whole


def test_solo_starts_fit_the_window(recorded):
    _, run, v = recorded
    assert run.counts["starts"] >= 1
    assert (v["env.reset_ms.solo"] * run.counts["starts"]
            <= 1e3 * run.window_s)


def test_solo_trace_readers_read_a_reduction(recorded):
    _, run, _ = recorded
    assert [_reader(n)(run) for n in TRACE] == [None, None]   # no trace
    a = run.agent
    least = sum(n * flops.least_seconds(flops.forward_flops(a, "p", b),
                                        flops.forward_bytes(a, b),
                                        {"flops_per_s": 197e12,
                                         "hbm_bytes_per_s": 819e9})
                for b, n in run.batch_sizes.items())

    class Traced:
        agent, batch_sizes, counts = run.agent, run.batch_sizes, run.counts
        peak = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
        window_s = 2.0
        reduction = devtrace.Reduction(
            window_s=2.0, n_devices=1, busy_s=0.5,
            program_s={"jit__lambda": 4 * least, "jit_softmax": 1.0},
            program_calls={}, top_ops=[], idle_gaps=[])

    assert _reader("forward_roofline.solo")(Traced) == pytest.approx(25.0)
    assert _reader("device.idle.solo")(Traced) == pytest.approx(75.0)
    per = flops.forward_flops(a, "p", 1)
    assert _reader("mfu.solo")(Traced) == pytest.approx(
        100.0 * run.counts["attempted"] * per / (2.0 * 197e12))


def test_solo_readers_read_none_without_the_spans(recorded, monkeypatch):
    _, run, _ = recorded

    class Later:
        t_open, t_close, window_s = run.t_close + 1.0, run.t_close + 2.0, 1.0

    # nothing recorded in the window
    assert [_reader(n)(Later) for n in SPANS] == [None] * len(SPANS)
    # a program that records the service's spans but not the env's
    telemetry.reset()
    with telemetry.capture():
        Later.t_open = time.perf_counter()
        for _ in range(3):
            with telemetry.span("lane.apply"):
                pass
            with telemetry.span("forward.wait"):
                pass
        Later.t_close = time.perf_counter()
    got = {n: _reader(n)(Later) for n in SPANS}
    assert [got[n] for n in ("env.advance_ms.solo", "env.encode_ms.solo",
                             "env.reset_ms.solo")] == [None] * 3
    assert got["lane.apply_ms.solo"] is not None
    # a window whose start the ring has overwritten
    telemetry.reset(size=4)
    with telemetry.capture():
        for _ in range(8):
            with telemetry.span("lane.apply"):
                pass
    assert [_reader(n)(run) for n in SPANS] == [None] * len(SPANS)
    # a program without spans at all
    monkeypatch.setitem(sys.modules, "repro.telemetry", None)
    monkeypatch.delattr(sys.modules["repro"], "telemetry")
    assert [_reader(n)(run) for n in SPANS] == [None] * len(SPANS)
    telemetry.reset()
