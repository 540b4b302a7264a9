"""CPU rehearsal of the readers of the program's spans and counters: the
tiny co-sim cell, run inside ``telemetry.capture()`` (on the chip the
profiler of a traced run switches the spans on), gives each reader a
finite number that agrees with the harness's own clock; a program without
the spans, or a window the span log no longer holds, reads as None."""
import importlib.util
import math
import os
import sys

import pytest

import _tiny
from repro import telemetry

METRICS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "metrics")
NAMES = ("forward.host_ms.serve", "forward.wait_ms.serve",
         "sim.advance_ms.serve", "state.encode_ms.serve",
         "service.start_ms.serve", "host.gc_ms.serve")


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
        res, run = _tiny.execute(_tiny.spec(tenants=3, sample=16),
                                 seconds=1.0)
    yield res, run, {n: _reader(n)(run) for n in NAMES}
    telemetry.reset()


@pytest.mark.parametrize("name", NAMES)
def test_reader_finds_a_finite_number(recorded, name):
    res, _, values = recorded
    assert res["correct"], res["checks"]
    assert values[name] is not None and math.isfinite(values[name])
    assert values[name] >= 0


def test_forward_split_lies_inside_the_harness_call(recorded):
    _, run, v = recorded
    split = v["forward.host_ms.serve"] + v["forward.wait_ms.serve"]
    whole = run.spans.mean_ms("forward")
    assert whole / 2 <= split <= whole


def test_episode_starts_fit_the_window(recorded):
    _, run, v = recorded
    assert run.counts["episodes"] >= 1
    assert (v["service.start_ms.serve"] * run.counts["episodes"]
            <= 1e3 * run.window_s)


def test_readers_read_none_without_the_spans(recorded, monkeypatch):
    _, run, _ = recorded

    class Later:
        t_open, t_close, window_s = run.t_close + 1.0, run.t_close + 2.0, 1.0

    # nothing recorded in the window
    assert [_reader(n)(Later) for n in NAMES] == [None] * len(NAMES)
    # a window whose start the ring has overwritten
    telemetry.reset(size=4)
    with telemetry.capture():
        for _ in range(8):
            with telemetry.span("service.round", request=1):
                pass
    assert [_reader(n)(run) for n in NAMES] == [None] * len(NAMES)
    # a program without spans at all
    monkeypatch.setitem(sys.modules, "repro.telemetry", None)
    monkeypatch.delattr(sys.modules["repro"], "telemetry")
    assert [_reader(n)(run) for n in NAMES] == [None] * len(NAMES)
