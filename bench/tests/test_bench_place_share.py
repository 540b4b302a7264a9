"""CPU rehearsal of ``env.place_share.solo``: the tiny solo cell, run
inside ``telemetry.capture()`` (on the chip the profiler of a traced run
switches the spans on), places every start of its fault-free world off
the background timeline; a start that replays its queue wait from the
start instant counts against the share; and a program without the
``env.place`` span, as the parent of the span's PR, reads None."""
import importlib.util
import os
import time

import pytest

from _tiny_solo import execute, spec
from repro import telemetry

NAME = "env.place_share.solo"


def _read():
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "metrics", NAME + ".py")
    s = importlib.util.spec_from_file_location("m_place_share", path)
    mod = importlib.util.module_from_spec(s)
    s.loader.exec_module(mod)
    return mod.read


class Window:
    def __init__(self, t_open, t_close):
        self.t_open, self.t_close = t_open, t_close
        self.window_s = t_close - t_open


def _spans(**counts):
    """A window holding ``counts[name]`` empty spans of each name."""
    telemetry.reset()
    with telemetry.capture():
        t0 = time.perf_counter()
        for name, n in counts.items():
            for _ in range(n):
                with telemetry.span(name.replace("_", ".")):
                    pass
        return Window(t0, time.perf_counter())


def test_tiny_solo_cell_places_its_starts_off_the_timeline():
    telemetry.reset()
    try:
        with telemetry.capture():
            res, run = execute(spec(), seconds=2.0)
        assert res["correct"], res["checks"]
        t = telemetry.totals(run.t_open, run.t_close)
        assert run.counts["starts"] >= 1 and t["env.reset"].count >= 1
        assert _read()(run) == pytest.approx(
            (t["env.place"].count - t["env.replay"].count)
            / t["env.reset"].count)
        assert _read()(run) == 1.0
    finally:
        telemetry.reset()


def test_place_share_counts_replays_against_the_starts():
    try:
        assert _read()(_spans(env_reset=4, env_place=4, env_replay=1)) \
            == pytest.approx(0.75)
        # starts on the fork path (past a fault) place nothing
        assert _read()(_spans(env_reset=4, env_place=2)) \
            == pytest.approx(0.5)
    finally:
        telemetry.reset()


def test_place_share_reads_none_without_the_spans():
    try:
        assert _read()(_spans(env_reset=3)) is None     # the parent
        assert _read()(_spans(lane_apply=3)) is None
        w = _spans(env_reset=3, env_place=3)
        assert _read()(Window(w.t_close + 1.0, w.t_close + 2.0)) is None
    finally:
        telemetry.reset()
