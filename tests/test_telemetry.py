"""Program spans and counters (``repro.telemetry``): off by default and
free of annotations, nested records with parent, round and self time, a
bounded ring that never answers for part of a window, the garbage
collector's pauses, the profiler's host plane, and the compile log."""
import gc
import glob
import time

import jax
import jax.numpy as jnp
import pytest

from repro import telemetry


@pytest.fixture(autouse=True)
def fresh_log():
    telemetry.reset()
    yield
    telemetry.reset()


def _records():
    """(name, parent name, request) of every span in the log, in order."""
    log = telemetry._spans
    names = [telemetry._names[i] for i in log.name[:log.n]]
    return [(names[k], names[p] if p >= 0 else None, int(r))
            for k, (p, r) in enumerate(zip(log.parent[:log.n],
                                           log.request[:log.n]))]


def test_off_records_nothing_and_builds_no_annotation(monkeypatch):
    built = []
    monkeypatch.setattr(telemetry, "TraceAnnotation",
                        lambda name: built.append(name))
    assert not telemetry.recording()
    assert telemetry.span("service.round", request=3) is \
        telemetry.span("forward.wait")
    t0 = time.perf_counter()
    with telemetry.span("service.round", request=3):
        with telemetry.span("forward.wait"):
            gc.collect()
    assert built == []
    assert telemetry.totals(t0, time.perf_counter()) == {}
    assert telemetry._spans.n == telemetry._gcs.n == 0


def test_capture_nests_parent_round_and_self_time():
    with telemetry.capture():
        assert telemetry.recording()
        t0 = time.perf_counter()
        with telemetry.span("service.round", request=7):
            with telemetry.span("service.batch"):
                time.sleep(0.002)
                with telemetry.span("forward.wait"):
                    time.sleep(0.003)
            with telemetry.span("cosim.advance"):
                time.sleep(0.001)
        with telemetry.span("service.start"):
            pass
        t1 = time.perf_counter()
    assert not telemetry.recording()
    assert _records() == [
        ("service.round", None, 7), ("service.batch", "service.round", 7),
        ("forward.wait", "service.batch", 7),
        ("cosim.advance", "service.round", 7), ("service.start", None, -1)]
    tot = telemetry.totals(t0, t1)
    rnd, batch = tot["service.round"], tot["service.batch"]
    wait, adv = tot["forward.wait"], tot["cosim.advance"]
    assert [t.count for t in (rnd, batch, wait, adv)] == [1, 1, 1, 1]
    assert wait.seconds >= 0.003 and batch.seconds >= 0.005
    assert rnd.self_s == pytest.approx(
        rnd.seconds - batch.seconds - adv.seconds, abs=1e-9)
    assert batch.self_s == pytest.approx(batch.seconds - wait.seconds,
                                         abs=1e-9)
    assert wait.self_s == wait.seconds
    assert tot["nothing"] == (0, 0.0, 0.0)
    # the window counts the spans that started inside it
    assert telemetry.totals(t1, t1 + 1.0) == {}


def test_full_ring_drops_the_oldest_and_refuses_a_part_window():
    telemetry.reset(size=8)
    starts = []
    with telemetry.capture():
        for _ in range(20):
            starts.append(time.perf_counter())
            with telemetry.span("lane.apply"):
                pass
    end = time.perf_counter()
    assert telemetry.dropped() == 12
    # records 0-11 were overwritten: a window that opens before the
    # oldest record held (12) may miss some, and is refused
    assert telemetry.totals(starts[0], end) is None
    assert telemetry.totals(starts[12], end) is None
    # one that opens after the oldest record held is whole
    assert telemetry.totals(starts[13], end)["lane.apply"].count == 7


def test_gc_pauses_are_counted_while_recording():
    with telemetry.capture():
        t0 = time.perf_counter()
        gc.collect()
        gc.collect()
        t1 = time.perf_counter()
    g = telemetry.totals(t0, t1)["host.gc"]
    assert g.count >= 2 and 0 < g.seconds <= t1 - t0
    # off again: the callback stays installed and records nothing
    n = telemetry._gcs.n
    gc.collect()
    assert telemetry._gcs.n == n


def test_spans_land_in_the_profilers_host_plane(tmp_path):
    x = jnp.ones((4, 4))
    with jax.profiler.trace(str(tmp_path)):
        assert telemetry.recording()
        with telemetry.span("service.round", request=1):
            with telemetry.span("forward.wait"):
                (x @ x).block_until_ready()
    assert not telemetry.recording()
    assert [r[0] for r in _records()] == ["service.round", "forward.wait"]
    [path] = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    host = data.find_plane_with_name("/host:CPU")
    names = {e.name for line in host.lines for e in line.events}
    assert {"mirage.service.round", "mirage.forward.wait"} <= names


def test_compile_log_counts_compiles_per_program():
    log = telemetry.CompileLog()

    def telemetry_probe(v):
        return v * 3.0 + 1.0

    jax.jit(telemetry_probe)(jnp.arange(7.0)).block_until_ready()
    n, secs = log.programs["jit(telemetry_probe)"]
    assert n == 1 and secs > 0
    assert log.total_s() >= secs
