"""The trace reduction on a hand-built trace, the operation counter
against the configured agent's arithmetic, and the peaks table."""
import jax
import pytest
from jax.profiler import ProfileData

import devtrace
import flops
import peaks

CONFIGURED = dict(kind="moe", n_experts=10, history=144, d_model=256,
                  n_layers=4, n_heads=8, d_ff=1024)


def _events(evs):
    return "\n".join(f"events {{ metadata_id: {m} offset_ps: {a * 1000} "
                     f"duration_ps: {d * 1000} }}" for m, a, d in evs)


def _meta(names):
    return "\n".join(f'event_metadata {{ key: {i} value {{ id: {i} '
                     f'name: "{n}" }} }}' for i, n in enumerate(names, 1))


def _trace(device_ops, host_spans, device="/device:TPU:0", modules=()):
    """Times in ns from 0; device_ops (name id, start, dur) over names
    op_a/op_b, modules over jit_f(1)/jit_g(2), host spans over the
    benchmark's names."""
    host_names = ["bench.window", "bench.forward", "bench.apply",
                  "bench.env_step"]
    mods = [(m + 2, a, d) for m, a, d in modules]
    return ProfileData.from_text_proto(f"""
planes {{ id: 1 name: "{device}"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0 {_events(device_ops)} }}
  lines {{ id: 3 name: "XLA Modules" timestamp_ns: 0 {_events(mods)} }}
  {_meta(["op_a = fusion(x)", "op_b", "jit_f(1)", "jit_g(2)"])} }}
planes {{ id: 2 name: "/host:CPU"
  lines {{ id: 2 name: "python" timestamp_ns: 0 {_events(host_spans)} }}
  {_meta(host_names)} }}
""")


def test_busy_idle_and_time_under_spans():
    # window [0, 1000); forward spans [100, 300) and [500, 700); apply
    # [300, 500) and [700, 1000); device ops overlap inside the forwards
    ops = [(1, 120, 100), (2, 200, 50), (1, 520, 100), (2, 640, 40)]
    spans = [(1, 0, 1000), (2, 100, 200), (3, 300, 200), (2, 500, 200),
             (3, 700, 300)]
    mods = [(1, 120, 130), (2, 520, 160)]
    r = devtrace.reduce(_trace(ops, spans, modules=mods))
    assert r.window_s == pytest.approx(1000e-9)
    assert r.n_devices == 1
    # union: [120, 250) + [520, 620) + [640, 680) = 130 + 100 + 40 ns
    assert r.busy_s == pytest.approx(270e-9)
    assert r.program_s == {"jit_f": pytest.approx(130e-9),
                           "jit_g": pytest.approx(160e-9)}
    assert r.program_calls == {"jit_f": 1, "jit_g": 1}
    ops_s = dict(r.top_ops)
    assert ops_s["op_a"] == pytest.approx(200e-9)
    assert ops_s["op_b"] == pytest.approx(90e-9)
    gaps = dict(r.idle_gaps)
    # idle: [0,120) outside, [250,520) mid 385 in apply, [620,640) mid
    # 630 in forward, [680,1000) mid 840 in apply
    assert gaps[devtrace.OUTSIDE] == pytest.approx(120e-9)
    assert gaps["apply"] == pytest.approx(270e-9 + 320e-9)
    assert gaps["forward"] == pytest.approx(20e-9)
    assert sum(gaps.values()) == pytest.approx(r.window_s - r.busy_s)


def test_events_outside_the_window_are_clipped():
    ops = [(1, 0, 300), (2, 900, 300)]
    spans = [(1, 100, 900), (2, 100, 900)]
    r = devtrace.reduce(_trace(ops, spans, modules=[(1, 0, 300)]))
    assert r.window_s == pytest.approx(900e-9)
    assert r.busy_s == pytest.approx(200e-9 + 100e-9)
    assert r.program_s["jit_f"] == pytest.approx(200e-9)


def test_innermost_span_takes_the_gap():
    spans = [(1, 0, 1000), (4, 0, 1000), (2, 400, 200)]
    r = devtrace.reduce(_trace([(1, 0, 100)], spans))
    gaps = dict(r.idle_gaps)
    # gaps: [100, 1000) one gap with midpoint 550 inside forward
    assert gaps == {"forward": pytest.approx(900e-9)}


def test_a_trace_without_window_or_device_is_refused():
    with pytest.raises(ValueError, match="bench.window"):
        devtrace.reduce(_trace([(1, 0, 10)], [(2, 0, 10)]))
    with pytest.raises(ValueError, match="device"):
        devtrace.reduce(_trace([(1, 0, 10)], [(1, 0, 10)],
                               device="/host:other"))


def test_merge():
    assert devtrace.merge([(5, 6), (0, 2), (1, 3)]) == [(0, 3), (5, 6)]


def test_configured_agent_arithmetic():
    # one encoder pass over 144 snapshots at d=256, d_ff 1024, 4 layers
    assert flops.trunk_pass_flops(CONFIGURED) == pytest.approx(0.99e9,
                                                               rel=0.01)
    # moe V-head: 2 actions x 10 experts = 20 passes a decision
    assert flops.passes(CONFIGURED, "q") == 20
    assert flops.forward_flops(CONFIGURED, "q", 1) == pytest.approx(
        19.8e9, rel=0.01)
    assert flops.forward_flops(dict(CONFIGURED, kind="transformer"), "p",
                               1) == pytest.approx(0.99e9, rel=0.01)


@pytest.mark.parametrize("kind", ["moe", "transformer"])
def test_parameter_count_is_the_programs(kind):
    from repro.core.foundation import FoundationConfig, init_foundation
    shapes = jax.eval_shape(lambda k: init_foundation(
        k, FoundationConfig(kind=kind)), jax.random.PRNGKey(0))
    n = sum(x.size for x in jax.tree.leaves(shapes))
    assert flops.param_count(dict(CONFIGURED, kind=kind)) == n


def test_roofline_time_takes_the_larger_bound():
    p = peaks.peak("TPU v5 lite")
    assert flops.least_seconds(197e12, 0.0, p) == pytest.approx(1.0)
    assert flops.least_seconds(0.0, 819e9, p) == pytest.approx(1.0)
    # the configured moe forward at batch 8 is bound by its operations
    a = CONFIGURED
    assert flops.forward_flops(a, "q", 8) / p["flops_per_s"] > (
        flops.forward_bytes(a, 8) / p["hbm_bytes_per_s"])


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peak("TPU v99")
