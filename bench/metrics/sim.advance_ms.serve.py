"""Simulator: milliseconds per co-sim round spent flushing the round's
submissions, advancing the shared cluster and resolving started
successors, from the program's ``sim.advance`` spans over its
``cosim.advance`` count. None where the program has no spans."""


def read(run):
    try:
        from repro import telemetry
    except ImportError:
        return None
    t = telemetry.totals(run.t_open, run.t_close)
    if t is None or not t["cosim.advance"].count:
        return None
    return 1e3 * t["sim.advance"].seconds / t["cosim.advance"].count
