"""State encoding: milliseconds per co-sim round spent pushing the
waiting tenants' snapshots into their histories and refreshing their
observations, from the program's ``state.encode`` spans over its
``cosim.advance`` count. None where the program has no spans."""


def read(run):
    try:
        from repro import telemetry
    except ImportError:
        return None
    t = telemetry.totals(run.t_open, run.t_close)
    if t is None or not t["cosim.advance"].count:
        return None
    return 1e3 * t["state.encode"].seconds / t["cosim.advance"].count
