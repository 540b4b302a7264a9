"""State encoding: cluster snapshots encoded per co-sim round, from the
program's ``state.snapshot`` spans over its ``cosim.advance`` count. The
one encode of each episode start's inject (one ``cosim.inject`` span a
start) is left out, so that one encode per round shared by every waiting
tenant reads 1 and one per tenant would read the tenant count. None where
the program has no such spans."""


def read(run):
    try:
        from repro import telemetry
    except ImportError:
        return None
    t = telemetry.totals(run.t_open, run.t_close)
    if (t is None or not t["cosim.advance"].count
            or not t["state.snapshot"].count):
        return None
    in_rounds = t["state.snapshot"].count - t["cosim.inject"].count
    return in_rounds / t["cosim.advance"].count
