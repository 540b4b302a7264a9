"""Policy forward, host side: milliseconds per ``act_batch`` call spent
launching the forward (the observations' copy in and the dispatch) and
turning its outputs into actions (argmax, exploration), from the
program's ``forward.launch`` and ``forward.fetch`` spans. None where the
program has no spans."""


def read(run):
    try:
        from repro import telemetry
    except ImportError:
        return None
    t = telemetry.totals(run.t_open, run.t_close)
    if t is None or not t["forward.wait"].count:
        return None
    host = t["forward.launch"].seconds + t["forward.fetch"].seconds
    return 1e3 * host / t["forward.wait"].count
