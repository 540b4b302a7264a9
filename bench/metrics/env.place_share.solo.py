"""Scalar environment: the share of tenant starts whose predecessor was
placed off the background timeline (a proved start, or a cascade synced
past the start instant), from the program's ``env.place`` spans less the
``env.replay`` spans of placements that replayed the queue wait from the
start instant, over its ``env.reset`` spans. None where the program has
no such spans, or placed no start off the timeline in the window."""


def read(run):
    try:
        from repro import telemetry
    except ImportError:
        return None
    t = telemetry.totals(run.t_open, run.t_close)
    if t is None or not t["env.reset"].count or not t["env.place"].count:
        return None
    return ((t["env.place"].count - t["env.replay"].count)
            / t["env.reset"].count)
