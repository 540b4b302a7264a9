"""Host runtime: milliseconds of Python garbage-collector pauses per
second of the window, from the program's ``host.gc`` counter. None where
the program has no spans or recorded none in the window."""


def read(run):
    try:
        from repro import telemetry
    except ImportError:
        return None
    t = telemetry.totals(run.t_open, run.t_close)
    if t is None or not t["service.round"].count:
        return None
    return 1e3 * t["host.gc"].seconds / run.window_s
