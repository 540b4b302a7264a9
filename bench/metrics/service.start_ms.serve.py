"""Service start: milliseconds per episode to build a service (world and
tenant lanes) and start it (fork the cluster, warm the histories up,
inject the predecessors), from the program's ``service.build`` and
``service.start`` spans. None where the program has no spans."""


def read(run):
    try:
        from repro import telemetry
    except ImportError:
        return None
    t = telemetry.totals(run.t_open, run.t_close)
    if t is None or not t["service.start"].count:
        return None
    s = t["service.build"].seconds + t["service.start"].seconds
    return 1e3 * s / t["service.start"].count
