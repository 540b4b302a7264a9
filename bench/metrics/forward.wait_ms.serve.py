"""Policy forward, the device's share seen from the host: milliseconds
per ``act_batch`` call spent waiting for the launched forward's outputs
on the host (its device time, the copy out and the runtime's wake-up),
from the program's ``forward.wait`` spans. None where the program has no
spans."""


def read(run):
    try:
        from repro import telemetry
    except ImportError:
        return None
    t = telemetry.totals(run.t_open, run.t_close)
    if t is None or not t["forward.wait"].count:
        return None
    w = t["forward.wait"]
    return 1e3 * w.seconds / w.count
