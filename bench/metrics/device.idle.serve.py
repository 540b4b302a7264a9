"""Share of the traced serve window in which no operation ran on the
device (profiler trace)."""
import readers


def read(run):
    return readers.idle_pct(run)
