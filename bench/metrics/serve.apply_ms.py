"""Service layer: host milliseconds per decision spent in the lanes'
``apply`` (journal, then step the simulator) and in the co-sim world's
``advance_round``."""


def read(run):
    n = run.counts["decisions"]
    if not n:
        return None
    s = run.spans.seconds
    return 1e3 * (s.get("apply", 0.0) + s.get("advance", 0.0)) / n
