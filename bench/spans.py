"""Readings of the program's spans (``repro.telemetry``) over a run's
window, for the metric readers in ``metrics/``. Each returns None where
the program has no such span, recorded none in the window, or no longer
holds the window's start."""
from __future__ import annotations

from typing import Optional, Sequence


def ms_per(run, names: Sequence[str], per: str) -> Optional[float]:
    """Milliseconds of the spans ``names`` together, per span ``per``."""
    try:
        from repro import telemetry
    except ImportError:
        return None
    t = telemetry.totals(run.t_open, run.t_close)
    if t is None or not t[per].count or not any(t[n].count for n in names):
        return None
    return 1e3 * sum(t[n].seconds for n in names) / t[per].count
