"""Program spans and counters of the serving path.

``span(name)`` marks one step of the host's work. While the program is
recording it opens a ``mirage.<name>`` annotation in the JAX profiler's
host plane, on the clock of the device's trace, and appends one record
(name, start, end, parent span, request) to a bounded in-memory log;
otherwise it returns a shared no-op context and costs one ``recording()``
check. The request is the service round: ``span("service.round",
request=n)`` sets it, and every span opened inside inherits it.

The program records while a JAX profiler session captures, so taking a
profile switches the spans on, and inside ``capture()``. ``totals(t0, t1)``
sums, per name, the spans that started in ``[t0, t1)``, with the counters
over the same interval. Counter: ``host.gc``, the pauses of Python's
garbage collector. ``CompileLog`` counts backend compiles per program.

The log is process-wide, as the profiler session that switches it on is,
and belongs to one thread: the service's loop is synchronous.
"""
from __future__ import annotations

import collections
import contextlib
import gc
import time
from typing import Dict, List, NamedTuple, Optional

import numpy as np
from jax.profiler import TraceAnnotation

# a private hook of JAX; without it, only capture() switches spans on
try:
    from jax._src.lib import _profiler
    _profiling = _profiler.TraceMe.is_enabled
except (ImportError, AttributeError):
    def _profiling() -> bool:
        return False

RING = 1 << 18                    # records a log keeps (about 9 MB)
_capturing = 0


def recording() -> bool:
    """True while a JAX profiler session captures or inside ``capture()``."""
    return _capturing > 0 or _profiling()


@contextlib.contextmanager
def capture():
    """Record spans and counters inside the block, profiler or not."""
    global _capturing
    _capturing += 1
    try:
        yield
    finally:
        _capturing -= 1


class _Log:
    """Bounded ring of records; record ``seq`` sits at ``seq % size``, and
    the oldest are overwritten once the ring is full."""

    def __init__(self, size: int):
        self.size = size
        self.n = 0
        self.name = np.zeros(size, np.int32)
        self.start = np.zeros(size, np.float64)
        self.end = np.full(size, np.nan, np.float64)
        self.parent = np.full(size, -1, np.int64)
        self.request = np.full(size, -1, np.int64)

    def open(self, name: int, t: float, parent: int, request: int) -> int:
        seq = self.n
        i = seq % self.size
        self.name[i], self.start[i], self.end[i] = name, t, np.nan
        self.parent[i], self.request[i] = parent, request
        self.n = seq + 1
        return seq

    def close(self, seq: int, t: float) -> None:
        if 0 < self.n - seq <= self.size:
            self.end[seq % self.size] = t

    def window(self, t0: float, t1: float):
        """(slots of the held records in sequence order, sequence number
        of the first, mask of the closed ones that started in [t0, t1));
        None where records of the window's start were overwritten."""
        lo = max(self.n - self.size, 0)
        slots = np.arange(lo, self.n) % self.size
        start = self.start[slots]
        if lo > 0 and start[0] >= t0:
            return None
        keep = (start >= t0) & (start < t1) & ~np.isnan(self.end[slots])
        return slots, lo, keep


_spans: Optional[_Log] = None
_gcs: Optional[_Log] = None
_names: List[str] = []
_ids: Dict[str, int] = {}
_open = -1                        # sequence number of the innermost span
_request = -1


def reset(size: int = RING) -> None:
    """Empty the span and counter logs, each a ring of ``size`` records."""
    global _spans, _gcs, _open
    _spans, _gcs, _open = _Log(size), _Log(size), -1


def dropped() -> int:
    """Span records overwritten since the last ``reset``."""
    return max(_spans.n - _spans.size, 0) if _spans else 0


def _id(name: str) -> int:
    i = _ids.get(name)
    if i is None:
        i = _ids[name] = len(_names)
        _names.append(name)
    return i


class _Span:
    __slots__ = ("name", "request", "seq", "outer", "ann")

    def __init__(self, name: str, request: Optional[int]):
        self.name = name
        self.request = request

    def __enter__(self):
        global _open, _request
        if _spans is None:
            reset()
        self.ann = TraceAnnotation("mirage." + self.name)
        self.ann.__enter__()
        self.outer = (_open, _request)
        if self.request is not None:
            _request = int(self.request)
        self.seq = _spans.open(_id(self.name), time.perf_counter(), _open,
                               _request)
        _open = self.seq
        return self

    def __exit__(self, *exc):
        global _open, _request
        _spans.close(self.seq, time.perf_counter())
        _open, _request = self.outer
        self.ann.__exit__(*exc)
        return False


_OFF = contextlib.nullcontext()


def span(name: str, request: Optional[int] = None):
    """A host span ``name``; ``request`` sets the round of it and of every
    span opened inside. A shared no-op unless ``recording()``."""
    if not recording():
        return _OFF
    return _Span(name, request)


_gc_open = None                   # (annotation, start) of a pause


def _on_gc(phase: str, info: Dict) -> None:
    """``gc.callbacks`` hook: one ``host.gc`` record per pause."""
    global _gc_open
    if phase == "start":
        if recording():
            ann = TraceAnnotation("mirage.host.gc")
            ann.__enter__()
            _gc_open = (ann, time.perf_counter())
    elif _gc_open is not None:
        ann, t0 = _gc_open
        _gc_open = None
        if _gcs is None:
            reset()
        _gcs.close(_gcs.open(0, t0, -1, _request), time.perf_counter())
        ann.__exit__(None, None, None)


if _on_gc not in gc.callbacks:
    gc.callbacks.append(_on_gc)


class Total(NamedTuple):
    count: int
    seconds: float
    self_s: float                 # seconds less those of child spans


class Totals(dict):
    """Name -> ``Total``; a name with no record reads as zero."""

    def __missing__(self, name: str) -> Total:
        return Total(0, 0.0, 0.0)


def totals(t0: float, t1: float) -> Optional[Totals]:
    """Count, seconds and self seconds per span name of the spans that
    started in ``[t0, t1)``, and the counters (``host.gc``) over the same
    interval; None where a log no longer holds the interval's start."""
    out = Totals()
    if _spans is None:
        return out
    got = _spans.window(t0, t1)
    gcs = _gcs.window(t0, t1)
    if got is None or gcs is None:
        return None
    slots, lo, keep = got
    dur = _spans.end[slots] - _spans.start[slots]
    parent = _spans.parent[slots]
    child = (parent >= lo) & ~np.isnan(dur)
    covered = np.bincount(parent[child] - lo, weights=dur[child],
                          minlength=len(slots))
    names = _spans.name[slots][keep]
    n = np.bincount(names, minlength=len(_names))
    s = np.bincount(names, weights=dur[keep], minlength=len(_names))
    own = np.bincount(names, weights=(dur - covered)[keep],
                      minlength=len(_names))
    for i in np.flatnonzero(n):
        out[_names[i]] = Total(int(n[i]), float(s[i]), float(own[i]))
    g_slots, _, g_keep = gcs
    g = (_gcs.end - _gcs.start)[g_slots][g_keep]
    if len(g):
        out["host.gc"] = Total(len(g), float(g.sum()), float(g.sum()))
    return out


class CompileLog:
    """Backend compiles (count and seconds) per jitted program, and
    persistent-cache hits, from JAX's monitoring events."""

    def __init__(self):
        import jax
        self.programs = collections.defaultdict(lambda: [0, 0.0])
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            entry = self.programs[kw.get("fun_name", "?")]
            entry[0] += 1
            entry[1] += secs

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def total_s(self) -> float:
        return sum(s for _, s in self.programs.values())
