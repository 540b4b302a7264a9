"""The benchmark's harness: one run of one cell.

A cell is an entry of ``workloads`` in ``BENCHMARK.json``: a configuration
(``bench/configs/<config>.json``) under a traffic mix
(``bench/traffic/<traffic>.json``). The traffic names its entry
(``bench/entries/<entry>.py``), and every metric is read by a reader of its
own (``bench/metrics/<metric>.py``). A new cell, configuration, traffic or
metric is a new file and a new entry in ``BENCHMARK.json``; nothing here
names one.

A run builds the system from the seed, warms up every shape its window
will use, measures for ``seconds`` with the benchmark's own clock and host
spans (and, with ``trace``, the profiler), reads the device's peak memory,
frees the program's state, and then decides ``correct`` by the comparison
in ``check.py``.
"""
from __future__ import annotations

import collections
import contextlib
import gc
import importlib.util
import json
import os
import shutil
import time
from pathlib import Path
from typing import Callable, Dict, List, Optional

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def _load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _read_json(path: Path) -> Dict:
    with open(path) as f:
        return json.load(f)


def load_cell(workload: str, manifest: Optional[Dict] = None) -> Dict:
    """The cell's spec: its manifest entry, configuration, traffic and the
    manifest's metric entries."""
    manifest = manifest or _read_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; known: {sorted(cells)}")
    cell = cells[workload]
    return {"cell": cell,
            "config": _read_json(BENCH / "configs" / f"{cell['config']}.json"),
            "traffic": _read_json(BENCH / "traffic"
                                  / f"{cell['traffic']}.json"),
            "end_to_end": manifest["end_to_end"],
            "per_layer": manifest["per_layer"]}


def metrics_of(spec: Dict, trace: bool) -> List[Dict]:
    """The metrics this cell reports: end-to-end ones without a trace,
    per-layer ones with it."""
    name = spec["cell"]["name"]
    e2e = [m for m in spec["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    if not trace:
        return e2e
    moved = {m["name"] for m in e2e}
    return [m for m in spec["per_layer"]
            if (name in m["workloads"] if "workloads" in m
                else m["moves"] in moved)]


class Spans:
    """Host spans of the benchmark's own wrappers: count and seconds per
    name, and with ``annotate`` a ``bench.<name>`` span in the profiler's
    trace on the same clock as the device."""

    def __init__(self, annotate: bool, clock: Callable[[], float]):
        self.annotate = annotate
        self.clock = clock
        self.reset()

    def reset(self) -> None:
        self.seconds: Dict[str, float] = collections.defaultdict(float)
        self.count: Dict[str, int] = collections.defaultdict(int)

    @contextlib.contextmanager
    def __call__(self, name: str):
        ann = None
        if self.annotate:
            import jax
            ann = jax.profiler.TraceAnnotation("bench." + name)
            ann.__enter__()
        t0 = self.clock()
        try:
            yield
        finally:
            self.seconds[name] += self.clock() - t0
            self.count[name] += 1
            if ann is not None:
                ann.__exit__(None, None, None)

    def mean_ms(self, name: str) -> Optional[float]:
        n = self.count.get(name, 0)
        return 1e3 * self.seconds[name] / n if n else None


class CompileLog:
    """Backend compiles (with their seconds) and persistent-cache hits,
    from JAX's monitoring events."""

    def __init__(self):
        import jax
        self.n = 0
        self.seconds = 0.0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.seconds += secs

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1


class Run:
    """One run: its spec, clock, spans and counts, handed to the entry
    module and then to the metric readers."""

    def __init__(self, spec: Dict, seed: int, seconds: float, trace: bool,
                 t_process: float, clock: Callable[[], float]):
        self.spec = spec
        self.name = spec["cell"]["name"]
        self.config = spec["config"]
        self.agent = spec["config"]["agent"]
        self.traffic = spec["traffic"]
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.t_process = t_process
        self.clock = clock
        self.spans = Spans(self.trace, clock)
        self.counts: Dict[str, float] = collections.defaultdict(int)
        self.latencies: List[float] = []
        self.batch_sizes: Dict[int, int] = collections.defaultdict(int)
        self.window_end = float("inf")
        self.t_open = self.t_close = None
        self.setup_s = None
        self.window_s = None
        self.reduction = None
        self.peak: Dict = {}

    def rng(self, stream: int) -> np.random.Generator:
        """An independent generator of this run's seed."""
        return np.random.default_rng([self.seed, stream])

    def say(self, msg: str) -> None:
        print(f"[bench] {msg}", flush=True)

    @property
    def open(self) -> bool:
        return self.t_open is not None and self.t_close is None

    def past_end(self) -> bool:
        return self.clock() >= self.window_end


def _trace_dir(run: Run) -> Path:
    return BENCH / ".trace" / f"{run.name}.{run.seed}"


def execute(spec: Dict, seed: int, seconds: float, trace: bool,
            t_process: float, require_tpu: bool = True,
            clock: Callable[[], float] = time.perf_counter) -> Dict:
    """One run of the cell ``spec``; returns the result object."""
    return measure(spec, seed, seconds, trace, t_process, require_tpu,
                   clock)[0]


def measure(spec: Dict, seed: int, seconds: float, trace: bool,
            t_process: float, require_tpu: bool = True,
            clock: Callable[[], float] = time.perf_counter):
    """One run of the cell ``spec``; returns (result object, ``Run``)."""
    import jax
    devices = jax.devices()
    dev = devices[0]
    chips = int(spec["cell"]["chips"])
    if require_tpu and (dev.platform != "tpu" or len(devices) < chips):
        raise NoChip(f"cell needs {chips} TPU chip(s); JAX has "
                     f"{len(devices)} {dev.platform!r} device(s)")
    from repro.launch.compile_cache import use_compile_cache
    cache_dir = use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    # no eviction: a cell's programs are few and small, and eviction fails
    # every write once the directory holds an entry without its access
    # time, as entries written by a process without eviction are
    jax.config.update("jax_compilation_cache_max_size", -1)
    import peaks
    run = Run(spec, seed, seconds, trace, t_process, clock)
    run.peak = peaks.peak(dev.device_kind) if require_tpu else {
        "flops_per_s": float("nan"), "hbm_bytes_per_s": float("nan")}
    run.say(f"device {dev.device_kind} x {len(devices)} ({dev.platform}); "
            f"compile cache {cache_dir}")
    log = CompileLog()
    entry = _load_module(BENCH / "entries" / f"{run.traffic['entry']}.py")
    session = entry.setup(run)

    tdir = _trace_dir(run)
    if trace:
        shutil.rmtree(tdir, ignore_errors=True)
        jax.profiler.start_trace(str(tdir))
    n_compiles = log.n
    run.spans.reset()
    run.t_open = clock()
    run.setup_s = run.t_open - t_process
    run.window_end = run.t_open + run.seconds
    ann = jax.profiler.TraceAnnotation("bench.window") if trace else None
    if ann:
        ann.__enter__()
    session.window(run)
    run.t_close = clock()
    if ann:
        ann.__exit__(None, None, None)
    run.window_s = run.t_close - run.t_open
    in_window = log.n - n_compiles
    if trace:
        jax.profiler.stop_trace()
        import devtrace
        run.reduction = devtrace.reduce(devtrace.load(str(tdir)))
        shutil.rmtree(tdir, ignore_errors=True)
    mem = (dev.memory_stats() or {}).get("peak_bytes_in_use", 0)
    run.say(f"setup {run.setup_s:.2f}s ({log.n} compiles, "
            f"{log.seconds:.1f}s compiling, {log.hits} persistent-cache "
            f"hits); window {run.window_s:.2f}s; compiles inside the "
            f"window: {in_window}")
    session.report(run)

    session.close()
    del session
    gc.collect()
    checks = entry.checks(run)
    checks["compiles_in_window"] = {"value": in_window, "limit": 0}

    import check
    metrics = {}
    for m in metrics_of(spec, trace):
        value = _load_module(BENCH / "metrics" / f"{m['name']}.py").read(run)
        if value is None:
            if not trace:
                raise RuntimeError(f"end-to-end metric {m['name']} read "
                                   "nothing")
            continue
        metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": int(mem)}
    result = {"correct": check.passed(checks),
              "attempted": int(run.counts["attempted"]),
              "failed": int(run.counts["failed"]),
              "metrics": metrics, "device": device}
    if trace:
        r = run.reduction
        device["busy_s"] = r.busy_s
        device["window_s"] = r.window_s
        result["breakdown"] = {
            "device_ops": [[k, v] for k, v in r.top_ops],
            "idle_gaps": [[k, v] for k, v in r.idle_gaps]}
    result["checks"] = checks
    return result, run
