# One function per paper table/figure. Prints ``name,us_per_call,derived``
# CSV lines; JSON artifacts land in experiments/bench/.
#
# Scale knobs: REPRO_BENCH_QUICK=0 for paper-scale episode counts (slow);
# default is the quick profile (~15 min on this CPU container).
#
# Usage: ``python -m benchmarks.run [filter ...]`` — with arguments, only
# suites whose names contain one of the (case-insensitive) filters run,
# e.g. ``python -m benchmarks.run rollout`` for the tracked RL rollout
# throughput number alone. scripts/check_bench.py uses this to gate
# regressions against the committed experiments/bench/*.json baselines.
import sys
import time
import traceback


def suites():
    from . import (bench_eval, bench_interruption, bench_kernels,
                   bench_moe_gating, bench_roofline, bench_serve,
                   bench_simulator)
    return [
        ("simulator (Table 1, 5.2)", bench_simulator.run),
        ("rollout throughput (5.1)", bench_simulator.bench_rollout_throughput),
        ("rollout faulty (robustness)", bench_simulator.bench_rollout_faulty),
        ("eval throughput (6, Figs. 8-9 grid)", bench_eval.run),
        ("serve decisions (multi-tenant service)", bench_serve.run),
        ("kernels", bench_kernels.run),
        ("moe gating (4.7)", bench_moe_gating.run),
        ("roofline (g)", bench_roofline.run),
        ("interruption (Figs. 8-10, abstract)", bench_interruption.run),
    ]


def main(argv=None) -> None:
    args = sys.argv[1:] if argv is None else argv
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    selected = suites()
    if args:
        selected = [s for s in selected
                    if any(a.lower() in s[0].lower() for a in args)]
        if not selected:
            print(f"no benchmark suite matches {args!r}; available: "
                  + ", ".join(name for name, _ in suites()))
            sys.exit(2)
    t0 = time.time()
    failed = []
    for name, fn in selected:
        print(f"# --- {name} ---", flush=True)
        try:
            fn()
        except Exception as e:
            failed.append(name)
            print(f"bench_error_{name.split()[0]},0.0,{type(e).__name__}: {e}")
            traceback.print_exc()
    print(f"# total wall: {time.time()-t0:.1f}s")
    if failed:
        sys.exit(1)


if __name__ == '__main__':
    main()
