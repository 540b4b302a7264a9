"""Run one cell of the benchmark and print its result.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout, on a machine with the chips the cell asks
for; one process, which holds the chip. Without a TPU it exits with code 3
and prints no result. Earlier lines of standard output report the device,
the set-up, the compiles inside the window and the decision mix; the last
line is the result object. The numbers compared for ``correct`` are also
the last lines of standard error.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(CHECKOUT, "src"))
# JAX's persistent compilation cache lives at a fixed path inside the
# checkout, whatever the environment says, so that two checkouts share
# nothing and a checkout's later runs find every program compiled
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(CHECKOUT, ".jax_cache")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    import harness
    spec = harness.load_cell(args.workload)
    try:
        result = harness.execute(spec, args.seed, args.seconds,
                                 bool(args.trace), T_PROCESS)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
