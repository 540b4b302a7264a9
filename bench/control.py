"""Readings that set the limits of ``correct``: the program's numbers and
the lower-precision control's, over many seeds in one process.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds <n> ...

For each seed it makes one run of the cell as ``run.py`` does and prints the
program's compared numbers; then, on the same sampled decisions, the
float8 control put in the program's place, and the numbers it reads. The benchmark's own runs never run the
control. Needs the chip, like ``run.py``; one process holds it.
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


def control_numbers(run):
    import check
    return check.forward_numbers(run.agent, run.sample, run.head,
                                 precision="float8")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    import harness
    rows = []
    for seed in args.seeds:
        t0 = T_PROCESS if not rows else time.perf_counter()
        try:
            res, run = harness.measure(harness.load_cell(args.workload),
                                       seed, args.seconds, False, t0)
        except harness.NoChip as e:
            print(f"control: {e}", file=sys.stderr)
            return 3
        prog = {k: c["value"] for k, c in res["checks"].items()}
        ctl = control_numbers(run)
        rows.append({"seed": seed, "program": prog, "control": ctl,
                     "metrics": {k: m["value"]
                                 for k, m in res["metrics"].items()}})
        print("CONTROL " + json.dumps(rows[-1]), flush=True)
    names = [k for k in rows[0]["control"] if k in rows[0]["program"]]
    summary = {k: {"program_max": max(r["program"][k] for r in rows),
                   "control_min": min(r["control"][k] for r in rows)}
               for k in names}
    print(json.dumps({"workload": args.workload, "seeds": len(rows),
                      "readings": summary}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
