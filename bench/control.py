"""Readings that set a cell's check limit, for several seeds in one
process: the program's mean served gap (as every run reads it) and
the control's (the reference at fp8 activations, the step below the
configuration's precision, at the same prompts and served tokens),
each with its widest gap.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds 1 2 3

Each seed deploys the cell afresh, warms it up and serves its traffic
for a window of ``--seconds`` at the cell's own load, as a run does,
then checks the usual sample. One JSON line per
seed, then the largest program reading and the smallest control
reading. The benchmark's own runs never run this.
"""

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:1] = [ROOT, os.path.join(ROOT, "src")]

from bench.harness import run_cell  # noqa: E402


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    program, control = [], []
    for seed in args.seeds:
        t0 = time.perf_counter()
        res = run_cell(args.workload, seed, args.seconds, False,
                       control=True)
        chk = res["check"]
        program.append(chk["gap_mean"]["value"])
        control.append(chk["control_gap_mean"]["value"])
        print(json.dumps({"seed": seed, "gap_mean": program[-1],
                          "control_gap_mean": control[-1],
                          "widest": res["env"]["widest"],
                          "control_widest": res["env"]["control_widest"],
                          "streams_short": chk["streams_short"]["value"],
                          "attempted": res["attempted"],
                          "failed": res["failed"],
                          "seconds": time.perf_counter() - t0}), flush=True)
    print(json.dumps({"workload": args.workload, "seeds": len(program),
                      "gap_mean_max": max(program),
                      "control_gap_mean_min": min(control)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
