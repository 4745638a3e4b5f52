"""Benchmark driver: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows:
  fig10_*     — paper Fig. 10 (model size + throughput across precisions)
  tableII_*   — paper Table II (MAC/qmm unit per precision mode)
  tableIII_*  — paper Table III (FASST NAF unit per function)
  tableIV_*   — paper Table IV (end-to-end accelerator throughput)
  roofline_*  — per (arch x shape) roofline bound from the dry-run records
  serve_*     — request-level engine tok/s per weight policy

``--smoke`` runs the reduced sweeps (modules that support it) so CI's
bench-smoke job can accumulate a per-PR perf trajectory cheaply.
"""

from __future__ import annotations

import argparse
import inspect
import sys
import traceback


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced sweeps where supported")
    args = ap.parse_args()
    from repro.runtime import configure_compile_cache
    configure_compile_cache()

    print("name,us_per_call,derived")
    from . import (bench_fasst, bench_qmm, bench_quant_formats,
                   bench_serving, bench_throughput, roofline)
    failed = []
    for mod in (bench_quant_formats, bench_qmm, bench_fasst,
                bench_throughput, bench_serving, roofline):
        try:
            if "smoke" in inspect.signature(mod.run).parameters:
                mod.run(smoke=args.smoke)
            else:
                mod.run()
        except Exception:
            failed.append(mod.__name__)
            print(f"# {mod.__name__} FAILED", file=sys.stderr)
            traceback.print_exc()
    if failed:
        # later modules still ran (partial trajectories stay useful),
        # but CI must see benchmark breakage as a red check
        sys.exit(f"benchmark modules failed: {', '.join(failed)}")


if __name__ == "__main__":
    main()
