"""Benchmark harness — one module per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run [--only NAME]

Prints ``name,us_per_call,derived`` CSV rows:
  convergence    — paper Fig. 2 (objective vs epoch, sync + delays)
  speedup        — paper Table 1 (event-driven coordination scalability)
  kernels        — fused-kernel HBM-traffic roofline projections
  roofline       — §Roofline table from the dry-run artifacts
"""
import argparse
import sys
import traceback

from . import convergence, kernels_bench, roofline_bench, speedup

SUITES = {
    "convergence": convergence.main,
    "speedup": speedup.main,
    "kernels": kernels_bench.main,
    "roofline": roofline_bench.main,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, choices=list(SUITES))
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    print("name,us_per_call,derived")
    failed = []
    for name, fn in SUITES.items():
        if args.only and name != args.only:
            continue
        try:
            fn(emit=print)
        except Exception as e:
            failed.append(name)
            print(f"{name}_FAILED,0,{e!r}", flush=True)
            traceback.print_exc(file=sys.stderr)
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
