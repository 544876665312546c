"""Benchmark runner: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.  Mapping to the paper:

  scalability        Fig. 2 / Fig. 14(a)  decision time vs active jobs
  overhead_breakdown Fig. 14(b)           schedule/pack/migrate split
  e2e_jct            Figs. 9, 12, 17      Avg JCT / makespan comparisons
  vs_optimization    Fig. 11              vs Gavel + migration ablation
  fairness           Fig. 13              FTF-ratio CDF stats
  parallelism        Fig. 15              parallelism-strategy packing
  noise              Fig. 16              profiling-noise sensitivity
  profiling_cost     Fig. 18              estimator quality
  sim_fidelity       Table 2              simulator variance
  matching_microbench (beyond paper)      LAP solver comparison
  kernels_microbench  (substrate)         Pallas kernels (interpret off-TPU)
  roofline_report     (substrate)         dry-run roofline table
  perf_summary        (substrate)         baseline vs optimized dominant terms

Run ``benchmarks/run_dryrun_sweep.sh`` first to populate the roofline
results (it needs its own process group for the 512-device XLA flag).
"""

from __future__ import annotations

import sys
import time


MODULES = [
    "scalability",
    "overhead_breakdown",
    "e2e_jct",
    "vs_optimization",
    "fairness",
    "parallelism",
    "compatibility",
    "noise",
    "profiling_cost",
    "sim_fidelity",
    "matching_microbench",
    "kernels_microbench",
    "roofline_report",
    "perf_summary",
]


def main() -> None:
    only = sys.argv[1:] or MODULES
    print("name,us_per_call,derived")
    failures = []
    for name in only:
        t0 = time.perf_counter()
        try:
            mod = __import__(f"benchmarks.{name}", fromlist=["main"])
            mod.main(print_csv=True)
        except Exception as e:  # noqa: BLE001
            failures.append((name, repr(e)))
            print(f"{name}/ERROR,0,{e!r}")
        print(f"{name}/_wall,{(time.perf_counter() - t0) * 1e6:.0f},elapsed")
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
