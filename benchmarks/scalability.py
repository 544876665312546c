"""Fig. 2 / Fig. 14(a): decision-making time vs number of active jobs,
plus the matching-engine cluster-scale sweep.

Part 1 (paper figure): 256-GPU cluster (64 nodes x 4), one full scheduling
round per measurement.  Validates the headline scalability claim: Tesserae
decides in < 1.6 s with 2048 active jobs (and < 1 s at 3000 in the paper's
§4.2 discussion), while Gavel's LP grows superlinearly in its O(n^2)
packing variables and POP only partially recovers.

Part 2 (beyond paper): one full Tesserae round at growing cluster scale —
256, 1024 and 2048 GPUs — with the migration/packing LAPs dispatched
through each matching-engine backend (``scipy`` vs ``auction`` vs
``auction_kernel``), demonstrating that backend choice is one config knob
on the scheduler.  Results are appended to a JSON perf record
(``--json``, default ``scalability.json``).
"""

from __future__ import annotations

import argparse
import json
import time
from typing import Dict, List

from benchmarks.common import csv_row
from repro.core.cluster import ClusterSpec
from repro.core.policies import GavelPolicy, PopPolicy, TiresiasPolicy
from repro.core.profiler import ThroughputProfile
from repro.core.scheduler import TesseraeScheduler
from repro.core.traces import synthetic_active_jobs

CLUSTER = ClusterSpec(64, 4)  # 256 GPUs
JOB_COUNTS = [128, 512, 1024, 2048]
LP_JOB_CAP = 1024  # LP baselines above this take minutes (that's the point)

#: Part-2 sweep: (nodes, gpus_per_node) up to a 2048-GPU cluster (512 nodes
#: -> the Algorithm-2 fan-out batches 512 node-pair LAPs per logical node).
SCALE_CLUSTERS = [(64, 4), (256, 4), (512, 4)]
SCALE_BACKENDS = ["scipy", "auction", "auction_kernel"]
SCALE_JOBS = 512


def tesserae_round_time(num_jobs: int, profile, cluster=CLUSTER, backend="auto") -> dict:
    """One cold full round (the PR-1 comparable ``total_s``) plus one WARM
    round: the scheduler's persistent ``MatchContext`` carries the packing
    / migration price state from the previous round, so ``warm_total_s``
    is the steady-state per-round decision time (placements change little
    round-to-round; identical fan-outs memo-hit outright)."""
    jobs = synthetic_active_jobs(num_jobs, seed=1, profile=profile)
    sched = TesseraeScheduler(
        cluster, TiresiasPolicy(profile), profile, lap_backend=backend
    )
    d1 = sched.decide(jobs, now=0.0)
    sched.match_context.reset()  # keep total_s comparable to the PR-1 record
    t0 = time.perf_counter()
    d2 = sched.decide(jobs, now=360.0, prev_plan=d1.plan)
    total = time.perf_counter() - t0
    t0 = time.perf_counter()
    d3 = sched.decide(jobs, now=720.0, prev_plan=d2.plan)
    warm_total = time.perf_counter() - t0
    return {
        "total_s": total,
        "warm_total_s": warm_total,
        "warm_migrate_s": d3.timings["migrate_s"],
        # identity-keyed context telemetry of the warm round: memo/warm
        # instance counts + bid iterations (regression signal for the
        # steady-state fast path, independent of wall clock)
        "warm_match_stats": dict(d3.match_stats),
        **d2.timings,
    }


def lp_round_time(num_jobs: int, profile, pop: bool) -> float:
    jobs = synthetic_active_jobs(num_jobs, seed=1, profile=profile)
    pol = PopPolicy(profile) if pop else GavelPolicy(profile)
    t0 = time.perf_counter()
    pol.refresh(jobs, CLUSTER)
    solve = time.perf_counter() - t0
    return solve


def bench_paper_figure(profile, rows: List[str], records: List[Dict]) -> None:
    claim = None
    for n in JOB_COUNTS:
        t = tesserae_round_time(n, profile)
        rows.append(
            csv_row(
                f"scalability/tesserae_jobs{n}",
                t["total_s"] * 1e6,
                f"decision_s={t['total_s']:.3f};pack_s={t['pack_s']:.3f};migrate_s={t['migrate_s']:.3f}",
            )
        )
        records.append(
            {"bench": "decision_time", "jobs": n, "gpus": CLUSTER.num_gpus, **t}
        )
        if n == 2048:
            claim = t["total_s"]
        if n <= LP_JOB_CAP:
            g = lp_round_time(n, profile, pop=False)
            p = lp_round_time(n, profile, pop=True)
            rows.append(csv_row(f"scalability/gavel_jobs{n}", g * 1e6, f"lp_solve_s={g:.3f}"))
            rows.append(csv_row(f"scalability/pop_jobs{n}", p * 1e6, f"lp_solve_s={p:.3f}"))
            records.append({"bench": "lp_baseline", "policy": "gavel", "jobs": n, "time_s": g})
            records.append({"bench": "lp_baseline", "policy": "pop", "jobs": n, "time_s": p})
    rows.append(
        csv_row(
            "scalability/claim_2048jobs_under_1.6s",
            (claim or 0) * 1e6,
            f"paper_claim=1.6s;ours={claim:.3f}s;pass={claim < 1.6}",
        )
    )
    records.append({"bench": "claim", "jobs": 2048, "time_s": claim, "pass": claim < 1.6})


def bench_cluster_scale(profile, rows: List[str], records: List[Dict]) -> None:
    """One full round per (cluster size x engine backend)."""
    import jax

    on_tpu = jax.default_backend() == "tpu"
    for nodes, gpn in SCALE_CLUSTERS:
        cluster = ClusterSpec(nodes, gpn)
        for backend in SCALE_BACKENDS:
            if backend == "auction_kernel" and not on_tpu:
                # interpret-mode Pallas is a correctness tool: one python
                # grid step per instance makes a full e2e round take ~8 min
                # even on the 64-node cluster.  The kernel backend sweeps
                # here on real TPU only; on CPU its interpret-mode timings
                # live in matching_microbench.py at bounded batch sizes.
                continue
            t = tesserae_round_time(SCALE_JOBS, profile, cluster, backend)
            rows.append(
                csv_row(
                    f"scalability/cluster{cluster.num_gpus}gpu_{backend}",
                    t["total_s"] * 1e6,
                    f"gpus={cluster.num_gpus};jobs={SCALE_JOBS};"
                    f"migrate_s={t['migrate_s']:.3f};pack_s={t['pack_s']:.3f}",
                )
            )
            records.append(
                {
                    "bench": "cluster_scale",
                    "backend": backend,
                    "nodes": nodes,
                    "gpus": cluster.num_gpus,
                    "jobs": SCALE_JOBS,
                    **t,
                }
            )


def main(argv=None, print_csv: bool = True) -> List[str]:
    """``argv``: CLI arg list (cluster sweep on by default); ``None`` when
    driven programmatically by ``benchmarks/run.py``, which runs only the
    Part-1 paper figure — the multi-minute Part-2 sweep (auction on a
    2048-GPU fan-out is ~50 s/round on CPU) is an explicit-CLI feature."""
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument(
        "--json",
        default="scalability.json",
        help="path of the JSON perf record (written at the end of the run)",
    )
    parser.add_argument(
        "--skip-cluster-sweep",
        action="store_true",
        help="only run the paper-figure measurements (Part 1)",
    )
    from_cli = argv is not None
    args = parser.parse_args(list(argv) if from_cli else [])

    profile = ThroughputProfile()
    rows: List[str] = []
    records: List[Dict] = []
    bench_paper_figure(profile, rows, records)
    if from_cli and not args.skip_cluster_sweep:
        bench_cluster_scale(profile, rows, records)

    with open(args.json, "w") as f:
        json.dump({"benchmark": "scalability", "records": records}, f, indent=2)
    rows.append(csv_row("scalability/json_report", 0.0, f"path={args.json}"))

    if print_csv:
        for r in rows:
            print(r)
    return rows


if __name__ == "__main__":
    import sys

    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main(sys.argv[1:])
