"""LAP-solver microbenchmarks (beyond-paper §Perf evidence).

Three parts:

1. The original single-instance comparisons (our numpy Hungarian vs scipy)
   — kept as CSV rows for continuity with the other paper-figure benches.
2. The **engine scale sweep**: the Algorithm-2 node-pair fan-out solved
   through ``solve_lap_batched`` with every registered backend, over batch
   sizes {1, 16, 64, 256} plus cluster-scale batches up to 512 node-pair
   instances (a 2048-GPU cluster: 512 nodes x 4 GPUs gives k_c = 512 and
   512-instance LAP batches per fan-out row).  Timings land in a JSON perf
   record for regression tracking:

       PYTHONPATH=src python benchmarks/matching_microbench.py \\
           --backend all --json matching_microbench.json

3. The **warm-start A/B replay** (``--warm-start``): a multi-round trace
   of cost batches with round-to-round churn (a few instances mutate one
   row per round — the Tesserae placement-locality model) replayed twice:
   *cold* resets the :class:`MatchContext` every round (PR-1 behaviour,
   the baseline) and *warm* threads one context across the whole trace.
   Per round it records bid-iteration counts, wall time, warm/memo hits
   and a scipy-parity gate; a rectangular (packing-shaped) replay pins the
   padding-free path, and ``--warm-scale-rounds N`` additionally measures
   per-round ``TesseraeScheduler.decide()`` at the 2048-GPU sweep point
   (512 nodes x 4) cold vs warm.  The JSON record defaults to
   ``BENCH_matching_warmstart.json``:

       PYTHONPATH=src python benchmarks/matching_microbench.py \\
           --warm-start --warm-scale-rounds 3

   ``--check-convergence`` turns the replay into a CI gate: exit non-zero
   if any auction fails to converge, any round loses scipy parity, or the
   warm arm does not strictly reduce total bid iterations (timings are
   recorded but never gated).

4. The **identity-keyed churn replay** (``--churn``): an arrival/departure
   rate sweep where LAP instances JOIN and LEAVE the batch every round
   (the batch size itself jitters — job churn, not just cost mutation),
   replayed through three arms: *identity* (persistent context +
   caller-supplied instance ids, this PR), *shape_keyed* (the PR-2
   emulation: positional ids, context reset on any batch-size change) and
   *cold*.  JSON record defaults to ``BENCH_matching_churn.json``
   (committed alongside ``BENCH_matching_warmstart.json``); with
   ``--check-convergence`` it gates on scipy parity, convergence,
   identity warm hits in EVERY post-warmup round, and identity bid
   iterations at least 2x below shape-keyed — never on timing: at these
   CI-sized batches on CPU the identity arm's wall clock is dominated by
   host dispatch + jit-signature warmup (power-of-two bucketing bounds
   the signature count, but the first occurrence of each still compiles),
   while at the 2048-GPU fan-out scale the same path wins wall clock
   outright (see the ``decide_scale_warmstart`` records):

       PYTHONPATH=src:. python benchmarks/matching_microbench.py --churn

5. The **fused decide() replay** (``--fused``): the migrate stage routed
   through :class:`repro.core.fused.FusedMigrationPlanner` — one jitted
   XLA program (occupancy diff, in-program cost assembly, the sharded
   pair-LAP fan-out, node match, physical scatter) and ONE device→host
   readout per round.  Two parts: a small-scale churn replay comparing
   fused plans bit-for-bit against the host planner under ``tie_break``,
   and a warm steady-state replay at the 2048-GPU sweep point (512 nodes
   x 4) recording per-round wall time and the per-round host-sync budget
   (``fused_readouts`` plus any ``MatchContext.host_syncs``).  JSON
   record defaults to ``BENCH_fused_decide.json``; with
   ``--check-convergence`` it gates on bit-parity, zero host fallbacks,
   exactly one readout per migration round, and full cache cleanliness
   (zero dirty pairs) once the steady state is reached — never on
   timing.  Shard-count invariance across forced host devices is the
   test suite's job (``tests/test_fused_decide.py``); run this lane
   under ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` to give
   ``--fused-shards`` real devices:

       XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
           PYTHONPATH=src:. python benchmarks/matching_microbench.py \\
           --fused --fused-shards 8
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from typing import Dict, List

import numpy as np

from benchmarks.common import csv_row, timed
from repro.core.matching import MatchContext, solve_lap_batched
from repro.core.matching.hungarian import solve_lap

#: Acceptance sweep: per-backend timings for these batch sizes ...
BATCH_SIZES = [1, 16, 64, 256]
#: ... plus the cluster-scale fan-out (>= 2048 GPUs -> 512-instance batches).
SCALE_BATCH_SIZES = [512]
#: node sizes k_l of the per-pair LAPs (4 = every evaluated cluster; 8
#: exercises the non-smallperm path).
NODE_SIZES = [4, 8]

SWEEP_BACKENDS = ["scipy", "numpy", "smallperm", "auction", "auction_kernel"]


def bench_single(rows: List[str], records: List[Dict]) -> None:
    rng = np.random.default_rng(0)
    for n in [16, 64, 256]:
        cost = rng.integers(0, 64, size=(n, n)).astype(float)
        _, t_np = timed(solve_lap, cost, backend="numpy")
        _, t_sp = timed(solve_lap, cost, backend="scipy")
        rows.append(csv_row(f"matching/numpy_n{n}", t_np * 1e6, f"n={n}"))
        rows.append(csv_row(f"matching/scipy_n{n}", t_sp * 1e6, f"n={n}"))
        records.append({"bench": "single", "backend": "numpy", "n": n, "time_s": t_np})
        records.append({"bench": "single", "backend": "scipy", "n": n, "time_s": t_sp})


def bench_scale_sweep(
    backends: List[str], rows: List[str], records: List[Dict], repeats: int = 3
) -> None:
    """Batched fan-out sweep: every backend x batch size x node size."""
    rng = np.random.default_rng(1)
    for k in NODE_SIZES:
        for batch in BATCH_SIZES + SCALE_BATCH_SIZES:
            costs = rng.integers(0, 16, size=(batch, k, k)).astype(np.float64)
            for backend in backends:
                if backend == "smallperm" and k > 6:
                    continue
                # warm-up outside the timed region (jit compile for the
                # auction backends, BLAS init for scipy)
                solve_lap_batched(costs, backend=backend)
                best = float("inf")
                for _ in range(repeats):
                    t0 = time.perf_counter()
                    res = solve_lap_batched(costs, backend=backend)
                    best = min(best, time.perf_counter() - t0)
                gpus = batch * k  # one GPU per LAP row at k GPUs/node
                rows.append(
                    csv_row(
                        f"matching/sweep_{backend}_b{batch}_k{k}",
                        best * 1e6,
                        f"batch={batch};k={k};per_instance_us={best / batch * 1e6:.1f}",
                    )
                )
                records.append(
                    {
                        "bench": "scale_sweep",
                        "backend": backend,
                        "batch": batch,
                        "k": k,
                        "gpus_equivalent": gpus,
                        "time_s": best,
                        "per_instance_us": best / batch * 1e6,
                        "fallbacks": int(res.used_fallback.sum()),
                    }
                )


def _mutated_trace(rng, base: np.ndarray, rounds: int, churn: float) -> List[np.ndarray]:
    """Round trace with placement-locality churn: each round, ``churn`` of
    the instances get one row re-randomised (a node pair whose occupancy
    changed); everything else is carried over unchanged."""
    lo, hi = 0, int(base.max()) + 1
    trace = [base]
    costs = base
    for _ in range(rounds - 1):
        costs = costs.copy()
        n_mut = max(1, int(round(churn * costs.shape[0])))
        for i in rng.choice(costs.shape[0], n_mut, replace=False):
            costs[i, rng.integers(costs.shape[1])] = rng.integers(lo, hi, costs.shape[2])
        trace.append(costs)
    return trace


def _replay(trace, backend: str, persistent: bool, maximize: bool = False) -> Dict:
    """Replay a cost-batch trace through one arm (cold or warm) and record
    per-round iteration counts, wall time and scipy parity."""
    ctx = MatchContext()
    # jit warm-up for BOTH variants (cold solve + warm-started solve take
    # different traced signatures) so compiles stay out of the timed region
    scratch = MatchContext()
    solve_lap_batched(trace[0], maximize=maximize, backend=backend,
                      context=scratch, context_key="_jit_warmup")
    perturbed = trace[0].copy()
    perturbed[0, 0, :] = perturbed[0, 0, ::-1] + 1.0
    solve_lap_batched(perturbed, maximize=maximize, backend=backend,
                      context=scratch, context_key="_jit_warmup")
    per_round = []
    for t, costs in enumerate(trace):
        if not persistent:
            ctx = MatchContext()
        t0 = time.perf_counter()
        res = solve_lap_batched(
            costs, maximize=maximize, backend=backend, context=ctx, context_key="replay"
        )
        dt = time.perf_counter() - t0
        ref = solve_lap_batched(costs, maximize=maximize, backend="scipy")
        # documented engine bound: S * eps_min with eps_min = 1/(S+1) and
        # S the solve size — the SHORT side for rectangular instances
        s = min(costs.shape[1], costs.shape[2])
        bound = s / (s + 1) + 1e-6
        per_round.append(
            {
                "round": t,
                "time_s": dt,
                "bid_iters": int(res.bid_iters.sum()),
                "warm_instances": int(res.warm.sum()),
                "fallbacks": int(res.used_fallback.sum()),
                "converged": bool(res.converged.all()),
                "parity_ok": bool(
                    np.all(np.abs(res.total_cost - ref.total_cost) <= bound)
                ),
                "embedding": res.embedding,
            }
        )
    return {
        "arm": "warm" if persistent else "cold",
        "backend": backend,
        "rounds": len(trace),
        "total_bid_iters": int(sum(r["bid_iters"] for r in per_round)),
        "total_time_s": float(sum(r["time_s"] for r in per_round)),
        "memo_hits": ctx.stats["memo_hits"] if persistent else 0,
        "per_round": per_round,
    }


def bench_warm_start(args, rows: List[str], records: List[Dict]) -> bool:
    """Warm-start A/B replay; returns True when every convergence /
    parity / iteration-reduction gate passed."""
    rng = np.random.default_rng(7)
    ok = True

    # square node-pair fan-out replay (integer costs -> auction is exact)
    base = rng.integers(0, 16, (args.warm_batch, 4, 4)).astype(np.float64)
    trace = _mutated_trace(rng, base, args.warm_rounds, args.warm_churn)
    arms = {}
    for persistent in (False, True):
        rec = _replay(trace, args.warm_backend, persistent)
        rec["bench"] = "warmstart_replay"
        rec["batch"] = args.warm_batch
        rec["k"] = 4
        rec["churn"] = args.warm_churn
        records.append(rec)
        arms[rec["arm"]] = rec
        rows.append(
            csv_row(
                f"matching/warmstart_{rec['arm']}_b{args.warm_batch}",
                rec["total_time_s"] * 1e6,
                f"rounds={rec['rounds']};bid_iters={rec['total_bid_iters']};"
                f"memo_hits={rec['memo_hits']}",
            )
        )
        ok &= all(r["converged"] and r["parity_ok"] for r in rec["per_round"])
    ok &= arms["warm"]["total_bid_iters"] < arms["cold"]["total_bid_iters"]

    # rectangular packing-shaped replay (|placed| >> |pending|): pins the
    # padding-free path — no max(n, m)^2 square embedding is allocated.
    rect_base = np.round(rng.uniform(0, 4, (8, args.warm_rect_rows, 12)), 2)
    rect_trace = _mutated_trace(rng, rect_base, max(4, args.warm_rounds // 4), 0.25)
    for persistent in (False, True):
        rec = _replay(rect_trace, args.warm_backend, persistent, maximize=True)
        rec["bench"] = "warmstart_rect_replay"
        rec["shape"] = [args.warm_rect_rows, 12]
        records.append(rec)
        ok &= all(r["embedding"] == "rect" for r in rec["per_round"])
        # rect bound is short-side * eps; parity gate uses the documented
        # engine bound, checked inside _replay via total-cost distance
        ok &= all(r["converged"] and r["parity_ok"] for r in rec["per_round"])
        rows.append(
            csv_row(
                f"matching/warmstart_rect_{rec['arm']}",
                rec["total_time_s"] * 1e6,
                f"shape={args.warm_rect_rows}x12;bid_iters={rec['total_bid_iters']}",
            )
        )
    return ok


def _churn_trace(rng, pool: int, k: int, rounds: int, rate: float):
    """Instance-level churn replay: each round ~``rate`` of the batch
    DEPARTS and a random number of fresh instances ARRIVES (new
    identities, so the batch size itself jitters round to round — the
    job-arrival/finish pattern of the Shockwave/Gavel traces), plus one
    row re-randomised on a few survivors.  Returns [(ids, costs), ...]."""
    costs = rng.integers(0, 16, (pool, k, k)).astype(np.float64)
    ids = np.arange(pool, dtype=np.int64)
    next_id = pool
    trace = [(ids, costs)]
    for _ in range(rounds - 1):
        b = len(ids)
        n_dep = min(b - 1, rng.binomial(b, rate))
        n_arr = rng.binomial(pool, rate)
        keep = rng.permutation(b)[: b - n_dep]
        fresh = rng.integers(0, 16, (n_arr, k, k)).astype(np.float64)
        costs = np.concatenate([costs[keep], fresh])
        ids = np.concatenate([ids[keep], next_id + np.arange(n_arr, dtype=np.int64)])
        next_id += n_arr
        n_mut = max(1, int(round(rate * len(keep) / 2)))
        costs = costs.copy()
        for i in rng.choice(len(keep), min(n_mut, len(keep)), replace=False):
            costs[i, rng.integers(k)] = rng.integers(0, 16, k)
        trace.append((ids, costs))
    return trace


def _churn_replay(trace, backend: str, arm: str, refs) -> Dict:
    """One arm of the churn A/B/C:

    * ``identity``  — persistent context, caller-supplied instance ids
      (this PR): survivors memo-hit / stay warm across shape changes.
    * ``shape_keyed`` — the PR-2 emulation: persistent context but
      positional ids AND a reset whenever the batch size changes (exact-
      shape keying), so every arrival/departure cold-starts the batch.
    * ``cold`` — context reset every round (the PR-1 baseline).
    """
    ctx = MatchContext()
    prev_b = None
    per_round = []
    for (t, (ids, costs)), ref in zip(enumerate(trace), refs):
        if arm == "cold" or (arm == "shape_keyed" and prev_b != costs.shape[0]):
            ctx = MatchContext()
        prev_b = costs.shape[0]
        stats0 = dict(ctx.stats)
        t0 = time.perf_counter()
        res = solve_lap_batched(
            costs,
            backend=backend,
            context=ctx,
            context_key="churn",
            instance_ids=ids if arm == "identity" else None,
        )
        dt = time.perf_counter() - t0
        per_round.append(
            {
                "round": t,
                "batch": int(costs.shape[0]),
                "time_s": dt,
                "bid_iters": int(res.bid_iters.sum()),
                "warm_instances": int(res.warm.sum()),
                "memo_instances": int(
                    ctx.stats["memo_instances"] - stats0.get("memo_instances", 0)
                ),
                "converged": bool(res.converged.all()),
                "parity_ok": bool(
                    np.allclose(res.total_cost, ref.total_cost, atol=1e-9)
                ),
            }
        )
    return {
        "arm": arm,
        "backend": backend,
        "rounds": len(trace),
        "total_bid_iters": int(sum(r["bid_iters"] for r in per_round)),
        "total_time_s": float(sum(r["time_s"] for r in per_round)),
        "per_round": per_round,
    }


def bench_churn(args, rows: List[str], records: List[Dict]) -> bool:
    """Arrival/departure-rate sweep of the identity-keyed context vs the
    shape-keyed PR-2 emulation vs fully cold; returns True when every gate
    passed: parity + convergence everywhere, identity warm hits in every
    post-warmup round, and identity bid iterations >= 2x below
    shape-keyed.  Timings are recorded but never gated."""
    ok = True
    for rate in args.churn_rates:
        rng = np.random.default_rng(13)
        trace = _churn_trace(
            rng, args.churn_pool, args.churn_k, args.churn_rounds, rate
        )
        # one scipy parity reference per round, shared by all three arms
        # (the trace is identical across arms)
        refs = [solve_lap_batched(costs, backend="scipy") for _, costs in trace]
        arms = {}
        for arm in ("identity", "shape_keyed", "cold"):
            rec = _churn_replay(trace, args.warm_backend, arm, refs)
            rec["bench"] = "churn_replay"
            rec["rate"] = rate
            rec["pool"] = args.churn_pool
            rec["k"] = args.churn_k
            records.append(rec)
            arms[arm] = rec
            rows.append(
                csv_row(
                    f"matching/churn_{arm}_r{rate}",
                    rec["total_time_s"] * 1e6,
                    f"rounds={rec['rounds']};bid_iters={rec['total_bid_iters']}",
                )
            )
            ok &= all(r["converged"] and r["parity_ok"] for r in rec["per_round"])
        ident, shape = arms["identity"], arms["shape_keyed"]
        warm_every_round = all(
            r["warm_instances"] > 0 for r in ident["per_round"][1:]
        )
        reduction_ok = (
            shape["total_bid_iters"] >= 2 * ident["total_bid_iters"]
        )
        arms["identity"]["gates"] = {
            "warm_every_post_warmup_round": warm_every_round,
            "iter_reduction_vs_shape_keyed": (
                shape["total_bid_iters"] / max(1, ident["total_bid_iters"])
            ),
            "iter_reduction_ok": reduction_ok,
        }
        ok &= warm_every_round and reduction_ok
    return ok


def bench_decide_scale(args, rows: List[str], records: List[Dict]) -> None:
    """Per-round ``decide()`` at the 2048-GPU sweep point, cold vs warm.

    Static steady-state job set: rounds after the first present the same
    LAP fan-outs, which is exactly the regime the persistent context is
    built for — the cold arm (context reset every round) is the PR-1
    baseline measured fresh."""
    from repro.core.cluster import ClusterSpec
    from repro.core.policies import TiresiasPolicy
    from repro.core.profiler import ThroughputProfile
    from repro.core.scheduler import TesseraeScheduler
    from repro.core.traces import synthetic_active_jobs

    profile = ThroughputProfile()
    cluster = ClusterSpec(args.scale_nodes, 4)
    jobs = synthetic_active_jobs(args.scale_jobs, seed=1, profile=profile)
    for arm in ("cold", "warm"):
        sched = TesseraeScheduler(
            cluster, TiresiasPolicy(profile), profile, lap_backend=args.warm_backend
        )
        d = sched.decide(jobs, now=0.0)
        prev = d.plan
        per_round = []
        for r in range(1, args.warm_scale_rounds + 1):
            if arm == "cold":
                sched.match_context.reset()
            t0 = time.perf_counter()
            d = sched.decide(jobs, now=360.0 * r, prev_plan=prev)
            dt = time.perf_counter() - t0
            prev = d.plan
            per_round.append({"round": r, "decide_s": dt, **d.timings})
        rec = {
            "bench": "decide_scale_warmstart",
            "arm": arm,
            "backend": args.warm_backend,
            "nodes": args.scale_nodes,
            "gpus": cluster.num_gpus,
            "jobs": args.scale_jobs,
            "mean_decide_s": float(np.mean([p["decide_s"] for p in per_round])),
            "per_round": per_round,
            "context_stats": dict(sched.match_context.stats),
        }
        records.append(rec)
        rows.append(
            csv_row(
                f"matching/decide2048_{arm}",
                rec["mean_decide_s"] * 1e6,
                f"gpus={cluster.num_gpus};rounds={args.warm_scale_rounds}",
            )
        )


def bench_fused_decide(args, rows: List[str], records: List[Dict]) -> bool:
    """Fused decide() replay: bit-parity churn gate + warm steady-state
    scale replay; returns True when every parity / fallback / readout /
    cleanliness gate passed (timings recorded, never gated)."""
    from repro.core.cluster import ClusterSpec
    from repro.core.policies import TiresiasPolicy
    from repro.core.profiler import ThroughputProfile
    from repro.core.scheduler import TesseraeScheduler
    from repro.core.traces import synthetic_active_jobs

    profile = ThroughputProfile()
    ok = True

    # --- part 1: small-scale churn replay, fused vs host, bit-identical --- #
    # tie_break makes the perturbed optimum unique, so the fused program
    # and the host planner must emit the SAME physical plan every round —
    # membership churn (jobs leaving/rejoining) exercises the per-node
    # invalidation path, not just the all-clean steady state.
    cluster = ClusterSpec(args.fused_check_nodes, 4)
    jobs = synthetic_active_jobs(
        args.fused_check_nodes * 3 // 2, seed=3, profile=profile
    )

    def _mk(fused: bool) -> TesseraeScheduler:
        return TesseraeScheduler(
            cluster,
            TiresiasPolicy(profile),
            profile,
            enable_packing=False,
            tie_break=True,
            lap_backend="auto",
            fused_fanout=fused,
            fanout_shards=args.fused_shards,
        )

    f_sched, h_sched = _mk(True), _mk(False)
    prev_f = prev_h = None
    parity_rounds = parity_ok_rounds = 0
    for r in range(args.fused_check_rounds):
        active = jobs[(r % 3):] if r % 2 else jobs  # membership churn
        df = f_sched.decide(active, now=360.0 * r, prev_plan=prev_f)
        dh = h_sched.decide(active, now=360.0 * r, prev_plan=prev_h)
        if prev_f is not None:
            parity_rounds += 1
            if bool(np.array_equal(df.plan.slots, dh.plan.slots)):
                parity_ok_rounds += 1
        prev_f, prev_h = df.plan, dh.plan
    fstats = dict(f_sched._fused_planner.stats)
    checks = {
        "parity_rounds": parity_rounds,
        "parity_ok_rounds": parity_ok_rounds,
        "fused_rounds": fstats["fused_rounds"],
        "host_fallbacks": fstats["fused_host_fallbacks"],
        "readouts": fstats["fused_readouts"],
    }
    ok &= parity_ok_rounds == parity_rounds > 0
    ok &= fstats["fused_host_fallbacks"] == 0
    ok &= fstats["fused_readouts"] == parity_rounds  # ONE readout per round
    records.append(
        {
            "bench": "fused_parity_churn",
            "nodes": args.fused_check_nodes,
            "shards": args.fused_shards,
            **checks,
        }
    )
    rows.append(
        csv_row(
            f"matching/fused_parity_n{args.fused_check_nodes}",
            0.0,
            f"parity={parity_ok_rounds}/{parity_rounds};"
            f"fallbacks={fstats['fused_host_fallbacks']}",
        )
    )

    # --- part 2: warm steady-state replay at the 2048-GPU sweep point ----- #
    # static job set: after the physical plan reaches its fixed point the
    # occupancy diff marks every pair clean, the while_loop auctions exit
    # with zero bid rounds, and the round's entire host-sync budget is the
    # single fused readout — the tentpole's O(1)-transfer contract.
    cluster = ClusterSpec(args.fused_nodes, 4)
    jobs = synthetic_active_jobs(args.fused_jobs, seed=1, profile=profile)
    sched = TesseraeScheduler(
        cluster,
        TiresiasPolicy(profile),
        profile,
        enable_packing=False,
        lap_backend="auto",
        fused_fanout=True,
        fanout_shards=args.fused_shards,
    )
    d = sched.decide(jobs, now=0.0)  # round 0: no prev plan, no migrate
    prev = d.plan
    per_round = []
    for r in range(1, args.fused_rounds + 1):
        stats0 = dict(sched._fused_planner.stats) if sched._fused_planner else {}
        sync0 = sched.match_context.stats["host_syncs"]
        t0 = time.perf_counter()
        d = sched.decide(jobs, now=360.0 * r, prev_plan=prev)
        dt = time.perf_counter() - t0
        prev = d.plan
        st = sched._fused_planner.stats
        per_round.append(
            {
                "round": r,
                "decide_s": dt,
                "migrate_s": d.timings["migrate_s"],
                # the round's host-sync budget: fused readouts plus any
                # MatchContext device readouts (packing is off, so the
                # context stays untouched — this pins that)
                "fused_readouts": st["fused_readouts"] - stats0.get("fused_readouts", 0),
                "context_host_syncs": sched.match_context.stats["host_syncs"] - sync0,
                "dirty_pairs": st["fused_dirty_pairs"] - stats0.get("fused_dirty_pairs", 0),
                "pair_instances": st["fused_pair_instances"]
                - stats0.get("fused_pair_instances", 0),
                "bid_iters": st["fused_bid_iters"] - stats0.get("fused_bid_iters", 0),
                "host_fallbacks": st["fused_host_fallbacks"]
                - stats0.get("fused_host_fallbacks", 0),
            }
        )
    warm = [p for p in per_round if p["dirty_pairs"] == 0]
    steady_wall = float(np.mean([p["decide_s"] for p in warm])) if warm else None
    rec = {
        "bench": "fused_decide_scale",
        "nodes": args.fused_nodes,
        "gpus": cluster.num_gpus,
        "jobs": args.fused_jobs,
        "shards": args.fused_shards,
        "rounds": args.fused_rounds,
        "warm_steady_rounds": len(warm),
        "warm_steady_decide_s": steady_wall,
        "host_syncs_per_round": [
            p["fused_readouts"] + p["context_host_syncs"] for p in per_round
        ],
        "per_round": per_round,
    }
    records.append(rec)
    ok &= all(p["host_fallbacks"] == 0 for p in per_round)
    ok &= all(p["fused_readouts"] == 1 for p in per_round)
    ok &= all(p["context_host_syncs"] == 0 for p in per_round)
    # the steady state must actually be reached and be all-clean
    ok &= len(warm) > 0 and per_round[-1]["dirty_pairs"] == 0
    rows.append(
        csv_row(
            f"matching/fused_decide_n{args.fused_nodes}",
            (steady_wall or 0.0) * 1e6,
            f"gpus={cluster.num_gpus};shards={args.fused_shards};"
            f"warm_rounds={len(warm)}/{args.fused_rounds};"
            f"syncs_per_round={rec['host_syncs_per_round'][-1]}",
        )
    )
    return ok


def main(argv=None, print_csv: bool = True) -> List[str]:
    """``argv``: CLI arg list; ``None`` when driven programmatically by
    ``benchmarks/run.py`` — that path drops the ``auction_kernel`` backend
    off-TPU (interpret mode adds minutes; its timings are an explicit-CLI
    feature via ``--backend all`` / ``--backend auction_kernel``)."""
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument(
        "--backend",
        default="all",
        choices=SWEEP_BACKENDS + ["all"],
        help="engine backend to sweep ('all' = every registered backend)",
    )
    parser.add_argument(
        "--json",
        default=None,
        help="path of the JSON perf record (default matching_microbench.json, "
        "or BENCH_matching_warmstart.json with --warm-start)",
    )
    parser.add_argument("--repeats", type=int, default=3)
    parser.add_argument(
        "--warm-start",
        action="store_true",
        help="run the warm-start A/B replay instead of the classic sweeps",
    )
    parser.add_argument(
        "--churn",
        action="store_true",
        help="run the identity-keyed churn replay (arrival/departure rate "
        "sweep): identity-keyed vs shape-keyed (PR-2 emulation) vs cold",
    )
    parser.add_argument(
        "--fused",
        action="store_true",
        help="run the fused decide() replay: bit-parity churn gate plus the "
        "warm steady-state scale replay through the one-readout-per-round "
        "FusedMigrationPlanner",
    )
    parser.add_argument("--fused-rounds", type=int, default=6,
                        help="rounds of the fused scale replay")
    parser.add_argument("--fused-nodes", type=int, default=512,
                        help="nodes (x4 GPUs) of the fused scale replay "
                        "(512 = the 2048-GPU sweep point)")
    parser.add_argument("--fused-jobs", type=int, default=512,
                        help="steady-state job count of the fused scale replay")
    parser.add_argument("--fused-shards", type=int, default=1,
                        help="devices to shard_map the pair fan-out over "
                        "(more than the visible device count is an error; "
                        "force host devices via XLA_FLAGS to exceed 1 on CPU)")
    parser.add_argument("--fused-check-nodes", type=int, default=8,
                        help="nodes of the fused-vs-host bit-parity churn gate")
    parser.add_argument("--fused-check-rounds", type=int, default=10,
                        help="rounds of the fused-vs-host bit-parity churn gate")
    parser.add_argument("--churn-rounds", type=int, default=30,
                        help="churn replay length")
    parser.add_argument("--churn-pool", type=int, default=64,
                        help="steady-state batch size of the churn replay")
    parser.add_argument("--churn-k", type=int, default=4,
                        help="LAP instance size of the churn replay")
    parser.add_argument(
        "--churn-rates", type=lambda v: [float(x) for x in v.split(",")],
        default=[0.05, 0.15, 0.3],
        help="comma-separated arrival/departure rates (fraction of the "
        "batch arriving AND departing per round)",
    )
    parser.add_argument("--warm-rounds", type=int, default=24, help="replay length")
    parser.add_argument("--warm-batch", type=int, default=256, help="instances per round")
    parser.add_argument("--warm-churn", type=float, default=0.05,
                        help="fraction of instances mutated per round")
    parser.add_argument("--warm-rect-rows", type=int, default=96,
                        help="placed-job count of the rectangular replay (pending=12)")
    parser.add_argument("--warm-backend", default="auction",
                        choices=["auction", "auction_kernel"])
    parser.add_argument(
        "--warm-scale-rounds", type=int, default=0,
        help="also measure per-round decide() at the 2048-GPU sweep point "
        "for this many rounds per arm (0 = skip; slow on CPU)",
    )
    parser.add_argument("--scale-nodes", type=int, default=512)
    parser.add_argument("--scale-jobs", type=int, default=512)
    parser.add_argument(
        "--check-convergence",
        action="store_true",
        help="CI gate: exit non-zero on auction non-convergence, parity "
        "loss, or a warm arm that does not reduce bid iterations "
        "(never gates on timing)",
    )
    from_cli = argv is not None
    args = parser.parse_args(list(argv) if from_cli else [])
    if sum([args.churn, args.warm_start, args.fused]) > 1:
        parser.error(
            "--churn, --warm-start and --fused are separate replays with "
            "separate JSON records and gates; run them as separate invocations"
        )
    backends = SWEEP_BACKENDS if args.backend == "all" else [args.backend]
    if not from_cli:
        import jax

        if jax.default_backend() != "tpu":
            backends = [b for b in backends if b != "auction_kernel"]

    rows: List[str] = []
    records: List[Dict] = []
    if args.fused:
        import jax

        json_path = args.json or "BENCH_fused_decide.json"
        gates_ok = bench_fused_decide(args, rows, records)
        report = {
            "benchmark": "fused_decide",
            "nodes": args.fused_nodes,
            "jobs": args.fused_jobs,
            "shards": args.fused_shards,
            "rounds": args.fused_rounds,
            "devices": len(jax.devices()),
            "gates_ok": gates_ok,
            "records": records,
        }
        with open(json_path, "w") as f:
            json.dump(report, f, indent=2)
        rows.append(csv_row("matching/json_report", 0.0, f"path={json_path}"))
        if print_csv:
            for r in rows:
                print(r)
        if args.check_convergence and not gates_ok:
            print("fused decide parity/readout gate FAILED", file=sys.stderr)
            raise SystemExit(2)
        return rows
    if args.churn:
        json_path = args.json or "BENCH_matching_churn.json"
        gates_ok = bench_churn(args, rows, records)
        report = {
            "benchmark": "matching_churn",
            "backend": args.warm_backend,
            "rounds": args.churn_rounds,
            "pool": args.churn_pool,
            "k": args.churn_k,
            "rates": args.churn_rates,
            "gates_ok": gates_ok,
            "records": records,
        }
        with open(json_path, "w") as f:
            json.dump(report, f, indent=2)
        rows.append(csv_row("matching/json_report", 0.0, f"path={json_path}"))
        if print_csv:
            for r in rows:
                print(r)
        if args.check_convergence and not gates_ok:
            print("churn replay warm-hit/parity/2x gate FAILED", file=sys.stderr)
            raise SystemExit(2)
        return rows
    if args.warm_start:
        json_path = args.json or "BENCH_matching_warmstart.json"
        gates_ok = bench_warm_start(args, rows, records)
        if args.warm_scale_rounds > 0:
            bench_decide_scale(args, rows, records)
        report = {
            "benchmark": "matching_warmstart",
            "backend": args.warm_backend,
            "rounds": args.warm_rounds,
            "batch": args.warm_batch,
            "churn": args.warm_churn,
            "gates_ok": gates_ok,
            "records": records,
        }
        with open(json_path, "w") as f:
            json.dump(report, f, indent=2)
        rows.append(csv_row("matching/json_report", 0.0, f"path={json_path}"))
        if print_csv:
            for r in rows:
                print(r)
        if args.check_convergence and not gates_ok:
            print("warm-start convergence/parity gate FAILED", file=sys.stderr)
            raise SystemExit(2)
        return rows

    bench_single(rows, records)
    bench_scale_sweep(backends, rows, records, repeats=args.repeats)

    report = {
        "benchmark": "matching_microbench",
        "backends": backends,
        "batch_sizes": BATCH_SIZES + SCALE_BATCH_SIZES,
        "node_sizes": NODE_SIZES,
        "records": records,
    }
    json_path = args.json or "matching_microbench.json"
    with open(json_path, "w") as f:
        json.dump(report, f, indent=2)
    rows.append(csv_row("matching/json_report", 0.0, f"path={json_path}"))

    if print_csv:
        for r in rows:
            print(r)
    return rows


if __name__ == "__main__":
    import sys

    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main(sys.argv[1:])
