#!/usr/bin/env python3
"""Run the scheduler's device path once on a TPU chip and check its decisions.

    python chip_smoke.py              # one chip: the main, tie-break and kernel phases
    python chip_smoke.py --chips 4    # four chips: the sharded pair fan-out only

Phases on one chip:

* **main** — ``Simulator`` -> ``TesseraeScheduler(fused_fanout=True)`` at
  512 nodes x 4 GPUs = 2048 GPUs, fed a seeded Shockwave backlog whose
  jobs all arrive within the first round (more jobs than GPU slots, so
  every round is saturated).  Every migrated round is re-solved by the
  host planner (``plan_migration(..., backend="scipy")``) on the same
  (previous plan, logical plan); its ``matching_cost`` must equal the
  fused one exactly.  Every round: zero host fallbacks, one readout.
* **tie-break** — 16 nodes with ``tie_break=True``: the fused physical
  plan must be bit-identical to the host planner's every round.  Gangs
  are 1 or 2 GPUs: with 4- and 8-GPU gangs the cost scale pushes a
  16-node instance past the fused program's 2^24 f32 budget, and every
  round would be served by the host.
* **kernel** — the main phase's rounds replayed through
  ``FusedMigrationPlanner(use_kernel=True)`` (the fused Pallas bid
  kernel): the same plans and costs as the jnp bid path, zero fallbacks,
  one readout per round, and ``tpu_custom_call`` in the compiled program
  (the kernel ran compiled, not interpreted).  Then one ``auction_kernel``
  engine solve at n = 256 against scipy.

With ``--chips 4`` only the sharded path runs: the main phase's
Simulator with the pair fan-out ``shard_map``-ed over four chips, each
round replayed through a one-shard planner; plans, costs and bid counts
must be bit-identical, and the sharded outputs must span four devices.

Exits non-zero on any mismatch, fallback, non-convergence or error, and
before any work when JAX finds no TPU or a device count other than
``--chips``.  The last line of stdout is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
from typing import Dict, List

import numpy as np

SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")

NODES = 512  # x 4 GPUs = 2048 GPUs, the top of the paper's decision-time sweep
GPUS_PER_NODE = 4
JOBS = 4096  # backlog: two jobs per GPU slot
ROUNDS = 9  # round 0 has no previous plan, so 8 migrated rounds
TIE_NODES = 16
TIE_JOBS = 96
TIE_ROUNDS = 12
ARRIVAL_WINDOW_S = 300.0  # mean span of the arrivals; rounds are 360 s
AUCTION_N = 256  # auction.KERNEL_MIN_N: where the engine turns the kernel on

COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)


class SmokeFailure(Exception):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def log(msg: str) -> None:
    print(msg, flush=True)


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling (or loading from
    the persistent cache), summed from its own monitoring events."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self)

    def __call__(self, event: str, duration: float, **_) -> None:
        if event in COMPILE_EVENTS:
            self.seconds += duration


@dataclasses.dataclass
class Round:
    prev: object
    logical: object
    num_gpus_of: Dict[int, int]
    kwargs: dict
    result: object
    stats: Dict[str, int]  # this round's delta of the planner's stats


def recording_planner(**kw):
    """A ``FusedMigrationPlanner`` that keeps each round's inputs, result
    and stats delta, so the reference and the replays see exactly what the
    scheduler handed it."""
    from repro.core.fused import FusedMigrationPlanner

    class RecordingPlanner(FusedMigrationPlanner):
        def __init__(self, **kw):
            super().__init__(**kw)
            self.rounds: List[Round] = []

        def plan(self, prev, new_logical, num_gpus_of, **kwargs):
            before = dict(self.stats)
            res = super().plan(prev, new_logical, num_gpus_of, **kwargs)
            delta = {k: v - before[k] for k, v in self.stats.items()}
            self.rounds.append(
                Round(prev, new_logical, dict(num_gpus_of), kwargs, res, delta)
            )
            return res

    return RecordingPlanner(**kw)


def saturating_trace(jobs: int, seed: int, profile, gangs=None):
    """Seeded backlog whose arrivals all land within the first round."""
    rate = jobs * 3600.0 / ARRIVAL_WINDOW_S
    if gangs is None:
        from repro.core.traces import shockwave_trace

        return shockwave_trace(
            num_jobs=jobs, arrival_rate_per_hour=rate, seed=seed, profile=profile
        )
    from repro.workloads.generators import Arrivals, GangSizes, TraceRecipe, generate_trace
    from repro.workloads.schema import to_jobspecs

    recipe = TraceRecipe(arrivals=Arrivals(rate_per_hour=rate), gangs=GangSizes(*gangs))
    return to_jobspecs(generate_trace(recipe, jobs, seed), profile)


def simulate(nodes: int, jobs: int, rounds: int, seed: int, *, shards=1,
             tie_break=False, gangs=None):
    """``rounds`` rounds of the normal Simulator path with the fused migrate
    stage on.  Returns (planner, metrics registry, active jobs per round)."""
    from repro.core.cluster import ClusterSpec
    from repro.core.policies import TiresiasPolicy
    from repro.core.profiler import ThroughputProfile
    from repro.core.scheduler import TesseraeScheduler
    from repro.core.simulator import Simulator
    from repro.obs import Observability

    profile = ThroughputProfile()
    cluster = ClusterSpec(nodes, GPUS_PER_NODE)
    sched = TesseraeScheduler(
        cluster,
        TiresiasPolicy(profile),
        profile,
        enable_packing=True,
        migration_algorithm="node",
        fused_fanout=True,
        fanout_shards=shards,
        tie_break=tie_break,
    )
    planner = recording_planner(shards=shards)
    sched._fused_planner = planner
    active: List[int] = []

    def hook(_round, _now, decision, _states, _health):
        active.append(len(decision.placed) + len(decision.pending))

    obs = Observability()
    sim = Simulator(cluster, saturating_trace(jobs, seed, profile, gangs), sched,
                    profile, round_hook=hook, obs=obs)
    check(sim.run(stop_after_rounds=rounds) is None,
          f"the workload finished before {rounds} rounds")
    check(len(active) == rounds, f"{len(active)} rounds ran, wanted {rounds}")
    check(len(planner.rounds) == rounds - 1,
          f"{len(planner.rounds)} migrated rounds, wanted {rounds - 1}")
    return planner, obs.metrics, active


def check_fused_rounds(planner, metrics, label: str) -> None:
    """Every migrated round served by the fused program with one readout,
    as both the planner and the Simulator's registry count it."""
    for t, r in enumerate(planner.rounds):
        check(r.stats["fused_host_fallbacks"] == 0, f"{label} round {t}: host fallback")
        check(r.stats["fused_readouts"] == 1,
              f"{label} round {t}: {r.stats['fused_readouts']} readouts")
        check(r.stats["fused_rounds"] == 1, f"{label} round {t}: not served fused")
    n = len(planner.rounds)
    for name, want in (("fused_rounds", n), ("fused_host_fallbacks", 0),
                       ("fused_readouts", n)):
        got = metrics.counter_value("match." + name)
        check(got == want, f"{label}: registry match.{name} = {got}, wanted {want}")


def host_reference(r: Round):
    from repro.core.migration import plan_migration

    return plan_migration(r.prev, r.logical, r.num_gpus_of, algorithm="node",
                          backend="scipy", **r.kwargs)


def fused_round_compiles() -> int:
    from repro.core.fused import _fused_round

    return _fused_round._cache_size()


def main_phase(seed: int, clock: CompileClock, *, nodes=NODES, jobs=JOBS,
               rounds=ROUNDS):
    compiles0, compile_s0 = fused_round_compiles(), clock.seconds
    t0 = time.perf_counter()
    planner, metrics, active = simulate(nodes, jobs, rounds, seed)
    wall = time.perf_counter() - t0
    gpus = nodes * GPUS_PER_NODE
    check(min(active) >= gpus, f"main: active jobs per round {active} < {gpus} GPUs")
    check_fused_rounds(planner, metrics, "main")
    decide_s = metrics.histogram_values("decide.latency_s")
    log(f"main: nodes={nodes} gpus={gpus} jobs={jobs} rounds={rounds} "
        f"migrated_rounds={len(planner.rounds)} wall_s={wall:.3f}")
    log(f"main: compile_s={clock.seconds - compile_s0:.3f} (set-up) "
        f"fused_round_compiles={fused_round_compiles() - compiles0}")
    log("main: fused_rounds={} fused_host_fallbacks={} fused_readouts={}".format(
        *(metrics.counter_value("match." + k)
          for k in ("fused_rounds", "fused_host_fallbacks", "fused_readouts"))))
    log(f"main: active_jobs_per_round={active}")
    log("main: decide_s_per_round=[" + ", ".join(f"{s:.4f}" for s in decide_s) + "]")
    for stage in ("schedule_s", "place_s", "pack_s", "migrate_s"):
        values = metrics.histogram_values("decide.stage." + stage)
        log(f"main: {stage}_per_round=[" + ", ".join(f"{v:.4f}" for v in values) + "]")
    log("main: bid_iters_per_round="
        f"{[r.stats['fused_bid_iters'] for r in planner.rounds]} "
        f"dirty_pairs_per_round={[r.stats['fused_dirty_pairs'] for r in planner.rounds]}")
    for t, r in enumerate(planner.rounds):
        ref = host_reference(r)
        check(ref.matching_cost == r.result.matching_cost,
              f"main round {t}: fused matching_cost {r.result.matching_cost!r} "
              f"!= host scipy {ref.matching_cost!r}")
    log(f"main: matching_cost equal to host scipy on {len(planner.rounds)}/"
        f"{len(planner.rounds)} rounds")
    return planner


def tie_break_phase(seed: int, *, nodes=TIE_NODES, jobs=TIE_JOBS, rounds=TIE_ROUNDS):
    planner, metrics, active = simulate(nodes, jobs, rounds, seed, tie_break=True,
                                        gangs=((1, 2), (0.7, 0.3)))
    check_fused_rounds(planner, metrics, "tie-break")
    for t, r in enumerate(planner.rounds):
        ref = host_reference(r)
        check(np.array_equal(ref.physical_plan.slots, r.result.physical_plan.slots),
              f"tie-break round {t}: fused plan != host scipy plan")
        check(ref.matching_cost == r.result.matching_cost,
              f"tie-break round {t}: matching_cost differs")
    log(f"tie-break: nodes={nodes} jobs={jobs} active_jobs_per_round={active} "
        f"plans bit-identical to host scipy on {len(planner.rounds)}/"
        f"{len(planner.rounds)} rounds")


def replay(rounds: List[Round], planner, label: str) -> List[int]:
    """Feed recorded rounds to another planner; its plans and costs must be
    the recorded ones.  Returns its bid iterations per round."""
    bids = []
    for t, r in enumerate(rounds):
        before = dict(planner.stats)
        res = planner.plan(r.prev, r.logical, r.num_gpus_of, **r.kwargs)
        d = {k: v - before[k] for k, v in planner.stats.items()}
        check(d["fused_host_fallbacks"] == 0, f"{label} round {t}: host fallback")
        check(d["fused_readouts"] == 1, f"{label} round {t}: {d['fused_readouts']} readouts")
        same_cost = res.matching_cost == r.result.matching_cost
        check(np.array_equal(res.physical_plan.slots, r.result.physical_plan.slots),
              f"{label} round {t}: plan differs (matching_cost "
              f"{'equal' if same_cost else 'differs too'})")
        check(same_cost, f"{label} round {t}: matching_cost differs")
        bids.append(d["fused_bid_iters"])
    return bids


def compiled_fused_round(rounds: List[Round], planner):
    from repro.core.fused import lower_fused_round

    r = rounds[0]
    kc, kl, pmax = r.prev.slots.shape
    return lower_fused_round(
        kc, kl, pmax, max(r.num_gpus_of) + 2, shards=planner.shards,
        use_kernel=planner.use_kernel, tie_break=r.kwargs.get("tie_break", False),
    ).compile()


def kernel_phase(rounds: List[Round], seed: int):
    import jax.numpy as jnp

    from repro.core.fused import FusedMigrationPlanner
    from repro.core.matching import solve_lap_batched
    from repro.core.matching.auction import _auction_lap_batched_jit, _auto_use_kernel

    planner = FusedMigrationPlanner(use_kernel=True)
    replay(rounds, planner, "kernel")
    compiled = compiled_fused_round(rounds, planner)
    jnp_compiled = compiled_fused_round(rounds, FusedMigrationPlanner())
    check("tpu_custom_call" in compiled.as_text(),
          "kernel: no tpu_custom_call in the compiled fused program")
    log(f"kernel: plans and costs equal to the jnp bid path on {len(rounds)}/"
        f"{len(rounds)} rounds, tpu_custom_call in the compiled program")
    for name, c in (("kernel", compiled), ("jnp", jnp_compiled)):
        mem = c.memory_analysis()
        log(f"kernel: {name} bid path temp_bytes={mem.temp_size_in_bytes} "
            f"argument_bytes={mem.argument_size_in_bytes}")

    rng = np.random.default_rng(seed)
    costs = rng.integers(0, 100, size=(2, AUCTION_N, AUCTION_N)).astype(np.float64)
    got = solve_lap_batched(costs, backend="auction_kernel")
    want = solve_lap_batched(costs, backend="scipy")
    check(bool(got.converged.all()) and not got.used_fallback.any(),
          "auction_kernel: an instance did not converge")
    check(np.array_equal(got.total_cost, want.total_cost),
          f"auction_kernel: total cost {got.total_cost} != scipy {want.total_cost}")
    auto = _auto_use_kernel(AUCTION_N)
    text = _auction_lap_batched_jit.lower(
        jnp.asarray(costs, jnp.float32), max_iters=20_000, use_kernel=auto
    ).as_text()
    check(auto and "tpu_custom_call" in text,
          f"auction: the kernel is not on at n={AUCTION_N} on this platform")
    log(f"kernel: auction_kernel n={AUCTION_N} total cost equal to scipy, "
        "kernel on by platform, tpu_custom_call in the compiled program")


def sharded_phase(seed: int, shards: int, *, nodes=NODES, jobs=JOBS, rounds=ROUNDS):
    from repro.core.fused import FusedMigrationPlanner

    planner, metrics, active = simulate(nodes, jobs, rounds, seed, shards=shards)
    check_fused_rounds(planner, metrics, f"shards={shards}")
    col_of = planner._cache[2]
    spanned = len(col_of.sharding.device_set)
    check(spanned == shards, f"sharded pair results span {spanned} devices, not {shards}")
    ones = replay(planner.rounds, FusedMigrationPlanner(shards=1), "shards=1")
    many = [r.stats["fused_bid_iters"] for r in planner.rounds]
    check(ones == many, f"bid iterations differ: shards={shards} {many} vs shards=1 {ones}")
    log(f"sharded: nodes={nodes} jobs={jobs} active_jobs_per_round={active}")
    log(f"sharded: shards={shards} (results on {spanned} devices) vs shards=1: plans, "
        f"costs and bid iterations {many} bit-identical on {len(planner.rounds)} rounds")


def require_chip(chips: int):
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: JAX found no TPU (platform {dev.platform!r})")
    if len(devices) != chips:
        sys.exit(f"chip_smoke: {len(devices)} devices visible, --chips {chips}")
    return {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    device = require_chip(args.chips)

    sys.path.insert(0, SRC)
    from repro.compile_cache import enable_compile_cache

    log(f"device: {device} compile_cache={enable_compile_cache()}")
    clock = CompileClock()
    try:
        if args.chips == 1:
            rounds = main_phase(args.seed, clock).rounds
            tie_break_phase(args.seed)
            kernel_phase(rounds, args.seed)
        else:
            sharded_phase(args.seed, args.chips)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    log(f"total compile_s={clock.seconds:.3f}")
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
