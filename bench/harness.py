"""Run one benchmark cell once and report it as one JSON line.

A cell is an entry of ``BENCHMARK.json``'s ``workloads``: a configuration
(``bench/configs/<config>.json``: cluster, job catalog, policy, scheduler
options) under a traffic mix (``bench/traffic/<traffic>.json``).  Every metric
is read by ``bench/metrics/<metric>.py`` from the run's record, so a later
change adds a cell, a deployment or a metric by adding files and entries.

Set-up: generate the trace from ``--seed``, build
``Simulator -> TesseraeScheduler -> fused migrate program``, and run the
traffic's warm-up rounds (the first migrated round loads or compiles the
fused program).  The window: whole rounds, one after another, until
``--seconds`` have passed; ``decide()`` is timed from outside.  After the
window every round's output is checked against the plain reference
(``bench/reference.py``).
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import gc
import glob
import importlib.util
import json
import os
import shutil
import sys
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")
OUT = os.path.join(ROOT, ".bench_out")
CACHE = os.path.join(ROOT, ".jax_cache")
SRC = os.path.join(ROOT, "src")
if SRC not in sys.path:
    sys.path.insert(0, SRC)

from bench import reference, trace_reduce, traffic  # noqa: E402

#: every number compared, with its limit: the fused relabelling's reported
#: cost and its plan's exact cost against the reference optimum (both exact
#: comparisons), and the rounds whose plan breaks a placement rule
LIMITS = {"cost_gap": 0.0, "plan_cost_gap": 0.0, "invalid_plans": 0}
#: window rounds the --trace 1 run profiles
TRACE_ROUNDS = 3
#: JAX's event around every backend compilation, a persistent-cache load too
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
#: the keys a configuration's ``cluster`` may hold; the last two are optional
CLUSTER_KEYS = ("num_nodes", "gpus_per_node", "node_gpu_types", "nodes_per_rack")


class NoChip(Exception):
    pass


def load_json(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


def cell(name: str) -> Dict:
    """The cell's configuration, traffic and metric entries, by name."""
    bm = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    (w,) = [w for w in bm["workloads"] if w["name"] == name]
    (c,) = [c for c in bm["configs"] if c["name"] == w["config"]]

    def applies(m):
        return name in m.get("workloads", [name])

    return {
        "name": name,
        "chips": int(w["chips"]),
        "config": load_json(os.path.join(ROOT, c["file"])),
        "traffic": load_json(os.path.join(BENCH, "traffic", w["traffic"] + ".json")),
        "end_to_end": [m for m in bm["end_to_end"] if applies(m)],
        "per_layer": [m for m in bm["per_layer"] if applies(m)],
    }


def require_chip(chips: int) -> Dict:
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devices[0].platform!r})")
    if len(devices) < chips:
        raise NoChip(f"{len(devices)} TPU chips visible, the cell needs {chips}")
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def metric_reader(name: str):
    path = os.path.join(BENCH, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class CompileCounter:
    """Counts XLA compilations, and the programs loaded from the persistent
    compilation cache instead."""

    def __init__(self):
        import jax

        self.counts = {"compiled": 0, "loaded": 0}
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, duration: float, **_) -> None:
        if event == COMPILE_EVENT:
            self.counts["compiled"] += 1

    def _on_event(self, event: str, **_) -> None:
        if event == CACHE_HIT_EVENT:
            self.counts["compiled"] -= 1
            self.counts["loaded"] += 1

    def snapshot(self) -> Dict[str, int]:
        return dict(self.counts)


@dataclasses.dataclass
class Migration:
    prev: np.ndarray
    logical: np.ndarray
    cost: float
    #: ``node_map[l]``: the physical node that hosts logical node ``l``
    node_map: Optional[np.ndarray]


@dataclasses.dataclass
class Round:
    decide_s: float
    timings: Dict[str, float]
    stats: Dict[str, int]
    degrade: str
    active: np.ndarray
    plan: np.ndarray
    migration: Optional[Migration]
    round_s: float = 0.0


class Recorder:
    """Times ``scheduler.decide`` from outside and keeps what each round
    decided, with the inputs and result of its relabelling.

    The scheduler builds its fused planner itself; while the recorder is
    open (a context manager) it wraps ``FusedMigrationPlanner.plan`` and
    keeps the calls of that scheduler's planner."""

    def __init__(self, sched, annotate):
        self.rounds: List[Round] = []
        self._pending = None
        self._sched = sched
        self._plan = None
        decide = sched.decide

        def timed_decide(active_jobs, now, prev_plan=None, num_gpus_of=None, **kw):
            self._pending = None
            with annotate("bench.decide"):
                t0 = time.perf_counter()
                d = decide(active_jobs, now, prev_plan, num_gpus_of, **kw)
                dt = time.perf_counter() - t0
            mig = None
            if self._pending is not None:
                prev, logical, res = self._pending
                node_map = (None if res.node_assignment is None
                            else np.array(res.node_assignment, np.int64))
                mig = Migration(prev.slots.copy(), logical.slots.copy(),
                                float(res.matching_cost), node_map)
            self.rounds.append(Round(
                dt, dict(d.timings), dict(d.match_stats), d.degrade_reason,
                np.fromiter((j.job_id for j in active_jobs), np.int64, len(active_jobs)),
                d.plan.slots.copy(), mig,
            ))
            return d

        sched.decide = timed_decide

    def __enter__(self) -> "Recorder":
        from repro.core.fused import FusedMigrationPlanner

        self._plan = plan = FusedMigrationPlanner.plan

        def recording_plan(planner, prev, new_logical, num_gpus_of, **kw):
            res = plan(planner, prev, new_logical, num_gpus_of, **kw)
            if planner is self._sched._fused_planner:
                self._pending = (prev, new_logical, res)
            return res

        FusedMigrationPlanner.plan = recording_plan
        return self

    def __exit__(self, *exc) -> None:
        from repro.core.fused import FusedMigrationPlanner

        FusedMigrationPlanner.plan = self._plan


def layout(cluster: Dict) -> Tuple[Optional[np.ndarray], Optional[np.ndarray]]:
    """Every node's GPU type and rack as a configuration's ``cluster``
    states them, each None where it states none.

    ``node_gpu_types`` is a run-length list, ``[["a100", 256], ["v100",
    256]]``: nodes 0-255 are A100 nodes, 256-511 V100 nodes.  With
    ``nodes_per_rack`` r > 0, nodes ``[k*r, (k+1)*r)`` form rack k.  Raises
    on a key outside ``CLUSTER_KEYS`` or counts that do not cover the
    nodes."""
    unknown = sorted(set(cluster) - set(CLUSTER_KEYS))
    if unknown:
        raise ValueError(f"unknown cluster keys {unknown}; known: {list(CLUSTER_KEYS)}")
    n = cluster["num_nodes"]
    types = racks = None
    if "node_gpu_types" in cluster:
        runs = cluster["node_gpu_types"]
        if not all(isinstance(t, str) and type(c) is int and c > 0 for t, c in runs):
            raise ValueError(f"node_gpu_types is not a list of [type, count]: {runs}")
        types = np.array([t for t, c in runs for _ in range(c)])
        if types.size != n:
            raise ValueError(f"node_gpu_types counts sum to {types.size}, not {n} nodes")
    if "nodes_per_rack" in cluster:
        per_rack = cluster["nodes_per_rack"]
        if type(per_rack) is not int or per_rack < 0:
            raise ValueError(f"nodes_per_rack is not a whole number >= 0: {per_rack!r}")
        if per_rack:
            racks = np.arange(n) // per_rack
    return types, racks


def cluster_spec(cluster: Dict):
    """The program's ``ClusterSpec`` for a configuration's ``cluster``."""
    from repro.core.cluster import ClusterSpec
    from repro.core.profiler import GPU_TYPES

    types, _ = layout(cluster)
    kw = {}
    if types is not None:
        unknown = sorted(set(types.tolist()) - set(GPU_TYPES))
        if unknown:
            raise ValueError(f"GPU types {unknown} not in the profile: {sorted(GPU_TYPES)}")
        kw["node_gpu_types"] = tuple(types.tolist())
    if "nodes_per_rack" in cluster:
        kw["nodes_per_rack"] = cluster["nodes_per_rack"]
    return ClusterSpec(cluster["num_nodes"], cluster["gpus_per_node"], **kw)


def build(config: Dict, traffic_mix: Dict, seed: int):
    """The system under test for one cell and seed, and every job's gang."""
    from repro.core import policies
    from repro.core.profiler import ThroughputProfile
    from repro.core.scheduler import TesseraeScheduler
    from repro.core.simulator import SimConfig, Simulator

    specs, gangs = traffic.job_specs(config, traffic_mix, seed)
    profile = ThroughputProfile()
    cluster = cluster_spec(config["cluster"])
    policy = getattr(policies, config["policy"])(profile)
    sched = TesseraeScheduler(cluster, policy, profile, **config["scheduler"])
    sim = Simulator(cluster, specs, sched, profile, SimConfig(**config["sim"]))
    return sim, sched, gangs


def one_round(sim, annotate) -> None:
    with annotate("bench.round"):
        if sim.run(stop_after_rounds=1) is not None:
            raise RuntimeError("the trace ran out of jobs before the window closed")


def check(rounds: List[Round], gangs: Dict[int, int],
          types: Optional[np.ndarray] = None,
          racks: Optional[np.ndarray] = None) -> Dict[str, Dict]:
    """Each window round against the reference: the relabelling's reported
    cost and its plan's exact cost against the optimum, and the plan's
    validity; on a cluster with GPU types or racks (``layout``), under the
    reference's type and rack rules, and the plan read through the
    program's node map.  Returns every number compared beside its limit."""
    cost_gap = plan_gap = 0.0
    invalid = 0
    typed = types is not None or racks is not None
    for t, r in enumerate(rounds):
        mig = r.migration
        node_map = mig.node_map if typed and mig is not None else None
        problems = reference.plan_problems(
            r.plan, r.active, gangs, None if mig is None else mig.logical,
            types=types, node_map=node_map,
        )
        if mig is None:
            problems.append("no relabelling recorded")
        elif typed and node_map is None:
            problems.append("no node map recorded")
        else:
            opt, _, _ = reference.relabel(mig.prev, mig.logical, gangs,
                                          types=types, racks=racks)
            cost_gap = max(cost_gap, abs(mig.cost - opt))
            exact = reference.plan_cost(mig.prev, r.plan, gangs, mig.logical,
                                        node_map=node_map, racks=racks)
            plan_gap = max(plan_gap, abs(exact - opt))
        if problems:
            invalid += 1
            print(f"round {t}: " + "; ".join(problems), file=sys.stderr)
    values = {"cost_gap": cost_gap, "plan_cost_gap": plan_gap, "invalid_plans": invalid}
    return {k: {"value": v, "limit": LIMITS[k]} for k, v in values.items()}


def enable_cache() -> None:
    """JAX's persistent compilation cache at ``<checkout>/.jax_cache``, with
    JAX's own thresholds: programs that compile in under a second (the
    program's per-round ``jnp.pad`` of the packing prologue among them) are
    compiled in every run alike, so a run's window does not depend on what
    an earlier run of the same seed left in the cache.  Call before JAX is
    imported."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE
    # the TPU runtime's logs, too, stay inside the checkout
    os.environ.setdefault("TPU_LOG_DIR", os.path.join(OUT, "tpu_logs"))
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()


def memory_peak_bytes(n: int) -> int:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()[:n]]
    return int(max(peaks))


def fused_compiles() -> int:
    from repro.core.fused import _fused_round

    return _fused_round._cache_size()


def window(sim, rec: Recorder, seconds: float, annotate, trace_dir: Optional[str]):
    """Whole rounds until ``seconds`` have passed, profiling the first
    ``TRACE_ROUNDS`` of them into ``trace_dir`` when given; returns the
    window's length, less the time taken to stop the profiler."""
    import jax

    first = len(rec.rounds)
    profiling = trace_dir is not None
    if profiling:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(trace_dir)
    paused = 0.0
    t0 = time.perf_counter()
    while True:
        r0 = time.perf_counter()
        one_round(sim, annotate)
        rec.rounds[-1].round_s = time.perf_counter() - r0
        if profiling and len(rec.rounds) - first >= TRACE_ROUNDS:
            p0 = time.perf_counter()
            jax.profiler.stop_trace()
            paused += time.perf_counter() - p0
            profiling = False
        if not profiling and time.perf_counter() - t0 - paused >= seconds:
            return time.perf_counter() - t0 - paused


def run(spec: Dict, seed: int, seconds: float, trace: bool, t_start: float,
        device: Dict) -> Dict:
    """Set up, run the window, check it: the result line as a dict, plus the
    window's rounds under ``"_rounds"`` and the gangs under ``"_gangs"``."""
    import jax

    counter = CompileCounter()
    annotate = jax.profiler.TraceAnnotation if trace else (lambda _n: contextlib.nullcontext())
    trace_dir = os.path.join(OUT, "trace", spec["name"]) if trace else None

    sim, sched, gangs = build(spec["config"], spec["traffic"], seed)
    with Recorder(sched, annotate) as rec:
        for _ in range(int(spec["traffic"]["warmup_rounds"])):
            one_round(sim, annotate)
        setup_s = time.perf_counter() - t_start
        compiles0, fused0 = counter.snapshot(), fused_compiles()
        first = len(rec.rounds)
        window_s = window(sim, rec, seconds, annotate, trace_dir)
        compiles1, fused1 = counter.snapshot(), fused_compiles()

    rounds = rec.rounds[first:]
    device = dict(device, memory_peak_bytes=memory_peak_bytes(device["count"]))
    in_window = {k: compiles1[k] - compiles0[k] for k in compiles0}
    print(f"window: rounds={len(rounds)} window_s={window_s!r} "
          f"active_jobs_first={rounds[0].active.size} "
          f"active_jobs_last={rounds[-1].active.size}", file=sys.stderr)
    for t, r in enumerate(rounds):
        print(f"round {t}: round_s={r.round_s:.4f} decide_s={r.decide_s:.4f} "
              + " ".join(f"{k}={v:.4f}" for k, v in r.timings.items())
              + f" bid_iters={r.stats.get('fused_bid_iters', 0)}"
              f" dirty_pairs={r.stats.get('fused_dirty_pairs', 0)}"
              f" active={r.active.size} degrade={r.degrade}", file=sys.stderr)
    print(f"window: compiled={in_window['compiled']} "
          f"loaded_from_cache={in_window['loaded']} "
          f"fused_round_compilations={fused1 - fused0}", file=sys.stderr)
    del sim, sched, rec
    gc.collect()

    record = {
        "setup_s": setup_s,
        "window_s": window_s,
        "rounds": [
            {"decide_s": r.decide_s, "round_s": r.round_s, "timings": r.timings,
             "stats": r.stats, "degrade": r.degrade, "active": int(r.active.size)}
            for r in rounds
        ],
        "trace": None,
    }
    breakdown = None
    if trace:
        xplane = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                               "*.xplane.pb")))[-1]
        record["trace"] = trace_reduce.reduce(
            trace_reduce.load(xplane), [r.timings for r in rounds[:TRACE_ROUNDS]]
        )
        device.update(busy_s=record["trace"]["busy_s"],
                      window_s=record["trace"]["window_s"])
        breakdown = record["trace"]["breakdown"]

    checks = check(rounds, gangs, *layout(spec["config"]["cluster"]))
    metrics = {}
    for m in spec["per_layer" if trace else "end_to_end"]:
        value = metric_reader(m["name"])(record)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    failed = sum(1 for r in rounds
                 if r.degrade != "none" or r.stats.get("fused_host_fallbacks", 0))
    result = {
        "correct": all(c["value"] <= c["limit"] for c in checks.values()),
        "attempted": len(rounds),
        "failed": failed,
        "metrics": metrics,
        "device": device,
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    result["_rounds"], result["_gangs"] = rounds, gangs
    return result


def main(argv=None, t_start: Optional[float] = None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        spec = cell(args.workload)
    except (ValueError, OSError) as e:
        print(f"bench: no cell {args.workload!r}: {e}", file=sys.stderr)
        return 2
    enable_cache()
    try:
        device = require_chip(spec["chips"])
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    result = run(spec, args.seed, args.seconds, bool(args.trace), t_start, device)
    result.pop("_rounds"), result.pop("_gangs")
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0
