"""The program's own spans, compile counts and named scopes, read for one cell.

The program (``repro.obs``) times its spans on its own clock and, given a
``Tracer(annotate=...)``, also writes each into the profiler's trace; its
scheduler counts JAX compilations (``jax.compiles.<fun_name>``) while an
``Observability`` is attached; and the fused migrate program names its
steps with ``jax.named_scope``.  This module reads all three:

* :func:`round_spans`: the self time of every span of one round, by path
  (``round/decide/pack/pack.graph``), from the tracer's roots;
* :func:`reduce`: :func:`bench.trace_reduce.reduce` plus, where the trace
  holds program-span annotations, idle gaps named by the innermost span
  open on the host (``decide/pack/lap.solve/lap.prologue``,
  ``sim/active_scan``) and the device time of each named scope of
  ``jit__fused_round`` per traced round;
* :func:`scope_map`: each HLO instruction of a compiled program to its
  scope, from the compiled text's ``op_name`` metadata.

Run as a script it runs one cell like ``bench/run.py`` with the program's
tracing on and prints one JSON line: every per-layer metric of
``bench/metrics`` that finds something to read::

    python3 bench/program_spans.py --workload <cell> --seed <n> --seconds <s> \\
        [--profile 0|1] [--nodes <k>] [--save <dir>]

``--profile 1`` profiles the first window rounds as ``--trace 1`` does;
``--nodes`` cuts the cluster (for a small recorded trace); ``--save``
keeps the trace (gzipped) and its scope map in ``<dir>``.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import os  # noqa: E402
import re  # noqa: E402
import sys  # noqa: E402
from collections import defaultdict  # noqa: E402
from typing import Dict, List, Optional, Sequence, Tuple  # noqa: E402

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import trace_reduce  # noqa: E402

#: prefix of the program's span annotations in the profiler's trace
PREFIX = "tesserae/"
#: the fused migrate program's named scopes, in the order they run
SCOPES = ("diff", "assemble", "pair_auction", "node_match", "scatter")
#: the metrics this module makes readable (each a ``bench/metrics`` reader)
METRICS = ("pair_loop_trips", "node_match_iters", "migrate_host_s", "pack_graph_s",
           "pack_lap_s", "sim_scan_s", "compiles_per_round", "pair_auction_device_s",
           "node_match_device_s")

Segment = Tuple[float, float, str]


def annotate(name: str):
    """The ``Tracer(annotate=...)`` factory: a profiler annotation named
    ``tesserae/<span>``."""
    import jax

    return jax.profiler.TraceAnnotation(PREFIX + name)


# --------------------------------------------------------------------------- #
# the program's spans and compile counts, per round
# --------------------------------------------------------------------------- #
def round_spans(roots) -> Dict[str, float]:
    """Self seconds of every span under ``roots`` (a round's root spans), by
    path of span names joined with ``/``; repeated paths add up."""
    out: Dict[str, float] = defaultdict(float)

    def walk(sp, prefix: str) -> None:
        path = prefix + sp.name
        out[path] += sp.dur_s - sum(c.dur_s for c in sp.children)
        for c in sp.children:
            walk(c, path + "/")

    for r in roots:
        walk(r, "")
    return dict(out)


def compiles(metrics) -> Dict[str, int]:
    """The registry's compile counts so far, by function name."""
    return metrics.counters_with_prefix("jax.compiles.")


# --------------------------------------------------------------------------- #
# the trace
# --------------------------------------------------------------------------- #
def _main_thread(pd) -> List[Tuple[str, float, float]]:
    """``(name, start_ns, end_ns)`` of every event on the host line that
    holds the ``bench.round`` annotations."""
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                evs = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                       for ev in line.events]
                if any(n == "bench.round" for n, _, _ in evs):
                    return evs
    return []


def label(open_names: Sequence[str]) -> Optional[str]:
    """What the host was doing, from the names open on it, outermost
    first: inside ``decide()`` the program spans' path from ``decide`` on
    (``decide`` alone before its span opens), else ``sim/`` and the path
    with the Simulator's ``round`` dropped, else ``sim`` inside the
    harness's ``bench.round``, else ``None`` (the harness's own time)."""
    path = [n[len(PREFIX):] for n in open_names if n.startswith(PREFIX)]
    if "decide" in path:
        return "/".join(path[path.index("decide"):])
    if "bench.decide" in open_names:
        return "decide"
    if path:
        return "sim/" + "/".join([n for n in path if n != "round"] or ["round"])
    if "bench.round" in open_names:
        return "sim"
    return None


def span_segments(events: Sequence[Tuple[str, float, float]]) -> List[Segment]:
    """The host's time cut where the set of open program spans and harness
    annotations changes, each piece labelled by :func:`label`; adjacent
    pieces with one label are merged, the harness's own time left out."""
    evs = [e for e in events
           if e[0].startswith(PREFIX) or e[0] in ("bench.round", "bench.decide")]
    edges = sorted({t for _, s, e in evs for t in (s, e)})
    order = sorted(evs, key=lambda e: (e[1], -e[2]))
    segs: List[Segment] = []
    for a, b in zip(edges, edges[1:]):
        name = label([n for n, s, e in order if s <= a and e >= b])
        if name is None:
            continue
        if segs and segs[-1][2] == name and segs[-1][1] == a:
            segs[-1] = (segs[-1][0], b, name)
        else:
            segs.append((a, b, name))
    return segs


def idle_pieces(pd) -> List[Tuple[str, float]]:
    """Every idle piece of the first chip in the traced window, ``(label,
    ns)``, named by the program spans open on the host (``bench`` where
    none and no harness annotation is)."""
    rounds = trace_reduce._host_annotations(pd, "bench.round")
    lo, hi = rounds[0][0], rounds[-1][1]
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            ops = [(s, e) for _, s, e in trace_reduce._events(plane, "XLA Ops")]
            if ops:
                idle = trace_reduce.gaps(trace_reduce.union(ops, lo, hi), lo, hi)
                return trace_reduce.attribute(idle, span_segments(_main_thread(pd)))
    return []


_INSTR = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*?op_name="([^"]*)"', re.M)


def scope_map(hlo_text: str) -> Dict[str, str]:
    """Each instruction of a compiled HLO module's text whose ``op_name``
    passes through one of :data:`SCOPES`, to that scope."""
    out = {}
    for name, op_name in _INSTR.findall(hlo_text):
        scope = next((p for p in op_name.split("/") if p in SCOPES), None)
        if scope is not None:
            out[name] = scope
    return out


def scope_device_ns(pd, smap: Dict[str, str], lo: float, hi: float
                    ) -> Tuple[Dict[str, float], float, float]:
    """Device ns of each named scope of the fused module inside ``[lo,
    hi]`` (the union of its operations' intervals, so an operation nested
    in a ``while`` is not counted twice), summed over chips; with the ns of
    all scoped operations together, and of the module."""

    def ns(ivs):
        return sum(e - s for s, e in trace_reduce.union(ivs, lo, hi))

    per_scope: Dict[str, float] = defaultdict(float)
    scoped = module = 0.0
    for plane in pd.planes:
        if not plane.name.startswith("/device:"):
            continue
        mods = [(s, e) for n, s, e in trace_reduce._events(plane, "XLA Modules")
                if n.startswith(trace_reduce.FUSED_MODULE)]
        by_scope: Dict[str, List[Tuple[float, float]]] = defaultdict(list)
        for name, s, e in trace_reduce._events(plane, "XLA Ops"):
            scope = smap.get(name.split(" ", 1)[0].lstrip("%"))
            # instruction names are unique only within a module
            if scope is not None and any(ms <= s < me for ms, me in mods):
                by_scope[scope].append((s, e))
        for scope, ivs in by_scope.items():
            per_scope[scope] += ns(ivs)
        scoped += ns([iv for ivs in by_scope.values() for iv in ivs])
        module += ns(mods)
    return dict(per_scope), scoped, module


def reduce(pd, smap: Optional[Dict[str, str]] = None) -> Dict:
    """:func:`bench.trace_reduce.reduce` of the trace, and where it holds
    program-span annotations, ``idle_gaps`` named by them (the ten longest)
    with ``idle_named_share``, the share of idle time named by a program
    span; given the fused program's scope map, ``scope_device_s`` (device
    seconds of each scope per traced round) and ``scope_coverage`` (the
    share of the module's time its scoped operations cover)."""
    out = trace_reduce.reduce(pd, [])
    if any(n.startswith(PREFIX) for n, _, _ in _main_thread(pd)):
        pieces = idle_pieces(pd)
        total = sum(ns for _, ns in pieces)
        named = sum(ns for name, ns in pieces if name not in ("bench", "sim", "decide"))
        top = sorted(pieces, key=lambda p: -p[1])[:10]
        out["breakdown"]["idle_gaps"] = [[name, ns / 1e9] for name, ns in top]
        out["idle_named_share"] = named / total if total else None
    if smap:
        rounds = trace_reduce._host_annotations(pd, "bench.round")
        per_scope, scoped, module = scope_device_ns(pd, smap, rounds[0][0], rounds[-1][1])
        per_round = out["chips"] * max(out["rounds"], 1) * 1e9
        out["scope_device_s"] = {k: v / per_round for k, v in per_scope.items()}
        out["scope_coverage"] = scoped / module if module else None
    return out


def program_spans_vs_modules(pd) -> List[Tuple[float, float]]:
    """For each ``migrate.fused.program`` span in the trace, its length and
    the time of the fused modules that end inside it, in ns (by their end:
    the device's clock may place a module's start slightly before the
    host's dispatch)."""
    mods = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            mods += [(s, e) for n, s, e in trace_reduce._events(plane, "XLA Modules")
                     if n.startswith(trace_reduce.FUSED_MODULE)]
    out = []
    for name, s, e in _main_thread(pd):
        if name == PREFIX + "migrate.fused.program":
            out.append((e - s, sum(me - ms for ms, me in mods if s < me <= e)))
    return out


# --------------------------------------------------------------------------- #
# one cell with the program's tracing on
# --------------------------------------------------------------------------- #
def _fused_text(spec: Dict, sim, rounds) -> str:
    """The compiled text of the fused program at the window's shapes."""
    from repro.core.fused import lower_fused_round

    cl, sc = spec["config"]["cluster"], spec["config"]["scheduler"]
    pmax = next(r.migration.prev.shape[-1] for r in rounds if r.migration is not None)
    n_weights = max(s.job_id for s in sim.trace) + 2
    return lower_fused_round(
        cl["num_nodes"], cl["gpus_per_node"], pmax, n_weights,
        shards=sc.get("fanout_shards", 1), tie_break=sc.get("tie_break", False),
    ).compile().as_text()


def run(spec: Dict, seed: int, seconds: float, profile: bool, t_start: float,
        device: Dict, save: Optional[str] = None) -> Dict:
    """One cell as ``bench.harness.run`` runs it, with an ``Observability``
    whose tracer annotates the profiler's trace attached to the Simulator;
    each window round's record also holds ``spans`` and ``compiles``."""
    import contextlib
    import glob
    import gzip
    import json
    import shutil

    import jax

    from bench import harness
    from repro.core.simulator import Simulator
    from repro.obs import Observability, Tracer

    counter = harness.CompileCounter()
    bench_annotate = (jax.profiler.TraceAnnotation if profile
                      else (lambda _n: contextlib.nullcontext()))
    trace_dir = os.path.join(harness.OUT, "spans", spec["name"]) if profile else None
    obs = Observability(tracer=Tracer(annotate=annotate))

    sim, sched, gangs = harness.build(spec["config"], spec["traffic"], seed)
    sim = Simulator(sim.cluster, sim.trace, sched, sim.true_profile, sim.config, obs=obs)
    with harness.Recorder(sched, bench_annotate) as rec:
        for _ in range(int(spec["traffic"]["warmup_rounds"])):
            harness.one_round(sim, bench_annotate)
        setup_s = time.perf_counter() - t_start
        compiles0 = counter.snapshot()
        first = len(rec.rounds)
        if profile:
            shutil.rmtree(trace_dir, ignore_errors=True)
            jax.profiler.start_trace(trace_dir)
        extra, paused, t0 = [], 0.0, time.perf_counter()
        while True:
            obs.tracer.reset()
            before = compiles(obs.metrics)
            r0 = time.perf_counter()
            harness.one_round(sim, bench_annotate)
            rec.rounds[-1].round_s = time.perf_counter() - r0
            after = compiles(obs.metrics)
            extra.append({
                "spans": round_spans([r for r in obs.tracer.roots() if r.tid == 0]),
                "compiles": {k: v - before.get(k, 0) for k, v in after.items()
                             if v != before.get(k, 0)},
            })
            if profile and len(rec.rounds) - first >= harness.TRACE_ROUNDS:
                p0 = time.perf_counter()
                jax.profiler.stop_trace()
                paused += time.perf_counter() - p0
                profile = False
            if not profile and time.perf_counter() - t0 - paused >= seconds:
                break
        window_s = time.perf_counter() - t0 - paused
        compiles1 = counter.snapshot()
    sched.set_observability(None)

    rounds = rec.rounds[first:]
    record = {
        "setup_s": setup_s,
        "window_s": window_s,
        "rounds": [
            dict({"decide_s": r.decide_s, "round_s": r.round_s, "timings": r.timings,
                  "stats": r.stats, "degrade": r.degrade, "active": int(r.active.size)}, **x)
            for r, x in zip(rounds, extra)
        ],
        "trace": None,
    }
    for t, r in enumerate(record["rounds"]):
        print(f"round {t}: decide_s={r['decide_s']:.4f} "
              + " ".join(f"{k}={v:.4f}" for k, v in r["timings"].items())
              + f" pair_trips={r['stats'].get('fused_pair_trips', 0)}"
              f" node_iters={r['stats'].get('fused_node_iters', 0)}"
              f" bid_iters={r['stats'].get('fused_bid_iters', 0)}"
              f" compiles={r['compiles']}", file=sys.stderr)
    if trace_dir is not None:
        xplane = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                               "*.xplane.pb")))[-1]
        smap = scope_map(_fused_text(spec, sim, rounds))
        pd = trace_reduce.load(xplane)
        record["trace"] = reduce(pd, smap)
        record["trace"]["program_vs_module_ns"] = program_spans_vs_modules(pd)
        if save:
            os.makedirs(save, exist_ok=True)
            cl = spec["config"]["cluster"]
            name = f"{spec['config']['name'].split('-')[0]}-{cl['num_nodes']}x{cl['gpus_per_node']}"
            with open(xplane, "rb") as f, gzip.open(
                    os.path.join(save, name + ".xplane.pb.gz"), "wb") as g:
                g.write(f.read())
            with open(os.path.join(save, name + ".scopes.json"), "w") as f:
                json.dump(smap, f, sort_keys=True, indent=0)

    metrics = {}
    for m in spec["per_layer"] + spec["end_to_end"] + [{"name": n} for n in METRICS]:
        value = harness.metric_reader(m["name"])(record)
        if value is not None:
            metrics[m["name"]] = value
    return {
        "correct": all(c["value"] <= c["limit"]
                       for c in harness.check(
                           rounds, gangs, *harness.layout(spec["config"]["cluster"])).values()),
        "attempted": len(rounds),
        "metrics": metrics,
        "compiled_in_window": compiles1["compiled"] - compiles0["compiled"],
        "trace": None if record["trace"] is None else {
            k: v for k, v in record["trace"].items() if k != "breakdown"},
        "breakdown": None if record["trace"] is None else record["trace"]["breakdown"],
        "device": device,
    }


def main(argv=None, t_start: Optional[float] = None) -> int:
    import argparse
    import json

    from bench import harness

    ap = argparse.ArgumentParser(description="Run one cell with the program's tracing on.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--profile", type=int, choices=(0, 1), default=1)
    ap.add_argument("--nodes", type=int, default=None)
    ap.add_argument("--save", default=None)
    args = ap.parse_args(argv)
    spec = harness.cell(args.workload)
    if args.nodes is not None:
        spec["config"]["cluster"]["num_nodes"] = args.nodes
    harness.enable_cache()
    try:
        device = harness.require_chip(spec["chips"])
    except harness.NoChip as e:
        print(f"program_spans: {e}", file=sys.stderr)
        return 1
    result = run(spec, args.seed, args.seconds, bool(args.profile),
                 time.perf_counter() if t_start is None else t_start, device, args.save)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:], T_START))
