"""Packing as maximum-weight bipartite matching (§4.2, Algorithm 4).

Build G = (V1, V2, E): V1 = placed_jobs, V2 = pending_jobs, an edge (u, v)
iff the two jobs request the same number of GPUs (so v can overlay u's
GPUs), weight = profiled combined normalised throughput — maximised over
job u's parallelism-strategy candidates when enabled (Fig. 7b).

Solving the matching (Hungarian / auction) yields at most one pending job
per placed job, maximising total cluster throughput.  Jobs flagged
non-packable (strict deadline / priority, §4.3 "Fairness") get no edges.

Implementation note: we embed the bipartite graph in a rectangular benefit
matrix with 0 for missing edges; a zero-weight "match" is interpreted as
*no packing* (packing with combined weight 0 is never beneficial since any
positive weight adds throughput for a job that would otherwise idle in the
queue).  The matrix is typically very skew (|placed| >> |pending| on a
busy cluster); the engine's rectangular path solves it without the
``max(n, m)^2`` square embedding, and a :class:`MatchContext` carried by
the scheduler warm-starts / memoises consecutive rounds whose graph barely
changed.

**Job classes.**  An edge weight depends on a job only through its class:
a placed job's row class is its model (and, on a heterogeneous cluster,
its node's GPU type), a pending job's column class is its model, and the
mask is equal gang size, packability on both sides and a ``packed_ok``
that is a function of the two gang sizes.  So the job-level matching has
the optimum of a transportation problem on class counts, one per gang
size, of at most (types x models) x models classes.  :func:`pack_jobs`
solves that problem exactly (:func:`max_weight_transport`) whenever the
caller left the solver to the code (``backend="auto"``, no ``tie_break``,
a vectorised or no ``packed_ok``), and hands the flows out to jobs in the
order it received them; no |placed| x |pending| matrix is built.  Every
other call keeps the engine path above.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.core.jobs import JobState
from repro.core.matching import MatchContext, solve_lap_batched
from repro.core.profiler import ThroughputProfile
from repro.obs.tracer import tracer_of


@dataclasses.dataclass
class PackingResult:
    #: pending job id -> placed job id
    matches: Dict[int, int]
    #: placed job id -> chosen parallelism strategy (LLM jobs whose strategy
    #: the matcher re-optimised to lift the edge weight)
    strategies: Dict[int, str]
    total_weight: float
    wall_time_s: float
    num_edges: int


@dataclasses.dataclass
class PackingClasses:
    """The class tables both packing paths read: the weight of placed job
    ``i`` and pending job ``j`` is ``pairw[row_class[i], col_class[j]]``
    where the mask (equal ``gi``/``gj``, ``ok_p[i]``, ``ok_q[j]``, and the
    caller's ``packed_ok``) admits the pair, else 0."""

    #: (row classes, column classes) combined weights; row class
    #: ``t * n_models + m`` is GPU type t (one type without
    #: ``placed_gpu_types``) and model m, column class m is model m
    pairw: np.ndarray
    row_class: np.ndarray
    col_class: np.ndarray
    gi: np.ndarray
    gj: np.ndarray
    ok_p: np.ndarray
    ok_q: np.ndarray


def packing_classes(
    placed: Sequence[JobState],
    pending: Sequence[JobState],
    profile: ThroughputProfile,
    optimize_strategy: bool = True,
    placed_gpu_types: Optional[Sequence[str]] = None,
) -> PackingClasses:
    """Every job's class, gang size and packability, and the per-class
    weight table (``combined_weight``, memoised in the profile)."""
    models = sorted({u.spec.model for u in placed} | {v.spec.model for v in pending})
    midx = {m: i for i, m in enumerate(models)}
    n_m = len(models)
    if placed_gpu_types is None:
        pairw = np.zeros((n_m, n_m), dtype=np.float64)
        for a in models:
            for b in models:
                pairw[midx[a], midx[b]] = profile.combined_weight(
                    a, b, optimize_strategy=optimize_strategy
                )[0]
        mp = np.array([midx[u.spec.model] for u in placed], dtype=np.int64)
    else:
        # one weight table per GPU type present among the placed jobs; the
        # placed row then indexes (its node's type, its model)
        types = sorted(set(placed_gpu_types))
        tidx = {t: k for k, t in enumerate(types)}
        pairw = np.zeros((len(types), n_m, n_m), dtype=np.float64)
        for t in types:
            prof_t = profile.for_gpu_type(t)
            for a in models:
                for b in models:
                    pairw[tidx[t], midx[a], midx[b]] = prof_t.combined_weight(
                        a, b, optimize_strategy=optimize_strategy
                    )[0]
        mp = np.array(
            [
                tidx[t] * n_m + midx[u.spec.model]
                for u, t in zip(placed, placed_gpu_types)
            ],
            dtype=np.int64,
        )
        pairw = pairw.reshape(len(types) * n_m, n_m)
    return PackingClasses(
        pairw=pairw,
        row_class=mp,
        col_class=np.array([midx[v.spec.model] for v in pending], dtype=np.int64),
        gi=np.array([u.num_gpus for u in placed], dtype=np.int64),
        gj=np.array([v.num_gpus for v in pending], dtype=np.int64),
        ok_p=np.array(
            [u.spec.packable and u.packed_with is None for u in placed], dtype=bool
        ),
        ok_q=np.array([v.spec.packable for v in pending], dtype=bool),
    )


def build_packing_graph(
    placed: Sequence[JobState],
    pending: Sequence[JobState],
    profile: ThroughputProfile,
    optimize_strategy: bool = True,
    packed_ok=None,
    placed_gpu_types: Optional[Sequence[str]] = None,
) -> np.ndarray:
    """Benefit matrix (|placed| x |pending|), fully vectorised.

    The per-MODEL-pair weight is memoised in the profile; the per-JOB-pair
    matrix is assembled with numpy indexing (the O(n^2) loop in pure Python
    was the scalability bottleneck — see EXPERIMENTS.md §Perf, scheduler
    iteration 1).

    ``placed_gpu_types`` (heterogeneous clusters) gives the GPU type of
    the node each PLACED job occupies; the edge weight — including memory
    feasibility, the thing that actually flips on 16 GB parts — is then
    profiled per type via :meth:`ThroughputProfile.for_gpu_type`.  ``None``
    (the default, and every homogeneous caller) is the seed path."""
    p, q = len(placed), len(pending)
    if p == 0 or q == 0:
        return np.zeros((p, q), dtype=np.float64)
    c = packing_classes(placed, pending, profile, optimize_strategy, placed_gpu_types)
    mask = (c.gi[:, None] == c.gj[None, :]) & c.ok_p[:, None] & c.ok_q[None, :]
    if packed_ok is not None:
        if getattr(packed_ok, "vectorized_on_gpus", False):
            mask &= packed_ok.gpu_mask(c.gi, c.gj)
        else:
            ii, jj = np.nonzero(mask)
            for i, j in zip(ii, jj):
                if not packed_ok(placed[i], pending[j]):
                    mask[i, j] = False
    return np.where(mask, c.pairw[c.row_class[:, None], c.col_class[None, :]], 0.0)


def max_weight_transport(
    w: np.ndarray, supply: np.ndarray, demand: np.ndarray
) -> np.ndarray:
    """Integral flows ``x`` (same shape as ``w``) that maximise
    ``(w * x).sum()`` subject to row sums <= ``supply``, column sums <=
    ``demand`` and ``x >= 0``, with flow only where ``w > 0`` (a unit left
    unshipped weighs 0).

    Successive shortest paths on the residual graph source -> row -> column
    -> sink, the row -> column arcs costing ``-w``: from zero flow, each
    augmentation follows a cheapest path (Bellman-Ford over the whole
    bipartite layer at once), so no negative cycle ever forms, and the
    solve stops when the cheapest path no longer gains.  Deterministic:
    ties go to the lowest index.  A relaxation must gain more than a
    rounding-sized tolerance, so float sums never loop the predecessors.
    """
    n_r, n_c = w.shape
    arc = w > 0
    x = np.zeros((n_r, n_c), dtype=np.int64)
    rs = np.asarray(supply, dtype=np.int64).copy()
    cd = np.asarray(demand, dtype=np.int64).copy()
    if not arc.any():
        return x
    tol = 1e-12 * max(1.0, float(w.max()))
    inf = np.inf
    ar, ac = np.arange(n_r), np.arange(n_c)
    fwd = np.where(arc, -w, inf)
    while True:
        # dr / dc: cheapest known cost from the source to each row / column;
        # pr[a] is the column whose backward arc reached row a (-1: the
        # source), pc[b] the row whose forward arc reached column b
        dr = np.where(rs > 0, 0.0, inf)
        pr = np.full(n_r, -1, dtype=np.int64)
        dc = np.full(n_c, inf)
        pc = np.full(n_c, -1, dtype=np.int64)
        bwd = np.where(x > 0, w, inf)
        for _ in range(n_r + n_c + 1):
            cand = dr[:, None] + fwd
            a_best = np.argmin(cand, axis=0)
            v = cand[a_best, ac]
            imp_c = v < dc - tol
            dc = np.where(imp_c, v, dc)
            pc = np.where(imp_c, a_best, pc)
            cand = dc[None, :] + bwd
            b_best = np.argmin(cand, axis=1)
            v = cand[ar, b_best]
            imp_r = v < dr - tol
            if not imp_r.any() and not imp_c.any():
                break
            dr = np.where(imp_r, v, dr)
            pr = np.where(imp_r, b_best, pr)
        sink = np.where(cd > 0, dc, inf)
        b = int(np.argmin(sink))
        if not sink[b] < -tol:
            return x
        # walk back to the source: column b <- row pc[b] (<- column pr[a]
        # <- row pc[...]) ... until a row the source reached directly
        path: List[Tuple[int, int, int]] = []  # (sign, row, column)
        col, bottleneck = b, int(cd[b])
        for _ in range(n_r + n_c + 1):
            a = int(pc[col])
            path.append((1, a, col))
            if pr[a] < 0:
                bottleneck = min(bottleneck, int(rs[a]))
                break
            col = int(pr[a])
            path.append((-1, a, col))
            bottleneck = min(bottleneck, int(x[a, col]))
        else:  # pragma: no cover - the tolerance keeps the predecessors a tree
            raise RuntimeError("packing transport: predecessor cycle")
        for sign, a, c in path:
            x[a, c] += sign * bottleneck
        rs[path[-1][1]] -= bottleneck
        cd[b] -= bottleneck


def _solve_classes(
    c: PackingClasses, packed_ok
) -> Tuple[np.ndarray, np.ndarray, int]:
    """The class path: per gang size, the transportation problem on class
    counts, then its flows handed to jobs.  Returns the matched (placed,
    pending) index arrays and the job-level edge count ``(w > 0).sum()``.

    Class pairs are taken in sorted (row class, column class) order; flow
    ``x_ab`` goes to the first ``x_ab`` not yet matched placed jobs of class
    a and pending jobs of class b, in the order the caller gave them."""
    n_r, n_c = c.pairw.shape
    rows: List[np.ndarray] = []
    cols: List[np.ndarray] = []
    num_edges = 0
    gangs = np.intersect1d(c.gi[c.ok_p], c.gj[c.ok_q])
    for g in gangs:
        if packed_ok is not None and not packed_ok.gpu_mask(
            np.array([g]), np.array([g])
        )[0, 0]:
            continue
        pi = np.flatnonzero(c.ok_p & (c.gi == g))
        qj = np.flatnonzero(c.ok_q & (c.gj == g))
        rc, cc = c.row_class[pi], c.col_class[qj]
        supply = np.bincount(rc, minlength=n_r)
        demand = np.bincount(cc, minlength=n_c)
        num_edges += int(supply @ (c.pairw > 0).astype(np.int64) @ demand)
        x = max_weight_transport(c.pairw, supply, demand)
        # each class's jobs in received order, and where the next free one is
        by_r = np.split(pi[np.argsort(rc, kind="stable")], np.cumsum(supply)[:-1])
        by_c = np.split(qj[np.argsort(cc, kind="stable")], np.cumsum(demand)[:-1])
        next_r = np.zeros(n_r, dtype=np.int64)
        next_c = np.zeros(n_c, dtype=np.int64)
        for a, b in zip(*np.nonzero(x)):
            f = x[a, b]
            rows.append(by_r[a][next_r[a] : next_r[a] + f])
            cols.append(by_c[b][next_c[b] : next_c[b] + f])
            next_r[a] += f
            next_c[b] += f
    if not rows:
        return np.zeros(0, np.int64), np.zeros(0, np.int64), num_edges
    return np.concatenate(rows), np.concatenate(cols), num_edges


def _uses_classes(backend: str, tie_break: bool, packed_ok) -> bool:
    """The class path serves a call that left the exact solver to the code
    and whose weights keep the class structure."""
    return (
        backend == "auto"
        and not tie_break
        and (packed_ok is None or getattr(packed_ok, "vectorized_on_gpus", False))
    )


def pack_jobs(
    placed: Sequence[JobState],
    pending: Sequence[JobState],
    profile: ThroughputProfile,
    optimize_strategy: bool = True,
    backend: str = "auto",
    packed_ok=None,
    context: Optional[MatchContext] = None,
    placed_gpu_types: Optional[Sequence[str]] = None,
    tie_break: bool = False,
) -> PackingResult:
    """Algorithm 4.

    With ``backend="auto"``, no ``tie_break`` and a ``packed_ok`` that is
    ``None`` or vectorised on gang sizes, packing is solved exactly on job
    classes (module docstring): the same optimum as a dense exact solve,
    with equal-weight choices going to the earliest jobs in the order given
    (``pending`` in placement order, so the policy's earlier job is
    packed).  ``context`` then only counts the solve
    (``stats["pack_class_solves"]``); no "packing" entry is read or kept.

    Otherwise ``backend`` is any matching-engine backend; the rectangular
    max-weight matching dispatches through
    :func:`repro.core.matching.solve_lap_batched`, so the same config knob
    that batches migration LAPs also selects the packing solver
    (``auction`` is near-optimal within ``n*eps`` on these float
    throughput weights).  ``context`` threads the scheduler's
    :class:`MatchContext`, keyed by JOB identity: rows are placed job ids
    and columns are pending job ids, so a graph that gains/loses a job (the
    dominant round-to-round event under churn) re-assembles last round's
    auction prices for the surviving jobs instead of cold-starting the
    whole matrix, and an unchanged graph memo-hits outright.
    """
    t0 = time.perf_counter()
    if not placed or not pending:
        return PackingResult({}, {}, 0.0, time.perf_counter() - t0, 0)
    tracer = tracer_of(context.obs if context is not None else None)
    if _uses_classes(backend, tie_break, packed_ok):
        with tracer.span("pack.classes") as sp:
            c = packing_classes(
                placed, pending, profile, optimize_strategy, placed_gpu_types
            )
            rows, cols, num_edges = _solve_classes(c, packed_ok)
            sp.annotate(edges=num_edges, matches=int(rows.size))
        if context is not None:
            context.stats["pack_class_solves"] = (
                context.stats.get("pack_class_solves", 0) + 1
            )
        weights = c.pairw[c.row_class[rows], c.col_class[cols]]
    else:
        with tracer.span("pack.graph"):
            w = build_packing_graph(
                placed, pending, profile, optimize_strategy, packed_ok,
                placed_gpu_types,
            )
        num_edges = int((w > 0).sum())
        if num_edges == 0:
            return PackingResult({}, {}, 0.0, time.perf_counter() - t0, 0)
        rows, cols = solve_lap_batched(
            w[None],
            maximize=True,
            backend=backend,
            context=context,
            context_key="packing",
            instance_ids=np.zeros(1, np.int64),
            row_ids=np.array([u.job_id for u in placed], np.int64),
            col_ids=np.array([v.job_id for v in pending], np.int64),
            tie_break=tie_break,
        ).pairs(0)
        weights = w[rows, cols]
    matches: Dict[int, int] = {}
    strategies: Dict[int, str] = {}
    total = 0.0
    with tracer.span("pack.apply"):
        for i, j, wij in zip(rows, cols, weights):
            if wij <= 0.0:
                continue  # zero-weight assignment = leave unpacked
            u, v = placed[i], pending[j]
            matches[v.job_id] = u.job_id
            prof_u = (
                profile
                if placed_gpu_types is None
                else profile.for_gpu_type(placed_gpu_types[i])
            )
            _, s = prof_u.combined_weight(
                u.spec.model, v.spec.model, optimize_strategy=optimize_strategy
            )
            if s != "dp":
                strategies[u.job_id] = s
            total += wij
    return PackingResult(
        matches, strategies, float(total), time.perf_counter() - t0, num_edges
    )
