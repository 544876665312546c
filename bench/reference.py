"""Plain reference for the scheduler's round output, and the checks that use it.

Written from the paper's definitions (Tesserae, arXiv 2508.04953, Algorithms
2 and 3), with no code of the program under test:

* A plan is an int array ``slots[node, gpu, pack_slot]`` of job ids, ``-1``
  for an empty slot.  Only jobs present in both the previous physical plan
  and the new logical plan enter the costs.
* Moving logical GPU ``v`` onto physical GPU ``u`` costs, for every job on
  exactly one of the two, ``1 / (2 * gang)``.
* A node pair's cost is the least sum over a one-to-one map of its GPUs
  (solved exactly here by dynamic programming over subsets of GPUs), and
  the relabelling is the node map of least total pair cost (solved by
  ``scipy.optimize.linear_sum_assignment``).

A cluster may give every node a GPU type and a rack (``types[k]``,
``racks[k]``).  Logical node ``l`` was laid out for physical node ``l``'s
GPU type and rack, and the relabelling keeps to two rules:

* A logical node may be hosted only on a physical node of its own GPU type:
  a node pair of two types is not in the assignment at all.
* Hosting a logical node on a node of another rack costs 1/2, whether the
  node is empty or not; the relabelling's cost includes it.

Every cost is a sum of a few multiples of 1/64 at these gang sizes, and of
halves, so the float64 arithmetic below is exact and the optimum is compared
for equality.  ``precision="bfloat16"`` computes the same relabelling with
every cost and every sum rounded to bfloat16, rack halves included: the
control, which the comparison must reject.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import numpy as np
from scipy.optimize import linear_sum_assignment

EMPTY = -1
#: the cost of hosting a logical node on a node of another rack
CROSS_RACK = 0.5


def weight_table(gangs: Dict[int, int]) -> np.ndarray:
    """``w[job] = 1 / (2 * gang)``; the last entry (index -1, EMPTY) is 0."""
    w = np.zeros(max(gangs) + 2, np.float64)
    ids = np.fromiter(gangs.keys(), np.int64, len(gangs))
    g = np.fromiter(gangs.values(), np.float64, len(gangs))
    w[ids] = 0.5 / g
    return w


def restrict(slots: np.ndarray, keep: np.ndarray) -> np.ndarray:
    """``slots`` with every job outside ``keep`` removed."""
    return np.where(np.isin(slots, keep), slots, EMPTY)


def common_jobs(prev: np.ndarray, logical: np.ndarray) -> np.ndarray:
    return np.intersect1d(prev[prev != EMPTY], logical[logical != EMPTY])


def _gpu_costs(phys: np.ndarray, logi: np.ndarray, w: np.ndarray) -> np.ndarray:
    """Cost of putting logical GPU content ``logi[..., v, :]`` where physical
    GPU content ``phys[..., u, :]`` was: ``(..., u, v)``.  Broadcasts over
    leading axes.  W(A) + W(B) - 2 W(A & B), each job on a GPU at most once."""
    wa = w[phys].sum(-1)  # (..., U)
    wb = w[logi].sum(-1)  # (..., V)
    shared = np.zeros(np.broadcast_shapes(wa[..., :, None].shape, wb[..., None, :].shape))
    for p in range(phys.shape[-1]):
        a = phys[..., :, None, p]
        for q in range(logi.shape[-1]):
            same = (a == logi[..., None, :, q]) & (a != EMPTY)
            shared += np.where(same, w[a], 0.0)
    return wa[..., :, None] + wb[..., None, :] - 2.0 * shared


def pair_costs(prev: np.ndarray, logical: np.ndarray, w: np.ndarray) -> np.ndarray:
    """``(kc, kc, kl, kl)``: ``[i, j, u, v]`` = cost of logical GPU ``v`` of
    logical node ``j`` landing on physical GPU ``u`` of physical node ``i``."""
    return _gpu_costs(prev[:, None, :, :], logical[None, :, :, :], w)


class _Arith:
    """Float64 (exact here) or bfloat16 arithmetic for the control."""

    def __init__(self, precision: str):
        if precision == "float64":
            self.dtype = np.float64
        elif precision == "bfloat16":
            import ml_dtypes

            self.dtype = ml_dtypes.bfloat16
        else:
            raise ValueError(f"unknown precision {precision!r}")

    def cast(self, x):
        return np.asarray(x).astype(self.dtype)

    def add(self, a, b):
        # each sum computed exactly in float64, then rounded once
        return (np.asarray(a, np.float64) + np.asarray(b, np.float64)).astype(self.dtype)

    def total(self, x):
        """Pairwise (tree) sum, rounded at every step."""
        x = self.cast(x)
        while x.size > 1:
            if x.size % 2:
                x = np.concatenate([x, self.cast([0.0])])
            x = self.add(x[0::2], x[1::2])
        return float(np.asarray(x, np.float64).sum())


def min_assignment(costs: np.ndarray, ar: _Arith, chunk: int = 16384) -> np.ndarray:
    """Least-cost one-to-one map of every ``(n, n)`` instance in ``costs``
    (``(B, n, n)``), by dynamic programming over subsets of columns: row
    ``popcount(mask) - 1`` takes one column of ``mask``.  Returns ``(B,)``."""
    b, n, _ = costs.shape
    out = np.empty(b, np.float64)
    bits = [[c for c in range(n) if mask >> c & 1] for mask in range(1 << n)]
    for s in range(0, b, chunk):
        c = ar.cast(costs[s : s + chunk])
        dp = np.empty((c.shape[0], 1 << n), ar.dtype)
        dp[:, 0] = 0.0
        for mask in range(1, 1 << n):
            row = len(bits[mask]) - 1
            best = None
            for col in bits[mask]:
                cand = ar.add(dp[:, mask ^ (1 << col)], c[:, row, col])
                best = cand if best is None else np.minimum(best, cand)
            dp[:, mask] = best
        out[s : s + chunk] = np.asarray(dp[:, -1], np.float64)
    return out


def gpu_map(cost: np.ndarray) -> np.ndarray:
    """For one ``(n, n)`` instance: ``u_of_v[v]``, the physical GPU that
    logical GPU ``v`` lands on in a least-cost map (exact, float64)."""
    rows, cols = linear_sum_assignment(np.asarray(cost, np.float64))
    u_of_v = np.empty(cost.shape[0], np.int64)
    u_of_v[cols] = rows
    return u_of_v


def node_assignment(
    node_cost: np.ndarray, types: Optional[np.ndarray]
) -> Tuple[np.ndarray, np.ndarray]:
    """Physical rows and logical columns of the node map of least total
    cost, rows ascending; with ``types``, one assignment for each GPU type
    over that type's nodes, so that no pair of two types is in it."""
    if types is None:
        return linear_sum_assignment(node_cost)
    types = np.asarray(types)
    rows, cols = [], []
    for t in np.unique(types):
        nodes = np.flatnonzero(types == t)
        r, c = linear_sum_assignment(node_cost[np.ix_(nodes, nodes)])
        rows.append(nodes[r])
        cols.append(nodes[c])
    rows, cols = np.concatenate(rows), np.concatenate(cols)
    order = np.argsort(rows)
    return rows[order], cols[order]


def relabel(
    prev: np.ndarray,
    logical: np.ndarray,
    gangs: Dict[int, int],
    precision: str = "float64",
    types: Optional[np.ndarray] = None,
    racks: Optional[np.ndarray] = None,
) -> Tuple[float, np.ndarray, np.ndarray]:
    """Algorithm 2: ``(matching cost, physical plan, node map)`` of the
    relabelling of ``logical`` onto the physical nodes of ``prev`` that moves
    least; ``node_map[l]`` is the physical node that hosts logical node
    ``l``.  ``types`` keeps every logical node on its own GPU type, and
    ``racks`` adds 1/2 to the cost for every rack crossing."""
    ar = _Arith(precision)
    w = weight_table(gangs)
    keep = common_jobs(prev, logical)
    pi, pj = restrict(prev, keep), restrict(logical, keep)
    kc, kl = prev.shape[:2]
    costs = pair_costs(pi, pj, w)  # (kc, kc, kl, kl)
    node_cost = min_assignment(costs.reshape(kc * kc, kl, kl), ar).reshape(kc, kc)
    if racks is not None:
        racks = np.asarray(racks)
        crossing = CROSS_RACK * (racks[:, None] != racks[None, :])
        node_cost = np.asarray(ar.add(node_cost, crossing), np.float64)
    rows, cols = node_assignment(node_cost, types)  # physical row -> logical col
    total = ar.total(node_cost[rows, cols])
    phys = np.full_like(logical, EMPTY)
    for i, j in zip(rows, cols):
        phys[i, gpu_map(ar.cast(costs[i, j]))] = logical[j]
    node_map = np.empty(kc, np.int64)
    node_map[cols] = rows
    return total, phys, node_map


def plan_cost(
    prev: np.ndarray,
    phys: np.ndarray,
    gangs: Dict[int, int],
    logical: np.ndarray,
    node_map: Optional[np.ndarray] = None,
    racks: Optional[np.ndarray] = None,
) -> float:
    """Exact cost of going from ``prev`` to the physical plan ``phys``: every
    physical GPU's old and new content, over the jobs common to ``prev`` and
    the logical plan; with ``racks``, plus 1/2 for each logical node that the
    plan's ``node_map`` hosts in another rack."""
    w = weight_table(gangs)
    keep = common_jobs(prev, logical)
    a, b = restrict(prev, keep), restrict(phys, keep)
    cost = float(np.diagonal(_gpu_costs(a, b, w), axis1=-2, axis2=-1).sum())
    if racks is not None:
        if node_map is None:
            raise ValueError("the rack term needs the plan's node map")
        racks = np.asarray(racks)
        cost += CROSS_RACK * int((racks[np.asarray(node_map)] != racks).sum())
    return cost


def _node_keys(slots: np.ndarray) -> List[Tuple[Tuple[int, ...], ...]]:
    """Each node's content, its GPUs' job sets in sorted order."""
    per_gpu = np.sort(slots, axis=-1)  # a GPU's jobs as a set
    return [tuple(sorted(map(tuple, node.tolist()))) for node in per_gpu]


def plan_problems(
    slots: np.ndarray,
    active: np.ndarray,
    gangs: Dict[int, int],
    logical: Optional[np.ndarray] = None,
    types: Optional[np.ndarray] = None,
    node_map: Optional[np.ndarray] = None,
) -> List[str]:
    """What is wrong with one round's plan (empty when it is valid):
    unknown jobs, a job twice on one GPU, a gang on another number of GPUs,
    a gang not consolidated (one node, or whole nodes), and, for a migrated
    round, a plan that is not a node-and-GPU relabelling of ``logical``.
    With the plan's ``node_map``: a logical node that is not where the map
    puts it, and, with ``types``, a logical node hosted on a node of another
    GPU type."""
    kc, kl, _ = slots.shape
    out: List[str] = []
    node, _, _ = np.nonzero(slots != EMPTY)
    jobs = slots[slots != EMPTY]
    unknown = np.setdiff1d(jobs, active)
    if unknown.size:
        out.append(f"{unknown.size} jobs not active, e.g. {int(unknown[0])}")
        return out
    s = np.sort(slots, axis=-1)
    twice = ((s[..., 1:] == s[..., :-1]) & (s[..., 1:] != EMPTY)).any(-1)
    if twice.any():
        out.append(f"{int(twice.sum())} GPUs hold one job twice")
    ids, n_gpus = np.unique(jobs, return_counts=True)
    want = np.array([gangs[int(j)] for j in ids], np.int64)
    if (n_gpus != want).any():
        k = int(np.argmax(n_gpus != want))
        out.append(f"job {int(ids[k])} on {int(n_gpus[k])} GPUs, gang {int(want[k])}")
    pairs, per_node = np.unique(np.stack([jobs, node], 1), axis=0, return_counts=True)
    nodes_of = np.bincount(np.searchsorted(ids, pairs[:, 0]), minlength=ids.size)
    g_of_pair = np.array([gangs[int(j)] for j in pairs[:, 0]], np.int64)
    split = (g_of_pair <= kl) & (nodes_of[np.searchsorted(ids, pairs[:, 0])] != 1)
    partial = (g_of_pair > kl) & (per_node != kl)
    if split.any() or partial.any():
        out.append(f"{int(split.sum() + partial.sum())} job-node pairs not consolidated")
    if logical is not None and sorted(_node_keys(slots)) != sorted(_node_keys(logical)):
        out.append("not a relabelling of the logical plan")
    if logical is not None and node_map is not None:
        node_map = np.asarray(node_map)
        if not np.array_equal(np.sort(node_map), np.arange(kc)):
            out.append("the node map is not one-to-one")
            return out
        hosted, laid_out = _node_keys(slots), _node_keys(logical)
        astray = sum(hosted[k] != laid_out[lo] for lo, k in enumerate(node_map.tolist()))
        if astray:
            out.append(f"{astray} logical nodes not where the node map puts them")
        if types is not None:
            types = np.asarray(types)
            crossed = int((types[node_map] != types).sum())
            if crossed:
                out.append(f"{crossed} logical nodes hosted on a node of another GPU type")
    return out
