"""One fused, sharded migration fan-out (the decide() hot path, on-device).

The host planner (:func:`repro.core.migration.plan_migration`, algorithm
``node``) runs Algorithm 2 as four host-orchestrated steps — cost
assembly, the k_c^2 pair-LAP fan-out, the node match, the scatter — with
a device readout between each.  This module compiles the whole migration
stage into ONE jitted XLA program with a SINGLE device→host readout per
round:

* **device-resident invalidation** — the planner caches last round's
  restricted slot matrices on device and diffs node occupancy there:
  one arrival/departure dirties only the pairs touching a changed
  physical or logical node (``dirty[i, j] = dirty_phys[i] |
  dirty_log[j]``).  Clean pairs re-enter the auction with their cached
  assignment and prices at ``eps_min`` — the ``lax.while_loop`` condition
  is immediately satisfied, so they cost ZERO bid rounds and never leave
  the device.
* **in-program benefit assembly** — pair costs are assembled from the
  slot matrices and the scaled ``1/(2g)`` weight table inside the same
  program (exact integers in f32 after the lcm scaling of
  ``migration._cost_scale``); with ``tie_break`` the positional
  perturbation ramp of ``engine._tie_break_perturb`` is added in-program
  (slot/node ids increase with position, so identity ranks equal
  positions — bit-identical to the host engine's identity-keyed ramp).
  With ``use_kernel`` the per-round bid top-2 routes through the fused
  Pallas kernel (:func:`repro.kernels.lap_bid.lap_bid_fused_pallas`),
  which assembles ``-cost + ramp - price`` inside its tiled VMEM sweep —
  the perturbed benefit never exists in HBM at all.
* **shard_map fan-out** — the pair axis is sharded across a device mesh
  (``fanout_shards``), each shard running its slice of the vmapped
  ``lax.while_loop`` auctions; the node match and the physical scatter
  run on the gathered results inside the same program.  Validated on CPU
  via ``--xla_force_host_platform_device_count`` (tests force 8).
* **auction via lax.while_loop** — both the pair fan-out and the node
  match reuse the Jacobi bid round of :mod:`repro.core.matching.auction`;
  dirty pairs and the node match run the full epsilon schedule seeded
  with the cached prices (valid for any initial prices on square
  instances), shifted to a zero minimum so f32 keeps resolving ``eps``.

Exactness / parity contract: scaled costs are integers and the tie-break
scale a power of two, so while ``k_l * scale / tb_scale < 2^24`` every
assembled f32 value is exact and the fused plan is **bit-identical** to
the host path's (with ``tie_break`` the perturbed optimum is unique, so
every exact solver — scipy shadow, warm host auction, this program —
returns the same assignment).  Instances outside that budget, and rounds
whose auctions fail to converge, fall back to the host planner (counted
in :attr:`FusedMigrationPlanner.stats`).
"""

from __future__ import annotations

import functools
import time
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.cluster import EMPTY, PlacementPlan, count_migrations
from repro.core.matching.auction import _inverse_assignment, _make_bid_round, _top2
from repro.core.migration import (
    MigrationResult,
    _cost_scale,
    _relabel_penalties,
    plan_migration,
)
from repro.obs.tracer import tracer_of

#: f32 mantissa budget: the largest scaled cost plus the finest tie-break
#: quantum must span fewer than 24 bits for the in-program f32 assembly to
#: be exact (see module docstring).
_F32_MANTISSA = float(1 << 24)


def _tb_scale(n: int, m: int) -> float:
    """Positional tie-break scale for an (n, m) integer-cost instance —
    the ``quantum = 1`` branch of ``engine._tie_break_perturb``."""
    bound = 2.0 * min(n, m) * float(n) * float(n) * float(m)
    return float(2.0 ** np.floor(np.log2(1.0 / bound)))


def _ramp(n: int, m: int, dtype=jnp.float32) -> jax.Array:
    """The (n, m) positional perturbation weights ``(i+1)^2 * (j+1)``."""
    gi = (jnp.arange(n, dtype=dtype) + 1.0)[:, None]
    gj = (jnp.arange(m, dtype=dtype) + 1.0)[None, :]
    return (gi * gi) * gj


def _pair_costs(pi_slots, pj_slots, weights_scaled):
    """All (kc, kc, kl, kl) scaled Algorithm-3 costs, in-program.

    Same computation as ``migration.pairwise_migration_cost`` over the
    full pair fan-out; EMPTY (-1) slots index a zero weight via an
    explicit remap (jnp clamps negative gather indices, so the host's
    negative-tail trick would silently read weight[0])."""
    zero_idx = weights_scaled.shape[0] - 1
    safe_i = jnp.where(pi_slots >= 0, pi_slots, zero_idx)
    safe_j = jnp.where(pj_slots >= 0, pj_slots, zero_idx)
    wu = weights_scaled[safe_i]  # (kc, kl, P)
    wv = weights_scaled[safe_j]
    eq = (
        pi_slots[:, None, :, None, :, None] == pj_slots[None, :, None, :, None, :]
    )  # (kc, kc, kl, kl, P, P)
    u_in_v = eq.any(-1)
    v_in_u = eq.any(-2)
    cost_out = (wu[:, None, :, None, :] * ~u_in_v).sum(-1)
    cost_in = (wv[None, :, None, :, :] * ~v_in_u).sum(-1)
    return cost_out + cost_in


def _pair_top2(use_kernel: bool, tb: float):
    """Bid top-2 over a raw COST matrix: jnp assembly (cheap on CPU) or
    the fused Pallas kernel (no HBM benefit matrix; same value order, so
    the two paths are bit-identical on in-budget integer instances)."""
    if use_kernel:
        from repro.kernels.lap_bid import lap_bid_fused_pallas

        return lambda cost, p: lap_bid_fused_pallas(cost, p, tb)
    return lambda cost, p: _top2((tb * _ramp(*cost.shape, cost.dtype) - cost) - p[None, :])


def _pair_auction(cost, eps_min, init_prices, init_col_of, warm, max_iters, use_kernel, tb):
    """One square Jacobi auction with explicit initial state, on a raw
    scaled COST matrix (benefit assembled in the bid's top-2 — see
    :func:`_pair_top2`).  The :func:`auction._auction_lap_jit` loop with
    an ``init_col_of``: a warm instance whose initial assignment is
    already complete terminates with ZERO bid rounds (the clean-pair
    fast path).  Returns ``(col_of, prices, iters, converged)``."""
    n = cost.shape[-1]
    eps_min = jnp.asarray(eps_min, jnp.float32)
    span = jnp.maximum(jnp.max(jnp.abs(cost)), 1.0)
    eps0 = jnp.where(warm, eps_min, jnp.maximum(span / 4.0, eps_min))
    bid_round = _make_bid_round(cost, n, _pair_top2(use_kernel, tb))

    def cond(state):
        _, col_of, eps, it = state
        done = jnp.all(col_of >= 0) & (eps <= eps_min * (1 + 1e-6))
        return (~done) & (it < max_iters)

    def body(state):
        prices, col_of, eps, it = state
        all_assigned = jnp.all(col_of >= 0)

        def next_phase(_):
            return prices, jnp.full((n,), -1, jnp.int32), jnp.maximum(eps / 5.0, eps_min)

        def same_phase(_):
            p, c = bid_round(prices, col_of, eps)
            return p, c, eps

        prices, col_of, eps = jax.lax.cond(
            all_assigned & (eps > eps_min * (1 + 1e-6)), next_phase, same_phase, None
        )
        return prices, col_of, eps, it + 1

    init = (init_prices, init_col_of, eps0, jnp.asarray(0, jnp.int32))
    prices, col_of, eps, iters = jax.lax.while_loop(cond, body, init)
    converged = jnp.all(col_of >= 0) & (eps <= eps_min * (1 + 1e-6))
    return col_of, prices, iters, converged


@functools.partial(
    jax.jit,
    static_argnames=("kc", "kl", "shards", "max_iters", "use_kernel", "tb_pair", "tb_node"),
)
def _fused_round(
    pi_slots,        # (kc, kl, P) int32 — restricted PREV (physical) plan
    pj_slots,        # (kc, kl, P) int32 — restricted NEW (logical) plan
    new_slots,       # (kc, kl, P) int32 — FULL new logical plan (scatter src)
    weights_scaled,  # (max_id + 2,) f32 — scale/(2g) per job id, zero tail
    pen_scaled,      # (kc, kc) f32 — scaled relabel penalties (zeros if none)
    cache_pi,        # (kc, kl, P) int32 — last round's pi_slots
    cache_pj,
    cache_col_of,    # (kc*kc, kl) int32 — last round's pair assignments
    cache_prices,    # (kc*kc, kl) f32 — last round's pair prices
    cache_node_prices,  # (kc,) f32
    cache_valid,     # () bool
    *,
    kc: int,
    kl: int,
    shards: int,
    max_iters: int,
    use_kernel: bool,
    tb_pair: float,  # 0.0 = tie-break off
    tb_node: float,
):
    """One fused migration round: diff → assemble → sharded pair fan-out →
    node match → physical scatter, all one XLA program.  Everything the
    host needs comes back in the single returned tuple (one readout)."""
    n_pairs = kc * kc
    eps_pair = (tb_pair if tb_pair > 0.0 else 1.0) / (kl + 1)
    eps_node = (tb_node if tb_node > 0.0 else 1.0) / (kc + 1)

    # --- per-node occupancy diff -> per-pair dirty mask ------------------ #
    dirty_i = jnp.any(pi_slots != cache_pi, axis=(1, 2)) | ~cache_valid
    dirty_j = jnp.any(pj_slots != cache_pj, axis=(1, 2)) | ~cache_valid
    dirty = (dirty_i[:, None] | dirty_j[None, :]).reshape(n_pairs)

    # --- in-program cost assembly (exact integers in f32) ---------------- #
    cost_p = _pair_costs(pi_slots, pj_slots, weights_scaled).reshape(n_pairs, kl, kl)

    # clean pairs re-enter at their cached optimum (zero bid rounds).  Dirty
    # pairs, like the node match below, run the full epsilon schedule from
    # the cached prices: one phase at eps_min from stale prices can take
    # ~span/eps bid rounds, and ran past max_iters on saturated Simulator
    # rounds from 16 nodes up.  Cached prices are shifted to a zero minimum
    # (a uniform shift changes no bid), so they cannot climb round over
    # round until f32 no longer resolves eps.
    arange_kl = jnp.arange(kl, dtype=jnp.int32)
    init_col = jnp.where(dirty[:, None], -1, cache_col_of)
    init_prices = jnp.where(
        cache_valid, cache_prices - cache_prices.min(axis=1, keepdims=True), 0.0
    )
    warm = ~dirty

    # --- sharded pair fan-out -------------------------------------------- #
    pad = (-n_pairs) % shards
    if pad:
        # dummy clean pairs: identity assignment, zero prices, zero cost —
        # the while_loop exits immediately; results are sliced off below
        cost_p = jnp.concatenate([cost_p, jnp.zeros((pad, kl, kl), cost_p.dtype)])
        init_col = jnp.concatenate(
            [init_col, jnp.broadcast_to(arange_kl, (pad, kl))]
        )
        init_prices = jnp.concatenate([init_prices, jnp.zeros((pad, kl), jnp.float32)])
        warm = jnp.concatenate([warm, jnp.ones((pad,), bool)])

    def solve_shard(cost_s, col_s, price_s, warm_s):
        return jax.vmap(
            lambda c, ic, ip, w: _pair_auction(
                c, eps_pair, ip, ic, w, max_iters, use_kernel, tb_pair
            )
        )(cost_s, col_s, price_s, warm_s)

    if shards > 1:
        mesh = Mesh(np.array(jax.devices()[:shards]), ("pairs",))
        solve_shard = jax.shard_map(
            solve_shard,
            mesh=mesh,
            in_specs=(P("pairs"), P("pairs"), P("pairs"), P("pairs")),
            out_specs=(P("pairs"), P("pairs"), P("pairs"), P("pairs")),
            check_vma=False,
        )
    col_of, prices, iters, conv = solve_shard(cost_p, init_col, init_prices, warm)
    if pad:
        col_of, prices, iters, conv = (
            col_of[:n_pairs],
            prices[:n_pairs],
            iters[:n_pairs],
            conv[:n_pairs],
        )
        cost_p = cost_p[:n_pairs]

    # --- node match over pair totals ------------------------------------- #
    picked = jnp.take_along_axis(cost_p, col_of[:, :, None], axis=2)
    total_scaled = picked[:, :, 0].sum(axis=1)  # (n_pairs,)
    node_cost = total_scaled.reshape(kc, kc) + pen_scaled
    node_col, node_prices, node_iters, node_conv = _pair_auction(
        node_cost,
        eps_node,
        jnp.where(cache_valid, cache_node_prices - cache_node_prices.min(), 0.0),
        jnp.full((kc,), -1, jnp.int32),
        False,
        max_iters,
        False,  # node instance: plain jnp assembly (one LAP, no fan-out win)
        tb_node,
    )

    # --- physical scatter (argsort == host gpu_assign, inverse == host
    # node_assignment[n_cols] = n_rows) ----------------------------------- #
    node_assignment = _inverse_assignment(node_col, kc)  # logical l -> physical k
    gpu_assign = jnp.argsort(col_of, axis=-1).astype(jnp.int32)  # (n_pairs, kl) v -> u
    pair_idx = node_assignment * kc + jnp.arange(kc, dtype=jnp.int32)
    u_of_v = gpu_assign[pair_idx]  # (kc_logical, kl)
    phys = jnp.full((kc, kl, new_slots.shape[-1]), EMPTY, new_slots.dtype)
    phys = phys.at[node_assignment[:, None], u_of_v].set(new_slots)

    matching_cost_scaled = jnp.sum(
        jnp.take_along_axis(node_cost, jnp.maximum(node_col, 0)[:, None], axis=1)[:, 0]
    )
    converged = jnp.all(conv) & node_conv
    stats = jnp.stack(
        [iters.sum(), node_iters, dirty.sum().astype(jnp.int32)]
    )
    return (
        phys,
        node_assignment,
        matching_cost_scaled,
        converged,
        stats,
        col_of,
        prices,
        node_prices,
        pi_slots,
        pj_slots,
    )


def lower_fused_round(
    kc: int,
    kl: int,
    pmax: int,
    n_weights: int,
    *,
    shards: int = 1,
    use_kernel: bool = False,
    tie_break: bool = False,
    max_iters: int = 20_000,
    sharding=None,
):
    """Lower :func:`_fused_round` for one cluster shape without running it.

    The arguments are shapes only (``n_weights`` is the weight table's
    length, ``max_id + 2``), so ``.compile()`` on the result gives the
    program a :class:`FusedMigrationPlanner` of that shape would run, and
    its ``as_text()`` / ``memory_analysis()``.  ``sharding`` places every
    argument, e.g. on a described device of a chip that is not attached.
    """

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    slots = arg((kc, kl, pmax), jnp.int32)
    return _fused_round.lower(
        slots,
        slots,
        slots,
        arg((n_weights,), jnp.float32),
        arg((kc, kc), jnp.float32),
        slots,
        slots,
        arg((kc * kc, kl), jnp.int32),
        arg((kc * kc, kl), jnp.float32),
        arg((kc,), jnp.float32),
        arg((), jnp.bool_),
        kc=kc,
        kl=kl,
        shards=shards,
        max_iters=max_iters,
        use_kernel=use_kernel,
        tb_pair=_tb_scale(kl, kl) if tie_break else 0.0,
        tb_node=_tb_scale(kc, kc) if tie_break else 0.0,
    )


class FusedMigrationPlanner:
    """Device-resident Algorithm-2 planner: one jitted, sharded program and
    one readout per round (see module docstring).

    Drop-in for the scheduler's migrate stage (``fused_fanout=True``):
    :meth:`plan` has the :func:`~repro.core.migration.plan_migration`
    contract for ``algorithm="node"`` and returns the same
    :class:`MigrationResult` (``algorithm="node-fused"``).  Rounds the
    fused program cannot serve exactly — f32 mantissa budget exceeded, or
    an auction hitting ``max_iters`` — fall back to the host planner and
    invalidate the device cache; both are counted in :attr:`stats`.
    """

    def __init__(
        self,
        shards: int = 1,
        use_kernel: bool = False,
        max_iters: int = 20_000,
        obs=None,
    ):
        n_dev = len(jax.devices())
        if not 1 <= int(shards) <= n_dev:
            raise ValueError(
                f"shards={shards}: the pair fan-out needs that many devices "
                f"and {n_dev} are visible"
            )
        self.shards = int(shards)
        self.use_kernel = bool(use_kernel)
        self.max_iters = int(max_iters)
        #: opt-in observability bundle — spans around the fused program,
        #: its single readout, and host fallbacks.  Pure host-side
        #: bookkeeping: no extra device work, no decision inputs touched.
        self.obs = obs
        self._cache = None  # device arrays: pi, pj, col_of, prices, node_prices
        self._cache_key = None  # (kc, kl, P, scale, tie_break)
        #: why the most recent :meth:`plan` call fell back to the host
        #: planner (``"fused-budget"`` / ``"fused-nonconverged"``), or
        #: ``None`` when it was served fused.  The scheduler folds this
        #: into the round's ``DegradeReason``.
        self.last_fallback_reason: Optional[str] = None
        self.stats: Dict[str, int] = {
            "fused_rounds": 0,
            "fused_host_fallbacks": 0,
            "fused_budget_fallbacks": 0,
            "fused_nonconverged_fallbacks": 0,
            "fused_dirty_pairs": 0,
            "fused_pair_instances": 0,
            "fused_bid_iters": 0,
            "fused_readouts": 0,
        }

    def invalidate(self) -> None:
        self._cache = None
        self._cache_key = None

    def invalidate_nodes(self, nodes) -> None:
        """TARGETED invalidation: poison only the cached occupancy rows of
        the given physical/logical nodes (node-down / node-up events), so
        next round's in-program diff marks exactly the pairs touching them
        dirty while every healthy pair stays clean (zero bid rounds).  The
        poison value ``-2`` can never equal a real slot id (ids are >= -1),
        so the dirty bit is guaranteed to trip even if the node's occupancy
        is coincidentally unchanged."""
        if self._cache is None:
            return
        idx = np.asarray(sorted(int(n) for n in nodes), dtype=np.int32)
        if idx.size == 0:
            return
        pi, pj, col_of, prices, node_prices = self._cache
        poison = jnp.full((idx.size,) + tuple(pi.shape[1:]), -2, pi.dtype)
        pi = pi.at[idx].set(poison)
        pj = pj.at[idx].set(poison)
        self._cache = (pi, pj, col_of, prices, node_prices)

    def plan(
        self,
        prev: PlacementPlan,
        new_logical: PlacementPlan,
        num_gpus_of: Dict[int, int],
        tie_break: bool = False,
        down_nodes: Optional[np.ndarray] = None,
        speed_factor: Optional[np.ndarray] = None,
    ) -> MigrationResult:
        tracer = tracer_of(self.obs)
        with tracer.span(
            "migrate.fused", shards=self.shards, kernel=self.use_kernel
        ) as sp:
            before = dict(self.stats)
            res = self._plan_impl(
                prev, new_logical, num_gpus_of, tie_break, down_nodes,
                speed_factor, tracer,
            )
            sp.annotate(
                fallback=self.last_fallback_reason or "none",
                dirty_pairs=self.stats["fused_dirty_pairs"]
                - before["fused_dirty_pairs"],
                bid_iters=self.stats["fused_bid_iters"]
                - before["fused_bid_iters"],
                readouts=self.stats["fused_readouts"]
                - before["fused_readouts"],
                migrations=res.num_migrations,
            )
        return res

    def _plan_impl(
        self,
        prev: PlacementPlan,
        new_logical: PlacementPlan,
        num_gpus_of: Dict[int, int],
        tie_break: bool,
        down_nodes: Optional[np.ndarray],
        speed_factor: Optional[np.ndarray],
        tracer,
    ) -> MigrationResult:
        t0 = time.perf_counter()
        self.last_fallback_reason = None
        cluster = prev.cluster
        kc, kl = cluster.num_nodes, cluster.gpus_per_node
        pmax = prev.slots.shape[-1]
        scale = _cost_scale(num_gpus_of, "auction")
        tb_pair = _tb_scale(kl, kl) if tie_break else 0.0
        tb_node = _tb_scale(kc, kc) if tie_break else 0.0

        # Health terms enter the fused program EXACTLY as the host planner
        # computes them: the same _relabel_penalties matrix (down-node
        # domination, straggler-drain half-units, type/rack terms) is
        # scaled and added to the in-program node cost, and its magnitude
        # counts against the same f32 mantissa budget below — so fused
        # plans with health terms on stay bit-identical to the host path.
        occupied_logical = (new_logical.slots != EMPTY).any(axis=(1, 2))
        pen = _relabel_penalties(
            cluster, down_nodes, occupied_logical, speed_factor
        )
        pen_max = 0.0 if pen is None else float(pen.max())

        # f32 exactness budget: the largest scaled node-cost magnitude
        # (each pair cell is <= 2 * MAX_PACK * 1/2 * scale, a pair total
        # sums kl cells, plus the relabel penalty) against the finest
        # tie-break quantum.  Outside the budget the fused program could
        # mis-round — serve the round from the host instead.
        quantum = min(tb_pair or 1.0, tb_node or 1.0)
        max_abs = (2.0 * pmax * kl + pen_max) * scale
        if max_abs / quantum >= _F32_MANTISSA:
            self.stats["fused_host_fallbacks"] += 1
            self.stats["fused_budget_fallbacks"] += 1
            self.last_fallback_reason = "fused-budget"
            self.invalidate()
            with tracer.span("migrate.fused.host_fallback", reason="fused-budget"):
                return self._host(
                    prev, new_logical, num_gpus_of, tie_break, down_nodes,
                    speed_factor,
                )

        common = prev.job_ids() & new_logical.job_ids()
        pi = prev.restricted_to(common).slots.astype(np.int32)
        pj = new_logical.restricted_to(common).slots.astype(np.int32)

        max_id = max(num_gpus_of) if num_gpus_of else 0
        weights = np.zeros(max_id + 2, np.float32)
        for j, g in num_gpus_of.items():
            weights[j] = scale / (2.0 * g)  # tessalint: mantissa-ok(exact for power-of-two gpu counts; the _F32_MANTISSA budget guard above falls back to host otherwise)
        pen_scaled = (
            np.zeros((kc, kc), np.float32)
            if pen is None
            else (pen * scale).astype(np.float32)
        )

        # NOT keyed on max_id: the weights table regrows as job ids climb,
        # but a clean pair's slots pin the exact same ids (and per-id
        # num_gpus is immutable), so its cached cost/assignment stays valid
        key = (kc, kl, pmax, scale, tie_break)
        if self._cache_key != key:
            self.invalidate()
        if self._cache is None:
            cache = (
                jnp.zeros((kc, kl, pmax), jnp.int32),
                jnp.zeros((kc, kl, pmax), jnp.int32),
                jnp.broadcast_to(jnp.arange(kl, dtype=jnp.int32), (kc * kc, kl)),
                jnp.zeros((kc * kc, kl), jnp.float32),
                jnp.zeros((kc,), jnp.float32),
                jnp.asarray(False),
            )
        else:
            cache = (*self._cache, jnp.asarray(True))

        with tracer.span("migrate.fused.program", kc=kc, kl=kl):
            out = _fused_round(
                jnp.asarray(pi),
                jnp.asarray(pj),
                jnp.asarray(new_logical.slots.astype(np.int32)),
                jnp.asarray(weights),
                jnp.asarray(pen_scaled),
                *cache,
                kc=kc,
                kl=kl,
                shards=self.shards,
                max_iters=self.max_iters,
                use_kernel=self.use_kernel,
                tb_pair=tb_pair,
                tb_node=tb_node,
            )
        # THE readout: everything host-side comes off the device here, once
        phys_dev, node_assign_dev, cost_dev, conv_dev, stats_dev = out[:5]
        with tracer.span("migrate.fused.readout"):
            phys, node_assignment, cost_scaled, converged, stats = jax.device_get(  # tessalint: sync-ok(THE one sanctioned readout per fused round; see BENCH_fused_decide.json)
                (phys_dev, node_assign_dev, cost_dev, conv_dev, stats_dev)
            )
        self.stats["fused_readouts"] += 1

        if not bool(converged):
            self.stats["fused_host_fallbacks"] += 1
            self.stats["fused_nonconverged_fallbacks"] += 1
            self.last_fallback_reason = "fused-nonconverged"
            self.invalidate()
            with tracer.span(
                "migrate.fused.host_fallback", reason="fused-nonconverged"
            ):
                return self._host(
                    prev, new_logical, num_gpus_of, tie_break, down_nodes,
                    speed_factor,
                )

        # cache stays device-resident for next round's diff / warm start
        self._cache = (out[8], out[9], out[5], out[6], out[7])
        self._cache_key = key
        self.stats["fused_rounds"] += 1
        self.stats["fused_pair_instances"] += kc * kc
        self.stats["fused_dirty_pairs"] += int(stats[2])
        self.stats["fused_bid_iters"] += int(stats[0]) + int(stats[1])

        phys_plan = PlacementPlan(cluster, np.asarray(phys, np.int64))
        n_mig = count_migrations(prev, phys_plan)
        return MigrationResult(
            phys_plan,
            n_mig,
            float(cost_scaled) / scale,
            np.asarray(node_assignment, np.int64),
            time.perf_counter() - t0,
            "node-fused",
        )

    def _host(
        self,
        prev,
        new_logical,
        num_gpus_of,
        tie_break,
        down_nodes=None,
        speed_factor=None,
    ) -> MigrationResult:
        res = plan_migration(
            prev,
            new_logical,
            num_gpus_of,
            algorithm="node",
            backend="auto",
            tie_break=tie_break,
            down_nodes=down_nodes,
            speed_factor=speed_factor,
        )
        return MigrationResult(
            res.physical_plan,
            res.num_migrations,
            res.matching_cost,
            res.node_assignment,
            res.wall_time_s,
            "node-fused-fallback",
        )
