"""Multi-device fused-decide parity suite.

The fused migration planner (:mod:`repro.core.fused`) compiles the whole
Algorithm-2 stage — occupancy diff, in-program cost assembly, the sharded
pair-LAP fan-out, the node match and the physical scatter — into one
jitted XLA program with a single readout per round.  This suite is its
churn-replay differential gate:

* **fused vs host, bit-identical**: the 60+ round churn replay of
  ``test_churn_replay`` driven with ``fused_fanout=True`` and a cold
  scipy shadow deciding from the SAME per-round inputs must produce
  bit-identical physical plans every round under ``tie_break`` (the
  perturbed optimum is unique, so every exact solver agrees), and
  exactly equal integer-quantised matching costs without it.
* **shard invariance**: conftest forces 8 host devices
  (``--xla_force_host_platform_device_count=8``); replays sharded over
  1 / 2 / 8 of them must be bit-identical to each other — sharding the
  fan-out batch is pure partitioning, never semantics.
* **hypothesis property**: for random plan pairs, ANY shard split of the
  pair axis preserves the full physical relabelling.
"""

import itertools
import re

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

import jax

from repro.core.cluster import EMPTY, ClusterSpec, PlacementPlan
from repro.core.fused import FusedMigrationPlanner
from repro.core.migration import plan_migration
from repro.core.placement import place_without_packing
from repro.core.profiler import ThroughputProfile
from repro.core.scheduler import TesseraeScheduler
from repro.core.simulator import SimConfig, Simulator
from repro.core.policies import TiresiasPolicy
from repro.core.traces import shockwave_trace, synthetic_active_jobs
from repro.workloads.generators import Arrivals, GangSizes, TraceRecipe, generate_trace
from repro.workloads.schema import to_jobspecs

from tests.test_churn_replay import MIN_ROUNDS, N_JOBS, ARRIVAL_RATE, SEED, RecordingScheduler

pytest.importorskip("scipy.optimize")

SHARD_COUNTS = (1, 2, 8)


def _run_fused(shards, tie_break, shadow=True):
    profile = ThroughputProfile()
    cluster = ClusterSpec(4, 4)
    shadow_sched = None
    if shadow:
        shadow_sched = TesseraeScheduler(
            cluster,
            TiresiasPolicy(profile, queue_base=900.0),
            profile,
            lap_backend="scipy",
            enable_packing=False,
            tie_break=tie_break,
        )
    sched = RecordingScheduler(
        cluster,
        TiresiasPolicy(profile, queue_base=900.0),
        profile,
        lap_backend="scipy",
        cold=False,
        shadow=shadow_sched,
        enable_packing=False,
        tie_break=tie_break,
        fused_fanout=True,
        fanout_shards=shards,
    )
    trace = shockwave_trace(
        num_jobs=N_JOBS, arrival_rate_per_hour=ARRIVAL_RATE, seed=SEED, profile=profile
    )
    sim = Simulator(
        cluster,
        trace,
        sched,
        profile,
        SimConfig(round_duration_s=360.0, resume_fraction=0.25),
    )
    return sim.run(), sched


class TestFusedChurnParity:
    """Fused planner vs the cold scipy shadow over the full churn replay."""

    @pytest.fixture(scope="class")
    def replays(self):
        # one replay per shard count, shadow only on the first (the others
        # are compared against it round-by-round)
        out = {}
        for s in SHARD_COUNTS:
            out[s] = _run_fused(s, tie_break=True, shadow=(s == SHARD_COUNTS[0]))
        return out

    def test_devices_actually_forced(self):
        assert len(jax.devices()) >= max(SHARD_COUNTS), (
            "conftest did not force 8 host devices — shard parity is vacuous"
        )

    def test_plans_bit_identical_to_host_all_rounds(self, replays):
        _, sched = replays[SHARD_COUNTS[0]]
        assert len(sched.round_log) >= MIN_ROUNDS
        for t, entry in enumerate(sched.round_log):
            assert entry["plan"] == entry["shadow"]["plan"], (
                f"round {t}: fused physical plan != cold scipy shadow"
            )

    def test_matching_costs_exact(self, replays):
        _, sched = replays[SHARD_COUNTS[0]]
        compared = 0
        for t, entry in enumerate(sched.round_log):
            if entry["mig_cost"] is None:
                continue
            compared += 1
            assert entry["mig_cost"] == pytest.approx(
                entry["shadow"]["mig_cost"], abs=1e-9
            ), f"round {t}"
        assert compared >= MIN_ROUNDS

    def test_shard_counts_bit_identical(self, replays):
        ref_res, ref_sched = replays[SHARD_COUNTS[0]]
        for s in SHARD_COUNTS[1:]:
            res, sched = replays[s]
            assert len(sched.round_log) == len(ref_sched.round_log)
            for t, (a, b) in enumerate(zip(sched.round_log, ref_sched.round_log)):
                assert a["plan"] == b["plan"], f"shards={s} round {t}: plans differ"
                assert a["mig_cost"] == b["mig_cost"], f"shards={s} round {t}"
            np.testing.assert_array_equal(
                [res.jobs[j].finish_time for j in sorted(res.jobs)],
                [ref_res.jobs[j].finish_time for j in sorted(ref_res.jobs)],
            )

    def test_fused_lane_actually_ran(self, replays):
        """The replay must have been served by the fused program, not the
        host fallback, with exactly ONE device readout per migration
        round — the tentpole's O(1)-readout contract."""
        _, sched = replays[SHARD_COUNTS[0]]
        rounds = [e["match_stats"] for e in sched.round_log]
        fused_rounds = sum(r.get("fused_rounds", 0) for r in rounds)
        fallbacks = sum(r.get("fused_host_fallbacks", 0) for r in rounds)
        readouts = sum(r.get("fused_readouts", 0) for r in rounds)
        mig_rounds = sum(1 for e in sched.round_log if e["mig_cost"] is not None)
        assert fused_rounds == mig_rounds, (fused_rounds, mig_rounds)
        assert fallbacks == 0
        assert readouts == mig_rounds

    def test_invalidation_is_partial(self, replays):
        """Occupancy diffing must keep some pairs clean on most rounds —
        a full-batch invalidation every round would make the device cache
        pointless."""
        _, sched = replays[SHARD_COUNTS[0]]
        partial = 0
        total = 0
        for e in sched.round_log:
            st_ = e["match_stats"]
            if not st_.get("fused_pair_instances"):
                continue
            total += 1
            if st_.get("fused_dirty_pairs", 0) < st_["fused_pair_instances"]:
                partial += 1
        assert total >= MIN_ROUNDS
        assert partial >= total // 2, (partial, total)


class TestFusedCostParityNoTieBreak:
    """Without tie-breaking, assignments may legitimately differ between
    solvers, but the integer-quantised matching cost must still be exact
    every round."""

    def test_costs_exact(self):
        _, sched = _run_fused(1, tie_break=False, shadow=True)
        compared = 0
        for t, entry in enumerate(sched.round_log):
            if entry["mig_cost"] is None:
                continue
            compared += 1
            assert entry["mig_cost"] == pytest.approx(
                entry["shadow"]["mig_cost"], abs=1e-9
            ), f"round {t}"
        assert compared >= MIN_ROUNDS


class TestShardSplitProperty:
    """Hypothesis: sharding the fan-out batch along ANY split of the pair
    axis preserves the physical relabelling bit-for-bit."""

    @given(
        seed=st.integers(0, 2**32 - 1),
        drop=st.integers(0, 3),
        shards=st.sampled_from(SHARD_COUNTS + (3, 5)),
    )
    @settings(max_examples=12, deadline=None)
    def test_any_split_preserves_plan(self, seed, drop, shards):
        profile = ThroughputProfile()
        cluster = ClusterSpec(4, 4)
        jobs = synthetic_active_jobs(12, seed=seed, profile=profile)
        jobs = [j for j in jobs if j.num_gpus <= 4 or j.num_gpus % 4 == 0]
        prev, _, _ = place_without_packing(cluster, jobs)
        new, _, _ = place_without_packing(cluster, jobs[drop:] or jobs)
        g = {j.job_id: j.num_gpus for j in jobs}

        base = FusedMigrationPlanner(shards=1).plan(prev, new, g, tie_break=True)
        split = FusedMigrationPlanner(shards=shards).plan(prev, new, g, tie_break=True)
        host = plan_migration(
            prev, new, g, algorithm="node", backend="scipy", tie_break=True
        )
        np.testing.assert_array_equal(
            base.physical_plan.slots, split.physical_plan.slots
        )
        np.testing.assert_array_equal(
            base.physical_plan.slots, host.physical_plan.slots
        )
        assert base.matching_cost == pytest.approx(host.matching_cost, abs=1e-9)


class TestFusedHealthTermParity:
    """Straggler-drain penalties folded into the in-program cost assembly
    must stay bit-identical to the host planner: both sides share the
    same host-computed pen matrix and the mantissa budget accounts for
    its magnitude, so parity holds by construction — this pins it."""

    @given(seed=st.integers(0, 2**32 - 1), drop=st.integers(0, 3))
    @settings(max_examples=10, deadline=None)
    def test_speed_terms_preserve_host_parity(self, seed, drop):
        profile = ThroughputProfile()
        cluster = ClusterSpec(4, 4)
        jobs = synthetic_active_jobs(12, seed=seed, profile=profile)
        jobs = [j for j in jobs if j.num_gpus <= 4 or j.num_gpus % 4 == 0]
        prev, _, _ = place_without_packing(cluster, jobs)
        new, _, _ = place_without_packing(cluster, jobs[drop:] or jobs)
        g = {j.job_id: j.num_gpus for j in jobs}
        rng = np.random.default_rng(seed)
        speed = np.where(rng.random(4) < 0.5,
                         rng.uniform(0.2, 0.9, 4), 1.0)

        fused = FusedMigrationPlanner().plan(
            prev, new, g, tie_break=True, speed_factor=speed
        )
        host = plan_migration(
            prev, new, g, algorithm="node", backend="scipy",
            tie_break=True, speed_factor=speed,
        )
        np.testing.assert_array_equal(
            fused.physical_plan.slots, host.physical_plan.slots
        )
        assert fused.matching_cost == pytest.approx(
            host.matching_cost, abs=1e-9
        )


def test_more_shards_than_devices_raises():
    with pytest.raises(ValueError, match="devices"):
        FusedMigrationPlanner(shards=len(jax.devices()) + 1)
    with pytest.raises(ValueError, match="devices"):
        FusedMigrationPlanner(shards=0)


def test_saturated_replay_every_round_fused():
    """16 nodes, 96 jobs all arriving in the first round: nearly every
    pair is dirty every round.  Each round must be served by the fused
    program, bit-identical to the cold scipy shadow under ``tie_break``.
    When dirty pairs and the node match ran one phase at ``eps_min`` from
    last round's (ever-climbing) prices, rounds ran past ``max_iters`` and
    fell back to the host."""
    profile = ThroughputProfile()
    cluster = ClusterSpec(16, 4)
    # 1- and 2-GPU gangs keep 16 nodes inside the f32 budget under tie_break
    recipe = TraceRecipe(
        arrivals=Arrivals(rate_per_hour=96 * 12.0),
        gangs=GangSizes((1, 2), (0.7, 0.3)),
    )
    trace = to_jobspecs(generate_trace(recipe, 96, seed=0), profile)

    def sched(cls, **kw):
        return cls(cluster, TiresiasPolicy(profile), profile, lap_backend="scipy",
                   tie_break=True, **kw)

    rec = sched(RecordingScheduler, shadow=sched(TesseraeScheduler), fused_fanout=True)
    assert Simulator(cluster, trace, rec, profile).run(stop_after_rounds=12) is None
    migrated = rec.round_log[1:]
    assert len(migrated) == 11
    for t, e in enumerate(migrated):
        assert e["match_stats"].get("fused_host_fallbacks", 0) == 0, f"round {t}"
        assert e["match_stats"]["fused_rounds"] == 1, f"round {t}"
        assert e["plan"] == e["shadow"]["plan"], f"round {t}"


def test_pair_trips_are_the_slowest_pairs_bid_rounds():
    """A cold 16x4 round: every pair is dirty and starts from zero prices.
    ``fused_pair_trips`` is the vmapped pair loop's trip count, the most bid
    rounds any one pair took (replayed here by vmapping ``_pair_auction``
    over the same pairs), and the node match's rounds plus the pairs' sum
    make ``fused_bid_iters``."""
    import jax.numpy as jnp

    from repro.core.fused import _pair_auction, _pair_costs
    from repro.core.migration import _cost_scale

    profile = ThroughputProfile()
    cluster = ClusterSpec(16, 4)
    jobs = synthetic_active_jobs(64, seed=3, profile=profile)
    jobs = [j for j in jobs if j.num_gpus <= 4 or j.num_gpus % 4 == 0]
    prev, _, _ = place_without_packing(cluster, jobs)
    new, _, _ = place_without_packing(cluster, jobs[::-1][4:])
    g = {j.job_id: j.num_gpus for j in jobs}
    planner = FusedMigrationPlanner()
    planner.plan(prev, new, g)
    assert planner.stats["fused_rounds"] == 1

    kc, kl = 16, 4
    scale = _cost_scale(g, "auction")
    common = prev.job_ids() & new.job_ids()
    pi = prev.restricted_to(common).slots.astype(np.int32)
    pj = new.restricted_to(common).slots.astype(np.int32)
    weights = np.zeros(max(g) + 2, np.float32)
    for j, n in g.items():
        weights[j] = scale / (2.0 * n)
    cost = _pair_costs(jnp.asarray(pi), jnp.asarray(pj), jnp.asarray(weights))
    _, _, iters, conv = jax.vmap(
        lambda c: _pair_auction(c, 1.0 / (kl + 1), jnp.zeros(kl, jnp.float32),
                                jnp.full((kl,), -1, jnp.int32), False, 20_000, False, 0.0)
    )(cost.reshape(kc * kc, kl, kl))
    iters = np.asarray(iters)
    assert bool(np.all(np.asarray(conv)))
    assert planner.stats["fused_pair_trips"] == iters.max()
    assert planner.stats["fused_pair_trips"] < iters.sum()
    assert planner.stats["fused_node_iters"] > 0
    assert planner.stats["fused_bid_iters"] == iters.sum() + planner.stats["fused_node_iters"]


@pytest.mark.parametrize("k", [1, 2, 3, 4, 8, 16, 64, 512])
def test_dense_bid_round_matches_indexed(k):
    """The fused program's dense bid round makes the indexed round's
    decisions bit for bit, trip by trip: tie-heavy integer costs, partial
    starting assignments, warm (non-zero) prices, with and without the
    positional tie-break, and a falling eps as the scaling schedule has.
    Pair sides (gpus per node) and the node match's side (512 nodes)."""
    import jax.numpy as jnp

    from repro.core.fused import _pair_top2, _tb_scale
    from repro.core.matching.auction import _make_bid_round

    rng = np.random.default_rng(1000 + k)
    batch = max(2, 512 // k)
    cost = jnp.asarray(rng.integers(0, 3, (batch, k, k)).astype(np.float32))
    prices0 = jnp.asarray(rng.integers(0, 4, (batch, k)).astype(np.float32))
    # a partial injective assignment per instance: a random permutation
    # with about half of its persons unassigned
    perm = np.argsort(rng.random((batch, k)), axis=1).astype(np.int32)
    col0 = jnp.asarray(np.where(rng.random((batch, k)) < 0.5, -1, perm))

    def rounds(tb, dense):
        return jax.jit(jax.vmap(
            lambda c, p, a, e: _make_bid_round(c, k, _pair_top2(False, tb), dense)(p, a, e),
            in_axes=(0, 0, 0, None)))

    for tb in (0.0, _tb_scale(k, k)):
        indexed, dense = rounds(tb, False), rounds(tb, True)
        prices, col_of = prices0, col0
        for trip in range(30):
            eps = jnp.float32(max(2.0 / 5**trip, 1.0 / (k + 1)) * (tb or 1.0))
            p_ref, c_ref = indexed(cost, prices, col_of, eps)
            p_new, c_new = dense(cost, prices, col_of, eps)
            np.testing.assert_array_equal(
                np.asarray(p_new).view(np.int32), np.asarray(p_ref).view(np.int32),
                err_msg=f"tb={tb} trip {trip}: prices",
            )
            np.testing.assert_array_equal(c_new, c_ref, err_msg=f"tb={tb} trip {trip}: col_of")
            prices, col_of = p_ref, c_ref
        # the trips did bid: prices rose and assignments filled
        assert np.asarray(col_of >= 0).sum() > np.asarray(col0 >= 0).sum()


def index_ops_by_scope(hlo_text):
    """``{scope: number of gather and scatter instructions}`` of a compiled
    ``_fused_round``, by :func:`bench.program_spans.scope_map`."""
    from bench.program_spans import scope_map

    smap = scope_map(hlo_text)
    out = {}
    for line in hlo_text.splitlines():
        m = re.match(r"\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=", line)
        if m and ("gather(" in line or "scatter(" in line):
            scope = smap.get(m.group(1))
            out[scope] = out.get(scope, 0) + 1
    return out


def test_pair_auction_compiles_without_gather_or_scatter():
    """At 16 nodes x 4 GPUs the compiled fused round keeps no gather or
    scatter inside ``pair_auction`` (the pair fan-out takes the dense bid
    round), while ``node_match`` keeps its own (the picked pair totals)."""
    from repro.core.fused import lower_fused_round

    ops = index_ops_by_scope(lower_fused_round(16, 4, 2, 66).compile().as_text())
    assert "pair_auction" not in ops, ops
    assert ops.get("node_match", 0) > 0, ops
    assert ops.get("scatter", 0) > 0, ops


# --------------------------------------------------------------------------- #
# clusters of several GPU types: the type-feasible node match
# --------------------------------------------------------------------------- #
def _saturated_node_totals(rng, kc, keep_diagonal=False):
    """Node-pair totals (half units) shaped like a saturated round's: moving
    logical node ``j`` onto physical node ``i`` costs everything on both,
    ``a[i] + b[j]``, less what a few node pairs share (4 a logical node), so
    nearly every row ties across its whole type.  ``keep_diagonal`` makes
    the identity the cheap choice, as in a cluster's first migrated round."""
    a = rng.integers(0, 9, kc) / 2.0
    b = rng.integers(0, 9, kc) / 2.0
    t = a[:, None] + b[None, :]
    i, j = rng.integers(0, kc, 4 * kc), np.repeat(np.arange(kc), 4)
    t[i, j] -= 2 * np.minimum(np.minimum(a[i], b[j]), rng.integers(1, 5, 4 * kc) / 2.0)
    if keep_diagonal:
        t[np.arange(kc), np.arange(kc)] = 0.0
    return t


def test_512_node_typed_match_converges_where_the_priced_type_block_cannot():
    """512 nodes x 4 GPUs, 256 A100 and 256 V100 nodes in racks of 16, cost
    scale 16.  Leaving pairs of two types out of the node match, a second
    round warm-started from the first one's prices converges to scipy's
    optimum on each type block.  Pricing the type block instead (4,097
    units, x16), as the host planner does in float64, starts the epsilon
    schedule at a quarter of that block: every node price climbs past
    2^24 eps, where f32 cannot add ``eps`` to a price, and the same
    instance runs to the 20,000-iteration cap without converging."""
    import jax.numpy as jnp
    from scipy.optimize import linear_sum_assignment

    from repro.core.fused import _pair_auction

    kc, kl, scale, cap = 512, 4, 16.0, 20_000
    eps = 1.0 / (kc + 1)
    types = np.repeat([0, 1], kc // 2)
    feasible = types[:, None] == types[None, :]
    racks = np.arange(kc) // 16
    rack = 0.5 * (racks[:, None] != racks[None, :])
    rng = np.random.default_rng(0)
    costs = [
        np.where(feasible, (_saturated_node_totals(rng, kc, first) + rack) * scale, 0.0)
        for first in (True, False)
    ]

    def solve(cost, prices, mask):
        col, p, it, conv = _pair_auction(
            jnp.asarray(cost, jnp.float32), eps, jnp.asarray(prices, jnp.float32),
            jnp.full((kc,), -1, jnp.int32), False, cap, False, 0.0,
            None if mask is None else jnp.asarray(mask),
        )
        return np.asarray(col), np.asarray(p), int(it), bool(conv)

    _, p1, _, conv1 = solve(costs[0], np.zeros(kc), feasible)
    assert conv1
    warm = p1 - np.where(feasible, p1[None, :], np.inf).min(axis=1)
    col, p2, iters, conv = solve(costs[1], warm, feasible)
    assert conv and iters < cap
    assert (types[col] == types).all()
    blocks = [np.flatnonzero(types == t) for t in (0, 1)]
    optimum = sum(costs[1][np.ix_(b, b)][linear_sum_assignment(costs[1][np.ix_(b, b)])].sum()
                  for b in blocks)
    assert costs[1][np.arange(kc), col].sum() == optimum
    assert p2.max() / eps < 2**24

    base = 2.0 * kl * kc + 1.0
    priced = costs[1] + np.where(feasible, 0.0, (base + rack) * scale)
    _, p_priced, iters, conv = solve(priced, np.zeros(kc), None)
    assert not conv and iters == cap
    assert p_priced.max() / eps >= 2**24
    top = np.float32(p_priced.max())
    assert top + np.float32(eps) == top  # a bid of eps no longer moves it


def _typed_round_plans(rng, kc, kl, gangs, prev=None):
    """A previous plan and a logical plan of whole-node jobs of 1, 2 or 4
    GPUs (one pack slot each): the previous plan's nodes and GPUs shuffled,
    a fifth of its jobs gone and two nodes laid out anew."""

    def fresh(n):
        slots = np.full((n, kl, 2), EMPTY, np.int64)
        for node, p in itertools.product(range(n), range(2)):
            u = 0
            while u < kl:
                if rng.random() < 0.3:
                    u += 1
                    continue
                g = int(rng.choice([g for g in (1, 2, 4) if g <= kl - u]))
                gangs[len(gangs)] = g
                slots[node, u:u + g, p] = len(gangs) - 1
                u += g
        return slots

    prev = fresh(kc) if prev is None else prev
    logical = prev[rng.permutation(kc)][:, rng.permutation(kl)].copy()
    gone = [j for j in np.unique(logical[logical != EMPTY]) if rng.random() < 0.2]
    logical[np.isin(logical, gone)] = EMPTY
    logical[rng.choice(kc, size=2, replace=False)] = fresh(2)
    return prev, logical


def _typed_cluster(types, per_rack):
    return ClusterSpec(len(types), 4, node_gpu_types=tuple(types), nodes_per_rack=per_rack)


def test_typed_racked_planner_matches_host_and_reference_over_replayed_rounds():
    """32 nodes, 16 A100 and 16 V100 in racks of 8, ten rounds replayed from
    seeded random plans, each round's physical plan the next one's previous
    plan: every round is served by the type-feasible fused program, with the
    host planner's cost and the plain reference's optimum, a node map that
    keeps every logical node on its GPU type, and a plan the reference finds
    valid at that cost."""
    from bench import reference

    cluster = _typed_cluster(["a100"] * 16 + ["v100"] * 16, 8)
    types = np.array(cluster.node_types())
    racks = np.arange(32) // 8
    rng = np.random.default_rng(17)
    gangs = {}
    planner = FusedMigrationPlanner()
    prev = None
    for t in range(10):
        prev, logical = _typed_round_plans(rng, 32, 4, gangs, prev)
        a, b = PlacementPlan(cluster, prev.copy()), PlacementPlan(cluster, logical.copy())
        res = planner.plan(a, b, gangs)
        host = plan_migration(a, b, gangs, algorithm="node", backend="scipy")
        opt, _, _ = reference.relabel(prev, logical, gangs, types=types, racks=racks)
        assert res.algorithm == "node-fused", f"round {t}"
        assert res.matching_cost == host.matching_cost == opt, f"round {t}"
        node_map = np.asarray(res.node_assignment)
        assert (types[node_map] == types).all(), f"round {t}"
        plan = res.physical_plan.slots
        active = np.unique(logical[logical != EMPTY])
        assert reference.plan_problems(plan, active, gangs, logical, types=types,
                                       node_map=node_map) == [], f"round {t}"
        assert reference.plan_cost(prev, plan, gangs, logical, node_map=node_map,
                                   racks=racks) == opt, f"round {t}"
        prev = plan
    assert planner.stats["fused_host_fallbacks"] == 0
    assert planner.stats["fused_typed_rounds"] == planner.stats["fused_rounds"] == 10


def test_a_gpu_type_of_one_node_keeps_its_node():
    """Seven A100 nodes and one V100 node: the V100 type block is a single
    node, with no second-best value to bid against.  The round converges
    on the device, the V100 logical node stays on the V100 node, and no
    price runs away."""
    from bench import reference

    cluster = _typed_cluster(["a100"] * 7 + ["v100"], 4)
    types = np.array(cluster.node_types())
    rng = np.random.default_rng(3)
    gangs = {}
    prev, logical = _typed_round_plans(rng, 8, 4, gangs)
    planner = FusedMigrationPlanner()
    res = planner.plan(PlacementPlan(cluster, prev), PlacementPlan(cluster, logical), gangs)
    opt, _, _ = reference.relabel(prev, logical, gangs, types=types, racks=np.arange(8) // 4)
    assert res.algorithm == "node-fused"
    assert res.node_assignment[7] == 7
    assert res.matching_cost == opt
    assert 0 < planner.stats["fused_node_price_top"] < 2**24


def test_one_gpu_type_runs_the_untyped_program():
    """A cluster whose nodes all carry one GPU type has no type ids: the
    planner runs the program of an untyped cluster, lowered with no type
    argument, and decides as on the plain cluster; only a typed lowering
    takes the (kc,) type ids."""
    from repro.core.fused import lower_fused_round
    from repro.core.migration import node_type_ids

    uniform = _typed_cluster(["a100"] * 8, 0)
    plain = ClusterSpec(8, 4)
    assert node_type_ids(uniform) is None
    assert node_type_ids(_typed_cluster(["v100"] * 4 + ["a100"] * 4, 0)).tolist() == [1] * 4 + [0] * 4
    rng = np.random.default_rng(5)
    gangs = {}
    prev, logical = _typed_round_plans(rng, 8, 4, gangs)
    out = []
    for cluster in (uniform, plain):
        planner = FusedMigrationPlanner()
        res = planner.plan(PlacementPlan(cluster, prev.copy()),
                           PlacementPlan(cluster, logical.copy()), gangs)
        assert planner.stats["fused_rounds"] == 1
        assert planner.stats["fused_typed_rounds"] == planner.stats["fused_node_price_top"] == 0
        out.append((res.physical_plan.slots, res.matching_cost))
    np.testing.assert_array_equal(out[0][0], out[1][0])
    assert out[0][1] == out[1][1]

    def signature(**kw):
        """The lowered program's argument types and its counters' length."""
        text = lower_fused_round(8, 4, 2, 66, **kw).as_text()
        main = next(line for line in text.splitlines() if "@main(" in line)
        counters = re.search(r'tensor<(\d+)xi32> \{jax.result_info = "result\[4\]"', main)
        return re.findall(r"%arg\d+: (tensor<[^>]*>)", main), int(counters.group(1))

    assert signature() == signature(typed=False)
    args, counters = signature()
    assert len(args) == 11 and counters == 4
    args, counters = signature(typed=True)
    assert args[11:] == ["tensor<8xi32>"] and counters == 5


@pytest.mark.parametrize("typed", [False, True], ids=["untyped", "typed"])
def test_a_fallback_round_keeps_the_programs_counters(typed):
    """A round cut off at ``max_iters`` falls back to the host planner, and
    the program's bid, node-match and pair-trip counters (and, typed, the
    top node price) are still counted; only a converged typed round counts
    in ``fused_typed_rounds``."""
    types = ["a100"] * 8 + (["v100"] * 8 if typed else ["a100"] * 8)
    cluster = _typed_cluster(types, 4 if typed else 0)
    rng = np.random.default_rng(11)
    gangs = {}
    prev, logical = _typed_round_plans(rng, 16, 4, gangs)
    a, b = PlacementPlan(cluster, prev), PlacementPlan(cluster, logical)

    cut = FusedMigrationPlanner(max_iters=3)
    res = cut.plan(a, b, gangs)
    assert res.algorithm == "node-fused-fallback"
    assert cut.last_fallback_reason == "fused-nonconverged"
    s = cut.stats
    assert s["fused_host_fallbacks"] == 1 and s["fused_rounds"] == 0
    assert s["fused_node_iters"] > 0 and s["fused_pair_trips"] > 0
    assert s["fused_bid_iters"] >= s["fused_node_iters"] + s["fused_pair_trips"]
    assert s["fused_typed_rounds"] == 0
    assert (s["fused_node_price_top"] > 0) == typed

    full = FusedMigrationPlanner()
    assert full.plan(a, b, gangs).algorithm == "node-fused"
    assert full.stats["fused_typed_rounds"] == int(typed)
    assert (0 < full.stats["fused_node_price_top"] < 2**24) == typed
