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

import re

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

import jax

from repro.core.cluster import ClusterSpec
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
