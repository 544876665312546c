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
