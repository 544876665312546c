"""Migration algorithm tests — including the paper's worked Examples 2-5."""

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.cluster import ClusterSpec, PlacementPlan, count_migrations
from repro.core.migration import (
    node_level_matching,
    pairwise_migration_cost,
    plan_migration,
    _weight_lookup,
)


def _single_node_plan(cluster, gpu_jobs):
    """gpu_jobs: list over GPUs of tuple-of-job-ids (paper example format)."""
    plan = PlacementPlan(cluster)
    for gpu, jobs in enumerate(gpu_jobs):
        if isinstance(jobs, int):
            jobs = (jobs,)
        for j in jobs:
            plan.place_job(j, [gpu])
    return plan


class TestPaperExamples:
    """Appendix A, Examples 2-4 (single 4-GPU node) and Example 5."""

    def test_example_2(self):
        cluster = ClusterSpec(1, 4)
        p_i = _single_node_plan(cluster, [1, 2, 3, 4])
        p_j = _single_node_plan(cluster, [4, 1, 2, 3])
        num_gpus = {j: 1 for j in [1, 2, 3, 4]}
        weights = _weight_lookup(num_gpus)
        cost = pairwise_migration_cost(p_i.slots[0], p_j.slots[0], weights)
        expected = np.array(
            [[1, 0, 1, 1], [1, 1, 0, 1], [1, 1, 1, 0], [0, 1, 1, 1]], dtype=float
        )
        np.testing.assert_allclose(cost, expected)
        c, _ = node_level_matching(p_i.slots[0], p_j.slots[0], num_gpus)
        assert c == 0.0
        res = plan_migration(p_i, p_j, num_gpus)
        assert res.num_migrations == 0

    def test_example_3(self):
        cluster = ClusterSpec(1, 4)
        p_i = _single_node_plan(cluster, [(1, 5), (2,), (3,), (4,)])
        p_j = _single_node_plan(cluster, [(4, 5), (1,), (2,), (3,)])
        num_gpus = {j: 1 for j in [1, 2, 3, 4, 5]}
        weights = _weight_lookup(num_gpus)
        cost = pairwise_migration_cost(p_i.slots[0], p_j.slots[0], weights)
        expected = np.array(
            [
                [1.0, 0.5, 1.5, 1.5],
                [1.5, 1.0, 0.0, 1.0],
                [1.5, 1.0, 1.0, 0.0],
                [0.5, 1.0, 1.0, 1.0],
            ]
        )
        np.testing.assert_allclose(cost, expected)
        c, _ = node_level_matching(p_i.slots[0], p_j.slots[0], num_gpus)
        assert c == 1.0  # job 5 relocates from (co-1) to (co-4)
        res = plan_migration(p_i, p_j, num_gpus)
        assert res.num_migrations == 1

    def test_example_4(self):
        cluster = ClusterSpec(1, 4)
        p_i = _single_node_plan(cluster, [(1, 6), (2,), (3,), (4,)])
        p_j = _single_node_plan(cluster, [(4, 5), (1,), (2,), (3,)])
        num_gpus = {j: 1 for j in [1, 2, 3, 4, 5, 6]}
        res = plan_migration(p_i, p_j, num_gpus)
        # jobs 5 and 6 are not in both rounds -> removed; remaining jobs 1-4
        # permute with zero migrations.
        assert res.matching_cost == 0.0
        assert res.num_migrations == 0

    def test_example_5_consolidation(self):
        """Flat (Alg. 5) matching may scatter a packed plan; node-level
        (Alg. 2+3) must keep every job consolidated."""
        cluster = ClusterSpec(2, 4)
        p_i = PlacementPlan(cluster)
        p_i.place_job(1, [0, 1, 2, 3])       # node 0
        p_i.place_job(2, [4, 5, 6, 7])       # node 1
        p_j = PlacementPlan(cluster)
        p_j.place_job(1, [0, 1, 2, 3])       # packed on node 0
        p_j.place_job(2, [0, 1, 2, 3])
        num_gpus = {1: 4, 2: 4}
        res = plan_migration(p_i, p_j, num_gpus, algorithm="node")
        assert res.physical_plan.is_consolidated(1)
        assert res.physical_plan.is_consolidated(2)

    def test_fig1_cross_node_renaming(self):
        """Fig. 1: Gavel's policy migrates 3 jobs; GPU-ID remapping needs 0."""
        cluster = ClusterSpec(2, 2)
        p_i = _mk(cluster, {1: [0, 1], 2: [2], 3: [3]})
        # logical new plan: same jobs, nodes swapped
        p_j = _mk(cluster, {1: [2, 3], 2: [0], 3: [1]})
        num_gpus = {1: 2, 2: 1, 3: 1}
        baseline = plan_migration(p_i, p_j, num_gpus, algorithm="none")
        ours = plan_migration(p_i, p_j, num_gpus, algorithm="node")
        assert baseline.num_migrations == 3
        assert ours.num_migrations == 0


def _mk(cluster, placements):
    plan = PlacementPlan(cluster)
    for j, gpus in placements.items():
        plan.place_job(j, gpus)
    return plan


def _random_plans(rng, num_nodes=4, gpn=4, n_jobs=10):
    """Two random consolidated single-GPU-granularity plans over shared jobs."""
    cluster = ClusterSpec(num_nodes, gpn)
    num_gpus_of = {}
    plans = []
    for _ in range(2):
        plan = PlacementPlan(cluster)
        free = {n: list(range(gpn)) for n in range(num_nodes)}
        for j in range(n_jobs):
            g = int(rng.choice([1, 2, 4], p=[0.6, 0.3, 0.1]))
            num_gpus_of[j] = g
            nodes = [n for n in free if len(free[n]) >= g]
            if not nodes:
                continue
            node = nodes[int(rng.integers(len(nodes)))]
            locs = free[node][:g]
            free[node] = free[node][g:]
            plan.place_job(j, [cluster.gpu_id(node, l) for l in locs])
        plans.append(plan)
    return cluster, plans[0], plans[1], num_gpus_of


class TestMigrationProperties:
    @given(st.integers(0, 2**32 - 1))
    @settings(max_examples=40, deadline=None)
    def test_matching_cost_never_worse_than_identity(self, seed):
        """The invariant Algorithm 2 actually guarantees: its Hungarian
        COST is <= the identity (no-remap) assignment's cost.  The integer
        migration COUNT (Def. 1) can occasionally exceed no-remap's when a
        multi-GPU job moves partially (fractional cost < 1 but it counts as
        one migration) — hypothesis found such a case (seed 11240); see
        migration.py docstring."""
        rng = np.random.default_rng(seed)
        cluster, p_i, p_j, num_gpus_of = _random_plans(rng)
        node = plan_migration(p_i, p_j, num_gpus_of, algorithm="node")
        # identity assignment cost: node l stays on node l, GPU v on GPU v
        common = p_i.job_ids() & p_j.job_ids()
        pi = p_i.restricted_to(common)
        pj = p_j.restricted_to(common)
        weights = _weight_lookup(num_gpus_of)
        identity_cost = 0.0
        for n in range(cluster.num_nodes):
            c = pairwise_migration_cost(pi.slots[n], pj.slots[n], weights)
            identity_cost += float(np.trace(c))
        assert node.matching_cost <= identity_cost + 1e-9

    @given(st.integers(0, 2**32 - 1))
    @settings(max_examples=40, deadline=None)
    def test_migration_count_close_to_no_remap(self, seed):
        """Count can exceed no-remap only via partial multi-GPU moves; it
        must stay within the number of multi-GPU jobs of the optimum."""
        rng = np.random.default_rng(seed)
        cluster, p_i, p_j, num_gpus_of = _random_plans(rng)
        base = plan_migration(p_i, p_j, num_gpus_of, algorithm="none")
        node = plan_migration(p_i, p_j, num_gpus_of, algorithm="node")
        multi = sum(1 for g in num_gpus_of.values() if g > 1)
        assert node.num_migrations <= base.num_migrations + multi

    @given(st.integers(0, 2**32 - 1))
    @settings(max_examples=40, deadline=None)
    def test_physical_plan_preserves_jobs_and_consolidation(self, seed):
        rng = np.random.default_rng(seed)
        cluster, p_i, p_j, num_gpus_of = _random_plans(rng)
        res = plan_migration(p_i, p_j, num_gpus_of, algorithm="node")
        # same jobs with same GPU counts
        new_map = res.physical_plan.job_gpu_map()
        old_map = p_j.job_gpu_map()
        assert set(new_map) == set(old_map)
        for j, gpus in new_map.items():
            assert len(gpus) == len(old_map[j])
            assert res.physical_plan.is_consolidated(j)

    @given(st.integers(0, 2**32 - 1))
    @settings(max_examples=15, deadline=None)
    def test_flat_not_better_than_node_for_these(self, seed):
        """Alg 5 optimises the same objective without node structure, so its
        matching cost is <= node-level; but it may break consolidation."""
        rng = np.random.default_rng(seed)
        cluster, p_i, p_j, num_gpus_of = _random_plans(rng)
        node = plan_migration(p_i, p_j, num_gpus_of, algorithm="node")
        flat = plan_migration(p_i, p_j, num_gpus_of, algorithm="flat")
        assert flat.matching_cost <= node.matching_cost + 1e-9

    def test_identical_plans_zero(self):
        rng = np.random.default_rng(7)
        cluster, p_i, _, num_gpus_of = _random_plans(rng)
        res = plan_migration(p_i, p_i.copy(), num_gpus_of)
        assert res.num_migrations == 0
        assert res.matching_cost == 0.0


class TestBatchedAuctionMigration:
    @given(st.integers(0, 2**32 - 1))
    @settings(max_examples=10, deadline=None)
    def test_matches_hungarian_cost(self, seed):
        rng = np.random.default_rng(seed)
        cluster, p_i, p_j, num_gpus_of = _random_plans(rng, num_nodes=3, gpn=2, n_jobs=6)
        hung = plan_migration(p_i, p_j, num_gpus_of, algorithm="node")
        auct = plan_migration(p_i, p_j, num_gpus_of, algorithm="node", backend="auction")
        # optimality of the batched auction == Hungarian on the SAME cost
        assert np.isclose(auct.matching_cost, hung.matching_cost)
        # count may differ from no-remap by partial multi-GPU moves (see
        # migration.py semantic note); bound it like the Hungarian test
        base = plan_migration(p_i, p_j, num_gpus_of, algorithm="none")
        multi = sum(1 for g in num_gpus_of.values() if g > 1)
        assert auct.num_migrations <= base.num_migrations + multi


class TestStragglerDrainPenalties:
    """Health terms in the relabelling benefit: the straggler-drain
    penalty drains degraded nodes through the SAME matching layer the
    rack/type terms use — half-unit quantised, occupied-rows only, and
    a no-op (None) on healthy clusters (the seed bit-identity)."""

    def test_healthy_speeds_add_no_term(self):
        from repro.core.migration import _relabel_penalties

        cluster = ClusterSpec(4, 4)
        assert _relabel_penalties(cluster) is None
        assert _relabel_penalties(cluster, speed_factor=np.ones(4)) is None

    def test_penalties_are_half_unit_quantised_and_targeted(self):
        from repro.core.migration import _relabel_penalties

        cluster = ClusterSpec(4, 4)
        speed = np.array([1.0, 0.37, 0.9, 1.0])
        occ = np.array([True, True, False, False])
        pen = _relabel_penalties(cluster, occupied_logical=occ,
                                 speed_factor=speed)
        assert pen is not None
        # exactness contract of the auction backends: multiples of 0.5
        np.testing.assert_array_equal(pen * 2.0, np.round(pen * 2.0))
        # only occupied logical columns are penalised, only slow rows pay
        assert np.all(pen[:, ~occ] == 0.0)
        assert np.all(pen[[0, 3], :] == 0.0)
        assert np.all(pen[1, occ] > 0.0)
        # deeper degradation, steeper penalty
        assert pen[1, 0] > pen[2, 0] > 0.0

    def test_drain_relabels_onto_spare_healthy_node(self):
        cluster = ClusterSpec(2, 4)
        prev = _mk(cluster, {0: [0, 1, 2, 3]})
        new = _mk(cluster, {0: [0, 1, 2, 3]})
        res = plan_migration(prev, new, {0: 4}, algorithm="node",
                             speed_factor=np.array([0.4, 1.0]))
        assert set(res.physical_plan.job_gpu_map()[0]) == {4, 5, 6, 7}
        assert res.num_migrations == 1
        # full-speed cluster: untouched (bit-identical seed path)
        res2 = plan_migration(prev, new, {0: 4}, algorithm="node",
                              speed_factor=np.ones(2))
        assert set(res2.physical_plan.job_gpu_map()[0]) == {0, 1, 2, 3}
        assert res2.num_migrations == 0
