"""Packing (Algorithm 4) tests: constraints, optimality, strategy lift."""

import itertools

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.jobs import JobSpec, JobState
from repro.core.matching import MatchContext
from repro.core.packing import build_packing_graph, max_weight_transport, pack_jobs
from repro.core.profiler import ThroughputProfile
from repro.core.scheduler import tiresias_single_packed_ok

MODELS = ["resnet50", "vgg19", "dcgan", "pointnet", "gpt3-medium", "gpt3-xl"]
#: the benchmark cells' catalog: the six above and gpt3-3b
ALL_MODELS = MODELS + ["gpt3-3b"]
#: the cells' gang sizes and how often each is drawn
GANGS, GANG_PROBS = [1, 2, 4, 8], [0.60, 0.30, 0.09, 0.01]


def _job(jid, model="resnet50", gpus=1, packable=True):
    spec = JobSpec(
        job_id=jid,
        model=model,
        num_gpus=gpus,
        total_iters=1000,
        arrival_time=0.0,
        packable=packable,
        is_llm=model.startswith("gpt3"),
    )
    return JobState(spec=spec)


@pytest.fixture
def profile():
    return ThroughputProfile()


class TestPackingConstraints:
    def test_gpu_count_must_match(self, profile):
        placed = [_job(0, gpus=2)]
        pending = [_job(1, gpus=1)]
        res = pack_jobs(placed, pending, profile)
        assert res.matches == {}

    def test_non_packable_bypassed(self, profile):
        placed = [_job(0, packable=False)]
        pending = [_job(1)]
        res = pack_jobs(placed, pending, profile)
        assert res.matches == {}

    def test_simple_match(self, profile):
        placed = [_job(0, "resnet50")]
        pending = [_job(1, "pointnet")]
        res = pack_jobs(placed, pending, profile)
        assert res.matches == {1: 0}
        assert res.total_weight > 1.0  # compute+memory-bound pair packs well

    def test_oom_pair_gets_no_edge(self):
        # v100 (16 GB): two 15 GB vgg19 cannot pack
        profile = ThroughputProfile(gpu_type="v100")
        placed = [_job(0, "vgg19")]
        pending = [_job(1, "vgg19")]
        res = pack_jobs(placed, pending, profile)
        assert res.matches == {}

    def test_at_most_one_partner(self, profile):
        placed = [_job(0, "resnet50")]
        pending = [_job(1, "pointnet"), _job(2, "dcgan")]
        res = pack_jobs(placed, pending, profile)
        assert len(res.matches) == 1


class TestPackingOptimality:
    @given(st.integers(0, 2**32 - 1), st.integers(1, 4), st.integers(1, 4))
    @settings(max_examples=40, deadline=None)
    def test_matches_bruteforce(self, seed, n_placed, n_pending):
        rng = np.random.default_rng(seed)
        profile = ThroughputProfile()
        placed = [
            _job(i, MODELS[rng.integers(len(MODELS))], gpus=int(rng.choice([1, 2])))
            for i in range(n_placed)
        ]
        pending = [
            _job(
                100 + i,
                MODELS[rng.integers(len(MODELS))],
                gpus=int(rng.choice([1, 2])),
            )
            for i in range(n_pending)
        ]
        w = build_packing_graph(placed, pending, profile)
        res = pack_jobs(placed, pending, profile)
        # brute force maximum-weight matching
        best = 0.0
        cols = list(range(n_pending))
        for k in range(min(n_placed, n_pending) + 1):
            for rows in itertools.permutations(range(n_placed), k):
                for cc in itertools.permutations(cols, k):
                    tot = sum(w[r, c] for r, c in zip(rows, cc))
                    best = max(best, tot)
        assert res.total_weight == pytest.approx(best, abs=1e-9)

    def test_strategy_optimisation_lifts_weight(self, profile):
        placed = [_job(0, "gpt3-3b", gpus=2)]
        pending = [_job(1, "resnet50", gpus=2)]
        res_plain = pack_jobs(placed, pending, profile, optimize_strategy=False)
        res_opt = pack_jobs(placed, pending, profile, optimize_strategy=True)
        assert res_opt.total_weight >= res_plain.total_weight


class TestPackingIdentityWarmStarts:
    """pack_jobs threads JOB identities into the matching context: a
    pending job arriving (the dominant churn event) must keep the
    surviving jobs' state warm instead of cold-starting the graph."""

    def test_unchanged_graph_memo_hits(self, profile):
        from repro.core.matching import MatchContext

        placed = [_job(i, MODELS[i % 3]) for i in range(6)]
        pending = [_job(10 + i, MODELS[i % 2]) for i in range(3)]
        ctx = MatchContext()
        r1 = pack_jobs(placed, pending, profile, backend="auction", context=ctx)
        r2 = pack_jobs(placed, pending, profile, backend="auction", context=ctx)
        assert ctx.stats["memo_hits"] == 1
        assert r1.matches == r2.matches

    def test_pending_arrival_stays_warm_and_matches_cold(self, profile):
        from repro.core.matching import MatchContext

        placed = [_job(i, MODELS[i % 4]) for i in range(8)]
        pending = [_job(20 + i, MODELS[i % 3]) for i in range(3)]
        ctx = MatchContext()
        pack_jobs(placed, pending, profile, backend="auction", context=ctx)
        pending2 = pending + [_job(30, MODELS[1])]
        warm = pack_jobs(placed, pending2, profile, backend="auction", context=ctx)
        cold = pack_jobs(placed, pending2, profile, backend="auction")
        # identity keying: the grown graph is not a cold start ...
        assert ctx.stats["warm_instances"] >= 1
        # ... and the warm result stays a valid Algorithm-4 matching with
        # the same total weight as a cold solve (assignment ids may differ
        # on equal-weight ties)
        assert warm.total_weight == pytest.approx(cold.total_weight, abs=1.0 + 1e-6)

    def test_job_departure_preserves_scipy_exactness(self, profile):
        from repro.core.matching import MatchContext

        placed = [_job(i, MODELS[i % 4]) for i in range(8)]
        pending = [_job(20 + i, MODELS[i % 3]) for i in range(4)]
        ctx = MatchContext()
        pack_jobs(placed, pending, profile, backend="scipy", context=ctx)
        # a placed job finishes, a pending job gets placed elsewhere
        placed2, pending2 = placed[1:], pending[:-1]
        warm = pack_jobs(placed2, pending2, profile, backend="scipy", context=ctx)
        cold = pack_jobs(placed2, pending2, profile, backend="scipy")
        assert warm.total_weight == pytest.approx(cold.total_weight, abs=1e-9)


def _scipy_optimum(w):
    from scipy.optimize import linear_sum_assignment

    r, c = linear_sum_assignment(w, maximize=True)
    return float(w[r, c].sum())


def _random_instance(seed, n_placed, n_pending, typed, unpackable, prepacked):
    rng = np.random.default_rng(seed)

    def draw(jid, packable=True):
        m = ALL_MODELS[rng.integers(len(ALL_MODELS))]
        return _job(jid, m, gpus=int(rng.choice(GANGS)), packable=packable)

    placed = [draw(i) for i in range(n_placed)]
    pending = [
        draw(1000 + i, packable=not (unpackable and rng.random() < 0.3))
        for i in range(n_pending)
    ]
    if prepacked:
        for u in placed:
            if rng.random() < 0.3:
                u.packed_with = 999_999
    types = [("a100", "v100")[rng.integers(2)] for _ in placed] if typed else None
    return placed, pending, types


def _check_valid(res, placed, pending, w):
    """Every match weighs > 0 and pairs equal gangs; no job twice."""
    pos_u = {u.job_id: i for i, u in enumerate(placed)}
    pos_v = {v.job_id: j for j, v in enumerate(pending)}
    assert len(set(res.matches.values())) == len(res.matches)
    for vid, uid in res.matches.items():
        i, j = pos_u[uid], pos_v[vid]
        assert w[i, j] > 0
        assert placed[i].num_gpus == pending[j].num_gpus


class TestPackingOnClasses:
    """The default packing (``backend="auto"``) solves a transportation
    problem on job classes: the optimum of the dense job-level LAP, from no
    |placed| x |pending| matrix."""

    @given(
        st.integers(0, 2**32 - 1),
        st.integers(1, 40),
        st.integers(1, 60),
        st.booleans(),
        st.booleans(),
        st.booleans(),
        st.booleans(),
    )
    @settings(max_examples=60, deadline=None)
    def test_class_path_is_the_lap_optimum_and_valid(
        self, seed, n_placed, n_pending, typed, unpackable, prepacked, single
    ):
        placed, pending, types = _random_instance(
            seed, n_placed, n_pending, typed, unpackable, prepacked
        )
        profile = ThroughputProfile()
        ok = tiresias_single_packed_ok if single else None
        w = build_packing_graph(
            placed, pending, profile, packed_ok=ok, placed_gpu_types=types
        )
        res = pack_jobs(placed, pending, profile, packed_ok=ok, placed_gpu_types=types)
        assert res.total_weight == pytest.approx(
            _scipy_optimum(w), abs=1e-9 * (1 + len(res.matches))
        )
        _check_valid(res, placed, pending, w)
        assert res.num_edges == int((w > 0).sum())
        again = pack_jobs(
            placed, pending, profile, packed_ok=ok, placed_gpu_types=types
        )
        assert again.matches == res.matches and again.strategies == res.strategies

    def test_v100_out_of_memory_pairs_stay_unpacked(self):
        # two 15 GB vgg19 fit an A100's HBM, not a V100's
        placed = [_job(0, "vgg19"), _job(1, "vgg19")]
        pending = [_job(10, "vgg19")]
        profile = ThroughputProfile()
        on_v100 = pack_jobs(placed[:1], pending, profile, placed_gpu_types=["v100"])
        assert on_v100.matches == {} and on_v100.num_edges == 0
        mixed = pack_jobs(placed, pending, profile, placed_gpu_types=["v100", "a100"])
        assert mixed.matches == {10: 1}

    def test_equal_weights_pack_the_earliest_jobs(self, profile):
        placed = [_job(i, "resnet50") for i in range(3)]
        pending = [_job(10 + i, "pointnet") for i in range(5)]
        res = pack_jobs(placed, pending, profile)
        assert res.matches == {10: 0, 11: 1, 12: 2}
        # in the order given, not by id: the earlier placed jobs host
        placed = [_job(i, "resnet50") for i in (7, 3, 5, 1)]
        pending = [_job(20, "pointnet"), _job(12, "pointnet")]
        res = pack_jobs(placed, pending, profile)
        assert res.matches == {20: 7, 12: 3}

    @pytest.mark.parametrize("typed", [False, True])
    def test_cell_sized_instance_matches_scipy(self, profile, typed):
        """~1,450 placed x 3,200 pending jobs with the cells' gang mix and
        models, as at ~4,600 active jobs on 2,048 GPUs."""
        rng = np.random.default_rng(2**31 + 18 + typed)

        def draw(jid):
            m = ALL_MODELS[rng.integers(len(ALL_MODELS))]
            return _job(jid, m, gpus=int(rng.choice(GANGS, p=GANG_PROBS)))

        placed = [draw(i) for i in range(1450)]
        pending = [draw(10_000 + i) for i in range(3200)]
        types = [("a100", "v100")[rng.integers(2)] for _ in placed] if typed else None
        w = build_packing_graph(placed, pending, profile, placed_gpu_types=types)
        res = pack_jobs(placed, pending, profile, placed_gpu_types=types)
        assert res.total_weight == pytest.approx(
            _scipy_optimum(w), abs=1e-9 * (1 + len(res.matches))
        )
        assert res.num_edges == int((w > 0).sum())
        _check_valid(res, placed, pending, w)

    @given(st.integers(0, 2**32 - 1), st.integers(1, 6), st.integers(1, 5))
    @settings(max_examples=100, deadline=None)
    def test_transport_is_the_expanded_assignment_optimum(self, seed, n_r, n_c):
        """Class counts expanded to one row or column per unit: the flows'
        weight is the assignment optimum, within supply and demand."""
        rng = np.random.default_rng(seed)
        # weights skewed toward 0, so the last augmentations gain little
        w = np.where(rng.random((n_r, n_c)) < 0.7, rng.random((n_r, n_c)) ** 3, 0.0)
        w[rng.random((n_r, n_c)) < 0.2] = 0.5  # ties
        supply = rng.integers(0, 6, n_r)
        demand = rng.integers(0, 6, n_c)
        x = max_weight_transport(w, supply, demand)
        assert (x >= 0).all() and (x[w <= 0] == 0).all()
        assert (x.sum(1) <= supply).all() and (x.sum(0) <= demand).all()
        dense = w[np.repeat(np.arange(n_r), supply)][:, np.repeat(np.arange(n_c), demand)]
        best = _scipy_optimum(dense) if dense.size else 0.0
        assert float((w * x).sum()) == pytest.approx(best, abs=1e-9)


def _ok_not_vectorised(u, v):
    return u.num_gpus == v.num_gpus


class TestPackingDispatch:
    """Which calls the class path serves: ``backend="auto"``, no
    ``tie_break``, a ``packed_ok`` that is None or vectorised.  Every other
    call keeps the engine and its context entry."""

    @staticmethod
    def _solve(profile, **kw):
        placed = [_job(i, MODELS[i % 3]) for i in range(6)]
        pending = [_job(10 + i, MODELS[i % 2]) for i in range(3)]
        ctx = MatchContext()
        res = pack_jobs(placed, pending, profile, context=ctx, **kw)
        stored = any(k[0] == "packing" for k in ctx._entries)
        return res, ctx.stats, stored

    @pytest.mark.parametrize("packed_ok", [None, tiresias_single_packed_ok])
    def test_class_path_counts_and_keeps_no_entry(self, profile, packed_ok):
        res, stats, stored = self._solve(profile, packed_ok=packed_ok)
        assert stats["pack_class_solves"] == 1
        assert stats["solves"] == 0 and not stored
        assert res.matches

    @pytest.mark.parametrize(
        "kw",
        [
            {"backend": "scipy"},
            {"backend": "auction"},
            {"tie_break": True},
            {"packed_ok": _ok_not_vectorised},
        ],
        ids=["scipy", "auction", "tie_break", "packed_ok-not-vectorised"],
    )
    def test_engine_path_keeps_its_entry(self, profile, kw):
        res, stats, stored = self._solve(profile, **kw)
        assert stats["pack_class_solves"] == 0
        assert stats["solves"] == 1 and stored
        cls, _, _ = self._solve(profile)
        assert res.total_weight == pytest.approx(cls.total_weight, abs=1.0 + 1e-6)
