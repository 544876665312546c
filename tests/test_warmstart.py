"""Warm-start + rectangular-path properties of the matching engine.

Pins the identity-keyed MatchContext contract: scipy parity of assignments
(totals within the documented eps bound) when prices are carried across
mutated cost batches — including the row-invalidation path — plus
per-instance memoisation with identity remapping (grow / shrink / permute
of instances, rows and columns), partial-batch compaction edge cases, the
padding-free rectangular dispatch, the a-posteriori price certificate, and
the strictly-fewer-bid-iterations acceptance criterion on a replayed
multi-round trace.
"""

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.matching import MatchContext, solve_lap_batched
from repro.core.matching.engine import (
    _f64_bits,
    _rect_bound_violation,
    _rows_unchanged,
)

scipy_lsa = pytest.importorskip("scipy.optimize").linear_sum_assignment


def _scipy_totals(costs, maximize=False):
    out = []
    for c in costs:
        r, col = scipy_lsa(c, maximize=maximize)
        out.append(c[r, col].sum())
    return np.array(out)


def _mutate(rng, costs, n_instances, integer=True):
    """Re-randomise one row in each of ``n_instances`` random instances."""
    costs = costs.copy()
    idx = rng.choice(costs.shape[0], n_instances, replace=False)
    for i in idx:
        row = rng.integers(costs.shape[1])
        if integer:
            costs[i, row] = rng.integers(0, 16, costs.shape[2])
        else:
            costs[i, row] = rng.uniform(0, 10, costs.shape[2])
    return costs, idx


class TestWarmStartCorrectness:
    @given(
        st.integers(2, 12),  # batch
        st.integers(2, 7),   # n
        st.integers(1, 4),   # mutation rounds
        st.integers(0, 2**32 - 1),
    )
    @settings(max_examples=15, deadline=None)
    def test_square_parity_across_mutations(self, b, n, rounds, seed):
        """Warm-started auction == scipy on every round of a mutating
        replay (integer costs -> the n*eps bound is exactness)."""
        rng = np.random.default_rng(seed)
        ctx = MatchContext()
        costs = rng.integers(0, 16, (b, n, n)).astype(float)
        for _ in range(rounds):
            res = solve_lap_batched(
                costs, backend="auction", context=ctx, context_key="prop"
            )
            want = _scipy_totals(costs)
            np.testing.assert_allclose(res.total_cost, want, atol=1e-9)
            costs, _ = _mutate(rng, costs, max(1, b // 3))

    @given(
        st.integers(2, 8),    # batch
        st.integers(2, 6),    # short side
        st.integers(7, 24),   # long side
        st.booleans(),        # transpose (rows > cols)
        st.integers(0, 2**32 - 1),
    )
    @settings(max_examples=15, deadline=None)
    def test_rect_parity_across_mutations(self, b, n, m, transpose, seed):
        """Rectangular warm starts stay within the documented bound, in
        both orientations (bidders are always the short side)."""
        rng = np.random.default_rng(seed)
        shape = (b, m, n) if transpose else (b, n, m)
        costs = rng.uniform(0, 10, shape)
        ctx = MatchContext()
        bound = n / (n + 1) + 1e-6
        for _ in range(3):
            res = solve_lap_batched(
                costs, backend="auction", context=ctx, context_key="rect"
            )
            assert res.embedding == "rect"
            want = _scipy_totals(costs)
            assert np.all(np.abs(res.total_cost - want) <= bound), (
                res.total_cost - want
            )
            costs, _ = _mutate(rng, costs, 1, integer=False)

    def test_row_invalidation_resets_only_changed_instances(self):
        rng = np.random.default_rng(0)
        b, n = 16, 5
        costs = rng.integers(0, 16, (b, n, n)).astype(float)
        ctx = MatchContext()
        solve_lap_batched(costs, backend="auction", context=ctx, context_key="inv")
        mutated, idx = _mutate(rng, costs, 4)
        res = solve_lap_batched(
            mutated, backend="auction", context=ctx, context_key="inv"
        )
        assert res.warm.sum() == b - 4
        assert not res.warm[idx].any()
        assert ctx.stats["rows_invalidated"] == 4
        np.testing.assert_allclose(res.total_cost, _scipy_totals(mutated))

    def test_transposed_invalidation_is_per_row(self):
        """n > m (skew packing shape): one changed original row is ONE
        oriented column, so it invalidates exactly one price — not every
        bidder fingerprint of the instance."""
        rng = np.random.default_rng(12)
        costs = rng.uniform(0, 10, (4, 30, 5))  # transposed rect path
        ctx = MatchContext()
        solve_lap_batched(costs, backend="auction", context=ctx, context_key="tr")
        mutated = costs.copy()
        mutated[2, 17] = rng.uniform(0, 10, 5)
        res = solve_lap_batched(
            mutated, backend="auction", context=ctx, context_key="tr"
        )
        assert res.embedding == "rect"
        assert ctx.stats["rows_invalidated"] == 1
        assert res.warm.sum() == 3 and not res.warm[2]
        bound = 5 / 6 + 1e-6
        assert np.all(np.abs(res.total_cost - _scipy_totals(mutated)) <= bound)

    def test_masked_and_forbidden_warm(self):
        """Masks and forbidden edges participate in the fingerprint, so a
        mask flip is a cost change and invalidates cleanly."""
        rng = np.random.default_rng(1)
        b, n, m = 6, 5, 9
        costs = rng.integers(0, 20, (b, n, m)).astype(float)
        costs[:, 0, 0] = np.inf
        rm = np.ones((b, n), bool)
        ctx = MatchContext()
        r1 = solve_lap_batched(
            costs, row_mask=rm, backend="auction", context=ctx, context_key="mf"
        )
        rm2 = rm.copy()
        rm2[2, 3] = False  # instance 2 loses a row
        r2 = solve_lap_batched(
            costs, row_mask=rm2, backend="auction", context=ctx, context_key="mf"
        )
        assert r2.warm.sum() == b - 1 and not r2.warm[2]
        assert (r2.col_of[2, 3] == -1) and (r2.col_of[~rm2] == -1).all()
        for i in range(b):
            want = _scipy_totals(costs[i][rm2[i]][None])
            assert abs(r2.total_cost[i] - want[0]) <= n / (n + 1) + 1e-6


class TestMemoisation:
    @pytest.mark.parametrize("backend", ["auction", "scipy", "numpy", "smallperm"])
    def test_identical_resolve_memo_hits(self, backend):
        rng = np.random.default_rng(2)
        k = 4 if backend == "smallperm" else 7
        costs = rng.integers(0, 25, (8, k, k)).astype(float)
        ctx = MatchContext()
        r1 = solve_lap_batched(costs, backend=backend, context=ctx, context_key="m")
        r2 = solve_lap_batched(costs, backend=backend, context=ctx, context_key="m")
        assert ctx.stats["memo_hits"] == 1
        assert r2.warm.all() and r2.bid_iters.sum() == 0
        assert (r1.col_of == r2.col_of).all()
        np.testing.assert_allclose(r1.total_cost, r2.total_cost)

    def test_context_keys_do_not_collide(self):
        rng = np.random.default_rng(3)
        costs = rng.integers(0, 10, (4, 5, 5)).astype(float)
        ctx = MatchContext()
        solve_lap_batched(costs, backend="auction", context=ctx, context_key="a")
        r = solve_lap_batched(costs, backend="auction", context=ctx, context_key="b")
        assert ctx.stats["memo_hits"] == 0 and not r.warm.any()
        assert len(ctx) == 2

    def test_shape_change_without_ids_is_not_warm(self):
        """With DEFAULT (positional) identities, a grown batch of fresh
        random contents matches positions 0-3 but every row's content
        changed — no instance is memoised or schedule-warm.  (Callers who
        want shape changes to stay warm pass stable instance_ids.)"""
        rng = np.random.default_rng(4)
        ctx = MatchContext()
        solve_lap_batched(
            rng.integers(0, 10, (4, 5, 5)).astype(float),
            backend="auction", context=ctx, context_key="s",
        )
        r = solve_lap_batched(
            rng.integers(0, 10, (5, 5, 5)).astype(float),
            backend="auction", context=ctx, context_key="s",
        )
        assert not r.warm.any()

    def test_reset_drops_state(self):
        rng = np.random.default_rng(5)
        costs = rng.integers(0, 10, (4, 5, 5)).astype(float)
        ctx = MatchContext()
        solve_lap_batched(costs, backend="auction", context=ctx, context_key="r")
        ctx.reset()
        assert len(ctx) == 0
        r = solve_lap_batched(costs, backend="auction", context=ctx, context_key="r")
        assert not r.warm.any()


class TestRectangularPath:
    @pytest.mark.parametrize("backend", ["auction", "scipy", "numpy"])
    def test_no_square_embedding_for_rect(self, backend, monkeypatch):
        """Acceptance: n != m instances never allocate the max(n, m)^2
        square embedding on rect-capable backends."""
        from repro.core.matching import engine as eng

        def _boom(*a, **k):  # pragma: no cover - failure path
            raise AssertionError("square embedding allocated for rect instance")

        monkeypatch.setattr(eng, "masked_square_benefit", _boom)
        rng = np.random.default_rng(6)
        costs = rng.uniform(0, 10, (3, 40, 6))
        res = solve_lap_batched(costs, backend=backend)
        assert res.embedding == "rect"
        bound = 6 / 7 + 1e-6 if backend == "auction" else 1e-9
        assert np.all(np.abs(res.total_cost - _scipy_totals(costs)) <= bound)

    def test_smallperm_still_square_embeds(self):
        rng = np.random.default_rng(7)
        costs = rng.integers(0, 10, (2, 3, 5)).astype(float)
        res = solve_lap_batched(costs, backend="smallperm")
        assert res.embedding == "square"
        np.testing.assert_allclose(res.total_cost, _scipy_totals(costs))

    def test_transposed_orientation_maps_back(self):
        """n > m: bidders are the columns; assignments invert correctly."""
        rng = np.random.default_rng(8)
        costs = rng.integers(0, 30, (4, 50, 7)).astype(float)
        res = solve_lap_batched(costs, backend="auction")
        assert res.embedding == "rect"
        for i in range(4):
            rows, cols = res.pairs(i)
            assert len(rows) == 7 and len(set(cols.tolist())) == 7
        np.testing.assert_allclose(res.total_cost, _scipy_totals(costs))


class TestCertificate:
    def test_poisoned_prices_are_caught(self):
        """Stale high prices on unassigned columns could break the rect
        bound; the certificate must flag them and the engine must re-solve
        to parity (counted as fallback)."""
        rng = np.random.default_rng(9)
        costs = rng.uniform(0, 10, (4, 4, 16))
        ctx = MatchContext()
        solve_lap_batched(costs, backend="auction", context=ctx, context_key="c")
        entry = next(iter(ctx._entries.values()))
        assigned = np.zeros((4, 16), bool)
        np.put_along_axis(assigned, entry.col_solve, entry.col_solve >= 0, axis=1)
        entry.prices = np.where(
            assigned, np.asarray(entry.prices), 1e6
        ).astype(np.float32)
        # mutate the poisoned instances so they actually RE-SOLVE with the
        # poisoned warm prices: an unchanged instance memo-hits (partial-
        # batch compaction) and never consults its prices at all.  The
        # mutation re-randomises a row, so the stale 1e6 prices on the
        # unassigned columns survive into the warm solve.
        costs2 = costs.copy()
        for i in range(4):
            costs2[i, i % 4] = rng.uniform(0, 10, 16)
        res = solve_lap_batched(costs2, backend="auction", context=ctx, context_key="c")
        assert ctx.stats["memo_hits"] == 0
        # the certificate must flag the poisoned warm instances and force
        # the exact re-solve (which is only COUNTED as a fallback when it
        # improves the result — parity is the contract either way)
        assert ctx.stats["cert_violations"] >= 1, "certificate never fired"
        np.testing.assert_allclose(
            res.total_cost, _scipy_totals(costs2), atol=4 / 5 + 1e-6
        )

    def test_violation_predicate(self):
        # 2 bidders over 4 columns; cols 0,1 assigned at low prices while
        # unassigned col 3 holds a stale high price -> violation.
        prices = np.array([[1.0, 2.0, 0.0, 50.0]], np.float32)
        col_solve = np.array([[0, 1]])
        assert _rect_bound_violation(prices, col_solve).all()
        # all-equal unassigned prices below assigned -> certified.
        prices = np.array([[5.0, 2.0, 0.0, 0.0]], np.float32)
        assert not _rect_bound_violation(prices, col_solve).any()
        # square instances never flag.
        assert not _rect_bound_violation(
            np.array([[3.0, 1.0]], np.float32), np.array([[1, 0]])
        ).any()
        # incomplete assignments are someone else's problem (convergence).
        assert not _rect_bound_violation(
            np.array([[1.0, 2.0, 9.0, 9.0]], np.float32), np.array([[0, -1]])
        ).any()


class TestReplayedTrace:
    def test_20_round_trace_strictly_fewer_bid_iters(self):
        """Acceptance: same assignments as cold start with strictly fewer
        total bid iterations on a replayed >= 20-round trace."""
        rng = np.random.default_rng(10)
        b, k, rounds = 48, 4, 22
        costs = rng.integers(0, 16, (b, k, k)).astype(float)
        trace = [costs]
        for _ in range(rounds - 1):
            costs, _ = _mutate(rng, costs, 2)
            trace.append(costs)

        totals = {}
        for arm in ("cold", "warm"):
            ctx = MatchContext()
            iters = 0
            for c in trace:
                if arm == "cold":
                    ctx = MatchContext()
                res = solve_lap_batched(
                    c, backend="auction", context=ctx, context_key="trace"
                )
                iters += int(res.bid_iters.sum())
                np.testing.assert_allclose(res.total_cost, _scipy_totals(c))
            totals[arm] = iters
        assert totals["warm"] < totals["cold"], totals


class TestFingerprints:
    """The context's fingerprints are the exact f64 bit patterns of the
    benefit cells (host uint32 lanes) — comparison is collision-free, so
    a memo hit can never return a stale result."""

    def _unchanged(self, new, old, old_idx, row_pos, col_pos):
        return _rows_unchanged(
            _f64_bits(new),
            _f64_bits(old),
            np.asarray(old_idx),
            np.asarray(row_pos),
            np.asarray(col_pos),
        )

    def test_bits_roundtrip_exact(self):
        rng = np.random.default_rng(11)
        a = rng.uniform(-5, 5, (3, 4, 6))
        bits = _f64_bits(a)
        assert bits.shape == (3, 4, 6, 2) and bits.dtype == np.uint32
        assert (bits.reshape(3, 4, 6 * 2).view(np.float64) == a).all()

    def test_single_cell_sensitivity(self):
        rng = np.random.default_rng(12)
        ben = rng.uniform(-5, 5, (3, 6, 9))
        ben2 = ben.copy()
        ben2[1, 4, 8] += 1e-12  # far below any float32 resolution
        b, n, m = ben.shape
        idx = np.arange(b)
        rp = np.broadcast_to(np.arange(n), (b, n))
        cp = np.broadcast_to(np.arange(m), (b, m))
        changed = ~self._unchanged(ben2, ben, idx, rp, cp)
        assert changed[1, 4] and changed.sum() == 1

    def test_new_columns_do_not_count_against_a_row(self):
        """A row that only GAINED a column is unchanged on survivors: the
        comparison is restricted to surviving column identities."""
        rng = np.random.default_rng(13)
        old = rng.uniform(0, 1, (1, 3, 4))
        new = np.concatenate([old, rng.uniform(0, 1, (1, 3, 1))], axis=2)
        rp = np.broadcast_to(np.arange(3), (1, 3))
        cp = np.array([[0, 1, 2, 3, -1]])  # last col is new
        assert self._unchanged(new, old, np.zeros(1, np.int64), rp, cp).all()

    def test_negative_zero_is_a_change(self):
        """-0.0 == 0.0 numerically but differs at the bit level; treating
        it as changed only costs a spurious (still valid) re-solve."""
        old = np.zeros((1, 2, 2))
        new = old.copy()
        new[0, 0, 0] = -0.0
        rp = np.broadcast_to(np.arange(2), (1, 2))
        cp = np.broadcast_to(np.arange(2), (1, 2))
        un = self._unchanged(new, old, np.zeros(1, np.int64), rp, cp)
        assert not un[0, 0] and un[0, 1]


class TestIdentityKeying:
    """Tentpole satellite: grow/shrink/permute instances, rows and columns
    between rounds — surviving identities reuse state, parity always
    holds, and unchanged-identity subsets pay zero bid iterations."""

    def test_instance_permutation_is_pure_memo(self):
        rng = np.random.default_rng(20)
        costs = rng.integers(0, 16, (6, 5, 5)).astype(float)
        ids = np.arange(6) * 7 + 3
        ctx = MatchContext()
        r1 = solve_lap_batched(
            costs, backend="auction", context=ctx, context_key="i",
            instance_ids=ids,
        )
        perm = rng.permutation(6)
        r2 = solve_lap_batched(
            costs[perm], backend="auction", context=ctx, context_key="i",
            instance_ids=ids[perm],
        )
        assert r2.warm.all() and r2.bid_iters.sum() == 0
        assert ctx.stats["memo_hits"] == 1
        assert (r2.col_of == r1.col_of[perm]).all()

    def test_instance_arrival_departure(self):
        """Survivors memo-hit with remapped assignments; only arrivals
        solve (the compaction path) — and parity holds for everyone."""
        rng = np.random.default_rng(21)
        costs = rng.integers(0, 16, (8, 4, 4)).astype(float)
        ids = np.arange(8)
        ctx = MatchContext()
        r1 = solve_lap_batched(
            costs, backend="auction", context=ctx, context_key="a",
            instance_ids=ids,
        )
        keep = np.array([0, 2, 3, 6, 7])
        fresh = rng.integers(0, 16, (2, 4, 4)).astype(float)
        costs2 = np.concatenate([costs[keep], fresh])
        ids2 = np.concatenate([ids[keep], [100, 101]])
        r2 = solve_lap_batched(
            costs2, backend="auction", context=ctx, context_key="a",
            instance_ids=ids2,
        )
        assert r2.warm[:5].all() and not r2.warm[5:].any()
        assert r2.bid_iters[:5].sum() == 0 and (r2.bid_iters[5:] > 0).all()
        assert (r2.col_of[:5] == r1.col_of[keep]).all()
        np.testing.assert_allclose(r2.total_cost, _scipy_totals(costs2))

    def test_row_col_permutation_within_instance(self):
        """Permuting rows AND columns of an unchanged instance memo-hits,
        with the cached assignment remapped through both identity maps."""
        rng = np.random.default_rng(22)
        cost = rng.integers(0, 30, (1, 6, 6)).astype(float)
        rid = np.arange(10, 16)
        cid = np.arange(50, 56)
        ctx = MatchContext()
        r1 = solve_lap_batched(
            cost, backend="auction", context=ctx, context_key="p",
            row_ids=rid, col_ids=cid,
        )
        rp = rng.permutation(6)
        cp = rng.permutation(6)
        cost2 = cost[:, rp][:, :, cp]
        r2 = solve_lap_batched(
            cost2, backend="auction", context=ctx, context_key="p",
            row_ids=rid[rp], col_ids=cid[cp],
        )
        assert r2.warm.all() and r2.bid_iters.sum() == 0
        np.testing.assert_allclose(r2.total_cost, r1.total_cost)
        # remapped assignment must BE the permuted original assignment
        inv_cp = np.argsort(cp)
        assert (r2.col_of[0] == inv_cp[r1.col_of[0][rp]]).all()

    def test_column_growth_keeps_surviving_prices(self):
        """Packing shape: pending set gains a job (one new column).  The
        surviving columns keep their prices (identity re-assembly), so
        the warm solve converges in fewer bid rounds than a cold solve of
        the same instance."""
        rng = np.random.default_rng(23)
        w = rng.uniform(0, 5, (1, 6, 24))
        cid = np.arange(24)
        ctx = MatchContext()
        solve_lap_batched(
            w, maximize=True, backend="auction", context=ctx,
            context_key="g", col_ids=cid,
        )
        w2 = np.concatenate([w, rng.uniform(0, 5, (1, 6, 1))], axis=2)
        warm = solve_lap_batched(
            w2, maximize=True, backend="auction", context=ctx,
            context_key="g", col_ids=np.concatenate([cid, [99]]),
        )
        cold = solve_lap_batched(w2, maximize=True, backend="auction")
        assert warm.warm[0]  # identity-only delta: schedule skipped
        assert warm.bid_iters.sum() < cold.bid_iters.sum(), (
            warm.bid_iters, cold.bid_iters
        )
        bound = 6 / 7 + 1e-6
        assert abs(warm.total_cost[0] - _scipy_totals(w2, True)[0]) <= bound

    def test_pad_cells_do_not_couple_instances(self):
        """Masked/forbidden-edge batches: the pad constant is PER
        instance, so the batch's max-|benefit| instance departing must not
        change the pad bit pattern of (and thereby un-memo) survivors."""
        rng = np.random.default_rng(25)
        b, n, m = 6, 4, 7  # rect auction path; forbidden cells take the pad
        costs = rng.uniform(0, 5, (b, n, m))
        costs[0] *= 100.0  # instance 0 holds the batch max
        costs[:, 1, 2] = np.inf  # forbidden edges -> pad cells everywhere
        ids = np.arange(b)
        ctx = MatchContext()
        solve_lap_batched(
            costs, backend="auction", context=ctx, context_key="pad",
            instance_ids=ids,
        )
        res = solve_lap_batched(
            costs[1:], backend="auction", context=ctx, context_key="pad",
            instance_ids=ids[1:],
        )
        assert res.warm.all() and res.bid_iters.sum() == 0, (
            "survivors lost memo status when the batch-max instance left"
        )
        bound = 4 / 5 + 1e-6
        assert np.all(np.abs(res.total_cost - _scipy_totals(costs[1:])) <= bound)

    def test_transposed_rect_permutation_memo(self):
        """n > m (skew packing orientation): permuting instances, rows AND
        columns of an unchanged batch memo-hits with the assignment
        remapped exactly through all three identity maps."""
        rng = np.random.default_rng(24)
        B, n, m = 5, 20, 6
        costs = rng.uniform(0, 10, (B, n, m))
        ids, rid, cid = np.arange(B), np.arange(100, 100 + n), np.arange(500, 500 + m)
        ctx = MatchContext()
        r1 = solve_lap_batched(
            costs, backend="auction", context=ctx, context_key="t",
            instance_ids=ids, row_ids=rid, col_ids=cid,
        )
        pi, pr, pc = rng.permutation(B), rng.permutation(n), rng.permutation(m)
        r2 = solve_lap_batched(
            costs[pi][:, pr][:, :, pc], backend="auction", context=ctx,
            context_key="t", instance_ids=ids[pi], row_ids=rid[pr],
            col_ids=cid[pc],
        )
        assert r2.embedding == "rect"
        assert r2.warm.all() and r2.bid_iters.sum() == 0
        inv_pc = np.argsort(pc)
        for b in range(B):
            orig = r1.col_of[pi[b]]
            expect = np.where(orig[pr] >= 0, inv_pc[np.clip(orig[pr], 0, None)], -1)
            assert (r2.col_of[b] == expect).all()
        # totals only differ by float summation order under permutation
        np.testing.assert_allclose(r2.total_cost, r1.total_cost[pi], rtol=1e-12)

    @given(
        st.integers(2, 8),    # starting batch
        st.integers(3, 6),    # n
        st.integers(2, 5),    # rounds
        st.integers(0, 2**32 - 1),
    )
    @settings(max_examples=12, deadline=None)
    def test_churn_property(self, b, n, rounds, seed):
        """Random instance arrivals/departures + row mutations every
        round: parity always holds, unchanged surviving instances always
        memo-hit with zero bid iterations."""
        rng = np.random.default_rng(seed)
        costs = rng.integers(0, 16, (b, n, n)).astype(float)
        ids = np.arange(b, dtype=np.int64)
        next_id = b
        ctx = MatchContext()
        prev = {}
        for _ in range(rounds):
            res = solve_lap_batched(
                costs, backend="auction", context=ctx, context_key="h",
                instance_ids=ids,
            )
            np.testing.assert_allclose(res.total_cost, _scipy_totals(costs))
            for k, i in enumerate(ids):
                if i in prev and prev[i] is not None:
                    assert res.warm[k], f"surviving unchanged {i} not warm"
                    assert res.bid_iters[k] == 0
            # next round: drop one, add one, mutate one survivor
            prev = {int(i): True for i in ids}
            order = rng.permutation(len(ids))
            keep = order[: max(1, len(ids) - 1)]
            costs, ids = costs[keep], ids[keep]
            if rng.random() < 0.8:
                costs = np.concatenate(
                    [costs, rng.integers(0, 16, (1, n, n)).astype(float)]
                )
                ids = np.concatenate([ids, [next_id]])
                prev[next_id] = None  # new this round: no memo claim
                next_id += 1
            mi = int(rng.integers(len(keep)))
            costs = costs.copy()
            costs[mi, rng.integers(n)] = rng.integers(0, 16, n)
            prev[int(ids[mi])] = None  # mutated: no memo claim


class TestCompaction:
    """Satellite: partial-batch compaction edge cases — 0-changed (pure
    memo), 1-changed, all-changed and majority-changed sub-batches all
    match the uncompacted path bit-for-bit, and the scatter preserves
    per-instance converged flags."""

    def _round_pair(self, n_changed, b=8, k=5, seed=30):
        rng = np.random.default_rng(seed)
        costs = rng.integers(0, 16, (b, k, k)).astype(float)
        ctx = MatchContext()
        solve_lap_batched(costs, backend="auction", context=ctx, context_key="e")
        costs2 = costs.copy()
        changed = rng.choice(b, n_changed, replace=False)
        # changed instances get FRESH identities so their compacted solve
        # is a cold solve — bit-for-bit comparable to the uncompacted path
        ids2 = np.arange(b, dtype=np.int64)
        for j, i in enumerate(changed):
            costs2[i] = rng.integers(0, 16, (k, k)).astype(float)
            ids2[i] = 1000 + j
        return ctx, costs, costs2, ids2, changed

    @pytest.mark.parametrize("n_changed", [0, 1, 5, 8])
    def test_compacted_matches_uncompacted_bitwise(self, n_changed):
        ctx, costs, costs2, ids2, changed = self._round_pair(n_changed)
        res = solve_lap_batched(
            costs2, backend="auction", context=ctx, context_key="e",
            instance_ids=ids2,
        )
        # uncompacted reference: the same batch, no context at all
        ref = solve_lap_batched(costs2, backend="auction")
        assert (res.col_of == ref.col_of).all()
        np.testing.assert_array_equal(res.total_cost, ref.total_cost)
        assert res.warm.sum() == 8 - n_changed
        assert (res.bid_iters[changed] > 0).all() if n_changed else True
        unchanged = np.setdiff1d(np.arange(8), changed)
        assert (res.bid_iters[unchanged] == 0).all()

    def test_scatter_preserves_converged_flags(self):
        """Regression: memoised instances keep their cached converged /
        fallback flags while a starved compacted solve reports its own —
        the scatter must not smear either across the batch."""
        # seed chosen so the 2-iteration solve is genuinely suboptimal
        # (some seeds luck into the optimum, where not counting a
        # fallback is the documented behaviour)
        rng = np.random.default_rng(32)
        b, k = 6, 8
        costs = rng.integers(0, 50, (b, k, k)).astype(float)
        ctx = MatchContext()
        r1 = solve_lap_batched(costs, backend="auction", context=ctx, context_key="f")
        assert r1.converged.all()
        costs2 = costs.copy()
        costs2[2] = rng.integers(0, 50, (k, k)).astype(float)
        ids2 = np.arange(b, dtype=np.int64)
        ids2[2] = 777  # fresh identity -> cold compacted solve
        res = solve_lap_batched(
            costs2, backend="auction", context=ctx, context_key="f",
            instance_ids=ids2, max_iters=2,  # starve ONLY the compacted lane
        )
        assert not res.converged[2] and res.used_fallback[2]
        keep = np.setdiff1d(np.arange(b), [2])
        assert res.converged[keep].all()
        assert not res.used_fallback[keep].any()
        np.testing.assert_allclose(res.total_cost, _scipy_totals(costs2))

    def test_memo_round_is_bit_identical(self):
        """0-changed: the pure-memo round reproduces the previous result
        bit-for-bit (assignments AND totals)."""
        ctx, costs, costs2, ids2, _ = self._round_pair(0)
        base = solve_lap_batched(costs, backend="auction")
        res = solve_lap_batched(
            costs2, backend="auction", context=ctx, context_key="e",
            instance_ids=ids2,
        )
        assert (res.col_of == base.col_of).all()
        np.testing.assert_array_equal(res.total_cost, base.total_cost)
        assert res.bid_iters.sum() == 0 and res.warm.all()


class TestDepartedIdentityLru:
    """Departed-identity LRU (ROADMAP): a column/instance identity that
    leaves a family parks its final auction price in a bounded LRU, and an
    identity that RESUMES after absent rounds (Tiresias demotion-resume)
    re-enters with that price as a head start instead of cold — with
    assignments still exactly scipy's (integer costs)."""

    def _solve(self, ctx, costs, ids, key="lru"):
        rows = np.arange(costs.shape[1], dtype=np.int64)
        return solve_lap_batched(
            costs,
            backend="auction",
            context=ctx,
            context_key=key,
            instance_ids=ids,
            row_ids=rows,
            col_ids=rows,
        )

    def _replay(self, ctx):
        """Round 1: three instances; round 2: instance 12 absent;
        round 3: it resumes unchanged.  Returns (r1, r3)."""
        rng = np.random.default_rng(42)
        costs = rng.integers(0, 50, (3, 8, 8)).astype(float)
        ids = np.array([10, 11, 12])
        r1 = self._solve(ctx, costs, ids)
        self._solve(ctx, costs[:2], ids[:2])
        r3 = self._solve(ctx, costs, ids)
        return costs, r1, r3

    def test_absent_round_resume_re_enters_warm(self):
        ctx = MatchContext()
        costs, r1, r3 = self._replay(ctx)
        assert ctx.stats["lru_parked_cols"] > 0, "departure parked nothing"
        assert ctx.stats["lru_restored_cols"] > 0, "resume restored nothing"
        # exactness and bit-stability vs the first solve
        np.testing.assert_array_equal(r3.col_of, r1.col_of)
        np.testing.assert_allclose(r3.total_cost, _scipy_totals(costs))
        # the resumed instance must NOT be reported warm (content was
        # never fingerprint-verified) ...
        assert not r3.warm[2]
        # ... but must beat its own cold-start cost
        assert r3.bid_iters[2] < r1.bid_iters[2]

    def test_lru_disabled_resume_is_cold(self):
        ctx = MatchContext(departed_lru_capacity=0)
        costs, r1, r3 = self._replay(ctx)
        assert ctx.stats["lru_parked_cols"] == 0
        assert ctx.stats["lru_restored_cols"] == 0
        # still correct, just cold: full schedule re-run
        np.testing.assert_allclose(r3.total_cost, _scipy_totals(costs))
        assert r3.bid_iters[2] >= r1.bid_iters[2]

    def test_lru_beats_cold_on_resume_iterations(self):
        with_lru = MatchContext()
        without = MatchContext(departed_lru_capacity=0)
        _, _, warm3 = self._replay(with_lru)
        _, _, cold3 = self._replay(without)
        assert warm3.bid_iters[2] < cold3.bid_iters[2]
        np.testing.assert_array_equal(warm3.col_of, cold3.col_of)

    def test_capacity_bound_evicts_lru_order(self):
        ctx = MatchContext(departed_lru_capacity=4)
        rng = np.random.default_rng(0)
        costs = rng.integers(0, 30, (4, 3, 3)).astype(float)
        self._solve(ctx, costs, np.array([1, 2, 3, 4]))
        # drop all four instances -> 4*3 = 12 departed cols, capacity 4
        self._solve(ctx, costs[:1] * 0 + 1.0, np.array([99]))
        lru = ctx._departed[("lru", "auction", False)]
        assert len(lru) <= 4

    def test_shrink_then_return_drops_stale_columns(self):
        """ISSUE-6 satellite: an identity that departs and RETURNS with a
        changed column set gets its surviving columns restored by IDENTITY
        and its no-longer-present columns dropped — stale parked prices
        must not linger past the return (they could otherwise seed a
        later, unrelated incarnation of the column id)."""
        ctx = MatchContext()
        rng = np.random.default_rng(7)
        costs = rng.integers(1, 50, (3, 8, 8)).astype(float)
        ids = np.array([10, 11, 12])
        rows = np.arange(8, dtype=np.int64)
        kw = dict(backend="auction", context=ctx, context_key="lru")
        solve_lap_batched(costs, instance_ids=ids, row_ids=rows,
                          col_ids=rows, **kw)
        # instance 12 departs -> its nonzero prices park in the LRU
        solve_lap_batched(costs[:2], instance_ids=ids[:2], row_ids=rows,
                          col_ids=rows, **kw)
        lru = ctx._departed[("lru", "auction", False)]
        parked12 = sorted(c for (i, c) in lru if i == 12)
        assert len(parked12) >= 3, "precondition: several prices parked"
        # 12 returns with a SHRUNK column set: two parked columns gone,
        # two brand-new column ids in their place
        gone = parked12[-2:]
        cols12 = np.array(
            [c for c in range(8) if c not in gone][:6] + [90, 91],
            np.int64,
        )
        cids3 = np.broadcast_to(rows, (3, 8)).copy()
        cids3[2] = cols12
        costs3 = costs.copy()
        costs3[2] = rng.integers(1, 50, (8, 8)).astype(float)
        dropped_before = ctx.stats["lru_dropped_cols"]
        r3 = solve_lap_batched(costs3, instance_ids=ids, row_ids=rows,
                               col_ids=cids3, **kw)
        # every parked (12, *) entry was consumed: survivors restored,
        # the departed-forever columns DROPPED (pre-fix they lingered)
        assert not any(i == 12 for (i, _) in lru)
        assert ctx.stats["lru_dropped_cols"] - dropped_before >= len(gone)
        assert ctx.stats["lru_restored_cols"] > 0
        np.testing.assert_allclose(r3.total_cost, _scipy_totals(costs3))
        # a LATER round that re-introduces the dropped column ids must
        # come up cold: no stale price resurfaces
        restored_after_r3 = ctx.stats["lru_restored_cols"]
        cids4 = np.broadcast_to(rows, (3, 8)).copy()
        costs4 = costs3.copy()
        costs4[2] = rng.integers(1, 50, (8, 8)).astype(float)
        solve_lap_batched(costs4, instance_ids=ids, row_ids=rows,
                          col_ids=cids4, **kw)
        assert ctx.stats["lru_restored_cols"] == restored_after_r3

    def test_reset_clears_parked_prices(self):
        ctx = MatchContext()
        costs, _, _ = self._replay(ctx)
        ctx.reset()
        assert not ctx._departed
        r = self._solve(ctx, costs, np.array([10, 11, 12]))
        assert ctx.stats["lru_restored_cols"] == 0 or r.bid_iters.sum() > 0

    def test_exact_backend_has_no_price_state_to_park(self):
        ctx = MatchContext()
        rng = np.random.default_rng(1)
        costs = rng.integers(0, 20, (2, 4, 4)).astype(float)
        rows = np.arange(4, dtype=np.int64)
        kw = dict(backend="scipy", context=ctx, context_key="x",
                  row_ids=rows, col_ids=rows)
        solve_lap_batched(costs, instance_ids=np.array([1, 2]), **kw)
        solve_lap_batched(costs[:1], instance_ids=np.array([1]), **kw)
        assert ctx.stats["lru_parked_cols"] == 0


class TestTieBreakEngine:
    """Canonical tie-break perturbation: solver-independent assignments on
    tied instances, optimal totals preserved, default-off bit-compat."""

    BACKENDS = ("scipy", "numpy", "smallperm", "auction")

    def _all_backends(self, costs, **kw):
        return {
            be: solve_lap_batched(costs, backend=be, tie_break=True, **kw)
            for be in self.BACKENDS
        }

    def test_all_backends_agree_on_fully_tied_instances(self):
        costs = np.zeros((3, 5, 5))
        outs = self._all_backends(costs)
        ref = outs["scipy"].col_of
        for be, r in outs.items():
            np.testing.assert_array_equal(r.col_of, ref, err_msg=be)
            np.testing.assert_array_equal(r.total_cost, np.zeros(3))

    def test_all_backends_agree_under_duplicated_columns(self):
        rng = np.random.default_rng(7)
        costs = rng.integers(0, 5, (6, 6, 6)).astype(float)
        costs[:, :, 4] = costs[:, :, 1]  # interchangeable columns
        costs[:, 3, :] = costs[:, 0, :]  # interchangeable rows
        outs = self._all_backends(costs)
        ref = outs["scipy"]
        for be, r in outs.items():
            np.testing.assert_array_equal(r.col_of, ref.col_of, err_msg=be)
        # totals are still the UNPERTURBED optimum
        np.testing.assert_allclose(ref.total_cost, _scipy_totals(costs))

    def test_perturbation_never_changes_the_optimal_total(self):
        rng = np.random.default_rng(3)
        for _ in range(5):
            costs = rng.integers(0, 40, (4, 7, 7)).astype(float)
            r = solve_lap_batched(costs, backend="auction", tie_break=True)
            np.testing.assert_allclose(r.total_cost, _scipy_totals(costs))

    def test_rectangular_and_masked_instances(self):
        rng = np.random.default_rng(5)
        costs = rng.integers(0, 9, (3, 4, 7)).astype(float)
        costs[:, :, 5] = costs[:, :, 2]
        outs = {
            be: solve_lap_batched(costs, backend=be, tie_break=True)
            for be in ("scipy", "numpy", "auction")
        }
        ref = outs["scipy"]
        for be, r in outs.items():
            np.testing.assert_array_equal(r.col_of, ref.col_of, err_msg=be)
        np.testing.assert_allclose(ref.total_cost, _scipy_totals(costs))

    def test_default_off_matches_pre_knob_behaviour(self):
        rng = np.random.default_rng(9)
        costs = rng.integers(0, 25, (4, 6, 6)).astype(float)
        a = solve_lap_batched(costs, backend="auction")
        b = solve_lap_batched(costs, backend="auction", tie_break=False)
        np.testing.assert_array_equal(a.col_of, b.col_of)

    def test_tie_break_composes_with_identity_context(self):
        """Memo/warm machinery still works under the perturbation: an
        unchanged round memo-hits and stays canonical."""
        rng = np.random.default_rng(11)
        costs = rng.integers(0, 12, (4, 5, 5)).astype(float)
        costs[:, :, 3] = costs[:, :, 0]
        ctx = MatchContext()
        ids = np.arange(4)
        kw = dict(backend="auction", context=ctx, context_key="tb",
                  instance_ids=ids, tie_break=True)
        r1 = solve_lap_batched(costs, **kw)
        r2 = solve_lap_batched(costs, **kw)
        assert r2.bid_iters.sum() == 0 and r2.warm.all()
        np.testing.assert_array_equal(r2.col_of, r1.col_of)
        ref = solve_lap_batched(costs, backend="scipy", tie_break=True)
        np.testing.assert_array_equal(r1.col_of, ref.col_of)


class TestTieBreakIdentityKeyed:
    """ISSUE-6 satellite: the tie-break perturbation is keyed by (row_id,
    col_id) identity RANKS, not batch positions — so with tie_break=True a
    permuted-but-unchanged batch still fingerprint-memo-hits and the
    remapped plan is bit-identical (pre-fix, the positional ramp moved
    under permutation, every fingerprint missed, and equally-optimal
    instances could flip assignments)."""

    def _tied_costs(self):
        rng = np.random.default_rng(17)
        costs = rng.integers(0, 6, (4, 6, 6)).astype(float)
        costs[:, :, 4] = costs[:, :, 1]  # interchangeable columns
        costs[:, 3, :] = costs[:, 0, :]  # interchangeable rows
        inst = np.array([20, 21, 22, 23])
        rids = np.array([[5, 3, 9, 1, 7, 0]] * 4) + 10 * np.arange(4)[:, None]
        cids = np.array([[2, 8, 4, 6, 11, 13]] * 4) + 10 * np.arange(4)[:, None]
        return costs, inst, rids, cids

    def _pairs(self, res, rids, cids):
        out = []
        for b in range(res.col_of.shape[0]):
            rows, cols = res.pairs(b)
            out.append(sorted(zip(rids[b, rows], cids[b, cols])))
        return out

    def test_permuted_batch_memo_hits_and_plan_is_identical(self):
        costs, inst, rids, cids = self._tied_costs()
        ctx = MatchContext()
        kw = dict(backend="auction", context=ctx, context_key="tbid",
                  tie_break=True)
        r1 = solve_lap_batched(costs, instance_ids=inst, row_ids=rids,
                               col_ids=cids, **kw)
        # permute the batch AND the rows/columns inside each instance
        rng = np.random.default_rng(3)
        bp = rng.permutation(4)
        rp = rng.permutation(6)
        cp = rng.permutation(6)
        costs2 = costs[bp][:, rp][:, :, cp]
        r2 = solve_lap_batched(
            costs2, instance_ids=inst[bp], row_ids=rids[bp][:, rp],
            col_ids=cids[bp][:, cp], **kw,
        )
        # identity-keyed perturbation => bit-identical fingerprints => memo
        assert r2.bid_iters.sum() == 0, "permuted batch missed the memo"
        assert r2.warm.all()
        # and the remapped plan is the SAME set of (row_id, col_id) pairs
        p1 = self._pairs(r1, rids, cids)
        p2 = self._pairs(r2, rids[bp][:, rp], cids[bp][:, cp])
        for b_new, b_old in enumerate(bp):
            assert p2[b_new] == p1[b_old]

    def test_canonical_plan_is_permutation_invariant_across_backends(self):
        """The canonical optimum itself must not depend on the ORDER the
        instance arrives in: solving the permuted instance fresh (no
        context) yields the same identity pairs, on every backend."""
        costs, inst, rids, cids = self._tied_costs()
        rng = np.random.default_rng(5)
        rp = rng.permutation(6)
        cp = rng.permutation(6)
        for be in ("scipy", "numpy", "auction"):
            a = solve_lap_batched(costs, backend=be, tie_break=True,
                                  row_ids=rids, col_ids=cids)
            bres = solve_lap_batched(
                costs[:, rp][:, :, cp], backend=be, tie_break=True,
                row_ids=rids[:, rp], col_ids=cids[:, cp],
            )
            assert self._pairs(a, rids, cids) == self._pairs(
                bres, rids[:, rp], cids[:, cp]
            ), be

    def test_positional_ramp_preserved_without_identities(self):
        """No identities supplied -> ranks degenerate to positions: the
        perturbed benefit is bit-identical to the historical ramp, so seed
        tie-break assignments are unchanged."""
        from repro.core.matching.engine import _tie_break_perturb

        rng = np.random.default_rng(23)
        ben = rng.integers(0, 9, (3, 5, 7)).astype(float)
        legacy_w = (np.arange(1, 6, dtype=np.float64) ** 2)[:, None] * np.arange(
            1, 8, dtype=np.float64
        )[None, :]
        pert, scale = _tie_break_perturb(ben)
        assert scale is not None
        np.testing.assert_array_equal(pert, ben + scale * legacy_w)
        # and explicit default identities (arange) give the same ramp
        rids = np.broadcast_to(np.arange(5, dtype=np.int64), (3, 5))
        cids = np.broadcast_to(np.arange(7, dtype=np.int64), (3, 7))
        pert2, scale2 = _tie_break_perturb(ben, np.asarray(rids), np.asarray(cids))
        assert scale2 == scale
        np.testing.assert_array_equal(pert2, pert)


class TestContextLookup:
    """The context lookup (instance/row/col identity matching + the exact
    fingerprint compare) is one host computation: any int64 ids, no
    device readout of its own."""

    def _churn_replay(self, ids_offset=0):
        rng = np.random.default_rng(31)
        ctx = MatchContext()
        plans = []
        ids = np.array([3, 1, 4, 5]) + ids_offset
        rows = np.arange(7, dtype=np.int64)
        costs = rng.integers(0, 30, (4, 7, 7)).astype(float)
        for _ in range(4):
            res = solve_lap_batched(
                costs, backend="auction", context=ctx, context_key="dev",
                instance_ids=ids, row_ids=rows, col_ids=rows,
            )
            plans.append(res.col_of.copy())
            costs, _ = _mutate(rng, costs, 1)
        return ctx, plans

    def test_plans_do_not_depend_on_id_magnitude(self):
        """Ids from 0 and ids offset by 2^32 take the same lookup: same
        costs, same plans."""
        ctx_lo, plans_lo = self._churn_replay(0)
        ctx_hi, plans_hi = self._churn_replay(1 << 32)
        for a, b in zip(plans_lo, plans_hi):
            np.testing.assert_array_equal(a, b)
        # both replays counted their auction readouts
        assert ctx_lo.stats["host_syncs"] > 0
        assert ctx_lo.stats["host_syncs"] == ctx_hi.stats["host_syncs"]

    def test_steady_state_rounds_read_nothing_back(self):
        """An unchanged auction round is served by the full-memo fast path
        from the host lookup alone: no bid iterations, all warm, and no
        device->host sync."""
        rng = np.random.default_rng(8)
        ctx = MatchContext()
        costs = rng.integers(0, 20, (4, 6, 6)).astype(float)
        ids = np.arange(4) + 100
        kw = dict(backend="auction", context=ctx, context_key="steady",
                  instance_ids=ids)
        solve_lap_batched(costs, **kw)
        before = ctx.stats["host_syncs"]
        res = solve_lap_batched(costs, **kw)
        assert res.bid_iters.sum() == 0 and res.warm.all()
        assert ctx.stats["host_syncs"] - before == 0

    def test_exact_backend_context_stays_on_host(self):
        """Three churn rounds of an exact backend with a context: the
        stored fingerprints are a numpy array of the real shape, nothing
        is read back from the device, and every result equals a
        context-free solve."""
        rng = np.random.default_rng(17)
        ctx = MatchContext()
        costs = rng.integers(0, 30, (5, 6, 6)).astype(float)
        ids = np.array([9, 2, 7, 4, 11])
        for _ in range(3):
            res = solve_lap_batched(
                costs, backend="scipy", context=ctx, context_key="exact",
                instance_ids=ids,
            )
            ref = solve_lap_batched(costs, backend="scipy")
            np.testing.assert_array_equal(res.col_of, ref.col_of)
            np.testing.assert_array_equal(res.total_cost, ref.total_cost)
            (entry,) = ctx._entries.values()
            assert isinstance(entry.fp_bits, np.ndarray)
            assert entry.fp_bits.shape == (5, 6, 6, 2)
            costs, _ = _mutate(rng, costs, 2)
        assert ctx.stats["host_syncs"] == 0
        assert ctx.stats["memo_instances"] > 0
