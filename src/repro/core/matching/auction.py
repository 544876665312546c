"""JAX auction-algorithm solver for the assignment problem (beyond-paper).

The paper solves every matching with scipy's Hungarian on the host CPU.  Two
observations make a JAX solver worthwhile:

1. Algorithm 2 solves **k_c^2 independent node-level LAPs** (one per node
   pair) before the final node-level matching — an embarrassingly batchable
   fan-out that ``jax.vmap`` turns into one XLA program.
2. Bertsekas' auction algorithm is data-parallel *inside* each instance: the
   bid step is a masked row-wise top-2 reduction over the benefit matrix —
   a natural accelerator kernel (see ``repro.kernels.lap_bid`` for the Pallas
   version tiled for VMEM).

We implement the Jacobi (all-unassigned-bid-simultaneously) forward auction
with epsilon scaling.  For integer-valued benefits and a final
``eps < 1/n`` the result is provably optimal; for float benefits the total
benefit is within ``n * eps_min`` of optimal (we quantise throughputs before
solving when exactness matters).

Warm starts (beyond-paper): every solver accepts ``init_prices`` and a
per-instance ``warm`` flag.  Auction correctness never depends on the
initial prices — each bid re-establishes eps-complementary slackness for
the bidder — so carrying last round's equilibrium prices into this round's
solve is always *valid*; when the costs barely changed (the Tesserae
round-to-round locality the paper's Fig. 2/14b exploits) it is also *fast*:
a warm instance skips the epsilon-scaling schedule entirely and runs one
phase at ``eps_min``.  The matching engine's identity-keyed
``MatchContext`` is the canonical producer of ``init_prices``: it
re-assembles last round's prices per *column identity* (jobs/nodes/GPUs),
so prices survive rows and columns arriving, finishing or permuting — any
re-assembly is valid by the argument above, it only has to be *useful*.
For square instances the ``n * eps`` bound holds for ANY initial prices
(both totals telescope over the same full column set); for rectangular
instances the matching engine verifies an a-posteriori price certificate
and re-solves the rare instance that fails it (see
``engine._rect_bound_violation``).  ``AuctionResult.prices`` is returned
as a device array and is cached as one — prices never round-trip through
the host between rounds.

Rectangular instances (n != m) also get a **native forward auction**
(:func:`auction_lap_rect_batched`): bidders are the short side, bids range
only over the real columns, and no ``max(n, m)^2`` square embedding is ever
materialised — the fix for very skew packing graphs (|placed| >> |pending|)
where the square embedding paid quadratic work for a linear-ish problem.

All shapes are static; the solvers are ``jit``- and ``vmap``-compatible.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

_NEG = -1e18

#: Instance size at which the Pallas bid kernel becomes the default bid
#: path on TPU (one (n, n) VMEM-tiled top-2 sweep per round beats the XLA
#: argmax/one-hot lowering).  Off-TPU the kernel only runs in interpret
#: mode, which is strictly slower than jnp — so auto mode never picks it
#: there; tests opt in explicitly with ``use_kernel=True``.
KERNEL_MIN_N = 256


def _auto_use_kernel(n: int) -> bool:
    return n >= KERNEL_MIN_N and jax.default_backend() == "tpu"


class AuctionResult(NamedTuple):
    # col_of[i]  = object assigned to person (row) i
    # row_of[j]  = person assigned to object (column) j
    col_of: jax.Array
    row_of: jax.Array
    prices: jax.Array
    iters: jax.Array
    converged: jax.Array


def _top2(vals: jax.Array):
    """Row-wise (best value, best index, second-best value).  The best
    value is the row max, not a gather at ``best_j``: the same number up to
    the sign of a zero, which cancels in ``best - second``."""
    best_j = jnp.argmax(vals, axis=-1)
    n = vals.shape[-1]
    best_v = jnp.max(vals, axis=-1)
    masked = jnp.where(
        jax.nn.one_hot(best_j, n, dtype=bool), _NEG, vals
    )
    second_v = jnp.max(masked, axis=-1)
    return best_v, best_j, second_v


def _inverse_assignment(assign: jax.Array, out_size: int) -> jax.Array:
    """Invert a partial injective map: ``assign`` (k,) holds values in
    ``[0, out_size)`` or -1; returns (out_size,) with ``inv[assign[i]] = i``
    and -1 elsewhere.  Square helpers are the ``out_size == k`` case."""
    k = assign.shape[0]
    safe = jnp.where(assign >= 0, assign, out_size)
    return (
        jnp.full((out_size + 1,), -1, jnp.int32)
        .at[safe]
        .set(jnp.arange(k, dtype=jnp.int32))[:out_size]
    )


def auction_lap(
    benefit: jax.Array,
    eps_min: float | jax.Array | None = None,
    max_iters: int = 20_000,
    use_kernel: bool | None = None,
    init_prices: jax.Array | None = None,
    warm: bool | jax.Array = False,
) -> AuctionResult:
    """Maximise ``sum_i benefit[i, col_of[i]]`` over permutations.

    Args:
      benefit: (n, n) float matrix.  Use ``-cost`` to minimise.  Forbidden
        edges should be a large negative number (not -inf, to keep bids
        finite) — see :func:`masked_square_benefit` for the embedding that
        handles rectangular / masked instances.
      eps_min: final epsilon of the scaling schedule.  Defaults to
        ``1 / (n + 1)`` — exact for integer benefits (only the STARTING
        epsilon is scaled by the benefit range).
      max_iters: safety cap on total bid rounds.
      use_kernel: route the bid top-2 through the Pallas kernel.  ``None``
        (default) picks the kernel automatically for instances with
        ``n >= KERNEL_MIN_N`` on TPU; off-TPU the kernel runs in interpret
        mode and is only used when explicitly requested.
      init_prices: (n,) warm-start prices (defaults to zeros).  Any values
        are valid; see the module docstring for the optimality argument.
      warm: skip the epsilon-scaling schedule and run a single phase at
        ``eps_min`` — the warm-start fast path when ``init_prices`` are
        near this round's equilibrium.
    """
    if use_kernel is None:
        use_kernel = _auto_use_kernel(int(benefit.shape[-1]))
    return _auction_lap_jit(
        benefit,
        eps_min,
        max_iters=max_iters,
        use_kernel=use_kernel,
        init_prices=init_prices,
        warm=jnp.asarray(warm),
    )


@functools.partial(jax.jit, static_argnames=("max_iters", "use_kernel"))
def _auction_lap_jit(
    benefit: jax.Array,
    eps_min: float | jax.Array | None = None,
    max_iters: int = 20_000,
    use_kernel: bool = False,
    init_prices: jax.Array | None = None,
    warm: jax.Array | None = None,
) -> AuctionResult:
    benefit = jnp.asarray(benefit, dtype=jnp.float32)
    n = benefit.shape[-1]
    if benefit.shape != (n, n):
        raise ValueError(f"benefit must be square, got {benefit.shape}")

    if eps_min is None:
        eps_min = 1.0 / (n + 1)
    eps_min = jnp.asarray(eps_min, dtype=jnp.float32)
    span = jnp.maximum(jnp.max(jnp.abs(benefit)), 1.0)
    eps0 = jnp.maximum(span / 4.0, eps_min)
    if warm is not None:
        # warm instances skip the scaling schedule: one phase at eps_min.
        eps0 = jnp.where(warm, eps_min, eps0)

    bid_round = _make_bid_round(benefit, n, _pick_top2(use_kernel))

    def cond(state):
        prices, col_of, eps, it, _ = state
        all_assigned = jnp.all(col_of >= 0)
        done = all_assigned & (eps <= eps_min * (1 + 1e-6))
        return (~done) & (it < max_iters)

    def body(state):
        prices, col_of, eps, it, _ = state
        all_assigned = jnp.all(col_of >= 0)
        # Phase change: shrink eps and restart the assignment, keep prices.
        def next_phase(_):
            return prices, jnp.full((n,), -1, jnp.int32), jnp.maximum(eps / 5.0, eps_min)

        def same_phase(_):
            p, c = bid_round(prices, col_of, eps)
            return p, c, eps

        prices, col_of, eps = jax.lax.cond(
            all_assigned & (eps > eps_min * (1 + 1e-6)), next_phase, same_phase, None
        )
        return prices, col_of, eps, it + 1, jnp.all(col_of >= 0)

    p0 = (
        jnp.zeros((n,), jnp.float32)
        if init_prices is None
        else jnp.asarray(init_prices, jnp.float32)
    )
    init = (
        p0,
        jnp.full((n,), -1, jnp.int32),
        eps0,
        jnp.asarray(0, jnp.int32),
        jnp.asarray(False),
    )
    prices, col_of, eps, iters, _ = jax.lax.while_loop(cond, body, init)
    # Converged = completed the FULL epsilon schedule with everyone
    # assigned.  All-assigned alone is not enough: an instance cut off by
    # ``max_iters`` mid-scaling can hold a complete but far-from-optimal
    # assignment (eps still large) — the engine must know to re-solve it.
    converged = jnp.all(col_of >= 0) & (eps <= eps_min * (1 + 1e-6))
    row_of = _inverse_assignment(col_of, n)
    return AuctionResult(col_of, row_of, prices, iters, converged)


def _pick_top2(use_kernel: bool):
    """Bid top-2 reduction as ``(benefit, prices) -> (best, arg, second)``.

    The kernel path hands benefit and prices to the Pallas kernel, which
    fuses the ``benefit - prices`` subtraction into its tiled sweep — no
    (n, m) ``vals`` temporary is materialised per bid round (the previous
    code precomputed ``vals`` in XLA and then had the kernel subtract a
    zero price vector from it)."""
    if use_kernel:
        from repro.kernels.ops import lap_bid

        return lap_bid
    return lambda benefit, prices: _top2(benefit - prices[None, :])


def _inverse_assignment_dense(assign: jax.Array, out_size: int) -> jax.Array:
    """:func:`_inverse_assignment` without its scatter, as a compare and a
    reduce: ``inv[j] = max_i where(assign[i] == j, i, -1)``.  The map is
    injective, so at most one ``i`` matches."""
    k = assign.shape[0]
    hit = assign[None, :] == jnp.arange(out_size, dtype=assign.dtype)[:, None]
    return jnp.max(jnp.where(hit, jnp.arange(k, dtype=jnp.int32), -1), axis=1)


def _make_bid_round(benefit: jax.Array, m: int, top2, dense: bool = False):
    """Jacobi bid round over an (n, m) benefit matrix (square or rect):
    every unassigned person bids for its best object; objects take the
    highest bid.  Returns ``(prices, col_of) -> (prices, col_of)``.

    ``dense`` makes the same decisions bit for bit with no gather and no
    scatter: the price at each bidder's best object is read through the
    bid's one-hot mask, and both inversions of the assignment are
    :func:`_inverse_assignment_dense`.  On the TPU, batched gathers and
    scatters run an element at a time, while these (n, m) compares and
    reductions fuse.  The fused migrate program's auctions take it; the
    engine's keep the indexed form."""
    n = benefit.shape[0]
    invert = _inverse_assignment_dense if dense else _inverse_assignment

    def bid_round(prices, col_of, eps):
        unassigned = col_of < 0
        best_v, best_j, second_v = top2(benefit, prices)
        incr = best_v - second_v + eps
        best = jax.nn.one_hot(best_j, m, dtype=bool)
        # Bid value person i offers for its best object.
        if dense:
            offer = jnp.max(jnp.where(best, prices[None, :], -jnp.inf), axis=1) + incr
        else:
            offer = prices[best_j] + incr
        # (n_persons, n_objects) matrix of offers; -inf where no bid.
        bids = jnp.where(unassigned[:, None] & best, offer[:, None], _NEG)
        has_bid = jnp.any(bids > _NEG / 2, axis=0)
        winner = jnp.argmax(bids, axis=0)
        new_price = jnp.max(bids, axis=0)
        prices = jnp.where(has_bid, new_price, prices)
        # Recompute owners: objects with a bid switch to the winner.
        row_of_prev = invert(col_of, m)
        row_of = jnp.where(has_bid, winner, row_of_prev)
        col_of = invert(row_of, n)
        return prices, col_of

    return bid_round


@functools.partial(jax.jit, static_argnames=("max_iters", "use_kernel"))
def _auction_lap_rect_jit(
    benefit: jax.Array,
    eps_min: float | jax.Array | None = None,
    max_iters: int = 20_000,
    use_kernel: bool = False,
    init_prices: jax.Array | None = None,
    warm: jax.Array | None = None,
) -> AuctionResult:
    """Native rectangular forward auction: (n, m) benefit with n <= m.

    The n persons (rows) bid over the m real objects — no square embedding,
    no padded bidders.  Termination: all n persons assigned (always
    feasible: the engine's rect benefit is finite everywhere).

    Unlike the square solver, the rectangular auction runs a SINGLE phase
    at ``eps_min``: the ``n * eps`` optimality bound for asymmetric
    instances requires the final prices of unassigned objects to never
    exceed those the optimum would use — automatic when initial prices are
    all equal, but *broken* by epsilon-scaling phase restarts (a column
    over-priced in an early large-eps phase and then abandoned keeps its
    stale price, and with m > n it is never forced back to equilibrium;
    empirically this loses several spans of benefit, not ``n * eps``).
    Warm starts pass non-equal ``init_prices``; the engine re-establishes
    the bound a posteriori via the price certificate
    (``engine._rect_bound_violation``) and re-solves instances that fail.
    """
    benefit = jnp.asarray(benefit, dtype=jnp.float32)
    n, m = benefit.shape
    if n > m:
        raise ValueError(f"rect auction requires n <= m, got {benefit.shape}")

    if eps_min is None:
        eps_min = 1.0 / (n + 1)
    eps = jnp.asarray(eps_min, dtype=jnp.float32)  # single phase
    del warm  # warmth only changes init_prices on the rect path

    bid_round = _make_bid_round(benefit, m, _pick_top2(use_kernel))

    def cond(state):
        _, col_of, it = state
        return (~jnp.all(col_of >= 0)) & (it < max_iters)

    def body(state):
        prices, col_of, it = state
        prices, col_of = bid_round(prices, col_of, eps)
        return prices, col_of, it + 1

    p0 = (
        jnp.zeros((m,), jnp.float32)
        if init_prices is None
        else jnp.asarray(init_prices, jnp.float32)
    )
    init = (p0, jnp.full((n,), -1, jnp.int32), jnp.asarray(0, jnp.int32))
    prices, col_of, iters = jax.lax.while_loop(cond, body, init)
    converged = jnp.all(col_of >= 0)
    row_of = _inverse_assignment(col_of, m)
    return AuctionResult(col_of, row_of, prices, iters, converged)


def auction_lap_batched(
    benefits: jax.Array,
    max_iters: int = 20_000,
    eps_min: float | jax.Array | None = None,
    use_kernel: bool | None = None,
    init_prices: jax.Array | None = None,
    warm: jax.Array | None = None,
) -> AuctionResult:
    """vmap'd auction over a batch of (n, n) benefit matrices.

    This is the Algorithm-2 fan-out: all k_c^2 node-pair LAPs solve in one
    XLA program instead of k_c^2 sequential scipy calls.  Every result
    field gains a leading batch axis — in particular ``converged`` is
    per-instance, which the matching engine uses to re-solve stragglers
    with scipy.  ``init_prices`` (B, n) and ``warm`` (B,) thread last
    round's price state per instance (see :class:`engine.MatchContext`).
    With ``use_kernel`` the bid top-2 lowers to ONE batched Pallas call per
    round: ``vmap``'s pallas batching rule lifts the 2-D kernel by
    prepending a batch grid axis (equivalent to the explicit
    ``lap_bid_pallas_batched``, which parity tests pin against it).
    """
    if use_kernel is None:
        use_kernel = _auto_use_kernel(int(benefits.shape[-1]))
    return _auction_lap_batched_jit(
        benefits,
        eps_min,
        max_iters=max_iters,
        use_kernel=use_kernel,
        init_prices=init_prices,
        warm=warm,
    )


def _vmap_auction(
    solver, benefits, eps_min, max_iters, use_kernel, init_prices, warm
) -> AuctionResult:
    """Shared vmap dispatch for the square and rectangular batched solvers
    (with / without per-instance warm-start state)."""
    if init_prices is None:
        return jax.vmap(
            lambda b: solver(b, eps_min, max_iters=max_iters, use_kernel=use_kernel)
        )(benefits)
    if warm is None:
        warm = jnp.zeros(benefits.shape[0], bool)
    return jax.vmap(
        lambda b, p, w: solver(
            b,
            eps_min,
            max_iters=max_iters,
            use_kernel=use_kernel,
            init_prices=p,
            warm=w,
        )
    )(benefits, init_prices, warm)


@functools.partial(jax.jit, static_argnames=("max_iters", "use_kernel"))
def _auction_lap_batched_jit(
    benefits: jax.Array,
    eps_min=None,
    max_iters: int = 20_000,
    use_kernel: bool = False,
    init_prices: jax.Array | None = None,
    warm: jax.Array | None = None,
) -> AuctionResult:
    return _vmap_auction(
        _auction_lap_jit, benefits, eps_min, max_iters, use_kernel, init_prices, warm
    )


def auction_lap_rect_batched(
    benefits: jax.Array,
    max_iters: int = 20_000,
    eps_min: float | jax.Array | None = None,
    use_kernel: bool | None = None,
    init_prices: jax.Array | None = None,
    warm: jax.Array | None = None,
) -> AuctionResult:
    """vmap'd **rectangular** forward auction over (B, n, m) benefits,
    n <= m.  Bids range only over the m real columns — the padded-instance
    fix for skew packing graphs.  Same warm-start contract as
    :func:`auction_lap_batched`; ``init_prices`` is (B, m)."""
    if use_kernel is None:
        use_kernel = _auto_use_kernel(int(benefits.shape[-1]))
    return _auction_lap_rect_batched_jit(
        benefits,
        eps_min,
        max_iters=max_iters,
        use_kernel=use_kernel,
        init_prices=init_prices,
        warm=warm,
    )


@functools.partial(jax.jit, static_argnames=("max_iters", "use_kernel"))
def _auction_lap_rect_batched_jit(
    benefits: jax.Array,
    eps_min=None,
    max_iters: int = 20_000,
    use_kernel: bool = False,
    init_prices: jax.Array | None = None,
    warm: jax.Array | None = None,
) -> AuctionResult:
    return _vmap_auction(
        _auction_lap_rect_jit,
        benefits,
        eps_min,
        max_iters,
        use_kernel,
        init_prices,
        warm,
    )


def _pad_value(benefit: np.ndarray, finite: np.ndarray) -> np.ndarray:
    """PER-INSTANCE benefit value for padded / forbidden cells: strictly
    below anything a real edge can contribute through an augmenting cycle.
    Must scale with the instance SIZE, not just the value span: displacing
    a pad edge can rearrange every real edge of the assignment, and each
    rearranged edge can swing the total by up to 2*span (see
    masked_square_benefit).  Returns shape ``benefit.shape[:-2]`` — the
    reduction is over each instance alone, NOT the batch: a batch-global
    span would couple every instance's pad cells to whichever instance
    holds the batch max, so one instance arriving or departing would
    change the pad bit pattern of every survivor and silently defeat the
    engine's identity-keyed fingerprint memoisation for masked /
    forbidden-edge batches."""
    n, m = benefit.shape[-2], benefit.shape[-1]
    size = max(n, m)
    span = np.where(finite, np.abs(benefit), 0.0).max(axis=(-2, -1))
    return -(2.0 * size * span + 1.0)


def masked_square_benefit(
    cost: np.ndarray,
    maximize: bool = False,
    row_mask: np.ndarray | None = None,
    col_mask: np.ndarray | None = None,
) -> np.ndarray:
    """Embed (possibly rectangular / masked / forbidden-edge) cost instances
    into square benefit matrices the auction can solve.

    ``cost``: (..., n, m).  ``row_mask``/``col_mask``: (..., n) / (..., m)
    bool, True = real.  Non-finite entries are forbidden edges.

    Padding / forbidden cells get a constant benefit low enough that no
    optimal assignment ever trades a (real, real) pair for a padded one —
    i.e. *padding never wins*: the square optimum restricted to real rows
    x real cols is the rectangular optimum.  The pad must scale with the
    instance SIZE, not just the value span: displacing a pad edge can
    rearrange every real edge of the assignment (an augmenting cycle), and
    each rearranged edge can swing the total by up to 2*span — a constant
    pad of -(2*span+1) provably fails on mixed-sign costs (e.g. minimise
    [[2, inf], [-2, 2]]: the forbidden cell at -(2*span+1) beats the
    complete finite matching).  Callers drop pairs whose original entry is
    padded or non-finite.
    """
    cost = np.asarray(cost, dtype=np.float64)
    n, m = cost.shape[-2], cost.shape[-1]
    size = max(n, m)
    benefit = cost if maximize else -cost
    finite = np.isfinite(benefit)
    pad = _pad_value(benefit, finite)[..., None, None]  # per instance
    sq = np.broadcast_to(
        pad, (*cost.shape[:-2], size, size)
    ).astype(np.float64, copy=True)
    sq[..., :n, :m] = np.where(finite, benefit, pad)
    if row_mask is not None:
        rm = np.asarray(row_mask, bool)[..., :, None]  # (..., n, 1)
        sq[..., :n, :] = np.where(rm, sq[..., :n, :], pad)
    if col_mask is not None:
        cm = np.asarray(col_mask, bool)[..., None, :]  # (..., 1, m)
        sq[..., :, :m] = np.where(cm, sq[..., :, :m], pad)
    return sq


def masked_rect_benefit(
    cost: np.ndarray,
    maximize: bool = False,
    row_mask: np.ndarray | None = None,
    col_mask: np.ndarray | None = None,
) -> np.ndarray:
    """Rectangular counterpart of :func:`masked_square_benefit`: same pad
    rule (masked rows/cols and forbidden edges become a size-scaled
    constant strictly below every real benefit), but the (..., n, m) shape
    is preserved — no ``max(n, m)^2`` square embedding is ever allocated.
    Callers drop pairs whose original entry is padded or non-finite, and
    orient the instance so bidders are the short side (n <= m)."""
    cost = np.asarray(cost, dtype=np.float64)
    benefit = np.where(np.isfinite(cost), cost if maximize else -cost, 0.0)
    finite = np.isfinite(cost)
    pad = _pad_value(benefit, finite)[..., None, None]  # per instance
    out = np.where(finite, benefit, pad)
    if row_mask is not None:
        out = np.where(np.asarray(row_mask, bool)[..., :, None], out, pad)
    if col_mask is not None:
        out = np.where(np.asarray(col_mask, bool)[..., None, :], out, pad)
    return out


def auction_assignment(
    cost: np.ndarray,
    maximize: bool = False,
    row_mask: np.ndarray | None = None,
    col_mask: np.ndarray | None = None,
    use_kernel: bool | None = None,
):
    """Numpy-friendly wrapper returning (row_ind, col_ind) like scipy.

    Handles rectangular instances, ``row_mask``/``col_mask`` padding, and
    non-finite (forbidden) entries via the square embedding of
    :func:`masked_square_benefit`; pairs landing on padded / forbidden
    cells are dropped from the returned assignment.
    """
    cost = np.asarray(cost, dtype=np.float64)
    n, m = cost.shape
    sq = masked_square_benefit(cost, maximize, row_mask, col_mask)
    res = auction_lap(jnp.asarray(sq), use_kernel=use_kernel)
    col_of = np.asarray(res.col_of)  # tessalint: sync-ok(single readout of the finished assignment; this wrapper's contract is scipy-style host output)
    row_ind = np.arange(sq.shape[0])
    ok = (row_ind < n) & (col_of < m) & (col_of >= 0)
    if row_mask is not None:
        ok &= np.asarray(row_mask, bool)[np.minimum(row_ind, n - 1)]
    if col_mask is not None:
        ok &= np.asarray(col_mask, bool)[np.minimum(col_of, m - 1)]
    row_ind, col_ind = row_ind[ok], col_of[ok]
    real = np.isfinite(cost[row_ind, col_ind])
    return row_ind[real], col_ind[real]
