"""Jit'd public wrappers around the Pallas kernels.

Each wrapper runs its kernel compiled on TPU and in interpret mode
elsewhere (:func:`default_interpret`; the tests run on the CPU), with
semantics identical to its ``ref.py`` oracle.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.lap_bid import (
    lap_bid_fused_pallas,
    lap_bid_fused_pallas_batched,
    lap_bid_pallas,
    lap_bid_pallas_batched,
)
from repro.kernels.migration_cost import migration_cost_pallas


def default_interpret() -> bool:
    """True (interpret mode) unless the default backend is a TPU."""
    return jax.default_backend() != "tpu"


def _require(cond: bool, msg: str) -> None:
    """Shape/dtype contract check.  Runs against static metadata only, so
    under jit it fires at trace time and costs nothing per call."""
    if not cond:
        raise ValueError(msg)


def _check_bid_args(name: str, mat: jax.Array, prices: jax.Array) -> None:
    _require(
        mat.ndim in (2, 3),
        f"{name}: matrix must be (n, m) or (B, n, m), got shape {mat.shape}",
    )
    _require(
        jnp.issubdtype(mat.dtype, jnp.floating),
        f"{name}: matrix must be floating, got dtype {mat.dtype}",
    )
    want = (
        (mat.shape[0], mat.shape[-1]) if mat.ndim == 3 else (mat.shape[-1],)
    )
    _require(
        tuple(prices.shape) == want,
        f"{name}: prices shape {prices.shape} does not match matrix "
        f"{mat.shape} (want {want})",
    )


def lap_bid_top2(vals: jax.Array):
    """Auction bid step on a precomputed (benefit - price) matrix.

    Drop-in replacement for ``ref.lap_bid_top2`` (kept as the parity-test
    oracle surface; ``auction_lap(use_kernel=True)`` now calls
    :func:`lap_bid` directly so the price subtraction fuses into the
    kernel's tiled sweep instead of materialising ``vals`` per bid
    round).  Accepts (n, m) or an explicit (B, n, m) stack, which routes
    to :func:`lap_bid_pallas_batched`.
    NOTE: the auction fan-out does NOT reach the 3-D branch — under
    ``jax.vmap`` each instance is a 2-D tracer and vmap's pallas batching
    rule lifts the 2-D kernel into one batched ``pallas_call`` itself;
    the explicit branch serves direct 3-D callers and parity tests.

    Shapes: ``vals`` (n, m) or (B, n, m), floating.  Returns
    ``(best_v, best_j, second_v)``, each (n,) / (B, n).
    """
    _require(
        vals.ndim in (2, 3),
        f"lap_bid_top2: vals must be (n, m) or (B, n, m), got shape {vals.shape}",
    )
    _require(
        jnp.issubdtype(vals.dtype, jnp.floating),
        f"lap_bid_top2: vals must be floating, got dtype {vals.dtype}",
    )
    if vals.ndim == 3:
        return lap_bid_pallas_batched(
            vals,
            jnp.zeros(vals.shape[::2], vals.dtype),
            interpret=default_interpret(),
        )
    return lap_bid_pallas(
        vals, jnp.zeros((vals.shape[-1],), vals.dtype), interpret=default_interpret()
    )


def lap_bid(a: jax.Array, prices: jax.Array):
    """Auction bid step on a BENEFIT matrix; prices subtract in-kernel.

    Shapes: ``a`` (n, m) with ``prices`` (m,), or batched ``a`` (B, n, m)
    with ``prices`` (B, m); both floating.  Returns
    ``(best_v, best_j, second_v)``, each (n,) / (B, n).
    """
    _check_bid_args("lap_bid", a, prices)
    if a.ndim == 3:
        return lap_bid_pallas_batched(a, prices, interpret=default_interpret())
    return lap_bid_pallas(a, prices, interpret=default_interpret())


def lap_bid_fused(cost: jax.Array, prices: jax.Array, tb_scale=0.0):
    """Fused-benefit bid step on a raw COST matrix (2-D or batched 3-D):
    the ``-cost`` negation and the positional tie-break ramp assemble
    inside the kernel's tiled sweep, so no perturbed benefit matrix is
    ever materialised in HBM (see ``lap_bid.lap_bid_fused_pallas``).
    ``tb_scale=0`` is the plain (un-perturbed) bid on ``-cost``.

    Shapes: ``cost`` (n, m) with ``prices`` (m,), or batched ``cost``
    (B, n, m) with ``prices`` (B, m); both floating.  ``tb_scale`` is a
    scalar (or (B,) when batched).  Returns ``(best_v, best_j, second_v)``,
    each (n,) / (B, n).
    """
    _check_bid_args("lap_bid_fused", cost, prices)
    if cost.ndim == 3:
        return lap_bid_fused_pallas_batched(
            cost, prices, tb_scale, interpret=default_interpret()
        )
    return lap_bid_fused_pallas(cost, prices, tb_scale, interpret=default_interpret())


def migration_cost_matrix(
    slots_u, slots_v, num_gpus_of: dict[int, int]
) -> np.ndarray:
    """Algorithm-3 cost matrix via the Pallas kernel.

    ``slots_u``/``slots_v``: (U, MAX_PACK) int arrays of job ids (-1 empty).
    Returns a host (U, V) float64 matrix.
    """
    slots_u = np.asarray(slots_u)
    slots_v = np.asarray(slots_v)
    _require(
        slots_u.ndim == 2 and slots_v.ndim == 2,
        "migration_cost_matrix: slots must be (U, MAX_PACK) / (V, MAX_PACK), "
        f"got shapes {slots_u.shape} and {slots_v.shape}",
    )
    _require(
        slots_u.shape[1] == slots_v.shape[1],
        "migration_cost_matrix: slots_u and slots_v disagree on MAX_PACK "
        f"({slots_u.shape[1]} vs {slots_v.shape[1]})",
    )
    _require(
        np.issubdtype(slots_u.dtype, np.integer)
        and np.issubdtype(slots_v.dtype, np.integer),
        "migration_cost_matrix: slots must hold integer job ids, got "
        f"dtypes {slots_u.dtype} and {slots_v.dtype}",
    )
    max_id = max(num_gpus_of, default=0)
    lookup = np.zeros(max_id + 2, dtype=np.float32)
    for j, g in num_gpus_of.items():
        lookup[j] = 1.0 / (2.0 * g)
    w_u = lookup[slots_u]  # EMPTY=-1 hits the zero tail
    w_v = lookup[slots_v]
    out = migration_cost_pallas(
        jnp.asarray(slots_u, jnp.int32),
        jnp.asarray(slots_v, jnp.int32),
        jnp.asarray(w_u),
        jnp.asarray(w_v),
        interpret=default_interpret(),
    )
    return np.asarray(out, dtype=np.float64)  # tessalint: sync-ok(this wrapper's documented contract is a host float64 matrix; one readout of the kernel output)


def flash_decode(q, k, v, valid_len):
    """Single-token GQA decode attention; q (B,H,D), cache k/v (B,S,KV,D).

    ``H`` must be a multiple of ``KV`` (query-head groups share a KV
    head); ``valid_len`` is (B,) integer occupancy of the ring buffer.
    Returns (B, H, D).
    """
    from repro.kernels.flash_decode import flash_decode_pallas

    _require(
        q.ndim == 3 and k.ndim == 4 and v.ndim == 4,
        f"flash_decode: want q (B,H,D), k/v (B,S,KV,D); got q {q.shape}, "
        f"k {k.shape}, v {v.shape}",
    )
    _require(
        k.shape == v.shape,
        f"flash_decode: k/v cache shapes differ ({k.shape} vs {v.shape})",
    )
    _require(
        q.shape[0] == k.shape[0] and q.shape[-1] == k.shape[-1],
        f"flash_decode: q {q.shape} and cache {k.shape} disagree on "
        "batch or head dim",
    )
    _require(
        q.shape[1] % k.shape[2] == 0,
        f"flash_decode: H={q.shape[1]} must be a multiple of KV={k.shape[2]}",
    )
    return flash_decode_pallas(q, k, v, valid_len, interpret=default_interpret())


def flash_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, causal: bool = True
) -> jax.Array:
    """Causal flash attention; q/k/v (B, H, S, D) or (BH, S, D).

    All three inputs must share one shape; returns that shape.
    """
    _require(
        q.ndim in (3, 4),
        f"flash_attention: q must be (B,H,S,D) or (BH,S,D), got {q.shape}",
    )
    _require(
        q.shape == k.shape == v.shape,
        f"flash_attention: q/k/v shapes differ: {q.shape}, {k.shape}, {v.shape}",
    )
    squeeze = False
    if q.ndim == 4:
        b, h, s, d = q.shape
        q = q.reshape(b * h, s, d)
        k = k.reshape(b * h, s, d)
        v = v.reshape(b * h, s, d)
        squeeze = True
    out = flash_attention_pallas(q, k, v, causal=causal, interpret=default_interpret())
    if squeeze:
        out = out.reshape(b, h, s, d)
    return out
