"""Pallas kernel: auction bid step — masked row-wise top-2 reduction.

Given the benefit matrix ``a`` (n, m) and prices ``p`` (m,), each
*unassigned person* (row) needs, per auction round:

    vals[i, j] = a[i, j] - p[j]
    best_v[i]  = max_j vals[i, j]
    best_j[i]  = argmax_j vals[i, j]
    second[i]  = max_{j != best_j} vals[i, j]

TPU mapping: the matrix streams HBM->VMEM in (BLOCK_ROWS x BLOCK_COLS)
tiles; the grid is (rows/BLOCK_ROWS, cols/BLOCK_COLS) with the column axis
minor (sequential on TPU), so each row-block keeps a running (top-1, arg,
top-2) carry in VMEM scratch across column tiles.  Blocks are 128-aligned
for the VPU lanes; a (128, 512) f32 tile is 256 KiB — far under the ~16 MiB
v5e VMEM budget even with double buffering.

Padding-free bids: the grid covers only the *real* columns (rounded up to
one tile); the ragged tile edge is masked **in-kernel** against global
column ids via :mod:`repro.kernels.tile_mask` (shared with
``flash_decode``), so the host-side padding is plain ``jnp.pad`` zeros —
no NEG_INF-filled copy of the benefit matrix is ever materialised, and a
rectangular (n, m) instance costs O(n * m) bid work, never O(max(n, m)^2).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tile_mask import mask_ragged_cols, tile_col_ids

NEG_INF = -1e30

BLOCK_ROWS = 128
BLOCK_COLS = 512

# f32 min tile is (8, 128) (sublane x lane); small instances — the k_l x k_l
# node-pair LAPs are 4x4-8x8 — shrink to one min tile instead of padding to
# the full (128, 512) block (a 4096x compute blowup per instance).
MIN_BLOCK_ROWS = 8
MIN_BLOCK_COLS = 128


def _resolve_interpret(interpret: bool | None) -> bool:
    """None = auto: compiled on TPU, interpret mode on CPU/GPU hosts."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)


def _block_dims(n: int, m: int) -> tuple[int, int]:
    """Largest-useful (block_rows, block_cols) for an (n, m) instance:
    tile-aligned, never larger than the default blocks, never smaller than
    the f32 min tile."""
    br = min(BLOCK_ROWS, max(MIN_BLOCK_ROWS, -(-n // MIN_BLOCK_ROWS) * MIN_BLOCK_ROWS))
    bc = min(BLOCK_COLS, max(MIN_BLOCK_COLS, -(-m // MIN_BLOCK_COLS) * MIN_BLOCK_COLS))
    return br, bc


def _tile_top2(vals, col_offset):
    """(best, arg, second) of one (BR, BC) tile, args in global columns.

    ``arg`` is the FIRST column holding the row maximum — ``jnp.argmax``'s
    rule, spelled out because the TPU lowering of ``argmax`` inside a
    kernel does not promise which of several equal maxima it returns (on
    a v5e the fused migrate program's plans then differed from the jnp
    bid path's on tied costs)."""
    col_ids = tile_col_ids(vals.shape, col_offset)
    tile_best = jnp.max(vals, axis=1, keepdims=True)  # (BR, 1)
    tile_arg = jnp.min(
        jnp.where(vals == tile_best, col_ids, jnp.iinfo(jnp.int32).max),
        axis=1,
        keepdims=True,
    )
    masked = jnp.where(col_ids == tile_arg, NEG_INF, vals)
    tile_second = jnp.max(masked, axis=1, keepdims=True)
    return tile_best, tile_arg, tile_second


def _merge_top2(run, tile):
    """Merge two (top1, arg, top2) summaries; the RUNNING (earlier-tile)
    summary wins ties so the argmax matches jnp.argmax's
    first-occurrence rule."""
    run_best, run_arg, run_second = run
    tile_best, tile_arg, tile_second = tile
    new_best = jnp.where(tile_best > run_best, tile_best, run_best)
    new_arg = jnp.where(tile_best > run_best, tile_arg, run_arg)
    # second = max of the loser's best and both seconds
    loser_best = jnp.where(tile_best > run_best, run_best, tile_best)
    new_second = jnp.maximum(loser_best, jnp.maximum(run_second, tile_second))
    return new_best, new_arg, new_second


def _bid_kernel(
    a_ref,      # (BR, BC) benefit tile
    p_ref,      # (1, BC) price tile
    best_v_ref,  # (BR, 1) out
    best_j_ref,  # (BR, 1) out int32
    second_ref,  # (BR, 1) out
    *,
    block_cols: int,
    valid_cols: int,
):
    ci = pl.program_id(1)
    vals = mask_ragged_cols(a_ref[...] - p_ref[...], ci * block_cols, valid_cols, NEG_INF)
    summary = _tile_top2(vals, ci * block_cols)

    @pl.when(ci == 0)
    def _init():
        best_v_ref[...], best_j_ref[...], second_ref[...] = summary

    @pl.when(ci > 0)
    def _accum():
        run = (best_v_ref[...], best_j_ref[...], second_ref[...])
        best_v_ref[...], best_j_ref[...], second_ref[...] = _merge_top2(
            run, summary
        )


def _bid_kernel_batched(
    a_ref,      # (1, BR, BC) benefit tile of one batch instance
    p_ref,      # (1, 1, BC) price tile
    best_v_ref,  # (1, BR, 1) out
    best_j_ref,  # (1, BR, 1) out int32
    second_ref,  # (1, BR, 1) out
    *,
    block_cols: int,
    valid_cols: int,
):
    """Batched variant of :func:`_bid_kernel` (same tile summary + merge).

    The grid is (batch, rows/BLOCK_ROWS, cols/BLOCK_COLS) with the column
    axis minor; the leading batch axis maps one grid step per instance so a
    single ``pallas_call`` covers a whole instance stack.  This is the
    explicit counterpart of what ``jax.vmap`` over :func:`lap_bid_pallas`
    produces via the lifted pallas batching rule (the path the batched
    auction actually takes); it exists for direct 3-D callers and as a
    parity oracle for that lifted path.
    """
    ci = pl.program_id(2)
    vals = mask_ragged_cols(a_ref[0] - p_ref[0], ci * block_cols, valid_cols, NEG_INF)
    summary = _tile_top2(vals, ci * block_cols)

    @pl.when(ci == 0)
    def _init():
        best_v_ref[0], best_j_ref[0], second_ref[0] = summary

    @pl.when(ci > 0)
    def _accum():
        run = (best_v_ref[0], best_j_ref[0], second_ref[0])
        best_v_ref[0], best_j_ref[0], second_ref[0] = _merge_top2(run, summary)


def lap_bid_pallas_batched(
    a: jax.Array, prices: jax.Array, interpret: bool | None = None
):
    """Batched bid step: ``a`` (B, n, m), ``prices`` (B, m).

    Returns (best_v, best_j, second_v), each (B, n).  Same padding contract
    as :func:`lap_bid_pallas`; the batch axis becomes the leading (major)
    grid dimension, so column tiles still run sequentially per instance and
    the running top-2 carry in the output refs stays per-instance.
    ``interpret=None`` resolves automatically: compiled on TPU, interpret
    mode elsewhere (the previous hard default of True silently ran the
    interpreter on TPU when callers forgot the flag).
    """
    return _lap_bid_pallas_batched_jit(a, prices, _resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _lap_bid_pallas_batched_jit(a: jax.Array, prices: jax.Array, interpret: bool):
    b, n, m = a.shape
    br, bc = _block_dims(n, m)
    n_pad = (n + br - 1) // br * br
    m_pad = (m + bc - 1) // bc * bc
    # zero padding only — the ragged edge is masked in-kernel by column id
    a_p = jnp.pad(a, ((0, 0), (0, n_pad - n), (0, m_pad - m)))
    p_p = jnp.pad(prices, ((0, 0), (0, m_pad - m)))[:, None, :]

    grid = (b, n_pad // br, m_pad // bc)
    best_v, best_j, second = pl.pallas_call(
        functools.partial(_bid_kernel_batched, block_cols=bc, valid_cols=m),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, br, bc), lambda bi, ri, ci: (bi, ri, ci)),
            pl.BlockSpec((1, 1, bc), lambda bi, ri, ci: (bi, 0, ci)),
        ],
        out_specs=[
            pl.BlockSpec((1, br, 1), lambda bi, ri, ci: (bi, ri, 0)),
            pl.BlockSpec((1, br, 1), lambda bi, ri, ci: (bi, ri, 0)),
            pl.BlockSpec((1, br, 1), lambda bi, ri, ci: (bi, ri, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, n_pad, 1), a.dtype),
            jax.ShapeDtypeStruct((b, n_pad, 1), jnp.int32),
            jax.ShapeDtypeStruct((b, n_pad, 1), a.dtype),
        ],
        interpret=interpret,
    )(a_p, p_p)
    return best_v[:, :n, 0], best_j[:, :n, 0], second[:, :n, 0]


def _fused_vals(cost_tile, price_tile, tb_scale, row_base, col_offset):
    """In-kernel benefit assembly for one tile:

        vals[i, j] = -cost[i, j] + tb_scale * (gi+1)^2 * (gj+1) - p[j]

    with ``gi``/``gj`` the GLOBAL row/column indices — the positional
    tie-break ramp of ``engine._tie_break_perturb`` (identity ranks ==
    positions when ids increase with position, as the migration fan-out's
    slot/node ids do).  ``tb_scale = 0`` degenerates to the plain bid.

    Exactness: ``tb_scale`` is a power of two and ``(gi+1)^2 * (gj+1)`` an
    integer, so for instances with ``n^2 * m < 2^24`` (every fan-out pair
    LAP and any node match below ~256 nodes) the ramp term is exact in f32
    and the assembled value is bit-identical to the host path's
    f64-assemble-then-cast — the fused auction's plans can then be
    compared bit-for-bit against the host engine.
    """
    shape = cost_tile.shape
    gi = (
        jax.lax.broadcasted_iota(jnp.int32, shape, len(shape) - 2) + row_base + 1
    ).astype(cost_tile.dtype)
    gj = (tile_col_ids(shape, col_offset) + 1).astype(cost_tile.dtype)
    return (tb_scale * (gi * gi) * gj - cost_tile) - price_tile


def _bid_fused_kernel(
    a_ref,      # (BR, BC) COST tile (not benefit)
    p_ref,      # (1, BC) price tile
    tb_ref,     # (1, 1) tie-break scale
    best_v_ref,  # (BR, 1) out
    best_j_ref,  # (BR, 1) out int32
    second_ref,  # (BR, 1) out
    *,
    block_rows: int,
    block_cols: int,
    valid_cols: int,
):
    ri = pl.program_id(0)
    ci = pl.program_id(1)
    vals = _fused_vals(
        a_ref[...], p_ref[...], tb_ref[0, 0], ri * block_rows, ci * block_cols
    )
    vals = mask_ragged_cols(vals, ci * block_cols, valid_cols, NEG_INF)
    summary = _tile_top2(vals, ci * block_cols)

    @pl.when(ci == 0)
    def _init():
        best_v_ref[...], best_j_ref[...], second_ref[...] = summary

    @pl.when(ci > 0)
    def _accum():
        run = (best_v_ref[...], best_j_ref[...], second_ref[...])
        best_v_ref[...], best_j_ref[...], second_ref[...] = _merge_top2(run, summary)


def _bid_fused_kernel_batched(
    a_ref,      # (1, BR, BC) cost tile of one batch instance
    p_ref,      # (1, 1, BC) price tile
    tb_ref,     # (1, 1) per-instance tie-break scale
    best_v_ref,  # (1, BR, 1) out
    best_j_ref,  # (1, BR, 1) out int32
    second_ref,  # (1, BR, 1) out
    *,
    block_rows: int,
    block_cols: int,
    valid_cols: int,
):
    ri = pl.program_id(1)
    ci = pl.program_id(2)
    vals = _fused_vals(
        a_ref[0], p_ref[0], tb_ref[0, 0], ri * block_rows, ci * block_cols
    )
    vals = mask_ragged_cols(vals, ci * block_cols, valid_cols, NEG_INF)
    summary = _tile_top2(vals, ci * block_cols)

    @pl.when(ci == 0)
    def _init():
        best_v_ref[0], best_j_ref[0], second_ref[0] = summary

    @pl.when(ci > 0)
    def _accum():
        run = (best_v_ref[0], best_j_ref[0], second_ref[0])
        best_v_ref[0], best_j_ref[0], second_ref[0] = _merge_top2(run, summary)


def lap_bid_fused_pallas(
    cost: jax.Array,
    prices: jax.Array,
    tb_scale: jax.Array | float = 0.0,
    interpret: bool | None = None,
):
    """Fused-benefit bid step: ``cost`` (n, m) raw COST matrix.

    The benefit — ``-cost`` plus the positional tie-break ramp — is
    assembled inside the kernel's tiled sweep (see :func:`_fused_vals`),
    so the auction driver never materialises the perturbed (n, m) benefit
    in HBM at all: one cost upload serves every bid round, and only the
    (m,) price vector changes between rounds.  Same padding contract and
    return shape as :func:`lap_bid_pallas`.
    """
    return _lap_bid_fused_jit(
        cost,
        prices,
        jnp.asarray(tb_scale, cost.dtype).reshape(1, 1),
        _resolve_interpret(interpret),
    )


@functools.partial(jax.jit, static_argnames=("interpret",))
def _lap_bid_fused_jit(
    cost: jax.Array, prices: jax.Array, tb_scale: jax.Array, interpret: bool
):
    n, m = cost.shape
    br, bc = _block_dims(n, m)
    n_pad = (n + br - 1) // br * br
    m_pad = (m + bc - 1) // bc * bc
    a_p = jnp.pad(cost, ((0, n_pad - n), (0, m_pad - m)))
    p_p = jnp.pad(prices, (0, m_pad - m))[None, :]

    grid = (n_pad // br, m_pad // bc)
    best_v, best_j, second = pl.pallas_call(
        functools.partial(
            _bid_fused_kernel, block_rows=br, block_cols=bc, valid_cols=m
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((br, bc), lambda ri, ci: (ri, ci)),
            pl.BlockSpec((1, bc), lambda ri, ci: (0, ci)),
            pl.BlockSpec((1, 1), lambda ri, ci: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((br, 1), lambda ri, ci: (ri, 0)),
            pl.BlockSpec((br, 1), lambda ri, ci: (ri, 0)),
            pl.BlockSpec((br, 1), lambda ri, ci: (ri, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_pad, 1), cost.dtype),
            jax.ShapeDtypeStruct((n_pad, 1), jnp.int32),
            jax.ShapeDtypeStruct((n_pad, 1), cost.dtype),
        ],
        interpret=interpret,
    )(a_p, p_p, tb_scale)
    return best_v[:n, 0], best_j[:n, 0], second[:n, 0]


def lap_bid_fused_pallas_batched(
    cost: jax.Array,
    prices: jax.Array,
    tb_scale: jax.Array | float = 0.0,
    interpret: bool | None = None,
):
    """Batched fused-benefit bid step: ``cost`` (B, n, m), ``prices``
    (B, m), ``tb_scale`` scalar or (B,) per instance.  Returns
    (best_v, best_j, second_v), each (B, n) — the bid path of the fused
    migration fan-out, where all pair LAPs share one cost upload and the
    tie-break ramp never exists as data."""
    b = cost.shape[0]
    tb = jnp.broadcast_to(
        jnp.asarray(tb_scale, cost.dtype).reshape(-1), (b,)
    ).reshape(b, 1)
    return _lap_bid_fused_batched_jit(cost, prices, tb, _resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _lap_bid_fused_batched_jit(
    cost: jax.Array, prices: jax.Array, tb_scale: jax.Array, interpret: bool
):
    b, n, m = cost.shape
    br, bc = _block_dims(n, m)
    n_pad = (n + br - 1) // br * br
    m_pad = (m + bc - 1) // bc * bc
    a_p = jnp.pad(cost, ((0, 0), (0, n_pad - n), (0, m_pad - m)))
    p_p = jnp.pad(prices, ((0, 0), (0, m_pad - m)))[:, None, :]

    grid = (b, n_pad // br, m_pad // bc)
    best_v, best_j, second = pl.pallas_call(
        functools.partial(
            _bid_fused_kernel_batched, block_rows=br, block_cols=bc, valid_cols=m
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, br, bc), lambda bi, ri, ci: (bi, ri, ci)),
            pl.BlockSpec((1, 1, bc), lambda bi, ri, ci: (bi, 0, ci)),
            pl.BlockSpec((1, 1), lambda bi, ri, ci: (bi, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, br, 1), lambda bi, ri, ci: (bi, ri, 0)),
            pl.BlockSpec((1, br, 1), lambda bi, ri, ci: (bi, ri, 0)),
            pl.BlockSpec((1, br, 1), lambda bi, ri, ci: (bi, ri, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, n_pad, 1), cost.dtype),
            jax.ShapeDtypeStruct((b, n_pad, 1), jnp.int32),
            jax.ShapeDtypeStruct((b, n_pad, 1), cost.dtype),
        ],
        interpret=interpret,
    )(a_p, p_p, tb_scale)
    return best_v[:, :n, 0], best_j[:, :n, 0], second[:, :n, 0]


def lap_bid_pallas(a: jax.Array, prices: jax.Array, interpret: bool | None = None):
    """Returns (best_v, best_j, second_v), each (n,).

    ``a`` may be rectangular (n, m); the grid covers only the real columns
    (rounded up to one tile) and the ragged edge is masked in-kernel, so
    padding is plain zeros (callers guarantee m >= 2 real columns).
    ``interpret=None`` resolves automatically (see
    :func:`lap_bid_pallas_batched`).
    """
    return _lap_bid_pallas_jit(a, prices, _resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _lap_bid_pallas_jit(a: jax.Array, prices: jax.Array, interpret: bool):
    n, m = a.shape
    br, bc = _block_dims(n, m)
    n_pad = (n + br - 1) // br * br
    m_pad = (m + bc - 1) // bc * bc
    a_p = jnp.pad(a, ((0, n_pad - n), (0, m_pad - m)))
    p_p = jnp.pad(prices, (0, m_pad - m))[None, :]

    grid = (n_pad // br, m_pad // bc)
    best_v, best_j, second = pl.pallas_call(
        functools.partial(_bid_kernel, block_cols=bc, valid_cols=m),
        grid=grid,
        in_specs=[
            pl.BlockSpec((br, bc), lambda ri, ci: (ri, ci)),
            pl.BlockSpec((1, bc), lambda ri, ci: (0, ci)),
        ],
        out_specs=[
            pl.BlockSpec((br, 1), lambda ri, ci: (ri, 0)),
            pl.BlockSpec((br, 1), lambda ri, ci: (ri, 0)),
            pl.BlockSpec((br, 1), lambda ri, ci: (ri, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_pad, 1), a.dtype),
            jax.ShapeDtypeStruct((n_pad, 1), jnp.int32),
            jax.ShapeDtypeStruct((n_pad, 1), a.dtype),
        ],
        interpret=interpret,
    )(a_p, p_p)
    return best_v[:n, 0], best_j[:n, 0], second[:n, 0]
