"""Jit'd wrapper for the static block-sparse matmul kernel."""
from __future__ import annotations

import jax.numpy as jnp

from repro.core.bsr import BlockSparseMatrix
from repro.core.partitioner import (BalancedPacking, PackedTiles,
                                    PackingPlan, pack_values, plan_packing,
                                    plan_packing_balanced)
from repro.kernels.bsmm.balanced import bsmm_balanced_call
from repro.kernels.bsmm.bsmm import bsmm_call
from repro.kernels.tiling import SUBLANE, dim_tile, lane_padded


def _block_tile(dim: int, b: int) -> int:
    """MXU-aligned block-multiple divisor of ``dim`` (shrunk for small
    problems); the whole ``dim`` when that divisor is not
    sublane-aligned."""
    t = min(128, dim) if dim % 128 else 128
    t = max(b, t - t % b)
    while dim % t:
        t //= 2
    return t if t == dim or t % SUBLANE == 0 else dim


def tile_shape(m: int, k: int, b: int):
    """``(tm, tk)``: block-multiple row/contraction tiles dividing ``m``
    and ``k``.  ``n`` has no part in them, so one packed tile stack
    serves every ``n``."""
    return _block_tile(m, b), _block_tile(k, b)


def _pick_tiles(m: int, k: int, n: int, b: int):
    """``(tm, tk, tn)``: ``tile_shape``, and ``tn`` from
    ``tiling.dim_tile`` (the executors pad ``n`` to a multiple of it)."""
    return (*tile_shape(m, k, b), dim_tile(n)[0])


def _packed(meta: PackingPlan, payload: PackedTiles):
    """The tile stack of a pre-packed payload, checked against the
    plan's layout (a stack packed for another pattern or tile size
    would be read in the wrong order)."""
    want = (meta.num_tiles + 1, meta.tm, meta.tk)
    if tuple(payload.tiles.shape) != want:
        raise ValueError(
            f"packed tiles {tuple(payload.tiles.shape)} do not match this "
            f"plan's layout {want}: pack the values of this pattern with "
            f"sparse.pack")
    return payload.tiles


def bsmm_from_plan(meta: PackingPlan, values, x, *, tn: int | None = None,
                   interpret: bool = False):
    """SpMM from a one-time ``partitioner.plan_packing`` analysis: the
    pattern metadata is a baked host constant, only the value relayout
    (``pack_values``) runs per call -- unless ``values`` is a
    ``PackedTiles`` payload, packed once for fixed weights, which goes
    to the kernel as it is.  This is the ``repro.sparse`` plan-execute
    path for the ``static_pallas`` route."""
    if isinstance(values, PackedTiles):
        tiles = _packed(meta, values)
    else:
        tiles = pack_values(meta, values)
    return lane_padded(
        lambda xp, tn: bsmm_call(
            jnp.asarray(meta.tile_rows), jnp.asarray(meta.tile_cols), tiles,
            xp, tm=meta.tm, tk=meta.tk, tn=tn, grid_m=meta.grid[0],
            interpret=interpret), x, tn)


def bsmm_balanced_from_plan(meta: BalancedPacking, values, x, *,
                            tn: int | None = None,
                            interpret: bool = False):
    """SpMM from a one-time ``partitioner.plan_packing_balanced``
    analysis (the ``static_balanced`` route's plan-execute path): the
    row-swizzled visit schedule is a baked host constant; per call only
    the value relayout (``pack_values``, identical to the uniform
    route's) plus the appended zero pad tile run -- neither for a
    ``PackedTiles`` payload, which already ends in the pad tile."""
    base = meta.base
    if isinstance(values, PackedTiles):
        tiles = _packed(base, values)
    else:
        tiles = pack_values(base, values)
        tiles = jnp.concatenate(
            [tiles, jnp.zeros((1, base.tm, base.tk), tiles.dtype)])
    return lane_padded(
        lambda xp, tn: bsmm_balanced_call(
            jnp.asarray(meta.visit_rows), jnp.asarray(meta.visit_cols),
            jnp.asarray(meta.visit_slot), tiles, xp, tm=base.tm,
            tk=base.tk, tn=tn, grid_m=base.grid[0], interpret=interpret),
        x, tn)


def bsmm_balanced(bsr: BlockSparseMatrix, x, *, tm: int | None = None,
                  tk: int | None = None, tn: int | None = None,
                  num_bins: int | None = None, interpret: bool = False):
    """One-shot convenience: balanced plan + multiply.  ``x: [k, n]``."""
    if not bsr.is_static:
        raise ValueError("bsmm_balanced requires a static pattern")
    m, k = bsr.shape
    n = x.shape[-1]
    atm, atk, atn = _pick_tiles(m, k, n, bsr.block_size)
    meta = plan_packing_balanced(bsr.row_idx, bsr.col_idx, bsr.shape,
                                 bsr.block_size, tm or atm, tk or atk,
                                 num_bins=num_bins)
    return bsmm_balanced_from_plan(meta, bsr.values, x, tn=tn or atn,
                                   interpret=interpret)


def bsmm(bsr: BlockSparseMatrix, x, *, tm: int | None = None,
         tk: int | None = None, tn: int | None = None,
         interpret: bool = False):
    """One-shot convenience: pack + multiply.  ``x: [k, n]``."""
    if not bsr.is_static:
        raise ValueError("bsmm requires a static pattern (use dsmm)")
    m, k = bsr.shape
    n = x.shape[-1]
    atm, atk, atn = _pick_tiles(m, k, n, bsr.block_size)
    meta = plan_packing(bsr.row_idx, bsr.col_idx, bsr.shape,
                        bsr.block_size, tm or atm, tk or atk)
    return bsmm_from_plan(meta, bsr.values, x, tn=tn or atn,
                          interpret=interpret)
