"""Balanced-walk grouped SpMM (the ``dynamic_grouped_balanced`` route).

``grouped_spmm`` hands the packed tile slots to the dsmm walk in
tile-sorted (row-major) order: on a skewed runtime pattern one hot
row-tile owns a long run of consecutive slots, and the walk serializes
on that run exactly like the static uniform walk does.  This variant
re-sorts the slots by a device-side row-swizzle -- the runtime analogue
of ``partitioner.plan_swizzle``: row-tiles are snake-binned by their
(runtime) tile counts and slots are ordered bin-contiguously, rows
ascending within a bin, so consecutive same-row runs are bounded by the
per-bin load instead of the hottest row's total.

Everything is jnp on runtime indices (jit-safe, no host metadata): the
dynamic-mode pendant of the static route's free plan-time swizzle, and
the same trade the paper makes for dynamic sparsity everywhere else --
the balance analysis itself costs device work per call.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.core.dynamic_sparse import DynamicOperand
from repro.kernels.dsmm.ops import slot_walk
from repro.kernels.gmm.ops import (clamped_tiles_cap, grouped_tile_size,
                                   pack_tiles_device)


def _encode_slots_balanced(op: DynamicOperand, num_bins: int):
    """Coverage slots + row-swizzled slot order (device-side).

    1. prepend one zero 'coverage' slot per output row-tile (identical
       to ``dsmm._encode_slots``) so every output tile is written;
    2. snake-bin row-tiles by their *valid* slot counts (descending),
       then stable-sort all slots by ``(bin, row)`` -- the walk stays
       row-contiguous (each row lives in exactly one bin), so the
       accumulate/flush invariant holds unchanged.
    """
    mt, _ = op.grid
    b = op.block_size
    nb = max(1, min(int(num_bins), mt))
    valid = jnp.arange(op.capacity) < op.nnz
    counts = jnp.zeros((mt,), jnp.int32).at[op.row_idx].add(
        valid.astype(jnp.int32))
    order_desc = jnp.argsort(-counts)
    i = jnp.arange(mt)
    pos, rnd = i % nb, i // nb
    dealt = jnp.where(rnd % 2 == 0, pos, nb - 1 - pos).astype(jnp.int32)
    bin_of_row = jnp.zeros((mt,), jnp.int32).at[order_desc].set(dealt)

    cov_rows = jnp.arange(mt, dtype=jnp.int32)
    rows = jnp.concatenate([cov_rows, op.row_idx])
    cols = jnp.concatenate([jnp.zeros((mt,), jnp.int32), op.col_idx])
    vals = jnp.concatenate(
        [jnp.zeros((mt, b, b), op.values.dtype), op.values])
    key = bin_of_row[rows] * jnp.int32(mt + 1) + rows
    order = jnp.argsort(key, stable=True)
    return rows[order], cols[order], vals[order]


def balanced_spmm(op: DynamicOperand, x, *, tile: int | None = None,
                  tiles_cap: int | None = None, num_bins: int = 8,
                  interpret: bool = False, return_stats: bool = False):
    """``Y = decode(op) @ X`` through device-side tile packing + the
    row-swizzled slot walk (the ``dynamic_grouped_balanced`` route).

    Capacity semantics (planned bucket, exact overflow accounting) are
    identical to ``grouped_spmm`` -- the pack is shared; only the slot
    visit order differs.
    """
    m, k = op.shape
    t = tile or grouped_tile_size(m, k, op.block_size)
    mt, kt = m // t, k // t
    if tiles_cap is None:
        tiles_cap = min(op.capacity, mt * kt)
    else:
        tiles_cap, _ = clamped_tiles_cap(tiles_cap, m, k, t)
    tiles_cap = max(1, tiles_cap)
    packed, stats = pack_tiles_device(op, tile=t, tiles_cap=tiles_cap,
                                      with_stats=return_stats)
    rows, cols, vals = _encode_slots_balanced(packed, num_bins)
    y = slot_walk(rows, cols, vals, x, b=t, grid_m=m // t,
                  interpret=interpret)
    if return_stats:
        return y, stats
    return y
