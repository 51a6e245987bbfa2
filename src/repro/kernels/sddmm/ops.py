"""Jit'd wrapper for the grouped SDDMM kernel (the ``sddmm_grouped``
backward dispatch route).

``grouped_sddmm`` consumes the same one-time pattern analysis the static
forward routes use (``partitioner.plan_packing``): the non-empty tile
list becomes the kernel grid, and the per-block slot/offset metadata
extracts the ``[nnz, b, b]`` value gradient from the computed tile
stack.  Everything pattern-dependent is a host constant baked at plan
time -- the backward face of the paper's compile-time contract.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.core.partitioner import PackingPlan
from repro.kernels.sddmm.sddmm import sddmm_tiles_call
from repro.kernels.tiling import SUBLANE, dim_tile, pad_to


def sddmm_tile_size(m: int, k: int, b: int, limit: int = 128) -> int:
    """Largest square tile ``t <= limit`` that is a block-multiple
    divisor of both the ``m`` (dy rows) and ``k`` (x rows) extents --
    the same sizing rule as ``gmm.grouped_tile_size``, applied to the
    sampled-output grid."""
    t = b * max(1, limit // b)
    while t > b and (m % t or k % t):
        t -= b
    if m % t or k % t:
        raise ValueError(f"no tile size <= {limit} divides both m={m} "
                         f"and k={k} at block {b}")
    return t


def grouped_sddmm(meta: PackingPlan, dy, x, *, tn: int | None = None,
                  interpret: bool = False):
    """``dW[z] = dY_block[row[z]] @ X_block[col[z]]^T`` restricted to the
    pattern captured in ``meta`` (a square-tile ``plan_packing`` of the
    pattern over the ``(m, k)`` grid).

    dy: [M, N] upstream cotangent; x: [K, N] forward rhs.
    Returns [nnz, b, b] in ``meta``'s block order.
    """
    if meta.tm != meta.tk:
        raise ValueError(f"grouped_sddmm needs square tiles, got "
                         f"({meta.tm}, {meta.tk})")
    t = meta.tm
    b = meta.block_size
    n = dy.shape[1]
    if x.shape[1] != n:
        raise ValueError(f"dy cols {n} != x cols {x.shape[1]}")
    # zero columns of the contraction (n) axis add nothing; a tile edge
    # that is not sublane-aligned gets zero rows inside every tile
    tn = tn or dim_tile(n)[0]
    n_pad = -(-n // tn) * tn
    tp = -(-t // SUBLANE) * SUBLANE

    def prep(a):
        a = pad_to(a, 1, n_pad)
        if tp != t:
            a = pad_to(a.reshape(-1, t, n_pad), 1, tp).reshape(-1, n_pad)
        return a
    tiles = sddmm_tiles_call(jnp.asarray(meta.tile_rows, jnp.int32),
                             jnp.asarray(meta.tile_cols, jnp.int32),
                             prep(dy), prep(x), t=tp, tn=tn,
                             interpret=interpret)[:, :t, :t]
    # host-metadata block extraction: tile stack -> [nnz, b, b] values
    rpb = t // b
    blocked = tiles.reshape(meta.num_tiles, rpb, b, rpb, b)
    return blocked[jnp.asarray(meta.block_slot),
                   jnp.asarray(np.asarray(meta.in_r)),
                   :, jnp.asarray(np.asarray(meta.in_c)), :]
