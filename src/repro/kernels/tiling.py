"""Tile choice shared by the ops wrappers.

Mosaic accepts a block whose last two dimensions are multiples of
``(8, 128)``, or that spans the whole array dimension.  The wrappers
therefore never shrink a tile below that alignment to make it divide an
awkward length (a prefill of 1023 tokens once got a tile of 1):
a dimension is either taken whole, or zero-padded up to a multiple of
an aligned tile and the result sliced back.  Zero rows and columns add
nothing to a contraction, so padding never changes a result.
"""
from __future__ import annotations

import jax.numpy as jnp

LANE = 128      # minor-dimension alignment of a block
SUBLANE = 8     # second-minor alignment of a block


def dim_tile(n: int) -> tuple[int, int]:
    """``(tile, padded_n)`` for a dimension of length ``n``: one whole
    block when ``n <= LANE``, else ``LANE``-wide tiles over ``n``
    rounded up to a multiple of ``LANE``."""
    if n <= LANE:
        return n, n
    return LANE, -(-n // LANE) * LANE


def pad_to(x, axis: int, size: int):
    """Zero-pad ``x`` along ``axis`` up to ``size`` (no-op when equal)."""
    extra = size - x.shape[axis]
    if extra == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, extra)
    return jnp.pad(x, widths)


def lane_padded(call, x, tn=None):
    """``call(x_padded, tn)`` for ``x [k, n]`` with ``n`` (the lane axis)
    zero-padded to a multiple of the tile ``tn`` (default
    ``dim_tile(n)``); the ``[m, n_padded]`` result is sliced back to
    ``n`` columns."""
    n = x.shape[-1]
    tn = tn or dim_tile(n)[0]
    n_pad = -(-n // tn) * tn
    y = call(pad_to(x, 1, n_pad), tn)
    return y[:, :n] if n_pad != n else y
