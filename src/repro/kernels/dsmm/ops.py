"""Jit'd wrapper + runtime slot encoder for the dynamic sparse kernel."""
from __future__ import annotations

import jax.numpy as jnp

from repro.core.dynamic_sparse import DynamicOperand
from repro.kernels.dsmm.dsmm import dsmm_call
from repro.kernels.tiling import SUBLANE, lane_padded, pad_to


def _encode_slots(op: DynamicOperand):
    """Runtime re-partitioning (the paper's dynamic distribution phase):

    1. prepend one zero 'coverage' slot per output block-row so every
       output tile is written even if a row has no non-zeros this step;
    2. stable-sort all slots by row so the kernel's accumulate/flush walk
       is valid for *any* runtime pattern.
    """
    mb, _ = op.grid
    b = op.block_size
    cov_rows = jnp.arange(mb, dtype=jnp.int32)
    rows = jnp.concatenate([cov_rows, op.row_idx])
    cols = jnp.concatenate([jnp.zeros((mb,), jnp.int32), op.col_idx])
    vals = jnp.concatenate(
        [jnp.zeros((mb, b, b), op.values.dtype), op.values])
    order = jnp.argsort(rows, stable=True)
    return rows[order], cols[order], vals[order]


def slot_walk(rows, cols, vals, x, *, b: int, grid_m: int,
              tn: int | None = None, interpret: bool = False):
    """Run the slot-walk kernel on encoded slots.  ``n`` is padded to the
    lane tile; a block edge ``b`` that is not sublane-aligned is padded
    to the next multiple of 8 (zero rows/columns inside every block), so
    every block size the contract admits compiles."""
    bp = -(-b // SUBLANE) * SUBLANE
    if bp != b:
        k, n = x.shape
        vals = pad_to(pad_to(vals, 1, bp), 2, bp)
        x = pad_to(x.reshape(k // b, b, n), 1, bp).reshape(-1, n)
    y = lane_padded(lambda xp, tn: dsmm_call(
        rows, cols, vals, xp, b=bp, tn=tn, grid_m=grid_m,
        interpret=interpret), x, tn)
    if bp != b:
        y = y.reshape(grid_m, bp, -1)[:, :b].reshape(grid_m * b, -1)
    return y


def dsmm(op: DynamicOperand, x, *, tn: int | None = None,
         interpret: bool = False):
    """Dynamic SpMM ``Y = decode(op) @ X`` through the Pallas kernel."""
    m, _ = op.shape
    b = op.block_size
    rows, cols, vals = _encode_slots(op)
    return slot_walk(rows, cols, vals, x, b=b, grid_m=m // b, tn=tn,
                     interpret=interpret)
