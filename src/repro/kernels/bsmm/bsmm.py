"""Static block-sparse matmul Pallas TPU kernel (PopSparse §3.2 on MXU).

Design (see DESIGN.md §2 for the IPU->TPU mapping):

* Logical ``b x b`` blocks are packed into MXU-aligned ``(tm, tk)`` tiles
  by ``partitioner.pack_tiles`` -- the compile-time value re-ordering of
  the paper.  ``tile_rows/tile_cols`` are **host constants**: the grid is
  sized to exactly the number of non-empty tiles, so the kernel performs
  zero wasted steps (the defining property of static sparsity).
* Grid = ``(N/tn, T)`` with the sparse-tile walk innermost.  Tiles are
  row-major sorted, so a VMEM accumulator carries partial sums while the
  output row-tile stays the same and flushes exactly once per (row, n)
  pair -- the "local dot product + final reduction" of paper Fig. 1a,
  with the reduction living in VMEM instead of IPU exchange.
* ``X`` tiles are fetched by a scalar-prefetch index map
  (``cols[s]``), i.e. the sparsity metadata drives the DMA schedule --
  the analogue of PopSparse pre-planning tile exchange at compile time.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _bsmm_kernel(rows_ref, cols_ref, a_ref, x_ref, o_ref, acc_ref):
    del cols_ref  # consumed by the index maps
    s = pl.program_id(1)
    t = pl.num_programs(1)

    @pl.when((s == 0) | (rows_ref[s] != rows_ref[jnp.maximum(s - 1, 0)]))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(a_ref[...], x_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when((s == t - 1) | (rows_ref[s] != rows_ref[jnp.minimum(s + 1, t - 1)]))
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tm", "tk", "tn", "grid_m",
                                             "interpret", "out_dtype"))
def bsmm_call(tile_rows, tile_cols, tiles, x, *, tm: int, tk: int, tn: int,
              grid_m: int, interpret: bool = False, out_dtype=None):
    """Raw kernel entry.

    tile_rows/cols: [T] int32 (host constants for static mode)
    tiles:          [T (+ pad), tm, tk] packed sparse tiles; the walk
                    visits the first T (trailing pad tiles are never read)
    x:              [K, N] dense operand
    returns         [grid_m * tm, N]
    """
    t = tile_rows.shape[0]
    k, n = x.shape
    out_dtype = out_dtype or x.dtype
    n_tiles = n // tn
    grid = (n_tiles, t)

    return pl.pallas_call(
        _bsmm_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((None, tm, tk),
                             lambda nj, s, rows, cols: (s, 0, 0)),
                pl.BlockSpec((tk, tn),
                             lambda nj, s, rows, cols: (cols[s], nj)),
            ],
            out_specs=pl.BlockSpec((tm, tn),
                                   lambda nj, s, rows, cols: (rows[s], nj)),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((grid_m * tm, n), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="bsmm_call",
    )(tile_rows, tile_cols, tiles, x)
