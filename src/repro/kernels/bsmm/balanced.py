"""Balanced-walk static block-sparse matmul (row-swizzle load balance).

The uniform ``bsmm`` walk visits the packed tiles row-major on one
``arbitrary`` grid axis: a power-law row profile serializes the walk on
the hot rows (most steps share one output row-tile, so the inter-step
flush/init bubbles pile onto a single lane).  Gale et al. 2020 (arxiv
2006.10901, §5.1) show row swizzling -- reordering rows so concurrent
lanes carry near-equal work -- recovers that loss on realistic (DLMC)
patterns.

This variant consumes ``partitioner.plan_packing_balanced``: row-tiles
are snake-binned by tile count at plan time, and the kernel walks a 3-D
grid ``(n // tn, num_bins, steps_per_bin)`` -- one *parallel* lane per
bin, each lane a short ``arbitrary`` walk over its bin's visit schedule
(scalar-prefetched ``[bins, steps]`` metadata).  Bins own disjoint
row-tile sets and every row-tile's tiles are contiguous within its
lane, so the accumulate/flush invariant of the uniform kernel holds per
lane unchanged.  Lanes shorter than ``steps_per_bin`` pad with an
appended all-zero tile and keep their last real row: the pad steps
accumulate zeros and defer that row's single flush to the lane end.
The inverse row permutation costs nothing at runtime -- the visit
schedule carries *original* row-tile ids, so the output index map
scatters each flush straight to its un-swizzled position.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _bsmm_balanced_kernel(rows_ref, cols_ref, slots_ref, a_ref, x_ref,
                          o_ref, acc_ref):
    del cols_ref, slots_ref  # consumed by the index maps
    g = pl.program_id(1)
    s = pl.program_id(2)
    t = pl.num_programs(2)

    @pl.when((s == 0) | (rows_ref[g, s] != rows_ref[g, jnp.maximum(s - 1, 0)]))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(a_ref[...], x_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when((s == t - 1)
             | (rows_ref[g, s] != rows_ref[g, jnp.minimum(s + 1, t - 1)]))
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tm", "tk", "tn", "grid_m",
                                             "interpret", "out_dtype"))
def bsmm_balanced_call(visit_rows, visit_cols, visit_slot, tiles, x, *,
                       tm: int, tk: int, tn: int, grid_m: int,
                       interpret: bool = False, out_dtype=None):
    """Raw kernel entry.

    visit_rows/cols/slot: [bins, steps] int32 (host constants)
    tiles:                [T + 1, tm, tk] packed tiles + trailing zero pad
    x:                    [K, N] dense operand
    returns               [grid_m * tm, N]
    """
    bins, steps = visit_rows.shape
    k, n = x.shape
    out_dtype = out_dtype or x.dtype
    grid = (n // tn, bins, steps)

    return pl.pallas_call(
        _bsmm_balanced_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=grid,
            in_specs=[
                pl.BlockSpec((None, tm, tk),
                             lambda nj, g, s, rows, cols, slots:
                             (slots[g, s], 0, 0)),
                pl.BlockSpec((tk, tn),
                             lambda nj, g, s, rows, cols, slots:
                             (cols[g, s], nj)),
            ],
            out_specs=pl.BlockSpec((tm, tn),
                                   lambda nj, g, s, rows, cols, slots:
                                   (rows[g, s], nj)),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((grid_m * tm, n), out_dtype),
        # bins write disjoint row-tile sets (pads keep the bin's own last
        # row), so the bin axis is safely parallel
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="bsmm_balanced_call",
    )(visit_rows, visit_cols, visit_slot, tiles, x)
