"""Grouped block-sampled dense-dense matmul (SDDMM) Pallas TPU kernel.

The weight gradient of a static block-sparse matmul is
``dW = (dY @ X^T) ⊙ M`` -- only the pattern's blocks are needed (paper
§3.2: backward keeps the same compile-time pattern, so sparse *training*
stays sparse).  Computing the full dense product and masking throws away
``1 - d`` of the FLOPs; walking logical ``b x b`` blocks under-fills the
128x128 MXU for small ``b`` (the same under-utilisation the forward
``dsmm`` walk pays).

This kernel is the SDDMM face of the grouped-tile idea (``kernels/gmm``):
the pattern's *tile* occupancy -- the same ``partitioner.plan_packing``
metadata the static forward kernel uses, transposed into sampled-output
form -- drives a grid over the non-empty ``t x t`` output tiles only.
Step ``(i, nj)`` accumulates ``dY[tile_rows[i]] @ X[tile_cols[i]]^T``
over the contraction (``n``) dimension; tile metadata is compile-time
scalar prefetch, exactly like ``bsmm``.  The per-block extraction from
the tile stack is host-metadata gather work and lives in ``ops.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _sddmm_kernel(trows_ref, tcols_ref, dy_ref, x_ref, o_ref, acc_ref):
    del trows_ref, tcols_ref
    nj = pl.program_id(1)
    nt = pl.num_programs(1)

    @pl.when(nj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # dY_tile [t, tn] @ X_tile [t, tn]^T: contract the n (lane) axis
    acc_ref[...] += jax.lax.dot_general(
        dy_ref[...], x_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(nj == nt - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("t", "tn", "interpret",
                                             "out_dtype"))
def sddmm_tiles_call(tile_rows, tile_cols, dy, x, *, t: int, tn: int,
                     interpret: bool = False, out_dtype=None):
    """Raw kernel entry: the sampled ``t x t`` output tiles.

    tile_rows/tile_cols: [T] int32 compile-time tile metadata (row-major
                         non-empty tiles of the pattern, from
                         ``partitioner.plan_packing``)
    dy:                  [M, N]    upstream cotangent
    x:                   [K, N]    forward rhs
    returns              [T, t, t] one sampled product tile per slot
    """
    n = dy.shape[1]
    num_tiles = tile_rows.shape[0]
    out_dtype = out_dtype or dy.dtype
    grid = (num_tiles, n // tn)

    return pl.pallas_call(
        _sddmm_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((t, tn),
                             lambda it, nj, tr, tc: (tr[it], nj)),
                pl.BlockSpec((t, tn),
                             lambda it, nj, tr, tc: (tc[it], nj)),
            ],
            out_specs=pl.BlockSpec((None, t, t),
                                   lambda it, nj, tr, tc: (it, 0, 0)),
            scratch_shapes=[pltpu.VMEM((t, t), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((num_tiles, t, t), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="sddmm_tiles_call",
    )(tile_rows, tile_cols, dy, x)
