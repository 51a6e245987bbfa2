"""Dynamic block-sparse matmul Pallas TPU kernel (PopSparse §3.3 on MXU).

Same walk as ``bsmm`` -- accumulate over a row-sorted slot list, flush on
row change -- but everything the static kernel gets for free at compile
time is paid for at runtime, reproducing the paper's dynamic-mode cost
taxonomy exactly:

* the slot list is **runtime data** (scalar-prefetch operands are traced
  arrays), so DMA targets are resolved per step instead of pre-planned;
* the grid is sized for **capacity** (``d_max``), not the true nnz: padded
  slots execute as zero-contribution steps -- the analogue of the paper's
  overflow/propagation phases which "must account for the largest
  communication volume possible";
* values stay at logical ``b x b`` granularity (no host tile packing is
  possible), so MXU utilisation is intrinsically lower -- mirroring the
  paper's per-block on-tile compute with extra control flow.

The encoder that produces the slot arrays (sort-by-row + coverage) is in
``ops.py`` and is itself jit-compiled: its cycles are part of dynamic
mode's measured overhead, like PopSparse's runtime partitioner.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _dsmm_kernel(rows_ref, cols_ref, a_ref, x_ref, o_ref, acc_ref):
    del cols_ref
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


@functools.partial(jax.jit, static_argnames=("b", "tn", "grid_m",
                                             "interpret", "out_dtype"))
def dsmm_call(rows, cols, values, x, *, b: int, tn: int, grid_m: int,
              interpret: bool = False, out_dtype=None):
    """Raw kernel entry.

    rows/cols: [S] int32 runtime slot metadata, row-sorted, all rows covered
    values:    [S, b, b] slot values (zero for padding slots)
    x:         [K, N]
    returns    [grid_m * b, N]
    """
    s_cap = values.shape[0]
    k, n = x.shape
    out_dtype = out_dtype or x.dtype
    grid = (n // tn, s_cap)

    return pl.pallas_call(
        _dsmm_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((None, b, b),
                             lambda nj, s, rows, cols: (s, 0, 0)),
                pl.BlockSpec((b, tn),
                             lambda nj, s, rows, cols: (cols[s], nj)),
            ],
            out_specs=pl.BlockSpec((b, tn),
                                   lambda nj, s, rows, cols: (rows[s], nj)),
            scratch_shapes=[pltpu.VMEM((b, tn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((grid_m * b, n), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="dsmm_call",
    )(rows, cols, values, x)
