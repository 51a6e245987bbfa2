"""Dense tiled matmul Pallas TPU kernel -- the paper's dense baseline
(IPU ``poplin::matMul`` / GPU ``cublasGemmEx`` analogue).

Classic 3-D tiling: ``grid = (M/tm, N/tn, K/tk)`` with a VMEM fp32
accumulator over the contraction dimension.  Exists so the benchmark
harness compares sparse kernels against a same-framework dense kernel,
like the paper compares popsparse:: against poplin::.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _mm_kernel(a_ref, b_ref, o_ref, acc_ref):
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(a_ref[...], b_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(kj == pl.num_programs(2) - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tm", "tk", "tn", "interpret",
                                             "out_dtype"))
def dense_mm_call(a, b, *, tm: int, tk: int, tn: int,
                  interpret: bool = False, out_dtype=None):
    m, k = a.shape
    _, n = b.shape
    out_dtype = out_dtype or a.dtype
    grid = (m // tm, n // tn, k // tk)
    return pl.pallas_call(
        _mm_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tm, tk), lambda i, j, kj: (i, kj)),
            pl.BlockSpec((tk, tn), lambda i, j, kj: (kj, j)),
        ],
        out_specs=pl.BlockSpec((tm, tn), lambda i, j, kj: (i, j)),
        scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)],
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="dense_mm_call",
    )(a, b)
