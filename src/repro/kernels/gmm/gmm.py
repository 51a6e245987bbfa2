"""Grouped matmul Pallas TPU kernel -- MoE expert compute as dynamic
block-diagonal sparsity (MegaBlocks, cited by the paper §1.2, on TPU).

``out[t] = x[t] @ W[expert_of(t)]`` where rows of ``x`` are grouped by
expert and groups are padded to row-tile multiples by the dispatcher
(``models/moe.py``), so each ``tm``-row tile belongs to exactly one
expert.  ``expert_ids`` ([T/tm] int32) is scalar-prefetched and drives the
W index map -- this is the dynamic-sparsity pattern-as-data idea applied
to the block-diagonal structure of expert routing: d_max == 1/E per tile,
capacity fixed by the dispatcher, pattern (routing) changes every step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _gmm_kernel(ids_ref, x_ref, w_ref, o_ref, acc_ref):
    d = pl.program_id(2)

    @pl.when(d == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[...], w_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(d == pl.num_programs(2) - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tm", "tf", "td", "interpret",
                                             "out_dtype"))
def gmm_call(expert_ids, x, w, *, tm: int, tf: int, td: int,
             interpret: bool = False, out_dtype=None):
    """expert_ids: [T/tm] int32; x: [T, D]; w: [E, D, F] -> out [T, F]."""
    t_rows, d_model = x.shape
    _, _, f = w.shape
    out_dtype = out_dtype or x.dtype
    grid = (t_rows // tm, f // tf, d_model // td)

    return pl.pallas_call(
        _gmm_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((tm, td), lambda t, fj, dj, ids: (t, dj)),
                pl.BlockSpec((None, td, tf),
                             lambda t, fj, dj, ids: (ids[t], dj, fj)),
            ],
            out_specs=pl.BlockSpec((tm, tf), lambda t, fj, dj, ids: (t, fj)),
            scratch_shapes=[pltpu.VMEM((tm, tf), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((t_rows, f), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="gmm_call",
    )(expert_ids, x, w)
