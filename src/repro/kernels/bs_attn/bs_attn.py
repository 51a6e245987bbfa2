"""Block-sparse flash attention Pallas TPU kernel.

The paper's *static* block sparsity applied to attention: a host-constant
block mask over (Sq/bq, Skv/bkv) tiles (e.g. local+global, banded --
``core/masks.py``) is flattened into (q_tile, kv_tile) visit pairs at
compile time, exactly like ``bsmm`` metadata.  The kernel walks pairs
sorted by q tile with an online-softmax accumulator and flushes when the
q tile changes; tiles outside the mask are never visited, so cost is
O(nnz_tiles) -- this is what makes the ``long_500k`` configs sub-
quadratic (DESIGN.md §3).

Supports causal intra-tile masking (derived from prefetch metadata, no
extra operands) and Gemma-2 style logit soft-capping.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _bs_attn_kernel(rows_ref, cols_ref, q_ref, k_ref, v_ref, o_ref,
                    m_ref, l_ref, acc_ref, *, scale, causal, bq, bkv,
                    softcap):
    s = pl.program_id(1)
    t = pl.num_programs(1)

    @pl.when((s == 0) | (rows_ref[s] != rows_ref[jnp.maximum(s - 1, 0)]))
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[...]                   # (bq, dh) -- None dim pre-squeezed
    k = k_ref[...]                   # (bkv, dh)
    v = v_ref[...]                   # (bkv, dh)
    logits = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale
    if softcap is not None:
        logits = softcap * jnp.tanh(logits / softcap)
    if causal:
        r0 = rows_ref[s] * bq
        c0 = cols_ref[s] * bkv
        ri = r0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0)
        ci = c0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
        logits = jnp.where(ri >= ci, logits, NEG_INF)

    m_prev = m_ref[:, :1]
    m_new = jnp.maximum(m_prev, logits.max(axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(logits - m_new)
    l_ref[:, :1] = l_ref[:, :1] * alpha + p.sum(axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(
        p.astype(v.dtype), v, preferred_element_type=jnp.float32)
    m_ref[:, :1] = m_new

    @pl.when((s == t - 1) | (rows_ref[s] != rows_ref[jnp.minimum(s + 1, t - 1)]))
    def _flush():
        denom = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[...] = (acc_ref[...] / denom).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bq", "bkv", "scale", "causal",
                                             "softcap", "interpret"))
def bs_attn_call(tile_rows, tile_cols, q, k, v, *, bq: int, bkv: int,
                 scale: float, causal: bool = True,
                 softcap: float | None = None, interpret: bool = False):
    """q: [H, Sq, dh], k/v: [H, Skv, dh]; tile pairs sorted by q tile.

    Every q tile must be covered by >= 1 pair (guaranteed for causal
    masks that include the diagonal; the ops wrapper enforces it).
    """
    h, sq, dh = q.shape
    grid = (h, tile_rows.shape[0])
    kern = functools.partial(_bs_attn_kernel, scale=scale, causal=causal,
                             bq=bq, bkv=bkv, softcap=softcap)
    return pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((None, bq, dh),
                             lambda hh, s, rows, cols: (hh, rows[s], 0)),
                pl.BlockSpec((None, bkv, dh),
                             lambda hh, s, rows, cols: (hh, cols[s], 0)),
                pl.BlockSpec((None, bkv, dh),
                             lambda hh, s, rows, cols: (hh, cols[s], 0)),
            ],
            out_specs=pl.BlockSpec((None, bq, dh),
                                   lambda hh, s, rows, cols: (hh, rows[s], 0)),
            scratch_shapes=[
                pltpu.VMEM((bq, 128), jnp.float32),
                pltpu.VMEM((bq, 128), jnp.float32),
                pltpu.VMEM((bq, dh), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="bs_attn_call",
    )(tile_rows, tile_cols, q, k, v)
