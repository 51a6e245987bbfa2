"""Tensor-parallel SpMM -- the paper's partitioning lifted to the mesh.

PopSparse Fig. 1a distributes non-zero blocks over IPU tiles with uneven,
nnz-balanced k-splits, computes local dot products, then reduces partial
outputs.  At pod scale the same scheme maps onto the ``model`` mesh axis:

* each model shard owns one nnz-balanced k-partition of the blocks
  (``partitioner.shard_blocks_by_k`` -> stacked ``[q, slots, ...]``),
* each shard computes its partial ``Y`` from its blocks,
* one ``psum`` over ``model`` produces the final output -- the paper's
  "final reduction across tiles".

Two entry points:

* ``tp_spmm_shard_map`` -- explicit shard_map + psum (paper-faithful,
  collective schedule fully pinned down; the ``static_tp_shardmap``
  plan route).
* ``tp_spmm_gspmd``     -- same math under plain jit with sharding
  constraints (GSPMD inserts the psum); composes freely inside larger
  pjit programs, used by model layers (the ``static_tp`` plan route).

Which one wins is a *measured* question (the all-reduce schedule and
the local-work overlap differ), so ``repro.sparse.plan`` races both --
plus the unsharded candidates -- under measured autotune when a mesh is
given (see docs/api.md, "Tensor-parallel plans").
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import AbstractMesh
from jax.sharding import PartitionSpec as P

from repro.core.partitioner import ShardedBlocks


def shard_map_executable(mesh, axis: str, q: int) -> bool:
    """Can ``tp_spmm_shard_map`` actually run on this mesh?  Needs a
    concrete (device-backed) mesh whose ``axis`` size equals the shard
    count ``q`` -- an ``AbstractMesh`` or a tp_q forced past the real
    device count can only execute the gspmd lowering."""
    if mesh is None or axis not in getattr(mesh, "axis_names", ()):
        return False
    if isinstance(mesh, AbstractMesh):   # shapes only, no devices
        return False
    return int(mesh.shape[axis]) == int(q)


def _local_spmm(values, row_idx, col_idx, x, *, mb: int, b: int):
    """Per-shard partial product: [slots,b,b] blocks against full X."""
    n = x.shape[-1]
    kb = x.shape[0] // b
    xb = x.reshape(kb, b, n)
    gathered = jnp.take(xb, col_idx, axis=0)
    partial = jnp.einsum("zab,zbn->zan", values, gathered)
    y = jax.ops.segment_sum(partial, row_idx, num_segments=mb)
    return y.reshape(mb * b, n)


def tp_spmm_shard_map(sb: ShardedBlocks, x: jax.Array, *, mesh,
                      axis: str = "model") -> jax.Array:
    """Explicit paper-style TP SpMM.  ``sb.q`` must equal the axis size
    (validated -- a mismatched shard plan would silently mis-shard)."""
    if not shard_map_executable(mesh, axis, sb.q):
        raise ValueError(
            f"tp_spmm_shard_map needs a concrete mesh with axis "
            f"{axis!r} of size q={sb.q}; got mesh axes "
            f"{tuple(getattr(mesh, 'axis_names', ()))} "
            f"{dict(getattr(mesh, 'shape', {}))}")
    mb = sb.shape[0] // sb.block_size
    b = sb.block_size

    def shard_fn(values, row_idx, col_idx, x_full):
        # leading q axis is sharded to size 1 locally
        y = _local_spmm(values[0], row_idx[0], col_idx[0], x_full,
                        mb=mb, b=b)
        return jax.lax.psum(y, axis)

    fn = jax.shard_map(shard_fn, mesh=mesh,
                       in_specs=(P(axis), P(axis), P(axis), P()),
                       out_specs=P(), check_vma=False)
    return fn(sb.values, sb.row_idx, sb.col_idx, x)


def tp_spmm_gspmd(sb: ShardedBlocks, x: jax.Array, *,
                  axis: str = "model") -> jax.Array:
    """Same computation expressed for GSPMD: values sharded on the stacked
    ``q`` axis, X replicated over ``model``; the trailing sum over ``q``
    lowers to an all-reduce on the ``model`` axis."""
    from repro.sharding.rules import constrain
    mb = sb.shape[0] // sb.block_size
    b = sb.block_size
    q = sb.q
    vals = constrain(sb.values, axis)   # no-op outside a mesh context
    n = x.shape[-1]
    kb = x.shape[0] // b
    xb = x.reshape(kb, b, n)
    gathered = jnp.take(xb, sb.col_idx.reshape(-1), axis=0)  # [q*slots,b,n]
    gathered = gathered.reshape(q, sb.slots, b, n)
    partial = jnp.einsum("qzab,qzbn->qzan", vals, gathered)
    flat_rows = sb.row_idx + (jnp.arange(q, dtype=jnp.int32) * mb)[:, None]
    y = jax.ops.segment_sum(partial.reshape(q * sb.slots, b, n),
                            flat_rows.reshape(-1), num_segments=q * mb)
    y = y.reshape(q, mb, b, n).sum(axis=0)   # -> all-reduce over model
    return y.reshape(mb * b, n)
