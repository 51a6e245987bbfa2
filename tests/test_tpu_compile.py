"""Compile-only guard: every Pallas kernel of the serving path, compiled
by Mosaic for a described TPU v5e at qwen2-1.5b FFN widths.

Interpret mode cannot see Mosaic's tiling and fast-memory rules, so
these tests compile (nothing runs) each kernel through its ``ops``
wrapper -- not through ``dispatch``, which asks ``default_backend()``
and sees the CPU here -- at decode ``n=8`` and at an unaligned
exact-length prefill ``n=1023`` (and
``bs_attn``, which has no route yet, at one 1024-token prefill).  The
topology is described inside a fixture, never at import: only one
process at a time may load the TPU compiler library.
"""
import functools
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import masks, partitioner
from repro.core.dynamic_sparse import DynamicOperand
from repro.kernels.bs_attn.ops import bs_attn
from repro.kernels.bsmm import ops as bsmm_ops
from repro.kernels.dense_mm import ops as dmm_ops
from repro.kernels.dsmm import ops as dsmm_ops
from repro.kernels.gmm import balanced as gmm_balanced
from repro.kernels.gmm import ops as gmm_ops
from repro.kernels.sddmm import ops as sddmm_ops

D_MODEL, D_FF, BLOCK, DENSITY = 1536, 8960, 16, 0.125
PROJ = {"up": (D_FF, D_MODEL), "down": (D_MODEL, D_FF)}
BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 -- any failure means no compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@functools.lru_cache(maxsize=None)
def _pattern(m, k, b=BLOCK):
    mask = masks.random_block_mask(m, k, b, DENSITY, seed=0)
    rows, cols = np.nonzero(mask)
    return rows.astype(np.int32), cols.astype(np.int32)


_CUSTOM_CALL = re.compile(r"%([a-z_]+)\.\d+ = .*custom-call\(.*"
                          r'custom_call_target="tpu_custom_call"'
                          r'.*op_name="([^"]*)"')


def _compile(fn, *shapes, sharding, kernels):
    """Compile for the chip; every Mosaic kernel in the program is one
    of ``kernels``, named after its jitted wrapper both as an operation
    (what a profiler trace shows) and in its ``op_name`` (the kernel's
    ``pallas_call(name=...)``)."""
    sds = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    compiled = jax.jit(fn).lower(*sds).compile()
    calls = _CUSTOM_CALL.findall(compiled.as_text())
    assert {name for name, _ in calls} == set(kernels)
    for name, op_name in calls:
        assert f"/{name}/pallas_call" in op_name, op_name


def _dynamic(m, k, b, values, rows, cols, nnz):
    return DynamicOperand(values, rows, cols, nnz, (m, k), b)


@pytest.mark.parametrize("n", [8, 1023])
@pytest.mark.parametrize("proj", ["up", "down"])
def test_dense_mm_compiles(one_chip, proj, n):
    m, k = PROJ[proj]
    # activation-major, as the serving engine's dense layers call it
    _compile(dmm_ops.dense_mm, ((n, k), BF16), ((k, m), BF16),
             sharding=one_chip, kernels=["dense_mm_call"])


@pytest.mark.parametrize("n", [8, 1023])
@pytest.mark.parametrize("proj", ["up", "down"])
@pytest.mark.parametrize("balanced", [False, True], ids=["bsmm", "balanced"])
def test_bsmm_compiles(one_chip, balanced, proj, n):
    m, k = PROJ[proj]
    rows, cols = _pattern(m, k)
    tm, tk, _ = bsmm_ops._pick_tiles(m, k, n, BLOCK)
    if balanced:
        meta = partitioner.plan_packing_balanced(rows, cols, (m, k), BLOCK,
                                                 tm, tk)
        fn = functools.partial(bsmm_ops.bsmm_balanced_from_plan, meta)
    else:
        meta = partitioner.plan_packing(rows, cols, (m, k), BLOCK, tm, tk)
        fn = functools.partial(bsmm_ops.bsmm_from_plan, meta)
    _compile(fn, ((len(rows), BLOCK, BLOCK), BF16), ((k, n), BF16),
             sharding=one_chip,
             kernels=["bsmm_balanced_call" if balanced else "bsmm_call"])


@pytest.mark.parametrize("n", [32, 1023])
@pytest.mark.parametrize("proj", ["up", "down"])
@pytest.mark.parametrize("balanced", [False, True], ids=["bsmm", "balanced"])
def test_bsmm_packed_compiles(one_chip, balanced, proj, n):
    """The serving engine's pre-packed payload: one ``[T + 1, tm, tk]``
    stack (``sparse.pack``) for both walks, which visit its first T
    tiles (and the balanced walk its pad tile)."""
    m, k = PROJ[proj]
    rows, cols = _pattern(m, k)
    tm, tk = bsmm_ops.tile_shape(m, k, BLOCK)
    if balanced:
        meta = partitioner.plan_packing_balanced(rows, cols, (m, k), BLOCK,
                                                 tm, tk)
        run, t = bsmm_ops.bsmm_balanced_from_plan, meta.base.num_tiles
    else:
        meta = partitioner.plan_packing(rows, cols, (m, k), BLOCK, tm, tk)
        run, t = bsmm_ops.bsmm_from_plan, meta.num_tiles

    def fn(tiles, x):
        return run(meta, partitioner.PackedTiles(tiles), x)
    _compile(fn, ((t + 1, tm, tk), BF16), ((k, n), BF16),
             sharding=one_chip,
             kernels=["bsmm_balanced_call" if balanced else "bsmm_call"])


@pytest.mark.parametrize("n", [8, 1023])
@pytest.mark.parametrize("kernel", ["dsmm", "gmm", "gmm_balanced"])
def test_dynamic_walks_compile(one_chip, kernel, n):
    m, k = PROJ["up"]
    nnz = len(_pattern(m, k)[0])
    if kernel == "dsmm":
        run = dsmm_ops.dsmm
    else:
        t = gmm_ops.grouped_tile_size(m, k, BLOCK)
        cap = len(partitioner.plan_packing(*_pattern(m, k), (m, k), BLOCK,
                                           t, t).tile_rows)
        spmm = (gmm_ops.grouped_spmm if kernel == "gmm"
                else gmm_balanced.balanced_spmm)
        run = functools.partial(spmm, tile=t, tiles_cap=cap)

    def fn(values, rows, cols, nnz_, x):
        return run(_dynamic(m, k, BLOCK, values, rows, cols, nnz_), x)
    _compile(fn, ((nnz, BLOCK, BLOCK), BF16), ((nnz,), jnp.int32),
             ((nnz,), jnp.int32), ((), jnp.int32), ((k, n), BF16),
             sharding=one_chip, kernels=["dsmm_call"])



def test_gmm_compiles(one_chip):
    """The grouped GEMM of the MoE route: two experts at qwen2-1.5b's FFN
    widths, one expert per 128-row tile."""
    _compile(functools.partial(gmm_ops.gmm, tm=128),
             ((256, D_MODEL), BF16), ((2, D_MODEL, D_FF), BF16),
             ((2,), jnp.int32), sharding=one_chip, kernels=["gmm_call"])

@pytest.mark.parametrize("n", [8, 1023])
def test_sddmm_compiles(one_chip, n):
    m, k = PROJ["up"]
    rows, cols = _pattern(m, k)
    t = sddmm_ops.sddmm_tile_size(m, k, BLOCK)
    meta = partitioner.plan_packing(rows, cols, (m, k), BLOCK, t, t)
    _compile(functools.partial(sddmm_ops.grouped_sddmm, meta),
             ((m, n), BF16), ((k, n), BF16), sharding=one_chip,
             kernels=["sddmm_tiles_call"])


def test_bs_attn_compiles(one_chip):
    """Causal block-sparse attention at qwen2-1.5b's 12 query heads of
    128 over a 1024-token prefill (8 x 8 tiles of 128)."""
    mask = np.tril(np.ones((8, 8), bool))
    _compile(functools.partial(bs_attn, block_mask=mask),
             ((12, 1024, 128), BF16), ((12, 1024, 128), BF16),
             ((12, 1024, 128), BF16), sharding=one_chip,
             kernels=["bs_attn_call"])


@pytest.mark.parametrize("b", [4, 12])
def test_small_blocks_compile(one_chip, b):
    """Block edges below the sublane alignment are padded inside the
    wrappers, so every block size the contracts admit compiles."""
    m, k, n = 72, 36, 200      # sddmm tile 36: not sublane-aligned
    rows, cols = _pattern(m, k, b)
    nnz = len(rows)

    def fn(values, x, dy):
        op = _dynamic(m, k, b, values, jnp.asarray(rows),
                      jnp.asarray(cols), jnp.int32(nnz))
        t = sddmm_ops.sddmm_tile_size(m, k, b)
        meta = partitioner.plan_packing(rows, cols, (m, k), b, t, t)
        return dsmm_ops.dsmm(op, x), sddmm_ops.grouped_sddmm(meta, dy, x)
    _compile(fn, ((nnz, b, b), BF16), ((k, n), BF16), ((m, n), BF16),
             sharding=one_chip, kernels=["dsmm_call", "sddmm_tiles_call"])


# -- the padding those compiles rely on, checked numerically ------------------
# (interpret mode on the host: zero padding must not change a result)

def _padded_case(kind):
    rng = np.random.default_rng(0)
    n = 130                              # pads to 256 lanes
    if kind == "dense_mm":
        a, b = rng.normal(size=(200, 136)), rng.normal(size=(136, n))
        return (dmm_ops.dense_mm(jnp.asarray(a), jnp.asarray(b),
                                 interpret=True), a @ b)
    m, k, blk = (72, 36, 4) if kind in ("dsmm", "sddmm") else (64, 64, 16)
    rows, cols = _pattern(m, k, blk)
    vals = rng.normal(size=(len(rows), blk, blk))
    dense = np.zeros((m, k))
    for z, (r, c) in enumerate(zip(rows, cols)):
        dense[r * blk:(r + 1) * blk, c * blk:(c + 1) * blk] = vals[z]
    x = rng.normal(size=(k, n))
    if kind == "sddmm":
        dy = rng.normal(size=(m, n))
        t = sddmm_ops.sddmm_tile_size(m, k, blk)
        meta = partitioner.plan_packing(rows, cols, (m, k), blk, t, t)
        got = sddmm_ops.grouped_sddmm(meta, jnp.asarray(dy), jnp.asarray(x),
                                      interpret=True)
        full = dy @ x.T
        want = np.stack([full[r * blk:(r + 1) * blk, c * blk:(c + 1) * blk]
                         for r, c in zip(rows, cols)])
        return got, want
    if kind == "dsmm":
        op = _dynamic(m, k, blk, jnp.asarray(vals), jnp.asarray(rows),
                      jnp.asarray(cols), jnp.int32(len(rows)))
        return dsmm_ops.dsmm(op, jnp.asarray(x), interpret=True), dense @ x
    tm, tk, _ = bsmm_ops._pick_tiles(m, k, n, blk)
    meta = partitioner.plan_packing_balanced(rows, cols, (m, k), blk, tm, tk)
    return (bsmm_ops.bsmm_balanced_from_plan(meta, jnp.asarray(vals),
                                             jnp.asarray(x), interpret=True),
            dense @ x)


@pytest.mark.parametrize("kind", ["dense_mm", "bsmm_balanced", "dsmm",
                                  "sddmm"])
def test_padded_shapes_match_reference(kind):
    got, want = _padded_case(kind)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4, atol=1e-4)
