"""Sharding rules (divisibility fallback, spec shapes, constrain no-op)
+ tensor-parallel SpMM lowering parity: ``tp_spmm_shard_map`` vs
``tp_spmm_gspmd`` on a host-platform mesh (the multi-device CI job runs
this file under ``XLA_FLAGS=--xla_force_host_platform_device_count=8``;
on a single device the mesh-bound cases skip)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh
from jax.sharding import PartitionSpec as P

from repro import configs
from repro.core import partitioner, tp
from repro.core.bsr import BlockSparseMatrix
from repro.launch.mesh import make_host_mesh, make_mesh
from repro.launch.train import train_loop
from repro.sharding import rules

NDEV = len(jax.devices())
needs_mesh = pytest.mark.skipif(
    NDEV < 4, reason="needs >= 4 devices "
    "(XLA_FLAGS=--xla_force_host_platform_device_count=8)")


@pytest.fixture(scope="module")
def mesh():
    return make_mesh((1, 1), ("data", "model"))


def _amesh(shape, names=("data", "model")):
    """Abstract mesh: rule tests need axis sizes, not real devices."""
    return AbstractMesh(shape, names)


def _sizes(mesh):
    return {n: mesh.shape[n] for n in mesh.axis_names}


@pytest.mark.parametrize("name", configs.ARCH_IDS)
def test_param_specs_exist_and_align(name, mesh):
    params = configs.param_specs(name)
    specs = rules.param_specs(params, mesh)
    flat_p = jax.tree_util.tree_leaves_with_path(params)
    flat_s = {jax.tree_util.keystr(p): s for p, s in
              jax.tree_util.tree_leaves_with_path(
                  specs, is_leaf=lambda x: isinstance(x, P))}
    for path, leaf in flat_p:
        key = jax.tree_util.keystr(path)
        spec = flat_s[key]
        assert len(spec) <= leaf.ndim, f"{key}: spec longer than rank"


def test_divisibility_fallback(mesh):
    big = _amesh((1, 16))
    # 14 heads * 64 = 896 divisible by 16; but a 100-wide dim is not
    sds = {"attn": {"wq": {"w": jax.ShapeDtypeStruct((100, 100),
                                                     jnp.float32)}}}
    specs = rules.param_specs(sds, big)
    # 100 % 16 != 0 -> the model axis falls back to replication
    assert specs["attn"]["wq"]["w"][1] is None


def test_table_rule(mesh):
    big = _amesh((1, 16))
    sds = {"embed": {"table": jax.ShapeDtypeStruct((102400, 2048),
                                                   jnp.float32)}}
    specs = rules.param_specs(sds, big)
    assert specs["embed"]["table"][0] == "model"


def test_stacked_leading_dims_are_replicated():
    big = _amesh((2, 4))
    sds = {"attn": {"wq": {"w": jax.ShapeDtypeStruct((16, 128, 128),
                                                     jnp.float32)}}}
    specs = rules.param_specs(sds, big)
    s = specs["attn"]["wq"]["w"]
    assert s[0] is None and s[1] == "data" and s[2] == "model"


def test_cache_specs_batch_vs_long(mesh):
    big = _amesh((4, 4))
    caches = {"k": jax.ShapeDtypeStruct((2, 16, 1024, 4, 64), jnp.bfloat16),
              "state": jax.ShapeDtypeStruct((2, 16, 8, 64, 16),
                                            jnp.float32)}
    specs = rules.cache_specs(caches, big, batch=16)
    assert specs["k"][1] == "data" and specs["k"][2] == "model"
    # batch=1 long context: sequence takes every available axis
    caches1 = {"k": jax.ShapeDtypeStruct((2, 1, 4096, 4, 64), jnp.bfloat16)}
    specs1 = rules.cache_specs(caches1, big, batch=1)
    assert specs1["k"][2] == ("data", "model")


def test_constrain_noop_without_mesh():
    x = jnp.ones((4, 4))
    y = rules.constrain(x, "batch", None)
    assert y is x


def test_constrain_applies_under_mesh():
    mesh = make_mesh((1, 1), ("data", "model"))
    x = jnp.ones((4, 4))
    with rules.activation_mesh(mesh):
        y = rules.constrain(x, "batch", "model")
    np.testing.assert_allclose(np.asarray(y), np.asarray(x))


def test_train_loop_runs_on_host_mesh():
    """The training entry point's default mesh (``make_host_mesh``)
    accepts the sharding constraints of a real step: two steps of the
    smoke config run to the end with finite losses."""
    _, losses = train_loop(configs.smoke("qwen2_1_5b"), steps=2,
                           batch_per_shard=2, seq=16, ckpt_dir=None,
                           mesh=make_host_mesh(), log_every=100)
    assert len(losses) == 2 and np.isfinite(losses).all()


def test_train_batch_specs(mesh):
    big = _amesh((8, 2))
    batch = {"tokens": jax.ShapeDtypeStruct((16, 128), jnp.int32),
             "targets": jax.ShapeDtypeStruct((16, 128), jnp.int32)}
    specs = rules.train_batch_specs(batch, big)
    assert specs["tokens"][0] == "data"
    odd = {"tokens": jax.ShapeDtypeStruct((3, 128), jnp.int32)}
    assert rules.train_batch_specs(odd, big)["tokens"][0] is None


# -- TP SpMM lowering parity (shard_map vs gspmd vs dense oracle) -------------

def _skewed_bsr(m=128, k=256, b=16, dtype=jnp.float32, seed=0):
    """Static BSR whose nnz mass is concentrated in the left block
    columns, so nnz-balanced k-splits land at genuinely uneven
    boundaries (the paper's Fig. 1a case)."""
    rng = np.random.default_rng(seed)
    mb, kb = m // b, k // b
    col_p = np.linspace(1.0, 0.1, kb)
    mask = rng.random((mb, kb)) < 0.6 * col_p[None, :]
    mask[0, 0] = True                      # never empty
    bsr = BlockSparseMatrix.from_mask(mask, b)
    vals = jax.random.normal(jax.random.PRNGKey(seed + 1),
                             bsr.values.shape).astype(dtype)
    return bsr.with_values(vals)


@needs_mesh
@pytest.mark.parametrize("balanced", [True, False])
@pytest.mark.parametrize("dtype,tol", [
    (jnp.float32, 1e-5),          # reduction-order-only differences
    (jnp.bfloat16, 4e-2),
    (jnp.float16, 4e-2),
])
def test_tp_shard_map_vs_gspmd_parity(balanced, dtype, tol):
    q = 4
    bsr = _skewed_bsr(dtype=dtype)
    x = jax.random.normal(jax.random.PRNGKey(9),
                          (bsr.shape[1], 32)).astype(dtype)
    meta = partitioner.plan_k_shards(bsr, q, balanced=balanced)
    if balanced:
        # the skewed pattern must actually exercise uneven boundaries
        widths = np.diff(meta.boundaries)
        assert widths.max() > widths.min()
    assert meta.balanced is balanced
    sb = partitioner.apply_k_shards(meta, bsr.values)
    mesh = make_mesh((q,), ("model",))
    y_sm = tp.tp_spmm_shard_map(sb, x, mesh=mesh, axis="model")
    y_gs = tp.tp_spmm_gspmd(sb, x, axis="model")
    np.testing.assert_allclose(
        np.asarray(y_sm, np.float32), np.asarray(y_gs, np.float32),
        rtol=tol, atol=tol)
    oracle = jnp.asarray(bsr.to_dense()).astype(jnp.float32) \
        @ x.astype(jnp.float32)
    np.testing.assert_allclose(np.asarray(y_sm, np.float32),
                               np.asarray(oracle),
                               rtol=10 * tol, atol=10 * tol)


@needs_mesh
def test_tp_shard_map_on_two_axis_mesh():
    """shard_map TP composes with a (data, model) mesh: shards over
    'model' only, output replicated everywhere."""
    bsr = _skewed_bsr()
    x = jax.random.normal(jax.random.PRNGKey(3), (bsr.shape[1], 16))
    meta = partitioner.plan_k_shards(bsr, 4)
    sb = partitioner.apply_k_shards(meta, bsr.values)
    mesh = make_mesh((NDEV // 4, 4), ("data", "model"))
    y = tp.tp_spmm_shard_map(sb, x, mesh=mesh, axis="model")
    oracle = jnp.asarray(bsr.to_dense()) @ x
    np.testing.assert_allclose(np.asarray(y), np.asarray(oracle),
                               rtol=1e-4, atol=1e-4)


def test_tp_shard_map_rejects_mismatched_mesh():
    """q != mesh axis size (or no concrete mesh at all) must fail loudly
    -- a silent mis-shard would psum garbage."""
    bsr = _skewed_bsr()
    x = jnp.zeros((bsr.shape[1], 8))
    meta = partitioner.plan_k_shards(bsr, 2)
    sb = partitioner.apply_k_shards(meta, bsr.values)
    with pytest.raises(ValueError, match="axis 'model'"):
        tp.tp_spmm_shard_map(sb, x, mesh=None, axis="model")
    mesh1 = make_mesh((1,), ("model",))
    if mesh1.shape["model"] != sb.q:
        with pytest.raises(ValueError, match="size q=2"):
            tp.tp_spmm_shard_map(sb, x, mesh=mesh1, axis="model")


def test_plan_k_shards_validates_q():
    bsr = _skewed_bsr(m=64, k=64, b=16)      # kb = 4
    with pytest.raises(ValueError, match="k-shards"):
        partitioner.plan_k_shards(bsr, 5)
    with pytest.raises(ValueError, match="k-shards"):
        partitioner.plan_k_shards(bsr, 0, balanced=False)


# -- PR 8: balance assertions on the uneven-split machinery -------------------

def test_swizzled_plan_balances_power_law_rows():
    """The row-swizzle pre-pass must equalize per-lane work: on a
    power-law mask the swizzled plan's max per-step load stays within
    1.5x of the mean (the uniform row order concentrates it on the hot
    rows' lane)."""
    from repro.core import masks
    mask = masks.power_law_block_mask(4096, 4096, 16, 1 / 16, seed=0)
    counts = mask.sum(axis=1).astype(np.int64)
    sw = partitioner.plan_swizzle(counts, num_bins=8)
    assert sw.loads.max() <= 1.5 * sw.loads.mean()
    # the swizzle is a permutation and its inverse really inverts it
    r = len(counts)
    assert (np.sort(sw.order) == np.arange(r)).all()
    assert (sw.order[sw.inverse] == np.arange(r)).all()
    # unswizzled (identity-order) binning would not balance: the hot
    # rows are adjacent, so contiguous bins inherit the skew
    naive = np.array_split(counts, 8)
    naive_max = max(float(c.sum()) for c in naive)
    assert sw.loads.max() <= naive_max


def test_balanced_packing_steps_cover_all_tiles():
    from repro.core import masks
    from repro.core.partitioner import plan_packing_balanced
    mask = masks.power_law_block_mask(512, 512, 16, 1 / 8, seed=2)
    bsr = BlockSparseMatrix.from_mask(mask, 16)
    meta = plan_packing_balanced(bsr.row_idx, bsr.col_idx, bsr.shape, 16)
    # every real slot is visited exactly once; pads point at the
    # appended zero tile
    real = meta.visit_slot[meta.visit_slot < meta.base.num_tiles]
    assert len(np.unique(real)) == meta.base.num_tiles
    assert meta.visit_slot.shape == (meta.num_bins, meta.steps_per_bin)
