"""Plan-first sparse API (repro.sparse): two-phase plan/execute
lifecycle, route parity, jit/grad/vmap safety, disk-cache round trip +
stale invalidation, deprecation-shim parity, and the DynamicOperand
grid/validation fixes that ride along."""
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import sparse
from repro.core import dispatch, dynamic_sparse as dsp, \
    static_sparse as ssp
from repro.core.bsr import BlockSparseMatrix
from repro.launch.mesh import make_mesh

M, K, N, B, DENSITY = 128, 256, 64, 16, 0.25


@pytest.fixture(autouse=True)
def _fresh_state():
    sparse.reset()
    sparse.configure(None)
    yield
    sparse.reset()
    sparse.configure(None)


def _bsr(seed=0, m=M, k=K, b=B, d=DENSITY, dtype=jnp.float32):
    return BlockSparseMatrix.random(jax.random.PRNGKey(seed), m, k, b, d,
                                    dtype=dtype, pattern_seed=seed)


def _problem(seed=0, dtype=jnp.float32):
    bsr = _bsr(seed, dtype=dtype)
    x = jax.random.normal(jax.random.PRNGKey(seed + 100),
                          (K, N)).astype(dtype)
    oracle = jnp.asarray(bsr.to_dense()) @ x
    return bsr, x, oracle


# -- plan construction + route parity -----------------------------------------

STATIC_ROUTES = ["static_xla", "dense_xla", "dynamic_xla"]
STATIC_INTERPRET = ["static_pallas", "dense_pallas", "dynamic_pallas",
                    "dynamic_grouped"]


@pytest.mark.parametrize("route", STATIC_ROUTES)
def test_static_plan_route_parity(route):
    bsr, x, oracle = _problem()
    p = sparse.plan(bsr, N, ctx=sparse.PlanContext(mode=route))
    assert p.route == route and p.executable
    np.testing.assert_allclose(np.asarray(p(bsr.values, x)),
                               np.asarray(oracle), rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("route", STATIC_INTERPRET)
def test_static_plan_route_parity_interpret(route):
    bsr, x, oracle = _problem()
    p = sparse.plan(bsr, N, ctx=sparse.PlanContext(mode=route,
                                                   interpret=True))
    np.testing.assert_allclose(np.asarray(p(bsr.values, x)),
                               np.asarray(oracle), rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("route", ["dynamic_xla", "dense_xla"])
def test_dynamic_plan_route_parity(route):
    bsr, x, oracle = _problem()
    op = dsp.encode_from_bsr(bsr, nnz_max=bsr.nnz_blocks + 4)
    p = sparse.plan(op, N, ctx=sparse.PlanContext(mode=route))
    np.testing.assert_allclose(np.asarray(p(op, x)), np.asarray(oracle),
                               rtol=1e-4, atol=1e-4)
    # bucket sizing ran at plan time
    assert p.artifacts["bucket_blocks"] >= 1


@pytest.mark.parametrize("route", ["dynamic_pallas", "dynamic_grouped"])
def test_dynamic_plan_pallas_parity_interpret(route):
    bsr, x, oracle = _problem()
    op = dsp.encode_from_bsr(bsr, nnz_max=bsr.nnz_blocks + 4)
    p = sparse.plan(op, N, ctx=sparse.PlanContext(mode=route,
                                                  interpret=True))
    np.testing.assert_allclose(np.asarray(p(op, x)), np.asarray(oracle),
                               rtol=1e-4, atol=1e-4)


def test_static_tp_plan_parity():
    """Mesh-aware route: nnz-balanced k-shards + one reduction."""
    bsr, x, oracle = _problem()
    p = sparse.plan(bsr, N, ctx=sparse.PlanContext(mode="static_tp",
                                                   tp_q=4))
    assert p.route == "static_tp"
    assert p.artifacts["tp_q"] == 4
    np.testing.assert_allclose(np.asarray(p(bsr.values, x)),
                               np.asarray(oracle), rtol=1e-4, atol=1e-4)


def test_auto_plan_parity_and_artifacts():
    bsr, x, oracle = _problem()
    p = sparse.plan(bsr, N)
    np.testing.assert_allclose(np.asarray(p.apply(bsr, x)),
                               np.asarray(oracle), rtol=1e-4, atol=1e-4)
    rep = p.explain()
    assert rep["chosen"] == p.route and rep["chosen"] in rep["candidates"]
    assert "plan" in rep and rep["plan"]["executable"]
    assert "dispatch" in sparse.format_plan(p)   # renders the report


def test_spec_only_static_plan_is_report_only():
    spec = sparse.OpSpec(kind="static", m=M, k=K, n=N, block_size=B,
                         density=DENSITY)
    p = sparse.plan(spec)
    assert not p.executable and p.route in sparse.PLAN_ROUTES
    with pytest.raises(ValueError, match="report-only|OpSpec"):
        p(jnp.zeros((1, B, B)), jnp.zeros((K, N)))


def test_spec_only_dynamic_and_dense_plans_execute():
    bsr, x, oracle = _problem()
    op = dsp.encode_from_bsr(bsr, nnz_max=bsr.nnz_blocks)
    spec = sparse.OpSpec.from_operand(op, N)
    p = sparse.plan(spec, ctx=sparse.PlanContext(mode="dynamic_xla"))
    np.testing.assert_allclose(np.asarray(p(op, x)), np.asarray(oracle),
                               rtol=1e-4, atol=1e-4)


# -- plan reuse: cache hits, jit, grad, vmap ----------------------------------

def test_plan_cache_reuse_same_pattern():
    bsr, x, _ = _problem()
    p1 = sparse.plan(bsr, N)
    p2 = sparse.plan(bsr.with_values(bsr.values * 2), N)
    assert p2 is p1                       # same pattern -> same plan obj
    assert sparse.cache_stats()["plan_hits"] == 1
    # a *different* pattern with the same fingerprint must NOT collide
    other = _bsr(seed=7)
    p3 = sparse.plan(other, N)
    assert p3 is not p1
    np.testing.assert_allclose(
        np.asarray(p3(other.values, x)),
        np.asarray(jnp.asarray(other.to_dense()) @ x), rtol=1e-4,
        atol=1e-4)


def test_plan_under_jit_grad_vmap():
    bsr, x, oracle = _problem()
    p = sparse.plan(bsr, N)

    # jit: the plan is closed over; the route is baked into the program
    f = jax.jit(lambda v, xx: p(v, xx))
    np.testing.assert_allclose(np.asarray(f(jnp.asarray(bsr.values), x)),
                               np.asarray(oracle), rtol=1e-4, atol=1e-4)

    # grad matches the dense formulation
    def loss_sparse(values, xx):
        return (p(values, xx) ** 2).sum()

    def loss_dense(values, xx):
        return ((bsr.with_values(values).to_dense() @ xx) ** 2).sum()

    gv_s, gx_s = jax.grad(loss_sparse, argnums=(0, 1))(
        jnp.asarray(bsr.values), x)
    gv_d, gx_d = jax.grad(loss_dense, argnums=(0, 1))(
        jnp.asarray(bsr.values), x)
    np.testing.assert_allclose(np.asarray(gv_s), np.asarray(gv_d),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(gx_s), np.asarray(gx_d),
                               rtol=1e-4, atol=1e-4)

    # vmap over a batch of activations
    xb = jax.random.normal(jax.random.PRNGKey(9), (3, K, 8))
    yv = jax.vmap(lambda xx: p(jnp.asarray(bsr.values), xx))(xb)
    want = jnp.einsum("mk,bkn->bmn", jnp.asarray(bsr.to_dense()), xb)
    np.testing.assert_allclose(np.asarray(yv), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_plan_vjp_helper():
    bsr, x, _ = _problem()
    p = sparse.plan(bsr, N)
    y, vjp_fn = p.vjp(jnp.asarray(bsr.values), x)
    gv, gx = vjp_fn(jnp.ones_like(y))
    assert gv.shape == bsr.values.shape and gx.shape == x.shape


def test_steady_state_is_decision_free():
    """After the first plan, repeated calls make NO new decisions."""
    bsr, x, _ = _problem()
    sparse.spmm(bsr, x)
    base = sparse.cache_stats()
    for _ in range(5):
        sparse.spmm(bsr, x)
    now = sparse.cache_stats()
    assert now["decisions"] == base["decisions"]
    assert now["plans_built"] == base["plans_built"]
    assert now["plan_hits"] == base["plan_hits"] + 5


# -- persistent cache ---------------------------------------------------------

def test_disk_cache_round_trip(tmp_path):
    """Write in 'process 1', reset all in-memory state, re-plan in
    'process 2' with zero measurements (the acceptance criterion)."""
    bsr, x, _ = _problem()
    ctx = sparse.PlanContext(measure=True, cache_dir=str(tmp_path))
    p1 = sparse.plan(bsr, N, x=x, ctx=ctx)
    s1 = sparse.cache_stats()
    # two measurement events: the forward route race + the backward
    # (dx/dvalues) race -- both verdicts persist in one record
    assert s1["measurements"] == 2 and s1["disk_writes"] >= 1
    assert p1.source == "measured" and not p1.from_disk

    sparse.reset()                        # fresh-process simulation
    p2 = sparse.plan(bsr, N, x=x, ctx=ctx)
    s2 = sparse.cache_stats()
    assert s2["measurements"] == 0        # zero re-measurement
    assert s2["disk_hits"] == 1
    assert p2.from_disk and p2.route == p1.route
    assert p2.executable
    np.testing.assert_allclose(np.asarray(p2(bsr.values, x)),
                               np.asarray(p1(bsr.values, x)),
                               rtol=1e-5, atol=1e-5)


def test_disk_cache_stale_version_invalidated(tmp_path):
    bsr, x, _ = _problem()
    ctx = sparse.PlanContext(measure=True, cache_dir=str(tmp_path))
    sparse.plan(bsr, N, x=x, ctx=ctx)
    path = os.path.join(str(tmp_path),
                        f"sparse-plans-v{sparse.SCHEMA_VERSION}.json")
    blob = json.load(open(path))
    blob["env"]["jax"] = "0.0.0-stale"
    json.dump(blob, open(path, "w"))

    sparse.reset()
    p = sparse.plan(bsr, N, x=x, ctx=ctx)
    s = sparse.cache_stats()
    assert not p.from_disk and s["stale_drops"] == 1
    assert s["measurements"] == 2         # re-measured (fwd + bwd), then
    #                                       re-persisted
    blob2 = json.load(open(path))
    assert blob2["env"]["jax"] != "0.0.0-stale"


def test_disk_cache_carries_capacity_fields(tmp_path):
    """Persisted dynamic_grouped plans carry the planned-capacity
    section (tile, tiles_cap, headroom, ...) and a fresh process
    re-plans to the identical bucket."""
    bsr, x, _ = _problem()
    op = dsp.encode_from_bsr(bsr, nnz_max=bsr.nnz_blocks + 4)
    ctx = sparse.PlanContext(mode="dynamic_grouped", interpret=True,
                             cache_dir=str(tmp_path))
    p1 = sparse.plan(op, N, ctx=ctx)
    path = os.path.join(str(tmp_path),
                        f"sparse-plans-v{sparse.SCHEMA_VERSION}.json")
    blob = json.load(open(path))
    rec = blob["entries"][p1.key]
    assert rec["route"] == "dynamic_grouped"
    cap = rec["capacity"]
    assert cap["tiles_cap"] == p1.artifacts["grouped_tiles_cap"]
    assert cap["headroom"] == ctx.resolved_headroom()
    assert {"tile", "expected_tiles", "worst_tiles", "overflow_p",
            "policy"} <= set(cap)

    sparse.reset()                        # fresh-process simulation
    p2 = sparse.plan(op, N, ctx=ctx)
    assert p2.from_disk
    assert p2.artifacts["grouped_tiles_cap"] == cap["tiles_cap"]
    np.testing.assert_allclose(np.asarray(p2(op, x)),
                               np.asarray(p1(op, x)), rtol=0, atol=0)


def test_pre_capacity_cache_version_invalidated(tmp_path):
    """A cache written before the capacity schema (old version tag in
    the file name AND env) must be ignored -- never mis-read as a
    planned-capacity verdict."""
    bsr, x, _ = _problem()
    op = dsp.encode_from_bsr(bsr, nnz_max=bsr.nnz_blocks + 4)
    ctx = sparse.PlanContext(mode="dynamic_grouped", interpret=True,
                             cache_dir=str(tmp_path))
    key = sparse.plan(op, N, ctx=ctx).key
    sparse.reset()
    # simulate the pre-PR cache: v1 file name, v1 env tag, a record for
    # the same key with NO capacity section and a different route
    old = {"env": {"schema": 1, "backend": "cpu", "jax": "0.4.0"},
           "entries": {key: {"route": "dynamic_xla",
                             "source": "analytic", "est_seconds": {}}}}
    os.remove(os.path.join(
        str(tmp_path), f"sparse-plans-v{sparse.SCHEMA_VERSION}.json"))
    with open(os.path.join(str(tmp_path), "sparse-plans-v1.json"),
              "w") as f:
        json.dump(old, f)
    p = sparse.plan(op, N, ctx=ctx)
    assert not p.from_disk                    # old tag never satisfies
    assert p.route == "dynamic_grouped"
    assert "capacity" in p.artifacts


def test_disk_cache_corrupt_file_ignored(tmp_path):
    bsr, x, _ = _problem()
    path = os.path.join(str(tmp_path),
                        f"sparse-plans-v{sparse.SCHEMA_VERSION}.json")
    with open(path, "w") as f:
        f.write("{not json")
    ctx = sparse.PlanContext(cache_dir=str(tmp_path))
    p = sparse.plan(bsr, N, ctx=ctx)
    assert not p.from_disk
    assert sparse.cache_stats()["stale_drops"] == 1


def test_no_persistence_without_cache_dir():
    bsr, x, _ = _problem()
    sparse.plan(bsr, N, ctx=sparse.PlanContext(measure=True), x=x)
    s = sparse.cache_stats()
    assert s["disk_writes"] == 0 and s["disk_hits"] == 0


def test_explicit_persist_without_dir_raises():
    bsr, _, _ = _problem()
    with pytest.raises(ValueError, match="no cache directory"):
        sparse.plan(bsr, N, ctx=sparse.PlanContext(persist=True))


def test_use_ctx_ambient_planning_context(tmp_path):
    bsr, x, _ = _problem()
    ctx = sparse.PlanContext(cache_dir=str(tmp_path))
    with sparse.use_ctx(ctx):
        sparse.spmm(bsr, x)               # picks up the ambient ctx
    assert sparse.cache_stats()["disk_writes"] >= 1
    # outside the scope, persistence is off again
    sparse.reset()
    sparse.spmm(bsr, x)
    assert sparse.cache_stats()["disk_writes"] == 0


def test_format_plan_dynamic_grouped_no_crash():
    bsr, x, _ = _problem()
    op = dsp.encode_from_bsr(bsr, nnz_max=bsr.nnz_blocks)
    p = sparse.plan(op, N, ctx=sparse.PlanContext(mode="dynamic_grouped",
                                                  interpret=True))
    assert "grouped" in sparse.format_plan(p)


# -- deprecation-shim parity --------------------------------------------------

def test_dispatch_spmm_shim_matches_plan():
    bsr, x, oracle = _problem()
    y_shim = dispatch.spmm(bsr, x)
    p = sparse.plan(bsr, N)
    np.testing.assert_allclose(np.asarray(y_shim),
                               np.asarray(p(bsr.values, x)), rtol=0,
                               atol=0)
    np.testing.assert_allclose(np.asarray(y_shim), np.asarray(oracle),
                               rtol=1e-4, atol=1e-4)
    # the shim went through the plan cache
    assert sparse.cache_stats()["plans_built"] >= 1


def test_static_sparse_spmm_shim_matches_plan():
    bsr, x, oracle = _problem()
    y_shim = ssp.spmm(bsr, x, backend="xla")
    p = sparse.plan(bsr, N, ctx=sparse.PlanContext(mode="static_xla"))
    np.testing.assert_allclose(np.asarray(y_shim),
                               np.asarray(p(bsr.values, x)), rtol=0,
                               atol=0)
    np.testing.assert_allclose(np.asarray(y_shim), np.asarray(oracle),
                               rtol=1e-4, atol=1e-4)


def test_dspmm_shim_matches_plan_and_supports_grouped():
    bsr, x, oracle = _problem()
    op = dsp.encode_from_bsr(bsr, nnz_max=bsr.nnz_blocks + 2)
    for backend, route in (("xla", "dynamic_xla"),):
        y_shim = dsp.dspmm(op, x, backend=backend)
        p = sparse.plan(op, N, ctx=sparse.PlanContext(mode=route))
        np.testing.assert_allclose(np.asarray(y_shim),
                                   np.asarray(p(op, x)), rtol=0, atol=0)
    y_grp = dsp.dspmm(op, x, backend="grouped", interpret=True)
    np.testing.assert_allclose(np.asarray(y_grp), np.asarray(oracle),
                               rtol=1e-4, atol=1e-4)


def test_sparse_matmul_and_batched_matmul():
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 8, 32))
    w = jax.random.normal(jax.random.PRNGKey(1), (32, 16))
    np.testing.assert_allclose(np.asarray(sparse.matmul(x, w)),
                               np.asarray(x @ w), rtol=1e-5, atol=1e-5)
    a = jax.random.normal(jax.random.PRNGKey(2), (3, 8, 16))
    b = jax.random.normal(jax.random.PRNGKey(3), (3, 16, 24))
    np.testing.assert_allclose(np.asarray(sparse.batched_matmul(a, b)),
                               np.asarray(jnp.matmul(a, b)), rtol=1e-5,
                               atol=1e-5)
    # second calls are plan-cache hits
    base = sparse.cache_stats()["plans_built"]
    sparse.matmul(x, w)
    sparse.batched_matmul(a, b)
    assert sparse.cache_stats()["plans_built"] == base


# -- dynamic_grouped as a dispatch candidate ----------------------------------

def test_dynamic_grouped_in_candidates():
    ctx = dispatch.DispatchContext(allow_pallas=True, differentiable=False)
    assert "dynamic_grouped" in dispatch._candidates("dynamic", ctx)
    # never offered to differentiable callers (forward-only kernel)
    grad_ctx = dispatch.DispatchContext(allow_pallas=True)
    assert "dynamic_grouped" not in dispatch._candidates("dynamic",
                                                         grad_ctx)


def test_dynamic_grouped_padded_capacity_exact_cap():
    """Padding slots (capacity > nnz) must not claim a tile slot: with
    tiles_cap == the exact true tile count the result is still exact."""
    # kernel-level capacity semantics under test: direct entry is
    # the point here, like tests/test_kernels.py
    from repro.kernels.gmm import ops as gmm_ops  # repro-lint: disable=R001
    bsr = _bsr(3, m=256, k=256, b=16, d=0.1)
    op = dsp.encode_from_bsr(bsr, nnz_max=bsr.nnz_blocks + 7)  # padded
    x = jax.random.normal(jax.random.PRNGKey(1), (256, 32))
    t = gmm_ops.grouped_tile_size(256, 256, 16)
    from repro.core.partitioner import plan_packing
    true_tiles = plan_packing(np.asarray(bsr.row_idx),
                              np.asarray(bsr.col_idx), (256, 256), 16,
                              t, t).num_tiles
    y = gmm_ops.grouped_spmm(op, x, tiles_cap=true_tiles, interpret=True)
    np.testing.assert_allclose(
        np.asarray(y), np.asarray(jnp.asarray(bsr.to_dense()) @ x),
        rtol=1e-4, atol=1e-4)


def test_dynamic_grouped_empty_operand_returns_zeros():
    # kernel-level capacity semantics under test: direct entry is
    # the point here, like tests/test_kernels.py
    from repro.kernels.gmm import ops as gmm_ops  # repro-lint: disable=R001
    op = dsp.DynamicOperand(jnp.zeros((0, 16, 16)),
                            jnp.zeros((0,), jnp.int32),
                            jnp.zeros((0,), jnp.int32),
                            jnp.asarray(0, jnp.int32), (128, 128), 16)
    x = jax.random.normal(jax.random.PRNGKey(0), (128, 8))
    y = gmm_ops.grouped_spmm(op, x, interpret=True)
    np.testing.assert_allclose(np.asarray(y), 0.0, atol=0.0)


def test_persistent_ctx_not_shadowed_by_prior_plan(tmp_path):
    """A plan built WITHOUT persistence must not satisfy a later
    persistent request from the memory cache (the disk write would be
    silently skipped and restarts would re-measure)."""
    bsr, x, _ = _problem()
    sparse.plan(bsr, N)                       # non-persistent first
    ctx = sparse.PlanContext(cache_dir=str(tmp_path))
    sparse.plan(bsr, N, ctx=ctx)              # persistent same problem
    assert sparse.cache_stats()["disk_writes"] >= 1


def test_plan_call_validates_contraction_dim():
    bsr, x, _ = _problem()
    p = sparse.plan(bsr, N)
    with pytest.raises(ValueError, match=f"k={K}"):
        p(bsr.values, jnp.zeros((K // 2, N)))
    # a different n than planned is fine (tiling re-derives at trace)
    y = p(bsr.values, jax.random.normal(jax.random.PRNGKey(0), (K, 24)))
    assert y.shape == (M, 24)


def test_static_pallas_plan_handles_unplanned_n():
    bsr, _, _ = _problem()
    p = sparse.plan(bsr, N, ctx=sparse.PlanContext(mode="static_pallas",
                                                   interpret=True))
    x96 = jax.random.normal(jax.random.PRNGKey(2), (K, 96))   # n != N
    np.testing.assert_allclose(
        np.asarray(p(bsr.values, x96)),
        np.asarray(jnp.asarray(bsr.to_dense()) @ x96), rtol=1e-4,
        atol=1e-4)


def test_dynamic_grouped_overflow_drops_like_buckets():
    """With a tile capacity below the distinct-tile count, overflow
    tiles are dropped -- the paper's fixed-bucket overflow semantics."""
    # kernel-level capacity semantics under test: direct entry is
    # the point here, like tests/test_kernels.py
    from repro.kernels.gmm import ops as gmm_ops  # repro-lint: disable=R001
    bsr = _bsr(0, m=256, k=256, b=16, d=0.25)
    op = dsp.encode_from_bsr(bsr, nnz_max=bsr.nnz_blocks)
    x = jax.random.normal(jax.random.PRNGKey(1), (256, 32))
    full = gmm_ops.grouped_spmm(op, x, interpret=True)
    np.testing.assert_allclose(
        np.asarray(full), np.asarray(jnp.asarray(bsr.to_dense()) @ x),
        rtol=1e-4, atol=1e-4)
    clipped = gmm_ops.grouped_spmm(op, x, tiles_cap=1, interpret=True)
    assert np.isfinite(np.asarray(clipped)).all()


# -- DynamicOperand grid + validation (satellite fixes) -----------------------

def test_dynamic_operand_grid_matches_bsr_grid():
    bsr = _bsr()
    op = dsp.encode_from_bsr(bsr, nnz_max=bsr.nnz_blocks)
    assert op.grid == bsr.grid


def test_dynamic_operand_rejects_non_divisible_shape():
    with pytest.raises(ValueError, match="not divisible"):
        dsp.DynamicOperand(jnp.zeros((1, 16, 16)), jnp.zeros((1,), jnp.int32),
                           jnp.zeros((1,), jnp.int32),
                           jnp.asarray(1, jnp.int32), (60, 64), 16)


def test_encode_from_bsr_clear_capacity_error():
    bsr = _bsr()
    with pytest.raises(ValueError, match="exceeds capacity"):
        dsp.encode_from_bsr(bsr, nnz_max=bsr.nnz_blocks - 1)


# -- moe / engine steady state ------------------------------------------------

def test_moe_expert_gemms_plan_once():
    """Expert GEMMs build their plans on the first call; later steps
    (same shapes) issue zero new dispatch decisions."""
    from repro.configs import qwen3_moe_30b_a3b
    from repro.models.moe import moe_apply, moe_init
    cfg = qwen3_moe_30b_a3b.make_smoke_config()
    params = moe_init(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model))
    moe_apply(params, cfg, x)
    base = sparse.cache_stats()
    for i in range(3):
        moe_apply(params, cfg,
                  jax.random.normal(jax.random.PRNGKey(2 + i), x.shape))
    now = sparse.cache_stats()
    assert now["decisions"] == base["decisions"]
    assert now["plans_built"] == base["plans_built"]


@pytest.mark.slow
def test_engine_builds_plans_at_startup_and_stays_decision_free():
    from repro import configs
    from repro.models.model import LM
    from repro.serve import Engine, Request
    cfg = configs.smoke("llama3_2_1b")
    lm = LM(cfg)
    params = lm.init(jax.random.PRNGKey(0))
    eng = Engine(lm, params, batch=2, max_len=64)
    # startup warm built the decode program's plans
    assert eng.plan_stats["plans_built"] + eng.plan_stats["plan_hits"] > 0
    req = Request(uid=0, prompt=np.arange(8, dtype=np.int32),
                  max_new_tokens=4)
    eng.run([req])
    base = sparse.cache_stats()
    req2 = Request(uid=1, prompt=np.arange(8, dtype=np.int32),
                   max_new_tokens=4)
    eng.run([req2])                       # steady state: same shapes
    now = sparse.cache_stats()
    assert now["decisions"] == base["decisions"]
    assert now["plans_built"] == base["plans_built"]
    rep = eng.plan_report()
    assert "startup" in rep
    # aggregated capacity/overflow telemetry rides along (per-plan
    # planned-bucket stats + MoE drops; totals always present)
    assert "totals" in rep["capacity"]
    # roofline efficiency of every held plan rides along too
    assert rep["roofline"]["totals"]["plans"] > 0
    eff = rep["roofline"]["totals"]["min_chosen_efficiency"]
    assert eff is None or 0 < eff <= 1.0


# -- tensor-parallel plans: measured race, mesh-keyed cache, TP report --------

NDEV = len(jax.devices())
needs_mesh2 = pytest.mark.skipif(
    NDEV < 2, reason="needs >= 2 devices "
    "(XLA_FLAGS=--xla_force_host_platform_device_count=8)")


def test_static_tp_shardmap_mode_requires_concrete_mesh():
    """tp_q alone can only execute the gspmd lowering; forcing the
    shard_map route without a device-backed mesh is an error, not a
    silent substitution."""
    bsr, _, _ = _problem()
    with pytest.raises(ValueError, match="static_tp_shardmap"):
        sparse.plan(bsr, N, ctx=sparse.PlanContext(
            mode="static_tp_shardmap", tp_q=4))


def test_mesh_without_tp_axis_raises():
    """Regression: a mesh whose axes do not include tp_axis used to
    silently plan unsharded; it must raise naming the expected axis."""
    bsr, _, _ = _problem()
    mesh = make_mesh((1,), ("x",))
    with pytest.raises(ValueError, match=r"tp_axis 'model'"):
        sparse.plan(bsr, N, ctx=sparse.PlanContext(mesh=mesh))
    # naming the right axis (or an explicit tp_q) fixes it
    p = sparse.plan(bsr, N, ctx=sparse.PlanContext(mesh=mesh,
                                                   tp_axis="x"))
    assert p.executable


def test_tp_decision_surfaced_in_explain_and_report():
    bsr, x, oracle = _problem()
    p = sparse.plan(bsr, N, ctx=sparse.PlanContext(mode="static_tp",
                                                   tp_q=4,
                                                   tp_balanced=False))
    tp = p.explain()["tp"]
    assert tp["chosen"] == "static_tp" and tp["q"] == 4
    assert tp["balanced"] is False and p.artifacts["tp_balanced"] is False
    np.testing.assert_allclose(np.asarray(p(bsr.values, x)),
                               np.asarray(oracle), rtol=1e-4, atol=1e-4)
    rep = sparse.tp_report()
    assert rep["totals"]["tp_planned"] == 1
    assert rep["totals"]["tp_chosen"] == 1
    assert "tp:" in sparse.format_plan(p)


@needs_mesh2
def test_tp_measured_race_gspmd_vs_shardmap_vs_unsharded():
    """The ROADMAP acceptance: with a real mesh, plan() races both TP
    lowerings against the unsharded candidates with wall-clock timings
    and surfaces the crossover."""
    bsr, x, oracle = _problem()
    mesh = make_mesh((NDEV,), ("model",))
    p = sparse.plan(bsr, N, x=x,
                    ctx=sparse.PlanContext(mesh=mesh, measure=True))
    assert p.source == "measured"
    assert {"static_tp", "static_tp_shardmap"} <= set(p.est_seconds)
    tp = p.artifacts["tp"]
    assert tp["source"] == "measured" and tp["mesh"] == {"model": NDEV}
    assert tp["tp_speedup_vs_unsharded"] is not None
    assert tp["best_tp_route"] in sparse.TP_ROUTES
    # whatever route won the race, the numbers are right
    np.testing.assert_allclose(np.asarray(p.apply(bsr, x)),
                               np.asarray(oracle), rtol=1e-4, atol=1e-4)


@needs_mesh2
def test_tp_verdict_disk_round_trip_is_mesh_keyed(tmp_path):
    """A measured TP verdict persists, restarts re-plan with zero
    measurements, and a different mesh topology never reuses it."""
    bsr, x, _ = _problem()
    mesh = make_mesh((NDEV,), ("model",))
    ctx = sparse.PlanContext(mesh=mesh, measure=True,
                             cache_dir=str(tmp_path))
    p1 = sparse.plan(bsr, N, x=x, ctx=ctx)
    assert sparse.cache_stats()["measurements"] >= 1

    sparse.reset()                        # fresh-process simulation
    p2 = sparse.plan(bsr, N, x=x, ctx=ctx)
    assert p2.from_disk and p2.route == p1.route
    assert sparse.cache_stats()["measurements"] == 0
    assert p2.artifacts["tp"]["mesh"] == {"model": NDEV}

    # same devices arranged as a different topology -> different key
    sub = make_mesh((1, NDEV), ("data", "model"))
    sparse.reset()
    p3 = sparse.plan(bsr, N, x=x,
                     ctx=dataclasses.replace(ctx, mesh=sub))
    assert not p3.from_disk


def test_pre_tp_schema_cache_invalidated(tmp_path):
    """A v2 (pre-mesh-fingerprint) cache file must be ignored: its TP
    verdicts were keyed on (q, axis) only and could answer for the
    wrong mesh topology."""
    bsr, x, _ = _problem()
    ctx = sparse.PlanContext(mode="static_tp", tp_q=4,
                             cache_dir=str(tmp_path))
    key = sparse.plan(bsr, N, ctx=ctx).key
    sparse.reset()
    os.remove(os.path.join(
        str(tmp_path), f"sparse-plans-v{sparse.SCHEMA_VERSION}.json"))
    old = {"env": {"schema": 2, "backend": jax.default_backend(),
                   "jax": jax.__version__},
           "entries": {key: {"route": "static_xla",
                             "source": "measured", "est_seconds": {}}}}
    with open(os.path.join(str(tmp_path), "sparse-plans-v2.json"),
              "w") as f:
        json.dump(old, f)
    p = sparse.plan(bsr, N, ctx=ctx)
    assert not p.from_disk                    # old tag never satisfies
    assert p.route == "static_tp"


def test_tp_q_and_mesh_fingerprints_differ():
    """A tp_q-only plan (no mesh) and a mesh-backed plan of the same q
    must not share a memory-cache entry."""
    bsr, _, _ = _problem()
    import importlib
    plan_mod = importlib.import_module("repro.sparse.plan")
    spec = sparse.OpSpec.from_operand(bsr, N, mode="auto")
    fp_q = plan_mod._fingerprint(spec, sparse.PlanContext(tp_q=2))
    mesh = make_mesh((1,), ("model",))
    fp_mesh = plan_mod._fingerprint(
        spec, sparse.PlanContext(mesh=mesh, tp_q=2))
    assert fp_q != fp_mesh


@needs_mesh2
def test_tp_race_remeasures_stale_analytic_unsharded_verdict():
    """A traced first plan leaves an *analytic* unsharded verdict in
    the decision cache under the measure=True key; a later concrete
    plan must re-measure that side rather than race model-seconds
    against wall-clock TP timings (incomparable units)."""
    bsr1, x, _ = _problem(seed=0)
    bsr2 = _bsr(seed=7)                   # same shapes, fresh pattern
    mesh = make_mesh((NDEV,), ("model",))
    ctx = sparse.PlanContext(mesh=mesh, measure=True)
    p1 = sparse.plan(bsr1, N, ctx=ctx)    # no x -> analytic, cached
    assert p1.source == "analytic"
    p2 = sparse.plan(bsr2, N, x=x, ctx=ctx)
    assert p2.source == "measured"
    un = p2.artifacts["tp"]["best_unsharded_route"]
    # the unsharded side was wall-clocked afresh, not replayed from the
    # analytic decision-cache entry
    assert p2.est_seconds[un] != p1.est_seconds[un]


def test_abstract_mesh_plans_gspmd_only():
    """An AbstractMesh (shape-only, no devices -- what tracing-time
    warmup sees) must plan fine with the shard_map route excluded, not
    crash probing .devices."""
    from jax.sharding import AbstractMesh
    amesh = AbstractMesh((8,), ("model",))
    bsr, _, _ = _problem()
    ctx = sparse.PlanContext(mesh=amesh)
    assert not ctx.shardmap_executable()
    p = sparse.plan(bsr, N, ctx=ctx)
    assert "static_tp_shardmap" not in p.est_seconds
    assert "static_tp" in p.est_seconds   # gspmd candidate still raced


# -- packed payloads: the bsmm routes' tiles packed once, for fixed weights --

def _empty_row_tile_bsr(dtype=jnp.bfloat16):
    """512 x 256 in blocks of 16 (128 x 128 tiles): row-tile 1 holds no
    block, so the walk's coverage tile and the pad tile both show."""
    mask = np.random.default_rng(3).random((32, 16)) < 0.25
    mask[8:16] = False
    return BlockSparseMatrix.from_mask(mask, 16, dtype=dtype, init="normal",
                                       key=jax.random.PRNGKey(3))


@pytest.mark.parametrize("n", [1, 32, 200])       # 200: not a multiple of tn
@pytest.mark.parametrize("route", sparse.PACKED_ROUTES)
def test_packed_payload_matches_per_call_relayout(route, n):
    bsr = _empty_row_tile_bsr()
    x = jax.random.normal(jax.random.PRNGKey(4), (256, n)).astype(bsr.dtype)
    p = sparse.plan(bsr, n, ctx=sparse.PlanContext(
        mode=route, interpret=True, differentiable=False))
    assert p.takes_packed
    packed = sparse.pack(bsr)
    # the kernels' layout plus one zero pad tile, one stack at every n
    assert packed.tiles.shape == (p.artifacts["packing_tiles"] + 1, 128, 128)
    assert not np.asarray(packed.tiles[-1], np.float32).any()
    np.testing.assert_array_equal(np.asarray(p(packed, x), np.float32),
                                  np.asarray(p(bsr.values, x), np.float32))
    # and the packed call runs no relayout
    jaxpr = str(jax.make_jaxpr(lambda t, xx: p(t, xx))(packed, x))
    assert "scatter" not in jaxpr and "concatenate" not in jaxpr


def test_packed_payload_is_refused_where_the_values_run():
    bsr = _empty_row_tile_bsr()
    x = jnp.ones((256, 8), bsr.dtype)
    packed = sparse.pack(bsr)
    for ctx in (sparse.PlanContext(mode="static_xla", differentiable=False),
                sparse.PlanContext(mode="static_pallas", interpret=True,
                                   differentiable=True)):
        p = sparse.plan(bsr, 8, ctx=ctx)
        assert not p.takes_packed
        with pytest.raises(ValueError, match="packed tiles"):
            p(packed, x)
        # spmm runs the values there instead
        np.testing.assert_array_equal(
            np.asarray(sparse.spmm(bsr, x, ctx=ctx, packed=packed)),
            np.asarray(p(bsr.values, x)))
    # a stack packed for another pattern does not fit this plan's layout
    other = BlockSparseMatrix.from_mask(np.ones((32, 16), bool), 16,
                                        dtype=bsr.dtype)
    p = sparse.plan(bsr, 8, ctx=sparse.PlanContext(
        mode="static_pallas", interpret=True, differentiable=False))
    with pytest.raises(ValueError, match="layout"):
        p(sparse.pack(other), x)


def _sparse_linear():
    from repro.core.sparse_layers import SparseLinear
    layer = SparseLinear.random_pattern(None, 256, 512, 16, 0.25, seed=5,
                                        dtype=jnp.bfloat16)
    params = layer.init(jax.random.PRNGKey(6))
    return layer, params, dict(params, packed=layer.pack(params["values"]))


@pytest.mark.parametrize("route", sparse.PACKED_ROUTES)
def test_sparse_linear_apply_with_packed_tiles(route):
    layer, params, served = _sparse_linear()
    x = jax.random.normal(jax.random.PRNGKey(7), (3, 5, 256))
    ctx = sparse.PlanContext(mode=route, interpret=True, differentiable=False)
    with sparse.use_ctx(ctx):
        want = layer.apply(params, x)
        got = layer.apply(served, x)
        jaxpr = str(jax.make_jaxpr(layer.apply)(served, x))
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))
    assert "scatter" not in jaxpr
    # a layer stack packs one matrix at a time, each as it packs alone
    stack = jnp.stack([params["values"], 2 * params["values"]])
    tiles = layer.pack(stack).tiles
    assert tiles.shape[0] == 2
    np.testing.assert_array_equal(
        np.asarray(tiles[1], np.float32),
        np.asarray(layer.pack(2 * params["values"]).tiles, np.float32))


def test_differentiable_apply_ignores_packed_tiles():
    layer, params, served = _sparse_linear()
    x = jax.random.normal(jax.random.PRNGKey(8), (4, 256))
    ctx = sparse.PlanContext(mode="static_pallas", interpret=True,
                             differentiable=True)

    def loss(p):
        return jnp.sum(layer.apply(p, x).astype(jnp.float32) ** 2)

    with sparse.use_ctx(ctx):
        want = jax.grad(loss)(params)
        got = jax.grad(loss)(served)
    assert np.abs(np.asarray(got["values"], np.float32)).max() > 0
    np.testing.assert_array_equal(np.asarray(got["values"], np.float32),
                                  np.asarray(want["values"], np.float32))
    assert not np.asarray(got["packed"].tiles, np.float32).any()


def test_evolve_drops_tiles_packed_for_the_old_pattern():
    layer, _, served = _sparse_linear()
    grown = layer.pattern.copy()
    grown[0, :] = True
    _, params = layer.evolve(grown, served)
    assert "packed" not in params and "values" in params
