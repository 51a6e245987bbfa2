"""Plan-lifecycle coverage for the planned backward (differentiable
plans): disk round-trip with zero re-measurement of the backward
verdicts, v3-file invalidation, grad knobs in the fingerprint, the
no-VJP clear error (satellite fix), and vjp under jit / vmap / the
train-step microbatch gradient-accumulation scan."""
import importlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import assert_close_for_dtype

from repro import sparse
from repro.core import dispatch, dynamic_sparse as dsp
from repro.core.bsr import BlockSparseMatrix
from repro.core.sparse_layers import SparseLinear
from repro.train.step import microbatch_grads

M, K, N, B, DENSITY = 128, 256, 64, 16, 0.25


@pytest.fixture(autouse=True)
def _fresh_state():
    sparse.reset()
    sparse.configure(None)
    yield
    sparse.reset()
    sparse.configure(None)


def _problem(seed=0):
    bsr = BlockSparseMatrix.random(jax.random.PRNGKey(seed), M, K, B,
                                   DENSITY, pattern_seed=seed)
    x = jax.random.normal(jax.random.PRNGKey(seed + 100), (K, N))
    return bsr, x


def _grads(p, bsr, x):
    return jax.grad(lambda v, xx: (p(v, xx) ** 2).sum(),
                    argnums=(0, 1))(jnp.asarray(bsr.values), x)


# -- persistence: backward verdicts ride in the forward record ----------------

def test_grad_verdicts_disk_round_trip_zero_remeasure(tmp_path):
    """Measured fwd+bwd verdicts persist in one record; a restarted
    process re-plans both with ZERO measurements."""
    bsr, x = _problem()
    ctx = sparse.PlanContext(measure=True, cache_dir=str(tmp_path))
    p1 = sparse.plan(bsr, N, x=x, ctx=ctx)
    g1 = p1.explain()["grad"]
    assert g1["mode"] == "planned"
    assert g1["dx"]["source"] == "measured"
    assert g1["dvalues"]["source"] == "measured"
    assert not g1["from_disk"]
    assert sparse.cache_stats()["measurements"] == 2   # fwd race + bwd race

    path = os.path.join(str(tmp_path),
                        f"sparse-plans-v{sparse.SCHEMA_VERSION}.json")
    rec = json.load(open(path))["entries"][p1.key]
    assert rec["grad"]["dx"]["route"] == g1["dx"]["route"]
    assert rec["grad"]["dvalues"]["route"] == g1["dvalues"]["route"]

    sparse.reset()                        # fresh-process simulation
    p2 = sparse.plan(bsr, N, x=x, ctx=ctx)
    s2 = sparse.cache_stats()
    assert s2["measurements"] == 0        # zero re-measurement, fwd AND bwd
    g2 = p2.explain()["grad"]
    assert g2["from_disk"] and p2.from_disk
    assert g2["dx"]["route"] == g1["dx"]["route"]
    assert g2["dvalues"]["route"] == g1["dvalues"]["route"]
    assert g2["dx"]["source"] == "measured"     # provenance preserved
    # the replayed backward is numerically identical
    gv1, gx1 = _grads(p1, bsr, x)
    gv2, gx2 = _grads(p2, bsr, x)
    np.testing.assert_allclose(np.asarray(gv1), np.asarray(gv2), rtol=0,
                               atol=0)
    np.testing.assert_allclose(np.asarray(gx1), np.asarray(gx2), rtol=0,
                               atol=0)


def test_pre_grad_v3_cache_file_invalidated(tmp_path):
    """A v3 (pre-grad-schema) cache file must be ignored wholesale: its
    records carry no backward verdicts, so replaying one would skip the
    backward decisions a restart is entitled to."""
    bsr, x = _problem()
    ctx = sparse.PlanContext(cache_dir=str(tmp_path))
    key = sparse.plan(bsr, N, ctx=ctx).key
    sparse.reset()
    os.remove(os.path.join(
        str(tmp_path), f"sparse-plans-v{sparse.SCHEMA_VERSION}.json"))
    old = {"env": {"schema": 3, "backend": jax.default_backend(),
                   "jax": jax.__version__},
           "entries": {key: {"route": "dense_xla", "source": "measured",
                             "est_seconds": {}}}}
    with open(os.path.join(str(tmp_path), "sparse-plans-v3.json"),
              "w") as f:
        json.dump(old, f)
    p = sparse.plan(bsr, N, ctx=ctx)
    assert not p.from_disk                # old tag never satisfies
    assert p.explain()["grad"]["mode"] == "planned"
    assert not p.explain()["grad"]["from_disk"]


def test_grad_knobs_in_fingerprint():
    """grad_mode / sddmm_mode are part of the plan identity: forcing a
    backward route must not be answered by an auto-raced plan (and vice
    versa), in memory or on disk."""
    bsr, _ = _problem()
    plan_mod = importlib.import_module("repro.sparse.plan")
    spec = sparse.OpSpec.from_operand(bsr, N)
    fp_auto = plan_mod._fingerprint(spec, sparse.PlanContext())
    fp_dx = plan_mod._fingerprint(
        spec, sparse.PlanContext(grad_mode="dense_xla"))
    fp_dv = plan_mod._fingerprint(
        spec, sparse.PlanContext(sddmm_mode="sddmm_xla"))
    assert len({fp_auto, fp_dx, fp_dv}) == 3
    # forward-only plans carry no grad section in the fingerprint
    fp_fwd = plan_mod._fingerprint(
        spec, sparse.PlanContext(differentiable=False))
    assert not any(part == "grad" for part in
                   jax.tree_util.tree_leaves(fp_fwd))

    p_auto = sparse.plan(bsr, N)
    p_forced = sparse.plan(bsr, N,
                           ctx=sparse.PlanContext(grad_mode="dense_xla"))
    assert p_forced is not p_auto
    assert p_forced.explain()["grad"]["dx"]["route"] == "dense_xla"
    assert p_forced.explain()["grad"]["dx"]["source"] == "forced"


def test_grad_mode_validation():
    with pytest.raises(ValueError, match="grad_mode"):
        sparse.PlanContext(grad_mode="bogus")
    with pytest.raises(ValueError, match="sddmm_mode"):
        sparse.PlanContext(sddmm_mode="static_xla")


# -- satellite fix: clear no-VJP error ----------------------------------------

@pytest.mark.parametrize("mode,kind", [("dynamic_grouped", "dynamic"),
                                       ("static_pallas", "static"),
                                       ("dense_pallas", "dense")])
def test_no_vjp_routes_raise_clear_error(mode, kind):
    """Regression: differentiating a forward-only plan used to die deep
    inside Pallas (or silently fall off the fast path).  It must raise
    naming the route and the ``mode=`` workaround."""
    bsr, x = _problem()
    if kind == "dynamic":
        payload = dsp.encode_from_bsr(bsr, nnz_max=bsr.nnz_blocks)
        operand = payload
    elif kind == "dense":
        operand = jnp.asarray(bsr.to_dense())
        payload = operand
    else:
        operand = bsr
        payload = jnp.asarray(bsr.values)
    p = sparse.plan(operand, N, ctx=sparse.PlanContext(
        mode=mode, interpret=True, differentiable=False))
    with pytest.raises(ValueError, match=f"{mode}.*no registered VJP"):
        if kind == "dynamic":
            jax.grad(lambda v: p(dsp.DynamicOperand(
                v, payload.row_idx, payload.col_idx, payload.nnz,
                payload.shape, payload.block_size), x).sum())(
                    jnp.asarray(payload.values))
        else:
            jax.grad(lambda v: p(v, x).sum())(payload)
    # the error names the workaround
    try:
        if kind == "static":
            jax.grad(lambda v: p(v, x).sum())(payload)
    except ValueError as e:
        assert "mode=" in str(e) or "differentiable=True" in str(e)


def test_batched_matmul_dense_pallas_grad_raises():
    a = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 16))
    b = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 8))
    ctx = sparse.PlanContext(mode="dense_pallas", interpret=True)
    with pytest.raises(ValueError, match="no registered VJP"):
        jax.grad(lambda aa: sparse.batched_matmul(aa, b, ctx=ctx).sum())(a)


def test_dense_pallas_matmul_planned_backward():
    """Forced dense_pallas matmul plans (differentiable) backprop
    through the planned dense products instead of failing."""
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 32))
    w = jax.random.normal(jax.random.PRNGKey(1), (32, 16))
    ctx = sparse.PlanContext(mode="dense_pallas", interpret=True)
    gw, gx = jax.grad(
        lambda w_, x_: (sparse.matmul(x_, w_, ctx=ctx) ** 2).sum(),
        argnums=(0, 1))(w, x)
    gw_d, gx_d = jax.grad(
        lambda w_, x_: ((x_ @ w_) ** 2).sum(), argnums=(0, 1))(w, x)
    assert_close_for_dtype(gw, gw_d, "float32", "dense_pallas dW")
    assert_close_for_dtype(gx, gx_d, "float32", "dense_pallas dX")


# -- vjp under jit / vmap / gradient accumulation -----------------------------

def test_plan_vjp_under_jit_and_vmap():
    bsr, x = _problem()
    p = sparse.plan(bsr, N)
    v = jnp.asarray(bsr.values)

    def loss(v_, x_):
        return (p(v_, x_) ** 2).sum()

    gv_e, gx_e = jax.grad(loss, argnums=(0, 1))(v, x)
    gv_j, gx_j = jax.jit(jax.grad(loss, argnums=(0, 1)))(v, x)
    np.testing.assert_allclose(np.asarray(gv_e), np.asarray(gv_j),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(gx_e), np.asarray(gx_j),
                               rtol=1e-6, atol=1e-6)
    # per-example grads: vmap over a batch of activations
    xb = jax.random.normal(jax.random.PRNGKey(7), (3, K, 8))
    gxb = jax.vmap(jax.grad(lambda x_: (p(v, x_) ** 2).sum()))(xb)
    # vmap reorders the fp32 sums; at magnitudes ~250 one ulp is 3e-5,
    # so the budget is relative to the gradient's max norm
    for i in range(3):
        gi = jax.grad(lambda x_: (p(v, x_) ** 2).sum())(xb[i])
        assert_close_for_dtype(gxb[i], gi, "float32", f"vmapped dx[{i}]")


def test_plan_grad_accumulation_microbatch_scan():
    """The planned backward composes with the production train-step
    accumulation scan (train/step.microbatch_grads): accumulated
    microbatch grads == full-batch grads."""
    bsr, _ = _problem()
    p = sparse.plan(bsr, N)
    params = {"values": jnp.asarray(bsr.values)}
    batch = jax.random.normal(jax.random.PRNGKey(3), (8, K, N))

    def loss_fn(params_, mb):
        y = jax.vmap(lambda x_: p(params_["values"], x_))(mb)
        loss = (y ** 2).mean()
        return loss, {"l2": loss}

    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)
    loss1, m1, g1 = microbatch_grads(grad_fn, params, batch, accum=1)
    loss4, m4, g4 = jax.jit(
        lambda pp, bb: microbatch_grads(grad_fn, pp, bb, accum=4))(
            params, batch)
    np.testing.assert_allclose(float(loss1), float(loss4), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(g1["values"]),
                               np.asarray(g4["values"]), rtol=1e-5,
                               atol=1e-6)
    assert np.isfinite(float(m4["l2"]))


def test_sparse_linear_trains_through_planned_backward():
    """SparseLinear's backward runs the planned siblings (and the layer
    knobs force backward routes end-to-end)."""
    layer = SparseLinear.random_pattern(None, K, M, B, DENSITY,
                                        grad_backend="static_xla",
                                        sddmm_backend="sddmm_xla")
    params = layer.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (4, K))

    g = jax.grad(lambda pp: (layer.apply(pp, x) ** 2).sum())(params)
    assert np.isfinite(np.asarray(g["values"], np.float32)).all()
    rep = sparse.plan_report()
    planned = [r for r in rep["per_plan"].values()
               if (r["grad"] or {}).get("mode") == "planned"]
    assert planned
    assert planned[0]["grad"]["dx"]["route"] == "static_xla"
    assert planned[0]["grad"]["dvalues"]["route"] == "sddmm_xla"


# -- reporting ----------------------------------------------------------------

def test_grad_in_explain_format_and_report():
    bsr, x = _problem()
    p = sparse.plan(bsr, N)
    rep = p.explain()
    assert rep["grad"]["mode"] == "planned"
    assert rep["grad"]["dx"]["route"] in dispatch.ROUTES
    assert rep["grad"]["dvalues"]["route"] in dispatch.SDDMM_ROUTES
    assert "grad:" in sparse.format_plan(p)
    totals = sparse.plan_report()["totals"]
    assert totals["plans"] == 1 and totals["grad_planned"] == 1

    # forward-only plans are reported, not grad-planned
    sparse.reset()
    sparse.plan(bsr, N, ctx=sparse.PlanContext(differentiable=False))
    totals = sparse.plan_report()["totals"]
    assert totals["plans"] == 1 and totals["grad_planned"] == 0


def test_spec_only_dynamic_plan_still_differentiable():
    """Dynamic plans built from an OpSpec (no concrete pattern) keep the
    runtime-index backward."""
    bsr, x = _problem()
    op = dsp.encode_from_bsr(bsr, nnz_max=bsr.nnz_blocks)
    spec = sparse.OpSpec.from_operand(op, N)
    p = sparse.plan(spec, ctx=sparse.PlanContext(mode="dynamic_xla"))
    gx = jax.grad(lambda x_: (p(op, x_) ** 2).sum())(x)
    gx_d = jax.grad(
        lambda x_: ((jnp.asarray(bsr.to_dense()) @ x_) ** 2).sum())(x)
    assert_close_for_dtype(gx, gx_d, "float32", "spec-only dynamic dX")
