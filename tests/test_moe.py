"""MoE dispatch: the paper's dynamic sparsity at layer scale."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import qwen3_moe_30b_a3b
from repro.models import moe as moe_lib
from repro.models.moe import moe_apply, moe_init
from repro.launch.mesh import make_mesh


import pytest

# model-level MoE dispatch: excluded from the fast tier-1 run (see pytest.ini)
pytestmark = pytest.mark.slow


def _cfg(**over):
    cfg = qwen3_moe_30b_a3b.make_smoke_config()
    if over:
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, **over))
    return cfg


def _dense_reference(params, cfg, x):
    """Route every token through its top-k experts with no capacity."""
    m = cfg.moe
    b, s, d = x.shape
    xf = x.reshape(-1, d)
    logits = xf.astype(jnp.float32) @ params["router"]["w"]
    scores = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(scores, m.top_k)
    if m.norm_topk_prob:
        top_p = top_p / top_p.sum(-1, keepdims=True)
    out = jnp.zeros_like(xf, dtype=jnp.float32)
    for e in range(m.num_experts):
        h = jax.nn.silu(xf @ params["w_gate"][e]) * (xf @ params["w_up"][e])
        ye = h @ params["w_down"][e]
        for kk in range(m.top_k):
            w = jnp.where(top_e[:, kk] == e, top_p[:, kk], 0.0)
            out += ye.astype(jnp.float32) * w[:, None]
    return out.reshape(b, s, d)


def test_moe_matches_dense_reference_when_no_drops():
    cfg = _cfg(capacity_factor=64.0)
    params = moe_init(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model))
    got, metrics = moe_apply(params, cfg, x)
    want = _dense_reference(params, cfg, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)
    assert float(metrics.dropped_frac) == 0.0


def test_moe_capacity_drops_accounted():
    cfg = _cfg(capacity_factor=0.25)     # force overflow
    params = moe_init(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 64, cfg.d_model))
    _, metrics = moe_apply(params, cfg, x)
    assert float(metrics.dropped_frac) > 0.0


def test_moe_aux_loss_uniform_router_is_one():
    """Switch LB loss equals 1.0 (its minimum, num_experts * (1/E)*(1/E)*E)
    under a perfectly uniform router."""
    cfg = _cfg(capacity_factor=64.0)
    params = moe_init(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)
    params["router"]["w"] = jnp.zeros_like(params["router"]["w"])
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 64, cfg.d_model))
    _, metrics = moe_apply(params, cfg, x)
    # uniform scores: frac_e == probs_mean_e == 1/E -> aux == 1
    np.testing.assert_allclose(float(metrics.aux_loss), 1.0, rtol=1e-2)


def test_moe_grads_flow_to_all_parts():
    cfg = _cfg(capacity_factor=8.0)
    params = moe_init(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 32, cfg.d_model))

    def loss(p):
        y, m = moe_apply(p, cfg, x)
        return (y ** 2).sum() + 0.01 * m.aux_loss

    g = jax.grad(loss)(params)
    for name in ("router", "w_gate", "w_up", "w_down"):
        leaf = g[name]["w"] if name == "router" else g[name]
        assert float(jnp.abs(leaf).sum()) > 0, f"no grad into {name}"


def test_moe_flops_accounting():
    cfg = _cfg()
    f = moe_lib.moe_flops_per_token(cfg)
    m = cfg.moe
    assert f >= 2 * cfg.d_model * m.d_ff_expert * 3 * m.top_k


def test_moe_shard_map_matches_gspmd():
    """The §Perf B3 optimization is bit-exact vs the GSPMD path on a
    named mesh (local dispatch + one psum == global dispatch)."""
    import jax.numpy as jnp
    from repro.sharding import rules
    cfg = _cfg(ranking="sort")
    params = moe_init(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 64, cfg.d_model))
    y0, m0 = moe_apply(params, cfg, x)
    cfg_sm = _cfg(ranking="sort", impl="shard_map")
    mesh = make_mesh((1, 1), ("data", "model"))
    with mesh, rules.activation_mesh(mesh):
        y1, m1 = jax.jit(lambda p, xx: moe_apply(p, cfg_sm, xx))(params, x)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y0),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(float(m1.aux_loss), float(m0.aux_loss),
                               rtol=1e-5)


def test_moe_shard_map_falls_back_without_mesh():
    cfg = _cfg(impl="shard_map")
    params = moe_init(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model))
    y, _ = moe_apply(params, cfg, x)    # no mesh installed -> gspmd path
    assert np.isfinite(np.asarray(y)).all()
