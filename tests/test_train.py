"""End-to-end training: loss decreases, checkpoint resume is exact,
grad accumulation is consistent."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.checkpoint import latest_step, restore, save
from repro.data import TokenPipeline
from repro.launch.mesh import make_mesh
from repro.launch.train import train_loop
from repro.models.model import LM
from repro.sharding import rules
from repro.train.step import TrainHParams, init_train_state, make_train_step


# model-level training loop: excluded from the fast tier-1 run (see pytest.ini)
pytestmark = pytest.mark.slow


def _tiny_cfg():
    return configs.smoke("llama3_2_1b")


def test_loss_decreases(tmp_path):
    cfg = _tiny_cfg()
    hp = TrainHParams(peak_lr=1e-3, warmup_steps=3, total_steps=30)
    _, losses = train_loop(cfg, steps=25, batch_per_shard=8, seq=64,
                           ckpt_dir=None, hp=hp, log_every=100)
    assert losses[-1] < losses[0] - 0.02, (losses[0], losses[-1])


def test_resume_is_exact(tmp_path):
    """Train 10; train 6 + crash + resume to 10: identical final loss."""
    cfg = _tiny_cfg()
    hp = TrainHParams(peak_lr=1e-3, warmup_steps=2, total_steps=10)
    kw = dict(batch_per_shard=4, seq=32, hp=hp, log_every=100,
              ckpt_every=3)
    _, l_straight = train_loop(cfg, steps=10, ckpt_dir=None, **kw)
    d = str(tmp_path / "ck")
    _, _ = train_loop(cfg, steps=6, ckpt_dir=d, **kw)
    _, l_resumed = train_loop(cfg, steps=10, ckpt_dir=d, **kw)
    np.testing.assert_allclose(l_resumed[-1], l_straight[-1], rtol=1e-4)


def test_grad_accumulation_matches_full_batch():
    cfg = _tiny_cfg()
    lm = LM(cfg)
    key = jax.random.PRNGKey(0)
    pipe = TokenPipeline(cfg.vocab_size, 8, 32)
    batch = jax.tree.map(jnp.asarray, pipe.get_batch(0))
    hp1 = TrainHParams(accum=1, peak_lr=1e-3, warmup_steps=1,
                       total_steps=10)
    hp4 = hp1._replace(accum=4)
    s1 = init_train_state(lm, key, hp=hp1)
    s4 = init_train_state(lm, key, hp=hp4)
    s1b, m1 = jax.jit(make_train_step(lm, hp1))(s1, batch)
    s4b, m4 = jax.jit(make_train_step(lm, hp4))(s4, batch)
    np.testing.assert_allclose(float(m1["loss"]), float(m4["loss"]),
                               rtol=5e-3)
    w1 = np.asarray(s1b.opt.master["embed"]["table"], np.float32)
    w4 = np.asarray(s4b.opt.master["embed"]["table"], np.float32)
    np.testing.assert_allclose(w1, w4, rtol=1e-2, atol=1e-5)


def test_checkpoint_atomicity_and_gc(tmp_path):
    tree = {"a": jnp.arange(8.0), "b": {"c": jnp.ones((2, 3), jnp.bfloat16)}}
    p = str(tmp_path)
    for s in (1, 2, 3):
        save(p, tree, step=s, extra={"data": {"step": s}})
    assert latest_step(p) == 3
    got, extra, step = restore(p, jax.eval_shape(lambda: tree))
    np.testing.assert_allclose(np.asarray(got["a"]), np.arange(8.0))
    assert got["b"]["c"].dtype == jnp.bfloat16
    assert extra["data"]["step"] == 3
    # a stale .tmp dir must be ignored
    os.makedirs(os.path.join(p, "step_9.tmp"), exist_ok=True)
    assert latest_step(p) == 3


def test_elastic_restore_across_mesh(tmp_path):
    """Checkpoint written unsharded restores onto a (1,1) named mesh with
    logical specs -- the elastic-restart contract."""
    cfg = _tiny_cfg()
    lm = LM(cfg)
    params = lm.init(jax.random.PRNGKey(0))
    p = str(tmp_path)
    save(p, params, step=1)
    mesh = make_mesh((1, 1), ("data", "model"))
    specs = rules.param_specs(jax.eval_shape(lambda: params), mesh)
    got, _, _ = restore(p, jax.eval_shape(lambda: params), mesh=mesh,
                        specs=specs)
    a = jax.tree.leaves(got)[0]
    assert hasattr(a, "sharding")
    np.testing.assert_allclose(
        np.asarray(jax.tree.leaves(got)[0], np.float32),
        np.asarray(jax.tree.leaves(params)[0], np.float32))


def test_data_pipeline_contract():
    pipe = TokenPipeline(100, 4, 16, num_shards=2, shard_id=0)
    pipe1 = TokenPipeline(100, 4, 16, num_shards=2, shard_id=1)
    b0 = pipe.get_batch(0)
    b0_again = pipe.get_batch(0)
    np.testing.assert_array_equal(b0["tokens"], b0_again["tokens"])
    b1 = pipe1.get_batch(0)
    assert not np.array_equal(b0["tokens"], b1["tokens"])  # disjoint shards
    # targets are next-token shifted
    np.testing.assert_array_equal(b0["tokens"][:, 1:], b0["targets"][:, :-1])
