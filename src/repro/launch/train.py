"""Fault-tolerant training driver.

    PYTHONPATH=src python -m repro.launch.train --arch llama3.2-1b \
        --smoke --steps 50 --ckpt-dir /tmp/ckpt

Production behaviours exercised end-to-end (and tested in
tests/test_train.py):

* deterministic sharded data pipeline with a checkpointable cursor,
* async atomic checkpoints every ``--ckpt-every`` steps,
* automatic resume from the latest checkpoint (crash/preemption model:
  kill the process at any point; rerun the same command),
* preemption signal handler (SIGTERM -> synchronous final checkpoint),
* elastic restart: checkpoints store logical shardings, so a restart on
  a different mesh re-shards on load,
* straggler mitigation at step granularity: the jitted step is a global
  barrier; the async checkpointer bounds the extra critical-path work to
  a device->host copy (see DESIGN.md §3).
"""
from __future__ import annotations

import argparse
import signal
import sys
import time

import jax

from repro import configs
from repro.checkpoint import Checkpointer, latest_step, restore
from repro.data import TokenPipeline
from repro.launch import compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models.model import LM
from repro.sharding import rules
from repro.train.step import TrainHParams, init_train_state, make_train_step


def train_loop(cfg, *, steps: int, batch_per_shard: int, seq: int,
               ckpt_dir: str | None, ckpt_every: int = 20,
               hp: TrainHParams = TrainHParams(), mesh=None,
               log_every: int = 10, on_step=None):
    lm = LM(cfg)
    mesh = mesh or make_host_mesh()
    train_step = make_train_step(lm, hp)

    pipe = TokenPipeline(cfg.vocab_size, batch_per_shard, seq)
    ckpt = Checkpointer(ckpt_dir) if ckpt_dir else None

    state_sds = jax.eval_shape(
        lambda: init_train_state(lm, jax.random.PRNGKey(0), hp=hp))
    state_specs = rules.train_state_specs(state_sds, mesh)

    start = 0
    if ckpt_dir and latest_step(ckpt_dir) is not None:
        state, extra, start = restore(ckpt_dir, state_sds, mesh=mesh,
                                      specs=state_specs)
        start = TokenPipeline.resume_step(extra["data"])
        print(f"[train] resumed from step {start}")
    else:
        state = init_train_state(lm, jax.random.PRNGKey(0), hp=hp)

    jit_step = jax.jit(train_step, donate_argnums=(0,))
    stop = {"now": False}

    def on_sigterm(signum, frame):
        stop["now"] = True
    old = signal.signal(signal.SIGTERM, on_sigterm)

    losses = []
    t0 = time.time()
    with mesh, rules.activation_mesh(mesh):
        for step in range(start, steps):
            batch = jax.tree.map(jax.numpy.asarray, pipe.get_batch(step))
            state, metrics = jit_step(state, batch)
            loss = float(metrics["loss"])
            losses.append(loss)
            if on_step:
                on_step(step, metrics)
            if step % log_every == 0 or step == steps - 1:
                dt = time.time() - t0
                print(f"[train] step {step} loss {loss:.4f} "
                      f"gnorm {float(metrics['grad_norm']):.3f} "
                      f"({dt:.1f}s)")
            if ckpt and ((step + 1) % ckpt_every == 0 or stop["now"]
                         or step == steps - 1):
                ckpt.save_async(state, step=step + 1,
                                extra={"data": pipe.state(step + 1)})
            if stop["now"]:
                print("[train] preemption signal: final checkpoint + exit")
                break
    if ckpt:
        ckpt.wait()
    signal.signal(signal.SIGTERM, old)
    return state, losses


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-friendly)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--lr", type=float, default=1e-3)
    args = ap.parse_args()
    compile_cache.enable()

    cfg = configs.smoke(args.arch) if args.smoke else configs.get(args.arch)
    hp = TrainHParams(peak_lr=args.lr, warmup_steps=max(1, args.steps // 10),
                      total_steps=args.steps)
    _, losses = train_loop(cfg, steps=args.steps,
                           batch_per_shard=args.batch, seq=args.seq,
                           ckpt_dir=args.ckpt_dir,
                           ckpt_every=args.ckpt_every, hp=hp)
    print(f"[train] done: first loss {losses[0]:.4f} "
          f"last loss {losses[-1]:.4f}")
    if not (losses[-1] < losses[0]):
        print("[train] WARNING: loss did not improve", file=sys.stderr)


if __name__ == "__main__":
    main()
