"""Serving driver: continuous-batching engine over a selectable arch.

    PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b \
        --smoke --requests 6 --batch 2 --max-len 96 [--retained]

``--retained`` serves with the ring-buffer local+global KV cache (the
paper's static block sparsity bounding long-context decode, DESIGN.md
§3); positions may then exceed the physical cache length.
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro import configs
from repro.launch import compile_cache
from repro.models.model import LM
from repro.serve import Engine, Request


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--max-len", type=int, default=96)
    ap.add_argument("--new-tokens", type=int, default=8)
    ap.add_argument("--retained", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--plan-cache", default=None, metavar="DIR",
                    help="persistent autotune cache dir (repro.sparse): "
                         "restarts skip re-planning/re-measurement")
    args = ap.parse_args()
    compile_cache.enable()

    cfg = configs.smoke(args.arch) if args.smoke else configs.get(args.arch)
    lm = LM(cfg)
    params = lm.init(jax.random.PRNGKey(args.seed))
    eng = Engine(lm, params, batch=args.batch, max_len=args.max_len,
                 retained=args.retained, plan_cache_dir=args.plan_cache)
    print(f"[serve] startup plans: {eng.plan_stats}")

    rng = np.random.default_rng(args.seed)
    reqs = [Request(uid=i,
                    prompt=rng.integers(0, cfg.vocab_size,
                                        size=int(rng.integers(4, 24))),
                    max_new_tokens=args.new_tokens)
            for i in range(args.requests)]
    t0 = time.time()
    done = []
    eng.run(reqs, on_finish=lambda r: done.append(
        (r.uid, time.time() - t0)))
    total_toks = sum(len(r.output) for r in reqs)
    dt = time.time() - t0
    dev = jax.devices()[0]
    for uid, t in done:
        r = next(r for r in reqs if r.uid == uid)
        print(f"[serve] req {uid}: {len(r.prompt)} prompt -> "
              f"{len(r.output)} tokens @ {t:.2f}s: {r.output[:6]}...")
    print(f"[serve] {len(reqs)} requests, {total_toks} tokens, "
          f"{dt:.2f}s host wall-clock ({total_toks/dt:.1f} tok/s on "
          f"{dev.platform} {dev.device_kind!r} x{len(jax.devices())}, "
          f"batch={args.batch}, retained={args.retained})")


if __name__ == "__main__":
    main()
