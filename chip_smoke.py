"""Bring-up check of the serving path on a TPU.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # tensor-parallel plan routes, 4 chips

One chip, three phases in one process:

1. kernels -- every Pallas route of ``repro.sparse.plan`` forced once and
   compiled by Mosaic (never interpreted) at the qwen2-1.5b FFN
   up-projection (m=8960, k=1536, blocks of 16, density 1/8, bf16), at a
   decode batch (n=8) and at an unaligned prefill length (n=1023, padded
   inside the kernels), each checked against a float32 ``jax.numpy``
   product;
2. serving, dense FFN -- qwen2-1.5b at its published widths with random
   weights, through ``serve.Engine`` (batch 8, max_len 1024): 8 requests
   of 16 to 1008 prompt tokens, 16 new tokens each, no compile while
   serving, the top bucket served; then checked against the same model
   planned ``dense_xla``: prefill logits of the shortest and the longest
   request at their buckets, of the longest at its exact length (token
   axis padded in the kernels and in attention), of one request at its
   exact length with an odd attention tile count under the balanced
   schedule, and the logits of one decode step of the whole batch;
3. serving, the paper's static block-sparse FFN (density 1/8, blocks of
   16) -- the same checks.

``--chips 4`` runs only the tensor-parallel path: the FFN weight planned
on a 4-chip ``model`` mesh with each TP route forced, against the
unsharded ``static_pallas`` plan on one chip.

Lines before the last are information (routes, compile counts, host
wall-clock per phase, labelled with the device).  The last line is one
JSON object naming the device, printed only when every phase passed.
Without a TPU the script names the platform it found and exits 1.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

# the bf16 budget of tests/conftest.py (GRAD_TOLS): max-norm relative
BF16_BUDGET = 6e-2
FFN = dict(m=8960, k=1536, b=16, density=0.125)     # qwen2-1.5b up-proj
KERNEL_NS = (8, 1023)        # decode batch; an unaligned prefill length
FORWARD_ROUTES = ("dense_pallas", "static_pallas", "static_balanced",
                  "dynamic_pallas", "dynamic_grouped",
                  "dynamic_grouped_balanced")
TP_ROUTES = ("static_tp", "static_tp_shardmap")

LOWERING = "/jax/core/compile/jaxpr_to_mlir_module_duration"


class CompileCounter:
    """Counts programs lowered in this process (every jit cache miss,
    whether XLA compiles it or the persistent cache supplies it)."""

    def __init__(self):
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == LOWERING:
            self.n += 1


def rel_err(got, want) -> float:
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.abs(got - want).max() / max(np.abs(want).max(), 1e-6))


def check(ok: bool, what: str):
    if not ok:
        raise RuntimeError(f"chip smoke failed: {what}")


class Phase:
    """Prints a phase's host wall-clock and lowering count at its end."""

    def __init__(self, name, counter, label):
        self.name, self.counter, self.label = name, counter, label

    def __enter__(self):
        self.t0, self.c0 = time.perf_counter(), self.counter.n
        print(f"[{self.name}] start", flush=True)
        return self

    def __exit__(self, *exc):
        if exc[0] is None:
            print(f"[{self.name}] done: {self.counter.n - self.c0} programs "
                  f"lowered, host wall-clock "
                  f"{time.perf_counter() - self.t0:.1f} s on {self.label}",
                  flush=True)


def ffn_operand(seed: int):
    """The FFN up-projection as a static BSR operand (bf16 values) and
    its float32 dense twin."""
    from repro.core import masks
    from repro.core.bsr import BlockSparseMatrix
    m, k, b = FFN["m"], FFN["k"], FFN["b"]
    mask = masks.random_block_mask(m, k, b, FFN["density"], seed=seed)
    rows, cols = (a.astype(np.int32) for a in np.nonzero(mask))
    vals = jax.random.normal(jax.random.PRNGKey(seed),
                             (len(rows), b, b)) / np.sqrt(k * FFN["density"])
    bsr = BlockSparseMatrix(vals.astype(jnp.bfloat16), rows, cols, (m, k), b)
    return bsr, np.asarray(bsr.to_dense(), np.float32)


def compiled_call(fn, *args):
    """Compile ``fn`` for the arguments, require a Mosaic kernel in the
    program, and run it."""
    compiled = jax.jit(fn).lower(*args).compile()
    check("tpu_custom_call" in compiled.as_text(),
          f"{getattr(fn, '__name__', fn)}: no Pallas kernel in the program")
    return compiled(*args)


def kernel_phase(seed: int):
    from repro import sparse
    bsr, w = ffn_operand(seed)
    v = jnp.asarray(bsr.values)
    m, k, b = FFN["m"], FFN["k"], FFN["b"]
    rows, cols = np.asarray(bsr.row_idx), np.asarray(bsr.col_idx)
    for n in KERNEL_NS:
        x = jax.random.normal(jax.random.PRNGKey(seed + n), (k, n),
                              jnp.bfloat16)
        want = w @ np.asarray(x, np.float32)
        for route in FORWARD_ROUTES:
            p = sparse.plan(bsr, n, ctx=sparse.PlanContext(
                mode=route, differentiable=False))
            check(p.route == route and not p.ctx.interpret,
                  f"{route} planned as {p.route}")
            err = rel_err(compiled_call(lambda v_, x_: p(v_, x_), v, x),
                          want)
            print(f"  {route:26s} n={n:5d} rel err {err:.2e}", flush=True)
            check(err <= BF16_BUDGET, f"{route} n={n}: {err:.2e} over the "
                  f"bf16 budget {BF16_BUDGET}")
        # sddmm_grouped is the dL/dvalues product of a plan's backward
        p = sparse.plan(bsr, n, ctx=sparse.PlanContext(
            mode="static_pallas", grad_mode="static_pallas",
            sddmm_mode="sddmm_grouped"))
        dy = jax.random.normal(jax.random.PRNGKey(seed - n), (m, n),
                               jnp.bfloat16)

        def backward(v_, x_, dy_):
            return jax.vjp(lambda a, c: p(a, c), v_, x_)[1](dy_)
        dv, dx = compiled_call(backward, v, x, dy)
        dy32 = np.asarray(dy, np.float32)
        full = dy32 @ np.asarray(x, np.float32).T
        dv_want = np.stack([full[r * b:(r + 1) * b, c * b:(c + 1) * b]
                            for r, c in zip(rows, cols)])
        for name, got, ref in (("sddmm_grouped dL/dvalues", dv, dv_want),
                               ("static_pallas dL/dx", dx, w.T @ dy32)):
            err = rel_err(got, ref)
            print(f"  {name:26s} n={n:5d} rel err {err:.2e}", flush=True)
            check(err <= BF16_BUDGET, f"{name} n={n}: {err:.2e} over the "
                  f"bf16 budget {BF16_BUDGET}")


def live_bytes() -> int:
    return sum(a.nbytes for a in jax.live_arrays())


def serving_phase(cfg, counter, *, batch: int, max_len: int, requests: int,
                  lens: tuple, new_tokens: int, seed: int):
    from repro import sparse
    from repro.models.attention import _seq_tile
    from repro.models.model import LM
    from repro.serve import Engine, Request
    sparse.reset()
    lm = LM(cfg)
    t0 = time.perf_counter()
    params = jax.block_until_ready(jax.jit(lm.init)(jax.random.PRNGKey(seed)))
    n_params = sum(a.size for a in jax.tree.leaves(params))
    print(f"  {cfg.name}: {n_params / 1e6:.1f}M parameters on device, "
          f"ffn={cfg.groups[0][0][0].ffn}, host wall-clock "
          f"{time.perf_counter() - t0:.1f} s", flush=True)
    t0 = time.perf_counter()
    eng = Engine(lm, params, batch=batch, max_len=max_len, warm_compile=True)
    print(f"  buckets {eng.buckets}; startup plans {eng.plan_stats}; "
          f"engine start (plans + warm compile) host wall-clock "
          f"{time.perf_counter() - t0:.1f} s", flush=True)
    for key, r in sorted(eng.plan_report()["plans"]["per_plan"].items(),
                         key=lambda kv: (kv[1]["op"], kv[1]["shape"])):
        m, k, n = r["shape"]
        print(f"  plan {r['op']:7s} {r['kind']:6s} m={m:6d} k={k:5d} "
              f"n={n:5d} -> {r['route']} ({r['source']})", flush=True)

    rng = np.random.default_rng(seed)
    reqs = [Request(uid=i, prompt=rng.integers(0, cfg.vocab_size, size=int(L),
                                               dtype=np.int32),
                    max_new_tokens=new_tokens)
            for i, L in enumerate(np.linspace(*lens, requests).round())]
    c0, t0 = counter.n, time.perf_counter()
    eng.run(reqs)
    lowered = counter.n - c0
    print(f"  served {requests} requests, prompts {[len(r.prompt) for r in reqs]}"
          f", {lowered} programs lowered while serving, host wall-clock "
          f"{time.perf_counter() - t0:.1f} s", flush=True)
    for r in reqs:
        check(len(r.output) == new_tokens
              and all(0 <= t < cfg.vocab_size for t in r.output),
              f"request {r.uid}: {len(r.output)} tokens {r.output}")
    check(lowered == 0, f"{lowered} programs lowered while serving")
    check(reqs[-1].bucket == eng.buckets[-1],
          f"the longest prompt was served at bucket {reqs[-1].bucket}, "
          f"not the top bucket {eng.buckets[-1]}")

    # the engine's plans against the same model planned dense_xla (and
    # the row attention schedule), on identical inputs
    ref_ctx = sparse.PlanContext(mode="dense_xla", differentiable=False)

    def prefill(prompt, L):
        tokens = np.zeros((1, L), np.int32)
        tokens[0, :len(prompt)] = prompt
        last = np.asarray([len(prompt) - 1], np.int32)

        def run(ctx, schedule):
            with sparse.use_ctx(ctx):
                return jax.jit(lambda p, t, i: lm.prefill(
                    p, t, max_len=max_len, last_index=i,
                    schedule=schedule)[0])(params, tokens, last)
        return run

    def decode(ctx, _):
        # one step of the whole batch from the caches serving left
        tokens = np.asarray([[r.output[-1]] for r in reqs], np.int32)
        with sparse.use_ctx(ctx):
            return jax.jit(lambda p, t, c, i: lm.decode_step(
                p, t, c, i)[0])(params, tokens, eng.caches,
                                np.asarray(eng.positions))

    def compare(what, run, served, schedule=None):
        t0 = time.perf_counter()
        built = sparse.cache_stats()["plans_built"]
        got = run(eng.plan_ctx, schedule)
        check(not served or sparse.cache_stats()["plans_built"] == built,
              f"{what}: the engine's context planned anew")
        err = rel_err(got, run(ref_ctx, None))
        print(f"  {what} vs dense_xla plans: rel err {err:.2e}, host "
              f"wall-clock {time.perf_counter() - t0:.1f} s", flush=True)
        check(err <= BF16_BUDGET, f"{what}: {err:.2e} over the bf16 "
              f"budget {BF16_BUDGET}")

    for r in (reqs[0], reqs[-1]):
        n, L = len(r.prompt), eng.bucket_for(len(r.prompt))
        compare(f"prefill logits, {n} tokens at bucket {L}",
                prefill(r.prompt, L), served=True)
    # exact length: the token axis padded inside every kernel and in
    # attention, where padded keys are masked
    n = len(reqs[-1].prompt)
    compare(f"prefill logits, {n} tokens at exact length",
            prefill(reqs[-1].prompt, n), served=False)
    # an odd number of attention tiles under the balanced schedule
    def attn_tiles(n):
        tile, padded = _seq_tile(n, cfg.attn_tile_q)
        return padded // tile
    odd = next(r.prompt for r in reqs
               if attn_tiles(len(r.prompt)) % 2
               and attn_tiles(len(r.prompt)) > 1)
    compare(f"prefill logits, {len(odd)} tokens at exact length, balanced "
            f"attention schedule", prefill(odd, len(odd)), served=False,
            schedule="balanced")
    compare(f"decode logits, batch of {batch}", decode, served=True)
    del eng, params
    sparse.reset()
    gc.collect()
    print(f"  released: {live_bytes() / 2**20:.0f} MiB of live arrays left",
          flush=True)


def tp_phase(seed: int):
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro import sparse
    from repro.launch.mesh import make_mesh
    from repro.sharding import rules
    bsr, w = ffn_operand(seed)
    mesh = make_mesh((4,), ("model",), devices=jax.devices()[:4])
    # mode="static_tp" on a concrete mesh races both lowerings; with the
    # shard count alone gspmd is the one candidate, and the activation
    # mesh makes its sharding constraint live
    ctxs = {"static_tp": dict(tp_q=4),
            "static_tp_shardmap": dict(mesh=mesh)}
    for n in KERNEL_NS:
        x = jax.random.normal(jax.random.PRNGKey(seed + n), (FFN["k"], n),
                              jnp.bfloat16)
        want = w @ np.asarray(x, np.float32)
        one = sparse.plan(bsr, n, ctx=sparse.PlanContext(
            mode="static_pallas", differentiable=False))
        base = compiled_call(lambda v_, x_: one(v_, x_), bsr.values, x)
        print(f"  static_pallas on 1 chip    n={n:5d} rel err "
              f"{rel_err(base, want):.2e}", flush=True)
        v4, x4 = (jax.device_put(a, NamedSharding(mesh, P()))
                  for a in (bsr.values, x))
        for route, kw in ctxs.items():
            p = sparse.plan(bsr, n, ctx=sparse.PlanContext(
                mode=route, differentiable=False, **kw))
            check(p.route == route, f"{route} planned as {p.route}")
            with rules.activation_mesh(mesh):
                compiled = jax.jit(lambda v_, x_: p(v_, x_)).lower(
                    v4, x4).compile()
            y = compiled(v4, x4)
            err, vs_one = rel_err(y, want), rel_err(y, base)
            reduced = "all-reduce" in compiled.as_text()
            spread = len(y.sharding.device_set)
            print(f"  {route:26s} n={n:5d} rel err {err:.2e}, vs 1-chip "
                  f"static_pallas {vs_one:.2e}; all-reduce in program: "
                  f"{reduced}; output on {spread} devices", flush=True)
            check(err <= BF16_BUDGET and vs_one <= BF16_BUDGET,
                  f"{route} n={n}: {err:.2e} / {vs_one:.2e} over the bf16 "
                  f"budget {BF16_BUDGET}")
            # a route that quietly ran replicated would match numerically
            check(reduced and spread == 4, f"{route} n={n}: all-reduce "
                  f"{reduced}, output on {spread} devices; not sharded")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {d.platform} "
              f"({d.device_kind!r} x{len(devs)})", file=sys.stderr)
        return 1
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices;"
              f" JAX found {len(devs)}", file=sys.stderr)
        return 1
    label = f"{d.platform} {d.device_kind!r} x{len(devs)}"
    from repro import configs
    from repro.launch import compile_cache
    print(f"device: {label}; jax {jax.__version__}; compile cache "
          f"{compile_cache.enable()}", flush=True)
    counter = CompileCounter()

    if args.chips == 4:
        with Phase("tp", counter, label):
            tp_phase(args.seed)
    else:
        with Phase("kernels", counter, label):
            kernel_phase(args.seed)
        dense = configs.get("qwen2_1_5b")
        for cfg in (dense, configs.sparse_ffn(dense, 0.125)):
            name = f"serve-{cfg.groups[0][0][0].ffn}"
            with Phase(name, counter, label):
                serving_phase(cfg, counter, batch=8, max_len=1024,
                              requests=8, lens=(16, 1008), new_tokens=16,
                              seed=args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind, "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
