"""Run one cell of the benchmark once.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell of ``BENCHMARK.json`` names a configuration (``bench/configs``)
and a traffic mix (``bench/traffic``).  The run builds the configured
model with the benchmark's own weights from ``--seed``, starts
``repro.serve.Engine`` as users do (``warm_compile=True``, no
re-planner, the default bucket ladder), sends the mix's requests through
``Engine.submit`` and drives ``Engine.serve``, and measures for
``--seconds`` once the mix's warm-up is over.  Token times are taken
where the engine appends to ``Request.output``.

With ``--trace 0`` the last line of standard output holds the cell's
end-to-end metrics; with ``--trace 1`` its per-layer metrics, read from
a profiler trace of the first ``trace_seconds`` of the window and from
the benchmark's host spans.  Every metric is a file under
``bench/metrics`` named as in ``BENCHMARK.json``.  After the window the
program is freed and a sample of the finished requests is checked
against ``bench/reference.py``; the numbers compared, with their limits
(``bench/limits/<cell>.json``), are the last lines of standard error
and the last key of the result.

The run fails, with no result, off a TPU or with fewer chips than the
cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import functools  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

import numpy as np  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(BENCH, "metrics"))

import arch  # noqa: E402
import peaks as peaks_lib  # noqa: E402
import trace_reduce  # noqa: E402
import traffic  # noqa: E402

LOWERING = "/jax/core/compile/jaxpr_to_mlir_module_duration"
TRACE_DIR = os.path.join(ROOT, ".bench_trace")
POST_CLOSE_S = 60.0     # longest wait after the close for what is owed


# ---------------------------------------------------------------------------
# the benchmark's data, found by name
# ---------------------------------------------------------------------------

def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def resolve(workload: str, root: str = ROOT) -> dict:
    """The cell, its configuration, mix and limits, and the metrics it
    reports, from ``BENCHMARK.json`` and the files it names."""
    spec = _json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; "
                         f"known: {sorted(cells)}")
    cell = cells[workload]

    def mine(metrics):
        return [m for m in metrics
                if workload in m.get("workloads", [workload])]
    return dict(pair(cell["config"], cell["traffic"], cell["chips"], root),
                cell=cell,
                limits=_json(os.path.join(root, "bench", "limits",
                                          workload + ".json")),
                end_to_end=mine(spec["end_to_end"]),
                per_layer=mine(spec["per_layer"]))


def pair(config: str, mix: str, chips: int = 1, root: str = ROOT) -> dict:
    """A configuration under a traffic mix, by their names, whether or
    not a cell of ``BENCHMARK.json`` joins them (a rate sweep runs
    before its cell exists)."""
    spec = _json(os.path.join(root, "BENCHMARK.json"))
    entry = {c["name"]: c for c in spec["configs"]}[config]
    return {
        "cell": {"name": f"{config}.{mix}", "config": config,
                 "traffic": mix, "chips": chips},
        "config": _json(os.path.join(root, entry["file"])),
        "mix": traffic.load(os.path.join(root, "bench", "traffic",
                                         mix + ".json")),
    }


def metric_reader(name: str):
    return arch.load(os.path.join(BENCH, "metrics", name + ".py")).read


# ---------------------------------------------------------------------------
# the system under test
# ---------------------------------------------------------------------------

def program_model(c: dict):
    """The program's model configuration for a configuration file, held
    to the sizes that the configuration's work module
    (``program_sizes``: attribute or dotted path to value) reads from
    the file."""
    from repro import configs
    p = c["program"]
    cfg = configs.get(p["preset"])
    sp = c.get("sparse_ffn")
    if sp:
        cfg = configs.sparse_ffn(cfg, sp["density"])
    want = arch.load(c["work"]).program_sizes(c)
    got = {k: functools.reduce(getattr, k.split("."), cfg) for k in want}
    if got != want:
        raise ValueError(f"program preset {p['preset']!r} departs from the "
                         f"configuration: {got} != {want}")
    return cfg


class TimedList(list):
    """``Request.output`` that records when each token is appended."""

    def __init__(self):
        super().__init__()
        self.times = []

    def append(self, tok):
        self.times.append(time.perf_counter())
        super().append(tok)


class WindowClosed(Exception):
    pass


class CompileCounter:
    """Programs lowered in this process (each jit cache miss)."""

    def __init__(self):
        import jax
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == LOWERING:
            self.n += 1


class Driver:
    """Sends a mix to an engine through ``submit`` and drives ``serve``,
    recording host spans around the instance's ``admit`` and ``step``."""

    def __init__(self, eng, specs, mix, seconds, *, trace: bool,
                 counter: CompileCounter):
        import jax
        from repro.serve import Request
        self.jax, self.Request = jax, Request
        self.eng, self.mix, self.seconds = eng, mix, seconds
        self.specs = specs
        self.trace, self.counter = trace, counter
        self.reqs = []                  # dicts, in submission order
        self.live = []
        self.admits, self.steps = [], []
        self.open = self.close = None
        self.traced = None
        self._annot = None
        self.finished = 0
        self.compiles_at_open = self.compiles_in_window = None
        self.lock = threading.Lock()
        self.wake = threading.Event()
        orig_admit, orig_step = eng.admit, eng.step

        def admit(req):
            self._tick()
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.admit"):
                r = orig_admit(req)
            t1 = time.perf_counter()
            self.admits.append((t0, t1, len(req.prompt), req.bucket))
            if not req.done:
                self.live.append(req)
            return r

        def step():
            self._tick()
            ctx = [len(r.prompt) + len(r.output) for r in self.live]
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.step"):
                out = orig_step()
            t1 = time.perf_counter()
            if ctx:
                self.steps.append((t0, t1, ctx))
            self.live = [r for r in self.live if not r.done]
            return out
        # instance attributes shadow the methods ``serve`` calls
        eng.admit, eng.step = admit, step

    # -- requests ----------------------------------------------------------
    def _submit(self, spec, due):
        req = self.Request(uid=len(self.reqs), prompt=spec.prompt,
                           max_new_tokens=spec.max_new, output=TimedList())
        rec = {"req": req, "due": due, "sent": time.perf_counter(),
               "done_at": None}
        with self.lock:
            self.reqs.append(rec)
        self.eng.submit(req)
        self.wake.set()

    def _on_finish(self, req):
        now = time.perf_counter()
        rec = self.reqs[req.uid]
        rec["done_at"] = now
        self.finished += 1
        if self.mix["loop"] != "closed":
            return
        need = self.mix["warmup"]["finished"]
        if self.open is None and self.finished >= need:
            self._open(now)
        if self.close is None or now < self.close:
            nxt = next(self._pool, None)
            if nxt is not None:
                self._submit(nxt, time.perf_counter())

    def _generate(self):
        """Open loop: submit each request at its due time."""
        for spec in self.specs:
            due = self.t_first + spec.due
            if due >= self.close:
                return
            wait = due - time.perf_counter()
            if wait > 0:
                with self.jax.profiler.TraceAnnotation("bench.gen_wait"):
                    time.sleep(wait)
            self._submit(spec, due)

    # -- the window --------------------------------------------------------
    def _open(self, now):
        self.open, self.close = now, now + self.seconds
        self.compiles_at_open = self.counter.n

    def _tick(self):
        now = time.perf_counter()
        if self.open is None and self.mix["loop"] == "open" \
                and now >= self.t_open:
            self._open(self.t_open)
        if self.open is None:
            return
        tsec = self.mix["trace_seconds"]
        if self.trace and self.traced is None:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            self.jax.profiler.start_trace(TRACE_DIR)
            self._annot = self.jax.profiler.TraceAnnotation(
                "bench.traced_window")
            self._annot.__enter__()
            self.traced = [time.perf_counter(), None]
        elif self._annot is not None and now >= self.traced[0] + tsec:
            self.traced[1] = time.perf_counter()
            self._annot.__exit__(None, None, None)
            self._annot = None
            self.jax.profiler.stop_trace()
        if now >= self.close:
            self.compiles_in_window = self.counter.n - self.compiles_at_open
            if not self._waiting() or now >= self.close + POST_CLOSE_S:
                raise WindowClosed()

    def _waiting(self):
        """Requests due before the close that still owe their first token;
        in an open loop, their last: there the check samples the requests
        due in the window, once they have finished."""
        if self.mix["loop"] == "open":
            return [r for r in self.reqs
                    if r["due"] < self.close and r["done_at"] is None]
        return [r for r in self.reqs
                if r["due"] < self.close and not r["req"].output]

    def run(self):
        mix = self.mix
        self.t_first = time.perf_counter()
        if mix["loop"] == "closed":
            self._pool = iter(self.specs)
            for spec in [next(self._pool) for _ in range(mix["clients"])]:
                self._submit(spec, self.t_first)
        else:
            self.t_open = self.t_first + mix["warmup"]["seconds"]
            self.close = self.t_open + self.seconds   # the generator's end
            gen = threading.Thread(target=self._generate, daemon=True)
            gen.start()
        try:
            while True:
                self.eng.serve(on_finish=self._on_finish)
                with self.jax.profiler.TraceAnnotation("bench.wait"):
                    self.wake.wait(0.02)
                self.wake.clear()
                self._tick()
        except WindowClosed:
            pass
        if self._annot is not None:
            self._annot.__exit__(None, None, None)
            self.jax.profiler.stop_trace()
            self.traced[1] = time.perf_counter()
        if mix["loop"] == "open":
            gen.join()
        # the engine's own methods again
        del self.eng.admit, self.eng.step


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def device_info(jax, chips: int, require_tpu: bool = True) -> dict:
    devs = jax.devices()
    d = devs[0]
    if require_tpu and d.platform != "tpu":
        raise SystemExit(f"bench: needs a TPU; JAX found {d.platform} "
                         f"({d.device_kind!r} x{len(devs)})")
    if len(devs) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips; JAX found "
                         f"{len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": chips}


def sample_checked(reqs, window, n: int, seed: int, by_due: bool = False
                   ) -> list:
    """Finished requests to check: the longest, and the rest drawn from
    the seed; of those finished in the window, or (``by_due``, an open
    loop) of those due in it."""
    done = [r for r in reqs if r["done_at"] is not None
            and window[0] <= r["due" if by_due else "done_at"] <= window[1]]
    if not done:
        return []
    done.sort(key=lambda r: -(len(r["req"].prompt) + len(r["req"].output)))
    rest = done[1:]
    rng = np.random.default_rng([seed, 7])
    pick = rng.choice(len(rest), size=min(n - 1, len(rest)), replace=False)
    return [done[0]] + [rest[i] for i in sorted(pick)]


def start(r: dict, seed: int, *, require_tpu: bool = True,
          model_cfg=None) -> dict:
    """Set-up: the device check, the compile cache, the benchmark's
    weights from the seed and the engine with its warm compile."""
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    device = device_info(jax, r["cell"]["chips"], require_tpu)
    from repro.launch import compile_cache
    from repro.models.model import LM
    from repro.serve import Engine
    c = r["config"]
    reference = arch.load(c["reference"])
    cache_dir = compile_cache.enable()
    dep = c["deployment"]
    lm = LM(model_cfg or program_model(c))
    # the program's parameter layout must be the one the weights have
    want = jax.tree.map(lambda s: (s.shape, s.dtype),
                        jax.eval_shape(lm.init, jax.random.PRNGKey(0)))
    have = jax.tree.map(lambda s: (s.shape, s.dtype), reference.layout(c))
    if want != have:
        raise ValueError("the program's parameter layout departs from the "
                         "benchmark's weights")
    counter = CompileCounter()
    params = jax.block_until_ready(reference.make_weights(c, seed))
    eng = Engine(lm, params, batch=dep["batch"], max_len=dep["max_len"],
                 warm_compile=True, replanner=False)
    return {"jax": jax, "eng": eng, "reference": reference,
            "counter": counter, "device": device, "cache_dir": cache_dir}


def run_cell(r: dict, seed: int, seconds: float, trace: bool, *,
             require_tpu: bool = True, model_cfg=None,
             control: bool = False) -> dict:
    """One run of a resolved cell; returns the run record.  Tests pass
    ``require_tpu=False`` and a small ``model_cfg``."""
    from repro import sparse
    s = start(r, seed, require_tpu=require_tpu, model_cfg=model_cfg)
    jax, eng, reference = s["jax"], s["eng"], s["reference"]
    device, cache_dir = s["device"], s["cache_dir"]
    c, mix = r["config"], r["mix"]
    dep = c["deployment"]
    specs = traffic.build(mix, seed, seconds, c["vocab_size"])
    drv = Driver(eng, specs, mix, seconds, trace=trace, counter=s["counter"])
    drv.run()
    window = (drv.open, drv.close)
    stats = jax.devices()[0].memory_stats() or {}
    device["memory_peak_bytes"] = int(stats.get("peak_bytes_in_use", 0))
    plans = [dict(v) for v in eng.plan_report()["plans"]["per_plan"].values()]
    buckets = eng.buckets
    checked = sample_checked(drv.reqs, window, mix["check"]["requests"],
                             seed, by_due=mix["loop"] == "open")
    served = [(np.asarray(x["req"].prompt), list(x["req"].output))
              for x in checked]
    del eng, drv.eng, s["eng"]
    sparse.reset()
    gc.collect()

    red = None
    if trace:
        red = trace_reduce.reduce(trace_reduce.load(TRACE_DIR))
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]

    t0 = time.perf_counter()
    ref = reference.Reference(c, reference.make_weights(c, seed),
                              dep["max_len"])
    gaps, ctl = [], []
    for prompt, out in served:
        g, gc_ = ref.gaps(prompt, out, control=control)
        gaps.append(g)
        ctl.append(gc_)
    ref_s = time.perf_counter() - t0
    gaps = np.concatenate(gaps) if gaps else np.zeros(0)
    ctl = np.concatenate(ctl) if ctl else np.zeros(0)
    del ref
    gc.collect()

    due = [x for x in drv.reqs if window[0] <= x["due"] < window[1]]
    return {
        "config": c, "cell": r["cell"], "mix": mix, "seed": seed,
        "device": device, "cache_dir": cache_dir,
        "peaks": peaks_lib.peaks_for(device["kind"])
        if device["platform"] == "tpu" else None,
        "setup_s": drv.open - T_START, "window": window,
        "traced": drv.traced, "trace": red,
        "batch": dep["batch"], "max_len": dep["max_len"],
        "buckets": buckets, "plans": plans,
        "requests": [{"prompt_len": len(x["req"].prompt), "due": x["due"],
                      "sent": x["sent"], "times": x["req"].output.times,
                      "done_at": x["done_at"]} for x in drv.reqs],
        "attempted": len(due),
        "failed": sum(1 for x in due if not x["req"].output),
        "admits": drv.admits, "steps": drv.steps,
        "compiles_in_window": drv.compiles_in_window,
        "checked_requests": len(served), "checked_tokens": int(gaps.size),
        "gap_max": float(gaps.max()) if gaps.size else None,
        "control_gap_max": float(ctl.max()) if ctl.size else None,
        "reference_s": ref_s,
    }


def lateness(rec) -> dict:
    w = rec["window"]
    late = [x["sent"] - x["due"] for x in rec["requests"]
            if w[0] <= x["due"] < w[1]]
    if not late:
        return {}
    return {"p50_ms": float(np.percentile(late, 50)) * 1e3,
            "max_ms": float(np.max(late)) * 1e3}


def checks(rec, limits: dict) -> dict:
    """Each number compared, beside its limit."""
    return {"logit_gap_max": {
        "value": rec["gap_max"], "limit": limits["logit_gap_max"]["limit"]},
        "checked_tokens": {"value": rec["checked_tokens"],
                           "limit": limits["checked_tokens"]["limit"]}}


def is_correct(ch: dict) -> bool:
    g, t = ch["logit_gap_max"], ch["checked_tokens"]
    return (g["value"] is not None and g["value"] <= g["limit"]
            and t["value"] >= t["limit"])


def result(r: dict, rec: dict, trace: bool) -> dict:
    metrics = {}
    for m in (r["per_layer"] if trace else r["end_to_end"]):
        v = metric_reader(m["name"])(rec)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    ch = checks(rec, r["limits"])
    out = {"correct": is_correct(ch), "attempted": rec["attempted"],
           "failed": rec["failed"], "metrics": metrics,
           "device": rec["device"],
           "compiles_in_window": rec["compiles_in_window"]}
    if trace and rec["trace"] is not None:
        out["breakdown"] = trace_reduce.breakdown(rec["trace"])
    out["checks"] = ch
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    r = resolve(args.workload)
    rec = run_cell(r, args.seed, args.seconds, bool(args.trace))
    out = result(r, rec, bool(args.trace))
    missing = [m["name"] for m in r["per_layer"]
               if args.trace and m["name"] not in out["metrics"]]
    if missing:
        # on the chip every metric the cell lists finds something to read
        raise SystemExit(f"bench: {args.workload} read nothing for "
                         f"{missing}; trace ops: "
                         f"{sorted((rec['trace'] or {}).get('ops', {}))[:40]}")
    late = lateness(rec)
    print(f"bench: {args.workload} seed {args.seed} on {rec['device']}; "
          f"setup {rec['setup_s']:.3f} s; buckets {rec['buckets']}; "
          f"{out['compiles_in_window']} programs lowered in the window; "
          f"generator lateness {late}; reference "
          f"{rec['reference_s']:.1f} s over {rec['checked_requests']} "
          f"requests", file=sys.stderr)
    for name, v in out["checks"].items():
        print(f"check {name} {v['value']} limit {v['limit']}",
              file=sys.stderr)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
