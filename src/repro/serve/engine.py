"""Request-queue continuous-batching engine over bucketed prefill pools.

A fixed [B, max_len] cache is compiled once; requests are admitted into
free slots as others finish -- vLLM-style continuous batching reduced to
its TPU-friendly static-shape core, with the plan-first lifecycle
running end to end:

* **Bucketed prefill**: prompts are right-padded to a shape bucket, so
  prefill compiles once per *bucket*, not once per prompt length.  The
  bucket ladder is chosen analytically at startup by the calibrated
  cost model (``dispatch.price_tokens`` over the model's matmul stack):
  buckets grow, over the token counts the kernels tile without padding,
  until the priced padding waste of the worst-padded prompt would
  exceed ``pad_max_frac``.  Padding is
  correct because logits are gathered at the *true* last prompt token
  (``LM.prefill(last_index=...)``) and decode attention masks cache
  slots beyond each slot's true position; SSM/hybrid stacks carry
  recurrent state that padding WOULD corrupt, so the engine detects
  them and falls back to exact-length prefill.
* **Plan pools**: every matmul plan the engine's programs build is
  registered under this engine's ``ctx.pool`` label; warmup abstractly
  traces the decode program and every bucket's prefill program
  (``jax.eval_shape``), so steady-state serving issues zero dispatch
  decisions and (with ``warm_compile=True``) zero recompiles.
* **Cost-priced admission**: each admission picks the cheapest
  admissible bucket and accounts the priced padding waste; prompts no
  bucket can hold under ``pad_max_frac`` fall back to exact-length
  prefill (counted -- an operator signal that the ladder is wrong).
* **Async re-planner**: a background thread upgrades the pool's
  analytic route verdicts to measured ones (``sparse.remeasure_plan``)
  while serving, so cold starts never block on a measurement race.
* **Packed weights**: serving is forward-only and its weights are
  fixed, so every block-sparse matrix is packed into its kernel tiles
  once, at startup (``models.model.pack_sparse``), not on every call.
* **Live stats**: ``stats()`` / ``plan_report()["engine"]`` expose
  per-bucket prefill p50/p99 latency, decode-step p50/p99, queue depth,
  padding waste (tokens and priced seconds), capacity overflow,
  ``dropped_frac`` under a bounded queue, and the packed matrices and
  their bytes (``packed_matrices``, ``packed_bytes``).

Termination contract: ``Request.output`` INCLUDES the token generated
at prefill, so a request finishes once ``len(output) >=
max_new_tokens`` -- ``max_new_tokens=4`` yields exactly 4 tokens, the
prefill token plus 3 decode tokens.  ``eos_id`` is honored everywhere a
token is produced, including at prefill (the slot frees immediately,
before a single decode step).

``retained=True`` serves long contexts with the ring-buffer
local+global cache -- the paper's static block sparsity keeping
500k-token decode O(window) (DESIGN.md §3).
"""
from __future__ import annotations

import collections
import dataclasses
import itertools
import threading
import time
from typing import (Callable, Deque, Dict, List, Optional, Sequence,
                    Tuple)

import jax
import jax.numpy as jnp
import numpy as np

from repro import sparse as sparse_api
from repro.core import dispatch
from repro.kernels.tiling import dim_tile
from repro.models.config import ModelCfg
from repro.models.model import LM, pack_sparse

# engine pool labels must be process-unique: two engines over the same
# checkpoint would otherwise share a pool and re-plan each other's work
_ENGINE_SEQ = itertools.count()

_LATENCY_WINDOW = 2048          # rolling percentile window (per stream)

# host spans on the profiler's clock (no-ops unless a trace is running);
# ``docs/api.md`` lists them
_span = jax.profiler.TraceAnnotation


@dataclasses.dataclass
class Request:
    uid: int
    prompt: np.ndarray                  # [S] int32
    max_new_tokens: int = 32
    eos_id: Optional[int] = None
    # filled by the engine
    output: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    bucket: Optional[int] = None        # prefill bucket used (None=exact)
    dropped: bool = False               # rejected by a bounded queue


def _pad_safe(cfg: ModelCfg) -> bool:
    """May prompts be right-padded to a shape bucket?  Attention-only
    stacks: pad rows beyond a slot's true position are never attended
    (decode masks ``slot > position``).  Any recurrent mixer (mamba)
    folds every input row into its state, so padding would corrupt it --
    those stacks serve with exact-length prefill."""
    return all(spec.mixer != "mamba"
               for period, _ in cfg.groups for spec in period)


def _stack_shapes(cfg: ModelCfg) -> List[Tuple[int, int]]:
    """The ``[m, k]`` matmul stack one token traverses -- the pricing
    model behind bucket selection and admission (``price_tokens``).  A
    per-layer proxy (MLA priced at GQA geometry, MoE at top-k expert
    FFNs, mamba at its in/out projections): admission pricing needs
    relative cost across token counts, not kernel-exact FLOPs."""
    d = cfg.d_model
    qd, kvd = cfg.attn_dims
    gated = cfg.act in ("silu", "gelu")
    shapes: List[Tuple[int, int]] = []
    for period, rep in cfg.groups:
        for spec in period:
            for _ in range(rep):
                if spec.mixer == "mamba" and cfg.ssm is not None:
                    di = cfg.ssm.d_inner(d)
                    shapes += [(2 * di, d), (d, di)]
                else:
                    shapes += [(qd + 2 * kvd, d), (d, qd)]
                if spec.ffn == "none":
                    continue
                if spec.ffn == "moe" and cfg.moe is not None:
                    m = cfg.moe
                    shapes.append((m.num_experts, d))        # router
                    ff = m.d_ff_expert * (m.top_k + m.num_shared)
                    shapes += [(ff * (2 if gated else 1), d), (d, ff)]
                else:
                    ff = cfg.d_ff
                    if spec.ffn == "sparse" and cfg.ffn_density:
                        ff = max(1, int(ff * cfg.ffn_density))
                    shapes += [(ff * (2 if gated else 1), d), (d, ff)]
    shapes.append((cfg.vocab_size, d))                       # unembed
    return shapes


def _auto_buckets(top: int, shapes: Sequence[Tuple[int, int]],
                  pad_max_frac: float, *,
                  granularity: int = 16) -> Tuple[int, ...]:
    """Analytic bucket ladder over the sizes the kernels run without
    padding (``tiling.dim_tile``): multiples of ``granularity`` up to
    one lane tile, multiples of the lane tile above it.  Starting from
    the smallest, each next bucket is the largest such size whose
    *priced* padding waste for the worst-padded prompt (one token past
    the previous bucket) stays under ``pad_max_frac`` -- cost-model
    geometry instead of blind powers of two, so fixed per-call overheads
    (which make short prefills cheap to pad) widen the small buckets and
    the ladder stays short.  Always ends at ``top`` (= max_len, the
    cache length)."""
    sizes = [n for n in range(granularity, top, granularity)
             if dim_tile(n)[1] == n] + [top]
    price = {}

    def _p(n: int) -> float:
        if n not in price:
            price[n] = dispatch.price_tokens(shapes, n)
        return price[n]

    i = 0
    buckets = [sizes[0]]
    while i + 1 < len(sizes):
        lo = buckets[-1]
        i += 1
        while (i + 1 < len(sizes)
               and 1.0 - _p(lo + 1) / _p(sizes[i + 1]) <= pad_max_frac):
            i += 1
        buckets.append(sizes[i])
    return tuple(buckets)


def _percentiles(samples: Sequence[float]) -> dict:
    if not samples:
        return {"count": 0, "p50_ms": None, "p99_ms": None}
    arr = np.asarray(samples, np.float64) * 1e3
    return {"count": int(arr.size),
            "p50_ms": round(float(np.percentile(arr, 50)), 4),
            "p99_ms": round(float(np.percentile(arr, 99)), 4)}


class Engine:
    def __init__(self, lm: LM, params, *, batch: int, max_len: int,
                 retained: bool = False, sample: str = "greedy",
                 dispatch_ctx: Optional[dispatch.DispatchContext] = None,
                 plan_cache_dir: Optional[str] = None,
                 warm_plans: bool = True, warm_compile: bool = False,
                 telemetry: bool = True,
                 mesh=None, tp_axis: str = "model",
                 buckets: Optional[Sequence[int]] = None,
                 pad_max_frac: float = 0.75,
                 max_queue: Optional[int] = None,
                 replanner: bool = False,
                 replanner_interval: float = 0.25,
                 replanner_reps: int = 3):
        self.lm = lm
        self.batch = batch
        self.max_len = max_len
        self.retained = retained
        self.pool = f"engine:{lm.cfg.name}:{next(_ENGINE_SEQ)}"
        # every matmul in the traced programs consults this context (the
        # decode/prefill matmul plans are built at engine startup);
        # serving is forward-only, so Pallas routes are admissible
        self.dispatch_ctx = dispatch_ctx or dispatch.DispatchContext(
            differentiable=False)
        # per-engine planning policy: the dispatch knobs plus persistent
        # autotune (measured/analytic route verdicts survive serving
        # restarts via the repro.sparse disk cache); scoped to THIS
        # engine's traced programs, not process-global state.  The
        # ``pool`` label lets the engine enumerate exactly its own plans
        # (sparse.pool_plans) -- the re-planner's worklist.
        # telemetry=False drops the per-call overflow recording (a host
        # callback per planned-capacity matmul per decode step) for
        # latency-critical deployments; plan_report() then shows only
        # plan-time capacity verdicts, no running overflow counts
        # mesh=... makes the engine's plans TP-aware: the k-sharded
        # routes (gspmd + shard_map) join every static plan's measured
        # race, and verdicts are keyed on this mesh's axis names+sizes
        self.plan_ctx = dataclasses.replace(
            sparse_api.PlanContext.from_dispatch(self.dispatch_ctx),
            telemetry=telemetry, mesh=mesh, tp_axis=tp_axis,
            pool=self.pool)
        if plan_cache_dir is not None:
            self.plan_ctx = dataclasses.replace(
                self.plan_ctx, cache_dir=plan_cache_dir, persist=True)
        # fixed weights: a forward-only engine packs every block-sparse
        # matrix into its kernel tiles once, here, and its programs take
        # the tiles as arguments beside the values (``pack_sparse``);
        # plans on a bsmm route then run no relayout per call
        self.params = params
        self.packed = {"matrices": 0, "bytes": 0}
        if not self.plan_ctx.differentiable:
            self.params, self.packed = pack_sparse(lm.cfg, params)
        self.caches = lm.init_cache(batch, max_len)
        self.positions = np.zeros((batch,), np.int32)
        self.live: Dict[int, Request] = {}       # slot -> request
        self.free = list(range(batch))
        self.queue: Deque[Request] = collections.deque()
        self.max_queue = max_queue

        # -- bucket ladder (cost-model geometry) ---------------------------
        self.pad_max_frac = float(pad_max_frac)
        self._shapes = _stack_shapes(lm.cfg)
        self.pad_safe = _pad_safe(lm.cfg)
        # the top bucket fills the whole cache row: a prompt is at most
        # max_len - 1 tokens, and the padded tail is never attended
        top = max_len
        if not self.pad_safe:
            self.buckets: Tuple[int, ...] = ()   # exact-length prefill
        elif buckets is not None:
            ladder = sorted({int(b) for b in buckets if 1 <= b <= top})
            if not ladder or ladder[-1] < top:
                ladder.append(top)
            self.buckets = tuple(ladder)
        else:
            self.buckets = _auto_buckets(top, self._shapes,
                                         self.pad_max_frac)
        self._price_cache: Dict[int, float] = {}

        # -- stats ----------------------------------------------------------
        self._stats_lock = threading.Lock()
        self._counters = collections.Counter()
        self._steps = 0
        self._peak_queue = 0
        self._step_lat: Deque[float] = collections.deque(
            maxlen=_LATENCY_WINDOW)
        self._bucket_stats: Dict[int, dict] = {
            L: {"prefills": 0, "prompt_tokens": 0, "pad_tokens": 0,
                "priced_waste_s": 0.0,
                "latency": collections.deque(maxlen=_LATENCY_WINDOW)}
            for L in self.buckets}

        # -- traced programs ------------------------------------------------
        def decode_fn(p, t, c, pos):
            with dispatch.use_ctx(self.dispatch_ctx), \
                    sparse_api.use_ctx(self.plan_ctx):
                return lm.decode_step(p, t, c, pos, retained=retained)

        def prefill_fn(p, t, last_index):
            with dispatch.use_ctx(self.dispatch_ctx), \
                    sparse_api.use_ctx(self.plan_ctx):
                return lm.prefill(p, t, max_len=max_len,
                                  last_index=last_index)

        self._decode = jax.jit(decode_fn)
        # one jitted program; XLA caches per token-length -- so exactly
        # one compile per bucket (plus one per exact-length fallback)
        self._prefill = jax.jit(prefill_fn)

        def write_slot(caches, row, slot):
            return jax.tree.map(
                lambda c, r: c.at[:, slot].set(r[:, 0]), caches, row)
        self._write_slot = jax.jit(write_slot)

        # plan-first startup: abstractly trace the decode program AND
        # every bucket's prefill program once, so every matmul plan the
        # engine needs is constructed NOW (disk-cached verdicts replay
        # with zero measurements) -- steady-state serving then issues
        # zero dispatch decisions: plan-cache hits only, and after the
        # per-bucket compile no Python at all
        self.plan_stats: Dict[str, int] = {}
        if warm_plans:
            before = sparse_api.cache_stats()
            jax.eval_shape(
                decode_fn, self.params,
                jax.ShapeDtypeStruct((batch, 1), jnp.int32), self.caches,
                jax.ShapeDtypeStruct((batch,), jnp.int32))
            for L in self.buckets:
                jax.eval_shape(
                    prefill_fn, self.params,
                    jax.ShapeDtypeStruct((1, L), jnp.int32),
                    jax.ShapeDtypeStruct((1,), jnp.int32))
            after = sparse_api.cache_stats()
            self.plan_stats = {k: after[k] - before.get(k, 0)
                               for k in ("plans_built", "plan_hits",
                                         "decisions", "measurements",
                                         "disk_hits")}
        if warm_compile:
            self._warm_compile()

        self._replan_thread: Optional[threading.Thread] = None
        self._replan_stop: Optional[threading.Event] = None
        self._replanner_reps = replanner_reps
        if replanner:
            self.start_replanner(interval=replanner_interval,
                                 reps=replanner_reps)

    # -- warmup -----------------------------------------------------------
    def _warm_compile(self):
        """Compile every foreground program up front (one prefill per
        bucket, the decode step, the slot writer) so the serving loop
        never hits an XLA compile.  Results are discarded; engine cache
        state is untouched."""
        row = None
        for L in self.buckets:
            # host arrays, as ``admit`` passes them: the jit cache keys
            # on the argument type, so device arrays here would leave a
            # second entry per bucket to fill while serving
            logits, row = self._prefill(
                self.params, np.zeros((1, L), np.int32),
                np.zeros((1,), np.int32))
            logits.block_until_ready()
        if row is not None:
            jax.block_until_ready(
                self._write_slot(self.caches, row, 0))
        logits, _ = self._decode(
            self.params, jnp.zeros((self.batch, 1), jnp.int32),
            self.caches, jnp.zeros((self.batch,), jnp.int32))
        self._next_tokens(logits)     # the step's device-side argmax

    @staticmethod
    def _next_tokens(logits) -> np.ndarray:
        """Greedy token per slot: argmax on the device, ``[B]`` to host."""
        return np.asarray(jnp.argmax(logits, axis=-1))

    # -- pricing ----------------------------------------------------------
    def _price(self, n_tokens: int) -> float:
        """Calibrated model-seconds for one prefill of ``n_tokens``
        through this model's matmul stack (memoized)."""
        p = self._price_cache.get(n_tokens)
        if p is None:
            p = self._price_cache[n_tokens] = dispatch.price_tokens(
                self._shapes, n_tokens)
        return p

    def bucket_for(self, prompt_len: int) -> Optional[int]:
        """Admission's padding policy: the smallest bucket holding the
        prompt, unless its priced padding waste exceeds
        ``pad_max_frac`` -- then None (exact-length prefill; larger
        buckets only waste more)."""
        for L in self.buckets:
            if L >= prompt_len:
                waste = 1.0 - self._price(prompt_len) / self._price(L)
                if waste <= self.pad_max_frac:
                    return L
                break
        return None

    # -- reports ----------------------------------------------------------
    def stats(self) -> dict:
        """Live serving telemetry -- the engine section of
        ``plan_report()``.  Latency percentiles are over a rolling
        window of the last ``2048`` samples per stream."""
        with self._stats_lock:
            c = dict(self._counters)
            buckets = {
                L: {"prefills": b["prefills"],
                    "prompt_tokens": b["prompt_tokens"],
                    "pad_tokens": b["pad_tokens"],
                    "priced_waste_s": round(b["priced_waste_s"], 9),
                    "latency": _percentiles(b["latency"])}
                for L, b in self._bucket_stats.items()}
            step_lat = _percentiles(self._step_lat)
            steps = self._steps
            peak_queue = self._peak_queue
            replan = {
                "running": self._replan_thread is not None
                and self._replan_thread.is_alive(),
                "sweeps": c.pop("replan_sweeps", 0),
                "upgrades": c.pop("replan_upgrades", 0),
            }
        submitted = c.get("submitted", 0)
        prompt_tokens = sum(b["prompt_tokens"] for b in buckets.values())
        pad_tokens = sum(b["pad_tokens"] for b in buckets.values())
        denom = prompt_tokens + pad_tokens
        return {
            "buckets": buckets,
            "pad_safe": self.pad_safe,
            "packed_matrices": self.packed["matrices"],
            "packed_bytes": self.packed["bytes"],
            "queue_depth": len(self.queue),
            "peak_queue_depth": peak_queue,
            "live_slots": len(self.live),
            "free_slots": len(self.free),
            "steps": steps,
            "step_latency": step_lat,
            "padding": {
                "prompt_tokens": prompt_tokens,
                "pad_tokens": pad_tokens,
                "waste_frac": (round(pad_tokens / denom, 6)
                               if denom else 0.0),
                "priced_waste_s": round(
                    sum(b["priced_waste_s"] for b in buckets.values()),
                    9),
            },
            "admission": {
                "submitted": submitted,
                "admitted": c.get("admitted", 0),
                "finished": c.get("finished", 0),
                "eos_at_prefill": c.get("eos_at_prefill", 0),
                "exact_prefills": c.get("exact_prefills", 0),
                "dropped": c.get("dropped", 0),
                "dropped_frac": (round(c.get("dropped", 0) / submitted, 6)
                                 if submitted else 0.0),
            },
            "capacity_overflow":
                sparse_api.capacity_report()["totals"],
            "replanner": replan,
        }

    def plan_report(self) -> dict:
        """Plans built at engine startup (decode + every prefill
        bucket) + live cache counters + aggregated capacity/overflow
        telemetry (per-plan planned-bucket stats and MoE routing drops)
        + every tensor-parallel decision (raced candidates, measured
        crossover) + the per-plan forward/backward route table
        (``sparse.plan_report()`` -- serving plans are forward-only, so
        ``grad`` is absent here unless the engine shares a process with
        training) + per-plan roofline efficiency with the
        ``kernel_work`` routes leaving >2x headroom
        (``sparse.roofline_report()``) + this engine's live serving
        stats (``engine`` section: per-bucket latency, queue depth,
        padding waste, dropped_frac) -- the serving view of the
        plan-first lifecycle."""
        return {"startup": dict(self.plan_stats),
                "now": sparse_api.cache_stats(),
                "capacity": sparse_api.capacity_report(),
                "tp": sparse_api.tp_report(),
                "plans": sparse_api.plan_report(),
                "roofline": sparse_api.roofline_report(),
                "engine": self.stats()}

    # -- admission --------------------------------------------------------
    def submit(self, req: Request) -> bool:
        """Enqueue a request (validated now, admitted when a slot
        frees).  Under a bounded queue (``max_queue``) a full queue
        drops the request -- ``req.dropped`` is set and the drop counts
        toward ``stats()["admission"]["dropped_frac"]``."""
        self._validate(req)
        with self._stats_lock:
            self._counters["submitted"] += 1
            if (self.max_queue is not None
                    and len(self.queue) >= self.max_queue):
                self._counters["dropped"] += 1
                req.dropped = True
                return False
        self.queue.append(req)
        with self._stats_lock:
            self._peak_queue = max(self._peak_queue, len(self.queue))
        return True

    def _validate(self, req: Request):
        n = int(np.asarray(req.prompt).size)
        if n < 1:
            raise ValueError("empty prompt: a request needs at least "
                             "one prompt token")
        if n >= self.max_len:
            raise ValueError(
                f"prompt length {n} does not fit the engine cache: "
                f"max_len={self.max_len} admits prompts of at most "
                f"{self.max_len - 1} tokens (one cache slot must remain "
                f"for decode)")

    def admit(self, req: Request) -> bool:
        """Prefill ``req`` into a free slot (False when none is free).
        The prompt is padded to the cheapest admissible bucket; the
        first generated token is appended to ``req.output``.  EOS at
        prefill (or ``max_new_tokens <= 1``) finishes the request here
        -- the slot frees immediately, no decode step is spent."""
        self._validate(req)
        if not self.free:
            return False
        prompt = np.asarray(req.prompt, np.int32).reshape(-1)
        n = prompt.shape[0]
        with _span("engine.admit", uid=req.uid, prompt_len=n) as span:
            bucket = self.bucket_for(n)
            span.set_metadata(bucket="exact" if bucket is None else bucket)
            return self._admit(req, self.free.pop(), prompt, bucket)

    def _admit(self, req: Request, slot: int, prompt: np.ndarray,
               bucket: Optional[int]) -> bool:
        n = prompt.shape[0]
        with _span("engine.admit.prefill"):
            if bucket is None:
                padded = prompt[None, :]
            else:
                padded = np.zeros((1, bucket), np.int32)
                padded[0, :n] = prompt
            t0 = time.perf_counter()
            logits, row_caches = self._prefill(
                self.params, padded, np.asarray([n - 1], np.int32))
        with _span("engine.admit.readback"):
            tok = int(np.asarray(logits)[0].argmax())
        dt = time.perf_counter() - t0
        with _span("engine.admit.write_slot"):
            self.caches = self._write_slot(self.caches, row_caches, slot)
        self.positions[slot] = n
        req.output.append(tok)
        req.bucket = bucket
        with self._stats_lock:
            self._counters["admitted"] += 1
            if bucket is None:
                self._counters["exact_prefills"] += 1
            else:
                b = self._bucket_stats[bucket]
                b["prefills"] += 1
                b["prompt_tokens"] += n
                b["pad_tokens"] += bucket - n
                b["priced_waste_s"] += self._price(bucket) \
                    - self._price(n)
                b["latency"].append(dt)
        hit_eos = req.eos_id is not None and tok == req.eos_id
        if hit_eos or len(req.output) >= req.max_new_tokens:
            req.done = True
            self.free.append(slot)
            with self._stats_lock:
                self._counters["finished"] += 1
                if hit_eos:
                    self._counters["eos_at_prefill"] += 1
            return True
        self.live[slot] = req
        return True

    # -- one decode tick ---------------------------------------------------
    def step(self) -> List[Request]:
        """One decode token for every live slot.  Returns the requests
        that finished THIS step (their slots are already free) -- the
        slot-release bookkeeping `run` fires ``on_finish`` from, so no
        caller ever rescans the full request list."""
        if not self.live:
            return []
        with _span("engine.step", step=self._steps, live=len(self.live)):
            return self._step()

    def _step(self) -> List[Request]:
        t0 = time.perf_counter()
        with _span("engine.step.feed"):
            tokens = np.zeros((self.batch, 1), np.int32)
            for slot, req in self.live.items():
                tokens[slot, 0] = req.output[-1]
            tokens, positions = jnp.asarray(tokens), jnp.asarray(
                self.positions)
        with _span("engine.step.launch"):
            logits, self.caches = self._decode(
                self.params, tokens, self.caches, positions)
        with _span("engine.step.readback"):
            nxt = self._next_tokens(logits)
        with _span("engine.step.retire"):
            return self._retire(nxt, t0)

    def _retire(self, nxt: np.ndarray, t0: float) -> List[Request]:
        """Append each live slot's token and free the slots whose
        request finished."""
        finished: List[Request] = []
        released: List[int] = []
        for slot, req in self.live.items():
            tok = int(nxt[slot])
            req.output.append(tok)
            self.positions[slot] += 1
            full = len(req.output) >= req.max_new_tokens
            hit_eos = req.eos_id is not None and tok == req.eos_id
            oom = self.positions[slot] >= self.max_len - 1
            if full or hit_eos or oom:
                req.done = True
                finished.append(req)
                released.append(slot)
        for slot in released:
            del self.live[slot]
            self.free.append(slot)
        with self._stats_lock:
            self._steps += 1
            self._step_lat.append(time.perf_counter() - t0)
            self._counters["finished"] += len(finished)
        return finished

    # -- the serving loop ---------------------------------------------------
    def serve(self,
              on_finish: Optional[Callable[[Request], None]] = None):
        """Drive until the queue and every live slot drain.
        ``on_finish`` fires exactly once per finished request, straight
        from admission / slot-release bookkeeping."""
        while self.queue or self.live:
            while self.queue and self.free:
                req = self.queue.popleft()
                self.admit(req)
                if req.done and on_finish:
                    on_finish(req)
            for req in self.step():
                if on_finish:
                    on_finish(req)

    def run(self, requests: List[Request],
            on_finish: Optional[Callable[[Request], None]] = None):
        """Enqueue ``requests`` and serve until done (continuous
        batching).  Dropped requests (bounded queue) never fire
        ``on_finish``; check ``req.dropped``."""
        for r in requests:
            self.submit(r)
        self.serve(on_finish=on_finish)
        return requests

    # -- background re-planner ----------------------------------------------
    def replan_once(self, *, reps: Optional[int] = None) -> int:
        """One synchronous re-planner sweep: upgrade every analytic
        route verdict in this engine's plan pool to a measured one
        (``sparse.remeasure_plan``).  Returns the number of upgrades.
        Safe to call while serving: already-compiled programs keep
        their route; upgrades apply to new traces and, via the disk
        cache, to restarts."""
        n = 0
        for p in sparse_api.analytic_plans(self.pool):
            info = sparse_api.remeasure_plan(
                p, reps=self._replanner_reps if reps is None else reps)
            if info:
                n += 1
        with self._stats_lock:
            self._counters["replan_sweeps"] += 1
            self._counters["replan_upgrades"] += n
        return n

    def start_replanner(self, *, interval: float = 0.25,
                        reps: Optional[int] = None):
        """Start the async re-planner thread: periodically sweeps this
        engine's pool, upgrading analytic verdicts to measured ones in
        the background so serving never blocks on a measurement race.
        Idempotent; stop with ``stop_replanner()`` (also safe to leave
        running -- the thread is a daemon)."""
        if self._replan_thread is not None \
                and self._replan_thread.is_alive():
            return
        stop = threading.Event()

        def loop():
            while not stop.is_set():
                self.replan_once(reps=reps)
                if stop.wait(interval):
                    return

        self._replan_stop = stop
        self._replan_thread = threading.Thread(
            target=loop, name=f"replanner[{self.pool}]", daemon=True)
        self._replan_thread.start()

    def stop_replanner(self, timeout: float = 10.0):
        if self._replan_stop is not None:
            self._replan_stop.set()
        if self._replan_thread is not None:
            self._replan_thread.join(timeout)
        self._replan_thread = None
        self._replan_stop = None
