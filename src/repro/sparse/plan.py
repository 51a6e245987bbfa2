"""Two-phase plan/execute sparse matmul (the plan-first public API).

    from repro import sparse

    p = sparse.plan(operand, n)            # phase 1: ALL one-time work
    y = p(values, x)                       # phase 2: zero-decision call
    y = p.apply(operand, x)                # payload extracted for you
    print(sparse.format_plan(p))           # what will run, and why

Phase 1 mirrors PopSparse's ahead-of-time planning: operand
normalization, pattern analysis (``partitioner.plan_packing`` /
``plan_k_shards`` -- the one-time halves of the packing and TP
sharding), route selection through the dispatch cost model (optionally
wall-clock measured), dynamic bucket sizing (``planner.plan_dynamic``),
and mesh-aware TP routes from ``core/tp.py``.  The result is a frozen
``MatmulPlan`` whose execute closure contains no decisions: safe under
``jax.jit`` / ``grad`` / ``vmap`` on every route -- differentiable
plans carry a plan-level ``jax.custom_vjp`` whose backward runs two
planned sibling products (transposed-pattern SpMM for dL/dx, block
SDDMM for dL/dvalues), so even Pallas forwards train -- and a plain
direct call in the steady state.

Verdicts persist to a versioned on-disk cache (``sparse.cache``), so a
serving restart re-plans without re-measuring.
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading
from typing import Any, Callable, Dict, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

import repro.core.dispatch as dispatch
import repro.core.partitioner as partitioner
import repro.core.planner as planner_lib
import repro.core.static_sparse as _ssp
import repro.core.tp as tp_lib
from repro.core.bsr import BlockSparseMatrix
from repro.core.dynamic_sparse import DynamicOperand, _dspmm
from repro.sparse import cache as cache_lib
from repro.sparse.spec import (CapacityStats, OpSpec, PlanContext,
                               PLAN_ROUTES, TP_ROUTES, pattern_key,
                               payload_of)

Operand = Union[jax.Array, np.ndarray, BlockSparseMatrix, DynamicOperand]

_plan_cache: Dict[tuple, "MatmulPlan"] = {}
_plan_lock = threading.Lock()

# per-problem running overflow telemetry (keyed by the plan's persistent
# key string, plus free-form names like "moe_dispatch"): outlives plan
# objects so escalation survives a plan-cache eviction and the serving
# engine can aggregate across its lifetime
_capacity_registry: Dict[str, CapacityStats] = {}
_capacity_lock = threading.Lock()

# process-wide evolution telemetry (MatmulPlan.evolve): how many
# topology updates ran, how many tripped the drift guardrail, and how
# many re-raced the routes -- surfaced in plan_report()["totals"]
_evolution_totals: Dict[str, int] = {"evolves": 0, "reraces": 0,
                                     "drift_trips": 0}

# serving-engine plan pools: ctx.pool label -> mem_keys of every plan
# built under that label.  Pool membership is runtime-only bookkeeping
# (the label joins neither the disk fingerprint nor the mem key -- see
# spec.PlanContext), so the engine can enumerate "my plans" without
# owning plan identity.  Guarded by _plan_lock.
_pool_registry: Dict[str, list] = {}

# background re-planner verdict overlay: persistent key string -> the
# upgraded (measured) decision record.  _decide consults this BEFORE
# the disk cache, so re-planned verdicts win even when persistence is
# off for the process; remeasure_plan also writes the record to disk
# when persistence is on, mirroring the escalation guardrail.
_replanned: Dict[str, dict] = {}

# the routes that multiply a ``partitioner.PackedTiles`` payload as it is
PACKED_ROUTES = ("static_pallas", "static_balanced")


def reset(*, counters: bool = True):
    """Forget every in-memory plan, decision, capacity stat, and
    (optionally) counter.  Disk cache files survive -- this simulates a
    fresh process."""
    with _plan_lock:
        _plan_cache.clear()
        _shard_meta_cache.clear()
        _transpose_cache.clear()
        _sddmm_meta_cache.clear()
        _pool_registry.clear()
        _replanned.clear()
        for k in _evolution_totals:
            _evolution_totals[k] = 0
    with _capacity_lock:
        _capacity_registry.clear()
    cache_lib.reset(counters=counters)
    dispatch.clear_cache()


def reset_telemetry():
    """Zero the process-wide telemetry aggregates -- the running
    ``capacity_report()`` counters and the ``plan_report()`` evolution
    totals -- WITHOUT forgetting plans or verdicts (``reset()`` does
    that).  Stats objects referenced by live cached plans are zeroed in
    place (plans keep recording into them); orphaned registry entries
    are dropped.  The test suite runs this between tests so telemetry
    assertions never depend on execution order."""
    with _plan_lock:
        live = {id(p.capacity_stats) for p in _plan_cache.values()
                if p.capacity_stats is not None}
        for k in _evolution_totals:
            _evolution_totals[k] = 0
    with _capacity_lock:
        for key in list(_capacity_registry):
            stats = _capacity_registry[key]
            if id(stats) not in live:
                del _capacity_registry[key]
                continue
            with stats._lock:
                stats.calls = 0
                stats.overflow_calls = 0
                stats.tiles_dropped_total = 0
                stats.blocks_dropped_total = 0
                stats.dropped_frac_sum = 0.0
                stats.max_dropped_frac = 0.0
                stats.last_tiles_total = 0
                stats.last_tiles_dropped = 0


def _capacity_stats_for(key: str, **kw) -> CapacityStats:
    with _capacity_lock:
        stats = _capacity_registry.get(key)
        if stats is None:
            stats = _capacity_registry[key] = CapacityStats(key, **kw)
        return stats


def capacity_report() -> dict:
    """Aggregated overflow telemetry across every planned-capacity
    problem this process has executed (plus free-form streams such as
    MoE routing drops).  The serving engine folds this into
    ``plan_report()``."""
    with _capacity_lock:
        per_key = {k: s.report() for k, s in _capacity_registry.items()}
    return {
        "per_plan": per_key,
        "totals": {
            "calls": sum(r["calls"] for r in per_key.values()),
            "overflow_calls": sum(r["overflow_calls"]
                                  for r in per_key.values()),
            "tiles_dropped_total": sum(r["tiles_dropped_total"]
                                       for r in per_key.values()),
            "escalated_plans": sum(1 for r in per_key.values()
                                   if r["escalated"]),
        },
    }


def record_dropped(name: str, dropped_frac) -> None:
    """Best-effort drop telemetry for non-plan capacity buckets (e.g.
    MoE routing ``dropped_frac``): folds one step's dropped fraction
    into the named ``CapacityStats`` stream.  No-op under tracing --
    eager callers (tests, eval loops) get exact accounting, compiled
    training steps pay nothing."""
    if isinstance(dropped_frac, jax.core.Tracer):
        return
    frac = float(np.asarray(dropped_frac).max())
    stats = _capacity_stats_for(name)
    # fraction-only stream: no tiles/blocks -- overflow_calls still
    # counts via frac > 0, and tile-drop totals stay uninflated
    stats.record(0, 0, 0, frac)


def cache_stats() -> dict:
    """Plan/decision counters + live cache sizes (see ``sparse.cache``)."""
    stats = cache_lib.cache_stats()
    stats["plan_entries"] = len(_plan_cache)
    return stats


def tp_report() -> dict:
    """Every tensor-parallel decision this process has planned: per plan
    the raced candidates, the verdict's source (measured / analytic /
    disk), and the measured crossover (best-unsharded / best-TP time --
    > 1 means the problem is past the TP crossover).  The serving
    engine folds this into ``plan_report()``."""
    with _plan_lock:
        plans = list(_plan_cache.values())
    per = {}
    for p in plans:
        tp = p.artifacts.get("tp")
        if tp:
            per[p.key] = dict(tp, route=p.route, from_disk=p.from_disk)
    return {
        "per_plan": per,
        "totals": {
            "tp_planned": len(per),
            "tp_chosen": sum(1 for r in per.values() if r["chosen"]),
            "measured": sum(1 for r in per.values()
                            if r["source"] == "measured"),
        },
    }


def plan_report() -> dict:
    """Every plan this process holds, with its forward route AND its
    backward (grad) route choices -- the one-stop training view of the
    plan-first lifecycle.  ``grad.mode`` per plan is "planned" (the
    plan-level custom_vjp runs the raced sibling products), "native"
    (autodiff of the XLA formulation), or "unavailable" (forward-only
    Pallas plan; differentiating raises)."""
    with _plan_lock:
        plans = list(_plan_cache.values())
        evo_totals = dict(_evolution_totals)
    per = {}
    for p in plans:
        grad = p.artifacts.get("grad")
        ev = p.artifacts.get("evolution")
        # an evolve chain shares one pattern-free disk key; suffix the
        # generation so live generations do not shadow each other here
        rkey = p.key if not ev else f"{p.key}#gen{ev['generation']}"
        per[rkey] = {
            "route": p.route, "source": p.source,
            "from_disk": p.from_disk, "op": p.spec.op,
            "kind": p.spec.kind, "shape": (p.spec.m, p.spec.k, p.spec.n),
            "grad": grad,
            "evolution": p.artifacts.get("evolution"),
        }
    planned = [r for r in per.values()
               if (r["grad"] or {}).get("mode") == "planned"]
    evolved = [r for r in per.values() if r["evolution"]]
    return {
        "per_plan": per,
        "totals": {
            "plans": len(per),
            "grad_planned": len(planned),
            "grad_measured": sum(
                1 for r in planned
                if "dx" in r["grad"]
                and r["grad"]["dx"].get("source") == "measured"),
            "grad_from_disk": sum(1 for r in planned
                                  if r["grad"].get("from_disk")),
            "evolution": dict(evo_totals,
                              evolved_plans=len(evolved),
                              max_generation=max(
                                  (r["evolution"]["generation"]
                                   for r in evolved), default=0)),
        },
    }


def roofline_report() -> dict:
    """Roofline efficiency of every plan this process holds: the chosen
    route's achieved-vs-bound fraction plus the union of routes flagged
    for leaving >2x headroom (``kernel_work``) -- the serving engine
    folds this into ``plan_report()``."""
    with _plan_lock:
        plans = list(_plan_cache.values())
    per = {}
    flagged = set()
    for p in plans:
        r = p.roofline()
        per[p.key] = {"route": p.route, "chosen": r["chosen"],
                      "kernel_work": r["kernel_work"]}
        flagged.update(r["kernel_work"])
    chosen_eff = [r["chosen"]["efficiency"] for r in per.values()
                  if r["chosen"]]
    return {
        "per_plan": per,
        "totals": {
            "plans": len(per),
            "chosen_flagged": sum(1 for r in per.values()
                                  if r["chosen"] and r["chosen"]["flagged"]),
            "min_chosen_efficiency": (round(min(chosen_eff), 4)
                                      if chosen_eff else None),
            "kernel_work_routes": sorted(flagged),
        },
    }


def pool_plans(pool: str) -> list:
    """Every live plan built under ``ctx.pool == pool``, in build order.
    Plans evicted from the in-memory cache (capacity escalation, a
    re-planner upgrade) drop out until the holder rebuilds them."""
    with _plan_lock:
        keys = list(_pool_registry.get(pool, ()))
        plans = [_plan_cache.get(k) for k in keys]
    return [p for p in plans if p is not None]


def _remeasurable(p: "MatmulPlan") -> bool:
    """Can the background re-planner wall-clock this plan?  Analytic
    forward verdicts only; TP plans are excluded (their race needs the
    real mesh installed -- the foreground ``measure=True`` path owns
    that); spec-only static plans have no pattern to synthesize."""
    if p.source != "analytic" or p.key in _replanned:
        return False
    if p.ctx.resolved_tp_q():
        return False
    if p.spec.kind == "static" and p.pattern is None:
        return False
    return True


def analytic_plans(pool: Optional[str] = None) -> list:
    """The re-planner's worklist: live plans whose forward verdict is
    still analytic (cost-model priced, never wall-clocked) and that
    ``remeasure_plan`` can upgrade.  ``pool`` restricts to one serving
    engine's plans; None scans the whole process."""
    if pool is not None:
        plans = pool_plans(pool)
    else:
        with _plan_lock:
            plans = list(_plan_cache.values())
    return [p for p in plans if _remeasurable(p)]


def _synth_inputs(spec: OpSpec, pattern, seed: int):
    """Concrete ``(operand, x)`` realizing the plan's spec, for the
    background measurement race.  Route timing depends on shapes,
    density, and pattern layout -- not values -- so synthesized normal
    values measure what the foreground race would have."""
    kv, kp = jax.random.split(jax.random.PRNGKey(seed))
    dt = jnp.dtype(spec.dtype)
    if not jnp.issubdtype(dt, jnp.floating):
        dt = jnp.dtype("float32")
    x = jax.random.normal(kv, (spec.k, spec.n), dt)
    b = spec.block_size
    if spec.kind == "dense":
        return jax.random.normal(kp, (spec.m, spec.k), dt), x
    if spec.kind == "static":
        rows, cols = pattern
        mask = np.zeros((spec.m // b, spec.k // b), bool)
        mask[np.asarray(rows), np.asarray(cols)] = True
        return BlockSparseMatrix.from_mask(mask, b, dtype=dt,
                                           init="normal", key=kp), x
    # dynamic: capacity-shaped operand at the spec's d_max density
    from repro.core import masks
    mask = masks.random_block_mask(spec.m, spec.k, b, spec.density,
                                   seed=seed)
    rows, cols = np.nonzero(mask)
    cap = max(1, len(rows))
    operand = DynamicOperand(
        values=jax.random.normal(kp, (cap, b, b), dt),
        row_idx=jnp.asarray(rows.astype(np.int32)),
        col_idx=jnp.asarray(cols.astype(np.int32)),
        nnz=jnp.asarray(len(rows), jnp.int32),
        shape=(spec.m, spec.k), block_size=b)
    return operand, x


def remeasure_plan(p: "MatmulPlan", *, reps: int = 3,
                   seed: int = 0) -> Optional[dict]:
    """Upgrade one plan's analytic forward verdict to a measured one --
    the serving engine's background re-planner body.  Wall-clocks every
    runnable candidate on synthesized inputs of the plan's spec (the
    same harness as the foreground ``measure=True`` race), installs the
    winning verdict in the ``_replanned`` overlay + the disk cache (when
    persistence is on), and evicts the stale plan from the in-memory
    cache so the holder's next ``plan()`` call adopts the measured
    route.  Already-compiled closures keep running the analytic route --
    upgrades apply to new traces, exactly like capacity escalation.

    Returns ``{key, route_before, route_after, measured, upgraded}`` or
    None when the plan is not remeasurable (already measured / TP /
    spec-only static)."""
    if not _remeasurable(p):
        return None
    spec, ctx = p.spec, p.ctx
    dctx = _selection_ctx(spec, ctx)
    operand, x = _synth_inputs(spec, p.pattern, seed)
    cands = dispatch._candidates(spec.kind, dctx)
    runnable = [r for r in cands if dispatch._executable(r, dctx)]
    if not runnable:
        return None
    measured = {r: dispatch._measure_route(r, operand, x, dctx,
                                           reps=reps)
                for r in runnable}
    cache_lib.bump("measurements")
    est = dict(p.est_seconds)
    est.update(measured)
    route = min(measured, key=measured.get)
    rec = {"route": route, "source": "measured",
           "est_seconds": {r: float(s) for r, s in est.items()}}
    cap = p.artifacts.get("capacity")
    if cap:
        rec["capacity"] = {k2: v for k2, v in cap.items()
                           if k2 != "escalated"}
    grad_art = p.artifacts.get("grad")
    if grad_art and grad_art.get("mode") == "planned" \
            and "dx" in grad_art and _grad_covered(spec, ctx):
        rec["grad"] = {side: dict(grad_art[side])
                       for side in ("dx", "dvalues")}
    with _plan_lock:
        _replanned[p.key] = rec
        for mk in [mk for mk, q in _plan_cache.items() if q is p]:
            _plan_cache.pop(mk, None)
    if ctx.cache and ctx.persistence_on():
        cache_lib.store_decision(ctx.resolved_cache_dir(), p.key, rec)
    return {"key": p.key, "route_before": p.route, "route_after": route,
            "measured": {r: float(s) for r, s in measured.items()},
            "upgraded": True}


def configure(cache_dir: Optional[str] = None):
    """Set the process-default persistent cache directory."""
    cache_lib.configure(cache_dir)


# ---------------------------------------------------------------------------
# MatmulPlan
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True, eq=False)
class MatmulPlan:
    """Frozen verdict of ``sparse.plan``: route + one-time artifacts +
    a decision-free execute closure.

    Call ``plan(payload, x)`` with the per-call payload:

    * static kind  -- the ``[nnz, b, b]`` values (pattern is baked in),
      or, where ``takes_packed``, the ``sparse.pack`` tile stack
    * dynamic kind -- the ``DynamicOperand`` (pattern is runtime data)
    * dense kind   -- the dense weight array

    ``apply(operand, x)`` extracts the payload from a full operand.
    """

    spec: OpSpec
    route: str
    source: str                      # analytic | measured | forced
    est_seconds: Dict[str, float]
    from_disk: bool
    ctx: PlanContext
    key: str                         # persistent-cache key string
    artifacts: Dict[str, Any]
    _execute: Optional[Callable] = None
    # running overflow telemetry for planned-capacity routes (mutable by
    # design; lives in the process-wide registry keyed by ``key`` so it
    # survives plan-cache eviction -- see ``capacity_report``)
    capacity_stats: Optional[CapacityStats] = None

    @property
    def executable(self) -> bool:
        return self._execute is not None

    @property
    def takes_packed(self) -> bool:
        """Does this plan multiply a ``sparse.pack`` payload?  A forward-
        only plan on a bsmm route: a packed stack carries no gradient
        back to the values."""
        return self.route in PACKED_ROUTES and not self.ctx.differentiable

    def __call__(self, payload, x) -> jax.Array:
        if self._execute is None:
            raise ValueError(
                f"plan for {self.spec} was built from an OpSpec without a "
                f"concrete pattern; build it from the operand to execute "
                f"(spec-only static plans are explain/report-only)")
        if isinstance(payload, partitioner.PackedTiles) \
                and not self.takes_packed:
            raise ValueError(
                f"a plan on route {self.route!r} (differentiable="
                f"{self.ctx.differentiable}) takes the [nnz, b, b] values; "
                f"packed tiles serve forward-only plans on "
                f"{PACKED_ROUTES}")
        s = self.spec
        # the contraction dim is baked into every route's metadata; a
        # mismatch must fail here, not deep inside a kernel.  (n may
        # differ from the planned n -- routes tile n at trace time.)
        if s.op == "spmm":
            if x.ndim != 2 or x.shape[0] != s.k:
                raise ValueError(f"plan expects x of shape [k={s.k}, n]; "
                                 f"got {x.shape}")
        elif s.op == "matmul":
            if x.shape[-1] != s.k or tuple(payload.shape) != (s.k, s.m):
                raise ValueError(
                    f"plan expects w [k={s.k}, n={s.m}] and x [..., "
                    f"{s.k}]; got w {payload.shape}, x {x.shape}")
        elif s.op == "batched_matmul":
            if payload.shape[-1] != s.k or x.shape[-2] != s.k:
                raise ValueError(
                    f"plan expects [..., C, D={s.k}] @ [..., D={s.k}, F]; "
                    f"got {payload.shape} @ {x.shape}")
        return self._execute(payload, x)

    def apply(self, operand: Operand, x) -> jax.Array:
        return self(payload_of(operand), x)

    def vjp(self, payload, x):
        """``(y, vjp_fn)`` through the planned route.  Plans built with
        ``ctx.differentiable`` (the default) carry a plan-level
        ``custom_vjp`` whose backward runs the planned sibling products
        (transposed-SpMM dL/dx + block-SDDMM dL/dvalues -- see
        ``explain()["grad"]``), so this works on every route, Pallas
        included.  Forward-only plans raise a ValueError naming the
        route and the ``mode=`` workaround when differentiated."""
        return jax.vjp(lambda v, xx: self(v, xx), payload, x)

    def explain(self) -> dict:
        """Full decision report (dispatch-report compatible + the plan's
        one-time artifacts)."""
        s = self.spec
        return {
            "problem": {"kind": s.kind, "m": s.m, "k": s.k, "n": s.n,
                        "block_size": s.block_size,
                        "density": round(s.density, 5),
                        "density_bucket":
                            dispatch._density_bucket(s.density),
                        "dtype": s.dtype},
            "mode": s.mode,
            "op": s.op,
            "pallas_admissible": dispatch._pallas_ok(
                _selection_ctx(s, self.ctx)),
            "candidates": {r: self.est_seconds[r] for r in
                           sorted(self.est_seconds,
                                  key=self.est_seconds.get)},
            "chosen": self.route,
            "source": self.source,
            "cached": self.from_disk,
            "from_disk": self.from_disk,
            "cache_key": self.key,
            "tp": self.artifacts.get("tp"),
            "grad": self.artifacts.get("grad"),
            "evolution": self.artifacts.get("evolution"),
            "roofline": self.roofline(),
            # underscore artifacts are host-side working state (pattern
            # arrays, carry maps), not report material
            "plan": dict({k2: v for k2, v in self.artifacts.items()
                          if not k2.startswith("_")},
                         executable=self.executable),
            "capacity": (dict(self.artifacts.get("capacity", {}),
                              stats=self.capacity_stats.report())
                         if self.capacity_stats is not None else
                         self.artifacts.get("capacity")),
        }

    def roofline(self, *, flag_headroom: float = 2.0) -> dict:
        """Per-route roofline efficiency over the raced forward
        candidates: how close each route's (estimated or measured) time
        sits to the hardware bound for the work it executes, against
        the peaks of the device JAX runs on (``analysis.roofline.PEAKS``).
        A device without published peaks (the CPU) gets no figures, and
        ``unavailable`` says why.

        ``routes[r]["flagged"]`` marks routes leaving more than
        ``flag_headroom``x on the table; ``kernel_work`` collects them
        -- the sparsity-roofline signal that a route is a kernel to
        optimize, not a shape to avoid.  TP routes are excluded (their
        estimates are per-mesh collective times, priced by
        ``explain()["tp"]`` instead)."""
        from repro.analysis import roofline as roofline_lib
        hw = roofline_lib.device_peaks()
        rep = {"hw": hw.name if hw else None,
               "flag_headroom": flag_headroom, "chosen": None,
               "routes": {}, "kernel_work": []}
        if hw is None:
            d = jax.devices()[0]
            rep["unavailable"] = (f"no published peaks for {d.platform} "
                                  f"device {d.device_kind!r}")
            return rep
        for route, est in self.est_seconds.items():
            if route in TP_ROUTES:
                continue
            eff = roofline_lib.route_efficiency(
                est, self.spec.roofline_cost(route), hw,
                flag_headroom=flag_headroom)
            rep["routes"][route] = {
                "achieved_us": round(eff["achieved_seconds"] * 1e6, 3),
                "bound_us": round(eff["bound_seconds"] * 1e6, 3),
                "dominant": eff["dominant"],
                "efficiency": round(eff["efficiency"], 4),
                "headroom": round(eff["headroom"], 2),
                "flagged": eff["flagged"],
            }
        rep["chosen"] = rep["routes"].get(self.route)
        rep["kernel_work"] = sorted(r for r, e in rep["routes"].items()
                                    if e["flagged"])
        return rep

    def capacity_report(self) -> Optional[dict]:
        """Planned capacity + running overflow stats for this plan
        (None for routes without a planned bucket)."""
        if self.capacity_stats is None:
            return None
        return dict(self.artifacts.get("capacity", {}),
                    stats=self.capacity_stats.report())

    @property
    def pattern(self) -> Optional[Tuple[np.ndarray, np.ndarray]]:
        """``(row_idx, col_idx)`` host block indices baked into an
        executable static plan (None otherwise).  The slot order is the
        values order the plan executes with."""
        return self.artifacts.get("_pattern")

    def evolve(self, new_pattern, *, rerace: Optional[bool] = None,
               x=None) -> "MatmulPlan":
        """Incremental plan mutation for dynamic sparse training
        (RigL-style topology updates on a *static* plan).

        Re-runs only the cheap host pattern phases -- tile packing
        (``plan_packing``), backward transpose (``plan_transpose``),
        TP k-sharding (``plan_k_shards``), grouped-capacity sizing --
        and keeps the existing route verdict, backward verdicts, and
        disk decision record: a no-drift evolve performs **zero** route
        decisions and **zero** measurements.  A full re-race runs only
        when the pattern's density/tile-occupancy profile drifts past
        ``ctx.evolve_drift`` relative to the profile the verdicts were
        raced on (or when ``rerace=True`` forces it; ``rerace=False``
        suppresses even the drift trip).  The evolution lineage
        (parent/root keys, generation, drift, re-race verdict) rides in
        ``explain()["evolution"]`` and the persisted decision record.

        ``new_pattern`` is a static ``BlockSparseMatrix`` (values
        ignored), a bool block mask over the ``[m/b, k/b]`` grid, or a
        ``(row_idx, col_idx)`` tuple.  ``x`` is used only when a
        re-race measures (``ctx.measure`` + concrete inputs).  Use
        ``carry_values(old_values)`` on the result to map the old
        values stack into the new pattern's slots.
        """
        s = self.spec
        if s.kind != "static" or s.op != "spmm":
            raise ValueError(
                f"evolve() mutates static spmm plans; this plan is "
                f"kind={s.kind!r} op={s.op!r} (dynamic-kind patterns "
                f"are runtime data -- change the operand, not the plan)")
        if self._execute is None or self.pattern is None:
            raise ValueError(
                "cannot evolve a spec-only (report-only) plan: the "
                "concrete pattern is required; build the plan from the "
                "operand")
        return _evolve_plan(self, _as_static_bsr(new_pattern, s),
                            rerace, x)

    def carry_values(self, old_values) -> jax.Array:
        """Map the parent pattern's ``[nnz_old, b, b]`` values into this
        evolved plan's slots: carried blocks keep their values, grown
        blocks start at zero (RigL semantics).  Jit-compatible."""
        ep = self.artifacts.get("_evolve")
        if ep is None:
            raise ValueError(
                "carry_values() needs an evolved plan (the result of "
                "plan.evolve(...)); this plan has no evolution parent")
        return partitioner.apply_evolution(ep, old_values)


def format_plan(plan: MatmulPlan) -> str:
    """Human-readable plan report (quickstart / perf_cell / debugging)."""
    rep = plan.explain()
    lines = [dispatch.format_explain(rep)]
    art = rep["plan"]
    extra = []
    if "packing_tiles" in art:
        extra.append(f"packing: {art['packing_tiles']} MXU tiles, "
                     f"occupancy {art['packing_occupancy']:.3f}")
    if "bucket_blocks" in art:
        extra.append(f"buckets: {art['bucket_blocks']} blocks/bucket over "
                     f"q=({art['q_m']},{art['q_k']},{art['q_n']})")
    if "tp_q" in art:
        extra.append(
            f"tp: {art.get('tp_route', 'static_tp')} q={art['tp_q']} "
            f"{'nnz-balanced' if art.get('tp_balanced', True) else 'even'}"
            f" k-shards over '{art['tp_axis']}'")
    tpd = art.get("tp")
    if tpd and tpd.get("tp_speedup_vs_unsharded") is not None:
        extra.append(
            f"tp race ({tpd['source']}): best {tpd['best_tp_route']} "
            f"{tpd['tp_speedup_vs_unsharded']}x vs "
            f"{tpd['best_unsharded_route']}"
            + (" [past crossover]" if tpd["tp_wins"] else ""))
    g = art.get("grad")
    if g:
        if g.get("mode") == "planned" and "dx" in g:
            extra.append(
                f"grad: dx={g['dx']['route']} "
                f"dvalues={g['dvalues']['route']} "
                f"({g['dx']['source']}"
                + (", disk-cached" if g.get("from_disk") else "") + ")")
        else:
            extra.append(f"grad: {g.get('mode')}")
    roof = rep.get("roofline")
    if roof and roof.get("chosen"):
        ch = roof["chosen"]
        line = (f"roofline: {ch['efficiency']:.0%} of "
                f"{ch['dominant']}-bound ({ch['headroom']:.1f}x headroom"
                + (", >2x -- kernel work" if ch["flagged"] else "") + ")")
        others = [r for r in roof["kernel_work"] if r != rep["chosen"]]
        if others:
            line += f"; also flagged: {', '.join(others)}"
        extra.append(line)
    ev = art.get("evolution")
    if ev:
        thr = ev.get("drift_threshold")
        extra.append(
            f"evolution: gen {ev['generation']} "
            f"(+{ev['grown']}/-{ev['dropped']} blocks, drift "
            f"{ev['drift']:.3f}/{'off' if thr is None else thr}"
            + (", re-raced" if ev.get("reraced")
               else ", verdicts reused") + ")")
    if "grouped_tile" in art:
        t = art["grouped_tile"]
        cap = art.get("grouped_tiles_cap")   # exact for static kind
        extra.append(f"grouped: {t}x{t} tile slots"
                     + (f" (cap {cap})" if cap is not None else ""))
    capsec = art.get("capacity")
    if capsec:
        extra.append(
            f"capacity: {capsec['policy']} cap {capsec['tiles_cap']} "
            f"(E[tiles] {capsec['expected_tiles']:.0f} x headroom "
            f"{capsec['headroom']:.2f}, worst {capsec['worst_tiles']}, "
            f"P[overflow] {capsec['overflow_p']:.3f})"
            + (" [clamped]" if capsec.get("clamped") else ""))
        if plan.capacity_stats is not None and plan.capacity_stats.calls:
            s = plan.capacity_stats
            extra.append(f"overflow: {s.overflow_calls}/{s.calls} calls, "
                         f"{s.tiles_dropped_total} tiles dropped"
                         + (" [escalated]" if s.escalated else ""))
    if extra:
        lines.append("   plan: " + "; ".join(extra))
    lines.append(f"   ({'disk-cached' if plan.from_disk else 'planned'} "
                 f"{'executable' if plan.executable else 'report-only'})")
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# Decision (memory -> disk -> dispatch cost model / measurement)
# ---------------------------------------------------------------------------

def _grad_covered(spec: OpSpec, ctx: PlanContext) -> bool:
    """Does this plan get the plan-level planned backward (custom_vjp
    over sibling transposed-SpMM + SDDMM products)?  Static patterns
    with a concrete-operand ``spmm`` op and a differentiable caller."""
    return (ctx.differentiable and spec.op == "spmm"
            and spec.kind == "static")


def _selection_ctx(spec: OpSpec, ctx: PlanContext) -> dispatch.DispatchContext:
    """The dispatch view used for *route selection*.  Plans with a
    plan-level backward (static/dynamic spmm) register their own
    ``custom_vjp``, so the forward kernel never needs a VJP of its own:
    Pallas forwards are admissible even for differentiable plans (the
    paper's fast path no longer falls away under training).  The plan
    fingerprint still carries the caller's ``differentiable`` flag --
    only the candidate gate is relaxed."""
    dctx = ctx.dispatch_ctx()
    if (dctx.differentiable and spec.op == "spmm"
            and spec.kind in ("static", "dynamic")):
        return dataclasses.replace(dctx, differentiable=False)
    return dctx


def _fingerprint(spec: OpSpec, ctx: PlanContext, operand=None) -> tuple:
    dctx = ctx.dispatch_ctx()
    # skew rides in the base key (dispatch.pattern_balance, bucketed):
    # a skewed pattern's verdict -- the balanced route winning -- must
    # not answer for a uniform pattern of the same shape/density
    base = dispatch._cache_key(spec.kind, spec.m, spec.k, spec.n,
                               spec.block_size, spec.density, spec.dtype,
                               dctx,
                               skew=dispatch.pattern_balance(operand))
    q = ctx.resolved_tp_q()
    # a TP verdict is a property of the mesh it was raced on: axis names
    # + sizes are part of the key (a verdict measured on a 1x8 mesh must
    # not answer for 2x4, nor for a tp_q-only plan without a mesh)
    tp = (("tp", q, ctx.tp_axis, ctx.tp_balanced)
          + ctx.mesh_fingerprint()) if q else ()
    # capacity *sizing* is part of the plan identity for dynamic
    # problems: a plan built at headroom 1.25 must not answer for
    # headroom 2.0.  The runtime-only knobs (overflow_threshold,
    # telemetry) deliberately stay OUT of this fingerprint -- they do
    # not change the route or the bucket, and splitting the disk key on
    # them would re-measure on restart whenever an operator toggles
    # them; they key the in-memory plan cache instead (see plan()).
    cap = (("cap", ctx.resolved_headroom(), ctx.capacity_policy)
           if spec.kind == "dynamic" else ())
    # the backward verdicts ride in the same record, so the backward
    # policy knobs are part of the plan identity: a plan whose dL/dx was
    # forced onto dynamic_xla must not answer for a grad_mode="auto" one
    grad = (("grad", ctx.grad_mode, ctx.sddmm_mode)
            if _grad_covered(spec, ctx) else ())
    return ("plan", spec.op, spec.mode) + base + tp + cap + grad


def _mem_key(fp: tuple, pkey, ctx: PlanContext) -> tuple:
    """In-memory plan-cache identity: fingerprint + concrete pattern +
    persistence policy + the runtime-only knobs that change plan
    *behavior* without changing the route or the disk verdict
    (overflow guardrail, telemetry, evolution drift threshold)."""
    persist_key = (ctx.resolved_cache_dir() if ctx.persistence_on()
                   else None)
    return (fp, pkey, persist_key, ctx.overflow_threshold,
            ctx.telemetry, ctx.evolve_drift)


def _tp_estimate(spec: OpSpec, q: int,
                 route: str = "static_tp") -> float:
    """Analytic prior for the TP routes (paper Fig 1a at mesh scale):
    nnz-balanced local SpMM (1/q of the static work) + the single output
    reduction over the TP axis.  This is only the *seed* of the race --
    with a mesh and ``measure=True`` both lowerings are wall-clocked on
    the real devices and the measured verdict wins (see ``_decide``)."""
    t_local = dispatch._estimate("static_xla", spec.m, spec.k, spec.n,
                                 spec.block_size, spec.density,
                                 spec.dtype) / max(1, q)
    bytes_el = max(1, jnp.dtype(spec.dtype).itemsize)
    t_reduce = (spec.m * spec.n * bytes_el) * max(0, q - 1) / max(1, q) \
        / planner_lib.ICI_BW
    # the gspmd lowering leaves the collective schedule to the compiler;
    # the explicit shard_map path pins it down -- mirror the small
    # xla-vs-pallas prior of dispatch._estimate so ties break toward the
    # pinned schedule when both are admissible and nothing was measured
    penalty = 1.05 if route == "static_tp" else 1.0
    return (t_local + t_reduce) * penalty


def _tp_candidates(spec: OpSpec, ctx: PlanContext,
                   q: Optional[int]) -> Tuple[str, ...]:
    """Admissible TP routes for this plan.  gspmd executes anywhere
    (the psum lowers to a local sum without a mesh); shard_map needs a
    concrete mesh whose tp_axis size equals q."""
    if spec.kind != "static" or not q or q < 2:
        return ()
    routes = ["static_tp"]
    if ctx.shardmap_executable():
        routes.append("static_tp_shardmap")
    return tuple(routes)


# one TP race + executor build calls _tp_closure up to three times for
# the same pattern; the host-side shard planning (argsort + scatter over
# all nnz blocks) is memoized per (pattern, q, balanced) so it runs once
_shard_meta_cache: Dict[tuple, partitioner.KShardPlan] = {}


def _shard_meta_for(operand, q: int,
                    balanced: bool) -> partitioner.KShardPlan:
    pk = pattern_key(operand)
    if pk is None:                       # no stable pattern identity
        return partitioner.plan_k_shards(operand, q, balanced=balanced)
    key = (pk, operand.shape, operand.block_size, q, balanced)
    with _plan_lock:
        meta = _shard_meta_cache.get(key)
    if meta is None:
        meta = partitioner.plan_k_shards(operand, q, balanced=balanced)
        with _plan_lock:
            meta = _shard_meta_cache.setdefault(key, meta)
    return meta


def _tp_closure(route: str, spec: OpSpec, ctx: PlanContext,
                operand: "BlockSparseMatrix"):
    """(execute_closure, artifacts) for one TP route -- shared by the
    executor builder and the measured race, so autotune wall-clocks
    exactly what the plan will run."""
    q = ctx.resolved_tp_q()
    shard_meta = _shard_meta_for(operand, q, ctx.tp_balanced)
    bal = partitioner.balance_report(shard_meta.real_counts)
    art = dict(tp_q=q, tp_axis=ctx.tp_axis, tp_route=route,
               tp_balanced=ctx.tp_balanced,
               tp_imbalance=bal["imbalance"], tp_slots=shard_meta.slots)
    axis = ctx.tp_axis
    if route == "static_tp_shardmap":
        mesh = ctx.mesh
        return (lambda v, x: tp_lib.tp_spmm_shard_map(
            partitioner.apply_k_shards(shard_meta, v), x, mesh=mesh,
            axis=axis)), art
    return (lambda v, x: tp_lib.tp_spmm_gspmd(
        partitioner.apply_k_shards(shard_meta, v), x, axis=axis)), art


def _measure_tp_route(route: str, spec: OpSpec, ctx: PlanContext,
                      operand, x) -> float:
    """Wall-clock one TP lowering on the real (or host-platform)
    devices.  The gspmd trace gets the mesh installed as the activation
    mesh so its sharding constraints are live -- the measurement covers
    the collective, not just the local math."""
    from repro.sharding import rules
    fn, _ = _tp_closure(route, spec, ctx, operand)
    if ctx.mesh is not None and route == "static_tp":
        with rules.activation_mesh(ctx.mesh):
            return dispatch.measure_callable(
                fn, jnp.asarray(operand.values), x)
    return dispatch.measure_callable(fn, jnp.asarray(operand.values), x)


def _decide(spec: OpSpec, ctx: PlanContext, operand: Optional[Operand],
            x) -> Tuple[str, Dict[str, float], str, bool, Optional[dict],
                        Optional[str], Optional[dict]]:
    """-> (route, est_seconds, source, from_disk, disk_capacity,
    tp_source, disk_grad).  ``tp_source`` labels the TP candidates'
    entries in ``est_seconds`` separately from the overall verdict: the
    unsharded side can be measured while the TP side stayed analytic
    (abstract inputs + a decision-cache replay), and the report must
    never call that ratio 'measured'.  ``disk_grad`` is the persisted
    backward-verdict section (dL/dx + dL/dvalues routes), replayed so a
    restart re-plans fwd+bwd with zero measurements.  The verdict is
    persisted by ``plan()`` (one store, after the executor -- and its
    capacity and grad sections -- are built)."""
    dctx = _selection_ctx(spec, ctx)
    key = cache_lib.key_string(_fingerprint(spec, ctx, operand))
    # background re-planner overlay first: an in-process upgraded
    # verdict wins over both the disk record (which store_decision has
    # already overwritten when persistence is on) and a fresh race
    rec = _replanned.get(key)
    if rec is not None and rec.get("route") in PLAN_ROUTES:
        return (rec["route"], dict(rec.get("est_seconds", {})),
                rec.get("source", "measured"), True,
                rec.get("capacity"),
                rec.get("tp_source", rec.get("source")),
                rec.get("grad"))
    use_disk = ctx.cache and ctx.persistence_on()
    if use_disk:
        rec = cache_lib.load_decision(ctx.resolved_cache_dir(), key)
        if rec is not None and rec.get("route") in PLAN_ROUTES:
            return (rec["route"], dict(rec.get("est_seconds", {})),
                    rec.get("source", "analytic"), True,
                    rec.get("capacity"),
                    rec.get("tp_source", rec.get("source")),
                    rec.get("grad"))

    cache_lib.bump("decisions")
    q = ctx.resolved_tp_q()
    forced_tp = spec.mode in TP_ROUTES
    tp_measurable = (operand is not None and x is not None
                     and dispatch._is_concrete(
                         x, *jax.tree_util.tree_leaves(operand)))
    if forced_tp:
        if spec.kind != "static":
            raise ValueError(f"mode {spec.mode!r} cannot execute a "
                             f"{spec.kind} operand")
        if not q:
            raise ValueError(f"mode {spec.mode!r} needs ctx.mesh (with "
                             "ctx.tp_axis) or an explicit ctx.tp_q")
        if spec.mode == "static_tp_shardmap":
            if not ctx.shardmap_executable():
                raise ValueError(
                    "mode 'static_tp_shardmap' needs a concrete "
                    f"ctx.mesh with axis {ctx.tp_axis!r} of size q={q} "
                    "(an AbstractMesh or bare tp_q can only execute "
                    "the 'static_tp' gspmd lowering)")
            cands = ("static_tp_shardmap",)
        else:
            # "static_tp" as a mode = the TP family: race both lowerings
            cands = _tp_candidates(spec, ctx, q) or ("static_tp",)
        est = {r: _tp_estimate(spec, q, r) for r in cands}
        source = "forced"
        if ctx.measure and len(cands) > 1 and tp_measurable:
            measured = {r: _measure_tp_route(r, spec, ctx, operand, x)
                        for r in cands}
            est.update(measured)
            cache_lib.bump("measurements")
            source = "measured"
        route = min(est, key=est.get)
        return route, est, source, False, None, source, None

    if operand is not None:
        dkey = dispatch._cache_key(spec.kind, spec.m, spec.k, spec.n,
                                   spec.block_size, spec.density,
                                   spec.dtype, dctx,
                                   skew=dispatch.pattern_balance(operand))
        already = dkey in dispatch._decision_cache
        dec = dispatch.decide(operand, spec.n, ctx=dctx, x=x)
        if dec.source == "measured" and not already:
            cache_lib.bump("measurements")
        route, est, source = dec.route, dict(dec.est_seconds), dec.source
    else:
        # OpSpec-only: analytic pricing straight off the cost model
        cands = dispatch._candidates(spec.kind, dctx)
        est = {r: dispatch._estimate(r, spec.m, spec.k, spec.n,
                                     spec.block_size, spec.density,
                                     spec.dtype) for r in cands}
        route = min(est, key=est.get)
        source = "forced" if len(cands) == 1 else "analytic"

    # mesh-aware TP candidates (auto mode, static pattern, mesh/tp_q
    # present): the measured-autotune race -- gspmd vs shard_map vs the
    # unsharded candidates -- or the analytic prior when not measuring
    tp_routes = (_tp_candidates(spec, ctx, q)
                 if spec.mode == "auto" and ctx.mesh is not None else ())
    tp_source = None
    if tp_routes:
        for r in tp_routes:
            est[r] = _tp_estimate(spec, q, r)
        tp_source = "analytic"
        if ctx.measure and tp_measurable:
            if source != "measured":
                # the unsharded side came back analytic (a decision-
                # cache replay from a traced first call): re-race it
                # cache-bypassed so both sides of the min() are wall
                # clocks -- analytic model seconds and host timings are
                # not comparable units
                dec2 = dispatch.decide(
                    operand, spec.n,
                    ctx=dataclasses.replace(dctx, cache=False), x=x)
                if dec2.source == "measured":
                    est.update(dec2.est_seconds)
                    route, source = dec2.route, dec2.source
                    cache_lib.bump("measurements")
            if source == "measured":
                measured_tp = {r: _measure_tp_route(r, spec, ctx,
                                                    operand, x)
                               for r in tp_routes}
                est.update(measured_tp)
                tp_source = "measured"
                cache_lib.bump("measurements")
                # compare measured against measured: the unsharded
                # race wall-clocked every runnable candidate
                runnable = {r: est[r] for r in est
                            if r in measured_tp
                            or dispatch._executable(r, dctx)}
                route = min(runnable, key=runnable.get)
        if (source != "measured"
                and est[min(tp_routes, key=est.get)] < est[route]):
            # analytic-vs-analytic only: never let a modeled TP number
            # overturn (or lose to) numbers of a different unit
            route = min(tp_routes, key=est.get)

    return route, est, source, False, None, tp_source, None


def _tp_decision(ctx: PlanContext, route: str, est: Dict[str, float],
                 source: str,
                 tp_source: Optional[str]) -> Optional[dict]:
    """The TP section of the plan report: what the race saw and where
    the crossover sits.  ``tp_speedup_vs_unsharded`` is best-unsharded
    time / best-TP time -- > 1 means TP is past the crossover for this
    problem on this mesh -- reported only when both sides carry the
    same units (both measured or both analytic); a mixed verdict (the
    unsharded side measured, the TP side stuck on its analytic prior
    because inputs were abstract) reports None rather than a
    model-seconds-vs-wall-clock ratio."""
    tp_est = {r: est[r] for r in TP_ROUTES if r in est}
    if not tp_est:
        return None
    q = ctx.resolved_tp_q()
    best_tp = min(tp_est, key=tp_est.get)
    unsh = {r: s for r, s in est.items() if r not in TP_ROUTES}
    best_un = min(unsh, key=unsh.get) if unsh else None
    tp_source = tp_source or source
    comparable = best_un is None or tp_source == source
    speedup = (est[best_un] / est[best_tp]
               if best_un is not None and comparable else None)
    mesh_fp = ctx.mesh_fingerprint()
    return {
        "q": q, "axis": ctx.tp_axis, "balanced": ctx.tp_balanced,
        "mesh": ({n: s for n, s in zip(*mesh_fp)} if mesh_fp else None),
        "candidates": {r: tp_est[r] for r in
                       sorted(tp_est, key=tp_est.get)},
        "chosen": route if route in TP_ROUTES else None,
        "best_tp_route": best_tp,
        "best_unsharded_route": best_un,
        "source": tp_source,
        "tp_speedup_vs_unsharded": (round(speedup, 4)
                                    if speedup is not None else None),
        "tp_wins": bool(speedup is not None and speedup > 1.0),
    }


# ---------------------------------------------------------------------------
# Execute-closure builders (one per (kind, route) arm; each closure is
# decision-free -- all metadata is a host constant baked at plan time)
# ---------------------------------------------------------------------------

def _promote_matmul(w, x, *, pallas: bool, interpret: bool):
    rt = jnp.result_type(w.dtype, x.dtype)
    if pallas:
        from repro.kernels.dense_mm import ops as dmm_ops
        return dmm_ops.dense_mm(w.astype(rt), x.astype(rt),
                                interpret=interpret)
    return jnp.matmul(w.astype(rt), x.astype(rt))


def _static_executor(spec: OpSpec, route: str, ctx: PlanContext,
                     operand: BlockSparseMatrix):
    m, k, b = spec.m, spec.k, spec.block_size
    mb, kb = m // b, k // b
    rows = np.asarray(operand.row_idx, np.int32)
    cols = np.asarray(operand.col_idx, np.int32)
    interpret = ctx.interpret
    # the baked pattern rides along (underscore = working state, not
    # report material): evolve() needs it to build the carry map and
    # the drift reference without re-deriving it from the caller
    art: Dict[str, Any] = {"nnz_blocks": len(rows),
                           "_pattern": (rows, cols)}

    if route == "static_xla":
        fn = _ssp.make_spmm(rows, cols, (mb, kb), b)
        return (lambda v, x: fn(jnp.asarray(v), x)), art

    if route == "static_pallas":
        from repro.kernels.bsmm import ops as bsmm_ops
        tm, tk = bsmm_ops.tile_shape(m, k, b)
        meta = partitioner.plan_packing(rows, cols, (m, k), b, tm, tk)
        art.update(packing_tiles=meta.num_tiles,
                   packing_occupancy=meta.occupancy)
        # tn is picked at trace time from the actual x (calling the plan
        # with a different n than planned must not mis-tile the kernel)
        return (lambda v, x: bsmm_ops.bsmm_from_plan(
            meta, v, x, interpret=interpret)), art

    if route == "static_balanced":
        from repro.kernels.bsmm import ops as bsmm_ops
        tm, tk = bsmm_ops.tile_shape(m, k, b)
        meta = partitioner.plan_packing_balanced(rows, cols, (m, k), b,
                                                 tm, tk)
        bal = partitioner.balance_report(meta.swizzle.loads)
        art.update(packing_tiles=meta.base.num_tiles,
                   packing_occupancy=meta.base.occupancy,
                   swizzle_bins=meta.num_bins,
                   swizzle_steps_per_bin=meta.steps_per_bin,
                   swizzle_imbalance=bal["imbalance"],
                   swizzle_cv=bal["cv"])
        return (lambda v, x: bsmm_ops.bsmm_balanced_from_plan(
            meta, v, x, interpret=interpret)), art

    if route in ("dense_xla", "dense_pallas"):
        rows_j, cols_j = jnp.asarray(rows), jnp.asarray(cols)
        pallas = route == "dense_pallas"

        def run(v, x):
            v = jnp.asarray(v)
            w = jnp.zeros((mb, kb, b, b), v.dtype).at[rows_j, cols_j].add(v)
            w = w.transpose(0, 2, 1, 3).reshape(m, k)
            return _promote_matmul(w, x, pallas=pallas, interpret=interpret)
        return run, art

    if route in ("dynamic_xla", "dynamic_pallas", "dynamic_grouped",
                 "dynamic_grouped_balanced"):
        rows_d = jnp.asarray(rows, jnp.int32)
        cols_d = jnp.asarray(cols, jnp.int32)
        nnz = jnp.asarray(len(rows), jnp.int32)
        if route == "dynamic_xla":
            return (lambda v, x: _dspmm(jnp.asarray(v), rows_d, cols_d, x,
                                        mb, b)), art

        def as_dyn(v):
            return DynamicOperand(jnp.asarray(v), rows_d, cols_d, nnz,
                                  (m, k), b)
        if route in ("dynamic_grouped", "dynamic_grouped_balanced"):
            from repro.kernels.gmm import ops as gmm_ops
            t = gmm_ops.grouped_tile_size(m, k, b)
            # static pattern -> the exact tile count is known at plan time
            meta = partitioner.plan_packing(rows, cols, (m, k), b, t, t)
            cap = meta.num_tiles
            art.update(grouped_tile=t, grouped_tiles_cap=cap)
            if route == "dynamic_grouped_balanced":
                from repro.kernels.gmm import balanced as gmm_balanced
                return (lambda v, x: gmm_balanced.balanced_spmm(
                    as_dyn(v), x, tile=t, tiles_cap=cap,
                    interpret=interpret)), art
            return (lambda v, x: gmm_ops.grouped_spmm(
                as_dyn(v), x, tile=t, tiles_cap=cap,
                interpret=interpret)), art
        from repro.kernels.dsmm import ops as dsmm_ops
        return (lambda v, x: dsmm_ops.dsmm(as_dyn(v), x,
                                           interpret=interpret)), art

    if route in TP_ROUTES:
        fn, tp_art = _tp_closure(route, spec, ctx, operand)
        art.update(tp_art)
        return fn, art

    raise ValueError(f"unknown static route {route!r}")


def _record_pack_stats(stats: CapacityStats, st) -> None:
    """Fold one pack's exact overflow accounting into the running stats.
    Concrete values record directly (eager calls); traced values go
    through ``jax.debug.callback`` so jitted programs (the serving
    engine's decode loop) still report."""
    leaves = (st.tiles_total, st.tiles_dropped, st.blocks_dropped,
              st.dropped_value_frac)
    if any(isinstance(v, jax.core.Tracer) for v in leaves):
        jax.debug.callback(stats.record, *leaves)
    else:
        stats.record(*leaves)


def _dynamic_executor(spec: OpSpec, route: str, ctx: PlanContext,
                      key: str, disk_capacity: Optional[dict] = None):
    m, k, b = spec.m, spec.k, spec.block_size
    mb = m // b
    interpret = ctx.interpret
    dplan = planner_lib.plan_dynamic(m, k, spec.n, d_max=spec.density,
                                     block_size=b, units=ctx.units)
    art: Dict[str, Any] = dict(bucket_blocks=dplan.bucket_blocks,
                               nnz_max_blocks=dplan.nnz_max_blocks,
                               q_m=dplan.q_m, q_k=dplan.q_k, q_n=dplan.q_n)

    if route == "dynamic_xla":
        return (lambda op, x: _dspmm(op.values, op.row_idx, op.col_idx,
                                     x, mb, b)), art
    if route == "dynamic_pallas":
        from repro.kernels.dsmm import ops as dsmm_ops
        return (lambda op, x: dsmm_ops.dsmm(op, x,
                                            interpret=interpret)), art
    if route in ("dynamic_grouped", "dynamic_grouped_balanced"):
        from repro.kernels.gmm import ops as gmm_ops
        if route == "dynamic_grouped_balanced":
            from repro.kernels.gmm.balanced import balanced_spmm as _gspmm
        else:
            _gspmm = gmm_ops.grouped_spmm
        t = gmm_ops.grouped_tile_size(m, k, b)
        # planned capacity (paper §3.3 bucket sizing): expected distinct
        # tiles at d_max, times the headroom knob -- NOT the safe worst
        # case.  Overflow is possible by design and counted exactly.
        slots = planner_lib.nnz_max_blocks(m, k, b, spec.density)
        capplan = planner_lib.plan_grouped_capacity(
            m, k, b, spec.density, tile=t, slots=slots,
            headroom=ctx.resolved_headroom())
        stats = _capacity_stats_for(
            key, tiles_cap=capplan.tiles_cap,
            worst_tiles=capplan.worst_tiles,
            overflow_threshold=ctx.overflow_threshold)
        stats.overflow_threshold = ctx.overflow_threshold
        # a persisted escalation (disk record at policy "worst") carries
        # across restarts: the guardrail's verdict is part of the plan,
        # not just process state
        if disk_capacity is not None and \
                disk_capacity.get("policy") == "worst":
            stats.escalated = True
        # guardrail: an escalated problem (observed overflow frequency
        # above ctx.overflow_threshold) re-plans at worst-case capacity
        policy = ("worst" if (ctx.capacity_policy == "worst"
                              or stats.escalated) else "planned")
        requested = (capplan.tiles_cap if policy == "planned"
                     else capplan.worst_tiles)
        cap, clamped = gmm_ops.clamped_tiles_cap(requested, m, k, t,
                                                 warn=False)
        stats.tiles_cap = cap
        stats.worst_tiles = capplan.worst_tiles
        stats.clamped = stats.clamped or clamped
        telemetry = ctx.telemetry
        art.update(grouped_tile=t, grouped_tiles_cap=cap,
                   capacity=dict(capplan.as_dict(), policy=policy,
                                 tiles_cap=cap, clamped=clamped,
                                 escalated=stats.escalated),
                   _capacity_stats=stats)

        def run(op, x):
            if not telemetry:        # skip the accounting reductions
                return _gspmm(op, x, tile=t, tiles_cap=cap,
                              interpret=interpret)
            y, st = _gspmm(op, x, tile=t, tiles_cap=cap,
                           interpret=interpret, return_stats=True)
            _record_pack_stats(stats, st)
            return y
        return run, art
    if route in ("dense_xla", "dense_pallas"):
        pallas = route == "dense_pallas"
        return (lambda op, x: _promote_matmul(op.to_dense(), x,
                                              pallas=pallas,
                                              interpret=interpret)), art
    raise ValueError(f"unknown dynamic route {route!r}")


def _dense_executor(spec: OpSpec, route: str, ctx: PlanContext):
    interpret = ctx.interpret
    art: Dict[str, Any] = {}
    if spec.op == "matmul":
        pallas = route == "dense_pallas"
        # activation-major: x2 @ w (operand order swapped vs spmm form)
        return (lambda w, x2: _promote_matmul(x2, w, pallas=pallas,
                                              interpret=interpret)), art
    if spec.op == "batched_matmul":
        pallas = route == "dense_pallas"

        def run(a, bb):
            rt = jnp.result_type(a.dtype, bb.dtype)
            if pallas:
                from repro.kernels.dense_mm import ops as dmm_ops
                def f(x_, y_):
                    return dmm_ops.dense_mm(x_, y_, interpret=interpret)
                for _ in range(a.ndim - 2):
                    f = jax.vmap(f)
                return f(a.astype(rt), bb.astype(rt))
            return jnp.matmul(a.astype(rt), bb.astype(rt))
        return run, art
    pallas = route == "dense_pallas"
    return (lambda w, x: _promote_matmul(jnp.asarray(w), x, pallas=pallas,
                                         interpret=interpret)), art


# ---------------------------------------------------------------------------
# Planned backward (the differentiable-plans tentpole): every executable
# spmm plan carries a plan-level jax.custom_vjp whose backward runs two
# sibling products chosen by the same decide/measure/persist machinery
# as the forward --
#
#   dL/dx       an SpMM on the *transposed* pattern (partitioner
#               metadata transposed once per pattern, cached), raced
#               over the dispatch route vocabulary;
#   dL/dvalues  a block SDDMM (static_sparse.make_sddmm, the
#               kernels/sddmm grouped tile kernel, or the dense
#               product), raced over dispatch.SDDMM_ROUTES.
#
# Verdicts join the persistent decision record under a "grad" section,
# so a training restart re-plans fwd+bwd with zero measurements.
# ---------------------------------------------------------------------------

_transpose_cache: Dict[tuple, partitioner.TransposePlan] = {}
_sddmm_meta_cache: Dict[tuple, partitioner.PackingPlan] = {}


def _transpose_plan_for(operand: BlockSparseMatrix) -> partitioner.TransposePlan:
    pk = pattern_key(operand)
    key = (pk, operand.shape, operand.block_size)
    with _plan_lock:
        tp = _transpose_cache.get(key)
    if tp is None:
        tp = partitioner.plan_transpose(operand.row_idx, operand.col_idx,
                                        operand.shape, operand.block_size)
        with _plan_lock:
            tp = _transpose_cache.setdefault(key, tp)
    return tp


def _sddmm_meta_for(operand: BlockSparseMatrix,
                    t: int) -> partitioner.PackingPlan:
    pk = pattern_key(operand)
    key = (pk, operand.shape, operand.block_size, t)
    with _plan_lock:
        meta = _sddmm_meta_cache.get(key)
    if meta is None:
        meta = partitioner.plan_packing(
            np.asarray(operand.row_idx), np.asarray(operand.col_idx),
            operand.shape, operand.block_size, t, t)
        with _plan_lock:
            meta = _sddmm_meta_cache.setdefault(key, meta)
    return meta


def _dx_closure(route: str, spec: OpSpec, ctx: PlanContext,
                operand: BlockSparseMatrix):
    """(values, dy) -> dL/dx for one candidate route: the forward
    executor vocabulary applied to the transposed pattern (value phase:
    permute + per-block transpose, a device gather per call)."""
    tplan = _transpose_plan_for(operand)
    spec_t = OpSpec(kind="static", m=spec.k, k=spec.m, n=spec.n,
                    block_size=spec.block_size, density=spec.density,
                    dtype=spec.dtype, op="spmm", mode="auto")
    # the executor arms close over the pattern metadata only and take
    # values per call, so any same-shape array works as the placeholder
    # -- the live values are re-permuted in run() below
    bsr_t = BlockSparseMatrix(operand.values, tplan.row_idx,
                              tplan.col_idx, tplan.shape,
                              tplan.block_size)
    inner, _ = _static_executor(spec_t, route, ctx, bsr_t)
    perm = jnp.asarray(tplan.perm)

    def run(v, dy):
        v_t = jnp.asarray(v)[perm].transpose(0, 2, 1)
        return inner(v_t, dy)
    return run


def _dv_closure(route: str, spec: OpSpec, ctx: PlanContext,
                operand: BlockSparseMatrix):
    """(dy, x) -> dL/dvalues ([nnz, b, b]) for one SDDMM route."""
    m, k, b = spec.m, spec.k, spec.block_size
    mb, kb = m // b, k // b
    rows = np.asarray(operand.row_idx, np.int32)
    cols = np.asarray(operand.col_idx, np.int32)
    if route == "sddmm_xla":
        return _ssp.make_sddmm(rows, cols, (mb, kb), b)
    if route == "sddmm_dense":
        rows_j, cols_j = jnp.asarray(rows), jnp.asarray(cols)

        def run(dy, x):
            rt = jnp.result_type(dy.dtype, x.dtype)
            dw = jnp.matmul(dy.astype(rt), x.astype(rt).T)
            blocked = dw.reshape(mb, b, kb, b).transpose(0, 2, 1, 3)
            return blocked[rows_j, cols_j]
        return run
    if route == "sddmm_grouped":
        from repro.kernels.sddmm import ops as sddmm_ops
        t = sddmm_ops.sddmm_tile_size(m, k, b)
        meta = _sddmm_meta_for(operand, t)
        interpret = ctx.interpret
        return lambda dy, x: sddmm_ops.grouped_sddmm(meta, dy, x,
                                                     interpret=interpret)
    raise ValueError(f"unknown sddmm route {route!r}")


def _grad_verdict(est, forced, *, measure_fns=None) -> dict:
    """One backward product's verdict: analytic ranking, optionally
    overturned by wall-clock measurement of the runnable candidates.
    A measured verdict publishes ONLY the wall-clocked entries --
    analytic model seconds and host timings are not comparable units,
    and a mixed dict labeled 'measured' would report bogus crossovers
    (the same rule PR 4's ``tp_source`` enforces for the TP race)."""
    source = "forced" if forced else "analytic"
    pick_from = est
    if measure_fns:
        pick_from = est = {r: dispatch.measure_callable(fn, *args)
                           for r, (fn, args) in measure_fns.items()}
        source = "measured"
    return {"route": min(pick_from, key=pick_from.get),
            "source": source,
            "est_seconds": {r: float(s) for r, s in est.items()}}


def _grad_decide(spec: OpSpec, ctx: PlanContext,
                 operand: BlockSparseMatrix, x,
                 disk_grad: Optional[dict]) -> dict:
    """Backward route verdicts (dx = transposed SpMM, dvalues = SDDMM):
    disk replay when the forward record carried them, else the analytic
    race, wall-clocked when ``ctx.measure`` and the inputs are concrete
    (the dy probe is shape data only -- zeros of the output shape)."""
    if disk_grad is not None and \
            disk_grad.get("dx", {}).get("route") in dispatch.ROUTES and \
            disk_grad.get("dvalues", {}).get("route") in dispatch.SDDMM_ROUTES:
        return dict(disk_grad, from_disk=True)
    bwd_ctx = dataclasses.replace(_selection_ctx(spec, ctx),
                                  differentiable=False, mode="auto")
    m, k, n, b = spec.m, spec.k, spec.n, spec.block_size
    d, dt = spec.density, spec.dtype
    dx_forced = ctx.grad_mode != "auto"
    dx_cands = ((ctx.grad_mode,) if dx_forced
                else dispatch._candidates("static", bwd_ctx))
    dv_forced = ctx.sddmm_mode != "auto"
    dv_cands = ((ctx.sddmm_mode,) if dv_forced
                else dispatch.sddmm_candidates(bwd_ctx))
    # dx is the transposed problem: [k, m] @ [m, n]
    dx_est = {r: dispatch._estimate(r, k, m, n, b, d, dt)
              for r in dx_cands}
    dv_est = {r: dispatch._estimate(r, m, k, n, b, d, dt)
              for r in dv_cands}
    dx_meas = dv_meas = None
    cache_lib.bump("decisions")
    if ctx.measure and x is not None and dispatch._is_concrete(
            x, *jax.tree_util.tree_leaves(operand)):
        dy = jnp.zeros((m, n), jnp.result_type(
            jnp.dtype(dt), jnp.asarray(x).dtype))
        v = jnp.asarray(operand.values)
        dx_run = [r for r in dx_cands if dispatch._executable(r, bwd_ctx)]
        dv_run = [r for r in dv_cands if dispatch._executable(r, bwd_ctx)]
        if dx_run:
            dx_meas = {r: (_dx_closure(r, spec, ctx, operand), (v, dy))
                       for r in dx_run}
        if dv_run:
            dv_meas = {r: (_dv_closure(r, spec, ctx, operand),
                           (dy, jnp.asarray(x)))
                       for r in dv_run}
        if dx_meas or dv_meas:
            cache_lib.bump("measurements")
    return {"dx": _grad_verdict(dx_est, dx_forced, measure_fns=dx_meas),
            "dvalues": _grad_verdict(dv_est, dv_forced,
                                     measure_fns=dv_meas),
            "from_disk": False}


def _planned_vjp(execute, dx_fn, dv_fn):
    """The plan-level custom_vjp for static plans: forward runs the
    planned route (Pallas included); backward runs the two sibling
    plans.  Built once at plan time, so the wrapped callable is a
    stable jit/vmap-safe identity."""
    @jax.custom_vjp
    def run(v, x):
        return execute(v, x)

    def fwd(v, x):
        return run(v, x), (v, x)

    def bwd(res, dy):
        v, x = res
        dv = dv_fn(dy, x)
        dx = dx_fn(v, dy)
        return (dv.astype(jnp.asarray(v).dtype), dx.astype(x.dtype))

    run.defvjp(fwd, bwd)
    return run


def _dynamic_planned_vjp(execute, spec: OpSpec):
    """Plan-level custom_vjp for dynamic-kind plans (runtime pattern):
    backward uses the runtime-index transposed-gather/scatter pair --
    the same products ``_dspmm``'s own vjp runs -- so the Pallas
    dynamic forwards (dsmm slot walk, grouped tile pack) become
    trainable.  Integer index/count leaves get no cotangent."""
    m, k, b = spec.m, spec.k, spec.block_size
    mb, kb = m // b, k // b

    @jax.custom_vjp
    def run(values, row_idx, col_idx, nnz, x):
        op = DynamicOperand(values, row_idx, col_idx, nnz, (m, k), b)
        return execute(op, x)

    def fwd(values, row_idx, col_idx, nnz, x):
        return run(values, row_idx, col_idx, nnz, x), \
            (values, row_idx, col_idx, x)

    def bwd(res, dy):
        values, row_idx, col_idx, x = res
        n = x.shape[-1]
        dyb = dy.reshape(mb, b, n)
        xb = x.reshape(kb, b, n)
        dyg = jnp.take(dyb, row_idx, axis=0)
        xg = jnp.take(xb, col_idx, axis=0)
        dvalues = jnp.einsum("zan,zbn->zab", dyg, xg).astype(values.dtype)
        partial = jnp.einsum("zab,zan->zbn", values, dyg)
        dx = jax.ops.segment_sum(partial, col_idx, num_segments=kb)
        return (dvalues, None, None, None,
                dx.reshape(kb * b, n).astype(x.dtype))

    run.defvjp(fwd, bwd)
    return lambda op, x: run(op.values, op.row_idx, op.col_idx, op.nnz, x)


def _dense_planned_vjp(execute, op: str):
    """custom_vjp for the dense_pallas forward kernel (no native VJP):
    backward is the two dense products via jnp.matmul."""
    @jax.custom_vjp
    def run(w, x):
        return execute(w, x)

    def fwd(w, x):
        return run(w, x), (w, x)

    if op == "matmul":     # execute(w, x2) = x2 @ w
        def bwd(res, dy):
            w, x2 = res
            return ((x2.T @ dy).astype(w.dtype),
                    (dy @ w.T).astype(x2.dtype))
    else:                  # spmm form: execute(w, x) = w @ x
        def bwd(res, dy):
            w, x = res
            return ((dy @ x.T).astype(w.dtype),
                    (w.T @ dy).astype(x.dtype))

    run.defvjp(fwd, bwd)
    return run


def _no_vjp_error(execute, route: str, workaround: str):
    """Forward-only plans (Pallas route, no planned backward): fail the
    backward *trace* with an actionable error instead of the opaque
    Pallas internal failure / silent wrong-gradient path."""
    @jax.custom_vjp
    def run(v, x):
        return execute(v, x)

    def fwd(v, x):
        return run(v, x), None

    def bwd(res, dy):
        raise ValueError(
            f"plan route {route!r} has no registered VJP (the Pallas "
            f"kernel is forward-only and this plan was built without a "
            f"planned backward); {workaround}")

    run.defvjp(fwd, bwd)
    return run


_PALLAS_FWD_ONLY = ("dense_pallas", "static_pallas", "static_balanced",
                    "dynamic_pallas", "dynamic_grouped",
                    "dynamic_grouped_balanced")


def _wrap_grad(spec: OpSpec, route: str, ctx: PlanContext,
               operand: Optional[Operand], x, execute,
               disk_grad: Optional[dict]):
    """-> (execute', grad_artifacts).  Attaches the plan-level backward
    (or the clear no-VJP error) to an executable plan's closure."""
    if route in TP_ROUTES:
        # gspmd / shard_map lowerings are jnp + psum: native autodiff
        # already runs sharded backward products
        return execute, ({"mode": "native"} if ctx.differentiable
                         else None)
    if spec.op == "spmm" and spec.kind == "static" \
            and isinstance(operand, BlockSparseMatrix):
        if _grad_covered(spec, ctx):
            grad = _grad_decide(spec, ctx, operand, x, disk_grad)
            dx_fn = _dx_closure(grad["dx"]["route"], spec, ctx, operand)
            dv_fn = _dv_closure(grad["dvalues"]["route"], spec, ctx,
                                operand)
            return (_planned_vjp(execute, dx_fn, dv_fn),
                    dict(grad, mode="planned"))
        if route in _PALLAS_FWD_ONLY:
            return _no_vjp_error(
                execute, route,
                "re-plan with PlanContext(differentiable=True) for the "
                "planned backward, or force an XLA route (e.g. "
                "mode='static_xla')"), {"mode": "unavailable"}
        return execute, None
    if spec.op == "spmm" and spec.kind == "dynamic":
        if ctx.differentiable:
            if route == "dynamic_xla":
                # _dspmm carries its own runtime-index custom_vjp
                return execute, {"mode": "native"}
            wrapped = _dynamic_planned_vjp(execute, spec)
            return wrapped, {
                "mode": "planned",
                "dx": {"route": "dynamic_xla", "source": "forced"},
                "dvalues": {"route": "sddmm_xla", "source": "forced"},
                "from_disk": False}
        if route in _PALLAS_FWD_ONLY:
            return _no_vjp_error(
                execute, route,
                "re-plan with PlanContext(differentiable=True) for the "
                "planned backward, or force an XLA route (e.g. "
                "mode='dynamic_xla')"), {"mode": "unavailable"}
        return execute, None
    # dense kind (spmm / matmul / batched_matmul ops)
    if route == "dense_pallas":
        if ctx.differentiable and spec.op in ("spmm", "matmul"):
            return (_dense_planned_vjp(execute, spec.op),
                    {"mode": "planned",
                     "dx": {"route": "dense_xla", "source": "forced"},
                     "dvalues": {"route": "dense_xla",
                                 "source": "forced"},
                     "from_disk": False})
        return _no_vjp_error(
            execute, route,
            "force the XLA route (mode='dense_xla') for differentiable "
            "callers"), {"mode": "unavailable"}
    return execute, ({"mode": "native"} if ctx.differentiable else None)


def _build_executor(spec: OpSpec, route: str, ctx: PlanContext,
                    operand: Optional[Operand], key: str,
                    disk_capacity: Optional[dict] = None):
    if spec.kind == "static":
        if operand is None or not isinstance(operand, BlockSparseMatrix):
            return None, {}          # spec-only static plan: report-only
        return _static_executor(spec, route, ctx, operand)
    if spec.kind == "dynamic":
        return _dynamic_executor(spec, route, ctx, key, disk_capacity)
    return _dense_executor(spec, route, ctx)


# ---------------------------------------------------------------------------
# Incremental plan mutation (MatmulPlan.evolve): dynamic sparse training
# with evolving static patterns.  A RigL topology step re-runs only the
# cheap host pattern phases (plan_packing / plan_transpose /
# plan_k_shards / grouped-capacity sizing -- all inside the executor
# builders) and inherits the parent's route + backward verdicts; the
# expensive decide/measure machinery re-runs only when the pattern
# profile drifts past ctx.evolve_drift (or rerace=True forces it).
# ---------------------------------------------------------------------------


def _as_static_bsr(new_pattern, spec: OpSpec) -> BlockSparseMatrix:
    """Normalize evolve()'s pattern argument to a static BSR with
    placeholder values (executor closures bake pattern metadata only;
    live values flow through the plan per call)."""
    b = spec.block_size
    mb, kb = spec.m // b, spec.k // b
    if isinstance(new_pattern, BlockSparseMatrix):
        if not new_pattern.is_static:
            raise ValueError(
                "evolve() needs a static (host-indexed) pattern; a "
                "runtime pattern is dynamic-kind data, not a plan "
                "mutation")
        if new_pattern.shape != (spec.m, spec.k) \
                or new_pattern.block_size != b:
            raise ValueError(
                f"evolved pattern shape {new_pattern.shape} block "
                f"{new_pattern.block_size} != plan's "
                f"({spec.m}, {spec.k}) block {b} -- evolve changes the "
                f"pattern, never the problem")
        return new_pattern.validate_pattern()
    if isinstance(new_pattern, tuple) and len(new_pattern) == 2:
        rows = np.asarray(new_pattern[0], np.int32)
        cols = np.asarray(new_pattern[1], np.int32)
        bsr = BlockSparseMatrix(jnp.zeros((len(rows), b, b), spec.dtype),
                                rows, cols, (spec.m, spec.k), b)
        return bsr.validate_pattern()
    mask = np.asarray(new_pattern, bool)
    if mask.shape != (mb, kb):
        raise ValueError(f"evolved block mask shape {mask.shape} != "
                         f"grid {(mb, kb)}")
    return BlockSparseMatrix.from_mask(mask, b, dtype=spec.dtype)


def _pattern_profile(rows: np.ndarray, cols: np.ndarray,
                     spec: OpSpec) -> Dict[str, float]:
    """The drift metric's inputs: block density + MXU-tile packing
    occupancy (the two pattern properties the dispatch cost model and
    the Pallas grid actually price)."""
    b = spec.block_size
    mb, kb = spec.m // b, spec.k // b
    t = b * max(1, 128 // b)
    meta = partitioner.plan_packing(rows, cols, (spec.m, spec.k), b,
                                    t, t)
    return {"density": len(rows) / max(1, mb * kb),
            "occupancy": meta.occupancy}


def _persist_lineage(ctx: PlanContext, p: "MatmulPlan", lineage: dict,
                     grad_art: Optional[dict] = None) -> None:
    """Write the evolved verdict + lineage at the evolved pattern's
    fingerprint, so a restart replays fwd+bwd for the evolved pattern
    with zero measurements and the lineage survives the process."""
    if not (ctx.cache and ctx.persistence_on()):
        return
    cdir = ctx.resolved_cache_dir()
    rec = cache_lib.load_decision(cdir, p.key)
    if rec is None:
        rec = {"route": p.route, "source": p.source,
               "est_seconds": {r: float(v)
                               for r, v in p.est_seconds.items()}}
        if grad_art and grad_art.get("mode") == "planned" \
                and "dx" in grad_art:
            rec["grad"] = {
                side: {k2: v for k2, v in grad_art[side].items()
                       if k2 in ("route", "source", "est_seconds")}
                for side in ("dx", "dvalues")}
    cache_lib.store_decision(cdir, p.key, dict(rec, evolution=lineage))


def _evolve_plan(parent: "MatmulPlan", new_bsr: BlockSparseMatrix,
                 rerace: Optional[bool], x) -> "MatmulPlan":
    ctx = parent.ctx
    old_rows, old_cols = parent.pattern
    new_rows = np.asarray(new_bsr.row_idx, np.int32)
    new_cols = np.asarray(new_bsr.col_idx, np.int32)
    new_spec = OpSpec.from_operand(new_bsr, parent.spec.n,
                                   mode=parent.spec.mode)
    b = new_spec.block_size
    grid = (new_spec.m // b, new_spec.k // b)
    eplan = partitioner.plan_evolution(old_rows, old_cols, new_rows,
                                       new_cols, grid)
    prof = _pattern_profile(new_rows, new_cols, new_spec)
    parent_ev = parent.artifacts.get("evolution")
    if parent_ev:
        # the drift reference is inherited through the evolve chain (it
        # is the profile the live verdicts were actually raced on) and
        # resets only on a re-race
        ref_d = parent_ev["ref_density"]
        ref_o = parent_ev["ref_occupancy"]
        gen = parent_ev["generation"] + 1
        root = parent_ev["root_key"]
    else:
        ref = _pattern_profile(np.asarray(old_rows),
                               np.asarray(old_cols), parent.spec)
        ref_d, ref_o = ref["density"], ref["occupancy"]
        gen, root = 1, parent.key
    thr = ctx.evolve_drift
    drift = max(abs(prof["density"] - ref_d) / max(ref_d, 1e-12),
                abs(prof["occupancy"] - ref_o) / max(ref_o, 1e-12))
    tripped = thr is not None and drift > thr
    do_rerace = tripped if rerace is None else bool(rerace)
    with _plan_lock:
        _evolution_totals["evolves"] += 1
        if tripped:
            _evolution_totals["drift_trips"] += 1
        if do_rerace:
            _evolution_totals["reraces"] += 1

    lineage = {
        "parent_key": parent.key, "root_key": root, "generation": gen,
        "drift": round(float(drift), 6), "drift_threshold": thr,
        "drift_tripped": bool(tripped), "reraced": bool(do_rerace),
        "carried": eplan.carried, "dropped": eplan.dropped,
        "grown": eplan.grown,
        "density": round(prof["density"], 6),
        "occupancy": round(prof["occupancy"], 6),
    }

    if do_rerace:
        # full plan(): decide (and measure, given ctx.measure + concrete
        # x) from scratch; the drift reference resets to this profile
        lineage.update(ref_density=round(prof["density"], 6),
                       ref_occupancy=round(prof["occupancy"], 6))
        p = plan(new_bsr, new_spec.n, x=x, ctx=ctx)
        p.artifacts["evolution"] = lineage
        p.artifacts["_evolve"] = eplan
        _persist_lineage(ctx, p, lineage, p.artifacts.get("grad"))
        return p

    lineage.update(ref_density=round(float(ref_d), 6),
                   ref_occupancy=round(float(ref_o), 6))
    # verdict-reuse path: rebuild the executor (the cheap host pattern
    # phases only) and replay the parent's route + backward verdicts --
    # zero decisions, zero measurements
    fp = _fingerprint(new_spec, ctx, new_bsr)
    key_str = cache_lib.key_string(fp)
    execute, artifacts = _static_executor(new_spec, parent.route, ctx,
                                          new_bsr)
    parent_grad = parent.artifacts.get("grad")
    inherited_grad = None
    if parent_grad and parent_grad.get("mode") == "planned" \
            and "dx" in parent_grad:
        inherited_grad = {"dx": dict(parent_grad["dx"]),
                          "dvalues": dict(parent_grad["dvalues"])}
    execute, grad_art = _wrap_grad(new_spec, parent.route, ctx, new_bsr,
                                   x, execute, inherited_grad)
    if grad_art is not None:
        if inherited_grad is not None \
                and grad_art.get("mode") == "planned":
            # _grad_decide's replay labels its input "from_disk"; these
            # verdicts were inherited from the parent plan in memory --
            # report the parent's disk provenance instead
            grad_art = dict(grad_art, evolved=True,
                            from_disk=parent_grad.get("from_disk",
                                                      False))
        artifacts["grad"] = grad_art
    if "tp" in parent.artifacts:
        artifacts["tp"] = parent.artifacts["tp"]
    artifacts["evolution"] = lineage
    artifacts["_evolve"] = eplan
    p = MatmulPlan(spec=new_spec, route=parent.route,
                   source=parent.source,
                   est_seconds=dict(parent.est_seconds),
                   from_disk=parent.from_disk, ctx=ctx, key=key_str,
                   artifacts=artifacts, _execute=execute,
                   capacity_stats=None)
    cache_lib.bump("plans_built")
    if ctx.cache:
        with _plan_lock:
            # overwrite, not setdefault: the evolved plan IS the
            # continuation for this pattern -- spmm()/SparseLinear calls
            # on the new pattern must hit it with zero decisions
            _plan_cache[_mem_key(fp, pattern_key(new_bsr), ctx)] = p
    _persist_lineage(ctx, p, lineage, grad_art)
    return p


def evolve(plan_: "MatmulPlan", new_pattern, *,
           rerace: Optional[bool] = None, x=None) -> "MatmulPlan":
    """Module-level spelling of ``plan.evolve(new_pattern)`` (see
    ``MatmulPlan.evolve``)."""
    return plan_.evolve(new_pattern, rerace=rerace, x=x)


def evolve_plans(old_pattern, new_pattern) -> int:
    """Evolve every cached executable static-spmm plan built on
    ``old_pattern`` onto ``new_pattern`` (any n / policy) -- the layer
    hook: after a RigL topology update the next forward on the new
    pattern is a plan-cache hit with zero decisions.  Both arguments
    are static ``BlockSparseMatrix`` (values ignored).  Returns the
    number of plans evolved."""
    pk_old = pattern_key(old_pattern)
    if pk_old is None:
        raise ValueError("evolve_plans() needs static patterns")
    with _plan_lock:
        matches = [p for mk, p in _plan_cache.items()
                   if mk[1] == pk_old]
    count = 0
    for p in matches:
        if (p.spec.kind == "static" and p.spec.op == "spmm"
                and p.executable):
            p.evolve(new_pattern)
            count += 1
    return count


# ---------------------------------------------------------------------------
# plan() + conveniences
# ---------------------------------------------------------------------------

_ctx_state = threading.local()


@contextlib.contextmanager
def use_ctx(ctx: PlanContext):
    """Install ``ctx`` as the ambient planning context (trace-scoped):
    every ``plan``/``matmul``/... call without an explicit ``ctx`` picks
    it up.  The serving engine wraps its traced programs with this so
    per-engine policy (persistent cache dir, Pallas admissibility) never
    leaks into process-global state."""
    prev = getattr(_ctx_state, "ctx", None)
    _ctx_state.ctx = ctx
    try:
        yield ctx
    finally:
        _ctx_state.ctx = prev


def _resolve_ctx(ctx) -> PlanContext:
    if ctx is None:
        ambient = getattr(_ctx_state, "ctx", None)
        if ambient is not None:
            return ambient
        return PlanContext.from_dispatch(dispatch.current_ctx())
    if isinstance(ctx, dispatch.DispatchContext):
        return PlanContext.from_dispatch(ctx)
    return ctx


def current_ctx() -> PlanContext:
    """The ambient plan context (``use_ctx``), else the dispatch view of
    the ambient dispatch context."""
    return _resolve_ctx(None)


def plan(operand_or_spec, n: Optional[int] = None, *, x=None,
         ctx: Optional[PlanContext] = None) -> MatmulPlan:
    """Phase 1 of the two-phase API: run all one-time work for
    ``operand @ [k, n]`` and return a frozen ``MatmulPlan``.

    ``operand_or_spec`` is a full operand (dense array /
    ``BlockSparseMatrix`` / ``DynamicOperand``) -- or an ``OpSpec`` for
    spec-only planning (dense/dynamic plans stay executable; static
    plans without the concrete pattern are report-only).  ``x`` is used
    only for measured autotune (``ctx.measure=True``, concrete inputs).
    """
    ctx = _resolve_ctx(ctx)
    if isinstance(operand_or_spec, OpSpec):
        spec, operand = operand_or_spec, None
        if ctx.mode != spec.mode:
            ctx = dataclasses.replace(ctx, mode=spec.mode)
    else:
        operand = operand_or_spec
        if n is None:
            raise ValueError("plan(operand, n): n is required when "
                             "planning from a concrete operand")
        spec = OpSpec.from_operand(operand, n, mode=ctx.mode)

    pkey = pattern_key(operand) if operand is not None else None
    fp = _fingerprint(spec, ctx, operand)
    # the persistence policy and the runtime-only knobs are part of the
    # in-memory plan-cache identity but not the disk fingerprint -- see
    # _mem_key / _fingerprint
    mem_key = _mem_key(fp, pkey, ctx)
    if ctx.cache:
        if ctx.pool:
            with _plan_lock:
                keys = _pool_registry.setdefault(ctx.pool, [])
                if mem_key not in keys:
                    keys.append(mem_key)
        hit = _plan_cache.get(mem_key)
        if hit is not None:
            cache_lib.bump("plan_hits")
            return hit

    route, est, source, from_disk, disk_cap, tp_source, disk_grad = \
        _decide(spec, ctx, operand, x)
    key_str = cache_lib.key_string(fp)
    execute, artifacts = _build_executor(spec, route, ctx, operand,
                                         key_str, disk_cap)
    if execute is not None:
        execute, grad_art = _wrap_grad(spec, route, ctx, operand, x,
                                       execute, disk_grad)
        if grad_art is not None:
            artifacts["grad"] = grad_art
    tp_info = _tp_decision(ctx, route, est, source, tp_source)
    if tp_info is not None:
        artifacts["tp"] = tp_info
    stats = artifacts.pop("_capacity_stats", None)
    p = MatmulPlan(spec=spec, route=route, source=source,
                   est_seconds=est, from_disk=from_disk, ctx=ctx,
                   key=key_str, artifacts=artifacts,
                   _execute=execute, capacity_stats=stats)
    cache_lib.bump("plans_built")

    # persist the verdict once, with the capacity/headroom section when
    # the route has a planned bucket -- so restarted processes allocate
    # the identical bucket (including an escalated policy="worst"
    # verdict).  store_decision short-circuits identical records, so a
    # disk-hit rebuild writes nothing.
    if ctx.cache and ctx.persistence_on():
        rec = {"route": route, "source": source,
               "est_seconds": {r: float(s) for r, s in est.items()}}
        if tp_source is not None:
            # TP entries can carry a different unit than the verdict
            # (analytic prior next to measured unsharded times); label
            # them so a disk replay reports the crossover honestly
            rec["tp_source"] = tp_source
        if "capacity" in artifacts:
            rec["capacity"] = {k2: v for k2, v in
                               artifacts["capacity"].items()
                               if k2 != "escalated"}
        grad_art = artifacts.get("grad")
        if grad_art and grad_art.get("mode") == "planned" \
                and "dx" in grad_art and _grad_covered(spec, ctx):
            # the backward verdicts ride in the forward record (one
            # entry per plan fingerprint): a restarted trainer replays
            # fwd route + dx route + dvalues route from one disk hit
            rec["grad"] = {side: dict(grad_art[side])
                           for side in ("dx", "dvalues")}
        cache_lib.store_decision(ctx.resolved_cache_dir(), key_str, rec)

    if ctx.cache:
        with _plan_lock:
            p = _plan_cache.setdefault(mem_key, p)
        if stats is not None and p.capacity_stats is stats:
            # guardrail plumbing: when observed overflow trips the
            # threshold, evict this plan so the next plan() re-plans at
            # worst-case capacity (already-compiled closures keep the
            # planned bucket -- escalation applies to new traces), and
            # persist the escalated verdict NOW -- a long-lived holder
            # of the plan (the serving engine) may never call plan()
            # again in this process, but the restart must see "worst"
            esc_rec = None
            if ctx.persistence_on() and "capacity" in artifacts:
                cap_art = {k2: v for k2, v in
                           artifacts["capacity"].items()
                           if k2 != "escalated"}
                cap_art["policy"] = "worst"
                cap_art["tiles_cap"] = cap_art["worst_tiles"]
                esc_rec = {"route": route, "source": source,
                           "est_seconds": {r: float(s)
                                           for r, s in est.items()},
                           "capacity": cap_art}
            esc_dir = ctx.resolved_cache_dir()

            def _escalate_trip():
                with _plan_lock:
                    _plan_cache.pop(mem_key, None)
                if esc_rec is not None:
                    cache_lib.store_decision(esc_dir, key_str, esc_rec)
            stats._on_escalate = _escalate_trip
    return p


def explain(operand_or_spec, n: Optional[int] = None, *,
            ctx: Optional[PlanContext] = None) -> dict:
    """Plan and report in one step (non-executing)."""
    return plan(operand_or_spec, n, ctx=ctx).explain()


def pack(operand: BlockSparseMatrix) -> partitioner.PackedTiles:
    """A static operand's values in the bsmm kernels' tile layout, for
    forward-only callers whose weights do not change (a serving engine):
    pack once, at weight-load, and pass the result to ``spmm`` /
    ``spmm_nt`` as ``packed=``.  The tiles are bit-identical to what the
    bsmm routes (``PACKED_ROUTES``) build per call; the stack ends in
    the balanced walk's zero pad tile, so it serves both routes at every
    ``n``.  Jit- and vmap-compatible (the pattern is host metadata)."""
    if not (isinstance(operand, BlockSparseMatrix) and operand.is_static):
        raise ValueError("sparse.pack takes a BlockSparseMatrix with a "
                         "static (host) pattern")
    from repro.kernels.bsmm import ops as bsmm_ops
    m, k = operand.shape
    b = operand.block_size
    tm, tk = bsmm_ops.tile_shape(m, k, b)
    meta = partitioner.plan_packing(
        np.asarray(operand.row_idx, np.int32),
        np.asarray(operand.col_idx, np.int32), (m, k), b, tm, tk)
    return partitioner.PackedTiles(
        partitioner.pack_values(meta, operand.values, pad=1))


def spmm(operand: Operand, x, *, ctx: Optional[PlanContext] = None,
         packed: Optional[partitioner.PackedTiles] = None):
    """One-shot ``Y = W @ X`` (plan + execute; the plan is cached, so
    repeated calls are dict hits -- prefer holding the plan in hot
    loops).  ``packed`` (``sparse.pack(operand)``) stands in for the
    values where the plan ``takes_packed``; every other plan runs the
    operand's values."""
    ctx = _resolve_ctx(ctx)
    _, _, k, _, _ = dispatch._normalize(operand)
    if x.ndim != 2:
        raise ValueError(f"x must be [k, n], got shape {x.shape}")
    if x.shape[0] != k:
        raise ValueError(f"X rows {x.shape[0]} != operand k {k}")
    p = plan(operand, int(x.shape[1]), x=x, ctx=ctx)
    if packed is not None and p.takes_packed:
        return p(packed, x)
    return p.apply(operand, x)


def spmm_nt(operand: Operand, x, *, ctx: Optional[PlanContext] = None,
            packed: Optional[partitioner.PackedTiles] = None):
    """Activation-major form ``x: [..., k] -> [..., m]`` (y = x @ W^T)."""
    _, m, k, _, _ = dispatch._normalize(operand)
    lead = x.shape[:-1]
    y = spmm(operand, x.reshape(-1, k).T, ctx=ctx, packed=packed)
    return y.T.reshape(*lead, m)


def matmul(x, w, *, ctx: Optional[PlanContext] = None):
    """Dense-layer form ``y = x @ w`` (``x: [..., k]``, ``w: [k, n]``) --
    what ``models.layers.dense`` and the serving engine execute with."""
    ctx = _resolve_ctx(ctx)
    if isinstance(w, (BlockSparseMatrix, DynamicOperand)):
        raise ValueError("matmul() takes a dense rhs; use spmm_nt for "
                         "sparse operands")
    lead = x.shape[:-1]
    k, n_out = w.shape
    x2 = x.reshape(-1, k)
    spec = OpSpec(kind="dense", m=n_out, k=k, n=int(x2.shape[0]),
                  dtype=jnp.dtype(w.dtype).name, op="matmul",
                  mode=ctx.mode if ctx.mode in dispatch.MODES else "auto")
    y = plan(spec, ctx=ctx)(w, x2)
    return y.reshape(*lead, n_out)


def batched_matmul(a, b, *, ctx: Optional[PlanContext] = None):
    """Batched dense ``[..., C, D] @ [..., D, F]`` (MoE expert GEMMs):
    one plan for the per-slice problem, vmapped over the batch axes."""
    ctx = _resolve_ctx(ctx)
    cdim, ddim = a.shape[-2], a.shape[-1]
    fdim = b.shape[-1]
    spec = OpSpec(kind="dense", m=cdim, k=ddim, n=int(fdim),
                  dtype=jnp.dtype(a.dtype).name, op="batched_matmul",
                  mode=ctx.mode if ctx.mode in dispatch.MODES else "auto")
    return plan(spec, ctx=ctx)(a, b)
