"""``repro.sparse`` -- the plan-first public sparse-matmul API.

PopSparse's headline speedups come from *ahead-of-time* planning: for
static sparsity the pattern is baked into the compiled graph (§3.2),
and even dynamic sparsity fixes its bucket plan up front (§3.3).  This
package makes that lifecycle explicit -- two phases:

    from repro import sparse

    # phase 1 (once): normalization, pattern analysis + tile packing,
    # route selection (cost model / measured autotune / disk cache),
    # dynamic bucket sizing, mesh-aware TP sharding
    p = sparse.plan(operand, n, ctx=sparse.PlanContext(...))

    # phase 2 (hot path): a decision-free direct call
    y = p(values, x)          # or p.apply(operand, x)

    # fixed weights, forward only (serving): the values' relayout into
    # kernel tiles runs once, not per call
    y = sparse.spmm(operand, x, ctx=ctx, packed=sparse.pack(operand))

Measured verdicts persist to a versioned on-disk cache (configure via
``sparse.configure(cache_dir=...)`` or $REPRO_CACHE_DIR), so serving
restarts re-plan with zero re-measurement.

``sparse.spmm`` / ``spmm_nt`` / ``matmul`` / ``batched_matmul`` are
one-shot conveniences over the plan cache; ``repro.core.dispatch``'s
entry points remain as deprecation shims that build-and-call a plan.
"""
from repro.core.partitioner import PackedTiles  # noqa: F401
from repro.sparse.cache import SCHEMA_VERSION  # noqa: F401
from repro.sparse.plan import (  # noqa: F401
    MatmulPlan,
    PACKED_ROUTES,
    analytic_plans,
    batched_matmul,
    cache_stats,
    capacity_report,
    configure,
    current_ctx,
    evolve,
    evolve_plans,
    explain,
    format_plan,
    matmul,
    pack,
    plan,
    plan_report,
    pool_plans,
    record_dropped,
    remeasure_plan,
    reset,
    reset_telemetry,
    roofline_report,
    spmm,
    spmm_nt,
    tp_report,
    use_ctx,
)
from repro.sparse.spec import (  # noqa: F401
    CAPACITY_POLICIES,
    ESCALATION_MIN_CALLS,
    GRAD_DX_MODES,
    GRAD_SDDMM_MODES,
    CapacityStats,
    OpSpec,
    PlanContext,
    PLAN_MODES,
    PLAN_ROUTES,
    TP_ROUTES,
)
