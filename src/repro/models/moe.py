"""Mixture-of-Experts FFN -- the paper's *dynamic* block sparsity at layer
scale.

MegaBlocks (Gale et al. 2022, cited in paper §1.2) frames MoE expert
compute as block-sparse matmul whose pattern (the routing) changes every
step with a capacity bound -- exactly PopSparse dynamic mode: ``d_max``
== top_k/E * capacity_factor is fixed at compile time, the pattern is
runtime data, and overflow (capacity drops) is the analogue of the
paper's bucket overflow.

Dispatch is sort-free "capacity gather": for each expert, take the first
C tokens routed to it (stable priority by token order), compute the
batched expert GEMM [E, C, D] @ [E, D, F], and scatter-combine weighted by
router probs.  Shardings: E over the ``model`` mesh axis (expert
parallelism), C inherits the token batch sharding -- the GSPMD view of the
paper's q^m x q^k x q^n partition grid.

TPU path: ``kernels/gmm`` grouped GEMM consumes the same (sorted) layout.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import sparse as sparse_api
from repro.sharding.rules import constrain


class MoEMetrics(NamedTuple):
    aux_loss: jax.Array        # load-balance loss (switch-style)
    z_loss: jax.Array          # router logit magnitude penalty
    dropped_frac: jax.Array    # fraction of assignments over capacity


def moe_init(key, cfg, *, dtype=jnp.bfloat16):
    m = cfg.moe
    d = cfg.d_model
    ks = jax.random.split(key, 5)
    scale = 1.0 / np.sqrt(d)
    p = {
        "router": {"w": (jax.random.normal(ks[0], (d, m.num_experts))
                         * scale).astype(jnp.float32)},
        # stacked expert weights [E, ...] -- the EP shard axis
        "w_gate": (jax.random.normal(ks[1], (m.num_experts, d, m.d_ff_expert))
                   * scale).astype(dtype),
        "w_up": (jax.random.normal(ks[2], (m.num_experts, d, m.d_ff_expert))
                 * scale).astype(dtype),
        "w_down": (jax.random.normal(
            ks[3], (m.num_experts, m.d_ff_expert, d))
            * (1.0 / np.sqrt(m.d_ff_expert))).astype(dtype),
    }
    if m.num_shared:
        from repro.models.layers import mlp_init
        p["shared"] = mlp_init(ks[4], d, m.num_shared * m.d_ff_shared,
                               act=cfg.act, dtype=dtype)
    return p


def _capacity(tokens: int, cfg) -> int:
    m = cfg.moe
    c = int(np.ceil(tokens * m.top_k / m.num_experts * m.capacity_factor))
    # keep the gather shape MXU-friendly and nonzero
    return max(8, -(-c // 8) * 8)


def moe_apply(params, cfg, x: jax.Array) -> tuple[jax.Array, MoEMetrics]:
    """x: [B, S, D] -> (y, metrics).  Capacity-bounded top-k routing.

    Two distribution strategies (cfg.moe.impl, see EXPERIMENTS.md §Perf):

    * "gspmd": single-program dispatch; GSPMD infers the collectives.
      Simple, but the data-sharded-tokens -> expert-sharded-buckets
      gather lowers to a full-bucket all-reduce (measured dominant on
      qwen3-moe train_4k).
    * "shard_map": explicit local dispatch -- tokens stay on their DP
      shard (replicated over 'model'), each model shard computes only
      its owned experts, one bf16 psum over 'model' combines.  This is
      the paper's static-partition philosophy applied to the dynamic
      pattern: local work from locally-available operands + one final
      reduction.
    """
    from repro.sharding.rules import batch_axes, current_mesh
    m = cfg.moe
    mesh = current_mesh()
    out = None
    if (m.impl == "shard_map" and mesh is not None
            and "model" in mesh.axis_names
            and m.num_experts % mesh.shape["model"] == 0):
        ba = batch_axes(mesh)
        dp = 1
        for a in ba:
            dp *= mesh.shape[a]
        if ba and x.shape[0] % dp == 0:
            out = _moe_shard_map(params, cfg, x, mesh, ba)
    if out is None:
        out = _moe_gspmd(params, cfg, x)
    y, metrics = out
    # the routing drop is the MoE face of the paper's bucket overflow:
    # fold it into the same capacity telemetry the dynamic_grouped plans
    # report through (eager calls only -- no-op under tracing)
    sparse_api.record_dropped("moe_dispatch", metrics.dropped_frac)
    return y, metrics


def _moe_gspmd(params, cfg, x: jax.Array) -> tuple[jax.Array, MoEMetrics]:
    """GSPMD-friendly dispatch: only the *index* map (token_for_slot
    [E, C]) is built by scatter; embeddings move through a single gather
    so the big [E, C, D] tensor is born expert-sharded.  Empty slots
    gather token 0 with combine-weight 0 -- wasted FLOPs on padding slots
    are exactly the paper's dynamic-mode overflow cost (§3.3), surfaced
    per-step in ``dropped_frac``.
    """
    m = cfg.moe
    b_, s, d = x.shape
    t = b_ * s
    xf = x.reshape(t, d)
    cap = _capacity(t, cfg)

    logits = xf.astype(jnp.float32) @ params["router"]["w"]     # [T, E]
    if m.router_score == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    else:
        scores = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(scores, m.top_k)               # [T, k]
    if m.norm_topk_prob:
        top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)

    # --- capacity assignment (the d_max bucket bound, paper §3.3) --------
    # position within expert queue = running count of that expert over the
    # flattened (T*k) assignment priority order.
    flat_e = top_e.reshape(-1)                                  # [T*k]
    if m.ranking == "sort":
        # O(Tk log Tk) HBM-light ranking (§Perf): stable-sort by expert,
        # rank within each run = index - first-index-of-expert
        order = jnp.argsort(flat_e, stable=True)
        sorted_e = flat_e[order]
        first = jnp.searchsorted(sorted_e, jnp.arange(m.num_experts))
        rank_sorted = jnp.arange(flat_e.shape[0]) - first[sorted_e]
        slot = jnp.zeros_like(flat_e).at[order].set(rank_sorted)
        counts = jnp.bincount(flat_e, length=m.num_experts)
    else:
        onehot = jax.nn.one_hot(flat_e, m.num_experts, dtype=jnp.int32)
        pos_in_e = jnp.cumsum(onehot, axis=0) * onehot          # 1-based
        slot = (pos_in_e.sum(-1) - 1)                           # [T*k]
        counts = onehot.sum(0)
    keep = slot < cap
    dropped = 1.0 - keep.mean(dtype=jnp.float32)

    # index map + combine weights (scatter of scalars only; overflow goes
    # to a scratch column that is cropped -- the paper's bucket overflow)
    e_idx = jnp.where(keep, flat_e, m.num_experts - 1)
    c_idx = jnp.where(keep, slot, cap)
    tok_idx = jnp.repeat(jnp.arange(t), m.top_k)
    token_for_slot = jnp.zeros((m.num_experts, cap + 1), jnp.int32
                               ).at[e_idx, c_idx].set(tok_idx)[:, :cap]
    w_slot = jnp.zeros((m.num_experts, cap + 1), jnp.float32
                       ).at[e_idx, c_idx].set(top_p.reshape(-1))[:, :cap]

    # --- expert compute: gather + batched GEMM over the E axis.
    # Sharding anchors (§Perf): E over 'model' (EP) and the capacity dim
    # over the DP axes -- without the C anchor GSPMD all-reduces the full
    # [E_loc, C, D] bucket tensor across data shards (measured 5.4 GB/
    # layer on qwen3-moe train_4k).
    buckets = constrain(jnp.take(xf, token_for_slot, axis=0),
                        "model", "batch", None)                 # [E, C, D]
    # expert GEMMs go through the plan-first sparse API (one plan for
    # the per-expert [C, D] @ [D, F] problem, built at first trace and
    # reused every step, vmapped over E)
    h_g = sparse_api.batched_matmul(buckets, params["w_gate"])
    h_u = sparse_api.batched_matmul(buckets, params["w_up"])
    act = jax.nn.silu if cfg.act == "silu" else jax.nn.gelu
    h = constrain(act(h_g) * h_u, "model", "batch", None)
    out_e = constrain(
        sparse_api.batched_matmul(h, params["w_down"]),
        "model", "batch", None)                                 # [E, C, D]

    # --- combine: expert-side weighted scatter-add (associative, so GSPMD
    # keeps experts sharded and all-reduces the [T, D] partials).
    # combine_dtype="bfloat16" halves that all-reduce volume (§Perf).
    cdt = jnp.bfloat16 if m.combine_dtype == "bfloat16" else jnp.float32
    contrib = out_e.astype(cdt) * w_slot[..., None].astype(cdt)
    y = jnp.zeros((t, d), cdt).at[
        token_for_slot.reshape(-1)].add(contrib.reshape(-1, d))
    y = constrain(y, "batch", None).astype(jnp.float32)

    if m.num_shared:
        from repro.models.layers import mlp
        y += mlp(params["shared"], xf, act=cfg.act).astype(jnp.float32)

    # --- aux losses (switch load-balance + z-loss) ------------------------
    probs_mean = jax.nn.softmax(logits, axis=-1).mean(0)        # [E]
    frac = counts.astype(jnp.float32) / (t * m.top_k)
    aux = m.num_experts * jnp.sum(frac * probs_mean)
    z = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
    return (y.reshape(b_, s, d).astype(x.dtype),
            MoEMetrics(aux, z, dropped))


def _route_and_rank(xf, router_w, cfg, cap):
    """Shared routing core: top-k + capacity slot assignment on a local
    token set.  Returns (top_p, slot index maps, metrics pieces)."""
    m = cfg.moe
    t = xf.shape[0]
    logits = xf.astype(jnp.float32) @ router_w
    scores = jax.nn.sigmoid(logits) if m.router_score == "sigmoid" \
        else jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(scores, m.top_k)
    if m.norm_topk_prob:
        top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
    flat_e = top_e.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    first = jnp.searchsorted(sorted_e, jnp.arange(m.num_experts))
    rank_sorted = jnp.arange(flat_e.shape[0]) - first[sorted_e]
    slot = jnp.zeros_like(flat_e).at[order].set(rank_sorted)
    counts = jnp.bincount(flat_e, length=m.num_experts)
    keep = slot < cap
    e_idx = jnp.where(keep, flat_e, m.num_experts - 1)
    c_idx = jnp.where(keep, slot, cap)
    tok_idx = jnp.repeat(jnp.arange(t), m.top_k)
    token_for_slot = jnp.zeros((m.num_experts, cap + 1), jnp.int32
                               ).at[e_idx, c_idx].set(tok_idx)[:, :cap]
    w_slot = jnp.zeros((m.num_experts, cap + 1), jnp.float32
                       ).at[e_idx, c_idx].set(top_p.reshape(-1))[:, :cap]
    dropped = 1.0 - keep.mean(dtype=jnp.float32)
    probs_mean = jax.nn.softmax(logits, axis=-1).mean(0)
    z = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
    return token_for_slot, w_slot, counts, dropped, probs_mean, z


def _moe_shard_map(params, cfg, x, mesh, ba) -> tuple[jax.Array, MoEMetrics]:
    """Explicit local EP dispatch (§Perf, cell B):

    * tokens: sharded over the DP axes, replicated over 'model';
    * expert weights: E over 'model' (+ FSDP 'data' shard all-gathered
      locally, reduce-scattered in the backward);
    * each model shard routes the *local* tokens, computes only its
      E/|model| experts, and contributes a partial [T_loc, D];
    * ONE psum over 'model' (bf16 if combine_dtype says so) combines.
    """
    from jax.sharding import PartitionSpec as P
    m = cfg.moe
    b_, s, d = x.shape
    ep = mesh.shape["model"]
    e_loc = m.num_experts // ep
    cdt = jnp.bfloat16 if m.combine_dtype == "bfloat16" else jnp.float32
    bspec = ba if len(ba) > 1 else ba[0]

    def local_fn(x_loc, router_w, w_gate, w_up, w_down):
        bl, s_, d_ = x_loc.shape
        xf = x_loc.reshape(bl * s_, d_)
        cap = _capacity(bl * s_, cfg)
        tfs, w_slot, counts, dropped, probs_mean, z = _route_and_rank(
            xf, router_w, cfg, cap)
        # this shard's experts
        e0 = jax.lax.axis_index("model") * e_loc
        tfs_loc = jax.lax.dynamic_slice_in_dim(tfs, e0, e_loc, 0)
        w_slot_loc = jax.lax.dynamic_slice_in_dim(w_slot, e0, e_loc, 0)
        # FSDP: gather the weight shards over 'data' (bwd: reduce-scatter)
        if "data" in mesh.axis_names and w_gate.shape[1] != d_:
            w_gate = jax.lax.all_gather(w_gate, "data", axis=1, tiled=True)
            w_up = jax.lax.all_gather(w_up, "data", axis=1, tiled=True)
            w_down = jax.lax.all_gather(w_down, "data", axis=1, tiled=True)
        buckets = jnp.take(xf, tfs_loc, axis=0)          # [E_loc, C, D]
        h_g = sparse_api.batched_matmul(buckets, w_gate)
        h_u = sparse_api.batched_matmul(buckets, w_up)
        act = jax.nn.silu if cfg.act == "silu" else jax.nn.gelu
        out_e = sparse_api.batched_matmul(act(h_g) * h_u, w_down)
        contrib = out_e.astype(cdt) * w_slot_loc[..., None].astype(cdt)
        y = jnp.zeros((bl * s_, d_), cdt).at[
            tfs_loc.reshape(-1)].add(contrib.reshape(-1, d_))
        y = jax.lax.psum(y, "model")                     # THE combine
        # metrics: mean over DP shards (identical across 'model')
        aux = m.num_experts * jnp.sum(
            counts.astype(jnp.float32) / (bl * s_ * m.top_k) * probs_mean)
        metrics = jax.lax.pmean(
            jnp.stack([aux, z, dropped]), ba[0]) if len(ba) == 1 else \
            jax.lax.pmean(jax.lax.pmean(
                jnp.stack([aux, z, dropped]), ba[0]), ba[1])
        return y.reshape(bl, s_, d_).astype(jnp.float32), metrics

    # expert weights: E over 'model', FSDP over 'data' on axis 1
    # (w_gate/w_up: D; w_down: F -- same rule as sharding/rules.py)
    w_spec = P("model", "data" if "data" in mesh.axis_names else None,
               None)
    fn = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(bspec, None, None), P(None, None),
                  w_spec, w_spec, w_spec),
        out_specs=(P(bspec, None, None), P()),
        check_vma=False)
    y, metrics = fn(x, params["router"]["w"], params["w_gate"],
                    params["w_up"], params["w_down"])
    if m.num_shared:
        from repro.models.layers import mlp
        b2, s2, d2 = x.shape
        xf = x.reshape(-1, d2)
        y = y + mlp(params["shared"], xf, act=cfg.act).astype(
            jnp.float32).reshape(b2, s2, d2)
    return (y.astype(x.dtype),
            MoEMetrics(metrics[0], metrics[1], metrics[2]))


def moe_flops_per_token(cfg) -> float:
    """Active-path FLOPs (the 6·N_active·D numerator's layer share)."""
    m = cfg.moe
    d = cfg.d_model
    f = 2.0 * d * m.d_ff_expert * 3 * m.top_k
    f += 2.0 * d * m.num_experts                 # router
    if m.num_shared:
        f += 2.0 * d * m.num_shared * m.d_ff_shared * 3
    return f
