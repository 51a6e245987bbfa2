"""Decoder layers + scan-based stacks.

A model is ``groups = ((period, repeat), ...)`` (see ``config.py``); each
period is a tuple of ``LayerSpec`` and the whole period is scanned
``repeat`` times over stacked params -- HLO stays O(period) regardless of
depth, which keeps 80 pod-scale dry-run compiles tractable.

Remat: the period function is wrapped in ``jax.checkpoint`` with a
configurable policy (cfg.remat); "full" recomputes everything (baseline),
"dots" saves matmul outputs (a §Perf lever trading HBM for FLOPs).
"""
from __future__ import annotations

import contextlib
import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.core import sparse_layers
from repro.sharding.rules import constrain
from repro.models import attention as attn
from repro.models import moe as moe_lib
from repro.models import ssm as ssm_lib
from repro.models.config import LayerSpec, ModelCfg
from repro.models.layers import mlp, mlp_init, rms_norm


def _zero_metrics():
    z = jnp.zeros((), jnp.float32)
    return {"aux_loss": z, "z_loss": z, "dropped_frac": z}


# ---------------------------------------------------------------------------
# Single layer
# ---------------------------------------------------------------------------

def layer_init(key, cfg: ModelCfg, spec: LayerSpec, *, dtype=jnp.bfloat16):
    ks = jax.random.split(key, 4)
    p: Dict[str, Any] = {"norm1": {"scale": jnp.ones((cfg.d_model,), jnp.float32)}}
    if spec.mixer in ("attn", "attn_local"):
        p["attn"] = attn.gqa_init(ks[0], cfg, dtype=dtype)
    elif spec.mixer == "mla":
        p["attn"] = attn.mla_init(ks[0], cfg, dtype=dtype)
    elif spec.mixer == "mamba":
        p["mixer"] = ssm_lib.ssm_init(ks[0], cfg, dtype=dtype)
    else:
        raise ValueError(spec.mixer)
    if spec.cross:
        p["cross"] = attn.cross_init(ks[2], cfg, dtype=dtype)
        p["norm_x"] = {"scale": jnp.ones((cfg.d_model,), jnp.float32)}
    if spec.ffn != "none":
        p["norm2"] = {"scale": jnp.ones((cfg.d_model,), jnp.float32)}
    if spec.ffn == "mlp":
        p["ffn"] = mlp_init(ks[1], cfg.d_model, cfg.d_ff, act=cfg.act,
                            dtype=dtype)
    elif spec.ffn == "moe":
        p["ffn"] = moe_lib.moe_init(ks[1], cfg, dtype=dtype)
    elif spec.ffn == "sparse":
        p["ffn"] = _sparse_ffn(cfg).init(ks[1])
    if cfg.post_norm:
        p["post_norm1"] = {"scale": jnp.ones((cfg.d_model,), jnp.float32)}
        if spec.ffn != "none":
            p["post_norm2"] = {"scale": jnp.ones((cfg.d_model,), jnp.float32)}
    return p


@functools.lru_cache(maxsize=None)
def _sparse_ffn_cached(d_model, d_ff, block, density, gated, dtype_str):
    return sparse_layers.SparseFFN(d_model, d_ff, block, density,
                                   gated=gated, dtype=jnp.bfloat16
                                   if dtype_str == "bfloat16" else jnp.float32)


def _sparse_ffn(cfg: ModelCfg):
    return _sparse_ffn_cached(cfg.d_model, cfg.d_ff, cfg.ffn_block_size,
                              cfg.ffn_density, cfg.act in ("silu", "gelu"),
                              cfg.dtype)


def sparse_linears(cfg: ModelCfg):
    """``(group, position, name, layer)`` of every ``SparseLinear`` in
    the stack: its params are ``params["stack"][group][position]["ffn"]
    [name]``, stacked over the group's repeat axis."""
    for gi, (period, _) in enumerate(cfg.groups):
        for si, spec in enumerate(period):
            if spec.ffn != "sparse":
                continue
            up, down, gate = _sparse_ffn(cfg)._layers()
            for name, layer in (("up", up), ("down", down), ("gate", gate)):
                if layer is not None:
                    yield gi, si, name, layer


def _mixer_scope(spec: LayerSpec):
    """``attn`` around an attention mixer's call (projections, rope, KV
    write, attention); the recurrent mixer stays unscoped."""
    if spec.mixer == "mamba":
        return contextlib.nullcontext()
    return jax.named_scope("attn")


def _apply_ffn(params, cfg, spec, h):
    metrics = _zero_metrics()
    if spec.ffn == "none":
        return jnp.zeros_like(h), metrics
    with jax.named_scope("ffn"):
        hn = rms_norm(params["norm2"], h, eps=cfg.norm_eps,
                      plus_one=cfg.post_norm)
        if spec.ffn == "mlp":
            out = mlp(params["ffn"], hn, act=cfg.act)
        elif spec.ffn == "moe":
            out, m = moe_lib.moe_apply(params["ffn"], cfg, hn)
            metrics = {"aux_loss": m.aux_loss, "z_loss": m.z_loss,
                       "dropped_frac": m.dropped_frac}
        elif spec.ffn == "sparse":
            out = _sparse_ffn(cfg).apply(params["ffn"], hn)
        else:
            raise ValueError(spec.ffn)
        if cfg.post_norm:
            out = rms_norm(params["post_norm2"], out, eps=cfg.norm_eps,
                           plus_one=True)
    return out, metrics


def layer_apply(params, cfg: ModelCfg, spec: LayerSpec, h, *, positions,
                memory=None, schedule=None):
    """Training / prefill path: full sequence, no cache.

    ``memory``: encoder output [B, T, D] for cross layers.
    """
    hn = rms_norm(params["norm1"], h, eps=cfg.norm_eps,
                  plus_one=cfg.post_norm)
    sched = schedule or cfg.attn_schedule
    with _mixer_scope(spec):
        if spec.mixer in ("attn", "attn_local"):
            mix = attn.gqa_train(params["attn"], cfg, hn,
                                 positions=positions,
                                 local=spec.mixer == "attn_local",
                                 causal=spec.causal, schedule=sched)
        elif spec.mixer == "mla":
            mix = attn.mla_train(params["attn"], cfg, hn,
                                 positions=positions, schedule=sched)
        else:
            mix = ssm_lib.ssm_train(params["mixer"], cfg, hn)
    if cfg.post_norm:
        mix = rms_norm(params["post_norm1"], mix, eps=cfg.norm_eps,
                       plus_one=True)
    h = h + mix
    if spec.cross:
        xk, xv = attn.cross_kv(params["cross"], cfg, memory)
        xn = rms_norm(params["norm_x"], h, eps=cfg.norm_eps,
                      plus_one=cfg.post_norm)
        h = h + attn.cross_apply(params["cross"], cfg, xn, xk, xv)
    out, metrics = _apply_ffn(params, cfg, spec, h)
    return h + out, metrics


def layer_cache_init(cfg: ModelCfg, spec: LayerSpec, batch: int,
                     max_len: int, *, dtype=jnp.bfloat16,
                     memory_len: int = 0):
    if spec.mixer in ("attn", "attn_local"):
        c = attn.gqa_cache_init(cfg, batch, max_len, dtype=dtype)
    elif spec.mixer == "mla":
        c = attn.mla_cache_init(cfg, batch, max_len, dtype=dtype)
    else:
        c = ssm_lib.ssm_cache_init(cfg, batch, dtype=dtype)
    if spec.cross:
        kv, dh = cfg.num_kv_heads, cfg.head_dim
        c["xk"] = jnp.zeros((batch, memory_len, kv, dh), dtype)
        c["xv"] = jnp.zeros((batch, memory_len, kv, dh), dtype)
    return c


def layer_prefill(params, cfg: ModelCfg, spec: LayerSpec, h, *, positions,
                  max_len: int, memory=None, schedule=None):
    """Full-sequence forward emitting (h, populated cache)."""
    hn = rms_norm(params["norm1"], h, eps=cfg.norm_eps,
                  plus_one=cfg.post_norm)
    sched = schedule or cfg.attn_schedule
    with _mixer_scope(spec):
        if spec.mixer in ("attn", "attn_local"):
            mix, cache = attn.gqa_prefill(params["attn"], cfg, hn,
                                          positions=positions,
                                          max_len=max_len,
                                          local=spec.mixer == "attn_local",
                                          schedule=sched)
        elif spec.mixer == "mla":
            mix, cache = attn.mla_prefill(params["attn"], cfg, hn,
                                          positions=positions,
                                          max_len=max_len, schedule=sched)
        else:
            mix, cache = ssm_lib.ssm_prefill(params["mixer"], cfg, hn)
    if cfg.post_norm:
        mix = rms_norm(params["post_norm1"], mix, eps=cfg.norm_eps,
                       plus_one=True)
    h = h + mix
    if spec.cross:
        xk, xv = attn.cross_kv(params["cross"], cfg, memory)
        cache["xk"], cache["xv"] = xk, xv
        xn = rms_norm(params["norm_x"], h, eps=cfg.norm_eps,
                      plus_one=cfg.post_norm)
        h = h + attn.cross_apply(params["cross"], cfg, xn, xk, xv)
    out, _ = _apply_ffn(params, cfg, spec, h)
    return h + out, cache


def layer_decode(params, cfg: ModelCfg, spec: LayerSpec, h, cache, *,
                 positions, slot=None, window_filter: bool = True):
    hn = rms_norm(params["norm1"], h, eps=cfg.norm_eps,
                  plus_one=cfg.post_norm)
    with _mixer_scope(spec):
        if spec.mixer in ("attn", "attn_local"):
            mix, cache = attn.gqa_decode(params["attn"], cfg, hn, cache,
                                         positions=positions, slot=slot,
                                         local=spec.mixer == "attn_local",
                                         window_filter=window_filter)
        elif spec.mixer == "mla":
            mix, cache = attn.mla_decode(params["attn"], cfg, hn, cache,
                                         positions=positions, slot=slot)
        else:
            mix, cache = ssm_lib.ssm_decode(params["mixer"], cfg, hn,
                                            cache)
    if cfg.post_norm:
        mix = rms_norm(params["post_norm1"], mix, eps=cfg.norm_eps,
                       plus_one=True)
    h = h + mix
    if spec.cross:
        xn = rms_norm(params["norm_x"], h, eps=cfg.norm_eps,
                      plus_one=cfg.post_norm)
        h = h + attn.cross_apply(params["cross"], cfg, xn,
                                 cache["xk"], cache["xv"])
    out, _ = _apply_ffn(params, cfg, spec, h)
    return h + out, cache


# ---------------------------------------------------------------------------
# Stack: scan each group's period over its repeat axis
# ---------------------------------------------------------------------------

def _remat_policy(cfg: ModelCfg):
    if cfg.remat == "none":
        return None
    if cfg.remat == "dots":
        return jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    return jax.checkpoint_policies.nothing_saveable


def stack_init(key, cfg: ModelCfg, *, dtype=jnp.bfloat16):
    """Params: list (per group) of list (per period position) of stacked
    layer params with leading ``repeat`` axis."""
    groups = []
    for gi, (period, repeat) in enumerate(cfg.groups):
        period_params = []
        for si, spec in enumerate(period):
            keys = jax.random.split(
                jax.random.fold_in(key, gi * 64 + si), repeat)
            stacked = jax.vmap(
                lambda k: layer_init(k, cfg, spec, dtype=dtype))(keys)
            period_params.append(stacked)
        groups.append(period_params)
    return groups


def stack_apply(params, cfg: ModelCfg, h, *, positions, memory=None,
                schedule=None):
    """Full-sequence stack.  Returns (h, metrics-sum)."""
    total = _zero_metrics()

    for (period, repeat), period_params in zip(cfg.groups, params):
        seq_ax = "model" if cfg.seq_shard else None

        def period_fn(h, layer_params, period=period):
            ms = _zero_metrics()
            for spec, p in zip(period, layer_params):
                h = constrain(h, "batch", seq_ax, None)
                h, m = layer_apply(p, cfg, spec, h, positions=positions,
                                   memory=memory, schedule=schedule)
                ms = jax.tree.map(lambda a, b: a + b, ms, m)
            return constrain(h, "batch", seq_ax, None), ms

        pol = _remat_policy(cfg)
        if pol is not None:
            period_fn = jax.checkpoint(period_fn, policy=pol,
                                       prevent_cse=False)
        h, ms = jax.lax.scan(lambda c, p: period_fn(c, p), h,
                             tuple(period_params))
        total = jax.tree.map(lambda a, b: a + b.sum(), total, ms)
    return h, total


def stack_cache_init(cfg: ModelCfg, batch: int, max_len: int, *,
                     dtype=jnp.bfloat16, memory_len: int = 0):
    caches = []
    for period, repeat in cfg.groups:
        period_caches = []
        for spec in period:
            one = layer_cache_init(cfg, spec, batch, max_len, dtype=dtype,
                                   memory_len=memory_len)
            stacked = jax.tree.map(
                lambda x: jnp.broadcast_to(x, (repeat,) + x.shape).copy(),
                one)
            period_caches.append(stacked)
        caches.append(period_caches)
    return caches


def stack_prefill(params, cfg: ModelCfg, h, *, positions, max_len: int,
                  memory=None, schedule=None):
    """Full-sequence stack emitting (h, stacked caches)."""
    caches = []
    for (period, repeat), period_params in zip(cfg.groups, params):
        def period_fn(h, layer_params, period=period):
            cs = []
            for spec, p in zip(period, layer_params):
                h, c = layer_prefill(p, cfg, spec, h, positions=positions,
                                     max_len=max_len, memory=memory,
                                     schedule=schedule)
                cs.append(c)
            return h, tuple(cs)

        h, cs = jax.lax.scan(lambda c, p: period_fn(c, p), h,
                             tuple(period_params))
        caches.append(list(cs))
    return h, caches


def stack_decode(params, cfg: ModelCfg, h, caches, *, positions, slot=None,
                 window_filter: bool = True):
    new_caches = []
    for (period, repeat), period_params, period_caches in zip(
            cfg.groups, params, caches):
        def period_fn(h, inp, period=period):
            layer_params, layer_caches = inp
            new_lc = []
            for spec, p, c in zip(period, layer_params, layer_caches):
                h, c2 = layer_decode(p, cfg, spec, h, c, positions=positions,
                                     slot=slot, window_filter=window_filter)
                new_lc.append(c2)
            return h, tuple(new_lc)

        h, nc = jax.lax.scan(period_fn, h,
                             (tuple(period_params), tuple(period_caches)))
        new_caches.append(list(nc))
    return h, new_caches
