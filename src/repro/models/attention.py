"""Attention mixers: GQA (RoPE, QK-norm, soft-cap, local windows) and MLA.

Three execution regimes, matching the assigned shape cells:

* ``attend_train``   -- full-sequence training/prefill.  Chunked online-
  softmax attention driven by a **static block visit list** -- the paper's
  static block sparsity applied to the attention score matrix.  Causal,
  local-window and local+global masks all reduce to a host block mask
  (``core/masks.py``); the XLA path scans the non-empty (q_tile, kv_tile)
  pairs, the TPU path hands the same pairs to ``kernels/bs_attn``.
* ``attend_decode``  -- one new token against a KV cache (decode_32k).
* retained-block decode for ``long_500k``: the cache keeps only the
  local-window + global-prefix blocks (static pattern ⇒ fixed cache
  shape), making decode O(window) instead of O(S) -- the paper's static
  sparsity is what makes the 500k cell feasible (DESIGN.md §3).

Scheduling note (see EXPERIMENTS.md §Perf): the baseline visit list for a
causal mask walks row-by-row, which makes the scan length the *max* row
population; ``schedule="balanced"`` pairs row i with row nq-1-i so every
scan step does uniform useful work -- ~2x fewer HLO FLOPs at equal output.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import masks as masks_lib
from repro.kernels.tiling import LANE
from repro.models.layers import apply_rope, dense, dense_init, rms_norm
from repro.sharding.rules import constrain

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Static block-mask schedule: the PopSparse partitioner idea applied to the
# (q_tile, kv_tile) score grid.
# ---------------------------------------------------------------------------

class AttnSchedule(NamedTuple):
    """Static visit plan over score tiles, padded to a rectangular scan.

    ``cols[i, j]`` is the j-th kv tile visited by q tile i; ``valid`` masks
    padding.  Built on host at trace time -- compile-time metadata exactly
    like ``bsmm`` tile lists.
    """

    cols: np.ndarray    # [nq, width] int32
    valid: np.ndarray   # [nq, width] bool
    rows: np.ndarray    # [nq] int32 -- q tile processed at scan step i

    @property
    def width(self) -> int:
        return int(self.cols.shape[1])

    @property
    def waste(self) -> float:
        tot = self.valid.size
        return 1.0 - float(self.valid.sum()) / tot if tot else 0.0


def build_schedule(block_mask: np.ndarray, *, balanced: bool = False
                   ) -> AttnSchedule:
    """Turn a host block mask into a rectangular scan schedule.

    ``balanced=True`` reorders rows so row i is interleaved with row
    nq-1-i (folded causal pairing): for a lower-triangular mask the
    per-step tile count becomes ~uniform, cutting padded (wasted) visits
    from ~50% to ~0 -- a beyond-paper schedule optimization recorded in
    §Perf.
    """
    mask = np.asarray(block_mask, bool)
    nq = mask.shape[0]
    if not mask.any(axis=1).all():
        raise ValueError("every q tile needs >=1 visible kv tile")
    row_cols = [np.flatnonzero(mask[i]) for i in range(nq)]
    order = np.arange(nq)
    if balanced:
        # fold: 0, nq-1, 1, nq-2, ... then chunk back into rows of pairs;
        # a simple interleave keeps per-adjacent-pair work ~constant.
        half = (nq + 1) // 2
        folded = np.empty(nq, np.int64)
        folded[0::2] = np.arange(half)
        folded[1::2] = nq - 1 - np.arange(nq - half)
        order = folded
    width = max(len(row_cols[i]) for i in range(nq))
    if balanced and nq > 1:
        # width of the max *pair* is what matters once rows alternate;
        # rectangular pad still needed per row, but adjacent rows now
        # average out so total padding is near zero for causal masks.
        pass
    cols = np.zeros((nq, width), np.int32)
    valid = np.zeros((nq, width), bool)
    for i, r in enumerate(order):
        c = row_cols[r]
        cols[i, :len(c)] = c
        # park padding lanes on the row's first visible tile (in-mask, so
        # masking only needs the `valid` bit, never an OOB index)
        cols[i, len(c):] = c[0] if len(c) else 0
        valid[i, :len(c)] = True
    return AttnSchedule(cols, valid, order.astype(np.int32))


@functools.lru_cache(maxsize=None)
def _causal_schedule(nq: int, nkv: int, window_tiles: int, global_tiles: int,
                     tile_q: int, tile_kv: int, balanced: bool,
                     causal: bool = True) -> AttnSchedule:
    if not causal:
        mask = np.ones((nq, nkv), bool)
    elif window_tiles > 0:
        mask = masks_lib.local_global_attention_mask(
            nq, nkv, window_blocks=window_tiles, global_blocks=global_tiles,
            causal=True)
    else:
        i = np.arange(nq)[:, None]
        j = np.arange(nkv)[None, :]
        # q tile i covers rows [i*tq, (i+1)*tq); visible iff any (r,c) with
        # c <= r + (nkv*tkv - nq*tq) offset; for self-attention S_q == S_kv
        mask = (j * tile_kv) <= ((i + 1) * tile_q - 1)
    return build_schedule(mask, balanced=balanced)


class PairSchedule(NamedTuple):
    """Folded-causal schedule: step i processes q tiles (i, nq-1-i) with a
    fused lane list of uniform length nq+1 -- every lane does useful work,
    so the scan executes ~nq^2/2 tile visits instead of the rectangular
    row schedule's nq^2 (the causal triangle at zero padding waste)."""

    rows: np.ndarray    # [nsteps, 2]
    cols: np.ndarray    # [nsteps, W2]
    tag: np.ndarray     # [nsteps, W2] which of the two rows a lane feeds
    valid: np.ndarray   # [nsteps, W2]

    @property
    def width(self) -> int:
        return int(self.cols.shape[1])

    @property
    def waste(self) -> float:
        return 1.0 - float(self.valid.sum()) / self.valid.size


@functools.lru_cache(maxsize=None)
def build_pair_schedule(nq: int) -> PairSchedule:
    nsteps = (nq + 1) // 2
    w2 = nq + 1
    rows = np.zeros((nsteps, 2), np.int32)
    cols = np.zeros((nsteps, w2), np.int32)
    tag = np.zeros((nsteps, w2), np.int32)
    valid = np.zeros((nsteps, w2), bool)
    for i in range(nsteps):
        a, b = i, nq - 1 - i
        rows[i] = (a, b)
        la = a + 1
        cols[i, :la] = np.arange(la)
        tag[i, :la] = 0
        valid[i, :la] = True
        if b != a:
            lb = b + 1
            cols[i, la:la + lb] = np.arange(lb)
            tag[i, la:la + lb] = 1
            valid[i, la:la + lb] = True
    return PairSchedule(rows, cols, tag, valid)


def _attend_balanced_causal(q, k, v, *, scale, softcap, tile_q, tile_kv,
                            kv_len: Optional[int] = None) -> jax.Array:
    """Causal full attention via the folded pair schedule (see
    EXPERIMENTS.md §Perf: ~2x fewer score-tile visits than the row
    schedule at identical output).  Keys at or past ``kv_len`` are
    padding and never attended."""
    b_, s, h, dh = q.shape
    nq = s // tile_q
    sched = build_pair_schedule(nq)
    qt = q.reshape(b_, nq, tile_q, h, dh).transpose(1, 0, 3, 2, 4)
    kt = k.reshape(b_, nq, tile_kv, h, dh).transpose(1, 0, 3, 2, 4)
    vt = v.reshape(b_, nq, tile_kv, h, dh).transpose(1, 0, 3, 2, 4)
    qt = constrain(qt, None, "batch", "model", None, None)
    kt = constrain(kt, None, "batch", "model", None, None)
    vt = constrain(vt, None, "batch", "model", None, None)
    rows = jnp.asarray(sched.rows)
    cols = jnp.asarray(sched.cols)
    tags = jnp.asarray(sched.tag)
    valid = jnp.asarray(sched.valid)

    def q_step(_, idx):
        qa = qt[rows[idx, 0]]
        qb = qt[rows[idx, 1]]

        @functools.partial(jax.checkpoint,
                           policy=jax.checkpoint_policies.nothing_saveable)
        def lane(carry, j):
            m, l, acc = carry                   # leading dim 2 (pair slot)
            c = cols[idx, j]
            t = tags[idx, j]
            ok = valid[idx, j]
            qsel = jnp.where(t == 0, qa, qb)
            kj, vj = kt[c], vt[c]
            logits = jnp.einsum("bhqd,bhkd->bhqk", qsel, kj,
                                preferred_element_type=jnp.float32) * scale
            if softcap is not None:
                logits = softcap * jnp.tanh(logits / softcap)
            r0 = jnp.where(t == 0, rows[idx, 0], rows[idx, 1]) * tile_q
            ri = r0 + jax.lax.broadcasted_iota(jnp.int32,
                                               (tile_q, tile_kv), 0)
            ci = c * tile_kv + jax.lax.broadcasted_iota(
                jnp.int32, (tile_q, tile_kv), 1)
            emask = (ri >= ci) & ok
            if kv_len is not None:
                emask &= ci < kv_len
            logits = jnp.where(emask[None, None], logits, NEG_INF)
            m_t, l_t, acc_t = m[t], l[t], acc[t]
            m_new = jnp.maximum(m_t, logits.max(axis=-1))
            alpha = jnp.exp(m_t - m_new)
            p = jnp.exp(logits - m_new[..., None])
            l_new = l_t * alpha + p.sum(axis=-1)
            acc_new = acc_t * alpha[..., None] + jnp.einsum(
                "bhqk,bhkd->bhqd", p.astype(vj.dtype), vj,
                preferred_element_type=jnp.float32)
            return (m.at[t].set(m_new), l.at[t].set(l_new),
                    acc.at[t].set(acc_new)), None

        init = (jnp.full((2, b_, h, tile_q), NEG_INF, jnp.float32),
                jnp.zeros((2, b_, h, tile_q), jnp.float32),
                jnp.zeros((2, b_, h, tile_q, dh), jnp.float32))
        (m, l, acc), _ = jax.lax.scan(lane, init, jnp.arange(sched.width))
        out = acc / jnp.maximum(l, 1e-30)[..., None]      # [2, B, H, tq, dh]
        return None, out.astype(q.dtype)

    _, outs = jax.lax.scan(q_step, None, jnp.arange(sched.rows.shape[0]))
    outs = outs.reshape(-1, b_, h, tile_q, dh)      # [2*nsteps, ...]
    # static inverse permutation: row r was emitted at flat slot inv[r]
    flat_rows = sched.rows.reshape(-1)
    # an odd nq lists its middle row twice and feeds only the first slot,
    # so each row's first occurrence is its slot
    _, inv = np.unique(flat_rows, return_index=True)
    outs = outs[jnp.asarray(inv)]
    return outs.transpose(1, 0, 3, 2, 4).reshape(b_, s, h, dh)


# ---------------------------------------------------------------------------
# Core chunked attention (XLA path): scan q tiles, inner scan over the
# schedule's visit lanes with online softmax.
# ---------------------------------------------------------------------------

def _attend_scheduled(q, k, v, sched: AttnSchedule, *, scale: float,
                      causal: bool, window: int, softcap: Optional[float],
                      tile_q: int, tile_kv: int,
                      global_prefix: int = 0,
                      kv_len: Optional[int] = None) -> jax.Array:
    """q: [B, S, H, dh]; k, v: [B, Skv, KV, dh] already head-repeated to H.

    Returns [B, S, H, dh].  fp32 softmax statistics, bf16 matmul inputs.
    Keys at or past ``kv_len`` are padding and never attended.
    """
    b_, s, h, dh = q.shape
    skv = k.shape[1]
    nq = s // tile_q
    qt = q.reshape(b_, nq, tile_q, h, dh).transpose(1, 0, 3, 2, 4)
    kt = k.reshape(b_, skv // tile_kv, tile_kv, h, dh).transpose(1, 0, 3, 2, 4)
    vt = v.reshape(b_, skv // tile_kv, tile_kv, h, dh).transpose(1, 0, 3, 2, 4)
    # re-anchor shardings: batch over DP axes, heads over the model axis
    # (GSPMD drops these through the nested scan otherwise)
    qt = constrain(qt, None, "batch", "model", None, None)
    kt = constrain(kt, None, "batch", "model", None, None)
    vt = constrain(vt, None, "batch", "model", None, None)
    cols = jnp.asarray(sched.cols)           # [nq, W]
    valid = jnp.asarray(sched.valid)
    rows = jnp.asarray(sched.rows)

    def q_step(_, idx):
        qi = qt[rows[idx]]                   # [B, H, tq, dh] (dynamic row)
        r0 = rows[idx] * tile_q

        @functools.partial(jax.checkpoint,
                           policy=jax.checkpoint_policies.nothing_saveable)
        def kv_step(carry, lane):
            # flash-style backward: nothing from the inner step is saved;
            # logits/probs are recomputed during bwd, so peak memory stays
            # O(tile) instead of O(S^2) (see EXPERIMENTS.md §Perf).
            m, l, acc = carry
            c = cols[idx, lane]
            ok = valid[idx, lane]
            kj = kt[c]                       # [B, H, tkv, dh]
            vj = vt[c]
            logits = jnp.einsum("bhqd,bhkd->bhqk", qi, kj,
                                preferred_element_type=jnp.float32) * scale
            if softcap is not None:
                logits = softcap * jnp.tanh(logits / softcap)
            c0 = c * tile_kv
            ri = r0 + jax.lax.broadcasted_iota(jnp.int32, (tile_q, tile_kv), 0)
            ci = c0 + jax.lax.broadcasted_iota(jnp.int32, (tile_q, tile_kv), 1)
            emask = jnp.full((tile_q, tile_kv), ok)
            if causal:
                emask &= ri >= ci
            if window > 0:
                emask &= (ri - ci < window) | (ci < global_prefix)
            if kv_len is not None:
                emask &= ci < kv_len
            logits = jnp.where(emask[None, None], logits, NEG_INF)
            m_new = jnp.maximum(m, logits.max(axis=-1))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(logits - m_new[..., None])
            l_new = l * alpha + p.sum(axis=-1)
            acc_new = acc * alpha[..., None] + jnp.einsum(
                "bhqk,bhkd->bhqd", p.astype(vj.dtype), vj,
                preferred_element_type=jnp.float32)
            return (m_new, l_new, acc_new), None

        init = (constrain(jnp.full((b_, h, tile_q), NEG_INF, jnp.float32),
                          "batch", "model", None),
                constrain(jnp.zeros((b_, h, tile_q), jnp.float32),
                          "batch", "model", None),
                constrain(jnp.zeros((b_, h, tile_q, dh), jnp.float32),
                          "batch", "model", None, None))
        (m, l, acc), _ = jax.lax.scan(kv_step, init,
                                      jnp.arange(sched.width))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return None, (rows[idx], constrain(out.astype(q.dtype),
                                           "batch", "model", None, None))

    _, (out_rows, outs) = jax.lax.scan(q_step, None, jnp.arange(nq))
    # un-permute rows (balanced schedule shuffles them)
    inv = jnp.zeros((nq,), jnp.int32).at[out_rows].set(jnp.arange(nq, dtype=jnp.int32))
    outs = outs[inv]                          # [nq, B, H, tq, dh]
    return outs.transpose(1, 0, 3, 2, 4).reshape(b_, s, h, dh)


def _repeat_kv(x: jax.Array, n_rep: int) -> jax.Array:
    if n_rep == 1:
        return x
    b_, s, kv, dh = x.shape
    return jnp.broadcast_to(x[:, :, :, None], (b_, s, kv, n_rep, dh)
                            ).reshape(b_, s, kv * n_rep, dh)


def _seq_tile(n: int, pref: int) -> tuple[int, int]:
    """``(tile, padded_n)`` for a sequence axis of length ``n``: the
    whole axis up to ``pref``; above it ``pref`` halved while it does not
    divide ``n``, but never below ``LANE`` -- the axis is zero-padded to
    a multiple of the tile instead (halving an odd prefill length down
    to a divisor gave one-token tiles and a scan of ``n**2 / 2`` steps)."""
    t = min(pref, n)
    while n % t and t // 2 >= LANE:
        t //= 2
    return t, -(-n // t) * t


def attend_train(q, k, v, *, causal: bool = True, window: int = 0,
                 global_prefix: int = 0, softcap: Optional[float] = None,
                 scale: Optional[float] = None, tile_q: int = 512,
                 tile_kv: int = 512, schedule: str = "row") -> jax.Array:
    """Full-sequence attention.  q: [B,S,H,dh], k/v: [B,Skv,KV,dh].

    ``window > 0`` restricts to a local causal window (+ ``global_prefix``
    always-visible leading tokens); both are folded into the static block
    schedule so out-of-window tiles are never visited.
    """
    b_, s, h, dh = q.shape
    skv, kv_heads = k.shape[1], k.shape[2]
    scale = scale if scale is not None else 1.0 / np.sqrt(dh)
    k = _repeat_kv(k, h // kv_heads)
    v = _repeat_kv(v, h // kv_heads)
    tile_q, s_pad = _seq_tile(s, tile_q)
    tile_kv, skv_pad = _seq_tile(skv, tile_kv)
    # zero padding at the end: padded query rows are dropped below, and
    # padded keys are masked by ``kv_len``
    def pad(a, n):
        return jnp.pad(a, ((0, 0), (0, n - a.shape[1]), (0, 0), (0, 0)))
    if s_pad != s:
        q = pad(q, s_pad)
    kv_len = None
    if skv_pad != skv:
        k, v, kv_len = pad(k, skv_pad), pad(v, skv_pad), skv
    nq, nkv = s_pad // tile_q, skv_pad // tile_kv
    if (schedule == "balanced" and causal and window == 0
            and nq == nkv and tile_q == tile_kv and nq > 1):
        out = _attend_balanced_causal(q, k, v, scale=float(scale),
                                      softcap=softcap, tile_q=tile_q,
                                      tile_kv=tile_kv, kv_len=kv_len)
        return out[:, :s]
    # a query's window can straddle one extra back tile: the earliest
    # visible key for the first row of tile i is i*tq - (window-1), so
    # floor((window-1)/tkv) + 1 back tiles (+1 for the strict-< builder)
    wt = (window - 1) // tile_kv + 2 if window > 0 else 0
    gt = -(-global_prefix // tile_kv) if global_prefix > 0 else 0
    sched = _causal_schedule(nq, nkv, wt, gt, tile_q, tile_kv,
                             False, causal)
    out = _attend_scheduled(q, k, v, sched, scale=float(scale),
                            causal=causal, window=window, softcap=softcap,
                            tile_q=tile_q, tile_kv=tile_kv,
                            global_prefix=global_prefix, kv_len=kv_len)
    return out[:, :s]


# ---------------------------------------------------------------------------
# Decode: one new token against a cache.
# ---------------------------------------------------------------------------

def attend_decode(q, k_cache, v_cache, *, lengths, softcap=None,
                  scale=None, window: int = 0, global_prefix: int = 0
                  ) -> jax.Array:
    """q: [B, 1, H, dh]; caches: [B, S, KV, dh]; lengths: [B] valid length.

    Dense over the cache (the cache itself is already the retained set for
    long-context configs).  fp32 logits; GQA repeat via reshape-free einsum.
    """
    b_, _, h, dh = q.shape
    s = k_cache.shape[1]
    kv = k_cache.shape[2]
    g = h // kv
    scale = scale if scale is not None else 1.0 / np.sqrt(dh)
    qg = q.reshape(b_, h, dh).reshape(b_, kv, g, dh)
    logits = jnp.einsum("bkgd,bskd->bkgs", qg.astype(jnp.float32),
                        k_cache.astype(jnp.float32)) * scale
    if softcap is not None:
        logits = softcap * jnp.tanh(logits / softcap)
    pos = jnp.arange(s)[None, None, None, :]
    mask = pos < lengths[:, None, None, None]
    if window > 0:
        lo = lengths[:, None, None, None] - window
        keep = (pos >= lo) | (pos < global_prefix)
        mask &= keep
    logits = jnp.where(mask, logits, NEG_INF)
    w = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgs,bskd->bkgd", w, v_cache.astype(jnp.float32))
    return out.reshape(b_, 1, h, dh).astype(q.dtype)


# ---------------------------------------------------------------------------
# GQA module
# ---------------------------------------------------------------------------

def gqa_init(key, cfg, *, dtype=jnp.bfloat16):
    d = cfg.d_model
    qd, kvd = cfg.attn_dims
    ks = jax.random.split(key, 4)
    p = {"wq": dense_init(ks[0], d, qd, bias=cfg.qkv_bias, dtype=dtype),
         "wk": dense_init(ks[1], d, kvd, bias=cfg.qkv_bias, dtype=dtype),
         "wv": dense_init(ks[2], d, kvd, bias=cfg.qkv_bias, dtype=dtype),
         "wo": dense_init(ks[3], qd, d, dtype=dtype)}
    if cfg.qk_norm:
        p["q_norm"] = {"scale": jnp.ones((cfg.head_dim,), jnp.float32)}
        p["k_norm"] = {"scale": jnp.ones((cfg.head_dim,), jnp.float32)}
    return p


def _project_qkv(params, cfg, x, positions):
    b_, s, _ = x.shape
    h, kv, dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = dense(params["wq"], x).reshape(b_, s, h, dh)
    k = dense(params["wk"], x).reshape(b_, s, kv, dh)
    v = dense(params["wv"], x).reshape(b_, s, kv, dh)
    if "q_norm" in params:
        q = rms_norm(params["q_norm"], q)
        k = rms_norm(params["k_norm"], k)
    if cfg.use_rope:
        q = apply_rope(q, positions, theta=cfg.rope_theta)
        k = apply_rope(k, positions, theta=cfg.rope_theta)
    return q, k, v


def gqa_train(params, cfg, x, *, positions, local: bool = False,
              causal: bool = True, schedule: str = "row") -> jax.Array:
    """Full-sequence GQA.  ``local=True`` uses cfg.local_window."""
    q, k, v = _project_qkv(params, cfg, x, positions)
    scale = cfg.attn_scale or 1.0 / np.sqrt(cfg.head_dim)
    out = attend_train(
        q, k, v, causal=causal,
        window=cfg.local_window if local else 0,
        global_prefix=cfg.global_prefix if local else 0,
        softcap=cfg.attn_softcap, scale=scale,
        tile_q=cfg.attn_tile_q, tile_kv=cfg.attn_tile_kv,
        schedule=schedule)
    b_, s = x.shape[:2]
    return dense(params["wo"], out.reshape(b_, s, -1))


def gqa_decode(params, cfg, x, cache, *, positions, slot=None,
               local: bool = False, window_filter: bool = True):
    """One-token decode.  cache: {"k": [B,S,KV,dh], "v": ...} updated in
    place at ``slot`` (ring-buffer slot for retained-block configs, where
    the window filter is off because the cache IS the retained set)."""
    q, k_new, v_new = _project_qkv(params, cfg, x, positions[:, None])
    slot = positions if slot is None else slot
    bidx = jnp.arange(x.shape[0])
    with jax.named_scope("kv_update"):
        k_cache = cache["k"].at[bidx, slot].set(k_new[:, 0])
        v_cache = cache["v"].at[bidx, slot].set(v_new[:, 0])
    lengths = jnp.minimum(positions + 1, k_cache.shape[1])
    scale = cfg.attn_scale or 1.0 / np.sqrt(cfg.head_dim)
    use_win = local and window_filter
    out = attend_decode(q, k_cache, v_cache, lengths=lengths,
                        softcap=cfg.attn_softcap, scale=scale,
                        window=cfg.local_window if use_win else 0,
                        global_prefix=cfg.global_prefix if use_win else 0)
    y = dense(params["wo"], out.reshape(x.shape[0], 1, -1))
    new_cache = dict(cache, k=k_cache, v=v_cache)
    return y, new_cache


def gqa_cache_init(cfg, batch: int, max_len: int, *, dtype=jnp.bfloat16):
    kv, dh = cfg.num_kv_heads, cfg.head_dim
    return {"k": jnp.zeros((batch, max_len, kv, dh), dtype),
            "v": jnp.zeros((batch, max_len, kv, dh), dtype)}


def gqa_prefill(params, cfg, x, *, positions, max_len: int,
                local: bool = False, schedule: str = "row"):
    """Full-sequence forward that also emits the populated KV cache
    (padded to ``max_len``).  Roped K is cached, so decode never re-ropes."""
    q, k, v = _project_qkv(params, cfg, x, positions)
    scale = cfg.attn_scale or 1.0 / np.sqrt(cfg.head_dim)
    out = attend_train(
        q, k, v, causal=True,
        window=cfg.local_window if local else 0,
        global_prefix=cfg.global_prefix if local else 0,
        softcap=cfg.attn_softcap, scale=scale,
        tile_q=cfg.attn_tile_q, tile_kv=cfg.attn_tile_kv, schedule=schedule)
    b_, s = x.shape[:2]
    y = dense(params["wo"], out.reshape(b_, s, -1))
    pad = [(0, 0), (0, max_len - s), (0, 0), (0, 0)]
    with jax.named_scope("kv_update"):
        cache = {"k": jnp.pad(k, pad).astype(x.dtype),
                 "v": jnp.pad(v, pad).astype(x.dtype)}
    return y, cache


# ---------------------------------------------------------------------------
# Cross attention (enc-dec decoder layers; no RoPE, non-causal over memory)
# ---------------------------------------------------------------------------

def cross_init(key, cfg, *, dtype=jnp.bfloat16):
    d = cfg.d_model
    qd, kvd = cfg.attn_dims
    ks = jax.random.split(key, 4)
    return {"wq": dense_init(ks[0], d, qd, dtype=dtype),
            "wk": dense_init(ks[1], d, kvd, dtype=dtype),
            "wv": dense_init(ks[2], d, kvd, dtype=dtype),
            "wo": dense_init(ks[3], qd, d, dtype=dtype)}


def cross_kv(params, cfg, memory):
    """Precompute memory K/V once (prefill); reused every decode step."""
    b_, t, _ = memory.shape
    kv, dh = cfg.num_kv_heads, cfg.head_dim
    k = dense(params["wk"], memory).reshape(b_, t, kv, dh)
    v = dense(params["wv"], memory).reshape(b_, t, kv, dh)
    return k, v


def cross_apply(params, cfg, x, k, v):
    """x: [B, S, D] attends over memory K/V: [B, T, KV, dh]."""
    b_, s, _ = x.shape
    h, dh = cfg.num_heads, cfg.head_dim
    q = dense(params["wq"], x).reshape(b_, s, h, dh)
    out = attend_train(q, k, v, causal=False, scale=1.0 / np.sqrt(dh),
                       tile_q=cfg.attn_tile_q, tile_kv=cfg.attn_tile_kv)
    return dense(params["wo"], out.reshape(b_, s, -1))


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V2 multi-head latent attention)
# ---------------------------------------------------------------------------

def mla_init(key, cfg, *, dtype=jnp.bfloat16):
    d = cfg.d_model
    h = cfg.num_heads
    qk_nope, qk_rope, v_dim = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    r = cfg.kv_lora_rank
    ks = jax.random.split(key, 6)
    qd = h * (qk_nope + qk_rope)
    if cfg.q_lora_rank:
        p_q = {"a": dense_init(ks[0], d, cfg.q_lora_rank, dtype=dtype),
               "norm": {"scale": jnp.ones((cfg.q_lora_rank,), jnp.float32)},
               "b": dense_init(ks[1], cfg.q_lora_rank, qd, dtype=dtype)}
    else:
        p_q = {"w": dense_init(ks[0], d, qd, dtype=dtype)}
    return {
        "q": p_q,
        # joint down-projection: latent kv (r) + decoupled rope key
        "kv_a": dense_init(ks[2], d, r + qk_rope, dtype=dtype),
        "kv_norm": {"scale": jnp.ones((r,), jnp.float32)},
        "kv_b": dense_init(ks[3], r, h * (qk_nope + v_dim), dtype=dtype),
        "wo": dense_init(ks[4], h * v_dim, d, dtype=dtype),
    }


def _mla_q(params, cfg, x):
    b_, s, _ = x.shape
    h = cfg.num_heads
    if cfg.q_lora_rank:
        qa = rms_norm(params["q"]["norm"], dense(params["q"]["a"], x))
        q = dense(params["q"]["b"], qa)
    else:
        q = dense(params["q"]["w"], x)
    q = q.reshape(b_, s, h, cfg.qk_nope_dim + cfg.qk_rope_dim)
    return jnp.split(q, [cfg.qk_nope_dim], axis=-1)  # nope, rope


def _mla_kv(params, cfg, x):
    b_, s, _ = x.shape
    r = cfg.kv_lora_rank
    kv_a = dense(params["kv_a"], x)
    latent, k_rope = jnp.split(kv_a, [r], axis=-1)
    latent = rms_norm(params["kv_norm"], latent)
    return latent, k_rope.reshape(b_, s, 1, cfg.qk_rope_dim)


def _mla_expand(params, cfg, latent):
    """Expand latent -> per-head k_nope, v."""
    h = cfg.num_heads
    b_, s, _ = latent.shape
    kv = dense(params["kv_b"], latent).reshape(
        b_, s, h, cfg.qk_nope_dim + cfg.v_head_dim)
    return jnp.split(kv, [cfg.qk_nope_dim], axis=-1)


def mla_train(params, cfg, x, *, positions, schedule: str = "row"):
    b_, s, _ = x.shape
    h = cfg.num_heads
    q_nope, q_rope = _mla_q(params, cfg, x)
    latent, k_rope = _mla_kv(params, cfg, x)
    k_nope, v = _mla_expand(params, cfg, latent)
    q_rope = apply_rope(q_rope, positions, theta=cfg.rope_theta)
    k_rope = apply_rope(k_rope, positions, theta=cfg.rope_theta)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate([k_nope,
                         jnp.broadcast_to(k_rope, (b_, s, h, cfg.qk_rope_dim))],
                        axis=-1)
    scale = 1.0 / np.sqrt(cfg.qk_nope_dim + cfg.qk_rope_dim)
    # v padded to qk head dim for the shared attend path, then cropped
    pad = q.shape[-1] - cfg.v_head_dim
    v_p = jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, pad)))
    out = attend_train(q, k, v_p, causal=True, scale=scale,
                       softcap=cfg.attn_softcap, tile_q=cfg.attn_tile_q,
                       tile_kv=cfg.attn_tile_kv, schedule=schedule)
    out = out[..., :cfg.v_head_dim].reshape(b_, s, -1)
    return dense(params["wo"], out)


def mla_cache_init(cfg, batch: int, max_len: int, *, dtype=jnp.bfloat16):
    """MLA decode caches the *latent* (r) + rope key -- the whole point of
    MLA: cache is r+rope wide, not h*(nope+v)."""
    return {"latent": jnp.zeros((batch, max_len, cfg.kv_lora_rank), dtype),
            "k_rope": jnp.zeros((batch, max_len, cfg.qk_rope_dim), dtype)}


def mla_prefill(params, cfg, x, *, positions, max_len: int,
                schedule: str = "row"):
    b_, s, _ = x.shape
    y = mla_train(params, cfg, x, positions=positions, schedule=schedule)
    latent, k_rope = _mla_kv(params, cfg, x)
    k_rope = apply_rope(k_rope, positions, theta=cfg.rope_theta)
    pad2 = [(0, 0), (0, max_len - s), (0, 0)]
    with jax.named_scope("kv_update"):
        cache = {"latent": jnp.pad(latent, pad2).astype(x.dtype),
                 "k_rope": jnp.pad(k_rope[:, :, 0, :], pad2).astype(
                     x.dtype)}
    return y, cache


def mla_decode(params, cfg, x, cache, *, positions, slot=None):
    b_ = x.shape[0]
    h = cfg.num_heads
    q_nope, q_rope = _mla_q(params, cfg, x)
    latent_new, k_rope_new = _mla_kv(params, cfg, x)
    q_rope = apply_rope(q_rope, positions[:, None], theta=cfg.rope_theta)
    k_rope_new = apply_rope(k_rope_new, positions[:, None],
                            theta=cfg.rope_theta)
    bidx = jnp.arange(b_)
    slot = positions if slot is None else slot
    with jax.named_scope("kv_update"):
        latent_c = cache["latent"].at[bidx, slot].set(latent_new[:, 0])
        k_rope_c = cache["k_rope"].at[bidx, slot].set(
            k_rope_new[:, 0, 0])
    s = latent_c.shape[1]
    lengths = jnp.minimum(positions + 1, s)

    # absorbed attention: score = q_nope·W_uk·latent + q_rope·k_rope
    wkv = params["kv_b"]["w"].reshape(cfg.kv_lora_rank, h,
                                      cfg.qk_nope_dim + cfg.v_head_dim)
    w_uk = wkv[:, :, :cfg.qk_nope_dim]        # [r, h, nope]
    w_uv = wkv[:, :, cfg.qk_nope_dim:]        # [r, h, v]
    q_abs = jnp.einsum("bqhn,rhn->bqhr", q_nope.astype(jnp.float32),
                       w_uk.astype(jnp.float32))
    logits = jnp.einsum("bqhr,bsr->bhqs", q_abs,
                        latent_c.astype(jnp.float32))
    logits += jnp.einsum("bqhn,bsn->bhqs", q_rope.astype(jnp.float32),
                         k_rope_c.astype(jnp.float32))
    logits *= 1.0 / np.sqrt(cfg.qk_nope_dim + cfg.qk_rope_dim)
    mask = jnp.arange(s)[None, None, None, :] < lengths[:, None, None, None]
    logits = jnp.where(mask, logits, NEG_INF)
    w = jax.nn.softmax(logits, axis=-1)
    ctx = jnp.einsum("bhqs,bsr->bqhr", w, latent_c.astype(jnp.float32))
    out = jnp.einsum("bqhr,rhv->bqhv", ctx, w_uv.astype(jnp.float32))
    y = dense(params["wo"], out.reshape(b_, 1, -1).astype(x.dtype))
    return y, dict(cache, latent=latent_c, k_rope=k_rope_c)
