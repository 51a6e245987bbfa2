"""Scheduled (block-visit-list) attention vs naive reference; schedule
properties; kernel/XLA agreement."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import attention as attn


def _naive(q, k, v, *, causal=True, window=0, global_prefix=0,
           softcap=None, scale=None):
    b, s, h, dh = q.shape
    skv = k.shape[1]
    g = h // k.shape[2]
    kk = jnp.repeat(k, g, axis=2)
    vv = jnp.repeat(v, g, axis=2)
    scale = scale or 1.0 / np.sqrt(dh)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        kk.astype(jnp.float32)) * scale
    if softcap:
        logits = softcap * jnp.tanh(logits / softcap)
    qi = np.arange(s)[:, None]
    ki = np.arange(skv)[None, :]
    mask = np.ones((s, skv), bool)
    if causal:
        mask &= qi >= ki
    if window > 0:
        mask &= (qi - ki < window) | (ki < global_prefix)
    logits = jnp.where(jnp.asarray(mask)[None, None], logits, -1e30)
    w = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", w, vv.astype(jnp.float32)
                      ).astype(q.dtype)


@pytest.mark.parametrize("schedule", ["row", "balanced"])
@pytest.mark.parametrize("kv_heads", [1, 2, 4])
def test_attend_train_causal(schedule, kv_heads):
    b, s, h, dh = 2, 256, 4, 32
    q = jax.random.normal(jax.random.PRNGKey(0), (b, s, h, dh)) * 0.4
    k = jax.random.normal(jax.random.PRNGKey(1), (b, s, kv_heads, dh)) * 0.4
    v = jax.random.normal(jax.random.PRNGKey(2), (b, s, kv_heads, dh))
    got = attn.attend_train(q, k, v, tile_q=64, tile_kv=64,
                            schedule=schedule)
    want = _naive(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("window,gp", [(64, 0), (64, 64), (128, 64)])
def test_attend_train_local_window(window, gp):
    b, s, h, dh = 1, 512, 2, 32
    q = jax.random.normal(jax.random.PRNGKey(0), (b, s, h, dh)) * 0.4
    k = jax.random.normal(jax.random.PRNGKey(1), (b, s, h, dh)) * 0.4
    v = jax.random.normal(jax.random.PRNGKey(2), (b, s, h, dh))
    got = attn.attend_train(q, k, v, window=window, global_prefix=gp,
                            tile_q=64, tile_kv=64)
    want = _naive(q, k, v, window=window, global_prefix=gp)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


def test_attend_train_softcap_noncausal():
    b, s, h, dh = 1, 128, 2, 32
    q = jax.random.normal(jax.random.PRNGKey(0), (b, s, h, dh))
    k = jax.random.normal(jax.random.PRNGKey(1), (b, s, h, dh))
    v = jax.random.normal(jax.random.PRNGKey(2), (b, s, h, dh))
    got = attn.attend_train(q, k, v, causal=False, softcap=20.0,
                            tile_q=64, tile_kv=64)
    want = _naive(q, k, v, causal=False, softcap=20.0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("causal,schedule,window",
                         [(True, "row", 0), (True, "balanced", 0),
                          (True, "row", 100), (False, "row", 0)])
def test_attend_train_unaligned_length(causal, schedule, window):
    """An odd length (an exact-length prefill) keeps
    128-row tiles over a zero-padded axis -- halving to a divisor would
    give one-token tiles -- and padded keys are never attended."""
    b, s, h, dh = 1, 383, 2, 32
    q = jax.random.normal(jax.random.PRNGKey(0), (b, s, h, dh)) * 0.4
    k = jax.random.normal(jax.random.PRNGKey(1), (b, s, h, dh)) * 0.4
    v = jax.random.normal(jax.random.PRNGKey(2), (b, s, h, dh))
    assert attn._seq_tile(s, 256) == (128, 384)
    got = attn.attend_train(q, k, v, causal=causal, window=window,
                            tile_q=256, tile_kv=256, schedule=schedule)
    want = _naive(q, k, v, causal=causal, window=window)
    assert got.shape == q.shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


def test_attend_decode_matches_last_row():
    b, s, h, kv, dh = 2, 96, 4, 2, 32
    q = jax.random.normal(jax.random.PRNGKey(0), (b, 1, h, dh))
    ks = jax.random.normal(jax.random.PRNGKey(1), (b, s, kv, dh))
    vs = jax.random.normal(jax.random.PRNGKey(2), (b, s, kv, dh))
    lengths = jnp.array([s, s - 20])
    got = attn.attend_decode(q, ks, vs, lengths=lengths)
    for i, L in enumerate([s, s - 20]):
        want = _naive(q[i:i+1], ks[i:i+1, :L], vs[i:i+1, :L], causal=False)
        np.testing.assert_allclose(np.asarray(got[i]), np.asarray(want[0]),
                                   rtol=2e-3, atol=2e-3)


# -- schedule properties -------------------------------------------------------------

@pytest.mark.parametrize("nq", [1, 2, 3, 5, 7, 8, 13, 16, 21, 24])
@pytest.mark.parametrize("balanced", [False, True])
def test_schedule_covers_causal_mask(nq, balanced):
    mask = np.tril(np.ones((nq, nq), bool))
    sched = attn.build_schedule(mask, balanced=balanced)
    visited = set()
    for i in range(nq):
        r = int(sched.rows[i])
        for j in range(sched.width):
            if sched.valid[i, j]:
                visited.add((r, int(sched.cols[i, j])))
    want = {(r, c) for r in range(nq) for c in range(r + 1)}
    assert visited == want
    assert sorted(sched.rows.tolist()) == list(range(nq))


def test_balanced_schedule_cuts_waste():
    """The §Perf claim: folded pairing turns ~50% padded lanes into ~0."""
    nq = 64
    mask = np.tril(np.ones((nq, nq), bool))
    row = attn.build_schedule(mask, balanced=False)
    pair = attn.build_pair_schedule(nq)
    assert row.waste > 0.45
    assert pair.waste < 0.02
    assert pair.valid.sum() == row.valid.sum()  # same useful work
    # coverage: every (r, c<=r) visited exactly once
    visited = set()
    for i in range(pair.rows.shape[0]):
        for j in range(pair.width):
            if pair.valid[i, j]:
                r = int(pair.rows[i, int(pair.tag[i, j])])
                visited.add((r, int(pair.cols[i, j])))
    assert visited == {(r, c) for r in range(nq) for c in range(r + 1)}


def test_balanced_pair_schedule_odd_nq():
    pair = attn.build_pair_schedule(7)
    visited = set()
    for i in range(pair.rows.shape[0]):
        for j in range(pair.width):
            if pair.valid[i, j]:
                r = int(pair.rows[i, int(pair.tag[i, j])])
                visited.add((r, int(pair.cols[i, j])))
    assert visited == {(r, c) for r in range(7) for c in range(r + 1)}


def test_gqa_cache_ring_buffer():
    """Retained-cache decode: ring slot overwrites oldest window entry."""
    from repro.models.config import ModelCfg, LayerSpec
    from repro.models.model import LM
    cfg = ModelCfg(name="t", family="dense", d_model=64, vocab_size=128,
                   num_heads=2, num_kv_heads=2, head_dim=32, d_ff=128,
                   groups=(((LayerSpec(),), 1),),
                   retained_prefix=4, retained_window=8,
                   attn_tile_q=32, attn_tile_kv=32)
    lm = LM(cfg)
    pos = jnp.array([3, 4, 11, 12, 20], jnp.int32)
    slots = lm._ring_slot(pos)
    assert slots.tolist() == [3, 4, 11, 4 + (12 - 4) % 8, 4 + (20 - 4) % 8]
