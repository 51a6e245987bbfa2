"""Serving engine: admission/termination semantics, bucketed prefill,
plan pools, the background re-planner, and live stats.

Fast tier: a stub LM whose next-token rule is ``tok+1 mod V`` via a
real ``sparse.matmul`` (so plan counters and pools are exercised) --
covers termination contracts, bucket compile counts, the
zero-decision acceptance criterion, and the re-planner.  Slow tier:
model-level parity and continuous batching on a real smoke LM.
"""
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs, sparse as sparse_api
from repro.core import dispatch
from repro.core.bsr import BlockSparseMatrix
from repro.core.sparse_layers import SparseFFN
from repro.kernels.tiling import dim_tile
from repro.models.model import LM
from repro.serve import Engine, Request
from repro.serve.engine import _auto_buckets, _pad_safe, _stack_shapes

V = 16            # stub vocab


class StubLM:
    """Duck-typed LM: next token = (last true token + 1) mod V, via a
    real ``sparse.matmul`` with a shift-permutation weight -- so the
    engine's traced programs build genuine plans (pools, counters)
    while outputs stay exactly predictable.  Reads the true last
    prompt token through ``last_index``: a pad-correctness oracle
    (wrong gather => wrong token, every test below notices)."""

    def __init__(self, cfg):
        self.cfg = cfg

    def make_params(self):
        w = np.zeros((V, V), np.float32)
        w[np.arange(V), (np.arange(V) + 1) % V] = 1.0
        return {"w": jnp.asarray(w)}

    def init_cache(self, batch, max_len, **kw):
        return {"tok": jnp.zeros((1, batch, max_len), jnp.int32)}

    def _logits(self, params, tokens):
        oh = jax.nn.one_hot(tokens, V, dtype=jnp.float32)
        return sparse_api.matmul(oh, params["w"])

    def prefill(self, params, tokens, *, max_len, last_index=None,
                **kw):
        b, s = tokens.shape
        h = self._logits(params, tokens)              # [B, S, V]
        if last_index is None:
            logits = h[:, -1]
        else:
            idx = jnp.asarray(last_index, jnp.int32).reshape(-1, 1, 1)
            logits = jnp.take_along_axis(
                h, jnp.broadcast_to(idx, (b, 1, V)), axis=1)[:, 0]
        cache = {"tok": jnp.zeros((1, b, max_len), jnp.int32)
                 .at[:, :, :s].set(tokens[None])}
        return logits, cache

    def decode_step(self, params, tokens, caches, positions,
                    retained=False):
        return self._logits(params, tokens)[:, 0], caches


class SparseStubLM(StubLM):
    """Stub whose prefill also routes through a static block-sparse
    plan (zero-weighted, so outputs are unchanged) -- gives the
    engine's pool an analytic verdict the re-planner can upgrade."""

    def __init__(self, cfg, wsp):
        super().__init__(cfg)
        self.wsp = wsp

    def prefill(self, params, tokens, *, max_len, last_index=None,
                **kw):
        logits, cache = super().prefill(
            params, tokens, max_len=max_len, last_index=last_index)
        oh = jax.nn.one_hot(tokens, V, dtype=jnp.float32)
        logits = logits + 0.0 * sparse_api.spmm_nt(self.wsp, oh)[:, -1]
        return logits, cache


def _stub_engine(batch=2, max_len=20, buckets=(4, 8, 16), lm=None,
                 **kw):
    sparse_api.reset()
    lm = lm or StubLM(configs.smoke("llama3_2_1b"))
    eng = Engine(lm, lm.make_params(), batch=batch, max_len=max_len,
                 buckets=buckets, **kw)
    return eng


def _req(prompt, uid=0, **kw):
    return Request(uid=uid, prompt=np.asarray(prompt, np.int32), **kw)


# -- admission validation (satellite bugfix 1) ------------------------------

def test_oversized_prompt_rejected():
    eng = _stub_engine(max_len=8, buckets=(4, 7))
    with pytest.raises(ValueError, match="max_len=8"):
        eng.admit(_req(np.arange(8) % V))
    with pytest.raises(ValueError, match="at most 7"):
        eng.submit(_req(np.arange(11) % V))
    with pytest.raises(ValueError, match="empty"):
        eng.admit(_req([]))
    # the limit itself admits
    assert eng.admit(_req(np.arange(7) % V, max_new_tokens=2))


# -- termination semantics (satellite bugfix 2 + tests) ---------------------

def test_eos_at_prefill_frees_slot_immediately():
    eng = _stub_engine()
    # prompt ends with 3 -> prefill generates 4 == eos
    req = _req([1, 2, 3], eos_id=4, max_new_tokens=8)
    assert eng.admit(req)
    assert req.done and req.output == [4]
    assert eng.live == {} and len(eng.free) == eng.batch
    st = eng.stats()
    assert st["admission"]["eos_at_prefill"] == 1
    assert st["steps"] == 0          # not one decode step was spent


def test_eos_at_decode():
    eng = _stub_engine()
    req = _req([1, 2, 3], eos_id=6, max_new_tokens=32)
    eng.run([req])
    assert req.output == [4, 5, 6]   # stops AT eos, slot freed
    assert eng.live == {} and len(eng.free) == eng.batch


def test_max_new_tokens_includes_prefill_token():
    eng = _stub_engine()
    req = _req([7], max_new_tokens=4)
    eng.run([req])
    # the contract: output INCLUDES the prefill-generated token, so
    # max_new_tokens=4 is exactly 4 tokens (1 prefill + 3 decode)
    assert req.output == [8, 9, 10, 11]
    one = _req([7], uid=1, max_new_tokens=1)
    assert eng.admit(one)
    assert one.done and one.output == [8]    # finished at admission


def test_padded_prefill_reads_true_last_token():
    # lengths 3 and 5 share bucket 8: pads must not leak into logits
    eng = _stub_engine()
    a, b = _req([1, 2, 3], uid=0, max_new_tokens=3), \
        _req([1, 2, 3, 4, 5], uid=1, max_new_tokens=3)
    eng.run([a, b])
    assert a.output == [4, 5, 6]
    assert b.output == [6, 7, 8]


# -- on_finish from slot-release bookkeeping (satellite bugfix 3) -----------

def test_on_finish_fires_exactly_once_per_request():
    eng = _stub_engine(batch=2)
    reqs = [_req([i % V], uid=i, max_new_tokens=2 + i % 3)
            for i in range(7)]
    # include an eos-at-prefill request: it must fire too
    reqs.append(_req([1, 2, 3], uid=99, eos_id=4, max_new_tokens=9))
    seen = []
    eng.run(reqs, on_finish=lambda r: seen.append(r.uid))
    assert sorted(seen) == sorted(r.uid for r in reqs)
    assert all(r.done for r in reqs)


# -- bucketed prefill: compiles + zero-decision acceptance ------------------

def test_prefill_compiles_once_per_bucket_not_per_length():
    eng = _stub_engine(batch=2, max_len=20, buckets=(4, 8, 16))
    assert eng.buckets == (4, 8, 16, 20)
    lengths = [2, 3, 4, 5, 7, 9, 11, 15]     # 8 lengths, 3 buckets
    reqs = [_req(np.arange(s) % V, uid=i, max_new_tokens=2)
            for i, s in enumerate(lengths)]
    eng.run(reqs)
    assert {r.bucket for r in reqs} == {4, 8, 16}
    assert eng._prefill._cache_size() == 3
    st = eng.stats()
    assert st["buckets"][4]["prefills"] == 3
    assert st["buckets"][8]["prefills"] == 2
    assert st["buckets"][16]["prefills"] == 3
    assert st["buckets"][8]["pad_tokens"] == (8 - 5) + (8 - 7)


def test_warm_serving_zero_recompiles_zero_decisions():
    """The PR acceptance criterion: after startup warmup, a
    mixed-length stream across >= 3 buckets triggers zero XLA
    recompiles and zero new dispatch decisions/measurements on the
    foreground path."""
    eng = _stub_engine(batch=2, max_len=20, buckets=(4, 8, 16),
                      warm_compile=True)
    assert eng.plan_stats["plans_built"] > 0
    compiles = (eng._prefill._cache_size(), eng._decode._cache_size())
    before = sparse_api.cache_stats()
    reqs = [_req(np.arange(s) % V, uid=i, max_new_tokens=3)
            for i, s in enumerate([2, 5, 9, 3, 15, 7, 12, 4])]
    eng.run(reqs)
    assert {r.bucket for r in reqs} == {4, 8, 16}   # >= 3 buckets hit
    after = sparse_api.cache_stats()
    assert (eng._prefill._cache_size(),
            eng._decode._cache_size()) == compiles
    assert after["decisions"] == before["decisions"]
    assert after["measurements"] == before["measurements"]
    assert after["plans_built"] == before["plans_built"]
    assert eng.stats()["admission"]["exact_prefills"] == 0


# -- queue + dropped_frac ----------------------------------------------------

def test_bounded_queue_drops_and_counts():
    eng = _stub_engine(batch=1, max_queue=2)
    reqs = [_req([i % V], uid=i, max_new_tokens=2) for i in range(5)]
    accepted = [eng.submit(r) for r in reqs]
    assert accepted == [True, True, False, False, False]
    assert [r.dropped for r in reqs] == [False, False, True, True, True]
    eng.serve()
    st = eng.stats()
    assert st["admission"]["dropped"] == 3
    assert st["admission"]["dropped_frac"] == pytest.approx(0.6)
    assert all(r.done for r in reqs[:2])
    assert not any(r.done for r in reqs[2:])


# -- stats endpoint ----------------------------------------------------------

def test_stats_and_plan_report_fields():
    eng = _stub_engine()
    eng.run([_req([1, 2, 3], max_new_tokens=4)])
    st = eng.stats()
    assert st["step_latency"]["count"] == st["steps"] == 3
    assert st["step_latency"]["p50_ms"] is not None
    assert st["step_latency"]["p99_ms"] is not None
    assert st["buckets"][4]["latency"]["count"] == 1
    assert st["padding"]["pad_tokens"] == 1          # 3 -> bucket 4
    assert 0.0 <= st["padding"]["waste_frac"] <= 1.0
    assert st["queue_depth"] == 0 and st["live_slots"] == 0
    assert "overflow_calls" in st["capacity_overflow"]
    assert st["replanner"] == {"running": False, "sweeps": 0,
                               "upgrades": 0}
    rep = eng.plan_report()
    assert rep["engine"]["steps"] == 3
    for key in ("startup", "now", "capacity", "tp", "plans",
                "roofline", "engine"):
        assert key in rep


# -- plan pools + background re-planner --------------------------------------

def _sparse_stub():
    cfg = configs.smoke("llama3_2_1b")
    wsp = BlockSparseMatrix.random(jax.random.PRNGKey(0), V, V, 4, 0.5)
    return SparseStubLM(cfg, wsp)


def test_pool_registers_engine_plans():
    eng = _stub_engine(lm=_sparse_stub())
    plans = sparse_api.pool_plans(eng.pool)
    assert plans, "warmup must register plans under the engine pool"
    assert all(p.ctx.pool == eng.pool for p in plans)
    # pool label is runtime-only: same problem, different pool label,
    # same disk fingerprint
    other = dataclasses.replace(plans[0].ctx, pool="other")
    q = sparse_api.plan(plans[0].spec, ctx=other)
    assert q.key == plans[0].key


def test_replanner_upgrades_analytic_verdicts():
    eng = _stub_engine(lm=_sparse_stub(), warm_compile=True)
    analytic = sparse_api.analytic_plans(eng.pool)
    assert analytic, "sparse stub must leave analytic verdicts to upgrade"
    before = sparse_api.cache_stats()
    n = eng.replan_once(reps=1)
    assert n == len(analytic)
    assert sparse_api.analytic_plans(eng.pool) == []
    st = eng.stats()["replanner"]
    assert st["sweeps"] == 1 and st["upgrades"] == n
    # the upgrade measured in the BACKGROUND; foreground serving stays
    # decision-free and the already-compiled programs still run
    fore = sparse_api.cache_stats()
    reqs = [_req(np.arange(s) % V, uid=i, max_new_tokens=3)
            for i, s in enumerate([2, 5, 9])]
    eng.run(reqs)
    after = sparse_api.cache_stats()
    assert after["decisions"] == fore["decisions"]
    assert after["measurements"] == fore["measurements"]
    assert after["measurements"] > before["measurements"]
    # a rebuild of the same problem now replays the measured verdict
    p = sparse_api.plan(analytic[0].spec, ctx=analytic[0].ctx)
    assert p.source == "measured" and p.from_disk


def test_replanner_thread_lifecycle():
    eng = _stub_engine(lm=_sparse_stub(), replanner=True,
                      replanner_interval=0.01, replanner_reps=1)
    deadline = 200
    while sparse_api.analytic_plans(eng.pool) and deadline:
        time.sleep(0.01)
        deadline -= 1
    assert sparse_api.analytic_plans(eng.pool) == []
    assert eng.stats()["replanner"]["running"]
    eng.stop_replanner()
    assert not eng.stats()["replanner"]["running"]


# -- SSM fallback + bucket ladder helpers ------------------------------------

def test_ssm_stack_disables_bucketing():
    cfg = configs.smoke("mamba2_130m")
    assert not _pad_safe(cfg)
    eng = _stub_engine(lm=StubLM(cfg), buckets=(4, 8, 16))
    assert eng.buckets == () and not eng.pad_safe
    req = _req([1, 2, 3], max_new_tokens=3)
    eng.run([req])
    assert req.bucket is None and req.output == [4, 5, 6]
    assert eng.stats()["admission"]["exact_prefills"] == 1


def test_auto_buckets_cover_and_end_at_top():
    shapes = _stack_shapes(configs.get("llama3_2_1b"))
    for frac in (0.25, 0.5, 0.75):
        ladder = _auto_buckets(511, shapes, frac)
        assert ladder[-1] == 511
        assert list(ladder) == sorted(set(ladder))
        # below the top, only lengths the kernels tile without padding
        assert all(dim_tile(b)[1] == b for b in ladder[:-1])
    # tighter waste budget => at least as many buckets
    assert len(_auto_buckets(511, shapes, 0.25)) >= \
        len(_auto_buckets(511, shapes, 0.75))
    assert _auto_buckets(8, shapes, 0.5) == (8,)


# -- block-sparse weights packed once, at engine start -----------------------

def _bsffn_engine(**kw):
    """A smoke-size qwen2 with the block-sparse FFN on the Pallas routes
    (interpreted), as the engine runs it on a TPU."""
    sparse_api.reset()
    cfg = configs.sparse_ffn(configs.smoke("qwen2_1_5b"), 0.125)
    lm = LM(cfg)
    params = lm.init(jax.random.PRNGKey(0))
    ctx = dispatch.DispatchContext(allow_pallas=True, interpret=True,
                                   differentiable=False)
    return Engine(lm, params, batch=2, max_len=32, dispatch_ctx=ctx,
                  **kw), params


def _decode_logits(eng, params):
    tokens = jnp.asarray([[3], [5]], jnp.int32)
    positions = jnp.asarray([4, 7], jnp.int32)
    logits, _ = eng._decode(params, tokens, eng.caches, positions)
    return np.asarray(logits, np.float32)


def _routes(eng):
    return {p.route for p in sparse_api.pool_plans(eng.pool)
            if p.spec.kind == "static"}


def test_engine_serves_packed_tiles():
    eng, params = _bsffn_engine()
    cfg = eng.lm.cfg
    layers = sum(rep for _, rep in cfg.groups)
    st = eng.stats()
    assert st["packed_matrices"] == 3 * layers
    tiles = [p["ffn"][n]["packed"].tiles for group in eng.params["stack"]
             for p in group for n in ("up", "down", "gate")]
    assert st["packed_bytes"] == sum(t.nbytes for t in tiles) > 0
    assert eng.plan_report()["engine"]["packed_matrices"] == 3 * layers
    assert _routes(eng) <= set(sparse_api.PACKED_ROUTES)
    # the served tree adds the tiles; the caller's params keep their layout
    assert jax.tree.structure(params) == jax.tree.structure(
        eng.lm.init(jax.random.PRNGKey(1)))
    np.testing.assert_array_equal(_decode_logits(eng, eng.params),
                                  _decode_logits(eng, params))


def test_dense_engine_packs_nothing():
    sparse_api.reset()
    lm = LM(configs.smoke("qwen2_1_5b"))
    params = lm.init(jax.random.PRNGKey(0))
    eng = Engine(lm, params, batch=2, max_len=32)
    st = eng.stats()
    assert (st["packed_matrices"], st["packed_bytes"]) == (0, 0)
    assert jax.tree.structure(eng.params) == jax.tree.structure(params)


def test_engine_off_the_bsmm_routes_decodes_from_the_values(monkeypatch):
    want = _decode_logits(*_bsffn_engine())
    layers = SparseFFN._layers
    monkeypatch.setattr(SparseFFN, "_layers", lambda self: tuple(
        None if lay is None else dataclasses.replace(lay, backend="static_xla")
        for lay in layers(self)))
    eng, params = _bsffn_engine()
    assert _routes(eng) == {"static_xla"}
    assert eng.stats()["packed_matrices"] > 0
    got = _decode_logits(eng, eng.params)
    np.testing.assert_array_equal(got, _decode_logits(eng, params))
    np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-2)
    req = _req([1, 2, 3, 4, 5], max_new_tokens=3)
    eng.run([req])
    assert req.done and len(req.output) == 3


# ===========================================================================
# model-level (slow tier): parity + continuous batching on a real LM
# ===========================================================================

def _setup():
    cfg = configs.smoke("llama3_2_1b")
    lm = LM(cfg)
    params = lm.init(jax.random.PRNGKey(0))
    return cfg, lm, params


def _manual_generate(lm, params, prompt, n, max_len):
    logits, caches = lm.prefill(params, prompt[None], max_len=max_len)
    out = [int(jnp.argmax(logits[0]))]
    pos = prompt.shape[0]
    for _ in range(n - 1):
        lg, caches = lm.decode_step(
            params, jnp.asarray([[out[-1]]], jnp.int32), caches,
            jnp.asarray([pos], jnp.int32))
        out.append(int(jnp.argmax(lg[0])))
        pos += 1
    return out


@pytest.mark.slow
def test_engine_matches_manual_decode():
    """Bucketed (padded) prefill must reproduce exact-length decode:
    the engine pads the 12-token prompt to a bucket, yet the gathered
    last-token logits and masked decode see only real tokens."""
    cfg, lm, params = _setup()
    prompt = jax.random.randint(jax.random.PRNGKey(1), (12,), 0,
                                cfg.vocab_size).astype(jnp.int32)
    want = _manual_generate(lm, params, prompt, 6, max_len=64)
    eng = Engine(lm, params, batch=2, max_len=64, buckets=(16, 32))
    req = Request(uid=0, prompt=np.asarray(prompt), max_new_tokens=6)
    eng.run([req])
    assert req.bucket == 16
    assert req.output[:6] == want


@pytest.mark.slow
def test_engine_continuous_batching():
    cfg, lm, params = _setup()
    reqs = []
    for i in range(5):       # more requests than the batch has slots
        prompt = jax.random.randint(jax.random.PRNGKey(i), (8 + i,), 0,
                                    cfg.vocab_size).astype(jnp.int32)
        reqs.append(Request(uid=i, prompt=np.asarray(prompt),
                            max_new_tokens=4 + i))
    eng = Engine(lm, params, batch=2, max_len=64)
    done = []
    eng.run(reqs, on_finish=lambda r: done.append(r.uid))
    assert sorted(done) == [0, 1, 2, 3, 4]
    for r in reqs:
        assert r.done and len(r.output) == r.max_new_tokens

    # slot isolation: rerun one of the requests alone -> same output
    solo = Request(uid=9, prompt=reqs[3].prompt,
                   max_new_tokens=reqs[3].max_new_tokens)
    eng2 = Engine(lm, params, batch=2, max_len=64)
    eng2.run([solo])
    assert solo.output == reqs[3].output
