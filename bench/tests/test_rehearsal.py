"""The harness end to end on the CPU at the smoke size of qwen2-1.5b and
its block-sparse twin (Pallas kernels interpreted), in every cell: the
result line,
the engine driven through ``submit`` and ``serve`` alone, ``correct``
false when the timed path is broken underneath, and no result off a
TPU."""
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

import run
from conftest import BENCH, ROOT
from repro import configs
from repro.models.model import LM
from repro.serve import Engine

CLOSED = {"loop": "closed", "clients": 4, "pool": 64,
          "prompt_len": {"dist": "lognormal", "median": 16, "sigma": 0.5,
                         "min": 8, "max": 40},
          "output_len": {"dist": "uniform", "min": 6, "max": 12},
          "warmup": {"finished": 2}, "trace_seconds": 1,
          "check": {"requests": 3}}
OPEN = {"loop": "open", "rate_per_s": 4.0,
        "prompt_len": {"dist": "lognormal", "median": 24, "sigma": 0.5,
                       "min": 8, "max": 50},
        "output_len": {"dist": "uniform", "min": 2, "max": 6},
        "warmup": {"seconds": 0.5}, "trace_seconds": 1,
        "check": {"requests": 4}}
SEED = 2**31 + 12345
CELLS = ("bsffn-decode", "dense-decode", "bsffn-prefill")
# the smoke-size mix of each cell
MIXES = {"bsffn-decode": CLOSED, "dense-decode": CLOSED,
         "bsffn-prefill": OPEN}
# Limits at the smoke size, where logits are small (d_model 64): sound
# runs read a widest gap of 0.0 to 0.006 and the float8 control 0.04 to
# 0.05; a short CPU window finishes a few tens of tokens.
SMOKE_LIMITS = {"logit_gap_max": {"limit": 0.02},
                "checked_tokens": {"limit": 10}}


def smoke(workload, mix):
    """The resolved cell with the smoke sizes of its configuration."""
    r = run.resolve(workload)
    c = dict(r["config"])
    m = configs.smoke(c["program"]["preset"])
    c.update(hidden_size=m.d_model, intermediate_size=m.d_ff,
             num_hidden_layers=m.num_layers, num_attention_heads=m.num_heads,
             num_key_value_heads=m.num_kv_heads, vocab_size=m.vocab_size,
             deployment={"chips": 1, "batch": 4, "max_len": 64})
    if c.get("sparse_ffn"):
        m = configs.sparse_ffn(m, c["sparse_ffn"]["density"])
    r.update(config=c, mix=mix)
    r["limits"] = SMOKE_LIMITS
    return r, m


def one_run(workload, mix, trace=False):
    r, m = smoke(workload, mix)
    rec = run.run_cell(r, SEED, 3.0, trace, require_tpu=False, model_cfg=m)
    return r, rec, run.result(r, rec, trace)


@pytest.fixture
def spy(monkeypatch):
    """Records every call of the engine's entry points with its caller."""
    calls = []

    def wrap(name, fn):
        def spied(self, *a, **k):
            frames, f = [], sys._getframe(1)
            while f is not None:
                frames.append((os.path.basename(f.f_code.co_filename),
                               f.f_code.co_name))
                f = f.f_back
            calls.append((name, frames))
            return fn(self, *a, **k)
        return spied
    for name in ("submit", "serve", "run", "admit", "step"):
        monkeypatch.setattr(Engine, name, wrap(name, getattr(Engine, name)))
    return calls


def test_closed_loop_result_line(spy):
    r, rec, out = one_run("bsffn-decode", CLOSED)
    assert list(out)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(out)[-1] == "checks"
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    # the gaps' 95th percentile is read per layer, in the traced run
    assert set(out["metrics"]) == {"tok_per_s", "setup_s"}
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert out["device"]["platform"] == "cpu"
    assert out["compiles_in_window"] == 0
    json.dumps(out)
    # the harness calls submit and serve; admit and step run only from
    # inside the engine's own serve loop
    names = {n for n, _ in spy}
    assert {"submit", "serve", "admit", "step"} <= names
    assert "run" not in names
    for name, frames in spy:
        if name in ("admit", "step"):
            assert ("engine.py", "serve") in frames, frames[:4]
        else:
            assert frames[0][0] == "run.py", frames[:4]


def test_open_loop_traced_result_line():
    r, rec, out = one_run("bsffn-prefill", OPEN, trace=True)
    assert out["correct"] is True, out["checks"]
    assert rec["admits"] and rec["attempted"] > 0
    # no device plane and no peaks on the CPU: the host-clock metrics
    # read, the others read nothing there
    assert rec["steps"]
    assert set(out["metrics"]) == {"engine.admit_ms.prefill",
                                   "engine.step_ms.decode"}
    # the decode steps between admissions are read as in the decode cells
    assert {m["name"] for m in r["per_layer"]} == {
        "engine.admit_ms.prefill", "mfu.prefill",
        "kernel.bsmm_roofline.prefill", "engine.step_ms.decode",
        "mfu.decode",
        "kernel.bsmm_roofline.decode", "kernel.dense_mm_roofline.decode",
        "device.idle_share.decode", "model.ffn_ms.decode",
        "model.attn_ms.decode", "engine.step_idle_ms.decode"}
    # the share of the peak, were the CPU a TPU v5e
    v5e = run.peaks_lib.peaks_for("TPU v5 lite")
    assert 0 < run.metric_reader("mfu.prefill")(dict(rec, peaks=v5e)) < 100
    assert {"busy_s", "window_s"} <= set(out["device"])
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
    assert rec["requests"][0]["due"] <= rec["requests"][-1]["due"]


def test_open_loop_result_line():
    r, rec, out = one_run("bsffn-prefill", OPEN)
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {m["name"] for m in r["end_to_end"]} == {
        "tok_per_s", "setup_s"}
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert out["compiles_in_window"] == 0


def _token_altered(monkeypatch, vocab):
    real = Engine._next_tokens
    monkeypatch.setattr(Engine, "_next_tokens", staticmethod(
        lambda logits: (real(logits) + 1) % vocab))


def _state_unchanged(monkeypatch, vocab):
    real = LM.decode_step

    def stale(self, params, tokens, caches, positions, **kw):
        logits, _ = real(self, params, tokens, caches, positions, **kw)
        return logits, caches
    monkeypatch.setattr(LM, "decode_step", stale)


def _half_batch_left_out(monkeypatch, vocab):
    real = LM.decode_step

    def half(self, params, tokens, caches, positions, **kw):
        logits, caches = real(self, params, tokens, caches, positions, **kw)
        b = logits.shape[0] // 2
        return logits.at[b:].set(logits[:b]), caches
    monkeypatch.setattr(LM, "decode_step", half)


@pytest.mark.parametrize("workload", CELLS)
@pytest.mark.parametrize("fault", [_token_altered, _state_unchanged,
                                   _half_batch_left_out],
                         ids=lambda f: f.__name__.strip("_"))
def test_broken_timed_path_is_not_correct(monkeypatch, workload, fault):
    fault(monkeypatch, configs.smoke("qwen2_1_5b").vocab_size)
    _, rec, out = one_run(workload, MIXES[workload])
    assert rec["checked_tokens"] >= SMOKE_LIMITS["checked_tokens"]["limit"]
    assert out["correct"] is False, out["checks"]


@pytest.mark.parametrize("workload", CELLS)
def test_float8_control_is_not_correct(workload):
    """The reference in float8 in the program's place fails the limit."""
    r, m = smoke(workload, MIXES[workload])
    rec = run.run_cell(r, SEED, 3.0, False, require_tpu=False, model_cfg=m,
                       control=True)
    assert run.is_correct(run.checks(rec, SMOKE_LIMITS))
    as_program = dict(rec, gap_max=rec["control_gap_max"])
    assert not run.is_correct(run.checks(as_program, SMOKE_LIMITS))


def _cli(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "bsffn-decode",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(p):
    assert p.returncode != 0
    for line in p.stdout.splitlines():
        assert not line.lstrip().startswith("{"), line


def test_off_tpu_exits_without_result():
    p = _cli(ROOT)
    _no_result(p)
    assert "needs a TPU" in p.stderr


def test_benchmark_alone_exits_without_result(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    _no_result(_cli(tmp_path))


def test_same_sizes_every_seed():
    mix = dict(OPEN, rate_per_s=5.0)
    a = run.traffic.build(mix, 1, 10.0, 512)
    b = run.traffic.build(mix, 2**33 + 5, 10.0, 512)
    assert sorted(len(s.prompt) for s in a) == sorted(len(s.prompt)
                                                      for s in b)
    assert sorted(s.max_new for s in a) == sorted(s.max_new for s in b)
    assert np.isclose(max(s.due for s in a), max(s.due for s in b))
    assert [len(s.prompt) for s in a] != [len(s.prompt) for s in b]


def test_closed_loop_first_fill_spans_every_stratum():
    mix = dict(CLOSED, pool=64)
    for seed in (3, 2**35 + 1):
        specs = run.traffic.build(mix, seed, 10.0, 512)
        for block in range(0, 64, 4):
            olens = sorted(s.max_new for s in specs[block:block + 4])
            strata = np.sort(run.traffic.quantiles(CLOSED["output_len"],
                                                   64)).reshape(4, 16)
            assert all(lo <= v <= hi for v, lo, hi in zip(
                olens, strata[:, 0], strata[:, -1]))
