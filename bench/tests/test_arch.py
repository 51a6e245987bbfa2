"""A configuration's own modules: the program held to the sizes that its
work module reads from the file, the readers counting work through that
module, the prefill readers on hand-made records, and the prefill mix
dealt alike on every seed."""
import json
import os

import numpy as np
import pytest

import arch
import peaks
import run
import trace_reduce as tr
import trace_scopes as ts
import work
from conftest import BENCH
from repro import configs

V5E = peaks.PEAKS["TPU v5 lite"]
STATIC = ("static_pallas", "static_balanced")


def config(path):
    with open(os.path.join(BENCH, path)) as f:
        return json.load(f)


DENSE = config("configs/qwen2-1.5b.json")
SPARSE = config("configs/qwen2-1.5b-bsffn.json")
MLA = config("tests/deepseek-v2-lite-smoke.json")


# ---------------------------------------------------------------------------
# the program held to the configuration's sizes
# ---------------------------------------------------------------------------

QWEN2_1_5B = {"d_model": 1536, "num_layers": 28, "num_heads": 12,
              "num_kv_heads": 2, "head_dim": 128, "d_ff": 8960,
              "vocab_size": 151936, "rope_theta": 1e6, "norm_eps": 1e-6,
              "tie_embeddings": True, "dtype": "bfloat16", "qkv_bias": True,
              "act": "silu"}


@pytest.mark.parametrize("c, want", [
    (DENSE, configs.get("qwen2_1_5b")),
    (SPARSE, configs.sparse_ffn(configs.get("qwen2_1_5b"), 0.125)),
], ids=["qwen2-1.5b", "qwen2-1.5b-bsffn"])
def test_program_model_unchanged(c, want):
    cfg = run.program_model(c)
    assert cfg == want
    assert {k: getattr(cfg, k) for k in QWEN2_1_5B} == QWEN2_1_5B
    assert arch.load(c["work"]).program_sizes(c) == dict(
        QWEN2_1_5B, **({"ffn_block_size": 16} if c is SPARSE else {}))


def test_head_width_not_hidden_over_heads(monkeypatch):
    """A DeepSeek-V2 configuration at the sizes of the program's smoke
    preset, whose head width is not hidden / heads, passes through its
    own work module; a size that departs still raises."""
    monkeypatch.setattr(configs, "get", configs.smoke)
    assert MLA["hidden_size"] // MLA["num_attention_heads"] == 32
    cfg = run.program_model(MLA)
    assert cfg == configs.smoke("deepseek_v2_lite_16b")
    assert cfg.head_dim == 48 and cfg.moe.num_experts == 4
    for key, v in (("qk_rope_head_dim", 8), ("n_routed_experts", 8),
                   ("hidden_size", 256)):
        with pytest.raises(ValueError, match="departs"):
            run.program_model(dict(MLA, **{key: v}))


def test_qwen2_sizes_refuse_the_mla_preset(monkeypatch):
    """Held to Qwen2's sizes (head width hidden / heads), the same
    preset departs."""
    monkeypatch.setattr(configs, "get", configs.smoke)
    with pytest.raises(ValueError, match="departs"):
        run.program_model(dict(MLA, work="bench/work.py", qkv_bias=False))


def test_loader_loads_once_by_path():
    """One module per file: a module beside the harness is the one that
    ``import`` gives (``bench/reference.py`` imports ``work``)."""
    import reference
    assert arch.load(DENSE["work"]) is arch.load(SPARSE["work"]) \
        is arch.load(os.path.join(BENCH, "work.py")) is work
    assert arch.load(DENSE["reference"]) is reference
    assert reference.work is work
    mla = arch.load(MLA["work"])
    assert mla is arch.load("bench/tests/deepseek_v2_lite_work.py")
    assert mla.__name__ == "bench_deepseek_v2_lite_work"


# ---------------------------------------------------------------------------
# the readers count work through the configuration's module
# ---------------------------------------------------------------------------

def dev(line, name, t0, t1):
    return {"dev": 0, "line": line, "name": name, "t0": t0, "dur": t1 - t0}


def host(name, t0, t1):
    return {"name": name, "t0": t0, "dur": t1 - t0, "thread": "python"}


D, FF, KV = 1536, 8960, 256


def plans(n):
    return [{"route": "static_pallas", "kind": "static", "shape": (FF, D, n)},
            {"route": "static_balanced", "kind": "static",
             "shape": (FF, D, n)},
            {"route": "static_pallas", "kind": "static", "shape": (D, FF, n)},
            {"route": "dense_pallas", "kind": "dense", "shape": (D, D, n)},
            {"route": "dense_pallas", "kind": "dense", "shape": (KV, D, n)},
            {"route": "dense_pallas", "kind": "dense", "shape": (FF, D, n)},
            {"route": "dense_pallas", "kind": "dense", "shape": (D, FF, n)}]


# one traced second: two decode programs and two prefill programs, each
# with its kernels (times in ns)
EVENTS = {
    "device": [
        dev(tr.MODULES, "jit_decode_fn(1)", 0, 30e6),
        dev(tr.OPS, "%bsmm_call.8 = bf16[] custom-call()", 1e6, 12e6),
        dev(tr.OPS, "%dense_mm_call.3 = bf16[] custom-call()", 12e6, 14e6),
        dev(tr.MODULES, "jit_decode_fn(1)", 40e6, 70e6),
        dev(tr.OPS, "%bsmm_balanced_call.4 = bf16[] custom-call()", 41e6,
            52e6),
        dev(tr.OPS, "%dense_mm_call.3 = bf16[] custom-call()", 52e6, 54e6),
        dev(tr.MODULES, "jit_prefill_fn(2)", 100e6, 380e6),
        dev(tr.OPS, "%bsmm_call.9 = bf16[] custom-call()", 110e6, 250e6),
        dev(tr.MODULES, "jit_prefill_fn(3)", 500e6, 800e6),
        dev(tr.OPS, "%bsmm_call.9 = bf16[] custom-call()", 510e6, 680e6),
    ],
    "host": [host(tr.WINDOW, 0, 1e9), host("bench.step", 0, 35e6),
             host("bench.step", 38e6, 72e6), host("bench.admit", 90e6, 420e6),
             host("bench.admit", 480e6, 830e6)],
}


def sdev(line, name, t0, t1):
    return dict(dev(line, name, t0, t1), scope=None)


def span(name, t0, t1, **args):
    return {"name": name, "t0": t0, "dur": t1 - t0, "args": args}


# the same second as ``trace_scopes`` reads it, with the engine's spans:
# besides the two prefills inside it, one that began before the window
# and one that the window's end cuts, which count on neither side
SCOPE_EVENTS = {
    "device": [sdev(e["line"], tr.short(e["name"]), e["t0"],
                    e["t0"] + e["dur"]) for e in EVENTS["device"]] + [
        sdev(tr.MODULES, "jit_prefill_fn(2)", -200e6, 60e6),
        sdev(tr.OPS, "bsmm_call.9", -190e6, 50e6),
        sdev(tr.MODULES, "jit_prefill_fn(3)", 950e6, 1250e6),
        sdev(tr.OPS, "bsmm_call.9", 960e6, 1100e6),
    ],
    "host": [span(tr.WINDOW, 0, 1e9),
             span("engine.admit", -210e6, 80e6, bucket=1920),
             span("engine.admit", 90e6, 420e6, bucket=1920),
             span("engine.admit", 480e6, 830e6, bucket=2048),
             span("engine.admit", 940e6, 1300e6, bucket=2048)],
}


def record(c, **kw):
    """A run record as the readers see it: the window from 0 to 10 s, its
    first second traced."""
    ctx = list(range(100, 132))
    rec = {"config": c, "peaks": V5E, "window": (0.0, 10.0),
           "traced": (0.0, 1.0), "trace": tr.reduce(EVENTS),
           "trace_scopes": ts.reduce(SCOPE_EVENTS), "batch": 32,
           "plans": plans(32) + plans(1920) + plans(2048),
           "steps": [(0.0, 0.035, ctx), (0.038, 0.072, ctx),
                     (2.0, 2.031, ctx[:20]), (11.0, 11.03, ctx)],
           "admits": [(0.09, 0.42, 700, 1920), (0.48, 0.83, 1950, 2048),
                      (3.0, 3.41, 300, 1920), (10.5, 10.9, 900, 1920)]}
    rec.update(kw)
    return rec


def test_mfu_decode_unchanged():
    for c in (DENSE, SPARSE):
        rec = record(c)
        bound = spent = 0.0
        for a, b, ctx in rec["steps"][:3]:
            bound += work.roofline_s(*work.decode_step(c, ctx), V5E)
            spent += b - a
        assert run.metric_reader("mfu.decode")(rec) == 100.0 * bound / spent


@pytest.mark.parametrize("metric, routes, ops, configs_", [
    ("kernel.bsmm_roofline.decode", STATIC, ("bsmm",), (SPARSE,)),
    ("kernel.dense_mm_roofline.decode", ("dense_pallas",),
     ("dense_mm_call", "_mm_kernel"), (DENSE, SPARSE)),
])
def test_decode_rooflines_unchanged(metric, routes, ops, configs_):
    for c in configs_:
        rec = record(c)
        bound = sum(work.kernel_bound_s(c, rec["plans"], 32, routes, V5E)
                    for _ in range(2))
        _, spent = tr.kernel_time(rec["trace"], "decode", ops)
        assert spent > 0
        assert run.metric_reader(metric)(rec) == 100.0 * bound / spent


# ---------------------------------------------------------------------------
# the prefill readers
# ---------------------------------------------------------------------------

PREFILL = ("engine.admit_ms.prefill", "mfu.prefill",
           "kernel.bsmm_roofline.prefill")


def read(metric, rec):
    return run.metric_reader(metric)(rec)


def test_admit_ms_prefill():
    # the three admissions that start in the window: 330, 350 and 410 ms
    assert read("engine.admit_ms.prefill", record(SPARSE)) == \
        pytest.approx(1e3 * (0.33 + 0.35 + 0.41) / 3)


def test_mfu_prefill_counts_true_prompt_tokens():
    c = SPARSE
    got = read("mfu.prefill", record(c))
    bound = sum(work.roofline_s(*work.prefill(c, n), V5E)
                for n in (700, 1950, 300))
    assert got == pytest.approx(100.0 * bound / (0.33 + 0.35 + 0.41))
    # 300 tokens over a 1920 bucket: far under the peak, and the padding
    # counts for nothing
    assert 0 < got < 2
    padded = [(a, b, bucket, bucket) for a, b, _, bucket in
              record(c)["admits"]]
    assert read("mfu.prefill", record(c, admits=padded)) > got


def test_bsmm_roofline_prefill():
    c = SPARSE
    rec = record(c)
    got = read("kernel.bsmm_roofline.prefill", rec)
    # the two prefill programs that ran whole in the traced second, at
    # their admissions' buckets, over their block-sparse kernels
    bound = sum(work.kernel_bound_s(c, rec["plans"], n, STATIC, V5E)
                for n in (1920, 2048))
    assert got == pytest.approx(100.0 * bound / 0.31)
    assert [n for n, _ in rec["trace_scopes"]["prefills"]] == [1920, 2048]
    # one call at n = 1920 moves the stored blocks, the input and the
    # output once: bound by bandwidth
    f, b = work.matmul_call(FF, D, 1920, 6720, 16)
    assert b / V5E["bytes_per_s"] > f / V5E["flops_per_s"]
    assert 0 < got < 100


def test_bsmm_roofline_prefill_cut_by_the_window():
    """A prefill that the traced window's end cuts moves neither the
    bound nor the time: the share reads as without it."""
    c = SPARSE
    whole = read("kernel.bsmm_roofline.prefill", record(c))
    cut = dict(SCOPE_EVENTS, device=SCOPE_EVENTS["device"][:-2])
    assert read("kernel.bsmm_roofline.prefill", record(
        c, trace_scopes=ts.reduce(cut))) == whole
    # a prefill held by no admission span is counted on neither side
    bare = dict(SCOPE_EVENTS, host=[h for h in SCOPE_EVENTS["host"]
                                    if h["t0"] != 480e6])
    bound = work.kernel_bound_s(c, record(c)["plans"], 1920, STATIC, V5E)
    assert read("kernel.bsmm_roofline.prefill", record(
        c, trace_scopes=ts.reduce(bare))) == pytest.approx(
        100.0 * bound / 0.14)


@pytest.mark.parametrize("metric", PREFILL)
def test_prefill_readers_read_nothing_without_admissions(metric):
    no_prefill = ts.reduce(dict(SCOPE_EVENTS, device=[
        e for e in SCOPE_EVENTS["device"]
        if not (e["t0"] >= 100e6 or e["t0"] < 0)]))
    assert no_prefill["prefills"] == []
    assert read(metric, record(SPARSE, admits=[],
                               trace_scopes=no_prefill)) is None
    late = [(a + 20.0, b + 20.0, n, k)
            for a, b, n, k in record(SPARSE)["admits"]]
    assert read(metric, record(SPARSE, admits=late,
                               trace_scopes=no_prefill)) is None


def test_prefill_readers_without_trace_or_peaks():
    untraced = record(SPARSE, trace=None, traced=None)
    assert read("kernel.bsmm_roofline.prefill", untraced) is None
    assert read("engine.admit_ms.prefill", untraced) is not None
    off_chip = record(SPARSE, peaks=None)
    assert read("mfu.prefill", off_chip) is None
    assert read("kernel.bsmm_roofline.prefill", off_chip) is None
    # the dense configuration plans no block-sparse route
    assert read("kernel.bsmm_roofline.prefill", record(DENSE)) is None


# ---------------------------------------------------------------------------
# the prefill mix
# ---------------------------------------------------------------------------

def test_prefill_mix_same_lengths_same_order():
    mix = run.traffic.load(os.path.join(BENCH, "traffic",
                                        "prefill-heavy.json"))
    a = run.traffic.build(mix, 2**31 + 7, 30.0, 151936)
    b = run.traffic.build(mix, 4_100_000_003, 30.0, 151936)
    assert [len(s.prompt) for s in a] == [len(s.prompt) for s in b]
    assert [s.max_new for s in a] == [s.max_new for s in b]
    # the same gaps between arrivals, in the same order
    assert [s.due for s in a] == [s.due for s in b]
    # without ``arrival_gap`` in ``same_order``: the same gaps, in the
    # seed's own order
    mix = dict(mix, same_order=["prompt_len", "output_len"])
    a, b = (run.traffic.build(mix, seed, 30.0, 151936)
            for seed in (2**31 + 7, 4_100_000_003))
    gaps = [np.diff([0.0] + [s.due for s in x]) for x in (a, b)]
    assert np.sort(gaps[0]) == pytest.approx(np.sort(gaps[1]))
    assert not np.allclose(gaps[0], gaps[1])
    assert any((x.prompt != y.prompt).any() for x, y in zip(a, b))
    assert all(256 <= len(s.prompt) <= 2000 and 8 <= s.max_new <= 32
               for s in a)


def test_checked_requests_closed_and_open_loop():
    """A closed loop checks requests finished in the window; an open loop
    those due in it, once finished (the run waits for them after the
    close)."""
    from types import SimpleNamespace as NS

    def req(due, done, n):
        return {"due": due, "done_at": done,
                "req": NS(prompt=[0] * n, output=[1] * 4)}
    reqs = [req(0.5, 2.0, 10),     # due before the window, done in it
            req(3.0, 9.0, 20),     # due and done in it
            req(8.0, 12.0, 30),    # due in it, done after the close
            req(9.0, None, 40)]    # due in it, never done
    pick = {False: run.sample_checked(reqs, (1.0, 10.0), 4, 7),
            True: run.sample_checked(reqs, (1.0, 10.0), 4, 7, by_due=True)}
    assert [r["due"] for r in pick[False]] == [3.0, 0.5]
    assert [r["due"] for r in pick[True]] == [8.0, 3.0]
