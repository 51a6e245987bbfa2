"""The trace reduction on hand-made events, on one decode step recorded
on a TPU v5e (``bench/testdata``), and the loader on a trace recorded on
the CPU (host spans only: the CPU has no device plane)."""
import json
import os

import pytest

from conftest import BENCH

import trace_reduce as tr

NS = 1e-9


def ev(line, name, t0, t1, dev=0):
    return {"dev": dev, "line": line, "name": name, "t0": t0, "dur": t1 - t0}


def host(name, t0, t1):
    return {"name": name, "t0": t0, "dur": t1 - t0, "thread": "python"}


EVENTS = {
    "device": [
        ev(tr.MODULES, "jit_decode_fn", 5, 45),
        ev(tr.OPS, "fusion.1", 10, 20),
        ev(tr.OPS, "bsmm_kernel", 15, 25),       # overlaps the fusion
        ev(tr.MODULES, "jit_prefill_fn", 48, 70),
        ev(tr.OPS, "fusion.1", 50, 60),
        ev(tr.OPS, "copy.2", 95, 110),           # runs past the window
    ],
    "host": [host(tr.WINDOW, 0, 100), host("bench.step", 0, 30),
             host("bench.admit", 40, 70), host("bench.wait", 70, 100)],
}


def test_busy_union_and_idle_share():
    red = tr.reduce(EVENTS)
    assert red["window_s"] == pytest.approx(100 * NS)
    # [10, 25] + [50, 60] + [95, 100]
    assert red["busy_s"] == pytest.approx(30 * NS)
    assert red["idle_share"] == pytest.approx(0.7)


def test_sums_by_operation_and_program():
    red = tr.reduce(EVENTS)
    assert red["ops"]["fusion.1"] == (2, pytest.approx(20 * NS))
    assert red["ops"]["copy.2"] == (1, pytest.approx(5 * NS))
    assert tr.kernel_time(red, "decode", ("bsmm",)) == (
        1, pytest.approx(10 * NS))
    assert tr.kernel_time(red, "prefill", ("bsmm",)) == (0, 0.0)
    assert tr.kernel_time(red, "", ("fusion",)) == (
        2, pytest.approx(20 * NS))
    assert red["by_module"]["(none)"]["copy.2"][0] == 1


def test_gaps_by_host_span():
    red = tr.reduce(EVENTS)
    # gaps [0, 10] (in the step), [25, 50] (between spans), [60, 95]
    # (its middle in the wait)
    assert red["idle_by_span"] == {
        "bench.step": (1, pytest.approx(10 * NS)),
        tr.OTHER: (1, pytest.approx(25 * NS)),
        "bench.wait": (1, pytest.approx(35 * NS))}
    b = tr.breakdown(red)
    assert [k for k, _ in b["idle_gaps"]] == ["bench.wait", tr.OTHER,
                                             "bench.step"]
    assert b["device_ops"][0][0] == "fusion.1"


def test_breakdown_ranks_own_time_under_short_names():
    # a TPU names an operation by its HLO text; the scan over layers is a
    # loop operation that holds the operations of its body
    red = tr.reduce({
        "device": [
            ev(tr.MODULES, "jit_decode_fn", 0, 100),
            ev(tr.OPS, "%while.1 = (s32[], bf16[8]) while(%tuple.3)", 0, 90),
            ev(tr.OPS, "%fusion.7 = bf16[8] fusion(bf16[8] %p)", 10, 40),
            ev(tr.OPS, "%dense_mm_call.3 = bf16[8] custom-call(%f)", 40, 80),
        ],
        "host": [host(tr.WINDOW, 0, 100)]})
    assert red["busy_s"] == pytest.approx(90 * NS)
    assert tr.breakdown(red)["device_ops"] == [
        ["dense_mm_call.3", pytest.approx(40 * NS)],
        ["fusion.7", pytest.approx(30 * NS)],
        ["while.1", pytest.approx(20 * NS)]]
    assert tr.kernel_time(red, "decode", ("dense_mm_call",)) == (
        1, pytest.approx(40 * NS))


def recorded_step():
    with open(os.path.join(BENCH, "testdata", "bsffn-decode-step.json")) as f:
        d = json.load(f)
    return {
        "device": [{"dev": dev, "line": line, "name": name, "t0": float(t0),
                    "dur": float(dur)} for dev, line, name, t0, dur
                   in d["device"]],
        "host": [{"name": name, "t0": float(t0), "dur": float(dur),
                  "thread": "python"} for name, t0, dur in d["host"]]}


def test_recorded_decode_step_names_the_kernels():
    """The names the kernel readers match, as a TPU v5e reports them: the
    decode program ``jit_decode_fn(...)``, the block-sparse FFN's
    ``bsmm_call.<n>`` (down and one of up/gate) and
    ``bsmm_balanced_call.<n>`` (the other), once per layer, and the four
    attention projections' ``dense_mm_call.<n>``."""
    red = tr.reduce(recorded_step())
    assert [m for m in red["by_module"] if "decode" in m] == [
        "jit_decode_fn(16014853037321776486)"]
    calls = {}
    for name, (n, _) in red["ops"].items():
        kind = name.rsplit(".", 1)[0]
        calls[kind] = calls.get(kind, 0) + n
    layers = 28
    assert calls["bsmm_call"] == 2 * layers
    assert calls["bsmm_balanced_call"] == layers
    assert calls["dense_mm_call"] == 4 * layers
    n, sec = tr.kernel_time(red, "decode", ("bsmm",))
    assert n == 3 * layers and sec == pytest.approx(0.011381619)
    n, sec = tr.kernel_time(red, "decode", ("dense_mm_call",))
    assert n == 4 * layers and sec == pytest.approx(0.001283845)
    # the step is busy but for 0.2 ms between programs
    assert red["window_s"] == pytest.approx(0.143614256)
    assert red["idle_share"] == pytest.approx(0.0013946, abs=1e-6)
    # the per-call relayout of the block-sparse values leads the step
    top = tr.breakdown(red)["device_ops"]
    assert [k for k, _ in top[:3]] == ["fusion.76", "fusion.75", "fusion.90"]
    assert top[0][1] == pytest.approx(0.015298869)


def test_loader_reads_host_spans_of_a_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(tr.WINDOW):
        with jax.profiler.TraceAnnotation("bench.step"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    events = tr.load(str(tmp_path))
    names = sorted(h["name"] for h in events["host"])
    assert names == ["bench.step", tr.WINDOW]
    w, s = sorted(events["host"], key=lambda h: h["name"] != tr.WINDOW)
    assert w["t0"] <= s["t0"] and s["t0"] + s["dur"] <= w["t0"] + w["dur"]

