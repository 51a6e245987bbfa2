"""Tracing inside the program: the named scopes of the model's compiled
programs, the serving engine's host spans in a profiler trace, and the
benchmark's reduction of both (``bench/trace_scopes.py``) on hand-made
events and on decode steps recorded on a TPU v5e (``bench/testdata``)."""
import collections
import contextlib
import functools
import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.core import dispatch
from repro.models import transformer as tfm
from repro.models.model import LM
from repro.serve import Engine, Request

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "bench")
for _p in (BENCH, os.path.join(BENCH, "metrics")):
    if _p not in sys.path:
        sys.path.append(_p)

import run  # noqa: E402
import trace_reduce  # noqa: E402
import trace_scopes as ts  # noqa: E402

NEW_METRICS = ("model.ffn_ms.decode", "model.attn_ms.decode",
               "kernel.relayout_ms.decode", "engine.step_idle_ms.decode")


# ---------------------------------------------------------------------------
# the scopes in the compiled programs
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def sparse_engine():
    """A smoke-size qwen2 with the block-sparse FFN on the Pallas routes
    (interpreted), as the engine runs it on a TPU."""
    cfg = configs.sparse_ffn(configs.smoke("qwen2_1_5b"), 0.125)
    lm = LM(cfg)
    ctx = dispatch.DispatchContext(allow_pallas=True, interpret=True,
                                   differentiable=False)
    return Engine(lm, lm.init(jax.random.PRNGKey(0)), batch=2, max_len=32,
                  dispatch_ctx=ctx)


def _compiled(eng, program):
    if program == "decode":
        lowered = eng._decode.lower(
            eng.params, jnp.zeros((eng.batch, 1), jnp.int32), eng.caches,
            jnp.zeros((eng.batch,), jnp.int32))
    else:
        lowered = eng._prefill.lower(
            eng.params, np.zeros((1, eng.buckets[0]), np.int32),
            np.zeros((1,), np.int32))
    return lowered.compile()


def _scope_paths(compiled):
    module = compiled.runtime_executable().hlo_modules()[0]
    return set(ts.hlo_scopes(_msg(
        (1, module.as_serialized_hlo_module_proto()))).values())


def _packing_program(eng):
    """The engine's one-time packing of its first block-sparse matrix
    (``models.model.pack_sparse``), compiled."""
    gi, si, name, layer = next(tfm.sparse_linears(eng.lm.cfg))
    values = eng.params["stack"][gi][si]["ffn"][name]["values"]
    return jax.jit(layer.pack).lower(values).compile()


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_programs_carry_the_scopes(sparse_engine, program):
    compiled = _compiled(sparse_engine, program)
    text = compiled.as_text()
    paths = _scope_paths(compiled)
    assert {"embed", "attn", "attn/kv_update", "ffn", "unembed"} <= paths
    assert {p for p in paths if "kv_update" in p} == {"attn/kv_update"}
    # the engine packed the block-sparse values into kernel tiles once,
    # at start: the served programs hold no relayout
    op_names = re.findall(r'op_name="([^"]*)"', text)
    assert not [p for p in paths if "pack_values" in p]
    assert not [m for m in op_names if "pack_values" in m]
    # the scopes survive the scan over layers: the loop body holds them
    body = [m for m in op_names if "/while/body/" in m]
    assert any("/ffn/" in m for m in body)
    assert any("/attn/" in m for m in body)
    # the one-time packing program carries the relayout's scope
    assert _scope_paths(_packing_program(sparse_engine)) - {ts.UNSCOPED} \
        == {"pack_values"}


def test_scope_paths_keep_the_vocabulary_in_order():
    assert ts.scope_of("jit(decode_fn)/while/body/closed_call/ffn/"
                       "pack_values/scatter-add") == "ffn/pack_values"
    # a kernel's name, a jitted wrapper and the loop are no scopes; a
    # name repeated by an interpreted kernel counts once
    assert ts.scope_of("jit(decode_fn)/while/body/closed_call/attn/"
                       "jit(dense_mm_call)/dense_mm_call/while/body/attn/"
                       "dot_general") == "attn"
    assert ts.scope_of("jit(decode_fn)/while/body/dynamic_slice") \
        == ts.UNSCOPED


# -- a serialized XSpace, as a TPU trace lays out an operation's metadata

def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _msg(*fields):
    """A protobuf message of ``(field, int | bytes | str | list)``."""
    out = bytearray()
    for f, v in fields:
        for x in (v if isinstance(v, list) else [v]):
            if isinstance(x, int):
                out += _varint(f << 3) + _varint(x)
            else:
                x = x.encode() if isinstance(x, str) else x
                out += _varint(f << 3 | 2) + _varint(len(x)) + x
    return bytes(out)


def _instr(iid, name, op_name="", operands=(), calls=(), packed=False):
    ops = list(operands)
    if packed:      # a packed repeated field, as protobuf writes them
        ops = b"".join(_varint(o) for o in ops)
    return _msg((1, name), (2, "fusion" if calls else "op"),
                (7, _msg((2, op_name))), (35, iid), (36, ops),
                (38, list(calls)))


def _comp(cid, root, *instrs):
    return _msg((2, list(instrs)), (5, cid), (6, root))


def test_scopes_of_a_compiled_program():
    """Each instruction's own ``op_name``; without one, a fusion's root's,
    else the scope its users agree on (the relayout's scatter fusion and
    zero fill, whose ``op_name`` the TPU compiler drops); users that
    disagree leave it unscoped, as does an ``op_name`` that names no
    scope (the scan's slices)."""
    body = "jit(f)/while/body/closed_call/"
    hlo = _msg((1, _msg((3, [
        _comp(10, 101, _instr(100, "p"), _instr(101, "scatter.26",
                                               operands=[100])),
        _comp(11, 111, _instr(111, "convert.5")),
        _comp(13, 131, _instr(131, "dot.4", "jit(f)/unembed/dot")),
        _comp(20, 4,
              _instr(1, "broadcast.279.clone"),
              _instr(2, "fusion.76", operands=[1], calls=[10]),
              _instr(3, "bitcast.146", body + "ffn/pack_values/scatter-add",
                     operands=[2], packed=True),
              _instr(4, "copy.130", body + "ffn/pack_values/reshape",
                     operands=[3]),
              _instr(5, "dynamic-slice.5", "jit(f)/while/body/dynamic_slice"),
              _instr(6, "fusion.6", "jit(f)/embed/mul", calls=[11]),
              _instr(7, "copy-start.1"),
              _instr(8, "dot.8", body + "attn/dot", operands=[7, 5]),
              _instr(9, "mul.9", body + "ffn/mul", operands=[7]),
              _instr(12, "fusion.12", calls=[13])),
    ]))))
    got = ts.hlo_scopes(hlo)
    assert {k: got[k] for k in ("broadcast.279.clone", "fusion.76",
                                "copy.130", "dynamic-slice.5", "fusion.6",
                                "copy-start.1", "fusion.12")} == {
        "broadcast.279.clone": "ffn/pack_values",
        "fusion.76": "ffn/pack_values", "copy.130": "ffn/pack_values",
        "dynamic-slice.5": ts.UNSCOPED, "fusion.6": "embed",
        "copy-start.1": ts.UNSCOPED, "fusion.12": "unembed"}
    # a trace keeps each program's HloProto in its metadata plane, under
    # the program's id
    stat = _msg((1, 3), (2, _msg((1, 3), (2, "Hlo Proto"))))
    pid = 6438526618425300169
    program = _msg((1, pid), (2, _msg((1, pid), (2, "jit_f(%d)" % pid), (5, [
        _msg((1, 3), (6, hlo))]))))
    space = _msg((1, [_msg((1, 1), (2, "/host:CPU")),
                      _msg((1, 2), (2, "/host:metadata"), (4, [program]),
                           (5, [stat]))]))
    assert ts.programs(space) == {pid: got}


# -- a program built without named scopes, as before they existed: its
# operations go by the functions on their source stacks

def pack_values(x):
    return jnp.sin(x) * 3.0


def _apply_ffn(x, scope):
    with jax.named_scope(scope) if scope else contextlib.nullcontext():
        return jnp.cos(pack_values(x)) + 1.0


@pytest.mark.parametrize("scope, paths", [
    (None, {"ffn", "ffn/pack_values"}),
    # any named scope in a program: its op_names rule, frames are unread
    ("unembed", {"unembed"})])
def test_scopes_of_a_program_without_named_scopes(scope, paths):
    compiled = jax.jit(functools.partial(_apply_ffn, scope=scope)).lower(
        jnp.ones((8,), jnp.float32)).compile()
    module = compiled.runtime_executable().hlo_modules()[0]
    got = set(ts.hlo_scopes(_msg(
        (1, module.as_serialized_hlo_module_proto()))).values())
    assert paths <= got <= paths | {ts.UNSCOPED}


def test_source_frames_keep_the_outer_scope_first():
    """Frames point at their callers; a stack cut below ``_apply_ffn``
    still puts the relayout in the FFN."""
    index = _msg((2, ["<module>", "_apply_ffn", "spmm_nt", "pack_values"]),
                 (3, [_msg((1, 1), (2, f)) for f in (1, 2, 3, 4)]),
                 (4, [_msg((1, 1)), _msg((1, 2), (2, 1)),
                      _msg((1, 4), (2, 2)), _msg((1, 3)),
                      _msg((1, 4), (2, 4))]))
    assert ts._frame_scopes(index) == {
        1: ts.UNSCOPED, 2: "ffn", 3: "ffn/pack_values", 4: "ffn",
        5: "ffn/pack_values"}


# ---------------------------------------------------------------------------
# the engine's spans in a profiler trace
# ---------------------------------------------------------------------------

ADMIT_PARTS = ("engine.admit.prefill", "engine.admit.readback",
               "engine.admit.write_slot")
STEP_PARTS = ("engine.step.feed", "engine.step.launch",
              "engine.step.readback", "engine.step.retire")


def _inside(inner, outer):
    return (outer["t0"] <= inner["t0"]
            and inner["t0"] + inner["dur"] <= outer["t0"] + outer["dur"])


def test_engine_spans_in_a_trace(tmp_path):
    cfg = configs.smoke("qwen2_1_5b")
    lm = LM(cfg)
    eng = Engine(lm, lm.init(jax.random.PRNGKey(0)), batch=2, max_len=32,
                 warm_compile=True)
    reqs = [Request(uid=7, prompt=np.arange(1, 6, dtype=np.int32),
                    max_new_tokens=4),
            Request(uid=8, prompt=np.arange(1, 21, dtype=np.int32),
                    max_new_tokens=3)]
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
        eng.run(reqs)
    jax.profiler.stop_trace()
    host = ts.load(str(tmp_path))["host"]
    by = collections.defaultdict(list)
    for h in host:
        by[h["name"]].append(h)

    admits = sorted(by["engine.admit"], key=lambda h: h["t0"])
    assert [(h["args"]["uid"], h["args"]["prompt_len"]) for h in admits] \
        == [(7, 5), (8, 20)]
    assert [h["args"]["bucket"] for h in admits] == [
        eng.bucket_for(5), eng.bucket_for(20)]
    steps = sorted(by["engine.step"], key=lambda h: h["t0"])
    # three decode steps: the first request's last three tokens
    assert [h["args"]["step"] for h in steps] == [0, 1, 2]
    assert [h["args"]["live"] for h in steps] == [2, 2, 1]
    assert eng.stats()["steps"] == 3
    for parent, parts in (("engine.admit", ADMIT_PARTS),
                          ("engine.step", STEP_PARTS)):
        for part in parts:
            assert len(by[part]) == len(by[parent]), part
            assert all(any(_inside(p, s) for s in by[parent])
                       for p in by[part]), part
    # one clock: every span lies inside the window that holds them
    window = by[trace_reduce.WINDOW][0]
    assert all(_inside(h, window) for h in host)
    # no device plane on the CPU: no device time or idle to read
    red = ts.reduce(ts.load(str(tmp_path)))
    assert red["steps"] == 0 and red["step_idle_s"] is None
    assert red["runs"] == {} and not red["scoped"]


# ---------------------------------------------------------------------------
# the reduction on hand-made events
# ---------------------------------------------------------------------------

NS = 1e-9


def ev(line, name, t0, t1, scope=None, dev=0):
    return {"dev": dev, "line": line, "name": name, "t0": t0,
            "dur": t1 - t0, "scope": scope}


def host(name, t0, t1, **args):
    return {"name": name, "t0": t0, "dur": t1 - t0, "args": args}


OPS, MODULES = trace_reduce.OPS, trace_reduce.MODULES
EVENTS = {
    "device": [
        ev(MODULES, "jit_decode_fn(1)", 10, 110),
        ev(OPS, "while.1", 10, 100, ts.UNSCOPED),  # holds the layer scan
        ev(OPS, "fusion.76", 20, 50, "ffn/pack_values"),
        ev(OPS, "bsmm_call.16", 50, 60, "ffn"),
        ev(OPS, "dense_mm_call.3", 60, 80, "attn"),
        ev(OPS, "fusion.9", 80, 84, "attn/kv_update"),
        ev(OPS, "fusion.2", 100, 105, "unembed"),
        ev(MODULES, "jit_prefill_fn(2)", 140, 170),
        ev(OPS, "fusion.1", 140, 170, "ffn"),
        # a decode program cut by the window's end counts nowhere
        ev(MODULES, "jit_decode_fn(1)", 190, 260),
        ev(OPS, "fusion.76", 190, 260, "ffn/pack_values"),
    ],
    "host": [
        host(trace_reduce.WINDOW, 0, 200),
        host("engine.step", 0, 120, step=4, live=2),
        host("engine.step.feed", 0, 4),
        host("engine.step.launch", 4, 8),
        host("engine.step.readback", 8, 116),
        host("engine.step.retire", 116, 120),
        host("engine.admit", 128, 175, uid=3, prompt_len=9, bucket=16),
        host("engine.admit.prefill", 128, 138),
        host("engine.admit.readback", 138, 174),
        # the next step runs past the window's end
        host("engine.step", 180, 265, step=5, live=2),
    ],
}


def test_own_time_by_scope_in_whole_programs():
    red = ts.reduce(EVENTS)
    assert red["runs"] == {"jit_decode_fn": (1, pytest.approx(100 * NS)),
                           "jit_prefill_fn": (1, pytest.approx(30 * NS))}
    own = red["scope_s"]["jit_decode_fn"]
    # the loop's own time is what its body's operations leave
    assert own == {ts.UNSCOPED: pytest.approx(26 * NS),
                   "ffn/pack_values": pytest.approx(30 * NS),
                   "ffn": pytest.approx(10 * NS),
                   "attn": pytest.approx(20 * NS),
                   "attn/kv_update": pytest.approx(4 * NS),
                   "unembed": pytest.approx(5 * NS)}
    # the split closes on the operations' union, 95 of the program's 100
    assert sum(own.values()) == pytest.approx(95 * NS)
    assert red["scope_s"]["jit_prefill_fn"] == {"ffn": pytest.approx(
        30 * NS)}


@pytest.mark.parametrize("part, ms", [("ffn", 40e-6), ("attn", 24e-6),
                                      ("pack_values", 30e-6),
                                      ("kv_update", 4e-6), ("embed", 0.0)])
def test_per_decode_step_by_part(part, ms):
    assert ts.per_run_ms(ts.reduce(EVENTS), part) == pytest.approx(ms)


def test_idle_clipped_to_engine_spans():
    red = ts.reduce(EVENTS)
    # busy [10, 105] + [140, 170] + [190, 200]: the first step holds
    # [0, 10] and [105, 120] idle; the second runs past the window
    assert red["steps"] == 1
    assert red["step_idle_s"] == pytest.approx(25 * NS)
    assert red["idle_s"] == pytest.approx(65 * NS)
    assert red["idle_by_span"] == {
        "engine.step.feed": pytest.approx(4 * NS),
        "engine.step.launch": pytest.approx(4 * NS),
        "engine.step.readback": pytest.approx(13 * NS),
        "engine.step.retire": pytest.approx(4 * NS),
        "engine.step": pytest.approx(0.0),
        "engine.admit.prefill": pytest.approx(10 * NS),
        "engine.admit.readback": pytest.approx(6 * NS),
        # [174, 175] of the admission is in none of its parts
        "engine.admit": pytest.approx(1 * NS),
        # [120, 128] and [175, 190]
        ts.OUTSIDE: pytest.approx(23 * NS)}
    b = ts.breakdown(red)
    assert b["idle_by_engine_span"][0] == [ts.OUTSIDE, pytest.approx(
        23 * NS)]
    assert b["scopes_ms"][0] == ["ffn/pack_values", pytest.approx(30e-6)]
    assert b["program_ms"] == pytest.approx(100e-6)


def _rec(events):
    """A traced run's record as the readers see it, its reduction made."""
    return {"trace": trace_reduce.reduce(events),
            "trace_scopes": ts.reduce(events)}


def test_readers_on_hand_made_events():
    rec = _rec(EVENTS)
    got = {m: run.metric_reader(m)(rec) for m in NEW_METRICS}
    assert got == {"model.ffn_ms.decode": pytest.approx(40e-6),
                   "model.attn_ms.decode": pytest.approx(24e-6),
                   "kernel.relayout_ms.decode": pytest.approx(30e-6),
                   "engine.step_idle_ms.decode": pytest.approx(25e-6)}
    # an untraced run reads nothing and opens no trace
    assert all(run.metric_reader(m)({"trace": None}) is None
               for m in NEW_METRICS)


def test_program_without_scopes_reads_nothing():
    """A trace that keeps no compiled program and no span of a decode
    step, as the CPU's, gives the new metrics nothing to read."""
    events = {"device": [dict(e, scope=None if e["line"] == OPS else
                              e["scope"]) for e in EVENTS["device"]],
              "host": [h for h in EVENTS["host"]
                       if not h["name"].startswith(ts.ENGINE)]}
    rec = _rec(events)
    assert all(run.metric_reader(m)(rec) is None for m in NEW_METRICS)


def test_steps_fall_back_to_the_drivers_span():
    """Without the engine's spans, a decode step is the benchmark
    driver's ``bench.step`` around the same call."""
    events = {"device": EVENTS["device"],
              "host": [dict(h, name="bench.step")
                       if h["name"] == "engine.step" else h
                       for h in EVENTS["host"]
                       if not h["name"].startswith("engine.step.")]}
    red = ts.reduce(events)
    assert red["steps"] == 1
    assert red["step_idle_s"] == pytest.approx(25 * NS)
    assert run.metric_reader("engine.step_idle_ms.decode")(
        _rec(events)) == pytest.approx(25e-6)
    # the engine's own span comes first where the program has it
    both = {"device": EVENTS["device"],
            "host": EVENTS["host"] + [host("bench.step", 0, 130)]}
    assert ts.reduce(both)["step_idle_s"] == pytest.approx(25 * NS)


# ---------------------------------------------------------------------------
# decode steps recorded on a TPU v5e
# ---------------------------------------------------------------------------

def _recorded(name):
    with open(os.path.join(BENCH, "testdata", name)) as f:
        d = json.load(f)
    cols = d["columns"]
    dev = [dict(zip(cols["device"], row)) for row in d["device"]]
    hst = [dict(zip(cols["host"], row)) for row in d["host"]]
    for e in dev:
        e["t0"], e["dur"] = float(e["t0"]), float(e["dur"])
        e.setdefault("scope", None)
    for h in hst:
        h["t0"], h["dur"] = float(h["t0"]), float(h["dur"])
        h.setdefault("args", {})
    return {"device": dev, "host": hst}


def test_existing_readers_unchanged_on_recorded_step():
    """The readers the benchmark had read what they read before on the
    step recorded before the program had scopes or engine spans; the new
    ones read nothing there (it keeps no compiled program, and its window
    is the program's, inside the ``bench.step`` span)."""
    events = _recorded("bsffn-decode-step.json")
    rec = _rec(events)
    red = rec["trace"]
    assert run.metric_reader("device.idle_share.decode")(rec) == \
        pytest.approx(0.13946, abs=1e-4)
    assert trace_reduce.kernel_time(red, "decode", ("bsmm",)) == (
        84, pytest.approx(0.011381619))
    assert trace_reduce.kernel_time(red, "decode", ("dense_mm_call",)) == (
        112, pytest.approx(0.001283845))
    top = trace_reduce.breakdown(red)["device_ops"]
    assert top[0] == ["fusion.76", pytest.approx(0.015298869)]
    assert all(run.metric_reader(m)(rec) is None for m in NEW_METRICS)


def test_recorded_step_by_scope():
    """One decode step of bsffn-decode recorded on a TPU v5e with the
    program's scopes and the engine's spans: the scope split closes on the
    program's length, the relayout lies inside the FFN, every kernel call
    is where the model makes it, and the readers give the values checked
    by hand in PERF.md."""
    events = _recorded("bsffn-decode-scopes.json")
    rec = _rec(events)
    red = rec["trace_scopes"]
    assert red["runs"]["jit_decode_fn"] == (1, pytest.approx(0.143430204))
    own = red["scope_s"]["jit_decode_fn"]
    assert sum(own.values()) == pytest.approx(0.143430204, rel=0.02)
    assert own == {"ffn/pack_values": pytest.approx(0.11500838),
                   "ffn": pytest.approx(0.011945123),
                   ts.UNSCOPED: pytest.approx(0.013671692),
                   "attn": pytest.approx(0.002049719),
                   "attn/kv_update": pytest.approx(0.000117335),
                   "unembed": pytest.approx(0.000635747),
                   "embed": pytest.approx(0.000001923)}
    calls = collections.Counter(
        (e["name"].rsplit(".", 1)[0], e["scope"]) for e in events["device"]
        if e["line"] == OPS and "_call." in e["name"])
    assert calls == {("bsmm_call", "ffn"): 56,
                     ("bsmm_balanced_call", "ffn"): 28,
                     ("dense_mm_call", "attn"): 112}
    got = {m: run.metric_reader(m)(rec) for m in NEW_METRICS}
    assert got == {"model.ffn_ms.decode": pytest.approx(126.953503),
                   "model.attn_ms.decode": pytest.approx(2.167054),
                   "kernel.relayout_ms.decode": pytest.approx(115.00838),
                   "engine.step_idle_ms.decode": pytest.approx(3.215477)}
    assert got["kernel.relayout_ms.decode"] <= got["model.ffn_ms.decode"]
    # the device waits for the host between the argmax read back and the
    # next launch; the trace's device clock leads the host's by about a
    # millisecond, so most of that wait shows in the read back
    assert red["idle_by_span"]["engine.step.readback"] == pytest.approx(
        0.002639057)
