"""Where the decode step goes by the program's own names, from a profiler
trace.

The program names its parts with ``jax.named_scope``: ``embed``,
``attn`` (with ``kv_update`` inside), ``ffn`` (with ``pack_values``, the
per-call relayout of block-sparse values, inside) and ``unembed``.  A
device operation carries the scopes it was traced under in its
``op_name``; its scope path is the vocabulary's names in that string, in
order (``ffn/pack_values``), or ``(unscoped)`` (what the layer scan
adds itself: per-layer slices of the stacked weights and caches).  The
serving engine marks its work with host spans
(``jax.profiler.TraceAnnotation``) named ``engine.admit``,
``engine.step`` and their parts, on the profiler's one clock beside the
device operations.

A trace of a program from before the scopes and the engine's spans is
read as near as it allows: an operation by the functions on its source
stack that the scopes wrap (``FUNCTIONS``), a decode step by the
benchmark driver's ``bench.step`` span around the same call.

``load`` reads the ``.xplane.pb`` and keeps the device operations and
programs with their scope paths, and the ``engine.`` spans with their
arguments (and ``bench.step``).  A TPU trace keeps each program as compiled (its
``HloProto``) in its metadata plane, which ``jax.profiler.ProfileData``
does not show, so ``programs`` reads it from the file itself and
``hlo_scopes`` gives each instruction its scope path; an operation in
the trace is the instruction of its program with its short name.
``reduce`` is pure, so it is checked on hand-made events.  The counts
of ``trace_reduce`` are not redone here: its window, busy time and
operation names stay its own.

    python bench/trace_scopes.py .bench_trace

prints the breakdown of the newest trace under a directory.
"""
from __future__ import annotations

import bisect
import collections
import glob
import json
import os
import sys

import numpy as np

import trace_reduce

BENCH = os.path.dirname(os.path.abspath(__file__))
# where ``run.py`` writes the trace of a ``--trace 1`` run
TRACE_DIR = os.path.join(os.path.dirname(BENCH), ".bench_trace")

SCOPES = ("embed", "attn", "kv_update", "ffn", "pack_values", "unembed")
UNSCOPED = "(unscoped)"
ENGINE = "engine."
OUTSIDE = "(no engine span)"
DECODE = "jit_decode_fn"
PREFILL = "jit_prefill_fn"
ADMIT = "engine.admit"
# For a program built before the named scopes: the functions they wrap,
# and, since a program keeps only the innermost ten frames of each
# operation's source stack, the sparse FFN's own layers (the relayout
# and the kernels sit deeper than ten frames below ``_apply_ffn``).
FUNCTIONS = {"LM._embed_tokens": "embed", "_apply_ffn": "ffn",
             "SparseFFN.apply": "ffn", "SparseLinear.apply": "ffn",
             "spmm_nt": "ffn",
             "pack_values": "ffn/pack_values", "LM._unembed": "unembed",
             **{f"{kind}_{phase}": "attn" for kind in ("gqa", "mla")
                for phase in ("train", "prefill", "decode")}}
# the span of one decode step: the engine's own, or, in a program without
# it, the benchmark driver's around the same ``Engine.step`` call
STEP_SPANS = ("engine.step", "bench.step")


def scope_of(op_name: str) -> str:
    """``ffn/pack_values`` for ``jit(decode_fn)/while/body/closed_call/
    ffn/pack_values/scatter-add``: the vocabulary's names in order, each
    once."""
    path = []
    for part in op_name.split("/"):
        if part in SCOPES and part not in path:
            path.append(part)
    return "/".join(path) or UNSCOPED


def program(name: str) -> str:
    """``jit_decode_fn`` for ``jit_decode_fn(16014853037321776486)``."""
    return name.split("(", 1)[0]


# -- protobuf, only the fields read here.  tsl/profiler/protobuf/
# xplane.proto: XSpace.planes = 1; XPlane.name = 2, .event_metadata = 4
# and .stat_metadata = 5 (maps: key 1, value 2); XEventMetadata.id = 1,
# .stats = 5; XStatMetadata.name = 2; XStat.metadata_id = 1,
# .bytes_value = 6.  xla/service/hlo.proto: HloProto.hlo_module = 1;
# HloModuleProto.computations = 3; HloComputationProto.instructions = 2,
# .id = 5, .root_id = 6; HloInstructionProto.name = 1, .opcode = 2,
# .metadata = 7, .id = 35, .operand_ids = 36 (packed),
# .called_computation_ids = 38 (packed); HloModuleProto.stack_frame_index
# = 17: StackFrameIndexProto.function_names = 2, .file_locations = 3
# (FileLocation.function_name_id = 2), .stack_frames = 4
# (StackFrame.file_location_id = 1, .parent_frame_id = 2), ids from 1;
# OpMetadata.op_name = 2, .stack_frame_id = 15.

def _varint(buf, i: int):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of one protobuf message: an int for a
    varint, a ``memoryview`` for anything else."""
    i, n = 0, len(buf)
    while i < n:
        tag, i = _varint(buf, i)
        kind = tag & 7
        if kind == 0:
            v, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            v, i = buf[i:i + size], i + size
        elif kind in (1, 5):
            size = 8 if kind == 1 else 4
            v, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {kind}")
        yield tag >> 3, v


def _ints(values) -> list:
    """A repeated integer field, packed or not."""
    out = []
    for v in values:
        if isinstance(v, int):
            out.append(v)
            continue
        i = 0
        while i < len(v):
            x, i = _varint(v, i)
            out.append(x)
    return out


def _text(v) -> str:
    return bytes(v).decode()


def _frame_scopes(index) -> dict:
    """``{frame id: scope path}`` of a serialized ``StackFrameIndexProto``:
    the scopes of ``FUNCTIONS`` on each frame's stack, outermost first."""
    idx = collections.defaultdict(list)
    for g, v in _fields(index):
        idx[g].append(v)
    funcs = [_text(v) for v in idx[2]]
    loc_func = [dict(_fields(v)).get(2, 0) for v in idx[3]]
    frames = [dict(_fields(v)) for v in idx[4]]
    names = {0: ()}

    def walk(fid):
        if fid not in names:
            fr = frames[fid - 1]
            fn = funcs[loc_func[fr.get(1, 1) - 1] - 1]
            names[fid] = walk(fr.get(2, 0)) + (
                (FUNCTIONS[fn],) if fn in FUNCTIONS else ())
        return names[fid]
    return {fid: scope_of("/".join(walk(fid)))
            for fid in range(1, len(frames) + 1)}


def hlo_scopes(hlo_proto) -> dict:
    """``{instruction name: scope path}`` of a serialized ``HloProto``
    (the program as compiled).  An instruction goes by its own
    ``op_name``; one that has none takes, if a fusion, its root's, and
    otherwise the scope its users agree on: XLA drops the ``op_name`` of
    some operations it makes or rewrites (the relayout's scatters and
    their zero fills on a TPU), and they count to the work that reads
    them.  Users that disagree, or none, leave it ``(unscoped)``.

    A program whose ``op_name``s name no scope at all was built before
    the model had named scopes; there an instruction goes by the
    functions on its stack of source frames (``FUNCTIONS``, the ones the
    scopes wrap), with the same rules for one without a frame."""
    module = next(v for f, v in _fields(hlo_proto) if f == 1)
    ins, roots, users = {}, {}, collections.defaultdict(set)
    frame_scope = {}
    for f, comp in _fields(module):
        if f == 17:
            frame_scope = _frame_scopes(comp)
        if f != 3:
            continue
        cf = collections.defaultdict(list)
        for g, v in _fields(comp):
            cf[g].append(v)
        roots[cf[5][0] if cf[5] else 0] = cf[6][0] if cf[6] else None
        for raw in cf[2]:
            fs = collections.defaultdict(list)
            for g, v in _fields(raw):
                fs[g].append(v)
            meta = dict(_fields(fs[7][0])) if fs[7] else {}
            iid = fs[35][0]
            op = _text(meta.get(2, b""))
            ins[iid] = {"name": _text(fs[1][0]),
                        "own": scope_of(op) if op else None,
                        "frame": meta.get(15, 0),
                        "calls": _ints(fs[38])}
            for o in _ints(fs[36]):
                users[o].add(iid)
    if frame_scope and all(i["own"] in (None, UNSCOPED)
                           for i in ins.values()):
        for i in ins.values():
            i["own"] = frame_scope.get(i["frame"])
    scope = {}

    def resolve(iid, seen=()):
        if iid in scope:
            return scope[iid]
        i = ins[iid]
        out = i["own"]
        if out is None and len(i["calls"]) == 1 \
                and roots.get(i["calls"][0]) in ins:
            out = ins[roots[i["calls"][0]]]["own"]
        if out is None:
            if iid in seen:
                return None
            got = {resolve(u, seen + (iid,)) for u in users[iid]} - {None}
            out = got.pop() if len(got) == 1 else UNSCOPED
        scope[iid] = out
        return out
    return {ins[i]["name"]: resolve(i) for i in ins}


def programs(xspace: bytes) -> dict:
    """``{program id: {instruction name: scope path}}`` of the programs a
    serialized ``XSpace`` holds (its ``/host:metadata`` plane keeps each
    program's ``HloProto`` under the program's id)."""
    out = {}
    for f, plane in _fields(memoryview(xspace)):
        if f != 1:
            continue
        pf = collections.defaultdict(list)
        for g, v in _fields(plane):
            pf[g].append(v)
        if not pf[2] or _text(pf[2][0]) != "/host:metadata":
            continue
        stat_names = {}
        for entry in pf[5]:
            meta = dict(_fields(dict(_fields(entry)).get(2, b"")))
            stat_names[meta.get(1, 0)] = _text(meta.get(2, b""))
        for entry in pf[4]:
            mf = collections.defaultdict(list)
            for g, v in _fields(dict(_fields(entry)).get(2, b"")):
                mf[g].append(v)
            for v in mf[5]:
                st = dict(_fields(v))
                if stat_names.get(st.get(1)) == "Hlo Proto" and 6 in st:
                    out[mf[1][0]] = hlo_scopes(st[6])
    return out


def load(trace_dir: str) -> dict:
    """Events of the newest trace under ``trace_dir``: ``{"device":
    [{"dev", "line", "name", "t0", "dur", "scope"}], "host": [{"name",
    "t0", "dur", "args"}]}``, times in ns on the profiler's one clock.
    ``name`` is an operation's short name; its ``scope`` is ``None``
    where the trace keeps no compiled program for it (outside a program,
    or a trace without them, as the CPU's)."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    with open(paths[-1], "rb") as f:
        raw = f.read()
    scopes = programs(raw)
    pd = ProfileData.from_serialized_xspace(raw)
    dev, host = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            tail = plane.name.rsplit(":", 1)[-1]
            idx = int(tail) if tail.isdigit() else 0
            lines = {line.name: list(line.events) for line in plane.lines
                     if line.name in (trace_reduce.OPS, trace_reduce.MODULES)}
            mods = sorted((e.start_ns, e.start_ns + e.duration_ns, e.name)
                          for e in lines.get(trace_reduce.MODULES, []))
            starts = [m[0] for m in mods]
            for a, b, name in mods:
                dev.append({"dev": idx, "line": trace_reduce.MODULES,
                            "name": name, "t0": float(a),
                            "dur": float(b - a), "scope": None})
            for e in lines.get(trace_reduce.OPS, []):
                mid = e.start_ns + 0.5 * e.duration_ns
                i = bisect.bisect_right(starts, mid) - 1
                name = trace_reduce.short(e.name)
                scope = None
                if i >= 0 and mid <= mods[i][1]:
                    pid = mods[i][2].rsplit("(", 1)[-1].rstrip(")")
                    if pid.isdigit():
                        scope = scopes.get(int(pid), {}).get(name)
                dev.append({"dev": idx, "line": trace_reduce.OPS,
                            "name": name, "t0": float(e.start_ns),
                            "dur": float(e.duration_ns), "scope": scope})
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(ENGINE) or e.name in (
                            trace_reduce.WINDOW, *STEP_SPANS):
                        host.append({"name": e.name,
                                     "t0": float(e.start_ns),
                                     "dur": float(e.duration_ns),
                                     "args": dict(e.stats)})
    return {"device": dev, "host": host}


def _tokens(admits, t: float):
    """The tokens prefilled by the program that runs at ``t``: the
    bucket of the ``engine.admit`` span around it (the prompt's length
    where the engine admits it unpadded), ``None`` outside one."""
    for a, b, args in admits:
        if a <= t <= b:
            bucket = args.get("bucket")
            return args.get("prompt_len") if bucket == "exact" else bucket
    return None


def _idle_within(gaps: np.ndarray):
    """``f(a, b)``: the idle time that falls inside ``[a, b]``, for
    sorted disjoint ``gaps`` ``[[start, end], ...]``."""
    starts, ends = gaps[:, 0], gaps[:, 1]
    before = np.concatenate([[0.0], np.cumsum(ends - starts)])

    def upto(t):
        k = np.searchsorted(starts, t, side="right")
        if k == 0:
            return 0.0
        return before[k - 1] + min(ends[k - 1], t) - starts[k - 1]
    return lambda a, b: float(upto(b) - upto(a))


def reduce(events: dict) -> dict:
    """Own device time by program and scope path, over the programs that
    ran whole inside the traced window, and the device's idle time inside
    the engine's spans.  Seconds throughout.

    ``runs``: ``{program: [executions, seconds]}``; ``scope_s``:
    ``{program: {scope path: own seconds}}``, summed over devices.
    ``steps``: the ``engine.step`` spans inside the window (the
    ``bench.step`` spans where the program has none);
    ``step_idle_s``: device idle time inside them (``None`` with no
    device operation in the trace, as on the CPU).  ``idle_by_span``:
    ``{span name: seconds}``, each idle stretch put to the innermost
    ``engine.`` span that holds it (``(no engine span)`` elsewhere).
    ``scoped``: whether any operation in those programs had a scope
    path at all, which a trace without compiled programs (the CPU's)
    has not.  ``prefills``: ``[(n, {operation: seconds})]``, one entry
    for each prefill program that ran whole in the window, with the
    device time of its operations by short name and the tokens it
    prefilled, the bucket of the ``engine.admit`` span that holds it
    (``None`` where no span does)."""
    host, dev = events["host"], events["device"]
    win = [h for h in host if h["name"] == trace_reduce.WINDOW]
    if win:
        w0, w1 = win[0]["t0"], win[0]["t0"] + win[0]["dur"]
    elif dev:
        w0 = min(e["t0"] for e in dev)
        w1 = max(e["t0"] + e["dur"] for e in dev)
    else:
        w0 = w1 = 0.0
    ops = [e for e in dev if e["line"] == trace_reduce.OPS]
    devices = sorted({e["dev"] for e in ops})

    runs = collections.defaultdict(lambda: [0, 0.0])
    scope_s = collections.defaultdict(lambda: collections.defaultdict(float))
    scoped = False
    admits = [(h["t0"], h["t0"] + h["dur"], h.get("args", {})) for h in host
              if h["name"] == ADMIT]
    prefills = []
    for d in devices:
        mods = sorted((e["t0"], e["t0"] + e["dur"], program(e["name"]))
                      for e in dev if e["dev"] == d
                      and e["line"] == trace_reduce.MODULES
                      and w0 <= e["t0"] and e["t0"] + e["dur"] <= w1)
        starts = [m[0] for m in mods]
        spans = collections.defaultdict(list)      # program -> spans
        op_s = [collections.defaultdict(float) for _ in mods]
        for e in ops:
            if e["dev"] != d:
                continue
            a, b = e["t0"], e["t0"] + e["dur"]
            i = np.searchsorted(starts, 0.5 * (a + b), side="right") - 1
            if i < 0 or mods[i][1] < 0.5 * (a + b):
                continue
            if e["scope"] not in (None, UNSCOPED):
                scoped = True
            spans[mods[i][2]].append((a, b, e["scope"] or UNSCOPED))
            op_s[i][e["name"]] += (b - a) * 1e-9
        for (a, b, prog), spent in zip(mods, op_s):
            runs[prog][0] += 1
            runs[prog][1] += (b - a) * 1e-9
            if prog == PREFILL:
                prefills.append((_tokens(admits, 0.5 * (a + b)),
                                 dict(spent)))
        for prog, sp in spans.items():
            for scope, t in trace_reduce._self_times(sp).items():
                scope_s[prog][scope] += t * 1e-9

    out = {"window_s": (w1 - w0) * 1e-9,
           "runs": {k: tuple(v) for k, v in runs.items()},
           "scope_s": {k: dict(v) for k, v in scope_s.items()},
           "scoped": scoped, "prefills": prefills,
           "steps": 0, "step_idle_s": None,
           "idle_s": None, "idle_by_span": {}}
    if not devices:
        return out      # no device operation: no idle to place
    # device idle inside the engine's spans (the first device, as the
    # idle gaps of ``trace_reduce``)
    busy = trace_reduce._merge(
        (max(e["t0"], w0), min(e["t0"] + e["dur"], w1)) for e in ops
        if e["dev"] == devices[0]
        and e["t0"] < w1 and e["t0"] + e["dur"] > w0)
    gaps, t = [], w0
    for a, b in busy + [[w1, w1]]:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    idle_in = _idle_within(np.asarray(gaps, np.float64).reshape(-1, 2))
    eng = sorted(((h["t0"], h["t0"] + h["dur"], h["name"]) for h in host
                  if h["name"].startswith(ENGINE)
                  and w0 <= h["t0"] and h["t0"] + h["dur"] <= w1),
                 key=lambda s: (s[0], -s[1]))
    for name in STEP_SPANS:
        steps = [(h["t0"], h["t0"] + h["dur"]) for h in host
                 if h["name"] == name
                 and w0 <= h["t0"] and h["t0"] + h["dur"] <= w1]
        if steps:
            break
    # the spans nest on the serving thread: a span's own idle is what is
    # inside it less what is inside its children
    own = collections.defaultdict(float)
    stack = []                              # [end, name, own idle]
    for a, b, name in eng:
        while stack and stack[-1][0] <= a:
            _, n, o = stack.pop()
            own[n] += o
        inside = idle_in(a, b)
        if stack:
            stack[-1][2] -= inside
        stack.append([b, name, inside])
    for _, n, o in stack:
        own[n] += o
    total = idle_in(w0, w1)
    own[OUTSIDE] = total - sum(own.values())
    out.update(steps=len(steps),
               step_idle_s=sum(idle_in(a, b) for a, b in steps) * 1e-9,
               idle_s=total * 1e-9,
               idle_by_span={k: v * 1e-9 for k, v in own.items()})
    return out


def per_run_ms(red: dict, part: str):
    """Own device time of the operations whose scope path holds
    ``part``, per decode step (ms); ``None`` where no decode program ran
    whole in the window or its operations carry no scope."""
    n, _ = red["runs"].get(DECODE, (0, 0.0))
    if not n or not red["scoped"]:
        return None
    s = sum(t for scope, t in red["scope_s"].get(DECODE, {}).items()
            if part in scope.split("/"))
    return 1e3 * s / n


def breakdown(red: dict) -> dict:
    """Own device time of the decode program by scope path (ms per
    execution, the program's length beside it) and the idle time by
    ``engine.`` span (seconds in the window)."""
    n, total = red["runs"].get(DECODE, (0, 0.0))
    scopes = sorted(red["scope_s"].get(DECODE, {}).items(),
                    key=lambda kv: -kv[1])
    return {
        "program": DECODE, "executions": n,
        "program_ms": 1e3 * total / n if n else None,
        "scopes_ms": [[k, 1e3 * v / n] for k, v in scopes] if n else [],
        "idle_by_engine_span": sorted(
            ([k, v] for k, v in red["idle_by_span"].items()),
            key=lambda kv: -kv[1]),
    }


def of_run(rec: dict):
    """The reduction of a ``--trace 1`` run's trace, read once per run
    record; ``None`` for an untraced run."""
    if rec.get("trace") is None:
        return None
    if "trace_scopes" not in rec:
        rec["trace_scopes"] = reduce(load(TRACE_DIR))
    return rec["trace_scopes"]


if __name__ == "__main__":
    red = reduce(load(sys.argv[1] if len(sys.argv) > 1 else TRACE_DIR))
    print(json.dumps({"breakdown": breakdown(red),
                      "runs": red["runs"], "steps": red["steps"],
                      "step_idle_s": red["step_idle_s"],
                      "idle_s": red["idle_s"],
                      "window_s": red["window_s"]}, indent=1))
