"""Reduction of a JAX profiler trace to the numbers the metrics read.

``load`` reads the ``.xplane.pb`` the profiler wrote and keeps two kinds
of events: operations and programs on each device (the ``XLA Ops`` and
``XLA Modules`` lines of a ``/device:`` plane) and the benchmark's own
host spans (``jax.profiler.TraceAnnotation`` names starting ``bench.``).
``reduce`` is pure and works on those events, so it is checked on
hand-made events (``bench/tests``).

Busy time is the union of the operation intervals on a device, inside
the traced window (the ``bench.traced_window`` span); the idle share is
one minus busy over the window.  Each idle gap is put down to the
innermost host span that covers its middle, or to ``host.other``.

On a TPU an operation's name is its HLO text, which names the operations
it reads too (``%copy.5 = copy(%bsmm_call.8)``), so an operation goes by
the short name before `` = ``.  A loop (the scan over layers) is an
operation that holds the operations of its body, so the breakdown ranks
operations by their own time, less that of the operations inside them.
"""
from __future__ import annotations

import bisect
import collections
import glob
import os

import numpy as np

OPS, MODULES = "XLA Ops", "XLA Modules"
WINDOW = "bench.traced_window"
OTHER = "host.other"


def load(trace_dir: str) -> dict:
    """Events of the newest trace under ``trace_dir``: ``{"device":
    [{"dev", "line", "name", "t0", "dur"}], "host": [{"name", "t0",
    "dur", "thread"}]}``, times in ns on the profiler's one clock."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(paths[-1])
    dev, host = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            idx = int(plane.name.rsplit(":", 1)[-1]) \
                if plane.name.rsplit(":", 1)[-1].isdigit() else 0
            for line in plane.lines:
                if line.name not in (OPS, MODULES):
                    continue
                for e in line.events:
                    dev.append({"dev": idx, "line": line.name,
                                "name": e.name, "t0": float(e.start_ns),
                                "dur": float(e.duration_ns)})
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        host.append({"name": e.name,
                                     "t0": float(e.start_ns),
                                     "dur": float(e.duration_ns),
                                     "thread": line.name})
    return {"device": dev, "host": host}


def _merge(intervals):
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def short(name: str) -> str:
    """``fusion.75`` for ``%fusion.75 = bf16[...] fusion(...)``."""
    return name.split(" = ", 1)[0].lstrip("%")


def _self_times(spans):
    """Each span's own time: its length less that of the spans nested in
    it.  ``spans`` are ``(start, end, name)``."""
    out = collections.defaultdict(float)
    stack = []                          # [end, name, own]
    for a, b, name in sorted(spans, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= a:
            _, n, own = stack.pop()
            out[n] += own
        if stack:
            stack[-1][2] -= min(b, stack[-1][0]) - a
        stack.append([b, name, b - a])
    for _, n, own in stack:
        out[n] += own
    return out


def reduce(events: dict) -> dict:
    """Window, busy and idle time, per-operation and per-program sums and
    the idle gaps by host span.  Seconds throughout."""
    host = events["host"]
    win = [h for h in host if h["name"] == WINDOW]
    dev = events["device"]
    if win:
        w0, w1 = win[0]["t0"], win[0]["t0"] + win[0]["dur"]
    else:
        w0 = min(e["t0"] for e in dev)
        w1 = max(e["t0"] + e["dur"] for e in dev)
    devices = sorted({e["dev"] for e in dev if e["line"] == OPS}) or [0]

    def clip(e):
        a, b = max(e["t0"], w0), min(e["t0"] + e["dur"], w1)
        return (a, b) if b > a else None

    ops = collections.defaultdict(lambda: [0, 0.0])
    by_module = collections.defaultdict(
        lambda: collections.defaultdict(lambda: [0, 0.0]))
    busy = {}
    gaps = []
    own = collections.defaultdict(float)
    for d in devices:
        mods = sorted((e["t0"], e["t0"] + e["dur"], e["name"]) for e in dev
                      if e["dev"] == d and e["line"] == MODULES)
        starts = [m[0] for m in mods]
        spans = []
        for e in dev:
            if e["dev"] != d or e["line"] != OPS:
                continue
            c = clip(e)
            if c is None:
                continue
            name = short(e["name"])
            spans.append((*c, name))
            sec = (c[1] - c[0]) * 1e-9
            ops[name][0] += 1
            ops[name][1] += sec
            mid = 0.5 * (c[0] + c[1])
            mod = "(none)"
            i = bisect.bisect_right(starts, mid) - 1
            if i >= 0 and mods[i][1] >= mid:
                mod = mods[i][2]
            by_module[mod][name][0] += 1
            by_module[mod][name][1] += sec
        for name, t in _self_times(spans).items():
            own[name] += t * 1e-9 / len(devices)
        merged = _merge((a, b) for a, b, _ in spans)
        busy[d] = sum(b - a for a, b in merged) * 1e-9
        if d == devices[0]:
            t = w0
            for a, b in merged + [[w1, w1]]:
                if a > t:
                    gaps.append((t, a))
                t = max(t, b)

    # each gap goes to the shortest host span that covers its middle
    inner = sorted((h for h in host if h["name"] != WINDOW),
                   key=lambda h: h["dur"])
    g = np.asarray(gaps, np.float64).reshape(-1, 2)
    mid = g.mean(axis=1)
    owner = np.full(len(g), -1)
    for i, h in enumerate(inner):
        hit = (owner < 0) & (h["t0"] <= mid) & (mid <= h["t0"] + h["dur"])
        owner[hit] = i
    idle = collections.defaultdict(lambda: [0, 0.0])
    for i, (a, b) in zip(owner, g):
        name = inner[i]["name"] if i >= 0 else OTHER
        idle[name][0] += 1
        idle[name][1] += (b - a) * 1e-9
    window_s = (w1 - w0) * 1e-9
    busy_s = sum(busy.values()) / len(busy) if busy else 0.0
    return {
        "window_s": window_s,
        "busy_s": busy_s,
        # no device operation in the window: nothing to read
        "idle_share": 1.0 - busy_s / window_s
        if window_s > 0 and busy_s > 0 else None,
        "ops": {k: tuple(v) for k, v in ops.items()},
        "own_s": dict(own),
        "by_module": {m: {k: tuple(v) for k, v in d.items()}
                      for m, d in by_module.items()},
        "idle_by_span": {k: tuple(v) for k, v in idle.items()},
    }


def breakdown(red: dict, n: int = 10) -> dict:
    """The device operations that took most time of their own (seconds
    per device) and the idle time by what the host was doing, ``n``
    entries each."""
    ops = sorted(red["own_s"].items(), key=lambda kv: -kv[1])[:n]
    idle = sorted(red["idle_by_span"].items(), key=lambda kv: -kv[1][1])[:n]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v[1]] for k, v in idle]}


def kernel_time(red: dict, module_part: str, op_parts) -> tuple:
    """``(events, seconds)`` of operations whose name holds one of
    ``op_parts``, inside programs whose name holds ``module_part``."""
    n, s = 0, 0.0
    for mod, d in red["by_module"].items():
        if module_part not in mod:
            continue
        for name, (cnt, sec) in d.items():
            if any(p in name for p in op_parts):
                n += cnt
                s += sec
    return n, s
