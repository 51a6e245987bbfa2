"""The one traffic generator: it reads a mix file under ``bench/traffic``
and makes the requests of a run from ``--seed``.

Every seed gets the same multiset of sizes and gaps, drawn at stratified
quantiles of the mix's distributions, with token ids of its own.  The
order is the seed's own too, except for the sizes that a mix names in
``same_order``: those come in one order for every seed.  In a closed
loop the output lengths alone decide when a slot frees and an admission
runs, so a mix that names them does the same work in its window on
every seed.  In an open loop near the engine's capacity the order of
the arrival gaps decides when a backlog builds, so a mix there names
them too.

A mix file holds:

* ``loop``: ``"closed"`` (``clients`` callers, each sending its next
  request, from a pool of ``pool``, as soon as the last one finished) or
  ``"open"`` (Poisson arrivals at ``rate_per_s``);
* ``prompt_len``, ``output_len``: ``{"dist": "lognormal", "median",
  "sigma", "min", "max"}`` or ``{"dist": "uniform", "min", "max"}``;
* ``warmup``: ``{"finished": n}`` (the window opens when ``n`` requests
  have finished) or ``{"seconds": s}`` (it opens ``s`` after the
  generator starts);
* ``trace_seconds``: the length of the traced part of the window;
* ``check``: ``{"requests": n}``, how many finished requests the
  reference checks;
* ``same_order`` (optional): the sizes (``"prompt_len"``,
  ``"output_len"``) and, in an open loop, the ``"arrival_gap"``s dealt
  in one order for every seed.
"""
from __future__ import annotations

import dataclasses
import json
import math
from statistics import NormalDist

import numpy as np


@dataclasses.dataclass
class Spec:
    prompt: np.ndarray
    max_new: int
    due: float = 0.0        # seconds after the start of the run (open loop)


def load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def quantiles(dist: dict, n: int) -> np.ndarray:
    """``n`` integer draws at the stratified quantiles ``(i + 1/2) / n``."""
    q = (np.arange(n) + 0.5) / n
    lo, hi = dist["min"], dist["max"]
    if dist["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(x) for x in q])
        v = dist["median"] * np.exp(dist["sigma"] * z)
    elif dist["dist"] == "uniform":
        v = lo + np.floor(q * (hi - lo + 1))
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return np.clip(np.rint(v), lo, hi).astype(np.int64)


def n_requests(mix: dict, seconds: float) -> int:
    """Closed loop: the pool the clients draw from.  Open loop: every
    arrival of the warm-up and the window."""
    if mix["loop"] == "closed":
        return int(mix["pool"])
    span = mix.get("warmup", {}).get("seconds", 0.0) + seconds
    return int(math.ceil(mix["rate_per_s"] * span))


def deal(values: np.ndarray, block: int, rng) -> np.ndarray:
    """``values`` (sorted) in an order drawn from ``rng`` in which every
    run of ``block`` consecutive entries holds one value of each of
    ``block`` equal strata.  ``block == len(values)`` is a plain
    permutation."""
    n = len(values)
    if n % block:
        raise ValueError(f"{n} requests do not split into blocks of {block}")
    strata = rng.permuted(values.reshape(block, n // block), axis=1)
    return rng.permuted(strata.T, axis=1).reshape(-1)


def build(mix: dict, seed: int, seconds: float, vocab: int) -> list:
    """The run's requests in the order they are sent.  In a closed loop
    each block of ``clients`` requests, the first fill of the slots
    among them, spans every stratum of both length distributions."""
    n = n_requests(mix, seconds)
    rng = np.random.default_rng(seed)
    block = mix["clients"] if mix["loop"] == "closed" else n
    fixed = mix.get("same_order", ())
    plens, olens = (
        deal(quantiles(mix[k], n), block,
             np.random.default_rng([i]) if k in fixed else rng)
        for i, k in enumerate(("prompt_len", "output_len")))
    specs = [Spec(rng.integers(0, vocab, size=int(p), dtype=np.int32),
                  int(o)) for p, o in zip(plens, olens)]
    if mix["loop"] == "open":
        # exponential gaps at stratified quantiles: mean 1 / rate; every
        # seed's last arrival falls at the same time
        q = (np.arange(n) + 0.5) / n
        order = np.random.default_rng([2]) if "arrival_gap" in fixed \
            else rng
        gaps = order.permutation(-np.log1p(-q) / mix["rate_per_s"])
        due = np.cumsum(gaps)
        for s, t in zip(specs, due):
            s.due = float(t)
    return specs
