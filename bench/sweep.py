"""Find the highest arrival rate an open-loop cell sustains.

    python bench/sweep.py --config <config> --traffic <mix> --seed <n> --seconds <s> --rates 6 9 12 ...

One engine, as ``run.py`` starts it, serves the open-loop mix at each rate
in turn for ``--seconds`` after the mix's warm-up, and drains between
rates.  For each rate it prints one JSON line: requests due and
admitted in the window, the backlog at the close (due, not yet
admitted), the admissions and output tokens per second in the window
(what the engine completes, whatever was due), and the time to first
token, p50 and p95 over the window and p50 over each half of it.  A
rate is sustained when the backlog stays small and the second half
waits no longer than the first; the sweep stops after the first rate
whose backlog passes ``STOP_BACKLOG``.  Runs on a TPU only, like
``run.py``; it is a tool for choosing a mix's rate, not part of a run.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import run  # noqa: E402
import traffic  # noqa: E402

STOP_BACKLOG = 20       # requests due and not admitted at the close


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    r = run.pair(args.config, args.traffic)
    s = run.start(r, args.seed)
    eng, c = s["eng"], r["config"]
    for rate in args.rates:
        mix = dict(r["mix"], rate_per_s=rate)
        specs = traffic.build(mix, args.seed, args.seconds, c["vocab_size"])
        drv = run.Driver(eng, specs, mix, args.seconds, trace=False,
                         counter=s["counter"])
        drv.run()
        w0, w1 = drv.open, drv.close
        mid = 0.5 * (w0 + w1)
        due = [x for x in drv.reqs if w0 <= x["due"] < w1]
        first = [x["req"].output.times[0] if x["req"].output.times
                 else np.inf for x in due]
        ttft = np.array([f - x["due"] for f, x in zip(first, due)])
        halves = [ttft[[w0 <= x["due"] < mid for x in due]],
                  ttft[[mid <= x["due"] < w1 for x in due]]]
        print(json.dumps({
            "rate_per_s": rate, "due": len(due),
            "admitted": int(np.sum(np.asarray(first) <= w1)),
            "backlog_at_close": int(np.sum(np.asarray(first) > w1)),
            "admits_per_s": sum(w0 <= a < w1 for a, _, _, _ in drv.admits)
            / (w1 - w0),
            "tok_per_s": sum(w0 <= t <= w1 for x in drv.reqs
                             for t in x["req"].output.times) / (w1 - w0),
            "ttft_p50_ms": float(np.percentile(ttft, 50)) * 1e3,
            "ttft_p95_ms": float(np.percentile(ttft, 95)) * 1e3,
            "ttft_p50_ms_halves": [float(np.percentile(h, 50)) * 1e3
                                   for h in halves],
            "compiles_in_window": drv.compiles_in_window}), flush=True)
        if np.sum(np.asarray(first) > w1) > STOP_BACKLOG:
            break                # the queue grew: higher rates only grow it
        eng.serve()              # drain before the next rate
    return 0


if __name__ == "__main__":
    sys.exit(main())
