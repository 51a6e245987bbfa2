"""95th percentile (nearest rank) of every gap between consecutive output
tokens of a request, both emitted in the window (host clock).  A per-layer
metric: in the decode-heavy mix about 5% of gaps span an admission, so
the percentile falls between the decode step's two modes (with and
without a slot written just before) and moves with every step that the
host holds up (PERF.md, section 2)."""
import math


def read(rec):
    w0, w1 = rec["window"]
    gaps = sorted(b - a for x in rec["requests"]
                  for a, b in zip(x["times"], x["times"][1:])
                  if w0 <= a and b <= w1)
    if not gaps:
        return None
    return gaps[math.ceil(0.95 * len(gaps)) - 1] * 1e3
