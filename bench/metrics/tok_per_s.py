"""Output tokens emitted in the window, over the window (host clock)."""


def read(rec):
    w0, w1 = rec["window"]
    n = sum(1 for x in rec["requests"] for t in x["times"] if w0 <= t <= w1)
    return n / (w1 - w0)
