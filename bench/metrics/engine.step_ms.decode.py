"""Mean host wall time of the engine instance's ``step`` (one decode
token for every live slot; it ends on the argmax read back), over the
steps of the window."""


def read(rec):
    w0, w1 = rec["window"]
    t = [b - a for a, b, _ in rec["steps"] if w0 <= a < w1]
    return sum(t) / len(t) * 1e3 if t else None
