"""Mean host wall time of the engine instance's ``admit`` (the bucket
choice, the prefill of one prompt, its first token read back and the
slot write), over the admissions of the window."""


def read(rec):
    w0, w1 = rec["window"]
    t = [b - a for a, b, _, _ in rec["admits"] if w0 <= a < w1]
    return sum(t) / len(t) * 1e3 if t else None
