"""One minus the union of device operation intervals over the traced
window (profiler trace), in a decode-heavy cell."""


def read(rec):
    red = rec["trace"]
    if red is None or red["idle_share"] is None:
        return None
    return 100.0 * red["idle_share"]
