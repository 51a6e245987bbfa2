"""Device idle time inside the serving engine's decode steps: each idle
stretch of the device in the traced window clipped to the
``engine.step`` host spans that lie in it, over those spans (the
benchmark's ``bench.step`` spans around the same call, for a program
without the engine's spans)."""
import trace_scopes


def read(rec):
    red = trace_scopes.of_run(rec)
    if red is None or not red["steps"] or red["step_idle_s"] is None:
        return None
    return 1e3 * red["step_idle_s"] / red["steps"]
