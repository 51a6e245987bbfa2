"""Shared by the kernel roofline readers: the roofline bound of the
calls a kernel made in the traced window, over its device time.  The
bound is the configuration's work module's ``kernel_bound_s``."""
import arch
import trace_reduce
import trace_scopes


def roofline_share(rec, *, program: str, routes, op_parts):
    """``program`` is ``"decode"`` (every engine step in the traced
    window, at ``n`` = the engine batch) or ``"prefill"`` (every prefill
    program that ran whole in the traced window, at ``n`` = the bucket
    of the admission that ran it: the bound and the time cover the same
    calls)."""
    red = rec["trace"]
    if red is None or rec["peaks"] is None or rec["traced"] is None:
        return None
    if program == "decode":
        t0, t1 = rec["traced"]
        ns = [rec["batch"] for a, _, _ in rec["steps"] if t0 <= a < t1]
        _, spent = trace_reduce.kernel_time(red, program, op_parts)
    else:
        calls = [(n, ops) for n, ops in trace_scopes.of_run(rec)["prefills"]
                 if n is not None]
        ns = [n for n, _ in calls]
        spent = sum(s for _, ops in calls for name, s in ops.items()
                    if any(p in name for p in op_parts))
    c = rec["config"]
    kernel_bound_s = arch.load(c["work"]).kernel_bound_s
    bound = sum(kernel_bound_s(c, rec["plans"], n, routes, rec["peaks"])
                for n in ns)
    if not bound or not spent:
        return None
    return 100.0 * bound / spent
