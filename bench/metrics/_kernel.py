"""Shared by the kernel roofline readers: the roofline bound of the
calls a kernel made in the traced window, over its device time."""
import trace_reduce
import work


def roofline_share(rec, *, program: str, routes, op_parts):
    """``program`` is ``"decode"`` (every engine step in the traced
    window, at ``n`` = the engine batch) or ``"prefill"`` (every
    admission, at ``n`` = its bucket)."""
    red = rec["trace"]
    if red is None or rec["peaks"] is None or rec["traced"] is None:
        return None
    t0, t1 = rec["traced"]
    if program == "decode":
        ns = [rec["batch"] for a, _, _ in rec["steps"] if t0 <= a < t1]
    else:
        ns = [bucket or n for a, _, n, bucket in rec["admits"]
              if t0 <= a < t1]
    bound = sum(work.kernel_bound_s(rec["config"], rec["plans"], n, routes,
                                    rec["peaks"]) for n in ns)
    _, spent = trace_reduce.kernel_time(red, program, op_parts)
    if not bound or not spent:
        return None
    return 100.0 * bound / spent
