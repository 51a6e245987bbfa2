"""Share of the chip's peak in decode steps: for each step of the window
the larger of its model FLOPs over peak FLOP/s and its model bytes (the
stored weights, the KV of the live positions) over peak bandwidth,
summed, over the steps' host wall time.  Sparse matrices count their
nonzero blocks.  The counts are the configuration's work module's."""
import arch


def read(rec):
    if rec["peaks"] is None:
        return None
    w0, w1 = rec["window"]
    c = rec["config"]
    work = arch.load(c["work"])
    bound = spent = 0.0
    for a, b, ctx in rec["steps"]:
        if w0 <= a < w1:
            bound += work.roofline_s(*work.decode_step(c, ctx), rec["peaks"])
            spent += b - a
    return 100.0 * bound / spent if spent else None
