"""Share of the chip's peak in admissions: for each admission of the
window the larger of the FLOPs of prefilling its true prompt tokens over
peak FLOP/s and its bytes (the stored weights read once, the prompt's KV
written once) over peak bandwidth, summed, over the admissions' host
wall time.  Bucket padding is not work; sparse matrices count their
nonzero blocks.  The counts are the configuration's work module's."""
import arch


def read(rec):
    if rec["peaks"] is None:
        return None
    w0, w1 = rec["window"]
    c = rec["config"]
    work = arch.load(c["work"])
    bound = spent = 0.0
    for a, b, n, _ in rec["admits"]:
        if w0 <= a < w1:
            bound += work.roofline_s(*work.prefill(c, n), rec["peaks"])
            spent += b - a
    return 100.0 * bound / spent if spent else None
