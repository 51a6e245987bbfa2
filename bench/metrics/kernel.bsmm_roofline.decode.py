"""Roofline share of the block-sparse kernels (``kernels/bsmm``: routes
``static_pallas`` and ``static_balanced``) in decode steps: the bound of
every call, from the configuration's nonzero blocks at the call's n,
over the device time of the kernels' events in the trace.  The per-call
relayout and empty tile slots are not work.  On a TPU v5e the kernels'
operations are named after their jitted wrappers (``bsmm_call.<n>``,
``bsmm_balanced_call.<n>``)."""
from _kernel import roofline_share


def read(rec):
    return roofline_share(rec, program="decode",
                          routes=("static_pallas", "static_balanced"),
                          op_parts=("bsmm",))
