"""Roofline share of the block-sparse kernels (``kernels/bsmm``: routes
``static_pallas`` and ``static_balanced``) in prefills: over the prefill
programs (``jit_prefill_fn``) that ran whole in the traced window, the
bound of every call, from the configuration's nonzero blocks at the
call's n (the bucket of the ``engine.admit`` span that ran the program),
over the device time of the kernels' operations in those programs.  A
prefill cut by the window's edge counts on neither side."""
from _kernel import roofline_share


def read(rec):
    return roofline_share(rec, program="prefill",
                          routes=("static_pallas", "static_balanced"),
                          op_parts=("bsmm",))
