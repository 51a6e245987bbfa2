"""Roofline share of the dense kernel (``kernels/dense_mm``: route
``dense_pallas``) in decode steps: the bound of every call at the call's
n, counted from the plans' routes times the steps run, over the device
time of the kernel's events in the trace.  On a TPU v5e the kernel's
operation is named after its jitted wrapper (``dense_mm_call.<n>``)."""
from _kernel import roofline_share


def read(rec):
    return roofline_share(rec, program="decode", routes=("dense_pallas",),
                          op_parts=("dense_mm_call", "_mm_kernel"))
