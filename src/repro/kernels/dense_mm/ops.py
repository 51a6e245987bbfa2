"""Jit'd wrapper for the dense matmul baseline kernel."""
from __future__ import annotations

from repro.kernels.dense_mm.dense_mm import dense_mm_call
from repro.kernels.tiling import dim_tile, pad_to


def dense_mm(a, b, *, interpret: bool = False):
    """``a [m, k] @ b [k, n]``.  Each dimension is one block up to 128,
    else 128-wide tiles over its zero-padded extent."""
    m, k = a.shape
    _, n = b.shape
    (tm, mp), (tk, kp), (tn, np_) = dim_tile(m), dim_tile(k), dim_tile(n)
    a = pad_to(pad_to(a, 0, mp), 1, kp)
    b = pad_to(pad_to(b, 0, kp), 1, np_)
    y = dense_mm_call(a, b, tm=tm, tk=tk, tn=tn, interpret=interpret)
    return y[:m, :n] if (mp, np_) != (m, n) else y
