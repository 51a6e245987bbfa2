from repro.kernels.bsmm.ops import bsmm, bsmm_balanced  # noqa: F401
from repro.kernels.bsmm.ref import bsmm_ref  # noqa: F401
from repro.kernels.contract import KernelContract, register

# static block-sparse SpMM: the BSR operand fixes m % b == k % b == 0 by
# construction; tm/tk are block-multiple divisors of m/k, and n is
# zero-padded to a multiple of tn (kernels.tiling), so n is free
CONTRACT = register(KernelContract(
    kernel="bsmm",
    routes=("static_pallas",),
    dtypes=("float32", "bfloat16", "float16"),
    min_block=1,
    max_block=128,
    divisibility=("m % b == 0", "k % b == 0"),
    grid="(m // tm) x (n_padded // tn), tm/tk/tn from _pick_tiles; "
         "inner walk over the row's packed tiles",
    capacity="exact",
    pallas=True,
))

# row-swizzled balanced walk: same operand constraints and value layout
# as bsmm; the visit schedule (plan_packing_balanced) adds one zero pad
# tile per lane and a [bins, steps] scalar-prefetch schedule
BALANCED_CONTRACT = register(KernelContract(
    kernel="bsmm_balanced",
    routes=("static_balanced",),
    dtypes=("float32", "bfloat16", "float16"),
    min_block=1,
    max_block=128,
    divisibility=("m % b == 0", "k % b == 0"),
    grid="(n // tn) x bins x steps_per_bin, tm/tk/tn as bsmm; one "
         "parallel lane per snake-assigned row bin, arbitrary walk "
         "inside the lane (pads -> appended zero tile)",
    capacity="exact",
    pallas=True,
))
