from repro.kernels.dsmm.ops import dsmm  # noqa: F401
from repro.kernels.dsmm.ref import dsmm_ref  # noqa: F401
from repro.kernels.contract import KernelContract, register

# dynamic slot-encoded SpMM: runtime pattern in a fixed nnz_max slot
# array (plus one coverage slot per block-row); n is zero-padded to a
# multiple of tn, and a block edge under 8 to 8 (ops.slot_walk)
CONTRACT = register(KernelContract(
    kernel="dsmm",
    routes=("dynamic_pallas",),
    dtypes=("float32", "bfloat16", "float16"),
    min_block=1,
    max_block=128,
    divisibility=("m % b == 0", "k % b == 0"),
    grid="(slots) x (n // tn) accumulate/flush walk over row-sorted "
         "slots, grid_m = m // b",
    capacity="slot_capacity",
    pallas=True,
))
