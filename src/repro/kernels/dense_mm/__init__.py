from repro.kernels.dense_mm.ops import dense_mm  # noqa: F401
from repro.kernels.dense_mm.ref import dense_mm_ref  # noqa: F401
from repro.kernels.contract import KernelContract, register

# dense tiled baseline: each dim is one block up to 128, else zero-padded
# to 128-wide tiles (kernels.tiling), so any shape is admitted; block
# size is irrelevant (dense has no blocks)
CONTRACT = register(KernelContract(
    kernel="dense_mm",
    routes=("dense_pallas",),
    dtypes=("float32", "bfloat16", "float16"),
    min_block=1,
    max_block=1024,
    divisibility=(),
    grid="(m // tm) x (n // tn) x (k // tk) over the padded dims, "
         "tm/tk/tn = tiling.dim_tile per dim",
    capacity="dense",
    pallas=True,
))
