"""Neural-network layers backed by PopSparse-style block-sparse matmul.

The framework uses a light functional module convention throughout:
each layer is a small class holding *static* configuration (shapes,
patterns -- compile-time data, exactly what PopSparse fixes at graph
construction) with two methods:

    init(key)            -> params pytree (trainable leaves only)
    apply(params, x, ..) -> output

Static patterns (np index arrays) live on the layer object, NOT in the
params pytree, so they are trace-time constants -- the compile-time
contract of static sparsity.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import dynamic_sparse as dsp
from repro.core import masks as masks_lib
from repro.core.bsr import BlockSparseMatrix


def _fan_in_init(key, nnz, b, fan_in, dtype):
    scale = 1.0 / np.sqrt(max(1, fan_in))
    return (jax.random.normal(key, (nnz, b, b)) * scale).astype(dtype)


@dataclasses.dataclass(frozen=True)
class SparseLinear:
    """y = x @ (M ⊙ W)^T (+ bias) with static block pattern M.

    ``pattern`` is a host block mask ``[out/b, in/b]``; effective density
    after masking is the paper's ``d``.
    """

    in_features: int
    out_features: int
    block_size: int
    pattern: np.ndarray                 # [out/b, in/b] bool (host)
    use_bias: bool = False
    dtype: object = jnp.float32
    backend: str = "auto"     # dispatch mode ("auto" / route id / family)
    # backward route policies for the plan-level custom_vjp (training
    # runs the planned transposed-SpMM + SDDMM siblings; "auto" races
    # the candidates, a route id forces one -- see PlanContext)
    grad_backend: str = "auto"
    sddmm_backend: str = "auto"

    def __post_init__(self):
        ob, ib = self.out_features // self.block_size, \
            self.in_features // self.block_size
        if self.pattern.shape != (ob, ib):
            raise ValueError(
                f"pattern {self.pattern.shape} != grid {(ob, ib)}")

    @property
    def nnz_blocks(self) -> int:
        return int(self.pattern.sum())

    @property
    def density(self) -> float:
        return self.nnz_blocks / self.pattern.size

    def _indices(self):
        rows, cols = np.nonzero(self.pattern)
        order = np.lexsort((cols, rows))
        return rows[order].astype(np.int32), cols[order].astype(np.int32)

    def init(self, key) -> dict:
        # fan-in of a sparse layer: expected nnz inputs per output row
        fan_in = self.in_features * self.density
        params = {"values": _fan_in_init(key, self.nnz_blocks,
                                         self.block_size, fan_in, self.dtype)}
        if self.use_bias:
            params["bias"] = jnp.zeros((self.out_features,), self.dtype)
        return params

    def as_bsr(self, params) -> BlockSparseMatrix:
        rows, cols = self._indices()
        return BlockSparseMatrix(params["values"], rows, cols,
                                 (self.out_features, self.in_features),
                                 self.block_size)

    def _plan_ctx(self):
        # the caller's ambient plan policy (a serving engine's pool, mesh
        # and forward-only flag, or a forced reference mode) holds unless
        # this layer pins a route of its own
        from repro import sparse as sparse_api
        over = {}
        if self.backend != "auto":
            over["mode"] = (f"static_{self.backend}"
                            if self.backend in ("xla", "pallas")  # old names
                            else self.backend)
        if self.grad_backend != "auto":
            over["grad_mode"] = self.grad_backend
        if self.sddmm_backend != "auto":
            over["sddmm_mode"] = self.sddmm_backend
        return dataclasses.replace(sparse_api.current_ctx(), **over)

    def pack(self, values):
        """Forward-only serving: ``values`` (``[..., nnz, b, b]``; leading
        axes such as a layer stack are walked one matrix at a time) as
        the bsmm kernels' tile stack (``sparse.pack``).  Put the result
        in params as ``packed``, beside the values: ``apply`` then skips
        the per-call relayout wherever its plan ``takes_packed``."""
        from repro import sparse as sparse_api
        lead, blocks = values.shape[:-3], values.shape[-3:]
        packed = jax.lax.map(
            lambda v: sparse_api.pack(self.as_bsr({"values": v})),
            values.reshape((-1,) + blocks))
        return jax.tree.map(
            lambda t: t.reshape(lead + t.shape[1:]), packed)

    def apply(self, params, x: jax.Array) -> jax.Array:
        # plan-first: the pattern analysis + route decision happen once
        # per (pattern, shape) in the sparse plan cache; training steps
        # re-enter with fresh values only.  A serving engine's params
        # also carry the pre-packed tiles (``pack``), which the plan
        # takes in place of the values where its route multiplies them
        from repro import sparse as sparse_api
        bsr = self.as_bsr(params)
        y = sparse_api.spmm_nt(bsr, x.astype(params["values"].dtype),
                               ctx=self._plan_ctx(),
                               packed=params.get("packed"))
        if self.use_bias:
            y = y + params["bias"]
        return y

    def evolve(self, new_pattern: np.ndarray, params: Optional[dict] = None):
        """Topology update (RigL drop/grow): returns ``(layer, params)``
        for ``new_pattern`` ``[out/b, in/b]``.

        Values of carried blocks are copied into their new slot order,
        grown blocks start at zero (RigL's convention), and every cached
        plan built on the old pattern is ``sparse.evolve``-d onto the new
        one -- so the next ``apply`` is a plan-cache hit with zero route
        decisions (unless the pattern drifted past the context's
        ``evolve_drift`` guardrail, which re-races).
        """
        from repro import sparse as sparse_api
        from repro.core import partitioner
        new_pattern = np.asarray(new_pattern, bool)
        layer = dataclasses.replace(self, pattern=new_pattern)
        if params is not None:
            old_r, old_c = self._indices()
            new_r, new_c = layer._indices()
            eplan = partitioner.plan_evolution(
                old_r, old_c, new_r, new_c, new_pattern.shape)
            new_params = dict(params)
            new_params.pop("packed", None)      # tiles of the old pattern
            new_params["values"] = partitioner.apply_evolution(
                eplan, params["values"])
            params = new_params
        # migrate every cached plan (any n) onto the new pattern
        dummy = jnp.zeros((self.nnz_blocks, self.block_size,
                           self.block_size), self.dtype)
        old_bsr = BlockSparseMatrix(
            dummy, *self._indices(),
            (self.out_features, self.in_features), self.block_size)
        new_bsr = BlockSparseMatrix(
            jnp.zeros((layer.nnz_blocks, self.block_size,
                       self.block_size), self.dtype),
            *layer._indices(),
            (self.out_features, self.in_features), self.block_size)
        sparse_api.evolve_plans(old_bsr, new_bsr)
        return layer, params

    @classmethod
    def random_pattern(cls, key_unused, in_features, out_features,
                       block_size, density, *, seed=0, **kw):
        pattern = masks_lib.random_block_mask(
            out_features, in_features, block_size, density, seed=seed)
        return cls(in_features, out_features, block_size, pattern, **kw)


@dataclasses.dataclass(frozen=True)
class DynamicSparseLinear:
    """Dense master weight + runtime block mask (dynamic sparse training).

    Matches PopSparse dynamic mode: capacity fixed by ``d_max`` at compile
    time; the mask is data and may change every step (RigL-style regrowth,
    see ``pruning.py``).  Params carry the dense master weight and the
    mask; ``apply`` encodes + multiplies through the dynamic path.
    """

    in_features: int
    out_features: int
    block_size: int
    d_max: float
    use_bias: bool = False
    dtype: object = jnp.float32
    backend: str = "auto"     # forwarded to dispatch via dspmm

    @property
    def nnz_max(self) -> int:
        grid = (self.out_features // self.block_size) * \
            (self.in_features // self.block_size)
        return max(1, int(np.ceil(grid * self.d_max)))

    def init(self, key) -> dict:
        kw, km = jax.random.split(key)
        scale = 1.0 / np.sqrt(self.in_features * self.d_max)
        w = (jax.random.normal(
            kw, (self.out_features, self.in_features)) * scale).astype(self.dtype)
        mask = masks_lib.random_block_mask(
            self.out_features, self.in_features, self.block_size,
            self.d_max, seed=int(jax.random.randint(km, (), 0, 2**31 - 1)))
        params = {"w": w, "mask": jnp.asarray(mask)}
        if self.use_bias:
            params["bias"] = jnp.zeros((self.out_features,), self.dtype)
        return params

    def apply(self, params, x: jax.Array) -> jax.Array:
        op = dsp.encode(params["w"], params["mask"],
                        block_size=self.block_size, nnz_max=self.nnz_max)
        y = dsp.dspmm_nt(op, x.astype(params["w"].dtype),
                         backend=self.backend)
        if self.use_bias:
            y = y + params["bias"]
        return y


@dataclasses.dataclass(frozen=True)
class SparseFFN:
    """Transformer FFN with block-sparse weights (gated or plain).

    This is the framework's first-class integration of the paper: swap a
    dense FFN for a sparse one via config (``ffn_density``,
    ``ffn_block_size``) -- see configs/*.py sparse variants.
    """

    d_model: int
    d_ff: int
    block_size: int
    density: float
    gated: bool = True
    seed: int = 0
    dtype: object = jnp.float32

    def _layers(self):
        def mk(i, o, s):
            return SparseLinear.random_pattern(
                None, i, o, self.block_size, self.density,
                seed=self.seed + s, dtype=self.dtype)
        up = mk(self.d_model, self.d_ff, 1)
        down = mk(self.d_ff, self.d_model, 2)
        gate = mk(self.d_model, self.d_ff, 3) if self.gated else None
        return up, down, gate

    def init(self, key) -> dict:
        up, down, gate = self._layers()
        ks = jax.random.split(key, 3)
        params = {"up": up.init(ks[0]), "down": down.init(ks[1])}
        if gate is not None:
            params["gate"] = gate.init(ks[2])
        return params

    def apply(self, params, x: jax.Array) -> jax.Array:
        up, down, gate = self._layers()
        h = up.apply(params["up"], x)
        if gate is not None:
            g = gate.apply(params["gate"], x)
            h = jax.nn.silu(g) * h
        else:
            h = jax.nn.gelu(h)
        return down.apply(params["down"], h)

    def flops_per_token(self) -> float:
        n_mats = 3 if self.gated else 2
        return 2.0 * self.d_model * self.d_ff * self.density * n_mats
