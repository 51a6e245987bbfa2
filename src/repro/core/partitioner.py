"""Static-sparsity partitioner (PopSparse §3.2, Fig. 1a).

The paper's static partitioner knows the sparsity pattern at compile time
and exploits it twice:

1. it splits the contraction (``k``) dimension at **uneven** positions so
   every partition holds the *same number of non-zeros* (perfect load
   balance, no runtime redistribution);
2. it re-orders the non-zero values once, at weight-upload time, to match
   the on-device distribution, so no extra exchange is needed at runtime.

On TPU the two consumers of this information are

* the **Pallas grid** -- logical ``b x b`` blocks are packed into MXU-
  aligned tiles; the exact list of non-empty tiles becomes the (compile-
  time constant) grid metadata, so the kernel executes *only* useful
  steps (``pack_tiles``);
* the **mesh** -- the ``model`` axis takes one nnz-balanced k-range each
  (``balanced_k_splits`` + ``shard_blocks_by_k``), so tensor-parallel
  SpMM needs a single output ``psum`` -- the paper's "final reduction
  across tiles", lifted to the pod level.

Everything here runs on host numpy at trace time: it *is* the compile-
time step of the paper.
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.bsr import BlockSparseMatrix, check_unique_blocks


@dataclasses.dataclass(frozen=True)
class TilePacking:
    """Logical blocks packed into physical (tm, tk) tiles.

    ``tile_rows/tile_cols`` are host constants listing the non-empty tiles
    in row-major order (every output row-tile is covered -- empty rows get
    one zero tile so the kernel always writes every output block).
    ``num_tiles`` is the static grid extent.
    """

    tile_rows: np.ndarray     # [T] int32
    tile_cols: np.ndarray     # [T] int32
    values: jax.Array         # [T, tm, tk]
    tm: int
    tk: int
    grid: Tuple[int, int]     # (Mt, Kt) tile grid of the full matrix
    shape: Tuple[int, int]    # (m, k) logical shape

    @property
    def num_tiles(self) -> int:
        return int(self.tile_rows.shape[0])

    @property
    def occupancy(self) -> float:
        """Fraction of packed-tile area holding logical non-zero blocks."""
        dense_area = self.num_tiles * self.tm * self.tk
        return float(self._nnz_area) / dense_area if dense_area else 0.0

    # populated by pack_tiles
    _nnz_area: int = 0


@dataclasses.dataclass(frozen=True)
class PackingPlan:
    """One-time host analysis of a static pattern's tile packing.

    Splits ``pack_tiles`` into its two phases: this object is the pattern
    half (pure host metadata, computed once per pattern -- the plan-first
    contract of ``repro.sparse``); ``pack_values`` is the value half (a
    device scatter that re-runs per call while weights train).
    """

    tile_rows: np.ndarray     # [T] int32
    tile_cols: np.ndarray     # [T] int32
    block_slot: np.ndarray    # [nnz] tile-stack slot of each logical block
    in_r: np.ndarray          # [nnz] block row within its tile
    in_c: np.ndarray          # [nnz] block col within its tile
    tm: int
    tk: int
    grid: Tuple[int, int]     # (Mt, Kt)
    shape: Tuple[int, int]    # (m, k)
    block_size: int
    nnz_blocks: int

    @property
    def num_tiles(self) -> int:
        return int(self.tile_rows.shape[0])

    @property
    def occupancy(self) -> float:
        dense_area = self.num_tiles * self.tm * self.tk
        nnz_area = self.nnz_blocks * self.block_size ** 2
        return float(nnz_area) / dense_area if dense_area else 0.0


def plan_packing(row_idx: np.ndarray, col_idx: np.ndarray,
                 shape: Tuple[int, int], block_size: int,
                 tm: int = 128, tk: int = 128) -> PackingPlan:
    """Pattern phase of ``pack_tiles``: which tiles exist and where each
    logical block lands.  Host-only, runs once per pattern."""
    m, k = shape
    b = block_size
    if tm % b or tk % b:
        raise ValueError(f"tile ({tm},{tk}) not divisible by block {b}")
    mt, kt = -(-m // tm), -(-k // tk)
    rpb, cpb = tm // b, tk // b  # logical blocks per tile, each dim

    rows = np.asarray(row_idx)
    cols = np.asarray(col_idx)
    # a duplicate block would be silently summed by pack_values' .add
    # scatter -- every plan path funnels through here, so this is the
    # backstop for patterns built from raw index arrays
    check_unique_blocks(rows, cols, (-(-m // b), -(-k // b)))
    t_r, t_c = rows // rpb, cols // cpb
    lin = t_r * kt + t_c
    uniq = np.unique(lin)
    # coverage: every row-tile must appear at least once
    present_rows = set((uniq // kt).tolist())
    pad = np.asarray([r * kt for r in range(mt) if r not in present_rows],
                     dtype=uniq.dtype)
    uniq = np.sort(np.concatenate([uniq, pad]))
    slot_of = {int(v): i for i, v in enumerate(uniq)}

    return PackingPlan(
        tile_rows=(uniq // kt).astype(np.int32),
        tile_cols=(uniq % kt).astype(np.int32),
        block_slot=np.asarray([slot_of[int(v)] for v in lin], np.int64),
        in_r=(rows % rpb).astype(np.int64),
        in_c=(cols % cpb).astype(np.int64),
        tm=tm, tk=tk, grid=(mt, kt), shape=(m, k), block_size=b,
        nnz_blocks=len(rows))


def pack_values(plan: PackingPlan, values, *, pad: int = 0) -> jax.Array:
    """Value phase of ``pack_tiles``: scatter ``[nnz, b, b]`` blocks into
    the ``[T, tm, tk]`` tile stack laid out in kernel-visit order, with
    ``pad`` all-zero tiles appended (``[T + pad, tm, tk]``).
    Jit-compatible (metadata is host constants)."""
    b = plan.block_size
    rpb, cpb = plan.tm // b, plan.tk // b
    t = plan.num_tiles + pad
    with jax.named_scope("pack_values"):
        vals = jnp.asarray(values)
        tiles = jnp.zeros((t, rpb, b, cpb, b), vals.dtype)
        tiles = tiles.at[jnp.asarray(plan.block_slot),
                         jnp.asarray(plan.in_r), :,
                         jnp.asarray(plan.in_c), :].add(vals)
        return tiles.reshape(t, plan.tm, plan.tk)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class PackedTiles:
    """Static block-sparse values already in the bsmm kernels' layout:
    the ``[T + 1, tm, tk]`` tile stack of ``pack_values(plan, values,
    pad=1)`` -- kernel-visit order plus the balanced walk's zero pad
    tile, so one stack serves both bsmm routes at every ``n``.  Forward-
    only callers with fixed weights (a serving engine) pack once, at
    weight-load, and pass this in place of the ``[nnz, b, b]`` values;
    the static plans' bsmm routes then skip the per-call relayout.
    Leading axes (a layer stack) ride along for ``jax.lax.scan``."""

    tiles: jax.Array


@dataclasses.dataclass(frozen=True)
class SwizzlePlan:
    """Row-swizzle pre-pass (Gale et al. 2020 §5.1, row binning): assign
    row-tiles to ``num_bins`` equal-work bins by sorted-snake dealing
    over their tile counts, so a balanced kernel grid can walk one bin
    per (parallel) grid lane with near-equal steps per lane.

    ``order`` is the swizzled visit order (bins concatenated, row-tiles
    ascending within a bin); ``inverse`` is its inverse permutation --
    the balanced kernels fold it into the output index map (each step
    writes its *original* row-tile), so no runtime un-permute runs.
    """

    order: np.ndarray       # [R] row-tiles in visit order
    inverse: np.ndarray     # [R] inverse permutation of ``order``
    bin_of: np.ndarray      # [R] owning bin per row-tile
    num_bins: int
    steps_per_bin: int      # max per-bin tile count (the padded lane length)
    loads: np.ndarray       # [num_bins] tile count per bin


def plan_swizzle(row_counts: np.ndarray,
                 num_bins: int | None = None) -> SwizzlePlan:
    """Bin row-tiles so per-bin work (tile counts) is equalized.

    Sorted-snake dealing: sort rows by count descending, deal them into
    bins boustrophedon (0..B-1, B-1..0, ...).  For power-law row
    profiles this bounds the max-bin load close to the mean -- the
    row-swizzle load balance of Gale et al. without any runtime cost.
    """
    counts = np.asarray(row_counts, np.int64)
    r = int(counts.size)
    nb = min(int(num_bins) if num_bins else 8, max(r, 1))
    nb = max(nb, 1)
    order_desc = np.argsort(-counts, kind="stable")
    bin_of = np.zeros(r, np.int32)
    for i, row in enumerate(order_desc):
        pos, rnd = i % nb, i // nb
        bin_of[row] = pos if rnd % 2 == 0 else nb - 1 - pos
    loads = np.bincount(bin_of, weights=counts,
                        minlength=nb).astype(np.int64)
    order = np.lexsort((np.arange(r), bin_of))
    inverse = np.argsort(order)
    steps = int(loads.max()) if r else 0
    return SwizzlePlan(order.astype(np.int64), inverse.astype(np.int64),
                       bin_of, nb, steps, loads)


@dataclasses.dataclass(frozen=True)
class BalancedPacking:
    """Swizzle-composed tile packing (plan-first contract): the base
    row-major ``PackingPlan`` (``pack_values`` layout is unchanged) plus
    the per-bin visit schedule the balanced kernels prefetch.

    ``visit_slot[g, s]`` is the tile-stack slot bin ``g`` multiplies at
    step ``s`` -- or ``base.num_tiles``, the appended all-zero pad tile,
    once the bin's real work is exhausted.  Pad steps keep the bin's
    last real row so the walk's flush fires once, at the lane end.
    ``visit_rows`` carries *original* row-tile ids: the inverse swizzle
    permutation is applied to the output by construction.
    """

    base: PackingPlan
    swizzle: SwizzlePlan
    visit_slot: np.ndarray   # [num_bins, steps] int32
    visit_rows: np.ndarray   # [num_bins, steps] int32 (original row-tiles)
    visit_cols: np.ndarray   # [num_bins, steps] int32

    @property
    def num_bins(self) -> int:
        return int(self.visit_slot.shape[0])

    @property
    def steps_per_bin(self) -> int:
        return int(self.visit_slot.shape[1])


def plan_packing_balanced(row_idx: np.ndarray, col_idx: np.ndarray,
                          shape: Tuple[int, int], block_size: int,
                          tm: int = 128, tk: int = 128,
                          num_bins: int | None = None) -> BalancedPacking:
    """Pattern phase of the balanced (row-swizzled) packing: the base
    ``plan_packing`` metadata plus the snake-binned visit schedule.
    Host-only, runs once per pattern."""
    base = plan_packing(row_idx, col_idx, shape, block_size, tm, tk)
    mt = base.grid[0]
    counts = np.bincount(base.tile_rows, minlength=mt)
    sw = plan_swizzle(counts, num_bins)
    nb, steps = sw.num_bins, sw.steps_per_bin
    # base.tile_rows is sorted row-major: each row-tile's slots are one
    # contiguous range
    starts = np.searchsorted(base.tile_rows, np.arange(mt), side="left")
    ends = np.searchsorted(base.tile_rows, np.arange(mt), side="right")
    visit_slot = np.full((nb, steps), base.num_tiles, np.int32)  # pad tile
    visit_rows = np.zeros((nb, steps), np.int32)
    visit_cols = np.zeros((nb, steps), np.int32)
    for g in range(nb):
        rows_g = np.flatnonzero(sw.bin_of == g)
        slots = np.concatenate([np.arange(starts[r], ends[r])
                                for r in rows_g]) if rows_g.size else \
            np.zeros(0, np.int64)
        t = slots.size
        visit_slot[g, :t] = slots
        visit_rows[g, :t] = base.tile_rows[slots]
        visit_cols[g, :t] = base.tile_cols[slots]
        if t:                      # pad keeps the lane's last real row
            visit_rows[g, t:] = visit_rows[g, t - 1]
    return BalancedPacking(base, sw, visit_slot, visit_rows, visit_cols)


def pack_tiles(bsr: BlockSparseMatrix, tm: int = 128, tk: int = 128) -> TilePacking:
    """Pack a static BSR matrix into MXU-aligned dense tiles.

    This is the TPU analogue of PopSparse's compile-time value re-ordering:
    the returned ``values`` tensor is laid out exactly in kernel-visit
    order, and the index arrays are baked into the grid as scalar-prefetch
    constants.  (Composition of ``plan_packing`` + ``pack_values``.)
    """
    if not bsr.is_static:
        raise ValueError("pack_tiles requires a static (host-indexed) pattern")
    meta = plan_packing(bsr.row_idx, bsr.col_idx, bsr.shape,
                        bsr.block_size, tm, tk)
    tiles = pack_values(meta, bsr.values)
    packing = TilePacking(meta.tile_rows, meta.tile_cols, tiles, tm, tk,
                          meta.grid, bsr.shape)
    object.__setattr__(packing, "_nnz_area", int(bsr.nnz_blocks)
                       * bsr.block_size ** 2)
    return packing


@dataclasses.dataclass(frozen=True)
class TransposePlan:
    """One-time host analysis of a pattern's transpose (plan-first
    contract): the backward transposed-SpMM plans run on ``W^T``'s
    pattern, which is the same nnz blocks re-sorted row-major in
    ``(col, row)`` coordinates with each block transposed.  ``perm`` is
    the value permutation (applied per call while weights train);
    ``row_idx``/``col_idx`` are the transposed pattern's host metadata.
    """

    perm: np.ndarray        # [nnz] source block for transposed slot z
    row_idx: np.ndarray     # [nnz] int32 (block rows of W^T == cols of W)
    col_idx: np.ndarray     # [nnz] int32 (block cols of W^T == rows of W)
    shape: Tuple[int, int]  # (k, m) -- the transposed logical shape
    block_size: int


def plan_transpose(row_idx: np.ndarray, col_idx: np.ndarray,
                   shape: Tuple[int, int],
                   block_size: int) -> TransposePlan:
    """Pattern phase of the backward transpose: computed once per
    pattern, shared by every sibling dL/dx plan on it.  The value phase
    (``values[perm].transpose(0, 2, 1)``) is a per-call device gather."""
    rows = np.asarray(row_idx, np.int64)
    cols = np.asarray(col_idx, np.int64)
    perm = np.lexsort((rows, cols))      # row-major in (col, row) coords
    m, k = shape
    return TransposePlan(perm, cols[perm].astype(np.int32),
                         rows[perm].astype(np.int32), (k, m), block_size)


def apply_transpose(plan: TransposePlan, values) -> jax.Array:
    """Value phase: permute the ``[nnz, b, b]`` blocks into the
    transposed pattern's row-major order and transpose each block.
    Jit-compatible (metadata is host constants)."""
    vals = jnp.asarray(values)
    return vals[jnp.asarray(plan.perm)].transpose(0, 2, 1)


def balanced_k_splits(block_mask: np.ndarray, q: int) -> np.ndarray:
    """Choose ``q`` *uneven* split positions over block-columns balancing nnz.

    Returns boundaries ``[q+1]`` over the block-column index (``k`` dim),
    with ``boundaries[0]=0`` and ``boundaries[q]=Kb``.  Faithful to paper
    Fig. 1a: split positions adapt to the known pattern.
    """
    col_nnz = np.asarray(block_mask, bool).sum(axis=0)
    kb = len(col_nnz)
    if q > kb:
        raise ValueError(f"q={q} partitions > {kb} block columns")
    total = int(col_nnz.sum())
    prefix = np.concatenate([[0], np.cumsum(col_nnz)])
    # target nnz per partition; walk boundaries greedily on the prefix
    # sum.  A boundary that lands on a *plateau* of the prefix (a run of
    # empty columns) is free to slide anywhere on the plateau without
    # changing any shard's nnz -- slide it toward the even-split
    # position so empty columns spread across shards instead of piling
    # every zero column (plus forced 1-column slivers) onto the last
    # shards when the mass sits in a prefix/suffix of the columns.
    boundaries = [0]
    for p in range(1, q):
        target = total * p / q
        e = int(round(kb * p / q))           # even-split position
        jlo = int(np.searchsorted(prefix, target, side="left"))
        jhi = jlo
        while jhi + 1 <= kb and prefix[jhi + 1] == prefix[jlo]:
            jhi += 1
        j = min(max(e, jlo), jhi)
        # leave room for the remaining partitions (each needs >= 1 col)
        j = max(j, boundaries[-1] + 1)
        hi = kb - (q - p)
        if j > hi:
            # forced clamp: whatever we ceded is empty column tail --
            # fall back toward the even position rather than hugging hi
            j = max(boundaries[-1] + 1, min(hi, e))
        boundaries.append(j)
    boundaries.append(kb)
    return np.asarray(boundaries, np.int64)


def even_k_splits(kb: int, q: int) -> np.ndarray:
    """Dynamic-mode fixed equal splits (paper §3.3): last may be smaller."""
    size = -(-kb // q)
    return np.minimum(np.arange(q + 1) * size, kb).astype(np.int64)


@dataclasses.dataclass(frozen=True)
class ShardedBlocks:
    """Per-mesh-shard stacked block arrays for TP SpMM via shard_map.

    Arrays are stacked on a leading ``q`` axis (to be sharded over the
    ``model`` mesh axis) and padded to a common ``slots`` length with
    zero-valued blocks at (row 0, col boundaries[i]) so padded slots
    contribute exactly zero.
    """

    values: jax.Array    # [q, slots, b, b]
    row_idx: jax.Array   # [q, slots] int32
    col_idx: jax.Array   # [q, slots] int32 (GLOBAL block-col index)
    boundaries: np.ndarray
    shape: Tuple[int, int]
    block_size: int
    real_counts: np.ndarray  # [q] nnz blocks actually owned per shard

    @property
    def q(self) -> int:
        return int(self.values.shape[0])

    @property
    def slots(self) -> int:
        return int(self.values.shape[1])


@dataclasses.dataclass(frozen=True)
class KShardPlan:
    """One-time host analysis of the nnz-balanced k-partition.

    Pattern half of ``shard_blocks_by_k`` (plan-first contract): split
    boundaries + per-block shard/slot destinations, all host constants.
    ``apply_k_shards`` is the per-call value half.
    """

    boundaries: np.ndarray   # [q+1] block-col split positions
    row_idx: np.ndarray      # [q, slots] int32 (host; padding row 0)
    col_idx: np.ndarray      # [q, slots] int32 (padding -> owned column)
    dst_q: np.ndarray        # [nnz] destination shard, in src_order
    dst_slot: np.ndarray     # [nnz] destination slot, in src_order
    src_order: np.ndarray    # [nnz] source permutation (stable by owner)
    shape: Tuple[int, int]
    block_size: int
    real_counts: np.ndarray  # [q] nnz blocks actually owned per shard
    balanced: bool = True    # nnz-balanced uneven splits vs fixed even

    @property
    def q(self) -> int:
        return int(self.row_idx.shape[0])

    @property
    def slots(self) -> int:
        return int(self.row_idx.shape[1])


def plan_k_shards(bsr: BlockSparseMatrix, q: int,
                  *, balanced: bool = True) -> KShardPlan:
    """Pattern phase of ``shard_blocks_by_k``: boundaries + destinations."""
    if not bsr.is_static:
        raise ValueError("plan_k_shards requires static pattern")
    mask = bsr.block_mask()
    mb, kb = mask.shape
    if q < 1 or q > kb:
        raise ValueError(f"q={q} k-shards outside [1, {kb} block "
                         f"columns] for shape {bsr.shape} at block "
                         f"{bsr.block_size}")
    bounds = (balanced_k_splits(mask, q) if balanced else even_k_splits(kb, q))
    rows = np.asarray(bsr.row_idx)
    cols = np.asarray(bsr.col_idx)
    owner = np.searchsorted(bounds, cols, side="right") - 1
    counts = np.bincount(owner, minlength=q)
    slots = int(counts.max()) if len(counts) else 1
    slots = max(slots, 1)

    row_out = np.zeros((q, slots), np.int32)
    col_out = np.zeros((q, slots), np.int32)
    for s in range(q):
        col_out[s, :] = bounds[s]  # padding points at an owned column
    fill = np.zeros(q, np.int64)
    src_order = np.argsort(owner, kind="stable")
    dst_q = owner[src_order]
    dst_slot = np.empty_like(dst_q)
    for i, qq in enumerate(dst_q):
        dst_slot[i] = fill[qq]
        fill[qq] += 1
    row_out[dst_q, dst_slot] = rows[src_order]
    col_out[dst_q, dst_slot] = cols[src_order]
    return KShardPlan(bounds, row_out, col_out, dst_q, dst_slot, src_order,
                      bsr.shape, bsr.block_size, counts, balanced)


def apply_k_shards(plan: KShardPlan, values) -> ShardedBlocks:
    """Value phase: scatter ``[nnz, b, b]`` blocks into the stacked
    ``[q, slots, b, b]`` shard layout.  Jit-compatible."""
    b = plan.block_size
    vals = jnp.asarray(values)
    val_out = jnp.zeros((plan.q, plan.slots, b, b), vals.dtype)
    val_out = val_out.at[jnp.asarray(plan.dst_q),
                         jnp.asarray(plan.dst_slot)].set(
        vals[jnp.asarray(plan.src_order)])
    return ShardedBlocks(val_out, jnp.asarray(plan.row_idx),
                         jnp.asarray(plan.col_idx), plan.boundaries,
                         plan.shape, b, plan.real_counts)


def shard_blocks_by_k(bsr: BlockSparseMatrix, q: int,
                      *, balanced: bool = True) -> ShardedBlocks:
    """Distribute blocks over ``q`` k-partitions (static partitioner output).

    ``balanced=True`` uses nnz-balanced uneven splits (static mode);
    ``balanced=False`` uses fixed equal splits (dynamic mode) -- useful to
    measure the imbalance cost the paper attributes to dynamic sparsity.
    (Composition of ``plan_k_shards`` + ``apply_k_shards``.)
    """
    return apply_k_shards(plan_k_shards(bsr, q, balanced=balanced),
                          bsr.values)


@dataclasses.dataclass(frozen=True)
class EvolvePlan:
    """One-time host analysis of a pattern *evolution* (old -> new).

    Pattern half of a RigL-style topology update on a static plan
    (plan-first contract, same split as ``plan_packing``/``pack_values``):
    for each block of the new pattern, the source slot in the old values
    stack, or -1 for a freshly grown block.  ``apply_evolution`` is the
    per-call value half -- a device gather where carried blocks keep
    their values exactly, grown blocks start at zero, and dropped blocks
    simply have no destination (RigL semantics, Evci et al. 2019 §3).
    """

    src_slot: np.ndarray      # [nnz_new] int64; -1 marks a grown block
    carried: int              # blocks present in both patterns
    dropped: int              # old blocks absent from the new pattern
    grown: int                # new blocks absent from the old pattern


def plan_evolution(old_rows: np.ndarray, old_cols: np.ndarray,
                   new_rows: np.ndarray, new_cols: np.ndarray,
                   grid: Tuple[int, int]) -> EvolvePlan:
    """Map each new-pattern block to its old values slot (host, once per
    topology step).  Neither pattern needs to be sorted; both must be
    duplicate-free (``check_unique_blocks``)."""
    mb, kb = grid
    check_unique_blocks(old_rows, old_cols, grid)
    check_unique_blocks(new_rows, new_cols, grid)
    old_lin = np.asarray(old_rows, np.int64) * kb + np.asarray(old_cols,
                                                               np.int64)
    new_lin = np.asarray(new_rows, np.int64) * kb + np.asarray(new_cols,
                                                               np.int64)
    if old_lin.size:
        order = np.argsort(old_lin)
        pos = np.searchsorted(old_lin[order], new_lin)
        pos_c = np.minimum(pos, old_lin.size - 1)
        found = old_lin[order][pos_c] == new_lin
        src = np.where(found, order[pos_c], -1).astype(np.int64)
    else:
        src = np.full(new_lin.size, -1, np.int64)
    carried = int((src >= 0).sum())
    return EvolvePlan(src, carried,
                      int(old_lin.size) - carried,
                      int(new_lin.size) - carried)


def apply_evolution(plan: EvolvePlan, old_values) -> jax.Array:
    """Value half of a topology update: carry ``[nnz_old, b, b]`` blocks
    into the new pattern's ``[nnz_new, b, b]`` stack (grown blocks
    zero-initialized).  Jit-compatible -- the map is a host constant."""
    vals = jnp.asarray(old_values)
    nnz_new = int(plan.src_slot.shape[0])
    if vals.shape[0] == 0:
        return jnp.zeros((nnz_new,) + vals.shape[1:], vals.dtype)
    src = jnp.asarray(plan.src_slot)
    gathered = vals[jnp.clip(src, 0, vals.shape[0] - 1)]
    keep = (src >= 0).reshape((-1,) + (1,) * (vals.ndim - 1))
    return jnp.where(keep, gathered, jnp.zeros_like(gathered))


def balance_report(counts: np.ndarray) -> dict:
    """Load-balance diagnostics (used by tests + benchmarks)."""
    counts = np.asarray(counts)
    if counts.size == 0:
        # degenerate pattern (no owners): a zeroed report, not a crash
        return {"max": 0, "min": 0, "mean": 0.0, "imbalance": 0.0,
                "padding_waste": 0.0, "frac_empty": 0.0, "cv": 0.0}
    mx, mn, mean = counts.max(), counts.min(), counts.mean()
    return {
        "max": int(mx), "min": int(mn), "mean": float(mean),
        # max/mean alone hides all-empty owners (min=0 still reports a
        # finite ratio): frac_empty + cv surface that skew honestly
        "imbalance": float(mx / mean) if mean else 0.0,
        "padding_waste": float((mx * len(counts) - counts.sum())
                               / max(1, counts.sum())),
        "frac_empty": float((counts == 0).mean()),
        "cv": float(counts.std() / mean) if mean else 0.0,
    }
