"""Grouped matmul kernel — rows sorted by group, each group's rows times that
group's weight matrix: the expert layer's two products (the sorted rows of
the assignments against the stacked expert weights).

  rows   [R, K]  sorted by group: group g owns rows offsets[g] ..
                 offsets[g] + sizes[g]; rows past the last group's end are
                 not computed (their output rows are left as they lie)
  w      [G, K, N]
  sizes  [G] int32, sum(sizes) <= R
  out    [R, N] in rows.dtype, each product accumulated in fp32

Grid: (column block of N, work item), the items a list made on the device
from the group sizes (`_work_list`) and the grid's dynamic size: one item a
(group, row tile) for the tiles each NON-EMPTY group's rows touch, group by
group, tiles ascending.  So an empty group costs nothing, rows past the last
group's end cost nothing, and consecutive items of one group keep the
weight block's index: the pipeline fetches each hit group's [K, tn] block of
`w` ONCE a column block, while the next one streams in behind it.  A row
tile that two groups share is visited by each of them in turn, each storing
its own rows under a row mask; the output block stays resident between
them.  A group's product is one [tm, K] x [K, tn] dot: K is whole in the
block, so no accumulator outlives a grid step.

Tiles come from the shapes alone (`row_tile`, `col_tile`): 128 rows (R
itself where smaller), and the widest column block, a multiple of 128
dividing N, whose [K, tn] weight block fits `_WEIGHT_BLOCK_BYTES`.  At the
expert cells' widths (bf16) the gate-and-up products take tn = 512 (hidden
6144), 1024 (4096) and 1536, all of N (2048); the down products 2048.

A kernel is traced and lowered to Mosaic at every call site: callers reach
it through `paddle_tpu.ops.grouped_matmul`, one `jax.jit`-wrapped entry, so a
step program lowers it once per distinct (R, K, N, dtype) and not once per
layer and rung.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._x64 import x64_off
from .paged_attention import _interpret

# rows of a tile: one MXU pass deep.  Fewer rows a tile would waste fewer
# rows on a group of a few tokens, but the product is weight-bound there
# and a shallower tile pays the MXU's weight loads for fewer rows
_ROW_TILE = 128
# a [K, tn] block of w, double-buffered by the pipeline: 16 MiB of VMEM
_WEIGHT_BLOCK_BYTES = 8 * 2 ** 20
# the weight blocks, the row tiles (128 x 6144 bf16: 1.5 MiB, doubled), the
# output blocks and the fp32 product of one step
_VMEM_LIMIT = 64 * 2 ** 20


def row_tile(R):
    """Rows of a row tile for R rows."""
    return min(_ROW_TILE, R)


def col_tile(K, N, dtype):
    """Columns of a weight block: N whole where [K, N] fits
    `_WEIGHT_BLOCK_BYTES`, else the widest multiple of 128 dividing N whose
    block does; 0 where none does."""
    itemsize = jnp.dtype(dtype).itemsize
    fits = [tn for tn in range(128, N + 1, 128)
            if N % tn == 0 and K * tn * itemsize <= _WEIGHT_BLOCK_BYTES]
    return max(fits, default=0)


def supports(rows_shape, w_shape, dtype) -> bool:
    """Shape predicate for ops.grouped_matmul's kernel-or-ragged_dot
    choice: rows [R, K] against w [G, K, N] in bf16 or fp32, K and N on
    whole 128-lane tiles, R a whole number of row tiles of whole sublane
    tiles (16 rows bf16, 8 fp32), and a weight block that fits."""
    if len(rows_shape) != 2 or len(w_shape) != 3 \
            or rows_shape[1] != w_shape[1]:
        return False
    (R, K), (G, _, N) = rows_shape, w_shape
    itemsize = jnp.dtype(dtype).itemsize
    if itemsize not in (2, 4) or R <= 0 or G <= 0:
        return False
    tm = row_tile(R)
    return (K % 128 == 0 and N % 128 == 0 and R % tm == 0
            and tm % (32 // itemsize) == 0 and col_tile(K, N, dtype) > 0)


def _tiles_of(sizes, tm):
    """(first row tile [G], row tiles [G], group ends [G]) of each group:
    0 tiles for an empty group.  In `lax` terms, not `jnp`'s (whose
    `cumsum`, `//` and `where` are jitted functions of their own): these
    few integer ops are traced and lowered for every shape of the kernel,
    and that is set-up time."""
    tm = jnp.int32(tm)
    ends = jax.lax.cumsum(sizes)
    first = jax.lax.div(ends - sizes, tm)
    # an empty group counts 0 whatever (ends - 1) / tm reads
    n_tiles = (sizes > 0) * (jax.lax.div(ends - 1, tm) - first + 1)
    return first, n_tiles, ends


def rows_walked(sizes, R):
    """Rows the kernel's work list covers for these group sizes [G] int32
    over R rows: its (group, row tile) items times the tile's rows (an
    int32 scalar; every column block walks the same list)."""
    return _rows_walked(sizes, row_tile(R))


# The two functions below are jitted (the tile and R static) for the same
# reason the kernel's entry is: every layer and rung calls them, and a
# jitted function is traced and lowered once per distinct shape in a
# program.

@functools.partial(jax.jit, static_argnums=(1,))
def _rows_walked(sizes, tm):
    _, n_tiles, _ = _tiles_of(sizes, tm)
    return jnp.sum(n_tiles, dtype=jnp.int32) * tm


@functools.partial(jax.jit, static_argnums=(1, 2))
def _work_list(sizes, R, tm):
    """The grid's items: (items, group_of [N], tile_of [N], starts [G],
    ends [G]) with N = R / tm + G - 1, the static bound (a tile is walked
    once for its own rows and once more for each group that starts inside
    it), and `items` of them real.  Compare-and-sum over [N, G], the
    latent-attention kernel's way: a few small fusions, no gather."""
    G = sizes.shape[0]
    first, n_tiles, ends = _tiles_of(sizes, tm)
    item_end = jax.lax.cumsum(n_tiles)
    w = jax.lax.iota(jnp.int32, R // tm + G - 1)
    group = jnp.minimum(jnp.sum(w[:, None] >= item_end, axis=1,
                                dtype=jnp.int32), G - 1)
    # item w's tile: its group's first tile + w's place among the group's
    # items (w - the group's first item); never below the first tile
    skew = jnp.sum((group[:, None] == jax.lax.iota(jnp.int32, G))
                   * (first - item_end + n_tiles), axis=1, dtype=jnp.int32)
    return (jnp.sum(n_tiles, dtype=jnp.int32), group,
            jnp.minimum(w + skew, R // tm - 1), ends - sizes, ends)


def _kernel(group_of, tile_of, starts, ends, rows_ref, w_ref, o_ref):
    i = pl.program_id(1)
    g = group_of[i]
    row = tile_of[i] * rows_ref.shape[0] + jax.lax.broadcasted_iota(
        jnp.int32, o_ref.shape, 0)
    out = jax.lax.dot_general(rows_ref[...], w_ref[...],
                              (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    # lax.select, not jnp.where (a jitted function of its own, lowered
    # into every body)
    o_ref[...] = jax.lax.select((row >= starts[g]) & (row < ends[g]),
                                out.astype(o_ref.dtype), o_ref[...])


def grouped_matmul(rows, w, sizes, interpret=None):
    """rows [R, K] sorted by group, w [G, K, N], sizes [G] int -> [R, N] in
    rows.dtype (see the module's docstring).  Raises ValueError for shapes
    `supports` refuses: ops.grouped_matmul asks the predicate first and
    takes jax.lax.ragged_dot for those."""
    interp = _interpret() if interpret is None else interpret
    if w.dtype != rows.dtype or not supports(rows.shape, w.shape,
                                             rows.dtype):
        raise ValueError(
            f"grouped_matmul tiling needs rows [R, K] and w [G, K, N] of one "
            f"dtype (bf16 or fp32), K and N multiples of 128 and R whole row "
            f"tiles (got rows {rows.shape} {rows.dtype}, w {w.shape} "
            f"{w.dtype})")
    (R, K), (G, _, N) = rows.shape, w.shape
    tm, tn = row_tile(R), col_tile(K, N, rows.dtype)
    with x64_off():
        items, *work = _work_list(jnp.asarray(sizes, jnp.int32), R, tm)
        return pl.pallas_call(
            _kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=4,
                # one step a column block and item: with nothing held, one
                # step whose row mask is empty
                grid=(N // tn, jnp.maximum(items, 1)),
                in_specs=[
                    pl.BlockSpec((tm, K), lambda n, i, group_of, tile_of,
                                 *_: (tile_of[i], 0)),
                    pl.BlockSpec((None, K, tn), lambda n, i, group_of, *_:
                                 (group_of[i], 0, n)),
                ],
                out_specs=pl.BlockSpec((tm, tn), lambda n, i, group_of,
                                       tile_of, *_: (tile_of[i], n)),
            ),
            out_shape=jax.ShapeDtypeStruct((R, N), rows.dtype),
            # a tile's groups follow one another: the steps run in order
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary"),
                vmem_limit_bytes=_VMEM_LIMIT),
            name="grouped_matmul",
            interpret=bool(interp),
        )(*work, rows, w)
