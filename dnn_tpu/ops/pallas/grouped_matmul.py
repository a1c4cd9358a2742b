"""Pallas TPU kernel for the grouped experts' matmul (parallel/moe.py):
rows sorted by expert, each meeting its own expert's matrix — read out of
the HELD STACK in place.

    out[r] = rows[r] @ stack[layer, expert_of(r)]      rows (R, K)
                                                        stack (L, E, K, N)

`jax.lax.ragged_dot` is this product for one layer's (E, K, N) matrices,
and lowers to a custom call whose operand must be materialised: fed from a
stack of layers, every layer of every program first COPIES its matrices
out (268 MB read and 268 MB written a matrix at OLMoE's widths — more than
half of that cell's busy device before this kernel). Here the whole stack
is the operand, left as the program holds it; the layer index and the
per-step metadata ride scalar prefetch, and the weight operand's block
index is (layer, expert of this visit, 0, column tile): the pipeline's
DMAs fetch tiles of the ACTIVE experts straight out of the stack.

The grid's second axis walks VISITS: (expert with rows, row tile it
overlaps) pairs in row order, made in the program from `group_sizes`
(`visits`). An expert with no rows has no visit and its matrices are never
fetched; a row tile past `sum(group_sizes)` has none either (under
`moe_ffn_grouped(held=)` the rows of experts held elsewhere sit there:
their output rows are left UNSPECIFIED, as `ragged_dot` leaves them). A
tile that holds the rows of several experts is visited once by each, every
visit storing its own expert's rows only; the contraction axis is never
tiled, so an expert that spans several row tiles keeps one block index
from visit to visit and its matrix is fetched once a column tile.

`reference_grouped_matmul` is the plain form: what runs off the TPU, and
the oracle of tests/test_grouped_matmul.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

__all__ = ["grouped_matmul", "reference_grouped_matmul", "visits"]

# one weight block (K, tn) at most this large: two of them in flight fill
# the DMA queue with long contiguous copies and leave the default VMEM
# budget room for the row and output tiles
_WEIGHT_BLOCK_BYTES = 4 << 20
_ROW_TILE = 128


def reference_grouped_matmul(rows, stack, layer, group_sizes):
    """rows (R, K), stack (L, E, K, N), layer an int32 scalar, group_sizes
    (E,) int32 -> (R, N) float32: `jax.lax.ragged_dot` against the layer's
    matrices, cut out of the stack."""
    return jax.lax.ragged_dot(rows, stack[layer], group_sizes,
                              preferred_element_type=jnp.float32)


def _tiles(r, k, n, dtype):
    """(row tile, column tile) for rows (r, k) against matrices (k, n).

    Columns: the widest divisor of n in whole 128-lane tiles whose (k, tn)
    block stays under `_WEIGHT_BLOCK_BYTES` (n itself where it has none).
    Rows: 128 — the MXU's height; the kernel is bound by the weights'
    bytes, and a visit's cost on the MXU is that of passing its weight
    tiles through it whatever the rows, up to there — or all of r, rounded
    up to the operand's sublane tile, where r is shorter."""
    itemsize = jnp.dtype(dtype).itemsize
    sublanes = 32 // itemsize  # 8 float32, 16 bfloat16
    tm = min(_ROW_TILE, -(-r // sublanes) * sublanes)
    fits = [t for t in range(128, n + 1, 128)
            if n % t == 0 and k * t * itemsize <= _WEIGHT_BLOCK_BYTES]
    return tm, (max(fits) if fits else n)


def visits(group_sizes, r, tm):
    """The grid's walk over rows sorted by group, from (E,) int32 sizes:
    -> (offsets (E + 1,), group_of_visit (V,), tile_of_visit (V,),
    n_visits) with V = cdiv(r, tm) + E - 1, the most there can be. Visit v
    (< n_visits) is group `group_of_visit[v]` on the `tm` rows of tile
    `tile_of_visit[v]`; a group is visited once for every tile it has a
    row in, groups in order, tiles in order within a group — so a tile's
    visits are consecutive. Entries from n_visits on repeat the last."""
    e = group_sizes.shape[0]
    group_sizes = group_sizes.astype(jnp.int32)
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    first = starts // tm
    tiles = jnp.where(group_sizes > 0, (ends - 1) // tm - first + 1, 0)
    visit_ends = jnp.cumsum(tiles)
    n_visits = visit_ends[-1]
    v = jnp.minimum(jnp.arange(-(-r // tm) + e - 1, dtype=jnp.int32),
                    jnp.maximum(n_visits - 1, 0))
    group = jnp.minimum(
        jnp.searchsorted(visit_ends, v, side="right").astype(jnp.int32),
        e - 1)
    tile = first[group] + v - (visit_ends[group] - tiles[group])
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    return offsets, group, tile.astype(jnp.int32), n_visits


def _kernel(layer_ref, offsets_ref, group_ref, tile_ref, x_ref, w_ref, o_ref,
            *, tm):
    from jax.experimental import pallas as pl

    del layer_ref  # the weight block's index alone reads it
    v = pl.program_id(1)
    g = group_ref[v]
    acc = jnp.dot(x_ref[...], w_ref[...], preferred_element_type=jnp.float32)
    row = tile_ref[v] * tm + jax.lax.broadcasted_iota(jnp.int32, acc.shape, 0)
    mine = (row >= offsets_ref[g]) & (row < offsets_ref[g + 1])
    # the tile's other rows are another visit's (or nobody's): kept as
    # they are, a select and no product, so what has not been written yet
    # may hold anything
    o_ref[...] = jnp.where(mine, acc, o_ref[...])


def grouped_matmul(rows, stack, layer, group_sizes, *, interpret=None,
                   tiles=None):
    """rows (R, K) sorted by group, stack (L, E, K, N) of the rows' dtype,
    layer an int32 scalar (traced allowed), group_sizes (E,) int32 -> (R,
    N) float32: row r times `stack[layer, g]` for the group g that holds
    it, float32 accumulation. Rows behind the last group are unspecified.

    Dispatches to the Pallas kernel on TPU; otherwise runs the reference.
    `interpret=True` forces the kernel in interpreter mode (CPU CI runs
    the real visit walk and block indexing). `tiles` = (row tile, column
    tile) overrides the code's choice (the chip's sweep)."""
    if interpret is None:
        if jax.default_backend() != "tpu":
            return reference_grouped_matmul(rows, stack, layer, group_sizes)
        interpret = False
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    (r, k), (_, e, k_w, n) = rows.shape, stack.shape
    if k != k_w or rows.dtype != stack.dtype or group_sizes.shape != (e,):
        raise ValueError(
            f"rows {rows.shape} {rows.dtype} do not meet a stack "
            f"{stack.shape} {stack.dtype} in {group_sizes.shape} groups")
    tm, tn = tiles or _tiles(r, k, n, rows.dtype)
    offsets, group, tile, n_visits = visits(group_sizes, r, tm)
    scalars = [jnp.asarray(layer, jnp.int32).reshape(1), offsets, group, tile]
    itemsize = rows.dtype.itemsize
    # two buffers a block, the product before its select, and room to spare
    vmem = 2 * (k * tn * itemsize + tm * k * itemsize + tm * tn * 4) \
        + 2 * tm * tn * 4 + (4 << 20)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        # columns outermost: a row tile's visits stay consecutive, so its
        # output block is written back once they are all through
        grid=(n // tn, n_visits),
        in_specs=[
            pl.BlockSpec((tm, k), lambda ni, v, layer, offs, group, tile:
                         (tile[v], 0)),
            pl.BlockSpec((None, None, k, tn),
                         lambda ni, v, layer, offs, group, tile:
                         (layer[0], group[v], 0, ni)),
        ],
        out_specs=pl.BlockSpec((tm, tn), lambda ni, v, layer, offs, group,
                               tile: (tile[v], ni)),
    )
    return pl.pallas_call(
        functools.partial(_kernel, tm=tm),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((r, n), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=max(vmem, 16 << 20)),
        cost_estimate=pl.CostEstimate(
            flops=2 * r * k * n, transcendentals=0,
            bytes_accessed=(e * k * n + r * k) * itemsize + r * n * 4),
        interpret=interpret,
        name="grouped_matmul",
    )(*scalars, rows, stack)
