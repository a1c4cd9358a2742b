"""Pallas TPU kernel for the way back from the experts (parallel/moe.py):
each LIVE row of the experts' result, times its weight, added into its
token's row.

    y[token_of(r)] += weight(r) * rows[r]        r < live
                                                  rows (R, D) float32
                                                  y (S, D) float32

The plain form is a scatter-add of R rows, which the chip's compiler runs
an update at a time. Here y (a column tile of it) stays in VMEM for the
whole walk, the grid's second axis ends at the last tile that holds a live
row (`live` is traced: a grid bound, as `grouped_matmul`'s visits), and a
tile's rows are added by a loop of as many trips as it has live rows: rows
from `live` on, which `grouped_matmul` leaves unspecified, are never read.

`reference_row_accumulate` is the plain form: what runs off the TPU, and
the oracle of tests/test_grouped_matmul.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from dnn_tpu.ops.pallas.grouped_matmul import _ROW_TILE  # a walk's row tile

__all__ = ["row_accumulate", "reference_row_accumulate"]

# y's column tile (S, dn) float32 at most this large: it is held twice
_Y_BLOCK_BYTES = 8 << 20


def reference_row_accumulate(y, rows, token_of_row, weight_of_row, live):
    """y (S, D) f32, rows (R, D) f32, token_of_row (R,) int32,
    weight_of_row (R,) f32, live an int32 scalar -> y with every row r <
    live added, weighted, into row token_of_row[r]; rows from `live` on
    may hold anything and are not read into the sum."""
    r = rows.shape[0]
    valid = jnp.arange(r, dtype=jnp.int32) < live
    add = jnp.where(valid[:, None],
                    rows * weight_of_row[:, None].astype(rows.dtype), 0.0)
    return y.at[jnp.where(valid, token_of_row, 0)].add(add)


def _kernel(live_ref, token_ref, weight_ref, y_in_ref, rows_ref, y_ref, *, tm):
    from jax.experimental import pallas as pl

    i = pl.program_id(1)

    @pl.when(i == 0)
    def _():
        y_ref[...] = y_in_ref[...]

    base = i * tm

    def add_row(r, carry):
        token = pl.ds(token_ref[base + r], 1)
        y_ref[token, :] += weight_ref[base + r] * rows_ref[pl.ds(r, 1), :]
        return carry

    jax.lax.fori_loop(0, jnp.clip(live_ref[0] - base, 0, tm), add_row, 0)


def row_accumulate(y, rows, token_of_row, weight_of_row, live, *,
                   interpret=None):
    """`reference_row_accumulate` as one pass with y resident: the Pallas
    kernel on a TPU, the reference elsewhere; `interpret=True` forces the
    kernel in interpreter mode (CPU CI runs the real walk). y is donated
    to the result."""
    if interpret is None:
        if jax.default_backend() != "tpu":
            return reference_row_accumulate(y, rows, token_of_row,
                                            weight_of_row, live)
        interpret = False
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    (s, d), (r, d_r) = y.shape, rows.shape
    if d != d_r or y.dtype != jnp.float32 or rows.dtype != jnp.float32:
        raise ValueError(f"rows {rows.shape} {rows.dtype} do not add into "
                         f"{y.shape} {y.dtype}")
    tm = min(_ROW_TILE, -(-r // 8) * 8)
    fits = [t for t in range(128, d + 1, 128)
            if d % t == 0 and s * t * 4 <= _Y_BLOCK_BYTES]
    dn = max(fits) if fits else d
    live = jnp.minimum(jnp.asarray(live, jnp.int32), r).reshape(1)
    # a tile at least, so that y is written where no row is live
    n_tiles = jnp.maximum(-(-live[0] // tm), 1)
    scalars = [live, token_of_row.astype(jnp.int32),
               weight_of_row.astype(jnp.float32)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(d // dn, n_tiles),
        in_specs=[
            pl.BlockSpec((s, dn), lambda dj, i, *_: (0, dj)),
            pl.BlockSpec((tm, dn), lambda dj, i, *_: (i, dj)),
        ],
        out_specs=pl.BlockSpec((s, dn), lambda dj, i, *_: (0, dj)),
    )
    return pl.pallas_call(
        functools.partial(_kernel, tm=tm),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s, d), jnp.float32),
        input_output_aliases={len(scalars): 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=4 * s * dn * 4 + 4 * tm * dn * 4 + (8 << 20)),
        cost_estimate=pl.CostEstimate(
            flops=2 * r * d, transcendentals=0,
            bytes_accessed=(2 * s * d + r * d) * 4),
        interpret=interpret,
        name="row_accumulate",
    )(*scalars, y, rows)
