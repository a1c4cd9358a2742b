"""Mamba-2's one-token rule as ONE pass over the state, in place in the pool
(models/mamba2.py `step_rule`): a slot's state a layer is H x (P, N)
float32 — 4.19 MB at H 32, P 128, N 256 — read once, decayed, given its
rank-one update, used to answer with C and written back where it was:

    S' = a_h S + (dt_h x_h) B_g^T       (P, N) a head h of group g
    y_h = S' C_g + D_h x_h              (P)

The pool (L, B, H, P, N) stays in HBM and is the call's input AND output
(`input_output_aliases`): the layer's index rides scalar prefetch into the
index maps, so no layer's states are ever cut out of the leaf or written
back into a copy — the plain form reads the layer's 0.27 GB (64 slots)
twice, once for the update and once for the answers, and writes them once;
the rule needs a read and a write, 0.54 GB, 0.656 ms at 819 GB/s.

One grid step is one slot's one B/C GROUP: (H / G, P, N) in and the same
out, 2.1 MB each at 16 heads, its heads walked by a `fori_loop`. Beside the
state's bytes a layer every other operand is small change: x (B, H, P) 1.05
MB and the answers the same, B and C (B, G, N) 0.13 MB each (one row a grid
step, over the lanes, broadcast down the sublanes as it is loaded), the
decay a = exp(dt A), dt and D scalars from SMEM (8 KB each). The one COLUMN
of the rule is dt x, one a head — and it is NOT handed in spread over the
lanes as ops/pallas/retention_step.py's value is (`vb`, 8 MB beside 1.1 GB
there): here that is (B, H, P, 128) float32 = 134 MB a layer, HALF the
state's own bytes, and a trailing axis of 1 pads to 128 lanes in HBM just
the same. x comes in as the (H, P) rows it is, and a head's column tile is
made in VMEM: its row broadcast down 128 sublanes and turned by ONE 128 x
128 transpose. The answers go the same way back: S' C summed over the two
lane tiles (P, 128), turned once, summed down the sublanes — a (1, P) row,
the layout y wants. Two transposes a head on a unit that is otherwise idle;
the products and sums are the VPU's, float32 throughout, nothing on the
MXU.

Heads NARROWER than the 128 lanes (Nemotron-H: 128 heads of P = 64, N = 128)
are FOLDED: 128 / P consecutive heads of a group are one (128, N) tile — the
leaf (L, B, H, P, N) read as (L, B, H P / 128, 128, N) and x as (B, H P / 128,
128), both the same bytes in the same order — so that the two transposes stay
128 x 128 and a grid step walks H P / 128 tiles; the heads of a tile differ
in their scalars alone, which become a row's (a: down the sublanes) and a
lane's (dt, D: along x). Left as they were, heads of 64 cost a (128, 64)
transpose each way and twice the trips: 2.69 ms a layer of 0.27 GB; folded
1.38-1.43 (heads of 128 over a state of 256 take 0.84: one pair of
transposes there serves two lane tiles of state, here one. PERF.md section
6, PR 66).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["ssm_step"]

_LANES = 128


def _kernel(layer_ref, a_ref, dt_ref, d_ref, s_ref, x_ref, b_ref, c_ref,
            o_ref, y_ref, *, per_group, fold):
    from jax.experimental import pallas as pl

    del layer_ref  # the index maps' alone
    slot, g = pl.program_id(0), pl.program_id(1)
    _, p, n = s_ref.shape
    w = min(n, _LANES)
    tiles = [slice(c * w, (c + 1) * w) for c in range(n // w)]
    # the group's B and C, a (1, w) row a lane tile of the state
    b_rows = [b_ref[:, t] for t in tiles]
    c_rows = [c_ref[:, t] for t in tiles]
    if fold > 1:
        # which of a tile's `fold` heads a row (of the state) and a lane (of
        # x) belongs to
        of_row = lax.broadcasted_iota(jnp.int32, (p, 1), 0) // (p // fold)
        of_lane = lax.broadcasted_iota(jnp.int32, (1, p), 1) // (p // fold)

    def scalars(i):
        """A tile's decay (down its rows), dt and D (along x): scalars a
        head, spread over the tile's heads where it holds several."""
        h = g * per_group + i
        if fold > 1:
            h = h * fold
        a, dt, d = a_ref[slot, h], dt_ref[slot, h], d_ref[0, h]
        for j in range(1, fold):
            a = jnp.where(of_row >= j, a_ref[slot, h + j], a)
            dt = jnp.where(of_lane >= j, dt_ref[slot, h + j], dt)
            d = jnp.where(of_lane >= j, d_ref[0, h + j], d)
        return a, dt, d

    def head(i, carry):
        a, dt, d = scalars(i)
        x = x_ref[pl.ds(i, 1), :]  # (1, P)
        # dt x down the rows, on every lane: the row over w sublanes, turned
        col = jnp.broadcast_to(dt * x, (w, p)).T
        sc = jnp.zeros((p, w), jnp.float32)
        for t, b_row, c_row in zip(tiles, b_rows, c_rows):
            s = a * s_ref[i, :, t] + col * b_row
            o_ref[i, :, t] = s
            sc = sc + s * c_row
        y_ref[pl.ds(i, 1), :] = jnp.sum(sc.T, axis=0, keepdims=True) + d * x
        return carry

    lax.fori_loop(0, per_group, head, 0)


def ssm_step(pool, layer, a, dt, d, x, bm, cm, *, interpret=False):
    """pool (L, B, H, P, N) float32, `layer` its index (a traced scalar), a
    (B, H) the decays exp(dt A), dt (B, H), d (H,), x (B, H, P), bm and cm
    (B, G, N), float32 -> (the pool with layer `layer`'s states updated IN
    PLACE, y (B, H, P) float32). N is a multiple of 128 or under it."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    shape = pool.shape
    _, b, h, p, n = shape
    g = bm.shape[1]
    if n % min(n, _LANES):
        raise ValueError(f"the state's width {n} must tile 128 lanes")
    # heads narrower than the lanes: `fold` of them a tile (module docstring)
    fold = _LANES // p if p < _LANES and _LANES % p == 0 else 1
    if (h // g) % fold:
        fold = 1
    if fold > 1:
        pool = pool.reshape(shape[0], b, h // fold, fold * p, n)
        x = x.reshape(b, h // fold, fold * p)
    per_group, p = h // g // fold, fold * p
    layer = jnp.asarray(layer, jnp.int32).reshape(1)

    smem = pl.BlockSpec(memory_space=pltpu.SMEM)  # whole
    state = pl.BlockSpec((None, None, per_group, p, n),
                         lambda s, g, layer: (layer[0], s, g, 0, 0))
    rows = pl.BlockSpec((None, per_group, p), lambda s, g, layer: (s, g, 0))
    # a group's row as a (1, N) block of (B, G, 1, N): the index map picks it
    group = pl.BlockSpec((None, None, 1, n), lambda s, g, layer: (s, g, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, g),
        in_specs=[smem, smem, smem, state, rows, group, group],
        out_specs=[state, rows],
    )
    f32 = jnp.float32
    pool, y = pl.pallas_call(
        functools.partial(_kernel, per_group=per_group, fold=fold),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(pool.shape, pool.dtype),
                   jax.ShapeDtypeStruct(x.shape, f32)],
        # operand numbers count the scalar: layer, a, dt, d, then the pool
        input_output_aliases={4: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=48 * 1024 * 1024),
        interpret=interpret,
        name="ssm_step",
    )(layer, a.astype(f32), dt.astype(f32), d.astype(f32).reshape(1, h),
      pool, x.astype(f32), bm.astype(f32)[:, :, None],
      cm.astype(f32)[:, :, None])
    return pool.reshape(shape), y.reshape(shape[1:4])
