"""The fixed-decay linear-attention rule's one-token step as ONE pass over the
state, in place in the pool (models/lightning.py `step_rule`): a slot's state
a layer is H x (d, d) float32 — 2.10 MB at H 32, d 128 — read once, decayed,
given its rank-one update, used to answer and written back where it was:

    S' = lambda_h S + k_h v_h^T        (d, d) a head: key channel x value
    o_h = S'^T q_h                     (d)

The pool (L, B, H, d, d) stays in HBM and is the call's input AND output
(`input_output_aliases`): the layer's index rides scalar prefetch into the
index maps, as in ops/pallas/ssm_step.py, whose shape this follows. The plain
form reads a layer's states twice (the update, then the answers) and writes
them once: 0.337 ms a layer at 32 slots where the rule needs a read and a
write, 0.134 GB, 0.164 ms at 819 GB/s (PERF.md section 6, PR 58).

One grid step is `heads` heads of one slot: (heads, d, d) in and the same
out, 1 MB each at 16 heads, walked by a `fori_loop`. k and q of a head are
COLUMNS of the rule (they run down the state's rows) and come in as the (H,
d) rows they are: a head's column tile is its row broadcast down 128
sublanes and turned by one 128 x 128 transpose, two a head on a unit that is
otherwise idle; v is a row as it stands, and the answer — the sum down the
rows of q's column times S' — comes out as the (1, d) row o wants. The
products and sums are the VPU's, float32 throughout, nothing on the MXU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["lin_step"]


def _kernel(layer_ref, lam_ref, s_ref, q_ref, k_ref, v_ref, o_ref, y_ref, *,
            per):
    from jax.experimental import pallas as pl

    del layer_ref  # the index maps' alone
    g = pl.program_id(1)
    _, d, _ = s_ref.shape

    def head(i, carry):
        lam = lam_ref[0, g * per + i]
        row = pl.ds(i, 1)
        # a (1, d) row down the rows, on every lane
        kcol = jnp.broadcast_to(k_ref[row, :], (d, d)).T
        qcol = jnp.broadcast_to(q_ref[row, :], (d, d)).T
        s = lam * s_ref[i] + kcol * v_ref[row, :]
        o_ref[i] = s
        y_ref[row, :] = jnp.sum(qcol * s, axis=0, keepdims=True)
        return carry

    lax.fori_loop(0, per, head, 0)


def lin_step(pool, layer, lam, q, k, v, *, heads=16, interpret=False):
    """pool (L, B, H, d, d) float32, `layer` its index (a traced scalar), lam
    (H,) the decays, q (scaled), k, v (B, H, d) float32 -> (the pool with
    layer `layer`'s states updated IN PLACE, o (B, H, d) float32). d fills
    128 lanes."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    _, b, h, d, _ = pool.shape
    per = heads if h % heads == 0 else h
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)  # whole
    state = pl.BlockSpec((None, None, per, d, d),
                         lambda s, g, layer: (layer[0], s, g, 0, 0))
    rows = pl.BlockSpec((None, per, d), lambda s, g, layer: (s, g, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, h // per),
        in_specs=[smem, state, rows, rows, rows],
        out_specs=[state, rows],
    )
    f32 = jnp.float32
    return pl.pallas_call(
        functools.partial(_kernel, per=per),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(pool.shape, pool.dtype),
                   jax.ShapeDtypeStruct((b, h, d), f32)],
        # operand numbers count the scalar: layer, lam, then the pool
        input_output_aliases={2: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=48 * 1024 * 1024),
        interpret=interpret,
        name="lin_step",
    )(layer, lam.astype(f32).reshape(1, h), pool, q.astype(f32),
      k.astype(f32), v.astype(f32))
