"""Power retention's one-token rule as ONE pass over the state, in place in
the pool (models/retention.py `step_rule`): a slot's state a layer is KV x
(d, D) float32 — 35.9 MB at KV 8, d 128, D 8 704 — read once, decayed,
given its rank-one update, used to answer the group's G query heads and
written back where it was:

    S' = g S + v phi(k)^T           (d, D) a KV head
    num = S' phi(q_i)               (d) a query head i of the group

The pool (L, B, KV, d, D) stays in HBM and is the call's input AND output
(`input_output_aliases`): the layer's index rides scalar prefetch into the
index maps, so no layer's states are ever cut out of the leaf or written
back into a copy — the plain form's `pool[layer]` ... `pool.at[layer].set`
moves the layer's 0.57 GB (16 slots) twice more than the rule needs.

One grid step is one slot's `block_d` columns of all KV heads ((KV, d,
block_d) in, the same out: 2 MB each at 512); the D axis is innermost and
"arbitrary", the (KV, G, d) answers accumulate over it in their output
block. The state is value-major so that everything broadcasts cheaply: the
expanded key is a ROW over the lanes, the decay a scalar from SMEM, and the
value — the one column — comes in already spread over 128 lanes (`vb`, (B,
KV, d, 128): 8 MB a layer a step beside 1.1 GB of state). The answers are
a matmul of the (G, block_d) expanded queries against the (d, block_d) new
state over the lanes, in `mm_dtype` with float32 accumulation (bfloat16 on
the chip: the state itself stays float32).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["retention_step", "BLOCK_D"]

BLOCK_D = 512
_LANES = 128


def _kernel(layer_ref, g_ref, s_ref, vb_ref, pk_ref, pq_ref, o_ref, num_ref,
            *, n_kv, block_d, mm_dtype):
    from jax.experimental import pallas as pl

    del layer_ref  # the index maps' alone
    slot, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _():
        num_ref[...] = jnp.zeros_like(num_ref)

    # the answers' product: one pass of the MXU in bfloat16, float32 whole
    precision = (lax.Precision.HIGHEST
                 if jnp.dtype(mm_dtype) == jnp.float32 else None)
    for h in range(n_kv):
        g = g_ref[slot, h]
        vb = vb_ref[h]  # (d, 128): the value down the rows, on every lane
        for c in range(block_d // _LANES):
            cols = slice(c * _LANES, (c + 1) * _LANES)
            o_ref[h, :, cols] = g * s_ref[h, :, cols] \
                + vb * pk_ref[h:h + 1, cols]
        num_ref[h] += lax.dot_general(
            pq_ref[h].astype(mm_dtype), o_ref[h].astype(mm_dtype),
            (((1,), (1,)), ((), ())), precision=precision,
            preferred_element_type=jnp.float32)


def retention_step(pool, layer, g, v, pk, pq, *, mm_dtype=None,
                   block_d=BLOCK_D, interpret=False):
    """pool (L, B, KV, d, D) float32, `layer` its index (a traced scalar),
    g (B, KV) the decays, v (B, KV, d), pk (B, KV, D) and pq (B, KV, G, D)
    the expanded keys and queries, float32 -> (the pool with layer
    `layer`'s states updated IN PLACE, num (B, KV, G, d) float32). D is a
    multiple of `block_d` or of 128 (the block then falls back to the
    largest multiple of 128 up to `block_d` that divides D)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    _, b, kv, d, wide = pool.shape
    grp = pq.shape[2]
    if wide % _LANES:
        raise ValueError(f"the state's width {wide} must tile 128 lanes")
    block_d = next(n for n in range(min(block_d, wide), 0, -_LANES)
                   if wide % n == 0)
    mm_dtype = jnp.float32 if mm_dtype is None else mm_dtype
    vb = jnp.broadcast_to(v[..., None], (b, kv, d, _LANES))
    layer = jnp.asarray(layer, jnp.int32).reshape(1)

    state = pl.BlockSpec((None, None, kv, d, block_d),
                         lambda s, j, layer: (layer[0], s, 0, 0, j))
    num = pl.BlockSpec((None, kv, grp, d), lambda s, j, layer: (s, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, wide // block_d),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),  # g, whole
            state,
            pl.BlockSpec((None, kv, d, _LANES),
                         lambda s, j, layer: (s, 0, 0, 0)),
            pl.BlockSpec((None, kv, block_d), lambda s, j, layer: (s, 0, j)),
            pl.BlockSpec((None, kv, grp, block_d),
                         lambda s, j, layer: (s, 0, 0, j)),
        ],
        out_specs=[state, num],
    )
    return pl.pallas_call(
        functools.partial(_kernel, n_kv=kv, block_d=block_d,
                          mm_dtype=mm_dtype),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(pool.shape, pool.dtype),
                   jax.ShapeDtypeStruct((b, kv, grp, d), jnp.float32)],
        # operand numbers count the scalar: layer, g, then the pool
        input_output_aliases={2: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=48 * 1024 * 1024),
        interpret=interpret,
        name="retention_step",
    )(layer, g.astype(jnp.float32), pool, vb, pk, pq)
