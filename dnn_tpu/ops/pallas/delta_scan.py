"""The chunked delta rule's scan over chunks with the state RESIDENT in
fast memory (models/kda.py `chunk_rule`: everything that does not depend on
the state is made outside, for all chunks at once; what is left is, a chunk,

    U = U~ - W S;  O = Qg S + B U;  S' = Diag(a) S + Kout^T U

four small float32 matmuls that the layer loop's plain form runs as a
`lax.scan` of sixteen iterations a 1024-position prefill chunk, the (d, d)
state a head going out to HBM and back between them).

One grid step is one (head, chunk): the chunk's five tiles come in, the
head's state stays in a VMEM scratch across the chunk axis ("arbitrary",
innermost), the output tile and — at the last chunk — the state go out.
Products are float32 at full precision (`precision=HIGHEST`: the state is
what a slot keeps for thousands of positions).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["delta_scan"]

_HI = lax.Precision.HIGHEST


def _kernel(w_ref, u_ref, q_ref, b_ref, k_ref, a_ref, s0_ref, o_ref, s_ref,
            state):
    from jax.experimental import pallas as pl

    n = pl.program_id(1)

    @pl.when(n == 0)
    def _():
        state[...] = s0_ref[...]

    s = state[...]
    dot = functools.partial(jnp.dot, precision=_HI,
                            preferred_element_type=jnp.float32)
    u = u_ref[...] - dot(w_ref[...], s)
    o_ref[...] = dot(q_ref[...], s) + dot(b_ref[...], u)
    new = a_ref[...] * s + lax.dot_general(
        k_ref[...], u, (((0,), (0,)), ((), ())), precision=_HI,
        preferred_element_type=jnp.float32)
    state[...] = new

    @pl.when(n == pl.num_programs(1) - 1)
    def _():
        s_ref[...] = new


def delta_scan(w, u0, qg, bq, k_out, decay, state, *, interpret=False):
    """w, qg, k_out (G, n, c, d), u0 (G, n, c, dv), bq (G, n, c, c), decay
    (G, n, d, 1), state (G, d, dv), float32, G = batch x heads -> (o (G, n,
    c, dv), the outgoing state (G, d, dv))."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    g, n, c, d = w.shape
    dv = u0.shape[-1]

    def tile(*shape):
        return pl.BlockSpec((None, None, *shape), lambda h, i: (h, i, 0, 0))

    head = pl.BlockSpec((None, d, dv), lambda h, i: (h, 0, 0))
    return pl.pallas_call(
        _kernel,
        grid=(g, n),
        in_specs=[tile(c, d), tile(c, dv), tile(c, d), tile(c, c),
                  tile(c, d), tile(d, 1), head],
        out_specs=[tile(c, dv), head],
        out_shape=[jax.ShapeDtypeStruct((g, n, c, dv), jnp.float32),
                   jax.ShapeDtypeStruct((g, d, dv), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((d, dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(w, u0, qg, bq, k_out, decay, state)
