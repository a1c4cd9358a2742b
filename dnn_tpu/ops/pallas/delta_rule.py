"""The chunked delta rule (models/kda.py `chunk_rule`) with everything
between its inputs and its outputs made in FAST MEMORY: one grid step is
one closed-form chunk of `_HEADS` heads, and from a chunk's q, k, v, g and
beta tiles ((c, d) float32, 32 KB each at c = 64, d = 128) it makes

  * the cumulative log-decay G (log2(c) shifted adds down the rows);
  * the decay products A[r, i] = sum_c k_r k_i exp(G_r - G_i) and B (the
    same over q_r) — BETWEEN sub-blocks of `block` positions as matmuls of
    rows and columns both scaled against the later sub-block's first
    cumulative log-decay, INSIDE a sub-block pair by pair: column i of
    every sub-block at once, a (c, d) multiply-and-reduce over the lanes
    against k_i exp(G_r - G_i), the exponent made ONCE for A and B. Pair
    by pair because inside a sub-block no reference makes both exponents
    <= 0, and with g down to -1.6 a position 1 / exp(G) overflows float32;
  * the pseudo-values U of (I + Diag(beta) A) U = Diag(beta) (V - (K *
    exp G) S) by exact forward substitution on the right-hand side: a
    sub-block's rows below the diagonal block by matmul against the
    sub-blocks already solved, its diagonal block row by row (row j, final
    by then, taken off the rows below it). No inverse and no power of the
    system's matrix is formed (a run of equal keys makes them huge before
    they cancel), and W = T Diag(beta) (K * exp G), U~ = T Diag(beta) V of
    the plain form never exist: U = U~ - W S is solved for directly;
  * O = (Q * exp G) S + B U and S' = Diag(exp G_C) S + (K * exp(G_C -
    G))^T U, the head's state resident in a VMEM scratch across the chunk
    axis ("arbitrary", innermost) and written out at the last chunk. The
    scratch holds S TRANSPOSED (value channel x key channel), so that the
    decay of a key channel scales a lane.

What crosses HBM is q, k, v, g, beta and the state in, o and the state out:
no array with a (c, c) or a pair axis leaves the grid step. Products are
float32 at `precision=HIGHEST` (the state is what a slot keeps for
thousands of positions); every exponent is a difference of cumulative
log-decays, <= 0 up to the rounding of their sums.

A grid step is a dependent chain — the state's products, three matmuls of
the substitution, B U — of small matmuls whose latency (~140 cycles each
on a v5e) no other work of the SAME head can hide, so the heads of a grid
step are advanced in LOCKSTEP, a stage each in turn (`_lockstep`): the
program's order interleaves them and one head's matmul is in flight while
the next head's is issued. What each choice read on the chip: PERF.md
section 6, PR 61.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["delta_rule"]

_HI = lax.Precision.HIGHEST
_NT = (((1,), (1,)), ((), ()))  # x y^T
_TN = (((0,), (0,)), ((), ()))  # x^T y
_HEADS = 8  # heads a grid step (5 MB of VMEM; sixteen pass the 16 MB limit)


def _lockstep(gens):
    """Advances the generators a step each in turn until all have returned
    -> their return values."""
    out, live = [None] * len(gens), list(enumerate(gens))
    while live:
        still = []
        for i, gen in live:
            try:
                next(gen)
                still.append((i, gen))
            except StopIteration as done:
                out[i] = done.value
        live = still
    return out


def _cumsum(g):
    """The cumulative sum down the rows of g (c, d), in ceil(log2(c))
    shifted adds."""
    from jax.experimental.pallas import tpu as pltpu

    row = lax.broadcasted_iota(jnp.int32, g.shape, 0)
    shift = 1
    while shift < g.shape[0]:
        g = g + jnp.where(row >= shift, pltpu.roll(g, shift, 0), 0.0)
        shift *= 2
    return g


def _chunk(q, k, v, g, beta_row, s_t, *, block):
    """One chunk of one head, a generator that yields between its stages:
    q, k, v, g (c, d), beta_row (1, c), s_t the TRANSPOSED incoming state
    (dv, d) -> (o (c, dv), the outgoing state, transposed)."""
    f32 = jnp.float32
    c, d = k.shape
    m = c // block
    dot = functools.partial(jnp.dot, precision=_HI, preferred_element_type=f32)
    dg = functools.partial(lax.dot_general, precision=_HI,
                           preferred_element_type=f32)
    rows = lax.broadcasted_iota(jnp.int32, (c, c), 0)
    cols = lax.broadcasted_iota(jnp.int32, (c, c), 1)
    pos = lax.broadcasted_iota(jnp.int32, (c, d), 0)
    beta = jnp.where(rows == cols, beta_row, 0.0).sum(1, keepdims=True)

    def own_row(x, i):  # row i of every sub-block, over its sub-block's rows
        return jnp.concatenate([jnp.broadcast_to(
            x[s * block + i:s * block + i + 1], (block, d))
            for s in range(m)], axis=0)

    # what the incoming state has decayed to, and what it gives
    cum = _cumsum(g)
    into = jnp.exp(cum)
    last = cum[c - 1:c]
    seen = dg(jnp.concatenate([beta * k * into, q * into], axis=0), s_t, _NT)
    yield
    rhs = beta * v - seen[:c]

    # the decay products between sub-blocks: rows against the cumulative
    # log-decay before their own sub-block (`ref`), the columns of EARLIER
    # sub-blocks against the later one's
    ref = jnp.concatenate([jnp.zeros((block, d), f32), own_row(
        cum, block - 1)[:c - block]], axis=0)
    inner = jnp.exp(cum - ref)
    k_in, q_in = k * inner, q * inner
    a_low = [jnp.zeros((block, c), f32)]
    b_low = [jnp.zeros((block, c), f32)]
    for j in range(1, m):
        lo, hi = j * block, (j + 1) * block
        k_cols = k * jnp.exp(jnp.where(pos < lo, ref[lo:lo + 1] - cum,
                                       -jnp.inf))
        both = dg(jnp.concatenate([k_in[lo:hi], q_in[lo:hi]], axis=0),
                  k_cols, _NT)  # (2 block, c), zero from column `lo` on
        a_low.append(beta[lo:hi] * both[:block])
        b_low.append(both[block:])
    yield

    # inside a sub-block, pair by pair: column i of every sub-block's A and
    # B from its rows r >= i
    own = pos % block  # a row's place in its sub-block
    first = rows // block * block  # its sub-block's first column
    bq = jnp.concatenate(b_low, axis=0)  # B: (c, c), zero above the diagonal
    a_cols = []  # column i of the sub-blocks' Diag(beta) A below the diagonal
    for i in range(block):
        kie = own_row(k, i) * jnp.exp(jnp.where(
            own >= i, cum - own_row(cum, i), -jnp.inf))
        a_cols.append(jnp.where(
            own[:, :1] > i, beta * (k * kie).sum(1, keepdims=True), 0.0))
        bq = jnp.where(cols == first + i, (q * kie).sum(1, keepdims=True), bq)
        if i % 4 == 3:
            yield

    # forward substitution, a sub-block at a time
    us = []
    for s in range(m):
        lo, hi = s * block, (s + 1) * block
        r = rhs[lo:hi]
        if s:
            below = dot(a_low[s], jnp.concatenate(
                us + [jnp.zeros((c - lo, r.shape[1]), f32)], axis=0))
            yield
            r = r - below
        for j in range(block - 1):
            r = r - a_cols[j][lo:hi] * r[j:j + 1]
            if j % 4 == 3:
                yield
        us.append(r)
    u = jnp.concatenate(us, axis=0)

    o = seen[c:] + dot(bq, u)
    new = jnp.exp(last) * s_t + dg(u, k * jnp.exp(last - cum), _TN)
    yield
    return o, new


def _kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, s0_ref, o_ref, s_ref,
            state, *, block):
    from jax.experimental import pallas as pl

    n = pl.program_id(1)
    heads = range(state.shape[0])

    @pl.when(n == 0)
    def _():
        for h in heads:
            state[h] = s0_ref[h].T

    outs = _lockstep([_chunk(
        q_ref[h], k_ref[h], v_ref[h], g_ref[h], beta_ref[h], state[h],
        block=block) for h in heads])
    for h, (o, new) in enumerate(outs):
        o_ref[h] = o
        state[h] = new

    @pl.when(n == pl.num_programs(1) - 1)
    def _():
        for h in heads:
            s_ref[h] = state[h].T


def delta_rule(q, k, v, g, beta, state, *, block=16, interpret=False):
    """q, k, g (G, n, c, d), v (G, n, c, dv), beta (G, n, c), state (G, d,
    dv), float32, G = batch x heads in n chunks of c positions, g <= 0 the
    log-decay a position a channel -> (o (G, n, c, dv), the outgoing state
    (G, d, dv)). Built for d and dv multiples of 128 lanes and c a multiple
    of `block`, itself a multiple of 8."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    groups, n, c, d = k.shape
    dv = v.shape[-1]
    heads = math.gcd(_HEADS, groups)

    def tile(*shape):
        return pl.BlockSpec((heads, None, *shape), lambda h, i: (h, i, 0, 0))

    head = pl.BlockSpec((heads, d, dv), lambda h, i: (h, 0, 0))
    return pl.pallas_call(
        functools.partial(_kernel, block=block),
        grid=(groups // heads, n),
        in_specs=[tile(c, d), tile(c, d), tile(c, dv), tile(c, d),
                  tile(1, c), head],
        out_specs=[tile(c, dv), head],
        out_shape=[jax.ShapeDtypeStruct((groups, n, c, dv), jnp.float32),
                   jax.ShapeDtypeStruct((groups, d, dv), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((heads, dv, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v, g, beta.reshape(groups, n, 1, c), state)
