"""Pallas TPU kernel for a prefill chunk of latent attention (models/
mla.py), up-projected: T queries at positions [start, start + T) against
the keys and values of everything before them and themselves.

A head's key is two parts of different kinds: `k_nope` (Dn wide, its own,
up-projected from the cached latent) and `k_rope` (Dr wide, ONE for all
heads, cached beside the latent), and its value is Dv wide — 128 | 64 and
128 for the DeepSeek-V3 family, so the key (192) is wider than the value.
The kernel takes the parts as they are and never builds a 192-wide key:

    s[t, j] = (q_nope[t] . k_nope[j] + q_rope[t] . k_rope[j]) * scale

two products a tile (the MXU makes two passes over a 192-wide contraction
anyway), the second against the shared rope keys. Causal, flash-style: the
online softmax runs over column tiles, a tile wholly past a query tile's
last position is neither fetched (its block index is clamped to the last
live one, and a repeated index is not copied again) nor computed, and
`start` rides scalar prefetch, so one compiled program serves every chunk
start.

**What one grid step covers: G heads x bq rows x bs columns** (`grid_step`).
Which (row, column) pairs of a tile are read — the causal limit, a
window's band, a set — is the same for every head, so a step takes a GROUP
of G heads under ONE (bq, bs) tile of pairs: the blocks are (G, bq, D) of
the queries, (G, bs, D) of `k_nope` and `v`, one (bs, 128) tile of the
shared rope keys and one (bq, bs) tile of the set, fetched once a group
where a head a step fetched them once a head. A tile that needs a mask
(the diagonal crosses it, the band's edge does, or there is a set) has it
built ONCE, as a float32 bias of 0 | -1e30 in VMEM that each head's scores
add; a tile wholly under the diagonal and inside the band, with no set,
takes a branch with no mask at all. The G heads' scores, online softmax
and accumulators then run in a loop under it. G is the largest divisor of
the head count whose blocks, softmax state and one head's (bq, bs)
temporaries fit half of the VMEM limit the call states; bq and bs are the
keyword arguments' (512 x 512), bs falling to 256 or 128 only for an S the
full tile does not divide — which is why the caller cuts the prefixes it
hands over at multiples of `BLOCK_S` (models/mla.py `prefix_lengths`): a
128-column step pays the step's fixed costs (its overhead, the softmax
state's rewrite, the accumulator's rescale) four times as often, and
took 3.2 times a 512-column step's time a pair on a v5e (PERF.md section
5, PR 42).

Two narrowings of what a query reads, both off by default (models/mla.py's
layer kinds): `window` = W bands it to the columns > its position - W — a
column tile wholly behind the band of a query tile's first row is skipped
like one past its last, so a chunk that was handed window + chunk
positions pays for those; `sel` (T, S) is a set a query (an indexer's
choice, within the causal limit), one more (bq, bs) int8 tile a step.

`reference_mla_prefill_attention` is the plain form: what runs off the TPU
and for shapes that do not tile, and the oracle of tests/test_mla.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from dnn_tpu.ops.pallas.sparse_attention import _last_live

_NEG_BIG = -1e30
# what a row's running maximum is held above inside the exponent: a row
# with no pair read so far has maximum _NEG_BIG, and exp(_NEG_BIG - _NO_ROW)
# is 0 where exp(_NEG_BIG - _NEG_BIG) would be 1
_NO_ROW = -1e29

BLOCK_Q = BLOCK_S = 512  # the full tile
VMEM_LIMIT_BYTES = 64 * 1024 * 1024

__all__ = ["mla_prefill_attention", "reference_mla_prefill_attention",
           "grid_step", "BLOCK_S"]


def reference_mla_prefill_attention(q_nope, q_rope, k_nope, k_rope, v, start,
                                    *, scale, window=None, sel=None):
    """q_nope (H, T, Dn), q_rope (H, T, Dr), k_nope (H, S, Dn), k_rope
    (S, Dr), v (H, S, Dv) -> (H, T, Dv) float32: query t at position
    start + t reads the columns <= start + t (with `window`, those >
    start + t - window; with `sel` (T, S), those it is true at)."""
    f32 = jnp.float32
    s = (jnp.einsum("htd,hsd->hts", q_nope.astype(f32), k_nope.astype(f32),
                    preferred_element_type=f32)
         + jnp.einsum("htd,sd->hts", q_rope.astype(f32), k_rope.astype(f32),
                      preferred_element_type=f32)) * scale
    t, s_len = s.shape[1:]
    cols, rows = jnp.arange(s_len)[None, :], start + jnp.arange(t)[:, None]
    keep = cols <= rows
    if window is not None:
        keep = keep & (cols > rows - window)
    if sel is not None:
        keep = keep & sel
    s = jnp.where(keep[None], s, _NEG_BIG)
    return jnp.einsum("hts,hsd->htd", jax.nn.softmax(s, axis=-1),
                      v.astype(f32), preferred_element_type=f32)


def grid_step(h, t, s_len, dn, dr, dv, itemsize, *, block_q=BLOCK_Q,
              block_s=BLOCK_S, select=False,
              vmem_limit_bytes=VMEM_LIMIT_BYTES):
    """(G, bq, bs) — the heads, rows and columns one grid step of the
    kernel covers for H = h heads of T = t queries against S = s_len
    columns (module docstring), widths dn | dr | dv of `itemsize` bytes —
    or None where (t, s_len) do not tile. G: the largest divisor of h
    whose share of VMEM fits half the limit (the other half is the
    compiler's: its own temporaries, the scores' relayouts)."""
    bq, bs = min(block_q, t), block_s
    while bs > 128 and s_len % bs:
        bs //= 2  # the widest of block_s, its half, ... that divides S
    if t % bq or bs > s_len or s_len % bs:
        return None
    dr += -dr % 128  # the rope parts are padded to whole lanes
    # a head: its blocks of q, k_nope and v and its float32 output, each
    # double-buffered, and its softmax state (maximum and sum 128 lanes
    # wide, the accumulator)
    a_head = (2 * itemsize * (bq * (dn + dr) + bs * (dn + dv))
              + 2 * 4 * bq * dv + 4 * bq * (2 * 128 + dv))
    # a step, whatever G: the shared rope keys and the set's int8 tile
    # (double-buffered), the bias, and one head's (bq, bs) float32
    # temporaries (scores, exponentials, the latter once more as operands)
    shared = (2 * itemsize * bs * dr + (2 * bq * bs if select else 0)
              + 4 * bq * bs * 4)
    fit = max((vmem_limit_bytes // 2 - shared) // a_head, 1)
    return max(g for g in range(1, h + 1) if h % g == 0 and g <= fit), bq, bs


def _first_live(start_ref, qi, bq, bs, window):
    """The first column tile that holds a position some row of query
    tile qi may read: 0 without a window."""
    if window is None:
        return 0
    return jnp.maximum(start_ref[0] + qi * bq - window + 1, 0) // bs


def _kernel(start_ref, qn_ref, qr_ref, kn_ref, kr_ref, v_ref, *rest,
            scale, bq, bs, window=None, select=False):
    from jax.experimental import pallas as pl

    sel_ref = None
    if select:
        sel_ref, *rest = rest
    o_ref, m_scr, l_scr, acc_scr, bias_scr = rest

    qi, si, ns = pl.program_id(1), pl.program_id(2), pl.num_programs(2)
    row0, col0 = start_ref[0] + qi * bq, si * bs  # the tile's first pair

    @pl.when(si == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_BIG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    lanes = m_scr.shape[-1]

    def across(x, n):
        """x (bq, lanes), every lane of a row the same -> (bq, n)."""
        if n % lanes:
            return jnp.broadcast_to(x[:, :1], (x.shape[0], n))
        return x if n == lanes else jnp.concatenate([x] * (n // lanes), 1)

    def folded(x, op):
        """x (bq, bs) -> (bq, lanes): `op` over the lane tiles, lane by
        lane (whole vector registers: no lane crosses another)."""
        out = x[:, :lanes]
        for c in range(lanes, x.shape[1], lanes):
            out = op(out, x[:, c:c + lanes])
        return out

    def heads(masked):
        """The group's heads over this tile, one after another: scores,
        online softmax, accumulation — under the bias where `masked`. The
        softmax state is held `lanes` wide: the maximum the same in every
        lane of a row, the SUM lane by lane (lane j the sum of the
        columns j, j + lanes, ...: `_finish` adds the lanes up), so that a
        tile costs one reduction across lanes a row — the maximum's — and
        the rescales are whole-register products."""
        contract = (((1,), (1,)), ((), ()))

        def head(g, _):
            s = (jax.lax.dot_general(qn_ref[g], kn_ref[g], contract,
                                     preferred_element_type=jnp.float32)
                 + jax.lax.dot_general(qr_ref[g], kr_ref[...], contract,
                                       preferred_element_type=jnp.float32)
                 ) * scale  # (bq, bs)
            if masked:
                s = s + bias_scr[...]
            m_prev = m_scr[g]
            m_new = jnp.maximum(m_prev, folded(s, jnp.maximum).max(
                axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            # a row with no pair read so far keeps its state: its
            # exponentials are 0, not exp(0)
            p = jnp.exp(s - across(jnp.maximum(m_new, _NO_ROW) if masked
                                   else m_new, s.shape[1]))
            l_scr[g] = l_scr[g] * alpha + folded(p, jnp.add)
            v = v_ref[g]
            acc_scr[g] = acc_scr[g] * across(alpha, v.shape[1]) \
                + jax.lax.dot_general(
                    p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
            m_scr[g] = m_new

        # a loop, not G copies of the body: unrolled eight times the
        # kernel alone ran 12 % faster on the chip and compiled in three
        # times the time, once a branch of the caller's prefix switch
        jax.lax.fori_loop(0, qn_ref.shape[0], head, None)

    live = ((si <= _last_live(start_ref, qi, bq, bs))
            & (si >= _first_live(start_ref, qi, bq, bs, window)))
    # every pair of the tile is read: its last column is within the first
    # row's causal limit, its first column inside the last row's band
    whole = col0 + bs - 1 <= row0
    if window is not None:
        whole = whole & (col0 > row0 + bq - 1 - window)

    @pl.when(live if select else live & jnp.logical_not(whole))
    def _under_a_mask():
        shape = bias_scr.shape
        ahead = (col0 - row0 + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
                 - jax.lax.broadcasted_iota(jnp.int32, shape, 0))
        keep = ahead <= 0  # column <= row
        if window is not None:
            keep = keep & (ahead > -window)
        if select:
            keep = keep & (sel_ref[...].astype(jnp.int32) != 0)
        bias_scr[...] = jnp.where(keep, 0.0, _NEG_BIG)
        heads(True)

    if not select:
        @pl.when(live & whole)
        def _whole():
            heads(False)

    @pl.when(si == ns - 1)
    def _finish():
        o_ref[...] = (acc_scr[...] / l_scr[...].sum(axis=-1, keepdims=True)
                      ).astype(o_ref.dtype)


@jax.named_scope("attn.mla_prefill")
def mla_prefill_attention(q_nope, q_rope, k_nope, k_rope, v, start, *,
                          scale, block_q=BLOCK_Q, block_s=BLOCK_S,
                          interpret=None, window=None, sel=None):
    """A chunk's causal attention with a two-part key (module docstring):
    q_nope (H, T, Dn) and q_rope (H, T, Dr) the queries at [start, start +
    T), k_nope (H, S, Dn), k_rope (S, Dr) shared by the heads, v (H, S,
    Dv) -> (H, T, Dv) float32; `window` (a static int) and `sel` (T, S)
    bool as the module docstring says. The kernel on the TPU (`interpret=True`:
    interpreted, for the CPU tests); the plain form elsewhere and for
    shapes that do not tile. What a grid step of the kernel covers is
    `grid_step`'s, from these shapes."""
    h, t, dn = q_nope.shape
    s_len, dv = v.shape[1:]
    if interpret is None and jax.default_backend() == "tpu":
        interpret = False
    step = None if interpret is None else grid_step(
        h, t, s_len, dn, q_rope.shape[-1], dv, q_nope.dtype.itemsize,
        block_q=block_q, block_s=block_s, select=sel is not None)
    if step is None:
        return reference_mla_prefill_attention(
            q_nope, q_rope, k_nope, k_rope, v, start, scale=scale,
            window=window, sel=sel)
    return _tiled(q_nope, q_rope, k_nope, k_rope, v, start, sel, scale=scale,
                  step=step, window=window, interpret=interpret)


# jitted and inlined: a caller that traces the same call again (a second
# stack of layers of the same shapes, in the same chunk program) reuses
# the traced kernel; three quarters of a chunk program's tracing time is
# these kernels', a branch of the prefix switch each
@functools.partial(jax.jit, inline=True, static_argnames=(
    "scale", "step", "window", "interpret"))
def _tiled(q_nope, q_rope, k_nope, k_rope, v, start, sel, *, scale, step,
           window, interpret):
    """`mla_prefill_attention`'s kernel call at a grid step of `step` =
    (G, bq, bs)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    (h, t, _), (s_len, dv) = q_nope.shape, v.shape[1:]
    g, bq, bs = step
    lanes = 128 if bs % 128 == 0 else bs  # the softmax state's width
    # the rope parts fill whole 128-lane tiles (zeros add nothing to a
    # score; the MXU's pass over 64 lanes costs what one over 128 does)
    pad = -q_rope.shape[-1] % 128
    if pad:
        q_rope = jnp.pad(q_rope, ((0, 0), (0, 0), (0, pad)))
        k_rope = jnp.pad(k_rope, ((0, 0), (0, pad)))

    def col(i, j, st):
        return jnp.clip(j, _first_live(st, i, bq, bs, window),
                        _last_live(st, i, bq, bs))

    def of_query(x):
        return pl.BlockSpec((g, bq, x.shape[-1]),
                            lambda hg, i, j, st: (hg, i, 0))

    def of_heads(x):
        return pl.BlockSpec((g, bs, x.shape[-1]),
                            lambda hg, i, j, st: (hg, col(i, j, st), 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(h // g, t // bq, s_len // bs),
        in_specs=[of_query(q_nope), of_query(q_rope), of_heads(k_nope),
                  pl.BlockSpec((bs, k_rope.shape[-1]),
                               lambda hg, i, j, st: (col(i, j, st), 0)),
                  of_heads(v)] + ([] if sel is None else [
                      pl.BlockSpec((bq, bs),
                                   lambda hg, i, j, st: (i, col(i, j, st)))]),
        out_specs=pl.BlockSpec((g, bq, dv), lambda hg, i, j, st: (hg, i, 0)),
        scratch_shapes=[
            pltpu.VMEM((g, bq, lanes), jnp.float32),  # running row max
            pltpu.VMEM((g, bq, lanes), jnp.float32),  # running row sums
            pltpu.VMEM((g, bq, dv), jnp.float32),   # output accumulator
            pltpu.VMEM((bq, bs), jnp.float32),      # the tile's bias
        ],
    )
    return pl.pallas_call(
        functools.partial(_kernel, scale=scale, bq=bq, bs=bs, **(
            {} if window is None else {"window": window}), **(
            {} if sel is None else {"select": True})),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((h, t, dv), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret, name="mla_prefill_attention",
    )(jnp.asarray(start, jnp.int32).reshape(1), q_nope, q_rope, k_nope,
      k_rope, v, *(() if sel is None else (sel.astype(jnp.int8),)))
