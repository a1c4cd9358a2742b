"""Pallas TPU kernels for a prefill chunk under learned sparse attention
(models/dsa.py): a chunk of T queries at positions [start, start + T)
against the transient row cache — everything before them and themselves.

  * `chunk_index_scores` — the indexer's scores I[t, s] of the chunk's
    queries against the row's index keys, (T, S) float32: per tile one
    (bq, Di) x (Di, bs) product a head, relu, the head's weight, summed
    in VMEM. The plain form materialises (T, Hi, S) first — 1 GB for a
    1024-token chunk against 16 k positions.
  * `sparse_prefill_attention` — flash-style attention of the chunk's
    queries over the row under each query's SET (a (T, S) mask, which
    already lies within the causal limit): the G query heads of a KV
    head ride one tile's rows, so K and V stream once a KV head, and the
    mask is applied inside the online softmax. Gathering 2048 rows for
    each of 1024 queries would move gigabytes; the mask costs the
    products a causal kernel makes anyway.

Both skip what lies past the chunk's last position: `start` rides scalar
prefetch, a tile past the limit is neither fetched (its block index is
clamped to the last live one, and a repeated index is not copied again)
nor computed. One compiled program serves every chunk start.

**What a grid step of `sparse_prefill_attention` costs, and which steps
exist (PR 59; PERF.md section 5, "The sparse prefill kernel alone").** A
step is G heads x bq rows against one (bs, D) tile of K and of V
(`grid_step`: 2048 rows x 512 columns at the Keye and SALA cuts), ONE
product each way — the chip timed a product a head of 128 rows at 1.7-1.8
times this form's time (each product reloads the K and V tiles into the
MXU, and 128 rows a load cannot hide it), a loop over heads of 512 rows
within 2 % of it, at G = 8 and at G = 16 alike. The set enters as one
(bq, bs) float32 bias of 0 | -1e30 a step, added under every head's
rows; the softmax state is 128 lanes wide with the sum kept lane by lane,
so a tile costs one reduction across lanes a row (the maximum's) and its
rescales are whole-register products; a row with nothing chosen so far
is held at zero by `_NO_ROW` in the exponent. That body runs a live step
at 3.1-3.2 ns per 1 k (query, column) pairs (the MXU's two products need
2.6) where two `where`s under a (G, bq, bs) broadcast of the mask, a
cross-lane sum and 128-wide re-broadcasts of the state took 6.3-7.7. The
grid's last axis is DYNAMIC: it ends with the chunk's live prefix
(`walked_columns`, a count the program reads as it runs), so the row
behind the prefix costs no step at all (0.2-0.3 us each, 770-980 of them
at start 0) — the same operands, one compile; a `lax.switch` over four or
eight static extents timed within 3 % of it and compiled a kernel a
branch.

Each has its plain `jax.numpy` form, which is what runs off the TPU and
for shapes that do not tile, and the oracle of tests/test_dsa.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_NEG_BIG = -1e30
# what a row's running maximum is held above inside the exponent: a row
# with nothing chosen so far has maximum _NEG_BIG, and exp(_NEG_BIG -
# _NO_ROW) is 0 where exp(_NEG_BIG - _NEG_BIG) would be 1
_NO_ROW = -1e29
_LANES = 128

BLOCK_Q, BLOCK_S = 256, 512  # sparse_prefill_attention's full tile
VMEM_LIMIT_BYTES = 64 * 1024 * 1024

__all__ = ["chunk_index_scores", "sparse_prefill_attention",
           "reference_chunk_index_scores",
           "reference_sparse_prefill_attention", "grid_step",
           "walked_columns"]


def reference_chunk_index_scores(qi, w, ki):
    """qi (T, Hi, Di), w (T, Hi) f32, ki (S, Di) -> (T, S) f32:
    (Hi * Di)^-1/2 * sum_j w[t, j] * relu(qi[t, j] . ki[s])."""
    from dnn_tpu.models.dsa import index_scores

    return index_scores(qi[None], w[None], ki[None])[0]


def reference_sparse_prefill_attention(q, k, v, sel):
    """q (KV, G, T, D), k/v (KV, S, D), sel (T, S) bool — or (KV, T, S), a
    set a KV head — -> (KV, G, T, D) f32: softmax(q . k / sqrt(D)) over the
    selected columns, times v."""
    d = q.shape[-1]
    s = jnp.einsum("kgtd,ksd->kgts", q.astype(jnp.float32),
                   k.astype(jnp.float32),
                   preferred_element_type=jnp.float32) / jnp.sqrt(d)
    s = jnp.where(sel[None, None] if sel.ndim == 2 else sel[:, None], s,
                  _NEG_BIG)
    return jnp.einsum("kgts,ksd->kgtd", jax.nn.softmax(s, axis=-1),
                      v.astype(jnp.float32),
                      preferred_element_type=jnp.float32)


def _tiles(t, s, block_q, block_s):
    """(bq, bs) that tile a (t, s) problem, or None."""
    bq = min(block_q, t)
    bs = next((b for b in (block_s, 256, 128) if b <= s and s % b == 0), None)
    if bs is None or t % bq:
        return None
    return bq, bs


def _last_live(start_ref, qi, bq, bs):
    """The last column tile that holds a position some row of query tile
    qi may read."""
    return (start_ref[0] + (qi + 1) * bq - 1) // bs


# ----------------------------------------------------------------------
# index scores
# ----------------------------------------------------------------------

def _index_kernel(start_ref, q_ref, w_ref, k_ref, o_ref, *, scale, bq, bs):
    from jax.experimental import pallas as pl

    qi, si = pl.program_id(0), pl.program_id(1)
    live = si <= _last_live(start_ref, qi, bq, bs)

    @pl.when(live)
    def _():
        k = k_ref[...]  # (bs, Di)
        w = w_ref[...]  # (bq, Hi) f32
        acc = jnp.zeros((bq, bs), jnp.float32)
        for j in range(q_ref.shape[0]):
            s = jax.lax.dot_general(
                q_ref[j], k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)  # (bq, bs)
            acc = acc + jnp.maximum(s, 0.0) * w[:, j:j + 1]
        o_ref[...] = acc * scale + 0.0

    @pl.when(jnp.logical_not(live))
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)


@jax.named_scope("dsa.index")
def chunk_index_scores(qi, w, ki, start, *, block_q=256, block_s=512,
                       interpret=None):
    """The chunk's index scores (module docstring): qi (T, Hi, Di) and w
    (T, Hi) of the queries at [start, start + T), ki (S, Di) the row's
    index keys -> (T, S) float32. Columns past a query's own position
    hold numbers nobody reads (zeros where a whole tile lies past the
    chunk). The kernel on the TPU (`interpret=True`: interpreted, for
    the CPU tests); the plain form elsewhere and for shapes that do not
    tile."""
    t, hi, di = qi.shape
    s_len = ki.shape[0]
    tiles = _tiles(t, s_len, block_q, block_s)
    if interpret is None and jax.default_backend() == "tpu":
        interpret = False
    if interpret is None or tiles is None:
        return reference_chunk_index_scores(qi, w, ki)
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bq, bs = tiles
    kernel = functools.partial(_index_kernel, scale=(hi * di) ** -0.5,
                               bq=bq, bs=bs)

    def k_map(i, j, st):
        return (jnp.minimum(j, _last_live(st, i, bq, bs)), 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(t // bq, s_len // bs),
        in_specs=[pl.BlockSpec((hi, bq, di), lambda i, j, st: (0, i, 0)),
                  pl.BlockSpec((bq, hi), lambda i, j, st: (i, 0)),
                  pl.BlockSpec((bs, di), k_map)],
        out_specs=pl.BlockSpec((bq, bs), lambda i, j, st: (i, j)),
    )
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((t, s_len), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret, name="chunk_index_scores",
    )(jnp.asarray(start, jnp.int32).reshape(1), jnp.swapaxes(qi, 0, 1),
      w.astype(jnp.float32), ki)


# ----------------------------------------------------------------------
# attention under the selection
# ----------------------------------------------------------------------

def walked_columns(start, t, s_len, *, block_q=BLOCK_Q, block_s=BLOCK_S):
    """The columns of a row of `s_len` that `sparse_prefill_attention`'s
    grid covers for a chunk of `t` queries at [start, start + t): the
    chunk's live prefix in whole column tiles, the whole row where the
    shapes do not tile (the plain form reads all of it). The kernel's
    last grid axis is this many columns long — `start` an int or traced —
    and the batcher counts `dsa.walked_positions_total` with it: one
    rule."""
    tiles = _tiles(t, s_len, block_q, block_s)
    if tiles is None:
        return s_len
    bs = tiles[1]
    live = (start + t + bs - 1) // bs * bs
    return min(live, s_len) if isinstance(live, int) \
        else jnp.minimum(live, s_len)


def grid_step(g, t, s_len, d, itemsize, *, block_q=BLOCK_Q, block_s=BLOCK_S):
    """(bq, bs) — the query rows a head and the columns one grid step of
    `sparse_prefill_attention` covers for G = g query heads a KV head, or
    None where (t, s_len) do not tile. The step's rows are the G heads' bq
    each, one product against the K tile and one against the V tile: bq is
    `block_q`, halved while what a row holds in VMEM — its float32 scores,
    exponentials and their operand copy, its blocks of q and of the
    float32 output (double-buffered), its softmax state — times G * bq
    passes half the limit the call states (the other half is the
    compiler's, as in ops/pallas/mla_attention.py `grid_step`): 2048 rows
    at G = 8 and at G = 16 for 128-wide heads."""
    tiles = _tiles(t, s_len, block_q, block_s)
    if tiles is None:
        return None
    bq, bs = tiles
    a_row = 3 * 4 * bs + 2 * d * itemsize + 3 * 4 * d + 2 * 4 * _LANES
    while bq % 16 == 0 and g * bq * a_row > VMEM_LIMIT_BYTES // 2:
        bq //= 2
    return bq, bs


def _sparse_prefill_kernel(start_ref, q_ref, k_ref, v_ref, sel_ref, o_ref,
                           m_scr, l_scr, acc_scr, *, scale, bq, bs):
    from jax.experimental import pallas as pl

    qi, si, ns = pl.program_id(1), pl.program_id(2), pl.num_programs(2)
    g, _, d = q_ref.shape[1:]
    rows = g * bq
    lanes = m_scr.shape[-1]

    @pl.when(si == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_BIG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def across(x, n):
        """x (rows, lanes), every lane of a row the same -> (rows, n)."""
        if n % lanes:
            return jnp.broadcast_to(x[:, :1], (rows, n))
        return x if n == lanes else jnp.concatenate([x] * (n // lanes), 1)

    def folded(x, op):
        """x (rows, bs) -> (rows, lanes): `op` over the lane tiles, lane
        by lane (whole vector registers: no lane crosses another)."""
        out = x[:, :lanes]
        for c in range(lanes, bs, lanes):
            out = op(out, x[:, c:c + lanes])
        return out

    @pl.when(si <= _last_live(start_ref, qi, bq, bs))
    def _step():
        # the tile's set as ONE float32 bias of 0 | -1e30, built once a
        # step (the set a KV head: this head's) and added under each of
        # the G heads' rows
        chosen = sel_ref[...] if len(sel_ref.shape) == 2 else sel_ref[0]
        bias = jnp.where(chosen.astype(jnp.int32) != 0, 0.0, _NEG_BIG)
        q = q_ref[0].reshape(rows, d)  # the G heads' rows of this tile
        k, v = k_ref[0], v_ref[0]      # (bs, d)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (rows, bs)
        s = (s.reshape(g, bq, bs) + bias[None]).reshape(rows, bs)
        # the state is `lanes` wide: the maximum the same in every lane of
        # a row, the SUM lane by lane (lane j the sum of the columns j, j +
        # lanes, ...: `_finish` adds the lanes up) — one reduction across
        # lanes a row a tile, the maximum's, and whole-register rescales
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, folded(s, jnp.maximum).max(
            axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        # a row with nothing chosen so far keeps its state: its
        # exponentials are 0, not exp(0)
        p = jnp.exp(s - across(jnp.maximum(m_new, _NO_ROW), bs))
        l_scr[...] = l_scr[...] * alpha + folded(p, jnp.add)
        acc_scr[...] = acc_scr[...] * across(alpha, d) + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(si == ns - 1)
    def _finish():
        o_ref[0] = (acc_scr[...] / l_scr[...].sum(axis=-1, keepdims=True)
                    ).reshape(g, bq, d).astype(o_ref.dtype)


@jax.named_scope("attn.sparse_prefill")
def sparse_prefill_attention(q, k, v, sel, start, *, block_q=BLOCK_Q,
                             block_s=BLOCK_S, interpret=None):
    """Attention of a chunk under each query's set (module docstring): q
    (KV, G, T, D) the queries at [start, start + T), G query heads a KV
    head; k/v (KV, S, D) the row; sel (T, S) bool, true where query t
    reads column s — within s <= start + t, and never empty —, or (KV, T, S)
    where the set differs by KV head (models/block_select.py). Returns
    (KV, G, T, D) float32. The kernel on the TPU (`interpret=True`:
    interpreted); the plain form elsewhere and for shapes that do not
    tile. What a grid step covers is `grid_step`'s and how far the grid
    goes `walked_columns`', both from these shapes."""
    kv, g, t, d = q.shape
    s_len = k.shape[1]
    if interpret is None and jax.default_backend() == "tpu":
        interpret = False
    step = None if interpret is None else grid_step(
        g, t, s_len, d, q.dtype.itemsize, block_q=block_q, block_s=block_s)
    if step is None:
        return reference_sparse_prefill_attention(q, k, v, sel)
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bq, bs = step
    lanes = _LANES if bs % _LANES == 0 else bs  # the softmax state's width
    kernel = functools.partial(_sparse_prefill_kernel, scale=d ** -0.5,
                               bq=bq, bs=bs)
    start = jnp.asarray(start, jnp.int32)

    def col(i, j, st):
        return jnp.minimum(j, _last_live(st, i, bq, bs))

    qspec = pl.BlockSpec((1, g, bq, d), lambda h, i, j, st: (h, 0, i, 0))
    cspec = pl.BlockSpec((1, bs, d), lambda h, i, j, st: (h, col(i, j, st), 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        # the last axis ends with the chunk's live prefix, a count the
        # program reads as it runs: the same operands whatever the start,
        # no step past the prefix
        grid=(kv, t // bq, walked_columns(
            start, t, s_len, block_q=block_q, block_s=block_s) // bs),
        in_specs=[qspec, cspec, cspec,
                  pl.BlockSpec((bq, bs),
                               lambda h, i, j, st: (i, col(i, j, st)))
                  if sel.ndim == 2 else pl.BlockSpec(
                      (1, bq, bs),
                      lambda h, i, j, st: (h, i, col(i, j, st)))],
        out_specs=qspec,
        scratch_shapes=[
            pltpu.VMEM((g * bq, lanes), jnp.float32),  # running row max
            pltpu.VMEM((g * bq, lanes), jnp.float32),  # running row sums
            pltpu.VMEM((g * bq, d), jnp.float32),      # output accumulator
        ],
    )
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((kv, g, t, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret, name="sparse_prefill_attention",
    )(start.reshape(1), q, k, v, sel.astype(jnp.int8))
