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

Each has its plain `jax.numpy` form, which is what runs off the TPU and
for shapes that do not tile, and the oracle of tests/test_dsa.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_NEG_BIG = -1e30

__all__ = ["chunk_index_scores", "sparse_prefill_attention",
           "reference_chunk_index_scores",
           "reference_sparse_prefill_attention"]


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

def _sparse_prefill_kernel(start_ref, q_ref, k_ref, v_ref, sel_ref, o_ref,
                           m_scr, l_scr, acc_scr, *, scale, bq, bs):
    from jax.experimental import pallas as pl

    qi, si, ns = pl.program_id(1), pl.program_id(2), pl.num_programs(2)
    g, _, d = q_ref.shape[1:]
    rows = g * bq

    @pl.when(si == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_BIG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(si <= _last_live(start_ref, qi, bq, bs))
    def _step():
        q = q_ref[0].reshape(rows, d)  # the G heads' rows of this tile
        k, v = k_ref[0], v_ref[0]      # (bs, d)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (rows, bs)
        chosen = sel_ref[...].astype(jnp.int32) != 0  # (bq, bs)
        if chosen.ndim == 3:  # a set a KV head: this head's
            chosen = chosen[0]
        keep = jnp.broadcast_to(chosen[None], (g, bq, bs)).reshape(rows, bs)
        s = jnp.where(keep, s, _NEG_BIG)
        m_prev = m_scr[:, :1]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        # a row with nothing chosen in this tile keeps its state
        p = jnp.where(keep, jnp.exp(s - m_new), 0.0)
        l_scr[...] = jnp.broadcast_to(
            l_scr[:, :1] * alpha + p.sum(axis=-1, keepdims=True),
            l_scr.shape)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)

    @pl.when(si == ns - 1)
    def _finish():
        o_ref[0] = (acc_scr[...] / l_scr[:, :1]).reshape(g, bq, d) \
            .astype(o_ref.dtype)


@jax.named_scope("attn.sparse_prefill")
def sparse_prefill_attention(q, k, v, sel, start, *, block_q=128,
                             block_s=512, interpret=None):
    """Attention of a chunk under each query's set (module docstring): q
    (KV, G, T, D) the queries at [start, start + T), G query heads a KV
    head; k/v (KV, S, D) the row; sel (T, S) bool, true where query t
    reads column s — within s <= start + t, and never empty —, or (KV, T, S)
    where the set differs by KV head (models/block_select.py). Returns
    (KV, G, T, D) float32. The kernel on the TPU (`interpret=True`:
    interpreted); the plain form elsewhere and for shapes that do not
    tile."""
    kv, g, t, d = q.shape
    s_len = k.shape[1]
    tiles = _tiles(t, s_len, block_q, block_s)
    if interpret is None and jax.default_backend() == "tpu":
        interpret = False
    if interpret is None or tiles is None:
        return reference_sparse_prefill_attention(q, k, v, sel)
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bq, bs = tiles
    kernel = functools.partial(_sparse_prefill_kernel, scale=d ** -0.5,
                               bq=bq, bs=bs)

    def col(i, j, st):
        return jnp.minimum(j, _last_live(st, i, bq, bs))

    qspec = pl.BlockSpec((1, g, bq, d), lambda h, i, j, st: (h, 0, i, 0))
    cspec = pl.BlockSpec((1, bs, d), lambda h, i, j, st: (h, col(i, j, st), 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(kv, t // bq, s_len // bs),
        in_specs=[qspec, cspec, cspec,
                  pl.BlockSpec((bq, bs),
                               lambda h, i, j, st: (i, col(i, j, st)))
                  if sel.ndim == 2 else pl.BlockSpec(
                      (1, bq, bs),
                      lambda h, i, j, st: (h, i, col(i, j, st)))],
        out_specs=qspec,
        scratch_shapes=[
            pltpu.VMEM((g * bq, 128), jnp.float32),  # running row max
            pltpu.VMEM((g * bq, 128), jnp.float32),  # running row sum
            pltpu.VMEM((g * bq, d), jnp.float32),    # output accumulator
        ],
    )
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((kv, g, t, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret, name="sparse_prefill_attention",
    )(jnp.asarray(start, jnp.int32).reshape(1), q, k, v,
      sel.astype(jnp.int8))
