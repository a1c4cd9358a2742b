"""Softmax attention that reads only the BLOCKS a score over mean-pooled keys
selects (InfLLM-V2 as MiniCPM4 / MiniCPM-SALA use it): the "full" layers of
a config with `LlamaConfig.block_select`, a `BlockSelectConfig` (block 64,
kernel 32, stride 16, window 2048, topk 64, init_blocks 1 as published).

With positions from 0, block b = positions [block b, block b + block - 1],
b_t = t // block, a KV head's group the G query heads that read it:

  * a POOLED KEY is the mean of `kernel` = 2 x `stride` consecutive keys of a
    KV head (after the head's norm, as the cache holds them), one every
    `stride` positions, no parameter. It is cache state, kept as ROW r of the
    strided leaf "kc": the mean over positions [stride (r - 1), stride (r +
    1)), which exists once position stride r + stride - 1 is written — a row
    lives in the block in which it COMPLETES, so the step that completes it
    writes it where it writes K and V, and a chunk at [start, start + T)
    writes exactly the rows [start / stride, (start + T) / stride). Row 0
    would start before position 0 and is never a key. Query t sees row r iff
    1 <= r < (t + 1) // stride.
  * p_h[t, r] = softmax over the rows t sees of q_h[t] . kc[r] / sqrt(d);
    P[t, r] = the sum of p_h over the G heads of the group (`group_scores`).
  * B[t, b] = the largest P[t, r] over the rows whose pooled window overlaps
    block b: r in [rows b, rows b + rows] with rows = block / stride
    (`block_scores`: kernel rows + 1, stride rows, padding 1 over the pooled
    keys).
  * the set of (t, group) (`choose`): the LOCAL blocks b_t - window / block <
    b <= b_t, always; and of the blocks before them the `topk` of largest B,
    the first `init_blocks` forced among them, ties to the smaller b, all of
    them while fewer exist (`dsa.select` over blocks). Every head of the
    group reads the same set, under the causal mask inside the query's block.

Three callers, one mathematics: `dense_attn` (whole sequences), `chunk_attn`
(a prefill chunk against the transient row, the set as a mask a KV group for
ops/pallas/sparse_attention.py) and `decode_attn` (one query a slot against
the paged pool: the group's LIST of table entries, `PagedKV.
write_attend_block_rows`); `BlockSelectRows` is the batcher's adapter of a
model whose "full" layers select. Scopes: `bsel.pool`, `bsel.score`,
`dsa.select`, `attn.block_prefill`, `attn.block_decode`.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from dnn_tpu.models import dsa, llama, state_kind
from dnn_tpu.ops.attention import merge_heads
from dnn_tpu.ops.nn import linear

_NEG_BIG = -1e30

__all__ = ["read_positions", "pooled_rows", "group_scores", "block_scores",
           "choose", "chosen_blocks", "block_list", "dense_attn", "chunk_attn",
           "decode_attn", "BlockSelectRows"]


def read_positions(m, n: int, count: int = 1) -> int:
    """Positions the `count` queries with contexts of n, n + 1, ... positions
    read (a query of context n stands at position n - 1), of the n each
    could: host arithmetic, for the counters — a query's local blocks up to
    itself and `topk` whole blocks of those before them."""
    if count == 1:  # a decode step asks once a live slot: plain integers
        t = max(n, 1) - 1
        first = max(t // m.block - m.local_blocks + 1, 0)
        return t - first * m.block + 1 + min(first, m.topk) * m.block
    t = np.arange(max(n, 1) - 1, n - 1 + count)
    first_local = np.maximum(t // m.block - m.local_blocks + 1, 0)
    return int((t - first_local * m.block + 1
                + np.minimum(first_local, m.topk) * m.block).sum())


def pooled_rows(prev, k, m):
    """The pooled keys that complete inside a run of positions: k (B, KV, T,
    d) the run's keys (T a multiple of `stride`), `prev` (B, KV, stride, d)
    the `stride` keys before it -> (B, KV, T / stride, d) float32, row j the
    mean over the two strides that end with the run's stride j."""
    b, kv, t, d = k.shape
    seg = jnp.concatenate([prev, k], axis=2).astype(jnp.float32).reshape(
        b, kv, t // m.stride + 1, m.stride, d).mean(3)
    return (seg[:, :, :-1] + seg[:, :, 1:]) * 0.5


def group_scores(q, kc, pos, m):
    """q (B, KV, G, T, d) the queries at positions `pos` (T,) or (B, T), kc
    (B, KV, R, d) the pooled rows -> P (B, KV, T, R) float32: each head's
    softmax over the rows its query sees, summed over the group; 0 at a row
    not seen."""
    d = q.shape[-1]
    s = jnp.einsum("bkgtd,bkrd->bkgtr", q, kc.astype(q.dtype),
                   preferred_element_type=jnp.float32) / math.sqrt(d)
    rows = jnp.arange(kc.shape[2])
    seen = (rows >= 1) & (rows < ((pos + 1) // m.stride)[..., None])
    seen = seen[:, None, None] if seen.ndim == 3 else seen
    p = jax.nn.softmax(jnp.where(seen, s, _NEG_BIG), axis=-1)
    return jnp.where(seen, p, 0.0).sum(2)


def block_scores(p, m):
    """P (..., R) -> B (..., R / rows): the largest over a block's own rows
    and the next block's first (module docstring)."""
    r = m.rows
    own = p.reshape(*p.shape[:-1], p.shape[-1] // r, r).max(-1)
    nxt = jnp.concatenate(
        [p[..., r::r], jnp.zeros_like(p[..., :1])], axis=-1)
    return jnp.maximum(own, nxt)


def choose(scores, pos, m):
    """The blocks a query reads: `scores` (..., nb) float32 its block scores,
    `pos` (...) its position -> bool (..., nb), the local blocks and the
    `topk` chosen of those before them."""
    b = jnp.arange(scores.shape[-1])
    bt = (pos // m.block)[..., None]
    local = (b > bt - m.local_blocks) & (b <= bt)
    scores = jnp.where(b < m.init_blocks, jnp.inf, scores)
    return local | dsa.select(scores, jnp.broadcast_to(
        b <= bt - m.local_blocks, scores.shape), m.topk)


def chosen_blocks(qg, kc, pos, m):
    """The blocks each query of a KV group reads: qg (B, KV, G, T, d) the
    group's queries at positions `pos` ((T,), or (B, T) with a position a
    slot), kc (B, KV, R, d) the pooled rows -> bool (B, KV, T, nb). The three
    callers' one path from scores to set (scopes `bsel.score`,
    `dsa.select`)."""
    with jax.named_scope("bsel.score"):
        scores = block_scores(group_scores(qg, kc, pos, m), m)
    with jax.named_scope("dsa.select"):
        return choose(scores, pos if pos.ndim == 1 else pos[:, None], m)


def block_list(chosen, m):
    """bool (..., nb) -> (the chosen blocks in ascending order (..., n) int32
    with n = min(local + topk, nb) — entries past the count hold blocks NOT
    chosen —, the count (...) int32). The last one is the query's own."""
    n = min(m.local_blocks + m.topk, chosen.shape[-1])
    order = jnp.argsort(~chosen, axis=-1, stable=True)[..., :n]
    return order.astype(jnp.int32), chosen.sum(-1).astype(jnp.int32)


def _position_mask(chosen, pos, m, s_len):
    """chosen (..., T, nb), pos (T,) -> bool (..., T, s_len): the chosen
    blocks' positions up to the query's own."""
    cols = jnp.arange(s_len)
    return (jnp.repeat(chosen, m.block, axis=-1)[..., :s_len]
            & (cols[None, :] <= pos[:, None]))


def dense_attn(bp, h, *, cfg, compute_dtype):
    """`llama._dense_attn` under the selection: whole sequences h (B, T, C),
    the pooled keys of the whole sequence, full (T, T / stride) scores."""
    m = cfg.block_select
    b, t, _ = h.shape
    kv, g, d = cfg.n_kv_head, cfg.n_head // cfg.n_kv_head, cfg.head_dim
    positions = jnp.arange(t)
    q, k, v = llama._qkv_rope(bp, h, positions, cfg=cfg,
                              compute_dtype=compute_dtype,
                              kind=llama.kv_kinds(cfg)["full"])
    with jax.named_scope("bsel.pool"):
        kp = jnp.pad(k, ((0, 0), (0, 0), (0, -t % m.block), (0, 0)))
        kc = pooled_rows(jnp.zeros_like(kp[:, :, :m.stride]), kp, m)
    sel = _position_mask(
        chosen_blocks(q.reshape(b, kv, g, t, d), kc.astype(k.dtype),
                      positions, m), positions, m, t)
    y = llama._gqa_scores_attend(
        q, k, v, lambda s: jnp.where(sel[:, :, None], s, _NEG_BIG))
    y = llama._gated(bp, h, merge_heads(y.astype(h.dtype)), compute_dtype)
    return linear(bp["attn"]["o"], y, compute_dtype=compute_dtype)


def chunk_attn(bp, h, rows, start_pos, *, cfg, compute_dtype, attn_kernel):
    """Attention of one "full" block over a prefill chunk's normed rows h (1,
    T, C) at [start_pos, start_pos + T): K and V written into the layer's
    rows of the transient row cache `rows` (bound to the layer:
    `paged_kvcache.LayerRows`; rows "k", "v" (1, KV, S, d), "kc" (1, KV, S /
    stride, d)), the pooled keys that complete inside the chunk written
    beside them, each query's
    blocks chosen a KV group, attention under the group's mask -> (the
    o-projected output (1, T, C), rows, the form the read took)."""
    from dnn_tpu.ops.pallas.sparse_attention import sparse_prefill_attention

    m = cfg.block_select
    t = h.shape[1]
    kv, g, d = cfg.n_kv_head, cfg.n_head // cfg.n_kv_head, cfg.head_dim
    interpret = True if attn_kernel == "interpret" else None
    positions = start_pos + jnp.arange(t)
    q, k, v = llama._qkv_rope(bp, h, positions, cfg=cfg,
                              compute_dtype=compute_dtype,
                              kind=llama.kv_kinds(cfg)["full"])
    kst = k.astype(rows.leaves["k"].dtype)  # as the pool holds it
    with jax.named_scope("bsel.pool"):
        # the stride before the chunk, from the row (at start 0 whatever
        # lies there: row 0 is never a key)
        prev = lax.dynamic_slice_in_dim(
            rows["k"], jnp.maximum(start_pos - m.stride, 0), m.stride, axis=2)
        kc = pooled_rows(prev, kst, m)
    with jax.named_scope("kv_pool.write"):
        rows.write(start_pos, k=kst, v=v)
        rows.write(start_pos // m.stride, kc=kc)
    c = rows.read("k", "v", "kc")
    sel = _position_mask(
        chosen_blocks(q.reshape(1, kv, g, t, d), c["kc"], positions,
                      m)[0], positions, m, c["k"].shape[2])  # (KV, T, S)
    with jax.named_scope("attn.block_prefill"):
        y = sparse_prefill_attention(
            q[0].reshape(kv, g, t, d), c["k"][0], c["v"][0], sel,
            start_pos, interpret=interpret)
    form = "masked_kernel" if (
        interpret or jax.default_backend() == "tpu") else "plain"
    y = y.reshape(1, cfg.n_head, t, d)
    o = linear(bp["attn"]["o"],
               llama._gated(bp, h, merge_heads(y.astype(h.dtype)),
                            compute_dtype), compute_dtype=compute_dtype)
    return o, rows, form


def decode_attn(q, k, v, c, pos, write, codec, *, cfg):
    """One query a slot against the paged pool: q (B, H, 1, d), this step's k
    and v (B, KV, 1, d) at per-slot positions `pos` (B,), gated by `write`
    (B,) -> (y (B, H, 1, d), the cache, the form the read took). The pooled
    key goes into the pool where this step completes one; the slot's pooled
    rows are scored against its query a KV group, and the read — which places
    this step's K and V first — walks the group's list of chosen table
    entries."""
    m = cfg.block_select
    b = q.shape[0]
    kv, g, d = cfg.n_kv_head, cfg.n_head // cfg.n_kv_head, cfg.head_dim
    with jax.named_scope("bsel.pool"):
        c = codec.write_pooled_rows(c, k, pos, write, stride=m.stride)
    with jax.named_scope("bsel.score"):
        kc = codec.pooled_view(c, "kc", d)
    chosen = chosen_blocks(q.reshape(b, kv, g, 1, d), kc, pos[:, None], m)
    with jax.named_scope("dsa.select"):
        blocks, count = block_list(chosen[:, :, 0], m)
    y, c = codec.write_attend_block_rows(q.reshape(b, kv, g, d), c, k, v,
                                         blocks, count, pos, write)
    return y.reshape(b, cfg.n_head, 1, d), c, codec.block_form(c)


class BlockSelectRows(state_kind.StateKindRows):
    """`StateKindRows` for a model whose "full" layers select (MiniCPM-SALA:
    models/lightning.py's rule in the "linear" layers): the "full" kind
    keeps K and V under "tables" and a THIRD paged leaf whose rows are
    STRIDES and not positions — the mean-pooled keys "kc" (L_full, n_blocks,
    KV, block_len / stride, d), `cache_kinds["full"]["strided_leaves"]`: name
    -> (heads, width, stride) — written by the chunk program for every
    pooled window that completes inside the chunk and by the step on the one
    slot-step in `stride` that completes one, from K as the pool holds it. A
    query reads the blocks `choose` names for its KV group: the chunk program
    under a mask a group (`chunk_attn`), the step by walking the group's LIST
    of table entries (`decode_attn`); `block_len` must be the selection's
    block."""

    def __init__(self, cfg, **kw):
        super().__init__(cfg, **kw)
        self.select = cfg.block_select
        self.cache_kinds["full"]["strided_leaves"] = {
            "kc": (cfg.n_kv_head, cfg.head_dim, self.select.stride)}
        # the paged pool's block must be the selection's (the batcher
        # refuses another `block_len` by this name)
        self.required_block_len = self.select.block

    def select_counts(self, n, count=1):
        """Positions the `count` queries of contexts n, n + 1, ... read: what
        the `dsa.*` counters count for a selection whose unit is a block
        (the batcher asks where the family has this method)."""
        return read_positions(self.select, n, count)

    def _chunk_attn(self, bp, h, rows, start_pos, kind, **chunk_kw):
        if self._runs_rule(kind):
            return super()._chunk_attn(bp, h, rows, start_pos, kind,
                                       **chunk_kw)
        o, rows, form = chunk_attn(
            bp, h, rows, start_pos, cfg=self.cfg,
            compute_dtype=self.compute_dtype, attn_kernel=self.attn_kernel)
        self.attn_forms[kind]["prefill"] = form
        return o, rows

    def _attn_rows(self, bp, x, layer_cache, pos, write, codec, window,
                   kind="full"):
        if self._runs_rule(kind):
            return super()._attn_rows(bp, x, layer_cache, pos, write, codec,
                                      window, kind)
        h = llama._pre_normed(bp, x, self.cfg)
        q, k, v = self._qkv_rows(bp, h, pos, kind=self.kinds[kind])
        y, layer_cache, form = decode_attn(
            q, k, v, layer_cache, pos, write, codec, cfg=self.cfg)
        self.attn_forms[kind]["decode"] = form
        o = linear(bp["attn"]["o"],
                   llama._gated(bp, h, merge_heads(y.astype(x.dtype)),
                                self.compute_dtype),
                   compute_dtype=self.compute_dtype)
        return h, o, layer_cache
