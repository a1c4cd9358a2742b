"""Learned sparse attention: a DeepSeek-Sparse-Attention-style indexer in
front of the LLaMA block's attention (`LlamaConfig.index_topk`).

Each layer carries, beside q/k/v/o, three small projections of the same
normed input h: index queries `wq` (C, Hi*Di), ONE index key head `wk`
(C, Di) and per-head weights `ww` (C, Hi). With RoPE on index queries and
keys,

    I[t, s] = (Hi * Di)^-1/2 * sum_j w[t, j] * relu(qI[t, j] . kI[s])

and query t attends the `index_topk` positions s <= t of largest I[t, s]
(ties to the smaller s; all of them while fewer exist) and nothing else.
The selection is EXACT and is a set — nothing needs its order: `select`
finds the k-th largest score by bisection over the float32 bit pattern
(32 counting passes, no sort) and breaks a tie at the threshold by
position. What attention then reads is masked by that set; whether a
kernel masks inside a streaming read or gathers rows is its own choice
(ops/pallas/sparse_attention.py, cached_attention.paged_decode_attention's
`sel`), the set is not.

The index key of every position is cache state: the transient prefill
row and the paged pool carry it as a third leaf "ik" beside "k" and "v"
(one head, Di wide; the pool stores it `lane_padded` like every row).

The same scores and the same exact selection serve a SECOND shape of
indexer (models/mla.py, a layer kind with `MlaConfig.index_topk`): index
queries projected from the query latent c_q where this module projects them
from h, a LayerNorm on the index key, RoPE on a part of the index lanes, and
a set that masks the absorbed read of a latent pool (decode) and a chunk's
up-projected latent attention (prefill) where this module's masks K and V of
GQA heads. There the index key is a leaf of ONE layer kind — "ik" beside
"latent" in the layers that select, absent from the layers under a window —
and its width, its layers and its block table are that kind's
(runtime/paged_kvcache.py, leaves by layer kind). `index_scores`, `select`
and `select_live` are shared; the projections and the callers are each
module's own.

Three callers, one mathematics:
  * `dense_attn` — the whole-sequence forward (`llama.block_apply`);
  * `DsaFamilyRows.prefill` — a chunk of queries at [start, start + T)
    against the transient row (everything before them and themselves);
  * `DsaFamilyRows._attn_rows` — one query a slot against the paged pool.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from dnn_tpu.models import llama
from dnn_tpu.ops.attention import apply_rope, merge_heads, rope_cos_sin
from dnn_tpu.ops.nn import linear
from dnn_tpu.runtime.paged_kvcache import scan_rows

_NEG_BIG = -1e30

__all__ = ["init_indexer", "index_project", "index_scores", "select",
           "select_live", "dense_attn", "DsaFamilyRows"]


def init_indexer(key, cfg, dtype=jnp.float32):
    """The indexer's three projections ({"kernel"} dicts: read through
    `ops.nn.linear`, so a serving process holds them in its compute dtype
    by `ops.nn.matmul_operand`'s rule). `ww` is drawn at unit scale over
    its fan-in so that the per-head weights are of order one."""
    c, hi, di = cfg.n_embd, cfg.index_n_head, cfg.index_head_dim
    kq, kk, kw = jax.random.split(key, 3)
    std = c ** -0.5  # unit-RMS h -> unit-variance index queries and keys

    def kern(k, shape):
        return {"kernel": (jax.random.normal(k, shape) * std).astype(dtype)}

    return {"wq": kern(kq, (c, hi * di)), "wk": kern(kk, (c, di)),
            "ww": kern(kw, (c, hi))}


def index_project(ip, h, positions, *, cfg, compute_dtype):
    """h (B, T, C), `positions` (T,) or (B, T) absolute -> index queries
    (B, T, Hi, Di) and the index key (B, T, Di), both rotated (the same
    theta as attention, over the whole Di), and head weights (B, T, Hi)
    float32."""
    b, t, _ = h.shape
    hi, di = cfg.index_n_head, cfg.index_head_dim
    qi = linear(ip["wq"], h, compute_dtype=compute_dtype).reshape(b, t, hi, di)
    ki = linear(ip["wk"], h, compute_dtype=compute_dtype)
    w = linear(ip["ww"], h, compute_dtype=compute_dtype).astype(jnp.float32)
    cos, sin = rope_cos_sin(positions, di, theta=cfg.rope_theta)
    return (apply_rope(qi, cos[..., None, :], sin[..., None, :]),
            apply_rope(ki, cos, sin), w)


def index_scores(qi, w, ki):
    """qi (B, T, Hi, Di), w (B, T, Hi), ki (B, S, Di) -> I (B, T, S)
    float32 (module docstring). `+ 0.0` makes a -0.0 sum +0.0: the two
    compare equal and must select alike."""
    hi, di = qi.shape[-2:]
    s = jnp.einsum("bthd,bsd->bths", qi, ki,
                   preferred_element_type=jnp.float32)
    out = jnp.einsum("bths,bth->bts", jax.nn.relu(s), w,
                     preferred_element_type=jnp.float32)
    return out * (hi * di) ** -0.5 + 0.0


def _ordered_bits(x):
    """float32 -> uint32 whose unsigned order is the floats' order."""
    bits = lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    neg = (bits >> 31) == 1
    return jnp.where(neg, ~bits, bits | jnp.uint32(1 << 31))


def select(scores, valid, k: int):
    """The set a query reads: scores (..., S) float32, `valid` (..., S)
    bool (the positions it may read: causal, live) -> bool (..., S), true
    at the k valid positions of largest score — ties to the smaller
    index — and at every valid position where there are at most k.

    Exact, with no sort: the k-th largest score's bit pattern is built
    from the top bit down (a candidate bit stays if at least k keys reach
    the candidate: 32 counting passes), everything above it is in, and
    of the keys equal to it the first (k - count above) by position."""
    key = jnp.where(valid, _ordered_bits(scores), jnp.uint32(0))
    # a valid key is never 0: the smallest, -inf's, is 0x007fffff

    def bit(i, tau):
        # (`asarray`: the loop hands a Python int where jit is disabled)
        cand = tau | (jnp.uint32(1) << (
            jnp.uint32(31) - jnp.asarray(i).astype(jnp.uint32)))
        enough = (key >= cand).sum(-1, keepdims=True) >= k
        return jnp.where(enough, cand, tau)

    tau = lax.fori_loop(0, 32, bit,
                        jnp.zeros(key.shape[:-1] + (1,), jnp.uint32))
    above = key > tau
    tied = (key == tau) & valid
    room = k - above.sum(-1, keepdims=True)
    # at least `room` keys are tied (k keys reach tau). Where exactly
    # `room` are — every row, but for a tie that straddles the cut — all
    # of them are in and nothing needs their order; the prefix count
    # (a pass of its own over the scores) runs only for such a tie
    return lax.cond(
        (tied.sum(-1, keepdims=True) > room).any(),
        lambda: above | (tied & (jnp.cumsum(tied, axis=-1) <= room)),
        lambda: above | tied) & valid


def select_live(scores, valid, k: int, n_live, parts: int = 4):
    """`select` for scores (..., S) of which only the first `n_live`
    columns (a traced count) can be valid: the passes run over the
    smallest of `parts` equal-step prefixes of S that holds them — one
    compiled program, the prefix chosen as it runs — and the rest is
    false. A chunk early in a prompt pays for its context, not for the
    row's."""
    s_len = scores.shape[-1]
    if s_len % parts:
        return select(scores, valid, k)
    step = s_len // parts

    def on_prefix(n):
        def run():
            sel = select(scores[..., :n], valid[..., :n], k)
            return jnp.pad(sel, [(0, 0)] * (sel.ndim - 1) + [(0, s_len - n)])
        return run

    return lax.switch(jnp.clip((n_live - 1) // step, 0, parts - 1),
                      [on_prefix(step * (i + 1)) for i in range(parts)])


def _masked_gqa(q, k, v, sel):
    """q (B, H, T, D), k/v (B, KV, S, D), sel (B, T, S) bool -> (B, H, T,
    D) float32: softmax over the selected positions only."""
    return llama._gqa_scores_attend(
        q, k, v, lambda s: jnp.where(sel[:, None, None], s, _NEG_BIG))


def dense_attn(bp, h, *, cfg, compute_dtype):
    """`llama._dense_attn` under the indexer's selection: the whole (B, T,
    C) sequence, full (T, T) index scores."""
    t = h.shape[1]
    positions = jnp.arange(t)
    q, k, v = llama._qkv_rope(bp, h, positions, cfg=cfg,
                              compute_dtype=compute_dtype)
    with jax.named_scope("dsa.index"):
        qi, ki, w = index_project(bp["attn"]["indexer"], h, positions,
                                  cfg=cfg, compute_dtype=compute_dtype)
        scores = index_scores(qi, w, ki)
    with jax.named_scope("dsa.select"):
        causal = positions[:, None] >= positions[None, :]
        sel = select(scores, jnp.broadcast_to(causal, scores.shape),
                     cfg.index_topk)
    y = _masked_gqa(q, k, v, sel)
    return linear(bp["attn"]["o"], merge_heads(y.astype(h.dtype)),
                  compute_dtype=compute_dtype)


def _chunk_block(bp, x, rows, start_pos, *, cfg, compute_dtype, ffn,
                 attn_kernel):
    """One block over a prefill chunk x (1, T, C) at [start_pos,
    start_pos + T): K, V and the index key written into the layer's rows of
    the transient row cache `rows` (bound to the layer: `paged_kvcache.
    LayerRows`; rows "k", "v" (1, KV, S, D), "ik" (1, 1, S, Di)), each
    query's set chosen among the row's positions up to its own, attention
    under it."""
    from dnn_tpu.ops.pallas.sparse_attention import (
        chunk_index_scores,
        sparse_prefill_attention,
    )

    b, t, _ = x.shape
    kv, g, d = cfg.n_kv_head, cfg.n_head // cfg.n_kv_head, cfg.head_dim
    # the kernels on the TPU, their plain forms elsewhere
    interpret = True if attn_kernel == "interpret" else None
    with jax.named_scope("llama.block.cached_attn"):
        h = llama._pre_normed(bp, x, cfg)
        positions = start_pos + jnp.arange(t)
        q, k, v = llama._qkv_rope(bp, h, positions, cfg=cfg,
                                  compute_dtype=compute_dtype)
        with jax.named_scope("dsa.index"):
            qi, ki, w = index_project(bp["attn"]["indexer"], h, positions,
                                      cfg=cfg, compute_dtype=compute_dtype)
        with jax.named_scope("kv_pool.write"):
            rows.write(start_pos, k=k, v=v, ik=ki[:, None])
        c = rows.read()
        with jax.named_scope("dsa.index"):
            scores = chunk_index_scores(qi[0], w[0], c["ik"][0, 0],
                                        start_pos, interpret=interpret)
        with jax.named_scope("dsa.select"):
            cols = jnp.arange(scores.shape[-1])
            sel = select_live(scores, cols[None, :] <= positions[:, None],
                              cfg.index_topk, start_pos + t)
        with jax.named_scope("attn.sparse_prefill"):
            y = sparse_prefill_attention(
                q[0].reshape(kv, g, t, d), c["k"][0], c["v"][0], sel,
                start_pos, interpret=interpret)
        y = y.reshape(1, cfg.n_head, t, d)
        o = linear(bp["attn"]["o"], merge_heads(y.astype(x.dtype)),
                   compute_dtype=compute_dtype)
    with jax.named_scope("llama.block.mlp"):
        return (llama._branches_residual(bp, x, o, h, cfg=cfg,
                                         compute_dtype=compute_dtype,
                                         ffn=ffn), rows)


class DsaFamilyRows(llama.LlamaFamilyRows):
    """`LlamaFamilyRows` for a config with an indexer: the caches carry
    the index key as a third leaf, the prefill chunk and the decode rows
    select before they attend. Paged pools only (`cache_leaves` tells the
    batcher what to allocate); what assumes two leaves — the prefix
    store, the KV tier, int8 pools, speculative verify — is refused by
    the batcher at construction (`requires_paged`, `cache_leaves`)."""

    requires_paged = True

    def __init__(self, cfg, **kw):
        super().__init__(cfg, **kw)
        if cfg.sliding_window is not None or cfg.attn_softcap is not None:
            raise ValueError("an indexer selects among all positions: no "
                             "sliding window, no softcap")
        self.index_dim = cfg.index_head_dim
        self.index_topk = cfg.index_topk
        # what a position's state is, name -> (heads, width): the pool
        # is built from it (paged_kvcache.init_paged_cache)
        kv = (cfg.n_kv_head, cfg.head_dim)
        self.cache_leaves = {"k": kv, "v": kv, "ik": (1, self.index_dim)}

    def init_cache(self, batch, max_len, dtype):
        if dtype in ("int8", "int4"):
            raise ValueError("a cache with an index-key leaf is float "
                             "(int8 / int4 caches assume K and V alone)")
        c = llama.init_cache(self.cfg, batch, max_len, dtype)
        c["ik"] = jnp.zeros((self.cfg.n_layer, batch, 1, max_len,
                             self.index_dim), c["k"].dtype)
        return c

    def prefill(self, prepared, padded, row_cache, start_pos=0, *,
                moe_stats=False):
        cfg, compute_dtype = self.cfg, self.compute_dtype
        x = llama._scaled_embed(prepared, padded, cfg)
        if compute_dtype is not None:
            x = x.astype(compute_dtype)

        blocks, bind = llama.scan_form(prepared["blocks"], self.ffn)

        def block(bp, carry, rows):
            x, acc = carry
            bp = bind(bp)

            def run(f):
                return _chunk_block(
                    bp, x, rows, start_pos, cfg=cfg,
                    compute_dtype=compute_dtype, ffn=f,
                    attn_kernel=self.attn_kernel)

            (y, rows), acc = llama._run_block(self.ffn, acc, run)
            return (y, acc), rows

        acc0 = llama._stats_acc(moe_stats)
        (x, acc), new_cache = scan_rows(block, (x, acc0), blocks, row_cache)
        x = x.astype(jnp.float32)  # what `head` is handed, in the finish
        if moe_stats:
            return x, new_cache, acc
        return x, new_cache

    def _attn_rows(self, bp, x, layer_cache, pos, write, codec, window):
        """`LlamaFamilyRows._attn_rows` with the selection between the
        projections and the read: this step's index key goes into the
        pool first (the slot's own position competes like any other),
        the slot's live index keys are scored against its one query, and
        the K/V write-and-attend is handed the set."""
        cfg, compute_dtype = self.cfg, self.compute_dtype
        b = x.shape[0]
        kv, g, d = cfg.n_kv_head, cfg.n_head // cfg.n_kv_head, cfg.head_dim
        h = llama._pre_normed(bp, x, cfg)
        q, k, v = self._qkv_rows(bp, h, pos)
        with jax.named_scope("dsa.index"):
            qi, ki, w = index_project(bp["attn"]["indexer"], h, pos[:, None],
                                      cfg=cfg, compute_dtype=compute_dtype)
        layer_cache = codec.write_index_rows(layer_cache, ki, pos, write)
        with jax.named_scope("dsa.index"):
            scores = index_scores(qi, w, codec.index_view(
                layer_cache, self.index_dim))[:, 0]  # (B, S)
        with jax.named_scope("dsa.select"):
            cols = jnp.arange(scores.shape[-1])
            sel = select(scores, (cols[None, :] <= pos[:, None])
                         & write[:, None], self.index_topk)
        with jax.named_scope("attn.sparse_decode"):
            y, layer_cache = codec.write_attend_rows(
                q.reshape(b, kv, g, d), layer_cache, k, v, pos, write,
                window=window, sel=sel)
        y = y.reshape(b, cfg.n_head, 1, d)
        o = linear(bp["attn"]["o"], merge_heads(y.astype(x.dtype)),
                   compute_dtype=compute_dtype)
        return h, o, layer_cache

    def verify_rows(self, *a, **kw):
        raise ValueError("speculative verify attends every cached "
                         "position: not available with an indexer")
