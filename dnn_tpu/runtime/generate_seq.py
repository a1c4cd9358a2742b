"""Sequence-sharded KV-cache decode — serving beyond one device's cache.

Why: at long context the KV cache, not the weights, is what no longer
fits: a GPT-2-small-shaped model at S=128k, B=8 carries a multi-GB f32
cache. The two sequence-parallel strategies already in the tree
(ring attention, Ulysses — dnn_tpu/parallel/{ring_attention,ulysses}.py)
cover STATELESS forwards; this module is the serving bridge: a decode loop whose cache is sharded over the
"seq" mesh axis, each device owning a contiguous block of positions.

Design (and why it is NOT a ring):

  * Cache layout: device i of n owns global positions
    [i*Sd, (i+1)*Sd), Sd = S_max/n — a (L, B, H, Sd, D) local cache.
    Nothing cache-shaped ever moves between devices.
  * Decode step at position p: the (B, 1, C) hidden state is replicated
    (it is tiny); every device computes q/k/v, but only p's OWNER writes
    k/v into its slice. Attention runs as a DISTRIBUTED SOFTMAX: each
    device reduces its local slice to per-row stats
    (m_i = max score, l_i = sum exp(s − m_i), o_i = exp(s − m_i) @ v),
    then one pmax + two psums combine them exactly:
        M = pmax(m_i);  l = Σ l_i e^{m_i−M};  o = Σ o_i e^{m_i−M};
        out = o / l.
    This is the online-softmax merge (same algebra as flash/ring
    attention) applied once across shards — exact, not approximate.
    A q-side ring (rotating the query past every cache shard, n hops of
    latency per layer) would serve a long QUERY; for single-token decode
    the query is one row, so collapsing each shard to O(B*H*D) stats and
    psum-ing them costs one collective round instead of n hops.
  * Prefill (prototype scope): the prompt's K/V are computed by the
    standard full forward — replicated compute over a TRANSIENT cache of
    the prompt's t positions only (never the decode region), from which
    each device gathers its own columns; peak per-device cache is
    t + S_max/n, and the S_max-sized state only ever exists sharded.
    This is acceptable until prompts themselves exceed one device; a
    production prefill would run the ring-attention forward and write
    shards in place (the two modules compose — same mesh axis).
  * Sampling runs replicated with the same rng on every device, so all
    shards agree on the next token with no extra collective.

Parity contract (tests/test_generate_seq.py): token-for-token equal to
the single-device `make_generate` while each device's cache holds only
S_max/n positions — the criterion that T exceeds one device's cache.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from dnn_tpu.models.gpt import GPTConfig, head
from dnn_tpu.ops.attention import merge_heads
from dnn_tpu.ops.nn import gelu, layer_norm, linear
from dnn_tpu.parallel.mesh import SEQ_AXIS
from dnn_tpu.runtime.generate import (
    _embed_at,
    _qkv_heads,
    _sample,
    forward_with_cache,
    init_cache,
)

_NEG_BIG = -1e30

__all__ = ["make_generate_seq_sharded"]


def _local_attn_stats(q, k_local, v_local, local_limit):
    """One shard's partial attention: q (B,H,1,D) vs the local cache
    slice (B,H,Sd,D), masked to local positions <= local_limit (a scalar;
    negative = nothing valid here). Returns (m, l, o): running max (B,H,1),
    exp-sum (B,H,1), unnormalized value sum (B,H,1,D) — the online-softmax
    partials the cross-shard psum combines."""
    d = q.shape[-1]
    s = jnp.einsum("bhtd,bhsd->bhts", q.astype(jnp.float32),
                   k_local.astype(jnp.float32),
                   preferred_element_type=jnp.float32) / jnp.sqrt(d)
    cols = jnp.arange(k_local.shape[2])
    s = jnp.where(cols[None, None, None, :] <= local_limit, s, _NEG_BIG)
    m = jnp.max(s, axis=-1)                      # (B,H,1)
    e = jnp.exp(s - m[..., None])
    # rows with no valid position: m == NEG_BIG and every e == 1; zero
    # them via the mask sum so they contribute nothing after the shift
    e = jnp.where(cols[None, None, None, :] <= local_limit, e, 0.0)
    l = jnp.sum(e, axis=-1)                      # (B,H,1)
    o = jnp.einsum("bhts,bhsd->bhtd", e, v_local.astype(jnp.float32))
    return m, l, o


def make_generate_seq_sharded(cfg: GPTConfig, mesh, *, max_new_tokens: int,
                              temperature: float = 0.0,
                              top_k: Optional[int] = None,
                              top_p: Optional[float] = None,
                              compute_dtype=None,
                              axis_name: str = SEQ_AXIS):
    """Build generate(prepared, ids, rng) with the KV cache sharded over
    `mesh`'s seq axis. The prompt length is static per compilation; the
    total context (prompt + max_new_tokens, padded up to a multiple of the
    axis size) partitions into per-device slices."""
    if max_new_tokens < 1:
        raise ValueError(f"max_new_tokens must be >= 1, got {max_new_tokens}")
    n = mesh.shape[axis_name]

    def per_device(prepared, ids, rng):
        b, t = ids.shape
        s_max = t + max_new_tokens
        sd = -(-s_max // n)  # ceil: each device owns sd positions
        i = lax.axis_index(axis_name)
        lo = i * sd  # my first global position

        # ---- prefill: full forward (replicated), keep my K/V slice.
        # The transient cache covers ONLY the prompt's t positions — never
        # the decode region — so peak per-device cache is t + sd, not the
        # full s_max everywhere (the whole point of sharding). Each device
        # then gathers the columns of its own global range; positions
        # beyond the prompt (or beyond s_max on the ragged last shard)
        # zero out and stay masked until decode writes them. ----
        prompt_cache = init_cache(cfg, b, t, compute_dtype or jnp.float32)
        # attn_kernel pinned off: this forward runs INSIDE shard_map,
        # where the "auto" policy's Pallas engagement is untested (same
        # pin as every other shard_map call site)
        logits, prompt_cache = forward_with_cache(
            prepared, ids, prompt_cache, 0, cfg=cfg,
            compute_dtype=compute_dtype, attn_kernel=False)
        g = lo + jnp.arange(sd)          # my global positions
        in_prompt = g < t
        local = {
            kk: jnp.where(
                in_prompt[None, None, None, :, None],
                jnp.take(prompt_cache[kk], jnp.clip(g, 0, t - 1), axis=3),
                0,
            )
            for kk in ("k", "v")
        }  # (L, B, H, Sd, D) — my positions only
        rng, sub = jax.random.split(rng)
        tok = _sample(logits[:, -1], sub, temperature=temperature,
                      top_k=top_k, top_p=top_p)

        def block_step(bp, x, lc_k, lc_v, p):
            """One block at decode position p against my cache slice."""
            h = layer_norm(bp["ln_1"], x, eps=cfg.ln_eps)
            q, k, v = _qkv_heads(bp, h, cfg=cfg, compute_dtype=compute_dtype)
            # p's owner writes the new row into its slice
            p_loc = jnp.clip(p - lo, 0, sd - 1)
            own = jnp.logical_and(p >= lo, p < lo + sd)
            lc_k = jnp.where(
                own,
                lax.dynamic_update_slice_in_dim(
                    lc_k, k.astype(lc_k.dtype), p_loc, axis=2),
                lc_k)
            lc_v = jnp.where(
                own,
                lax.dynamic_update_slice_in_dim(
                    lc_v, v.astype(lc_v.dtype), p_loc, axis=2),
                lc_v)
            # distributed softmax over shards: local stats, then combine
            local_limit = jnp.minimum(p - lo, sd - 1)  # < 0 -> no valid pos
            m, l, o = _local_attn_stats(q, lc_k, lc_v, local_limit)
            g_m = lax.pmax(m, axis_name)
            w = jnp.exp(m - g_m)
            g_l = lax.psum(l * w, axis_name)
            g_o = lax.psum(o * w[..., None], axis_name)
            y = g_o / jnp.maximum(g_l, 1e-30)[..., None]
            x = x + linear(bp["attn"]["proj"], merge_heads(y.astype(x.dtype)),
                           compute_dtype=compute_dtype)
            h = layer_norm(bp["ln_2"], x, eps=cfg.ln_eps)
            mlp = linear(bp["mlp"]["proj"],
                         gelu(linear(bp["mlp"]["fc"], h,
                                     compute_dtype=compute_dtype)),
                         compute_dtype=compute_dtype)
            return x + mlp, lc_k, lc_v

        def decode_one(local, tok, rng, p):
            x = _embed_at(prepared, tok[:, None], p,
                          compute_dtype=compute_dtype)

            def layer(carry, layer_in):
                bp, lk, lv = layer_in
                y, lk, lv = block_step(bp, carry, lk, lv, p)
                return y, (lk, lv)

            x, (k_new, v_new) = lax.scan(
                layer, x, (prepared["blocks"], local["k"], local["v"]))
            logits = head(prepared, x.astype(jnp.float32), cfg=cfg,
                          compute_dtype=compute_dtype)
            rng, sub = jax.random.split(rng)
            nxt = _sample(logits[:, -1], sub, temperature=temperature,
                          top_k=top_k, top_p=top_p)
            return {"k": k_new, "v": v_new}, nxt, rng

        def step(carry, j):
            local, tok, rng = carry
            local, nxt, rng = decode_one(local, tok, rng, t + j)
            return (local, nxt, rng), tok

        (_, last, _), toks = lax.scan(
            step, (local, tok, rng), jnp.arange(max_new_tokens - 1))
        toks = jnp.moveaxis(toks, 0, 1)
        return jnp.concatenate([toks, last[:, None]], axis=1)

    @jax.jit
    def generate(prepared, ids, rng):
        b, t = ids.shape
        if t + max_new_tokens > cfg.block_size:
            raise ValueError(
                f"prompt {t} + max_new_tokens {max_new_tokens} exceeds "
                f"block_size {cfg.block_size}")
        return jax.shard_map(
            per_device, mesh=mesh,
            in_specs=(P(), P(), P()),
            out_specs=P(),
            check_vma=False,
        )(prepared, ids, rng)

    return generate
