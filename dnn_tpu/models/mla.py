"""Multi-head latent attention (DeepSeek-V2/V3's MLA) in the LLaMA block
(`MixtralConfig.mla`, an `MlaConfig`).

Queries go through a low-rank bottleneck, and keys and values of ALL the
heads are up-projections of ONE compressed latent a position. With h the
normed input of position t (H heads; r = `kv_lora_rank`, dn | dr =
`qk_nope_head_dim` | `qk_rope_head_dim`, dv = `v_head_dim`):

    c_q = RMSNorm(h W_qa);  [q_nope | q_rope] = c_q W_qb  a head (dn | dr)
    [c_raw | k_raw] = h W_kva;  c = RMSNorm(c_raw) (r);  k_rope = RoPE(k_raw)
    [k_nope | v] = c W_kvb  a head (dn | dv)
    s = (q_nope . k_nope + RoPE(q_rope) . k_rope) / sqrt(dn + dr)
    o = softmax(s) v;  out = concat(o) W_o

`k_rope` is one vector for all the heads. **The cache holds (c, k_rope):
r + dr values a position a layer** — one leaf, "latent", of one head
(`MlaFamilyRows.cache_leaves`) — where K and V a head would be H x (dn +
dr + dv).

Two forms of the same numbers, each where it is cheaper:

  * **absorbed** (decode: one query a slot against thousands of cached
    positions). With W_kvb = [W_uk | W_uv] a head: q' = q_nope W_uk^T (r
    wide), s = (q' . c + q_rope . k_rope) / sqrt(dn + dr), o_lat = P c (r
    wide), o = o_lat W_uv. The slot's H heads are the rows of ONE product
    against its cached rows, which are key as they stand and value in
    their first r lanes: the cache is read once, never up-projected
    (`PagedKV.write_attend_latent_rows`, the paged kernel's `latent=`).
  * **up-projected** (prefill: a chunk of T queries). The chunk's context
    is up-projected to k_nope and v once a chunk and attended flash-style
    with the two-part key (ops/pallas/mla_attention.py): 2 x (dn + dr +
    dv) FLOPs a (query, position, head) pair where the absorbed form pays
    2 x (2r + dr) — 3.4x as much at DeepSeek-V3's widths — against an
    up-projection of 2 x r x (dn + dv) FLOPs a position a head, which a
    chunk of more than ~(dn + dv) r / (2r + dr - dn - dr - dv) ~ 340
    queries repays. The transient row between chunks holds LATENTS (what
    the pool gets): up-projected K and V would be H (dn + dr + dv) values
    a position, 20 KB in bfloat16 at the published widths, 1.7 GB for a
    16 k row of 5 layers. The up-projection runs over the smallest of
    `_PREFIXES` equal-step prefixes of the row that holds the chunk's
    context (one compiled program, the prefix chosen as it runs).

RoPE pairs dimensions (2i, 2i + 1) when `rope_interleave` (DeepSeek-V3's
checkpoints); here the rotary part is de-interleaved first and rotated in
the half-split convention of `ops.attention.apply_rope`, on q and k alike:
every score is that of the interleaved rotation, and the cache holds the
permuted key.

Three callers, one mathematics, as models/dsa.py: `dense_attn` (the
whole-sequence forward), `MlaFamilyRows.prefill` (a chunk against the
transient row), `MlaFamilyRows._attn_rows` (one query a slot against the
paged pool). Scopes: `mla.project` (W_qa, W_qb, W_kva, the two norms,
RoPE), `mla.absorb` (W_uk on the query, W_uv on the output),
`mla.up_project` (W_kvb on cached latents), `attn.mla_decode`,
`attn.mla_prefill`.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax import lax

from dnn_tpu.models import llama
from dnn_tpu.ops.attention import apply_rope, rope_cos_sin
from dnn_tpu.ops.nn import linear, rms_norm

__all__ = ["MlaConfig", "init_attn", "project", "dense_attn",
           "MlaFamilyRows"]

_PREFIXES = 8


@dataclasses.dataclass(frozen=True)
class MlaConfig:
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    # True: the checkpoint's rotary pairs are (2i, 2i + 1)
    rope_interleave: bool = True

    @property
    def latent_dim(self):
        """What a position's cache row holds: the latent and the rope key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def scale(self):
        return (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5


def init_attn(key, cfg, dtype=jnp.float32):
    """A block's attention params: {"q_a", "q_b", "kv_a", "kv_b", "o"}
    ({"kernel"} dicts read through `ops.nn.linear`: held in the compute
    dtype by `ops.nn.matmul_operand`'s rule) and the two latent norms.
    `kv_b`'s columns are a head's [k_nope | v], head-major, as the
    published checkpoints store `kv_b_proj`."""
    m, c, h = cfg.mla, cfg.n_embd, cfg.n_head
    # six, the last unused: the five kernels keep the values that PERF.md's
    # chip runs drew from their seeds
    ks = jax.random.split(key, 6)

    def kern(k, shape, std=0.02):
        return {"kernel": (jax.random.normal(k, shape) * std).astype(dtype)}

    def ones(n):
        # the seeded gains are exactly one: sharper or flatter attention
        # (0.6, 1.5) moved bfloat16's agreement with float32 by under a
        # point on the chip (PERF.md section 6, PR 35)
        return {"scale": jnp.ones((n,), dtype)}

    return {
        "q_a": kern(ks[0], (c, m.q_lora_rank)),
        "q_a_norm": ones(m.q_lora_rank),
        "q_b": kern(ks[1], (m.q_lora_rank,
                            h * (m.qk_nope_head_dim + m.qk_rope_head_dim))),
        "kv_a": kern(ks[2], (c, m.latent_dim)),
        "kv_a_norm": ones(m.kv_lora_rank),
        "kv_b": kern(ks[3], (m.kv_lora_rank,
                             h * (m.qk_nope_head_dim + m.v_head_dim))),
        "o": kern(ks[4], (h * m.v_head_dim, c),
                  std=0.02 / (2 * cfg.n_layer) ** 0.5),
    }


def _rotate(x, cos, sin, m: MlaConfig):
    """RoPE on x (..., dr) with tables (..., dr) (module docstring)."""
    if m.rope_interleave:
        x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
    return apply_rope(x, cos, sin)


@jax.named_scope("mla.project")
def project(ap, h, positions, *, cfg, compute_dtype):
    """h (B, T, C) normed, `positions` (T,) or (B, T) absolute -> q_nope
    (B, T, H, dn), q_rope (B, T, H, dr) rotated, and the position's cache
    row (B, T, r + dr): the normed latent and the rotated rope key."""
    m = cfg.mla
    b, t, _ = h.shape
    dn, dr = m.qk_nope_head_dim, m.qk_rope_head_dim
    c_q = rms_norm(ap["q_a_norm"],
                   linear(ap["q_a"], h, compute_dtype=compute_dtype),
                   eps=cfg.rms_eps)
    q = linear(ap["q_b"], c_q, compute_dtype=compute_dtype).reshape(
        b, t, cfg.n_head, dn + dr)
    kv = linear(ap["kv_a"], h, compute_dtype=compute_dtype)
    c = rms_norm(ap["kv_a_norm"], kv[..., :m.kv_lora_rank], eps=cfg.rms_eps)
    cos, sin = rope_cos_sin(positions, dr, theta=cfg.rope_theta)
    q_rope = _rotate(q[..., dn:], cos[..., None, :], sin[..., None, :], m)
    k_rope = _rotate(kv[..., m.kv_lora_rank:], cos, sin, m)
    return (q[..., :dn], q_rope,
            jnp.concatenate([c, k_rope.astype(c.dtype)], axis=-1))


def _kv_b(ap, cfg, compute_dtype):
    """W_kvb as (r, H, dn + dv), in the compute dtype."""
    m = cfg.mla
    w = ap["kv_b"]["kernel"]
    if compute_dtype is not None:
        w = w.astype(compute_dtype)
    return w.reshape(m.kv_lora_rank, cfg.n_head,
                     m.qk_nope_head_dim + m.v_head_dim)


@jax.named_scope("mla.up_project")
def up_project(ap, latent, *, cfg, compute_dtype):
    """Cached rows' latents (S, r) -> k_nope (H, S, dn), v (H, S, dv)."""
    dn = cfg.mla.qk_nope_head_dim
    w = _kv_b(ap, cfg, compute_dtype)
    kv = jnp.einsum("sr,rhd->hsd", latent.astype(w.dtype), w,
                    preferred_element_type=jnp.float32).astype(latent.dtype)
    return kv[..., :dn], kv[..., dn:]


def _chunk_attn(ap, q_nope, q_rope, rows, start, *, cfg, compute_dtype,
                interpret):
    """The up-projected form for T queries at [start, start + T): q_nope
    (T, H, dn), q_rope (T, H, dr), `rows` (S, r + dr) the cached rows the
    queries may read (theirs among them) -> (T, H * dv)."""
    from dnn_tpu.ops.pallas.mla_attention import mla_prefill_attention

    m = cfg.mla
    k_nope, v = up_project(ap, rows[:, :m.kv_lora_rank], cfg=cfg,
                           compute_dtype=compute_dtype)
    y = mla_prefill_attention(
        jnp.swapaxes(q_nope, 0, 1), jnp.swapaxes(q_rope, 0, 1), k_nope,
        rows[:, m.kv_lora_rank:], v, start, scale=m.scale,
        interpret=interpret)  # (H, T, dv)
    return jnp.swapaxes(y, 0, 1).reshape(q_nope.shape[0], -1)


def dense_attn(bp, h, *, cfg, compute_dtype):
    """The whole (B, T, C) sequence, up-projected: `llama._dense_attn`'s
    place in `block_apply`."""
    ap = bp["attn"]
    q_nope, q_rope, rows = project(ap, h, jnp.arange(h.shape[1]), cfg=cfg,
                                   compute_dtype=compute_dtype)
    y = jnp.stack([
        _chunk_attn(ap, q_nope[i], q_rope[i], rows[i], 0, cfg=cfg,
                    compute_dtype=compute_dtype, interpret=None)
        for i in range(h.shape[0])])
    return linear(ap["o"], y.astype(h.dtype), compute_dtype=compute_dtype)


class MlaFamilyRows(llama.LlamaFamilyRows):
    """`LlamaFamilyRows` for a config with latent attention: the caches
    hold ONE leaf, decode is absorbed, a prefill chunk up-projected
    (module docstring). Paged pools only; what assumes K and V — the
    prefix store, the KV tier, int8 / int4 pools, interleaved prefill,
    speculative verify — is refused by the batcher at construction
    (`requires_paged`, `cache_leaves`)."""

    requires_paged = True
    latent_attention = True

    def __init__(self, cfg, **kw):
        super().__init__(cfg, **kw)
        if cfg.sliding_window is not None or cfg.attn_softcap is not None \
                or cfg.rope_scaling is not None or cfg.post_norms \
                or cfg.parallel_block or not cfg.pre_norm:
            raise ValueError("latent attention is built for the plain "
                             "pre-norm sequential block: no sliding window, "
                             "softcap, RoPE scaling or post-norms")
        self.cache_leaves = {"latent": (1, cfg.mla.latent_dim)}

    def init_cache(self, batch, max_len, dtype):
        if dtype in ("int8", "int4"):
            raise ValueError("a cache of latents is float (int8 / int4 "
                             "caches assume K and V alone)")
        return {"latent": jnp.zeros((self.cfg.n_layer, batch, 1, max_len,
                                     self.cfg.mla.latent_dim), dtype)}

    def _chunk_block(self, bp, x, rows, start_pos, ffn):
        """One block over a prefill chunk x (1, T, C) at [start_pos,
        start_pos + T): the chunk's cache rows written into the layer's
        transient row `rows` (1, 1, S, r + dr), attention up-projected
        over the smallest prefix of it that holds the context."""
        cfg, compute_dtype = self.cfg, self.compute_dtype
        interpret = True if self.attn_kernel == "interpret" else None
        t, s_len = x.shape[1], rows.shape[2]
        with jax.named_scope("llama.block.cached_attn"):
            h = llama._pre_normed(bp, x, cfg)
            q_nope, q_rope, new = project(
                bp["attn"], h, start_pos + jnp.arange(t), cfg=cfg,
                compute_dtype=compute_dtype)
            with jax.named_scope("kv_pool.write"):
                rows = lax.dynamic_update_slice_in_dim(
                    rows, new[:, None].astype(rows.dtype), start_pos, axis=2)

            def over(n):
                return lambda: _chunk_attn(
                    bp["attn"], q_nope[0], q_rope[0], rows[0, 0, :n],
                    start_pos, cfg=cfg, compute_dtype=compute_dtype,
                    interpret=interpret)

            step = s_len // _PREFIXES
            if s_len % _PREFIXES or step < t:
                y = over(s_len)()
            else:
                y = lax.switch(
                    jnp.clip((start_pos + t - 1) // step, 0, _PREFIXES - 1),
                    [over(step * (i + 1)) for i in range(_PREFIXES)])
            o = linear(bp["attn"]["o"], y[None].astype(x.dtype),
                       compute_dtype=compute_dtype)
        with jax.named_scope("llama.block.mlp"):
            return (llama._branches_residual(bp, x, o, h, cfg=cfg,
                                             compute_dtype=compute_dtype,
                                             ffn=ffn), rows)

    def prefill(self, prepared, padded, row_cache, start_pos=0, *,
                moe_stats=False):
        cfg = self.cfg
        x = llama._scaled_embed(prepared, padded, cfg)
        if self.compute_dtype is not None:
            x = x.astype(self.compute_dtype)

        def layer(bind, carry, layer_in):
            x, acc = carry
            bp, rows = layer_in
            bp = bind(bp)
            (y, rows), acc = llama._run_block(
                self.ffn, acc,
                lambda f: self._chunk_block(bp, x, rows, start_pos, f))
            return (y, acc), rows

        carry = (x, jnp.zeros((3,), jnp.int32) if moe_stats else None)
        new_rows = []
        for stack, layers in llama.layer_stacks(prepared, cfg):
            rows = row_cache["latent"]
            if layers is not None:
                rows = rows[layers[0]:layers[1]]
            blocks, bind = llama.scan_form(stack, self.ffn)
            carry, rows = lax.scan(functools.partial(layer, bind), carry,
                                   (blocks, rows))
            new_rows.append(rows)
        x, acc = carry
        new_cache = {"latent": new_rows[0] if len(new_rows) == 1
                     else jnp.concatenate(new_rows)}
        x = x.astype(jnp.float32)  # what `head` is handed, in the finish
        if moe_stats:
            return x, new_cache, acc
        return x, new_cache

    def _attn_rows(self, bp, x, layer_cache, pos, write, codec, window):
        """The absorbed form: this step's row goes into the pool, the
        slot's heads meet its cached rows in one product each way."""
        cfg, compute_dtype = self.cfg, self.compute_dtype
        m, ap = cfg.mla, bp["attn"]
        dn = m.qk_nope_head_dim
        h = llama._pre_normed(bp, x, cfg)
        q_nope, q_rope, row = project(ap, h, pos[:, None], cfg=cfg,
                                      compute_dtype=compute_dtype)
        w = _kv_b(ap, cfg, compute_dtype)
        with jax.named_scope("mla.absorb"):
            q_lat = jnp.einsum("bhd,rhd->bhr", q_nope[:, 0].astype(w.dtype),
                               w[..., :dn],
                               preferred_element_type=jnp.float32)
            q = jnp.concatenate([q_lat.astype(x.dtype), q_rope[:, 0]], -1)
        with jax.named_scope("attn.mla_decode"):
            y, layer_cache = codec.write_attend_latent_rows(
                q, layer_cache, row, pos, write, value_dim=m.kv_lora_rank,
                scale=m.scale)  # (B, H, r) float32
        with jax.named_scope("mla.absorb"):
            o = jnp.einsum("bhr,rhd->bhd", y.astype(w.dtype), w[..., dn:],
                           preferred_element_type=jnp.float32)
        o = linear(ap["o"], o.reshape(o.shape[0], 1, -1).astype(x.dtype),
                   compute_dtype=compute_dtype)
        return h, o, layer_cache

    def verify_rows(self, *a, **kw):
        raise ValueError("speculative verify reads K and V: not available "
                         "with a cache of latents")
