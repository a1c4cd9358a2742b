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
    at most `_PREFIXES` prefixes of the row that holds the chunk's context
    (one compiled program, the prefix chosen as it runs), cut at multiples
    of the kernel's full column tile (`prefix_lengths`).

RoPE pairs dimensions (2i, 2i + 1) when `rope_interleave` (DeepSeek-V3's
checkpoints); here the rotary part is de-interleaved first and rotated in
the half-split convention of `ops.attention.apply_rope`, on q and k alike:
every score is that of the interleaved rotation, and the cache holds the
permuted key.

**Widths by layer KIND.** An `MlaConfig` is one kind's widths, and a model
has one (`MixtralConfig.mla`: every layer, the kind "full") or two
(`mla_window` beside it, the kind "window", with `layer_types` saying
which layer is which). A kind may have its own head count and RoPE theta,
and further (all off by default, so a one-kind model computes what it
did):

  * `lora_rescale`: c_q and c are multiplied by sqrt(C / r_q) and sqrt(C /
    r) after their norms (LongCat-Flash's `mla_scale_q_lora` /
    `mla_scale_kv_lora`); the cache holds the rescaled c.
  * `head_gate`: g = sigmoid(h W_g), one number a head, multiplies the
    head's output before W_o ("Gated Attention for LLMs", head-wise;
    scope `mla.gate`).
  * `window` = W: a query at t reads t - W < u <= t only. The cache
    leaf of such a kind is read over the window's blocks, a prefill
    chunk up-projects window + chunk positions and no more.
  * `index_topk`: a DeepSeek-V3.2-style indexer (models/dsa.py's scores
    and exact selection) whose index queries come from the QUERY LATENT
    c_q, whose index key is LayerNorm(h W_ik) with RoPE on its first
    `index_rope_dim` lanes, and whose set masks the absorbed read of the
    latent pool (decode) and the chunk's up-projected attention
    (prefill). The index key is cache state: a second leaf "ik" of the
    kind, beside "latent".

What a position's cache holds therefore differs BY KIND (`cache_kinds`:
kind -> its layers, its leaves name -> (heads, width), its window, the
name of its block tables): "latent" (r + dr) and "ik" for the full kind
under "tables", "latent_w" (r_w + dr) for the window kind under
"tables_w" (runtime/paged_kvcache.py).

Three callers, one mathematics, as models/dsa.py: `dense_attn` (the
whole-sequence forward), `MlaFamilyRows.prefill` (a chunk against the
transient row), `MlaFamilyRows._attn_rows` (one query a slot against the
paged pool). Scopes: `mla.project` (W_qa, W_qb, W_kva, the two norms,
RoPE), `mla.absorb` (W_uk on the query, W_uv on the output),
`mla.up_project` (W_kvb on cached latents), `mla.gate`, `dsa.index`,
`dsa.select`, `attn.mla_decode` / `attn.mla_prefill` (one kind, whole
context), `attn.mla_sparse_decode` / `attn.mla_sparse_prefill` (under a
selection), `attn.mla_window_decode` / `attn.mla_window_prefill`.
"""

from __future__ import annotations

import contextlib
import dataclasses
import logging
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from dnn_tpu.models import dsa, llama
from dnn_tpu.ops.attention import apply_rope, rope_cos_sin
from dnn_tpu.ops.nn import linear, rms_norm

__all__ = ["MlaConfig", "init_attn", "project", "dense_attn",
           "prefix_lengths", "MlaFamilyRows"]

log = logging.getLogger(__name__)

_PREFIXES = 8


def prefix_lengths(s_len, t):
    """The prefixes of a transient row of `s_len` positions over which a
    chunk of `t` queries is attended, shortest first; the chunk whose last
    position is p takes number p // lengths[0]. At most `_PREFIXES`, in
    equal steps, the last the whole row — and the step a multiple of the
    kernel's full column tile (ops/pallas/mla_attention.py `BLOCK_S`), not
    `s_len // _PREFIXES`: a prefix the tile does not divide is attended in
    128- or 256-column steps (a row of 13 312 cut in eighths of 1664 = 13
    x 128 ran half its pairs in the former, a fifth in the latter), at 3.2
    and 1.7 times a full tile's time a pair on a v5e (PERF.md section 5,
    PR 42). A step is also at least the chunk, so that no prefix is
    shorter than the queries it would hold. The up-projection covers at
    most one step more than the context, as it did."""
    from dnn_tpu.ops.pallas.mla_attention import BLOCK_S

    step = -(-max(-(-s_len // _PREFIXES), t) // BLOCK_S) * BLOCK_S
    return [min(n, s_len) for n in range(step, s_len + step, step)]


@dataclasses.dataclass(frozen=True)
class MlaConfig:
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    # True: the checkpoint's rotary pairs are (2i, 2i + 1)
    rope_interleave: bool = True
    # ---- a layer KIND's own (module docstring; defaults: the model's
    # head count and theta, none of the further mechanisms) ----
    n_head: Optional[int] = None
    rope_theta: Optional[float] = None
    lora_rescale: bool = False
    head_gate: bool = False
    window: Optional[int] = None
    index_topk: Optional[int] = None
    index_n_head: int = 64
    index_head_dim: int = 128
    index_rope_dim: int = 64

    @property
    def latent_dim(self):
        """What a position's cache row holds: the latent and the rope key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def scale(self):
        return (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5

    def heads(self, cfg):
        return self.n_head or cfg.n_head

    def theta(self, cfg):
        return self.rope_theta or cfg.rope_theta


# a kind's cache leaves and block tables, by name (module docstring)
KIND_LEAVES = {"full": ("latent", "ik", "tables"),
               "window": ("latent_w", None, "tables_w")}


def kinds(cfg):
    """{kind: its MlaConfig} of a model config, "full" first."""
    out = {"full": cfg.mla}
    if getattr(cfg, "mla_window", None) is not None:
        out["window"] = cfg.mla_window
    return out


def kind_layers(cfg):
    """{kind: how many of the model's layers are of it}."""
    types = getattr(cfg, "layer_types", None)
    if types is None:
        return {"full": cfg.n_layer}
    return {k: sum(t == k for t in types) for k in kinds(cfg)}


def init_attn(key, cfg, dtype=jnp.float32, m=None):
    """A block's attention params of kind `m` (None: `cfg.mla`): {"q_a",
    "q_b", "kv_a", "kv_b", "o"} ({"kernel"} dicts read through
    `ops.nn.linear`: held in the compute dtype by `ops.nn.matmul_operand`'s
    rule) and the two latent norms; with `head_gate` "gate" (C, H), with an
    indexer "indexer" {"wq" (r_q, Hi * Di), "wk" (C, Di), "k_norm", "ww"
    (C, Hi)}. `kv_b`'s columns are a head's [k_nope | v], head-major, as
    the published checkpoints store `kv_b_proj`."""
    m = m or cfg.mla
    c, h = cfg.n_embd, m.heads(cfg)
    # six: the five kernels keep the values that PERF.md's chip runs drew
    # from their seeds, the sixth is the gate's where a kind has one
    ks = jax.random.split(key, 6)

    def kern(k, shape, std=0.02):
        return {"kernel": (jax.random.normal(k, shape) * std).astype(dtype)}

    def ones(n):
        # the seeded gains are exactly one: sharper or flatter attention
        # (0.6, 1.5) moved bfloat16's agreement with float32 by under a
        # point on the chip (PERF.md section 6, PR 35)
        return {"scale": jnp.ones((n,), dtype)}

    ap = {
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
    if m.head_gate:
        # unit-RMS h -> gate logits of order one: a program that left the
        # gate out is told apart
        ap["gate"] = kern(ks[5], (c, h), std=c ** -0.5)
    if m.index_topk is not None:
        kq, kk, kw = jax.random.split(jax.random.fold_in(key, 19), 3)
        hi, di = m.index_n_head, m.index_head_dim
        # c_q has RMS sqrt(C / r_q) under the rescale, one without:
        # either way index queries and keys of unit variance
        q_in = c if m.lora_rescale else m.q_lora_rank
        ap["indexer"] = {
            "wq": kern(kq, (m.q_lora_rank, hi * di), std=q_in ** -0.5),
            "wk": kern(kk, (c, di), std=c ** -0.5),
            "k_norm": {"scale": jnp.ones((di,), dtype),
                       "bias": jnp.zeros((di,), dtype)},
            "ww": kern(kw, (c, hi), std=c ** -0.5),
        }
    return ap


def _rotate(x, cos, sin, m: MlaConfig):
    """RoPE on x (..., dr) with tables (..., dr) (module docstring)."""
    if m.rope_interleave:
        x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
    return apply_rope(x, cos, sin)


@jax.named_scope("mla.project")
def project(ap, h, positions, *, cfg, compute_dtype, m=None,
            with_query_latent=False):
    """h (B, T, C) normed, `positions` (T,) or (B, T) absolute -> q_nope
    (B, T, H, dn), q_rope (B, T, H, dr) rotated, and the position's cache
    row (B, T, r + dr): the normed (and rescaled) latent and the rotated
    rope key; with `with_query_latent` also c_q (B, T, r_q), what an
    indexer's queries are projected from. `m`: the layer's kind (None:
    `cfg.mla`)."""
    m = m or cfg.mla
    b, t, _ = h.shape
    dn, dr = m.qk_nope_head_dim, m.qk_rope_head_dim
    c_q = rms_norm(ap["q_a_norm"],
                   linear(ap["q_a"], h, compute_dtype=compute_dtype),
                   eps=cfg.rms_eps)
    kv = linear(ap["kv_a"], h, compute_dtype=compute_dtype)
    c = rms_norm(ap["kv_a_norm"], kv[..., :m.kv_lora_rank], eps=cfg.rms_eps)
    if m.lora_rescale:
        c_q = c_q * jnp.asarray((cfg.n_embd / m.q_lora_rank) ** 0.5,
                                c_q.dtype)
        c = c * jnp.asarray((cfg.n_embd / m.kv_lora_rank) ** 0.5, c.dtype)
    q = linear(ap["q_b"], c_q, compute_dtype=compute_dtype).reshape(
        b, t, m.heads(cfg), dn + dr)
    cos, sin = rope_cos_sin(positions, dr, theta=m.theta(cfg))
    q_rope = _rotate(q[..., dn:], cos[..., None, :], sin[..., None, :], m)
    k_rope = _rotate(kv[..., m.kv_lora_rank:], cos, sin, m)
    out = (q[..., :dn], q_rope,
           jnp.concatenate([c, k_rope.astype(c.dtype)], axis=-1))
    return out + (c_q,) if with_query_latent else out


def index_project(ip, c_q, h, positions, *, cfg, m, compute_dtype):
    """The indexer's three projections for a kind whose index queries
    come from the query latent: c_q (B, T, r_q), h (B, T, C) -> index
    queries (B, T, Hi, Di), the index key (B, T, Di) = LayerNorm(h W_ik),
    both rotated in their first `index_rope_dim` lanes (half-split, the
    kind's theta), and head weights (B, T, Hi) float32."""
    from dnn_tpu.ops.nn import layer_norm

    b, t, _ = h.shape
    hi, di, rd = m.index_n_head, m.index_head_dim, m.index_rope_dim
    qi = linear(ip["wq"], c_q, compute_dtype=compute_dtype).reshape(
        b, t, hi, di)
    ki = layer_norm(ip["k_norm"], linear(ip["wk"], h,
                                         compute_dtype=compute_dtype),
                    eps=1e-6)  # DeepSeek-V3.2-Exp's LayerNorm(eps=1e-6)
    w = linear(ip["ww"], h, compute_dtype=compute_dtype).astype(jnp.float32)
    cos, sin = rope_cos_sin(positions, rd, theta=m.theta(cfg))

    def partly(x, cos, sin):
        return jnp.concatenate(
            [apply_rope(x[..., :rd], cos, sin), x[..., rd:]], axis=-1)

    return (partly(qi, cos[..., None, :], sin[..., None, :]),
            partly(ki, cos, sin).astype(h.dtype), w)


@jax.named_scope("mla.gate")
def gated(ap, h, y, *, compute_dtype):
    """y (..., T, H, dv) times sigmoid(h W_g) (..., T, H), a head's
    output by its one gate; y itself for a kind without the gate."""
    if "gate" not in ap:
        return y
    g = jax.nn.sigmoid(linear(ap["gate"], h, compute_dtype=compute_dtype
                              ).astype(jnp.float32))
    return (y.astype(jnp.float32) * g[..., None]).astype(y.dtype)


def _kv_b(ap, cfg, compute_dtype, m=None):
    """W_kvb as (r, H, dn + dv), in the compute dtype."""
    m = m or cfg.mla
    w = ap["kv_b"]["kernel"]
    if compute_dtype is not None:
        w = w.astype(compute_dtype)
    return w.reshape(m.kv_lora_rank, m.heads(cfg),
                     m.qk_nope_head_dim + m.v_head_dim)


@jax.named_scope("mla.up_project")
def up_project(ap, latent, *, cfg, compute_dtype, m=None):
    """Cached rows' latents (S, r) -> k_nope (H, S, dn), v (H, S, dv)."""
    dn = (m or cfg.mla).qk_nope_head_dim
    w = _kv_b(ap, cfg, compute_dtype, m)
    kv = jnp.einsum("sr,rhd->hsd", latent.astype(w.dtype), w,
                    preferred_element_type=jnp.float32).astype(latent.dtype)
    return kv[..., :dn], kv[..., dn:]


def _chunk_attn(ap, q_nope, q_rope, rows, start, *, cfg, compute_dtype,
                interpret, m=None, sel=None):
    """The up-projected form for T queries at [start, start + T): q_nope
    (T, H, dn), q_rope (T, H, dr), `rows` (S, r + dr) the cached rows the
    queries may read (theirs among them; column 0 is position 0, or for a
    kind with a window any position: `start` is then the first query's
    column) -> (T, H, dv). `sel` (T, S) bool narrows what each query
    reads; the kind's window bands it."""
    from dnn_tpu.ops.pallas.mla_attention import mla_prefill_attention

    m = m or cfg.mla
    # a kind's own scope around its up-projection and its kernel alike
    # (`mla.up_project` stays the innermost name of the former)
    scope = ("attn.mla_sparse_prefill" if sel is not None else
             "attn.mla_window_prefill" if m.window else None)
    extra = {k: x for k, x in (("sel", sel), ("window", m.window))
             if x is not None}
    with jax.named_scope(scope) if scope else contextlib.nullcontext():
        k_nope, v = up_project(ap, rows[:, :m.kv_lora_rank], cfg=cfg,
                               compute_dtype=compute_dtype, m=m)
        y = mla_prefill_attention(
            jnp.swapaxes(q_nope, 0, 1), jnp.swapaxes(q_rope, 0, 1), k_nope,
            rows[:, m.kv_lora_rank:], v, start, scale=m.scale,
            interpret=interpret, **extra)  # (H, T, dv)
    return jnp.swapaxes(y, 0, 1)


def dense_attn(bp, h, *, cfg, compute_dtype, m=None):
    """The whole (B, T, C) sequence, up-projected: `llama._dense_attn`'s
    place in `block_apply`, for a layer of kind `m`."""
    m = m or cfg.mla
    ap = bp["attn"]
    t = h.shape[1]
    positions = jnp.arange(t)
    q_nope, q_rope, rows, c_q = project(
        ap, h, positions, cfg=cfg, compute_dtype=compute_dtype, m=m,
        with_query_latent=True)
    sel = [None] * h.shape[0]
    if m.index_topk is not None:
        with jax.named_scope("dsa.index"):
            qi, ki, w = index_project(ap["indexer"], c_q, h, positions,
                                      cfg=cfg, m=m,
                                      compute_dtype=compute_dtype)
            scores = dsa.index_scores(qi, w, ki)
        with jax.named_scope("dsa.select"):
            causal = positions[:, None] >= positions[None, :]
            sel = dsa.select(scores, jnp.broadcast_to(causal, scores.shape),
                             m.index_topk)
    y = jnp.stack([
        _chunk_attn(ap, q_nope[i], q_rope[i], rows[i], 0, cfg=cfg,
                    compute_dtype=compute_dtype, interpret=None, m=m,
                    sel=sel[i])
        for i in range(h.shape[0])])  # (B, T, H, dv)
    y = gated(ap, h, y.astype(h.dtype), compute_dtype=compute_dtype)
    return linear(ap["o"], y.reshape(*y.shape[:2], -1),
                  compute_dtype=compute_dtype)


class MlaFamilyRows(llama.LlamaFamilyRows):
    """`LlamaFamilyRows` for a config with latent attention: the caches
    hold latents (and, for a kind with an indexer, index keys), decode is
    absorbed, a prefill chunk up-projected (module docstring). Paged
    pools only; what assumes K and V — the prefix store, the KV tier,
    int8 / int4 pools, interleaved prefill, speculative verify — is
    refused by the batcher at construction (`requires_paged`,
    `cache_leaves`).

    `cache_leaves` is the full kind's (name -> (heads, width)): all there
    is for a model of one kind. `cache_kinds` says it BY KIND for a model
    of two — kind -> {"layers", "leaves", "tables", "window"} — which the
    pool, its tables and the batcher's admission are built from
    (runtime/paged_kvcache.init_paged_cache)."""

    requires_paged = True
    latent_attention = True

    def __init__(self, cfg, **kw):
        super().__init__(cfg, **kw)
        if cfg.sliding_window is not None or cfg.attn_softcap is not None \
                or cfg.rope_scaling is not None or cfg.post_norms \
                or cfg.parallel_block or not cfg.pre_norm:
            raise ValueError("latent attention is built for the plain "
                             "pre-norm sequential block: no sliding window, "
                             "softcap, RoPE scaling or post-norms (a layer "
                             "kind's window is `MlaConfig.window`)")
        self.kinds = kinds(cfg)
        n_of = kind_layers(cfg)
        self.cache_kinds = {}
        for kind, m in self.kinds.items():
            latent, ik, tables = KIND_LEAVES[kind]
            leaves = {latent: (1, m.latent_dim)}
            if m.index_topk is not None:
                leaves[ik] = (1, m.index_head_dim)
            self.cache_kinds[kind] = {
                "layers": n_of[kind], "leaves": leaves, "tables": tables,
                "window": m.window}
        self.cache_leaves = self.cache_kinds["full"]["leaves"]
        if len(self.kinds) == 1 and self.kinds["full"].window is None:
            # one kind, every position kept: the pool every family has
            self.cache_kinds = None
        # what `dsa_*` counters and /statusz report for a selecting kind
        self.index_topk = self.kinds["full"].index_topk
        # kind -> {columns handed to the prefill kernel: what a grid step
        # of that call covers}, said while the chunk programs were traced
        # (/statusz `components.attention.mla_prefill`)
        self.prefill_steps = {kind: {} for kind in self.kinds}

    def init_cache(self, batch, max_len, dtype):
        if dtype in ("int8", "int4"):
            raise ValueError("a cache of latents is float (int8 / int4 "
                             "caches assume K and V alone)")
        n_of = kind_layers(self.cfg)
        out = {}
        for kind, m in self.kinds.items():
            latent, ik, _ = KIND_LEAVES[kind]
            out[latent] = jnp.zeros((n_of[kind], batch, 1, max_len,
                                     m.latent_dim), dtype)
            if m.index_topk is not None:
                out[ik] = jnp.zeros((n_of[kind], batch, 1, max_len,
                                     m.index_head_dim), dtype)
        return out

    def _attend(self, kind, ap, q_nope, q_rope, rows, start, sel=None):
        """`_chunk_attn` for a layer of `kind` over the cached `rows`;
        what a grid step of its kernel covers (`grid_step` of the call's
        shapes; None each for the plain form) is kept in
        `self.prefill_steps[kind]` by the rows' count, and logged where it
        is first said."""
        from dnn_tpu.ops.pallas.mla_attention import grid_step

        m = self.kinds[kind]
        interpret = True if self.attn_kernel == "interpret" else None
        step = None
        if interpret or jax.default_backend() == "tpu":
            step = grid_step(
                q_nope.shape[1], len(q_nope), len(rows), m.qk_nope_head_dim,
                m.qk_rope_head_dim, m.v_head_dim, q_nope.dtype.itemsize,
                select=sel is not None)
        step = dict(zip(("heads_per_step", "block_q", "block_s"),
                        step or (None,) * 3))
        if self.prefill_steps[kind].get(len(rows)) != step:
            self.prefill_steps[kind][len(rows)] = step
            log.info("mla prefill, %s layers over %d columns: a grid step "
                     "of %s", kind, len(rows), step)
        return _chunk_attn(
            ap, q_nope, q_rope, rows, start, cfg=self.cfg,
            compute_dtype=self.compute_dtype, m=m, sel=sel,
            interpret=interpret)

    def _chunk_block(self, bp, x, rows, start_pos, ffn, kind="full"):
        """One block over a prefill chunk x (1, T, C) at [start_pos,
        start_pos + T): the chunk's cache rows written into the layer's
        rows of the transient row cache `rows` (bound to the layer:
        `paged_kvcache.LayerRows`; a row (1, 1, S, width)), attention
        up-projected over the smallest prefix of the row that holds the
        context (`prefix_lengths`: cut where the kernel's full column
        tile divides them, one branch of a switch each) — or, for a kind
        with a window, over the window and the chunk. What a grid step of
        each kernel call covers goes to `self.prefill_steps` (`_attend`)."""
        cfg, compute_dtype = self.cfg, self.compute_dtype
        m = self.kinds[kind]
        latent, ik, _ = KIND_LEAVES[kind]
        interpret = True if self.attn_kernel == "interpret" else None
        t, s_len = x.shape[1], rows.leaves[latent].shape[3]
        ap = bp["attn"]
        with jax.named_scope("llama.block.cached_attn"):
            h = llama._pre_normed(bp, x, cfg)
            positions = start_pos + jnp.arange(t)
            q_nope, q_rope, new, c_q = project(
                ap, h, positions, cfg=cfg, compute_dtype=compute_dtype, m=m,
                with_query_latent=True)
            index = m.index_topk is not None
            if index:
                with jax.named_scope("dsa.index"):
                    qi, ki, w = index_project(
                        ap["indexer"], c_q, h, positions, cfg=cfg, m=m,
                        compute_dtype=compute_dtype)
            with jax.named_scope("kv_pool.write"):
                rows.write(start_pos, **{latent: new[:, None]})
                if index:
                    rows.write(start_pos, **{ik: ki[:, None]})
            lat = rows[latent][0, 0]
            sel = None
            if index:
                from dnn_tpu.ops.pallas.sparse_attention import (
                    chunk_index_scores,
                )

                with jax.named_scope("dsa.index"):
                    scores = chunk_index_scores(
                        qi[0], w[0], rows[ik][0, 0], start_pos,
                        interpret=interpret)
                with jax.named_scope("dsa.select"):
                    cols = jnp.arange(s_len)
                    sel = dsa.select_live(
                        scores, cols[None, :] <= positions[:, None],
                        m.index_topk, start_pos + t)

            def over(n):
                return lambda: self._attend(
                    kind, ap, q_nope[0], q_rope[0], lat[:n], start_pos,
                    None if sel is None else sel[:, :n])

            lengths = prefix_lengths(s_len, t)
            back = 0 if m.window is None else -(-(m.window - 1) // t) * t
            if m.window is not None and back + t < s_len:
                # the window's positions before the chunk, and the chunk
                first = jnp.clip(start_pos - back, 0, s_len - back - t)
                y = self._attend(
                    kind, ap, q_nope[0], q_rope[0],
                    lax.dynamic_slice_in_dim(lat, first, back + t),
                    start_pos - first)
            elif len(lengths) == 1:
                y = over(s_len)()
            else:
                y = lax.switch(
                    jnp.clip((start_pos + t - 1) // lengths[0], 0,
                             len(lengths) - 1),
                    [over(n) for n in lengths])
            y = gated(ap, h, y[None].astype(x.dtype),
                      compute_dtype=compute_dtype)
            o = linear(ap["o"], y.reshape(1, t, -1),
                       compute_dtype=compute_dtype)
        with jax.named_scope("llama.block.mlp"):
            return (llama._branches_residual(bp, x, o, h, cfg=cfg,
                                             compute_dtype=compute_dtype,
                                             ffn=ffn), rows)

    def prefill(self, prepared, padded, row_cache, start_pos=0, *,
                moe_stats=False):
        return llama.prefill_by_kind(
            self, prepared, padded, row_cache, start_pos, moe_stats)

    def _attn_rows(self, bp, x, layer_cache, pos, write, codec, window,
                   kind="full"):
        """The absorbed form: this step's row goes into the pool, the
        slot's heads meet its cached rows in one product each way — all
        of them, the set an indexer chose among them, or the window's."""
        cfg, compute_dtype = self.cfg, self.compute_dtype
        m, ap = self.kinds[kind], bp["attn"]
        latent, ik, tables = KIND_LEAVES[kind]
        dn = m.qk_nope_head_dim
        h = llama._pre_normed(bp, x, cfg)
        q_nope, q_rope, row, c_q = project(
            ap, h, pos[:, None], cfg=cfg, compute_dtype=compute_dtype, m=m,
            with_query_latent=True)
        w = _kv_b(ap, cfg, compute_dtype, m)
        with jax.named_scope("mla.absorb"):
            q_lat = jnp.einsum("bhd,rhd->bhr", q_nope[:, 0].astype(w.dtype),
                               w[..., :dn],
                               preferred_element_type=jnp.float32)
            q = jnp.concatenate([q_lat.astype(x.dtype), q_rope[:, 0]], -1)
        extra, scope = {}, "attn.mla_decode"
        if m.index_topk is not None:
            with jax.named_scope("dsa.index"):
                qi, ki, wi = index_project(
                    ap["indexer"], c_q, h, pos[:, None], cfg=cfg, m=m,
                    compute_dtype=compute_dtype)
            layer_cache = codec.write_index_rows(layer_cache, ki, pos, write)
            with jax.named_scope("dsa.index"):
                scores = dsa.index_scores(qi, wi, codec.index_view(
                    layer_cache, m.index_head_dim))[:, 0]  # (B, S)
            with jax.named_scope("dsa.select"):
                cols = jnp.arange(scores.shape[-1])
                extra["sel"] = dsa.select(
                    scores, (cols[None, :] <= pos[:, None])
                    & write[:, None], m.index_topk)
            scope = "attn.mla_sparse_decode"
        if m.window is not None:
            extra.update(window=m.window, leaf=latent, tables=tables)
            scope = "attn.mla_window_decode"
        with jax.named_scope(scope):
            y, layer_cache = codec.write_attend_latent_rows(
                q, layer_cache, row, pos, write, value_dim=m.kv_lora_rank,
                scale=m.scale, **extra)  # (B, H, r) float32
        with jax.named_scope("mla.absorb"):
            o = jnp.einsum("bhr,rhd->bhd", y.astype(w.dtype), w[..., dn:],
                           preferred_element_type=jnp.float32)
        o = gated(ap, h, o[:, None].astype(x.dtype),
                  compute_dtype=compute_dtype)
        o = linear(ap["o"], o.reshape(o.shape[0], 1, -1),
                   compute_dtype=compute_dtype)
        return h, o, layer_cache

    def verify_rows(self, *a, **kw):
        raise ValueError("speculative verify reads K and V: not available "
                         "with a cache of latents")
