"""Power retention in place of attention (Buckman, Gelada, Zhang, "Scaling
Context Requires Rethinking Attention", arXiv:2507.04239), degree 2, in the
LLaMA block (`LlamaConfig.retention`, a `llama.RetentionConfig`): a model
with NO K/V layer at all — every layer keeps a STATE a slot.

With h the normed input of position t, H query heads in groups of G over KV
heads of d (q and k normed a head and rotated as the family's):

    log g_t = logsigmoid(h W_g + b_g)          one scalar a KV head, float32
    a_ts = exp(G_t - G_s) (q_t . k_s / sqrt d)^2,   G_t = sum_{r <= t} log g_r
    y_t  = sum_{s <= t} a_ts v_s / (sum_{s <= t} a_ts + eps)

The weights are non-negative because the degree is even; the division is
the layer's normaliser. As a recurrence, a KV head:

    S_t = g_t S_{t-1} + v_t phi(k_t)^T     (d x D)
    z_t = g_t z_{t-1} + phi(k_t)           (D)
    y_t = S_t phi(q_t) / (z_t . phi(q_t) + eps)

where phi(x) . phi(y) = (x . y)^2 / d EXACTLY: the symmetric square in
tiles (`phi`): x in d / t tiles of t, the t x t products of every pair of
tiles once, pairs of two tiles times sqrt 2 — D = t sum_i (d - i t): 8 704
for d 128, t 8 (the untiled symmetric square is 8 256, the full outer product
16 384). **What a slot keeps is S and z, float32, d D + D numbers a KV head
a layer whatever the length** (35.9 MB a layer at the published widths: the
K and V of 8 772 positions) — the cache kind "retention" has two leaves
without a position axis, `state` (L, slots, KV, d, D) and `norm` (L, slots,
KV, D), no blocks and no tables (`slot_leaves`, behind models/state_kind.py
`StateKindRows` as this module's `RULE`; runtime/paged_kvcache.py's module
docstring). The state is held value-major (d x
D): the expanded axis fills the lanes, and a step's rank-one update needs
the VALUE as a column (d numbers) and the expanded key as a row.

Three forms of the same numbers:

  * **the quadratic form** (`quadratic`): the (T, T) weights as they stand —
    what chipbench/reference/brumby.py computes on its own, here for the
    tests.
  * **the step** (decode, `step_rule`): the recurrence, one token a slot,
    the state read and written ONCE — on the chip in ops/pallas/
    retention_step.py, in place in the pool at the layer's index.
  * **the chunked rule** (prefill, `chunk_rule`): positions in chunks of
    `RetentionConfig.chunk`, a chunk FROM AN INCOMING STATE: inside the
    chunk the quadratic form (no expansion), the state's part as exp(G_t)
    S_0 phi(q_t), and the outgoing state exp(G_C) S_0 + sum_s exp(G_C - G_s)
    v_s phi(k_s)^T — the two expansions are matmuls with a contraction of D,
    a KV head at a time (all heads' expanded queries at once would be 0.7
    GB). Every decay enters as exp of a DIFFERENCE of cumulative logs that
    is <= 0.
  * a PAD position (at or past `n_real` in the chunk) has g = 1 and k = 0:
    it changes neither state nor normaliser (`takes_n_real`, as models/
    kda.py).

Products run in the compute dtype with float32 accumulation (bfloat16 on
the chip: one pass of the MXU, as a softmax layer's P V), float32 at
"highest" where the compute dtype is float32; the gate, the cumulative
logs, the decays, state and normaliser are float32 always.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from dnn_tpu.models import llama, state_kind
from dnn_tpu.ops.attention import merge_heads
from dnn_tpu.ops.nn import linear

_HI = lax.Precision.HIGHEST


def state_width(d: int, tile: int) -> int:
    """D: the width of phi of a d-wide key in tiles of `tile`."""
    return tile * sum(d - i * tile for i in range(d // tile))


def slot_leaves(cfg):
    """The retention kind's cache leaves — no position axis, no tables —:
    name -> (the shape a slot a layer, dtype). Float32 whatever the
    cache's dtype."""
    d = cfg.head_dim
    wide = state_width(d, cfg.retention.tile)
    return {"state": ((cfg.n_kv_head, d, wide), jnp.float32),
            "norm": ((cfg.n_kv_head, wide), jnp.float32)}


def init_gate(key, cfg):
    """A layer's `attn["decay"]`: W_g (C, KV) ~ N(0, 0.02^2) and b_g, both
    float32 (`ops.nn.matmul_operand` casts kernels alone), the biases
    spread a KV head in the logit over `gate_range`: the slowest heads
    remember thousands of positions and the fastest forget within ten."""
    lo, hi = (math.log(g / (1.0 - g)) for g in cfg.retention.gate_range)
    return {"w": jax.random.normal(key, (cfg.n_embd, cfg.n_kv_head)) * 0.02,
            "bias": jnp.linspace(lo, hi, cfg.n_kv_head, dtype=jnp.float32)}


def phi(x, tile: int):
    """x (..., d) -> (..., D) with phi(x) . phi(y) == (x . y)^2 / d: for
    each tile i of `tile`, its products with everything from tile i on (its
    own tile's once, the later tiles' times sqrt 2), over sqrt d. (A form
    whose every block of lanes was d wide, the partners SELECTED by a 0/1
    matmul, took 9.95 ms a layer a 1024-chunk on the chip where this one
    takes 5.92: its own factors and partners are written out beside the
    product. PERF.md section 6, PR 50.)"""
    d = x.shape[-1]
    xt = x.reshape(*x.shape[:-1], d // tile, tile)
    parts = []
    for i in range(d // tile):
        rest = d - i * tile
        scale = jnp.concatenate([
            jnp.ones((tile,), x.dtype),
            jnp.full((rest - tile,), math.sqrt(2.0), x.dtype)]) / math.sqrt(d)
        part = xt[..., i, :, None] * (x[..., i * tile:] * scale)[..., None, :]
        parts.append(part.reshape(*x.shape[:-1], tile * rest))
    return jnp.concatenate(parts, axis=-1)


@functools.lru_cache(maxsize=None)
def _phi_selections(d: int, tile: int):
    """`phi`'s lanes as two 0/1 selections of x and a scale: lane n is
    x[own[n]] * x[partner[n]] * scale[n] -> (own (d, D), partner (d, D),
    scale (D,)), numpy."""
    own, partner, scale = [], [], []
    for i in range(d // tile):
        rest = np.arange(i * tile, d)
        for a in range(tile):
            own.append(np.full(len(rest), i * tile + a))
            partner.append(rest)
            scale.append(np.where(rest < (i + 1) * tile, 1.0, math.sqrt(2.0)))
    lanes = np.arange(sum(len(x) for x in own))

    def one_hot(index):
        sel = np.zeros((d, len(lanes)), np.float32)
        sel[np.concatenate(index), lanes] = 1.0
        return sel

    return one_hot(own), one_hot(partner), (
        np.concatenate(scale) / math.sqrt(d)).astype(np.float32)


def phi_selected(x, tile: int, mm_dtype=None):
    """`phi` of FEW rows (a decode step's: a row a slot a head): the same
    lanes, each factor selected by a matmul against a 0/1 matrix (exact: one
    term a lane) instead of cut, multiplied and concatenated piece by
    ragged piece — 0.5 ms a step of sixteen slots less on the chip, where
    over a chunk's thousands of rows the two written-out factors cost more
    than they save (`phi`)."""
    own, partner, scale = _phi_selections(x.shape[-1], tile)
    return (_mm("...d,dx->...x", x, own, mm_dtype)
            * _mm("...d,dx->...x", x, partner, mm_dtype) * scale)


def _mm(eq, a, b, dtype):
    """einsum of float32 operands: at "highest" where `dtype` is float32,
    else the operands cast to `dtype`; float32 out."""
    if dtype is None or jnp.dtype(dtype) == jnp.float32:
        return jnp.einsum(eq, a, b, precision=_HI)
    return jnp.einsum(eq, a.astype(dtype), b.astype(dtype),
                      preferred_element_type=jnp.float32)


def log_gate(a, h):
    """h (..., C) -> log g (..., KV), float32, <= 0."""
    return jax.nn.log_sigmoid(jnp.einsum(
        "...c,ck->...k", h.astype(jnp.float32), a["decay"]["w"],
        precision=_HI) + a["decay"]["bias"])


def quadratic(q, k, v, logg, *, eps):
    """The (T, T) weights a head, from an empty state: q (B, KV, G, T, d),
    k, v (B, KV, T, d), logg (B, KV, T), float32 -> y (B, KV, G, T, d)."""
    t, d = q.shape[-2:]
    cum = jnp.cumsum(logg, axis=-1)
    s = jnp.einsum("bkgtd,bksd->bkgts", q, k, precision=_HI) / math.sqrt(d)
    decay = jnp.exp(jnp.where(jnp.tril(jnp.ones((t, t), bool)),
                              cum[..., :, None] - cum[..., None, :], -jnp.inf))
    a = s * s * decay[:, :, None]
    return jnp.einsum("bkgts,bksd->bkgtd", a, v, precision=_HI) / (
        a.sum(-1, keepdims=True) + eps)


def _step_sides(q, k, logg, norm, *, tile, mm_dtype):
    """What a step needs beside the state: (g, phi(k), phi(q), the
    normaliser after the step)."""
    g = jnp.exp(logg)
    pk = phi_selected(k, tile, mm_dtype)
    return g, pk, phi_selected(q, tile, mm_dtype), g[..., None] * norm + pk


def _normalised(num, pq, norm, eps):
    den = jnp.einsum("bkgx,bkx->bkg", pq, norm, precision=_HI)
    return num / (den[..., None] + eps)


def step_rule(q, k, v, logg, state, norm, *, tile, eps, mm_dtype=None):
    """One position: q (B, KV, G, d), k, v (B, KV, d), logg (B, KV), `state`
    (B, KV, d, D), `norm` (B, KV, D), float32 -> (y (B, KV, G, d), state,
    norm). The plain form (the chip's is `step_rule_kernel`)."""
    g, pk, pq, norm = _step_sides(q, k, logg, norm, tile=tile,
                                  mm_dtype=mm_dtype)
    state = g[..., None, None] * state + v[..., :, None] * pk[..., None, :]
    num = _mm("bkgx,bkvx->bkgv", pq, state, mm_dtype)
    return _normalised(num, pq, norm, eps), state, norm


def step_rule_kernel(q, k, v, logg, pool, norms, layer, *, tile, eps,
                     mm_dtype=None, interpret=False):
    """`step_rule` on the WHOLE pool's leaves `pool` (L, B, KV, d, D) and
    `norms` (L, B, KV, D) at layer `layer`: the state's one pass — decay,
    add v phi(k)^T, answer the group's queries, write back in place — in
    ops/pallas/retention_step.py; the normaliser (a 128th of the bytes)
    beside it in plain form -> (y, pool, norms)."""
    from dnn_tpu.ops.pallas.retention_step import retention_step

    g, pk, pq, norm = _step_sides(q, k, logg, norms[layer], tile=tile,
                                  mm_dtype=mm_dtype)
    pool, num = retention_step(pool, layer, g, v, pk, pq, mm_dtype=mm_dtype,
                               interpret=interpret)
    return _normalised(num, pq, norm, eps), pool, norms.at[layer].set(norm)


def _head_chunk(q, k, v, logg, s0, z0, fresh, *, tile, eps, mm_dtype):
    """One chunk of one KV head from an incoming state: q (G, c, d), k, v
    (c, d), logg (c,), s0 (d, D), z0 (D,) -> (y (G, c, d), s1, z1). `fresh`
    (a traced bool): the incoming state is EMPTY — a prompt's first chunk —
    and its part of the answers, the one that needs the queries expanded
    (five sixths of the rule's expansions), is not computed."""
    c, d = k.shape
    cum = jnp.cumsum(logg)
    s = _mm("gtd,sd->gts", q, k, mm_dtype) / math.sqrt(d)
    decay = jnp.exp(jnp.where(jnp.tril(jnp.ones((c, c), bool)),
                              cum[:, None] - cum[None, :], -jnp.inf))
    a = s * s * decay

    def from_state():
        pq = phi(q, tile)
        into = jnp.exp(cum)  # what the incoming state has decayed to
        return (into[:, None] * _mm("gtx,vx->gtv", pq, s0, mm_dtype),
                into * _mm("gtx,x->gt", pq, z0, mm_dtype))

    num, den = lax.cond(
        fresh, lambda: (jnp.zeros(q.shape, jnp.float32),
                        jnp.zeros(q.shape[:2], jnp.float32)), from_state)
    num = num + _mm("gts,sv->gtv", a, v, mm_dtype)
    den = den + a.sum(-1)
    out = jnp.exp(cum[-1] - cum)  # each position's share of what goes out
    pk = phi(k, tile)
    s1 = jnp.exp(cum[-1]) * s0 + _mm("sv,sx->vx", v * out[:, None], pk,
                                     mm_dtype)
    z1 = jnp.exp(cum[-1]) * z0 + _mm("s,sx->x", out, pk, mm_dtype)
    return num / (den[..., None] + eps), s1, z1


def chunk_rule(q, k, v, logg, state, norm, *, chunk, tile, eps,
               mm_dtype=None, fresh=False):
    """Retention over T positions in chunks of `chunk` (module docstring):
    q (B, KV, G, T, d), k, v (B, KV, T, d), logg (B, KV, T) <= 0, `state`
    (B, KV, d, D) and `norm` (B, KV, D) the incoming ones, float32 -> (y (B,
    KV, G, T, d), state, norm). T is a multiple of `chunk`. A scan over the
    chunks, inside it one over the KV heads: a head's expanded queries are
    G c D numbers. `fresh` (a bool, traced or not): the incoming state is
    empty, so the first chunk expands no query (`_head_chunk`)."""
    b, kv, g, t, d = q.shape
    n = t // chunk

    def split(x, at):  # T at axis `at` once B and KV are one -> chunks first
        x = x.reshape(b * kv, *x.shape[2:])
        x = x.reshape(*x.shape[:at], n, chunk, *x.shape[at + 1:])
        return jnp.moveaxis(x, at, 0)

    def one_chunk(carry, xs):
        s0, z0, fresh = carry
        y, s1, z1 = lax.map(lambda head: _head_chunk(
            *head, fresh, tile=tile, eps=eps, mm_dtype=mm_dtype),
            (*xs, s0, z0))
        return (s1, z1, jnp.asarray(False)), y

    carry = (state.reshape(b * kv, *state.shape[2:]),
             norm.reshape(b * kv, norm.shape[-1]), jnp.asarray(fresh))
    (s1, z1, _), y = lax.scan(one_chunk, carry, (
        split(q, 2), split(k, 1), split(v, 1), split(logg, 1)))
    # y (n, B * KV, G, c, d) -> (B, KV, G, T, d)
    y = jnp.moveaxis(y, 0, 2).reshape(b, kv, g, t, d)
    return y, s1.reshape(state.shape), z1.reshape(norm.shape)


def _grouped(q, cfg):
    """q (B, H, T, d) -> (B, KV, G, T, d) float32."""
    b, h, t, d = q.shape
    return q.astype(jnp.float32).reshape(b, cfg.n_kv_head,
                                         h // cfg.n_kv_head, t, d)


def _out(bp, y, x_dtype, *, compute_dtype):
    """y (B, KV, G, T, d) float32 -> the mixer's output (B, T, C)."""
    b, kv, g, t, d = y.shape
    return linear(bp["attn"]["o"],
                  merge_heads(y.reshape(b, kv * g, t, d).astype(x_dtype)),
                  compute_dtype=compute_dtype)


def mixer_chunk(bp, h, leaves, start_pos, n_real, *, cfg, compute_dtype,
                kernel=False):
    """The rule's chunk form (`state_kind.Rule`): the retention mixer over a
    chunk h (B, T, C) at positions [start_pos, start_pos + T) whose first
    `n_real` are real; `leaves` — `state` (B, KV, d, D) and `norm` (B, KV,
    D) — come in and are left as they are after the last REAL position ->
    the mixer's output (B, T, C). No kernel: `kernel` is ignored."""
    m = cfg.retention
    t = h.shape[1]
    with jax.named_scope("ret.project"):
        q, k, v = llama._qkv_rope(bp, h, start_pos + jnp.arange(t), cfg=cfg,
                                  compute_dtype=compute_dtype)
        real = jnp.arange(t) < n_real
        logg = jnp.where(real[None, :, None], log_gate(bp["attn"], h), 0.0)
        k = jnp.where(real[None, None, :, None], k.astype(jnp.float32), 0.0)
    with jax.named_scope("ret.chunk"):
        y, state, norm = chunk_rule(
            _grouped(q, cfg), k, v.astype(jnp.float32),
            jnp.moveaxis(logg, 1, 2), leaves["state"], leaves["norm"],
            chunk=math.gcd(m.chunk, t), tile=m.tile, eps=m.eps,
            mm_dtype=compute_dtype, fresh=start_pos == 0)
    leaves.update(state=state, norm=norm)
    with jax.named_scope("ret.out"):
        return _out(bp, y, h.dtype, compute_dtype=compute_dtype)


def mixer_step(bp, h, leaves, pos, *, cfg, compute_dtype, kernel=False,
               layer=None):
    """The rule's step form: one token a slot, h (B, 1, C) at per-slot
    positions `pos` (B,) -> the mixer's output (B, 1, C). `leaves` are one
    layer's for the plain form; under `kernel` (True / "interpret") the
    WHOLE pool's, updated in place at `layer` (`step_rule_kernel`). They are
    read after the projection and written before the output's."""
    m = cfg.retention
    with jax.named_scope("ret.project"):
        q, k, v = llama.qkv_rows(bp, h, pos, cfg=cfg,
                                 compute_dtype=compute_dtype)
        q = _grouped(q, cfg)[:, :, :, 0]
        k, v = (a[:, :, 0].astype(jnp.float32) for a in (k, v))
        logg = log_gate(bp["attn"], h)[:, 0]
    state, norm = leaves["state"], leaves["norm"]
    rule = functools.partial(
        step_rule_kernel, layer=layer,
        interpret=kernel == "interpret") if kernel else step_rule
    with jax.named_scope("ret.step"):
        y, state, norm = rule(q, k, v, logg, state, norm, tile=m.tile,
                              eps=m.eps, mm_dtype=compute_dtype)
    leaves.update(state=state, norm=norm)
    with jax.named_scope("ret.out"):
        return _out(bp, y[:, :, :, None], h.dtype,
                    compute_dtype=compute_dtype)


def _init_block(blk, key, cfg, dtype):
    del dtype  # the gate is float32
    blk["attn"]["decay"] = init_gate(jax.random.fold_in(key, 29), cfg)


RULE = state_kind.Rule(
    field="retention", kind="retention", params=None, slot_leaves=slot_leaves,
    init=_init_block, chunk=mixer_chunk, step=mixer_step, kernel="step",
    whole=("state", "norm"))
