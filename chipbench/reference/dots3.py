"""dots3-note-prev, plainly: one chip's share of the forward pass in
straightforward `jax.numpy`, float32, no kernels, no cache, no paging, no
batching, no absorption of one matrix into another, no scan over layers.

The layers (dots-studio/dots3-note-prev `config.json`, `model_type`
dots3_note; x is (T, C), t a query position, u <= t a key position, C =
5120). `layer_types` says which of two KINDS a layer is; both compute

  1. h = RMSNorm(x) (eps 1e-5). With `apply_mla_qkv_lora_rescale`: a_q =
     sqrt(C / r_q), a_kv = sqrt(C / r).
     c_q = a_q RMSNorm(h W_qa); [q_nope | q_rope] = c_q W_qb a head;
     [c_raw | k_raw] = h W_kva; c = a_kv RMSNorm(c_raw); k_rope =
     RoPE(k_raw), one vector for all the heads; [k_nope | v] = c W_kvb a
     head — EVERY position's k_nope and v are materialised here.
  2. s[t, u] = (q_nope[t] . k_nope[u] + RoPE(q_rope)[t] . k_rope[u]) /
     sqrt(dn + dr) over the ALLOWED u <= t, softmax, o = P v.
  3. g = sigmoid(h W_g), one number a head; y = x + concat(g_head o_head)
     W_o.

  * "full" layers (0, 1, 5, 9, ...): 128 heads, r_q 1024, r 512, dn | dr
    128 | 64, dv 128, theta 8e7. Allowed: the `index_topk` 2048 positions
    u <= t of largest I[t, u] (all while fewer exist; ties to the smaller
    u: a STABLE descending argsort of the full (T, T) index scores, the
    future sorted last). qI = c_q W_iq (64 heads of 128), kI =
    LayerNorm(h W_ik) (eps 1e-6), both rotated in their first 64 lanes
    (pairs (i, i + 32), theta 8e7); w = h W_iw;
    I[t, u] = (64 x 128)^-1/2 sum_j w[t, j] relu(qI[t, j] . kI[u]).
  * "window" layers: 64 heads, r_q 1024, r 1024, dn | dr 192 | 64, dv
    128, theta 50000. Allowed: t - 513 < u <= t, a band mask
    (`sliding_window_size` 513 counts the query's own position).
  4. Layer 0: out = y + SwiGLU_13824(RMSNorm(y)). Layers 1..: h2 =
     RMSNorm(y); p = sigmoid(h2 W_r) over ALL 256 experts; the 8 largest
     of p + b (`noaux_tc`, one group); w = p[picked] / (sum + 1e-20) x 1;
     out = y + sum of w_e E_e(h2) + S(h2), E and S SwiGLU of 1536, S (the
     shared expert) ungated.
  Final RMSNorm, untied head over the vocabulary rows this chip holds.

RoPE on the 64 rope lanes of q and k rotates the pairs (2i, 2i + 1) by
position x theta^(-2i/64) IN PLACE (the DeepSeek-V3 lineage's interleaved
checkpoints). The program de-interleaves q_rope and k_raw first and
rotates in the half-split layout, so its cache holds the PERMUTED rope
key: the permutation is the same on both sides of every dot product and
every score is equal. The indexer's RoPE is half-split on both sides
here and there (DeepSeek-V3.2-Exp's inference code).

The held range (`held` = (first, count)) and what is left out are as
`reference/joyai.py` says: every held expert on every token, weighted by
that token's weight for it, zero unless among its eight of ALL 256; what
the experts held elsewhere would add is left out and the partial result
goes on. The vocabulary rows held elsewhere are simply absent (ids are
drawn from the slice, logits are over the slice).

Departures from the published description, each with its reason:
  * the vision and audio towers and any multi-token-prediction module are
    not here: text ids only, as the program serves;
  * attention runs one head at a time and a block of query rows at a time
    (scans, so one body compiles): (128, T, T) scores at T = 16 640 would
    be 142 GB. The index scores are taken a block of query rows at a time
    for the same reason, each row against the whole sequence. The sums are
    the same;
  * everything is float32, so no cast of the routing weights.

It reads the parameter tree of `dnn_tpu.models.llama_moe.init` (as
`reference/joyai.py`; a full layer's "attn" also holds "gate" and
"indexer": {"wq", "wk", "k_norm", "ww"}, a window layer's "gate")
because the weights under test are made by the program from `--seed`;
nothing else of the program is used. `embed`, `layer` and `head` are its
three steps on their own: the check draws one layer's weights at a time
(`chipbench/serve_dots.py`). Callers wrap it in
`jax.default_matmul_precision("highest")`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

__all__ = ["embed", "layer", "head", "layer_args", "hidden", "forward",
           "logits", "selected"]

ROWS = 1024  # query rows a block of attention
SEL_ROWS = 128  # and of index scores: (Hi, rows, T) float32 a block


def _rms_norm(scale, x, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _layer_norm(p, x, eps):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def _angles(t, d, theta):
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    return jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]


def _rope_pairs(x, theta):
    """x (..., T, d): rotate the pairs (2i, 2i + 1) by position *
    theta^(-2i/d)."""
    t, d = x.shape[-2:]
    a = _angles(t, d, theta)
    cos, sin = jnp.cos(a), jnp.sin(a)
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     axis=-1).reshape(x.shape)


def _rope_halves(x, theta, lanes):
    """x (..., T, d): rotate the pairs (i, i + lanes/2) of its first
    `lanes` lanes; the rest pass through."""
    t = x.shape[-2]
    a = _angles(t, lanes, theta)
    cos, sin = jnp.cos(a), jnp.sin(a)
    lo, hi = x[..., :lanes // 2], x[..., lanes // 2:lanes]
    return jnp.concatenate([lo * cos - hi * sin, hi * cos + lo * sin,
                            x[..., lanes:]], axis=-1)


def _swiglu(p, h):
    return (jax.nn.silu(h @ p["gate"]["kernel"]) * (h @ p["up"]["kernel"])
            ) @ p["down"]["kernel"]


def _blocks(t, rows=ROWS):
    """(rows a block, blocks): `rows` where it divides T, else one block."""
    return (rows, t // rows) if t % rows == 0 and t > rows else (t, 1)


def _selection(ip, c_q, h, *, index_heads, rope_lanes, theta, topk):
    """(T, T) bool: row t true at the positions it may read."""
    t = h.shape[0]
    qi = (c_q @ ip["wq"]["kernel"]).reshape(t, index_heads, -1)
    qi = _rope_halves(qi.transpose(1, 0, 2), theta, rope_lanes)  # (Hi,T,Di)
    ki = _rope_halves(_layer_norm(ip["k_norm"], h @ ip["wk"]["kernel"],
                                  1e-6), theta, rope_lanes)      # (T, Di)
    w = h @ ip["ww"]["kernel"]                                   # (T, Hi)
    width = qi.shape[0] * qi.shape[2]
    rows, n = _blocks(t, SEL_ROWS)
    cols = jnp.arange(t)

    def block(_, b):
        q, wb, first = b  # (Hi, rows, Di), (rows, Hi), ()
        scores = jnp.einsum("jts,tj->ts", jax.nn.relu(q @ ki.T), wb
                            ) / jnp.sqrt(jnp.float32(width))
        causal = cols[None, :] <= (first + jnp.arange(rows))[:, None]
        # descending, stable: of equal scores the smaller position first;
        # what lies in the future sorts last
        order = jnp.argsort(jnp.where(causal, -scores, jnp.inf), axis=-1,
                            stable=True)
        rank = jnp.argsort(order, axis=-1)
        return None, causal & (rank < topk)

    _, sel = jax.lax.scan(block, None, (
        qi.reshape(index_heads, n, rows, -1).transpose(1, 0, 2, 3),
        w.reshape(n, rows, -1), jnp.arange(n) * rows))
    return sel.reshape(t, t)


def _attention(a, h, *, n_head, nope, rope, eps, theta, window, topk,
               index_heads, index_rope, gate, rescale):
    t, c = h.shape
    r_q = a["q_a"]["kernel"].shape[-1]
    c_q = _rms_norm(a["q_a_norm"]["scale"], h @ a["q_a"]["kernel"], eps)
    kv = h @ a["kv_a"]["kernel"]
    rank = kv.shape[-1] - rope
    lat = _rms_norm(a["kv_a_norm"]["scale"], kv[:, :rank], eps)
    if rescale:
        c_q = c_q * jnp.sqrt(jnp.float32(c / r_q))
        lat = lat * jnp.sqrt(jnp.float32(c / rank))
    q = (c_q @ a["q_b"]["kernel"]).reshape(t, n_head, nope + rope)
    q = q.transpose(1, 0, 2)
    q_nope, q_rope = q[..., :nope], _rope_pairs(q[..., nope:], theta)
    k_rope = _rope_pairs(kv[:, rank:], theta)  # (T, dr): one for all heads
    up = (lat @ a["kv_b"]["kernel"]).reshape(t, n_head, -1).transpose(1, 0, 2)
    k_nope, v = up[..., :nope], up[..., nope:]
    cols = jnp.arange(t)
    allowed = cols[None, :] <= cols[:, None]
    if window is not None:
        allowed = allowed & (cols[None, :] > cols[:, None] - window)
    if topk is not None:
        allowed = allowed & _selection(
            a["indexer"], c_q, h, index_heads=index_heads,
            rope_lanes=index_rope, theta=theta, topk=topk)
    rows, n = _blocks(t)
    allowed = allowed.reshape(n, rows, t)

    def one_head(_, head):
        qn, qr, kn, vh = head

        def block(_, b):
            qn_b, qr_b, ok = b
            s = (qn_b @ kn.T + qr_b @ k_rope.T) / jnp.sqrt(
                jnp.float32(nope + rope))
            s = jnp.where(ok, s, -jnp.inf)
            return None, jax.nn.softmax(s, axis=-1) @ vh

        _, y = jax.lax.scan(block, None, (
            qn.reshape(n, rows, -1), qr.reshape(n, rows, -1), allowed))
        return None, y.reshape(t, -1)

    _, y = jax.lax.scan(one_head, None, (q_nope, q_rope, k_nope, v))
    y = y.transpose(1, 0, 2)  # (T, H, dv)
    if gate:
        y = y * jax.nn.sigmoid(h @ a["gate"]["kernel"])[..., None]
    return y.reshape(t, -1) @ a["o"]["kernel"]


def _experts(p, h, *, top_k, first, scale, bias):
    """(T, C) -> the held experts' part, and the shared expert's."""
    n_expert = p["router"]["kernel"].shape[-1]
    scores = jax.nn.sigmoid(h @ p["router"]["kernel"])  # (T, E)
    pick = scores + p["router"]["select_bias"] if bias else scores
    _, idx = jax.lax.top_k(pick, top_k)
    top = jnp.take_along_axis(scores, idx, axis=-1)
    top = top / (top.sum(-1, keepdims=True) + 1e-20) * scale
    weights = (jax.nn.one_hot(idx, n_expert) * top[..., None]).sum(1)
    held = weights[:, first:first + p["wg"].shape[0]]  # (T, count)

    def one_expert(out, expert):
        wg, wu, wd, w = expert
        return out + w[:, None] * ((jax.nn.silu(h @ wg) * (h @ wu)) @ wd), None

    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(h),
                          (p["wg"], p["wu"], p["wd"], held.T))
    return out, _swiglu(p["shared"], h)


_STATIC = ("n_head", "nope", "rope", "eps", "theta", "window", "topk",
           "index_heads", "index_rope", "gate", "rescale", "top_k", "first",
           "scale", "shared", "bias")


@functools.partial(jax.jit, static_argnames=_STATIC)
def layer(p, x, *, n_head, nope, rope, eps, theta, window, topk, index_heads,
          index_rope, gate, rescale, top_k, first, scale, shared=True,
          bias=True):
    """One block, (T, C) -> (T, C). `shared` False leaves the shared
    expert out (the shares test counts it once); `gate`, `rescale`,
    `bias` False, another `window` or `topk` are the controls' one thing
    wrong."""
    h = _rms_norm(p["ln_1"]["scale"], x, eps)
    x = x + _attention(
        p["attn"], h, n_head=n_head, nope=nope, rope=rope, eps=eps,
        theta=theta, window=window, topk=topk, index_heads=index_heads,
        index_rope=index_rope, gate=gate, rescale=rescale)
    h = _rms_norm(p["ln_2"]["scale"], x, eps)
    if "mlp" in p:  # the leading dense layer
        return x + _swiglu(p["mlp"], h)
    routed, common = _experts(p["moe"], h, top_k=top_k, first=first,
                              scale=scale, bias=bias)
    return x + routed + (common if shared else 0.0)


@functools.partial(jax.jit, static_argnames=("eps",))
def head(ln_f, kernel, x, *, eps):
    return _rms_norm(ln_f["scale"], x, eps) @ kernel


def embed(wte, ids):
    return wte["embedding"][jnp.asarray(ids)]


def layer_args(cfg, i, held=None, **wrong):
    """The program's model config -> `layer`'s arguments for layer i;
    `held` = (first, count), the config's own range when None; `wrong`
    overrides one of them (the controls)."""
    m = cfg.mla if cfg.layer_types[i] == "full" else cfg.mla_window
    kw = dict(
        n_head=m.n_head or cfg.n_head, nope=m.qk_nope_head_dim,
        rope=m.qk_rope_head_dim, eps=float(cfg.rms_eps),
        theta=float(m.rope_theta or cfg.rope_theta), window=m.window,
        topk=m.index_topk, index_heads=m.index_n_head,
        index_rope=m.index_rope_dim, gate=bool(m.head_gate),
        rescale=bool(m.lora_rescale), top_k=cfg.router_top_k,
        first=int(cfg.experts_first) if held is None else int(held[0]),
        scale=float(cfg.router.scale))
    for k, v in wrong.items():
        if k in ("window", "topk") and kw[k] is None:
            continue  # the kind has no such thing to get wrong
        kw[k] = v
    return kw


def hidden(cfg, params, ids, held=None, **wrong):
    """(T,) ids of ONE sequence -> (T, C): the last block's output, before
    the final norm and the head."""
    x = embed(params["wte"], ids)
    for i in range(cfg.n_layer):
        x = layer(params[f"h_{i}"], x, **layer_args(cfg, i, held, **wrong))
    return x


def forward(cfg, params, ids, rows=None, held=None, **wrong):
    """(T,) ids -> (T, vocab) float32 logits, or those of `rows` only."""
    x = hidden(cfg, params, ids, held, **wrong)
    if rows is not None:
        x = x[rows]
    return head(params["ln_f"], params["lm_head"]["kernel"], x,
                eps=float(cfg.rms_eps))


def logits(cfg, params, ids):
    """What the check calls in every reference module: (B, T) ids -> (B,
    T, vocab), one sequence at a time."""
    return jnp.stack([forward(cfg, params, row) for row in jnp.asarray(ids)])


def selected(cfg, params, ids, i):
    """(T, T) bool: what layer i's queries may read under the indexer's
    selection alone, for the input that reaches layer i (the tests'
    view of the set)."""
    x = embed(params["wte"], ids)
    for j in range(i):
        x = layer(params[f"h_{j}"], x, **layer_args(cfg, j))
    kw = layer_args(cfg, i)
    p = params[f"h_{i}"]
    a = p["attn"]
    h = _rms_norm(p["ln_1"]["scale"], x, kw["eps"])
    c_q = _rms_norm(a["q_a_norm"]["scale"], h @ a["q_a"]["kernel"],
                    kw["eps"])
    if kw["rescale"]:
        c_q = c_q * jnp.sqrt(jnp.float32(
            h.shape[-1] / a["q_a"]["kernel"].shape[-1]))
    return _selection(a["indexer"], c_q, h, index_heads=kw["index_heads"],
                      rope_lanes=kw["index_rope"], theta=kw["theta"],
                      topk=kw["topk"])
