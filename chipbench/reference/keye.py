"""Keye-VL-2.0-30B-A3B's language model, plainly: one chip's share of the
forward pass in straightforward `jax.numpy`, float32, no kernels, no cache,
no paging, no batching, no sorting or grouping of rows, no scan over layers.

The block (Kwai-Keye/Keye-VL-2.0-30B-A3B `config.json`; a Qwen3-MoE block
whose attention reads what a DeepSeek-Sparse-Attention-style indexer
selects; x is (T, C), t a query position, s <= t a key position):

  1. h = RMSNorm(x); q = h Wq -> (T, 32, 128), k, v = h Wk, h Wv -> (T, 4,
     128), no bias; RMSNorm over each head's 128 (one gain vector for q,
     one for k), then RoPE (rotate-half, theta 1e7) on all 128.
  2. Indexer: qI = h WqI -> (T, 16, 64), kI = h WkI -> (T, 64) (ONE key
     head for all 16), w = h Ww -> (T, 16); RoPE, same theta, on qI and kI;
     I[t, s] = (16 * 64)^-1/2 * sum_j w[t, j] * relu(qI[t, j] . kI[s]).
  3. S_t = the `topk` positions s <= t of largest I[t, s], ties to the
     smaller s; all of them while t < topk. Here: a STABLE ARGSORT of the
     full (T, T) scores in descending order, the first `topk` of each row.
  4. o[t] = softmax over s in S_t of (q[t] . k[s] / sqrt(128)) v[s], KV
     head g // 8 for query head g; y = x + o Wo.
  5. h2 = RMSNorm(y); p = softmax(h2 Wr) over ALL the layer's experts; the
     8 largest, renormalised to sum 1; out = y + sum over those of
     p_e * (silu(h2 Wg_e) * (h2 Wu_e)) Wd_e.
  Final RMSNorm, untied head.

The held range: the deployment this reference describes divides each
layer's experts among chips; `held = (first, count)` says which this chip
holds, and `params` carries those experts' matrices only (the router is
whole). Every held expert is computed for EVERY token and weighted by that
token's renormalised probability for it — zero unless it is among the
token's eight. What the experts held elsewhere would add is left out, and
the partial result goes on to the next layer, exactly as the program under
test does. With held = (0, all) this is the whole model.

Departures from the published description, each with its reason:
  * the vision tower and its projector are not here: ids in, logits out;
  * `mrope_section` [16, 24, 24] splits the 64 rotary pairs among three
    position streams; for text the three are equal and the result is the
    ordinary RoPE below;
  * the config has no key for the per-head q/k RMSNorm (it is the Qwen3
    block's, whose every other number this model has), nor for the
    indexer's details: RoPE over the whole 64, the (heads x width)^-1/2
    scale, no norm on kI, ties to the smaller position — the assumptions
    `chipbench/configs/keye-vl-2.0-30b-a3b-ep8-1chip.json` lists;
  * `q_chunk_size` / `kv_chunk_size` tile the published indexer's
    computation and change no result: the scores here are one (T, T);
  * attention runs one query head at a time (a loop, written as a scan so
    the block compiles one head's body): (32, T, T) scores at T = 12 544
    would be 20 GB. The sums are the same;
  * everything is float32, so no cast of the routing weights.

It reads the parameter tree of `dnn_tpu.models.llama_moe.init` ({"wte",
"h_<i>": {"ln_1", "attn": {"q", "k", "v", "o", "q_norm", "k_norm",
"indexer": {"wq", "wk", "ww"}}, "ln_2", "moe": {"router", "wg", "wu",
"wd"}}, "ln_f", "lm_head"}; kernels stored (in, out), expert stacks
expert-major) because the weights under test are made by the program from
`--seed`; nothing else of the program is used. Callers wrap it in
`jax.default_matmul_precision("highest")`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

__all__ = ["hidden", "forward", "logits", "selected"]


def _rms_norm(scale, x, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x (..., T, d) with T second to last: rotate the pairs (i, i + d/2)
    by position * theta^(-2i/d)."""
    t, d = x.shape[-2:]
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.concatenate([jnp.cos(angles)] * 2, axis=-1)
    sin = jnp.concatenate([jnp.sin(angles)] * 2, axis=-1)
    rotated = jnp.concatenate([-x[..., d // 2:], x[..., : d // 2]], axis=-1)
    return x * cos + rotated * sin


def _selection(ip, h, *, index_heads, theta, topk):
    """(T, T) bool: row t true at the positions S_t."""
    t = h.shape[0]
    qi = (h @ ip["wq"]["kernel"]).reshape(t, index_heads, -1)  # (T, Hi, Di)
    ki = _rope(h @ ip["wk"]["kernel"], theta)                  # (T, Di)
    w = h @ ip["ww"]["kernel"]                                 # (T, Hi)
    qi = _rope(qi.transpose(1, 0, 2), theta)                   # (Hi, T, Di)
    width = qi.shape[0] * qi.shape[2]
    scores = jnp.einsum("jts,tj->ts",
                        jax.nn.relu(qi @ ki.T), w) / jnp.sqrt(
                            jnp.float32(width))
    causal = jnp.tril(jnp.ones((t, t), bool))
    # descending, stable: of equal scores the smaller position first; what
    # lies in the future sorts last
    order = jnp.argsort(jnp.where(causal, -scores, jnp.inf), axis=-1,
                        stable=True)
    rank = jnp.argsort(order, axis=-1)  # position -> its place in the order
    return causal & (rank < topk)


def _experts(p, h, *, top_k, first):
    """(T, C) -> (T, C): every HELD expert on every token, weighted by
    the token's renormalised probability for it (zero unless among its
    top_k of ALL the experts)."""
    n_expert = p["router"]["kernel"].shape[-1]
    probs = jax.nn.softmax(h @ p["router"]["kernel"], axis=-1)  # (T, E)
    top, idx = jax.lax.top_k(probs, top_k)
    top = top / top.sum(-1, keepdims=True)
    weights = (jax.nn.one_hot(idx, n_expert) * top[..., None]).sum(1)
    held = weights[:, first:first + p["wg"].shape[0]]  # (T, count)

    def one_expert(out, expert):
        wg, wu, wd, w = expert
        return out + w[:, None] * ((jax.nn.silu(h @ wg) * (h @ wu)) @ wd), None

    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(h),
                          (p["wg"], p["wu"], p["wd"], held.T))
    return out


_STATIC = ("n_head", "n_kv_head", "index_heads", "eps", "theta", "topk",
           "top_k", "first")


@functools.partial(jax.jit, static_argnames=_STATIC)
def _block(p, x, *, n_head, n_kv_head, index_heads, eps, theta, topk, top_k,
           first):
    t = x.shape[0]
    a = p["attn"]
    h = _rms_norm(p["ln_1"]["scale"], x, eps)
    q = (h @ a["q"]["kernel"]).reshape(t, n_head, -1).transpose(1, 0, 2)
    k = (h @ a["k"]["kernel"]).reshape(t, n_kv_head, -1).transpose(1, 0, 2)
    v = (h @ a["v"]["kernel"]).reshape(t, n_kv_head, -1).transpose(1, 0, 2)
    d = q.shape[-1]
    q = _rope(_rms_norm(a["q_norm"]["scale"], q, eps), theta)
    k = _rope(_rms_norm(a["k_norm"]["scale"], k, eps), theta)
    sel = _selection(a["indexer"], h, index_heads=index_heads, theta=theta,
                     topk=topk)
    group = n_head // n_kv_head

    def one_head(_, head):
        qh, g = head
        s = qh @ k[g // group].T / jnp.sqrt(jnp.float32(d))
        s = jnp.where(sel, s, -jnp.inf)
        return None, jax.nn.softmax(s, axis=-1) @ v[g // group]

    _, y = jax.lax.scan(one_head, None, (q, jnp.arange(n_head)))
    x = x + y.transpose(1, 0, 2).reshape(t, n_head * d) @ a["o"]["kernel"]
    h = _rms_norm(p["ln_2"]["scale"], x, eps)
    return x + _experts(p["moe"], h, top_k=top_k, first=first), sel


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(ln_f, kernel, x, *, eps):
    return _rms_norm(ln_f["scale"], x, eps) @ kernel


def _run(params, ids, *, n_layer, **kw):
    x = params["wte"]["embedding"][ids]
    sels = []
    for i in range(n_layer):
        x, sel = _block(params[f"h_{i}"], x, **kw)
        sels.append(sel)
    return x, sels


def _kw(cfg, held):
    """The program's model config -> this module's arguments: depth, heads,
    epsilon, rotary base, the indexer's heads and topk, experts per token;
    and the first expert held: `held` = (first, count), the config's own
    range when None. `params` carries `count` experts a layer."""
    first = int(cfg.experts_first) if held is None else int(held[0])
    return dict(n_layer=cfg.n_layer, n_head=cfg.n_head,
                n_kv_head=cfg.n_kv_head, index_heads=cfg.index_n_head,
                eps=float(cfg.rms_eps), theta=float(cfg.rope_theta),
                topk=int(cfg.index_topk), top_k=cfg.router_top_k,
                first=first)


def hidden(cfg, params, ids, held=None):
    """(T,) ids of ONE sequence -> (T, C): the last block's output, before
    the final norm and the head."""
    return _run(params, jnp.asarray(ids), **_kw(cfg, held))[0]


def selected(cfg, params, ids, held=None):
    """(T,) ids -> [(T, T) bool a layer]: the sets S_t."""
    return _run(params, jnp.asarray(ids), **_kw(cfg, held))[1]


def forward(cfg, params, ids, rows=None, held=None):
    """(T,) ids -> (T, vocab) float32 logits, or those of `rows` only (the
    head on 16 k positions x 151 936 words is 10 GB)."""
    x = hidden(cfg, params, ids, held)
    if rows is not None:
        x = x[rows]
    return _head(params["ln_f"], params["lm_head"]["kernel"], x,
                 eps=float(cfg.rms_eps))


def logits(cfg, params, ids):
    """What the check calls in every reference module: (B, T) ids -> (B,
    T, vocab), one sequence at a time. The held range is the config's
    (`experts_first`, and as many experts as `params` carries)."""
    return jnp.stack([forward(cfg, params, row) for row in jnp.asarray(ids)])
