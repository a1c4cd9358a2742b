"""Mellum2-12B-A2.5B-Instruct, plainly: one pipeline stage's forward pass in
straightforward `jax.numpy`, float32, no kernels, no cache, no paging, no
batching, no group folded into rows, no scan over layers.

The layers (JetBrains/Mellum2-12B-A2.5B-Instruct `config.json`, `model_type`
mellum; x is (T, C), t a query position, u <= t a key position, C = 2304; 32
query heads and 4 KV heads of 128, query head j reads KV head j // 8; eps
1e-6, no bias anywhere). `layer_types` says which of two KINDS a layer is;
both compute

  1. h = RMSNorm(x; g1); q = h W_q (32 x 128), k = h W_k, v = h W_v (4 x
     128); q and k RMSNorm'd a head (gains of 128) BEFORE the rotation.
  2. q and k rotated at their position p in half-split pairs (i, i + 64), i
     = 0..63: x_i <- (x_i cos(p f_i) - x_{i+64} sin(p f_i)) a, x_{i+64} <-
     (x_{i+64} cos(p f_i) + x_i sin(p f_i)) a, with t_i = theta^(-2 i / 128),
     theta 500000, and the KIND's own f and a (below).
  3. s[t, u] = q[t] . k[u] / sqrt(128) over the ALLOWED u <= t, softmax,
     o = P v; y = x + concat(o) W_o.

  * "sliding_attention" layers (0, 1, 2, 4, ...): f_i = t_i, a = 1 (plain
    RoPE). Allowed: t - 1024 < u <= t (HF's sliding-window mask: the window
    counts the query's own position).
  * "full_attention" layers (3, 7, ...): YaRN (arXiv:2309.00071 as
    `transformers`' `_compute_yarn_parameters` states it): r_i = clip((i -
    low) / (high - low), 0, 1), low = floor(c(beta_fast)), high =
    ceil(c(beta_slow)), c(n) = 128 ln(8192 / (2 pi n)) / (2 ln theta) — low
    18, high 35 —; f_i = t_i (1 - r_i) + (t_i / 16) r_i; a = 1.2772588722239782
    on q AND k, so a score carries a^2. Every u <= t.
  4. h2 = RMSNorm(y; g2); p = softmax(h2 W_r) over the 64 experts (float32);
     the 8 largest; w = p[picked] / their sum; out = y + sum of w_e E_e(h2),
     E_e(h) = W_down,e (silu(h W_gate,e) * h W_up,e), width 896. No shared
     expert, no selection bias, no dense layer.
  Final RMSNorm, untied head over the 98 304 rows.

Departures from the published description, each with its reason:
  * per-head q/k RMSNorm is `assumed` (Qwen3-MoE's, whose keys the config
    carries; `config.json` has no key for it): ONE argument (`qk_norm`), so
    that a control reads the other;
  * the "MTP head" is not here: the config has no key for it, it enters no
    next-token logit, and the program does not serve it;
  * the scores are made a KV head at a time and in blocks of `ROWS` query
    rows (a scan, so one body compiles): 32 x (T, T) scores at T = 25 600
    would be 84 GB. The sums are the same;
  * every expert runs on every row, weighted by the row's weight for it
    (zero unless among its eight): the sum is the picked experts';
  * everything is float32, so no cast of the routing weights.

It reads the parameter tree of `dnn_tpu.models.llama_moe.init` because the
weights under test are made by the program from `--seed`; of the program's
config it reads NUMBERS only (`layer_args`): the tables are made here.
`embed`, `layer` and `head` are its three steps on their own: the check
draws one layer's weights at a time (`chipbench/serve_dots.py`). Callers
wrap it in `jax.default_matmul_precision("highest")`.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

__all__ = ["embed", "layer", "head", "layer_args", "hidden", "forward",
           "logits", "yarn_numbers"]

ROWS = 256  # query rows a block of the scores


def _rms_norm(scale, x, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def yarn_numbers(d, theta, yarn, ramp_off=(0, 0)):
    """-> (low, high, [f_i], a) of a head of width d: `yarn` = (factor,
    original positions, beta_fast, beta_slow, attention_factor) or None
    (plain RoPE: f_i = t_i, a = 1, no ramp). Plain Python, so that the
    published numbers can be read without a model. `ramp_off` moves low and
    high (the controls)."""
    t = [theta ** (-2.0 * i / d) for i in range(d // 2)]
    if yarn is None:
        return None, None, t, 1.0
    factor, original, beta_fast, beta_slow, a = yarn

    def c(n):  # the pair that turns n times over the original positions
        return d * math.log(original / (2 * math.pi * n)) / (
            2 * math.log(theta))

    low = max(math.floor(c(beta_fast)), 0) + ramp_off[0]
    high = min(math.ceil(c(beta_slow)), d - 1) + ramp_off[1]
    r = [min(max((i - low) / (high - low), 0.0), 1.0) for i in range(d // 2)]
    f = [ti * (1 - ri) + ti / factor * ri for ti, ri in zip(t, r)]
    return low, high, f, (0.1 * math.log(factor) + 1.0 if a is None else a)


def _rotated(x, f, a):
    """x (..., T, d) at positions 0..T-1: the pairs (i, i + d/2) turned by p
    f_i, times a."""
    t, d = x.shape[-2:]
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * jnp.asarray(
        f, jnp.float32)[None, :]
    cos, sin = jnp.cos(ang) * a, jnp.sin(ang) * a
    lo, hi = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([lo * cos - hi * sin, hi * cos + lo * sin], -1)


def _attention(p, h, *, n_head, n_kv_head, eps, theta, yarn, ramp_off, window,
               qk_norm):
    t = h.shape[0]
    group = n_head // n_kv_head

    def heads(w, n):  # (T, n * D) -> (n, T, D)
        return (h @ w["kernel"]).reshape(t, n, -1).transpose(1, 0, 2)

    q, k, v = heads(p["q"], n_head), heads(p["k"], n_kv_head), \
        heads(p["v"], n_kv_head)
    d = q.shape[-1]
    if qk_norm:
        q = _rms_norm(p["q_norm"]["scale"], q, eps)
        k = _rms_norm(p["k_norm"]["scale"], k, eps)
    _, _, f, a = yarn_numbers(d, theta, yarn, ramp_off)
    q, k = _rotated(q, f, a), _rotated(k, f, a)
    pad = -t % ROWS
    q = jnp.pad(q, ((0, 0), (0, pad), (0, 0)))
    cols = jnp.arange(t)

    def kv_head(_, j):
        qg = jax.lax.dynamic_slice_in_dim(q, j * group, group)  # (G, T', D)

        def rows_block(_, r0):
            rows = r0 + jnp.arange(ROWS)
            qr = jax.lax.dynamic_slice_in_dim(qg, r0, ROWS, axis=1)
            s = jnp.einsum("grd,ud->gru", qr, k[j]) / jnp.sqrt(jnp.float32(d))
            allowed = cols[None, :] <= rows[:, None]
            if window is not None:
                allowed = allowed & (cols[None, :] > rows[:, None] - window)
            s = jnp.where(allowed[None], s, -jnp.inf)
            return None, jnp.einsum("gru,ud->grd",
                                    jax.nn.softmax(s, axis=-1), v[j])

        _, y = jax.lax.scan(rows_block, None,
                            jnp.arange(0, t + pad, ROWS))  # (nR, G, R, D)
        return None, y.transpose(1, 0, 2, 3).reshape(group, t + pad, d)

    _, y = jax.lax.scan(kv_head, None, jnp.arange(n_kv_head))
    y = y.reshape(n_head, t + pad, d)[:, :t]
    return y.transpose(1, 0, 2).reshape(t, -1) @ p["o"]["kernel"]


def _experts(p, h, *, top_k, renorm):
    n_expert = p["router"]["kernel"].shape[-1]
    probs = jax.nn.softmax(h @ p["router"]["kernel"], axis=-1)  # (T, E)
    top, idx = jax.lax.top_k(probs, top_k)
    if renorm:
        top = top / top.sum(-1, keepdims=True)
    weights = (jax.nn.one_hot(idx, n_expert) * top[..., None]).sum(1)

    def one_expert(out, expert):
        wg, wu, wd, w = expert
        return out + w[:, None] * ((jax.nn.silu(h @ wg) * (h @ wu)) @ wd), None

    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(h),
                          (p["wg"], p["wu"], p["wd"], weights.T))
    return out


_STATIC = ("n_head", "n_kv_head", "eps", "theta", "yarn", "ramp_off",
           "window", "qk_norm", "top_k", "renorm")


@functools.partial(jax.jit, static_argnames=_STATIC)
def layer(p, x, *, n_head, n_kv_head, eps, theta, yarn, window, top_k,
          ramp_off=(0, 0), qk_norm=True, renorm=True):
    """One block, (T, C) -> (T, C). `yarn` None: plain RoPE; another
    `window`, `yarn`, `ramp_off`, `qk_norm` or `renorm` are the controls'
    one thing wrong (`layer_args`)."""
    x = x + _attention(
        p["attn"], _rms_norm(p["ln_1"]["scale"], x, eps), n_head=n_head,
        n_kv_head=n_kv_head, eps=eps, theta=theta, yarn=yarn,
        ramp_off=ramp_off, window=window, qk_norm=qk_norm)
    return x + _experts(p["moe"], _rms_norm(p["ln_2"]["scale"], x, eps),
                        top_k=top_k, renorm=renorm)


@functools.partial(jax.jit, static_argnames=("eps",))
def head(ln_f, kernel, x, *, eps):
    return _rms_norm(ln_f["scale"], x, eps) @ kernel


def embed(wte, ids):
    return wte["embedding"][jnp.asarray(ids)]


def _table(rot):
    """A kind's rotation (the program's config's NUMBERS) -> (theta, yarn)."""
    if rot.scaling is None:
        return float(rot.theta), None
    if rot.scaling != "yarn":
        raise ValueError(f"this model's tables are plain or yarn, not "
                         f"{rot.scaling!r}")
    return float(rot.theta), (
        float(rot.scale), int(rot.original_len), float(rot.beta_fast),
        float(rot.beta_slow), rot.attention_factor)


def layer_args(cfg, i, **wrong):
    """The program's model config -> `layer`'s arguments for layer i. `wrong`
    sets ONE thing wrong (the controls), each only in the layers that have
    the thing: `window` (sliding layers; None ignores it), `full_table`
    "sliding" / `sliding_table` "full" (that kind on the OTHER kind's
    table), `attention_factor` and `ramp_off` (layers under YaRN), `qk_norm`
    and `renorm` (every layer)."""
    sliding = cfg.layer_types[i] == "window"
    kind = cfg.kv_window if sliding else cfg.kv_full
    other = cfg.kv_full if sliding else cfg.kv_window
    swapped = wrong.get("sliding_table" if sliding else "full_table")
    theta, yarn = _table((other if swapped else kind).rotation)
    if yarn is not None and "attention_factor" in wrong:
        yarn = (*yarn[:4], wrong["attention_factor"])
    kw = dict(
        n_head=cfg.n_head, n_kv_head=cfg.n_kv_head, eps=float(cfg.rms_eps),
        theta=theta, yarn=yarn, window=kind.window, top_k=cfg.router_top_k,
        renorm=bool(wrong.get("renorm", cfg.router_norm_topk)),
        qk_norm=bool(wrong.get("qk_norm", cfg.qk_norm)))
    if sliding and "window" in wrong:
        kw["window"] = wrong["window"]
    if yarn is not None and "ramp_off" in wrong:
        kw["ramp_off"] = tuple(wrong["ramp_off"])
    return kw


def hidden(cfg, params, ids, **wrong):
    """(T,) ids of ONE sequence -> (T, C): the last block's output, before
    the final norm and the head."""
    x = embed(params["wte"], ids)
    for i in range(cfg.n_layer):
        x = layer(params[f"h_{i}"], x, **layer_args(cfg, i, **wrong))
    return x


def forward(cfg, params, ids, rows=None, **wrong):
    """(T,) ids -> (T, vocab) float32 logits, or those of `rows` only."""
    x = hidden(cfg, params, ids, **wrong)
    if rows is not None:
        x = x[rows]
    return head(params["ln_f"], params["lm_head"]["kernel"], x,
                eps=float(cfg.rms_eps))


def logits(cfg, params, ids):
    """What the check calls in every reference module: (B, T) ids -> (B,
    T, vocab), one sequence at a time."""
    return jnp.stack([forward(cfg, params, row) for row in jnp.asarray(ids)])
