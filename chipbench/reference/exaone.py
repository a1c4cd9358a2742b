"""K-EXAONE-236B-A23B, plainly: one chip's share of the forward pass in
straightforward `jax.numpy`, float32, no kernels, no cache, no paging, no
batching, no group folded into rows, no scan over layers.

The layers (LGAI-EXAONE/K-EXAONE-236B-A23B `config.json`, `model_type`
exaone_moe; x is (T, C), t a query position, u <= t a key position, C =
6144; 64 query heads and 8 KV heads of 128, query head j reads KV head
j // 8). `layer_types` says which of two KINDS a layer is; both compute

  1. h = RMSNorm(x) (eps 1e-5); q = h W_q (64 x 128), k = h W_k, v = h W_v
     (8 x 128), no biases; q and k RMSNorm'd a head (gains of 128).
  2. s[t, u] = q[t] . k[u] / sqrt(128) over the ALLOWED u <= t, softmax,
     o = P v — the full (T, T) scores a head, what is not allowed masked.
  3. y = x + concat(o) W_o.

  * "sliding_attention" layers (0, 1, 2, 4, ...): q and k are rotated
    before the scores (pairs (i, i + 64), theta 1e6). Allowed:
    t - 128 < u <= t, a band mask (HF's sliding-window mask: the window
    counts the query's own position).
  * "full_attention" layers (3, 7, ...): NO rotation; every u <= t.
  4. Layer 0: out = y + SwiGLU_18432(RMSNorm(y)). Layers 1..: h2 =
     RMSNorm(y); p = sigmoid(h2 W_r) over ALL 128 experts (float32); the 8
     largest of p + b (one group); w = 2.5 p[picked] / (sum + 1e-20);
     out = y + sum of w_e E_e(h2) + S(h2), E and S SwiGLU of 2048, S (the
     shared expert) ungated.
  Final RMSNorm, untied head over the vocabulary rows this chip holds.

The held range (`held` = (first, count)) and what is left out are as
`reference/joyai.py` says: every held expert on every token, weighted by
that token's weight for it, zero unless among its eight of ALL 128; what
the experts held elsewhere would add is left out and the partial result
goes on. The vocabulary rows held elsewhere are simply absent.

Departures from the published description, each with its reason:
  * the multi-token-prediction layer is not here: it enters no next-token
    logit, and the program does not serve it;
  * what `config.json` does not say is taken as the configuration's file
    says under `assumed`: per-head q/k RMSNorm (EXAONE 4.0's), no rotation
    in full layers (EXAONE 4.0's hybrid layout), the selection bias (the
    DeepSeek-V3 router the routing keys belong to), pre-norm residuals.
    The norm placement is ONE argument (`post_norm`: the branch OUTPUTS
    normed, EXAONE 4.0's dense models) so that a control reads the other;
  * attention runs one head at a time (a scan, so one body compiles): 64
    x (T, T) scores at T = 6144 would be 9.7 GB. The sums are the same;
  * everything is float32, so no cast of the routing weights.

It reads the parameter tree of `dnn_tpu.models.llama_moe.init` because the
weights under test are made by the program from `--seed`; nothing else of
the program is used. `embed`, `layer` and `head` are its three steps on
their own: the check draws one layer's weights at a time
(`chipbench/serve_dots.py`). Callers wrap it in
`jax.default_matmul_precision("highest")`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

__all__ = ["embed", "layer", "head", "layer_args", "hidden", "forward",
           "logits"]


def _rms_norm(scale, x, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _rope_halves(x, theta):
    """x (..., T, d): rotate the pairs (i, i + d/2) by position *
    theta^(-2i/d)."""
    t, d = x.shape[-2:]
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    a = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(a), jnp.sin(a)
    lo, hi = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([lo * cos - hi * sin, hi * cos + lo * sin], -1)


def _swiglu(p, h):
    return (jax.nn.silu(h @ p["gate"]["kernel"]) * (h @ p["up"]["kernel"])
            ) @ p["down"]["kernel"]


def _attention(a, h, *, n_head, n_kv_head, eps, theta, window, rope):
    t = h.shape[0]
    group = n_head // n_kv_head

    def heads(w, n):  # (T, n * D) -> (n, T, D)
        return (h @ w["kernel"]).reshape(t, n, -1).transpose(1, 0, 2)

    q, k, v = heads(a["q"], n_head), heads(a["k"], n_kv_head), \
        heads(a["v"], n_kv_head)
    q = _rms_norm(a["q_norm"]["scale"], q, eps)
    k = _rms_norm(a["k_norm"]["scale"], k, eps)
    if rope:
        q, k = _rope_halves(q, theta), _rope_halves(k, theta)
    cols = jnp.arange(t)
    allowed = cols[None, :] <= cols[:, None]
    if window is not None:
        allowed = allowed & (cols[None, :] > cols[:, None] - window)

    def one_head(_, head):
        qh, j = head
        s = qh @ k[j // group].T / jnp.sqrt(jnp.float32(qh.shape[-1]))
        s = jnp.where(allowed, s, -jnp.inf)  # the full (T, T) scores
        return None, jax.nn.softmax(s, axis=-1) @ v[j // group]

    _, y = jax.lax.scan(one_head, None, (q, jnp.arange(n_head)))
    return y.transpose(1, 0, 2).reshape(t, -1) @ a["o"]["kernel"]


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


_STATIC = ("n_head", "n_kv_head", "eps", "theta", "window", "rope", "top_k",
           "first", "scale", "shared", "bias", "post_norm")


@functools.partial(jax.jit, static_argnames=_STATIC)
def layer(p, x, *, n_head, n_kv_head, eps, theta, window, rope, top_k, first,
          scale, shared=True, bias=True, post_norm=False):
    """One block, (T, C) -> (T, C). `shared` False leaves the shared
    expert out (the shares test counts it once); another `window`, `rope`,
    `scale`, `bias` or `post_norm` (each branch's OUTPUT normed, its input
    the residual as it is) are the controls' one thing wrong."""
    def normed(name, y):
        return _rms_norm(p[name]["scale"], y, eps)

    def branch(name, f):
        return normed(name, f(x)) if post_norm else f(normed(name, x))

    x = x + branch("ln_1", lambda h: _attention(
        p["attn"], h, n_head=n_head, n_kv_head=n_kv_head, eps=eps,
        theta=theta, window=window, rope=rope))
    if "mlp" in p:  # the leading dense layer
        return x + branch("ln_2", lambda h: _swiglu(p["mlp"], h))

    def moe(h):
        routed, common = _experts(p["moe"], h, top_k=top_k, first=first,
                                  scale=scale, bias=bias)
        return routed + (common if shared else 0.0)

    return x + branch("ln_2", moe)


@functools.partial(jax.jit, static_argnames=("eps",))
def head(ln_f, kernel, x, *, eps):
    return _rms_norm(ln_f["scale"], x, eps) @ kernel


def embed(wte, ids):
    return wte["embedding"][jnp.asarray(ids)]


def layer_args(cfg, i, held=None, **wrong):
    """The program's model config -> `layer`'s arguments for layer i;
    `held` = (first, count), the config's own range when None; `wrong`
    overrides one of them (the controls): `window` only where the layer
    has one, `rope` only where it has none."""
    kind = cfg.kv_window if cfg.layer_types[i] == "window" else cfg.kv_full
    kw = dict(
        n_head=cfg.n_head, n_kv_head=cfg.n_kv_head, eps=float(cfg.rms_eps),
        theta=float(cfg.rope_theta), window=kind.window,
        rope=bool(kind.rope), top_k=cfg.router_top_k,
        first=int(cfg.experts_first) if held is None else int(held[0]),
        scale=float(cfg.router.scale))
    for k, v in wrong.items():
        if (k == "window" and kw[k] is None) or (k == "rope" and kw[k]):
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
