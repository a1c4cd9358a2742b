"""Brumby-14B-Base, plainly: one pipeline stage's layers in straightforward
`jax.numpy`, float32, the QUADRATIC form of power retention — the (T, T)
weights a head — with no state, no expansion of keys or queries, no
recurrence, no chunked rule, no cache, no batching, no kernels and no scan
over layers: it shares no code and no algebra with the program's recurrent
forms (dnn_tpu/models/retention.py).

The layers (manifestai/Brumby-14B-Base `config.json`, `model_type` brumby;
x is (T, C), C = 5120; 40 query heads and 8 KV heads of 128, query head i
reads KV head i // 5; every layer is the same kind). What `config.json` has
no key for is the published definition of power retention (Buckman,
Gelada, Zhang, arXiv:2507.04239) as the configuration's file says under
`assumed`:

  1. h = RMSNorm(x) (eps 1e-6); q = h W_q (40 x 128), k = h W_k, v = h W_v
     (8 x 128), no biases; q and k RMSNorm'd a head (one gain of 128 each)
     and then rotated (pairs (i, i + 64), theta 1e6, all 128 dimensions).
  2. The gate, one scalar a KV head: log g_t = logsigmoid(h W_g + b_g), W_g
     5120 x 8; G_t = sum_{r <= t} log g_r.
  3. Degree 2. For query head i in KV group j and s <= t:
       a[t, s] = exp(G_t - G_s) (q_t . k_s / sqrt(128))^2
       y_t = sum_s a[t, s] v_s / (sum_s a[t, s] + 1e-6)
     — the full (T, T) weights a head, what lies above the diagonal zero; a
     scan over the 40 heads, so that one body compiles and one head's
     weights (37 MB at T = 3072) are live at a time.
  4. y = x + concat_i(y_i) W_o; out = y + SwiGLU_17408(RMSNorm(y)).
  Final RMSNorm, untied head over the whole vocabulary.

Arguments that set ONE thing wrong, for the controls (`layer`): `degree` 1
(the weights' power), `gate` False (g = 1), `normaliser` False (no
division), `head_gate` False (the gate's heads averaged: every KV head
decays alike), `rope` False, `qk_norm` False, `reset` n (the state reset at
every n-th position: a row sees no column of an earlier block of n —
what a program that dropped the state between chunks computes).

It reads the parameter tree of `dnn_tpu.models.llama.init` because the
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


def _retention(a, h, *, n_head, n_kv_head, eps, theta, ret_eps, degree, gate,
               normaliser, head_gate, rope, qk_norm, reset):
    t = h.shape[0]
    group = n_head // n_kv_head

    def heads(w, n):  # (T, n * D) -> (n, T, D)
        return (h @ w["kernel"]).reshape(t, n, -1).transpose(1, 0, 2)

    q, k, v = heads(a["q"], n_head), heads(a["k"], n_kv_head), \
        heads(a["v"], n_kv_head)
    if qk_norm:
        q = _rms_norm(a["q_norm"]["scale"], q, eps)
        k = _rms_norm(a["k_norm"]["scale"], k, eps)
    if rope:
        q, k = _rope_halves(q, theta), _rope_halves(k, theta)
    log_g = jax.nn.log_sigmoid(h @ a["decay"]["w"] + a["decay"]["bias"])
    if not head_gate:
        log_g = jnp.broadcast_to(log_g.mean(-1, keepdims=True), log_g.shape)
    if not gate:
        log_g = jnp.zeros_like(log_g)
    cum = jnp.cumsum(log_g, axis=0).T  # (KV, T)
    cols = jnp.arange(t)
    allowed = cols[None, :] <= cols[:, None]
    if reset:
        allowed = allowed & (cols[None, :] // reset == cols[:, None] // reset)

    def one_head(_, head):
        qh, i = head
        j = i // group
        s = qh @ k[j].T / jnp.sqrt(jnp.float32(qh.shape[-1]))
        decay = jnp.exp(jnp.where(allowed, cum[j][:, None] - cum[j][None, :],
                                  -jnp.inf))
        w = decay * s ** degree  # the full (T, T) weights
        y = w @ v[j]
        if normaliser:
            y = y / (w.sum(-1, keepdims=True) + ret_eps)
        return None, y

    _, y = jax.lax.scan(one_head, None, (q, jnp.arange(n_head)))
    return y.transpose(1, 0, 2).reshape(t, -1) @ a["o"]["kernel"]


_STATIC = ("n_head", "n_kv_head", "eps", "theta", "ret_eps", "degree", "gate",
           "normaliser", "head_gate", "rope", "qk_norm", "reset")


@functools.partial(jax.jit, static_argnames=_STATIC)
def layer(p, x, *, n_head, n_kv_head, eps, theta, ret_eps, degree=2,
          gate=True, normaliser=True, head_gate=True, rope=True, qk_norm=True,
          reset=0):
    """One block, (T, C) -> (T, C). The arguments past `ret_eps` each set
    one thing wrong (module docstring)."""
    h = _rms_norm(p["ln_1"]["scale"], x, eps)
    x = x + _retention(
        p["attn"], h, n_head=n_head, n_kv_head=n_kv_head, eps=eps,
        theta=theta, ret_eps=ret_eps, degree=degree, gate=gate,
        normaliser=normaliser, head_gate=head_gate, rope=rope,
        qk_norm=qk_norm, reset=reset)
    return x + _swiglu(p["mlp"], _rms_norm(p["ln_2"]["scale"], x, eps))


@functools.partial(jax.jit, static_argnames=("eps",))
def head(ln_f, kernel, x, *, eps):
    return _rms_norm(ln_f["scale"], x, eps) @ kernel


def embed(wte, ids):
    return wte["embedding"][jnp.asarray(ids)]


def layer_args(cfg, i, **wrong):
    """The program's model config -> `layer`'s arguments for layer i (every
    layer is the same kind); `wrong` overrides (the controls)."""
    del i
    kw = dict(n_head=cfg.n_head, n_kv_head=cfg.n_kv_head,
              eps=float(cfg.rms_eps), theta=float(cfg.rope_theta),
              ret_eps=float(cfg.retention.eps))
    kw.update(wrong)
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
