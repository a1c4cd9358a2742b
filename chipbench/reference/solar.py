"""Solar-Open2-250B, plainly: one chip's share of the forward pass in
straightforward `jax.numpy`, float32, no kernels, no cache, no paging, no
batching, no chunked rule, no scan over layers.

The layers (upstage/Solar-Open2-250B `config.json`, `model_type`
solar_open2; x is (T, C), C = 4096, t a position). `gqa_layers` says which
layers are SOFTMAX layers (0, 4, ..., 44); the other three of every four
are LINEAR layers. Both are pre-norm residual blocks, h = RMSNorm(x) (eps
1e-5):

  * softmax layer: q = h W_q (64 heads of 128), k = h W_k, v = h W_v (8
    heads of 128; query head j reads KV head j // 8), NO rotation
    (`use_rope` false) and no q/k norm; s[t, u] = q[t] . k[u] / sqrt(128)
    over u <= t, softmax, o = P v; y = x + [concat(o) * sigmoid(h W_gate)]
    W_o, the gate 4096 -> 8192, element-wise (`use_gqa_gate`).
  * linear layer, a head of 64, d = 128 (`linear_attn_config`): q~ = h W_q,
    k~ = h W_k, v~ = h W_v (8192 each); each passes a causal depthwise
    convolution of 4 taps and SiLU, q'[t] = silu(sum_j c_j q~[t - 3 + j]),
    zeros before position 0; q = l2norm(q') / sqrt(128), k = l2norm(k') a
    head, v = v'. Decay a CHANNEL: g = -exp(A_log_h) softplus(h W_f1 W_f2
    + dt_bias), a = exp(g) in (0, 1) (64 x 128). beta = 2 sigmoid(h W_b) a
    head. State S (128 x 128 a head, float32, S_0 = 0), ONE POSITION AT A
    TIME (`lax.scan` over t):

        S_t = (I - beta_t k_t k_t^T) Diag(a_t) S_{t-1} + beta_t k_t v_t^T
        o_t = S_t^T q_t

    y = x + [RMSNorm_head(o) * sigmoid(h W_g1 W_g2)] W_o (a gain of 128,
    one for all heads).
  * every layer: h2 = RMSNorm(y); p = sigmoid(h2 W_r) over ALL 320 experts
    (float32); the 8 largest of p + b; w = p[picked] / (sum + 1e-20); out =
    y + sum of w_e E_e(h2) + S(h2), E and S SwiGLU of 1280, S (the shared
    expert) ungated.
  Final RMSNorm, untied head over the vocabulary rows this chip holds.

The held range (`held` = (first, count)) and what is left out are as
`reference/joyai.py` says: every held expert on every token, weighted by
that token's weight for it, zero unless among its eight of ALL 320; what
the experts held elsewhere would add is left out and the partial result
goes on. The vocabulary rows held elsewhere are simply absent.

Departures from the published description, each with its reason:
  * what `config.json` has no key for is taken as the configuration's file
    says under `assumed`: the softmax layer's gate element-wise and its
    missing q/k norm, the DeepSeek-V3 router with a selection bias, the
    ungated shared expert, the state in float32, l2norm's eps 1e-6;
  * softmax attention runs one head at a time (a scan, so one body
    compiles): 64 x (T, T) scores at T = 9216 would be 21.7 GB;
  * `intermediate_size` 10240 belongs to dense layers, of which
    `first_k_dense_replace` 0 leaves none; no multi-token-prediction
    module is declared;
  * everything is float32, so no cast of the routing weights.

Arguments that set ONE thing wrong, for the controls (`layer`): `beta_scale`
1.0 (beta not doubled), `head_decay` (a decay a HEAD: the channels' mean),
`conv` False (the convolution left out: q' = silu(q~)), `state_dtype`
bfloat16 (the state rounded after every position), `rope` True (the softmax
layer rotated, theta 10000), `gate` False (its gate left out), `shared`
False, `bias` False, and `skip` (T,) bool: positions that the other
positions' rows do not see as columns in the SOFTMAX layers while the
linear layers run over them — exactly what a program that let a padded
tail into the state computes, since without rotation a position reaches
the softmax layers through the mask alone.

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
    t, d = x.shape[-2:]
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    a = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(a), jnp.sin(a)
    lo, hi = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([lo * cos - hi * sin, hi * cos + lo * sin], -1)


def _swiglu(p, h):
    return (jax.nn.silu(h @ p["gate"]["kernel"]) * (h @ p["up"]["kernel"])
            ) @ p["down"]["kernel"]


def _softmax_mixer(a, h, *, n_head, n_kv_head, rope, gate, skip):
    t = h.shape[0]
    group = n_head // n_kv_head

    def heads(w, n):  # (T, n * D) -> (n, T, D)
        return (h @ w["kernel"]).reshape(t, n, -1).transpose(1, 0, 2)

    q, k, v = heads(a["q"], n_head), heads(a["k"], n_kv_head), \
        heads(a["v"], n_kv_head)
    if rope:
        q, k = _rope_halves(q, 10000.0), _rope_halves(k, 10000.0)
    cols = jnp.arange(t)
    allowed = cols[None, :] <= cols[:, None]
    if skip is not None:  # hidden from the rows that are not skipped
        allowed = allowed & ~(skip[None, :] & ~skip[:, None])

    def one_head(_, head):
        qh, j = head
        s = qh @ k[j // group].T / jnp.sqrt(jnp.float32(qh.shape[-1]))
        s = jnp.where(allowed, s, -jnp.inf)  # the full (T, T) scores
        return None, jax.nn.softmax(s, axis=-1) @ v[j // group]

    _, y = jax.lax.scan(one_head, None, (q, jnp.arange(n_head)))
    y = y.transpose(1, 0, 2).reshape(t, -1)
    if gate:
        y = y * jax.nn.sigmoid(h @ a["gate"]["kernel"])
    return y @ a["o"]["kernel"]


def _conv(taps, x, on):
    """Causal depthwise convolution: y[t] = sum_j taps[j] x[t - K + 1 + j],
    zeros before position 0; `on` False: y = x."""
    if not on:
        return x
    n = taps.shape[0]
    padded = jnp.concatenate([jnp.zeros((n - 1, x.shape[1]), x.dtype), x])
    return sum(taps[j] * padded[j:j + x.shape[0]] for j in range(n))


def _linear_mixer(a, h, *, eps, beta_scale, head_decay, conv, state_dtype):
    t = h.shape[0]
    n_head = a["a_log"].shape[0]

    def heads(x):  # (T, H * d) -> (T, H, d)
        return x.reshape(t, n_head, -1)

    def l2(x):
        return x * jax.lax.rsqrt((x * x).sum(-1, keepdims=True) + 1e-6)

    q, k, v = (heads(jax.nn.silu(_conv(
        a["conv"][n]["taps"], h @ a[n]["kernel"], conv))) for n in "qkv")
    d = q.shape[-1]
    q, k = l2(q) / jnp.sqrt(jnp.float32(d)), l2(k)
    f = (h @ a["f1"]["kernel"]) @ a["f2"]["kernel"] + a["dt_bias"]
    g = -jnp.exp(a["a_log"])[:, None] * heads(jax.nn.softplus(f))
    if head_decay:
        g = jnp.broadcast_to(g.mean(-1, keepdims=True), g.shape)
    beta = beta_scale * jax.nn.sigmoid(h @ a["b"]["kernel"])  # (T, H)

    kept = jnp.finfo(state_dtype)

    def position(s, now):  # s (H, d, d): key channel x value channel
        q_t, k_t, v_t, g_t, b_t = now
        s = jnp.exp(g_t)[:, :, None] * s
        u = b_t[:, None] * (v_t - jnp.einsum("hc,hcv->hv", k_t, s))
        s = s + k_t[:, :, None] * u[:, None, :]
        # rounded to `state_dtype` and held in float32: `reduce_precision`,
        # since XLA drops a cast there and back as excess precision
        s = jax.lax.reduce_precision(s, kept.nexp, kept.nmant)
        return s, jnp.einsum("hc,hcv->hv", q_t, s)

    _, o = jax.lax.scan(position, jnp.zeros((n_head, d, d), jnp.float32),
                        (q, k, v, g, beta))
    y = _rms_norm(a["o_norm"]["scale"], o, eps).reshape(t, -1)
    y = y * jax.nn.sigmoid((h @ a["g1"]["kernel"]) @ a["g2"]["kernel"])
    return y @ a["o"]["kernel"]


def _experts(p, h, *, top_k, first, bias):
    """(T, C) -> the held experts' part, and the shared expert's."""
    n_expert = p["router"]["kernel"].shape[-1]
    scores = jax.nn.sigmoid(h @ p["router"]["kernel"])  # (T, E)
    pick = scores + p["router"]["select_bias"] if bias else scores
    _, idx = jax.lax.top_k(pick, top_k)
    top = jnp.take_along_axis(scores, idx, axis=-1)
    top = top / (top.sum(-1, keepdims=True) + 1e-20)
    weights = (jax.nn.one_hot(idx, n_expert) * top[..., None]).sum(1)
    held = weights[:, first:first + p["wg"].shape[0]]  # (T, count)

    def one_expert(out, expert):
        wg, wu, wd, w = expert
        return out + w[:, None] * ((jax.nn.silu(h @ wg) * (h @ wu)) @ wd), None

    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(h),
                          (p["wg"], p["wu"], p["wd"], held.T))
    return out, _swiglu(p["shared"], h)


_STATIC = ("kind", "n_head", "n_kv_head", "eps", "top_k", "first", "shared",
           "bias", "rope", "gate", "beta_scale", "head_decay", "conv",
           "state_dtype")


@functools.partial(jax.jit, static_argnames=_STATIC)
def layer(p, x, *, kind, n_head, n_kv_head, eps, top_k, first, shared=True,
          bias=True, rope=False, gate=True, beta_scale=2.0, head_decay=False,
          conv=True, state_dtype="float32", skip=None):
    """One block, (T, C) -> (T, C); `kind` "full" (softmax) or "linear".
    The other arguments past `first` each set one thing wrong (module
    docstring)."""
    h = _rms_norm(p["ln_1"]["scale"], x, eps)
    if kind == "full":
        x = x + _softmax_mixer(p["attn"], h, n_head=n_head,
                               n_kv_head=n_kv_head, rope=rope, gate=gate,
                               skip=skip)
    else:
        x = x + _linear_mixer(p["attn"], h, eps=eps, beta_scale=beta_scale,
                              head_decay=head_decay, conv=conv,
                              state_dtype=jnp.dtype(state_dtype))
    h2 = _rms_norm(p["ln_2"]["scale"], x, eps)
    routed, common = _experts(p["moe"], h2, top_k=top_k, first=first,
                              bias=bias)
    return x + routed + (common if shared else 0.0)


@functools.partial(jax.jit, static_argnames=("eps",))
def head(ln_f, kernel, x, *, eps):
    return _rms_norm(ln_f["scale"], x, eps) @ kernel


def embed(wte, ids):
    return wte["embedding"][jnp.asarray(ids)]


def layer_args(cfg, i, held=None, **wrong):
    """The program's model config -> `layer`'s arguments for layer i;
    `held` = (first, count), the config's own range when None; `wrong`
    overrides (the controls)."""
    kw = dict(
        kind=cfg.layer_types[i], n_head=cfg.n_head, n_kv_head=cfg.n_kv_head,
        eps=float(cfg.rms_eps), top_k=cfg.router_top_k,
        first=int(cfg.experts_first) if held is None else int(held[0]))
    kw.update(wrong)
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
