"""NVIDIA-Nemotron-3-Super-120B-A12B, plainly: one chip's share of the first
eleven blocks in straightforward `jax.numpy`, float32 — a block is ONE norm,
ONE mixer and one residual; the state-space rule as a `lax.scan` over
POSITIONS, full (T, T) softmax scores a head, the routing weights gathered a
pick at a time into a (T, experts) table and the experts one after the other
over every row, no cache, no batching, no kernels and no scan over layers: it
shares no code and no algebra with the program's chunked rule
(dnn_tpu/models/mamba2.py) nor with its sorted, grouped experts
(dnn_tpu/parallel/moe.py).

The blocks (nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16 `config.json`,
`model_type` nemotron_h; x is (T, C), C = 4096), as the configuration's file
says under `assumed`. No bias anywhere but the convolution's.

  x_0 = wte[id]. Block i of kind `hybrid_override_pattern[i]`:
    h = RMSNorm(x; g_i) (eps 1e-5, plain gain);  x' = x + Mixer(h).
  logits = W_head RMSNorm(x_L; g_f), untied.
  M (128 heads of P = 64 in 8 groups, state N = 128, 4 taps):
    [z 8192 | c 10 240 | dt 128] = h W_in, c = [x 8192 | B 8 x 128 | C 8 x 128];
    c' = SiLU(conv4(c) + b): causal, depthwise, zeros before position 0;
    head j reads group j // 16's B and C; d_t = softplus(dt_t + dt_bias_j)
    (no clamp), a_t = exp(-exp(A_log_j) d_t);

        S_t = a_t S_{t-1} + d_t x_t B_t^T   (P x N a head, S_0 = 0), ONE
        y_t = S_t C_t + D_j x_t              POSITION AT A TIME

    o = W_out (RMSNorm within each group's 1024 channels of (y * SiLU(z))):
    the gate FIRST, then the norm, then its gain of 8192.
  * : q = h W_q (32 heads of 128), k, v = h W_k, h W_v (2 heads of 128), query
    head j reads KV head j // 16, scores q . k / sqrt(128), u <= t, softmax,
    W_o. NO rotary embedding and no other position signal (`rope` True is the
    controls': theta 1e4, pairs (i, i + 64)).
  E : s = sigmoid(h W_r) (512 scores, of the FULL-width h); the 22 largest of
    s + b (b: the selection bias, selection only); w_e = 5 s_e / (sum of the
    22 s + 1e-20); u = h W_down (1024 wide); E_e(u) = W_2e relu(W_1e u)^2
    (1024 -> 2688 -> 1024, no gate); r = sum over the picks HELD here (experts
    `first` .. `first` + count) of w_e E_e(u), w normalised over all 22;
    y = r W_up + W_s2 relu(W_s1 h)^2 (the shared expert 4096 -> 5376 -> 4096 on
    the full-width h). W_up is linear: the chips' r_c W_up add up to r W_up,
    and the shared expert counts once (`shared` False leaves it out, for the
    shares test).

Arguments that set ONE thing wrong, for the controls and the CPU tests
(`layer`): `act` "relu" (relu in place of relu^2), `gated` True (a gated
expert: SiLU(a) * a of the one product a = W_1 u, where the model squares a
relu), `route_latent` True (the router fed the latent, through the router's
first 1024 rows), `shared_latent` True (the shared expert fed the latent,
through W_s1's first 1024 rows), `shared` False, `norm_held` True (weights
normalised over the HELD picks), `scale` 1.0, `bias_in_weight` True (the
selection bias added to the weights), `gate_first` False (the norm before the
gate), `grouped_norm` False (one norm over all 8192 channels), `rope` True,
`ffn_after` True (an M block given a second sublayer, as a block of two
sublayers would: x'' = x' + relu(RMSNorm(x'))^2 — the block holds no weights
for one), `d_skip` False (D left out), `dt_bias` False, `state_dtype`
"bfloat16" (the state held in bfloat16 between positions).

It reads the parameter tree of `dnn_tpu.models.llama_moe.init` because the
weights under test are made by the program from `--seed`; nothing else of the
program is used. `embed`, `layer` (with `layer_args`) and `head` are its steps
on their own: the check draws one block's weights at a time
(`chipbench/serve_dots.py`). Callers wrap it in
`jax.default_matmul_precision("highest")`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

__all__ = ["embed", "layer", "head", "layer_args", "hidden", "forward",
           "logits", "routed_latent"]


def _rms_norm(scale, x, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _relu2(x):
    return jnp.maximum(x, 0.0) ** 2


def _rope_halves(x, theta):
    """x (..., T, d): rotate the pairs (i, i + d/2) by position *
    theta^(-2i/d) (the controls' one thing wrong: the model has none)."""
    t, d = x.shape[-2:]
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    a = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(a), jnp.sin(a)
    lo, hi = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([lo * cos - hi * sin, hi * cos + lo * sin], -1)


def _attention(a, h, *, n_head, n_kv_head, rope):
    t = h.shape[0]
    group = n_head // n_kv_head

    def heads(w, n):  # (T, n * D) -> (n, T, D)
        return (h @ w["kernel"]).reshape(t, n, -1).transpose(1, 0, 2)

    q, k, v = heads(a["q"], n_head), heads(a["k"], n_kv_head), \
        heads(a["v"], n_kv_head)
    if rope:
        q, k = _rope_halves(q, 1e4), _rope_halves(k, 1e4)
    cols = jnp.arange(t)
    allowed = cols[None, :] <= cols[:, None]

    def one_head(_, head):
        qh, j = head
        s = qh @ k[j // group].T / jnp.sqrt(jnp.float32(qh.shape[-1]))
        s = jnp.where(allowed, s, -jnp.inf)  # the full (T, T) scores
        return None, jax.nn.softmax(s, axis=-1) @ v[j // group]

    _, y = jax.lax.scan(one_head, None, (q, jnp.arange(n_head)))
    return y.transpose(1, 0, 2).reshape(t, -1) @ a["o"]["kernel"]


def _ssm(s, h, *, eps, n_head, n_groups, d_state, gate_first, grouped_norm,
         d_skip, dt_bias, state_dtype):
    t = h.shape[0]
    d_ssm = s["norm"]["scale"].shape[0]
    gn = n_groups * d_state
    p = h @ s["in"]["kernel"]
    z, c, dt = p[:, :d_ssm], p[:, d_ssm:2 * d_ssm + 2 * gn], \
        p[:, 2 * d_ssm + 2 * gn:]
    # the convolution as four shifted sums: tap j reads row t - 3 + j
    taps = s["conv"]["taps"]
    n_taps = taps.shape[0]
    pos = jnp.arange(t)
    conv = jnp.zeros_like(c) + s["conv"]["bias"]
    for j in range(n_taps):
        src = pos - (n_taps - 1 - j)
        conv = conv + taps[j] * jnp.where(
            (src >= 0)[:, None], c[jnp.clip(src, 0, t - 1)], 0.0)
    c = jax.nn.silu(conv)
    x = c[:, :d_ssm].reshape(t, n_head, -1)
    group_of = jnp.arange(n_head) // (n_head // n_groups)
    bm = c[:, d_ssm:d_ssm + gn].reshape(t, n_groups, d_state)[:, group_of]
    cm = c[:, d_ssm + gn:].reshape(t, n_groups, d_state)[:, group_of]
    d = jax.nn.softplus(dt + (s["dt_bias"] if dt_bias else 0.0))  # (T, H)
    a = jnp.exp(-jnp.exp(s["a_log"]) * d)
    held = jnp.dtype(state_dtype)

    def one(state, xs):
        a_t, in_t, b_t, c_t = xs
        state = (a_t[:, None, None] * state.astype(jnp.float32)
                 + in_t[:, :, None] * b_t[:, None])
        return state.astype(held), (state * c_t[:, None]).sum(-1)

    state0 = jnp.zeros((n_head, x.shape[-1], d_state), held)
    _, y = jax.lax.scan(one, state0, (a, d[..., None] * x, bm, cm))
    if d_skip:
        y = y + s["d"][:, None] * x
    y = y.reshape(t, d_ssm)
    gate = jax.nn.silu(z)
    groups = n_groups if grouped_norm else 1

    def norm(v):
        v = v.reshape(t, groups, -1)
        return (v * jax.lax.rsqrt((v * v).mean(-1, keepdims=True) + eps)
                ).reshape(t, d_ssm) * s["norm"]["scale"]

    g = norm(y * gate) if gate_first else norm(y) * gate
    return g @ s["out"]["kernel"]


def routed_latent(p, h, *, top_k, first, scale, act="relu2", gated=False,
                  route_latent=False, norm_held=False,
                  bias_in_weight=False):
    """(T, C) -> r (T, latent): the weighted sum of the experts HELD in `p`
    (`first` .. `first` + their count) over u = h W_down, before W_up."""
    n_expert = p["router"]["kernel"].shape[-1]
    count = p["wi"].shape[0]
    u = h @ p["latent_down"]["kernel"]
    if route_latent:  # the router fed the latent, through its first rows
        s = jax.nn.sigmoid(u @ p["router"]["kernel"][:u.shape[-1]])
    else:
        s = jax.nn.sigmoid(h @ p["router"]["kernel"])  # (T, experts)
    bias = p["router"]["select_bias"]
    _, idx = jax.lax.top_k(s + bias, top_k)  # (T, picks)
    picked = jnp.take_along_axis(s, idx, axis=-1)
    if bias_in_weight:
        picked = picked + bias[idx]
    here = (idx >= first) & (idx < first + count)
    total = (jnp.where(here, picked, 0.0) if norm_held else picked).sum(
        -1, keepdims=True)
    w = scale * picked / (total + 1e-20)
    # a pick at a time into the (T, experts held) table of weights
    table = jnp.zeros((h.shape[0], count), jnp.float32)
    for j in range(top_k):
        table = table + jnp.where(
            here[:, j, None],
            jax.nn.one_hot(idx[:, j] - first, count) * w[:, j, None], 0.0)

    def one_expert(r, expert):
        w1, w2, w_e = expert
        a = u @ w1
        if gated:  # a SwiGLU of the one product: what a gate would do
            a = jax.nn.silu(a) * a
        else:
            a = _relu2(a) if act == "relu2" else jnp.maximum(a, 0.0)
        return r + w_e[:, None] * (a @ w2), None

    r, _ = jax.lax.scan(one_expert, jnp.zeros_like(u),
                        (p["wi"], p["wo"], table.T))
    return r


def _shared(p, h, *, act, shared_latent):
    w1 = p["shared"]["up"]["kernel"]
    if shared_latent:  # the shared expert fed the latent
        u = h @ p["latent_down"]["kernel"]
        a = u @ w1[:u.shape[-1]]
    else:
        a = h @ w1
    a = _relu2(a) if act == "relu2" else jnp.maximum(a, 0.0)
    return a @ p["shared"]["down"]["kernel"]


_STATIC = ("kind", "n_head", "n_kv_head", "eps", "ssm_heads", "n_groups",
           "d_state", "top_k", "first", "scale", "act", "gated",
           "route_latent", "shared_latent", "shared", "norm_held",
           "bias_in_weight", "gate_first", "grouped_norm", "rope",
           "ffn_after", "d_skip", "dt_bias", "state_dtype")


@functools.partial(jax.jit, static_argnames=_STATIC)
def layer(p, x, *, kind, n_head, n_kv_head, eps, ssm_heads, n_groups,
          d_state, top_k, first, scale, act="relu2", gated=False,
          route_latent=False, shared_latent=False, shared=True,
          norm_held=False, bias_in_weight=False, gate_first=True,
          grouped_norm=True, rope=False, ffn_after=False, d_skip=True,
          dt_bias=True, state_dtype="float32"):
    """One block of `kind` ("ssm", "full" or "experts"), (T, C) -> (T, C).
    The arguments past `scale` each set one thing wrong (module
    docstring)."""
    h = _rms_norm(p["ln_1"]["scale"], x, eps)
    if kind == "ssm":
        x = x + _ssm(p["ssm"], h, eps=eps, n_head=ssm_heads,
                     n_groups=n_groups, d_state=d_state,
                     gate_first=gate_first, grouped_norm=grouped_norm,
                     d_skip=d_skip, dt_bias=dt_bias, state_dtype=state_dtype)
        if ffn_after:
            x = x + _relu2(_rms_norm(p["ln_1"]["scale"], x, eps))
        return x
    if kind == "full":
        return x + _attention(p["attn"], h, n_head=n_head,
                              n_kv_head=n_kv_head, rope=rope)
    m = p["moe"]
    r = routed_latent(m, h, top_k=top_k, first=first, scale=scale, act=act,
                      gated=gated, route_latent=route_latent,
                      norm_held=norm_held, bias_in_weight=bias_in_weight)
    y = r @ m["latent_up"]["kernel"]
    if shared:
        y = y + _shared(m, h, act=act, shared_latent=shared_latent)
    return x + y


@functools.partial(jax.jit, static_argnames=("eps",))
def head(ln_f, kernel, x, *, eps):
    return _rms_norm(ln_f["scale"], x, eps) @ kernel


def embed(wte, ids):
    return wte["embedding"][jnp.asarray(ids)]


def layer_args(cfg, i, held=None, **wrong):
    """The program's model config -> `layer`'s arguments for block i;
    `held` = (first, count) of the experts in the tree, the config's own
    range when None; `wrong` overrides (the controls)."""
    m = cfg.mamba
    kw = dict(kind=cfg.layer_types[i], n_head=cfg.n_head,
              n_kv_head=cfg.n_kv_head, eps=float(cfg.rms_eps),
              ssm_heads=m.n_head, n_groups=m.n_groups, d_state=m.d_state,
              top_k=cfg.router_top_k,
              first=int(cfg.experts_first) if held is None else int(held[0]),
              scale=float(cfg.router.scale))
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
