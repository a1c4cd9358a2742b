"""Falcon-H1-34B-Instruct, plainly: one pipeline stage's layers in
straightforward `jax.numpy`, float32 — the state-space rule as a `lax.scan`
over POSITIONS (the recurrence as it stands: no chunks, no cumulative logs),
full (T, T) softmax scores a head, the convolution as four shifted sums, no
cache, no batching, no kernels and no scan over layers: it shares no code and
no algebra with the program's chunked rule (dnn_tpu/models/mamba2.py).

The layers (tiiuae/Falcon-H1-34B-Instruct `config.json`, `model_type`
falcon_h1; x is (T, C), C = 5120; every layer is the same kind), as the
configuration's file says under `assumed`, the multipliers by their `config`
names:

  x_0 = embedding_multiplier wte[id];  logits = lm_head_multiplier W_head
  RMSNorm_final(x_L).
  Block: h = RMSNorm_in(x) (eps 1e-5);
    x' = x + ssm_out_multiplier SSM(h) + attention_out_multiplier Attn(h);
    x'' = x' + MLP(RMSNorm_ff(x')).
  Attn(h): a = attention_in_multiplier h; q = W_q a (20 heads of 128), k =
    key_multiplier W_k a, v = W_v a (4 heads of 128); rotary embedding on q
    and k (theta 1e11, all 128 dimensions, pairs (i, i + 64)); causal softmax
    of q . k / sqrt(128), query head i reads KV head i // 5; W_o.
  SSM(h): u = ssm_in_multiplier h; p = (W_in u) * mup, `mup` holding
    ssm_multipliers[0..4] over the slices [z 4096 | x 4096 | B 2 x 256 | C 2 x
    256 | dt 32]. [x | B | C] passes a causal depthwise convolution of 4 taps
    with bias, then SiLU: c_t = silu(b + sum_j w_j xBC_{t-3+j}), zeros before
    position 0. A head h of 32 (P = 128 channels, group h // 16's B and C, N =
    256): dt_t = softplus(dt_t + dt_bias_h), A_h = -exp(A_log_h), a_t =
    exp(dt_t A_h);

        S_t = a_t S_{t-1} + dt_t x_t B_t^T  (P x N, S_0 = 0), ONE POSITION
        y_t = S_t C_t + D_h x_t              AT A TIME (`lax.scan` over t)

    g = y * silu(z) (the gate FIRST), RMS-normalised within each of the 2
    groups of 2048 channels (eps 1e-5), times a gain of 4096; SSM(h) = W_out g.
  MLP(m) = mlp_multipliers[1] W_down(silu(mlp_multipliers[0] W_gate m) * W_up
    m), width 21 504.

Arguments that set ONE thing wrong, for the controls (`layer`): `ssm` False
(the state-space branch left out), `attn` False (the attention branch left
out), `reset` n (the state reset to zero before every n-th position: what a
program that dropped the state between chunks computes), `decay` False (a =
1), `dt_in` False (dt left out of the input term), `group0` True (B and C of
group 0 used by every head), `gate_first` False (the gate applied AFTER the
norm), `grouped_norm` False (the norm over all 4096 channels), `tail_reset` n
(the convolution reads zeros for rows of an earlier block of n positions: a
tail dropped at a chunk's edge), `ssm_mup` False (ssm_multipliers all 1),
`key_mup` False (key_multiplier 1), `rope` False.

It reads the parameter tree of `dnn_tpu.models.llama.init` because the
weights under test are made by the program from `--seed`; nothing else of the
program is used. `embed`, `layer` and `head` are its three steps on their own:
the check draws one layer's weights at a time (`chipbench/serve_fh1.py`);
`embed` and `head` take the configuration, whose multipliers are theirs.
Callers wrap it in `jax.default_matmul_precision("highest")`.
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


def _attention(a, h, *, n_head, n_kv_head, theta, attn_in, key_mult, rope):
    t = h.shape[0]
    h = attn_in * h

    def heads(w, n):  # (T, n * D) -> (n, T, D)
        return (h @ w["kernel"]).reshape(t, n, -1).transpose(1, 0, 2)

    q, k, v = heads(a["q"], n_head), key_mult * heads(a["k"], n_kv_head), \
        heads(a["v"], n_kv_head)
    if rope:
        q, k = _rope_halves(q, theta), _rope_halves(k, theta)
    causal = jnp.tril(jnp.ones((t, t), bool))
    group = n_head // n_kv_head

    def one_head(_, head):
        qh, i = head
        j = i // group
        s = qh @ k[j].T / jnp.sqrt(jnp.float32(qh.shape[-1]))
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return None, p @ v[j]

    _, y = jax.lax.scan(one_head, None, (q, jnp.arange(n_head)))
    return y.transpose(1, 0, 2).reshape(t, -1) @ a["o"]["kernel"]


def _ssm(s, h, *, eps, n_head, n_groups, d_state, ssm_in, multipliers, reset,
         decay, dt_in, group0, gate_first, grouped_norm, tail_reset):
    t = h.shape[0]
    d_ssm = s["norm"]["scale"].shape[0]
    gn = n_groups * d_state
    p = (ssm_in * h) @ s["in"]["kernel"]
    sizes = (d_ssm, d_ssm, gn, gn, n_head)
    p = p * jnp.concatenate([jnp.full((w,), m, jnp.float32)
                             for w, m in zip(sizes, multipliers)])
    z, xbc, dt = p[:, :d_ssm], p[:, d_ssm:2 * d_ssm + 2 * gn], \
        p[:, 2 * d_ssm + 2 * gn:]
    # the convolution as four shifted sums: tap j reads row t - 3 + j
    taps = s["conv"]["taps"]
    n_taps = taps.shape[0]
    pos = jnp.arange(t)
    conv = jnp.zeros_like(xbc) + s["conv"]["bias"]
    for j in range(n_taps):
        back = n_taps - 1 - j
        src = pos - back
        shifted = jnp.where((src >= 0)[:, None],
                            xbc[jnp.clip(src, 0, t - 1)], 0.0)
        if tail_reset:
            shifted = jnp.where((src // tail_reset == pos // tail_reset)
                                [:, None], shifted, 0.0)
        conv = conv + taps[j] * shifted
    c = jax.nn.silu(conv)
    x = c[:, :d_ssm].reshape(t, n_head, -1)
    bm = c[:, d_ssm:d_ssm + gn].reshape(t, n_groups, d_state)
    cm = c[:, d_ssm + gn:].reshape(t, n_groups, d_state)
    per = n_head // n_groups
    group_of = jnp.zeros((n_head,), jnp.int32) if group0 \
        else jnp.arange(n_head) // per
    bm, cm = bm[:, group_of], cm[:, group_of]  # (T, H, N)
    dt = jax.nn.softplus(dt + s["dt_bias"])
    a = jnp.exp(dt * -jnp.exp(s["a_log"])) if decay else jnp.ones_like(dt)
    inp = (dt if dt_in else jnp.ones_like(dt))[..., None] * x

    def one(state, xs):
        i, a_t, inp_t, b_t, c_t = xs
        if reset:
            state = jnp.where(i % reset == 0, 0.0, state)
        state = a_t[:, None, None] * state + inp_t[:, :, None] * b_t[:, None]
        return state, (state * c_t[:, None]).sum(-1)

    state0 = jnp.zeros((n_head, x.shape[-1], d_state), jnp.float32)
    _, y = jax.lax.scan(one, state0, (pos, a, inp, bm, cm))
    y = (y + s["d"][:, None] * x).reshape(t, d_ssm)
    gate = jax.nn.silu(z)
    groups = n_groups if grouped_norm else 1

    def norm(v):
        v = v.reshape(t, groups, -1)
        return (v * jax.lax.rsqrt((v * v).mean(-1, keepdims=True) + eps)
                ).reshape(t, d_ssm) * s["norm"]["scale"]

    g = norm(y * gate) if gate_first else norm(y) * gate
    return g @ s["out"]["kernel"]


def _mlp(p, m, gate_mult, down_mult):
    return down_mult * ((jax.nn.silu(gate_mult * (m @ p["gate"]["kernel"]))
                         * (m @ p["up"]["kernel"])) @ p["down"]["kernel"])


_STATIC = ("n_head", "n_kv_head", "eps", "theta", "ssm_heads", "n_groups",
           "d_state", "attn_in", "attn_out", "key_mult", "ssm_in", "ssm_out",
           "multipliers", "mlp_mult", "ssm", "attn", "reset", "decay",
           "dt_in", "group0", "gate_first", "grouped_norm", "tail_reset",
           "ssm_mup", "key_mup", "rope")


@functools.partial(jax.jit, static_argnames=_STATIC)
def layer(p, x, *, n_head, n_kv_head, eps, theta, ssm_heads, n_groups,
          d_state, attn_in, attn_out, key_mult, ssm_in, ssm_out, multipliers,
          mlp_mult, ssm=True, attn=True, reset=0, decay=True, dt_in=True,
          group0=False, gate_first=True, grouped_norm=True, tail_reset=0,
          ssm_mup=True, key_mup=True, rope=True):
    """One block, (T, C) -> (T, C). The arguments past `mlp_mult` each set
    one thing wrong (module docstring)."""
    h = _rms_norm(p["ln_1"]["scale"], x, eps)
    if ssm:
        x = x + ssm_out * _ssm(
            p["ssm"], h, eps=eps, n_head=ssm_heads, n_groups=n_groups,
            d_state=d_state, ssm_in=ssm_in,
            multipliers=multipliers if ssm_mup else (1.0,) * 5, reset=reset,
            decay=decay, dt_in=dt_in, group0=group0, gate_first=gate_first,
            grouped_norm=grouped_norm, tail_reset=tail_reset)
    if attn:
        x = x + attn_out * _attention(
            p["attn"], h, n_head=n_head, n_kv_head=n_kv_head, theta=theta,
            attn_in=attn_in, key_mult=key_mult if key_mup else 1.0, rope=rope)
    return x + _mlp(p["mlp"], _rms_norm(p["ln_2"]["scale"], x, eps),
                    *mlp_mult)


@functools.partial(jax.jit, static_argnames=("eps", "mult"))
def _head(ln_f, kernel, x, *, eps, mult):
    return mult * (_rms_norm(ln_f["scale"], x, eps) @ kernel)


def head(cfg, ln_f, kernel, x):
    return _head(ln_f, kernel, x, eps=float(cfg.rms_eps),
                 mult=float(cfg.mup.lm_head))


def embed(cfg, wte, ids):
    return cfg.mup.embedding * wte["embedding"][jnp.asarray(ids)]


def layer_args(cfg, i, **wrong):
    """The program's model config -> `layer`'s arguments for layer i (every
    layer is the same kind); `wrong` overrides (the controls)."""
    del i
    m, mup = cfg.mamba, cfg.mup
    kw = dict(n_head=cfg.n_head, n_kv_head=cfg.n_kv_head,
              eps=float(cfg.rms_eps), theta=float(cfg.rope_theta),
              ssm_heads=m.n_head, n_groups=m.n_groups, d_state=m.d_state,
              attn_in=float(mup.attention_in),
              attn_out=float(mup.attention_out), key_mult=float(mup.key),
              ssm_in=float(m.ssm_in), ssm_out=float(m.ssm_out),
              multipliers=tuple(float(v) for v in m.ssm_multipliers),
              mlp_mult=tuple(float(v) for v in mup.mlp))
    kw.update(wrong)
    return kw


def hidden(cfg, params, ids, **wrong):
    """(T,) ids of ONE sequence -> (T, C): the last block's output, before
    the final norm and the head."""
    x = embed(cfg, params["wte"], ids)
    for i in range(cfg.n_layer):
        x = layer(params[f"h_{i}"], x, **layer_args(cfg, i, **wrong))
    return x


def forward(cfg, params, ids, rows=None, **wrong):
    """(T,) ids -> (T, vocab) float32 logits, or those of `rows` only."""
    x = hidden(cfg, params, ids, **wrong)
    if rows is not None:
        x = x[rows]
    return head(cfg, params["ln_f"], params["lm_head"]["kernel"], x)


def logits(cfg, params, ids):
    """What the check calls in every reference module: (B, T) ids -> (B,
    T, vocab), one sequence at a time."""
    return jnp.stack([forward(cfg, params, row) for row in jnp.asarray(ids)])
