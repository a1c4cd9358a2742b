"""MiniCPM-SALA, plainly: one pipeline stage's share of the forward pass in
straightforward `jax.numpy`, float32, no kernels, no cache, no paging, no
batching, no chunked rule, no scan over layers.

The layers (openbmb/MiniCPM-SALA `config.json`, `model_type` minicpm_sala; x
is (T, C), C = 4096, t a position from 0). `mixer_types` says which layers
are `minicpm4` (softmax, "full") and which `lightning-attn` ("linear"). Both
are pre-norm residual blocks with RMSNorm eps 1e-6 and NO bias anywhere, and
both residual branches are scaled by r = `scale_depth` / sqrt(32) (the
PUBLISHED depth's): h = x + r Mixer(RMSNorm(x)); x' = h + r SwiGLU(RMSNorm(h)),
width 16384, SiLU. x_0 = `scale_emb` wte[id]; logits = W_head (RMSNorm(x_L) /
(`hidden_size` / `dim_model_base`)), untied.

  * `minicpm4`, 32 query heads, 2 KV heads of 128, group g = heads 16 g .. 16
    g + 15: q, k = RMSNorm a head of W_q h, W_k h (`qk_norm`), v = W_v h, NO
    rotation (`attn_use_rope` false). Block b = positions [64 b, 64 b + 63],
    b_t = t // 64.
      - pooled key i of KV head g: kc_g[i] = the mean of k_g over positions
        [16 i, 16 i + 31]; query t sees it iff 16 i + 31 <= t;
      - p_h[t, i] = softmax over the i that t sees of q_h[t] . kc_g[i] /
        sqrt(128); P_g[t, i] = the sum of p_h over the 16 heads of g;
      - B_g[t, b] = the largest P_g[t, i] over i in [4 b - 1, 4 b + 3] that t
        sees (the pooled windows that overlap block b);
      - the set of (t, g): the LOCAL blocks b_t - 31 .. b_t always, and of
        the blocks before them the 64 of largest B_g[t, b], block 0 forced
        among the 64, ties to the smaller b (a stable argsort of -B), all of
        them while fewer exist;
      - o_h[t] = softmax over the positions s <= t of the set of q_h[t] .
        k_g[s] / sqrt(128), times v_g; y = W_o (o * sigmoid(W_gate h)).
  * `lightning-attn`, 32 heads of 128: q, k = RMSNorm a head of W_q h, W_k h,
    then rotated (theta 10000, all 128 dimensions, pairs (i, i + 64)); v =
    W_v h. With lambda_h = exp(-s_h), s_h = 2^(-8 h / 32), h = 1..32, ONE
    POSITION AT A TIME (`lax.scan` over t): S_t = lambda_h S_{t-1} + k_t
    v_t^T (128 x 128 a head, float32, zero before position 0); o_t = S_t^T
    q_t / sqrt(128); y = W_o (RMSNorm a head of o, under ONE gain of 4096, *
    sigmoid(W_gate h)).

The full (T, T) scores and the (T, T / 16) pooled scores are made in blocks
of `ROWS` query rows (a scan, so one body compiles) and one KV group at a
time: 32 x (T, T) at T = 33 792 would be 146 GB.

Arguments that set ONE thing wrong, for the controls (`layer`; module
docstring of chipbench/sala_controls.py): `state_dtype` "bfloat16" (the
state rounded after every position), `kv_dtype` (K and V of the softmax
layer rounded to it: "float8_e4m3fn"), `decay` False (lambda = 1), `slopes`
"reversed", `lin_rope` False, `full_rope` True, `pick` "smallest", `local`
False (the local window left out), `init` False (block 0 not forced), `early`
True (a pooled key seen 16 positions early), `group_sum` False (head 0's
scores alone), `gate` False (either kind's gate left out), `out_norm` False,
`r` 1.0; `embed` and `head` take `scale_emb` 1.0 and `head_div` 1.0.

It reads the parameter tree of `dnn_tpu.models.llama.init` because the
weights under test are made by the program from `--seed`; nothing else of
the program is used. `embed`, `layer` and `head` are its three steps on their
own: the check draws one layer's weights at a time (`chipbench/serve_fh1.py`).
Callers wrap it in `jax.default_matmul_precision("highest")`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

__all__ = ["embed", "layer", "head", "layer_args", "hidden", "forward",
           "logits", "chosen_blocks"]

ROWS = 256  # query rows a block of the scores


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


def _heads(x, n):  # (T, n * d) -> (n, T, d)
    return x.reshape(x.shape[0], n, -1).transpose(1, 0, 2)


def _pooled_keys(kg, sel):
    """kg (T, d) a KV head's keys -> (n_pool, d): kc[i] = the mean of k over
    positions [stride i, stride i + kernel - 1]."""
    kernel, stride = sel[4:]
    t, d = kg.shape
    n_pool = max((t - kernel) // stride + 1, 1)
    padded = jnp.pad(kg, ((0, kernel), (0, 0)))
    return jnp.stack([
        padded[j:j + n_pool * stride].reshape(n_pool, stride, d)[:, 0]
        for j in range(kernel)]).mean(0)


def _block_sets(qg, kc, t, rows, sel, *, pick, local, init, early,
                group_sum):
    """One KV group's sets for the query rows `rows` (R,) int32 of a sequence
    of `t` positions: qg (G, R, d) their queries, kc (n_pool, d) the group's
    pooled keys -> bool (R, nb): the blocks each row reads. `sel` = (block,
    topk, window, init_blocks, kernel, stride)."""
    block, topk, window, init_blocks, kernel, stride = sel
    n_pool, d = kc.shape
    starts = jnp.arange(n_pool) * stride
    ends = starts + kernel - 1 - (stride if early else 0)
    seen = ends[None, :] <= rows[:, None]  # (R, n_pool)
    s = jnp.einsum("grd,id->gri", qg, kc) / jnp.sqrt(jnp.float32(d))
    p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
    p = jnp.where(seen[None], p, 0.0)  # a row that sees none: nan -> 0
    pg = p.sum(0) if group_sum else p[0]  # (R, n_pool)
    nb = -(-t // block)
    per = block // stride
    # the pooled windows that overlap block b: i in [per b - 1, per b + per
    # - 1]
    i = per * jnp.arange(nb)[:, None] + jnp.arange(-1, per)[None, :]
    ok = (i >= 0) & (i < n_pool)
    score = jnp.where(ok[None], pg[:, jnp.clip(i, 0, n_pool - 1)],
                      0.0).max(-1)  # (R, nb)
    b = jnp.arange(nb)
    bt = rows[:, None] // block
    n_local = window // block
    is_local = (b[None] > bt - n_local) & (b[None] <= bt)
    cand = b[None] <= bt - n_local
    if pick == "smallest":
        score = -score
    if init:
        score = jnp.where(b[None] < init_blocks, jnp.inf, score)
    # the topk candidates of largest score, ties to the smaller b: a stable
    # argsort of the negated scores, the candidates first
    order = jnp.argsort(jnp.where(cand, -score, jnp.inf), axis=-1,
                        stable=True)
    rank = jnp.argsort(order, axis=-1)
    chosen = cand & (rank < topk)
    return (chosen | is_local) if local else (chosen | (b[None] == bt))


def _softmax_mixer(a, h, *, n_head, n_kv_head, eps, sel, rope, gate,
                   kv_dtype, **wrong):
    t = h.shape[0]
    group = n_head // n_kv_head
    q = _heads(h @ a["q"]["kernel"], n_head)
    k = _heads(h @ a["k"]["kernel"], n_kv_head)
    v = _heads(h @ a["v"]["kernel"], n_kv_head)
    q = _rms_norm(a["q_norm"]["scale"], q, eps)
    k = _rms_norm(a["k_norm"]["scale"], k, eps)
    if rope:
        q, k = _rope_halves(q, 10000.0), _rope_halves(k, 10000.0)
    if kv_dtype is not None:
        # rounded to `kv_dtype` and held in float32: `reduce_precision`,
        # since XLA drops a cast there and back as excess precision
        kept = jnp.finfo(kv_dtype)
        k = jax.lax.reduce_precision(k, kept.nexp, kept.nmant)
        v = jax.lax.reduce_precision(v, kept.nexp, kept.nmant)
    d = q.shape[-1]
    block = sel[0]
    cols = jnp.arange(t)
    pad = -t % ROWS
    rows_all = jnp.arange(t + pad).reshape(-1, ROWS)

    def one_group(_, g):
        kg, vg = k[g], v[g]
        kc = _pooled_keys(kg, sel)
        qg = jax.lax.dynamic_slice_in_dim(q, g * group, group, axis=0)
        qg = jnp.pad(qg, ((0, 0), (0, pad), (0, 0)))

        def rows_block(_, rows):
            qr = qg[:, rows]  # (G, R, d)
            sets = _block_sets(qr, kc, t, rows, sel, **wrong)  # (R, nb)
            allowed = jnp.repeat(sets, block, axis=-1)[:, :t] \
                & (cols[None, :] <= rows[:, None])
            s = jnp.einsum("grd,sd->grs", qr, kg) / jnp.sqrt(jnp.float32(d))
            s = jnp.where(allowed[None], s, -jnp.inf)
            return None, jax.nn.softmax(s, axis=-1) @ vg  # (G, R, d)

        _, y = jax.lax.scan(rows_block, None, rows_all)  # (nR, G, R, d)
        return None, y.transpose(1, 0, 2, 3).reshape(group, -1, d)[:, :t]

    _, y = jax.lax.scan(one_group, None, jnp.arange(n_kv_head))
    y = y.reshape(n_head, t, d).transpose(1, 0, 2).reshape(t, -1)
    if gate:
        y = y * jax.nn.sigmoid(h @ a["gate"]["kernel"])
    return y @ a["o"]["kernel"]


def _linear_mixer(a, h, *, n_head, eps, rope, gate, decay, slopes, out_norm,
                  state_dtype):
    t = h.shape[0]
    q = _heads(h @ a["q"]["kernel"], n_head)
    k = _heads(h @ a["k"]["kernel"], n_head)
    v = _heads(h @ a["v"]["kernel"], n_head)
    q = _rms_norm(a["q_norm"]["scale"], q, eps)
    k = _rms_norm(a["k_norm"]["scale"], k, eps)
    if rope:
        q, k = _rope_halves(q, 10000.0), _rope_halves(k, 10000.0)
    d = q.shape[-1]
    s_h = 2.0 ** (-8.0 * jnp.arange(1, n_head + 1, dtype=jnp.float32)
                  / n_head)
    if slopes == "reversed":
        s_h = s_h[::-1]
    lam = jnp.exp(-s_h) if decay else jnp.ones_like(s_h)
    kept = jnp.finfo(state_dtype)

    def position(s, now):  # s (H, d, d): key channel x value channel
        q_t, k_t, v_t = now
        s = lam[:, None, None] * s + k_t[:, :, None] * v_t[:, None, :]
        # rounded to `state_dtype` and held in float32: `reduce_precision`,
        # since XLA drops a cast there and back as excess precision
        s = jax.lax.reduce_precision(s, kept.nexp, kept.nmant)
        return s, jnp.einsum("hc,hcv->hv", q_t, s) / jnp.sqrt(jnp.float32(d))

    _, o = jax.lax.scan(position, jnp.zeros((n_head, d, d), jnp.float32),
                        (q.transpose(1, 0, 2), k.transpose(1, 0, 2),
                         v.transpose(1, 0, 2)))  # (T, H, d)
    if out_norm:
        o = o * jax.lax.rsqrt((o * o).mean(-1, keepdims=True) + eps)
        y = o.reshape(t, -1) * a["o_norm"]["scale"]
    else:
        y = o.reshape(t, -1)
    if gate:
        y = y * jax.nn.sigmoid(h @ a["gate"]["kernel"])
    return y @ a["o"]["kernel"]


_STATIC = ("kind", "n_head", "n_kv_head", "lin_head", "eps", "r", "sel",
           "full_rope", "lin_rope", "gate", "decay", "slopes", "out_norm",
           "state_dtype", "kv_dtype", "pick", "local", "init", "early",
           "group_sum")


@functools.partial(jax.jit, static_argnames=_STATIC)
def layer(p, x, *, kind, n_head, n_kv_head, lin_head, eps, r, sel,
          full_rope=False, lin_rope=True, gate=True, decay=True,
          slopes="published", out_norm=True, state_dtype="float32",
          kv_dtype=None, pick="largest", local=True, init=True, early=False,
          group_sum=True):
    """One block, (T, C) -> (T, C); `kind` "full" (`minicpm4`) or "linear"
    (`lightning-attn`); `sel` = (block, topk, window, init_blocks, kernel,
    stride). The arguments past `sel` each set one thing wrong (module
    docstring)."""
    h = _rms_norm(p["ln_1"]["scale"], x, eps)
    if kind == "full":
        m = _softmax_mixer(
            p["attn"], h, n_head=n_head, n_kv_head=n_kv_head, eps=eps,
            sel=sel, rope=full_rope, gate=gate,
            kv_dtype=None if kv_dtype is None else jnp.dtype(kv_dtype),
            pick=pick, local=local, init=init, early=early,
            group_sum=group_sum)
    else:
        m = _linear_mixer(
            p["attn"], h, n_head=lin_head, eps=eps, rope=lin_rope, gate=gate,
            decay=decay, slopes=slopes, out_norm=out_norm,
            state_dtype=jnp.dtype(state_dtype))
    x = x + r * m
    return x + r * _swiglu(p["mlp"], _rms_norm(p["ln_2"]["scale"], x, eps))


def _scales(cfg):
    """(scale_emb, the head's divisor) as the program's config holds them."""
    return float(cfg.mup.embedding), 1.0 / float(cfg.mup.lm_head)


def embed(cfg, wte, ids, scale_emb=None):
    s = _scales(cfg)[0] if scale_emb is None else scale_emb
    return s * wte["embedding"][jnp.asarray(ids)]


@functools.partial(jax.jit, static_argnames=("eps", "div"))
def _head(ln_f, kernel, x, *, eps, div):
    return (_rms_norm(ln_f["scale"], x, eps) / div) @ kernel


def head(cfg, ln_f, kernel, x, head_div=None):
    return _head(ln_f, kernel, x, eps=float(cfg.rms_eps),
                 div=_scales(cfg)[1] if head_div is None else head_div)


def layer_args(cfg, i, **wrong):
    """The program's model config -> `layer`'s arguments for layer i;
    `wrong` overrides (the controls)."""
    m = cfg.block_select
    kw = dict(
        kind=cfg.layer_types[i], n_head=cfg.n_head, n_kv_head=cfg.n_kv_head,
        lin_head=cfg.lightning.n_head, eps=float(cfg.rms_eps),
        r=float(cfg.mup.attention_out),
        sel=(m.block, m.topk, m.window, m.init_blocks, m.kernel, m.stride))
    kw.update({k: v for k, v in wrong.items()
               if k not in ("scale_emb", "head_div")})
    return kw


def hidden(cfg, params, ids, **wrong):
    """(T,) ids of ONE sequence -> (T, C): the last block's output, before
    the final norm and the head."""
    x = embed(cfg, params["wte"], ids, wrong.get("scale_emb"))
    for i in range(cfg.n_layer):
        x = layer(params[f"h_{i}"], x, **layer_args(cfg, i, **wrong))
    return x


def forward(cfg, params, ids, rows=None, **wrong):
    """(T,) ids -> (T, vocab) float32 logits, or those of `rows` only."""
    x = hidden(cfg, params, ids, **wrong)
    if rows is not None:
        x = x[rows]
    return head(cfg, params["ln_f"], params["lm_head"]["kernel"], x,
                wrong.get("head_div"))


def logits(cfg, params, ids):
    """What the check calls in every reference module: (B, T) ids -> (B,
    T, vocab), one sequence at a time."""
    return jnp.stack([forward(cfg, params, row) for row in jnp.asarray(ids)])


def chosen_blocks(cfg, p, x, **wrong):
    """The sets of the softmax layer `p` over the sequence x (T, C): bool
    (KV, T, nb) — for the tests that hold the program's selection to a
    brute-force one."""
    kw = layer_args(cfg, 0, **wrong)
    a, eps = p["attn"], kw["eps"]
    h = _rms_norm(p["ln_1"]["scale"], x, eps)
    q = _rms_norm(a["q_norm"]["scale"],
                  _heads(h @ a["q"]["kernel"], cfg.n_head), eps)
    k = _rms_norm(a["k_norm"]["scale"],
                  _heads(h @ a["k"]["kernel"], cfg.n_kv_head), eps)
    group = cfg.n_head // cfg.n_kv_head
    rows = jnp.arange(x.shape[0])
    opts = {n: kw.get(n, v) for n, v in (
        ("pick", "largest"), ("local", True), ("init", True),
        ("early", False), ("group_sum", True))}
    return jnp.stack([
        _block_sets(q[g * group:(g + 1) * group],
                    _pooled_keys(k[g], kw["sel"]), x.shape[0], rows,
                    kw["sel"], **opts) for g in range(cfg.n_kv_head)])
