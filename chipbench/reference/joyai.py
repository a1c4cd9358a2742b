"""JoyAI-LLM-Flash, plainly: one chip's share of the forward pass in
straightforward `jax.numpy`, float32, no kernels, no cache, no paging, no
batching, no absorption of one matrix into another, no sorting or grouping
of rows, no scan over layers.

The block (jdopensource/JoyAI-LLM-Flash `config.json`, `model_type`
joyai_llm_flash: the DeepSeek-V3 decoder as HF `DeepseekV3` computes it;
x is (T, C), t a query position, s <= t a key position, 32 heads):

  1. h = RMSNorm(x). Queries: c_q = RMSNorm(h W_qa) (1536); [q_nope |
     q_rope] = c_q W_qb, a head 128 | 64.
  2. Keys and values: [c_raw | k_raw] = h W_kva (512 | 64); c =
     RMSNorm(c_raw); [k_nope | v] = c W_kvb, a head 128 | 128 — EVERY
     position's k_nope and v are materialised here; k_rope = RoPE(k_raw),
     one vector for all the heads.
  3. RoPE (theta 32e6, `rope_interleave` true) rotates the pairs (2i,
     2i + 1) of the 64 by position * theta^(-2i/64), on q_rope and k_raw.
  4. s[t, s'] = (q_nope[t] . k_nope[s'] + q_rope[t] . k_rope[s']) /
     sqrt(192), the full (T, T) scores a head, causal softmax, o = P v,
     y = x + concat(o) W_o (4096 -> 2048). `rope_scaling` is null: no
     mscale on the softmax scale.
  5. Layer 0: out = y + SwiGLU_7168(RMSNorm(y)). Layers 1..: h2 =
     RMSNorm(y); s = sigmoid(h2 W_g) over ALL the layer's 256 experts; the
     8 largest of s + b (b = `e_score_correction_bias`; `n_group` =
     `topk_group` = 1, so the grouped top-k is the plain one); w =
     s[picked] / (sum of s[picked] + 1e-20) x 2.5 — the bias moves the
     pick and never enters a weight; out = y + sum of w_e E_e(h2) + S(h2),
     E and S SwiGLU of width 768, S (the shared expert) with no gate.
  Final RMSNorm, untied head.

The held range: the deployment this reference describes divides each
expert layer's 256 experts among chips; `held = (first, count)` says which
this chip holds, and `params` carries those experts' matrices only (the
router, its bias and the shared expert are whole). Every held expert is
computed for EVERY token and weighted by that token's weight for it — zero
unless it is among the token's eight. What the experts held elsewhere
would add is left out, and the partial result goes on to the next layer,
exactly as the program under test does. With held = (0, all) this is the
whole model.

Departures from the published description, each with its reason:
  * the multi-token-prediction module (`num_nextn_predict_layers` 1) is
    not here: it is a 41st block that drafts the token after next, enters
    neither the 40 layers' forward pass nor the next token's logits, and
    the public inference code drops it;
  * HF's interleaved RoPE first permutes q_rope and k_raw to the
    half-split layout and rotates there; the rotation below is in place,
    on the pairs (2i, 2i + 1). The permutation is the same on both sides
    of every dot product, so the scores are equal;
  * attention runs one head at a time (a loop, written as a scan so the
    block compiles one head's body): (32, T, T) scores at T = 12 864 would
    be 21 GB. The sums are the same;
  * everything is float32, so no cast of the routing weights.

It reads the parameter tree of `dnn_tpu.models.llama_moe.init` ({"wte",
"h_<i>": {"ln_1", "attn": {"q_a", "q_a_norm", "q_b", "kv_a", "kv_a_norm",
"kv_b", "o"}, "ln_2", "mlp": {"gate", "up", "down"} (layer 0) or "moe":
{"router": {"kernel", "select_bias"}, "wg", "wu", "wd", "shared": {"gate",
"up", "down"}}}, "ln_f", "lm_head"}; kernels stored (in, out), `kv_b`'s
columns a head's [k_nope | v], expert stacks expert-major) because the
weights under test are made by the program from `--seed`; nothing else of
the program is used. Callers wrap it in
`jax.default_matmul_precision("highest")`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

__all__ = ["hidden", "forward", "logits", "layer"]


def _rms_norm(scale, x, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _rope_pairs(x, theta):
    """x (..., T, d) with T second to last: rotate the pairs (2i, 2i + 1)
    by position * theta^(-2i/d)."""
    t, d = x.shape[-2:]
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angles), jnp.sin(angles)  # (T, d/2)
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     axis=-1).reshape(x.shape)


def _swiglu(p, h):
    return (jax.nn.silu(h @ p["gate"]["kernel"]) * (h @ p["up"]["kernel"])
            ) @ p["down"]["kernel"]


def _attention(a, h, *, n_head, nope, rope, eps, theta):
    t = h.shape[0]
    c_q = _rms_norm(a["q_a_norm"]["scale"], h @ a["q_a"]["kernel"], eps)
    q = (c_q @ a["q_b"]["kernel"]).reshape(t, n_head, nope + rope)
    q = q.transpose(1, 0, 2)  # (H, T, 192)
    q_nope, q_rope = q[..., :nope], _rope_pairs(q[..., nope:], theta)
    kv = h @ a["kv_a"]["kernel"]
    rank = kv.shape[-1] - rope
    c = _rms_norm(a["kv_a_norm"]["scale"], kv[:, :rank], eps)
    k_rope = _rope_pairs(kv[:, rank:], theta)  # (T, 64): one for all heads
    up = (c @ a["kv_b"]["kernel"]).reshape(t, n_head, -1).transpose(1, 0, 2)
    k_nope, v = up[..., :nope], up[..., nope:]  # (H, T, 128) each
    causal = jnp.tril(jnp.ones((t, t), bool))

    def one_head(_, head):
        qn, qr, kn, vh = head
        s = (qn @ kn.T + qr @ k_rope.T) / jnp.sqrt(jnp.float32(nope + rope))
        s = jnp.where(causal, s, -jnp.inf)
        return None, jax.nn.softmax(s, axis=-1) @ vh

    _, y = jax.lax.scan(one_head, None, (q_nope, q_rope, k_nope, v))
    return y.transpose(1, 0, 2).reshape(t, -1) @ a["o"]["kernel"]


def _experts(p, h, *, top_k, first, scale):
    """(T, C) -> (T, C): every HELD expert on every token, weighted by the
    token's weight for it (zero unless among its top_k of ALL the
    experts), and the shared expert, which every chip computes alike."""
    n_expert = p["router"]["kernel"].shape[-1]
    scores = jax.nn.sigmoid(h @ p["router"]["kernel"])  # (T, E)
    _, idx = jax.lax.top_k(scores + p["router"]["select_bias"], top_k)
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


_STATIC = ("n_head", "nope", "rope", "eps", "theta", "top_k", "first",
           "scale", "shared")


@functools.partial(jax.jit, static_argnames=_STATIC)
def layer(p, x, *, n_head, nope, rope, eps, theta, top_k, first, scale,
          shared=True):
    """One block, (T, C) -> (T, C). `shared` False leaves the shared
    expert out (the shares test counts it once)."""
    h = _rms_norm(p["ln_1"]["scale"], x, eps)
    x = x + _attention(p["attn"], h, n_head=n_head, nope=nope, rope=rope,
                       eps=eps, theta=theta)
    h = _rms_norm(p["ln_2"]["scale"], x, eps)
    if "mlp" in p:  # a leading dense layer
        return x + _swiglu(p["mlp"], h)
    routed, common = _experts(p["moe"], h, top_k=top_k, first=first,
                              scale=scale)
    return x + routed + (common if shared else 0.0)


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(ln_f, kernel, x, *, eps):
    return _rms_norm(ln_f["scale"], x, eps) @ kernel


def _kw(cfg, held):
    """The program's model config -> this module's arguments; `held` =
    (first, count), the config's own range when None. `params` carries
    `count` experts a layer."""
    m = cfg.mla
    return dict(n_head=cfg.n_head, nope=m.qk_nope_head_dim,
                rope=m.qk_rope_head_dim, eps=float(cfg.rms_eps),
                theta=float(cfg.rope_theta), top_k=cfg.router_top_k,
                first=int(cfg.experts_first) if held is None
                else int(held[0]), scale=float(cfg.router.scale))


def hidden(cfg, params, ids, held=None):
    """(T,) ids of ONE sequence -> (T, C): the last block's output, before
    the final norm and the head."""
    x = params["wte"]["embedding"][jnp.asarray(ids)]
    for i in range(cfg.n_layer):
        x = layer(params[f"h_{i}"], x, **_kw(cfg, held))
    return x


def forward(cfg, params, ids, rows=None, held=None):
    """(T,) ids -> (T, vocab) float32 logits, or those of `rows` only (the
    head on 16 k positions x 129 280 words is 8.5 GB)."""
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
