"""OLMoE, plainly: the forward pass of `OlmoeForCausalLM` in straightforward
`jax.numpy`, float32, no kernels, no cache, no sorting or grouping of rows,
no scan over layers. It follows the published module (HF `modeling_olmoe.py`):
pre-RMSNorm blocks; separate q/k/v projections without biases; RMSNorm over
the WHOLE projection width on q and on k before the head split; rotary
position embedding in the rotate-half layout; causal softmax attention over
16 heads of 128; and in every layer a router (softmax over all 64 experts
in float32, the top 8, their RAW probabilities as weights —
`norm_topk_prob` false) in front of 64 SwiGLU experts of width 1024; a final
RMSNorm and an untied output head.

EVERY expert is computed densely for EVERY token, in a loop over the
experts, and masked by that token's eight probabilities (zero elsewhere) —
64/8 times the work the model asks for, and nothing in common with how
the program under test dispatches.

Departures from the HF module, each with its reason:
  * HF computes an expert only on the tokens routed to it (`index_add_`);
    here every expert sees every token and a zero weight removes it. The
    sums are the same up to float32 summation order.
  * HF casts the routing weights to the hidden dtype; here everything is
    float32, so there is no cast.
  * `clip_qkv` is null in the published config and is not implemented.
  * HF batches sequences; here `logits` maps over them one at a time, so
    one (heads, T, T) score matrix is held at once (1 GB at T = 4096).

It reads the parameter tree of `dnn_tpu.models.llama_moe.init` ({"wte",
"h_<i>": {"ln_1", "attn": {"q", "k", "v", "o", "q_norm", "k_norm"}, "ln_2",
"moe": {"router", "wg", "wu", "wd"}}, "ln_f", "lm_head"}; kernels stored
(in, out), expert stacks expert-major) because the weights under test are
made by the program from `--seed`; nothing else of the program is used.
Callers wrap it in `jax.default_matmul_precision("highest")`: on a TPU a
float32 matmul otherwise runs in bfloat16 passes.

One block is one jitted call, applied layer by layer from Python.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

__all__ = ["forward", "logits"]


def _rms_norm(scale, x, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x (heads, T, d): rotate the pairs (i, i + d/2) of each head vector
    by position * theta^(-2i/d) — HF `apply_rotary_pos_emb`."""
    _, t, d = x.shape
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.concatenate([jnp.cos(angles)] * 2, axis=-1)
    sin = jnp.concatenate([jnp.sin(angles)] * 2, axis=-1)
    rotated = jnp.concatenate([-x[..., d // 2:], x[..., : d // 2]], axis=-1)
    return x * cos + rotated * sin


def _experts(p, h, top_k):
    """(T, C) -> (T, C): every expert on every token, weighted."""
    n_expert = p["router"]["kernel"].shape[-1]
    probs = jax.nn.softmax(h @ p["router"]["kernel"], axis=-1)  # (T, E)
    top, idx = jax.lax.top_k(probs, top_k)
    # (T, E): a token's probability for its top_k experts, else zero
    weights = (jax.nn.one_hot(idx, n_expert) * top[..., None]).sum(1)

    def one_expert(out, expert):  # its three matrices, its column of weights
        wg, wu, wd, w = expert
        return out + w[:, None] * ((jax.nn.silu(h @ wg) * (h @ wu)) @ wd), None

    # a loop over the experts, written as a scan so that the block
    # compiles one expert's body and not sixty-four
    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(h),
                          (p["wg"], p["wu"], p["wd"], weights.T))
    return out


@functools.partial(jax.jit,
                   static_argnames=("n_head", "eps", "theta", "top_k"))
def _block(p, x, *, n_head, eps, theta, top_k):
    t, c = x.shape
    d = c // n_head
    a = p["attn"]
    h = _rms_norm(p["ln_1"]["scale"], x, eps)
    # q/k norm over the whole projection, before the heads are split
    q = _rms_norm(a["q_norm"]["scale"], h @ a["q"]["kernel"], eps)
    k = _rms_norm(a["k_norm"]["scale"], h @ a["k"]["kernel"], eps)
    v = h @ a["v"]["kernel"]
    q, k, v = (m.reshape(t, n_head, d).transpose(1, 0, 2) for m in (q, k, v))
    q, k = _rope(q, theta), _rope(k, theta)
    scores = q @ k.transpose(0, 2, 1) / jnp.sqrt(jnp.float32(d))
    scores = jnp.where(jnp.tril(jnp.ones((t, t), bool)), scores, -jnp.inf)
    y = jax.nn.softmax(scores, axis=-1) @ v
    x = x + y.transpose(1, 0, 2).reshape(t, c) @ a["o"]["kernel"]
    h = _rms_norm(p["ln_2"]["scale"], x, eps)
    return x + _experts(p["moe"], h, top_k)


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(ln_f, kernel, x, *, eps):
    return _rms_norm(ln_f["scale"], x, eps) @ kernel


def forward(params, ids, *, n_layer, n_head, eps, theta, top_k):
    """(T,) int32 ids of ONE sequence -> (T, vocab) float32 logits."""
    x = params["wte"]["embedding"][ids]
    for i in range(n_layer):
        x = _block(params[f"h_{i}"], x, n_head=n_head, eps=eps, theta=theta,
                   top_k=top_k)
    return _head(params["ln_f"], params["lm_head"]["kernel"], x, eps=eps)


def logits(cfg, params, ids):
    """What the check calls in every reference module: the program's model
    config (depth, heads, epsilon, rotary base, experts per token), its
    parameters, (B, T) ids -> (B, T, vocab), one sequence at a time."""
    return jnp.stack([
        forward(params, row, n_layer=cfg.n_layer, n_head=cfg.n_head,
                eps=float(cfg.rms_eps), theta=float(cfg.rope_theta),
                top_k=cfg.router_top_k)
        for row in jnp.asarray(ids)])
