"""GPT-2, plainly: the forward pass in straightforward `jax.numpy`, float32,
no kernels, no cache, no batching tricks, no layer scan. It follows the
published architecture (pre-LayerNorm blocks, fused qkv projection, causal
softmax attention, tanh-approximate GELU, learned positions, output head
tied to the token embedding at initialisation).

It reads the parameter tree of `dnn_tpu.models.gpt.init` ({"wte", "wpe",
"h_<i>", "ln_f", "lm_head"}; kernels stored (in, out)) because the weights
under test are made by the program from `--seed`; nothing else of the
program is used. Callers wrap it in
`jax.default_matmul_precision("highest")`: on a TPU a float32 matmul
otherwise runs in bfloat16 passes.

One block is one jitted call, applied layer by layer from Python, so a
36-layer model compiles one small program.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

__all__ = ["forward", "logits"]


def _layer_norm(p, x, eps):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        0.7978845608028654 * (x + 0.044715 * x ** 3)))


@functools.partial(jax.jit, static_argnames=("n_head", "eps"))
def _block(p, x, *, n_head, eps):
    b, t, c = x.shape
    d = c // n_head
    h = _layer_norm(p["ln_1"], x, eps)
    qkv = h @ p["attn"]["qkv"]["kernel"] + p["attn"]["qkv"]["bias"]
    q, k, v = (a.reshape(b, t, n_head, d).transpose(0, 2, 1, 3)
               for a in jnp.split(qkv, 3, axis=-1))
    scores = q @ k.transpose(0, 1, 3, 2) / jnp.sqrt(jnp.float32(d))
    causal = jnp.tril(jnp.ones((t, t), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    y = jax.nn.softmax(scores, axis=-1) @ v
    y = y.transpose(0, 2, 1, 3).reshape(b, t, c)
    x = x + y @ p["attn"]["proj"]["kernel"] + p["attn"]["proj"]["bias"]
    h = _layer_norm(p["ln_2"], x, eps)
    m = _gelu_tanh(h @ p["mlp"]["fc"]["kernel"] + p["mlp"]["fc"]["bias"])
    return x + m @ p["mlp"]["proj"]["kernel"] + p["mlp"]["proj"]["bias"]


@jax.jit
def _embed(wte, wpe, ids):
    return wte[ids] + wpe[jnp.arange(ids.shape[-1])]


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(ln_f, kernel, x, *, eps):
    return _layer_norm(ln_f, x, eps) @ kernel


def forward(params, ids, *, n_layer: int, n_head: int, eps: float = 1e-5):
    """(B, T) int32 ids -> (B, T, vocab) float32 logits."""
    x = _embed(params["wte"]["embedding"], params["wpe"]["embedding"], ids)
    for i in range(n_layer):
        x = _block(params[f"h_{i}"], x, n_head=n_head, eps=eps)
    return _head(params["ln_f"], params["lm_head"]["kernel"], x, eps=eps)


def logits(cfg, params, ids):
    """What `check.py` calls in every reference module: the program's
    model config (depth, heads, epsilon), its parameters, (B, T) ids."""
    return forward(params, ids, n_layer=cfg.n_layer, n_head=cfg.n_head,
                   eps=cfg.ln_eps)
