"""Multi-head causal self-attention.

The reference's GPT partitions delegate attention to a nanoGPT-style `Block`
imported from a `model.py` that is absent from its repo
(/root/reference/partitions/gpt_model_parts.py:4); this module re-authors
that math TPU-first:

  * one fused qkv projection (a single big MXU matmul),
  * attention computed per head via einsum (XLA maps these onto the MXU),
  * optional Pallas flash-attention kernel on TPU for long sequences
    (dnn_tpu/ops/pallas/flash_attention.py) with this jnp version as the
    numerically-identical fallback / ground truth.

Shapes: x is (B, T, C); params:
  {"qkv": {"kernel": (C, 3C), "bias": (3C,)},
   "proj": {"kernel": (C, C), "bias": (C,)}}
"""

from __future__ import annotations

import jax.numpy as jnp

from dnn_tpu.ops.nn import linear


def split_heads(x, n_head):
    b, t, c = x.shape
    return x.reshape(b, t, n_head, c // n_head).transpose(0, 2, 1, 3)  # (B, H, T, D)


def merge_heads(x):
    b, h, t, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b, t, h * d)


# "auto" switches on the Pallas flash kernel from this sequence length:
# XLA's path materializes (B,H,T,T) scores, which grow as T^2, and the
# flash kernel never holds them. A heuristic: no cell of the chip
# benchmark runs a forward this long, so the threshold is not measured.
FLASH_AUTO_THRESHOLD = 4096


def causal_self_attention(params, x, *, n_head, use_flash=False, compute_dtype=None):
    """Full causal MHA: fused qkv matmul -> per-head attention -> out proj.

    `use_flash`: True routes the inner attention through the Pallas TPU
    kernel (falls back to the jnp path off-TPU or for tiny shapes); False
    uses the XLA einsum path; "auto" picks flash when the sequence length
    reaches FLASH_AUTO_THRESHOLD (a heuristic — see above).
    `compute_dtype` (e.g. bf16) casts the matmul operands for the MXU.
    """
    qkv = linear(params["qkv"], x, compute_dtype=compute_dtype)  # (B, T, 3C)
    q, k, v = jnp.split(qkv, 3, axis=-1)
    q, k, v = (split_heads(t, n_head) for t in (q, k, v))

    if use_flash == "auto":
        use_flash = x.shape[-2] >= FLASH_AUTO_THRESHOLD  # static under jit

    # Single source of truth for the attention math: the flash kernel and
    # its jnp reference live in one module, so both paths share numerics.
    from dnn_tpu.ops.pallas.flash_attention import flash_attention, reference_attention

    if use_flash:
        y = flash_attention(q, k, v, causal=True)
    else:
        y = reference_attention(q, k, v, causal=True)

    y = merge_heads(y)
    return linear(params["proj"], y, compute_dtype=compute_dtype)


def rope_cos_sin(positions, head_dim, *, theta=10000.0, inv_freq=None):
    """cos/sin tables for rotary position embedding at absolute
    `positions` (any shape P...), HF half-split convention: frequencies
    1/theta^(2i/d) over the first half of the head dim — or `inv_freq`
    (head_dim / 2,), a scaling's own —, tables tiled to the full dim.
    Returns (cos, sin) of shape (*P, head_dim), f32."""
    if inv_freq is None:
        inv_freq = 1.0 / (theta ** (
            jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    angles = positions.astype(jnp.float32)[..., None] * inv_freq  # (*P, d/2)
    emb = jnp.concatenate([angles, angles], axis=-1)
    return jnp.cos(emb), jnp.sin(emb)


def apply_rope(x, cos, sin):
    """Rotate head vectors x (..., T, D) by per-position tables
    (T, D) — torch rotate_half convention: the two halves of the head dim
    form the rotation pairs (NOT interleaved even/odd lanes; matching HF
    weights requires matching this layout)."""
    d = x.shape[-1]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    rotated = jnp.concatenate([-x2, x1], axis=-1)
    return (x.astype(jnp.float32) * cos + rotated.astype(jnp.float32) * sin
            ).astype(x.dtype)
