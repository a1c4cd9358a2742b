"""Core neural-net ops as pure functions over parameter pytrees.

TPU-first conventions:
  * Activations are NHWC and weights HWIO — the layouts XLA tiles best onto
    the TPU MXU (the reference is NCHW PyTorch; see
    /root/reference/cifar_model_parts.py:10-26 for the ops this module must
    be able to express).
  * Everything is a pure function of (params, x): jit/vmap/shard_map safe,
    no module objects, no Python-side state.
  * Matmul-bearing ops accept a `compute_dtype` so models can run bf16 on
    the MXU while keeping f32 params.

Parameter pytrees are plain dicts:
  conv2d:    {"kernel": (kh, kw, in_ch, out_ch), "bias": (out_ch,)}
  linear:    {"kernel": (in_features, out_features), "bias": (out_features,)}
  layer_norm:{"scale": (dim,), "bias": (dim,)}
  embedding: {"embedding": (vocab, dim)}
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def conv2d(params, x, *, stride=(1, 1), padding="SAME", compute_dtype=None):
    """2-D convolution, NHWC activations / HWIO kernel.

    Equivalent capability to torch nn.Conv2d as used by the reference CNN
    (/root/reference/cifar_model_parts.py:9,11 — k3 s1 p1 == SAME).
    """
    kernel = params["kernel"]
    if compute_dtype is not None:
        x = x.astype(compute_dtype)
        kernel = kernel.astype(compute_dtype)
    out = lax.conv_general_dilated(
        x,
        kernel,
        window_strides=stride,
        padding=padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )
    bias = params.get("bias")
    if bias is not None:
        out = out + bias.astype(out.dtype)
    return out


def max_pool2d(x, *, window=(2, 2), stride=(2, 2)):
    """Max pooling over spatial dims of an NHWC tensor.

    Reference: torch nn.MaxPool2d(kernel_size=2, stride=2, padding=0)
    (/root/reference/cifar_model_parts.py:10).
    """
    return lax.reduce_window(
        x,
        -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) else jnp.iinfo(x.dtype).min,
        lax.max,
        window_dimensions=(1, *window, 1),
        window_strides=(1, *stride, 1),
        padding="VALID",
    )


def linear(params, x, *, compute_dtype=None, accum_dtype=None):
    """Dense layer: x @ kernel + bias. kernel is (in, out) — already the
    layout XLA wants for an MXU matmul (torch stores (out, in); the
    checkpoint converter transposes — see dnn_tpu/io/checkpoint.py).

    `compute_dtype` casts the matmul operands (e.g. bf16 for the MXU) and
    casts the result back to the input dtype. `accum_dtype` instead keeps
    the accumulator dtype as the output (`preferred_element_type`) — e.g.
    compute_dtype=bf16 + accum_dtype=f32 reads bf16 operands but returns
    f32, the idiom for a logits head.

    Also accepts int8 weight-only-quantized params ({"q", "scale"} instead
    of {"kernel"} — see dnn_tpu/quant.py). Every matmul path in the
    framework (block forward, KV-cache decode, serving, pipeline stages)
    funnels through this function, so quantized checkpoints work
    everywhere without per-path plumbing.

    A `"lora"` entry ({a, b, sel} — built by lora.lora_view for
    per-request multi-adapter serving) adds the selected low-rank delta
    on top of whichever base path ran — float or quantized (the
    QLoRA-style combination: int8 base weights + per-slot float
    adapters).

    Reference: torch nn.Linear (/root/reference/cifar_model_parts.py:12-13).
    """
    lora = params.get("lora")
    if "q" in params:
        base = (_linear_int4 if params["q"].dtype == jnp.int4
                else _linear_int8)
        out = base(params, x, compute_dtype=compute_dtype,
                   accum_dtype=accum_dtype)
        if lora is not None:
            out = out + _lora_delta(lora, x, compute_dtype).astype(out.dtype)
        return out
    kernel = params["kernel"]
    orig_dtype = x.dtype
    if compute_dtype is not None:
        x = x.astype(compute_dtype)
        # a name for the device trace (chipbench/spans.py reads it): a
        # weight kept in another dtype is converted on every call, and
        # the compiler hoists the convert of a scanned stack out of the
        # layer loop, where nothing else says what it is
        with jax.named_scope("weights.cast"):
            kernel = kernel.astype(compute_dtype)
    if accum_dtype is not None:
        out = lax.dot_general(
            x, kernel,
            (((x.ndim - 1,), (0,)), ((), ())),
            preferred_element_type=accum_dtype,
        )
    else:
        out = x @ kernel
    bias = params.get("bias")
    if bias is not None:
        out = out + bias.astype(out.dtype)
    if lora is not None:
        out = out + _lora_delta(lora, x, compute_dtype).astype(out.dtype)
    if accum_dtype is None and compute_dtype is not None:
        out = out.astype(orig_dtype)
    return out


def _lora_delta(lora, x, compute_dtype):
    """Per-slot low-rank delta for multi-adapter serving (see
    lora.lora_view): x (B, T, C) against adapter stacks a (N, C, r) /
    b (N, r, O), selected per batch row by the one-hot sel (B, N).

    Computed for ALL N adapters then masked by sel — N x the (tiny)
    rank-r flops, but no gather of weight-sized operands and no dynamic
    shapes: the TPU-friendly trade at serving-realistic N. The one-hot
    contraction folds into each einsum, so what actually runs is two
    batched rank-r matmuls."""
    a, b, sel = lora["a"], lora["b"], lora["sel"]
    dt = compute_dtype if compute_dtype is not None else x.dtype
    sel = sel.astype(dt)
    xa = jnp.einsum("btc,ncr,bn->btr", x.astype(dt), a.astype(dt), sel)
    return jnp.einsum("btr,nro,bn->bto", xa, b.astype(dt), sel)


def _linear_int8(params, x, *, compute_dtype=None, accum_dtype=None):
    """Weight-only int8 dense layer: out = (x @ q) * scale + bias.

    `q` is the int8 kernel, `scale` the per-output-channel dequant factor
    (dnn_tpu/quant.py). The int8->compute_dtype convert fuses into the
    dot's operand read, so the kernel's HBM traffic is 1 byte/weight —
    the win this exists for: decode steps are weight-bandwidth-bound, so
    int8 weights roughly double decode throughput at large model sizes.
    Per-channel scales commute with the contraction, so scaling the
    *output* columns is exact (not an approximation of scaling weights).
    """
    q = params["q"]
    orig_dtype = x.dtype
    cd = compute_dtype if compute_dtype is not None else x.dtype
    acc = accum_dtype if accum_dtype is not None else cd
    out = lax.dot_general(
        x.astype(cd), q.astype(cd),
        (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=acc,
    )
    # scale is (..., 1, out); drop the kept contraction axis for broadcast
    out = out * params["scale"][..., 0, :].astype(acc)
    bias = params.get("bias")
    if bias is not None:
        out = out + bias.astype(out.dtype)
    if accum_dtype is None and compute_dtype is not None:
        out = out.astype(orig_dtype)
    return out


def _linear_int4(params, x, *, compute_dtype=None, accum_dtype=None):
    """Weight-only GROUP-WISE int4 dense layer (dnn_tpu/quant.py
    quantize_tensor_int4): q (in, out) native jnp.int4, scale
    (in/group, out) f32. Group scales do not commute with the full
    contraction, so the dot runs batched per group —
    out = sum_G (x_G @ q_G) * scale_G — which XLA lowers to one batched
    MXU matmul plus an epilogue multiply-and-reduce on the (small)
    per-group outputs; the s4->compute convert fuses into the operand
    read, so kernel HBM traffic is 0.5 bytes/weight. Stacked (L, ...)
    trees arrive here already layer-sliced by the blocks scan, exactly
    like the int8 path."""
    q, scale = params["q"], params["scale"]
    orig_dtype = x.dtype
    cd = compute_dtype if compute_dtype is not None else x.dtype
    acc = accum_dtype if accum_dtype is not None else cd
    in_dim, out_dim = q.shape[-2], q.shape[-1]
    g_count = scale.shape[-2]
    gsz = in_dim // g_count
    qg = q.reshape(g_count, gsz, out_dim)
    xg = x.reshape(*x.shape[:-1], g_count, gsz)
    out = jnp.einsum("...gi,gio->...go", xg.astype(cd), qg.astype(cd),
                     preferred_element_type=acc)
    out = (out * scale.astype(acc)).sum(axis=-2)
    bias = params.get("bias")
    if bias is not None:
        out = out + bias.astype(out.dtype)
    if accum_dtype is None and compute_dtype is not None:
        out = out.astype(orig_dtype)
    return out


def relu(x):
    return jax.nn.relu(x)


def gelu(x):
    """tanh-approximate GELU (the GPT-2 nonlinearity)."""
    return jax.nn.gelu(x, approximate=True)


def softmax(x, axis=-1):
    """Reference: torch nn.Softmax(dim=1) on (B, 10) logits
    (/root/reference/cifar_model_parts.py:15,25)."""
    return jax.nn.softmax(x, axis=axis)


def layer_norm(params, x, *, eps=1e-5):
    """LayerNorm over the last dim (torch nn.LayerNorm semantics, biased
    variance, as in GPT-2)."""
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mean) * lax.rsqrt(var + eps)
    y = y * params["scale"].astype(jnp.float32) + params["bias"].astype(jnp.float32)
    return y.astype(x.dtype)


def embedding(params, ids):
    """Token/position embedding lookup.

    Reference: torch nn.Embedding via wte/wpe
    (/root/reference/partitions/gpt_model_parts.py:9-10,16-18).
    """
    return jnp.take(params["embedding"], ids, axis=0)


def silu(x):
    """SiLU / swish (the LLaMA-family gate nonlinearity)."""
    return jax.nn.silu(x)


def rms_norm(params, x, *, eps=1e-6, plus_one=False):
    """RMSNorm over the last dim (LLaMA-family normalization: no mean
    subtraction, no bias — torch LlamaRMSNorm semantics, f32 statistics).

    `plus_one=True` scales by (1 + w) instead of w — the Gemma-family
    convention (torch GemmaRMSNorm), whose checkpoints store the scale
    as a zero-centered delta."""
    x32 = x.astype(jnp.float32)
    y = x32 * lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    scale = params["scale"].astype(jnp.float32)
    if plus_one:
        scale = 1.0 + scale
    return (y * scale).astype(x.dtype)
