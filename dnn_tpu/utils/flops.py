"""FLOPs accounting and MFU (model FLOPs utilization).

A rate says nothing about the hardware until it is set against the
chip's peak. This module supplies the accounting: analytic forward
FLOPs for the model families (matmuls + attention — the operations the MXU
executes; elementwise and gathers are noise at these shapes) and a peak-
FLOPs table per TPU generation, so the live gauges (obs/goodput.py,
obs/trainlens.py) can report
    mfu = achieved FLOPs/s / chip peak FLOPs/s.
The chip benchmark keeps its own copy of the peaks and the forward count
(chipbench/peaks.py), so that the yardstick does not move with the program.

Conventions (the standard MFU bookkeeping, e.g. the PaLM appendix):
  * a matmul (m, k) @ (k, n) costs 2*m*k*n FLOPs;
  * causal attention is charged the FULL T^2 score/value matmuls — that is
    what the dense einsum path executes, and it keeps MFU comparable with
    published numbers (flash kernels that skip masked tiles simply bank
    the savings as higher throughput at equal charged FLOPs);
  * training steps cost ~3x a forward (fwd + 2x bwd).
"""

from __future__ import annotations

from typing import Optional

import jax

# bf16 peak FLOPs/s per chip, by TPU generation. Matched as substrings of
# `jax.Device.device_kind` (e.g. "TPU v5 lite"); first hit wins, so more
# specific entries come first.
_TPU_PEAK_BF16 = (
    ("v5 lite", 197e12),   # v5e
    ("v5e", 197e12),
    ("v5p", 459e12),
    ("v6 lite", 918e12),   # Trillium
    ("v6e", 918e12),
    ("v4", 275e12),
    ("v3", 123e12),
    ("v2", 45e12),
)


def _tpu_peak(device: jax.Device, table, what: str) -> float:
    """Look `device.device_kind` up in a peak table. A TPU the table does
    not know is an ERROR, not None: a utilization against no peak — or a
    guessed one — must never reach a record of a chip run."""
    kind = device.device_kind.lower()
    for sub, peak in table:
        if sub in kind:
            return peak
    raise ValueError(
        f"no {what} peak for TPU device_kind {device.device_kind!r}; add "
        f"it (with its source) to the table in dnn_tpu/utils/flops.py")


def device_peak_flops(device: Optional[jax.Device] = None) -> Optional[float]:
    """bf16 peak FLOPs/s of `device` (default: the first default device).
    None off-TPU (a CPU host has no peak worth a utilization — callers
    omit the mfu field, and nothing in the environment can state one);
    an unrecognized TPU raises."""
    if device is None:
        device = jax.devices()[0]
    if device.platform != "tpu":
        return None
    return _tpu_peak(device, _TPU_PEAK_BF16, "bf16 FLOP/s")


def gpt_forward_flops(cfg, batch: int, seq: int) -> float:
    """Analytic forward FLOPs for one GPT batch (dnn_tpu/models/gpt.py
    layout): per layer 24*T*C^2 of linear matmuls (qkv 6TC^2 + attn proj
    2TC^2 + mlp 8TC^2 + 8TC^2) plus 4*T^2*C of attention score/value
    matmuls, plus the 2*T*C*V lm_head."""
    c, l, v = cfg.n_embd, cfg.n_layer, cfg.vocab_size
    per_seq = l * (24 * seq * c * c + 4 * seq * seq * c) + 2 * seq * c * v
    return float(batch) * per_seq


def llama_forward_flops(cfg, batch: int, seq: int) -> float:
    """Analytic forward FLOPs for one LLaMA batch
    (dnn_tpu/models/llama.py): per layer q 2TC^2 + k/v 2*2TC*(KV*D) +
    o 2TC^2 + SwiGLU 6TCF, plus the full-T^2 attention charge 4T^2C
    (GQA narrows the K/V PROJECTIONS and cache, not the score/value
    einsum FLOPs — every query head still attends), plus the 2TCV head."""
    c, l, v, f = cfg.n_embd, cfg.n_layer, cfg.vocab_size, cfg.d_ff
    kv_width = cfg.n_kv_head * cfg.head_dim
    per_seq = l * (2 * seq * c * c            # q proj
                   + 2 * 2 * seq * c * kv_width  # k + v projs
                   + 2 * seq * c * c          # o proj
                   + 6 * seq * c * f          # gate + up + down
                   + 4 * seq * seq * c)       # attention score/value
    return float(batch) * (per_seq + 2 * seq * c * v)


# ----------------------------------------------------------------------
# serving-shape accounting (dnn_tpu/obs/goodput.py): one DECODED token's
# FLOPs and HBM bytes. Decode runs T=1 forwards against a live cache, so
# the per-token cost depends on the CONTEXT (cache positions attended),
# not on a full-sequence T^2 charge — these helpers price what the decode
# program actually executes, which is what live MFU/MBU must divide by.
# ----------------------------------------------------------------------

def gpt_param_count(cfg) -> float:
    """Analytic parameter count of the GPT family (models/gpt.py layout:
    wte V*C + wpe block*C + per layer qkv 3C^2 + attn proj C^2 + mlp
    8C^2 + biases/norms ~4C, + lm_head V*C materialized untied + ln_f).
    Within ~0.1% of the real tree at gpt2 shapes — close enough for the
    weight-streaming MBU denominator."""
    c, l, v = cfg.n_embd, cfg.n_layer, cfg.vocab_size
    per_layer = 12 * c * c + 13 * c  # qkv/proj/mlp kernels + their biases
    # + 2 layernorms (scale+bias)
    return float(v * c + cfg.block_size * c + l * per_layer
                 + 2 * c            # ln_f
                 + v * c)           # lm_head (materialized even when tied)


def llama_param_count(cfg) -> float:
    """Analytic parameter count of the LLaMA family (models/llama.py):
    embed V*C + per layer q C*(H*D) + k/v 2*C*(KV*D) + o (H*D)*C +
    SwiGLU 3*C*F + 2 RMSNorm scales, + final norm + lm_head (absent when
    tie_word_embeddings)."""
    c, l, v, f = cfg.n_embd, cfg.n_layer, cfg.vocab_size, cfg.d_ff
    q_width = cfg.n_head * cfg.head_dim
    kv_width = cfg.n_kv_head * cfg.head_dim
    per_layer = (c * q_width + 2 * c * kv_width + q_width * c
                 + 3 * c * f + 2 * c)
    head = 0 if getattr(cfg, "tie_word_embeddings", False) else v * c
    return float(v * c + l * per_layer + c + head)


def gpt_decode_token_flops(cfg, context: float) -> float:
    """FLOPs to decode ONE token with `context` live cache positions: the
    T=1 forward's linear matmuls (24*C^2 per layer: qkv 6C^2 + proj 2C^2
    + mlp 16C^2, the 2*m*k*n convention at m=1) plus the score/value
    matmuls against the cache (4*context*C per layer) plus the 2*C*V
    head. This is what the decode program executes — the live-MFU
    numerator, NOT the full-T^2 prefill charge."""
    c, l, v = cfg.n_embd, cfg.n_layer, cfg.vocab_size
    return l * (24.0 * c * c + 4.0 * context * c) + 2.0 * c * v


def llama_decode_token_flops(cfg, context: float) -> float:
    """LLaMA-family decode-token FLOPs at `context` live positions:
    q/o 2C*(H*D) each, k/v 2*C*(KV*D) each, SwiGLU 6*C*F, attention
    4*context*(H*D) (every query head attends the full context — GQA
    narrows the cache, not the score/value FLOPs), + the 2*C*V head."""
    c, l, v, f = cfg.n_embd, cfg.n_layer, cfg.vocab_size, cfg.d_ff
    q_width = cfg.n_head * cfg.head_dim
    kv_width = cfg.n_kv_head * cfg.head_dim
    per_layer = (2.0 * c * q_width + 2.0 * 2.0 * c * kv_width
                 + 2.0 * q_width * c + 6.0 * c * f
                 + 4.0 * context * q_width)
    return l * per_layer + 2.0 * c * v


def tree_weight_bytes_by_dtype(tree) -> dict:
    """{dtype name: HBM bytes} of a parameter pytree's array leaves,
    priced at the DEVICE layout: int8 at 1 byte/element (quantized
    kernels), int4/uint4 at their packed HALF byte (host numpy views pad
    to one byte, so a dtype.itemsize walk would overstate the
    weight-streaming MBU denominator 2x for int4 trees), bfloat16 at 2
    (matmul operands a serving tree holds in the compute dtype). The f32
    scale rows quantized trees carry are counted at full width — they
    stream with the weights every decode step."""
    out: dict = {}
    for leaf in jax.tree_util.tree_leaves(tree):
        dt = getattr(leaf, "dtype", None)
        if dt is None:
            continue
        name = getattr(dt, "name", str(dt))
        per = 0.5 if name in ("int4", "uint4") else dt.itemsize
        out[name] = out.get(name, 0.0) + leaf.size * per
    return out


def tree_weight_bytes(tree) -> float:
    """Total HBM bytes of a parameter pytree's array leaves
    (`tree_weight_bytes_by_dtype`, summed). This is THE weight-bytes
    accounting the serving goodput gauges use (obs/goodput.model_cost),
    so an LMServer(weights="int8") daemon's MBU prices its quantized
    stream correctly instead of flattering itself with f32 bytes."""
    return float(sum(tree_weight_bytes_by_dtype(tree).values()))


def kv_bytes_per_pos(cfg, *, kv_bytes: float = 2,
                     kv_dtype=None) -> float:
    """HBM bytes one cache POSITION occupies (K + V rows across all
    layers) — decode streams `context` of these per token, and prefill
    writes one per prompt position. GQA caches carry n_kv_head*head_dim
    per row; dense GPT carries C.

    `kv_dtype` overrides `kv_bytes` with EXACT accounting for the
    serving cache specs (runtime/kvcache.py): a dtype prices at its
    itemsize; the codec strings "int8"/"int4" price the quantized
    payload (int4 packs two elements per byte — pricing it at the
    1-byte host itemsize would overstate the MBU denominator 2x) PLUS
    the per-(position, head) f32 K and V scale rows the quantized
    codecs stream alongside."""
    kv_width = (cfg.n_kv_head * cfg.head_dim
                if hasattr(cfg, "n_kv_head") else cfg.n_embd)
    heads = (cfg.n_kv_head if hasattr(cfg, "n_kv_head") else cfg.n_head)
    if kv_dtype is not None:
        name = str(getattr(kv_dtype, "name", kv_dtype))
        if name in ("int8", "int4"):
            per_elem = 1.0 if name == "int8" else 0.5
            return float(2 * cfg.n_layer
                         * (kv_width * per_elem + heads * 4))
        import jax.numpy as jnp

        kv_bytes = jnp.dtype(kv_dtype).itemsize
    return float(2 * cfg.n_layer * kv_width * kv_bytes)


def _train_step_factor(batch: int, accum_steps: int, remat: bool) -> float:
    """The forward→train-step multiplier (the PaLM-appendix bookkeeping):
    3x a forward (fwd + backward's two matmuls per forward matmul), 4x
    under full rematerialization (the backward replays the forward).
    Microbatch accumulation does not change TOTAL step FLOPs — the
    forward is linear in batch, so `accum_steps` microbatches of B/a
    rows cost exactly one batch-B pass — but the divisibility check
    here catches the same misconfiguration make_train_step rejects, so
    the priced shape and the executed shape cannot drift apart."""
    if accum_steps < 1:
        raise ValueError(f"accum_steps must be >= 1, got {accum_steps}")
    if batch % accum_steps:
        raise ValueError(
            f"batch {batch} not divisible by accum_steps {accum_steps}")
    return 4.0 if remat else 3.0


def gpt_train_step_flops(cfg, batch: int, seq: int, *,
                         accum_steps: int = 1, remat: bool = False) -> float:
    """Training-step FLOPs for one GPT batch: factor x forward (3x, or
    4x with remat — the backward replays the forward). `accum_steps`
    validates the microbatch split but leaves the total unchanged
    (forward FLOPs are linear in batch). The trainlens MFU numerator
    (obs/trainlens.py) prices from this walk."""
    return _train_step_factor(batch, accum_steps, remat) \
        * gpt_forward_flops(cfg, batch, seq)


def llama_train_step_flops(cfg, batch: int, seq: int, *,
                           accum_steps: int = 1, remat: bool = False) -> float:
    """Training-step FLOPs for one LLaMA batch — same factor bookkeeping
    as gpt_train_step_flops over the GQA/SwiGLU forward walk."""
    return _train_step_factor(batch, accum_steps, remat) \
        * llama_forward_flops(cfg, batch, seq)


def mfu(flops_per_item: float, items_per_sec: float,
        device: Optional[jax.Device] = None) -> Optional[float]:
    """Achieved-FLOPs / peak, or None off-TPU. `flops_per_item` is the
    analytic cost of one benchmark item (an image, a token's share of a
    batch, ...); items_per_sec the measured rate."""
    peak = device_peak_flops(device)
    if peak is None:
        return None
    return flops_per_item * items_per_sec / peak


# HBM peak bandwidth (bytes/s) per chip, by TPU generation — same matching
# scheme as the FLOPs table. Decode throughput is bounded by this number,
# not by peak FLOPs (every generated token streams the weights + KV cache
# from HBM once), so decode rows report MBU, not MFU.
_TPU_PEAK_HBM = (
    ("v5 lite", 819e9),    # v5e: 819 GB/s
    ("v5e", 819e9),
    ("v5p", 2765e9),
    ("v6 lite", 1640e9),   # Trillium
    ("v6e", 1640e9),
    ("v4", 1228e9),
    ("v3", 900e9),
    ("v2", 700e9),
)


def device_peak_hbm_bw(device: Optional[jax.Device] = None) -> Optional[float]:
    """HBM peak bytes/s of `device`: None off-TPU, an error for a TPU the
    table does not know."""
    if device is None:
        device = jax.devices()[0]
    if device.platform != "tpu":
        return None
    return _tpu_peak(device, _TPU_PEAK_HBM, "HBM bytes/s")


def mbu(bytes_per_item: float, items_per_sec: float,
        device: Optional[jax.Device] = None) -> Optional[float]:
    """Memory-bandwidth utilization: achieved bytes/s / HBM peak, or None
    off-TPU. For decode, `bytes_per_item` is the bytes one generated token
    must stream (weights/batch + its rows of the KV cache) — the roofline
    that decides whether int8 weights/cache pay off."""
    peak = device_peak_hbm_bw(device)
    if peak is None:
        return None
    return bytes_per_item * items_per_sec / peak
