"""GPT-2 model family, TPU-native.

The reference ships GPT partition wrappers
(/root/reference/partitions/gpt_model_parts.py) over a nanoGPT-style
`GPT/GPTConfig/Block` imported from a `model.py` that is ABSENT from its
repo (gpt_model_parts.py:4) — so this module re-authors the base model from
the standard GPT-2 architecture (the reference survey mandates this:
SURVEY.md §7g), weight-compatible with HuggingFace GPT-2 checkpoints via
the converter in dnn_tpu/io/checkpoint.py.

Partitioning mirrors the reference's three wrapper classes:
  * first stage  = wte + wpe + blocks[0..k]      (ModelPart0, :6-22)
  * middle stage = blocks[i..j]                  (ModelPartIntermediate, :26-34)
  * final stage  = blocks[..] + ln_f + lm_head   (ModelPartFinal_GPT, :36-50)
and generalizes to any num_parts <= n_layer.

TPU-first choices (vs a torch translation):
  * params are a flat dict keyed by stage-sliceable units
    ({"wte","wpe","h_0".."h_{L-1}","ln_f","lm_head"});
  * blocks are a single pure function -> stacked-params `lax.scan` over
    layers inside a stage (one compiled block body, MXU-friendly);
  * bf16 compute / f32 params via `compute_dtype`;
  * optional Pallas flash attention for long sequences.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from dnn_tpu.ops.attention import causal_self_attention
from dnn_tpu.ops.nn import embedding, gelu, layer_norm, linear
from dnn_tpu.registry import ModelSpec, StageSpec, register_model


@dataclasses.dataclass(frozen=True)
class GPTConfig:
    """Mirrors the nanoGPT GPTConfig the reference depends on
    (gpt_model_parts.py:4,15 uses config.block_size)."""

    block_size: int = 1024
    vocab_size: int = 50257
    n_layer: int = 12
    n_head: int = 12
    n_embd: int = 768
    ln_eps: float = 1e-5


PRESETS = {
    "gpt2": GPTConfig(n_layer=12, n_head=12, n_embd=768),
    "gpt2-medium": GPTConfig(n_layer=24, n_head=16, n_embd=1024),
    "gpt2-large": GPTConfig(n_layer=36, n_head=20, n_embd=1280),
    "gpt2-xl": GPTConfig(n_layer=48, n_head=25, n_embd=1600),
    # long-context variants (train-from-scratch; the classic presets cap
    # block_size at GPT-2's 1024, below the flash-attention auto crossover —
    # these are the configs where use_flash="auto" engages the Pallas
    # kernel and where the seq-parallel ring is worth its collectives)
    "gpt2-4k": GPTConfig(block_size=4096, n_layer=12, n_head=12, n_embd=768),
    "gpt2-8k": GPTConfig(block_size=8192, n_layer=12, n_head=12, n_embd=768),
    # tiny config for tests / CPU-mesh CI
    "gpt2-test": GPTConfig(block_size=64, vocab_size=256, n_layer=4, n_head=4, n_embd=64),
}


# --------------------------------------------------------------------------
# init
# --------------------------------------------------------------------------

def _normal(key, shape, dtype, std=0.02):
    return (jax.random.normal(key, shape) * std).astype(dtype)


def init_block(key, cfg: GPTConfig, dtype=jnp.float32):
    c = cfg.n_embd
    ks = jax.random.split(key, 4)
    # GPT-2 scales residual-projection init by 1/sqrt(2*n_layer).
    proj_std = 0.02 / (2 * cfg.n_layer) ** 0.5
    return {
        "ln_1": {"scale": jnp.ones((c,), dtype), "bias": jnp.zeros((c,), dtype)},
        "attn": {
            "qkv": {"kernel": _normal(ks[0], (c, 3 * c), dtype), "bias": jnp.zeros((3 * c,), dtype)},
            "proj": {"kernel": _normal(ks[1], (c, c), dtype, proj_std), "bias": jnp.zeros((c,), dtype)},
        },
        "ln_2": {"scale": jnp.ones((c,), dtype), "bias": jnp.zeros((c,), dtype)},
        "mlp": {
            "fc": {"kernel": _normal(ks[2], (c, 4 * c), dtype), "bias": jnp.zeros((4 * c,), dtype)},
            "proj": {"kernel": _normal(ks[3], (4 * c, c), dtype, proj_std), "bias": jnp.zeros((c,), dtype)},
        },
    }


def init(rng, cfg: GPTConfig = PRESETS["gpt2"], dtype=jnp.float32, tie_lm_head=True):
    keys = jax.random.split(rng, cfg.n_layer + 3)
    c = cfg.n_embd
    params = {
        "wte": {"embedding": _normal(keys[0], (cfg.vocab_size, c), dtype)},
        "wpe": {"embedding": _normal(keys[1], (cfg.block_size, c), dtype, std=0.01)},
        "ln_f": {"scale": jnp.ones((c,), dtype), "bias": jnp.zeros((c,), dtype)},
    }
    for i in range(cfg.n_layer):
        params[f"h_{i}"] = init_block(keys[2 + i], cfg, dtype)
    # GPT-2 ties lm_head to wte; we materialize the tied weight under its own
    # key so pipeline stages stay cleanly sliceable (the reference's final
    # stage likewise carries original_model.lm_head — gpt_model_parts.py:42).
    params["lm_head"] = {
        "kernel": params["wte"]["embedding"].T if tie_lm_head else _normal(keys[-1], (c, cfg.vocab_size), dtype)
    }
    return params


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------

def _block_core(block_params, x, attn_fn, *, cfg: GPTConfig, compute_dtype=None):
    """Pre-LN transformer block with a pluggable attention implementation
    (local causal MHA, Pallas flash, or sequence-parallel ring).

    The named_scopes are trace-time only (zero runtime cost post-compile):
    they ride into XLA op metadata so device profiles (POST /profilez,
    dnn_tpu/obs/profile.py) name attention vs MLP instead of fused-op soup."""
    with jax.named_scope("gpt.block.attn"):
        h = layer_norm(block_params["ln_1"], x, eps=cfg.ln_eps)
        x = x + attn_fn(block_params["attn"], h)
    with jax.named_scope("gpt.block.mlp"):
        h = layer_norm(block_params["ln_2"], x, eps=cfg.ln_eps)
        m = linear(
            block_params["mlp"]["proj"],
            gelu(linear(block_params["mlp"]["fc"], h, compute_dtype=compute_dtype)),
            compute_dtype=compute_dtype,
        )
    return x + m


def block_apply(block_params, x, *, cfg: GPTConfig, use_flash=False, compute_dtype=None):
    """Pre-LN transformer block (nanoGPT Block semantics). With
    `compute_dtype=bf16`, every matmul runs bf16 on the MXU while residuals
    and layer norms stay in the activation dtype."""
    return _block_core(
        block_params, x,
        lambda ap, h: causal_self_attention(
            ap, h, n_head=cfg.n_head, use_flash=use_flash, compute_dtype=compute_dtype
        ),
        cfg=cfg, compute_dtype=compute_dtype,
    )


def stack_blocks(params, layer_ids):
    """Stack per-layer block params along a leading axis (for lax.scan over
    layers, and for sharding the stack over a pipeline mesh axis).

    Do this ONCE at load time (see `prepare_stacked` / the pipeline engine),
    not per forward call — restacking is an O(params) copy."""
    blocks = [params[f"h_{i}"] for i in layer_ids]
    return jax.tree.map(lambda *xs: jnp.stack(xs), *blocks)


def prepare_stacked(params, cfg: GPTConfig):
    """One-time load-side transform: {'h_0'..'h_{L-1}', ...} ->
    {'blocks': stacked, 'wte', 'wpe', 'ln_f', 'lm_head'} for use with
    `make_apply_stacked`. The stacked layout is also what the pipeline
    runtime shards over the 'stage' mesh axis."""
    out = {k: v for k, v in params.items() if not k.startswith("h_")}
    for name, layers in stack_layers(cfg).items():
        out[name] = stack_blocks(params, layers)
    return out


def stack_ranges(cfg):
    """{name in the prepared tree: (first layer, stop)} in layer order.
    A model's layers are ONE stack, "blocks", unless the config has a
    dense prefix (llama_moe.MixtralConfig.first_k_dense): layers of
    another kind, which stack apart as "dense_blocks" in front of it.
    `prepare_stacked`, `node._stack_and_release` and `llama.layer_stacks`
    all lay the tree out from this."""
    k = getattr(cfg, "first_k_dense", 0)
    return {**({"dense_blocks": (0, k)} if k else {}),
            "blocks": (k, cfg.n_layer)}


def stack_layers(cfg):
    """{name in the prepared tree: its layers' numbers} in the order of
    each stack's first layer: `stack_ranges`' ranges, or — for a config
    whose layers are of KINDS that interleave (`layer_types`, models/
    mla.py: layers whose attention and cache differ) — the dense prefix,
    the "full" expert layers ("blocks") and the "window" expert layers
    ("window_blocks"; "linear_blocks" for models/kda.py's "linear"
    layers; "ssm_blocks" and "expert_blocks" for the blocks of ONE mixer
    that is a state-space rule or the experts, models/llama.py
    `one_mixer`), each stacked apart whatever lies between its members."""
    types = getattr(cfg, "layer_types", None)
    if types is None:
        return {name: tuple(range(*r))
                for name, r in stack_ranges(cfg).items()}
    k = getattr(cfg, "first_k_dense", 0)
    out = {"dense_blocks": tuple(range(k))} if k else {}
    for name, kind in (("blocks", "full"), ("window_blocks", "window"),
                       ("linear_blocks", "linear"), ("ssm_blocks", "ssm"),
                       ("expert_blocks", "experts")):
        layers = tuple(i for i in range(k, cfg.n_layer) if types[i] == kind)
        if layers:
            out[name] = layers
    return out


def layer_runs(cfg):
    """The layer loop of a config with `layer_types`, as runs of
    consecutive layers that lie in one stack: [(stack name, (first, stop)
    within the stack, kind, (first, stop) among the kind's layers)] in
    layer order. A run is one scan; the kind's range is where its layers'
    cache lives (a kind's leaves have that kind's layers only)."""
    types = cfg.layer_types
    where = {layer: (name, j) for name, layers in stack_layers(cfg).items()
             for j, layer in enumerate(layers)}
    seen = {}
    runs = []
    for layer in range(cfg.n_layer):
        name, j = where[layer]
        kind = types[layer]
        nth = seen.get(kind, 0)
        seen[kind] = nth + 1
        if runs and runs[-1][0] == name and runs[-1][1][1] == j:
            _, (a, _), _, (ka, _) = runs[-1]
            runs[-1] = (name, (a, j + 1), kind, (ka, nth + 1))
        else:
            runs.append((name, (j, j + 1), kind, (nth, nth + 1)))
    return runs


def blocks_scan(stacked, x, *, cfg: GPTConfig, use_flash=False, compute_dtype=None,
                attn_fn=None, remat=False):
    """Run a stack of blocks via lax.scan: one compiled block body regardless
    of depth (the TPU-idiomatic form of the reference's Python
    `for block in self.h` loop, gpt_model_parts.py:20-21). `attn_fn`
    overrides the attention implementation (e.g. the sequence-parallel ring
    — see make_apply_seq_parallel); default is local causal MHA.

    `remat=True` wraps the block body in `jax.checkpoint`: the backward
    pass recomputes each block's internals instead of keeping all
    intermediates alive across the scan — activation memory drops from
    O(L x intermediates) to O(L x residual + 1 block), the standard
    FLOPs-for-HBM trade for training deep stacks."""

    def block(layer_params, carry):
        if attn_fn is None:
            return block_apply(layer_params, carry, cfg=cfg, use_flash=use_flash,
                               compute_dtype=compute_dtype)
        return _block_core(layer_params, carry, attn_fn, cfg=cfg,
                           compute_dtype=compute_dtype)

    if remat:
        block = jax.checkpoint(block)

    def body(carry, layer_params):
        return block(layer_params, carry), None

    out, _ = jax.lax.scan(body, x, stacked)
    return out


def embed(params, idx, *, cfg: GPTConfig):
    """Token + position embedding (ModelPart0 semantics,
    gpt_model_parts.py:13-18, incl. the T <= block_size guard)."""
    t = idx.shape[-1]
    if t > cfg.block_size:
        raise ValueError(f"Cannot forward: sequence length {t} > block_size {cfg.block_size}")
    pos = jnp.arange(t)
    with jax.named_scope("gpt.embed"):
        return embedding(params["wte"], idx) + embedding(params["wpe"], pos)


def head(params, x, *, cfg: GPTConfig, compute_dtype=None, logits_dtype=None):
    """Final LN + lm_head (ModelPartFinal_GPT semantics,
    gpt_model_parts.py:44-50).

    With `compute_dtype=bf16` the lm_head matmul reads bf16 operands and
    accumulates f32 (`preferred_element_type`) — logits stay f32. This is
    the dominant-cost matmul of a forward (C x V = 768 x 50257 for
    gpt2-small). On v5e the default f32 matmul "precision" is a bf16 MXU
    pass already, so the output is the same there; the explicit operand
    dtype matters on platforms where f32 matmul really runs f32, and makes
    the memory traffic intent visible rather than relying on a backend
    default.

    `logits_dtype=bf16` rounds the f32-accumulated logits on the way out
    (XLA fuses the cast into the matmul epilogue): the (B, T, V) logit
    write is the single largest HBM store of a forward — 823 MB at
    B=8/T=512/V=50257 in f32 (PERF.md section 5 has its share of the
    pipeline cell's busy time). Accumulation is
    still f32; only the stored values are rounded. Default None keeps f32
    logits (the parity-test configuration)."""
    with jax.named_scope("gpt.head"):
        x = layer_norm(params["ln_f"], x, eps=cfg.ln_eps)
        if compute_dtype is None:
            out = linear(params["lm_head"], x)
        else:
            out = linear(params["lm_head"], x, compute_dtype=compute_dtype,
                         accum_dtype=jnp.float32)
        return out if logits_dtype is None else out.astype(logits_dtype)


def make_apply(cfg: GPTConfig, *, use_flash=False, compute_dtype=None, remat=False):
    """Full-model forward over the per-layer param layout (restacks blocks
    per call — fine under jit for tests/small models; perf paths should use
    `prepare_stacked` + `make_apply_stacked`). `remat=True` checkpoints
    each block for training memory (see blocks_scan)."""

    def apply(params, idx):
        x = embed(params, idx, cfg=cfg)
        if compute_dtype is not None:
            x = x.astype(compute_dtype)
        stacked = stack_blocks(params, range(cfg.n_layer))
        x = blocks_scan(stacked, x, cfg=cfg, use_flash=use_flash,
                        compute_dtype=compute_dtype, remat=remat)
        logits = head(params, x.astype(jnp.float32), cfg=cfg, compute_dtype=compute_dtype)
        return logits

    return apply


def make_hidden_stacked(cfg: GPTConfig, *, compute_dtype=None):
    """Final-normed hidden states over the prepare_stacked layout —
    make_apply_stacked minus the lm_head projection (== HF
    GPT2Model.last_hidden_state). The embedding endpoint's forward
    (runtime/embeddings.py); kept HERE so it can never drift from the
    logits forward below."""

    def hidden(prepared, idx):
        x = embed(prepared, idx, cfg=cfg)
        if compute_dtype is not None:
            x = x.astype(compute_dtype)
        x = blocks_scan(prepared["blocks"], x, cfg=cfg,
                        compute_dtype=compute_dtype)
        return layer_norm(prepared["ln_f"], x.astype(jnp.float32),
                          eps=cfg.ln_eps)

    return hidden


def make_apply_stacked(cfg: GPTConfig, *, use_flash=False, compute_dtype=None,
                       remat=False, logits_dtype=None):
    """Forward over `prepare_stacked` params: zero per-call restacking.
    When `compute_dtype` is set, the head matmul also runs in it (f32
    accumulation — see `head`). `logits_dtype=bf16` halves the logit
    store, the serving-path configuration (see `head`)."""

    def apply(prepared, idx):
        x = embed(prepared, idx, cfg=cfg)
        if compute_dtype is not None:
            x = x.astype(compute_dtype)
        x = blocks_scan(prepared["blocks"], x, cfg=cfg, use_flash=use_flash,
                        compute_dtype=compute_dtype, remat=remat)
        return head(prepared, x.astype(jnp.float32), cfg=cfg,
                    compute_dtype=compute_dtype, logits_dtype=logits_dtype)

    return apply


def prepare_tp_blocks(stacked_blocks, cfg: GPTConfig, tp: int):
    """One-time load-side transform for MANUAL (shard_map) tensor
    parallelism over the fused-qkv layout: reorder the qkv output columns
    SHARD-MAJOR so that slicing the last axis into `tp` equal parts hands
    each tensor-parallel rank its own n_head/tp heads of q, k AND v
    contiguously.

    The fused kernel stores columns as [Q(C) | K(C) | V(C)] (one matmul —
    ops/attention.py:52); naively sharding that axis would give rank 0 all
    of Q plus half of K at tp=2, which no local attention can use. After
    the reorder the columns read [Q_0 K_0 V_0 | Q_1 K_1 V_1 | ...] where
    X_t is rank t's head slice, so the sharded local (C, 3C/tp) kernel
    splits into three (C, C/tp) head-aligned pieces (make_tp_block_fn).
    attn.proj / mlp.* need no reorder: merged heads already put rank t's
    activation columns at rows [t*C/tp, (t+1)*C/tp) of the row-sharded
    projection, and the MLP hidden axis is a single contiguous block.

    Works on any leaf layout whose LAST axis is the fused 3C — per-layer,
    (L, ...)-stacked, or (S, L/S, ...)-stage-stacked trees alike."""
    if cfg.n_head % tp:
        raise ValueError(f"n_head {cfg.n_head} not divisible by tp {tp}")
    c = cfg.n_embd
    shard = c // tp

    def reorder(a):  # (..., 3C) -> (..., 3C) shard-major
        q, k, v = a[..., :c], a[..., c:2 * c], a[..., 2 * c:]
        parts = []
        for t in range(tp):
            sl = slice(t * shard, (t + 1) * shard)
            parts += [q[..., sl], k[..., sl], v[..., sl]]
        return jnp.concatenate(parts, axis=-1)

    return {
        **stacked_blocks,
        "attn": {
            **stacked_blocks["attn"],
            "qkv": {
                "kernel": reorder(stacked_blocks["attn"]["qkv"]["kernel"]),
                "bias": reorder(stacked_blocks["attn"]["qkv"]["bias"]),
            },
        },
    }


def make_tp_block_fn(cfg: GPTConfig, *, axis_name=None, compute_dtype=None,
                     remat=False):
    """Tensor-parallel stacked-block function for the pipeline runtimes —
    the Megatron recipe inside shard_map (TP x PP composition):

      * qkv and mlp.fc are COLUMN-parallel: the local kernel holds this
        rank's output slice ((C, 3C/tp) head-aligned via prepare_tp_blocks,
        (C, 4C/tp) hidden slice), operand replicated, no communication;
      * attention runs on the rank's own n_head/tp heads (heads are
        independent, so local heads need no collective);
      * attn.proj and mlp.proj are ROW-parallel: local (C/tp, C) /
        (4C/tp, C) kernels produce partial sums combined by one
        `lax.psum`, with the replicated bias added ONCE after the reduce.

    Two psums per block over the `model` axis — the standard Megatron
    count. Unlike classic Megatron there is NO explicit conjugate `f`/`g`
    operator at the column-parallel inputs: shard_map's AD tracks per-axis
    replication and inserts the exact transposes itself (gradient parity
    vs the 1D pipeline is pinned by tests/test_tp_pp.py — see the note in
    parallel/collectives.py). Returns block_fn(local_stacked, x) for
    `spmd_pipeline_stacked(..., model_axis=...)`, where local_stacked
    leaves carry (L_per_stage, ...) with model-sharded trailing dims.
    `remat=True` checkpoints each block body (backward recomputes block
    internals; the two forward psums replay in the recompute)."""
    from jax import lax

    from dnn_tpu.ops.pallas.flash_attention import reference_attention
    from dnn_tpu.parallel.mesh import MODEL_AXIS

    axis = axis_name or MODEL_AXIS

    def one_block(bp, x):
        tp = lax.axis_size(axis)
        local_heads = cfg.n_head // tp
        from dnn_tpu.ops.attention import merge_heads, split_heads

        h = layer_norm(bp["ln_1"], x, eps=cfg.ln_eps)
        qkv = linear(bp["attn"]["qkv"], h, compute_dtype=compute_dtype)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        q, k, v = (split_heads(t, local_heads) for t in (q, k, v))
        y = merge_heads(reference_attention(q, k, v, causal=True))
        att = linear({"kernel": bp["attn"]["proj"]["kernel"]}, y,
                     compute_dtype=compute_dtype)
        att = lax.psum(att, axis) + bp["attn"]["proj"]["bias"].astype(x.dtype)
        x = x + att

        h = layer_norm(bp["ln_2"], x, eps=cfg.ln_eps)
        m = gelu(linear(bp["mlp"]["fc"], h, compute_dtype=compute_dtype))
        mm = linear({"kernel": bp["mlp"]["proj"]["kernel"]}, m,
                    compute_dtype=compute_dtype)
        mm = lax.psum(mm, axis) + bp["mlp"]["proj"]["bias"].astype(x.dtype)
        return x + mm

    if remat:
        one_block = jax.checkpoint(one_block)

    def block_fn(local, x):
        def body(carry, lp):
            return one_block(lp, carry), None

        out, _ = jax.lax.scan(body, x, local)
        return out

    return block_fn


def make_apply_seq_parallel(cfg: GPTConfig, mesh, *, axis_name=None,
                            compute_dtype=None, method: str = "ring"):
    """Sequence-parallel (long-context) full-model forward.

    The reference hard-caps sequence length (`T <= block_size` assert,
    gpt_model_parts.py:15) and holds every activation whole on one device.
    This path shards the SEQUENCE dimension over the mesh's "seq" axis:
    embed/LN/MLP/head act position-wise and run on local shards; attention
    crosses shards via one of two strategies (`method`):

      * "ring": K/V blocks rotate the ring via `lax.ppermute` with
        online-softmax accumulation (dnn_tpu/parallel/ring_attention.py) —
        per-device activation memory is O(T/n) and the full (T, T) score
        matrix never exists anywhere; works for any head count.
      * "ulysses": two `lax.all_to_all`s swap sequence sharding for head
        sharding around one dense local attention
        (dnn_tpu/parallel/ulysses.py) — fewer, denser collectives;
        needs n_head divisible by the axis size.

    `apply(prepared, ids)`: `prepared` from `prepare_stacked` (replicated);
    ids (B, T) with T divisible by the seq-axis size. Returns f32 logits
    sharded over the sequence axis.
    """
    from jax.sharding import PartitionSpec as P

    from dnn_tpu.ops.attention import merge_heads, split_heads
    from dnn_tpu.parallel.mesh import SEQ_AXIS
    from dnn_tpu.parallel.ring_attention import ring_attention_local
    from dnn_tpu.parallel.ulysses import ulysses_attention_local

    if method not in ("ring", "ulysses"):
        raise ValueError(f"method must be ring|ulysses, got {method!r}")
    axis = axis_name or SEQ_AXIS
    if method == "ulysses" and cfg.n_head % mesh.shape[axis] != 0:
        raise ValueError(
            f"ulysses needs n_head ({cfg.n_head}) divisible by the seq-axis "
            f"size ({mesh.shape[axis]}); use method='ring'"
        )

    def ring_attn(attn_params, h):
        qkv = linear(attn_params["qkv"], h, compute_dtype=compute_dtype)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        q, k, v = (split_heads(t, cfg.n_head) for t in (q, k, v))
        if method == "ring":
            y = ring_attention_local(q, k, v, axis_name=axis, causal=True)
        else:
            y = ulysses_attention_local(q, k, v, axis_name=axis, causal=True)
        return linear(attn_params["proj"], merge_heads(y), compute_dtype=compute_dtype)

    def local_fn(prepared, ids_local):
        t_local = ids_local.shape[-1]
        my = jax.lax.axis_index(axis)
        pos = my * t_local + jnp.arange(t_local)  # global positions
        x = embedding(prepared["wte"], ids_local) + embedding(prepared["wpe"], pos)
        if compute_dtype is not None:
            x = x.astype(compute_dtype)
        x = blocks_scan(prepared["blocks"], x, cfg=cfg,
                        compute_dtype=compute_dtype, attn_fn=ring_attn)
        return head(prepared, x.astype(jnp.float32), cfg=cfg,
                    compute_dtype=compute_dtype)

    def apply(prepared, ids):
        t = ids.shape[-1]
        if t > cfg.block_size:
            raise ValueError(
                f"Cannot forward: sequence length {t} > block_size {cfg.block_size}"
            )
        n = mesh.shape[axis]
        if t % n != 0:
            raise ValueError(f"sequence length {t} not divisible by seq axis size {n}")
        return jax.shard_map(
            local_fn, mesh=mesh,
            in_specs=(P(), P(None, axis)),
            out_specs=P(None, axis, None),
            check_vma=False,
        )(prepared, ids)

    return apply


# --------------------------------------------------------------------------
# partitioning (mirrors gpt_model_parts.py stage layout)
# --------------------------------------------------------------------------

def layer_ranges(n_layer: int, num_parts: int):
    """Split n_layer blocks into num_parts contiguous ranges, earlier stages
    taking the remainder (matches the reference's inclusive
    [start_layer, end_layer] convention, gpt_model_parts.py:12,30,40)."""
    if not 1 <= num_parts <= n_layer:
        raise ValueError(f"num_parts must be in [1, {n_layer}], got {num_parts}")
    base, rem = divmod(n_layer, num_parts)
    ranges, lo = [], 0
    for p in range(num_parts):
        hi = lo + base + (1 if p < rem else 0)
        ranges.append((lo, hi))
        lo = hi
    return ranges


def make_partition(cfg: GPTConfig, *, use_flash=False, compute_dtype=None):
    def partition(num_parts):
        ranges = layer_ranges(cfg.n_layer, num_parts)
        stages = []
        for p, (lo, hi) in enumerate(ranges):
            is_first, is_last = p == 0, p == num_parts - 1
            hkeys = tuple(f"h_{i}" for i in range(lo, hi))
            param_keys = hkeys
            if is_first:
                param_keys = ("wte", "wpe") + param_keys
            if is_last:
                param_keys = param_keys + ("ln_f", "lm_head")

            def stage_fn(params, x, _lo=lo, _hi=hi, _first=is_first, _last=is_last):
                if _first:
                    x = embed(params, x, cfg=cfg)
                if compute_dtype is not None and jnp.issubdtype(x.dtype, jnp.floating):
                    x = x.astype(compute_dtype)
                if _hi > _lo:
                    stacked = stack_blocks(params, range(_lo, _hi))
                    x = blocks_scan(
                        stacked, x, cfg=cfg, use_flash=use_flash, compute_dtype=compute_dtype
                    )
                if _last:
                    x = head(params, x.astype(jnp.float32), cfg=cfg,
                             compute_dtype=compute_dtype)
                return x

            stages.append(
                StageSpec(
                    name=f"gpt_blocks[{lo}:{hi}]"
                    + ("+embed" if is_first else "")
                    + ("+head" if is_last else ""),
                    apply=stage_fn,
                    param_keys=param_keys,
                )
            )
        return stages

    return partition


def make_example_input(cfg: GPTConfig):
    def example_input(batch_size=1, seq_len=None, rng=None):
        t = min(seq_len or cfg.block_size, cfg.block_size)
        rng = rng if rng is not None else jax.random.PRNGKey(0)
        return jax.random.randint(rng, (batch_size, t), 0, cfg.vocab_size, dtype=jnp.int32)

    return example_input


def _register(name: str, cfg: GPTConfig):
    def convert(sd, _cfg=cfg):
        from dnn_tpu.io.checkpoint import gpt_params_from_state_dict

        return gpt_params_from_state_dict(sd, n_layer=_cfg.n_layer)

    register_model(
        ModelSpec(
            name=name,
            init=lambda rng, dtype=jnp.float32, _cfg=cfg: init(rng, _cfg, dtype),
            apply=make_apply(cfg),
            partition=make_partition(cfg),
            example_input=make_example_input(cfg),
            supported_parts=tuple(range(1, cfg.n_layer + 1)),
            convert_state_dict=convert,
            config=cfg,
            extras={
                # dtype/flash-aware factories so the engine can honor the
                # config's `dtype` key (make_apply/make_partition above are
                # the f32 defaults).
                "make_apply": lambda compute_dtype=None, use_flash=False, _cfg=cfg: make_apply(
                    _cfg, compute_dtype=compute_dtype, use_flash=use_flash
                ),
                "make_partition": lambda compute_dtype=None, use_flash=False, _cfg=cfg: make_partition(
                    _cfg, compute_dtype=compute_dtype, use_flash=use_flash
                ),
            },
        )
    )


for _name, _cfg in PRESETS.items():
    _register(_name, _cfg)
