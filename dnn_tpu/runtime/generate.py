"""Autoregressive generation with a KV cache for the GPT family.

The reference's GPT path is a single stateless full-sequence forward per
request — no KV cache, no sampling, no incremental decode (SURVEY §5
'Long-context': "each forward is full-sequence, stateless",
/root/reference/partitions/gpt_model_parts.py:13-50). A GPT user needs
generation, so the rebuild supplies it TPU-first:

  * prefill is one full-sequence forward that also writes K/V into a
    preallocated static-shape cache (XLA-friendly: no growing arrays);
  * decode is a `lax.scan` over steps — one compiled step regardless of
    token count — each step a (B, 1) forward against the cache with
    position masking instead of dynamic shapes;
  * the cache is laid out (L, B, H, S, D) so layers scan over the leading
    axis with the same stacked block params the pipeline runtime shards.

Greedy (temperature=0), temperature/top-k, and nucleus (top-p) sampling
are supported, composably (top-k filter first, nucleus over the rest).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from dnn_tpu.models.gpt import GPTConfig, head
from dnn_tpu.ops.attention import merge_heads, split_heads
from dnn_tpu.ops.nn import gelu, layer_norm, linear
from dnn_tpu.runtime.kvcache import (
    FloatKV,
    Int4KV,
    Int8KV,
    codec_for_cache,
)
from dnn_tpu.runtime.paged_kvcache import scan_rows

_NEG_BIG = -1e30

# nucleus sampling ranks only this many candidates per step (see _sample):
# top-256 probability mass on a trained LM exceeds 0.999, so any practical
# p's nucleus fits inside the prefilter and the result is bit-identical to
# ranking the full vocabulary.
TOP_P_PREFILTER_K = 256


def init_cache(cfg: GPTConfig, batch: int, max_len: int, dtype=jnp.float32):
    """Preallocated K/V cache, one leading layer axis: (L, B, H, S, D).
    dtype="int8" / "int4" build the quantized caches (per-row scales
    ride along — dnn_tpu/runtime/kvcache.Int8KV / Int4KV; int4 stores
    native jnp.int4, two values per byte)."""
    if dtype == "int8":
        return Int8KV().init(cfg, batch, max_len)
    if dtype == "int4":
        return Int4KV().init(cfg, batch, max_len)
    return FloatKV(dtype).init(cfg, batch, max_len)


def _qkv_heads(bp, h, *, cfg: GPTConfig, compute_dtype):
    qkv = linear(bp["attn"]["qkv"], h, compute_dtype=compute_dtype)
    q, k, v = jnp.split(qkv, 3, axis=-1)
    return tuple(split_heads(t, cfg.n_head) for t in (q, k, v))  # (B,H,T,D)


def _block_with_cache(bp, x, rows, start_pos, *, cfg: GPTConfig,
                      compute_dtype, ffn=None, codec=None):
    """One transformer block over x (B, T, C) whose tokens sit at positions
    [start_pos, start_pos+T); writes this block's K/V into its layer of the
    cache `rows` (the whole codec pytree — float or int8+scales — bound to
    the layer: paged_kvcache.scan_rows) and attends against everything
    cached so far. T=prompt_len for prefill, T=1 for decode — same code
    path. `ffn(bp, h)` overrides the dense MLP (the MoE family plugs its
    routed FFN in here, dnn_tpu/runtime/generate_moe.py)."""
    codec = codec or codec_for_cache(rows.leaves)
    t = x.shape[1]
    # the scope names of models/gpt._block_core (device-trace names)
    with jax.named_scope("gpt.block.attn"):
        h = layer_norm(bp["ln_1"], x, eps=cfg.ln_eps)
        q, k, v = _qkv_heads(bp, h, cfg=cfg, compute_dtype=compute_dtype)
        rows = codec.write(rows, k, v, start_pos)
        pos_limit = start_pos + jnp.arange(t)  # causal within the new tokens
        # base= asserts the contiguous-limit contract the Pallas kernel
        # needs (kvcache.FloatKV.attend) — einsum codecs ignore it
        y = codec.attend(q, rows.read(), pos_limit, base=start_pos)
        x = x + linear(bp["attn"]["proj"], merge_heads(y.astype(x.dtype)),
                       compute_dtype=compute_dtype)
    with jax.named_scope("gpt.block.mlp"):
        h = layer_norm(bp["ln_2"], x, eps=cfg.ln_eps)
        if ffn is None:
            m = linear(bp["mlp"]["proj"],
                       gelu(linear(bp["mlp"]["fc"], h,
                                   compute_dtype=compute_dtype)),
                       compute_dtype=compute_dtype)
        else:
            m = ffn(bp, h).astype(x.dtype)
    return x + m, rows


def forward_with_cache(prepared, ids, cache, start_pos, *, cfg: GPTConfig,
                       compute_dtype=None, ffn=None, attn_kernel="auto"):
    """Forward ids (B, T) at positions [start_pos, start_pos+T) through all
    layers (scan over the stacked blocks), updating the cache. Returns
    (logits (B, T, V), cache). The cache format picks the storage codec:
    {"k","v"} float (init_cache default) or the int8+scales form
    (init_cache(..., dtype="int8")). `attn_kernel=True` runs cache
    attention through the Pallas streaming kernel
    (dnn_tpu/ops/pallas/cached_attention.py) — decode steps AND prefill
    chunks alike, one compiled program regardless of position; the
    default "auto" engages that kernel only on TPU against caches of
    >= kvcache.AUTO_KERNEL_MIN_S positions (length-aware dispatch: the
    long-context regime where clamped streaming beats reading the full
    allocation) and is the plain einsum everywhere else."""
    x, new_cache = hidden_with_cache(
        prepared, ids, cache, start_pos, cfg=cfg,
        compute_dtype=compute_dtype, ffn=ffn, attn_kernel=attn_kernel)
    logits = head(prepared, x, cfg=cfg, compute_dtype=compute_dtype)
    return logits, new_cache


def hidden_with_cache(prepared, ids, cache, start_pos, *, cfg: GPTConfig,
                      compute_dtype=None, ffn=None, attn_kernel="auto"):
    """`forward_with_cache` up to the last block: (hidden (B, T, C)
    float32 — what `head` is handed — and the cache). A serving prefill
    chunk ends here (GPTFamilyRows.prefill): the head meets one row of
    one chunk, where the first token is sampled."""
    codec = codec_for_cache(cache, use_kernel=attn_kernel)
    x = _embed_at(prepared, ids, start_pos, compute_dtype=compute_dtype)

    def block(bp, x, rows):
        return _block_with_cache(
            bp, x, rows, start_pos, cfg=cfg, compute_dtype=compute_dtype,
            ffn=ffn, codec=codec)

    with jax.named_scope("layers.scan"):  # the loop's own slicing
        x, new_cache = scan_rows(block, x, prepared["blocks"], cache)
    return x.astype(jnp.float32), new_cache


def logit_bias_row(logit_bias, vocab_size: int):
    """{token_id: additive bias} -> a dense (V,) f32 row (None -> None).
    The OpenAI-style knob: +big forces a token, -big (e.g. -100) bans it
    — applied to logits AFTER the repetition penalty, BEFORE
    temperature/filters, so bans bind for greedy rows too. Validates ids
    against the vocab (a silently-clipped id would bias the wrong
    token)."""
    if not logit_bias:
        return None
    row = np.zeros((vocab_size,), np.float32)
    for tok, val in logit_bias.items():
        t = int(tok)
        if not 0 <= t < vocab_size:
            raise ValueError(
                f"logit_bias token id {t} outside [0, {vocab_size})")
        v = float(val)
        if not np.isfinite(v):
            raise ValueError(f"logit_bias value for {t} not finite: {v}")
        row[t] = v
    return jnp.asarray(row)


def apply_repetition_penalty(logits, seen, penalty):
    """CTRL-style repetition penalty on RAW logits (HF semantics, applied
    before temperature): for tokens already in the sequence (`seen`,
    (..., V) bool), a positive logit is divided by `penalty` and a
    negative one multiplied — both push repeated tokens down when
    penalty > 1. Pure elementwise select: O(V), static shapes."""
    pen = jnp.where(logits > 0, logits / penalty, logits * penalty)
    return jnp.where(seen, pen, logits)


def _sample(logits, rng, *, temperature: float, top_k: Optional[int],
            top_p: Optional[float] = None, min_p: Optional[float] = None):
    """logits (B, V) -> token ids (B,). temperature=0 is greedy; top_k
    truncates to the k highest logits; min_p drops tokens whose
    probability is below min_p x the top token's (a sort-free relative
    cutoff — one max + one compare); top_p (nucleus) keeps the smallest
    set of tokens whose probability mass reaches p. All static-shape
    (threshold masks, no dynamic vocab slicing) and composable, applied
    in that order."""
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = logits / temperature
    if top_k is not None:
        kth = lax.top_k(logits, top_k)[0][..., -1:]
        logits = jnp.where(logits < kth, _NEG_BIG, logits)
    if min_p is not None:
        # prob_i >= min_p * prob_max  <=>  logit_i >= logit_max + log(min_p)
        mx = jnp.max(logits, axis=-1, keepdims=True)
        logits = jnp.where(logits < mx + jnp.log(min_p), _NEG_BIG, logits)
    if top_p is not None:
        # The nucleus threshold can only fall inside the highest-probability
        # tokens, so rank just TOP_P_PREFILTER_K candidates (lax.top_k,
        # O(V log k)) instead of sorting the full vocab (O(V log V)) inside
        # every decode step. Probabilities use the FULL softmax denominator
        # (logsumexp — O(V), sort-free), so the kept set and the sampled
        # token are bit-identical to the full-vocab filter whenever the
        # nucleus fits inside k; if it ever overflows (p greater than the
        # top-k's total mass), the cut truncates to the k best — strictly
        # tighter, never looser.
        k = min(TOP_P_PREFILTER_K, logits.shape[-1])
        vals = lax.top_k(logits, k)[0]  # (..., k) descending
        lse = jax.scipy.special.logsumexp(logits, axis=-1, keepdims=True)
        probs = jnp.exp(vals - lse)
        cum = jnp.cumsum(probs, axis=-1)
        # keep a token while the mass BEFORE it is < p (top-1 always kept);
        # the cutoff logit is the smallest kept one
        keep = (cum - probs) < top_p
        n_keep = jnp.maximum(keep.sum(axis=-1), 1)
        thresh = jnp.take_along_axis(vals, (n_keep - 1)[..., None], axis=-1)
        logits = jnp.where(logits < thresh, _NEG_BIG, logits)
    return jax.random.categorical(rng, logits, axis=-1).astype(jnp.int32)


def _sample_rows(logits, keys, *, temperature, top_k, top_p, min_p=None):
    """Per-ROW sampling for the slot pool: every row carries its own
    request's parameters. logits (B, V); keys (B, 2) uint32; temperature
    (B,) f32 (0 = greedy); top_k (B,) int32 (0 = off, clamped to
    TOP_P_PREFILTER_K); top_p (B,) f32 (outside (0, 1) = off); min_p
    (B,) f32 (outside (0, 1] = off; None skips the filter entirely).

    Row i with uniform parameters reproduces `_sample`'s draw for the same
    key bit-for-bit — same thresholds (the k-th-largest value and the
    nucleus cutoff are computed by the same ops) and the same categorical
    call shape — so a request in a mixed pool samples exactly what it
    would in a single-request server (tests/test_serving_options.py).
    An all-greedy pool skips the filter math at runtime (real lax.cond at
    the top level of the step program, not a select)."""
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def do_sample(_):
        k_cap = min(TOP_P_PREFILTER_K, logits.shape[-1])
        safe_t = jnp.where(temperature > 0, temperature, 1.0)
        lg = logits / safe_t[:, None]
        # per-row top-k: threshold at the row's k-th largest value
        vals = lax.top_k(lg, k_cap)[0]  # (B, k_cap) descending
        k_idx = jnp.clip(top_k, 1, k_cap) - 1
        kth = jnp.take_along_axis(vals, k_idx[:, None], axis=-1)
        lg = jnp.where((top_k[:, None] > 0) & (lg < kth), _NEG_BIG, lg)
        if min_p is not None:
            # per-row relative cutoff (see _sample): rows with min_p
            # outside (0, 1] pass through untouched (1.0 = keep only
            # tokens tied with the max, matching _sample's threshold)
            m_on = (min_p > 0) & (min_p <= 1.0)
            safe_mp = jnp.where(m_on, min_p, 0.5)
            mx = jnp.max(lg, axis=-1, keepdims=True)
            lg = jnp.where(
                m_on[:, None] & (lg < mx + jnp.log(safe_mp)[:, None]),
                _NEG_BIG, lg)
        # per-row nucleus: the _sample prefilter with a row-wise p
        pvals = lax.top_k(lg, k_cap)[0]
        lse = jax.scipy.special.logsumexp(lg, axis=-1, keepdims=True)
        probs = jnp.exp(pvals - lse)
        cum = jnp.cumsum(probs, axis=-1)
        keep = (cum - probs) < top_p[:, None]
        n_keep = jnp.maximum(keep.sum(axis=-1), 1)
        thresh = jnp.take_along_axis(pvals, (n_keep - 1)[:, None], axis=-1)
        p_on = (top_p > 0) & (top_p < 1.0)
        lg = jnp.where(p_on[:, None] & (lg < thresh), _NEG_BIG, lg)
        # mirror the pool's per-row call shape (categorical over (1, V))
        # so draws match the uniform-parameter _sample vmap exactly
        return jax.vmap(
            lambda l, k: jax.random.categorical(k, l[None, :], axis=-1)[0]
        )(lg, keys).astype(jnp.int32)

    sampled = lax.cond(jnp.any(temperature > 0.0), do_sample,
                       lambda _: greedy, operand=None)
    return jnp.where(temperature > 0.0, sampled, greedy)


def _embed_at(aux, ids, start_pos, *, compute_dtype):
    """Token+position embedding for ids (B, T) at absolute positions
    [start_pos, start_pos+T) — the incremental-decode counterpart of
    gpt.embed (same gathers as forward_with_cache, so pipeline and
    single-device generation match bit for bit)."""
    pos = start_pos + jnp.arange(ids.shape[1])
    x = jnp.take(aux["wte"]["embedding"], ids, axis=0) + \
        jnp.take(aux["wpe"]["embedding"], pos, axis=0)
    if compute_dtype is not None:
        x = x.astype(compute_dtype)
    return x


def prepare_pipeline_stacked(prepared, cfg: GPTConfig, mesh, *, axis_name=None):
    """One-time load-side transform for pipeline-parallel generation:
    reshape the (L, ...) block stack stage-major to (S, L/S, ...) and place
    it sharded over the stage axis (each device holds only its own stage's
    blocks — HBM-resident per-stage weights, same layout the inference
    engine's stacked pipeline uses). Returns (stage_blocks, aux)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from dnn_tpu.parallel.mesh import STAGE_AXIS

    axis = axis_name or STAGE_AXIS
    num_stages = mesh.shape[axis]
    if cfg.n_layer % num_stages != 0:
        raise ValueError(
            f"n_layer {cfg.n_layer} not divisible by {num_stages} stages"
        )
    per_stage = cfg.n_layer // num_stages
    stage_blocks = jax.tree.map(
        lambda p: p.reshape(num_stages, per_stage, *p.shape[1:]),
        prepared["blocks"],
    )
    stage_blocks = jax.device_put(
        stage_blocks, NamedSharding(mesh, P(axis))
    )
    aux = {k: v for k, v in prepared.items() if k != "blocks"}
    return stage_blocks, aux


class GPTPipelineFamily:
    """Per-stage decode hooks for the pipeline-parallel generator — the
    family-adapter pattern the batcher uses (serving.GPTFamilyRows),
    applied to the ppermute ring: a family supplies its stage-local cache
    layout, cached block, embed, and head; the ring schedule, cache-shard
    bookkeeping, and sampling broadcast stay family-agnostic. LLaMA's
    adapter is models/llama.LlamaPipelineFamily (RoPE positions,
    KV-head-width cache shards)."""

    def __init__(self, cfg, *, compute_dtype=None, ffn=None, kv_dtype=None):
        self.cfg = cfg
        self.compute_dtype = compute_dtype
        self.ffn = ffn  # block-MLP override (MoE: generate_moe.moe_cache_ffn)
        self.kv_dtype = kv_dtype  # None follows compute_dtype; "int8" quantizes

    def stage_cache(self, per_stage: int, batch: int, s_max: int):
        import dataclasses

        cfg = self.cfg
        dt = self.kv_dtype if self.kv_dtype is not None else (
            self.compute_dtype or jnp.float32)
        # a per-stage cache is just a cache whose "layer count" is the
        # stage's slice — reuse init_cache (and its codec dispatch)
        stage_cfg = dataclasses.replace(cfg, n_layer=per_stage)
        return init_cache(stage_cfg, batch, s_max, dt)

    def block_with_cache(self, bp, x, rows, start_pos):
        return _block_with_cache(
            bp, x, rows, start_pos, cfg=self.cfg,
            compute_dtype=self.compute_dtype, ffn=self.ffn)

    def embed(self, aux, ids, start_pos):
        return _embed_at(aux, ids, start_pos, compute_dtype=self.compute_dtype)

    def head(self, aux, h):
        return head(aux, h.astype(jnp.float32), cfg=self.cfg,
                    compute_dtype=self.compute_dtype)


def make_pipeline_generate(cfg: GPTConfig, mesh, *, max_new_tokens: int,
                           temperature: float = 0.0, top_k: Optional[int] = None,
                           top_p: Optional[float] = None,
                           compute_dtype=None, axis_name=None, family=None,
                           kv_dtype=None):
    """Pipeline-parallel KV-cache generation across a stage-sharded mesh.

    The serving capability the reference's 8-stage GPT pipeline stops short
    of: its partitions can emit one stateless forward's logits
    (/root/reference/partitions/gpt_model_parts.py:36-50) but cannot
    decode. Here the whole decode loop runs as ONE SPMD program:

      * each device holds its stage's blocks AND that stage's slice of the
        KV cache — cache shards live with the weights they serve, nothing
        cache-shaped ever crosses a device boundary;
      * per token, the (B, 1, C) hidden state makes one full circuit of the
        `ppermute` ring: at sub-step s the real value sits on stage s, which
        applies its blocks against its local cache; every device computes
        each sub-step (SPMD — one program), but only the active stage's
        cache update is kept (`where` on the stage coordinate). Since
        single-stream decode is inherently sequential through the stages,
        wall-clock equals the sequential stage latency — the idle devices'
        discarded compute costs energy, not time;
      * embed runs where the ring starts and head/sampling where it ends
        (stage 0 after the wraparound hop), and the sampled token is
        psum-broadcast so every stage enters the next step agreed.

    Token-for-token identical to single-device `make_generate` (same gather,
    block, head, and rng-split sequence). Returns
    generate(stage_blocks, aux, ids, rng) over `prepare_pipeline_stacked`
    outputs.
    """
    from jax.sharding import PartitionSpec as P

    from dnn_tpu.parallel.mesh import STAGE_AXIS

    if max_new_tokens < 1:
        raise ValueError(f"max_new_tokens must be >= 1, got {max_new_tokens}")
    axis = axis_name or STAGE_AXIS
    num_stages = mesh.shape[axis]
    if cfg.n_layer % num_stages != 0:
        raise ValueError(
            f"n_layer {cfg.n_layer} not divisible by {num_stages} stages"
        )
    per_stage = cfg.n_layer // num_stages
    perm = [(i, (i + 1) % num_stages) for i in range(num_stages)]
    if family is not None:
        # same contract as ContinuousBatcher: with an explicit family the
        # model math runs at the FAMILY's compute_dtype; a diverging
        # top-level knob would silently lose
        fam_dtype = getattr(family, "compute_dtype", None)
        if compute_dtype is not None and fam_dtype != compute_dtype:
            raise ValueError(
                f"compute_dtype mismatch: make_pipeline_generate="
                f"{compute_dtype} vs family adapter={fam_dtype} — set it "
                f"on the adapter")
        if kv_dtype is not None:
            raise ValueError("pass kv_dtype on the family adapter, not "
                             "alongside family=")
    fam = family or GPTPipelineFamily(cfg, compute_dtype=compute_dtype,
                                      kv_dtype=kv_dtype)

    def per_device(stage_blocks, aux, ids, rng):
        local = jax.tree.map(lambda p: p[0], stage_blocks)  # (per_stage, ...)
        d = lax.axis_index(axis)
        b, t = ids.shape
        s_max = t + max_new_tokens
        cache = fam.stage_cache(per_stage, b, s_max)

        def my_blocks(x, cache, start_pos):
            return scan_rows(
                lambda bp, x, rows: fam.block_with_cache(bp, x, rows,
                                                         start_pos),
                x, local, cache)

        def ring_pass(x, cache, start_pos):
            """x real on stage 0 -> through all stages in order -> real
            result back on stage 0 (wraparound hop)."""
            def sub(carry, s):
                h, cache = carry
                h2, cache2 = my_blocks(h, cache, start_pos)
                active = d == s
                cache = jax.tree.map(
                    lambda new, old: jnp.where(active, new, old), cache2, cache)
                h = lax.ppermute(h2, axis, perm)
                return (h, cache), None

            (h, cache), _ = lax.scan(sub, (x, cache), jnp.arange(num_stages))
            return h, cache

        def sample_last(h, sub_rng):
            logits = fam.head(aux, h[:, -1:])
            tok = _sample(logits[:, -1], sub_rng,
                          temperature=temperature, top_k=top_k, top_p=top_p)
            # only stage 0 holds the real hidden state; broadcast its token
            return lax.psum(jnp.where(d == 0, tok, jnp.zeros_like(tok)), axis)

        # prefill: full prompt, one ring circuit
        x = fam.embed(aux, ids, 0)
        h, cache = ring_pass(x, cache, 0)
        rng, sub = jax.random.split(rng)
        tok = sample_last(h, sub)

        def step(carry, i):
            cache, tok, rng = carry
            x = fam.embed(aux, tok[:, None], t + i)
            h, cache = ring_pass(x, cache, t + i)
            rng, sub = jax.random.split(rng)
            nxt = sample_last(h, sub)
            return (cache, nxt, rng), tok

        (_, last, _), toks = lax.scan(
            step, (cache, tok, rng), jnp.arange(max_new_tokens - 1)
        )
        toks = jnp.moveaxis(toks, 0, 1)  # (B, max_new_tokens-1)
        return jnp.concatenate([toks, last[:, None]], axis=1)

    @jax.jit
    def generate(stage_blocks, aux, ids, rng):
        b, t = ids.shape
        if t + max_new_tokens > cfg.block_size:
            raise ValueError(
                f"prompt {t} + max_new_tokens {max_new_tokens} exceeds "
                f"block_size {cfg.block_size}"
            )
        return jax.shard_map(
            per_device, mesh=mesh,
            in_specs=(P(axis), P(), P(), P()),
            out_specs=P(),
            check_vma=False,
        )(stage_blocks, aux, ids, rng)

    return generate


def make_generate(cfg: GPTConfig, *, max_new_tokens: int, temperature: float = 0.0,
                  top_k: Optional[int] = None, top_p: Optional[float] = None,
                  min_p: Optional[float] = None,
                  repetition_penalty: Optional[float] = None,
                  logit_bias=None,
                  compute_dtype=None, ffn=None, kv_dtype=None,
                  attn_kernel="auto"):
    """Build a jitted generate(prepared, ids, rng) -> (B, max_new_tokens).

    `prepared` is the stacked layout from `gpt.prepare_stacked`. The prompt
    length is static per compilation (usual JAX contract); decode runs as a
    single lax.scan. `ffn(bp, h)` overrides the dense block MLP (the MoE
    family's entry point, dnn_tpu/runtime/generate_moe.py). `kv_dtype`
    picks the cache storage: None follows compute_dtype (f32 default),
    jnp.bfloat16 halves cache bandwidth, "int8" quarters it
    (dnn_tpu/runtime/kvcache.py). `attn_kernel=True` streams the cache
    through the Pallas attention kernel on TPU (fused int8 dequant; einsum
    fallback elsewhere); the default "auto" engages it only for
    long-context caches on TPU (kvcache.AUTO_KERNEL_MIN_S), False forces
    the einsum. `min_p` drops tokens below min_p x the top
    probability; `repetition_penalty` (HF/CTRL semantics) penalizes every
    token already in the sequence — when set, a (B, V) seen-mask rides
    the decode carry (scatter per step; only materialized when the
    penalty is on, so the default program is unchanged). `logit_bias`
    ({token_id: additive bias}) forces or bans specific tokens — applied
    after the penalty, before the filters, binding for greedy too.
    """
    if max_new_tokens < 1:
        raise ValueError(f"max_new_tokens must be >= 1, got {max_new_tokens}")
    if repetition_penalty is not None and repetition_penalty <= 0:
        raise ValueError(
            f"repetition_penalty must be > 0, got {repetition_penalty}")
    if min_p is not None and not 0.0 <= min_p <= 1.0:
        # min_p > 1 would mask EVERY token (threshold above the max
        # logit) and categorical would then draw uniformly — reject loud
        raise ValueError(f"min_p must be in [0, 1], got {min_p}")
    bias_row = logit_bias_row(logit_bias, cfg.vocab_size)
    pen_on = repetition_penalty is not None and repetition_penalty != 1.0

    @functools.partial(jax.jit, static_argnames=())
    def generate(prepared, ids, rng):
        b, t = ids.shape
        s_max = t + max_new_tokens
        if s_max > cfg.block_size:
            raise ValueError(
                f"prompt {t} + max_new_tokens {max_new_tokens} exceeds "
                f"block_size {cfg.block_size}"
            )
        cache_dtype = kv_dtype if kv_dtype is not None else (compute_dtype or jnp.float32)
        cache = init_cache(cfg, b, s_max, cache_dtype)

        # prefill: full prompt in one forward
        logits, cache = forward_with_cache(
            prepared, ids, cache, 0, cfg=cfg, compute_dtype=compute_dtype,
            ffn=ffn, attn_kernel=attn_kernel,
        )
        rng, sub = jax.random.split(rng)

        seen = None
        if pen_on:
            seen = jnp.zeros((b, cfg.vocab_size), bool)
            seen = seen.at[jnp.arange(b)[:, None], ids].set(True)

        def pick(lg, seen, sub):
            if pen_on:
                lg = apply_repetition_penalty(lg, seen, repetition_penalty)
            if bias_row is not None:
                lg = lg + bias_row
            tok = _sample(lg, sub, temperature=temperature, top_k=top_k,
                          top_p=top_p, min_p=min_p)
            if pen_on:
                seen = seen.at[jnp.arange(b), tok].set(True)
            return tok, seen

        tok, seen = pick(logits[:, -1], seen, sub)

        def step(carry, i):
            # carry token tok_i sits at sequence position t + i
            cache, tok, rng, seen = carry
            logits, cache = forward_with_cache(
                prepared, tok[:, None], cache, t + i, cfg=cfg,
                compute_dtype=compute_dtype, ffn=ffn,
                attn_kernel=attn_kernel,
            )
            rng, sub = jax.random.split(rng)
            nxt, seen = pick(logits[:, -1], seen, sub)
            return (cache, nxt, rng, seen), tok

        (_, last, _, _), toks = lax.scan(
            step, (cache, tok, rng, seen), jnp.arange(max_new_tokens - 1)
        )
        toks = jnp.moveaxis(toks, 0, 1)  # (B, max_new_tokens-1)
        return jnp.concatenate([toks, last[:, None]], axis=1)

    return generate
