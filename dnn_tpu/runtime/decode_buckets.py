"""Length-aware bucketed decode: cache views sized to the LIVE context.

Decode is bandwidth-bound, and the XLA decode step reads the whole
preallocated cache every step — at `max_len` allocation with a short live
context, bytes/step are proportional to the ALLOCATION, not the position.
The Pallas decode kernel fixes this on TPU by clamping its cache fetches
at the live limit (ops/pallas/cached_attention.decode_attention); this
module is the portable XLA-side counterpart:

  * compile the decode step against a small LADDER of contiguous
    cache-view lengths (powers of two up to `max_len`);
  * HOST-side dispatch picks the smallest bucket covering the batch's
    furthest live position, pads the cache up when a sequence grows
    through a bucket edge, and runs the step program compiled for that
    bucket — so per-step cache bytes track the live context;
  * token identity is preserved across bucket boundaries by construction:
    a bucket view differs from the full allocation only in columns BEYOND
    every row's position limit, and the band mask already zeroes those
    columns' probability mass exactly (appended zero terms in the
    contractions change no partial sum), so greedy streams are
    bit-identical to the unbucketed program (tests/test_decode_buckets.py
    pins this for f32, bf16, and int8 caches, through a bucket edge).

Two consumers: `make_bucketed_generate` (the solo host-loop decoder)
and `ContinuousBatcher(decode_buckets=...)` (runtime/serving.py), whose
pool grows bucket-by-bucket as its slots advance. Compiled-program count
is bounded by the ladder length (one step program per live bucket), a
deliberate, bounded relaxation of the serving three-program contract.
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp

__all__ = ["DEFAULT_MIN_BUCKET", "bucket_ladder", "bucket_for",
           "normalize_ladder", "pad_cache_to", "make_bucketed_generate"]

DEFAULT_MIN_BUCKET = 64

# every dense codec leaf carries positions at axis 3: K/V (L, B, H, S, D)
# and the int8 scales (L, B, H, S) alike (runtime/kvcache.py)
_POS_AXIS = 3


def bucket_ladder(max_len: int, min_bucket: int = DEFAULT_MIN_BUCKET):
    """Powers of two from `min_bucket` up, terminated at `max_len`
    (always the top rung, whatever its divisibility): e.g.
    bucket_ladder(1536) -> (64, 128, 256, 512, 1024, 1536)."""
    if max_len < 1:
        raise ValueError(f"max_len must be >= 1, got {max_len}")
    if min_bucket < 1:
        raise ValueError(f"min_bucket must be >= 1, got {min_bucket}")
    b = 1
    while b < min_bucket:
        b *= 2
    out = []
    while b < max_len:
        out.append(b)
        b *= 2
    out.append(max_len)
    return tuple(out)


def normalize_ladder(buckets: Sequence[int], max_len: int):
    """Validate an explicit ladder: ascending positive ints, entries
    beyond `max_len` dropped, `max_len` appended as the top rung when
    missing (the full allocation must be reachable)."""
    out = []
    for b in buckets:
        b = int(b)
        if b < 1:
            raise ValueError(f"bucket lengths must be >= 1, got {b}")
        if out and b <= out[-1]:
            raise ValueError(f"bucket ladder must ascend, got {buckets}")
        if b < max_len:
            out.append(b)
    out.append(max_len)
    return tuple(out)


def bucket_for(ladder: Sequence[int], need: int) -> int:
    """Smallest ladder bucket holding `need` live positions."""
    for b in ladder:
        if b >= need:
            return b
    raise ValueError(
        f"{need} positions exceed the ladder's top bucket {ladder[-1]}")


def pad_cache_to(cache, n: int):
    """Grow every cache leaf's position axis to `n` columns (zeros).
    The new columns sit beyond every live position limit, so the band
    mask excludes them until a write claims them — padding is
    attention-invisible. Callers jit this with `n` static (one compiled
    grow program per (from, to) bucket pair)."""
    def pad(a):
        grow = n - a.shape[_POS_AXIS]
        if grow < 0:
            raise ValueError(
                f"cannot shrink a cache leaf from {a.shape[_POS_AXIS]} "
                f"to {n} positions (buckets grow only)")
        if grow == 0:
            return a
        widths = [(0, 0)] * a.ndim
        widths[_POS_AXIS] = (0, grow)
        return jnp.pad(a, widths)

    return {k: pad(v) for k, v in cache.items()}


def make_bucketed_generate(cfg, *, max_len: int, max_new_tokens: int,
                           buckets=None, temperature: float = 0.0,
                           top_k: Optional[int] = None,
                           top_p: Optional[float] = None,
                           min_p: Optional[float] = None,
                           compute_dtype=None, kv_dtype=None, ffn=None,
                           attn_kernel="auto", family: str = "gpt"):
    """Host-dispatched bucketed decoder: generate(prepared, ids, rng) ->
    (B, max_new_tokens), token-identical to the family's scan-based
    decoder (generate.make_generate / llama.make_generate) but with the
    cache allocated at `max_len`-serving semantics AND per-step bytes
    tracking the live position via the bucket ladder.

    `max_len` is the serving allocation the ladder tops out at (the
    batcher's preallocation; prompt + max_new_tokens must fit inside
    it). `buckets=None` takes the power-of-two ladder; an explicit
    ascending tuple overrides it; `(max_len,)` degenerates to the
    UNBUCKETED program — the A/B baseline the `decode_bucketing`
    benchmark and the parity tests compare against. `family` picks the
    cached forward: "gpt" (runtime/generate.forward_with_cache) or
    "llama" (models/llama.forward_with_cache — dense caches only; a
    sliding-window config already decodes O(window) on the rolling ring
    and is rejected here).

    rng discipline matches the scan decoders split-for-split, so sampled
    streams agree draw-for-draw, not just greedy ones."""
    from dnn_tpu.runtime.generate import _sample

    if attn_kernel == "auto":
        # bucketing IS the length-aware dispatch: the allocation already
        # tracks the live position, and letting "auto" flip einsum ->
        # Pallas kernel as a stream grows past AUTO_KERNEL_MIN_S would
        # change attention implementations MID-STREAM — breaking the
        # bit-identity-to-the-unbucketed-program guarantee this module
        # documents. Explicit True/"interpret" remain available for
        # callers who accept that trade.
        attn_kernel = False

    if max_new_tokens < 1:
        raise ValueError(
            f"max_new_tokens must be >= 1, got {max_new_tokens}")
    if max_len < 2:
        raise ValueError(f"max_len must be >= 2, got {max_len}")
    if max_len > cfg.block_size:
        raise ValueError(
            f"max_len {max_len} exceeds block_size {cfg.block_size}")
    ladder = (bucket_ladder(max_len) if buckets is None
              else normalize_ladder(buckets, max_len))

    if family == "gpt":
        from dnn_tpu.runtime import generate as _gen

        init_cache = _gen.init_cache

        def _forward(prepared, ids, cache, start):
            return _gen.forward_with_cache(
                prepared, ids, cache, start, cfg=cfg,
                compute_dtype=compute_dtype, ffn=ffn,
                attn_kernel=attn_kernel)
    elif family == "llama":
        from dnn_tpu.models import llama as _llama

        if cfg.sliding_window is not None and not cfg.alt_window:
            raise ValueError(
                "sliding-window configs decode O(window) on the rolling "
                "ring (llama.make_generate) — bucketing targets the "
                "dense full-length cache")
        init_cache = _llama.init_cache

        def _forward(prepared, ids, cache, start):
            return _llama.forward_with_cache(
                prepared, ids, cache, start, cfg=cfg,
                compute_dtype=compute_dtype, ffn=ffn,
                attn_kernel=attn_kernel)
    else:
        raise ValueError(f"unknown family {family!r} (gpt|llama)")

    cache_dtype = (kv_dtype if kv_dtype is not None
                   else (compute_dtype or jnp.float32))

    # donate the prefill cache too: the freshly-initialized allocation is
    # written once and returned — without aliasing the write is a full
    # extra copy of the first bucket (same contract as _step's donation)
    @functools.partial(jax.jit, donate_argnums=(2,))
    def _prefill(prepared, ids, cache):
        logits, cache = _forward(prepared, ids, cache, 0)
        return logits[:, -1], cache

    @functools.partial(jax.jit, donate_argnums=(1,))
    def _step(prepared, cache, tok, pos, rng):
        # one compiled program PER BUCKET (cache shape); `pos` is a
        # traced scalar, so every step of a bucket shares its program.
        # The named_scope is trace-time only: device profiles name each
        # bucket's step program (obs/profile.py)
        bucket = jax.tree.leaves(cache)[0].shape[_POS_AXIS]
        with jax.named_scope(f"decode_buckets.step_b{bucket}"):
            logits, cache = _forward(prepared, tok[:, None], cache, pos)
            rng, sub = jax.random.split(rng)
            nxt = _sample(logits[:, -1], sub, temperature=temperature,
                          top_k=top_k, top_p=top_p, min_p=min_p)
            return cache, nxt, rng

    # no donation: a pad's output never fits the input buffer, and the
    # unusable-donation warning would fire on every bucket crossing
    _grow = jax.jit(pad_cache_to, static_argnums=(1,))

    def generate(prepared, ids, rng):
        ids = jnp.asarray(ids)
        b, t = ids.shape
        if t + max_new_tokens > max_len:
            raise ValueError(
                f"prompt {t} + max_new_tokens {max_new_tokens} exceeds "
                f"max_len {max_len}")
        n = bucket_for(ladder, t)
        cache = init_cache(cfg, b, n, cache_dtype)
        logits_last, cache = _prefill(prepared, ids, cache)
        rng, sub = jax.random.split(rng)
        tok = _sample(logits_last, sub, temperature=temperature,
                      top_k=top_k, top_p=top_p, min_p=min_p)
        toks = [tok]
        # bucket dispatch/grow tallied locally and flushed to the obs
        # registry AFTER the loop — the decode loop itself stays free of
        # per-step lock traffic (dnn_tpu/obs overhead budget)
        dispatch: dict = {}
        grows = 0
        for i in range(max_new_tokens - 1):
            pos = t + i  # this step's cache-write position
            nb = bucket_for(ladder, pos + 1)
            if nb != n:
                cache = _grow(cache, nb)
                n = nb
                grows += 1
            cache, tok, rng = _step(prepared, cache, tok,
                                    jnp.int32(pos), rng)
            dispatch[n] = dispatch.get(n, 0) + 1
            toks.append(tok)
        from dnn_tpu import obs

        m = obs.metrics()
        if m is not None:
            from dnn_tpu.utils.metrics import labeled

            # same metric family as ContinuousBatcher (the README's
            # documented names): bucket-ladder activity is one concept
            # whether the pool or the solo decoder drives it
            for bk, cnt in dispatch.items():
                m.inc(labeled("serving.decode_bucket_dispatch_total",
                              bucket=bk), cnt)
            if grows:
                m.inc("serving.decode_bucket_grow_total", grows)
        return jnp.stack(toks, axis=1)

    generate.buckets = ladder
    return generate
