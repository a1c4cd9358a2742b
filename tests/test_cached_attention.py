"""Pallas cached-attention kernel tests (serving decode/prefill hot loop).

The kernel's distinguishing features over ops/pallas/flash_attention.py —
RUNTIME position limits (one compiled program for every chunk start and
slot position) and fused int8-cache dequant — are exercised in Pallas
interpreter mode so CPU CI runs the real kernel logic, then integrated
through the full decode loop (make_generate / ContinuousBatcher with
attn_kernel="interpret") with token parity against the einsum path."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dnn_tpu.models import gpt
from dnn_tpu.ops.pallas.cached_attention import (
    cached_attention,
    decode_attention,
    reference_cached_attention,
    reference_decode_attention,
)

RNG = np.random.default_rng(0)


def _rand(shape, dtype=jnp.float32):
    return jnp.asarray(RNG.standard_normal(shape), dtype)


def test_kernel_decode_float_and_bf16():
    B, H, S, D = 3, 4, 256, 64
    q = _rand((B, H, 1, D))
    k, v = _rand((B, H, S, D)), _rand((B, H, S, D))
    pos = jnp.asarray([5, 130, 255], jnp.int32)  # incl. first/last block
    for cast in (jnp.float32, jnp.bfloat16):
        want = reference_cached_attention(q, k.astype(cast), v.astype(cast), pos)
        got = cached_attention(q, k.astype(cast), v.astype(cast), pos,
                               interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5, rtol=1e-5)


def test_kernel_decode_int8_scales():
    B, H, S, D = 2, 4, 256, 64
    q = _rand((B, H, 1, D))
    kq = jnp.asarray(RNG.integers(-127, 128, (B, H, S, D)), jnp.int8)
    vq = jnp.asarray(RNG.integers(-127, 128, (B, H, S, D)), jnp.int8)
    ks = jnp.asarray(RNG.uniform(0.005, 0.02, (B, H, S)), jnp.float32)
    vs = jnp.asarray(RNG.uniform(0.005, 0.02, (B, H, S)), jnp.float32)
    pos = jnp.asarray([7, 200], jnp.int32)
    want = reference_cached_attention(q, kq, vq, pos, ks=ks, vs=vs)
    got = cached_attention(q, kq, vq, pos, ks=ks, vs=vs, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-4, rtol=1e-4)


def test_kernel_prefill_chunk_at_dynamic_start():
    """The flash-can't-do-this case: a (T) query block whose absolute start
    is a runtime value — same compiled kernel for chunk 0 and chunk N."""
    B, H, S, D, T = 2, 4, 256, 64, 128
    q = _rand((B, H, T, D))
    k, v = _rand((B, H, S, D)), _rand((B, H, S, D))
    for start in (0, 128):
        pos = jnp.full((B,), start, jnp.int32)
        want = reference_cached_attention(q, k, v, pos)
        got = cached_attention(q, k, v, pos, interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("t,s_len,tiles", [
    (256, 1024, (256, 1024)),  # GPT-2 Large's chunk at a v5e's ridge
    (64, 1024, (128, 1024)), (5, 1024, (128, 1024)),  # own tile under 128
    (512, 2048, (256, 1024)), (384, 1536, (128, 512)),
    (256, 640, (256, 128)), (128, 100, (128, 128))])  # no tiling: the plain form
def test_chunk_tiles(t, s_len, tiles):
    from dnn_tpu.ops.pallas.cached_attention import chunk_tiles

    assert chunk_tiles(t, s_len) == dict(zip(("block_q", "block_s"), tiles))


@pytest.mark.parametrize("quant", [False, True], ids=["float", "int8"])
@pytest.mark.parametrize("t,start", [(256, 0), (256, 256), (256, 768),
                                     (64, 0), (64, 448), (5, 700)])
def test_kernel_chunk_tiles_at_dynamic_start(t, start, quant):
    """ISSUE 67: the GPT codec's tiles (`chunk_tiles`) — the whole
    1024-position row in ONE column tile, heads of 64 — for a ridge-wide
    chunk, a narrow one and a verify's few rows, at the row's start,
    inside it and at its end, float and int8 with scales: the plain
    form's rows."""
    from dnn_tpu.ops.pallas.cached_attention import chunk_tiles

    B, H, S, D = 1, 2, 1024, 64
    q = _rand((B, H, t, D))
    pos = jnp.full((B,), start, jnp.int32)
    if quant:
        k, v = (jnp.asarray(RNG.integers(-127, 128, (B, H, S, D)), jnp.int8)
                for _ in range(2))
        scales = dict(zip(("ks", "vs"), (jnp.asarray(
            RNG.uniform(0.005, 0.02, (B, H, S)), jnp.float32)
            for _ in range(2))))
    else:
        k, v, scales = _rand((B, H, S, D)), _rand((B, H, S, D)), {}
    want = reference_cached_attention(q, k, v, pos, **scales)
    got = cached_attention(q, k, v, pos, interpret=True, **scales,
                           **chunk_tiles(t, S))
    tol = 1e-4 if quant else 1e-5
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=tol, rtol=tol)


def test_kernel_nontiling_falls_back():
    B, H, S, D = 2, 2, 100, 64  # S % 128 != 0
    q = _rand((B, H, 1, D))
    k, v = _rand((B, H, S, D)), _rand((B, H, S, D))
    pos = jnp.asarray([5, 99], jnp.int32)
    got = cached_attention(q, k, v, pos)  # silently reference
    want = reference_cached_attention(q, k, v, pos)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-6, rtol=1e-6)


# ----------------------------------------------------------------------
# decode-specialized kernel (heads folded into one program per slot;
# clamped index map skips dead cache blocks)
# ----------------------------------------------------------------------


def test_decode_kernel_r1_positions_span_blocks():
    """R=1 (plain MHA decode rows) at positions inside the first block,
    mid-buffer, and the last column — incl. limits that leave most blocks
    dead (the clamped index map must not corrupt the live prefix)."""
    B, H, S, D = 4, 4, 512, 64
    q = _rand((B, H, 1, D))
    k, v = _rand((B, H, S, D)), _rand((B, H, S, D))
    pos = jnp.asarray([3, 127, 128, 511], jnp.int32)
    for cast in (jnp.float32, jnp.bfloat16):
        want = reference_decode_attention(q, k.astype(cast), v.astype(cast),
                                          pos)
        got = decode_attention(q, k.astype(cast), v.astype(cast), pos,
                               interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5, rtol=1e-5)


def test_decode_kernel_gqa_rows_share_limit():
    """R=G>1 (the LLaMA GQA fold): every group row of a slot shares the
    slot's limit — the case the general kernel's +row contract excludes."""
    B, KV, G, S, D = 2, 2, 4, 256, 64
    q = _rand((B, KV, G, D))
    k, v = _rand((B, KV, S, D)), _rand((B, KV, S, D))
    pos = jnp.asarray([9, 255], jnp.int32)
    want = reference_decode_attention(q, k, v, pos)
    got = decode_attention(q, k, v, pos, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


def test_decode_kernel_int8_scales():
    B, H, S, D = 2, 4, 256, 64
    q = _rand((B, H, 1, D))
    kq = jnp.asarray(RNG.integers(-127, 128, (B, H, S, D)), jnp.int8)
    vq = jnp.asarray(RNG.integers(-127, 128, (B, H, S, D)), jnp.int8)
    ks = jnp.asarray(RNG.uniform(0.005, 0.02, (B, H, S)), jnp.float32)
    vs = jnp.asarray(RNG.uniform(0.005, 0.02, (B, H, S)), jnp.float32)
    pos = jnp.asarray([7, 200], jnp.int32)
    want = reference_decode_attention(q, kq, vq, pos, ks=ks, vs=vs)
    got = decode_attention(q, kq, vq, pos, ks=ks, vs=vs, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-4, rtol=1e-4)


def test_decode_kernel_block_size_fallback():
    """S=128 engages the 128 block; S=96 doesn't tile -> reference path."""
    B, H, D = 2, 2, 64
    for S in (128, 96):
        q = _rand((B, H, 1, D))
        k, v = _rand((B, H, S, D)), _rand((B, H, S, D))
        pos = jnp.asarray([5, S - 1], jnp.int32)
        want = reference_decode_attention(q, k, v, pos)
        got = decode_attention(q, k, v, pos,
                               interpret=True if S == 128 else None)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5, rtol=1e-5)


def test_llama_generate_with_kernel_matches_einsum():
    """LLaMA solo decode (GQA fold through attend_rows) with
    attn_kernel='interpret': greedy tokens equal the einsum path. Cache
    length 120+8=128 tiles the kernel's 128 block so decode steps really
    run it (llama-test's block_size=64 cache would silently fall back)."""
    from dnn_tpu.models import llama

    cfg = llama.LlamaConfig(block_size=256, vocab_size=256, n_layer=2,
                            n_head=4, n_kv_head=2, n_embd=64, d_ff=128)
    prepared = gpt.prepare_stacked(
        llama.init(jax.random.PRNGKey(0), cfg), cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 120), 0,
                                cfg.vocab_size, dtype=jnp.int32)
    want = llama.make_generate(cfg, max_new_tokens=8)(
        prepared, prompt, jax.random.PRNGKey(2))
    got = llama.make_generate(cfg, max_new_tokens=8,
                              attn_kernel="interpret")(
        prepared, prompt, jax.random.PRNGKey(2))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_llama_batcher_with_kernel_matches_einsum():
    """LlamaFamilyRows(attn_kernel='interpret') through the
    ContinuousBatcher: R=G decode rows hit the decode kernel; tokens equal
    the plain batcher."""
    from dnn_tpu.models import llama
    from dnn_tpu.runtime.serving import ContinuousBatcher

    cfg = llama.LlamaConfig(block_size=256, vocab_size=256, n_layer=2,
                            n_head=4, n_kv_head=2, n_embd=64, d_ff=128)
    prepared = gpt.prepare_stacked(
        llama.init(jax.random.PRNGKey(3), cfg), cfg)
    prompt = np.asarray(jax.random.randint(
        jax.random.PRNGKey(4), (100,), 0, cfg.vocab_size, dtype=jnp.int32))

    def run(**kw):
        # max_len 128 tiles the decode kernel's 128 block
        srv = ContinuousBatcher(
            cfg, prepared, slots=2, max_len=128, prompt_pad=128,
            family=llama.LlamaFamilyRows(cfg, **kw))
        rid = srv.submit(prompt, max_new_tokens=6)
        return srv.drain()[rid]

    np.testing.assert_array_equal(run(attn_kernel="interpret"), run())


# ----------------------------------------------------------------------
# integration: the real kernel inside the full decode loop
# ----------------------------------------------------------------------

KCFG = gpt.GPTConfig(block_size=128, vocab_size=128, n_layer=2, n_head=4,
                     n_embd=64)


def _kprepared(seed=0):
    return gpt.prepare_stacked(gpt.init(jax.random.PRNGKey(seed), KCFG), KCFG)


def test_generate_with_kernel_matches_einsum_path():
    """make_generate(attn_kernel='interpret') greedy tokens == the einsum
    decode on the same weights/prompt (prefill T=120 tiles the S=128 cache,
    decode runs T=1 rows)."""
    from dnn_tpu.runtime.generate import make_generate

    prepared = _kprepared()
    prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 120), 0,
                                KCFG.vocab_size, dtype=jnp.int32)
    want = make_generate(KCFG, max_new_tokens=8)(
        prepared, prompt, jax.random.PRNGKey(2))
    got = make_generate(KCFG, max_new_tokens=8, attn_kernel="interpret")(
        prepared, prompt, jax.random.PRNGKey(2))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_batcher_with_kernel_matches_einsum_batcher():
    """ContinuousBatcher(attn_kernel='interpret'): chunked prefill AND
    per-row decode run the kernel; greedy results equal the plain batcher."""
    from dnn_tpu.runtime.serving import ContinuousBatcher

    prepared = _kprepared(seed=3)
    prompts = [np.asarray(jax.random.randint(
        jax.random.PRNGKey(10 + i), (100 + i,), 0, KCFG.vocab_size,
        dtype=jnp.int32)) for i in range(2)]

    def run(**kw):
        srv = ContinuousBatcher(KCFG, prepared, slots=2, max_len=128,
                                prompt_pad=128, **kw)
        rids = [srv.submit(p, max_new_tokens=6) for p in prompts]
        out = srv.drain()
        return [out[r] for r in rids]

    want = run()
    got = run(attn_kernel="interpret")
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a, b)


def test_batcher_with_kernel_int8_cache():
    """int8 cache + kernel: the fused-dequant path through the live pool;
    tokens equal the einsum int8 batcher (identical quantization math)."""
    from dnn_tpu.runtime.serving import ContinuousBatcher

    prepared = _kprepared(seed=4)
    prompt = np.asarray(jax.random.randint(
        jax.random.PRNGKey(20), (64,), 0, KCFG.vocab_size, dtype=jnp.int32))

    def run(**kw):
        srv = ContinuousBatcher(KCFG, prepared, slots=1, max_len=128,
                                prompt_pad=128, kv_dtype="int8", **kw)
        rid = srv.submit(prompt, max_new_tokens=5)
        return srv.drain()[rid]

    np.testing.assert_array_equal(run(attn_kernel="interpret"), run())
