"""Prefix-cache tests: requests sharing a prompt prefix must reuse cached
K/V chunks instead of re-prefilling them — with token output IDENTICAL to
the uncached batcher (the reuse is a pure work-savings, never a numerics
change), and the three-program compile contract intact."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dnn_tpu.models import gpt
from dnn_tpu.runtime.serving import ContinuousBatcher

CFG = gpt.PRESETS["gpt2-test"]  # block_size=64
P_PAD = 8


def _prepared(seed=0):
    return gpt.prepare_stacked(gpt.init(jax.random.PRNGKey(seed), CFG), CFG)


def _prompt(prefix_tokens, suffix_tokens):
    return np.concatenate([prefix_tokens, suffix_tokens]).astype(np.int32)


PREFIX = np.arange(1, 17, dtype=np.int32)          # 16 tokens = 2 full chunks
SUF_A = np.array([21, 22, 23], np.int32)
SUF_B = np.array([31, 32, 33, 34], np.int32)


def test_shared_prefix_parity_and_chunk_savings():
    prepared = _prepared()

    def run(cache_entries):
        srv = ContinuousBatcher(CFG, prepared, slots=2, max_len=48,
                                prompt_pad=P_PAD,
                                prefix_cache=cache_entries)
        r1 = srv.submit(_prompt(PREFIX, SUF_A), max_new_tokens=6)
        chunks_first = srv.prefill_chunks_run
        r2 = srv.submit(_prompt(PREFIX, SUF_B), max_new_tokens=6)
        chunks_second = srv.prefill_chunks_run - chunks_first
        out = srv.drain()
        return out[r1], out[r2], chunks_first, chunks_second, srv

    a0, b0, c1_off, c2_off, _ = run(0)
    a1, b1, c1_on, c2_on, srv = run(8)

    # parity: cached == uncached, token for token
    np.testing.assert_array_equal(a1, a0)
    np.testing.assert_array_equal(b1, b0)

    # measured prefill-work drop: request 2 shares 2 full chunks with
    # request 1 and must re-run only its tail chunk
    assert c1_on == c1_off == 3   # 19 tokens / pad 8 -> 3 chunks
    assert c2_off == 3            # uncached: full re-prefill
    assert c2_on == 1, f"expected 1 chunk after prefix hit, ran {c2_on}"
    assert srv.prefix_hits == 1


def test_identical_full_chunk_prompt_runs_zero_chunks():
    """A prompt that is exactly N full chunks, submitted twice: the second
    submission reuses everything including the first-token logits."""
    prepared = _prepared(seed=1)
    prompt = np.arange(1, 17, dtype=np.int32)  # exactly 2 chunks
    srv = ContinuousBatcher(CFG, prepared, slots=2, max_len=48,
                            prompt_pad=P_PAD, prefix_cache=8)
    r1 = srv.submit(prompt, max_new_tokens=5)
    n1 = srv.prefill_chunks_run
    r2 = srv.submit(prompt, max_new_tokens=5)
    n2 = srv.prefill_chunks_run - n1
    out = srv.drain()
    assert n1 == 2 and n2 == 0
    np.testing.assert_array_equal(out[r1], out[r2])  # greedy determinism

    # uncached oracle for absolute correctness
    ref = ContinuousBatcher(CFG, prepared, slots=1, max_len=48,
                            prompt_pad=P_PAD)
    rr = ref.submit(prompt, max_new_tokens=5)
    np.testing.assert_array_equal(out[r1], ref.drain()[rr])


def test_prefix_cache_with_int8_cache():
    """The int8 codec's row pytree (k/v/ks/vs) caches and copies the same
    way; parity against the uncached int8 batcher."""
    prepared = _prepared(seed=2)
    prompt = _prompt(PREFIX, SUF_A)

    def run(**kw):
        srv = ContinuousBatcher(CFG, prepared, slots=2, max_len=48,
                                prompt_pad=P_PAD, kv_dtype="int8", **kw)
        a = srv.submit(prompt, max_new_tokens=4)
        b = srv.submit(prompt, max_new_tokens=4)
        out = srv.drain()
        return out[a], out[b]

    (a0, b0), (a1, b1) = run(), run(prefix_cache=4)
    np.testing.assert_array_equal(a1, a0)
    np.testing.assert_array_equal(b1, b0)


def test_lru_eviction():
    prepared = _prepared(seed=3)
    srv = ContinuousBatcher(CFG, prepared, slots=1, max_len=48,
                            prompt_pad=P_PAD, prefix_cache=1)
    p1 = np.arange(1, 9, dtype=np.int32)    # 1 full chunk
    p2 = np.arange(50, 58, dtype=np.int32)  # different chunk
    srv.submit(p1, max_new_tokens=2)
    srv.drain()
    srv.submit(p2, max_new_tokens=2)        # evicts p1 (capacity 1)
    srv.drain()
    n = srv.prefill_chunks_run
    srv.submit(p1, max_new_tokens=2)        # p1 must re-run its chunk
    srv.drain()
    assert srv.prefill_chunks_run - n == 1
    assert srv.prefix_hits == 0


def test_compile_count_unchanged():
    """The prefix cache must not add compiled programs: chunk, finish and
    decode each stay at ONE jit cache entry through mixed cached/uncached
    traffic (incl. the whole-prompt-cached logits rebuild)."""
    prepared = _prepared(seed=4)
    srv = ContinuousBatcher(CFG, prepared, slots=2, max_len=48,
                            prompt_pad=P_PAD, prefix_cache=8)
    full = np.arange(1, 17, dtype=np.int32)       # exact chunks
    tailed = _prompt(PREFIX, SUF_B)               # padded tail
    for p in (full, full, tailed, tailed):
        srv.submit(p, max_new_tokens=3)
        srv.drain()
    assert srv._prefill_chunk._cache_size() == 1
    assert srv._prefill_finish._cache_size() == 1
    assert srv._decode._cache_size() == 1


# ----------------------------------------------------------------------
# prefix-cache counters + gauge on /metrics
# ----------------------------------------------------------------------

def test_prefix_counters_and_hit_ratio_gauge():
    cfg = gpt.GPTConfig(block_size=32, vocab_size=64, n_layer=1,
                        n_head=1, n_embd=16)
    prepared = gpt.prepare_stacked(gpt.init(jax.random.PRNGKey(0), cfg),
                                   cfg)
    srv = ContinuousBatcher(cfg, prepared, slots=2, max_len=24,
                            prompt_pad=4, prefix_cache=2)
    p = np.arange(1, 9, dtype=np.int32)  # 2 full chunks
    srv.submit(p, max_new_tokens=2)
    srv.drain()
    assert (srv.prefix_hits, srv.prefix_misses) == (0, 1)
    assert srv._prefix_ratio_read() == 0.0
    srv.submit(p, max_new_tokens=2)  # identical prompt: full-chunk hit
    srv.drain()
    assert (srv.prefix_hits, srv.prefix_misses) == (1, 1)
    assert srv._prefix_ratio_read() == pytest.approx(0.5)
    # the gauge is registered (weakly) under the public name
    assert "dnn_tpu_prefix_hit_ratio" in srv._obs_gauges
    assert srv._obs_gauges["dnn_tpu_prefix_hit_ratio"]() == \
        pytest.approx(0.5)
    # capacity 2: a different 2-chunk prompt's inserts evict
    before = srv.prefix_evictions
    srv.submit(np.arange(20, 28, dtype=np.int32), max_new_tokens=2)
    srv.drain()
    assert srv.prefix_evictions > before
    # the registry counters moved with the attrs
    from dnn_tpu import obs

    m = obs.metrics()
    if m is not None:
        snap = m.snapshot()["counters"]
        assert snap.get("serving.prefix_misses_total", 0) >= 1
        assert snap.get("serving.prefix_evictions_total", 0) >= 1


def test_prefix_ratio_gauge_absent_without_cache():
    cfg = gpt.GPTConfig(block_size=32, vocab_size=64, n_layer=1,
                        n_head=1, n_embd=16)
    prepared = gpt.prepare_stacked(gpt.init(jax.random.PRNGKey(0), cfg),
                                   cfg)
    srv = ContinuousBatcher(cfg, prepared, slots=1, max_len=16,
                            prompt_pad=4)
    assert "dnn_tpu_prefix_hit_ratio" not in srv._obs_gauges
