"""Sliding-window attention (Mistral-class) tests.

The reference has no attention at all (its GPT wrappers are stateless
full-sequence parts, /root/reference/partitions/gpt_model_parts.py), so
the window is pure widening — but it must compose with every runtime the
LLaMA family already rides. Strategy mirrors tests/test_models_llama.py:

  * HF parity: transformers.MistralForCausalLM == our forward on
    converted weights at T > window (the band itself is cross-checked
    against an independent implementation, not just our own mask);
  * masked-vs-rolling equivalence at the codec level (ring occupancy
    predicate == lower-bound mask over a full cache, wrap included);
  * rolling decode == dense-band full recompute, token for token, with
    the stream crossing the window boundary;
  * the continuous batcher (window-masked pool) == solo decode (rolling
    ring) — two different storage designs, one attention function.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dnn_tpu.models import gpt, llama
from dnn_tpu.runtime.kvcache import FloatKV, RollingFloatKV
from dnn_tpu.runtime.paged_kvcache import LayerRows

CFG = llama.PRESETS["mistral-test"]  # L=4, H=4, KV=2, C=64, V=256, W=16
DENSE = dataclasses.replace(CFG, sliding_window=None)


def _params(seed=0):
    return llama.init(jax.random.PRNGKey(seed), CFG)


def test_short_sequences_see_no_window():
    """T <= window: the band covers the whole causal triangle."""
    params = _params()
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, CFG.sliding_window),
                             0, CFG.vocab_size)
    a = llama.make_apply(CFG)(params, ids)
    b = llama.make_apply(DENSE)(params, ids)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_long_sequences_are_banded():
    """T > window: late positions must IGNORE out-of-band tokens.
    Receptive field grows by one window per LAYER (the Mistral design's
    point), so the strict invariance check uses a single-layer config:
    perturbing a token more than W behind the last position leaves its
    logits bit-unchanged, while the dense model shifts."""
    cfg1 = dataclasses.replace(CFG, n_layer=1)
    dense1 = dataclasses.replace(cfg1, sliding_window=None)
    params = llama.init(jax.random.PRNGKey(1), cfg1)
    t = cfg1.sliding_window + 8
    ids = np.asarray(jax.random.randint(jax.random.PRNGKey(2), (1, t),
                                        0, cfg1.vocab_size))
    ids2 = ids.copy()
    ids2[0, 0] = (ids2[0, 0] + 1) % cfg1.vocab_size  # outside the last row's band
    w_a = np.asarray(llama.make_apply(cfg1)(params, jnp.asarray(ids)))
    w_b = np.asarray(llama.make_apply(cfg1)(params, jnp.asarray(ids2)))
    np.testing.assert_array_equal(w_a[0, -1], w_b[0, -1])
    d_a = np.asarray(llama.make_apply(dense1)(params, jnp.asarray(ids)))
    d_b = np.asarray(llama.make_apply(dense1)(params, jnp.asarray(ids2)))
    assert np.abs(d_a[0, -1] - d_b[0, -1]).max() > 0


def test_hf_mistral_logit_parity():
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")

    hf_cfg = llama.to_hf_config(CFG, attn_implementation="eager")
    assert isinstance(hf_cfg, transformers.MistralConfig)
    assert hf_cfg.sliding_window == CFG.sliding_window
    torch.manual_seed(0)
    model = transformers.MistralForCausalLM(hf_cfg).eval()
    sd = {k: v.numpy() for k, v in model.state_dict().items()}

    from dnn_tpu.io.checkpoint import llama_params_from_state_dict

    params = llama_params_from_state_dict(sd)
    t = CFG.sliding_window + 8  # past the window: the band is live
    ids = np.random.RandomState(1).randint(0, CFG.vocab_size, (2, t))
    with torch.no_grad():
        want = model(torch.from_numpy(ids)).logits.numpy()
    got = np.asarray(llama.make_apply(CFG)(params, jnp.asarray(ids)))
    np.testing.assert_allclose(got, want, atol=2e-3, rtol=2e-3)
    np.testing.assert_array_equal(got.argmax(-1), want.argmax(-1))


@pytest.mark.parametrize("p_query", [5, 27])
def test_ring_codec_matches_masked_full_cache(p_query):
    """RollingFloatKV over a W-slot ring == FloatKV(window=W) over a
    full-length cache, fed the same position stream — before the first
    wrap (p=5 < W) and after it (p=27 > W)."""
    B, H, D, W, S = 2, 2, 8, 16, 40
    rng = np.random.RandomState(0)
    # a codec writes into a cache bound to a layer: caches of ONE layer
    full = LayerRows({"k": jnp.zeros((1, B, H, S, D)),
                      "v": jnp.zeros((1, B, H, S, D))}, 0)
    ring = LayerRows({"k": jnp.zeros((1, B, H, W, D)),
                      "v": jnp.zeros((1, B, H, W, D))}, 0)
    flat, roll = FloatKV(window=W), RollingFloatKV(window=W)
    for p in range(p_query + 1):
        k = jnp.asarray(rng.randn(B, H, 1, D), jnp.float32)
        v = jnp.asarray(rng.randn(B, H, 1, D), jnp.float32)
        full = flat.write(full, k, v, p)
        ring = roll.write(ring, k, v, p)
    full, ring = full.read(), ring.read()
    q = jnp.asarray(rng.randn(B, H, 3, D), jnp.float32)  # R=3 folded rows
    pos = jnp.full((B,), p_query, jnp.int32)
    np.testing.assert_allclose(
        np.asarray(flat.attend_rows(q, full, pos)),
        np.asarray(roll.attend_rows(q, ring, pos)), atol=1e-5)


def test_rolling_decode_matches_full_recompute():
    """Greedy rolling-ring decode == dense banded forward recomputed from
    scratch each step; the stream crosses the window boundary (t=12,
    +20 new = 32 total > W=16), so gather, wrap, and ring masking all
    execute."""
    params = _params(seed=5)
    prepared = gpt.prepare_stacked(params, CFG)
    apply_fn = llama.make_apply(CFG)
    ids = jax.random.randint(jax.random.PRNGKey(6), (2, 12), 0,
                             CFG.vocab_size)
    n_new = 20
    gen = llama.make_generate(CFG, max_new_tokens=n_new)
    got = np.asarray(gen(prepared, ids, jax.random.PRNGKey(0)))

    cur = np.asarray(ids)
    want = []
    for _ in range(n_new):
        logits = apply_fn(params, jnp.asarray(cur))
        nxt = np.asarray(jnp.argmax(logits[:, -1], -1)).astype(np.int32)
        want.append(nxt)
        cur = np.concatenate([cur, nxt[:, None]], axis=1)
    np.testing.assert_array_equal(got, np.stack(want, axis=1))


def test_rolling_decode_long_prompt():
    """Prompt itself longer than the window: the ring gather keeps only
    the live band of the prefill."""
    params = _params(seed=6)
    prepared = gpt.prepare_stacked(params, CFG)
    apply_fn = llama.make_apply(CFG)
    ids = jax.random.randint(jax.random.PRNGKey(7), (1, 24), 0,
                             CFG.vocab_size)
    n_new = 8
    got = np.asarray(llama.make_generate(CFG, max_new_tokens=n_new)(
        prepared, ids, jax.random.PRNGKey(0)))
    cur = np.asarray(ids)
    want = []
    for _ in range(n_new):
        logits = apply_fn(params, jnp.asarray(cur))
        nxt = np.asarray(jnp.argmax(logits[:, -1], -1)).astype(np.int32)
        want.append(nxt)
        cur = np.concatenate([cur, nxt[:, None]], axis=1)
    np.testing.assert_array_equal(got, np.stack(want, axis=1))


def test_rolling_int8_tracks_f32():
    params = _params(seed=7)
    prepared = gpt.prepare_stacked(params, CFG)
    ids = jax.random.randint(jax.random.PRNGKey(8), (2, 10), 0,
                             CFG.vocab_size)
    f32 = np.asarray(llama.make_generate(CFG, max_new_tokens=14)(
        prepared, ids, jax.random.PRNGKey(0)))
    i8 = np.asarray(llama.make_generate(CFG, max_new_tokens=14,
                                        kv_dtype="int8")(
        prepared, ids, jax.random.PRNGKey(0)))
    assert (i8 == f32).mean() >= 0.5, "int8 ring cache diverged wholesale"


def test_batcher_windowed_matches_solo_decode():
    """The batcher's window-masked slot pool == the solo rolling decode —
    two storage designs, one attention definition. Streams cross W."""
    from dnn_tpu.runtime.serving import ContinuousBatcher

    params = _params(seed=11)
    prepared = gpt.prepare_stacked(params, CFG)
    prompts = [np.array([5, 3, 7, 1, 2]), np.array([9, 8, 2])]
    n_new = 18  # 5 + 18 = 23 > W=16
    srv = ContinuousBatcher(
        CFG, prepared, slots=2, max_len=32, prompt_pad=8,
        family=llama.LlamaFamilyRows(CFG))
    rids = [srv.submit(p, max_new_tokens=n_new) for p in prompts]
    results = srv.drain()

    gen = llama.make_generate(CFG, max_new_tokens=n_new)
    for rid, p in zip(rids, prompts):
        want = np.asarray(gen(prepared, jnp.asarray(p, jnp.int32)[None, :],
                              jax.random.PRNGKey(0)))[0]
        np.testing.assert_array_equal(results[rid], want)


def test_paged_pool_serves_window_families():
    """Windowed families now ride the paged pool (PagedKV band-masks;
    the batcher reclaims rolled-out blocks — tests/test_paged.py pins
    the full parity/reclaim contract). Token parity vs the dense
    windowed batcher on a short stream here as the family-level pin."""
    from dnn_tpu.runtime.serving import ContinuousBatcher

    params = _params(seed=12)
    prepared = gpt.prepare_stacked(params, CFG)
    prompt = np.asarray([3, 1, 4, 1, 5, 9, 2, 6], np.int32)
    outs = {}
    for paged in (False, True):
        extra = dict(paged_blocks=12, block_len=8) if paged else {}
        srv = ContinuousBatcher(CFG, prepared, slots=2, max_len=48,
                                prompt_pad=8,
                                family=llama.LlamaFamilyRows(CFG),
                                **extra)
        rid = srv.submit(prompt, max_new_tokens=24)  # past window=16
        srv.drain()
        outs[paged] = srv.results[rid]
    np.testing.assert_array_equal(outs[False], outs[True])


def test_seq_parallel_banded_ring_matches_dense():
    """Sliding-window configs now ride the BANDED ring on the
    sequence-parallel forward (parallel/ring_attention.py): the band's
    lower bound masks per ring block and out-of-window hops are skipped
    — logits must match the dense band-masked forward. At t=32 over a
    4-ring, t_local=8 and window=16 gives live hops
    ceil(15/8)+1 = 3 < 4, so the hop-skip is genuinely exercised."""
    from dnn_tpu.parallel.mesh import make_mesh

    mesh = make_mesh({"seq": 4})
    params = _params(seed=13)
    prepared = gpt.prepare_stacked(params, CFG)
    t = 32  # window 16 spans 3 of the 4 shards' blocks
    ids = np.random.RandomState(14).randint(0, CFG.vocab_size, (2, t))
    want = np.asarray(llama.make_apply(CFG)(params, jnp.asarray(ids)))
    got = np.asarray(llama.make_apply_seq_parallel(CFG, mesh)(
        prepared, jnp.asarray(ids)))
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)


def test_seq_sharded_decode_rejects_window():
    from dnn_tpu.parallel.mesh import make_mesh

    mesh = make_mesh({"seq": 2})
    with pytest.raises(ValueError, match="sliding-window"):
        llama.make_generate_seq_sharded(CFG, mesh, max_new_tokens=4)


def test_mistral_preset_registered():
    from dnn_tpu.registry import get_model

    spec = get_model("mistral-7b")
    assert spec.config.sliding_window == 4096
    assert spec.config.n_kv_head == 8
