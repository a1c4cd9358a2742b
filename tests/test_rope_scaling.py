"""Long-context RoPE scaling (linear position interpolation, NTK-aware
base stretch, YaRN's frequencies by parts) for the LLaMA family.

Cross-checks: scale 1 is a bit-exact no-op; linear scaling matches
transformers' rope_scaling={"rope_type": "linear"} logits; the NTK form
matches an HF model whose theta is pre-multiplied by scale^(d/(d-2));
and the cached decode (the path serving actually runs) stays
token-identical to the dense forward under scaling — every RoPE site
goes through one table builder (llama._rope_tables).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dnn_tpu.models import gpt, llama

BASE = llama.PRESETS["llama-test"]


def _params(seed=0, cfg=BASE):
    return llama.init(jax.random.PRNGKey(seed), cfg)


def test_scale_one_is_identity():
    params = _params()
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0,
                             BASE.vocab_size)
    want = np.asarray(llama.make_apply(BASE)(params, ids))
    for kind in ("linear", "ntk", "yarn"):
        cfg = dataclasses.replace(BASE, rope_scaling=kind, rope_scale=1.0)
        got = np.asarray(llama.make_apply(cfg)(params, ids))
        np.testing.assert_array_equal(got, want)


# YaRN over llama-test's 16-wide heads (8 pairs, theta 1e4): trained at 16
# positions, scaled 4x; betas chosen so that the ramp (low 1, high 5) holds
# pairs in all three parts
YARN = llama.Rotation(original_len=16, beta_fast=0.5, beta_slow=0.015,
                      attention_factor=1.3)


def _yarn_cfg(**kw):
    return dataclasses.replace(BASE, block_size=BASE.block_size * 4,
                               rope_scaling="yarn", rope_scale=4.0,
                               rope_yarn=dataclasses.replace(YARN, **kw))


def test_unknown_scaling_rejected():
    cfg = dataclasses.replace(BASE, rope_scaling="longrope", rope_scale=2.0)
    with pytest.raises(ValueError,
                       match="rope_scaling.*'linear', 'ntk' or 'yarn'"):
        llama.make_apply(cfg)(_params(), jnp.zeros((1, 4), jnp.int32))
    with pytest.raises(ValueError, match="no transformers mapping"):
        llama.to_hf_config(cfg)
    # yarn without the length it was trained at
    bare = dataclasses.replace(BASE, rope_scaling="yarn", rope_scale=2.0)
    with pytest.raises(ValueError, match="original_len"):
        llama.make_apply(bare)(_params(), jnp.zeros((1, 4), jnp.int32))
    bad = dataclasses.replace(BASE, rope_scaling="linear", rope_scale=0.5)
    with pytest.raises(ValueError, match="rope_scale"):
        llama.make_apply(bad)(_params(), jnp.zeros((1, 4), jnp.int32))
    # factor set but type forgotten — the likely long-context typo
    half = dataclasses.replace(BASE, rope_scale=4.0)
    with pytest.raises(ValueError, match="no effect"):
        llama.make_apply(half)(_params(), jnp.zeros((1, 4), jnp.int32))


def test_yarn_tables_by_parts():
    """The ramp's bounds, the three parts of the frequencies and the
    attention factor on cos AND sin; the factor inferred where none is
    given; the bounds left as they fall without `truncate`."""
    import math

    cfg, d = _yarn_cfg(), BASE.head_dim
    rot = llama.rotation_of(cfg)
    assert rot == dataclasses.replace(YARN, theta=BASE.rope_theta,
                                      scaling="yarn", scale=4.0)
    low, high = llama.yarn_ramp(rot, d)
    assert (low, high) == (1, 5) and 0 < low < high < d // 2 - 1
    cos, sin = llama._rope_tables(cfg, jnp.asarray([0, 1]))
    assert cos.shape == (2, d)
    np.testing.assert_allclose(np.asarray(cos[0]), 1.3, rtol=1e-6)
    t = np.asarray([BASE.rope_theta ** (-2 * i / d) for i in range(d // 2)])
    r = np.clip((np.arange(d // 2) - low) / (high - low), 0, 1)
    f = np.arctan2(np.asarray(sin[1]), np.asarray(cos[1]))
    np.testing.assert_allclose(f[:d // 2], t * (1 - r) + t / 4 * r,
                               rtol=1e-5)
    np.testing.assert_array_equal(f[:d // 2], f[d // 2:])
    assert f[0] == pytest.approx(t[0]) and f[-1] == pytest.approx(t[-1] / 4)
    inferred = llama._rope_tables(_yarn_cfg(attention_factor=None),
                                  jnp.asarray([0]))[0]
    np.testing.assert_allclose(np.asarray(inferred),
                               0.1 * math.log(4.0) + 1, rtol=1e-6)
    lo, hi = llama.yarn_ramp(dataclasses.replace(rot, truncate=False), d)
    assert low < lo < low + 1 and high - 1 < hi < high


@pytest.mark.parametrize("kind", ["linear", "ntk", "yarn"])
def test_hf_parity_under_scaling(kind):
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")

    # extended context: 2x the trained block size via scaling
    cfg = dataclasses.replace(BASE, block_size=BASE.block_size * 2,
                              rope_scaling=kind, rope_scale=2.0,
                              rope_yarn=YARN if kind == "yarn" else None)
    hf_cfg = llama.to_hf_config(cfg, attn_implementation="eager")
    if kind == "linear":
        assert hf_cfg.rope_scaling["factor"] == 2.0
    elif kind == "yarn":
        assert hf_cfg.rope_scaling["rope_type"] == "yarn"
        assert hf_cfg.rope_scaling["attention_factor"] == 1.3
    else:
        assert hf_cfg.rope_theta > cfg.rope_theta  # pre-multiplied base
    torch.manual_seed(0)
    model = transformers.LlamaForCausalLM(hf_cfg).eval()
    sd = {k: v.numpy() for k, v in model.state_dict().items()}

    from dnn_tpu.io.checkpoint import llama_params_from_state_dict

    params = llama_params_from_state_dict(sd)
    t = BASE.block_size + 16  # past the ORIGINAL context length
    ids = np.random.RandomState(1).randint(0, cfg.vocab_size, (2, t))
    with torch.no_grad():
        want = model(torch.from_numpy(ids)).logits.numpy()
    got = np.asarray(llama.make_apply(cfg)(params, jnp.asarray(ids)))
    np.testing.assert_allclose(got, want, atol=2e-3, rtol=2e-3)
    np.testing.assert_array_equal(got.argmax(-1), want.argmax(-1))


@pytest.mark.parametrize("kind", ["linear", "ntk", "yarn"])
def test_cached_decode_matches_dense_under_scaling(kind):
    """Greedy cached decode past the original context == full dense
    recompute — the decode path's per-position tables scale exactly like
    the prefill's."""
    cfg = dataclasses.replace(BASE, block_size=BASE.block_size * 2,
                              rope_scaling=kind, rope_scale=2.0,
                              rope_yarn=YARN if kind == "yarn" else None)
    params = _params(seed=3, cfg=cfg)
    prepared = gpt.prepare_stacked(params, cfg)
    apply_fn = llama.make_apply(cfg)
    t = BASE.block_size - 2  # prompt near the original limit
    ids = jax.random.randint(jax.random.PRNGKey(4), (1, t), 0,
                             cfg.vocab_size)
    n_new = 8  # decode crosses the original block_size
    got = np.asarray(llama.make_generate(cfg, max_new_tokens=n_new)(
        prepared, ids, jax.random.PRNGKey(0)))
    cur = np.asarray(ids)
    want = []
    for _ in range(n_new):
        logits = apply_fn(params, jnp.asarray(cur))
        nxt = np.asarray(jnp.argmax(logits[:, -1], -1)).astype(np.int32)
        want.append(nxt)
        cur = np.concatenate([cur, nxt[:, None]], axis=1)
    np.testing.assert_array_equal(got, np.stack(want, axis=1))


def test_batcher_scaled_matches_solo():
    """The batcher's per-slot rope (LlamaFamilyRows._block_rows) uses the
    same scaled tables as the solo decoder."""
    from dnn_tpu.runtime.serving import ContinuousBatcher

    cfg = dataclasses.replace(BASE, rope_scaling="linear", rope_scale=2.0)
    params = _params(seed=5, cfg=cfg)
    prepared = gpt.prepare_stacked(params, cfg)
    prompt = np.array([5, 3, 7, 1, 2])
    srv = ContinuousBatcher(cfg, prepared, slots=2, max_len=32,
                            prompt_pad=8, family=llama.LlamaFamilyRows(cfg))
    rid = srv.submit(prompt, max_new_tokens=6)
    got = srv.drain()[rid]
    want = np.asarray(llama.make_generate(cfg, max_new_tokens=6)(
        prepared, jnp.asarray(prompt, jnp.int32)[None, :],
        jax.random.PRNGKey(0)))[0]
    np.testing.assert_array_equal(got, want)
