"""Mixtral (LLaMA block + sparse MoE MLP, models/llama_moe.py): HF
parity at no-drop capacity, cached-decode and batcher parity via the
llama `ffn` hook, and the capacity-drop fallback."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dnn_tpu.models import gpt, llama, llama_moe

CFG = llama_moe.PRESETS["mixtral-test"]


def _params(seed=0):
    return llama_moe.init(jax.random.PRNGKey(seed), CFG)


def test_structure():
    p = _params()
    blk = p["h_0"]
    assert "mlp" not in blk and "moe" in blk
    assert blk["moe"]["wg"].shape == (CFG.n_expert, CFG.n_embd, CFG.d_ff)
    assert blk["moe"]["router"]["kernel"].shape == (CFG.n_embd,
                                                   CFG.n_expert)
    assert "lm_head" in p  # mixtral does not tie


def test_hf_mixtral_parity():
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")

    hf_cfg = llama_moe.to_hf_config(CFG, attn_implementation="eager")
    torch.manual_seed(0)
    model = transformers.MixtralForCausalLM(hf_cfg).eval()
    sd = {k: v.numpy() for k, v in model.state_dict().items()}
    params = llama_moe.params_from_state_dict(sd)

    ids = np.random.RandomState(1).randint(0, CFG.vocab_size, (2, 16))
    with torch.no_grad():
        want = model(torch.from_numpy(ids)).logits.numpy()
    got = np.asarray(llama_moe.make_apply(CFG)(params, jnp.asarray(ids)))
    np.testing.assert_allclose(got, want, atol=3e-3, rtol=3e-3)
    np.testing.assert_array_equal(got.argmax(-1), want.argmax(-1))

    # greedy cached decode == HF generate (experts route per decode step)
    prompt = np.random.RandomState(2).randint(0, CFG.vocab_size, (1, 9))
    n_new = 10
    with torch.no_grad():
        hf_out = model.generate(torch.from_numpy(prompt),
                                max_new_tokens=n_new, do_sample=False,
                                pad_token_id=0)
    want_toks = hf_out.numpy()[0, 9:]
    prepared = gpt.prepare_stacked(params, CFG)
    got_toks = np.asarray(llama_moe.make_generate(
        CFG, max_new_tokens=n_new)(prepared, jnp.asarray(prompt),
                                   jax.random.PRNGKey(0)))[0]
    np.testing.assert_array_equal(got_toks, want_toks)


def test_generate_matches_stepwise_forward():
    p = _params(seed=3)
    prepared = gpt.prepare_stacked(p, CFG)
    apply = llama_moe.make_apply(CFG)
    prompt = np.random.RandomState(4).randint(0, CFG.vocab_size, (1, 8))
    n_new = 8
    ids = list(prompt[0])
    for _ in range(n_new):
        logits = np.asarray(apply(p, jnp.asarray([ids])))
        ids.append(int(logits[0, -1].argmax()))
    want = np.asarray(ids[len(prompt[0]):])
    got = np.asarray(llama_moe.make_generate(CFG, max_new_tokens=n_new)(
        prepared, jnp.asarray(prompt), jax.random.PRNGKey(0)))[0]
    np.testing.assert_array_equal(got, want)


def test_batcher_matches_solo():
    from dnn_tpu.runtime.serving import ContinuousBatcher

    p = _params(seed=5)
    prepared = gpt.prepare_stacked(p, CFG)
    prompts = [np.asarray([3, 1, 4, 1, 5]), np.asarray([9, 2, 6, 5, 3,
                                                        5, 8, 9])]
    n_new = 7
    solo = llama_moe.make_generate(CFG, max_new_tokens=n_new)
    want = [np.asarray(solo(prepared, jnp.asarray(pr[None]),
                            jax.random.PRNGKey(0)))[0] for pr in prompts]
    srv = ContinuousBatcher(CFG, prepared, slots=2, max_len=CFG.block_size,
                            prompt_pad=8,
                            family=llama_moe.family_rows(CFG))
    rids = [srv.submit(pr, max_new_tokens=n_new) for pr in prompts]
    srv.drain()
    for rid, w in zip(rids, want):
        np.testing.assert_array_equal(srv.results[rid], w)


@pytest.mark.parametrize("path", ["single_device", "ep_dense_twin"])
def test_capacity_acts_only_on_the_ep_path(path):
    """A starved capacity factor changes nothing on one device — the
    grouped experts are drop-free and never read it — and on the
    expert-parallel path's dense twin (`groups=n`, static capacity per
    routing group) it still runs, dropped tokens passing through on the
    residual, with a different output than full capacity. (Before PR 26
    this pinned dropping on the single-device path.)"""
    p = _params(seed=6)
    tight = dataclasses.replace(CFG, capacity_factor=0.25)
    ids = jnp.asarray(
        np.random.RandomState(7).randint(0, CFG.vocab_size, (2, 16)))
    if path == "single_device":
        full = np.asarray(llama_moe.make_apply(CFG)(p, ids))
        starved = np.asarray(llama_moe.make_apply(tight)(p, ids))
        np.testing.assert_array_equal(starved, full)
        return
    full, dropped = (np.asarray(llama.make_apply(
        c, ffn=llama_moe.make_ffn(c, groups=2))(p, ids))
        for c in (CFG, tight))
    assert np.isfinite(dropped).all()
    assert np.abs(full - dropped).max() > 1e-6


def test_registry_and_partition_compose():
    """Multi-stage relay partitioning works like any llama family — the
    stage scan resolves the expert hook from the config."""
    from dnn_tpu.registry import get_model

    spec = get_model("mixtral-test")
    p = spec.init(jax.random.PRNGKey(8))
    ids = np.random.RandomState(9).randint(0, CFG.vocab_size, (1, 8))
    out = np.asarray(spec.apply(p, jnp.asarray(ids)))
    assert out.shape == (1, 8, CFG.vocab_size)
    for parts in (2, 3):
        x = jnp.asarray(ids)
        for st in spec.partition(parts):
            x = st.apply(st.slice_params(p), x)
        np.testing.assert_allclose(np.asarray(x), out, atol=1e-5,
                                   rtol=1e-5)


def test_config_resolved_hook_reaches_every_dispatcher():
    """Beam, the embedding extractor, and plain llama.make_apply must
    all work on Mixtral params WITHOUT llama_moe-specific wiring —
    MixtralConfig.default_ffn is the one resolution point."""
    from dnn_tpu.models import llama
    from dnn_tpu.runtime.beam import make_beam_generate
    from dnn_tpu.runtime.embeddings import make_embed

    p = _params(seed=10)
    prepared = gpt.prepare_stacked(p, CFG)
    ids = np.random.RandomState(11).randint(0, CFG.vocab_size, (1, 8))

    # plain llama entry points resolve the hook from the config
    via_llama = np.asarray(llama.make_apply(CFG)(p, jnp.asarray(ids)))
    via_moe = np.asarray(llama_moe.make_apply(CFG)(p, jnp.asarray(ids)))
    np.testing.assert_array_equal(via_llama, via_moe)

    greedy = np.asarray(llama_moe.make_generate(CFG, max_new_tokens=5)(
        prepared, jnp.asarray(ids), jax.random.PRNGKey(0)))
    b1 = np.asarray(make_beam_generate(CFG, max_new_tokens=5,
                                       beam_size=1)(prepared,
                                                    jnp.asarray(ids)))
    np.testing.assert_array_equal(b1, greedy)

    vec = np.asarray(make_embed(CFG, pooling="mean")(
        prepared, ids.astype(np.int32), np.asarray([8], np.int32)))
    assert vec.shape == (1, CFG.n_embd) and np.isfinite(vec).all()

    # seq/pipeline paths reject MoE explicitly rather than mis-routing
    from dnn_tpu.parallel.mesh import SEQ_AXIS, make_mesh

    mesh = make_mesh({SEQ_AXIS: jax.device_count()})
    with pytest.raises(ValueError, match="MoE"):
        llama.make_apply_seq_parallel(CFG, mesh)
    with pytest.raises(ValueError, match="MoE"):
        llama.LlamaPipelineFamily(CFG)


def test_ep_matches_grouped_dense():
    """Expert-parallel Mixtral over the expert axis == the dense forward
    with matching routing groups (the GShard parity contract, llama-MoE
    edition): tokens cross devices via all_to_all, logits must be
    identical."""
    from dnn_tpu.models import llama
    from dnn_tpu.parallel.mesh import EXPERT_AXIS, make_mesh

    n = 4
    assert CFG.n_expert % n == 0
    mesh = make_mesh({EXPERT_AXIS: n}, jax.devices()[:n])
    p = _params(seed=12)
    ids = np.random.RandomState(13).randint(0, CFG.vocab_size, (n * 2, 8))

    want = np.asarray(llama.make_apply(
        CFG, ffn=llama_moe.make_ffn(CFG, groups=n))(p, jnp.asarray(ids)))
    got = np.asarray(llama_moe.make_apply_ep(CFG, mesh)(
        p, jnp.asarray(ids)))
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)

    with pytest.raises(ValueError, match="divisible"):
        llama_moe.make_apply_ep(CFG, mesh)(p, jnp.asarray(ids[:3]))


def test_ep_decode_matches_solo_grouped():
    """EP KV-cache generation == the solo decoder with matching routing
    groups, token-for-token (greedy) — the GPT-MoE family's EP decode
    parity contract (tests/test_generate_moe.py) extended to Mixtral."""
    from dnn_tpu.models import llama
    from dnn_tpu.parallel.mesh import EXPERT_AXIS, make_mesh

    n = 4
    mesh = make_mesh({EXPERT_AXIS: n}, jax.devices()[:n])
    p = _params(seed=16)
    prepared = gpt.prepare_stacked(p, CFG)
    prompt = np.random.RandomState(17).randint(0, CFG.vocab_size, (n * 2, 6))
    n_new = 5
    want = np.asarray(llama.make_generate(
        CFG, max_new_tokens=n_new, ffn=llama_moe.make_ffn(CFG, groups=n))(
        prepared, jnp.asarray(prompt), jax.random.PRNGKey(18)))
    got = np.asarray(llama_moe.make_generate_ep(
        CFG, mesh, max_new_tokens=n_new)(
        prepared, jnp.asarray(prompt), jax.random.PRNGKey(18)))
    np.testing.assert_array_equal(got, want)

    with pytest.raises(ValueError, match="divisible"):
        llama_moe.make_generate_ep(CFG, mesh, max_new_tokens=2)(
            prepared, jnp.asarray(prompt[:3]), jax.random.PRNGKey(0))


def test_ep_pp_decode_matches_solo_grouped():
    """EP x PP 2D Mixtral decode ({stage, expert} mesh: all_to_all expert
    dispatch inside every stage-ring sub-step) == the solo decoder with
    matching routing groups, token-for-token."""
    from dnn_tpu.models import llama
    from dnn_tpu.parallel.mesh import EXPERT_AXIS, STAGE_AXIS, make_mesh
    from dnn_tpu.runtime.generate import prepare_pipeline_stacked

    stages, n_exp = 3, 2  # n_layer=3 stages x 2 expert columns
    assert CFG.n_layer % stages == 0 and CFG.n_expert % n_exp == 0
    mesh = make_mesh({STAGE_AXIS: stages, EXPERT_AXIS: n_exp},
                     jax.devices()[:stages * n_exp])
    p = _params(seed=19)
    prepared = gpt.prepare_stacked(p, CFG)
    stage_blocks, aux = prepare_pipeline_stacked(prepared, CFG, mesh)
    prompt = np.random.RandomState(20).randint(0, CFG.vocab_size,
                                               (n_exp * 2, 6))
    n_new = 5
    want = np.asarray(llama.make_generate(
        CFG, max_new_tokens=n_new,
        ffn=llama_moe.make_ffn(CFG, groups=n_exp))(
        prepared, jnp.asarray(prompt), jax.random.PRNGKey(21)))
    got = np.asarray(llama_moe.make_pipeline_generate_ep(
        CFG, mesh, max_new_tokens=n_new)(
        stage_blocks, aux, jnp.asarray(prompt), jax.random.PRNGKey(21)))
    np.testing.assert_array_equal(got, want)


def test_ep_handles_config_variants():
    """The EP spec derives from the real pytree: a q/k/v-biased Mixtral
    variant (extra bias leaves) shards and matches the grouped dense
    forward instead of tripping a hardcoded-structure mismatch."""
    from dnn_tpu.models import llama
    from dnn_tpu.parallel.mesh import EXPERT_AXIS, make_mesh

    biased = dataclasses.replace(CFG, attn_bias=True)
    n = 4
    mesh = make_mesh({EXPERT_AXIS: n}, jax.devices()[:n])
    p = llama_moe.init(jax.random.PRNGKey(14), biased)
    assert "bias" in p["h_0"]["attn"]["q"]
    ids = np.random.RandomState(15).randint(0, biased.vocab_size, (n, 8))
    want = np.asarray(llama.make_apply(
        biased, ffn=llama_moe.make_ffn(biased, groups=n))(
        p, jnp.asarray(ids)))
    got = np.asarray(llama_moe.make_apply_ep(biased, mesh)(
        p, jnp.asarray(ids)))
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def test_int8_expert_stacks():
    """quantize_tree recognizes the gated stacks; the int8 expert triple
    dequantizes in the epilogue — forward stays close to f32, greedy
    decode heads agree, and EP shards the scale leaves."""
    from dnn_tpu import quant
    from dnn_tpu.models import llama
    from dnn_tpu.parallel.mesh import EXPERT_AXIS, make_mesh

    p = _params(seed=16)
    q = quant.quantize_tree(p)
    moe_q = q["h_0"]["moe"]
    assert moe_q["wg"].dtype == jnp.int8 and "wg_scale" in moe_q
    assert moe_q["router"]["kernel"].dtype != jnp.int8, "router stays f32"

    ids = np.random.RandomState(17).randint(0, CFG.vocab_size, (2, 12))
    f32 = np.asarray(llama_moe.make_apply(CFG)(p, jnp.asarray(ids)))
    i8 = np.asarray(llama_moe.make_apply(CFG)(q, jnp.asarray(ids)))
    # int8 rounding noise (attention kernels quantize too under the
    # default predicate), but the distribution must track
    assert np.abs(f32 - i8).max() < 0.6
    agree = (f32.argmax(-1) == i8.argmax(-1)).mean()
    assert agree > 0.8, f"argmax agreement {agree}"

    # greedy decode runs end-to-end on the quantized stacks
    prep_q = gpt.prepare_stacked(q, CFG)
    toks = np.asarray(llama_moe.make_generate(CFG, max_new_tokens=6)(
        prep_q, jnp.asarray(ids[:1, :6]), jax.random.PRNGKey(0)))[0]
    assert toks.shape == (6,)

    # quantizing AFTER stacking works too (the 4-D (L, E, D, F) form):
    # same decode trajectory as quantize-then-stack
    q_stacked = quant.quantize_tree(gpt.prepare_stacked(p, CFG))
    assert q_stacked["blocks"]["moe"]["wg"].dtype == jnp.int8
    assert q_stacked["blocks"]["moe"]["wg_scale"].ndim == 4
    toks2 = np.asarray(llama_moe.make_generate(CFG, max_new_tokens=6)(
        q_stacked, jnp.asarray(ids[:1, :6]), jax.random.PRNGKey(0)))[0]
    np.testing.assert_array_equal(toks2, toks)

    # EP over int8 stacks: pytree-derived spec shards the scales too
    n = 4
    mesh = make_mesh({EXPERT_AXIS: n}, jax.devices()[:n])
    want = np.asarray(llama.make_apply(
        CFG, ffn=llama_moe.make_ffn(CFG, groups=n))(q, jnp.asarray(
            np.tile(ids, (2, 1)))))
    got = np.asarray(llama_moe.make_apply_ep(CFG, mesh)(
        q, jnp.asarray(np.tile(ids, (2, 1)))))
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def test_quantize_tree_idempotent_on_expert_stacks():
    """Re-quantizing an already-int8 tree must be a no-op — without the
    dtype/scale guard it would overwrite the real expert scales with
    ~1.0 (amax of int8) and silently corrupt the model."""
    from dnn_tpu import quant

    p = _params(seed=20)
    q1 = quant.quantize_tree(p)
    q2 = quant.quantize_tree(q1)
    s1 = q1["h_0"]["moe"]["wg_scale"]
    s2 = q2["h_0"]["moe"]["wg_scale"]
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))
    ids = np.random.RandomState(21).randint(0, CFG.vocab_size, (1, 8))
    np.testing.assert_array_equal(
        np.asarray(llama_moe.make_apply(CFG)(q1, jnp.asarray(ids))),
        np.asarray(llama_moe.make_apply(CFG)(q2, jnp.asarray(ids))))
