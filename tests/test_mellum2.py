"""Mellum2: K/V layers of two KINDS that BOTH rotate q and k, each by its own
table (`llama.KvKind.rotation`) — "window" layers under a window and plain
RoPE, "full" layers under YaRN — over experts that are all held, behind the
batcher and ONE paged pool, against the plain reference
(chipbench/reference/mellum.py, which makes its own tables). Everything at
`mellum2-test` size (hidden 64, 8 layers S S S F twice, window 8, YaRN over 8
original positions, GQA 4 / 2 heads of 32, <= 62 positions), one
module-scoped model.

Tolerances: float32 on the CPU, every program against the reference's full
forward: logits and log-probabilities within 1e-3 (observed: 1e-6
whole-sequence, 2e-6 through chunked prefill and paged decode). The least a
control moves a logit is 6e-3 (the ramp's `high` one pair up)."""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import mellum as ref
from dnn_tpu.models import llama, llama_moe
from dnn_tpu.models.gpt import layer_runs, prepare_stacked, stack_layers
from dnn_tpu.registry import ParamParts, get_model
from dnn_tpu.runtime.serving import ContinuousBatcher

TOL = 1e-3


@pytest.fixture(scope="module")
def model():
    spec = get_model("mellum2-test")
    return spec, spec.config, spec.init(jax.random.PRNGKey(3))


def _ids(n, seed=1):
    return np.asarray(jax.random.randint(
        jax.random.PRNGKey(seed), (n,), 1, 256), np.int32)


def _batcher(model, attn_kernel=False, **kw):
    spec, cfg, params = model
    family = spec.extras["family_rows"]()
    family.attn_kernel = attn_kernel
    opts = dict(slots=3, max_len=64, prompt_pad=16, kv="paged", block_len=8,
                family=family)
    opts.update(kw)
    return ContinuousBatcher(cfg, prepare_stacked(dict(params), cfg), **opts)


def test_preset_has_every_switch_acting(model):
    _, cfg, params = model
    assert cfg.layer_types == ("window", "window", "window", "full") * 2
    assert cfg.kv_window.window == 8 and cfg.kv_full.window is None
    assert cfg.kv_window.rope and cfg.kv_full.rope
    assert cfg.kv_window.rotation.scaling is None
    yarn = cfg.kv_full.rotation
    assert yarn.scaling == "yarn" and yarn.attention_factor != 1.0
    # all three parts of the ramp hold pairs, and 40 positions are past four
    # times the original length and four windows
    low, high = llama.yarn_ramp(yarn, cfg.head_dim)
    assert 0 < low < high < cfg.head_dim // 2 - 1
    assert 40 > 4 * yarn.original_len and 40 > 4 * cfg.kv_window.window
    assert cfg.n_head // cfg.n_kv_head == 2 and cfg.head_dim == 32
    assert cfg.qk_norm and cfg.qk_norm_width == "head"
    assert cfg.router_norm_topk and cfg.router.scoring == "softmax"
    assert cfg.n_expert == 8 and cfg.router_top_k == 4
    assert cfg.experts_held is None and not cfg.d_shared
    assert not cfg.first_k_dense and not cfg.tie_word_embeddings
    assert stack_layers(cfg) == {"blocks": (3, 7),
                                 "window_blocks": (0, 1, 2, 4, 5, 6)}
    assert all("moe" in params[f"h_{i}"] for i in range(8))


def test_the_published_model_and_its_cut():
    cfg = get_model("mellum2-12b-a2.5b").config
    assert [i for i, t in enumerate(cfg.layer_types) if t == "full"] == \
        list(range(3, 28, 4))
    runs = layer_runs(cfg)
    assert runs[:3] == [("window_blocks", (0, 3), "window", (0, 3)),
                        ("blocks", (0, 1), "full", (0, 1)),
                        ("window_blocks", (3, 6), "window", (3, 6))]
    assert len(runs) == 14
    cut = get_model("mellum2-12b-a2.5b-pp4-1chip").config
    assert cut.n_layer == 8 and cut.layer_types == cfg.layer_types[:8]
    assert (cut.n_embd, cut.n_head, cut.n_kv_head, cut.head_dim, cut.d_ff,
            cut.n_expert, cut.router_top_k, cut.vocab_size,
            cut.experts_held, cut.block_size) == (
                2304, 32, 4, 128, 896, 64, 8, 98304, None, 131072)
    assert cut.kv_window == cfg.kv_window and cut.kv_full == cfg.kv_full
    assert cut.kv_window.window == 1024
    assert cut.kv_window.rotation == llama.Rotation(theta=500000.0)


@pytest.mark.parametrize("tables", ["program", "reference"])
def test_the_published_numbers_without_a_model(tables):
    """low 18, high 35, f_0 = 1, f_63 = t_63 / 16, a pair inside the ramp,
    and a — from the program's tables and from the reference's own."""
    cfg = get_model("mellum2-12b-a2.5b").config
    rot, d = cfg.kv_full.rotation, 128
    t = [500000.0 ** (-2 * i / d) for i in range(d // 2)]
    if tables == "program":
        low, high = llama.yarn_ramp(rot, d)
        cos, sin = llama._rope_tables(cfg, jnp.asarray([0, 1]), cfg.kv_full)
        a = float(cos[0, 0])  # cos(0) a
        # position 1's angle IS the frequency
        f = np.arctan2(np.asarray(sin[1, :d // 2]),
                       np.asarray(cos[1, :d // 2]))
        plain = llama._rope_tables(cfg, jnp.asarray([1]), cfg.kv_window)
        assert np.allclose(np.asarray(plain[1])[0, :d // 2], np.sin(t),
                           atol=1e-7)
        assert float(plain[0][0, 0]) == pytest.approx(math.cos(1.0))
    else:
        theta, yarn = ref._table(rot)
        low, high, f, a = ref.yarn_numbers(d, theta, yarn)
        assert ref.yarn_numbers(d, theta, None)[2] == t
    assert (low, high) == (18, 35)
    assert a == pytest.approx(1.2772588722239782, rel=1e-6)
    assert a == pytest.approx(0.1 * math.log(16) + 1, rel=1e-6)
    assert f[0] == pytest.approx(1.0, rel=1e-6)
    assert f[63] == pytest.approx(t[63] / 16, rel=1e-5)
    assert f[18] == pytest.approx(t[18], rel=1e-5)
    assert f[35] == pytest.approx(t[35] / 16, rel=1e-5)
    r = (27 - 18) / (35 - 18)  # a pair inside the ramp
    assert f[27] == pytest.approx(t[27] * (1 - r) + t[27] / 16 * r, rel=1e-5)


def test_a_config_names_its_kinds_whole():
    base = llama_moe.PRESETS["mellum2-test"]
    with pytest.raises(ValueError, match="layer_types comes with"):
        dataclasses.replace(base, layer_types=None)
    with pytest.raises(ValueError, match="kv_window .which has a window"):
        dataclasses.replace(base, kv_full=llama.KvKind(window=4))
    # a kind's table of a type nobody wrote is refused by name
    odd = dataclasses.replace(base, kv_full=llama.KvKind(
        rotation=llama.Rotation(theta=100.0, scaling="longrope", scale=4.0)))
    with pytest.raises(ValueError, match="'linear', 'ntk' or 'yarn'"):
        llama._rope_tables(odd, jnp.arange(4), odd.kv_full)
    bare = dataclasses.replace(base, kv_full=llama.KvKind(
        rotation=llama.Rotation(theta=100.0, scaling="yarn", scale=4.0)))
    with pytest.raises(ValueError, match="original_len"):
        llama._rope_tables(bare, jnp.arange(4), bare.kv_full)


@pytest.fixture(scope="module")
def served_logits(model):
    """(ids (40,), the program's whole-sequence logits of them)."""
    spec, _, params = model
    ids = np.stack([_ids(40, 1), _ids(40, 7)])
    return ids[1], spec.apply(params, jnp.asarray(ids))[1]


def test_whole_sequence_logits_match_the_reference(model):
    spec, cfg, params = model
    ids = jnp.asarray(np.stack([_ids(40, 1), _ids(40, 7)]))
    got = spec.apply(params, ids)
    assert float(jnp.abs(got - ref.logits(cfg, params, ids)).max()) < TOL


WRONG = [
    {"full_table": "sliding"}, {"sliding_table": "full"},
    {"attention_factor": 1.0}, {"ramp_off": (1, 0)}, {"ramp_off": (-1, 0)},
    {"ramp_off": (0, 1)}, {"ramp_off": (0, -1)}, {"window": 7},
    {"window": 9}, {"renorm": False}, {"qk_norm": False}]


@pytest.mark.parametrize(
    "wrong", WRONG, ids=lambda w: "-".join(f"{k}={v}" for k, v in w.items()))
def test_each_kind_rotates_by_its_own_table(model, served_logits, wrong):
    """The program's logits are the reference's, and NOT those of the
    reference with ONE thing wrong: the full layers on the sliding table,
    the sliding layers on the YaRN table, the attention factor left out, the
    ramp's low or high one pair off either way, the window one position off
    either way (`t - W < u <= t`), the weights not renormalised, q/k norm
    left out."""
    _, cfg, params = model
    ids, got = served_logits
    assert float(jnp.abs(got - ref.forward(cfg, params, ids)).max()) < TOL
    off = ref.forward(cfg, params, ids, **wrong)
    assert float(jnp.abs(got - off).max()) > 2 * TOL


@pytest.mark.parametrize("attn_kernel", [False, "interpret"],
                         ids=["einsum", "kernels"])
def test_chunked_prefill_and_paged_decode_match_the_reference(model,
                                                              attn_kernel):
    """Three requests through the batcher's programs (chunk, finish and
    install, decode step), prompts of one to three chunks of 16, every
    context past four windows of 8 AND past four times YaRN's original 8
    positions: each served token's log-probability is the reference's full
    forward's, and its argmax — and NOT the reference's with the full
    layers on the sliding table (the decode step's per-slot tables are the
    kind's too)."""
    _, cfg, params = model
    b = _batcher(model, attn_kernel=attn_kernel, logprobs_k=2)
    assert sorted(b.cache) == ["k", "k_w", "tables", "tables_w", "v", "v_w"]
    assert b.cache["k"].shape[:3] == (2, 3 * 8 + 1, 2)   # two full layers
    assert b.cache["k_w"].shape[:2] == (6, 3 * 2 + 1)    # 2 blocks a slot
    prompts = [_ids(29, 4), _ids(11, 5), _ids(37, 6)]
    rids = [b.submit(p, 54 - len(p), logprobs=True) for p in prompts]
    out = b.drain()
    swapped = 0.0
    for rid, p in zip(rids, prompts):
        seq = np.concatenate([p, out[rid]])
        assert len(seq) > 4 * cfg.kv_window.window
        assert len(seq) > 4 * cfg.kv_full.rotation.original_len
        want = jax.nn.log_softmax(ref.forward(cfg, params, jnp.asarray(seq)))
        rows = np.arange(len(p) - 1, len(seq) - 1)
        assert (np.asarray(want.argmax(-1))[rows] == out[rid]).all()
        chosen = np.asarray(want)[rows, out[rid]]
        assert np.abs(b.token_logprobs[rid]["chosen"] - chosen).max() < TOL
        off = jax.nn.log_softmax(ref.forward(
            cfg, params, jnp.asarray(seq), full_table="sliding"))
        swapped = max(swapped, np.abs(
            b.token_logprobs[rid]["chosen"]
            - np.asarray(off)[rows, out[rid]]).max())
    assert swapped > 2 * TOL
    assert b.window_blocks_freed >= 6
    assert b._allocator.n_used == b._allocator.of("tables_w").n_used == 0
    forms = b.family.attn_forms
    if attn_kernel:
        assert forms == {
            "full": {"prefill": "kernel", "decode": "paged_kernel"},
            "window": {"prefill": "banded_kernel",
                       "decode": "gather_einsum"}}
    else:
        assert forms["window"] == {"prefill": "plain",
                                   "decode": "gather_einsum"}
    assert b.family.kind_tables() == {
        "full": {"window": None, "rotation": {
            "type": "yarn", "theta": 100.0, "factor": 8.0,
            "attention_factor": 1.25}},
        "window": {"window": 8, "rotation": {
            "type": "default", "theta": 100.0, "factor": 1.0,
            "attention_factor": 1.0}}}


def test_the_seeded_experts_are_scaled_and_nothing_else():
    """`expert_out_init` (the served presets' 0.25, MEASURED on the chip:
    `llama_moe.py`) scales the seeded experts' down projections, exactly (a
    power of two), and draws nothing else differently."""
    cfg = llama_moe.PRESETS["mellum2-test"]
    assert cfg.expert_out_init == 1.0
    assert get_model("mellum2-12b-a2.5b-pp4-1chip").config.expert_out_init \
        == get_model("mellum2-12b-a2.5b").config.expert_out_init == 0.25
    key = jax.random.PRNGKey(5)
    a = llama_moe.init(key, cfg)
    b = llama_moe.init(key, dataclasses.replace(cfg, expert_out_init=0.25))
    for path, x in jax.tree_util.tree_leaves_with_path(a):
        y = b
        for k in path:
            y = y[k.key]
        scale = 0.25 if path[-1].key == "wd" else 1.0
        assert (np.asarray(y) == scale * np.asarray(x)).all(), path


def test_the_held_tree_is_bit_identical_to_the_whole_inits():
    from dnn_tpu.node import _stack_and_release
    from dnn_tpu.ops.nn import hold_in_compute_dtype

    spec = get_model("mellum2-test")
    key = jax.random.PRNGKey(5)
    parts = spec.init_parts(key)
    assert isinstance(parts, ParamParts)
    got = _stack_and_release(parts, spec.config, jnp.bfloat16)
    want = hold_in_compute_dtype(
        prepare_stacked(spec.init(key), spec.config), jnp.bfloat16)
    la, ta = jax.tree_util.tree_flatten(got)
    lb, tb = jax.tree_util.tree_flatten(want)
    assert ta == tb
    for x, y in zip(la, lb):
        assert x.dtype == y.dtype and (np.asarray(x) == np.asarray(y)).all()
    assert "dense_blocks" not in got
    assert got["window_blocks"]["moe"]["wg"].shape[:2] == (6, 8)
    assert got["blocks"]["moe"]["wg"].shape[:2] == (2, 8)
    assert got["blocks"]["moe"]["router"]["kernel"].dtype == jnp.float32
    assert "shared" not in got["blocks"]["moe"]


def test_the_checks_margins_a_layer_at_a_time_are_the_whole_trees(model):
    """`serve_dots.served_margins` drives this reference as it stands
    (layer outer, sequence inner, weights drawn as it goes) and gives
    `serve_keye.served_margins`' numbers on the whole tree; a control's
    `wrong` reaches the layers."""
    from chipbench import serve_dots, serve_keye

    spec, cfg, params = model
    prompts = [_ids(30, 2), _ids(30, 1)]
    tokens = [list(_ids(6, 3)), list(_ids(6, 4))]
    a = serve_dots.served_margins(
        "mellum", cfg, spec.init_parts(jax.random.PRNGKey(3)), prompts,
        tokens)
    b = serve_keye.served_margins("mellum", cfg, params, prompts, tokens)
    for key in ("worst_margin", "mean_margin", "argmax_share",
                "mean_logit_sigma"):
        assert abs(a[key] - b[key]) < 1e-5, key
    assert a["positions"] == b["positions"] == 12  # one length: one compile
    c = serve_dots.served_margins(
        "mellum", cfg, spec.init_parts(jax.random.PRNGKey(3)), prompts,
        tokens, qk_norm=False)
    assert abs(c["mean_margin"] - a["mean_margin"]) > 1e-3
