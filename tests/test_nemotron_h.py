"""Nemotron-H (NVIDIA-Nemotron-3-Super-120B-A12B): a block is ONE norm, ONE
mixer and one residual — a Mamba-2 rule IN PLACE of attention ("ssm"),
unrotated softmax attention ("full") or experts in a LATENT ("experts": the
router scores the model-wide h, the rows permuted are h W_down, one W_up
follows the weighted sum) — behind the batcher and a pool whose kinds cover
the blocks that keep something (slot leaves for "ssm", paged K and V for
"full", nothing for "experts"), against the plain reference
(chipbench/reference/nemotron_h.py: a `lax.scan` over positions, full
softmax, the experts one after the other over every row). Everything at
`nemotron-h-test` size (hidden 64, MEME*EM, 4 state heads of 16 in 2 groups,
state 16, GQA 2:1 with heads of 16, 16 experts of 24 in a latent of 32, 6 a
token, 4 held, scaling 2.5, a closed-form chunk of 8 in prefill chunks of
16, <= 96 positions), one module-scoped model whose ONE attention block is
sharpened (q, k and o times 4: at the seeded 0.02 a rotation moves nothing a
test could see).

Tolerances: float32 on the CPU, every program against the reference's full
forward: log-probabilities over the WHOLE vocabulary within 1e-3 (observed:
1e-6 through chunked prefill, install and decode; 1e-6 whole-sequence). Each
one-thing-wrong case misses TWICE that."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import nemotron_h as ref
from dnn_tpu.models import llama, llama_moe, mamba2, state_kind
from dnn_tpu.models.gpt import layer_runs, prepare_stacked, stack_layers
from dnn_tpu.parallel import moe
from dnn_tpu.registry import get_model
from dnn_tpu.runtime.serving import ContinuousBatcher

TOL = 1e-3
PAD = 16  # the batchers' prompt_pad


@pytest.fixture(scope="module")
def model():
    spec = get_model("nemotron-h-test")
    params = spec.init(jax.random.PRNGKey(3))
    attn = params["h_4"]["attn"]
    params["h_4"] = {**params["h_4"], "attn": {
        n: {"kernel": w["kernel"] * (1.0 if n == "v" else 4.0)}
        for n, w in attn.items()}}
    return spec, spec.config, params


def _ids(n, seed=1):
    return np.asarray(jax.random.randint(
        jax.random.PRNGKey(seed), (n,), 1, 256), np.int32)


def _batcher(model, family=None, **kw):
    _, cfg, params = model
    opts = dict(slots=3, max_len=96, prompt_pad=PAD, kv="paged", block_len=8,
                family=family or llama.family_rows(cfg))
    opts.update(kw)
    return ContinuousBatcher(cfg, prepare_stacked(dict(params), cfg), **opts)


@pytest.fixture(scope="module")
def plain(model):
    """One batcher whose log-probabilities cover the vocabulary, for the
    tests that each drain it: its three programs compile once."""
    return _batcher(model, logprobs_k=256)


def _by_vocabulary(lp):
    full = np.empty_like(lp["top_logprobs"])
    np.put_along_axis(full, lp["top_ids"], lp["top_logprobs"], axis=-1)
    return full


def _served_logprobs(b, prompt, n_new):
    rid = b.submit(prompt, n_new, logprobs=True)
    toks = b.drain()[rid]
    return toks, _by_vocabulary(b.token_logprobs[rid])


def _reference_logprobs(cfg, params, prompt, toks, **wrong):
    seq = np.concatenate([prompt, toks])
    rows = np.arange(len(prompt) - 1, len(seq) - 1)
    with jax.default_matmul_precision("highest"):
        return np.asarray(jax.nn.log_softmax(
            ref.forward(cfg, params, jnp.asarray(seq), rows=rows, **wrong)))


def test_preset_has_every_switch_acting(model):
    _, cfg, params = model
    m, types = cfg.mamba, cfg.layer_types
    assert cfg.one_mixer and set(types) == {"ssm", "full", "experts"}
    # an E first after an M, and a * between two E
    assert types[:2] == ("ssm", "experts")
    at = types.index("full")
    assert types[at - 1] == types[at + 1] == "experts"
    assert m.n_groups >= 2 and m.n_head // m.n_groups >= 2 and not m.beside
    assert cfg.n_head // cfg.n_kv_head >= 2 and m.chunk * 2 == PAD
    assert (cfg.n_expert, cfg.router_top_k, cfg.held) == (16, 6, (0, 4))
    assert cfg.moe_latent < cfg.n_embd and cfg.d_ff % 8 == 0 != cfg.d_ff % 128
    assert cfg.router.scale != 1.0 and cfg.router.select_bias
    assert not cfg.expert_gated and cfg.mlp_act == "relu2"
    assert cfg.n_expert_layer == 3
    # a block holds its ONE mixer's params and one norm, nothing else
    assert set(params["h_0"]) == {"ln_1", "ssm"}
    assert set(params["h_1"]) == {"ln_1", "moe"}
    assert set(params["h_4"]) == {"ln_1", "attn"}
    assert set(params["h_4"]["attn"]) == {"q", "k", "v", "o"}
    e = params["h_1"]["moe"]
    assert set(e) == {"router", "wi", "wo", "latent_down", "latent_up",
                      "shared"}
    assert e["router"]["kernel"].shape == (64, 16)  # the full-width h
    assert e["wi"].shape == (4, 32, 24) and e["wo"].shape == (4, 24, 32)
    assert set(e["shared"]) == {"up", "down"}
    assert e["shared"]["up"]["kernel"].shape == (64, 48)  # h, not the latent
    assert float(jnp.abs(e["router"]["select_bias"]).min()) > 0


def test_the_published_numbers():
    """The pattern's counts, W_in's columns, a state's bytes, an expert and
    the whole, from the presets' widths — no model is made."""
    cfg = get_model("nemotron-3-super-120b-a12b").config
    cut = get_model("nemotron-3-super-120b-a12b-ep4-1chip").config
    count = lambda c: [c.layer_types.count(k)  # noqa: E731
                       for k in ("ssm", "full", "experts")]
    assert count(cfg) == [40, 8, 40] and cfg.n_layer == 88
    assert count(cut) == [5, 1, 5] and cut.n_layer == 11
    assert cut.layer_types == llama_moe.pattern_types("MEMEMEM*EME") \
        == cfg.layer_types[:11]
    assert dataclasses.replace(
        cut, n_layer=88, layer_types=cfg.layer_types, vocab_size=131072,
        experts_held=None) == cfg
    assert (cut.vocab_size, cut.held) == (131072 // 4, (0, 512 // 4))
    assert (cfg.n_embd, cfg.n_head, cfg.n_kv_head, cfg.head_dim, cfg.d_ff,
            cfg.moe_latent, cfg.d_shared, cfg.n_expert, cfg.router_top_k,
            cfg.router.scale, cfg.rms_eps) == (
                4096, 32, 2, 128, 2688, 1024, 5376, 512, 22, 5.0, 1e-5)
    m = cfg.mamba
    assert (m.d_ssm, m.n_head, m.head_dim, m.n_groups, m.d_state, m.conv,
            m.chunk) == (8192, 128, 64, 8, 128, 4, 128)
    assert m.d_ssm == 2 * cfg.n_embd  # `expand`
    assert (m.conv_width, m.proj_width) == (10240, 18560)
    shape, dtype = mamba2.slot_leaves(cfg)["ssm_state"]
    assert shape == (128, 64, 128) and dtype == jnp.float32
    assert int(np.prod(shape)) * 4 == 4_194_304
    c = cfg.n_embd
    ssm = c * 18560 + 8192 * c + 4 * 10240 + 10240 + 3 * 128 + 8192 + c
    attn = 2 * c * 4096 + 2 * c * 256 + c
    expert = 2 * cfg.moe_latent * cfg.d_ff
    block_e = (c * 512 + 512 + 2 * c * cfg.moe_latent + 2 * c * cfg.d_shared
               + 512 * expert + c)
    assert round(ssm / 1e6, 1) == 109.6 and round(attn / 1e6, 2) == 35.66
    assert round(expert / 1e6, 3) == 5.505 and round(block_e / 1e6) == 2873
    whole = 40 * ssm + 8 * attn + 40 * block_e + 2 * 131072 * c + c
    assert round(whole / 1e9, 1) == 120.7
    active = 40 * ssm + 8 * attn + 2 * 131072 * c + 40 * (
        block_e - (512 - 22) * expert)
    assert round(active / 1e9, 1) == 12.8  # with embedding and head: A12B


def test_a_config_refuses_what_the_block_was_not_built_for():
    base = llama_moe.PRESETS["nemotron-h-test"]
    for wrong in (dict(first_k_dense=1, d_ff_dense=32),
                  dict(kv_full=llama.KvKind(window=8)),
                  dict(mup=llama.MupConfig(embedding=2.0)),
                  dict(layer_types=("ssm",) * 7),
                  dict(layer_types=base.layer_types[:6]),
                  dict(mamba=dataclasses.replace(base.mamba, ssm_out=0.5))):
        with pytest.raises(ValueError, match="ONE mixer"):
            dataclasses.replace(base, **wrong)
    with pytest.raises(ValueError, match="layer_types comes with"):
        dataclasses.replace(base, layer_types=None)
    with pytest.raises(ValueError, match="blocks of one mixer"):
        dataclasses.replace(llama_moe.PRESETS["mixtral-test"], moe_latent=32)
    for export in (llama.to_hf_config, llama_moe.to_hf_config):
        with pytest.raises(ValueError, match="hybrid_override_pattern.*"
                                             "moe_latent_size"):
            export(base)


def test_the_stacks_and_the_loop_go_by_the_blocks_kind(model):
    _, cfg, params = model
    assert stack_layers(cfg) == {"blocks": (4,), "ssm_blocks": (0, 2, 6),
                                 "expert_blocks": (1, 3, 5)}
    runs = layer_runs(cfg)
    assert [(r[0], r[2], r[3]) for r in runs] == [
        ("ssm_blocks", "ssm", (0, 1)), ("expert_blocks", "experts", (0, 1)),
        ("ssm_blocks", "ssm", (1, 2)), ("expert_blocks", "experts", (1, 2)),
        ("blocks", "full", (0, 1)), ("expert_blocks", "experts", (2, 3)),
        ("ssm_blocks", "ssm", (2, 3))]
    prepared = prepare_stacked(dict(params), cfg)
    assert prepared["expert_blocks"]["moe"]["wi"].shape == (3, 4, 32, 24)
    assert "attn" not in prepared["ssm_blocks"]
    assert "ln_2" not in prepared["blocks"]


def test_whole_sequence_logits_match_the_reference(model):
    spec, cfg, params = model
    ids = jnp.asarray(np.stack([_ids(43, 1), _ids(43, 7)]))
    got = llama_moe.make_apply(cfg)(params, ids)
    with jax.default_matmul_precision("highest"):
        want = ref.logits(cfg, params, ids)
    assert float(jnp.abs(got - want).max()) < TOL


# prompts that end inside a chunk, on a chunk's edge, one position past it
# and over several chunks: prefill in chunks, install, decode through the
# pool, against the reference's scan over positions
@pytest.mark.parametrize("n", [5, PAD, PAD + 1, 2 * PAD + 9, 4 * PAD])
def test_served_logprobs_match_the_reference(model, plain, n):
    _, cfg, params = model
    prompt = _ids(n, seed=n)
    toks, got = _served_logprobs(plain, prompt, 12)
    want = _reference_logprobs(cfg, params, prompt, toks)
    assert np.abs(got - want).max() < TOL
    assert (got.argmax(-1) == toks).all()


# each ONE thing wrong in the reference misses the served log-probabilities
# at twice the tolerance (and the reference as it stands meets them)
WRONG = [
    dict(act="relu"), dict(gated=True), dict(route_latent=True),
    dict(shared_latent=True), dict(shared=False), dict(norm_held=True),
    dict(scale=1.0), dict(bias_in_weight=True), dict(gate_first=False),
    dict(grouped_norm=False), dict(rope=True), dict(ffn_after=True),
    dict(d_skip=False), dict(dt_bias=False)]


@pytest.fixture(scope="module")
def served(model, plain):
    prompt = _ids(3 * PAD + 5, seed=21)
    toks, got = _served_logprobs(plain, prompt, 24)
    return prompt, toks, got


@pytest.mark.parametrize("wrong", WRONG, ids=lambda w: "-".join(
    f"{k}={v}" for k, v in w.items()))
def test_each_one_thing_wrong_misses_twice_the_tolerance(model, served,
                                                        wrong):
    _, cfg, params = model
    prompt, toks, got = served
    assert np.abs(got - _reference_logprobs(cfg, params, prompt, toks)
                  ).max() < TOL
    off = np.abs(got - _reference_logprobs(cfg, params, prompt, toks,
                                           **wrong)).max()
    assert off > 2 * TOL, off


def test_the_four_shares_add_up_to_the_uncut_block(model):
    """Guide section 4: the four shares' r_c (experts 0-3, 4-7, 8-11, 12-15),
    summed, through W_up, plus the shared expert ONCE, are the uncut
    reference's E block — and the program's share is the reference's."""
    _, cfg, params = model
    whole_cfg = dataclasses.replace(cfg, experts_held=None)
    whole = llama_moe.init(jax.random.PRNGKey(3), whole_cfg)["h_1"]
    assert whole["moe"]["wi"].shape[0] == 16
    x = jax.random.normal(jax.random.PRNGKey(5), (40, cfg.n_embd))
    with jax.default_matmul_precision("highest"):
        kw = ref.layer_args(whole_cfg, 1)
        uncut = ref.layer(whole, x, **kw) - x
        h = ref._rms_norm(whole["ln_1"]["scale"], x, kw["eps"])
        r = 0.0
        for first in range(0, 16, 4):
            share = {**whole["moe"], "wi": whole["moe"]["wi"][first:first + 4],
                     "wo": whole["moe"]["wo"][first:first + 4]}
            r_c = ref.routed_latent(share, h, top_k=6, first=first,
                                    scale=2.5)
            assert float(jnp.abs(r_c).max()) > 0
            r = r + r_c
            # the program's share of the block is the reference's
            blk = {"ln_1": whole["ln_1"], "moe": share}
            c = dataclasses.replace(cfg, experts_first=first)
            got = llama.block_apply(blk, x[None], cfg=c, kind="experts",
                                    ffn=c.default_ffn())[0] - x
            want = ref.layer(blk, x, **ref.layer_args(c, 1)) - x
            assert float(jnp.abs(got - want).max()) < 1e-5
        total = r @ whole["moe"]["latent_up"]["kernel"] + ref._shared(
            whole["moe"], h, act="relu2", shared_latent=False)
    assert float(jnp.abs(total - uncut).max()) < 1e-5


@pytest.mark.parametrize("rounds", [False, True], ids=["one_pass", "rounds"])
def test_the_latent_rows_ride_the_permutation(rounds, monkeypatch):
    """`moe_ffn_grouped(rows=)`: the router scores x, the rows permuted and
    computed are the latent's and so is the result's width — through the
    kernels that read the stacks in place (interpreted), in one pass and in
    rounds of the extent, against the reference's table of weights."""
    e, k, held, t, d, w, f = 32, 6, 8, 80, 64, 32, 24
    p = dict(moe.init_moe_plain(jax.random.PRNGKey(2), d, e, f, n_held=held,
                                d_in=w))
    assert set(p) == {"router", "wi", "wo"} and p["wi"].shape == (held, w, f)
    p["router"]["select_bias"] = 0.05 * jax.random.normal(
        jax.random.PRNGKey(4), (e,))
    p["latent_down"] = {"kernel": jax.random.normal(
        jax.random.PRNGKey(5), (d, w)) / 8.0}
    x = jax.random.normal(jax.random.PRNGKey(3), (t, d))
    u = x @ p["latent_down"]["kernel"]
    if rounds:
        monkeypatch.setattr(moe, "_MIN_ROWS_SAVED", 0)
        monkeypatch.setattr(moe, "permutation_extent", lambda *a: 32)
    stacks = {n: moe.LayerOf(jnp.stack([jnp.full_like(p[n], jnp.nan), p[n]]),
                             jnp.int32(1)) for n in ("wi", "wo")}
    got, stats = moe.moe_ffn_grouped(
        {**p, **stacks}, x, rows=u, top_k=k, normalize=True,
        activation=llama.relu2, scoring="sigmoid", scale=2.5,
        held=(0, held), interpret=True, return_stats=True)
    assert got.shape == (t, w)
    with jax.default_matmul_precision("highest"):
        want = ref.routed_latent(p, x, top_k=k, first=0, scale=2.5)
    assert float(jnp.abs(got - want).max()) < 1e-4
    assert int(stats[4]) == (-(-int(stats[0]) // 32) - 1 if rounds else 0)
    assert int(stats[3]) == (-(-int(stats[0]) // 32) * 32 if rounds
                             else t * k)


def test_the_kernels_interpreted_serve_the_plain_logprobs(model, plain):
    """A batcher whose family runs the step kernel (ops/pallas/ssm_step.py at
    heads of 16 and a state of 16) and the paged kernel, interpreted, serves
    what the plain one serves, and says so under the names /statusz
    reports."""
    _, cfg, params = model
    srv = _batcher(model, family=llama.family_rows(
        cfg, attn_kernel="interpret"), logprobs_k=256)
    prompt = _ids(2 * PAD + 7, 12)
    toks, lps = _served_logprobs(srv, prompt, 6)
    toks_plain, lps_plain = _served_logprobs(plain, prompt, 6)
    assert np.array_equal(toks, toks_plain)
    assert np.abs(lps - lps_plain).max() < 1e-4
    assert srv.family.attn_forms["ssm"] == {"prefill": "chunked_jnp",
                                            "decode": "step_kernel"}
    assert plain.family.attn_forms["ssm"]["decode"] == "step_jnp"
    assert set(srv.family.attn_forms) == {"full", "ssm"}


@pytest.mark.parametrize("h,g,p,n", [(32, 2, 64, 128), (16, 2, 32, 256),
                                     (6, 2, 64, 128)],
                         ids=["published_two_a_tile", "four_a_tile",
                              "odd_heads_a_group_unfolded"])
def test_the_step_kernel_folds_narrow_heads(h, g, p, n):
    """ops/pallas/ssm_step.py at heads narrower than the lanes (P = 64, N =
    128 as published, 16 heads a group): 128 / P heads of a group are ONE
    (128, N) tile, their scalars a row's and a lane's — interpreted, the
    plain step; a group whose heads the fold does not divide stays as it
    was."""
    slots, layers, layer = 2, 3, 1
    m = llama.Mamba2Config(d_ssm=h * p, n_head=h, d_state=n, n_groups=g)
    ks = jax.random.split(jax.random.PRNGKey(9), 7)
    pool = jax.random.normal(ks[0], (layers, slots, h, p, n))
    x = jax.random.normal(ks[1], (slots, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[2], (slots, h)))
    bm, cm = (jax.random.normal(kk, (slots, g, n)) for kk in ks[3:5])
    a_log = jax.random.uniform(ks[5], (h,), minval=0.0, maxval=np.log(16.0))
    d = jax.random.normal(ks[6], (h,))
    want, s_want = mamba2.step_rule(x, dt, a_log, d, bm, cm, pool[layer],
                                    m=m)
    got, pool2 = mamba2.step_rule_kernel(
        x, dt, a_log, d, bm, cm, pool, m=m, layer=jnp.int32(layer),
        interpret=True)
    assert float(jnp.abs(got - want).max()) < 1e-5 * float(
        jnp.abs(want).max())
    assert float(jnp.abs(pool2[layer] - s_want).max()) < 1e-5
    for other in (0, 2):
        assert jnp.array_equal(pool2[other], pool[other])


def test_a_readmitted_slot_is_a_fresh_daemons(model, plain):
    first = [_served_logprobs(plain, _ids(n, seed=40 + n), 6)
             for n in (21, 33, 7)]       # fills every slot once
    again = [_served_logprobs(plain, _ids(n, seed=40 + n), 6)
             for n in (21, 33, 7)]       # every slot re-admitted
    fresh = _batcher(model, logprobs_k=256)
    for (t1, l1), (t2, l2), n in zip(first, again, (21, 33, 7)):
        t3, l3 = _served_logprobs(fresh, _ids(n, seed=40 + n), 6)
        assert (t1 == t2).all() and (t2 == t3).all()
        assert np.abs(l2 - l3).max() < 1e-5 and np.abs(l1 - l2).max() < 1e-5


def test_the_pool_covers_the_blocks_that_keep_something(model):
    """Four of the seven blocks keep something: slot leaves for the
    three "ssm" blocks, paged K and V for the one "full" block, NOTHING for
    the three "experts" blocks; admission is by slots and by the full
    kind's blocks."""
    _, cfg, _ = model
    fam = llama.family_rows(cfg)
    assert isinstance(fam, state_kind.StateKindRows)
    assert state_kind.config_rule(cfg) is mamba2.RULE_ALONE
    assert list(fam.cache_kinds) == ["full", "ssm"]
    full, ssm = fam.cache_kinds["full"], fam.cache_kinds["ssm"]
    assert set(full["leaves"]) == {"k", "v"} and full["tables"] == "tables"
    assert full["layers"] == 1 and "slot_leaves" not in full
    assert ssm["leaves"] == {} and ssm["tables"] is None
    assert set(ssm["slot_leaves"]) == {"ssm_state", "conv_tail"}
    assert ssm["layers"] == 3
    assert fam.kinds["full"].rope is False and fam.takes_n_real
    b = _batcher(model, family=fam)
    m = cfg.mamba
    assert set(b.cache) == {"k", "v", "tables", "ssm_state", "conv_tail"}
    assert b.cache["ssm_state"].shape == (3, 3, m.n_head, m.head_dim,
                                          m.d_state)
    assert b.cache["conv_tail"].shape == (3, 3, m.conv - 1, m.conv_width)
    assert b.cache["k"].shape[:2] == (1, 3 * (96 // 8) + 1)
    rids = [b.submit(_ids(n, seed=n), 5) for n in (20, 9, 41)]
    assert b._allocator.n_used == sum(-(-(n + 5) // 8) for n in (20, 9, 41))
    with pytest.raises(Exception):   # bounded by slots: all three are taken
        b.submit(_ids(4), 2)
    out = b.drain()
    assert sorted(out) == sorted(rids) and b._allocator.n_used == 0


def test_the_latent_rows_are_counted(model):
    """`moe_latent_rows_total{program}`: every row of every expert layer
    call of a program — a chunk's 16 positions, a step's 3 slots, 3 E
    blocks each — beside the moe_* series the permutation counts."""
    from dnn_tpu.obs.timeline import StepClock

    b = _batcher(model)
    b.step_clock = clock = StepClock().install()
    b.submit(_ids(PAD + 3, seed=2), 4)   # two chunks of 16, then 3 steps
    b.drain()
    b.step_clock = None
    assert clock.moe_total["prefill"][0] == 3 * 2
    steps = clock.moe_total["decode"][0] // 3
    assert steps == 3
    assert clock.moe_latent_rows_total == {"prefill": 3 * 2 * PAD,
                                           "decode": 3 * steps * 3}
