"""Falcon-H1: every layer runs a Mamba-2 STATE-SPACE mixer (models/mamba2.py)
AND softmax attention on the same normed input, their scaled outputs added
before one residual; behind the batcher and ONE paged pool whose one kind
has paged leaves {k, v} under tables AND slot leaves {ssm_state, conv_tail},
against the plain reference (chipbench/reference/falcon_h1.py: a `lax.scan`
over positions, full softmax). Everything at `falcon-h1-test` size (hidden
64, 3 layers, 4 state-space heads of 16 in 2 groups, state 16, GQA 2:1 with
heads of 16, a closed-form chunk of 8 in prefill chunks of 16, <= 96
positions, every multiplier different from 1 and from each other), one
module-scoped model.

Tolerances: float32 on the CPU, every program against the reference's full
forward: log-probabilities over the WHOLE vocabulary (the logits up to a
row's constant) within 1e-3 (observed: 3e-6 through chunked prefill, install
and decode; 5e-7 whole-sequence). Each negative control misses the same
tolerance."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import falcon_h1 as ref
from dnn_tpu.models import llama, mamba2, state_kind
from dnn_tpu.models.gpt import prepare_stacked
from dnn_tpu.registry import get_model
from dnn_tpu.runtime.serving import ContinuousBatcher

TOL = 1e-3
PAD = 16  # the batchers' prompt_pad


@pytest.fixture(scope="module")
def model():
    spec = get_model("falcon-h1-test")
    return spec, spec.config, spec.init(jax.random.PRNGKey(3))


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
    """(tokens, (n_new, V) log-probabilities in vocabulary order) of one
    greedy request through the batcher's chunk, finish and step programs."""
    rid = b.submit(prompt, n_new, logprobs=True)
    toks = b.drain()[rid]
    return toks, _by_vocabulary(b.token_logprobs[rid])


def _reference_logprobs(cfg, params, prompt, toks, **wrong):
    seq = np.concatenate([prompt, toks])
    rows = np.arange(len(prompt) - 1, len(seq) - 1)
    return np.asarray(jax.nn.log_softmax(
        ref.forward(cfg, params, jnp.asarray(seq), rows=rows, **wrong)))


def test_preset_has_every_switch_acting(model):
    _, cfg, params = model
    m, mup = cfg.mamba, cfg.mup
    assert m.n_groups >= 2 and m.n_head >= 4 and cfg.n_kv_head >= 2
    assert m.n_head // m.n_groups >= 2 and cfg.n_head // cfg.n_kv_head == 2
    assert m.chunk * 2 == PAD and m.conv == 4
    mults = [mup.embedding, mup.lm_head, mup.attention_in, mup.attention_out,
             mup.key, *mup.mlp, m.ssm_in, m.ssm_out, *m.ssm_multipliers]
    assert 1.0 not in mults and len(set(mults)) == len(mults) == 14
    assert set(params["h_0"]) == {"ln_1", "attn", "ssm", "ln_2", "mlp"}
    assert set(params["h_0"]["ssm"]) == {"in", "out", "conv", "a_log", "d",
                                         "dt_bias", "norm"}
    assert params["h_0"]["ssm"]["in"]["kernel"].shape == (64, m.proj_width)
    # a token's retention runs from 0.999 in head 0 to 0.2 in the last
    s = params["h_0"]["ssm"]
    keep = np.exp(-np.exp(s["a_log"]) * jax.nn.softplus(s["dt_bias"]))
    assert keep[0] == pytest.approx(0.999, abs=1e-4)
    assert keep[-1] == pytest.approx(np.exp(-1.6), abs=1e-3)
    assert (np.diff(keep) < 0).all()


def test_the_published_model_and_its_cut():
    cfg = get_model("falcon-h1-34b").config
    cut = get_model("falcon-h1-34b-pp8-1chip").config
    assert (cfg.n_layer, cfg.vocab_size) == (72, 261120)
    assert (cut.n_layer, cut.vocab_size) == (9, 32640) == (72 // 8,
                                                           261120 // 8)
    assert dataclasses.replace(cut, n_layer=72, vocab_size=261120) == cfg
    assert (cut.n_embd, cut.n_head, cut.n_kv_head, cut.head_dim, cut.d_ff,
            cut.rope_theta, cut.rms_eps, cut.block_size) == (
                5120, 20, 4, 128, 21504, 1e11, 1e-5, 262144)
    m = cut.mamba
    assert (m.d_ssm, m.n_head, m.head_dim, m.d_state, m.n_groups, m.conv,
            m.chunk) == (4096, 32, 128, 256, 2, 4, 128)
    assert (m.conv_width, m.proj_width) == (5120, 9248)
    # a layer is 430.1 M parameters, 68.35 M of them the state-space mixer's
    c, f = 5120, 21504
    ssm = c * 9248 + 5120 * 4 + 5120 + 3 * 32 + 4096 + 4096 * c
    attn = 2 * c * 2560 + 2 * c * 512
    assert ssm == 68_351_072 and attn == 31_457_280
    assert round((ssm + attn + 3 * c * f + 2 * c) / 1e6, 1) == 430.1
    # a slot's state a layer: 4.19 MB float32, the K and V of 2048 positions
    leaves = mamba2.slot_leaves(cfg)
    shape, dtype = leaves["ssm_state"]
    assert shape == (32, 128, 256) and dtype == jnp.float32
    assert int(np.prod(shape)) * 4 == 4_194_304 == 2048 * (4 * 128 * 2 * 2)
    assert leaves["conv_tail"] == ((3, 5120), None)


def test_the_rule_is_still_beside_attention_with_its_multipliers(model):
    """ISSUE 66 moved the rule's kind and `beside` into the config
    (`Mamba2Config.beside`, `state_kind.Rule.resolve`): Falcon-H1's stays
    the "full" kind's, BESIDE attention, under its multipliers; the same
    widths with `beside` False resolve to the rule in attention's place, a
    kind of its own."""
    _, cfg, _ = model
    rule = state_kind.config_rule(cfg)
    assert cfg.mamba.beside and not cfg.one_mixer
    assert rule is mamba2.RULE and state_kind.layer_rule(cfg, None) is rule
    assert (rule.kind, rule.beside, rule.forms) == (
        "full", mamba2.mixers_sum, ("ssm_prefill", "ssm_decode"))
    attn_o, ssm_o = jnp.full((1, 2, 4), 3.0), jnp.full((1, 2, 4), 2.0)
    want = cfg.mup.attention_out * 3.0 + cfg.mamba.ssm_out * 2.0
    assert float(jnp.abs(rule.beside(attn_o, ssm_o, cfg) - want).max()) < 1e-6
    assert mamba2._mup_vector(cfg.mamba) is not None  # they are traced
    assert list(llama.family_rows(cfg).cache_kinds) == ["full"]
    alone = state_kind.config_rule(dataclasses.replace(
        cfg, mamba=dataclasses.replace(cfg.mamba, beside=False)))
    assert alone is mamba2.RULE_ALONE
    assert (alone.kind, alone.beside, alone.forms) == (
        "ssm", None, ("prefill", "decode"))
    assert (alone.chunk, alone.step, alone.slot_leaves) == (
        rule.chunk, rule.step, rule.slot_leaves)


def test_a_config_refuses_what_the_block_was_not_built_for():
    base = llama.PRESETS["falcon-h1-test"]
    for wrong in (dict(sliding_window=8), dict(attn_softcap=30.0),
                  dict(parallel_block=True), dict(index_topk=4),
                  dict(retention=llama.RetentionConfig()),
                  dict(mamba=dataclasses.replace(base.mamba, n_groups=3))):
        with pytest.raises(ValueError, match="state-space mixer"):
            dataclasses.replace(base, **wrong)


def test_whole_sequence_logits_match_the_reference(model):
    spec, cfg, params = model
    ids = jnp.asarray(np.stack([_ids(43, 1), _ids(43, 7)]))
    got = spec.apply(params, ids)
    assert float(jnp.abs(got - ref.logits(cfg, params, ids)).max()) < TOL


# (1) prompts that end inside a chunk, on a chunk's edge, one position past
# it and over several chunks: prefill in chunks, install, decode through the
# pool, against the reference's scan over positions
@pytest.mark.parametrize("n", [5, PAD, PAD + 1, 2 * PAD + 9, 4 * PAD])
def test_served_logprobs_match_the_reference(model, plain, n):
    _, cfg, params = model
    prompt = _ids(n, seed=n)
    toks, got = _served_logprobs(plain, prompt, 12)
    want = _reference_logprobs(cfg, params, prompt, toks)
    assert np.abs(got - want).max() < TOL
    assert (got.argmax(-1) == toks).all()


# (2) the three forms of the rule against each other and the reference's
# position scan, at the strongest and the weakest decays, from a zero and
# from an incoming state
@pytest.mark.parametrize("a,dt", [(16.0, 0.1), (1.0, 1e-3)],
                         ids=["strongest", "weakest"])
@pytest.mark.parametrize("incoming", [False, True], ids=["zero", "state"])
def test_the_three_forms_of_the_rule_agree(model, a, dt, incoming):
    """... and a FOURTH: the step kernel (ops/pallas/ssm_step.py),
    interpreted, walking the same positions in place in a pool leaf."""
    _, cfg, _ = model
    m = cfg.mamba
    b, t = 2, 4 * m.chunk
    ks = jax.random.split(jax.random.PRNGKey(int(a)), 5)
    x = jax.random.normal(ks[0], (b, t, m.n_head, m.head_dim))
    bm = jax.random.normal(ks[1], (b, t, m.n_groups, m.d_state))
    cm = jax.random.normal(ks[2], (b, t, m.n_groups, m.d_state))
    # every head at the named extreme, the token's own part on top
    steps = dt * jnp.exp(0.3 * jax.random.normal(ks[3], (b, t, m.n_head)))
    a_log = jnp.full((m.n_head,), np.log(a), jnp.float32)
    d = jnp.linspace(0.5, 1.5, m.n_head)
    s0 = jax.random.normal(ks[4], (b, m.n_head, m.head_dim, m.d_state)) \
        if incoming else jnp.zeros((b, m.n_head, m.head_dim, m.d_state))
    with jax.default_matmul_precision("highest"):
        y_rec, s_rec = mamba2.recurrence(x, steps, a_log, d, bm, cm, s0)
        y_chk, s_chk = mamba2.chunk_rule(x, steps, a_log, d, bm, cm, s0,
                                         m=m, chunk=m.chunk)
        s, ys = s0, []
        for i in range(t):
            y, s = mamba2.step_rule(x[:, i], steps[:, i], a_log, d,
                                    bm[:, i], cm[:, i], s, m=m)
            ys.append(y)

        def kernel_step(pool, xs):
            x_t, dt_t, b_t, c_t = xs
            y, pool = mamba2.step_rule_kernel(
                x_t, dt_t, a_log, d, b_t, c_t, pool, m=m, layer=jnp.int32(1),
                interpret=True)
            return pool, y

        pool, y_ker = jax.lax.scan(
            kernel_step, jnp.stack([s0 + 1.0, s0, s0 - 1.0]),
            tuple(jnp.moveaxis(v, 1, 0) for v in (x, steps, bm, cm)))
    scale = float(jnp.abs(y_rec).max())
    assert float(jnp.abs(y_chk - y_rec).max()) < 1e-5 * scale
    assert float(jnp.abs(jnp.stack(ys, 1) - y_rec).max()) < 1e-5 * scale
    assert float(jnp.abs(jnp.moveaxis(y_ker, 0, 1) - y_rec).max()) \
        < 1e-5 * scale
    s_scale = max(float(jnp.abs(s_rec).max()), 1.0)
    assert float(jnp.abs(s_chk - s_rec).max()) < 1e-5 * s_scale
    assert float(jnp.abs(s - s_rec).max()) < 1e-5 * s_scale
    assert float(jnp.abs(pool[1] - s_rec).max()) < 1e-5 * s_scale
    assert jnp.array_equal(pool[0], s0 + 1.0)
    assert jnp.array_equal(pool[2], s0 - 1.0)
    # the weakest decay REMEMBERS: an incoming state is still most of itself
    if incoming and a == 1.0:
        assert float(jnp.abs(s_rec).mean()) > 0.9 * float(jnp.abs(s0).mean())


# the step kernel, interpreted, IS the plain step: (slots, heads, groups,
# head dim, state width, layers, the layer) — an odd slot count, heads that
# are ONE group, a state of two lane tiles, the test model's own shapes
@pytest.mark.parametrize("slots,h,g,p,n,layers,layer", [
    (3, 4, 2, 16, 16, 3, 1), (5, 4, 1, 16, 16, 4, 2), (1, 6, 3, 8, 256, 3, 1),
    (2, 2, 2, 128, 128, 5, 3)],
    ids=["test_model", "odd_slots_one_group", "two_lane_tiles",
         "one_head_groups"])
def test_the_step_kernel_is_the_plain_step(slots, h, g, p, n, layers, layer):
    """ops/pallas/ssm_step.py, interpreted: one pass over the WHOLE leaf at
    a layer's index that is neither first nor last gives the plain step's
    answers and state and leaves every other layer's states bit-identical."""
    m = llama.Mamba2Config(d_ssm=h * p, n_head=h, d_state=n, n_groups=g)
    ks = jax.random.split(jax.random.PRNGKey(slots), 7)
    pool = jax.random.normal(ks[0], (layers, slots, h, p, n))
    x = jax.random.normal(ks[1], (slots, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[2], (slots, h)))
    bm, cm = (jax.random.normal(k, (slots, g, n)) for k in ks[3:5])
    a_log = jax.random.uniform(ks[5], (h,), minval=0.0, maxval=np.log(16.0))
    d = jax.random.normal(ks[6], (h,))
    want, s_want = mamba2.step_rule(x, dt, a_log, d, bm, cm, pool[layer],
                                    m=m)
    got, pool2 = mamba2.step_rule_kernel(
        x, dt, a_log, d, bm, cm, pool, m=m, layer=jnp.int32(layer),
        interpret=True)
    assert pool2.dtype == jnp.float32 and got.dtype == jnp.float32
    scale = float(jnp.abs(want).max())
    assert float(jnp.abs(got - want).max()) < 1e-5 * scale
    assert float(jnp.abs(pool2[layer] - s_want).max()) < 1e-5
    for other in set(range(layers)) - {layer}:
        assert jnp.array_equal(pool2[other], pool[other])


def test_the_step_kernel_serves_the_plain_steps_logprobs(model, plain):
    """A batcher whose family runs the kernel (interpreted) serves what the
    plain one serves — the reference's log-probabilities — and says so
    under the name the daemon's /statusz reports."""
    _, cfg, params = model
    srv = _batcher(model, family=llama.family_rows(
        cfg, attn_kernel="interpret"), logprobs_k=256)
    prompt = _ids(2 * PAD + 7, 12)
    toks, lps = _served_logprobs(srv, prompt, 6)
    toks_plain, lps_plain = _served_logprobs(plain, prompt, 6)
    assert np.array_equal(toks, toks_plain)
    assert np.abs(lps - lps_plain).max() < 1e-4
    assert np.abs(lps - _reference_logprobs(cfg, params, prompt, toks)
                  ).max() < TOL
    assert srv.family.attn_forms["full"]["ssm_decode"] == "step_kernel"
    assert plain.family.attn_forms["full"]["ssm_decode"] == "step_jnp"
    assert srv.cache["ssm_state"].dtype == jnp.float32


# (3) the padded tail leaves state and convolution tail alone
def test_a_padded_tail_leaves_state_and_tail_alone(model):
    _, cfg, params = model
    p = params["h_1"]["ssm"]
    h = jax.random.normal(jax.random.PRNGKey(5), (1, PAD, cfg.n_embd))
    fresh = state_kind.fresh(mamba2.slot_leaves(cfg), 1, jnp.float32)
    fresh["ssm_state"] = fresh["ssm_state"] + 0.3
    n_real = 11

    def chunk(rows, n):
        """(the output, the leaves a chunk of `rows` leaves behind)."""
        leaves = dict(fresh)
        o = mamba2.mixer_chunk(p, rows, leaves, 0, jnp.int32(n), cfg=cfg,
                               compute_dtype=None)
        return o, leaves

    o_pad, pad = chunk(h, n_real)
    # the same real rows followed by OTHER pad rows: nothing real moves
    other = h.at[:, n_real:].set(7.0)
    o2, pad2 = chunk(other, n_real)
    assert jnp.array_equal(pad["ssm_state"], pad2["ssm_state"])
    assert jnp.array_equal(pad["conv_tail"], pad2["conv_tail"])
    assert jnp.array_equal(o_pad[:, :n_real], o2[:, :n_real])
    # and they are what a chunk of the real rows alone leaves behind
    step = dict(fresh)
    for i in range(n_real):
        mamba2.mixer_step(p, h[:, i:i + 1], step, None, cfg=cfg,
                          compute_dtype=None)
    assert float(jnp.abs(pad["ssm_state"] - step["ssm_state"]).max()) < 1e-5
    assert float(jnp.abs(pad["conv_tail"] - step["conv_tail"]).max()) < 1e-6
    # a program NOT told its count of real positions moves the state
    _, whole = chunk(other, PAD)
    assert float(jnp.abs(whole["ssm_state"] - pad["ssm_state"]).max()) > 1e-2


# (4) a retired and re-admitted slot is a fresh daemon's
def test_a_readmitted_slot_is_a_fresh_daemons(model, plain):
    spec, cfg, params = model
    first = [_served_logprobs(plain, _ids(n, seed=40 + n), 6)
             for n in (21, 33, 7)]       # fills every slot once
    again = [_served_logprobs(plain, _ids(n, seed=40 + n), 6)
             for n in (21, 33, 7)]       # every slot re-admitted
    fresh = _batcher(model, logprobs_k=256)
    for (t1, l1), (t2, l2), n in zip(first, again, (21, 33, 7)):
        t3, l3 = _served_logprobs(fresh, _ids(n, seed=40 + n), 6)
        assert (t1 == t2).all() and (t2 == t3).all()
        assert np.abs(l2 - l3).max() < 1e-5 and np.abs(l1 - l2).max() < 1e-5


# (5) ONE kind with paged leaves AND slot leaves under admit / retire /
# re-admit, with its blocks handed back
def test_one_kind_pages_its_blocks_and_keeps_its_slot_leaves(model):
    spec, cfg, _ = model
    fam = llama.family_rows(cfg)
    assert list(fam.cache_kinds) == ["full"]
    kind = fam.cache_kinds["full"]
    assert set(kind["leaves"]) == {"k", "v"} and kind["tables"] == "tables"
    assert set(kind["slot_leaves"]) == {"ssm_state", "conv_tail"}
    assert kind["layers"] == cfg.n_layer and kind["window"] is None
    assert fam.takes_n_real and fam.requires_paged
    b = _batcher(model, family=fam)
    m = cfg.mamba
    assert b.cache["ssm_state"].shape == (3, 3, m.n_head, m.head_dim,
                                          m.d_state)
    assert b.cache["ssm_state"].dtype == jnp.float32
    assert b.cache["conv_tail"].shape == (3, 3, m.conv - 1, m.conv_width)
    assert b.cache["k"].shape[:2] == (3, 3 * (96 // 8) + 1)
    assert b.cache["tables"].shape == (3, 3, 96 // 8)
    assert b._paged and b._allocator.n_used == 0
    rids = [b.submit(_ids(n, seed=n), 5) for n in (20, 9, 41)]
    # blocks a slot: its prompt's and the step's, whatever the state weighs
    assert b._allocator.n_used == sum(-(-(n + 5) // 8) for n in (20, 9, 41))
    with pytest.raises(Exception):   # bounded by slots: all three are taken
        b.submit(_ids(4), 2)
    out = b.drain()
    assert sorted(out) == sorted(rids) and b._allocator.n_used == 0
    state = np.asarray(b.cache["ssm_state"])
    assert np.abs(state).max() > 0  # retired slots' states are whatever
    rid = b.submit(_ids(20, seed=20), 5)   # re-admitted over them
    assert (b.drain()[rid] == out[rids[0]]).all()
    assert b._allocator.n_used == 0
    # bounded by blocks too: a pool of few blocks holds the second back
    from dnn_tpu.runtime.paged_kvcache import InsufficientBlocks

    small = _batcher(model, paged_blocks=8)
    small.submit(_ids(40, seed=1), 8)
    with pytest.raises(InsufficientBlocks):
        small.submit(_ids(40, seed=2), 8)


# (6) each multiplier changes the logits by more than the tolerance
_MULTIPLIERS = [
    ("mup", "embedding"), ("mup", "lm_head"), ("mup", "attention_in"),
    ("mup", "attention_out"), ("mup", "key"), ("mup", "mlp", 0),
    ("mup", "mlp", 1), ("mamba", "ssm_in"), ("mamba", "ssm_out"),
    *[("mamba", "ssm_multipliers", i) for i in range(5)]]


@pytest.mark.parametrize("path", _MULTIPLIERS,
                         ids=["-".join(map(str, p[1:])) for p in _MULTIPLIERS])
def test_each_multiplier_matters(model, path):
    spec, cfg, params = model
    group, name, *at = path
    old = getattr(getattr(cfg, group), name)
    new = 1.0 if not at else tuple(
        1.0 if i == at[0] else v for i, v in enumerate(old))
    wrong = dataclasses.replace(cfg, **{group: dataclasses.replace(
        getattr(cfg, group), **{name: new})})
    ids = jnp.asarray(_ids(40, 2))[None]
    want = jax.nn.log_softmax(ref.logits(cfg, params, ids))
    # the program with the multiplier left out, and the reference with it
    got = jax.nn.log_softmax(llama.make_apply(wrong)(params, ids))
    assert float(jnp.abs(got - want).max()) > TOL
    assert float(jnp.abs(jax.nn.log_softmax(ref.logits(wrong, params, ids))
                         - got).max()) < TOL


# (7) each control of the reference changes the logits by more than the
# tolerance (the test preset's theta is 1e4: at the published 1e11 ninety
# positions turn one pair of eight)
@pytest.mark.parametrize("wrong", [
    dict(ssm=False), dict(attn=False), dict(reset=PAD), dict(decay=False),
    dict(dt_in=False), dict(group0=True), dict(gate_first=False),
    dict(grouped_norm=False), dict(tail_reset=PAD), dict(ssm_mup=False),
    dict(key_mup=False), dict(rope=False)],
    ids=lambda w: "-".join(f"{k}={v}" for k, v in w.items()))
def test_each_control_matters(model, plain, wrong):
    _, cfg, params = model
    if "rope" in wrong:
        # a rotation left out shows with distance and in few rows: every row
        # of the longest sequence, through the whole-sequence program
        ids = jnp.asarray(_ids(96, seed=13))[None]
        got = np.asarray(jax.nn.log_softmax(model[0].apply(params, ids)))
        assert np.abs(got - np.asarray(jax.nn.log_softmax(
            ref.logits(cfg, params, ids)))).max() < TOL
        off = np.abs(got - np.asarray(jax.nn.log_softmax(
            ref.forward(cfg, params, ids[0], **wrong)))).max()
        assert off > TOL, off
        return
    prompt = _ids(2 * PAD + 9, seed=13)
    toks, got = _served_logprobs(plain, prompt, 12)
    assert np.abs(got - _reference_logprobs(cfg, params, prompt, toks)
                  ).max() < TOL
    off = np.abs(got - _reference_logprobs(cfg, params, prompt, toks,
                                           **wrong)).max()
    assert off > TOL, off


def test_a_state_in_bfloat16_is_told_apart_on_the_cpu(model):
    """What `correct` cannot see on the chip: the state rounded to bfloat16
    after every step misses the tolerance."""
    _, cfg, params = model
    p = params["h_0"]["ssm"]
    h = jax.random.normal(jax.random.PRNGKey(9), (1, 40, cfg.n_embd))
    fresh = state_kind.fresh(mamba2.slot_leaves(cfg), 1, jnp.float32)
    outs = {}
    for name, cast in (("f32", lambda s: s),
                       ("bf16", lambda s: s.astype(jnp.bfloat16).astype(
                           jnp.float32))):
        leaves, ys = dict(fresh), []
        for i in range(40):
            ys.append(mamba2.mixer_step(p, h[:, i:i + 1], leaves, None,
                                        cfg=cfg, compute_dtype=None))
            leaves["ssm_state"] = cast(leaves["ssm_state"])
        outs[name] = jnp.concatenate(ys, 1)
    scale = float(jnp.abs(outs["f32"]).max())
    assert float(jnp.abs(outs["f32"] - outs["bf16"]).max()) > 1e-4 * scale


# (8) the refusals, by the leaves' names
@pytest.mark.parametrize("kw,what", [
    (dict(prefix_cache=4), "prefix_cache"),
    (dict(kv_dtype="int8"), "int8 KV pool"),
    (dict(kv_dtype="int4"), "int4 KV pool"),
    (dict(prefill_chunk_tokens=8), "interleaved prefill")])
def test_refusals_name_the_leaves(model, kw, what):
    with pytest.raises(ValueError, match="k/v/ssm_state/conv_tail") as e:
        _batcher(model, **kw)
    assert what in str(e.value)


def test_a_dense_cache_and_a_verifier_are_refused(model):
    spec, cfg, params = model
    with pytest.raises(ValueError, match="lives in the paged pool|paged"):
        _batcher(model, kv="dense")
    fam = llama.family_rows(cfg)
    assert not fam.paged_ok
    with pytest.raises(ValueError, match="speculative verify"):
        fam.verify_rows(None, None, None, None, None, None)
    with pytest.raises(ValueError, match="forward_with_cache"):
        llama.forward_with_cache(None, None, None, 0, cfg=cfg)


def test_the_step_reaches_blocks_and_state_in_one_layer_body(model):
    """The lowered decode step: the paged read and the one-token rule under
    the same layer loop, the state written at the layer's index (a
    dynamic-update-slice of the whole leaf, no stacking of layers)."""
    from tests.test_chip_compile import first_calls

    b = _batcher(model)
    calls = first_calls([(b, ("_decode",))], prompt_len=21)
    fn, args = calls["_decode"]
    text = fn.lower(*args).as_text(debug_info=True)
    for scope in ("ssm.project", "ssm.conv", "ssm.step", "ssm.out",
                  "state_pool.read", "state_pool.write", "kv_pool."):
        assert scope in text, scope
    assert text.count("stablehlo.while") >= 1
