"""Solar-Open2: three layers in four keep a STATE — the gated delta rule
with a decay a channel behind a 4-tap convolution (models/kda.py) — and no
position's anything, beside one softmax layer (unrotated, gated) whose
cache is K and V; behind the batcher and ONE paged pool whose "linear" kind
has leaves without a position axis, against the plain reference
(chipbench/reference/solar.py: a `lax.scan` over positions). Everything at
`solar-open2-test` size (hidden 64, 4 layers F L L L, 4 heads of 16, a
closed-form chunk of 8 in prefill chunks of 16, <= 96 positions), one
module-scoped model.

Tolerances: float32 on the CPU, every program against the reference's full
forward: log-probabilities over the WHOLE vocabulary (the logits up to a
row's constant) within 1e-3 (observed: 2e-6 through chunked prefill,
install and decode; 8e-7 whole-sequence). Each negative control misses the
same tolerance by the factor its case states."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import solar as ref
from dnn_tpu.models import kda, llama, llama_moe
from dnn_tpu.models.gpt import layer_runs, prepare_stacked, stack_layers
from dnn_tpu.registry import ParamParts, get_model
from dnn_tpu.runtime.serving import ContinuousBatcher

TOL = 1e-3
PAD = 16  # the batchers' prompt_pad


@pytest.fixture(scope="module")
def model():
    spec = get_model("solar-open2-test")
    return spec, spec.config, spec.init(jax.random.PRNGKey(3))


def _ids(n, seed=1):
    return np.asarray(jax.random.randint(
        jax.random.PRNGKey(seed), (n,), 1, 256), np.int32)


def _batcher(model, family=None, **kw):
    spec, cfg, params = model
    opts = dict(slots=3, max_len=96, prompt_pad=PAD, kv="paged", block_len=8,
                family=family or spec.extras["family_rows"]())
    opts.update(kw)
    return ContinuousBatcher(cfg, prepare_stacked(dict(params), cfg), **opts)


@pytest.fixture(scope="module")
def plain(model):
    """One batcher whose log-probabilities cover the vocabulary, for the
    tests that each drain it: its three programs compile once."""
    return _batcher(model, logprobs_k=256)


def _served_logprobs(b, prompt, n_new):
    """(tokens, (n_new, V) log-probabilities in vocabulary order) of one
    greedy request through the batcher's chunk, finish and step programs."""
    rid = b.submit(prompt, n_new, logprobs=True)
    toks = b.drain()[rid]
    return toks, _by_vocabulary(b.token_logprobs[rid])


def _by_vocabulary(lp):
    """A request's top-V log-probabilities, in vocabulary order."""
    full = np.empty_like(lp["top_logprobs"])
    np.put_along_axis(full, lp["top_ids"], lp["top_logprobs"], axis=-1)
    return full


def _reference_logprobs(cfg, params, prompt, toks, **wrong):
    seq = np.concatenate([prompt, toks])
    rows = np.arange(len(prompt) - 1, len(seq) - 1)
    return np.asarray(jax.nn.log_softmax(
        ref.forward(cfg, params, jnp.asarray(seq), rows=rows, **wrong)))


def test_preset_has_every_switch_acting(model):
    _, cfg, params = model
    assert cfg.layer_types == ("full", "linear", "linear", "linear")
    assert cfg.kv_full == llama.KvKind(window=None, rope=False)
    assert cfg.attn_gate and cfg.kda.chunk * 2 == PAD and cfg.kda.conv == 4
    assert cfg.n_head // cfg.n_kv_head == 2 and cfg.head_dim == 32
    assert cfg.router.select_bias and cfg.router.scoring == "sigmoid"
    assert cfg.d_shared and not cfg.shared_gate and not cfg.first_k_dense
    assert cfg.experts_held < cfg.n_expert
    assert stack_layers(cfg) == {"blocks": (0,), "linear_blocks": (1, 2, 3)}
    assert layer_runs(cfg) == [("blocks", (0, 1), "full", (0, 1)),
                               ("linear_blocks", (0, 3), "linear", (0, 3))]
    assert "gate" in params["h_0"]["attn"] and "moe" in params["h_0"]
    assert set(params["h_1"]["attn"]) == {
        "q", "k", "v", "o", "f1", "f2", "g1", "g2", "b", "conv", "a_log",
        "dt_bias", "o_norm"}
    # the decay's initialisation spans (e^-1.6, e^-0.001) a position
    a = np.exp(np.asarray(params["h_1"]["attn"]["a_log"]))
    assert (1.0 <= a).all() and (a <= 16.0).all()


def test_the_published_model_and_its_cut():
    cfg = get_model("solar-open2-250b").config
    assert [i for i, t in enumerate(cfg.layer_types) if t == "full"] == \
        list(range(0, 48, 4))
    assert len(layer_runs(cfg)) == 24
    cut = get_model("solar-open2-250b-ep8-1chip").config
    assert cut.layer_types == cfg.layer_types[:4] and cut.vocab_size == 24576
    assert (cut.n_embd, cut.n_head, cut.n_kv_head, cut.head_dim, cut.d_ff,
            cut.d_shared, cut.n_expert, cut.router_top_k,
            cut.experts_held) == (4096, 64, 8, 128, 1280, 1280, 320, 8, 40)
    assert cut.kda == cfg.kda == kda.KdaConfig(64, 128, 4, 128, 64)
    # one linear mixer is 137.7 M parameters, the softmax layer's 109.1 M
    c, w, r, h = 4096, 8192, 128, 64
    assert 4 * c * w + 2 * (c * r + r * w) + c * h + 3 * w * 4 == 137_723_904
    assert 2 * c * w + 2 * c * 1024 + c * w == 109_051_904


def test_a_config_names_its_kinds_whole():
    base = llama_moe.PRESETS["solar-open2-test"]
    with pytest.raises(ValueError, match="layer_types comes with"):
        dataclasses.replace(base, layer_types=None)
    with pytest.raises(ValueError, match="kda names the"):
        dataclasses.replace(base, layer_types=("linear",) * 4)
    with pytest.raises(ValueError, match="kda names the"):
        dataclasses.replace(base, kv_full=llama.KvKind(window=4))
    with pytest.raises(ValueError, match="kda names the"):
        dataclasses.replace(base, layer_types=("full", "window", "linear",
                                               "linear"))


def test_whole_sequence_logits_match_the_reference(model):
    spec, cfg, params = model
    ids = jnp.asarray(np.stack([_ids(43, 1), _ids(43, 7)]))
    got = spec.apply(params, ids)
    assert float(jnp.abs(got - ref.logits(cfg, params, ids)).max()) < TOL


# (1) prompts that end inside a chunk, on a chunk's edge and one position
# past it, across three and more chunks of 16
@pytest.mark.parametrize("n_prompt", [5, 16, 17, 39, 48, 49, 64],
                         ids=lambda n: f"prompt{n}")
def test_prefill_install_and_decode_match_the_reference(model, plain,
                                                        n_prompt):
    _, cfg, params = model
    assert sorted(plain.cache) == ["conv_tail", "k", "state", "tables", "v"]
    assert plain.cache["state"].shape == (3, 3, 4, 16, 16)
    assert plain.cache["state"].dtype == jnp.float32
    assert plain.cache["conv_tail"].shape == (3, 3, 3, 3 * 64)
    assert plain.cache["k"].shape[0] == 1  # the ONE softmax layer
    prompt = _ids(n_prompt, 10 + n_prompt)
    toks, got = _served_logprobs(plain, prompt, 9)
    want = _reference_logprobs(cfg, params, prompt, toks)
    assert (want.argmax(-1) == toks).all()
    assert np.abs(got - want).max() < TOL
    assert plain._allocator.n_used == 0


# (2) the chunked form against the token recurrence, where it is hardest
RULE_CASES = ["beta_above_one", "strongest_decay", "equal_keys"]


def _rule_inputs(case, b, h, t, d):
    """q, k, v, g, beta and a random INCOMING state for `chunk_rule`: with
    every beta in (1, 2) (negative eigenvalues: the triangular system's
    off-diagonal is at its largest); with the initialisation's strongest
    decay, g = -1.6 a position in every channel over a whole chunk of 64
    (exp(102) overflows float32: the rule must never form 1 / cumulative
    decay); with ONE key in every position, beta 1.99 and next to no decay
    (a repeated token: the triangular system's matrix is 1.99 everywhere
    below the diagonal and its powers reach 1e6 before they cancel: the
    solve is by exact substitution); and with the last 37 positions PADS
    (g = 0, beta = 0: the identity on the state)."""
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    q, k, v = (jax.random.normal(kk, (b, h, t, d)) for kk in ks[:3])
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) / 4.0
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    if case == "strongest_decay":
        beta = 2.0 * jax.nn.sigmoid(jax.random.normal(ks[3], (b, h, t)))
        g = jnp.full((b, h, t, d), -1.6)
    elif case == "equal_keys":
        k = jnp.broadcast_to(k[:, :, :1], k.shape)
        beta = jnp.full((b, h, t), 1.99)
        g = jnp.full((b, h, t, d), -1e-3)
    else:
        beta = 1.0 + jax.random.uniform(ks[3], (b, h, t)) * 0.999
        g = -jnp.exp(jax.random.uniform(ks[4], (b, h, t, d), minval=-7.0,
                                        maxval=0.5))
    if case == "padded_tail":
        real = jnp.arange(t) < t - 37
        g = jnp.where(real[:, None], g, 0.0)
        beta = jnp.where(real, beta, 0.0)
    return q, k, v, g, beta, jax.random.normal(ks[5], (b, h, d, d))


@pytest.mark.parametrize("case", RULE_CASES)
def test_the_chunked_rule_is_the_recurrence(case):
    """`chunk_rule` from an incoming state equals `step_rule` a position
    at a time in `_rule_inputs`' three hard cases. Finite, within 1e-4
    (2e-3 with equal keys)."""
    q, k, v, g, beta, s0 = _rule_inputs(case, 2, 3, 128, 16)
    with jax.default_matmul_precision("highest"):
        got, s_got = kda.chunk_rule(q, k, v, g, beta, s0, chunk=64)

        def one(s, xs):
            o, s = kda.step_rule(*xs, s)
            return s, o

        s_want, want = jax.lax.scan(one, s0, tuple(
            jnp.moveaxis(x, 2, 0) for x in (q, k, v, g, beta)))
    want = jnp.moveaxis(want, 0, 2)
    assert bool(jnp.isfinite(got).all() & jnp.isfinite(s_got).all())
    # (equal keys: the system is as ill-conditioned as float32 lets a
    # sound solve show — 4e-4 observed, on outputs of 0.3)
    tol = 2e-3 if case == "equal_keys" else 1e-4
    assert float(jnp.abs(got - want).max()) < tol
    assert float(jnp.abs(s_got - s_want).max()) < tol


@pytest.mark.parametrize("case", [*RULE_CASES, "padded_tail"])
def test_the_rule_kernel_is_the_plain_rule(case):
    """ops/pallas/delta_rule.py, interpreted, at the widths it is built for
    (heads of 128, chunks of 64 in sub-blocks of 16): the chunked rule
    made in the kernel, from an incoming state, gives the plain form's
    outputs and outgoing state within 1e-5 (2e-3 with equal keys), finite —
    where the system is worst conditioned, where a decay underflows, and
    over a padded tail."""
    args = _rule_inputs(case, 1, 4, 128, 128)
    with jax.default_matmul_precision("highest"):
        want = kda.chunk_rule(*args, chunk=64)
        got = kda.chunk_rule(*args, chunk=64, kernel="interpret")
    tol = 2e-3 if case == "equal_keys" else 1e-5
    for x, y in zip(got, want):
        assert bool(jnp.isfinite(x).all())
        assert float(jnp.abs(x - y).max()) < tol


@pytest.mark.parametrize("head_dim,chunk,form", [
    (128, 64, "chunked_kernel"), (16, 8, "chunked_jnp")],
    ids=["heads_of_128", "heads_of_16"])
def test_the_rule_kernel_behind_the_batcher(model, head_dim, chunk, form):
    """Through the batcher with the family's kernels interpreted: the
    reference's log-probabilities, the chunk program's linear layers in
    ops/pallas/delta_rule.py where the widths are the kernel's (heads of
    128, closed-form chunks of 64) and in the plain form where they are
    not (the test preset's heads of 16), `attn_forms` saying which."""
    spec, cfg, params = model
    if head_dim != cfg.kda.head_dim:
        cfg = dataclasses.replace(cfg, kda=dataclasses.replace(
            cfg.kda, n_head=2, head_dim=head_dim, chunk=chunk))
        params = llama_moe.init(jax.random.PRNGKey(5), cfg)
    family = llama_moe.family_rows(cfg)
    family.attn_kernel = "interpret"
    srv = _batcher((spec, cfg, params), family=family, logprobs_k=256,
                   prompt_pad=max(PAD, chunk))
    prompt = _ids(39, 12)
    toks, lps = _served_logprobs(srv, prompt, 5)
    assert np.abs(lps - _reference_logprobs(cfg, params, prompt, toks)
                  ).max() < TOL
    assert srv.family.attn_forms["linear"]["prefill"] == form
    assert srv.family.attn_forms["full"]["prefill"] == "kernel"


# (3) the shares
@pytest.mark.parametrize("n_shares", [2, 8])
def test_the_shares_add_up(model, n_shares):
    """The shares' routed parts plus the shared expert counted ONCE are
    the uncut layer (8 shares: the deployment's eight chips, one expert
    each at this size), for a linear layer."""
    _, cfg, _ = model
    whole_cfg = dataclasses.replace(cfg, experts_held=None)
    whole = llama_moe.init(jax.random.PRNGKey(3), whole_cfg)
    x = jax.random.normal(jax.random.PRNGKey(7), (30, cfg.n_embd))
    p, kw = whole["h_2"], ref.layer_args(whole_cfg, 2)
    want = ref.layer(p, x, **kw)
    none = {**p, "moe": {**p["moe"], **{n: p["moe"][n][:0]
                                        for n in ("wg", "wu", "wd")}}}
    total = ref.layer(none, x, **kw)  # the mixer and the shared expert
    count = cfg.n_expert // n_shares
    for first in range(0, cfg.n_expert, count):
        share = {**p, "moe": {**p["moe"], **{
            n: p["moe"][n][first:first + count] for n in ("wg", "wu", "wd")}}}
        total = total + (
            ref.layer(share, x, **{**kw, "first": first}, shared=False)
            - ref.layer(none, x, **kw, shared=False))
        held = dataclasses.replace(cfg, experts_first=first,
                                   experts_held=count)
        got = held.default_ffn()(share, x[None])[0]
        routed, common = ref._experts(share["moe"], x, top_k=kw["top_k"],
                                      first=first, bias=True)
        assert float(jnp.abs(got - (routed + common)).max()) < TOL
    assert float(jnp.abs(total - want).max()) < TOL


# (4) a slot retired and admitted again under the pipelined loop
def test_a_readmitted_slot_under_the_pipeline_is_a_fresh_daemons(model):
    """Two slots; the request in slot 1 retires while the pipelined loop
    has a step in flight (its stale step updates the retired slot's
    state once more), then a new request is installed there: its
    log-probabilities are those of a batcher that never served anything
    — the install writes the whole state and tail, and nothing else
    resets a slot."""
    _, cfg, params = model
    prompt = _ids(21, 77)
    fresh = _batcher(model, slots=2, logprobs_k=256, overlap=True)
    toks, want = _served_logprobs(fresh, prompt, 8)
    srv = _batcher(model, slots=2, logprobs_k=256, overlap=True)
    srv.submit(_ids(30, 5), 40)          # slot 0 lives on throughout
    first = srv.submit(_ids(19, 6), 5)   # slot 1 retires early
    while first not in srv.results:
        srv.step()
    assert srv._inflight is not None  # a step is in flight over slot 1
    assert float(jnp.abs(srv.cache["state"][:, 1]).max()) > 0
    rid = srv.submit(prompt, 8, logprobs=True)
    srv.drain()
    assert srv.stale_rows >= 1
    got = _by_vocabulary(srv.token_logprobs[rid])
    assert srv.results[rid].tolist() == toks.tolist()
    assert np.abs(got - want).max() < 1e-5
    assert np.abs(got - _reference_logprobs(cfg, params, prompt, toks)
                  ).max() < TOL


# (5) negative controls: the reference with ONE thing wrong misses the
# program's log-probabilities by at least `factor` tolerances
@pytest.mark.parametrize("wrong,factor", [
    ({"beta_scale": 1.0}, 20), ({"head_decay": True}, 3),
    ({"conv": False}, 50), ({"rope": True}, 3), ({"gate": False}, 20),
    ({"shared": False}, 100), ({"bias": False}, 50)],
    ids=lambda w: "-".join(f"{k}={v}" for k, v in w.items())
    if isinstance(w, dict) else None)
def test_one_thing_wrong_misses_the_tolerance(model, plain, wrong, factor):
    _, cfg, params = model
    prompt = _ids(64, 49)  # (the rotation moves 4e-3 at 64 positions)
    toks, got = _served_logprobs(plain, prompt, 9)
    assert np.abs(got - _reference_logprobs(cfg, params, prompt, toks)
                  ).max() < TOL
    off = _reference_logprobs(cfg, params, prompt, toks, **wrong)
    assert np.abs(got - off).max() > factor * TOL


def test_a_state_held_in_bfloat16_is_told_apart_below_the_tolerance(model,
                                                                   plain):
    """The state rounded to bfloat16 after every position moves the tiny
    model's log-probabilities by half a tolerance — `correct` on the chip
    cannot tell it either (the configuration's `check.why`) — so it is
    held here at a tenth of the tolerance: the program's float32 state
    agrees with the reference to that, the rounded state does not to
    three times it."""
    _, cfg, params = model
    prompt = _ids(64, 49)
    toks, got = _served_logprobs(plain, prompt, 9)
    assert plain.cache["state"].dtype == jnp.float32
    assert np.abs(got - _reference_logprobs(cfg, params, prompt, toks)
                  ).max() < TOL / 10
    off = _reference_logprobs(cfg, params, prompt, toks,
                              state_dtype="bfloat16")
    assert np.abs(got - off).max() > 3 * TOL / 10


def test_pad_positions_let_into_the_state_miss_the_tolerance(model, plain):
    """A program that runs the recurrence over its padded tail (the chunk
    program not told how many positions are real) misses the reference by
    50 tolerances — and IS the reference whose linear layers run over the
    pad positions while the softmax layer does not see them (`skip`):
    what the controls on the chip emulate."""
    spec, cfg, params = model
    family = spec.extras["family_rows"]()
    family.takes_n_real = False
    leaky = _batcher(model, family=family, logprobs_k=256)
    prompt = _ids(39, 49)  # 9 pad positions in its third chunk
    toks, got = _served_logprobs(leaky, prompt, 9)
    want = _reference_logprobs(cfg, params, prompt, toks)
    assert np.abs(got - want).max() > 50 * TOL
    n_pad = -len(prompt) % PAD
    seq = np.concatenate([prompt, np.zeros(n_pad, np.int32), toks])
    skip = np.zeros(len(seq), bool)
    skip[len(prompt):len(prompt) + n_pad] = True
    rows = np.r_[len(prompt) - 1, np.arange(len(prompt) + n_pad,
                                            len(seq) - 1)]
    emulated = np.asarray(jax.nn.log_softmax(ref.forward(
        cfg, params, jnp.asarray(seq), rows=rows, skip=jnp.asarray(skip))))
    # the first token comes from the last REAL row either way
    assert np.abs(got[0] - want[0]).max() < TOL
    assert np.abs(got[1:] - emulated[1:]).max() < TOL
    # and the sound program, told, is not that
    toks, sound = _served_logprobs(plain, prompt, 9)
    assert np.abs(sound - _reference_logprobs(cfg, params, prompt, toks)
                  ).max() < TOL


# (6) what assumes K and V alone refuses the family, by name
@pytest.mark.parametrize("kw,match", [
    ({"prefix_cache": 4}, "prefix_cache .the radix prefix store and the "
                          "fleet KV tier"),
    ({"kv_dtype": "int8"}, "an int8 KV pool"),
    ({"kv_dtype": "int4"}, "an int4 KV pool"),
    ({"prefill_chunk_tokens": 8}, "interleaved prefill"),
    ({"kv": "dense"}, "lives in the paged pool|dense")],
    ids=["prefix_cache_and_kv_tier", "int8", "int4", "interleaved", "dense"])
def test_refusals_name_the_leaves(model, kw, match):
    with pytest.raises(ValueError, match=match) as e:
        _batcher(model, **kw)
    if "kv" not in kw:
        assert "k/v/state/conv_tail" in str(e.value)


def test_speculative_verify_is_refused(model):
    spec, _, _ = model
    with pytest.raises(ValueError, match="speculative verify"):
        spec.extras["family_rows"]().verify_rows()


# ----------------------------------------------------------------------
# the pool, the counters, the boot
# ----------------------------------------------------------------------

def test_the_state_kind_draws_no_blocks_and_is_counted(plain):
    """Admission by length counts the softmax layer's blocks alone; the
    `state_pool_*` counters count a step's state bytes (every slot, read
    and written), the live K and V bytes, real and pad positions of a
    chunk and the states installed; `/statusz` names both kinds' forms."""
    from dnn_tpu.obs.timeline import StepClock

    b = plain
    b.step_clock = clock = StepClock().install()
    b.submit(_ids(20, 1), 6)   # two chunks of 16 (12 pads), then 5 steps
    assert b._allocator.n_used == 4 and not b._allocator.kinds
    assert b._kind_tables == ["tables"] and not b._window_kinds
    b.drain()
    b.step_clock = None
    tot = clock.state_total
    state = 3 * 3 * (4 * 16 * 16 * 4 + 3 * 192 * 4)  # layers x slots x bytes
    assert b._state_step_bytes == state
    assert tot["bytes_read"] == tot["bytes_written"] == 5 * state
    assert tot["prefill_real_positions"] == 20
    assert tot["prefill_pad_positions"] == 12
    assert tot["installs"] == 3
    # steps at positions 20 .. 23 read 21 .. 24 rows of 2 heads x 32 x K, V
    assert tot["kv_bytes_read"] == sum(range(21, 25)) * 2 * 32 * 2 * 4
    assert clock.mla_kind_total[("attn", "full", "decode")] == sum(
        range(21, 25))
    assert b.family.attn_forms["linear"] == {"prefill": "chunked_jnp",
                                             "decode": "step_jnp"}
    assert b.family.attn_forms["full"]["decode"] == "gather_einsum"


def test_the_chunk_program_compiles_once_for_any_count_of_real_positions(
        plain):
    for n in (3, 16, 23, 40):
        plain.submit(_ids(n, n), 2)
        plain.drain()
    assert plain._prefill_chunk._cache_size() == 1
    assert plain._prefill_finish._cache_size() == 1
    assert plain._decode._cache_size() == 1


def test_the_held_tree_is_bit_identical_to_the_whole_inits():
    from dnn_tpu.node import _stack_and_release
    from dnn_tpu.ops.nn import hold_in_compute_dtype

    spec = get_model("solar-open2-test")
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
    lin = got["linear_blocks"]["attn"]
    assert lin["q"]["kernel"].shape == (3, 64, 64)
    assert lin["q"]["kernel"].dtype == lin["f2"]["kernel"].dtype \
        == jnp.bfloat16
    # what the rule reads in its own dtype stays float32
    for leaf in (lin["a_log"], lin["dt_bias"], lin["conv"]["q"]["taps"],
                 lin["o_norm"]["scale"],
                 got["blocks"]["moe"]["router"]["kernel"]):
        assert leaf.dtype == jnp.float32
    assert got["blocks"]["attn"]["gate"]["kernel"].dtype == jnp.bfloat16


def test_the_checks_margins_a_layer_at_a_time_are_the_whole_trees(model):
    from chipbench import serve_dots, serve_keye

    spec, cfg, params = model
    prompts = [_ids(30, 2), _ids(30, 1)]
    tokens = [list(_ids(6, 3)), list(_ids(6, 4))]
    a = serve_dots.served_margins(
        "solar", cfg, spec.init_parts(jax.random.PRNGKey(3)), prompts,
        tokens)
    b = serve_keye.served_margins("solar", cfg, params, prompts, tokens)
    for key in ("worst_margin", "mean_margin", "argmax_share",
                "mean_logit_sigma"):
        assert abs(a[key] - b[key]) < 1e-5, key
    assert a["positions"] == b["positions"] == 12
