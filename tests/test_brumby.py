"""Brumby: a model with NO K/V layer — every layer keeps a degree-2
power-retention STATE a slot (models/retention.py) and no position's
anything — behind the batcher and a pool with ZERO paged kinds (two leaves
without a position axis, nothing paged, admission by slots), against the
plain reference (chipbench/reference/brumby.py: the quadratic form, the (T,
T) weights a head). Everything at `brumby-test` size (hidden 64, 3 layers,
4 / 2 heads of 32 in tiles of 8: a state 640 wide; a closed-form chunk of 8
in prefill chunks of 16, <= 96 positions), one module-scoped model.

Tolerances: float32 on the CPU, every program against the reference's full
forward: log-probabilities over the WHOLE vocabulary (the logits up to a
row's constant) within 1e-3 (observed: 1e-6 through chunked prefill,
install and decode). Each negative control misses the same tolerance by
the factor its case states."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import brumby as ref
from dnn_tpu.models import llama, retention
from dnn_tpu.models.gpt import prepare_stacked, stack_layers
from dnn_tpu.registry import get_model
from dnn_tpu.runtime.serving import ContinuousBatcher

TOL = 1e-3
PAD = 16  # the batchers' prompt_pad


@pytest.fixture(scope="module")
def model():
    spec = get_model("brumby-test")
    return spec, spec.config, spec.init(jax.random.PRNGKey(3))


def _ids(n, seed=1):
    return np.asarray(jax.random.randint(
        jax.random.PRNGKey(seed), (n,), 1, 256), np.int32)


def _batcher(model, family=None, **kw):
    _, cfg, params = model
    opts = dict(slots=3, max_len=96, prompt_pad=PAD, kv="auto",
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
    return np.asarray(jax.nn.log_softmax(
        ref.forward(cfg, params, jnp.asarray(seq), rows=rows, **wrong)))


def test_preset_has_every_switch_acting(model):
    _, cfg, params = model
    m = cfg.retention
    assert (m.tile, m.chunk * 2, m.eps) == (8, PAD, 1e-6)
    assert cfg.n_head // cfg.n_kv_head == 2 and cfg.head_dim == 32
    assert cfg.qk_norm and not cfg.tie_word_embeddings
    assert getattr(cfg, "layer_types", None) is None
    assert stack_layers(cfg) == {"blocks": (0, 1, 2)}  # period 1: ONE stack
    assert set(params["h_0"]["attn"]) == {"q", "k", "v", "o", "q_norm",
                                          "k_norm", "decay"}
    gate = params["h_1"]["attn"]["decay"]
    assert gate["w"].shape == (64, 2) and gate["w"].dtype == jnp.float32
    # the biases span g = 0.9 .. 0.9999 a position, a KV head each
    g = np.asarray(jax.nn.sigmoid(gate["bias"]))
    assert np.allclose(g, [0.9, 0.9999], atol=1e-6)
    assert retention.slot_leaves(cfg) == {
        "state": ((2, 32, 640), jnp.float32), "norm": ((2, 640), jnp.float32)}


def test_the_published_model_and_its_cut():
    cfg = get_model("brumby-14b").config
    cut = get_model("brumby-14b-pp8-1chip").config
    assert (cfg.n_layer, cut.n_layer) == (40, 5)
    assert dataclasses.replace(cfg, n_layer=5) == cut  # nothing else is cut
    assert (cut.n_embd, cut.n_head, cut.n_kv_head, cut.head_dim, cut.d_ff,
            cut.vocab_size, cut.rope_theta, cut.rms_eps, cut.block_size) == (
        5120, 40, 8, 128, 17408, 151936, 1e6, 1e-6, 32768)
    assert cut.retention == llama.RetentionConfig(8, 1024, (0.9, 0.9999),
                                                  1e-6)
    # D: 8 704 = 68 x 128 lanes, under the issue's 9 216; a slot a layer is
    # 35.9 MB, what the K and V of 8 772 positions weigh
    wide = retention.state_width(128, 8)
    assert wide == 8704 and wide % 128 == 0
    assert retention.state_width(128, 1) == 8256  # the untiled square
    leaves = retention.slot_leaves(cut)
    nbytes = sum(4 * int(np.prod(s)) for s, _ in leaves.values())
    assert round(nbytes / 1e6, 1) == 35.9 and nbytes // 4096 == 8772
    g = jax.nn.sigmoid(retention.init_gate(jax.random.PRNGKey(0),
                                           cut)["bias"])
    assert np.allclose(g, [0.9, 0.961, 0.985, 0.9945, 0.998, 0.99925,
                           0.99973, 0.9999], atol=3e-4)
    # a layer is 330.3 M parameters
    c, f = 5120, 17408
    assert 2 * c * c + 2 * c * 1024 + c * 8 + 8 + 3 * c * f == 330_342_408


def test_a_config_refuses_what_does_not_go_with_retention():
    base = llama.PRESETS["brumby-test"]
    for wrong in ({"sliding_window": 8}, {"attn_softcap": 30.0},
                  {"parallel_block": True}, {"index_topk": 4},
                  {"rotary_dim": 8}, {"head_dim_override": 36}):
        with pytest.raises(ValueError, match="retention replaces"):
            dataclasses.replace(base, **wrong)


@pytest.mark.parametrize("d,tile", [(32, 8), (128, 8), (128, 16), (16, 1)])
def test_phi_is_the_symmetric_square(d, tile):
    """phi(x) . phi(y) == (x . y)^2 / d for the form chosen, whatever the
    tile (tile 1: the untiled symmetric square, d (d + 1) / 2 wide)."""
    x, y = jax.random.normal(jax.random.PRNGKey(d + tile), (2, 7, d))
    px, py = retention.phi(x, tile), retention.phi(y, tile)
    assert px.shape == (7, retention.state_width(d, tile))
    want = (x * y).sum(-1) ** 2 / d
    assert float(jnp.abs((px * py).sum(-1) - want).max()) \
        < 1e-5 * float(want.max())
    # the step's form — every factor selected by a 0/1 matmul — has the
    # chunk's lanes, one for one: they share the state
    assert float(jnp.abs(retention.phi_selected(x, tile) - px).max()) < 1e-6


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
    assert sorted(plain.cache) == ["norm", "state"]
    assert plain.cache["state"].shape == (3, 3, 2, 32, 640)
    assert plain.cache["norm"].shape == (3, 3, 2, 640)
    assert plain.cache["state"].dtype == jnp.float32
    prompt = _ids(n_prompt, 10 + n_prompt)
    toks, got = _served_logprobs(plain, prompt, 9)
    want = _reference_logprobs(cfg, params, prompt, toks)
    assert (want.argmax(-1) == toks).all()
    assert np.abs(got - want).max() < TOL


# (2) the three forms against each other, where the gates are hardest
def _rule_inputs(case, b=2, kv=2, g=2, t=48, d=32):
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    q = jax.random.normal(ks[0], (b, kv, g, t, d))
    k, v = (jax.random.normal(kk, (b, kv, t, d)) for kk in ks[1:3])
    logit = {"strongest_gate": jnp.full((b, kv, t), -2.0),   # g = 0.12
             "weakest_gate": jnp.full((b, kv, t), 12.0),     # g = 1 - 6e-6
             "mixed_gates": jax.random.normal(ks[3], (b, kv, t)) * 3 + 3}
    return q, k, v, jax.nn.log_sigmoid(logit[case])


@pytest.mark.parametrize("case", ["strongest_gate", "weakest_gate",
                                  "mixed_gates"])
def test_the_chunked_rule_is_the_recurrence_is_the_quadratic_form(case):
    """`chunk_rule` in chunks of 8 and 16, `step_rule` a position at a
    time and `quadratic`, from an empty state: the same outputs, and the
    two recurrent forms the same state and normaliser — with a gate that
    forgets within a position (exp(G_t - G_s) underflows inside a chunk:
    no decay is ever inverted) and one that forgets nothing."""
    q, k, v, logg = _rule_inputs(case)
    b, kv, _, t, d = q.shape
    wide = retention.state_width(d, 8)
    s0, z0 = jnp.zeros((b, kv, d, wide)), jnp.zeros((b, kv, wide))
    want = retention.quadratic(q, k, v, logg, eps=1e-6)

    def one(carry, xs):
        y, s, z = retention.step_rule(*xs, *carry, tile=8, eps=1e-6)
        return (s, z), y

    (s_step, z_step), y_step = jax.lax.scan(one, (s0, z0), (
        jnp.moveaxis(q, 3, 0), jnp.moveaxis(k, 2, 0), jnp.moveaxis(v, 2, 0),
        jnp.moveaxis(logg, 2, 0)))
    y_step = jnp.moveaxis(y_step, 0, 3)
    scale = float(jnp.abs(want).max())
    # (the strongest gate leaves a position next to alone in its own
    # normaliser: 1e-4 of the scale observed there, 1e-6 elsewhere)
    assert float(jnp.abs(y_step - want).max()) < 3e-4 * scale
    for chunk in (8, 16):
        got, s, z = retention.chunk_rule(q, k, v, logg, s0, z0, chunk=chunk,
                                         tile=8, eps=1e-6)
        assert bool(jnp.isfinite(got).all() & jnp.isfinite(s).all())
        assert float(jnp.abs(got - want).max()) < 3e-4 * scale
        assert float(jnp.abs(s - s_step).max()) < 1e-4 * max(
            1.0, float(jnp.abs(s_step).max()))
        assert float(jnp.abs(z - z_step).max()) < 1e-4 * max(
            1.0, float(jnp.abs(z_step).max()))


def test_the_chunked_rule_starts_from_an_incoming_state():
    """48 positions in one call equal 32 and then 16 from the first call's
    state: what a prompt's second prefill chunk computes."""
    q, k, v, logg = _rule_inputs("mixed_gates")
    b, kv, _, _, d = q.shape
    wide = retention.state_width(d, 8)
    rule = lambda q, k, v, g, s, z: retention.chunk_rule(  # noqa: E731
        q, k, v, g, s, z, chunk=16, tile=8, eps=1e-6)
    s0, z0 = jnp.zeros((b, kv, d, wide)), jnp.zeros((b, kv, wide))
    want, s_want, z_want = rule(q, k, v, logg, s0, z0)
    y1, s1, z1 = rule(q[..., :32, :], k[:, :, :32], v[:, :, :32],
                      logg[..., :32], s0, z0)
    y2, s2, z2 = rule(q[..., 32:, :], k[:, :, 32:], v[:, :, 32:],
                      logg[..., 32:], s1, z1)
    got = jnp.concatenate([y1, y2], axis=3)
    assert float(jnp.abs(got - want).max()) < 1e-5
    assert float(jnp.abs(s2 - s_want).max()) < 1e-4
    assert float(jnp.abs(z2 - z_want).max()) < 1e-4


def test_the_step_kernel_is_the_plain_step(model):
    """ops/pallas/retention_step.py, interpreted: one pass over the WHOLE
    pool at a layer's index gives the plain step's answers and state,
    leaves the other layers' states alone, and through the batcher the
    reference's log-probabilities."""
    q, k, v, logg = _rule_inputs("mixed_gates")
    b, kv, _, _, d = q.shape
    wide = retention.state_width(d, 8)
    ks = jax.random.split(jax.random.PRNGKey(5), 2)
    pool = jax.random.normal(ks[0], (3, b, kv, d, wide))
    norms = jnp.abs(jax.random.normal(ks[1], (3, b, kv, wide))) * 9
    args = (q[:, :, :, 7], k[:, :, 7], v[:, :, 7], logg[:, :, 7])
    want, s_want, z_want = retention.step_rule(*args, pool[1], norms[1],
                                               tile=8, eps=1e-6)
    got, pool2, norms2 = retention.step_rule_kernel(
        *args, pool, norms, jnp.int32(1), tile=8, eps=1e-6, interpret=True)
    assert float(jnp.abs(got - want).max()) < 1e-5
    assert float(jnp.abs(pool2[1] - s_want).max()) < 1e-5
    assert float(jnp.abs(norms2[1] - z_want).max()) < 1e-5
    for other in (0, 2):
        assert bool((pool2[other] == pool[other]).all())
        assert bool((norms2[other] == norms[other]).all())
    _, cfg, params = model
    srv = _batcher(model, family=llama.family_rows(
        cfg, attn_kernel="interpret"), logprobs_k=256)
    prompt = _ids(39, 12)
    toks, lps = _served_logprobs(srv, prompt, 5)
    assert np.abs(lps - _reference_logprobs(cfg, params, prompt, toks)
                  ).max() < TOL
    assert srv.family.attn_forms["retention"] == {
        "prefill": "chunked_jnp", "decode": "step_kernel"}


# (3) a pool with zero paged kinds
def test_a_pool_with_no_paged_kind_admits_by_slots_alone(model):
    """Nothing is paged: no tables, no allocator, no codec, no block
    gauges; three slots admit three requests whatever their lengths, a
    fourth waits for a slot (the permanent error is the slot count's, not a
    block count's), a retirement frees the slot and the next request is
    served in it — admit / retire / re-admit, six requests through three
    slots, each the reference's."""
    _, cfg, params = model
    srv = _batcher(model, logprobs_k=256)
    assert not srv._paged and srv._allocator is None
    assert srv._kind_tables == [] and srv._slot_leaves.keys() == {
        "state", "norm"}
    assert not any("blocks" in str(name) for name in srv._obs_gauges)
    assert srv._n_index_layers == 0 and not srv._kv_kinds
    assert srv._state_step_bytes == sum(
        x.nbytes for x in srv.cache.values()) == 3 * 3 * 2 * 33 * 640 * 4
    prompts = [_ids(n, 30 + n) for n in (90, 7, 33, 16, 50, 21)]
    rids = [srv.submit(p, 6, logprobs=True) for p in prompts[:3]]
    with pytest.raises(RuntimeError, match="slot"):
        srv.submit(prompts[3], 6)
    srv.drain()
    rids += [srv.submit(p, 6, logprobs=True) for p in prompts[3:]]
    srv.drain()
    for rid, prompt in zip(rids, prompts):
        want = _reference_logprobs(cfg, params, prompt, srv.results[rid])
        assert np.abs(_by_vocabulary(srv.token_logprobs[rid]) - want
                      ).max() < TOL


def test_there_is_nothing_to_page(model):
    with pytest.raises(ValueError, match="nothing to page"):
        _batcher(model, kv="paged")
    with pytest.raises(ValueError, match="nothing to page"):
        _batcher(model, paged_blocks=64)
    assert not _batcher(model, kv="dense")._paged


# (4) a slot retired and admitted again under the pipelined loop
def test_a_readmitted_slot_under_the_pipeline_is_a_fresh_daemons(model):
    """Two slots; the request in slot 1 retires while the pipelined loop
    has a step in flight (its stale step updates the retired slot's state
    once more), then a new request is installed there: its
    log-probabilities are those of a batcher that never served anything —
    the install writes the whole state and normaliser, and nothing else
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


# (5) the padded tail
def test_the_padded_tail_leaves_state_and_normaliser_alone(model):
    """A chunk of 16 whose first 5 positions are real: state and
    normaliser after it are those after the 5 alone (a recurrence has no
    mask to hide a tail behind), and a program NOT told its count of real
    positions has a state that the pads moved."""
    _, cfg, params = model
    family = llama.family_rows(cfg)
    prepared = prepare_stacked(dict(params), cfg)
    row = family.init_cache(1, 96, jnp.float32)
    ids = _ids(5, 3)
    padded = jnp.asarray(np.pad(ids, (0, 11)))[None]
    _, told = family.prefill(prepared, padded, row, 0, n_real=jnp.int32(5))
    _, alone = family.prefill(prepared, jnp.asarray(ids)[None], row, 0)
    _, untold = family.prefill(prepared, padded, row, 0)
    for name in ("state", "norm"):
        scale = float(jnp.abs(alone[name]).max())
        assert float(jnp.abs(told[name] - alone[name]).max()) < 1e-5 * scale
        assert float(jnp.abs(untold[name] - alone[name]).max()) \
            > 0.05 * scale


# (6) negative controls: the reference with ONE thing wrong misses the
# program's log-probabilities by at least `factor` tolerances
@pytest.mark.parametrize("wrong,factor", [
    ({"degree": 1}, 100), ({"gate": False}, 10), ({"normaliser": False}, 100),
    ({"head_gate": False}, 3), ({"rope": False}, 10),
    ({"qk_norm": False}, 3), ({"reset": PAD}, 10)],
    ids=lambda w: "-".join(f"{k}={v}" for k, v in w.items())
    if isinstance(w, dict) else None)
def test_one_thing_wrong_misses_the_tolerance(model, plain, wrong, factor):
    _, cfg, params = model
    prompt = _ids(64, 49)
    toks, got = _served_logprobs(plain, prompt, 9)
    assert np.abs(got - _reference_logprobs(cfg, params, prompt, toks)
                  ).max() < TOL
    off = _reference_logprobs(cfg, params, prompt, toks, **wrong)
    assert np.abs(got - off).max() > factor * TOL


def test_a_state_held_in_bfloat16_is_held_here(model, plain, monkeypatch):
    """What `correct` on the chip cannot tell apart (the configuration's
    `check.why`): a state rounded to bfloat16 after every step. The
    program's float32 state agrees with the reference to a tenth of the
    tolerance over 40 decoded tokens; the same step with its state rounded
    misses that by more than three times."""
    _, cfg, params = model
    prompt = _ids(33, 49)
    toks, got = _served_logprobs(plain, prompt, 40)
    want = _reference_logprobs(cfg, params, prompt, toks)
    assert plain.cache["state"].dtype == jnp.float32
    assert np.abs(got - want).max() < TOL / 10
    step = retention.step_rule

    def rounded(*a, **kw):
        y, s, z = step(*a, **kw)
        return (y, s.astype(jnp.bfloat16).astype(jnp.float32),
                z.astype(jnp.bfloat16).astype(jnp.float32))

    monkeypatch.setattr(retention, "step_rule", rounded)
    srv = _batcher(model, logprobs_k=256)
    rid = srv.submit(prompt, 40, logprobs=True)
    srv.drain()
    off = _reference_logprobs(cfg, params, prompt, srv.results[rid])
    assert np.abs(_by_vocabulary(srv.token_logprobs[rid]) - off).max() \
        > 3 * TOL / 10


# (7) the refusals, by the leaves' names
@pytest.mark.parametrize("kw,what", [
    ({"prefix_cache": 8}, "prefix_cache"),
    ({"kv_dtype": "int8"}, "int8 KV pool"),
    ({"kv_dtype": "int4"}, "int4 KV pool"),
    ({"prefill_chunk_tokens": 16}, "interleaved prefill"),
    ({"decode_buckets": True}, "decode_buckets")])
def test_what_assumes_k_and_v_is_refused_by_name(model, kw, what):
    with pytest.raises(ValueError, match="state/norm") as e:
        _batcher(model, **kw)
    assert what in str(e.value)


def test_speculative_verify_is_refused(model):
    _, cfg, _ = model
    family = llama.family_rows(cfg)
    assert not family.paged_ok
    with pytest.raises(ValueError, match="state/norm"):
        family.verify_rows()
    with pytest.raises(ValueError, match="float32"):
        family.init_cache(2, 32, "int8")


def test_the_counters_count_state_bytes_and_real_and_pad_positions(
        model, monkeypatch):
    """`state_pool.*_total`: a step reads and writes every slot's two
    leaves, a chunk's positions are real or pad, a finish installs a state
    a layer; no K/V byte is ever read."""
    from dnn_tpu import obs
    from dnn_tpu.obs.timeline import StepClock

    monkeypatch.setattr(obs, "enabled", lambda: True)
    srv = _batcher(model)
    srv.step_clock = clock = StepClock()
    srv.submit(_ids(21, 3), 4)  # two chunks: 16 real, then 5 real + 11 pad
    srv.drain()
    total = clock.state_total
    steps = total["bytes_read"] // srv._state_step_bytes
    assert steps >= 3 and total["bytes_read"] == total["bytes_written"] \
        == steps * srv._state_step_bytes
    assert total["kv_bytes_read"] == 0
    assert (total["prefill_real_positions"],
            total["prefill_pad_positions"]) == (21, 11)
    assert total["installs"] == model[1].n_layer


def test_a_prefill_handed_off_carries_the_state(model):
    """`export_prefill` / `submit(prefilled=)`: the transient row's two
    leaves ARE the prompt's state — a decode replica that adopts them
    serves the tokens of one that prefilled the prompt itself."""
    prompt = _ids(37, 91)
    here = _batcher(model)
    rid = here.submit(prompt, 7)
    want = here.drain()[rid].tolist()
    payload = _batcher(model).export_prefill(prompt)
    assert [np.asarray(x).shape for x in payload["row"]] == [
        (3, 1, 2, 640), (3, 1, 2, 32, 640)]
    there = _batcher(model)
    rid = there.submit(prompt, 7, prefilled=payload)
    assert there.drain()[rid].tolist() == want
    assert there.prefill_chunks_run == 0
