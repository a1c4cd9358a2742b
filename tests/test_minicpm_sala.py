"""MiniCPM-SALA: a DENSE model of two kinds of layers — a softmax layer
(unrotated, gated) that reads only the BLOCKS a score over mean-pooled keys
selects (models/block_select.py; the pooled keys a STRIDED leaf of the paged
pool) beside three linear-attention layers with a FIXED decay a head and a
state alone a slot (models/lightning.py) — behind the batcher and ONE paged
pool, against the plain reference (chipbench/reference/minicpm_sala.py: a
`lax.scan` over positions, full (T, T) scores under a mask from a stable
argsort). Everything at `minicpm-sala-test` size (hidden 64, 4 layers F L L
L, 2 KV heads of 2 query heads of 16, blocks of 8 with a pooled key of 4
every 2 positions, a window of 2 blocks and the 2 of largest score, a
closed-form chunk of 8 in prefill chunks of 16, <= 256 positions), one
module-scoped model.

Tolerances: float32 on the CPU, every program against the reference's full
forward: log-probabilities over the WHOLE vocabulary (the logits up to a
row's constant) within 1e-4 (observed: 1e-6 through chunked prefill, install
and decode at 221 positions, where 21 of 27 blocks are dropped). Each
negative control misses the same tolerance by the factor its case states."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import minicpm_sala as ref
from dnn_tpu.models import block_select, lightning, llama
from dnn_tpu.models.gpt import layer_runs, prepare_stacked, stack_layers
from dnn_tpu.registry import get_model
from dnn_tpu.runtime.serving import ContinuousBatcher

TOL = 1e-4
PAD = 16  # the batchers' prompt_pad


@pytest.fixture(scope="module")
def model():
    spec = get_model("minicpm-sala-test")
    return spec, spec.config, spec.init(jax.random.PRNGKey(3))


def _ids(n, seed=1):
    return np.asarray(jax.random.randint(
        jax.random.PRNGKey(seed), (n,), 1, 256), np.int32)


def _batcher(model, family=None, **kw):
    spec, cfg, params = model
    opts = dict(slots=3, max_len=256, prompt_pad=PAD, kv="paged", block_len=8,
                family=family or spec.extras["family_rows"]())
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
    assert cfg.layer_types == ("full", "linear", "linear", "linear")
    assert cfg.kv_full == llama.KvKind(window=None, rope=False)
    assert cfg.attn_gate and cfg.qk_norm and cfg.lightning.chunk * 2 == PAD
    assert cfg.n_head // cfg.n_kv_head == 2 and cfg.n_kv_head == 2
    m = cfg.block_select
    assert (m.block, m.rows, m.local_blocks, m.topk, m.init_blocks) == (
        8, 4, 2, 2, 1) and m.kernel == 2 * m.stride
    mup = cfg.mup
    assert len({mup.embedding, mup.lm_head, mup.attention_out, 1.0}) == 4
    assert mup.mlp == (1.0, mup.attention_out)  # ONE r, both branches
    assert stack_layers(cfg) == {"blocks": (0,), "linear_blocks": (1, 2, 3)}
    assert layer_runs(cfg) == [("blocks", (0, 1), "full", (0, 1)),
                               ("linear_blocks", (0, 3), "linear", (0, 3))]
    assert set(params["h_0"]["attn"]) == {"q", "k", "v", "o", "gate",
                                          "q_norm", "k_norm"}
    assert set(params["h_1"]["attn"]) == {"q", "k", "v", "o", "gate",
                                          "q_norm", "k_norm", "o_norm"}
    assert "mlp" in params["h_0"] and "moe" not in params["h_0"]
    s = np.asarray(lightning.slopes(32))
    assert s[0] == pytest.approx(2 ** -0.25) and s[-1] == 2.0 ** -8


def test_the_published_model_and_its_cut():
    cfg = get_model("minicpm-sala").config
    assert [i for i, t in enumerate(cfg.layer_types) if t == "full"] == \
        [0, 9, 16, 17, 22, 29, 30, 31]
    cut = get_model("minicpm-sala-pp8-1chip").config
    assert cut.layer_types == cfg.layer_types[:4] == (
        "full", "linear", "linear", "linear")
    assert (cut.n_embd, cut.n_head, cut.n_kv_head, cut.head_dim, cut.d_ff,
            cut.vocab_size, cut.n_layer) == (4096, 32, 2, 128, 16384, 73448,
                                             4)
    assert cut.lightning == llama.LightningConfig(32, 128, 256, 10000.0)
    assert cut.block_select == llama.BlockSelectConfig(64, 64, 2048, 1, 32,
                                                       16)
    # r stays the PUBLISHED depth's
    assert cut.mup.attention_out == pytest.approx(1.4 / 32 ** 0.5)
    assert cut.mup.embedding == 12.0 and cut.mup.lm_head == 1 / 16
    # a `minicpm4` mixer 52.43 M, a `lightning-attn` mixer 83.89 M
    c = 4096
    assert 3 * c * c + 2 * c * 256 == 52_428_800 and 5 * c * c == 83_886_080


def test_a_config_names_its_kinds_whole():
    base = llama.PRESETS["minicpm-sala-test"]
    with pytest.raises(ValueError, match="lightning names the"):
        dataclasses.replace(base, layer_types=None)
    with pytest.raises(ValueError, match="lightning names the"):
        dataclasses.replace(base, layer_types=("linear",) * 4)
    with pytest.raises(ValueError, match="lightning names the"):
        dataclasses.replace(base, kv_full=llama.KvKind(window=4))
    with pytest.raises(ValueError, match="block_select goes with"):
        dataclasses.replace(base, block_select=llama.BlockSelectConfig(
            block=8, topk=2, window=12, kernel=4, stride=2))
    with pytest.raises(ValueError, match="block_select goes with"):
        dataclasses.replace(base, block_select=llama.BlockSelectConfig(
            block=8, topk=2, window=16, kernel=6, stride=2))


def test_whole_sequence_logits_match_the_reference(model):
    spec, cfg, params = model
    ids = jnp.asarray(np.stack([_ids(203, 1), _ids(203, 7)]))
    with jax.default_matmul_precision("highest"):
        got = spec.apply(params, ids)
        assert float(jnp.abs(got - ref.logits(cfg, params, ids)).max()) < TOL


# (1) prompts that end inside a chunk, on a chunk's, a block's and a pooled
# window's edge and one past it, before and after blocks are dropped (a
# context of 40 positions is 5 blocks: 2 local, 2 chosen, 1 dropped)
@pytest.mark.parametrize("n_prompt", [5, 16, 17, 39, 40, 49, 150, 201],
                         ids=lambda n: f"prompt{n}")
def test_prefill_install_and_decode_match_the_reference(model, plain,
                                                        n_prompt):
    _, cfg, params = model
    assert sorted(plain.cache) == ["k", "kc", "state", "tables", "v"]
    assert plain.cache["state"].shape == (3, 3, 4, 16, 16)
    assert plain.cache["state"].dtype == jnp.float32
    assert plain.cache["k"].shape == (1, 3 * 32 + 1, 2, 8, 128)
    # the strided leaf: 4 rows a block of 8 positions, in the ONE full layer
    assert plain.cache["kc"].shape == (1, 3 * 32 + 1, 2, 4, 128)
    prompt = _ids(n_prompt, 10 + n_prompt)
    toks, got = _served_logprobs(plain, prompt, 20)
    want = _reference_logprobs(cfg, params, prompt, toks)
    assert (want.argmax(-1) == toks).all()
    assert np.abs(got - want).max() < TOL
    assert plain._allocator.n_used == 0


# (2) the three forms of the rule
@pytest.mark.parametrize("case", ["published", "a_pad_tail", "odd_edge"])
def test_the_three_forms_of_the_rule_agree(case):
    """`chunk_rule` from an incoming state equals `recurrence` equals
    `step_rule` a position at a time, at the published slopes (decays from
    0.43 to 0.996 a position over chunks of 64: exp(54) would overflow
    nothing yet, exp(0.84 x 256) does — the rule never forms 1 / a
    cumulative decay); with a pad tail (log-decay 0 and k 0 past `n_real`:
    the identity on the state); and with the sequence cut into two calls at a
    position that is no chunk's edge."""
    b, h, t, d, chunk = 2, 32, 256, 16, 64
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    q, k, v = (jax.random.normal(kk, (b, h, t, d)) for kk in ks[:3])
    s0 = jax.random.normal(ks[3], (b, h, d, d))
    n_real = 200 if case == "a_pad_tail" else t
    real = jnp.arange(t) < n_real
    g = jnp.broadcast_to(lightning._log_decay(
        llama.LightningConfig(n_head=h, head_dim=d), real), (b, h, t))
    k = jnp.where(real[None, None, :, None], k, 0.0)
    with jax.default_matmul_precision("highest"):
        want, s_want = lightning.recurrence(q, k, v, g, s0)
        if case == "odd_edge":
            cut = 192
            o1, s1 = lightning.chunk_rule(q[:, :, :cut], k[:, :, :cut],
                                          v[:, :, :cut], g[:, :, :cut], s0,
                                          chunk=chunk)
            o2, s_got = lightning.chunk_rule(q[:, :, cut:], k[:, :, cut:],
                                             v[:, :, cut:], g[:, :, cut:],
                                             s1, chunk=chunk)
            got = jnp.concatenate([o1, o2], axis=2)
        else:
            got, s_got = lightning.chunk_rule(q, k, v, g, s0, chunk=chunk)

        def one(s, xs):
            o, s = lightning.step_rule(*xs, s)
            return s, o

        s_step, o_step = jax.lax.scan(one, s0, tuple(
            jnp.moveaxis(x, 2, 0) for x in (q, k, v, g)))
    assert bool(jnp.isfinite(got).all() & jnp.isfinite(s_got).all())
    # outputs and states reach ~100 in the slowest heads: 1e-5 of the scale
    tol_o = 1e-5 * float(jnp.abs(want).max())
    tol_s = 1e-5 * float(jnp.abs(s_want).max())
    assert float(jnp.abs(got - want).max()) < tol_o
    assert float(jnp.abs(s_got - s_want).max()) < tol_s
    assert float(jnp.abs(jnp.moveaxis(o_step, 0, 2) - want).max()) < tol_o
    assert float(jnp.abs(s_step - s_want).max()) < tol_s
    if case == "a_pad_tail":
        # the state after the last REAL position: the pads did nothing
        _, s_short = lightning.recurrence(
            q[:, :, :n_real], k[:, :, :n_real], v[:, :, :n_real],
            g[:, :, :n_real], s0)
        assert float(jnp.abs(s_got - s_short).max()) < tol_s


# (3) the selected set against a brute-force one
def _brute_force_sets(q, k, m, t_len):
    """The equations, a query and a block at a time in numpy: q (G, T, d), k
    (T, d) of ONE KV group -> bool (T, nb)."""
    q, k = np.asarray(q, np.float64), np.asarray(k, np.float64)
    d = k.shape[-1]
    nb = -(-t_len // m.block)
    out = np.zeros((t_len, nb), bool)
    for t in range(t_len):
        seen = [i for i in range(t_len) if m.stride * i + m.kernel - 1 <= t]
        p = np.zeros(len(seen))
        for head in q:
            s = np.asarray([head[t] @ k[m.stride * i:m.stride * i
                                         + m.kernel].mean(0)
                            for i in seen]) / np.sqrt(d)
            if len(s):
                e = np.exp(s - s.max())
                p += e / e.sum()
        bt = t // m.block
        score = {}
        for b in range(bt - m.local_blocks + 1):  # the blocks before local
            rows = [j for j, i in enumerate(seen)
                    if m.rows * b - 1 <= i <= m.rows * b + m.rows - 1]
            score[b] = max(p[rows]) if rows else 0.0
            if b < m.init_blocks:
                score[b] = np.inf
        best = sorted(score, key=lambda b: (-score[b], b))[:m.topk]
        for b in best:
            out[t, b] = True
        for b in range(max(bt - m.local_blocks + 1, 0), bt + 1):
            out[t, b] = True
    return out


def test_the_selected_set_is_the_equations(model):
    """`block_select.choose` over the program's pooled rows equals a
    brute-force set written from the equations — the forced initial block,
    the excluded local window, fewer candidates than `topk`, a pooled window
    that completes on the query's own position, a context that ends
    mid-block — and equals the reference's (a stable argsort)."""
    _, cfg, params = model
    m = cfg.block_select
    t = 75  # ends mid-block (9 blocks and 3 positions), mid-stride
    x = jax.random.normal(jax.random.PRNGKey(5), (t, cfg.n_embd))
    p = params["h_0"]
    with jax.default_matmul_precision("highest"):
        h = llama._pre_normed(p, x[None], cfg)
        q, k, _ = llama._qkv_rope(p, h, jnp.arange(t), cfg=cfg,
                                  compute_dtype=None, kind=cfg.kv_full)
        kp = jnp.pad(k, ((0, 0), (0, 0), (0, -t % m.block), (0, 0)))
        kc = block_select.pooled_rows(jnp.zeros_like(kp[:, :, :m.stride]),
                                      kp, m)
        g = cfg.n_head // cfg.n_kv_head
        scores = block_select.block_scores(block_select.group_scores(
            q.reshape(1, cfg.n_kv_head, g, t, -1), kc, jnp.arange(t), m), m)
        got = np.asarray(block_select.choose(scores, jnp.arange(t), m))[0]
        want_ref = np.asarray(ref.chosen_blocks(cfg, p, x))
    nb = got.shape[-1]
    for kv in range(cfg.n_kv_head):
        want = _brute_force_sets(q[0, kv * g:(kv + 1) * g], k[0, kv], m, t)
        assert (got[kv, :, :want.shape[1]] == want).all()
        assert (want_ref[kv] == want).all()
    assert nb == 10
    # a query in block 9 reads blocks 8-9 (local), 0 (forced) and ONE more
    assert got[:, 74].sum(-1).tolist() == [4, 4] and got[:, 74, 0].all()
    # the two KV groups choose differently somewhere
    assert (got[0] != got[1]).any()
    # fewer candidates than topk: all of them; a query at 23 has block 0
    assert got[:, 23, :3].all() and not got[:, 23, 3:].any()


def test_the_pooled_key_of_this_very_step_is_scored(model, plain):
    """A decode step at a position that completes a pooled window (pos % 2
    == 1) writes that row and scores it on the same step: the strided leaf
    after the step holds the mean of the last 4 keys, in the row of the
    block that holds `pos`."""
    _, cfg, params = model
    b = _batcher(model)
    prompt = _ids(21, 3)  # decode starts at position 21: 21 % 2 == 1
    rid = b.submit(prompt, 2)
    slot = next(i for i, r in enumerate(b._slot_req) if r is not None)
    b.step()
    table = np.asarray(b.cache["tables"][0, slot])
    kpool, kc = np.asarray(b.cache["k"][0]), np.asarray(b.cache["kc"][0])
    keys = np.concatenate([kpool[table[j]] for j in range(3)], axis=1)
    # row r = 10 completes at position 21 and covers positions 18..21: block
    # 2 (positions 16-23), row (21 % 8) // 2 = 2
    want = keys[:, 18:22].mean(1)
    assert np.abs(kc[table[2], :, 2] - want).max() < 1e-6
    # the chunk program wrote rows 0..7 of the first chunk: row 3 of block 0
    # covers positions 4..7
    assert np.abs(kc[table[0], :, 3] - keys[:, 4:8].mean(1)).max() < 1e-6
    b.drain()
    assert rid in b.results


# (4) the kernel forms against their plain forms, interpreted
def test_the_kernels_are_their_plain_forms(model):
    """ops/pallas/block_list_attention.py and the masked prefill kernel
    under a mask a KV group, interpreted, through the batcher: the
    reference's log-probabilities, and `/statusz`'s forms say which ran."""
    spec, cfg, params = model
    family = spec.extras["family_rows"]()
    family.attn_kernel = "interpret"
    srv = _batcher(model, family=family, logprobs_k=256)
    prompt = _ids(150, 12)
    toks, lps = _served_logprobs(srv, prompt, 12)
    assert np.abs(lps - _reference_logprobs(cfg, params, prompt, toks)
                  ).max() < TOL
    assert srv.family.attn_forms["full"] == {"prefill": "masked_kernel",
                                             "decode": "list_kernel"}


def test_the_list_kernel_reads_and_places_like_its_plain_form():
    from dnn_tpu.ops.pallas.block_list_attention import (
        block_list_attention,
        reference_block_list_attention,
    )

    n_layer, nb, hk, bp, d, b, r, n = 2, 40, 2, 8, 128, 3, 4, 6
    ks = jax.random.split(jax.random.PRNGKey(0), 7)
    kp = jax.random.normal(ks[0], (n_layer, nb, hk, bp, d))
    vp = jax.random.normal(ks[1], (n_layer, nb, hk, bp, d))
    q = jax.random.normal(ks[2], (b, hk, r, d))
    ids = (jax.random.permutation(ks[3], nb - 1)[:b * hk * n]
           .reshape(b, hk, n) + 1).astype(jnp.int32)
    # full lists, partial ones, a list of one block, an EMPTY one
    count = jnp.asarray([[6, 3], [1, 5], [0, 2]], jnp.int32)
    pos = jnp.asarray([13, 7, 21], jnp.int32)
    want = reference_block_list_attention(q, kp, vp, ids, count, pos, layer=1)
    got = block_list_attention(q, kp, vp, ids, count, pos,
                               layer=jnp.int32(1), interpret=True)
    live = np.asarray(count) > 0
    assert np.abs(np.asarray(got - want))[live].max() < 1e-5
    assert not np.asarray(got)[~live].any()
    # with the step's rows: placed at pos in the list's LAST block, a
    # gated-off slot neither read nor written
    count = jnp.asarray([[6, 3], [1, 5], [2, 2]], jnp.int32)
    gate = jnp.asarray([True, False, True])
    kn, vn = (jax.random.normal(kk, (b, hk, 1, d)) for kk in ks[4:6])
    want, kw, _ = reference_block_list_attention(
        q, kp, vp, ids, count, pos, layer=1, new=(kn, vn, gate))
    got, kg, _ = block_list_attention(
        q, kp, vp, ids, count, pos, layer=jnp.int32(1), new=(kn, vn, gate),
        interpret=True)
    assert float(jnp.abs(got - want).max()) < 1e-5
    assert not np.asarray(got[1]).any()
    # the pools agree outside junk block 0 (the plain form's gated-off rows)
    assert float(jnp.abs(kg[:, 1:] - kw[:, 1:]).max()) == 0.0
    assert float(jnp.abs(kg[0] - kp[0]).max()) == 0.0  # the other layer
    last = int(ids[0, 1, 2])
    assert float(jnp.abs(kg[1, last, 1, 13 % bp] - kn[0, 1, 0]).max()) == 0.0


def test_the_list_kernel_walks_several_groups():
    """Lists longer than one group of 16 blocks: full groups, a partial last
    group, a list that ends on a group's edge."""
    from dnn_tpu.ops.pallas.block_list_attention import (
        block_list_attention,
        reference_block_list_attention,
    )

    nb, hk, bp, d, b, r, n = 200, 2, 8, 128, 2, 3, 40
    ks = jax.random.split(jax.random.PRNGKey(1), 6)
    kp = jax.random.normal(ks[0], (1, nb, hk, bp, d))
    vp = jax.random.normal(ks[1], (1, nb, hk, bp, d))
    q = jax.random.normal(ks[2], (b, hk, r, d))
    ids = (jax.random.permutation(ks[3], nb - 1)[:b * hk * n]
           .reshape(b, hk, n) + 1).astype(jnp.int32)
    count = jnp.asarray([[40, 17], [32, 33]], jnp.int32)
    pos = jnp.asarray([5, 14], jnp.int32)
    kn, vn = (jax.random.normal(kk, (b, hk, 1, d)) for kk in ks[4:6])
    gate = jnp.asarray([True, True])
    want, kw, vw = reference_block_list_attention(
        q, kp, vp, ids, count, pos, layer=0, new=(kn, vn, gate))
    got, kg, vg = block_list_attention(
        q, kp, vp, ids, count, pos, layer=jnp.int32(0), new=(kn, vn, gate),
        interpret=True)
    assert float(jnp.abs(got - want).max()) < 1e-5
    assert float(jnp.abs(kg - kw).max()) == 0.0
    assert float(jnp.abs(vg - vw).max()) == 0.0


def test_the_step_kernel_is_the_step_rule():
    """ops/pallas/lin_step.py, interpreted, on a whole leaf of two layers at
    the served head width: layer 1's states get the plain rule's update and
    answers, layer 0's are untouched. (The test preset's heads are 16 wide:
    the family keeps the plain form there, `kernel_form`.)"""
    n_layer, b, h, d = 2, 3, 32, 128
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    pool = jax.random.normal(ks[0], (n_layer, b, h, d, d))
    q, k, v = (jax.random.normal(kk, (b, h, d)) for kk in ks[1:])
    g = jnp.broadcast_to(-lightning.slopes(h), (b, h))
    want_o, want_s = lightning.step_rule(q, k, v, g, pool[1])
    o, got = lightning.step_rule_kernel(q, k, v, pool, layer=jnp.int32(1),
                                        interpret=True)
    assert float(jnp.abs(o - want_o).max()) < 1e-6 * float(
        jnp.abs(want_o).max()) * d
    assert float(jnp.abs(got[1] - want_s).max()) < 1e-5
    assert float(jnp.abs(got[0] - pool[0]).max()) == 0.0
    spec = get_model("minicpm-sala-test")
    family = spec.extras["family_rows"]()
    family.attn_kernel = "interpret"
    assert family.kernel_form("step") is False
    served = llama.family_rows(get_model("minicpm-sala-pp8-1chip").config,
                               attn_kernel="interpret")
    assert served.kernel_form("step") == "interpret"
    assert served.kernel_form("chunk") is False


# (5) a slot freed and reused
def test_a_readmitted_slot_starts_from_a_zero_state_and_no_stale_key(model):
    """Two slots; the request in slot 1 retires while the pipelined loop has
    a step in flight, then a new request is installed there: its
    log-probabilities are those of a batcher that never served anything —
    the install writes the whole state and every pooled row the new request
    can see, and the blocks went back to the allocator."""
    _, cfg, params = model
    prompt = _ids(45, 77)
    fresh = _batcher(model, slots=2, logprobs_k=256, overlap=True)
    toks, want = _served_logprobs(fresh, prompt, 8)
    srv = _batcher(model, slots=2, logprobs_k=256, overlap=True)
    srv.submit(_ids(30, 5), 40)          # slot 0 lives on throughout
    first = srv.submit(_ids(99, 6), 5)   # slot 1 retires early
    while first not in srv.results:
        srv.step()
    assert float(jnp.abs(srv.cache["state"][:, 1]).max()) > 0
    rid = srv.submit(prompt, 8, logprobs=True)
    srv.drain()
    got = _by_vocabulary(srv.token_logprobs[rid])
    assert srv.results[rid].tolist() == toks.tolist()
    assert np.abs(got - want).max() < 1e-5
    assert np.abs(got - _reference_logprobs(cfg, params, prompt, toks)
                  ).max() < TOL
    assert srv._allocator.n_used == 0


# (6) negative controls: the reference with ONE thing wrong misses the
# program's log-probabilities by at least `factor` tolerances
@pytest.mark.parametrize("wrong,factor", [
    ({"state_dtype": "bfloat16"}, 3), ({"decay": False}, 100),
    ({"slopes": "reversed"}, 100), ({"lin_rope": False}, 100),
    ({"full_rope": True}, 30), ({"pick": "smallest"}, 10),
    ({"local": False}, 30), ({"init": False}, 3), ({"early": True}, 3),
    ({"group_sum": False}, 3), ({"gate": False}, 100),
    ({"out_norm": False}, 100), ({"r": 1.0}, 100), ({"scale_emb": 1.0}, 100),
    ({"head_div": 1.0}, 100)],
    ids=lambda w: "-".join(f"{k}={v}" for k, v in w.items())
    if isinstance(w, dict) else None)
def test_one_thing_wrong_misses_the_tolerance(model, plain, wrong, factor):
    _, cfg, params = model
    prompt = _ids(190, 49)
    toks, got = _served_logprobs(plain, prompt, 20)
    assert np.abs(got - _reference_logprobs(cfg, params, prompt, toks)
                  ).max() < TOL
    off = _reference_logprobs(cfg, params, prompt, toks, **wrong)
    assert np.abs(got - off).max() > factor * TOL


# (7) the daemon's surfaces
def test_the_refusals_name_the_leaves(model):
    for kw, what in (({"prefix_cache": 4}, "prefix_cache"),
                     ({"kv_dtype": "int8"}, "int8 KV pool"),
                     ({"prefill_chunk_tokens": 16}, "interleaved prefill")):
        with pytest.raises(ValueError, match="k/v/kc/state") as e:
            _batcher(model, **kw)
        assert what in str(e.value)
    with pytest.raises(ValueError, match="block_len 8, not 16"):
        _batcher(model, block_len=16)
    with pytest.raises(ValueError, match="lives in the paged pool"):
        _batcher(model, kv="dense")


def test_the_counters_count_positions_of_blocks(model):
    """`dsa.*` counts selected and candidate POSITIONS: a query of context n
    reads its local blocks up to itself and `topk` whole blocks before
    them."""
    _, cfg, _ = model
    m = cfg.block_select
    assert block_select.read_positions(m, 1) == 1
    assert block_select.read_positions(m, 16) == 16  # two blocks: all local
    assert block_select.read_positions(m, 17) == 17  # block 0 is chosen
    # context 41: position 40 in block 5; local 4-5 (9 positions), 2 of 4
    assert block_select.read_positions(m, 41) == 9 + 16
    assert block_select.read_positions(m, 41, 3) == (9 + 10 + 11) + 3 * 16
    big = llama.BlockSelectConfig()
    assert block_select.read_positions(big, 6144) == 6144
    # context 6145: position 6144 opens block 96; blocks 65-96 are local
    # (1985 positions), 64 of the 65 before them are chosen: one dropped
    assert block_select.read_positions(big, 6145) == 1985 + 64 * 64 == 6081
    assert block_select.read_positions(big, 6208) == 6144
    assert block_select.read_positions(big, 24577) == 1985 + 64 * 64


def test_the_batcher_counts_what_the_prefill_grid_walks(model, plain):
    """`dsa.walked_positions_total{program="prefill"}`: the full layers x a
    chunk's queries x the columns the masked kernel's grid covers for it —
    `sparse_attention.walked_columns`, the rule the kernel's wrapper sizes
    its grid with, of the batcher's transient row."""
    from dnn_tpu import obs
    from dnn_tpu.obs.timeline import StepClock
    from dnn_tpu.ops.pallas.sparse_attention import walked_columns

    if not obs.enabled():
        pytest.skip("observability is off")
    layers = plain.family.cache_kinds["full"]["layers"]
    before, plain.step_clock = plain.step_clock, StepClock().install()
    try:
        plain.submit(_ids(40, 5), 2)  # three chunks of 16: 0, 16, 32
        plain.drain()
        calls, cand, _, walked = plain.step_clock.dsa_total["prefill"]
    finally:
        plain.step_clock = before
    assert calls == 3 * layers
    assert cand == layers * sum(
        s + t + 1 for s in (0, PAD, 2 * PAD) for t in range(PAD))
    assert walked == layers * PAD * sum(
        walked_columns(s, PAD, plain._row_len) for s in (0, PAD, 2 * PAD))
    assert 0 < cand <= walked


def test_statusz_names_the_strided_leaf(model):
    from dnn_tpu.runtime.lm_server import LMServer

    spec, cfg, params = model
    srv = LMServer.__new__(LMServer)
    srv.batcher = _batcher(model)
    kinds = srv.batcher.family.cache_kinds
    assert kinds["full"]["strided_leaves"] == {"kc": (2, 16, 2)}
    assert kinds["full"]["tables"] == "tables"
    assert kinds["linear"]["slot_leaves"].keys() == {"state"}
    assert kinds["linear"]["tables"] is None
