"""K-EXAONE: layers of two KINDS whose cache is K and V — "window" layers
(a window of W, rotated; the window's blocks kept) and "full" layers (every
position kept, NO rotation) — with a dense AND windowed layer 0, behind
the batcher and ONE paged pool, against the plain reference
(chipbench/reference/exaone.py). Everything at `k-exaone-test` size
(hidden 64, 5 layers S S S F S, window 8, GQA 4 / 2 heads of 32, <= 62
positions), one module-scoped model.

Tolerances: float32 on the CPU, every program against the reference's
full forward: logits and log-probabilities within 1e-3 (observed: 1e-6
whole-sequence, 5e-7 through chunked prefill and paged decode; the
reference attends a head at a time over full (T, T) scores, the program
with the group folded into rows and the window's blocks gathered)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import exaone as ref
from dnn_tpu.models import llama, llama_moe
from dnn_tpu.models.gpt import layer_runs, prepare_stacked, stack_layers
from dnn_tpu.ops.pallas import cached_attention as ca
from dnn_tpu.registry import ParamParts, get_model
from dnn_tpu.runtime.paged_kvcache import PagedKV
from dnn_tpu.runtime.serving import ContinuousBatcher

TOL = 1e-3


@pytest.fixture(scope="module")
def model():
    spec = get_model("k-exaone-test")
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


@pytest.fixture(scope="module")
def plain(model):
    """One batcher on the plain forms (no kernel) for the tests that each
    drain it: its three programs compile once."""
    return _batcher(model, logprobs_k=2)


def test_preset_has_every_switch_acting(model):
    _, cfg, params = model
    assert cfg.layer_types == ("window",) * 3 + ("full", "window")
    assert cfg.kv_window == llama.KvKind(window=8, rope=True)
    assert cfg.kv_full == llama.KvKind(window=None, rope=False)
    assert cfg.first_k_dense == 1 and cfg.layer_types[0] == "window"
    assert cfg.n_head // cfg.n_kv_head == 2 and cfg.head_dim == 32
    assert cfg.qk_norm and cfg.qk_norm_width == "head"
    assert cfg.router.scale == 2.5 and cfg.router.select_bias
    assert cfg.d_shared and not cfg.shared_gate
    assert cfg.experts_held < cfg.n_expert
    assert stack_layers(cfg) == {"dense_blocks": (0,), "blocks": (3,),
                                 "window_blocks": (1, 2, 4)}
    assert "mlp" in params["h_0"] and "moe" in params["h_1"]
    assert set(params["h_3"]["attn"]) == set(params["h_1"]["attn"])


def test_the_published_model_loops_over_runs_of_its_kinds():
    cfg = get_model("k-exaone-236b-a23b").config
    assert [i for i, t in enumerate(cfg.layer_types) if t == "full"] == \
        list(range(3, 48, 4))
    runs = layer_runs(cfg)
    assert runs[:4] == [("dense_blocks", (0, 1), "window", (0, 1)),
                        ("window_blocks", (0, 2), "window", (1, 3)),
                        ("blocks", (0, 1), "full", (0, 1)),
                        ("window_blocks", (2, 5), "window", (3, 6))]
    assert len(runs) == 25 and runs[-1] == ("blocks", (11, 12), "full",
                                            (11, 12))
    cut = get_model("k-exaone-236b-a23b-ep8-1chip").config
    assert cut.layer_types == cfg.layer_types[:5] and cut.vocab_size == 19200
    assert (cut.n_embd, cut.n_head, cut.n_kv_head, cut.head_dim, cut.d_ff,
            cut.d_ff_dense, cut.d_shared, cut.n_expert, cut.router_top_k) == (
                6144, 64, 8, 128, 2048, 18432, 2048, 128, 8)
    assert cut.kv_window == cfg.kv_window and cut.experts_held == 16


def test_a_config_names_its_kinds_whole():
    base = llama_moe.PRESETS["k-exaone-test"]
    with pytest.raises(ValueError, match="layer_types comes with"):
        dataclasses.replace(base, layer_types=None)
    with pytest.raises(ValueError, match="kv_window .which has a window"):
        dataclasses.replace(base, kv_window=llama.KvKind())
    with pytest.raises(ValueError, match="kv_window .which has a window"):
        dataclasses.replace(base, kv_full=llama.KvKind(window=4))


@pytest.fixture(scope="module")
def served_logits(model):
    """(ids (2, 40), the program's whole-sequence logits of them)."""
    spec, _, params = model
    ids = np.stack([_ids(40, 1), _ids(40, 7)])
    return ids[1], spec.apply(params, jnp.asarray(ids))[1]


def test_whole_sequence_logits_match_the_reference(model, served_logits):
    spec, cfg, params = model
    ids = jnp.asarray(np.stack([_ids(40, 1), _ids(40, 7)]))
    got = spec.apply(params, ids)
    assert float(jnp.abs(got - ref.logits(cfg, params, ids)).max()) < TOL
    assert (np.asarray(got[1]) == np.asarray(served_logits[1])).all()


@pytest.mark.parametrize("wrong", [
    {"window": 7}, {"window": 9}, {"rope": True}, {"scale": 1.0},
    {"post_norm": True}],
    ids=lambda w: "-".join(f"{k}={v}" for k, v in w.items()))
def test_the_windows_edge_is_exact_and_the_full_layer_unrotated(
        model, served_logits, wrong):
    """The program's logits are the reference's, and NOT those of the
    reference with one thing wrong: the window one position off either
    way (`t - W < u <= t`), the full layer rotated, the scale 2.5 left
    out, the norms on the branch outputs. (Rotating the one full layer moves a logit by
    3e-3 at this size — theta 1e6 over 40 positions — where the program
    and the sound reference agree to 1e-6.)"""
    _, cfg, params = model
    ids, got = served_logits
    off = ref.forward(cfg, params, ids, **wrong)
    assert float(jnp.abs(got - off).max()) > 2 * TOL


@pytest.mark.parametrize("attn_kernel", [False, "interpret"],
                         ids=["einsum", "kernels"])
def test_chunked_prefill_and_paged_decode_match_the_reference(model, plain,
                                                              attn_kernel):
    """Three requests through the batcher's programs (chunk, finish and
    install, decode step), prompts of one to three chunks of 16, every
    context past four times the window of 8: each served token's
    log-probability is the reference's full forward's, and its argmax.
    Decoding walks past the window: blocks of the window kind go back to
    the allocator and are drawn again (a physical block serves two
    logical ones) and the logits do not change."""
    _, cfg, params = model
    b = _batcher(model, attn_kernel=attn_kernel, logprobs_k=2) \
        if attn_kernel else plain
    freed0, flushes0 = b.window_blocks_freed, b.window_table_flushes
    assert sorted(b.cache) == ["k", "k_w", "tables", "tables_w", "v", "v_w"]
    assert b.cache["k"].shape[:3] == (1, 3 * 8 + 1, 2)  # the full layer
    assert b.cache["k_w"].shape[:2] == (4, 3 * 2 + 1)   # 2 blocks a slot
    drawn = []
    alloc = b._allocator.of("tables_w")
    real = alloc.alloc
    alloc.alloc = lambda n: (drawn.extend(got := real(n)) or got)
    # one total length, so the reference compiles once a layer kind
    prompts = [_ids(29, 4), _ids(11, 5), _ids(37, 6)]
    rids = [b.submit(p, 54 - len(p), logprobs=True) for p in prompts]
    out = b.drain()
    alloc.alloc = real
    for rid, p in zip(rids, prompts):
        seq = np.concatenate([p, out[rid]])
        assert len(seq) > 4 * cfg.kv_window.window
        want = jax.nn.log_softmax(ref.forward(cfg, params, jnp.asarray(seq)))
        rows = np.arange(len(p) - 1, len(seq) - 1)
        assert (np.asarray(want.argmax(-1))[rows] == out[rid]).all()
        chosen = np.asarray(want)[rows, out[rid]]
        assert np.abs(b.token_logprobs[rid]["chosen"] - chosen).max() < TOL
    assert b.window_blocks_freed - freed0 >= 6
    assert b.window_table_flushes - flushes0 >= 3
    assert len(set(drawn)) < len(drawn)  # a block was drawn twice
    assert b._allocator.n_used == alloc.n_used == 0  # and all came back
    forms = b.family.attn_forms
    if attn_kernel:
        assert forms == {
            "full": {"prefill": "kernel", "decode": "paged_kernel"},
            "window": {"prefill": "banded_kernel",
                       "decode": "gather_einsum"}}
    else:
        assert forms["window"] == {"prefill": "plain",
                                   "decode": "gather_einsum"}


def test_the_reads_are_counted_by_kind(plain):
    """`attn_cached_positions_read_total{kind, program}`: what the
    algorithm reads — pos + 1 a full layer and min(pos + 1, W) a window
    layer a step, a chunk's causal pairs and its pairs within the band —
    and the launches of the table flush."""
    from dnn_tpu.obs.timeline import StepClock

    b = plain
    b.step_clock = clock = StepClock().install()
    flushes0 = b.window_table_flushes
    b.submit(_ids(20, 1), 6)   # two chunks of 16, then 5 steps
    b.drain()
    b.step_clock = None
    w, n_win, t = 8, 4, 16
    tot = clock.mla_kind_total
    pairs = lambda start: t * start + t * (t + 1) // 2  # noqa: E731
    assert tot[("attn", "full", "prefill")] == pairs(0) + pairs(16)
    banded = sum(min(p + 1, w) for p in range(32))
    assert tot[("attn", "window", "prefill")] == n_win * banded
    # steps at positions 20 .. 23 (the first token came from the finish;
    # the step that retires a request counts the slots still live after
    # it, as the dsa_* and mla_* series do)
    assert tot[("attn", "full", "decode")] == sum(range(21, 25))
    assert tot[("attn", "window", "decode")] == n_win * 4 * w
    assert not any(k[0] == "full" for k in tot)  # no mla_* series
    assert b.window_table_flushes > flushes0


def test_admission_counts_both_kinds(plain):
    from dnn_tpu.runtime.paged_kvcache import InsufficientBlocks

    b = plain
    b.submit(_ids(24, 1), 30)  # 54 positions: 7 blocks of 8; window: 2
    assert b._allocator.n_used == 7 and b._allocator.of("tables_w").n_used == 2
    alloc = b._allocator.of("tables_w")
    held = alloc.alloc(alloc.n_free)
    with pytest.raises(InsufficientBlocks, match="tables_w"):
        b.submit(_ids(12, 2), 4)
    assert b._allocator.n_used == 7  # the full kind's draw was undone
    alloc.free(held)
    b.submit(_ids(12, 2), 4)
    b.drain()


def test_refusals_keep_their_messages(model):
    spec, cfg, params = model
    with pytest.raises(ValueError, match="prefix_cache"):
        _batcher(model, prefix_cache=4)
    with pytest.raises(ValueError, match="lives in the paged pool|dense"):
        _batcher(model, kv="dense")
    with pytest.raises(ValueError, match="int8"):
        _batcher(model, kv_dtype="int8")
    gemma = get_model("gemma2-test")  # alt_window, no declared kinds
    with pytest.raises(ValueError, match="alternating-window"):
        ContinuousBatcher(
            gemma.config,
            prepare_stacked(gemma.init(jax.random.PRNGKey(0)), gemma.config),
            slots=2, max_len=32, prompt_pad=8, kv="paged", block_len=8,
            family=llama.family_rows(gemma.config))
    # a pool without kinds still has no per-layer window channel
    codec = PagedKV(8)
    c = {"k": jnp.zeros((3, 2, 8, 128)), "v": jnp.zeros((3, 2, 8, 128)),
         "tables": jnp.zeros((1, 2), jnp.int32)}
    q = jnp.zeros((1, 2, 1, 32))
    with pytest.raises(ValueError, match="no per-layer window channel"):
        codec.attend_rows(q, c, jnp.zeros((1,), jnp.int32), window=4)
    with pytest.raises(ValueError, match="no per-layer window channel"):
        codec.write_attend_rows(q, c, q, q, jnp.zeros((1,), jnp.int32),
                                jnp.ones((1,), bool), window=4)
    with pytest.raises(ValueError, match="one stack of layers"):
        llama.forward_with_cache(
            prepare_stacked(dict(params), cfg), jnp.zeros((1, 4), jnp.int32),
            {}, 0, cfg=cfg)
    with pytest.raises(ValueError, match="speculative verify"):
        spec.extras["family_rows"]().verify_rows()


@pytest.mark.parametrize("n_shares", [2, 8])
def test_the_shares_add_up(model, n_shares):
    """The shares' routed parts plus the shared expert counted ONCE are the
    uncut layer (8 shares: the deployment's eight chips, one expert each
    at this size)."""
    _, cfg, _ = model
    whole_cfg = dataclasses.replace(cfg, experts_held=None)
    whole = llama_moe.init(jax.random.PRNGKey(3), whole_cfg)
    x = jax.random.normal(jax.random.PRNGKey(7), (30, cfg.n_embd))
    p, kw = whole["h_2"], ref.layer_args(whole_cfg, 2)
    want = ref.layer(p, x, **kw)
    none = {**p, "moe": {**p["moe"], **{n: p["moe"][n][:0]
                                        for n in ("wg", "wu", "wd")}}}
    total = ref.layer(none, x, **kw)  # attention and the shared expert
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
                                      first=first, scale=kw["scale"],
                                      bias=True)
        assert float(jnp.abs(got - (routed + common)).max()) < TOL
    assert float(jnp.abs(total - want).max()) < TOL


# ----------------------------------------------------------------------
# the kernels' band forms, interpreted, against their plain forms
# ----------------------------------------------------------------------

@pytest.mark.parametrize("start,window,group", [
    (0, None, 2), (48, None, 4), (0, 20, 2), (32, 20, 2), (80, 40, 4),
    (16, 5, 1)])
def test_prefill_kernel_folds_the_group_and_bands(start, window, group):
    """Row g * T + t of a KV head reads columns <= start + t (and >
    start + t - W): the kernel with the column tiles clamped to the live
    ones against the plain masked einsum, and against the unfolded
    per-head form."""
    rng = np.random.default_rng(start + group)
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa: E731
    kv, t, s_len, d = 2, 32, 128, 16
    q, k, v = f(1, kv, group * t, d), f(1, kv, s_len, d), f(1, kv, s_len, d)
    pos = jnp.asarray([start], jnp.int32)
    a = ca.reference_cached_attention(q, k, v, pos, rows_mod=t, window=window)
    b = ca.cached_attention(q, k, v, pos, rows_mod=t, window=window,
                            block_q=16, block_s=16, interpret=True)
    assert float(jnp.abs(a - b).max()) < 1e-5
    # the fold is the per-head form: head (k, g) at rows [g * T, (g + 1) T)
    per_head = ca.reference_cached_attention(
        q.reshape(1, kv * group, t, d), jnp.repeat(k, group, axis=1),
        jnp.repeat(v, group, axis=1), pos, window=window)
    assert float(jnp.abs(a.reshape(per_head.shape) - per_head).max()) < 1e-5


def test_a_window_kinds_decode_read_gathers_the_windows_blocks():
    """`PagedKV.write_attend_rows(window=, leaves=, tables=)` against the
    band written out: the step's rows land at `pos`, each slot reads
    (pos - W, pos] and no more — with table entries BEFORE the window
    pointing at the junk block, as a rolled slot's do, a gated-off slot's
    blocks untouched — and the kernel setting changes nothing (a window
    kind's read is the gather on the chip too)."""
    rng = np.random.default_rng(5)
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa: E731
    b, nb, bp, hk, r, d, w = 4, 6, 8, 2, 2, 32, 20
    n_w = -(-w // bp) + 1
    pos = np.asarray([3, 21, bp * nb - 1, 30], np.int32)
    tables = np.zeros((b, nb), np.int32)
    nxt = 1
    for i, p in enumerate(pos):  # only the window's blocks are held
        lo = max(0, p - w + 1) // bp
        for j in range(lo, min(lo + n_w, nb)):
            tables[i, j], nxt = nxt, nxt + 1
    pool = lambda: f(3, nxt, hk, bp, 128).at[..., d:].set(0.0)  # noqa: E731
    c = {"k": jnp.zeros((1, 2, hk, bp, 128)), "k_w": pool(), "v_w": pool(),
         "tables": jnp.zeros((1, b, nb), jnp.int32),
         "tables_w": jnp.broadcast_to(jnp.asarray(tables), (3, b, nb))}
    kinds = {"window": {"leaves": {"k_w": 0, "v_w": 0}, "tables": "tables_w"}}
    q, k, v = f(b, hk, r, d), f(b, hk, 1, d), f(b, hk, 1, d)
    gate = jnp.asarray([True, True, True, False])
    kw = dict(window=w, layer=1, leaves=("k_w", "v_w"), tables="tables_w")
    codec = PagedKV(bp, use_kernel="interpret", kinds=kinds)
    assert codec.decode_form(c, 1, w) == "gather_einsum"
    assert codec.decode_form(c, 1) == "paged_kernel"
    got, c2 = codec.write_attend_rows(q, c, k, v, jnp.asarray(pos), gate,
                                      **kw)
    plain, c3 = PagedKV(bp, kinds=kinds).write_attend_rows(
        q, c, k, v, jnp.asarray(pos), gate, **kw)
    assert (np.asarray(got) == np.asarray(plain)).all()
    for i in range(3):  # the live slots, a position at a time
        held = {}
        for u in range(max(0, pos[i] - w + 1), pos[i] + 1):
            blk, row = tables[i, u // bp], u % bp
            assert blk != 0
            held[u] = (np.asarray(c2["k_w"])[1, blk, :, row, :d],
                       np.asarray(c2["v_w"])[1, blk, :, row, :d])
        assert (held[pos[i]][0] == np.asarray(k)[i, :, 0]).all()
        ks = np.stack([held[u][0] for u in sorted(held)], 1)  # (Hk, S, d)
        vs = np.stack([held[u][1] for u in sorted(held)], 1)
        sc = np.einsum("hrd,hsd->hrs", np.asarray(q)[i], ks) / np.sqrt(d)
        pr = np.exp(sc - sc.max(-1, keepdims=True))
        want = np.einsum("hrs,hsd->hrd", pr / pr.sum(-1, keepdims=True), vs)
        assert np.abs(np.asarray(got)[i] - want).max() < 1e-5
    # the gated-off slot wrote nowhere but the junk block, and layers 0
    # and 2 were not touched
    for name in ("k_w", "v_w"):
        before, after = np.asarray(c[name]), np.asarray(c2[name])
        assert (before[[0, 2]] == after[[0, 2]]).all()
        assert (before[1, tables[3][tables[3] > 0]]
                == after[1, tables[3][tables[3] > 0]]).all()
        assert (np.asarray(c3[name])[:, 1:] == after[:, 1:]).all()


# ----------------------------------------------------------------------
# the boot, /statusz, and the benchmark's side
# ----------------------------------------------------------------------

def test_the_held_tree_is_bit_identical_to_the_whole_inits():
    from dnn_tpu.node import _stack_and_release
    from dnn_tpu.ops.nn import hold_in_compute_dtype

    spec = get_model("k-exaone-test")
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
    assert got["dense_blocks"]["mlp"]["gate"]["kernel"].shape == (1, 64, 96)
    assert got["window_blocks"]["moe"]["wg"].shape[:2] == (3, 4)
    assert got["blocks"]["moe"]["router"]["kernel"].dtype == jnp.float32


def test_the_checks_margins_a_layer_at_a_time_are_the_whole_trees(model):
    """`serve_dots.served_margins` drives this reference as it stands
    (layer outer, sequence inner, weights drawn as it goes) and gives
    `serve_keye.served_margins`' numbers on the whole tree."""
    from chipbench import serve_dots, serve_keye

    spec, cfg, params = model
    prompts = [_ids(30, 2), _ids(30, 1)]
    tokens = [list(_ids(6, 3)), list(_ids(6, 4))]
    a = serve_dots.served_margins(
        "exaone", cfg, spec.init_parts(jax.random.PRNGKey(3)), prompts,
        tokens)
    b = serve_keye.served_margins("exaone", cfg, params, prompts, tokens)
    for key in ("worst_margin", "mean_margin", "argmax_share",
                "mean_logit_sigma"):
        assert abs(a[key] - b[key]) < 1e-5, key
    assert a["positions"] == b["positions"] == 12  # one length: one compile
