"""dots3: layers of two KINDS that differ in their cache — latent attention
under an indexer's selection ("full": a latent leaf and an index-key leaf,
every position kept) and latent attention of other widths under a sliding
window ("window": a latent leaf of its own, the window's blocks kept) —
behind the batcher and ONE paged pool, against the plain reference
(chipbench/reference/dots3.py). Everything at `dots3-test` size (hidden
64, 5 layers F F S S S, window 9, topk 12, <= 62 positions), one
module-scoped model.

Tolerances: float32 on the CPU, every program against the reference's
full forward: logits and log-probabilities within 1e-3 (observed: 6e-7
whole-sequence, 5e-7 through chunked prefill and paged decode; the
reference rotates interleaved pairs in place and attends un-absorbed, so
the two are different sums of the same numbers)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import dots3 as ref
from dnn_tpu.models import dsa, llama, llama_moe, mla
from dnn_tpu.models.gpt import layer_runs, prepare_stacked, stack_layers
from dnn_tpu.ops.pallas import cached_attention as ca
from dnn_tpu.ops.pallas import mla_attention as ma
from dnn_tpu.registry import ParamParts, get_model
from dnn_tpu.runtime.serving import ContinuousBatcher

TOL = 1e-3


@pytest.fixture(scope="module")
def model():
    spec = get_model("dots3-test")
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
    full, win = cfg.mla, cfg.mla_window
    assert cfg.layer_types == ("full", "full", "window", "window", "window")
    assert full.index_topk == 12 and win.index_topk is None
    assert win.window == 9 and full.window is None
    assert win.n_head == 2 != cfg.n_head and win.rope_theta != cfg.rope_theta
    assert win.kv_lora_rank != full.kv_lora_rank
    assert win.qk_nope_head_dim != full.qk_nope_head_dim
    assert full.lora_rescale and win.lora_rescale
    assert full.head_gate and win.head_gate
    assert full.index_rope_dim < full.index_head_dim
    assert stack_layers(cfg) == {"dense_blocks": (0,), "blocks": (1,),
                                 "window_blocks": (2, 3, 4)}
    assert set(params["h_1"]["attn"]) - set(params["h_2"]["attn"]) == \
        {"indexer"}
    assert "mlp" in params["h_0"] and "moe" in params["h_1"]


def test_the_published_model_loops_over_runs_of_its_kinds():
    cfg = get_model("dots3-note-prev").config
    assert sum(t == "full" for t in cfg.layer_types) == 13
    assert [i for i, t in enumerate(cfg.layer_types) if t == "full"] == \
        [0] + list(range(1, 46, 4))
    runs = layer_runs(cfg)
    assert runs[:4] == [("dense_blocks", (0, 1), "full", (0, 1)),
                        ("blocks", (0, 1), "full", (1, 2)),
                        ("window_blocks", (0, 3), "window", (0, 3)),
                        ("blocks", (1, 2), "full", (2, 3))]
    assert len(runs) == 24 and runs[-1] == ("blocks", (11, 12), "full",
                                            (12, 13))
    cut = get_model("dots3-note-prev-ep8-1chip").config
    assert cut.layer_types == cfg.layer_types[:5] and cut.vocab_size == 19008
    assert dataclasses.replace(cut.mla) == cfg.mla  # no width differs
    assert cut.mla_window == cfg.mla_window and cut.n_embd == 5120


def test_whole_sequence_logits_match_the_reference(model):
    spec, cfg, params = model
    ids = jnp.asarray(np.stack([_ids(40, 1), _ids(40, 2)]))
    got = spec.apply(params, ids)
    assert float(jnp.abs(got - ref.logits(cfg, params, ids)).max()) < TOL


@pytest.mark.parametrize("attn_kernel", [False, "interpret"],
                         ids=["einsum", "kernels"])
def test_chunked_prefill_and_paged_decode_match_the_reference(model,
                                                              attn_kernel):
    """Three requests through the batcher's programs (chunk, finish and
    install, decode step), prompts of two and three chunks of 16, every
    context past the window of 9 and the topk of 12: each served token's
    log-probability is the reference's full forward's, and its argmax.
    Decoding walks past the window: blocks of the window kind go back to
    the allocator and are drawn again (a physical block serves two
    logical ones) and the logits do not change."""
    _, cfg, params = model
    b = _batcher(model, attn_kernel=attn_kernel, logprobs_k=2)
    assert sorted(b.cache) == ["ik", "latent", "latent_w", "tables",
                               "tables_w"]
    assert b.cache["latent"].shape[0] == 2      # the full layers
    assert b.cache["latent_w"].shape[:2] == (3, 3 * 3 + 1)  # 3 blocks a slot
    drawn = []
    alloc = b._allocator.of("tables_w")
    real = alloc.alloc
    alloc.alloc = lambda n: (drawn.extend(got := real(n)) or got)
    prompts = [_ids(29, 4), _ids(11, 5), _ids(37, 6)]
    rids = [b.submit(p, n, logprobs=True)
            for p, n in zip(prompts, (20, 16, 25))]
    out = b.drain()
    for rid, p in zip(rids, prompts):
        seq = np.concatenate([p, out[rid]])
        want = jax.nn.log_softmax(ref.forward(cfg, params, jnp.asarray(seq)))
        rows = np.arange(len(p) - 1, len(seq) - 1)
        assert (np.asarray(want.argmax(-1))[rows] == out[rid]).all()
        chosen = np.asarray(want)[rows, out[rid]]
        assert np.abs(b.token_logprobs[rid]["chosen"] - chosen).max() < TOL
    assert b.window_blocks_freed >= 6
    assert len(set(drawn)) < len(drawn)  # a block was drawn twice
    assert b._allocator.n_used == alloc.n_used == 0  # and all came back


def test_admission_counts_both_kinds(model):
    """A request holds ceil(len / bp) blocks of the full kind and the
    window's of the window kind; with the window kind's blocks gone the
    pool holds the next request back."""
    from dnn_tpu.runtime.paged_kvcache import InsufficientBlocks

    b = _batcher(model)
    b.submit(_ids(24, 1), 30)  # 54 positions: 7 blocks of 8; window: 3
    assert b._allocator.n_used == 7 and b._allocator.of("tables_w").n_used == 3
    alloc = b._allocator.of("tables_w")
    held = alloc.alloc(alloc.n_free)
    with pytest.raises(InsufficientBlocks, match="tables_w"):
        b.submit(_ids(12, 2), 4)
    assert b._allocator.n_used == 7  # the full kind's draw was undone
    alloc.free(held)
    b.submit(_ids(12, 2), 4)
    b.drain()


def test_refusals_keep_their_messages(model):
    spec, cfg, _ = model
    with pytest.raises(ValueError, match="prefix_cache"):
        _batcher(model, prefix_cache=4)
    with pytest.raises(ValueError, match="lives in the paged pool|dense"):
        _batcher(model, kv="dense")
    gemma = get_model("gemma2-test")  # alt_window, no declared kinds
    with pytest.raises(ValueError, match="alternating-window"):
        ContinuousBatcher(
            gemma.config,
            prepare_stacked(gemma.init(jax.random.PRNGKey(0)), gemma.config),
            slots=2, max_len=32, prompt_pad=8, kv="paged", block_len=8,
            family=llama.family_rows(gemma.config))


def test_selected_set_equals_the_references(model):
    """Layer 1's set a query, beyond index_topk (40 positions, topk 12):
    the program's indexer on the query latent, its partial RoPE, its
    LayerNorm and the exact selection, against the reference's stable
    argsort."""
    _, cfg, params = model
    ids = _ids(40, 9)
    want = ref.selected(cfg, params, ids, 1)
    x = ref.layer(params["h_0"], ref.embed(params["wte"], ids),
                  **ref.layer_args(cfg, 0))
    p, m = params["h_1"], cfg.mla
    h = llama._pre_normed(p, x[None], cfg)
    pos = jnp.arange(40)
    *_, c_q = mla.project(p["attn"], h, pos, cfg=cfg, compute_dtype=None,
                          m=m, with_query_latent=True)
    qi, ki, w = mla.index_project(p["attn"]["indexer"], c_q, h, pos, cfg=cfg,
                                  m=m, compute_dtype=None)
    scores = dsa.index_scores(qi, w, ki)
    causal = jnp.broadcast_to(pos[:, None] >= pos[None, :], scores.shape)
    got = dsa.select(scores, causal, m.index_topk)[0]
    assert (np.asarray(got) == np.asarray(want)).all()
    assert int(want[-1].sum()) == 12 and int(want[5].sum()) == 6


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
# the kernels' new arguments, interpreted, against their plain forms
# ----------------------------------------------------------------------

@pytest.mark.parametrize("start,window,select", [
    (0, 20, False), (32, 20, False), (96, 40, False), (64, None, True)])
def test_prefill_kernel_bands_and_selects(start, window, select):
    rng = np.random.default_rng(start)
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa: E731
    h, t, s_len, dn, dr, dv = 3, 32, 128, 16, 8, 24
    args = (f(h, t, dn), f(h, t, dr), f(h, s_len, dn), f(s_len, dr),
            f(h, s_len, dv))
    sel = None
    if select:
        rows, cols = start + np.arange(t)[:, None], np.arange(s_len)[None, :]
        sel = jnp.asarray((rng.random((t, s_len)) < 0.3) | (cols == rows))
        sel = sel & (cols <= rows)
    a = ma.reference_mla_prefill_attention(*args, start, scale=0.2,
                                           window=window, sel=sel)
    b = ma.mla_prefill_attention(*args, start, scale=0.2, block_q=16,
                                 block_s=128 if select else 16,
                                 interpret=True, window=window, sel=sel)
    assert float(jnp.abs(a - b).max()) < 1e-5


def test_every_branch_of_the_cells_row_is_handed_whole_column_tiles(
        model, monkeypatch):
    """`dots3-longnote-saturated` serves rows of 13 x `prompt_pad` (13 312
    = 13 x 1024, a full column tile 512 = half a chunk), which eighths of
    the row (1664 = 13 x 128) cut where only 128-column tiles fit. The
    same row at 1/128: 13 chunks of 8 under a tile of 4. Every branch the
    chunk program builds for a full layer — traced, not run — is handed a
    count of columns the tile divides, seven of them, the last the whole
    row; a window layer is handed the window and the chunk."""
    assert mla.prefix_lengths(13312, 1024) == [
        2048, 4096, 6144, 8192, 10240, 12288, 13312]
    assert not any(n % ma.BLOCK_S for n in mla.prefix_lengths(13312, 1024))
    spec, cfg, params = model
    fam = spec.extras["family_rows"]()
    monkeypatch.setattr(ma, "BLOCK_S", 4)
    prepared = prepare_stacked(dict(params), cfg)
    jax.eval_shape(fam.prefill, prepared, jnp.zeros((1, 8), jnp.int32),
                   fam.init_cache(1, 13 * 8, jnp.float32), 40)
    handed = sorted(fam.prefill_steps["full"])
    assert handed == [16, 32, 48, 64, 80, 96, 104]  # each 4 x a whole number
    assert sorted(fam.prefill_steps["window"]) == [8 + 8]  # window 9


@pytest.mark.parametrize("nb", [5, 40], ids=["one-group", "groups-of-128"])
def test_latent_decode_kernel_reads_the_selected_positions(nb):
    """`nb` 40 walked 128 positions at a time: whole groups, copied as
    straight-line code, before a partial last one (ISSUE 51)."""
    from tests.test_decode_hotpath import _pinned_span

    rng = np.random.default_rng(3)
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa: E731
    b, bp, heads, d, dv = 3, 8, 4, 40, 32
    pool = f(2, b * nb + 1, 1, bp, 128).at[..., d:].set(0.0)
    tables = jnp.asarray(1 + rng.permutation(b * nb).reshape(b, nb),
                         jnp.int32)
    pos = jnp.asarray([3, bp * 3 + 1, bp * nb - 1], jnp.int32)
    gate = jnp.asarray([True, True, True])
    cols = np.arange(nb * bp)[None, :]
    sel = jnp.asarray(((rng.random((b, nb * bp)) < 0.4)
                       | (cols == np.asarray(pos)[:, None]))
                      & (cols <= np.asarray(pos)[:, None]))
    q, row = f(b, 1, heads, d), f(b, 1, 1, 128).at[..., d:].set(0.0)
    want, pool_a = ca._reference_latent_step(
        q, pool, tables, pos, 1, (row, gate), dv, 0.3, sel)
    with _pinned_span(128 if nb == 40 else None):
        got, pool_b = ca.paged_decode_attention(
            q, pool, None, tables, pos, layer=1, new=(row, gate), latent=dv,
            scale=0.3, sel=sel, interpret=True)
    assert float(jnp.abs(got - want).max()) < 1e-5
    assert (np.asarray(pool_a) == np.asarray(pool_b)).all()


# ----------------------------------------------------------------------
# the boot: a layer at a time, the same values
# ----------------------------------------------------------------------

ZOO = ["gpt2-test", "llama-test", "mixtral-test", "olmoe-test", "keye-test",
       "joyai-test", "dots3-test"]


@pytest.mark.parametrize("name", ZOO)
def test_the_held_tree_is_bit_identical_to_the_whole_inits(name):
    """What the daemon holds — drawn an entry at a time (`init_parts`),
    cast and stacked layer by layer — against `prepare_stacked` of the
    whole float32 init held by `hold_in_compute_dtype`: the tree before
    this PR."""
    from dnn_tpu.node import _stack_and_release
    from dnn_tpu.ops.nn import hold_in_compute_dtype

    spec = get_model(name)
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


def test_a_layer_is_drawn_when_it_is_taken(model):
    """`init_parts` makes nothing until read, a popped layer is not kept,
    and each entry is `init`'s, bit for bit."""
    spec, cfg, params = model
    made = []
    parts = spec.init_parts(jax.random.PRNGKey(3))
    for name in list(parts):
        make = parts._makers[name]
        parts._makers[name] = (lambda n=name, f=make: (made.append(n), f())[1])
    assert not made and sorted(parts) == sorted(params)
    blk = parts.pop("h_2")
    assert made == ["h_2"] and "h_2" not in parts
    for x, y in zip(jax.tree.leaves(blk), jax.tree.leaves(params["h_2"])):
        assert (np.asarray(x) == np.asarray(y)).all()
    assert (np.asarray(parts["wte"]["embedding"])
            == np.asarray(params["wte"]["embedding"])).all()
    assert made == ["h_2", "wte"]


def test_the_checks_margins_a_layer_at_a_time_are_the_whole_trees(model):
    """`serve_dots.served_margins` (layer outer, sequence inner, weights
    drawn as it goes) gives `serve_keye.served_margins`' numbers on the
    whole tree."""
    from chipbench import serve_dots, serve_keye

    spec, cfg, params = model
    prompts = [_ids(9, 1), _ids(30, 2)]
    tokens = [list(_ids(5, 3)), list(_ids(7, 4))]
    a = serve_dots.served_margins(
        "dots3", cfg, spec.init_parts(jax.random.PRNGKey(3)), prompts, tokens)
    b = serve_keye.served_margins("dots3", cfg, params, prompts, tokens)
    for key in ("worst_margin", "mean_margin", "argmax_share",
                "mean_logit_sigma"):
        assert abs(a[key] - b[key]) < 1e-5, key
    assert a["positions"] == b["positions"] == 12
