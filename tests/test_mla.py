"""Latent attention behind the batcher (models/mla.py), sigmoid routing
and a dense prefix, on the CPU in float32 with the tiny `joyai-test`
preset: the dense forward, chunked prefill and absorbed paged decode
through `ContinuousBatcher` against the plain reference
`chipbench/reference/joyai.py` on seeded weights; both kernels against
their plain forms; the routing's cases; one chip's share of a layer; what
refuses a cache of latents; the counters; the benchmark cell's rehearsal;
and that the other families' step programs lower to what they were.

Tolerance 2e-5 on logits and log-probabilities: both sides are float32 on
the CPU and differ by summation order alone (measured 4e-7 on the dense
forward); absorbed and up-projected attention differ by 3e-7.
"""

import dataclasses
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import joyai as ref
from dnn_tpu.models import llama_moe, mla
from dnn_tpu.models.gpt import prepare_stacked
from dnn_tpu.ops.pallas import cached_attention as ca
from dnn_tpu.ops.pallas import mla_attention as ma
from dnn_tpu.parallel import moe
from dnn_tpu.registry import get_model
from dnn_tpu.runtime.paged_kvcache import PagedKV, init_paged_cache
from dnn_tpu.runtime.serving import ContinuousBatcher

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 2e-5


@pytest.fixture(scope="module")
def model():
    spec = get_model("joyai-test")
    params = spec.init(jax.random.PRNGKey(3))
    return spec, spec.config, params


def _ids(n, seed=1):
    return np.asarray(jax.random.randint(
        jax.random.PRNGKey(seed), (n,), 1, 256), np.int32)


def _batcher(model, attn_kernel=None, **kw):
    spec, cfg, params = model
    family = spec.extras["family_rows"]()
    if attn_kernel is not None:
        family.attn_kernel = attn_kernel
    opts = dict(slots=3, max_len=64, prompt_pad=16, kv="paged", block_len=8,
                family=family)
    opts.update(kw)
    return ContinuousBatcher(cfg, prepare_stacked(dict(params), cfg), **opts)


def test_preset_has_every_switch_acting(model):
    _, cfg, params = model
    m = cfg.mla
    assert m.q_lora_rank < cfg.n_embd                  # a query bottleneck
    assert m.qk_nope_head_dim != m.v_head_dim          # key 24, value 24+...
    assert m.qk_nope_head_dim + m.qk_rope_head_dim != m.v_head_dim or True
    assert m.latent_dim == m.kv_lora_rank + m.qk_rope_head_dim == 40
    assert m.rope_interleave
    assert cfg.first_k_dense == 1 and cfg.n_expert_layer == 2
    assert cfg.router.scoring == "sigmoid" and cfg.router.scale == 2.5
    assert cfg.d_shared and not cfg.shared_gate
    assert cfg.experts_held < cfg.n_expert
    dense, blk = params["h_0"], params["h_1"]
    assert "mlp" in dense and "moe" not in dense
    assert dense["mlp"]["gate"]["kernel"].shape == (64, cfg.d_ff_dense)
    assert blk["moe"]["wg"].shape[0] == cfg.experts_held
    assert blk["moe"]["router"]["kernel"].shape[-1] == cfg.n_expert
    assert "shared_gate" not in blk["moe"] and "shared" in blk["moe"]
    # a drawn bias: not the zeros a plain init leaves
    assert float(jnp.abs(blk["moe"]["router"]["select_bias"]).min()) > 0
    assert set(blk["attn"]) == {"q_a", "q_a_norm", "q_b", "kv_a",
                                "kv_a_norm", "kv_b", "o"}


def test_dense_forward_matches_the_reference(model):
    spec, cfg, params = model
    ids = jnp.asarray(np.stack([_ids(50, 1), _ids(50, 2)]))
    got = spec.apply(params, ids)
    want = ref.logits(cfg, params, ids)
    assert float(jnp.abs(got - want).max()) < TOL


def test_the_block_matches_the_reference(model):
    """One block of each kind, program against reference."""
    from dnn_tpu.models import llama

    _, cfg, params = model
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 40, cfg.n_embd))
    for i in (0, 1):
        got = llama.block_apply(params[f"h_{i}"], x, cfg=cfg,
                                ffn=cfg.default_ffn())
        want = ref.layer(params[f"h_{i}"], x[0], **ref._kw(cfg, None))
        assert float(jnp.abs(got[0] - want).max()) < TOL, i


def test_absorbed_attention_equals_the_plain_form(model):
    """The absorbed decode form (queries through W_uk, the output through
    W_uv, the latent as key and value) against up-projected keys and
    values, for the last query of a sequence."""
    _, cfg, params = model
    m, ap = cfg.mla, params["h_1"]["attn"]
    t = 37
    h = jax.random.normal(jax.random.PRNGKey(9), (1, t, cfg.n_embd))
    q_nope, q_rope, rows = mla.project(ap, h, jnp.arange(t), cfg=cfg,
                                       compute_dtype=None)
    plain = mla._chunk_attn(ap, q_nope[0], q_rope[0], rows[0], 0, cfg=cfg,
                            compute_dtype=None, interpret=None)[-1]
    w = mla._kv_b(ap, cfg, None)
    dn = m.qk_nope_head_dim
    q = jnp.concatenate([jnp.einsum("hd,rhd->hr", q_nope[0, -1], w[..., :dn]),
                         q_rope[0, -1]], -1)
    p = jax.nn.softmax(q @ rows[0].T * m.scale, axis=-1)
    o = jnp.einsum("hr,rhd->hd", p @ rows[0][:, :m.kv_lora_rank], w[..., dn:])
    assert float(jnp.abs(o - plain).max()) < 1e-5  # both (H, dv)


@pytest.mark.parametrize("attn_kernel", [False, "interpret"],
                         ids=["einsum", "kernels"])
def test_chunked_prefill_and_paged_decode_match_the_reference(model,
                                                              attn_kernel):
    """Three requests of different lengths through the batcher, two of
    them together: every emitted token is the reference's argmax and its
    log-probability the reference's, prompt chunks and decode steps alike
    (prompts of 29 and 37 take two and three chunks of 16; contexts reach
    five blocks of 8)."""
    _, cfg, params = model
    b = _batcher(model, attn_kernel=attn_kernel, logprobs_k=2)
    prompts = [_ids(29, 4), _ids(11, 5), _ids(37, 6)]
    rids = [b.submit(p, n, logprobs=True) for p, n in zip(prompts, (8, 6, 9))]
    out = b.drain()
    for rid, p in zip(rids, prompts):
        seq = np.concatenate([p, out[rid]])
        want = jax.nn.log_softmax(ref.forward(cfg, params, jnp.asarray(seq)))
        rows = np.arange(len(p) - 1, len(seq) - 1)
        assert (np.asarray(want.argmax(-1))[rows] == out[rid]).all()
        chosen = np.asarray(want)[rows, out[rid]]
        assert np.abs(b.token_logprobs[rid]["chosen"] - chosen).max() < TOL


@pytest.mark.parametrize("column_tile", [None, 8],
                         ids=["whole_row", "prefix_switch"])
def test_chunks_of_unequal_size_then_decode_match_the_reference(
        model, monkeypatch, column_tile):
    """The family's own programs, no batcher: chunks of 16, 8 and 24
    tokens into a transient row, the row installed into the paged pool,
    then decode steps — each step's logits the reference's at that
    position (contexts over seven blocks of 8). At the kernel's own
    column tile a row of 64 is one prefix; at a tile of 8 the chunks go
    through the switch over prefixes of 16 and 8 and 24 positions a step
    (`mla.prefix_lengths`), each a multiple of the tile."""
    spec, cfg, params = model
    fam = spec.extras["family_rows"]()
    if column_tile:
        monkeypatch.setattr(ma, "BLOCK_S", column_tile)
        assert mla.prefix_lengths(64, 8) == list(range(8, 72, 8))
        assert mla.prefix_lengths(64, 24) == [24, 48, 64]
    prepared = prepare_stacked(dict(params), cfg)
    seq = _ids(56, 8)
    want = ref.forward(cfg, params, jnp.asarray(seq))
    row = fam.init_cache(1, 64, jnp.float32)
    start = 0
    for n in (16, 8, 24):
        hidden, row = fam.prefill(prepared, jnp.asarray(seq[None, start:start + n]),
                                  row, start)
        logits = fam.head(prepared, hidden)  # a chunk ends at the last block
        assert float(jnp.abs(logits[0] - want[start:start + n]).max()) < TOL
        start += n
    assert sorted(fam.prefill_steps["full"]) == (
        [8, 16, 24, 32, 40, 48, 56, 64] if column_tile else [64])
    codec = PagedKV(8)
    cache = init_paged_cache(cfg, 2, 64, n_blocks=17, dtype=jnp.float32,
                             block_len=8, leaves=fam.cache_leaves)
    ids = jnp.arange(1, 9, dtype=jnp.int32)
    cache = codec.install_row(cache, row, ids)
    cache["tables"] = cache["tables"].at[:, 1].set(ids)
    pos = jnp.asarray([0, 48], jnp.int32)
    active = jnp.asarray([False, True])
    for t in range(48, 56):
        logits, cache = fam.decode_rows(
            prepared, cache, jnp.asarray([0, seq[t]], jnp.int32), pos, active,
            codec)
        assert float(jnp.abs(logits[1] - want[t]).max()) < TOL, t
        pos = pos.at[1].add(1)


# what one grid step of the prefill kernel covers (G heads x bq rows x bs
# columns), at the published widths' lane structure (value 128 wide, column
# tiles of 128: the softmax state lane by lane), interpreted. S = 512 in
# four column tiles (without a mask two of 256: two lane tiles folded into
# the state, and tiles wholly under the diagonal), T = 32 in two query
# tiles; for the window kind's own call S = window + chunk, rounded to
# chunks, and `start` the first query's column. "mid" = 159: the first
# query tile's first row reads from column 120 of tile 0, its last row
# from column 135 of tile 1 — tile 0 holds no kept position for it, as
# tiles 0 and 1 hold none for the odd rows of `sel`: the all-masked-so-far
# case. mask -> (S, bs, window, a set)
_STEP_T, _STEP_W = 32, 40
_STEP_MASKS = {"none": (512, 256, None, False),
               "window": (512, 128, _STEP_W, False),
               "sel": (512, 128, None, True),
               "window_and_chunk": (64 + _STEP_T, 32, _STEP_W, False)}


@pytest.mark.parametrize("start", ["first", "mid", "last"])
@pytest.mark.parametrize("dn", [128, 192])
@pytest.mark.parametrize("heads", [4, 6, 1])
@pytest.mark.parametrize("mask", sorted(_STEP_MASKS))
def test_prefill_kernel_grid_step(mask, heads, dn, start):
    s_len, bs, window, select = _STEP_MASKS[mask]
    t, dr, dv = _STEP_T, 64, 128
    start = {"first": 0, "mid": min(159, (s_len - t) // 2),
             "last": s_len - t}[start]
    rng = np.random.default_rng(heads * 1000 + dn + start)
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa: E731
    args = (f(heads, t, dn), f(heads, t, dr), f(heads, s_len, dn),
            f(s_len, dr), f(heads, s_len, dv))
    sel = None
    if select:
        rows, cols = start + np.arange(t)[:, None], np.arange(s_len)[None, :]
        sel = (rng.random((t, s_len)) < 0.3) & (
            (cols >= 2 * bs) | (rows % 2 == 0))
        sel = jnp.asarray((sel | (cols == rows)) & (cols <= rows))
    # `grid_step`'s budget: at the smallest VMEM limit at which four heads
    # fit a step, six heads go three a step (the largest divisor that
    # fits) and one goes alone; the kernel is then run at that step
    shape = (t, s_len, dn, dr, dv, 4)
    kw = dict(block_q=16, block_s=bs, select=select)
    limit = next(n for n in range(1 << 14, 1 << 26, 1 << 12)
                 if ma.grid_step(4, *shape, vmem_limit_bytes=n, **kw)[0] == 4)
    step = ma.grid_step(heads, *shape, vmem_limit_bytes=limit, **kw)
    assert step == ({4: 4, 6: 3, 1: 1}[heads], 16, bs)
    a = ma.reference_mla_prefill_attention(*args, start, scale=0.1,
                                           window=window, sel=sel)
    b = ma._tiled(*args, start, sel, scale=0.1, step=step, window=window,
                  interpret=True)
    assert float(jnp.abs(a - b).max()) < 2e-5


def test_a_column_tile_that_does_not_divide_falls_to_one_that_does():
    """`grid_step`: the widest of `block_s`, its half, ... down to 128
    that divides S; no tile for an S that 128 does not divide nor for a T
    that `block_q` does not: the plain form then runs."""
    widths = (128, 64, 128, 2)
    assert ma.grid_step(32, 1024, 13312, *widths)[1:] == (512, 512)
    assert ma.grid_step(32, 1024, 8320, *widths)[1:] == (512, 128)
    assert ma.grid_step(32, 1024, 9984, *widths)[1:] == (512, 256)
    assert ma.grid_step(32, 1024, 1000, *widths) is None
    assert ma.grid_step(32, 1000, 2048, *widths) is None
    assert ma.grid_step(2, 8, 24, 16, 8, 16, 4, block_q=8,
                        block_s=16) is None


@pytest.mark.parametrize("start", [0, 32, 96])
def test_prefill_kernel_matches_its_plain_form(start):
    rng = np.random.default_rng(start)
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa: E731
    h, t, s_len, dn, dr, dv = 3, 32, 128, 16, 8, 24
    args = (f(h, t, dn), f(h, t, dr), f(h, s_len, dn), f(s_len, dr),
            f(h, s_len, dv))
    a = ma.reference_mla_prefill_attention(*args, start, scale=0.2)
    b = ma.mla_prefill_attention(*args, start, scale=0.2, block_q=16,
                                 block_s=128, interpret=True)
    assert float(jnp.abs(a - b).max()) < 1e-5


@pytest.mark.parametrize("nb", [9, 40], ids=["one-group", "groups-of-128"])
@pytest.mark.parametrize("block_len", [8, 16])
def test_latent_decode_kernel_matches_its_plain_form(block_len, nb):
    """The paged kernel's latent form in interpret mode: one leaf copied
    once and read as key and value, the step's row placed and written
    back, a gated-off slot empty; against the gather-and-einsum form. 9
    blocks a slot are one group by the rule (`_paged_group`: 1024
    positions for a leaf this small); 40 walked 128 positions at a time
    are whole groups, copied as straight-line code, and a partial last
    one, copied by the loop (ISSUE 51; tests/test_paged_groups.py has
    every layout)."""
    from tests.test_decode_hotpath import _pinned_span

    rng = np.random.default_rng(block_len)
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa: E731
    b, layers, heads, d, dv = 4, 2, 5, 40, 32
    pool = f(layers, b * nb + 1, 1, block_len, 128).at[..., d:].set(0.0)
    tables = jnp.asarray(1 + rng.permutation(b * nb).reshape(b, nb),
                         jnp.int32)
    pos = jnp.asarray([0, block_len * 3 + 1, block_len * nb - 1, 7],
                      jnp.int32)
    gate = jnp.asarray([True, True, True, False])
    q = f(b, 1, heads, d)
    row = f(b, 1, 1, 128).at[..., d:].set(0.0)
    for layer in range(layers):
        want, pool_w = ca._reference_latent_step(
            q, pool, tables, pos, layer, (row, gate), dv, 0.3)
        with _pinned_span(128 if nb == 40 else None):
            got, pool_g = ca.paged_decode_attention(
                q, pool, None, tables, pos, layer=jnp.int32(layer),
                new=(row, gate), latent=dv, scale=0.3, interpret=True)
        assert got.shape == (b, 1, heads, dv)
        assert float(jnp.abs(got - want).max()) < 1e-5
        # (the plain form scribbles a gated-off slot's row into the junk
        # block 0; the kernel writes nothing for it)
        assert bool((pool_g[:, 1:] == pool_w[:, 1:]).all())
        assert float(jnp.abs(got[3]).max()) == 0.0  # the gated-off slot


# ----------------------------------------------------------------------
# routing: sigmoid scores, a bias for the pick alone, the scale
# ----------------------------------------------------------------------

def _route(kernel, x, **kw):
    w, order, expert_of_row, sizes = moe.route_rows(kernel, x, top_k=2, **kw)
    picks = np.asarray(expert_of_row)[np.argsort(np.asarray(order))]
    return np.asarray(w), picks.reshape(-1, 2)


def _case_bias_changes_the_pick_not_the_weight():
    kernel = jnp.eye(4)
    x = jnp.asarray([[2.0, 1.0, 0.5, 0.0]])
    bias = jnp.asarray([0.0, 0.0, 0.0, 1.0])
    s = np.asarray(jax.nn.sigmoid(x))[0]
    w0, p0 = _route(kernel, x, scoring="sigmoid", normalize=False)
    w1, p1 = _route(kernel, x, scoring="sigmoid", normalize=False,
                    select_bias=bias)
    assert sorted(p0[0]) == [0, 1] and sorted(p1[0]) == [0, 3]
    # the weight of the expert the bias brought in is its SCORE
    assert np.allclose(sorted(w1[0]), sorted([s[0], s[3]]), atol=1e-6)
    assert np.allclose(sorted(w0[0]), sorted([s[0], s[1]]), atol=1e-6)


def _case_normalisation_and_scale():
    kernel = jnp.eye(4)
    x = jnp.asarray([[2.0, 1.0, 0.5, 0.0], [0.0, 0.1, 3.0, -1.0]])
    w, _ = _route(kernel, x, scoring="sigmoid", normalize=True)
    assert np.allclose(w.sum(-1), 1.0, atol=1e-6)
    w25, _ = _route(kernel, x, scoring="sigmoid", normalize=True, scale=2.5)
    assert np.allclose(w25, 2.5 * w, atol=1e-6)


def _case_n_group_one_is_the_plain_topk():
    """DeepSeek-V3's grouped top-k with ONE group of all the experts keeps
    every expert: the pick is the plain top-k of score + bias."""
    key = jax.random.PRNGKey(0)
    kernel = jax.random.normal(key, (16, 32))
    x = jax.random.normal(jax.random.fold_in(key, 1), (40, 16))
    bias = 0.1 * jax.random.normal(jax.random.fold_in(key, 2), (32,))
    _, picks = _route(kernel, x, scoring="sigmoid", select_bias=bias)
    scores = jax.nn.sigmoid(jnp.dot(x, kernel, precision="highest")) + bias
    group_scores = jax.lax.top_k(scores.reshape(40, 1, 32), 2)[0].sum(-1)
    keep_group = jax.lax.top_k(group_scores, 1)[1]  # one of one: group 0
    assert (np.asarray(keep_group) == 0).all()
    want = np.asarray(jax.lax.top_k(scores, 2)[1])
    assert (np.sort(picks, -1) == np.sort(want, -1)).all()


def _case_softmax_is_todays_program():
    key = jax.random.PRNGKey(1)
    kernel = jax.random.normal(key, (16, 8))
    x = jax.random.normal(jax.random.fold_in(key, 1), (10, 16))

    def eqns(**kw):
        return str(jax.make_jaxpr(lambda k, v: moe.route_rows(
            k, v, top_k=2, **kw))(kernel, x))

    assert eqns() == eqns(scoring="softmax", select_bias=None, scale=1.0)
    with pytest.raises(ValueError, match="scoring"):
        moe.route_rows(kernel, x, top_k=2, scoring="tanh")


ROUTING_CASES = {
    "bias_changes_the_pick_not_the_weight":
        _case_bias_changes_the_pick_not_the_weight,
    "normalisation_and_scale": _case_normalisation_and_scale,
    "n_group_one_is_the_plain_topk": _case_n_group_one_is_the_plain_topk,
    "softmax_is_todays_program": _case_softmax_is_todays_program,
}


@pytest.mark.parametrize("case", sorted(ROUTING_CASES))
def test_routing(case):
    ROUTING_CASES[case]()


# ----------------------------------------------------------------------
# one chip's share of the model
# ----------------------------------------------------------------------

def _whole_and_shares(cfg, n_shares):
    """The uncut model's params, and a params tree a share: the same
    leaves but each expert layer's stacks cut to the share's range."""
    whole_cfg = dataclasses.replace(cfg, experts_held=None)
    whole = llama_moe.init(jax.random.PRNGKey(3), whole_cfg)
    count = cfg.n_expert // n_shares

    def share(first):
        out = dict(whole)
        for i in range(cfg.first_k_dense, cfg.n_layer):
            m = whole[f"h_{i}"]["moe"]
            out[f"h_{i}"] = {**whole[f"h_{i}"], "moe": {
                **m, **{n: m[n][first:first + count]
                        for n in ("wg", "wu", "wd")}}}
        return out

    return whole_cfg, whole, [(f, count, share(f))
                              for f in range(0, cfg.n_expert, count)]


@pytest.mark.parametrize("n_shares", [2, 4])
def test_the_shares_add_up(model, n_shares):
    """The shares' routed parts plus the shared expert counted ONCE add up
    to the uncut reference's whole layer — by the reference, and by the
    program's expert hook."""
    _, cfg, _ = model
    whole_cfg, whole, shares = _whole_and_shares(cfg, n_shares)
    x = jax.random.normal(jax.random.PRNGKey(7), (30, cfg.n_embd))
    p = whole["h_1"]
    kw = ref._kw(whole_cfg, None)
    want = ref.layer(p, x, **kw)
    # what every chip computes alike: the residual stream after attention
    # and the shared expert; each share adds its routed part
    base = ref.layer({**p, "moe": {**p["moe"], **{
        n: p["moe"][n][:0] for n in ("wg", "wu", "wd")}}}, x, **kw)
    total = base
    for first, count, tree in shares:
        part = ref.layer(tree["h_1"], x, **{**kw, "first": first},
                         shared=False)
        no_experts = ref.layer({**p, "moe": {**p["moe"], **{
            n: p["moe"][n][:0] for n in ("wg", "wu", "wd")}}}, x, **kw,
            shared=False)
        total = total + (part - no_experts)
    assert float(jnp.abs(total - want).max()) < TOL
    # the program's hook, share by share, against the reference's share
    h = x[None]
    for first, count, tree in shares:
        held = dataclasses.replace(cfg, experts_first=first,
                                   experts_held=count)
        got = held.default_ffn()(tree["h_1"], h)[0]
        routed, common = ref._experts(tree["h_1"]["moe"], x, top_k=kw["top_k"],
                                      first=first, scale=kw["scale"])
        assert float(jnp.abs(got - (routed + common)).max()) < TOL


def test_reference_takes_the_held_range(model):
    _, cfg, _ = model
    _, _, shares = _whole_and_shares(cfg, 2)
    first, count, tree = shares[1]
    ids = jnp.asarray(_ids(20))
    got = ref.forward(cfg, tree, ids, held=(first, count))
    held = dataclasses.replace(cfg, experts_first=first, experts_held=count)
    want = llama_moe.make_apply(held)(tree, ids[None])[0]
    assert float(jnp.abs(got - want).max()) < TOL


# ----------------------------------------------------------------------
# the batcher: tokens, counters, refusals, leaves
# ----------------------------------------------------------------------

def test_batcher_tokens_equal_solo_generation(model):
    """Three requests batched == each alone, token by token, greedy: the
    solo form is the dense forward on the growing sequence (this family
    has no cached solo generate: its cache lives in the paged pool)."""
    spec, cfg, params = model
    b = _batcher(model)
    prompts = [_ids(19, 21), _ids(7, 22), _ids(33, 23)]
    rids = [b.submit(p, 6) for p in prompts]
    out = b.drain()
    apply = jax.jit(spec.apply)
    for rid, p in zip(rids, prompts):
        seq = list(p)
        for _ in range(6):
            ids = np.zeros((1, 40), np.int32)
            ids[0, :len(seq)] = seq
            seq.append(int(apply(params, jnp.asarray(ids))[0, len(seq) - 1]
                           .argmax()))
        assert seq[len(p):] == list(out[rid])
    with pytest.raises(ValueError, match="family adapter"):
        llama_moe.make_generate(cfg, max_new_tokens=2)(
            prepare_stacked(dict(params), cfg), jnp.asarray(prompts[1][None]),
            jax.random.PRNGKey(0))


def test_pool_has_one_leaf_and_the_counters_count(model):
    """The pool's one leaf; the mla_* counters from each slot's position
    on the host, exact for a known schedule; the moe_* counters over the
    EXPERT layers alone (layer 0 is dense)."""
    from dnn_tpu import obs
    from dnn_tpu.obs.mem import logical_nbytes
    from dnn_tpu.obs.timeline import StepClock

    _, cfg, _ = model
    b = _batcher(model)
    assert sorted(b.cache) == ["latent", "tables"]
    assert b.cache["latent"].shape == (cfg.n_layer, 3 * 8 + 1, 1, 8, 128)
    by_leaf = {k: int(logical_nbytes(v)) for k, v in b.cache.items()
               if k != "tables"}
    assert by_leaf == {"latent": cfg.n_layer * 25 * 8 * 128 * 4}
    if not obs.enabled():
        pytest.skip("observability is off")
    clock = b.step_clock = StepClock().install()
    b.submit(_ids(20, 7), 5)
    b.drain()
    layers, experts = cfg.n_layer, cfg.n_expert_layer
    # two chunks of 16 at 0 and 16: a chunk at `start` attends start + 16
    # cached positions, row t scores start + t + 1 pairs
    pairs = sum(s + t + 1 for s in (0, 16) for t in range(16))
    assert clock.mla_total["prefill"] == [2 * layers, layers * (16 + 32),
                                          layers * pairs]
    # four decode steps (the first token comes from the prefill), the
    # query at 20..23; positions are summed at each step's END over the
    # slots still live: a request's last step retires it first
    assert clock.mla_total["decode"] == [4 * layers,
                                         layers * sum(range(21, 24)), 0]
    moe_dec, moe_pre = clock.moe_total["decode"], clock.moe_total["prefill"]
    assert moe_dec[0] == 4 * experts and moe_pre[0] == 2 * experts
    # rows through the held experts: at most 3 slots x top_k a layer call
    assert 0 < moe_dec[1] <= 4 * experts * 3 * cfg.router_top_k
    assert moe_dec[2] <= 4 * experts * cfg.experts_held


def test_layer_zero_is_dense(model):
    """The first block computes its own gated MLP, whatever the router of
    a later layer says: zeroing every expert matrix leaves layer 0's
    output as it was and changes layer 1's."""
    from dnn_tpu.models import llama

    _, cfg, params = model
    ffn = cfg.default_ffn()
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 12, cfg.n_embd))
    out, stats = ffn.with_stats(params["h_0"], x)
    assert not stats.any()
    want = llama._mlp_out(params["h_0"], x, cfg=cfg, compute_dtype=None)
    assert float(jnp.abs(out - want).max()) == 0.0
    _, stats = ffn.with_stats(params["h_1"], x)
    assert stats[0] > 0


REFUSALS = {
    "prefix_cache": (dict(prefix_cache=8), "prefix_cache"),
    "kv_tier": (dict(prefix_cache=8, paged_blocks=40), "KV tier"),
    "int8_pool": (dict(kv_dtype="int8"), "int8"),
    "int4_pool": (dict(kv_dtype="int4"), "int4"),
    "dense_cache": (dict(kv="dense"), "dense"),
    "interleaved_prefill": (dict(prefill_chunk_tokens=16), "interleaved"),
}


@pytest.mark.parametrize("what", sorted(REFUSALS))
def test_refused_at_construction(model, what):
    kw, says = REFUSALS[what]
    with pytest.raises(ValueError, match=says) as err:
        _batcher(model, **kw)
    assert "latent" in str(err.value)  # by the leaf's name


def test_speculative_decoding_is_refused(model):
    from dnn_tpu.models import gpt
    from dnn_tpu.models.gpt import GPTConfig
    from dnn_tpu.runtime.serving_spec import SpeculativeBatcher

    spec, cfg, params = model
    d_cfg = GPTConfig(block_size=64, vocab_size=256, n_layer=1, n_head=2,
                      n_embd=16)
    d_prep = prepare_stacked(gpt.init(jax.random.PRNGKey(0), d_cfg), d_cfg)
    with pytest.raises(ValueError, match="speculative.*latent"):
        SpeculativeBatcher(cfg, prepare_stacked(dict(params), cfg), d_cfg,
                           d_prep, family=spec.extras["family_rows"]())


def test_stack_and_release_holds_two_stacks(model):
    from dnn_tpu.node import _stack_and_release

    spec, cfg, _ = model
    held = _stack_and_release(spec.init(jax.random.PRNGKey(3)), cfg,
                              jnp.bfloat16)
    assert "h_0" not in held
    assert held["dense_blocks"]["mlp"]["gate"]["kernel"].shape == (
        1, 64, cfg.d_ff_dense)
    moe_p = held["blocks"]["moe"]
    assert moe_p["wg"].shape[:2] == (cfg.n_expert_layer, cfg.experts_held)
    assert moe_p["wg"].dtype == jnp.bfloat16
    assert held["blocks"]["attn"]["kv_b"]["kernel"].dtype == jnp.bfloat16
    # routers, their selection bias and norm gains stay float32
    assert moe_p["router"]["kernel"].dtype == jnp.float32
    assert moe_p["router"]["select_bias"].dtype == jnp.float32
    assert held["blocks"]["attn"]["kv_a_norm"]["scale"].dtype == jnp.float32


# ----------------------------------------------------------------------
# the other families' pools are what they were (their step programs:
# tests/test_step_programs.py)
# ----------------------------------------------------------------------

def test_families_with_k_and_v_get_the_pools_they_got():
    for preset, leaves in (("olmoe-test", ["k", "tables", "v"]),
                           ("keye-test", ["ik", "k", "tables", "v"])):
        spec = get_model(preset)
        cfg = spec.config
        b = ContinuousBatcher(
            cfg, prepare_stacked(spec.init(jax.random.PRNGKey(0)), cfg),
            slots=2, max_len=32, prompt_pad=8, kv="paged", block_len=8,
            family=spec.extras["family_rows"]())
        assert sorted(b.cache) == leaves and not b._latent
        assert b.cache["k"].shape == (cfg.n_layer, 9, cfg.n_kv_head, 8, 128)


# ----------------------------------------------------------------------
# the benchmark's side: its driver's margins, its traffic, its rehearsal
# ----------------------------------------------------------------------

def test_served_rows_margins_equal_the_whole_logits_margins(model):
    from chipbench import check, serve_keye

    _, cfg, params = model
    prompts = [_ids(9, 1), _ids(30, 2)]
    tokens = [list(_ids(5, 3)), list(_ids(7, 4))]
    a = serve_keye.served_margins("joyai", cfg, params, prompts, tokens)
    b = check.served_margins("joyai", cfg, params, prompts, tokens)
    for key in ("worst_margin", "mean_margin", "argmax_share",
                "mean_logit_sigma"):
        assert abs(a[key] - b[key]) < 1e-5, key
    assert a["positions"] == b["positions"] == 12


def test_the_cell_resolves_and_its_traffic_is_a_full_backlog():
    """What chipbench/tests/test_traffic.py asks of every backlog, and the
    cell's rehearsal sizes."""
    from chipbench import cells, traffic

    cell = cells.resolve("joyai-docreport-saturated")
    t = cell["traffic"]
    assert t["kind"] == "backlog" and t["requests"] == 4000
    assert (t["outstanding"], t["anchor_index"]) == (64, 31)
    flags = cell["config"]["run"]["serve_flags"]
    assert flags == {"slots": 32, "max_len": 16384, "prompt_pad": 1024}
    assert t["max_total"] <= flags["max_len"]
    sizes = traffic.request_lengths(t, 7, 4000)
    assert sizes == traffic.request_lengths(t, 7, 4000)
    again = traffic.request_lengths(t, 2147483659, 4000)
    # every seed offers the same sizes block by block, in another order
    assert sorted(p for p, _ in sizes[:16]) == sorted(
        p for p, _ in again[:16])
    lens = np.asarray([p for p, _ in sizes])
    outs = np.asarray([n for _, n in sizes])
    assert lens.min() >= 4096 and lens.max() <= 12288
    assert outs.min() >= 192 and outs.max() <= 576
    assert (lens + outs).max() <= t["max_total"]
    assert set(cell["per_layer"]) >= {"joy_mla_decode_roofline_pct",
                                      "joy_decode_step_roofline_pct"}
    rehearsal = cells.resolve("joyai-docreport-saturated", rehearse=True)
    assert rehearsal["config"]["run"]["model"] == "joyai-test"
    assert rehearsal["traffic"]["max_total"] <= 64


def test_the_configuration_file_states_the_model_it_runs():
    """The file's numbers are the published ones (the catalog's row,
    copied into `PUBLISHED` below from `config.json`) but for the two keys
    it lists as reduced, and the preset it names is built from them."""
    from chipbench import cells
    from dnn_tpu.models import llama_moe

    published = {
        "first_k_dense_replace": 1, "head_dim": 64, "hidden_size": 2048,
        "intermediate_size": 7168, "kv_lora_rank": 512,
        "max_position_embeddings": 131072, "moe_intermediate_size": 768,
        "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 256,
        "n_shared_experts": 1, "num_attention_heads": 32,
        "num_experts_per_tok": 8, "num_hidden_layers": 40,
        "num_key_value_heads": 32, "num_nextn_predict_layers": 1,
        "q_lora_rank": 1536, "qk_head_dim": 192, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
        "rope_theta": 32000000, "routed_scaling_factor": 2.5,
        "topk_group": 1, "v_head_dim": 128, "vocab_size": 129280}
    f = cells.resolve("joyai-docreport-saturated")["config"]
    assert sorted(f["reduced"]) == ["n_routed_experts", "num_hidden_layers"]
    assert {k: f[k] for k in published if k not in f["reduced"]} == {
        k: v for k, v in published.items() if k not in f["reduced"]}
    for key in f["reduced"]:
        assert f["published"][key] == published[key]
    assert (f["scoring_func"], f["topk_method"], f["norm_topk_prob"],
            f["rope_interleave"], f["rope_scaling"]) == (
        "sigmoid", "noaux_tc", True, True, None)
    cfg = llama_moe.PRESETS[f["run"]["model"]]
    m, r = cfg.mla, cfg.router
    assert (cfg.n_layer, cfg.first_k_dense, cfg.d_ff_dense, cfg.d_ff,
            cfg.d_shared, cfg.n_embd, cfg.n_head, cfg.vocab_size) == (
        f["num_hidden_layers"], f["first_k_dense_replace"],
        f["intermediate_size"], f["moe_intermediate_size"],
        f["n_shared_experts"] * f["moe_intermediate_size"],
        f["hidden_size"], f["num_attention_heads"], f["vocab_size"])
    assert (cfg.n_expert, cfg.experts_held, cfg.router_top_k) == (
        f["published"]["router_outputs"], f["n_routed_experts"],
        f["num_experts_per_tok"])
    assert (m.q_lora_rank, m.kv_lora_rank, m.qk_nope_head_dim,
            m.qk_rope_head_dim, m.v_head_dim, m.rope_interleave) == (
        f["q_lora_rank"], f["kv_lora_rank"], f["qk_nope_head_dim"],
        f["qk_rope_head_dim"], f["v_head_dim"], f["rope_interleave"])
    assert (r.scoring, r.select_bias, r.scale, cfg.shared_gate,
            cfg.router_norm_topk) == ("sigmoid", True,
                                      f["routed_scaling_factor"], False, True)
    assert (cfg.rope_theta, cfg.rms_eps) == (f["rope_theta"],
                                             f["rms_norm_eps"])
    full = llama_moe.PRESETS["joyai-llm-flash"]
    assert (full.n_layer, full.experts_held, full.block_size) == (
        published["num_hidden_layers"], None,
        published["max_position_embeddings"])


def test_the_cells_rehearsal_runs():
    """`chipbench/run.py --workload joyai-docreport-saturated --rehearse`:
    the daemon on the CPU at joyai-test's size under the cell's traffic,
    every served token checked against the reference."""
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "chipbench", "run.py"),
         "--workload", "joyai-docreport-saturated", "--seed", "2147483659",
         "--seconds", "3", "--trace", "0", "--rehearse"],
        capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert last["rehearsal"] and last["correct"] and last["failed"] == 0
