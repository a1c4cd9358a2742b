"""Learned sparse attention behind the batcher (models/dsa.py), on the CPU
in float32 with the tiny `keye-test` preset: the dense forward, chunked
prefill and paged decode through `ContinuousBatcher` against the plain
reference `chipbench/reference/keye.py` on seeded weights; the selection's
edge cases; one chip's share of an expert layer; what refuses the third
cache leaf; the benchmark cell's rehearsal.

Tolerance 2e-5 on logits and log-probabilities: both sides are float32 on
the CPU and differ by summation order alone (measured 6e-7 on the dense
forward); a selection that differed in one position would move a row by
1e-2 and more.
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

from chipbench.reference import keye as ref
from dnn_tpu.models import dsa, llama_moe
from dnn_tpu.models.gpt import prepare_stacked
from dnn_tpu.ops.pallas import cached_attention as ca
from dnn_tpu.ops.pallas import sparse_attention as sa
from dnn_tpu.parallel import moe
from dnn_tpu.registry import get_model
from dnn_tpu.runtime.serving import ContinuousBatcher

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 2e-5


@pytest.fixture(scope="module")
def model():
    spec = get_model("keye-test")
    params = spec.init(jax.random.PRNGKey(3))
    return spec, spec.config, params


def _ids(n, seed=1):
    return np.asarray(jax.random.randint(
        jax.random.PRNGKey(seed), (n,), 1, 256), np.int32)


def _batcher(model, **kw):
    spec, cfg, params = model
    prepared = prepare_stacked(dict(params), cfg)
    opts = dict(slots=3, max_len=64, prompt_pad=16, kv="paged", block_len=8,
                family=spec.extras["family_rows"]())
    opts.update(kw)
    return ContinuousBatcher(cfg, prepared, **opts)


def test_preset_has_every_switch_acting(model):
    _, cfg, params = model
    assert cfg.n_head // cfg.n_kv_head == 2            # GQA
    assert cfg.head_dim != cfg.n_embd // cfg.n_head    # decoupled head
    assert cfg.qk_norm and cfg.qk_norm_width == "head"
    assert cfg.index_topk < cfg.block_size // 2        # selection discards
    assert cfg.router_norm_topk
    assert cfg.experts_held < cfg.n_expert
    blk = params["h_0"]
    assert blk["moe"]["wg"].shape[0] == cfg.experts_held
    assert blk["moe"]["router"]["kernel"].shape[-1] == cfg.n_expert
    assert set(blk["attn"]["indexer"]) == {"wq", "wk", "ww"}
    # drawn gains: not the ones a plain init leaves
    assert float(jnp.abs(blk["attn"]["q_norm"]["scale"] - 1.0).min()) > 0


def test_dense_forward_matches_the_reference(model):
    spec, cfg, params = model
    ids = jnp.asarray(np.stack([_ids(50, 1), _ids(50, 2)]))
    got = spec.apply(params, ids)
    want = ref.logits(cfg, params, ids)
    assert float(jnp.abs(got - want).max()) < TOL


def test_chunked_prefill_and_paged_decode_match_the_reference(model):
    """Three requests of different lengths through the batcher, two of
    them together: every emitted token is the reference's argmax and its
    log-probability the reference's, prompt chunks and decode steps
    alike (prompts of 29 and 37 take two and three chunks of 16)."""
    _, cfg, params = model
    b = _batcher(model, logprobs_k=2)
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


def _stable_topk(scores, valid, k):
    order = np.argsort(-np.where(valid, scores, -np.inf), axis=-1,
                       kind="stable")
    want = np.zeros(scores.shape, bool)
    for r in range(scores.shape[0]):
        want[r, order[r, :min(int(valid[r].sum()), k)]] = True
    return want


def _served_equals_reference(model, lens, new=4, **kw):
    _, cfg, params = model
    b = _batcher(model, **kw)
    prompts = [_ids(n, 10 + n) for n in lens]
    rids = [b.submit(p, new) for p in prompts]
    out = b.drain()
    for rid, p in zip(rids, prompts):
        seq = np.concatenate([p, out[rid]])
        want = np.asarray(ref.forward(cfg, params, jnp.asarray(seq))
                          .argmax(-1))[len(p) - 1:len(seq) - 1]
        assert (want == out[rid]).all(), (len(p), want, out[rid])


def _case_tie(model):
    # scores rounded to one decimal: ties everywhere, some across the cut
    rng = np.random.default_rng(0)
    scores = np.round(rng.normal(size=(6, 64)), 1).astype(np.float32)
    pos = np.array([3, 7, 8, 20, 47, 63])
    valid = np.arange(64)[None, :] <= pos[:, None]
    for k in (8, 16):
        got = dsa.select(jnp.asarray(scores), jnp.asarray(valid), k)
        assert (np.asarray(got) == _stable_topk(scores, valid, k)).all()
        live = dsa.select_live(jnp.asarray(scores), jnp.asarray(valid), k,
                               jnp.int32(64))
        assert (np.asarray(live) == np.asarray(got)).all()
    # -0.0 and 0.0 are one score
    z = jnp.asarray([[0.0, -0.0, 0.0, -0.0, 1.0]], jnp.float32) + 0.0
    got = dsa.select(z, jnp.ones((1, 5), bool), 3)
    assert np.asarray(got).tolist() == [[True, True, False, False, True]]


def _case_gated_off_slot(model, nb=4, pos=(5, 19, 30), span=None):
    """The paged kernel under a set, interpreted: a gated-off slot reads
    and writes nothing and returns zeros; the others attend their set.
    `span` pins the positions a group of the kernel covers."""
    from tests.test_decode_hotpath import _pinned_span

    rng = np.random.default_rng(1)
    b, hk, r, d, bp, n_layer = 3, 2, 2, 128, 8, 2
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa: E731
    kp, vp = f(n_layer, b * nb + 1, hk, bp, d), f(n_layer, b * nb + 1, hk, bp, d)
    tables = jnp.asarray(1 + rng.permutation(b * nb).reshape(b, nb), jnp.int32)
    pos = jnp.asarray(pos, jnp.int32)
    gate = jnp.asarray([True, False, True])
    valid = (jnp.arange(nb * bp)[None, :] <= pos[:, None]) & gate[:, None]
    sel = dsa.select(f(b, nb * bp), valid, 6)
    new = (f(b, hk, 1, d), f(b, hk, 1, d), gate)
    q = f(b, hk, r, d)
    with _pinned_span(span):
        got = ca.paged_decode_attention(q, kp, vp, tables, pos,
                                        layer=jnp.int32(1), new=new, sel=sel,
                                        interpret=True)
    want = ca._reference_paged_step(q, [kp, vp], tables, pos, jnp.int32(1),
                                    new, sel)
    assert float(jnp.abs(got[0] - want[0]).max()) < 1e-5
    assert float(jnp.abs(got[0][1]).max()) == 0.0
    # what a set leaves out matters: the full read differs
    with _pinned_span(span):
        full = ca.paged_decode_attention(q, kp, vp, tables, pos,
                                         layer=jnp.int32(1), new=new,
                                         interpret=True)
    assert float(jnp.abs(full[0][2] - got[0][2]).max()) > 1e-3


SELECTION_CASES = {
    # keye-test: topk 8, block_len 8, chunks of 16
    "context_under_topk": lambda m: _served_equals_reference(m, [5]),
    "context_exactly_topk": lambda m: _served_equals_reference(m, [7], new=3),
    "block_boundary": lambda m: _served_equals_reference(m, [16, 24], new=9),
    "two_slots_at_different_positions":
        lambda m: _served_equals_reference(m, [9, 41], new=6),
    "paged_kernel_interpreted":
        lambda m: _served_equals_reference(
            m, [21, 34], new=5,
            family=m[0].extras["family_rows"](attn_kernel="interpret")),
    "tie": _case_tie,
    "gated_off_slot": _case_gated_off_slot,
    # ISSUE 51, groups of 128 positions (16 blocks of 8): whole groups are
    # copied as straight-line code, a slot's last by the loop — two whole
    # and a partial one, an empty slot, exactly two whole
    "gated_off_slot_between_full_groups":
        lambda m: _case_gated_off_slot(m, nb=40, pos=(300, 290, 255),
                                       span=128),
    "groups_of_256_under_a_set":
        lambda m: _case_gated_off_slot(m, nb=72, pos=(570, 290, 511),
                                       span=256),
}


@pytest.mark.parametrize("case", sorted(SELECTION_CASES))
def test_selection(model, case):
    SELECTION_CASES[case](model)


# sparse_prefill_attention's cases beside the indexer's: the row's length
# and the KV x G heads, the chunk's start, the set's kind ("topk": the
# indexer's own over the plain scores; "hole": a (T, S) set with NO column
# in the first two tiles but the query's own, so rows pass live tiles with
# nothing chosen SO FAR and keep an empty state;
# "heads": a set a KV head), and what the grid must walk
ATTEND_CASES = {
    # the three starts PR 33 tested, one column tile
    "row128-start0": (128, 2, 2, 0, "topk", 128),
    "row128-start32": (128, 2, 2, 32, "topk", 128),
    "row128-start96": (128, 2, 2, 96, "topk", 128),
    # a row of four column tiles: a start in each prefix the grid may end
    # with, the last (the whole row) among them
    "row512-start0": (512, 2, 2, 0, "topk", 128),
    "row512-start96": (512, 2, 2, 96, "topk", 128),
    "row512-start100": (512, 2, 2, 100, "topk", 256),
    "row512-start300": (512, 2, 2, 300, "topk", 384),
    "row512-start480": (512, 2, 2, 480, "topk", 512),
    "hole-start224": (512, 2, 2, 224, "hole", 256),
    "hole-start300": (512, 2, 2, 300, "hole", 384),
    "hole-start480": (512, 2, 2, 480, "hole", 512),
    "heads-start0": (512, 2, 2, 0, "heads", 128),
    "heads-start300": (512, 2, 2, 300, "heads", 384),
    "heads-start480": (512, 3, 4, 480, "heads", 512),
    "g1-start300": (512, 2, 1, 300, "topk", 384),
    "g4-start224": (512, 1, 4, 224, "hole", 256),
    "g4-start480": (512, 2, 4, 480, "topk", 512),
    # 200 is no multiple of a column tile: the plain form, the whole row
    "row200-untiled": (200, 2, 2, 100, "topk", 200),
}


@pytest.mark.parametrize("case", sorted(ATTEND_CASES))
def test_chunk_kernels_match_their_plain_forms(case):
    s_len, kv, g, start, kind, walked = ATTEND_CASES[case]
    rng = np.random.default_rng(start + s_len + g)
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa: E731
    t, d, hi, di = 32, 16, 4, 8
    qi, w, ki = f(t, hi, di), f(t, hi), f(s_len, di)
    plain = sa.reference_chunk_index_scores(qi, w, ki)
    got = sa.chunk_index_scores(qi, w, ki, start, block_q=16, block_s=128,
                                interpret=True)
    pos = start + np.arange(t)
    valid = np.arange(s_len)[None, :] <= pos[:, None]
    assert float(jnp.abs(jnp.where(valid, got - plain, 0.0)).max()) < 1e-5
    if kind == "topk":
        sel = dsa.select(plain, jnp.asarray(valid), 8)
    else:
        shape = (kv, t, s_len) if kind == "heads" else (t, s_len)
        sel = valid & (rng.random(shape) < 0.1)
        if kind == "hole":
            sel[:, :256] = False
        sel[..., np.arange(t), pos] = True  # never empty
        sel = jnp.asarray(sel)
    q, k, v = f(kv, g, t, d), f(kv, s_len, d), f(kv, s_len, d)
    a = sa.reference_sparse_prefill_attention(q, k, v, sel)
    b = sa.sparse_prefill_attention(q, k, v, sel, start, block_q=16,
                                    block_s=128, interpret=True)
    assert float(jnp.abs(a - b).max()) < 1e-5
    # the grid ends with the chunk's live prefix: the same result from a
    # row that IS that prefix, whatever lies behind it
    assert sa.walked_columns(start, t, s_len, block_q=16,
                             block_s=128) == walked
    assert int(jax.jit(lambda st: sa.walked_columns(
        st, t, s_len, block_q=16, block_s=128))(start)) == walked
    c = sa.sparse_prefill_attention(
        q, k[:, :walked], v[:, :walked], sel[..., :walked], start,
        block_q=16, block_s=128, interpret=True)
    assert float(jnp.abs(b - c).max()) < 1e-6


def test_grid_step_fits_the_heads_rows_in_vmem():
    """`grid_step` from the shapes alone: 2048 rows a step at the Keye
    cut's G = 8 and at SALA's G = 16, fewer where they would not fit."""
    assert sa.grid_step(8, 1024, 16384, 128, 2) == (256, 512)
    assert sa.grid_step(16, 1024, 25600, 128, 2) == (128, 512)
    assert sa.grid_step(64, 1024, 16384, 128, 2) == (32, 512)
    assert sa.grid_step(8, 1024, 16384 + 64, 128, 2) is None
    assert sa.grid_step(8, 1000, 16384, 128, 2) is None
    # a row only narrower tiles divide
    assert sa.grid_step(8, 1024, 13312 + 256, 128, 2) == (256, 256)


# ----------------------------------------------------------------------
# one chip's share of an expert layer
# ----------------------------------------------------------------------

@pytest.fixture(scope="module")
def layer128():
    """A whole 128-expert layer at tiny widths, and tokens."""
    params = moe.init_moe_gated(jax.random.PRNGKey(0), 16, 128, 8)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 24, 16))
    return params, x


def _share(params, first, count):
    return {"router": params["router"],
            **{n: params[n][first:first + count] for n in ("wg", "wu", "wd")}}


def test_the_shares_add_up(layer128):
    """Eight shares of 16 of a 128-expert layer sum to the whole layer's
    result — the program's, and the uncut reference's."""
    params, x = layer128
    kw = dict(top_k=8, normalize=True, activation=jax.nn.silu)
    whole = moe.moe_ffn_grouped(params, x, **kw)
    total, rows = 0.0, 0
    for first in range(0, 128, 16):
        y, stats = moe.moe_ffn_grouped(_share(params, first, 16), x,
                                       held=(first, 16), return_stats=True,
                                       **kw)
        total, rows = total + y, rows + int(stats[0])
    assert float(jnp.abs(total - whole).max()) < 1e-5
    assert rows == 2 * 24 * 8  # every pick is held by exactly one share
    plain = ref._experts(params, x.reshape(-1, 16), top_k=8, first=0)
    assert float(jnp.abs(whole.reshape(-1, 16) - plain).max()) < 1e-5
    part = ref._experts(_share(params, 32, 16), x.reshape(-1, 16), top_k=8,
                        first=32)
    mine = moe.moe_ffn_grouped(_share(params, 32, 16), x, held=(32, 16), **kw)
    assert float(jnp.abs(mine.reshape(-1, 16) - part).max()) < 1e-5


# the extent of a share's permutation (ISSUES 64 and 65): 32 experts, 4 a
# token, 4 a share, 80 tokens of width 512 (the router reads the first 32
# columns: a token carries its own logits) — 320 picks, of which an even
# routing holds 40 here and ONE round of the compact form covers 128
_E, _K, _HELD, _T, _D = 32, 4, 4, 80, 512
_OUTSIDE = np.arange(_HELD, _E)


def _tokens_picking(n_all, n_one, rng, *, pad=0):
    """(T, E) logits: `n_all` tokens pick exactly experts 0-3, `n_one`
    expert 0 and three outside, the rest four outside — so share 0 holds
    4 * n_all + n_one picks, whatever the scoring. `pad`: the last `pad`
    tokens are ONE token (a prompt's last chunk), which picks experts 0-3."""
    logits = 0.1 * rng.standard_normal((_T, _E)).astype(np.float32)
    for t in range(_T):
        mine = ([0, 1, 2, 3] if t < n_all else
                [0] if t < n_all + n_one else [])
        others = rng.choice(_OUTSIDE, _K - len(mine), replace=False)
        logits[t, np.concatenate([mine, others]).astype(int)] += 6.0
    if pad:
        logits[_T - pad:] = 0.1 * rng.standard_normal(_E).astype(np.float32)
        logits[_T - pad:, :_HELD] += 6.0
    return logits


#: case -> (tokens picking all four held experts, tokens picking one — None:
#: the router's own picks of random tokens; pad positions; the extent pinned
#: — None: `permutation_extent`'s 128; column tiles of y)
EXTENT_CASES = {
    "none_held": ((0, 0), 0, None, 1),
    "far_under": (None, 0, None, 1),
    "exactly_the_extent": ((32, 0), 0, None, 1),
    "one_row_over": ((32, 1), 0, None, 1),
    "twice_the_extent": ((64, 0), 0, None, 1),
    "every_pick_held": ((_T, 0), 0, None, 1),
    # each held expert's 80 rows lie over three windows of 32
    "a_group_over_three_windows": ((_T, 0), 0, 32, 1),
    # 50 pad positions: four groups of 50 and more, two rounds
    "a_padded_chunk": ((2, 3), 50, None, 1),
    "a_padded_chunk_in_short_windows": ((2, 3), 50, 24, 1),
    # four consecutive rows, one row of y
    "one_token_with_every_pick_held": ((1, 0), 0, None, 1),
    "y_in_two_column_tiles": ((64, 0), 0, None, 2),
    "y_in_four_column_tiles": ((64, 1), 0, None, 4),
}


def _plain_share(params, x, *, first, scoring, gated):
    """What a share computes, with no sort and no group: every held expert
    on every token, weighted by the token's weight for it."""
    logits = jnp.dot(x, params["router"]["kernel"], precision="highest")
    scores = (jax.nn.softmax(logits, -1) if scoring == "softmax"
              else jax.nn.sigmoid(logits))
    top, idx = jax.lax.top_k(scores, _K)
    top = top / top.sum(-1, keepdims=True)
    weights = (jax.nn.one_hot(idx, _E) * top[..., None]).sum(1)
    out = jnp.zeros_like(x)
    for j in range(first, first + _HELD):
        if gated:
            y = (jax.nn.silu(x @ params["wg"][j]) * (x @ params["wu"][j])
                 ) @ params["wd"][j]
        else:
            y = jax.nn.silu(x @ params["wi"][j] + params["bi"][j]
                            ) @ params["wo"][j] + params["bo"][j]
        out = out + weights[:, j, None] * y
    return out


@pytest.mark.parametrize("scoring", ["softmax", "sigmoid"])
@pytest.mark.parametrize("gated", [True, False], ids=["gated", "plain"])
@pytest.mark.parametrize("case", sorted(EXTENT_CASES))
def test_the_extent_follows_the_rows_held(case, gated, scoring, monkeypatch):
    """ISSUES 64 and 65: where the experts go through the kernel that reads
    their stacks in place, a share's dispatch, experts and combine cover
    `permutation_extent` rows a round and as many rounds as its live rows
    need — none when no pick is held here, one up to exactly the extent,
    two from one row over it, S*k / extent when every pick is held; a group
    may lie over several windows, a token's rows may follow each other, y
    may be walked in column tiles: a share equals the plain form, no row is
    dropped at any routing, and the eight shares add up to the whole
    layer's result."""
    from dnn_tpu.ops.pallas import row_accumulate as ra

    picks, pad, pinned, y_tiles = EXTENT_CASES[case]
    rng = np.random.default_rng(64)
    f = 16
    init = moe.init_moe_gated if gated else moe.init_moe
    params = dict(init(jax.random.PRNGKey(2), _D, _E, f))
    params["router"] = {"kernel": jnp.eye(_D, _E)}
    if not gated:  # the seeded biases are zeros: a wrong row's would hide
        params["bi"] = jnp.asarray(rng.standard_normal((_E, f)), jnp.float32)
        params["bo"] = jnp.asarray(rng.standard_normal((_E, _D)), jnp.float32)
    x = np.array(jax.random.normal(jax.random.PRNGKey(3), (_T, _D)))
    if pad:
        x[_T - pad:] = x[-1]
    if picks is not None:
        x[:, :_E] = _tokens_picking(*picks, rng, pad=pad)
    x = jnp.asarray(x)
    live = None if picks is None else 4 * picks[0] + picks[1] + 4 * pad
    # a call this small keeps one pass in the program (the rounds' loop
    # would cost more than it saves): here every size takes the rounds
    assert moe.permutation_extent(_T * _K, _E, _HELD) == _T * _K
    monkeypatch.setattr(moe, "_MIN_ROWS_SAVED", 0)
    assert moe.permutation_extent(_T * _K, _E, _HELD) == 128 < _T * _K
    extent = pinned or 128
    if pinned:
        monkeypatch.setattr(moe, "permutation_extent", lambda *a: pinned)
    monkeypatch.setattr(ra, "_Y_BLOCK_BYTES", _T * (_D // y_tiles) * 4)
    kw = dict(top_k=_K, normalize=True, activation=jax.nn.silu,
              scoring=scoring, return_stats=True)

    def share_of(first, interpret):
        """Share `first`'s stacks as layer 1 of a loop's whole stacks
        (`first` is traced: the eight shares are one compiled program)."""
        mine = {n: w if n == "router" else
                jax.lax.dynamic_slice_in_dim(w, first, _HELD)
                for n, w in params.items()}
        stacks = {n: moe.LayerOf(jnp.stack([jnp.full_like(w, jnp.nan), w]),
                                 jnp.int32(1))
                  for n, w in mine.items() if n in moe.EXPERT_MATRICES}
        return moe.moe_ffn_grouped({**mine, **stacks}, x, held=(first, _HELD),
                                   interpret=interpret, **kw)

    compact = jax.jit(lambda first: share_of(first, True))
    one_pass = jax.jit(lambda first: share_of(first, False))

    def share(first, interpret=False):
        return (compact if interpret else one_pass)(jnp.int32(first))

    whole, _ = moe.moe_ffn_grouped(params, x, **{**kw, "held": None})
    total = 0.0
    for first in range(0, _E, _HELD):
        y, stats = share(first, interpret=True)
        total = total + y
        rows, _, _, moved, extra = (int(v) for v in stats)
        rounds = -(-rows // extent)
        assert (moved, extra) == (rounds * extent, max(rounds - 1, 0))
        want = _plain_share(params, x, first=first, scoring=scoring,
                            gated=gated)
        assert float(jnp.abs(y - want).max()) < 1e-5, first
        if first == 0 and live is not None:
            assert rows == live
    assert float(jnp.abs(total - whole).max()) < 1e-5
    if gated and scoring == "softmax":
        part = ref._experts({"router": params["router"], **{
            n: params[n][:_HELD] for n in ("wg", "wu", "wd")}}, x,
            top_k=_K, first=0)
        assert float(jnp.abs(share(0, interpret=True)[0] - part).max()) < 1e-5
    # off the TPU the stacks are cut and the extent is S*k: one pass
    y, stats = share(0)
    assert int(stats[3]) == _T * _K and int(stats[4]) == 0
    assert float(jnp.abs(y - share(0, interpret=True)[0]).max()) < 1e-5


def test_every_expert_held_is_todays_program(layer128):
    params, x = layer128
    kw = dict(top_k=8, normalize=False, activation=jax.nn.silu,
              return_stats=True)
    y0, s0 = moe.moe_ffn_grouped(params, x, **kw)
    y1, s1 = moe.moe_ffn_grouped(params, x, held=(0, 128), **kw)
    assert (np.asarray(y0) == np.asarray(y1)).all()
    assert np.asarray(s0).tolist() == np.asarray(s1).tolist()
    # the permutation moved every pick's row, in one pass
    assert np.asarray(s0)[3:].tolist() == [2 * 24 * 8, 0]
    # and without the argument nothing of it is traced: the rows behind
    # the last group are masked only for a share
    def eqns(**more):
        return len(jax.make_jaxpr(lambda p, v: moe.moe_ffn_grouped(
            p, v, top_k=8, activation=jax.nn.silu, **more))(params, x).eqns)

    assert eqns() < eqns(held=(0, 128))


# ----------------------------------------------------------------------
# what assumes K and V alone refuses the third leaf
# ----------------------------------------------------------------------

REFUSALS = {
    "prefix_cache": (dict(prefix_cache=8), "prefix_cache"),
    "kv_tier": (dict(prefix_cache=8, paged_blocks=40), "KV tier"),
    "int8_pool": (dict(kv_dtype="int8"), "int8"),
    "dense_cache": (dict(kv="dense"), "dense"),
    "interleaved_prefill": (dict(prefill_chunk_tokens=16), "interleaved"),
}


@pytest.mark.parametrize("what", sorted(REFUSALS))
def test_refused_at_construction(model, what):
    kw, says = REFUSALS[what]
    with pytest.raises(ValueError, match=says):
        _batcher(model, **kw)


def test_speculative_decoding_is_refused(model):
    from dnn_tpu.models.gpt import GPTConfig
    from dnn_tpu.models import gpt
    from dnn_tpu.runtime.serving_spec import SpeculativeBatcher

    spec, cfg, params = model
    d_cfg = GPTConfig(block_size=64, vocab_size=256, n_layer=1, n_head=2,
                      n_embd=16)
    d_prep = prepare_stacked(gpt.init(jax.random.PRNGKey(0), d_cfg), d_cfg)
    with pytest.raises(ValueError, match="speculative"):
        SpeculativeBatcher(cfg, prepare_stacked(dict(params), cfg), d_cfg,
                           d_prep, family=spec.extras["family_rows"]())


def test_families_without_an_indexer_allocate_nothing_new():
    spec = get_model("olmoe-test")
    cfg = spec.config
    b = ContinuousBatcher(
        cfg, prepare_stacked(spec.init(jax.random.PRNGKey(0)), cfg), slots=2,
        max_len=32, prompt_pad=8, kv="paged", block_len=8,
        family=spec.extras["family_rows"]())
    assert sorted(b.cache) == ["k", "tables", "v"]
    assert b._index_topk is None


def test_pool_has_three_leaves_and_counts_what_it_selects(model):
    """The pool's third leaf, and the dsa_* counters: from each slot's
    position on the host, exact for a known schedule."""
    from dnn_tpu import obs
    from dnn_tpu.obs.timeline import StepClock

    _, cfg, _ = model
    b = _batcher(model)
    assert sorted(b.cache) == ["ik", "k", "tables", "v"]
    assert b.cache["ik"].shape == (cfg.n_layer, 3 * 8 + 1, 1, 8, 128)
    if not obs.enabled():
        pytest.skip("observability is off")
    clock = b.step_clock = StepClock().install()
    b.submit(_ids(20, 7), 5)
    b.drain()
    layers, k = cfg.n_layer, cfg.index_topk
    pf = clock.dsa_total["prefill"]
    # two chunks of 16 at 0 and 16: row t of a chunk at `start` scores
    # start + t + 1 positions and reads min(that, topk); the masked
    # kernel's grid walks `walked_columns` of the transient row a query —
    # the rule the kernel's wrapper sizes its grid with
    cand = sum(s + t + 1 for s in (0, 16) for t in range(16))
    picked = sum(min(s + t + 1, k) for s in (0, 16) for t in range(16))
    walked = sum(16 * sa.walked_columns(s, 16, b._row_len) for s in (0, 16))
    assert pf == [2 * layers, layers * cand, layers * picked,
                  layers * walked]
    # four decode steps (the first token comes from the prefill), the
    # query at 20, 21, 22, 23; positions are summed at each step's END
    # over the slots still live, as step_attn_live_blocks_total is: a
    # request's last step retires it first and adds no positions
    dec = clock.dsa_total["decode"]
    assert dec == [4 * layers, layers * sum(range(21, 24)), layers * 3 * k,
                   0]


# ----------------------------------------------------------------------
# the benchmark's side: its driver's margins, its cell's rehearsal
# ----------------------------------------------------------------------

def test_served_rows_margins_equal_the_whole_logits_margins(model):
    from chipbench import check, serve_keye

    _, cfg, params = model
    prompts = [_ids(9, 1), _ids(30, 2)]
    tokens = [list(_ids(5, 3)), list(_ids(7, 4))]
    a = serve_keye.served_margins("keye", cfg, params, prompts, tokens)
    b = check.served_margins("keye", cfg, params, prompts, tokens)
    for key in ("worst_margin", "mean_margin", "argmax_share",
                "mean_logit_sigma"):
        assert abs(a[key] - b[key]) < 1e-5, key
    assert a["positions"] == b["positions"] == 12


def test_reference_takes_the_held_range(model):
    _, cfg, params = model
    whole = dataclasses.replace(cfg, experts_held=None)
    full = llama_moe.init(jax.random.PRNGKey(3), whole)
    ids = jnp.asarray(_ids(20))
    # the same seed draws other expert matrices for 8 experts than for 4:
    # hold the first 4 of the whole model's instead
    share = jax.tree_util.tree_map(lambda x: x, full)
    for i in range(cfg.n_layer):
        m = share[f"h_{i}"]["moe"]
        share[f"h_{i}"]["moe"] = {"router": m["router"], **{
            n: m[n][2:6] for n in ("wg", "wu", "wd")}}
    got = ref.forward(cfg, share, ids, held=(2, 4))
    held = dataclasses.replace(cfg, experts_first=2)
    want = llama_moe.make_apply(held)(share, ids[None])[0]
    assert float(jnp.abs(got - want).max()) < TOL


def test_the_cells_rehearsal_runs():
    """`chipbench/run.py --workload keye-videoqa-saturated --rehearse`:
    the daemon on the CPU at keye-test's size under the cell's traffic,
    every served token checked against the reference."""
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "chipbench", "run.py"),
         "--workload", "keye-videoqa-saturated", "--seed", "2147483659",
         "--seconds", "3", "--trace", "0", "--rehearse"],
        capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert last["rehearsal"] and last["correct"] and last["failed"] == 0
