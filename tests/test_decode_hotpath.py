"""ISSUE 6 decode-hot-path contracts: donation/aliasing as an asserted
invariant, the kv=paged|dense serving flag, int4 quantized KV, the fused
paged flash-decode kernel, and quantization-aware byte accounting.

This module pins the CORRECTNESS surface of the decode hot path (its
speed is the chip benchmark's business, PERF.md):

  * every donated leaf of every decode-step program (dense f32/int8/
    int4, bucketed, paged, speculative) aliases an output, and the
    StableHLO carries zero cache-sized copies — the static form of
    "the KV update is in-place", enforced here AND by the analysis gate
    (analysis/program.audit_serving_decode);
  * paged-vs-dense token parity under the batcher, through the kv flag's
    three spellings and the auto-sizing path;
  * int4 cache parity across layouts (dense == bucketed == paged — one
    quantizer, three storages) and bounded rounding error vs f32;
  * the paged decode kernel's interpret-mode parity against the
    gather_view einsum oracle;
  * logical_nbytes / kv_bytes_per_pos pricing int4 at its packed half
    byte plus scale rows (the obs/mem + flops satellite).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dnn_tpu.models import gpt
from dnn_tpu.runtime.serving import ContinuousBatcher


@pytest.fixture(scope="module")
def tiny():
    cfg = gpt.GPTConfig(vocab_size=89, block_size=128, n_layer=2,
                        n_head=2, n_embd=32)
    prepared = gpt.prepare_stacked(
        gpt.init(jax.random.PRNGKey(0), cfg), cfg)
    return cfg, prepared


def _run(cfg, prepared, prompt, new_tokens=16, **kw):
    b = ContinuousBatcher(cfg, prepared, slots=2, max_len=64,
                          prompt_pad=16, **kw)
    rid = b.submit(prompt, max_new_tokens=new_tokens)
    out = b.drain()
    return np.asarray(out[rid]), b


# ----------------------------------------------------------------------
# donation coverage + zero cache-sized copies (the tentpole invariant)
# ----------------------------------------------------------------------

def test_serving_decode_fully_aliased_no_cache_copies():
    from dnn_tpu.analysis.program import audit_serving_decode

    report = audit_serving_decode()
    assert not report["findings"], [f.message for f in report["findings"]]
    # >= : ISSUE 12 added the mixed-step/fused-finish variants, pinned
    # by name in tests/test_overlap.py — this gate only requires that
    # none of the original six ever drop out of the audit
    assert set(report["variants"]) >= {
        "dense_f32", "dense_int8", "dense_int4", "bucketed", "paged",
        "speculative"}
    for name, v in report["variants"].items():
        assert v["aliased"] == v["expected"], (name, v)
        assert v["cache_sized_ops"] == {}, (name, v)


def _eqns(jaxpr):
    """Every equation of a jaxpr, sub-jaxprs (scan / cond / pjit bodies)
    included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


@pytest.mark.parametrize("variant", [
    "paged", "paged_int8", "paged_kernel", "paged_mixed", "dense"])
def test_paged_pool_rides_the_layer_loop_as_carry(tiny, variant):
    """ISSUE 25: the decode step reaches a paged pool in place, by layer
    index. From the step's jaxpr: no leaf of any scan's xs / ys has a pool
    leaf's shape (the pool is a carry, never sliced per layer and
    stacked back), and no dynamic_slice / dynamic_update_slice in the
    step reads or writes a layer-slice-sized operand. The dense cache
    keeps riding as xs / ys — which is what this test would catch on a
    paged pool."""
    from dnn_tpu.runtime.serving import GPTFamilyRows

    cfg, prepared = tiny
    kw = {"kv": "dense"} if variant == "dense" else {"kv": "paged"}
    if variant == "paged_int8":
        kw["kv_dtype"] = "int8"
    if variant == "paged_kernel":
        kw["family"] = GPTFamilyRows(cfg, attn_kernel="interpret")
    if variant == "paged_mixed":
        kw["prefill_chunk_tokens"] = 16
    b = ContinuousBatcher(cfg, prepared, slots=2, max_len=64,
                          prompt_pad=16, **kw)
    args = (b._decode_view, b.cache, b.pos, b.tok, b.active, b.keys,
            b._temp, b._topk, b._topp, b._minp, b._rep, b._seen,
            b._bias, b._crow, b._ctable, b._ctrans)
    fn = b._decode
    if variant == "paged_mixed":
        fn = b._mixed
        args = (args[0],) + args + (b._ilv_new_row(),
                                    jnp.zeros((1, 16), jnp.int32),
                                    jnp.int32(0))
    jaxpr = fn.trace(*args).jaxpr.jaxpr
    pool_shapes = {x.shape for x in jax.tree.leaves(b.cache) if x.ndim > 3}
    slice_elems = min(int(np.prod(s[1:])) for s in pool_shapes)
    stacked, sliced = [], []
    for eqn in _eqns(jaxpr):
        if eqn.primitive.name == "scan":
            n_in = eqn.params["num_consts"] + eqn.params["num_carry"]
            xs_ys = (eqn.invars[n_in:]
                     + eqn.outvars[eqn.params["num_carry"]:])
            stacked += [v.aval.shape for v in xs_ys
                        if v.aval.shape in pool_shapes]
        elif eqn.primitive.name in ("dynamic_slice",
                                    "dynamic_update_slice"):
            sliced += [v.aval.shape for v in eqn.invars[:2]
                       if hasattr(v.aval, "shape")
                       and int(np.prod(v.aval.shape)) >= slice_elems]
    if variant == "dense":
        assert stacked  # (L, B, H, S, D) still rides xs / ys by layer
    else:
        assert stacked == [] and sliced == []


# ----------------------------------------------------------------------
# the kv flag
# ----------------------------------------------------------------------

def test_kv_paged_dense_auto_token_parity(tiny):
    cfg, prepared = tiny
    prompt = np.arange(1, 13) % 89
    t_dense, bd = _run(cfg, prepared, prompt, kv="dense")
    t_paged, bp = _run(cfg, prepared, prompt, kv="paged")
    t_auto, ba = _run(cfg, prepared, prompt, kv="auto")
    assert not bd._paged and bp._paged and ba._paged
    np.testing.assert_array_equal(t_dense, t_paged)
    np.testing.assert_array_equal(t_dense, t_auto)
    # auto-sizing preserves the dense pool's capacity (+ junk block 0)
    assert bp._allocator.n_blocks == 2 * (64 // 16) + 1


def test_kv_auto_falls_back_dense_visibly(tiny):
    cfg, prepared = tiny
    prompt = np.arange(1, 13) % 89
    t_dense, _ = _run(cfg, prepared, prompt, kv="dense")
    # decode_buckets is a dense-pool feature: auto must fall back AND say so
    t_b, bb = _run(cfg, prepared, prompt, kv="auto", decode_buckets=True)
    assert not bb._paged and bb._buckets is not None
    np.testing.assert_array_equal(t_dense, t_b)
    # indivisible geometry falls back too
    b2 = ContinuousBatcher(cfg, prepared, slots=2, max_len=60,
                           prompt_pad=20, kv="auto")
    assert not b2._paged


def test_kv_flag_validation(tiny):
    cfg, prepared = tiny
    with pytest.raises(ValueError, match="paged.*dense|dense.*paged"):
        ContinuousBatcher(cfg, prepared, slots=2, max_len=64, kv="bogus")
    with pytest.raises(ValueError, match="contradicts"):
        ContinuousBatcher(cfg, prepared, slots=2, max_len=64,
                          prompt_pad=16, kv="dense", paged_blocks=8)
    with pytest.raises(ValueError, match="not available"):
        ContinuousBatcher(cfg, prepared, slots=2, max_len=64,
                          prompt_pad=16, kv="paged", decode_buckets=True)
    # auto must NOT silently discard an EXPLICIT pool sizing: the same
    # misconfiguration that failed loud pre-flag still fails loud
    with pytest.raises(ValueError, match="not available"):
        ContinuousBatcher(cfg, prepared, slots=2, max_len=64,
                          prompt_pad=16, kv="auto", paged_blocks=8,
                          decode_buckets=True)


# ----------------------------------------------------------------------
# int4 KV
# ----------------------------------------------------------------------

def test_int4_same_tokens_across_layouts(tiny):
    """One quantizer, three storages: dense, bucketed and paged int4
    caches must emit IDENTICAL tokens (each stores the same quantized
    rows; attention math is the shared scaled einsum)."""
    cfg, prepared = tiny
    prompt = (np.arange(1, 19) * 5) % 89
    t_dense, _ = _run(cfg, prepared, prompt, new_tokens=40,
                      kv_dtype="int4")
    t_buck, _ = _run(cfg, prepared, prompt, new_tokens=40,
                     kv_dtype="int4", decode_buckets=True)
    t_paged, _ = _run(cfg, prepared, prompt, new_tokens=40,
                      kv_dtype="int4", kv="paged")
    np.testing.assert_array_equal(t_dense, t_buck)
    np.testing.assert_array_equal(t_dense, t_paged)


def test_int4_attend_close_to_float():
    """Per-row int4 rounding stays bounded: cosine similarity of the
    attended output vs the f32 codec on the same K/V > 0.99."""
    from dnn_tpu.runtime.kvcache import FloatKV, Int4KV
    from dnn_tpu.runtime.paged_kvcache import LayerRows

    cfg = gpt.GPTConfig(vocab_size=31, block_size=64, n_layer=1,
                        n_head=2, n_embd=32)
    key = jax.random.PRNGKey(1)
    f32 = FloatKV()
    i4 = Int4KV()
    k = jax.random.normal(jax.random.fold_in(key, 1), (2, 2, 40, 16))
    v = jax.random.normal(jax.random.fold_in(key, 2), (2, 2, 40, 16))
    # a codec writes into the cache bound to a layer, and attends its rows
    cf = f32.write(LayerRows(f32.init(cfg, 2, 48), 0), k, v, 0).read()
    ci = i4.write(LayerRows(i4.init(cfg, 2, 48), 0), k, v, 0).read()
    assert ci["k"].dtype == jnp.int4
    q = jax.random.normal(jax.random.fold_in(key, 3), (2, 2, 1, 16))
    pos = jnp.asarray([20, 39], jnp.int32)
    of = np.asarray(f32.attend_rows(q, cf, pos)).reshape(-1)
    oi = np.asarray(i4.attend_rows(q, ci, pos)).reshape(-1)
    cos = float(np.dot(of, oi)
                / (np.linalg.norm(of) * np.linalg.norm(oi)))
    assert cos > 0.99, cos


def test_int4_rolling_rejected():
    from dnn_tpu.runtime.kvcache import Int4KV, codec_for_cache

    cfg = gpt.GPTConfig(vocab_size=31, block_size=64, n_layer=1,
                        n_head=2, n_embd=32)
    cache = Int4KV().init(cfg, 1, 16)
    with pytest.raises(ValueError, match="rolling int4"):
        codec_for_cache(cache, rolling=True, window=16)


# ----------------------------------------------------------------------
# fused paged flash-decode kernel (interpret mode runs the real index
# maps on CPU)
# ----------------------------------------------------------------------

def _random_pool(key, lead, quant):
    """K/V pool (*lead, Hk, bp, D) — float, or int8 with scale leaves."""
    Hk, bp, D = 2, 16, 16
    if not quant:
        return (jax.random.normal(jax.random.fold_in(key, 14),
                                  (*lead, Hk, bp, D)),
                jax.random.normal(jax.random.fold_in(key, 15),
                                  (*lead, Hk, bp, D)), None, None)
    kp, vp = (jax.random.randint(
        jax.random.fold_in(key, i), (*lead, Hk, bp, D), -127, 128,
        dtype=jnp.int32).astype(jnp.int8) for i in (10, 11))
    ks, vs = (jax.random.uniform(
        jax.random.fold_in(key, i), (*lead, Hk, bp)) + 0.5 for i in (12, 13))
    return kp, vp, ks, vs


@pytest.mark.parametrize("r,quant", [(1, False), (4, False), (1, True)])
def test_paged_kernel_matches_gather_einsum(r, quant):
    from dnn_tpu.ops.pallas.cached_attention import (
        paged_decode_attention,
        reference_paged_decode_attention,
    )

    key = jax.random.PRNGKey(2)
    B, Hk, D, nb, NB = 3, 2, 16, 4, 12
    tables = jnp.asarray(
        np.random.RandomState(0).randint(1, NB, (B, nb)), jnp.int32)
    pos = jnp.asarray([5, 33, 63], jnp.int32)
    q = jax.random.normal(jax.random.fold_in(key, r), (B, Hk, r, D))
    kp, vp, ks, vs = _random_pool(key, (NB,), quant)
    ref = reference_paged_decode_attention(q, kp, vp, tables, pos,
                                           ks=ks, vs=vs)
    out = paged_decode_attention(q, kp, vp, tables, pos, ks=ks,
                                 vs=vs, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("layer", [0, 2, 4])
@pytest.mark.parametrize("quant", [False, True], ids=["float", "int8"])
def test_paged_kernel_on_the_whole_pool(layer, quant):
    """The decode loop's form (interpret mode): the whole (L, n_blocks,
    ...) pool entered at `layer`, first read-only, then with the step's
    rows placed by the kernel — a gated-off slot's row lands in junk
    block 0 and nowhere else. The oracle is the plain scatter at
    [layer, block, :, row] and the gather einsum on that layer."""
    from dnn_tpu.ops.pallas.cached_attention import (
        paged_decode_attention,
        reference_paged_decode_attention,
    )

    key = jax.random.PRNGKey(3)
    L, B, Hk, D, nb, bp, NB = 5, 3, 2, 16, 4, 16, 13
    # every slot its own blocks (a written block has one owner)
    tables = jnp.asarray(1 + np.random.RandomState(1).permutation(
        NB - 1).reshape(B, nb), jnp.int32)
    pos = jnp.asarray([5, 33, 63], jnp.int32)
    gate = jnp.asarray([True, False, True])
    q = jax.random.normal(jax.random.fold_in(key, 1), (B, Hk, 1, D))
    kp, vp, ks, vs = _random_pool(key, (L, NB), quant)
    pools = [x for x in (kp, vp, ks, vs) if x is not None]

    # read-only at a layer == the per-layer form on that layer's slice
    got = paged_decode_attention(q, kp, vp, tables, pos, ks=ks, vs=vs,
                                 layer=jnp.int32(layer), interpret=True)
    want = reference_paged_decode_attention(
        q, *(x[layer] for x in pools[:2]), tables, pos,
        **({"ks": ks[layer], "vs": vs[layer]} if quant else {}))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)

    # with the step's rows: the kernel writes, then attends what it wrote
    rows = [jax.random.normal(jax.random.fold_in(key, 20 + i),
                              (B, Hk, 1, D)) for i in range(2)]
    if quant:
        rows = [jnp.round(x * 40).astype(jnp.int8) for x in rows] + [
            jax.random.uniform(jax.random.fold_in(key, 30 + i),
                               (B, Hk, 1)) + 0.5 for i in range(2)]
    new = (*rows, gate)
    got, *got_pools = paged_decode_attention(
        q, kp, vp, tables, pos, ks=ks, vs=vs, layer=jnp.int32(layer),
        new=new, interpret=True)
    # interpret=None off the TPU is the plain-jnp form of the same call
    want, *want_pools = paged_decode_attention(
        q, kp, vp, tables, pos, ks=ks, vs=vs, layer=jnp.int32(layer),
        new=new)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    row_at = np.asarray(pos) % bp
    for g, w, before, r in zip(got_pools, want_pools, pools, rows):
        g, before = np.asarray(g), np.asarray(before)
        # off the junk block the kernel's pool IS the oracle's, bit for bit
        np.testing.assert_array_equal(g[:, 1:], np.asarray(w)[:, 1:])
        # live slots: the row is in its block; the gated-off slot's block
        # is untouched, and so is every other layer
        for b_ in range(B):
            blk = int(tables[b_, int(pos[b_]) // bp])
            at = g[layer, blk][:, row_at[b_]]
            if bool(gate[b_]):
                np.testing.assert_array_equal(at, np.asarray(r)[b_, :, 0])
            else:
                np.testing.assert_array_equal(g[layer, blk],
                                              before[layer, blk])
        others = [x for x in range(L) if x != layer]
        np.testing.assert_array_equal(g[others], before[others])


def _pinned_span(span):
    """The kernel's group held at `span` positions whatever the call's
    shapes ask for (`_paged_group`'s rule would give these small pools
    1024): the paths of a group — straight-line copies for a full one, the
    loop for a slot's last — at every span the rule can pick. None leaves
    the rule in place."""
    import contextlib
    from unittest import mock

    from dnn_tpu.ops.pallas import cached_attention as ca

    if span is None:
        return contextlib.nullcontext()
    return mock.patch.object(
        ca, "_paged_group",
        lambda bp, nb, _bytes, widest=None: max(1, min(span // bp, nb)))


def _paged_step_case(key, *, pos, gate=None, bp=16, nb=4, hk=2, r=1, d=16,
                     width=None, quant=False, layers=1, layer=0,
                     tables=None, select=False, span=None):
    """One call of the kernel (interpret mode) on a random whole pool
    against the plain-jnp form of the same call: read-only without
    `gate`, with the step's rows placed under it. -> (got, want, the
    pools before, tables): `got` / `want` are (out, *pools) with a gate
    and (out,) without. `width` stores the rows lane-padded. `select`
    reads a random set: about half of a slot's positions, `pos` among
    them. `span` pins the positions a group (`_pinned_span`)."""
    from dnn_tpu.ops.pallas.cached_attention import (
        _reference_paged_step,
        paged_decode_attention,
    )

    B, Hk, W = len(pos), hk, width or d
    NB = B * nb + 1
    if tables is None:  # every slot its own blocks
        tables = 1 + np.random.RandomState(len(pos) + nb).permutation(
            NB - 1).reshape(B, nb)
    tables = jnp.asarray(tables, jnp.int32)
    pos = jnp.asarray(pos, jnp.int32)
    lead = (layers, NB)
    pad = [(0, 0)] * 4 + [(0, W - d)]

    def rows_of(i, shape):
        x = jax.random.normal(jax.random.fold_in(key, i), shape)
        if quant:
            x = jnp.round(x * 40).astype(jnp.int8)
        return jnp.pad(x, pad[-x.ndim:])

    pools = [rows_of(i, (*lead, Hk, bp, d)) for i in (1, 2)]
    rows = [rows_of(i, (B, Hk, 1, d)) for i in (3, 4)]
    if quant:
        pools += [jax.random.uniform(jax.random.fold_in(key, i),
                                     (*lead, Hk, bp)) + 0.5 for i in (5, 6)]
        rows += [jax.random.uniform(jax.random.fold_in(key, i),
                                    (B, Hk, 1)) + 0.5 for i in (7, 8)]
    q = jax.random.normal(jax.random.fold_in(key, 9), (B, Hk, r, d))
    new = None if gate is None else (*rows, jnp.asarray(gate))
    ks, vs = pools[2:] or (None, None)
    sel = None
    if select:
        sel = jax.random.bernoulli(
            jax.random.fold_in(key, 10), 0.5, (B, nb * bp)) | (
            jnp.arange(nb * bp)[None, :] == pos[:, None])
    with _pinned_span(span):
        got = paged_decode_attention(q, *pools[:2], tables, pos, ks=ks,
                                     vs=vs, layer=jnp.int32(layer), new=new,
                                     sel=sel, interpret=True)
    want = _reference_paged_step(q, pools, tables, pos, jnp.int32(layer),
                                 new, sel=sel)
    if gate is None:
        got, want = (got,), (want,)
    return got, want, pools, tables


def _assert_step_matches(got, want):
    """The attention rows within the oracle's tolerance; the pools the
    oracle's bit for bit off junk block 0."""
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]),
                               rtol=2e-5, atol=2e-5)
    for g, w in zip(got[1:], want[1:]):
        np.testing.assert_array_equal(np.asarray(g)[:, 1:],
                                      np.asarray(w)[:, 1:])


# 20 blocks of 16 a slot, walked 8 at a time (8, 8 and 4): the first
# position, a block's last and the next block's first, a group's last and
# the next group's first, two groups' last, the table's last — over every
# position, and (a float pool) over a set
@pytest.mark.parametrize("pos", [0, 15, 16, 127, 128, 255, 319])
@pytest.mark.parametrize("quant,select", [(False, False), (True, False),
                                          (False, True)],
                         ids=["float", "int8", "set"])
@pytest.mark.parametrize("write", [False, True], ids=["read", "write"])
def test_paged_kernel_at_the_edges_of_blocks_and_groups(pos, quant, select,
                                                        write):
    got, want, _, _ = _paged_step_case(
        jax.random.PRNGKey(pos), pos=[pos, 40], nb=20, quant=quant,
        select=select, gate=[True, True] if write else None, span=128)
    _assert_step_matches(got, want)


@pytest.mark.parametrize("bp", [8, 16, 32, 128])
@pytest.mark.parametrize("quant", [False, True], ids=["float", "int8"])
@pytest.mark.parametrize("hk,r", [(2, 1), (2, 4), (4, 8), (8, 8)],
                         ids=["1", "4", "4x8", "8x8"])
def test_paged_kernel_block_lengths_and_query_rows(bp, quant, hk, r):
    """Every block length groups by the rule (`_paged_group`: 128, 64, 32
    and 8 blocks an update where these small pools' copies allow 1024
    positions, 16, 8, 4 and 1 where 16 KV heads' — or an int8 pool's
    scale blocks — allow 128), on 3 blocks a slot — fewer than a group,
    or not a multiple of one — for one query row a KV head, for four, and
    for the grouped shapes the cells serve (Keye's 4 x 8; K-EXAONE's and
    Solar's 8 x 8). The rule at the cells' own leaves:
    tests/test_paged_groups.py."""
    from dnn_tpu.ops.pallas.cached_attention import _paged_group

    nb = 3
    # what these pools hold a position: K and V rows of 16 float32 (int8
    # with a float32 scale each) a KV head
    held = 2 * hk * (16 + 4 if quant else 16 * 4)
    assert held * 512 * 2 <= 2 ** 20
    assert _paged_group(bp, nb, held) == min(1024 // bp, nb)
    assert _paged_group(bp, 1024, held) == 1024 // bp
    assert _paged_group(bp, nb, held, widest=128) == min(128 // bp, nb)
    got, want, _, _ = _paged_step_case(
        jax.random.PRNGKey(bp + r), pos=[bp * nb - 1, bp + 1, 0], bp=bp,
        nb=nb, hk=hk, r=r, quant=quant, layers=2, layer=1,
        gate=[True, True, True])
    _assert_step_matches(got, want)


@pytest.mark.parametrize("quant", [False, True], ids=["float", "int8"])
@pytest.mark.parametrize("r", [1, 4])
def test_paged_kernel_on_lane_padded_rows(quant, r):
    """64-wide heads stored 128 lanes wide (paged_kvcache.lane_padded):
    q is padded to the stored width and the result cut back."""
    got, want, _, _ = _paged_step_case(
        jax.random.PRNGKey(7), pos=[37, 5], d=64, width=128, r=r,
        quant=quant, gate=[True, False])
    assert got[0].shape[-1] == 64
    _assert_step_matches(got, want)


# a slot's group gi lies in buffer (first + gi) % 2 and the next slot's
# first group in the other one: three groups, an empty slot, two groups, an
# empty slot, one group hand the buffers on in both parities
@pytest.mark.parametrize("pos,gate", [
    ([319, 300, 21, 77], [True, False, True, False]),
    ([300, 200, 250, 100, 17], [True, False, True, False, True]),
], ids=["full-among-empty", "off-between-live"])
@pytest.mark.parametrize("layer", [0, 2], ids=["first", "last"])
@pytest.mark.parametrize("quant", [False, True], ids=["float", "int8"])
def test_paged_kernel_full_slot_among_empty_ones(layer, quant, pos, gate):
    """One slot at the table's last position, one short one, and two
    gated-off slots that kept a large stale `pos` and a stale table — the
    FULL slot's own block ids, as a retired slot's table points at blocks
    since handed to another request. The gated-off slots are empty: zeros
    out, nothing of theirs read or placed, so the pool is the oracle's
    off junk block 0 (the full slot's blocks hold exactly its own row)
    and the live slots' rows are what they are without the others. And
    gated-off slots BETWEEN live ones whose last groups end in different
    buffers."""
    nb, bp = 20, 16
    own = 1 + np.arange(len(pos) * nb).reshape(len(pos), nb)
    tables = np.stack([own[s] if on else own[0]
                       for s, on in enumerate(gate)])
    key = jax.random.PRNGKey(11)
    got, want, before, _ = _paged_step_case(
        key, pos=pos, gate=gate, nb=nb, quant=quant, layers=3, layer=layer,
        tables=tables, span=128)
    _assert_step_matches(got, want)
    out = np.asarray(got[0])
    live = [s for s, on in enumerate(gate) if on]
    assert (np.delete(out, live, 0) == 0).all() and np.isfinite(out).all()
    alone, _, _, _ = _paged_step_case(
        key, pos=pos, gate=[True] * len(pos), nb=nb, quant=quant,
        layers=3, layer=layer, tables=own, span=128)
    np.testing.assert_array_equal(out[live], np.asarray(alone[0])[live])
    for g, b in zip(got[1:], before):
        g, b = np.asarray(g), np.asarray(b)
        changed = {tuple(i[:2]) for i in np.argwhere(
            (g != b).reshape(*g.shape[:2], -1).any(-1))} - {(layer, 0)}
        assert changed == {(layer, int(tables[s, pos[s] // bp]))
                           for s in live}


def test_paged_kernel_serving_parity(tiny):
    """attn_kernel="interpret" on a paged pool runs the REAL kernel
    inside the decode loop — token-identical to the einsum pool."""
    from dnn_tpu.runtime.serving import GPTFamilyRows

    cfg, prepared = tiny
    prompt = np.arange(1, 13) % 89
    t_ein, _ = _run(cfg, prepared, prompt, kv="paged")
    fam = GPTFamilyRows(cfg, attn_kernel="interpret")
    t_ker, bk = _run(cfg, prepared, prompt, kv="paged", family=fam)
    assert bk._paged
    np.testing.assert_array_equal(t_ein, t_ker)


# ----------------------------------------------------------------------
# multi-row gated writes (the gate-folded single-scatter form)
# ----------------------------------------------------------------------

def test_rows_write_multirow_gate_keeps_inactive_rows():
    """A gated-off slot's cache must be untouched by a T>1 verify-shaped
    write (the speculative path) — the gate folds into the written rows,
    not a cache-sized select, and must not smear row 0 over T
    positions."""
    from dnn_tpu.runtime.kvcache import FloatKV

    cfg = gpt.GPTConfig(vocab_size=31, block_size=64, n_layer=1,
                        n_head=2, n_embd=32)
    codec = FloatKV()
    c = jax.tree.map(lambda x: x[0], codec.init(cfg, 2, 32))
    base_k = jax.random.normal(jax.random.PRNGKey(3), c["k"].shape)
    c = {"k": base_k, "v": base_k + 1}
    k_new = jnp.ones((2, 2, 3, 16))
    pos = jnp.asarray([4, 9], jnp.int32)
    gate = jnp.asarray([True, False])
    out = codec.write_rows(c, k_new, k_new, pos, gate)
    # active slot: rows 4..6 overwritten
    np.testing.assert_array_equal(np.asarray(out["k"][0, :, 4:7]), 1.0)
    # inactive slot: bitwise untouched everywhere
    np.testing.assert_array_equal(np.asarray(out["k"][1]),
                                  np.asarray(base_k[1]))


# ----------------------------------------------------------------------
# quantization-aware byte accounting (obs/mem + utils/flops satellite)
# ----------------------------------------------------------------------

def test_logical_nbytes_prices_packed_int4():
    from dnn_tpu.obs.mem import logical_nbytes

    f32 = {"k": jnp.zeros((4, 8), jnp.float32)}
    i8 = {"k": jnp.zeros((4, 8), jnp.int8)}
    i4 = {"k": jnp.zeros((4, 8), jnp.int4)}
    assert logical_nbytes(f32) == 128.0
    assert logical_nbytes(i8) == 32.0
    assert logical_nbytes(i4) == 16.0  # packed half byte, NOT itemsize


def test_kv_bytes_per_pos_quantized_exact():
    from dnn_tpu.utils.flops import kv_bytes_per_pos

    cfg = gpt.GPTConfig(vocab_size=31, block_size=64, n_layer=3,
                        n_head=4, n_embd=64)
    # f32 dtype: 2 leaves x L x C x 4 bytes
    assert kv_bytes_per_pos(cfg, kv_dtype=jnp.float32) == 2 * 3 * 64 * 4
    # int8: 1-byte payload + per-(position, head) f32 K and V scales
    assert kv_bytes_per_pos(cfg, kv_dtype="int8") == \
        2 * 3 * (64 * 1 + 4 * 4)
    # int4: packed half-byte payload + the same scale rows
    assert kv_bytes_per_pos(cfg, kv_dtype="int4") == \
        2 * 3 * (64 * 0.5 + 4 * 4)
    # legacy kv_bytes path unchanged
    assert kv_bytes_per_pos(cfg, kv_bytes=2) == 2 * 3 * 64 * 2


def test_kv_cache_bytes_gauge_tracks_quantization(tiny):
    cfg, prepared = tiny
    _, bf = _run(cfg, prepared, np.arange(1, 5), new_tokens=2)
    _, b4 = _run(cfg, prepared, np.arange(1, 5), new_tokens=2,
                 kv_dtype="int4")
    f32_bytes = bf._kv_bytes_read()
    i4_bytes = b4._kv_bytes_read()
    assert f32_bytes > 0
    # int4 payload is 1/8 of f32; scales push the total a bit above that
    assert i4_bytes < f32_bytes / 4
    assert "serving.kv_cache_bytes" in bf._obs_gauges
