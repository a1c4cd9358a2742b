"""ISSUE 12 — overlap & fusion: interleaved chunked prefill, the
double-buffered dispatch pipeline, fused on-device admission sampling,
and the int8-weights serving rung.

The load-bearing contracts:

  * mixed-step token parity: a batcher admitting through the MIXED
    program (prefill_chunk_tokens — chunks fold into decode steps, the
    fused finish samples the first token on device) produces token
    streams IDENTICAL to the convoy path, greedy and sampled
    draw-for-draw, across dense/paged/bucketed/speculative pools and
    for requests admitted mid-decode;
  * double-buffer ordering: overlap=True never surfaces step N+1's
    tokens before step N's commit, and drain()/flush_overlap() commit
    the trailing dispatched step;
  * fused-sampling logprob agreement: the fused finish's first-token
    logprobs match the convoy finish's exactly;
  * the analysis gate extends to the mixed-step programs: full
    donation aliasing + zero cache-sized copies on HEAD, and a
    deliberately un-aliased mixed variant FAILS the gate;
  * int8 weight serving (LMServer weights=): token parity within a
    cosine bound, and the MBU byte accounting prices the quantized
    stream (utils/flops.tree_weight_bytes).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dnn_tpu.models import gpt
from dnn_tpu.runtime.serving import ContinuousBatcher
from dnn_tpu.runtime.serving_spec import SpeculativeBatcher


@pytest.fixture(scope="module")
def model():
    cfg = gpt.GPTConfig(block_size=64, vocab_size=64, n_layer=2,
                        n_head=2, n_embd=32)
    prepared = gpt.prepare_stacked(gpt.init(jax.random.PRNGKey(0), cfg),
                                   cfg)
    return cfg, prepared


def _serve(cfg, prepared, submits, *, spec=None, **kw):
    """Run a submission schedule (list of (prompt, max_new, opts,
    steps_before)) through a batcher; returns {idx: tokens list}."""
    if spec is not None:
        srv = SpeculativeBatcher(cfg, prepared, cfg, spec, spec_k=2,
                                 slots=3, max_len=64, prompt_pad=8, **kw)
    else:
        srv = ContinuousBatcher(cfg, prepared, slots=3, max_len=64,
                                prompt_pad=8, **kw)
    rids = []
    for prompt, max_new, opts, steps_before in submits:
        for _ in range(steps_before):
            srv.step()
        rids.append(srv.submit(np.asarray(prompt, np.int32), max_new,
                               **opts))
    srv.drain()
    return [srv.results[r].tolist() for r in rids], srv


SCHEDULE = [
    (range(1, 10), 12, {"seed": 0}, 0),
    (range(2, 8), 10, {"seed": 1, "temperature": 0.9, "top_k": 5}, 0),
    # admitted mid-decode: three steps in, while others stream
    (range(3, 20), 8, {"seed": 2}, 3),
    # budget-1, admitted once a slot has freed (20 further steps covers
    # the deferred-commit lag of the interleaved path too): retires on
    # its first token without ever decoding
    (range(1, 6), 1, {"seed": 3}, 20),
]


@pytest.mark.parametrize("pool_kw", [
    {},  # dense
    {"kv": "paged", "block_len": 8},
    {"decode_buckets": True},
])
def test_mixed_step_token_parity(model, pool_kw):
    cfg, prepared = model
    base, _ = _serve(cfg, prepared, SCHEDULE, **pool_kw)
    mixed, srv = _serve(cfg, prepared, SCHEDULE,
                        prefill_chunk_tokens=8, **pool_kw)
    assert mixed == base
    both, _ = _serve(cfg, prepared, SCHEDULE, prefill_chunk_tokens=8,
                     overlap=True, **pool_kw)
    assert both == base
    # the interleave actually engaged (pendings flowed through steps)
    assert srv._ilv and srv._mixed is not None


def test_mixed_step_sampled_draw_for_draw(model):
    """Fused on-device admission sampling == the convoy finish,
    draw-for-draw: same per-request rng streams, same filter math."""
    cfg, prepared = model
    sched = [
        (range(1, 12), 9,
         {"seed": 11, "temperature": 0.8, "top_p": 0.9,
          "repetition_penalty": 1.3}, 0),
        (range(4, 9), 7,
         {"seed": 12, "temperature": 1.1, "min_p": 0.05}, 2),
    ]
    base, _ = _serve(cfg, prepared, sched)
    mixed, _ = _serve(cfg, prepared, sched, prefill_chunk_tokens=8)
    assert mixed == base
    both, _ = _serve(cfg, prepared, sched, prefill_chunk_tokens=8,
                     overlap=True)
    assert both == base


def test_multi_chunk_interleaved_prompt(model):
    """A prompt spanning several interleave chunks folds chunk-by-chunk
    across consecutive steps and still matches the convoy stream."""
    cfg, prepared = model
    sched = [(range(1, 30), 10, {"seed": 4}, 0),
             (range(2, 25), 8, {"seed": 5}, 1)]
    base, _ = _serve(cfg, prepared, sched)
    mixed, _ = _serve(cfg, prepared, sched, prefill_chunk_tokens=8)
    assert mixed == base


def test_overlap_ordering_one_step_pipeline(model):
    """The double buffer's contract: step() call N returns step N-1's
    tokens — no step N+1 result is ever consumed before step N's
    commit — and flush_overlap()/drain() commit the trailing step."""
    cfg, prepared = model
    srv = ContinuousBatcher(cfg, prepared, slots=2, max_len=64,
                            prompt_pad=8, overlap=True)
    ref = ContinuousBatcher(cfg, prepared, slots=2, max_len=64,
                            prompt_pad=8)
    r = srv.submit(np.arange(1, 10), 6, seed=0)
    ref.submit(np.arange(1, 10), 6, seed=0)
    out1 = srv.step()      # dispatches step 0, pipeline filling
    assert out1 == {}
    assert srv._inflight is not None
    out2 = srv.step()      # dispatches step 1, commits step 0
    ref1 = ref.step()
    assert out2 == ref1    # exactly step 0's tokens, one call later
    # drain commits everything, including the trailing in-flight step
    srv.drain()
    ref.drain()
    assert srv._inflight is None
    assert srv.results[r].tolist() == ref.results[0].tolist()
    # an idle flush on a drained pool is a no-op
    assert srv.flush_overlap() == {}


def test_overlap_streams_match_and_flush_idempotent(model):
    cfg, prepared = model
    srv = ContinuousBatcher(cfg, prepared, slots=2, max_len=64,
                            prompt_pad=8, overlap=True,
                            prefill_chunk_tokens=8)
    r = srv.submit(np.arange(1, 10), 4, seed=0)
    seen = []
    while srv.n_active:
        out = srv.step()
        for t in out.values():
            seen.extend(t if isinstance(t, list) else [t])
    out = srv.flush_overlap()
    for t in out.values():
        seen.extend(t if isinstance(t, list) else [t])
    assert seen == srv.results[r].tolist()


def test_spec_mixed_parity(model):
    cfg, prepared = model
    draft = gpt.prepare_stacked(gpt.init(jax.random.PRNGKey(7), cfg),
                                cfg)
    sched = [(range(1, 10), 12, {"seed": 0}, 0),
             (range(3, 14), 9, {"seed": 2}, 2)]
    plain, _ = _serve(cfg, prepared, sched)
    spec_base, _ = _serve(cfg, prepared, sched, spec=draft)
    assert spec_base == plain  # greedy spec == plain batcher (standing)
    spec_ilv, srv = _serve(cfg, prepared, sched, spec=draft,
                           prefill_chunk_tokens=8)
    assert spec_ilv == plain
    assert srv._spec_mixed is not None
    spec_both, _ = _serve(cfg, prepared, sched, spec=draft,
                          prefill_chunk_tokens=8, overlap=True)
    assert spec_both == plain
    # sampled spec: mixed vs convoy draw-for-draw (server-level params)
    s_sched = [(range(1, 10), 8, {"seed": 5}, 0)]
    kw = {"temperature": 0.8, "top_k": 8}
    s_base, _ = _serve(cfg, prepared, s_sched, spec=draft, **kw)
    s_ilv, _ = _serve(cfg, prepared, s_sched, spec=draft,
                      prefill_chunk_tokens=8, overlap=True, **kw)
    assert s_ilv == s_base


def test_spec_bucketed_mixed_parity(model):
    cfg, prepared = model
    draft = gpt.prepare_stacked(gpt.init(jax.random.PRNGKey(7), cfg),
                                cfg)
    sched = [(range(1, 10), 26, {"seed": 0}, 0)]
    base, _ = _serve(cfg, prepared, sched, spec=draft,
                     decode_buckets=True)
    mixed, _ = _serve(cfg, prepared, sched, spec=draft,
                      decode_buckets=True, prefill_chunk_tokens=8,
                      overlap=True)
    assert mixed == base


def test_fused_sampling_logprob_agreement(model):
    """The fused finish's first-token logprob record (chosen + top-k)
    agrees exactly with the convoy finish's, and the per-step records
    ride the deferred commit unchanged."""
    cfg, prepared = model

    def lp_run(**kw):
        srv = ContinuousBatcher(cfg, prepared, slots=2, max_len=64,
                                prompt_pad=8, logprobs_k=3, **kw)
        r = srv.submit(np.arange(1, 10), 6, seed=0, logprobs=True)
        srv.drain()
        lp = srv.token_logprobs[r]
        return (srv.results[r].tolist(), lp["chosen"].tolist(),
                lp["top_ids"].tolist(), lp["top_logprobs"].tolist())

    base = lp_run()
    assert lp_run(prefill_chunk_tokens=8) == base
    assert lp_run(prefill_chunk_tokens=8, overlap=True) == base


def test_eos_on_deferred_first_token(model):
    """A request whose FIRST token is eos (forced via logit bias)
    retires correctly off the deferred commit, discarding the lagged
    decode token."""
    cfg, prepared = model

    def run(**kw):
        srv = ContinuousBatcher(cfg, prepared, slots=2, max_len=64,
                                prompt_pad=8, eos_id=5,
                                allow_logit_bias=True, **kw)
        r = srv.submit(np.arange(1, 10), 8, seed=0,
                       logit_bias={5: 1e9})
        srv.drain()
        return srv.results[r].tolist(), srv.finish_reasons[r]

    base = run()
    assert base[1] == "eos"
    assert run(prefill_chunk_tokens=8) == base
    assert run(prefill_chunk_tokens=8, overlap=True) == base


def test_interleave_validations(model):
    cfg, prepared = model
    with pytest.raises(ValueError, match="prefix cache"):
        ContinuousBatcher(cfg, prepared, slots=2, max_len=64,
                          prompt_pad=8, prefill_chunk_tokens=8,
                          prefix_cache=4)
    with pytest.raises(ValueError, match="block_len"):
        ContinuousBatcher(cfg, prepared, slots=2, max_len=64,
                          prompt_pad=8, kv="paged", block_len=8,
                          prefill_chunk_tokens=12)
    with pytest.raises(ValueError, match="max_len"):
        ContinuousBatcher(cfg, prepared, slots=2, max_len=64,
                          prompt_pad=8, prefill_chunk_tokens=128)


def test_cancel_pending_interleaved_request(model):
    """Cancelling a request whose prefill is still queued frees its
    slot (and paged blocks) without a step ever running it."""
    cfg, prepared = model
    srv = ContinuousBatcher(cfg, prepared, slots=2, max_len=64,
                            prompt_pad=8, kv="paged", block_len=8,
                            prefill_chunk_tokens=8)
    used0 = srv._allocator.n_used
    rid = srv.submit(np.arange(1, 10), 6, seed=0)
    assert srv._pending_q
    assert srv.cancel(rid)
    assert not srv._pending_q
    assert srv._allocator.n_used == used0
    assert srv.n_active == 0
    # the pool still serves cleanly afterwards
    r2 = srv.submit(np.arange(1, 10), 4, seed=1)
    srv.drain()
    assert len(srv.results[r2]) == 4


def test_audit_covers_mixed_step_programs():
    """audit_serving_decode extends to the mixed-step programs: every
    donated leaf aliased, zero cache-sized copies, on HEAD."""
    from dnn_tpu.analysis.program import audit_serving_decode

    rep = audit_serving_decode()
    names = set(rep["variants"])
    for want in ("mixed_dense", "mixed_dense_finish", "mixed_paged",
                 "mixed_bucketed", "mixed_speculative",
                 "mixed_speculative_finish", "mixed_dense_chunk",
                 "paged_chunk", "paged_keye_chunk", "mellum2-test_chunk"):
        assert want in names, names
        v = rep["variants"][want]
        assert v["aliased"] == v["expected"], (want, v)
        assert v["cache_sized_ops"] == {}, (want, v)
    assert rep["findings"] == []


def test_audit_gate_fails_unaliased_mixed_variant(model):
    """The gate actually gates: the REAL mixed-step program re-jitted
    WITHOUT donation fails the donation-coverage check."""
    from dnn_tpu.analysis.program import (
        check_decode_program,
        mixed_step_args,
    )

    cfg, prepared = model
    b = ContinuousBatcher(cfg, prepared, slots=2, max_len=64,
                          prompt_pad=8, prefill_chunk_tokens=8)
    # the tuple the real audit lowers with: a signature change reaches
    # this test through it
    args = mixed_step_args(b, 8)
    elems = 2 * cfg.n_head * 64 * (cfg.n_embd // cfg.n_head)
    # HEAD's program passes...
    _, ok_findings = check_decode_program(
        "mixed_ok", b._mixed, args, b._mixed_donate, elems)
    assert ok_findings == []
    # ...the same function jitted with its donations dropped FAILS
    bad = jax.jit(b._mixed.__wrapped__)
    entry, findings = check_decode_program(
        "mixed_unaliased", bad, args, b._mixed_donate, elems)
    assert entry["aliased"] == 0
    assert findings and findings[0].rule == "PRG003"


def test_audit_gate_fails_a_chunk_loop_that_rides_its_row(model,
                                                          monkeypatch):
    """The chunk gate gates (ISSUE 63): HEAD's chunk program — the row
    carried, a chunk's positions written — passes; the SAME batcher's
    program traced with the row riding its layer loop as xs in and ys out
    (the form before: each layer's whole cut-out written back) trips it;
    and so does the real program with its donation dropped."""
    from dnn_tpu.analysis.program import check_chunk_program, chunk_args
    from dnn_tpu.runtime import generate
    from tests.test_chunk_rows_in_place import xs_ys_scan_rows

    cfg, prepared = model
    b = ContinuousBatcher(cfg, prepared, slots=2, max_len=64, prompt_pad=8)
    args = chunk_args(b)
    entry, ok_findings = check_chunk_program("chunk_ok", b._prefill_chunk,
                                             args)
    assert ok_findings == [] and entry["aliased"] == 2
    bad = jax.jit(b._prefill_chunk.__wrapped__)
    entry, findings = check_chunk_program("chunk_unaliased", bad, args)
    assert entry["aliased"] == 0
    assert findings and findings[0].rule == "PRG003"
    monkeypatch.setattr(generate, "scan_rows", xs_ys_scan_rows)
    rides = jax.jit(lambda *a: b._prefill_chunk.__wrapped__(*a),
                    donate_argnums=(1,))  # a new function: a new trace
    entry, findings = check_chunk_program("chunk_xs_ys", rides, args)
    assert entry["aliased"] == 2  # the donation is there: the copies hide
    assert entry["cache_sized_ops"].get("dynamic_update_slice", 0) >= 2
    assert findings and findings[0].rule == "PRG003"


def test_int8_weights_serving_parity_and_bytes(model):
    """The weight-quant rung: int8 weights through the serving decode
    path stay token-parity-close (cosine-bound logits; identical
    greedy streams at this scale), and the byte accounting prices the
    quantized stream correctly."""
    from dnn_tpu.obs.goodput import model_cost
    from dnn_tpu.quant import quantize_gpt
    from dnn_tpu.utils.flops import tree_weight_bytes

    cfg, prepared = model
    q = quantize_gpt(prepared, bits=8)

    f_bytes = tree_weight_bytes(prepared)
    q_bytes = tree_weight_bytes(q)
    assert q_bytes < 0.55 * f_bytes  # kernels 4x down, embeddings f32
    # goodput's MBU denominator follows the served tree exactly
    assert model_cost(cfg, q).weight_bytes == pytest.approx(q_bytes)
    assert model_cost(cfg, prepared).weight_bytes == \
        pytest.approx(f_bytes)

    # serving parity: same pool, quantized weights — logits cosine
    # bound, greedy token stream identical at this model scale
    def logits_and_tokens(tree):
        srv = ContinuousBatcher(cfg, tree, slots=2, max_len=64,
                                prompt_pad=8, logprobs_k=4)
        r = srv.submit(np.arange(1, 12), 8, seed=0, logprobs=True)
        srv.drain()
        lp = srv.token_logprobs[r]
        return srv.results[r], lp["chosen"]

    toks_f, lp_f = logits_and_tokens(prepared)
    toks_q, lp_q = logits_and_tokens(q)
    assert toks_q.tolist() == toks_f.tolist()
    # chosen-logprob agreement as the scalar parity bound
    assert float(np.max(np.abs(lp_f - lp_q))) < 0.15


def test_int4_packed_weight_pricing():
    """int4 leaves price at the packed half byte + their scale rows —
    the itemsize walk would read 2x."""
    from dnn_tpu.quant import quantize_tensor_int4
    from dnn_tpu.utils.flops import tree_weight_bytes

    w = jnp.ones((64, 32), jnp.float32)
    q, scale = quantize_tensor_int4(w, group=64)
    got = tree_weight_bytes({"q": q, "scale": scale})
    assert got == pytest.approx(64 * 32 * 0.5 + scale.size * 4)


def test_stepclock_mixed_tag_and_overlap_depth(model):
    """StepClock satellites: interleaved steps carry the `mixed` tag
    (records + summary + prom), and the overlap_depth gauge reports
    the producer's pipeline depth."""
    from dnn_tpu import obs
    from dnn_tpu.obs.timeline import StepClock

    cfg, prepared = model
    was = obs.enabled()
    obs.set_enabled(True)
    try:
        srv = ContinuousBatcher(cfg, prepared, slots=2, max_len=64,
                                prompt_pad=8, prefill_chunk_tokens=8,
                                overlap=True)
        clock = StepClock()
        srv.step_clock = clock
        srv.submit(np.arange(1, 10), 6, seed=0)
        srv.drain()
        recs = clock.records()
        assert any(r["mixed"] for r in recs)
        assert any(not r["mixed"] for r in recs)
        s = clock.summary()
        assert s["mixed_steps"] >= 1
        assert 0 < s["mixed_frac"] <= 1
        assert s["overlap_depth"] == 1
        prom = clock.render_prom()
        assert "dnn_tpu_step_mixed_steps" in prom
        assert "dnn_tpu_step_overlap_depth 1" in prom
    finally:
        obs.set_enabled(was)


def test_worker_streams_interleaved_and_overlap_tokens(model):
    """The lm_server worker serves interleaved admissions end to end:
    the deferred first token streams through on_token, the future
    resolves with the full budget, and the overlap idle-flush keeps
    the trailing step from dangling."""
    from dnn_tpu.runtime.lm_server import _BatcherWorker

    cfg, prepared = model
    srv = ContinuousBatcher(cfg, prepared, slots=2, max_len=64,
                            prompt_pad=8, prefill_chunk_tokens=8,
                            overlap=True)
    w = _BatcherWorker(srv)
    w.start()
    try:
        streamed = []
        fut = w.submit(np.arange(1, 10, dtype=np.int32), 6, None,
                       on_token=streamed.append)
        out = fut.result(timeout=120)
        assert len(out) == 6
        assert streamed == list(out)
        # idle worker flushed the trailing overlap step
        deadline = 50
        while srv._inflight is not None and deadline:
            import time as _t

            _t.sleep(0.1)
            deadline -= 1
        assert srv._inflight is None
    finally:
        w.stop()
        w.join(timeout=10)


# ----------------------------------------------------------------------
# ISSUE 45 — the pipeline is the daemon's step loop for every family
# ----------------------------------------------------------------------

#: one test-size model of each family the daemon serves: K/V (GPT-2),
#: expert layers (OLMoE), an index-key leaf (Keye's `DsaFamilyRows`), one
#: latent leaf (JoyAI's `MlaFamilyRows`), two latent kinds with a window
#: kind (dots3's), K/V kinds with a window (K-EXAONE's), a kind with no
#: position axis beside K and V (Solar-Open2's state), and no K/V layer at
#: all (Brumby's state: nothing paged, no allocator), ONE kind with
#: paged K and V AND a state a slot in every layer (Falcon-H1's), and a
#: STRIDED leaf beside K and V read by lists of blocks, with a state kind of
#: one leaf (MiniCPM-SALA's), and blocks of ONE mixer whose expert blocks
#: keep nothing (Nemotron-H's)
FAMILIES = ["gpt2-test", "olmoe-test", "keye-test", "joyai-test",
            "dots3-test", "k-exaone-test", "solar-open2-test", "brumby-test",
            "falcon-h1-test", "minicpm-sala-test", "nemotron-h-test"]
_BUILT: dict = {}


def _family(name):
    """(cfg, prepared, family factory or None), built once a module."""
    if name not in _BUILT:
        from dnn_tpu.registry import get_model

        spec = get_model(name)
        cfg = spec.config
        params = spec.init(jax.random.PRNGKey(3))
        _BUILT[name] = (cfg, gpt.prepare_stacked(dict(params), cfg),
                        (spec.extras or {}).get("family_rows"))
    return _BUILT[name]


def _family_batcher(name, **kw):
    cfg, prepared, rows = _family(name)
    opts = dict(slots=3, max_len=64, prompt_pad=16, kv="paged", block_len=8)
    if rows is not None:
        opts["family"] = rows()
        kinds = getattr(opts["family"], "cache_kinds", None)
        if kinds and not any(k["tables"] for k in kinds.values()):
            opts["kv"] = "auto"  # state leaves alone: nothing to page
    opts.update(kw)
    return ContinuousBatcher(cfg, prepared, **opts)


def _prompt(n, seed):
    return np.asarray(jax.random.randint(
        jax.random.PRNGKey(seed), (n,), 1, 256), np.int32)


#: every second request of `_script` samples, the others are greedy
_SAMPLED = {"temperature": 0.9, "top_k": 12, "repetition_penalty": 1.2}


def _script(srv):
    """Admissions, retirements and a cancel between step() calls — under
    the pipeline each lands while a step is in flight: three requests
    fill the pool, one joins mid-decode after a CANCEL freed its slot
    (the in-flight step still holds the cancelled row), one more when a
    retirement frees a slot (admitted under the retired row's stale
    step). Greedy and sampled requests side by side. Returns every
    finished request's tokens by submission order."""
    rids = [srv.submit(_prompt(9, 1), 14, seed=0),
            srv.submit(_prompt(20, 2), 4, seed=1, **_SAMPLED),
            srv.submit(_prompt(12, 3), 30, seed=2)]
    for _ in range(3):
        srv.step()
    assert srv.cancel(rids[0])
    rids.append(srv.submit(_prompt(17, 4), 9, seed=3, **_SAMPLED))
    while not srv.free_slots():
        srv.step()
    rids.append(srv.submit(_prompt(5, 5), 11, seed=4, **_SAMPLED))
    srv.drain()
    return [srv.results[r].tolist() for r in rids[1:]]


@pytest.mark.parametrize("name", FAMILIES)
def test_pipeline_equals_synchronous_loop_for_every_family(name):
    """Token streams of the pipeline equal the synchronous loop's, greedy
    and sampled draw for draw, with admissions, retirements and a cancel
    landing while a step is in flight; the stale rows are counted and
    every block comes back."""
    base = _script(_family_batcher(name))
    srv = _family_batcher(name, overlap=True)
    free = [a.n_free for a in _allocators(srv)]
    assert _script(srv) == base
    assert [len(t) for t in base] == [4, 30, 9, 11]
    # one row the cancel left in flight, one a retirement (the last three
    # retire into an emptying pool: their stale rows count too)
    assert srv.steps_pipelined >= 25 and 2 <= srv.stale_rows <= 5
    assert srv._inflight is None
    assert [a.n_free for a in _allocators(srv)] == free


def _allocators(srv):
    """The pool's allocator and each window kind's (none at all where
    nothing is paged)."""
    if srv._allocator is None:
        return []
    return [srv._allocator, *(srv._allocator.of(t)
                              for t in srv._window_kinds)]


def _window_batcher(name, **kw):
    if name == "llama-window":  # a whole-family window: `_paged_window`
        from dnn_tpu.models import llama

        cfg = llama.LlamaConfig(block_size=64, vocab_size=256, n_layer=2,
                                n_head=2, n_kv_head=1, n_embd=32,
                                sliding_window=9)
        if name not in _BUILT:
            _BUILT[name] = gpt.prepare_stacked(
                llama.init(jax.random.PRNGKey(5), cfg), cfg)
        return ContinuousBatcher(
            cfg, _BUILT[name], slots=2, max_len=64, prompt_pad=8,
            kv="paged", block_len=8, family=llama.LlamaFamilyRows(cfg), **kw)
    return _family_batcher(name, slots=2, **kw)


def _watch_dispatches(srv, slot=0):
    """Record, at every decode dispatch, the slot's row of each table the
    program is handed and the slot's position."""
    seen, real = [], srv._decode

    def decode(view, cache, pos, *rest):
        seen.append(({t: np.asarray(cache[t])[:, slot].copy()
                      for t in cache if t.startswith("tables")},
                     int(np.asarray(pos)[slot])))
        return real(view, cache, pos, *rest)

    srv._decode = decode
    return seen


@pytest.mark.parametrize("name", ["dots3-test", "k-exaone-test",
                                  "llama-window"])
def test_window_blocks_under_the_pipeline_are_the_synchronous_loops(name):
    """A slot decoded to more than 3x its window: every dispatch of the
    pipeline reads and writes, inside each kind's band, the very blocks
    the synchronous loop's dispatch at that position used (its tables are
    one roll older: what differs lies outside the band), the tables after
    each commit are equal, and so are the allocators at the end."""
    ref, srv = _window_batcher(name), _window_batcher(name, overlap=True)
    windows = dict(srv._window_kinds)
    if srv._paged_window is not None:
        windows["tables"] = srv._paged_window
    assert windows and max(windows.values()) <= 9
    bp, n_new = srv._block_len, 40
    seen_ref, seen_srv = _watch_dispatches(ref), _watch_dispatches(srv)
    for b in (ref, srv):
        b.submit(_prompt(11, 7), n_new, seed=0)
    assert srv.step() == {}  # fills the pipeline
    n = 0
    while srv.n_active:
        assert srv.step() == ref.step()
        n += 1
        for t in srv.cache:
            if t.startswith("tables"):
                np.testing.assert_array_equal(
                    np.asarray(srv.cache[t]), np.asarray(ref.cache[t]))
        if srv._slot_req[0] is not None:
            assert srv._slot_req[0].get("wblocks") == \
                ref._slot_req[0].get("wblocks")
            assert srv._slot_req[0]["freed"] == ref._slot_req[0]["freed"]
    assert n == n_new - 1 >= 3 * max(windows.values())
    assert srv.results[0].tolist() == ref.results[0].tolist()
    assert srv.flush_overlap() == {}
    # the pipeline dispatched one step more: the stale one
    assert len(seen_srv) == len(seen_ref) + 1 and srv.stale_rows == 1
    for (tab_s, pos_s), (tab_r, pos_r) in zip(seen_srv, seen_ref):
        assert pos_s == pos_r
        for t, w in windows.items():
            band = slice(max(0, pos_s - w + 1) // bp, pos_s // bp + 1)
            np.testing.assert_array_equal(tab_s[t][:, band],
                                          tab_r[t][:, band])
            assert (tab_s[t][:, band] > 0).all()  # none of it the junk block
    assert [a.n_free for a in _allocators(srv)] == \
        [a.n_free for a in _allocators(ref)]
    if srv._window_kinds:
        assert srv.window_blocks_freed == ref.window_blocks_freed > 0


@pytest.mark.parametrize("name", ["gpt2-test", "dots3-test",
                                  "k-exaone-test", "solar-open2-test"])
def test_stale_step_at_max_len_writes_no_live_block(name):
    """A request that ends at `max_len` with its last allocated position
    unwritten: the stale step dispatched past its retirement stands at
    that position (never past the table) and writes a block of the
    RETIRED request alone, or the junk block — never one a live request
    holds — and the request admitted into those blocks next reads what
    the synchronous loop's does."""
    ref, srv = (_family_batcher(name, slots=2),
                _family_batcher(name, slots=2, overlap=True))
    seen = _watch_dispatches(srv, slot=1)
    max_len, bp = srv.max_len, srv._block_len
    outs = []
    for b in (ref, srv):
        other = b.submit(_prompt(6, 9), 40, seed=5)  # a live neighbour
        last = b.submit(_prompt(max_len - 12, 8), 12, seed=6)
        held = {t: set(np.asarray(b.cache[t])[0, 1].tolist()) - {0}
                for t in b.cache if t.startswith("tables")}
        while last not in b.results:
            b.step()
        live = {t: set(np.asarray(b.cache[t])[0, 0].tolist()) - {0}
                for t in b.cache if t.startswith("tables")}
        again = b.submit(_prompt(max_len - 30, 10), 6, seed=7)
        b.drain()
        outs.append([b.results[r].tolist() for r in (other, last, again)])
    assert outs[0] == outs[1]
    # the stale dispatch: the last one that held slot 1's request
    tabs, pos = [s for s in seen if s[1] == max_len - 1][0]
    assert pos == max_len - 1
    for t, row in tabs.items():
        target = int(row[0, pos // bp])
        assert target not in live[t]
        if t not in srv._window_kinds:  # (a window kind's blocks change
            # hands while a request runs: `held` is its admission's)
            assert target == 0 or target in held[t]


def test_the_daemon_pipelines_by_default(model):
    """An `LMServer` over a dense batcher runs the pipeline, whatever its
    caller left unsaid — `/statusz` says so and why, `/metrics` counts it
    — its worker streams through it, and the idle worker commits the
    trailing step; a speculative daemon (its batcher's own default) and a
    batcher built directly keep the synchronous loop."""
    import time as _t

    from dnn_tpu import obs
    from dnn_tpu.runtime.lm_server import LMServer

    cfg, prepared = model
    assert not ContinuousBatcher(cfg, prepared, slots=2, max_len=64,
                                 prompt_pad=8)._overlap
    ref = ContinuousBatcher(cfg, prepared, slots=2, max_len=64,
                            prompt_pad=8, seed=0)
    want = [ref.submit(np.arange(1, 10, dtype=np.int32), 9, seed=4),
            ref.submit(np.arange(3, 9, dtype=np.int32), 5, seed=5)]
    ref.drain()
    srv = LMServer(cfg, prepared, slots=2, max_len=64, prompt_pad=8, seed=0)
    try:
        b = srv.batcher
        assert b._overlap and b.step_loop()["loop"] == "pipelined"
        streamed = []
        futs = [srv.worker.submit(np.arange(1, 10, dtype=np.int32), 9, 4,
                                  on_token=streamed.append),
                srv.worker.submit(np.arange(3, 9, dtype=np.int32), 5, 5)]
        outs = [f.result(timeout=120) for f in futs]
        assert [o.tolist() for o in outs] == \
            [ref.results[r].tolist() for r in want]
        assert streamed == outs[0].tolist()
        assert b.steps_pipelined >= 7
        for _ in range(100):  # the idle worker commits the trailing step
            if b._inflight is None and b.stale_rows == 2:
                break  # (a row a retirement: the flush counts the last)
            _t.sleep(0.05)
        assert b._inflight is None and b.stale_rows == 2
        comp = srv._statusz()["components"]["batcher"]
        assert comp["loop"] == "pipelined" and comp["depth"] == 1
        assert "LMServer asks of a dense batcher" in comp["why"]
        assert comp["steps_pipelined"] == b.steps_pipelined
        if obs.enabled():
            from dnn_tpu.utils.metrics import render_prometheus

            page = render_prometheus(obs.metrics())
            assert f"step_pipelined_total {b.steps_pipelined}\n" in page
            assert f"step_stale_rows_total {b.stale_rows}\n" in page
    finally:
        srv.close()
    spec = LMServer(cfg, prepared, draft_cfg=cfg, draft_prepared=prepared,
                    spec_k=2, slots=2, max_len=64, prompt_pad=8)
    try:
        assert not spec.batcher._overlap
        comp = spec._statusz()["components"]["batcher"]
        assert comp["loop"] == "synchronous"
        assert "speculative one, which LMServer leaves" in comp["why"]
    finally:
        spec.close()


@pytest.mark.parametrize("name", ["keye-test", "joyai-test", "dots3-test",
                                  "k-exaone-test", "solar-open2-test",
                                  "brumby-test", "falcon-h1-test",
                                  "minicpm-sala-test", "nemotron-h-test"])
def test_interleaved_admission_stays_refused_by_name(name):
    """A family that lives in the paged pool alone still refuses the
    mixed step, by name, with or without the pipeline — which it takes."""
    for kw in ({"prefill_chunk_tokens": 8},
               {"prefill_chunk_tokens": 8, "overlap": True}):
        with pytest.raises(ValueError, match="interleaved prefill"):
            _family_batcher(name, **kw)
    assert _family_batcher(name, overlap=True)._overlap
