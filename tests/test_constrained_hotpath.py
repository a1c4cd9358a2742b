"""ISSUE 16 — constrained decoding joins the interleaved/overlap hot
path: the grammar DFA walk is a DEVICE-side operation (an int32
transition-table pool next to the mask pool, the state advance folded
into the decode/mixed/fused-finish programs as donated per-slot carried
state), and the composition rejections that pinned constraints to
convoy admission are gone.

The load-bearing contracts:

  * constrained token parity: a grammar-constrained population served
    through the MIXED program (and with overlap=True on top) produces
    token streams IDENTICAL to the convoy path — greedy and sampled
    draw-for-draw, across dense/paged/bucketed pools, for requests
    admitted mid-decode, across bucket-rung crossings, and with several
    grammars resident in the pool at once;
  * EOS legality is in-program: with an eos_id configured, accept-state
    mask rows admit EOS on device and the retired body full-matches;
  * overlap ordering: the one-step pipeline's commit discipline holds
    with a constraint live, and retirement resets the slot's device DFA
    row to the unconstrained zero row;
  * prefix-cache adoption installs the correct device DFA state (the
    grammar constrains GENERATED tokens — an adopted prompt prefix
    leaves the walk at its post-first-token state);
  * speculative serving still rejects constraints LOUD (the k-token
    verify cannot gate per-token masks);
  * the transition pool evicts LRU-unreferenced entries next to the
    mask pool, and uploaded rows carry GLOBAL (offset-rebased)
    coordinates.
"""

import re as pyre

import numpy as np
import pytest

import jax

from dnn_tpu.models import gpt
from dnn_tpu.runtime.constrain import TokenConstraint, byte_vocab
from dnn_tpu.runtime.serving import ContinuousBatcher
from dnn_tpu.runtime.serving_spec import SpeculativeBatcher


@pytest.fixture(scope="module")
def model():
    cfg = gpt.GPTConfig(block_size=64, vocab_size=64, n_layer=2,
                        n_head=2, n_embd=32)
    prepared = gpt.prepare_stacked(gpt.init(jax.random.PRNGKey(0), cfg),
                                   cfg)
    return cfg, prepared


# grammars over single-byte tokens that exist in the tiny vocab
# (digits are bytes 48-57 < 64); compiled once — the pool keys by id()
VOCAB = byte_vocab(64)
DIGITS = TokenConstraint.from_regex(r"[0-9]+", VOCAB)
EVENS = TokenConstraint.from_regex(r"[02468]{3}", VOCAB)
ODDS = TokenConstraint.from_regex(r"[13579]+", VOCAB)


def _serve(cfg, prepared, submits, **kw):
    """Run a submission schedule (list of (prompt, max_new, opts,
    steps_before)) through a constrained-capable batcher; returns
    ([tokens...], batcher)."""
    kw.setdefault("slots", 3)
    kw.setdefault("constraint_rows", 16)
    srv = ContinuousBatcher(cfg, prepared, max_len=64, prompt_pad=8,
                            allow_constraints=True, **kw)
    rids = []
    for prompt, max_new, opts, steps_before in submits:
        for _ in range(steps_before):
            srv.step()
        rids.append(srv.submit(np.asarray(prompt, np.int32), max_new,
                               **opts))
    srv.drain()
    return [srv.results[r].tolist() for r in rids], srv


# greedy + sampled constrained requests, an unconstrained rider, and a
# mid-decode admission under a SECOND grammar — the population every
# parity leg below replays
SCHEDULE = [
    (range(1, 10), 8, {"seed": 0, "constraint": DIGITS}, 0),
    (range(2, 8), 8, {"seed": 1, "temperature": 0.9, "top_k": 5,
                      "constraint": DIGITS}, 0),
    # admitted mid-decode into the free third slot, a SECOND grammar
    # resident alongside; [02468]{3} retires via c_done at 3 tokens,
    # under budget — the constraint-finish on the hot path
    (range(1, 6), 6, {"seed": 2, "temperature": 1.1,
                      "constraint": EVENS}, 3),
    # unconstrained rider admitted once slots have freed (20 steps
    # covers the interleaved path's deferred-commit lag too)
    (range(3, 12), 6, {"seed": 3}, 20),
]


@pytest.mark.parametrize("pool_kw", [
    {},  # dense
    {"kv": "paged", "block_len": 8},
    {"decode_buckets": True},
])
def test_constrained_mixed_parity(model, pool_kw):
    """mixed == convoy == mixed+overlap, token for token, with the
    grammar walk live — the composition this PR lifted the rejections
    for."""
    cfg, prepared = model
    base, _ = _serve(cfg, prepared, SCHEDULE, **pool_kw)
    mixed, srv = _serve(cfg, prepared, SCHEDULE,
                        prefill_chunk_tokens=8, **pool_kw)
    assert mixed == base
    both, _ = _serve(cfg, prepared, SCHEDULE, prefill_chunk_tokens=8,
                     overlap=True, **pool_kw)
    assert both == base
    assert srv._ilv and srv._mixed is not None
    # every constrained stream full-matches its grammar
    for toks, cons in ((base[0], r"[0-9]+"), (base[1], r"[0-9]+"),
                       (base[2], r"[02468]{1,3}")):
        assert pyre.fullmatch(cons.encode(),
                              bytes(int(t) for t in toks)), toks


def test_constrained_bucket_rung_crossing(model):
    """A constrained decode that crosses bucket rungs keeps parity: the
    carried crow state survives the cache-view re-bucketing."""
    cfg, prepared = model
    # prompt 8 + 40 new tokens walks the bucketed cache across rungs
    sched = [(range(1, 9), 40,
              {"seed": 7, "temperature": 1.0, "constraint": DIGITS}, 0),
             (range(2, 7), 12, {"seed": 8, "constraint": DIGITS}, 2)]
    base, _ = _serve(cfg, prepared, sched, decode_buckets=True)
    both, _ = _serve(cfg, prepared, sched, decode_buckets=True,
                     prefill_chunk_tokens=8, overlap=True)
    assert both == base
    assert pyre.fullmatch(rb"[0-9]+", bytes(int(t) for t in base[0]))


def test_eos_at_accept_state_on_device(model):
    """EOS legality rides the mask row (dead/accept-state rows are in
    the pool): with eos configured, a sampled EOS only ever lands at an
    accepting state, and the hot path agrees with convoy exactly."""
    cfg, prepared = model
    grammar = r"[0-9]{2,6}"
    c = TokenConstraint.from_regex(grammar, VOCAB)
    sched = [(range(1, 8), 10,
              {"seed": s, "temperature": 1.0, "constraint": c}, 0)
             for s in range(3)]
    base, bsrv = _serve(cfg, prepared, sched, eos_id=0)
    both, hsrv = _serve(cfg, prepared, sched, eos_id=0,
                        prefill_chunk_tokens=8, overlap=True)
    assert both == base
    for rid in range(3):
        assert hsrv.finish_reasons[rid] == bsrv.finish_reasons[rid]
        body = bytes(int(t) for t in base[rid] if t != 0)
        assert pyre.fullmatch(grammar.encode(), body), (body, rid)
        assert hsrv.finish_reasons[rid] in ("eos", "constraint", "length")


def test_overlap_ordering_with_constraint_live(model):
    """The double buffer's one-step-pipeline contract holds with a
    grammar walking on device, and retirement resets the slot's DFA
    row on the POST-step buffer (no stale state leaks into the next
    admission)."""
    cfg, prepared = model
    kw = dict(slots=2, max_len=64, prompt_pad=8, allow_constraints=True,
              constraint_rows=16)
    srv = ContinuousBatcher(cfg, prepared, overlap=True, **kw)
    ref = ContinuousBatcher(cfg, prepared, **kw)
    r = srv.submit(np.arange(1, 10), 6, seed=0, constraint=DIGITS)
    ref.submit(np.arange(1, 10), 6, seed=0, constraint=DIGITS)
    out1 = srv.step()      # dispatches step 0, pipeline filling
    assert out1 == {}
    assert srv._inflight is not None
    out2 = srv.step()      # dispatches step 1, commits step 0
    ref1 = ref.step()
    assert out2 == ref1    # exactly step 0's tokens, one call later
    srv.drain()
    ref.drain()
    assert srv._inflight is None
    assert srv.results[r].tolist() == ref.results[0].tolist()
    # retirement landed the zero-row reset on the carried device state
    assert int(np.asarray(srv._crow)[0]) == 0


def test_prefix_cache_adoption_installs_dfa_state(model):
    """A prefix-cache hit adopts cached K/V rows but the grammar
    constrains GENERATED tokens: the device row must hold the
    post-first-token walk state, and the hit stream must equal the
    cold one."""
    cfg, prepared = model
    srv = ContinuousBatcher(cfg, prepared, slots=2, max_len=64,
                            prompt_pad=8, allow_constraints=True,
                            constraint_rows=16, prefix_cache=4)
    prompt = np.arange(1, 17)  # two full prompt_pad chunks -> cacheable
    r0 = srv.submit(prompt, 6, seed=5, constraint=DIGITS)
    srv.drain()
    hits0 = srv.prefix_hits
    r1 = srv.submit(prompt, 6, seed=5, constraint=DIGITS)
    assert srv.prefix_hits == hits0 + 1, "second submit must hit"
    slot = next(i for i, q in enumerate(srv._slot_req)
                if q is not None and q["rid"] == r1)
    req = srv._slot_req[slot]
    off = srv._ctab_entries[id(DIGITS)]["off"]
    # the device row is the GLOBAL post-first-token state of the walk
    assert int(np.asarray(srv._crow)[slot]) == off + req["c_state"]
    srv.drain()
    assert srv.results[r1].tolist() == srv.results[r0].tolist()


def test_speculative_rejection_still_loud(model):
    """The k-token verify cannot gate per-token masks: speculative
    serving keeps its LOUD construction-time rejection."""
    cfg, prepared = model
    with pytest.raises(ValueError, match="constraint"):
        SpeculativeBatcher(cfg, prepared, cfg, prepared, spec_k=2,
                           slots=2, max_len=64, prompt_pad=8,
                           allow_constraints=True)


def test_transition_pool_lru_eviction_golden(model):
    """The transition pool shares the mask pool's allocator: an
    unreferenced LRU entry is evicted to make room, rows upload in
    GLOBAL coordinates (local next-state + offset), and row 0 stays the
    all-zero unconstrained self-loop."""
    cfg, prepared = model
    # pool sized so DIGITS (2 states) + EVENS (4 states) fit but a
    # third grammar forces an eviction: 1 reserved + 7 allocatable
    srv = ContinuousBatcher(cfg, prepared, slots=2, max_len=64,
                            prompt_pad=8, allow_constraints=True,
                            constraint_rows=8)
    assert not np.asarray(srv._ctrans[0]).any(), "row 0 = self-loop"
    off_d = srv._ctab_register(DIGITS)
    off_e = srv._ctab_register(EVENS)
    # global-coordinate golden: uploaded rows == local table + offset
    want = DIGITS.trans_table(srv.eos_id) + np.int32(off_d)
    got = np.asarray(srv._ctrans[off_d:off_d + want.shape[0]])
    np.testing.assert_array_equal(got, want)
    # DIGITS retires (refs -> 0) and stays cached; EVENS stays live
    srv._ctab_release(DIGITS)
    assert srv._ctab_entries[id(DIGITS)]["refs"] == 0
    assert srv._ctab_entries[id(EVENS)]["refs"] == 1
    # ODDS (same 2-state shape) needs DIGITS' gap -> the unreferenced
    # LRU entry is evicted, the live one survives
    off_p = srv._ctab_register(ODDS)
    assert id(DIGITS) not in srv._ctab_entries
    assert id(EVENS) in srv._ctab_entries
    want_p = ODDS.trans_table(srv.eos_id) + np.int32(off_p)
    got_p = np.asarray(srv._ctrans[off_p:off_p + want_p.shape[0]])
    np.testing.assert_array_equal(got_p, want_p)
    # a live entry can NEVER be evicted: exhaust the pool while EVENS
    # and PAIRS hold references
    big = TokenConstraint.from_regex(r"[0-9]{1,5}", VOCAB)
    if big.table.shape[0] <= srv._ctab_rows - 1:
        with pytest.raises(ValueError, match="exhausted"):
            srv._ctab_register(big)


def test_constrained_slots_gauge(model):
    """The StepClock's `constrained_slots` gauge tracks live grammar
    admissions (up at submit, down at retire) — the /stepz receipt that
    constrained traffic actually rode a measured run."""
    from dnn_tpu import obs
    from dnn_tpu.obs.timeline import StepClock

    cfg, prepared = model
    was = obs.enabled()
    obs.set_enabled(True)
    try:
        srv = ContinuousBatcher(cfg, prepared, slots=2, max_len=64,
                                prompt_pad=8, allow_constraints=True,
                                constraint_rows=16)
        clock = StepClock().install()
        srv.step_clock = clock
        srv.submit(np.arange(1, 9), 4, seed=0, constraint=DIGITS)
        assert clock.constrained_slots == 1
        assert clock.summary()["constrained_slots"] == 1
        srv.submit(np.arange(2, 9), 4, seed=1)  # unconstrained: no bump
        assert clock.constrained_slots == 1
        srv.drain()
        assert clock.constrained_slots == 0
        assert "constrained_slots" in clock.render_prom()
    finally:
        obs.set_enabled(was)


# ----------------------------------------------------------------------
# ISSUE 48 — the mask pool is bit-packed and a step reads `slots` rows
# ----------------------------------------------------------------------

def _pool_rows(srv, off, n):
    """Rows [off, off + n) of the batcher's mask pool, unpacked on the
    host."""
    from dnn_tpu.runtime.constrain import unpack_mask_table

    return unpack_mask_table(np.asarray(srv._ctable[off:off + n]),
                             srv.cfg.vocab_size)


@pytest.mark.parametrize("eos_id", [None, 0], ids=["no-eos", "eos"])
def test_mask_pool_upload_release_evict_reupload(model, eos_id):
    """The packed mask pool through the allocator's whole life: a grammar
    uploaded at a non-zero offset reads back as its `mask_table`, and a
    release, an LRU eviction and a re-upload change the rows they own and
    no other grammar's — row 0 stays all ones throughout."""
    cfg, prepared = model
    srv = ContinuousBatcher(cfg, prepared, slots=2, max_len=64,
                            prompt_pad=8, allow_constraints=True,
                            constraint_rows=8, eos_id=eos_id)

    def holds(c, off):
        want = c.mask_table(srv.eos_id)
        np.testing.assert_array_equal(_pool_rows(srv, off, len(want)), want)
        assert _pool_rows(srv, 0, 1).all(), "row 0 = unconstrained"

    off_d = srv._ctab_register(DIGITS)
    off_e = srv._ctab_register(EVENS)
    assert 1 <= off_d < off_e  # EVENS sits past DIGITS: a non-zero offset
    holds(DIGITS, off_d)
    holds(EVENS, off_e)
    srv._ctab_release(DIGITS)
    holds(DIGITS, off_d)  # released, still cached
    off_o = srv._ctab_register(ODDS)  # evicts DIGITS, takes its gap
    assert id(DIGITS) not in srv._ctab_entries and off_o == off_d
    holds(ODDS, off_o)
    holds(EVENS, off_e)  # the live neighbour's rows are untouched
    srv._ctab_release(ODDS)
    off_d2 = srv._ctab_register(DIGITS)  # re-upload over ODDS' rows
    assert id(ODDS) not in srv._ctab_entries
    holds(DIGITS, off_d2)
    holds(EVENS, off_e)


def _parent_mask_rows(ctable, crow, vocab):
    """The parent's form of the read, from the same pool: the WHOLE table
    as `bool[rows, vocab]` (what the pool was before PR 48), gathered by
    row."""
    import jax.numpy as jnp

    from dnn_tpu.runtime.constrain import unpack_mask_table

    return unpack_mask_table(ctable, vocab, jnp)[crow]


@pytest.mark.parametrize("pool_kw", [
    {},
    {"kv": "paged", "block_len": 8},
    {"prefill_chunk_tokens": 8},
    {"kv": "paged", "block_len": 8, "prefill_chunk_tokens": 8,
     "overlap": True},
], ids=["dense", "paged", "mixed", "paged-mixed-overlap"])
def test_row_read_samples_the_parents_tokens(model, pool_kw, monkeypatch):
    """Constrained and unconstrained slots in one pool (two grammars, greedy
    and sampled, a mid-decode admission, a rider) sample, for fixed seeds,
    the tokens they sample when the step reads the mask as the parent did:
    a gather from the whole boolean table."""
    from dnn_tpu.runtime import serving

    cfg, prepared = model
    got, _ = _serve(cfg, prepared, SCHEDULE, **pool_kw)
    monkeypatch.setattr(serving, "_mask_rows", _parent_mask_rows)
    want, _ = _serve(cfg, prepared, SCHEDULE, **pool_kw)
    assert got == want
    assert len({tuple(t) for t in got}) == len(got)  # four streams, not one


@pytest.mark.parametrize("variant,program", [
    ("dense_constrained", "_decode"), ("paged_constrained", "_decode"),
    ("dense_constrained", "_mixed"), ("paged_constrained", "_prefill_finish"),
])
def test_step_touches_slots_rows_of_the_mask_pool(model, variant, program):
    """The HLO-level shadow of "a step reads the rows it uses": in the
    lowered `*_constrained` programs of `analysis/program.py`, no
    instruction but the parameter itself produces a value with the pool's
    row count as an extent, and the mask pool is consumed by one-row
    `dynamic_slice`s alone, one a slot (one in all for the finish) — never
    by a gather, which the chip's compiler answers by relaying out its
    whole operand (`tests/test_chip_compile.py` holds the compiled
    programs to the same at the daemon's 3600 rows)."""
    from dnn_tpu.analysis import program as prg
    from dnn_tpu.runtime.constrain import mask_words
    from tests.test_decode_hotpath import _eqns

    cfg, prepared = model
    rows, slots = 37, 3  # an extent nothing else in the program has
    kw = dict(prg.SERVING_DECODE_VARIANTS[variant], constraint_rows=rows)
    if program == "_mixed":
        kw["prefill_chunk_tokens"] = 16
    b = ContinuousBatcher(cfg, prepared, slots=slots, max_len=64,
                          prompt_pad=16, **kw)
    args = {"_decode": lambda: prg.decode_step_args(b),
            "_mixed": lambda: prg.mixed_step_args(b, 16),
            "_prefill_finish": lambda: prg.finish_args(b, 16)}[program]()
    pool = (rows, mask_words(cfg.vocab_size))
    assert b._ctable.shape == pool and b._ctrans.shape[0] == rows
    traced = getattr(b, program).trace(*args)
    reads = []
    for eqn in _eqns(traced.jaxpr.jaxpr):
        for v in eqn.outvars:
            assert rows not in v.aval.shape, (eqn.primitive.name, v.aval)
        if any(getattr(v.aval, "shape", None) == pool
               and v.aval.dtype == np.uint32 for v in eqn.invars
               if hasattr(v, "aval")):
            reads.append((eqn.primitive.name, eqn.outvars[0].aval.shape))
    want = 1 if program == "_prefill_finish" else slots
    assert reads == [("dynamic_slice", (1, pool[1]))] * want, reads
    # and in the lowered text: the pool's type appears on the signature
    # and on those slices, nowhere else
    pool_type = "tensor<%dx%dxui32>" % pool
    lines = [ln for ln in traced.lower().as_text().splitlines()
             if pool_type in ln and "func.func" not in ln]
    assert len(lines) == want
    assert all("stablehlo.dynamic_slice" in ln for ln in lines), lines
