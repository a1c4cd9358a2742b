"""ISSUE 67: a prompt's chunks at the width the chip wants — a batcher that
is asked for no `prompt_pad` takes the chip's ridge (`serving.ridge_pad`),
so the ONE chunk program holds as many tokens as its weight stream pays
for, on every admit path.

The ridge exists only where the device states its peaks, so every case here
reaches it by patching the two peaks in `utils/flops` while a batcher is
built, never by an option:

  * `ridge_pad` is the ridge rounded up to a power of two, inside `max_len`
    and the model's positions, and nothing off the TPU;
  * who chooses: an explicit `prompt_pad` wins, the family's compute dtype
    gives the itemsize, and without the peaks the batcher is what it was;
  * the ridge-wide batcher against a `prompt_pad` 8 one on the SAME weights
    in float32: the installed rows agree to rounding and greedy streams are
    equal on and around every boundary, for a GPT, a LLaMA and a state
    family's test preset (`n_real` of a wide launch), and on the prefix
    cache's, the radix store's, the interleaved and the speculative paths;
  * after ONE admission no prompt length compiles or traces;
  * what `/metrics` and the `admit.prefill` span say of it.
"""

import functools

import jax
import numpy as np
import pytest

from dnn_tpu import obs
from dnn_tpu.models.gpt import prepare_stacked
from dnn_tpu.obs import profile
from dnn_tpu.registry import get_model
from dnn_tpu.runtime.serving import ContinuousBatcher, ridge_pad
from dnn_tpu.utils import flops

NARROW, RIDGE, MAX_LEN, NEW = 8, 32, 64, 3
PRESETS = ("gpt2-test", "llama-test", "falcon-h1-test")
#: on and around every chunk boundary of both pads, and the longest the row
#: takes
LENGTHS = (1, NARROW - 1, NARROW, NARROW + 1, RIDGE - 1, RIDGE, RIDGE + 1,
           RIDGE + NARROW, RIDGE + NARROW + 1, MAX_LEN - NEW)


def peaks(monkeypatch, flops_per_byte=12.0):
    """A device whose ridge for float32 weights is `2 x flops_per_byte`
    tokens: 24, which rounds up to RIDGE."""
    monkeypatch.setattr(flops, "device_peak_flops",
                        lambda device=None: flops_per_byte * 1e9)
    monkeypatch.setattr(flops, "device_peak_hbm_bw",
                        lambda device=None: 1e9)


@functools.cache
def model(preset):
    spec = get_model(preset)
    return spec, prepare_stacked(
        dict(spec.init(jax.random.PRNGKey(5))), spec.config)


def build(preset, prompt_pad, cls=ContinuousBatcher, **kw):
    """The preset's batcher on a paged float32 pool: at `prompt_pad`, or —
    asked for none — on a device whose ridge is RIDGE."""
    spec, prepared = model(preset)
    opts = dict(slots=2, max_len=MAX_LEN, prompt_pad=prompt_pad, kv="paged",
                block_len=8)
    if "family_rows" in spec.extras:
        opts["family"] = spec.extras["family_rows"]()
    opts.update(kw)
    with pytest.MonkeyPatch.context() as m:
        if prompt_pad is None:
            peaks(m)
        return cls(spec.config, prepared, **opts)


def prompt(n, seed=1):
    """On the host: a prompt's length must compile nothing."""
    return np.random.default_rng(seed).integers(1, 256, n, dtype=np.int32)


# --------------------------------------------------------------------------
# the reckoning, and who chooses
# --------------------------------------------------------------------------

@pytest.mark.parametrize("itemsize,max_len,positions,pad", [
    (2, 1024, 1024, 256),  # GPT-2 Large's cells: 241 tokens for bfloat16
    (4, 1024, 1024, 512), (1, 1024, 1024, 128),
    (2, 96, 1024, 96),       # a short pool is one chunk
    (2, 1000, 1024, 256),    # the row rounds up to 1024: inside the table
    (2, 1000, 1000, None)])  # and here past it
def test_ridge_pad_on_a_v5e(monkeypatch, itemsize, max_len, positions, pad):
    monkeypatch.setattr(flops, "device_peak_flops", lambda d=None: 197e12)
    monkeypatch.setattr(flops, "device_peak_hbm_bw", lambda d=None: 819e9)
    assert ridge_pad(itemsize, max_len, positions) == pad


def test_no_ridge_without_peaks():
    assert flops.device_peak_flops() is None  # the CPU states none
    assert ridge_pad(2, 1024, 1024) is None


def test_who_chooses_the_pad():
    """Asked for none the batcher takes the ridge of its compute dtype; an
    explicit `prompt_pad` wins; without the peaks it is min(64, max_len) as
    it was."""
    import jax.numpy as jnp

    spec, prepared = model("gpt2-test")
    assert spec.config.block_size == MAX_LEN
    ridge = build("gpt2-test", None)
    assert (ridge.prompt_pad, ridge._row_len) == (RIDGE, MAX_LEN)
    assert build("gpt2-test", None, kv="dense", compute_dtype=jnp.bfloat16
                 ).prompt_pad == RIDGE // 2
    assert build("llama-test", None).prompt_pad == RIDGE  # the family's
    with pytest.MonkeyPatch.context() as m:
        peaks(m)
        assert ContinuousBatcher(spec.config, prepared, max_len=MAX_LEN,
                                 prompt_pad=NARROW).prompt_pad == NARROW
        # a pool of 40 is two ridge chunks: the row rounds up to 64
        b = ContinuousBatcher(spec.config, prepared, max_len=40)
        assert (b.prompt_pad, b._row_len) == (RIDGE, 64)
    assert ContinuousBatcher(spec.config, prepared, max_len=MAX_LEN
                             ).prompt_pad == MAX_LEN
    assert ContinuousBatcher(spec.config, prepared, max_len=40
                             ).prompt_pad == 40


# --------------------------------------------------------------------------
# the ridge-wide chunk against the narrow one on the same weights
# --------------------------------------------------------------------------

@functools.cache
def pair(preset):
    ridge, narrow = build(preset, None), build(preset, NARROW)
    assert (ridge.prompt_pad, narrow.prompt_pad) == (RIDGE, NARROW)
    assert ridge._row_len == narrow._row_len == MAX_LEN
    return ridge, narrow


def slot_rows(b, slot, n):
    """{leaf: what `slot` holds of it}: the first `n` positions of each
    paged leaf through the slot's table row, a slot leaf whole."""
    ids = np.asarray(b.cache["tables"])[0, slot]
    rows = {}
    for name, x in b.cache.items():
        x = np.asarray(x)
        if name.startswith("tables"):
            continue
        if name in b._slot_leaves:
            rows[name] = x[:, slot]
            continue
        got = np.moveaxis(x[:, ids], 1, 2)  # (L, H, blocks, block_len, D)
        rows[name] = got.reshape(*got.shape[:2], -1, got.shape[-1])[:, :, :n]
    return rows


@pytest.mark.parametrize("n", LENGTHS)
@pytest.mark.parametrize("preset", PRESETS)
def test_ridge_chunks_install_the_narrow_chunks_rows(preset, n):
    ridge, narrow = pair(preset)
    p = prompt(n, seed=n)
    rows, streams, runs = [], [], []
    for b in (ridge, narrow):
        before = b.prefill_chunks_run
        rid = b.submit(p, max_new_tokens=NEW)
        runs.append(b.prefill_chunks_run - before)
        slot = next(i for i, r in enumerate(b._slot_req) if r is not None)
        rows.append(slot_rows(b, slot, n))
        streams.append(b.drain()[rid])
    assert runs == [-(-n // RIDGE), -(-n // NARROW)]
    assert rows[0].keys() == rows[1].keys() and rows[0]
    for name in rows[0]:
        assert np.isfinite(rows[0][name]).all(), name
        np.testing.assert_allclose(rows[0][name], rows[1][name],
                                   rtol=2e-4, atol=2e-5, err_msg=name)
    np.testing.assert_array_equal(streams[0], streams[1])


def test_streams_under_sampling_and_logprobs():
    """A seeded sampled stream and its log-probabilities agree across the
    two pads."""
    got = []
    for pad in (None, NARROW):
        b = build("gpt2-test", pad, logprobs_k=2, seed=7)
        rid = b.submit(prompt(RIDGE + 3, seed=9), max_new_tokens=4,
                       temperature=0.9, top_k=20, seed=123, logprobs=True)
        toks = b.drain()[rid]
        got.append((toks, b.token_logprobs[rid]["chosen"]))
    np.testing.assert_array_equal(got[0][0], got[1][0])
    np.testing.assert_allclose(got[0][1], got[1][1], rtol=1e-4, atol=1e-5)


def _speculative(**kw):
    from dnn_tpu.runtime.serving_spec import SpeculativeBatcher

    spec, prepared = model("gpt2-test")
    return functools.partial(SpeculativeBatcher, draft_cfg=spec.config,
                             draft_prepared=prepared, spec_k=2, **kw)


@pytest.mark.parametrize("why,kw", [
    ("dense prefix cache", dict(kv="dense", prefix_cache=4)),
    ("radix store", dict(prefix_cache=4)),
    ("interleaved admission", dict(prefill_chunk_tokens=NARROW)),
    ("speculative", dict(kv="dense", cls=_speculative())),
])
def test_every_admit_path_takes_the_ridge(why, kw):
    """One width, so no path is fenced off: each takes the ridge where it
    is asked for no pad, and streams what its narrow twin streams — twice,
    so that the second admission meets what the first one cached."""
    ridge, narrow = build("gpt2-test", None, **kw), \
        build("gpt2-test", NARROW, **kw)
    assert (ridge.prompt_pad, narrow.prompt_pad) == (RIDGE, NARROW), why
    for n in (RIDGE + 3, RIDGE + 5):
        p = prompt(RIDGE + 5, seed=2)[:n]
        streams = []
        for b in (ridge, narrow):
            rid = b.submit(p, max_new_tokens=NEW)
            streams.append(b.drain()[rid])
        np.testing.assert_array_equal(streams[0], streams[1], err_msg=why)


def test_export_and_adoption_at_the_ridge():
    """Two replicas that took the same ridge hand a row to each other (the
    fingerprint carries `prompt_pad`), and the adopted stream is the narrow
    batcher's own."""
    ridge, narrow = pair("gpt2-test")
    p = prompt(RIDGE + 5, seed=4)
    payload = build("gpt2-test", None).export_prefill(p, max_new_tokens=NEW)
    rid = ridge.submit(p, max_new_tokens=NEW, prefilled=payload)
    adopted = ridge.drain()[rid]
    rid = narrow.submit(p, max_new_tokens=NEW)
    np.testing.assert_array_equal(adopted, narrow.drain()[rid])


# --------------------------------------------------------------------------
# one program, and what is written of it
# --------------------------------------------------------------------------

@pytest.fixture
def compile_counters():
    was = obs.enabled()
    obs.set_enabled(True)
    assert obs.install_compile_telemetry()
    m = obs.metrics()
    try:
        yield lambda: (m.counters.get("jax_compilations_total", 0),
                       m.counters.get("jax_traces_total", 0))
    finally:
        obs.set_enabled(was)


@pytest.mark.parametrize("first", [3, RIDGE, RIDGE + 3])
@pytest.mark.parametrize("preset", ["gpt2-test", "falcon-h1-test"])
def test_first_admission_compiles_all_there_is(compile_counters, preset,
                                               first):
    b = build(preset, None)
    b.submit(prompt(first), max_new_tokens=NEW)
    b.drain()
    before = compile_counters()
    for n in LENGTHS:
        b.submit(prompt(n, seed=n), max_new_tokens=NEW)
        b.drain()
    assert compile_counters() == before


def test_metrics_and_span_carry_launches_and_positions(monkeypatch):
    """/metrics: launches and positions (pad included) under the width the
    batcher took; the `admit.prefill` span: both counts of the admission."""
    b = build("gpt2-test", None)
    spans = []
    monkeypatch.setattr(
        profile, "open_span",
        lambda name, **stats: spans.append((name, stats)))
    monkeypatch.setattr(profile, "close_span", lambda span: None)
    b.submit(prompt(RIDGE + 2), max_new_tokens=NEW)
    b.drain()
    b.submit(prompt(RIDGE - 2), max_new_tokens=NEW)
    b.drain()
    assert b.prefill_chunks_run == 3
    read = {k: fn() for k, fn in b._obs_gauges.items() if "chunk_" in k}
    assert read == {
        'serving.prefill_chunk_launches_total{width="32"}': 3.0,
        'serving.prefill_chunk_positions_total{width="32"}': 96.0}
    assert [s for n, s in spans if n == "admit.prefill"] == [
        {"rid": 0, "chunks": 2, "positions": 64},
        {"rid": 1, "chunks": 1, "positions": 32}]
