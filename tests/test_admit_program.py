"""ISSUE 34: a convoy admission is `n_chunks + 2` device programs — a
fresh transient row, the chunk programs and ONE donated
finish-and-install — and nothing else touches the device from
`submit()`.

Two contracts, over a GPT, a LLaMA-MoE and a selecting (three-leaf) test
preset:

  * the launch count: a warm `submit()` of an n-chunk prompt executes at
    most n + 2 programs and binds no primitive eagerly (the parent bound
    81, 70 of them through `dispatch.apply_primitive`);
  * the streams: every kind of request emits the tokens the parent
    (commit 34e183d: eager key derivation, an install-less finish and
    eleven eager per-slot scatters) emitted — recorded below from that
    commit by `python tests/test_admit_program.py` — and interleaved
    admission agrees with convoy draw for draw.
"""

import json
import os

import jax
import numpy as np
import pytest

from dnn_tpu.models.gpt import prepare_stacked
from dnn_tpu.registry import get_model
from dnn_tpu.runtime.constrain import TokenConstraint, byte_vocab
from dnn_tpu.runtime.serving import ContinuousBatcher

PRESETS = ("gpt2-test", "olmoe-test", "keye-test")
PAD = 16
GOLDEN_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "admit_streams_parent.json")


def build(preset, **kw):
    """The preset's batcher as the daemon builds one: a paged pool and
    every per-request capability compiled in."""
    spec = get_model(preset)
    cfg = spec.config
    prepared = prepare_stacked(dict(spec.init(jax.random.PRNGKey(3))), cfg)
    opts = dict(slots=3, max_len=64, prompt_pad=PAD, kv="paged", block_len=8,
                seed=11, logprobs_k=3, allow_logit_bias=True,
                allow_constraints=True, constraint_rows=16)
    family = spec.extras.get("family_rows")
    if family is not None:
        opts["family"] = family()
    opts.update(kw)
    return ContinuousBatcher(cfg, prepared, **opts)


def prompt(n, seed=1):
    return np.asarray(jax.random.randint(
        jax.random.PRNGKey(seed), (n,), 1, 256), np.int32)


_GRAMMAR = {}


def grammar():
    if not _GRAMMAR:
        _GRAMMAR["c"] = TokenConstraint.from_regex(r"[a-f]{3,9}",
                                                   byte_vocab(256))
    return _GRAMMAR["c"]


# name -> (prompt length, submit options); 39 tokens are three chunks
SCENARIOS = {
    "greedy": (39, dict()),
    "greedy_one_chunk": (7, dict()),
    "sampled_seeded": (39, dict(seed=123, temperature=0.9, top_k=20,
                                top_p=0.9, min_p=0.02)),
    # above 2**31: the benchmark's seeds are
    "sampled_big_seed": (21, dict(seed=3000000019, temperature=1.0)),
    # no seed: the stream is named by the request id (namespace 0)
    "sampled_by_rid": (21, dict(temperature=1.0, top_k=50)),
    # the prompt's tokens are penalised in the FIRST sample too
    "repetition": (39, dict(repetition_penalty=1.7)),
    "repetition_sampled": (21, dict(seed=5, temperature=0.8,
                                    repetition_penalty=1.3)),
    # under 1 it favours what was seen: the first token comes FROM the
    # prompt's mask, whatever the model
    "repetition_reward": (39, dict(repetition_penalty=0.2)),
    "logit_bias": (21, dict(logit_bias={65: 9.0, 66: 8.5, 3: -100.0})),
    "constraint": (21, dict(seed=9, temperature=1.0, constraint=True)),
    "constraint_greedy": (39, dict(constraint=True)),
    "logprobs": (39, dict(seed=77, temperature=0.7, logprobs=True)),
}
N_NEW = 8


def run(srv, name, *, prefilled=None):
    """One scenario's request through `srv` -> {"tokens", "reason"[,
    "chosen", "top_ids"]}."""
    plen, opts = SCENARIOS[name]
    opts = dict(opts)
    if opts.pop("constraint", False):
        opts["constraint"] = grammar()
    if "seed" not in opts:
        srv._next_rid = 1000 + plen  # the stream's name, whatever ran before
    rid = srv.submit(prompt(plen, seed=plen), N_NEW, prefilled=prefilled,
                     **opts)
    srv.drain()
    lps = srv.token_logprobs.get(rid)
    tokens, reason, _ = srv.claim(rid)
    out = {"tokens": [int(t) for t in tokens], "reason": reason}
    if lps is not None:
        out["chosen"] = [float(x) for x in lps["chosen"]]
        out["top_ids"] = [[int(i) for i in r] for r in lps["top_ids"]]
    return out


def record():
    """What the tree at hand emits, scenario by scenario and preset by
    preset (run on the parent commit to write GOLDEN_PATH)."""
    out = {}
    for preset in PRESETS:
        srv = build(preset)
        out[preset] = {name: run(srv, name) for name in SCENARIOS}
        # adoption of a row another replica prefilled: a sampled stream
        out[preset]["prefilled"] = run(
            srv, "sampled_seeded",
            prefilled=build(preset).export_prefill(prompt(39, seed=39)))
    return out


@pytest.fixture(scope="module")
def golden():
    with open(GOLDEN_PATH) as f:
        return json.load(f)


@pytest.fixture(scope="module", params=PRESETS)
def convoy(request):
    return request.param, build(request.param)


@pytest.fixture(scope="module", params=PRESETS[:2])
def interleaved(request):
    # (a three-leaf family refuses interleaved admission)
    return request.param, build(request.param, prefill_chunk_tokens=PAD)


def _same(got, want):
    assert got["tokens"] == want["tokens"]
    assert got["reason"] == want["reason"]
    if "chosen" in want:
        np.testing.assert_allclose(got["chosen"], want["chosen"], atol=1e-5)
        assert got["top_ids"] == want["top_ids"]


@pytest.mark.parametrize("name", list(SCENARIOS))
def test_convoy_stream_equals_the_parents(convoy, golden, name):
    preset, srv = convoy
    _same(run(srv, name), golden[preset][name])


def test_adopted_prefill_stream_equals_the_parents(convoy, golden):
    preset, srv = convoy
    pay = srv.export_prefill(prompt(39, seed=39))
    _same(run(srv, "sampled_seeded", prefilled=pay),
          golden[preset]["prefilled"])
    # and the adoption drew what a local prefill of the same seed draws
    assert golden[preset]["prefilled"] == golden[preset]["sampled_seeded"]


@pytest.mark.parametrize("name", list(SCENARIOS))
def test_interleaved_agrees_with_convoy_draw_for_draw(interleaved, golden,
                                                      name):
    preset, srv = interleaved
    _same(run(srv, name), golden[preset][name])


def test_recorded_streams_tell_the_scenarios_apart(golden):
    """The goldens would catch a lost option: sampling, the penalty, the
    bias and the grammar each change the stream they are asked of."""
    for preset in PRESETS:
        g = golden[preset]
        assert g["sampled_seeded"]["tokens"] != g["greedy"]["tokens"]
        # the prompt's mask reaches the FIRST sample: the penalty moves
        # it off a prompt token, or the reward moves it onto one
        firsts = {g[k]["tokens"][0] for k in ("repetition",
                                              "repetition_reward")}
        assert firsts != {g["greedy"]["tokens"][0]}
        assert g["repetition_reward"]["tokens"][0] in prompt(39, seed=39)
        assert g["logit_bias"]["tokens"][0] in (65, 66)
        assert g["constraint"]["tokens"] != g["constraint_greedy"]["tokens"]
        for name in ("constraint", "constraint_greedy"):
            assert all(97 <= t <= 102 for t in g[name]["tokens"])
        assert len(g["logprobs"]["chosen"]) == N_NEW


# ----------------------------------------------------------------------
# the launch count
# ----------------------------------------------------------------------

class Launches:
    """Counts what a `submit()` launches, until the monkeypatch is
    undone: the batcher's jitted programs by wrapping each (a warm call
    takes the C++ fast path, which no Python hook sees), every primitive
    bound eagerly by `EvalTrace.process_primitive`, and those of them
    that run as a program of their own by `dispatch.apply_primitive`."""

    def __init__(self, srv, monkeypatch):
        from jax._src import core, dispatch

        self.programs, self.eager, self.applied = [], [], []
        for fn in srv.jit_programs():
            for attr, val in vars(srv).items():
                if val is fn:
                    monkeypatch.setattr(srv, attr, self._counted(attr, fn))
        real_process = core.EvalTrace.process_primitive
        real_apply = dispatch.apply_primitive

        def process(trace, primitive, *a, **kw):
            if primitive.name not in ("jit", "pjit"):
                self.eager.append(primitive.name)
            return real_process(trace, primitive, *a, **kw)

        def apply(prim, *a, **kw):
            self.applied.append(prim.name)
            return real_apply(prim, *a, **kw)

        monkeypatch.setattr(core.EvalTrace, "process_primitive", process)
        monkeypatch.setattr(dispatch, "apply_primitive", apply)

    def _counted(self, attr, fn):
        def call(*args):
            self.programs.append(attr)
            return fn(*args)
        call._cache_size = fn._cache_size
        return call


LAUNCH_CASES = {
    "plain": dict(),
    "sampled": dict(seed=4, temperature=0.9, top_k=20, top_p=0.9,
                    repetition_penalty=1.2),
    "constrained": dict(seed=4, temperature=1.0, constraint=True),
    "logprobs": dict(logprobs=True),
}


@pytest.mark.parametrize("case", list(LAUNCH_CASES))
@pytest.mark.parametrize("n_chunks", [1, 3])
def test_warm_admission_launches_chunks_plus_two(convoy, monkeypatch, case,
                                                 n_chunks):
    _, srv = convoy
    opts = dict(LAUNCH_CASES[case])
    if opts.pop("constraint", False):
        opts["constraint"] = grammar()
    plen = n_chunks * PAD - 9
    srv.submit(prompt(plen), 2, **opts)  # warm: compiles, uploads the grammar
    srv.drain()
    ids = prompt(plen, seed=2)
    n = Launches(srv, monkeypatch)
    srv.submit(ids, 2, **opts)
    assert n.eager == [] and n.applied == []
    assert len(n.programs) <= n_chunks + 2, n.programs
    assert n.programs.count("_prefill_chunk") == n_chunks
    assert n.programs[-1] == "_prefill_finish"
    monkeypatch.undo()
    srv.drain()


def test_a_dispatched_chunk_holds_hidden_rows_not_logits(convoy):
    """The chunk loop dispatches every chunk of an admission without
    reading or waiting (ISSUE 38): what a dispatched chunk allocates is
    its (1, P, C) hidden rows — the (1, P, V) float32 logits that the
    loop once held to a budget of bytes in flight are never made
    (tests/test_chunk_head.py reads the traced programs)."""
    _, srv = convoy
    ids = np.zeros((1, PAD), np.int32)
    row = srv._new_row()
    for c in range(4):
        hidden, row = srv._run_prefill_chunk(
            srv.prepared, row, ids, np.int32(c * PAD))
        assert hidden.shape == (1, PAD, srv.cfg.n_embd)
        assert hidden.nbytes * 4 <= PAD * srv.cfg.vocab_size * 4
    assert not hasattr(srv, "_CHUNK_AHEAD_BYTES")


if __name__ == "__main__":
    with open(GOLDEN_PATH, "w") as f:
        json.dump(record(), f, indent=1, sort_keys=True)
    print("wrote", GOLDEN_PATH)
