"""Constrained (structured) decoding: the regex->DFA->token-mask stack
(runtime/constrain.py) and its continuous-batcher integration.

The engine is cross-checked against Python's `re` on the shared subset,
then driven end-to-end: every constrained completion must FULL-MATCH its
grammar, greedy decoding must pick the argmax AMONG allowed tokens, and
"JSON mode" output must json.loads. The reference framework has no
decode loop at all (node.py:137-200) — this is serving surface built
beyond it.
"""

import json
import re as pyre

import jax
import numpy as np
import pytest

from dnn_tpu.runtime import constrain
from dnn_tpu.runtime.constrain import (
    TokenConstraint,
    byte_vocab,
    compile_regex,
    json_regex,
    match,
)

# ----------------------------------------------------------------------
# regex engine vs Python re (shared subset, full-match semantics)
# ----------------------------------------------------------------------

CASES = [
    (r"abc", ["abc"], ["ab", "abcd", ""]),
    (r"a*b+c?", ["b", "aab", "aabbc"], ["a", "c", "bcc"]),
    (r"[a-f0-9]{2,4}", ["ab", "12ef", "0f0"], ["a", "abcde", "gh"]),
    (r"(ab|cd)*", ["", "ab", "abcdab"], ["a", "abc"]),
    (r"-?(0|[1-9][0-9]*)(\.[0-9]+)?", ["0", "-42", "3.14"],
     ["00", "1.", "-", "+1"]),
    (r"[^xyz]+", ["abc", "123"], ["", "axb"]),
    (r"\d{3}-\d{4}", ["555-1234"], ["5551234", "55-1234"]),
    (r"\w+@\w+\.(com|org)", ["a_1@b.com", "x@y.org"], ["a@b.net", "@b.com"]),
    (r"a.c", ["abc", "a0c"], ["ac", "a\nc"]),
    (r"(x|y){2}z?", ["xy", "yxz"], ["x", "xyzz"]),
    (r"\{\"k\": [0-9]+\}", ['{"k": 7}', '{"k": 42}'], ['{"k": }', "{k: 1}"]),
    (r"a{2,}", ["aa", "aaaa"], ["a", ""]),
    (r"colou?r", ["color", "colour"], ["colouur"]),
]


@pytest.mark.parametrize("pattern,good,bad", CASES)
def test_engine_matches_python_re(pattern, good, bad):
    dfa = compile_regex(pattern)
    for s in good:
        assert pyre.fullmatch(pattern, s), f"test premise: {s!r}"
        assert match(dfa, s.encode()), f"{pattern!r} should accept {s!r}"
    for s in bad:
        assert not pyre.fullmatch(pattern, s), f"test premise: {s!r}"
        assert not match(dfa, s.encode()), f"{pattern!r} should reject {s!r}"


def test_engine_randomized_against_re():
    """Fuzz short strings over a tiny alphabet against Python re for a
    few patterns — the systematic check the hand cases can't cover."""
    rs = np.random.RandomState(0)
    for pattern in [r"a*b|c", r"(ab?)+", r"[ab]{1,3}c*", r"a(b|c){2}d?"]:
        dfa = compile_regex(pattern)
        for _ in range(300):
            n = rs.randint(0, 6)
            s = "".join(rs.choice(list("abcd")) for _ in range(n))
            assert bool(pyre.fullmatch(pattern, s)) == match(
                dfa, s.encode()), (pattern, s)


def test_token_table_multibyte_tokens():
    """BPE-style multi-byte tokens walk the DFA atomically: a token is
    allowed iff its WHOLE byte string survives."""
    vocab = [b"a", b"b", b"ab", b"abc", b"c", b""]
    c = TokenConstraint.from_regex(r"ab*c", vocab)
    s = c.start
    allowed = c.allowed[s]
    assert allowed[0] and allowed[2] and allowed[3]   # a, ab, abc
    assert not allowed[1] and not allowed[4]           # b, c can't start
    assert not allowed[5], "empty-byte tokens are always banned"
    s_a = c.advance(s, 0)
    assert c.advance(s_a, 1) >= 0      # b continues
    s_abc = c.advance(s, 3)
    assert c.is_accepting(s_abc)
    # 'abc' consumed the closing c: under ab*c no byte may follow, so no
    # token can continue from this state
    assert not c.has_continuation(s_abc)


def test_json_regex_accepts_real_json():
    dfa = compile_regex(json_regex(max_depth=2))
    good = [
        42, -3.5, True, None, "hi there", [1, 2, 3],
        {"a": 1, "b": "x"}, {"outer": [1, "two", None]},
        [], {},
    ]
    for obj in good:
        s = json.dumps(obj)
        assert match(dfa, s.encode()), s
    for s in ['{"a": }', "[1,, 2]", "tru", '"unterminated', "01"]:
        assert not match(dfa, s.encode()), s
    # depth 3 exceeds the expansion budget — rejected by construction
    assert not match(dfa, json.dumps([[[1]]]).encode())


# ----------------------------------------------------------------------
# batcher integration (byte-level vocab: llama-test has V=256)
# ----------------------------------------------------------------------

from dnn_tpu.models import gpt, llama  # noqa: E402

CFG = llama.PRESETS["llama-test"]


def _batcher(**kw):
    from dnn_tpu.runtime.serving import ContinuousBatcher

    params = llama.init(jax.random.PRNGKey(0), CFG)
    prepared = gpt.prepare_stacked(params, CFG)
    kw.setdefault("slots", 2)
    return ContinuousBatcher(
        CFG, prepared, max_len=CFG.block_size, prompt_pad=8,
        family=llama.LlamaFamilyRows(CFG), allow_constraints=True, **kw)


def test_constrained_output_matches_grammar_sampled():
    srv = _batcher(temperature=1.0, slots=3)
    pattern = r"[ab]{5}"
    c = TokenConstraint.from_regex(pattern, byte_vocab(CFG.vocab_size))
    rids = [srv.submit(np.asarray([65, 66, 67]), max_new_tokens=32,
                       seed=s, constraint=c) for s in (1, 2, 3)]
    # one compiled constraint object serves many concurrent requests
    srv.drain()
    for rid in rids:
        toks = srv.results[rid]
        text = bytes(int(t) for t in toks)
        assert pyre.fullmatch(pattern.encode(), text), text
        assert srv.finish_reasons[rid] == "constraint"


def test_constrained_greedy_is_argmax_over_allowed():
    """Greedy + constraint == restrict-then-argmax of the unconstrained
    distribution (constraints must not perturb allowed logits)."""
    srv = _batcher()
    c = TokenConstraint.from_regex(r"[qz]+", byte_vocab(CFG.vocab_size))
    prompt = np.asarray([1, 2, 3, 4])
    rid = srv.submit(prompt, max_new_tokens=4, constraint=c)

    srv2 = _batcher(logprobs_k=8)
    rid2 = srv2.submit(prompt, max_new_tokens=4, logprobs=True)
    srv.drain()
    srv2.drain()
    got = srv.results[rid]
    assert all(int(t) in (ord("q"), ord("z")) for t in got)
    # cross-check first step against the unconstrained top-k record:
    # among {q, z}, the constrained pick is the higher-logprob one
    lp = srv2.token_logprobs[rid2]
    ids0 = list(lp["top_ids"][0] if lp["top_ids"].ndim == 2
                else lp["top_ids"][0])
    if ord("q") in ids0 and ord("z") in ids0:
        want = (ord("q") if ids0.index(ord("q")) < ids0.index(ord("z"))
                else ord("z"))
        assert int(got[0]) == want


def test_json_mode_end_to_end():
    """A bounded JSON grammar forces a parseable object from a RANDOM
    model under sampling — the 'JSON mode' aha in one test."""
    srv = _batcher(temperature=1.0)
    # no leading zeros: [0-9]{1,3} admits "002", which regex-matches but
    # is not a legal JSON number — the constraint engine faithfully
    # produced it and json.loads rightly refused (the old failure)
    pattern = r"\{\"k\": (true|false|0|[1-9][0-9]{0,2})\}"
    c = TokenConstraint.from_regex(pattern, byte_vocab(CFG.vocab_size))
    rid = srv.submit(np.asarray([10, 20]), max_new_tokens=24, seed=7,
                     constraint=c)
    srv.drain()
    text = bytes(int(t) for t in srv.results[rid]).decode()
    obj = json.loads(text)
    assert set(obj) == {"k"}
    assert srv.finish_reasons[rid] == "constraint"


def test_eos_only_in_accepting_states():
    """With an eos_id configured, open-ended grammars stop via a real
    sampled EOS — and the emitted prefix is a complete match."""
    eos = 0
    srv = _batcher(temperature=1.0, eos_id=eos, slots=4)
    pattern = r"[xy]{2,6}"
    c = TokenConstraint.from_regex(pattern, byte_vocab(CFG.vocab_size))
    rids = [srv.submit(np.asarray([5, 6]), max_new_tokens=10, seed=s,
                       constraint=c) for s in range(4)]
    srv.drain()
    for rid in rids:
        toks = [int(t) for t in srv.results[rid]]
        reason = srv.finish_reasons[rid]
        body = bytes(t for t in toks if t != eos)
        assert pyre.fullmatch(pattern.encode(), body), (body, reason)
        assert reason in ("eos", "constraint"), reason


def test_constraint_requires_capability_and_matching_vocab():
    from dnn_tpu.runtime.serving import ContinuousBatcher

    params = llama.init(jax.random.PRNGKey(0), CFG)
    prepared = gpt.prepare_stacked(params, CFG)
    srv = ContinuousBatcher(CFG, prepared, slots=1, max_len=64,
                            prompt_pad=8,
                            family=llama.LlamaFamilyRows(CFG))
    c = TokenConstraint.from_regex(r"a+", byte_vocab(CFG.vocab_size))
    with pytest.raises(ValueError, match="allow_constraints"):
        srv.submit(np.asarray([1]), max_new_tokens=4, constraint=c)

    srv2 = _batcher()
    bad = TokenConstraint.from_regex(r"a+", byte_vocab(128))
    with pytest.raises(ValueError, match="vocab"):
        srv2.submit(np.asarray([1]), max_new_tokens=4, constraint=bad)


def test_constraint_rejects_grammar_relevant_eos():
    """An eos_id that aliases bytes the grammar can consume must be
    rejected at submit — mask_row's eos override would otherwise ban a
    required token (and an emitted one would retire as 'eos' mid-match)."""
    srv = _batcher(eos_id=ord("x"))
    c = TokenConstraint.from_regex(r"[xy]{3}", byte_vocab(CFG.vocab_size))
    with pytest.raises(ValueError, match="eos"):
        srv.submit(np.asarray([1, 2]), max_new_tokens=5, constraint=c)


def test_constraint_accepts_eos_aliased_only_in_unreachable_states():
    """BPE-style multi-byte tokens jump over byte-DFA states; eos bytes
    consumable ONLY in those token-unreachable states must not trip the
    submit guard (regression pin for the reachable-quantified check —
    reverting to `allowed[:, eos_id].any()` breaks this)."""
    vocab = [b"ab", b"b"] + [b""] * (CFG.vocab_size - 2)
    c = TokenConstraint.from_regex(r"ab", vocab)
    # the post-'a' byte state exists (it consumes b"b", token 1) but no
    # token walk from start lands on it — token b"ab" jumps over it
    unreachable = ~c.reachable
    assert c.allowed[unreachable, 1].any()
    assert not c.allowed[c.reachable, 1].any()
    srv = _batcher(eos_id=1)
    rid = srv.submit(np.asarray([3, 4]), max_new_tokens=4, constraint=c)
    srv.drain()
    toks = [int(t) for t in srv.results[rid]]
    assert [t for t in toks if t != 1] == [0]  # b"ab" (eos may trail)
    assert srv.finish_reasons[rid] in ("eos", "constraint")


def test_constraint_composes_with_user_logit_bias():
    """logit_bias steers WITHIN the grammar: banning 'a' under [ab]{3}
    yields bbb."""
    srv = _batcher(allow_logit_bias=True, temperature=1.0)
    c = TokenConstraint.from_regex(r"[ab]{3}", byte_vocab(CFG.vocab_size))
    rid = srv.submit(np.asarray([9]), max_new_tokens=8, seed=1,
                     constraint=c, logit_bias={ord("a"): -100.0})
    srv.drain()
    assert bytes(int(t) for t in srv.results[rid]) == b"bbb"


def test_empty_string_grammar_serves_empty_match():
    """A grammar matching ONLY the empty string is legal when eos can
    express it: the first sample is forced to eos and the request
    retires with a valid empty match. Without an eos there is no way to
    express it — rejected."""
    c = TokenConstraint.from_regex(r"", byte_vocab(CFG.vocab_size))
    assert not c.allowed[c.start].any() and c.is_accepting(c.start)
    srv = _batcher(eos_id=0)
    rid = srv.submit(np.asarray([5]), max_new_tokens=4, constraint=c)
    srv.drain()
    assert [t for t in srv.results[rid] if t != 0] == []
    assert srv.finish_reasons[rid] == "eos"

    srv2 = _batcher(eos_id=None)
    with pytest.raises(ValueError, match="no first token"):
        srv2.submit(np.asarray([5]), max_new_tokens=4, constraint=c)


def test_constraint_table_pool_hit_refcount_eviction():
    """The device mask pool uploads each grammar ONCE (pool hit on
    resubmit), keeps unreferenced entries cached, and evicts them LRU
    when space runs out."""
    srv = _batcher(constraint_rows=12)
    v = byte_vocab(CFG.vocab_size)
    c1 = TokenConstraint.from_regex(r"[ab]{3}", v)
    n1 = c1.table.shape[0]
    rid = srv.submit(np.asarray([1]), max_new_tokens=8, constraint=c1)
    assert len(srv._ctab_entries) == 1
    e1 = srv._ctab_entries[id(c1)]
    assert e1["refs"] == 1 and e1["n"] == n1 and e1["off"] >= 1
    srv.drain()
    assert e1["refs"] == 0  # retired; entry stays cached
    assert srv.finish_reasons[rid] == "constraint"

    srv.submit(np.asarray([1]), max_new_tokens=8, constraint=c1)
    assert len(srv._ctab_entries) == 1 and e1["refs"] == 1  # pool hit
    srv.drain()

    # fill the pool with fresh grammars until c1's entry must evict
    fillers = [TokenConstraint.from_regex(r"[cd]{%d}" % k, v)
               for k in (3, 4)]
    for f in fillers:
        srv.submit(np.asarray([1]), max_new_tokens=10, constraint=f)
        srv.drain()
    assert id(c1) not in srv._ctab_entries, "LRU entry should have evicted"


def test_constraint_pool_rejects_oversized_and_exhausted():
    srv = _batcher(constraint_rows=8)
    v = byte_vocab(CFG.vocab_size)
    big = TokenConstraint.from_regex(r"[ab]{20}", v)
    assert big.table.shape[0] > 7
    with pytest.raises(ValueError, match="constraint_rows"):
        srv.submit(np.asarray([1]), max_new_tokens=4, constraint=big)

    # two LIVE grammars that cannot coexist in an 8-row pool: the second
    # submit must fail loudly (no unreferenced entry to evict)
    c1 = TokenConstraint.from_regex(r"[ab]{4}", v)
    c2 = TokenConstraint.from_regex(r"[cd]{4}", v)
    assert c1.table.shape[0] + c2.table.shape[0] > 7
    srv.submit(np.asarray([1]), max_new_tokens=8, constraint=c1)  # live
    with pytest.raises(ValueError, match="exhausted"):
        srv.submit(np.asarray([2]), max_new_tokens=8, constraint=c2)
    srv.drain()


def test_constraints_need_no_bias_buffer():
    """Device-resident tables removed the constraint path's dependence
    on the (slots, V) bias buffer: an allow_constraints-only server
    keeps the zero-width buffer (memory win) and the per-slot state
    vector mirrors the host DFA walk."""
    srv = _batcher(slots=2)
    assert srv._bias.shape == (2, 0)
    c = TokenConstraint.from_regex(r"[ab]{4}", byte_vocab(CFG.vocab_size))
    srv.submit(np.asarray([1]), max_new_tokens=2, constraint=c)
    srv.step()
    off = srv._ctab_entries[id(c)]["off"]
    req = srv._slot_req[0]
    if req is not None:  # still live: device row tracks the host state
        assert int(np.asarray(srv._crow)[0]) == off + req["c_state"]
    srv.drain()
    assert int(np.asarray(srv._crow)[0]) == 0  # released back to the zero row


def test_choice_constraint_picks_exactly_one_label():
    """The enum/classifier pattern: output is VERBATIM one of the
    options, across several sampled requests."""
    from dnn_tpu.runtime.constrain import choice_regex, regex_escape

    options = ["positive", "negative", "neutral(ish)"]  # metachars too
    pattern = choice_regex(options)
    dfa = compile_regex(pattern)
    for o in options:
        assert match(dfa, o.encode())
    assert not match(dfa, b"positiv")
    assert not match(dfa, b"neutralXishX"), "metachars match literally"
    assert pyre.fullmatch(pyre.escape("a.b{c"),
                          "a.b{c") and match(
        compile_regex(regex_escape("a.b{c")), b"a.b{c")

    srv = _batcher(temperature=1.0, slots=3)
    c = TokenConstraint.from_regex(pattern, byte_vocab(CFG.vocab_size))
    rids = [srv.submit(np.asarray([11, 12]), max_new_tokens=32, seed=s,
                       constraint=c) for s in (1, 2, 3)]
    srv.drain()
    for rid in rids:
        text = bytes(int(t) for t in srv.results[rid]).decode()
        assert text in options, text
        assert srv.finish_reasons[rid] == "constraint"


def test_lm_server_json_mode_wiring():
    """The daemon's ':j=DEPTH' gen option: parse -> compile-once
    constraint over the tokenizer's byte vocab -> constrained submit
    through the worker; output json.loads."""
    from dnn_tpu.io.tokenizer import ByteTokenizer
    from dnn_tpu.runtime.lm_server import LMServer, parse_gen_options

    mx, seed, opts = parse_gen_options("gen:40:7:j=1", 32)
    assert (mx, seed, opts) == (40, 7, {"json_depth": 1})

    params = llama.init(jax.random.PRNGKey(0), CFG)
    prepared = gpt.prepare_stacked(params, CFG)
    srv = LMServer(CFG, prepared, tokenizer=ByteTokenizer(CFG.vocab_size),
                   slots=2, max_len=CFG.block_size, prompt_pad=8,
                   family=llama.LlamaFamilyRows(CFG),
                   allow_constraints=True, temperature=1.0)
    try:
        assert srv.json_constraint(0) is srv.json_constraint(0), "cached"
        with pytest.raises(ValueError, match="depth"):
            srv.json_constraint(9)
        fut = srv.worker.submit(np.asarray([3, 4, 5], np.int32), 40, 7,
                                opts={"constraint": srv.json_constraint(0)})
        toks = fut.result(timeout=120)
        json.loads(bytes(int(t) for t in toks).decode())
    finally:
        srv.close()

    # a server whose tokenizer has no byte map cannot serve JSON mode
    srv2 = LMServer(CFG, prepared, tokenizer=None, slots=1, max_len=32,
                    prompt_pad=8, family=llama.LlamaFamilyRows(CFG))
    try:
        assert srv2.json_constraint(1) is None
    finally:
        srv2.close()


def test_hf_vocab_bytes_sentencepiece_convention():
    """Convention is detected ONCE per vocab: a SentencePiece piece made
    of alias-alphabet chars ('é') must yield its UTF-8 bytes, not the
    Latin-1 byte the BPE alias table would give; '<0xNN>' pieces are raw
    bytes; padding ids beyond the tokenizer map to b""."""
    from dnn_tpu.io.tokenizer import hf_vocab_bytes

    class FakeSP:
        all_special_tokens = ["<s>"]

        @staticmethod
        def get_vocab():
            return {"<s>": 0, "▁caf": 1, "é": 2, "<0x0A>": 3, "hello": 4}

    vb = hf_vocab_bytes(FakeSP())
    assert vb[0] == b""                       # special: banned
    assert vb[1] == " caf".encode()
    assert vb[2] == "é".encode("utf-8")       # b'\xc3\xa9', NOT b'\xe9'
    assert vb[3] == b"\n"
    assert vb[4] == b"hello"
    vb2 = hf_vocab_bytes(FakeSP(), vocab_size=10)
    assert len(vb2) == 10 and vb2[9] == b""   # padded embedding table


def test_hf_vocab_bytes_real_bpe_constrained_decode():
    """Constrained decoding over a REAL byte-level BPE vocabulary
    (multi-byte tokens), not just the byte tokenizer: hf_vocab_bytes
    inverts the GPT-2 alias alphabet, and a grammar holds token streams
    whose tokens span several grammar bytes at once."""
    import dataclasses

    tokenizers = pytest.importorskip("tokenizers")
    transformers = pytest.importorskip("transformers")

    from dnn_tpu.io.tokenizer import hf_vocab_bytes

    bpe = tokenizers.implementations.ByteLevelBPETokenizer()
    corpus = (['{"name": "value", "count": 123, "flag": true}'] * 40
              + ["hello world, plain text with spaces"] * 40)
    bpe.train_from_iterator(corpus, vocab_size=300, min_frequency=1)
    fast = transformers.PreTrainedTokenizerFast(
        tokenizer_object=bpe._tokenizer)
    vb = hf_vocab_bytes(fast)

    # THE invariant constraints rely on: concatenating a real encoding's
    # token bytes reproduces the text's utf-8 bytes exactly
    for text in ['{"count": 42}', "hello world", '{"flag": true}']:
        ids = fast.encode(text)
        assert b"".join(vb[i] for i in ids) == text.encode(), text

    V = len(vb)
    cfg = dataclasses.replace(CFG, vocab_size=V)
    from dnn_tpu.runtime.serving import ContinuousBatcher

    params = llama.init(jax.random.PRNGKey(3), cfg)
    prepared = gpt.prepare_stacked(params, cfg)
    srv = ContinuousBatcher(cfg, prepared, slots=2, max_len=cfg.block_size,
                            prompt_pad=8, family=llama.LlamaFamilyRows(cfg),
                            allow_constraints=True, temperature=1.0)
    c = TokenConstraint.from_regex(r"\{\"count\": [0-9]{1,3}\}", vb)
    # multi-byte tokens must be usable: the grammar's fixed prefix
    # ('{"count": ') is in-corpus, so merged tokens cover it
    assert any(len(vb[t]) > 1 and c.allowed[:, t].any() for t in range(V))
    rid = srv.submit(np.asarray(fast.encode("hello world")),
                     max_new_tokens=32, seed=5, constraint=c)
    srv.drain()
    text = b"".join(vb[int(t)] for t in srv.results[rid]).decode()
    obj = json.loads(text)
    assert set(obj) == {"count"}
    assert srv.finish_reasons[rid] == "constraint"


def test_speculative_batcher_rejects_constraints():
    """The speculative batcher commits multiple tokens per step — it
    rejects allow_constraints at CONSTRUCTION (before allocating the
    device mask pool it could never use), and constraint= submits on an
    unconstrained instance fail with the capability error."""
    from dnn_tpu.runtime.serving_spec import SpeculativeBatcher

    cfg = gpt.PRESETS["gpt2-test"]
    rng = jax.random.PRNGKey(0)
    params = gpt.init(rng, cfg)
    prepared = gpt.prepare_stacked(params, cfg)
    with pytest.raises(ValueError, match="allow_constraints"):
        SpeculativeBatcher(cfg, prepared, cfg, prepared, spec_k=2,
                           slots=1, max_len=32, prompt_pad=8,
                           allow_constraints=True)
    srv = SpeculativeBatcher(cfg, prepared, cfg, prepared, spec_k=2,
                             slots=1, max_len=32, prompt_pad=8)
    c = TokenConstraint.from_regex(r"a+", byte_vocab(cfg.vocab_size))
    with pytest.raises(ValueError, match="constraint"):
        srv.submit(np.asarray([1, 2, 3]), max_new_tokens=4, constraint=c)


# ----------------------------------------------------------------------
# the bit-packed mask pool (ISSUE 48)
# ----------------------------------------------------------------------

@pytest.mark.parametrize("eos_id", [None, 5], ids=["no-eos", "eos"])
@pytest.mark.parametrize("vocab", [50257, 256, 70])
def test_packed_mask_table_unpacks_exactly(vocab, eos_id):
    """Packing a boolean is lossless at every vocabulary — GPT-2's 50 257
    (32 and 128 divide neither it nor its word count), 256 (both do) and
    70 (under one word's 128-lane tile) — on the host and through the
    decode program's own unpack, for every row at once and in any
    order."""
    import jax.numpy as jnp

    from dnn_tpu.runtime.serving import _mask_rows

    c = TokenConstraint.from_regex(r"[a-c]{2}[0-9]+|x", byte_vocab(vocab))
    want = c.mask_table(eos_id)
    if vocab > 256:  # byte_vocab's tail is empty tokens: mark some rows
        want = want.copy()
        want[:, 256:] = np.random.default_rng(vocab).random(
            (want.shape[0], vocab - 256)) < 0.5
    packed = constrain.pack_mask_table(want)
    assert packed.dtype == np.uint32
    assert packed.shape == (want.shape[0], constrain.mask_words(vocab))
    assert packed.shape[1] % 128 == 0 and packed.shape[1] * 32 >= vocab
    np.testing.assert_array_equal(
        constrain.unpack_mask_table(packed, vocab), want)
    rows = np.arange(want.shape[0])[::-1].astype(np.int32)
    got = jax.jit(_mask_rows, static_argnums=2)(
        jnp.asarray(packed), jnp.asarray(rows), vocab)
    assert got.dtype == jnp.bool_ and got.shape == (len(rows), vocab)
    np.testing.assert_array_equal(np.asarray(got), want[rows])


@pytest.mark.parametrize("vocab_size", [256, 70])
def test_mask_pool_row_zero_allows_everything(vocab_size):
    """Row 0 of the pool is the reserved unconstrained row: all ones, in
    the pool as built and after an upload beside it."""
    import dataclasses

    import jax.numpy as jnp

    from dnn_tpu.runtime.serving import ContinuousBatcher, _mask_rows

    cfg = dataclasses.replace(CFG, vocab_size=vocab_size)
    prepared = gpt.prepare_stacked(llama.init(jax.random.PRNGKey(0), cfg),
                                   cfg)
    srv = ContinuousBatcher(
        cfg, prepared, slots=2, max_len=cfg.block_size, prompt_pad=8,
        family=llama.LlamaFamilyRows(cfg), allow_constraints=True,
        constraint_rows=8)
    assert srv._ctable.dtype == jnp.uint32
    assert srv._ctable.shape == (8, constrain.mask_words(vocab_size))
    zero = jnp.zeros((2,), jnp.int32)
    assert bool(_mask_rows(srv._ctable, zero, vocab_size).all())
    c = TokenConstraint.from_regex(r"[ab]{3}", byte_vocab(vocab_size))
    off = srv._ctab_register(c)
    assert off >= 1
    assert bool(_mask_rows(srv._ctable, zero, vocab_size).all())
    np.testing.assert_array_equal(
        np.asarray(_mask_rows(srv._ctable, jnp.asarray([off, 0]),
                              vocab_size)),
        np.stack([c.mask_table(srv.eos_id)[0],
                  np.ones((vocab_size,), bool)]))
