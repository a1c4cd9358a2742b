"""ISSUE 38: the prefill chunk program stops at the last block, and the
head runs once an admission — on the one row that is sampled, inside the
finish-and-install program.

Over the four serving families (GPT, LLaMA-MoE, the selecting DSA family,
latent-attention MLA; tiny presets, float32 on the CPU):

  * the first token and its log-probabilities are the PARENT's form's —
    the head over the whole last chunk, then row `last_local` — for a
    prompt that ends inside a padded tail chunk and one that ends on a
    chunk edge;
  * the traced chunk program holds no operation under `gpt.head` /
    `llama.head` and no result with a vocabulary-sized dimension; the
    traced finish holds exactly ONE matmul onto the vocabulary, of one
    row (this took the place of the `_CHUNK_AHEAD_BYTES` test of
    tests/test_admit_program.py: nothing a dispatched chunk allocates is
    large any more, so the hold it tested is gone);
  * the paths no benchmark cell runs — a radix full hit, a whole-prompt
    hit of the dense prefix cache, a `prefilled=` adoption, a block run
    adopted from a sibling replica, interleaved admission's deferred
    first token — sample what a cold admission of the same prompt and
    seed samples: they carry the HIDDEN row (C values where V were) into
    the same finish program.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dnn_tpu.models import gpt, llama_moe
from dnn_tpu.registry import get_model
from dnn_tpu.runtime.serving import ContinuousBatcher

PRESETS = ("gpt2-test", "olmoe-test", "keye-test", "joyai-test")
PAD = 16
VOCAB = 251  # no other extent of the tiny presets (256 is an MLP's width)


def build(preset, *, vocab=None, **kw):
    """The preset's batcher over a paged pool, float32."""
    cfg = get_model(preset).config
    if vocab is not None:
        cfg = dataclasses.replace(cfg, vocab_size=vocab)
    family = None
    if isinstance(cfg, gpt.GPTConfig):
        params = gpt.init(jax.random.PRNGKey(3), cfg)
    else:
        params = llama_moe.init(jax.random.PRNGKey(3), cfg)
        family = llama_moe.family_rows(cfg)
    opts = dict(slots=3, max_len=64, prompt_pad=PAD, kv="paged", block_len=8,
                seed=11, logprobs_k=3, family=family)
    opts.update(kw)
    return ContinuousBatcher(cfg, gpt.prepare_stacked(dict(params), cfg),
                             **opts)


def prompt(n, seed=1, vocab=256):
    return np.asarray(jax.random.randint(
        jax.random.PRNGKey(seed), (n,), 1, vocab), np.int32)


def first(srv, ids, **opts):
    """(first token, its chosen log-probability, its top ids and their
    log-probabilities) of one request through `srv`."""
    rid = srv.submit(ids, 1, logprobs=True, **opts)
    srv.drain()
    lps = srv.token_logprobs[rid]
    tokens, _, _ = srv.claim(rid)
    assert len(tokens) == 1
    return (int(tokens[0]), float(lps["chosen"][0]),
            [int(i) for i in lps["top_ids"][0]],
            np.asarray(lps["top_logprobs"][0], np.float32))


def same_first(got, want):
    assert got[0] == want[0] and got[2] == want[2]
    np.testing.assert_allclose(got[1], want[1], atol=1e-5)
    np.testing.assert_allclose(got[3], want[3], atol=1e-5)


@pytest.fixture(scope="module", params=PRESETS)
def served(request):
    return build(request.param)


# ----------------------------------------------------------------------
# (a) the sampled row's logits are the parent's
# ----------------------------------------------------------------------

@pytest.mark.parametrize("plen", [39, 32, 7],
                         ids=["padded_tail", "chunk_edge", "one_chunk"])
def test_first_token_is_the_row_of_the_head_over_the_whole_chunk(served,
                                                                 plen):
    """The parent ran the family's head over all P rows of every chunk
    and the finish read row `last_local` of the last chunk's logits. The
    chunks here are the batcher's own program; the head over the whole
    last chunk is run beside it, as the parent's chunk ended."""
    srv = served
    ids = prompt(plen, seed=plen)
    got = first(srv, ids)

    n_chunks = -(-plen // PAD)
    padded = np.zeros((1, n_chunks * PAD), np.int32)
    padded[0, :plen] = ids
    row = srv._new_row()
    for c in range(n_chunks):
        hidden, row = srv._prefill_chunk(
            srv.prepared, row, padded[:, c * PAD:(c + 1) * PAD],
            np.int32(c * PAD))[:2]
    assert hidden.shape == (1, PAD, srv.cfg.n_embd)
    assert hidden.dtype == jnp.float32  # what `head` was handed
    logits = jax.jit(srv.family.head)(srv.prepared, hidden)  # (1, P, V)
    last_local = plen - 1 - (n_chunks - 1) * PAD
    lp = np.asarray(jax.nn.log_softmax(logits[0, last_local]))
    tok = int(lp.argmax())
    top = np.argsort(-lp, kind="stable")[:3]
    same_first(got, (tok, lp[tok], [int(i) for i in top], lp[top]))


# ----------------------------------------------------------------------
# (b) where the head is, in the traced programs
# ----------------------------------------------------------------------

def _eqns(jaxpr):
    """Every equation of a jaxpr, those of its sub-programs (the jitted
    call, the layer loops, kernels' bodies) included."""
    for e in jaxpr.eqns:
        yield e
        for sub in jax.core.jaxprs_in_params(e.params):
            yield from _eqns(sub)


def _under_head(eqn):
    stack = str(eqn.source_info.name_stack)
    return "gpt.head" in stack or "llama.head" in stack


def _traced(srv, plen=21):
    """{program: its jaxpr} of the chunk and the finish, traced from the
    arguments of their first real calls."""
    from tests.test_chip_compile import first_calls

    calls = first_calls([(srv, ("_prefill_chunk", "_prefill_finish"))],
                        prompt_len=plen)
    return {name: jax.make_jaxpr(fn)(*args).jaxpr
            for name, (fn, args) in calls.items()}


@pytest.fixture(scope="module", params=PRESETS)
def traced(request):
    return _traced(build(request.param, vocab=VOCAB))


def test_the_chunk_program_holds_no_head_and_nothing_vocabulary_sized(
        traced):
    n = 0
    for e in _eqns(traced["_prefill_chunk"]):
        n += 1
        assert not _under_head(e), e
        for v in e.outvars:
            assert VOCAB not in getattr(v.aval, "shape", ()), e
    assert n > 50  # the walk went into the layer loops


def test_the_finish_program_holds_one_head_matmul_of_one_row(traced):
    onto_vocab = [e for e in _eqns(traced["_prefill_finish"])
                  if e.primitive.name == "dot_general"
                  and VOCAB in e.outvars[0].aval.shape]
    assert len(onto_vocab) == 1
    dot = onto_vocab[0]
    assert _under_head(dot)
    assert int(np.prod(dot.outvars[0].aval.shape)) == VOCAB  # ONE row
    # and nothing under the head's scope has more rows than that one
    for e in _eqns(traced["_prefill_finish"]):
        if _under_head(e):
            for v in e.outvars:
                assert int(np.prod(v.aval.shape)) <= VOCAB, e


def test_the_finish_is_handed_the_heads_leaves_alone(served):
    """Final norm + head kernel (the input table where the two are tied),
    not the whole tree: the launch flattens its arguments on the worker
    thread."""
    leaves = served.family.head_leaves(served.prepared)
    assert set(leaves) == {"ln_f", "lm_head"}
    assert len(jax.tree.leaves(leaves)) <= 3
    assert len(jax.tree.leaves(served.prepared)) > 10
    for got, held in zip(jax.tree.leaves(leaves), jax.tree.leaves(
            {k: served.prepared[k] for k in leaves})):
        assert got is held  # the held arrays themselves: no copy


def test_a_tied_head_hands_the_finish_the_input_table():
    from dnn_tpu.models import llama

    cfg = llama.LlamaConfig(block_size=64, vocab_size=256, n_layer=2,
                            n_head=4, n_kv_head=2, n_embd=64, d_ff=96,
                            tie_word_embeddings=True)
    prepared = gpt.prepare_stacked(
        dict(llama.init(jax.random.PRNGKey(5), cfg)), cfg)
    assert "lm_head" not in prepared
    srv = ContinuousBatcher(cfg, prepared, slots=2, max_len=64,
                            prompt_pad=PAD, logprobs_k=3, seed=11,
                            family=llama.family_rows(cfg))
    assert set(srv.family.head_leaves(prepared)) == {"ln_f", "wte"}
    ids = prompt(23, seed=4)
    got = first(srv, ids)
    want = jax.nn.log_softmax(llama.make_apply(cfg)(
        dict(llama.init(jax.random.PRNGKey(5), cfg)),
        jnp.asarray(ids[None]))[0, -1])
    assert got[0] == int(want.argmax())
    np.testing.assert_allclose(got[1], float(want.max()), atol=2e-5)


# ----------------------------------------------------------------------
# the paths no cell runs carry the hidden row into the same finish
# ----------------------------------------------------------------------

SAMPLED = dict(seed=123, temperature=0.9, top_k=20, top_p=0.9)


def _cold(preset, ids, **kw):
    return first(build(preset, **kw), ids, **SAMPLED)


def _radix_full_hit(preset, ids):
    srv = build(preset, prefix_cache=16)
    first(srv, ids, **SAMPLED)
    c0 = srv.prefill_chunks_run
    got = first(srv, ids, **SAMPLED)
    assert srv.prefill_chunks_run == c0  # zero chunks: the stored row
    return got


def _dense_prefix_full_hit(preset, ids):
    srv = build(preset, kv="dense", prefix_cache=4)
    first(srv, ids, **SAMPLED)
    c0 = srv.prefill_chunks_run
    got = first(srv, ids, **SAMPLED)
    assert srv.prefill_chunks_run == c0 and srv.prefix_hits == 1
    return got


def _prefilled(preset, ids):
    from dnn_tpu.control import handoff

    pay = build(preset).export_prefill(ids)
    assert pay["hidden_row"].shape == (64,)  # C values, not V
    wire = handoff.unpack(handoff.pack(pay))  # as a decode replica gets it
    srv = build(preset)
    got = first(srv, ids, prefilled=wire, **SAMPLED)
    assert srv.prefill_chunks_run == 0
    return got


def _kvtier_adopted(preset, ids):
    from dnn_tpu.kvtier import migrate

    donor = build(preset, prefix_cache=16)
    first(donor, ids, **SAMPLED)
    pay = donor.kvtier_export(ids)
    assert {r.shape for r in pay["hidden_rows"].values()} == {(64,)}
    srv = build(preset, prefix_cache=16)
    srv.kvtier_adopt(migrate.unpack_blocks(migrate.pack_blocks(pay)))
    got = first(srv, ids, **SAMPLED)
    assert srv.prefill_chunks_run == 0  # a full hit on adopted blocks
    return got


def _interleaved(preset, ids):
    srv = build(preset, prefill_chunk_tokens=PAD)
    assert srv._ilv  # the first token is sampled by a later step's finish
    return first(srv, ids, **SAMPLED)


PATHS = {
    # (the path, the prompt's length: a full hit needs whole blocks)
    "radix_full_hit": (_radix_full_hit, 32),
    "dense_prefix_full_hit": (_dense_prefix_full_hit, 32),
    "prefilled": (_prefilled, 39),
    "prefilled_chunk_edge": (_prefilled, 32),
    "kvtier_adopted": (_kvtier_adopted, 32),
    "interleaved": (_interleaved, 39),
    "interleaved_chunk_edge": (_interleaved, 32),
}


@pytest.mark.parametrize("path", list(PATHS))
@pytest.mark.parametrize("preset", ["gpt2-test", "olmoe-test"])
def test_first_token_equals_a_cold_admissions(preset, path):
    fn, plen = PATHS[path]
    ids = prompt(plen, seed=plen)
    kw = {"kv": "dense"} if path == "dense_prefix_full_hit" else {}
    same_first(fn(preset, ids), _cold(preset, ids, **kw))
