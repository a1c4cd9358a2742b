"""The transient row is the chunk program's CARRY (ISSUE 63): every
family's `_prefill_chunk` runs its layer loop through `paged_kvcache.
scan_rows`, which carries the whole row cache and hands a block the row
bound to its layer (`LayerRows`: one read of the layer's row, a write of
the chunk's T positions at `(layer, start_pos)` of the whole leaf).

Held here, for every recorded preset and `mellum2-test`: two chunks and a
padded tail give, bit for bit, the row and the hidden rows that the form
before gave — the row riding the loop as xs in and ys out, each layer's
cut-out written by the block and put back whole (`xs_ys_scan_rows`, the
plain reference) — and the lowered chunk program writes no positional
leaf's whole length."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from dnn_tpu.models import dsa
from dnn_tpu.runtime import generate, paged_kvcache
from dnn_tpu.runtime.paged_kvcache import LayerRows
from dnn_tpu.runtime.serving import ContinuousBatcher
from dnn_tpu.utils.hlo_audit import dus_updates
from tests.step_program_texts import PRESETS, _model

CASES = (*PRESETS, "mellum2-test")
P = 16  # a chunk; the prompt is two of them and a tail of 5


def xs_ys_scan_rows(block, carry, blocks, rows, *xs, layers=None):
    """`scan_rows` as the loops were before ISSUE 63: the stack's range of
    the row's layers rides the scan as xs, each layer's cut-out is handed
    to the block (as a row cache of that ONE layer), and what the block
    leaves of it goes out as ys — the whole cut-out, written back over the
    range. (A leaf with fewer layers than the range is another kind's.)"""
    n = len(jax.tree.leaves(blocks)[0])
    first = 0 if layers is None else int(layers[0])
    ride = {k: v[first:first + n] for k, v in rows.items()
            if len(v) >= first + n}

    def body(carry, layer_in):
        bp, cut, *rest = layer_in
        carry, one = block(bp, carry, LayerRows(
            {k: v[None] for k, v in cut.items()}, 0), *rest)
        return carry, {k: v[0] for k, v in one.leaves.items()}

    carry, ys = lax.scan(body, carry, (blocks, ride, *xs))
    return carry, {**rows, **{k: lax.dynamic_update_slice_in_dim(
        rows[k], v, first, axis=0) for k, v in ys.items()}}


def _batcher(preset):
    spec, cfg, prepared = _model(preset)
    opts = dict(slots=3, max_len=64, prompt_pad=P, kv="auto", block_len=8)
    if "family_rows" in spec.extras:
        opts["family"] = spec.extras["family_rows"]()
    return ContinuousBatcher(cfg, prepared, **opts)


def _chunks(b, prompt):
    """The admission's chunk loop alone -> ([each chunk's results but the
    row], the finished row)."""
    padded = np.zeros((1, -(-len(prompt) // P) * P), np.int32)
    padded[0, :len(prompt)] = prompt
    row, outs = b._new_row(), []
    # primitive by primitive: the two forms then run the SAME compiled
    # arithmetic and differ in how the row moves alone (whole programs
    # are fused apart by the compiler, and round apart in the last bit)
    with jax.disable_jit():
        for c in range(padded.shape[1] // P):
            hidden, row, *stats = b._prefill_chunk(
                b._lora_prefill_view(0), row, padded[:, c * P:(c + 1) * P],
                np.int32(c * P), *b._n_real(len(prompt), c))
            outs.append((hidden, *stats))
    return jax.tree.map(np.asarray, outs), jax.tree.map(np.asarray, row)


@pytest.mark.parametrize("preset", CASES)
def test_the_carried_row_is_the_xs_ys_row_bit_for_bit(preset, monkeypatch):
    prompt = np.arange(1, 2 * P + 6, dtype=np.int32) % 50 + 1
    outs, row = _chunks(_batcher(preset), prompt)
    for mod in (paged_kvcache, generate, dsa):
        monkeypatch.setattr(mod, "scan_rows", xs_ys_scan_rows)
    want_outs, want_row = _chunks(_batcher(preset), prompt)  # a fresh trace
    assert row.keys() == want_row.keys()
    for name in row:
        assert row[name].dtype == want_row[name].dtype, name
        np.testing.assert_array_equal(row[name], want_row[name], name)
        assert np.any(row[name] != 0), name  # the chunks wrote the leaf
    for got, want in zip(jax.tree.leaves(outs), jax.tree.leaves(want_outs)):
        np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("preset", CASES)
def test_no_write_spans_a_positional_leafs_length(preset):
    b = _batcher(preset)
    row = jax.eval_shape(b._new_row)
    text = b._prefill_chunk.lower(
        b._lora_prefill_view(0), row, jnp.zeros((1, P), jnp.int32),
        np.int32(0), *b._n_real(P, 0)).as_text()
    positional = {x.shape: n for n, x in row.items()
                  if n not in b._slot_leaves}
    writes = [(a, u) for a, u in dus_updates(text) if a in positional]
    # every positional leaf is written, a chunk's rows of one layer a write
    assert {a for a, _ in writes} == set(positional)
    for a, u in writes:
        assert u[0] == 1 and u[3] < a[3] and u[3] <= P, (positional[a], u)
