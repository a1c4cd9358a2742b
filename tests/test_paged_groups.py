"""ISSUE 51 — the paged decode kernel pays for a group once: a FULL
group's copies are straight-line code, a slot's last (partial) group keeps
the loop over a count known at run time, and a group spans what the call's
shapes ask for (128 to 1024 positions). Interpret mode, every form the
kernel has, against the plain forms; the pools bit-equal."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dnn_tpu.ops.pallas import cached_attention as ca
from tests.test_decode_hotpath import (
    _assert_step_matches,
    _paged_step_case,
    _pinned_span,
)

# Slots by (whole groups of `span` positions, blocks past them), in blocks
# of 16 — and the gate, where a slot between two live ones is off with a
# stale `pos` and a live slot's table
GROUP_LAYOUTS = {
    "full": ([(2, 0), (1, 0)], None),  # every group of both slots whole
    "full+partial": ([(2, 3), (1, 1)], None),
    "short": ([(0, 3), (0, 1), (0, 2)], None),  # fewer blocks than a group
    "off-between": ([(2, 3), (2, 3), (1, 0)], [True, False, True]),
}
# a gate comes with the step's rows: no read-only call has a slot off
LAYOUT_WRITE = [(name, write) for name in sorted(GROUP_LAYOUTS)
                for write in (False, True)
                if write or GROUP_LAYOUTS[name][1] is None]
_IDS = [f"{name}-{'write' if w else 'read'}" for name, w in LAYOUT_WRITE]


def group_layout(name, span, bp=16):
    """-> (pos, gate or None, tables, blocks a slot's table has) of a
    layout: a slot's `pos` is five short of its last block's end (at the
    end, where that block closes a whole group); a gated-off slot's table
    is the first slot's."""
    slots, gate = GROUP_LAYOUTS[name]
    pos = [g * span + b * bp - 1 - (5 if b else 0) for g, b in slots]
    nb = 2 * span // bp + 4
    own = 1 + np.arange(len(pos) * nb).reshape(len(pos), nb)
    tables = np.stack([own[s] if gate is None or gate[s] else own[0]
                       for s in range(len(pos))])
    return pos, gate, tables, nb


@pytest.mark.parametrize("span", [128, 256])
@pytest.mark.parametrize("layout,write", LAYOUT_WRITE, ids=_IDS)
@pytest.mark.parametrize("quant,select", [(False, False), (True, False),
                                          (False, True)],
                         ids=["float", "int8", "set"])
def test_kv_form_over_full_and_partial_groups(span, layout, write, quant,
                                              select):
    pos, gate, tables, nb = group_layout(layout, span)
    got, want, _, _ = _paged_step_case(
        jax.random.PRNGKey(span), pos=pos, nb=nb, quant=quant, select=select,
        gate=(gate or [True] * len(pos)) if write else None, tables=tables,
        span=span)
    _assert_step_matches(got, want)
    if gate is not None:
        assert (np.asarray(got[0])[1] == 0).all()


@pytest.mark.parametrize("span", [128, 512])
@pytest.mark.parametrize("layout,write", LAYOUT_WRITE, ids=_IDS)
@pytest.mark.parametrize("select", [False, True], ids=["all", "set"])
def test_latent_form_over_full_and_partial_groups(span, layout, write,
                                                  select):
    """The ONE leaf of one head (models/mla.py), 40 values stored 128
    lanes wide, five heads as the slot's rows."""
    bp, heads, d, dv, layers, layer = 16, 5, 40, 32, 2, 1
    pos, gate, tables, nb = group_layout(layout, span, bp)
    b = len(pos)
    rng = np.random.default_rng(span + len(layout))
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa: E731
    pool = f(layers, b * nb + 1, 1, bp, 128).at[..., d:].set(0.0)
    q, row = f(b, 1, heads, d), f(b, 1, 1, 128).at[..., d:].set(0.0)
    tables, pos = jnp.asarray(tables, jnp.int32), jnp.asarray(pos, jnp.int32)
    new = (row, jnp.asarray(gate or [True] * b)) if write else None
    sel = None
    if select:
        cols = np.arange(nb * bp)[None, :]
        sel = jnp.asarray((rng.random((b, nb * bp)) < 0.4)
                          | (cols == np.asarray(pos)[:, None]))
    want = ca._reference_latent_step(q, pool, tables, pos, layer, new, dv,
                                     0.3, sel)
    with _pinned_span(span):
        got = ca.paged_decode_attention(
            q, pool, None, tables, pos, layer=jnp.int32(layer), new=new,
            latent=dv, scale=0.3, sel=sel, interpret=True)
    if not write:
        got, want = (got,), (want,)
    assert got[0].shape == (b, 1, heads, dv)
    assert float(jnp.abs(got[0] - want[0]).max()) < 1e-5
    if write:
        # (the plain form scribbles a gated-off slot's row into the junk
        # block 0; the kernel writes nothing for it)
        assert bool((got[1][:, 1:] == want[1][:, 1:]).all())
    if gate is not None:
        assert float(jnp.abs(got[0][1]).max()) == 0.0


def test_the_rule_reads_the_bytes_a_position():
    """`_paged_group`: as wide as a group's copies stay within 1.25 MiB,
    between 128 and 1024 positions, capped by the table — whatever the
    block length, and from nothing but the leaves' shapes and dtypes."""
    bf16, f32, i8 = jnp.bfloat16, jnp.float32, jnp.int8

    def group(shapes, nb, whole=True):
        leaves = [jax.ShapeDtypeStruct(s, dt) for s, dt in shapes]
        return ca.paged_group(leaves, nb, whole=whole)

    for bp in (8, 16, 32, 128):
        kv = lambda hk, dt=bf16, w=128: [  # noqa: E731
            ((3, 9, hk, bp, w), dt)] * 2
        # a latent leaf of 640 lanes; Keye's 4 KV heads; K-EXAONE's and
        # Solar's 8; OLMoE's 16; GPT-2 Large's 20 (lane-padded 64 -> 128)
        assert group([((5, 9, 1, bp, 640), bf16)], 1024) == 1024 // bp
        assert [group(kv(hk), 1024) for hk in (4, 8, 16, 20)] == [
            512 // bp, 256 // bp, 128 // bp, 128 // bp]
        # float32 rows weigh twice bfloat16's; an int8 pool, whose scale
        # blocks lie side by side only at 128, stays there
        assert group(kv(4, f32), 1024) == 256 // bp
        assert group(kv(4, i8) + [((3, 9, 4, bp), f32)] * 2,
                     1024) == 128 // bp
        # the per-layer form: no leading layer axis
        assert group([((9, 8, bp, 128), bf16)] * 2, 1024,
                     whole=False) == 256 // bp
        # never more than the table has, never fewer than one block
        assert group(kv(4), 3) == min(512 // bp, 3)
    assert group([((3, 9, 64, 256, 128), bf16)] * 2, 64) == 1
