"""Pallas TPU kernel for a prefill chunk of latent attention (models/
mla.py), up-projected: T queries at positions [start, start + T) against
the keys and values of everything before them and themselves.

A head's key is two parts of different kinds: `k_nope` (Dn wide, its own,
up-projected from the cached latent) and `k_rope` (Dr wide, ONE for all
heads, cached beside the latent), and its value is Dv wide — 128 | 64 and
128 for the DeepSeek-V3 family, so the key (192) is wider than the value.
The kernel takes the parts as they are and never builds a 192-wide key:

    s[t, j] = (q_nope[t] . k_nope[j] + q_rope[t] . k_rope[j]) * scale

two products a tile (the MXU makes two passes over a 192-wide contraction
anyway), the second against the shared rope keys, which so stream once a
head without a copy a head. Causal, flash-style: the online softmax runs
over column tiles, a tile wholly past a query tile's last position is
neither fetched (its block index is clamped to the last live one, and a
repeated index is not copied again) nor computed, and `start` rides scalar
prefetch, so one compiled program serves every chunk start.

Two narrowings of what a query reads, both off by default (models/mla.py's
layer kinds): `window` = W bands it to the columns > its position - W — a
column tile wholly behind the band of a query tile's first row is skipped
like one past its last, so a chunk that was handed window + chunk
positions pays for those; `sel` (T, S) is a set a query (an indexer's
choice, within the causal limit), one more (bq, bs) int8 tile a step,
applied inside the online softmax as ops/pallas/sparse_attention.py does.

`reference_mla_prefill_attention` is the plain form: what runs off the TPU
and for shapes that do not tile, and the oracle of tests/test_mla.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from dnn_tpu.ops.pallas.sparse_attention import _last_live, _tiles

_NEG_BIG = -1e30

__all__ = ["mla_prefill_attention", "reference_mla_prefill_attention"]


def reference_mla_prefill_attention(q_nope, q_rope, k_nope, k_rope, v, start,
                                    *, scale, window=None, sel=None):
    """q_nope (H, T, Dn), q_rope (H, T, Dr), k_nope (H, S, Dn), k_rope
    (S, Dr), v (H, S, Dv) -> (H, T, Dv) float32: query t at position
    start + t reads the columns <= start + t (with `window`, those >
    start + t - window; with `sel` (T, S), those it is true at)."""
    f32 = jnp.float32
    s = (jnp.einsum("htd,hsd->hts", q_nope.astype(f32), k_nope.astype(f32),
                    preferred_element_type=f32)
         + jnp.einsum("htd,sd->hts", q_rope.astype(f32), k_rope.astype(f32),
                      preferred_element_type=f32)) * scale
    t, s_len = s.shape[1:]
    cols, rows = jnp.arange(s_len)[None, :], start + jnp.arange(t)[:, None]
    keep = cols <= rows
    if window is not None:
        keep = keep & (cols > rows - window)
    if sel is not None:
        keep = keep & sel
    s = jnp.where(keep[None], s, _NEG_BIG)
    return jnp.einsum("hts,hsd->htd", jax.nn.softmax(s, axis=-1),
                      v.astype(f32), preferred_element_type=f32)


def _first_live(start_ref, qi, bq, bs, window):
    """The first column tile that holds a position some row of query
    tile qi may read: 0 without a window."""
    if window is None:
        return 0
    return jnp.maximum(start_ref[0] + qi * bq - window + 1, 0) // bs


def _kernel(start_ref, qn_ref, qr_ref, kn_ref, kr_ref, v_ref, *rest,
            scale, bq, bs, window=None, select=False):
    from jax.experimental import pallas as pl

    sel_ref = None
    if select:
        sel_ref, *rest = rest
    o_ref, m_scr, l_scr, acc_scr = rest

    qi, si, ns = pl.program_id(1), pl.program_id(2), pl.num_programs(2)

    @pl.when(si == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_BIG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when((si <= _last_live(start_ref, qi, bq, bs))
             & (si >= _first_live(start_ref, qi, bq, bs, window)))
    def _step():
        contract = (((1,), (1,)), ((), ()))
        s = (jax.lax.dot_general(qn_ref[0], kn_ref[0], contract,
                                 preferred_element_type=jnp.float32)
             + jax.lax.dot_general(qr_ref[0], kr_ref[...], contract,
                                   preferred_element_type=jnp.float32)
             ) * scale  # (bq, bs)
        rows = start_ref[0] + qi * bq + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 0)
        cols = si * bs + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        keep = cols <= rows
        if window is not None:
            keep = keep & (cols > rows - window)
        if select:
            keep = keep & (sel_ref[...].astype(jnp.int32) != 0)
        s = jnp.where(keep, s, _NEG_BIG)
        m_prev = m_scr[:, :1]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(keep, jnp.exp(s - m_new), 0.0)
        l_scr[...] = jnp.broadcast_to(
            l_scr[:, :1] * alpha + p.sum(axis=-1, keepdims=True),
            l_scr.shape)
        v = v_ref[0]
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)

    @pl.when(si == ns - 1)
    def _finish():
        o_ref[0] = (acc_scr[...] / l_scr[:, :1]).astype(o_ref.dtype)


@jax.named_scope("attn.mla_prefill")
def mla_prefill_attention(q_nope, q_rope, k_nope, k_rope, v, start, *,
                          scale, block_q=512, block_s=512, interpret=None,
                          window=None, sel=None):
    """A chunk's causal attention with a two-part key (module docstring):
    q_nope (H, T, Dn) and q_rope (H, T, Dr) the queries at [start, start +
    T), k_nope (H, S, Dn), k_rope (S, Dr) shared by the heads, v (H, S,
    Dv) -> (H, T, Dv) float32; `window` (a static int) and `sel` (T, S)
    bool as the module docstring says. The kernel on the TPU (`interpret=True`:
    interpreted, for the CPU tests); the plain form elsewhere and for
    shapes that do not tile."""
    h, t, _ = q_nope.shape
    s_len, dv = v.shape[1:]
    tiles = _tiles(t, s_len, block_q, block_s)
    if interpret is None and jax.default_backend() == "tpu":
        interpret = False
    if interpret is None or tiles is None:
        return reference_mla_prefill_attention(
            q_nope, q_rope, k_nope, k_rope, v, start, scale=scale,
            window=window, sel=sel)
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bq, bs = tiles
    # the rope parts fill whole 128-lane tiles (zeros add nothing to a
    # score; the MXU's pass over 64 lanes costs what one over 128 does)
    pad = -q_rope.shape[-1] % 128
    if pad:
        q_rope = jnp.pad(q_rope, ((0, 0), (0, 0), (0, pad)))
        k_rope = jnp.pad(k_rope, ((0, 0), (0, pad)))

    def col(i, j, st):
        return jnp.clip(j, _first_live(st, i, bq, bs, window),
                        _last_live(st, i, bq, bs))

    def of_query(x):
        return pl.BlockSpec((1, bq, x.shape[-1]),
                            lambda hd, i, j, st: (hd, i, 0))

    def of_head(x):
        return pl.BlockSpec((1, bs, x.shape[-1]),
                            lambda hd, i, j, st: (hd, col(i, j, st), 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(h, t // bq, s_len // bs),
        in_specs=[of_query(q_nope), of_query(q_rope), of_head(k_nope),
                  pl.BlockSpec((bs, k_rope.shape[-1]),
                               lambda hd, i, j, st: (col(i, j, st), 0)),
                  of_head(v)] + ([] if sel is None else [
                      pl.BlockSpec((bq, bs),
                                   lambda hd, i, j, st: (i, col(i, j, st)))]),
        out_specs=pl.BlockSpec((1, bq, dv), lambda hd, i, j, st: (hd, i, 0)),
        scratch_shapes=[
            pltpu.VMEM((bq, 128), jnp.float32),  # running row max
            pltpu.VMEM((bq, 128), jnp.float32),  # running row sum
            pltpu.VMEM((bq, dv), jnp.float32),   # output accumulator
        ],
    )
    return pl.pallas_call(
        functools.partial(_kernel, scale=scale, bq=bq, bs=bs, **(
            {} if window is None else {"window": window}), **(
            {} if sel is None else {"select": True})),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((h, t, dv), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret, name="mla_prefill_attention",
    )(jnp.asarray(start, jnp.int32).reshape(1), q_nope, q_rope, k_nope,
      k_rope, v, *(() if sel is None else (sel.astype(jnp.int8),)))
