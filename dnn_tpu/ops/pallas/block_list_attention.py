"""Paged decode attention over a LIST of blocks a KV head (models/
block_select.py): slot b's KV head g reads the `count[b, g]` pool blocks its
list names and no other — where `paged_decode_attention` (ops/pallas/
cached_attention.py) walks a slot's live blocks, all of them, for every head
alike.

One grid step is one (slot, KV head): the list's physical block ids ride
scalar prefetch, each listed block's (block_len, d) rows of that head are
copied straight from the pool (which stays in HBM) into a double-buffered
VMEM scratch, `_GROUP` blocks an online-softmax update of the head's R query
rows. The list is in ascending order and its last block is the one that holds
`pos`: that block is read up to `pos`, every other whole. A block that is not
on the list is not copied — the point of selecting by block: 96 of 400 blocks
a slot is a quarter of the bytes. With `new`, the step's own K and V row is
placed in the VMEM copy of the list's last block before it is attended, and
that one block of the head goes back to the pool, which is the kernel's
aliased output — as `paged_decode_attention` does it: an XLA scatter into the
pool beside a kernel that reads it made the chip's compiler copy the whole
leaf four times a step to reconcile their layouts (tests/test_chip_compile.py).
A gated-off slot reads and writes nothing and answers zeros.

`reference_block_list_attention` is the plain form (a gather of the listed
blocks and two einsums): the oracle, and what runs off the TPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_NEG_BIG = -1e30
_GROUP = 16  # blocks an online-softmax update

__all__ = ["block_list_attention", "reference_block_list_attention"]


def reference_block_list_attention(q, kp, vp, ids, count, pos, *, layer,
                                   new=None):
    """q (B, Hk, R, D); kp / vp the WHOLE pool (L, n_blocks, Hk, bp, Dp); ids
    (B, Hk, n) physical blocks, the first `count` (B, Hk) of them read, the
    last of those up to `pos` (B,) -> (B, Hk, R, D) float32. With `new` = (k
    (B, Hk, 1, Dp), v, gate (B,)): the rows placed first at `pos` in the last
    listed block (a gated-off slot's at junk block 0 and its answer zeros)
    -> (out, kp, vp)."""
    b, hk, _, d = q.shape
    bp = kp.shape[-2]
    n = ids.shape[-1]
    heads = jnp.arange(hk)[None, :, None]
    if new is not None:
        k_new, v_new, gate = new
        last = jnp.take_along_axis(
            ids, jnp.maximum(count - 1, 0)[..., None], axis=2)[..., 0]
        live = gate[:, None] & (count > 0)
        blk = jnp.where(live, last, 0)
        row = jnp.where(live, (pos % bp)[:, None], 0)
        at = (layer, blk, heads[..., 0], row)
        kp = kp.at[at].set(k_new[:, :, 0].astype(kp.dtype))
        vp = vp.at[at].set(v_new[:, :, 0].astype(vp.dtype))
        out = reference_block_list_attention(
            q, kp, vp, ids, jnp.where(gate[:, None], count, 0), pos,
            layer=layer)
        return jnp.where(live[..., None, None], out, 0.0), kp, vp

    def view(pool):  # -> (B, Hk, n * bp, D)
        g = pool[layer, ids, heads]
        return g.reshape(b, hk, n * bp, -1)[..., :d].astype(jnp.float32)

    k, v = view(kp), view(vp)
    s = jnp.einsum("bhrd,bhsd->bhrs", q.astype(jnp.float32), k,
                   preferred_element_type=jnp.float32) / jnp.sqrt(d)
    entry = jnp.repeat(jnp.arange(n), bp)[None, None, :]
    row = jnp.tile(jnp.arange(bp), n)[None, None, :]
    last = (count - 1)[..., None]
    keep = (entry < last) | ((entry == last)
                             & (row <= (pos % bp)[:, None, None]))
    s = jnp.where(keep[:, :, None, :], s, _NEG_BIG)
    return jnp.einsum("bhrs,bhsd->bhrd", jax.nn.softmax(s, axis=-1), v,
                      preferred_element_type=jnp.float32)


def _kernel(ids_ref, count_ref, pos_ref, lay_ref, q_ref, *refs, scale, n,
            bp, group, write):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if write:
        # the pool is read where it is written: through the aliased output
        _, _, knew_ref, vnew_ref, o_ref, kp, vp, *refs = refs
    else:
        kp, vp, o_ref, *refs = refs
    kbuf, vbuf, sem, m_scr, l_scr, acc_scr = refs

    b, g, hk = pl.program_id(0), pl.program_id(1), pl.num_programs(1)
    at = b * hk + g
    base, cnt = at * n, count_ref[at]
    n_groups = (cnt + group - 1) // group

    def copies(gi, buf_i, go):
        """Start, or wait for, the copies of the listed blocks of group gi
        into buffer `buf_i`: nothing is copied past the list's count."""
        def block(j, _):
            blk = ids_ref[base + gi * group + j]
            for pool, buf in ((kp, kbuf), (vp, vbuf)):
                getattr(pltpu.make_async_copy(
                    pool.at[lay_ref[0], blk, g],
                    buf.at[buf_i, pl.ds(pl.multiple_of(j * bp, bp), bp)],
                    sem.at[buf_i]), go)()

        jax.lax.fori_loop(0, jnp.clip(cnt - gi * group, 0, group), block,
                          None)

    @pl.when((b == 0) & (g == 0))
    def _first():
        # a group's unread tail must hold numbers: 0 x it has to be 0
        kbuf[...] = jnp.zeros_like(kbuf)
        vbuf[...] = jnp.zeros_like(vbuf)

    @pl.when(n_groups == 0)
    def _empty():  # a stale slot whose list is empty
        o_ref[...] = jnp.zeros_like(o_ref)

    def attend(gi, _):
        buf_i = jax.lax.rem(gi, 2)

        @pl.when(gi + 1 < n_groups)
        def _():
            copies(gi + 1, 1 - buf_i, "start")

        copies(gi, buf_i, "wait")
        if write:
            # the list's last block holds `pos`: the step's rows go into its
            # buffered copy before it is attended, and it goes back meanwhile
            holds_pos = gi == n_groups - 1
            start = pl.multiple_of((cnt - 1 - gi * group) * bp, bp)

            def write_back(go):
                blk = ids_ref[base + cnt - 1]
                for pool, buf in ((kp, kbuf), (vp, vbuf)):
                    getattr(pltpu.make_async_copy(
                        buf.at[buf_i, pl.ds(start, bp)],
                        pool.at[lay_ref[0], blk, g], sem.at[2]), go)()

            @pl.when(holds_pos)
            def _place():
                for buf, new in ((kbuf, knew_ref), (vbuf, vnew_ref)):
                    view = buf.at[buf_i, pl.ds(start, bp)]  # (bp, d)
                    rows = view[...].astype(jnp.float32)
                    here = jax.lax.broadcasted_iota(
                        jnp.int32, rows.shape, 0) == pos_ref[b] % bp
                    view[...] = jnp.where(
                        here, new[0, 0].astype(jnp.float32), rows
                    ).astype(buf.dtype)
                write_back("start")

        k, v = kbuf[buf_i], vbuf[buf_i]  # (G * bp, d)
        narrow = q_ref.dtype == jnp.bfloat16 and k.dtype == jnp.bfloat16
        cdt = jnp.bfloat16 if narrow else jnp.float32
        s = jax.lax.dot_general(
            q_ref[0, 0].astype(cdt), k.astype(cdt), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (R, G * bp)
        col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        entry = gi * group + col // bp
        seen = (entry < cnt - 1) | (
            (entry == cnt - 1) & (col % bp <= pos_ref[b] % bp))
        s = jnp.where(seen, s, _NEG_BIG)
        m_prev = m_scr[:, :1]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(seen, jnp.exp(s - m_new), 0.0)
        l_scr[...] = jnp.broadcast_to(
            l_scr[:, :1] * alpha + p.sum(axis=-1, keepdims=True),
            l_scr.shape)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v.astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        if write:
            pl.when(holds_pos)(lambda: write_back("wait"))

    @pl.when(n_groups > 0)
    def _live():
        m_scr[...] = jnp.full_like(m_scr, _NEG_BIG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)
        copies(0, 0, "start")
        jax.lax.fori_loop(0, n_groups, attend, None)
        o_ref[0, 0] = (acc_scr[...] / l_scr[:, :1]).astype(o_ref.dtype)


@jax.named_scope("attn.block_decode")
def block_list_attention(q, kp, vp, ids, count, pos, *, layer, new=None,
                         interpret=None):
    """The read of a list of blocks a KV head (module docstring): q (B, Hk,
    R, D); kp / vp the WHOLE pool (L, n_blocks, Hk, bp, Dp), `layer` its
    index (a traced scalar); ids (B, Hk, n) int32 PHYSICAL block ids in the
    list's order, count (B, Hk) how many are read, pos (B,) the queries'
    positions -> (B, Hk, R, D) float32. With `new` = (k (B, Hk, 1, Dp), v,
    gate (B,)) — this step's rows as the pool stores them — the kernel also
    WRITES them at `pos` and the pools come back through aliased outputs:
    -> (out, kp, vp); a gated-off slot is empty. The Pallas kernel on the TPU
    (`interpret=True`: interpreted, for the CPU tests), the plain form
    elsewhere and for rows narrower than 128 lanes."""
    if interpret is None and jax.default_backend() == "tpu":
        interpret = False
    if interpret is None or not (interpret or kp.shape[-1] % 128 == 0):
        return reference_block_list_attention(q, kp, vp, ids, count, pos,
                                              layer=layer, new=new)
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, hk, r, d_q = q.shape
    d, bp, n = kp.shape[-1], kp.shape[-2], ids.shape[-1]
    if d != d_q:
        q = jnp.pad(q, [(0, 0)] * 3 + [(0, d - d_q)])
    group = min(_GROUP, n)
    write = new is not None
    qspec = pl.BlockSpec((1, 1, r, d), lambda bi, gi, *_: (bi, gi, 0, 0))
    in_place = pl.BlockSpec(memory_space=pl.ANY)  # a leaf, left in HBM
    in_specs, rows = [qspec, in_place, in_place], ()
    out_specs = qspec
    out_shape = jax.ShapeDtypeStruct((b, hk, r, d), jnp.float32)
    aliases = {}
    if write:
        *rows, gate = new
        count = jnp.where(gate[:, None], count, 0)
        in_specs += [pl.BlockSpec((1, 1, 1, d),
                                  lambda bi, gi, *_: (bi, gi, 0, 0))] * 2
        out_specs = [qspec, in_place, in_place]
        out_shape = [out_shape, jax.ShapeDtypeStruct(kp.shape, kp.dtype),
                     jax.ShapeDtypeStruct(vp.shape, vp.dtype)]
        # operand numbers count the four scalars, then q
        aliases = {5: 1, 6: 2}
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(b, hk),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((2, group * bp, d), kp.dtype),
            pltpu.VMEM((2, group * bp, d), vp.dtype),
            pltpu.SemaphoreType.DMA((3,)),
            pltpu.VMEM((r, 128), jnp.float32),  # running row max
            pltpu.VMEM((r, 128), jnp.float32),  # running row sum
            pltpu.VMEM((r, d), jnp.float32),    # accumulator
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, scale=d_q ** -0.5, n=n, bp=bp,
                          group=group, write=write),
        grid_spec=grid_spec,
        out_shape=out_shape,
        input_output_aliases=aliases,
        compiler_params=pltpu.CompilerParams(
            # the first step clears the buffers, and the steps share them
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="block_list_attention",
    )(ids.reshape(-1).astype(jnp.int32), count.reshape(-1).astype(jnp.int32),
      pos.astype(jnp.int32), jnp.asarray(layer, jnp.int32).reshape(1), q, kp,
      vp, *rows)
    if write:
        return (out[0][..., :d_q], *out[1:])
    return out[..., :d_q]
