"""Pallas TPU kernel for attention AGAINST A KV CACHE — the serving hot
loop (decode + chunked prefill).

Why `flash_attention.py` doesn't cover this: the cache path's masking is
positional against a PREALLOCATED buffer — query token i (at absolute
position pos+i) may attend cache columns <= pos+i, where `pos` is a
RUNTIME value (a decode slot's current length, a prefill chunk's start).
The flash kernel's causal offset is a compile-time constant baked into the
kernel closure; specializing on it would recompile per chunk index and per
decode length — exactly what the serving runtime's three-program contract
forbids (dnn_tpu/runtime/serving.py). Here the limit arrives as a small
array input instead, one scalar per (batch, head) program, so ONE compiled
kernel serves every chunk start and every slot position.

Second serving-specific capability: the cache may be stored int8 with
per-(position, head) scales (dnn_tpu/runtime/kvcache.Int8KV). The kernel
streams the int8 bytes directly from HBM and folds the scales into the
score matrix / probability matrix inside VMEM — the dequantized cache
never exists in HBM, which is the entire point of quantizing a
bandwidth-bound loop. (The XLA einsum path expresses the same math, but
whether the f32 upcast fuses into the dot or materializes is the
compiler's choice; the kernel makes the 1-byte-per-element read a
guarantee.)

Decode is the degenerate case T=1 with a per-slot position vector — same
kernel, block_q=1 grid row.

Numerics: online softmax (running row max / row sum) in f32, identical to
`reference_cached_attention` below, which is also the fallback for
non-TPU backends and non-tiling shapes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_NEG_BIG = -1e30


# ----------------------------------------------------------------------
# reference (fallback + test oracle) — the kvcache.py einsum math
# ----------------------------------------------------------------------

def reference_cached_attention(q, k, v, pos, *, ks=None, vs=None):
    """q (B, H, T, D) at absolute positions pos[b] + t; k/v (B, H, S, D)
    cache buffers (any float dtype, or int8 with `ks`/`vs` scales
    (B, H, S)); pos (B,) int32. Row (b, t) attends columns
    <= pos[b] + t. Returns (B, H, T, D) f32."""
    d = q.shape[-1]
    s = jnp.einsum("bhtd,bhsd->bhts", q.astype(jnp.float32),
                   k.astype(jnp.float32),
                   preferred_element_type=jnp.float32)
    if ks is not None:
        s = s * ks[:, :, None, :]
    s = s / jnp.sqrt(d)
    cols = jnp.arange(k.shape[2])
    rows = jnp.arange(q.shape[2])
    limit = pos[:, None, None, None] + rows[None, None, :, None]
    s = jnp.where(cols[None, None, None, :] <= limit, s, _NEG_BIG)
    p = jax.nn.softmax(s, axis=-1)
    if vs is not None:
        p = p * vs[:, :, None, :]
    return jnp.einsum("bhts,bhsd->bhtd", p, v.astype(jnp.float32),
                      preferred_element_type=jnp.float32)


# ----------------------------------------------------------------------
# kernel
# ----------------------------------------------------------------------

def _cached_attn_kernel(pos_ref, q_ref, k_ref, v_ref, *rest,
                        scale, block_q, block_s, quant):
    from jax.experimental import pallas as pl

    # the quant variant carries two extra scale inputs; the float variant
    # omits them entirely (no placeholder traffic — see _kernel_call)
    if quant:
        ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr = rest
    else:
        ks_ref = vs_ref = None
        o_ref, m_scr, l_scr, acc_scr = rest

    qi = pl.program_id(1)
    si = pl.program_id(2)
    ns = pl.num_programs(2)

    @pl.when(si == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_BIG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # base position for this (batch, head) program: a RUNTIME scalar, read
    # from the scalar-prefetch ref (SMEM) — scalars driving control flow
    # must not come from VMEM vector lanes on real hardware
    pos = pos_ref[pl.program_id(0)]
    # dead cache block iff its first column exceeds the block's largest
    # row limit (pos + last row index). Unlike flash_attention this is a
    # DYNAMIC predicate — pl.when skips the block's COMPUTE (the BlockSpec
    # pipeline still fetches every block; the bandwidth story is the int8
    # byte width and fused dequant, not block skipping).
    live = si * block_s <= pos + (qi + 1) * block_q - 1

    @pl.when(live)
    def _step():
        q = q_ref[0].astype(jnp.float32)  # (block_q, d)
        k = k_ref[0].astype(jnp.float32)  # (block_s, d) — int8 streams raw
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (block_q, block_s)
        if quant:
            s = s * ks_ref[0]  # (1, block_s) per-position K scales
        s = s * scale

        rows = jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_s), 0) + qi * block_q
        cols = jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_s), 1) + si * block_s
        s = jnp.where(cols <= pos + rows, s, _NEG_BIG)

        m_prev = m_scr[:, :1]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        if quant:
            # V scale folds into the (small) probability matrix; the raw
            # int8 V contracts directly (scales commute — kvcache.py)
            pv = p * vs_ref[0]
        else:
            pv = p
        v = v_ref[0].astype(jnp.float32)
        l_new = l_scr[:, :1] * alpha + p.sum(axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            pv, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(si == ns - 1)
    def _finish():
        o_ref[0] = (acc_scr[...] / l_scr[:, :1]).astype(o_ref.dtype)


def _kernel_call(q3, k3, v3, pos1d, ks3, vs3, *, block_q, block_s, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bh, t, d = q3.shape
    s_len = k3.shape[1]
    nq, ns = t // block_q, s_len // block_s
    quant = ks3 is not None
    kernel = functools.partial(
        _cached_attn_kernel, scale=1.0 / (d ** 0.5), block_q=block_q,
        block_s=block_s, quant=quant,
    )
    # index maps gain a TRAILING scalar-prefetch ref argument (unused here
    # — blocks are addressed by grid coordinates alone)
    qspec = pl.BlockSpec((1, block_q, d), lambda b, qi, si, p: (b, qi, 0))
    sspec = pl.BlockSpec((1, block_s, d), lambda b, qi, si, p: (b, si, 0))
    scale_spec = pl.BlockSpec((1, 1, block_s),
                              lambda b, qi, si, p: (b, 0, si))
    in_specs = [qspec, sspec, sspec]
    args = [q3, k3, v3]
    if quant:
        in_specs += [scale_spec, scale_spec]
        args += [ks3, vs3]
    # pos rides scalar prefetch: the whole (bh,) vector lands in SMEM and
    # each program reads its scalar — the supported pattern for runtime
    # values steering pl.when control flow on real hardware
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(bh, nq, ns),
        in_specs=in_specs,
        out_specs=qspec,
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),  # running row max
            pltpu.VMEM((block_q, 128), jnp.float32),  # running row sum
            pltpu.VMEM((block_q, d), jnp.float32),    # output accumulator
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bh, t, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
        name="cached_attention",
    )(pos1d, *args)


# The scopes on the three entry points (attn.prefill / attn.decode /
# attn.paged_decode) and the kernels' `name=` are what a device trace
# calls this file's work (the HLO op_name path; the custom call's own
# name) — chipbench/spans.py reads them, so renaming one moves a metric.
@jax.named_scope("attn.prefill")
def cached_attention(q, k, v, pos, *, ks=None, vs=None, block_q=128,
                     block_s=128, interpret=None):
    """Cache attention with runtime position limits (see module docstring).

    q (B, H, T, D); k/v (B, H, S, D) — float, or int8 with ks/vs (B, H, S)
    scales; pos (B,) int32 base positions (row t attends cols
    <= pos[b] + t). Returns (B, H, T, D) f32.

    Dispatches to the Pallas kernel on TPU when S tiles by `block_s`
    (T tiles by block_q, or T < block_q which shrinks the q block);
    otherwise runs the identical-math reference. `interpret=True` forces
    the kernel in interpreter mode (CPU CI)."""
    b, h, t, d = q.shape
    s_len = k.shape[2]
    on_tpu = jax.default_backend() == "tpu"
    if interpret is None:
        if not on_tpu:
            return reference_cached_attention(q, k, v, pos, ks=ks, vs=vs)
        interpret = False
    if t <= block_q:
        block_q = t  # decode: T=1 -> one q row per program
    tiles = (s_len % block_s == 0 and t % block_q == 0)
    if not tiles:
        return reference_cached_attention(q, k, v, pos, ks=ks, vs=vs)

    bh = b * h
    q3 = q.reshape(bh, t, d)
    k3 = k.reshape(bh, s_len, d)
    v3 = v.reshape(bh, s_len, d)
    # per-(batch, head) base position: heads share their batch row's limit
    pos1d = jnp.repeat(pos.astype(jnp.int32), h)
    ks3 = ks.reshape(bh, 1, s_len).astype(jnp.float32) if ks is not None else None
    vs3 = vs.reshape(bh, 1, s_len).astype(jnp.float32) if vs is not None else None
    out = _kernel_call(q3, k3, v3, pos1d, ks3, vs3, block_q=block_q,
                       block_s=block_s, interpret=interpret)
    return out.reshape(b, h, t, d)


# ----------------------------------------------------------------------
# decode-specialized kernel (T=1 steps; all query rows share the slot's
# position limit)
# ----------------------------------------------------------------------
#
# Why the general kernel above fails at decode: with block_q=1 its grid is
# (B*H, 1, S/128) — thousands of programs each DMAing a 128-row cache tile
# (~32 KB), a latency-bound pipeline. Decode attention is
# pure bandwidth: the right shape is FEW programs streaming BIG blocks.
# This kernel folds all heads into one program — grid (B, S/block_s),
# each step DMAing an (Hk, block_s, D) K and V slab (hundreds of KB) —
# and clamps the cache index map at the slot's live limit, so blocks past
# `pos` are never fetched (Pallas skips the copy when consecutive grid
# steps map to the same block): per-step traffic scales with the ACTIVE
# context, not the allocation.
#
# On a DENSE cache with D=64 the block's minor dim fills only half of the
# 128 VMEM lanes, so every DMA moves half-empty tiles, and XLA's einsum
# already fuses the int8 dequant into its read: a dense cache takes this
# kernel only under `attn_kernel="auto"`'s length rule
# (kvcache.AUTO_KERNEL_MIN_S) or when asked, and is otherwise served by
# the einsum. This kernel is also the runtime-position chunked prefill
# program (which flash_attention.py cannot express). The paged pool's
# kernel below is the one the chip benchmark measures (PERF.md section 5).
#
# The query is (B, Hk, R, D): R rows per KV head, ALL sharing their
# slot's limit pos[b]. R=1 is plain MHA decode; R=G covers GQA's folded
# query groups (models/llama.py decode) — the fold that the general
# kernel's +row masking contract had to exclude.


def reference_decode_attention(q, k, v, pos, *, ks=None, vs=None):
    """q (B, Hk, R, D) decode rows; every row of slot b attends cache
    columns <= pos[b]. k/v (B, Hk, S, D) float — or int8 with ks/vs
    (B, Hk, S) scales. Returns (B, Hk, R, D) f32. Identical math to
    FloatKV/Int8KV.attend_rows' einsum (dnn_tpu/runtime/kvcache.py)."""
    d = q.shape[-1]
    s = jnp.einsum("bhrd,bhsd->bhrs", q.astype(jnp.float32),
                   k.astype(jnp.float32),
                   preferred_element_type=jnp.float32)
    if ks is not None:
        s = s * ks[:, :, None, :]
    s = s / jnp.sqrt(d)
    cols = jnp.arange(k.shape[2])
    s = jnp.where(cols[None, None, None, :] <= pos[:, None, None, None],
                  s, _NEG_BIG)
    p = jax.nn.softmax(s, axis=-1)
    if vs is not None:
        p = p * vs[:, :, None, :]
    return jnp.einsum("bhrs,bhsd->bhrd", p, v.astype(jnp.float32),
                      preferred_element_type=jnp.float32)


def _decode_attn_kernel(pos_ref, q_ref, k_ref, v_ref, *rest,
                        scale, block_s, quant):
    from jax.experimental import pallas as pl

    if quant:
        ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr = rest
    else:
        ks_ref = vs_ref = None
        o_ref, m_scr, l_scr, acc_scr = rest

    si = pl.program_id(1)
    ns = pl.num_programs(1)

    @pl.when(si == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_BIG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    pos = pos_ref[pl.program_id(0)]
    # blocks past the live limit: index map re-targets them at the limit
    # block (no DMA — see _decode_call) and compute is skipped here
    live = si * block_s <= pos

    @pl.when(live)
    def _step():
        q = q_ref[0].astype(jnp.float32)   # (Hk, R, d)
        k = k_ref[0].astype(jnp.float32)   # (Hk, block_s, d)
        hk, r, d = q.shape
        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )  # (Hk, R, block_s)
        if quant:
            s = s * ks_ref[0][:, None, :]
        s = s * scale
        s2 = s.reshape(hk * r, block_s)
        cols = jax.lax.broadcasted_iota(
            jnp.int32, (hk * r, block_s), 1) + si * block_s
        s2 = jnp.where(cols <= pos, s2, _NEG_BIG)

        m_prev = m_scr[:, :1]
        m_new = jnp.maximum(m_prev, s2.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s2 - m_new)  # (Hk*R, block_s)
        if quant:
            # V scales broadcast over the R query rows of each KV head
            pv = p.reshape(hk, r, block_s) * vs_ref[0][:, None, :]
        else:
            pv = p.reshape(hk, r, block_s)
        v = v_ref[0].astype(jnp.float32)   # (Hk, block_s, d)
        out = jax.lax.dot_general(
            pv, v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )  # (Hk, R, d)
        l_new = l_scr[:, :1] * alpha + p.sum(axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + out.reshape(hk * r, d)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(si == ns - 1)
    def _finish():
        hk, r, d = q_ref.shape[1:]
        o_ref[0] = (acc_scr[...] / l_scr[:, :1]).reshape(hk, r, d) \
            .astype(o_ref.dtype)


def _decode_call(q, k, v, pos1d, ks, vs, *, block_s, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, hk, r, d = q.shape
    s_len = k.shape[2]
    ns = s_len // block_s
    quant = ks is not None
    kernel = functools.partial(
        _decode_attn_kernel, scale=1.0 / (d ** 0.5), block_s=block_s,
        quant=quant,
    )

    # cache blocks clamp their index at the slot's last LIVE block:
    # consecutive grid steps past the limit map to the same block, and the
    # Pallas TPU pipeline skips the copy when a block index repeats —
    # dead allocation is never streamed.
    def _cache_map(bi, si, p):
        return (bi, 0, jnp.minimum(si, p[bi] // block_s), 0)

    def _scale_map(bi, si, p):
        return (bi, 0, jnp.minimum(si, p[bi] // block_s))

    qspec = pl.BlockSpec((1, hk, r, d), lambda bi, si, p: (bi, 0, 0, 0))
    cspec = pl.BlockSpec((1, hk, block_s, d), _cache_map)
    in_specs = [qspec, cspec, cspec]
    args = [q, k, v]
    if quant:
        in_specs += [pl.BlockSpec((1, hk, block_s), _scale_map)] * 2
        args += [ks, vs]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, ns),
        in_specs=in_specs,
        out_specs=qspec,
        scratch_shapes=[
            pltpu.VMEM((hk * r, 128), jnp.float32),  # running row max
            pltpu.VMEM((hk * r, 128), jnp.float32),  # running row sum
            pltpu.VMEM((hk * r, d), jnp.float32),    # output accumulator
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hk, r, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
        name="decode_attention",
    )(pos1d, *args)


# ----------------------------------------------------------------------
# paged flash-decode kernel (block-table cache: runtime/paged_kvcache.py)
# ----------------------------------------------------------------------
#
# The paged pool's einsum baseline MATERIALIZES a dense (B, H, S_max, D)
# view of every slot's blocks each step (PagedKV.gather_view) — a full
# logical-cache copy in HBM before attention even starts, which is the
# one place the paged layout pays bandwidth the dense layout doesn't.
# This kernel removes the materialization: the slot's block TABLE rides
# scalar prefetch, and each grid step's index map chases the table to DMA
# the PHYSICAL block straight from the pool into VMEM. Two clamps do the
# live-length work:
#   * logical blocks past the slot's live limit re-target the last live
#     block (repeated index -> the Pallas pipeline skips the copy), so
#     per-step traffic scales with each slot's ACTUAL context — the pool
#     analog of _decode_call's position clamp;
#   * columns past `pos` are masked inside the online softmax as usual.
# int8 pools stream their 1-byte payload with the per-(position, head)
# scales folded in VMEM, exactly like the dense decode kernel. (int4
# pools stay on the einsum: sub-byte VMEM loads are not wired.)


def reference_paged_decode_attention(q, kp, vp, tables, pos, *, ks=None,
                                     vs=None):
    """Oracle for the paged kernel: gather the dense view, then the
    dense decode reference. q (B, Hk, R, D); kp/vp (n_blocks, Hk, bp, D)
    pool; tables (B, nb_max) int32; pos (B,). Returns (B, Hk, R, D) f32."""
    b, nb = tables.shape
    bp = kp.shape[2]

    def view(leaf):
        g = jnp.take(leaf, tables.reshape(-1), axis=0)
        hk = g.shape[1]
        rest = g.shape[3:]
        g = g.reshape(b, nb, hk, bp, *rest)
        g = jnp.moveaxis(g, 1, 2)
        return g.reshape(b, hk, nb * bp, *rest)

    d = q.shape[-1]  # a pool may store its rows lane-padded, wider than q
    return reference_decode_attention(
        q, view(kp)[..., :d], view(vp)[..., :d], pos,
        ks=view(ks) if ks is not None else None,
        vs=view(vs) if vs is not None else None)


def _reference_paged_step(q, pools, tables, pos, layer, new):
    """paged_decode_attention's whole-pool forms in plain jnp: place
    `new`'s rows at [layer, block, :, row] (gated-off slots at junk
    block 0, row 0), then the oracle on that layer. Returns what the
    kernel does: the attention output, and with `new` the pools too."""
    bp = pools[0].shape[-2]
    if new is not None:
        *rows, gate = new
        blk = jnp.take_along_axis(tables, (pos // bp)[:, None], axis=1)[:, 0]
        blk, row = jnp.where(gate, blk, 0), jnp.where(gate, pos % bp, 0)
        pools = [p.at[layer, blk, :, row].set(r[:, :, 0])
                 for p, r in zip(pools, rows)]
    kp, vp, *scales = [p[layer] for p in pools]
    ks, vs = scales or (None, None)
    out = reference_paged_decode_attention(q, kp, vp, tables, pos, ks=ks,
                                           vs=vs)
    return out if new is None else (out, *pools)


def _paged_decode_kernel(*refs, scale, block_len, quant, write):
    """Scalar prefetch: pos, table, layer (and with `write` the gate).
    Inputs: q, the K/V (and scale) blocks, and with `write` this step's
    rows. Outputs: the attention rows, and with `write` the block that
    holds position `pos` with the row placed."""
    from jax.experimental import pallas as pl

    n_pool = 4 if quant else 2
    pos_ref = refs[0]
    gate_ref = refs[3] if write else None
    refs = refs[4 if write else 3:]
    q_ref, pool_refs, refs = refs[0], refs[1:1 + n_pool], refs[1 + n_pool:]
    if write:
        new_refs, refs = refs[:n_pool], refs[n_pool:]
        out_refs, refs = refs[1:1 + n_pool], refs[:1] + refs[1 + n_pool:]
    o_ref, m_scr, l_scr, acc_scr = refs

    si = pl.program_id(1)
    ns = pl.num_programs(1)

    @pl.when(si == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_BIG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    pos = pos_ref[pl.program_id(0)]
    live = si * block_len <= pos
    if write:
        gate = gate_ref[pl.program_id(0)] != 0

    @pl.when(live)
    def _step():
        q = q_ref[0].astype(jnp.float32)   # (Hk, R, d)
        # the block's leaves as the softmax reads them: K, V (Hk,
        # block_len, d) and an int8 pool's scales (Hk, block_len)
        blk = [r[0].astype(jnp.float32) for r in pool_refs]
        if write:
            # the step's own row goes into the block that holds `pos`
            # before it is attended, and that one block goes back out
            # through the aliased pool: the pool is updated by the
            # kernel that reads it and XLA never lays a hand on it. A
            # gated-off slot places nothing: its block goes, as it was,
            # to junk block 0 (the output's index map)
            last = si == jnp.minimum(pos // block_len, ns - 1)
            row = jnp.where(last & gate, pos % block_len, -1)
            here = {shp: jax.lax.broadcasted_iota(jnp.int32, shp, 1) == row
                    for shp in {b.shape for b in blk}}
            blk = [jnp.where(here[b.shape], n[0].astype(jnp.float32), b)
                   for b, n in zip(blk, new_refs)]

            @pl.when(last)
            def _place():
                for o, b in zip(out_refs, blk):
                    o[0] = b.astype(o.dtype)

        k, v = blk[:2]
        hk, r, d = q.shape
        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )  # (Hk, R, block_len)
        if quant:
            s = s * blk[2][:, None, :]
        s = s * scale
        s2 = s.reshape(hk * r, block_len)
        cols = jax.lax.broadcasted_iota(
            jnp.int32, (hk * r, block_len), 1) + si * block_len
        s2 = jnp.where(cols <= pos, s2, _NEG_BIG)

        m_prev = m_scr[:, :1]
        m_new = jnp.maximum(m_prev, s2.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s2 - m_new)
        if quant:
            pv = p.reshape(hk, r, block_len) * blk[3][:, None, :]
        else:
            pv = p.reshape(hk, r, block_len)
        out = jax.lax.dot_general(
            pv, v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )
        l_new = l_scr[:, :1] * alpha + p.sum(axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + out.reshape(hk * r, d)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(si == ns - 1)
    def _finish():
        hk, r, d = q_ref.shape[1:]
        o_ref[0] = (acc_scr[...] / l_scr[:, :1]).reshape(hk, r, d) \
            .astype(o_ref.dtype)


@jax.named_scope("attn.paged_decode")
def paged_decode_attention(q, kp, vp, tables, pos, *, ks=None, vs=None,
                           layer=None, new=None, interpret=None):
    """Fused paged decode attention (see the section comment above).

    q (B, Hk, R, D) — R query rows per KV head, all attending logical
    columns <= pos[b] of their slot; kp/vp (n_blocks, Hk, bp, D) block
    pool — float, or int8 with ks/vs (n_blocks, Hk, bp) scales; tables
    (B, nb_max) int32 logical->physical block map; pos (B,) int32.
    Returns (B, Hk, R, D) f32, identical math to the gather_view einsum
    (reference_paged_decode_attention is the oracle). The pool's rows
    may be stored wider than D (paged_kvcache.lane_padded, the upper
    lanes zero): q is then zero-padded to match, which leaves every
    score as it was, and the result cut back to D.

    With `layer` (a traced int32 scalar) kp/vp/ks/vs are the WHOLE
    pool, one more leading (L,) axis: the layer rides scalar prefetch
    beside `pos` and the table and leads each block index, so the
    kernel reads layer `layer`'s blocks in place and nobody slices the
    pool (the decode loop's form, paged_kvcache.scan_blocks).

    With `new` = (k (B, Hk, 1, D), v[, ks (B, Hk, 1), vs], gate (B,)) —
    this step's rows as the pool stores them, whole-pool form only — the
    kernel also WRITES: each slot's row goes into the block that holds
    position pos[b] before it is attended (a gated-off slot places
    nothing, and its block goes as it was to junk block 0), and the
    pools come back updated through aliased outputs:
    returns (out, kp, vp[, ks, vs]). The step then touches the pool with
    nothing but this call.

    Dispatches to the Pallas kernel on TPU; otherwise runs the
    reference. `interpret=True` forces the kernel in interpreter mode
    (CPU CI runs the real table-chasing index maps)."""
    quant = ks is not None
    pools = [kp, vp] + ([ks.astype(jnp.float32), vs.astype(jnp.float32)]
                        if quant else [])
    if new is not None and layer is None:
        raise ValueError("the kernel places rows in the whole pool only: "
                         "pass layer= with new=")
    on_tpu = jax.default_backend() == "tpu"
    if interpret is None:
        if not on_tpu:
            if layer is None:
                return reference_paged_decode_attention(
                    q, kp, vp, tables, pos, ks=ks, vs=vs)
            return _reference_paged_step(q, pools, tables, pos, layer, new)
        interpret = False
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, hk, r, d_q = q.shape
    d = kp.shape[-1]  # the pool's row width: D, or D lane-padded
    if d != d_q:
        q = jnp.pad(q, [(0, 0)] * 3 + [(0, d - d_q)])
    nb_max = tables.shape[1]
    bp = kp.shape[-2]
    write = new is not None
    kernel = functools.partial(
        _paged_decode_kernel, scale=1.0 / (d_q ** 0.5), block_len=bp,
        quant=quant, write=write,
    )

    # the block table chases through scalar prefetch: logical block si of
    # slot bi lives at physical pool block tab[bi * nb_max + si], and
    # blocks past the live limit re-target the last LIVE logical block
    # (repeated physical index -> no DMA). A whole pool is entered at
    # its layer: one squeezed leading block index, the same kernel body.
    whole = layer is not None
    lead = (None,) if whole else ()

    def _pool_map(bi, si, p, tab, lay, *_):
        blk = tab[bi * nb_max + jnp.minimum(si, p[bi] // bp)]
        return ((lay[0],) if whole else ()) + (blk, 0, 0, 0)

    # the written block: the one that holds `pos`, whatever the grid
    # step — one write-back per slot, when the slot's steps are over
    def _out_map(bi, si, p, tab, lay, gate):
        blk = tab[bi * nb_max + jnp.minimum(p[bi] // bp, nb_max - 1)]
        return (lay[0], jnp.where(gate[bi] != 0, blk, 0), 0, 0, 0)

    def _row_map(bi, si, *_):
        return (bi, 0, 0, 0)

    def cut(index_map, n):  # a scale leaf has no D axis
        return lambda *a: index_map(*a)[:n]

    qspec = pl.BlockSpec((1, hk, r, d), _row_map)
    shapes = [lead + (1, hk, bp, d)] * 2 + [lead + (1, hk, bp)] * 2
    in_specs = [qspec] + [
        pl.BlockSpec(shp, cut(_pool_map, len(shp)))
        for shp in shapes[:len(pools)]]
    out_specs, out_shape = qspec, jax.ShapeDtypeStruct((b, hk, r, d),
                                                       jnp.float32)
    scalars = [pos.astype(jnp.int32), tables.reshape(-1).astype(jnp.int32),
               jnp.asarray(0 if layer is None else layer,
                           jnp.int32).reshape(1)]
    aliases, rows = {}, ()
    if write:
        *rows, gate = new
        scalars.append(gate.astype(jnp.int32))
        in_specs += [pl.BlockSpec((1,) + x.shape[1:],
                                  cut(_row_map, x.ndim)) for x in rows]
        out_specs = [qspec] + [
            pl.BlockSpec(shp, cut(_out_map, len(shp)))
            for shp in shapes[:len(pools)]]
        out_shape = [out_shape] + [
            jax.ShapeDtypeStruct(x.shape, x.dtype) for x in pools]
        # operand numbers count the scalars: 4 of them, then q
        aliases = {5 + i: 1 + i for i in range(len(pools))}
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(b, nb_max),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((hk * r, 128), jnp.float32),  # running row max
            pltpu.VMEM((hk * r, 128), jnp.float32),  # running row sum
            pltpu.VMEM((hk * r, d), jnp.float32),    # output accumulator
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        input_output_aliases=aliases,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
        name="paged_decode_attention",
    )(*scalars, q, *pools, *rows)
    if write:
        return (out[0][..., :d_q], *out[1:])
    return out[..., :d_q]


@jax.named_scope("attn.decode")
def decode_attention(q, k, v, pos, *, ks=None, vs=None, block_s=512,
                     interpret=None):
    """Decode-step cache attention (see the section comment above).

    q (B, Hk, R, D) — R query rows per KV head, all attending columns
    <= pos[b] of their slot; k/v (B, Hk, S, D) float or int8 with ks/vs
    (B, Hk, S) scales; pos (B,) int32. Returns (B, Hk, R, D) f32.

    Dispatches to the Pallas streaming kernel on TPU when S tiles by a
    {512, 256, 128} block; otherwise runs the identical-math reference.
    `interpret=True` forces the kernel in interpreter mode (CPU CI)."""
    on_tpu = jax.default_backend() == "tpu"
    if interpret is None:
        if not on_tpu:
            return reference_decode_attention(q, k, v, pos, ks=ks, vs=vs)
        interpret = False
    s_len = k.shape[2]
    for bs in (block_s, 256, 128):
        if s_len % bs == 0:
            block_s = bs
            break
    else:
        return reference_decode_attention(q, k, v, pos, ks=ks, vs=vs)
    pos1d = pos.astype(jnp.int32)
    ks_f = ks.astype(jnp.float32) if ks is not None else None
    vs_f = vs.astype(jnp.float32) if vs is not None else None
    return _decode_call(q, k, v, pos1d, ks_f, vs_f, block_s=block_s,
                        interpret=interpret)
