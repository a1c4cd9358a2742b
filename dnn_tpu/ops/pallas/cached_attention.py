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
import math

import jax
import jax.numpy as jnp

_NEG_BIG = -1e30


# ----------------------------------------------------------------------
# reference (fallback + test oracle) — the kvcache.py einsum math
# ----------------------------------------------------------------------

def reference_cached_attention(q, k, v, pos, *, ks=None, vs=None,
                               rows_mod=None, window=None):
    """q (B, H, T, D) at absolute positions pos[b] + t; k/v (B, H, S, D)
    cache buffers (any float dtype, or int8 with `ks`/`vs` scales
    (B, H, S)); pos (B,) int32. Row (b, t) attends columns
    <= pos[b] + t — t modulo `rows_mod` where the rows are a query group
    folded over its KV head — and, with `window`, > pos[b] + t - window.
    Returns (B, H, T, D) f32."""
    d = q.shape[-1]
    s = jnp.einsum("bhtd,bhsd->bhts", q.astype(jnp.float32),
                   k.astype(jnp.float32),
                   preferred_element_type=jnp.float32)
    if ks is not None:
        s = s * ks[:, :, None, :]
    s = s / jnp.sqrt(d)
    cols = jnp.arange(k.shape[2])
    rows = jnp.arange(q.shape[2])
    if rows_mod is not None:
        rows = rows % rows_mod
    limit = pos[:, None, None, None] + rows[None, None, :, None]
    keep = cols[None, None, None, :] <= limit
    if window is not None:
        keep &= cols[None, None, None, :] > limit - window
    s = jnp.where(keep, s, _NEG_BIG)
    p = jax.nn.softmax(s, axis=-1)
    if vs is not None:
        p = p * vs[:, :, None, :]
    return jnp.einsum("bhts,bhsd->bhtd", p, v.astype(jnp.float32),
                      preferred_element_type=jnp.float32)


# ----------------------------------------------------------------------
# kernel
# ----------------------------------------------------------------------

def _live_tiles(pos, q0, *, block_q, block_s, window):
    """Of a row tile whose first row reads up to column pos + q0: the
    first and the last column tile that holds a column some row of it
    reads (the band's, with `window`)."""
    last = (pos + q0 + block_q - 1) // block_s
    if window is None:
        return 0, last
    return jnp.maximum(pos + q0 - window + 1, 0) // block_s, last


def _cached_attn_kernel(pos_ref, q_ref, k_ref, v_ref, *rest,
                        scale, block_q, block_s, quant, rows_mod=None,
                        window=None):
    """`rows_mod` / `window`: the folded-group and banded form
    (`cached_attention`'s docstring), operands then meet in their own
    dtype."""
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
    folded = rows_mod is not None or window is not None
    if folded:
        # the tile's first row among its head's T (`rows_mod`): the column
        # tiles outside [lo, hi] are neither computed nor — the index map
        # clamps to the same two — fetched. Under a band the grid's last
        # axis is only as long as a band is wide (`_kernel_call`), and
        # step si holds column tile lo + si
        q0 = (qi * block_q) % (rows_mod or (block_q * pl.num_programs(1)))
        lo, hi = _live_tiles(pos, q0, block_q=block_q, block_s=block_s,
                             window=window)
        ti = si if window is None else lo + si
        live = ti <= hi
    else:
        ti = si
        live = si * block_s <= pos + (qi + 1) * block_q - 1

    @pl.when(live)
    def _step():
        # the folded form's operands meet in their own dtype (bfloat16
        # products summed in float32 are the float32 form's)
        cdt = q_ref.dtype if folded else jnp.float32
        q = q_ref[0].astype(cdt)  # (block_q, d)
        k = k_ref[0].astype(cdt)  # (block_s, d) — int8 streams raw
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (block_q, block_s)
        if quant:
            s = s * ks_ref[0]  # (1, block_s) per-position K scales
        s = s * scale

        rows = jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_s), 0) + (q0 if folded
                                                 else qi * block_q)
        cols = jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_s), 1) + ti * block_s
        keep = cols <= pos + rows
        if window is not None:
            keep &= cols > pos + rows - window
        s = jnp.where(keep, s, _NEG_BIG)

        m_prev = m_scr[:, :1]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        if window is not None:
            # a row the band leaves nothing of in this tile: its max is
            # still the mask's, and exp(0) would count the masked
            p = jnp.where(keep, p, 0.0)
        if quant:
            # V scale folds into the (small) probability matrix; the raw
            # int8 V contracts directly (scales commute — kvcache.py)
            pv = p * vs_ref[0]
        else:
            pv = p.astype(cdt) if folded else p
        v = v_ref[0].astype(cdt)
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


def _kernel_call(q3, k3, v3, pos1d, ks3, vs3, *, block_q, block_s, interpret,
                 rows_mod=None, window=None):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bh, t, d = q3.shape
    s_len = k3.shape[1]
    nq, ns = t // block_q, s_len // block_s
    quant = ks3 is not None
    folded = rows_mod is not None or window is not None
    kernel = functools.partial(
        _cached_attn_kernel, scale=1.0 / (d ** 0.5), block_q=block_q,
        block_s=block_s, quant=quant, **(
            {"rows_mod": rows_mod, "window": window} if folded else {}),
    )
    # index maps gain a TRAILING scalar-prefetch ref argument (unused here
    # — blocks are addressed by grid coordinates alone)
    qspec = pl.BlockSpec((1, block_q, d), lambda b, qi, si, p: (b, qi, 0))
    sspec = pl.BlockSpec((1, block_s, d), lambda b, qi, si, p: (b, si, 0))
    if folded:
        # a column tile outside the row tile's live ones is asked for as
        # the nearest live one: the pipeline skips a copy whose block
        # index repeats, so what is not computed is not fetched either.
        # Under a band a row tile reads at most `block_q + window - 1`
        # columns, whatever the row's length: the grid's last axis is that
        # many tiles (a skipped grid step still costs a quarter of a
        # microsecond on a v5e: 24 576 of them were 6.6 ms a layer, PERF.md
        # section 6, PR 43), counted from the band's first
        n_tiles = ns  # of the row, whatever the grid's last axis is

        def live_tile(b, qi, si, p):
            lo, hi = _live_tiles(
                p[b], (qi * block_q) % (rows_mod or t), block_q=block_q,
                block_s=block_s, window=window)
            at = si if window is None else lo + si
            return b, jnp.clip(at, lo, jnp.minimum(hi, n_tiles - 1)), 0

        sspec = pl.BlockSpec((1, block_s, d), live_tile)
        if window is not None:
            ns = min(ns, -(-(block_q + window - 1) // block_s) + 1)
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
        name="cached_attention_banded" if window is not None
        else "cached_attention",
    )(pos1d, *args)


# The scopes on the three entry points (attn.prefill / attn.decode /
# attn.paged_decode) and the kernels' `name=` are what a device trace
# calls this file's work (the HLO op_name path; the custom call's own
# name) — chipbench/spans.py reads them, so renaming one moves a metric.
def chunk_tiles(t: int, s_len: int) -> dict:
    """`block_q` / `block_s` for `t` queries over a row of `s_len` positions
    whose rows are NOT folded (the GPT codec's calls, `kvcache.attend`):
    the largest row tile up to 256 that divides the queries (a chunk under
    128 is its own tile) and the largest column tile up to 1024 that
    divides the row. A grid step of this kernel costs ~0.3 us on a v5e
    whatever it holds — every column tile is fetched, a dead one's compute
    alone is skipped — and a (128, 128) tile of 64-wide heads is ~0.02 us
    of products: a 256-query launch over GPT-2 Large's 1024-position row
    is 320 steps a layer at (128, 128) and 20 at (256, 1024), 10.0 -> 5.3
    ms a launch at start 768 (PERF.md section 6, PR 67). Same float32
    operands and accumulation; the column tile sets the order in which the
    running softmax meets the columns."""
    return {"block_q": 256 if t % 256 == 0 else 128,
            "block_s": next((n for n in (1024, 512, 256, 128)
                             if s_len % n == 0), 128)}


def cached_attention(q, k, v, pos, *, window=None, **kw):
    """`_cached_attention` under the scope `attn.prefill` — or, with a
    `window`, under none of its own: a window kind's read lies in its
    caller's `attn.window_prefill`, and `attn.prefill` stays the reads of
    every position."""
    if window is not None:
        return _cached_attention(q, k, v, pos, window=window, **kw)
    with jax.named_scope("attn.prefill"):
        return _cached_attention(q, k, v, pos, **kw)


def _cached_attention(q, k, v, pos, *, ks=None, vs=None, block_q=128,
                      block_s=128, interpret=None, rows_mod=None,
                      window=None):
    """Cache attention with runtime position limits (see module docstring).

    q (B, H, T, D); k/v (B, H, S, D) — float, or int8 with ks/vs (B, H, S)
    scales; pos (B,) int32 base positions (row t attends cols
    <= pos[b] + t). Returns (B, H, T, D) f32.

    `rows_mod` = T' (GQA's fold: H is the KV heads and a head's T rows
    are its G query heads' T' rows one after another, `block_q` dividing
    T'): row t reads up to pos[b] + t % T'. `window` = W: and no column
    <= that limit - W (`band_keep`'s predicate). With either, a column
    tile no row of a row tile reads — past its diagonal or behind its
    band — is neither fetched nor computed, and float operands meet in
    their own dtype.

    Dispatches to the Pallas kernel on TPU when S tiles by `block_s`
    (T tiles by block_q, or T < block_q which shrinks the q block);
    otherwise runs the identical-math reference. `interpret=True` forces
    the kernel in interpreter mode (CPU CI)."""
    b, h, t, d = q.shape
    s_len = k.shape[2]
    on_tpu = jax.default_backend() == "tpu"
    folded = {} if rows_mod is None and window is None else {
        "rows_mod": rows_mod, "window": window}
    if interpret is None:
        if not on_tpu:
            return reference_cached_attention(q, k, v, pos, ks=ks, vs=vs,
                                              **folded)
        interpret = False
    if t <= block_q:
        block_q = t  # decode: T=1 -> one q row per program
    tiles = (s_len % block_s == 0 and t % block_q == 0
             and (rows_mod or block_q) % block_q == 0)
    if not tiles:
        return reference_cached_attention(q, k, v, pos, ks=ks, vs=vs,
                                          **folded)

    bh = b * h
    q3 = q.reshape(bh, t, d)
    k3 = k.reshape(bh, s_len, d)
    v3 = v.reshape(bh, s_len, d)
    # per-(batch, head) base position: heads share their batch row's limit
    pos1d = jnp.repeat(pos.astype(jnp.int32), h)
    ks3 = ks.reshape(bh, 1, s_len).astype(jnp.float32) if ks is not None else None
    vs3 = vs.reshape(bh, 1, s_len).astype(jnp.float32) if vs is not None else None
    out = _kernel_call(q3, k3, v3, pos1d, ks3, vs3, block_q=block_q,
                       block_s=block_s, interpret=interpret, **folded)
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
# This kernel removes the materialization, and does work in proportion to
# what each slot HOLDS, not to what its table could hold:
#   * the pool's leaves stay in HBM (`memory_space=ANY`) and the grid is
#     over SLOTS alone. Inside a slot's grid step a loop walks its live
#     blocks 0 .. pos // block_len, G at a time: the table entry comes
#     from SMEM (scalar prefetch), each physical block is DMAed straight
#     from the pool into a double-buffered VMEM scratch, and the next
#     group — the next SLOT's first, when this is the slot's last — is in
#     flight while this one is attended, so the copies run on through the
#     slots' edges. A table entry past `pos` costs nothing — no grid
#     step, no DMA, no compare. Where a slot goes from one group to its
#     next, a FULL group's copies are straight-line code (runs of 16
#     blocks); the group a slot's last block falls in, and a slot's first
#     group, loop over a count known at run time;
#   * one online-softmax update covers a whole group: G blocks of 128 to
#     1024 positions, capped by the table — `_paged_group`, a rule over
#     the bytes the call's leaves hold a position: as wide as a group's
#     copies stay within 1.25 MiB. The copies lay a group's blocks side by
#     side, so a KV head's keys are ONE (G * block_len, d) matrix and its
#     values another: the scores of a group are (Hk, R, G * block_len) —
#     one (R, d) x (d, G * block_len) product a head, every vector
#     operation of the softmax on full 128-lane registers — and a query
#     row keeps ONE running state (max, sum, accumulator) from the slot's
#     first group to its last;
#   * columns past `pos` (the tail of the last live block, and the rest
#     of a last group that is not full) are masked as usual;
#   * with `new`, the slot's own row is placed in the VMEM copy of the
#     block that holds `pos` before that block is attended, and that one
#     block goes back to the pool, which is the kernel's aliased output:
#     the pool is updated by the kernel that reads it and XLA never lays
#     a hand on it. A gated-off slot (a retired slot keeps a stale `pos`
#     and a stale table) is an EMPTY slot: it reads no block, places and
#     writes nothing, and its output rows are zeros.
# int8 pools stream their 1-byte payload with the per-(position, head)
# scales folded in VMEM, exactly like the dense decode kernel. (int4
# pools stay on the einsum: sub-byte VMEM loads are not wired.)


def reference_paged_decode_attention(q, kp, vp, tables, pos, *, ks=None,
                                     vs=None, sel=None):
    """Oracle for the paged kernel: gather the dense view, then the
    dense decode reference. q (B, Hk, R, D); kp/vp (n_blocks, Hk, bp, D)
    pool; tables (B, nb_max) int32; pos (B,); `sel` (B, nb_max * bp) bool
    narrows slot b's columns to those it is true at. Returns (B, Hk, R,
    D) f32."""
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
    if sel is None:
        return reference_decode_attention(
            q, view(kp)[..., :d], view(vp)[..., :d], pos,
            ks=view(ks) if ks is not None else None,
            vs=view(vs) if vs is not None else None)
    if ks is not None:
        raise ValueError("a selection reads a float pool")
    k, v = view(kp)[..., :d], view(vp)[..., :d]
    s = jnp.einsum("bhrd,bhsd->bhrs", q.astype(jnp.float32),
                   k.astype(jnp.float32),
                   preferred_element_type=jnp.float32) / jnp.sqrt(d)
    keep = (jnp.arange(k.shape[2])[None, :] <= pos[:, None]) & sel
    s = jnp.where(keep[:, None, None, :], s, _NEG_BIG)
    return jnp.einsum("bhrs,bhsd->bhrd", jax.nn.softmax(s, axis=-1),
                      v.astype(jnp.float32),
                      preferred_element_type=jnp.float32)


def _reference_paged_step(q, pools, tables, pos, layer, new, sel=None):
    """paged_decode_attention's whole-pool forms in plain jnp: place
    `new`'s rows at [layer, block, :, row] (gated-off slots at junk
    block 0, row 0), then the oracle on that layer. Returns what the
    kernel does: the attention output (zeros for a gated-off slot), and
    with `new` the pools too."""
    bp = pools[0].shape[-2]
    gate = None
    if new is not None:
        *rows, gate = new
        blk = jnp.take_along_axis(tables, (pos // bp)[:, None], axis=1)[:, 0]
        blk, row = jnp.where(gate, blk, 0), jnp.where(gate, pos % bp, 0)
        pools = [p.at[layer, blk, :, row].set(r[:, :, 0])
                 for p, r in zip(pools, rows)]
    kp, vp, *scales = [p[layer] for p in pools]
    ks, vs = scales or (None, None)
    out = reference_paged_decode_attention(q, kp, vp, tables, pos, ks=ks,
                                           vs=vs, sel=sel)
    if gate is None:
        return out
    return (jnp.where(gate[:, None, None, None], out, 0.0), *pools)


def _reference_latent_step(q, cp, tables, pos, layer, new, latent, scale,
                           sel=None):
    """paged_decode_attention's `latent` form in plain jnp: place `new`'s
    row, then q (B, 1, R, D) against the slot's gathered rows, key whole
    and value in the first `latent` lanes. As the kernel: zeros for a
    gated-off slot, and with `new` the leaf too."""
    bp = cp.shape[-2]
    gate = None
    if new is not None:
        row, gate = new
        blk = jnp.take_along_axis(tables, (pos // bp)[:, None], axis=1)[:, 0]
        blk, at = jnp.where(gate, blk, 0), jnp.where(gate, pos % bp, 0)
        cp = cp.at[layer, blk, :, at].set(row[:, :, 0])
    leaf = cp if layer is None else cp[layer]
    b, nb = tables.shape
    kv = jnp.take(leaf, tables.reshape(-1), axis=0)[:, 0]  # (B*nb, bp, D)
    kv = kv.reshape(b, nb * bp, -1).astype(jnp.float32)
    qf = jnp.pad(q.astype(jnp.float32),
                 [(0, 0)] * 3 + [(0, kv.shape[-1] - q.shape[-1])])
    s = jnp.einsum("bhrd,bsd->bhrs", qf, kv,
                   preferred_element_type=jnp.float32) * (
        q.shape[-1] ** -0.5 if scale is None else scale)
    keep = jnp.arange(nb * bp)[None, :] <= pos[:, None]
    if sel is not None:
        keep = keep & sel
    s = jnp.where(keep[:, None, None, :], s, _NEG_BIG)
    out = jnp.einsum("bhrs,bsd->bhrd", jax.nn.softmax(s, axis=-1),
                     kv[..., :latent], preferred_element_type=jnp.float32)
    if gate is None:
        return out
    return jnp.where(gate[:, None, None, None], out, 0.0), cp


# the widest group of the paged decode kernel, and the most its copies
# may bring: 1.25 MiB is the largest group timed to win (a latent leaf of
# 640 lanes at 1024 positions; 2 MiB — 8 K/V heads at 512 — ties 1 MiB)
_WIDEST_GROUP = 1024
_GROUP_BYTES = 5 * 2 ** 18
_COPY_RUN = 16  # blocks whose copies are written out one after another


def _paged_group(block_len, nb_max, position_bytes, widest=_WIDEST_GROUP):
    """Blocks attended in one online-softmax update — a rule over the
    shapes of the call alone. `position_bytes`: what the pool's leaves
    hold a position, every leaf and head. A group costs its copies' bytes
    AND an update that is a latency chain (two MXU round trips, two
    cross-lane reductions: ~0.4 us + 0.1 us per 128 positions, whatever
    the bytes), so a group is as WIDE as its copies stay within
    `_GROUP_BYTES` — two buffers a leaf hold it in VMEM, and the update's
    latency is spread over up to eight times the positions — between 128
    and `widest` positions and never more than the table has: a latent
    leaf of 640 lanes (1280 B a position) takes 1024, 4 K/V heads of 128
    lanes 512, 8 heads 256, 16 heads and more 128, where the copies bind
    already (PERF.md section 5, PR 51)."""
    span = 128
    while span < widest and 2 * span * position_bytes <= _GROUP_BYTES:
        span *= 2
    return max(1, min(span // block_len, nb_max))


def paged_group(leaves, nb_max, *, whole):
    """`_paged_group` of a call on the pool's `leaves` as the kernel gets
    them — (n_blocks, Hk, bp[, d]), with `whole` one more leading (L,) —
    against tables of `nb_max` entries a slot."""
    lead = 1 + bool(whole)
    bp = leaves[0].shape[lead + 1]
    block_bytes = sum(math.prod(x.shape[lead:]) * x.dtype.itemsize
                      for x in leaves)
    # an int8 pool's (Hk, bp) scale blocks lie side by side as lane slices
    # of a (Hk, span) buffer, which the chip's compiler copies into only
    # where Hk fills whole sublane tiles ("Slice shape along dimension 1
    # must be aligned to tiling (8), but is 12"): its groups stay at 128
    # positions (no cell serves one, and none was timed wider)
    scales = any(x.ndim == lead + 2 for x in leaves)
    return _paged_group(bp, nb_max, block_bytes // bp,
                        widest=128 if scales else _WIDEST_GROUP)


def _paged_decode_kernel(*refs, scale, nb_max, quant, write, whole,
                         select=False, latent=0):
    """One grid step = one slot. Scalar prefetch: pos, table, layer (and
    with `write` the gate). Inputs: q, the pool's leaves where they lie
    in HBM, and with `write` this step's rows. Outputs: the attention
    rows, and with `write` the pool's leaves again (aliased). Scratch: a
    two-deep buffer of a group a leaf — its G blocks side by side, ONE
    (G * bp, d) matrix a head — the DMA semaphores (one a buffer, one
    for the write-back), which of the two buffers the slot's first group
    is in, and ONE running softmax state a query row. With `select` one
    more input, last: the slot's set (n_groups, 1, G * bp) int32, nonzero
    where a position is read. With `latent` = the value's width the pool
    is ONE leaf of one head (models/mla.py): the group's matrix is key as
    it stands and value in its first `latent` lanes, and the slot's R
    query rows (its heads) meet it in one product each way."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n_pool = 1 if latent else 4 if quant else 2
    pos_ref, tab_ref, lay_ref = refs[:3]
    gate_ref = refs[3] if write else None
    q_ref, *refs = refs[4 if write else 3:]
    pools, refs = refs[:n_pool], refs[n_pool:]
    new_refs = ()
    if write:
        new_refs, refs = refs[:n_pool], refs[n_pool:]
    sel_ref = None
    if select:
        sel_ref, *refs = refs
    o_ref, *refs = refs
    if write:
        # the pool is read where it is written: through the aliased
        # output (on the chip the same memory as the input; under
        # interpret=True the copy the outputs start from)
        pools, refs = refs[:n_pool], refs[n_pool:]
    bufs, (sem, first_ref, m_scr, l_scr, acc_scr) = \
        refs[:n_pool], refs[n_pool:]

    bi, n_slots = pl.program_id(0), pl.num_programs(0)
    hk, r, d = q_ref.shape[1:]
    bp = pools[0].shape[-2]
    span = bufs[0].shape[2]  # positions a group: G blocks of bp
    group = span // bp

    def live_blocks(slot_i):
        """Of slot `slot_i`: how many blocks hold a position it attends
        (none where the gate is off, whatever its stale `pos` says), and
        the block that holds `pos`."""
        last = jnp.minimum(pos_ref[slot_i] // bp, nb_max - 1)
        if not write:
            return last + 1, last
        return jnp.where(gate_ref[slot_i] != 0, last + 1, 0), last

    def at(blk):  # a physical block of each leaf, where it lies
        return (lay_ref[0], blk) if whole else (blk,)

    def in_group(buf, buf_i, g):
        """Block g of the group in buffer `buf_i`: rows g * bp onward of
        every head's matrix (of its row of scales)."""
        start = g * bp if isinstance(g, int) else pl.multiple_of(g * bp, bp)
        return buf.at[buf_i, :, pl.ds(start, bp)]

    # a full group's copies are straight-line code in runs of at most
    # `_COPY_RUN` blocks, one run a turn of a loop whose count is static,
    # and only where a slot goes from one group to its next (`attend`):
    # every copy written out (64 a latent group) at all five places that
    # copy is 0.58 ms of JoyAI's call where this is 0.66, but cost the
    # daemon's boot 6-8 s of tracing and lowering the decode program
    run = math.gcd(group, _COPY_RUN)

    def group_dma(slot_i, gi, buf_i, go, straight=False):
        """Start, or wait for, the copies of the live blocks of slot
        `slot_i`'s group gi into buffer `buf_i`: a loop over a count known
        only at run time — nothing is copied for a block past the slot's
        last. With `straight` a FULL group's are straight-line code (in
        runs of `run` blocks), the table read at offsets the loop does
        not compute: the core issues them back to back, and only the
        group a slot's last block falls in takes the loop."""
        n_live, _ = live_blocks(slot_i)
        base = slot_i * nb_max + gi * group
        n_here = jnp.clip(n_live - gi * group, 0, group)

        def block(g, _=None):
            blk = tab_ref[base + g]
            for pool, buf in zip(pools, bufs):
                getattr(pltpu.make_async_copy(
                    pool.at[at(blk)], in_group(buf, buf_i, g),
                    sem.at[buf_i]), go)()

        if not straight:
            jax.lax.fori_loop(0, n_here, block, None)
            return

        @pl.when(n_here == group)
        def _full():
            if run == group:
                for g in range(group):
                    block(g)
                return

            def a_run(c, _):
                for u in range(run):
                    block(c * run + u)

            jax.lax.fori_loop(0, group // run, a_run, None)

        @pl.when(n_here < group)
        def _partial():
            jax.lax.fori_loop(0, n_here, block, None)

    @pl.when(bi == 0)
    def _first():
        # a group's unread tail must hold numbers: 0 x it has to be 0
        for buf in bufs:
            buf[...] = jnp.zeros_like(buf)
        first_ref[0] = 0
        group_dma(0, 0, 0, "start")

    pos = pos_ref[bi]
    n_live, last = live_blocks(bi)
    n_groups = (n_live + group - 1) // group
    # the slot's group gi is in buffer (first + gi) % 2. Its group 0 is
    # on its way already: whoever finishes before a slot — its own last
    # group, or an empty slot's whole step — starts the next slot's first
    # copies, so the DMA engine runs on through the slots' edges
    first = first_ref[0]
    first_ref[0] = jax.lax.rem(first + n_groups, 2)

    def start_next_slot(buf_i):
        @pl.when(bi + 1 < n_slots)
        def _():
            group_dma(bi + 1, 0, buf_i, "start")

    @pl.when(n_groups == 0)
    def _empty():  # a gated-off slot: nothing read, nothing placed
        start_next_slot(first)
        o_ref[...] = jnp.zeros_like(o_ref)

    def update(gi, s2, weigh):
        """One online-softmax update of the slot's query rows with group
        gi's scores s2 (rows, G * bp), lane c position gi * G * bp + c.
        `weigh` (p) -> the rows' sums of values under the weights p."""
        cols = gi * span + jax.lax.broadcasted_iota(jnp.int32, s2.shape, 1)
        seen = cols <= pos
        if select:
            # the group's set as one row of G * bp lanes, for every row
            seen = seen & (sel_ref[0, gi] != 0)
        s2 = jnp.where(seen, s2, _NEG_BIG)
        m_prev = m_scr[:, :1]
        m_new = jnp.maximum(m_prev, s2.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        # a group none of whose positions is read leaves the state empty
        p = jnp.where(seen, jnp.exp(s2 - m_new), 0.0)
        l_scr[...] = jnp.broadcast_to(
            l_scr[:, :1] * alpha + p.sum(axis=-1, keepdims=True),
            l_scr.shape)
        acc_scr[...] = acc_scr[...] * alpha + weigh(p)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)

    def update_latent(buf_i, gi):
        """The slot's R rows over group gi of the latent leaf."""
        kv = bufs[0][buf_i, 0]  # (G * bp, d)
        narrow = q_ref.dtype == jnp.bfloat16 and kv.dtype == jnp.bfloat16
        cdt = jnp.bfloat16 if narrow else jnp.float32
        kv = kv.astype(cdt)
        s2 = jax.lax.dot_general(
            q_ref[0, 0].astype(cdt), kv, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (R, G*bp)
        update(gi, s2, lambda p: jax.lax.dot_general(
            p.astype(cdt), kv[:, :latent], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32))  # (R, latent)

    def update_kv(buf_i, gi):
        """The slot's Hk x R rows over group gi of K and V: one (R, d) x
        (d, G * bp) product a KV head, and one back."""
        # K, V (Hk, G*bp, d), an int8 pool's scales (Hk, G*bp)
        k, v, *scales = [buf[buf_i] for buf in bufs]
        # bfloat16 rows meet a bfloat16 pool (or int8, exact in bfloat16)
        # on the MXU as they are: the products and the float32 sums are
        # those of the float32 form
        narrow = (q_ref.dtype == jnp.bfloat16
                  and k.dtype in (jnp.bfloat16, jnp.int8))
        cdt = jnp.bfloat16 if narrow else jnp.float32
        s = jax.lax.dot_general(
            q_ref[0].astype(cdt), k.astype(cdt),
            (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )  # (Hk, R, G*bp)
        if quant:
            s = s * scales[0][:, None, :]

        def weigh(p):
            pv = p.reshape(hk, r, span)
            if quant:
                pv = pv * scales[1][:, None, :]
            return jax.lax.dot_general(
                pv, v.astype(jnp.float32), (((2,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.float32,
            ).reshape(hk * r, d)

        update(gi, (s * scale).reshape(hk * r, span), weigh)

    def attend(gi, _):
        buf_i = jax.lax.rem(first + gi, 2)

        @pl.when(gi + 1 < n_groups)
        def _():
            group_dma(bi, gi + 1, 1 - buf_i, "start", straight=True)

        @pl.when(gi + 1 == n_groups)
        def _():
            start_next_slot(1 - buf_i)

        group_dma(bi, gi, buf_i, "wait", straight=True)

        if write:
            # the group that holds `pos` is the slot's last: its row goes
            # into the buffered block before the group is attended, and
            # that one block goes back to the pool meanwhile
            holds_pos = gi == n_groups - 1
            g = last - gi * group

            def write_back(go):
                blk = tab_ref[bi * nb_max + last]
                for pool, buf in zip(pools, bufs):
                    getattr(pltpu.make_async_copy(
                        in_group(buf, buf_i, g), pool.at[at(blk)],
                        sem.at[2]), go)()

            @pl.when(holds_pos)
            def _place():
                for buf, new in zip(bufs, new_refs):
                    view = in_group(buf, buf_i, g)  # (Hk, bp[, d])
                    blk = view[...].astype(jnp.float32)
                    here = jax.lax.broadcasted_iota(
                        jnp.int32, blk.shape, 1) == pos % bp
                    view[...] = jnp.where(
                        here, new[0].astype(jnp.float32), blk
                    ).astype(buf.dtype)
                write_back("start")

        (update_latent if latent else update_kv)(buf_i, gi)

        if write:
            pl.when(holds_pos)(lambda: write_back("wait"))

    @pl.when(n_groups > 0)
    def _live():
        m_scr[...] = jnp.full_like(m_scr, _NEG_BIG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)
        jax.lax.fori_loop(0, n_groups, attend, None)
        o_ref[0] = (acc_scr[...] / l_scr[:, :1]).reshape(
            o_ref.shape[1:]).astype(o_ref.dtype)


@jax.named_scope("attn.paged_decode")
def paged_decode_attention(q, kp, vp, tables, pos, *, ks=None, vs=None,
                           layer=None, new=None, sel=None, latent=None,
                           scale=None, interpret=None):
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
    beside `pos` and the table and leads each block's address, so the
    kernel reads layer `layer`'s blocks in place and nobody slices the
    pool (the decode loop's form, paged_kvcache.scan_blocks).

    With `new` = (k (B, Hk, 1, D), v[, ks (B, Hk, 1), vs], gate (B,)) —
    this step's rows as the pool stores them, whole-pool form only — the
    kernel also WRITES: each slot's row goes into the block that holds
    position pos[b] before its group is attended, and the pools come back
    updated through aliased outputs: returns (out, kp, vp[, ks, vs]).
    The step then touches the pool with nothing but this call. A
    gated-off slot is empty whatever its `pos` and table say: no block
    of it is read or written, and its output rows are zeros.

    With `sel` (B, nb_max * bp) bool, slot b's softmax runs over the
    positions <= pos[b] at which `sel` is true and no others (the set
    models/dsa.py chose). The kernel still walks every live block — a
    set scattered over the context leaves hardly a block without a
    member — and masks inside the group's update, one row of the group's
    lanes for every query row alike; float pools only.

    With `latent` = the value's width (latent attention, models/mla.py)
    `kp` is the pool's ONE leaf of one head, `vp` is None and q is (B, 1,
    R, D): the slot's R heads as rows, against rows that are key as they
    stand (all D lanes, scores times `scale`) and value in their first
    `latent` lanes. A block is copied into VMEM once and read both ways.
    Returns (B, 1, R, latent), and with `new` = (row (B, 1, 1, D), gate)
    the leaf. Float pools; `sel` masks the group's one matrix of scores
    for every head alike.

    Dispatches to the Pallas kernel on TPU; otherwise runs the
    reference. `interpret=True` forces the kernel in interpreter mode
    (CPU CI runs the real table chase and block copies)."""
    quant = ks is not None
    if latent and (quant or q.shape[1] != 1 or kp.shape[-3] != 1):
        raise ValueError("latent attention reads one float leaf of one "
                         "head, whole")
    pools = [kp] if latent else [kp, vp] + (
        [ks.astype(jnp.float32), vs.astype(jnp.float32)] if quant else [])
    if new is not None and layer is None:
        raise ValueError("the kernel places rows in the whole pool only: "
                         "pass layer= with new=")
    if interpret is None and jax.default_backend() == "tpu":
        interpret = False
    # the chip's compiler copies a block out of a leaf only in whole
    # 128-lane rows ("Slice shape along dimension 3 must be aligned to
    # tiling (128), but is 16"): rows stored narrower than a tile, and an
    # int8 pool's (Hk, bp) scale blocks unless bp tiles, take the
    # gather-and-einsum form there
    lowers = all(x.shape[-1] % 128 == 0 for x in pools)
    if interpret is None or not (interpret or lowers):
        if latent:
            return _reference_latent_step(q, kp, tables, pos, layer, new,
                                          latent, scale, sel)
        if layer is None:
            return reference_paged_decode_attention(
                q, kp, vp, tables, pos, ks=ks, vs=vs, sel=sel)
        return _reference_paged_step(q, pools, tables, pos, layer, new, sel)
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, hk, r, d_q = q.shape
    d = kp.shape[-1]  # the pool's row width: D, or D lane-padded
    if d != d_q:
        q = jnp.pad(q, [(0, 0)] * 3 + [(0, d - d_q)])
    nb_max = tables.shape[1]
    bp = kp.shape[-2]
    group = paged_group(pools, nb_max, whole=layer is not None)
    write = new is not None
    whole = layer is not None
    select = sel is not None
    if select and quant:
        raise ValueError("a selection reads a float pool")
    kernel = functools.partial(
        _paged_decode_kernel,
        scale=1.0 / (d_q ** 0.5) if scale is None else scale, nb_max=nb_max,
        quant=quant, write=write, whole=whole, **(
            {"select": True} if select else {}), **(
            {"latent": latent} if latent else {}),
    )

    def rows_of_slot(x):  # grid step bi sees x[bi]
        return pl.BlockSpec((1,) + x.shape[1:],
                            lambda bi, *_: (bi,) + (0,) * (x.ndim - 1))

    qspec = rows_of_slot(q)
    in_place = pl.BlockSpec(memory_space=pl.ANY)  # a leaf, left in HBM
    in_specs = [qspec] + [in_place] * len(pools)
    out_specs, out_shape = qspec, jax.ShapeDtypeStruct((b, hk, r, d),
                                                       jnp.float32)
    if latent:
        out_shape = jax.ShapeDtypeStruct((b, hk, r, latent), jnp.float32)
        out_specs = rows_of_slot(out_shape)
    out_rows = out_specs
    scalars = [pos.astype(jnp.int32), tables.reshape(-1).astype(jnp.int32),
               jnp.asarray(0 if layer is None else layer,
                           jnp.int32).reshape(1)]
    aliases, rows = {}, ()
    if write:
        *rows, gate = new
        scalars.append(gate.astype(jnp.int32))
        in_specs += [rows_of_slot(x) for x in rows]
        out_specs = [out_rows] + [in_place] * len(pools)
        out_shape = [out_shape] + [
            jax.ShapeDtypeStruct(x.shape, x.dtype) for x in pools]
        # operand numbers count the scalars: 4 of them, then q
        aliases = {5 + i: 1 + i for i in range(len(pools))}
    if select:
        # the set as the kernel's groups see it: a row of G * bp lanes a
        # group, the table's tail padded with positions never read
        n_groups = -(-nb_max // group)
        sel4 = jnp.pad(sel.astype(jnp.int32),
                       ((0, 0), (0, n_groups * group * bp - nb_max * bp))
                       ).reshape(b, n_groups, 1, group * bp)
        in_specs.append(rows_of_slot(sel4))
        rows = (*rows, sel4)
    n_states = hk * r  # one softmax state a query row
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(b,),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[
            # two buffers of a group a leaf, its blocks side by side:
            # (Hk, G * bp, d) rows, (Hk, G * bp) scales
            *(pltpu.VMEM((2, hk, group * bp) + x.shape[3 + whole:], x.dtype)
              for x in pools),
            pltpu.SemaphoreType.DMA((3,)),
            pltpu.SMEM((1,), jnp.int32),  # the slot's first buffer
            pltpu.VMEM((n_states, 128), jnp.float32),  # running row max
            pltpu.VMEM((n_states, 128), jnp.float32),  # running row sum
            pltpu.VMEM((n_states, latent or d), jnp.float32),  # accumulator
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        input_output_aliases=aliases,
        compiler_params=pltpu.CompilerParams(
            # slot 0 clears the buffers, and the slots share them
            dimension_semantics=("arbitrary",)
        ),
        interpret=interpret,
        name="paged_decode_attention",
    )(*scalars, q, *pools, *rows)
    if latent:
        return out
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
