"""Flash attention as a Pallas TPU kernel.

The hot op of the GPT family (SURVEY.md §5 "long-context"). Online-softmax
blockwise attention: never materializes the (T, T) score matrix in HBM —
scores live in VMEM one (block_q, block_k) tile at a time, with running
row-max / row-sum rescaling (the flash-attention recurrence).

Grid: (batch*heads, q_blocks, k_blocks); the k dimension is sequential
("arbitrary") so the f32 accumulator scratch persists across k steps, while
batch/head/q blocks parallelize. Causal masking skips fully-masked k blocks
outright (upper triangle), so causal costs ~half the FLOPs of full.

Falls back to the jnp reference implementation (numerically identical math)
when not running on TPU, when shapes don't tile, or when the sequence is too
short to be worth a kernel launch.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_NEG_BIG = -1e30  # finite "minus infinity": keeps exp()/max() NaN-free


# ----------------------------------------------------------------------
# reference path (also the off-TPU fallback and the test oracle)
# ----------------------------------------------------------------------

def reference_attention(q, k, v, *, causal=True):
    d = q.shape[-1]
    s = jnp.einsum("bhtd,bhsd->bhts", q, k).astype(jnp.float32) / jnp.sqrt(d)
    if causal:
        t, sk = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((t, sk), dtype=bool), k=sk - t)
        s = jnp.where(mask, s, _NEG_BIG)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhts,bhsd->bhtd", p.astype(v.dtype), v)


# ----------------------------------------------------------------------
# pallas kernel
# ----------------------------------------------------------------------

def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *, causal, scale, block_q, block_k, offset
):
    """`offset = S - T` aligns the causal mask bottom-right (query t attends
    to keys <= t + offset), matching reference_attention's tril(k=S-T) —
    the KV-cache decode convention when S > T."""
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_BIG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # causal: k block is dead iff its first col exceeds the max valid col of
    # this q block's last row (qi*bq + bq - 1 + offset).
    live = (not causal) or (ki * block_k <= qi * block_q + block_q - 1 + offset)

    @pl.when(live)
    def _step():
        q = q_ref[0].astype(jnp.float32)  # (block_q, d)
        k = k_ref[0].astype(jnp.float32)  # (block_k, d)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (block_q, block_k)

        if causal:
            rows = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0) + qi * block_q
            cols = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1) + ki * block_k
            s = jnp.where(rows + offset >= cols, s, _NEG_BIG)

        m_prev = m_scr[:, :1]  # (block_q, 1) row stats, lane-broadcast storage
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = l_scr[:, :1] * alpha + p.sum(axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ki == nk - 1)
    def _finish():
        o_ref[0] = (acc_scr[...] / l_scr[:, :1]).astype(o_ref.dtype)


def _fwd_lse_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                    *, causal, scale, block_q, block_k, offset):
    """Forward that additionally writes the per-row logsumexp (lane-broadcast
    to 128, the TPU row-stat storage convention — see the lse residual note
    in _flash_tpu_fwd). Shares the step math with _flash_kernel via
    delegation so the two can never drift."""
    from jax.experimental import pallas as pl

    _flash_kernel(
        q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
        causal=causal, scale=scale, block_q=block_q, block_k=block_k,
        offset=offset,
    )

    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == nk - 1)
    def _save_lse():
        lse_ref[0] = m_scr[...] + jnp.log(l_scr[...])


def _recompute_pds(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, qi, ki,
                   *, causal, scale, block_q, block_k, offset):
    """Shared backward-step recompute (single source — the dq and dkv
    kernels must apply identical masking/scaling or dQ silently disagrees
    with dK/dV): rebuild the normalized probabilities P from the saved
    logsumexp, then dS = P * (dP - D). Returns (q, k, do, p, ds) in f32."""
    q = q_ref[0].astype(jnp.float32)    # (block_q, d)
    k = k_ref[0].astype(jnp.float32)    # (block_k, d)
    v = v_ref[0].astype(jnp.float32)
    do = do_ref[0].astype(jnp.float32)  # (block_q, d)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale
    if causal:
        rows = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0) + qi * block_q
        cols = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1) + ki * block_k
        s = jnp.where(rows + offset >= cols, s, _NEG_BIG)
    p = jnp.exp(s - lse_ref[0][:, :1])  # normalized probs (block_q, block_k)
    dp = jax.lax.dot_general(            # dO V^T
        do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    ds = p * (dp - di_ref[0][:, :1])
    return q, k, do, p, ds


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dq_ref,
                   acc_scr, *, causal, scale, block_q, block_k, offset):
    """dQ for one q block, accumulated over the (sequential) k-block grid
    axis: dQ = scale * dS K."""
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    live = (not causal) or (ki * block_k <= qi * block_q + block_q - 1 + offset)

    @pl.when(live)
    def _step():
        _, k, _, _, ds = _recompute_pds(
            q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, qi, ki,
            causal=causal, scale=scale, block_q=block_q, block_k=block_k,
            offset=offset,
        )
        acc_scr[...] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(ki == nk - 1)
    def _finish():
        dq_ref[0] = (acc_scr[...] * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref,
                    dk_ref, dv_ref, dk_scr, dv_scr,
                    *, causal, scale, block_q, block_k, offset):
    """dK and dV for one k block, accumulated over the (sequential) q-block
    grid axis: dV = P^T dO, dK = scale * dS^T Q."""
    from jax.experimental import pallas as pl

    ki = pl.program_id(1)
    qi = pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    live = (not causal) or (ki * block_k <= qi * block_q + block_q - 1 + offset)

    @pl.when(live)
    def _step():
        q, _, do, p, ds = _recompute_pds(
            q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, qi, ki,
            causal=causal, scale=scale, block_q=block_q, block_k=block_k,
            offset=offset,
        )
        dv_scr[...] += jax.lax.dot_general(          # P^T dO  (block_k, d)
            p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        dk_scr[...] += jax.lax.dot_general(          # dS^T Q  (block_k, d)
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(qi == nq - 1)
    def _finish():
        dk_ref[0] = (dk_scr[...] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _qspec(block_q, d):
    from jax.experimental import pallas as pl

    return pl.BlockSpec((1, block_q, d), lambda bh_, qi, ki: (bh_, qi, 0))


def _kspec(block_k, d):
    from jax.experimental import pallas as pl

    return pl.BlockSpec((1, block_k, d), lambda bh_, qi, ki: (bh_, ki, 0))


def _call_fwd(q3, k3, v3, *, causal, block_q, block_k, interpret, with_lse):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bh, t, d = q3.shape
    s_len = k3.shape[1]
    nq, nk = t // block_q, s_len // block_k
    common = dict(causal=causal, scale=1.0 / (d ** 0.5), block_q=block_q,
                  block_k=block_k, offset=s_len - t)
    out_shape = [jax.ShapeDtypeStruct((bh, t, d), q3.dtype)]
    out_specs = [_qspec(block_q, d)]
    if with_lse:
        kernel = functools.partial(_fwd_lse_kernel, **common)
        out_shape.append(jax.ShapeDtypeStruct((bh, t, 128), jnp.float32))
        out_specs.append(_qspec(block_q, 128))
    else:
        kernel = functools.partial(_flash_kernel, **common)
    res = pl.pallas_call(
        kernel,
        grid=(bh, nq, nk),
        in_specs=[_qspec(block_q, d), _kspec(block_k, d), _kspec(block_k, d)],
        out_specs=out_specs if with_lse else out_specs[0],
        out_shape=out_shape if with_lse else out_shape[0],
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),  # running row max
            pltpu.VMEM((block_q, 128), jnp.float32),  # running row sum
            pltpu.VMEM((block_q, d), jnp.float32),  # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(q3, k3, v3)
    return res if with_lse else (res, None)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_tpu(q, k, v, causal, block_q, block_k, interpret):
    """Pallas flash attention with a custom VJP: `jax.grad` through
    `use_flash=True` runs the recompute-based backward kernels below instead
    of failing (pallas_call has no autodiff rule). Inference-only calls take
    this primal path and never pay the logsumexp write."""
    b, h, t, d = q.shape
    bh = b * h
    out, _ = _call_fwd(
        q.reshape(bh, t, d), k.reshape(bh, k.shape[2], d),
        v.reshape(bh, v.shape[2], d),
        causal=causal, block_q=block_q, block_k=block_k, interpret=interpret,
        with_lse=False,
    )
    return out.reshape(b, h, t, d)


def _flash_tpu_fwd(q, k, v, causal, block_q, block_k, interpret):
    b, h, t, d = q.shape
    bh = b * h
    out, lse = _call_fwd(
        q.reshape(bh, t, d), k.reshape(bh, k.shape[2], d),
        v.reshape(bh, v.shape[2], d),
        causal=causal, block_q=block_q, block_k=block_k, interpret=interpret,
        with_lse=True,
    )
    # lse residual is (bh, t, 128) lane-broadcast f32 — the TPU-native row
    # stat layout (row vectors must live along sublanes to broadcast against
    # (block_q, block_k) score tiles; a (bh, t) array would put them in
    # lanes and force an in-kernel transpose). 128 lanes of redundancy cost
    # 128*T*4B per head — noise next to the (T, T) scores flash avoids.
    return out.reshape(b, h, t, d), (q, k, v, out.reshape(b, h, t, d), lse)


def _flash_tpu_bwd(causal, block_q, block_k, interpret, residuals, do):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    q, k, v, o, lse = residuals
    b, h, t, d = q.shape
    s_len = k.shape[2]
    bh = b * h
    nq, nk = t // block_q, s_len // block_k

    # D_i = rowsum(dO * O): elementwise + reduce — jnp, not a kernel, and
    # stored lane-broadcast like lse.
    di = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    di = jnp.broadcast_to(di.reshape(bh, t, 1), (bh, t, 128))

    q3 = q.reshape(bh, t, d)
    k3 = k.reshape(bh, s_len, d)
    v3 = v.reshape(bh, s_len, d)
    do3 = do.reshape(bh, t, d).astype(q.dtype)

    common = dict(causal=causal, scale=1.0 / (d ** 0.5), block_q=block_q,
                  block_k=block_k, offset=s_len - t)
    row_specs = [_qspec(block_q, d), _kspec(block_k, d), _kspec(block_k, d),
                 _qspec(block_q, d), _qspec(block_q, 128), _qspec(block_q, 128)]

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, **common),
        grid=(bh, nq, nk),
        in_specs=row_specs,
        out_specs=_qspec(block_q, d),
        out_shape=jax.ShapeDtypeStruct((bh, t, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(q3, k3, v3, do3, lse, di)

    # dkv grid iterates k blocks in the parallel axis, q blocks sequentially;
    # index maps therefore swap roles: grid = (bh, ki, qi).
    def kblock(block, width):
        return pl.BlockSpec((1, block, width), lambda bh_, ki, qi: (bh_, ki, 0))

    def qblock(block, width):
        return pl.BlockSpec((1, block, width), lambda bh_, ki, qi: (bh_, qi, 0))

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, **common),
        grid=(bh, nk, nq),
        in_specs=[qblock(block_q, d), kblock(block_k, d), kblock(block_k, d),
                  qblock(block_q, d), qblock(block_q, 128), qblock(block_q, 128)],
        out_specs=[kblock(block_k, d), kblock(block_k, d)],
        out_shape=[jax.ShapeDtypeStruct((bh, s_len, d), k.dtype),
                   jax.ShapeDtypeStruct((bh, s_len, d), v.dtype)],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(q3, k3, v3, do3, lse, di)

    return (dq.reshape(b, h, t, d), dk.reshape(b, h, s_len, d),
            dv.reshape(b, h, s_len, d))


_flash_tpu.defvjp(_flash_tpu_fwd, _flash_tpu_bwd)


def flash_attention(q, k, v, *, causal=True, block_q=128, block_k=128, interpret=None):
    """(B, H, T, D) scaled-dot-product attention. Dispatches to the Pallas
    TPU kernel when shapes tile cleanly on a TPU backend; otherwise runs the
    numerically-identical jnp reference (so `use_flash=True` is always safe —
    the review contract of dnn_tpu/ops/attention.py)."""
    t, s_len = q.shape[2], k.shape[2]
    if causal:
        block_k = block_q  # diagonal-block masking assumes square tiles
    on_tpu = jax.default_backend() == "tpu"
    if interpret is None:
        interpret = False
        if not on_tpu:
            return reference_attention(q, k, v, causal=causal)
    tiles = t % block_q == 0 and s_len % block_k == 0 and t >= block_q and s_len >= block_k
    if not tiles or (causal and s_len < t):
        # s < t causal (queries before the first key) is a degenerate case
        # the kernel's masking doesn't model — use the reference path.
        return reference_attention(q, k, v, causal=causal)
    return _flash_tpu(q, k, v, causal, block_q, block_k, interpret)
