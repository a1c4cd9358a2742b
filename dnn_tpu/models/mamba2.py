"""A Mamba-2 STATE-SPACE mixer beside softmax attention in every layer
(Falcon-H1, `model_type` falcon_h1), in the LLaMA block (`LlamaConfig.mamba`,
a `llama.Mamba2Config`; the other muP multipliers in `LlamaConfig.mup`): both
mixers read the SAME normed input h and their scaled outputs are ADDED before
one residual,

    x' = x + ssm_out SSM(h) + attention_out Attn(h);  x'' = x' + MLP(norm(x')).

— or IN PLACE of attention in the blocks of kind "ssm" of a model whose blocks
are ONE mixer each (Nemotron-H, `Mamba2Config.beside` False: `RULE_ALONE`, a
kind of slot leaves alone, x' = x + SSM(h) and nothing after it; the other
blocks run no rule). Which of the two a config runs is `Rule.resolve`'s.

Attn is models/llama.py's (GQA, rotary embedding, K and V a position). SSM,
with H heads of P in G groups, a state N wide, u = ssm_in h:

    p = (u W_in) * mup            [z H P | x H P | B G N | C G N | dt H], `mup`
                                  holding ssm_multipliers[0..4] over the slices
    c = silu(b + conv4([x | B | C]))      causal, depthwise, 4 taps, a bias
    dt = softplus(dt + dt_bias_h), A_h = -exp(A_log_h), a = exp(dt A_h)
    S_t = a_t S_{t-1} + dt_t x_t B_t^T    (P x N a head; head h reads group
    y_t = S_t C_t + D_h x_t                h // (H / G)'s B and C)
    g = y * silu(z), RMS-normalised within each of the G groups of H P / G
    channels (the gate FIRST), times a gain; SSM(h) = g W_out

**What a slot keeps, a layer: S (H, P, N) float32, the last three rows of
the un-convolved [x | B | C] — the TAIL — and the K and V of every
position.** ONE cache kind, "full", has paged `leaves` {k, v} under `tables`
AND `slot_leaves` {`ssm_state`, `conv_tail`} (behind models/state_kind.py
`StateKindRows` as this module's `RULE`, which runs BESIDE attention;
runtime/paged_kvcache.py's module docstring): a layer reaches its K/V blocks
through the slot's table and its state at the slot's row, in the same layer
body.

The multipliers ride where they cost nothing: `ssm_in` and the slices'
multipliers are ONE vector over the in-projection's outputs, whose [x | B |
C] part scales the convolution's taps (the convolution is linear) — so the
tail holds the rows as W_in gives them, in the compute dtype, and a row reads
the same whether it reaches the convolution through the tail or inside a
chunk.

Three forms of the same numbers:

  * **the recurrence** (`recurrence`): a `lax.scan` over positions — what
    chipbench/reference/falcon_h1.py computes on its own, here for the tests.
  * **the step** (decode, `step_rule`): one token a slot, the state read and
    written ONCE — on the chip by ops/pallas/ssm_step.py, in place in the
    pool's leaf (`step_rule_kernel`); `step_rule` is the CPU's path and the
    kernel's judge.
  * **the chunked rule** (prefill, `chunk_rule`; "SSD"): positions in chunks
    of `Mamba2Config.chunk` FROM AN INCOMING STATE. With G_t the cumulative
    sum of dt A inside the chunk: y_t = sum_{s <= t} exp(G_t - G_s) dt_s (C_t
    . B_s) x_s + exp(G_t) S_0 C_t + D x_t, and the outgoing state exp(G_c)
    S_0 + sum_s exp(G_c - G_s) dt_s x_s B_s^T. Every decay is the exp of a
    DIFFERENCE of cumulative logs that is <= 0. What does not depend on the
    state is made for all chunks at once; the scan over chunks is two
    matmuls a chunk.
  * a PAD position (at or past `n_real` in the chunk) has dt = 0: it is the
    identity on S and does not enter the tail (`takes_n_real`, as models/
    kda.py).

dt, the decays, their cumulative logs, the state and the rule's sums are
float32 at "highest" matmul precision whatever the cache's dtype (the rule
is 5.4 MFLOP a token a layer beside 860 of weights); the projections run in
the compute dtype.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from dnn_tpu.models import state_kind
from dnn_tpu.ops.nn import linear, silu

_HI = lax.Precision.HIGHEST
# the seeded initialisation (Mamba-2's published ranges): A = exp(A_log) and
# the step dt = softplus(dt_bias) are spread log-evenly a head over these
_A_RANGE = (1.0, 16.0)
_DT_RANGE = (1e-3, 1e-1)


def slot_leaves(cfg):
    """The kind's leaves with no position axis: name -> (the shape a slot a
    layer, dtype or None for the cache's)."""
    m = cfg.mamba
    return {"ssm_state": ((m.n_head, m.head_dim, m.d_state), jnp.float32),
            "conv_tail": ((m.conv - 1, m.conv_width), None)}


def _slices(m):
    """The in-projection's slices [z | x | B | C | dt]: their widths."""
    gn = m.n_groups * m.d_state
    return (m.d_ssm, m.d_ssm, gn, gn, m.n_head)


def _mup_vector(m) -> Optional[np.ndarray]:
    """`ssm_in` times `ssm_multipliers` over the in-projection's outputs;
    None — nothing to trace — where every one of them is 1."""
    if m.ssm_in == 1.0 and all(s == 1.0 for s in m.ssm_multipliers):
        return None
    return m.ssm_in * np.concatenate([
        np.full((w,), s, np.float32)
        for w, s in zip(_slices(m), m.ssm_multipliers)])


def init_mixer(key, cfg, dtype=jnp.float32):
    """A layer's `ssm` entry. W_in and W_out N(0, 0.02^2) (W_out over sqrt(2
    x layers)) with their multipliers divided out (`llama.init_block`); the
    taps N(0, 1 / conv) over the scale a row has at initialisation, so that
    c_t is of order 1 and every tap matters; `A_log` and `dt_bias` spread a
    head, log-evenly, over `_A_RANGE` and `_DT_RANGE`: a token's retention
    exp(dt A) runs from 0.999 in head 0 to 0.2 in the last, before the
    token's own part of dt; gains and D exactly 1."""
    m, c = cfg.mamba, cfg.n_embd
    ks = jax.random.split(key, 3)
    mup = _mup_vector(m)
    w_in = jax.random.normal(ks[0], (c, m.proj_width)) * 0.02 / (
        1.0 if mup is None else mup)
    w_out = (jax.random.normal(ks[1], (m.d_ssm, c)) * 0.02
             / (2 * cfg.n_layer) ** 0.5 / m.ssm_out)
    row_sigma = 0.02 * math.sqrt(c)  # of a row of W_in's products, scaled
    a = jnp.exp(jnp.linspace(*(math.log(x) for x in _A_RANGE), m.n_head))
    dt = jnp.exp(jnp.linspace(*(math.log(x) for x in _DT_RANGE), m.n_head))
    return {
        "in": {"kernel": w_in.astype(dtype)},
        "out": {"kernel": w_out.astype(dtype)},
        "conv": {"taps": (jax.random.normal(ks[2], (m.conv, m.conv_width))
                          / (math.sqrt(m.conv) * row_sigma)
                          ).astype(jnp.float32),
                 "bias": jnp.zeros((m.conv_width,), jnp.float32)},
        "a_log": jnp.log(a).astype(jnp.float32),
        "d": jnp.ones((m.n_head,), jnp.float32),
        # softplus(dt_bias) == dt
        "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(jnp.float32),
        "norm": {"scale": jnp.ones((m.d_ssm,), jnp.float32)},
    }


def _project(p, h, *, m, compute_dtype):
    """h (B, T, C) -> (z (B, T, H P) float32, the un-convolved rows [x | B |
    C] (B, T, W) as W_in gives them, dt (B, T, H) float32 > 0)."""
    proj = linear(p["in"], h, compute_dtype=compute_dtype)
    wz, wc = m.d_ssm, m.d_ssm + m.conv_width
    mup = _mup_vector(m)

    def scaled(v, part):
        return v if mup is None else v * mup[part]

    z = scaled(proj[..., :wz].astype(jnp.float32), slice(None, wz))
    dt = jax.nn.softplus(
        scaled(proj[..., wc:].astype(jnp.float32), slice(wc, None))
        + p["dt_bias"])
    return z, proj[..., wz:wc], dt


def _taps(p, m):
    """The convolution's taps with the slices' multipliers in them."""
    mup = _mup_vector(m)
    return p["conv"]["taps"] if mup is None else (
        p["conv"]["taps"] * mup[m.d_ssm:m.d_ssm + m.conv_width])


def _heads(conved, p, m):
    """The convolution's output (B, T, W) float32 -> x (B, T, H, P), B and C
    (B, T, G, N) after the bias and SiLU."""
    c = silu(conved + p["conv"]["bias"])
    gn = m.n_groups * m.d_state
    lead = c.shape[:-1]
    return (c[..., :m.d_ssm].reshape(*lead, m.n_head, m.head_dim),
            c[..., m.d_ssm:m.d_ssm + gn].reshape(*lead, m.n_groups, m.d_state),
            c[..., m.d_ssm + gn:].reshape(*lead, m.n_groups, m.d_state))


def _out(p, y, z, x_dtype, *, m, eps, compute_dtype):
    """y (B, T, H, P) float32 -> the mixer's output (B, T, C): the gate
    FIRST, the RMS norm within each group's channels, the gain, W_out."""
    lead = y.shape[:-2]
    g = (y.reshape(*lead, m.d_ssm) * silu(z)).reshape(
        *lead, m.n_groups, m.d_ssm // m.n_groups)
    g = g * lax.rsqrt((g * g).mean(-1, keepdims=True) + eps)
    g = g.reshape(*lead, m.d_ssm) * p["norm"]["scale"]
    return linear(p["out"], g.astype(x_dtype), compute_dtype=compute_dtype)


def recurrence(x, dt, a_log, d, bm, cm, state):
    """The rule as it stands, a scan over positions: x (B, T, H, P), dt (B,
    T, H), bm, cm (B, T, G, N), `state` (B, H, P, N), float32 -> (y (B, T, H,
    P), the state after position T)."""
    h, g = x.shape[2], bm.shape[2]
    a = -jnp.exp(a_log)

    def one(s, xs):
        x_t, dt_t, b_t, c_t = xs
        b_t, c_t = (jnp.repeat(v, h // g, axis=1) for v in (b_t, c_t))
        s = (jnp.exp(dt_t * a)[..., None, None] * s
             + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :])
        return s, (s * c_t[:, :, None, :]).sum(-1) + d[:, None] * x_t

    state, y = lax.scan(one, state, tuple(
        jnp.moveaxis(v, 1, 0) for v in (x, dt, bm, cm)))
    return jnp.moveaxis(y, 0, 1), state


def chunk_rule(x, dt, a_log, d, bm, cm, state, *, m, chunk):
    """The rule over T positions in closed-form chunks of `chunk` (module
    docstring), arguments as `recurrence`'s; T is a multiple of `chunk`."""
    b, t, h, p = x.shape
    n = t // chunk

    def split(v):  # (B, T, ...) -> (B, n, chunk, ...)
        return v.reshape(b, n, chunk, *v.shape[2:])

    xg = split(x).reshape(b, n, chunk, m.n_groups, -1, p)   # b n s g r p
    dtg = split(dt).reshape(b, n, chunk, m.n_groups, -1)    # b n s g r
    bm, cm = split(bm), split(cm)                           # b n s g k
    cum = jnp.cumsum(dtg * -jnp.exp(a_log).reshape(m.n_groups, -1), axis=2)
    # inside a chunk: exp(G_t - G_s) dt_s (C_t . B_s), s <= t
    tri = jnp.tril(jnp.ones((chunk, chunk), bool))
    cumt = jnp.moveaxis(cum, 2, -1)                          # b n g r t
    decay = jnp.exp(jnp.where(tri, cumt[..., :, None] - cumt[..., None, :],
                              -jnp.inf))                     # b n g r t s
    cb = jnp.einsum("bntgk,bnsgk->bngts", cm, bm, precision=_HI)
    w = decay * cb[:, :, :, None] * jnp.moveaxis(dtg, 2, -1)[..., None, :]
    y = jnp.einsum("bngrts,bnsgrp->bntgrp", w, xg, precision=_HI)
    # what a chunk hands on: sum_s exp(G_c - G_s) dt_s x_s B_s^T
    last = cum[:, :, -1:]                                    # b n 1 g r
    xs = xg * (dtg * jnp.exp(last - cum))[..., None]
    add = jnp.einsum("bnsgrp,bnsgk->bngrpk", xs, bm, precision=_HI)
    keep = jnp.exp(last[:, :, 0])[..., None, None]           # b n g r 1 1
    into = jnp.exp(cum)                                      # b n t g r

    def one(s0, xs):
        c_c, into_c, add_c, keep_c = xs
        from_state = jnp.einsum("bgrpk,btgk->btgrp", s0, c_c, precision=_HI)
        return keep_c * s0 + add_c, into_c[..., None] * from_state

    state, ys = lax.scan(one, state.reshape(b, m.n_groups, -1, p, m.d_state),
                         tuple(jnp.moveaxis(v, 1, 0)
                               for v in (cm, into, add, keep)))
    y = y + jnp.moveaxis(ys, 0, 1) + d.reshape(m.n_groups, -1, 1) * xg
    return y.reshape(b, t, h, p), state.reshape(b, h, p, m.d_state)


def step_rule(x, dt, a_log, d, bm, cm, state, *, m):
    """One position: x (B, H, P), dt (B, H), bm, cm (B, G, N), `state` (B, H,
    P, N) -> (y (B, H, P), the new state); the state is read and written
    once. The plain form (the chip's is `step_rule_kernel`)."""
    b, h, p = x.shape
    s = state.reshape(b, m.n_groups, -1, p, m.d_state)
    a = jnp.exp(dt * -jnp.exp(a_log)).reshape(b, m.n_groups, -1, 1, 1)
    s = a * s + (dt[..., None] * x).reshape(
        b, m.n_groups, -1, p, 1) * bm[:, :, None, None, :]
    y = (s * cm[:, :, None, None, :]).sum(-1).reshape(b, h, p)
    return y + d[:, None] * x, s.reshape(state.shape)


def step_rule_kernel(x, dt, a_log, d, bm, cm, pool, *, m, layer,
                     interpret=False):
    """`step_rule` on the WHOLE pool leaf `pool` (L, B, H, P, N) at layer
    `layer`: the state's one pass — decay, add (dt x) B^T, answer with C,
    write back in place — in ops/pallas/ssm_step.py -> (y, pool)."""
    from dnn_tpu.ops.pallas.ssm_step import ssm_step

    del m  # the groups are bm's second axis
    pool, y = ssm_step(pool, layer, jnp.exp(dt * -jnp.exp(a_log)), dt, d, x,
                       bm, cm, interpret=interpret)
    return y, pool


def mixer_chunk(p, h, leaves, start_pos, n_real, *, cfg, compute_dtype,
                kernel=False):
    """The rule's chunk form (`state_kind.Rule`): the state-space mixer over
    a chunk h (B, T, C) whose first `n_real` positions are real; `leaves` —
    `ssm_state` (B, H, P, N) float32 and `conv_tail` (B, conv - 1, W) — come
    in and are left as they are after the last REAL position -> SSM(h) (B,
    T, C). No position enters the rule and the chunk form has no kernel."""
    del start_pos, kernel
    m = cfg.mamba
    t = h.shape[1]
    state, tail = leaves["ssm_state"], leaves["conv_tail"]
    with jax.named_scope("ssm.project"):
        z, pre, dt = _project(p, h, m=m, compute_dtype=compute_dtype)
        dt = jnp.where((jnp.arange(t) < n_real)[None, :, None], dt, 0.0)
    with jax.named_scope("ssm.conv"):
        conved, new_tail = state_kind.conv_chunk(tail, pre, _taps(p, m),
                                                 n_real)
        x, bm, cm = _heads(conved, p, m)
    with jax.named_scope("ssm.chunk"):
        y, state = chunk_rule(x, dt, p["a_log"], p["d"], bm, cm, state, m=m,
                              chunk=math.gcd(m.chunk, t))
    with jax.named_scope("ssm.out"):
        o = _out(p, y, z, h.dtype, m=m, eps=cfg.rms_eps,
                 compute_dtype=compute_dtype)
    leaves.update(ssm_state=state, conv_tail=new_tail.astype(tail.dtype))
    return o


def mixer_step(p, h, leaves, pos, *, cfg, compute_dtype, kernel=False,
               layer=None):
    """The rule's step form: one token a slot, h (B, 1, C), `leaves` as
    `mixer_chunk`'s -> SSM(h) (B, 1, C). `ssm_state` is one
    layer's states for the plain form; under `kernel` (True / "interpret")
    the WHOLE leaf, updated in place at `layer` (`step_rule_kernel`)."""
    del pos
    m = cfg.mamba
    tail, state = leaves["conv_tail"], leaves["ssm_state"]
    rule = functools.partial(
        step_rule_kernel, layer=layer,
        interpret=kernel == "interpret") if kernel else step_rule
    with jax.named_scope("ssm.project"):
        z, pre, dt = _project(p, h, m=m, compute_dtype=compute_dtype)
    with jax.named_scope("ssm.conv"):
        conved, rows = state_kind.conv_step(tail, pre, _taps(p, m))
        x, bm, cm = _heads(conved, p, m)
    with jax.named_scope("ssm.step"):
        y, state = rule(x[:, 0], dt[:, 0], p["a_log"], p["d"], bm[:, 0],
                        cm[:, 0], state, m=m)
    with jax.named_scope("ssm.out"):
        o = _out(p, y[:, None], z, h.dtype, m=m, eps=cfg.rms_eps,
                 compute_dtype=compute_dtype)
    leaves.update(ssm_state=state, conv_tail=rows[:, 1:].astype(tail.dtype))
    return o


def mixers_sum(attn_o, ssm_o, cfg):
    """attention_out Attn(h) + ssm_out SSM(h): what the block's first
    residual adds."""
    ao = 1.0 if cfg.mup is None else cfg.mup.attention_out
    return (attn_o.astype(jnp.float32) * ao
            + ssm_o.astype(jnp.float32) * cfg.mamba.ssm_out
            ).astype(attn_o.dtype)


def _init_block(blk, key, cfg, dtype):
    blk["ssm"] = init_mixer(jax.random.fold_in(key, 31), cfg, dtype)


def _rule_of(cfg):
    """The rule as `cfg` runs it (`Mamba2Config.beside`)."""
    return RULE if cfg.mamba.beside else RULE_ALONE


# beside softmax attention in every layer, in the kind that pages K and V
# (Falcon-H1)
RULE = state_kind.Rule(
    field="mamba", kind="full", params="ssm", slot_leaves=slot_leaves,
    init=_init_block, chunk=mixer_chunk, step=mixer_step, kernel="step",
    whole=("ssm_state",), beside=mixers_sum,
    forms=("ssm_prefill", "ssm_decode"), resolve=_rule_of)
# in attention's PLACE in the layers of kind "ssm", a kind of slot leaves
# alone (Nemotron-H: `Mamba2Config.beside` False)
RULE_ALONE = dataclasses.replace(RULE, kind="ssm", beside=None,
                                 forms=("prefill", "decode"))
