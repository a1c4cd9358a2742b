"""Linear attention with a FIXED decay a head (Lightning Attention) in a
DENSE LLaMA block whose layers are of two kinds (`LlamaConfig.lightning`, a
`LightningConfig`, with `layer_types` naming each layer "full" — softmax
attention over K and V that reads the blocks models/block_select.py chooses
— or "linear", this file).

With x the normed input of position t (H heads of d):

    q = rope(RMSNorm_head(x W_q)) / sqrt(d),  k = rope(RMSNorm_head(x W_k)),
    v = x W_v                      (theta `rope_theta`, the whole head)
    S_t = lambda_h S_{t-1} + k_t v_t^T          (d x d a head, S_{-1} = 0)
    o_t = S_t^T q_t;   y = [RMSNorm_head(o) * sigmoid(x W_gate)] W_o

lambda_h = exp(-s_h), s_h = 2^(-8 h / H) for h = 1..H (`slopes`): a constant
of the architecture, not a parameter — no convolution, no gate on the update,
nothing data-dependent in the decay. The output norm is a head's RMSNorm with
ONE gain of H d.

**What a slot keeps is S alone** — float32, 4 d^2 bytes a head whatever the
length (`slot_leaves`): the cache kind "linear" has one leaf with no position
axis, no blocks and no tables, as models/kda.py's has two — behind
models/state_kind.py `StateKindRows` as this module's `RULE`.

Three forms of the same numbers:

  * `recurrence` — the rule as it stands, a scan over positions: the oracle.
  * `step_rule` (decode) — one token a slot; the state read once and written
    once, the answer taken from the new state on its way out.
  * `chunk_rule` (prefill) — positions in chunks of `LightningConfig.chunk`
    FROM AN INCOMING STATE. With g_t the position's log-decay (-s_h, or 0 at
    a pad) and G_t its cumulative sum inside the chunk,

        o_t = exp(G_t) S_0^T q_t + sum_{s <= t} exp(G_t - G_s) (q_t . k_s) v_s
        S_C = exp(G_C) S_0 + sum_s exp(G_C - G_s) k_s v_s^T

    **every decay the exp of a difference <= 0**. What does not depend on the
    state — the (c, c) weights, the chunk's own sum of k v^T — is made for
    all chunks at once; the scan over chunks is one multiply-add of states.
  * a PAD position (at or past `n_real`) is the identity on S — log-decay 0,
    k 0 — so the chunk program is told how many of its positions are real
    (`takes_n_real`, as models/kda.py).

The rule's own arithmetic is float32 at "highest" matmul precision; the
projections run in the compute dtype. Scopes: `lin.project`, `lin.chunk`,
`lin.step`, `lin.out`.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from dnn_tpu.models import state_kind
from dnn_tpu.ops.attention import (
    apply_rope,
    merge_heads,
    rope_cos_sin,
    split_heads,
)
from dnn_tpu.ops.nn import linear, rms_norm

_HI = lax.Precision.HIGHEST

__all__ = ["slopes", "slot_leaves", "init_mixer", "recurrence", "step_rule",
           "step_rule_kernel", "chunk_rule", "mixer_chunk", "mixer_step",
           "RULE"]


def slopes(n_head: int):
    """s_h = 2^(-8 h / H), h = 1..H: the decay a head is exp(-s_h)."""
    return jnp.asarray([2.0 ** (-8.0 * h / n_head)
                        for h in range(1, n_head + 1)], jnp.float32)


def slot_leaves(cfg):
    """The linear kind's ONE cache leaf — no position axis, no tables —:
    name -> (the shape a slot a layer, dtype)."""
    m = cfg.lightning
    return {"state": ((m.n_head, m.head_dim, m.head_dim), jnp.float32)}


def init_mixer(key, cfg, dtype=jnp.float32):
    """A linear layer's `attn` entry."""
    m, c = cfg.lightning, cfg.n_embd
    ks = jax.random.split(key, 7)

    def kernel(k, shape, std=0.02):
        return {"kernel": (jax.random.normal(k, shape) * std).astype(dtype)}

    def gain(k):
        if cfg.qk_norm_init == 1.0:
            return jnp.ones((m.head_dim,), dtype)
        return (cfg.qk_norm_init * (1.0 + 0.1 * jax.random.normal(
            k, (m.head_dim,)))).astype(dtype)

    return {
        "q": kernel(ks[0], (c, m.width)),
        "k": kernel(ks[1], (c, m.width)),
        "v": kernel(ks[2], (c, m.width)),
        "o": kernel(ks[3], (m.width, c), 0.02 / (2 * cfg.n_layer) ** 0.5),
        "gate": kernel(ks[4], (c, m.width)),
        "q_norm": {"scale": gain(ks[5])},
        "k_norm": {"scale": gain(ks[6])},
        "o_norm": {"scale": jnp.ones((m.width,), jnp.float32)},
    }


def _project(p, h, positions, *, cfg, compute_dtype):
    """h (B, T, C) at absolute `positions` (T,) or (B, T) -> q (scaled), k, v
    (B, H, T, d) float32 — q and k normed a head and rotated — and the output
    gate's pre-activation (B, T, H d)."""
    m = cfg.lightning

    def lin(name):
        return linear(p[name], h, compute_dtype=compute_dtype)

    q, k, v = (split_heads(lin(n), m.n_head) for n in "qkv")
    q = rms_norm(p["q_norm"], q, eps=cfg.rms_eps)
    k = rms_norm(p["k_norm"], k, eps=cfg.rms_eps)
    cos, sin = rope_cos_sin(positions, m.head_dim, theta=m.rope_theta)
    if cos.ndim == 3:  # (B, T, d): a position a slot
        cos, sin = cos[:, None], sin[:, None]
    q = apply_rope(q.astype(jnp.float32), cos, sin)
    k = apply_rope(k.astype(jnp.float32), cos, sin)
    return (q / math.sqrt(m.head_dim), k, v.astype(jnp.float32),
            lin("gate"))


def _out(p, o, gate, x_dtype, *, cfg, compute_dtype):
    """o (B, H, T, d) float32 -> the mixer's output (B, T, C): a head's
    RMSNorm under its slice of the one gain, the sigmoid gate, W_o."""
    y = o * lax.rsqrt((o * o).mean(-1, keepdims=True) + cfg.rms_eps)
    y = merge_heads(y) * p["o_norm"]["scale"].astype(jnp.float32)
    y = y * jax.nn.sigmoid(gate.astype(jnp.float32))
    return linear(p["o"], y.astype(x_dtype), compute_dtype=compute_dtype)


def recurrence(q, k, v, g, state):
    """The rule one position at a time: q, k, v (B, H, T, d), g (B, H, T) the
    positions' log-decays (<= 0), `state` (B, H, d, d) -> (o (B, H, T, d),
    the state after the last position)."""
    def position(s, now):
        q_t, k_t, v_t, g_t = now
        s = jnp.exp(g_t)[..., None, None] * s \
            + k_t[..., :, None] * v_t[..., None, :]
        return s, jnp.einsum("bhc,bhcv->bhv", q_t, s, precision=_HI)

    state, o = lax.scan(position, state, tuple(
        jnp.moveaxis(x, 2, 0) for x in (q, k, v, g)))
    return jnp.moveaxis(o, 0, 2), state


def step_rule(q, k, v, g, state):
    """One position: q, k, v (B, H, d), g (B, H) its log-decay, `state` (B,
    H, d, d) -> (o (B, H, d), the new state)."""
    s = jnp.exp(g)[..., None, None] * state + k[..., :, None] * v[..., None, :]
    return (q[..., :, None] * s).sum(-2), s


def step_rule_kernel(q, k, v, pool, *, layer, interpret=False):
    """`step_rule` on the WHOLE state leaf `pool` (L, B, H, d, d), layer
    `layer` of it updated in place by ops/pallas/lin_step.py -> (o (B, H,
    d), the leaf)."""
    from dnn_tpu.ops.pallas.lin_step import lin_step

    pool, o = lin_step(pool, layer, jnp.exp(-slopes(q.shape[1])), q, k, v,
                       interpret=interpret)
    return o, pool


def chunk_rule(q, k, v, g, state, *, chunk):
    """The rule over T positions in closed-form chunks of `chunk` (module
    docstring): q, k, v (B, H, T, d), g (B, H, T) <= 0, float32; `state` (B,
    H, d, d) the incoming S -> (o (B, H, T, d), the outgoing S). T is a
    multiple of `chunk`."""
    b, h, t, d = q.shape
    n = t // chunk

    def split(x):  # (B, H, T, ...) -> (B, H, n, chunk, ...)
        return x.reshape(b, h, n, chunk, *x.shape[3:])

    q, k, v, g = (split(x) for x in (q, k, v, g))
    cum = jnp.cumsum(g, axis=3)  # G_t (B, H, n, c)
    last = cum[..., -1:]
    # exp(G_t - G_s), s <= t: the exp of a difference <= 0
    tri = jnp.tril(jnp.ones((chunk, chunk), bool))
    weights = jnp.exp(jnp.where(tri, cum[..., :, None] - cum[..., None, :],
                                -jnp.inf))
    a = jnp.einsum("bhntd,bhnsd->bhnts", q, k, precision=_HI) * weights
    o = jnp.einsum("bhnts,bhnsd->bhntd", a, v, precision=_HI)
    # the chunk's own sum, decayed to its end
    own = jnp.einsum("bhnsc,bhnsv->bhncv", k * jnp.exp(last - cum)[..., None],
                     v, precision=_HI)
    decay = jnp.exp(last)[..., None]  # (B, H, n, 1, 1)

    def one(s0, xs):
        own_c, a_c = xs
        return a_c * s0 + own_c, s0

    state, into = lax.scan(one, state, (jnp.moveaxis(own, 2, 0),
                                        jnp.moveaxis(decay, 2, 0)))
    into = jnp.moveaxis(into, 0, 2)  # each chunk's incoming S
    o = o + jnp.einsum("bhntc,bhncv->bhntv", q * jnp.exp(cum)[..., None],
                       into, precision=_HI)
    return o.reshape(b, h, t, d), state


def _log_decay(m, real):
    """real (..., T) bool -> (..., H, T): -s_h at a real position, 0 at a
    pad."""
    return jnp.where(real[..., None, :], -slopes(m.n_head)[:, None], 0.0)


def mixer_chunk(p, h, leaves, start_pos, n_real, *, cfg, compute_dtype,
                kernel=False):
    """The rule's chunk form (`state_kind.Rule`): the linear mixer over a
    chunk h (B, T, C) at [start_pos, start_pos + T) whose first `n_real`
    positions are real; `leaves` — `state` (B, H, d, d) float32 — comes in
    and is left as it is after the last REAL position -> y (B, T, C). The
    chunk form has no kernel."""
    del kernel
    m = cfg.lightning
    t = h.shape[1]
    with jax.named_scope("lin.project"):
        q, k, v, gate = _project(p, h, start_pos + jnp.arange(t), cfg=cfg,
                                 compute_dtype=compute_dtype)
        real = jnp.arange(t) < n_real
        k = jnp.where(real[None, None, :, None], k, 0.0)
        g = jnp.broadcast_to(_log_decay(m, real), q.shape[:3])
    with jax.named_scope("lin.chunk"):
        o, state = chunk_rule(q, k, v, g, leaves["state"],
                              chunk=math.gcd(m.chunk, t))
    with jax.named_scope("lin.out"):
        y = _out(p, o, gate, h.dtype, cfg=cfg, compute_dtype=compute_dtype)
    leaves.update(state=state)
    return y


def mixer_step(p, h, leaves, pos, *, cfg, compute_dtype, kernel=False,
               layer=None):
    """The rule's step form: one token a slot, h (B, 1, C) at per-slot
    positions `pos` (B,) -> y (B, 1, C). `state` is one layer's
    for the plain form; under `kernel` (True / "interpret") the WHOLE leaf,
    updated in place at `layer` (`step_rule_kernel`)."""
    m = cfg.lightning
    state = leaves["state"]
    with jax.named_scope("lin.project"):
        q, k, v, gate = _project(p, h, pos[:, None], cfg=cfg,
                                 compute_dtype=compute_dtype)
    with jax.named_scope("lin.step"):
        if kernel:
            o, state = step_rule_kernel(
                q[:, :, 0], k[:, :, 0], v[:, :, 0], state, layer=layer,
                interpret=kernel == "interpret")
        else:
            g = jnp.broadcast_to(-slopes(m.n_head), q.shape[:2])
            o, state = step_rule(q[:, :, 0], k[:, :, 0], v[:, :, 0], g,
                                 state)
    with jax.named_scope("lin.out"):
        y = _out(p, o[:, :, None], gate, h.dtype, cfg=cfg,
                 compute_dtype=compute_dtype)
    leaves.update(state=state)
    return y


def _init_block(blk, key, cfg, dtype):
    blk["attn"] = init_mixer(jax.random.fold_in(key, 37), cfg, dtype)


RULE = state_kind.Rule(
    field="lightning", kind="linear", params="attn", slot_leaves=slot_leaves,
    init=_init_block, chunk=mixer_chunk, step=mixer_step, kernel="step",
    whole=("state",),
    # the kernel's tiles are whole lanes of a head's width
    fits=lambda cfg: cfg.lightning.head_dim % 128 == 0)
