"""Linear attention with a state and NO cached positions: the gated delta
rule with a decay a CHANNEL (Kimi Delta Attention) behind a short causal
convolution, in the LLaMA block (`MixtralConfig.kda`, a `KdaConfig`, with
`layer_types` naming each layer "full" — softmax attention over K and V,
models/llama.py — or "linear", this file).

With x the normed input of position t (H heads of d; r the low rank):

    q~ = x W_q, k~ = x W_k, v~ = x W_v                       (H d each)
    q' = silu(conv4(q~)), k', v' alike   (causal, depthwise, 4 taps:
                                          y_t = sum_j c_j x_{t-3+j})
    q = l2norm(q') / sqrt(d), k = l2norm(k') a head; v = v'
    g = -exp(A_log_h) softplus(x W_f1 W_f2 + dt_bias)   (H d, <= 0)
    beta = 2 sigmoid(x W_b) a head                      (0, 2)
    S_t = (I - beta k k^T) Diag(exp g) S_{t-1} + beta k v^T   (d x d a head)
    o = S_t^T q;  y = [RMSNorm_head(o) * sigmoid(x W_g1 W_g2)] W_o

**What a slot keeps is S and the last three rows of [q~ | k~ | v~]** — a
state, float32, 4 d^2 bytes a head whatever the length, and a convolution
tail — not a position's anything: the cache kind "linear" has leaves
without a position axis and without blocks (`slot_leaves`, behind
models/state_kind.py `StateKindRows` as this module's `RULE`;
runtime/paged_kvcache.py's module docstring).

Two forms of the same numbers:

  * **the step** (decode, `step_rule`): the recurrence as it stands, one
    token a slot, the state read and written once.
  * **the chunked rule** (prefill, `chunk_rule`): positions in chunks of
    `KdaConfig.chunk`, a chunk in closed (WY) form FROM AN INCOMING STATE.
    With G_r the cumulative log-decay inside the chunk, every position's
    update is S_r = Diag(a_r) S_{r-1} + k_r u_r^T where the pseudo-values
    solve the unit lower-triangular system

        (I + Diag(beta) A) U = Diag(beta) (V - (K * exp G) S_0),
        A[r, i] = sum_c k_r[c] k_i[c] exp(G_r[c] - G_i[c])      (i < r)

    and O = (Q * exp G) S_0 + B U with B[r, i] the same sum over q_r (i <=
    r), S_C = Diag(exp G_C) S_0 + (K * exp(G_C - G))^T U. **Every decay
    enters as exp of a DIFFERENCE of cumulative log-decays that is <= 0**:
    with g down to -1.6 a position the usual k / exp(G) overflows float32
    inside a 64-position chunk. A[r, i] and B[r, i] are formed pair by
    pair (a multiply-and-reduce over the channel) inside sub-blocks of 16
    positions, and between sub-blocks as matmuls of rows and columns both
    scaled against the later sub-block's first cumulative log-decay; the
    triangular system is solved by exact block substitution (no power of
    its matrix: a run of equal keys makes them huge before they cancel).
    All of that is independent of the state. The plain form (the CPU's,
    and the tests' twin of the kernel) makes it for every chunk of a
    prefill chunk at once, as arrays with a pair axis in HBM, and scans
    the chunks with four matmuls each; on the chip ONE kernel does all of
    it, a chunk of a few heads a grid step, in fast memory
    (ops/pallas/delta_rule.py, behind `StateKindRows.kernel_form`): what
    crosses HBM is q, k, v, g, beta and the state.
  * a PAD position (at or past `n_real` in the chunk) is the identity on
    S — beta 0, g 0 — and does not enter the tail: a recurrence has no
    mask to hide a padded tail behind, so the chunk program is told how
    many of its positions are real (`runtime/serving.py` `prefill_chunk`).

The rule's own arithmetic is float32 at "highest" matmul precision (it is
~9 GFLOP a 1024-position chunk a layer: nothing beside the projections);
the projections run in the compute dtype. The step is plain `jax.numpy`
inside the step program; so is the chunked rule wherever the kernel is not
built for the widths (`RULE.fits`: heads of 128 lanes, chunks of 64; a
prefill chunk that is not whole chunks) or the backend is not the chip.
(What was measured: PERF.md section 6 — PR 47, the pair-by-pair form the
blocked one replaced and the scan's kernel; PR 61, the whole rule's.)
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
from jax import lax

from dnn_tpu.models import state_kind
from dnn_tpu.ops.attention import merge_heads, split_heads
from dnn_tpu.ops.nn import linear, rms_norm, silu

_HI = lax.Precision.HIGHEST
_L2_EPS = 1e-6


@dataclasses.dataclass(frozen=True)
class KdaConfig:
    """The widths of a model's "linear" layers."""
    n_head: int = 64
    head_dim: int = 128
    conv: int = 4          # taps of the short convolution
    rank: int = 128        # the decay's and the output gate's low rank
    chunk: int = 64        # positions a closed-form chunk

    @property
    def width(self):
        return self.n_head * self.head_dim


def slot_leaves(cfg):
    """The linear kind's cache leaves — no position axis, no tables —: name
    -> (the shape a slot a layer, dtype or None for the cache's)."""
    m = cfg.kda
    return {"state": ((m.n_head, m.head_dim, m.head_dim), jnp.float32),
            "conv_tail": ((m.conv - 1, 3 * m.width), None)}


# the seeded initialisation (the family's): A = exp(A_log) ~ U(_A_RANGE) a
# head, dt log-uniform in _DT_RANGE a channel, dt_bias its inverse softplus
_A_RANGE = (1.0, 16.0)
_DT_RANGE = (1e-3, 1e-1)


def init_mixer(key, cfg, dtype=jnp.float32):
    """A linear layer's `attn` entry."""
    m, c = cfg.kda, cfg.n_embd
    ks = jax.random.split(key, 14)

    def kernel(k, shape, std=0.02):
        return {"kernel": (jax.random.normal(k, shape) * std).astype(dtype)}

    a = jax.random.uniform(ks[9], (m.n_head,), minval=_A_RANGE[0],
                           maxval=_A_RANGE[1])
    dt = jnp.exp(jax.random.uniform(
        ks[10], (m.width,), minval=math.log(_DT_RANGE[0]),
        maxval=math.log(_DT_RANGE[1])))
    return {
        "q": kernel(ks[0], (c, m.width)),
        "k": kernel(ks[1], (c, m.width)),
        "v": kernel(ks[2], (c, m.width)),
        "o": kernel(ks[3], (m.width, c), 0.02 / (2 * cfg.n_layer) ** 0.5),
        "f1": kernel(ks[4], (c, m.rank)),
        "f2": kernel(ks[5], (m.rank, m.width)),
        "g1": kernel(ks[6], (c, m.rank)),
        "g2": kernel(ks[7], (m.rank, m.width)),
        "b": kernel(ks[8], (c, m.n_head)),
        # taps: N(0, 1 / conv) so that a tap's output keeps its input's
        # scale and every tap matters (a program without the convolution,
        # or without the tail between chunks, is told apart)
        "conv": {n: {"taps": (jax.random.normal(k, (m.conv, m.width))
                              / math.sqrt(m.conv)).astype(jnp.float32)}
                 for n, k in zip("qkv", ks[11:14])},
        "a_log": jnp.log(a).astype(jnp.float32),
        "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(jnp.float32),
        "o_norm": {"scale": jnp.ones((m.head_dim,), jnp.float32)},
    }


def _taps(p):
    return jnp.concatenate([p["conv"][n]["taps"] for n in "qkv"], axis=-1)


def _project(p, h, *, m, compute_dtype):
    """h (B, T, C) -> (pre-convolution rows [q~ | k~ | v~] (B, T, 3 H d),
    g (B, T, H, d) float32 <= 0, beta (B, T, H) float32, the output gate's
    pre-activation (B, T, H d))."""
    def lin(name, x):
        return linear(p[name], x, compute_dtype=compute_dtype)

    pre = jnp.concatenate([lin(n, h) for n in "qkv"], axis=-1)
    f = lin("f2", lin("f1", h)).astype(jnp.float32) + p["dt_bias"]
    g = -jnp.exp(p["a_log"])[:, None] * jax.nn.softplus(f).reshape(
        *f.shape[:-1], m.n_head, m.head_dim)
    beta = 2.0 * jax.nn.sigmoid(lin("b", h).astype(jnp.float32))
    return pre, g, beta, lin("g2", lin("g1", h))


def _qkv_heads(conved, m):
    """silu(conv) rows (B, T, 3 H d) -> q (normalised, scaled), k
    (normalised), v, each (B, H, T, d) float32."""
    y = silu(conved.astype(jnp.float32))
    q, k, v = (split_heads(part, m.n_head) for part in jnp.split(y, 3, -1))

    def l2(x):
        return x * lax.rsqrt((x * x).sum(-1, keepdims=True) + _L2_EPS)

    return l2(q) / math.sqrt(m.head_dim), l2(k), v


def _out(p, o, gate, x_dtype, *, m, eps, compute_dtype):
    """o (B, H, T, d) float32 -> the mixer's output (B, T, C): a head's
    RMSNorm (one gain of d), the sigmoid gate, W_o."""
    y = rms_norm(p["o_norm"], o, eps=eps)
    y = merge_heads(y) * jax.nn.sigmoid(gate.astype(jnp.float32))
    return linear(p["o"], y.astype(x_dtype), compute_dtype=compute_dtype)


def _unit_lower_inverse(low, block):
    """(I + `low`)^-1 for strictly lower-triangular `low` (..., c, c), by
    exact substitution in blocks of `block`: each diagonal block's inverse
    row by row (row i = e_i - low[i, :i] X[:i]), then the block rows below
    it, T[I, :I] = -X_I (low[I, :I] T[:I, :I]). No power of `low` is ever
    formed: with equal keys in a run of positions (a repeated token) and
    beta near 2 they reach 1e6 before they cancel."""
    c = low.shape[-1]
    n = c // block
    lead = low.shape[:-2]
    blocks = low.reshape(*lead, n, block, n, block)
    diag = jnp.stack([blocks[..., i, :, i, :] for i in range(n)], axis=-3)
    eye = jnp.eye(block, dtype=low.dtype)
    rows = [jnp.broadcast_to(eye[0], (*diag.shape[:-2], block))]
    for i in range(1, block):
        rows.append(eye[i] - jnp.einsum(
            "...j,...jk->...k", diag[..., i, :i], jnp.stack(rows, axis=-2),
            precision=_HI))
    x = jnp.stack(rows, axis=-2)  # (..., n, block, block)
    out = x[..., 0, :, :]
    for i in range(1, n):
        left = low[..., i * block:(i + 1) * block, :i * block]
        below = -jnp.einsum("...ab,...bc->...ac", x[..., i, :, :], jnp.einsum(
            "...ab,...bc->...ac", left, out, precision=_HI), precision=_HI)
        top = jnp.concatenate(
            [out, jnp.zeros((*lead, i * block, block), low.dtype)], axis=-1)
        out = jnp.concatenate(
            [top, jnp.concatenate([below, x[..., i, :, :]], axis=-1)],
            axis=-2)
    return out


def chunk_rule(q, k, v, g, beta, state, *, chunk, block=16, kernel=False):
    """The delta rule over T positions in closed-form chunks of `chunk`
    (module docstring): q, k, v (B, H, T, d), g (B, H, T, d) <= 0, beta
    (B, H, T), all float32; `state` (B, H, d, d) the incoming S -> (o (B,
    H, T, d), the outgoing S). T is a multiple of `chunk`.

    `kernel` (True / "interpret"): ops/pallas/delta_rule.py makes all of
    it in fast memory, a chunk of a few heads a grid step (d a multiple of
    128, `block` of 8). The plain form below makes what does not depend on
    the state for ALL chunks at once: the decay products A and B — between
    sub-blocks of `block` positions as matmuls of rows and columns scaled
    against the later sub-block's first cumulative log-decay (both
    exponents <= 0), inside a sub-block pair by pair —, T = (I + Diag(beta)
    A)^-1 by exact block substitution, and W = T Diag(beta) (K * exp G),
    U~ = T Diag(beta) V. The scan over chunks is then four matmuls a chunk:
    U = U~ - W S, O = (Q * exp G) S + B U, S' = Diag(exp G_C) S + (K *
    exp(G_C - G))^T U."""
    b, h, t, d = q.shape
    n = t // chunk
    block = math.gcd(block, chunk)
    m = chunk // block

    def split(x):  # (B, H, T, ...) -> (B, H, n, chunk, ...)
        return x.reshape(b, h, n, chunk, *x.shape[3:])

    q, k, v, g, beta = (split(x) for x in (q, k, v, g, beta))
    if kernel:  # everything below, a chunk of a few heads a grid step
        from dnn_tpu.ops.pallas.delta_rule import delta_rule

        o, state = delta_rule(*(x.reshape(b * h, *x.shape[2:]) for x in (
            q, k, v, g, beta, state)), block=block,
            interpret=kernel == "interpret")
        return o.reshape(b, h, t, d), state.reshape(b, h, d, d)
    cum = jnp.cumsum(g, axis=3)  # G_r (B, H, n, c, d)
    # sub-blocks: (B, H, n, m, block, d); `ref` the cumulative log-decay
    # before a sub-block's first position
    sub = lambda x: x.reshape(b, h, n, m, block, d)  # noqa: E731
    cs, ks, qs = sub(cum), sub(k), sub(q)
    ref = jnp.concatenate([jnp.zeros_like(cs[:, :, :, :1, -1]),
                           cs[:, :, :, :-1, -1]], axis=3)  # (B, H, n, m, d)
    inner = jnp.exp(cs - ref[:, :, :, :, None])  # rows against their own
    # columns of EARLIER sub-blocks against a later sub-block's `ref`
    earlier = (jnp.arange(chunk)[None, :] // block
               < jnp.arange(m)[:, None])  # (m, c)
    outer = jnp.exp(jnp.where(
        earlier[:, :, None], ref[:, :, :, :, None] - cum[:, :, :, None],
        -jnp.inf))  # (B, H, n, m, c, d)
    k_cols = k[:, :, :, None] * outer

    def between(rows):  # (B, H, n, m, block, d) -> (B, H, n, c, c)
        return jnp.einsum("bhnmsd,bhnmcd->bhnmsc", rows * inner, k_cols,
                          precision=_HI).reshape(b, h, n, chunk, chunk)

    # inside a sub-block, pair by pair: exp(G_r - G_i), i <= r
    tri = jnp.tril(jnp.ones((block, block), bool))
    pair = jnp.exp(jnp.where(
        tri[:, :, None], cs[:, :, :, :, :, None] - cs[:, :, :, :, None],
        -jnp.inf))  # (B, H, n, m, r, i, d)
    k_pair = ks[:, :, :, :, None] * pair

    def within(rows):  # -> (B, H, n, c, c), the diagonal blocks
        blocks = (rows[:, :, :, :, :, None] * k_pair).sum(-1)
        eye = jnp.eye(m, dtype=blocks.dtype)
        return jnp.einsum("bhnmri,mj->bhnmrji", blocks, eye).reshape(
            b, h, n, chunk, chunk)

    strict = jnp.tril(jnp.ones((chunk, chunk), bool), -1)
    a = jnp.where(strict, between(ks) + within(ks), 0.0)
    bq = between(qs) + within(qs)  # zero above the diagonal
    inv = _unit_lower_inverse(beta[..., None] * a, block)
    into = jnp.exp(cum)  # Diag(a_r ... a_1): what S_0 has decayed to
    solved = jnp.einsum(
        "bhnri,bhnix->bhnrx", inv, beta[..., None] * jnp.concatenate(
            [k * into, v], axis=-1), precision=_HI)
    w, u0 = solved[..., :d], solved[..., d:]
    last = cum[:, :, :, -1:, :]
    k_out = k * jnp.exp(last - cum)
    decay = jnp.swapaxes(jnp.exp(last), 3, 4)  # (B, H, n, d, 1)

    def one(s0, xs):
        w_c, u_c, q_c, b_c, k_c, a_c = xs
        u = u_c - jnp.einsum("bhrc,bhcv->bhrv", w_c, s0, precision=_HI)
        o = jnp.einsum("bhrc,bhcv->bhrv", q_c, s0, precision=_HI) \
            + jnp.einsum("bhri,bhiv->bhrv", b_c, u, precision=_HI)
        s1 = a_c * s0 + jnp.einsum("bhic,bhiv->bhcv", k_c, u, precision=_HI)
        return s1, o

    state, o = lax.scan(one, state, tuple(
        jnp.moveaxis(x, 2, 0) for x in (w, u0, q * into, bq, k_out, decay)))
    return jnp.moveaxis(o, 0, 2).reshape(b, h, t, d), state


def step_rule(q, k, v, g, beta, state):
    """One position: q, k, v, g (B, H, d), beta (B, H), `state` (B, H, d,
    d) -> (o (B, H, d), the new state). The state is read TWICE and
    written once: k . S~ and q . S~ of the decayed S~ in one pass (o = S'^T
    q = S~^T q + (q . k) u needs no pass over the new state), the rank-one
    update in the other."""
    s = jnp.exp(g)[..., None] * state
    ks, qs = (k[..., None] * s).sum(-2), (q[..., None] * s).sum(-2)
    u = beta[..., None] * (v - ks)
    o = qs + (q * k).sum(-1, keepdims=True) * u
    return o, s + k[..., None] * u[..., None, :]


def mixer_chunk(p, h, leaves, start_pos, n_real, *, cfg, compute_dtype,
                kernel=False):
    """The rule's chunk form (`state_kind.Rule`): the linear mixer over a
    chunk h (B, T, C) whose first `n_real` positions are real; `leaves` —
    `state` (B, H, d, d) float32 and `conv_tail` (B, conv - 1, 3 H d) —
    come in and are left as they are after the last REAL position -> y (B,
    T, C). `kernel` (True / "interpret"): the chunked rule runs in
    ops/pallas/delta_rule.py where T is whole chunks of `cfg.kda.chunk`,
    else in the plain form. No position enters the rule."""
    del start_pos
    m = cfg.kda
    t = h.shape[1]
    state, tail = leaves["state"], leaves["conv_tail"]
    with jax.named_scope("kda.project"):
        pre, g, beta, gate = _project(p, h, m=m, compute_dtype=compute_dtype)
        conved, new_tail = state_kind.conv_chunk(
            tail, pre, lambda: _taps(p), n_real)
        q, k, v = _qkv_heads(conved, m)
        real = jnp.arange(t) < n_real
        g = jnp.where(real[None, :, None, None], g, 0.0)
        beta = jnp.where(real[None, :, None], beta, 0.0)
    with jax.named_scope("kda.scan"):
        c = math.gcd(m.chunk, t)
        o, state = chunk_rule(q, k, v, jnp.moveaxis(g, 1, 2),
                              jnp.moveaxis(beta, 1, 2), state, chunk=c,
                              kernel=c == m.chunk and kernel)
    with jax.named_scope("kda.out"):
        y = _out(p, o, gate, h.dtype, m=m, eps=cfg.rms_eps,
                 compute_dtype=compute_dtype)
    leaves.update(state=state, conv_tail=new_tail.astype(tail.dtype))
    return y


def mixer_step(p, h, leaves, pos, *, cfg, compute_dtype, kernel=False,
               layer=None):
    """The rule's step form: the linear mixer for one token a slot, h (B, 1,
    C), `leaves` as `mixer_chunk`'s -> y (B, 1, C). Plain
    `jax.numpy` (S1: a step kernel enters as `RULE.kernel`'s second form)."""
    del pos, kernel, layer
    m = cfg.kda
    state, tail = leaves["state"], leaves["conv_tail"]
    with jax.named_scope("kda.project"):
        pre, g, beta, gate = _project(p, h, m=m, compute_dtype=compute_dtype)
        conved, rows = state_kind.conv_step(tail, pre, lambda: _taps(p))
        q, k, v = _qkv_heads(conved, m)
    with jax.named_scope("kda.step"):
        o, state = step_rule(q[:, :, 0], k[:, :, 0], v[:, :, 0], g[:, 0],
                             beta[:, 0], state)
    with jax.named_scope("kda.out"):
        y = _out(p, o[:, :, None], gate, h.dtype, m=m, eps=cfg.rms_eps,
                 compute_dtype=compute_dtype)
    leaves.update(state=state, conv_tail=rows[:, 1:].astype(tail.dtype))
    return y


def _init_block(blk, key, cfg, dtype):
    blk["attn"] = init_mixer(jax.random.fold_in(key, 19), cfg, dtype)


RULE = state_kind.Rule(
    field="kda", kind="linear", params="attn", slot_leaves=slot_leaves,
    init=_init_block, chunk=mixer_chunk, step=mixer_step, kernel="chunk",
    # ops/pallas/delta_rule.py: a head's channels the 128 lanes, a chunk of
    # 64 positions in four sub-blocks of 16
    fits=lambda cfg: cfg.kda.head_dim % 128 == 0 and cfg.kda.chunk == 64)
