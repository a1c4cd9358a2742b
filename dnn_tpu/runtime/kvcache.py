"""KV-cache storage codecs: float and int8.

Decode at long context is bounded by CACHE reads, not weights: every step
streams the whole (L, B, H, S, D) K/V history from HBM for one token of
compute. Weight-only quantization (dnn_tpu/quant.py) halves/quarters the
weight bytes; this module does the same for the cache — the other half of
the decode-bandwidth story.

Scheme, mirroring quant.py's weight recipe:

  * **Symmetric per-(position, head) int8.** Each cached K/V row (the D
    head-dim vector written at one position) gets one f32 scale:
    ``scale = max|row| / 127``. Rows are the natural grain: each is
    written once at its own decode step (so quantization is a cheap local
    epilogue on the new row, never a re-pass over the cache) and scales
    broadcast along D.
  * **Scales commute with both attention einsums.** Scores:
    ``q @ (k_q * ks)^T == (q @ k_q^T) * ks`` — dequant lands on the
    (T, S) score matrix, not a materialized float cache copy. Values:
    ``p @ (v_q * vs) == (p * vs) @ v_q`` — fold the scale into the
    (small) probability matrix before the contraction. The int8 cache is
    read at 1 byte/element; nothing float-sized is ever rebuilt.
  * Numerics: probabilities and accumulation stay f32 (same as the float
    path); the only new error is the per-row int8 rounding of K/V, which
    the parity test bounds (cosine > 0.999, token-parity on real decodes).

A codec is three functions over a cache pytree whose every leaf carries a
leading L axis: `init`; `write`, which is handed the WHOLE cache bound to
one layer (`paged_kvcache.LayerRows`: the layer loop carries the cache and
a block writes its T positions in place, `paged_kvcache.scan_rows`); and
`attend` over one layer's leaves. `generate.forward_with_cache` threads
whichever codec matches its cache, so the same decode loop serves f32,
bf16, and int8 caches.

**Sliding windows** (Mistral-class models) come in two forms:

  * `window=` on the standard codecs adds a LOWER-bound mask — key
    positions <= limit - window are dropped — over an ordinary
    full-length cache. Storage is unchanged; every runtime (batcher,
    pipeline stages, chunked prefill) gets window semantics for free.
  * `RollingFloatKV` / `RollingInt8KV` store only `window` positions as
    a ring buffer (write at ``pos % window``): the solo decode loop's
    memory win — cache bytes are O(window) however long the stream runs.
    Ring slot j holds absolute position ``a_j = p - ((p - j) % W)`` at
    step p; masking ``a_j >= 0`` is exactly "written and in-window", so
    the two forms are attention-equivalent (pinned in
    tests/test_sliding_window.py).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

_NEG_BIG = -1e30

__all__ = ["FloatKV", "Int8KV", "Int4KV", "RollingFloatKV", "RollingInt8KV",
           "band_keep", "codec_for_cache", "AUTO_KERNEL_MIN_S"]

# `use_kernel="auto"` threshold: below this many cache positions the XLA
# einsum path is preferred: it reads a short allocation whole at little
# cost, and the bucketed decode path — runtime/decode_buckets.py — keeps
# the allocation tracking the live length anyway. At or above it, a
# decode step against a LONG preallocated cache routes through the
# position-clamped kernel (ops/pallas/cached_attention.decode_attention),
# whose index-map clamp makes bytes/step proportional to the live
# position instead of the allocation. A heuristic: no cell of the chip
# benchmark runs a dense cache (PERF.md section 7), so the threshold is
# not measured.
AUTO_KERNEL_MIN_S = 1024


def band_keep(cols, limit, window):
    """THE sliding-window band predicate: causal upper bound
    (cols <= limit) plus the optional lower bound
    (cols > limit - window). Every codec that band-masks — the dense
    codecs via _KernelDispatch._band_keep AND the paged pool
    (runtime/paged_kvcache.PagedKV) — goes through here, so the
    boundary semantics can never diverge between them. Broadcasts over
    whatever shapes the caller aligned; `window` may be traced."""
    keep = cols <= limit
    if window is not None:
        keep &= cols > limit - window
    return keep


class _KernelDispatch:
    """Shared use_kernel plumbing: True engages the Pallas path with its
    own TPU/tiling dispatch; the string "auto" engages it ONLY on a TPU
    backend AND only against caches of at least AUTO_KERNEL_MIN_S
    positions (the length-aware policy: long-context decode streams
    through the position-clamped kernel, everything else stays on the
    einsum / bucketed-XLA path); the string "interpret" forces the kernel
    in Pallas interpreter mode (CPU CI runs the REAL kernel logic inside
    the full decode loop instead of silently falling back to the einsum).

    Also hosts THE window predicate: every attend variant of every codec
    masks through `_band_keep` / `_rows_keep`, so the sliding-window
    band-edge semantics live in exactly one place. Each attend variant
    additionally accepts a per-call `window=` override — a TRACED scalar
    is allowed, which is how per-LAYER windows (Gemma-2's alternating
    local/global attention) ride one scanned block body: the caller
    threads a (L,) window array through its layer scan and passes each
    layer's value here (a "no window" layer passes cfg.block_size, which
    makes the lower bound vacuous). A traced override disables the Pallas
    kernel path for that call (the kernel masks causally only).

    `softcap` (Gemma-2 attn_logit_softcapping) bounds scores to
    (-cap, cap) via cap*tanh(s/cap) BEFORE masking — also einsum-only."""

    use_kernel = False
    window: Optional[int] = None
    softcap: Optional[float] = None

    def _interp(self):
        return True if self.use_kernel == "interpret" else None

    def _kernel_on(self, c) -> bool:
        """Resolve the use_kernel mode against a concrete cache: True/
        "interpret" are unconditional, "auto" is the length-aware policy
        (TPU backend AND cache length >= AUTO_KERNEL_MIN_S — see the
        class docstring). Tiling/window/softcap guards stay with each
        attend variant's call site."""
        if self.use_kernel == "auto":
            return (jax.default_backend() == "tpu"
                    and c["k"].shape[2] >= AUTO_KERNEL_MIN_S)
        return bool(self.use_kernel)

    def write_attend_rows(self, q, c, k, v, pos, write_gate, window=None):
        """One decode step against this cache: write the step's k/v rows
        at `pos`, then attend through them -> (y, c). The block's one
        call into its codec; a codec whose write and attend are ONE
        device operation (PagedKV's kernel over the whole pool)
        overrides it."""
        c = self.write_rows(c, k, v, pos, write_gate)
        return self.attend_rows(q, c, pos, window=window), c

    def _cap(self, s):
        """Apply attention-logit softcapping (identity when unset)."""
        if self.softcap is not None:
            s = self.softcap * jnp.tanh(s / self.softcap)
        return s

    def _band_keep(self, cols, limit, window=None):
        """The shared band predicate (module-level band_keep) with the
        codec's static window as the default; `window` overrides it
        (may be traced — see class docstring)."""
        return band_keep(cols, limit,
                         window if window is not None else self.window)

    def _rows_keep(self, c, pos, window=None):
        """(B, 1, 1, S) keep-mask for shared-limit decode rows at per-slot
        positions pos (B,). _RingStorage overrides this with the ring
        occupancy predicate — that override is the ONLY masking
        difference between a rolling codec and its base."""
        cols = jnp.arange(c["k"].shape[2])
        return self._band_keep(cols[None, None, None, :],
                               pos[:, None, None, None], window)


def _rows_update(cache, new, pos):
    """cache (B,H,S,...) <- new (B,H,1,...) at per-row positions pos (B,)."""
    return jax.vmap(
        lambda c, n, p: lax.dynamic_update_slice_in_dim(c, n, p, axis=1)
    )(cache, new, pos)


def _rows_write(cache, new, pos, write_gate):
    """cache (B,H,S,...) <- new (B,H,T,...) at per-row positions pos (B,)
    (T=1 decode steps, T=k+1 speculative verify blocks); rows with
    write_gate False re-write their EXISTING content at pos (a bitwise
    no-op — gather and scatter share the same clamped start). The gate
    folds into the (B,H,T,...) written ROWS — one gather + one
    dynamic-update-slice per leaf — instead of the older
    full-update-then-cache-sized-select form, whose select materialized
    a second allocation-sized buffer per leaf per layer even under
    donation (the CPU-optimized decode step carried 3 cache-sized copies
    per step from exactly this; the gate-folded form lowers to a true
    in-place update — asserted by the analysis gate's decode audit,
    dnn_tpu/analysis/program.audit_serving_decode)."""
    t = new.shape[2]
    cur = jax.vmap(
        lambda c, p: lax.dynamic_slice_in_dim(c, p, t, axis=1)
    )(cache, pos)
    gate = write_gate.reshape((-1,) + (1,) * (cache.ndim - 1))
    rows = jnp.where(gate, new.astype(cache.dtype), cur)
    return _rows_update(cache, rows, pos)


class FloatKV(_KernelDispatch):
    """The plain cache: K/V stored in `dtype` (f32 default, bf16 for
    halved bandwidth).

    `use_kernel=True` routes attend/attend_rows through the Pallas
    cached-attention kernel (dnn_tpu/ops/pallas/cached_attention.py):
    online-softmax streaming of the cache with runtime position limits —
    one compiled program for every chunk start and slot position. Falls
    back to the einsum path off-TPU or when shapes don't tile.

    `window=W` adds the sliding-window lower bound: key positions
    <= limit - W are masked in every attend variant (the kernel has no
    window support, so a window forces the einsum path)."""

    def __init__(self, dtype=jnp.float32, use_kernel=False,
                 window: Optional[int] = None,
                 softcap: Optional[float] = None):
        self.dtype = dtype
        self.use_kernel = use_kernel
        self.window = window
        self.softcap = softcap

    def init(self, cfg, batch: int, max_len: int):
        shape = (cfg.n_layer, batch, cfg.n_head, max_len,
                 cfg.n_embd // cfg.n_head)
        return {"k": jnp.zeros(shape, self.dtype),
                "v": jnp.zeros(shape, self.dtype)}

    def write(self, c, k, v, start_pos):
        """k/v (B,H,T,D) at start_pos of the layer's rows of `c`: the whole
        cache {"k","v"} (L,B,H,S,D) bound to the layer
        (paged_kvcache.LayerRows) — T positions are written, in place."""
        c.write(start_pos, k=k, v=v)
        return c

    def attend(self, q, c, pos_limit, base=None, window=None):
        """q (B,H,T,D) against the full cache, masking key positions >
        their row's limit (pos_limit (T,)).

        `base` is the kernel contract marker: the caller asserts
        pos_limit == base + arange(T) by passing the start position
        (generate.py's _block_with_cache does). The kernel path engages
        ONLY with it — call sites with folded/tiled row limits (the LLaMA
        GQA group trick, llama.py) never pass base, so use_kernel can't
        silently mis-mask them; they fall through to the einsum (or, for
        T==1 folded rows, route via attend_rows' decode kernel)."""
        if (self._kernel_on(c) and base is not None and self.window is None
                and window is None and self.softcap is None):
            from dnn_tpu.ops.pallas.cached_attention import (
                cached_attention, chunk_tiles, decode_attention,
            )

            pos_b = jnp.broadcast_to(base, (q.shape[0],))
            if q.shape[2] == 1:
                # decode step: the heads-folded streaming kernel (few
                # programs, big DMAs) — the general kernel's block_q=1
                # grid runs one tiny program a (row, head, block)
                return decode_attention(
                    q, c["k"], c["v"], pos_b,
                    interpret=self._interp()).astype(c["v"].dtype)
            return cached_attention(
                q, c["k"], c["v"], pos_b, interpret=self._interp(),
                **chunk_tiles(q.shape[2], c["k"].shape[2])
            ).astype(c["v"].dtype)
        d = q.shape[-1]
        s = jnp.einsum("bhtd,bhsd->bhts", q, c["k"]).astype(jnp.float32) / jnp.sqrt(d)
        s = self._cap(s)
        cols = jnp.arange(c["k"].shape[2])
        keep = self._band_keep(cols[None, None, None, :],
                               pos_limit[None, None, :, None], window)
        s = jnp.where(keep, s, _NEG_BIG)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhts,bhsd->bhtd", p.astype(c["v"].dtype), c["v"])

    # --- per-row variants (continuous batching: each slot at its own
    # position; `write_gate` (B,) bool keeps inactive slots untouched) ---

    def write_rows(self, c, k, v, pos, write_gate):
        return {"k": _rows_write(c["k"], k, pos, write_gate),
                "v": _rows_write(c["v"], v, pos, write_gate)}

    def attend_rows_causal(self, q, c, pos, window=None):
        """q (B, H, T, D) VERIFY blocks: row t of slot b attends cache
        columns <= pos[b] + t (per-row positions AND within-block
        causality — the speculative verify chunk's masking, which neither
        attend (shared batch limits) nor attend_rows (shared row limit)
        expresses). Op-and-dtype recipe mirrors attend_rows exactly —
        score einsum in the operand dtype, f32 softmax, probs cast to the
        cache dtype — so a greedy verify reproduces the step-by-step
        decode's argmax even under bf16 compute (the spec batcher's
        token-identity contract)."""
        d = q.shape[-1]
        s = jnp.einsum("bhtd,bhsd->bhts", q, c["k"]).astype(jnp.float32) \
            / jnp.sqrt(d)
        s = self._cap(s)
        cols = jnp.arange(c["k"].shape[2])
        rows = jnp.arange(q.shape[2])
        limit = pos[:, None, None, None] + rows[None, None, :, None]
        keep = self._band_keep(cols[None, None, None, :], limit, window)
        s = jnp.where(keep, s, _NEG_BIG)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhts,bhsd->bhtd", p.astype(c["v"].dtype),
                          c["v"])

    def attend_rows(self, q, c, pos, window=None):
        """q (B, H, R, D); every row of slot b masked to keys at positions
        <= pos[b]. R=1 is plain per-slot decode; R=G is the LLaMA GQA fold
        (all group rows share their slot's limit — llama.LlamaFamilyRows)."""
        if (self._kernel_on(c) and self.window is None and window is None
                and self.softcap is None):
            from dnn_tpu.ops.pallas.cached_attention import decode_attention

            return decode_attention(q, c["k"], c["v"], pos,
                                    interpret=self._interp()) \
                .astype(c["v"].dtype)
        d = q.shape[-1]
        s = jnp.einsum("bhtd,bhsd->bhts", q, c["k"]).astype(jnp.float32) / jnp.sqrt(d)
        s = self._cap(s)
        s = jnp.where(self._rows_keep(c, pos, window), s, _NEG_BIG)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhts,bhsd->bhtd", p.astype(c["v"].dtype), c["v"])


def _quantize_rows(x):
    """x (..., D) -> (int8 (..., D), f32 scales (...,)) — symmetric
    per-row, the cache analog of quant.quantize_tensor."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]), -127, 127)
    return q.astype(jnp.int8), scale


def _quantize_rows_int4(x):
    """x (..., D) -> (int4 (..., D), f32 scales (...,)) — symmetric
    per-row at 7 levels. The scale grain is the same per-(position, head)
    row as the int8 codec's (each row is quantized once, at its own
    write, against its own max — the "per-bucket" scales of the cache
    recipe: one scale per D-wide bucket), which is what keeps 4-bit
    rounding bounded: a whole-tensor scale at 7 levels would be
    useless, a per-row one is the cache analog of quant.py's int4
    GROUP scheme (quantize_tensor_int4)."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = jnp.where(amax > 0, amax / 7.0, 1.0)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]), -7, 7)
    return q.astype(jnp.int4), scale


class Int8KV(_KernelDispatch):
    """int8 K/V with per-(position, head) f32 scales — 4x less cache
    bandwidth per decode step than f32, 2x less than bf16.

    `use_kernel=True`: the Pallas cached-attention kernel streams the
    int8 bytes straight from HBM and folds the scales inside VMEM — the
    1-byte read becomes a guarantee instead of an XLA fusion hope (see
    dnn_tpu/ops/pallas/cached_attention.py).

    `window=W`: sliding-window lower bound, exactly as FloatKV's."""

    # the quantization recipe, overridden by Int4KV (same layout, 4-bit
    # payload); every write funnels through _quant so the two codecs
    # cannot drift
    _qdtype = jnp.int8
    _quant = staticmethod(_quantize_rows)

    def __init__(self, use_kernel=False,
                 window: Optional[int] = None,
                 softcap: Optional[float] = None):
        self.use_kernel = use_kernel
        self.window = window
        self.softcap = softcap

    def init(self, cfg, batch: int, max_len: int):
        shape = (cfg.n_layer, batch, cfg.n_head, max_len,
                 cfg.n_embd // cfg.n_head)
        return {
            "k": jnp.zeros(shape, self._qdtype),
            "v": jnp.zeros(shape, self._qdtype),
            "ks": jnp.ones(shape[:-1], jnp.float32),
            "vs": jnp.ones(shape[:-1], jnp.float32),
        }

    def write(self, c, k, v, start_pos):
        kq, ks = self._quant(k)
        vq, vs = self._quant(v)
        c.write(start_pos, k=kq, v=vq, ks=ks, vs=vs)
        return c

    def attend(self, q, c, pos_limit, base=None, window=None):
        # `base` marks the pos_limit == base + arange(T) contract (see
        # FloatKV.attend) — kernel path only with it
        if (self._kernel_on(c) and base is not None and self.window is None
                and window is None and self.softcap is None):
            from dnn_tpu.ops.pallas.cached_attention import (
                cached_attention, chunk_tiles, decode_attention,
            )

            pos_b = jnp.broadcast_to(base, (q.shape[0],))
            if q.shape[2] == 1:  # decode step: streaming kernel
                return decode_attention(
                    q, c["k"], c["v"], pos_b, ks=c["ks"], vs=c["vs"],
                    interpret=self._interp())
            return cached_attention(
                q, c["k"], c["v"], pos_b,
                ks=c["ks"], vs=c["vs"], interpret=self._interp(),
                **chunk_tiles(q.shape[2], c["k"].shape[2]))
        d = q.shape[-1]
        # scores in f32; the per-position K scale lands on the score matrix
        # (commutes with the D contraction)
        s = jnp.einsum("bhtd,bhsd->bhts", q.astype(jnp.float32),
                       c["k"].astype(jnp.float32),
                       preferred_element_type=jnp.float32)
        s = s * c["ks"][:, :, None, :] / jnp.sqrt(d)
        s = self._cap(s)
        cols = jnp.arange(c["k"].shape[2])
        keep = self._band_keep(cols[None, None, None, :],
                               pos_limit[None, None, :, None], window)
        s = jnp.where(keep, s, _NEG_BIG)
        p = jax.nn.softmax(s, axis=-1)
        # fold the V scale into the (small) probability matrix, then
        # contract against the raw int8 values
        p = p * c["vs"][:, :, None, :]
        return jnp.einsum("bhts,bhsd->bhtd", p, c["v"].astype(jnp.float32),
                          preferred_element_type=jnp.float32)

    # --- per-row variants (continuous batching) ---

    def write_rows(self, c, k, v, pos, write_gate):
        kq, ks = self._quant(k)   # (B,H,1,D), (B,H,1)
        vq, vs = self._quant(v)
        return {
            "k": _rows_write(c["k"], kq, pos, write_gate),
            "v": _rows_write(c["v"], vq, pos, write_gate),
            "ks": _rows_write(c["ks"], ks, pos, write_gate),
            "vs": _rows_write(c["vs"], vs, pos, write_gate),
        }

    def attend_rows_causal(self, q, c, pos, window=None):
        # per-row causal verify blocks (see FloatKV.attend_rows_causal);
        # scales fold exactly as in attend_rows' recipe
        d = q.shape[-1]
        s = jnp.einsum("bhtd,bhsd->bhts", q.astype(jnp.float32),
                       c["k"].astype(jnp.float32),
                       preferred_element_type=jnp.float32)
        s = s * c["ks"][:, :, None, :] / jnp.sqrt(d)
        s = self._cap(s)
        cols = jnp.arange(c["k"].shape[2])
        rows = jnp.arange(q.shape[2])
        limit = pos[:, None, None, None] + rows[None, None, :, None]
        keep = self._band_keep(cols[None, None, None, :], limit, window)
        s = jnp.where(keep, s, _NEG_BIG)
        p = jax.nn.softmax(s, axis=-1)
        p = p * c["vs"][:, :, None, :]
        return jnp.einsum("bhts,bhsd->bhtd", p,
                          c["v"].astype(jnp.float32),
                          preferred_element_type=jnp.float32)

    def attend_rows(self, q, c, pos, window=None):
        # shared-limit decode rows, any R (see FloatKV.attend_rows)
        if (self._kernel_on(c) and self.window is None and window is None
                and self.softcap is None):
            from dnn_tpu.ops.pallas.cached_attention import decode_attention

            return decode_attention(q, c["k"], c["v"], pos,
                                    ks=c["ks"], vs=c["vs"],
                                    interpret=self._interp())
        d = q.shape[-1]
        s = jnp.einsum("bhtd,bhsd->bhts", q.astype(jnp.float32),
                       c["k"].astype(jnp.float32),
                       preferred_element_type=jnp.float32)
        s = s * c["ks"][:, :, None, :] / jnp.sqrt(d)
        s = self._cap(s)
        s = jnp.where(self._rows_keep(c, pos, window), s, _NEG_BIG)
        p = jax.nn.softmax(s, axis=-1)
        p = p * c["vs"][:, :, None, :]
        return jnp.einsum("bhts,bhsd->bhtd", p, c["v"].astype(jnp.float32),
                          preferred_element_type=jnp.float32)


class Int4KV(Int8KV):
    """int4 K/V with per-(position, head) f32 scales — 8x less cache
    payload bandwidth per decode step than f32, 2x less than int8.
    Storage is NATIVE jnp.int4 (XLA S4: two values per byte in the HBM
    layout, the same packing quant.py's int4 weights ride).

    Same layout and attend math as Int8KV — only the quantizer (7
    levels, per-row scales) differs, so every attend variant (scores
    scaled on the (T, S) matrix, V scales folded into the probability
    matrix) is inherited verbatim. Einsum-only: the Pallas cached-
    attention kernel streams 1-byte elements; sub-byte VMEM loads are
    not wired, so the kernel path stays off whatever `use_kernel` says
    (the s4->f32 upcast fuses into the XLA dot instead). Accuracy: the
    parity tests bound per-row int4 rounding (cosine > 0.99 on real
    decode shapes); prefer int8 when the quality budget is tight —
    int4 is the bandwidth-endpoint rung of the serving-spec ladder
    (kv_dtype="int4", composable with the bucket ladder and the paged
    pool like every other cache dtype)."""

    _qdtype = jnp.int4
    _quant = staticmethod(_quantize_rows_int4)

    def __init__(self, window: Optional[int] = None,
                 softcap: Optional[float] = None):
        super().__init__(use_kernel=False, window=window, softcap=softcap)

    def _kernel_on(self, c) -> bool:
        return False  # no sub-byte kernel path (see class docstring)


def ring_positions(pos, w: int):
    """Absolute position held by ring slot j at stream position `pos`:
    ``a_j = pos - ((pos - j) % w)`` — the latest position congruent to j
    that is <= pos. Negative means "slot not yet written". Broadcasts
    over pos's shape, appending a (w,) axis. The SINGLE source of truth
    for ring occupancy: both Rolling codecs' masks and the prompt->ring
    gather (llama._ring_from_prompt) derive from it."""
    pos = jnp.asarray(pos)
    j = jnp.arange(w)
    return pos[..., None] - jnp.mod(pos[..., None] - j, w)


class _RingStorage:
    """Shared rolling-ring discipline, mixed over a base codec: only
    `window` positions are stored, a write lands at ``pos % window``, and
    attends mask ring slot j by ``ring_positions(pos, W) >= 0`` —
    "written and inside the live band" in one predicate (keys are stored
    already rotated at their absolute positions, so relative RoPE
    geometry is untouched by the wrap).

    Decode-oriented: multi-row attends (prefill chunks, speculative
    verify blocks) belong on a full-length cache with `window=` masking —
    prefill there, then gather the live band into the ring
    (llama.make_generate's rolling path does exactly this). A multi-row
    ring attend would let early query rows see slots their own future
    already overwrote, so it is rejected rather than mis-masked."""

    def init(self, cfg, batch: int, max_len: int):
        # `max_len` is the stream bound; storage is the window
        del max_len
        return super().init(cfg, batch, self.window)

    def attend(self, q, c, pos_limit, base=None, window=None):
        if q.shape[2] != 1:
            raise ValueError(
                "rolling cache attends single decode rows only — prefill "
                "on a full-length cache with window= masking, then gather "
                "the live band (llama.make_generate's rolling path)")
        del base, window
        return self.attend_rows(
            q, c, jnp.broadcast_to(pos_limit[0], (q.shape[0],)))

    def write_rows(self, c, k, v, pos, write_gate):
        w = c["k"].shape[2]
        return super().write_rows(c, k, v, jnp.mod(pos, w), write_gate)

    def attend_rows_causal(self, q, c, pos, window=None):
        raise ValueError(
            "speculative verify blocks need a full-length cache — rolling "
            "storage cannot express per-row history beyond the ring")

    def _rows_keep(self, c, pos, window=None):
        """Ring occupancy replaces the band mask — the one masking
        difference vs the base codec (see _KernelDispatch._rows_keep).
        A per-call window override makes no sense on a ring (storage IS
        the window) and is ignored."""
        del window
        return (ring_positions(pos, c["k"].shape[2]) >= 0)[:, None, None, :]

    @staticmethod
    def _ring_scatter(c, new, start_pos):
        """Write rows at absolute positions [start_pos, start_pos+t) into
        their ring slots of the layer's rows of `c` (a LayerRows); only
        the last min(t, w) rows survive the wrap, and their slots are
        distinct — a plain scatter into the layer's ring."""
        t = next(iter(new.values())).shape[2]
        w = c.leaves["k"].shape[3]
        if t == 1:
            c.write(jnp.mod(start_pos, w), **new)
            return c
        m = min(t, w)
        slots = jnp.mod(start_pos + jnp.arange(t - m, t), w)
        c.update(**{kk: c[kk].at[:, :, slots].set(
            rows[:, :, t - m:].astype(c.leaves[kk].dtype))
            for kk, rows in new.items()})
        return c


class RollingFloatKV(_RingStorage, FloatKV):
    """Ring-buffer float cache for sliding-window decode (see
    _RingStorage for the storage discipline and contract)."""

    def __init__(self, dtype=jnp.float32, window: Optional[int] = None):
        if window is None or window < 1:
            raise ValueError(
                f"rolling cache needs a positive window, got {window}")
        super().__init__(dtype, use_kernel=False, window=window)

    def write(self, c, k, v, start_pos):
        return self._ring_scatter(c, {"k": k, "v": v}, start_pos)
    # attend_rows: FloatKV's einsum with _RingStorage._rows_keep


class RollingInt8KV(_RingStorage, Int8KV):
    """Ring-buffer int8 cache: _RingStorage's discipline with Int8KV's
    per-row scales."""

    def __init__(self, window: Optional[int] = None):
        if window is None or window < 1:
            raise ValueError(
                f"rolling cache needs a positive window, got {window}")
        super().__init__(use_kernel=False, window=window)

    def write(self, c, k, v, start_pos):
        kq, ks = self._quant(k)
        vq, vs = self._quant(v)
        return self._ring_scatter(
            c, {"k": kq, "v": vq, "ks": ks, "vs": vs}, start_pos)
    # attend_rows: Int8KV's scaled einsum with _RingStorage._rows_keep


def codec_for_cache(cache, use_kernel=False,
                    window: Optional[int] = None, rolling: bool = False,
                    softcap: Optional[float] = None):
    """Infer the codec from a cache pytree's structure (int8 caches carry
    scale leaves). `use_kernel` opts attend/attend_rows into the Pallas
    cached-attention kernel (TPU; einsum fallback elsewhere): False/True
    as before, "auto" = the length-aware policy (kernel only on TPU
    against caches >= AUTO_KERNEL_MIN_S positions — long-context decode
    streams through the position-clamped kernel, short caches stay on
    the einsum), "interpret" = kernel in Pallas interpreter mode. `window`
    adds the sliding-window lower bound; `rolling=True` additionally
    treats the cache as a `window`-length ring buffer (rolling cannot be
    inferred from structure — a ring leaf looks like a short cache).
    `softcap` is Gemma-2's attention-logit softcapping (einsum paths
    only; no rolling support — Gemma-2 alternates local/global layers,
    so its decode never rolls)."""
    if rolling:
        if softcap is not None:
            raise ValueError("softcap is not supported on rolling caches")
        if "ks" in cache:
            if cache["k"].dtype == jnp.int4:
                raise ValueError(
                    "rolling int4 caches are not built — roll at int8 "
                    "(RollingInt8KV) or keep int4 on a full-length cache")
            return RollingInt8KV(window=window)
        return RollingFloatKV(cache["k"].dtype, window=window)
    if "ks" in cache:
        if cache["k"].dtype == jnp.int4:
            return Int4KV(window=window, softcap=softcap)
        return Int8KV(use_kernel=use_kernel, window=window, softcap=softcap)
    return FloatKV(cache["k"].dtype, use_kernel=use_kernel, window=window,
                   softcap=softcap)
