"""Paged KV cache: a shared block pool + per-slot block tables.

The dense codecs (dnn_tpu/runtime/kvcache.py) reserve `max_len` cache
positions per slot — a pool of S slots costs S x max_len positions of HBM
whether requests use them or not. This module stores K/V in fixed-size
POSITION BLOCKS drawn from one shared pool, with each slot holding a
small int32 table mapping its logical block index -> physical pool block
(the vLLM design, rebuilt TPU-style: the pool and tables are plain
static-shaped arrays, block lookup is a gather, block write is a scatter
— no dynamic shapes anywhere, so the serving runtime keeps its
fixed-program-count compile story).

What this buys a serving pool (tests/test_paged.py measures both):
  * admission by ACTUAL length — a pool sized for 2 full-length requests
    admits 4+ short ones concurrently (sum of ceil(len/bp) blocks, not
    slots x max_len);
  * allocation/free at block granularity per request lifetime, host-side
    (a free-list of ints — no device work to retire a request).

Layout (per K and per V, mirroring the dense cache's (L, B, H, S, D)):

    pool   (L, n_blocks, H, block_len, D)
    tables (L, B, max_blocks)  int32   -- replicated over L so the decode
                                          scan over layers peels tables
                                          alongside the pool leaves
    pos    (B,)                        -- slot lengths, as in dense

The codec interface matches FloatKV (write_rows / attend_rows /
install_row), so GPTFamilyRows / LlamaFamilyRows decode through it
unchanged. Attention gathers the slot's blocks into a (B, H, S_max, D)
view and runs the identical masked einsum — the reference math is the
dense codec's, so token parity is exact. (A Pallas paged-attention kernel
would instead feed the table through the scalar-prefetch index map of
ops/pallas/cached_attention._decode_call, reading blocks straight from
the pool; the einsum path is the correctness baseline.)

No counterpart exists in the reference framework (its only state is a
per-request activation, /root/reference/node.py:45-105 — no cache at
all); this is part of the modern-serving surface built on top of parity.
"""

from __future__ import annotations

from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from dnn_tpu.runtime.kvcache import band_keep

_NEG_BIG = -1e30

__all__ = ["PagedKV", "BlockAllocator", "InsufficientBlocks",
           "init_paged_cache"]


class InsufficientBlocks(RuntimeError):
    """The pool cannot currently satisfy an admission — a TRANSIENT
    condition (blocks free as running requests retire), distinct from the
    permanent no-free-slot/never-fits errors: queueing fronts (the LM
    daemon worker) catch this and hold the request back instead of
    failing it."""


class BlockAllocator:
    """Host-side free-list over pool block ids. Block 0 is RESERVED as the
    junk target: 0-initialized / unowned table entries point at it, so
    install scribbles and inactive-slot decode writes land there instead
    of aliasing a live block; its content is never attended (the per-row
    position mask stops at each slot's length)."""

    def __init__(self, n_blocks: int):
        if n_blocks < 2:
            raise ValueError("need at least 2 blocks (block 0 is reserved)")
        self.n_blocks = n_blocks
        self._free: List[int] = list(range(1, n_blocks))
        # block id -> reference count. SHARING (paged prefix cache): a
        # block may be held by several slots plus a prefix-cache entry at
        # once; it returns to the free list when the last holder lets go.
        self._rc = {}
        # memory observability (dnn_tpu/obs/mem.py): the pool's
        # high-water mark — max blocks ever simultaneously in use. "How
        # close did the pool come to full" is the capacity-planning
        # number a used-right-now gauge cannot answer after the burst
        # has passed; the serving layer exports all three as gauges.
        self.high_water = 0

    @property
    def n_free(self) -> int:
        return len(self._free)

    @property
    def n_used(self) -> int:
        """Blocks currently held (block 0's permanent reservation is not
        "use"); n_used + n_free == n_blocks - 1 always."""
        return self.n_blocks - 1 - len(self._free)

    def alloc(self, n: int) -> Optional[List[int]]:
        """n fresh block ids (each at refcount 1), or None if the pool
        can't satisfy the request (caller decides whether to queue or
        reject)."""
        if n > len(self._free):
            return None
        taken, self._free = self._free[:n], self._free[n:]
        for b in taken:
            self._rc[b] = 1
        if self.n_used > self.high_water:
            self.high_water = self.n_used
        return taken

    def ref(self, blocks: List[int]):
        """Take an additional reference on live blocks (prefix sharing).
        Validates the WHOLE list before mutating: a bad id mid-list must
        not leave earlier refcounts raised (callers treat ref/free as
        atomic when unwinding)."""
        for b in blocks:
            if self._rc.get(b, 0) < 1:
                raise ValueError(f"ref on non-live block {b}")
        for b in blocks:
            self._rc[b] += 1

    def free(self, blocks: List[int]):
        """Release one reference per listed occurrence; blocks whose last
        reference drops return to the free list. Validates the WHOLE list
        (including duplicate occurrences against the refcount) before
        mutating, so a bad id can never leave the allocator half-freed —
        callers unwind by re-freeing lists and must not double-decrement."""
        from collections import Counter

        counts = Counter(blocks)
        for b, n in counts.items():
            if b == 0 or b >= self.n_blocks or self._rc.get(b, 0) < n:
                raise ValueError(f"free of non-live block {b}")
        for b, n in counts.items():
            rc = self._rc[b] - n
            if rc == 0:
                del self._rc[b]
                self._free.append(b)
            else:
                self._rc[b] = rc


def init_paged_cache(cfg, slots: int, max_len: int, *, n_blocks: int,
                     block_len: int = 16, dtype=jnp.float32,
                     kv_heads: Optional[int] = None):
    """Pool + tables pytree for `slots` decode rows of up to `max_len`
    positions each, sharing `n_blocks` physical blocks of `block_len`
    positions. The pytree rides the same lax.scan-over-layers as the
    dense cache (leading L on every leaf). `kv_heads` overrides the
    pool's head width — GQA families store KV heads, not query heads
    (llama.init_cache's narrowing, here applied to the pool).
    dtype="int8" / "int4" build the quantized pools: int8/int4 K/V
    blocks plus per-(position, head) f32 scale blocks, the paged forms
    of kvcache.Int8KV / Int4KV's layouts (int4 stores native jnp.int4,
    two values per byte)."""
    if max_len % block_len:
        raise ValueError(f"max_len {max_len} must tile block_len {block_len}")
    head_dim = cfg.n_embd // cfg.n_head
    heads = kv_heads if kv_heads is not None else cfg.n_head
    nb_max = max_len // block_len
    shape = (cfg.n_layer, n_blocks, heads, block_len, head_dim)
    tables = jnp.zeros((cfg.n_layer, slots, nb_max), jnp.int32)
    if dtype in ("int8", "int4"):
        qdt = jnp.int8 if dtype == "int8" else jnp.int4
        return {
            "k": jnp.zeros(shape, qdt),
            "v": jnp.zeros(shape, qdt),
            "ks": jnp.ones(shape[:-1], jnp.float32),
            "vs": jnp.ones(shape[:-1], jnp.float32),
            "tables": tables,
        }
    return {
        "k": jnp.zeros(shape, dtype),
        "v": jnp.zeros(shape, dtype),
        "tables": tables,
    }


class PagedKV:
    """Codec over the shared block pool (see module docstring).

    Same call surface the batcher's decode/install paths use on the
    dense codecs (kvcache.FloatKV).

    `window=W` (Mistral-class sliding windows) adds the band's lower
    bound to attend_rows — positions <= pos - W never attend — and is
    what lets the SERVING layer reclaim fully-rolled-out blocks while a
    request still runs (ContinuousBatcher._free_rolled_blocks): a long
    windowed stream holds O(window) pool blocks, not O(stream).

    `use_kernel` routes attend_rows through the fused paged flash-decode
    kernel (ops/pallas/cached_attention.paged_decode_attention): the
    slot's block table rides scalar prefetch and each grid step DMAs its
    PHYSICAL block straight from the pool — no gather_view
    materialization, per-step traffic clamped at each slot's live
    length. True/"interpret" are unconditional; "auto" engages it only
    on TPU against pools whose per-slot logical length reaches
    kvcache.AUTO_KERNEL_MIN_S (the dense codecs' length-aware policy).
    Windowed pools and int4 pools stay on the einsum (the kernel masks
    causally only / sub-byte VMEM loads are not wired)."""

    def __init__(self, block_len: int, window: Optional[int] = None,
                 use_kernel=False):
        self.block_len = block_len
        self.window = window
        self.use_kernel = use_kernel

    def _kernel_on(self, c) -> bool:
        """Resolve use_kernel against a concrete per-layer pool view
        (pool (n_blocks, H, bp, D), tables (B, nb_max)) — the paged
        mirror of kvcache._KernelDispatch._kernel_on."""
        if self.window is not None or c["k"].dtype == jnp.int4:
            return False
        if self.use_kernel == "auto":
            from dnn_tpu.runtime.kvcache import AUTO_KERNEL_MIN_S

            logical = c["tables"].shape[-1] * self.block_len
            return (jax.default_backend() == "tpu"
                    and logical >= AUTO_KERNEL_MIN_S)
        return bool(self.use_kernel)

    # --- decode-row paths (per-layer views: pool (n_blocks, H, bp, D),
    #     tables (B, nb_max)) ------------------------------------------

    # write_rows / gather_view / install_row carry `kv_pool.*` scopes and
    # the attention after them `attn.paged_decode`: the names a device
    # trace files this codec's operations under (chipbench/spans.py).

    @jax.named_scope("kv_pool.write")
    def write_rows(self, c, k, v, pos, write_gate):
        """k/v (B, H, 1, D) at per-slot positions pos (B,); write_gate (B,)
        keeps inactive slots' LIVE state untouched. Physical target: block
        tables[b, pos//bp], row pos%bp — one scatter per leaf. An int8
        pool quantizes the incoming rows first (kvcache._quantize_rows)
        and scatters the per-(position, head) scales alongside.

        Gated-off slots are ROUTED TO the reserved junk block (0, row 0)
        rather than restored-in-place: a retired slot's stale table can
        point at a block since REALLOCATED to another request, and a
        duplicate scatter index (stale restore vs the new owner's write)
        has unspecified winner — the restore could resurrect the old
        request's K/V inside the new one's cache. Junk-block collisions
        between gated slots are harmless (block 0 is never owned, never
        attended live)."""
        bp = self.block_len
        blk = jnp.take_along_axis(
            c["tables"], (pos // bp)[:, None], axis=1)[:, 0]  # (B,)
        row = pos % bp
        blk = jnp.where(write_gate, blk, 0)
        row = jnp.where(write_gate, row, 0)
        out = {"tables": c["tables"]}
        if "ks" in c:
            from dnn_tpu.runtime.kvcache import (
                _quantize_rows,
                _quantize_rows_int4,
            )

            quantize = (_quantize_rows_int4 if c["k"].dtype == jnp.int4
                        else _quantize_rows)
            kq, ks = quantize(k)  # (B,H,1,D), (B,H,1)
            vq, vs = quantize(v)
            out["k"] = c["k"].at[blk, :, row].set(kq[:, :, 0])
            out["v"] = c["v"].at[blk, :, row].set(vq[:, :, 0])
            out["ks"] = c["ks"].at[blk, :, row].set(ks[:, :, 0])
            out["vs"] = c["vs"].at[blk, :, row].set(vs[:, :, 0])
            return out
        out["k"] = c["k"].at[blk, :, row].set(k[:, :, 0].astype(c["k"].dtype))
        out["v"] = c["v"].at[blk, :, row].set(v[:, :, 0].astype(c["v"].dtype))
        return out

    @jax.named_scope("kv_pool.gather")
    def gather_view(self, c, names=("k", "v")):
        """Dense (B, H, S_max, ...) views of every slot's logical cache —
        the einsum attention baseline (a paged Pallas kernel would skip
        this materialization). Handles K/V blocks (…, bp, D) and scale
        blocks (…, bp) alike."""
        tables = c["tables"]  # (B, nb_max)
        b, nb = tables.shape
        out = []
        for name in names:
            leaf = c[name]
            g = jnp.take(leaf, tables.reshape(-1), axis=0)  # (B*nb, H, bp[, D])
            h, bp = g.shape[1], g.shape[2]
            rest = g.shape[3:]
            g = g.reshape(b, nb, h, bp, *rest)
            g = jnp.moveaxis(g, 1, 2)  # (B, H, nb, bp[, D])
            out.append(g.reshape(b, h, nb * bp, *rest))
        return out

    def attend_rows(self, q, c, pos, window=None):
        """q (B, H, R, D); every row of slot b attends logical positions
        <= pos[b] (identical math to kvcache.FloatKV/Int8KV.attend_rows
        on the gathered view — int8 pools fold their per-position scales
        onto the score/probability matrices, never a float cache copy),
        band-limited by the codec's `window` when set. A per-call
        `window` override is the dense codecs' per-LAYER channel
        (alt-window configs) — those are rejected at batcher
        construction for paged pools, so an override here is a
        programming error."""
        if window is not None:
            raise ValueError(
                "PagedKV has no per-layer window channel (alt-window "
                "families are rejected for paged pools); set the codec's "
                "window at construction")
        quant = "ks" in c
        if self._kernel_on(c):
            from dnn_tpu.ops.pallas.cached_attention import (
                paged_decode_attention,
            )

            interp = True if self.use_kernel == "interpret" else None
            out = paged_decode_attention(
                q, c["k"], c["v"], c["tables"], pos,
                ks=c["ks"] if quant else None,
                vs=c["vs"] if quant else None,
                interpret=interp)
            # same output-dtype recipe as the einsum path below
            return out if quant else out.astype(c["v"].dtype)
        if quant:
            k, v, ks, vs = self.gather_view(c, ("k", "v", "ks", "vs"))
        else:
            k, v = self.gather_view(c)
        with jax.named_scope("attn.paged_decode"):
            d = q.shape[-1]
            s = jnp.einsum("bhtd,bhsd->bhts", q.astype(jnp.float32),
                           k.astype(jnp.float32),
                           preferred_element_type=jnp.float32)
            if quant:
                s = s * ks[:, :, None, :]
            s = s / jnp.sqrt(d)
            cols = jnp.arange(k.shape[2])
            mask = band_keep(cols[None, None, None, :],
                             pos[:, None, None, None], self.window)
            s = jnp.where(mask, s, _NEG_BIG)
            p = jax.nn.softmax(s, axis=-1)
            if quant:
                p = p * vs[:, :, None, :]
            out = jnp.einsum("bhts,bhsd->bhtd", p.astype(jnp.float32),
                             v.astype(jnp.float32),
                             preferred_element_type=jnp.float32)
            return out if quant else out.astype(c["v"].dtype)

    # --- prefill install (full-cache view: pool (L, n_blocks, H, bp, D),
    #     tables (L, B, nb_max)) ---------------------------------------

    @jax.named_scope("kv_pool.install")
    def install_row(self, cache, row, blk_ids):
        """Scatter a finished transient row cache (the dense chunked-
        prefill output, leaves (L, 1, H, row_len, D)) into the physical
        blocks `blk_ids` (nb_max,). ALL nb_max logical blocks install
        unconditionally (one compiled program for every prompt length):
        entries the request must not write — unowned tail AND shared
        prefix blocks (another request's live data!) — are routed to the
        reserved junk block 0, whose content is never attended live (the
        per-row position mask), so scribbling it is harmless."""
        bp = self.block_len
        out = {"tables": cache["tables"]}
        nb_max = blk_ids.shape[0]
        for kk in cache:
            if kk == "tables":
                continue
            r = row[kk][:, 0]  # (L, H, row_len[, D]) — scales have no D
            l_, h, rl = r.shape[:3]
            rest = r.shape[3:]
            blocks = r.reshape(l_, h, rl // bp, bp, *rest)[:, :, :nb_max]
            blocks = jnp.moveaxis(blocks, 2, 1)  # (L, nb_max, H, bp[, D])
            out[kk] = cache[kk].at[:, blk_ids].set(
                blocks.astype(cache[kk].dtype))
        return out


def codec_is_paged(cache) -> bool:
    return isinstance(cache, dict) and "tables" in cache
