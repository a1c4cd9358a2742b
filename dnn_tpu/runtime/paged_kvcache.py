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

Layout (per leaf KIND, mirroring the dense cache's (L, B, H, S, D)). A
position's state is what its FAMILY says it is — `cache_leaves`, name ->
(heads, width), every leaf written, installed, gathered and freed with
the block. By default K and V per KV head; for a family whose attention
selects what it reads (models/dsa.py) also the position's index key, a
third leaf "ik" of one head; for latent attention (models/mla.py) ONE
leaf "latent" of one head — the compressed latent and the shared rotary
key side by side — which attention reads as key AND value:

    pool   (L, n_blocks, H, block_len, Dp) Dp = D rounded up to whole
                                          128-lane tiles (`lane_padded`)
    ik     (L, n_blocks, 1, block_len, Dip)  Dip = lane_padded(index_dim)
    latent (L, n_blocks, 1, block_len, Dlp)  Dlp = lane_padded(r + dr)
    tables (L, B, max_blocks)  int32   -- replicated over L (the leaf
                                          shape every install / copy /
                                          tier program was written to)
    pos    (B,)                        -- slot lengths, as in dense

**Leaves by layer KIND.** A model whose layers differ in what they keep
(models/mla.py: "full" layers keep every position's latent and index
key, "window" layers a latent of another width for the last W positions
only) declares `cache_kinds`: kind -> {"layers": L_k, "leaves": name ->
(heads, width), "tables": the name of the kind's tables, "window": W or
None}. Each kind's leaves have THAT kind's layers, their own width and
their own number of blocks, and a block table of their own:

    latent   (L_full, n_blocks,   1, block_len, Dlp)   under "tables"
    ik       (L_full, n_blocks,   1, block_len, Dip)   under "tables"
    latent_w (L_win,  n_blocks_w, 1, block_len, Dwp)   under "tables_w"
    tables   (L_full, B, max_blocks);  tables_w (L_win, B, max_blocks)

A family whose cache is K and V declares kinds the same way
(models/llama.py `LlamaKindRows`: "k" / "v" under "tables" for layers that
keep every position, "k_w" / "v_w" under "tables_w" for layers under a
window; `write_attend_rows(window=, leaves=, tables=)` is their decode
read). A layer reaches its kind's leaves at its index AMONG THE KIND's layers
(`scan_blocks(layers=)`). Block ids are a kind's own (each kind's block 0
is its junk block), drawn from ONE `BlockAllocator` (`of(kind)`): a slot
of a window kind holds the blocks its window and the step's write can
touch — ceil(W / block_len) + 1 — whatever its length, entry j of its
table row pointing at the block of positions [j * bp, (j + 1) * bp) while
that is within the window and at junk block 0 before and after
(`ContinuousBatcher._roll_window_blocks` hands the block that fell behind
back and draws the next). Decode over such a leaf gathers the window's
blocks, not the row (`write_attend_latent_rows(window=)`; `_window_rows`
for K and V leaves).

**Leaves whose rows are STRIDES.** A kind may also page leaves that hold one
row every `stride` positions (models/block_select.py: the mean-pooled keys
"kc" a KV head, one every 16 positions): `strided_leaves`, name -> (heads,
width, stride). Such a leaf has `block_len / stride` rows a block and lives
under the kind's `tables` like its other leaves — installed, gathered and
freed with the block —

    kc     (L_full, n_blocks, KV, block_len / stride, Dp)   under "tables"

but is WRITTEN on the one slot-step in `stride` that completes a row
(`PagedKV.write_pooled_rows`: the mean of the last 2 x stride keys the pool
holds), and its transient row has row_len / stride rows. A slot's read may
then be a LIST of table entries a KV head and not the live prefix of its
table (`PagedKV.write_attend_block_rows`).

**Leaves with NO position axis.** A layer that keeps a STATE — a matrix a
head whatever the length, and the last rows of a short convolution — says so
in its kind's `slot_leaves`: name -> (the shape a slot a layer, dtype or None
for the pool's; models/state_kind.py). A kind may have `slot_leaves` ALONE
(models/kda.py's "linear" kind: no `leaves`, `tables` None, beside a "full"
kind of K and V in other layers) or paged `leaves` under `tables` AND
`slot_leaves` (models/mamba2.py: ONE kind whose every layer keeps K and V a
position and a state a slot):

    state     (L_lin, B, H, d, d)  float32        kda's "linear" kind
    conv_tail (L_lin, B, conv - 1, channels)
    k, v      (L, n_blocks, KV, block_len, Dp)    mamba2's "full" kind,
    ssm_state (L, B, H, P, N)      float32        under "tables" (L, B,
    conv_tail (L, B, conv - 1, channels)          max_blocks)

Slot leaves ride the same pytree through `scan_blocks(layers=)` and the
step's donation, reached at the layer's index among ITS kind's layers and
the slot's row, in the same layer body that reaches the kind's blocks
through the slot's table; they draw nothing from the `BlockAllocator`
("blocks a slot" counts paged leaves alone), `install_row` installs the
kind's blocks and leaves them alone, and the finish-and-install program
writes the transient row's running state at the slot — which is also the
only thing that resets a slot.

**A cache of state kinds ALONE is not paged at all.** A model with no K/V
layer (models/retention.py: every layer keeps `state` (L, B, KV, d, D) and
`norm` (L, B, KV, D), float32) declares ONE kind whose `tables` is None:
there is no block, no table, no `BlockAllocator` and no codec for it — the
batcher holds the family's own `init_cache` leaves, admits by slots, and
`scan_blocks` carries them whole and reaches them at the layer's index, as
it carries a paged pool (`serving.py` `nothing_to_page`).

The codec interface matches FloatKV (write_rows / attend_rows /
write_attend_rows / install_row), so GPTFamilyRows / LlamaFamilyRows
decode through it unchanged. The decode step reaches the pool IN PLACE:
the layer loop carries the whole pytree (`scan_blocks`) and the codec
takes the layer (`_PagedLayer`), so no layer's slice is ever cut
out of the pool or written back. Attention gathers the slot's blocks
into a (B, H, S_max, D) view and runs the identical masked einsum — the
reference math is the dense codec's, so token parity is exact — or, with
the kernel on, ops/pallas/cached_attention.paged_decode_attention takes
one grid step a SLOT and walks that slot's live blocks inside it: table
entries from SMEM, each physical block copied straight from the pool
(which stays in HBM) into a double-buffered VMEM scratch, 128 to 1024
positions' worth of blocks an online-softmax update (by what the leaves
hold a position: `cached_attention._paged_group`), the step's own row placed in
the block that holds `pos` and that block written back. Its work follows
what the slots HOLD — a table entry past `pos` and a gated-off slot cost
nothing — which is what `step.attn_live_blocks_total` over
`step.attn_table_blocks_total` measures (obs/timeline.StepClock). The
einsum path is the correctness baseline, and what the chip runs for the
shapes the kernel's block copies cannot take (an int8 pool's scale blocks
unless block_len fills 128 lanes).

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
from jax.experimental.layout import Layout, with_layout_constraint

from dnn_tpu.runtime.kvcache import band_keep

_NEG_BIG = -1e30

__all__ = ["PagedKV", "BlockAllocator", "InsufficientBlocks",
           "init_paged_cache", "lane_padded", "cache_head_dim", "scan_blocks",
           "scan_rows", "LayerRows", "window_blocks", "is_tables"]


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

    def __init__(self, n_blocks: int, kinds=None):
        if n_blocks < 2:
            raise ValueError("need at least 2 blocks (block 0 is reserved)")
        # the further layer kinds' blocks (module docstring): {kind:
        # n_blocks}, an id space each, managed from here (`of`)
        self._kinds = {k: BlockAllocator(n) for k, n in (kinds or {}).items()}
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

    def of(self, kind=None) -> "BlockAllocator":
        """The blocks of a layer kind, by the name of its tables: this
        allocator for the first kind (None, "tables"), its own id space
        for a further one."""
        return self if kind in (None, "tables") else self._kinds[kind]

    @property
    def kinds(self):
        return tuple(self._kinds)

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


LANES = 128  # the minor-most extent of a TPU tile


def lane_padded(head_dim: int) -> int:
    """The stored width of a pool block's rows: `head_dim` rounded up to
    whole 128-lane tiles (64-wide heads are stored 128 wide, the upper
    lanes zero and never read back). Why the pool pays for it: a block
    (H, block_len, D) must be one contiguous run on the device — it is
    what the decode kernel's block DMA and every block-granular install
    / copy address — and the TPU lays an array out by its SHAPE: with a
    minor-most extent under 128 it puts n_blocks minor-most instead
    (bf16[L,1025,20,16,64] comes out {1,4,3,2,0}), no block is
    contiguous, and every program that touches blocks relayouts the
    whole pool at its edges, every step. A row-major layout of the
    narrow shape would pad the same lanes in HBM anyway (and asking for
    one by `jax.experimental.layout` does not survive the persistent
    compile cache: a deserialized executable's results report the
    default layout). So the padding is put in the shape, where every
    program sees it."""
    return -(-head_dim // LANES) * LANES


def cache_head_dim(cfg) -> int:
    """The width of a K/V row: the config's own `head_dim` where it has
    one (a LLaMA-family config may decouple it from n_embd / n_head)."""
    return getattr(cfg, "head_dim", None) or cfg.n_embd // cfg.n_head


def _pad_lanes(x, width):
    """Zero-pad x's last axis up to `width` (a pool block's stored row)."""
    if x.shape[-1] == width:
        return x
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, width - x.shape[-1])])


def window_blocks(window: int, block_len: int) -> int:
    """Blocks a slot of a window kind holds: the window's, and the one
    the step's write opens."""
    return -(-window // block_len) + 1


def init_paged_cache(cfg, slots: int, max_len: int, *, n_blocks,
                     block_len: int = 16, dtype=jnp.float32,
                     kv_heads: Optional[int] = None, leaves=None,
                     kinds=None):
    """Pool + tables pytree for `slots` decode rows of up to `max_len`
    positions each, sharing `n_blocks` physical blocks of `block_len`
    positions (leading L on every leaf, like the dense cache; K/V rows
    stored `lane_padded`). `kv_heads` overrides the
    pool's head width — GQA families store KV heads, not query heads
    (llama.init_cache's narrowing, here applied to the pool).
    dtype="int8" / "int4" build the quantized pools: int8/int4 K/V
    blocks plus per-(position, head) f32 scale blocks, the paged forms
    of kvcache.Int8KV / Int4KV's layouts (int4 stores native jnp.int4,
    two values per byte). `leaves` = {name: (heads, width)} is a family's
    own declaration of a position's state (`cache_leaves`: K, V and an
    index key; one latent) in place of K and V, float pools only.
    `kinds` = a family's `cache_kinds` says it by layer kind (module
    docstring): `n_blocks` is then {tables name: blocks}."""
    if max_len % block_len:
        raise ValueError(f"max_len {max_len} must tile block_len {block_len}")
    nb_max = max_len // block_len
    if kinds is not None:
        if dtype in ("int8", "int4"):
            raise ValueError("a pool with leaves by layer kind is float")
        out = {}
        for k in kinds.values():
            for name, (heads, width) in k["leaves"].items():
                out[name] = jnp.zeros(
                    (k["layers"], n_blocks[k["tables"]], heads, block_len,
                     lane_padded(width)), dtype)
            for name, (heads, width, stride) in k.get("strided_leaves",
                                                      {}).items():
                if block_len % stride:
                    raise ValueError(f"block_len {block_len} must tile the "
                                     f"leaf {name}'s stride {stride}")
                out[name] = jnp.zeros(
                    (k["layers"], n_blocks[k["tables"]], heads,
                     block_len // stride, lane_padded(width)), dtype)
            for name, (shape, leaf_dtype) in k.get("slot_leaves",
                                                   {}).items():
                # no position axis, no blocks: a slot's own, a layer
                out[name] = jnp.zeros((k["layers"], slots, *shape),
                                      leaf_dtype or dtype)
            if k["tables"] is not None:
                out[k["tables"]] = jnp.zeros((k["layers"], slots, nb_max),
                                             jnp.int32)
        return out
    tables = jnp.zeros((cfg.n_layer, slots, nb_max), jnp.int32)
    if leaves is not None:
        if dtype in ("int8", "int4"):
            raise ValueError(
                "a pool with the leaves " + "/".join(leaves) + " is float: "
                "int8 / int4 pools assume K and V alone")
        # every leaf's rows are stored LANE-PADDED (`lane_padded`)
        return {**{name: jnp.zeros((cfg.n_layer, n_blocks, heads, block_len,
                                    lane_padded(width)), dtype)
                   for name, (heads, width) in leaves.items()},
                "tables": tables}
    head_dim = cache_head_dim(cfg)
    heads = kv_heads if kv_heads is not None else cfg.n_head
    # K/V blocks are stored LANE-PADDED (`lane_padded`): see there
    shape = (cfg.n_layer, n_blocks, heads, block_len, lane_padded(head_dim))
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
    slot's block table rides scalar prefetch and the kernel copies each
    live PHYSICAL block straight from the pool — no gather_view
    materialization, work and traffic in proportion to each slot's live
    length. True/"interpret" are unconditional; "auto" engages it only
    on TPU against pools whose per-slot logical length reaches
    kvcache.AUTO_KERNEL_MIN_S (the dense codecs' length-aware policy).
    Windowed pools and int4 pools stay on the einsum (the kernel masks
    causally only / sub-byte VMEM loads are not wired)."""

    def __init__(self, block_len: int, window: Optional[int] = None,
                 use_kernel=False, kinds=None):
        self.block_len = block_len
        self.window = window
        self.use_kernel = use_kernel
        # leaf -> the name of its block tables, where the leaves are by
        # layer kind (module docstring); every leaf under "tables" else
        self.leaf_tables = {name: k["tables"] for k in (kinds or {}).values()
                            for name in (*k["leaves"],
                                         *k.get("strided_leaves", ()))}
        # leaves with no position axis (a state kind's): not installed by
        # blocks — the finish program writes them at the slot
        self.slot_leaves = frozenset(
            name for k in (kinds or {}).values()
            for name in k.get("slot_leaves", ()))
        # positions a group of the paged decode kernel covers, by the
        # first leaf a call reads ("k" / "latent"): said while a decode
        # program was traced, empty while none called the kernel
        self.kernel_spans: dict = {}

    def _kernel_on(self, c) -> bool:
        """Resolve use_kernel against a concrete per-layer pool view
        (pool (n_blocks, H, bp, D), tables (B, nb_max)) — the paged
        mirror of kvcache._KernelDispatch._kernel_on."""
        if self.window is not None or c.get("k", c.get("latent")).dtype \
                == jnp.int4:
            return False
        if self.use_kernel == "auto":
            from dnn_tpu.runtime.kvcache import AUTO_KERNEL_MIN_S

            logical = c["tables"].shape[-1] * self.block_len
            return (jax.default_backend() == "tpu"
                    and logical >= AUTO_KERNEL_MIN_S)
        return bool(self.use_kernel)

    def _note_span(self, name, leaves, tables, layer):
        """Record what a group of the kernel call about to be traced on
        `leaves` covers (`kernel_spans`)."""
        from dnn_tpu.ops.pallas.cached_attention import paged_group

        self.kernel_spans[name] = self.block_len * paged_group(
            leaves, tables.shape[-1], whole=layer is not None)

    # --- decode-row paths (per-layer views: pool (n_blocks, H, bp, D),
    #     tables (B, nb_max)) ------------------------------------------

    # write_rows / gather_view / install_row carry `kv_pool.*` scopes and
    # the attention after them `attn.paged_decode`: the names a device
    # trace files this codec's operations under (chipbench/spans.py).

    @jax.named_scope("kv_pool.write")
    def write_rows(self, c, k, v, pos, write_gate, layer=None):
        """k/v (B, H, 1, D) at per-slot positions pos (B,); write_gate (B,)
        keeps inactive slots' LIVE state untouched. Physical target: block
        tables[b, pos//bp], row pos%bp — one scatter per leaf. An int8
        pool quantizes the incoming rows first (kvcache._quantize_rows)
        and scatters the per-(position, head) scales alongside.

        `layer` selects the WHOLE-POOL form: `c` is then the full cache
        pytree (pool (L, n_blocks, H, bp, D), tables (L, B, nb_max)) and
        the same rows scatter at [layer, blk, :, row] — the pool is
        updated in place by index, never sliced per layer (the decode
        loop's form, `scan_blocks`).

        Gated-off slots are ROUTED TO the reserved junk block (0, row 0)
        rather than restored-in-place: a retired slot's stale table can
        point at a block since REALLOCATED to another request, and a
        duplicate scatter index (stale restore vs the new owner's write)
        has unspecified winner — the restore could resurrect the old
        request's K/V inside the new one's cache. Junk-block collisions
        between gated slots are harmless (block 0 is never owned, never
        attended live)."""
        return self._scatter_rows(c, lambda: self._rows(c, k, v), pos,
                                  write_gate, layer)

    def _scatter_rows(self, c, rows, pos, write_gate, layer):
        """`rows()` {leaf: (B, H, 1[, D])} into block tables[b, pos //
        bp], row pos % bp of each named leaf; the other leaves pass
        through."""
        bp = self.block_len
        tables = c["tables"] if layer is None else c["tables"][layer]
        blk = jnp.take_along_axis(
            tables, (pos // bp)[:, None], axis=1)[:, 0]  # (B,)
        row = pos % bp
        blk = jnp.where(write_gate, blk, 0)
        row = jnp.where(write_gate, row, 0)
        at = (blk, slice(None), row)  # -> (B, H[, D]) rows
        if layer is not None:
            at = (layer,) + at
        out = dict(c)
        for name, r in rows().items():
            out[name] = c[name].at[at].set(r[:, :, 0])
        return out

    @jax.named_scope("kv_pool.write")
    def write_index_rows(self, c, ik, pos, write_gate, layer=None):
        """This step's index keys ik (B, 1, Di) into the "ik" leaf at
        `pos`, gated and junk-routed exactly as `write_rows`."""
        row = _pad_lanes(ik.astype(c["ik"].dtype)[:, None],
                         c["ik"].shape[-1])  # (B, 1, 1, Dip)
        return self._scatter_rows(c, lambda: {"ik": row}, pos, write_gate,
                                  layer)

    def write_attend_latent_rows(self, q, c, row, pos, write_gate, *,
                                 value_dim: int, scale: float, layer=None,
                                 sel=None, window=None, leaf="latent",
                                 tables="tables"):
        """Latent attention's decode step (models/mla.py): this step's
        latent rows `row` (B, 1, Dl) into the "latent" leaf at `pos`
        (gated and junk-routed as `write_rows`), then every query row of
        q (B, R, Dl) — the R heads of a slot — against the slot's cached
        latents: scores q . latent * `scale` over the whole row, softmax
        over the positions <= pos, and as VALUE the row's first
        `value_dim` lanes. -> (y (B, R, value_dim) float32, c). The leaf
        is read once: with the kernel on, a block is copied into VMEM
        once and used as key and value (paged_decode_attention's
        `latent=`), and the write is the kernel's as well; the einsum
        form gathers one view. `sel` (B, S_max) bool narrows what slot b
        reads to the positions it is true at (an indexer's set).

        `window` = W (a layer kind's, with its `leaf` and `tables`): the
        row is scattered, and ceil(W / bp) + 1 blocks are gathered a
        slot — those that hold positions (pos - W, pos] — and attended
        under the band; the rest of the row is not touched, and its
        table entries may point anywhere."""
        if window is not None:
            return self._window_latent_rows(
                q, c, row, pos, write_gate, value_dim=value_dim, scale=scale,
                layer=layer, window=window, leaf=leaf, tables=tables)
        leaf = c["latent"]
        new = _pad_lanes(row.astype(leaf.dtype)[:, None], leaf.shape[-1])
        if layer is not None and self._kernel_on(c):
            from dnn_tpu.ops.pallas.cached_attention import (
                paged_decode_attention,
            )

            self._note_span("latent", [leaf], c["tables"][layer], layer)
            y, pool = paged_decode_attention(
                q[:, None], leaf, None, c["tables"][layer], pos, layer=layer,
                new=(new, write_gate), latent=value_dim, scale=scale, sel=sel,
                interpret=True if self.use_kernel == "interpret" else None)
            return y[:, 0], {**c, "latent": pool}
        with jax.named_scope("kv_pool.write"):
            c = self._scatter_rows(c, lambda: {"latent": new}, pos,
                                   write_gate, layer)
        (view,) = self.gather_view(c, ("latent",), layer=layer,
                                   width=q.shape[-1])
        kv = view[:, 0]  # (B, S_max, Dl)
        s = jnp.einsum("brd,bsd->brs", q.astype(jnp.float32),
                       kv.astype(jnp.float32),
                       preferred_element_type=jnp.float32) * scale
        cols = jnp.arange(kv.shape[1])
        keep = cols[None, :] <= pos[:, None]
        if sel is not None:
            keep = keep & sel
        s = jnp.where(keep[:, None, :], s, _NEG_BIG)
        y = jnp.einsum("brs,bsd->brd", jax.nn.softmax(s, axis=-1),
                       kv[..., :value_dim].astype(jnp.float32),
                       preferred_element_type=jnp.float32)
        return y, c

    def _window_latent_rows(self, q, c, row, pos, write_gate, *, value_dim,
                            scale, layer, window, leaf, tables):
        """`write_attend_latent_rows` for a window kind (see there)."""
        bp = self.block_len
        pool = c[leaf]
        new = _pad_lanes(row.astype(pool.dtype)[:, None], pool.shape[-1])
        tab = c[tables] if layer is None else c[tables][layer]  # (B, nb)
        nb = tab.shape[1]
        with jax.named_scope("kv_pool.write"):
            blk = jnp.take_along_axis(tab, (pos // bp)[:, None], axis=1)[:, 0]
            blk = jnp.where(write_gate, blk, 0)
            at = (blk, slice(None), jnp.where(write_gate, pos % bp, 0))
            pool = pool.at[at if layer is None else (layer,) + at].set(
                new[:, :, 0])
        n = min(window_blocks(window, bp), nb)
        with jax.named_scope("kv_pool.gather"):
            first = jnp.clip(jnp.maximum(pos - window + 1, 0) // bp, 0,
                             nb - n)  # (B,)
            ids = jnp.take_along_axis(
                tab, first[:, None] + jnp.arange(n)[None, :], axis=1)
            g = (pool if layer is None else pool[layer])[ids.reshape(-1)]
            kv = g[:, 0].reshape(ids.shape[0], n * bp, -1)  # (B, n*bp, Dp)
        kv = kv[..., :q.shape[-1]]
        s = jnp.einsum("brd,bsd->brs", q.astype(kv.dtype), kv,
                       preferred_element_type=jnp.float32) * scale
        cols = first[:, None] * bp + jnp.arange(n * bp)[None, :]  # (B, S)
        keep = band_keep(cols, pos[:, None], window)
        s = jnp.where(keep[:, None, :], s, _NEG_BIG)
        p = jax.nn.softmax(s, axis=-1)
        y = jnp.einsum("brs,bsd->brd", p.astype(kv.dtype),
                       kv[..., :value_dim],
                       preferred_element_type=jnp.float32)
        return y, {**c, leaf: pool}

    def decode_form(self, c, layer=0, window=None):
        """Which form a decode read of K and V takes against cache `c`:
        the paged kernel, or a gather of blocks and two einsums — always
        the latter for a window kind's read (`_window_rows`)."""
        return "paged_kernel" if (
            window is None and layer is not None and self._kernel_on(c)
        ) else "gather_einsum"

    def _window_rows(self, q, c, k, v, pos, write_gate, *, layer, window,
                     leaves, tables):
        """`write_attend_rows` for a window kind's K and V leaves: q (B,
        Hk, R, D), the step's k / v (B, Hk, 1, D) scattered in at `pos`
        (gated and junk-routed as `write_rows`), then ceil(W / bp) + 1
        blocks a slot gathered — those that hold positions (pos - W, pos]
        — and attended under the band -> (y (B, Hk, R, D), c). The rest of
        the row is not touched, and its table entries may point anywhere.
        On the chip as on the CPU: the paged kernel started at the window's
        first block was built and measured against this form at 64 slots
        and four layers — alone 1.20 ms against this form's 0.96, inside
        the decode program 1.12 against 1.28 of a 16.5 ms step (it walks
        two groups of eight blocks a slot for nine, and both forms are
        bound by launches, not bytes) — and not kept: a hundredth of a
        step either way did not pay for a second variant of that kernel
        (PERF.md section 6, PR 43)."""
        kn, vn = leaves
        bp = self.block_len
        width = c[kn].shape[-1]
        rows = {kn: _pad_lanes(k.astype(c[kn].dtype), width),
                vn: _pad_lanes(v.astype(c[vn].dtype), width)}
        tab = c[tables] if layer is None else c[tables][layer]  # (B, nb)
        nb = tab.shape[1]
        d = q.shape[-1]
        with jax.named_scope("kv_pool.write"):
            blk = jnp.take_along_axis(tab, (pos // bp)[:, None], axis=1)[:, 0]
            blk = jnp.where(write_gate, blk, 0)
            at = (blk, slice(None), jnp.where(write_gate, pos % bp, 0))
            at = at if layer is None else (layer,) + at
            pools = {n: c[n].at[at].set(r[:, :, 0]) for n, r in rows.items()}
        n = min(window_blocks(window, bp), nb)
        with jax.named_scope("kv_pool.gather"):
            first = jnp.clip(jnp.maximum(pos - window + 1, 0) // bp, 0,
                             nb - n)  # (B,)
            ids = jnp.take_along_axis(
                tab, first[:, None] + jnp.arange(n)[None, :], axis=1)

            def view(pool):  # -> (B, Hk, n * bp, D)
                g = (pool if layer is None else pool[layer])[ids.reshape(-1)]
                g = g.reshape(ids.shape[0], n, *g.shape[1:])[..., :d]
                return jnp.moveaxis(g, 1, 2).reshape(
                    ids.shape[0], g.shape[2], n * bp, d)

            kv, vv = view(pools[kn]), view(pools[vn])
        s = jnp.einsum("bhrd,bhsd->bhrs", q.astype(kv.dtype), kv,
                       preferred_element_type=jnp.float32) / jnp.sqrt(d)
        cols = first[:, None] * bp + jnp.arange(n * bp)[None, :]  # (B, S)
        keep = band_keep(cols, pos[:, None], window)
        s = jnp.where(keep[:, None, None, :], s, _NEG_BIG)
        p = jax.nn.softmax(s, axis=-1)
        y = jnp.einsum("bhrs,bhsd->bhrd", p.astype(vv.dtype), vv,
                       preferred_element_type=jnp.float32)
        return y.astype(c[vn].dtype), {**c, **pools}

    def index_view(self, c, index_dim, layer=None):
        """Every slot's index keys in logical order, (B, S_max, Di): what
        the indexer scores one query a slot against."""
        (view,) = self.gather_view(c, ("ik",), layer=layer, width=index_dim)
        return view[:, 0]

    @staticmethod
    def _rows(c, k, v):
        """The step's k/v (B, H, 1, D) as pool `c` stores them, by leaf:
        cast to the pool's dtype, or quantized with their (B, H, 1)
        scale rows beside them, and padded to the pool's row width."""
        width = c["k"].shape[-1]  # lane-padded (`lane_padded`)
        if "ks" not in c:
            return {"k": _pad_lanes(k.astype(c["k"].dtype), width),
                    "v": _pad_lanes(v.astype(c["v"].dtype), width)}
        from dnn_tpu.runtime.kvcache import (
            _quantize_rows,
            _quantize_rows_int4,
        )

        quantize = (_quantize_rows_int4 if c["k"].dtype == jnp.int4
                    else _quantize_rows)
        kq, ks = quantize(k)  # (B,H,1,D), (B,H,1)
        vq, vs = quantize(v)
        return {"k": _pad_lanes(kq, width), "v": _pad_lanes(vq, width),
                "ks": ks, "vs": vs}

    @jax.named_scope("kv_pool.gather")
    def gather_view(self, c, names=("k", "v"), layer=None, width=None):
        """Dense (B, H, S_max, ...) views of every slot's logical cache —
        the einsum attention baseline (the paged Pallas kernel skips this
        materialization). Handles K/V blocks (…, bp, D) and scale blocks
        (…, bp) alike. With `layer`, `c` is the whole cache pytree and
        the blocks are gathered at [layer, tables[layer]]. `width` cuts
        K/V rows back from the pool's lane-padded width to head_dim."""
        tables = c["tables"] if layer is None else c["tables"][layer]
        b, nb = tables.shape  # (B, nb_max)
        ids = tables.reshape(-1)
        out = []
        for name in names:
            leaf = c[name]
            if layer is None:
                g = jnp.take(leaf, ids, axis=0)  # (B*nb, H, bp[, D])
            else:
                g = leaf[layer, ids]
            if g.ndim == 4 and width is not None:
                g = g[..., :width]
            h, bp = g.shape[1], g.shape[2]
            rest = g.shape[3:]
            g = g.reshape(b, nb, h, bp, *rest)
            g = jnp.moveaxis(g, 1, 2)  # (B, H, nb, bp[, D])
            out.append(g.reshape(b, h, nb * bp, *rest))
        return out

    def attend_rows(self, q, c, pos, window=None, layer=None, sel=None):
        """q (B, H, R, D); every row of slot b attends logical positions
        <= pos[b] (identical math to kvcache.FloatKV/Int8KV.attend_rows
        on the gathered view — int8 pools fold their per-position scales
        onto the score/probability matrices, never a float cache copy),
        band-limited by the codec's `window` when set. `layer` selects
        the whole-pool form (see write_rows): the kernel and the gather
        both reach the pool at [layer, block] in place. A per-call
        `window` override is the dense codecs' per-LAYER channel
        (alt-window configs) — those are rejected at batcher
        construction for paged pools, so an override here is a
        programming error. `sel` (B, S_max) bool narrows what slot b
        reads to the positions it is true at (models/dsa.py's set; it
        lies within <= pos[b])."""
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
            tables = c["tables"] if layer is None else c["tables"][layer]
            self._note_span("k", [c[n] for n in ("k", "v", "ks", "vs")
                                  if n in c], tables, layer)
            out = paged_decode_attention(
                q, c["k"], c["v"], tables, pos,
                ks=c["ks"] if quant else None,
                vs=c["vs"] if quant else None,
                layer=layer, sel=sel, interpret=interp)
            # same output-dtype recipe as the einsum path below
            return out if quant else out.astype(c["v"].dtype)
        d = q.shape[-1]
        if quant:
            k, v, ks, vs = self.gather_view(c, ("k", "v", "ks", "vs"),
                                            layer=layer, width=d)
        else:
            k, v = self.gather_view(c, layer=layer, width=d)
        with jax.named_scope("attn.paged_decode"):
            s = jnp.einsum("bhtd,bhsd->bhts", q.astype(jnp.float32),
                           k.astype(jnp.float32),
                           preferred_element_type=jnp.float32)
            if quant:
                s = s * ks[:, :, None, :]
            s = s / jnp.sqrt(d)
            cols = jnp.arange(k.shape[2])
            mask = band_keep(cols[None, None, None, :],
                             pos[:, None, None, None], self.window)
            if sel is not None:
                mask = mask & sel[:, None, None, :]
            s = jnp.where(mask, s, _NEG_BIG)
            p = jax.nn.softmax(s, axis=-1)
            if quant:
                p = p * vs[:, :, None, :]
            out = jnp.einsum("bhts,bhsd->bhtd", p.astype(jnp.float32),
                             v.astype(jnp.float32),
                             preferred_element_type=jnp.float32)
            return out if quant else out.astype(c["v"].dtype)

    def write_attend_rows(self, q, c, k, v, pos, write_gate, window=None,
                          layer=None, sel=None, leaves=("k", "v"),
                          tables="tables"):
        """The decode step's one call (kvcache._KernelDispatch.
        write_attend_rows): k/v rows in at `pos`, attention out -> (y, c).
        On the whole pool with the kernel on, both are ONE operation: the
        kernel places each slot's row in the block it is about to read
        and hands the pool back through aliased outputs, so the compiled
        step holds no other operation on the pool — no scatter whose
        layout XLA would have to reconcile with the kernel's.

        `window` = W with a layer kind's `leaves` and `tables` (a pool
        whose leaves are by kind): the slot reads (pos - W, pos] of those
        leaves — `_window_rows`. For a pool without kinds a per-call
        window stays `attend_rows`' refusal."""
        if window is not None and self.leaf_tables:
            return self._window_rows(q, c, k, v, pos, write_gate, layer=layer,
                                     window=window, leaves=leaves,
                                     tables=tables)
        if layer is None or window is not None or not self._kernel_on(c):
            c = self.write_rows(c, k, v, pos, write_gate, layer=layer)
            return self.attend_rows(q, c, pos, window=window, layer=layer,
                                    sel=sel), c
        from dnn_tpu.ops.pallas.cached_attention import paged_decode_attention

        with jax.named_scope("kv_pool.write"):
            rows = self._rows(c, k, v)
        names = list(rows)  # k, v[, ks, vs]: the kernel's operand order
        self._note_span("k", [c[n] for n in names], c["tables"][layer], layer)
        y, *pools = paged_decode_attention(
            q, c["k"], c["v"], c["tables"][layer], pos,
            ks=c.get("ks"), vs=c.get("vs"), layer=layer,
            new=(*rows.values(), write_gate), sel=sel,
            interpret=True if self.use_kernel == "interpret" else None)
        c = {**c, **dict(zip(names, pools))}
        # same output-dtype recipe as attend_rows
        return (y if "ks" in c else y.astype(c["v"].dtype)), c

    def write_pooled_rows(self, c, k, pos, write_gate, *, stride, leaf="kc",
                          source="k", layer=None):
        """The strided leaf `leaf`'s row that THIS step completes: where pos
        % stride == stride - 1, the mean of the last 2 x stride rows of
        `source` up to `pos` — the 2 x stride - 1 before `pos` as the pool
        holds them (they lie in the slot's last two blocks) and this step's
        own k (B, Hk, 1, D), which need not be in the pool yet — goes into
        row (pos % bp) // stride of the block that holds `pos`; every other
        slot, and a gated-off one, writes junk block 0."""
        bp = self.block_len
        tab = c["tables"] if layer is None else c["tables"][layer]  # (B, nb)
        bt = pos // bp
        two = jnp.stack([jnp.maximum(bt - 1, 0), bt], axis=-1)
        ids = jnp.take_along_axis(tab, two, axis=1)  # (B, 2) physical
        pool = c[source]
        kk = pool[ids] if layer is None else pool[layer, ids]
        kk = jnp.moveaxis(kk, 1, 2)  # (B, Hk, 2, bp, Dp)
        kk = kk.reshape(*kk.shape[:2], 2 * bp, kk.shape[-1])
        idx = (bp + pos % bp - 2 * stride + 1)[:, None] \
            + jnp.arange(2 * stride - 1)[None, :]
        win = jnp.take_along_axis(kk, idx[:, None, :, None], axis=2)
        own = _pad_lanes(k.astype(pool.dtype), pool.shape[-1])
        row = (win.astype(jnp.float32).sum(2)
               + own[:, :, 0].astype(jnp.float32)) / (2 * stride)
        gate = write_gate & (pos % stride == stride - 1)
        blk = jnp.where(gate, ids[:, 1], 0)
        at = (blk, slice(None), jnp.where(gate, (pos % bp) // stride, 0))
        return {**c, leaf: c[leaf].at[
            at if layer is None else (layer,) + at].set(
                row.astype(c[leaf].dtype))}

    def pooled_view(self, c, leaf, width, layer=None):
        """Every slot's rows of a strided leaf in logical order, (B, Hk,
        nb_max x rows a block, width): `gather_view`'s gather, under the
        caller's scope (the rows are what its scores read)."""
        tab = c["tables"] if layer is None else c["tables"][layer]
        b, nb = tab.shape
        ids = tab.reshape(-1)
        g = (c[leaf][ids] if layer is None else c[leaf][layer, ids])[
            ..., :width]  # (B * nb, Hk, rows, width)
        h, rows = g.shape[1:3]
        return jnp.moveaxis(g.reshape(b, nb, h, rows, width), 1, 2).reshape(
            b, h, nb * rows, width)

    def block_form(self, c, layer=0):
        """Which form `write_attend_block_rows` takes against cache `c`."""
        return "list_kernel" if (
            layer is not None and self._kernel_on(c)) else "list_gather"

    def write_attend_block_rows(self, q, c, k, v, blocks, count, pos,
                                write_gate, layer=None):
        """The decode step of a selection by blocks: this step's k / v (B,
        Hk, 1, D) in at `pos` (gated and junk-routed as `write_rows`), then a
        read of a LIST of blocks a KV head: q (B, Hk, R, D) the rows of slot
        b's KV head g; `blocks` (B, Hk, n) int32 logical block numbers in
        ascending order of which the first `count` (B, Hk) are read — the
        last of them holds `pos` (B,), and is read up to it —; no block
        outside the list is touched -> (y (B, Hk, R, D), c). With the kernel
        on (ops/pallas/block_list_attention.py) each listed block is copied
        from the pool where it lies and the rows are placed by the kernel,
        which hands the pools back through aliased outputs; else the rows
        are scattered, the listed blocks gathered and attended by two
        einsums (its plain form)."""
        from dnn_tpu.ops.pallas.block_list_attention import (
            block_list_attention,
            reference_block_list_attention,
        )

        tab = c["tables"] if layer is None else c["tables"][layer]
        ids = jnp.take_along_axis(tab[:, None, :], blocks, axis=2)
        if layer is not None and self._kernel_on(c):
            with jax.named_scope("kv_pool.write"):
                rows = self._rows(c, k, v)
            y, kp, vp = block_list_attention(
                q, c["k"], c["v"], ids, count, pos, layer=layer,
                new=(rows["k"], rows["v"], write_gate),
                interpret=True if self.use_kernel == "interpret" else None)
            return y.astype(c["v"].dtype), {**c, "k": kp, "v": vp}
        c = self.write_rows(c, k, v, pos, write_gate, layer=layer)
        with jax.named_scope("attn.block_decode"):
            whole = layer is not None
            y = reference_block_list_attention(
                q, c["k"] if whole else c["k"][None],
                c["v"] if whole else c["v"][None], ids, count, pos,
                layer=layer if whole else 0)
        return y.astype(c["v"].dtype), c

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
        out = {kk: cache[kk] for kk in cache
               if is_tables(kk) or kk in self.slot_leaves}
        for kk in cache:
            if kk in out:
                continue
            # leaves by layer kind: `blk_ids` is then {tables name: ids}
            ids = blk_ids[self.leaf_tables[kk]] if isinstance(
                blk_ids, dict) else blk_ids
            nb_max = ids.shape[0]
            r = row[kk][:, 0]  # (L, H, row_len[, D]) — scales have no D
            l_, h, rl = r.shape[:3]
            rest = r.shape[3:]
            bp = cache[kk].shape[3]  # a strided leaf's rows a block are fewer
            blocks = r.reshape(l_, h, rl // bp, bp, *rest)[:, :, :nb_max]
            blocks = jnp.moveaxis(blocks, 2, 1)  # (L, nb_max, H, bp[, D])
            if rest:  # K/V rows go in at the pool's lane-padded width
                blocks = _pad_lanes(blocks, cache[kk].shape[-1])
            out[kk] = cache[kk].at[:, ids].set(
                blocks.astype(cache[kk].dtype))
        return out


class _PagedLayer:
    """A codec bound to one layer of the WHOLE pool (`scan_blocks`): the
    same write_attend_rows a block calls on a per-layer cache view, but `c`
    is the full cache pytree and nothing is ever sliced out of it. A pool
    of state leaves alone has no codec (None): a block reads `layer`."""

    def __init__(self, codec: PagedKV, layer):
        self.codec, self.layer = codec, layer

    def write_attend_rows(self, q, c, k, v, pos, write_gate, window=None,
                          sel=None, **kind):
        return self.codec.write_attend_rows(
            q, c, k, v, pos, write_gate, window=window, layer=self.layer,
            sel=sel, **kind)

    def decode_form(self, c, window=None):
        return self.codec.decode_form(c, self.layer, window)

    def write_pooled_rows(self, c, k, pos, write_gate, **kw):
        return self.codec.write_pooled_rows(c, k, pos, write_gate,
                                            layer=self.layer, **kw)

    def pooled_view(self, c, leaf, width):
        return self.codec.pooled_view(c, leaf, width, layer=self.layer)

    def block_form(self, c):
        return self.codec.block_form(c, self.layer)

    def write_attend_block_rows(self, q, c, k, v, blocks, count, pos,
                                write_gate):
        return self.codec.write_attend_block_rows(
            q, c, k, v, blocks, count, pos, write_gate, layer=self.layer)

    def write_index_rows(self, c, ik, pos, write_gate):
        return self.codec.write_index_rows(c, ik, pos, write_gate,
                                           layer=self.layer)

    def index_view(self, c, index_dim):
        return self.codec.index_view(c, index_dim, layer=self.layer)

    def write_attend_latent_rows(self, q, c, row, pos, write_gate, **kw):
        return self.codec.write_attend_latent_rows(
            q, c, row, pos, write_gate, layer=self.layer, **kw)


def codec_is_paged(cache) -> bool:
    return isinstance(cache, dict) and "tables" in cache


def is_tables(name: str) -> bool:
    """Whether a cache entry is a kind's block tables, not a leaf."""
    return name.startswith("tables")


class LayerRows:
    """The WHOLE transient row cache `leaves` {name: (L, 1, H, S[, D])}
    (a state kind's slot leaves (L, 1, ...)) bound to one layer's index
    (`scan_rows`): what a prefill chunk's block is handed in place of the
    layer's rows cut out of it. Two things reach the row —

      * `rows[name]` / `read(*names)`: the layer's row of a leaf, (1, H,
        S[, D]) — one `dynamic_index_in_dim` of the whole leaf, the read
        the attention kernel needs anyway;
      * `write(start, name=new)`: `new` (1, H, T[, D]) goes in at `[layer,
        :, :, start:start + T]` of the whole leaf — T rows move, not S (a
        strided leaf's caller says its own start and hands its T / stride
        rows); `update(name=new)`: a leaf that IS the layer's whole content
        (a state and its convolution's tail) is written whole at the
        layer's index — the mapping a state rule's chunk form is handed
        (models/state_kind.py).

    Write before read: a read after a write sees it."""

    def __init__(self, leaves, layer):
        self.leaves, self.layer = leaves, layer

    def __getitem__(self, name):
        # held as the leaf is: what reads the row relays the ROW (left to
        # itself the compiler relaid a whole latent leaf and cut the
        # layer's row out of the copy, every layer)
        return _as_held(lax.dynamic_index_in_dim(
            self.leaves[name], self.layer, 0, keepdims=False))

    def read(self, *names):
        return {name: self[name] for name in names or self.leaves}

    def _put(self, new, start=None):
        for name, rows in new.items():
            leaf = self.leaves[name]
            at = [self.layer] + [0] * (leaf.ndim - 1)
            if start is not None:
                at[3] = start
            self.leaves = {**self.leaves, name: lax.dynamic_update_slice(
                leaf, rows[None].astype(leaf.dtype), at)}

    def write(self, start, **new):
        self._put(new, start)

    def update(self, **new):
        self._put(new)


def scan_rows(block, carry, blocks, rows, *xs, layers=None):
    """`scan_blocks` turned to a prefill chunk's transient row cache `rows`
    {name: (L, 1, H, S[, D])}: `block(bp, carry, rows, *xs_l) -> (carry,
    rows)` once a layer, `rows` the WHOLE row cache bound to the layer's
    index (`LayerRows`) -> (carry, the row cache). The row cache is the
    loop's CARRY and never its xs / ys — a scan cannot alias the two, so a
    row that rode as xs was copied whole, each layer's row cut out of it
    and the whole cut-out written back for a chunk's T new positions —: the
    chunk program's donated row is the carry and the result, touched only
    by the blocks' writes. `blocks` and the layers' indices are the only
    xs (`xs`: further per-layer inputs); `layers` is the stack's own range
    of the row's layers where the model's layers are of kinds, each kind's
    stack over ITS leaves of the one carry (llama.layer_stacks)."""
    if layers is None:
        layers = jnp.arange(jax.tree.leaves(rows)[0].shape[0])

    def body(c, layer_in):
        (carry, leaves), (bp, layer, *rest) = c, layer_in
        carry, bound = block(bp, carry, LayerRows(leaves, layer), *rest)
        return (carry, _as_held(bound.leaves)), None

    (carry, rows), _ = lax.scan(body, (carry, rows), (blocks, layers, *xs))
    return carry, rows


def _as_held(leaves):
    """`leaves`, each constrained to the layout in which the backend's
    devices HOLD an array of its shape — the layout the row arrives in and
    leaves in, which a program cannot choose. Left to itself the chip's
    compiler gives the loop's carry the layout the attention kernel reads
    (head width minor-most) where the device holds positions minor-most (a
    width that does not fill whole 128-lane tiles: GPT-2's heads of 64, a
    latent row of 576), and transposes the WHOLE row cache into the loop
    and out of it again every chunk: two whole-row copies a leaf, more than
    the xs / ys form paid. Held as it arrives, a layer's row is transposed
    once, where the kernel reads it."""
    device = jax.devices()[0]

    return jax.tree.map(lambda x: with_layout_constraint(
        x, Layout.from_pjrt_layout(device.client.get_default_layout(
            x.dtype, x.shape, device))), leaves)


def scan_blocks(block, x, blocks, cache, codec, *xs, layers=None):
    """Run the stacked `blocks` over a KV cache: `block(bp, x, c, codec,
    *xs_l) -> (x, c)` once per layer -> (x, cache). What the cache IS
    decides how it rides the loop (a property of the input, not an
    option):

      * a paged pool is CARRIED whole and reached by layer index
        (`_PagedLayer`): the pool (L, n_blocks, H, bp, D) is never
        an xs/ys of the scan, so no layer's slice is ever cut out of it
        or written back — the step's donated buffer is the carry and the
        output, touched only by the row scatter and the kernel's block
        reads; so is a pool of state leaves ALONE, which has no tables and
        no codec (`codec` None: the block is handed the layer's index);
      * a dense cache (L, B, H, S, D) rides as xs/ys, one layer's slots
        per iteration.

    `blocks` is whatever of the layers' params rides the loop, `bp` its
    layer's part (`llama.scan_form` keeps expert stacks out of it, whole).
    `xs` are further per-layer inputs (LLaMA's per-layer windows).
    `layers` (paged pools): the pool's layer indices that `blocks` are,
    where they are not all of them — a model whose layers are of two
    kinds scans each kind's stack over its own range of ONE pool
    (llama.layer_stacks)."""
    # `layers.scan` names the loop's OWN work on a device trace: slicing
    # each layer's weights (and a dense cache's layer) out of the stacks
    # and writing the slice back; the block's work carries the inner
    # scopes (gpt.block.*, attn.*, kv_pool.*)
    with jax.named_scope("layers.scan"):
        if codec is not None and not codec_is_paged(cache):
            def dense(x, layer_in):
                bp, c, *rest = layer_in
                return block(bp, x, c, codec, *rest)

            return lax.scan(dense, x, (blocks, cache, *xs))

        def paged(carry, layer_in):
            bp, layer, *rest = layer_in
            return block(bp, *carry, _PagedLayer(codec, layer), *rest), None

        if layers is None:
            layers = jnp.arange(cache.get(
                "tables", jax.tree.leaves(cache)[0]).shape[0])
        (x, cache), _ = lax.scan(paged, (x, cache), (blocks, layers, *xs))
        return x, cache
