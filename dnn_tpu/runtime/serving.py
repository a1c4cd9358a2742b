"""Continuous-batching decode server for the GPT family.

The reference serves exactly one request per pipeline traversal — a
single stateless forward with no decode at all (SURVEY §3.2-3.3,
/root/reference/node.py:137-200). `runtime/generate.py` already rebuilds
batch decode; this module adds the modern serving layer on top:
CONTINUOUS BATCHING — a fixed pool of decode slots over one static-shape
KV cache, where requests enter (prefill into a free slot) and leave
(EOS / token budget) independently while the other slots keep decoding.
Throughput stays at full batch width without waiting for stragglers.

TPU-first mechanics (everything static under jit, THREE compiled
programs total — chunk prefill, prefill finish, decode step):

  * ONE decode step program for the whole pool: every slot advances one
    token per call. Per-slot sequence positions live in a (B,) vector;
    K/V writes land at each row's own position (vmap'd dynamic update —
    rows are independent), attention masks each row against its own
    length, inactive slots are fully masked no-ops.
  * ONE prefill-chunk program: prompts prefill as full prompt_pad-sized
    chunks plus one right-padded tail, each at its absolute position —
    any prompt length (up to max_len - max_new) reuses the same compiled
    chunk. Where no `prompt_pad` is asked for it is the chip's ridge
    (`ridge_pad`: the tokens a launch must hold before its matmuls cost
    more than streaming the block weights once — below it a launch costs
    that stream whatever rows it holds). Tail pad positions write garbage
    K/V that is never attended
    (the per-row position mask stops at the true length) and is
    overwritten as the sequence grows through it; a second small program
    FINISHES AND INSTALLS: it derives the request's rng stream, samples
    the first token from the true last prompt row, installs the finished
    slot-row cache into the pool and sets every per-slot state vector,
    all donated — an admission is a fresh row, its chunks and that one
    program, and nothing else touches the device from `submit()`
    (tests/test_admit_program.py counts the launches).
  * Slot bookkeeping (which request owns which slot, emitted tokens, EOS)
    is plain host Python — it changes per request, so it must not live
    inside the compiled graphs.

Numerics are the same ops as `make_generate` (same embed/block/head
path), so a greedy slot's token stream is identical to a solo batch-1 run
of the same prompt — the parity contract `tests/test_serving.py` pins.
Isolation holds for sampling too: every request gets its own rng stream,
derived from (server seed, request id) and stepped per generated token,
so one request's tokens never depend on what else shares the pool or
when it arrived. (A sampled stream matches `make_generate`'s only in
distribution, not token-for-token — the solo decoder uses one batch-wide
key sequence.)
"""

from __future__ import annotations

import functools
import time
from collections import deque
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from dnn_tpu import obs
from dnn_tpu.obs import profile as _profile
from dnn_tpu.obs.profile import annotation_ctx as _prof_annotation
from dnn_tpu.models.gpt import GPTConfig, head
from dnn_tpu.utils import flops
from dnn_tpu.utils.metrics import Throughput, labeled
from dnn_tpu.ops.attention import merge_heads
from dnn_tpu.ops.nn import gelu, layer_norm, linear
from dnn_tpu.ops.pallas.sparse_attention import walked_columns
from dnn_tpu.runtime.generate import (
    TOP_P_PREFILTER_K,
    _NEG_BIG,
    _qkv_heads,
    _sample_rows,
    apply_repetition_penalty,
    hidden_with_cache,
    init_cache,
    logit_bias_row,
)
from dnn_tpu.runtime.constrain import (
    mask_words, pack_mask_table, unpack_mask_table,
)
from dnn_tpu.runtime.kvcache import codec_for_cache
from dnn_tpu.runtime.paged_kvcache import (
    is_tables, scan_blocks, window_blocks,
)


def _decode_block_rows(bp, x, layer_cache, pos, write, *, cfg, compute_dtype,
                       codec, ffn=None):
    """One block over x (B,1,C) with per-row positions. `write` (B,) bool
    gates the cache update (inactive slots must not touch their rows).
    The cache codec (float or int8 — dnn_tpu/runtime/kvcache.py) owns the
    per-row write/attend; `ffn(bp, h)` overrides the dense MLP (MoE
    serving, dnn_tpu/runtime/generate_moe.moe_cache_ffn)."""
    # the same scope names as models/gpt._block_core, so a device trace
    # files the cached step's matmuls with the plain forward's
    with jax.named_scope("gpt.block.attn"):
        h = layer_norm(bp["ln_1"], x, eps=cfg.ln_eps)
        q, k, v = _qkv_heads(bp, h, cfg=cfg, compute_dtype=compute_dtype)
        y, layer_cache = codec.write_attend_rows(q, layer_cache, k, v, pos,
                                                 write)
        x = x + linear(bp["attn"]["proj"], merge_heads(y.astype(x.dtype)),
                       compute_dtype=compute_dtype)
    with jax.named_scope("gpt.block.mlp"):
        h = layer_norm(bp["ln_2"], x, eps=cfg.ln_eps)
        if ffn is None:
            m = linear(bp["mlp"]["proj"],
                       gelu(linear(bp["mlp"]["fc"], h,
                                   compute_dtype=compute_dtype)),
                       compute_dtype=compute_dtype)
        else:
            m = ffn(bp, h).astype(x.dtype)
    return x + m, layer_cache


def install_dense_row(cache, row, slot):
    """Install a finished transient row cache into `slot` of a dense
    pool, CLAMPED at the pool's own position count — the row is
    chunk-rounded and may overhang the pool, and a dynamic update whose
    operand exceeds the target would clamp the start index back onto
    real positions and corrupt the cache (the prefill_finish lesson).
    The one shared implementation for every finish/install program
    (convoy finish, fused interleaved finish, the speculative draft
    installs) so the clamp invariant cannot drift per path."""
    return {
        kk: lax.dynamic_update_slice_in_dim(
            cache[kk],
            lax.slice_in_dim(row[kk], 0, cache[kk].shape[3], axis=3),
            slot, axis=1)
        for kk in cache
    }


class GPTFamilyRows:
    """The GPT family's per-slot decode hooks — the default
    `ContinuousBatcher` family adapter. A family adapter supplies three
    things: the cache layout, the padded-prompt prefill forward, and the
    per-row decode forward (per-slot positions); everything else —
    slot bookkeeping, sampling streams, retirement — is family-agnostic
    and lives in the batcher. Other families plug in the same way
    (LLaMA: dnn_tpu/models/llama.LlamaFamilyRows — RoPE positions and a
    KV-head-width cache; MoE stays a GPT block with `ffn` overridden)."""

    def __init__(self, cfg, *, compute_dtype=None, ffn=None,
                 attn_kernel="auto"):
        self.cfg = cfg
        self.compute_dtype = compute_dtype
        self.ffn = ffn
        # cache-attention routing (prefill chunks + decode rows): True =
        # always the Pallas streaming kernel, False = always the einsum,
        # "auto" (default) = the length-aware policy — kernel only on TPU
        # against caches >= kvcache.AUTO_KERNEL_MIN_S positions
        self.attn_kernel = attn_kernel

    def init_cache(self, batch, max_len, dtype):
        return init_cache(self.cfg, batch, max_len, dtype)

    def prefill(self, prepared, padded, row_cache, start_pos=0):
        """One (1, P) prompt chunk at positions [start_pos, start_pos+P)
        -> (hidden (1, P, C) float32, row_cache): the last block's
        output — no final norm, no head: the head meets ONE row of an
        admission, in the finish program. Long prompts prefill as several
        full chunks + one padded tail (the batcher's chunk loop)."""
        return hidden_with_cache(
            prepared, padded, row_cache, start_pos, cfg=self.cfg,
            compute_dtype=self.compute_dtype, ffn=self.ffn,
            attn_kernel=self.attn_kernel)

    def head_leaves(self, prepared):
        """The leaves `head` reads (final norm, head kernel) of a param
        view: what the finish program is handed in place of the whole
        tree."""
        return {k: prepared[k] for k in ("ln_f", "lm_head")}

    def head(self, aux, h):
        """Logits of hidden rows h (..., C) under `gpt.head`."""
        return head(aux, h.astype(jnp.float32), cfg=self.cfg,
                    compute_dtype=self.compute_dtype)

    def verify_rows(self, prepared, cache, chunk, pos, active, codec):
        """A (B, T) token block at PER-ROW start positions pos (B,):
        writes K/V for positions pos..pos+T-1 of each active row, attends
        with per-row within-block causality (codec.attend_rows_causal),
        returns (logits (B, T, V), cache). The speculative batcher's
        target-scoring / draft-sync program — row t's logits predict the
        token at position pos+t+1."""
        cfg, compute_dtype = self.cfg, self.compute_dtype
        b, t = chunk.shape
        positions = pos[:, None] + jnp.arange(t)  # (B, T)
        x = jnp.take(prepared["wte"]["embedding"], chunk, axis=0) + \
            jnp.take(prepared["wpe"]["embedding"], positions, axis=0)
        if compute_dtype is not None:
            x = x.astype(compute_dtype)

        def layer(carry, layer_in):
            bp, layer_cache = layer_in
            h = layer_norm(bp["ln_1"], carry, eps=cfg.ln_eps)
            q, kk, vv = _qkv_heads(bp, h, cfg=cfg,
                                   compute_dtype=compute_dtype)
            layer_cache = codec.write_rows(layer_cache, kk, vv, pos, active)
            y = codec.attend_rows_causal(q, layer_cache, pos)
            carry = carry + linear(bp["attn"]["proj"],
                                   merge_heads(y.astype(carry.dtype)),
                                   compute_dtype=compute_dtype)
            h = layer_norm(bp["ln_2"], carry, eps=cfg.ln_eps)
            if self.ffn is None:
                m = linear(bp["mlp"]["proj"],
                           gelu(linear(bp["mlp"]["fc"], h,
                                       compute_dtype=compute_dtype)),
                           compute_dtype=compute_dtype)
            else:
                m = self.ffn(bp, h).astype(carry.dtype)
            return carry + m, layer_cache

        with jax.named_scope("layers.scan"):
            x, new_cache = lax.scan(layer, x, (prepared["blocks"], cache))
        logits = head(prepared, x.astype(jnp.float32), cfg=cfg,
                      compute_dtype=compute_dtype)
        return logits, new_cache

    def decode_rows(self, prepared, cache, tok, pos, active, codec):
        """One per-slot decode step: tok/pos/active (B,) ->
        (logits (B, V), cache)."""
        cfg, compute_dtype = self.cfg, self.compute_dtype
        x = jnp.take(prepared["wte"]["embedding"], tok[:, None], axis=0) + \
            prepared["wpe"]["embedding"][pos][:, None, :]
        if compute_dtype is not None:
            x = x.astype(compute_dtype)

        def block(bp, x, c, codec):
            return _decode_block_rows(
                bp, x, c, pos, active, cfg=cfg,
                compute_dtype=compute_dtype, codec=codec, ffn=self.ffn)

        # a paged pool rides the loop whole, a dense cache by layer
        x, new_cache = scan_blocks(
            block, x, prepared["blocks"], cache, codec)
        logits = head(prepared, x.astype(jnp.float32), cfg=cfg,
                      compute_dtype=compute_dtype)
        return logits[:, -1], new_cache


def _admit_span(submit):
    """`submit()` as an `admit` annotation (stat `prompt_len`) in a
    recording profiler capture, entry to return: the parent of the
    `admit.prefill` / `admit.first_token` / `admit.install` spans the
    body opens, which carry the `rid` it assigns. One attribute check
    when no capture records."""
    @functools.wraps(submit)
    def traced(self, prompt, *args, **kw):
        if not _profile._capturing:
            return submit(self, prompt, *args, **kw)
        with _prof_annotation("admit", prompt_len=int(np.size(prompt))):
            return submit(self, prompt, *args, **kw)
    return traced


def _capped_pairs(start: int, t: int, cap: int) -> int:
    """Sum over the rows i of a chunk at [start, start + t) of min(start +
    i + 1, cap): the positions its queries read where each reads at most
    `cap` (an indexer's topk, a window) of the start + i + 1 it could."""
    n_all = min(max(cap - start, 0), t)  # rows that read everything
    return n_all * start + n_all * (n_all + 1) // 2 + (t - n_all) * cap


def _mask_rows(ctable, crow, vocab: int):
    """(B, vocab) bool, True = allowed: row `crow[i]` of the bit-packed
    mask pool `ctable` (rows, W) uint32 for each of the B slots, unpacked.
    The rows are read as B one-row `dynamic_slice`s, not as a gather: the
    chip's compiler brings a gather's whole OPERAND into fast memory
    first (the pool's relayout PR 48 removed, and an eighth of it for a
    packed pool), a slice's row alone."""
    words = jnp.concatenate(
        [lax.dynamic_slice_in_dim(ctable, crow[i], 1, axis=0)
         for i in range(crow.shape[0])], axis=0)
    return unpack_mask_table(words, vocab, jnp)


def ridge_pad(itemsize: int, max_len: int, positions: int) -> Optional[int]:
    """The `prompt_pad` of a batcher that is asked for none, on a chip
    that states its peaks: the chip's ridge in tokens — `device_peak_flops
    x itemsize / (2 x device_peak_hbm_bw)`, the rows a matmul over weights
    of `itemsize` bytes must hold before it costs the MXU more than the
    weights' stream costs the HBM (241 for bfloat16 on a v5e) — rounded up
    to a power of two, at most `max_len`. Below the ridge a chunk
    launch costs its weight stream whatever rows it holds, so a prompt is
    cheapest in the fewest launches. None off the TPU (no peaks), and
    where a row of whole such chunks would reach past the model's
    `positions` (a learned position table has no row there, and its fill
    value is a NaN no mask hides)."""
    peak, bw = flops.device_peak_flops(), flops.device_peak_hbm_bw()
    if peak is None or bw is None:
        return None
    pad = 1
    while pad < peak * itemsize / (2 * bw):
        pad *= 2
    pad = min(pad, max_len)
    return pad if -(-max_len // pad) * pad <= positions else None


class ContinuousBatcher:
    """Slot-pool decode server. `slots` concurrent sequences over one
    static cache of `max_len` positions; prompts prefill in
    `prompt_pad`-sized chunks (one prefill compilation for all requests,
    any prompt length; by default the chip's ridge, `ridge_pad`, and
    min(64, max_len) off the TPU).

    Usage:
        srv = ContinuousBatcher(cfg, prepared, slots=4, max_len=96)
        rid = srv.submit(prompt_ids, max_new_tokens=32)   # needs a free slot
        srv.step()       # every active slot advances one token
        srv.drain()      # run to completion -> {rid: np.ndarray tokens}
    """

    # class-level capability: variants that commit >1 token per step
    # (SpeculativeBatcher) override this to False — per-token grammar
    # masks cannot gate a verified chunk. A class attribute (not an
    # instance flag set around super().__init__) so there is no
    # initialization-order hazard to refactor away.
    _constraints_ok = True

    def __init__(self, cfg: GPTConfig, prepared, *, slots: int = 4,
                 max_len: Optional[int] = None, prompt_pad: Optional[int] = None,
                 temperature: float = 0.0, top_k: Optional[int] = None,
                 top_p: Optional[float] = None, min_p: Optional[float] = None,
                 repetition_penalty: Optional[float] = None,
                 compute_dtype=None, eos_id: Optional[int] = None, seed: int = 0,
                 ffn=None, kv_dtype=None, family=None,
                 attn_kernel="auto", prefix_cache: int = 0,
                 decode_buckets=False,
                 logprobs_k: int = 0,
                 kv: Optional[str] = None,
                 paged_blocks: int = 0, block_len: int = 16,
                 lora_adapters=None, lora_alphas=None,
                 allow_logit_bias: bool = False,
                 allow_constraints: bool = False,
                 constraint_rows: int = 1024,
                 prefill_chunk_tokens: int = 0,
                 overlap: bool = False):
        self.cfg = cfg
        self.prepared = prepared
        self.slots = slots
        # multi-LoRA serving: `lora_adapters` is a list of adapter trees
        # (lora.init_lora/load_lora against THIS prepared layout); each
        # request picks one by index at submit(adapter=i) or serves the
        # base model by default. One set of base weights, per-slot
        # low-rank deltas applied inside ops.nn.linear via a param VIEW
        # (lora.lora_view) — the compiled step programs are shared by
        # every adapter mix.
        self._lora = None
        self._n_adapters = 0
        if lora_adapters:
            from dnn_tpu.lora import stack_loras, transpose_lora_stack

            # transpose layer-stacked slabs to scan order ONCE — per-view
            # construction below is then pure host dict surgery
            self._lora = transpose_lora_stack(
                stack_loras(list(lora_adapters), alphas=lora_alphas))
            self._n_adapters = len(lora_adapters)
        self._aid = np.zeros((slots,), np.int32)  # 0 = base model
        self._decode_view = None
        self._pf_views: dict = {}  # aid -> memoized single-row prefill view
        self.max_len = min(max_len or cfg.block_size, cfg.block_size)
        self.eos_id = eos_id
        self._seed = seed
        # constructor values become the per-request DEFAULTS; submit() may
        # override any of them per request (per-slot parameter vectors
        # below — same compiled step program for every mix)
        self._default_temp = float(temperature)
        self._default_topk = int(top_k) if top_k else 0
        self._default_topp = float(top_p) if top_p else 0.0
        self._default_minp = float(min_p) if min_p else 0.0
        self._default_rep = (float(repetition_penalty)
                             if repetition_penalty else 1.0)
        # logprobs_k > 0 compiles the step/finish programs to also emit
        # the chosen token's logprob + the top-k (ids, logprobs) per step;
        # a CONSTRUCTION-time choice so the program count stays fixed
        self._logprobs_k = int(logprobs_k)
        # `family` supplies the model-specific cache/prefill/decode hooks
        # (default: the GPT block family; LLaMA passes LlamaFamilyRows).
        # With an explicit family, the model math runs at the FAMILY's
        # compute_dtype — a diverging batcher-level knob would silently
        # lose, so it is rejected, and the cache default follows the
        # family's dtype.
        if family is not None:
            if ffn is not None:
                raise ValueError(
                    "pass ffn on the family adapter, not alongside family=")
            if attn_kernel not in ("auto", False):
                raise ValueError(
                    "pass attn_kernel on the family adapter, not alongside "
                    "family= (the adapter owns its attention path)")
            fam_dtype = getattr(family, "compute_dtype", None)
            if compute_dtype is not None and fam_dtype != compute_dtype:
                raise ValueError(
                    f"compute_dtype mismatch: batcher={compute_dtype} vs "
                    f"family adapter={fam_dtype} — set it on the adapter")
            compute_dtype = fam_dtype
        self.family = family or GPTFamilyRows(
            cfg, compute_dtype=compute_dtype, ffn=ffn,
            attn_kernel=attn_kernel)
        # the pad no caller chose follows the chip: the ridge of the dtype
        # the blocks' matmuls stream their weights in (`ridge_pad`)
        self.prompt_pad = prompt_pad or ridge_pad(
            jnp.dtype(compute_dtype or jnp.float32).itemsize, self.max_len,
            cfg.block_size) or min(64, self.max_len)
        # a family whose cache is not K and V alone (models/dsa.py: the
        # index key of every position as a third leaf; models/mla.py: ONE
        # compressed latent a position) serves from the paged pool alone
        # (checked below, once paging is decided), and what assumes K and
        # V alone refuses it here, by name
        self._index_topk = getattr(self.family, "index_topk", None)
        # a selection whose unit is a BLOCK of the pool (models/
        # block_select.py): the family says what a query reads of its
        # context, in positions, and which `block_len` its pool needs
        self._select_counts = getattr(self.family, "select_counts", None)
        need_bp = getattr(self.family, "required_block_len", None)
        if need_bp is not None and block_len != need_bp and kv != "dense" \
                and (paged_blocks or kv in ("paged", "auto")):
            raise ValueError(
                f"this model selects what it reads by blocks of {need_bp} "
                f"positions: its paged pool needs block_len {need_bp}, not "
                f"{block_len}")
        self._latent = bool(getattr(self.family, "latent_attention", False))
        # leaves BY LAYER KIND (models/mla.py: layers that differ in what
        # they keep; paged_kvcache's module docstring), or None
        self._cache_kinds = getattr(self.family, "cache_kinds", None)
        # the layers that select: all of them, or the full kind's; and the
        # window kinds as the step-end counters read them
        full = (self._cache_kinds or {}).get("full")
        self._n_index_layers = (cfg.n_layer if self._cache_kinds is None
                                else full["layers"] if full else 0)
        self._win_kinds = [(kind, k["layers"], k["window"])
                           for kind, k in (self._cache_kinds or {}).items()
                           if k["window"] is not None]
        # kinds whose cache is K and V (models/llama.py `LlamaKindRows`):
        # their reads are the attn.* series', a latent family's the mla.*
        self._kv_kinds = full is not None and not self._latent
        # leaves with NO position axis (a kind's `slot_leaves` — a state
        # and a convolution tail a slot a layer: models/kda.py's kind of
        # them alone, models/mamba2.py's beside paged K and V in the SAME
        # kind): name -> (shape a slot a layer, dtype). They ride the
        # pool's pytree, are installed and reset by the finish program
        # alone and draw no blocks; the chunk program of such a family is
        # told how many of its positions are real (`prefill_chunk`)
        self._slot_leaves = {
            n: v for k in (self._cache_kinds or {}).values()
            for n, v in k.get("slot_leaves", {}).items()}
        self._n_state_layers = sum(
            k["layers"] for k in (self._cache_kinds or {}).values()
            if k.get("slot_leaves"))
        self._takes_n_real = bool(getattr(self.family, "takes_n_real",
                                          False))
        # a cache whose every kind is a state kind (models/retention.py)
        # has no block tables: there is nothing to page, the leaves are
        # held as they are, admission is by slots alone and `max_len`
        # bounds positions, no memory
        nothing_to_page = bool(self._cache_kinds) and all(
            k["tables"] is None for k in self._cache_kinds.values())
        if getattr(self.family, "requires_paged", False) or self._slot_leaves:
            leaves = "/".join(
                n for k in self._cache_kinds.values()
                for n in (*k["leaves"], *k.get("strided_leaves", ()),
                          *k.get("slot_leaves", ()))
            ) if self._cache_kinds else "/".join(self.family.cache_leaves)
            refused = None
            if nothing_to_page and decode_buckets:
                refused = ("decode_buckets (no leaf has a position axis to "
                           "bucket)")
            elif prefix_cache > 0:
                refused = ("prefix_cache (the radix prefix store and the "
                           "fleet KV tier share and move K/V blocks alone)")
            elif kv_dtype in ("int8", "int4"):
                refused = (f"an {kv_dtype} KV pool (its scale leaves "
                           "assume K and V alone)")
            elif prefill_chunk_tokens:
                # the one-step dispatch pipeline (overlap=) is NOT refused:
                # it launches the very decode program this family runs and
                # never the mixed step
                refused = ("interleaved prefill (its mixed step was built "
                           "for K and V alone)")
            if refused is not None:
                raise ValueError(
                    f"this model's cache has the leaves {leaves}: "
                    f"{refused} is not available with it")
        # kv_dtype picks the cache storage codec (None follows
        # compute_dtype; "int8" = quantized cache, kvcache.Int8KV)
        cache_dtype = kv_dtype if kv_dtype is not None else (compute_dtype or jnp.float32)
        self._cache_dtype = cache_dtype

        # `kv` picks the cache layout by NAME — the serving-path selector
        # ("--kv=paged|dense" at the daemon edge):
        #   * None (legacy): paged iff paged_blocks > 0 (the pre-flag
        #     contract, kept for direct constructors and old tests);
        #   * "dense": the per-slot dense pool, rejecting a contradictory
        #     paged_blocks;
        #   * "paged": the block pool; paged_blocks=0 auto-sizes it to
        #     the dense pool's capacity (slots x max_len positions + the
        #     reserved junk block), so flipping the flag never shrinks
        #     admission capacity — it only adds block-granular packing;
        #   * "auto" (the LMServer default): "paged" whenever this
        #     configuration can page, else the dense fallback — recorded
        #     as a `kv_fallback_dense` flight event so the operator can
        #     see WHY the default didn't engage.
        if kv not in (None, "dense", "paged", "auto"):
            raise ValueError(
                f"kv must be 'paged', 'dense' or 'auto', got {kv!r}")
        if kv == "dense" and paged_blocks:
            raise ValueError(
                "kv='dense' contradicts paged_blocks="
                f"{paged_blocks}; drop one of them")
        if kv in ("paged", "auto"):
            blocker = None
            if nothing_to_page:
                blocker = ("no leaf of this model's cache has a position "
                           "axis: there is nothing to page")
            elif decode_buckets:
                blocker = ("decode_buckets is a dense-pool feature (the "
                           "paged pool is already length-proportional)")
            elif (getattr(self.family, "softcap", None) is not None
                    or getattr(self.family, "alt_window", False)):
                blocker = ("softcapped / alternating-window families "
                           "have no paged channel")
            elif (getattr(self.family, "window", None) is not None
                    and prefix_cache > 0):
                blocker = ("windowed paged pools do not compose with "
                           "the prefix cache")
            elif self.max_len % block_len or self.prompt_pad % block_len:
                blocker = (f"max_len {self.max_len} / prompt_pad "
                           f"{self.prompt_pad} must tile block_len "
                           f"{block_len}")
            if blocker is None:
                if not paged_blocks:
                    paged_blocks = slots * (self.max_len // block_len) + 1
            elif kv == "paged" or paged_blocks:
                # an explicit paged_blocks is an explicit ask for the
                # pool — silently discarding its sizing on the auto path
                # would swap the cache layout under a misconfigured
                # deployment that used to fail loud here
                raise ValueError(
                    f"kv={kv!r}"
                    + (f" with paged_blocks={paged_blocks}" if paged_blocks
                       else "")
                    + f" is not available: {blocker}")
            elif not nothing_to_page:
                # auto, nothing explicit: dense fallback, visibly
                obs.flight.record("kv_fallback_dense", reason=blocker)

        # device state (functional updates). paged_blocks > 0 swaps the
        # per-slot dense cache for the shared block pool + per-slot block
        # tables (runtime/paged_kvcache.py): admission is then by ACTUAL
        # request length (sum of blocks), not slots x max_len.
        self._paged = int(paged_blocks) > 0
        if getattr(self.family, "requires_paged", False) and not self._paged:
            raise ValueError(
                "this model's cache has the leaves "
                + "/".join(self.family.cache_leaves)
                + " and lives in the paged pool: a dense per-slot cache is "
                "not available with it (kv='auto' could not page here)")
        # decode bucketing (runtime/decode_buckets.py): the dense pool is
        # allocated at the smallest ladder bucket covering the longest
        # LIVE position and grown bucket-by-bucket as sequences advance,
        # so decode bytes/step track the pool's live context instead of
        # the max_len allocation. Opt-in (`decode_buckets=True` for the
        # power-of-two ladder, or an explicit ascending tuple): a
        # bucketed pool compiles its three programs once PER LIVE BUCKET
        # — a bounded relaxation of the three-program contract.
        self._buckets = None
        self._cache_len = self.max_len
        if decode_buckets:
            if self._paged:
                raise ValueError(
                    "decode_buckets applies to the dense per-slot cache; "
                    "the paged pool is already length-proportional "
                    "(blocks held track actual request length)")
            from dnn_tpu.runtime.decode_buckets import (
                bucket_ladder, normalize_ladder, pad_cache_to,
            )

            self._buckets = (bucket_ladder(self.max_len)
                             if decode_buckets is True
                             else normalize_ladder(decode_buckets,
                                                   self.max_len))
            self._cache_len = self._buckets[0]
            # no donation: a pad's output never fits the input buffer
            self._grow_cache = jax.jit(pad_cache_to, static_argnums=(1,))
        self._allocator = None
        self._paged_window = None
        self._window_kinds = {}
        self._kind_tables = [k["tables"]
                             for k in (self._cache_kinds or {}).values()
                             if k["tables"] is not None]
        if self._paged:
            fam_window = getattr(self.family, "window", None)
            if (getattr(self.family, "softcap", None) is not None
                    or getattr(self.family, "alt_window", False)):
                raise ValueError(
                    "softcapped / alternating-window families are not "
                    "supported with the paged pool (PagedKV has no "
                    "softcap or per-layer window channel; use the dense "
                    "per-slot cache)")
            if fam_window is not None and prefix_cache > 0:
                raise ValueError(
                    "windowed paged pools do not compose with the prefix "
                    "cache: rolled-out blocks are reclaimed mid-request, "
                    "which would free blocks a prefix entry still shares "
                    "— serve windowed families with prefix_cache=0")
            self._paged_window = fam_window
            from dnn_tpu.runtime.paged_kvcache import (
                BlockAllocator, PagedKV, cache_head_dim, init_paged_cache,
            )

            if self.max_len % block_len:
                raise ValueError(
                    f"max_len {self.max_len} must tile block_len "
                    f"{block_len}")
            if self.prompt_pad % block_len:
                raise ValueError(
                    f"prompt_pad {self.prompt_pad} must tile block_len "
                    f"{block_len} (prefill rows install whole blocks)")
            # pool head width follows the FAMILY's cache (GQA families
            # store KV heads — llama.LlamaFamilyRows sets kv_heads)
            # a window kind's slot holds its window's blocks whatever its
            # length: its leaves are sized for every slot's, and no more
            self._window_kinds = {
                k["tables"]: k["window"]
                for k in (self._cache_kinds or {}).values()
                if k["window"] is not None}
            if "tables" in self._window_kinds:
                raise ValueError("the first layer kind keeps every "
                                 "position: a window kind comes after it")
            kind_blocks = {
                t: slots * min(window_blocks(w, block_len),
                               self.max_len // block_len) + 1
                for t, w in self._window_kinds.items()}
            self.cache = init_paged_cache(
                cfg, slots, self.max_len,
                n_blocks={"tables": paged_blocks, **kind_blocks}
                if self._cache_kinds else paged_blocks,
                block_len=block_len, dtype=cache_dtype,
                kv_heads=getattr(self.family, "kv_heads", None),
                leaves=getattr(self.family, "cache_leaves", None),
                kinds=self._cache_kinds)
            self._allocator = BlockAllocator(paged_blocks, kinds=kind_blocks)
            self._block_len = block_len
            # table entries of window kinds to set before the next
            # dispatch: (tables name, slot, logical block, physical)
            self._wtab_pending: list = []
            self.window_blocks_freed = 0
            self.window_table_flushes = 0  # launches of `_set_tables`

            def set_tables(tab, slot_ix, blk_ix, vals):
                return tab.at[:, slot_ix, blk_ix].set(vals, mode="drop")

            self._set_tables = jax.jit(set_tables, donate_argnums=(0,))
            # the family's attn_kernel policy routes paged decode through
            # the fused flash-decode kernel (paged_decode_attention): the
            # "auto" ladder rung for block pools — TPU + long slots
            # stream table-chased blocks, everything else stays on the
            # gather_view einsum (PagedKV._kernel_on)
            codec = PagedKV(block_len, window=fam_window,
                            use_kernel=getattr(self.family, "attn_kernel",
                                               False),
                            kinds=self._cache_kinds)
            # says, once a decode program is traced, what a group of the
            # paged decode kernel covers (`kernel_spans`)
            self._paged_codec = codec

            head_dim = cache_head_dim(cfg)  # init_paged_cache's

            def gather_row(cache, ids_row):
                """Rebuild a transient prefill row from pool blocks (the
                prefix-hit path: remaining chunks attend the shared
                prefix through this row). Junk beyond the prefix is never
                attended (chunk attention masks at its positions).
                Rank-agnostic: K/V blocks (…, bp, D) and int8 scale
                blocks (…, bp) alike."""
                out = {}
                for kk in cache:
                    if is_tables(kk):
                        continue
                    g = jnp.take(cache[kk], ids_row, axis=1)
                    if g.ndim == 5:  # K/V: drop the pool's lane padding
                        g = g[..., :head_dim]
                    l_, nb, h, bl = g.shape[:4]  # (L, nb_max, H, bp[, D])
                    rest = g.shape[4:]
                    r = jnp.moveaxis(g, 1, 2).reshape(l_, h, nb * bl, *rest)
                    pad = self._row_len - nb * bl
                    if pad:
                        r = jnp.pad(r, [(0, 0), (0, 0), (0, pad)]
                                    + [(0, 0)] * len(rest))
                    out[kk] = r[:, None]  # (L, 1, H, row_len[, D])
                return out

            self._gather_row = jax.jit(gather_row)
        else:
            self.cache = self.family.init_cache(slots, self._cache_len,
                                                cache_dtype)
            use_k = getattr(self.family, "attn_kernel", False)
            if self._buckets is not None and use_k == "auto":
                # bucketing IS the length-aware path: letting "auto"
                # switch einsum -> kernel when the pool grows past
                # AUTO_KERNEL_MIN_S would change attention
                # implementations mid-stream and break the bucketed==
                # unbucketed token-identity contract
                use_k = False
            # a pool of state leaves alone has no K and V for a codec
            codec = None if nothing_to_page else codec_for_cache(
                self.cache,
                use_kernel=use_k,
                window=getattr(self.family, "window", None),
                softcap=getattr(self.family, "softcap", None))
        if self._slot_leaves:
            # the `state_pool.*` counters' units: every slot's state
            # leaves, and a position's K and V in one full layer
            self._state_step_bytes = sum(
                self.cache[n].nbytes for n in self._slot_leaves)
            self._kv_position_bytes = sum(
                heads * width * self.cache[n].dtype.itemsize
                for n, (heads, width) in (full["leaves"] if full
                                          else {}).items())
            # a strided leaf's rows, read whole by a step that selects: a
            # row every `stride` positions
            self._strided_position_bytes = sum(
                heads * width * self.cache[n].dtype.itemsize / stride
                for n, (heads, width, stride) in (
                    full.get("strided_leaves", {}) if full else {}).items())
        self.pos = jnp.zeros((slots,), jnp.int32)      # next write position
        self.tok = jnp.zeros((slots,), jnp.int32)      # last sampled token
        self.active = jnp.zeros((slots,), bool)
        # per-slot rng keys: each request's stream derives from
        # (server seed, request id) alone — pool-independent sampling
        self.keys = jnp.zeros((slots, 2), jnp.uint32)
        # per-slot sampling parameters (set at submit; plain dynamic args
        # of the one decode program — no recompiles across mixes)
        self._temp = jnp.zeros((slots,), jnp.float32)
        self._topk = jnp.zeros((slots,), jnp.int32)
        self._topp = jnp.zeros((slots,), jnp.float32)
        self._minp = jnp.zeros((slots,), jnp.float32)
        self._rep = jnp.ones((slots,), jnp.float32)  # 1.0 = no penalty
        # per-slot vocabulary seen-mask for the repetition penalty: prompt
        # tokens scatter in at submit, each committed token per step.
        # slots x V bools — trivial next to one block of K/V
        self._seen = jnp.zeros((slots, cfg.vocab_size), bool)
        # per-slot additive logit bias (OpenAI-style force/ban) — a
        # CONSTRUCTION-time capability like logprobs_k: the dense
        # (slots, V) buffer and its per-step add only exist when
        # allow_logit_bias=True (at large-vocab, many-slot servers the
        # buffer alone is tens of MB), so the default programs/memory
        # are unchanged. The LM daemon enables it (its clients choose
        # options per request).
        self._allow_user_bias = bool(allow_logit_bias)
        self._allow_constraints = bool(allow_constraints)
        self._allow_bias = self._allow_user_bias
        self._bias = (jnp.zeros((slots, cfg.vocab_size), jnp.float32)
                      if self._allow_bias
                      else jnp.zeros((slots, 0), jnp.float32))
        # constrained decoding (runtime/constrain.TokenConstraint) rides
        # DEVICE-RESIDENT table pools: each grammar uploads ONCE into
        #   * `_ctable` (S, W) uint32 BIT-PACKED mask rows, W =
        #     constrain.mask_words(V) — the decode program reads one row
        #     a slot and unpacks it to ban off-grammar logits
        #     (`_mask_rows`; row 0 reserved all-ones = unconstrained), and
        #   * `_ctrans` (S, V) int32 next-state rows in GLOBAL pool
        #     coordinates — the DFA walk itself, so the decode program
        #     advances each slot's state `crow' = ctrans[crow, sampled]`
        #     in the same dispatch that sampled the token (row 0 all-zero
        #     = the unconstrained self-loop).
        # The per-slot state vector `_crow` is CARRIED DEVICE STATE,
        # donated through the step exactly like pos/tok/keys — there is
        # NO per-step host->device constraint traffic at all, which is
        # what lets constrained requests ride the interleaved/overlap
        # hot path (the host still mirrors the walk per committed token
        # for finish detection, off the dispatch critical path).
        # `constraint_rows` bounds both pools (bytes: rows x W x 4, about
        # rows x vocab / 8, packed + rows x vocab x 4 int32 — 1024 x
        # 50257 ≈ 7 + 206 MB); a step touches `slots` rows of the mask
        # pool and `slots` words of the other. Entries are refcounted by
        # live slots, evicted LRU when unreferenced.
        self._ctab_rows = int(constraint_rows) if self._allow_constraints \
            else 0
        if self._allow_constraints:
            if self._ctab_rows < 2:
                raise ValueError(
                    f"constraint_rows must be >= 2, got {constraint_rows}")
            self._ctable = jnp.full(
                (self._ctab_rows, mask_words(cfg.vocab_size)),
                0xFFFFFFFF, jnp.uint32)
            self._ctrans = jnp.zeros(
                (self._ctab_rows, cfg.vocab_size), jnp.int32)
            from collections import OrderedDict as _OD

            # id(constraint) -> {"off", "n", "refs", "c"} in LRU order
            self._ctab_entries: dict = _OD()
        else:
            self._ctable = jnp.ones((1, 0), jnp.bool_)
            self._ctrans = jnp.zeros((1, 0), jnp.int32)
        self._crow = jnp.zeros((slots,), jnp.int32)

        # host bookkeeping
        self._next_rid = 0
        self._slot_req: List[Optional[dict]] = [None] * slots
        # observability (dnn_tpu/obs): windowed tokens/sec for the
        # serving.tokens_per_sec gauge; all per-step bookkeeping below is
        # gated on obs.metrics() so DNN_TPU_OBS=off costs one None check
        self._tps = Throughput()
        self._bucket_keys: Dict[int, str] = {}
        # live goodput accounting (obs/goodput.GoodputTracker): fed from
        # the same obs-gated blocks as the series above, so it costs one
        # attribute read when unset and nothing when the gate is off.
        # Set post-construction (`pool.goodput = tracker`) — LMServer
        # auto-builds one from its model config.
        self.goodput = None
        # step-timeline attribution (obs/timeline.StepClock): splits
        # every decode step into named phases (admit/host/dispatch/
        # wait/commit/obs) for the /stepz endpoint and the whole-window
        # totals on /metrics the chip benchmark reads. Attached post-construction like
        # goodput (`pool.step_clock = StepClock().install()` — LMServer
        # auto-builds one); unset it costs one attribute read per step,
        # and the clock itself gates on DNN_TPU_OBS (begin() returns
        # None when off).
        self.step_clock = None
        # a family whose MLP is a mixture of experts counts what its
        # expert layers cost (LlamaFamilyRows.moe_stats): the step and
        # chunk programs then return an int32 (N_STATS,) beside the tokens
        # they already hand back, noted here in dispatch order and given
        # to the StepClock once a token of the same or a later dispatch
        # is on the host (_moe_flush) — never a device wait of its own
        self._moe_stats = bool(getattr(self.family, "moe_stats", False))
        self._moe_pending: deque = deque(maxlen=4096)
        # live slots holding a grammar constraint — pushed to the
        # StepClock's constrained_slots gauge at admit/retire (one attr
        # store per transition, nothing per step)
        self._n_constrained = 0
        # scrape-time callable gauges, (re-)registered with every bulk
        # update below: the most recently ACTIVE pool owns the series —
        # a once-only registration would let a dead pool keep reporting,
        # and would never recover from a registry clear(). WEAKLY bound:
        # the process-global registry must not pin a closed pool (and
        # its slots x max_len KV cache) for the process lifetime — a
        # collected pool's gauges read 0, which is what "no pool" means.
        import weakref

        pool_ref = weakref.ref(self)

        def _weak_gauge(method_name, *args):
            def read():
                pool = pool_ref()
                return getattr(pool, method_name)(*args) \
                    if pool is not None else 0.0
            return read

        self._obs_gauges = {
            "serving.tokens_per_sec": _weak_gauge("_tps_read"),
            "serving.batch_occupancy": _weak_gauge("_occupancy_read"),
            "serving.kv_slot_utilization": _weak_gauge("_kv_util_read"),
            # memory watermarks (obs/mem.py naming): "how close did the
            # pool come to full" survives the burst that set it
            "serving.kv_live_positions_high_water":
                _weak_gauge("_kv_live_hw_read"),
            "serving.active_slots_high_water":
                _weak_gauge("_active_hw_read"),
            # allocated KV bytes, QUANTIZATION-AWARE: int8 payloads price
            # at 1 byte/element and int4 at their packed HALF byte (plus
            # the f32 scale leaves, which ride the same pytree) — an
            # itemsize walk would overstate an int4 pool 2x
            # (obs/mem.logical_nbytes owns the dtype pricing)
            "serving.kv_cache_bytes": _weak_gauge("_kv_bytes_read"),
            # the step loop's pipeline (see `steps_pipelined` below),
            # beside the clock's `step.steps_total`
            "step.pipelined_total": _weak_gauge("_pipelined_read"),
            "step.stale_rows_total": _weak_gauge("_stale_rows_read"),
        }
        self._kv_live_hw = 0
        self._active_hw = 0
        # step-obs accumulator (see _obs_flush): the per-step registry
        # bulk (lock + counter updates + reservoir + gauge check) was
        # the largest part of what observability adds to a step, so steps
        # batch into plain fields and land every _OBS_FLUSH_STEPS
        # steps, on a bucket switch, or when the pool goes idle (end
        # of every drain — tests and scrapes that look after traffic
        # see exact totals). Producer-thread only, no locks.
        self._obs_acc_steps = 0
        self._obs_acc_tokens = 0
        self._obs_acc_bk: Optional[str] = None
        self._obs_acc_samples: list = []
        self._pool_exhausted_episode = False  # latch: one flight event /
        # counter tick per shortage episode, cleared when blocks return
        # to the pool (retire/cancel/window reclaim) or a paged admission
        # succeeds — NOT only on re-admission of the held request, which
        # never happens if its caller deadline-cancels while held
        if self._paged:
            self._obs_gauges.update({
                "serving.paged_blocks_used": _weak_gauge("_paged_used_read"),
                "serving.paged_blocks_free": _weak_gauge("_paged_free_read"),
                "serving.paged_blocks_high_water":
                    _weak_gauge("_paged_hw_read"),
            })
        if self._paged and self._cache_kinds:
            # the pool BY LAYER KIND: blocks a kind holds now, and what
            # its window kinds handed back while their requests ran
            for kind, k in self._cache_kinds.items():
                if k["tables"] is None:
                    continue  # slot leaves alone: the kind holds no blocks
                self._obs_gauges[labeled(
                    "kv_pool.blocks_in_use", kind=kind)] = _weak_gauge(
                        "_kind_used_read", k["tables"])
            self._obs_gauges["kv_pool.window_blocks_freed_total"] = \
                _weak_gauge("_window_freed_read")
            self._obs_gauges["kv_pool.window_table_flushes_total"] = \
                _weak_gauge("_window_flushes_read")
        self.results: Dict[int, np.ndarray] = {}
        self.finish_reasons: Dict[int, str] = {}
        self.token_logprobs: Dict[int, dict] = {}

        # prefix cache (`prefix_cache` = capacity; 0 disables). Two
        # implementations by cache layout:
        #   * DENSE pools: the legacy exact-prefix LRU (OrderedDict
        #     keyed on the token bytes of every completed full-chunk
        #     boundary; values are COPIES of the transient row — the
        #     live row is donated through the chunk loop);
        #   * PAGED pools: the RADIX prefix store (dnn_tpu/kvtier) — a
        #     trie over block_len token chunks mapped onto the shared
        #     BlockAllocator. Longest-prefix-match returns a run of
        #     refcounted physical blocks (copy-free sharing), mid-block
        #     divergence copy-on-writes ONLY the boundary block, and
        #     eviction is leaf-LRU under refcount protection; capacity
        #     counts resident BLOCKS. The store is also the fleet
        #     tier's substrate: stage_prefix/kvtier_export/kvtier_adopt
        #     below move its blocks between replicas (kvtier/migrate).
        # Either way: same compiled admission programs — hits/puts are
        # host bookkeeping + block-sized device work, never new shapes.
        # LoRA caveat (paged): radix entries are base-model KV and the
        # trie has no adapter axis, so adapted submissions on a PAGED
        # pool run UNCACHED (full prefill every time) — a documented
        # regression vs the removed per-adapter paged LRU; adapter-
        # heavy prefix workloads should serve dense pools, whose LRU
        # still keys by (adapter, tokens).
        from collections import OrderedDict

        self._prefix_store = None
        self._prefix_cache: "Optional[OrderedDict]" = None
        if prefix_cache > 0:
            if self._paged:
                from dnn_tpu.kvtier.store import PrefixStore

                self._prefix_store = PrefixStore(
                    self._allocator, self._block_len, prefix_cache)
            else:
                self._prefix_cache = OrderedDict()
        self._prefix_cap = prefix_cache
        self.prefix_hits = 0       # submissions that reused >= 1 chunk
        self.prefix_misses = 0     # lookups that reused nothing (the
        # denominator half serving.prefix_hits_total always lacked — a
        # hit counter alone can't say whether 100 hits is a hot cache
        # or a rounding error against 1e6 misses)
        self.prefix_evictions = 0
        self.prefill_chunks_run = 0  # chunk programs actually executed
        # scrape-time: the launches and the positions they held, pad
        # included, under the width the batcher launches (`prompt_pad`,
        # which the code may have chosen: `ridge_pad`)
        for what, each in (("launches", 1), ("positions", self.prompt_pad)):
            self._obs_gauges[labeled(
                f"serving.prefill_chunk_{what}_total",
                width=self.prompt_pad)] = _weak_gauge(
                    "_chunks_run_read", each)
        if self._prefix_cache is not None or self._prefix_store is not None:
            # scrape-time effectiveness ratio (ROADMAP item 2's metric):
            # hits / (hits + misses) over the pool's lifetime, weakly
            # bound like every pool gauge
            self._obs_gauges["dnn_tpu_prefix_hit_ratio"] = _weak_gauge(
                "_prefix_ratio_read")
        if self._prefix_store is not None:
            # KV-tier residency + cross-replica effectiveness: resident
            # radix blocks, and the fraction of block-granular hits
            # served from ADOPTED (migrated-in) blocks — the fleet
            # tier's whole point
            self._obs_gauges["dnn_tpu_kvtier_blocks"] = _weak_gauge(
                "_kvtier_blocks_read")
            self._obs_gauges["dnn_tpu_kvtier_remote_hit_ratio"] = \
                _weak_gauge("_kvtier_remote_ratio_read")
        # memory-economy observatory (obs/kvlens.py): reuse-distance
        # sampling + miss-ratio curves + block-lifetime forensics over
        # the radix store. Attached only when the obs gate is ON at
        # construction — a gate-off process pays exactly one
        # `lens is not None` check per store hook. The lens itself
        # re-checks the gate per call, so a runtime flip of the gate
        # stops recording immediately.
        self._kvlens = None
        if self._prefix_store is not None and obs.enabled():
            from dnn_tpu.obs.kvlens import KVLens

            try:
                per_block = int(self._kv_bytes_read()) // max(
                    1, self._allocator.n_blocks)
            except Exception:  # noqa: BLE001 — pricing is advisory
                per_block = 0
            # curve axis = the EFFECTIVE pool: with auto-sized
            # paged_blocks the allocator (minus the reserved null
            # block) can be smaller than the prefix_cache knob, and
            # the allocator is what actually bounds residency — a 1x
            # label pinned to the nominal knob would mis-scale every
            # multiplier
            eff_pool = min(int(prefix_cache),
                           self._allocator.n_blocks - 1)
            self._kvlens = KVLens(
                eff_pool, self._block_len, seed=seed,
                bytes_per_block=per_block)
            self._prefix_store.lens = self._kvlens
            # curve + thrash as weak scrape-time gauges next to the
            # kvtier residency pair (fleet.py rolls these up per stage)
            self._obs_gauges.update(self._kvlens.prom_gauges())

        logprobs_k = self._logprobs_k

        def _lp_outputs(logits, chosen):
            """(chosen logprob (B,), top-k logprobs (B, K), ids (B, K))
            from the step's logits — only compiled in when the server was
            constructed with logprobs_k > 0."""
            lsm = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
            chosen_lp = jnp.take_along_axis(lsm, chosen[:, None], axis=-1)[:, 0]
            top_lp, top_ids = lax.top_k(lsm, logprobs_k)
            return chosen_lp, top_lp, top_ids.astype(jnp.int32)

        def _decode_core(prepared, cache, pos, tok, active, keys,
                         temp, tk, tp, mp, rep, seen, bias, crow, ctable,
                         ctrans):
            """Advance every active slot one token (per-slot sampling
            parameters — see _sample_rows; `rep`/`seen` drive the
            repetition penalty, `mp` the min-p cutoff, `bias` (B, V) the
            per-slot additive logit bias, `crow` (B,) the per-slot
            constraint-table row index into the device-resident
            bit-packed mask pool `ctable` — row 0 is the reserved
            all-allowed row, so unconstrained slots add nothing). The
            grammar walk
            happens HERE too: `ctrans` holds each grammar's next-state
            rows in global pool coordinates, so the step returns
            `crow' = ctrans[crow, sampled]` as donated carried state —
            no host sync between steps, which is what admits
            constrained requests to the interleaved/overlap hot path.
            Shared by the plain decode step and the MIXED step (decode
            + one interleaved prefill chunk in the same compiled
            program), so the two paths' decode math is identical by
            construction — the mixed==convoy token-parity contract."""
            if self._moe_stats:
                logits, new_cache, moe = self.family.decode_rows(
                    prepared, cache, tok, pos, active, codec, moe_stats=True)
            else:
                logits, new_cache = self.family.decode_rows(
                    prepared, cache, tok, pos, active, codec)
            # `sample` names the sampling tail on a device trace (the
            # scopes of chipbench/spans.py; the model's own are gpt.*)
            with jax.named_scope("sample"):
                # repetition penalty on raw logits (HF order: before the
                # temperature/filters inside _sample_rows); rows at the
                # neutral 1.0 pass through bit-identically. ONE formula for
                # solo and pool paths: generate.apply_repetition_penalty
                b = logits.shape[0]
                rp_on = rep != 1.0
                lg = apply_repetition_penalty(
                    logits, rp_on[:, None] & seen, rep[:, None])
                if self._allow_bias:
                    lg = lg + bias
                if self._allow_constraints:
                    lg = jnp.where(_mask_rows(ctable, crow, lg.shape[-1]),
                                   lg, _NEG_BIG)
                # advance each slot's own stream; sample each row with its key
                split = jax.vmap(jax.random.split)(keys)  # (B, 2, 2)
                new_keys, subs = split[:, 0], split[:, 1]
                # inactive slots sample greedy (result discarded below): a
                # RETIRED sampled request's stale temperature must not keep
                # an otherwise-greedy pool on the filtered-sampling branch
                nxt = _sample_rows(lg, subs,
                                   temperature=jnp.where(active, temp, 0.0),
                                   top_k=tk, top_p=tp, min_p=mp)
                nxt = jnp.where(active, nxt, tok)
                new_keys = jnp.where(active[:, None], new_keys, keys)
                seen_upd = seen.at[jnp.arange(b), nxt].set(True)
                new_seen = jnp.where(active[:, None], seen_upd, seen)
                if self._allow_constraints:
                    # device DFA walk: self-loop closure (trans_table) makes
                    # the gather total over masked-off tokens AND eos, so a
                    # stale overlap step replays to the same state
                    new_crow = jnp.where(active, ctrans[crow, nxt], crow)
                else:
                    new_crow = crow
            out = (new_cache, pos + active.astype(jnp.int32), nxt, new_keys,
                   new_seen, new_crow)
            if logprobs_k:
                # logprobs report the MODEL's distribution (pre-penalty,
                # pre-temperature — the usual serving-API convention)
                out += _lp_outputs(logits, nxt)
            if self._moe_stats:
                out += (moe,)  # after the optional logprobs
            if self._overlap:
                # the step's tokens once more, as a result that is never
                # fed back and so never donated: what the pipeline's commit
                # reads a dispatch later, when `nxt`'s own buffer has gone
                # into the next step (the compiler gives a result that
                # appears twice a buffer each). Last of the core's results.
                out += (nxt,)
            return out

        def decode_step(prepared, cache, pos, tok, active, keys,
                        temp, tk, tp, mp, rep, seen, bias, crow, ctable,
                        ctrans):
            return _decode_core(prepared, cache, pos, tok, active, keys,
                                temp, tk, tp, mp, rep, seen, bias, crow,
                                ctable, ctrans)

        def mixed_step(prepared, pf_prepared, cache, pos, tok, active,
                       keys, temp, tk, tp, mp, rep, seen, bias, crow,
                       ctable, ctrans, row, chunk, chunk_start):
            """One INTERLEAVED step (ISSUE 12): the decode leg advances
            every active slot exactly as decode_step, and the same
            compiled program folds one prompt chunk of an admitting
            request into its transient row cache — admission rides the
            decode cadence instead of convoying it behind a separate
            prefill program. The legs touch disjoint buffers (pool
            cache vs transient row), so the decode math — and every
            slot's token stream — is bit-identical to the convoy path.
            `pf_prepared` is the admitting request's prefill param view
            (its LoRA adapter when multi-LoRA is on; the same tree as
            `prepared` otherwise)."""
            out = _decode_core(prepared, cache, pos, tok, active, keys,
                               temp, tk, tp, mp, rep, seen, bias, crow,
                               ctable, ctrans)
            # (pf_hidden, new_row) and, for an MoE family, the chunk's
            # expert-layer stats last
            return out + prefill_chunk(pf_prepared, row, chunk, chunk_start)

        def prefill_chunk(prepared, row, chunk, chunk_start, n_real=None):
            """One (1, prompt_pad) chunk of a prompt into the slot-row
            cache at positions [chunk_start, chunk_start+P). Long prompts
            loop this (full chunks + one padded tail) — ONE compiled
            program for any prompt length. Pad positions in the tail write
            K/V that the per-row position mask never attends; a family
            that keeps a STATE (`takes_n_real`, models/kda.py) has no mask
            to hide them behind and is handed `n_real`, the count of the
            chunk's real positions (a traced scalar: still one program),
            to leave state and tail untouched by the rest. Returns
            (hidden (1, P, C) float32, row): the program stops at the
            last block, and the finish applies the head to the one row
            that is sampled. An MoE family returns its expert layers'
            stats as a third result."""
            kw = {} if n_real is None else {"n_real": n_real}
            if self._moe_stats:
                return self.family.prefill(prepared, chunk, row, chunk_start,
                                           moe_stats=True, **kw)
            return self.family.prefill(prepared, chunk, row, chunk_start,
                                       **kw)

        def prefill_finish(cache, pos, tok, active, keys, temp_v, tk_v,
                           tp_v, mp_v, rep_v, seen, bias_buf, crow,
                           row, hidden, head_leaves, ints, floats,
                           seen_row, b_row, blocks, ctable, ctrans):
            """FINISH AND INSTALL, the one program that ends every
            admission (convoy, interleaved, `prefilled=` adoption, a
            radix hit; the speculative finish wraps it): apply the
            family's head to the final chunk's true-last HIDDEN row (the
            one head matmul of an admission, on one row), sample the
            first token from it, install the finished row cache into the
            slot, and set EVERY per-slot state vector — nothing of an
            admission is left for Python to scatter. The first thirteen
            arguments are the batcher's state (`_slot_state`), donated
            and handed back in the same order; `hidden` (1, P, C) is the
            last chunk's output and `head_leaves` what the family's head
            reads (`family.head_leaves` of the admitting request's param
            view, so a LoRA'd head stays right; not donated); the request
            arrives as two host arrays:

              ints (7,) int32: slot, last_local (the true last prompt
                row within `hidden`), prompt_len, top_k, c_row (this
                request's start-state row in the constraint mask pool, 0
                = unconstrained, so the FIRST token obeys the grammar
                too and `crow[slot] = ctrans[c_row, first]` seeds the
                device walk), and the two uint32 words that name its rng
                stream (bit patterns): the namespace and the request
                seed or id;
              floats (4,) float32: temperature, top_p, min_p and the
                repetition penalty.

            The request's private stream is derived HERE, from (server
            seed, namespace, request seed) — independent of what else is
            in the pool or when this arrived; the namespace fold keeps
            auto-assigned rids and explicit seeds from colliding (rid=3
            vs seed=3 must be distinct streams). One half samples the
            first token, the other is the slot's key from then on.
            `seen_row` (V,) marks the prompt's tokens so the repetition
            penalty applies to the FIRST sample too. `blocks` (2, nb_max)
            (paged mode; (2, 0) for a dense pool): the slot's table row,
            and the per-logical-block physical install targets — shared
            prefix blocks routed to junk block 0. A pool whose leaves are
            by layer kind has two such rows a kind (a window kind's: the
            blocks of its window at the prompt's end, junk elsewhere)."""
            slot, last_local, prompt_len, k, c_row = (
                ints[i] for i in range(5))
            words = lax.bitcast_convert_type(ints[5:7], jnp.uint32)
            t, p, mp_, rp = (floats[i] for i in range(4))
            rng, slot_key = jax.random.split(jax.random.fold_in(
                jax.random.fold_in(jax.random.PRNGKey(self._seed), words[0]),
                words[1]))
            lg = self.family.head(head_leaves, lax.dynamic_slice_in_dim(
                hidden, last_local, 1, axis=1))[:, 0]  # (1, 1, C) -> (1, V)
            with jax.named_scope("sample"):
                raw = lg
                lg = apply_repetition_penalty(
                    lg, (rp != 1.0) & seen_row[None, :], rp)
                if self._allow_bias:
                    lg = lg + b_row[None, :]
                if self._allow_constraints:
                    lg = jnp.where(
                        _mask_rows(ctable, c_row[None], lg.shape[-1]),
                        lg, _NEG_BIG)
                first = _sample_rows(
                    lg, rng[None], temperature=t[None], top_k=k[None],
                    top_p=p[None], min_p=mp_[None],
                )[0]
            # the row cache is chunk-rounded (possibly > the pool); only
            # the pool's own position count installs — the overhang holds
            # nothing but tail-pad garbage (real prompt tokens always fit:
            # submit() bounds the prompt by max_len and, on a bucketed
            # pool, grows the pool past the prompt before finishing)
            if self._paged and self._cache_kinds:
                # two rows of `blocks` a kind, in `_kind_tables`' order
                names = self._kind_tables
                cache = codec.install_row(cache, row, {
                    name: blocks[2 * i + 1] for i, name in enumerate(names)})
                for i, name in enumerate(names):
                    cache[name] = cache[name].at[:, slot].set(blocks[2 * i])
            elif self._paged:
                cache = codec.install_row(cache, row, blocks[1])
                cache["tables"] = cache["tables"].at[:, slot].set(blocks[0])
            else:
                cache = {**cache, **install_dense_row(
                    {kk: cache[kk] for kk in cache
                     if kk not in self._slot_leaves}, row, slot)}
            with jax.named_scope("state_pool.install"):
                # the row's RUNNING state after the prompt's last real
                # position becomes the slot's: what resets a slot
                for name in self._slot_leaves:
                    cache[name] = cache[name].at[:, slot].set(
                        row[name][:, 0].astype(cache[name].dtype))
            pos = pos.at[slot].set(prompt_len)
            tok = tok.at[slot].set(first)
            active = active.at[slot].set(True)
            keys = keys.at[slot].set(slot_key)
            temp_v = temp_v.at[slot].set(t)
            tk_v = tk_v.at[slot].set(k)
            tp_v = tp_v.at[slot].set(p)
            mp_v = mp_v.at[slot].set(mp_)
            rep_v = rep_v.at[slot].set(rp)
            seen = seen.at[slot].set(seen_row.at[first].set(True))
            if self._allow_bias:
                bias_buf = bias_buf.at[slot].set(b_row)
            if self._allow_constraints:
                crow = crow.at[slot].set(ctrans[c_row, first])
            out = (cache, pos, tok, active, keys, temp_v, tk_v, tp_v, mp_v,
                   rep_v, seen, bias_buf, crow, first)
            if logprobs_k:
                # raw model distribution, as in decode_step
                out += _lp_outputs(raw, first[None])
            return out

        # the transient slot-row cache rounds max_len UP to whole chunks:
        # a tail chunk starting at (n_chunks-1)*prompt_pad must never have
        # its write clamped back onto real prompt positions (dynamic
        # updates clamp silently — an unrounded row corrupts the cache
        # whenever max_len % prompt_pad != 0)
        self._row_len = -(-self.max_len // self.prompt_pad) * self.prompt_pad

        def row_program(row_len):
            """A fresh transient row as ONE launch (eagerly it is a
            broadcast a leaf); a new buffer every call — the chunk loop
            donates the row."""
            def new_row():
                return self.family.init_cache(1, row_len, cache_dtype)
            return jax.jit(new_row)

        self._new_row = row_program(self._row_len)
        # donate the caches: without aliasing, every token would copy the
        # whole (L, B, H, S, D) cache (hundreds of MB of HBM traffic per
        # step at real sizes). The call sites reassign from the results,
        # so the donated inputs are never reused. Alongside the cache:
        # every per-slot state vector the step RETURNS (pos, tok, keys,
        # seen — and `crow` on constrained servers, where the DFA walk
        # makes it carried device state) — `active`, `bias`, `ctable`
        # and `ctrans` are read-only through the step (host-updated
        # between calls) and must NOT be donated; on UNconstrained
        # servers `crow` is a read-only pass-through too (the core
        # returns it untouched), so donating it would be an un-aliasable
        # copy. Full aliasing of every donated leaf is a standing
        # invariant, asserted statically by the analysis gate
        # (dnn_tpu/analysis/program.audit_serving_decode via
        # hlo_audit.count_aliased).
        self._decode_donate = (1, 2, 3, 5, 11) + (
            (13,) if self._allow_constraints else ())
        self._decode = jax.jit(decode_step,
                               donate_argnums=self._decode_donate)
        self._prefill_chunk = jax.jit(prefill_chunk, donate_argnums=(1,))
        # the finish donates the pool cache and every per-slot vector it
        # returns (`active` included — the finish RETURNS it, unlike the
        # decode step where it is host-updated between calls), the bias
        # buffer only when it is real and crow only on constrained
        # servers (elsewhere the finish returns them untouched — an
        # un-aliasable donation). The transient row is SLICED into the
        # pool, never returned whole: donating it would alias nothing.
        # The speculative variant composes its own finish from this core
        # (serving_spec.SpeculativeBatcher)
        self._finish_core = prefill_finish
        self._finish_donate = tuple(range(11)) + (
            (11,) if self._allow_bias else ()) + (
            (12,) if self._allow_constraints else ())
        self._prefill_finish = jax.jit(
            prefill_finish, donate_argnums=self._finish_donate)
        # the finish-shaped hidden array of an admission that ran no chunk
        # (a whole-prompt prefix hit, an adopted prefill): the stored
        # true-last hidden row in place, so the finish keeps its one shape
        self._row_hidden = jax.jit(
            lambda hr, at: jnp.zeros(
                (1, self.prompt_pad, hr.shape[-1]), hr.dtype
            ).at[0, at].set(hr))
        # what an admission passes for an absent logit_bias and, on a
        # dense pool, for the block ids — built once, not per request
        self._no_bias = jnp.zeros(
            (cfg.vocab_size if self._allow_bias else 0,), jnp.float32)
        self._no_blocks = np.zeros((2, 0), np.int32)

        # KV-tier device programs (dnn_tpu/kvtier) — only compiled-in
        # when the radix store is on:
        #   * _cow_copy: the copy-on-write boundary — duplicate ONE
        #     physical block (all leaves: K/V and, on quantized pools,
        #     their scale blocks) so a divergent request can extend a
        #     shared prefix mid-block without scribbling the original;
        #   * _kv_put_block: block-granular ingest for migration — one
        #     migrated block's leaves scattered at a physical id;
        #   * _kvtier_install: install a staged transient row into pool
        #     blocks WITHOUT a slot (stage_prefix: the prefill-replica
        #     half of block migration computes KV straight into the
        #     store; no finish, no sampling, no slot scatter). The row
        #     is sliced per block, never returned whole — only the pool
        #     cache donation is real (the prefill_finish lesson).
        self._cow_copy = None
        self._kv_put_block = None
        self._kvtier_install = None
        self._kv_get_block = None
        if self._prefix_store is not None:
            def cow_copy(cache, src, dst):
                out = {"tables": cache["tables"]}
                for kk in cache:
                    if kk != "tables":
                        out[kk] = cache[kk].at[:, dst].set(
                            cache[kk][:, src])
                return out

            def kv_put_block(cache, vals, dst):
                out = {"tables": cache["tables"]}
                for kk in cache:
                    if kk != "tables":
                        out[kk] = cache[kk].at[:, dst].set(
                            vals[kk].astype(cache[kk].dtype))
                return out

            def kvtier_install(cache, row, install_ids):
                return codec.install_row(cache, row, install_ids)

            def kv_get_block(cache, idx):
                # read-only (no donation): one block's leaves, int4
                # widened to int8 values for the host trip
                out = {}
                for kk in cache:
                    if kk != "tables":
                        x = lax.dynamic_index_in_dim(
                            cache[kk], idx, axis=1, keepdims=False)
                        if x.dtype == jnp.int4:
                            x = x.astype(jnp.int8)
                        out[kk] = x
                return out

            self._cow_copy = jax.jit(cow_copy, donate_argnums=(0,))
            self._kv_put_block = jax.jit(kv_put_block,
                                         donate_argnums=(0,))
            self._kvtier_install = jax.jit(kvtier_install,
                                           donate_argnums=(0,))
            self._kv_get_block = jax.jit(kv_get_block)

        # --------------------------------------------------------------
        # overlap & fusion (ISSUE 12): interleaved chunked prefill + the
        # one-step double-buffered dispatch pipeline
        # --------------------------------------------------------------
        # prefill_chunk_tokens > 0 switches ADMISSION from the convoy
        # path (submit() runs the whole chunk loop + finish inline,
        # stalling every decode slot for the prefill's duration: the
        # `admit` share of PERF.md section 5) to the MIXED
        # step: submit() only validates, allocates and enqueues, and
        # each subsequent decode step folds ONE prompt chunk of that
        # width into the same compiled program. The fused finish then
        # installs the row, samples the first token ON DEVICE with the
        # request's own params/rng, and scatters the slot state — one
        # dispatch, no per-admit device->host sync (the first token's
        # readback rides the NEXT step's commit).
        self._ilv = int(prefill_chunk_tokens or 0)
        if self._ilv < 0:
            raise ValueError(
                f"prefill_chunk_tokens must be >= 0, got "
                f"{prefill_chunk_tokens}")
        if self._ilv:
            if self._ilv > self.max_len:
                raise ValueError(
                    f"prefill_chunk_tokens {self._ilv} exceeds max_len "
                    f"{self.max_len} — a chunk wider than the pool can "
                    "never install")
            if self._paged and self._ilv % self._block_len:
                raise ValueError(
                    f"prefill_chunk_tokens {self._ilv} must tile "
                    f"block_len {self._block_len} (prefill rows install "
                    "whole blocks)")
            # allow_constraints composes with interleaved admission
            # since the DFA walk moved on device: the fused finish masks
            # the first token with the grammar's start row and seeds the
            # slot's crow in-program — no admission-time host walk, no
            # sync. (It used to reject loud here.)
            if self._prefix_cache is not None \
                    or self._prefix_store is not None:
                raise ValueError(
                    "prefill_chunk_tokens does not compose with the "
                    "prefix cache (dense entries are keyed/shaped on "
                    "the convoy path's chunk geometry, and the radix "
                    "store's resume/COW/insert bookkeeping lives on "
                    "the convoy admission path) — prefix-heavy "
                    "workloads keep convoy admission")
        # overlap=True runs a ONE-STEP dispatch pipeline: step() DISPATCHES
        # step N and commits step N-1's tokens, so the host slot loop
        # (commit/obs, and the next admission's bookkeeping) runs while
        # the device executes step N. Tokens surface one step()
        # call later; drain()/flush_overlap() commit the trailing step.
        self._overlap = bool(overlap)
        # how often the pipeline engaged, and what it cost (scrape-time
        # `step.pipelined_total` / `step.stale_rows_total`): steps that
        # were dispatched while the step before them was uncommitted, and
        # slot-rows a dispatched step computed whose token no commit took
        # (the step went out past a retirement or a cancel: the one step
        # by which the pipeline learns of either late)
        self.steps_pipelined = 0
        self.stale_rows = 0
        # allow_constraints composes with the one-step pipeline since
        # the DFA walk moved on device: step N+1's mask row comes from
        # the crow that step N's program computed and carried — never
        # one state stale. The one garbage step dispatched past a
        # retirement replays through trans_table's self-loop closure
        # and is overwritten at commit, like tok/active.
        self._pending_q: List[int] = []   # slots awaiting interleaved
        # prefill, FIFO (one chunk folds per step)
        self._inflight = None             # overlap: the dispatched,
        # not-yet-committed step — (step_idx, token refs, logprob refs,
        # slot-rows live at its dispatch)
        self._step_idx = 0                # monotonically counts dispatches;
        # install_step gating keys off it (a slot's decode tokens exist
        # only for steps dispatched AFTER its fused finish)
        # interleaved transient rows round max_len up to whole chunks of
        # the INTERLEAVE width (same clamp-protection argument as
        # _row_len above)
        self._ilv_row_len = (-(-self.max_len // self._ilv) * self._ilv
                             if self._ilv else 0)
        self._ilv_new_row = (row_program(self._ilv_row_len)
                             if self._ilv else None)
        self._mixed = None
        if self._ilv:
            # donate the decode leg's state exactly as _decode does, plus
            # the prefill leg's transient row — audited like every other
            # decode program (analysis/program.audit_serving_decode)
            self._mixed_donate = (2, 3, 4, 6, 12, 17) + (
                (14,) if self._allow_constraints else ())
            self._mixed = jax.jit(mixed_step,
                                  donate_argnums=self._mixed_donate)

        # the decode step's param argument: a lora_view when multi-LoRA is
        # on (rebuilt whenever a slot's adapter assignment changes — same
        # structure, so the same compiled program), plain prepared when off
        self._decode_view = self._lora_prepared(self._aid)

    def step_loop(self) -> dict:
        """Which loop step() runs and why (/statusz `components.batcher`)."""
        if not self._overlap:
            return {"loop": "synchronous", "depth": 0,
                    "why": "overlap=False (a batcher built directly, or a "
                           "speculative one, which LMServer leaves its own "
                           "default): each step is read before the next is "
                           "dispatched"}
        return {"loop": "pipelined", "depth": 1,
                "why": "overlap=True (what LMServer asks of a dense "
                       "batcher): step N+1 is dispatched before step N is "
                       "read",
                "steps_pipelined": self.steps_pipelined,
                "stale_rows": self.stale_rows}

    def jit_programs(self):
        """The batcher's compiled entry points — what a long-lived server
        counts toward its compile-cache budget (lm_server's
        CompileCacheGuard). Variants with extra programs
        (SpeculativeBatcher) extend this."""
        fns = [self._decode, self._new_row, self._prefill_chunk,
               self._prefill_finish]
        if self._paged:
            fns.append(self._gather_row)
        if self._buckets is not None:
            fns.append(self._grow_cache)
        if self._mixed is not None:
            fns += [self._mixed, self._ilv_new_row]
        if self._prefix_store is not None:
            fns += [self._cow_copy, self._kv_put_block,
                    self._kvtier_install, self._kv_get_block]
        return fns

    # ------------------------------------------------------------------

    def _lora_prepared(self, aids):
        """Param view selecting each row's adapter (lora.lora_view);
        plain prepared when multi-LoRA is off. `aids` indexes the stacked
        adapter axis (0 = the all-zero base adapter)."""
        if self._lora is None:
            return self.prepared
        from dnn_tpu.lora import lora_view

        sel = jax.nn.one_hot(jnp.asarray(aids, jnp.int32),
                             self._n_adapters + 1, dtype=jnp.float32)
        return lora_view(self.prepared, self._lora, sel, transposed=True)

    def _lora_prefill_view(self, aid: int):
        """Memoized single-row prefill view for one adapter id — at most
        N+1 builds over the server's lifetime, then pure dict reuse."""
        if self._lora is None:
            return self.prepared
        view = self._pf_views.get(aid)
        if view is None:
            view = self._lora_prepared(np.asarray([aid], np.int32))
            self._pf_views[aid] = view
        return view

    # ------------------------------------------------------------------

    def free_slots(self) -> int:
        return sum(r is None for r in self._slot_req)

    @property
    def n_active(self) -> int:
        return sum(r is not None for r in self._slot_req)

    @_admit_span
    def submit(self, prompt, max_new_tokens: int,
               seed: Optional[int] = None, *,
               temperature: Optional[float] = None,
               top_k: Optional[int] = None,
               top_p: Optional[float] = None,
               min_p: Optional[float] = None,
               repetition_penalty: Optional[float] = None,
               logit_bias: Optional[dict] = None,
               stop: Optional[list] = None,
               logprobs: bool = False,
               adapter: Optional[int] = None,
               constraint=None, prefilled=None, trace=None) -> int:
        """Prefill `prompt` (1-D int array) into a free slot; returns the
        request id. The first token is sampled during prefill and counts
        toward max_new_tokens. `seed` names the request's private rng
        stream (default: the request id) — a seeded sampled request
        reproduces the same tokens regardless of pool contents or arrival
        order.

        Per-request options (None = the server constructor's defaults;
        the pool mixes them freely within the same compiled programs):
        `temperature` (0 = greedy), `top_k` (clamped to the static
        prefilter width, generate.TOP_P_PREFILTER_K), `top_p` (nucleus),
        `min_p` (drop tokens below min_p x the top probability),
        `repetition_penalty` (HF/CTRL semantics over this request's
        prompt + generated tokens, tracked in a per-slot seen-mask),
        `logit_bias` ({token_id: additive bias} — +big forces, -big
        bans, binding for greedy rows too);
        `stop` — list of token-id sequences: generation retires when the
        emitted stream ends with any of them, the result is trimmed to
        exclude the match, and `finish_reasons[rid]` records "stop"
        (vs "eos" / "length" — the reference has no stop mechanism at
        all, its one forward can't, node.py:137-200); `logprobs=True`
        records the chosen token's logprob and the top-k alternatives per
        step into `token_logprobs[rid]` (server must be constructed with
        logprobs_k > 0); `adapter` — index into the constructor's
        `lora_adapters` list (None = the base model): this request's
        prefill and every decode step apply that adapter's low-rank
        delta while other slots apply theirs; `constraint` — a
        runtime/constrain.TokenConstraint (compiled regex/JSON grammar):
        every emitted token is masked to the grammar's continuations,
        EOS is only reachable in accepting states, and when a match
        completes with no possible continuation the request retires with
        finish_reason "constraint" (server must be constructed with
        allow_constraints=True); `trace` — an obs span (dnn_tpu/obs) to
        parent this request's span tree under: submit records an "admit"
        span with a nested "prefill", and each step maintains a
        per-bucket "decode" span until the request retires. None (the
        default) skips all span work; metrics counters are recorded
        either way when observability is on.

        `prefilled` (disaggregated serving, dnn_tpu/control): a
        PREFILL replica's `export_prefill` payload — this request's
        transient row cache plus the final chunk's true-last hidden
        row. Admission then ADOPTS the handed-off KV instead of
        running the chunk loop: same slot install, same
        `_prefill_finish` program, same rng derivation, so tokens
        agree draw-for-draw with a locally-prefilled submission of the
        same seed. Requires matching geometry on both replicas (model
        config, max_len, prompt_pad, kv dtype — mismatches fail loud);
        rejects interleaved admission (`prefill_chunk_tokens` — the
        convoy install path IS the adoption path) and `adapter` (the
        exported row was computed against the prefill replica's base
        weights)."""
        # step-timeline: this submit's whole wall (validation, slot
        # install, prefill chunks, first-token sample) is the "admit"
        # phase, attached to the NEXT step's record in note_admit —
        # with the seconds of its prefill / first_token / install parts
        # (timeline.ADMIT_PARTS), stamped below where each begins and
        # ends; the rest of the wall is admission's own host time
        _sc = self.step_clock
        _t_sub = time.perf_counter() if _sc is not None else 0.0
        _parts = (0.0, 0.0, 0.0)
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if len(prompt) == 0:
            raise ValueError("prompt must have at least one token")
        if max_new_tokens < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1, got {max_new_tokens}")
        if len(prompt) + max_new_tokens > self.max_len:
            raise ValueError(
                f"prompt {len(prompt)} + max_new_tokens {max_new_tokens} "
                f"exceeds max_len {self.max_len}"
            )
        temp = self._default_temp if temperature is None else float(temperature)
        tk = self._default_topk if top_k is None else int(top_k)
        tp = self._default_topp if top_p is None else float(top_p)
        mp = self._default_minp if min_p is None else float(min_p)
        rp = (self._default_rep if repetition_penalty is None
              else float(repetition_penalty))
        if temp < 0:
            raise ValueError(f"temperature must be >= 0, got {temp}")
        if tk < 0:
            raise ValueError(f"top_k must be >= 0, got {tk}")
        if not 0.0 <= tp <= 1.0:
            raise ValueError(f"top_p must be in [0, 1], got {tp}")
        if not 0.0 <= mp <= 1.0:
            raise ValueError(f"min_p must be in [0, 1], got {mp}")
        if rp <= 0:
            raise ValueError(f"repetition_penalty must be > 0, got {rp}")
        if logit_bias and not self._allow_user_bias:
            raise ValueError(
                "logit_bias requires allow_logit_bias=True at construction "
                "(the per-slot bias buffer is a construction-time choice)")
        if constraint is not None:
            if not self._allow_constraints:
                raise ValueError(
                    "constraint= requires allow_constraints=True at "
                    "construction (the per-slot bias buffer is a "
                    "construction-time choice)")
            if not self._constraints_ok:
                raise ValueError(
                    "this batcher variant commits multiple tokens per "
                    "step and cannot honor per-token constraints")
            if constraint.vocab_size != self.cfg.vocab_size:
                raise ValueError(
                    f"constraint compiled for vocab "
                    f"{constraint.vocab_size} != model vocab "
                    f"{self.cfg.vocab_size}")
            if (self.eos_id is not None
                    and constraint.allowed[constraint.reachable,
                                           self.eos_id].any()):
                # the eos override in mask_row would ban a byte token the
                # grammar NEEDS (and an emitted one would retire as "eos"
                # mid-match) — fail fast instead of either wrong behavior.
                # Quantified over REACHABLE states only: multi-byte (BPE)
                # tokens can jump OVER byte-DFA states, leaving states no
                # token path ever enters — eos aliasing confined to those
                # is harmless. (On single-byte vocabs every state is token
                # -reachable and the quantifier changes nothing: a byte
                # vocab whose eos_id is a grammar-consumable byte is still
                # rejected — use an eos outside the grammar's alphabet,
                # e.g. below ByteTokenizer's offset.)
                raise ValueError(
                    f"eos_id {self.eos_id} maps to bytes this constraint's "
                    "grammar can consume; serve constrained requests with "
                    "a dedicated special token as eos")
        b_row = logit_bias_row(logit_bias, self.cfg.vocab_size)
        if b_row is None:
            b_row = self._no_bias
        c_off = None
        if constraint is not None:
            # a grammar matching ONLY the empty string is legal when eos
            # can express it (accepting start + eos override): the first
            # sample is forced to eos and the request retires with a
            # valid empty match
            if not (constraint.allowed[constraint.start].any()
                    or (self.eos_id is not None
                        and constraint.is_accepting(constraint.start))):
                raise ValueError(
                    "constraint permits no first token (empty language "
                    "over this vocab)")
            # upload the grammar's mask table once (pool hit = free);
            # the user's logit_bias rides self._bias unchanged — the
            # device composes bias + table row per step
            c_off = self._ctab_register(constraint)
        tk = min(tk, TOP_P_PREFILTER_K)
        stop_seqs = []
        for s in (stop or []):
            s = np.asarray(s, np.int32).reshape(-1)
            if len(s) == 0:
                raise ValueError("empty stop sequence")
            stop_seqs.append(s)
        if logprobs and not self._logprobs_k:
            raise ValueError(
                "logprobs requested but the server was constructed with "
                "logprobs_k=0")
        aid = 0
        if adapter is not None:
            if self._lora is None:
                raise ValueError(
                    "adapter= requires lora_adapters at construction")
            if not 0 <= int(adapter) < self._n_adapters:
                raise ValueError(
                    f"adapter {adapter} out of range "
                    f"[0, {self._n_adapters})")
            aid = int(adapter) + 1  # stack row 0 is the base model
        if prefilled is not None:
            if self._ilv:
                raise ValueError(
                    "prefilled= does not compose with "
                    "prefill_chunk_tokens: interleaved admission folds "
                    "chunks into decode steps — KV adoption rides the "
                    "convoy install path")
            if adapter is not None:
                raise ValueError(
                    "prefilled= does not compose with adapter=: the "
                    "handed-off row was computed against the prefill "
                    "replica's base weights")
        try:
            slot = self._slot_req.index(None)
        except ValueError:
            raise RuntimeError("no free slot; call step()/drain() first") from None

        # longest cached prefix (host lookup). K/V rows depend on the
        # WEIGHTS that produced them, so dense entries are keyed by
        # (adapter, tokens) and the paged RADIX store serves only
        # base-model requests (adapted submissions bypass it).
        p_pad = self.prompt_pad
        key_ns = np.int32(aid).tobytes()
        n_chunks = -(-len(prompt) // p_pad)
        hit_c, hit_entry = 0, None
        if self._prefix_cache is not None and prefilled is None:
            for c in range(len(prompt) // p_pad, 0, -1):
                e = self._prefix_cache.get(
                    key_ns + prompt[: c * p_pad].tobytes())
                if e is not None:
                    self._prefix_cache.move_to_end(
                        key_ns + prompt[: c * p_pad].tobytes())
                    hit_c, hit_entry = c, e
                    break
        # radix lookup (paged + kvtier store): longest block-aligned
        # run of resident blocks, plus the copy-on-write boundary — the
        # cached block whose first `cow_tokens` positions this prompt
        # still agrees with past the last full-block match
        kv_hit = None
        use_radix = (self._prefix_store is not None and prefilled is None
                     and aid == 0)
        if use_radix:
            kv_hit = self._prefix_store.lookup(prompt)

        paged_taken, blocks, n_shared = None, self._no_blocks, 0
        cow_src, cow_tok = -1, 0
        w_taken = {}
        if self._paged:
            from dnn_tpu.runtime.paged_kvcache import InsufficientBlocks

            # admission by ACTUAL length: this request holds
            # ceil((prompt + budget) / block_len) pool blocks for its
            # lifetime — a free slot alone is not enough. A prefix hit is
            # COPY-FREE: the entry's blocks are shared by reference
            # (refcounted), so only the tail is allocated.
            bp = self._block_len
            n_need = -(-(len(prompt) + max_new_tokens) // bp)
            if n_need > self._allocator.n_blocks - 1:
                # permanent: this request can NEVER fit the pool — fail it
                # (a transient InsufficientBlocks would wait forever)
                raise ValueError(
                    f"request needs {n_need} blocks but the pool only has "
                    f"{self._allocator.n_blocks - 1} allocatable")
            if kv_hit is not None:
                shared_ids = list(kv_hit.shared)[:n_need]
                if len(shared_ids) == len(kv_hit.shared):
                    cow_src, cow_tok = kv_hit.cow_src, kv_hit.cow_tokens
            else:
                # no radix store consulted (prefix_cache off, an
                # adapted request, or prefilled= adoption): paged
                # admission shares nothing — the dense LRU never
                # serves paged pools
                shared_ids = []
            n_shared = len(shared_ids)
            # ref the shared prefix (and the COW source) BEFORE any
            # eviction below can run: the hit entry itself may be
            # evicted while we hunt for tail blocks, and without our
            # reference its blocks could recycle into this very
            # allocation (aliasing the prefix)
            ref_ids = shared_ids + ([cow_src] if cow_tok > 0 else [])
            if ref_ids:
                self._allocator.ref(ref_ids)
            try:
                owned = self._allocator.alloc(n_need - n_shared)
                while owned is None and self._evictable_prefix():
                    # entry-pinned blocks must never starve admission
                    # (livelock: entries only evict on insertion, which
                    # needs a successful prefill): evict LRU entries until
                    # the tail fits. Entries whose blocks live slots still
                    # share free nothing (refcount) — keep evicting.
                    self._evict_prefix_entry()
                    owned = self._allocator.alloc(n_need - n_shared)
                if owned is None:
                    # ONE event/count per exhaustion episode, not per
                    # retry: the lm_server worker re-submits its held
                    # request every decode step, and a minutes-long
                    # shortage at ms cadence would otherwise flood the
                    # flight ring (evicting the post-mortem context it
                    # exists to keep) and turn the "admissions held
                    # back" counter into a retry counter
                    if not self._pool_exhausted_episode:
                        self._pool_exhausted_episode = True
                        m = obs.metrics()
                        if m is not None:
                            m.inc("serving.pool_exhausted_total")
                        obs.flight.record(
                            "pool_exhausted", need=n_need - n_shared,
                            free=self._allocator.n_free,
                            high_water=self._allocator.high_water)
                    raise InsufficientBlocks(
                        f"insufficient free cache blocks: need "
                        f"{n_need - n_shared}, have "
                        f"{self._allocator.n_free} "
                        f"(pool {self._allocator.n_blocks}, block {bp} "
                        f"pos)")
            except BaseException:
                if ref_ids:
                    self._allocator.free(ref_ids)
                raise
            # a window kind's blocks: those its window and the next write
            # touch at the prompt's end, drawn now so that admission by
            # actual length counts every kind
            w_taken = {}
            for t, w in self._window_kinds.items():
                lo = max(0, len(prompt) - w + 1) // bp
                n_w = min(n_need - lo, window_blocks(w, bp))
                ids = self._allocator.of(t).alloc(n_w)
                if ids is None:
                    for t2, got in w_taken.items():
                        self._allocator.of(t2).free(list(got.values()))
                    self._allocator.free(ref_ids + owned)
                    raise InsufficientBlocks(
                        f"insufficient free cache blocks of the kind under "
                        f"{t}: need {n_w}, have "
                        f"{self._allocator.of(t).n_free}")
                w_taken[t] = dict(zip(range(lo, lo + n_w), ids))
            self._pool_exhausted_episode = False  # blocks came free
            paged_taken = shared_ids + owned
            # the slot's table row, and under it the install targets:
            # both reach the device with the finish program, which writes
            # the row into the tables (nothing reads a slot's row before
            # its first decode step)
            blocks = np.zeros((2 * max(1, len(self._kind_tables)),
                               self.cache["tables"].shape[-1]), np.int32)
            blocks[:2, :n_need] = paged_taken
            for t, got in w_taken.items():
                i = self._kind_tables.index(t)
                for j, b in got.items():
                    blocks[2 * i:2 * i + 2, j] = b
            if cow_tok > 0:
                # copy-on-write at the divergence boundary: duplicate
                # the ONE cached block this prompt still partially
                # agrees with into this request's first owned block
                # (logical index n_shared); prefill then resumes
                # MID-BLOCK after the agreed tokens instead of
                # recomputing the whole block. The original stays
                # intact for its own holders — the temporary reference
                # taken above kept it alive through the eviction hunt,
                # and is dropped now that the copy is enqueued
                # (in-order backends run the copy before any later
                # write could recycle the source).
                try:
                    self.cache = self._cow_copy(
                        self.cache, np.int32(cow_src), np.int32(owned[0]))
                finally:
                    # the temporary reference drops either way — a
                    # failed dispatch must not strand the source block
                    self._allocator.free([cow_src])
            # install must NOT touch shared blocks (another request's live
            # prefix): their install targets are routed to junk block 0
            blocks[1, :n_shared] = 0
            if kv_hit is not None and (n_shared or cow_tok):
                # admission HOLDS the blocks now — record the reuse
                # (post-truncation, post-allocation: the hit ratio
                # must never count blocks the request didn't actually
                # get)
                self._prefix_store.note_reuse(
                    n_shared + (1 if cow_tok > 0 else 0),
                    kv_hit.remote_used(n_shared, cow_tok > 0),
                    cow=cow_tok > 0)

        if self._buckets is not None:
            # the installed prompt must fit the pool AND the first decode
            # write (at position len(prompt)) must have a column
            self._ensure_cache_len(len(prompt) + 1)

        # span tree (only when the caller passed a trace handle): "admit"
        # covers slot install end-to-end, "prefill" the device work inside
        adm = trace.child("admit", slot=slot, prompt_len=len(prompt)) \
            if trace else obs.NULL_SPAN
        try:
            rid = self._next_rid
            self._next_rid += 1
            # what names this request's private rng stream (derived in
            # the finish program): the namespace — 0: the auto-assigned
            # rid, 1: an explicit seed — and the rid or seed; a seed that
            # is no uint32 fails here, before any prefill
            stream = np.asarray((0, rid) if seed is None else (1, seed),
                                np.uint32)

            def finish_request(last_local):
                """The request as the finish program takes it
                (prefill_finish documents the fields): the head's leaves
                under this request's adapter, its numbers as TWO host
                arrays, the prompt's seen-mask, the bias row and the
                block ids — the same for both admit paths, so greedy AND
                sampled streams agree token-for-token across them."""
                ints = np.empty((7,), np.int32)
                ints[:5] = (slot, last_local, len(prompt), tk,
                            0 if c_off is None else c_off + constraint.start)
                ints[5:] = stream.view(np.int32)
                seen_row = np.zeros((self.cfg.vocab_size,), bool)
                seen_row[prompt] = True
                return (self.family.head_leaves(self._lora_prefill_view(aid)),
                        ints, np.asarray((temp, tp, mp, rp), np.float32),
                        seen_row, b_row, blocks)

            if self._ilv:
                # interleaved admission (ISSUE 12): NO device work here.
                # The prompt's chunks fold into subsequent decode steps
                # (mixed_step), the fused finish samples the first token
                # on device, and its readback rides a later step's
                # commit — submit() is host bookkeeping only, so the
                # prefill convoy never forms.
                p_c = self._ilv
                n_c = -(-len(prompt) // p_c)
                padded_i = np.zeros((1, n_c * p_c), np.int32)
                padded_i[0, : len(prompt)] = prompt
                if self._lora is not None and self._aid[slot] != aid:
                    self._aid[slot] = aid
                    self._decode_view = self._lora_prepared(self._aid)
                req = {"rid": rid, "emitted": [],
                       "budget": max_new_tokens, "stop": stop_seqs,
                       "logprobs": logprobs and self._logprobs_k,
                       "blocks": paged_taken,
                       "prompt_len": len(prompt), "freed": 0,
                       "t_last": None,
                       "pending": {
                           "padded": padded_i, "n_chunks": n_c,
                           "next": 0, "row": self._ilv_new_row(),
                           "aid": aid,
                           # what the finish takes beside the state, the
                           # row and the last chunk's hidden rows
                           "finish": finish_request(
                               (len(prompt) - 1) % p_c),
                       }}
                if constraint is not None:
                    req["constraint"] = constraint
                    req["c_state"] = constraint.start
                    req["c_off"] = c_off
                    self._note_constrained(+1)
                if req["logprobs"]:
                    req["lp"] = []
                    req["lp_top"] = []
                if trace:
                    req["trace"] = trace
                self._slot_req[slot] = req
                self._pending_q.append(slot)
                return rid

            # chunked prefill: full prompt_pad-sized chunks + one padded tail,
            # each at its absolute start position — prompts of ANY length (up
            # to max_len - max_new) reuse the one compiled chunk program
            padded = np.zeros((1, n_chunks * p_pad), np.int32)
            padded[0, : len(prompt)] = prompt
            # prefilled (KV adoption): the row arrives from the prefill
            # replica — never allocate (or compute) one here
            row = self._new_row() if prefilled is None else None
            hidden = None
            start_chunk = 0
            prefix_hit_flag = False
            prefix_lookup_ran = prefilled is None and (
                self._prefix_cache is not None or use_radix)
            if use_radix:
                prefix_hit_flag = n_shared > 0 or cow_tok > 0
            elif hit_c:
                prefix_hit_flag = True
            if prefix_lookup_ran:
                if prefix_hit_flag:
                    self.prefix_hits += 1
                else:
                    self.prefix_misses += 1
            if hit_c:
                # dense-LRU hit (the radix store replaces this path on
                # paged pools): copy out — the live row is donated
                # through the chunk loop and must not invalidate the
                # cached entry
                start_chunk = hit_c
                row = jax.tree.map(jnp.copy, hit_entry[0])
                if hit_c == n_chunks:
                    # whole prompt cached: rebuild a chunk-shaped hidden
                    # array with the stored last row in place (position
                    # p_pad-1 == the true last prompt token of an exact
                    # full-chunk prompt) so _prefill_finish keeps its one
                    # compiled shape
                    hidden = self._row_hidden(hit_entry[1], p_pad - 1)
            pf_prepared = self._lora_prefill_view(aid)
            # the admission's launches and the positions they hold, pad
            # included
            n_launch = n_chunks - start_chunk
            sp_pf = adm.child("prefill", chunks=n_launch,
                              positions=n_launch * p_pad,
                              prompt_len=len(prompt))
            t_pf = time.perf_counter()  # the PREFILL interval only —
            # submit-entry-to-here is validation/slot/host bookkeeping,
            # which belongs to the admit span, not this metric
            _sp = _profile.open_span("admit.prefill", rid=rid,
                                     chunks=n_launch,
                                     positions=n_launch * p_pad)
            chunks_before = self.prefill_chunks_run
            last_local = (len(prompt) - 1) % p_pad
            kv_boundary_rows: dict = {}
            if prefilled is not None:
                # KV ADOPTION (disaggregated serving, dnn_tpu/control):
                # the prefill replica already ran this chunk loop;
                # rebuild its transient row + the finish-shaped hidden
                # array and fall through to the SAME _prefill_finish
                # install below — the decode replica spends zero prompt
                # FLOPs but the one head row
                row, hidden = self._adopt_prefilled(prefilled, prompt)
            elif use_radix:
                row, hidden, last_local = self._radix_prefill(
                    prompt, blocks[0], pf_prepared, row, kv_hit, n_shared,
                    cow_tok, kv_boundary_rows)
            else:
                for c in range(start_chunk, n_chunks):
                    hidden, row = self._run_prefill_chunk(
                        pf_prepared, row,
                        padded[:, c * p_pad:(c + 1) * p_pad],
                        np.int32(c * p_pad),
                        *self._n_real(len(prompt), c))
                    self.prefill_chunks_run += 1
                    if self._prefix_cache is not None \
                            and (c + 1) * p_pad <= len(prompt):
                        key = key_ns + prompt[: (c + 1) * p_pad].tobytes()
                        # scan-resistant insertion: evict the current
                        # LRU first, then park the NEW entry at the LRU
                        # end — only a HIT promotes to MRU. A long novel
                        # prompt therefore cycles its own one-shot
                        # chunks through the LRU slot instead of
                        # flushing the hot shared-prefix entries it
                        # never matches.
                        while len(self._prefix_cache) >= self._prefix_cap:
                            self._evict_prefix_entry()
                        self._prefix_cache[key] = (
                            jax.tree.map(jnp.copy, row),
                            jnp.copy(hidden[0, -1]))
                        self._prefix_cache.move_to_end(key, last=False)
            t_pf1 = time.perf_counter()  # every chunk is dispatched
            _profile.close_span(_sp)
            # finish and install (admit.install): build the program's
            # inputs and launch it — the admission's LAST device program,
            # which leaves nothing for Python to scatter
            t_in0 = time.perf_counter()
            _sp = _profile.open_span("admit.install", rid=rid)
            first, first_lps = self._finish(
                row, hidden, finish_request(last_local))
            t_in1 = time.perf_counter()
            _profile.close_span(_sp)
            if use_radix:
                # insert this prompt's full-block path now that the
                # install has populated the owned blocks. The store
                # refs every NEWLY resident block (existing nodes are
                # reused untouched); the slot keeps its own references
                # until retirement, so the trie and the live request
                # share blocks exactly as two requests would. Origins
                # propagate per block: re-creating an evicted ADOPTED
                # node must not launder it local (the cross-replica
                # accounting would decay with cache churn).
                n_cover = len(prompt) // self._block_len
                kv_borig = list(kv_hit.origins[:n_shared]) \
                    if kv_hit is not None else []
                if n_cover:
                    self._prefix_store.insert(
                        prompt[: n_cover * self._block_len],
                        [int(x) for x in paged_taken[:n_cover]],
                        hidden_rows=kv_boundary_rows, origin=kv_borig)
            # the device-to-host read: where the host waits for the
            # prefill (admit.first_token)
            t_ft0 = time.perf_counter()
            _sp = _profile.open_span("admit.first_token", rid=rid)
            first = int(first)  # blocks until the prefill really finished
            if self._moe_stats:
                self._moe_flush()
            if logprobs and self._logprobs_k:
                c_lp, t_lp, t_ids = first_lps
                first_lp = (float(np.asarray(c_lp)[0]),
                            (np.asarray(t_ids)[0], np.asarray(t_lp)[0]))
            t_ft1 = time.perf_counter()
            _profile.close_span(_sp)
            sp_pf.end()
            m = obs.metrics()
            if m is not None:
                # the FIRST token commits here (sampled during prefill),
                # so it is credited here — counting only in step() would
                # under-report every request by one token (and budget-1
                # requests, which never reach step(), entirely)
                self._tps.add(1)
                counters = {
                    "serving.tokens_total": 1,
                    "serving.prefill_chunks_total":
                        self.prefill_chunks_run - chunks_before,
                }
                if prefix_hit_flag:
                    counters["serving.prefix_hits_total"] = 1
                    if use_radix:
                        # block-granular effectiveness (the radix
                        # extension of the hit/miss pair): how many
                        # physical blocks this admission reused, and
                        # how many of them arrived by MIGRATION from a
                        # sibling replica (origin="adopted") — the
                        # cross-replica number. Post-truncation counts, matching
                        # note_reuse above.
                        counters["serving.prefix_blocks_reused_total"] \
                            = n_shared + (1 if cow_tok > 0 else 0)
                        remote_used = kv_hit.remote_used(
                            n_shared, cow_tok > 0)
                        if remote_used:
                            counters[
                                "serving.kvtier_remote_block_hits_total"
                            ] = remote_used
                elif prefix_lookup_ran:
                    # the lookup ran (prefilled= adoptions skip it) and
                    # reused nothing — the other half of the ratio
                    counters["serving.prefix_misses_total"] = 1
                m.bulk(
                    counters=counters,
                    observations={"serving.prefill_seconds":
                                  [time.perf_counter() - t_pf]},
                    gauge_fns=self._obs_gauges,
                )
                if (g := self.goodput) is not None:
                    g.on_prefill(len(prompt))
                if self._kvlens is not None:
                    # the thrash detector's price signal: what ONE
                    # prefill chunk costs on this host right now — an
                    # evict→refetch bills this EMA per re-run chunk
                    self._kvlens.note_prefill(
                        self.prefill_chunks_run - chunks_before,
                        time.perf_counter() - t_pf)
            _parts = (t_pf1 - t_pf, t_ft1 - t_ft0, t_in1 - t_in0)
            if self._lora is not None and self._aid[slot] != aid:
                self._aid[slot] = aid
                self._decode_view = self._lora_prepared(self._aid)
            req = {"rid": rid, "emitted": [first], "budget": max_new_tokens,
                   "stop": stop_seqs, "logprobs": logprobs and self._logprobs_k,
                   "blocks": paged_taken, "prompt_len": len(prompt),
                   "freed": 0}
            if w_taken:
                # a window kind's blocks, {logical: physical}; the very
                # dicts the failure path below would free
                req["wblocks"] = w_taken
            if use_radix:
                # retire-time store insertion needs the token ids and
                # the per-block provenance (adopted blocks re-inserted
                # after eviction must stay adopted)
                req["ptoks"] = prompt
                req["borig"] = kv_borig
            if constraint is not None:
                req["constraint"] = constraint
                req["c_state"] = constraint.start
                req["c_off"] = c_off
                self._note_constrained(+1)
            if req["logprobs"]:
                req["lp"] = [first_lp[0]]
                req["lp_top"] = [first_lp[1]]
            if trace:
                req["trace"] = trace  # step() hangs decode spans off this
            req["t_last"] = time.perf_counter()  # inter-token clock
            if self._overlap and self._inflight is not None:
                # the uncommitted in-flight step was dispatched while
                # this slot was still free: its row of that step's
                # tokens is garbage and must not commit (the same
                # install gating the interleaved path uses; the first
                # token here is already in `emitted`)
                req["install_step"] = self._step_idx - 1
            self._slot_req[slot] = req
            if constraint is not None:
                # host mirror of the walk the finish already did on
                # device (the first token masked with the grammar's start
                # row, crow[slot] seeded from it) — finish detection
                # only. Prefix-cache / kvtier / prefilled adoption
                # changes nothing: the grammar constrains GENERATED
                # tokens only, so the adopted prefix's state is still
                # `start`.
                self._constraint_advance(slot, first)
            # a prompt longer than the window rolls blocks out at install
            self._free_rolled_blocks(slot, in_step=False)
            self._flush_window_tables(in_step=False)
            self._retire_if_done(slot, in_step=False)
            return rid
        except BaseException:
            # a failure ANYWHERE in the prefill path must return this
            # request's pool blocks (and un-point its table row) or the
            # pool shrinks permanently on every such failure — same for
            # its constraint-table reference. For windowed pools,
            # _free_rolled_blocks may ALREADY have returned the rolled
            # -out prefix (it runs before _retire_if_done): free only
            # the remainder, and release the slot if the req landed.
            if paged_taken:
                req_now = self._slot_req[slot]
                skip = (req_now["freed"]
                        if isinstance(req_now, dict)
                        and req_now.get("blocks") is paged_taken else 0)
                self._allocator.free(paged_taken[skip:])
                self.cache["tables"] = \
                    self.cache["tables"].at[:, slot].set(0)
                for t, got in w_taken.items():  # (rolled in place)
                    self._allocator.of(t).free(list(got.values()))
                    self.cache[t] = self.cache[t].at[:, slot].set(0)
            # the slot was free at entry, so it must end inactive on ANY
            # failure — active may have been set before the req landed,
            # and a True-active/None-req slot would spin drain() forever
            self._slot_req[slot] = None
            self.active = self.active.at[slot].set(False)
            if c_off is not None:
                self._ctab_release(constraint)
            raise
        finally:
            adm.end()
            if _sc is not None:
                _sc.note_admit(_t_sub, _parts)

    def _slot_state(self) -> tuple:
        """The device state an admission's finish rewrites — the pool
        cache and every per-slot vector — in the order the finish
        program takes and returns it."""
        return (self.cache, self.pos, self.tok, self.active, self.keys,
                self._temp, self._topk, self._topp, self._minp, self._rep,
                self._seen, self._bias, self._crow)

    def _set_slot_state(self, state):
        (self.cache, self.pos, self.tok, self.active, self.keys,
         self._temp, self._topk, self._topp, self._minp, self._rep,
         self._seen, self._bias, self._crow) = state

    def _finish(self, row, hidden, request):
        """Launch the finish-and-install program for `request` (submit's
        `finish_request`) on the finished `row` and the last chunk's
        `hidden` rows -> (first token, its logprob outputs or ()), all
        still on the device."""
        out = self._prefill_finish(*self._slot_state(), row, hidden,
                                   *request, self._ctable, self._ctrans)
        if self._slot_leaves and self.step_clock is not None:
            self.step_clock.note_state(installs=self._n_state_layers)
        self._set_slot_state(out[:13])
        return out[13], out[14:]

    def _n_real(self, prompt_len: int, c: int) -> tuple:
        """The chunk program's last argument for chunk `c` of a prompt:
        the count of its real positions, for a family that keeps a state
        (`prefill_chunk`); () for every other."""
        if not self._takes_n_real:
            return ()
        return (np.int32(min(self.prompt_pad,
                             prompt_len - c * self.prompt_pad)),)

    def _run_prefill_chunk(self, *args):
        """The chunk program -> (hidden, row); an MoE family's third
        result, the chunk's expert-layer stats, is noted on the way. A
        chunk loop dispatches all its chunks without reading or waiting:
        what a dispatched chunk allocates is its (1, prompt_pad, C)
        hidden rows (8 MB at 1024 x 2048) and the donated row."""
        res = self._prefill_chunk(*args)
        if self._moe_stats:
            self._moe_note("prefill", res[2])
        if self._select_counts is not None and self.step_clock is not None:
            # a selection by blocks: the positions the chunk's queries read
            # (their local blocks and the chosen ones) of those they could
            start, t = int(args[3]), int(args[2].shape[-1])
            self.step_clock.note_dsa(
                "prefill", self._n_index_layers,
                self._n_index_layers * (t * start + t * (t + 1) // 2),
                self._n_index_layers * self._select_counts(start + 1, t),
                self._n_index_layers * t * walked_columns(
                    start, t, self._row_len))
        if self._index_topk and self.step_clock is not None:
            # what the chunk's indexers scored and selected, from its
            # start and length alone (pad rows too: the device scores
            # them): row t reads min(start + t + 1, topk) of start + t + 1
            start, t = int(args[3]), int(args[2].shape[-1])
            n_sel = _capped_pairs(start, t, self._index_topk)
            # the masked kernel's grid (a latent model's chunk goes
            # through ops/pallas/mla_attention.py, over its own prefixes)
            self.step_clock.note_dsa(
                "prefill", self._n_index_layers,
                self._n_index_layers * (t * start + t * (t + 1) // 2),
                self._n_index_layers * n_sel,
                0 if self._latent else self._n_index_layers * t
                * walked_columns(start, t, self._row_len))
            if self._cache_kinds:
                self.step_clock.note_mla_kind(
                    "prefill", "full", self._n_index_layers * n_sel)
        if self._latent and self.step_clock is not None:
            # the cached latents the chunk's layers attend (everything
            # before it and itself) and its causal (query, position)
            # pairs, pad rows too
            start, t = int(args[3]), int(args[2].shape[-1])
            self.step_clock.note_mla(
                "prefill", self.cfg.n_layer, self.cfg.n_layer * (start + t),
                self.cfg.n_layer * (t * start + t * (t + 1) // 2))
            for kind, n_l, w in self._win_kinds:
                # (query, position) pairs within the band
                self.step_clock.note_mla_kind(
                    "prefill", kind, n_l * _capped_pairs(start, t, w))
        if self._takes_n_real and self.step_clock is not None:
            t = int(args[2].shape[-1])
            real = int(args[4]) if len(args) > 4 else t
            self.step_clock.note_state(prefill_real_positions=real,
                                       prefill_pad_positions=t - real)
        if self._kv_kinds and self.step_clock is not None:
            # K and V leaves by kind: the chunk's causal pairs a full
            # layer, those within the band a window layer (pad rows too)
            start, t = int(args[3]), int(args[2].shape[-1])
            self.step_clock.note_mla_kind(
                "prefill", "full", self._n_index_layers
                * (t * start + t * (t + 1) // 2), series="attn")
            for kind, n_l, w in self._win_kinds:
                self.step_clock.note_mla_kind(
                    "prefill", kind, n_l * _capped_pairs(start, t, w),
                    series="attn")
        return res[0], res[1]

    def _moe_note(self, program: str, stats, idx: Optional[int] = None):
        """Keep one dispatched program's expert-layer stats (a device
        array) until it is known to have run. `idx`: the decode
        dispatch it belongs to (a step's own, or the next one for a
        program dispatched between steps)."""
        self._moe_pending.append(
            (self._step_idx if idx is None else idx, program, stats))

    def _moe_flush(self, upto: Optional[int] = None):
        """Give the StepClock the stats of every noted program that has
        finished: those of decode dispatch `upto` and earlier, once its
        tokens are on the host (None: all of them — a first token was
        just read, and the device runs programs in dispatch order). One
        transfer of twenty bytes a program, from programs already
        complete."""
        pend, sc = self._moe_pending, self.step_clock
        ready = []
        while pend and (upto is None or pend[0][0] <= upto):
            ready.append(pend.popleft())
        if sc is None or not ready:
            return
        for (_, program, _), stats in zip(
                ready, jax.device_get([r[2] for r in ready])):
            # a dense prefix's layers are no expert layer calls
            calls = getattr(self.cfg, "n_expert_layer", self.cfg.n_layer)
            sc.note_moe(program, calls, stats)
            if getattr(self.cfg, "moe_latent", None) is not None:
                # every row of the program goes through W_down, a layer
                sc.note_moe_latent(program, calls * (
                    self.slots if program == "decode" else self.prompt_pad))

    def _ensure_cache_len(self, need: int):
        """Grow the bucketed dense pool to the smallest ladder bucket
        covering `need` live positions (no-op when already covered, or on
        unbucketed pools). Grow-only by design: shrinking mid-flight
        would thrash the jit cache on every retire; an idle server that
        wants the small allocation back reconstructs."""
        if self._buckets is None or need <= self._cache_len:
            return
        from dnn_tpu.runtime.decode_buckets import bucket_for

        target = bucket_for(self._buckets, need)
        self.cache = self._grow_cache(self.cache, target)
        self._cache_len = target
        m = obs.metrics()
        if m is not None:
            m.inc("serving.decode_bucket_grow_total")

    # -- disaggregated prefill/decode (dnn_tpu/control) -----------------

    def handoff_fingerprint(self) -> dict:
        """The geometry both sides of a KV handoff must agree on. The
        adopt path re-verifies leaf-by-leaf anyway (shapes + dtypes vs
        this pool's own row structure); the fingerprint exists so a
        kvput against a mismatched replica fails at INGEST with a
        readable diff instead of at admission."""
        leaves = jax.tree_util.tree_flatten(self._row_shape())[0]
        return {
            "family": type(self.family).__name__,
            "vocab_size": int(self.cfg.vocab_size),
            "n_embd": int(self.cfg.n_embd),  # the hidden row's width
            "prompt_pad": int(self.prompt_pad),
            "row_len": int(self._row_len),
            "row_leaves": [[list(x.shape), str(x.dtype)] for x in leaves],
        }

    def _row_shape(self):
        """ShapeDtypeStruct pytree of the transient row cache (no
        allocation) — the adoption path's geometry oracle."""
        struct = getattr(self, "_row_struct", None)
        if struct is None:
            struct = jax.eval_shape(self._new_row)
            self._row_struct = struct
        return struct

    def export_prefill(self, prompt, *, max_new_tokens: int = 1):
        """PREFILL-replica half of the disaggregated split: run ONLY
        the chunk loop for `prompt` — no slot held, no install, no
        sampling — and return the handoff payload a decode replica
        adopts via `submit(prefilled=...)`: the transient row cache's
        leaves (host arrays) plus the final chunk's true-last HIDDEN
        row (C values: the decode replica's finish applies the head to
        it). `max_new_tokens` only sizes the length check (the decode
        side re-validates with the request's real budget).

        Prices like any prefill: the chunk counter, the prefill-
        seconds series and the goodput tracker's prefill FLOPs all
        tick here, so MFU/MBU on the prefill replica account the work
        it actually does (the handoff's wire cost is priced by the
        router's handoff gauges)."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if len(prompt) == 0:
            raise ValueError("prompt must have at least one token")
        if len(prompt) + max(int(max_new_tokens), 1) > self.max_len:
            raise ValueError(
                f"prompt {len(prompt)} + max_new_tokens "
                f"{max_new_tokens} exceeds max_len {self.max_len}")
        p_pad = self.prompt_pad
        n_chunks = -(-len(prompt) // p_pad)
        padded = np.zeros((1, n_chunks * p_pad), np.int32)
        padded[0, : len(prompt)] = prompt
        # the row is built the convoy way — _new_row + the chunk
        # program — even on an interleaved-admission server (the chunk
        # program is compiled unconditionally), so ANY replica can
        # take the prefill role
        row = self._new_row()
        hidden = None
        t_pf = time.perf_counter()
        for c in range(n_chunks):
            hidden, row = self._run_prefill_chunk(
                self.prepared, row,
                padded[:, c * p_pad:(c + 1) * p_pad],
                np.int32(c * p_pad),
                *self._n_real(len(prompt), c))
            self.prefill_chunks_run += 1
        last_local = len(prompt) - 1 - (n_chunks - 1) * p_pad
        hidden_row = np.asarray(hidden[0, last_local])
        leaves = [np.asarray(x) for x in jax.tree_util.tree_flatten(row)[0]]
        m = obs.metrics()
        if m is not None:
            m.bulk(
                counters={"serving.prefill_chunks_total": n_chunks},
                observations={"serving.prefill_seconds":
                              [time.perf_counter() - t_pf]},
            )
            if (g := self.goodput) is not None:
                g.on_prefill(len(prompt))
        return {"row": leaves, "hidden_row": hidden_row,
                "prompt_len": len(prompt),
                "fingerprint": self.handoff_fingerprint()}

    def _adopt_prefilled(self, prefilled, prompt) -> tuple:
        """Decode-replica half: verify the handed-off payload against
        THIS pool's row geometry, rebuild the row pytree and the
        finish-shaped hidden array (the stored true-last row placed at
        `last_local`, exactly like a whole-prompt prefix hit). Every
        mismatch is a loud ValueError — adopting mis-shaped KV would
        generate plausible garbage."""
        struct = self._row_shape()
        want, treedef = jax.tree_util.tree_flatten(struct)
        got = prefilled.get("row") if isinstance(prefilled, dict) else None
        if not isinstance(got, (list, tuple)):
            raise ValueError(
                "prefilled= expects an export_prefill payload dict "
                "with a 'row' leaf list")
        if len(got) != len(want):
            raise ValueError(
                f"handoff row has {len(got)} leaves but this pool's "
                f"row cache has {len(want)} — prefill and decode "
                "replicas must share model config and kv dtype")
        for i, (w, h) in enumerate(zip(want, got)):
            h = np.asarray(h)
            if tuple(h.shape) != tuple(w.shape) \
                    or str(h.dtype) != str(np.dtype(w.dtype)):
                raise ValueError(
                    f"handoff row leaf {i} is {h.dtype}{h.shape} but "
                    f"this pool expects {w.dtype}{tuple(w.shape)} — "
                    "prefill and decode replicas must share model "
                    "config, max_len, prompt_pad and kv dtype")
        plen = prefilled.get("prompt_len")
        if plen is not None and int(plen) != len(prompt):
            raise ValueError(
                f"handoff was exported for a {plen}-token prompt but "
                f"this request's prompt has {len(prompt)} tokens")
        row = jax.tree_util.tree_unflatten(
            treedef, [jnp.asarray(x) for x in got])
        hr = np.asarray(prefilled.get("hidden_row"))
        if hr.shape != (self.cfg.n_embd,) or hr.dtype != np.float32:
            raise ValueError(
                f"handoff hidden_row is {hr.dtype}{hr.shape}, expected "
                f"float32({self.cfg.n_embd},): the last block's output at "
                "the prompt's last token (a payload that carries a "
                "logits row comes from a replica of an older version)")
        m = obs.metrics()
        if m is not None:
            m.inc("serving.kv_adoptions_total")
        return row, self._row_hidden(hr, (len(prompt) - 1) % self.prompt_pad)

    # -- fleet KV tier (dnn_tpu/kvtier): stage / export / adopt ---------

    def _require_store(self):
        if self._prefix_store is None:
            raise ValueError(
                "the KV tier needs the radix prefix store: construct "
                "with kv='paged' (or paged_blocks>0) and prefix_cache>0")

    def kvtier_fingerprint(self) -> dict:
        """Block geometry both sides of a block migration must share —
        checked at adopt with a readable diff, exactly like the
        row-handoff fingerprint. int4 pools report their true dtype
        (blocks cross the host boundary as int8 values and re-pack on
        ingest)."""
        self._require_store()
        leaves = {}
        for kk in self.cache:
            if kk == "tables":
                continue
            shp = list(self.cache[kk].shape)
            # one block's leaf: drop the n_blocks axis (axis 1)
            leaves[kk] = [[shp[0]] + shp[2:], str(self.cache[kk].dtype)]
        return {"family": type(self.family).__name__,
                "vocab_size": int(self.cfg.vocab_size),
                "n_embd": int(self.cfg.n_embd),  # the hidden rows' width
                "block_len": int(self._block_len),
                "leaves": leaves}

    def _read_block(self, block_id: int) -> dict:
        """One physical block's leaves on host — fixed-shape jitted
        gather (a per-run-length gather would compile per length).
        int4 payloads widen to int8 VALUES for the host trip (native
        int4 has no stable host view; the wire codec nibble-packs
        them back to half a byte)."""
        got = self._kv_get_block(self.cache, jnp.int32(block_id))
        return {kk: np.asarray(v) for kk, v in got.items()}

    def kvtier_export(self, tokens):
        """Donor half of block migration: the longest resident run of
        full blocks matching `tokens`, read off the pool. Returns the
        payload dict `kvtier_adopt` ingests (kvtier/migrate.py packs it
        for the wire), or None when nothing is resident. Worker-thread
        only (reads pool leaves between steps)."""
        self._require_store()
        tokens = np.asarray(tokens, np.int32).reshape(-1)
        nodes = self._prefix_store.nodes_for(tokens)
        if not nodes:
            return None
        blocks = [self._read_block(n.block) for n in nodes]
        leaves = {kk: np.stack([b[kk] for b in blocks], axis=1)
                  for kk in blocks[0]}
        hidden_rows = {i: np.asarray(n.hidden_row)
                       for i, n in enumerate(nodes)
                       if n.hidden_row is not None}
        bp = self._block_len
        return {"tokens": tokens[: len(nodes) * bp],
                "block_len": bp, "leaves": leaves,
                "hidden_rows": hidden_rows,
                "fingerprint": self.kvtier_fingerprint()}

    def kvtier_adopt(self, payload, *, origin: str = "adopted") -> int:
        """Adopter half: ingest a sibling's exported block run — verify
        geometry, allocate fresh LOCAL blocks for the non-resident
        suffix (never aliasing anything live: a dying donor cannot
        corrupt an adopter, because nothing of the donor's is mapped),
        scatter the payload in block-by-block, and insert the radix
        path with origin="adopted" so hit accounting knows these
        blocks crossed replicas. Returns blocks actually migrated
        (0 = everything was already resident). Worker-thread only."""
        from dnn_tpu.runtime.paged_kvcache import InsufficientBlocks

        self._require_store()
        mine = self.kvtier_fingerprint()
        theirs = payload.get("fingerprint") or {}
        if theirs and theirs != mine:
            diff = {k: (theirs.get(k), mine.get(k))
                    for k in set(theirs) | set(mine)
                    if theirs.get(k) != mine.get(k)}
            raise ValueError(
                f"kvtier geometry mismatch (theirs, mine): {diff} — "
                "donor and adopter must share model config, block_len "
                "and kv dtype")
        tokens = np.asarray(payload["tokens"], np.int32).reshape(-1)
        bp = self._block_len
        n_total = tokens.size // bp
        if n_total == 0:
            return 0
        have = self._prefix_store.nodes_for(tokens)
        n_have = len(have)
        if n_have >= n_total:
            return 0
        n_missing = n_total - n_have
        # ref the matched resident run BEFORE the make-room loop: the
        # eviction hunt below may otherwise evict those very nodes,
        # free their blocks, and recycle them into `owned` — the
        # insert would then map two trie paths onto one physical block
        # (the same aliasing hazard submit() guards against)
        have_ids = [n.block for n in have]
        if have_ids:
            self._allocator.ref(have_ids)
        try:
            owned = self._allocator.alloc(n_missing)
            while owned is None and self._evictable_prefix():
                self._evict_prefix_entry()
                owned = self._allocator.alloc(n_missing)
        except BaseException:
            if have_ids:
                self._allocator.free(have_ids)
            raise
        if owned is None:
            if have_ids:
                self._allocator.free(have_ids)
            raise InsufficientBlocks(
                f"kvtier adopt needs {n_missing} free blocks, have "
                f"{self._allocator.n_free}")
        try:
            for j, dst in zip(range(n_have, n_total), owned):
                vals = {kk: jnp.asarray(np.ascontiguousarray(
                    payload["leaves"][kk][:, j]))
                    for kk in payload["leaves"]}
                self.cache = self._kv_put_block(self.cache, vals,
                                                jnp.int32(dst))
            ids = have_ids + owned
            hrs = {int(i): jnp.asarray(r)
                   for i, r in (payload.get("hidden_rows") or {}).items()}
            self._prefix_store.insert(tokens[: n_total * bp], ids,
                                      hidden_rows=hrs, origin=origin)
        finally:
            # the store now holds its own reference per inserted node;
            # dropping ours (owned allocs + the matched-run guards)
            # frees exactly the blocks that did NOT make it in (cap
            # pressure, or an exception mid-scatter)
            self._allocator.free(owned + have_ids)
        m = obs.metrics()
        if m is not None:
            m.inc("serving.kvtier_blocks_adopted_total", n_missing)
        if self._kvlens is not None:
            # migration forensics: blocks that crossed the wire, priced
            # in payload bytes when the transport recorded them
            self._kvlens.on_migrate(
                n_missing, int(payload.get("_wire_bytes") or 0))
        return n_missing

    def stage_prefix(self, prompt) -> dict:
        """Prefill `prompt`'s full blocks STRAIGHT INTO the radix store
        — no slot held, no sampling, no install into any request's
        table: the prefill-replica half of disaggregated block
        migration (the router stages here, then tells the decode
        replica to pull), and a warm-up hook. Resumes at the first
        non-resident block like any admission; a fully resident prompt
        is a no-op. Worker-thread only."""
        from dnn_tpu.runtime.paged_kvcache import InsufficientBlocks

        self._require_store()
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        bp = self._block_len
        p_pad = self.prompt_pad
        n_cover = prompt.size // bp
        stats = {"covered_blocks": n_cover, "staged_blocks": 0,
                 "computed_chunks": 0}
        if n_cover == 0:
            return stats
        nodes = self._prefix_store.nodes_for(prompt[: n_cover * bp])
        n_shared = len(nodes)
        if n_shared >= n_cover:
            return stats
        shared_ids = [n.block for n in nodes]
        if shared_ids:
            self._allocator.ref(shared_ids)
        owned = None
        try:
            owned = self._allocator.alloc(n_cover - n_shared)
            while owned is None and self._evictable_prefix():
                self._evict_prefix_entry()
                owned = self._allocator.alloc(n_cover - n_shared)
            if owned is None:
                raise InsufficientBlocks(
                    f"stage_prefix needs {n_cover - n_shared} free "
                    f"blocks, have {self._allocator.n_free}")
            nb_max = self.cache["tables"].shape[-1]
            ids_row = np.zeros((nb_max,), np.int32)
            ids_row[:n_cover] = shared_ids + owned
            end = n_cover * bp
            resume = n_shared * bp
            if resume + (-(-(end - resume) // p_pad)) * p_pad \
                    > self._row_len:
                resume = (resume // p_pad) * p_pad
            row = (self._gather_row(self.cache, jnp.asarray(ids_row))
                   if resume else self._new_row())
            n_k = -(-(end - resume) // p_pad)
            padded = np.zeros((1, n_k * p_pad), np.int32)
            padded[0, : end - resume] = prompt[resume:end]
            boundary: dict = {}
            hidden = None
            t_pf = time.perf_counter()
            for i in range(n_k):
                start = resume + i * p_pad
                hidden, row = self._run_prefill_chunk(
                    self.prepared, row,
                    padded[:, i * p_pad:(i + 1) * p_pad],
                    np.int32(start))
                self.prefill_chunks_run += 1
                for b in range(start // bp, n_cover):
                    pos = (b + 1) * bp - 1
                    if pos >= start + p_pad:
                        break
                    if pos >= start:
                        boundary[b] = jnp.copy(hidden[0, pos - start])
            inst = ids_row.copy()
            inst[:n_shared] = 0
            self.cache = self._kvtier_install(self.cache, row,
                                              jnp.asarray(inst))
            self._prefix_store.insert(
                prompt[:end], [int(x) for x in ids_row[:n_cover]],
                hidden_rows=boundary)
            m = obs.metrics()
            if m is not None:
                m.bulk(counters={"serving.prefill_chunks_total": n_k},
                       observations={"serving.prefill_seconds":
                                     [time.perf_counter() - t_pf]},
                       gauge_fns=self._obs_gauges)
                if (g := self.goodput) is not None:
                    g.on_prefill(end - resume)
                if self._kvlens is not None:
                    self._kvlens.note_prefill(
                        n_k, time.perf_counter() - t_pf)
            stats.update(staged_blocks=n_cover - n_shared,
                         computed_chunks=n_k)
            return stats
        finally:
            # transient references only: the store refs what it keeps
            if shared_ids:
                self._allocator.free(shared_ids)
            if owned:
                self._allocator.free(owned)

    def _evictable_prefix(self) -> bool:
        """Whether the admission make-room loop has anything left to
        evict — either prefix-cache form."""
        if self._prefix_store is not None:
            return self._prefix_store.n_blocks > 0
        return bool(self._prefix_cache)

    def _evict_prefix_entry(self, cause: str = "capacity"):
        """Drop the LRU prefix entry — the dense dict's LRU head, or
        the radix store's LRU LEAF (interior nodes carry every
        descendant's prefix). Either way blocks still shared by live
        slots survive via refcount until those retire. `cause`
        attributes the eviction ("capacity" = admission pressure; the
        kvput-TTL and lease-reclaim sweeps are separate paths that
        label their own events) — the unlabeled total stays as-is, the
        by-cause family rides alongside so forensics can tell real
        pressure from housekeeping."""
        if self._prefix_store is not None:
            if not self._prefix_store.evict_one(cause=cause):
                return
            self.prefix_evictions += 1
            left = self._prefix_store.n_blocks
        else:
            _, _entry = self._prefix_cache.popitem(last=False)
            self.prefix_evictions += 1
            left = len(self._prefix_cache)
        m = obs.metrics()
        if m is not None:
            m.inc("serving.prefix_evictions_total")
            m.inc(labeled("serving.prefix_evictions_cause_total",
                          cause=cause))
        obs.flight.record("prefix_evict", entries_left=left, cause=cause)

    def _radix_prefill(self, prompt, ids_row, pf_prepared, row, kv_hit,
                       n_shared, cow_tok, boundary_rows):
        """The radix-store admission prefill: resume the chunk loop at
        the first non-cached position instead of chunk 0.

        Returns (row, hidden, last_local). Three regimes:

          * FULL HIT — the prompt is exactly the shared block run and
            the final node stored its hidden row: zero chunks, rebuild
            the finish-shaped hidden array with the stored row in place;
          * PARTIAL — resume at `n_shared * block_len + cow_tok` (the
            copy-on-write boundary block, already duplicated into this
            request's first owned block, covers the agreed mid-block
            tokens); the transient row is GATHERED from the slot's
            table so later chunks attend the shared prefix, then
            full-width chunks run from the (block- or mid-block-
            aligned) resume position — `chunk_start` is a dynamic
            scalar, so unaligned starts reuse the one compiled chunk
            program;
          * capacity BACKOFF — a resume point whose remaining chunks
            would overhang the transient row is rounded down to its
            chunk boundary (a dynamic-update overhang would CLAMP the
            write onto real positions — the standing row-rounding
            lesson); backing into already-shared territory only
            recomputes values the install then routes to junk.

        Boundary hidden rows (the last block's output at each completed
        block's last token: what the head turns into that position's
        logits) are collected into `boundary_rows` for the store insert
        — what makes a later exactly-block-aligned prompt a zero-chunk
        full hit."""
        p_len = len(prompt)
        bp = self._block_len
        p_pad = self.prompt_pad
        if kv_hit.hidden_row is not None and p_len == n_shared * bp \
                and cow_tok == 0:
            last_local = (p_len - 1) % p_pad
            return (row, self._row_hidden(kv_hit.hidden_row, last_local),
                    last_local)
        resume = min(n_shared * bp + cow_tok, p_len - 1)
        if resume + (-(-(p_len - resume) // p_pad)) * p_pad \
                > self._row_len:
            # overhang only ever comes from an UNALIGNED resume near a
            # full row; rounding down to the chunk boundary always fits
            # (end <= ceil(p/P)*P <= row_len), at the price of
            # recomputing at most one chunk's worth of already-shared
            # positions — whose installs route to junk, never corrupt
            resume = (resume // p_pad) * p_pad
        if resume:
            # by the slot's table row `ids_row`, which reaches the
            # device's tables only with the finish
            row = self._gather_row(self.cache, ids_row)
        n_k = -(-(p_len - resume) // p_pad)
        padded_r = np.zeros((1, n_k * p_pad), np.int32)
        padded_r[0, : p_len - resume] = prompt[resume:]
        hidden = None
        for i in range(n_k):
            start = resume + i * p_pad
            hidden, row = self._run_prefill_chunk(
                pf_prepared, row,
                padded_r[:, i * p_pad:(i + 1) * p_pad],
                np.int32(start))
            self.prefill_chunks_run += 1
            for b in range(start // bp, p_len // bp):
                pos = (b + 1) * bp - 1
                if pos >= start + p_pad:
                    break
                if pos >= start:
                    boundary_rows[b] = jnp.copy(hidden[0, pos - start])
        last_local = (p_len - resume - 1) - (n_k - 1) * p_pad
        return row, hidden, last_local

    @staticmethod
    def _stop_match(emitted: list, stop_seqs: list):
        """Length of the stop sequence the emitted stream ends with, else 0."""
        for s in stop_seqs:
            n = len(s)
            if len(emitted) >= n and emitted[-n:] == list(map(int, s)):
                return n
        return 0

    def _ctab_register(self, c) -> int:
        """Place a constraint's (S, V) mask table in the device pool,
        returning its row offset. A pool hit just bumps the refcount; a
        miss allocates a gap (evicting LRU unreferenced entries as
        needed) and uploads the table ONCE, bit-packed on the host.
        Raises when the grammar cannot fit even an empty pool — size
        `constraint_rows` to the grammar set (json_regex(2) needs ~900
        rows)."""
        key = id(c)
        e = self._ctab_entries.get(key)
        if e is not None:
            e["refs"] += 1
            self._ctab_entries.move_to_end(key)
            return e["off"]
        n = c.table.shape[0]
        if n > self._ctab_rows - 1:
            raise ValueError(
                f"constraint has {n} DFA states but the device mask pool "
                f"holds {self._ctab_rows - 1} rows — construct the server "
                f"with constraint_rows >= {n + 1}")

        def _free_gap():
            # first gap >= n after reserved row 0, between sorted entries
            taken = sorted((v["off"], v["off"] + v["n"])
                           for v in self._ctab_entries.values())
            at = 1
            for lo, hi in taken:
                if lo - at >= n:
                    return at
                at = max(at, hi)
            return at if self._ctab_rows - at >= n else None

        off = _free_gap()
        while off is None:
            victim = next((k for k, v in self._ctab_entries.items()
                           if v["refs"] == 0), None)
            if victim is None:
                raise ValueError(
                    f"constraint mask pool exhausted: {n} rows needed, "
                    f"all {self._ctab_rows - 1} allocatable rows occupied "
                    "by live requests — construct the server with a "
                    "larger constraint_rows")
            del self._ctab_entries[victim]
            off = _free_gap()
        self._ctable = self._ctable.at[off:off + n].set(
            jnp.asarray(pack_mask_table(c.mask_table(self.eos_id))))
        # transition rows upload in GLOBAL pool coordinates (local next
        # state + this grammar's offset), so the decode program's walk
        # `ctrans[crow, tok]` needs no per-grammar rebase — and the
        # functional .at[].set means an in-flight overlap step keeps
        # its own (pre-upload) buffers untouched
        self._ctrans = self._ctrans.at[off:off + n].set(
            jnp.asarray(c.trans_table(self.eos_id) + np.int32(off)))
        self._ctab_entries[key] = {"off": off, "n": n, "refs": 1, "c": c}
        return off

    def _ctab_release(self, c):
        e = self._ctab_entries.get(id(c))
        if e is not None and e["refs"] > 0:
            e["refs"] -= 1  # entry stays cached for reuse until evicted

    def _free_rolled_blocks(self, slot: int, in_step: bool = True):
        """Windowed paged pools reclaim FULLY rolled-out blocks while
        the request still runs: block j (positions [j*bp, (j+1)*bp)) is
        dead once its last position <= attend_limit - window — the band
        mask excludes it at this and every later step, so its physical
        block returns to the allocator (a long stream holds O(window)
        blocks, the pool form of the rolling cache's win) and its table
        entry points at junk block 0, whose content the mask never
        admits. No-op for dense/unwindowed pools."""
        w = self._paged_window
        req = self._slot_req[slot]
        if req is not None and req.get("wblocks"):
            self._roll_window_blocks(slot, req, in_step)
        if w is None or req is None or not req["blocks"]:
            return
        bp = self._block_len
        limit = req["prompt_len"] + len(req["emitted"]) - 1
        n_dead = min(max(0, limit - w + 1) // bp, len(req["blocks"]))
        freed = req["freed"]
        if n_dead <= freed:
            return
        self._allocator.free(req["blocks"][freed:n_dead])
        self._pool_exhausted_episode = False  # blocks came free
        self.cache["tables"] = \
            self.cache["tables"].at[:, slot, freed:n_dead].set(0)
        req["freed"] = n_dead

    def _roll_window_blocks(self, slot: int, req, in_step: bool = True):
        """A WINDOW KIND's blocks follow the slot (paged_kvcache's module
        docstring): the next step's query stands at `limit` and reads
        (limit - W, limit], so a block wholly at or before limit - W goes
        back to the allocator and its table entry to junk block 0, and
        the blocks ahead, up to the kind's quota, are drawn (never more
        than were just handed back or held in reserve since admission:
        the draw cannot fail). The table edits reach the device before
        the next dispatch (`_flush_window_tables`). While a capture
        records, a roll that hands a block back inside a step's commit is
        a `step.commit.window` span, as the flush is."""
        bp = self._block_len
        limit = req["prompt_len"] + len(req["emitted"]) - 1
        n_need = len(req["blocks"])
        sp = None
        for t, w in self._window_kinds.items():
            got = req["wblocks"][t]
            lo = max(0, limit - w + 1) // bp
            dead = [j for j in got if j < lo]
            if not dead:
                continue
            if sp is None and in_step:
                sp = _profile.open_span("step.commit.window", slot=slot)
            alloc = self._allocator.of(t)
            alloc.free([got.pop(j) for j in dead])
            self.window_blocks_freed += len(dead)
            self._wtab_pending += [(t, slot, j, 0) for j in dead]
            quota = min(n_need - lo, window_blocks(w, bp))
            nxt = max(got, default=lo - 1) + 1
            n_new = min(quota - len(got), n_need - nxt)
            if n_new > 0:
                for j, b in zip(range(nxt, nxt + n_new), alloc.alloc(n_new)):
                    got[j] = b
                    self._wtab_pending.append((t, slot, j, b))
            self._pool_exhausted_episode = False  # blocks came free
        _profile.close_span(sp)

    def _flush_window_tables(self, in_step: bool = True):
        """The pending table edits of window kinds, one program a kind:
        padded to a fixed count (the pad's slot index is out of range and
        is dropped), so nothing compiles after the first. Each launch
        counts (`kv_pool.window_table_flushes_total`)."""
        if not self._window_kinds or not self._wtab_pending:
            return
        sp = _profile.open_span("step.commit.window") if in_step else None
        pend, self._wtab_pending = self._wtab_pending, []
        n = 4 * self.slots
        for t in self._window_kinds:
            mine = [e[1:] for e in pend if e[0] == t]
            for i in range(0, len(mine), n):
                part = np.full((3, n), self.slots, np.int32)
                part[:, :len(mine[i:i + n])] = np.asarray(mine[i:i + n]).T
                self.cache[t] = self._set_tables(self.cache[t], *part)
                self.window_table_flushes += 1
        _profile.close_span(sp)

    def _free_window_kinds(self, req):
        for t, got in (req.get("wblocks") or {}).items():
            self._allocator.of(t).free(list(got.values()))
            got.clear()

    def _constraint_advance(self, slot: int, token: int):
        """HOST MIRROR of the device DFA walk, for finish detection
        only: the device already advanced `crow[slot]` in the step (or
        fused finish) that sampled `token` — this walks the same
        transition on host bookkeeping so retirement logic can ask
        "is the match complete with no continuation?". Sets `c_done`
        when nothing can extend the match and EOS can't express the
        stop (retires as "constraint" — the grammar, not the budget,
        ended the stream). Runs at commit, OFF the dispatch critical
        path: zero per-step host->device constraint traffic."""
        req = self._slot_req[slot]
        c = req.get("constraint")
        if c is None or (self.eos_id is not None and token == self.eos_id):
            return
        ns = c.advance(req["c_state"], token)
        if ns < 0:
            # unreachable when masking works (the sampled token was
            # allowed); defensive stop rather than emitting off-grammar
            req["c_done"] = True
            return
        req["c_state"] = ns
        if not c.has_continuation(ns) and (
                self.eos_id is None or not c.is_accepting(ns)):
            # nothing can extend the match and EOS can't express the stop
            req["c_done"] = True

    # ------------------------------------------------------------------
    # observability helpers (dnn_tpu/obs) — shared by the dense step and
    # the speculative override (serving_spec.SpeculativeBatcher.step)
    # ------------------------------------------------------------------

    def _obs_commit(self, req, m, t_now, n_new: int = 1,
                    samples: Optional[list] = None):
        """Per-slot bookkeeping after committing `n_new` tokens: the
        inter-token clock (a speculative chunk spreads its gap over the
        chunk; samples accumulate into `samples` for the step's ONE bulk
        registry update) and the per-BUCKET decode span — one child per
        cache-view rung a request decodes through (a single span on
        unbucketed pools), closed with token/reason attrs at retire."""
        if m is not None:
            tl = req.get("t_last")
            if tl is not None and samples is not None:
                samples.append((t_now - tl) / max(n_new, 1))
            req["t_last"] = t_now
        else:
            # gate off: clear the clock so a runtime re-enable
            # (obs.set_enabled) doesn't observe the whole disabled gap
            # as one giant inter-token sample
            req["t_last"] = None
        tr = req.get("trace")
        if tr is not None and req.get("b_bucket") != self._cache_len:
            bs = req.get("b_span")
            if bs is not None:
                bs.end(tokens=len(req["emitted"]) - n_new)
            req["b_span"] = tr.child("decode", bucket=self._cache_len)
            req["b_bucket"] = self._cache_len

    def attn_kernel_span(self) -> int:
        """Positions a group of the paged decode kernel covers in the
        decode program traced so far (`PagedKV.kernel_spans`; one leaf
        kind of a pool calls it) — 0 while none does: a dense cache, a
        pool read by gather and einsums, no program yet."""
        codec = getattr(self, "_paged_codec", None)
        return max(codec.kernel_spans.values(), default=0) if codec else 0

    def _bucket_key(self) -> str:
        """Memoized labeled() key for the current bucket — the string
        formatting is measurable on the per-step path."""
        key = self._bucket_keys.get(self._cache_len)
        if key is None:
            key = self._bucket_keys[self._cache_len] = labeled(
                "serving.decode_bucket_dispatch_total",
                bucket=self._cache_len)
        return key

    def _obs_step_end(self, m, n_adv: int, samples: Optional[list] = None):
        """Pool-level series for one completed device step (`n_adv` =
        tokens committed across all slots): counters/samples land in ONE
        bulk registry update, and the pool gauges are CALLABLE — read at
        scrape time from host state. Both choices are load-bearing:
        per-series locking taxes a sub-ms decode step once per series
        instead of once per step, and stored gauges freeze at
        the last step's value on an idle pool (throughput would never
        decay, occupancy would report the retired batch forever)."""
        if m is None:
            return
        # memory high-waters, maintained at step end (slots is small, so
        # this stays inside the bulk-update budget): the gauges above
        # read them at scrape time. One pass over the slots for both
        # live positions and the active count — this runs every step,
        # and a second genexpr sweep would be paid every step too.
        live = 0
        n_act = 0
        blocks = 0  # of a paged pool that hold a live position
        bp = self._block_len if self._paged else 0
        # blocks a group of the paged decode kernel, where a traced decode
        # program calls it; its groups walked, and the full ones
        group = self.attn_kernel_span() // bp if bp else 0
        groups = full_groups = 0
        topk = self._index_topk
        picked = 0  # by an indexer, of the live - n_act it scored
        wins = self._win_kinds
        in_window = [0] * len(wins)
        for r in self._slot_req:
            if r is not None:
                n = r["prompt_len"] + len(r["emitted"])
                live += n
                n_act += 1
                if bp:
                    nb = -(-n // bp)
                    blocks += nb
                    if group:
                        groups += -(-nb // group)
                        full_groups += nb // group
                if topk:
                    # the step's query stood at n - 2: n - 1 candidates
                    picked += min(n - 1, topk)
                elif self._select_counts is not None:
                    picked += self._select_counts(n - 1)
                for i, (_, _, w) in enumerate(wins):
                    in_window[i] += min(n - 1, w)
        if (topk or self._select_counts is not None) \
                and self.step_clock is not None:
            self.step_clock.note_dsa(
                "decode", self._n_index_layers,
                self._n_index_layers * (live - n_act),
                self._n_index_layers * picked)
        if self._cache_kinds and self.step_clock is not None:
            series = "attn" if self._kv_kinds else "mla"
            if topk:
                self.step_clock.note_mla_kind(
                    "decode", "full", self._n_index_layers * picked)
            elif self._kv_kinds:
                # each live slot's query stood at n - 2 and read n - 1 (of
                # which, under a selection by blocks, the chosen blocks')
                self.step_clock.note_mla_kind(
                    "decode", "full", self._n_index_layers * (
                        live - n_act if self._select_counts is None
                        else picked), series=series)
            if self._slot_leaves:
                # a step reads AND writes every slot's state, live or not
                # (`_state_step_bytes`), beside the live K and V it reads —
                # under a selection by blocks the positions chosen, and
                # every live row of the strided leaves it scores
                read = self._kv_position_bytes * (
                    live - n_act if self._select_counts is None else picked)
                if self._select_counts is not None:
                    read += self._strided_position_bytes * (live - n_act)
                self.step_clock.note_state(
                    bytes_read=self._state_step_bytes,
                    bytes_written=self._state_step_bytes,
                    kv_bytes_read=int(read) * self._n_index_layers)
            for (kind, n_l, _), n in zip(wins, in_window):
                self.step_clock.note_mla_kind("decode", kind, n_l * n,
                                              series=series)
        if self._latent and self.step_clock is not None:
            # each live slot's query stood at n - 2 and read n - 1 latents
            self.step_clock.note_mla(
                "decode", self.cfg.n_layer,
                self.cfg.n_layer * (live - n_act), 0)
        if live > self._kv_live_hw:
            self._kv_live_hw = live
        if n_act > self._active_hw:
            self._active_hw = n_act
        if bp and self.step_clock is not None:
            # what the paged decode kernel walks, against what its tables
            # could hold (step.attn_{live,table}_blocks_total)
            self.step_clock.note_attn_blocks(
                blocks, self.slots * (self.max_len // bp))
            if group:
                # every layer that calls the kernel walks them
                self.step_clock.note_attn_groups(
                    self._n_index_layers * groups,
                    self._n_index_layers * full_groups)
        # batched registry feed (fields documented at construction): a
        # bucket switch flushes first so the whole batch shares one
        # dispatch-counter key; an idle pool flushes so totals are
        # exact the moment a drain returns
        bk = self._bucket_key()
        if bk is not self._obs_acc_bk:
            self._obs_flush(m)
            self._obs_acc_bk = bk
        self._obs_acc_steps += 1
        self._obs_acc_tokens += n_adv
        if samples:
            self._obs_acc_samples.extend(samples)
        if self._obs_acc_steps >= self._OBS_FLUSH_STEPS or n_act == 0:
            self._obs_flush(m)
        if (g := self.goodput) is not None:
            # live MFU/MBU numerators + the inter-token SLO window
            # (obs/goodput.py) — `live` is the summed live positions the
            # high-water bookkeeping above already computed
            g.on_decode_step(n_adv, live)
            if samples:
                g.on_inter_token(samples)

    #: step-obs batching cadence — same idea (and number) as
    #: StepClock.FLUSH_EVERY and goodput's _FLUSH_STEPS: a 60 s rate
    #: window and a human scrape cannot resolve a <100 ms batching
    #: delay, and the per-step bulk was the obs bill's largest line
    _OBS_FLUSH_STEPS = 32

    def _obs_flush(self, m):
        """Land the accumulated step counters / inter-token samples in
        ONE bulk registry update. Called by _obs_step_end every
        _OBS_FLUSH_STEPS steps, on a bucket switch (the batch shares
        one dispatch-counter key — _bucket_key memoizes, so the `is`
        check in the caller is exact), and whenever the pool goes idle
        (every drain ends flushed). Producer-thread only."""
        n = self._obs_acc_steps
        if not n:
            return
        if self._obs_acc_tokens:
            self._tps.add(self._obs_acc_tokens)
        samples = self._obs_acc_samples
        m.bulk(
            counters={"serving.decode_steps_total": n,
                      "serving.tokens_total": self._obs_acc_tokens,
                      self._obs_acc_bk: n},
            observations={"serving.inter_token_seconds": samples}
            if samples else None,
            gauge_fns=self._obs_gauges,
        )
        self._obs_acc_steps = 0
        self._obs_acc_tokens = 0
        if samples:
            self._obs_acc_samples = []

    def _tps_read(self) -> float:
        return self._tps.per_sec

    def _occupancy_read(self) -> float:
        return self.n_active / self.slots

    def _kv_util_read(self) -> float:
        # live KV positions over the current allocation; reads host
        # bookkeeping concurrently with the worker — transiently stale
        # values are fine for a gauge, and CPython list iteration over
        # `_slot_req` is safe against its element assignments
        live = sum(r["prompt_len"] + len(r["emitted"])
                   for r in self._slot_req if r is not None)
        return live / (self.slots * self._cache_len)

    def _kv_live_hw_read(self) -> float:
        return float(self._kv_live_hw)

    def _kv_bytes_read(self) -> float:
        # shape/dtype walk only — a scrape must never force a device sync
        from dnn_tpu.obs.mem import logical_nbytes

        return logical_nbytes(self.cache)

    def _active_hw_read(self) -> float:
        return float(self._active_hw)

    def _prefix_ratio_read(self) -> float:
        # lifetime hit ratio of the prefix-cache LOOKUP (prefilled=
        # adoptions never consult it); 0.0 before the first lookup —
        # what "no evidence yet" reads as on every other pool gauge
        looked = self.prefix_hits + self.prefix_misses
        return self.prefix_hits / looked if looked else 0.0

    def _kvtier_blocks_read(self) -> float:
        s = self._prefix_store
        return float(s.n_blocks) if s is not None else 0.0

    def _kvtier_remote_ratio_read(self) -> float:
        # of all block-granular hits, the fraction served from blocks
        # MIGRATED in from a sibling replica — the fleet tier's working
        # number (0.0 on a replica that has never adopted anything)
        s = self._prefix_store
        if s is None or not s.block_hits:
            return 0.0
        return s.remote_block_hits / s.block_hits

    def _paged_used_read(self) -> float:
        return float(self._allocator.n_used)

    def _paged_free_read(self) -> float:
        return float(self._allocator.n_free)

    def _paged_hw_read(self) -> float:
        return float(self._allocator.high_water)

    def _kind_used_read(self, tables: str) -> float:
        return float(self._allocator.of(tables).n_used)

    def _chunks_run_read(self, each: int) -> float:
        return float(self.prefill_chunks_run * each)

    def _pipelined_read(self) -> float:
        return float(self.steps_pipelined)

    def _stale_rows_read(self) -> float:
        return float(self.stale_rows)

    def _window_freed_read(self) -> float:
        return float(self.window_blocks_freed)

    def _window_flushes_read(self) -> float:
        return float(self.window_table_flushes)

    def _obs_retire(self, req, reason: str):
        """Close a leaving request's decode span + outcome counter +
        flight event — the one block _retire_if_done and cancel share."""
        bs = req.get("b_span")
        if bs is not None:
            bs.end(tokens=len(req["emitted"]), reason=reason)
        m = obs.metrics()
        if m is not None:
            m.inc(labeled("serving.requests_total", outcome=reason))
            if (g := self.goodput) is not None:
                # availability SLO: a natural retirement (eos/stop/
                # length/constraint) served its caller; "cancelled"
                # covers both client abandonment and deadline eviction —
                # count it against the budget (the conservative side: a
                # burn alert on mass cancellation is signal, not noise)
                g.on_outcome(ok=reason != "cancelled")
        tr = req.get("trace")
        obs.flight.record("retire", rid=req["rid"], reason=reason,
                          tokens=len(req["emitted"]),
                          trace_id=tr.trace_id if tr else None)

    def _retire_if_done(self, slot: int, in_step: bool = True):
        """Retire `slot`'s request if it has ended. While a capture
        records, the device edits of a retirement inside a step's commit
        (the freed blocks' table entries, the constraint row, the slot's
        `active` flag: one eager program each) are a `step.commit.retire`
        span; submit() retires outside a step (`in_step=False`)."""
        req = self._slot_req[slot]
        reason = None
        if self.eos_id is not None and req["emitted"][-1] == self.eos_id:
            reason = "eos"
        elif (n_stop := self._stop_match(req["emitted"], req["stop"])):
            reason = "stop"
        elif req.get("c_done"):
            reason = "constraint"
        elif len(req["emitted"]) >= req["budget"]:
            reason = "length"
        if reason is None:
            return
        emitted = req["emitted"]
        if reason == "stop":
            emitted = emitted[:-n_stop]  # the match itself is not returned
        rid = req["rid"]
        self.results[rid] = np.asarray(emitted, np.int32)
        self.finish_reasons[rid] = reason
        if req["logprobs"]:
            n = len(emitted)
            self.token_logprobs[rid] = {
                "chosen": np.asarray(req["lp"][:n], np.float32),
                "top_ids": np.stack([t[0] for t in req["lp_top"][:n]])
                if n else np.zeros((0, self._logprobs_k), np.int32),
                "top_logprobs": np.stack([t[1] for t in req["lp_top"][:n]])
                if n else np.zeros((0, self._logprobs_k), np.float32),
            }
        if self._prefix_store is not None \
                and req.get("ptoks") is not None and req["blocks"] \
                and not req["freed"]:
            # retire-time insertion (the chat-follow-up win): this
            # request's transcript KV — prompt plus every FED decode
            # token (the last sampled token was never fed, so its
            # position holds nothing) — is sitting in blocks about to
            # be released. Inserting the full-block path into the
            # radix store keeps them resident, so turn N+1's prompt
            # (= turn N's transcript + the new user message) adopts
            # them instead of re-prefilling the whole conversation.
            fed = req["prompt_len"] + len(req["emitted"]) - 1
            n_cover = min(fed // self._block_len, len(req["blocks"]))
            if n_cover:
                toks = np.concatenate([
                    np.asarray(req["ptoks"], np.int32),
                    np.asarray(req["emitted"][:-1], np.int32)])
                self._prefix_store.insert(
                    toks[: n_cover * self._block_len],
                    req["blocks"][:n_cover],
                    origin=req.get("borig") or [])
        if req["blocks"]:
            # windowed pools already reclaimed the rolled-out prefix
            self._allocator.free(req["blocks"][req["freed"]:])
            self._free_window_kinds(req)
            self._pool_exhausted_episode = False  # blocks came free
        sp = _profile.open_span("step.commit.retire", rid=rid, slot=slot) \
            if in_step else None
        self._release_slot_constraint(slot, req)
        self._slot_req[slot] = None
        self.active = self.active.at[slot].set(False)
        _profile.close_span(sp)
        self._obs_retire(req, reason)

    def _note_constrained(self, delta: int):
        """Track the live constrained-slot count and mirror it onto the
        attached StepClock's scrape-time gauge (obs/timeline.py)."""
        self._n_constrained += delta
        sc = self.step_clock
        if sc is not None:
            sc.constrained_slots = self._n_constrained

    def _release_slot_constraint(self, slot: int, req: dict):
        """Drop a retiring slot's constraint: refcount down, device
        state-row back to the reserved all-allowed row 0 (a functional
        edit of the CURRENT crow buffer — under overlap that is the
        in-flight step's OUTPUT, already unpacked at dispatch, so the
        reset lands before the next dispatch reads it)."""
        c = req.get("constraint")
        if c is None:
            return
        self._ctab_release(c)
        self._crow = self._crow.at[slot].set(0)
        self._note_constrained(-1)

    def claim(self, rid: int):
        """Pop a finished (or cancelled) request's COMPLETE record —
        (tokens or None, finish_reason, token_logprobs or None) —
        releasing all host-side bookkeeping for it. Long-running servers
        (the LM daemon) must claim rather than read `results` directly,
        or the per-request dicts grow without bound. A cancelled rid
        yields (None, "cancelled", None). KeyError for an
        unknown/unfinished rid."""
        tokens = self.results.pop(rid, None)
        reason = self.finish_reasons.pop(rid, None)
        lps = self.token_logprobs.pop(rid, None)
        if tokens is None and reason is None:
            raise KeyError(rid)
        return tokens, reason or "length", lps

    def first_token(self, rid: int):
        """The token sampled during a request's prefill (the first entry of
        its emitted stream), or None for an unknown rid — the streaming
        front needs it before the first step() (budget == 1 requests are
        already retired into results by then)."""
        if rid in self.results:
            return int(self.results[rid][0])
        for req in self._slot_req:
            if req is not None and req["rid"] == rid:
                if not req["emitted"]:
                    # interleaved admission: still prefilling, or the
                    # fused finish's first token has not committed yet —
                    # the caller picks it up from a later step()'s output
                    return None
                return int(req["emitted"][0])
        return None

    def cancel(self, rid: int) -> bool:
        """Retire a request's slot WITHOUT producing a result — the slot
        re-enters the free pool immediately (the next admit overwrites its
        cache rows; nothing needs clearing because inactive slots are fully
        masked in the decode program). Safe between step() calls (host
        bookkeeping only). Returns True if the request was live (slot
        freed) or still unclaimed in results (result dropped); False for
        an unknown/already-claimed rid."""
        for slot, req in enumerate(self._slot_req):
            if req is not None and req["rid"] == rid:
                if req.get("pending") is not None:
                    # cancelled while its interleaved prefill waited:
                    # drop the queue entry too (the chunk folder skips
                    # dead slots defensively, but never growing the
                    # queue with corpses is cheaper)
                    self._pending_q = [s for s in self._pending_q
                                       if s != slot]
                if req["blocks"]:
                    self._allocator.free(req["blocks"][req["freed"]:])
                    self._free_window_kinds(req)
                    self._pool_exhausted_episode = False  # blocks came free
                self._release_slot_constraint(slot, req)
                self._slot_req[slot] = None
                self.active = self.active.at[slot].set(False)
                self.finish_reasons[rid] = "cancelled"
                self._obs_retire(req, "cancelled")
                return True
        if rid in self.results:
            # cancelling an already-finished, unclaimed request drops its
            # WHOLE record (reason + logprobs too, or they leak forever)
            del self.results[rid]
            self.finish_reasons.pop(rid, None)
            self.token_logprobs.pop(rid, None)
            return True
        return False

    def _ilv_next(self):
        """Front pending slot's next-chunk descriptor, or None. Skips
        (and dequeues) slots whose pending request was cancelled while
        it waited."""
        while self._pending_q:
            slot = self._pending_q[0]
            req = self._slot_req[slot]
            if req is None or req.get("pending") is None:
                self._pending_q.pop(0)
                continue
            p = req["pending"]
            c = p["next"]
            p_c = self._ilv
            return {"slot": slot, "req": req, "p": p,
                    "chunk": p["padded"][:, c * p_c:(c + 1) * p_c],
                    "start": np.int32(c * p_c),
                    "last": c + 1 == p["n_chunks"]}
        return None

    def _ilv_after_chunk(self, ilv, pf_hidden, new_row, s_idx):
        """Bookkeeping after a mixed step's prefill leg: stash the grown
        row, or — on the final chunk — dispatch the FUSED finish
        (install + on-device first-token sample + slot-state scatter,
        one program) and defer the first token's readback to the next
        step's commit: admission never blocks on a device->host sync."""
        req, p = ilv["req"], ilv["p"]
        self.prefill_chunks_run += 1
        m = obs.metrics()
        if m is not None:
            m.inc("serving.prefill_chunks_total")
        if not ilv["last"]:
            p["row"] = new_row
            p["next"] += 1
            return
        self._pending_q.pop(0)
        first, first_lps = self._finish(new_row, pf_hidden, p["finish"])
        req["first_dev"] = (first, first_lps if req["logprobs"] else None)
        req["install_step"] = s_idx
        del req["pending"]

    def _commit_step(self, s_idx, toks, c_lp, t_lp, t_ids, rec, sc,
                     n_rows: int = 0):
        """Commit one completed step's tokens to host bookkeeping.
        `s_idx` names the DISPATCH this data came from: a slot whose
        fused admission finish landed at install_step >= s_idx had no
        decode leg in that dispatch, so its row of `toks` is garbage
        and is skipped; the first commit past the install materializes
        the deferred first token (and its logprobs) ahead of the
        step's own token. Returns {rid: token | [tokens]} (a list when
        the deferred first commits together with a decode token).
        `n_rows`: the slot-rows that were live when the pipeline
        dispatched this step; those no commit takes are stale rows."""
        m = obs.metrics()
        t_now = time.perf_counter() if m is not None else 0.0
        n_adv = 0
        n_dec = 0  # rows of `toks` committed
        it_samples: list = []
        out = {}
        for slot, req in enumerate(self._slot_req):
            if req is None or req.get("pending") is not None:
                continue
            inst = req.get("install_step")
            committed: list = []
            if inst is not None:
                if s_idx <= inst:
                    continue  # this step's dispatch predates the install
                del req["install_step"]
                fd = req.pop("first_dev", None)
                if fd is not None:  # deferred interleaved first token
                    first, f_lp = fd
                    tok0 = int(np.asarray(first))
                    req["emitted"].append(tok0)
                    if req["logprobs"]:
                        req["lp"].append(float(np.asarray(f_lp[0])[0]))
                        req["lp_top"].append(
                            (np.asarray(f_lp[2])[0],
                             np.asarray(f_lp[1])[0]))
                    committed.append(tok0)
                    if m is not None and (g := self.goodput) is not None:
                        # prefill goodput is credited when its first
                        # token commits (the convoy path: at submit)
                        g.on_prefill(req["prompt_len"])
                    if "constraint" in req:
                        # host mirror of the walk the fused finish
                        # already did on device (finish detection only)
                        self._constraint_advance(slot, tok0)
                    self._free_rolled_blocks(slot)
                    self._retire_if_done(slot)
            if self._slot_req[slot] is req:
                token = int(toks[slot])
                n_dec += 1
                req["emitted"].append(token)
                if req["logprobs"]:
                    req["lp"].append(float(c_lp[slot]))
                    req["lp_top"].append((t_ids[slot], t_lp[slot]))
                committed.append(token)
                self._obs_commit(req, m, t_now, n_new=len(committed),
                                 samples=it_samples)
                if "constraint" in req:
                    # host mirror of the device walk — finish
                    # detection only, never a device write
                    self._constraint_advance(slot, token)
                self._free_rolled_blocks(slot)  # windowed pools reclaim
                self._retire_if_done(slot)
            if committed:
                n_adv += len(committed)
                out[req["rid"]] = (committed[0] if len(committed) == 1
                                   else committed)
        if n_rows > n_dec:
            self.stale_rows += n_rows - n_dec
        self._flush_window_tables()
        if rec is not None:
            sc.mark(rec, "commit")
        self._obs_step_end(m, n_adv, it_samples)
        if self._moe_stats:
            self._moe_flush(s_idx)  # this dispatch's tokens are here
        if rec is not None:
            sc.mark(rec, "obs")
            sc.end(rec, n_adv)
        return out

    def _lp_host(self, lp_refs):
        if lp_refs is None:
            return None, None, None
        return (np.asarray(lp_refs[0]), np.asarray(lp_refs[1]),
                np.asarray(lp_refs[2]))

    def _uncommitted_need(self, lag_per_step: int) -> int:
        """Furthest position count the next dispatch's writes need
        covered, including tokens the host has NOT committed yet: a
        deferred interleaved first token, plus `lag_per_step` positions
        per uncommitted in-flight step under overlap (1 for the dense
        step, spec_k+1 for a speculative chunk). One definition shared
        by both step loops — an under-grown bucket silently clamps the
        device write, so this formula must not drift per batcher.
        Returns 0 when nothing decodes (pending-only pools)."""
        need = 0
        for req in self._slot_req:
            if req is None or req.get("pending") is not None:
                continue
            u = 1 if "first_dev" in req else 0
            need = max(need, req["prompt_len"] + len(req["emitted"]) + u)
        if need and self._inflight is not None:
            need += lag_per_step
        return need

    def _pipeline_fill_end(self, rec, sc):
        """Close a step record for a pipeline-FILLING dispatch (the
        overlap pipeline's first call: a step went out, nothing commits
        yet) — shared by the dense and speculative step loops so the
        StepClock phase protocol stays identical across batchers."""
        if rec is not None:
            sc.mark(rec, "wait")
            sc.mark(rec, "commit")
        self._obs_step_end(obs.metrics(), 0, None)
        if rec is not None:
            sc.mark(rec, "obs")
            sc.end(rec, 0)
        return {}

    def flush_overlap(self) -> Dict[int, int]:
        """Commit the trailing in-flight step (overlap mode); {} and a
        no-op otherwise. drain() calls it once the pool empties, and
        the idle lm_server worker calls it so the final dispatched
        step's bookkeeping (its StepClock record, tokens past
        retirement) never dangles across an idle period."""
        if self._inflight is None:
            return {}
        sc = self.step_clock
        rec = sc.begin("wait") if sc is not None else None
        p_idx, p_tok, p_lps, p_rows = self._inflight
        self._inflight = None
        toks = np.asarray(p_tok)
        c_lp, t_lp, t_ids = self._lp_host(p_lps)
        if rec is not None:
            sc.mark(rec, "wait")
        return self._commit_step(p_idx, toks, c_lp, t_lp, t_ids, rec, sc,
                                 p_rows)

    def step(self) -> Dict[int, int]:
        """One decode step for every active slot. Returns {rid: token}
        for slots that advanced ({rid: [tokens]} when an interleaved
        admission's deferred first token commits in the same call);
        finished requests move to .results. With overlap=True the call
        DISPATCHES step N and commits step N-1 — tokens surface one
        call later (drain()/flush_overlap() commit the trailing step)."""
        if self.n_active == 0:
            return self.flush_overlap()
        # step-timeline phase clock (obs/timeline.py): rec is None when
        # no clock is attached OR the obs gate is off — every later
        # site is one None check
        sc = self.step_clock
        rec = sc.begin() if sc is not None else None
        if self._buckets is not None:
            # this step writes each active slot's next position; cover
            # the furthest one, host-uncommitted tokens included
            # (_uncommitted_need: deferred interleaved firsts + one
            # position per in-flight step under overlap)
            need = self._uncommitted_need(1)
            if need:
                self._ensure_cache_len(need)
        ilv = self._ilv_next() if self._ilv else None
        if rec is not None:
            sc.mark(rec, "host")
        # (while a POST /profilez capture records, the clock writes each
        # phase into it as a `step.<phase>` annotation: `rec.spans`,
        # obs/timeline._StepSpans — `step.dispatch` is this call)
        # one shared positional block for both dispatch forms — the
        # mixed program's decode leg takes the decode step's exact
        # argument order (donate_argnums indices align by construction)
        state = self._slot_state() + (self._ctable, self._ctrans)
        if ilv is None:
            res = self._decode(self._decode_view, *state)
        else:
            res = self._mixed(
                self._decode_view,
                self._lora_prefill_view(ilv["p"]["aid"]), *state,
                ilv["p"]["row"], ilv["chunk"], ilv["start"])
            if self._moe_stats:
                res, pf_moe = res[:-1], res[-1]
            res, pf_hidden, new_row = res[:-2], res[-2], res[-1]
        # drop the tuple's references to the just-donated buffers NOW:
        # holding them to frame teardown makes their deletion run after
        # the step record closes, and deleting a donated-but-pending
        # buffer blocks on the in-flight computation: about a device
        # step of time per call that no phase accounts for
        # (tests/test_obs_timeline.py's coverage bound)
        del state
        if rec is not None:
            sc.mark(rec, "dispatch")
            rec.mixed = ilv is not None
        s_idx = self._step_idx
        self._step_idx += 1
        if self._overlap:
            res, snap = res[:-1], res[-1]  # the core's last result
        if self._moe_stats:
            self._moe_note("decode", res[-1], s_idx)
            res = res[:-1]
            if ilv is not None:
                self._moe_note("prefill", pf_moe, s_idx)
        lp_refs = None
        if self._logprobs_k:
            (self.cache, self.pos, self.tok, self.keys, self._seen,
             self._crow, c_lp_d, t_lp_d, t_ids_d) = res
            lp_refs = (c_lp_d, t_lp_d, t_ids_d)
        else:
            (self.cache, self.pos, self.tok, self.keys, self._seen,
             self._crow) = res
        if ilv is not None:
            self._ilv_after_chunk(ilv, pf_hidden, new_row, s_idx)
        if self._overlap:
            if sc is not None:
                sc.overlap_depth = 1
            # THIS step's tokens are read a dispatch later, when the next
            # step has taken `self.tok`'s buffer by donation: `snap` is
            # the program's own second copy of them (the decode core's
            # last result — no launch of its own). The logprob outputs
            # are never fed back (hence never donated) — bare refs
            # suffice. The rows live now are what the commit counts its
            # stale rows against.
            keep = (s_idx, snap, lp_refs,
                    sum(r is not None and "pending" not in r
                        and r.get("install_step") != s_idx
                        for r in self._slot_req))
            prev, self._inflight = self._inflight, keep
            if prev is None:
                return self._pipeline_fill_end(rec, sc)
            self.steps_pipelined += 1
            p_idx, p_tok, p_lps, p_rows = prev
            toks = np.asarray(p_tok)
            c_lp, t_lp, t_ids = self._lp_host(p_lps)
            if rec is not None:
                # with the pipeline live, "wait" is only the RESIDUAL
                # unhidden device time of step N-1
                sc.mark(rec, "wait")
            return self._commit_step(p_idx, toks, c_lp, t_lp, t_ids,
                                     rec, sc, p_rows)
        toks = np.asarray(self.tok)
        c_lp, t_lp, t_ids = self._lp_host(lp_refs)
        if rec is not None:
            # the np.asarray above is the per-token device->host sync:
            # dispatch-return -> committed-tokens-on-host is the "wait"
            sc.mark(rec, "wait")
        return self._commit_step(s_idx, toks, c_lp, t_lp, t_ids, rec, sc)

    def drain(self) -> Dict[int, np.ndarray]:
        """Run until every submitted request finishes; returns .results."""
        while self.n_active:
            self.step()
        self.flush_overlap()
        return self.results
