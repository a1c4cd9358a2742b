"""Speculative CONTINUOUS batching: draft-assisted decode inside the slot
pool.

`runtime/speculative.py` breaks decode's serial chain for ONE stream (its
batch-1 check points here for throughput); this module lifts the same
construction into the continuous batcher, where it was the one serving
feature that didn't compose (README's composition matrix). The insight
that makes it fit: the batcher already tracks PER-ROW positions, and the
solo design's core trick — "variable acceptance exists only as an
integer, never as a shape" — vectorizes to a (B,) integer: every step,
ALL active slots propose k draft tokens, the target verifies every row's
k+1 positions in one forward, and each slot commits its own m+1 <= k+1
tokens. Static shapes throughout; rejected proposals roll back by not
advancing that row's position (their stale cache entries sit beyond the
per-row attention limit, exactly as in the solo loop and the chunked
prefill's tail pad).

Per step, one compiled program (`spec_step`) runs:
  1. draft sync: idempotent re-feed of each row's previous verify chunk
     at its old positions (fills exactly the draft-cache entries that
     could be missing; recomputing present ones is a no-op);
  2. k draft decode steps propose (B, k) tokens (greedy, or sampled from
     the draft's filtered distribution with each slot's own rng stream);
  3. one target verify over the (B, k+1) chunks [last, p1..pk] at
     per-row positions (GPTFamilyRows.verify_rows);
  4. per-row acceptance — greedy: longest prefix where the draft matches
     the target's argmax (output tokens ARE the target's picks, so
     greedy results are token-identical to the plain batcher: the parity
     contract tests/test_serving_spec.py pins); sampled: the
     rejection-sampling construction of Leviathan et al. 2023 (accept
     with min(1, p_t/p_d), resample the first rejection from the
     normalized residual, bonus sample when all accepted), vectorized
     over rows;
  5. per-row commit: pos += m+1 (inactive rows 0), last = w[m], and the
     (B, k+1) committed-token block + (B,) counts return to the host,
     which appends each slot's tokens (budget/stop/eos checks run per
     token, so a mid-chunk stop retires the slot and discards the rest).

Restrictions (all checked at construction/submit): target and draft
with equal vocabularies — any FAMILY pair works (GPT default; pass
family=/draft_family= adapters with verify_rows, e.g.
llama.LlamaFamilyRows, including cross-family GPT-draft-for-LLaMA
-target), as long as both attend dense (no sliding window / softcap);
float caches (the solo module's reasoning: chunked re-feeds would
re-quantize int8 rows differently from the oracle path), dense
(non-paged) pool, server-level temperature/top_k (the rejection math
runs one distribution transform for the whole pool; per-request
sampling overrides are the dense batcher's feature), prompts of at
least k+1 tokens (the first sync chunk re-feeds the prompt tail), and
len(prompt) + max_new + k <= max_len (verify writes up to k positions of
scratch beyond the last committed token).

`decode_buckets=` COMPOSES (ISSUE 6): the target pool grows through
the ladder exactly as the dense batcher's, the draft pool grows in
lockstep, and every grow covers the verify chunk's +k scratch
(_ensure_cache_len). The spec programs re-trace once per ladder rung —
the same bounded relaxation of the program-count contract the dense
bucketed step accepted in PR 1 — and greedy token identity to the
UNBUCKETED spec pool (and hence to the plain batcher) holds by the
bucket-view argument: a rung differs from the full allocation only in
columns beyond every row's band limit. Acceptance-weighted tokens/step
now multiplies the bucketed bytes/step win instead of forfeiting it
(tests/test_spec_buckets.py pins parity through rung crossings).

The reference framework has no decode at all (SURVEY §3.2); this is the
deepest point of the serving stack built beyond it.
"""

from __future__ import annotations

import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from dnn_tpu import obs
from dnn_tpu.models.gpt import GPTConfig, prepare_stacked  # noqa: F401
from dnn_tpu.runtime.kvcache import codec_for_cache
from dnn_tpu.runtime.serving import (ContinuousBatcher, GPTFamilyRows,
                                     install_dense_row)
# the ONE sampling transform shared with the solo speculative loop:
# rejection sampling is only exact when draft and target use the
# identical transform, so both paths must import the same function
from dnn_tpu.runtime.speculative import _probs

__all__ = ["SpeculativeBatcher"]


class SpeculativeBatcher(ContinuousBatcher):
    """ContinuousBatcher whose step() advances every active slot by UP TO
    k+1 tokens per call via draft-model speculation. Submit/retire/stop/
    finish-reason surfaces are inherited unchanged."""

    # a verified chunk commits up to k+1 tokens in one device call —
    # per-token grammar masks cannot gate it (submit rejects constraint=)
    _constraints_ok = False

    def __init__(self, cfg: GPTConfig, prepared, draft_cfg: GPTConfig,
                 draft_prepared, *, spec_k: int = 4, draft_family=None,
                 **kw):
        if cfg.vocab_size != draft_cfg.vocab_size:
            raise ValueError(
                f"draft vocab {draft_cfg.vocab_size} != target vocab "
                f"{cfg.vocab_size}")
        if getattr(kw.get("family"), "requires_paged", False):
            raise ValueError(
                "speculative decoding is not available with this model: "
                "its verify step attends every cached position and its "
                "codecs assume K and V alone, where this model's cache "
                "has the leaves "
                + "/".join(kw["family"].cache_leaves))
        if kw.get("kv") == "paged":
            raise ValueError(
                "SpeculativeBatcher pins the dense pool (the spec codecs "
                "attend dense; paged x speculative is not composed)")
        if kw.get("kv") == "auto":
            # the serving-path default resolves to dense here — the
            # parent's auto-paging would hand the spec codecs a block
            # pool they cannot attend. Recorded like every other auto
            # fallback (the README's kv contract: a fallback always
            # leaves a flight event saying why).
            from dnn_tpu import obs

            obs.flight.record(
                "kv_fallback_dense",
                reason="speculative serving pins the dense pool")
            kw["kv"] = "dense"
        for bad in ("ffn", "paged_blocks", "logprobs_k",
                    "attn_kernel", "top_p", "min_p", "repetition_penalty",
                    "lora_adapters", "allow_constraints"):
            # allow_constraints would allocate the constraint_rows-row
            # device mask and transition pools for a batcher that rejects
            # every constrained submit (_constraints_ok=False) — fail at
            # construction, not per request
            val = kw.get(bad)
            if val and not (bad == "attn_kernel" and val == "auto"):
                # "auto" is ContinuousBatcher's default mode, not an
                # opt-in: spelling the default out loud is not an error
                raise ValueError(
                    f"SpeculativeBatcher does not support {bad}=")
        # ...but the unsupported kernel path must also not sneak in via
        # the "auto" default on long pools (max_len >= AUTO_KERNEL_MIN_S
        # on TPU would engage it): pin the einsum explicitly
        kw["attn_kernel"] = False
        if kw.get("kv_dtype") == "int8":
            raise ValueError(
                "SpeculativeBatcher pins float caches (chunked re-feeds "
                "would re-quantize int8 rows differently from the oracle "
                "path — see runtime/speculative.py)")
        super().__init__(cfg, prepared, **kw)
        if draft_cfg.block_size < self.max_len:
            # draft positions run to max_len-1 (submit's budget check);
            # past its wpe table the position gather would silently clamp
            # and acceptance would collapse with no error anywhere
            raise ValueError(
                f"draft block_size {draft_cfg.block_size} < max_len "
                f"{self.max_len}; shrink max_len or use a longer draft")
        self.spec_k = int(spec_k)
        if self.spec_k < 1:
            raise ValueError(f"spec_k must be >= 1, got {spec_k}")
        self.draft_cfg = draft_cfg
        self.draft_prepared = draft_prepared
        self._temperature = float(kw.get("temperature", 0.0) or 0.0)
        self._top_k_opt = kw.get("top_k")
        self._greedy = self._temperature == 0.0

        k = self.spec_k
        cache_dtype = self.cache["k"].dtype
        # family adapters generalize the pair beyond GPT: any adapter
        # with verify_rows (llama.LlamaFamilyRows included) serves as
        # target (kw family=) or draft (draft_family=) — cross-family
        # pairs only need matching vocabularies. Windowed/softcapped
        # families are rejected: the spec codecs attend dense.
        if draft_family is None and not isinstance(draft_cfg, GPTConfig):
            # defaulting a LLaMA-class draft onto the GPT adapter would
            # fail deep inside the jitted spec_step trace (missing wpe,
            # no ln_eps) — fail at construction with the fix instead
            raise ValueError(
                f"draft_cfg is {type(draft_cfg).__name__}, not GPTConfig "
                "— pass draft_family= (e.g. llama.LlamaFamilyRows("
                "draft_cfg)) for non-GPT drafts")
        d_family = draft_family or GPTFamilyRows(
            draft_cfg, compute_dtype=self.family.compute_dtype)
        for fam, which in ((self.family, "target"), (d_family, "draft")):
            # paged_ok is the family's "attends plain causal" capability
            # flag (False for window/softcap/alt-window configs —
            # llama.LlamaFamilyRows) — exactly the condition the dense
            # spec codecs need; absent attribute (GPT) means True
            if not getattr(fam, "paged_ok", True):
                raise ValueError(
                    f"speculative serving supports dense-attention "
                    f"families only (the {which} family has a sliding "
                    "window or attention softcap)")
            if not hasattr(fam, "verify_rows"):
                raise ValueError(
                    f"the {which} family adapter has no verify_rows — "
                    "speculative serving needs the per-row block-verify "
                    "program")
        # the draft needs the same scratch headroom past max_len the
        # target gets via the submit budget check (verify/propose write
        # up to k positions beyond the last committed token). On a
        # bucketed pool (decode_buckets= now composes — the spec
        # programs re-trace once per ladder rung, the same bounded
        # relaxation the dense step accepted in PR 1) the draft cache
        # starts at the target's first bucket and grows in LOCKSTEP
        # through _ensure_cache_len, so both sides' verify blocks always
        # cover pos + k.
        self.d_cache = d_family.init_cache(self.slots, self._cache_len,
                                           cache_dtype)
        self._d_family = d_family
        d_codec = codec_for_cache(self.d_cache)
        t_codec = codec_for_cache(self.cache)
        t_family = self.family

        # per-slot draft-sync chunk: the previous verify block + its start
        self.prev_chunk = jnp.zeros((self.slots, k + 1), jnp.int32)
        self.prev_pos = jnp.zeros((self.slots,), jnp.int32)
        # acceptance telemetry
        self.spec_steps = 0
        self.spec_proposed = 0
        self.spec_accepted = 0

        greedy = self._greedy
        temperature, top_k = self._temperature, self._top_k_opt

        def _spec_core(t_prepared, d_prepared, t_cache, d_cache, tok, pos,
                       active, keys, prev_chunk, prev_pos):
            b = tok.shape[0]
            # 1. draft sync (write-only; logits discarded)
            _, d_cache = d_family.verify_rows(
                d_prepared, d_cache, prev_chunk, prev_pos, active, d_codec)

            # 2. k draft proposal steps
            def d_step(carry, i):
                cache, last, kk = carry
                logits, cache = d_family.decode_rows(
                    d_prepared, cache, last, pos + i, active, d_codec)
                if greedy:
                    nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                    rows = jnp.zeros((b, 1), jnp.float32)  # placeholder
                    new_k = kk
                else:
                    split = jax.vmap(jax.random.split)(kk)
                    new_k, subs = split[:, 0], split[:, 1]
                    rows = _probs(logits, temperature=temperature,
                                  top_k=top_k)  # (B, V)
                    nxt = jax.vmap(
                        lambda r, s: jax.random.categorical(s, jnp.log(r))
                    )(rows, subs).astype(jnp.int32)
                nxt = jnp.where(active, nxt, last)
                return (cache, nxt, new_k), (nxt, rows)

            (d_cache, _, keys), (props_t, d_rows_t) = lax.scan(
                d_step, (d_cache, tok, keys), jnp.arange(k))
            props = jnp.moveaxis(props_t, 0, 1)      # (B, k)
            d_rows = jnp.moveaxis(d_rows_t, 0, 1)    # (B, k, V) or (B,k,1)

            # 3. target verify over [last, p1..pk]
            chunk = jnp.concatenate([tok[:, None], props], axis=1)
            t_logits, t_cache = t_family.verify_rows(
                t_prepared, t_cache, chunk, pos, active, t_codec)
            rows = t_logits  # (B, k+1, V); row i predicts pos+i+1

            if greedy:
                t_toks = jnp.argmax(rows, axis=-1).astype(jnp.int32)
                match = props == t_toks[:, :k]
                m = jnp.where(match.all(axis=1), k,
                              jnp.argmax(~match, axis=1)).astype(jnp.int32)
                w = t_toks  # (B, k+1): committed tokens ARE target picks
            else:
                split = jax.vmap(lambda kk: jax.random.split(kk, 3))(keys)
                keys, r_acc, r_rep = split[:, 0], split[:, 1], split[:, 2]
                t_dist = _probs(rows, temperature=temperature, top_k=top_k)
                idx = jnp.arange(k)
                t_probs = jnp.take_along_axis(
                    t_dist[:, :k], props[:, :, None], axis=2)[..., 0]
                d_probs = jnp.take_along_axis(
                    d_rows, props[:, :, None], axis=2)[..., 0]
                ratio = t_probs / jnp.maximum(d_probs, 1e-30)
                u = jax.vmap(lambda r: jax.random.uniform(r, (k,)))(r_acc)
                accept = u < jnp.minimum(ratio, 1.0)  # (B, k)
                m = jnp.where(accept.all(axis=1), k,
                              jnp.argmax(~accept, axis=1)).astype(jnp.int32)
                d_row_m = jnp.where(
                    (m < k)[:, None],
                    jnp.take_along_axis(
                        d_rows, jnp.minimum(m, k - 1)[:, None, None],
                        axis=1)[:, 0],
                    jnp.zeros_like(d_rows[:, 0]))
                t_row_m = jnp.take_along_axis(
                    t_dist, m[:, None, None], axis=1)[:, 0]
                resid = jnp.maximum(t_row_m - d_row_m, 0.0)
                z = resid.sum(axis=-1, keepdims=True)
                resid = jnp.where(z > 0, resid / jnp.maximum(z, 1e-30),
                                  t_row_m)
                rep = jax.vmap(
                    lambda r, s: jax.random.categorical(s, jnp.log(r))
                )(resid, r_rep).astype(jnp.int32)
                props_ext = jnp.concatenate(
                    [props, jnp.zeros((b, 1), jnp.int32)], axis=1)
                w = jnp.where(jnp.arange(k + 1)[None, :] == m[:, None],
                              rep[:, None], props_ext)

            committed = jnp.where(active, m + 1, 0)
            last = jnp.take_along_axis(w, m[:, None], axis=1)[:, 0]
            last = jnp.where(active, last, tok)
            new_prev_chunk = jnp.where(active[:, None], chunk, prev_chunk)
            new_prev_pos = jnp.where(active, pos, prev_pos)
            return (t_cache, d_cache, last, pos + committed, keys,
                    new_prev_chunk, new_prev_pos, w, m)

        def spec_step(t_prepared, d_prepared, t_cache, d_cache, tok, pos,
                      active, keys, prev_chunk, prev_pos):
            return _spec_core(t_prepared, d_prepared, t_cache, d_cache,
                              tok, pos, active, keys, prev_chunk,
                              prev_pos)

        # donate BOTH caches and every per-slot vector the step returns
        # (tok, pos, keys, prev_chunk, prev_pos) — `active` is read-only
        # through the step and host-updated between calls, so it stays
        # undonated. Aliasing coverage is asserted by the analysis gate
        # (analysis/program.audit_serving_decode).
        self._spec_step = jax.jit(spec_step,
                                  donate_argnums=(2, 3, 4, 5, 7, 8, 9))

        # interleaved chunked prefill (ISSUE 12), speculative shape: the
        # spec step program grows BOTH prefill legs — one target chunk
        # and one draft chunk for the admitting request fold into the
        # same compiled program as every active slot's draft/verify
        # round, and the fused finish installs both rows, samples the
        # first token on device, and seeds the draft-sync state
        # (prev_chunk/prev_pos) in one dispatch.
        self._spec_mixed = None
        self._spec_ilv_finish = None
        if self._ilv:
            def spec_mixed(t_prepared, d_prepared, t_cache, d_cache,
                           tok, pos, active, keys, prev_chunk, prev_pos,
                           row, d_row, chunk, chunk_start):
                out = _spec_core(t_prepared, d_prepared, t_cache,
                                 d_cache, tok, pos, active, keys,
                                 prev_chunk, prev_pos)
                pf_hidden, new_row = t_family.prefill(
                    t_prepared, chunk, row, chunk_start)
                _, new_d_row = d_family.prefill(
                    d_prepared, chunk, d_row, chunk_start)
                return out + (pf_hidden, new_row, new_d_row)

            self._spec_mixed_donate = (2, 3, 4, 5, 7, 8, 9, 10, 11)
            self._spec_mixed = jax.jit(
                spec_mixed, donate_argnums=self._spec_mixed_donate)

            parent_fin = self._finish_core
            # its arguments: the slot state, then row, hidden, the head's
            # leaves, ints and six more (serving.prefill_finish)
            i_ints = len(self._slot_state()) + 3
            n_core = i_ints + 7
            kk1 = k + 1

            def spec_ilv_finish(*args):
                """The parent's finish-and-install (its arguments
                first, unchanged), then `d_cache, prev_chunk, prev_pos,
                d_row, tail`: the draft row installs beside the target's
                and the slot's draft-sync state is seeded in the same
                dispatch. Returns the parent's results, then the three
                draft-side buffers."""
                core = args[:n_core]
                d_cache, prev_chunk, prev_pos, d_row, tail = args[n_core:]
                slot, prompt_len = core[i_ints][0], core[i_ints][2]
                # draft-row install: the one shared clamped install
                # (serving.install_dense_row)
                d_cache = install_dense_row(d_cache, d_row, slot)
                # first sync chunk: the prompt's own tail at its own
                # positions — an exact no-op re-feed
                prev_chunk = prev_chunk.at[slot].set(tail)
                prev_pos = prev_pos.at[slot].set(prompt_len - kk1)
                return parent_fin(*core) + (d_cache, prev_chunk, prev_pos)

            # the parent's donations (the spec batcher never enables
            # constraints, so crow passes through undonated) and the
            # three draft-side buffers
            self._spec_ilv_finish_donate = self._finish_donate + (
                n_core, n_core + 1, n_core + 2)
            self._spec_ilv_finish = jax.jit(
                spec_ilv_finish,
                donate_argnums=self._spec_ilv_finish_donate)

        # draft-side chunked prefill (the target side reuses the parent's
        # programs); the install is the parent's dense slice-install
        # shape, clamped at the CACHE's current position count (the
        # bucketed draft pool may sit below max_len — the row's overhang
        # holds nothing but tail-pad garbage, exactly as in
        # serving.prefill_finish)
        def d_prefill_chunk(prepared, row, chunk, chunk_start):
            return d_family.prefill(prepared, chunk, row, chunk_start)

        def d_install(cache, row, slot):
            return install_dense_row(cache, row, slot)

        self._d_prefill_chunk = jax.jit(d_prefill_chunk,
                                        donate_argnums=(1,))
        # the row (arg 1) is sliced, never returned whole — donating it
        # would alias nothing (serving.py's prefill_finish lesson)
        self._d_install = jax.jit(d_install, donate_argnums=(0,))

    # ------------------------------------------------------------------

    def _ensure_cache_len(self, need: int):
        """Bucketed growth with the spec path's scratch headroom: the
        verify/propose chunk writes up to spec_k positions past the last
        committed token, so every grow covers `need + k` — and the DRAFT
        pool grows in lockstep (both sides' chunks write the same
        positions). The submit budget check (prompt + max_new + k <=
        max_len) guarantees the padded need never exceeds the ladder
        top."""
        if self._buckets is None:
            return
        super()._ensure_cache_len(min(need + self.spec_k, self.max_len))
        d_len = jax.tree.leaves(self.d_cache)[0].shape[3]
        if d_len < self._cache_len:
            self.d_cache = self._grow_cache(self.d_cache, self._cache_len)

    def jit_programs(self):
        """Parent programs plus the spec path's own — a speculative
        daemon's compile-cache budget must count the programs it
        actually churns (_d_prefill_chunk recompiles per prompt-length
        bucket, exactly like the parent's chunk program)."""
        fns = super().jit_programs() + [
            self._spec_step, self._d_prefill_chunk, self._d_install]
        if self._spec_mixed is not None:
            fns += [self._spec_mixed, self._spec_ilv_finish]
        return fns

    def submit(self, prompt, max_new_tokens: int,
               seed: Optional[int] = None, **opts) -> int:
        for bad in ("temperature", "top_k", "top_p", "min_p",
                    "repetition_penalty", "logit_bias", "logprobs"):
            # explicit-None check: temperature=0.0 / top_k=0 are real
            # overrides and must be rejected too, not slip past truthiness
            # — but an EMPTY logit_bias dict is a no-op everywhere else
            # and must not hard-fail only here
            v = opts.get(bad)
            if v is None or v is False or (isinstance(v, dict) and not v):
                continue
            raise ValueError(
                "SpeculativeBatcher uses the server-level sampling "
                f"configuration; per-request {bad}= is the dense "
                "batcher's feature")
        if opts.get("prefilled") is not None:
            # KV adoption (dnn_tpu/control) would install the TARGET
            # cache only — the draft cache would never see the prompt
            # and every verify chunk would diverge
            raise ValueError(
                "prefilled= (disaggregated KV adoption) does not "
                "compose with speculative serving: the draft cache "
                "needs its own prompt prefill")
        prompt_arr = np.asarray(prompt, np.int32).reshape(-1)
        k = self.spec_k
        if len(prompt_arr) < k + 1:
            raise ValueError(
                f"prompt length {len(prompt_arr)} < spec_k+1 ({k + 1}) — "
                "the first draft-sync chunk re-feeds the prompt tail")
        if len(prompt_arr) + max_new_tokens + k > self.max_len:
            raise ValueError(
                f"prompt {len(prompt_arr)} + max_new {max_new_tokens} + "
                f"spec_k {k} exceeds max_len {self.max_len} (the verify "
                "chunk writes up to k scratch positions)")
        rid = super().submit(prompt_arr, max_new_tokens, seed=seed, **opts)
        # slot the parent picked; a budget-1 request already retired at
        # submit (the prefill-sampled token was its whole budget) and
        # needs no draft state at all
        slot = next((i for i, r in enumerate(self._slot_req)
                     if r is not None and r["rid"] == rid), None)
        if slot is None:
            return rid
        if self._ilv:
            # interleaved admission: the parent enqueued the pending
            # prefill; attach the draft side — its transient row (grown
            # chunk-by-chunk in lockstep through spec_mixed) and the
            # prompt tail the fused finish seeds prev_chunk with
            p = self._slot_req[slot].get("pending")
            if p is not None:
                p["d_row"] = self._d_family.init_cache(
                    1, self._ilv_row_len, self.d_cache["k"].dtype)
                p["tail"] = prompt_arr[-(k + 1):]
            return rid
        # draft prefill: same chunk loop as the parent, through the draft
        p_pad = self.prompt_pad
        n_chunks = -(-len(prompt_arr) // p_pad)
        padded = np.zeros((1, n_chunks * p_pad), np.int32)
        padded[0, : len(prompt_arr)] = prompt_arr
        d_row = self._d_family.init_cache(
            1, self._row_len, self.d_cache["k"].dtype)
        for c in range(n_chunks):
            _, d_row = self._d_prefill_chunk(
                self.draft_prepared, d_row,
                jnp.asarray(padded[:, c * p_pad:(c + 1) * p_pad]),
                jnp.int32(c * p_pad))
        self.d_cache = self._d_install(self.d_cache, d_row, slot)
        # first sync chunk: the prompt's own tail at its own positions —
        # an exact no-op re-feed
        tail = prompt_arr[-(k + 1):]
        self.prev_chunk = self.prev_chunk.at[slot].set(jnp.asarray(tail))
        self.prev_pos = self.prev_pos.at[slot].set(
            len(prompt_arr) - (k + 1))
        return rid

    def _ilv_after_chunk(self, ilv, pf_hidden, rows, s_idx):
        """Speculative override of the interleave bookkeeping: `rows`
        is the (target row, draft row) pair the spec mixed program
        returned; the final chunk dispatches the fused finish that
        installs BOTH rows, samples the first token on device, and
        seeds the draft-sync state."""
        req, p = ilv["req"], ilv["p"]
        new_row, new_d_row = rows
        self.prefill_chunks_run += 1
        m = obs.metrics()
        if m is not None:
            m.inc("serving.prefill_chunks_total")
        if not ilv["last"]:
            p["row"], p["d_row"] = new_row, new_d_row
            p["next"] += 1
            return
        self._pending_q.pop(0)
        out = self._spec_ilv_finish(
            *self._slot_state(), new_row, pf_hidden, *p["finish"],
            self._ctable, self._ctrans,
            self.d_cache, self.prev_chunk, self.prev_pos, new_d_row,
            p["tail"])
        self._set_slot_state(out[:13])
        # the parent core appends logprob outputs only when logprobs_k
        # is compiled in — the spec batcher bans it, so the tail is
        # exactly (d_cache, prev_chunk, prev_pos)
        self.d_cache, self.prev_chunk, self.prev_pos = out[14:]
        req["first_dev"] = (out[13], None)
        req["install_step"] = s_idx
        del req["pending"]

    def _commit_spec(self, s_idx, w_np, m_np, rec, sc):
        """Commit one completed speculative step (the chunk block `w`
        and acceptance counts `m`), with the same install gating as the
        dense _commit_step: slots whose fused finish landed at
        install_step >= s_idx had no verify leg in that dispatch."""
        self.spec_steps += 1
        obs_m = obs.metrics()
        t_now = time.perf_counter() if obs_m is not None else 0.0
        n_adv = 0
        it_samples: list = []
        out = {}
        for slot, req in enumerate(self._slot_req):
            if req is None or req.get("pending") is not None:
                continue
            inst = req.get("install_step")
            emitted = []
            if inst is not None:
                if s_idx <= inst:
                    continue
                del req["install_step"]
                fd = req.pop("first_dev", None)
                if fd is not None:
                    tok0 = int(np.asarray(fd[0]))
                    req["emitted"].append(tok0)
                    emitted.append(tok0)
                    if obs_m is not None \
                            and (g := self.goodput) is not None:
                        g.on_prefill(req["prompt_len"])
                    self._retire_if_done(slot)
            if self._slot_req[slot] is req:
                n_commit = int(m_np[slot]) + 1
                self.spec_proposed += self.spec_k
                self.spec_accepted += int(m_np[slot])
                for t in [int(x) for x in w_np[slot, :n_commit]]:
                    req["emitted"].append(t)
                    emitted.append(t)
                    self._retire_if_done(slot)
                    if self._slot_req[slot] is None:
                        break  # budget/stop/eos mid-chunk: rest discarded
            if not emitted:
                continue
            # shared obs bookkeeping (serving.ContinuousBatcher helpers):
            # the inter-token gap spreads over the committed chunk; the
            # decode span closes at retire like the dense path. Skipped
            # for a request that retired mid-chunk — its span is already
            # closed and must not reopen on a dead slot.
            n_adv += len(emitted)
            if self._slot_req[slot] is req:
                self._obs_commit(req, obs_m, t_now, n_new=len(emitted),
                                 samples=it_samples)
            out[req["rid"]] = emitted
        if rec is not None:
            sc.mark(rec, "commit")
        self._obs_step_end(obs_m, n_adv, it_samples)
        if rec is not None:
            sc.mark(rec, "obs")
            sc.end(rec, n_adv)
        return out

    def flush_overlap(self):
        """Speculative flush: the inflight struct holds the chunk block
        and acceptance counts (never donated by later dispatches, so
        bare refs suffice — no copy needed)."""
        if self._inflight is None:
            return {}
        sc = self.step_clock
        rec = sc.begin("wait") if sc is not None else None
        s_idx, w_ref, m_ref = self._inflight
        self._inflight = None
        w_np, m_np = np.asarray(w_ref), np.asarray(m_ref)
        if rec is not None:
            sc.mark(rec, "wait")
        return self._commit_spec(s_idx, w_np, m_np, rec, sc)

    def step(self):
        """One speculative step: every active slot advances by its own
        1..k+1 committed tokens. Returns {rid: [tokens...]}. Interleave
        and overlap compose exactly as in the dense step: a pending
        admission's chunk folds into the spec program, and overlap=True
        dispatches step N while committing step N-1."""
        if self.n_active == 0:
            return self.flush_overlap()
        # step-timeline clock: same phase protocol as the dense step
        # (serving.ContinuousBatcher.step) — one speculative step's
        # "wait" is the draft+verify chunk's device->host sync
        sc = self.step_clock
        rec = sc.begin() if sc is not None else None
        if self._buckets is not None:
            # this step verifies at pos..pos+k for every active slot
            # (pos = prompt_len + emitted - 1); _ensure_cache_len adds
            # the +k scratch itself and grows the draft pool in
            # lockstep. Host-uncommitted tokens count too — a deferred
            # first, plus up to k+1 per in-flight step under overlap
            # (the shared _uncommitted_need accounting).
            need = self._uncommitted_need(self.spec_k + 1)
            if need:
                self._ensure_cache_len(need)
        ilv = self._ilv_next() if self._ilv else None
        if rec is not None:
            sc.mark(rec, "host")
        if ilv is None:
            (self.cache, self.d_cache, self.tok, self.pos, self.keys,
             self.prev_chunk, self.prev_pos, w, m) = self._spec_step(
                self.prepared, self.draft_prepared, self.cache,
                self.d_cache, self.tok, self.pos, self.active,
                self.keys, self.prev_chunk, self.prev_pos)
        else:
            p = ilv["p"]
            (self.cache, self.d_cache, self.tok, self.pos, self.keys,
             self.prev_chunk, self.prev_pos, w, m, pf_hidden, new_row,
             new_d_row) = self._spec_mixed(
                self.prepared, self.draft_prepared, self.cache,
                self.d_cache, self.tok, self.pos, self.active,
                self.keys, self.prev_chunk, self.prev_pos,
                p["row"], p["d_row"], ilv["chunk"], ilv["start"])
        if rec is not None:
            sc.mark(rec, "dispatch")
            rec.mixed = ilv is not None
        s_idx = self._step_idx
        self._step_idx += 1
        if ilv is not None:
            self._ilv_after_chunk(ilv, pf_hidden, (new_row, new_d_row),
                                  s_idx)
        if self._overlap:
            if sc is not None:
                sc.overlap_depth = 1
            keep = (s_idx, w, m)
            prev, self._inflight = self._inflight, keep
            if prev is None:
                return self._pipeline_fill_end(rec, sc)
            self.steps_pipelined += 1
            s_prev, w_prev, m_prev = prev
            w_np, m_np = np.asarray(w_prev), np.asarray(m_prev)
            if rec is not None:
                sc.mark(rec, "wait")
            return self._commit_spec(s_prev, w_np, m_np, rec, sc)
        w_np, m_np = np.asarray(w), np.asarray(m)
        if rec is not None:
            sc.mark(rec, "wait")
        return self._commit_spec(s_idx, w_np, m_np, rec, sc)
