"""Mixtral, Qwen2-MoE, OLMoE: the LLaMA block with a sparse
mixture-of-experts MLP.

No counterpart exists in the reference (no MoE anywhere — SURVEY §2).
Mixtral = LLaMA attention (GQA, RoPE, RMSNorm) + per-layer top-2-of-8
SwiGLU experts with renormalized routing; Qwen2-MoE adds raw top-k
weights and an always-on shared expert; OLMoE is 64 fine-grained experts,
8 per token, raw weights, q/k RMSNorm over the projection width.

TPU-first composition, not a new model implementation:

  * the block is llama.py's — every path (dense forward, cached decode,
    batcher rows, speculative verify) is the LLaMA path with the `ffn`
    hook installed, so parity contracts and runtime features (int8
    caches, constraints, streaming, beam) carry over wherever the hook
    threads;
  * on one device the expert math is parallel/moe.moe_ffn_grouped:
    softmax over all experts, top-k, rows sorted by expert, the gated
    stack (silu(x@wg)*(x@wu)@wd) as ragged matmuls. DROP-FREE: there is
    no capacity, every routed row is computed, as the HF modules do —
    `capacity_factor` does not reach this path;
  * across devices (make_apply_ep / make_generate_ep /
    make_pipeline_generate_ep) per-rank static shapes need GShard's
    static-capacity dispatch (parallel/moe.moe_ffn_local): there
    `capacity_factor >= n_expert` guarantees nothing drops, smaller
    factors trade drops for memory, and dropped tokens degrade to the
    residual. `make_ffn(groups=n)` is that path's dense twin, for the
    parity tests.

Nemotron-H (`one_mixer`): a block is ONE mixer — the experts among them,
in a LATENT (`moe_latent`: "moe" then holds "wi" / "wo" (E, L, F) / (E, F,
L), "latent_down" / "latent_up" and an ungated "shared" {"up", "down"}).

Param pytree: llama's, with each block's "mlp" replaced by
  "moe": {"router": {"kernel" (D, E)}, "wg"/"wu" (E, D, F), "wd" (E, F, D)}
(HF MixtralForCausalLM: block_sparse_moe.gate + experts.i.{w1,w3,w2};
Qwen2MoeForCausalLM / OlmoeForCausalLM: mlp.gate + mlp.experts.i.
{gate,up,down}_proj).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp

from dnn_tpu.models import gpt, llama
from dnn_tpu.models.kda import KdaConfig
from dnn_tpu.models.mla import MlaConfig
from dnn_tpu.parallel.moe import (
    N_STATS, init_moe_gated, init_moe_plain, moe_ffn, moe_ffn_grouped)
from dnn_tpu.registry import ModelSpec, register_model


# sigma of the seeded init's selection bias: at random init a token's
# sigmoid scores lie close together, and at this sigma the bias changes
# the set of eight on 99.6 % of tokens (PERF.md section 6, PR 35), so a
# program that dropped it is told apart
_SELECT_BIAS_INIT = 0.05


@dataclasses.dataclass(frozen=True)
class RouterConfig:
    """How an expert layer scores and weighs (parallel/moe.route_rows).
    The default is the softmax top-k every earlier preset has."""
    # "softmax" over all experts, or "sigmoid" of each logit on its own
    # (DeepSeek-V3's `scoring_func`)
    scoring: str = "softmax"
    # a per-expert bias added to the scores for the PICK alone
    # (`e_score_correction_bias`, `topk_method` noaux_tc): the tree then
    # carries `moe.router.select_bias` (E,) float32
    select_bias: bool = False
    # `routed_scaling_factor`: the k weights times this
    scale: float = 1.0


@dataclasses.dataclass(frozen=True)
class MixtralConfig(llama.LlamaConfig):
    n_expert: int = 8
    router_top_k: int = 2
    # expert-parallel paths only (per-rank static shapes): >= n_expert
    # guarantees no token ever drops. The single-device path is
    # drop-free and never reads it (parallel/moe.moe_ffn_grouped)
    capacity_factor: float = 8.0
    # ---- Qwen2-MoE-class switches (defaults = Mixtral semantics) ----
    # Always-on SHARED expert (DeepSeek/Qwen-MoE recipe): a dense SwiGLU
    # of width d_shared whose output, scaled by a per-token sigmoid gate
    # (shared_expert_gate), adds to the routed experts' output.
    d_shared: Optional[int] = None
    # True (Mixtral): renormalize the selected top-k router weights.
    # False (Qwen2-MoE norm_topk_prob=false): keep raw softmax probs.
    router_norm_topk: bool = True
    # ---- one chip's share of an expert-parallel deployment ----
    # `experts_held` set: this process holds the stacks of experts
    # [experts_first, experts_first + experts_held) only. The router
    # still scores all n_expert and every token still picks its top_k
    # among them; a pick whose expert is not held contributes nothing
    # here (the chip that holds it computes it). None = every expert.
    experts_first: int = 0
    experts_held: Optional[int] = None
    # ---- DeepSeek-V3-class switches (defaults = what was there) ----
    router: RouterConfig = RouterConfig()
    # False: the shared expert's output adds as it is (no sigmoid gate,
    # no `shared_gate` leaf)
    shared_gate: bool = True
    # the first `first_k_dense` layers carry a dense gated MLP of width
    # `d_ff_dense` in place of experts. They are a stack of their own
    # (`prepared["dense_blocks"]`, llama.layer_stacks) in front of the
    # expert stack; the expert hook computes a block by what its params
    # hold, and the moe_* counters count expert layers only
    first_k_dense: int = 0
    d_ff_dense: Optional[int] = None
    # multi-head latent attention (models/mla.py): the cache holds ONE
    # compressed latent a position
    mla: Optional[MlaConfig] = None
    # layers of two KINDS (models/mla.py): `layer_types[i]` is "full"
    # (`mla`'s widths) or "window" (`mla_window`'s, a latent attention of
    # its own widths, head count and theta under a sliding window). The
    # kinds' params stack apart (gpt.stack_layers) and their cache leaves
    # and block tables are apart (paged_kvcache, by kind)
    mla_window: Optional[MlaConfig] = None
    layer_types: Optional[tuple] = None
    # the same for a family whose cache is K and V (models/llama.py
    # `KvKind`: a kind's window and whether it rotates q and k):
    # `layer_types[i]` is "full" (`kv_full`, None: every position,
    # rotated) or "window" (`kv_window`, which has a window). Stacks are
    # by (MLP kind, attention kind): a layer of the dense prefix may be of
    # either kind
    kv_full: Optional[llama.KvKind] = None
    kv_window: Optional[llama.KvKind] = None
    # layers that keep a STATE and no position's anything (models/kda.py:
    # the gated delta rule with a decay a channel behind a short
    # convolution): `layer_types[i]` is "full" (K and V, `kv_full`) or
    # "linear" (`kda`'s widths); `attn_gate`: the "full" layers multiply
    # their attention output by sigmoid(h W_gate), element-wise
    kda: Optional[KdaConfig] = None
    attn_gate: bool = False
    # the SEEDED init's expert down projections (1 / sqrt(fan-in)) times
    # this: how much of the residual stream the experts are at random
    # weights, and so how far one expert swapped under bfloat16 moves a
    # logit. No trained checkpoint reads it
    expert_out_init: float = 1.0
    # ---- blocks of ONE mixer (Nemotron-H, `hybrid_override_pattern`):
    # `layer_types[i]` is "ssm" (`mamba`, whose `beside` is False: the
    # state-space rule in attention's place), "full" (softmax attention,
    # `kv_full`) or "experts" (`llama.EXPERTS`) — one norm, that mixer, one
    # residual, and nothing after it (`one_mixer`). `pattern_types` reads
    # the published spelling (M, *, E).
    # `moe_latent`: the experts work in a LATENT of this width — the router scores the
    # model-wide h, the routed rows are h W_down, the weighted sum goes
    # through ONE W_up (`moe.latent_down` / `latent_up`, replicated); the
    # shared expert reads h itself
    moe_latent: Optional[int] = None
    # False: an expert (and the shared one) is two matrices without bias
    # under `mlp_act` ("relu2"), no gate
    expert_gated: bool = True

    @property
    def one_mixer(self) -> bool:
        return llama.EXPERTS in (self.layer_types or ())

    def __post_init__(self):
        super().__post_init__()
        if (self.layer_types is None) != (
                self.mla_window is None and self.kv_window is None
                and self.kda is None
                and (self.mamba is None or self.mamba.beside)):
            raise ValueError("layer_types comes with mla_window, "
                             "kv_window, kda or a mamba in attention's "
                             "place, and they with it")
        if self.mamba is not None and not self.mamba.beside:
            if (self.mla is not None or self.kda is not None
                    or self.kv_window is not None or self.first_k_dense
                    or self.index_topk is not None or self.mup is not None
                    or self.mamba.ssm_out != 1.0
                    or (self.kv_full or llama.KvKind()).window is not None
                    or len(self.layer_types) != self.n_layer
                    or set(self.layer_types) - {"ssm", "full", llama.EXPERTS}
                    or not {"ssm", llama.EXPERTS} <= set(self.layer_types)):
                raise ValueError(
                    "a mamba in attention's place (`beside` False) names "
                    "the \"ssm\" blocks of layer_types, kv_full (which has "
                    "no window) the \"full\" ones and \"experts\" the rest: "
                    "blocks of ONE mixer, at least one of each of the two "
                    "(no mla, kda, window kind, dense prefix, indexer or "
                    "muP multiplier; ssm_out 1)")
        elif self.moe_latent is not None or not self.expert_gated:
            raise ValueError("moe_latent and ungated experts are built for "
                             "the blocks of one mixer (a mamba in "
                             "attention's place)")
        elif self.kda is not None:
            if (self.mla is not None or self.mla_window is not None
                    or self.index_topk is not None
                    or self.kv_window is not None or self.first_k_dense
                    or (self.kv_full or llama.KvKind()).window is not None
                    or len(self.layer_types) != self.n_layer
                    or set(self.layer_types) - {"full", "linear"}
                    or "full" not in self.layer_types):
                raise ValueError(
                    "kda names the \"linear\" layers of layer_types and "
                    "kv_full (which has no window) the \"full\" ones, of "
                    "which there is at least one (no mla, no indexer, no "
                    "window kind, no dense prefix)")
        elif self.kv_window is not None or self.kv_full is not None:
            if (self.mla is not None or self.mla_window is not None
                    or self.index_topk is not None or self.kv_window is None
                    or self.kv_window.window is None
                    or (self.kv_full or llama.KvKind()).window is not None
                    or len(self.layer_types) != self.n_layer
                    or set(self.layer_types) - {"full", "window"}):
                raise ValueError(
                    "kv_window (which has a window) names the \"window\" "
                    "layers of layer_types and kv_full (which has none) "
                    "the \"full\" ones, of a model whose cache is K and V "
                    "(no mla, no indexer)")
        elif self.layer_types is not None and (
                self.mla is None or len(self.layer_types) != self.n_layer
                or set(self.layer_types) - {"full", "window"}
                or "window" in self.layer_types[:self.first_k_dense]
                or self.mla_window.window is None):
            raise ValueError(
                "layer_types names each of the n_layer layers \"full\" "
                "(mla) or \"window\" (mla_window, which has a window); "
                "the dense prefix is of full layers")
        if self.first_k_dense and not (
                0 < self.first_k_dense < self.n_layer and self.d_ff_dense):
            raise ValueError(
                "first_k_dense needs d_ff_dense and at least one expert "
                f"layer after it (n_layer {self.n_layer})")

    @property
    def n_expert_layer(self):
        if self.one_mixer:
            return self.layer_types.count(llama.EXPERTS)
        return self.n_layer - self.first_k_dense

    @property
    def held(self):
        """(first, count) of the experts held, or None for all."""
        if self.experts_held is None:
            return None
        return self.experts_first, self.experts_held

    def default_ffn(self, compute_dtype=None):
        """The config-resolved MLP override every llama runtime entry
        point picks up (LlamaConfig.default_ffn) — beam, speculative,
        embeddings, partitions, and the family adapter all route through
        the experts without Mixtral-specific dispatch."""
        return make_ffn(self, compute_dtype=compute_dtype)


PRESETS = {
    # Mixtral-8x7B shape: LLaMA-2-ish block, GQA 4:1, 8 experts top-2
    "mixtral-8x7b": MixtralConfig(block_size=32768, vocab_size=32000,
                                  n_layer=32, n_head=32, n_kv_head=8,
                                  n_embd=4096, d_ff=14336,
                                  rope_theta=1_000_000.0, rms_eps=1e-5,
                                  n_expert=8, router_top_k=2),
    # tiny config for tests/CI (4 experts top-2, GQA 2:1)
    "mixtral-test": MixtralConfig(block_size=64, vocab_size=256,
                                  n_layer=3, n_head=4, n_kv_head=2,
                                  n_embd=64, d_ff=128,
                                  n_expert=4, router_top_k=2,
                                  capacity_factor=4.0),
    # Qwen1.5-MoE-A2.7B shape: Qwen2 attention (q/k/v biases), 60
    # fine-grained experts top-4 with RAW softmax weights
    # (norm_topk_prob=false), plus the always-on sigmoid-gated shared
    # expert — the modern shared-expert MoE recipe
    "qwen15-moe-a2.7b": MixtralConfig(block_size=8192, vocab_size=151936,
                                      n_layer=24, n_head=16, n_kv_head=16,
                                      n_embd=2048, d_ff=1408,
                                      rope_theta=1_000_000.0,
                                      rms_eps=1e-6, attn_bias=True,
                                      n_expert=60, router_top_k=4,
                                      # no-drop (>= n_expert): the HF
                                      # parity convention; serving can
                                      # size it down (capacity trade)
                                      capacity_factor=60.0,
                                      d_shared=5632,
                                      router_norm_topk=False),
    # tiny shared-expert config for tests (every switch acts: biases,
    # raw top-k weights, shared expert + gate)
    "qwen2moe-test": MixtralConfig(block_size=64, vocab_size=256,
                                   n_layer=3, n_head=4, n_kv_head=2,
                                   n_embd=64, d_ff=32, attn_bias=True,
                                   n_expert=4, router_top_k=2,
                                   capacity_factor=4.0, d_shared=96,
                                   router_norm_topk=False),
    # OLMoE-1B-7B (allenai/OLMoE-1B-7B-0125-Instruct config.json): the
    # pre-norm LLaMA block, MHA with 128-wide heads, RMSNorm over the
    # whole q/k projection width before RoPE, and in EVERY layer 64
    # experts of width 1024, 8 per token, the eight RAW softmax
    # probabilities as weights (norm_topk_prob false), no shared expert
    "olmoe-1b-7b": MixtralConfig(block_size=4096, vocab_size=50304,
                                 n_layer=16, n_head=16, n_kv_head=16,
                                 n_embd=2048, d_ff=1024,
                                 rope_theta=10000.0, rms_eps=1e-5,
                                 tie_word_embeddings=False, attn_bias=False,
                                 pre_norm=True, qk_norm=True,
                                 qk_norm_width="proj",
                                 n_expert=64, router_top_k=8,
                                 router_norm_topk=False,
                                 capacity_factor=64.0),
    # tiny OLMoE for the CPU tests: every switch of the real one acts
    # (proj-width q/k norm, raw top-k weights, no GQA — OLMoE has none)
    "olmoe-test": MixtralConfig(block_size=64, vocab_size=256,
                                n_layer=3, n_head=4, n_kv_head=4,
                                n_embd=64, d_ff=32,
                                rope_theta=10000.0, rms_eps=1e-5,
                                tie_word_embeddings=False, attn_bias=False,
                                pre_norm=True, qk_norm=True,
                                qk_norm_width="proj",
                                n_expert=8, router_top_k=4,
                                router_norm_topk=False,
                                capacity_factor=8.0),
}
# Keye-VL-2.0-30B-A3B's language model (Kwai-Keye/Keye-VL-2.0-30B-A3B
# config.json): a Qwen3-MoE block — GQA 32 query / 4 KV heads of 128
# (decoupled from 2048 / 32), per-head q/k RMSNorm, RoPE theta 1e7 (for
# text positions `mrope_section`'s three streams are equal: ordinary
# RoPE), every layer 128 experts of width 768, 8 per token renormalised,
# no shared expert — whose attention reads only the 2048 positions a
# DeepSeek-Sparse-Attention-style indexer selects (`sa_config`: 16 index
# heads of 64, one index key head; models/dsa.py). The vision tower is
# not served: the daemon takes token ids. `qk_norm_init` 0.7: the seeded
# init's q/k norm gains, MEASURED (PERF.md section 6, PR 33): attention
# logits then have a sigma of ~0.5, any bfloat16 computation agrees with
# float32 on 85-89 % of tokens and a wrong selection (top-1024) on 37-53
# %; at gains of 1.3 / 1.7 (sigma 1.7 / 3) bfloat16 itself agrees on 53 /
# 9 % only and `correct` could tell nothing apart.
PRESETS["keye-vl-2.0-30b-a3b"] = MixtralConfig(
    block_size=262144, vocab_size=151936, n_layer=48, n_head=32,
    n_kv_head=4, n_embd=2048, d_ff=768, head_dim_override=128,
    rope_theta=10_000_000.0, rms_eps=1e-6, qk_norm=True,
    qk_norm_width="head", qk_norm_init=0.7,
    n_expert=128, router_top_k=8, router_norm_topk=True,
    capacity_factor=128.0,
    index_topk=2048, index_n_head=16, index_head_dim=64)
# the benchmark's cut (chipbench/configs/keye-vl-2.0-30b-a3b-ep8-1chip
# .json): one chip's share of an 8-chip expert-parallel deployment —
# experts 0-15 of each layer's 128 held, attention, indexer, router,
# embedding and head whole — and 6 of the 48 layers (8 completed 68
# requests a 45 s window of the cell where 100 were asked; PERF.md)
PRESETS["keye-vl-2.0-30b-a3b-ep8-1chip"] = dataclasses.replace(
    PRESETS["keye-vl-2.0-30b-a3b"], n_layer=6, experts_first=0,
    experts_held=16)
# tiny Keye for the CPU tests, every switch of the real one acting: GQA
# 2:1, a head width decoupled from n_embd / n_head, head-width q/k norm
# with drawn gains, an indexer whose topk is far below the contexts,
# normalised top-k, a held share smaller than the expert count
PRESETS["keye-test"] = MixtralConfig(
    block_size=64, vocab_size=256, n_layer=3, n_head=4, n_kv_head=2,
    n_embd=64, d_ff=32, head_dim_override=32, rope_theta=10_000_000.0,
    rms_eps=1e-6, qk_norm=True, qk_norm_width="head", qk_norm_init=0.7,
    n_expert=8, router_top_k=4, router_norm_topk=True, capacity_factor=8.0,
    experts_first=0, experts_held=4,
    index_topk=8, index_n_head=4, index_head_dim=16)
# JoyAI-LLM-Flash (jdopensource/JoyAI-LLM-Flash config.json, `model_type`
# joyai_llm_flash): a DeepSeek-V3-shaped decoder — latent attention
# (models/mla.py) in all 40 layers, layer 0 a dense SwiGLU of 7168, then
# 39 layers of 256 routed experts of 768, 8 a token by sigmoid scores
# with a selection bias (`topk_method` noaux_tc; `n_group` = `topk_group`
# = 1: the grouped top-k is the plain one), weights normalised and times
# 2.5, and ONE shared expert that adds ungated. The multi-token-
# prediction module (`num_nextn_predict_layers` 1) is a 41st block that
# drafts: it is no part of the next token's logits and is not served.
# The seeded init's choices (`_SELECT_BIAS_INIT`, norm gains of one): MEASURED
# (PERF.md section 6, PR 35).
PRESETS["joyai-llm-flash"] = MixtralConfig(
    block_size=131072, vocab_size=129280, n_layer=40, n_head=32,
    n_kv_head=32, n_embd=2048, d_ff=768, rope_theta=32_000_000.0,
    rms_eps=1e-6, n_expert=256, router_top_k=8, router_norm_topk=True,
    capacity_factor=256.0, d_shared=768, shared_gate=False,
    first_k_dense=1, d_ff_dense=7168,
    router=RouterConfig(scoring="sigmoid", select_bias=True,
                        scale=2.5),
    mla=MlaConfig(q_lora_rank=1536, kv_lora_rank=512, qk_nope_head_dim=128,
                  qk_rope_head_dim=64, v_head_dim=128, rope_interleave=True))
# the benchmark's cut (chipbench/configs/joyai-llm-flash-ep16-1chip.json):
# one chip's share of a 16-chip expert-parallel deployment — experts 0-15
# of each expert layer's 256 held; attention, router, shared expert,
# embedding and head whole — and layer 0 with four of the 39 expert layers
# (the depth by requests completed a window: PERF.md section 6, PR 35)
PRESETS["joyai-llm-flash-ep16-1chip"] = dataclasses.replace(
    PRESETS["joyai-llm-flash"], n_layer=5, experts_first=0, experts_held=16)
# tiny JoyAI for the CPU tests, every switch of the real one acting: a
# query bottleneck, latent and rope widths with nope != value width, 1
# dense + 2 expert layers, sigmoid + bias + scale, an ungated shared
# expert, a held share smaller than the expert count
PRESETS["joyai-test"] = MixtralConfig(
    block_size=64, vocab_size=256, n_layer=3, n_head=4, n_kv_head=4,
    n_embd=64, d_ff=32, rope_theta=32_000_000.0, rms_eps=1e-6,
    n_expert=8, router_top_k=4, router_norm_topk=True, capacity_factor=8.0,
    experts_first=0, experts_held=4, d_shared=32, shared_gate=False,
    first_k_dense=1, d_ff_dense=96,
    router=RouterConfig(scoring="sigmoid", select_bias=True,
                        scale=2.5),
    mla=MlaConfig(q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=16,
                  qk_rope_head_dim=8, v_head_dim=24, rope_interleave=True))
# dots3-note-prev (dots-studio/dots3-note-prev config.json, `model_type`
# dots3_note; 288B-A17B): 46 layers of latent attention of two KINDS
# (models/mla.py) — 13 "full" layers (0, 1, 5, 9, ..., 45: 128 heads, a
# latent of 512, and a DeepSeek-V3.2-style indexer on the query latent
# that picks 2048 positions) and 33 "window" layers (64 heads, a latent
# of 1024, nope 192, theta 50000, a window of 513 that counts the query's
# own position) — both with the low-rank rescale and a head-wise output
# gate; layer 0 a dense SwiGLU of 13824, then 256 experts of 1536, 8 a
# token by sigmoid scores with a selection bias (`noaux_tc`, one group),
# weights normalised, times 1, and one ungated shared expert. The vision
# and audio towers and any MTP module are not served. What the config
# leaves open is `assumed` in chipbench/configs/dots3-note-prev-ep8-1chip
# .json. Never instantiated whole.
_DOTS3_TYPES = tuple("full" if i < 2 or i % 4 == 1 else "window"
                     for i in range(46))
_DOTS3_FULL = MlaConfig(
    q_lora_rank=1024, kv_lora_rank=512, qk_nope_head_dim=128,
    qk_rope_head_dim=64, v_head_dim=128, rope_interleave=True,
    lora_rescale=True, head_gate=True, index_topk=2048, index_n_head=64,
    index_head_dim=128, index_rope_dim=64)
_DOTS3_WINDOW = MlaConfig(
    q_lora_rank=1024, kv_lora_rank=1024, qk_nope_head_dim=192,
    qk_rope_head_dim=64, v_head_dim=128, rope_interleave=True, n_head=64,
    rope_theta=50_000.0, lora_rescale=True, head_gate=True, window=513)
PRESETS["dots3-note-prev"] = MixtralConfig(
    block_size=524288, vocab_size=152064, n_layer=46, n_head=128,
    n_kv_head=128, n_embd=5120, d_ff=1536, rope_theta=80_000_000.0,
    rms_eps=1e-5, n_expert=256, router_top_k=8, router_norm_topk=True,
    capacity_factor=256.0, d_shared=1536, shared_gate=False,
    first_k_dense=1, d_ff_dense=13824,
    router=RouterConfig(scoring="sigmoid", select_bias=True, scale=1.0),
    mla=_DOTS3_FULL, mla_window=_DOTS3_WINDOW, layer_types=_DOTS3_TYPES)
# the benchmark's cut (chipbench/configs/dots3-note-prev-ep8-1chip.json):
# one chip's share of an 8-chip expert-parallel deployment — experts 0-31
# of each expert layer's 256, rows 0-19007 of the vocabulary (an eighth),
# attention, router, shared expert and norms whole — and layer 0 with ONE
# whole period of four (F | F S S S): 8.4 GB held, the guide's floors
PRESETS["dots3-note-prev-ep8-1chip"] = dataclasses.replace(
    PRESETS["dots3-note-prev"], n_layer=5, layer_types=_DOTS3_TYPES[:5],
    vocab_size=19008, experts_first=0, experts_held=32)
# tiny dots3 for the CPU tests, every switch of the real one acting: two
# kinds of other head counts, latents, nope widths and thetas; the
# rescale and the gate; a window and a topk that a 40-token sequence
# exceeds; an indexer on the query latent with a partial RoPE; 1 dense +
# 4 expert layers F F S S S; a held share smaller than the expert count
PRESETS["dots3-test"] = MixtralConfig(
    block_size=64, vocab_size=256, n_layer=5, n_head=4, n_kv_head=4,
    n_embd=64, d_ff=32, rope_theta=80_000_000.0, rms_eps=1e-5,
    n_expert=8, router_top_k=4, router_norm_topk=True, capacity_factor=8.0,
    experts_first=0, experts_held=4, d_shared=32, shared_gate=False,
    first_k_dense=1, d_ff_dense=96,
    router=RouterConfig(scoring="sigmoid", select_bias=True, scale=1.0),
    mla=MlaConfig(q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=16,
                  qk_rope_head_dim=8, v_head_dim=16, rope_interleave=True,
                  lora_rescale=True, head_gate=True, index_topk=12,
                  index_n_head=4, index_head_dim=16, index_rope_dim=8),
    mla_window=MlaConfig(q_lora_rank=32, kv_lora_rank=32,
                         qk_nope_head_dim=24, qk_rope_head_dim=8,
                         v_head_dim=16, rope_interleave=True, n_head=2,
                         rope_theta=50_000.0, lora_rescale=True,
                         head_gate=True, window=9),
    layer_types=("full", "full", "window", "window", "window"))
# K-EXAONE-236B-A23B (LGAI-EXAONE/K-EXAONE-236B-A23B config.json,
# `model_type` exaone_moe): 48 layers of GQA 64 query / 8 KV heads of 128
# with per-head q/k RMSNorm, of two KINDS whose cache is K and V
# (models/llama.py `KvKind`) in the period S S S F — 36 "window" layers
# (0, 1, 2, 4, ...: a window of 128, RoPE theta 1e6) and 12 "full" layers
# (3, 7, ...: every position, NO rotation) — layer 0 a dense SwiGLU of
# 18432, then 128 experts of 2048, 8 a token by sigmoid scores with a
# selection bias (one group), weights normalised, times 2.5, and one
# ungated shared expert; vocabulary 153600, untied. The multi-token-
# prediction layer (`num_nextn_predict_layers` 1) drafts: it is no part of
# the next token's logits and is not served. What the config leaves open
# (QK-norm, the unrotated full layers, the selection bias, pre-norm) is
# `assumed` in chipbench/configs/k-exaone-236b-a23b-ep8-1chip.json. Never
# instantiated whole.
_KEXAONE_TYPES = tuple("full" if i % 4 == 3 else "window" for i in range(48))
PRESETS["k-exaone-236b-a23b"] = MixtralConfig(
    block_size=262144, vocab_size=153600, n_layer=48, n_head=64, n_kv_head=8,
    n_embd=6144, d_ff=2048, head_dim_override=128, rope_theta=1_000_000.0,
    rms_eps=1e-5, qk_norm=True, qk_norm_width="head", n_expert=128,
    router_top_k=8, router_norm_topk=True, capacity_factor=128.0,
    d_shared=2048, shared_gate=False, first_k_dense=1, d_ff_dense=18432,
    router=RouterConfig(scoring="sigmoid", select_bias=True, scale=2.5),
    kv_full=llama.KvKind(window=None, rope=False),
    kv_window=llama.KvKind(window=128, rope=True),
    layer_types=_KEXAONE_TYPES)
# the benchmark's cut (chipbench/configs/k-exaone-236b-a23b-ep8-1chip
# .json): one chip's share of an 8-chip expert-parallel deployment —
# experts 0-15 of each expert layer's 128, rows 0-19199 of the vocabulary
# (an eighth), attention, router, shared expert and norms whole — and
# layer 0 with ONE whole period of four (S | S S F S): 7.67 GB held
PRESETS["k-exaone-236b-a23b-ep8-1chip"] = dataclasses.replace(
    PRESETS["k-exaone-236b-a23b"], n_layer=5,
    layer_types=_KEXAONE_TYPES[:5], vocab_size=19200, experts_first=0,
    experts_held=16)
# tiny K-EXAONE for the CPU tests, every switch of the real one acting:
# GQA 2:1 with a decoupled head width and head-width q/k norm, a window a
# 40-token sequence exceeds four times over, unrotated full layers, a
# dense AND windowed layer 0, S S S F S, sigmoid + bias + scale 2.5, an
# ungated shared expert, a held share smaller than the expert count
PRESETS["k-exaone-test"] = MixtralConfig(
    block_size=64, vocab_size=256, n_layer=5, n_head=4, n_kv_head=2,
    n_embd=64, d_ff=32, head_dim_override=32, rope_theta=1_000_000.0,
    rms_eps=1e-5, qk_norm=True, qk_norm_width="head", n_expert=8,
    router_top_k=4, router_norm_topk=True, capacity_factor=8.0,
    experts_first=0, experts_held=4, d_shared=32, shared_gate=False,
    first_k_dense=1, d_ff_dense=96,
    router=RouterConfig(scoring="sigmoid", select_bias=True, scale=2.5),
    kv_full=llama.KvKind(window=None, rope=False),
    kv_window=llama.KvKind(window=8, rope=True),
    layer_types=("window", "window", "window", "full", "window"))
# Mellum2-12B-A2.5B-Instruct (JetBrains/Mellum2-12B-A2.5B-Instruct
# config.json, `model_type` mellum): a Qwen3-MoE-shaped decoder — GQA 32
# query / 4 KV heads of 128 with per-head q/k RMSNorm, every layer 64
# experts of 896, 8 a token by softmax scores renormalised, no shared
# expert, no dense layer — whose 28 layers are of two K/V KINDS in the
# period S S S F and BOTH kinds rotate q and k, each by its own table
# (`KvKind.rotation`): 21 "window" layers under a window of 1024 and plain
# RoPE (theta 5e5), 7 "full" layers under YaRN (theta 5e5, factor 16 over
# 8192 original positions, cos and sin times 1.2772588722239782) up to
# 131072 positions. `intermediate_size` 7168 is read by no layer; the
# "MTP head" `described_as` mentions has no key and is not served. What
# the config leaves open (QK-norm) is `assumed` in chipbench/configs/
# mellum2-12b-a2.5b-pp4-1chip.json. Never instantiated whole.
# `expert_out_init` 0.25: the seeded init's expert outputs, MEASURED
# (PERF.md section 6, PR 62): at 1 the experts are ~7x the rest of the
# stream, all 64 are held in 8 layers, and one expert swapped under
# bfloat16 rewrites a row — the float32 reference agrees with ITSELF at
# default precision on 57 % of tokens and `correct` could see neither
# rotation table; at 0.25 on 91 %, with every control under 71 %; at 0.125
# on 99 %, but then the weights not renormalised read 91 %.
_MELLUM2_TYPES = tuple("full" if i % 4 == 3 else "window" for i in range(28))
_MELLUM2_YARN = llama.Rotation(
    theta=500_000.0, scaling="yarn", scale=16.0, original_len=8192,
    beta_fast=32.0, beta_slow=1.0, attention_factor=1.2772588722239782)
PRESETS["mellum2-12b-a2.5b"] = MixtralConfig(
    block_size=131072, vocab_size=98304, n_layer=28, n_head=32, n_kv_head=4,
    n_embd=2304, d_ff=896, head_dim_override=128, rope_theta=500_000.0,
    rms_eps=1e-6, qk_norm=True, qk_norm_width="head",
    tie_word_embeddings=False, n_expert=64, router_top_k=8,
    router_norm_topk=True, capacity_factor=64.0, expert_out_init=0.25,
    kv_full=llama.KvKind(window=None, rotation=_MELLUM2_YARN),
    kv_window=llama.KvKind(window=1024,
                           rotation=llama.Rotation(theta=500_000.0)),
    layer_types=_MELLUM2_TYPES)
# the benchmark's cut (chipbench/configs/mellum2-12b-a2.5b-pp4-1chip.json):
# one of four pipeline stages — layers 0-7, two whole periods S S S F, ALL
# 64 experts of each, with `wte` and the head on it: 8.05 GB held
PRESETS["mellum2-12b-a2.5b-pp4-1chip"] = dataclasses.replace(
    PRESETS["mellum2-12b-a2.5b"], n_layer=8, layer_types=_MELLUM2_TYPES[:8])
# tiny Mellum2 for the CPU tests, every switch of the real one acting: GQA
# 2:1 with a decoupled head width and head-width q/k norm with drawn
# gains, a window a 40-token sequence exceeds four times over, the full
# kind under YaRN whose original 8 positions the sequence exceeds four
# times over and whose ramp (low 3, high 8 of 16 pairs at theta 100) holds
# pairs in all three parts, an attention factor that is not 1, 8 experts 4
# a token renormalised, S S S F twice
PRESETS["mellum2-test"] = MixtralConfig(
    block_size=64, vocab_size=256, n_layer=8, n_head=4, n_kv_head=2,
    n_embd=64, d_ff=32, head_dim_override=32, rope_theta=100.0,
    rms_eps=1e-6, qk_norm=True, qk_norm_width="head", qk_norm_init=1.4,
    tie_word_embeddings=False, n_expert=8, router_top_k=4,
    router_norm_topk=True, capacity_factor=8.0,
    kv_full=llama.KvKind(window=None, rotation=llama.Rotation(
        theta=100.0, scaling="yarn", scale=8.0, original_len=8,
        beta_fast=0.5, beta_slow=0.15, attention_factor=1.25)),
    kv_window=llama.KvKind(window=8, rotation=llama.Rotation(theta=100.0)),
    layer_types=("window", "window", "window", "full") * 2)
# Solar-Open2-250B (upstage/Solar-Open2-250B config.json, `model_type`
# solar_open2): 48 layers in periods of four — one softmax layer
# (`gqa_layers` 0, 4, ...: 64 query / 8 KV heads of 128, NO rotation, a
# sigmoid output gate) and three linear-attention layers (models/kda.py:
# 64 heads of 128, a 4-tap convolution, the gated delta rule with a decay
# a channel, beta in (0, 2)) — every layer 320 experts of 1280, 8 a token
# by sigmoid scores with a selection bias, weights normalised, and one
# ungated shared expert; vocabulary 196608, untied. What the config leaves
# open is `assumed` in chipbench/configs/solar-open2-250b-ep8-1chip.json.
# Never instantiated whole.
_SOLAR_TYPES = tuple("full" if i % 4 == 0 else "linear" for i in range(48))
PRESETS["solar-open2-250b"] = MixtralConfig(
    block_size=1048576, vocab_size=196608, n_layer=48, n_head=64,
    n_kv_head=8, n_embd=4096, d_ff=1280, head_dim_override=128,
    rms_eps=1e-5, n_expert=320, router_top_k=8, router_norm_topk=True,
    capacity_factor=320.0, d_shared=1280, shared_gate=False,
    router=RouterConfig(scoring="sigmoid", select_bias=True, scale=1.0),
    kv_full=llama.KvKind(window=None, rope=False), attn_gate=True,
    kda=KdaConfig(n_head=64, head_dim=128, conv=4, rank=128, chunk=64),
    layer_types=_SOLAR_TYPES)
# the benchmark's cut (chipbench/configs/solar-open2-250b-ep8-1chip.json):
# one chip's share of an 8-chip expert-parallel deployment — experts 0-39
# of each layer's 320, rows 0-24575 of the vocabulary (an eighth), both
# mixers, router, shared expert and norms whole — and ONE whole period of
# four (F L L L): 6.8 GB held
PRESETS["solar-open2-250b-ep8-1chip"] = dataclasses.replace(
    PRESETS["solar-open2-250b"], n_layer=4, layer_types=_SOLAR_TYPES[:4],
    vocab_size=24576, experts_first=0, experts_held=40)
# tiny Solar-Open2 for the CPU tests, every switch of the real one acting:
# GQA 2:1 with a decoupled head width, no rotation, the output gate, three
# linear layers of 4 heads of 16 whose chunk of 8 a 16-token prefill chunk
# holds twice, sigmoid + bias routing, an ungated shared expert, a held
# share smaller than the expert count
PRESETS["solar-open2-test"] = MixtralConfig(
    block_size=128, vocab_size=256, n_layer=4, n_head=4, n_kv_head=2,
    n_embd=64, d_ff=32, head_dim_override=32, rms_eps=1e-5, n_expert=8,
    router_top_k=4, router_norm_topk=True, capacity_factor=8.0,
    experts_first=0, experts_held=4, d_shared=32, shared_gate=False,
    router=RouterConfig(scoring="sigmoid", select_bias=True, scale=1.0),
    kv_full=llama.KvKind(window=None, rope=False), attn_gate=True,
    kda=KdaConfig(n_head=4, head_dim=16, conv=4, rank=8, chunk=8),
    layer_types=("full", "linear", "linear", "linear"))
# the benchmark's cut (chipbench/configs/olmoe-1b-7b-1chip.json): three of
# the sixteen layers — the pattern has period 1 — so that float32 weights,
# a 16-slot pool of 4096 positions and the programs fit one 16 GB chip
PRESETS["olmoe-1b-7b-1chip"] = dataclasses.replace(
    PRESETS["olmoe-1b-7b"], n_layer=3)


def pattern_types(pattern: str) -> tuple:
    """`layer_types` of a `hybrid_override_pattern` (`model_type`
    nemotron_h): M a Mamba-2 block, * an attention block, E an experts
    block — the ONE spelling the block kinds have here."""
    return tuple({"M": "ssm", "*": "full", "E": llama.EXPERTS}[c]
                 for c in pattern)


# NVIDIA-Nemotron-3-Super-120B-A12B (nvidia/NVIDIA-Nemotron-3-Super-120B-
# A12B-BF16 config.json, `model_type` nemotron_h): 88 blocks of ONE mixer
# each — h = RMSNorm(x), x' = x + Mixer(h) — 40 Mamba-2 blocks (128 heads of
# 64 in 8 groups, state 128, 4 taps, the gate before a norm within each
# group), 8 attention blocks (GQA 32 query / 2 KV heads of 128, NO rotary
# embedding and no other position signal) and 40 LatentMoE blocks: sigmoid
# scores of the 4096-wide h with a selection bias, 22 of 512 a token,
# weights normalised times 5, the experts relu^2 (two matrices, no gate)
# 1024 -> 2688 -> 1024 on u = h W_down, ONE W_up after the weighted sum, and
# an ungated relu^2 shared expert 4096 -> 5376 -> 4096 on h itself; no bias
# but the convolution's; vocabulary 131072, untied. The multi-token-
# prediction module is not served. What the config leaves open is `assumed`
# in chipbench/configs/nemotron-3-super-120b-a12b-ep4-1chip.json. Never
# instantiated whole. The pattern is the published one: 40 M, 8 *, 40 E.
# `expert_out_init` 0.5: the seeded init's expert and shared-expert output
# projections (1 / sqrt(fan-in)) times this — relu^2 of a unit-variance
# product has an RMS of 1.22, and at 1 an E block is 3-4x the stream a
# Mamba-2 block leaves
_NEMOTRON3_PATTERN = (
    "MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
    "EMEMEMEM*EMEMEMEME")
PRESETS["nemotron-3-super-120b-a12b"] = MixtralConfig(
    block_size=262144, vocab_size=131072, n_layer=88, n_head=32, n_kv_head=2,
    n_embd=4096, d_ff=2688, head_dim_override=128, rms_eps=1e-5,
    mlp_act="relu2", expert_gated=False, moe_latent=1024, n_expert=512,
    router_top_k=22, router_norm_topk=True, capacity_factor=512.0,
    d_shared=5376, shared_gate=False, expert_out_init=0.5,
    router=RouterConfig(scoring="sigmoid", select_bias=True, scale=5.0),
    kv_full=llama.KvKind(window=None, rope=False),
    mamba=llama.Mamba2Config(d_ssm=8192, n_head=128, d_state=128, n_groups=8,
                             conv=4, chunk=128, beside=False),
    layer_types=pattern_types(_NEMOTRON3_PATTERN))
# the benchmark's cut (chipbench/configs/nemotron-3-super-120b-a12b-ep4-
# 1chip.json): one chip's share of a 4-chip expert-parallel layer — experts
# 0-127 of each E block's 512, rows 0-32767 of the vocabulary (a quarter),
# mixers, router, latent projections, shared expert and norms whole — and
# the first period of eleven blocks (MEMEMEM*EME: 5 M, 1 *, 5 E)
PRESETS["nemotron-3-super-120b-a12b-ep4-1chip"] = dataclasses.replace(
    PRESETS["nemotron-3-super-120b-a12b"], n_layer=11,
    layer_types=pattern_types(_NEMOTRON3_PATTERN[:11]), vocab_size=32768,
    experts_first=0, experts_held=128)
# tiny Nemotron-H for the CPU tests, every switch of the real one acting:
# all three kinds in an order that puts an E first after an M and a *
# between two E, 2 groups of 2 state heads of 16 (state 16, a chunk of 8
# that a 16-token prefill chunk holds twice), GQA 2:1 with a decoupled head
# width and NO rotation, 16 experts 6 a token with 4 held, a latent of 32
# under a model of 64, an expert width (24) that is no multiple of a tile,
# a seeded selection bias, scaling 2.5, an ungated relu^2 shared expert
PRESETS["nemotron-h-test"] = MixtralConfig(
    block_size=128, vocab_size=256, n_layer=7, n_head=4, n_kv_head=2,
    n_embd=64, d_ff=24, head_dim_override=16, rms_eps=1e-5,
    mlp_act="relu2", expert_gated=False, moe_latent=32, n_expert=16,
    router_top_k=6, router_norm_topk=True, capacity_factor=16.0,
    experts_first=0, experts_held=4, d_shared=48, shared_gate=False,
    expert_out_init=0.5,
    router=RouterConfig(scoring="sigmoid", select_bias=True, scale=2.5),
    kv_full=llama.KvKind(window=None, rope=False),
    mamba=llama.Mamba2Config(d_ssm=64, n_head=4, d_state=16, n_groups=2,
                             conv=4, chunk=8, beside=False),
    layer_types=pattern_types("MEME*EM"))


@jax.named_scope("moe.shared")
def _shared_expert_out(moe_p, h, *, compute_dtype=None):
    """The always-on shared expert (Qwen2-MoE / DeepSeek recipe): a
    dense SwiGLU over h, scaled per token by sigmoid(h @ shared_gate)
    where the tree carries that gate (Qwen2-MoE; DeepSeek-V3's adds as
    it is). Adds to the ROUTED output — identical math on the
    dense-grouped and EP paths (the shared weights replicate; only routed
    experts shard)."""
    from dnn_tpu.ops.nn import linear, silu

    sp = moe_p["shared"]
    if "gate" not in sp:  # two matrices under relu^2, no gate (Nemotron-H)
        return linear(sp["down"], llama.relu2(
            linear(sp["up"], h, compute_dtype=compute_dtype)),
            compute_dtype=compute_dtype).astype(h.dtype)
    s = linear(sp["down"],
               silu(linear(sp["gate"], h, compute_dtype=compute_dtype))
               * linear(sp["up"], h, compute_dtype=compute_dtype),
               compute_dtype=compute_dtype)
    if "shared_gate" not in moe_p:
        return s.astype(h.dtype)
    g = jax.nn.sigmoid(
        linear(moe_p["shared_gate"], h,
               compute_dtype=compute_dtype).astype(jnp.float32))
    return (g * s.astype(jnp.float32)).astype(h.dtype)


def _local_ep_ffn(cfg: MixtralConfig, *, axis: str, capacity: int,
                  compute_dtype=None):
    """The per-device EP ffn closure every expert-parallel builder
    installs (make_apply_ep, make_generate_ep, make_pipeline_generate_ep
    — ONE definition so a new MoE switch cannot silently diverge
    between them): routed experts via moe_ffn_local (all_to_all over
    `axis`), plus the locally-computed shared expert for d_shared
    configs (its weights replicate; only routed experts shard)."""
    from dnn_tpu.parallel.moe import moe_ffn_local

    def ffn(bp, h):
        d = h.shape[-1]
        out = moe_ffn_local(
            bp["moe"], h.reshape(-1, d), top_k=cfg.router_top_k,
            capacity=capacity, axis_name=axis,
            compute_dtype=compute_dtype,
            normalize=cfg.router_norm_topk,
        ).reshape(h.shape).astype(h.dtype)
        if cfg.d_shared:
            out = out + _shared_expert_out(bp["moe"], h,
                                           compute_dtype=compute_dtype)
        return out

    return ffn


def make_ffn(cfg: MixtralConfig, *, compute_dtype=None, groups: int = 1):
    """The llama `ffn` hook: (block_params, h) -> MoE MLP output, through
    the grouped drop-free experts. `ffn.with_stats(bp, h)` also returns
    that layer call's cost (moe_ffn_grouped's int32 (N_STATS,)), which the
    batcher's adapter sums into the moe_* counters. `ffn.expert_forms`:
    the forms the expert matmuls of the programs traced so far took
    (parallel/moe._experts_grouped: "stack_kernel" / "ragged_dot"; what
    /statusz reports) — and, by being there, what tells a layer loop that
    this hook takes its expert stacks whole (`llama.scan_form`).

    `groups` > 1 is NOT a serving option: it selects the expert-parallel
    path's dense twin (static capacity per routing group, parallel/moe.
    moe_ffn), which the EP parity tests compare an n-device run with."""
    from dnn_tpu.ops.nn import linear, silu

    forms = set()

    def routed(bp, h, return_stats):
        if groups != 1:
            return moe_ffn(bp["moe"], h, top_k=cfg.router_top_k,
                           capacity_factor=cfg.capacity_factor,
                           groups=groups, compute_dtype=compute_dtype,
                           normalize=cfg.router_norm_topk)
        grouped = functools.partial(
            moe_ffn_grouped, bp["moe"], h, top_k=cfg.router_top_k,
            normalize=cfg.router_norm_topk, compute_dtype=compute_dtype,
            return_stats=return_stats, held=cfg.held,
            scoring=cfg.router.scoring, scale=cfg.router.scale, forms=forms)
        if cfg.moe_latent is None:
            return grouped(activation=silu)
        # the router scores h; the experts read, and their weighted sum is,
        # a latent row; ONE up-projection follows the sum
        with jax.named_scope("moe.latent_down"):
            u = linear(bp["moe"]["latent_down"], h,
                       compute_dtype=compute_dtype)
        out = grouped(activation=llama._mlp_act(cfg), rows=u)
        r, stats = out if return_stats else (out, None)
        with jax.named_scope("moe.latent_up"):
            y = linear(bp["moe"]["latent_up"], r,
                       compute_dtype=compute_dtype).astype(h.dtype)
        return (y, stats) if return_stats else y

    def with_shared(bp, h, out):
        if cfg.d_shared:
            out = out + _shared_expert_out(bp["moe"], h,
                                           compute_dtype=compute_dtype)
        return out

    def dense(bp, h):
        # a block of the dense prefix (`first_k_dense`): its own gated MLP
        return llama._mlp_out(bp, h, cfg=cfg, compute_dtype=compute_dtype)

    def ffn(bp, h):
        if "moe" not in bp:
            return dense(bp, h)
        return with_shared(bp, h, routed(bp, h, False))

    if groups == 1:
        def with_stats(bp, h):
            if "moe" not in bp:  # no expert layer call: nothing counted
                return dense(bp, h), jnp.zeros((N_STATS,), jnp.int32)
            out, stats = routed(bp, h, True)
            return with_shared(bp, h, out), stats

        ffn.with_stats = with_stats
        ffn.expert_forms = forms
    return ffn


def init_parts(rng, cfg: MixtralConfig = PRESETS["mixtral-test"],
               dtype=jnp.float32):
    """`init`, a top-level entry at a time (`llama.init_parts`): a layer's
    function draws its attention half and then its dense MLP or its
    expert stacks, from that layer's keys alone."""
    import math

    parts = llama.init_parts(rng, cfg, dtype, include_mlp=False)
    keys = jax.random.split(jax.random.fold_in(rng, 7), cfg.n_layer)

    def dense_layer(i, block):
        blk = block()
        blk["mlp"] = llama.init_gated_mlp(
            jax.random.split(keys[i], 3), cfg, cfg.d_ff_dense, dtype)
        return blk

    def kernel(key, shape):  # 1 / sqrt(fan-in)
        return {"kernel": (jax.random.normal(key, shape)
                           / math.sqrt(shape[0])).astype(dtype)}

    def expert_layer(i, block):
        blk = block()
        if cfg.expert_gated:
            moe = init_moe_gated(keys[i], cfg.n_embd, cfg.n_expert, cfg.d_ff,
                                 dtype, n_held=cfg.experts_held)
        else:
            moe = init_moe_plain(keys[i], cfg.n_embd, cfg.n_expert, cfg.d_ff,
                                 dtype, n_held=cfg.experts_held,
                                 d_in=cfg.moe_latent)
        out = "wd" if cfg.expert_gated else "wo"
        if cfg.expert_out_init != 1.0:
            moe[out] = moe[out] * jnp.asarray(cfg.expert_out_init,
                                              moe[out].dtype)
        if cfg.moe_latent is not None:
            kd, ku = jax.random.split(jax.random.fold_in(keys[i], 3))
            moe["latent_down"] = kernel(kd, (cfg.n_embd, cfg.moe_latent))
            moe["latent_up"] = kernel(ku, (cfg.moe_latent, cfg.n_embd))
        if cfg.router.select_bias:
            moe["router"]["select_bias"] = (
                _SELECT_BIAS_INIT * jax.random.normal(
                    jax.random.fold_in(keys[i], 2), (cfg.n_expert,))
            ).astype(jnp.float32)
        if cfg.d_shared and not cfg.expert_gated:
            ks = jax.random.split(jax.random.fold_in(keys[i], 1), 2)
            down = kernel(ks[1], (cfg.d_shared, cfg.n_embd))
            down["kernel"] = down["kernel"] * jnp.asarray(
                cfg.expert_out_init, dtype)
            moe["shared"] = {"up": kernel(ks[0], (cfg.n_embd, cfg.d_shared)),
                             "down": down}
        elif cfg.d_shared:
            ks = jax.random.split(jax.random.fold_in(keys[i], 1), 4)
            si = 1.0 / math.sqrt(cfg.n_embd)
            so = 1.0 / math.sqrt(cfg.d_shared)
            moe["shared"] = {
                "gate": {"kernel": (jax.random.normal(
                    ks[0], (cfg.n_embd, cfg.d_shared)) * si).astype(dtype)},
                "up": {"kernel": (jax.random.normal(
                    ks[1], (cfg.n_embd, cfg.d_shared)) * si).astype(dtype)},
                "down": {"kernel": (jax.random.normal(
                    ks[2], (cfg.d_shared, cfg.n_embd)) * so).astype(dtype)},
            }
            if cfg.shared_gate:
                moe["shared_gate"] = {"kernel": (jax.random.normal(
                    ks[3], (cfg.n_embd, 1)) * si).astype(dtype)}
        blk["moe"] = moe
        return blk

    for i in range(cfg.n_layer):
        if cfg.one_mixer and cfg.layer_types[i] != llama.EXPERTS:
            continue  # the block's one mixer is llama.init_block's
        parts[f"h_{i}"] = functools.partial(
            dense_layer if i < cfg.first_k_dense else expert_layer, i,
            parts[f"h_{i}"])
    return parts


def init(rng, cfg: MixtralConfig = PRESETS["mixtral-test"],
         dtype=jnp.float32):
    """llama.init minus the dense MLPs (include_mlp=False — no transient
    dense weights at 8x7b scale), plus each block's gated expert stack
    (and, for d_shared configs, the always-on shared expert + its
    sigmoid gate)."""
    return {name: make() for name, make in init_parts(rng, cfg,
                                                      dtype).items()}


def make_apply(cfg: MixtralConfig, *, compute_dtype=None, remat=False):
    # cfg.default_ffn resolves the expert hook inside llama.make_apply
    return llama.make_apply(cfg, compute_dtype=compute_dtype, remat=remat)


def make_generate(cfg: MixtralConfig, *, max_new_tokens: int,
                  temperature: float = 0.0, top_k: Optional[int] = None,
                  top_p: Optional[float] = None, compute_dtype=None,
                  kv_dtype=None, attn_kernel="auto"):
    """llama.make_generate with the MoE hook (config-resolved) — prefill
    routes (B, T) tokens, each decode step routes (B, 1); same KV-width
    GQA cache, same attn_kernel/kv_dtype options."""
    return llama.make_generate(
        cfg, max_new_tokens=max_new_tokens, temperature=temperature,
        top_k=top_k, top_p=top_p, compute_dtype=compute_dtype,
        kv_dtype=kv_dtype, attn_kernel=attn_kernel)


def family_rows(cfg: MixtralConfig, *, compute_dtype=None,
                attn_kernel="auto"):
    """ContinuousBatcher adapter: LlamaFamilyRows resolves the MoE hook
    from the config — prefill chunks, per-slot decode rows, and
    speculative verify all route through the experts."""
    return llama.family_rows(cfg, compute_dtype=compute_dtype,
                             attn_kernel=attn_kernel)


def _ep_param_spec(path, leaf, *, axis, stage_axis=None):
    """PartitionSpec for one param leaf under expert parallelism, derived
    from the ACTUAL pytree (config variants — attn_bias, post-norms,
    tied/no-lm_head — shard correctly instead of tripping a hardcoded
    structure): only the expert stacks shard on their E axis; everything
    else replicates (or shards over `stage_axis` for pipeline stage
    blocks, whose leaves carry a leading (S, per_stage, ...) so E sits at
    index 2)."""
    from jax.sharding import PartitionSpec as P

    keys = [p.key for p in path if hasattr(p, "key")]
    expert_leaf = "moe" in keys and keys and keys[-1] in (
        "wg", "wu", "wd", "wg_scale", "wu_scale", "wd_scale")
    if stage_axis is not None:
        if expert_leaf:
            return P(stage_axis, None, axis)
        return P(stage_axis)
    if expert_leaf:
        return P(None, axis)
    return P()


def make_apply_ep(cfg: MixtralConfig, mesh, *, axis_name: Optional[str] = None,
                  compute_dtype=None):
    """Expert-parallel Mixtral forward over `mesh`'s expert axis — the
    GShard fabric (parallel/moe.moe_ffn_local: two all_to_alls move
    tokens to their experts' owners and back over ICI) under the llama
    block via the ffn hook.

    apply(params, ids): ids (B, T), B divisible by the axis size; the
    batch shards over the expert axis (each device's local batch is its
    routing group), expert stacks shard on their E axis, attention/norm
    weights replicate. Identical math to the dense forward with
    `make_ffn(cfg, groups=n)` — the parity contract
    tests/test_mixtral.py pins (same as the GPT-MoE family's)."""
    from jax.sharding import PartitionSpec as P

    from dnn_tpu.parallel.mesh import EXPERT_AXIS
    from dnn_tpu.parallel.moe import moe_capacity, moe_ffn_local

    axis = axis_name or EXPERT_AXIS
    n = mesh.shape[axis]
    if cfg.n_expert % n:
        raise ValueError(
            f"n_expert={cfg.n_expert} not divisible by axis size {n}")

    def local_fn(prep_local, ids_local):
        x = llama._scaled_embed(prep_local, ids_local, cfg)
        if compute_dtype is not None:
            x = x.astype(compute_dtype)
        b_local, t = ids_local.shape
        s = b_local * t  # this device's tokens = one routing group
        capacity = moe_capacity(s, cfg.n_expert, cfg.router_top_k,
                                cfg.capacity_factor)

        ep_ffn = _local_ep_ffn(cfg, axis=axis, capacity=capacity,
                               compute_dtype=compute_dtype)

        x = llama.blocks_scan(prep_local["blocks"], x, cfg=cfg,
                              compute_dtype=compute_dtype, ffn=ep_ffn,
                              windows=llama.layer_windows(cfg))
        return llama.head(prep_local, x.astype(jnp.float32), cfg=cfg,
                          compute_dtype=compute_dtype)

    def apply(params, ids):
        b = ids.shape[0]
        if b % n:
            raise ValueError(
                f"batch {b} not divisible by expert-axis size {n}")
        prepared = _as_prepared(params, cfg)
        param_specs = jax.tree_util.tree_map_with_path(
            lambda p, leaf: _ep_param_spec(p, leaf, axis=axis), prepared)
        return jax.shard_map(
            local_fn, mesh=mesh,
            in_specs=(param_specs, P(axis)),
            out_specs=P(axis),
            check_vma=False,
        )(prepared, ids)

    return apply


def _as_prepared(params, cfg):
    """Accept either the raw h_i layout or the stacked-blocks layout."""
    if "blocks" in params:
        return params
    prepared = {k: v for k, v in params.items() if not k.startswith("h_")}
    prepared["blocks"] = gpt.stack_blocks(params, range(cfg.n_layer))
    return prepared


def make_generate_ep(cfg: MixtralConfig, mesh, *, max_new_tokens: int,
                     temperature: float = 0.0,
                     sample_top_k: Optional[int] = None,
                     compute_dtype=None, kv_dtype=None,
                     axis_name: Optional[str] = None):
    """Expert-parallel Mixtral KV-cache generation over `mesh`'s expert
    axis — the serving form of make_apply_ep: the WHOLE generate (prefill
    + lax.scan decode) is one shard_map program; batch and its KV cache
    shard over the expert axis (each device's local batch is its routing
    group, so the cache lives with the tokens it serves), expert stacks
    shard on E, and tokens reach their experts via all_to_all inside
    every prefill and decode-step forward
    (parallel/moe.moe_ffn_local).

    generate(params, ids, rng): ids (B, T), B divisible by the axis size.
    Greedy output equals the solo decoder with `make_ffn(cfg,
    groups=axis_size)` token-for-token (same per-column routing groups —
    the GPT-MoE family's EP parity contract, generate_moe.py, extended to
    this family); sampled output folds the device index into the rng
    stream, matching in distribution rather than draw-for-draw."""
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from dnn_tpu.parallel.mesh import EXPERT_AXIS
    from dnn_tpu.parallel.moe import moe_capacity, moe_ffn_local
    from dnn_tpu.runtime.generate import _sample

    if max_new_tokens < 1:
        raise ValueError(f"max_new_tokens must be >= 1, got {max_new_tokens}")
    axis = axis_name or EXPERT_AXIS
    n = mesh.shape[axis]
    if cfg.n_expert % n:
        raise ValueError(
            f"n_expert={cfg.n_expert} not divisible by axis size {n}")

    def per_device(prep_local, ids_local, rng):
        b, t = ids_local.shape  # local batch = this device's routing group
        s_max = t + max_new_tokens
        cache_dtype = kv_dtype if kv_dtype is not None else (
            compute_dtype or jnp.float32)
        cache = llama.init_cache(cfg, b, s_max, cache_dtype)

        def ffn_for(tokens_per_group):
            capacity = moe_capacity(tokens_per_group, cfg.n_expert,
                                    cfg.router_top_k, cfg.capacity_factor)
            return _local_ep_ffn(cfg, axis=axis, capacity=capacity,
                                 compute_dtype=compute_dtype)

        logits, cache = llama.forward_with_cache(
            prep_local, ids_local, cache, 0, cfg=cfg,
            compute_dtype=compute_dtype, ffn=ffn_for(b * t),
            attn_kernel=False)  # inside shard_map: keep the einsum
        rng = jax.random.fold_in(rng, lax.axis_index(axis))
        rng, sub = jax.random.split(rng)
        tok = _sample(logits[:, -1], sub, temperature=temperature,
                      top_k=sample_top_k)
        step_ffn = ffn_for(b)

        def step(carry, i):
            cache, tok, rng = carry
            logits, cache = llama.forward_with_cache(
                prep_local, tok[:, None], cache, t + i, cfg=cfg,
                compute_dtype=compute_dtype, ffn=step_ffn,
                attn_kernel=False)
            rng, sub = jax.random.split(rng)
            nxt = _sample(logits[:, -1], sub, temperature=temperature,
                          top_k=sample_top_k)
            return (cache, nxt, rng), tok

        (_, last, _), toks = lax.scan(
            step, (cache, tok, rng), jnp.arange(max_new_tokens - 1))
        toks = jnp.moveaxis(toks, 0, 1)
        return jnp.concatenate([toks, last[:, None]], axis=1)

    @jax.jit
    def generate(params, ids, rng):
        b, t = ids.shape
        if b % n:
            raise ValueError(
                f"batch {b} not divisible by expert-axis size {n}")
        if t + max_new_tokens > cfg.block_size:
            raise ValueError(
                f"prompt {t} + max_new_tokens {max_new_tokens} exceeds "
                f"block_size {cfg.block_size}")
        prepared = _as_prepared(params, cfg)
        param_specs = jax.tree_util.tree_map_with_path(
            lambda p, leaf: _ep_param_spec(p, leaf, axis=axis), prepared)
        return jax.shard_map(
            per_device, mesh=mesh,
            in_specs=(param_specs, P(axis), P()),
            out_specs=P(axis),
            check_vma=False,
        )(prepared, ids, rng)

    return generate


def make_pipeline_generate_ep(cfg: MixtralConfig, mesh, *,
                              max_new_tokens: int,
                              temperature: float = 0.0,
                              sample_top_k: Optional[int] = None,
                              compute_dtype=None, kv_dtype=None,
                              stage_axis: Optional[str] = None,
                              expert_axis: Optional[str] = None):
    """EP x PP 2D Mixtral decode over a {stage, expert} mesh — the llama
    -family mirror of generate_moe.make_pipeline_generate_moe_ep: layers
    shard over the STAGE axis (the ppermute decode ring, KV-head-width
    stage cache shards), each stage's expert stacks shard over the EXPERT
    axis, tokens reach their experts via all_to_all WITHIN the stage row
    while the hidden state rides the stage ring — both collectives per
    decode step, each on its own mesh axis.

    generate(stage_blocks, aux, ids, rng): `stage_blocks` from
    runtime.generate.prepare_pipeline_stacked (expert leaves are
    re-placed over the expert axis here); ids (B, T), B divisible by the
    expert-axis size. Greedy output equals the solo decoder with
    `make_ffn(cfg, groups=n_exp)` token-for-token.

    Same deliberate schedule duplication as the GPT EP x PP decoder (see
    generate_moe.py's NOTE): the capacity-dependent ffn (one compiled
    program for the prefill chunk, another for decode steps) cannot ride
    the one-block-function family-adapter protocol."""
    from jax import lax
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from dnn_tpu.parallel.mesh import EXPERT_AXIS, STAGE_AXIS
    from dnn_tpu.parallel.moe import moe_capacity, moe_ffn_local
    from dnn_tpu.runtime.generate import _sample
    from dnn_tpu.runtime.kvcache import codec_for_cache
    from dnn_tpu.runtime.paged_kvcache import scan_rows

    if max_new_tokens < 1:
        raise ValueError(f"max_new_tokens must be >= 1, got {max_new_tokens}")
    if cfg.alt_window:
        raise ValueError(
            "alternating-window configs are not supported on the pipeline "
            "decode path (no per-layer window channel in the stage scan)")
    s_axis = stage_axis or STAGE_AXIS
    e_axis = expert_axis or EXPERT_AXIS
    num_stages = mesh.shape[s_axis]
    n_exp = mesh.shape[e_axis]
    if cfg.n_layer % num_stages:
        raise ValueError(
            f"n_layer {cfg.n_layer} not divisible by {num_stages} stages")
    if cfg.n_expert % n_exp:
        raise ValueError(
            f"n_expert {cfg.n_expert} not divisible by expert axis {n_exp}")
    per_stage = cfg.n_layer // num_stages
    perm = [(i, (i + 1) % num_stages) for i in range(num_stages)]

    def _place(stage_blocks):
        specs = jax.tree_util.tree_map_with_path(
            lambda p, leaf: _ep_param_spec(p, leaf, axis=e_axis,
                                           stage_axis=s_axis), stage_blocks)
        return jax.device_put(
            stage_blocks,
            jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                         is_leaf=lambda x: isinstance(x, P)),
        ), specs

    def per_device(stage_blocks, aux, ids_local, rng):
        local = jax.tree.map(lambda p: p[0], stage_blocks)  # (per, ...)
        d = lax.axis_index(s_axis)
        b, t = ids_local.shape  # local batch = this expert column's group
        s_max = t + max_new_tokens
        cache_dtype = kv_dtype if kv_dtype is not None else (
            compute_dtype or jnp.float32)
        stage_cfg = dataclasses.replace(cfg, n_layer=per_stage)
        cache = llama.init_cache(stage_cfg, b, s_max, cache_dtype)
        codec = codec_for_cache(cache, window=cfg.sliding_window,
                                softcap=cfg.attn_softcap)

        def ffn_for(tokens_per_group):
            capacity = moe_capacity(tokens_per_group, cfg.n_expert,
                                    cfg.router_top_k, cfg.capacity_factor)
            return _local_ep_ffn(cfg, axis=e_axis, capacity=capacity,
                                 compute_dtype=compute_dtype)

        def ring_pass(x, cache, start_pos, ffn):
            def sub(carry, s):
                h, cache = carry

                h2, cache2 = scan_rows(
                    lambda bp, x, rows: llama._block_with_cache(
                        bp, x, rows, start_pos, cfg=cfg,
                        compute_dtype=compute_dtype, codec=codec, ffn=ffn),
                    h, local, cache)
                active = d == s
                cache = jax.tree.map(
                    lambda new, old: jnp.where(active, new, old),
                    cache2, cache)
                h = lax.ppermute(h2, s_axis, perm)
                return (h, cache), None

            (h, cache), _ = lax.scan(sub, (x, cache), jnp.arange(num_stages))
            return h, cache

        def sample_last(h, sub_rng):
            logits = llama.head(aux, h[:, -1:].astype(jnp.float32), cfg=cfg,
                                compute_dtype=compute_dtype)
            tok = _sample(logits[:, -1], sub_rng, temperature=temperature,
                          top_k=sample_top_k)
            return lax.psum(
                jnp.where(d == 0, tok, jnp.zeros_like(tok)), s_axis)

        rng = jax.random.fold_in(rng, lax.axis_index(e_axis))
        x = llama._scaled_embed(aux, ids_local, cfg)
        if compute_dtype is not None:
            x = x.astype(compute_dtype)
        h, cache = ring_pass(x, cache, 0, ffn_for(b * t))
        rng, sub = jax.random.split(rng)
        tok = sample_last(h, sub)
        step_ffn = ffn_for(b)

        def step(carry, i):
            cache, tok, rng = carry
            x = llama._scaled_embed(aux, tok[:, None], cfg)
            if compute_dtype is not None:
                x = x.astype(compute_dtype)
            h, cache = ring_pass(x, cache, t + i, step_ffn)
            rng, sub = jax.random.split(rng)
            nxt = sample_last(h, sub)
            return (cache, nxt, rng), tok

        (_, last, _), toks = lax.scan(
            step, (cache, tok, rng), jnp.arange(max_new_tokens - 1))
        toks = jnp.moveaxis(toks, 0, 1)
        return jnp.concatenate([toks, last[:, None]], axis=1)

    compiled = {}  # one jitted program per param-tree structure

    def generate(stage_blocks, aux, ids, rng):
        b, t = ids.shape
        if b % n_exp:
            raise ValueError(
                f"batch {b} not divisible by expert-axis size {n_exp}")
        if t + max_new_tokens > cfg.block_size:
            raise ValueError(
                f"prompt {t} + max_new_tokens {max_new_tokens} exceeds "
                f"block_size {cfg.block_size}")
        placed, specs = _place(stage_blocks)
        key = jax.tree_util.tree_structure(stage_blocks)
        if key not in compiled:
            compiled[key] = jax.jit(jax.shard_map(
                per_device, mesh=mesh,
                in_specs=(specs, P(), P(e_axis), P()),
                out_specs=P(e_axis),
                check_vma=False,
            ))
        return compiled[key](placed, aux, ids, rng)

    return generate


# --------------------------------------------------------------------------
# HF conversion
# --------------------------------------------------------------------------

def params_from_state_dict(sd, *, n_layer: Optional[int] = None):
    """HF MixtralForCausalLM, Qwen2MoeForCausalLM OR OlmoeForCausalLM
    state dict -> this pytree (layout auto-detected from the keys).
    Attention/norm/embed
    leaves ride checkpoint.llama_params_from_state_dict's mapping; each
    layer's MoE converts here: the router weight (E, D) -> kernel
    (D, E); per-expert SwiGLU triples stack expert-major to wg/wu/wd
    (Mixtral: block_sparse_moe.experts.i.{w1,w3,w2}; Qwen2-MoE:
    mlp.experts.i.{gate,up,down}_proj, plus mlp.shared_expert.* and the
    sigmoid shared_expert_gate; OLMoE: the same expert and router names,
    no shared expert, and self_attn.{q,k}_norm over the projection
    width, which the llama converter maps)."""
    import numpy as np

    sd = {(k[len("model."):] if k.startswith("model.") else k): v
          for k, v in sd.items()}
    if any(".mlp.experts." in k for k in sd):
        return _qwen2_moe_from_sd(sd, n_layer=n_layer)
    if n_layer is None:
        n_layer = 1 + max(
            int(k.split(".")[1]) for k in sd
            if k.startswith("layers.") and k.split(".")[1].isdigit())

    # attention/norms/embed via the llama converter on a filtered dict
    # (it requires mlp.* keys, which Mixtral does not have — feed it
    # per-layer aliases pointing at one expert, then overwrite)
    base_keys = {k: v for k, v in sd.items() if "block_sparse_moe" not in k}
    for i in range(n_layer):
        p = f"layers.{i}."
        e0 = p + "block_sparse_moe.experts.0."
        base_keys[p + "mlp.gate_proj.weight"] = sd[e0 + "w1.weight"]
        base_keys[p + "mlp.up_proj.weight"] = sd[e0 + "w3.weight"]
        base_keys[p + "mlp.down_proj.weight"] = sd[e0 + "w2.weight"]
    from dnn_tpu.io.checkpoint import llama_params_from_state_dict

    params = llama_params_from_state_dict(base_keys, n_layer=n_layer)

    def _t(w):  # torch Linear (out, in) -> (in, out)
        return np.ascontiguousarray(np.asarray(w).T)

    for i in range(n_layer):
        p = f"layers.{i}.block_sparse_moe."
        n_expert = 1 + max(
            int(k[len(p + "experts."):].split(".")[0]) for k in sd
            if k.startswith(p + "experts."))
        blk = dict(params[f"h_{i}"])
        del blk["mlp"]
        blk["moe"] = {
            "router": {"kernel": _t(sd[p + "gate.weight"])},
            "wg": np.stack([_t(sd[f"{p}experts.{e}.w1.weight"])
                            for e in range(n_expert)]),
            "wu": np.stack([_t(sd[f"{p}experts.{e}.w3.weight"])
                            for e in range(n_expert)]),
            "wd": np.stack([_t(sd[f"{p}experts.{e}.w2.weight"])
                            for e in range(n_expert)]),
        }
        params[f"h_{i}"] = blk
    return params


def _qwen2_moe_from_sd(sd, *, n_layer: Optional[int] = None):
    """Qwen2MoeForCausalLM / OlmoeForCausalLM layout (already
    model.-stripped): routed experts under mlp.experts.i.{gate,up,down}
    _proj, router under mlp.gate, and for Qwen2-MoE the shared expert +
    its scalar gate alongside."""
    import numpy as np

    if n_layer is None:
        n_layer = 1 + max(
            int(k.split(".")[1]) for k in sd
            if k.startswith("layers.") and k.split(".")[1].isdigit())

    # attention/norms/embed via the llama converter on a filtered dict
    # (it requires mlp.* keys; feed it per-layer aliases pointing at one
    # expert, then overwrite — the Mixtral converter's trick)
    base_keys = {k: v for k, v in sd.items() if ".mlp." not in k}
    for i in range(n_layer):
        p = f"layers.{i}."
        e0 = p + "mlp.experts.0."
        base_keys[p + "mlp.gate_proj.weight"] = sd[e0 + "gate_proj.weight"]
        base_keys[p + "mlp.up_proj.weight"] = sd[e0 + "up_proj.weight"]
        base_keys[p + "mlp.down_proj.weight"] = sd[e0 + "down_proj.weight"]
    from dnn_tpu.io.checkpoint import llama_params_from_state_dict

    params = llama_params_from_state_dict(base_keys, n_layer=n_layer)

    def _t(w):  # torch Linear (out, in) -> (in, out)
        return np.ascontiguousarray(np.asarray(w).T)

    for i in range(n_layer):
        p = f"layers.{i}.mlp."
        n_expert = 1 + max(
            int(k[len(p + "experts."):].split(".")[0]) for k in sd
            if k.startswith(p + "experts."))
        blk = dict(params[f"h_{i}"])
        del blk["mlp"]
        blk["moe"] = {
            "router": {"kernel": _t(sd[p + "gate.weight"])},
            "wg": np.stack([_t(sd[f"{p}experts.{e}.gate_proj.weight"])
                            for e in range(n_expert)]),
            "wu": np.stack([_t(sd[f"{p}experts.{e}.up_proj.weight"])
                            for e in range(n_expert)]),
            "wd": np.stack([_t(sd[f"{p}experts.{e}.down_proj.weight"])
                            for e in range(n_expert)]),
        }
        if p + "shared_expert_gate.weight" in sd:  # Qwen2-MoE; OLMoE has none
            blk["moe"]["shared"] = {
                "gate": {"kernel": _t(sd[p + "shared_expert.gate_proj"
                                         ".weight"])},
                "up": {"kernel": _t(sd[p + "shared_expert.up_proj"
                                       ".weight"])},
                "down": {"kernel": _t(sd[p + "shared_expert.down_proj"
                                         ".weight"])},
            }
            blk["moe"]["shared_gate"] = {
                "kernel": _t(sd[p + "shared_expert_gate.weight"])}
        params[f"h_{i}"] = blk
    return params


def to_hf_config(cfg: MixtralConfig, **overrides):
    """transformers.MixtralConfig (or Qwen2MoeConfig for shared-expert
    configs) for parity tests."""
    import transformers

    if cfg.one_mixer:
        return llama.to_hf_config(cfg)  # refuses, by name
    if cfg.d_shared:
        return transformers.Qwen2MoeConfig(
            vocab_size=cfg.vocab_size, hidden_size=cfg.n_embd,
            intermediate_size=cfg.d_ff,
            moe_intermediate_size=cfg.d_ff,
            shared_expert_intermediate_size=cfg.d_shared,
            num_hidden_layers=cfg.n_layer,
            num_attention_heads=cfg.n_head,
            num_key_value_heads=cfg.n_kv_head,
            max_position_embeddings=cfg.block_size,
            rope_theta=cfg.rope_theta, rms_norm_eps=cfg.rms_eps,
            num_experts=cfg.n_expert,
            num_experts_per_tok=cfg.router_top_k,
            norm_topk_prob=cfg.router_norm_topk,
            decoder_sparse_step=1,  # every layer sparse (this pytree)
            tie_word_embeddings=cfg.tie_word_embeddings,
            **overrides)

    kw = dict(
        vocab_size=cfg.vocab_size, hidden_size=cfg.n_embd,
        intermediate_size=cfg.d_ff, num_hidden_layers=cfg.n_layer,
        num_attention_heads=cfg.n_head, num_key_value_heads=cfg.n_kv_head,
        max_position_embeddings=cfg.block_size, rope_theta=cfg.rope_theta,
        rms_norm_eps=cfg.rms_eps, num_local_experts=cfg.n_expert,
        num_experts_per_tok=cfg.router_top_k,
        # HF Mixtral defaults a 4096 sliding window; the released models
        # attend dense and so do we
        sliding_window=None,
    )
    kw.update(overrides)
    return transformers.MixtralConfig(**kw)


# --------------------------------------------------------------------------
# registry
# --------------------------------------------------------------------------

def _register(name: str, cfg: MixtralConfig):
    def convert(sd, _cfg=cfg):
        return params_from_state_dict(sd, n_layer=_cfg.n_layer)

    register_model(ModelSpec(
        name=name,
        init=lambda rng, dtype=jnp.float32, _cfg=cfg: init(rng, _cfg, dtype),
        apply=make_apply(cfg),
        # llama.make_partition resolves the expert hook per stage scan —
        # multi-stage relay partitioning works like any llama family
        partition=llama.make_partition(cfg),
        example_input=gpt.make_example_input(cfg),
        # layers of kinds that interleave do not cut into equal stages
        supported_parts=(1,) if cfg.layer_types is not None
        else tuple(range(1, cfg.n_layer + 1)),
        convert_state_dict=convert,
        config=cfg,
        extras={
            "init_parts": lambda rng, dtype=jnp.float32, _cfg=cfg:
                init_parts(rng, _cfg, dtype),
            "make_apply": lambda compute_dtype=None, **_kw: make_apply(
                cfg, compute_dtype=compute_dtype),
            "family_rows": lambda compute_dtype=None, **_kw: family_rows(
                cfg, compute_dtype=compute_dtype),
        },
    ))


for _name, _cfg in PRESETS.items():
    _register(_name, _cfg)
