"""LLaMA-family decoder-only LM: RMSNorm, RoPE, SwiGLU, grouped-query
attention (GQA).

No counterpart exists in the reference (its only LM is the GPT-2 wrapper
family, /root/reference/partitions/gpt_model_parts.py); this module widens
the model zoo to the architecture most open-weight LMs ship today
(LLaMA 1/2/3, Mistral, Qwen2, TinyLlama — all this block, different
shapes). TPU-first choices:

  * separate q/k/v projections sized H*D and KV*D (GQA's point is the
    smaller KV projections and cache; a fused qkv matmul would erase the
    asymmetry) — all bias-free single matmuls on the MXU;
  * GQA attends GROUPED: q reshapes to (B, KV, G*T, D) so the score and
    value einsums run at KV heads with the group folded into the row dim
    — no repeat/materialization of K/V to H heads, on the forward AND on
    the cached decode path (the KV cache stores KV heads, which is the
    architecture's bandwidth win at decode time);
  * RoPE tables are computed per call from absolute positions (decode
    positions offset by the cache pointer) in f32, HF half-split
    convention (ops/attention.rope_cos_sin/apply_rope) so converted HF
    weights reproduce logits exactly;
  * pipeline partitioning, stacking, and the KV-cache decode reuse the
    same machinery as the GPT family (gpt.layer_ranges / prepare_stacked
    signatures, kvcache codecs), so every parallel runtime — stacked
    pipeline, dp x tp via generic specs, interleaved schedule — and the
    int8 weight/cache paths apply unchanged.

Param pytree (HF LlamaForCausalLM names map 1:1 — see
io/checkpoint.llama_params_from_state_dict):

  {"wte": {"embedding" (V, C)},
   "h_i": {"ln_1": {"scale"}, "attn": {"q","k","v","o": {"kernel"}},
           "ln_2": {"scale"}, "mlp": {"gate","up","down": {"kernel"}}},
   "ln_f": {"scale"}, "lm_head": {"kernel" (C, V)}}
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from dnn_tpu.models import gpt
from dnn_tpu.ops.attention import apply_rope, merge_heads, rope_cos_sin, split_heads
from dnn_tpu.ops.nn import embedding, linear, rms_norm, silu
from dnn_tpu.registry import ModelSpec, StageSpec, register_model

_NEG_BIG = -1e30


@dataclasses.dataclass(frozen=True)
class RetentionConfig:
    """What `LlamaConfig.retention` holds: every layer's mixer is degree-2
    POWER RETENTION (models/retention.py) — no softmax, no K or V kept; a
    slot keeps the decayed sum of its EXPANDED keys a KV head. `tile` is
    the t of the expansion phi (tile pairs i <= j of t x t products:
    `retention.state_width`), `chunk` the positions a closed-form chunk of
    the chunked rule, `gate_range` the decays g a position that the seeded
    gate biases are spread over, a KV head each, `eps` the normaliser's."""
    tile: int = 8
    chunk: int = 1024
    gate_range: tuple = (0.9, 0.9999)
    eps: float = 1e-6


@dataclasses.dataclass(frozen=True)
class Mamba2Config:
    """What `LlamaConfig.mamba` holds: every layer runs a Mamba-2 STATE-SPACE
    mixer (models/mamba2.py) beside its softmax attention, on the same
    normed input, and the two outputs are scaled and added before ONE
    residual. `d_ssm` = `n_head` heads of `head_dim`; B and C are shared by
    the heads of a group (`n_groups`) and `d_state` wide; `conv` taps of the
    short causal convolution over [x | B | C]; `chunk` the positions a
    closed-form chunk of the chunked rule. The muP multipliers by their
    published names: `ssm_in` scales the mixer's input, `ssm_out` its
    output, `ssm_multipliers` the in-projection's slices [z | x | B | C |
    dt]. `beside` False (Nemotron-H): the mixer stands IN PLACE of attention
    in the layers of kind "ssm" of `layer_types` — a kind of slot leaves
    alone — and the other layers run no rule."""
    d_ssm: int = 4096
    n_head: int = 32
    d_state: int = 256
    n_groups: int = 2
    conv: int = 4
    chunk: int = 128
    ssm_in: float = 1.0
    ssm_out: float = 1.0
    ssm_multipliers: tuple = (1.0, 1.0, 1.0, 1.0, 1.0)
    beside: bool = True

    @property
    def head_dim(self):
        return self.d_ssm // self.n_head

    @property
    def conv_width(self):
        """Channels of [x | B | C]: what the convolution runs over."""
        return self.d_ssm + 2 * self.n_groups * self.d_state

    @property
    def proj_width(self):
        """The in-projection's outputs [z | x | B | C | dt]."""
        return self.d_ssm + self.conv_width + self.n_head


@dataclasses.dataclass(frozen=True)
class MupConfig:
    """What `LlamaConfig.mup` holds: the muP multipliers of a block that
    are not the state-space mixer's own (`Mamba2Config`), by their
    published names — a scale on the embedding, on the logits, on
    attention's input, output and keys, on the MLP's gate product and on
    its output. A multiplier left out is a different model that still
    runs."""
    embedding: float = 1.0
    lm_head: float = 1.0
    attention_in: float = 1.0
    attention_out: float = 1.0
    key: float = 1.0
    mlp: tuple = (1.0, 1.0)


@dataclasses.dataclass(frozen=True)
class LightningConfig:
    """What `LlamaConfig.lightning` holds: the widths of a model's "linear"
    layers (models/lightning.py) — linear attention with a FIXED decay a
    head, `n_head` heads of `head_dim`, q and k normed a head and rotated
    (`rope_theta`, the whole head). `chunk`: the positions a closed-form
    chunk of the chunked rule."""
    n_head: int = 32
    head_dim: int = 128
    chunk: int = 256
    rope_theta: float = 10000.0

    @property
    def width(self):
        return self.n_head * self.head_dim


@dataclasses.dataclass(frozen=True)
class BlockSelectConfig:
    """What `LlamaConfig.block_select` holds: the "full" layers read only
    the BLOCKS of `block` positions that a score over mean-pooled keys
    selects (models/block_select.py). A pooled key is the mean of `kernel`
    = 2 x `stride` consecutive keys of a KV head, one every `stride`
    positions; a query reads the `window` positions' blocks up to its own
    always, and of the blocks before them the `topk` of largest score, the
    first `init_blocks` forced among them."""
    block: int = 64
    topk: int = 64
    window: int = 2048
    init_blocks: int = 1
    kernel: int = 32
    stride: int = 16

    @property
    def local_blocks(self):
        return self.window // self.block

    @property
    def rows(self):
        """Pooled keys a block."""
        return self.block // self.stride


@dataclasses.dataclass(frozen=True)
class Rotation:
    """One rotary table: `theta` and a long-context scaling (`_rope_tables`
    says what each type does). `scale` is the type's factor. The rest is
    YaRN's: `original_len` the positions the rotation was trained at,
    `beta_fast` / `beta_slow` the rotations over them that bound the ramp,
    `attention_factor` what cos and sin are multiplied by (None: 0.1 ln
    scale + 1), `truncate` whether the ramp's bounds are whole pairs."""
    theta: float = 10000.0
    scaling: Optional[str] = None
    scale: float = 1.0
    original_len: Optional[int] = None
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: Optional[float] = None
    truncate: bool = True

    @property
    def cos_sin_factor(self) -> float:
        """What cos and sin are multiplied by: 1 but under YaRN."""
        if self.scaling != "yarn":
            return 1.0
        if self.attention_factor is None:
            return 0.1 * math.log(self.scale) + 1.0
        return self.attention_factor


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    block_size: int = 2048
    vocab_size: int = 32000
    n_layer: int = 22
    n_head: int = 32
    n_kv_head: int = 4
    n_embd: int = 2048
    d_ff: int = 5632
    rope_theta: float = 10000.0
    rms_eps: float = 1e-5
    # Mistral-class sliding-window attention: each position attends only
    # the previous `sliding_window` positions (None = dense causal).
    # Dense forwards band-mask; cached decode either window-masks a
    # full-length cache (batcher/pipeline) or stores a rolling ring of
    # exactly `sliding_window` positions (solo generate) — both
    # attention-equivalent (runtime/kvcache.py docstring).
    sliding_window: Optional[int] = None
    # Long-context RoPE scaling (set block_size to the EXTENDED length):
    #   "linear" — positions divided by rope_scale before the tables
    #     (position interpolation; HF rope_scaling type "linear");
    #   "ntk" — theta multiplied by rope_scale^(d/(d-2)) (NTK-aware base
    #     stretch: high frequencies keep local resolution, low
    #     frequencies interpolate);
    #   "yarn" — frequencies by parts and an attention factor on cos and
    #     sin (HF rope_type "yarn"); `rope_yarn` holds its further
    #     parameters (a `Rotation` whose theta, scaling and scale are
    #     these three fields').
    # Every RoPE site goes through _rope_tables, so the dense forward,
    # cached/ring decode, batcher rows, and seq-parallel ring all scale
    # identically. A layer KIND may name a table of its own
    # (`KvKind.rotation`).
    rope_scaling: Optional[str] = None
    rope_scale: float = 1.0
    rope_yarn: Optional[Rotation] = None
    # Qwen2-class q/k/v projection biases (o and the MLP stay bias-free).
    # ops.nn.linear applies any "bias" leaf it finds, so the flag only
    # affects init and the HF config mapping — converted checkpoints
    # carry their biases regardless.
    attn_bias: bool = False
    # ---- Gemma-family architecture switches (all default off, so every
    # pre-Gemma preset is bit-identical to before they existed) ----
    # Gemma decouples head_dim from n_embd/n_head (e.g. 2048/8 heads but
    # d=256); None keeps the LLaMA relation.
    head_dim_override: Optional[int] = None
    # RMSNorm scales by (1 + w) — Gemma checkpoints store zero-centered
    # norm weights (ops.nn.rms_norm plus_one).
    norm_plus_one: bool = False
    # MLP gate nonlinearity: "silu" (LLaMA SwiGLU) or "gelu_tanh"
    # (Gemma GeGLU — torch gelu_pytorch_tanh == jax.nn.gelu approximate).
    mlp_act: str = "silu"
    # Tied input/output embeddings: params carry NO lm_head leaf; head()
    # projects through wte.embedding.T (true weight sharing — one copy in
    # HBM, and a training gradient that flows to the single table).
    tie_word_embeddings: bool = False
    # Gemma scales token embeddings by sqrt(n_embd) at input.
    embed_scale: bool = False
    # Gemma-2: attention scores divide by sqrt(query_scale) instead of
    # sqrt(head_dim) (HF query_pre_attn_scalar). Folded into q after RoPE
    # (q *= sqrt(head_dim/query_scale)) so every attention path — dense,
    # cached, per-row — inherits it through its existing 1/sqrt(d).
    query_scale: Optional[float] = None
    # Gemma-2 logit softcaps: s -> cap * tanh(s / cap) on attention
    # scores (before masking) and on the final lm_head logits.
    attn_softcap: Optional[float] = None
    final_softcap: Optional[float] = None
    # Gemma-2 block shape: RMSNorms AFTER attention and after the MLP
    # (applied to the branch output before its residual add), in addition
    # to the usual pre-norms — param leaves post_ln_1 / post_ln_2.
    post_norms: bool = False
    # Gemma-2 alternating attention: EVEN layers use sliding_window,
    # ODD layers attend globally (matches HF Gemma2's layer pattern).
    # Implemented by threading a per-layer window through the block scan
    # (kvcache._KernelDispatch docstring); a global layer's entry is
    # block_size, which makes the band's lower bound vacuous.
    alt_window: bool = False
    # ---- Phi-family architecture switches (all default off) ----
    # LayerNorm (scale + bias, like GPT-2) instead of RMSNorm at every
    # norm site; rms_eps doubles as the LayerNorm eps.
    layer_norm: bool = False
    # Parallel residual (Phi/GPT-J): attention AND MLP both read the
    # SAME ln_1 output; y = x + attn(h) + mlp(h). No ln_2 exists.
    parallel_block: bool = False
    # Partial rotary (Phi): only the first `rotary_dim` dims of each
    # head rotate; the rest pass through untouched. None = full head.
    rotary_dim: Optional[int] = None
    # Phi puts biases on EVERY projection (o/dense, the MLP pair, and
    # lm_head) — attn_bias covers q/k/v alone (Qwen2).
    dense_bias: bool = False
    # False = the plain 2-layer MLP (fc1 -> act -> fc2; params carry
    # "up"/"down" only, no "gate") instead of the gated SwiGLU/GeGLU.
    mlp_gated: bool = True
    # Qwen3/OLMo-2-class q/k RMSNorm BEFORE RoPE — the training
    # -stability recipe replacing qkv biases. Width "head" (Qwen3):
    # each head's D-vector norms independently (weights (head_dim,));
    # "proj" (OLMo-2): the FULL projected vector norms jointly across
    # heads (weights (H*D,)/(KV*D,)). Leaves attn.q_norm/k_norm.
    qk_norm: bool = False
    qk_norm_width: str = "head"
    # False (OLMo-2): NO pre-norms — attention and the MLP read the RAW
    # residual stream, and only the post-branch norms exist (requires
    # post_norms=True; blocks carry post_ln_1/post_ln_2 but no
    # ln_1/ln_2 leaves).
    pre_norm: bool = True
    # ---- learned sparse attention (DeepSeek-Sparse-Attention-style
    # indexer, models/dsa.py; all default off) ----
    # `index_topk` set: each query attends only the index_topk positions
    # s <= t of largest index score (ties to the smaller s; all of them
    # while fewer exist). The score is the indexer's: index_n_head query
    # heads of index_head_dim against ONE key head per position, which
    # the caches store beside K and V (a third leaf, "ik").
    index_topk: Optional[int] = None
    index_n_head: int = 16
    index_head_dim: int = 64
    # the seeded init draws each q/k norm gain as this times (1 + 0.1 z):
    # per-head unit-RMS q and k give attention logits a sigma of gain_q *
    # gain_k, which decides how far a served token can tell a right
    # selection from a wrong one and float32 from bfloat16 (the Keye
    # presets' value is measured, models/llama_moe.py). 1.0 keeps the
    # gains at exactly one.
    qk_norm_init: float = 1.0
    # ---- power retention in place of attention (models/retention.py;
    # default off): every layer keeps a STATE a slot and no position's
    # anything
    retention: Optional[RetentionConfig] = None
    # ---- a Mamba-2 state-space mixer BESIDE softmax attention in every
    # layer (models/mamba2.py; default off): a slot keeps a state and a
    # convolution tail a layer AND every position's K and V
    mamba: Optional[Mamba2Config] = None
    # muP multipliers (Falcon-H1; None = all 1, nothing traced)
    mup: Optional[MupConfig] = None
    # ---- layers of KINDS in a DENSE model (MiniCPM-SALA; `MixtralConfig`
    # has the same three for its own hybrids): `layer_types[i]` is "full"
    # (softmax attention over K and V, `kv_full`; `attn_gate`: its output
    # times sigmoid(h W_gate), element-wise; `block_select`: it reads the
    # blocks a score over mean-pooled keys selects) or "linear"
    # (`lightning`: a fixed-decay linear-attention state a slot)
    layer_types: Optional[tuple] = None
    kv_full: Optional["KvKind"] = None
    attn_gate: bool = False
    lightning: Optional[LightningConfig] = None
    block_select: Optional[BlockSelectConfig] = None

    @property
    def one_mixer(self) -> bool:
        """Whether a block is ONE norm, ONE mixer and one residual — a
        state-space mixer, attention or experts by the layer's kind
        (`MixtralConfig.layer_types` with "experts": Nemotron-H) — and not
        a mixer followed by a feed-forward part."""
        return False

    def __post_init__(self):
        if self.lightning is not None and (
                self.layer_types is None
                or len(self.layer_types) != self.n_layer
                or set(self.layer_types) - {"full", "linear"}
                or "full" not in self.layer_types
                or (self.kv_full or KvKind()).window is not None
                or self.retention is not None or self.mamba is not None
                or self.sliding_window is not None or self.alt_window
                or self.attn_softcap is not None or self.parallel_block
                or self.index_topk is not None or self.post_norms
                or not self.pre_norm):
            raise ValueError(
                "lightning names the \"linear\" layers of layer_types and "
                "kv_full (which has no window) the \"full\" ones, of which "
                "there is at least one, in the sequential pre-norm block: no "
                "window, softcap, indexer, retention or state-space mixer "
                "goes with it")
        if self.block_select is not None:
            m = self.block_select
            if (self.lightning is None or m.kernel != 2 * m.stride
                    or m.block % m.stride or m.window % m.block
                    or not 0 <= m.init_blocks <= m.topk):
                raise ValueError(
                    "block_select goes with lightning's \"full\" layers; a "
                    "pooled key spans two strides, a stride divides a block "
                    "and a block the window, and the forced blocks are among "
                    "the topk")
        if self.mamba is not None and (
                self.retention is not None or self.sliding_window is not None
                or self.alt_window or self.attn_softcap is not None
                or self.parallel_block or self.index_topk is not None
                or self.post_norms or not self.pre_norm
                or self.mamba.d_ssm % self.mamba.n_head
                or self.mamba.n_head % self.mamba.n_groups):
            raise ValueError(
                "a state-space mixer beside attention is built for the "
                "sequential pre-norm block with dense causal attention: no "
                "window, softcap, indexer, retention or post-norm goes with "
                "it; its heads divide d_ssm and its groups its heads")
        if self.retention is not None and (
                self.sliding_window is not None or self.alt_window
                or self.attn_softcap is not None or self.parallel_block
                or self.index_topk is not None or self.rotary_dim is not None
                or self.head_dim % self.retention.tile):
            raise ValueError(
                "retention replaces softmax attention in the sequential "
                "block: no window, softcap, indexer or partial rotation "
                "goes with it, and its tile divides head_dim")
        if self.parallel_block and self.post_norms:
            raise ValueError(
                "parallel_block (Phi) and post_norms (Gemma-2) describe "
                "incompatible residual structures")
        if not self.pre_norm and (not self.post_norms
                                  or self.parallel_block):
            raise ValueError(
                "pre_norm=False (OLMo-2) requires post_norms=True and a "
                "sequential block — without pre-norms the post-branch "
                "norms are the only normalization")
        if self.qk_norm_width not in ("head", "proj"):
            raise ValueError(
                f"qk_norm_width must be 'head' or 'proj', got "
                f"{self.qk_norm_width!r}")
        if self.rotary_dim is not None and (
                self.rotary_dim % 2 or not
                0 < self.rotary_dim <= self.head_dim):
            raise ValueError(
                f"rotary_dim must be an even value in (0, head_dim="
                f"{self.head_dim}], got {self.rotary_dim}")

    @property
    def head_dim(self):
        if self.head_dim_override is not None:
            return self.head_dim_override
        return self.n_embd // self.n_head

    def default_ffn(self, compute_dtype=None):
        """The config's MLP-override hook, resolved by every runtime
        entry point when no explicit `ffn` is passed (forward_with_cache,
        make_apply*, make_hidden_stacked, LlamaFamilyRows) — so
        dispatch-by-config call sites (beam, speculative, embeddings)
        work for MoE subclasses without knowing about them. None = the
        dense gated MLP; MixtralConfig (models/llama_moe.py) overrides
        this to return its expert hook."""
        return None


PRESETS = {
    # TinyLlama-1.1B shape — the smallest real open-weight GQA model
    "tinyllama-1.1b": LlamaConfig(),
    # LLaMA-2-7B shape (MHA: kv == q heads)
    "llama2-7b": LlamaConfig(block_size=4096, n_layer=32, n_head=32,
                             n_kv_head=32, n_embd=4096, d_ff=11008),
    # LLaMA-3-8B shape (GQA 4:1, big vocab, long rope)
    "llama3-8b": LlamaConfig(block_size=8192, vocab_size=128256, n_layer=32,
                             n_head=32, n_kv_head=8, n_embd=4096, d_ff=14336,
                             rope_theta=500000.0),
    # tiny config for tests / CPU-mesh CI (GQA 2:1, 4 layers)
    "llama-test": LlamaConfig(block_size=64, vocab_size=256, n_layer=4,
                              n_head=4, n_kv_head=2, n_embd=64, d_ff=128),
    # Mistral-7B-v0.1 shape: the LLaMA block with GQA 4:1 and a 4096-token
    # sliding window (the architecture's long-context claim: cache and
    # attention cost are O(window), not O(seq))
    "mistral-7b": LlamaConfig(block_size=32768, vocab_size=32000,
                              n_layer=32, n_head=32, n_kv_head=8,
                              n_embd=4096, d_ff=14336,
                              rope_theta=10000.0, sliding_window=4096),
    # tiny sliding-window config for tests (window far below block_size
    # so CI exercises the wrap)
    "mistral-test": LlamaConfig(block_size=64, vocab_size=256, n_layer=4,
                                n_head=4, n_kv_head=2, n_embd=64, d_ff=128,
                                sliding_window=16),
    # Qwen2-7B shape: the LLaMA block with q/k/v biases, GQA 7:1, long
    # rope base
    "qwen2-7b": LlamaConfig(block_size=32768, vocab_size=152064,
                            n_layer=28, n_head=28, n_kv_head=4,
                            n_embd=3584, d_ff=18944,
                            rope_theta=1_000_000.0, rms_eps=1e-6,
                            attn_bias=True),
    # tiny biased config for tests
    "qwen2-test": LlamaConfig(block_size=64, vocab_size=256, n_layer=4,
                              n_head=4, n_kv_head=2, n_embd=64, d_ff=128,
                              attn_bias=True),
    # Gemma-2B shape: (1+w) RMSNorm, GeGLU, tied + sqrt(C)-scaled
    # embeddings, MQA with head_dim decoupled from n_embd/n_head
    "gemma-2b": LlamaConfig(block_size=8192, vocab_size=256000,
                            n_layer=18, n_head=8, n_kv_head=1,
                            n_embd=2048, d_ff=16384,
                            head_dim_override=256, rms_eps=1e-6,
                            norm_plus_one=True, mlp_act="gelu_tanh",
                            tie_word_embeddings=True, embed_scale=True),
    # Gemma-7B shape (MHA, same block recipe)
    "gemma-7b": LlamaConfig(block_size=8192, vocab_size=256000,
                            n_layer=28, n_head=16, n_kv_head=16,
                            n_embd=3072, d_ff=24576,
                            head_dim_override=256, rms_eps=1e-6,
                            norm_plus_one=True, mlp_act="gelu_tanh",
                            tie_word_embeddings=True, embed_scale=True),
    # tiny Gemma-1 config for tests (MQA + head_dim override exercised)
    "gemma-test": LlamaConfig(block_size=64, vocab_size=256, n_layer=4,
                              n_head=4, n_kv_head=1, n_embd=64, d_ff=128,
                              head_dim_override=32, rms_eps=1e-6,
                              norm_plus_one=True, mlp_act="gelu_tanh",
                              tie_word_embeddings=True, embed_scale=True),
    # Gemma-2-9B shape: Gemma block + post-norms, logit softcaps,
    # query_pre_attn_scalar, alternating 4096-window/global layers
    "gemma2-9b": LlamaConfig(block_size=8192, vocab_size=256000,
                             n_layer=42, n_head=16, n_kv_head=8,
                             n_embd=3584, d_ff=14336,
                             head_dim_override=256, rms_eps=1e-6,
                             norm_plus_one=True, mlp_act="gelu_tanh",
                             tie_word_embeddings=True, embed_scale=True,
                             post_norms=True, query_scale=256.0,
                             attn_softcap=50.0, final_softcap=30.0,
                             sliding_window=4096, alt_window=True),
    # tiny Gemma-2 config for tests: window far below block_size and
    # query_scale != head_dim so every switch actually acts
    "gemma2-test": LlamaConfig(block_size=64, vocab_size=256, n_layer=4,
                               n_head=4, n_kv_head=2, n_embd=64, d_ff=128,
                               head_dim_override=32, rms_eps=1e-6,
                               norm_plus_one=True, mlp_act="gelu_tanh",
                               tie_word_embeddings=True, embed_scale=True,
                               post_norms=True, query_scale=64.0,
                               attn_softcap=50.0, final_softcap=30.0,
                               sliding_window=16, alt_window=True),
    # Phi-2 shape: parallel residual block (attn + MLP both read ln_1's
    # output), biased LayerNorms, partial rotary (32 of 80 head dims),
    # plain gelu MLP, biases on every projection incl. lm_head
    "phi-2": LlamaConfig(block_size=2048, vocab_size=51200, n_layer=32,
                         n_head=32, n_kv_head=32, n_embd=2560,
                         d_ff=10240, rms_eps=1e-5, layer_norm=True,
                         parallel_block=True, rotary_dim=32,
                         attn_bias=True, dense_bias=True,
                         mlp_gated=False, mlp_act="gelu_tanh"),
    # tiny Phi config for tests (partial_rotary_factor 0.5 on 16-dim
    # heads so the rotate/pass-through split actually acts)
    "phi-test": LlamaConfig(block_size=64, vocab_size=256, n_layer=4,
                            n_head=4, n_kv_head=4, n_embd=64, d_ff=128,
                            rms_eps=1e-5, layer_norm=True,
                            parallel_block=True, rotary_dim=8,
                            attn_bias=True, dense_bias=True,
                            mlp_gated=False, mlp_act="gelu_tanh"),
    # Qwen3-8B shape: the LLaMA block with per-head q/k RMSNorm
    # (qk_norm — replaces Qwen2's projection biases), GQA 4:1, decoupled
    # head_dim, long rope base
    "qwen3-8b": LlamaConfig(block_size=40960, vocab_size=151936,
                            n_layer=36, n_head=32, n_kv_head=8,
                            n_embd=4096, d_ff=12288,
                            head_dim_override=128,
                            rope_theta=1_000_000.0, rms_eps=1e-6,
                            qk_norm=True),
    # tiny qk-norm config for tests
    "qwen3-test": LlamaConfig(block_size=64, vocab_size=256, n_layer=4,
                              n_head=4, n_kv_head=2, n_embd=64, d_ff=128,
                              head_dim_override=32, rms_eps=1e-6,
                              qk_norm=True),
    # OLMo-2-7B shape: POST-norm-only block (attention/MLP read the raw
    # residual stream; each branch output norms before its residual
    # add) + full-projection-width q/k norms
    "olmo2-7b": LlamaConfig(block_size=4096, vocab_size=100352,
                            n_layer=32, n_head=32, n_kv_head=32,
                            n_embd=4096, d_ff=11008,
                            rope_theta=500000.0, rms_eps=1e-6,
                            qk_norm=True, qk_norm_width="proj",
                            pre_norm=False, post_norms=True),
    # tiny OLMo-2 config for tests (GQA so the KV-width k_norm acts)
    "olmo2-test": LlamaConfig(block_size=64, vocab_size=256, n_layer=4,
                              n_head=4, n_kv_head=2, n_embd=64, d_ff=128,
                              rms_eps=1e-5, qk_norm=True,
                              qk_norm_width="proj", pre_norm=False,
                              post_norms=True),
    # Brumby-14B-Base (manifestai/Brumby-14B-Base config.json, `model_type`
    # brumby): Qwen3-14B's block — GQA 5:1 with heads of 128, per-head q/k
    # RMSNorm, SwiGLU, untied head — with every layer's attention replaced
    # by degree-2 power retention (models/retention.py). What config.json
    # has no key for is `assumed` in chipbench/configs/
    # brumby-14b-pp8-1chip.json. Never instantiated whole.
    "brumby-14b": LlamaConfig(block_size=32768, vocab_size=151936,
                              n_layer=40, n_head=40, n_kv_head=8,
                              n_embd=5120, d_ff=17408,
                              head_dim_override=128,
                              rope_theta=1_000_000.0, rms_eps=1e-6,
                              qk_norm=True, retention=RetentionConfig()),
    # tiny Brumby for the CPU tests: GQA 2:1 with a decoupled head width,
    # heads of 32 in tiles of 8 (a state 640 wide: five 128-lane blocks for
    # the interpreted step kernel), a chunk of 8 that a 16-token prefill
    # chunk holds twice
    "brumby-test": LlamaConfig(block_size=128, vocab_size=256, n_layer=3,
                               n_head=4, n_kv_head=2, n_embd=64, d_ff=128,
                               head_dim_override=32, rms_eps=1e-6,
                               qk_norm=True,
                               retention=RetentionConfig(tile=8, chunk=8)),
}
# the benchmark's cut (chipbench/configs/brumby-14b-pp8-1chip.json): one of
# eight pipeline stages of five whole layers, layers 0-4, with `wte` and the
# head placed on it
PRESETS["brumby-14b-pp8-1chip"] = dataclasses.replace(
    PRESETS["brumby-14b"], n_layer=5)
# Falcon-H1-34B-Instruct (tiiuae/Falcon-H1-34B-Instruct config.json,
# `model_type` falcon_h1): 72 layers of ONE kind — a Mamba-2 mixer (32 heads
# of 128, state 256, 2 groups, 4 taps with bias, gated grouped RMSNorm) and
# GQA 5:1 softmax attention (heads of 128, theta 1e11) read the same normed
# input, their scaled outputs are added before one residual; SwiGLU 21 504,
# untied head, the muP multipliers. The equations are `assumed` in
# chipbench/configs/falcon-h1-34b-pp8-1chip.json. Never instantiated whole.
PRESETS["falcon-h1-34b"] = LlamaConfig(
    block_size=262144, vocab_size=261120, n_layer=72, n_head=20, n_kv_head=4,
    n_embd=5120, d_ff=21504, head_dim_override=128, rope_theta=1e11,
    rms_eps=1e-5,
    mamba=Mamba2Config(
        d_ssm=4096, n_head=32, d_state=256, n_groups=2, conv=4, chunk=128,
        ssm_in=0.25, ssm_out=0.08838834764831845,
        ssm_multipliers=(0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                         0.3535533905932738)),
    mup=MupConfig(embedding=5.656854249492381, lm_head=0.0078125,
                  attention_in=1.0, attention_out=0.0375,
                  key=0.011048543456039804,
                  mlp=(0.1767766952966369, 0.011160714285714284)))
# the benchmark's cut (chipbench/configs/falcon-h1-34b-pp8-1chip.json): one
# of eight pipeline stages of nine whole layers, layers 0-8, and this chip's
# eighth of the embedding's and the head's rows, rows 0-32 639
PRESETS["falcon-h1-34b-pp8-1chip"] = dataclasses.replace(
    PRESETS["falcon-h1-34b"], n_layer=9, vocab_size=32640)
# tiny Falcon-H1 for the CPU tests: 2 groups of 2 state-space heads of 16
# (state 16), GQA 2:1 with heads of 16, a chunk of 8 that a 16-token prefill
# chunk holds twice, EVERY multiplier different from 1 and from each other;
# theta 1e4, not 1e11, at which 96 positions turn one pair of 8 and a
# rotation left out could not be seen
PRESETS["falcon-h1-test"] = LlamaConfig(
    block_size=128, vocab_size=256, n_layer=3, n_head=4, n_kv_head=2,
    n_embd=64, d_ff=128, head_dim_override=16, rope_theta=1e4, rms_eps=1e-5,
    mamba=Mamba2Config(
        d_ssm=64, n_head=4, d_state=16, n_groups=2, conv=4, chunk=8,
        ssm_in=0.6, ssm_out=0.45,
        ssm_multipliers=(0.8, 1.3, 0.7, 1.6, 0.55)),
    mup=MupConfig(embedding=2.5, lm_head=0.35, attention_in=1.2,
                  attention_out=0.65, key=0.4, mlp=(1.7, 0.3)))


def layer_windows(cfg: LlamaConfig):
    """Per-layer sliding-window array for alternating-attention configs:
    (L,) int32, cfg.sliding_window on EVEN layers, block_size (a vacuous
    band bound — positions never reach it) on ODD/global layers. None for
    uniform-attention configs, which keep the static codec window."""
    if not cfg.alt_window:
        return None
    if cfg.sliding_window is None:
        # silently returning None would make every layer attend globally —
        # a misconfigured Gemma-2-style preset must fail loudly, not degrade
        raise ValueError(
            "alt_window=True requires sliding_window to be set: alternating "
            "window/global layers need a window width for the even layers")
    return jnp.asarray(
        [cfg.sliding_window if i % 2 == 0 else cfg.block_size
         for i in range(cfg.n_layer)], jnp.int32)


@dataclasses.dataclass(frozen=True)
class KvKind:
    """One KIND of K/V attention layer of a model whose layers differ
    (`MixtralConfig.kv_full` / `kv_window` with `layer_types`, models/
    llama_moe.py): `window` = W makes a query at t read t - W < u <= t
    only (`band_keep`), and its cache leaf hold the window's blocks;
    `rope` off leaves q and k unrotated (position then reaches the layer
    through the mask alone); `rotation` is the kind's OWN table where the
    kinds rotate by different ones (None: the config's `rope_theta` /
    `rope_scaling`). Everything else of the block is the config's."""
    window: Optional[int] = None
    rope: bool = True
    rotation: Optional[Rotation] = None


# a K/V kind's cache leaves and block tables, by name (models/mla.py
# `KIND_LEAVES` is the latent family's)
KV_KIND_LEAVES = {"full": ("k", "v", "tables"),
                  "window": ("k_w", "v_w", "tables_w")}


def kv_kinds(cfg):
    """{kind: its KvKind} of a config whose K/V layers are of two kinds,
    "full" first — of the ONE kind "full" where layers keep a state
    (models/state_kind.py) in other layers or beside K and V —; None for
    every other config."""
    from dnn_tpu.models import state_kind

    rule = state_kind.config_rule(cfg)
    if rule is not None:
        if rule.beside is None and getattr(cfg, "layer_types", None) is None:
            return None  # the rule replaces every layer's attention
        return {"full": getattr(cfg, "kv_full", None) or KvKind()}
    if getattr(cfg, "kv_window", None) is None:
        return None
    return {"full": cfg.kv_full or KvKind(), "window": cfg.kv_window}


# MiniCPM-SALA (openbmb/MiniCPM-SALA config.json, `model_type` minicpm_sala):
# a DENSE model of 32 layers of two kinds — `minicpm4` ("full": GQA 16:1
# softmax attention, q/k normed a head and NOT rotated, an element-wise output
# gate, reading only the 64-position blocks a score over mean-pooled keys
# selects beside a local window) and `lightning-attn` ("linear": 32 heads of
# 128 with a fixed decay a head, q and k normed and rotated) —, SwiGLU 16384,
# untied head, MiniCPM's three muP scalars: `scale_emb` 12 on the embedding,
# `scale_depth` / sqrt(32) on BOTH residual branches, hidden / `dim_model_base`
# = 16 dividing the head's input. The equations are `assumed` in
# chipbench/configs/minicpm-sala-pp8-1chip.json. Never instantiated whole.
_SALA_TYPES = tuple(
    "full" if i in (0, 9, 16, 17, 22, 29, 30, 31) else "linear"
    for i in range(32))
_SALA_R = 1.4 / 32 ** 0.5
PRESETS["minicpm-sala"] = LlamaConfig(
    block_size=524288, vocab_size=73448, n_layer=32, n_head=32, n_kv_head=2,
    n_embd=4096, d_ff=16384, head_dim_override=128, rope_theta=10000.0,
    rms_eps=1e-6, qk_norm=True,
    layer_types=_SALA_TYPES, kv_full=KvKind(window=None, rope=False),
    attn_gate=True, lightning=LightningConfig(), block_select=BlockSelectConfig(),
    mup=MupConfig(embedding=12.0, lm_head=1 / 16, attention_out=_SALA_R,
                  mlp=(1.0, _SALA_R)))
# the benchmark's cut (chipbench/configs/minicpm-sala-pp8-1chip.json): one of
# eight pipeline stages of four whole layers, layers 0-3 (one period: a
# `minicpm4` layer and three `lightning-attn`), with `wte` and the head on it;
# r stays scale_depth / sqrt(32), the PUBLISHED depth's
PRESETS["minicpm-sala-pp8-1chip"] = dataclasses.replace(
    PRESETS["minicpm-sala"], n_layer=4, layer_types=_SALA_TYPES[:4])
# tiny MiniCPM-SALA for the CPU tests: two KV heads of two query
# heads each (so a selection a KV group differs), blocks of 8 with pooled keys
# of 4 every 2 positions, a window of 2 blocks and the 2 blocks of largest
# score (block 0 forced): a 200-position context holds 25 blocks and drops 21;
# a chunk of 8 that a 16-token prefill chunk holds twice; the three scalars
# different from 1 and from each other; q/k norm gains of 1.4 x (1 + 0.1 z),
# so that at 16-wide heads a selection is far from a coin toss (the served
# preset keeps gains of exactly 1: at 1.4 the float32 reference agrees with
# itself in bfloat16 on 79 % of tokens, at 1.0 on 91 % — PERF.md, PR 58)
PRESETS["minicpm-sala-test"] = LlamaConfig(
    block_size=256, vocab_size=256, n_layer=4, n_head=4, n_kv_head=2,
    n_embd=64, d_ff=128, head_dim_override=16, rope_theta=10000.0,
    rms_eps=1e-6, qk_norm=True, qk_norm_init=1.4,
    layer_types=("full", "linear", "linear", "linear"),
    kv_full=KvKind(window=None, rope=False), attn_gate=True,
    lightning=LightningConfig(n_head=4, head_dim=16, chunk=8),
    block_select=BlockSelectConfig(block=8, topk=2, window=16, init_blocks=1,
                                   kernel=4, stride=2),
    mup=MupConfig(embedding=2.5, lm_head=0.35, attention_out=0.6,
                  mlp=(1.0, 0.6)))


# --------------------------------------------------------------------------
# init
# --------------------------------------------------------------------------

def _kernel(key, shape, dtype, std=0.02):
    return {"kernel": (jax.random.normal(key, shape) * std).astype(dtype)}


def init_gated_mlp(keys, cfg: LlamaConfig, d_ff: int, dtype=jnp.float32):
    """A dense gated MLP of width `d_ff` from three keys (a block's own,
    or a dense-prefix block's of another width, models/llama_moe.py)."""
    c = cfg.n_embd
    return {
        "gate": _kernel(keys[0], (c, d_ff), dtype),
        "up": _kernel(keys[1], (c, d_ff), dtype),
        "down": _kernel(keys[2], (d_ff, c), dtype,
                        std=0.02 / (2 * cfg.n_layer) ** 0.5),
    }


def init_block(key, cfg: LlamaConfig, dtype=jnp.float32, *,
               include_mlp: bool = True, kind=None):
    """`include_mlp=False` builds the attention/norm half only — MoE
    families (llama_moe) add their expert stacks instead of allocating
    dense MLP weights just to delete them (22 GB of transient garbage at
    mixtral-8x7b scale). `kind`: the layer's kind where the config's
    layers are of several (models/mla.py)."""
    c, d = cfg.n_embd, cfg.head_dim
    ks = jax.random.split(key, 7)
    if cfg.one_mixer:
        # one norm and the kind's ONE mixer: attention ("full"), the
        # state rule's params, or — added by models/llama_moe.py — experts
        from dnn_tpu.models import state_kind

        blk = {"ln_1": {"scale": jnp.ones((c,), dtype)}}
        if kind == "full":
            blk["attn"] = {
                "q": _kernel(ks[0], (c, cfg.n_head * d), dtype),
                "k": _kernel(ks[1], (c, cfg.n_kv_head * d), dtype),
                "v": _kernel(ks[2], (c, cfg.n_kv_head * d), dtype),
                "o": _kernel(ks[3], (cfg.n_head * d, c), dtype,
                             std=0.02 / (2 * cfg.n_layer) ** 0.5)}
        rule = state_kind.layer_rule(cfg, kind)
        if rule is not None:
            rule.init(blk, key, cfg, dtype)
        return blk

    def _qkv(k, shape):
        p = _kernel(k, shape, dtype)
        if cfg.attn_bias:
            p["bias"] = jnp.zeros((shape[-1],), dtype)
        return p

    # Gemma norms init at ZERO ((1+w) scaling makes 0 the identity);
    # plain RMSNorm inits at one. LayerNorm (Phi) adds a bias leaf.
    norm_init = jnp.zeros if cfg.norm_plus_one else jnp.ones

    def _norm_p(shape):
        p = {"scale": norm_init(shape, dtype)}
        if cfg.layer_norm:
            p["bias"] = jnp.zeros(shape, dtype)
        return p

    def _dense(k, shape, std=0.02):
        p = _kernel(k, shape, dtype, std=std)
        if cfg.dense_bias:  # Phi biases every projection
            p["bias"] = jnp.zeros((shape[-1],), dtype)
        return p

    blk = {
        "ln_1": _norm_p((c,)),
        "attn": {
            "q": _qkv(ks[0], (c, cfg.n_head * d)),
            "k": _qkv(ks[1], (c, cfg.n_kv_head * d)),
            "v": _qkv(ks[2], (c, cfg.n_kv_head * d)),
            "o": _dense(ks[3], (cfg.n_head * d, c),
                        std=0.02 / (2 * cfg.n_layer) ** 0.5),
        },
    }
    if cfg.qk_norm:
        # "head" (Qwen3): per-head over head_dim; "proj" (OLMo-2): the
        # full projected width, jointly across heads
        qn = d if cfg.qk_norm_width == "head" else cfg.n_head * d
        kn = d if cfg.qk_norm_width == "head" else cfg.n_kv_head * d
        blk["attn"]["q_norm"] = {"scale": jnp.ones((qn,), dtype)}
        blk["attn"]["k_norm"] = {"scale": jnp.ones((kn,), dtype)}
        if cfg.qk_norm_init != 1.0:
            kq, kk = jax.random.split(jax.random.fold_in(key, 11))
            for name, kg, n in (("q_norm", kq, qn), ("k_norm", kk, kn)):
                blk["attn"][name]["scale"] = (cfg.qk_norm_init * (
                    1.0 + 0.1 * jax.random.normal(kg, (n,)))).astype(dtype)
    if cfg.index_topk is not None:
        from dnn_tpu.models import dsa

        blk["attn"]["indexer"] = dsa.init_indexer(
            jax.random.fold_in(key, 13), cfg, dtype)
    if getattr(cfg, "mla", None) is not None:
        from dnn_tpu.models import mla

        blk["attn"] = mla.init_attn(jax.random.fold_in(key, 17), cfg, dtype,
                                    mla.kinds(cfg)[kind or "full"])
    from dnn_tpu.models import state_kind

    rule = state_kind.layer_rule(cfg, kind)
    if rule is not None:
        rule.init(blk, key, cfg, dtype)
    elif cfg.attn_gate:
        # the softmax layer's sigmoid OUTPUT gate, element-wise
        blk["attn"]["gate"] = _dense(jax.random.fold_in(key, 23),
                                     (c, cfg.n_head * d))
    if not cfg.parallel_block:  # Phi's parallel block has ONE norm
        blk["ln_2"] = _norm_p((c,))
    if not cfg.pre_norm:  # OLMo-2: only the post-branch norms exist
        del blk["ln_1"]
        del blk["ln_2"]
    if include_mlp:
        if cfg.mlp_gated:
            blk["mlp"] = init_gated_mlp(ks[4:7], cfg, cfg.d_ff, dtype)
        else:  # Phi plain MLP: fc1 -> act -> fc2
            blk["mlp"] = {
                "up": _dense(ks[5], (c, cfg.d_ff)),
                "down": _dense(ks[6], (cfg.d_ff, c),
                               std=0.02 / (2 * cfg.n_layer) ** 0.5),
            }
    if cfg.post_norms:
        blk["post_ln_1"] = _norm_p((c,))
        blk["post_ln_2"] = _norm_p((c,))
    if cfg.mup is not None:
        # muP pairs each multiplier with an initial scale: every kernel
        # whose product a multiplier scales is drawn with it divided out,
        # so that at initialisation each product has the scale the
        # family's other presets give it and a multiplier left out is a
        # visible error, not one that random weights absorb
        for name, by in (("q", cfg.mup.attention_in),
                         ("k", cfg.mup.attention_in * cfg.mup.key),
                         ("v", cfg.mup.attention_in),
                         ("o", cfg.mup.attention_out)):
            blk["attn"][name]["kernel"] = _unscaled(
                blk["attn"][name]["kernel"], by)
        if include_mlp:
            for name, by in zip(("gate", "down"), cfg.mup.mlp):
                blk["mlp"][name]["kernel"] = _unscaled(
                    blk["mlp"][name]["kernel"], by)
    return blk


def _unscaled(kernel, by: float):
    """`kernel` with the multiplier `by` divided out, in its own dtype."""
    return (kernel / by).astype(kernel.dtype) if by != 1.0 else kernel


def init_parts(rng, cfg: LlamaConfig = PRESETS["llama-test"],
               dtype=jnp.float32, *, include_mlp: bool = True):
    """`init`, a top-level entry at a time: {name: a function that draws
    that entry} — "wte", "ln_f", "lm_head" and each "h_<i>" from its own
    key, so that a process which holds the tree in another dtype can draw,
    cast and free one layer at a time (`registry.ParamParts`,
    `node._stack_and_release`) and hold the very values `init` gives."""
    keys = jax.random.split(rng, cfg.n_layer + 3)
    c = cfg.n_embd
    norm_init = jnp.zeros if cfg.norm_plus_one else jnp.ones
    types = getattr(cfg, "layer_types", None)

    def ln_f():
        p = {"scale": norm_init((c,), dtype)}
        if cfg.layer_norm:
            p["bias"] = jnp.zeros((c,), dtype)
        return p

    def lm_head():
        p = _kernel(keys[1], (c, cfg.vocab_size), dtype,
                    std=0.02 / (cfg.mup.lm_head if cfg.mup else 1.0))
        if cfg.dense_bias:  # Phi: lm_head carries a bias too
            p["bias"] = jnp.zeros((cfg.vocab_size,), dtype)
        return p

    parts = {
        "wte": lambda: {"embedding": (
            jax.random.normal(keys[0], (cfg.vocab_size, c))
            * (0.02 / (cfg.mup.embedding if cfg.mup else 1.0))
        ).astype(dtype)},
        "ln_f": ln_f,
    }
    if not cfg.tie_word_embeddings:
        # tied configs carry NO lm_head leaf — head() projects through
        # wte.embedding.T (one table in HBM, shared gradient)
        parts["lm_head"] = lm_head
    for i in range(cfg.n_layer):
        parts[f"h_{i}"] = functools.partial(
            init_block, keys[2 + i], cfg, dtype, include_mlp=include_mlp,
            kind=None if types is None else types[i])
    return parts


def init(rng, cfg: LlamaConfig = PRESETS["llama-test"], dtype=jnp.float32,
         *, include_mlp: bool = True):
    return {name: make() for name, make in init_parts(
        rng, cfg, dtype, include_mlp=include_mlp).items()}


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------

ROPE_SCALINGS = ("linear", "ntk", "yarn")


def rotation_of(cfg: LlamaConfig, kind: Optional[KvKind] = None) -> Rotation:
    """The table a layer of `kind` rotates by: the kind's own, else the
    config's ONE (`rope_theta`, `rope_scaling`, `rope_scale`, `rope_yarn`)."""
    if kind is not None and kind.rotation is not None:
        return kind.rotation
    yarn = cfg.rope_yarn or Rotation()
    return dataclasses.replace(yarn, theta=cfg.rope_theta,
                               scaling=cfg.rope_scaling, scale=cfg.rope_scale)


def yarn_ramp(rot: Rotation, d: int):
    """(low, high) of YaRN's ramp over the d / 2 pairs (arXiv:2309.00071 as
    transformers' `_compute_yarn_parameters` states it): pair i turns
    `original_len` positions c^-1(i) times, c(n) = d ln(original_len / (2 pi
    n)) / (2 ln theta); pairs below c(beta_fast) keep their frequency, pairs
    above c(beta_slow) take theta's divided by `scale`, those between a
    mixture that is linear in i."""
    def pair_of(rotations):
        return d * math.log(rot.original_len / (rotations * 2 * math.pi)) / (
            2 * math.log(rot.theta))

    low, high = pair_of(rot.beta_fast), pair_of(rot.beta_slow)
    if rot.truncate:
        low, high = math.floor(low), math.ceil(high)
    return max(low, 0), min(high, d - 1)


def _yarn_tables(rot: Rotation, positions, d: int):
    if rot.original_len is None:
        raise ValueError("rope_scaling='yarn' needs the positions the "
                         "rotation was trained at (Rotation.original_len)")
    low, high = yarn_ramp(rot, d)
    if low == high:
        high += 0.001  # a ramp of no width: a step
    freq = 1.0 / rot.theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ramp = jnp.clip((jnp.arange(d // 2, dtype=jnp.float32) - low)
                    / (high - low), 0, 1)
    cos, sin = rope_cos_sin(
        positions, d, inv_freq=freq * (1 - ramp) + freq / rot.scale * ramp)
    return cos * rot.cos_sin_factor, sin * rot.cos_sin_factor


def _rope_tables(cfg: LlamaConfig, positions, kind: Optional[KvKind] = None):
    """cos/sin at `positions` with the long-context scaling of the table a
    layer of `kind` rotates by (`rotation_of`: the config's ONE where
    kinds name none) applied — the ONE place scaling happens, shared by
    every attention path (dense, cached decode, batcher rows, seq-parallel
    ring):
      "linear" — positions divided by the scale before the tables;
      "ntk" — theta multiplied by scale^(d/(d-2));
      "yarn" — frequencies by parts (`yarn_ramp`) and cos and sin times
        the attention factor, so that q . k carries its square."""
    rot = rotation_of(cfg, kind)
    theta = rot.theta
    d = cfg.rotary_dim or cfg.head_dim  # partial rotary: narrow tables
    if rot.scaling is None:
        if rot.scale != 1.0:
            # the likely long-context typo: factor set, type forgotten —
            # serving an unscaled model here would silently collapse
            # quality past the trained range
            raise ValueError(
                f"rope_scale={rot.scale} has no effect without "
                "rope_scaling='linear', 'ntk' or 'yarn'")
        return rope_cos_sin(positions, d, theta=theta)
    if rot.scaling not in ROPE_SCALINGS:
        raise ValueError(
            f"unknown rope_scaling {rot.scaling!r} "
            "(expected 'linear', 'ntk' or 'yarn')")
    if rot.scale == 1.0:
        return rope_cos_sin(positions, d, theta=theta)
    if rot.scale < 1.0:
        raise ValueError(f"rope_scale must be >= 1, got {rot.scale}")
    if rot.scaling == "yarn":
        return _yarn_tables(rot, positions, d)
    if rot.scaling == "linear":
        positions = positions.astype(jnp.float32) / rot.scale
    else:  # "ntk"
        theta = theta * rot.scale ** (d / (d - 2))
    return rope_cos_sin(positions, d, theta=theta)


def _norm(p, x, cfg: LlamaConfig):
    """The family's norm: RMSNorm with cfg.rms_eps ((1+w) scaling for
    Gemma, norm_plus_one) — or biased LayerNorm for Phi-class configs
    (layer_norm). EVERY norm site in this module goes through here."""
    if cfg.layer_norm:
        from dnn_tpu.ops.nn import layer_norm

        return layer_norm(p, x, eps=cfg.rms_eps)
    return rms_norm(p, x, eps=cfg.rms_eps, plus_one=cfg.norm_plus_one)


def relu2(x):
    return jnp.square(jax.nn.relu(x))


def _mlp_act(cfg: LlamaConfig):
    if cfg.mlp_act == "silu":
        return silu
    if cfg.mlp_act == "gelu_tanh":  # Gemma GeGLU (gelu_pytorch_tanh)
        from dnn_tpu.ops.nn import gelu
        return gelu
    if cfg.mlp_act == "relu2":  # Nemotron-H: relu(x)^2, ungated
        return relu2
    raise ValueError(f"unknown mlp_act {cfg.mlp_act!r}")


def _q_rescale(q, cfg: LlamaConfig):
    """Fold Gemma-2's query_pre_attn_scalar into q: every attention path
    divides scores by sqrt(head_dim), so scaling q by
    sqrt(head_dim/query_scale) makes the effective divisor
    sqrt(query_scale) with zero per-path plumbing."""
    if cfg.query_scale is not None:
        q = q * jnp.asarray((cfg.head_dim / cfg.query_scale) ** 0.5, q.dtype)
    return q


def _rope_apply(x, cos, sin, cfg: LlamaConfig):
    """apply_rope with the config's partial-rotary slice (Phi): only the
    first rotary_dim dims of each head rotate, the rest pass through.
    EVERY q/k rotation site in this module goes through here — the
    partial slice must never diverge between the dense forward, the
    cached decode, batcher rows, verify rows, and the seq-parallel
    paths."""
    if cfg.rotary_dim is None:
        return apply_rope(x, cos, sin)
    rot = apply_rope(x[..., :cfg.rotary_dim], cos, sin)
    return jnp.concatenate([rot, x[..., cfg.rotary_dim:]], axis=-1)


def _pre_normed(bp, x, cfg: LlamaConfig):
    """The block input the branches read: ln_1(x) for pre-norm blocks
    (LLaMA and every descendant), the RAW residual stream for OLMo-2's
    post-norm-only block (pre_norm=False). ONE definition for every
    block body."""
    if not cfg.pre_norm:
        return x
    return _norm(bp["ln_1"], x, cfg)


def _qk_normed(bp, q, k, cfg: LlamaConfig):
    """q/k RMSNorm BEFORE RoPE — the ONE definition every q/k projection
    site shares (_qkv_rope, the batcher's _block_rows, verify_rows), or
    the paths' parity contracts would diverge on qk_norm configs.
    Inputs arrive head-split ((B, H, T, D) / (B, KV, T, D)); width
    "head" (Qwen3) norms each D-vector, width "proj" (OLMo-2) norms the
    merged (H*D,)/(KV*D,) vector jointly across heads (merge -> norm ->
    split — XLA folds the transposes). Identity when the switch is
    off."""
    if not cfg.qk_norm:
        return q, k
    if cfg.qk_norm_width == "proj":
        hq, hk = q.shape[1], k.shape[1]
        q2 = rms_norm(bp["attn"]["q_norm"], merge_heads(q), eps=cfg.rms_eps)
        k2 = rms_norm(bp["attn"]["k_norm"], merge_heads(k), eps=cfg.rms_eps)
        return split_heads(q2, hq), split_heads(k2, hk)
    return (rms_norm(bp["attn"]["q_norm"], q, eps=cfg.rms_eps),
            rms_norm(bp["attn"]["k_norm"], k, eps=cfg.rms_eps))


def _mup_scaled(x, cfg: LlamaConfig, name: str, at=None):
    """x times the muP multiplier `name` (its entry `at` where it is a
    pair) of `cfg.mup` (`MupConfig`); x itself — nothing traced — where the
    config has none or it is 1."""
    if cfg.mup is None:
        return x
    m = getattr(cfg.mup, name)
    m = m if at is None else m[at]
    return x if m == 1.0 else x * jnp.asarray(m, x.dtype)


def _qkv_rope(bp, h, positions, *, cfg: LlamaConfig, compute_dtype,
              kind: Optional[KvKind] = None):
    """Project h (B, T, C) and rotate q/k at absolute `positions` (T,).
    Returns q (B, H, T, D), k/v (B, KV, T, D) — KV heads stay narrow.
    `kind` is the layer's (`KvKind`) where layers are of kinds: its `rope`
    off leaves q and k as they are normed, its `rotation` is the table."""
    h = _mup_scaled(h, cfg, "attention_in")
    q = split_heads(linear(bp["attn"]["q"], h, compute_dtype=compute_dtype),
                    cfg.n_head)
    k = split_heads(_mup_scaled(
        linear(bp["attn"]["k"], h, compute_dtype=compute_dtype), cfg, "key"),
        cfg.n_kv_head)
    v = split_heads(linear(bp["attn"]["v"], h, compute_dtype=compute_dtype),
                    cfg.n_kv_head)
    q, k = _qk_normed(bp, q, k, cfg)
    if kind is not None and not kind.rope:
        return _q_rescale(q, cfg), k, v
    cos, sin = _rope_tables(cfg, positions, kind)
    return (_q_rescale(_rope_apply(q, cos, sin, cfg), cfg),
            _rope_apply(k, cos, sin, cfg), v)


def _mlp_out(bp, h, *, cfg: LlamaConfig, compute_dtype, ffn=None):
    """The MLP branch over an already-normed h: gated SwiGLU/GeGLU, the
    plain 2-layer Phi MLP (mlp_gated=False), or the `ffn` override
    (Mixtral MoE hook)."""
    if ffn is not None:
        return ffn(bp, h)
    act = _mlp_act(cfg)
    if not cfg.mlp_gated:
        return linear(bp["mlp"]["down"],
                      act(linear(bp["mlp"]["up"], h,
                                 compute_dtype=compute_dtype)),
                      compute_dtype=compute_dtype)
    # muP (`MupConfig.mlp`): the gate product scaled before its activation,
    # the MLP's output after the down-projection
    g = _mup_scaled(linear(bp["mlp"]["gate"], h, compute_dtype=compute_dtype),
                    cfg, "mlp", 0)
    return _mup_scaled(
        linear(bp["mlp"]["down"],
               act(g) * linear(bp["mlp"]["up"], h,
                               compute_dtype=compute_dtype),
               compute_dtype=compute_dtype), cfg, "mlp", 1)


def _mlp_residual(bp, x, *, cfg: LlamaConfig, compute_dtype, ffn=None):
    """Post-attention half of the SEQUENTIAL block: norm + MLP
    (gated or plain), Gemma-2 post-MLP norm, residual. ONE definition
    shared by the stateless forward, the cached decode, and the per-slot
    batcher path — their parity contracts depend on these never
    diverging. `ffn(bp, h)` overrides the MLP (the Mixtral MoE hook —
    models/llama_moe.py; same convention as the GPT family's ffn)."""
    h = x if not cfg.pre_norm else _norm(bp["ln_2"], x, cfg)
    m = _mlp_out(bp, h, cfg=cfg, compute_dtype=compute_dtype, ffn=ffn)
    if cfg.post_norms:
        m = _norm(bp["post_ln_2"], m, cfg)
    return x + m.astype(x.dtype)


def _attn_out_residual(bp, x, o, cfg: LlamaConfig):
    """Attention branch output -> residual add, through Gemma-2's
    post-attention norm when configured. `o` is the o-projected branch
    output in x's dtype."""
    if cfg.post_norms:
        o = _norm(bp["post_ln_1"], o, cfg)
    if cfg.mup is not None and cfg.mamba is None:
        # a state-space mixer's block scales its two mixers as it adds them
        # (models/mamba2.py `mixers_sum`)
        o = _mup_scaled(o, cfg, "attention_out")
    return x + o.astype(x.dtype)


def _branches_residual(bp, x, o, h, *, cfg: LlamaConfig, compute_dtype,
                       ffn=None):
    """Compose the attention branch output `o` and the MLP into the
    residual stream — the ONE definition every block body (dense
    forward, cached decode, batcher rows, verify rows, seq-sharded
    decode) shares. Sequential (LLaMA): x + o, then ln_2 + MLP +
    residual. Parallel (Phi, parallel_block): both branches read the
    SAME ln_1 output `h`; y = x + o + mlp(h), no ln_2. A block of ONE
    mixer (`one_mixer`): x + o and nothing after it."""
    if cfg.one_mixer:
        return _attn_out_residual(bp, x, o, cfg)
    if cfg.parallel_block:
        m = _mlp_out(bp, h, cfg=cfg, compute_dtype=compute_dtype, ffn=ffn)
        return x + o.astype(x.dtype) + m.astype(x.dtype)
    x = _attn_out_residual(bp, x, o, cfg)
    return _mlp_residual(bp, x, cfg=cfg, compute_dtype=compute_dtype,
                         ffn=ffn)


def _gqa_scores_attend(q, k, v, mask_fn, softcap=None):
    """Grouped attention: q (B, H, T, D) vs k/v (B, KV, S, D) with
    H = G * KV. Folds the group into the row dim so einsums run at KV
    heads; `mask_fn(scores (B, KV, G, T, S)) -> masked scores`;
    `softcap` bounds scores via cap*tanh(s/cap) BEFORE masking
    (Gemma-2 attn_logit_softcapping)."""
    b, h, t, d = q.shape
    kv = k.shape[1]
    g = h // kv
    qg = q.reshape(b, kv, g, t, d)
    s = jnp.einsum("bkgtd,bksd->bkgts", qg.astype(jnp.float32),
                   k.astype(jnp.float32),
                   preferred_element_type=jnp.float32) / jnp.sqrt(d)
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    p = jax.nn.softmax(mask_fn(s), axis=-1)
    y = jnp.einsum("bkgts,bksd->bkgtd", p, v.astype(jnp.float32))
    return y.reshape(b, h, t, d)


def _gated(bp, h, y, compute_dtype):
    """The attention output y (B, T, H D), heads merged, times sigmoid(h
    W_gate) element-wise where the layer's params hold an output gate
    (scope `attn_gate`, models/kda.py's softmax layers); y itself else."""
    if "gate" not in bp["attn"]:
        return y
    with jax.named_scope("attn_gate"):
        g = linear(bp["attn"]["gate"], h, compute_dtype=compute_dtype)
        return (y.astype(jnp.float32)
                * jax.nn.sigmoid(g.astype(jnp.float32))).astype(y.dtype)


def _dense_attn(bp, h, *, cfg: LlamaConfig, compute_dtype, window=None,
                kind: Optional[KvKind] = None):
    """Default attention: local causal GQA over the whole (B, T, C) h,
    band-limited to cfg.sliding_window when set. `window` overrides the
    config's window for this call (traced allowed) — the per-layer hook
    alternating-attention configs thread through blocks_scan, and a layer
    kind's own (`kind`, a `KvKind`, whose rotation q and k then take)."""
    t = h.shape[1]
    q, k, v = _qkv_rope(bp, h, jnp.arange(t), cfg=cfg,
                        compute_dtype=compute_dtype, kind=kind)
    rows = jnp.arange(t)
    w = window if window is not None else cfg.sliding_window

    def causal(s):
        qr = rows[None, None, None, :, None]
        kr = rows[None, None, None, None, :]
        keep = qr >= kr
        if w is not None:
            keep &= kr > qr - w
        return jnp.where(keep, s, _NEG_BIG)

    y = _gqa_scores_attend(q, k, v, causal, softcap=cfg.attn_softcap)
    y = _gated(bp, h, merge_heads(y.astype(h.dtype)), compute_dtype)
    return linear(bp["attn"]["o"], y, compute_dtype=compute_dtype)


# the kind of a block whose one mixer is its experts (`one_mixer`): it keeps
# no state and no K or V, so no cache kind goes by this name
EXPERTS = "experts"


def experts_block(bp, x, ffn, *, cfg: LlamaConfig):
    """A block of kind `EXPERTS`: x + ffn(norm x), `ffn` the MoE hook."""
    with jax.named_scope("llama.block.mlp"):
        return x + ffn(bp, _pre_normed(bp, x, cfg)).astype(x.dtype)


def block_apply(bp, x, *, cfg: LlamaConfig, compute_dtype=None, attn_fn=None,
                window=None, ffn=None, kind=None):
    """Pre-RMSNorm block: GQA attention + gated MLP, both residual
    (Gemma-2 additionally norms each branch output — post_norms).
    `attn_fn(bp, h)` overrides the attention (the sequence-parallel ring
    plugs in here — same hook pattern as gpt._block_core); `window` is
    the per-layer window override for the default dense attention;
    `ffn(bp, h)` overrides the MLP (Mixtral MoE); `kind` is the layer's
    kind where the config's layers are of several (models/mla.py)."""
    if kind == EXPERTS:
        return experts_block(bp, x, ffn, cfg=cfg)
    fn = attn_fn or (lambda bp2, h: _dense_attn(
        bp2, h, cfg=cfg, compute_dtype=compute_dtype, window=window))
    rule = None
    if attn_fn is None:
        from dnn_tpu.models import state_kind

        rule = state_kind.layer_rule(cfg, kind)
    attends = attn_fn is None and (rule is None or rule.beside is not None)
    if attends and getattr(cfg, "block_select", None) is not None:
        from dnn_tpu.models import block_select

        fn = lambda bp2, h: block_select.dense_attn(  # noqa: E731
            bp2, h, cfg=cfg, compute_dtype=compute_dtype)
    elif attends and (kinds := kv_kinds(cfg)) is not None:
        kk = kinds[kind or "full"]
        fn = lambda bp2, h: _dense_attn(  # noqa: E731
            bp2, h, cfg=cfg, compute_dtype=compute_dtype, window=kk.window,
            kind=kk)
    if attn_fn is None and cfg.index_topk is not None:
        from dnn_tpu.models import dsa

        fn = lambda bp2, h: dsa.dense_attn(  # noqa: E731
            bp2, h, cfg=cfg, compute_dtype=compute_dtype)
    if attn_fn is None and getattr(cfg, "mla", None) is not None:
        from dnn_tpu.models import mla

        fn = lambda bp2, h: mla.dense_attn(  # noqa: E731
            bp2, h, cfg=cfg, compute_dtype=compute_dtype,
            m=mla.kinds(cfg)[kind or "full"])
    if rule is not None:
        # the layer keeps a state: its rule in attention's place, or beside it
        fn = state_kind.dense_mixer(rule, fn, cfg=cfg,
                                    compute_dtype=compute_dtype)
    # trace-time scopes: device profiles (obs/profile.py) name the
    # attention branch vs the residual/MLP compose; zero runtime cost
    with jax.named_scope("llama.block.attn"):
        h = _pre_normed(bp, x, cfg)
        o = fn(bp, h)
    with jax.named_scope("llama.block.mlp"):
        return _branches_residual(bp, x, o, h, cfg=cfg,
                                  compute_dtype=compute_dtype, ffn=ffn)


def _scaled_embed(p, ids, cfg: LlamaConfig):
    """Token lookup + Gemma's sqrt(C) input scaling — the ONE definition
    every path (dense forward, cached decode, batcher rows, seq-parallel,
    pipeline embed hook) must share, or their parity contracts break on
    embed_scale configs."""
    with jax.named_scope("llama.embed"):
        e = embedding(p["wte"], ids)
        if cfg.embed_scale:
            e = e * jnp.asarray(cfg.n_embd ** 0.5, e.dtype)
        return _mup_scaled(e, cfg, "embedding")


def embed(params, idx, *, cfg: LlamaConfig):
    t = idx.shape[-1]
    if t > cfg.block_size:
        raise ValueError(
            f"Cannot forward: sequence length {t} > block_size {cfg.block_size}")
    return _scaled_embed(params, idx, cfg)  # positions live in RoPE


def head(params, x, *, cfg: LlamaConfig, compute_dtype=None, logits_dtype=None):
    with jax.named_scope("llama.head"):
        x = _norm(params["ln_f"], x, cfg)
        if "lm_head" in params:
            lm = params["lm_head"]
        else:
            # tied embeddings (Gemma, LLaMA-3.2-1B class): project through
            # the input table's transpose — XLA folds the transpose into
            # the dot
            lm = {"kernel": params["wte"]["embedding"].T}
        if compute_dtype is None:
            out = linear(lm, x)
        else:
            out = linear(lm, x, compute_dtype=compute_dtype,
                         accum_dtype=jnp.float32)
        out = _mup_scaled(out, cfg, "lm_head")
        if cfg.final_softcap is not None:  # Gemma-2 final_logit_softcapping
            out = cfg.final_softcap * jnp.tanh(out / cfg.final_softcap)
        return out if logits_dtype is None else out.astype(logits_dtype)


def layer_stacks(prepared, cfg):
    """[(stacked blocks, (first layer, stop) or None, kind or None)] in
    layer order: a model's layers are ONE stack, `prepared["blocks"]`
    (None: all of them), unless the config has a dense prefix
    (`first_k_dense`, models/llama_moe.py) — layers of another kind,
    whose params stack apart as `prepared["dense_blocks"]` in front of
    the expert layers'. Each is scanned on its own; a paged pool is
    reached by layer index across both (`paged_kvcache.scan_blocks(
    layers=)`), as is a transient row (`paged_kvcache.scan_rows(layers=)`).

    A config whose layers are of KINDS that interleave (`layer_types`,
    models/mla.py) has a stack a kind of params (`gpt.stack_layers`) and
    its loop is `gpt.layer_runs`: one entry, one scan, a run of
    consecutive layers of one stack; the range is then among the KIND's
    layers — where the kind's cache leaves hold them — and the kind is
    named. (A 46-layer F F S S S F S S S ... model is 24 runs: the loop
    over whole periods as ONE scan is not written; the cut that is served
    is three runs, each a whole stack.)"""
    if getattr(cfg, "layer_types", None) is not None:
        return [(_span(prepared[name], span), layers, kind)
                for name, span, kind, layers in gpt.layer_runs(cfg)]
    ranges = gpt.stack_ranges(cfg)
    if len(ranges) == 1:
        return [(prepared["blocks"], None, None)]
    return [(prepared[name], r, None) for name, r in ranges.items()]


class _Span:
    """Layers [first, stop) of a stack that holds more: `scan_form` lets
    their indices ride a cached loop (the body takes its layer out of the
    whole stack); the whole-sequence forward scans `cut()`."""

    def __init__(self, stack, first, stop):
        self.stack, self.first, self.stop = stack, first, stop

    def cut(self):
        """The span's layers of the stack."""
        return jax.tree.map(lambda x: x[self.first:self.stop], self.stack)


def _span(stack, span):
    n = len(jax.tree.leaves(stack)[0])
    return stack if span == (0, n) else _Span(stack, *span)


def scan_form(stack, ffn):
    """How a stack of blocks rides a cached layer loop -> (xs, bind):
    `xs` goes where the loop's xs had `stack`, and the body opens with
    `bp = bind(xs_l)`. Under the grouped MoE hook (llama_moe.make_ffn:
    it has `expert_forms`) the expert matrices do NOT ride as xs: the
    loop keeps each `(L, E, K, N)` stack whole, `xs` carries the layer
    index in their place, and `bp["moe"]` hands them on as
    `moe.LayerOf(stack, layer)` — so no layer's matrices are ever cut
    out of the stack for the grouped matmul (as `paged_kvcache.
    scan_blocks` carries the pool). Any other stack or hook: `stack`
    itself and the identity. A `_Span` rides as its layers' INDICES
    alone."""
    from dnn_tpu.parallel.moe import EXPERT_MATRICES, LayerOf

    span = None
    if isinstance(stack, _Span):
        span, stack = (stack.first, stack.stop), stack.stack

    moe = stack.get("moe") if hasattr(ffn, "expert_forms") else None
    whole = {k: moe[k] for k in EXPERT_MATRICES if k in (moe or {})}
    rest = stack if not whole else {
        **stack, "moe": {k: v for k, v in moe.items() if k not in whole}}

    def with_experts(bp, layer):
        return {**bp, "moe": {**bp["moe"], **{
            k: LayerOf(w, layer) for k, w in whole.items()}}}

    if span is not None:
        # a span of a stack that holds more: ONLY the layer index rides
        # the loop and the body takes its layer out of the whole stack
        # (cutting the span out first copied its weights every step:
        # `slice bf16[2,8192,6144]` in K-EXAONE's decode program, PERF.md
        # section 6, PR 43)
        def bind_span(layer):
            bp = jax.tree.map(lambda x: lax.dynamic_index_in_dim(
                x, layer, keepdims=False), rest)
            return with_experts(bp, layer) if whole else bp

        return jnp.arange(*span, dtype=jnp.int32), bind_span
    if not whole:
        return stack, lambda bp: bp
    n_layer = next(iter(whole.values())).shape[0]
    return (rest, jnp.arange(n_layer, dtype=jnp.int32)), \
        lambda xs_l: with_experts(*xs_l)


def _scan_all_stacks(prepared, x, *, cfg, **kw):
    """`blocks_scan` over every stack of `layer_stacks`."""
    wins = kw.pop("windows", None)
    for stack, layers, kind in layer_stacks(prepared, cfg):
        w = wins if wins is None or layers is None else wins[
            layers[0]:layers[1]]
        if isinstance(stack, _Span):
            stack = stack.cut()
        x = blocks_scan(stack, x, cfg=cfg, windows=w, kind=kind, **kw)
    return x


def blocks_scan(stacked, x, *, cfg, compute_dtype, remat=False, attn_fn=None,
                windows=None, ffn=None, kind=None):
    """Scan the stacked blocks. `windows` is the per-layer window array
    for alternating-attention configs ((L',) — already sliced to this
    stack's layer range); None scans without the extra input. `ffn`
    overrides every block's MLP (Mixtral MoE)."""
    block = (lambda bp, carry, window=None: block_apply(
        bp, carry, cfg=cfg, compute_dtype=compute_dtype,
        attn_fn=attn_fn, window=window, ffn=ffn, kind=kind))
    if remat:
        block = jax.checkpoint(block)

    if windows is None:
        def body(carry, bp):
            return block(bp, carry), None

        out, _ = jax.lax.scan(body, x, stacked)
    else:
        def body_w(carry, xs):
            bp, w = xs
            return block(bp, carry, w), None

        out, _ = jax.lax.scan(body_w, x, (stacked, windows))
    return out


def make_apply(cfg: LlamaConfig, *, compute_dtype=None, remat=False,
               ffn=None):
    ffn = ffn or cfg.default_ffn(compute_dtype)

    def apply(params, idx):
        x = embed(params, idx, cfg=cfg)
        if compute_dtype is not None:
            x = x.astype(compute_dtype)
        x = _scan_all_stacks(
            gpt.prepare_stacked(params, cfg), x, cfg=cfg,
            compute_dtype=compute_dtype, remat=remat,
            windows=layer_windows(cfg), ffn=ffn)
        return head(params, x.astype(jnp.float32), cfg=cfg,
                    compute_dtype=compute_dtype)

    return apply


def make_hidden_stacked(cfg: LlamaConfig, *, compute_dtype=None):
    """Final-normed hidden states over the prepare_stacked layout —
    make_apply_stacked minus the lm_head projection (== HF
    LlamaModel/GemmaModel.last_hidden_state, every family switch
    included). The embedding endpoint's forward
    (runtime/embeddings.py); kept HERE so it can never drift from the
    logits forward above."""

    ffn = cfg.default_ffn(compute_dtype)

    def hidden(prepared, idx):
        x = embed(prepared, idx, cfg=cfg)
        if compute_dtype is not None:
            x = x.astype(compute_dtype)
        x = _scan_all_stacks(prepared, x, cfg=cfg,
                             compute_dtype=compute_dtype,
                             windows=layer_windows(cfg), ffn=ffn)
        return _norm(prepared["ln_f"], x.astype(jnp.float32), cfg)

    return hidden


def make_apply_stacked(cfg: LlamaConfig, *, compute_dtype=None,
                       logits_dtype=None, remat=False):
    """Forward over the prepare_stacked layout (gpt.prepare_stacked works
    unchanged — it only needs h_i keys and cfg.n_layer)."""

    ffn = cfg.default_ffn(compute_dtype)

    def apply(prepared, idx):
        x = embed(prepared, idx, cfg=cfg)
        if compute_dtype is not None:
            x = x.astype(compute_dtype)
        x = _scan_all_stacks(prepared, x, cfg=cfg,
                             compute_dtype=compute_dtype, remat=remat,
                             windows=layer_windows(cfg), ffn=ffn)
        return head(prepared, x.astype(jnp.float32), cfg=cfg,
                    compute_dtype=compute_dtype, logits_dtype=logits_dtype)

    return apply


# --------------------------------------------------------------------------
# KV-cache decode (kvcache codecs; cache holds KV heads, not H)
# --------------------------------------------------------------------------

def _stats_acc(moe_stats):
    """What a layer loop's carry starts with beside the rows: zeros for the
    hook's stats a layer call (parallel/moe.N_STATS of them), or None."""
    from dnn_tpu.parallel.moe import N_STATS

    return jnp.zeros((N_STATS,), jnp.int32) if moe_stats else None


def _run_block(ffn, acc, run):
    """`run(ffn) -> result` for one block of a layer loop whose carry also
    holds `acc`: None, or the int32 MoE stats summed so far. With an
    `acc`, the block is traced with the hook's counting form
    (`ffn.with_stats`, llama_moe.make_ffn) and this layer call's stats
    are added. Returns (result, acc). The list is filled and read inside
    one trace of the block, so nothing leaves the loop's body but through
    its carry."""
    if acc is None:
        return run(ffn), None
    got = []

    def counting(bp, h):
        out, stats = ffn.with_stats(bp, h)
        got.append(stats)
        return out

    result = run(counting)
    # a block of one mixer that is not its experts calls no hook
    return result, acc + got[0] if got else acc


def _block_with_cache(bp, x, rows, start_pos, *, cfg: LlamaConfig,
                      compute_dtype, codec, window=None, ffn=None):
    """Block over x (B, T, C) at absolute positions [start_pos,
    start_pos+T), writing ROTATED k (and v) into its layer of the narrow
    KV-head cache `rows` (the whole cache bound to the layer:
    paged_kvcache.scan_rows).
    GQA against the cache rides the same codec.attend as the GPT family by
    folding the q group into the row dim and tiling pos_limit. `window`
    overrides the codec's window for this layer (the alternating-attention
    per-layer value — traced allowed)."""
    b, t, c = x.shape
    kv, g = cfg.n_kv_head, cfg.n_head // cfg.n_kv_head
    with jax.named_scope("llama.block.cached_attn"):
        h = _pre_normed(bp, x, cfg)
        q, k, v = _qkv_rope(bp, h, start_pos + jnp.arange(t), cfg=cfg,
                            compute_dtype=compute_dtype)
        rows = codec.write(rows, k, v, start_pos)
        layer_cache = rows.read()
        qg = q.reshape(b, kv, g * t, cfg.head_dim)
        if t == 1:
            # decode step: the folded group rows all share the slot's
            # limit — exactly attend_rows' contract, which streams through
            # the Pallas decode kernel when the codec carries use_kernel
            yg = codec.attend_rows(
                qg, layer_cache,
                jnp.broadcast_to(jnp.asarray(start_pos, jnp.int32), (b,)),
                window=window)
        else:
            pos_limit = start_pos + jnp.arange(t)
            yg = codec.attend(qg, layer_cache, jnp.tile(pos_limit, g),
                              window=window)
        y = yg.reshape(b, cfg.n_head, t, cfg.head_dim)
        o = linear(bp["attn"]["o"], merge_heads(y.astype(x.dtype)),
                   compute_dtype=compute_dtype)
    with jax.named_scope("llama.block.mlp"):
        return (_branches_residual(bp, x, o, h, cfg=cfg,
                                   compute_dtype=compute_dtype, ffn=ffn),
                rows)


def init_cache(cfg: LlamaConfig, batch: int, max_len: int, dtype=jnp.float32):
    """KV cache at KV-head width (L, B, KV, S, D) — GQA's decode-bandwidth
    win made concrete: H/KV times fewer cache bytes per step than MHA.
    Codec dispatch (f32/bf16/"int8") is generate.init_cache's."""
    from dnn_tpu.runtime import generate

    gqa_cfg = dataclasses.replace(
        cfg, n_head=cfg.n_kv_head, n_embd=cfg.n_kv_head * cfg.head_dim)
    return generate.init_cache(gqa_cfg, batch, max_len, dtype)


def forward_with_cache(prepared, ids, cache, start_pos, *, cfg: LlamaConfig,
                       compute_dtype=None, attn_kernel="auto", rolling=False,
                       ffn=None, moe_stats=False):
    """-> (logits, new_cache); with `moe_stats` (an ffn that has
    `with_stats`: the MoE hook) also the int32 (N_STATS,) sum over the layers
    of what each expert layer call cost (parallel/moe.moe_ffn_grouped).
    `hidden_with_cache` and the head over every row."""
    x, *rest = hidden_with_cache(
        prepared, ids, cache, start_pos, cfg=cfg, compute_dtype=compute_dtype,
        attn_kernel=attn_kernel, rolling=rolling, ffn=ffn,
        moe_stats=moe_stats)
    return (head(prepared, x, cfg=cfg, compute_dtype=compute_dtype), *rest)


def hidden_with_cache(prepared, ids, cache, start_pos, *, cfg: LlamaConfig,
                      compute_dtype=None, attn_kernel="auto", rolling=False,
                      ffn=None, moe_stats=False):
    """`forward_with_cache` up to the last block: (hidden (B, T, C)
    float32 — what `head` is handed — the cache and, with `moe_stats`,
    the expert layers' sums). A serving prefill chunk ends here
    (LlamaFamilyRows.prefill)."""
    from dnn_tpu.runtime.kvcache import codec_for_cache
    from dnn_tpu.runtime.paged_kvcache import scan_rows

    if getattr(cfg, "mla", None) is not None or getattr(
            cfg, "first_k_dense", 0) or kv_kinds(cfg) is not None:
        raise ValueError(
            "forward_with_cache holds K and V of one stack of layers: a "
            "model with latent attention, a dense prefix or layers of "
            "kinds prefills and decodes through its family adapter "
            "(models/mla.py, LlamaKindRows) and the paged pool")
    ffn = ffn or cfg.default_ffn(compute_dtype)
    wins = layer_windows(cfg)  # (L,) for alternating configs, else None
    codec = codec_for_cache(cache, use_kernel=attn_kernel,
                            window=None if wins is not None
                            else cfg.sliding_window,
                            rolling=rolling, softcap=cfg.attn_softcap)
    x = _scaled_embed(prepared, ids, cfg)
    if compute_dtype is not None:
        x = x.astype(compute_dtype)

    blocks, bind = scan_form(prepared["blocks"], ffn)

    def block(bp, carry, rows, window=None):  # this layer's, if any
        x, acc = carry
        bp = bind(bp)

        def run(f):
            return _block_with_cache(
                bp, x, rows, start_pos, cfg=cfg, compute_dtype=compute_dtype,
                codec=codec, window=window, ffn=f)

        (y, rows), acc = _run_block(ffn, acc, run)
        return (y, acc), rows

    acc0 = _stats_acc(moe_stats)
    (x, acc), new_cache = scan_rows(
        block, (x, acc0), blocks, cache, *(() if wins is None else (wins,)))
    x = x.astype(jnp.float32)
    if moe_stats:
        return x, new_cache, acc
    return x, new_cache


def _ring_from_prompt(prompt_cache, t: int, w: int):
    """Gather a prompt-length cache's live sliding-window band into a
    w-slot ring: slot j takes position ``a_j = (t-1) - ((t-1-j) % w)``
    (the latest prompt position congruent to j), zeroed where no such
    position exists (a_j < 0 — short prompts). Decode steps then keep
    writing positions t, t+1, ... at ``pos % w``; kvcache's ring
    predicate recovers exactly this occupancy at every later step."""
    from dnn_tpu.runtime.kvcache import ring_positions

    a = ring_positions(t - 1, w)  # (w,) absolute position per ring slot
    src = jnp.clip(a, 0, t - 1)
    out = {}
    for kk, leaf in prompt_cache.items():  # leaves (L, B, KV, S[, D])
        g = jnp.take(leaf, src, axis=3)
        live = (a >= 0).reshape((1, 1, 1, w) + (1,) * (leaf.ndim - 4))
        out[kk] = jnp.where(live, g, jnp.zeros_like(g))
    return out


def make_generate(cfg: LlamaConfig, *, max_new_tokens: int,
                  temperature: float = 0.0, top_k: Optional[int] = None,
                  top_p: Optional[float] = None,
                  compute_dtype=None, kv_dtype=None, attn_kernel="auto",
                  ffn=None):
    """Jitted generate(prepared, ids, rng) — same contract as the GPT
    family's decoder, including kv_dtype (f32/bf16/"int8") cache storage
    and attn_kernel (Pallas streaming cache attention on decode steps).

    Sliding-window configs whose total stream exceeds the window decode
    on a ROLLING cache: prefill runs window-masked on a transient
    prompt-length cache, its live band is gathered into a
    `sliding_window`-slot ring, and every decode step reads/writes only
    the ring — cache bytes per step are O(window) regardless of how long
    the stream runs (the Mistral architecture's decode claim)."""
    from dnn_tpu.runtime.generate import _sample

    if max_new_tokens < 1:
        raise ValueError(f"max_new_tokens must be >= 1, got {max_new_tokens}")

    @jax.jit
    def generate(prepared, ids, rng):
        b, t = ids.shape
        s_max = t + max_new_tokens
        if s_max > cfg.block_size:
            raise ValueError(
                f"prompt {t} + max_new_tokens {max_new_tokens} exceeds "
                f"block_size {cfg.block_size}")
        cache_dtype = kv_dtype if kv_dtype is not None else (compute_dtype or jnp.float32)
        w = cfg.sliding_window
        # alternating configs (Gemma-2) keep GLOBAL layers, so the cache
        # can never roll down to the window — full-length cache with the
        # per-layer band handled inside forward_with_cache
        rolling = w is not None and s_max > w and not cfg.alt_window
        if rolling:
            # transient prompt-length cache (window-masked attends), then
            # the live band moves into the ring
            prompt_cache = init_cache(cfg, b, t, cache_dtype)
            logits, prompt_cache = forward_with_cache(
                prepared, ids, prompt_cache, 0, cfg=cfg,
                compute_dtype=compute_dtype, ffn=ffn)
            cache = _ring_from_prompt(prompt_cache, t, w)
        else:
            cache = init_cache(cfg, b, s_max, cache_dtype)
            logits, cache = forward_with_cache(
                prepared, ids, cache, 0, cfg=cfg, compute_dtype=compute_dtype,
                attn_kernel=attn_kernel, ffn=ffn)
        rng, sub = jax.random.split(rng)
        tok = _sample(logits[:, -1], sub, temperature=temperature,
                      top_k=top_k, top_p=top_p)

        def step(carry, i):
            cache, tok, rng = carry
            logits, cache = forward_with_cache(
                prepared, tok[:, None], cache, t + i, cfg=cfg,
                compute_dtype=compute_dtype,
                attn_kernel=False if rolling else attn_kernel,
                rolling=rolling,
                ffn=ffn)
            rng, sub = jax.random.split(rng)
            nxt = _sample(logits[:, -1], sub, temperature=temperature,
                          top_k=top_k, top_p=top_p)
            return (cache, nxt, rng), tok

        (_, last, _), toks = lax.scan(
            step, (cache, tok, rng), jnp.arange(max_new_tokens - 1))
        toks = jnp.moveaxis(toks, 0, 1)
        return jnp.concatenate([toks, last[:, None]], axis=1)

    return generate


def make_apply_seq_parallel(cfg: LlamaConfig, mesh, *, axis_name=None,
                            compute_dtype=None):
    """Sequence-parallel (long-context) LLaMA forward over the "seq" mesh
    axis — ring attention with GQA-narrow K/V blocks.

    Embed/RMSNorm/SwiGLU/head act position-wise on local shards; RoPE uses
    each shard's GLOBAL positions; attention crosses shards by rotating
    K/V blocks around the ring at KV-HEAD width (H/KV times fewer ICI
    bytes per hop than an MHA ring — GQA's bandwidth advantage applies to
    the collective exactly as it does to the decode cache), with the
    query group folded into rows (parallel/ring_attention.py's GQA mode).

    apply(prepared, ids): ids (B, T), T divisible by the axis size;
    returns f32 logits sharded over the sequence axis. Parity vs the
    dense forward is pinned in tests/test_models_llama.py."""
    from jax.sharding import PartitionSpec as P

    from dnn_tpu.parallel.mesh import SEQ_AXIS
    from dnn_tpu.parallel.ring_attention import ring_attention_local

    if cfg.alt_window:
        raise ValueError(
            "alternating-window configs (Gemma-2) are not supported on "
            "the sequence-parallel path: blocks share one attention "
            "body, and the per-layer window channel is not threaded "
            "through the ring (uniform sliding_window IS supported — "
            "the banded ring schedule)")
    if cfg.attn_softcap is not None:
        raise ValueError(
            "attention softcapping is not supported on the ring-attention "
            "path (the online-softmax hop combine assumes raw scores)")
    if cfg.default_ffn() is not None:
        raise ValueError(
            "MoE configs are not supported on the sequence-parallel path "
            "(per-shard routing groups would diverge from the dense "
            "routing — EP x SP composition is follow-on work)")
    axis = axis_name or SEQ_AXIS

    def local_fn(prepared, ids_local):
        b, t_local = ids_local.shape
        my = lax.axis_index(axis)
        pos = my * t_local + jnp.arange(t_local)  # global positions
        x = _scaled_embed(prepared, ids_local, cfg)
        if compute_dtype is not None:
            x = x.astype(compute_dtype)
        kv, g, d = cfg.n_kv_head, cfg.n_head // cfg.n_kv_head, cfg.head_dim

        def ring_attn(bp, h):
            q, k, v = _qkv_rope(bp, h, pos, cfg=cfg,
                                compute_dtype=compute_dtype)
            qg = q.reshape(b, kv, g * t_local, d)  # fold group into rows
            # sliding-window configs ride the banded ring: the band's
            # lower bound masks per block AND the ring stops after the
            # live hops (parallel/ring_attention.py)
            y = ring_attention_local(qg, k, v, axis_name=axis, causal=True,
                                     window=cfg.sliding_window)
            y = y.reshape(b, cfg.n_head, t_local, d)
            return linear(bp["attn"]["o"], merge_heads(y.astype(h.dtype)),
                          compute_dtype=compute_dtype)

        x = blocks_scan(prepared["blocks"], x, cfg=cfg,
                        compute_dtype=compute_dtype, attn_fn=ring_attn)
        return head(prepared, x.astype(jnp.float32), cfg=cfg,
                    compute_dtype=compute_dtype)

    def apply(prepared, ids):
        t = ids.shape[-1]
        if t > cfg.block_size:
            raise ValueError(
                f"Cannot forward: sequence length {t} > block_size "
                f"{cfg.block_size}")
        n = mesh.shape[axis]
        if t % n != 0:
            raise ValueError(
                f"sequence length {t} not divisible by seq axis size {n}")
        return jax.shard_map(
            local_fn, mesh=mesh,
            in_specs=(P(), P(None, axis)),
            out_specs=P(None, axis, None),
            check_vma=False,
        )(prepared, ids)

    return apply


def make_generate_seq_sharded(cfg: LlamaConfig, mesh, *, max_new_tokens: int,
                              temperature: float = 0.0,
                              top_k: Optional[int] = None,
                              top_p: Optional[float] = None,
                              compute_dtype=None, axis_name=None):
    """Sequence-sharded KV-cache decode for the LLaMA family: each device
    of the "seq" axis owns a contiguous block of cache POSITIONS at
    KV-head width, and every decode step combines per-shard partial
    attention with the exact distributed online-softmax
    (runtime/generate_seq.py's design — pmax + two psums, no K/V
    movement), with the GQA query group folded into the stats rows and
    RoPE at absolute positions. Token-parity with llama.make_generate
    while each shard holds only ceil(S_max/n) positions.

    NOTE: mirrors runtime/generate_seq.make_generate_seq_sharded's loop
    (same reason as the EP x PP decoder's mirror — the per-family block
    internals differ where that module's are GPT-fixed); drift is caught
    by each file's parity tests against its own solo decoder."""
    from dnn_tpu.parallel.mesh import SEQ_AXIS
    from dnn_tpu.runtime.generate import _sample
    from dnn_tpu.runtime.generate_seq import _local_attn_stats

    if max_new_tokens < 1:
        raise ValueError(f"max_new_tokens must be >= 1, got {max_new_tokens}")
    if cfg.sliding_window is not None:
        raise ValueError(
            "sequence-sharded decode keeps full history shards; "
            "sliding-window configs are not supported on this path")
    if cfg.attn_softcap is not None:
        raise ValueError(
            "attention softcapping is not supported on the seq-sharded "
            "decode path (the distributed online-softmax combines raw "
            "per-shard score stats)")
    if cfg.default_ffn() is not None:
        raise ValueError(
            "MoE configs are not supported on the seq-sharded decode "
            "path (its inline block body has no ffn hook)")
    axis = axis_name or SEQ_AXIS
    n = mesh.shape[axis]
    kv, g, hd = cfg.n_kv_head, cfg.n_head // cfg.n_kv_head, cfg.head_dim

    def per_device(prepared, ids, rng):
        b, t = ids.shape
        s_max = t + max_new_tokens
        sd = -(-s_max // n)
        i = lax.axis_index(axis)
        lo = i * sd

        # prefill: full forward over a transient prompt-length KV-width
        # cache; each device gathers its own position columns
        prompt_cache = init_cache(cfg, b, t, compute_dtype or jnp.float32)
        # attn_kernel pinned off: this forward runs INSIDE shard_map,
        # where the "auto" policy's Pallas engagement is untested — the
        # sharded path keeps the einsum unconditionally
        logits, prompt_cache = forward_with_cache(
            prepared, ids, prompt_cache, 0, cfg=cfg,
            compute_dtype=compute_dtype, attn_kernel=False)
        gpos = lo + jnp.arange(sd)
        in_prompt = gpos < t
        local = {
            kk: jnp.where(
                in_prompt[None, None, None, :, None],
                jnp.take(prompt_cache[kk], jnp.clip(gpos, 0, t - 1), axis=3),
                0,
            )
            for kk in ("k", "v")
        }  # (L, B, KV, Sd, D)
        rng, sub = jax.random.split(rng)
        tok = _sample(logits[:, -1], sub, temperature=temperature,
                      top_k=top_k, top_p=top_p)

        def block_step(bp, x, lc_k, lc_v, p):
            h = _pre_normed(bp, x, cfg)
            q, k, v = _qkv_rope(bp, h, p + jnp.arange(1), cfg=cfg,
                                compute_dtype=compute_dtype)
            p_loc = jnp.clip(p - lo, 0, sd - 1)
            own = jnp.logical_and(p >= lo, p < lo + sd)
            lc_k = jnp.where(own, lax.dynamic_update_slice_in_dim(
                lc_k, k.astype(lc_k.dtype), p_loc, axis=2), lc_k)
            lc_v = jnp.where(own, lax.dynamic_update_slice_in_dim(
                lc_v, v.astype(lc_v.dtype), p_loc, axis=2), lc_v)
            local_limit = jnp.minimum(p - lo, sd - 1)
            qg = q.reshape(b, kv, g, hd)  # fold group into stats rows
            m, l, o = _local_attn_stats(qg, lc_k, lc_v, local_limit)
            g_m = lax.pmax(m, axis)
            w = jnp.exp(m - g_m)
            g_l = lax.psum(l * w, axis)
            g_o = lax.psum(o * w[..., None], axis)
            y = g_o / jnp.maximum(g_l, 1e-30)[..., None]
            y = y.reshape(b, cfg.n_head, 1, hd)
            o = linear(bp["attn"]["o"], merge_heads(y.astype(x.dtype)),
                       compute_dtype=compute_dtype)
            return (_branches_residual(bp, x, o, h, cfg=cfg,
                                       compute_dtype=compute_dtype),
                    lc_k, lc_v)

        def decode_one(local, tok, rng, p):
            x = _scaled_embed(prepared, tok[:, None], cfg)
            if compute_dtype is not None:
                x = x.astype(compute_dtype)

            def layer(carry, layer_in):
                bp, lk, lv = layer_in
                y, lk, lv = block_step(bp, carry, lk, lv, p)
                return y, (lk, lv)

            x, (k_new, v_new) = lax.scan(
                layer, x, (prepared["blocks"], local["k"], local["v"]))
            logits = head(prepared, x.astype(jnp.float32), cfg=cfg,
                          compute_dtype=compute_dtype)
            rng, sub = jax.random.split(rng)
            nxt = _sample(logits[:, -1], sub, temperature=temperature,
                          top_k=top_k, top_p=top_p)
            return {"k": k_new, "v": v_new}, nxt, rng

        def step(carry, j):
            local, tok, rng = carry
            local, nxt, rng = decode_one(local, tok, rng, t + j)
            return (local, nxt, rng), tok

        (_, last, _), toks = lax.scan(
            step, (local, tok, rng), jnp.arange(max_new_tokens - 1))
        toks = jnp.moveaxis(toks, 0, 1)
        return jnp.concatenate([toks, last[:, None]], axis=1)

    @jax.jit
    def generate(prepared, ids, rng):
        from jax.sharding import PartitionSpec as P

        b, t = ids.shape
        if t + max_new_tokens > cfg.block_size:
            raise ValueError(
                f"prompt {t} + max_new_tokens {max_new_tokens} exceeds "
                f"block_size {cfg.block_size}")
        return jax.shard_map(
            per_device, mesh=mesh,
            in_specs=(P(), P(), P()),
            out_specs=P(),
            check_vma=False,
        )(prepared, ids, rng)

    return generate


def family_rows(cfg, **kw):
    """The batcher adapter a LLaMA-family config serves through: by what
    its attention keeps a position (`LlamaFamilyRows`' K and V — by layer
    kind `LlamaKindRows`'; with an indexer models/dsa.py's third leaf;
    with latent attention models/mla.py's one) and by whether layers keep
    a STATE a slot in its place or beside it (models/state_kind.py
    `StateKindRows`; models/block_select.py's where the K/V layers select
    the blocks they read). `kw`: `LlamaFamilyRows`' own."""
    from dnn_tpu.models import state_kind

    if cfg.index_topk is not None:
        from dnn_tpu.models.dsa import DsaFamilyRows as rows
    elif getattr(cfg, "mla", None) is not None:
        from dnn_tpu.models.mla import MlaFamilyRows as rows
    elif state_kind.config_rule(cfg) is None:
        rows = LlamaFamilyRows if kv_kinds(cfg) is None else LlamaKindRows
    elif getattr(cfg, "block_select", None) is not None:
        from dnn_tpu.models.block_select import BlockSelectRows as rows
    else:
        rows = state_kind.StateKindRows
    return rows(cfg, **kw)


def qkv_rows(bp, h, pos, *, cfg, compute_dtype, kind=None):
    """h (B, 1, C) normed rows at per-slot positions pos (B,) -> q (B, H, 1,
    D) normed, rotated and rescaled, k (rotated) and v (B, KV, 1, D); `kind`
    (the layer's `KvKind`): its table, or unrotated where its `rope` is
    off."""
    kv = cfg.n_kv_head
    h = _mup_scaled(h, cfg, "attention_in")
    q = split_heads(linear(bp["attn"]["q"], h, compute_dtype=compute_dtype),
                    cfg.n_head)
    k = split_heads(_mup_scaled(
        linear(bp["attn"]["k"], h, compute_dtype=compute_dtype), cfg,
        "key"), kv)
    v = split_heads(linear(bp["attn"]["v"], h, compute_dtype=compute_dtype),
                    kv)
    q, k = _qk_normed(bp, q, k, cfg)
    if kind is not None and not kind.rope:
        return _q_rescale(q, cfg), k, v
    cos, sin = _rope_tables(cfg, pos, kind)  # (B, D)
    cos, sin = cos[:, None, None, :], sin[:, None, None, :]
    q, k = _rope_apply(q, cos, sin, cfg), _rope_apply(k, cos, sin, cfg)
    return _q_rescale(q, cfg), k, v


class LlamaFamilyRows:
    """ContinuousBatcher family adapter (see
    runtime/serving.GPTFamilyRows for the protocol): per-slot LLaMA decode
    with RoPE at each slot's own position and the KV-head-width cache. The
    GQA fold for per-row attention treats the query group as the row dim —
    q (B, H, 1, D) -> (B, KV, G, D) — since every group row shares its
    slot's position limit."""

    def __init__(self, cfg: LlamaConfig, *, compute_dtype=None,
                 attn_kernel="auto", ffn=None):
        self.cfg = cfg
        self.compute_dtype = compute_dtype
        # picked up by ContinuousBatcher for the decode-rows codec too
        self.attn_kernel = attn_kernel
        # MLP override (Mixtral MoE — llama_moe.make_ffn); rides every
        # path of this adapter: prefill, decode rows, verify rows.
        # Resolved from the config when not passed, so
        # LlamaFamilyRows(mixtral_cfg) just works.
        self.ffn = ffn or cfg.default_ffn(compute_dtype)
        # an MoE hook can count what its layer calls cost: prefill and
        # decode_rows then take `moe_stats=True` and return the sums as
        # a third result (ContinuousBatcher's moe_* counters)
        self.moe_stats = getattr(self.ffn, "with_stats", None) is not None
        # paged-pool head width: the cache stores KV heads (GQA)
        self.kv_heads = cfg.n_kv_head
        # picked up by ContinuousBatcher: sliding-window masking over the
        # slot pool's full-length cache (storage unchanged — the pool is
        # shared across slots, so the ring form doesn't apply here).
        # Alternating-window configs (Gemma-2) keep the CODEC dense and
        # thread the per-layer window through the block scan instead.
        self._wins = layer_windows(cfg)
        self.window = None if self._wins is not None else cfg.sliding_window
        # alt-window configs keep window=None (per-layer channel) — the
        # paged batcher needs the distinction to reject them explicitly
        self.alt_window = cfg.alt_window
        # Gemma-2 attention softcapping rides the codec (serving builds
        # the decode codec from this attr)
        self.softcap = cfg.attn_softcap
        # "attends plain dense causal" — what the SPECULATIVE verifier
        # requires (its codecs attend dense; serving_spec checks this
        # flag). The paged pool no longer keys on it: it gates on
        # softcap/alt_window directly and band-masks uniform windows
        # itself (runtime/paged_kvcache.PagedKV window=).
        self.paged_ok = (cfg.sliding_window is None
                         and cfg.attn_softcap is None)

    def init_cache(self, batch, max_len, dtype):
        return init_cache(self.cfg, batch, max_len, dtype)

    def prefill(self, prepared, padded, row_cache, start_pos=0, *,
                moe_stats=False):
        """One (1, P) prompt chunk -> (hidden (1, P, C) float32: the
        last block's output, no final norm, no head; the row cache[; the
        expert layers' sums])."""
        return hidden_with_cache(
            prepared, padded, row_cache, start_pos, cfg=self.cfg,
            compute_dtype=self.compute_dtype, attn_kernel=self.attn_kernel,
            ffn=self.ffn, moe_stats=moe_stats)

    def head_leaves(self, prepared):
        """The leaves `head` reads — the final norm and the head kernel
        (the input table where the two are tied) — of a param view: what
        the finish program is handed in place of the whole tree."""
        return {k: prepared[k] for k in
                ("ln_f", "lm_head" if "lm_head" in prepared else "wte")}

    def head(self, aux, h):
        """Logits of hidden rows h (..., C) under `llama.head`: final
        norm, head kernel, softcap where the config has one."""
        return head(aux, h.astype(jnp.float32), cfg=self.cfg,
                    compute_dtype=self.compute_dtype)

    def _block_rows(self, bp, x, layer_cache, pos, write, codec,
                    window=None, ffn=None, **kind):
        with jax.named_scope("llama.block.cached_attn"):
            h, o, layer_cache = self._attn_rows(bp, x, layer_cache, pos,
                                                write, codec, window, **kind)
        with jax.named_scope("llama.block.mlp"):
            return (_branches_residual(bp, x, o, h, cfg=self.cfg,
                                       compute_dtype=self.compute_dtype,
                                       ffn=ffn or self.ffn),
                    layer_cache)

    def _qkv_rows(self, bp, h, pos, kind=None):
        return qkv_rows(bp, h, pos, cfg=self.cfg,
                        compute_dtype=self.compute_dtype, kind=kind)

    def _attn_rows(self, bp, x, layer_cache, pos, write, codec, window):
        cfg, compute_dtype = self.cfg, self.compute_dtype
        b = x.shape[0]
        kv, g, d = cfg.n_kv_head, cfg.n_head // cfg.n_kv_head, cfg.head_dim
        h = _pre_normed(bp, x, cfg)
        q, k, v = self._qkv_rows(bp, h, pos)
        qg = q.reshape(b, kv, g, d)  # group rows share the slot's limit
        y, layer_cache = codec.write_attend_rows(qg, layer_cache, k, v, pos,
                                                 write, window=window)
        y = y.reshape(b, cfg.n_head, 1, d)
        o = linear(bp["attn"]["o"], merge_heads(y.astype(x.dtype)),
                   compute_dtype=compute_dtype)
        return h, o, layer_cache

    def verify_rows(self, prepared, cache, chunk, pos, active, codec):
        """A (B, T) token block at PER-ROW start positions pos (B,) —
        the speculative batcher's target-scoring / draft-sync program
        (see runtime/serving.GPTFamilyRows.verify_rows): writes ROTATED
        K/V for positions pos..pos+T-1 of each active row, attends GQA
        with per-row within-block causality, row t's logits predict the
        token at position pos+t+1.

        Restrictions match the speculative batcher's: float caches
        (attention reads the cache leaves directly — the codec handles
        the write gate) and dense attention (no window/softcap; those
        families are rejected at batcher construction). The score/probs
        dtype recipe mirrors kvcache.FloatKV.attend_rows exactly, so a
        greedy verify reproduces the step-by-step decode's argmax even
        under bf16 compute (the spec batcher's token-identity
        contract)."""
        cfg, compute_dtype = self.cfg, self.compute_dtype
        if cfg.sliding_window is not None or cfg.attn_softcap is not None:
            raise ValueError(
                "speculative verify supports dense-attention LLaMA-family "
                "configs only (no sliding window / softcap)")
        b, t = chunk.shape
        kv, g, hd = cfg.n_kv_head, cfg.n_head // cfg.n_kv_head, cfg.head_dim
        positions = pos[:, None] + jnp.arange(t)  # (B, T)
        x = _scaled_embed(prepared, chunk, cfg)
        if compute_dtype is not None:
            x = x.astype(compute_dtype)
        # loop-invariant: one table for all layers (a scan body would
        # recompute it per layer — JAX does not hoist out of scan)
        cos, sin = _rope_tables(cfg, positions)  # (B, T, D)
        cos_, sin_ = cos[:, None], sin[:, None]  # broadcast over heads

        def layer(carry, layer_in):
            bp, lc = layer_in
            h = _pre_normed(bp, carry, cfg)
            q = split_heads(linear(bp["attn"]["q"], h,
                                   compute_dtype=compute_dtype), cfg.n_head)
            kk = split_heads(linear(bp["attn"]["k"], h,
                                    compute_dtype=compute_dtype), kv)
            vv = split_heads(linear(bp["attn"]["v"], h,
                                    compute_dtype=compute_dtype), kv)
            q, kk = _qk_normed(bp, q, kk, cfg)
            q, kk = (_rope_apply(q, cos_, sin_, cfg),
                     _rope_apply(kk, cos_, sin_, cfg))
            q = _q_rescale(q, cfg)
            lc = codec.write_rows(lc, kk, vv, pos, active)
            # GQA per-row causal attend on the float cache: fold the
            # group NEXT TO the row dim (5-D scores) so each row keeps
            # its own within-block limit — the 4-D fold used by decode
            # (all rows share one limit) cannot express this
            ck, cv = lc["k"], lc["v"]  # (B, KV, S, D)
            qg = q.reshape(b, kv, g, t, hd)
            s = jnp.einsum("bkgtd,bksd->bkgts", qg,
                           ck).astype(jnp.float32) / jnp.sqrt(hd)
            cols = jnp.arange(ck.shape[2])
            limit = (pos[:, None, None, None, None]
                     + jnp.arange(t)[None, None, None, :, None])
            s = jnp.where(cols[None, None, None, None, :] <= limit, s,
                          _NEG_BIG)
            p = jax.nn.softmax(s, axis=-1)
            y = jnp.einsum("bkgts,bksd->bkgtd", p.astype(cv.dtype), cv)
            y = y.reshape(b, cfg.n_head, t, hd)
            o = linear(bp["attn"]["o"], merge_heads(y.astype(carry.dtype)),
                       compute_dtype=compute_dtype)
            return (_branches_residual(bp, carry, o, h, cfg=cfg,
                                       compute_dtype=compute_dtype,
                                       ffn=self.ffn), lc)

        x, new_cache = lax.scan(layer, x, (prepared["blocks"], cache))
        logits = head(prepared, x.astype(jnp.float32), cfg=cfg,
                      compute_dtype=compute_dtype)
        return logits, new_cache

    def decode_rows(self, prepared, cache, tok, pos, active, codec, *,
                    moe_stats=False):
        from dnn_tpu.runtime.paged_kvcache import scan_blocks

        x = _scaled_embed(prepared, tok[:, None], self.cfg)  # (B, 1, C)
        if self.compute_dtype is not None:
            x = x.astype(self.compute_dtype)

        # the loop's carry is (x, the MoE stats summed so far or None):
        # scan_blocks hands it through whole
        def block(bind, kind, bp, carry, c, codec, window=None):
            x, acc = carry
            bp = bind(bp)

            def run(f):
                return self._block_rows(bp, x, c, pos, active, codec,
                                        window=window, ffn=f, **kind)

            (y, c), acc = _run_block(self.ffn, acc, run)
            return (y, acc), c

        # a paged pool rides the loop whole, a dense cache by layer
        acc0 = _stats_acc(moe_stats)
        carry, new_cache = (x, acc0), cache
        for stack, layers, kind in layer_stacks(prepared, self.cfg):
            # one of several stacks scans its own range of the pool (of
            # its kind's leaves, where the layers are of kinds)
            wins = () if self._wins is None else (
                self._wins if layers is None
                else self._wins[layers[0]:layers[1]],)
            blocks, bind = scan_form(stack, self.ffn)
            carry, new_cache = scan_blocks(
                functools.partial(block, bind,
                                  {} if kind is None else {"kind": kind}),
                carry, blocks, new_cache, codec, *wins,
                layers=None if layers is None else jnp.arange(*layers))
        x, acc = carry
        logits = head(prepared, x.astype(jnp.float32), cfg=self.cfg,
                      compute_dtype=self.compute_dtype)
        if moe_stats:
            return logits[:, -1], new_cache, acc
        return logits[:, -1], new_cache


def prefill_by_kind(family, prepared, padded, row_cache, start_pos,
                    moe_stats, first="full", **chunk_kw):
    """A family's `prefill` where the transient row's leaves are BY LAYER
    KIND (models/mla.py's latents, `LlamaKindRows`' K and V, a state
    kind's slot leaves): each stack of `layer_stacks` scans the ONE row
    cache over its own range of its kind's layers
    (`paged_kvcache.scan_rows(layers=)`, as `decode_rows` scans the pool)
    through `family._chunk_block(bp, x, rows, start_pos, ffn, kind)` ->
    (x, rows), `rows` the whole row cache bound to the layer: a block
    reaches its kind's leaves by name (`first`: the kind of a model that
    is ONE stack). -> (hidden (1, P, C) float32, the row cache[, the
    expert layers' sums]). `chunk_kw` goes on to `_chunk_block` (a state
    kind's count of real positions, models/kda.py)."""
    from dnn_tpu.runtime.paged_kvcache import scan_rows

    x = _scaled_embed(prepared, padded, family.cfg)
    if family.compute_dtype is not None:
        x = x.astype(family.compute_dtype)

    def block(bind, kind, bp, carry, rows):
        x, acc = carry
        bp = bind(bp)
        (y, rows), acc = _run_block(
            family.ffn, acc,
            lambda f: family._chunk_block(bp, x, rows, start_pos, f, kind,
                                          **chunk_kw))
        return (y, acc), rows

    carry = (x, _stats_acc(moe_stats))
    for stack, layers, kind in layer_stacks(prepared, family.cfg):
        blocks, bind = scan_form(stack, family.ffn)
        carry, row_cache = scan_rows(
            functools.partial(block, bind, kind or first), carry, blocks,
            row_cache, layers=None if layers is None else jnp.arange(*layers))
    x, acc = carry
    x = x.astype(jnp.float32)  # what `head` is handed, in the finish
    if moe_stats:
        return x, row_cache, acc
    return x, row_cache


class LlamaKindRows(LlamaFamilyRows):
    """`LlamaFamilyRows` for a config whose K/V layers are of two KINDS
    (`kv_kinds`: "full" layers keep every position, "window" layers the
    last W, and a kind may leave q and k unrotated). What a position's
    cache holds is K and V either way, but a kind's leaves have THAT
    kind's layers, blocks and tables: `cache_kinds` (kind -> {"layers",
    "leaves", "tables", "window"}: runtime/paged_kvcache.py's module
    docstring) names "k" / "v" under "tables" for the full kind and "k_w"
    / "v_w" under "tables_w" for the window kind, whose slot holds
    `window_blocks(W, block_len)` blocks whatever its length. Paged pools
    only; what assumes ONE stack of K and V — the prefix store, the KV
    tier, int8 / int4 pools, interleaved prefill, speculative verify — is
    refused by the batcher at construction (`requires_paged`).

    A prefill chunk writes its rows into the kind's transient row
    (whole-length for either kind) and attends through the runtime-limit
    kernel (ops/pallas/cached_attention.cached_attention) with the query
    group folded into the rows: column tiles past a row tile's diagonal
    — and, for a window kind, behind its band — are neither fetched nor
    computed. Scopes: `attn.prefill` / `attn.paged_decode` are the full
    kind's read, `attn.window_prefill` / `attn.window_decode` lie around
    a window kind's."""

    requires_paged = True

    def __init__(self, cfg, **kw):
        super().__init__(cfg, **kw)
        if cfg.sliding_window is not None or cfg.attn_softcap is not None \
                or cfg.alt_window or cfg.parallel_block:
            raise ValueError(
                "layers of kinds (kv_full / kv_window) are built for the "
                "sequential block without a uniform sliding window, an "
                "alternating one or a softcap: a kind's window is "
                "`KvKind.window`")
        self.kinds = kv_kinds(cfg) or {}  # none: every layer keeps a state
        d = cfg.head_dim
        self.cache_kinds = {}
        # a config without `layer_types` has ONE kind: every layer's
        types = getattr(cfg, "layer_types", None) or ("full",) * cfg.n_layer
        for kind, kk in self.kinds.items():
            k_name, v_name, tables = KV_KIND_LEAVES[kind]
            self.cache_kinds[kind] = {
                "layers": sum(t == kind for t in types),
                "leaves": {k_name: (cfg.n_kv_head, d),
                           v_name: (cfg.n_kv_head, d)},
                "tables": tables, "window": kk.window}
        self.cache_leaves = self.cache_kinds.get("full", {}).get("leaves", {})
        # which form each kind's reads took in the programs traced so far
        # (/statusz `components.attention.kinds`)
        self.attn_forms = {kind: {} for kind in self.kinds}

    def kind_tables(self):
        """{kind: its window and the table it rotates by (None: unrotated)}
        for /statusz, beside the forms its reads took."""
        out = {}
        for kind, kk in self.kinds.items():
            rot = rotation_of(self.cfg, kk)
            out[kind] = {"window": kk.window, "rotation": {
                "type": rot.scaling or "default", "theta": rot.theta,
                "factor": rot.scale,
                "attention_factor": rot.cos_sin_factor} if kk.rope else None}
        return out

    def init_cache(self, batch, max_len, dtype):
        """What `cache_kinds` says each kind keeps, dense: a row a position
        of its `leaves`, one every `stride` of its `strided_leaves`, its
        `slot_leaves` as they are."""
        from dnn_tpu.models import state_kind

        if dtype in ("int8", "int4"):
            raise ValueError("a cache of leaves by layer kind is float (a "
                             "state leaf float32)")
        cache = {}
        for k in self.cache_kinds.values():
            strided = k.get("strided_leaves", {})
            for name, (heads, width, *stride) in (*k["leaves"].items(),
                                                  *strided.items()):
                rows = -(-max_len // stride[0]) if stride else max_len
                cache[name] = jnp.zeros(
                    (k["layers"], batch, heads, rows, width), dtype)
            cache.update(state_kind.fresh(k.get("slot_leaves", {}), batch,
                                          dtype, k["layers"]))
        return cache

    def _chunk_attn(self, bp, h, rows, start_pos, kind):
        """Attention of one block over a prefill chunk's normed rows h (1,
        T, C) at [start_pos, start_pos + T): the chunk's K and V written
        into the layer's rows of the transient row cache `rows` (bound to
        the layer: `paged_kvcache.LayerRows`; a row (1, KV, S, D)), then
        attended with the group folded into the kernel's rows (row g * T +
        t reads columns <= start_pos + t, within the kind's band) -> (the
        o-projected output (1, T, C), rows)."""
        from dnn_tpu.ops.pallas.cached_attention import cached_attention

        cfg, compute_dtype = self.cfg, self.compute_dtype
        kk = self.kinds[kind]
        k_name, v_name, _ = KV_KIND_LEAVES[kind]
        t = h.shape[1]
        kv, g, d = cfg.n_kv_head, cfg.n_head // cfg.n_kv_head, cfg.head_dim
        q, k, v = _qkv_rope(bp, h, start_pos + jnp.arange(t), cfg=cfg,
                            compute_dtype=compute_dtype, kind=kk)
        with jax.named_scope("kv_pool.write"):
            rows.write(start_pos, **{k_name: k, v_name: v})
        k_row, v_row = rows[k_name], rows[v_name]
        interpret = True if self.attn_kernel == "interpret" else None
        # the largest tile up to 512 that divides the chunk and the
        # row: a 128 x 128 tile costs 3.4-4.7x a (512, 512) one on a
        # v5e for the same pairs (PERF.md section 6, PR 43)
        tile = next(n for n in (512, 256, 128, t)
                    if t % n == 0 and k_row.shape[2] % n == 0)
        attend = functools.partial(
            cached_attention, q.reshape(1, kv, g * t, d), k_row, v_row,
            jnp.reshape(start_pos, (1,)).astype(jnp.int32),
            rows_mod=t, block_q=tile, block_s=tile, interpret=interpret)
        if kk.window is None:
            y = attend()
        else:
            with jax.named_scope("attn.window_prefill"):
                y = attend(window=kk.window)
        self.attn_forms[kind]["prefill"] = (
            "banded_kernel" if kk.window else "kernel") if (
                interpret or jax.default_backend() == "tpu") else "plain"
        y = y.reshape(1, cfg.n_head, t, d)
        return linear(bp["attn"]["o"],
                      _gated(bp, h, merge_heads(y.astype(h.dtype)),
                             compute_dtype), compute_dtype=compute_dtype), rows

    def _chunk_block(self, bp, x, rows, start_pos, ffn, kind, **chunk_kw):
        """One block over a prefill chunk x (1, T, C) at [start_pos,
        start_pos + T): `_chunk_attn`, then the residuals and the MLP."""
        cfg = self.cfg
        with jax.named_scope("llama.block.cached_attn"):
            h = _pre_normed(bp, x, cfg)
            o, rows = self._chunk_attn(bp, h, rows, start_pos, kind,
                                       **chunk_kw)
        with jax.named_scope("llama.block.mlp"):
            return (_branches_residual(bp, x, o, h, cfg=cfg,
                                       compute_dtype=self.compute_dtype,
                                       ffn=ffn),
                    rows)

    def prefill(self, prepared, padded, row_cache, start_pos=0, *,
                moe_stats=False, **chunk_kw):
        """The transient row's leaves of a kind are what `cache_kinds` says
        the kind keeps: paged, strided and slot leaves."""
        return prefill_by_kind(
            self, prepared, padded, row_cache, start_pos, moe_stats,
            next(iter(self.cache_kinds)), **chunk_kw)

    def _attn_rows(self, bp, x, layer_cache, pos, write, codec, window,
                   kind="full"):
        cfg, compute_dtype = self.cfg, self.compute_dtype
        kk = self.kinds[kind]
        b = x.shape[0]
        kv, g, d = cfg.n_kv_head, cfg.n_head // cfg.n_kv_head, cfg.head_dim
        h = _pre_normed(bp, x, cfg)
        q, k, v = self._qkv_rows(bp, h, pos, kind=kk)
        qg = q.reshape(b, kv, g, d)  # group rows share the slot's limit
        self.attn_forms[kind]["decode"] = codec.decode_form(
            layer_cache, kk.window)
        if kk.window is None:
            y, layer_cache = codec.write_attend_rows(qg, layer_cache, k, v,
                                                     pos, write)
        else:
            k_name, v_name, tables = KV_KIND_LEAVES[kind]
            with jax.named_scope("attn.window_decode"):
                y, layer_cache = codec.write_attend_rows(
                    qg, layer_cache, k, v, pos, write, window=kk.window,
                    leaves=(k_name, v_name), tables=tables)
        y = y.reshape(b, cfg.n_head, 1, d)
        o = linear(bp["attn"]["o"],
                   _gated(bp, h, merge_heads(y.astype(x.dtype)),
                          compute_dtype), compute_dtype=compute_dtype)
        return h, o, layer_cache

    def verify_rows(self, *a, **kw):
        raise ValueError(
            "speculative verify reads ONE stack of K and V: not available "
            "with cache leaves by layer kind (" + "/".join(
                n for k in self.cache_kinds.values()
                for n in (*k["leaves"], *k.get("slot_leaves", ()))) + ")")


class LlamaPipelineFamily:
    """Pipeline-parallel decode hooks (see
    runtime/generate.GPTPipelineFamily): stage-local cache shards at
    KV-head width, RoPE at the ring's absolute positions."""

    def __init__(self, cfg: LlamaConfig, *, compute_dtype=None, kv_dtype=None):
        if cfg.alt_window:
            raise ValueError(
                "alternating-window configs (Gemma-2) are not supported on "
                "the pipeline decode path: the stage scan has no per-layer "
                "window channel (use the solo decoder or the batcher)")
        if cfg.default_ffn() is not None:
            raise ValueError(
                "MoE configs are not supported on this pipeline decode "
                "path (MoE pipeline decode is runtime/generate_moe's "
                "machinery)")
        self.cfg = cfg
        self.compute_dtype = compute_dtype
        self.kv_dtype = kv_dtype  # None follows compute_dtype; "int8" quantizes

    def stage_cache(self, per_stage, batch, s_max):
        dt = self.kv_dtype if self.kv_dtype is not None else (
            self.compute_dtype or jnp.float32)
        stage_cfg = dataclasses.replace(self.cfg, n_layer=per_stage)
        return init_cache(stage_cfg, batch, s_max, dt)

    def block_with_cache(self, bp, x, rows, start_pos):
        from dnn_tpu.runtime.kvcache import codec_for_cache

        return _block_with_cache(
            bp, x, rows, start_pos, cfg=self.cfg,
            compute_dtype=self.compute_dtype,
            codec=codec_for_cache(rows.leaves,
                                  window=self.cfg.sliding_window,
                                  softcap=self.cfg.attn_softcap))

    def embed(self, aux, ids, start_pos):
        x = _scaled_embed(aux, ids, self.cfg)
        if self.compute_dtype is not None:
            x = x.astype(self.compute_dtype)
        return x

    def head(self, aux, h):
        return head(aux, h.astype(jnp.float32), cfg=self.cfg,
                    compute_dtype=self.compute_dtype)


def make_pipeline_generate(cfg: LlamaConfig, mesh, *, max_new_tokens: int,
                           temperature: float = 0.0,
                           top_k: Optional[int] = None,
                           top_p: Optional[float] = None,
                           compute_dtype=None, axis_name=None,
                           kv_dtype=None):
    """Pipeline-parallel KV-cache generation for the LLaMA family: each
    stage keeps its blocks AND its KV-head-width cache shard, the hidden
    state rides the ppermute ring per token (runtime/generate's ring
    schedule with this family's hooks). Token-for-token identical to
    llama.make_generate."""
    from dnn_tpu.runtime.generate import (
        make_pipeline_generate as _mk,
    )

    return _mk(cfg, mesh, max_new_tokens=max_new_tokens,
               temperature=temperature, top_k=top_k, top_p=top_p,
               compute_dtype=compute_dtype, axis_name=axis_name,
               family=LlamaPipelineFamily(cfg, compute_dtype=compute_dtype,
                                          kv_dtype=kv_dtype))


# --------------------------------------------------------------------------
# pipeline partitioning + registry
# --------------------------------------------------------------------------

def make_partition(cfg: LlamaConfig, *, compute_dtype=None):
    part_ffn = cfg.default_ffn(compute_dtype)

    def partition(num_parts):
        if getattr(cfg, "layer_types", None) is not None:
            # layers of kinds: one stage, the whole forward
            keys = ("wte", "ln_f", "lm_head") + tuple(
                f"h_{i}" for i in range(cfg.n_layer))
            return [StageSpec(
                name=f"llama_blocks[0:{cfg.n_layer}]+embed+head",
                apply=make_apply(cfg, compute_dtype=compute_dtype),
                param_keys=keys)]
        ranges = gpt.layer_ranges(cfg.n_layer, num_parts)
        stages = []
        wins = layer_windows(cfg)
        for p, (lo, hi) in enumerate(ranges):
            is_first, is_last = p == 0, p == num_parts - 1
            param_keys = tuple(f"h_{i}" for i in range(lo, hi))
            if is_first:
                param_keys = ("wte",) + param_keys
            if is_last:
                param_keys = param_keys + ("ln_f",)
                if cfg.tie_word_embeddings:
                    # tied head projects through the embedding table — the
                    # LAST stage needs wte too (both stages then hold a
                    # copy, the standard tied-embeddings PP trade)
                    if not is_first:
                        param_keys = param_keys + ("wte",)
                else:
                    param_keys = param_keys + ("lm_head",)

            def stage_fn(params, x, _lo=lo, _hi=hi, _first=is_first, _last=is_last):
                if _first:
                    x = embed(params, x, cfg=cfg)
                if compute_dtype is not None and jnp.issubdtype(x.dtype, jnp.floating):
                    x = x.astype(compute_dtype)
                if _hi > _lo:
                    stacked = gpt.stack_blocks(params, range(_lo, _hi))
                    x = blocks_scan(stacked, x, cfg=cfg,
                                     compute_dtype=compute_dtype,
                                     windows=None if wins is None
                                     else wins[_lo:_hi], ffn=part_ffn)
                if _last:
                    x = head(params, x.astype(jnp.float32), cfg=cfg,
                             compute_dtype=compute_dtype)
                return x

            stages.append(StageSpec(
                name=f"llama_blocks[{lo}:{hi}]"
                + ("+embed" if is_first else "") + ("+head" if is_last else ""),
                apply=stage_fn,
                param_keys=param_keys,
            ))
        return stages

    return partition


def to_hf_config(cfg: LlamaConfig, *, tie_word_embeddings: bool = False,
                 **overrides):
    """The one LlamaConfig -> transformers config mapping (tests, the
    HF-serve example, and any converter round-trip share it — the field
    list must not fork). Sliding-window configs map to
    transformers.MistralConfig (the HF class that implements the window);
    attn_bias configs to Qwen2Config (the HF class with q/k/v biases);
    dense bias-free ones to LlamaConfig. Requires transformers; extra
    kwargs pass through (e.g. attn_implementation="eager")."""
    import transformers

    if cfg.one_mixer:
        # no transformers class of this mapping has blocks of one mixer
        raise ValueError(
            "blocks of ONE mixer by kind (`hybrid_override_pattern`) and "
            "experts in a latent (`moe_latent_size`) have no transformers "
            "config in this mapping — map this config by hand")
    kw = dict(
        vocab_size=cfg.vocab_size, hidden_size=cfg.n_embd,
        intermediate_size=cfg.d_ff, num_hidden_layers=cfg.n_layer,
        num_attention_heads=cfg.n_head, num_key_value_heads=cfg.n_kv_head,
        max_position_embeddings=cfg.block_size, rope_theta=cfg.rope_theta,
        rms_norm_eps=cfg.rms_eps,
        tie_word_embeddings=tie_word_embeddings or cfg.tie_word_embeddings,
    )
    if cfg.parallel_block:
        # Phi family: parallel residual, biased LayerNorms, partial
        # rotary, plain gelu MLP (HF "gelu_new" IS the tanh approx).
        # Reuses kw (the one-mapping contract) — only the eps key
        # renames and the Phi-specific fields add on top.
        kw["layer_norm_eps"] = kw.pop("rms_norm_eps")
        kw.update(
            partial_rotary_factor=(cfg.rotary_dim or cfg.head_dim)
            / cfg.head_dim,
            hidden_act="gelu_new")
        kw.update(overrides)
        return transformers.PhiConfig(**kw)
    if cfg.norm_plus_one:
        # Gemma family: (1+w) norms, GeGLU, scaled+tied embeddings
        kw.update(head_dim=cfg.head_dim,
                  hidden_activation="gelu_pytorch_tanh")
        if cfg.post_norms:  # Gemma-2
            kw.update(
                query_pre_attn_scalar=cfg.query_scale or cfg.head_dim,
                attn_logit_softcapping=cfg.attn_softcap,
                final_logit_softcapping=cfg.final_softcap,
                sliding_window=cfg.sliding_window,
            )
            kw.update(overrides)
            return transformers.Gemma2Config(**kw)
        kw.update(overrides)
        return transformers.GemmaConfig(**kw)
    if any(k.rotation is not None for k in (kv_kinds(cfg) or {}).values()):
        # no transformers class of this mapping rotates by layer kind
        raise ValueError(
            "layer kinds with tables of their own (KvKind.rotation) have no "
            "transformers config in this mapping — map this config by hand")
    if cfg.rope_scaling is not None and cfg.rope_scaling not in ROPE_SCALINGS:
        raise ValueError(f"rope_scaling {cfg.rope_scaling!r} has no "
                         "transformers mapping here (linear, ntk, yarn)")
    if cfg.rope_scaling == "linear" and cfg.rope_scale != 1.0:
        kw["rope_scaling"] = {"rope_type": "linear",
                              "factor": cfg.rope_scale}
    elif cfg.rope_scaling == "yarn" and cfg.rope_scale != 1.0:
        rot = rotation_of(cfg)
        kw["rope_scaling"] = {
            "rope_type": "yarn", "factor": rot.scale,
            "original_max_position_embeddings": rot.original_len,
            "beta_fast": rot.beta_fast, "beta_slow": rot.beta_slow,
            "truncate": rot.truncate,
            **({} if rot.attention_factor is None else
               {"attention_factor": rot.attention_factor})}
    elif cfg.rope_scaling == "ntk" and cfg.rope_scale != 1.0:
        # transformers has no STATIC ntk type (its "dynamic" rescales
        # with runtime length) — an equivalent HF config is theta
        # pre-multiplied, which we emit rather than a silent mismatch
        kw["rope_theta"] = cfg.rope_theta * cfg.rope_scale ** (
            cfg.head_dim / (cfg.head_dim - 2))
    if not cfg.pre_norm:
        # OLMo-2: post-norm-only block. HF Olmo2 hard-codes proj-width
        # q/k norms, no decoupled head_dim, no biases, no window —
        # anything else has no Olmo2Config mapping; emit an error
        # rather than a silently-dropped field (this function's
        # convention)
        if (not (cfg.qk_norm and cfg.qk_norm_width == "proj")
                or cfg.head_dim_override is not None or cfg.attn_bias
                or cfg.sliding_window is not None):
            raise ValueError(
                "pre_norm=False maps to Olmo2Config only with "
                "qk_norm=True/qk_norm_width='proj' and no "
                "head_dim_override/attn_bias/sliding_window — map this "
                "config by hand")
        kw.update(overrides)
        return transformers.Olmo2Config(**kw)
    if cfg.qk_norm:
        # Qwen3: PER-HEAD q/k RMSNorm, bias-free, decoupled head_dim
        if (cfg.attn_bias or cfg.sliding_window is not None
                or cfg.qk_norm_width != "head"):
            raise ValueError(
                "qk_norm with attn_bias/sliding_window/proj-width norms "
                "has no direct Qwen3Config mapping here — map this "
                "config by hand")
        kw.update(head_dim=cfg.head_dim, attention_bias=False)
        kw.update(overrides)
        return transformers.Qwen3Config(**kw)
    if cfg.sliding_window is not None:
        if cfg.attn_bias:
            raise ValueError(
                "attn_bias + sliding_window has no single HF class "
                "(MistralConfig is bias-free, Qwen2Config's window "
                "support differs) — map this config by hand")
        kw.update(sliding_window=cfg.sliding_window, head_dim=cfg.head_dim)
        kw.update(overrides)  # after defaults: overrides must win
        return transformers.MistralConfig(**kw)
    if cfg.attn_bias:
        # Qwen2's sliding window is OFF unless use_sliding_window is set
        kw.update(overrides)
        return transformers.Qwen2Config(**kw)
    kw.update(attention_bias=False, mlp_bias=False)
    kw.update(overrides)
    return transformers.LlamaConfig(**kw)


def _register(name: str, cfg: LlamaConfig):
    def convert(sd, _cfg=cfg):
        if _cfg.parallel_block:  # Phi layout (fc1/fc2, dense, LN biases)
            from dnn_tpu.io.checkpoint import phi_params_from_state_dict

            return phi_params_from_state_dict(sd, n_layer=_cfg.n_layer)
        from dnn_tpu.io.checkpoint import llama_params_from_state_dict

        return llama_params_from_state_dict(
            sd, n_layer=_cfg.n_layer, post_norms=_cfg.post_norms,
            tied_head="omit" if _cfg.tie_word_embeddings else "materialize")

    register_model(ModelSpec(
        name=name,
        init=lambda rng, dtype=jnp.float32, _cfg=cfg: init(rng, _cfg, dtype),
        apply=make_apply(cfg),
        partition=make_partition(cfg),
        example_input=gpt.make_example_input(cfg),
        # layers of kinds that interleave do not cut into equal stages
        supported_parts=(1,) if cfg.layer_types is not None
        else tuple(range(1, cfg.n_layer + 1)),
        convert_state_dict=convert,
        config=cfg,
        extras={
            "make_apply": lambda compute_dtype=None, **_kw: make_apply(
                cfg, compute_dtype=compute_dtype),
            "make_partition": lambda compute_dtype=None, **_kw: make_partition(
                cfg, compute_dtype=compute_dtype),
            # a layer at a time (`registry.ParamParts`): the daemon draws,
            # casts and frees one before the next
            "init_parts": lambda rng, dtype=jnp.float32, _cfg=cfg:
                init_parts(rng, _cfg, dtype),
            "family_rows": lambda compute_dtype=None, **_kw: family_rows(
                cfg, compute_dtype=compute_dtype),
        },
    ))


for _name, _cfg in PRESETS.items():
    _register(_name, _cfg)
