"""KV-cache generation for the MoE GPT family (dense-routed and
expert-parallel).

The reference has neither MoE nor any decode loop (SURVEY.md §2 "no MoE
modules exist"; §5 "no KV-cache even" — /root/reference/node.py:137-200 is
one stateless forward). This module closes the round-2 gap where
`gpt_moe` could train and forward but not serve: it reuses the dense
family's cached-attention machinery (dnn_tpu/runtime/generate.py) and
swaps the block MLP for the routed MoE FFN (dnn_tpu/parallel/moe.py).

Routing granularity during decode: the MoE FFN routes over whatever
tokens a forward sees. On one device the experts are drop-free
(parallel/moe.moe_ffn_grouped), so per-token top-k routing is
batch-independent and decode output matches the full-sequence forward —
the contract `tests/test_generate_moe.py` pins. The expert-parallel
decoders keep GShard's static capacity (per-rank static shapes for the
all_to_all), where drops are batch-dependent by construction; their
parity tests use a generous capacity_factor and the `groups=n` dense twin.

Expert-parallel decode (`make_generate_moe_ep`) runs the WHOLE generate —
prefill + `lax.scan` decode — as one shard_map program on the expert
mesh axis: batch shards over the axis (each device's local batch is its
routing group, so the local KV cache lives with the tokens it serves),
expert weights shard on their leading E axis, and tokens travel to their
experts via `jax.lax.all_to_all` per step. Greedy EP decode equals the
dense path with groups == axis size token-for-token; sampled EP decode
folds the device index into the rng stream (per-device local sampling),
so it matches the dense path in distribution, not draw-for-draw.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from dnn_tpu.models.gpt import head
from dnn_tpu.models.gpt_moe import GPTMoEConfig
from dnn_tpu.parallel.mesh import EXPERT_AXIS
from dnn_tpu.parallel.moe import (
    moe_capacity,
    moe_ffn,
    moe_ffn_grouped,
    moe_ffn_local,
)
from dnn_tpu.runtime.generate import (
    _embed_at,
    _sample,
    forward_with_cache,
    init_cache,
    make_generate,
)

__all__ = [
    "moe_cache_ffn",
    "forward_with_cache_moe",
    "make_generate_moe",
    "make_generate_moe_ep",
    "make_pipeline_generate_moe",
    "make_pipeline_generate_moe_ep",
]


def moe_cache_ffn(cfg: GPTMoEConfig, *, groups: int = 1, compute_dtype=None):
    """The `ffn(bp, h)` hook that turns any dense cached decoder
    (forward_with_cache / make_generate / ContinuousBatcher) into its MoE
    counterpart: h's tokens through bp["moe"], drop-free (parallel/moe.
    moe_ffn_grouped — no capacity on a single device). `groups` > 1 is
    the expert-parallel decoders' dense twin (static capacity per routing
    group), which their parity tests compare an n-device run with."""

    def ffn(bp, h):
        if groups == 1:
            return moe_ffn_grouped(bp["moe"], h, top_k=cfg.top_k,
                                   compute_dtype=compute_dtype)
        return moe_ffn(
            bp["moe"], h, top_k=cfg.top_k,
            capacity_factor=cfg.capacity_factor, groups=groups,
            compute_dtype=compute_dtype,
        )

    return ffn


def forward_with_cache_moe(prepared, ids, cache, start_pos, *,
                           cfg: GPTMoEConfig, compute_dtype=None,
                           groups: int = 1):
    """MoE analog of generate.forward_with_cache: ids (B, T) at positions
    [start_pos, start_pos+T), routed in `groups` groups per layer."""
    return forward_with_cache(
        prepared, ids, cache, start_pos, cfg=cfg,
        compute_dtype=compute_dtype,
        ffn=moe_cache_ffn(cfg, groups=groups, compute_dtype=compute_dtype),
    )


def make_generate_moe(cfg: GPTMoEConfig, *, max_new_tokens: int,
                      temperature: float = 0.0,
                      sample_top_k: Optional[int] = None,
                      sample_top_p: Optional[float] = None,
                      compute_dtype=None, groups: int = 1):
    """Jitted generate(prepared, ids, rng) for the MoE family — the dense
    family's make_generate with the routed FFN plugged in. `sample_top_k`
    is the SAMPLING truncation (cfg.top_k is the ROUTING fan-out)."""
    return make_generate(
        cfg, max_new_tokens=max_new_tokens, temperature=temperature,
        top_k=sample_top_k, top_p=sample_top_p, compute_dtype=compute_dtype,
        ffn=moe_cache_ffn(cfg, groups=groups, compute_dtype=compute_dtype),
    )


def make_pipeline_generate_moe(cfg: GPTMoEConfig, mesh, *,
                               max_new_tokens: int,
                               temperature: float = 0.0,
                               sample_top_k: Optional[int] = None,
                               compute_dtype=None, groups: int = 1,
                               axis_name=None, kv_dtype=None):
    """Pipeline-parallel MoE decode over the STAGE axis: each stage holds
    its block stack (attention + its layers' full expert sets) and its
    cache shard; the hidden state rides the ppermute ring per token with
    the routed FFN plugged into the cached block. Experts are NOT sharded
    here — this is PP x dense-MoE (per-stage expert replication); for
    experts sharded within each stage use make_pipeline_generate_moe_ep.
    Token-parity vs make_generate_moe on the same grouping."""
    from dnn_tpu.runtime.generate import (
        GPTPipelineFamily,
        make_pipeline_generate,
    )

    fam = GPTPipelineFamily(
        cfg, compute_dtype=compute_dtype, kv_dtype=kv_dtype,
        ffn=moe_cache_ffn(cfg, groups=groups, compute_dtype=compute_dtype))
    return make_pipeline_generate(
        cfg, mesh, max_new_tokens=max_new_tokens, temperature=temperature,
        top_k=sample_top_k, axis_name=axis_name, family=fam)


def make_pipeline_generate_moe_ep(cfg: GPTMoEConfig, mesh, *,
                                  max_new_tokens: int,
                                  temperature: float = 0.0,
                                  sample_top_k: Optional[int] = None,
                                  sample_top_p: Optional[float] = None,
                                  compute_dtype=None,
                                  stage_axis: Optional[str] = None,
                                  expert_axis: str = EXPERT_AXIS):
    """EP x PP 2D MoE decode: layers shard over the STAGE axis (the
    ppermute decode ring) while each stage's experts shard over the
    EXPERT axis — the 2D composition the dense-expert pipeline decoder
    leaves out.

    Mesh {stage: S, expert: n}: the batch and its KV cache shard over the
    expert axis (each expert column is a routing group, exactly the EP
    forward's layout), each stage column holds 1/S of the layers with 1/n
    of every layer's experts, tokens reach their experts via all_to_all
    WITHIN the stage row while the hidden state rides the stage ring —
    both collectives per decode step, each on its own mesh axis.

    generate(stage_blocks, aux, ids, rng): `stage_blocks` from
    prepare_pipeline_stacked (this function re-places the expert leaves
    over the expert axis); ids (B, T), B divisible by the expert axis.
    Greedy output equals make_generate_moe(groups=n) token-for-token
    (same per-column routing groups, same stage math).

    NOTE on the deliberate duplication: the stage-ring schedule below
    mirrors generate.make_pipeline_generate's (where-gated cache merge,
    ppermute hop, stage-0 psum token broadcast). It cannot ride that
    builder's family adapter because the EP FFN is capacity-dependent —
    a DIFFERENT compiled ffn for the prefill chunk vs the decode step —
    while the adapter protocol fixes one block function; and the 2D
    specs shard the batch/rng over a second axis the generic builder
    doesn't model. If the ring schedule in generate.py changes, change
    it here too (both are pinned by token-parity tests against the solo
    decoders, which is what actually catches drift).
    """
    from jax.sharding import NamedSharding

    from dnn_tpu.parallel.mesh import STAGE_AXIS
    from dnn_tpu.runtime.generate import _block_with_cache
    from dnn_tpu.runtime.paged_kvcache import scan_rows

    if max_new_tokens < 1:
        raise ValueError(f"max_new_tokens must be >= 1, got {max_new_tokens}")
    s_axis = stage_axis or STAGE_AXIS
    num_stages = mesh.shape[s_axis]
    n_exp = mesh.shape[expert_axis]
    if cfg.n_layer % num_stages:
        raise ValueError(
            f"n_layer {cfg.n_layer} not divisible by {num_stages} stages")
    if cfg.n_experts % n_exp:
        raise ValueError(
            f"n_experts {cfg.n_experts} not divisible by expert axis {n_exp}")
    per_stage = cfg.n_layer // num_stages
    perm = [(i, (i + 1) % num_stages) for i in range(num_stages)]

    # stage_blocks leaves carry (S, per_stage, ...); MoE expert stacks add
    # their E axis right after -> P(stage, None, expert); router + dense
    # block leaves replicate across expert columns
    def _spec(path, leaf):
        keys = [getattr(p, "key", None) for p in path]
        if "moe" in keys and "router" not in keys:
            return P(s_axis, None, expert_axis)
        return P(s_axis)

    def _place(stage_blocks):
        specs = jax.tree_util.tree_map_with_path(_spec, stage_blocks)
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
        cache = init_cache(
            _stage_cfg(cfg, per_stage), b, s_max,
            compute_dtype or jnp.float32)

        def ffn_for(tokens_per_group):
            capacity = moe_capacity(tokens_per_group, cfg.n_experts,
                                    cfg.top_k, cfg.capacity_factor)

            def ffn(bp, h):
                dd = h.shape[-1]
                return moe_ffn_local(
                    bp["moe"], h.reshape(-1, dd), top_k=cfg.top_k,
                    capacity=capacity, axis_name=expert_axis,
                    compute_dtype=compute_dtype,
                ).reshape(h.shape)

            return ffn

        def ring_pass(x, cache, start_pos, ffn):
            def sub(carry, s):
                h, cache = carry

                h2, cache2 = scan_rows(
                    lambda bp, x, rows: _block_with_cache(
                        bp, x, rows, start_pos, cfg=cfg,
                        compute_dtype=compute_dtype, ffn=ffn),
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
            logits = head(aux, h[:, -1:].astype(jnp.float32), cfg=cfg,
                          compute_dtype=compute_dtype)
            tok = _sample(logits[:, -1], sub_rng, temperature=temperature,
                          top_k=sample_top_k, top_p=sample_top_p)
            return lax.psum(
                jnp.where(d == 0, tok, jnp.zeros_like(tok)), s_axis)

        rng = jax.random.fold_in(rng, lax.axis_index(expert_axis))
        x = _embed_at(aux, ids_local, 0, compute_dtype=compute_dtype)
        h, cache = ring_pass(x, cache, 0, ffn_for(b * t))
        rng, sub = jax.random.split(rng)
        tok = sample_last(h, sub)
        step_ffn = ffn_for(b)

        def step(carry, i):
            cache, tok, rng = carry
            x = _embed_at(aux, tok[:, None], t + i,
                          compute_dtype=compute_dtype)
            h, cache = ring_pass(x, cache, t + i, step_ffn)
            rng, sub = jax.random.split(rng)
            nxt = sample_last(h, sub)
            return (cache, nxt, rng), tok

        (_, last, _), toks = lax.scan(
            step, (cache, tok, rng), jnp.arange(max_new_tokens - 1))
        toks = jnp.moveaxis(toks, 0, 1)
        return jnp.concatenate([toks, last[:, None]], axis=1)

    compiled = {}  # one jitted program per param-tree structure; repeat
    # calls with the same shapes reuse it (the make_* builder contract)

    def generate(stage_blocks, aux, ids, rng):
        b, t = ids.shape
        if b % n_exp:
            raise ValueError(
                f"batch {b} not divisible by expert-axis size {n_exp}")
        if t + max_new_tokens > cfg.block_size:
            raise ValueError(
                f"prompt {t} + max_new_tokens {max_new_tokens} exceeds "
                f"block_size {cfg.block_size}")
        # device_put is a no-op for already-correctly-placed arrays, so
        # long-lived callers that keep the returned placement pay it once
        placed, specs = _place(stage_blocks)
        key = jax.tree_util.tree_structure(stage_blocks)
        if key not in compiled:
            compiled[key] = jax.jit(jax.shard_map(
                per_device, mesh=mesh,
                in_specs=(specs, P(), P(expert_axis), P()),
                out_specs=P(expert_axis),
                check_vma=False,
            ))
        return compiled[key](placed, aux, ids, rng)

    return generate


def _stage_cfg(cfg, per_stage):
    import dataclasses

    return dataclasses.replace(cfg, n_layer=per_stage)


def make_generate_moe_ep(cfg: GPTMoEConfig, mesh, *, max_new_tokens: int,
                         temperature: float = 0.0,
                         sample_top_k: Optional[int] = None,
                         compute_dtype=None, axis_name: str = EXPERT_AXIS):
    """Expert-parallel KV-cache generation over `mesh`'s expert axis.

    generate(prepared, ids, rng): ids (B, T), B divisible by the axis
    size. Batch and KV cache shard over the axis; expert weights shard on
    E; tokens reach their experts via all_to_all inside every prefill and
    decode-step forward. Greedy output equals
    make_generate_moe(groups=axis_size) token-for-token.
    """
    if max_new_tokens < 1:
        raise ValueError(f"max_new_tokens must be >= 1, got {max_new_tokens}")
    n = mesh.shape[axis_name]
    if cfg.n_experts % n:
        raise ValueError(
            f"n_experts={cfg.n_experts} not divisible by axis size {n}")

    moe_spec = {"router": {"kernel": P()},
                "wi": P(None, axis_name), "bi": P(None, axis_name),
                "wo": P(None, axis_name), "bo": P(None, axis_name)}
    param_specs = {
        "wte": {"embedding": P()}, "wpe": {"embedding": P()},
        "ln_f": {"scale": P(), "bias": P()}, "lm_head": {"kernel": P()},
        "blocks": {
            "ln_1": {"scale": P(), "bias": P()},
            "attn": {"qkv": {"kernel": P(), "bias": P()},
                     "proj": {"kernel": P(), "bias": P()}},
            "ln_2": {"scale": P(), "bias": P()},
            "moe": moe_spec,
        },
    }

    def per_device(prep_local, ids_local, rng):
        b, t = ids_local.shape  # local batch = this device's routing group
        s_max = t + max_new_tokens
        cache = init_cache(cfg, b, s_max, compute_dtype or jnp.float32)

        def ffn_for(tokens_per_group):
            capacity = moe_capacity(
                tokens_per_group, cfg.n_experts, cfg.top_k,
                cfg.capacity_factor)

            def ffn(bp, h):
                d = h.shape[-1]
                return moe_ffn_local(
                    bp["moe"], h.reshape(-1, d), top_k=cfg.top_k,
                    capacity=capacity, axis_name=axis_name,
                    compute_dtype=compute_dtype,
                ).reshape(h.shape)

            return ffn

        logits, cache = forward_with_cache(
            prep_local, ids_local, cache, 0, cfg=cfg,
            compute_dtype=compute_dtype, ffn=ffn_for(b * t),
            attn_kernel=False)  # inside shard_map: keep the einsum
        # per-device stream: local rows sample locally (greedy ignores rng)
        rng = jax.random.fold_in(rng, lax.axis_index(axis_name))
        rng, sub = jax.random.split(rng)
        tok = _sample(logits[:, -1], sub, temperature=temperature,
                      top_k=sample_top_k)

        step_ffn = ffn_for(b)

        def step(carry, i):
            cache, tok, rng = carry
            logits, cache = forward_with_cache(
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
    def generate(prepared, ids, rng):
        b, t = ids.shape
        if b % n:
            raise ValueError(f"batch {b} not divisible by expert-axis size {n}")
        if t + max_new_tokens > cfg.block_size:
            raise ValueError(
                f"prompt {t} + max_new_tokens {max_new_tokens} exceeds "
                f"block_size {cfg.block_size}")
        return jax.shard_map(
            per_device, mesh=mesh,
            in_specs=(param_specs, P(axis_name), P()),
            out_specs=P(axis_name),
            check_vma=False,
        )(prepared, ids, rng)

    return generate
