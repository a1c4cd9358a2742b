"""GPT with Mixture-of-Experts FFNs — the expert-parallel model family.

No counterpart exists in the reference (SURVEY.md §2: "no MoE modules
exist"); this family extends the GPT-2 re-authoring (models/gpt.py, built
because the reference's `model.py` is absent — gpt_model_parts.py:4) with
sparse FFNs:

  * every block's dense MLP is replaced by a top-k routed MoE FFN
    (dnn_tpu/parallel/moe.py) — attention, embeddings, and the LM head are
    exactly GPT-2's;
  * dense path routes in `groups` so it equals the expert-parallel path
    bit-for-bit at groups == n_devices;
  * `make_apply_ep(cfg, mesh)` runs the whole forward under `shard_map`
    with the batch sharded over the "expert" mesh axis (dp and ep share
    the axis): attention/embed/head compute on local batches, expert
    weights live sharded P("expert"), and tokens reach their experts via
    `jax.lax.all_to_all` — the EP row of the parallelism table;
  * pipeline partitioning reuses gpt.layer_ranges, so the family also
    stages across the "stage" axis like its dense sibling.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from dnn_tpu.models import gpt
from dnn_tpu.ops.attention import causal_self_attention
from dnn_tpu.ops.nn import layer_norm
from dnn_tpu.parallel.mesh import EXPERT_AXIS
from dnn_tpu.parallel.moe import (
    init_moe,
    moe_capacity,
    moe_ffn,
    moe_ffn_grouped,
    moe_ffn_local,
)
from dnn_tpu.registry import ModelSpec, StageSpec, register_model


@dataclasses.dataclass(frozen=True)
class GPTMoEConfig(gpt.GPTConfig):
    n_experts: int = 8
    top_k: int = 2
    capacity_factor: float = 1.25
    d_ff: int = 0  # 0 = 4 * n_embd (per expert)

    @property
    def ff_dim(self):
        return self.d_ff or 4 * self.n_embd


PRESETS = {
    # 8-expert small model: ~2x the active FLOPs of gpt2-small's MLP budget
    # spread over 8x the MLP params — the classic sparse-scaling shape
    "gpt2-moe": GPTMoEConfig(n_layer=12, n_head=12, n_embd=768, n_experts=8),
    # tiny config for tests / CPU-mesh CI (experts divisible by 2 and 4)
    "gpt2-moe-test": GPTMoEConfig(block_size=64, vocab_size=256, n_layer=2,
                                  n_head=4, n_embd=32, n_experts=4, d_ff=64),
}


def init_block(key, cfg: GPTMoEConfig, dtype=jnp.float32):
    c = cfg.n_embd
    ks = jax.random.split(key, 3)
    proj_std = 0.02 / (2 * cfg.n_layer) ** 0.5
    return {
        "ln_1": {"scale": jnp.ones((c,), dtype), "bias": jnp.zeros((c,), dtype)},
        "attn": {
            "qkv": {"kernel": (jax.random.normal(ks[0], (c, 3 * c)) * 0.02).astype(dtype),
                    "bias": jnp.zeros((3 * c,), dtype)},
            "proj": {"kernel": (jax.random.normal(ks[1], (c, c)) * proj_std).astype(dtype),
                     "bias": jnp.zeros((c,), dtype)},
        },
        "ln_2": {"scale": jnp.ones((c,), dtype), "bias": jnp.zeros((c,), dtype)},
        "moe": init_moe(ks[2], c, cfg.n_experts, cfg.ff_dim, dtype),
    }


def init(rng, cfg: GPTMoEConfig = PRESETS["gpt2-moe"], dtype=jnp.float32):
    keys = jax.random.split(rng, cfg.n_layer + 3)
    c = cfg.n_embd
    params = {
        "wte": {"embedding": (jax.random.normal(keys[0], (cfg.vocab_size, c)) * 0.02).astype(dtype)},
        "wpe": {"embedding": (jax.random.normal(keys[1], (cfg.block_size, c)) * 0.01).astype(dtype)},
        "ln_f": {"scale": jnp.ones((c,), dtype), "bias": jnp.zeros((c,), dtype)},
    }
    for i in range(cfg.n_layer):
        params[f"h_{i}"] = init_block(keys[2 + i], cfg, dtype)
    params["lm_head"] = {"kernel": params["wte"]["embedding"].T}
    return params


def _block_core(block_params, x, ffn_fn, *, cfg: GPTMoEConfig, compute_dtype=None):
    """Pre-LN block: causal MHA + a pluggable FFN (dense-routed or
    expert-parallel), both residual. ONE definition for both execution
    paths — the dense==EP parity invariant depends on them never
    diverging."""
    h = layer_norm(block_params["ln_1"], x, eps=cfg.ln_eps)
    x = x + causal_self_attention(
        block_params["attn"], h, n_head=cfg.n_head, compute_dtype=compute_dtype
    )
    h = layer_norm(block_params["ln_2"], x, eps=cfg.ln_eps)
    m = ffn_fn(block_params["moe"], h)
    return x + m.astype(x.dtype)


def block_apply(block_params, x, *, cfg: GPTMoEConfig, groups: int = 1,
                compute_dtype=None):
    """Single-device block: drop-free grouped experts (parallel/moe.
    moe_ffn_grouped; no capacity). `groups` > 1 is the EP path's dense
    twin — static capacity per routing group — for the parity tests."""
    if groups == 1:
        def ffn(mp, h):
            return moe_ffn_grouped(mp, h, top_k=cfg.top_k,
                                   compute_dtype=compute_dtype)
    else:
        def ffn(mp, h):
            return moe_ffn(
                mp, h, top_k=cfg.top_k, capacity_factor=cfg.capacity_factor,
                groups=groups, compute_dtype=compute_dtype)
    return _block_core(block_params, x, ffn, cfg=cfg,
                       compute_dtype=compute_dtype)


def _blocks_scan(stacked, x, *, cfg, groups, compute_dtype):
    def body(carry, layer_params):
        return block_apply(layer_params, carry, cfg=cfg, groups=groups,
                           compute_dtype=compute_dtype), None

    out, _ = jax.lax.scan(body, x, stacked)
    return out


def make_apply(cfg: GPTMoEConfig, *, groups: int = 1, compute_dtype=None):
    """Dense (single-program) forward. `groups` sets the routing-group
    count; groups == n matches an n-device EP run exactly."""

    def apply(params, idx):
        x = gpt.embed(params, idx, cfg=cfg)
        if compute_dtype is not None:
            x = x.astype(compute_dtype)
        stacked = gpt.stack_blocks(params, range(cfg.n_layer))
        x = _blocks_scan(stacked, x, cfg=cfg, groups=groups,
                         compute_dtype=compute_dtype)
        return gpt.head(params, x.astype(jnp.float32), cfg=cfg,
                        compute_dtype=compute_dtype)

    return apply


def make_apply_ep(cfg: GPTMoEConfig, mesh, *, axis_name: str = EXPERT_AXIS,
                  compute_dtype=None):
    """Expert-parallel forward over `mesh`'s expert axis.

    apply(params, ids): ids (B, T), B divisible by the axis size. The batch
    shards over the expert axis (each device's local batch = its routing
    group); per-block expert weights shard on their E axis; everything else
    replicates. Logits come back sharded over the batch.

    `params` may be the raw per-layer pytree ({"h_0"...}) or the stacked
    form from `gpt.prepare_stacked(params, cfg)` (a {"blocks": ...} key).
    Long-lived callers should prepare ONCE at load time — restacking
    inside a jitted step is an O(params) copy per call (the same contract
    as the dense family's prepare_stacked)."""
    n = mesh.shape[axis_name]
    if cfg.n_experts % n:
        raise ValueError(f"n_experts={cfg.n_experts} not divisible by axis size {n}")

    def _spec_for(path, leaf):
        # derived from the ACTUAL pytree (same approach as
        # llama_moe.make_apply_ep), so int8-quantized trees — expert
        # *_scale leaves, {q, scale} attention linears — shard correctly
        # instead of tripping a hardcoded-structure mismatch. Only the
        # expert stacks shard (stacked blocks carry a leading L, so E is
        # axis 1); the router and everything else replicate.
        keys = [p.key for p in path if hasattr(p, "key")]
        if "moe" in keys and keys and keys[-1] in (
                "wi", "wo", "bi", "bo", "wi_scale", "wo_scale"):
            return P(None, axis_name)
        return P()

    def local_fn(prep_local, ids_local):
        x = gpt.embed(prep_local, ids_local, cfg=cfg)
        if compute_dtype is not None:
            x = x.astype(compute_dtype)

        b_local, t = ids_local.shape
        s = b_local * t  # this device's tokens = one routing group
        capacity = moe_capacity(s, cfg.n_experts, cfg.top_k, cfg.capacity_factor)

        def ep_ffn(mp, h):
            d = h.shape[-1]
            return moe_ffn_local(
                mp, h.reshape(-1, d), top_k=cfg.top_k, capacity=capacity,
                axis_name=axis_name, compute_dtype=compute_dtype,
            ).reshape(h.shape)

        def body(carry, layer_params):
            return _block_core(layer_params, carry, ep_ffn, cfg=cfg,
                               compute_dtype=compute_dtype), None

        x, _ = jax.lax.scan(body, x, prep_local["blocks"])
        return gpt.head(prep_local, x.astype(jnp.float32), cfg=cfg,
                        compute_dtype=compute_dtype)

    def apply(params, ids):
        b = ids.shape[0]
        if b % n:
            raise ValueError(f"batch {b} not divisible by expert-axis size {n}")
        if "blocks" in params:
            prepared = params
        else:
            prepared = {k: v for k, v in params.items() if not k.startswith("h_")}
            prepared["blocks"] = gpt.stack_blocks(params, range(cfg.n_layer))
        param_specs = jax.tree_util.tree_map_with_path(_spec_for, prepared)
        return jax.shard_map(
            local_fn, mesh=mesh,
            in_specs=(param_specs, P(axis_name)),
            out_specs=P(axis_name),
            check_vma=False,
        )(prepared, ids)

    return apply


# --------------------------------------------------------------------------
# registration (pipeline partitioning reuses gpt.layer_ranges)
# --------------------------------------------------------------------------

def make_partition(cfg: GPTMoEConfig, *, compute_dtype=None):
    """Pipeline stages over layer ranges (the dense family's layout).

    NOTE: under a MICROBATCHED pipeline each microbatch is its own routing
    group (the MoE FFN routes over whatever batch it sees), so outputs
    differ from the whole-batch forward — not an error, the standard
    batch-dependence of capacity-based MoE. Exact parity with the dense
    forward needs microbatches=1 (or dense groups == microbatches)."""
    def partition(num_parts):
        ranges = gpt.layer_ranges(cfg.n_layer, num_parts)
        stages = []
        for p, (lo, hi) in enumerate(ranges):
            is_first, is_last = p == 0, p == num_parts - 1
            param_keys = tuple(f"h_{i}" for i in range(lo, hi))
            if is_first:
                param_keys = ("wte", "wpe") + param_keys
            if is_last:
                param_keys = param_keys + ("ln_f", "lm_head")

            def stage_fn(params, x, _lo=lo, _hi=hi, _first=is_first, _last=is_last):
                if _first:
                    x = gpt.embed(params, x, cfg=cfg)
                if compute_dtype is not None and jnp.issubdtype(x.dtype, jnp.floating):
                    x = x.astype(compute_dtype)
                if _hi > _lo:
                    stacked = gpt.stack_blocks(params, range(_lo, _hi))
                    x = _blocks_scan(stacked, x, cfg=cfg, groups=1,
                                     compute_dtype=compute_dtype)
                if _last:
                    x = gpt.head(params, x.astype(jnp.float32), cfg=cfg,
                                 compute_dtype=compute_dtype)
                return x

            stages.append(StageSpec(
                name=f"moe_blocks[{lo}:{hi}]"
                + ("+embed" if is_first else "") + ("+head" if is_last else ""),
                apply=stage_fn,
                param_keys=param_keys,
            ))
        return stages

    return partition


def _register(name: str, cfg: GPTMoEConfig):
    register_model(ModelSpec(
        name=name,
        init=lambda rng, dtype=jnp.float32, _cfg=cfg: init(rng, _cfg, dtype),
        apply=make_apply(cfg),
        partition=make_partition(cfg),
        example_input=gpt.make_example_input(cfg),
        supported_parts=tuple(range(1, cfg.n_layer + 1)),
        config=cfg,
        extras={
            "make_apply": lambda compute_dtype=None, **_kw: make_apply(
                cfg, compute_dtype=compute_dtype
            ),
            "make_partition": lambda compute_dtype=None, **_kw: make_partition(
                cfg, compute_dtype=compute_dtype
            ),
            "make_apply_ep": lambda mesh, compute_dtype=None: make_apply_ep(
                cfg, mesh, compute_dtype=compute_dtype
            ),
        },
    ))


for _name, _cfg in PRESETS.items():
    _register(_name, _cfg)
