"""Roofline shares of a model with latent attention (the JoyAI
configuration: `configs/joyai-llm-flash-ep16-1chip.json`), from the
configuration's widths, the program's `mla_*` / `moe_*` counters over the
window and the device time of its scopes on the capture.
`layers/<metric>.json` names these functions as `"mla_roofline:<function>"`.

Operations and bytes are the ALGORITHM's, computed from shapes, the SAME
work whatever implements it (w = the operand width of the served dtype,
2 B for bfloat16; H heads, r = kv_lora_rank, dn | dr = the key's nope |
rope parts, dv = the value's width):

  a cached position (a layer)     (r + dr) x w            (576 x 2 = 1152 B)
  decode, a cached position       H x 2 x ((r + dr) + r) FLOPs
                                  (a score over 576, a value of 512: 69 632)
  prefill, a (query, position)    H x 2 x ((dn + dr) + dv) FLOPs
                                  (a score over 192, a value of 128: 20 480)

A decode step reads every live position of every slot once a layer
(`mla_cached_positions_total`, summed over the layers by the counter):
least time = max(positions x 1152 B / peak bytes/s, positions x 69 632 /
peak FLOP/s) — 60 FLOP a byte against the chip's ridge of 240, so bandwidth
binds, by a factor of four only. A prefill chunk scores its causal pairs
(`mla_query_pairs_total`) and is bound by their FLOPs, priced in the
up-projected form, the cheaper one; what up-projecting (or absorbing)
costs on top is the implementation's price for a latent cache, lies under
the scopes the share divides by, and reads as distance from the roofline.
So does the pool's storing 576 values in 640 lanes.

`decode_step_roofline_pct` is the whole step as `keye_roofline.
decode_step_roofline_pct` builds it: the parameters a step must stream —
per layer attention, in an expert layer the shared expert, the float32
router and the held experts that had a row (`moe_active_experts_total`
over `moe_layer_calls_total`, both over EXPERT layers), in a leading dense
layer its MLP, once the head — plus the cached positions' bytes, over the
step's mean device time. It leaves a `note` row naming the bound.

A reader returns None where what it reads is not there (a program without
the counters or the scopes), and the harness leaves the metric out.
"""

from __future__ import annotations

from typing import Optional

from chipbench import scopes as sc
from chipbench import spans

__all__ = ["attn_roofline_pct", "decode_step_roofline_pct"]


def _widths(config: dict) -> dict:
    w = sc.OPERAND_BYTES[config["run"]["dtype"]]
    c, heads = config["hidden_size"], config["num_attention_heads"]
    q_rank, r = config["q_lora_rank"], config["kv_lora_rank"]
    dn, dr, dv = (config["qk_nope_head_dim"], config["qk_rope_head_dim"],
                  config["v_head_dim"])
    f = config["moe_intermediate_size"]
    dense = config["first_k_dense_replace"]
    return {
        "w": w, "row_bytes": (r + dr) * w,
        "decode_flops": heads * 2 * ((r + dr) + r),
        "pair_flops": heads * 2 * ((dn + dr) + dv),
        "layers": config["num_hidden_layers"], "dense_layers": dense,
        "expert_layers": config["num_hidden_layers"] - dense,
        # parameters: a layer's attention, the shared expert(s), the
        # router (every expert of the layer), one expert, a leading dense
        # layer's MLP, the head
        "attn_params": (c * q_rank + q_rank * heads * (dn + dr)
                        + c * (r + dr) + r * heads * (dn + dv)
                        + heads * dv * c),
        "shared_params": config["n_shared_experts"] * 3 * c * f,
        "router_params": c * config["published"]["router_outputs"],
        "expert_params": 3 * c * f,
        "dense_params": 3 * c * config["intermediate_size"],
        "head_params": c * config["vocab_size"],
    }


def _per_call(facts, label: str) -> Optional[dict]:
    """Window means per execution of the program `label`: cached positions
    read and causal pairs scored (both summed over its layers)."""
    d = sc._deltas(facts, [f'mla_{n}{{program="{label}"}}' for n in
                               ("layer_calls_total",
                                "cached_positions_total",
                                "query_pairs_total")])
    if d is None or not d[0]:
        return None
    calls = d[0] / facts["config"]["num_hidden_layers"]
    return {"calls": calls, "cached": d[1] / calls, "pairs": d[2] / calls}


def _attn_least(facts, label: str) -> Optional[dict]:
    """The least time of one execution's latent attention, all layers."""
    per, peaks = _per_call(facts, label), facts.get("peaks")
    if per is None or not peaks:
        return None
    x = _widths(facts["config"])
    nbytes = per["cached"] * x["row_bytes"]
    flops = (per["cached"] * x["decode_flops"] if label == "decode"
             else per["pairs"] * x["pair_flops"])
    t_flops = flops / peaks["bf16_flops_per_s"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return {"least_s": max(t_flops, t_bytes), "flops": flops,
            "bytes": nbytes, **per,
            "bound": "bandwidth" if t_bytes >= t_flops else "compute"}


def attn_roofline_pct(facts, *, program: str, inside: str, label: str,
                      scopes: list) -> Optional[float]:
    """Least time of the latent attention of one execution of `program`
    (`label` "decode": a step's reads; "prefill": a chunk's causal pairs)
    over the device time under the `scopes` prefixes inside it."""
    least = _attn_least(facts, label)
    cap, t = sc._capture_of(facts), facts.get("trace")
    if least is None or cap is None or not t or program not in t["programs"]:
        return None
    # an operation counts if a component of its op_name starts with one of
    # `scopes` (the kernel under `attn.mla_decode` is named
    # `attn.paged_decode` innermost)
    spent = sum(v for k, v in sc.scope_seconds(
        cap, scopes, inside=inside).items() if k is not None)
    if not spent:
        return None
    per_call_ms = 1e3 * spent / t["programs"][program]["count"]
    facts.setdefault("notes", []).append(
        {"roofline": f"{program}: latent attention", "bound": least["bound"],
         "least_ms": 1e3 * least["least_s"], "spent_ms": per_call_ms,
         "bytes": least["bytes"], "flops": least["flops"],
         "cached_positions": least["cached"], "query_pairs": least["pairs"]})
    return 100.0 * 1e3 * least["least_s"] / per_call_ms


def decode_step_roofline_pct(facts, *, program: str) -> Optional[float]:
    """Least time of a whole decode step (module docstring) over its mean
    device time."""
    t, peaks = facts.get("trace"), facts.get("peaks")
    attn = _attn_least(facts, "decode")
    active = sc.counter_ratio(
        facts, num='moe_active_experts_total{program="decode"}',
        den='moe_layer_calls_total{program="decode"}')
    rows = sc.counter_ratio(
        facts, num='moe_assignments_total{program="decode"}',
        den='moe_layer_calls_total{program="decode"}')
    tokens = spans.occupancy_win_pct(facts)
    if (not t or not peaks or program not in t["programs"] or attn is None
            or active is None or rows is None or tokens is None):
        return None
    config = facts["config"]
    x = _widths(config)
    tokens = tokens / 100.0 * config["run"]["serve_flags"]["slots"]
    dense, experts = x["dense_layers"], x["expert_layers"]
    params = (x["layers"] * x["attn_params"] + dense * x["dense_params"]
              + experts * (x["shared_params"] + active * x["expert_params"])
              + x["head_params"])
    nbytes = (params * x["w"] + experts * x["router_params"] * 4
              + attn["bytes"])
    flops = (2 * tokens * (x["layers"] * x["attn_params"]
                           + dense * x["dense_params"]
                           + experts * (x["shared_params"]
                                        + x["router_params"])
                           + x["head_params"])
             + 2 * experts * rows * x["expert_params"] + attn["flops"])
    t_flops = flops / peaks["bf16_flops_per_s"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    least = max(t_flops, t_bytes)
    step_ms = t["programs"][program]["mean_ms"]
    facts.setdefault("notes", []).append(
        {"roofline": program,
         "bound": "bandwidth" if t_bytes >= t_flops else "compute",
         "least_ms": 1e3 * least, "step_ms": step_ms, "bytes": nbytes,
         "flops": flops, "streamed_params": params,
         "cached_position_bytes": attn["bytes"],
         "active_held_experts_per_layer": active})
    return 100.0 * 1e3 * least / step_ms
