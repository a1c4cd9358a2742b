"""Roofline shares of a model whose layers are of two KINDS over K and V
leaves — "full" layers that keep every position and "window" layers that
keep the last W — the K-EXAONE configuration
(`configs/k-exaone-236b-a23b-ep8-1chip.json`), from the configuration's
widths, the program's `attn_*` / `moe_*` / `kv_pool_*` counters over the
window and the device time of its scopes on the capture.
`layers/<metric>.json` names these functions as
`"exaone_roofline:<function>"`.

Operations and bytes are the ALGORITHM's, computed from shapes, the same
work whatever implements it (w = 2 B for bfloat16; H query heads and KV
heads of D):

  reading a cached position, a layer      KV x D x 2 (K and V) x w bytes
                                          (8 x 128 x 2 x 2 = 4096 B); a
                                          decode query pays H x 4 x D
                                          FLOPs for it (32 768)
  a chunk's (query, position) pair        H x 4 x D FLOPs (64 x 4 x 128)

The counters count what the algorithm needs:
`attn_cached_positions_read_total{kind, program}` — a decode step's pos +
1 a full layer and min(pos + 1, W) a window layer, a chunk's causal pairs
and its pairs within the band — summed over the kind's layers. That the
window's 128 positions arrive as nine blocks of 16, that the decode
kernel walks whole groups of blocks, that a chunk's column tiles straddle
the band, are the implementation's: they lie under the scopes a share
divides by and read as distance from the roofline, so no later kernel can
read over 100 %.

`decode_step_roofline_pct` is the whole step: the parameters it must
stream (attention in every layer, layer 0's dense MLP, in an expert layer
the shared expert, the float32 router and the held experts that had a
row, once the head over the vocabulary rows held) plus the cached
positions read, over the step's mean device time.
`experts_roofline_pct` is `scopes.experts_roofline_pct` with the steps
counted from the EXPERT layers (that reader divides the expert layers'
calls by `num_hidden_layers` and so misreads a model with a dense layer
0) and the expert's width read from `moe_intermediate_size`.

A reader returns None where what it reads is not there (a program without
the counters or the scopes), and the harness leaves the metric out.
"""

from __future__ import annotations

from typing import Optional

from chipbench import hosttime, spans
from chipbench import scopes as sc

__all__ = ["kind_roofline_pct", "decode_step_roofline_pct",
           "experts_roofline_pct", "window_roll_ms_per_step",
           "full_cache_read_share"]


def _widths(config: dict) -> dict:
    w = sc.OPERAND_BYTES[config["run"]["dtype"]]
    c, d = config["hidden_size"], config["head_dim"]
    h, kv = config["num_attention_heads"], config["num_key_value_heads"]
    f = config["moe_intermediate_size"]
    dense = config["first_k_dense_replace"]
    types = config["layer_types"]
    n_full = sum(t == "full_attention" for t in types)
    return {
        "w": w,
        "layers": {"full": n_full, "window": len(types) - n_full},
        "row_bytes": kv * d * 2 * w,        # K and V of one position
        "pair_flops": h * 4 * d,            # q . k and p . v, every head
        # W_q, W_o (C x H D each) and W_k, W_v (C x KV D each)
        "attn_params": 2 * c * h * d + 2 * c * kv * d,
        "dense_layers": dense, "expert_layers": len(types) - dense,
        "shared_params": config["num_shared_experts"] * 3 * c * f,
        "router_params": c * config["published"]["router_outputs"],
        "expert_params": 3 * c * f,
        "dense_params": 3 * c * config["intermediate_size"],
        "head_params": c * config["vocab_size"],
    }


def _read(facts, label: str, kind: str) -> Optional[dict]:
    """Window means per execution of the program `label`: the positions
    (decode) or pairs (prefill) the layers of `kind` read, summed over
    them; the executions are the expert layers' calls of the program over
    the expert layers."""
    got = sc._deltas(facts, [
        f'attn_cached_positions_read_total{{kind="{kind}",'
        f'program="{label}"}}',
        f'moe_layer_calls_total{{program="{label}"}}'])
    if got is None:
        return None
    calls = got[1] / _widths(facts["config"])["expert_layers"]
    if not calls:
        return None
    return {"calls": calls, "read": got[0] / calls}


def _least(facts, label: str, kind: str) -> Optional[dict]:
    """The least time of one execution's reads of `kind`'s layers."""
    per, peaks = _read(facts, label, kind), facts.get("peaks")
    if per is None or not peaks:
        return None
    x = _widths(facts["config"])
    flops = per["read"] * x["pair_flops"]
    if label == "decode":
        nbytes = per["read"] * x["row_bytes"]
    else:
        # a chunk's queries share what they read: each cached row is read
        # once a layer for the chunk, not once a query. Rows a layer:
        # pairs / T for the full kind (mean context), window + T at most
        t = facts["config"]["run"]["serve_flags"]["prompt_pad"]
        rows = per["read"] / x["layers"][kind] / t + t / 2
        if kind == "window":
            rows = min(rows, facts["config"]["sliding_window"] + t)
        nbytes = x["layers"][kind] * rows * x["row_bytes"]
    t_flops = flops / peaks["bf16_flops_per_s"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return {"least_s": max(t_flops, t_bytes), "flops": flops,
            "bytes": nbytes, **per,
            "bound": "bandwidth" if t_bytes >= t_flops else "compute"}


def kind_roofline_pct(facts, *, program: str, inside: str, label: str,
                      kind: str, scopes: list) -> Optional[float]:
    """Least time of the reads of `kind`'s layers in one execution of
    `program` over the device time under the `scopes` prefixes inside it
    (op_name prefix `inside`), per execution of it in the capture."""
    least = _least(facts, label, kind)
    cap, t = sc._capture_of(facts), facts.get("trace")
    if least is None or cap is None or not t or program not in t["programs"]:
        return None
    spent = sum(v for k, v in sc.scope_seconds(
        cap, scopes, inside=inside).items() if k is not None)
    if not spent or not least["least_s"]:
        return None
    per_call_ms = 1e3 * spent / t["programs"][program]["count"]
    facts.setdefault("notes", []).append(
        {"roofline": f"{program}: {kind}", "bound": least["bound"],
         "least_ms": 1e3 * least["least_s"], "spent_ms": per_call_ms,
         "bytes": least["bytes"], "flops": least["flops"],
         "read_per_call": least["read"]})
    return 100.0 * 1e3 * least["least_s"] / per_call_ms


def _step(facts) -> Optional[dict]:
    """A decode step's streamed parameters, cached bytes and FLOPs (window
    means), or None."""
    full, window = _least(facts, "decode", "full"), _least(
        facts, "decode", "window")
    active = sc.counter_ratio(
        facts, num='moe_active_experts_total{program="decode"}',
        den='moe_layer_calls_total{program="decode"}')
    rows = sc.counter_ratio(
        facts, num='moe_assignments_total{program="decode"}',
        den='moe_layer_calls_total{program="decode"}')
    tokens = spans.occupancy_win_pct(facts)
    if None in (full, window, active, rows, tokens):
        return None
    config = facts["config"]
    x = _widths(config)
    tokens = tokens / 100.0 * config["run"]["serve_flags"]["slots"]
    dense, experts = x["dense_layers"], x["expert_layers"]
    attn = (dense + experts) * x["attn_params"]
    params = (attn + dense * x["dense_params"]
              + experts * (x["shared_params"] + active * x["expert_params"])
              + x["head_params"])
    cache_bytes = full["bytes"] + window["bytes"]
    weight_bytes = params * x["w"] + experts * x["router_params"] * 4
    flops = (2 * tokens * (attn + dense * x["dense_params"]
                           + experts * (x["shared_params"]
                                        + x["router_params"])
                           + x["head_params"])
             + 2 * experts * rows * x["expert_params"]
             + full["flops"] + window["flops"])
    return {"params": params, "weight_bytes": weight_bytes,
            "cache_bytes": cache_bytes, "full_bytes": full["bytes"],
            "flops": flops, "active": active}


def decode_step_roofline_pct(facts, *, program: str) -> Optional[float]:
    """Least time of a whole decode step (module docstring) over its mean
    device time."""
    t, peaks, step = facts.get("trace"), facts.get("peaks"), _step(facts)
    if not t or not peaks or step is None or program not in t["programs"]:
        return None
    nbytes = step["weight_bytes"] + step["cache_bytes"]
    t_flops = step["flops"] / peaks["bf16_flops_per_s"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    least = max(t_flops, t_bytes)
    step_ms = t["programs"][program]["mean_ms"]
    facts.setdefault("notes", []).append(
        {"roofline": program,
         "bound": "bandwidth" if t_bytes >= t_flops else "compute",
         "least_ms": 1e3 * least, "step_ms": step_ms, "bytes": nbytes,
         "flops": step["flops"], "streamed_params": step["params"],
         "cached_position_bytes": step["cache_bytes"],
         "active_held_experts_per_layer": step["active"]})
    return 100.0 * 1e3 * least / step_ms


def full_cache_read_share(facts) -> Optional[float]:
    """Of the bytes a decode step must move — cached positions and
    streamed weights — the share that is the FULL kind's cached
    positions: a property of the traffic (how long the contexts are)."""
    step = _step(facts)
    if step is None:
        return None
    return step["full_bytes"] / (step["weight_bytes"] + step["cache_bytes"])


def experts_roofline_pct(facts, *, program: str, inside: str, scope: str,
                         label: str) -> Optional[float]:
    """`scopes.experts_roofline_pct` for a model with a dense layer 0: the
    executions are the expert layers' calls over the EXPERT layers, the
    expert's width `moe_intermediate_size`."""
    cap, t, peaks = sc._capture_of(facts), facts.get("trace"), facts.get(
        "peaks")
    known = sc.known_scopes(facts)
    if cap is None or not known or not t or not peaks \
            or program not in t["programs"]:
        return None
    d = sc._deltas(facts, [f'moe_{name}{{program="{label}"}}' for name in
                           ("layer_calls_total", "assignments_total",
                            "active_experts_total")])
    if d is None or not d[0]:
        return None
    config = facts["config"]
    steps = d[0] / _widths(config)["expert_layers"]
    least = sc.experts_least_s(
        {**config, "intermediate_size": config["moe_intermediate_size"]},
        rows=d[1] / steps, active_experts=d[2] / steps, peaks=peaks)
    secs = sc.scope_seconds(cap, known, inside=inside)
    spent = sum(v for k, v in secs.items()
                if k is not None and k.startswith(scope))
    if not spent:
        return None
    per_step = spent / t["programs"][program]["count"]
    facts.setdefault("notes", []).append(
        {"roofline": f"{program}: experts", "bound": least["bound"],
         "least_ms": 1e3 * least["least_s"], "spent_ms": 1e3 * per_step,
         "rows_per_step": d[1] / steps, "active_per_step": d[2] / steps})
    return 100.0 * least["least_s"] / per_step


def window_roll_ms_per_step(facts, *, under: str) -> Optional[float]:
    """The worker's time under the spans named `under` (a window kind's
    blocks handed back and drawn, its table edits flushed) a `step` span
    of the capture."""
    cap = hosttime.capture_of(facts)
    if cap is None:
        return None
    mine = [s for s in cap["spans"] if s[0] == under]
    steps = sum(1 for s in cap["spans"] if s[0] == "step")
    if not mine or not steps:
        return None
    return sum(s[2] for s in mine) / 1e6 / steps
