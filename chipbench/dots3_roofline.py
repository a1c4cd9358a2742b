"""Roofline shares of a model whose layers are of two KINDS — latent
attention under an indexer's selection ("full") and latent attention of
other widths under a sliding window ("window") — the dots3 configuration
(`configs/dots3-note-prev-ep8-1chip.json`), from the configuration's
widths, the program's `dsa_*` / `mla_*` / `moe_*` counters over the window
and the device time of its scopes on the capture. `layers/<metric>.json`
names these functions as `"dots3_roofline:<function>"`.

Operations and bytes are the ALGORITHM's, computed from shapes, the same
work whatever implements it (w = 2 B for bfloat16; a kind's H heads, r its
latent, dn | dr its key's parts, dv its value; Hi index heads of Di):

  full, scoring a live position     Di x w read, 2 x Hi x Di FLOPs
                                    (256 B, 16 384 FLOPs)
  full, reading a SELECTED position (r + dr) x w read (1152 B); decode
                                    H x 2 x ((r + dr) + r) FLOPs (278 528),
                                    prefill H x 2 x ((dn + dr) + dv) (81 920)
  window, reading a position IN THE WINDOW
                                    (r_w + dr) x w read (2176 B); decode
                                    H_w x 2 x ((r_w + dr) + r_w) (270 336),
                                    prefill H_w x 2 x ((dn_w + dr) + dv)
                                    (49 152)

The counters count what the algorithm needs: `dsa_candidate_positions`
(every live position is scored), `dsa_selected_positions` (min(position +
1, 2048) a query) and `mla_cached_positions_read_total{kind="window"}`
(min(position + 1, 513) a query), each summed over the kind's layers.
That today's decode kernel walks every live block of a full layer and
masks, that the window's 513 are gathered as 34 blocks of 16, that a
latent of 576 is stored 640 lanes wide, and what a chunk's up-projection
costs, are the implementation's: they lie under the scopes a share
divides by and read as distance from the roofline, so no later kernel
can read over 100 %.

`decode_step_roofline_pct` is the whole step: the parameters it must
stream (attention and indexer by kind, the leading dense MLP, in an expert
layer the shared expert, the float32 router and the held experts that had
a row, once the head over the vocabulary rows held) plus the three
position terms, over the step's mean device time.

A reader returns None where what it reads is not there (a program without
the counters or the scopes), and the harness leaves the metric out.
"""

from __future__ import annotations

from typing import Optional

from chipbench import scopes as sc
from chipbench import spans

__all__ = ["kind_roofline_pct", "decode_step_roofline_pct",
           "window_blocks_share",
           "window_blocks_freed_per_step"]


def _widths(config: dict) -> dict:
    w = sc.OPERAND_BYTES[config["run"]["dtype"]]
    c = config["hidden_size"]
    types = config["layer_types"]
    dense = config["first_k_dense_replace"]
    f = config["moe_intermediate_size"]

    def kind(prefix, heads):
        r_q, r = config[prefix + "q_lora_rank"], config[prefix + "kv_lora_rank"]
        dn, dr = (config[prefix + "qk_nope_head_dim"],
                  config[prefix + "qk_rope_head_dim"])
        dv = config[prefix + "v_head_dim"]
        return {
            "row_bytes": (r + dr) * w,
            "decode_flops": heads * 2 * ((r + dr) + r),
            "pair_flops": heads * 2 * ((dn + dr) + dv),
            # W_qa, W_qb, W_kva, W_kvb, W_o and the head-wise gate
            "attn_params": (c * r_q + r_q * heads * (dn + dr) + c * (r + dr)
                            + r * heads * (dn + dv) + heads * dv * c
                            + c * heads)}

    hi, di = config["index_n_heads"], config["index_head_dim"]
    full = kind("", config["num_attention_heads"])
    full["layers"] = sum(t == "full_attention" for t in types)
    win = kind("swa_", config["swa_num_attention_heads"])
    win["layers"] = len(types) - full["layers"]
    return {
        "w": w, "full": full, "window": win,
        "index_key_bytes": di * w, "score_flops": 2 * hi * di,
        "index_params": config["q_lora_rank"] * hi * di + c * di + c * hi,
        "dense_layers": dense, "expert_layers": len(types) - dense,
        "shared_params": config["n_shared_experts"] * 3 * c * f,
        "router_params": c * config["published"]["router_outputs"],
        "expert_params": 3 * c * f,
        "dense_params": 3 * c * config["intermediate_size"],
        "head_params": c * config["vocab_size"],
    }


def _per_call(facts, label: str) -> Optional[dict]:
    """Window means per execution of the program `label`: candidate and
    selected positions (the full kind's layers) and the positions in the
    window (the window kind's), each summed over its layers."""
    d = sc._deltas(facts, [
        f'dsa_layer_calls_total{{program="{label}"}}',
        f'dsa_candidate_positions_total{{program="{label}"}}',
        f'dsa_selected_positions_total{{program="{label}"}}',
        f'mla_cached_positions_read_total{{kind="window",'
        f'program="{label}"}}'])
    if d is None or not d[0]:
        return None
    x = _widths(facts["config"])
    calls = d[0] / x["full"]["layers"]
    return {"calls": calls, "candidates": d[1] / calls,
            "selected": d[2] / calls, "in_window": d[3] / calls}


def _least(facts, label: str, part: str) -> Optional[dict]:
    """The least time of one execution's `part`: "sparse" (the full
    kind's scoring and reading), "index" (its scoring alone) or "window"
    (the window kind's reading)."""
    per, peaks = _per_call(facts, label), facts.get("peaks")
    if per is None or not peaks:
        return None
    x = _widths(facts["config"])
    read = "decode_flops" if label == "decode" else "pair_flops"
    nbytes = flops = 0.0
    if part in ("sparse", "index"):
        nbytes += per["candidates"] * x["index_key_bytes"]
        flops += per["candidates"] * x["score_flops"]
    if part == "sparse":
        nbytes += per["selected"] * x["full"]["row_bytes"]
        flops += per["selected"] * x["full"][read]
    if part == "window":
        nbytes += per["in_window"] * x["window"]["row_bytes"]
        flops += per["in_window"] * x["window"][read]
    if label == "prefill":
        # a chunk's queries share what they read: each cached row is read
        # once a layer for the chunk, not once a query
        t = facts["config"]["run"]["serve_flags"]["prompt_pad"]
        k = x["full" if part != "window" else "window"]
        live = per["candidates"] / (x["full"]["layers"] * t) + t / 2
        if part == "window":
            live = min(live, facts["config"]["sliding_window_size"] + t)
            nbytes = k["layers"] * live * k["row_bytes"]
        else:
            nbytes = k["layers"] * live * (
                x["index_key_bytes"]
                + (k["row_bytes"] if part == "sparse" else 0))
    t_flops = flops / peaks["bf16_flops_per_s"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return {"least_s": max(t_flops, t_bytes), "flops": flops,
            "bytes": nbytes, **per,
            "bound": "bandwidth" if t_bytes >= t_flops else "compute"}


def kind_roofline_pct(facts, *, program: str, inside: str, label: str,
                      part: str, scopes: list) -> Optional[float]:
    """Least time of `part` (`_least`) of one execution of `program` over
    the device time under the `scopes` prefixes inside it."""
    least = _least(facts, label, part)
    cap, t = sc._capture_of(facts), facts.get("trace")
    if least is None or cap is None or not t or program not in t["programs"]:
        return None
    spent = sum(v for k, v in sc.scope_seconds(
        cap, scopes, inside=inside).items() if k is not None)
    if not spent or not least["least_s"]:
        return None
    per_call_ms = 1e3 * spent / t["programs"][program]["count"]
    facts.setdefault("notes", []).append(
        {"roofline": f"{program}: {part}", "bound": least["bound"],
         "least_ms": 1e3 * least["least_s"], "spent_ms": per_call_ms,
         "bytes": least["bytes"], "flops": least["flops"],
         "candidates": least["candidates"], "selected": least["selected"],
         "in_window": least["in_window"]})
    return 100.0 * 1e3 * least["least_s"] / per_call_ms


def decode_step_roofline_pct(facts, *, program: str) -> Optional[float]:
    """Least time of a whole decode step (module docstring) over its mean
    device time."""
    t, peaks = facts.get("trace"), facts.get("peaks")
    sparse = _least(facts, "decode", "sparse")
    window = _least(facts, "decode", "window")
    active = sc.counter_ratio(
        facts, num='moe_active_experts_total{program="decode"}',
        den='moe_layer_calls_total{program="decode"}')
    rows = sc.counter_ratio(
        facts, num='moe_assignments_total{program="decode"}',
        den='moe_layer_calls_total{program="decode"}')
    tokens = spans.occupancy_win_pct(facts)
    if (not t or not peaks or program not in t["programs"] or sparse is None
            or window is None or active is None or rows is None
            or tokens is None):
        return None
    config = facts["config"]
    x = _widths(config)
    tokens = tokens / 100.0 * config["run"]["serve_flags"]["slots"]
    dense, experts = x["dense_layers"], x["expert_layers"]
    attn = (x["full"]["layers"] * (x["full"]["attn_params"]
                                   + x["index_params"])
            + x["window"]["layers"] * x["window"]["attn_params"])
    params = (attn + dense * x["dense_params"]
              + experts * (x["shared_params"] + active * x["expert_params"])
              + x["head_params"])
    cache_bytes = sparse["bytes"] + window["bytes"]
    nbytes = params * x["w"] + experts * x["router_params"] * 4 + cache_bytes
    flops = (2 * tokens * (attn + dense * x["dense_params"]
                           + experts * (x["shared_params"]
                                        + x["router_params"])
                           + x["head_params"])
             + 2 * experts * rows * x["expert_params"]
             + sparse["flops"] + window["flops"])
    t_flops = flops / peaks["bf16_flops_per_s"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    least = max(t_flops, t_bytes)
    step_ms = t["programs"][program]["mean_ms"]
    facts.setdefault("notes", []).append(
        {"roofline": program,
         "bound": "bandwidth" if t_bytes >= t_flops else "compute",
         "least_ms": 1e3 * least, "step_ms": step_ms, "bytes": nbytes,
         "flops": flops, "streamed_params": params,
         "cached_position_bytes": cache_bytes,
         "active_held_experts_per_layer": active})
    return 100.0 * 1e3 * least / step_ms


def window_blocks_share(facts) -> Optional[float]:
    """Blocks the window kind holds over the blocks every kind holds, at
    the window's last scrape."""
    m = facts.get("metrics1") or {}
    kinds = {k: v for k, v in m.items()
             if k.startswith("kv_pool_blocks_in_use{")}
    win = m.get('kv_pool_blocks_in_use{kind="window"}')
    total = sum(kinds.values())
    return None if win is None or not total else win / total


def window_blocks_freed_per_step(facts) -> Optional[float]:
    """Blocks the window kind handed back while their requests ran, a
    decode step of the window."""
    return sc.counter_ratio(facts, num="kv_pool_window_blocks_freed_total",
                            den="step_steps_total")
