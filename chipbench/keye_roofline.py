"""Roofline shares of a model whose attention selects what it reads (the
Keye configuration: `configs/keye-vl-2.0-30b-a3b-ep8-1chip.json`), from
the configuration's widths, the program's `dsa_*` / `moe_*` counters over
the window and the device time of its scopes on the capture.
`layers/<metric>.json` names these functions as `"keye_roofline:<function>"`.

Operations and bytes are the ALGORITHM's, computed from shapes (w = the
operand width of the served dtype, 2 B for bfloat16):

  an index key      Di x w                      (64 x 2 = 128 B)
  a K and V row     2 x KV heads x head x w     (2 x 4 x 128 x 2 = 2048 B)
  scoring a position   2 x Hi x Di FLOPs        (16 heads of 64)
  attending a position 4 x heads x head FLOPs   (q.k and p.v, 32 of 128)

A decode step scores every live position of every slot (`candidate`
positions, summed over the layers by the counter) and reads the K/V rows
of the selected ones: least time = (candidates x 128 B + selected x 2048 B)
/ peak bytes/s (its FLOPs are a hundredth of that). A prefill chunk does
the same for T queries at once and is bound by its FLOPs. That the pool
stores a 64-wide index key in 128 lanes, and that today's kernels read
every live K/V row and mask, is the implementation's cost: it reads as
distance from the roofline.

`decode_step_roofline_pct` is the whole step as `reducers.
decode_step_roofline_pct` builds GPT-2's: the parameters a step must
stream — per layer attention, indexer, router (float32) and the experts
that had a row (the `moe_active_experts_total` counter: held experts
only), once the head — plus the two position terms, over the step's mean
device time. It leaves a `note` row naming the bound.

A reader returns None where what it reads is not there.
"""

from __future__ import annotations

from typing import Optional

from chipbench import scopes, spans

__all__ = ["sparse_decode_roofline_pct", "sparse_prefill_roofline_pct",
           "decode_step_roofline_pct"]

def _widths(config: dict) -> dict:
    sa = config["sa_config"]
    w = scopes.OPERAND_BYTES[config["run"]["dtype"]]
    heads, kv, d = (config["num_attention_heads"],
                    config["num_key_value_heads"], config["head_dim"])
    hi, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    c = config["hidden_size"]
    return {
        "w": w, "index_key_bytes": di * w, "kv_row_bytes": 2 * kv * d * w,
        "score_flops": 2 * hi * di, "attend_flops": 4 * heads * d,
        # parameters of one layer but its experts, of one expert, the head
        "attn_params": c * heads * d * 2 + c * kv * d * 2,
        "index_params": c * hi * di + c * di + c * hi,
        "router_params": c * config["num_local_experts"],
        "expert_params": 3 * c * config["moe_intermediate_size"],
        "head_params": c * config["vocab_size"],
    }


def _per_call(facts, label: str) -> Optional[dict]:
    """Window means per execution of the program `label`: candidate and
    selected positions (summed over its layers), and executions."""
    d = scopes._deltas(facts, [f'dsa_{n}{{program="{label}"}}' for n in
                               ("layer_calls_total",
                                "candidate_positions_total",
                                "selected_positions_total")])
    if d is None or not d[0]:
        return None
    calls = d[0] / facts["config"]["num_hidden_layers"]
    return {"calls": calls, "candidates": d[1] / calls,
            "selected": d[2] / calls}


def _sparse_least(facts, label: str) -> Optional[dict]:
    per, peaks = _per_call(facts, label), facts.get("peaks")
    if per is None or not peaks:
        return None
    x = _widths(facts["config"])
    nbytes = (per["candidates"] * x["index_key_bytes"]
              + per["selected"] * x["kv_row_bytes"])
    if label == "prefill":
        # a chunk's queries share what they read: the live positions'
        # index keys and K/V rows once a layer. Live positions of the
        # mean chunk = mean candidates a query + half a chunk
        t = facts["config"]["run"]["serve_flags"]["prompt_pad"]
        layers = facts["config"]["num_hidden_layers"]
        live = per["candidates"] / (layers * t) + t / 2
        nbytes = layers * live * (x["index_key_bytes"] + x["kv_row_bytes"])
    flops = (per["candidates"] * x["score_flops"]
             + per["selected"] * x["attend_flops"])
    t_flops = flops / peaks["bf16_flops_per_s"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return {"least_s": max(t_flops, t_bytes), "flops": flops,
            "bytes": nbytes, **per,
            "bound": "bandwidth" if t_bytes >= t_flops else "compute"}


def _sparse_roofline_pct(facts, *, program: str, inside: str, label: str,
                         scopes_read: list) -> Optional[float]:
    least = _sparse_least(facts, label)
    cap, t = scopes._capture_of(facts), facts.get("trace")
    if least is None or cap is None or not t or program not in t["programs"]:
        return None
    # an operation counts if ANY component of its op_name starts with one
    # of `scopes_read` (the kernel inside `attn.sparse_decode` is named
    # `attn.paged_decode` innermost)
    spent = sum(v for k, v in scopes.scope_seconds(
        cap, scopes_read, inside=inside).items() if k is not None)
    if not spent:
        return None
    per_call_ms = 1e3 * spent / t["programs"][program]["count"]
    facts.setdefault("notes", []).append(
        {"roofline": f"{program}: selection and read",
         "bound": least["bound"], "least_ms": 1e3 * least["least_s"],
         "spent_ms": per_call_ms, "bytes": least["bytes"],
         "flops": least["flops"], "candidates": least["candidates"],
         "selected": least["selected"]})
    return 100.0 * 1e3 * least["least_s"] / per_call_ms


def sparse_decode_roofline_pct(facts, *, program: str, inside: str,
                               scopes: list) -> Optional[float]:
    """Least time of one decode step's scoring, selecting and reading over
    the device time under the `scopes` prefixes (`dsa.`,
    `attn.sparse_decode`) inside the step."""
    return _sparse_roofline_pct(facts, program=program, inside=inside,
                                label="decode", scopes_read=scopes)


def sparse_prefill_roofline_pct(facts, *, program: str, inside: str,
                                scopes: list) -> Optional[float]:
    """The same for one prefill chunk (`dsa.`, `attn.sparse_prefill`)."""
    return _sparse_roofline_pct(facts, program=program, inside=inside,
                                label="prefill", scopes_read=scopes)


def decode_step_roofline_pct(facts, *, program: str) -> Optional[float]:
    """Least time of a whole decode step (module docstring) over its mean
    device time."""
    t, peaks = facts.get("trace"), facts.get("peaks")
    sparse = _sparse_least(facts, "decode")
    active = scopes.counter_ratio(
        facts, num='moe_active_experts_total{program="decode"}',
        den='moe_layer_calls_total{program="decode"}')
    rows = scopes.counter_ratio(
        facts, num='moe_assignments_total{program="decode"}',
        den='moe_layer_calls_total{program="decode"}')
    tokens = spans.occupancy_win_pct(facts)
    if (not t or not peaks or program not in t["programs"] or sparse is None
            or active is None or rows is None or tokens is None):
        return None
    config = facts["config"]
    x, layers = _widths(config), config["num_hidden_layers"]
    tokens = tokens / 100.0 * config["run"]["serve_flags"]["slots"]
    params = (layers * (x["attn_params"] + x["index_params"]
                        + active * x["expert_params"]) + x["head_params"])
    nbytes = (params * x["w"] + layers * x["router_params"] * 4
              + sparse["bytes"])
    flops = (2 * tokens * (layers * (x["attn_params"] + x["index_params"]
                                     + x["router_params"])
                           + x["head_params"])
             + 2 * layers * rows * x["expert_params"] + sparse["flops"])
    t_flops = flops / peaks["bf16_flops_per_s"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    least = max(t_flops, t_bytes)
    step_ms = t["programs"][program]["mean_ms"]
    facts.setdefault("notes", []).append(
        {"roofline": program,
         "bound": "bandwidth" if t_bytes >= t_flops else "compute",
         "least_ms": 1e3 * least, "step_ms": step_ms, "bytes": nbytes,
         "flops": flops, "streamed_params": params,
         "active_held_experts_per_layer": active})
    return 100.0 * 1e3 * least / step_ms
