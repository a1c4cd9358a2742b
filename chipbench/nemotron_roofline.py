"""Roofline shares of a model whose blocks are ONE mixer each — a Mamba-2
rule, attention or experts in a LATENT: the Nemotron-3-Super configuration
(`configs/nemotron-3-super-120b-a12b-ep4-1chip.json`), from the
configuration's own keys, the program's `moe_*` and `state_pool_*` counters
over the window and the device time of its scopes on the capture.
`layers/<metric>.json` names these functions as
`"nemotron_roofline:<function>"`.

Operations and bytes are the ALGORITHM's, computed from shapes, the same work
whatever implements it (w = 2 B for bfloat16; C = 4096; H = 128 state heads
of P = 64 in G = 8 groups, a state N = 128 wide; a latent of L = 1024, an
expert F = 2688 wide, a shared expert of 5376):

  an expert layer's held experts,   every expert with at least one row read
  a call                            once, 2 L F w (11.0 MB); a row in (L),
                                    its hidden row written and read (F) and
                                    out (L), w each; 2 matmuls x 2 L F FLOPs
                                    a row
  a layer's one-token rule, a slot  the state read AND written, 2 x H P N x 4
                                    B (8.39 MB); 5 H P N FLOPs
  a block's weights                 M: W_in C x (2 H P + 2 G N + H), the taps
                                    and bias, A_log, D, dt_bias, the gain,
                                    W_out H P x C; *: W_q, W_o C x 32 x 128,
                                    W_k, W_v C x 2 x 128; E (beside the
                                    experts): router C x 512, W_down and
                                    W_up C x L, the shared expert 2 x C x 5376
  live K and V                      `state_pool_kv_bytes_read_total`: the
                                    live positions x 1024 B, the one * block

A share divides the least time — the larger of bytes over the peak bytes/s
and FLOPs over the peak FLOP/s — by device time, so what an implementation
adds (a permutation's rows moved, a state copied on its way through the layer
loop) reads as distance from the roofline and no later kernel can read over
100 %. A reader returns None where what it reads is not there (a program
without the counters or the scopes), and the harness leaves the metric out.
"""

from __future__ import annotations

from typing import Optional

from chipbench import scopes as sc
from chipbench import spans
from chipbench.solar_roofline import _STATE, _per_step, _share, _spent_ms

__all__ = ["widths", "decode_step_roofline_pct",
           "latent_experts_roofline_pct", "ssm_step_roofline_pct"]


def widths(config: dict) -> dict:
    w = sc.OPERAND_BYTES[config["run"]["dtype"]]
    c, d = config["hidden_size"], config["head_dim"]
    h, kv = config["num_attention_heads"], config["num_key_value_heads"]
    heads, p = config["mamba_num_heads"], config["mamba_head_dim"]
    g, n, taps = config["n_groups"], config["ssm_state_size"], \
        config["conv_kernel"]
    hp, conv = heads * p, heads * p + 2 * g * n
    lat, f = config["moe_latent_size"], config["moe_intermediate_size"]
    pattern = config["hybrid_override_pattern"]
    router = config.get("published", {}).get(
        "router_outputs", config["n_routed_experts"])
    return {
        "w": w,
        "blocks": {k: pattern.count(k) for k in "M*E"},
        "ssm_params": c * (hp + conv + heads) + conv * (taps + 1)
        + 3 * heads + hp + hp * c,
        "attn_params": 2 * c * h * d + 2 * c * kv * d,
        # an E block beside its routed experts: router, latent, shared
        "dense_e_params": c * router + 2 * c * lat
        + 2 * c * config["moe_shared_expert_intermediate_size"],
        "expert_params": 2 * lat * f,
        "expert_row_bytes": (lat + f + lat) * w,
        "expert_row_flops": 2 * 2 * lat * f,
        "head_params": c * config["vocab_size"],
        "state_bytes": hp * n * 4,           # a slot a layer, float32
        "step_flops": 5 * hp * n,            # a slot a layer
        "row_bytes": kv * d * 2 * w,         # K and V of one position
        "pair_flops": h * 4 * d,             # q . k and p . v, every head
    }


def _experts(facts, label: str) -> Optional[dict]:
    """An execution of the `label` program (window means): rows through the
    held experts and experts with at least one row, summed over its E
    blocks."""
    d = sc._deltas(facts, [f'moe_{name}{{program="{label}"}}' for name in
                           ("layer_calls_total", "assignments_total",
                            "active_experts_total")])
    if d is None or not d[0]:
        return None
    runs = d[0] / widths(facts["config"])["blocks"]["E"]
    return {"rows": d[1] / runs, "active": d[2] / runs}


def latent_experts_roofline_pct(facts, *, program: str, inside: str,
                                scope: str, label: str) -> Optional[float]:
    """The held experts' two grouped products of one execution of `program`
    over the device time under `scope` inside it."""
    m = _experts(facts, label)
    if m is None:
        return None
    x = widths(facts["config"])
    return _share(
        facts, f"{program}: latent experts",
        flops=m["rows"] * x["expert_row_flops"],
        nbytes=m["active"] * x["expert_params"] * x["w"]
        + m["rows"] * x["expert_row_bytes"],
        spent_ms=_spent_ms(facts, program, inside, [scope]),
        rows=m["rows"], active_experts=m["active"])


def _step(facts) -> Optional[dict]:
    """A decode step (window means): its live slots, the live K and V
    bytes read."""
    per = _per_step(facts, _STATE)
    occupied = spans.occupancy_win_pct(facts)
    if per is None or occupied is None:
        return None
    slots = facts["config"]["run"]["serve_flags"]["slots"]
    return {"kv_bytes": per[2], "tokens": occupied / 100.0 * slots}


def ssm_step_roofline_pct(facts, *, program: str, inside: str,
                          scopes: list) -> Optional[float]:
    """The one-token rule alone: the LIVE slots' states read and written, a
    state layer, over the device time of its scopes inside one decode
    step."""
    m = _step(facts)
    if m is None:
        return None
    x = widths(facts["config"])
    layers = x["blocks"]["M"]
    return _share(facts, f"{program}: one-token rule",
                  flops=layers * m["tokens"] * x["step_flops"],
                  nbytes=layers * m["tokens"] * 2 * x["state_bytes"],
                  spent_ms=_spent_ms(facts, program, inside, scopes),
                  live_slots=m["tokens"])


def decode_step_roofline_pct(facts, *, program: str) -> Optional[float]:
    """Least time of a whole decode step — every block's weights and the
    head streamed once (of the routed experts those with a row), every live
    slot's state read and written, the live K and V read — over its mean
    device time."""
    t, m, e = facts.get("trace"), _step(facts), _experts(facts, "decode")
    if not t or program not in t["programs"] or m is None or e is None:
        return None
    x = widths(facts["config"])
    n = x["blocks"]
    dense = (n["M"] * x["ssm_params"] + n["*"] * x["attn_params"]
             + n["E"] * x["dense_e_params"] + x["head_params"])
    state_bytes = n["M"] * m["tokens"] * 2 * x["state_bytes"]
    weight_bytes = (dense + e["active"] * x["expert_params"]) * x["w"]
    positions = m["kv_bytes"] / x["row_bytes"]
    return _share(
        facts, program,
        flops=2 * m["tokens"] * dense + e["rows"] * x["expert_row_flops"]
        + n["M"] * m["tokens"] * x["step_flops"]
        + positions * x["pair_flops"],
        nbytes=weight_bytes + e["rows"] * x["expert_row_bytes"]
        + state_bytes + m["kv_bytes"],
        spent_ms=t["programs"][program]["mean_ms"],
        weight_bytes=weight_bytes, state_bytes=state_bytes,
        kv_bytes=m["kv_bytes"], active_held_experts=e["active"])
