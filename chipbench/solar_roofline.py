"""Roofline shares of a model that keeps a STATE a slot in three layers of
four — the gated delta rule behind a short convolution — beside K and V in
the fourth: the Solar-Open2 configuration
(`configs/solar-open2-250b-ep8-1chip.json`), from the configuration's
widths, the program's `state_pool_*` / `attn_*` / `moe_*` counters over the
window and the device time of its scopes on the capture.
`layers/<metric>.json` names these functions as `"solar_roofline:<function>"`.

Operations and bytes are the ALGORITHM's, computed from shapes, the same
work whatever implements it (w = 2 B for bfloat16; H = 64 linear heads of
d = 128, c = 64 positions a closed-form chunk):

  a linear layer's decode step, a slot    the state read AND written, 2 x
                                          H d^2 x 4 B (8.39 MB), and its
                                          tail, 2 x 3 x 3 H d x w; 7 H d^2
                                          FLOPs (decay, k.S, the rank-one
                                          update, q.S)
  the softmax layer's, a cached position  KV x D x 2 x w bytes (4096 B),
                                          H x 4 x D FLOPs
  the chunked rule, a chunk of c, a head  4 c^2 d + 6 c d^2 FLOPs: A and B
                                          (the triangles of two c x c x d
                                          products), the triangular solve
                                          and B U (c^2 d each), K S_0, Q S_0
                                          and K^T U (2 c d^2 each); bytes:
                                          q, k, v, g read and o written in
                                          float32 (5 x c d x 4) and the
                                          state once in, once out a layer

The counters count what the algorithm needs: `state_pool_bytes_read_total`
/ `..._written_total` (every slot's state leaves a step: the program reads
and writes all of them whatever is live), `state_pool_kv_bytes_read_total`
(the live positions' K and V), `state_pool_prefill_{real,pad}_positions_
total`. What an implementation adds — a state copied on its way through
the layer loop, the pair-by-pair decay products where a blocked form would
use matmuls, float32 "highest" products at six passes — lies under the
scopes a share divides by and reads as distance from the roofline, so no
later kernel can read over 100 %.

`kda_step_roofline_pct` takes ISSUE 47's numerator (2 x state bytes + the
linear mixers' weights) and divides by the device time of EVERY scope of
the linear mixer inside the decode step (`kda.project`, `kda.step`,
`kda.out`, `state_pool.`): the weights are streamed under `kda.project`
and `kda.out`, and their bytes over `kda.step`'s time alone could read
over 100 %.

A reader returns None where what it reads is not there (a program without
the counters or the scopes), and the harness leaves the metric out.
"""

from __future__ import annotations

from typing import Optional

from chipbench import scopes as sc
from chipbench import spans

__all__ = ["widths", "decode_step_roofline_pct", "kda_step_roofline_pct",
           "kda_scan_roofline_pct", "full_decode_roofline_pct",
           "state_read_share", "counter_share"]

CHUNK = 64  # positions a closed-form chunk (`KdaConfig.chunk`)


def widths(config: dict) -> dict:
    w = sc.OPERAND_BYTES[config["run"]["dtype"]]
    c, d = config["hidden_size"], config["head_dim"]
    h, kv = config["num_attention_heads"], config["num_key_value_heads"]
    lin = config["linear_attn_config"]
    hl, dl, taps = lin["num_heads"], lin["head_dim"], \
        lin["short_conv_kernel_size"]
    wl = hl * dl
    f = config["moe_intermediate_size"]
    n_full = len(config["gqa_layers"])
    rank = dl  # the decay's and the gate's low rank (`assumed`)
    return {
        "w": w,
        "layers": {"full": n_full,
                   "linear": config["num_hidden_layers"] - n_full},
        "row_bytes": kv * d * 2 * w,       # K and V of one position
        "pair_flops": h * 4 * d,           # q . k and p . v, every head
        # W_q, W_o, W_gate (C x H D each) and W_k, W_v (C x KV D each)
        "full_params": 3 * c * h * d + 2 * c * kv * d,
        # W_q, W_k, W_v, W_o, two low-rank pairs, W_b, three convolutions
        "linear_params": 4 * c * wl + 2 * (c * rank + rank * wl) + c * hl
        + 3 * wl * taps,
        "state_bytes": hl * dl * dl * 4,   # a slot a layer, float32
        "tail_bytes": (taps - 1) * 3 * wl * w,
        "step_flops": 7 * hl * dl * dl,    # a slot a layer
        # the chunked rule a chunk of CHUNK positions a layer (all heads)
        "chunk_flops": hl * (4 * CHUNK * CHUNK * dl + 6 * CHUNK * dl * dl),
        "chunk_bytes": hl * 5 * CHUNK * dl * 4,
        "shared_params": config["n_shared_experts"] * 3 * c * f,
        "router_params": c * config["published"]["router_outputs"],
        "expert_params": 3 * c * f,
        "head_params": c * config["vocab_size"],
    }


def _per_step(facts, series: list) -> Optional[list]:
    """Window means a decode step of the counters `series`."""
    got = sc._deltas(facts, [*series, "step_steps_total"])
    if got is None or not got[-1]:
        return None
    return [g / got[-1] for g in got[:-1]]


def _spent_ms(facts, program: str, inside: str, scopes: list
              ) -> Optional[float]:
    """Device ms under the `scopes` prefixes inside one execution of
    `program` (op_name prefix `inside`), mean over the capture's."""
    cap, t, known = sc._capture_of(facts), facts.get("trace"), \
        sc.known_scopes(facts)
    if cap is None or not t or not known or program not in t["programs"]:
        return None
    spent = sum(v for k, v in sc.scope_seconds(
        cap, known, inside=inside).items()
        if k is not None and k.startswith(tuple(scopes)))
    if not spent:
        return None
    return 1e3 * spent / t["programs"][program]["count"]


def _share(facts, name: str, *, flops: float, nbytes: float,
           spent_ms: Optional[float], **note) -> Optional[float]:
    peaks = facts.get("peaks")
    if not peaks or not spent_ms:
        return None
    t_flops = flops / peaks["bf16_flops_per_s"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    least_ms = 1e3 * max(t_flops, t_bytes)
    facts.setdefault("notes", []).append(
        {"roofline": name,
         "bound": "bandwidth" if t_bytes >= t_flops else "compute",
         "least_ms": least_ms, "spent_ms": spent_ms, "bytes": nbytes,
         "flops": flops, **note})
    return 100.0 * least_ms / spent_ms


_STATE = ["state_pool_bytes_read_total", "state_pool_bytes_written_total",
          "state_pool_kv_bytes_read_total"]


def _mixer_step(facts) -> Optional[dict]:
    """The linear mixers' decode step (window means): bytes and FLOPs."""
    per = _per_step(facts, _STATE)
    tokens = spans.occupancy_win_pct(facts)
    if per is None or tokens is None:
        return None
    config = facts["config"]
    x = widths(config)
    slots = config["run"]["serve_flags"]["slots"]
    tokens = tokens / 100.0 * slots
    n = x["layers"]["linear"]
    return {"state_bytes": per[0] + per[1], "kv_bytes": per[2],
            "weight_bytes": n * x["linear_params"] * x["w"],
            "flops": n * (2 * tokens * x["linear_params"]
                          + slots * x["step_flops"]),
            "tokens": tokens}


def kda_step_roofline_pct(facts, *, program: str, inside: str,
                          scopes: list) -> Optional[float]:
    """The state's bytes read and written plus the linear mixers' weights
    (module docstring) over the device time of the mixers' scopes inside
    one decode step."""
    m = _mixer_step(facts)
    if m is None:
        return None
    return _share(facts, f"{program}: linear mixers", flops=m["flops"],
                  nbytes=m["state_bytes"] + m["weight_bytes"],
                  spent_ms=_spent_ms(facts, program, inside, scopes),
                  state_bytes=m["state_bytes"])


def full_decode_roofline_pct(facts, *, program: str, inside: str,
                             scopes: list) -> Optional[float]:
    """The live K and V a step reads in the softmax layer over the device
    time of the paged decode read."""
    per = _per_step(facts, _STATE[2:])
    if per is None:
        return None
    x = widths(facts["config"])
    positions = per[0] / x["row_bytes"]
    return _share(facts, f"{program}: full", nbytes=per[0],
                  flops=positions * x["pair_flops"],
                  spent_ms=_spent_ms(facts, program, inside, scopes),
                  positions_read=positions)


def kda_scan_roofline_pct(facts, *, program: str, inside: str,
                          scopes: list) -> Optional[float]:
    """The chunked rule of one prefill chunk — its FLOPs and bytes from
    the widths, all the chunk's positions (pads too: the device runs
    them) — over the device time under `kda.scan` inside it."""
    config = facts["config"]
    x = widths(config)
    n = x["layers"]["linear"]
    chunks = config["run"]["serve_flags"]["prompt_pad"] / CHUNK
    return _share(
        facts, f"{program}: chunked rule",
        flops=n * chunks * x["chunk_flops"],
        nbytes=n * (chunks * x["chunk_bytes"] + 2 * x["state_bytes"]),
        spent_ms=_spent_ms(facts, program, inside, scopes))


def decode_step_roofline_pct(facts, *, program: str) -> Optional[float]:
    """Least time of a whole decode step — the parameters it must stream
    (both kinds' mixers, in every layer the shared expert, the float32
    router and the held experts that had a row, once the head over the
    vocabulary rows held), every slot's state read and written and the
    live K and V read — over its mean device time."""
    t, m = facts.get("trace"), _mixer_step(facts)
    active = sc.counter_ratio(
        facts, num='moe_active_experts_total{program="decode"}',
        den='moe_layer_calls_total{program="decode"}')
    rows = sc.counter_ratio(
        facts, num='moe_assignments_total{program="decode"}',
        den='moe_layer_calls_total{program="decode"}')
    if not t or program not in t["programs"] or None in (m, active, rows):
        return None
    x = widths(facts["config"])
    layers = x["layers"]["full"] + x["layers"]["linear"]
    dense = (x["layers"]["full"] * x["full_params"] + layers
             * x["shared_params"] + x["head_params"])
    weight_bytes = (m["weight_bytes"] + x["w"] * (
        dense + layers * active * x["expert_params"])
        + layers * x["router_params"] * 4)
    positions = m["kv_bytes"] / x["row_bytes"]
    flops = (m["flops"] + 2 * m["tokens"] * (dense + layers
                                             * x["router_params"])
             + 2 * layers * rows * x["expert_params"]
             + positions * x["pair_flops"])
    return _share(facts, program, flops=flops,
                  nbytes=weight_bytes + m["state_bytes"] + m["kv_bytes"],
                  spent_ms=t["programs"][program]["mean_ms"],
                  weight_bytes=weight_bytes, state_bytes=m["state_bytes"],
                  kv_bytes=m["kv_bytes"],
                  active_held_experts_per_layer=active)


def state_read_share(facts) -> Optional[float]:
    """Of the cache bytes a decode step touches — every slot's state read
    and written, the live K and V read — the share that is the state's."""
    per = _per_step(facts, _STATE)
    if per is None or not sum(per):
        return None
    return (per[0] + per[1]) / sum(per)


def counter_share(facts, *, num: str, den: list) -> Optional[float]:
    """Window difference of series `num` over the sum of those of `den`."""
    got = sc._deltas(facts, [num, *den])
    if got is None or not sum(got[1:]):
        return None
    return got[0] / sum(got[1:])
