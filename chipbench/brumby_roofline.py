"""Roofline shares of a model with NO K/V layer, whose every layer keeps a
degree-2 power-retention STATE a slot: the Brumby configuration
(`configs/brumby-14b-pp8-1chip.json`), from the configuration's own keys
and the state width D it states (`retention.state_width`), the program's
`state_pool_*` counters over the window and the device time of its scopes
on the capture. `layers/<metric>.json` names these functions as
`"brumby_roofline:<function>"`.

Operations and bytes are the ALGORITHM's, computed from shapes, the same
work whatever implements it (w = 2 B for bfloat16; KV = 8 heads of d = 128
in groups of G = 5 query heads; D = 8 704; c positions a closed-form chunk):

  a layer's decode step, a slot    the state read AND written, 2 x KV x (d
                                   D + D) x 4 B (71.9 MB); 13 KV d D FLOPs
                                   (decay, the rank-one update: 3 a number;
                                   the G answers: 2 G a number)
  a layer's weights                W_q, W_o (C x H d each), W_k, W_v (C x
                                   KV d each), the gate (C x KV + KV),
                                   SwiGLU 3 x C x F: 330.3 M parameters
  the chunked rule, a chunk of     KV x [4 G d c (c + 1) / 2 (the causal
  c positions, a layer             half of q . k and of a . v) + 2 c D d
                                   (the outgoing state) + 2 c D (its
                                   normaliser)] FLOPs, and for a chunk that
                                   comes AFTER another of its prompt KV x
                                   [2 G c D d + 2 G c D] more (the incoming
                                   state's part of the answers: a prompt's
                                   first chunk starts from an empty state
                                   and needs none; the share of such chunks
                                   is the counters': one a finish); bytes:
                                   q, k, v read and y written in float32
                                   and the state once in, once out

The counters count what the algorithm needs: `state_pool_bytes_read_total`
/ `..._written_total` (every slot's state leaves a step: the program reads
and writes all of them whatever is live). What an implementation adds — the
expanded keys and queries it materialises, a value spread over the lanes,
a state copied on its way through the layer loop — lies under the scopes a
share divides by and reads as distance from the roofline, so no later
kernel can read over 100 %.

A reader returns None where what it reads is not there (a program without
the counters or the scopes), and the harness leaves the metric out.
"""

from __future__ import annotations

from typing import Optional

from chipbench import scopes as sc
from chipbench import spans
from chipbench.solar_roofline import _per_step, _share, _spent_ms

__all__ = ["widths", "decode_step_roofline_pct", "ret_step_roofline_pct",
           "ret_chunk_roofline_pct", "state_bytes_per_token"]

_STATE = ["state_pool_bytes_read_total", "state_pool_bytes_written_total"]


def widths(config: dict) -> dict:
    w = sc.OPERAND_BYTES[config["run"]["dtype"]]
    c, d, f = config["hidden_size"], config["head_dim"], \
        config["intermediate_size"]
    h, kv = config["num_attention_heads"], config["num_key_value_heads"]
    ret = config["retention"]
    wide, chunk, g = ret["state_width"], ret["chunk"], h // kv
    return {
        "w": w, "layers": config["num_hidden_layers"], "group": g,
        # W_q, W_o, W_k, W_v, the gate with its bias, SwiGLU
        "layer_params": 2 * c * h * d + 2 * c * kv * d + c * kv + kv
        + 3 * c * f,
        "head_params": c * config["vocab_size"],
        "state_bytes": kv * (d * wide + wide) * 4,  # a slot a layer
        "step_flops": 13 * kv * d * wide,           # a slot a layer
        # the chunked rule, a chunk of `chunk` positions a layer: what
        # every chunk needs, and what one after another of its prompt adds
        "chunk_flops": kv * (4 * g * d * chunk * (chunk + 1) // 2
                             + 2 * chunk * wide * d + 2 * chunk * wide),
        "chunk_state_flops": kv * (2 * g * chunk * wide * d
                                   + 2 * g * chunk * wide),
        "chunk_bytes": (h + 2 * kv + h) * chunk * d * 4,
        "chunk": chunk,
    }


def _step(facts) -> Optional[dict]:
    """A decode step (window means): its state bytes, its rows."""
    per = _per_step(facts, _STATE)
    tokens = spans.occupancy_win_pct(facts)
    if per is None or tokens is None:
        return None
    slots = facts["config"]["run"]["serve_flags"]["slots"]
    return {"state_bytes": per[0] + per[1], "slots": slots,
            "tokens": tokens / 100.0 * slots}


def ret_step_roofline_pct(facts, *, program: str, inside: str,
                          scopes: list) -> Optional[float]:
    """The one-token rule alone: the state's bytes read and written a step
    (the counters') over the device time of its scopes inside one decode
    step."""
    m = _step(facts)
    if m is None:
        return None
    x = widths(facts["config"])
    return _share(facts, f"{program}: one-token rule",
                  flops=x["layers"] * m["slots"] * x["step_flops"],
                  nbytes=m["state_bytes"],
                  spent_ms=_spent_ms(facts, program, inside, scopes))


def ret_chunk_roofline_pct(facts, *, program: str, inside: str,
                           scopes: list) -> Optional[float]:
    """The chunked rule of one prefill chunk — its FLOPs and bytes from
    the widths, all the chunk's positions (pads too: the device runs them),
    the incoming state's part for the window's share of chunks that had
    one (all chunks less one a finish) — over the device time under
    `ret.chunk` inside it."""
    config = facts["config"]
    x = widths(config)
    pad = config["run"]["serve_flags"]["prompt_pad"]
    got = sc._deltas(facts, [
        "state_pool_installs_total",
        "state_pool_prefill_real_positions_total",
        "state_pool_prefill_pad_positions_total"])
    if got is None or not got[1] + got[2]:
        return None
    later = max(0.0, 1.0 - (got[0] / x["layers"]) / ((got[1] + got[2]) / pad))
    chunks = pad / x["chunk"]
    return _share(
        facts, f"{program}: chunked rule", later_chunks_share=later,
        flops=x["layers"] * chunks * (x["chunk_flops"]
                                      + later * x["chunk_state_flops"]),
        nbytes=x["layers"] * (chunks * x["chunk_bytes"]
                              + 2 * x["state_bytes"]),
        spent_ms=_spent_ms(facts, program, inside, scopes))


def decode_step_roofline_pct(facts, *, program: str) -> Optional[float]:
    """Least time of a whole decode step — every layer's weights and the
    head streamed once, every slot's state read and written — over its
    mean device time."""
    t, m = facts.get("trace"), _step(facts)
    if not t or program not in t["programs"] or m is None:
        return None
    x = widths(facts["config"])
    params = x["layers"] * x["layer_params"] + x["head_params"]
    return _share(
        facts, program,
        flops=2 * m["tokens"] * params
        + x["layers"] * m["slots"] * x["step_flops"],
        nbytes=params * x["w"] + m["state_bytes"],
        spent_ms=t["programs"][program]["mean_ms"],
        weight_bytes=params * x["w"], state_bytes=m["state_bytes"])


def state_bytes_per_token(facts) -> Optional[float]:
    """State bytes read and written over the window, a token the steps
    advanced."""
    got = sc._deltas(facts, [*_STATE, "step_tokens_advanced_total"])
    if got is None or not got[-1]:
        return None
    return (got[0] + got[1]) / got[-1]
