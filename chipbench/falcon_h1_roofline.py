"""Roofline shares of a model whose every layer runs a Mamba-2 STATE-SPACE
mixer AND softmax attention: the Falcon-H1 configuration
(`configs/falcon-h1-34b-pp8-1chip.json`), from the configuration's own keys,
the program's `state_pool_*` counters over the window and the device time of
its scopes on the capture. `layers/<metric>.json` names these functions as
`"falcon_h1_roofline:<function>"`.

Operations and bytes are the ALGORITHM's, computed from shapes, the same work
whatever implements it (w = 2 B for bfloat16; H = 32 state-space heads of P =
128 in G = 2 groups, a state N = 256 wide; c positions a closed-form chunk):

  a layer's one-token rule, a slot   the state read AND written, 2 x H P N x
                                     4 B (8.39 MB); 5 H P N FLOPs (the decay,
                                     the rank-one update, the answer)
  a layer's weights                  attention W_q, W_o (C x 20 x 128 each),
                                     W_k, W_v (C x 4 x 128 each); W_in C x
                                     (2 H P + 2 G N + H), the taps and bias,
                                     W_out H P x C; SwiGLU 3 x C x F: 430.1 M
  the chunked rule, a chunk of c     2 G N c (c + 1) / 2 (C_t . B_s, the
  positions, a layer                 causal half) + 2 H P c (c + 1) / 2 (the
                                     weights times x) + 2 H P N c (the
                                     incoming state's answers) + 2 H P N c
                                     (the outgoing state) FLOPs; bytes: x and
                                     y (H P), B and C (G N), dt (H) a position
                                     in float32, the state once in, once out
  live K and V                       `state_pool_kv_bytes_read_total`: the
                                     live positions x 2048 B a layer

A share divides the least time — the larger of bytes over the peak bytes/s
and FLOPs over the peak FLOP/s — by device time, so what an implementation
adds (a state copied on its way through the layer loop, a decay matrix
written out) reads as distance from the roofline and no later kernel can read
over 100 %. A reader returns None where what it reads is not there (a program
without the counters or the scopes), and the harness leaves the metric out.
"""

from __future__ import annotations

from typing import Optional

from chipbench import scopes as sc
from chipbench import spans
from chipbench.solar_roofline import _STATE, _per_step, _share, _spent_ms

__all__ = ["widths", "decode_step_roofline_pct", "ssm_step_roofline_pct",
           "ssm_chunk_roofline_pct"]


def widths(config: dict) -> dict:
    w = sc.OPERAND_BYTES[config["run"]["dtype"]]
    c, d, f = config["hidden_size"], config["head_dim"], \
        config["intermediate_size"]
    h, kv = config["num_attention_heads"], config["num_key_value_heads"]
    hp, n, g = config["mamba_d_ssm"], config["mamba_d_state"], \
        config["mamba_n_groups"]
    heads, taps, chunk = config["mamba_n_heads"], config["mamba_d_conv"], \
        config["mamba_chunk_size"]
    conv = hp + 2 * g * n
    return {
        "w": w, "layers": config["num_hidden_layers"],
        "attn_params": 2 * c * h * d + 2 * c * kv * d,
        # W_in, the taps and their bias, A_log, D, dt_bias, the norm's gain,
        # W_out
        "ssm_params": c * (hp + conv + heads) + conv * (taps + 1)
        + 3 * heads + hp + hp * c,
        "mlp_params": 3 * c * f,
        "head_params": c * config["vocab_size"],
        "state_bytes": hp * n * 4,            # a slot a layer, float32
        "tail_bytes": (taps - 1) * conv * w,  # a slot a layer
        "row_bytes": kv * d * 2 * w,          # K and V of one position
        "pair_flops": h * 4 * d,              # q . k and p . v, every head
        "step_flops": 5 * hp * n,             # a slot a layer
        "chunk_flops": (g * n + hp) * chunk * (chunk + 1)
        + 4 * hp * n * chunk,
        "chunk_bytes": (2 * hp + 2 * g * n + heads) * chunk * 4,
        "chunk": chunk,
    }


def layer_params(x: dict) -> int:
    return x["attn_params"] + x["ssm_params"] + x["mlp_params"]


def _step(facts) -> Optional[dict]:
    """A decode step (window means): the state's bytes read and written, the
    live K and V bytes read, its rows."""
    per = _per_step(facts, _STATE)
    tokens = spans.occupancy_win_pct(facts)
    if per is None or tokens is None:
        return None
    slots = facts["config"]["run"]["serve_flags"]["slots"]
    return {"state_bytes": per[0] + per[1], "kv_bytes": per[2],
            "slots": slots, "tokens": tokens / 100.0 * slots}


def ssm_step_roofline_pct(facts, *, program: str, inside: str,
                          scopes: list) -> Optional[float]:
    """The one-token rule alone: the state's (and the tail's) bytes read and
    written a step (the counters') over the device time of its scopes inside
    one decode step."""
    m = _step(facts)
    if m is None:
        return None
    x = widths(facts["config"])
    return _share(facts, f"{program}: one-token rule",
                  flops=x["layers"] * m["slots"] * x["step_flops"],
                  nbytes=m["state_bytes"],
                  spent_ms=_spent_ms(facts, program, inside, scopes))


def ssm_chunk_roofline_pct(facts, *, program: str, inside: str,
                           scopes: list) -> Optional[float]:
    """The chunked rule of one prefill chunk — the larger of its FLOPs' and
    its bytes' time, from the widths, all the chunk's positions (pads too:
    the device runs them) — over the device time under `ssm.chunk` inside
    it."""
    config = facts["config"]
    x = widths(config)
    chunks = config["run"]["serve_flags"]["prompt_pad"] / x["chunk"]
    return _share(
        facts, f"{program}: chunked rule",
        flops=x["layers"] * chunks * x["chunk_flops"],
        nbytes=x["layers"] * (chunks * x["chunk_bytes"]
                              + 2 * x["state_bytes"]),
        spent_ms=_spent_ms(facts, program, inside, scopes))


def decode_step_roofline_pct(facts, *, program: str) -> Optional[float]:
    """Least time of a whole decode step — every layer's weights and the head
    streamed once, every slot's state read and written, the live K and V read
    — over its mean device time."""
    t, m = facts.get("trace"), _step(facts)
    if not t or program not in t["programs"] or m is None:
        return None
    x = widths(facts["config"])
    params = x["layers"] * layer_params(x) + x["head_params"]
    positions = m["kv_bytes"] / x["row_bytes"]
    return _share(
        facts, program,
        flops=2 * m["tokens"] * params
        + x["layers"] * m["slots"] * x["step_flops"]
        + positions * x["pair_flops"],
        nbytes=params * x["w"] + m["state_bytes"] + m["kv_bytes"],
        spent_ms=t["programs"][program]["mean_ms"],
        weight_bytes=params * x["w"], state_bytes=m["state_bytes"],
        kv_bytes=m["kv_bytes"])
