"""Roofline shares of a DENSE model whose layers are of two kinds: softmax
layers that read only the BLOCKS a score over mean-pooled keys selects, and
linear-attention layers that keep a fixed-decay STATE a slot: the
MiniCPM-SALA configuration (`configs/minicpm-sala-pp8-1chip.json`), from the
configuration's own keys, the program's `state_pool_*` and `dsa_*` counters
over the window and the device time of its scopes on the capture.
`layers/<metric>.json` names these functions as `"sala_roofline:<function>"`.

Operations and bytes are the ALGORITHM's, computed from shapes, the same work
whatever implements it (w = 2 B for bfloat16; C = 4096; 32 query heads and 2
KV heads of d = 128 in the softmax kind; H = 32 heads of d in the linear
kind; a block 64 positions, a pooled key every 16):

  a linear layer's one-token rule,  the state read AND written, 2 x H d d x
  a slot                            4 B (4.19 MB); 4 H d d FLOPs (the decay,
                                    the rank-one update, the answer)
  the chunked rule, a chunk of c    2 H d c (c + 1) (q . k and the weights
  positions, a layer                times v, the causal half) + 4 H d d c (the
                                    incoming state's answers, the outgoing
                                    state) FLOPs; q, k, v, o (H d each) a
                                    position in float32, the state once in,
                                    once out
  the block-selected read, decode   `dsa_selected_positions_total{decode}`:
                                    the positions of the chosen blocks up to
                                    the query, x 1024 B of K and V; 4 d FLOPs
                                    a position a query head
  the block-selected read, a chunk  `dsa_selected_positions_total{prefill}`:
                                    (query, position) pairs x 4 d FLOPs a
                                    query head; K and V of the positions ONE
                                    query reads at least
  pooling and scoring, decode       the live pooled rows (a row every 16
                                    positions, 2 x 128 x 2 B) read once; 2 d
                                    FLOPs a row a query head
  a layer's weights                 softmax kind W_q, W_o, W_gate (C x 4096
                                    each), W_k, W_v (C x 256 each): 52.4 M;
                                    linear kind five of C x 4096: 83.9 M;
                                    SwiGLU 3 x C x 16384: 201.3 M

A share divides the least time — the larger of bytes over the peak bytes/s
and FLOPs over the peak FLOP/s — by device time, so what an implementation
adds (blocks gathered into a copy before they are read, a state copied on
its way through the layer loop, float32 products in six passes) reads as
distance from the roofline and no later kernel can read over 100 %. A reader
returns None where what it reads is not there (a program without the
counters or the scopes), and the harness leaves the metric out.
"""

from __future__ import annotations

from typing import Optional

from chipbench import scopes as sc
from chipbench import spans
from chipbench.solar_roofline import _STATE, _per_step, _share, _spent_ms

__all__ = ["widths", "decode_step_roofline_pct", "lin_step_roofline_pct",
           "lin_chunk_roofline_pct", "block_decode_roofline_pct",
           "block_prefill_roofline_pct", "bsel_roofline_pct"]

CHUNK = 256  # positions a closed-form chunk (`LightningConfig.chunk`)

_SELECTED = 'dsa_selected_positions_total{program="%s"}'
_CANDIDATES = 'dsa_candidate_positions_total{program="%s"}'
_CALLS = 'dsa_layer_calls_total{program="%s"}'


def widths(config: dict) -> dict:
    w = sc.OPERAND_BYTES[config["run"]["dtype"]]
    c, d, f = config["hidden_size"], config["head_dim"], \
        config["intermediate_size"]
    h, kv = config["num_attention_heads"], config["num_key_value_heads"]
    hl, dl = config["lightning_nh"], config["lightning_head_dim"]
    sel = config["sparse_config"]
    n_full = sum(t == "minicpm4" for t in config["mixer_types"])
    return {
        "w": w,
        "layers": {"full": n_full,
                   "linear": config["num_hidden_layers"] - n_full},
        # W_q, W_o, W_gate (C x H d each), W_k, W_v (C x KV d each)
        "full_params": 3 * c * h * d + 2 * c * kv * d,
        # W_q, W_k, W_v, W_o, W_gate
        "linear_params": 5 * c * hl * dl,
        "mlp_params": 3 * c * f,
        "head_params": c * config["vocab_size"],
        "state_bytes": hl * dl * dl * 4,     # a slot a layer, float32
        "row_bytes": kv * d * 2 * w,         # K and V of one position
        "pooled_row_bytes": kv * d * w,      # a pooled key a KV head
        "stride": sel["kernel_stride"],
        "pair_flops": h * 4 * d,             # q . k and p . v, every head
        "score_flops": h * 2 * d,            # q . kc, every head
        "step_flops": 4 * hl * dl * dl,      # a slot a layer
        "chunk_flops": hl * (2 * dl * CHUNK * (CHUNK + 1)
                             + 4 * dl * dl * CHUNK),
        "chunk_bytes": hl * 4 * CHUNK * dl * 4,
    }


def _params(x: dict) -> int:
    """Every layer's weights and the head."""
    n = x["layers"]
    return (n["full"] * x["full_params"] + n["linear"] * x["linear_params"]
            + (n["full"] + n["linear"]) * x["mlp_params"]
            + x["head_params"])


def _step(facts) -> Optional[dict]:
    """A decode step (window means): the state's bytes read and written,
    the cache bytes read (the chosen blocks' K and V and the pooled rows),
    its rows."""
    per = _per_step(facts, _STATE)
    tokens = spans.occupancy_win_pct(facts)
    if per is None or tokens is None:
        return None
    slots = facts["config"]["run"]["serve_flags"]["slots"]
    return {"state_bytes": per[0] + per[1], "kv_bytes": per[2],
            "slots": slots, "tokens": tokens / 100.0 * slots}


def lin_step_roofline_pct(facts, *, program: str, inside: str,
                          scopes: list) -> Optional[float]:
    """The one-token rule alone: the state's bytes read and written a step
    (the counters') over the device time of its scopes inside one decode
    step."""
    m = _step(facts)
    if m is None:
        return None
    x = widths(facts["config"])
    return _share(facts, f"{program}: one-token rule",
                  flops=x["layers"]["linear"] * m["slots"] * x["step_flops"],
                  nbytes=m["state_bytes"],
                  spent_ms=_spent_ms(facts, program, inside, scopes))


def lin_chunk_roofline_pct(facts, *, program: str, inside: str,
                           scopes: list) -> Optional[float]:
    """The chunked rule of one prefill chunk — the larger of its FLOPs' and
    its bytes' time, from the widths, all the chunk's positions (pads too:
    the device runs them) — over the device time under `lin.chunk` inside
    it."""
    config = facts["config"]
    x = widths(config)
    n = x["layers"]["linear"]
    chunks = config["run"]["serve_flags"]["prompt_pad"] / CHUNK
    return _share(
        facts, f"{program}: chunked rule",
        flops=n * chunks * x["chunk_flops"],
        nbytes=n * (chunks * x["chunk_bytes"] + 2 * x["state_bytes"]),
        spent_ms=_spent_ms(facts, program, inside, scopes))


def _per_call(facts, series: str, program: str) -> Optional[float]:
    """Window difference of a `dsa_*` series over the program's layer
    calls: a mean a layer a dispatched program."""
    return sc.counter_ratio(facts, num=series % program,
                            den=_CALLS % program)


def block_decode_roofline_pct(facts, *, program: str, inside: str,
                              scopes: list) -> Optional[float]:
    """The block-selected read of a decode step: K and V of the positions
    the slots' queries read (the chosen blocks up to the query) over the
    device time of the read inside one decode step."""
    positions = _per_call(facts, _SELECTED, "decode")
    if positions is None:
        return None
    x = widths(facts["config"])
    n = x["layers"]["full"]
    return _share(facts, f"{program}: block read",
                  nbytes=n * positions * x["row_bytes"],
                  flops=n * positions * x["pair_flops"],
                  spent_ms=_spent_ms(facts, program, inside, scopes),
                  positions_read=positions)


def block_prefill_roofline_pct(facts, *, program: str, inside: str,
                               scopes: list) -> Optional[float]:
    """The block-selected read of one prefill chunk: its (query, position)
    pairs' products, and K and V of the positions one query reads, over the
    device time of the read inside it."""
    pairs = _per_call(facts, _SELECTED, "prefill")
    if pairs is None:
        return None
    config = facts["config"]
    x = widths(config)
    n = x["layers"]["full"]
    one_query = pairs / config["run"]["serve_flags"]["prompt_pad"]
    return _share(facts, f"{program}: block read",
                  nbytes=n * one_query * x["row_bytes"],
                  flops=n * pairs * x["pair_flops"],
                  spent_ms=_spent_ms(facts, program, inside, scopes),
                  pairs=pairs)


def bsel_roofline_pct(facts, *, program: str, inside: str,
                      scopes: list) -> Optional[float]:
    """Pooling and scoring of a decode step: the live pooled rows read once
    and scored by every query head, over the device time under `bsel.` and
    `dsa.select` inside one decode step."""
    live = _per_call(facts, _CANDIDATES, "decode")
    if live is None:
        return None
    x = widths(facts["config"])
    n = x["layers"]["full"]
    rows = live / x["stride"]
    return _share(facts, f"{program}: pooled scores",
                  nbytes=n * rows * x["pooled_row_bytes"],
                  flops=n * rows * x["score_flops"],
                  spent_ms=_spent_ms(facts, program, inside, scopes),
                  pooled_rows=rows)


def decode_step_roofline_pct(facts, *, program: str) -> Optional[float]:
    """Least time of a whole decode step — every layer's weights and the head
    streamed once, every slot's state read and written, the chosen blocks' K
    and V and the live pooled rows read — over its mean device time."""
    t, m = facts.get("trace"), _step(facts)
    if not t or program not in t["programs"] or m is None:
        return None
    x = widths(facts["config"])
    params = _params(x)
    positions = m["kv_bytes"] / x["row_bytes"]
    return _share(
        facts, program,
        flops=2 * m["tokens"] * params
        + x["layers"]["linear"] * m["slots"] * x["step_flops"]
        + positions * x["pair_flops"],
        nbytes=params * x["w"] + m["state_bytes"] + m["kv_bytes"],
        spent_ms=t["programs"][program]["mean_ms"],
        weight_bytes=params * x["w"], state_bytes=m["state_bytes"],
        kv_bytes=m["kv_bytes"])
