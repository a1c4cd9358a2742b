"""The table of peaks and the arithmetic of operations and bytes.

Peaks are the published ones (Google Cloud documentation, "TPU v5e": 197
TFLOP/s bf16, 819 GB/s HBM), keyed by JAX's `device_kind`; a kind that is
not in the table is an error, not a default. The arithmetic is copied from
`dnn_tpu/utils/flops.py` (PR 23) so that no later PR can move the
yardstick; the original is listed in PERF.md's open questions.
"""

from __future__ import annotations

__all__ = ["PEAKS", "peaks_for", "gpt_forward_flops", "gpt_step_weight_bytes",
           "kv_bytes_per_pos", "decode_step_least_s"]

PEAKS = {
    "TPU v5 lite": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
    "TPU v5e": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
}


def peaks_for(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks known for device kind {device_kind!r}")
    return PEAKS[device_kind]


def gpt_forward_flops(cfg: dict, batch: int, seq: int) -> float:
    """Forward FLOPs of one GPT batch: per layer 24*T*C^2 of linear
    matmuls (qkv 6, attention projection 2, MLP 8 + 8) plus 4*T^2*C of
    score and value matmuls, plus the 2*T*C*V head."""
    c, l, v = cfg["n_embd"], cfg["n_layer"], cfg["vocab_size"]
    per_seq = l * (24 * seq * c * c + 4 * seq * seq * c) + 2 * seq * c * v
    return float(batch) * per_seq


def gpt_step_weight_bytes(cfg: dict, bytes_per_param: float) -> float:
    """Bytes of the weights one decode step must read: every block
    (12*C^2 of kernels, 13*C of biases and norms), the final norm and the
    output head (V*C, held apart from the embedding table). The embedding
    tables are gathered by row and are not counted."""
    c, l, v = cfg["n_embd"], cfg["n_layer"], cfg["vocab_size"]
    return float(bytes_per_param) * (l * (12 * c * c + 13 * c) + 2 * c + v * c)


def kv_bytes_per_pos(cfg: dict, kv_bytes: float) -> float:
    """Bytes one cache position holds: K and V rows of every layer."""
    return float(2 * cfg["n_layer"] * cfg["n_embd"] * kv_bytes)


def decode_step_least_s(cfg: dict, *, tokens: float, live_positions: float,
                        bytes_per_param: float, kv_bytes: float,
                        peaks: dict) -> dict:
    """The least time one decode step can take on the chip: the larger of
    its operations over peak FLOP/s and its bytes (weights once, every
    live cache position once) over peak bytes/s — and which of them binds."""
    flops = tokens * (gpt_forward_flops(cfg, 1, 1)
                      + 4.0 * cfg["n_layer"] * cfg["n_embd"]
                      * live_positions / max(tokens, 1.0))
    nbytes = (gpt_step_weight_bytes(cfg, bytes_per_param)
              + live_positions * kv_bytes_per_pos(cfg, kv_bytes))
    t_flops = flops / peaks["bf16_flops_per_s"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return {"least_s": max(t_flops, t_bytes),
            "bound": "bandwidth" if t_bytes >= t_flops else "compute",
            "flops": flops, "bytes": nbytes}
