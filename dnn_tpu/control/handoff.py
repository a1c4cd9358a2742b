"""Prefill->decode KV handoff: the wire format.

Disaggregated serving (ROADMAP item 1's second leg) moves the prompt's
computed KV from a PREFILL replica to a DECODE replica. The payload is
`ContinuousBatcher.export_prefill`'s output — the transient row cache's
leaves (the same pytree `submit` builds during convoy admission) plus
the final chunk's true-last logit row (so the decode side samples the
first token exactly as the convoy path would, draw-for-draw) — packed
here into ONE 1-D uint8 tensor so it rides the existing SendTensor
wire message on the negotiated transport's grpc rung unchanged.
(The shm/device rungs would move these bytes zero-copy, but the LM
daemon declines negotiation today — explicit shm/device against it
fails loud, exactly like every other unprovable rung; ROADMAP item 2's
paged-block migration is the real zero-copy fix.)

Format: magic + length-prefixed JSON header (leaf shapes/dtypes, the
geometry fingerprint both sides must agree on) + the raw leaf bytes in
C order. Non-numpy cache dtypes ship viewed as same-width integers
(bfloat16 <-> uint16); int4 caches are rejected at export — their
packed jax representation has no stable host view to ship.

Pure numpy + stdlib; both the router (no jax) and the serving stack
import it.
"""

from __future__ import annotations

import json
from typing import Dict, List, Tuple

import numpy as np

__all__ = ["pack", "unpack", "HandoffFormatError"]

_MAGIC = b"dnnkv1\n"

# dtypes shipped as themselves; anything else must have a registered
# same-width integer view (below) or is rejected loud
_VIEW_AS = {"bfloat16": "uint16"}


class HandoffFormatError(ValueError):
    """A payload this module cannot pack or parse — corrupt bytes, an
    unsupported cache dtype, or a header/byte-length mismatch. A
    ValueError so server endpoints map it to INVALID_ARGUMENT."""


def _dtype_name(arr: np.ndarray) -> str:
    return arr.dtype.name


def _resolve_dtype(name: str) -> np.dtype:
    try:
        return np.dtype(name)
    except TypeError:
        pass
    import ml_dtypes  # jax dependency; only needed for bf16 payloads

    try:
        return np.dtype(getattr(ml_dtypes, name))
    except AttributeError:
        raise HandoffFormatError(
            f"handoff payload names unknown dtype {name!r}") from None


def _wire_view(arr: np.ndarray) -> Tuple[np.ndarray, str]:
    """-> (same-bytes array in a wire-safe dtype, original dtype name)."""
    arr = np.ascontiguousarray(arr)
    name = _dtype_name(arr)
    view = _VIEW_AS.get(name)
    if view is not None:
        return arr.view(np.dtype(view)), name
    try:
        np.dtype(name)  # a stock numpy dtype ships as itself
    except TypeError:
        raise HandoffFormatError(
            f"cache dtype {name!r} has no handoff wire form (int4 "
            "caches cannot hand off; serve the prefill/decode split "
            "with f32/bf16/int8 KV)") from None
    return arr, name


def pack(payload: Dict) -> np.ndarray:
    """{'row': [leaves], 'hidden_row': (C,), 'prompt_len': int,
    'fingerprint': dict} -> one 1-D uint8 array (the wire tensor).
    `hidden_row` is the last block's output at the prompt's last token:
    the decode replica's finish program applies the head to it."""
    leaves: List[np.ndarray] = [np.asarray(x) for x in payload["row"]]
    hidden = np.ascontiguousarray(np.asarray(payload["hidden_row"]))
    chunks, specs = [], []
    for leaf in leaves + [hidden]:
        wire, name = _wire_view(leaf)
        chunks.append(wire.tobytes())
        specs.append({"shape": list(leaf.shape), "dtype": name,
                      "bytes": len(chunks[-1])})
    header = json.dumps({
        "v": 2,  # 1 carried a logits row (V values) where the hidden is
        "prompt_len": int(payload["prompt_len"]),
        "fingerprint": payload.get("fingerprint") or {},
        "leaves": specs[:-1],
        "hidden": specs[-1],
    }).encode()
    buf = b"".join([_MAGIC, len(header).to_bytes(4, "big"), header]
                   + chunks)
    return np.frombuffer(buf, np.uint8)


def _read_leaf(body: memoryview, off: int, spec: dict
               ) -> Tuple[np.ndarray, int]:
    n = int(spec["bytes"])
    if off + n > len(body):
        raise HandoffFormatError(
            "handoff payload truncated: header promises more leaf "
            "bytes than the tensor carries")
    dt = _resolve_dtype(spec["dtype"])
    wire_dt = np.dtype(_VIEW_AS.get(spec["dtype"], spec["dtype"]))
    arr = np.frombuffer(body[off:off + n], wire_dt)
    if wire_dt is not dt and wire_dt != dt:
        arr = arr.view(dt)
    try:
        arr = arr.reshape(spec["shape"])
    except ValueError:
        raise HandoffFormatError(
            f"handoff leaf bytes do not match shape {spec['shape']} "
            f"dtype {spec['dtype']}") from None
    return arr, off + n


def unpack(buf) -> Dict:
    """Inverse of pack: the wire tensor -> {'row': [leaves],
    'hidden_row', 'prompt_len', 'fingerprint'}. Raises
    HandoffFormatError (a ValueError) on anything malformed — a decode
    replica must answer INVALID_ARGUMENT, never adopt garbage KV."""
    raw = np.asarray(buf, np.uint8).tobytes()
    if not raw.startswith(_MAGIC):
        raise HandoffFormatError(
            "not a KV handoff payload (bad magic) — was this tensor "
            "produced by ContinuousBatcher.export_prefill?")
    at = len(_MAGIC)
    if len(raw) < at + 4:
        raise HandoffFormatError("handoff payload truncated (no header)")
    hlen = int.from_bytes(raw[at:at + 4], "big")
    at += 4
    try:
        head = json.loads(raw[at:at + hlen].decode())
    except (ValueError, UnicodeDecodeError):
        raise HandoffFormatError(
            "handoff header is not valid JSON") from None
    at += hlen
    body = memoryview(raw)
    leaves = []
    off = at
    for spec in head.get("leaves", []):
        leaf, off = _read_leaf(body, off, spec)
        leaves.append(leaf)
    if "hidden" not in head:
        raise HandoffFormatError(
            "handoff payload carries no hidden row (version "
            f"{head.get('v')!r}: a prefill replica of an older version "
            "sends a logits row)")
    hidden, off = _read_leaf(body, off, head["hidden"])
    return {
        "row": leaves,
        "hidden_row": hidden,
        "prompt_len": int(head["prompt_len"]),
        "fingerprint": head.get("fingerprint") or {},
    }
