"""Block migration: move a prefix's KV blocks between replicas.

Three layers, composing the PR 7 transport lessons with the PR 12
handoff idiom — but PER BLOCK, not pack-the-whole-row:

  * the WIRE CODEC (`pack_blocks` / `unpack_blocks`): one uint8 tensor
    = magic + length-prefixed JSON header + raw block leaves in C
    order. Quantized pools migrate AS-IS: int8 K/V ships at 1 byte per
    element and int4 ships NIBBLE-PACKED at half a byte (two values
    per byte — the 4–8x wire win the quantized-KV ladder bought now
    pays on the network too; note the row handoff of PR 12 REJECTS
    int4 outright — block migration supersedes it there). bfloat16
    ships viewed as uint16, exactly like handoff.py.

  * the LEASE state machine (`Lease` / `LeaseTable`, donor side): a
    staged export is a lease — `offered` (bytes staged, optionally
    published to a shm segment) -> `pulling` (the adopter started a
    grpc fetch) -> `adopted` (adopter acked ingest) ->  `released`
    (donor freed the staging). TTL expiry from offered/pulling lands
    in `expired`, whose ONLY exit is the sweep's `lease_reclaim` back
    to released — delete that edge and staged payloads leak forever,
    which is exactly what the protocol gate's PRO002 check reports
    (analysis/protocol.KVLEASE declares this table; both directions
    are model-checked in CI). A dying donor can never corrupt an
    adopter: the adopter ingests only fully-parsed, geometry-verified
    payloads into FRESH local blocks, and a lease that dies mid-pull
    simply expires — the adopter re-prefills, loud, via a
    `kvtier_fallback` flight event.

  * the RUNGS (`publish_shm` / `attach_shm` / `pull_blocks`): on the
    same host the payload crosses as one memcpy through a POSIX shared
    -memory segment whose first bytes carry the offer's nonce — the
    adopter PROVES it attached the right segment by echoing the nonce
    check, the PR 7 proof-carrying idiom; anything else (attach
    failure, nonce mismatch, cross-host) falls back to the grpc fetch
    rung, recorded as a `kvtier_shm_fallback` flight event. `auto`
    degradation, never silent failure.

Pure numpy + stdlib (+ ml_dtypes for bf16 payloads) — no device work
anywhere: the only jax-adjacent import is the flight recorder the rest
of the control plane already uses.
"""

from __future__ import annotations

import json
import os
import secrets
import threading
import time
from typing import Dict, Optional, Tuple

import numpy as np

from dnn_tpu import obs

__all__ = ["pack_blocks", "unpack_blocks", "MigrateFormatError",
           "Lease", "LeaseTable", "publish_shm", "attach_shm",
           "pull_blocks", "DEFAULT_LEASE_TTL_S"]

_MAGIC = b"dnnkvt1\n"
_NONCE_BYTES = 16
DEFAULT_LEASE_TTL_S = 30.0

# dtypes shipped as themselves; registered views for the rest
_VIEW_AS = {"bfloat16": "uint16"}


class MigrateFormatError(ValueError):
    """A payload this module cannot pack or parse — corrupt bytes, an
    unsupported dtype, or a header/byte-length mismatch. A ValueError
    so server endpoints map it to INVALID_ARGUMENT."""


def _resolve_dtype(name: str) -> np.dtype:
    try:
        return np.dtype(name)
    except TypeError:
        pass
    import ml_dtypes  # jax dependency; only needed for bf16 payloads

    try:
        return np.dtype(getattr(ml_dtypes, name))
    except AttributeError:
        raise MigrateFormatError(
            f"kvtier payload names unknown dtype {name!r}") from None


def _pack_nibbles(arr: np.ndarray) -> bytes:
    """int8 VALUES in [-8, 7] -> two's-complement nibbles, two per
    byte (even index = low nibble). Odd element counts pad one zero
    nibble; the header's shape recovers the true count."""
    flat = np.ascontiguousarray(arr, np.int8).reshape(-1)
    if flat.size % 2:
        flat = np.concatenate([flat, np.zeros((1,), np.int8)])
    u = (flat.astype(np.int16) & 0xF).astype(np.uint8)
    return (u[0::2] | (u[1::2] << 4)).tobytes()


def _unpack_nibbles(raw: np.ndarray, n: int) -> np.ndarray:
    """Inverse of _pack_nibbles -> n int8 values in [-8, 7]."""
    lo = (raw & 0xF).astype(np.int8)
    hi = ((raw >> 4) & 0xF).astype(np.int8)
    out = np.empty((raw.size * 2,), np.int8)
    out[0::2], out[1::2] = lo, hi
    out = np.where(out > 7, out - 16, out).astype(np.int8)
    return out[:n]


def _leaf_dtype_name(fingerprint: dict, name: str, arr: np.ndarray
                     ) -> str:
    """The TRUE cache dtype of a leaf — int4 pools cross the host
    boundary as int8 values, so the fingerprint (not the host array)
    is the authority."""
    spec = (fingerprint or {}).get("leaves", {}).get(name)
    return spec[1] if spec else arr.dtype.name


def pack_blocks(payload: Dict) -> np.ndarray:
    """`ContinuousBatcher.kvtier_export`'s dict -> one 1-D uint8 wire
    tensor. Leaves ride raw C-order bytes; int4 leaves nibble-pack."""
    fp = payload.get("fingerprint") or {}
    tokens = np.ascontiguousarray(payload["tokens"], np.int32)
    chunks = [tokens.tobytes()]
    leaf_specs = {}
    for name in sorted(payload["leaves"]):
        arr = np.ascontiguousarray(payload["leaves"][name])
        true_dt = _leaf_dtype_name(fp, name, arr)
        if true_dt == "int4":
            wire = _pack_nibbles(arr)
            enc = "nibble"
        else:
            view = _VIEW_AS.get(true_dt)
            if view is not None:
                wire = arr.view(np.dtype(view)).tobytes()
            else:
                try:
                    np.dtype(true_dt)
                except TypeError:
                    raise MigrateFormatError(
                        f"cache dtype {true_dt!r} has no kvtier wire "
                        "form") from None
                wire = arr.tobytes()
            enc = "raw"
        chunks.append(wire)
        leaf_specs[name] = {"shape": list(arr.shape), "dtype": true_dt,
                            "enc": enc, "bytes": len(wire)}
    lr = payload.get("hidden_rows") or {}
    lr_idx = sorted(int(i) for i in lr)
    lr_arr = (np.stack([np.asarray(lr[i], np.float32) for i in lr_idx])
              if lr_idx else np.zeros((0, 0), np.float32))
    chunks.append(np.ascontiguousarray(lr_arr).tobytes())
    header = json.dumps({
        "v": 1,
        "block_len": int(payload["block_len"]),
        "n_tokens": int(tokens.size),
        "fingerprint": fp,
        "leaves": leaf_specs,
        "hidden_idx": lr_idx,
        "hidden_shape": list(lr_arr.shape),
    }).encode()
    buf = b"".join([_MAGIC, len(header).to_bytes(4, "big"), header]
                   + chunks)
    return np.frombuffer(buf, np.uint8)


def unpack_blocks(buf) -> Dict:
    """Inverse of pack_blocks. Raises MigrateFormatError (a ValueError)
    on anything malformed — an adopter must answer INVALID_ARGUMENT,
    never ingest garbage blocks."""
    raw = np.asarray(buf, np.uint8).tobytes()
    if not raw.startswith(_MAGIC):
        raise MigrateFormatError(
            "not a kvtier block payload (bad magic) — was this tensor "
            "produced by pack_blocks?")
    at = len(_MAGIC)
    if len(raw) < at + 4:
        raise MigrateFormatError("kvtier payload truncated (no header)")
    hlen = int.from_bytes(raw[at:at + 4], "big")
    at += 4
    try:
        head = json.loads(raw[at:at + hlen].decode())
    except (ValueError, UnicodeDecodeError):
        raise MigrateFormatError(
            "kvtier header is not valid JSON") from None
    at += hlen
    body = memoryview(raw)
    n_tok = int(head["n_tokens"])
    if at + n_tok * 4 > len(body):
        raise MigrateFormatError("kvtier payload truncated (tokens)")
    tokens = np.frombuffer(body[at:at + n_tok * 4], np.int32)
    at += n_tok * 4
    leaves = {}
    for name in sorted(head.get("leaves", {})):
        spec = head["leaves"][name]
        n = int(spec["bytes"])
        if at + n > len(body):
            raise MigrateFormatError(
                f"kvtier payload truncated (leaf {name})")
        shape = tuple(spec["shape"])
        count = int(np.prod(shape)) if shape else 1
        wire = np.frombuffer(body[at:at + n], np.uint8)
        if spec.get("enc") == "nibble":
            arr = _unpack_nibbles(wire, count).reshape(shape)
        else:
            dt = _resolve_dtype(spec["dtype"])
            wire_dt = np.dtype(_VIEW_AS.get(spec["dtype"],
                                            spec["dtype"]))
            arr = np.frombuffer(body[at:at + n], wire_dt)
            if wire_dt != dt:
                arr = arr.view(dt)
            try:
                arr = arr.reshape(shape)
            except ValueError:
                raise MigrateFormatError(
                    f"kvtier leaf {name} bytes do not match shape "
                    f"{shape} dtype {spec['dtype']}") from None
        leaves[name] = arr
        at += n
    lr_shape = tuple(head.get("hidden_shape") or (0, 0))
    lr_count = int(np.prod(lr_shape)) if lr_shape else 0
    lr_arr = np.frombuffer(body[at:at + lr_count * 4], np.float32)
    if lr_arr.size != lr_count:
        raise MigrateFormatError("kvtier payload truncated (hidden rows)")
    lr_arr = lr_arr.reshape(lr_shape) if lr_count else lr_arr
    hidden_rows = {int(i): lr_arr[j]
                   for j, i in enumerate(head.get("hidden_idx", []))}
    return {"tokens": tokens, "block_len": int(head["block_len"]),
            "leaves": leaves, "hidden_rows": hidden_rows,
            "fingerprint": head.get("fingerprint") or {}}


# ----------------------------------------------------------------------
# shm rung: same-host zero-serialization block transfer
# ----------------------------------------------------------------------

#: segment names THIS process created (publish_shm): attach_shm must
#: not deregister those from the resource tracker — the creator's own
#: unlink still needs the registration (in-process attach = tests)
_OWN_SHM_NAMES: set = set()


def publish_shm(data: bytes) -> Optional[Tuple[str, str, object]]:
    """Stage `data` in a fresh POSIX shm segment: first _NONCE_BYTES
    hold a random nonce the adopter must verify (proof it attached THE
    offered segment, not a stale or hostile one — the PR 7 handshake
    idiom). Returns (name, nonce_hex, segment) or None when shm is
    unavailable on this platform."""
    try:
        from multiprocessing import shared_memory
    except ImportError:  # pragma: no cover — ancient platform
        return None
    nonce = secrets.token_bytes(_NONCE_BYTES)
    try:
        seg = shared_memory.SharedMemory(
            create=True, size=_NONCE_BYTES + len(data))
        seg.buf[:_NONCE_BYTES] = nonce
        seg.buf[_NONCE_BYTES:_NONCE_BYTES + len(data)] = data
    except OSError:  # pragma: no cover — /dev/shm full or missing
        return None
    _OWN_SHM_NAMES.add(seg.name)
    return seg.name, nonce.hex(), seg


def attach_shm(name: str, nonce_hex: str, nbytes: int) -> bytes:
    """Adopter-side memcpy out of the donor's segment. Verifies the
    nonce before reading a byte of payload; any failure raises (the
    caller falls back to the grpc fetch rung, loud)."""
    from multiprocessing import shared_memory

    seg = shared_memory.SharedMemory(name=name)
    if name not in _OWN_SHM_NAMES:
        # CPython registers ATTACHED segments with its resource
        # tracker as if it owned them; the DONOR owns and unlinks
        # this one, so deregister or the adopter's interpreter warns
        # about (and may try to clean) a segment that was never its
        # to free. Same-process attaches (tests) skip this — the
        # creator's unlink still needs its registration.
        try:
            from multiprocessing import resource_tracker

            resource_tracker.unregister(seg._name, "shared_memory")
        except Exception:  # noqa: BLE001 — tracker internals vary by
            pass           # version; worst case is a shutdown warning
    try:
        if bytes(seg.buf[:_NONCE_BYTES]).hex() != nonce_hex:
            raise ValueError(
                f"shm segment {name} nonce mismatch — not the offered "
                "lease")
        return bytes(seg.buf[_NONCE_BYTES:_NONCE_BYTES + nbytes])
    finally:
        seg.close()


# ----------------------------------------------------------------------
# the lease state machine (donor side)
# ----------------------------------------------------------------------

class Lease:
    """One staged export. The lifecycle table is DECLARED in
    analysis/protocol.KVLEASE and model-checked both directions — edit
    the two together."""

    def __init__(self, lease_id: str, data: bytes, ttl_s: float):
        self.lease_id = lease_id
        self.data: Optional[bytes] = data
        self.nbytes = len(data)
        self.ttl_s = float(ttl_s)
        self.t_offer = time.monotonic()
        self.shm_name: Optional[str] = None
        self.shm_nonce: Optional[str] = None
        self._seg = None
        self.state = "offered"

    def _free(self):
        self.data = None
        if self._seg is not None:
            try:
                self._seg.close()
                self._seg.unlink()
            except OSError:  # pragma: no cover — already gone
                pass
            self._seg = None


class LeaseTable:
    """Donor-side staging: offers carry a TTL so an adopter that dies
    mid-pull can never pin staged payloads (or their shm segments)
    forever. Thread-safe — gRPC handler threads offer/fetch/ack, the
    worker's idle sweep expires."""

    def __init__(self, *, ttl_s: float = DEFAULT_LEASE_TTL_S,
                 max_leases: int = 16, use_shm: bool = True):
        self.ttl_s = float(ttl_s)
        self.max_leases = int(max_leases)
        self.use_shm = bool(use_shm)
        self._leases: "Dict[str, Lease]" = {}
        self._lock = threading.Lock()
        self._seq = 0

    def offer(self, data: bytes, *, ttl_s: Optional[float] = None
              ) -> dict:
        """Stage `data`; returns the offer meta the adopter needs:
        {lease, bytes, shm?, nonce?}. Publishes a shm segment when the
        platform has one — the adopter proves attachment via the
        nonce, or falls back to kvfetch."""
        with self._lock:
            self._seq += 1
            lease_id = f"L{os.getpid()}_{self._seq}"
            lease = Lease(lease_id, data, ttl_s or self.ttl_s)
            if self.use_shm:
                pub = publish_shm(data)
                if pub is not None:
                    lease.shm_name, lease.shm_nonce, lease._seg = pub
            self._leases[lease_id] = lease
            # bounded: expire the oldest past-capacity offer NOW (the
            # sweep would get it anyway; capacity must not wait for it)
            while len(self._leases) > self.max_leases:
                oldest = min(self._leases.values(),
                             key=lambda x: x.t_offer)
                self._expire(oldest)
        meta = {"lease": lease_id, "bytes": lease.nbytes}
        if lease.shm_name:
            meta["shm"] = lease.shm_name
            meta["nonce"] = lease.shm_nonce
        return meta

    def fetch(self, lease_id: str) -> bytes:
        """grpc rung: the adopter pulls the staged bytes. offered ->
        pulling. KeyError for unknown/expired leases (the adopter
        re-prefills, loud)."""
        with self._lock:
            lease = self._leases.get(lease_id)
            if lease is None or lease.data is None:
                raise KeyError(lease_id)
            if lease.state == "offered":
                lease.state = "pulling"
                obs.flight.record("lease_pull", lease=lease_id,
                                  bytes=lease.nbytes)
            return lease.data

    def ack(self, lease_id: str) -> bool:
        """The adopter confirmed ingest: -> adopted, then the donor
        releases the staging immediately (-> released). False for
        unknown/expired leases (the ack raced the sweep — harmless,
        the adopter already holds the blocks)."""
        with self._lock:
            lease = self._leases.pop(lease_id, None)
            if lease is None or lease.state in ("expired", "released"):
                return False
            lease.state = "adopted"
            obs.flight.record("lease_adopt", lease=lease_id)
            lease.state = "released"
            lease._free()
            obs.flight.record("lease_release", lease=lease_id)
            return True

    def _expire(self, lease: Lease):
        # under _lock. expired is NOT terminal: its one exit is the
        # reclaim below — delete it and staged payloads (and their shm
        # segments) leak forever, the exact PRO002 shape the protocol
        # gate pins
        lease.state = "expired"
        obs.flight.record("lease_expire", lease=lease.lease_id,
                          bytes=lease.nbytes,
                          age_s=round(time.monotonic() - lease.t_offer,
                                      2),
                          cause="lease_reclaim")
        lease._free()
        lease.state = "released"
        obs.flight.record("lease_reclaim", lease=lease.lease_id,
                          cause="lease_reclaim")
        self._leases.pop(lease.lease_id, None)

    def sweep(self, now: Optional[float] = None) -> int:
        """Expire offers past their TTL; returns how many. Called from
        the serving worker's idle boundary (and before every offer)."""
        now = time.monotonic() if now is None else now
        n = 0
        with self._lock:
            for lease in list(self._leases.values()):
                if lease.state in ("offered", "pulling") \
                        and now - lease.t_offer > lease.ttl_s:
                    self._expire(lease)
                    n += 1
        return n

    @property
    def n_leases(self) -> int:
        return len(self._leases)

    def close(self):
        with self._lock:
            for lease in list(self._leases.values()):
                self._expire(lease)


# ----------------------------------------------------------------------
# adopter-side pull driver (negotiated rungs: shm -> grpc)
# ----------------------------------------------------------------------

def pull_blocks(client, tokens, *, timeout: float = 30.0) -> Dict:
    """Pull a prefix's blocks from a donor replica through `client`
    (a comm.client.NodeClient pointed at the donor): lease the export,
    move the bytes over the best provable rung (shm when the nonce
    checks out, else the grpc fetch), ack, unpack. Raises on any
    failure — the CALLER records `kvtier_fallback` and re-prefills;
    this function never fabricates blocks."""
    meta = client.kv_lease(tokens, timeout=timeout)
    lease_id = meta["lease"]
    data: Optional[bytes] = None
    if meta.get("shm"):
        try:
            data = attach_shm(meta["shm"], meta.get("nonce", ""),
                              int(meta["bytes"]))
        except Exception as e:  # noqa: BLE001 — cross-host / stale
            # segment / nonce mismatch: degrade to the grpc rung, loud
            obs.flight.record("kvtier_shm_fallback",
                              error=f"{type(e).__name__}: {e}"[:160])
    if data is None:
        data = client.kv_fetch(lease_id, timeout=timeout).tobytes()
    payload = unpack_blocks(np.frombuffer(data, np.uint8))
    payload["_wire_bytes"] = len(data)  # the on-the-wire price, for
    # the adopter's migrated-bytes gauges (nibble-packed int4 and int8
    # payloads price at their true half/one byte per element)
    try:
        client.kv_ack(lease_id, timeout=min(timeout, 5.0))
    except Exception:  # noqa: BLE001 — best-effort: the donor's TTL
        # sweep reclaims an unacked lease; the blocks are already ours
        pass
    return payload
