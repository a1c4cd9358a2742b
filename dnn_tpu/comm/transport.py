"""Pluggable inter-stage transport: device-native hops, shm, gRPC.

A gRPC-relayed pipeline is mostly bubble: each hop is a nested unary RPC
held open for the full downstream latency, and every payload round-trips through
host serialization copies. This module makes the hop a NEGOTIATED,
pluggable layer — ROADMAP item 1 — with gRPC demoted to the cross-pod /
reference-interop fallback:

    device   same-process hops move the activation device-to-device with
             no host serialization at all: the jit output rides a
             process-global mailbox as a ticket (tiny gRPC control
             message), and the receiver `jax.device_put`s it onto its
             stage device — the RelayExecutor hop, formalized. For
             mesh-resident activations, `make_hop_program` is the
             compiled ppermute send/recv (XLA CollectivePermute over
             ICI) the SPMD runtime uses; its switch branches are
             PRG001-audited (analysis/program.audit_transport_programs).
    shm      same-host cross-process hops write the payload ONCE into a
             POSIX shared-memory ring slot; the receiver maps a zero-
             copy numpy view. Same-host reachability is PROVEN at
             handshake (the server attaches the client's probe segment
             and echoes a nonce out of it), never inferred from
             hostnames.
    grpc     the reference wire protocol, unchanged bytes (wire.proto),
             now zero-copy at both ends (comm/wirecodec.py) and — when
             both peers are dnn_tpu — non-nested: the streamed Relay
             path acks upstream as soon as a microbatch is accepted, so
             stages overlap across processes (the MPMD schedule,
             arxiv 2412.14374) instead of holding every hop open.

Negotiation is a single SendMessage RPC (sender_id
`dnn_tpu.transport.hello`, JSON offer/accept in the text fields) —
wire-compatible by construction: a reference peer answers with its
normal confirmation string, which fails to parse as an accept, and the
ladder lands on grpc. `auto` walks device -> shm -> grpc and records a
`transport_fallback` flight event when it degrades; an EXPLICIT
`--transport device|shm` that cannot be satisfied fails loud
(TransportMisconfigError), never silently downgrades.

Deadlines follow the negotiated transport: a warm device/shm hop budgets
seconds, not the 30 s gRPC margin sized for serialization + LAN + jit
compiles (hop_budget_s). Streamed relay hops are non-idempotent (the
ack already released the upstream sender) and are never retried.
"""

from __future__ import annotations

import json
import logging
import socket
import threading
import uuid
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from dnn_tpu import obs
from dnn_tpu.chaos import inject as _chaos_inject
from dnn_tpu.comm import wirecodec as wc
from dnn_tpu.utils.metrics import labeled

log = logging.getLogger("dnn_tpu.comm")

TRANSPORTS = ("auto", "grpc", "shm", "device")

#: channel/server options every dnn_tpu gRPC endpoint shares: the
#: stock 4 MB message cap silently breaks KV-sized unary payloads —
#: a gpt2 row handoff (control/handoff.py) packs ~7 MB, and kvtier
#: block payloads (kvtier/migrate.py) scale with prefix length — so
#: both sides raise it to one bound, high enough for any single
#: tensor the serving stack ships, low enough to still catch runaway
#: frames. (The streamed relay chunks its frames and never needed
#: this; unary KV tensors cannot chunk.)
GRPC_MSG_OPTIONS = [
    ("grpc.max_receive_message_length", 64 * 1024 * 1024),
    ("grpc.max_send_message_length", 64 * 1024 * 1024),
]

# The negotiation side-channel rides SendMessage with this sender_id
# prefix; every dnn_tpu server (stage + LM daemon) routes it to
# answer_hello / decline_hello instead of its normal text handling.
HELLO_SENDER = "dnn_tpu.transport.hello"

# Ticket payloads ride the ordinary Tensor message with these dtype
# markers. They are only ever sent AFTER a successful negotiation, so a
# reference peer never sees one; an un-negotiated ticket arriving at a
# dnn_tpu server is a loud INVALID_ARGUMENT, not a silent mis-decode.
TICKET_DTYPE_DEV = "dnn.dev1"
TICKET_DTYPE_SHM = "dnn.shm1"
TICKET_DTYPES = (TICKET_DTYPE_DEV, TICKET_DTYPE_SHM)

# One token per process / per host: the proof substrate for the device
# (same-process) rung; shm is proven by the probe-segment attach, not by
# token comparison.
PROC_TOKEN = uuid.uuid4().hex


def host_token() -> str:
    try:
        with open("/proc/sys/kernel/random/boot_id") as f:
            boot = f.read().strip()
    except OSError:
        boot = "-"
    return f"{socket.gethostname()}:{boot}"


# ----------------------------------------------------------------------
# deadline budgets (satellite: per-hop deadlines follow the transport)
# ----------------------------------------------------------------------

# The reference-compatible gRPC per-stage slice (moved here from
# comm/service.py, which re-exports it): compute budget for one stage's
# jit-compiled forward (first-call XLA compiles included) plus the gRPC
# wire margin (serialize + LAN + deserialize of MB-scale payloads).
STAGE_COMPUTE_BUDGET_S = 25.0
HOP_MARGIN_S = {"grpc": 5.0, "shm": 1.0, "device": 0.5}
PER_STAGE_BUDGET_S = STAGE_COMPUTE_BUDGET_S + HOP_MARGIN_S["grpc"]  # 30.0
# After a hop's first successful send, the downstream stage's programs
# are compiled; device/shm hops then budget per-stage seconds instead of
# inheriting the compile-inclusive slice. grpc keeps the full slice
# always — its budget arithmetic is part of the reference-compatible
# contract (client.pipeline_budget strictly dominating the first hop's
# server-side budget).
WARM_STAGE_COMPUTE_BUDGET_S = 5.0


def hop_budget_s(transport: str, downstream_stages: int, *,
                 warm: bool = False) -> float:
    """Overall budget for one hop covering `downstream_stages` stages,
    derived from the NEGOTIATED transport. `warm`: at least one send on
    this hop already succeeded (device/shm only — see above)."""
    name = "grpc" if transport not in HOP_MARGIN_S else transport
    compute = STAGE_COMPUTE_BUDGET_S
    if warm and name != "grpc":
        compute = WARM_STAGE_COMPUTE_BUDGET_S
    return (compute + HOP_MARGIN_S[name]) * max(downstream_stages, 1)


class TransportError(RuntimeError):
    """Base for transport negotiation/resolution failures."""


class TransportMisconfigError(TransportError):
    """An EXPLICITLY requested transport cannot be satisfied on this
    hop (e.g. --transport device across processes). Fail-loud by
    design: auto-degrading an explicit request would hide a deployment
    error behind a 100x slower wire."""


# ----------------------------------------------------------------------
# device mailbox (same-process zero-serialization hops)
# ----------------------------------------------------------------------

class _DeviceMailbox:
    """Process-global rendezvous for device-resident activations: the
    sender parks the jit output under a ticket, the receiving stage
    (same process, possibly another thread/event loop) picks it up and
    `device_put`s it onto its own stage device. Entries are peeked, not
    popped, so a transport-level retry can resend the same ticket; the
    SENDER drops the entry once the hop's response lands."""

    def __init__(self):
        self._lock = threading.Lock()
        self._entries: Dict[str, Any] = {}

    def put(self, value) -> str:
        ticket = uuid.uuid4().hex
        with self._lock:
            self._entries[ticket] = value
        return ticket

    def peek(self, ticket: str):
        with self._lock:
            return self._entries.get(ticket)

    def drop(self, ticket: str):
        with self._lock:
            self._entries.pop(ticket, None)

    def __len__(self):
        with self._lock:
            return len(self._entries)


MAILBOX = _DeviceMailbox()


def make_hop_program(mesh, axis_name: str = "stage"):
    """Compiled device send/recv for mesh-resident activations: ONE
    program, `lax.switch` over the hop index, branch i a single
    `lax.ppermute` moving stage i's row to stage i+1 (XLA
    CollectivePermute over ICI on real pods). Every branch must issue
    the identical collective sequence or ranks deadlock — the same SPMD
    contract as the pipeline's stage switch, and the analyzer's PRG001
    pass audits exactly this program
    (analysis/program.audit_transport_programs).

    Returns `hop(hop_index, buf)` jitted; `buf` is sharded P(axis_name)
    with one (1, ...) row per stage."""
    import jax
    from jax import lax
    from jax.sharding import PartitionSpec as P

    n = mesh.shape[axis_name]
    if n < 2:
        raise ValueError(f"hop program needs >= 2 stages on '{axis_name}'")

    def branch(i):
        def b(x):
            return lax.ppermute(x, axis_name, [(i, i + 1)])
        return b

    branches = [branch(i) for i in range(n - 1)]

    def per_device(hop, buf):
        return lax.switch(hop, branches, buf)

    shuttled = jax.shard_map(
        per_device, mesh=mesh, in_specs=(P(), P(axis_name)),
        out_specs=P(axis_name), check_vma=False)
    return jax.jit(shuttled)


# ----------------------------------------------------------------------
# shm ring (same-host cross-process hops)
# ----------------------------------------------------------------------

class _ShmSlot:
    def __init__(self, nbytes: int):
        from multiprocessing import shared_memory

        self.shm = shared_memory.SharedMemory(
            create=True, size=max(int(nbytes), 1),
            name=f"dnn_tpu_{uuid.uuid4().hex[:16]}")
        self.busy = False

    def ensure(self, nbytes: int):
        if self.shm.size < nbytes:
            old = self.shm
            self.shm = None
            try:
                old.unlink()
                old.close()
            except (OSError, BufferError):
                pass
            from multiprocessing import shared_memory

            self.shm = shared_memory.SharedMemory(
                create=True, size=int(nbytes),
                name=f"dnn_tpu_{uuid.uuid4().hex[:16]}")

    def close(self):
        if self.shm is not None:
            try:
                self.shm.unlink()
                self.shm.close()
            except (OSError, BufferError):
                # BufferError: a receiver-side zero-copy view still pins
                # the mapping; the segment is already unlinked, and the
                # mmap goes with the last view.
                pass
            self.shm = None


class ShmRing:
    """Sender-owned ring of reusable shared-memory slots, one hop's
    in-flight window. A slot is busy from `write` until the receiving
    side's response/ack frees it (the receiver consumes the payload into
    device memory synchronously inside its handler, so a freed slot is
    safe to overwrite). Segments grow in place (new name) when a payload
    outsizes them; unlinked on close."""

    def __init__(self, slots: int = 4):
        self._slots: List[Optional[_ShmSlot]] = [None] * max(slots, 1)
        self._lock = threading.Lock()
        self._free = threading.Condition(self._lock)

    def _acquire_locked(self, nbytes: int):
        """Find + mark a free slot; caller holds the lock. None if all
        slots are busy."""
        for i, s in enumerate(self._slots):
            if s is None or not s.busy:
                if s is None:
                    s = self._slots[i] = _ShmSlot(nbytes)
                else:
                    s.ensure(nbytes)
                s.busy = True
                return i, s
        return None

    def write(self, view: memoryview, timeout: float = 30.0) -> Tuple[str, int]:
        """Copy `view` into a free slot (THE one host copy of the shm
        path); returns (segment_name, slot_index). BLOCKS while all
        slots are in flight — callers on an event loop must use
        write_nowait first and fall back to a worker thread."""
        with self._free:
            if not self._free.wait_for(
                    lambda: any(s is None or not s.busy for s in self._slots),
                    timeout=timeout):
                raise TransportError(
                    "shm ring exhausted: no slot freed within "
                    f"{timeout}s ({len(self._slots)} slots)")
            idx, slot = self._acquire_locked(len(view))
        slot.shm.buf[: len(view)] = view
        return slot.shm.name, idx

    def write_nowait(self, view: memoryview) -> Optional[Tuple[str, int]]:
        """Non-blocking write: None when every slot is in flight (the
        event-loop fast path — a free slot costs one memcpy, never a
        wait)."""
        with self._free:
            got = self._acquire_locked(len(view))
            if got is None:
                return None
            idx, slot = got
        slot.shm.buf[: len(view)] = view
        return slot.shm.name, idx

    def release(self, idx: int):
        with self._free:
            s = self._slots[idx]
            if s is not None:
                s.busy = False
            self._free.notify_all()

    def close(self):
        with self._lock:
            for s in self._slots:
                if s is not None:
                    s.close()
            self._slots = [None] * len(self._slots)


# ----------------------------------------------------------------------
# senders (the per-hop client side, shared by NodeClient and the stage
# server's downstream forward)
# ----------------------------------------------------------------------

class Sender:
    """One negotiated hop. `make_request(arr, request_id)` builds the
    wire message (inline tensor or ticket); `sent_ok`/`cleanup` manage
    per-send resources; senders are thread-compatible for the unary
    path (one in-flight send per sender at a time on shm)."""

    name = "grpc"
    zero_serialization = False

    def make_request(self, arr, request_id: str) -> wc.TensorRequest:
        raise NotImplementedError

    def make_request_nowait(self, arr, request_id: str
                            ) -> Optional[wc.TensorRequest]:
        """Non-blocking variant for event-loop callers: None when the
        send would have to WAIT for a resource (shm ring full) — the
        caller then retries `make_request` off-loop. Default: nothing
        to wait on."""
        return self.make_request(arr, request_id)

    def sent_ok(self, request: wc.TensorRequest):
        """Called once the hop's response landed (payload consumed)."""

    def cleanup(self, request: wc.TensorRequest):
        """Called when the send is abandoned (terminal failure)."""

    def close(self):
        pass


class GrpcSender(Sender):
    name = "grpc"

    def make_request(self, arr, request_id: str) -> wc.TensorRequest:
        return wc.TensorRequest(request_id=request_id,
                                tensor=wc.make_tensor(arr))


class DeviceSender(Sender):
    """Same-process hop: the activation never leaves device-resident
    form. `device` (optional) is the RECEIVING stage's device — pinning
    the transfer here overlaps it with the control message instead of
    serializing it into the receiver's handler."""

    name = "device"
    zero_serialization = True

    def __init__(self, device=None):
        self.device = device

    def make_request(self, arr, request_id: str) -> wc.TensorRequest:
        val = arr
        if self.device is not None:
            import jax

            val = jax.device_put(arr, self.device)
        ticket = MAILBOX.put(val)
        return wc.TensorRequest(
            request_id=request_id,
            tensor=wc.Tensor(tensor_data=ticket.encode(),
                             shape=(), dtype=TICKET_DTYPE_DEV))

    def _ticket(self, request) -> str:
        return bytes(request.tensor.tensor_data).decode()

    def sent_ok(self, request):
        MAILBOX.drop(self._ticket(request))

    cleanup = sent_ok


class ShmSender(Sender):
    """Same-host cross-process hop: one host copy into a shared ring
    slot; the ticket (segment name + layout) rides the control RPC."""

    name = "shm"

    def __init__(self, slots: int = 4):
        self._ring = ShmRing(slots)

    @staticmethod
    def _ticket(request_id: str, seg: str, idx: int, view, shape, dtype
                ) -> wc.TensorRequest:
        meta = json.dumps({"seg": seg, "slot": idx, "nbytes": len(view),
                           "shape": list(shape), "dtype": dtype})
        return wc.TensorRequest(
            request_id=request_id,
            tensor=wc.Tensor(tensor_data=meta.encode(),
                             shape=(), dtype=TICKET_DTYPE_SHM))

    def make_request(self, arr, request_id: str) -> wc.TensorRequest:
        view, shape, dtype, _copied = wc.tensor_payload(arr)
        seg, idx = self._ring.write(view)
        return self._ticket(request_id, seg, idx, view, shape, dtype)

    def make_request_nowait(self, arr, request_id: str
                            ) -> Optional[wc.TensorRequest]:
        view, shape, dtype, _copied = wc.tensor_payload(arr)
        got = self._ring.write_nowait(view)
        if got is None:
            return None
        return self._ticket(request_id, got[0], got[1], view, shape, dtype)

    def _slot(self, request) -> int:
        return json.loads(bytes(request.tensor.tensor_data).decode())["slot"]

    def sent_ok(self, request):
        self._ring.release(self._slot(request))

    cleanup = sent_ok

    def close(self):
        self._ring.close()


# ----------------------------------------------------------------------
# negotiation
# ----------------------------------------------------------------------

def _ladder(transport: str) -> List[str]:
    if transport == "auto":
        return ["device", "shm"]
    if transport in ("device", "shm"):
        return [transport]
    return []


def build_offer(transport: str) -> Tuple[dict, Optional[object]]:
    """-> (offer_dict, probe_shm_or_None). The caller owns the probe
    segment (close+unlink after the handshake)."""
    want = _ladder(transport)
    offer = {"v": 1, "want": want, "proc": PROC_TOKEN,
             "host": host_token(), "nonce": uuid.uuid4().hex}
    probe = None
    if "shm" in want:
        try:
            from multiprocessing import shared_memory

            probe = shared_memory.SharedMemory(
                create=True, size=64,
                name=f"dnn_tpu_probe_{uuid.uuid4().hex[:12]}")
            nb = offer["nonce"].encode()
            probe.buf[: len(nb)] = nb
            probe.buf[len(nb)] = 0
            offer["shm_probe"] = probe.name
        except (OSError, ImportError, ValueError):
            offer["want"] = [w for w in want if w != "shm"]
    return offer, probe


def answer_hello(text: str, *, allow: Tuple[str, ...] = ("device", "shm"),
                 stage: str = "") -> str:
    """Server side of the handshake: pick the highest rung of the
    client's ladder this process can PROVE. Returns the accept/decline
    JSON (the SendMessage confirmation_text)."""
    try:
        offer = json.loads(text)
        want = list(offer.get("want", ()))
        nonce = str(offer.get("nonce", ""))
    except (json.JSONDecodeError, AttributeError, TypeError):
        return json.dumps({"v": 1, "ok": False, "reason": "bad offer"})
    m = obs.metrics()
    if "device" in want and "device" in allow \
            and offer.get("proc") == PROC_TOKEN:
        chosen = "device"
    elif "shm" in want and "shm" in allow and offer.get("shm_probe"):
        # proof, not inference: attach the client's probe segment and
        # read the nonce out of the mapped bytes
        try:
            from multiprocessing import shared_memory

            probe = shared_memory.SharedMemory(name=offer["shm_probe"])
            try:
                raw = bytes(probe.buf[:64]).split(b"\x00", 1)[0].decode()
            finally:
                probe.close()
            if raw != nonce:
                return json.dumps({"v": 1, "ok": False, "relay": True,
                                   "reason": "shm probe nonce mismatch"})
            chosen = "shm"
        except (OSError, ValueError):
            return json.dumps({"v": 1, "ok": False, "relay": True,
                               "reason": "shm probe unreachable"})
    else:
        # declines still advertise the streamed Relay RPC: a cross-host
        # dnn_tpu peer keeps the non-nested schedule on the grpc rung
        return json.dumps({"v": 1, "ok": False, "relay": True,
                           "reason": "no common transport"})
    if m is not None:
        m.inc(labeled("comm.transport_negotiations_total",
                      chosen=chosen, role="server", stage=stage or "-"))
    return json.dumps({"v": 1, "ok": True, "chosen": chosen,
                       "proc": PROC_TOKEN, "nonce": nonce, "relay": True})


def decline_hello(reason: str = "transport negotiation not supported "
                                "on this endpoint") -> str:
    """For dnn_tpu endpoints that opt out (the LM daemon's text front —
    prompt payloads are tiny); the client's ladder lands on grpc."""
    return json.dumps({"v": 1, "ok": False, "reason": reason})


class Negotiated:
    """Outcome of one hop's handshake. `relay_ok` is only meaningful
    when `relay_known` — a hop that never completed a handshake
    (explicit grpc, hello transport failure) probes the Relay RPC
    lazily instead of assuming either way."""

    __slots__ = ("name", "sender", "relay_ok", "relay_known", "reason")

    def __init__(self, name: str, sender: Sender, *, relay_ok: bool = False,
                 relay_known: bool = False, reason: str = ""):
        self.name = name
        self.sender = sender
        self.relay_ok = relay_ok
        self.relay_known = relay_known
        self.reason = reason


def close_probe(probe):
    """Release the handshake's shm probe segment (idempotent)."""
    if probe is not None:
        try:
            probe.close()
            probe.unlink()
        except (OSError, BufferError):
            pass


def conclude(offer: dict, reply_text: str, *, transport: str,
             target: str = "", device=None, shm_slots: int = 4
             ) -> Negotiated:
    """Shared handshake tail: interpret the peer's SendMessage reply for
    `offer`. Raises TransportMisconfigError when an explicit request
    cannot be satisfied; `auto` degrades to grpc with a
    `transport_fallback` flight event (a silent fallback must be
    observable, never invisible)."""
    want = list(offer.get("want", ()))
    if transport != "auto" and not want:
        raise TransportMisconfigError(
            f"transport={transport!r} unavailable on this host "
            f"(shared memory unsupported)")
    try:
        acc = json.loads(reply_text)
        if not isinstance(acc, dict):
            raise TypeError
    except (json.JSONDecodeError, TypeError):
        acc = {"ok": False, "reason": "peer is not transport-aware "
                                      "(reference protocol)"}
    ok = bool(acc.get("ok")) and acc.get("chosen") in want \
        and acc.get("nonce") == offer.get("nonce")
    m = obs.metrics()
    if ok:
        chosen = acc["chosen"]
        sender: Sender = DeviceSender(device) if chosen == "device" \
            else ShmSender(shm_slots)
        if m is not None:
            m.inc(labeled("comm.transport_negotiations_total",
                          chosen=chosen, role="client", target=target))
        return Negotiated(chosen, sender, relay_ok=bool(acc.get("relay")),
                          relay_known=True)
    reason = str(acc.get("reason", "declined"))
    if transport != "auto":
        raise TransportMisconfigError(
            f"transport={transport!r} to {target or 'peer'} refused: "
            f"{reason}")
    obs.flight.record("transport_fallback", target=target,
                      wanted=want, chosen="grpc", reason=reason)
    if m is not None:
        m.inc(labeled("comm.transport_negotiations_total",
                      chosen="grpc", role="client", target=target))
    log.info("transport negotiation with %s fell back to grpc (%s)",
             target or "peer", reason)
    # a dnn_tpu peer's decline still advertises Relay; a reference
    # peer's non-JSON reply leaves relay_ok False (unary chain only)
    return Negotiated("grpc", GrpcSender(), reason=reason,
                      relay_ok=bool(acc.get("relay")), relay_known=True)


def negotiate_over(send_message_fn, *, transport: str = "auto",
                   target: str = "", device=None,
                   shm_slots: int = 4) -> Negotiated:
    """Run the handshake through `send_message_fn(sender_id, text) ->
    reply_text` (sync; the caller owns the RPC plumbing and its
    timeout). Transport-level RPC errors propagate to the caller (the
    endpoint may simply not be up yet — don't cache a verdict)."""
    if transport not in TRANSPORTS:
        raise ValueError(
            f"transport must be one of {TRANSPORTS}, got {transport!r}")
    if transport == "grpc":
        return Negotiated("grpc", GrpcSender(), reason="explicit")
    offer, probe = build_offer(transport)
    try:
        reply = send_message_fn(HELLO_SENDER, json.dumps(offer))
        return conclude(offer, reply, transport=transport, target=target,
                        device=device, shm_slots=shm_slots)
    finally:
        close_probe(probe)


# ----------------------------------------------------------------------
# receiver side: ticket resolution
# ----------------------------------------------------------------------

class TransportHost:
    """Per-server receiver state: answers hellos and resolves inbound
    tickets into arrays. Caches shm attachments per segment name (one
    mmap per segment lifetime, not per message)."""

    #: max cached shm attachments. Senders retire a segment whenever a
    #: payload outgrows its ring slot (fresh name per growth), and the
    #: receiver has no other signal that the old name is dead — an
    #: unbounded cache would strand one unlinked mmap per growth for
    #: the server's lifetime. LRU eviction unmaps stale segments while
    #: comfortably covering live rings (slots x peers << 64).
    MAX_SHM_ATTACHMENTS = 64

    def __init__(self, *, stage: str = ""):
        self.stage = stage
        self._lock = threading.Lock()
        # insertion-ordered: move-to-end on hit makes eviction LRU
        self._shm_attached: Dict[str, object] = {}

    # -- handshake --
    def answer_hello(self, text: str) -> str:
        return answer_hello(text, stage=self.stage)

    # -- data plane --
    @staticmethod
    def is_ticket(msg) -> bool:
        return msg.dtype in TICKET_DTYPES

    def resolve(self, msg):
        """Ticket Tensor -> the activation (device array for device
        hops, zero-copy host view for shm). Fail-loud on unknown or
        stale tickets — a ticket can only legitimately arrive after
        negotiation against THIS process."""
        if msg.dtype == TICKET_DTYPE_DEV:
            ticket = bytes(msg.tensor_data).decode()
            val = MAILBOX.peek(ticket)
            if val is None:
                raise TransportError(
                    f"device ticket {ticket[:8]}... not in this process's "
                    "mailbox (mis-negotiated or already consumed)")
            return val
        if msg.dtype == TICKET_DTYPE_SHM:
            meta = json.loads(bytes(msg.tensor_data).decode())
            name, nbytes = meta["seg"], int(meta["nbytes"])
            with self._lock:
                shm = self._shm_attached.get(name)
                if shm is not None:
                    # LRU refresh
                    self._shm_attached.pop(name)
                    self._shm_attached[name] = shm
                else:
                    from multiprocessing import shared_memory

                    try:
                        shm = shared_memory.SharedMemory(name=name)
                    except OSError as e:
                        raise TransportError(
                            f"shm segment {name} unreachable: {e}") from e
                    self._shm_attached[name] = shm
                    while len(self._shm_attached) > self.MAX_SHM_ATTACHMENTS:
                        _stale_name, stale = next(
                            iter(self._shm_attached.items()))
                        self._shm_attached.pop(_stale_name)
                        try:
                            stale.close()
                        except (OSError, BufferError):
                            pass  # a live view pins it; unmaps with it
            from dnn_tpu.io.serialization import _np_dtype

            dt = _np_dtype(meta["dtype"])
            shape = tuple(int(s) for s in meta["shape"])
            count = int(np.prod(shape)) if shape else 1
            if count * dt.itemsize != nbytes or nbytes > shm.size:
                raise TransportError(
                    f"shm ticket layout invalid: {meta}")
            return np.frombuffer(shm.buf, dtype=dt,
                                 count=count).reshape(shape)
        raise TransportError(f"not a transport ticket: dtype={msg.dtype!r}")

    def close(self):
        with self._lock:
            for shm in self._shm_attached.values():
                try:
                    shm.close()
                except (OSError, BufferError):
                    # a zero-copy view handed to a still-running stage
                    # computation pins the mapping; it unmaps with the
                    # last view
                    pass
            self._shm_attached.clear()


# ----------------------------------------------------------------------
# streamed relay framing (chunking + seq tags on request_id)
# ----------------------------------------------------------------------

# request_id transport segments (opaque to reference peers, stripped
# before the payload reaches any stage/LM handler):
#   s=<seq>           microbatch sequence number within one relay stream
#   c=<i>/<n>         chunk i of n for one oversized inline payload
_SEQ_PREFIX = "s="
_CHUNK_PREFIX = "c="

# Inline gRPC payloads above this ride the Relay stream in chunks (the
# default gRPC message cap is 4 MB; the reference's unary path simply
# breaks there). Tickets are never chunked — they are bytes-tiny.
CHUNK_BYTES = 1 << 20


# Relay response status conventions (the Relay RPC is dnn_tpu-only, so
# these are free to be machine-readable): an `ack:<seq>` frees the
# sender's payload slot for that microbatch; a `res:<seq>:<human text>`
# carries the final result (or an error status string) for it.
_ACK_PREFIX = "ack:"
_RES_PREFIX = "res:"


def ack_status(seq: int) -> str:
    return f"{_ACK_PREFIX}{seq}"


def parse_ack(status: str) -> Optional[int]:
    if status.startswith(_ACK_PREFIX):
        try:
            return int(status[len(_ACK_PREFIX):])
        except ValueError:
            return None
    return None


def result_status(seq: int, human: str) -> str:
    return f"{_RES_PREFIX}{seq}:{human}"


def parse_result(status: str) -> Tuple[Optional[int], str]:
    """-> (seq_or_None, human_status). Tolerates plain statuses (unary
    responses relayed through)."""
    if status.startswith(_RES_PREFIX):
        rest = status[len(_RES_PREFIX):]
        seq_s, _, human = rest.partition(":")
        try:
            return int(seq_s), human
        except ValueError:
            pass
    return None, status


def tag_seq(request_id: str, seq: int, chunk: Optional[Tuple[int, int]] = None
            ) -> str:
    rid = f"{request_id}:{_SEQ_PREFIX}{seq}"
    if chunk is not None:
        rid += f":{_CHUNK_PREFIX}{chunk[0]}/{chunk[1]}"
    return rid


def parse_seq(request_id: str) -> Tuple[str, Optional[int],
                                        Optional[Tuple[int, int]]]:
    """-> (base_request_id, seq_or_None, (chunk_i, chunk_n)_or_None)."""
    base, seq, chunk = [], None, None
    for seg in (request_id or "").split(":"):
        if seg.startswith(_SEQ_PREFIX):
            try:
                seq = int(seg[len(_SEQ_PREFIX):])
                continue
            except ValueError:
                pass
        if seg.startswith(_CHUNK_PREFIX):
            try:
                i, n = seg[len(_CHUNK_PREFIX):].split("/")
                chunk = (int(i), int(n))
                continue
            except ValueError:
                pass
        base.append(seg)
    return ":".join(base), seq, chunk


_DL_PREFIX = "dl="


def tag_deadline(request_id: str, remaining_s: float) -> str:
    """Append (or replace) the propagated-deadline segment: the
    REMAINING budget, in seconds, the sender grants the rest of the
    pipeline. Rides the existing request_id field like the trace tag
    (`tr=`) and the relay segments (`s=`/`c=`) — opaque to reference
    peers, skipped by parse_gen_options — so downstream hops can cap
    their own retry/forward budgets to it instead of over-spending a
    nearly-dead deadline (comm/client.py, comm/service.py,
    runtime/lm_server.py all honor it)."""
    return (f"{strip_deadline(request_id)}:"
            f"{_DL_PREFIX}{max(float(remaining_s), 0.001):.3f}")


def extract_deadline(request_id: str) -> Optional[float]:
    """The inbound `dl=` budget in seconds, or None when the sender
    propagated none (reference clients)."""
    for seg in (request_id or "").split(":"):
        if seg.startswith(_DL_PREFIX):
            try:
                return float(seg[len(_DL_PREFIX):])
            except ValueError:
                return None
    return None


def strip_deadline(request_id: str) -> str:
    if _DL_PREFIX not in (request_id or ""):
        return request_id
    return ":".join(seg for seg in request_id.split(":")
                    if not seg.startswith(_DL_PREFIX))


def split_requests(request: wc.TensorRequest, seq: int,
                   chunk_bytes: int = CHUNK_BYTES) -> List[wc.TensorRequest]:
    """One logical send -> the Relay stream's frames. Small payloads and
    tickets pass through whole (one frame); oversized inline payloads
    split into chunk frames (zero-copy memoryview slices — chunking adds
    no host copies on the send side)."""
    t = request.tensor
    data = t.tensor_data
    if t.dtype in TICKET_DTYPES or len(data) <= chunk_bytes:
        return [wc.TensorRequest(request_id=tag_seq(request.request_id, seq),
                                 tensor=t)]
    view = memoryview(data)
    n = (len(view) + chunk_bytes - 1) // chunk_bytes
    out = []
    for i in range(n):
        part = view[i * chunk_bytes:(i + 1) * chunk_bytes]
        # chunk 0 carries the logical header (shape/dtype/crc); later
        # chunks carry payload only
        frame_t = wc.Tensor(tensor_data=part,
                            shape=t.shape if i == 0 else (),
                            dtype=t.dtype if i == 0 else "",
                            crc32c=t.crc32c if i == 0 else None)
        out.append(wc.TensorRequest(
            request_id=tag_seq(request.request_id, seq, (i, n)),
            tensor=frame_t))
    return out


class ChunkAssembler:
    """Receiver-side reassembly for the Relay stream: in-order chunks
    of one sequence are filled into a single preallocated buffer (ONE
    copy total — the reassembly itself)."""

    def __init__(self):
        self._cur: Optional[dict] = None

    def add(self, request: wc.TensorRequest
            ) -> Optional[Tuple[str, int, wc.Tensor]]:
        """-> (base_request_id, seq, whole_tensor) when a logical
        payload completes, else None."""
        if _chaos_inject.perturb_relay():
            # injected relay-frame drop: the frame vanishes in
            # "transit" — the sender's seq never answers, surfacing as
            # an explicit stream error at the client (never a silent
            # loss; relay_corrupt raises PayloadCorruptError here
            # instead, the per-item DATA_LOSS path)
            return None
        base, seq, chunk = parse_seq(request.request_id)
        seq = 0 if seq is None else seq
        t = request.tensor
        if chunk is None:
            return base, seq, t
        i, n = chunk
        if i == 0:
            self._cur = {"base": base, "seq": seq, "n": n,
                         "shape": list(t.shape), "dtype": t.dtype,
                         "crc": t.crc32c, "parts": [],
                         "next": 0}
        cur = self._cur
        if cur is None or cur["seq"] != seq or cur["next"] != i:
            raise TransportError(
                f"relay chunk out of order: got {i}/{n} for seq {seq}")
        cur["parts"].append(t.tensor_data)
        cur["next"] += 1
        if cur["next"] < cur["n"]:
            return None
        self._cur = None
        whole = bytearray(sum(len(p) for p in cur["parts"]))
        off = 0
        for p in cur["parts"]:
            whole[off:off + len(p)] = p
            off += len(p)
        return cur["base"], seq, wc.Tensor(
            tensor_data=memoryview(whole), shape=cur["shape"],
            dtype=cur["dtype"], crc32c=cur["crc"])
