"""gRPC edge service: the cross-host / interop transport.

Re-implements the reference's NodeService server (node.py:34-133) on top of
the same wire protocol (dnn_tpu/comm/wire.proto), with the differences the
rebuild mandates (SURVEY §5):

  * the stage computation is a jit-compiled JAX program on a TPU device,
    not a torch module on CPU (node.py:52-54);
  * one channel per downstream neighbor, opened once and reused — the
    reference opens a fresh insecure channel per request per hop
    (node.py:73);
  * HealthCheck is actually used (clients probe it; the reference's version
    had no caller — SURVEY §3.4);
  * errors still relay upward as status strings in the response chain, for
    behavioral parity (node.py:91-100).

PR 7 makes the hop itself pluggable (comm/transport.py): each downstream
edge NEGOTIATES `device | shm | grpc` at first forward (a wire-compatible
SendMessage handshake — reference peers land on grpc), payloads ride
zero-copy at both ends (comm/wirecodec.py), and the streamed `Relay` RPC
replaces the nested hold-every-hop-open unary chain with
forward-and-ack-upstream semantics so microbatches overlap across
processes. Every hop's RPC histogram and span carries a `transport`
label, so the fleet collector reads the transport's effect directly.

This path exists for multi-host deployments without ICI and for interop
with reference nodes; the intra-pod fast path is the SPMD mesh runtime
(dnn_tpu/parallel/pipeline.py) with zero gRPC hops.
"""

from __future__ import annotations

import asyncio
import json
import logging
import time
from typing import Optional

import grpc
import numpy as np

from dnn_tpu import obs
from dnn_tpu.chaos import inject as _chaos_inject
from dnn_tpu.comm import transport as _tx
from dnn_tpu.comm import wire_pb2 as pb
from dnn_tpu.comm import wirecodec as wc
from dnn_tpu.comm.transport import PER_STAGE_BUDGET_S  # noqa: F401 — the
# budget constant lives with the other transport budgets now; re-exported
# here because the edge client (and external callers) import it from the
# service module since PR 0.
from dnn_tpu.io.serialization import PayloadCorruptError
from dnn_tpu.utils.metrics import labeled

log = logging.getLogger("dnn_tpu.comm")

SERVICE_NAME = "node_service.NodeService"

# Transient codes worth retrying, shared by the edge client and the server's
# downstream relay; anything else (INVALID_ARGUMENT, UNIMPLEMENTED, ...) is a
# real error and surfaces immediately.
RETRYABLE_CODES = frozenset({
    grpc.StatusCode.UNAVAILABLE,
    grpc.StatusCode.RESOURCE_EXHAUSTED,
    # a receiver detected payload corruption (crc32c mismatch) — the
    # pipeline is stateless per request, so resending is safe and likely
    # to succeed
    grpc.StatusCode.DATA_LOSS,
})
# DEADLINE_EXCEEDED is deliberately NOT retryable: a deadline here always
# spans the entire remaining pipeline (see _forward / pipeline_budget), so
# when it expires, resending toward the same hung stage can only duplicate
# every downstream stage's work — the timeout surfaces upward instead.


def full_jitter_delay(backoff: float, attempt: int) -> float:
    """FULL-JITTER exponential backoff: uniform in (0, backoff *
    2^attempt], shared by the edge client and the stage forward ladder.
    Deterministic backoff meant every caller that failed together
    retried together — a retry storm re-spiking the very stage that was
    recovering; jitter decorrelates the herd. The small floor keeps the
    delay > 0 so budget checks still terminate ladders. (Uses `random`,
    never in traced code — chaos-plan determinism lives in
    chaos/plan.decide, not here.)"""
    import random

    return max(backoff * (2 ** attempt) * random.random(),
               backoff * 0.05)


def _tensor_msg(arr) -> wc.Tensor:
    """array -> wire Tensor, zero-copy (the payload rides as a memoryview
    of the array's own buffer until the single join into the gRPC message
    — comm/wirecodec.py). Checksummed only when the native codec is
    built, same policy as before; field absent == "not checksummed",
    same as a reference peer."""
    return wc.make_tensor(arr)


def _tensor_arr(msg) -> np.ndarray:
    """wire Tensor -> zero-copy (read-only) ndarray view over the
    message payload; crc-verified when declared. Raises
    PayloadCorruptError on checksum mismatch."""
    return wc.tensor_view(msg)


class StageServer:
    """Serves one pipeline stage (the reference's per-node role,
    node.py:34-113). `engine` supplies the staged model; `node_id` selects
    which part this process owns via the shared topology config.
    `transport` is this server's DOWNSTREAM hop preference
    (auto | grpc | shm | device — comm/transport.py; default follows the
    engine's config)."""

    def __init__(self, engine, node_id: str,
                 transport: Optional[str] = None):
        # Warm the native codec NOW (a synchronous g++ compile on first
        # build) so it never runs inside an async RPC handler, where it
        # would freeze the event loop for the duration of the compile.
        from dnn_tpu.native import native_available

        native_available()
        self.engine = engine
        self.config = engine.config
        self.node = self.config.node_by_id(node_id)
        self.part_index = self.node.part_index
        self.is_last = self.part_index == self.config.num_parts - 1
        nxt = self.config.next_node(self.node)
        self.next_address = nxt.address if nxt else None
        self._next_channel: Optional[grpc.aio.Channel] = None
        if transport is None:
            transport = getattr(engine, "transport", "auto")
        if transport not in _tx.TRANSPORTS:
            raise ValueError(
                f"transport must be one of {_tx.TRANSPORTS}, got "
                f"{transport!r}")
        self.transport = transport
        self._thost = _tx.TransportHost(stage=self.node.id)
        self._negotiated: Optional[_tx.Negotiated] = None
        self._hop_warm = False  # one successful send on the downstream hop
        self._neg_lock = asyncio.Lock()

    #: streamed-relay accept window: how many decoded microbatches may sit
    #: acked-but-not-yet-computed per stream. Depth trades upstream overlap
    #: against per-stage memory (window * activation bytes); a full queue
    #: stalls acks, so backpressure propagates upstream hop by hop.
    ACCEPT_WINDOW = 4

    # --- RPC implementations (names/signatures fixed by the protocol) ---

    def _ingress(self, tensor):
        """Inbound payload -> (activation, transport_name). Tickets
        resolve through the transport host (device mailbox / shm);
        inline tensors decode zero-copy. shm payloads are COPIED out of
        their ring slot here (the slot is sender-owned and may be
        released + overwritten the moment the sender stops waiting —
        e.g. its deadline expires mid-compute; one memcpy is the price
        of a race-free license, and still no serialization).
        TransportError is fail-loud at the RPC boundary
        (INVALID_ARGUMENT), never a silent mis-decode."""
        if self._thost.is_ticket(tensor):
            if tensor.dtype == _tx.TICKET_DTYPE_DEV:
                return self._thost.resolve(tensor), "device"
            return np.array(self._thost.resolve(tensor)), "shm"
        return _tensor_arr(tensor), "grpc"

    async def SendTensor(self, request, context):
        nid = self.node.id
        result_msg = None
        t_handler = time.perf_counter()
        # propagated deadline (dl= request_id segment): the remaining
        # budget the SENDER granted the rest of the pipeline — our
        # downstream forward must fit inside it minus our own elapsed
        inbound_dl = _tx.extract_deadline(request.request_id)
        m = obs.metrics()
        if m is not None:
            m.inc(labeled("comm.payload_bytes_total", direction="in",
                          stage=nid), request.ByteSize())
        # continue the sender's trace (or start fresh); the tree crosses
        # every relay hop because _forward re-tags the request_id it
        # forwards with its own span
        root = obs.continue_or_start("stage.request", request.request_id,
                                     stage=nid, part=self.part_index)
        t_in = "grpc"
        try:
            try:
                x, t_in = self._ingress(request.tensor)
            except PayloadCorruptError as e:
                # Fail the RPC itself (not a status string) so the sender's
                # retry loop sees DATA_LOSS and resends — transient wire
                # corruption must not become a terminal pipeline error.
                log.warning("corrupt payload on %s: %s", nid, e)
                root.end(error="payload_corrupt")
                await context.abort(grpc.StatusCode.DATA_LOSS, str(e))
            except _tx.TransportError as e:
                # a ticket this process cannot resolve is a deployment
                # error (mis-negotiated transport), not data corruption
                log.warning("transport ticket error on %s: %s", nid, e)
                root.end(error="transport_ticket")
                await context.abort(grpc.StatusCode.INVALID_ARGUMENT,
                                    str(e))
            root.set(transport=t_in)
            with root.child("stage.compute", part=self.part_index):
                # the barrier forces device completion — the span
                # measures the stage's real compute, not its dispatch.
                # The output STAYS device-resident: a device-negotiated
                # downstream hop hands it on without ever pulling it to
                # the host (the sender's make_request decides).
                # OFF-LOOP (CON001): _compute_stage blocks on device
                # completion; running it inline held the event loop for
                # the full stage compute (first call: the jit compile),
                # stalling every concurrent RPC — including the Relay
                # acks that free upstream send windows. The streamed
                # path already computed via to_thread; the unary path
                # now matches.
                y = await asyncio.to_thread(self._compute_stage, x)
            if self.is_last:
                y = np.asarray(y)
                pred = int(np.argmax(y))
                log.info("final stage done (node %s), prediction=%d", nid, pred)
                status = f"[{nid}] Processing complete. Prediction: {pred}"
                result_msg = _tensor_msg(y)
            else:
                remaining_dl = None
                if inbound_dl is not None:
                    remaining_dl = inbound_dl - (time.perf_counter()
                                                 - t_handler)
                resp = await self._forward(request.request_id, y,
                                           parent=root,
                                           inbound_budget=remaining_dl)
                status = f"[{nid}] Forwarded. Next node status: {resp.status}"
                if resp.HasField("result_tensor"):
                    result_msg = resp.result_tensor
        except grpc.aio.AbortError:
            raise  # the DATA_LOSS abort above must fail the RPC, not relay
        except grpc.aio.AioRpcError as e:
            log.error("forward from %s to %s failed: %s", nid, self.next_address, e.details())
            status = f"[{nid}] Error forwarding: {e.details()}"
        except Exception as e:  # noqa: BLE001 — status-string relay, like node.py:96-100
            log.exception("error processing tensor on %s", nid)
            status = f"[{nid}] Error: {e}"
        finally:
            root.end()
        if m is not None:
            m.observe_hist(
                labeled("comm.rpc_latency_seconds", method="SendTensor",
                        role="server", stage=nid, transport=t_in),
                time.perf_counter() - t_handler)
        resp_msg = wc.TensorResponse(status=status, result_tensor=result_msg)
        if m is not None:
            m.inc(labeled("comm.payload_bytes_total", direction="out",
                          stage=nid), resp_msg.ByteSize())
        return resp_msg

    async def HealthCheck(self, request: pb.Empty, context) -> pb.HealthCheckResponse:
        return pb.HealthCheckResponse(is_healthy=True)

    async def SendMessage(self, request: pb.MessageRequest, context) -> pb.MessageReply:
        if request.sender_id.startswith(_tx.HELLO_SENDER):
            # transport negotiation side-channel (comm/transport.py):
            # answer with this process's proof-backed accept/decline.
            # Rides the reference's own SendMessage RPC, so the
            # handshake is wire-compatible by construction.
            return pb.MessageReply(
                confirmation_text=self._thost.answer_hello(
                    request.message_text))
        log.info("message for %s from %s", self.node.id, request.sender_id)
        return pb.MessageReply(
            confirmation_text=f"[{self.node.id}] got msg '{request.message_text}'"
        )

    # --- streamed relay (non-nested MPMD forwarding) -------------------

    async def Relay(self, request_iterator, context):
        """Streamed relay: the non-nested replacement for the unary
        SendTensor chain. Each inbound frame (one microbatch, possibly
        chunked) is ACKED UPSTREAM as soon as it is accepted — the
        upstream sender's window advances while THIS stage computes, so
        microbatch m+1 runs on stage i while microbatch m runs on stage
        i+1 (the MPMD overlap the nested chain could never express:
        node.py:84 holds every hop open for the full downstream
        latency). Results ride back asynchronously, tagged `res:<seq>:`.

        Non-idempotent by design (the ack already released the upstream
        sender's payload slot), so this path is NEVER retried — a broken
        stream surfaces to the caller, which falls back to the unary
        path for a fresh attempt.

        Acks are EAGER: inbound frames are decoded and acknowledged as
        they arrive into a bounded accept queue (ACCEPT_WINDOW deep),
        while a separate consumer runs the stage computes in order — so
        the measured ack latency is the TRANSPORT cost of the hop, and
        the upstream stage pipelines up to the window depth ahead. A
        full queue stalls the reader, which stalls acks — backpressure
        propagates upstream hop by hop. shm payloads are copied out of
        their ring slot at accept time (one memcpy — the ack is the
        sender's license to overwrite the slot); device/grpc payloads
        need no copy.

        Frames carry transport tickets when the upstream hop negotiated
        device/shm: the streamed schedule and the payload transport
        compose."""
        nid = self.node.id
        m = obs.metrics()
        out_q: asyncio.Queue = asyncio.Queue()
        _DONE = object()
        ds_state = {"call": None, "pump": None, "writer": None,
                    "consumer": None, "wq": None, "pending": {},
                    "sent_at": {}}

        async def _ensure_downstream():
            if ds_state["call"] is not None:
                return ds_state["call"]
            await self._ensure_negotiated()
            if self._next_channel is None:
                self._next_channel = grpc.aio.insecure_channel(
                    self.next_address)
            # NO per-stream deadline: a relay stream lives as long as
            # the upstream keeps feeding it (a per-hop budget would
            # kill any healthy run longer than one hop's slice); its
            # lifetime is bounded by the upstream stream — when that
            # ends or breaks, the cleanup below cancels this call
            call = self._next_channel.stream_stream(
                f"/{SERVICE_NAME}/Relay",
                request_serializer=wc.serialize_request,
                response_deserializer=wc.parse_response,
            )()
            ds_state["call"] = call
            # dedicated writer: the compute loop hands frames to a
            # bounded queue and moves on — the gRPC flush never holds
            # the stage. Backpressure survives: the queue bound (and
            # the shm ring ahead of it) stalls the compute loop when
            # the downstream genuinely can't drain.
            ds_state["wq"] = asyncio.Queue(maxsize=2 * self.ACCEPT_WINDOW)
            ds_state["writer"] = asyncio.ensure_future(
                _write_downstream(call, ds_state["wq"]))
            ds_state["pump"] = asyncio.ensure_future(_pump_downstream(call))
            return call

        async def _write_downstream(call, wq):
            try:
                while True:
                    frame = await wq.get()
                    if frame is None:
                        break
                    await call.write(frame)
            except Exception as e:  # noqa: BLE001 — surface, don't vanish
                # a dead writer must tell the upstream NOW — otherwise
                # the client only learns at its own deadline
                log.warning("relay downstream write failed on %s: %s",
                            nid, e)
                await out_q.put(wc.TensorResponse(
                    status=_tx.result_status(
                        -1, f"[{nid}] Error forwarding: {e}")))
                await out_q.put(_DONE)
            finally:
                try:
                    await call.done_writing()
                except Exception:  # noqa: BLE001 — already-broken call
                    pass

        async def _pump_downstream(call):
            """Relay downstream results upstream; downstream ACKS free
            this stage's sender resources (shm slots / mailbox) and
            stamp the hop latency — submit -> downstream-accept, the
            time THIS stage would have been blocked under the nested
            chain."""
            neg = self._negotiated
            try:
                async for resp in call:
                    seq = _tx.parse_ack(resp.status)
                    if seq is not None:
                        req = ds_state["pending"].pop(seq, None)
                        if req is not None and neg is not None:
                            neg.sender.sent_ok(req)
                        t_sent = ds_state["sent_at"].pop(seq, None)
                        if m is not None and t_sent is not None:
                            # DELIVERY latency (submit -> downstream
                            # accept): includes queueing when the
                            # accept window backs up — the backpressure
                            # signal, distinct from hop OCCUPANCY
                            dt = time.perf_counter() - t_sent
                            m.observe(labeled("comm.hop_ack_seconds",
                                              stage=nid,
                                              transport=neg.name), dt)
                            m.observe_hist(
                                labeled("comm.rpc_latency_seconds",
                                        method="relay_hop", role="client",
                                        stage=nid, transport=neg.name),
                                dt)
                        self._hop_warm = True
                        continue
                    await out_q.put(resp)
            finally:
                await out_q.put(_DONE)

        async def _forward_one(base_rid, seq, y, root, neg):
            """Forward one computed microbatch downstream: streamed when
            the peer speaks Relay, else the bounded-retry unary chain
            (reference peers) — THIS stage's ack-early overlap survives
            either way."""
            if neg.relay_ok:
                await _ensure_downstream()
                sp = obs.start_span("rpc.forward", parent=root,
                                    target=self.next_address,
                                    transport=neg.name, streamed=True)
                t0 = time.perf_counter()
                # fast path: a non-blocking make (free shm slot = one
                # memcpy). When the ring is FULL the make must not run
                # on the event loop — the loop processes the very acks
                # that free slots, so a blocking wait here deadlocks
                # the stream until the ring timeout; the slow path
                # waits on a worker thread instead (honest backpressure)
                rid_out = obs.tag_request_id(base_rid, sp) if sp else base_rid
                req_out = neg.sender.make_request_nowait(y, rid_out)
                if req_out is None:
                    req_out = await asyncio.to_thread(
                        neg.sender.make_request, y, rid_out)
                ds_state["pending"][seq] = req_out
                ds_state["sent_at"][seq] = t0
                for sub in _tx.split_requests(req_out, seq):
                    await ds_state["wq"].put(sub)
                if m is not None:
                    # hop OCCUPANCY: how long this stage was held by
                    # the hop before it could move to the next
                    # microbatch — under the nested chain this is the
                    # full downstream round trip (see _forward); here
                    # it is the payload handoff (shm-ring/mailbox write
                    # + frame enqueue, including any backpressure stall
                    # when the ring or the writer queue is full)
                    m.observe(labeled("comm.hop_seconds", stage=nid,
                                      transport=neg.name,
                                      mode="streamed"),
                              time.perf_counter() - t0)
                sp.end()
                return
            resp = await self._forward(base_rid, y, parent=root)
            human = f"[{nid}] Forwarded. Next node status: {resp.status}"
            await out_q.put(wc.TensorResponse(
                status=_tx.result_status(seq, human),
                result_tensor=resp.result_tensor
                if resp.HasField("result_tensor") else None))

        accept_q: asyncio.Queue = asyncio.Queue(maxsize=self.ACCEPT_WINDOW)

        async def _read_inputs():
            """Eager accept: decode + ack each frame as it arrives; the
            bounded accept queue is the pipelining window (full queue ->
            reads stall -> acks stall -> backpressure upstream)."""
            asm = _tx.ChunkAssembler()
            try:
                async for frame in request_iterator:
                    done = asm.add(frame)
                    if done is None:
                        continue
                    base_rid, seq, tensor = done
                    t0 = time.perf_counter()
                    # _ingress copies shm payloads out of their slot:
                    # the ack below licenses the sender to overwrite it
                    x, t_in = self._ingress(tensor)
                    await accept_q.put((base_rid, seq, x, t_in, t0))
                    # ack upstream NOW: the sender's window advances
                    # while this stage's compute queue drains
                    await out_q.put(wc.TensorResponse(
                        status=_tx.ack_status(seq)))
            finally:
                await accept_q.put(None)

        async def _compute_loop():
            try:
                while True:
                    item = await accept_q.get()
                    if item is None:
                        break
                    base_rid, seq, x, t_in, t0 = item
                    root = obs.continue_or_start(
                        "stage.request", base_rid, stage=nid,
                        part=self.part_index, transport=t_in, seq=seq)
                    try:
                        with root.child("stage.compute",
                                        part=self.part_index):
                            y = await asyncio.to_thread(
                                self._compute_stage, x)
                        if self.is_last:
                            y = np.asarray(y)
                            await out_q.put(wc.TensorResponse(
                                status=_tx.result_status(
                                    seq, f"[{nid}] Processing complete. "
                                         f"Prediction: {int(np.argmax(y))}"),
                                result_tensor=_tensor_msg(y)))
                        else:
                            neg = await self._ensure_negotiated()
                            await _forward_one(base_rid, seq, y, root, neg)
                    except asyncio.CancelledError:
                        raise
                    except Exception as e:  # noqa: BLE001 — PER-ITEM
                        # degradation, matching the unary chain: one bad
                        # microbatch answers its own seq with an error
                        # status and the stream lives on
                        log.warning("relay item %s failed on %s: %s",
                                    seq, nid, e)
                        root.set(error=str(e))
                        await out_q.put(wc.TensorResponse(
                            status=_tx.result_status(
                                seq, f"[{nid}] Error: {e}")))
                    finally:
                        root.end()
                    if m is not None:
                        m.observe_hist(
                            labeled("comm.rpc_latency_seconds",
                                    method="Relay", role="server",
                                    stage=nid, transport=t_in),
                            time.perf_counter() - t0)
            except asyncio.CancelledError:
                raise
            except Exception as e:  # noqa: BLE001 — infrastructure
                # failure outside any one item: ends the stream
                log.exception("relay stream failure on %s", nid)
                await out_q.put(wc.TensorResponse(
                    status=_tx.result_status(-1, f"[{nid}] Error: {e}")))
            finally:
                if ds_state["writer"] is not None:
                    # drain the writer, whose own finally closes the
                    # downstream send side (done_writing)
                    await ds_state["wq"].put(None)
                    await ds_state["writer"]
                else:
                    await out_q.put(_DONE)

        async def _pump_inputs():
            """Reader + consumer; decode/ingress errors surface like
            compute errors (status-string relay)."""
            ds_state["consumer"] = asyncio.ensure_future(_compute_loop())
            try:
                await _read_inputs()
            except (_tx.TransportError, PayloadCorruptError,
                    ValueError) as e:
                log.warning("relay ingress error on %s: %s", nid, e)
                await out_q.put(wc.TensorResponse(
                    status=_tx.result_status(-1, f"[{nid}] Error: {e}")))
                await accept_q.put(None)
            await ds_state["consumer"]

        reader = asyncio.ensure_future(_pump_inputs())
        try:
            while True:
                item = await out_q.get()
                if item is _DONE:
                    break
                yield item
        finally:
            # cancel EVERY task this stream spawned — cancelling the
            # reader alone would strand a consumer blocked on a full
            # writer queue after a downstream failure (a leak per
            # broken stream) — and tear down the downstream call
            reader.cancel()
            for key in ("pump", "writer", "consumer"):
                if ds_state[key] is not None:
                    ds_state[key].cancel()
            if ds_state["call"] is not None:
                ds_state["call"].cancel()
            # release any sender resources stranded by a broken stream
            neg = self._negotiated
            if neg is not None:
                for req in ds_state["pending"].values():
                    neg.sender.cleanup(req)
            ds_state["pending"].clear()

    # --- plumbing ---

    def _compute_stage(self, x):
        """Run this process's stage and BLOCK until the device finished
        (honest compute spans/timings), without pulling the result to
        the host — the transport decides whether host bytes ever exist:
        grpc/shm senders np.asarray on encode, the device sender hands
        the device-resident array through the mailbox untouched."""
        from dnn_tpu.utils.tracing import device_sync

        y = self.engine.run_stage(self.part_index, x)
        device_sync(y)
        return y

    def _next_device(self):
        """The downstream stage's device when it lives in this process
        (the relay runtime pins one device per stage) — lets the device
        sender start the D2D transfer before the control message."""
        relay = getattr(self.engine, "_relay", None)
        nxt = self.part_index + 1
        if relay is not None and nxt < len(relay.devices):
            return relay.devices[nxt]
        return None

    async def _ensure_negotiated(self) -> _tx.Negotiated:
        """Negotiate the downstream hop once (comm/transport.py). A
        transport-level failure (downstream not up yet) yields an
        UNCACHED grpc verdict so the handshake is retried on the next
        forward; an explicit misconfig raises (fail-loud)."""
        async with self._neg_lock:
            if self._negotiated is not None:
                return self._negotiated
            if self.transport == "grpc":
                self._negotiated = _tx.Negotiated(
                    "grpc", _tx.GrpcSender(), reason="explicit")
                return self._negotiated
            if self._next_channel is None:
                self._next_channel = grpc.aio.insecure_channel(
                    self.next_address)
            offer, probe = _tx.build_offer(self.transport)
            try:
                call = self._next_channel.unary_unary(
                    f"/{SERVICE_NAME}/SendMessage",
                    request_serializer=pb.MessageRequest.SerializeToString,
                    response_deserializer=pb.MessageReply.FromString,
                )
                try:
                    reply = await call(
                        pb.MessageRequest(sender_id=_tx.HELLO_SENDER,
                                          message_text=json.dumps(offer)),
                        timeout=10.0)
                except grpc.aio.AioRpcError as e:
                    # no verdict — this forward rides grpc, handshake
                    # retried next time
                    return _tx.Negotiated(
                        "grpc", _tx.GrpcSender(),
                        reason=f"hello failed: {e.code()}")
                self._negotiated = _tx.conclude(
                    offer, reply.confirmation_text,
                    transport=self.transport, target=self.next_address,
                    device=self._next_device())
                return self._negotiated
            finally:
                _tx.close_probe(probe)

    async def _forward(
        self, request_id: str, y: np.ndarray, *, retries: int = 2,
        backoff: float = 0.2, timeout: Optional[float] = None,
        parent=None, inbound_budget: Optional[float] = None,
    ):
        """Relay downstream with bounded retries on transient failures,
        reusing the shared channel across attempts (gRPC reconnects a broken
        channel on the next call) — the per-hop resilience the reference
        lacks (SURVEY §5: failures only become status strings, "No retry").

        The hop rides the NEGOTIATED transport (comm/transport.py):
        device/shm sends carry a ticket (the payload stays in the mailbox
        / shm ring until the response lands, so a transport-level retry
        resends the same ticket safely); grpc sends carry the inline
        zero-copy tensor — byte-identical to the reference wire.

        Deadline discipline: the relayed call spans the ENTIRE remaining
        pipeline (response-chain semantics, SURVEY §3.3), so this hop gets
        an OVERALL budget that scales with remaining depth — derived from
        the negotiated transport (transport.hop_budget_s): grpc keeps the
        reference-compatible PER_STAGE_BUDGET_S slice per downstream
        stage; a WARM device/shm hop budgets seconds per stage instead of
        inheriting the 30 s serialization+compile margin. The budget is
        shared across all attempts and backoff sleeps (each attempt's
        gRPC deadline is the budget REMAINING, mirroring
        NodeClient.send_tensor). DEADLINE_EXCEEDED itself is not
        retryable (see RETRYABLE_CODES): the expired budget already
        covered the whole remaining pipeline.

        The relayed request_id is RE-TAGGED with this hop's span
        (obs.tag_request_id), so the downstream stage's spans nest under
        this hop's `rpc.forward` — one tree per request across the whole
        chain; retries count into comm.retries_total{stage=...} with the
        trace id in the log line, so a backoff storm is visible and
        attributable instead of silent."""
        neg = await self._ensure_negotiated()
        sp = obs.start_span("rpc.forward", parent=parent,
                            target=self.next_address, transport=neg.name)
        downstream = max(self.config.num_parts - self.part_index - 1, 1)
        if timeout is None:
            timeout = _tx.hop_budget_s(neg.name, downstream,
                                       warm=self._hop_warm)
        if inbound_budget is not None:
            # never grant downstream more than the sender still has:
            # the propagated deadline caps the derived budget, so a
            # nearly-dead request can't spend a fresh full ladder at
            # every remaining hop (the floor keeps gRPC's deadline
            # validation happy; an already-expired budget fails fast)
            timeout = max(min(timeout, inbound_budget), 0.001)
        # non-blocking make when a slot is free; with concurrent
        # in-flight requests the shm ring can fill, and the WAIT must
        # leave the loop free to process the downstream responses that
        # release slots — so the full make runs on a worker thread.
        # The forwarded request_id re-tags the deadline with what THIS
        # hop grants (<= what it was granted).
        rid_out = obs.tag_request_id(request_id, sp) if sp else request_id
        rid_out = _tx.tag_deadline(rid_out, timeout)
        request = neg.sender.make_request_nowait(y, rid_out)
        if request is None:
            request = await asyncio.to_thread(
                neg.sender.make_request, y, rid_out)
        if self._next_channel is None:
            self._next_channel = grpc.aio.insecure_channel(self.next_address)
        call = self._next_channel.unary_unary(
            f"/{SERVICE_NAME}/SendTensor",
            request_serializer=wc.serialize_request,
            response_deserializer=wc.parse_response,
        )
        deadline = time.monotonic() + timeout
        attempt = 0
        m = obs.metrics()
        nid = self.node.id
        completed = False
        try:
            while True:
                remaining = deadline - time.monotonic()
                # refresh the propagated deadline per attempt (see the
                # edge client): the wire advertises the budget LEFT
                request.request_id = _tx.tag_deadline(
                    rid_out, max(remaining, 0.001))
                t_try = time.perf_counter()
                if m is not None:
                    # per ATTEMPT, like the edge client: relayed bytes
                    # must reconcile with the downstream stage's
                    # direction="in" count even through retries
                    m.inc(labeled("comm.payload_bytes_total",
                                  direction="out", stage=nid),
                          request.ByteSize())
                try:
                    _chaos_inject.perturb_rpc("stage", self.next_address)
                    t_send_wall = time.time() if sp else 0.0
                    resp = await call(request, timeout=max(remaining, 0.001))
                    dt = time.perf_counter() - t_try
                    if sp:
                        # clock-offset sampling fields for cross-host
                        # stitching, as in client.send_tensor: the
                        # successful attempt's wall-clock window only
                        sp.set(cs=t_send_wall, cr=time.time())
                    if m is not None:
                        m.observe_hist(
                            labeled("comm.rpc_latency_seconds",
                                    method="forward", role="client",
                                    stage=nid, transport=neg.name),
                            dt)
                        # exact-quantile per-hop series (the bench's
                        # regression-asserted number rides this);
                        # mode="nested": the sender was held for the
                        # full downstream round trip
                        m.observe(labeled("comm.hop_seconds",
                                          stage=nid, transport=neg.name,
                                          mode="nested"),
                                  dt)
                    sp.set(attempts=attempt + 1)
                    completed = True
                    self._hop_warm = True
                    return resp
                except (grpc.RpcError, PayloadCorruptError) as e:
                    # NOTE: the shared channel is deliberately NOT closed
                    # between attempts — other requests may have calls in
                    # flight on it, and gRPC reconnects a broken channel on
                    # the next call anyway. grpc.RpcError (not the aio
                    # subclass alone) so injected transport faults walk
                    # the same ladder real ones do; PayloadCorruptError
                    # maps to the DATA_LOSS retry policy like the edge
                    # client's.
                    code = e.code() if isinstance(e, grpc.RpcError) \
                        else grpc.StatusCode.DATA_LOSS
                    if m is not None and \
                            code == grpc.StatusCode.DEADLINE_EXCEEDED:
                        m.inc(labeled("comm.deadline_exceeded_total",
                                      stage=nid))
                    # full jitter (see client._backoff_delay): the
                    # budget check uses the worst-case delay so the
                    # ladder never outlives the propagated deadline
                    worst = backoff * (2 ** attempt)
                    out_of_budget = deadline - time.monotonic() <= worst
                    if code not in RETRYABLE_CODES or attempt >= retries \
                            or out_of_budget:
                        sp.set(error=str(code), attempts=attempt + 1)
                        raise
                    delay = full_jitter_delay(backoff, attempt)
                    if m is not None:
                        m.inc(labeled("comm.retries_total",
                                      stage=nid,
                                      outcome=code.name.lower()))
                    log.warning(
                        "forward %s -> %s failed (%s), retry %d/%d in "
                        "%.2fs [trace=%s]",
                        nid, self.next_address, code,
                        attempt + 1, retries, delay, sp.trace_id or "-",
                    )
                    await asyncio.sleep(delay)
                    attempt += 1
        finally:
            # in a FINALLY, not the except branch: a cancelled handler
            # (upstream deadline mid-forward) must still release the
            # ticket's shm slot / mailbox entry, or four cancellations
            # wedge the 4-slot ring for good
            if completed:
                neg.sender.sent_ok(request)
            else:
                neg.sender.cleanup(request)
            sp.end()

    async def close(self):
        if self._next_channel is not None:
            await self._next_channel.close()
            self._next_channel = None
        neg, self._negotiated = self._negotiated, None
        if neg is not None:
            neg.sender.close()
        self._thost.close()


def _resolve_port(servicer: StageServer, node_id: str, port: Optional[int]) -> int:
    bind_port = port if port is not None else servicer.node.port
    if bind_port is None:
        raise ValueError(
            f"node '{node_id}' has no address in the config; serving a stage "
            "requires nodes[].address with an IP:Port (config.json:6)"
        )
    return bind_port


def _handlers(servicer: StageServer):
    handlers = {
        "SendTensor": grpc.unary_unary_rpc_method_handler(
            servicer.SendTensor,
            request_deserializer=wc.parse_request,
            response_serializer=wc.serialize_response,
        ),
        "HealthCheck": grpc.unary_unary_rpc_method_handler(
            servicer.HealthCheck,
            request_deserializer=pb.Empty.FromString,
            response_serializer=pb.HealthCheckResponse.SerializeToString,
        ),
        "SendMessage": grpc.unary_unary_rpc_method_handler(
            servicer.SendMessage,
            request_deserializer=pb.MessageRequest.FromString,
            response_serializer=pb.MessageReply.SerializeToString,
        ),
    }
    # streamed relay (stage servers): the non-nested MPMD forward path.
    # An ADDITIVE method like GenerateStream — reference peers never call
    # it, callers probing it on a reference server get UNIMPLEMENTED and
    # fall back to the unary chain.
    if hasattr(servicer, "Relay"):
        handlers["Relay"] = grpc.stream_stream_rpc_method_handler(
            servicer.Relay,
            request_deserializer=wc.parse_request,
            response_serializer=wc.serialize_response,
        )
    # the LM daemon's per-token streaming front (wire.proto GenerateStream);
    # stage servers don't implement it and callers get UNIMPLEMENTED
    if hasattr(servicer, "GenerateStream"):
        handlers["GenerateStream"] = grpc.unary_stream_rpc_method_handler(
            servicer.GenerateStream,
            request_deserializer=wc.parse_request,
            response_serializer=wc.serialize_response,
        )
    return grpc.method_handlers_generic_handler(SERVICE_NAME, handlers)


async def serve_stage(engine, node_id: str, *, port: Optional[int] = None,
                      metrics_port: Optional[int] = None,
                      transport: Optional[str] = None):
    """Start the gRPC server for this node's stage and block until
    termination (the rebuild of serve(), node.py:114-133).
    `transport` sets the downstream hop preference (auto | grpc | shm |
    device; None follows the engine config — see comm/transport.py).
    `metrics_port` (None = off, 0 = ephemeral) additionally serves the
    observability endpoint — GET /metrics (Prometheus text format:
    per-stage RPC latency with per-transport labels, payload bytes,
    retry/deadline counters, XLA compile telemetry, device/host memory
    gauges), /trace (Chrome-trace JSON), /debugz (flight ring), POST
    /profilez (on-demand device profile; no auto-trigger — that needs
    the LM daemon's step loop) — over stdlib HTTP."""
    obs.install_compile_telemetry()
    servicer = StageServer(engine, node_id, transport=transport)
    server = grpc.aio.server(options=_tx.GRPC_MSG_OPTIONS)
    server.add_generic_rpc_handlers((_handlers(servicer),))
    bind_port = _resolve_port(servicer, node_id, port)
    listen = f"[::]:{bind_port}"
    if server.add_insecure_port(listen) == 0:
        # grpc reports bind failure as port 0, not an exception (the
        # reference prints-and-exits on the same failure, node.py:124-126)
        raise RuntimeError(f"failed to bind gRPC server to {listen}")
    metrics_srv = None
    if metrics_port is not None:
        metrics_srv = obs.serve_metrics(metrics_port)
    log.info("gRPC stage server %s listening on %s (part %d, transport=%s)",
             node_id, listen, servicer.part_index, servicer.transport)
    await server.start()
    # loop-lag sanitizer (analysis/sanitize.py): env-gated tripwire for
    # blocking calls the AST pass can't see through an indirection.
    # Installed AFTER
    # startup so the native-codec warm compile doesn't count.
    from dnn_tpu.analysis import sanitize as _sanitize

    lagmon = _sanitize.maybe_install(where=f"serve_stage:{node_id}")
    try:
        await server.wait_for_termination()
    finally:
        if lagmon is not None:
            lagmon.stop()
        await servicer.close()
        await server.stop(grace=1)
        if metrics_srv is not None:
            metrics_srv.close()


def start_stage_server_in_background(engine, node_id: str, *,
                                     port: Optional[int] = None,
                                     transport: Optional[str] = None):
    """Test/embedding helper: run serve_stage on a daemon thread; returns
    (thread, stop_callback)."""
    import threading

    loop = asyncio.new_event_loop()
    started = threading.Event()
    state = {}

    async def _run():
        # grpc.aio binds to the event loop current at construction time, so
        # the server (and the servicer's forwarding channel) must be created
        # inside this thread's loop, not the caller's.
        try:
            servicer = StageServer(engine, node_id, transport=transport)
            server = grpc.aio.server(options=_tx.GRPC_MSG_OPTIONS)
            server.add_generic_rpc_handlers((_handlers(servicer),))
            bind_port = _resolve_port(servicer, node_id, port)
            if server.add_insecure_port(f"[::]:{bind_port}") == 0:
                raise RuntimeError(f"failed to bind gRPC server to [::]:{bind_port}")
            await server.start()
            state["servicer"], state["server"] = servicer, server
            state["done"] = asyncio.Event()
        except BaseException as e:  # surface startup failure to the caller
            state["error"] = e
            raise
        finally:
            started.set()
        await state["done"].wait()
        # drain one cycle so the stop() future resolves before the loop ends
        await asyncio.sleep(0.05)

    def _thread_main():
        asyncio.set_event_loop(loop)
        try:
            loop.run_until_complete(_run())
        except BaseException:
            if "error" not in state:
                raise  # startup succeeded; die loudly on later failures
            # startup error already recorded and re-raised to the caller

    t = threading.Thread(target=_thread_main, daemon=True)
    t.start()
    if not started.wait(timeout=15):
        raise RuntimeError(f"stage server for {node_id} failed to start")
    if "error" in state:
        t.join(timeout=5)
        raise RuntimeError(
            f"stage server for {node_id} failed to start: {state['error']}"
        ) from state["error"]

    def stop():
        async def _stop():
            await state["servicer"].close()
            await state["server"].stop(grace=0.2)
            state["done"].set()

        asyncio.run_coroutine_threadsafe(_stop(), loop).result(timeout=10)
        t.join(timeout=5)

    return t, stop
