"""gRPC edge client — the initiator role.

Rebuilds the reference's node-0 client path (initiate_inference,
node.py:137-200): run the local stage, send the activation downstream, wait
for the result to ride back up the response chain, return the final tensor.
Adds what the reference lacked (SURVEY §5 "Failure detection ... No retry"):
a real HealthCheck probe before submitting (its HealthCheck had no caller —
SURVEY §3.4), channel reuse, and bounded retries with exponential backoff
on transient transport failures.
"""

from __future__ import annotations

import logging
import threading
import time
from typing import Optional

import grpc
import numpy as np

from dnn_tpu import obs
from dnn_tpu.chaos import inject as _chaos_inject
from dnn_tpu.comm import transport as _tx
from dnn_tpu.comm import wire_pb2 as pb
from dnn_tpu.comm import wirecodec as wc
from dnn_tpu.comm.service import (
    PER_STAGE_BUDGET_S,
    RETRYABLE_CODES,
    SERVICE_NAME,
    _tensor_arr,
    _tensor_msg,
    full_jitter_delay as _backoff_delay,
)
from dnn_tpu.io.serialization import PayloadCorruptError
from dnn_tpu.utils.metrics import labeled

log = logging.getLogger("dnn_tpu.comm")


def pipeline_budget(num_parts: int, *, margin: float = 30.0,
                    transport: str = "grpc", warm: bool = False) -> float:
    """Overall edge-client budget for one pipeline traversal: one per-stage
    slice per part plus a margin. Strictly larger than the first hop's
    server-side budget (transport.hop_budget_s over num_parts - 1 stages,
    see StageServer._forward), so a downstream timeout surfaces to the
    client as an error status from the first stage, never as the client's
    own DEADLINE_EXCEEDED racing the relay. `transport` is the edge hop's
    NEGOTIATED transport — a device/shm pipeline sheds the gRPC
    serialization margin per stage (the satellite fix), and `warm=True`
    additionally drops to the post-compile slice ONLY when the caller
    knows every downstream hop is warm too: the domination invariant
    above assumes uniform rungs, so a cold or mixed pipeline must keep
    the default cold slice (and a pipeline whose downstream rungs fall
    back to grpc should keep transport="grpc", whose arithmetic is
    reference-compatible bit-exact)."""
    if transport in ("device", "shm"):
        return _tx.hop_budget_s(transport, num_parts, warm=warm) + margin
    return PER_STAGE_BUDGET_S * num_parts + margin



class CircuitOpenError(RuntimeError):
    """Raised by a fast-failing client whose breaker is OPEN: the target
    has failed `threshold` consecutive calls and the cooldown has not
    elapsed. Callers treat it like UNAVAILABLE without paying the
    connect timeout + retry ladder per request."""


class CircuitBreaker:
    """Per-target circuit breaker: closed -> (threshold consecutive
    failures) -> open -> (cooldown) -> half-open (ONE probe call) ->
    closed on success / open with doubled cooldown on failure. A
    flapping stage then sheds load in O(1) per request instead of
    burning a full retry ladder each, and the half-open probe bounds
    detection of recovery to one cooldown. Thread-safe; state
    transitions land in the flight ring and the
    `comm.circuit_state{target=}` gauge (0 closed / 1 half-open / 2
    open)."""

    _STATE_VAL = {"closed": 0.0, "half_open": 1.0, "open": 2.0}

    def __init__(self, target: str = "", *, threshold: int = 5,
                 cooldown_s: float = 1.0, max_cooldown_s: float = 30.0):
        self.target = target
        self.threshold = int(threshold)
        self.cooldown_s = float(cooldown_s)
        self.max_cooldown_s = float(max_cooldown_s)
        self._lock = threading.Lock()
        self._state = "closed"
        self._failures = 0
        self._opened_at = 0.0
        self._cooldown = self.cooldown_s
        m = obs.metrics()
        if m is not None:
            m.set_fn(labeled("comm.circuit_state", target=target),
                     lambda: self._STATE_VAL[self._state])

    @property
    def state(self) -> str:
        return self._state

    def release(self):
        """Give back a HALF-OPEN probe slot without judging it — used
        when the caller that consumed the slot DELEGATES the actual
        call elsewhere (send_tensors falling back to per-item
        send_tensor, which runs its own allow/record cycle). Re-opens
        with the cooldown already elapsed, so the next allow() hands
        the probe slot to the delegate immediately; without this the
        breaker would sit in half_open with no probe in flight and
        shed 100% of traffic forever."""
        with self._lock:
            if self._state == "half_open":
                self._state = "open"
                self._opened_at = time.monotonic() - self._cooldown

    def allow(self) -> bool:
        """True when a call may proceed. In OPEN, flips to HALF-OPEN
        (allowing exactly one probe) once the cooldown elapses."""
        with self._lock:
            if self._state == "closed":
                return True
            if self._state == "open":
                if time.monotonic() - self._opened_at < self._cooldown:
                    return False
                self._state = "half_open"
                obs.flight.record("circuit_half_open", target=self.target)
                return True
            # half_open: one probe is already in flight
            return False

    def record(self, ok: bool):
        with self._lock:
            if ok:
                if self._state != "closed":
                    obs.flight.record("circuit_close", target=self.target)
                self._state = "closed"
                self._failures = 0
                self._cooldown = self.cooldown_s
                return
            self._failures += 1
            if self._state == "half_open":
                # failed probe: reopen with a longer cooldown
                self._state = "open"
                self._opened_at = time.monotonic()
                self._cooldown = min(self._cooldown * 2,
                                     self.max_cooldown_s)
                obs.flight.record("circuit_reopen", target=self.target,
                                  cooldown_s=round(self._cooldown, 3))
            elif self._state == "closed" \
                    and self._failures >= self.threshold:
                self._state = "open"
                self._opened_at = time.monotonic()
                obs.flight.record("circuit_open", target=self.target,
                                  failures=self._failures,
                                  cooldown_s=round(self._cooldown, 3))


def _gen_rid(max_new_tokens, seed, temperature, top_k, top_p,
             adapter=None, min_p=None, repetition_penalty=None,
             logit_bias=None, dedup=None):
    """Encode generation options into the request_id the LM daemon parses
    (lm_server.parse_gen_options): positional max_new/seed, then named
    t=/k=/p=/m=/r= sampling overrides and a= (the per-request LoRA
    adapter index of a multi-adapter server)."""
    rid = f"gen:{max_new_tokens}" + (f":{seed}" if seed is not None else "")
    if temperature is not None:
        rid += f":t={temperature}"
    if top_k is not None:
        rid += f":k={top_k}"
    if top_p is not None:
        rid += f":p={top_p}"
    if min_p is not None:
        rid += f":m={min_p}"
    if repetition_penalty is not None:
        rid += f":r={repetition_penalty}"
    if logit_bias:
        pairs = ",".join(f"{int(t)}~{float(v)}"
                         for t, v in logit_bias.items())
        rid += f":b={pairs}"
    if adapter is not None:
        rid += f":a={adapter}"
    if dedup is not None:
        # exactly-once guard: the LM daemon's admission dedups on this
        # key, so a client-side retry after a drain/requeue can never
        # run the same generation twice (lm_server parse_gen_options d=)
        rid += f":d={dedup}"
    return rid


class _Channel:
    """One gRPC channel and the count of calls riding it. A channel
    NodeClient has replaced is closed when its last call ends, never
    under one: closing cancels every call on it, and a call that is
    decoding on a live server is not the wedged connect the rebuild is
    for."""

    __slots__ = ("chan", "live", "retired")

    def __init__(self, address: str):
        self.chan = grpc.insecure_channel(
            address, options=_tx.GRPC_MSG_OPTIONS)
        self.live = 0
        self.retired = False


class NodeClient:
    """Sync client for a NodeService endpoint (ours or a reference node's —
    the wire protocol is identical).

    `transport` sets the hop preference for tensor submissions
    (comm/transport.py): "auto" (default) negotiates device -> shm ->
    grpc on first send via a wire-compatible SendMessage handshake —
    reference peers (and the LM daemon, which declines) land on grpc
    transparently; explicit "device"/"shm" fail loud when unsatisfiable;
    "grpc" skips the handshake entirely (byte-identical reference
    behavior).

    Resilience (ISSUE 8): `breaker=True` (default) runs a per-client
    CircuitBreaker — after `threshold` consecutive terminal send
    failures the client fails fast (CircuitOpenError) for a cooldown
    instead of burning the full retry ladder per request, with one
    half-open probe per cooldown to detect recovery; pass False to
    disable or a prebuilt CircuitBreaker to share/tune one. A gRPC
    channel that entered connect backoff is REBUILT (fresh channel)
    after `rebuild_after` consecutive UNAVAILABLE outcomes: a sync
    channel whose first connects failed can sit out gRPC's internal
    reconnect backoff and miss a server that has since come up — the
    PR 7 lesson the transport test used to work around with a fresh
    client per poll. Calls still running on the replaced channel finish
    there (`_Channel`). Health probes count toward (and benefit from) the
    rebuild streak but bypass the breaker — they ARE the recovery
    probe."""

    REBUILD_AFTER = 2  # consecutive UNAVAILABLEs before a fresh channel

    def __init__(self, address: str, *, transport: str = "auto",
                 breaker=True, rebuild_after: Optional[int] = None):
        from dnn_tpu.native import native_available

        native_available()  # warm the one-time native codec build up front
        if transport not in _tx.TRANSPORTS:
            raise ValueError(
                f"transport must be one of {_tx.TRANSPORTS}, got "
                f"{transport!r}")
        self.address = address
        self.transport = transport
        self._chan = _Channel(address)
        self._chan_lock = threading.Lock()
        self._conn_fail_streak = 0
        self._last_rebuild = 0.0
        self.rebuild_after = self.REBUILD_AFTER if rebuild_after is None \
            else int(rebuild_after)
        self.channel_rebuilds = 0
        if breaker is True:
            self.breaker: Optional[CircuitBreaker] = CircuitBreaker(address)
        elif breaker:
            self.breaker = breaker
        else:
            self.breaker = None
        self._negotiated: Optional[_tx.Negotiated] = None
        self._neg_lock = threading.Lock()

    # -- channel health (the wedged-backoff rebuild, ISSUE 8 satellite) --

    def _note_conn_result(self, code) -> None:
        """Track consecutive connect-level failures; at `rebuild_after`
        the channel is replaced wholesale. Only UNAVAILABLE counts —
        it is the one code gRPC returns both for a refused connect and
        for a channel sitting in reconnect backoff; application errors
        (INVALID_ARGUMENT, DEADLINE on a live server) prove the
        connection works and reset the streak."""
        if code != grpc.StatusCode.UNAVAILABLE:
            self._conn_fail_streak = 0
            return
        self._conn_fail_streak += 1
        if self._conn_fail_streak >= self.rebuild_after:
            self._rebuild_channel()

    def _lease(self) -> _Channel:
        """The current channel, with one more call counted on it; every
        lease is handed back through `_release`."""
        with self._chan_lock:
            ch = self._chan
            ch.live += 1
        return ch

    def _release(self, ch: _Channel) -> None:
        with self._chan_lock:
            ch.live -= 1
            close = ch.retired and ch.live == 0
        if close:
            ch.chan.close()

    def _unary(self, method: str, request, *, timeout: float,
               request_serializer, response_deserializer):
        """One unary call on the current channel (looked up per call: a
        rebuild between attempts takes effect on the next attempt)."""
        ch = self._lease()
        try:
            return ch.chan.unary_unary(
                f"/{SERVICE_NAME}/{method}",
                request_serializer=request_serializer,
                response_deserializer=response_deserializer,
            )(request, timeout=timeout)
        finally:
            self._release(ch)

    def _rebuild_channel(self):
        with self._chan_lock:
            now = time.monotonic()
            if now - self._last_rebuild < 1.0:
                # concurrent failing calls all cross the streak at once
                # during an outage; one fresh channel per second is the
                # fix — a rebuild storm is not
                self._conn_fail_streak = 0
                return
            self._last_rebuild = now
            old, self._chan = self._chan, _Channel(self.address)
            old.retired = True
            self._conn_fail_streak = 0
            self.channel_rebuilds += 1
            close = old.live == 0
        if close:
            old.chan.close()  # else its last call's _release closes it
        m = obs.metrics()
        if m is not None:
            m.inc(labeled("comm.channel_rebuilds_total",
                          target=self.address))
        obs.flight.record("channel_rebuild", target=self.address,
                          rebuilds=self.channel_rebuilds)
        log.info("rebuilt gRPC channel to %s after %d consecutive "
                 "connect failures", self.address, self.rebuild_after)

    # -- transport negotiation (comm/transport.py) ----------------------

    def _raw_send_message(self, sender_id: str, text: str,
                          timeout: float = 10.0) -> str:
        """Bare SendMessage (no spans/tagging) — the negotiation
        side-channel."""
        return self._unary(
            "SendMessage",
            pb.MessageRequest(sender_id=sender_id, message_text=text),
            timeout=timeout,
            request_serializer=pb.MessageRequest.SerializeToString,
            response_deserializer=pb.MessageReply.FromString,
        ).confirmation_text

    def _ensure_negotiated(self) -> _tx.Negotiated:
        """Negotiate once per client. A transport-level RPC failure
        (endpoint not up yet) returns an UNCACHED grpc verdict — the
        unary send's own retry loop handles the outage, and the
        handshake reruns on the next call. TransportMisconfigError
        (explicit request refused) propagates — fail-loud."""
        with self._neg_lock:
            if self._negotiated is not None:
                return self._negotiated
            if self.transport == "grpc":
                self._negotiated = _tx.Negotiated(
                    "grpc", _tx.GrpcSender(), reason="explicit")
                return self._negotiated
            try:
                neg = _tx.negotiate_over(
                    self._raw_send_message, transport=self.transport,
                    target=self.address)
            except grpc.RpcError as e:
                code = e.code() if hasattr(e, "code") else None
                if code == grpc.StatusCode.UNIMPLEMENTED:
                    # peer has no SendMessage at all: a permanent verdict
                    self._negotiated = _tx.Negotiated(
                        "grpc", _tx.GrpcSender(), reason="no SendMessage")
                    return self._negotiated
                return _tx.Negotiated("grpc", _tx.GrpcSender(),
                                      reason=f"hello failed: {code}")
            self._negotiated = neg
            return neg

    def health_check(self, timeout: float = 5.0) -> bool:
        try:
            healthy = bool(self._unary(
                "HealthCheck", pb.Empty(), timeout=timeout,
                request_serializer=pb.Empty.SerializeToString,
                response_deserializer=pb.HealthCheckResponse.FromString,
            ).is_healthy)
            self._note_conn_result(None)
            return healthy
        except grpc.RpcError as e:
            # a probe that can't CONNECT advances the rebuild streak, so
            # polling health against a late-starting server self-heals
            # out of gRPC's internal backoff (wait_healthy needs no
            # fresh-client workaround anymore)
            self._note_conn_result(e.code() if hasattr(e, "code")
                                   else None)
            return False

    def send_message(self, sender_id: str, text: str, timeout: float = 5.0) -> str:
        # trace tag rides sender_id (the text front's request_id analog)
        with obs.start_span("rpc.SendMessage", parent=obs.current_span(),
                            target=self.address) as sp:
            return self._unary(
                "SendMessage",
                pb.MessageRequest(
                    sender_id=obs.tag_request_id(sender_id, sp),
                    message_text=text),
                timeout=timeout,
                request_serializer=pb.MessageRequest.SerializeToString,
                response_deserializer=pb.MessageReply.FromString,
            ).confirmation_text

    def wait_healthy(self, deadline: float = 30.0, interval: float = 0.5) -> bool:
        """Poll HealthCheck until it answers healthy or `deadline` seconds
        elapse. The startup-ordering fix for the reference's blind 2-second
        sleep before initiating (start_inference_after_delay, node.py:203-207)."""
        t_end = time.monotonic() + deadline
        while True:
            if self.health_check(timeout=min(5.0, interval * 4)):
                return True
            if time.monotonic() >= t_end:
                return False
            time.sleep(interval)

    def send_tensor(
        self,
        arr: np.ndarray,
        *,
        request_id: str = "req",
        timeout: float = 60.0,
        retries: int = 2,
        backoff: float = 0.2,
    ) -> tuple[str, Optional[np.ndarray]]:
        """Submit an activation; returns (status, final_tensor_or_None) —
        the response-chain semantics of node.py:180-194. Transient transport
        failures (RETRYABLE_CODES) are retried up to `retries` times with
        exponential backoff; the pipeline is stateless per request, so a
        resend is safe. `timeout` is the OVERALL budget across all attempts
        and backoff sleeps, not a per-attempt deadline.

        The payload rides the NEGOTIATED transport: a device hop hands
        the array through the in-process mailbox (zero serialization), a
        shm hop writes it once into a shared ring slot, and the grpc
        fallback carries the inline zero-copy tensor — byte-identical to
        the reference wire. Ticket payloads persist until the response
        lands, so transport-level retries stay safe on every rung.

        Observability: the call runs under an `rpc.SendTensor` span
        (parented to the ambient obs span when one is active) carrying a
        `transport` attr, and the span's trace rides to the server as a
        `tr=` request_id segment — wire-compatible (every peer treats
        request_id as opaque; our servers parse and continue the trace).
        Per-attempt latency and payload bytes land in the shared
        registry (histograms labeled by transport, plus the
        exact-quantile `comm.hop_seconds` series); each retry bumps
        `comm.retries_total{target=...,outcome=<code>}` (full-jitter
        backoff — see _backoff_delay) and logs the trace id so a
        backoff storm is attributable to the requests living through
        it. The remaining budget rides the wire as a `dl=` request_id
        segment (comm/transport.tag_deadline) for downstream hops to
        honor, and the client-side circuit breaker (see the class
        docstring) fails fast when the target is flapping."""
        if self.breaker is not None and not self.breaker.allow():
            raise CircuitOpenError(
                f"circuit open for {self.address}: shedding fast "
                f"(cooldown {self.breaker._cooldown:.1f}s)")
        neg = self._ensure_negotiated()
        sp = obs.start_span("rpc.SendTensor", parent=obs.current_span(),
                            target=self.address, transport=neg.name)
        # the propagated deadline (dl=) rides the request_id: downstream
        # hops cap their own forward/retry budgets to what THIS caller
        # still has, so a chain can never over-spend a dying deadline
        rid = obs.tag_request_id(request_id, sp) if sp else request_id
        request = neg.sender.make_request(
            arr, _tx.tag_deadline(rid, timeout))
        m = obs.metrics()
        deadline = time.monotonic() + timeout
        attempt = 0
        completed = False
        try:
            while True:
                remaining = deadline - time.monotonic()
                # refresh the propagated deadline EVERY attempt: after
                # retries + backoff the wire must advertise what is
                # actually left, not the original budget — or every
                # downstream hop over-spends a nearly-dead request
                request.request_id = _tx.tag_deadline(rid, remaining)
                t_try = time.perf_counter()
                if m is not None:
                    # per ATTEMPT: retries resend the payload, and the
                    # counter must match the bytes actually on the wire
                    # (and the server's direction="in" count)
                    m.inc(labeled("comm.payload_bytes_total",
                                  direction="out"), request.ByteSize())
                try:
                    _chaos_inject.perturb_rpc("client", self.address)
                    t_send_wall = time.time() if sp else 0.0
                    resp = self._unary(
                        "SendTensor", request,
                        timeout=max(remaining, 0.001),
                        request_serializer=wc.serialize_request,
                        response_deserializer=wc.parse_response)
                    dt = time.perf_counter() - t_try
                    if sp:
                        # clock-offset sampling for cross-host trace
                        # stitching (obs/fleet.py): the SUCCESSFUL
                        # attempt's wall-clock send/receive window — the
                        # span's own ts/dur covers retries and backoff
                        # sleeps, which would bias the NTP-style
                        # midpoint estimate by seconds
                        sp.set(cs=t_send_wall, cr=time.time())
                    if m is not None:
                        m.observe_hist(
                            labeled("comm.rpc_latency_seconds",
                                    method="SendTensor", role="client",
                                    transport=neg.name),
                            dt)
                        m.observe(labeled("comm.hop_seconds",
                                          target=self.address,
                                          transport=neg.name,
                                          mode="nested"), dt)
                        m.inc(labeled("comm.payload_bytes_total",
                                      direction="in"), resp.ByteSize())
                    # decode INSIDE the loop: a crc32c mismatch on the
                    # response is transient corruption, and resending is as
                    # safe as for a transport failure.
                    result = (
                        _tensor_arr(resp.result_tensor)
                        if resp.HasField("result_tensor") else None
                    )
                    sp.set(attempts=attempt + 1)
                    completed = True
                    self._note_conn_result(None)
                    return resp.status, result
                except (grpc.RpcError, PayloadCorruptError) as e:
                    code = e.code() if isinstance(e, grpc.RpcError) else None
                    self._note_conn_result(code)
                    if m is not None and \
                            code == grpc.StatusCode.DEADLINE_EXCEEDED:
                        m.inc(labeled("comm.deadline_exceeded_total",
                                          target=self.address))
                    retryable = isinstance(e, PayloadCorruptError) \
                        or code in RETRYABLE_CODES
                    # full jitter: decorrelates the retry herd so a
                    # partial outage is not amplified by synchronized
                    # resends; the out-of-budget check uses the WORST
                    # CASE delay, so the ladder still respects the
                    # propagated deadline exactly
                    worst = backoff * (2 ** attempt)
                    out_of_budget = deadline - time.monotonic() <= worst
                    if not retryable or attempt >= retries or out_of_budget:
                        sp.set(error=str(code or e), attempts=attempt + 1)
                        raise
                    delay = _backoff_delay(backoff, attempt)
                    if m is not None:
                        m.inc(labeled(
                            "comm.retries_total", target=self.address,
                            outcome=(code.name.lower() if code
                                     else "payload_corrupt")))
                    obs.flight.record(
                        "rpc_retry", target=self.address,
                        code=str(code or type(e).__name__),
                        attempt=attempt + 1, trace_id=sp.trace_id)
                    log.warning(
                        "send_tensor to %s failed (%s), retry %d/%d in "
                        "%.2fs [trace=%s]",
                        self.address, code or e, attempt + 1, retries,
                        delay, sp.trace_id or "-",
                    )
                    time.sleep(delay)
                    attempt += 1
        finally:
            # ticket payloads (device mailbox entry / shm ring slot)
            # live until the hop resolves, so retries can resend them
            if completed:
                neg.sender.sent_ok(request)
            else:
                neg.sender.cleanup(request)
            if self.breaker is not None:
                self.breaker.record(completed)
            sp.end()

    def send_tensors(
        self,
        arrs,
        *,
        request_id: str = "req",
        timeout: float = 120.0,
    ):
        """Submit a SEQUENCE of activations (microbatches) over the
        streamed Relay path: every item is acked by the first stage as
        soon as it is accepted, so stage 0 computes microbatch m+1 while
        the downstream stages work on m — the cross-process MPMD overlap
        the nested unary chain cannot express. Oversized payloads ride
        chunked (comm/transport.py CHUNK_BYTES), lifting the unary
        path's 4 MB gRPC message ceiling.

        Returns [(status, result_or_None), ...] in submission order.
        NOT retried: the stream is stateful (acks already released
        payload slots) — callers needing at-least-once fall back to
        per-item `send_tensor`. Peers without the Relay RPC (reference
        nodes) degrade to exactly that sequential unary fallback."""
        arrs = list(arrs)
        if not arrs:
            return []
        if self.breaker is not None and not self.breaker.allow():
            raise CircuitOpenError(
                f"circuit open for {self.address}: shedding fast")
        # EVERY exit path below must settle the breaker exactly once:
        # record an outcome, or RELEASE the (possibly half-open) probe
        # slot when the call is delegated to send_tensor, which runs
        # its own allow/record cycle — an un-settled half_open slot
        # would shed all traffic forever
        recorded = False

        def _breaker_done(ok: bool):
            nonlocal recorded
            if self.breaker is not None and not recorded:
                self.breaker.record(ok)
            recorded = True

        def _breaker_release():
            nonlocal recorded
            if self.breaker is not None and not recorded:
                self.breaker.release()
            recorded = True

        request_id = _tx.tag_deadline(request_id, timeout)
        neg = self._ensure_negotiated()
        if neg.relay_known and not neg.relay_ok:
            # the handshake already said the peer has no Relay RPC
            # (reference protocol): go straight to the unary chain
            # instead of paying a doomed probe per call
            _breaker_release()
            return [self.send_tensor(a, request_id=request_id,
                                     timeout=timeout) for a in arrs]
        sp = obs.start_span("rpc.Relay", parent=obs.current_span(),
                            target=self.address, transport=neg.name,
                            items=len(arrs))
        m = obs.metrics()
        pending = {}
        send_ts = {}
        results: dict = {}
        statuses: dict = {}

        def frames():
            for seq, arr in enumerate(arrs):
                req = neg.sender.make_request(
                    arr, obs.tag_request_id(request_id, sp)
                    if sp else request_id)
                pending[seq] = req
                send_ts[seq] = time.perf_counter()
                yield from _tx.split_requests(req, seq)

        ch = self._lease()
        try:
            call = ch.chan.stream_stream(
                f"/{SERVICE_NAME}/Relay",
                request_serializer=wc.serialize_request,
                response_deserializer=wc.parse_response,
            )
            for resp in call(frames(), timeout=timeout):
                seq = _tx.parse_ack(resp.status)
                if seq is not None:
                    req = pending.pop(seq, None)
                    if req is not None:
                        neg.sender.sent_ok(req)
                    if m is not None and seq in send_ts:
                        # hop latency under the streamed schedule:
                        # submit -> first-stage accept
                        dt = time.perf_counter() - send_ts[seq]
                        m.observe(labeled("comm.hop_ack_seconds",
                                          target=self.address,
                                          transport=neg.name), dt)
                        m.observe_hist(
                            labeled("comm.rpc_latency_seconds",
                                    method="Relay", role="client",
                                    transport=neg.name), dt)
                    continue
                seq, human = _tx.parse_result(resp.status)
                if seq is None or seq < 0:
                    # stream-level error status: surfaces on every
                    # not-yet-answered item
                    raise RuntimeError(
                        f"relay stream error: {human or resp.status}")
                statuses[seq] = human
                results[seq] = (_tensor_arr(resp.result_tensor)
                                if resp.HasField("result_tensor") else None)
                if len(results) == len(arrs):
                    break
        except grpc.RpcError as e:
            if e.code() == grpc.StatusCode.UNIMPLEMENTED:
                # reference peer: sequential unary fallback (idempotent
                # per item, so the ordinary retry machinery applies)
                sp.end(fallback="unary")
                _breaker_release()
                return [self.send_tensor(a, request_id=request_id,
                                         timeout=timeout) for a in arrs]
            sp.set(error=str(e.code()))
            self._note_conn_result(e.code())
            _breaker_done(False)
            raise
        except Exception:  # noqa: BLE001 — stream-level errors (relay
            # error status, response corruption) settle the breaker too
            _breaker_done(False)
            raise
        finally:
            self._release(ch)
            for req in pending.values():
                neg.sender.cleanup(req)
            pending.clear()
            sp.end()
        missing = [i for i in range(len(arrs)) if i not in statuses]
        _breaker_done(not missing)
        if missing:
            raise RuntimeError(
                f"relay stream ended without results for items {missing}")
        return [(statuses[i], results[i]) for i in range(len(arrs))]

    def generate(
        self,
        prompt_ids,
        *,
        max_new_tokens: int = 32,
        seed: Optional[int] = None,
        temperature: Optional[float] = None,
        top_k: Optional[int] = None,
        top_p: Optional[float] = None,
        min_p: Optional[float] = None,
        repetition_penalty: Optional[float] = None,
        logit_bias: Optional[dict] = None,
        adapter: Optional[int] = None,
        dedup: Optional[str] = None,
        timeout: float = 120.0,
    ) -> np.ndarray:
        """Client path for the LM daemon (dnn_tpu/runtime/lm_server.py):
        prompt token ids -> generated tokens. Options ride the request_id
        as "gen:max_new[:seed][:t=..][:k=..][:p=..][:m=..][:r=..][:b=..][:a=..]" — the same wire
        message a reference-built client would send, just with an integer
        payload. Sampling overrides are per request (None = server
        defaults). A request is self-contained (prompt + options), so the
        transport-level retries in send_tensor stay safe here; `dedup`
        (an opaque key, rides as d=) makes that at-least-once
        EXACTLY-once — the daemon's admission joins a retried key to
        the original request instead of generating twice."""
        rid = _gen_rid(max_new_tokens, seed, temperature, top_k, top_p,
                       adapter, min_p, repetition_penalty, logit_bias,
                       dedup)
        status, result = self.send_tensor(
            np.asarray(prompt_ids, np.int32).reshape(-1),
            request_id=rid, timeout=timeout,
        )
        if result is None:
            raise RuntimeError(f"LM server returned no tokens: {status}")
        return np.asarray(result, np.int32)

    def embed(self, prompt_ids, *, pooling: str = "mean",
              timeout: float = 60.0) -> np.ndarray:
        """Embedding endpoint of the LM daemon: prompt token ids -> the
        pooled final hidden state (f32 (C,)). `pooling` is "mean" (masked
        average over real tokens) or "last" (final token's state). Same
        wire message as everything else — the request_id "embed[:pool]"
        selects the endpoint (runtime/lm_server.SendTensor)."""
        status, result = self.send_tensor(
            np.asarray(prompt_ids, np.int32).reshape(-1),
            request_id=f"embed:{pooling}", timeout=timeout,
        )
        if result is None:
            raise RuntimeError(f"LM server returned no embedding: {status}")
        return np.asarray(result, np.float32)

    # -- disaggregated prefill/decode (dnn_tpu/control) -----------------

    def prefill_kv(self, prompt_ids, *, timeout: float = 60.0) -> np.ndarray:
        """Prefill-export endpoint: ask a PREFILL replica to run the
        prompt's chunk loop and return the packed KV handoff payload
        (one uint8 tensor — dnn_tpu/control/handoff.py). Hand it to a
        decode replica with `put_kv` and generate with the matching
        h=<key> option; the router does all three per request on a
        role-split fleet."""
        status, result = self.send_tensor(
            np.asarray(prompt_ids, np.int32).reshape(-1),
            request_id="prefill", timeout=timeout,
        )
        if result is None:
            raise RuntimeError(f"LM server returned no KV payload: {status}")
        return np.asarray(result, np.uint8)

    def put_kv(self, key: str, payload, *, timeout: float = 60.0) -> str:
        """Stage a prefill replica's KV payload on THIS server under
        `key` (single-use; consumed by a generate carrying h=<key>).
        Returns the server's status line; a geometry mismatch raises
        as INVALID_ARGUMENT."""
        status, _ = self.send_tensor(
            np.asarray(payload, np.uint8).reshape(-1),
            request_id=f"kvput:{key}", timeout=timeout,
        )
        return status

    # -- fleet KV tier (dnn_tpu/kvtier): block-granular migration -------

    def kv_stage(self, prompt_ids, *, timeout: float = 60.0) -> str:
        """Ask a replica to prefill these tokens' full blocks straight
        into its radix prefix store (no decode slot held) — the
        prefill half of disaggregated BLOCK handoff. Returns the
        status line (stage stats as JSON suffix)."""
        status, _ = self.send_tensor(
            np.asarray(prompt_ids, np.int32).reshape(-1),
            request_id="kvstage", timeout=timeout)
        return status

    def kv_lease(self, prompt_ids, *, timeout: float = 30.0) -> dict:
        """Donor side of a block pull: lease the longest resident
        block run for these tokens. Returns the offer meta — {lease,
        bytes, blocks, n_tokens, shm?, nonce?} (kvtier/migrate.py)."""
        import json as _json

        status, result = self.send_tensor(
            np.asarray(prompt_ids, np.int32).reshape(-1),
            request_id="kvlease", timeout=timeout)
        if result is None:
            raise RuntimeError(f"kvlease returned no meta: {status}")
        return _json.loads(np.asarray(result, np.uint8).tobytes())

    def kv_fetch(self, lease_id: str, *, timeout: float = 30.0
                 ) -> np.ndarray:
        """grpc rung of a block pull: the staged payload bytes for a
        lease. NOT_FOUND (raised as RpcError) = expired; the caller
        re-prefills."""
        status, result = self.send_tensor(
            np.zeros((1,), np.int32),
            request_id=f"kvfetch:{lease_id}", timeout=timeout)
        if result is None:
            raise RuntimeError(f"kvfetch returned no payload: {status}")
        return np.asarray(result, np.uint8)

    def kv_ack(self, lease_id: str, *, timeout: float = 10.0) -> str:
        """Confirm ingest of a pulled lease so the donor releases its
        staging NOW instead of waiting out the TTL."""
        status, _ = self.send_tensor(
            np.zeros((1,), np.int32),
            request_id=f"kvack:{lease_id}", timeout=timeout)
        return status

    def kv_pull_from(self, donor_address: str, prompt_ids, *,
                     timeout: float = 60.0) -> str:
        """Instruct THIS replica to pull the prefix's blocks from
        `donor_address` and adopt them (the router's migration
        instruction). Advisory: a failed pull answers a
        kvtier_fallback status, never an error — the follow-up
        generate re-prefills."""
        import json as _json

        spec = _json.dumps({
            "donor": donor_address,
            "tokens": [int(x) for x in
                       np.asarray(prompt_ids, np.int32).reshape(-1)],
        }).encode()
        status, _ = self.send_tensor(
            np.frombuffer(spec, np.uint8),
            request_id="kvpull", timeout=timeout)
        return status

    def send_tensor_stream(self, arr, *, request_id: str,
                           timeout: float = 120.0):
        """RAW streaming passthrough: submit `arr` on GenerateStream
        with `request_id` VERBATIM and yield each TensorResponse as it
        arrives — the router's relay primitive (generate_stream
        re-encodes options; a front door must forward the original
        id, dl=/tr=/d= segments and all). Abandoning the iterator
        cancels the RPC, which frees the upstream decode slot."""
        ch = self._lease()
        try:
            stream = self._generate_stream_call(ch)(
                wc.TensorRequest(
                    request_id=request_id,
                    tensor=_tensor_msg(
                        np.asarray(arr, np.int32).reshape(-1))),
                timeout=timeout,
            )
            try:
                yield from stream
            finally:
                stream.cancel()  # no-op on a finished stream
        finally:
            self._release(ch)

    @staticmethod
    def _generate_stream_call(ch: _Channel):
        return ch.chan.unary_stream(
            f"/{SERVICE_NAME}/GenerateStream",
            request_serializer=wc.serialize_request,
            response_deserializer=wc.parse_response,
        )

    def generate_stream(
        self,
        prompt_ids,
        *,
        max_new_tokens: int = 32,
        seed: Optional[int] = None,
        temperature: Optional[float] = None,
        top_k: Optional[int] = None,
        top_p: Optional[float] = None,
        min_p: Optional[float] = None,
        repetition_penalty: Optional[float] = None,
        logit_bias: Optional[dict] = None,
        adapter: Optional[int] = None,
        dedup: Optional[str] = None,
        timeout: float = 120.0,
    ):
        """Streaming client for the LM daemon's GenerateStream RPC: yields
        each token (int) as the server commits it. Abandoning the iterator
        (break / close / GC) cancels the RPC, which frees the server-side
        decode slot at its next step boundary — a disconnected client never
        decodes on to its budget. NOT retried: a stream is stateful (tokens
        already delivered), unlike the self-contained unary generate() —
        for the same reason a `dedup` key cannot JOIN a stream; the
        server accepts and ignores it."""
        rid = _gen_rid(max_new_tokens, seed, temperature, top_k, top_p,
                       adapter, min_p, repetition_penalty, logit_bias,
                       dedup)
        sp = obs.start_span("rpc.GenerateStream",
                            parent=obs.current_span(),
                            target=self.address)
        n = 0
        ch = self._lease()
        try:
            stream = self._generate_stream_call(ch)(
                wc.TensorRequest(
                    request_id=obs.tag_request_id(rid, sp),
                    tensor=_tensor_msg(
                        np.asarray(prompt_ids, np.int32).reshape(-1))),
                timeout=timeout,
            )
            try:
                for resp in stream:
                    if resp.HasField("result_tensor"):
                        n += 1
                        yield int(_tensor_arr(resp.result_tensor)[0])
            finally:
                stream.cancel()  # no-op on a finished stream
        finally:
            self._release(ch)
            sp.end(tokens=n)

    def generate_text(
        self,
        prompt: str,
        *,
        max_new_tokens: int = 32,
        seed: Optional[int] = None,
        temperature: Optional[float] = None,
        top_k: Optional[int] = None,
        top_p: Optional[float] = None,
        min_p: Optional[float] = None,
        repetition_penalty: Optional[float] = None,
        logit_bias: Optional[dict] = None,
        adapter: Optional[int] = None,
        timeout: float = 120.0,
    ) -> str:
        """Text client for a tokenizer-equipped LM daemon: the prompt rides
        SendMessage's message_text, generation options ride sender_id as
        "gen:max_new[:seed][:t=..][:k=..][:p=..][:m=..][:r=..][:b=..][:a=..]", and the reply is the
        generated continuation (lm_server.LMServer.SendMessage)."""
        rid = _gen_rid(max_new_tokens, seed, temperature, top_k, top_p,
                       adapter, min_p, repetition_penalty, logit_bias)
        return self.send_message(rid, prompt, timeout=timeout)

    def generate_text_stream(
        self,
        prompt: str,
        tokenizer,
        *,
        max_new_tokens: int = 32,
        seed: Optional[int] = None,
        temperature: Optional[float] = None,
        top_k: Optional[int] = None,
        top_p: Optional[float] = None,
        min_p: Optional[float] = None,
        repetition_penalty: Optional[float] = None,
        logit_bias: Optional[dict] = None,
        adapter: Optional[int] = None,
        timeout: float = 120.0,
    ):
        """Streaming TEXT client: encode the prompt with `tokenizer`
        (which must match the daemon's — the ids ride GenerateStream),
        yield UTF-8-safe text chunks as tokens commit. A multi-byte
        character split across BPE pieces is held until complete
        (io/tokenizer.stream_detokenizer), so the concatenation of the
        yielded chunks equals the one-shot decode of the full stream
        byte-for-byte for prefix-monotone tokenizers (ByteTokenizer and
        this package's HF adapter; see StreamingDetokenizer's docstring
        for the cleanup-rewriting caveat) — the text form of the serving
        edge the reference's unary SendTensor could never express
        (node_service.proto:7). Abandoning the iterator cancels the RPC
        (frees the server's decode slot), same as generate_stream."""
        from dnn_tpu.io.tokenizer import stream_detokenizer

        det = stream_detokenizer(tokenizer)
        for tok in self.generate_stream(
                tokenizer.encode(prompt), max_new_tokens=max_new_tokens,
                seed=seed, temperature=temperature, top_k=top_k,
                top_p=top_p, min_p=min_p,
                repetition_penalty=repetition_penalty,
                logit_bias=logit_bias, adapter=adapter, timeout=timeout):
            chunk = det.push(tok)
            if chunk:
                yield chunk
        tail = det.flush()
        if tail:
            yield tail

    def close(self):
        neg, self._negotiated = self._negotiated, None
        if neg is not None:
            neg.sender.close()
        self._chan.chan.close()
