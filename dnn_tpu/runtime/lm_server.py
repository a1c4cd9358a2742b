"""LM serving daemon: the ContinuousBatcher behind the gRPC edge.

The reference's defining trait is a long-lived serving *process*
(/root/reference/node.py:114-133 hosts a gRPC server until termination);
its only workload is one CNN forward per request. The rebuild's LM analog
is this daemon: a `NodeService` server whose SendTensor accepts a PROMPT
(1-D int32 token ids) and answers with the GENERATED TOKENS, decoding all
in-flight requests together through one continuous-batching pool
(dnn_tpu/runtime/serving.py) — requests enter and leave slots
independently, so concurrent callers share full batch width.

Wire-compatible by construction: every reference RPC is byte-identical
(dnn_tpu/comm/wire.proto keeps node_service.proto's methods, messages and
field numbering untouched) — a reference-built client drives this server
unmodified. Generation options ride the existing `request_id` field as
"gen[:max_new[:seed]]" (anything unparseable falls back to server
defaults). One ADDITIVE method exists beyond the reference protocol:
`GenerateStream`, the per-token streaming front (new method name;
reference peers never call it, so compatibility is preserved).

Threading model: gRPC handlers are async, device compute is blocking, so
ONE worker thread owns the batcher — it admits queued prompts whenever
slots free up, steps the pool while anything is active, and resolves a
`concurrent.futures.Future` per request that the async handlers await via
`asyncio.wrap_future`. Handlers never touch the device; the pool never
blocks the event loop (the reference blocks its loop on every hop,
node.py:181 — SURVEY §3.3).
"""

from __future__ import annotations

import asyncio
import json
import logging
import queue
import threading
import time
import weakref
from typing import Any, NamedTuple, Optional

import grpc
import numpy as np

from dnn_tpu import obs
from dnn_tpu.chaos import inject as _chaos_inject
from dnn_tpu.comm import transport as _tx
from dnn_tpu.comm import wire_pb2 as pb
from dnn_tpu.comm import wirecodec as wc
from dnn_tpu.comm.service import (
    PayloadCorruptError,
    _handlers,
    _tensor_arr,
    _tensor_msg,
)
from dnn_tpu.obs import profile as _profile
from dnn_tpu.obs.timeline import RPC_PARTS, RpcLoopClock, rpc_event_loop
from dnn_tpu.runtime.serving import ContinuousBatcher

log = logging.getLogger("dnn_tpu.lm_server")

__all__ = ["LMServer", "serve_lm", "start_lm_server_in_background",
           "parse_gen_options", "DrainingError", "EXIT_RESTART"]

#: exit code serve_lm returns when a wedged-policy escalation asked the
#: SUPERVISOR (node --supervise / chaos.supervisor) to restart this
#: process — distinct from crash (nonzero) and clean shutdown (0) so an
#: operator reading the supervisor log can tell policy from accident
EXIT_RESTART = 43


class DrainingError(RuntimeError):
    """A request rejected because the server is DRAINING: admission is
    closed, in-flight decodes are finishing, and this request should be
    retried against another replica. Maps to gRPC UNAVAILABLE — which
    the edge client's existing retry ladder already treats as
    retriable — so queued work is handed BACK, never lost."""


def parse_gen_options(request_id: str, default_max_new: int):
    """'gen[:max_new[:seed]][:t=TEMP][:k=TOPK][:p=TOPP][:m=MINP]
    [:r=REPPEN][:b=ID~VAL,ID~VAL][:a=ADAPTER][:j=JSONDEPTH]'
    -> (max_new, seed, opts).
    Only the literal 'gen' prefix carries options —
    any other request_id (e.g. a reference client's tracing id like
    'req:1234') gets the server defaults instead of being reinterpreted as
    a token budget. Positional segments are max_new then seed; named
    `key=value` segments (per-request sampling overrides, forwarded to
    ContinuousBatcher.submit) may appear anywhere after the prefix.
    Unparseable segments fall back to defaults (seed None = derive from
    the request id, the batcher's own convention). Unknown named
    segments are skipped — in particular `tr=...`, the obs layer's trace
    tag (dnn_tpu/obs.tag_request_id), rides through here untouched."""
    max_new, seed, opts = default_max_new, None, {}
    parts = (request_id or "").split(":")
    if parts[0] != "gen":
        return max_new, seed, opts
    def _parse_bias(val: str) -> dict:
        # "ID~VAL,ID~VAL" — ":" is the segment separator, so pairs ride
        # "~" within one segment
        out = {}
        for pair in val.split(","):
            tok, _, v = pair.partition("~")
            out[int(tok)] = float(v)
        return out

    named = {"t": ("temperature", float), "k": ("top_k", int),
             "p": ("top_p", float), "a": ("adapter", int),
             "m": ("min_p", float), "r": ("repetition_penalty", float),
             "b": ("logit_bias", _parse_bias),
             # exactly-once guard: admission dedups on this opaque key
             # (LMServer._dedup) so a client retry after a drain or a
             # worker-death requeue can never run the generation twice
             "d": ("dedup", str),
             # disaggregated prefill/decode (dnn_tpu/control): consume
             # the kvput:<key> payload a prefill replica handed off —
             # admission then ADOPTS the KV instead of prefilling
             # (LMServer._resolve_kv_handle -> submit(prefilled=...))
             "h": ("kv_handle", str),
             # JSON mode: constrain the completion to a JSON value nested
             # up to DEPTH levels (runtime/constrain.json_regex); resolved
             # to a compiled TokenConstraint in LMServer._preflight
             "j": ("json_depth", int)}
    pos = 0
    for seg in parts[1:]:
        if "=" in seg:
            key, _, val = seg.partition("=")
            if key in named:
                name, conv = named[key]
                try:
                    opts[name] = conv(val)
                except ValueError:
                    pass
            continue
        pos += 1
        try:
            if pos == 1:
                max_new = max(1, int(seg))
            elif pos == 2:
                seed = int(seg)
        except ValueError:
            pass
    return max_new, seed, opts


def _fail_future(fut, exc):
    """set_exception tolerant of a future the caller already abandoned
    (cancelled via asyncio.wait_for on its deadline) — InvalidStateError
    out of a cleanup path must never kill the worker."""
    try:
        if not fut.done():
            fut.set_exception(exc)
    except Exception:  # noqa: BLE001 — done()/set race with a cancel
        pass


class TokenSink:
    """What a streamed request registers with the worker as its `on_token`:
    the event loop its consumer runs on and the consumer's `put` (an
    asyncio.Queue's `put_nowait`). The worker never calls into it: the
    sinks' tokens of one loop iteration cross to `loop` in ONE
    `call_soon_threadsafe` (`_BatcherWorker._hand_off`), and `_fan_out`,
    on the loop's thread, gives each `put` its `("tok", (token,
    t_commit))`. The consumer waits on that queue alone: whatever else
    must wake it (`GenerateStream`'s `"done"`, its deadline's one timer)
    arrives as an item through the same `put`."""

    __slots__ = ("loop", "put")

    def __init__(self, loop, put):
        self.loop = loop
        self.put = put


def _fan_out(pairs, t_commit, clock=None, handoff=0):
    """On the event loop's thread: each `(put, token)` of one hand-off to
    its stream's queue, in commit order. A consumer that went away loses
    its own token only. `clock`, the loop thread's RpcLoopClock, gets the
    hand-off's lag and the section's seconds (`fan_out`); while a capture
    records the section is an `rpc.fan_out` annotation, `handoff` the
    hand-off's number among `serving.emit_handoffs_total`."""
    span = None
    if clock is not None:
        t = clock.fan_out_begins(t_commit)
        if _profile._capturing:
            span = _profile.open_span("rpc.fan_out", tokens=len(pairs),
                                      handoff=handoff)
    for put, tok in pairs:
        try:
            put(("tok", (tok, t_commit)))
        except Exception:  # noqa: BLE001 — one dead stream consumer
            log.debug("token sink refused a token", exc_info=True)
    if clock is not None:
        clock.section_ends("fan_out", t, span)


class _QueuedRequest(NamedTuple):
    """One request waiting for the batcher worker — named fields so the
    submit/admit/hold/drain sites stay self-describing (the tuple form
    needed every unpack edited in lockstep per added field)."""

    prompt: Any
    max_new: int
    seed: Any
    opts: Optional[dict]
    on_token: Any
    cancel_evt: Any
    trace: Any
    t_q: float  # perf_counter at enqueue — the queue-wait clock
    fut: Any
    attempts: int = 0  # worker-death requeues consumed (retry budget)


class _BatcherWorker(threading.Thread):
    """The one thread that talks to the device. Owns the ContinuousBatcher;
    everyone else submits (prompt, max_new, seed, future) through a queue."""

    def __init__(self, batcher: ContinuousBatcher,
                 compile_cache_budget: int = 512):
        super().__init__(daemon=True, name="lm-batcher")
        self.batcher = batcher
        # guard against unbounded XLA compile-cache growth (the suite's
        # segfault pathology — utils/xla_cache.py): counts the batcher's
        # compiled programs and clears ALL caches at the idle boundary
        # when the budget trips. A steady server (three programs) never
        # reaches 512; shape-churning workloads (many prompt buckets,
        # adapters, pooling variants) do, and recompile after the clear.
        from dnn_tpu.utils.xla_cache import CompileCacheGuard

        self.cache_guard = CompileCacheGuard(compile_cache_budget)
        for fn in batcher.jit_programs():  # spec variants add their own
            self.cache_guard.register(fn)
        self.q: "queue.Queue" = queue.Queue()
        self._stop_evt = threading.Event()
        self._abandon = False
        self._draining = False
        # worker-death hook (LMServer._on_worker_death): when set, a
        # step crash hands the surviving work (in-flight + queued
        # items) to the owner for requeue-or-fail instead of failing
        # everything — the recovery half of the `worker_died` event
        self.on_death = None
        # watchdog heartbeat (obs/watchdog.py): LMServer points this at
        # Watchdog.beat — one None check per loop iteration when off.
        # step_done -> Watchdog.step_done: until the first completed
        # step, a stale heartbeat is first-compile warmup, not a wedge
        self.heartbeat = None
        self.step_done = None
        # auto-profile arm (obs/profile.py, POST /profilez?auto=1): when
        # set, the loop times each step and captures the one AFTER the
        # first that exceeds the threshold; one None check per step when
        # disarmed
        self.auto_profile = None
        self._profile_hit = False
        # goodput/SLO tracker (obs/goodput.py): LMServer points this at
        # its GoodputTracker — _admit feeds the TTFT objective; one
        # None check when off
        self.goodput = None
        self.cpu_clock_id = None  # this thread's CPU clock, set by run()
        self._held_logged = None  # last item whose hold hit the flight
        # ring — identity-gates the per-retry held_back event
        # _lock serializes submit against the dead-marking in _fail_all /
        # abandon: without it a future enqueued between the worker's final
        # queue drain and thread exit would never resolve (the caller
        # would hang for request_timeout instead of failing fast)
        self._lock = threading.Lock()
        self._dead: "Exception | None" = None
        # rid -> {"fut", "on_token", "cancel_evt"}
        self._futures = {}
        # paged back-pressure: a request the batcher could not admit for
        # TRANSIENT lack of pool blocks (paged_kvcache.InsufficientBlocks)
        # waits here — retried ahead of the queue once decodes retire —
        # instead of failing its caller
        self._held = None
        # control ops (dnn_tpu/kvtier): batcher mutations that are NOT
        # request admissions — stage_prefix / kvtier_export /
        # kvtier_adopt all reassign pool leaves, so they MUST run on
        # this thread between steps (the single-producer contract the
        # donation invariant rests on). Drained at the top of every
        # loop iteration: a busy pool still applies a pull within one
        # step, not only at idle.
        self._cq: list = []
        # periodic housekeeping hook (LMServer wires lease/handoff TTL
        # sweeps): called once per loop iteration, rate-limited inside
        self.tick = None
        # [hand-offs, tokens they carried]: `call_soon_threadsafe` calls
        # made for TokenSinks' tokens (_hand_off). LMServer points this at
        # its own pair, so the scrape-time counters outlive a worker
        self.emit_counts = [0, 0]
        # the event-loop thread's clock, which `_fan_out` reports to
        # there (LMServer's, as the pair above; None: nobody's)
        self.rpc_clock = None

    def submit(self, prompt: np.ndarray, max_new: int, seed, *,
               opts=None, on_token=None, cancel_evt=None, trace=None):
        """Queue a request. `opts` (optional dict) forwards per-request
        sampling overrides to ContinuousBatcher.submit (temperature /
        top_k / top_p). `on_token` (optional) is the streaming hook: a
        `TokenSink` (what `GenerateStream` registers) has its tokens
        handed to its event loop ONCE a loop iteration, together with
        every other sink's of that step, in commit order — tokens cross
        threads once a step, not once a token; a plain callable
        `on_token(tok)` fires from the worker thread for every token as
        it commits, outside that batch.
        `cancel_evt` (optional threading.Event) set by the caller retires
        the request's slot at the next step boundary; its future resolves
        cancelled. `trace` (optional obs span) parents this request's
        span tree: the worker records queue_wait at admission and the
        batcher hangs admit/prefill/decode spans under it."""
        import concurrent.futures

        fut = concurrent.futures.Future()
        with self._lock:
            if self._draining and self._dead is None:
                # admission is CLOSED but the pool is still finishing:
                # hand the request straight back with the retriable
                # draining status (never enqueue work the drain exit
                # would have to fail later anyway). Through the guarded
                # settle (CON002): the future is fresh here, but every
                # settle in this module goes through one guarded path —
                # the unguarded form is exactly the PR 4 worker-killer.
                _fail_future(fut, DrainingError(
                    "LM server draining: admission closed; retry "
                    "against another replica"))
                return fut
            if self._dead is not None:
                _fail_future(fut, self._dead)
                if (g := self.goodput) is not None:
                    g.on_outcome(False)  # fast-fails burn availability
                return fut
            self.q.put(_QueuedRequest(prompt, max_new, seed, opts,
                                      on_token, cancel_evt, trace,
                                      time.perf_counter(), fut))
            m = obs.metrics()
            if m is not None:
                # CALLABLE gauge: the shutdown/failure paths drain the
                # queue with bare get_nowait(), so a stored depth would
                # freeze at its pre-drain value — qsize reads fresh at
                # every scrape instead
                m.set_fn("serving.queue_depth", self.q.qsize)
        return fut

    def submit_control(self, fn):
        """Queue `fn()` to run on the worker thread between steps (the
        KV-tier seam: stage/export/adopt mutate pool state the step
        loop owns). Returns a concurrent.futures.Future resolving to
        fn()'s result; fails fast when the worker is dead."""
        import concurrent.futures

        fut = concurrent.futures.Future()
        with self._lock:
            if self._dead is not None:
                _fail_future(fut, self._dead)
                return fut
            self._cq.append((fn, fut))
        return fut

    def _run_control_ops(self):
        """Drain queued control ops — top of every loop iteration, so
        a pull lands within one step even on a busy pool. Settles are
        guarded (CON002): the caller may have deadline-cancelled."""
        while True:
            with self._lock:
                if not self._cq:
                    return
                fn, fut = self._cq.pop(0)
            try:
                res = fn()
            except BaseException as e:  # noqa: BLE001 — the op's error
                # belongs to its caller, never to the serving loop
                _fail_future(fut, e)
            else:
                try:
                    fut.set_result(res)
                except Exception:  # noqa: BLE001 — abandoned future
                    pass

    def _fail_control(self, exc):
        """Fail every queued control op (worker death / shutdown)."""
        with self._lock:
            ops, self._cq = self._cq, []
        for _fn, fut in ops:
            _fail_future(fut, exc)

    def _resubmit(self, item: _QueuedRequest) -> bool:
        """Requeue a surviving item from a DEAD predecessor worker,
        preserving its future / queue clock / attempt count. False when
        this worker is itself already dead (the caller then fails the
        item's future)."""
        with self._lock:
            if self._dead is not None or self._draining:
                return False
            self.q.put(item)
        return True

    def begin_drain(self):
        """Connection-draining entry: stop admission NOW, finish
        in-flight decodes, hand queued-but-unadmitted work back with
        the retriable draining status, then exit the thread. The run
        loop notices `_draining` at its next iteration; submit() starts
        rejecting immediately."""
        with self._lock:
            self._draining = True
        self._stop_evt.set()  # wake a worker parked in q.get(timeout)
        obs.flight.record("drain_begin", queued=self.q.qsize(),
                          active=self.batcher.n_active)

    def _drain_handback(self):
        """Fail every queued (never-admitted) item with the RETRIABLE
        draining error — the hand-back half of draining. Held-back
        items never prefilled, so they hand back too."""
        exc = DrainingError(
            "LM server draining: request was queued but not admitted; "
            "retry against another replica")
        n = 0
        with self._lock:
            if self._held is not None:
                held, self._held = self._held, None
                _fail_future(held.fut, exc)
                n += 1
            while True:
                try:
                    _fail_future(self.q.get_nowait().fut, exc)
                    n += 1
                except queue.Empty:
                    break
        if n:
            obs.flight.record("drain_handback", requests=n)

    def stop(self, *, drain: bool = True):
        """Signal shutdown. drain=True: the loop exits once the pool and
        queue are empty. drain=False: abandon in-flight decodes too —
        queued futures are cancelled here, admitted ones by the loop on
        its next iteration (the worker must not keep stepping the device
        after close())."""
        with self._lock:
            if not drain:
                self._abandon = True
                if self._dead is None:
                    self._dead = RuntimeError("LM server shut down")
                while True:
                    try:
                        self.q.get_nowait().fut.cancel()
                    except queue.Empty:
                        break
            elif self._dead is None:
                # drain path: mark dead BEFORE signaling stop so a submit
                # racing the loop's final pool-empty/queue-empty check fails
                # fast instead of enqueueing a future after the thread
                # exits (which would hang its caller for request_timeout).
                # Items already queued under the lock are still drained.
                self._dead = RuntimeError("LM server shutting down")
        self._stop_evt.set()

    # ------------------------------------------------------------------

    def _admit(self, item: _QueuedRequest) -> bool:
        """Admit one queued request. Returns False when the request was
        HELD BACK (paged pool transiently full) — the admission loop must
        then stop pulling more work until blocks free (`t_q` is preserved
        through holds, so the recorded queue wait spans until the attempt
        that actually admits)."""
        from dnn_tpu.runtime.paged_kvcache import InsufficientBlocks

        if item.cancel_evt is not None and item.cancel_evt.is_set():
            item.fut.cancel()  # cancelled while still queued: never admit
            return True
        wait = time.perf_counter() - item.t_q
        try:
            if _chaos_inject.kv_exhaust():
                # injected pool exhaustion (dnn_tpu/chaos): exercises
                # the held-back path below exactly as a real full pool
                raise InsufficientBlocks(
                    "chaos: injected KV pool exhaustion")
            rid = self.batcher.submit(item.prompt, item.max_new,
                                      seed=item.seed, trace=item.trace,
                                      **(item.opts or {}))
        except InsufficientBlocks:
            # flight: submit() already recorded pool_exhausted (once per
            # episode); this is the queueing front's held-back decision —
            # recorded once per ITEM, not once per retry (the run loop
            # re-submits the held item every decode step, which at ms
            # cadence would flood the ring during a long shortage)
            if item is not self._held_logged:
                obs.flight.record("held_back", queue_depth=self.q.qsize())
                self._held_logged = item
            self._held = item
            return False
        except Exception as e:  # noqa: BLE001 — validation errors belong to
            obs.flight.record("admit_rejected", error=str(e)[:200])
            # the submitting request, not the loop — and guarded: the
            # caller may have deadline-cancelled this future while it
            # queued, and an InvalidStateError here would kill the worker
            _fail_future(item.fut, e)
            return True
        obs.flight.record(
            "admit", rid=rid, queue_wait_ms=round(wait * 1e3, 3),
            prompt_len=int(np.asarray(item.prompt).size),
            max_new=item.max_new,
            trace_id=item.trace.trace_id if item.trace else None)
        # first token: the convoy path samples it during submit()'s
        # inline prefill; interleaved admission (prefill_chunk_tokens)
        # defers it to a later mixed step's commit — first_token then
        # reads None and TTFT is recorded when the rid first appears in
        # the step loop's output instead
        first = self.batcher.first_token(rid)
        m = obs.metrics()
        if m is not None:
            m.observe("serving.queue_wait_seconds", wait)
            m.set_fn("serving.queue_depth", self.q.qsize)
            if first is not None:
                # end-to-end TTFT: enqueue -> first token (sampled
                # during the batcher's prefill, which submit() just ran)
                ttft = time.perf_counter() - item.t_q
                m.observe("serving.ttft_seconds", ttft)
                if (g := self.goodput) is not None:
                    g.on_ttft(ttft)  # SLO burn-rate window (obs/goodput)
        if item.trace:
            obs.record_span("queue_wait", item.t_q, wait,
                            parent=item.trace)
        rec = {"fut": item.fut, "on_token": item.on_token,
               "cancel_evt": item.cancel_evt,
               # the original submission, kept so a worker death can
               # requeue it (attempts bounds the retries; lm_server
               # _on_worker_death)
               "item": item}
        if first is None:
            rec["ttft_t0"] = item.t_q  # deferred: the run loop records
            # TTFT at the first committed token
        self._futures[rid] = rec
        if item.on_token is not None and first is not None:
            # handed over now, alone: TTFT does not wait for the step
            batch = {}
            self._emit_token(rec, rid, first, batch)
            self._hand_off(batch)
        return True

    def _emit_token(self, rec, rid, tok, batch):
        """One committed token of request `rid` (record `rec`) to its
        consumer: a TokenSink's joins `batch` ({loop: [(put, token)]},
        which the caller hands off), a plain callable is called here."""
        cb = rec["on_token"]
        if cb is None:
            return
        if type(cb) is TokenSink:
            batch.setdefault(cb.loop, []).append((cb.put, int(tok)))
            return
        try:
            cb(int(tok))
        except Exception:  # noqa: BLE001 — a dead stream consumer must not
            log.debug("on_token callback failed for rid %d", rid,
                      exc_info=True)  # kill the device loop

    def _hand_off(self, batch):
        """`batch`, {loop: [(put, token)] in commit order}, to the sinks'
        event loops: ONE `call_soon_threadsafe` a loop (the daemon has
        one), under one `perf_counter` stamp. Each call writes the loop's
        self-pipe and wakes its thread, which then wants the interpreter
        lock this thread needs to launch the next step — once a step
        instead of once a token. A closed loop loses its own tokens only."""
        if not batch:
            return
        t_commit = time.perf_counter()
        for loop, pairs in batch.items():
            try:
                loop.call_soon_threadsafe(_fan_out, pairs, t_commit,
                                          self.rpc_clock,
                                          self.emit_counts[0] + 1)
            except RuntimeError:  # the loop closed under its streams
                log.debug("hand-off of %d tokens failed", len(pairs),
                          exc_info=True)
                continue
            self.emit_counts[0] += 1
            self.emit_counts[1] += len(pairs)

    def _process_cancels(self):
        """Retire cancelled requests at the step boundary: the slot
        re-enters the free pool (batcher.cancel) and the future resolves
        cancelled — the caller's disconnect must not decode on to its
        token budget."""
        for rid, rec in list(self._futures.items()):
            evt = rec["cancel_evt"]
            if evt is not None and evt.is_set():
                if self.batcher.cancel(rid):
                    try:  # drop the cancelled record — nobody claims it
                        self.batcher.claim(rid)
                    except KeyError:
                        pass
                del self._futures[rid]
                rec["fut"].cancel()

    def _publish_done(self):
        b = self.batcher
        for rid in [r for r in self._futures if r in b.results]:
            # claim (not read) releases the batcher's per-request
            # bookkeeping — results, finish reason, logprobs — so a
            # long-lived daemon's dicts don't grow without bound
            tokens, _reason, _lps = b.claim(rid)
            fut = self._futures.pop(rid)["fut"]
            try:
                fut.set_result(tokens)
            except Exception:  # noqa: BLE001 — the caller abandoned the
                # future (a unary deadline abort cancels it through
                # asyncio.wait_for -> wrap_future); publishing to a
                # cancelled future raises InvalidStateError and used to
                # KILL the worker thread — the result is simply dropped
                pass

    def _shutdown_drain_queue(self):
        """Final drain-path exit step, under _lock: mark dead and fail any
        future that slipped into the queue between the loop's last
        queue-empty check and its stop-event check (the TOCTOU window —
        submit saw _dead=None and enqueued just before stop() marked dead).
        Failing fast here bounds that racer to an immediate shutdown error
        instead of a request_timeout hang."""
        with self._lock:
            if self._dead is None:
                self._dead = RuntimeError("LM server shutting down")
            if self._held is not None:
                held, self._held = self._held, None
                _fail_future(held.fut, self._dead)
        self._fail_control(self._dead)
        with self._lock:
            while True:
                try:
                    _fail_future(self.q.get_nowait().fut, self._dead)
                except queue.Empty:
                    return

    def _collect_for_requeue(self):
        """Death-path collection for the requeue hook: mark this worker
        dead (so racing submits fail fast) and hand over the surviving
        work — [(rid, item)] for admitted-but-unfinished requests,
        [item] for queued/held ones. The futures stay UNRESOLVED; the
        hook owns their fate (requeue into a successor worker, or
        fail)."""
        with self._lock:
            if self._dead is None:
                self._dead = RuntimeError("LM batcher worker died")
        # control ops are replica-local pool mutations: never requeued
        # onto a successor (its pool is fresh — a stale pull would
        # ingest against different block ids); their callers retry
        self._fail_control(self._dead)
        with self._lock:
            inflight = [(rid, rec["item"])
                        for rid, rec in self._futures.items()
                        if rec.get("item") is not None]
            self._futures.clear()
            queued = []
            if self._held is not None:
                queued.append(self._held)
                self._held = None
            while True:
                try:
                    queued.append(self.q.get_nowait())
                except queue.Empty:
                    break
        return inflight, queued

    def _fail_all(self, exc):
        self._fail_control(exc)
        with self._lock:
            self._dead = exc  # submits from here on fail immediately
            failed = len(self._futures)
            for rec in self._futures.values():
                _fail_future(rec["fut"], exc)
            self._futures.clear()
            if self._held is not None:
                held, self._held = self._held, None
                _fail_future(held.fut, exc)
                failed += 1
            while True:
                try:
                    _fail_future(self.q.get_nowait().fut, exc)
                    failed += 1
                except queue.Empty:
                    break
        # error-path failures must burn the availability budget too — a
        # worker death that fails every in-flight request is exactly the
        # outage the objective exists to page on (retirement-path
        # outcomes feed from _obs_retire; this path never retires)
        if (g := self.goodput) is not None:
            for _ in range(failed):
                g.on_outcome(False)

    def _step_pool(self, b):
        """One pool step, with the auto-profile arm folded in: disarmed
        (the steady state) this is one None check around b.step().
        Armed, each step is timed; the step AFTER the first breach runs
        inside a jax.profiler capture (obs/profile.py) and disarms."""
        _chaos_inject.step_fault()  # injected device fault: raises at
        # the scheduled step counter -> the ordinary worker-death path
        ap = self.auto_profile
        if ap is None:
            self._profile_hit = False
            return b.step()
        if self._profile_hit:
            from dnn_tpu.obs.profile import ProfilerBusy, capture_step

            self.auto_profile = None
            self._profile_hit = False
            try:
                path, stepped = capture_step(
                    b.step, capture_root=ap.get("capture_root"),
                    keep=ap.get("keep", 8), extra_s=ap.get("extra_s", 0.0),
                    perfetto=ap.get("perfetto", False),
                    python_tracer=ap.get("python_tracer"))
                log.info("auto-profile captured slow-step follow-up to %s",
                         path)
                return stepped
            except ProfilerBusy:
                return b.step()
        t0 = time.perf_counter()
        stepped = b.step()
        if time.perf_counter() - t0 > ap["threshold_s"]:
            self._profile_hit = True
        return stepped

    def run(self):
        """The worker's loop. Each iteration tells the step clock which
        part it is in (obs/timeline.LOOP_PARTS: `pre`, `wait`, `admit`,
        the step, `emit`), so that the thread's time outside step() and
        submit() is counted, and written as `loop.*` spans while a
        capture records; one None check a part without a clock."""
        b = self.batcher
        # this thread's CPU clock, for the scrape-time
        # process.thread_cpu_seconds_total{thread="worker"} (the id of a
        # thread that has ended fails cleanly in clock_gettime)
        self.cpu_clock_id = time.pthread_getcpuclockid(self.ident)
        n_iter = 0
        while True:
            sc = b.step_clock
            if sc is not None:
                sc.loop_part("pre", n_iter)
            n_iter += 1
            hb = self.heartbeat
            if hb is not None:
                hb()
            # KV-tier control ops + housekeeping tick: between steps,
            # on the one thread that owns the pool (one len-check /
            # None-check each when idle)
            if self._cq:
                self._run_control_ops()
            tk = self.tick
            if tk is not None:
                tk()
            if self._abandon:
                self._fail_control(RuntimeError("LM server shut down"))
                with self._lock:
                    for rec in self._futures.values():
                        rec["fut"].cancel()
                    self._futures.clear()
                    if self._held is not None:
                        held, self._held = self._held, None
                        held.fut.cancel()
                return
            self._process_cancels()  # step boundary: free cancelled slots
            arrived = None  # what the idle wait below was woken by
            if self._draining:
                # connection draining: queued work handed back
                # retriable, in-flight decodes stepped to completion
                # below, then a clean exit (submit already rejects)
                self._drain_handback()
                if b.n_active == 0:
                    with self._lock:
                        if self._dead is None:
                            self._dead = DrainingError(
                                "LM server drained and exited")
                    self._fail_control(self._dead)
                    obs.flight.record("drain_done")
                    return
            elif b.n_active == 0 and self.q.empty() and self._held is None:
                # overlap mode: the pool emptied with one dispatched
                # step still uncommitted (its tokens are all past
                # retirement) — commit it so its bookkeeping (StepClock
                # record, discarded tokens) never dangles across idle
                fo = getattr(b, "flush_overlap", None)
                if fo is not None:
                    fo()
                if self._stop_evt.is_set():
                    self._shutdown_drain_queue()
                    return
                # SAFE BOUNDARY: nothing in flight, nothing queued — the
                # only place the worker may drop compiled executables.
                # Bounds the week-long daemon against the compile-cache
                # growth pathology that segfaults XLA's CPU compiler in
                # the test suite (utils/xla_cache.py has the story);
                # cleared programs recompile transparently on next use.
                # A guard failure must never kill the worker (callers
                # would hang to request_timeout) — serving correctness
                # does not depend on the clear happening.
                if sc is not None:
                    sc.loop_part("wait")
                try:
                    self.cache_guard.maybe_clear()
                except Exception:  # noqa: BLE001
                    log.exception("compile-cache guard failed; continuing")
                try:
                    arrived = self.q.get(timeout=0.1)
                except queue.Empty:
                    continue
            if sc is not None:
                sc.loop_part("admit")
            if arrived is not None:
                self._admit(arrived)
            while not self._draining and b.free_slots():
                if self._held is not None:
                    # retry the held-back request before new work; still
                    # short on blocks -> keep holding, stop admitting
                    item, self._held = self._held, None
                    if not self._admit(item):
                        break
                    continue
                try:
                    if not self._admit(self.q.get_nowait()):
                        break
                except queue.Empty:
                    break
            had_active = bool(b.n_active)
            if sc is not None:
                sc.loop_part("step")
            try:
                stepped = self._step_pool(b) if had_active else {}
            except Exception as e:  # noqa: BLE001 — one device-side error
                # must not leave callers hanging for request_timeout:
                # either hand the surviving work to the owner's
                # requeue-or-fail hook (LMServer._on_worker_death spawns
                # a successor worker), or fail every pending future fast
                # and die visibly (HealthCheck reports not-alive;
                # SendTensor aborts UNAVAILABLE)
                handler = self.on_death
                obs.flight.record("worker_died", error=str(e)[:500],
                                  pending=len(self._futures),
                                  requeue=handler is not None)
                if handler is not None:
                    log.exception("batcher worker died; handing %d "
                                  "in-flight + queued requests to the "
                                  "requeue hook", len(self._futures))
                    inflight, queued = self._collect_for_requeue()
                    try:
                        handler(e, inflight, queued)
                        return
                    except Exception:  # noqa: BLE001 — a broken hook
                        # must not strand the collected futures
                        log.exception("worker-death requeue hook failed;"
                                      " failing survivors")
                        exc = RuntimeError(
                            f"LM batcher worker died: {e}")
                        for _rid, it in inflight:
                            _fail_future(it.fut, exc)
                        for it in queued:
                            _fail_future(it.fut, exc)
                        return
                log.exception("batcher worker died; failing %d pending "
                              "requests", len(self._futures))
                self._fail_all(RuntimeError(f"LM batcher worker died: {e}"))
                return
            if sc is not None:
                sc.loop_part("emit")
            if had_active and (sd := self.step_done) is not None:
                sd()  # a real step completed: the watchdog is warmed
            batch = {}  # the step's tokens for the sinks, commit order
            for rid, tok in stepped.items():  # streaming: tokens as they
                # commit, before done-publish; the speculative batcher
                # (and an interleaved deferred-first commit) deliver a
                # LIST of tokens per step
                rec = self._futures.get(rid)
                if rec is None:
                    continue
                if "ttft_t0" in rec:
                    # interleaved admission: this is the request's FIRST
                    # committed token — record the real TTFT now
                    t0 = rec.pop("ttft_t0")
                    m = obs.metrics()
                    if m is not None:
                        ttft = time.perf_counter() - t0
                        m.observe("serving.ttft_seconds", ttft)
                        if (g := self.goodput) is not None:
                            g.on_ttft(ttft)
                if isinstance(tok, (list, tuple)):
                    for t in tok:
                        self._emit_token(rec, rid, t, batch)
                else:
                    self._emit_token(rec, rid, tok, batch)
            # before done-publish: call_soon_threadsafe is FIFO, so each
            # stream's "done" trails its last token
            self._hand_off(batch)
            self._publish_done()  # submit alone can retire (budget == 1)


class LMServer:
    """NodeService servicer mapping SendTensor(prompt) -> generated tokens.

    Build with the same (cfg, prepared) pair the batcher takes; batcher
    kwargs pass through (slots, max_len, prompt_pad, temperature, top_k,
    top_p, compute_dtype, eos_id, seed, ffn, kv_dtype, family — `ffn` is
    how the MoE family serves,
    dnn_tpu/runtime/generate_moe.moe_cache_ffn). Two of them shape the
    daemon's decode-bandwidth story (both length-aware, both default-on
    or opt-in as noted): `attn_kernel` defaults to "auto" — long-context
    cache attention streams through the position-clamped Pallas kernel
    on TPU, the einsum elsewhere (runtime/kvcache.AUTO_KERNEL_MIN_S) —
    and `decode_buckets=True` grows the dense pool bucket-by-bucket so
    decode bytes/step track the pool's LIVE context instead of max_len
    (runtime/decode_buckets.py; dense pools only — paged pools are
    already length-proportional).

    Observability (dnn_tpu/obs): every request gets a span tree (queue
    wait, admit, prefill, per-bucket decode; trace id continued from a
    client's `tr=` request_id tag), the pool exports TTFT / inter-token
    / occupancy / queue-depth / memory-watermark metrics, a
    jax.monitoring listener counts XLA compiles, and serving events
    (admissions, deadline misses, worker death) feed the flight
    recorder — dumped automatically on unhandled crash. `metrics_port`
    (None = no endpoint; 0 = ephemeral) serves it all over stdlib HTTP:
    GET /metrics (Prometheus text), /trace (Chrome-trace JSON, ?id= for
    one request), /debugz (flight ring), /statusz (watchdog detail),
    /healthz, POST /profilez (on-demand jax.profiler capture, ?auto=1
    arms capture-the-next-slow-step). `watchdog` (None/False = off;
    True or a period in seconds, or a prebuilt obs.watchdog.Watchdog)
    runs the hung-device watchdog: deadline-bounded in-process device
    probes plus this worker's loop heartbeat decide ok|degraded|wedged."""

    def __init__(self, cfg, prepared, *, default_max_new: int = 32,
                 request_timeout: float = 120.0, tokenizer=None,
                 draft_cfg=None, draft_prepared=None, spec_k: int = 4,
                 compile_cache_budget: int = 512,
                 metrics_port: Optional[int] = None,
                 watchdog=None,
                 goodput=None, slo=None,
                 on_wedged: str = "503",
                 worker_restarts: int = 2,
                 max_request_retries: int = 1,
                 drain_grace_s: float = 30.0,
                 weights: str = "f32",
                 role: str = "both",
                 kv_handoff_cap: int = 64,
                 kv_handoff_ttl_s: float = 120.0,
                 kv_lease_ttl_s: float = 30.0,
                 **batcher_kwargs):
        # weight-only quantized serving (ISSUE 12 satellite — the first
        # rung of ROADMAP item 4's weight-quant ladder): weights="int8"
        # quantizes the served tree ONCE at construction (quant.py's
        # symmetric per-output-channel scheme; every matmul funnels
        # through ops.nn.linear, which dispatches on the q dtype), so
        # decode streams ~4x fewer weight bytes per step. The goodput
        # MBU denominator prices the quantized tree exactly
        # (utils/flops.tree_weight_bytes) because model_cost below sums
        # the REAL leaves of the tree the batcher actually serves.
        if weights not in ("f32", "int8"):
            raise ValueError(
                f"weights must be 'f32' or 'int8', got {weights!r}")
        if weights == "int8":
            if batcher_kwargs.get("lora_adapters"):
                raise ValueError(
                    "weights='int8' does not compose with LoRA serving: "
                    "lora_view applies low-rank deltas to float kernels, "
                    "not quantized {q, scale} pairs")
            from dnn_tpu.ops.nn import hold_in_compute_dtype
            from dnn_tpu.quant import quantize_gpt

            # quantized from the float32 values; a kernel the quantizer
            # leaves float is then held as its reader wants it
            prepared = hold_in_compute_dtype(
                quantize_gpt(prepared, bits=8),
                batcher_kwargs.get("compute_dtype"))
        self.weights = weights
        # what the served tree holds, per dtype (the boot log line and
        # /statusz's `weights` component): matmul operands in the compute
        # dtype, routers / norms / biases / embeddings float32, int8
        # kernels and their scales when quantized
        from dnn_tpu.utils.flops import tree_weight_bytes_by_dtype

        self._weight_bytes = {k: int(v) for k, v in sorted(
            tree_weight_bytes_by_dtype(prepared).items())}
        log.info("weights held: %s", ", ".join(
            f"{k} {v / 1e9:.3f} GB" for k, v in self._weight_bytes.items()))
        # resilience state (ISSUE 8) before anything that can serve a
        # request or a scrape: drain flag, wedged-policy escalation
        # latch, admission dedup, worker-restart bookkeeping
        if on_wedged not in ("503", "restart", "drain"):
            raise ValueError(
                f"on_wedged must be 503|restart|drain, got {on_wedged!r}")
        # fleet role (dnn_tpu/control, disaggregated prefill/decode):
        # ADVISORY — the router routes prefill exports to `prefill`
        # replicas and generation to `decode`/`both`; the server itself
        # serves every endpoint whatever its role (a mis-routed request
        # still answers correctly, just on the wrong replica's FLOPs).
        # Advertised on /statusz (the FleetCollector's per-replica role
        # column) and as the dnn_tpu_replica_role gauge.
        if role not in ("prefill", "decode", "both"):
            raise ValueError(
                f"role must be prefill|decode|both, got {role!r}")
        self.role = role
        # prefill->decode KV handoff inbox (kvput:<key> ingests, the
        # h=<key> gen option consumes exactly once): bounded LRU — an
        # orphaned handoff (router died between kvput and gen) must not
        # hold row-cache-sized payloads forever. Entries are ALSO
        # time-bounded: staged handoffs carry an ingest timestamp and
        # the worker's housekeeping tick sweeps entries older than
        # `kv_handoff_ttl_s` with a `kvput_expired` flight event — a
        # cap alone let one abandoned prefill pin a row-sized payload
        # until 63 siblings arrived to push it out (ttl <= 0 disables)
        self._kv_handoff: "dict" = {}
        self._kv_lock = threading.Lock()
        self._kv_handoff_cap = int(kv_handoff_cap)
        self._kv_handoff_ttl_s = float(kv_handoff_ttl_s)
        self._kv_lease_ttl_s = float(kv_lease_ttl_s)
        self._kvtier_leases = None  # built after the batcher (kvtier
        # endpoints exist only when the radix store is on)
        self._hk_last = 0.0
        self.on_wedged = on_wedged
        self.worker_restarts = int(worker_restarts)
        self.max_request_retries = int(max_request_retries)
        self.drain_grace_s = float(drain_grace_s)
        self._draining = False
        self._drain_thread = None
        self._drain_lock = threading.Lock()
        self._escalated = threading.Event()
        self._escalate_reason: Optional[str] = None
        self._restart_lock = threading.Lock()
        self._restart_times: list = []
        self._restart_window_s = 300.0
        self._dedup_lock = threading.Lock()
        self._dedup: "dict" = {}   # key -> worker future (insertion-ordered)
        self._DEDUP_CAP = 512
        # observability first: the compile listener must be live before
        # the batcher's first program compiles, so jax_compilations_total
        # counts the daemon's own warmup too (dnn_tpu/obs)
        obs.install_compile_telemetry()
        if (m := obs.metrics()) is not None:
            from dnn_tpu.utils.metrics import labeled

            m.set(labeled("dnn_tpu_replica_role", role=self.role), 1.0)
        if obs.enabled():
            # black box: an unhandled crash anywhere in this process
            # dumps the flight ring (obs/flight.py) — the daemon is the
            # thing whose post-mortems matter
            obs.flight.install_crash_dump()
            from dnn_tpu.obs.mem import install_memory_gauges

            install_memory_gauges()
        # how long a committed token waits for the event-loop thread:
        # [sum, count, max] seconds from the worker's hand-off to the
        # stream handler's dequeue, plain numbers only the loop thread
        # writes, read by scrape-time callables (no observe a token)
        self._emit_lag = [0.0, 0, 0.0]
        # [hand-offs, tokens they carried] of the streams' tokens, which
        # only the worker thread writes (_BatcherWorker._hand_off); their
        # ratio is the tokens a wake-up of the event loop delivers
        self._emit_counts = [0, 0]
        # the event-loop thread's wall time by part (obs/timeline.py),
        # which that thread writes: its selector's two stamps once
        # note_rpc_loop_thread() has met a stamped loop, `_fan_out` and
        # GenerateStream's token section on any loop
        self._rpc = RpcLoopClock()
        self._rpc_cpu_clock_id = None  # note_rpc_loop_thread()
        self._thread_cpu_last = {}
        self._install_host_gauges()
        self.metrics_server = None
        self._watchdog = None
        # step-timeline attribution (obs/timeline.py): the daemon's
        # decode steps feed a StepClock — /stepz serves the per-phase
        # decomposition, /statusz gains a `step` component, and the
        # profiler's sidecar meta records this clock's step-counter
        # range so a capture aligns to the step axis. Auto-built like
        # the goodput tracker; off with the obs gate.
        self.step_clock = None
        self._device_static = {}  # _device_facts; read below, once
        if obs.enabled():
            from dnn_tpu.obs.timeline import StepClock

            self.step_clock = StepClock().install()
        if metrics_port is not None:
            from dnn_tpu.obs.profile import Profiler

            # /metrics /trace /debugz /statusz /stepz /profilez
            # endpoint; /healthz mirrors HealthCheck, then degrades
            # through the watchdog's ok|degraded|wedged when attached
            self.metrics_server = obs.serve_metrics(
                metrics_port,
                healthy=lambda: (w := getattr(self, "worker", None))
                is not None and w.is_alive() and not self._draining,
                status=self._statusz,
                profiler=Profiler(arm_target=self),
                drain=self._drainz,
                stepclock=self.step_clock)
        try:
            self._init_rest(
                cfg, prepared, default_max_new=default_max_new,
                request_timeout=request_timeout, tokenizer=tokenizer,
                draft_cfg=draft_cfg, draft_prepared=draft_prepared,
                spec_k=spec_k, compile_cache_budget=compile_cache_budget,
                **batcher_kwargs)
            if getattr(self.batcher, "_prefix_store", None) is not None:
                # fleet KV tier live on this replica: donor-side lease
                # staging (kvlease/kvfetch/kvack — kvtier/migrate.py)
                from dnn_tpu.kvtier.migrate import LeaseTable

                self._kvtier_leases = LeaseTable(ttl_s=kv_lease_ttl_s)
            if self.metrics_server is not None:
                # /kvz comes alive once the batcher (and its lens)
                # exists — the endpoint was bound before the batcher,
                # so the lens is attached late (http.py reads it per
                # request). None when the obs gate or the KV tier is
                # off: /kvz then 404s honestly.
                self.metrics_server._kvlens = getattr(
                    self.batcher, "_kvlens", None)
            # housekeeping rides the worker loop (lease TTL + kvput
            # inbox TTL), rate-limited inside the tick
            self.worker.tick = self._housekeeping_tick
            # what /statusz says of the device: read here, on the
            # constructing thread, now that the engine holds the backend
            # (never from the HTTP thread)
            import jax

            devs = jax.devices()
            self._device_static = {
                "device_kind": devs[0].device_kind,
                "device_count": len(devs), "platform": devs[0].platform}
        except BaseException:
            # a failed construction (bad batcher kwargs) must release the
            # already-bound endpoint, or a retry hits EADDRINUSE forever
            if self.metrics_server is not None:
                self.metrics_server.close()
                self.metrics_server = None
            raise
        if watchdog:
            # hung-device watchdog (obs/watchdog.py): `watchdog` is True
            # (defaults), a float (period seconds), or a prebuilt
            # Watchdog (tests inject stubbed probes). Wired to the
            # worker's loop heartbeat + thread liveness, started here —
            # after _init_rest, so the worker exists to monitor.
            from dnn_tpu.obs.watchdog import Watchdog, in_process_device_probe

            if isinstance(watchdog, Watchdog):
                self._watchdog = watchdog
            else:

                period = 30.0 if watchdog is True else float(watchdog)
                self._watchdog = Watchdog(
                    period_s=period,
                    probe_deadline_s=min(10.0, max(6.0, period / 3)),
                    # this process HOLDS the device it serves on, and a
                    # chip belongs to one process: a probe child could
                    # not open it and would read a healthy server as
                    # degraded or wedged. Probe in-process; the Watchdog
                    # bounds the call by a thread joined at the deadline
                    device_probe=in_process_device_probe)
            if self._watchdog.alive_check is None:
                # a LAMBDA over self.worker, not a bound method: the
                # worker-death requeue path swaps in a successor worker,
                # and a stale bound is_alive would read the corpse
                self._watchdog.alive_check = \
                    lambda: self.worker.is_alive()
            self.worker.heartbeat = self._watchdog.beat
            self.worker.step_done = self._watchdog.step_done
            if self.on_wedged != "503":
                # wedged is a POLICY now, not just a 503: the watchdog's
                # once-per-episode escalation hook fires the restart /
                # drain path (warm-up grace preserved — the watchdog
                # never reports wedged before the first completed step)
                self._watchdog.on_wedged = self._wedged_escalate
            if not self._watchdog._thread.is_alive():
                self._watchdog.start()
        # live goodput accounting (obs/goodput.py): dnn_tpu_mfu /
        # dnn_tpu_mbu / dnn_tpu_goodput_tokens_per_sec scrape-time
        # gauges + optional SLO burn rates. `goodput` is None (auto:
        # build from the model config when obs is enabled), False (off),
        # or a prebuilt GoodputTracker. `slo` is an obs.goodput.
        # SLOConfig (implies auto-build when goodput is None).
        self.goodput = None
        if goodput is None and obs.enabled():
            from dnn_tpu.obs.goodput import GoodputTracker, model_cost

            # same fallback chain as the batcher's cache allocation
            # (serving.py: kv_dtype, else the family's resolved
            # compute_dtype, else f32) — a bf16 server must not have its
            # MBU KV term priced at f32 width, and the QUANTIZED specs
            # ("int8"/"int4") price their packed payload + f32 scale
            # rows exactly (utils/flops.kv_bytes_per_pos kv_dtype=;
            # int4 at jnp.dtype itemsize would overstate 2x and miss
            # the scales)
            import jax.numpy as jnp

            kv_spec = (batcher_kwargs.get("kv_dtype")
                       or getattr(self.batcher.family, "compute_dtype",
                                  None)
                       or jnp.float32)
            try:
                cost = model_cost(cfg, prepared, kv_dtype=kv_spec)
            except Exception:  # noqa: BLE001 — exotic kv_dtype spec
                cost = model_cost(cfg, prepared, kv_bytes=2)
            self.goodput = GoodputTracker(cost, slo=slo).install()
        elif goodput:
            self.goodput = goodput.install()
        if self.goodput is not None:
            self.batcher.goodput = self.goodput
            self.worker.goodput = self.goodput
        if self.step_clock is not None:
            self.batcher.step_clock = self.step_clock

    def note_rpc_loop_thread(self):
        """Called once ON the thread that runs the gRPC aio server's
        event loop (serve_lm, start_lm_server_in_background): its CPU
        clock is process.thread_cpu_seconds_total{thread="rpc_loop"}, and
        its selector, where the loop has a stamped one
        (`timeline.rpc_event_loop`), stamps this server's RpcLoopClock
        from here on."""
        self._rpc_cpu_clock_id = time.pthread_getcpuclockid(
            threading.get_ident())
        selector = getattr(asyncio.get_running_loop(), "stamped_selector",
                           None)
        if selector is not None:
            selector.clock = self._rpc

    def _thread_cpu(self, thread: str) -> float:
        """CPU seconds of the batcher worker or of the event-loop thread,
        read at scrape time from the thread's own CPU clock; the last
        reading once the thread has ended (a successor worker starts
        from zero: a counter reset), 0.0 before it has started."""
        cid = self._rpc_cpu_clock_id if thread == "rpc_loop" else getattr(
            getattr(self, "worker", None), "cpu_clock_id", None)
        if cid is not None:
            try:
                self._thread_cpu_last[thread] = time.clock_gettime(cid)
            except OSError:
                pass
        return self._thread_cpu_last.get(thread, 0.0)

    def _install_host_gauges(self):
        """Scrape-time callables for who burns the host's CPU and how
        long a token waits for the event loop; nothing on a hot path."""
        m = obs.metrics()
        if m is None:
            return
        from dnn_tpu.utils.metrics import labeled

        lag, handed, rpc = self._emit_lag, self._emit_counts, self._rpc
        ref = weakref.ref(self)  # the registry outlives a server

        def thread_cpu(thread):
            def read():
                srv = ref()
                return srv._thread_cpu(thread) if srv is not None else 0.0
            return read

        for name, fn in {
            **{labeled("process.thread_cpu_seconds_total", thread=t):
               thread_cpu(t) for t in ("worker", "rpc_loop")},
            "process.cpu_seconds_total": time.process_time,
            # this process's perf_counter at the scrape: the clock of
            # the step series and of a capture's meta.json, so a reader
            # knows a window's seconds and where a capture lies in it
            "process.perf_counter_seconds": time.perf_counter,
            "serving.emit_lag_seconds_sum": lambda: lag[0],
            "serving.emit_lag_seconds_count": lambda: float(lag[1]),
            "serving.emit_lag_seconds_max": lambda: lag[2],
            "serving.emit_handoffs_total": lambda: float(handed[0]),
            "serving.emit_tokens_total": lambda: float(handed[1]),
            # the event-loop thread's time (RpcLoopClock)
            labeled("serving.rpc_loop_seconds_total", part="select"):
                rpc.select_seconds,
            **{labeled("serving.rpc_loop_seconds_total", part=p):
               (lambda p=p: rpc.seconds[p]) for p in RPC_PARTS[1:]},
            "serving.rpc_loop_iterations_total":
                lambda: float(rpc.iterations),
            "serving.fan_out_lag_seconds_sum": lambda: rpc.fan_out_lag[0],
            "serving.fan_out_lag_seconds_count":
                lambda: float(rpc.fan_out_lag[1]),
        }.items():
            m.set_fn(name, fn)

    @property
    def auto_profile(self):
        """POST /profilez?auto=1 arm state — delegates to the batcher
        worker (the thread that times and captures steps)."""
        return self.worker.auto_profile

    @auto_profile.setter
    def auto_profile(self, value):
        self.worker.auto_profile = value

    def _device_facts(self) -> dict:
        """`device_kind`, `device_count`, `platform` (read once, in
        __init__) and the boot gauges node.py published
        (`dnn_tpu_boot_*_seconds`, as `boot_*_s`), for /statusz's
        `device` component."""
        out = dict(self._device_static)
        m = obs.metrics()
        if m is not None:
            prefix, suffix = "dnn_tpu_boot_", "_seconds"
            out.update({"boot_" + k[len(prefix):-len(suffix)] + "_s": v
                        for k, v in list(m.gauges.items())
                        if k.startswith(prefix) and k.endswith(suffix)})
        return out

    def _statusz(self):
        """The /statusz payload: watchdog state when one runs, else None
        — the HTTP handler then falls back to its worker-liveness shape
        (one fallback, not two drifting copies; obs/http.py). A DRAINING
        server overlays the `draining` state (unless already wedged) so
        routers/fleet collectors stop sending it work while in-flight
        decodes finish. Once the pool has stepped, a `step` component
        overlays the step clock's summary (last step duration, host
        fraction, steps/sec) so an operator can tell slow-but-healthy
        from wedged without pulling a profile — it reads the SAME
        worker loop the watchdog's decode heartbeat beats from, so
        their recency agrees; the component is informational (state
        "ok"), escalation stays the watchdog's."""
        s = self._watchdog.status() if self._watchdog is not None \
            else None
        sc = self.step_clock
        if sc is not None and sc.steps_total:
            if s is None:
                # no watchdog: synthesize the handler's worker-liveness
                # shape here so the step component still has a home
                alive = (w := getattr(self, "worker", None)) is not None \
                    and w.is_alive()
                s = {"state": "ok" if alive else "wedged",
                     "components": {"worker": {
                         "state": "ok" if alive else "wedged",
                         "detail": "serving worker thread liveness"}}}
            else:
                s = dict(s)
            comps = dict(s.get("components") or {})
            comps["step"] = sc.status_component()
            s["components"] = comps
        if s is None:
            # no watchdog and no step record yet: synthesize the
            # handler's worker-liveness shape so the payload still
            # carries the fleet-facing fields below (role — the
            # FleetCollector's per-replica role column reads /statusz)
            alive = (w := getattr(self, "worker", None)) is not None \
                and w.is_alive()
            s = {"state": "ok" if alive else "wedged",
                 "components": {"worker": {
                     "state": "ok" if alive else "wedged",
                     "detail": "serving worker thread liveness"}}}
        else:
            s = dict(s)
        s["role"] = self.role
        comps = dict(s.get("components") or {})
        # what the chip is and what boot cost, beside the watchdog's
        # probe verdict: a client learns the device without taking it.
        # With no probe the component carries the facts and NO `state`:
        # a device nobody probed is not reported as ok
        comps["device"] = {
            **(comps.get("device")
               or {"detail": "facts only: no device probe runs"}),
            **self._device_facts()}
        # facts, no `state`: the bytes the served tree holds per dtype
        comps["weights"] = {
            "detail": "bytes of the served tree by dtype",
            "bytes": sum(self._weight_bytes.values()),
            "bytes_by_dtype": self._weight_bytes}
        # how the expert layers of the programs built so far meet their
        # matrices ("stack_kernel": read out of the held stack in place;
        # "ragged_dot": a copy a layer on the TPU) — said while they were
        # traced (parallel/moe._experts_grouped), so a daemon that fell
        # back shows it without a capture
        family = getattr(self.batcher, "family", None)
        forms = getattr(getattr(family, "ffn", None), "expert_forms", None)
        if forms:
            comps["weights"]["moe_experts"] = "+".join(sorted(forms))
        # blocks of ONE mixer (models/llama.py `one_mixer`): how many of
        # each kind, and what the experts are handed
        cfg = getattr(self.batcher, "cfg", None)
        if getattr(cfg, "one_mixer", False):
            comps["blocks"] = {
                "detail": "blocks of one mixer, by kind; the experts' "
                          "latent width, picks a row and share held",
                "kinds": {k: cfg.layer_types.count(k)
                          for k in dict.fromkeys(cfg.layer_types)},
                "moe": {"latent_size": cfg.moe_latent,
                        "picks": cfg.router_top_k,
                        "held": cfg.experts_held or cfg.n_expert,
                        "of": cfg.n_expert}}
        # what a grid step of the latent prefill kernel covers in the
        # chunk programs built so far, by layer kind and by the columns a
        # call was handed (models/mla.py `prefix_lengths`): said while
        # they were traced, as `moe_experts` is — a `block_s` under the
        # full tile, or None (the plain form), shows without a capture
        steps = getattr(family, "prefill_steps", None)
        if steps and any(steps.values()):
            comps["attention"] = {
                "detail": "what a grid step of the built prefill programs' "
                          "latent attention kernel covers",
                "mla_prefill": {
                    # a copy: a chunk program traced now adds an entry
                    kind: [{"columns": n, **step}
                           for n, step in sorted(dict(by_columns).items())]
                    for kind, by_columns in steps.items()}}
        # the forms the reads of K and V leaves by layer kind took in the
        # programs built so far (models/llama.py `LlamaKindRows`): the
        # paged kernel or a gather and einsums for a decode read, the
        # (banded) kernel or the plain form for a chunk's — beside a K/V
        # kind's window and the table it rotates q and k by (`KvKind`)
        kinds = getattr(family, "attn_forms", None)
        if kinds and any(kinds.values()):
            tables = family.kind_tables()
            comps["attention"] = {
                "detail": "the form each layer kind's reads took in the "
                          "built programs, a K/V kind's window and rotation",
                "kinds": {kind: {**f, **tables.get(kind, {})}
                          for kind, f in kinds.items()}}
        # facts, no `state`: the KV cache's bytes leaf by leaf (K, V, an
        # int8 pool's scales, a selecting model's index keys "ik"; a
        # layer kind's leaves under their own names), from shapes alone
        kv_leaves = getattr(self.batcher, "cache", None)
        if isinstance(kv_leaves, dict):
            from dnn_tpu.obs.mem import logical_nbytes

            by_leaf = {k: int(logical_nbytes(v))
                       for k, v in kv_leaves.items()
                       if not k.startswith("tables")}
            comps["kv_cache"] = {
                "detail": "bytes of the KV cache by leaf",
                "bytes": sum(by_leaf.values()), "bytes_by_leaf": by_leaf}
            # a paged pool's block, and the positions a group of the
            # paged decode kernel covers in the built decode program, by
            # the first leaf it reads — none where no program calls it
            kinds = getattr(family, "cache_kinds", None)
            if kinds:
                # which of the leaves a kind pages under its tables and
                # which a slot holds whole (no position axis)
                comps["kv_cache"]["kinds"] = {
                    kind: {"leaves": sorted(k["leaves"]),
                           # rows that are strides, not positions: name ->
                           # the positions a row
                           **({"strided_leaves": {
                               n: v[2] for n, v in
                               k["strided_leaves"].items()}}
                              if k.get("strided_leaves") else {}),
                           "slot_leaves": sorted(k.get("slot_leaves", ())),
                           "tables": k["tables"]}
                    for kind, k in kinds.items()}
            codec = getattr(self.batcher, "_paged_codec", None)
            if codec is not None:
                comps["kv_cache"]["block_len"] = int(codec.block_len)
                comps["kv_cache"]["decode_group_span"] = dict(
                    codec.kernel_spans)
        # facts, no `state`: which loop the worker's step() runs, why,
        # and how often the pipeline engaged (`step_pipelined_total`,
        # `step_stale_rows_total` on /metrics)
        loop = getattr(self.batcher, "step_loop", None)
        if loop is not None:
            comps["batcher"] = {
                "detail": "the serving worker's step loop", **loop()}
        s["components"] = comps
        if self._kvtier_on():
            # KV-tier residency rides /statusz (informational): the
            # FleetCollector's per-replica rows read it next to role
            st = self.batcher._prefix_store
            comps = dict(s.get("components") or {})
            comps["kvtier"] = {
                "state": "ok",
                "detail": (f"resident_blocks={st.n_blocks} "
                           f"block_hits={st.block_hits} "
                           f"remote_hits={st.remote_block_hits} "
                           f"leases={self._kvtier_leases.n_leases}"),
                "kvtier_blocks": st.n_blocks,
            }
            s["components"] = comps
        if not self._draining:
            return s
        comps = dict(s.get("components") or {})
        comps["drain"] = {"state": "draining",
                          "detail": "admission closed; finishing "
                                    "in-flight decodes"}
        s["components"] = comps
        if s.get("state") != "wedged":
            s["state"] = "draining"
        return s

    # -- resilience: drain / requeue / wedged policy (ISSUE 8) ----------

    def _wedged_escalate(self, detail: str):
        """Watchdog wedged-episode hook (once per episode; obs/
        watchdog.py): turn the passive 503 into the configured policy.
        `restart` exits fast so the process supervisor relaunches from
        the latest checkpoint; `drain` finishes in-flight work first
        (on a wedged DEVICE that usually can't finish — the drain grace
        bounds the wait)."""
        obs.flight.record("wedged_policy", policy=self.on_wedged,
                          detail=str(detail)[:300])
        if self.on_wedged == "drain":
            self._drainz()
            # the drain thread sets _escalated when done (or grace out)
        else:
            self._escalate(f"wedged: {detail}")

    def _escalate(self, reason: str):
        self._escalate_reason = reason
        self._escalated.set()

    def drain(self, grace_s: Optional[float] = None) -> dict:
        """Connection draining, blocking: stop admission (preflight
        rejects with UNAVAILABLE "draining" — retriable by the existing
        client ladder), let in-flight decodes finish, hand queued work
        back, then the worker exits. Returns a status dict; bounded by
        `grace_s` (default drain_grace_s) — in-flight work still
        running at the deadline is abandoned (futures cancel) so a
        wedged decode cannot hold the drain open forever."""
        grace = self.drain_grace_s if grace_s is None else float(grace_s)
        self._draining = True
        self.worker.begin_drain()
        self.worker.join(timeout=grace)
        clean = not self.worker.is_alive()
        if not clean:
            # grace expired with decodes still in flight: abandon them
            # (the supervisor is about to restart us anyway)
            self.worker.stop(drain=False)
            self.worker.join(timeout=5)
        obs.flight.record("drain_exit", clean=clean,
                          grace_s=round(grace, 3))
        return {"drained": True, "clean": clean}

    def _drainz(self) -> dict:
        """POST /drainz handler (and the wedged drain policy's entry):
        kick a background drain once; report current drain state.
        Idempotent — repeated POSTs watch the same drain."""
        with self._drain_lock:
            if self._drain_thread is None:
                def _run():
                    self.drain()
                    self._escalate("drained")

                obs.flight.record("drainz", source="http_or_policy")
                self._drain_thread = threading.Thread(
                    target=_run, daemon=True, name="lm-drain")
                self._draining = True  # reject admissions immediately
                self._drain_thread.start()
        return {"draining": True,
                "active": self.batcher.n_active,
                "queued": self.worker.q.qsize(),
                "worker_alive": self.worker.is_alive()}

    def _on_worker_death(self, exc, inflight, queued):
        """The batcher worker died mid-step (device fault, injected or
        real). Instead of failing every in-flight request permanently
        (the pre-ISSUE-8 behavior), spawn a successor worker and
        REQUEUE the idempotent survivors: unary requests with retry
        budget left (`attempts` < max_request_retries) and deadline
        remaining. A streamed request that was still queued (no token
        delivered) is requeued too and streams from the successor: its
        item carries its sink. Streams already admitted (tokens
        delivered) and budget-exhausted requests fail fast. Restarts are
        bounded —
        `worker_restarts` within a 5-minute window — so a hard-broken
        device degrades to the old fail-fast shape instead of a
        requeue loop."""
        now = time.perf_counter()
        with self._restart_lock:
            self._restart_times = [
                t for t in self._restart_times
                if now - t <= self._restart_window_s]
            can_restart = (len(self._restart_times) < self.worker_restarts
                           and not self._draining)
            if can_restart:
                self._restart_times.append(now)
        items = [(rid, it) for rid, it in inflight] \
            + [(None, it) for it in queued]
        fail_exc = RuntimeError(f"LM batcher worker died: {exc}")
        if not can_restart:
            obs.flight.record("worker_restart_exhausted",
                              window_s=self._restart_window_s,
                              budget=self.worker_restarts,
                              failed=len(items))
            for _rid, it in items:
                _fail_future(it.fut, fail_exc)
            if (g := self.goodput) is not None:
                for _ in items:
                    g.on_outcome(False)
            return
        # retire the dead requests' slots host-side: prefill overwrites
        # device state, so the successor serves from a clean pool
        for rid, _it in inflight:
            try:
                if self.batcher.cancel(rid):
                    self.batcher.claim(rid)
            except Exception:  # noqa: BLE001 — slot already retired
                pass
        new_worker = self._spawn_worker()
        if self.goodput is not None:
            new_worker.goodput = self.goodput
        old = self.worker
        new_worker.heartbeat = old.heartbeat
        new_worker.step_done = old.step_done
        self.worker = new_worker
        new_worker.start()
        requeued = failed = 0
        for rid, it in items:
            ok = ((it.on_token is None or rid is None)
                  and (it.cancel_evt is None or not it.cancel_evt.is_set())
                  and it.attempts < self.max_request_retries
                  and now - it.t_q < self.request_timeout)
            if ok:
                ok = new_worker._resubmit(
                    it._replace(attempts=it.attempts + 1))
            if ok:
                requeued += 1
            else:
                failed += 1
                _fail_future(it.fut, fail_exc)
                if (g := self.goodput) is not None:
                    g.on_outcome(False)
        obs.flight.record("worker_restart",
                          restarts=len(self._restart_times),
                          requeued=requeued, failed=failed,
                          error=str(exc)[:300])
        log.warning("batcher worker restarted after death (%s): "
                    "%d requests requeued, %d failed", exc, requeued,
                    failed)

    def _init_rest(self, cfg, prepared, *, default_max_new,
                   request_timeout, tokenizer, draft_cfg, draft_prepared,
                   spec_k, compile_cache_budget, **batcher_kwargs):
        # the daemon's DEFAULT cache layout is the paged pool ("auto"
        # resolves to paged whenever this configuration can page, with a
        # visible dense fallback — serving.ContinuousBatcher kv=): the
        # serving path admits by ACTUAL request length instead of
        # slots x max_len. Callers opt out with kv="dense" (the
        # --kv=dense CLI fallback) or pin kv="paged" to fail loud when
        # paging is impossible.
        batcher_kwargs.setdefault("kv", "auto")
        if (batcher_kwargs.get("allow_constraints")
                and "constraint_rows" not in batcher_kwargs):
            # the daemon's JSON mode goes up to depth _MAX_JSON_DEPTH=3,
            # whose byte DFA has 3519 states — the batcher's device pools
            # must hold it (serving.ContinuousBatcher constraint_rows; the
            # bit-packed mask pool is rows x vocab / 8 bytes or so, ~24 MB
            # at GPT-2 vocab, and a step reads `slots` rows of it; the
            # int32 transition pool is rows x vocab x 4, ~724 MB).
            # Operators who never serve deep JSON can pass a smaller
            # constraint_rows explicitly.
            batcher_kwargs["constraint_rows"] = 3600
        if draft_cfg is not None:
            # speculative serving: the slot pool advances up to spec_k+1
            # tokens per device step (runtime/serving_spec.py)
            from dnn_tpu.runtime.serving_spec import SpeculativeBatcher

            cls, models = SpeculativeBatcher, (
                cfg, prepared, draft_cfg, draft_prepared)
            batcher_kwargs["spec_k"] = spec_k
        else:
            cls, models = ContinuousBatcher, (cfg, prepared)
            # the worker's step loop over a dense batcher is the one-step
            # dispatch pipeline (step N+1 launched before step N is read),
            # for every family and cache; a speculative batcher keeps its
            # own default. A caller's explicit `overlap=` wins either way
            batcher_kwargs.setdefault("overlap", True)
        self.batcher = cls(*models, **batcher_kwargs)
        self.default_max_new = default_max_new
        self.request_timeout = request_timeout
        # optional text front (dnn_tpu/io/tokenizer.py): with it,
        # SendMessage serves prompt text -> generated text
        self.tokenizer = tokenizer
        # JSON-mode constraints are per-(depth) compile-once artifacts —
        # the token table is vocab-sized work shared by every request
        self._constraint_cache: dict = {}
        # embedding endpoint: one make_embed per pooling (jit caches per
        # padded-length shape underneath)
        self._embed_fns: dict = {}
        # embed calls run device work OUTSIDE the worker thread
        # (asyncio.to_thread) — the cache guard must not clear while one
        # is in flight, and must never iterate _embed_fns mid-insert.
        # The inflight transitions happen under guard.lock (the guard's
        # check+clear is atomic under it), closing the race where an
        # embed enters its program between the check and the clear.
        self._embed_inflight = 0
        self._compile_cache_budget = compile_cache_budget
        self.worker = self._spawn_worker()
        self.worker.start()

    def _spawn_worker(self) -> _BatcherWorker:
        """Build a batcher worker wired to this server — used at
        construction AND by the worker-death restart path, so a
        successor worker can never drift behind the original's hooks
        (cache-guard registrations, requeue hook)."""
        worker = _BatcherWorker(
            self.batcher,
            compile_cache_budget=self._compile_cache_budget)
        # lazily-created program families count toward the compile budget
        # (snapshot copy: the guard runs on the worker thread)
        worker.cache_guard.register(
            lambda: list(self._embed_fns.values()))
        worker.cache_guard.add_busy_check(
            lambda: self._embed_inflight > 0)
        if self.worker_restarts > 0:
            worker.on_death = self._on_worker_death
        worker.emit_counts = self._emit_counts
        worker.rpc_clock = self._rpc
        return worker

    _MAX_JSON_DEPTH = 3  # regex expansion grows with depth; bound it

    def json_constraint(self, depth: int):
        """Compile-once TokenConstraint for a depth-bounded JSON value
        (the gen option ':j=DEPTH'). Returns None when the server's
        tokenizer exposes no token->bytes map (constraints need one).
        Raises ValueError for an out-of-range depth."""
        depth = int(depth)
        if not 0 <= depth <= self._MAX_JSON_DEPTH:
            raise ValueError(
                f"json depth must be in [0, {self._MAX_JSON_DEPTH}], "
                f"got {depth}")
        vb = getattr(self.tokenizer, "vocab_bytes", None)
        if vb is None:
            return None
        c = self._constraint_cache.get(depth)
        if c is None:
            from dnn_tpu.runtime.constrain import TokenConstraint, json_regex

            # compile over the MODEL's vocab size: padded embedding
            # tables (model vocab > tokenizer vocab) must still match
            # the batcher's vocab check, with padding ids banned.
            # Tolerate zero-arg vocab_bytes() adapters (the protocol
            # predates the size parameter) by padding/trimming here.
            model_v = self.batcher.cfg.vocab_size
            try:
                vocab = vb(model_v)
            except TypeError:
                vocab = list(vb())
            if len(vocab) < model_v:
                vocab = list(vocab) + [b""] * (model_v - len(vocab))
            elif len(vocab) > model_v:
                vocab = list(vocab)[:model_v]
            c = TokenConstraint.from_regex(json_regex(depth), vocab)
            self._constraint_cache[depth] = c
        return c

    def _request_span(self, request_id: str, **attrs):
        """Root span for one served request: a client that tagged its
        request_id (obs.tag_request_id — the `tr=` segment rides the
        existing wire field) gets its trace CONTINUED across the process
        boundary; untagged requests start fresh. NULL_SPAN when off."""
        return obs.continue_or_start("lm.request", request_id, **attrs)

    # --- RPC implementations (names/signatures fixed by the protocol) ---

    async def _preflight(self, request_id: str, context):
        """Shared request preflight for both RPC fronts: drain gate,
        worker liveness, option parsing — one place, one status
        mapping. A draining server rejects with UNAVAILABLE — the
        retriable status the edge client's ladder honors — so admission
        stops without losing anything."""
        if self._draining:
            await context.abort(
                grpc.StatusCode.UNAVAILABLE,
                "draining: admission closed; retry against another "
                "replica")
        if not self.worker.is_alive():
            await context.abort(
                grpc.StatusCode.UNAVAILABLE,
                "LM batcher worker is not running (died or shut down)")
        max_new, seed, opts = parse_gen_options(request_id,
                                                self.default_max_new)
        if "json_depth" in opts:
            try:
                # first use per depth compiles an (S, V) token table —
                # vocab-sized host work that must not block the event
                # loop (every concurrent RPC stalls behind _preflight)
                c = await asyncio.to_thread(self.json_constraint,
                                            opts.pop("json_depth"))
            except ValueError as e:
                await context.abort(grpc.StatusCode.INVALID_ARGUMENT,
                                    str(e))
            if c is None:
                await context.abort(
                    grpc.StatusCode.INVALID_ARGUMENT,
                    "JSON mode (j=) needs a server tokenizer with a "
                    "token->bytes map (io/tokenizer.ByteTokenizer)")
            opts["constraint"] = c
        return max_new, seed, opts

    async def _result_or_abort(self, fut, context):
        """Map a COMPLETED worker future to the shared status ladder
        (both fronts route every terminal outcome through here, so a
        streaming caller and a unary caller always see the same gRPC code
        for the same server condition): cancelled -> UNAVAILABLE
        (server-side abandon), ValueError -> INVALID_ARGUMENT (caller
        error), other exceptions -> UNAVAILABLE (worker death/shutdown).
        Returns the result on success."""
        if fut.cancelled():
            await context.abort(grpc.StatusCode.UNAVAILABLE,
                                "LM server shut down")
        exc = fut.exception()
        if isinstance(exc, ValueError):
            await context.abort(grpc.StatusCode.INVALID_ARGUMENT, str(exc))
        if exc is not None:
            await context.abort(grpc.StatusCode.UNAVAILABLE, str(exc))
        return fut.result()

    async def _submit_and_await(self, ids, request_id: str, context,
                                root=None):
        """Unary submit/await: preflight, wait with the request deadline
        (-> DEADLINE_EXCEEDED), client RPC cancellation re-raised for
        grpc.aio, all terminal outcomes mapped by _result_or_abort.
        `root` — an already-created request span whose ending the CALLER
        owns (SendMessage appends a detokenize child after the tokens
        come back); None creates and ends one here."""
        own_root = root is None
        if own_root:
            root = self._request_span(request_id, method="SendTensor")
        fut = None
        try:
            max_new, seed, opts = await self._preflight(request_id,
                                                        context)
            await self._resolve_kv_handle(opts, context)
            # propagated deadline (dl= segment, comm/transport.py): the
            # caller's REMAINING budget caps the server-side wait, so a
            # nearly-dead request can't hold a slot for the full local
            # request_timeout after its client already gave up
            inbound_dl = _tx.extract_deadline(request_id)
            timeout_s = self.request_timeout if inbound_dl is None \
                else max(min(self.request_timeout, inbound_dl), 0.001)
            dkey = opts.pop("dedup", None)
            root.set(max_new=max_new,
                     prompt_len=int(np.asarray(ids).size))
            # cancel_evt: a deadline abort must also retire the slot at
            # the next step boundary — without it the pool decodes on to
            # the abandoned request's full token budget
            cancel_evt = threading.Event()
            if dkey is not None:
                # exactly-once admission: a retried dedup key JOINS the
                # original request's future instead of generating twice
                # (failed/cancelled entries are replaced — retrying
                # after a real failure is the point of retrying)
                with self._dedup_lock:
                    cached = self._dedup.get(dkey)
                    if cached is not None and not cached.cancelled() \
                            and not (cached.done()
                                     and cached.exception() is not None):
                        fut = cached
            joined = fut is not None
            if joined:
                obs.flight.record(
                    "dedup_join", key=str(dkey)[:80],
                    trace_id=root.trace_id if root else None)
                root.set(dedup="join")
            else:
                fut = self.worker.submit(
                    np.asarray(ids, np.int32).reshape(-1), max_new, seed,
                    opts=opts, trace=root, cancel_evt=cancel_evt)
                if dkey is not None:
                    with self._dedup_lock:
                        self._dedup[dkey] = fut
                        while len(self._dedup) > self._DEDUP_CAP:
                            self._dedup.pop(next(iter(self._dedup)))
            try:
                # a JOINED wait is shielded: this caller timing out must
                # abandon only its own wait, never cancel the original
                # submitter's future out from under it
                wrapped = asyncio.wrap_future(fut)
                await asyncio.wait_for(
                    asyncio.shield(wrapped) if joined else wrapped,
                    timeout=timeout_s)
            except asyncio.TimeoutError:
                cancel_evt.set()
                m = obs.metrics()
                if m is not None:
                    m.inc("serving.deadline_exceeded_total")
                # availability SLO: no direct feed here — the eviction
                # retires through batcher.cancel -> _obs_retire
                # ("cancelled"), which counts it against the budget once
                # the post-mortem record: the dump (/debugz) carries this
                # event plus whatever surrounded it (admissions, compiles,
                # watchdog state flips) — the window a stall hides in
                obs.flight.record(
                    "deadline_miss", method="SendTensor",
                    timeout_s=timeout_s,
                    trace_id=root.trace_id if root else None)
                await context.abort(
                    grpc.StatusCode.DEADLINE_EXCEEDED,
                    f"generation exceeded {timeout_s}s")
            except asyncio.CancelledError:
                if not fut.cancelled():
                    raise  # client cancelled the RPC: grpc.aio handles it
            except Exception:  # noqa: BLE001 — the future itself holds
                pass           # the outcome; _result_or_abort maps it
            return await self._result_or_abort(fut, context)
        finally:
            # end-of-span in ALL outcomes — a preflight abort's trace
            # (the failed request an operator most wants to see) must
            # still reach the collector, which stores ended spans only
            if own_root:
                done = fut is not None and fut.done() \
                    and not fut.cancelled() and fut.exception() is None
                root.end(tokens=len(fut.result()) if done else None)

    async def _validated_prompt(self, request: pb.TensorRequest, context):
        """Decode + validate the raw-id prompt (shared by the unary and
        streaming fronts): integrity, integer dtype, vocab range — JAX's
        clip-mode gather would otherwise silently substitute edge-of-table
        embeddings and generate plausible output from a corrupt prompt."""
        try:
            prompt = _tensor_arr(request.tensor)
        except PayloadCorruptError as e:
            await context.abort(grpc.StatusCode.DATA_LOSS, str(e))
        if not np.issubdtype(prompt.dtype, np.integer):
            await context.abort(
                grpc.StatusCode.INVALID_ARGUMENT,
                f"prompt must be integer token ids, got dtype {prompt.dtype}")
        vocab = self.batcher.cfg.vocab_size
        if prompt.size and (prompt.min() < 0 or prompt.max() >= vocab):
            await context.abort(
                grpc.StatusCode.INVALID_ARGUMENT,
                f"prompt token ids must be in [0, {vocab}), got range "
                f"[{prompt.min()}, {prompt.max()}]")
        return prompt

    def _embed_prompt(self, prompt: np.ndarray, pooling: str) -> np.ndarray:
        """Pooled hidden-state embedding of one prompt
        (runtime/embeddings.make_embed). Prompts pad up to a prompt_pad
        multiple — pad content is free under causal attention, so ONE
        jitted program per (pooling, padded length) serves every request
        of that bucket. Runs concurrently with the decode worker (JAX
        serializes device execution); called via asyncio.to_thread so
        the event loop never blocks on device time."""
        cfg = self.batcher.cfg
        if (getattr(self.batcher.family, "ffn", None) is not None
                and getattr(cfg, "default_ffn", lambda **_: None)()
                is None):
            # the extractor resolves CONFIG-carried MLP overrides
            # (Mixtral's default_ffn) itself; an ffn set only on the
            # family adapter (the GPT-MoE daemon) has no hook in the
            # extractor's block forward — reject cleanly instead of
            # KeyError-ing inside the trace
            raise ValueError(
                "the embedding endpoint does not support ffn-overridden "
                "families whose config carries no default_ffn (the "
                "GPT-MoE daemon)")
        t = int(prompt.size)
        if t < 1:
            raise ValueError("embedding needs at least one token")
        if t > cfg.block_size:
            raise ValueError(
                f"prompt length {t} > block_size {cfg.block_size}")
        fn = self._embed_fns.get(pooling)
        if fn is None:
            from dnn_tpu.runtime.embeddings import make_embed

            fn = make_embed(cfg, pooling=pooling,
                            compute_dtype=self.batcher.family.compute_dtype)
            self._embed_fns[pooling] = fn
        p_pad = self.batcher.prompt_pad
        padded_len = min(-(-t // p_pad) * p_pad, cfg.block_size)
        ids = np.zeros((1, max(padded_len, t)), np.int32)
        ids[0, :t] = prompt.reshape(-1)
        # in-flight marker: the worker's cache guard must not
        # jax.clear_caches() while this thread is inside the program —
        # transitions under guard.lock make the guard's check+clear
        # atomic against them (utils/xla_cache.py)
        guard = self.worker.cache_guard
        with guard.lock:
            self._embed_inflight += 1
        try:
            out = fn(self.batcher.prepared, ids,
                     np.asarray([t], np.int32))
            return np.asarray(out[0], np.float32)
        finally:
            with guard.lock:
                self._embed_inflight -= 1

    # -- disaggregated prefill/decode (dnn_tpu/control) -----------------

    def _prefill_export(self, prompt: np.ndarray) -> np.ndarray:
        """Run the chunk loop only (no slot, no sampling) and pack the
        handoff payload. Same off-worker device-work discipline as the
        embed endpoint: the _embed_inflight counter (really "aux device
        work in flight") fences the worker's cache guard so a clear
        can never land mid-program."""
        from dnn_tpu.control import handoff as _handoff

        guard = self.worker.cache_guard
        with guard.lock:
            self._embed_inflight += 1
        try:
            return np.asarray(
                _handoff.pack(self.batcher.export_prefill(prompt)))
        finally:
            with guard.lock:
                self._embed_inflight -= 1

    async def _kvput(self, key: str, request: pb.TensorRequest,
                     context) -> pb.TensorResponse:
        """Ingest a prefill replica's packed KV payload under `key`.
        Unpacked and geometry-checked NOW — a mismatched handoff fails
        at ingest with a readable diff, not at admission; handles are
        single-use (the h= gen option consumes them) and the inbox is
        a bounded LRU."""
        key = key.strip()
        if not key:
            await context.abort(
                grpc.StatusCode.INVALID_ARGUMENT,
                "kvput needs a nonempty handle key (kvput:<key>)")
        if getattr(self.batcher, "spec_k", None) is not None:
            await context.abort(
                grpc.StatusCode.INVALID_ARGUMENT,
                "speculative servers cannot adopt handed-off KV (the "
                "draft cache needs its own prompt prefill)")
        if getattr(self.batcher, "_ilv", 0):
            await context.abort(
                grpc.StatusCode.INVALID_ARGUMENT,
                "interleaved-admission servers (prefill_chunk_tokens) "
                "cannot adopt handed-off KV — adoption rides the "
                "convoy install path")
        try:
            raw = _tensor_arr(request.tensor)
        except PayloadCorruptError as e:
            await context.abort(grpc.StatusCode.DATA_LOSS, str(e))
        from dnn_tpu.control import handoff as _handoff

        try:
            # full-payload byte parse: host-only, but row-cache-sized —
            # off the event loop like every other non-trivial handler leg
            payload = await asyncio.to_thread(_handoff.unpack, raw)
        except ValueError as e:
            await context.abort(grpc.StatusCode.INVALID_ARGUMENT, str(e))
        mine = self.batcher.handoff_fingerprint()
        theirs = payload.get("fingerprint") or {}
        if theirs and theirs != mine:
            diff = {k: (theirs.get(k), mine.get(k))
                    for k in set(theirs) | set(mine)
                    if theirs.get(k) != mine.get(k)}
            await context.abort(
                grpc.StatusCode.INVALID_ARGUMENT,
                f"handoff geometry mismatch (theirs, mine): {diff} — "
                "prefill and decode replicas must share model config, "
                "max_len, prompt_pad and kv dtype")
        self._sweep_kv_handoffs()
        with self._kv_lock:
            self._kv_handoff[key] = (payload, time.monotonic())
            while len(self._kv_handoff) > self._kv_handoff_cap:
                self._kv_handoff.pop(next(iter(self._kv_handoff)))
        obs.flight.record("kv_staged", key=key[:80],
                          prompt_len=payload["prompt_len"])
        return wc.TensorResponse(
            status=f"[lm] ok: kv handle {key!r} staged "
                   f"({payload['prompt_len']} prompt positions)")

    def _sweep_kv_handoffs(self, now: Optional[float] = None):
        """TTL sweep over the kvput inbox: staged handoffs are single-
        use and were previously unbounded-LIFETIME until collected — an
        abandoned prefill (router death between kvput and generate)
        pinned its row-sized payload until cap pressure pushed it out.
        Swept from the worker's housekeeping tick AND on every ingest;
        each expiry is a `kvput_expired` flight event."""
        ttl = self._kv_handoff_ttl_s
        if ttl <= 0:
            return
        now = time.monotonic() if now is None else now
        expired = []
        with self._kv_lock:
            for k in list(self._kv_handoff):
                payload, t0 = self._kv_handoff[k]
                if now - t0 > ttl:
                    expired.append((k, payload.get("prompt_len")))
                    del self._kv_handoff[k]
        if expired:
            m = obs.metrics()
            for k, plen in expired:
                if m is not None:
                    m.inc("serving.kvput_expired_total")
                obs.flight.record("kvput_expired", key=str(k)[:80],
                                  prompt_len=plen, ttl_s=ttl,
                                  cause="kvput_ttl")

    def _housekeeping_tick(self):
        """Worker-loop housekeeping (rate-limited to ~1 Hz so the hot
        loop pays one float compare): kvput inbox TTL + kvtier lease
        TTL sweeps."""
        now = time.monotonic()
        if now - self._hk_last < 1.0:
            return
        self._hk_last = now
        self._sweep_kv_handoffs(now)
        if self._kvtier_leases is not None:
            self._kvtier_leases.sweep()

    # -- fleet KV tier endpoints (dnn_tpu/kvtier) -----------------------

    def _kvtier_on(self) -> bool:
        return getattr(self.batcher, "_prefix_store", None) is not None

    async def _kvtier_require(self, context):
        if not self._kvtier_on():
            await context.abort(
                grpc.StatusCode.INVALID_ARGUMENT,
                "the KV tier is off on this replica: serve with "
                "kv=paged (or paged_blocks>0) and prefix_cache>0")

    async def _kvtier_stage(self, request, context):
        """kvstage: prefill these tokens' full blocks straight into
        the radix store (no slot, no sampling) — the prefill-replica
        half of disaggregated block migration."""
        await self._kvtier_require(context)
        prompt = await self._validated_prompt(request, context)
        fut = self.worker.submit_control(
            lambda: self.batcher.stage_prefix(np.asarray(prompt)))
        try:
            stats = await asyncio.wrap_future(fut)
        except ValueError as e:
            await context.abort(grpc.StatusCode.INVALID_ARGUMENT, str(e))
        except Exception as e:  # noqa: BLE001 — InsufficientBlocks etc:
            # transient, the caller treats staging as advisory
            await context.abort(grpc.StatusCode.RESOURCE_EXHAUSTED,
                                f"{type(e).__name__}: {e}")
        return wc.TensorResponse(
            status="[lm] ok: kvstage " + json.dumps(stats))

    async def _kvtier_lease(self, request, context):
        """kvlease: export the longest resident block run for these
        tokens, stage it under a TTL'd lease (kvtier/migrate.py), and
        answer the offer meta — lease id, byte count, and the shm
        segment + nonce when this host can publish one. The adopter
        pulls via shm attach or kvfetch and acks via kvack."""
        await self._kvtier_require(context)
        prompt = await self._validated_prompt(request, context)
        fut = self.worker.submit_control(
            lambda: self.batcher.kvtier_export(np.asarray(prompt)))
        try:
            payload = await asyncio.wrap_future(fut)
        except Exception as e:  # noqa: BLE001 — export failures are the
            # donor's problem, reported readable
            await context.abort(grpc.StatusCode.INTERNAL,
                                f"{type(e).__name__}: {e}")
        if payload is None:
            await context.abort(
                grpc.StatusCode.NOT_FOUND,
                "no resident prefix blocks for these tokens")
        from dnn_tpu.kvtier import migrate as _mig

        # host-side pack is row-cache-sized — off the event loop
        wire = await asyncio.to_thread(_mig.pack_blocks, payload)
        meta = self._kvtier_leases.offer(wire.tobytes())
        meta["n_tokens"] = int(np.asarray(payload["tokens"]).size)
        meta["blocks"] = int(
            np.asarray(payload["tokens"]).size // payload["block_len"])
        return wc.TensorResponse(
            status=f"[lm] ok: lease {meta['lease']} offered "
                   f"({meta['bytes']} bytes)",
            result_tensor=_tensor_msg(np.frombuffer(
                json.dumps(meta).encode(), np.uint8)))

    async def _kvtier_fetch(self, lease_id: str, context):
        """kvfetch:<lease>: the grpc rung — staged bytes back to the
        adopter. An expired/unknown lease is NOT_FOUND: the adopter
        records kvtier_fallback and re-prefills."""
        await self._kvtier_require(context)
        try:
            data = self._kvtier_leases.fetch(lease_id)
        except KeyError:
            await context.abort(
                grpc.StatusCode.NOT_FOUND,
                f"unknown or expired kvtier lease {lease_id!r}")
        return wc.TensorResponse(
            status=f"[lm] ok: lease {lease_id} ({len(data)} bytes)",
            result_tensor=_tensor_msg(np.frombuffer(data, np.uint8)))

    async def _kvtier_ack(self, lease_id: str, context):
        await self._kvtier_require(context)
        ok = self._kvtier_leases.ack(lease_id)
        return wc.TensorResponse(
            status=f"[lm] ok: lease {lease_id} "
                   + ("released" if ok else "already gone"))

    async def _kvtier_pull(self, request, context):
        """kvpull: {donor, tokens} — pull the prefix's blocks FROM the
        donor replica and adopt them locally. ADVISORY by design: any
        failure (donor dead, lease expired, geometry mismatch, pool
        full) answers a `kvtier_fallback` status instead of an error —
        the follow-up generate simply re-prefills, loud in the flight
        ring, never wrong."""
        await self._kvtier_require(context)
        try:
            raw = _tensor_arr(request.tensor)
            spec = json.loads(np.asarray(raw, np.uint8).tobytes())
            donor = str(spec["donor"])
            tokens = np.asarray(spec["tokens"], np.int32).reshape(-1)
        except (PayloadCorruptError, ValueError, KeyError, TypeError):
            await context.abort(
                grpc.StatusCode.INVALID_ARGUMENT,
                'kvpull expects a uint8 JSON tensor '
                '{"donor": "host:port", "tokens": [...]}')

        def _pull():
            from dnn_tpu.comm.client import NodeClient
            from dnn_tpu.kvtier import migrate as _mig

            cl = NodeClient(donor, transport="grpc", breaker=False)
            try:
                return _mig.pull_blocks(cl, tokens,
                                        timeout=self._kv_lease_ttl_s)
            finally:
                cl.close()

        m = obs.metrics()
        try:
            _chaos_inject.kv_migrate()  # donor-death-mid-migration seam
            payload = await asyncio.to_thread(_pull)
            fut = self.worker.submit_control(
                lambda: self.batcher.kvtier_adopt(payload))
            n = await asyncio.wrap_future(fut)
        except Exception as e:  # noqa: BLE001 — the whole point: a
            # dying donor (or an expired lease) must never fail the
            # request, only the OPTIMIZATION — loud, then re-prefill
            if m is not None:
                m.inc("dnn_tpu_kvtier_fallback_total")
            obs.flight.record("kvtier_fallback", donor=donor,
                              error=f"{type(e).__name__}: {e}"[:200])
            return wc.TensorResponse(
                status="[lm] kvtier_fallback: "
                       f"{type(e).__name__}: {e}"[:240])
        nbytes = int(payload.get("_wire_bytes", 0))
        if m is not None and n:
            m.inc("dnn_tpu_kvtier_migrated_blocks_total", n)
            if nbytes:
                m.inc("dnn_tpu_kvtier_migrated_bytes_total", nbytes)
        obs.flight.record("kvtier_adopted", donor=donor, blocks=n,
                          bytes=nbytes)
        return wc.TensorResponse(
            status=f"[lm] ok: kvpull adopted {n} blocks "
                   f"({nbytes} bytes) from {donor}")

    async def _resolve_kv_handle(self, opts: dict, context):
        """Swap a parsed h=<key> option for its staged payload
        (single-use). Unknown handle = INVALID_ARGUMENT — generating
        WITHOUT the adopted KV would silently re-prefill, hiding a
        broken handoff path."""
        h = opts.pop("kv_handle", None)
        if h is None:
            return
        with self._kv_lock:
            entry = self._kv_handoff.pop(h, None)
        if entry is None:
            await context.abort(
                grpc.StatusCode.INVALID_ARGUMENT,
                f"unknown or already-consumed kv handle {h!r} "
                "(kvput: it first; handles are single-use — an expired "
                "handle was TTL-swept, re-stage it)")
        opts["prefilled"] = entry[0]

    async def SendTensor(self, request: pb.TensorRequest, context) -> pb.TensorResponse:
        rid = request.request_id or ""
        # client-side transport metadata may ride any request_id — the
        # trace tag (tr=...) and the propagated deadline (dl=...); both
        # are stripped before endpoint parse (the deadline is honored
        # inside _submit_and_await, which reads the RAW rid)
        rid_clean = _tx.strip_deadline(obs.strip_wire_tag(rid))
        if rid_clean.startswith("kvput:"):
            # KV-handoff ingest (disaggregated serving): the tensor is
            # a packed export_prefill payload, NOT token ids — decoded
            # raw, before the vocab-range prompt validation below
            return await self._kvput(rid_clean.split(":", 1)[1],
                                     request, context)
        # fleet KV tier (dnn_tpu/kvtier): block-granular stage / lease /
        # fetch / ack / pull — kvpull and kvfetch/kvack carry non-token
        # tensors, so they too dispatch before prompt validation
        if rid_clean == "kvstage":
            return await self._kvtier_stage(request, context)
        if rid_clean == "kvlease":
            return await self._kvtier_lease(request, context)
        if rid_clean.startswith("kvfetch:"):
            return await self._kvtier_fetch(
                rid_clean.split(":", 1)[1], context)
        if rid_clean.startswith("kvack:"):
            return await self._kvtier_ack(
                rid_clean.split(":", 1)[1], context)
        if rid_clean == "kvpull":
            return await self._kvtier_pull(request, context)
        prompt = await self._validated_prompt(request, context)
        if rid_clean == "embed" or rid_clean.startswith("embed:"):
            # embedding endpoint: 'embed[:mean|last]' returns the pooled
            # final hidden state instead of generated tokens
            pooling = rid_clean.split(":", 1)[1] if ":" in rid_clean \
                else "mean"
            if pooling not in ("mean", "last"):
                await context.abort(
                    grpc.StatusCode.INVALID_ARGUMENT,
                    f"embed pooling must be mean|last, got {pooling!r}")
            root = self._request_span(rid, method="embed", pooling=pooling)
            try:
                vec = await asyncio.to_thread(
                    self._embed_prompt, np.asarray(prompt), pooling)
            except ValueError as e:
                await context.abort(grpc.StatusCode.INVALID_ARGUMENT,
                                    str(e))
            finally:
                root.end()
            return wc.TensorResponse(
                status=f"[lm] ok: embedding dim {vec.shape[-1]}",
                result_tensor=_tensor_msg(vec),
            )
        if rid_clean == "prefill":
            # prefill-export endpoint (disaggregated serving): run ONLY
            # the chunk loop for this prompt and answer with the packed
            # KV payload — the router (or any client) hands it to a
            # decode replica via kvput: + h=. Device work off-loop,
            # cache-guard-fenced, exactly like the embed endpoint.
            root = self._request_span(rid, method="prefill")
            try:
                payload = await asyncio.to_thread(
                    self._prefill_export, np.asarray(prompt))
            except ValueError as e:
                await context.abort(grpc.StatusCode.INVALID_ARGUMENT,
                                    str(e))
            finally:
                root.end()
            return wc.TensorResponse(
                status=f"[lm] ok: prefill kv {payload.size} bytes",
                result_tensor=_tensor_msg(payload),
            )
        tokens = await self._submit_and_await(prompt, rid, context)
        return wc.TensorResponse(
            status=f"[lm] ok: {len(tokens)} tokens",
            result_tensor=_tensor_msg(np.asarray(tokens, np.int32)),
        )

    async def GenerateStream(self, request: pb.TensorRequest, context):
        """Server-streaming generate: one TensorResponse PER TOKEN as it
        commits (result_tensor = [token]); stream end = generation done.
        The request registers a `TokenSink` (this loop, this handler's
        queue) with the worker: the tokens a step commits, of every
        stream, cross to this thread in one `call_soon_threadsafe` and
        are put on their queues here (`_fan_out`).
        A stream waits on its queue with a bare `get()`, and a token's
        message is made from the integer (`wc.make_token_tensor`): no
        timer and no array a token. The deadline is fixed when the
        request arrives and armed ONCE (`loop.call_at`, cancelled when
        the handler leaves, however it leaves): the timer puts a
        `"deadline"` item on the queue, which wakes a stream that waits.
        Its place in the queue decides nothing: the clock is read before
        every `get()`, so a stream whose deadline has passed aborts
        before it yields another token, whatever still waits in front of
        the timer's item.
        Client cancellation (disconnect / stream.cancel) sets the request's
        cancel event, and the batcher worker retires the slot at the next
        step boundary — a dropped stream never decodes on to its budget.
        The unary SendTensor front stays untouched for reference
        wire-compat (wire.proto)."""
        prompt = await self._validated_prompt(request, context)
        root = self._request_span(request.request_id,
                                  method="GenerateStream")
        n = 0
        cancel_evt = timer = None
        try:
            max_new, seed, opts = await self._preflight(
                request.request_id, context)
            # streaming requests cannot dedup-join (tokens already
            # stream to one consumer) — drop the key rather than let it
            # reach batcher.submit as an unknown kwarg
            opts.pop("dedup", None)
            # ...but they CAN adopt handed-off KV: resolve h= the same
            # way the unary front does
            await self._resolve_kv_handle(opts, context)
            root.set(max_new=max_new, prompt_len=int(prompt.size))
            loop = asyncio.get_running_loop()
            q: "asyncio.Queue" = asyncio.Queue()
            cancel_evt = threading.Event()

            lag, rpc = self._emit_lag, self._rpc
            fut = self.worker.submit(
                np.asarray(prompt, np.int32).reshape(-1), max_new, seed,
                opts=opts, on_token=TokenSink(loop, q.put_nowait),
                cancel_evt=cancel_evt, trace=root)

            def _done(f):
                # fires in the worker thread AFTER the hand-off of this
                # request's last token: call_soon_threadsafe preserves
                # that order, so the "done" sentinel always trails the
                # last token in the queue
                loop.call_soon_threadsafe(q.put_nowait, ("done", f))

            fut.add_done_callback(_done)
            inbound_dl = _tx.extract_deadline(request.request_id)
            timeout_s = self.request_timeout if inbound_dl is None \
                else max(min(self.request_timeout, inbound_dl), 0.001)
            deadline = loop.time() + timeout_s
            # the request's ONE timer: it wakes a stream that waits
            timer = loop.call_at(deadline, q.put_nowait, ("deadline", None))
            while True:
                if loop.time() < deadline:
                    kind, val = await q.get()
                else:
                    kind = "deadline"  # passed while this stream was at
                    # work or its items waited their turn on the loop
                if kind == "tok":
                    n += 1
                    tok, t_commit = val
                    # the `token` section of this thread's time: from
                    # here to the message's yield
                    t_tok = time.perf_counter()
                    waited = t_tok - t_commit
                    lag[0] += waited
                    lag[1] += 1
                    if waited > lag[2]:
                        lag[2] = waited
                    msg = wc.TensorResponse(
                        status=f"[lm] token {n}",
                        result_tensor=wc.make_token_tensor(tok))
                    rpc.token_ends(t_tok)
                    yield msg
                    continue
                if kind == "deadline":
                    cancel_evt.set()
                    m = obs.metrics()
                    if m is not None:
                        m.inc("serving.deadline_exceeded_total")
                    obs.flight.record(
                        "deadline_miss", method="GenerateStream",
                        timeout_s=timeout_s, tokens=n,
                        trace_id=root.trace_id if root else None)
                    await context.abort(
                        grpc.StatusCode.DEADLINE_EXCEEDED,
                        f"generation exceeded {timeout_s}s")
                await self._result_or_abort(val, context)
                return
        except asyncio.CancelledError:
            # the client went away: free the slot at the next step
            # boundary (None: cancelled during preflight, nothing queued)
            if cancel_evt is not None:
                cancel_evt.set()
            raise
        finally:
            if timer is not None:
                timer.cancel()
            root.end(tokens=n)

    async def HealthCheck(self, request: pb.Empty, context) -> pb.HealthCheckResponse:
        # a DRAINING server reports unhealthy so load balancers and
        # wait_healthy pollers stop routing to it while it finishes
        return pb.HealthCheckResponse(
            is_healthy=self.worker.is_alive() and not self._draining)

    async def SendMessage(self, request: pb.MessageRequest, context) -> pb.MessageReply:
        """Text endpoint. "!stats" (or any text without a tokenizer)
        answers with pool stats; with a tokenizer, the message text is a
        PROMPT and the reply is the generated continuation — the job the
        reference defined this RPC for but never gave it (node.py:111-113,
        no caller). Options ride the sender_id as "gen[:max_new[:seed]]".
        Transport negotiation hellos (comm/transport.py) are declined
        FIRST — prompt payloads are bytes-tiny, so the LM daemon keeps
        the grpc rung, and a hello must never reach the tokenizer as a
        "prompt"."""
        if request.sender_id.startswith(_tx.HELLO_SENDER):
            return pb.MessageReply(
                confirmation_text=_tx.decline_hello(
                    "LM daemon serves grpc only"))
        b = self.batcher
        text = request.message_text
        if self.tokenizer is None or text == "!stats":
            prefix = ""
            if b._prefix_cache is not None:
                prefix = (f", prefix cache: {b.prefix_hits} hits / "
                          f"{b.prefill_chunks_run} chunks run / "
                          f"{len(b._prefix_cache)} entries")
            elif getattr(b, "_prefix_store", None) is not None:
                s = b._prefix_store
                prefix = (f", kvtier: {b.prefix_hits} hits / "
                          f"{s.block_hits} block hits "
                          f"({s.remote_block_hits} remote) / "
                          f"{s.n_blocks} resident blocks")
            return pb.MessageReply(
                confirmation_text=(
                    f"[lm] pool: {b.n_active}/{b.slots} slots active, "
                    f"{len(b.results)} unclaimed results" + prefix))
        ids = self.tokenizer.encode(text)
        if not ids:
            await context.abort(grpc.StatusCode.INVALID_ARGUMENT,
                                "prompt text tokenized to nothing")
        root = self._request_span(request.sender_id, method="SendMessage")
        try:
            tokens = await self._submit_and_await(
                ids, request.sender_id, context, root=root)
            with root.child("detokenize"):  # host-side text assembly
                reply = self.tokenizer.decode([int(t) for t in tokens])
        finally:
            root.end()
        return pb.MessageReply(confirmation_text=reply)

    def close(self):
        self.worker.stop(drain=False)
        self.worker.join(timeout=10)
        if self._watchdog is not None:
            self._watchdog.close()
            self._watchdog = None
        if self.metrics_server is not None:
            self.metrics_server.close()
            self.metrics_server = None


async def serve_lm(cfg, prepared, *, port: int, **server_kwargs) -> int:
    """Start the LM daemon and block until termination — the LM analog of
    comm.service.serve_stage (reference serve(), node.py:114-133).

    Resilience exits (ISSUE 8): SIGTERM triggers CONNECTION DRAINING —
    admission closes (UNAVAILABLE "draining", retriable), in-flight
    decodes finish within the drain grace, queued work hands back —
    then the server exits cleanly (rc 0). A watchdog wedged-policy
    escalation (`on_wedged=restart|drain`) exits with EXIT_RESTART (43)
    so a supervisor (node --supervise / chaos.supervisor) relaunches
    the process, restoring from the latest good checkpoint."""
    import signal

    servicer = LMServer(cfg, prepared, **server_kwargs)
    server = grpc.aio.server(options=_tx.GRPC_MSG_OPTIONS)
    server.add_generic_rpc_handlers((_handlers(servicer),))
    listen = f"[::]:{port}"
    if server.add_insecure_port(listen) == 0:
        raise RuntimeError(f"failed to bind gRPC server to {listen}")
    log.info("gRPC LM server listening on %s (%d slots)", listen,
             servicer.batcher.slots)
    await server.start()
    servicer.note_rpc_loop_thread()
    loop = asyncio.get_running_loop()
    sigterm_drained = False

    def _on_sigterm():
        nonlocal sigterm_drained
        sigterm_drained = True
        obs.flight.record("sigterm_drain")
        log.info("SIGTERM: draining (admission closed, finishing "
                 "in-flight decodes)")
        servicer._drainz()  # background drain -> sets the escalation

    try:
        loop.add_signal_handler(signal.SIGTERM, _on_sigterm)
    except (NotImplementedError, ValueError, RuntimeError):
        pass  # non-main thread / platform without signal support
    async def _wait_escalated():
        # bounded waits so cancellation never strands a thread parked
        # in Event.wait() forever at shutdown
        while not await asyncio.to_thread(servicer._escalated.wait, 1.0):
            pass

    # loop-lag sanitizer (analysis/sanitize.py): env-gated tripwire for
    # event-loop-blocking calls the AST pass can't see — verify paths
    # run with DNN_TPU_LOOP_SANITIZE=1 and read breaches off /debugz
    from dnn_tpu.analysis import sanitize as _sanitize

    lagmon = _sanitize.maybe_install(where="serve_lm")
    esc_task = asyncio.ensure_future(_wait_escalated())
    term_task = asyncio.ensure_future(server.wait_for_termination())
    try:
        await asyncio.wait({esc_task, term_task},
                           return_when=asyncio.FIRST_COMPLETED)
        if servicer._escalated.is_set():
            reason = servicer._escalate_reason or "escalated"
            log.warning("serve_lm exiting on escalation: %s", reason)
            if servicer.on_wedged == "restart" and not sigterm_drained \
                    and not reason.startswith("drained"):
                # restart policy: no drain — the device is wedged and
                # in-flight work cannot finish; the supervisor restarts
                # us from the latest checkpoint
                return EXIT_RESTART
            return 0 if sigterm_drained else EXIT_RESTART
        return 0
    finally:
        # teardown ORDER matters: stop the server FIRST (which lets
        # wait_for_termination complete on its own), THEN reap the
        # watcher tasks — cancelling wait_for_termination while stop()
        # runs makes grpc.aio surface CancelledError out of this
        # finally, clobbering the escalation return code (the verify
        # scenario caught exactly that as rc=1 instead of 43/0)
        if lagmon is not None:
            lagmon.stop()
        esc_task.cancel()
        try:
            await server.stop(grace=1)
        except asyncio.CancelledError:
            pass
        for t in (esc_task, term_task):
            if not t.done():
                t.cancel()
            try:
                await t
            except BaseException:  # noqa: BLE001 — reaped, not consulted
                pass
        servicer.close()


def start_lm_server_in_background(cfg, prepared, *, port: int, **server_kwargs):
    """Test/embedding helper: serve_lm on a daemon thread; returns
    (thread, stop_callback) — mirrors
    comm.service.start_stage_server_in_background."""
    loop = rpc_event_loop()
    started = threading.Event()
    state = {}

    async def _run():
        try:
            servicer = LMServer(cfg, prepared, **server_kwargs)
            server = grpc.aio.server(options=_tx.GRPC_MSG_OPTIONS)
            server.add_generic_rpc_handlers((_handlers(servicer),))
            if server.add_insecure_port(f"[::]:{port}") == 0:
                servicer.close()
                raise RuntimeError(f"failed to bind gRPC server to [::]:{port}")
            await server.start()
            servicer.note_rpc_loop_thread()
            state["servicer"], state["server"] = servicer, server
            state["done"] = asyncio.Event()
        except BaseException as e:
            state["error"] = e
            raise
        finally:
            started.set()
        await state["done"].wait()
        await asyncio.sleep(0.05)

    def _thread_main():
        asyncio.set_event_loop(loop)
        try:
            loop.run_until_complete(_run())
        except BaseException:
            if "error" not in state:
                raise
            # startup error already recorded and re-raised to the caller

    t = threading.Thread(target=_thread_main, daemon=True)
    t.start()
    if not started.wait(timeout=30):
        raise RuntimeError("LM server failed to start")
    if "error" in state:
        t.join(timeout=5)
        raise RuntimeError(f"LM server failed to start: {state['error']}") \
            from state["error"]

    def stop():
        async def _stop():
            await state["server"].stop(grace=0.2)
            state["done"].set()

        asyncio.run_coroutine_threadsafe(_stop(), loop).result(timeout=10)
        state["servicer"].close()
        t.join(timeout=5)

    # expose the servicer (tests read e.g. the ephemeral metrics_port=0
    # endpoint via stop.servicer.metrics_server.port)
    stop.servicer = state["servicer"]
    return t, stop
