"""Router: the fleet front door.

A stdlib-asyncio gRPC server speaking the EXISTING NodeService wire
format — SendTensor (generate / embed / prefill / kvput), the additive
GenerateStream, HealthCheck, SendMessage — so every client that talks
to one LM daemon (`NodeClient`, reference-built clients) points at the
router unchanged and gets a FLEET. Per request the router:

  1. ADMITS or SHEDS (SLO-driven): `policy.shed_reason` over the live
     replica views — when every candidate is saturated (the router's
     exact per-replica in-flight bound) or burning error budget past
     the configured rate, the request is shed with UNAVAILABLE, the
     status the whole client ladder (retry, breaker) already treats
     as explicitly-rejected-retriable.
     Shedding is what keeps an overloaded fleet's queues short enough
     that admitted work finishes inside its deadline instead of
     degenerating into admit-then-deadline-cancel waste.
  2. PICKS a replica via the pluggable policy (`round_robin |
     least_queue | slo_burn`), honoring dedup-key session affinity:
     a `d=`/`h=` tagged request re-routes to the replica that saw the
     key before (the per-replica prefix cache and the server-side
     dedup join both only help on the same replica — until ROADMAP
     item 2's fleet-wide KV tier lands, affinity IS the cache policy).
  3. RE-TAGS the `dl=` deadline per hop: the forward carries only the
     caller's REMAINING budget (comm/client re-tags per attempt), so
     sibling retries can never over-spend a dying request.
  4. RETRIES ON A SIBLING when a replica answers UNAVAILABLE (draining
     /ConnectionRefused/breaker-open): a drained replica's handed-back
     queue lands on its siblings with no client involvement.
  5. DISAGGREGATES prefill/decode when the fleet is role-split: the
     prompt goes to a `role=prefill` replica (`export_prefill` — the
     full chunk loop, no slot held), the returned KV payload is
     installed on the chosen decode replica (`kvput:` + `h=`), and
     only then does the generate forward — the decode replica spends
     ZERO prompt FLOPs. The handoff rides the grpc rung of the
     negotiated transport (the LM daemon declines shm/device — those
     rungs fail loud when forced, like everywhere else) and is priced
     on the router's own gauges (handoff bytes/seconds) next to the
     goodput gauges the replicas already export.

The router's lifecycle is a declared state machine
(init/serving/shedding/draining/stopped — `analysis/protocol.ROUTER`,
model-checked both directions); transitions land in the flight ring as
`router_*` events. Autoscaling: the scrape-time
`dnn_tpu_wanted_replicas` gauge (policy.wanted_replicas) rides the
router's /metrics even though nothing consumes it yet.
"""

from __future__ import annotations

import asyncio
import itertools
import logging
import threading
import time
import weakref
from collections import OrderedDict
from typing import Dict, Optional, Set

import grpc
import numpy as np

from dnn_tpu import obs
from dnn_tpu.comm import transport as _tx
from dnn_tpu.comm import wire_pb2 as pb
from dnn_tpu.comm import wirecodec as wc
from dnn_tpu.comm.service import _handlers, _tensor_arr, _tensor_msg
from dnn_tpu.control.policy import Policy, get_policy, shed_reason, \
    wanted_replicas
from dnn_tpu.control.replicaset import ReplicaHandle, ReplicaSet
from dnn_tpu.io.serialization import PayloadCorruptError
from dnn_tpu.utils.metrics import labeled

log = logging.getLogger("dnn_tpu.control")

__all__ = ["Router", "serve_router", "start_router_in_background"]


def _size_forward_executor(loop, router: "Router"):
    """Give the loop a default executor sized to the router's own
    admission bound. asyncio.to_thread rides the DEFAULT executor,
    whose stock size is min(32, cpu_count + 4) — on a small host that
    caps concurrent forwards at ~5 threads, an invisible throttle far
    below max_inflight_per_replica x replicas; the admission
    controller, not the executor, must be the concurrency bound."""
    import concurrent.futures

    n = max(16, router.max_inflight * len(router.replicaset.replicas)
            + 8)
    loop.set_default_executor(concurrent.futures.ThreadPoolExecutor(
        max_workers=n, thread_name_prefix="router-fwd"))

#: gRPC codes a sibling can plausibly do better on — everything else
#: (INVALID_ARGUMENT, DATA_LOSS, ...) is the REQUEST's fault and
#: passes through verbatim
_SIBLING_RETRIABLE = (grpc.StatusCode.UNAVAILABLE,)


class _Shed(Exception):
    """Internal: the admission decision said shed (reason in args)."""


def _affinity_key(request_id: str) -> Optional[str]:
    """The session-affinity key riding the request id: the dedup key
    (`d=`) or a KV-handoff handle (`h=`) — both only work on the
    replica that has seen them before."""
    for seg in (request_id or "").split(":"):
        if seg.startswith("d=") or seg.startswith("h="):
            return seg
    return None


def _role_ok(role: str, need: str) -> bool:
    return role == "both" or role == need


class Router:
    """NodeService servicer that routes across a ReplicaSet.

    `policy` is a name (`round_robin | least_queue | slo_burn`) or a
    prebuilt `control.policy.Policy`. `max_inflight_per_replica`
    bounds the router's outstanding forwards per replica (the
    admission controller's exact signal); `shed_burn` (None = off)
    additionally sheds when EVERY candidate's worst SLO burn rate is
    at or past it. `default_deadline_s` caps requests that propagate
    no `dl=` budget of their own. `retry_siblings` bounds how many
    OTHER replicas an UNAVAILABLE forward retries against (the drain
    hand-back path). `disagg="auto"` routes gen requests through the
    prefill->decode handoff whenever the fleet is actually role-split
    ("off" never does; "on" fails loud when it can't)."""

    def __init__(self, replicaset: ReplicaSet, *,
                 policy="least_queue",
                 default_deadline_s: float = 30.0,
                 max_inflight_per_replica: int = 8,
                 shed_burn: Optional[float] = None,
                 retry_siblings: int = 2,
                 disagg: str = "auto",
                 slots_hint: int = 4,
                 affinity_cap: int = 4096,
                 kvtier: str = "auto",
                 kv_block_len: int = 16,
                 kv_pull_timeout_s: float = 10.0):
        if disagg not in ("auto", "on", "off"):
            raise ValueError(
                f"disagg must be auto|on|off, got {disagg!r}")
        # fleet KV tier (dnn_tpu/kvtier): prefix-aware placement.
        #   "auto" — route a gen request to the replica the directory
        #     says holds its deepest prefix (when routable); otherwise
        #     pick by policy and INSTRUCT A PULL from the holder —
        #     affinity stops being a cache-correctness constraint;
        #   "pull" — never prefer the holder (the policy alone places),
        #     always instruct pulls — the migration-stress mode;
        #   "off"  — PR 12 behavior (dedup-key affinity only).
        if kvtier not in ("auto", "pull", "off"):
            raise ValueError(
                f"kvtier must be auto|pull|off, got {kvtier!r}")
        self._kvtier = kvtier
        self._kvdir = None
        self.kv_pull_timeout_s = float(kv_pull_timeout_s)
        self._kv_on_names: Set[str] = set()
        self._kv_on_ts = 0.0
        if kvtier != "off":
            from dnn_tpu.kvtier.directory import PrefixDirectory

            self._kvdir = PrefixDirectory(kv_block_len)
        self.replicaset = replicaset
        self.policy: Policy = policy if isinstance(policy, Policy) \
            else get_policy(policy)
        self.default_deadline_s = float(default_deadline_s)
        self.max_inflight = int(max_inflight_per_replica)
        self.shed_burn = shed_burn
        self.retry_siblings = int(retry_siblings)
        self.disagg = disagg
        self.slots_hint = int(slots_hint)
        # the router lifecycle machine is DECLARED (and model-checked)
        # in analysis/protocol.ROUTER — edit both together. All writes
        # under _lock (handlers run on the event loop; close()/serve()
        # may run on other threads).
        self._state = "init"  # init|serving|shedding|draining|stopped
        self._lock = threading.Lock()
        self._draining = False
        self._inflight: Dict[str, int] = {}
        self._clients: Dict[str, object] = {}
        self._affinity: "OrderedDict[str, str]" = OrderedDict()
        self._affinity_cap = int(affinity_cap)
        self._handle_seq = itertools.count()
        self.shed_total = 0
        # the capacity observatory (obs/caplens): demand from this
        # router's admission seam, capacity from its commits, the
        # cold-start ledger from the replicaset's lifecycle seams.
        # One lens per router; every hook below guards with one
        # `lens is not None` test (the kvlens overhead contract).
        self.caplens = None
        m = obs.metrics()
        if m is not None:
            from dnn_tpu.obs.caplens import CapLens

            self.caplens = CapLens(
                slots_per_replica=self.slots_hint,
                max_inflight=self.max_inflight,
                deadline_s=self.default_deadline_s)
            replicaset.attach_caplens(self.caplens)
            for k, fn in self.caplens.prom_gauges().items():
                m.set_fn(k, fn)
        self._install_gauges()

    @property
    def state(self) -> str:
        with self._lock:
            return self._state

    # -- state machine -------------------------------------------------

    def start(self):
        """init -> serving (the gRPC server is about to take traffic)."""
        with self._lock:
            if self._state != "init":
                return
            self._state = "serving"
        obs.flight.record("router_start",
                          replicas=len(self.replicaset.replicas),
                          policy=self.policy.name)

    def _note_shed(self, reason: str):
        self.shed_total += 1
        lens = self.caplens
        if lens is not None:
            lens.on_shed(reason)
        m = obs.metrics()
        if m is not None:
            m.inc(labeled("dnn_tpu_router_shed_total", reason=reason))
            m.inc(labeled("dnn_tpu_router_requests_total",
                          outcome="shed"))
        with self._lock:
            if self._state != "serving":
                return
            self._state = "shedding"
        obs.flight.record("router_shed", reason=reason)

    def _note_admitted(self):
        with self._lock:
            if self._state != "shedding":
                return
            self._state = "serving"
        obs.flight.record("router_unshed")

    def drain(self):
        """serving|shedding -> draining: stop admitting; in-flight
        forwards finish on their replicas. The serve loop exits once
        drained (serve_router watches the escalation event)."""
        with self._lock:
            if self._state in ("draining", "stopped"):
                return
            self._state = "draining"
            self._draining = True
        obs.flight.record("router_drain",
                          inflight=sum(self._inflight.values()))

    def close(self):
        with self._lock:
            already = self._state == "stopped"
            self._state = "stopped"
        if not already:
            obs.flight.record("router_stop")
        for c in self._clients.values():
            try:
                c.close()
            except Exception:  # noqa: BLE001 — teardown best-effort
                pass
        self._clients.clear()

    # -- plumbing ------------------------------------------------------

    def _install_gauges(self):
        m = obs.metrics()
        if m is None:
            return
        ref = weakref.ref(self)

        def _queue():
            r = ref()
            return float(sum(r._inflight.values())) if r is not None \
                else 0.0

        def _wanted():
            r = ref()
            if r is None:
                return 0.0
            # v2 (obs/caplens): the audited what-if planner's verdict,
            # when it has evidence; the v1 occupancy heuristic until
            # then (and whenever obs is off)
            lens = r.caplens
            if lens is not None:
                n_live = sum(1 for v in r._views()
                             if v.state == "serving")
                w = lens.wanted_replicas(n_live=n_live)
                if w is not None:
                    return float(w)
            return float(wanted_replicas(
                r._views(), slots_hint=r.slots_hint,
                shedding=r.state == "shedding"))

        m.set_fn("dnn_tpu_router_queue_depth", _queue)
        m.set_fn("dnn_tpu_wanted_replicas", _wanted)

    def _client(self, handle: ReplicaHandle):
        c = self._clients.get(handle.name)
        if c is None:
            from dnn_tpu.comm.client import CircuitBreaker, NodeClient

            # tight breaker: during an outage the router must fail over
            # to a sibling within ~a second, not ride a 30 s cooldown
            c = NodeClient(handle.address, transport="grpc",
                           breaker=CircuitBreaker(
                               handle.address, threshold=3,
                               cooldown_s=0.5, max_cooldown_s=4.0))
            self._clients[handle.name] = c
        return c

    def _track(self, name: str):
        router = self

        class _Tracker:
            def __enter__(self):
                with router._lock:
                    router._inflight[name] = \
                        router._inflight.get(name, 0) + 1

            def __exit__(self, *exc):
                with router._lock:
                    router._inflight[name] = \
                        max(router._inflight.get(name, 1) - 1, 0)

        return _Tracker()

    def _views(self):
        views = self.replicaset.views()
        with self._lock:
            for v in views:
                v.inflight = self._inflight.get(v.name, 0)
        return views

    def _count(self, outcome: str):
        m = obs.metrics()
        if m is not None:
            m.inc(labeled("dnn_tpu_router_requests_total",
                          outcome=outcome))

    def _budget(self, rid: str) -> float:
        """The forward's total budget: a caller-supplied `dl=` tag is
        trusted AS-IS (the client re-tags remaining budget per attempt
        — clamping it would silently lower every explicit client
        deadline); only tagless requests get `default_deadline_s`."""
        inbound = _tx.extract_deadline(rid)
        return max(inbound if inbound is not None
                   else self.default_deadline_s, 0.001)

    # -- prefix-aware placement (dnn_tpu/kvtier) ------------------------

    def _kv_is_gen(self, rid: str, arr, need: str) -> bool:
        """Whether this request participates in prefix-aware placement
        (KV tier on, a decode-role gen forward with real tokens)."""
        if self._kvdir is None or need != "decode" or arr is None:
            return False
        rid_clean = _tx.strip_deadline(obs.strip_wire_tag(rid))
        return rid_clean.split(":")[0] == "gen"

    def _kv_replica_on(self, name: str) -> bool:
        """Scrape-evidenced: the replica exports kvtier residency, so
        it actually serves the radix store. Preferring a 'holder' (or
        instructing a pull onto a target) with no tier is pure loss —
        on a dense fleet the directory must never steer placement.
        Cached ~1 s: this runs up to twice per request and the views
        walk behind it costs a fleet-snapshot build."""
        now = time.monotonic()
        if now - self._kv_on_ts > 1.0:
            self._kv_on_names = {
                v.name for v in self._views()
                if v.kvtier_blocks is not None}
            self._kv_on_ts = now
        return name in self._kv_on_names

    def _kv_locate(self, rid: str, arr, need: str):
        """-> (prefer_replica or None, PrefixLocation or None) for a
        gen request when the KV tier is on. "auto" prefers the holder
        (placement follows the blocks); "pull" never does (placement
        follows the policy, the blocks follow the placement)."""
        if not self._kv_is_gen(rid, arr, need):
            return None, None
        loc = self._kvdir.locate(arr)
        if loc is None:
            return None, None
        prefer = (loc.replica if self._kvtier == "auto"
                  and self._kv_replica_on(loc.replica) else None)
        return prefer, loc

    async def _kv_maybe_pull(self, target: ReplicaHandle, arr, loc,
                             remaining: float):
        """Instruct `target` to pull `loc`'s blocks from their holder
        before the gen forward lands — ADVISORY end to end: a failed
        pull only costs the optimization (the replica re-prefills),
        recorded loud either way."""
        if loc is None or loc.replica == target.name:
            return
        donor = self.replicaset.replicas.get(loc.replica)
        if donor is None or donor.state not in ("serving", "draining"):
            return
        if not self._kv_replica_on(target.name):
            # the target has no radix store — a pull could only fail
            # (and on a dense fleet this path must cost nothing)
            return
        m = obs.metrics()
        try:
            with self._track(target.name):
                status = await asyncio.to_thread(
                    self._client(target).kv_pull_from, donor.address,
                    np.asarray(arr, np.int32)[
                        : loc.n_blocks * self._kvdir.block_len],
                    timeout=max(min(remaining,
                                    self.kv_pull_timeout_s), 0.5))
            if m is not None:
                m.inc("dnn_tpu_router_kvtier_pulls_total")
            if "kvtier_fallback" in (status or ""):
                obs.flight.record("kvtier_pull_fallback",
                                  target=target.name, donor=donor.name,
                                  detail=str(status)[:160])
        except Exception as e:  # noqa: BLE001 — advisory by contract
            obs.flight.record("kvtier_pull_failed", target=target.name,
                              donor=loc.replica,
                              error=f"{type(e).__name__}: {e}"[:160])

    def _kv_observe(self, arr, replica_name: str):
        if self._kvdir is not None and arr is not None:
            self._kvdir.observe(arr, replica_name)

    def _wants_disagg(self, rid_clean: str) -> bool:
        """gen requests take the prefill->decode handoff — except when
        the client already carries a handle (`h=`), or rides a LoRA
        adapter (`a=`: the decode-side `submit(prefilled=)` adoption
        rejects adapters, so those take the plain single-replica
        forward)."""
        if self.disagg == "off":
            return False
        segs = rid_clean.split(":")
        return segs[0] == "gen" and not any(
            s.startswith(("h=", "a=")) for s in segs)

    # -- admission + pick ----------------------------------------------

    def _admit(self, need: str, sticky: Optional[str],
               excluded: Set[str],
               prefer: Optional[str] = None) -> ReplicaHandle:
        """One admission decision: shed (raises _Shed) or the picked
        replica handle. Policy sees only routable candidates (serving,
        role-compatible, not excluded, below the inflight bound).
        `prefer` (prefix-aware placement, dnn_tpu/kvtier): route to
        this replica when it is routable — the directory says it holds
        the request's prefix blocks; overridden by dedup-key affinity
        (a `d=` join MUST land where the original runs)."""
        cands = [v for v in self._views()
                 if v.state == "serving" and v.name not in excluded
                 and _role_ok(v.role, need)]
        reason = shed_reason(cands, max_inflight=self.max_inflight,
                             shed_burn=self.shed_burn)
        if reason is not None:
            raise _Shed(reason)
        routable = [v for v in cands if v.inflight < self.max_inflight]
        names = {v.name for v in routable}
        pick = None
        if sticky is not None:
            bound = self._affinity.get(sticky)
            if bound in names:
                pick = bound
                self._affinity.move_to_end(sticky)
        if pick is None and prefer is not None and prefer in names:
            m = obs.metrics()
            if m is not None:
                m.inc("dnn_tpu_router_kvtier_route_hits_total")
            pick = prefer
        if pick is None:
            pick = self.policy.pick(routable).name
            if sticky is not None:
                self._affinity[sticky] = pick
                self._affinity.move_to_end(sticky)
                while len(self._affinity) > self._affinity_cap:
                    self._affinity.popitem(last=False)
        self._note_admitted()
        return self.replicaset.replicas[pick]

    def _disagg_active(self) -> bool:
        if self.disagg == "off":
            return False
        views = [v for v in self._views() if v.state == "serving"]
        split = (any(v.role == "prefill" for v in views)
                 and any(_role_ok(v.role, "decode") for v in views))
        if self.disagg == "on" and not split:
            raise _Shed("disagg_unsatisfiable")
        return split

    # -- the unary forward core ----------------------------------------

    async def _forward_unary(self, arr, rid: str, context, *,
                             need: str = "decode",
                             pinned: Optional[ReplicaHandle] = None,
                             sticky: Optional[str] = None,
                             fallback_rid: Optional[str] = None):
        """Route one unary request: admission, policy pick (or the
        `pinned` replica — the disagg path already placed the KV),
        deadline-capped forward, sibling retry on UNAVAILABLE. A
        caller-supplied `dl=` budget is trusted as-is (the client
        already re-tags remaining budget per attempt); only tagless
        requests get `default_deadline_s`. `fallback_rid` is the
        disagg path's escape hatch: the router-minted `h=` handle is
        staged ONLY on the pinned replica, so if that forward fails
        the retry loop reverts to the plain rid (decode-side prefill)
        instead of offering siblings a handle they never saw."""
        budget = self._budget(rid)
        t0 = time.monotonic()
        if sticky is None:
            sticky = _affinity_key(rid)
        excluded: Set[str] = set()
        attempts = self.retry_siblings + 1
        last = "no replica attempted"
        kv_gen = self._kv_is_gen(rid, arr, need)
        kv_prefer, kv_loc = self._kv_locate(rid, arr, need) if kv_gen \
            else (None, None)

        def _revert_to_plain():
            # fall back LOUD to plain decode-side prefill — same
            # counter/event as a handoff-leg failure
            nonlocal rid, sticky, fallback_rid
            m = obs.metrics()
            if m is not None:
                m.inc("dnn_tpu_router_handoff_fallback_total")
            obs.flight.record("handoff_fallback", error=last[:200])
            rid = fallback_rid
            sticky = _affinity_key(rid)
            fallback_rid = None

        for _ in range(attempts):
            remaining = budget - (time.monotonic() - t0)
            if remaining <= 0:
                self._count("deadline")
                await context.abort(
                    grpc.StatusCode.DEADLINE_EXCEEDED,
                    f"router budget {budget:.1f}s exhausted ({last})")
            was_pinned = pinned is not None
            if pinned is not None:
                target = pinned
                pinned = None  # a failed pinned forward falls back to
                # the ordinary pick on the next attempt
            else:
                try:
                    target = self._admit(need, sticky, excluded,
                                         prefer=kv_prefer)
                except _Shed as s:
                    self._note_shed(s.args[0])
                    await context.abort(
                        grpc.StatusCode.UNAVAILABLE,
                        f"router shedding: {s.args[0]}")
            if kv_loc is not None and target.name != kv_loc.replica:
                # placement went somewhere the blocks are NOT (holder
                # saturated/dead on "auto", policy pick on "pull"):
                # instruct the migration before the forward, once
                await self._kv_maybe_pull(target, arr, kv_loc,
                                          remaining)
                kv_loc = None
            client = self._client(target)
            try:
                # capacity signal: inflight BEFORE this dispatch — a
                # commit that rode a free slot is pure service time,
                # one that queued behind a full batch is not, and the
                # caplens planner must not learn the queue it simulates
                infl0 = self._inflight.get(target.name, 0)
                t_fwd = time.monotonic()
                with self._track(target.name):
                    status, result = await asyncio.to_thread(
                        client.send_tensor, arr, request_id=rid,
                        timeout=max(remaining, 0.001), retries=0)
                self._count("ok")
                lens = self.caplens
                if lens is not None:
                    lens.on_commit(
                        target.name, role=target.role,
                        tokens=int(result.size)
                        if result is not None else 0,
                        wall_s=time.monotonic() - t_fwd,
                        inflight_at_dispatch=infl0)
                if kv_gen:
                    # feed the directory: this replica now holds the
                    # prompt's blocks (admission inserted the path)
                    self._kv_observe(arr, target.name)
                if result is None:
                    return wc.TensorResponse(status=status)
                return wc.TensorResponse(
                    status=status, result_tensor=_tensor_msg(result))
            except grpc.RpcError as e:
                code = e.code() if hasattr(e, "code") else None
                if was_pinned and fallback_rid is not None \
                        and code != grpc.StatusCode.DEADLINE_EXCEEDED:
                    # the pinned (handle-tagged) forward failed —
                    # drain, breaker, or the decode replica REJECTING
                    # the adoption (adapter/speculative/consumed
                    # handle). Exclude the replica only when its
                    # health, not the handle, was the problem.
                    last = f"{target.name}: {code} (handoff)"
                    if code in _SIBLING_RETRIABLE:
                        excluded.add(target.name)
                    _revert_to_plain()
                    continue
                if code in _SIBLING_RETRIABLE:
                    # draining / dead / refusing replica: its queued
                    # work was handed back retriable — a SIBLING picks
                    # it up without the client ever seeing the drain
                    excluded.add(target.name)
                    if sticky is not None:
                        self._affinity.pop(sticky, None)
                    last = f"{target.name}: {code}"
                    obs.flight.record("router_retry_sibling",
                                      replica=target.name,
                                      code=str(code))
                    continue
                if code == grpc.StatusCode.DEADLINE_EXCEEDED:
                    self._count("deadline")
                else:
                    self._count("error")
                await context.abort(
                    code or grpc.StatusCode.UNKNOWN,
                    e.details() if hasattr(e, "details")
                    else str(e))
            except PayloadCorruptError as e:
                excluded.add(target.name)
                last = f"{target.name}: payload corrupt ({e})"
                if was_pinned and fallback_rid is not None:
                    _revert_to_plain()
                continue
            except Exception as e:  # noqa: BLE001 — breaker-open and
                # connect-level failures: try a sibling
                excluded.add(target.name)
                if sticky is not None:
                    self._affinity.pop(sticky, None)
                last = f"{target.name}: {type(e).__name__}: {e}"
                if was_pinned and fallback_rid is not None:
                    _revert_to_plain()
                continue
        self._count("unroutable")
        await context.abort(
            grpc.StatusCode.UNAVAILABLE,
            f"no replica could serve the request (last: {last[:200]})")

    # -- disaggregated prefill/decode ----------------------------------

    async def _disagg_blocks(self, arr, rid: str, context,
                             budget: float):
        """Block-migration disaggregation (dnn_tpu/kvtier): the
        prefill replica STAGES the prompt's blocks into its radix
        store, the decode replica PULLS them over the lease rungs, and
        the generate forwards PLAIN — admission adopts the blocks from
        its own store, no single-use handle, and a warm decode replica
        pulls only what it is missing (zero bytes for a shared system
        prompt it has seen before — the thing the packed-row handoff
        re-shipped on every request). Returns the response, or None to
        fall back to the row-pack handoff (recorded loud). _Shed
        propagates to the caller's abort."""
        # precondition, SILENT: without scrape evidence of a radix
        # store on a serving prefill-capable replica, this fleet is a
        # PR 12 row-handoff fleet — skipping without a flight event
        # per request (not a failure, just not applicable)
        if not any(v.state == "serving"
                   and v.kvtier_blocks is not None
                   and _role_ok(v.role, "prefill")
                   for v in self._views()):
            return None
        m = obs.metrics()
        try:
            pre = self._admit("prefill", None, set())
            t_h = time.perf_counter()
            with self._track(pre.name):
                await asyncio.to_thread(
                    self._client(pre).kv_stage, arr,
                    timeout=max(budget / 2, 1.0))
            dec = self._admit("decode", _affinity_key(rid), set())
            with self._track(dec.name):
                pull_status = await asyncio.to_thread(
                    self._client(dec).kv_pull_from, pre.address, arr,
                    timeout=max(budget / 2, 1.0))
            if "kvtier_fallback" in (pull_status or ""):
                raise RuntimeError(
                    f"pull degraded: {str(pull_status)[:160]}")
            dt = time.perf_counter() - t_h
            if m is not None:
                m.observe("dnn_tpu_router_handoff_seconds", dt)
                m.inc("dnn_tpu_router_kvtier_pulls_total")
            obs.flight.record("kv_handoff", prefill=pre.name,
                              decode=dec.name, mode="blocks",
                              ms=round(dt * 1e3, 2))
            self._kv_observe(arr, dec.name)
        except _Shed:
            raise
        except Exception as e:  # noqa: BLE001 — ANY block-leg failure
            # degrades to the row-pack handoff, recorded loud
            if m is not None:
                m.inc("dnn_tpu_kvtier_fallback_total")
            obs.flight.record("kvtier_fallback",
                              error=f"{type(e).__name__}: {e}"[:200])
            return None
        return await self._forward_unary(arr, rid, context, pinned=dec)

    async def _forward_disagg(self, arr, rid: str, context):
        """gen request on a role-split fleet: prefill replica computes
        the KV, decode replica adopts it, generate forwards with the
        handle. When the KV tier is live the BLOCK-migration path runs
        first (stage + pull — kvtier/migrate.py) and the packed-row
        handoff is its fallback. Any handoff-leg failure falls back
        LOUD (flight event + counter) to plain decode-side prefill —
        availability beats disaggregation."""
        m = obs.metrics()
        budget = self._budget(rid)
        if self._kvdir is not None:
            try:
                resp = await self._disagg_blocks(arr, rid, context,
                                                 budget)
            except _Shed as s:
                self._note_shed(s.args[0])
                await context.abort(grpc.StatusCode.UNAVAILABLE,
                                    f"router shedding: {s.args[0]}")
            if resp is not None:
                return resp
        try:
            pre = self._admit("prefill", None, set())
            t_h = time.perf_counter()
            with self._track(pre.name):
                payload = await asyncio.to_thread(
                    self._client(pre).prefill_kv, arr,
                    timeout=max(budget / 2, 1.0))
            handle = f"rt{next(self._handle_seq)}"
            dec = self._admit("decode", _affinity_key(rid), set())
            with self._track(dec.name):
                await asyncio.to_thread(
                    self._client(dec).put_kv, handle, payload,
                    timeout=max(budget / 2, 1.0))
            dt = time.perf_counter() - t_h
            if m is not None:
                m.inc("dnn_tpu_router_handoff_bytes_total",
                      int(payload.size))
                m.observe("dnn_tpu_router_handoff_seconds", dt)
            obs.flight.record("kv_handoff", prefill=pre.name,
                              decode=dec.name, bytes=int(payload.size),
                              ms=round(dt * 1e3, 2))
        except _Shed as s:
            self._note_shed(s.args[0])
            await context.abort(grpc.StatusCode.UNAVAILABLE,
                                f"router shedding: {s.args[0]}")
        except Exception as e:  # noqa: BLE001 — ANY handoff failure
            # degrades to decode-side prefill, recorded loud
            if m is not None:
                m.inc("dnn_tpu_router_handoff_fallback_total")
            obs.flight.record("handoff_fallback",
                              error=f"{type(e).__name__}: {e}"[:200])
            return await self._forward_unary(arr, rid, context)
        return await self._forward_unary(
            arr, f"{rid}:h={handle}", context, pinned=dec,
            fallback_rid=rid)

    # --- RPC implementations (wire names fixed by the protocol) --------

    async def SendTensor(self, request: pb.TensorRequest,
                         context) -> pb.TensorResponse:
        if self._draining:
            self._count("draining")
            await context.abort(
                grpc.StatusCode.UNAVAILABLE,
                "router draining: retry against another front door")
        try:
            arr = _tensor_arr(request.tensor)
        except PayloadCorruptError as e:
            await context.abort(grpc.StatusCode.DATA_LOSS, str(e))
        rid = request.request_id or ""
        rid_clean = _tx.strip_deadline(obs.strip_wire_tag(rid))
        lens = self.caplens
        if lens is not None:
            lens.on_arrival(arr.size if arr is not None else 0,
                            scenario=rid_clean.split(":", 1)[0]
                            or "other")
        if rid_clean == "prefill" or rid_clean.startswith("prefill:"):
            return await self._forward_unary(arr, rid, context,
                                             need="prefill")
        if rid_clean.startswith("kvput:"):
            # client-driven kvput-then-generate: bind the handle key
            # NOW so the upcoming `h=<key>` generate re-routes to the
            # replica that staged it
            key = rid_clean.split(":", 1)[1]
            return await self._forward_unary(arr, rid, context,
                                             sticky=f"h={key}")
        if self._wants_disagg(rid_clean):
            try:
                disagg = self._disagg_active()
            except _Shed as s:
                self._note_shed(s.args[0])
                await context.abort(grpc.StatusCode.UNAVAILABLE,
                                    f"router shedding: {s.args[0]}")
            if disagg:
                return await self._forward_disagg(arr, rid, context)
        return await self._forward_unary(arr, rid, context)

    async def GenerateStream(self, request: pb.TensorRequest, context):
        """Streaming passthrough: one upstream replica stream, tokens
        relayed as they arrive. NOT sibling-retried (a stream is
        stateful — tokens already delivered) and never disaggregated
        (the handoff is a pre-admission hop; streams keep the simple
        path — README documents the caveat)."""
        if self._draining:
            await context.abort(grpc.StatusCode.UNAVAILABLE,
                                "router draining")
        try:
            arr = _tensor_arr(request.tensor)
        except PayloadCorruptError as e:
            await context.abort(grpc.StatusCode.DATA_LOSS, str(e))
        rid = request.request_id or ""
        lens = self.caplens
        if lens is not None:
            lens.on_arrival(arr.size if arr is not None else 0,
                            scenario="stream")
        budget = self._budget(rid)
        kv_gen = self._kv_is_gen(rid, arr, "decode")
        kv_prefer, kv_loc = self._kv_locate(rid, arr, "decode") \
            if kv_gen else (None, None)
        try:
            target = self._admit("decode", _affinity_key(rid), set(),
                                 prefer=kv_prefer)
        except _Shed as s:
            self._note_shed(s.args[0])
            await context.abort(grpc.StatusCode.UNAVAILABLE,
                                f"router shedding: {s.args[0]}")
        if kv_loc is not None and target.name != kv_loc.replica:
            await self._kv_maybe_pull(target, arr, kv_loc, budget)
        if kv_gen:
            self._kv_observe(arr, target.name)
        client = self._client(target)
        loop = asyncio.get_running_loop()
        q: "asyncio.Queue" = asyncio.Queue()
        stop = threading.Event()

        def pump():
            with self._track(target.name):
                try:
                    for resp in client.send_tensor_stream(
                            arr, request_id=rid, timeout=budget):
                        loop.call_soon_threadsafe(
                            q.put_nowait, ("resp", resp))
                        if stop.is_set():
                            break
                    loop.call_soon_threadsafe(q.put_nowait,
                                              ("done", None))
                except BaseException as e:  # noqa: BLE001 — surfaced
                    loop.call_soon_threadsafe(q.put_nowait, ("err", e))

        infl0 = self._inflight.get(target.name, 0)
        t_fwd = time.monotonic()
        n_resp = 0
        threading.Thread(target=pump, daemon=True,
                         name="router-stream-pump").start()
        try:
            while True:
                kind, val = await q.get()
                if kind == "resp":
                    n_resp += 1
                    yield val
                elif kind == "done":
                    self._count("ok")
                    if lens is not None:
                        lens.on_commit(
                            target.name, role=target.role,
                            tokens=n_resp,
                            wall_s=time.monotonic() - t_fwd,
                            inflight_at_dispatch=infl0)
                    return
                else:
                    self._count("error")
                    if isinstance(val, grpc.RpcError):
                        await context.abort(
                            val.code() or grpc.StatusCode.UNKNOWN,
                            val.details() if hasattr(val, "details")
                            else str(val))
                    await context.abort(grpc.StatusCode.UNAVAILABLE,
                                        str(val)[:200])
        finally:
            stop.set()  # client went away: the pump breaks at its next
            # token and its generator's finally cancels the upstream RPC

    async def HealthCheck(self, request: pb.Empty,
                          context) -> pb.HealthCheckResponse:
        healthy = (not self._draining
                   and bool(self.replicaset.serving()))
        return pb.HealthCheckResponse(is_healthy=healthy)

    async def SendMessage(self, request: pb.MessageRequest,
                          context) -> pb.MessageReply:
        """Hellos declined (the router fronts the grpc rung); "!stats"
        answers the router's own view; any other text forwards to a
        decode replica (the tokenizer text front, routed)."""
        if request.sender_id.startswith(_tx.HELLO_SENDER):
            return pb.MessageReply(
                confirmation_text=_tx.decline_hello(
                    "router fronts the grpc rung"))
        if request.message_text == "!stats":
            views = self._views()
            with self._lock:
                state = self._state
            return pb.MessageReply(confirmation_text=(
                f"[router] state={state} policy={self.policy.name} "
                f"replicas="
                + ",".join(f"{v.name}:{v.state}:{v.role}"
                           f"(inflight={v.inflight})" for v in views)
                + f" shed_total={self.shed_total}"))
        if self._draining:
            await context.abort(grpc.StatusCode.UNAVAILABLE,
                                "router draining")
        try:
            target = self._admit("decode",
                                 _affinity_key(request.sender_id), set())
        except _Shed as s:
            self._note_shed(s.args[0])
            await context.abort(grpc.StatusCode.UNAVAILABLE,
                                f"router shedding: {s.args[0]}")
        client = self._client(target)
        with self._track(target.name):
            reply = await asyncio.to_thread(
                client.send_message, request.sender_id,
                request.message_text, self.default_deadline_s)
        return pb.MessageReply(confirmation_text=reply)

    # -- obs endpoint --------------------------------------------------

    def statusz(self) -> dict:
        """The router's /statusz: its own state plus one component per
        replica (lifecycle state + role) — the FleetCollector treats
        the router as a first-class target off this shape."""
        with self._lock:
            state = self._state
        as_watchdog = {"init": "degraded", "serving": "ok",
                       "shedding": "degraded", "draining": "draining",
                       "stopped": "wedged"}[state]
        comps = {}
        for r in self.replicaset.replicas.values():
            comps[r.name] = {
                "state": {"serving": "ok", "idle": "degraded",
                          "warming": "degraded",
                          "draining": "degraded"}.get(r.state, "wedged"),
                "detail": f"replica state={r.state} role={r.role} "
                          f"addr={r.address}",
                "role": r.role,
            }
        return {"state": as_watchdog, "router_state": state,
                "role": "router", "policy": self.policy.name,
                "components": comps}


async def serve_router(replicaset: ReplicaSet, *, port: int,
                       metrics_port: Optional[int] = None,
                       **router_kwargs) -> int:
    """Serve the front door and block until termination — the router
    analog of `serve_lm`. SIGTERM drains (admission closes UNAVAILABLE,
    in-flight forwards finish) and exits 0."""
    import signal

    router = Router(replicaset, **router_kwargs)
    srv = None
    if metrics_port is not None:
        srv = obs.serve_metrics(
            metrics_port, status=router.statusz,
            fleet=replicaset.collector,
            caplens=router.caplens,
            healthy=lambda: not router._draining
            and bool(replicaset.serving()))
    server = grpc.aio.server(options=_tx.GRPC_MSG_OPTIONS)
    server.add_generic_rpc_handlers((_handlers(router),))
    if server.add_insecure_port(f"[::]:{port}") == 0:
        raise RuntimeError(f"failed to bind router to [::]:{port}")
    await server.start()
    _size_forward_executor(asyncio.get_running_loop(), router)
    router.start()
    log.info("router listening on [::]:%d (%d replicas, policy=%s)",
             port, len(replicaset.replicas),
             router.policy.name)
    drained = asyncio.Event()
    loop = asyncio.get_running_loop()

    def _on_sigterm():
        log.info("SIGTERM: router draining")
        router.drain()
        loop.call_soon_threadsafe(drained.set)

    try:
        loop.add_signal_handler(signal.SIGTERM, _on_sigterm)
    except (NotImplementedError, ValueError, RuntimeError):
        pass
    term = asyncio.ensure_future(server.wait_for_termination())
    drain_w = asyncio.ensure_future(drained.wait())
    try:
        await asyncio.wait({term, drain_w},
                           return_when=asyncio.FIRST_COMPLETED)
        return 0
    finally:
        try:
            await server.stop(grace=1)
        except asyncio.CancelledError:
            pass
        for t in (term, drain_w):
            if not t.done():
                t.cancel()
            try:
                await t
            except BaseException:  # noqa: BLE001 — reaped, not consulted
                pass
        router.close()
        if srv is not None:
            srv.close()


def start_router_in_background(replicaset: ReplicaSet, *, port: int,
                               **router_kwargs):
    """Test helper: router on a daemon thread; returns
    (router, stop_callback) — mirrors start_lm_server_in_background."""
    loop = asyncio.new_event_loop()
    started = threading.Event()
    state: dict = {}

    async def _run():
        try:
            router = Router(replicaset, **router_kwargs)
            server = grpc.aio.server(options=_tx.GRPC_MSG_OPTIONS)
            server.add_generic_rpc_handlers((_handlers(router),))
            if server.add_insecure_port(f"[::]:{port}") == 0:
                raise RuntimeError(f"failed to bind router to :{port}")
            await server.start()
            _size_forward_executor(asyncio.get_running_loop(), router)
            router.start()
            state["router"], state["server"] = router, server
            state["done"] = asyncio.Event()
        except BaseException as e:
            state["error"] = e
            raise
        finally:
            started.set()
        await state["done"].wait()
        await asyncio.sleep(0.05)

    def _main():
        asyncio.set_event_loop(loop)
        try:
            loop.run_until_complete(_run())
        except BaseException:
            if "error" not in state:
                raise

    t = threading.Thread(target=_main, daemon=True)
    t.start()
    if not started.wait(timeout=30):
        raise RuntimeError("router failed to start")
    if "error" in state:
        t.join(timeout=5)
        raise RuntimeError(
            f"router failed to start: {state['error']}") \
            from state["error"]

    def stop():
        async def _stop():
            await state["server"].stop(grace=0.2)
            state["done"].set()

        asyncio.run_coroutine_threadsafe(_stop(), loop).result(timeout=10)
        state["router"].close()
        t.join(timeout=5)

    stop.router = state["router"]
    return state["router"], stop
