"""caplens: the capacity observatory for the elastic fleet.

PR 12's router emits `dnn_tpu_wanted_replicas` and nothing consumes
it; ROADMAP item 3 (demand-matched capacity) is the last pillar with
no instrument. The repo's proven sequence — StepClock before overlap,
kvlens before the hierarchical tier, trainlens before training at
scale — says the autoscaler must be judged by an observatory built
first. This module is that observatory, three instruments in one
object:

  1. **Demand estimator.** The router's admission seam feeds every
     arrival (monotonic stamp, prefill tokens, scenario tag from the
     request id) into a seed-pinned bounded ring; commits feed a
     second ring of delivered tokens. Scrape-side `demand()` derives
     the windowed arrival rate, burstiness (index of dispersion and
     peak-to-mean over per-second buckets — the PR 13 diurnal/bursty
     envelopes show up here), per-scenario token demand, and a
     change-point flag (recent-half vs prior-half rate ratio).

  2. **Learned per-replica capacity + cold-start ledger.** Committed
     forwards teach per-role service-time reservoirs — a sample is
     admitted to the PLANNING reservoir only when the replica had a
     free slot at dispatch (`inflight_at_dispatch < slots`), so the
     learned distribution is service, not service-plus-queue — and a
     per-replica delivered-tokens/s EMA. Each replica spawn opens a
     ledger entry; `spawn_ready` and the first committed token close
     it, attributing the spawn->first-token wall into process-start /
     weight-load / compile / warmup buckets using the child's boot
     gauges (`dnn_tpu_boot_*_seconds`, node.py) and the existing
     compile-telemetry counter (`jax_compile_seconds_total`,
     obs/compile_watch). Buckets are measured INDEPENDENTLY — the
     ledger reports the coverage fraction they explain rather than
     defining a residual bucket to claim 100% — and each finalized
     spawn is a `coldstart` flight event.

  3. **What-if planner + audited wanted-replicas v2.** `plan(n)`
     deterministically replays the recorded arrival ring through a
     discrete-event simulation of n replicas (slots-per-replica
     servers, the router's n*max_inflight admission bound, service
     times drawn from the learned reservoir by seed-pinned inverse
     CDF — same ring => bit-identical verdict), pricing cold-start
     debt as a not-yet-free interval on cold replicas' slots. It
     predicts availability (admitted AND inside the deadline),
     queue-wait and TTFT quantiles, and shed fraction at n replicas.
     `wanted_replicas(n_live)` is the smallest n whose predicted SLO
     holds; every transition records its full decision inputs (demand
     window, capacity estimates, per-n verdicts, SLO margins) in a
     bounded audit trail and as a `caplens_decision` flight event.
     Served on `/capz` (JSON | `?format=prom`), as `/fleetz` rollup
     columns, and via `python -m dnn_tpu.obs caplens
     [--url|PATH|--selftest]`.

Overhead contract: every producer opens with the obs gate check and
the router/replicaset hook sites guard with one `lens is not None`
test; producers append to bounded deques and bump counters — all
derivation (windowing, quantiles, planning) is scrape-side, and
planning is additionally throttled by `replan_interval_s`.

Threading: producers run on the router's event loop and the
replicaset monitor thread; scrape-side readers copy bounded deques
and load ints/floats — the same tolerance every serving gauge lives
with (kvlens contract).
"""

from __future__ import annotations

import hashlib
import heapq
import time
import weakref
from collections import deque
from typing import Callable, Dict, List, Optional

from dnn_tpu.obs.flight import FlightRecorder
from dnn_tpu.utils.metrics import labeled

__all__ = ["CapLens", "CapSLO", "MIN_RING", "MIN_SERVICE"]

# the planner refuses to plan (wanted_replicas returns None -> v1
# heuristic fallback) below these floors: a verdict replayed from a
# handful of arrivals is noise wearing a confidence interval
MIN_RING = 16
MIN_SERVICE = 8

_ROLES = ("prefill", "decode", "both")

_obs = None  # lazy: breaks the obs<->caplens import cycle (flight idiom)


def _enabled() -> bool:
    global _obs
    if _obs is None:
        from dnn_tpu import obs as _o

        _obs = _o
    return _obs.enabled()


def _q(sorted_vals: List[float], frac: float) -> Optional[float]:
    if not sorted_vals:
        return None
    i = min(int(frac * len(sorted_vals)), len(sorted_vals) - 1)
    return sorted_vals[i]


class CapSLO:
    """The serving objective the planner sizes against."""

    def __init__(self, availability: float = 0.99,
                 wait_p95_s: Optional[float] = None):
        self.availability = float(availability)
        self.wait_p95_s = None if wait_p95_s is None else float(wait_p95_s)

    def as_dict(self) -> dict:
        return {"availability": self.availability,
                "wait_p95_s": self.wait_p95_s}


class CapLens:
    """One lens per Router. See module docstring."""

    def __init__(self, *, slots_per_replica: int = 4,
                 max_inflight: int = 8,
                 deadline_s: float = 30.0,
                 seed: int = 0,
                 window_s: float = 60.0,
                 ring_cap: int = 4096,
                 service_cap: int = 512,
                 ledger_cap: int = 256,
                 max_replicas: int = 8,
                 slo: Optional[CapSLO] = None,
                 coldstart_default_s: float = 20.0,
                 replan_interval_s: float = 1.0,
                 settle_s: float = 2.0,
                 now=time.monotonic,
                 signals: Optional[Callable[[str], dict]] = None):
        self.slots_per_replica = max(1, int(slots_per_replica))
        self.max_inflight = max(1, int(max_inflight))
        self.deadline_s = float(deadline_s)
        self.seed = int(seed)
        self.window_s = float(window_s)
        self.max_replicas = max(1, int(max_replicas))
        self.slo = slo if slo is not None else CapSLO()
        self.coldstart_default_s = float(coldstart_default_s)
        self.replan_interval_s = float(replan_interval_s)
        # a committed spawn's buckets are computed this long after the
        # first token, so the 1 s fleet scrape has flushed the child's
        # compile counter for the first (compiling) request
        self.settle_s = float(settle_s)
        self._now = now
        self._signals = signals
        self._prefix = f"caplens:{self.seed}:"
        # demand: bounded arrival/commit rings (producers append only)
        self._ring: "deque[tuple]" = deque(maxlen=int(ring_cap))
        self._commits: "deque[tuple]" = deque(maxlen=int(ring_cap))
        self.arrivals_total = 0
        self.prefill_tokens_total = 0
        self.committed_tokens_total = 0
        self.commits_total = 0
        self.sheds_by_reason: Dict[str, int] = {}
        self._scenarios: Dict[str, list] = {}  # name -> [count, tokens]
        # capacity: per-role service reservoirs (bounded, deterministic
        # ring-replacement so the same commit sequence always leaves
        # the same reservoir) + per-replica tokens/s EMA
        self._svc_cap = max(MIN_SERVICE, int(service_cap))
        self._svc: Dict[str, list] = {r: [] for r in _ROLES}
        self._svc_n: Dict[str, int] = {r: 0 for r in _ROLES}
        self._svc_all: List[float] = []
        self._svc_all_n = 0
        self._tps_ema: Dict[str, float] = {}
        self._queued_commits = 0  # samples kept out of the planning set
        # cold-start ledger: name -> open entry; finalized ring
        self._pending: Dict[str, dict] = {}
        self._finalized: "deque[dict]" = deque(maxlen=64)
        self.spawns_total = 0
        self.ledger = FlightRecorder(ledger_cap)
        # planner cache + audit trail
        self._plan_cache: Dict[int, dict] = {}
        self._plan_cache_key = None
        self._wanted_last: Optional[int] = None
        self._wanted_ts = 0.0
        self._audit: "deque[dict]" = deque(maxlen=64)

    # -- deterministic randomness (chaos-planner idiom) ----------------

    def _uniform(self, name: str, i: int) -> float:
        h = hashlib.blake2s(f"{self._prefix}{name}:{i}".encode(),
                            digest_size=8).digest()
        return int.from_bytes(h, "big") / 2.0 ** 64

    # -- producers (router event loop / replicaset monitor) ------------

    def on_arrival(self, prefill_tokens: int, scenario: str = "other",
                   now: Optional[float] = None):
        """One request hit the router's front door (pre-admission)."""
        if not _enabled():
            return
        t = self._now() if now is None else now
        tok = max(0, int(prefill_tokens))
        self._ring.append((t, tok, scenario))
        self.arrivals_total += 1
        self.prefill_tokens_total += tok
        s = self._scenarios.get(scenario)
        if s is None:
            if len(self._scenarios) < 64:
                self._scenarios[scenario] = [1, tok]
        else:
            s[0] += 1
            s[1] += tok

    def on_shed(self, reason: str):
        if not _enabled():
            return
        self.sheds_by_reason[reason] = \
            self.sheds_by_reason.get(reason, 0) + 1

    def on_commit(self, replica: str, role: str = "both", *,
                  tokens: int = 0, wall_s: float = 0.0,
                  inflight_at_dispatch: int = 0,
                  now: Optional[float] = None):
        """One forward committed on `replica`. `wall_s` is the router's
        dispatch->response wall; it is admitted to the PLANNING
        reservoir only when the replica had a free slot at dispatch
        (otherwise it prices replica-internal queueing into "service"
        and the sim double-counts the queue it simulates)."""
        if not _enabled():
            return
        t = self._now() if now is None else now
        tok = max(0, int(tokens))
        w = float(wall_s)
        self.commits_total += 1
        self.committed_tokens_total += tok
        self._commits.append((t, tok))
        role = role if role in _ROLES else "both"
        if w > 0.0:
            if int(inflight_at_dispatch) < self.slots_per_replica:
                self._res_push(self._svc, self._svc_n, role, w)
            else:
                self._queued_commits += 1
            i = self._svc_all_n % self._svc_cap
            if len(self._svc_all) <= i:
                self._svc_all.append(w)
            else:
                self._svc_all[i] = w
            self._svc_all_n += 1
            if tok > 0:
                tps = tok / w
                prev = self._tps_ema.get(replica)
                self._tps_ema[replica] = tps if prev is None \
                    else 0.2 * tps + 0.8 * prev
        ent = self._pending.get(replica)
        if ent is not None and ent.get("t_first") is None:
            ent["t_first"] = t
            ent["first_wall_s"] = w

    def _res_push(self, res: Dict[str, list], counts: Dict[str, int],
                  role: str, v: float):
        i = counts[role] % self._svc_cap
        lst = res[role]
        if len(lst) <= i:
            lst.append(v)
        else:
            lst[i] = v
        counts[role] += 1

    # cold-start ledger producers (replicaset lifecycle seams)

    def spawn_begin(self, name: str, role: str = "both",
                    now: Optional[float] = None):
        if not _enabled():
            return
        t = self._now() if now is None else now
        self.spawns_total += 1
        self._pending[name] = {"replica": name, "role": role,
                               "t_spawn": t, "t_ready": None,
                               "t_first": None, "first_wall_s": None}
        self.ledger.record("spawn_begin", replica=name, role=role)

    def spawn_ready(self, name: str, now: Optional[float] = None):
        if not _enabled():
            return
        t = self._now() if now is None else now
        ent = self._pending.get(name)
        if ent is not None and ent.get("t_ready") is None:
            ent["t_ready"] = t
            self.ledger.record("spawn_ready", replica=name,
                               spawn_to_ready_s=round(
                                   t - ent["t_spawn"], 3))

    def spawn_gone(self, name: str):
        """The replica died or drained before its first token: close
        the ledger entry unfinalized (a spawn that never served)."""
        if not _enabled():
            return
        ent = self._pending.pop(name, None)
        if ent is not None and ent.get("t_first") is None:
            self.ledger.record("spawn_abandoned", replica=name,
                               role=ent["role"])

    # -- cold-start attribution (scrape side) --------------------------

    def _signals_for(self, name: str) -> dict:
        if self._signals is None:
            return {}
        try:
            return self._signals(name) or {}
        except Exception:  # noqa: BLE001 — a scrape hiccup is not a
            return {}      # reason to drop a ledger entry

    def _maybe_finalize(self, now: float):
        """Commit->buckets, `settle_s` after the first token (so the
        periodic fleet scrape has flushed the child's compile counter
        for the first, compiling, request). Buckets:

          process_start  child's dnn_tpu_boot_imports_seconds gauge
                         (exec + interpreter + imports, from /proc)
          weight_load    child's dnn_tpu_boot_weight_load_seconds
                         (engine build + weight prepare wall, minus
                         compile seconds inside that span)
          compile        jax_compile_seconds_total at finalize (the
                         child is fresh: its whole counter is boot)
          warmup         post-ready wall to the first token, minus
                         the compile seconds that landed after ready

        Coverage = sum(buckets) / (t_first - t_spawn). What the sum
        honestly misses: fork->exec lag, the child's serve-bind span
        (grpc server construction), and the caller's poll gap before
        the first request."""
        done = []
        for name, ent in list(self._pending.items()):
            t_first = ent.get("t_first")
            if t_first is None or now - t_first < self.settle_s:
                continue
            sig = self._signals_for(name)
            t_spawn = ent["t_spawn"]
            t_ready = ent.get("t_ready")
            total = max(t_first - t_spawn, 1e-9)
            imports = float(sig.get("boot_imports_s") or 0.0)
            weight = float(sig.get("boot_weight_load_s") or 0.0)
            compile_s = float(sig.get("compile_seconds_total") or 0.0)
            pre = float(sig.get("boot_compile_preready_s") or 0.0)
            ready_total = float(sig.get("boot_ready_total_s") or 0.0)
            post_compile = max(0.0, compile_s - pre)
            if ready_total > 0.0:
                warm = max(0.0, total - ready_total - post_compile)
            elif t_ready is not None:
                warm = max(0.0, (t_first - t_ready) - post_compile)
            else:
                warm = 0.0
            buckets = {"process_start_s": round(imports, 3),
                       "weight_load_s": round(weight, 3),
                       "compile_s": round(compile_s, 3),
                       "warmup_s": round(warm, 3)}
            covered = imports + weight + compile_s + warm
            rec = {"replica": name, "role": ent["role"],
                   "total_s": round(total, 3),
                   "spawn_to_ready_s":
                       round(t_ready - t_spawn, 3)
                       if t_ready is not None else None,
                   "buckets": buckets,
                   "coverage": round(min(covered / total, 1.0), 4)}
            self._finalized.append(rec)
            self.ledger.record("coldstart", **{
                "replica": name, "role": ent["role"],
                "total_s": rec["total_s"],
                "coverage": rec["coverage"], **buckets})
            done.append(name)
        for name in done:
            self._pending.pop(name, None)

    def coldstart(self) -> dict:
        """Finalized-spawn distributions (the /capz coldstart block)."""
        self._maybe_finalize(self._now())
        ents = list(self._finalized)
        totals = sorted(e["total_s"] for e in ents)
        out = {"spawns": self.spawns_total,
               "finalized": len(ents),
               "pending": len(self._pending),
               "total_p50_s": _q(totals, 0.5),
               "total_p95_s": _q(totals, 0.95),
               "coverage_mean": round(
                   sum(e["coverage"] for e in ents) / len(ents), 4)
               if ents else None,
               "buckets_p50_s": {}, "entries": ents[-8:]}
        if ents:
            for b in ("process_start_s", "weight_load_s", "compile_s",
                      "warmup_s"):
                vals = sorted(e["buckets"][b] for e in ents)
                out["buckets_p50_s"][b] = _q(vals, 0.5)
        return out

    def coldstart_delay_s(self) -> float:
        """The planner's price for one cold replica (p50 observed
        spawn->first-token wall; the configured default until any
        spawn has finalized)."""
        self._maybe_finalize(self._now())
        totals = sorted(e["total_s"] for e in self._finalized)
        v = _q(totals, 0.5)
        return float(v) if v is not None else self.coldstart_default_s

    # -- demand (scrape side) ------------------------------------------

    def demand(self, now: Optional[float] = None) -> dict:
        t = self._now() if now is None else now
        lo = t - self.window_s
        win = [(a, tok, sc) for (a, tok, sc) in list(self._ring)
               if a >= lo]
        n = len(win)
        out = {"window_s": self.window_s, "arrivals": n,
               "arrivals_total": self.arrivals_total,
               "rate_hz": round(n / self.window_s, 4),
               "prefill_tokens_per_s": round(
                   sum(w[1] for w in win) / self.window_s, 2),
               "committed_tokens_per_s": round(
                   sum(tok for (a, tok) in list(self._commits)
                       if a >= lo) / self.window_s, 2),
               "index_of_dispersion": None, "peak_to_mean": None,
               "change_point": False, "scenarios": {
                   k: {"count": v[0], "prefill_tokens": v[1]}
                   for k, v in sorted(self._scenarios.items())}}
        if n >= 2:
            t0 = win[0][0]
            span = max(win[-1][0] - t0, 1e-9)
            nb = max(2, min(int(span) + 1, 120))
            buckets = [0] * nb
            for (a, _tok, _sc) in win:
                buckets[min(int((a - t0) / span * nb), nb - 1)] += 1
            mean = n / nb
            var = sum((b - mean) ** 2 for b in buckets) / nb
            out["index_of_dispersion"] = round(var / mean, 3)
            out["peak_to_mean"] = round(max(buckets) / mean, 3)
            mid = t0 + span / 2.0
            early = sum(1 for (a, _t, _s) in win if a < mid)
            late = n - early
            ratio = late / max(early, 1)
            out["rate_ratio_recent"] = round(ratio, 3)
            out["change_point"] = bool(ratio > 2.0 or ratio < 0.5)
        return out

    # -- capacity (scrape side) ----------------------------------------

    def _planning_services(self) -> List[float]:
        """The sorted service-time sample the sim draws from: the
        free-slot-at-dispatch reservoir, falling back to the
        unconditioned one while the conditioned set is too thin."""
        svc = [v for r in _ROLES for v in self._svc[r]]
        if len(svc) < MIN_SERVICE:
            svc = list(self._svc_all)
        return sorted(svc)

    def capacity(self) -> dict:
        per_role = {}
        for r in _ROLES:
            vals = sorted(self._svc[r])
            if vals:
                per_role[r] = {"samples": min(self._svc_n[r],
                                              self._svc_cap),
                               "service_p50_s": _q(vals, 0.5),
                               "service_p95_s": _q(vals, 0.95)}
        return {"slots_per_replica": self.slots_per_replica,
                "max_inflight_per_replica": self.max_inflight,
                "commits_total": self.commits_total,
                "queued_commits_excluded": self._queued_commits,
                "service_by_role": per_role,
                "tokens_per_s_by_replica": {
                    k: round(v, 2)
                    for k, v in sorted(self._tps_ema.items())},
                "coldstart_delay_s": round(self.coldstart_delay_s(), 3)}

    # -- the what-if planner -------------------------------------------

    def plan(self, n: int, warm: Optional[int] = None
             ) -> Optional[dict]:
        """Deterministically replay the recorded arrival ring against
        an n-replica fleet: n*slots servers (FIFO, earliest-free),
        the router's n*max_inflight admission bound (arrivals beyond
        it shed, exactly `shed_reason`'s saturation test), service
        times drawn from the learned reservoir by seed-pinned inverse
        CDF. Replicas beyond `warm` start cold: their slots are not
        free until the observed p50 spawn->first-token wall has
        elapsed. Same ring + reservoir + seed => bit-identical
        verdict. None until MIN_RING arrivals and MIN_SERVICE
        committed samples exist — a planner with no evidence defers
        to the v1 heuristic."""
        n = int(n)
        if n < 1:
            return None
        ring = list(self._ring)
        svc = self._planning_services()
        if len(ring) < MIN_RING or len(svc) < MIN_SERVICE:
            return None
        warm_n = n if warm is None else max(0, min(n, int(warm)))
        cold = n - warm_n
        cold_delay = self.coldstart_delay_s()
        t0 = ring[0][0]
        servers: List[float] = []
        for r in range(n):
            free0 = t0 if r < warm_n else t0 + cold_delay
            servers.extend([free0] * self.slots_per_replica)
        heapq.heapify(servers)
        bound = n * self.max_inflight
        active: List[float] = []  # in-system finish times
        m = len(svc)
        ok = shed = late = 0
        waits: List[float] = []
        walls: List[float] = []
        for i, (t, _tok, _sc) in enumerate(ring):
            while active and active[0] <= t:
                heapq.heappop(active)
            if len(active) >= bound:
                shed += 1
                continue
            s = svc[min(int(self._uniform("svc", i) * m), m - 1)]
            free = heapq.heappop(servers)
            start = max(t, free)
            finish = start + s
            heapq.heappush(servers, finish)
            heapq.heappush(active, finish)
            waits.append(start - t)
            walls.append(finish - t)
            if finish - t <= self.deadline_s:
                ok += 1
            else:
                late += 1
        total = len(ring)
        waits.sort()
        walls.sort()
        return {"n": n, "warm": warm_n, "cold": cold,
                "arrivals": total,
                "availability": round(ok / total, 4),
                "shed_frac": round(shed / total, 4),
                "deadline_frac": round(late / total, 4),
                "wait_p50_s": round(_q(waits, 0.5) or 0.0, 4),
                "wait_p95_s": round(_q(waits, 0.95) or 0.0, 4),
                "ttft_p95_s": round(_q(walls, 0.95) or 0.0, 4),
                "coldstart_debt_s": round(cold * cold_delay, 3),
                "service_samples": m}

    def _meets_slo(self, p: dict) -> bool:
        if p["availability"] < self.slo.availability:
            return False
        if self.slo.wait_p95_s is not None \
                and p["wait_p95_s"] > self.slo.wait_p95_s:
            return False
        return True

    def wanted_replicas(self, n_live: int = 0,
                        now: Optional[float] = None) -> Optional[int]:
        """Smallest n in 1..max_replicas whose predicted SLO holds
        (max_replicas when none does — saturate loud, not silent).
        None while the planner lacks evidence (caller falls back to
        the v1 occupancy heuristic). Cached for `replan_interval_s`;
        every transition appends its full decision inputs to the
        audit trail."""
        t = self._now() if now is None else now
        if self._wanted_last is not None \
                and t - self._wanted_ts < self.replan_interval_s:
            return self._wanted_last
        plans = []
        chosen = None
        for n in range(1, self.max_replicas + 1):
            p = self.plan(n, warm=min(n, max(0, int(n_live))))
            if p is None:
                return None
            p["meets_slo"] = self._meets_slo(p)
            p["availability_margin"] = round(
                p["availability"] - self.slo.availability, 4)
            plans.append(p)
            if chosen is None and p["meets_slo"]:
                chosen = n
                break
        wanted = chosen if chosen is not None else self.max_replicas
        prev = self._wanted_last
        self._wanted_last = wanted
        self._wanted_ts = t
        if wanted != prev:
            entry = {"t": round(t, 3), "from": prev, "to": wanted,
                     "n_live": int(n_live),
                     "slo": self.slo.as_dict(),
                     "slo_unmet": chosen is None,
                     "demand": self.demand(now=t),
                     "capacity": self.capacity(),
                     "plans": plans}
            self._audit.append(entry)
            self.ledger.record(
                "caplens_decision", wanted=wanted,
                prev=prev, n_live=int(n_live),
                slo_unmet=chosen is None,
                rate_hz=entry["demand"]["rate_hz"],
                availability=plans[-1]["availability"])
        return wanted

    # -- scrape surface ------------------------------------------------

    def summary(self) -> dict:
        """The /capz JSON body."""
        now = self._now()
        plans = [p for p in (self.plan(n, warm=None)
                             for n in (1, 2, 4)) if p is not None]
        return {
            "config": {"slots_per_replica": self.slots_per_replica,
                       "max_inflight_per_replica": self.max_inflight,
                       "deadline_s": self.deadline_s,
                       "seed": self.seed,
                       "window_s": self.window_s,
                       "max_replicas": self.max_replicas,
                       "slo": self.slo.as_dict()},
            "demand": self.demand(now=now),
            "sheds_by_reason": dict(self.sheds_by_reason),
            "capacity": self.capacity(),
            "coldstart": self.coldstart(),
            "plans": plans,
            "wanted_replicas": self._wanted_last,
            "audit": list(self._audit)[-8:],
            "ledger": self.ledger.events(last=64),
        }

    def render_prom(self) -> str:
        """Prometheus text for `/capz?format=prom` (self-contained:
        the lens's own families, not the shared registry)."""
        d = self.demand()
        cs = self.coldstart()
        lines = [
            "# HELP dnn_tpu_caplens_arrival_rate_hz windowed arrival "
            "rate seen at the router front door",
            "# TYPE dnn_tpu_caplens_arrival_rate_hz gauge",
            f"dnn_tpu_caplens_arrival_rate_hz {d['rate_hz']:.6f}",
            "# TYPE dnn_tpu_caplens_index_of_dispersion gauge",
            f"dnn_tpu_caplens_index_of_dispersion "
            f"{(d['index_of_dispersion'] or 0.0):.6f}",
            "# TYPE dnn_tpu_caplens_peak_to_mean gauge",
            f"dnn_tpu_caplens_peak_to_mean "
            f"{(d['peak_to_mean'] or 0.0):.6f}",
            "# TYPE dnn_tpu_caplens_change_point gauge",
            f"dnn_tpu_caplens_change_point "
            f"{1.0 if d['change_point'] else 0.0}",
            "# TYPE dnn_tpu_caplens_arrivals_total counter",
            f"dnn_tpu_caplens_arrivals_total {self.arrivals_total}",
            "# TYPE dnn_tpu_caplens_commits_total counter",
            f"dnn_tpu_caplens_commits_total {self.commits_total}",
            "# TYPE dnn_tpu_caplens_coldstart_p50_seconds gauge",
            f"dnn_tpu_caplens_coldstart_p50_seconds "
            f"{(cs['total_p50_s'] or 0.0):.6f}",
            "# TYPE dnn_tpu_caplens_coldstart_coverage gauge",
            f"dnn_tpu_caplens_coldstart_coverage "
            f"{(cs['coverage_mean'] or 0.0):.6f}",
            "# TYPE dnn_tpu_caplens_wanted_replicas gauge",
            f"dnn_tpu_caplens_wanted_replicas "
            f"{float(self._wanted_last or 0)}",
        ]
        if cs["buckets_p50_s"]:
            lines.append("# TYPE dnn_tpu_caplens_coldstart_bucket"
                         "_p50_seconds gauge")
            for b, v in sorted(cs["buckets_p50_s"].items()):
                lines.append(
                    f'dnn_tpu_caplens_coldstart_bucket_p50_seconds'
                    f'{{bucket="{b}"}} {(v or 0.0):.6f}')
        lines.append("# TYPE dnn_tpu_caplens_plan_availability gauge")
        for n in (1, 2, 4):
            p = self.plan(n)
            if p is not None:
                lines.append(
                    f'dnn_tpu_caplens_plan_availability{{n="{n}"}} '
                    f"{p['availability']:.6f}")
        lines.append("# TYPE dnn_tpu_caplens_shed_total counter")
        for reason in sorted(self.sheds_by_reason):
            lines.append(
                f'dnn_tpu_caplens_shed_total{{reason="{reason}"}} '
                f"{self.sheds_by_reason[reason]}")
        return "\n".join(lines) + "\n"

    def prom_gauges(self) -> dict:
        """Weak scrape-time gauge closures for the serving registry
        (`_obs_gauges` idiom, kvlens contract): the registry outlives
        any router, so closures hold a weakref — a collected lens
        reads 0, never a dangling object."""
        ref = weakref.ref(self)

        def _g(fn):
            def read():
                lens = ref()
                if lens is None:
                    return 0.0
                v = fn(lens)
                return float(v) if v is not None else 0.0
            return read

        out = {
            "dnn_tpu_caplens_arrival_rate_hz":
                _g(lambda l: l.demand()["rate_hz"]),
            "dnn_tpu_caplens_peak_to_mean":
                _g(lambda l: l.demand()["peak_to_mean"]),
            "dnn_tpu_caplens_coldstart_p50_seconds":
                _g(lambda l: l.coldstart()["total_p50_s"]),
            "dnn_tpu_caplens_coldstart_coverage":
                _g(lambda l: l.coldstart()["coverage_mean"]),
            "dnn_tpu_caplens_wanted_replicas":
                _g(lambda l: l._wanted_last),
        }
        for n in (1, 2, 4):
            out[labeled("dnn_tpu_caplens_plan_availability",
                        n=str(n))] = _g(
                lambda l, nn=n: (l.plan(nn) or {}).get("availability"))
        return out
