"""ReplicaSet: replica lifecycle for the fleet front door.

One `ReplicaHandle` per LM replica: its gRPC address, its obs endpoint,
its serving `role` (prefill | decode | both — the disaggregation
attribute), optionally a `chaos.supervisor.Supervisor` that owns the
real `node --serve_lm` child process (spawn / restart-with-backoff /
wedged detection — nothing here re-implements recovery; the PR 8
machinery IS the recovery), and the replica's lifecycle state machine:

    idle -> warming -> serving -> draining -> dead -> (respawn) warming

The table is DECLARED in `analysis/protocol.REPLICA` and model-checked
both directions by the CI gate, exactly like breaker/drain/supervisor
— edit the two together. Transitions land in the flight ring
(`replica_*` events), so a fleet incident reconstructs from /debugz
the way a chaos incident does.

`ReplicaSet` owns the handles plus the monitor thread that drives the
machines off fresh health probes, and (when the replicas expose obs
endpoints) an `obs.fleet.FleetCollector` scraping the signals the
routing policies consume — queue depth, KV-slot utilization, TTFT/ITL
percentiles, burn rates (`views()` merges them into
`policy.ReplicaView` rows). Attach mode (no supervisor) wraps already-
running endpoints — tests and `node --route` use it; the spawning mode
is `ReplicaSet.spawn_lm_fleet` / `python -m dnn_tpu.control`.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time
from typing import Dict, List, Optional

from dnn_tpu.control.policy import ROLES, ReplicaView
from dnn_tpu.obs import flight

__all__ = ["ReplicaHandle", "ReplicaSet", "lm_replica_argv"]


class ReplicaHandle:
    """One replica: endpoints + lifecycle state (+ optional Supervisor).

    `address` is the gRPC host:port `NodeClient` dials; `obs_url` the
    replica's observability base (http://host:port) — health probes and
    signal scraping ride it when present, else health falls back to a
    fresh gRPC HealthCheck per poll (fresh per poll for the same reason
    the Supervisor's is: a probe wedged in a dead socket must never
    mask a recovery). The state attr is written ONLY under `_lock`;
    the monitor thread and the owning ReplicaSet are the writers, the
    router reads.
    """

    def __init__(self, name: str, address: str, *,
                 obs_url: Optional[str] = None,
                 role: str = "both",
                 supervisor=None):
        if role not in ROLES:
            raise ValueError(f"role must be one of {ROLES}, got {role!r}")
        self.name = name
        self.address = address
        self.obs_url = obs_url.rstrip("/") if obs_url else None
        self.role = role
        self.supervisor = supervisor
        # the replica lifecycle machine is DECLARED (and model-checked)
        # in analysis/protocol.REPLICA — edit both together
        self.state = "idle"  # idle|warming|serving|draining|dead
        self._lock = threading.Lock()
        self._health_fails = 0
        # lifecycle stamps: every transition event carries its duration
        # (spawn->ready, ready->drain), so the cold-start ledger
        # (obs/caplens) and a future autoscaler read ONE event stream
        self.t_spawn: Optional[float] = None
        self.t_ready: Optional[float] = None
        self._caplens = None  # set by ReplicaSet.attach_caplens

    # -- lifecycle entry points (ReplicaSet/monitor-thread callers) ----

    def start(self):
        """idle -> warming: launch the supervised child (attach mode
        has nothing to launch — the probe loop promotes it the moment
        its endpoint answers)."""
        with self._lock:
            if self.state != "idle":
                return
            self.state = "warming"
        self.t_spawn = time.monotonic()
        self.t_ready = None
        flight.record("replica_spawn", replica=self.name,
                      role=self.role, address=self.address,
                      supervised=self.supervisor is not None)
        lens = self._caplens
        if lens is not None:
            lens.spawn_begin(self.name, self.role, now=self.t_spawn)
        if self.supervisor is not None:
            self.supervisor.start()

    def drain(self) -> bool:
        """serving -> draining: close the replica's admission (POST
        /drainz — the PR 8 drain; queued work hands back retriable and
        the router's retry-on-sibling picks it up). Returns False when
        the replica has no obs endpoint to drain through."""
        import urllib.request

        with self._lock:
            if self.state != "serving":
                return False
            self.state = "draining"
        t = time.monotonic()
        flight.record("replica_drain", replica=self.name,
                      served_s=round(t - self.t_ready, 3)
                      if self.t_ready is not None else None)
        lens = self._caplens
        if lens is not None:
            lens.spawn_gone(self.name)
        if self.obs_url is None:
            return False
        try:
            req = urllib.request.Request(
                self.obs_url + "/drainz", method="POST", data=b"")
            with urllib.request.urlopen(req, timeout=5.0) as r:
                return r.status in (200, 202)
        except Exception:  # noqa: BLE001 — a dead replica can't drain;
            return False   # the monitor will mark it dead shortly

    def kill(self):
        """SIGKILL the supervised child NOW (the chaos hand): the
        supervisor notices the exit and respawns; the monitor drives
        dead -> warming -> serving off the same health probes
        production would."""
        if self.supervisor is not None:
            self.supervisor.inject_kill()

    # -- monitor-thread transitions ------------------------------------

    def _mark_serving(self):
        with self._lock:
            prev, self.state = self.state, "serving"
        if prev != "serving":
            t = time.monotonic()
            self.t_ready = t
            flight.record("replica_ready", replica=self.name,
                          role=self.role,
                          spawn_to_ready_s=round(t - self.t_spawn, 3)
                          if self.t_spawn is not None else None)
            lens = self._caplens
            if lens is not None:
                lens.spawn_ready(self.name, now=t)

    def _mark_dead(self, reason: str):
        with self._lock:
            prev, self.state = self.state, "dead"
        if prev != "dead":
            t = time.monotonic()
            flight.record("replica_dead", replica=self.name,
                          was=prev, reason=reason,
                          alive_s=round(t - self.t_spawn, 3)
                          if self.t_spawn is not None else None)
            lens = self._caplens
            if lens is not None:
                lens.spawn_gone(self.name)

    def _mark_respawning(self):
        with self._lock:
            prev, self.state = self.state, "warming"
        if prev != "warming":
            self.t_spawn = time.monotonic()
            self.t_ready = None
            flight.record("replica_respawn", replica=self.name)
            lens = self._caplens
            if lens is not None:
                lens.spawn_begin(self.name, self.role,
                                 now=self.t_spawn)

    # -- health --------------------------------------------------------

    def _healthy_once(self, timeout_s: float) -> bool:
        """One FRESH health probe. Obs endpoint when present (200 =
        healthy; 503 covers wedged AND draining); gRPC HealthCheck
        otherwise."""
        if self.obs_url is not None:
            import urllib.request

            try:
                with urllib.request.urlopen(
                        self.obs_url + "/healthz", timeout=timeout_s) as r:
                    return r.status == 200
            except Exception:  # noqa: BLE001 — unreachable = unhealthy
                return False
        from dnn_tpu.comm.client import NodeClient

        probe = NodeClient(self.address, breaker=False, transport="grpc")
        try:
            return probe.health_check(timeout=timeout_s)
        finally:
            probe.close()


class ReplicaSet:
    """The fleet's replica collection + the monitor that keeps each
    handle's lifecycle machine current.

    `scrape=True` (default, when every handle has an obs_url) runs an
    `obs.fleet.FleetCollector` over the replica endpoints —
    spans are NOT polled (poll_traces=False): the router wants signal
    rows at its poll cadence, not trace stitching."""

    def __init__(self, replicas: List[ReplicaHandle], *,
                 interval_s: float = 1.0,
                 health_timeout_s: float = 2.0,
                 dead_after: int = 3,
                 scrape: bool = True):
        if not replicas:
            raise ValueError("ReplicaSet needs at least one replica")
        names = [r.name for r in replicas]
        if len(set(names)) != len(names):
            raise ValueError(f"duplicate replica names: {names}")
        self.replicas: Dict[str, ReplicaHandle] = {
            r.name: r for r in replicas}
        self.interval_s = float(interval_s)
        self.health_timeout_s = float(health_timeout_s)
        self.dead_after = int(dead_after)
        self.collector = None
        if scrape and all(r.obs_url for r in replicas):
            from dnn_tpu.obs.fleet import FleetCollector

            self.collector = FleetCollector(
                {r.name: r.obs_url for r in replicas},
                interval_s=self.interval_s,
                timeout_s=self.health_timeout_s,
                poll_traces=False)
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self.caplens = None

    def attach_caplens(self, lens):
        """Wire the capacity observatory (obs/caplens) into the
        lifecycle seams: every handle's spawn/ready/drain transition
        feeds the cold-start ledger, and the lens reads each child's
        boot/compile gauges through this set's collector (its default
        `signals` source, unless the lens already has one)."""
        self.caplens = lens
        for r in self.replicas.values():
            r._caplens = lens
            if lens is None:
                continue
            # backfill spawns that predate the lens (the usual order:
            # fleet starts, THEN the router builds its lens) — the
            # handles' stamps keep the walls honest
            if r.t_spawn is not None and r.state in ("warming",
                                                     "serving"):
                lens.spawn_begin(r.name, r.role, now=r.t_spawn)
                if r.t_ready is not None:
                    lens.spawn_ready(r.name, now=r.t_ready)
        if lens is not None and lens._signals is None \
                and self.collector is not None:
            lens._signals = self.collector.boot_signals

    # -- lifecycle -----------------------------------------------------

    def start(self) -> "ReplicaSet":
        for r in self.replicas.values():
            r.start()
        if self.collector is not None:
            self.collector.start()
        self._thread = threading.Thread(
            target=self._monitor, daemon=True, name="control-replicaset")
        self._thread.start()
        return self

    def stop(self):
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5)
        if self.collector is not None:
            self.collector.close()
        for r in self.replicas.values():
            if r.supervisor is not None:
                r.supervisor.stop()

    def wait_serving(self, n: int = 1, deadline_s: float = 180.0) -> bool:
        """Block until >= n replicas reach `serving` (boot includes a
        jax import + first compile — the deadline defaults generous)."""
        t_end = time.monotonic() + deadline_s
        while time.monotonic() < t_end:
            if len(self.serving()) >= n:
                return True
            if self._stop.wait(0.25):
                return False
        return False

    # -- the monitor ---------------------------------------------------

    def _tick_one(self, r: ReplicaHandle):
        sup = r.supervisor
        child_gone = (
            sup is not None and
            (sup.proc is None or sup.proc.poll() is not None
             or sup.state in ("restarting", "crashloop")))
        if r.state == "dead":
            # a supervised child the Supervisor relaunched re-enters
            # warming immediately; an ATTACHED endpoint (no supervisor)
            # re-enters only once it actually answers healthy again —
            # its next probe then promotes it to serving
            if sup is not None:
                if not child_gone:
                    r._mark_respawning()
            elif r._healthy_once(self.health_timeout_s):
                r._mark_respawning()
            return
        healthy = (not child_gone) and r._healthy_once(
            self.health_timeout_s)
        if healthy:
            r._health_fails = 0
            if r.state in ("warming", "serving"):
                r._mark_serving()
            # draining stays draining while the endpoint still answers
            # (it 503s once the drain takes; unreachable ends it below)
            return
        if r.state == "warming":
            # boot grace for SUPERVISED children is the Supervisor's
            # ready_deadline job — the monitor only condemns one whose
            # child is actually gone. An attached endpoint has no boot
            # story: consecutive failures send it back to dead (a
            # drained/stopped server must not read "warming" forever)
            if child_gone:
                r._mark_dead("child exited during boot")
            elif sup is None:
                r._health_fails += 1
                if r._health_fails >= self.dead_after:
                    r._mark_dead(f"{r._health_fails} consecutive "
                                 "health failures while warming")
            return
        r._health_fails += 1
        if child_gone or r._health_fails >= self.dead_after:
            r._mark_dead("child gone" if child_gone
                         else f"{r._health_fails} consecutive health "
                              "failures")

    def _monitor(self):
        while not self._stop.wait(self.interval_s):
            for r in list(self.replicas.values()):
                try:
                    self._tick_one(r)
                except Exception:  # noqa: BLE001 — one replica's probe
                    pass           # blowing up must not stop the fleet

    # -- views (what the router/policies consume) ----------------------

    def serving(self) -> List[ReplicaHandle]:
        return [r for r in self.replicas.values()
                if r.state == "serving"]

    def views(self) -> List[ReplicaView]:
        """Every replica as a `policy.ReplicaView`: lifecycle state from
        the handles, signals from the collector's freshest rows (None
        when scraping is off / a row is missing — policies degrade to
        the router's local inflight counts)."""
        rows: Dict[str, dict] = {}
        if self.collector is not None:
            try:
                rows = self.collector.fleetz().get("stages") or {}
            except Exception:  # noqa: BLE001 — a scrape hiccup must
                rows = {}      # not take routing down
        out = []
        for r in self.replicas.values():
            row = rows.get(r.name) or {}
            out.append(ReplicaView(
                name=r.name, state=r.state,
                role=row.get("role") or r.role,
                queue_depth=row.get("queue_depth"),
                kv_util=row.get("kv_util"),
                ttft_p99_ms=row.get("ttft_p99_ms"),
                inter_token_p99_ms=row.get("inter_token_p99_ms"),
                tokens_per_sec=row.get("tokens_per_sec"),
                burn=row.get("slo_burn"),
                kvtier_blocks=row.get("kvtier_blocks"),
            ))
        return out

    # -- spawning real replicas ----------------------------------------

    @classmethod
    def spawn_lm_fleet(cls, tmpdir: str, *, model: str,
                       base_port: int, metrics_base_port: int,
                       roles: List[str],
                       slots: int = 4,
                       max_len: Optional[int] = None,
                       seed: int = 0,
                       kv: str = "auto",
                       extra_args: Optional[List[str]] = None,
                       env: Optional[dict] = None,
                       interval_s: float = 1.0,
                       ready_deadline_s: float = 240.0,
                       slo_args: Optional[List[str]] = None
                       ) -> "ReplicaSet":
        """Spawn len(roles) real `node --serve_lm` children, each under
        its own `chaos.supervisor.Supervisor` polling that child's OWN
        obs endpoint (the injectable ready-probe URL — distinct
        metrics ports without subclassing). Config JSONs land in
        `tmpdir`, which must outlive the set (supervisors respawn from
        them)."""
        import subprocess

        from dnn_tpu.chaos.supervisor import Supervisor

        handles = []
        for i, role in enumerate(roles):
            name = f"r{i}"
            port = base_port + i
            mport = metrics_base_port + i
            cfg = {"nodes": [{"id": name,
                              "address": f"127.0.0.1:{port}",
                              "part_index": 0}],
                   "num_parts": 1, "model": model, "device_type": "cpu"}
            cfg_path = os.path.join(tmpdir, f"replica_{name}.json")
            with open(cfg_path, "w") as f:
                json.dump(cfg, f)
            argv = lm_replica_argv(
                name, cfg_path, metrics_port=mport, role=role,
                slots=slots, max_len=max_len, seed=seed, kv=kv,
                extra_args=extra_args)
            child_env = dict(os.environ, JAX_PLATFORMS="cpu")
            child_env.pop("XLA_FLAGS", None)
            if env:
                child_env.update(env)

            def spawn(argv=argv, child_env=child_env):
                return subprocess.Popen(
                    argv, env=child_env, stdout=subprocess.DEVNULL,
                    stderr=subprocess.DEVNULL)

            obs_url = f"http://127.0.0.1:{mport}"
            handles.append(ReplicaHandle(
                name, f"127.0.0.1:{port}", obs_url=obs_url, role=role,
                supervisor=Supervisor(
                    spawn, name=name, health_url=obs_url,
                    health_interval_s=1.0, health_timeout_s=2.0,
                    wedged_after=3, on_wedged="restart",
                    backoff_s=0.5, ready_deadline_s=ready_deadline_s)))
        return cls(handles, interval_s=interval_s)


def lm_replica_argv(node_id: str, config_path: str, *,
                    metrics_port: int, role: str = "both",
                    slots: int = 4, max_len: Optional[int] = None,
                    seed: int = 0, kv: str = "auto",
                    extra_args: Optional[List[str]] = None) -> List[str]:
    """The replica child's command line — one place, so the CLI
    (`python -m dnn_tpu.control`) and tests spawn byte-identical
    children."""
    argv = [sys.executable, "-m", "dnn_tpu.node",
            "--node_id", node_id, "--config", config_path,
            "--serve_lm", "--role", role,
            "--slots", str(slots), "--seed", str(seed), "--kv", kv,
            "--metrics_port", str(metrics_port)]
    if max_len is not None:
        argv += ["--max_len", str(max_len)]
    if extra_args:
        argv += list(extra_args)
    return argv
