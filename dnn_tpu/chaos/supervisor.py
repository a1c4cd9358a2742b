"""Stage supervisor: restart a dead or wedged serving process.

The recovery half of ROADMAP item 5's "watchdog detects, nothing
reacts": a `Supervisor` owns ONE serving child (a stage server or the
LM daemon), and

  * restarts it when it EXITS, with exponential backoff (reset after a
    stable uptime) and crash-loop detection — more than
    `crash_loop_max` restarts inside `crash_loop_window_s` records a
    `crash_loop` flight event and gives up (a config that can never
    boot must not be kill-9'd in a tight loop forever);
  * detects a WEDGED child (alive but unresponsive — the SIGSTOP /
    hung-driver shape the watchdog classifies in-process) by polling
    `health_url` with a hard per-poll timeout; `wedged_after`
    consecutive failures fire the `on_wedged` policy: "restart"
    (SIGKILL + restart), "drain" (POST /drainz, wait for in-flight
    work, then restart) or "none" (detect + record only — the passive
    503 behavior);
  * optionally runs `restore()` before each (re)launch — the
    checkpoint hook; `restore_latest_good` below restores the newest
    checkpoint that LOADS, failing loud per corrupt artifact — and
    `warm()` after health returns, so recovery is declared only once
    the child actually serves again (a cold restart's first-compile
    window is part of the outage, not of "recovered").

Flight events (`supervisor_*`) pair with the injections that caused
them: `stage_down`/`stage_wedged` on detection, `supervisor_restart`
on a completed recovery.
"""

from __future__ import annotations

import os
import signal
import subprocess
import threading
import time
from typing import Callable, List, Optional

from dnn_tpu.obs import flight

__all__ = ["Supervisor", "restore_latest_good"]


class Supervisor:
    """Supervise one serving child process.

    `spawn`: callable -> subprocess.Popen (re-invoked for every
    launch; argv closures keep restore/launch decisions in one place).
    `health_url`: an obs endpoint base (http://host:port) — or a
    CALLABLE returning one, resolved fresh per poll, so a child that
    rebinds an ephemeral port on relaunch stays pollable — whose
    `health_path` (default /healthz) is polled every
    `health_interval_s` with a `health_timeout_s` hard timeout; each
    poll opens a FRESH connection, so a previous poll wedged in a dead
    socket can never mask a recovery (the PR 7 stale-channel lesson,
    applied here). The injectable endpoint/path is what lets a fleet
    spawner (dnn_tpu/control/replicaset.py) supervise N replicas on N
    distinct metrics ports without subclassing; `drain_path` names the
    drain kicker the same way (default /drainz).
    `ready`: callable -> bool, polled after launch until the child
    serves (default: health_url reachable); `warm`: optional callable
    run once after ready — a real request through the child, so
    `supervisor_restart` means "serving", not "bound a port".
    """

    def __init__(self, spawn: Callable[[], subprocess.Popen], *,
                 name: str = "stage",
                 health_url=None,
                 health_path: str = "/healthz",
                 drain_path: str = "/drainz",
                 health_interval_s: float = 1.0,
                 health_timeout_s: float = 2.0,
                 wedged_after: int = 3,
                 on_wedged: str = "restart",
                 backoff_s: float = 0.5,
                 backoff_max_s: float = 15.0,
                 stable_after_s: float = 30.0,
                 crash_loop_max: int = 5,
                 crash_loop_window_s: float = 120.0,
                 ready_deadline_s: float = 120.0,
                 restore: Optional[Callable[[], None]] = None,
                 warm: Optional[Callable[[], None]] = None,
                 ready: Optional[Callable[[], bool]] = None):
        if on_wedged not in ("restart", "drain", "none"):
            raise ValueError(
                f"on_wedged must be restart|drain|none, got {on_wedged!r}")
        self.spawn = spawn
        self.name = name
        self.health_url = health_url
        self.health_path = health_path
        self.drain_path = drain_path
        self.health_interval_s = float(health_interval_s)
        self.health_timeout_s = float(health_timeout_s)
        self.wedged_after = int(wedged_after)
        self.on_wedged = on_wedged
        self.backoff_s = float(backoff_s)
        self.backoff_max_s = float(backoff_max_s)
        self.stable_after_s = float(stable_after_s)
        self.crash_loop_max = int(crash_loop_max)
        self.crash_loop_window_s = float(crash_loop_window_s)
        self.ready_deadline_s = float(ready_deadline_s)
        self.restore = restore
        self.warm = warm
        self.ready = ready
        self.proc: Optional[subprocess.Popen] = None
        self.restarts = 0
        # the supervisor state machine is DECLARED (and model-checked)
        # in analysis/protocol.SUPERVISOR — edit both together
        self.state = "init"  # init|up|restarting|crashloop|stopped
        self._restart_times: List[float] = []
        self._health_fails = 0
        self._ever_healthy = False  # boot grace: a child still importing
        # jax must not read as wedged before its first healthy poll
        self._launched_at = 0.0
        self._stop = threading.Event()
        self._lock = threading.Lock()
        self._thread = threading.Thread(
            target=self._run, daemon=True, name=f"chaos-supervisor-{name}")

    # -- lifecycle -----------------------------------------------------

    def start(self) -> "Supervisor":
        self._launch(first=True)
        self._thread.start()
        return self

    def stop(self, kill_child: bool = True):
        self._stop.set()
        self._thread.join(timeout=self.health_timeout_s
                          + self.health_interval_s + 5)
        if kill_child and self.proc is not None \
                and self.proc.poll() is None:
            try:
                self.proc.kill()
                self.proc.wait(timeout=10)
            except Exception:  # noqa: BLE001 — already-gone child
                pass
        self.state = "stopped"

    # -- fault-injection helpers (the chaos driver's hands) -------------

    def inject_kill(self):
        """SIGKILL the child NOW (the kill_stage fault). The run loop
        notices the exit and drives the ordinary restart path — the
        injection and the recovery use the same machinery production
        would."""
        p = self.proc
        if p is not None and p.poll() is None:
            p.kill()

    def inject_hang(self):
        """SIGSTOP the child (the hang_stage fault): alive but
        unresponsive — exactly the wedge shape. Recovery comes from the
        health poller's wedged policy, never from a SIGCONT."""
        p = self.proc
        if p is not None and p.poll() is None:
            os.kill(p.pid, signal.SIGSTOP)

    # -- internals -----------------------------------------------------

    def _health_base(self) -> Optional[str]:
        """Resolve the probe base URL: a plain string, or a callable
        re-evaluated per poll (ephemeral-port children)."""
        u = self.health_url
        if callable(u):
            try:
                u = u()
            except Exception:  # noqa: BLE001 — "don't know the URL
                return None    # yet" reads as not-healthy, not a crash
        return u

    def _healthy_once(self) -> bool:
        import urllib.request

        base = self._health_base()
        if self.health_url is None:
            return True
        if base is None:
            return False
        try:
            with urllib.request.urlopen(
                    base.rstrip("/") + self.health_path,
                    timeout=self.health_timeout_s) as r:
                return r.status == 200
        except Exception:  # noqa: BLE001 — any failure is "not healthy"
            return False

    def _wait_ready(self) -> bool:
        t_end = time.monotonic() + self.ready_deadline_s
        check = self.ready if self.ready is not None else self._healthy_once
        while time.monotonic() < t_end and not self._stop.is_set():
            if self.proc is not None and self.proc.poll() is not None:
                return False  # died during boot: the loop restarts it
            try:
                if check():
                    return True
            except Exception:  # noqa: BLE001 — not ready yet
                pass
            time.sleep(0.25)
        return False

    def _launch(self, first: bool = False):
        if self.restore is not None:
            try:
                self.restore()
            except Exception as e:  # noqa: BLE001 — a failed restore is
                # part of the incident record, not a supervisor death
                flight.record("supervisor_restore_failed", stage=self.name,
                              error=str(e)[:300])
        self.proc = self.spawn()
        self._launched_at = time.monotonic()
        self._health_fails = 0
        self._ever_healthy = False
        self.state = "up"
        if not first:
            ok = self._wait_ready()
            if ok:
                self._ever_healthy = True
            if ok and self.warm is not None:
                try:
                    self.warm()
                except Exception as e:  # noqa: BLE001
                    flight.record("supervisor_warm_failed",
                                  stage=self.name, error=str(e)[:300])
                    ok = False
            if ok:
                flight.record("supervisor_restart", stage=self.name,
                              restarts=self.restarts,
                              pid=self.proc.pid)

    def _crash_looping(self, now: float) -> bool:
        self._restart_times = [
            t for t in self._restart_times
            if now - t <= self.crash_loop_window_s]
        return len(self._restart_times) >= self.crash_loop_max

    def _restart(self, reason: str):
        now = time.monotonic()
        if self._crash_looping(now):
            self.state = "crashloop"
            flight.record("crash_loop", stage=self.name,
                          restarts=self.restarts,
                          window_s=self.crash_loop_window_s,
                          max=self.crash_loop_max)
            return
        self.state = "restarting"
        # exponential backoff over RECENT restarts only: a child that
        # stayed up past stable_after_s earns a fresh ladder
        recent = len(self._restart_times)
        if now - self._launched_at >= self.stable_after_s:
            recent = 0
            self._restart_times.clear()
        delay = min(self.backoff_s * (2 ** recent), self.backoff_max_s)
        flight.record("supervisor_backoff", stage=self.name,
                      reason=reason, delay_s=round(delay, 3),
                      attempt=recent + 1)
        if self._stop.wait(delay):
            return
        self._restart_times.append(time.monotonic())
        self.restarts += 1
        self._launch()

    def _kill_child(self):
        p = self.proc
        if p is None or p.poll() is not None:
            return
        try:
            p.kill()
            p.wait(timeout=10)
        except Exception:  # noqa: BLE001 — D-state child: move on
            pass

    def _drain_child(self) -> bool:
        """POST /drainz and wait (bounded) for the child to report
        drained / become unreachable — the graceful half of the drain
        policy; the caller restarts afterwards either way."""
        import urllib.request

        base = self._health_base()
        if base is None:
            return False
        try:
            req = urllib.request.Request(
                base.rstrip("/") + self.drain_path, method="POST",
                data=b"")
            with urllib.request.urlopen(
                    req, timeout=self.health_timeout_s) as r:
                ok = r.status in (200, 202)
        except Exception:  # noqa: BLE001 — a wedged child can't drain
            return False
        if not ok:
            return False
        t_end = time.monotonic() + max(self.ready_deadline_s, 10.0)
        while time.monotonic() < t_end and not self._stop.is_set():
            p = self.proc
            if p is not None and p.poll() is not None:
                return True  # drained and exited
            time.sleep(0.5)
        return False

    def _run(self):
        while not self._stop.is_set():
            p = self.proc
            if self.state == "crashloop":
                self._stop.wait(self.health_interval_s)
                continue
            if p is None or p.poll() is not None:
                rc = p.returncode if p is not None else None
                flight.record("stage_down", stage=self.name, rc=rc)
                self._restart(f"exit rc={rc}")
                continue
            if self.health_url is not None and self.state == "up":
                if self._healthy_once():
                    self._ever_healthy = True
                    self._health_fails = 0
                elif not self._ever_healthy:
                    # boot grace: never healthy yet — only the ready
                    # deadline (not the consecutive-failure count) can
                    # condemn a child that is still importing/compiling
                    if time.monotonic() - self._launched_at \
                            > self.ready_deadline_s:
                        flight.record("stage_wedged", stage=self.name,
                                      reason="never became ready",
                                      policy=self.on_wedged)
                        if self.on_wedged != "none":
                            self._kill_child()
                            self._restart("never ready")
                            continue
                else:
                    self._health_fails += 1
                    if self._health_fails >= self.wedged_after:
                        flight.record(
                            "stage_wedged", stage=self.name,
                            consecutive_failures=self._health_fails,
                            policy=self.on_wedged)
                        if self.on_wedged == "none":
                            self._health_fails = 0  # re-detect, re-record
                        else:
                            if self.on_wedged == "drain":
                                self._drain_child()
                            self._kill_child()
                            self._restart("wedged")
                            continue
            self._stop.wait(self.health_interval_s)


def restore_latest_good(ckpt_dir: str, like, *, max_back: int = 5):
    """Restore the newest checkpoint under `ckpt_dir` that actually
    LOADS. A corrupt newest artifact (the ckpt_corrupt fault, or real
    crash debris) fails loud — a `ckpt_restore_failed` flight event
    naming the file — and the walk falls back to the previous good one
    instead of serving garbage or dying. Returns (state, step, path);
    raises RuntimeError when nothing within `max_back` steps loads.

    `like` is the template pytree `io.train_ckpt.restore_train_state`
    needs (a freshly-initialized state of the right treedef)."""
    from dnn_tpu.io.train_ckpt import latest_checkpoint, restore_train_state

    if not os.path.isdir(ckpt_dir):
        raise RuntimeError(f"no checkpoint directory at {ckpt_dir!r}")
    candidates = []
    for name in sorted(os.listdir(ckpt_dir), reverse=True):
        if name.startswith("step_") and name.endswith(".npz"):
            candidates.append(os.path.join(ckpt_dir, name))
    if not candidates:
        latest = latest_checkpoint(ckpt_dir)
        if latest is None:
            raise RuntimeError(f"no checkpoints under {ckpt_dir!r}")
        candidates = [latest[0]]
    errors = []
    for path in candidates[:max_back]:
        try:
            state, step = restore_train_state(path, like)
            if errors:  # recovered past >=1 corrupt artifact: record it
                flight.record("ckpt_restore_recovered", path=path,
                              step=step, skipped=len(errors))
            return state, step, path
        except Exception as e:  # noqa: BLE001 — corrupt/truncated/foreign
            flight.record("ckpt_restore_failed", path=path,
                          error=str(e)[:300])
            errors.append((path, str(e)))
    raise RuntimeError(
        f"no loadable checkpoint in the newest {max_back} under "
        f"{ckpt_dir!r}; failures: "
        + "; ".join(f"{os.path.basename(p)}: {e[:80]}"
                    for p, e in errors))

