"""Hung-device watchdog: bounded liveness probes + heartbeat staleness.

The failure this guards against: a wedged accelerator hangs the first
device op — or a later one — IN PROCESS, where nothing can catch it. A
server on a wedged chip doesn't crash; it just stops, and a /healthz that
only checked thread liveness would keep saying "ok". This module is the
detector:

  * a daemon thread runs a DEVICE PROBE once per period. Every probe
    callable is bounded by a probe thread joined at the deadline, so a
    hang costs one leaked daemon thread, not the watchdog. Which probe
    depends on who owns the device — a chip belongs to ONE process:
      - a process that HOLDS the device (the LM daemon) probes it
        in-process (`in_process_device_probe`): a child could not open
        the chip its parent holds, and would read a healthy server as
        degraded or wedged;
      - a process that holds NO device probes in a SUBPROCESS with a
        hard deadline (`subprocess_device_probe`) — a wedged chip hangs
        the child, never the caller;
  * a DECODE HEARTBEAT: the LM batcher worker calls `beat()` every loop
    iteration; a heartbeat older than `heartbeat_stale_s` while the
    thread is supposedly alive means a step wedged inside the device
    runtime;
  * state is the worst component: `ok` -> `degraded` (probe errored
    fast — backend unhealthy but not hung) -> `wedged` (probe deadline
    exceeded, or heartbeat stale). Transitions land in the flight
    recorder (obs/flight.py) and the `dnn_tpu_watchdog_state` gauge
    (0/1/2); `GET /statusz` serves the full per-component detail and
    /healthz degrades from binary to ok|degraded|wedged (obs/http.py).
"""

from __future__ import annotations

import os
import subprocess
import sys
import threading
import time
from typing import Callable, Optional, Tuple

__all__ = ["Watchdog", "subprocess_device_probe",
           "in_process_device_probe", "STATE_VALUES"]

STATE_VALUES = {"ok": 0.0, "degraded": 1.0, "wedged": 2.0}

_PROBE_CODE = ("import jax, jax.numpy as jnp; "
               "x = jnp.ones((128,128)) @ jnp.ones((128,128)); "
               "x.block_until_ready(); print(jax.default_backend())")


def in_process_device_probe(deadline_s: float = 10.0) -> Tuple[bool, str]:
    """One probe from the process that HOLDS the device: a tiny matmul on
    the default backend, queued behind whatever the server has in flight
    (the device stream is in-order, so it also shows the queue drains).
    `deadline_s` is enforced by the caller — `Watchdog` joins the probe
    thread at the deadline, so a hang here reads as wedged; an exception
    reads as degraded."""
    import jax
    import jax.numpy as jnp

    x = jnp.ones((128, 128), jnp.float32)
    (x @ x).block_until_ready()
    return True, f"ok ({jax.default_backend()}, in-process)"


def subprocess_device_probe(deadline_s: float = 10.0,
                            platform: Optional[str] = None,
                            ) -> Tuple[bool, str, bool]:
    """One bounded probe for a caller that holds NO device: a tiny matmul
    in a child process, on `platform` if given (JAX_PLATFORMS in the
    child's environment), else the default backend. Never use it from a
    process that has initialized an accelerator backend — the child
    cannot open a chip its parent holds. Returns
    (ok, detail, timed_out) — `timed_out` is the STRUCTURED hung-vs-
    failed distinction the watchdog classifies on (wedged vs degraded);
    the free-text detail is for humans only.
    Popen + wait(timeout), NOT subprocess.run: run()
    reaps the child after kill(), and a probe stuck in uninterruptible
    device I/O (D-state inside a wedged driver) cannot be reaped until
    the syscall returns — run() would hang right here. On timeout we
    kill best-effort and move on.

    The deadline clock covers the child's whole lifetime, `import jax`
    included (~4 s cold on a quiet 2-core host) — deadlines below ~6 s
    read a HEALTHY backend as wedged."""
    env = dict(os.environ, JAX_PLATFORMS=platform) if platform else None
    proc = subprocess.Popen(
        [sys.executable, "-c", _PROBE_CODE], env=env,
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
    try:
        rc = proc.wait(timeout=deadline_s)
    except subprocess.TimeoutExpired:
        proc.kill()
        return False, f"probe timeout after {deadline_s:.0f}s", True
    return rc == 0, "ok" if rc == 0 else f"probe exited rc={rc}", False


class Watchdog:
    """Liveness monitor for one serving process. Construct, then
    `start()`; read `state()` / `status()`; `close()` to stop.

    device_probe: callable(deadline_s) -> (ok, detail) or (ok, detail,
    timed_out), or None to disable the device leg (CPU-only test
    servers). The default is `subprocess_device_probe`, for callers
    that hold no device; a process that holds one passes
    `in_process_device_probe` (module docstring). Hung-vs-failed
    is decided STRUCTURALLY, never by sniffing the detail text: wedged
    when the probe reports timed_out=True, or when the call itself
    outlives its deadline (even if it eventually returns); a fast
    (False, detail) from a 2-tuple custom probe is by definition not
    hung and reads as degraded.

    alive_check: optional callable -> bool for the serving worker
    thread; False -> wedged (the work loop is gone).

    on_wedged: optional callable(detail) fired ONCE per wedged EPISODE
    (latched while the state stays wedged, re-armed when it recovers) —
    the escalation hook `--on_wedged restart|drain` wires to the
    supervisor/drain path (runtime/lm_server.py). Fired from the
    watchdog thread AFTER the state flip, so /statusz already reads
    wedged when the policy runs; exceptions are swallowed-but-logged
    (a broken policy must not kill the detector). The first-step
    warm-up grace rules are unchanged — a cold chip's compile still
    reads degraded, so the policy can never evict a healthy warming
    server.

    Chaos hook (dnn_tpu/chaos): when a fault plan with an active
    `wedge_device` window is installed in this process, the probe
    round reports that injected wedge (timed_out=True semantics)
    WITHOUT touching any device — the injection exercises exactly the
    classification + escalation path a real wedge would.
    """

    def __init__(self, *, period_s: float = 30.0,
                 probe_deadline_s: float = 10.0,
                 device_probe: "Optional[Callable]" = subprocess_device_probe,
                 heartbeat_stale_s: float = 120.0,
                 alive_check: Optional[Callable[[], bool]] = None,
                 on_wedged: Optional[Callable[[str], None]] = None,
                 registry=None):
        self.period_s = float(period_s)
        self.probe_deadline_s = float(probe_deadline_s)
        self.device_probe = device_probe
        self.heartbeat_stale_s = float(heartbeat_stale_s)
        self.alive_check = alive_check
        self.on_wedged = on_wedged
        self._wedged_latched = False
        self._lock = threading.Lock()
        self._components: dict = {}
        self._t_beat: Optional[float] = None
        self._warmed = False  # a step has completed: see step_done()
        self._stop = threading.Event()
        self._probe_thread: Optional[threading.Thread] = None
        self._probe_result: Optional[tuple] = None  # (ok, detail[, timed_out])
        self._thread = threading.Thread(
            target=self._run, daemon=True, name="obs-watchdog")
        self._register_gauge(registry)

    def _register_gauge(self, registry):
        from dnn_tpu import obs

        reg = registry if registry is not None else obs.metrics()
        if reg is None:
            return
        import weakref

        ref = weakref.ref(self)

        def read() -> float:
            wd = ref()
            return STATE_VALUES[wd.state()] if wd is not None else 0.0

        reg.set_fn("dnn_tpu_watchdog_state", read)

    # -- producer side --------------------------------------------------

    def start(self) -> "Watchdog":
        self._thread.start()
        return self

    def beat(self):
        """Heartbeat from the serving work loop (one perf_counter read +
        one attribute store; called every worker iteration)."""
        self._t_beat = time.perf_counter()

    def step_done(self):
        """A decode/prefill step COMPLETED (one attribute store; the LM
        worker calls this after every successful step). Until the first
        one, a stale heartbeat reads `degraded`, not `wedged`: the first
        step's XLA compile on a cold chip legitimately blocks the loop
        for minutes, and a 503
        there makes an orchestrator evict a healthy warming server —
        potentially forever, since each restart re-compiles."""
        self._warmed = True

    def close(self):
        self._stop.set()
        if self._thread.is_alive():
            self._thread.join(timeout=self.period_s + 1)

    # -- state ----------------------------------------------------------

    def _set_component(self, name: str, state: str, detail: str):
        from dnn_tpu.obs import flight

        with self._lock:
            prev = self._components.get(name, {}).get("state")
            self._components[name] = {
                "state": state, "detail": detail, "t": time.time()}
        if prev != state:
            flight.record("watchdog", component=name,
                          prev=prev or "unknown", state=state,
                          detail=detail)

    def _check_heartbeat(self):
        if self.alive_check is not None and not self.alive_check():
            self._set_component("decode_heartbeat", "wedged",
                                "serving worker thread is not alive")
            return
        tb = self._t_beat
        if tb is None:
            return  # no loop has ever beaten: component not tracked
        age = time.perf_counter() - tb
        if age > self.heartbeat_stale_s:
            if not self._warmed:
                # no step has EVER completed: the loop is most likely
                # blocked in the first step's XLA compile (minutes on a
                # cold chip), not a wedge — visible, but not a 503
                self._set_component(
                    "decode_heartbeat", "degraded",
                    f"last heartbeat {age:.0f}s ago with no completed "
                    "step yet: first-step compile in progress, or the "
                    "device wedged at init")
                return
            self._set_component(
                "decode_heartbeat", "wedged",
                f"last heartbeat {age:.0f}s ago (stale > "
                f"{self.heartbeat_stale_s:.0f}s: a step is stuck inside "
                "the device runtime)")
        else:
            self._set_component("decode_heartbeat", "ok",
                                f"last heartbeat {age:.1f}s ago")

    def _run_probe(self):
        """One device-probe round. The probe runs on ITS OWN thread and
        we join with the deadline (+ slack for the subprocess probe,
        which bounds itself): a stubbed/in-process probe that hangs
        leaks exactly one daemon thread and reads as a timeout — and no
        new probe is spawned while the stuck one lives."""
        from dnn_tpu.chaos import inject as _chaos_inject

        injected = _chaos_inject.wedge_detail()
        if injected is not None:
            # chaos wedge_device window: the probe result IS the
            # injection (structural timed_out semantics) — no device
            # touched, same classification path as a real hang
            self._set_component("device", "wedged", injected)
            return
        if self._probe_thread is not None and self._probe_thread.is_alive():
            self._set_component(
                "device", "wedged",
                "previous probe still hung past its deadline")
            return

        def probe_main():
            try:
                self._probe_result = self.device_probe(self.probe_deadline_s)
            except Exception as e:  # noqa: BLE001 — a broken probe is a
                self._probe_result = (False, f"probe raised: {e}")  # result

        self._probe_result = None
        t = threading.Thread(target=probe_main, daemon=True,
                             name="obs-watchdog-probe")
        self._probe_thread = t
        t.start()
        # +2 s slack covers thread scheduling + Popen spawn only — the
        # subprocess probe's deadline clock already covers the child's
        # whole lifetime (jax import included), so a wedged chip reads
        # as wedged within probe_deadline_s + 2, well inside one period
        # at the production 30 s/10 s defaults
        t.join(timeout=self.probe_deadline_s + 2.0)
        res = self._probe_result
        if t.is_alive() or (res is None):
            self._set_component(
                "device", "wedged",
                f"device probe hung past {self.probe_deadline_s:.0f}s "
                "deadline")
            return
        ok, detail = res[0], res[1]
        timed_out = len(res) > 2 and bool(res[2])
        if ok:
            self._set_component("device", "ok", detail)
        elif timed_out:
            self._set_component("device", "wedged", detail)
        else:
            # fast failure: the backend answered, unhealthily — a HUNG
            # probe never reaches here (child timeout sets timed_out;
            # an in-process hang is caught by the join deadline above)
            self._set_component("device", "degraded", detail)

    def _fire_escalation(self):
        """Once-per-episode wedged escalation: latched while wedged,
        re-armed on recovery. Runs AFTER the component flip, so the
        policy sees consistent /statusz state."""
        if self.state() == "wedged":
            if not self._wedged_latched:
                self._wedged_latched = True
                cb = self.on_wedged
                if cb is not None:
                    detail = "; ".join(
                        f"{k}: {v['detail']}"
                        for k, v in self.status()["components"].items()
                        if v["state"] == "wedged")
                    try:
                        cb(detail)
                    except Exception:  # noqa: BLE001 — a broken policy
                        import logging

                        logging.getLogger("dnn_tpu.obs").exception(
                            "on_wedged escalation hook failed")
        else:
            self._wedged_latched = False

    def _run(self):
        while not self._stop.is_set():
            if self.device_probe is not None:
                self._run_probe()
            self._check_heartbeat()
            self._fire_escalation()
            # first round runs immediately (a wedged chip must be
            # reported within ONE period of startup), then period cadence
            self._stop.wait(self.period_s)

    def state(self) -> str:
        with self._lock:
            states = [c["state"] for c in self._components.values()]
        if not states:
            return "ok"
        return max(states, key=lambda s: STATE_VALUES[s])

    def status(self) -> dict:
        self._check_heartbeat()  # staleness must be fresh at read time
        with self._lock:
            comps = {k: dict(v) for k, v in self._components.items()}
        states = [c["state"] for c in comps.values()]
        return {
            "state": max(states, key=lambda s: STATE_VALUES[s])
            if states else "ok",
            "components": comps,
            "period_s": self.period_s,
            "probe_deadline_s": self.probe_deadline_s,
            "t": time.time(),
        }
