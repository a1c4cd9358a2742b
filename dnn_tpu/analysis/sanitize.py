"""Loop-lag sanitizer: the runtime companion to the CON001 AST rule.

The AST pass flags blocking calls it can SEE reaching an async body;
a blocking call behind an indirection the per-module analysis cannot
resolve (a callback registry, a duck-typed sender, a C extension) is
invisible to it. This sanitizer is the dynamic tripwire: a periodic
self-timer on the event loop measures how late each tick fires — any
callback that held the loop for >= threshold shows up as exactly that
much tick overshoot, the same way the PR 7 `ShmRing.write` deadlock
held the loop for the full 30 s ring timeout.

Shape follows the obs conventions: OFF by one boolean
(`DNN_TPU_LOOP_SANITIZE`, default off — it is a test/verify-path
instrument, not a production default), bounded (a deque of recent lag
samples, a cap on emitted flight events), and flight-event-emitting —
each breach lands in the ring as a `loop_lag` event with the measured
lag, so a run can read the served /debugz back and assert the bound
against the artifact. A `loop_sanitize_on` event at install proves the sanitizer
actually ran (an assertion against an empty ring must not pass
vacuously).

Env knobs: DNN_TPU_LOOP_SANITIZE=1 enables;
DNN_TPU_LOOP_SANITIZE_THRESHOLD_S overrides the breach threshold
(default 0.25 s — well above scheduler jitter, well below any real
blocking primitive's timeout).
"""

from __future__ import annotations

import os
import time
from collections import deque
from typing import Optional

__all__ = ["LoopLagSanitizer", "enabled", "maybe_install",
           "DEFAULT_THRESHOLD_S"]

ENV_GATE = "DNN_TPU_LOOP_SANITIZE"
ENV_THRESHOLD = "DNN_TPU_LOOP_SANITIZE_THRESHOLD_S"
DEFAULT_THRESHOLD_S = 0.25
DEFAULT_INTERVAL_S = 0.05


def enabled() -> bool:
    return os.environ.get(ENV_GATE, "").lower() in ("1", "on", "true",
                                                    "yes")


class LoopLagSanitizer:
    """Periodic event-loop self-timer. `install()` must run with the
    target loop current (or be handed one); `stop()` cancels the tick.
    Breaches (overshoot >= threshold) are counted, the worst is kept,
    and at most `max_events` land in the flight ring — a loop wedged in
    a tight blocking cycle must not flood the post-mortem record."""

    def __init__(self, threshold_s: float = DEFAULT_THRESHOLD_S,
                 interval_s: float = DEFAULT_INTERVAL_S, *,
                 max_events: int = 32, where: str = ""):
        self.threshold_s = float(threshold_s)
        self.interval_s = float(interval_s)
        self.max_events = int(max_events)
        self.where = where
        self.samples: "deque[float]" = deque(maxlen=256)
        self.breaches = 0
        self.max_lag_s = 0.0
        self._emitted = 0
        self._handle = None
        self._loop = None
        self._stopped = False

    # -- lifecycle -----------------------------------------------------

    def install(self, loop=None) -> "LoopLagSanitizer":
        import asyncio

        from dnn_tpu.obs import flight

        self._loop = loop or asyncio.get_running_loop()
        self._stopped = False
        flight.record("loop_sanitize_on", where=self.where,
                      threshold_ms=round(self.threshold_s * 1e3, 1),
                      interval_ms=round(self.interval_s * 1e3, 1))
        m = self._metrics()
        if m is not None:
            # scrape-time callable: the worst observed lag, live
            m.set_fn("obs.loop_lag_max_seconds", lambda: self.max_lag_s)
        self._arm(time.perf_counter() + self.interval_s)
        return self

    def stop(self):
        self._stopped = True
        if self._handle is not None:
            self._handle.cancel()
            self._handle = None

    # -- internals -----------------------------------------------------

    @staticmethod
    def _metrics():
        from dnn_tpu import obs

        return obs.metrics()

    def _arm(self, expected: float):
        delay = max(expected - time.perf_counter(), 0.0)
        self._handle = self._loop.call_later(delay, self._tick, expected)

    def _tick(self, expected: float):
        if self._stopped:
            return
        now = time.perf_counter()
        lag = max(now - expected, 0.0)
        self.samples.append(lag)
        if lag > self.max_lag_s:
            self.max_lag_s = lag
        if lag >= self.threshold_s:
            self.breaches += 1
            if self._emitted < self.max_events:
                from dnn_tpu.obs import flight

                self._emitted += 1
                flight.record("loop_lag", where=self.where,
                              lag_ms=round(lag * 1e3, 1),
                              threshold_ms=round(
                                  self.threshold_s * 1e3, 1),
                              breaches=self.breaches)
        self._arm(now + self.interval_s)

    # -- reading back --------------------------------------------------

    def status(self) -> dict:
        return {"where": self.where, "breaches": self.breaches,
                "max_lag_ms": round(self.max_lag_s * 1e3, 1),
                "threshold_ms": round(self.threshold_s * 1e3, 1),
                "samples": len(self.samples)}

    def assert_bounded(self, bound_s: float):
        """Raise AssertionError when any observed lag exceeded
        `bound_s` (a bound that tolerates first-compile GIL stalls
        still catches a reintroduced blocking-primitive wait, which
        blows well past it)."""
        if self.max_lag_s > bound_s:
            raise AssertionError(
                f"event loop lag {self.max_lag_s * 1e3:.0f} ms exceeds "
                f"the {bound_s * 1e3:.0f} ms bound ({self.breaches} "
                f"breaches >= {self.threshold_s * 1e3:.0f} ms) — a "
                "callback blocked the loop; see `loop_lag` flight "
                "events")


def read_endpoint(base_url: str, timeout: float = 10.0) -> dict:
    """Read a serving process's sanitizer record back off its /debugz
    (what a caller asserts on is the served ARTIFACT, not in-process
    state): -> {installed, breaches, max_lag_ms}. `installed` False
    means the assertion would be vacuous — the caller should fail it."""
    import json as _json
    import urllib.request

    base = base_url.rstrip("/")
    out = {"installed": False, "breaches": 0, "max_lag_ms": 0.0}
    with urllib.request.urlopen(base + "/debugz?format=json",
                                timeout=timeout) as r:
        events = _json.loads(r.read().decode())
    for ev in events:
        if ev.get("kind") == "loop_sanitize_on":
            out["installed"] = True
        elif ev.get("kind") == "loop_lag":
            out["breaches"] += 1
            out["max_lag_ms"] = max(out["max_lag_ms"],
                                    float(ev.get("lag_ms", 0.0)))
    return out


def maybe_install(loop=None, *, where: str = ""
                  ) -> Optional[LoopLagSanitizer]:
    """Env-gated install (the serving entry points call this): returns
    the sanitizer when DNN_TPU_LOOP_SANITIZE is on, else None at the
    cost of one env read."""
    if not enabled():
        return None
    try:
        threshold = float(os.environ.get(ENV_THRESHOLD,
                                         DEFAULT_THRESHOLD_S))
    except ValueError:
        threshold = DEFAULT_THRESHOLD_S
    return LoopLagSanitizer(threshold_s=threshold,
                            where=where).install(loop)
