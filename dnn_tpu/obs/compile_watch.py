"""Compile telemetry: XLA compilations as live counters.

PR 2's static recompile census (PRG004) bounds how many programs a
workload SHOULD compile; this module is the runtime cross-check. A
jax.monitoring duration listener turns every backend compile into two
registry series:

    jax_compilations_total        — count of XLA backend compiles
    jax_compile_seconds_total     — wall seconds spent compiling
    jax_trace_seconds_total       — jaxpr tracing seconds (the Python
                                    side of a cache miss)
    jax_traces_total              — count of jaxpr traces: a program
                                    first met shows here even when the
                                    persistent compile cache served its
                                    executable and nothing compiled

A serving daemon whose step programs are stable sits at a small constant;
a recompile storm (shape churn, traced-value leaks) shows up as a
climbing counter on /metrics instead of a mystery stall. The listener
writes only when observability is enabled (the gate is re-checked per
event), costs ~a dict update per compile, and never raises into jax.
"""

from __future__ import annotations

import logging

log = logging.getLogger("dnn_tpu.obs")

# event keys fired by jax.monitoring during a jit cache miss
_COMPILE_KEY = "/jax/core/compile/backend_compile_duration"
_TRACE_KEY = "/jax/core/compile/jaxpr_trace_duration"


def _on_duration(name: str, dur: float, **kwargs):
    try:
        from dnn_tpu import obs

        m = obs.metrics()
        if m is None:
            return
        if name == _COMPILE_KEY:
            m.inc("jax_compilations_total")
            m.inc("jax_compile_seconds_total", dur)
            # the flight ring keeps compiles next to the admissions/
            # retirements they interleave with — a post-mortem dump shows
            # "recompile right before the deadline miss" as adjacency
            from dnn_tpu.obs import flight

            flight.record("compile", seconds=round(dur, 4))
        elif name == _TRACE_KEY:
            m.inc("jax_traces_total")
            m.inc("jax_trace_seconds_total", dur)
    except Exception:  # noqa: BLE001 — telemetry must never break compiles
        log.debug("compile telemetry listener failed", exc_info=True)


def _install() -> bool:
    """Register the listener with jax.monitoring. Called once per process
    via obs.install_compile_telemetry(); returns False (and stays
    uninstalled) on jax versions without the monitoring API."""
    try:
        from jax import monitoring

        monitoring.register_event_duration_secs_listener(_on_duration)
        return True
    except Exception:  # noqa: BLE001 — absent/old jax: telemetry is optional
        log.debug("jax.monitoring unavailable; compile telemetry off",
                  exc_info=True)
        return False
