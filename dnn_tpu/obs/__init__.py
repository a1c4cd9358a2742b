"""dnn_tpu.obs — observability for the serving stack.

The reference's only observability is ad-hoc stdout prints (SURVEY §5:
"Tracing/profiling: ABSENT"); PRs 1-2 built the perf and correctness
legs, this package builds the eyes. Three coordinated layers share one
registry and one span collector:

  * request tracing (obs/trace.py): per-request span trees — queue wait,
    admission, prefill, per-bucket decode, detokenize, per-hop RPC —
    propagated across the wire on the existing `request_id` field and
    exportable as JSONL / Chrome-trace JSON (`python -m dnn_tpu.obs
    trace`, or GET /trace on the metrics endpoint);
  * metrics (utils/metrics.py grown for this layer): counters, gauges,
    quantile summaries and histograms, rendered in Prometheus text
    format and served from a stdlib-HTTP `/metrics` endpoint
    (obs/http.py) attached to the LM daemon and the stage servers;
  * compile telemetry (obs/compile_watch.py): a jax.monitoring listener
    counting XLA compilations and compile-seconds into the same registry
    — the RUNTIME cross-check of the static recompile census (PRG004,
    dnn_tpu/analysis): a live recompile storm is a counter, not a stall.

v2 adds the failure-facing layer on the same substrate:

  * flight recorder (obs/flight.py): a bounded ring of structured
    events (admissions, evictions, retries, deadline misses, compiles,
    errors, watchdog firings) — dumped via GET /debugz, `python -m
    dnn_tpu.obs flight`, and automatically on unhandled crash;
  * on-demand device profiling (obs/profile.py): POST /profilez drives
    a programmatic jax.profiler capture into a bounded spool, with an
    arm-the-next-slow-step auto trigger; host annotations + model
    named_scopes make the timelines name layers and stages;
  * memory observability (obs/mem.py): per-device memory_stats, host
    RSS, and pool watermark gauges through the same registry;
  * hung-device watchdog (obs/watchdog.py): subprocess-bounded device
    probes + decode heartbeat staleness -> ok|degraded|wedged on
    /statusz, with /healthz degrading accordingly.

v3 adds the CROSS-PROCESS layer — the first obs subsystem that sees the
whole pipeline instead of one process:

  * fleet collector (obs/fleet.py): polls every stage's /metrics +
    /statusz + /trace.jsonl, serves the merged view on /fleetz
    (worst-of health, per-stage percentile tables, fleet totals),
    estimates per-stage clock offsets NTP-style from the existing RPC
    spans, and stitches per-hop span trees from different hosts into
    ONE Perfetto timeline with per-request critical-path and bubble-
    fraction attribution (`python -m dnn_tpu.obs fleet`);
  * goodput accounting (obs/goodput.py): live MFU / MBU / goodput
    tokens-per-sec scrape-time gauges from the decode/prefill step
    stream + utils/flops.py serving-shape estimates, plus SLO
    error-budget burn-rate tracking (TTFT / inter-token /
    availability) with flight events on breach.

v4 adds the INTRA-STEP layer — the instrument for the overlap/fusion
arc (ROADMAP item 4):

  * step-timeline attribution (obs/timeline.py): a per-phase decode-
    step clock on the serving pool (admit / host / dispatch / wait /
    commit / obs) with dispatch-slack, sync-tax and host-fraction
    series on /stepz (+ a Perfetto host-track export), capture
    analysis over the profiler's spooled artifacts (device busy/idle,
    host-gap histogram, top ops) aligned to the step axis through
    profile.py's sidecar meta. The chip benchmark reads the same
    counters and spans (chipbench/spans.py; PERF.md section 3).

v5 adds the JUDGMENT layer:

  * SLO verdicts + incident bundles (obs/slo.py): a run's per-request
    records judged against a declared SLOSpec into one ok/breach
    report, and — on breach — an on-disk incident bundle (flight ring
    over the breach window, /stepz, /fleetz) that
    `python -m dnn_tpu.obs incident PATH` renders back as the
    event-by-event post-mortem (trainlens writes one on divergence).

v6 adds the MEMORY-ECONOMY layer — the sizing instrument for the KV
capacity hierarchy (ROADMAP item 4) and the autoscaler's
capacity-vs-compute question (item 3):

  * kvlens (obs/kvlens.py): SHARDS-style sampled reuse-distance
    tracking over the radix KV tier's admission stream (deterministic
    blake2s spatial sampling — zero wall-clock randomness), miss-ratio
    curves predicting the block-hit ratio at 0.5x..8x of the
    configured pool on /kvz (+ weak scrape gauges, /fleetz rollup
    columns, `python -m dnn_tpu.obs kvlens`), a bounded per-block
    lifecycle ledger (birth/share/COW/evict/migrate/refetch with
    cause attribution), and a thrash detector pricing
    evict→refetch-within-window churn in re-prefill chunk-seconds and
    migrated bytes.

v7 adds the TRAINING layer — the observatory for the one ROADMAP
pillar that had none (built before the training-at-scale PR it
judges, the instrument-first pattern):

  * trainlens (obs/trainlens.py): a per-step TRAINING clock in the
    StepClock idiom — train.fit splits every iteration into
    data/dispatch/wait/ckpt/eval/obs phases with a derived
    `data_stall_fraction` and step-time MFU/tokens-per-sec priced by
    the utils/flops.py training helpers against the same
    device_peak_flops rooflines goodput uses (weak gauges
    dnn_tpu_train_mfu / _tokens_per_sec / _data_stall; /trainz
    JSON|prom|trace; `python -m dnn_tpu.obs trainlens`) — plus
    gradient-health sentinels over the train steps' opt-in on-device
    stats leg (grad_spike / loss_nan / train_stall flight events, an
    incident bundle on divergence) and checkpoint observability
    (save/restore histograms, dnn_tpu_ckpt_last_good_step /
    staleness gauges, ckpt_saved/ckpt_restored events).

Gate: DNN_TPU_OBS=off (or 0/false) disables everything — producers see
`metrics()` return None, `start_span` return the free NULL_SPAN, and
`flight.record` short-circuit on one boolean. The gate is re-checked
per call, so a run can flip it (`set_enabled`) to measure the
instrumentation tax.

Import cost: this package imports stdlib + utils.metrics only; jax is
touched lazily inside install_compile_telemetry() and obs/profile.
"""

from __future__ import annotations

import os
import threading

from dnn_tpu.obs.trace import (  # noqa: F401 — the package's public API
    NULL_SPAN,
    Span,
    TraceCollector,
    collector,
    continue_or_start,
    current_span,
    new_trace_id,
    parse_wire_tag,
    record_span,
    span,
    spans_to_chrome,
    start_span,
    strip_wire_tag,
    tag_request_id,
)

from dnn_tpu.obs import flight  # noqa: F401 — obs.flight.record(...)

__all__ = [
    "enabled", "set_enabled", "metrics", "collector", "span",
    "start_span", "record_span", "current_span", "continue_or_start",
    "tag_request_id", "parse_wire_tag", "strip_wire_tag", "new_trace_id",
    "NULL_SPAN", "Span", "TraceCollector", "spans_to_chrome",
    "install_compile_telemetry", "serve_metrics", "flight",
]

_enabled = os.environ.get("DNN_TPU_OBS", "on").lower() not in (
    "off", "0", "false", "no")


def enabled() -> bool:
    return _enabled


def set_enabled(on: bool):
    """Runtime toggle (benchmarks, tests). Producers re-check per call,
    so flipping takes effect immediately — no reconstruction needed."""
    global _enabled
    _enabled = bool(on)


_default_metrics = None  # resolved lazily once: metrics() is on every
# per-step hot path, and a per-call submodule import is measurable there


def metrics():
    """The shared registry (utils.metrics.default_metrics) when
    observability is on, else None — hot paths guard with one `is not
    None` check and skip all bookkeeping when off."""
    if not _enabled:
        return None
    global _default_metrics
    if _default_metrics is None:
        from dnn_tpu.utils.metrics import default_metrics

        _default_metrics = default_metrics
    return _default_metrics


_install_lock = threading.Lock()
_compile_installed = False


def install_compile_telemetry() -> bool:
    """Install the jax.monitoring compile listener once per process
    (idempotent — every engine/server constructor calls this). Returns
    True when the listener is active. See obs/compile_watch.py."""
    global _compile_installed
    with _install_lock:
        if _compile_installed:
            return True
        from dnn_tpu.obs.compile_watch import _install

        _compile_installed = _install()
        return _compile_installed


def serve_metrics(port: int = 0, host: str = "127.0.0.1", *,
                  healthy=None, status=None, profiler=None, fleet=None,
                  drain=None, stepclock=None, kvlens=None,
                  trainlens=None, caplens=None):
    """Start the observability HTTP endpoint on a daemon thread; returns
    the MetricsHTTPServer (`.port` for port=0 ephemeral binds,
    `.close()` to stop; loopback by default — pass host="0.0.0.0" to
    expose to a scrape fleet). Serves the full surface — GET /metrics
    /trace /debugz /statusz /healthz, POST /profilez — and installs the
    device/host memory gauges (obs/mem.py; no-op with observability
    off). This is THE construction path: LMServer and comm.serve_stage
    both go through it, so the public helper cannot drift behind the
    endpoints the real servers expose. `healthy`/`status` as on
    MetricsHTTPServer; `profiler` defaults to a fresh
    obs.profile.Profiler (pass one to enable auto-trigger arming, or
    False to disable /profilez). `fleet` (an obs.fleet.FleetCollector)
    additionally serves the merged fleet view on /fleetz (JSON;
    ?format=prom|trace|report). `drain` (callable -> dict) enables
    POST /drainz — connection draining (runtime/lm_server.LMServer
    passes its handler). `stepclock` (an obs.timeline.StepClock)
    additionally serves the step-timeline attribution on /stepz (JSON;
    ?format=prom|trace). `kvlens` (an obs.kvlens.KVLens) additionally
    serves the memory-economy observatory on /kvz (JSON;
    ?format=prom) — LMServer attaches its batcher's lens after
    construction by assigning `server._kvlens` (the batcher is built
    after the endpoint comes up). `trainlens` (an
    obs.trainlens.TrainClock) additionally serves the training-step
    observatory on /trainz (JSON; ?format=prom|trace) — the training
    counterpart of /stepz. `caplens` (an obs.caplens.CapLens)
    additionally serves the capacity observatory on /capz (JSON;
    ?format=prom) — serve_router passes its router's lens. See
    obs/http.py."""
    from dnn_tpu.obs.http import MetricsHTTPServer
    from dnn_tpu.obs.mem import install_memory_gauges

    install_memory_gauges()
    if profiler is None:
        from dnn_tpu.obs.profile import Profiler

        profiler = Profiler()
    return MetricsHTTPServer(port=port, host=host, healthy=healthy,
                             status=status, profiler=profiler or None,
                             fleet=fleet, drain=drain,
                             stepclock=stepclock, kvlens=kvlens,
                             trainlens=trainlens, caplens=caplens)
