"""Stdlib-HTTP observability endpoint: metrics, traces, flight, status,
profiling.

Attached to the LM daemon (runtime/lm_server.LMServer(metrics_port=...))
and the stage servers (comm/service.serve_stage(metrics_port=...)) — a
ThreadingHTTPServer on a daemon thread, zero dependencies, so any
Prometheus scraper or a plain curl can watch the serving stack:

    GET  /metrics      Prometheus text format (utils.metrics
                       render_prometheus over the shared registry)
    GET  /healthz      liveness, now three-valued: 200 "ok" / 200
                       "degraded" / 503 "wedged" from the watchdog
                       (obs/watchdog.py) when one is attached; an
                       optional `healthy` callable (worker thread
                       liveness) downgrades to 503 "unhealthy"
    GET  /statusz      watchdog state with per-component detail (JSON;
                       ?format=prom re-renders it as Prometheus gauges
                       for scrape-only collectors)
    GET  /debugz       flight-recorder ring as JSONL (obs/flight.py;
                       Content-Type application/x-ndjson); ?format=json
                       returns a proper JSON array (application/json) —
                       pollers never sniff; ?kind= ?trace= filter,
                       ?last=N keeps newest N
    GET  /fleetz       merged fleet view (obs/fleet.py) when a
                       FleetCollector is attached: worst-of health,
                       per-stage tables, totals, clock offsets
                       (?format=prom re-exports it as Prometheus text;
                       ?format=trace returns the stitched cross-host
                       Perfetto JSON, ?id=<trace> for one request;
                       ?format=report the human-readable text)
    GET  /stepz        step-timeline attribution (obs/timeline.py) when
                       a StepClock is attached: per-phase decode-step
                       decomposition (admit/host/dispatch/wait/commit/
                       obs), host fraction
                       (JSON; ?format=prom re-renders as gauges,
                       ?last=N bounds the window). The steps on a
                       timeline are in a POST /profilez capture: the
                       batcher writes them there as step.* / admit*
                       annotations, beside the device's operations
    GET  /trainz       training-step observatory (obs/trainlens.py)
                       when a TrainClock is attached: per-phase
                       training-iteration decomposition (data/dispatch/
                       wait/ckpt/eval/obs), data_stall_fraction, MFU /
                       tokens-per-sec, checkpoint freshness (JSON;
                       ?format=prom re-renders as gauges, ?format=trace
                       exports the last N steps as a Perfetto host
                       track, ?last=N bounds the window)
    GET  /kvz          memory-economy observatory (obs/kvlens.py) when
                       a KVLens is attached: sampled reuse-distance
                       stats, the predicted hit-ratio-vs-capacity
                       curve (0.5x..8x of the pool), block lifecycle
                       counts, thrash pricing, and the bounded
                       per-block ledger tail (JSON; ?format=prom
                       re-renders the curve + thrash as gauges)
    GET  /capz         capacity observatory (obs/caplens.py) when a
                       CapLens is attached: windowed demand (rate,
                       burstiness, change points), learned per-role
                       service capacity, the cold-start ledger, what-if
                       plans at 1/2/4 replicas, the wanted-replicas
                       audit trail (JSON; ?format=prom re-renders the
                       headline series as gauges)
    GET  /trace        Chrome-trace JSON of collected spans; ?id=<trace>
                       filters to one request's tree (load the response
                       in Perfetto / chrome://tracing)
    GET  /trace.jsonl  the same spans as JSONL (one span per line)
    GET  /traces       the distinct trace ids currently in the ring
    GET  /profilez     capture spool + auto-trigger arm state (JSON)
    POST /profilez?ms=N            capture N ms of device+host profile
                       into the bounded spool (obs/profile.py); returns
                       the capture path + its .xplane.pb files
    POST /profilez?auto=1&threshold_ms=T[&ms=N]   arm the auto trigger:
                       capture the next decode step after one exceeds
                       T ms (LM daemon only); ?auto=0 disarms
                       (&perfetto=1 on either: also export the
                       Perfetto-loadable *.trace.json.gz, which takes
                       several times as long as the capture itself;
                       &py=0|1: leave out / record Python frames)
    POST /drainz       connection draining (LM daemon): stop admission,
                       finish in-flight decodes, hand queued work back
                       retriable, then exit — 202 + drain state JSON;
                       idempotent. /healthz reads 503 "draining" while
                       it runs (runtime/lm_server.LMServer.drain)
"""

from __future__ import annotations

import json
import logging
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Callable, Optional
from urllib.parse import parse_qs, urlparse

log = logging.getLogger("dnn_tpu.obs")

_STATE_GAUGE = {"ok": 0.0, "degraded": 1.0, "draining": 1.0,
                "wedged": 2.0}


def _status_prom(status: dict) -> str:
    """Render a /statusz payload (watchdog or fleet shape) as Prometheus
    gauges: dnn_tpu_status_state 0|1|2 (ok|degraded|wedged) plus one
    per-component series — the ?format=prom passthrough for collectors
    that only speak scrapes. A component that carries no `state` (facts
    only, nothing probed) gets no series."""
    from dnn_tpu.utils.metrics import Metrics, labeled, render_prometheus

    m = Metrics()
    m.set("dnn_tpu_status_state",
          _STATE_GAUGE.get(status.get("state"), 1.0))
    for name, comp in (status.get("components") or {}).items():
        if "state" not in (comp or {}):
            continue
        m.set(labeled("dnn_tpu_status_component_state", component=name),
              _STATE_GAUGE.get((comp or {}).get("state"), 1.0))
    return render_prometheus(m)


class MetricsHTTPServer:
    """Serve the shared registry + span collector + flight ring (or
    explicit ones) over HTTP. port=0 binds an ephemeral port — read
    `.port` after init.

    Binds LOOPBACK by default: the endpoint is unauthenticated, /trace
    and /debugz expose per-request timelines, and POST /profilez
    triggers device work — so wider exposure (a scrape fleet) is an
    explicit `host="0.0.0.0"` opt-in, not a default.

    `status`: callable -> dict with at least {"state": "ok|degraded|
    wedged"} (obs/watchdog.Watchdog.status), or None to fall back to
    the worker-liveness shape built from `healthy`. `profiler`: an
    obs/profile.Profiler. `flight`: a FlightRecorder (default: the
    process-wide ring)."""

    def __init__(self, *, port: int = 0, host: str = "127.0.0.1",
                 registry=None, collector=None,
                 healthy: Optional[Callable[[], bool]] = None,
                 status: Optional[Callable[[], dict]] = None,
                 profiler=None, flight=None, fleet=None,
                 drain: Optional[Callable[[], dict]] = None,
                 stepclock=None, kvlens=None, trainlens=None,
                 caplens=None):
        from dnn_tpu import obs
        from dnn_tpu.obs import flight as _flight
        from dnn_tpu.utils import metrics as _metrics

        self._registry = registry if registry is not None \
            else _metrics.default_metrics
        self._collector = collector if collector is not None \
            else obs.collector()
        self._flight = flight if flight is not None \
            else _flight.recorder()
        self._healthy = healthy
        self._status = status
        self._profiler = profiler
        # fleet collector (obs/fleet.FleetCollector): serves /fleetz;
        # when no explicit `status` is given the fleet's worst-of
        # rollup also becomes /statusz + /healthz (503 on a wedged or
        # unreachable stage — the fleet endpoint's health IS the fleet's)
        self._fleet = fleet
        # POST /drainz (connection draining, ISSUE 8): the serving
        # process's drain kicker — idempotent, returns drain state
        self._drain = drain
        # step-timeline clock (obs/timeline.StepClock): serves /stepz
        self._stepclock = stepclock
        # memory-economy lens (obs/kvlens.KVLens): serves /kvz. The LM
        # daemon attaches it AFTER construction (the batcher — and its
        # lens — is built after the endpoint comes up), so the handler
        # reads it per request rather than capturing it here
        self._kvlens = kvlens
        # training-step clock (obs/trainlens.TrainClock): serves /trainz
        self._trainlens = trainlens
        # capacity observatory (obs/caplens.CapLens): serves /capz
        self._caplens = caplens
        if fleet is not None and status is None:
            self._status = fleet.status
        outer = self

        class Handler(BaseHTTPRequestHandler):
            def log_message(self, fmt, *args):  # route to logging, not stderr
                log.debug("metrics http: " + fmt, *args)

            def _send(self, code: int, body: str, ctype: str):
                data = body.encode()
                self.send_response(code)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(data)))
                self.end_headers()
                self.wfile.write(data)

            def _send_json(self, code: int, obj):
                # default=str: flight events may carry exotic values;
                # degrading one to its repr beats failing the dump
                self._send(code, json.dumps(obj, default=str),
                           "application/json")

            def _statusz(self):
                if outer._status is not None:
                    s = outer._status()
                    if s is not None:  # None = "no watchdog: fall back"
                        return s
                # no watchdog attached: report the one component every
                # server has — its worker/liveness callable
                ok = outer._healthy() if outer._healthy else True
                return {"state": "ok" if ok else "wedged",
                        "components": {"worker": {
                            "state": "ok" if ok else "wedged",
                            "detail": "serving worker thread liveness"}}}

            def _healthz(self):
                if outer._healthy is not None and not outer._healthy():
                    self._send(503, "unhealthy\n",
                               "text/plain; charset=utf-8")
                    return
                state = self._statusz()["state"]
                # draining is 503 too: a load balancer must stop
                # routing here while in-flight decodes finish
                self._send(503 if state in ("wedged", "draining")
                           else 200,
                           state + "\n", "text/plain; charset=utf-8")

            def _fleetz(self, q):
                if outer._fleet is None:
                    self._send(404, "no fleet collector attached\n",
                               "text/plain; charset=utf-8")
                    return
                fmt = q.get("format", ["json"])[0]
                if fmt == "json":
                    self._send_json(200, outer._fleet.fleetz())
                elif fmt == "prom":
                    self._send(200, outer._fleet.render_prom(),
                               "text/plain; version=0.0.4; charset=utf-8")
                elif fmt == "trace":
                    tid = q.get("id", [None])[0]
                    self._send(200, json.dumps(outer._fleet.stitch(tid)),
                               "application/json")
                elif fmt == "report":
                    tid = q.get("id", [None])[0]
                    self._send(200, outer._fleet.report(tid) + "\n",
                               "text/plain; charset=utf-8")
                else:
                    self._send(400, f"unknown format {fmt!r} "
                               "(json|prom|trace|report)\n",
                               "text/plain; charset=utf-8")

            def _stepz(self, q):
                if outer._stepclock is None:
                    self._send(404, "no step clock attached\n",
                               "text/plain; charset=utf-8")
                    return
                last = None
                if "last" in q:
                    try:
                        last = int(q["last"][0])
                    except ValueError:
                        last = 0
                    if last < 1:
                        # a negative slice bound would silently invert
                        # the window (newest-N becomes all-but-oldest-N)
                        self._send(400, "last must be an int >= 1\n",
                                   "text/plain; charset=utf-8")
                        return
                fmt = q.get("format", ["json"])[0]
                if fmt == "json":
                    self._send_json(200, outer._stepclock.summary(last))
                elif fmt == "prom":
                    self._send(200, outer._stepclock.render_prom(last),
                               "text/plain; version=0.0.4; charset=utf-8")
                else:
                    self._send(400, f"unknown format {fmt!r} "
                               "(json|prom)\n",
                               "text/plain; charset=utf-8")

            def _trainz(self, q):
                if outer._trainlens is None:
                    self._send(404, "no train clock attached\n",
                               "text/plain; charset=utf-8")
                    return
                last = None
                if "last" in q:
                    try:
                        last = int(q["last"][0])
                    except ValueError:
                        last = 0
                    if last < 1:
                        self._send(400, "last must be an int >= 1\n",
                                   "text/plain; charset=utf-8")
                        return
                fmt = q.get("format", ["json"])[0]
                if fmt == "json":
                    self._send_json(200, outer._trainlens.summary(last))
                elif fmt == "prom":
                    self._send(200, outer._trainlens.render_prom(last),
                               "text/plain; version=0.0.4; charset=utf-8")
                elif fmt == "trace":
                    self._send(200, json.dumps(
                        outer._trainlens.chrome_trace(last)),
                        "application/json")
                else:
                    self._send(400, f"unknown format {fmt!r} "
                               "(json|prom|trace)\n",
                               "text/plain; charset=utf-8")

            def _kvz(self, q):
                if outer._kvlens is None:
                    self._send(404, "no kvlens attached\n",
                               "text/plain; charset=utf-8")
                    return
                fmt = q.get("format", ["json"])[0]
                if fmt == "json":
                    self._send_json(200, outer._kvlens.summary())
                elif fmt == "prom":
                    self._send(200, outer._kvlens.render_prom(),
                               "text/plain; version=0.0.4; charset=utf-8")
                else:
                    self._send(400, f"unknown format {fmt!r} "
                               "(json|prom)\n",
                               "text/plain; charset=utf-8")

            def _capz(self, q):
                if outer._caplens is None:
                    self._send(404, "no caplens attached\n",
                               "text/plain; charset=utf-8")
                    return
                fmt = q.get("format", ["json"])[0]
                if fmt == "json":
                    self._send_json(200, outer._caplens.summary())
                elif fmt == "prom":
                    self._send(200, outer._caplens.render_prom(),
                               "text/plain; version=0.0.4; charset=utf-8")
                else:
                    self._send(400, f"unknown format {fmt!r} "
                               "(json|prom)\n",
                               "text/plain; charset=utf-8")

            def do_GET(self):
                try:
                    url = urlparse(self.path)
                    q = parse_qs(url.query)
                    if url.path == "/metrics":
                        self._send(200, _metrics.render_prometheus(
                            outer._registry),
                            "text/plain; version=0.0.4; charset=utf-8")
                    elif url.path == "/healthz":
                        self._healthz()
                    elif url.path == "/statusz":
                        fmt = q.get("format", ["json"])[0]
                        if fmt == "prom":
                            # scrape-only collectors ingest status as
                            # gauges instead of sniffing JSON
                            self._send(200,
                                       _status_prom(self._statusz()),
                                       "text/plain; version=0.0.4; "
                                       "charset=utf-8")
                        elif fmt == "json":
                            self._send_json(200, self._statusz())
                        else:
                            self._send(400, f"unknown format {fmt!r} "
                                       "(json|prom)\n",
                                       "text/plain; charset=utf-8")
                    elif url.path == "/debugz":
                        filters = {}
                        if "kind" in q:
                            filters["kind"] = q["kind"][0]
                        if "trace" in q:
                            filters["trace_id"] = q["trace"][0]
                        if "last" in q:
                            try:
                                filters["last"] = int(q["last"][0])
                            except ValueError:
                                self._send(400, "last must be an int\n",
                                           "text/plain; charset=utf-8")
                                return
                        fmt = q.get("format", ["jsonl"])[0]
                        if fmt == "json":
                            # a proper JSON array for pollers; the
                            # JSONL default stays for `obs flight --url`
                            # and log-shipper tails
                            self._send_json(200,
                                            outer._flight.events(**filters))
                        elif fmt == "jsonl":
                            self._send(200,
                                       outer._flight.jsonl(**filters),
                                       "application/x-ndjson")
                        else:
                            self._send(400, f"unknown format {fmt!r} "
                                       "(jsonl|json)\n",
                                       "text/plain; charset=utf-8")
                    elif url.path == "/fleetz":
                        self._fleetz(q)
                    elif url.path == "/stepz":
                        self._stepz(q)
                    elif url.path == "/kvz":
                        self._kvz(q)
                    elif url.path == "/capz":
                        self._capz(q)
                    elif url.path == "/trainz":
                        self._trainz(q)
                    elif url.path == "/profilez":
                        if outer._profiler is None:
                            self._send(404, "no profiler attached\n",
                                       "text/plain; charset=utf-8")
                        else:
                            self._send_json(200, outer._profiler.status())
                    elif url.path == "/trace":
                        tid = q.get("id", [None])[0]
                        self._send(200, json.dumps(
                            outer._collector.chrome_trace(tid)),
                            "application/json")
                    elif url.path == "/trace.jsonl":
                        tid = q.get("id", [None])[0]
                        self._send(200, outer._collector.jsonl(tid),
                                   "application/jsonl")
                    elif url.path == "/traces":
                        self._send(200, json.dumps(
                            outer._collector.trace_ids()),
                            "application/json")
                    else:
                        self._send(404, "not found\n",
                                   "text/plain; charset=utf-8")
                except BrokenPipeError:  # scraper hung up mid-response
                    pass
                except Exception:  # noqa: BLE001 — one bad request must
                    # not kill the observer thread
                    log.exception("metrics endpoint request failed")
                    try:
                        self._send(500, "internal error\n",
                                   "text/plain; charset=utf-8")
                    except Exception:  # noqa: BLE001
                        pass

            def do_POST(self):
                try:
                    url = urlparse(self.path)
                    q = parse_qs(url.query)
                    if url.path == "/drainz":
                        if outer._drain is None:
                            self._send(404, "no drain handler attached "
                                       "(stage servers drain via their "
                                       "supervisor)\n",
                                       "text/plain; charset=utf-8")
                            return
                        self._send_json(202, outer._drain())
                        return
                    if url.path != "/profilez":
                        self._send(404, "not found\n",
                                   "text/plain; charset=utf-8")
                        return
                    if outer._profiler is None:
                        self._send(404, "no profiler attached\n",
                                   "text/plain; charset=utf-8")
                        return
                    from dnn_tpu.obs.profile import (
                        ProfilerBusy, trace_files, xplane_files)

                    perfetto = q.get("perfetto", ["0"])[0] not in (
                        "0", "false", "off")
                    # py=0|1: Python frames in the capture or not; left
                    # out, obs/profile.PYTHON_TRACER decides
                    py = q["py"][0] not in ("0", "false", "off") \
                        if "py" in q else None
                    if "auto" in q:
                        arm = q["auto"][0] not in ("0", "false", "off")
                        if not arm:
                            outer._profiler.disarm()
                            self._send_json(200, {"armed": None})
                            return
                        try:
                            outer._profiler.arm_auto(
                                float(q.get("threshold_ms", ["100"])[0]),
                                float(q.get("ms", ["0"])[0]), perfetto,
                                py)
                        except ValueError as e:
                            self._send(400, str(e) + "\n",
                                       "text/plain; charset=utf-8")
                            return
                        self._send_json(200, outer._profiler.status())
                        return
                    try:
                        ms = float(q.get("ms", ["1000"])[0])
                    except ValueError:
                        self._send(400, "ms must be a number\n",
                                   "text/plain; charset=utf-8")
                        return
                    try:
                        path = outer._profiler.capture(ms, perfetto, py)
                    except ProfilerBusy as e:
                        self._send(409, str(e) + "\n",
                                   "text/plain; charset=utf-8")
                        return
                    self._send_json(200, {
                        "capture": path, "ms": ms,
                        "xplane_files": xplane_files(path),
                        "trace_files": trace_files(path)})
                except BrokenPipeError:
                    pass
                except Exception:  # noqa: BLE001
                    log.exception("profilez request failed")
                    try:
                        self._send(500, "internal error\n",
                                   "text/plain; charset=utf-8")
                    except Exception:  # noqa: BLE001
                        pass

        self._httpd = ThreadingHTTPServer((host, port), Handler)
        self._httpd.daemon_threads = True
        self.port = self._httpd.server_address[1]
        self._thread = threading.Thread(
            target=self._httpd.serve_forever, daemon=True,
            name=f"obs-metrics-http:{self.port}")
        self._thread.start()
        log.info("observability endpoint on http://%s:%d/metrics",
                 host or "0.0.0.0", self.port)

    def close(self):
        self._httpd.shutdown()
        self._httpd.server_close()
        self._thread.join(timeout=5)
