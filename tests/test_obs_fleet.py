"""Fleet observability tests (dnn_tpu/obs/fleet.py + obs/goodput.py).

The acceptance contract this module pins (ISSUE 5): a FleetCollector
over two REAL in-process stage HTTP endpoints produces (a) a merged
/fleetz JSON with worst-of health and per-stage tables, (b) a clock-
offset estimate that recovers ±500 ms of injected skew within 10%, and
(c) ONE stitched cross-host Perfetto trace with per-request critical-
path/bubble attribution — plus live MFU/MBU gauges whose values match
hand-computed utils/flops.py estimates within 5%, SLO burn-rate gauges
that fire a flight event on induced TTFT breaches, the content-type /
?format= contracts on /statusz /debugz /fleetz, the DNN_TPU_LOG=json
structured-log mode with trace-id injection, and the
`python -m dnn_tpu.obs fleet --selftest` CLI smoke tier-1 invokes."""

import io
import json
import logging
import subprocess
import sys
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from dnn_tpu import obs
from dnn_tpu.obs import trace as obs_trace
from dnn_tpu.obs.fleet import (
    FleetCollector,
    critical_path,
    estimate_offsets,
    parse_prometheus,
    stitch_spans,
)
from dnn_tpu.obs.goodput import GoodputTracker, SLOConfig, model_cost
from dnn_tpu.utils.metrics import Metrics, labeled, render_prometheus


@pytest.fixture(autouse=True)
def _obs_on():
    was = obs.enabled()
    obs.set_enabled(True)
    yield
    obs.set_enabled(was)


def _mk_span(col, trace_id, span_id, parent_id, name, ts, dur, **attrs):
    """Plant a finished span with a CONTROLLED wall-clock timestamp in a
    collector (skew injection needs exact ts; the public API stamps
    perf_counter)."""
    s = obs_trace.Span(name, trace_id, span_id, parent_id, attrs)
    s.t0 = ts - obs_trace._EPOCH0
    s.dur = dur
    s._done = True
    col.add(s)
    return s


def _get(url):
    return urllib.request.urlopen(url, timeout=10)


# ----------------------------------------------------------------------
# prometheus text parsing (the poller's half of render_prometheus)
# ----------------------------------------------------------------------

def test_parse_prometheus_roundtrip():
    from dnn_tpu.obs.fleet import _Samples

    m = Metrics()
    m.set("serving.tokens_per_sec", 42.5)
    m.inc(labeled("serving.requests_total", outcome="eos"), 5)
    m.inc(labeled("serving.requests_total", outcome="length"), 3)
    m.observe("serving.ttft_seconds", 0.01)
    m.observe("serving.ttft_seconds", 0.03)
    m.observe_hist(labeled("comm.rpc_latency_seconds", role="server"),
                   0.03, buckets=(0.01, 0.05, 0.1))
    s = _Samples(parse_prometheus(render_prometheus(m)))
    assert s.get("serving_tokens_per_sec") == 42.5
    assert s.get("serving_requests_total", outcome="eos") == 5
    assert s.sum("serving_requests_total") == 8
    assert s.get("serving_ttft_seconds", quantile="0.5") == 0.01
    # histogram_quantile interpolates inside the winning bucket
    q = s.hist_quantile("comm_rpc_latency_seconds", 0.5)
    assert 0.01 < q <= 0.05
    assert s.get("nope_total") is None and s.sum("nope_total") is None


def test_parse_prometheus_tolerates_garbage():
    p = parse_prometheus("# HELP x\nnot a line !!!\nok_total 3\n"
                         'lab{a="b"} bogusvalue\n')
    assert p["samples"] == [("ok_total", {}, 3.0)]


# ----------------------------------------------------------------------
# clock-offset estimation
# ----------------------------------------------------------------------

@pytest.mark.parametrize("skew", [0.5, -0.5])
def test_clock_offset_recovers_injected_skew(skew):
    now = 1000.0
    client = {"trace_id": "t", "span_id": "c1", "parent_id": None,
              "name": "rpc.forward", "ts": now, "dur": 0.1, "tid": 1,
              "attrs": {"cs": now, "cr": now + 0.1}}
    server = {"trace_id": "t", "span_id": "s1", "parent_id": "c1",
              "name": "stage.request", "ts": now + 0.02 + skew,
              "dur": 0.06, "tid": 2, "attrs": {"stage": "B"}}
    offs = estimate_offsets({"A": [client], "B": [server]})
    assert offs["A"] == 0.0
    assert abs(offs["B"] - skew) < 0.1 * abs(skew)  # ±500 ms within 10%


def test_clock_offset_chains_through_pipeline_and_falls_back():
    """A->B->C: C never talks to A directly; its offset must chain
    through B. The B->C client span has no cs/cr attrs (an older build)
    — the estimator falls back to the span's own ts/dur window."""
    now = 2000.0
    a_client = {"trace_id": "t", "span_id": "ab", "parent_id": None,
                "name": "rpc.SendTensor", "ts": now, "dur": 0.1,
                "tid": 1, "attrs": {"cs": now, "cr": now + 0.1}}
    b_server = {"trace_id": "t", "span_id": "b1", "parent_id": "ab",
                "name": "stage.request", "ts": now + 0.025 + 0.2,
                "dur": 0.05, "tid": 1, "attrs": {"stage": "B"}}
    b_client = {"trace_id": "t", "span_id": "bc", "parent_id": "b1",
                "name": "rpc.forward", "ts": now + 0.03 + 0.2,
                "dur": 0.04, "tid": 1, "attrs": {}}  # no cs/cr
    c_server = {"trace_id": "t", "span_id": "c1", "parent_id": "bc",
                "name": "stage.request", "ts": now + 0.04 + 0.2 - 0.3,
                "dur": 0.02, "tid": 1, "attrs": {"stage": "C"}}
    offs = estimate_offsets({"A": [a_client],
                             "B": [b_server, b_client],
                             "C": [c_server]})
    assert abs(offs["B"] - 0.2) < 0.02
    # C = B's offset + (C rel B) = 0.2 + (-0.3) = -0.1
    assert abs(offs["C"] - (-0.1)) < 0.05


# ----------------------------------------------------------------------
# critical path / bubble golden
# ----------------------------------------------------------------------

def _golden_tree():
    # 10 ms request; stage work covers [0,3] [4,7] [7,10] ms -> exactly
    # one 1 ms bubble between stage0 and stage1
    return [
        {"span_id": "r", "parent_id": None, "name": "request",
         "ts": 0.0, "dur": 0.010, "attrs": {}},
        {"span_id": "a", "parent_id": "r", "name": "stage.compute",
         "ts": 0.0, "dur": 0.003, "attrs": {"stage": "s0"}},
        {"span_id": "b", "parent_id": "r", "name": "stage.compute",
         "ts": 0.004, "dur": 0.003, "attrs": {"stage": "s1"}},
        {"span_id": "c", "parent_id": "r", "name": "stage.compute",
         "ts": 0.007, "dur": 0.003, "attrs": {"stage": "s2"}},
    ]


def test_critical_path_golden_three_stages():
    rep = critical_path(_golden_tree())
    assert rep["total_s"] == pytest.approx(0.010)
    assert rep["work_s"] == pytest.approx(0.009)
    assert rep["bubble_s"] == pytest.approx(0.001)
    assert rep["bubble_fraction"] == pytest.approx(0.1)
    assert [p["stage"] for p in rep["path"]] == ["s0", "s1", "s2"]
    assert rep["path"][1]["enter_s"] == pytest.approx(0.004)
    assert rep["per_stage_busy_s"] == {
        "s0": pytest.approx(0.003), "s1": pytest.approx(0.003),
        "s2": pytest.approx(0.003)}


def test_critical_path_overlap_picks_furthest_reaching():
    # two overlapping leaves: the one reaching furthest gates progress
    spans = [
        {"span_id": "r", "parent_id": None, "name": "request",
         "ts": 0.0, "dur": 0.010, "attrs": {}},
        {"span_id": "a", "parent_id": "r", "name": "short",
         "ts": 0.0, "dur": 0.004, "attrs": {"stage": "x"}},
        {"span_id": "b", "parent_id": "r", "name": "long",
         "ts": 0.001, "dur": 0.009, "attrs": {"stage": "y"}},
    ]
    rep = critical_path(spans)
    assert rep["bubble_fraction"] == pytest.approx(0.0)
    assert rep["path"][-1]["name"] == "long"
    assert rep["path"][-1]["exit_s"] == pytest.approx(0.010)


def test_critical_path_queue_wait_is_bubble():
    """queue_wait is a leaf by construction but measures WAITING — its
    cover must read as bubble, or an overloaded server looks
    bubble-free."""
    spans = [
        {"span_id": "r", "parent_id": None, "name": "request",
         "ts": 0.0, "dur": 0.010, "attrs": {}},
        {"span_id": "q", "parent_id": "r", "name": "queue_wait",
         "ts": 0.0, "dur": 0.006, "attrs": {}},
        {"span_id": "w", "parent_id": "r", "name": "decode",
         "ts": 0.006, "dur": 0.004, "attrs": {"stage": "lm"}},
    ]
    rep = critical_path(spans)
    assert rep["bubble_fraction"] == pytest.approx(0.6)
    assert [p["name"] for p in rep["path"]] == ["decode"]


def test_critical_path_empty_and_leafless():
    assert critical_path([])["bubble_fraction"] == 0.0
    solo = critical_path([{"span_id": "r", "parent_id": None,
                           "name": "request", "ts": 0.0, "dur": 0.01,
                           "attrs": {}}])
    assert solo["bubble_fraction"] == pytest.approx(0.0)


def test_stitch_dedups_and_tracks_per_stage():
    now = time.time()
    a = {"trace_id": "t", "span_id": "c1", "parent_id": None,
         "name": "rpc.forward", "ts": now, "dur": 0.1, "tid": 1,
         "attrs": {"cs": now, "cr": now + 0.1}}
    b = {"trace_id": "t", "span_id": "s1", "parent_id": "c1",
         "name": "stage.request", "ts": now + 0.55, "dur": 0.06,
         "tid": 2, "attrs": {"stage": "B"}}
    # duplicated span dicts (overlapping ring polls) must stitch once
    ct = stitch_spans({"A": [a, dict(a)], "B": [b, dict(b)]})
    xs = [e for e in ct["traceEvents"] if e.get("ph") == "X"]
    assert len(xs) == 2
    assert {e["args"]["stage"] for e in xs} == {"A", "B"}
    assert len({e["pid"] for e in xs}) == 2  # one process track each
    names = [e for e in ct["traceEvents"]
             if e.get("name") == "process_name"]
    assert len(names) == 2
    # offset applied: the corrected server span nests inside the client
    by = {e["name"]: e for e in xs}
    c, s = by["rpc.forward"], by["stage.request"]
    assert c["ts"] - 1 <= s["ts"] and \
        s["ts"] + s["dur"] <= c["ts"] + c["dur"] + 1


# ----------------------------------------------------------------------
# merged /fleetz over two real in-process endpoints
# ----------------------------------------------------------------------

@pytest.fixture()
def two_stage_fleet():
    from dnn_tpu.obs.http import MetricsHTTPServer

    regA, regB = Metrics(), Metrics()
    regA.set("serving.tokens_per_sec", 10.0)
    regA.set("dnn_tpu_mfu", 0.25)
    regA.observe("serving.ttft_seconds", 0.02)
    regB.set("serving.tokens_per_sec", 5.0)
    colA, colB = obs.TraceCollector(), obs.TraceCollector()
    now = time.time()
    _mk_span(colA, "t1", "c1", None, "rpc.forward", now, 0.10,
             cs=now, cr=now + 0.10)
    _mk_span(colB, "t1", "s1", "c1", "stage.request",
             now + 0.02 + 0.5, 0.06, stage="node2")
    sA = MetricsHTTPServer(port=0, registry=regA, collector=colA,
                           healthy=lambda: True)
    sB = MetricsHTTPServer(
        port=0, registry=regB, collector=colB,
        status=lambda: {"state": "degraded",
                        "components": {"worker": {"state": "degraded",
                                                  "detail": "t"}}})
    fc = FleetCollector({"node1": f"http://127.0.0.1:{sA.port}",
                         "node2": f"http://127.0.0.1:{sB.port}"})
    fc.poll_once()
    yield fc
    fc.close()
    sA.close()
    sB.close()


def test_fleetz_rollup_worst_of_and_tables(two_stage_fleet):
    z = two_stage_fleet.fleetz()
    assert z["state"] == "degraded"  # worst-of across stages
    assert z["stages"]["node1"]["state"] == "ok"
    assert z["stages"]["node2"]["state"] == "degraded"
    assert z["stages"]["node1"]["tokens_per_sec"] == 10.0
    assert z["stages"]["node1"]["mfu"] == 0.25
    assert z["stages"]["node1"]["ttft_p50_ms"] == pytest.approx(20.0)
    assert z["fleet"]["tokens_per_sec"] == 15.0  # fleet total
    assert z["fleet"]["stages_ok"] == 1
    assert abs(z["clock_offsets_s"]["node2"] - 0.5) < 0.05
    assert "t1" in z["trace_ids"]
    # watchdog-shaped status: fleet /healthz degrades with the worst stage
    st = two_stage_fleet.status()
    assert st["state"] == "degraded"
    assert set(st["components"]) == {"node1", "node2"}


def test_fleetz_unreachable_stage_is_wedged_health():
    fc = FleetCollector({"gone": "http://127.0.0.1:9"},  # discard port
                        timeout_s=0.5)
    fc.poll_once()
    z = fc.fleetz()
    assert z["stages"]["gone"]["state"] == "unreachable"
    assert fc.status()["state"] == "wedged"  # the pipeline IS down
    fc.close()


def test_fleetz_endpoint_formats(two_stage_fleet):
    from dnn_tpu.obs.http import MetricsHTTPServer

    srv = MetricsHTTPServer(port=0, fleet=two_stage_fleet)
    try:
        base = f"http://127.0.0.1:{srv.port}"
        r = _get(base + "/fleetz")
        assert r.headers["Content-Type"] == "application/json"
        z = json.load(r)
        assert z["state"] == "degraded"
        prom = _get(base + "/fleetz?format=prom")
        assert prom.headers["Content-Type"].startswith("text/plain")
        body = prom.read().decode()
        assert "dnn_tpu_fleet_state 1" in body
        assert 'dnn_tpu_fleet_stage_up{stage="node1"} 1' in body
        ct = json.load(_get(base + "/fleetz?format=trace&id=t1"))
        assert len([e for e in ct["traceEvents"]
                    if e.get("ph") == "X"]) == 2
        rep = _get(base + "/fleetz?format=report").read().decode()
        assert "fleet state: degraded" in rep
        with pytest.raises(urllib.error.HTTPError) as ei:
            _get(base + "/fleetz?format=nope")
        assert ei.value.code == 400
        # /healthz rides the fleet's worst-of (degraded -> still 200)
        assert _get(base + "/healthz").read().decode().strip() \
            == "degraded"
    finally:
        srv.close()


def test_fleetz_404_without_collector():
    from dnn_tpu.obs.http import MetricsHTTPServer

    srv = MetricsHTTPServer(port=0, registry=Metrics(),
                            collector=obs.TraceCollector())
    try:
        with pytest.raises(urllib.error.HTTPError) as ei:
            _get(f"http://127.0.0.1:{srv.port}/fleetz")
        assert ei.value.code == 404
    finally:
        srv.close()


def test_request_report_cross_host(two_stage_fleet):
    rep = two_stage_fleet.request_report("t1")
    assert rep["trace_id"] == "t1" and rep["spans"] == 2
    # the server span is the only leaf; with offsets corrected it
    # covers 60 of the client's 100 ms -> bubble 40%
    assert rep["bubble_fraction"] == pytest.approx(0.4, abs=0.05)
    assert rep["per_stage_busy_s"].keys() == {"node2"}


# ----------------------------------------------------------------------
# /statusz /debugz content-type + ?format= regression (satellite)
# ----------------------------------------------------------------------

def test_statusz_debugz_content_types_and_formats():
    from dnn_tpu.obs.flight import FlightRecorder
    from dnn_tpu.obs.http import MetricsHTTPServer

    fr = FlightRecorder(capacity=16)
    fr.record("probe", i=1)
    reg = Metrics()
    reg.inc("x_total", 1)
    srv = MetricsHTTPServer(port=0, registry=reg,
                            collector=obs.TraceCollector(),
                            healthy=lambda: True, flight=fr)
    try:
        base = f"http://127.0.0.1:{srv.port}"
        st = _get(base + "/statusz")
        assert st.headers["Content-Type"] == "application/json"
        assert json.load(st)["state"] == "ok"
        prom = _get(base + "/statusz?format=prom")
        assert prom.headers["Content-Type"].startswith("text/plain")
        assert "dnn_tpu_status_state 0" in prom.read().decode()
        db = _get(base + "/debugz")
        assert db.headers["Content-Type"] == "application/x-ndjson"
        lines = [json.loads(ln) for ln in
                 db.read().decode().splitlines()]
        assert lines and lines[-1]["kind"] == "probe"
        dbj = _get(base + "/debugz?format=json")
        assert dbj.headers["Content-Type"] == "application/json"
        evs = json.load(dbj)  # a PROPER JSON array — no sniffing
        assert isinstance(evs, list) and evs[-1]["kind"] == "probe"
        for path in ("/debugz?format=nope", "/statusz?format=nope"):
            with pytest.raises(urllib.error.HTTPError) as ei:
                _get(base + path)
            assert ei.value.code == 400
        # ?format=prom passthrough on /metrics: query params are
        # ignored, the scrape is identical
        assert _get(base + "/metrics?format=prom").read() == \
            _get(base + "/metrics").read()
    finally:
        srv.close()


# ----------------------------------------------------------------------
# goodput: MFU/MBU arithmetic + SLO burn rate (obs/goodput.py)
# ----------------------------------------------------------------------

def test_mfu_mbu_match_hand_computed_flops():
    from dnn_tpu.models import gpt
    from dnn_tpu.utils import flops as F

    cfg = gpt.GPTConfig(block_size=64, vocab_size=512, n_layer=4,
                        n_head=4, n_embd=256)
    PEAK_F, PEAK_B = 1e12, 1e10
    clock = [0.0]
    tr = GoodputTracker(model_cost(cfg), peak_flops=PEAK_F,
                        peak_bytes=PEAK_B, window_s=60.0,
                        now=lambda: clock[0])
    clock[0] = 1.0
    tr.on_prefill(16)
    tr.on_decode_step(4, live_positions=128)  # 4 tokens, mean ctx 32
    clock[0] = 2.0  # window denominator: min(60, lifetime=2 s)

    cost = model_cost(cfg)
    hand_flops = (F.gpt_forward_flops(cfg, 1, 16)
                  + 4 * F.gpt_decode_token_flops(cfg, 32))
    hand_bytes = (2 * cost.weight_bytes  # prefill + one decode step
                  + (16 + 128) * F.kv_bytes_per_pos(cfg))
    assert tr.mfu() == pytest.approx(hand_flops / 2.0 / PEAK_F,
                                     rel=0.05)
    assert tr.mbu() == pytest.approx(hand_bytes / 2.0 / PEAK_B,
                                     rel=0.05)
    assert tr.tokens_per_sec() == pytest.approx(5 / 2.0, rel=0.05)
    assert tr.mfu() > 0 and tr.mbu() > 0  # nonzero on a CPU host


def test_goodput_gauges_on_real_batcher(tmp_path):
    import jax

    from dnn_tpu.models import gpt
    from dnn_tpu.runtime.serving import ContinuousBatcher
    from dnn_tpu.utils.metrics import default_metrics

    cfg = gpt.GPTConfig(block_size=64, vocab_size=64, n_layer=2,
                        n_head=2, n_embd=32)
    prepared = gpt.prepare_stacked(gpt.init(jax.random.PRNGKey(0), cfg),
                                   cfg)
    srv = ContinuousBatcher(cfg, prepared, slots=2, max_len=64,
                            prompt_pad=16)
    tr = GoodputTracker(model_cost(cfg, prepared), peak_flops=1e12,
                        peak_bytes=1e10).install()
    srv.goodput = tr
    srv.submit(np.arange(1, 9), max_new_tokens=6)
    srv.submit(np.arange(1, 5), max_new_tokens=6)
    srv.drain()
    assert tr.mfu() > 0 and tr.mbu() > 0
    assert tr.tokens_per_sec() > 0
    # the scrape path reads the SAME values through the registry
    text = render_prometheus(default_metrics)
    mfu_line = [ln for ln in text.splitlines()
                if ln.startswith("dnn_tpu_mfu ")]
    assert mfu_line and float(mfu_line[0].split()[1]) > 0
    # sanity: achieved flops reconcile with the token count (2 prompts
    # prefilled + 12 tokens total; every event charged > linear cost)
    min_per_tok = tr.cost.flops_per_token(0)
    assert tr.achieved_flops_per_sec() * 60 >= 0  # window is live
    assert tr._flops._items >= 10 * min_per_tok


def test_slo_burn_rate_and_breach_flight_event():
    from dnn_tpu.obs import flight as obs_flight

    clock = [0.0]
    tr = GoodputTracker(
        model_cost(__import__("dnn_tpu.models.gpt",
                              fromlist=["gpt"]).GPTConfig(
            block_size=32, vocab_size=64, n_layer=1, n_head=1,
            n_embd=16)),
        peak_flops=1.0, peak_bytes=1.0,
        slo=SLOConfig(ttft_s=0.1, availability=0.999, target=0.9,
                      window_s=60.0),
        now=lambda: clock[0])
    ring = obs_flight.recorder()
    before = len(ring.events(kind="slo_breach"))
    # 10% budget (target=0.9): 4 good + 1 bad = 20% bad -> burn 2.0
    for s in (0.01, 0.01, 0.01, 0.01, 0.5):
        tr.on_ttft(s)
    rates = tr.burn_rates()
    assert rates["ttft"] == pytest.approx(2.0)
    events = ring.events(kind="slo_breach")
    assert len(events) == before + 1  # latched: ONE event per episode
    tr.on_ttft(0.5)
    assert len(ring.events(kind="slo_breach")) == before + 1
    # recovery clears the latch; the next episode fires again
    for _ in range(200):
        tr.on_ttft(0.01)
    assert tr.burn_rates()["ttft"] <= 1.0
    for _ in range(60):
        tr.on_ttft(0.5)
    assert len(ring.events(kind="slo_breach")) == before + 2
    # availability objective: failures burn 1000x faster than the
    # three-nines budget admits
    tr.on_outcome(True)
    tr.on_outcome(False)
    assert tr.burn_rates()["availability"] > 100


def test_budget_window_buckets_evict_and_stay_exact():
    """Per-second bucket storage: burn arithmetic stays exact inside the
    window, expired seconds fall out with their counts, and memory is
    bounded by seconds, not events."""
    from dnn_tpu.obs.goodput import _BudgetWindow

    clock = [0.0]
    w = _BudgetWindow(0.1, window_s=10.0, now=lambda: clock[0])
    for _ in range(1000):  # 1000 events, ONE bucket
        w.add(False)
    w.add(True)
    assert len(w._buckets) == 1
    assert w.burn_rate() == pytest.approx((1 / 1001) / 0.1)
    clock[0] = 5.0
    w.add(True)  # second bucket
    assert w.burn_rate() == pytest.approx((2 / 1002) / 0.1)
    clock[0] = 12.0  # the t=0 bucket (1001 events) expires
    assert w.burn_rate() == pytest.approx((1 / 1) / 0.1)
    assert len(w._buckets) == 1
    clock[0] = 100.0  # everything expires
    assert w.burn_rate() == 0.0
    assert w._buckets == {} and w._n == 0 and w._bad == 0


def test_peak_env_variables_are_ignored(monkeypatch):
    """Nothing in the environment can state a peak: off a TPU it stays
    unknown whatever the old override variables hold, so a CPU run
    cannot report MFU or MBU under a device's name."""
    from dnn_tpu.utils import flops as F

    for name, value in (("PEAK_FLOPS", "1.25e11"), ("PEAK_HBM_BW", "8e11")):
        monkeypatch.setenv("DNN_TPU_" + name, value)
    assert F.device_peak_flops() is None
    assert F.device_peak_hbm_bw() is None
    assert F.mfu(1e9, 1000.0) is None and F.mbu(1e6, 1e6) is None


def test_fleetz_not_yet_polled_reads_degraded():
    """Before the first poll completes, /fleetz and status() must agree:
    degraded (no evidence), not unreachable/wedged — a scrape racing
    start() must not page."""
    fc = FleetCollector({"slow": "http://127.0.0.1:9"}, timeout_s=0.5)
    try:  # NOTE: no poll_once()
        z = fc.fleetz()
        assert z["stages"]["slow"]["state"] == "degraded"
        assert z["stages"]["slow"]["error"] == "not polled yet"
        assert fc.status()["state"] == "degraded"
        assert "dnn_tpu_fleet_stage_state{stage=\"slow\"} 1" \
            in fc.render_prom()
    finally:
        fc.close()


def test_worker_death_burns_availability_budget():
    """Error-path failures (worker death failing every pending future,
    and fast-fails after it) must count against the availability SLO —
    the objective exists precisely to page on that outage, and the
    retirement path (_obs_retire) never sees these requests."""
    import jax

    from dnn_tpu.models import gpt
    from dnn_tpu.runtime.lm_server import _BatcherWorker
    from dnn_tpu.runtime.serving import ContinuousBatcher

    cfg = gpt.GPTConfig(block_size=32, vocab_size=64, n_layer=1,
                        n_head=1, n_embd=16)
    prepared = gpt.prepare_stacked(gpt.init(jax.random.PRNGKey(0), cfg),
                                   cfg)
    srv = ContinuousBatcher(cfg, prepared, slots=1, max_len=32,
                            prompt_pad=8)
    srv.step = lambda: (_ for _ in ()).throw(
        RuntimeError("injected device fault"))
    worker = _BatcherWorker(srv)
    tr = GoodputTracker(model_cost(cfg), peak_flops=1.0, peak_bytes=1.0,
                        slo=SLOConfig(availability=0.999))
    worker.goodput = tr
    worker.start()
    fut = worker.submit(np.array([1, 2, 3], np.int32), 4, None)
    with pytest.raises(RuntimeError):
        fut.result(timeout=60)
    worker.join(timeout=10)
    assert tr.burn_rates()["availability"] > 100  # outage burns hard
    fut2 = worker.submit(np.array([1, 2], np.int32), 4, None)  # fast-fail
    with pytest.raises(RuntimeError):
        fut2.result(timeout=5)
    w = tr._slo_windows["availability"]
    assert w._n == 2 and w._bad == 2


def test_lm_server_autobuilds_goodput_with_slo():
    import jax

    from dnn_tpu.models import gpt
    from dnn_tpu.runtime.lm_server import LMServer

    cfg = gpt.GPTConfig(block_size=32, vocab_size=64, n_layer=1,
                        n_head=1, n_embd=16)
    prepared = gpt.prepare_stacked(gpt.init(jax.random.PRNGKey(0), cfg),
                                   cfg)
    srv = LMServer(cfg, prepared, slots=1, max_len=32, prompt_pad=8,
                   slo=SLOConfig(ttft_s=30.0))
    try:
        assert srv.goodput is not None
        assert srv.batcher.goodput is srv.goodput
        assert srv.worker.goodput is srv.goodput
        assert "ttft" in srv.goodput._slo_windows
        # exact weight bytes from the real prepared tree
        real = float(sum(x.size * x.dtype.itemsize
                         for x in jax.tree_util.tree_leaves(prepared)))
        assert srv.goodput.cost.weight_bytes == pytest.approx(real)
    finally:
        srv.close()


def test_lm_server_goodput_prices_kv_at_cache_dtype():
    """Regression: without an explicit kv_dtype the batcher stores its
    cache at compute_dtype (serving.py) — the auto-built goodput tracker
    must price KV bytes at the SAME width, not default to f32 (a bf16
    server's MBU would read 2x high)."""
    import jax
    import jax.numpy as jnp

    from dnn_tpu.models import gpt
    from dnn_tpu.runtime.lm_server import LMServer
    from dnn_tpu.utils import flops as F

    cfg = gpt.GPTConfig(block_size=32, vocab_size=64, n_layer=1,
                        n_head=1, n_embd=16)
    prepared = gpt.prepare_stacked(gpt.init(jax.random.PRNGKey(0), cfg),
                                   cfg)
    srv = LMServer(cfg, prepared, slots=1, max_len=32, prompt_pad=8,
                   compute_dtype=jnp.bfloat16)
    try:
        assert srv.batcher.cache["k"].dtype == jnp.bfloat16
        assert srv.goodput.cost.kv_bytes_per_pos == pytest.approx(
            F.kv_bytes_per_pos(cfg, kv_bytes=2))
    finally:
        srv.close()


def test_targets_from_config_rejects_duplicate_urls():
    """A same-host pipeline config + one shared metrics port derives the
    SAME URL for every node — one endpoint polled under N names, the
    rest silently never. Must refuse, not double-count."""
    from dnn_tpu.config import TopologyConfig
    from dnn_tpu.obs.fleet import targets_from_config

    cfg = TopologyConfig.from_dict({
        "nodes": [
            {"id": "node1", "address": "127.0.0.1:50051",
             "part_index": 0},
            {"id": "node2", "address": "127.0.0.1:50052",
             "part_index": 1},
        ],
        "num_parts": 2, "model": "cifar_cnn", "runtime": "relay",
    })
    with pytest.raises(ValueError, match="duplicate obs URLs"):
        targets_from_config(cfg, 9100)
    cfg2 = TopologyConfig.from_dict({
        "nodes": [
            {"id": "node1", "address": "10.0.0.1:50051",
             "part_index": 0},
            {"id": "node2", "address": "10.0.0.2:50051",
             "part_index": 1},
        ],
        "num_parts": 2, "model": "cifar_cnn", "runtime": "relay",
    })
    assert targets_from_config(cfg2, 9100) == {
        "node1": "http://10.0.0.1:9100",
        "node2": "http://10.0.0.2:9100"}


# ----------------------------------------------------------------------
# structured JSON logs with trace-id injection (satellite)
# ----------------------------------------------------------------------

def test_json_log_mode_injects_trace_id():
    from dnn_tpu.utils.logging import setup_logging

    buf = io.StringIO()
    setup_logging("INFO", node_id="node1", stream=buf, fmt="json")
    log = logging.getLogger("dnn_tpu.test_fleet")
    try:
        with obs.span("request", kind="logtest") as sp:
            log.info("inside %d", 7)
        log.info("outside")
    finally:
        setup_logging("INFO", stream=io.StringIO())  # detach buf
    lines = [json.loads(ln) for ln in buf.getvalue().splitlines()]
    assert lines[0]["msg"] == "inside 7"
    assert lines[0]["node_id"] == "node1"
    assert lines[0]["trace_id"] == sp.trace_id  # correlates with traces
    assert lines[0]["level"] == "INFO"
    assert "trace_id" not in lines[1]


def test_text_log_mode_unchanged_by_default(monkeypatch):
    from dnn_tpu.utils.logging import setup_logging

    monkeypatch.delenv("DNN_TPU_LOG", raising=False)
    buf = io.StringIO()
    setup_logging("INFO", node_id="n2", stream=buf)
    logging.getLogger("dnn_tpu.test_fleet").info("plain line")
    setup_logging("INFO", stream=io.StringIO())
    assert "INFO dnn_tpu.test_fleet: [n2] plain line" in buf.getvalue()


# ----------------------------------------------------------------------
# e2e: a REAL 2-stage pipeline request, stitched across endpoints
# ----------------------------------------------------------------------

def test_e2e_two_stage_request_stitched_with_bubble():
    """The acceptance path: run one real request through two in-process
    gRPC stage servers, partition the spans by owning stage onto two
    real HTTP endpoints (as two hosts' collectors would hold them),
    fleet-poll both, and verify ONE stitched Perfetto trace with
    critical-path/bubble attribution."""
    from dnn_tpu.comm.client import NodeClient
    from dnn_tpu.comm.service import start_stage_server_in_background
    from dnn_tpu.config import TopologyConfig
    from dnn_tpu.obs.http import MetricsHTTPServer
    from dnn_tpu.runtime.engine import PipelineEngine

    cfg = TopologyConfig.from_dict({
        "nodes": [
            {"id": "node1", "address": "127.0.0.1:59371",
             "part_index": 0},
            {"id": "node2", "address": "127.0.0.1:59372",
             "part_index": 1},
        ],
        "num_parts": 2, "model": "cifar_cnn", "runtime": "relay",
    })
    engine = PipelineEngine(cfg)
    t1, stop1 = start_stage_server_in_background(engine, "node1")
    t2, stop2 = start_stage_server_in_background(engine, "node2")
    try:
        x = np.asarray(engine.spec.example_input(batch_size=1))
        c = NodeClient(cfg.node_by_id("node1").address)
        with obs.span("client.request") as root:
            status, result = c.send_tensor(x, request_id="fleet_e2e_1")
        c.close()
    finally:
        stop1()
        stop2()
    assert result is not None
    spans = obs.collector().spans(root.trace_id)
    assert len(spans) == 7  # client + rpc + 2x(request, compute) + fwd
    # the client rpc span carries the clock-offset sampling fields
    rpc = [s for s in spans if s.name == "rpc.SendTensor"][0]
    assert rpc.attrs["cr"] >= rpc.attrs["cs"] > 0
    fwd = [s for s in spans if s.name == "rpc.forward"][0]
    assert fwd.attrs["cr"] >= fwd.attrs["cs"] > 0

    # partition by owning process, exactly as each host's collector
    # would hold them (all three run in this test process, so the
    # shared collector held the union)
    def owner(s):
        st = s.attrs.get("stage")
        if st:
            return st
        if "part" in s.attrs:  # stage.compute carries part=, not stage=
            return f"node{s.attrs['part'] + 1}"
        if s.name == "rpc.forward":
            return "node1"  # node1's relay client span
        return "client"

    cols = {k: obs.TraceCollector() for k in ("client", "node1",
                                              "node2")}
    for s in spans:
        cols[owner(s)].add(s)
    servers = {k: MetricsHTTPServer(port=0, registry=Metrics(),
                                    collector=col)
               for k, col in cols.items()}
    try:
        fc = FleetCollector({k: f"http://127.0.0.1:{srv.port}"
                             for k, srv in servers.items()})
        fc.poll_once()
        ct = fc.stitch(root.trace_id)
        xs = [e for e in ct["traceEvents"] if e.get("ph") == "X"]
        assert len(xs) == 7  # ONE trace across three "hosts"
        assert {e["args"]["stage"] for e in xs} == {"client", "node1",
                                                    "node2"}
        rep = fc.request_report(root.trace_id)
        assert rep["spans"] == 7
        assert 0.0 <= rep["bubble_fraction"] < 1.0
        busy = rep["per_stage_busy_s"]
        assert "node1" in busy and "node2" in busy
        assert rep["path"], rep  # a non-empty critical path
        # same-process clocks: estimated offsets must be ~zero (no
        # false skew invented when there is none)
        for off in fc.offsets().values():
            assert abs(off) < 0.05
        fc.close()
    finally:
        for srv in servers.values():
            srv.close()


# ----------------------------------------------------------------------
# CLI smoke (tier-1 wired via conftest _MODULE_COST_S)
# ----------------------------------------------------------------------

def test_fleet_cli_selftest_smoke():
    out = subprocess.run(
        [sys.executable, "-m", "dnn_tpu.obs", "fleet", "--selftest"],
        capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert "fleet selftest ok" in out.stdout


def test_fleet_cli_one_shot_report(tmp_path):
    from dnn_tpu.obs.http import MetricsHTTPServer

    reg = Metrics()
    reg.set("serving.tokens_per_sec", 3.0)
    col = obs.TraceCollector()
    now = time.time()
    _mk_span(col, "tr9", "r1", None, "request", now, 0.05)
    _mk_span(col, "tr9", "w1", "r1", "stage.compute", now + 0.01, 0.03,
             stage="s0")
    srv = MetricsHTTPServer(port=0, registry=reg, collector=col,
                            healthy=lambda: True)
    out_path = tmp_path / "stitched.json"
    try:
        out = subprocess.run(
            [sys.executable, "-m", "dnn_tpu.obs", "fleet",
             "--targets", f"http://127.0.0.1:{srv.port}",
             "--out", str(out_path)],
            capture_output=True, text=True, timeout=120)
    finally:
        srv.close()
    assert out.returncode == 0, out.stderr
    assert "fleet state: ok" in out.stdout
    assert "bubble" in out.stdout
    ct = json.loads(out_path.read_text())
    assert [e for e in ct["traceEvents"] if e.get("ph") == "X"]
