"""Observability v2 tests (ISSUE 4): flight recorder, on-demand device
profiling, memory watermarks, hung-device watchdog.

The acceptance contract this module pins: /statusz reports `wedged`
(and /healthz degrades to 503) within one watchdog period when the
device probe is stubbed to hang, WHILE the serving loop keeps answering
CPU-path requests; a deadline-missed request's /debugz dump contains
its trace id and the surrounding event window; POST /profilez on a live
LMServer produces a Perfetto-loadable capture containing the new
layer/stage annotations; /metrics and /profilez survive concurrent
scraping under load — plus the unit contracts underneath: flight-ring
overflow/ordering, crash-dump excepthook (in a subprocess), paged-pool
watermark arithmetic, memory gauges, and the deprecated
utils.tracing shim honoring the obs gate."""

import gzip
import json
import os
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from dnn_tpu import obs
from dnn_tpu.obs.flight import FlightRecorder
from dnn_tpu.obs.watchdog import STATE_VALUES, Watchdog


# ----------------------------------------------------------------------
# flight recorder
# ----------------------------------------------------------------------

def test_flight_ring_overflow_and_ordering_golden():
    fr = FlightRecorder(capacity=4)
    for i in range(7):
        fr.record("ev", i=i)
    evs = fr.events()
    # bounded ring: newest 4 survive, in order, seq strictly increasing
    assert [e["i"] for e in evs] == [3, 4, 5, 6]
    assert [e["seq"] for e in evs] == [4, 5, 6, 7]
    assert all(evs[k]["ts"] <= evs[k + 1]["ts"] for k in range(3))
    # jsonl: one valid object per line, schema keys present
    lines = [json.loads(ln) for ln in fr.jsonl().splitlines()]
    assert len(lines) == 4
    for d in lines:
        assert {"seq", "ts", "kind", "i"} <= set(d)


def test_flight_filters_and_window():
    fr = FlightRecorder(capacity=64)
    fr.record("admit", rid=1)
    miss = fr.record("deadline_miss", trace_id="abcd", rid=1)
    fr.record("retire", rid=2)
    assert [e["kind"] for e in fr.events(kind="deadline_miss")] == \
        ["deadline_miss"]
    assert fr.events(trace_id="abcd")[0]["seq"] == miss["seq"]
    assert len(fr.events(last=2)) == 2
    win = fr.window(miss["ts"], before_s=60, after_s=60)
    assert len(win) == 3  # the miss plus its surrounding events


def test_flight_record_respects_gate():
    fr = obs.flight.recorder()
    obs.set_enabled(False)
    try:
        n = len(fr)
        assert obs.flight.record("nope") is None
        assert len(fr) == n
    finally:
        obs.set_enabled(True)
    assert obs.flight.record("yep") is not None


def test_flight_cli_selftest_smoke():
    out = subprocess.run(
        [sys.executable, "-m", "dnn_tpu.obs", "flight", "--selftest"],
        capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert "flight selftest ok" in out.stdout


def test_crash_dump_excepthook_in_subprocess(tmp_path):
    # a subprocess, because the hook fires on process-level unhandled
    # exceptions — exactly what a test must not raise in-process
    code = f"""
import sys
from dnn_tpu import obs
d = obs.flight.install_crash_dump({str(tmp_path)!r})
assert d == {str(tmp_path)!r}
obs.flight.record("admit", rid=1)
obs.flight.record("retire", rid=1, reason="length")
raise RuntimeError("synthetic crash for the flight recorder")
"""
    out = subprocess.run([sys.executable, "-c", code],
                         capture_output=True, text=True, timeout=120)
    assert out.returncode != 0
    dumps = list(tmp_path.glob("flight-crash-*.jsonl"))
    assert len(dumps) == 1, out.stderr
    events = [json.loads(ln) for ln in
              dumps[0].read_text().splitlines()]
    kinds = [e["kind"] for e in events]
    assert kinds[:2] == ["admit", "retire"]  # the pre-crash window rides
    crash = events[-1]
    assert crash["kind"] == "crash"
    assert crash["exc_type"] == "RuntimeError"
    assert "synthetic crash" in crash["message"]
    assert "Traceback" in crash["traceback"]
    # the original traceback still reached stderr (hooks chain, not mask)
    assert "synthetic crash" in out.stderr


# ----------------------------------------------------------------------
# paged-pool watermark arithmetic
# ----------------------------------------------------------------------

def test_block_allocator_watermark_arithmetic():
    from dnn_tpu.runtime.paged_kvcache import BlockAllocator

    a = BlockAllocator(8)  # 7 allocatable (block 0 reserved)
    assert (a.n_used, a.n_free, a.high_water) == (0, 7, 0)
    b1 = a.alloc(3)
    assert (a.n_used, a.n_free, a.high_water) == (3, 4, 3)
    b2 = a.alloc(2)
    assert (a.n_used, a.n_free, a.high_water) == (5, 2, 5)
    a.free(b1)
    # high water survives the release — the point of a watermark
    assert (a.n_used, a.n_free, a.high_water) == (2, 5, 5)
    b3 = a.alloc(1)
    assert (a.n_used, a.high_water) == (3, 5)  # below HW: no move
    a.free(b2)
    a.free(b3)
    assert (a.n_used, a.n_free, a.high_water) == (0, 7, 5)
    # invariant everywhere: used + free == n_blocks - 1
    assert a.n_used + a.n_free == 7
    # refcounted sharing counts as use until the LAST holder frees
    b4 = a.alloc(2)
    a.ref(b4)
    a.free(b4)
    assert a.n_used == 2
    a.free(b4)
    assert a.n_used == 0


def test_paged_pool_gauges_export(tiny_gpt):
    from dnn_tpu.runtime.serving import ContinuousBatcher
    from dnn_tpu.utils.metrics import default_metrics

    cfg, prepared = tiny_gpt
    srv = ContinuousBatcher(cfg, prepared, slots=2, max_len=64,
                            prompt_pad=16, paged_blocks=12, block_len=16)
    srv.submit(np.arange(1, 9), 4)
    srv.drain()
    snap = default_metrics.snapshot()["gauges"]
    assert snap["serving.paged_blocks_high_water"] >= 1
    assert snap["serving.paged_blocks_used"] == 0  # retired -> freed
    assert snap["serving.paged_blocks_free"] == 11
    assert snap["serving.kv_live_positions_high_water"] >= 9
    assert snap["serving.active_slots_high_water"] >= 1


# ----------------------------------------------------------------------
# memory gauges
# ----------------------------------------------------------------------

def test_memory_gauges_install_and_render():
    from dnn_tpu.obs.mem import install_memory_gauges, rss_bytes
    from dnn_tpu.utils.metrics import Metrics, render_prometheus

    assert rss_bytes() > 1e6  # this process surely exceeds a megabyte
    reg = Metrics()
    registered = install_memory_gauges(reg)
    assert "process_resident_bytes" in registered
    body = render_prometheus(reg)
    line = next(ln for ln in body.splitlines()
                if ln.startswith("process_resident_bytes"))
    assert float(line.split()[-1]) > 1e6
    # idempotent per registry object
    assert install_memory_gauges(reg) == []


# ----------------------------------------------------------------------
# watchdog
# ----------------------------------------------------------------------

def test_watchdog_wedged_on_hanging_probe_within_one_period():
    def hang_probe(deadline_s):
        time.sleep(deadline_s + 60)

    wd = Watchdog(period_s=0.3, probe_deadline_s=0.2,
                  device_probe=hang_probe, registry=None)
    wd.start()
    try:
        deadline = time.monotonic() + 0.2 + 2.0 + 2.0  # deadline+join slack
        while time.monotonic() < deadline and wd.state() != "wedged":
            time.sleep(0.05)
        assert wd.state() == "wedged"
        st = wd.status()
        assert st["components"]["device"]["state"] == "wedged"
        assert "deadline" in st["components"]["device"]["detail"]
        # the firing landed in the flight ring
        fired = [e for e in obs.flight.recorder().events(kind="watchdog")
                 if e.get("component") == "device" and
                 e.get("state") == "wedged"]
        assert fired
    finally:
        wd.close()


def test_watchdog_ok_probe_and_heartbeat_staleness():
    wd = Watchdog(period_s=0.2, probe_deadline_s=5.0,
                  device_probe=lambda d: (True, "ok"),
                  heartbeat_stale_s=0.3)
    wd.start()
    try:
        time.sleep(0.4)
        assert wd.state() == "ok"  # probe ok, no heartbeat expected yet
        wd.beat()
        assert wd.status()["components"]["decode_heartbeat"]["state"] == "ok"
        time.sleep(0.5)  # beat goes stale BEFORE any step completed:
        st = wd.status()  # warmup grace — the first step's cold-chip
        # compile blocks the loop for minutes legitimately, so this is
        # degraded (visible), not wedged (503 -> orchestrator evicts a
        # healthy warming server)
        assert st["components"]["decode_heartbeat"]["state"] == "degraded"
        assert st["state"] == "degraded"
        wd.beat()
        wd.step_done()  # a step completed: staleness now means wedged
        time.sleep(0.5)
        st = wd.status()
        assert st["components"]["decode_heartbeat"]["state"] == "wedged"
        assert st["state"] == "wedged"
        wd.beat()  # recovery
        assert wd.status()["state"] == "ok"
    finally:
        wd.close()


def test_watchdog_degraded_on_fast_probe_error_and_gauge():
    from dnn_tpu.utils.metrics import Metrics

    reg = Metrics()  # private registry: gauge assertions stay isolated
    wd = Watchdog(period_s=0.2, probe_deadline_s=5.0,
                  device_probe=lambda d: (False, "probe exited rc=1"),
                  registry=reg)
    wd.start()
    try:
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline and wd.state() != "degraded":
            time.sleep(0.05)
        assert wd.state() == "degraded"
        assert reg.snapshot()["gauges"][
            "dnn_tpu_watchdog_state"] == STATE_VALUES["degraded"]
    finally:
        wd.close()


def test_subprocess_device_probe_real_and_bounded():
    from dnn_tpu.obs.watchdog import subprocess_device_probe

    ok, detail, timed_out = subprocess_device_probe(deadline_s=120.0)
    assert ok and not timed_out, detail  # the CPU backend answers


def test_subprocess_device_probe_platform_pinned():
    # the LMServer wiring probes the SERVER's backend, not whatever a
    # fresh child resolves by default (a cpu-substrate daemon must not
    # queue behind a device plugin it never uses)
    from dnn_tpu.obs.watchdog import subprocess_device_probe

    ok, detail, timed_out = subprocess_device_probe(deadline_s=120.0,
                                                    platform="cpu")
    assert ok and not timed_out, detail


# ----------------------------------------------------------------------
# LMServer integration: statusz/healthz/debugz/profilez
# ----------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny_gpt():
    import jax

    from dnn_tpu.models import gpt

    cfg = gpt.GPTConfig(block_size=64, vocab_size=64, n_layer=2, n_head=2,
                        n_embd=32)
    prepared = gpt.prepare_stacked(gpt.init(jax.random.PRNGKey(0), cfg),
                                   cfg)
    return cfg, prepared


@pytest.fixture(scope="module")
def lm_v2_server(tiny_gpt, tmp_path_factory):
    import os

    from dnn_tpu.runtime.lm_server import start_lm_server_in_background

    # route crash dumps / profile spool somewhere disposable
    os.environ["DNN_TPU_OBS_DIR"] = str(
        tmp_path_factory.mktemp("obs_spool"))
    cfg, prepared = tiny_gpt

    def hang_probe(deadline_s):
        time.sleep(deadline_s + 60)

    wd = Watchdog(period_s=0.3, probe_deadline_s=0.2,
                  device_probe=hang_probe)
    t, stop = start_lm_server_in_background(
        cfg, prepared, port=59561, slots=2, max_len=64, prompt_pad=16,
        default_max_new=8, request_timeout=60.0, metrics_port=0,
        watchdog=wd)
    yield stop.servicer
    stop()
    os.environ.pop("DNN_TPU_OBS_DIR", None)


def _get(url, timeout=30):
    return urllib.request.urlopen(url, timeout=timeout)


def test_statusz_wedged_while_serving_answers(lm_v2_server):
    from dnn_tpu.comm.client import NodeClient

    base = f"http://127.0.0.1:{lm_v2_server.metrics_server.port}"
    # within one watchdog period (+ probe deadline + thread-join slack)
    deadline = time.monotonic() + 0.2 + 2.0 + 3.0
    state = None
    while time.monotonic() < deadline:
        state = json.load(_get(base + "/statusz"))
        if state["state"] == "wedged":
            break
        time.sleep(0.05)
    assert state["state"] == "wedged", state
    assert state["components"]["device"]["state"] == "wedged"
    # /healthz degrades to 503 "wedged"
    with pytest.raises(urllib.error.HTTPError) as ei:
        _get(base + "/healthz")
    assert ei.value.code == 503
    assert ei.value.read().decode().strip() == "wedged"
    # ...while the serving loop keeps answering CPU-path requests
    c = NodeClient("127.0.0.1:59561")
    toks = c.generate([1, 2, 3, 4], max_new_tokens=6, seed=0)
    c.close()
    assert len(toks) == 6
    # the worker's own heartbeat stays fresh (it is not the wedged part)
    assert state["components"]["decode_heartbeat"]["state"] == "ok"
    # and the watchdog gauge rides the /metrics scrape
    body = _get(base + "/metrics").read().decode()
    line = next(ln for ln in body.splitlines()
                if ln.startswith("dnn_tpu_watchdog_state"))
    assert float(line.split()[-1]) == STATE_VALUES["wedged"]


def test_deadline_miss_lands_in_debugz_with_trace_id(lm_v2_server):
    from dnn_tpu.comm.client import NodeClient

    base = f"http://127.0.0.1:{lm_v2_server.metrics_server.port}"
    c = NodeClient("127.0.0.1:59561")
    with obs.span("client.doomed") as root:
        # force the miss by shrinking the SERVER deadline under the
        # request (5 ms cannot cover a 55-token decode even warm);
        # DEADLINE_EXCEEDED is deliberately non-retryable client-side
        lm_v2_server.request_timeout = 0.005
        try:
            with pytest.raises(Exception) as ei:
                c.generate([1, 2, 3], max_new_tokens=55, seed=1,
                           timeout=30.0)
        finally:
            lm_v2_server.request_timeout = 60.0
    c.close()
    assert "DEADLINE" in str(ei.value).upper() or \
        "exceeded" in str(ei.value)
    # the dump: deadline_miss event carrying this request's trace id,
    # with the surrounding event window (admissions etc.) around it
    body = _get(base + "/debugz").read().decode()
    events = [json.loads(ln) for ln in body.splitlines()]
    misses = [e for e in events if e["kind"] == "deadline_miss"
              and e.get("trace_id") == root.trace_id]
    assert misses, [e["kind"] for e in events]
    fr = obs.flight.recorder()
    win = fr.window(misses[-1]["ts"], before_s=120, after_s=5)
    assert any(e["kind"] == "admit" for e in win)
    # filtered fetch matches the CLI's ?trace= path
    filt = _get(base + f"/debugz?trace={root.trace_id}").read().decode()
    assert all(json.loads(ln)["trace_id"] == root.trace_id
               for ln in filt.splitlines())


def test_profilez_auto_trigger_captures_annotated_step(lm_v2_server):
    import urllib.parse

    from dnn_tpu.comm.client import NodeClient
    from dnn_tpu.obs.profile import trace_files

    base = f"http://127.0.0.1:{lm_v2_server.metrics_server.port}"
    # arm: threshold 0 ms -> the first step breaches, the NEXT one is
    # captured (exactly one step: the capture stays small enough that
    # the trace-viewer JSON exporter's 1M-event cap cannot drop the
    # annotation events)
    req = urllib.request.Request(
        base + "/profilez?auto=1&threshold_ms=0&perfetto=1", method="POST")
    armed = json.load(urllib.request.urlopen(req, timeout=30))
    assert armed["armed"]["threshold_ms"] == 0
    c = NodeClient("127.0.0.1:59561")
    toks = c.generate([1, 2, 3, 4], max_new_tokens=10, seed=2)
    c.close()
    assert len(toks) == 10
    # the capture landed in the spool and is disarmed now
    deadline = time.monotonic() + 30
    caps = []
    while time.monotonic() < deadline and not caps:
        status = json.load(_get(base + "/profilez"))
        caps = status["captures"]
        time.sleep(0.1)
    assert caps, "auto-trigger produced no capture"
    assert status["armed"] is None
    tf = trace_files(caps[-1])
    assert tf, f"no trace.json.gz under {caps[-1]}"
    raw = gzip.open(tf[0]).read().decode(errors="replace")
    # the step's StepClock phases, written into the capture as
    # annotations (the jit call is `step.dispatch`)
    assert "step.dispatch" in raw
    events = [e for e in json.loads(raw)["traceEvents"]
              if e.get("name") in ("step", "step.dispatch", "step.wait")]
    assert {e["name"] for e in events} == {"step", "step.dispatch",
                                           "step.wait"}
    assert all(e.get("ph") == "X" for e in events)


def test_concurrent_metrics_and_profilez_scrape_under_load(lm_v2_server):
    from dnn_tpu.comm.client import NodeClient

    base = f"http://127.0.0.1:{lm_v2_server.metrics_server.port}"
    errors = []
    stop = threading.Event()

    def load():
        c = NodeClient("127.0.0.1:59561")
        while not stop.is_set():
            c.generate([1, 2, 3], max_new_tokens=8, seed=3)
        c.close()

    def scrape():
        try:
            while not stop.is_set():
                body = _get(base + "/metrics").read().decode()
                assert "serving_decode_steps_total" in body
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=load),
               threading.Thread(target=scrape),
               threading.Thread(target=scrape)]
    for t in threads:
        t.start()
    try:
        # two on-demand captures racing the scrapes and each other: the
        # loser of the race gets 409 (ProfilerBusy), never corruption
        results = []

        def post():
            req = urllib.request.Request(base + "/profilez?ms=150",
                                         method="POST")
            try:
                results.append(
                    json.load(urllib.request.urlopen(req, timeout=60)))
            except urllib.error.HTTPError as e:
                results.append(e.code)

        p1, p2 = threading.Thread(target=post), threading.Thread(target=post)
        p1.start(), p2.start()
        p1.join(60), p2.join(60)
    finally:
        stop.set()
        for t in threads:
            t.join(30)
    assert not errors, errors
    oks = [r for r in results if isinstance(r, dict)]
    assert len(oks) >= 1  # at least one capture succeeded
    assert all(r == 409 for r in results if not isinstance(r, dict))
    for r in oks:
        assert r["xplane_files"], r  # the capture's artifact exists


def test_profilez_writes_the_perfetto_json_only_when_asked(lm_v2_server):
    # ending a capture collects the events and writes the .xplane.pb;
    # the Perfetto JSON export (most of stop_trace's time on a loaded
    # daemon) is made for &perfetto=1 alone
    base = f"http://127.0.0.1:{lm_v2_server.metrics_server.port}"

    def post(query):
        req = urllib.request.Request(base + "/profilez?ms=50" + query,
                                     method="POST")
        return json.load(urllib.request.urlopen(req, timeout=60))

    plain, asked = post(""), post("&perfetto=1")
    assert plain["capture"] != asked["capture"]
    for r in (plain, asked):
        assert r["xplane_files"] and all(
            os.path.getsize(f) > 0 for f in r["xplane_files"]), r
        assert os.path.isfile(os.path.join(r["capture"], "meta.json"))
    assert plain["trace_files"] == []
    assert asked["trace_files"], asked
    with gzip.open(asked["trace_files"][0]) as f:
        assert json.load(f)["traceEvents"]


def test_statusz_without_watchdog_reports_worker(tiny_gpt):
    from dnn_tpu.runtime.lm_server import LMServer

    cfg, prepared = tiny_gpt
    srv = LMServer(cfg, prepared, slots=1, max_len=32, prompt_pad=16,
                   metrics_port=0)
    try:
        base = f"http://127.0.0.1:{srv.metrics_server.port}"
        st = json.load(_get(base + "/statusz"))
        assert st["state"] == "ok"
        assert st["components"]["worker"]["state"] == "ok"
        assert _get(base + "/healthz").status == 200
        # the device component says what the chip is, probe or no probe;
        # with no probe it carries no verdict, in JSON or as a gauge
        dev = st["components"]["device"]
        assert "state" not in dev
        prom = _get(base + "/statusz?format=prom").read().decode()
        assert 'component="worker"' in prom
        assert 'component="device"' not in prom
        assert dev["device_kind"] == "cpu" and dev["platform"] == "cpu"
        assert dev["device_count"] >= 1
        # ... and what boot cost, once node.py has published its gauges
        obs.metrics().bulk(gauges={"dnn_tpu_boot_ready_total_seconds": 7.5,
                                   "dnn_tpu_boot_imports_seconds": 1.25})
        dev = json.load(_get(base + "/statusz"))["components"]["device"]
        assert dev["boot_ready_total_s"] == 7.5
        assert dev["boot_imports_s"] == 1.25
    finally:
        srv.close()


# ----------------------------------------------------------------------
# tracing shim + gate
# ----------------------------------------------------------------------

def test_tracing_shim_is_the_obs_annotation():
    from dnn_tpu.obs import profile
    from dnn_tpu.utils import tracing

    assert tracing.span is profile.annotation
    assert tracing.step_span is profile.step_annotation
    # the gate: off -> the hot-path ctx is the shared nullcontext
    obs.set_enabled(False)
    try:
        assert profile.annotation_ctx("x") is profile._NULL_CTX
        with tracing.span("gated"):
            pass  # still a working context manager
    finally:
        obs.set_enabled(True)


def test_profiler_busy_is_exclusive():
    from dnn_tpu.obs import profile

    with profile._capture_lock:
        with pytest.raises(profile.ProfilerBusy):
            profile.capture(1, capture_root="/tmp/never")


def test_legacy_trace_to_still_annotates(monkeypatch):
    # the deprecated trace_to + span pattern must keep producing
    # annotated captures: trace_to marks the capture as recording so
    # annotation_ctx's hot-path gate (which otherwise only opens during
    # obs-driven captures) emits real TraceAnnotations
    import jax

    from dnn_tpu.obs import profile
    from dnn_tpu.utils import tracing

    monkeypatch.setattr(jax.profiler, "start_trace", lambda d: None)
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    assert not profile.capturing()
    with tracing.trace_to("/tmp/never-written"):
        assert profile.capturing()
        ctx = profile.annotation_ctx("legacy-span")
        assert ctx is not profile._NULL_CTX
        with ctx:
            pass
    assert not profile.capturing()
    assert profile.annotation_ctx("after") is profile._NULL_CTX


def test_serve_metrics_is_the_full_v2_surface():
    # the public helper must not drift behind the endpoints the real
    # servers expose: it installs memory gauges and serves the whole
    # surface (LMServer and serve_stage construct through it)
    srv = obs.serve_metrics(0)
    try:
        base = f"http://127.0.0.1:{srv.port}"
        for path in ("/metrics", "/debugz", "/statusz", "/healthz"):
            with urllib.request.urlopen(base + path, timeout=5) as r:
                assert r.status == 200, path
        with urllib.request.urlopen(base + "/metrics", timeout=5) as r:
            assert b"process_resident_bytes" in r.read()
    finally:
        srv.close()


def test_memory_gauges_reinstall_after_registry_clear():
    # regression (ISSUE 10 tier-1 find): install -> registry.clear()
    # (a test/bench leg resetting series) -> any later server's
    # install must RE-register, not trust the per-registry id marker —
    # the latched marker left every later /metrics scrape without
    # host/device memory series, a deterministic cross-module suite
    # failure (LMServer installed, a transport test cleared, this
    # module's surface test scraped)
    from dnn_tpu.obs.mem import install_memory_gauges

    m = obs.metrics()
    assert m is not None
    install_memory_gauges(m)
    assert "process_resident_bytes" in m.gauges
    m.clear()
    assert "process_resident_bytes" not in m.gauges
    install_memory_gauges(m)  # must self-heal past the id marker
    assert "process_resident_bytes" in m.gauges


def test_pool_exhausted_episode_reopens_after_cancel_frees_blocks(tiny_gpt):
    # the episode latch dedupes per-step retries, but a shortage whose
    # held request is cancelled (never re-admitted) must not suppress
    # the NEXT episode: returning blocks to the pool ends the episode
    from dnn_tpu.runtime.paged_kvcache import InsufficientBlocks
    from dnn_tpu.runtime.serving import ContinuousBatcher

    cfg, prepared = tiny_gpt
    srv = ContinuousBatcher(cfg, prepared, slots=3, max_len=64,
                            prompt_pad=16, paged_blocks=5, block_len=16)

    def n_exhausted():
        return sum(1 for e in obs.flight.recorder().events()
                   if e["kind"] == "pool_exhausted")

    base = n_exhausted()
    srv.submit(np.arange(1, 9), 24)            # 32 pos -> 2 of 4 blocks
    rid_small = srv.submit(np.arange(1, 9), 4)  # 12 pos -> 1 block
    with pytest.raises(InsufficientBlocks):     # needs 2, 1 free
        srv.submit(np.arange(1, 9), 24)
    assert n_exhausted() == base + 1
    with pytest.raises(InsufficientBlocks):     # retry: same episode
        srv.submit(np.arange(1, 9), 24)
    assert n_exhausted() == base + 1
    assert srv.cancel(rid_small)                # blocks return -> episode over
    with pytest.raises(InsufficientBlocks):     # needs 3, 2 free: NEW episode
        srv.submit(np.arange(1, 9), 40)
    assert n_exhausted() == base + 2


def test_watchdog_classifies_structurally_not_by_detail_text():
    # hung-vs-failed is the probe's structured timed_out flag, never a
    # substring sniff of the free-text detail: a FAST failure whose
    # message happens to contain "timeout" is degraded (the backend
    # answered), and a reported child timeout is wedged regardless of
    # its wording
    wd = Watchdog(period_s=0.2, probe_deadline_s=5.0,
                  device_probe=lambda d: (
                      False, "rpc timeout contacting coordinator"),
                  registry=None)
    wd.start()
    try:
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline and wd.state() == "ok":
            time.sleep(0.05)
        assert wd.state() == "degraded"
    finally:
        wd.close()

    wd = Watchdog(period_s=0.2, probe_deadline_s=5.0,
                  device_probe=lambda d: (False, "chip stuck", True),
                  registry=None)
    wd.start()
    try:
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline and wd.state() != "wedged":
            time.sleep(0.05)
        assert wd.state() == "wedged"
    finally:
        wd.close()
