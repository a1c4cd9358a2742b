"""Step-timeline attribution (ISSUE 11): StepClock phase accounting,
capture analysis, /stepz, and the sidecar-meta alignment.

Covers the layer's contracts:
  * phase sums cover the externally measured wall (no dark time);
  * derived-series arithmetic (dispatch slack, sync tax, host
    fraction) under a deterministic injected clock;
  * admit attribution from real submit() calls;
  * the one-None-check gate (DNN_TPU_OBS off -> begin() is None and a
    stepped pool records nothing);
  * analyze() goldens over synthetic Perfetto JSON, including
    truncated/garbage inputs failing loud;
  * the steps and admissions IN a capture (ISSUE 24): `step.*` /
    `admit*` annotations on the worker's line of the `.xplane.pb`,
    nested, with `step` / `rid` stats — and none with no capture
    recording or with the obs gate off;
  * the admit split and the cumulative totals that are exact at a
    /metrics scrape between two flushes;
  * /stepz scrape (JSON + ?format=prom);
  * CLI smoke (`python -m dnn_tpu.obs timeline --selftest`).
"""

import gzip
import json
import os
import time
import urllib.request

import numpy as np
import pytest

from dnn_tpu import obs
from dnn_tpu.obs import timeline as tl
from dnn_tpu.obs.timeline import PHASES, StepClock, analyze
from dnn_tpu.utils.metrics import Metrics


@pytest.fixture(autouse=True)
def _obs_on():
    was = obs.enabled()
    obs.set_enabled(True)
    yield
    obs.set_enabled(was)


def _fake_clock(**kw):
    """StepClock on an injected, manually advanced clock."""
    t = [100.0]
    clk = StepClock(registry=kw.pop("registry", Metrics()),
                    now=lambda: t[0], **kw)
    return clk, t


def _drive(clk, t, *, admit=0.0, host=0.001, dispatch=0.002, wait=0.004,
           commit=0.001, obs_p=0.001, n_adv=4):
    if admit:
        t[0] += admit
        clk.note_admit(t[0] - admit)
    rec = clk.begin()
    assert rec is not None
    for phase, dt in (("host", host), ("dispatch", dispatch),
                      ("wait", wait), ("commit", commit),
                      ("obs", obs_p)):
        t[0] += dt
        clk.mark(rec, phase)
    clk.end(rec, n_adv=n_adv)
    return rec


# ----------------------------------------------------------------------
# StepClock arithmetic (deterministic injected clock)
# ----------------------------------------------------------------------

def test_derived_series_arithmetic():
    clk, t = _fake_clock()
    for _ in range(4):
        _drive(clk, t, admit=0.0005)
        t[0] += 0.001  # inter-step gap, deliberately dark
    s = clk.summary()
    assert s["window_steps"] == 4 and s["steps_total"] == 4
    # per step: wall 9.5 ms (9 in-step + 0.5 admit), host 3.5, device 6
    assert s["host_fraction"] == pytest.approx(3.5 / 9.5, abs=1e-3)
    assert s["phases"]["wait"]["mean_ms"] == pytest.approx(4.0, abs=1e-6)
    assert s["tokens"] == 16


def test_ring_bounded_and_records():
    clk, t = _fake_clock(capacity=4)
    for _ in range(9):
        _drive(clk, t)
    assert clk.steps_total == 9
    recs = clk.records()
    assert len(recs) == 4  # bounded
    assert all(set(r["phases"]) == set(PHASES) - {"admit"} for r in recs)
    assert clk.records(last=2)[-1]["t0"] == recs[-1]["t0"]


def test_registry_histograms_and_gauges_land():
    reg = Metrics()
    clk, t = _fake_clock(registry=reg)
    for _ in range(3):
        _drive(clk, t)
    clk.flush()  # batched flush: tests force it (FLUSH_EVERY is 32)
    snap = reg.snapshot()
    assert snap["gauges"]["step.steps_total"] == 3
    h = snap["histogram"]['step.phase_seconds{phase="wait"}']
    assert h["count"] == 3
    assert snap["histogram"]["step.wall_seconds"]["count"] == 3
    assert snap["gauges"]["step.host_fraction"] == pytest.approx(
        3.0 / 9.0, abs=1e-3)  # no admits in this test
    # render carries the step family for scrapers
    from dnn_tpu.utils.metrics import render_prometheus

    text = render_prometheus(reg)
    assert "step_phase_seconds_bucket" in text
    assert "step.host_fraction".replace(".", "_") in text


def test_summary_flushes_pending():
    """A scrape must never read a stale histogram: summary() flushes
    the batch even below FLUSH_EVERY. The cumulative totals need no
    flush at all."""
    reg = Metrics()
    clk, t = _fake_clock(registry=reg)
    _drive(clk, t)
    snap = reg.snapshot()
    assert snap["gauges"]["step.steps_total"] == 1
    assert "histogram" not in snap
    clk.summary()
    assert reg.snapshot()["histogram"]["step.wall_seconds"]["count"] == 1


def test_admit_split_sums_to_admit_phase():
    """(b) the four parts of `admit_split` sum to the `admit` phase, and
    pure_host_s is host_s less the prefill an admission dispatches and
    waits for."""
    clk, t = _fake_clock()
    t[0] += 0.010
    clk.note_admit(t[0] - 0.010, (0.004, 0.003, 0.002))
    _drive(clk, t)
    _drive(clk, t, admit=0.002)  # an admission that reports no parts
    s = clk.summary()
    split = s["admit_split"]
    assert set(split) == set(tl.ADMIT_PARTS)
    assert sum(split.values()) == pytest.approx(s["phases"]["admit"]["s"])
    assert split == pytest.approx({"self": 0.003, "prefill": 0.004,
                                   "first_token": 0.003,
                                   "install": 0.002})
    assert s["pure_host_s"] == pytest.approx(s["host_s"] - 0.007)
    assert s["pure_host_s"] <= s["host_s"]
    assert s["pure_host_s"] == pytest.approx(
        split["self"] + split["install"] + sum(
            s["phases"][p]["s"] for p in ("host", "commit", "obs")))
    # the same split, cumulative
    assert clk.admit_seconds_total == pytest.approx(split)


def test_cumulative_series_exact_between_flushes():
    """(c) drive 5 steps (FLUSH_EVERY is 32, so nothing was billed),
    scrape /metrics: the totals read 5, and the scrape — which renders
    the gauges under the registry's lock — does not deadlock."""
    from dnn_tpu.utils.metrics import render_prometheus

    reg = Metrics()
    clk, t = _fake_clock(registry=reg)
    for i in range(5):
        _drive(clk, t, admit=0.0005 if i == 0 else 0.0)
    assert len(clk._pending_flush) == 5  # between two flushes
    text = render_prometheus(reg)
    series = dict(line.rsplit(" ", 1) for line in text.splitlines()
                  if line and not line.startswith("#"))
    assert float(series["step_steps_total"]) == 5
    assert float(series["step_tokens_advanced_total"]) == 20
    assert float(series['step_phase_seconds_total{phase="wait"}']) == \
        pytest.approx(5 * 0.004)
    assert float(series['step_phase_seconds_total{phase="admit"}']) == \
        pytest.approx(0.0005)
    assert float(series['step_admit_seconds_total{part="self"}']) == \
        pytest.approx(0.0005)
    assert "step_steps_total" not in reg.snapshot()["counters"]
    # a scrape from INSIDE a registry render: a gauge whose callable
    # scrapes the clock's own gauges again while the lock is held
    reg.set_fn("outer", lambda: clk._gauges["step.steps_total"]()
               + clk.host_fraction())
    import threading

    out = []
    th = threading.Thread(target=lambda: out.append(reg.snapshot()),
                          daemon=True)
    th.start()
    th.join(10)
    assert not th.is_alive(), "a /metrics scrape deadlocked"
    assert out[0]["gauges"]["outer"] > 5


def test_metrics_bulk_hists():
    m = Metrics()
    m.bulk(hists={"x_seconds": [0.1, 0.2]}, hist_buckets=(0.15, 1.0))
    snap = m.snapshot()["histogram"]["x_seconds"]
    assert snap["count"] == 2
    assert snap["buckets"][0.15] == 1  # 0.1 below, 0.2 above


# ----------------------------------------------------------------------
# the worker's loop outside step() and submit(), and the CPU clock
# (ISSUE 37; injected wall and CPU clocks)
# ----------------------------------------------------------------------

class _ScriptedWorker:
    """A StepClock on an injected clock, driven as `_BatcherWorker.run`
    drives it."""

    def __init__(self):
        self.t = [100.0]
        self.clk = StepClock(registry=Metrics(), now=lambda: self.t[0])
        self.n = 0

    def spend(self, dt):
        self.t[0] += dt

    def admission(self):
        """A submit(): 1 ms of its own, 2 ms of chunk launches, 1 ms
        install, 4 ms waiting for the first token."""
        t0 = self.t[0]
        self.spend(0.008)
        self.clk.note_admit(t0, (0.002, 0.004, 0.001))

    def step(self):
        clk = self.clk
        rec = clk.begin()
        for phase, dt in (("host", 0.0002), ("dispatch", 0.001),
                          ("wait", 0.004), ("commit", 0.001),
                          ("obs", 0.0005)):
            self.spend(dt)
            clk.mark(rec, phase)
        clk.end(rec, n_adv=2)

    def busy_iteration(self, admissions=0, report=True):
        """`report=False`: the same steps and admissions from a caller
        that reports no loop (a batcher driven directly)."""
        part = self.clk.loop_part if report else (lambda *a: None)
        part("pre", self.n)
        self.n += 1
        self.spend(0.0001)                  # heartbeat, cancels
        part("admit")
        for _ in range(admissions):
            self.spend(0.0003)              # _admit around submit()
            self.admission()
        part("step")
        self.spend(0.00001)                 # the call of step()
        self.step()
        part("emit")
        self.spend(0.0008)                  # the hand-offs

    def idle_iteration(self, waited=0.1):
        clk = self.clk
        clk.loop_part("pre", self.n)
        self.n += 1
        self.spend(0.0001)
        clk.loop_part("wait")
        self.spend(waited)                  # q.get(timeout=0.1)


def _ten(clk):
    return dict(**{"phase." + p: v
                   for p, v in clk.phase_seconds_total.items()},
                **{"loop." + p: v for p, v in clk.loop_seconds_total.items()})


def test_ten_series_partition_a_scripted_worker_loop():
    """`step_phase_seconds_total` (six) and `step_loop_seconds_total`
    (four) partition the worker thread's time exactly: every second from
    the first `loop_part` to the last is in one of the ten, admissions and
    steps in their phases and the loop's parts less those."""
    w = _ScriptedWorker()
    clk = w.clk
    t_first = w.t[0]
    w.busy_iteration(admissions=2)
    w.idle_iteration()
    w.busy_iteration()
    w.busy_iteration(admissions=1)
    clk.loop_part("pre", w.n)  # the next iteration opens: `emit` ends
    ten = _ten(clk)
    assert sum(ten.values()) == pytest.approx(w.t[0] - t_first, abs=1e-12)
    assert ten == pytest.approx({
        "phase.admit": 3 * 0.008, "phase.host": 3 * 0.0002,
        "phase.dispatch": 3 * 0.001, "phase.wait": 3 * 0.004,
        "phase.commit": 3 * 0.001, "phase.obs": 3 * 0.0005,
        "loop.pre": 4 * 0.0001, "loop.wait": 0.1,
        "loop.admit": 3 * 0.0003,
        # the microseconds around step()'s own record count as emit
        "loop.emit": 3 * (0.0008 + 0.00001)}, abs=1e-12)
    # the same totals as scrape-time series
    from dnn_tpu.utils.metrics import render_prometheus

    clk._register_gauges()
    series = dict(line.rsplit(" ", 1)
                  for line in render_prometheus(clk._registry).splitlines()
                  if line and not line.startswith("#"))
    for part in tl.LOOP_PARTS:
        assert float(series[f'step_loop_seconds_total{{part="{part}"}}']) \
            == pytest.approx(ten["loop." + part])


def test_the_step_clock_reads_no_cpu_clock(monkeypatch):
    """One read of the thread CPU clock is a system call of 5.9 us on the
    chip's host, in steps of 10 ms (PERF.md section 6): nothing the
    worker calls a step or an admission may read one."""
    def refuse(*a):
        raise AssertionError("a CPU clock was read on the step path")

    for name in ("thread_time", "thread_time_ns", "process_time",
                 "process_time_ns", "clock_gettime", "clock_gettime_ns"):
        monkeypatch.setattr(time, name, refuse)
    w = _ScriptedWorker()
    w.busy_iteration(admissions=1)
    w.idle_iteration()
    w.busy_iteration()
    assert w.clk.steps_total == 2


def test_a_waiting_iteration_adds_to_wait_and_pre_alone():
    w = _ScriptedWorker()
    clk = w.clk
    w.busy_iteration(admissions=1)
    clk.loop_part("pre", w.n)
    before = _ten(clk)
    steps = clk.steps_total
    w.idle_iteration(waited=0.1)
    w.idle_iteration(waited=0.03)
    clk.loop_part("pre", w.n)
    after = _ten(clk)
    moved = {k: after[k] - before[k] for k in after
             if after[k] != before[k]}
    assert moved == pytest.approx({"loop.wait": 0.13,
                                   "loop.pre": 2 * 0.0001})
    assert clk.steps_total == steps


def test_stepz_is_unchanged_for_records_without_loop_parts():
    """A clock nobody reports a loop to (a batcher driven directly) gives
    the records, the fold and every /stepz field it gave before; a worker
    that does report one changes none of them, and adds `loop_split`."""
    bare, loop = _ScriptedWorker(), _ScriptedWorker()
    for i in range(3):
        bare.busy_iteration(admissions=i % 2, report=False)
        loop.busy_iteration(admissions=i % 2)
    sb, sl = bare.clk.summary(), loop.clk.summary()
    assert {k: v for k, v in sb.items() if k != "loop_split"} == \
        {k: v for k, v in sl.items() if k != "loop_split"}
    assert set(sb["phases"]["wait"]) == {"s", "frac", "mean_ms"}
    assert sb["loop_split"] == dict.fromkeys(tl.LOOP_PARTS, 0.0)
    assert all(r["loop"] is None for r in bare.clk.records())
    assert [r["phases"] for r in bare.clk.records()] == \
        [r["phases"] for r in loop.clk.records()]
    # what the loop adds: its parts over the ring's records (each record
    # carries what the loop spent since the one before ended, so the last
    # iteration's `emit` is not in yet)
    assert sl["loop_split"] == pytest.approx({
        "pre": 3 * 0.0001, "wait": 0.0, "admit": 0.0003,
        "emit": 2 * (0.0008 + 0.00001)}, abs=1e-9)
    comp = loop.clk.status_component()
    assert comp["pure_host_fraction"] == pytest.approx(
        sl["pure_host_s"] / sl["window_wall_s"], abs=1e-4)
    assert comp["pure_host_fraction"] < comp["host_fraction"]


def test_loop_parts_restart_clean_after_the_gate_was_off():
    w = _ScriptedWorker()
    clk = w.clk
    w.busy_iteration()
    obs.set_enabled(False)
    clk.loop_part("pre", w.n)       # gate off: nothing accrues
    w.spend(5.0)
    obs.set_enabled(True)
    before = _ten(clk)
    clk.loop_part("pre", w.n)       # starts clean: the 5 s are no part
    w.spend(0.0001)
    clk.loop_part("admit")
    after = _ten(clk)
    assert after["loop.pre"] - before["loop.pre"] == pytest.approx(0.0001)
    assert sum(after.values()) - sum(before.values()) == \
        pytest.approx(0.0001)


# ----------------------------------------------------------------------
# the instrumented pool (real batcher)
# ----------------------------------------------------------------------

@pytest.fixture(scope="module")
def pool():
    import jax

    from dnn_tpu.models import gpt
    from dnn_tpu.runtime.serving import ContinuousBatcher

    cfg = gpt.GPTConfig(block_size=32, vocab_size=128, n_layer=2,
                        n_head=2, n_embd=64)
    prepared = gpt.prepare_stacked(gpt.init(jax.random.PRNGKey(0), cfg),
                                   cfg)
    return ContinuousBatcher(cfg, prepared, slots=2, max_len=32,
                             prompt_pad=8)


def _round(srv, new_tokens=12):
    for i in range(srv.slots):
        srv.submit(np.arange(1, 5), new_tokens, seed=i)
    srv.drain()
    srv.results.clear()
    srv.finish_reasons.clear()


def test_phase_sum_covers_measured_wall(pool):
    """Attributed seconds against an EXTERNAL wall clock around the
    round. The bound is loose (0.85) because this pool's sub-ms steps
    make the python loop glue a large share of each step."""
    clock = StepClock(capacity=1024)
    pool.step_clock = clock
    try:
        _round(pool)  # warm/compile outside the measured window
        base = clock.steps_total
        t0 = time.perf_counter()
        _round(pool)
        wall = time.perf_counter() - t0
        n = clock.steps_total - base
        assert n >= 10
        recs = clock.records()[-n:]
        attributed = sum(r["wall"] for r in recs)
        assert attributed <= wall * 1.001  # can't attribute time that
        # didn't pass
        assert attributed / wall >= 0.85, (attributed, wall)
        # every in-step phase present on every record
        for r in recs:
            assert set(r["phases"]) >= {"host", "dispatch", "wait",
                                        "commit", "obs"}, r
    finally:
        pool.step_clock = None


def test_admit_attributed_to_next_step(pool):
    clock = StepClock(capacity=64)
    pool.step_clock = clock
    try:
        pool.submit(np.arange(1, 5), 4, seed=0)
        pool.step()
        recs = clock.records()
        assert recs, "step must record"
        first = recs[-1]
        assert first["phases"].get("admit", 0.0) > 0.0
        assert first["admit_slices"], first
        # the admit slice predates the step's own t0
        a0, a1 = first["admit_slices"][0]
        assert a0 < a1 <= first["t0"] + 1e-3
        pool.drain()
        pool.results.clear()
        pool.finish_reasons.clear()
    finally:
        pool.step_clock = None


def test_gate_off_records_nothing(pool):
    clock = StepClock(capacity=64)
    pool.step_clock = clock
    try:
        obs.set_enabled(False)
        assert clock.begin() is None  # the one-None-check gate
        pool.submit(np.arange(1, 5), 4, seed=0)
        pool.drain()
        pool.results.clear()
        pool.finish_reasons.clear()
        assert clock.steps_total == 0
        assert clock.records() == []
        obs.set_enabled(True)  # re-enable takes effect immediately
        _round(pool, new_tokens=4)
        assert clock.steps_total > 0
    finally:
        pool.step_clock = None


def test_statusz_step_component(pool):
    clock = StepClock(capacity=64)
    pool.step_clock = clock
    try:
        _round(pool, new_tokens=4)
        comp = clock.status_component()
        assert comp["state"] == "ok"
        assert comp["steps_total"] == clock.steps_total
        assert comp["last_wall_ms"] > 0
        assert comp["last_step_age_s"] >= 0
        assert "host fraction" in comp["detail"]
    finally:
        pool.step_clock = None


# ----------------------------------------------------------------------
# analyze(): synthetic capture goldens
# ----------------------------------------------------------------------

def _synthetic_trace(tmp_path, *, gz=True, meta=None, n_steps=3,
                     step_ms=10.0, busy_ms=6.0, lead_ms=1.5):
    """One 6 ms device op per 10 ms step, plus track metadata — the
    deterministic shape the selftest also pins."""
    events = [
        {"ph": "M", "pid": 7, "name": "process_name",
         "args": {"name": "/host:CPU"}},
        {"ph": "M", "pid": 7, "tid": 2, "name": "thread_name",
         "args": {"name": "tf_XLATfrtCpuClient"}},
        {"ph": "M", "pid": 7, "tid": 1, "name": "thread_name",
         "args": {"name": "python"}},
    ]
    for i in range(n_steps):
        events.append({"ph": "X", "pid": 7, "tid": 2, "name": "fusion.1",
                       "ts": (lead_ms + step_ms * i) * 1e3,
                       "dur": busy_ms * 1e3,
                       "args": {"hlo_op": "fusion.1"}})
    # a host-python event must NOT count as device time
    events.append({"ph": "X", "pid": 7, "tid": 1, "name": "step()",
                   "ts": 0.0, "dur": n_steps * step_ms * 1e3})
    doc = {"traceEvents": events, "displayTimeUnit": "ns"}
    name = "vm.trace.json.gz" if gz else "vm.trace.json"
    p = os.path.join(tmp_path, name)
    if gz:
        with gzip.open(p, "wt") as f:
            json.dump(doc, f)
    else:
        with open(p, "w") as f:
            json.dump(doc, f)
    if meta is not None:
        with open(os.path.join(tmp_path, "meta.json"), "w") as f:
            json.dump(meta, f)
    return p


def test_analyze_synthetic_golden(tmp_path):
    d = str(tmp_path)
    _synthetic_trace(d)
    a = analyze(d)  # dir form resolves the trace file itself
    assert a["device"]["ops"] == 3
    assert a["device"]["busy_s"] == pytest.approx(0.018, abs=1e-9)
    # window = event span (no meta): 1.5 .. 27.5 ms -> 26 ms? no: the
    # host event spans 0..30 ms, so the window is 30 ms
    assert a["window_s"] == pytest.approx(0.030, abs=1e-6)
    assert a["device"]["busy_frac"] == pytest.approx(0.6, abs=1e-3)
    assert a["host_gaps"]["count"] == 2
    assert a["host_gaps"]["p50_ms"] == pytest.approx(4.0, abs=1e-3)
    assert a["top_ops"][0]["name"] == "fusion.1"
    assert a["top_ops"][0]["frac_of_device"] == pytest.approx(1.0)
    # host python track exists and is distinct from the device ops
    assert any("python" in k for k in a["tracks"])
    assert "steps" not in a  # the steps are IN a capture (annotations)


def test_analyze_plain_json_equals_gzip(tmp_path):
    d1, d2 = tmp_path / "a", tmp_path / "b"
    d1.mkdir()
    d2.mkdir()
    pg = _synthetic_trace(str(d1), gz=True)
    pj = _synthetic_trace(str(d2), gz=False)
    ag, aj = analyze(pg), analyze(pj)
    for k in ("window_s", "events"):
        assert ag[k] == aj[k]
    assert ag["device"] == aj["device"]


def test_analyze_rejects_garbage_and_truncated(tmp_path):
    bad = tmp_path / "bad.json"
    bad.write_text("definitely { not json")
    with pytest.raises(ValueError):
        analyze(str(bad))
    # truncated gzip: a valid header with a cut-off body
    good = _synthetic_trace(str(tmp_path))
    data = open(good, "rb").read()
    trunc = tmp_path / "trunc.trace.json.gz"
    trunc.write_bytes(data[: len(data) // 2])
    with pytest.raises(ValueError):
        analyze(str(trunc))
    # valid JSON, wrong shape
    shape = tmp_path / "shape.json"
    shape.write_text(json.dumps({"notTraceEvents": []}))
    with pytest.raises(ValueError):
        analyze(str(shape))
    # empty dir
    empty = tmp_path / "emptydir"
    empty.mkdir()
    with pytest.raises(ValueError):
        analyze(str(empty))


def test_meta_bounds_the_analysis_window(tmp_path):
    """With a sidecar meta the window is the ARMED window, not the
    event span (a first capture's profiler init is not idle time)."""
    d = str(tmp_path)
    _synthetic_trace(d, meta={"perf_begin": 100.0, "perf_end": 100.032,
                              "step_begin": 5, "step_end": 8,
                              "backend": "cpu"})
    a = analyze(d)
    assert a["window_s"] == pytest.approx(0.032, abs=1e-6)
    assert a["device"]["ops"] == 3


def _host_spans(capture_dir):
    """{line name: [(name, start_ns, end_ns, stats)]} of the `step*` /
    `admit*` annotations on the capture's /host:CPU plane."""
    import glob

    from jax.profiler import ProfileData

    path = glob.glob(os.path.join(capture_dir, "**", "*.xplane.pb"),
                     recursive=True)[-1]
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                    dict(e.stats)) for e in line.events
                   if e.name.split(".")[0] in ("step", "admit")]
            if evs:
                out[line.name] = evs
    return out


def _children(spans, parent):
    _, p0, p1, _ = parent
    return [s for s in spans if s is not parent and p0 <= s[1]
            and s[2] <= p1]


def test_capture_holds_step_and_admit_spans(pool, tmp_path):
    """(a) End to end on a REAL jax.profiler capture (CPU backend): the
    `.xplane.pb` holds the batcher's phases as annotations on ONE
    thread's line, nested step > step.<phase> and admit > admit.<part>,
    with `step` / `rid` stats; profile.py's sidecar meta carries the
    step-counter range the `step` stats lie in."""
    from dnn_tpu.obs.profile import capture_step

    clock = StepClock(capacity=1024).install()
    pool.step_clock = clock
    try:
        _round(pool, new_tokens=6)  # warm
        before = clock.steps_total
        path, _ = capture_step(lambda: _round(pool, new_tokens=6),
                               capture_root=str(tmp_path))
        meta = json.load(open(os.path.join(path, "meta.json")))
        assert meta["step_begin"] == before
        assert meta["step_end"] == clock.steps_total
        assert meta["perf_end"] > meta["perf_begin"]
        assert meta["backend"] == "cpu"
        lines = _host_spans(path)
        assert len(lines) == 1, list(lines)  # the worker's line
        (spans,) = lines.values()
        steps = [s for s in spans if s[0] == "step"]
        assert len(steps) == clock.steps_total - before
        assert [s[3]["step"] for s in steps] == list(
            range(before, clock.steps_total))
        retired = [s for s in spans if s[0] == "step.commit.retire"]
        for st in steps:
            kids = [k for k in _children(spans, st) if k not in retired]
            # the five phases, in order, each carrying the step's index
            assert [k[0] for k in kids] == [
                "step." + p for p in PHASES[1:]], kids
            assert all(k[3]["step"] == st[3]["step"] for k in kids)
            # contiguous: each phase starts where the last one ended
            for a, b in zip(kids, kids[1:]):
                assert a[2] <= b[1] <= a[2] + 1_000_000  # < 1 ms apart
        # a retirement's device edits: one span a request that ended in
        # the capture, inside the `step.commit` of the step it ended in
        commits = [s for s in spans if s[0] == "step.commit"]
        assert len(retired) == pool.slots
        assert len({r[3]["rid"] for r in retired}) == pool.slots
        assert {r[3]["slot"] for r in retired} == set(range(pool.slots))
        assert all(any(r in _children(spans, c) for c in commits)
                   for r in retired)
        admits = [s for s in spans if s[0] == "admit"]
        assert len(admits) == pool.slots
        rids = set()
        for ad in admits:
            assert ad[3]["prompt_len"] == 4
            kids = _children(spans, ad)
            # the finish-and-install launch precedes the first-token read
            assert [k[0] for k in kids] == [
                "admit.prefill", "admit.install", "admit.first_token"]
            assert kids[0][3]["chunks"] == 1
            assert len({k[3]["rid"] for k in kids}) == 1
            rids.add(kids[0][3]["rid"])
        assert len(rids) == pool.slots  # one rid per admission
        # no step lies inside an admission: they alternate on the thread
        assert not any(_children(steps, ad) for ad in admits)
        # the old name of step.dispatch is gone with its annotation
        assert not any(s[0].startswith("serving.decode_step")
                       for s in spans)
    finally:
        pool.step_clock = None


@pytest.mark.parametrize("spec", [False, True])
def test_overlap_steps_keep_the_phase_order_in_a_capture(tmp_path, spec):
    """The overlap pipeline's calls share the protocol: a filling
    dispatch closes `wait` and `commit` empty, the trailing flush opens
    in `wait`, and the speculative batcher's override does the same."""
    import jax

    from dnn_tpu.models import gpt
    from dnn_tpu.obs.profile import capture_step

    cfg = gpt.GPTConfig(block_size=32, vocab_size=128, n_layer=2,
                        n_head=2, n_embd=64)
    prepared = gpt.prepare_stacked(gpt.init(jax.random.PRNGKey(0), cfg),
                                   cfg)
    kw = dict(slots=2, max_len=32, prompt_pad=8, prefill_chunk_tokens=8,
              overlap=True)
    if spec:
        from dnn_tpu.runtime.serving_spec import SpeculativeBatcher

        srv = SpeculativeBatcher(cfg, prepared, cfg, prepared, spec_k=2,
                                 **kw)
    else:
        from dnn_tpu.runtime.serving import ContinuousBatcher

        srv = ContinuousBatcher(cfg, prepared, **kw)
    clock = StepClock(capacity=256)
    srv.step_clock = clock
    _round(srv, new_tokens=5)  # warm
    before = clock.steps_total
    path, _ = capture_step(lambda: _round(srv, new_tokens=5),
                           capture_root=str(tmp_path))
    (spans,) = _host_spans(path).values()
    steps = [s for s in spans if s[0] == "step"]
    assert len(steps) == clock.steps_total - before
    full = ["step." + p for p in PHASES[1:]]
    shapes = set()
    for st in steps:
        kids = [k[0] for k in _children(spans, st)
                if k[0] != "step.commit.retire"]  # a grandchild
        assert kids in (full, full[2:]), kids  # a flush opens in `wait`
        shapes.add(len(kids))
    assert shapes == {5, 3}
    # interleaved admission dispatches nothing in submit(): an `admit`
    # with no parts, all of it the admission's own host time
    admits = [s for s in spans if s[0] == "admit"]
    assert len(admits) == srv.slots
    assert not any(s[0].startswith("admit.") for s in spans)
    assert clock.admit_seconds_total["prefill"] == 0.0
    assert clock.admit_seconds_total["self"] > 0.0
    assert clock.summary()["mixed_steps"] > 0


def test_no_annotation_without_a_recording_capture(pool, tmp_path,
                                                   monkeypatch):
    """(a) With no obs-driven capture recording the batcher opens no
    annotation at all (rec.spans stays None; a bare start_trace sees
    nothing), and none with the obs gate off even while one records."""
    import jax

    from dnn_tpu.obs import profile

    opened = []
    real = profile.annotation_ctx

    def spy(name, **stats):
        ctx = real(name, **stats)
        if ctx is not profile._NULL_CTX:
            opened.append(name)
        return ctx

    monkeypatch.setattr(profile, "annotation_ctx", spy)
    clock = StepClock(capacity=64)
    pool.step_clock = clock
    try:
        _round(pool, new_tokens=4)  # warm
        assert not profile.capturing()
        rec = clock.begin()
        assert rec is not None and rec.spans is None
        bare = tmp_path / "bare"
        jax.profiler.start_trace(str(bare))  # not an obs-driven capture
        try:
            _round(pool, new_tokens=4)
        finally:
            jax.profiler.stop_trace()
        assert opened == []
        assert _host_spans(str(bare)) == {}
        # gate off, capture recording: still nothing
        obs.set_enabled(False)
        path, _ = profile.capture_step(
            lambda: _round(pool, new_tokens=4),
            capture_root=str(tmp_path / "off"))
        obs.set_enabled(True)
        assert opened == []
        assert _host_spans(path) == {}
        # and with both on, the spy does see them
        profile.capture_step(lambda: _round(pool, new_tokens=4),
                             capture_root=str(tmp_path / "on"))
        assert "step.dispatch" in opened and "admit.install" in opened
    finally:
        obs.set_enabled(True)
        pool.step_clock = None


def test_worker_loop_builds_no_annotation_without_a_capture(pool,
                                                            monkeypatch):
    """`_BatcherWorker.run` and `step()` with no capture recording: no
    span is opened and no TraceAnnotation object is built, whatever the
    traffic (the loop checks `_capturing` once an iteration). While one
    records, the loop's iterations and parts, the steps nested in them
    and a retirement's device edits are all written, from the worker's
    thread."""
    import threading

    import jax

    from dnn_tpu.obs import profile
    from dnn_tpu.runtime.lm_server import _BatcherWorker

    built = []  # (name, thread) of every annotation object constructed

    class Spy(jax.profiler.TraceAnnotation):
        def __init__(self, name, **stats):
            built.append((name, threading.current_thread().name))
            super().__init__(name, **stats)

    monkeypatch.setattr(profile, "_trace_annotation", Spy)
    clock = StepClock(capacity=64)
    pool.step_clock = clock
    worker = _BatcherWorker(pool)
    worker.start()
    try:
        def serve(n):
            streamed = []
            futs = [worker.submit(np.arange(1, 5), 5, seed=i,
                                  on_token=streamed.append)
                    for i in range(n)]
            assert all(len(f.result(timeout=120)) == 5 for f in futs)
            assert len(streamed) == 5 * n
            time.sleep(0.25)  # two timeouts of the idle wait

        serve(3)  # more requests than slots: the admission loop holds one
        assert not profile.capturing()
        assert built == [] and clock._loop_spans is None
        assert clock.loop_seconds_total["wait"] > 0.2
        assert clock.loop_seconds_total["emit"] > 0.0
        with profile.mark_recording():
            serve(3)
        names = {n for n, _ in built}
        assert {"loop", "loop.pre", "loop.wait", "loop.admit", "loop.step",
                "loop.emit",
                "step", "step.commit", "step.commit.retire", "admit",
                "admit.install"} <= names, sorted(names)
        assert {t for _, t in built} == {"lm-batcher"}
        n_built = len(built)
        serve(1)  # the capture has ended: nothing more is built
        # (the iteration in flight when it ended may close its parts)
        assert len(built) <= n_built + 2
        serve(1)
        assert len(built) <= n_built + 2
    finally:
        worker.stop()
        worker.join(30)
        pool.step_clock = None
        pool.results.clear()
        pool.finish_reasons.clear()
    assert not worker.is_alive()
    assert worker.cpu_clock_id is not None  # for the scrape-time gauge


# ----------------------------------------------------------------------
# /stepz + CLI
# ----------------------------------------------------------------------

def test_stepz_endpoint_json_prom():
    clk, t = _fake_clock()
    for _ in range(3):
        _drive(clk, t, admit=0.0005)
    srv = obs.serve_metrics(0, stepclock=clk)
    try:
        base = f"http://127.0.0.1:{srv.port}/stepz"
        s = json.loads(urllib.request.urlopen(base, timeout=10).read())
        assert s["window_steps"] == 3
        assert s["phases"]["wait"]["mean_ms"] == pytest.approx(4.0)
        assert s["admit_split"]["self"] == pytest.approx(0.0015)
        assert s["pure_host_s"] == pytest.approx(s["host_s"])
        prom = urllib.request.urlopen(base + "?format=prom",
                                      timeout=10).read().decode()
        assert "dnn_tpu_step_host_fraction" in prom
        assert 'dnn_tpu_step_phase_frac{phase="wait"}' in prom
        # the host-only timeline went with StepClock.chrome_trace():
        # the steps are in a POST /profilez capture now
        with pytest.raises(urllib.error.HTTPError) as e:
            urllib.request.urlopen(base + "?format=trace&last=2",
                                   timeout=10)
        assert e.value.code == 400
    finally:
        srv.close()


def test_stepz_404_without_clock():
    srv = obs.serve_metrics(0)
    try:
        urllib.request.urlopen(f"http://127.0.0.1:{srv.port}/stepz",
                               timeout=10)
        raise AssertionError("expected 404")
    except urllib.error.HTTPError as e:
        assert e.code == 404
    finally:
        srv.close()


def test_cli_selftest_and_path(tmp_path, capsys):
    from dnn_tpu.obs.__main__ import main

    assert main(["timeline", "--selftest"]) == 0
    out = capsys.readouterr().out
    assert "timeline selftest ok" in out
    p = _synthetic_trace(str(tmp_path))
    assert main(["timeline", p]) == 0
    out = capsys.readouterr().out
    assert "device: busy" in out and "fusion.1" in out
    assert main(["timeline", p, "--json"]) == 0
    parsed = json.loads(capsys.readouterr().out)
    assert parsed["device"]["ops"] == 3
