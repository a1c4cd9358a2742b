"""Readers of where the worker thread's time goes between device programs
(PR 37): the worker's `loop*` spans in the capture, and the whole-window
counters of its loop, of the streams' hand-off and of the threads' CPU.
`layers/<metric>.json` names them as `"hosttime:<function>"`.

**Spans.** `chipbench/spans.py` keeps the worker's `step*` and `admit*`
spans; what lies under neither is its `outside`. The program now writes its
whole loop: one `loop` span an iteration with `loop.pre` (heartbeat, control
operations, cancels), `loop.wait` (blocked on an empty queue with nothing
active: the one part that is not a cost), `loop.admit` (the admission loop;
the `admit*` spans nest inside, so its own remainder is the work around
`submit()`), `loop.step` (the call of `step()`; the `step*` spans nest
inside, so its own remainder is the call's own microseconds) and `loop.emit`
(handing each token to its stream, publishing finished requests) as
children, each opening where the last one closed. `load_capture` keeps all
three roots, on the same worker line, so `idle_pct(under="loop.<part>")` is
that part's own remainder and the parts add up, with `unnamed` (under no
span of the worker, or under `loop` alone), to
`spans.idle_pct(under="outside")`.
Device operations are taken from `spans.capture_of`: one definition of idle.

**Counters** (window differences of `/metrics`, as `spans._delta`):
`step_loop_seconds_total{part}` beside `step_phase_seconds_total{phase}` (ten
series that partition the worker thread's time);
`serving_emit_lag_seconds_{sum,count,max}` (a committed token's wait for the
event-loop thread, both ends on `perf_counter`); `process_perf_counter_seconds`
(the daemon's own clock at each scrape: the window's seconds, and where the
capture's `meta.json` lies in it); `jax_traces_total`;
`process_thread_cpu_seconds_total{thread="worker"|"rpc_loop"}` and
`process_cpu_seconds_total`, read at scrape time only. **The chip host's CPU
clocks tick in steps of 10 ms and charge a blocked thread a quarter of its
blocked time** (my chip runs, PR 37: `.chipscratch/cpu_clock_check.py`), so
the shares made from them (`*_cpu_*` in the note row) are indications over a
45 s window, no more, and no entry of `BENCHMARK.json` reads them; the
program stamps no CPU clock inside a step for the same reason.

Every reader returns None where a series or span it needs is missing (a
program that predates it) and never raises. `BENCHMARK.json` holds 6 of the
38 metrics ISSUE 37 lists (it may have 128 per-layer entries and had 120):
the first reader called in a run leaves the WHOLE split as one `note` row
(`hosttime`) in the run's output, which is where PERF.md's tables come from.
"""

from __future__ import annotations

import json
import os
from typing import Dict, Optional

from chipbench import spans, tracered

__all__ = ["ROOTS", "LOOP_PARTS", "SPAN_PARTS", "load_capture", "capture_of", "idle_pct",
           "clock_lead_ms", "step_cycle", "launch_wake_ms_per_step",
           "emit_lag_ms", "loop_ms_per_step", "capture_rates", "split"]

ROOTS = ("step", "admit", "loop")
LOOP_PARTS = ("pre", "wait", "admit", "emit")   # of the wall series
SPAN_PARTS = ("pre", "wait", "admit", "step", "emit")  # `loop.<part>` spans
#: the loop's parts in which the worker works (`wait` is no cost)
_WORKING = ("step_loop_seconds_total", "part", ("pre", "admit", "emit"))
#: where the worker neither waits for the device nor for an arrival
_BUSY = (("step_phase_seconds_total", "phase",
          ("host", "dispatch", "commit", "obs")),
         ("step_admit_seconds_total", "part", ("self", "prefill", "install")),
         _WORKING)


def load_capture(path: str) -> list:
    """The worker thread's `step*`, `admit*` and `loop*` spans of one
    `.xplane.pb`, as `spans.load_capture` lists them."""
    space = spans._xspace_class()()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    lines = []
    for plane in space.planes:
        if plane.name != tracered.HOST_PLANE:
            continue
        stat_names = {e.key: e.value.name for e in plane.stat_metadata}
        meta = {e.key: e.value for e in plane.event_metadata}
        wanted = {k for k, m in meta.items()
                  if m.name.split(".")[0] in ROOTS}
        for line in plane.lines:
            lines.append([[meta[ev.metadata_id].name,
                           int(line.timestamp_ns + ev.offset_ps / 1e3),
                           int(ev.duration_ps / 1e3),
                           spans._stat_values(ev.stats, stat_names)]
                          for ev in line.events if ev.metadata_id in wanted])
    return spans.worker_line(lines)


def capture_of(facts) -> Optional[dict]:
    """{"devices", "spans"}: `spans.capture_of`'s device operations with
    the worker's line read again for all of ROOTS; kept on `facts`."""
    if "hosttime_capture" not in facts:
        cap, base = None, spans.capture_of(facts)
        if base is not None:
            path = tracered.find_xplane(facts["trace_capture"])
            cap = {"devices": base["devices"], "spans": load_capture(path)}
        facts["hosttime_capture"] = cap
    return facts["hosttime_capture"]


def _idle(facts) -> Optional[dict]:
    cap = capture_of(facts)
    if cap is None or not any(s[0].startswith("loop") for s in cap["spans"]):
        return None
    if "hosttime_idle" not in facts:
        facts["hosttime_idle"] = spans.idle_by_span(cap)
    return facts["hosttime_idle"]


def idle_pct(facts, *, under: str) -> Optional[float]:
    """Device idle time under the worker's span `under` itself (what its
    children leave of it), as a share of the traced extent; `"unnamed"`:
    under no span of the worker, or under `loop` alone."""
    _note(facts)
    return _share(_idle(facts), under)


def _share(idle: Optional[dict], under: str) -> Optional[float]:
    if idle is None or not idle["window_s"]:
        return None
    names = ("outside", "loop") if under == "unnamed" else (under,)
    return 100.0 * sum(idle["by"].get(n, 0.0) for n in names) \
        / idle["window_s"]


# ----------------------------------------------------------------------
# a step's cycle, without the device plane's clock
# ----------------------------------------------------------------------

#: the worker's spans under which it neither waits for the device nor for
#: an arrival, and (no cell overlaps its steps) the device has nothing to
#: do: what each keeps of itself is serial host time
HOST_SERIAL = ("step.host", "step.commit", "step.commit.retire", "step.obs",
               "loop.pre", "loop.admit", "loop.step", "loop.emit", "loop")


def _coarse_busy(ops, join_ns=50_000):
    """The device's busy intervals with gaps under `join_ns` closed: one
    interval a program, or a run of programs launched back to back."""
    out = []
    for s, e in tracered._merge([o[0], o[0] + o[1]] for o in ops):
        if out and s - out[-1][1] < join_ns:
            out[-1][1] = e
        else:
            out.append([s, e])
    return out


def clock_lead_ms(capture: dict) -> Optional[Dict[str, float]]:
    """Bounds on how far the device plane's clock runs AHEAD of the host
    plane's in this capture. The two are meant to be one clock and are not
    (1-2 ms, another value each capture: my chip runs, PR 37). A step's
    program cannot start before its `step.dispatch` begins (`lo`: the 90th
    percentile over the steps of dispatch start less the program's apparent
    start) nor end after its `step.wait` ends (`hi`: the 10th percentile of
    wait end less apparent end); the truth lies between, `lo` + the least
    launch latency = `hi` - the least wake-up latency."""
    import bisect

    busy = _coarse_busy(capture["devices"][0]["ops"])
    starts = [b[0] for b in busy]
    by_step: Dict[object, dict] = {}
    for name, start, dur, stats in capture["spans"]:
        if name in ("step.dispatch", "step.wait"):
            by_step.setdefault(stats.get("step"), {})[name] = (start,
                                                               start + dur)
    lo, hi = [], []
    for parts in by_step.values():
        if len(parts) < 2:
            continue
        d0, w1 = parts["step.dispatch"][0], parts["step.wait"][1]
        i = bisect.bisect_left(starts, d0)
        near = [j for j in (i - 1, i) if 0 <= j < len(busy)
                and abs(starts[j] - d0) < 5_000_000]
        if not near:
            continue
        j = min(near, key=lambda j: abs(starts[j] - d0))
        lo.append(d0 - busy[j][0])
        hi.append(w1 - busy[j][1])
    if len(lo) < 8:
        return None
    lo.sort()
    hi.sort()
    return {"lo": lo[int(0.9 * (len(lo) - 1))] / 1e6,
            "hi": hi[int(0.1 * (len(hi) - 1))] / 1e6, "steps": len(lo)}


def step_cycle(facts) -> Optional[Dict[str, float]]:
    """Milliseconds a step of the capture, none of which needs the device
    plane's clock to agree with the host's: `idle` (the device's idle time
    over the `step` spans in the extent), `under_admit` and `under_wait`
    (idle under `admit*` and `loop.wait`: spans of many milliseconds),
    `host_serial` (what the HOST_SERIAL spans keep of themselves: serial
    work on the host's own clock, during which the device has nothing to
    run) and its parts, and `launch_wake` = idle - under_admit - under_wait
    - host_serial: what is left for the time from `step.dispatch`'s begin
    to the program's start and from its end to `step.wait`'s return — the
    runtime's launch and wake-up, the interpreter lock's return included."""
    idle = _idle(facts)
    cap = capture_of(facts)
    if idle is None:
        return None
    ops = [o for d in cap["devices"] for o in d["ops"]]
    t0 = min(o[0] for o in ops)
    t1 = max(o[0] + o[1] for o in ops)
    # one cycle a step that BEGINS in the extent: the extent's two ends cut
    # a cycle each, and half-open counting charges the pair as one
    n = sum(1 for s in cap["spans"] if s[0] == "step" and t0 <= s[1] < t1)
    if not n:
        return None
    own: Dict[str, float] = {}
    for a, b, name in spans._innermost(cap["spans"]):
        a, b = max(a, t0), min(b, t1)
        if b > a and name in HOST_SERIAL:
            own[name] = own.get(name, 0.0) + (b - a) / 1e9
    by = idle["by"]
    under_admit = sum(v for k, v in by.items() if spans._under(k, "admit"))
    under_wait = by.get("loop.wait", 0.0) + by.get("outside", 0.0)
    serial = sum(own.values())
    per = 1e3 / n
    out = {"steps": n, "idle": idle["idle_s"] * per,
           "under_admit": under_admit * per, "under_wait": under_wait * per,
           "host_serial": serial * per,
           "launch_wake": (idle["idle_s"] - under_admit - under_wait
                           - serial) * per}
    out.update({"own." + k: v * per for k, v in sorted(own.items())})
    return out


def launch_wake_ms_per_step(facts) -> Optional[float]:
    """`step_cycle`'s `launch_wake`: the device's idle time a step that no
    serial host work, admission or wait for an arrival explains."""
    _note(facts)
    cycle = step_cycle(facts)
    return cycle["launch_wake"] if cycle else None


def _shifted(capture: dict, ns: int) -> dict:
    return {"devices": [{"name": d["name"],
                         "ops": [[o[0] + ns, o[1], o[2]] for o in d["ops"]]}
                        for d in capture["devices"]],
            "spans": capture["spans"]}


# ----------------------------------------------------------------------
# whole-window counters
# ----------------------------------------------------------------------

def _ratio(num: Optional[float], den: Optional[float], scale: float = 1.0):
    return None if num is None or not den else scale * num / den


def emit_lag_ms(facts) -> Optional[float]:
    """Mean time a committed token waited for the event-loop thread, over
    the window: from the worker's `on_token` (`call_soon_threadsafe`) to the
    stream handler's dequeue, both on `perf_counter`. The client sees it
    in every gap and first token."""
    _note(facts)
    return _ratio(spans._delta(facts, "serving_emit_lag_seconds_sum"),
                  spans._delta(facts, "serving_emit_lag_seconds_count"), 1e3)


def loop_ms_per_step(facts) -> Optional[float]:
    """Wall milliseconds a step that the worker's loop spends working
    outside `step()` and `submit()` (`pre`, `admit` less the admissions,
    `emit`), over the window."""
    _note(facts)
    return _ratio(spans._delta_sum(facts, *_WORKING),
                  spans._delta(facts, "step_steps_total"), 1e3)


def _window_s(facts) -> Optional[float]:
    return spans._delta(facts, "process_perf_counter_seconds")


def _thread_cpu(facts, thread: str) -> Optional[float]:
    return spans._delta(
        facts, f'process_thread_cpu_seconds_total{{thread="{thread}"}}')


def capture_rates(facts) -> Optional[Dict[str, float]]:
    """Steps a second inside the capture, in the quiet rest of the window
    (before the capture began and after its events were collected) and
    while `stop_trace` collected them, from the capture's `meta.json` and
    the window's `step_steps_total`; `slowdown_pct` = 100 x (1 - inside /
    quiet). None without the daemon's clock on `/metrics`."""
    root = facts.get("trace_capture")
    m0, m1 = facts.get("metrics0") or {}, facts.get("metrics1") or {}
    keys = ("process_perf_counter_seconds", "step_steps_total")
    if any(k not in m for k in keys for m in (m0, m1)) or not root:
        return None
    try:
        with open(os.path.join(root, "meta.json")) as f:
            meta = json.load(f)
        t_in0, t_in1 = meta["perf_begin"], meta["perf_end"]
        n_in0, n_in1 = meta["step_begin"], meta["step_end"]
        t_stop, n_stop = t_in1 + meta["stop_s"], meta["step_stopped"]
    except (OSError, ValueError, KeyError, TypeError):
        return None
    if None in (n_in0, n_in1, n_stop) or t_in1 <= t_in0:
        return None
    t0, t1 = (m[keys[0]] for m in (m0, m1))
    n0, n1 = (m[keys[1]] for m in (m0, m1))
    quiet_s, quiet_n = max(0.0, t_in0 - t0), max(0.0, n_in0 - n0)
    if t1 > t_stop:
        quiet_s, quiet_n = quiet_s + (t1 - t_stop), quiet_n + (n1 - n_stop)
    if quiet_s <= 0 or not quiet_n:
        return None
    inside = (n_in1 - n_in0) / (t_in1 - t_in0)
    quiet = quiet_n / quiet_s
    out = {"inside_steps_per_s": inside, "quiet_steps_per_s": quiet,
           "quiet_s": quiet_s, "stop_s": meta["stop_s"],
           "slowdown_pct": 100.0 * (1.0 - inside / quiet),
           "python_tracer": meta.get("python_tracer")}
    if meta["stop_s"] > 0:
        out["stopping_steps_per_s"] = (n_stop - n_in1) / meta["stop_s"]
    return out


def split(facts) -> dict:
    """Everything ISSUE 37's table lists, for one run: the idle shares by
    span (`idle_*_pct`), the counters' ratios, the capture's cost, and the
    two sums the shares have to meet. Missing pieces read None."""
    out: Dict[str, Optional[float]] = {}
    for phase in ("host", "dispatch", "wait", "commit", "obs"):
        out[f"idle_step_{phase}_pct"] = spans.idle_pct(
            facts, under="step." + phase)
    idle = _idle(facts)
    for part in SPAN_PARTS:
        out[f"idle_loop_{part}_pct"] = _share(idle, "loop." + part)
    out["idle_unnamed_pct"] = _share(idle, "unnamed")
    loop = [out[f"idle_loop_{p}_pct"] for p in SPAN_PARTS] \
        + [out["idle_unnamed_pct"]]
    out["idle_loop_parts_sum_pct"] = None if None in loop else sum(loop)
    out["idle_outside_pct"] = spans.idle_pct(facts, under="outside")
    steps = [out[f"idle_step_{p}_pct"]
             for p in ("host", "dispatch", "wait", "commit", "obs")]
    out["idle_step_phases_sum_pct"] = None if None in steps else sum(steps)
    out["idle_step_pct"] = spans.idle_pct(facts, under="step")

    # the same shares with the device plane moved to the middle of the
    # bounds on its clock's lead; and the step's cycle, which needs none
    cap = capture_of(facts) if idle else None
    lead = clock_lead_ms(cap) if cap else None
    out["device_clock_lead_ms"] = lead
    if lead:
        by = spans.idle_by_span(_shifted(
            cap, int((lead["lo"] + lead["hi"]) / 2 * 1e6)))
        out["lead_corrected"] = {
            k: 100.0 * by["by"].get(k, 0.0) / by["window_s"]
            for k in ("step.dispatch", "step.wait", "step.commit",
                      "step.obs", "loop.emit", "loop.pre", "loop.admit")}
    out["step_cycle_ms"] = step_cycle(facts)

    n_steps = spans._delta(facts, "step_steps_total")
    tokens = spans._delta(facts, "step_tokens_advanced_total")
    window = _window_s(facts)
    out["loop_ms_per_step"] = loop_ms_per_step(facts)
    for phase in spans.PHASES:
        out[f"phase_{phase}_ms_per_step"] = _ratio(spans._delta(
            facts, f'step_phase_seconds_total{{phase="{phase}"}}'),
            n_steps, 1e3)
    for part in LOOP_PARTS:
        out[f"loop_{part}_ms_per_step"] = _ratio(
            spans._delta(facts, f'step_loop_seconds_total{{part="{part}"}}'),
            n_steps, 1e3)
    out["loop_emit_us_per_token"] = _ratio(
        spans._delta(facts, 'step_loop_seconds_total{part="emit"}'),
        tokens, 1e6)
    # from the host's CPU clocks (10 ms ticks: indications only)
    busy = [spans._delta_sum(facts, *b) for b in _BUSY]
    worker = _thread_cpu(facts, "worker")
    out["worker_busy_wall_share_pct"] = None if None in busy \
        else _ratio(sum(busy), window, 100.0)
    out["worker_cpu_over_busy_wall"] = None if None in busy \
        else _ratio(worker, sum(busy))
    out["worker_cpu_ms_per_step"] = _ratio(worker, n_steps, 1e3)
    rpc = _thread_cpu(facts, "rpc_loop")
    out["rpc_cpu_share_pct"] = _ratio(rpc, window, 100.0)
    out["rpc_cpu_us_per_token"] = _ratio(rpc, tokens, 1e6)
    out["worker_cpu_share_pct"] = _ratio(worker, window, 100.0)
    out["process_cpu_cores"] = _ratio(
        spans._delta(facts, "process_cpu_seconds_total"), window)
    out["emit_lag_ms"] = emit_lag_ms(facts)
    out["emit_lag_max_since_boot_ms"] = _ratio(
        (facts.get("metrics1") or {}).get("serving_emit_lag_seconds_max"),
        1.0, 1e3)
    out["traces_in_window"] = spans._delta(facts, "jax_traces_total")
    # the ten series that partition the worker thread's time, against the
    # window on the daemon's own clock
    ten = [spans._delta_sum(facts, "step_phase_seconds_total", "phase",
                            spans.PHASES),
           spans._delta_sum(facts, "step_loop_seconds_total", "part",
                            LOOP_PARTS)]
    out["partition_over_window"] = None if None in ten \
        else _ratio(sum(ten), window)
    rates = capture_rates(facts)
    out["capture_slowdown_pct"] = rates["slowdown_pct"] if rates else None
    out["capture"] = rates
    out["window_s"] = window
    return out


def _note(facts):
    """Leave `split` in the run's output, once."""
    if "hosttime_note" not in facts:
        facts["hosttime_note"] = True
        try:
            row = split(facts)
        except Exception as e:  # noqa: BLE001 — a note never fails a run
            row = {"error": repr(e)}
        facts.setdefault("notes", []).append({"hosttime": row})
