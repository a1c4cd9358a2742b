"""Readers of the event-loop thread's time (PR 52): what a streamed token
costs after the worker hands it off, and who holds the interpreter when the
worker's launch returns late. `layers/<metric>.json` names them as
`"rpctime:<function>"`.

**Counters** (window differences of `/metrics`, as `spans._delta`): the
daemon's event loop runs over a selector that stamps `perf_counter` on each
side of `select()`, and the thread adds its own wall time to four series,
`serving_rpc_loop_seconds_total{part=}`: `select` (blocked: nothing to do),
`fan_out` (a hand-off's tokens put on their streams' queues), `token` (in
the stream handler, from the dequeue of a token to the yield of its
message) and `rest` (everything else between two `select()`s: asyncio's
wake-ups and timers, gRPC's serialization and write). Over a window the four
sum to the window (`parts_over_window` in the note row). With them
`serving_rpc_loop_iterations_total` and `serving_fan_out_lag_seconds_{sum,
count}` (from the worker's stamp at a hand-off to `_fan_out`'s entry: the
loop's wake-up); `serving_emit_tokens_total` and `step_steps_total` are
what they are divided by.

**Spans**, HOST plane only. While a capture records the same thread writes
`rpc.run` (a `select()`'s return to the next one's call) with `rpc.fan_out`
and an `rpc.tokens` marker nested in it, on its own line of the `/host:CPU`
plane; the worker's `step*` / `admit*` / `loop*` spans are on the worker's
line of the same plane, and so are the runtime's own events (below): ONE
clock, where the device planes' runs 0.1-2.6 ms ahead by another amount
each capture (`hosttime.clock_lead_ms`). `host_overlap_rpc_ms_per_step` is
the time a step during which the worker is inside one of its spans other
than the three in which it waits (`step.wait`, `admit.first_token`,
`loop.wait`) AND the event-loop thread is inside `rpc.run`: two threads of
one interpreter cannot both run Python, so this bounds from above what the
interpreter lock costs the worker a step, and a reading near 0 says the
lock costs it nothing. `dispatch_tail` splits `step.dispatch` at the return
of the runtime's enqueue inside it: before it the launch proper, after it
the jit call's way back into Python, and how much of that lies under
`rpc.run`.

**The device plane's lead**, from the runtime's events instead of the
Python spans (`device_lead_ms`): a program that starts after an idle gap
cannot start before the runtime handed it to the device. That bounds the
lead from below, 0.1-0.3 ms closer than `hosttime.clock_lead_ms`'s `lo`
(the dispatch span's begin); from above nothing does under the one-step
pipeline — a step's token read waits for the step before, which is why
`clock_lead_ms`'s `hi`, taken from `step.wait`, has read below its `lo`
since PR 45. Printed in the note row beside those bounds; no entry of
`BENCHMARK.json` reads across the two planes.

Every reader returns None where a series or span it needs is missing (a
program that predates it) and never raises; the first one called in a run
leaves the whole split as one `note` row (`rpctime`).
"""

from __future__ import annotations

import bisect
from typing import Dict, List, Optional

from chipbench import hosttime, spans, tracered

__all__ = ["PARTS", "WAITING", "load_lines", "capture_of", "loop_busy_pct",
           "us_per_token", "token_build_us", "fan_out_lag_ms",
           "host_overlap_rpc_ms_per_step", "overlap", "dispatch_tail",
           "device_lead_ms", "split"]

PARTS = ("select", "fan_out", "token", "rest")
_SERIES = "serving_rpc_loop_seconds_total"
#: the worker's spans in which it waits (for the device, for an arrival):
#: inside any other of its spans it works
WAITING = ("step.wait", "admit.first_token", "loop.wait")
SPAN_ROOTS = hosttime.ROOTS + ("rpc",)
#: the runtime's own events of a launch, on a host line of their own (the
#: plugin's): `PJRT_LoadedExecutable_Execute`, the enqueue, which returns
#: 0.2-0.6 ms into a `step.dispatch`, and inside it `tpu::System::Execute`,
#: the moment the program is handed to the device
ENQUEUE = "PJRT_LoadedExecutable_Execute"
ISSUE = "tpu::System::Execute"


# ----------------------------------------------------------------------
# whole-window counters
# ----------------------------------------------------------------------

def _parts(facts) -> Optional[Dict[str, float]]:
    out = {p: spans._delta(facts, f'{_SERIES}{{part="{p}"}}') for p in PARTS}
    return None if None in out.values() else out


def _working_s(facts) -> Optional[float]:
    parts = _parts(facts)
    return None if parts is None else sum(parts.values()) - parts["select"]


def _tokens(facts) -> Optional[float]:
    return spans._delta(facts, "serving_emit_tokens_total")


def loop_busy_pct(facts) -> Optional[float]:
    """The event-loop thread's busy share of its wall time over the window:
    100 x (1 - `select` / all four parts). One thread ends at 100."""
    _note(facts)
    parts = _parts(facts)
    if parts is None:
        return None
    total = sum(parts.values())
    return hosttime._ratio(total - parts["select"], total, 100.0)


def us_per_token(facts) -> Optional[float]:
    """Wall microseconds of the event-loop thread outside `select()` a
    token handed off to it, over the window."""
    _note(facts)
    return hosttime._ratio(_working_s(facts), _tokens(facts), 1e6)


def token_build_us(facts) -> Optional[float]:
    """The `token` part alone a token: the message's own cost."""
    _note(facts)
    return hosttime._ratio(
        spans._delta(facts, f'{_SERIES}{{part="token"}}'), _tokens(facts),
        1e6)


def fan_out_lag_ms(facts) -> Optional[float]:
    """Mean time from the worker's stamp at a hand-off to `_fan_out`'s
    entry on the event-loop thread: the loop's wake-up."""
    _note(facts)
    return hosttime._ratio(
        spans._delta(facts, "serving_fan_out_lag_seconds_sum"),
        spans._delta(facts, "serving_fan_out_lag_seconds_count"), 1e3)


# ----------------------------------------------------------------------
# the host plane: two threads' lines and the runtime's events
# ----------------------------------------------------------------------

def load_lines(path: str) -> dict:
    """{"worker", "rpc", "runtime"} of one `.xplane.pb`, each a list of
    `[name, start_ns, duration_ns, stats]` from the host plane: the line
    with the most `step` spans (the program's `step*` / `admit*` / `loop*`
    spans), the line with the most `rpc.run` spans (`rpc*`), and the
    ENQUEUE and ISSUE events of every line (the worker alone launches)."""
    space = spans._xspace_class()()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    lines, runtime = [], []
    for plane in space.planes:
        if plane.name != tracered.HOST_PLANE:
            continue
        stat_names = {e.key: e.value.name for e in plane.stat_metadata}
        names = {e.key: e.value.name for e in plane.event_metadata}
        ours = {k for k, n in names.items()
                if n.split(".")[0] in SPAN_ROOTS}
        theirs = {k for k, n in names.items() if n in (ENQUEUE, ISSUE)}
        for line in plane.lines:
            events = [[names[ev.metadata_id],
                       int(line.timestamp_ns + ev.offset_ps / 1e3),
                       int(ev.duration_ps / 1e3),
                       spans._stat_values(ev.stats, stat_names)]
                      for ev in line.events
                      if ev.metadata_id in ours or ev.metadata_id in theirs]
            lines.append([e for e in events if e[0] not in (ENQUEUE, ISSUE)])
            runtime += [e for e in events if e[0] in (ENQUEUE, ISSUE)]

    def most(name):
        return max(lines, key=lambda ln: sum(s[0] == name for s in ln),
                   default=[])

    return {"worker": most("step"), "rpc": most("rpc.run"),
            "runtime": sorted(runtime, key=lambda e: e[1])}


def capture_of(facts) -> Optional[dict]:
    """`load_lines` of the run's capture with `spans.capture_of`'s device
    operations as `devices`, parsed once and kept on `facts`; None without
    a capture, or without the `rpc.run` and `step` spans in it."""
    if "rpctime_capture" not in facts:
        cap = None
        root = facts.get("trace_capture")
        path = tracered.find_xplane(root) if root else None
        if path:
            cap = load_lines(path)
            base = spans.capture_of(facts)
            cap["devices"] = base["devices"] if base else []
        facts["rpctime_capture"] = cap
    cap = facts["rpctime_capture"]
    if cap is None or not any(s[0] == "rpc.run" for s in cap["rpc"]) \
            or not any(s[0] == "step" for s in cap["worker"]):
        return None
    return cap


def _named(events, name) -> List[list]:
    """Sorted [start, end] of the events called `name`."""
    return sorted([s[1], s[1] + s[2]] for s in events if s[0] == name)


def _cover(merged, a, b) -> int:
    """Length of [a, b) covered by the sorted disjoint intervals `merged`."""
    total = 0
    i = max(bisect.bisect_right(merged, [a]) - 1, 0)
    while i < len(merged) and merged[i][0] < b:
        total += max(0, min(merged[i][1], b) - max(merged[i][0], a))
        i += 1
    return total


def overlap(capture: dict) -> Optional[dict]:
    """{"steps", "ms_per_step", "by": {worker span: ms a step}}: the time
    the worker is inside one of its spans other than WAITING while the
    event-loop thread is inside `rpc.run`, by the worker's innermost span,
    over the stretch of the host plane that both threads' spans cover."""
    runs = tracered._merge(_named(capture["rpc"], "rpc.run"))
    prog = [s for s in capture["worker"]
            if s[0].split(".")[0] in hosttime.ROOTS]
    if not runs or not prog:
        return None
    t0 = max(runs[0][0], min(s[1] for s in prog))
    t1 = min(runs[-1][1], max(s[1] + s[2] for s in prog))
    n = sum(1 for s in prog if s[0] == "step" and t0 <= s[1] < t1)
    if not n:
        return None
    by: Dict[str, float] = {}
    for a, b, name in spans._innermost(prog):
        a, b = max(a, t0), min(b, t1)
        if b > a and name not in WAITING:
            by[name] = by.get(name, 0.0) + _cover(runs, a, b)
    per = 1e-6 / n
    return {"steps": n, "ms_per_step": sum(by.values()) * per,
            "by": {k: v * per for k, v in sorted(by.items()) if v}}


def host_overlap_rpc_ms_per_step(facts) -> Optional[float]:
    """`overlap`'s `ms_per_step`: an upper bound on what the interpreter
    lock costs the worker a step. The host plane alone."""
    _note(facts)
    cap = capture_of(facts)
    over = overlap(cap) if cap else None
    return over["ms_per_step"] if over else None


def dispatch_tail(capture: dict) -> Optional[dict]:
    """`step.dispatch` split at the return of the runtime's enqueue inside
    it, in ms a step with one: `launch` before it (argument handling, the
    enqueue itself), `tail` after it (the jit call's way back into Python:
    wrapping the results, and waiting for the interpreter where another
    thread has it), and `tail_under_rpc`, the part of the tail during
    which the event-loop thread is inside `rpc.run`. None where the
    runtime's enqueue events are not in the capture."""
    runs = tracered._merge(_named(capture["rpc"], "rpc.run"))
    enqueues = _named(capture.get("runtime", ()), ENQUEUE)
    starts = [e[0] for e in enqueues]
    launch = tail = under = n = 0
    for d0, d1 in _named(capture["worker"], "step.dispatch"):
        i = bisect.bisect_left(starts, d0)
        if i == len(enqueues) or not enqueues[i][1] <= d1:
            continue  # no enqueue that began and ended inside it
        end = enqueues[i][1]
        n += 1
        launch += end - d0
        tail += d1 - end
        under += _cover(runs, end, d1)
    if not n:
        return None
    per = 1e-6 / n
    return {"steps": n, "launch": launch * per, "tail": tail * per,
            "tail_under_rpc": under * per}


def device_lead_ms(capture: dict, near_ns: int = 3_000_000) -> Optional[dict]:
    """A bound from below, in `hosttime.clock_lead_ms`'s sense (what to ADD
    to the device plane's timestamps), on how far the device plane's clock
    runs ahead of the host plane's, from the runtime's events instead of
    the Python spans: a stretch of device work cannot begin before the
    runtime handed its program to the device. `lo` is ISSUE's begin less
    the stretch's apparent begin, over the decode steps' launches (the
    ISSUE inside a `step.dispatch`) with ONE stretch beginning within
    `near_ns` of them; the 90th percentile, as `clock_lead_ms` takes it (a
    launch queued behind an admission's programs pairs with the wrong
    stretch).

    No bound from ABOVE is given. A program cannot end after the read that
    waited for it returned, but under the one-step pipeline a step's token
    read waits for the step BEFORE the one in flight, and pairing the one
    read that does wait for the device's last program (an admission's
    first token) with that program's end needs the lead itself: two
    pairings tried on the chip read widths from 0.002 ms (the pick slides
    onto the next launch's operations) to 59 ms (PERF.md section 7)."""
    devices = capture.get("devices")
    issues = [i[0] for i in _named(capture.get("runtime", ()), ISSUE)]
    if not devices or not issues:
        return None
    begins = [b[0] for b in hosttime._coarse_busy(devices[0]["ops"])]
    lo = []
    for d0, d1 in _named(capture["worker"], "step.dispatch"):
        k = bisect.bisect_left(issues, d0)
        if k == len(issues) or issues[k] >= d1:
            continue
        i = bisect.bisect_left(begins, issues[k] - near_ns)
        if bisect.bisect_right(begins, issues[k] + near_ns) - i == 1:
            lo.append(issues[k] - begins[i])
    if len(lo) < 8:
        return None
    return {"lo": sorted(lo)[int(0.9 * (len(lo) - 1))] / 1e6,
            "launches": len(lo)}


# ----------------------------------------------------------------------
# the note row
# ----------------------------------------------------------------------

def split(facts) -> dict:
    """The whole reading of one run: the four parts in ms a step and us a
    token, loop iterations a step, the overlap by worker span, the tail of
    `step.dispatch`, the bound on the device plane's lead. Missing pieces
    read None."""
    out: Dict[str, object] = {}
    parts = _parts(facts)
    steps = spans._delta(facts, "step_steps_total")
    tokens = _tokens(facts)
    window = spans._delta(facts, "process_perf_counter_seconds")
    for p in PARTS:
        v = parts[p] if parts else None
        out[f"{p}_ms_per_step"] = hosttime._ratio(v, steps, 1e3)
        out[f"{p}_us_per_token"] = hosttime._ratio(v, tokens, 1e6)
    out["parts_over_window"] = hosttime._ratio(
        sum(parts.values()) if parts else None, window)
    out["iterations_per_step"] = hosttime._ratio(
        spans._delta(facts, "serving_rpc_loop_iterations_total"), steps)
    out["loop_busy_pct"] = loop_busy_pct(facts)
    out["us_per_token"] = us_per_token(facts)
    out["fan_out_lag_ms"] = fan_out_lag_ms(facts)
    out["tokens_per_s"] = hosttime._ratio(tokens, window)
    out["window_s"] = window
    cap = capture_of(facts)
    out["overlap"] = overlap(cap) if cap else None
    out["dispatch_tail"] = dispatch_tail(cap) if cap else None
    lead = device_lead_ms(cap) if cap else None
    out["device_lead_ms"] = lead
    base = hosttime.capture_of(facts) if cap else None
    out["device_lead_ms_from_spans"] = hosttime.clock_lead_ms(base) \
        if base else None
    return out


def _note(facts):
    """Leave `split` in the run's output, once."""
    if "rpctime_note" not in facts:
        facts["rpctime_note"] = True
        try:
            row = split(facts)
        except Exception as e:  # noqa: BLE001 — a note never fails a run
            row = {"error": repr(e)}
        facts.setdefault("notes", []).append({"rpctime": row})
