"""From a profiler trace (`.xplane.pb`) to numbers: device busy and idle
time, time per compiled program, time per operation, and the longest idle
gaps with what the host was doing in them.

Two steps, so that the arithmetic can be tested on a small recorded trace
without the profiler: `load_xplane` turns the protobuf into plain lists
(`planes -> lines -> [name, start_ns, duration_ns]`), and `reduce_trace`
works on those lists alone.

What the reducer takes from the program is names only: a compiled program
appears on a device plane's "XLA Modules" line as `<jit name>(<id>)`, its
operations on the "XLA Ops" line.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Dict, List, Optional

__all__ = ["find_xplane", "load_xplane", "reduce_trace", "union_length"]

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_MIN_NS = 20_000  # host events shorter than this explain no gap
TOP = 10


def find_xplane(root: str) -> Optional[str]:
    paths = sorted(glob.glob(os.path.join(root, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    return paths[-1] if paths else None


def load_xplane(path: str) -> dict:
    """{"planes": [{"name", "lines": [{"name", "events": [[name, start_ns,
    duration_ns], ...]}]}]} for the device planes (every event) and the
    host plane (events of at least HOST_MIN_NS)."""
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(path).planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        if not device and plane.name != HOST_PLANE:
            continue
        lines = []
        for line in plane.lines:
            events = [[op_name(e.name) if device else e.name,
                       int(e.start_ns), int(e.duration_ns)]
                      for e in line.events
                      if device or e.duration_ns >= HOST_MIN_NS]
            if events:
                lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def _merge(intervals):
    """Sorted, disjoint [start, end) intervals covering the same points."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def union_length(intervals) -> int:
    """Total length covered by possibly overlapping [start, end) pairs."""
    return sum(e - s for s, e in _merge(intervals))


def op_name(text: str) -> str:
    """`%copy.53 = bf16[1,1025,20,16,64]{4,2,3,1,0:T(8,128)} copy(...)` ->
    `copy.53 bf16[1,1025,20,16,64]`: an operation's own name and result
    shape, without its layout and operands (an operand may be named after
    another operation; matching on the whole text would count it twice)."""
    name, sep, rest = text.partition(" = ")
    if not sep:
        return text
    shape = re.match(r"\(?[a-z0-9]+\[[^\]]*\]", rest)
    return name.lstrip("%") + (" " + shape.group(0) if shape else "")


def program_name(module_event_name: str) -> str:
    """`jit_decode_step(1234)` -> `jit_decode_step`."""
    return re.sub(r"\(\d+\)$", "", module_event_name).strip()


def _label(text: str, n: int = 64) -> str:
    return re.sub(r"[^A-Za-z0-9_.\-:>|\[\],()]+", "_", text)[:n]


def _lines(plane, name):
    return [l for l in plane["lines"] if l["name"] == name]


def _module_at(modules, t):
    """Name of the program running at time t (modules sorted by start)."""
    lo, hi = 0, len(modules)
    while lo < hi:
        mid = (lo + hi) // 2
        if modules[mid][1] <= t:
            lo = mid + 1
        else:
            hi = mid
    if lo and modules[lo - 1][1] + modules[lo - 1][2] > t:
        return program_name(modules[lo - 1][0])
    return None


def _host_activity(host_events, host_starts, start, end, scan=4000):
    """The innermost host event covering most of [start, end): among the
    events overlapping at least half the gap, the shortest. Only the
    `scan` events that started last before the gap's end are looked at;
    what started earlier and still runs is an outer loop, not an answer."""
    hi = bisect.bisect_left(host_starts, end)
    best = None
    for name, s, d in host_events[max(0, hi - scan):hi]:
        overlap = min(s + d, end) - max(s, start)
        if overlap * 2 >= end - start and (best is None or d < best[1]):
            best = (name, d)
    return best[0] if best else "no_host_event"


def _self_times(ops):
    """(ops sorted by start, each one's own time): an operation that
    encloses others (a loop, a call) keeps only what its children leave."""
    ordered = sorted(ops, key=lambda e: (e[1], -e[2]))
    own = [e[2] for e in ordered]
    stack = []  # indices of the operations enclosing the current one
    for i, (_, start, dur) in enumerate(ordered):
        # an operation is a child only of one that encloses it whole; two
        # that merely overlap (an asynchronous copy beside a fusion) are not
        while stack and start + dur > (ordered[stack[-1]][1]
                                       + ordered[stack[-1]][2]):
            stack.pop()
        if stack:
            own[stack[-1]] -= dur
        stack.append(i)
    return ordered, [max(0, o) for o in own]


def reduce_trace(trace: dict) -> Optional[dict]:
    """The summary every trace metric reads, or None for a trace with no
    operation on a device plane.

      window_s   first device operation's start to the last one's end,
                 over all device planes
      busy_s     union of the operation intervals, mean over the planes
      programs   {name: {"count", "total_s", "mean_ms"}} — counts and
                 totals are means over the planes
      op_s       {operation name: seconds}, mean over the planes
      device_ops the operations with the most time of their own (what an
                 enclosing loop or call spends in its children is theirs),
                 as `<program>|<operation>`
      idle_gaps  the idle time between operations, summed by
                 `<program before>-><program after>|<host activity>`
    """
    devices = [p for p in trace["planes"] if DEVICE_PLANE.match(p["name"])]
    per_dev = []
    for plane in devices:
        ops = [e for l in _lines(plane, OPS_LINE) for e in l["events"]]
        if ops:
            mods = sorted((e for l in _lines(plane, MODULES_LINE)
                           for e in l["events"]), key=lambda e: e[1])
            per_dev.append((plane["name"], ops, mods))
    if not per_dev:
        return None
    n = len(per_dev)
    t_first = min(e[1] for _, ops, _ in per_dev for e in ops)
    t_last = max(e[1] + e[2] for _, ops, _ in per_dev for e in ops)
    host = sorted((e for p in trace["planes"] if p["name"] == HOST_PLANE
                   for l in p["lines"] for e in l["events"]),
                  key=lambda e: e[1])
    host_starts = [e[1] for e in host]

    busy_ns = 0
    programs: Dict[str, List[float]] = {}
    op_ns: Dict[str, float] = {}
    op_by_program: Dict[str, float] = {}
    gaps: Dict[str, float] = {}
    for _, ops, mods in per_dev:
        merged = _merge([e[1], e[1] + e[2]] for e in ops)
        busy_ns += sum(e - s for s, e in merged)
        for name, _, dur in mods:
            rec = programs.setdefault(program_name(name), [0, 0.0])
            rec[0] += 1
            rec[1] += dur
        for (name, start, dur), own in zip(*_self_times(ops)):
            op_ns[name] = op_ns.get(name, 0.0) + dur
            key = f"{_module_at(mods, start) or 'no_program'}|{name}"
            op_by_program[key] = op_by_program.get(key, 0.0) + own
        # the longest idle gaps of this device, by neighbours and host
        idle = sorted(((b[0] - a[1], a[1], b[0])
                       for a, b in zip(merged, merged[1:])), reverse=True)
        for length, g0, g1 in idle[:200]:
            before = _module_at(mods, g0 - 1) or "idle"
            after = _module_at(mods, g1) or "idle"
            key = f"{before}->{after}|{_host_activity(host, host_starts, g0, g1)}"
            gaps[key] = gaps.get(key, 0.0) + length

    def top(d):
        return [[_label(k), v / n / 1e9] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:TOP]]

    return {
        "n_devices": n,
        "window_s": (t_last - t_first) / 1e9,
        "busy_s": busy_ns / n / 1e9,
        "programs": {k: {"count": c / n, "total_s": t / n / 1e9,
                         "mean_ms": t / c / 1e6}
                     for k, (c, t) in programs.items()},
        "op_s": {k: v / n / 1e9 for k, v in op_ns.items()},
        "device_ops": top(op_by_program),
        "idle_gaps": top(gaps),
    }
