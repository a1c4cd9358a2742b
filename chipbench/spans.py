"""Readers of what the program itself writes into a run: its host spans
and scope names in the profiler capture, and its cumulative counters on
/metrics. `layers/<metric>.json` names them as `"spans:<function>"`.

**The capture.** `facts["trace_capture"]` is the directory `POST /profilez`
returned; its `.xplane.pb` is parsed once per run (`capture_of`, kept on
`facts`) into plain lists, so that the arithmetic below can be tested on a
small recorded capture (`tests/recorded_spans.json.gz`):

    {"devices": [{"name": "/device:TPU:0",
                  "ops": [[start_ns, duration_ns, scope], ...]}],
     "spans": [[name, start_ns, duration_ns, {"step": 3}], ...]}

`ops` are the events of a device plane's "XLA Ops" line, as
`tracered.load_xplane` takes them. An operation's `scope` is the innermost
component of its HLO `op_name` (the `tf_op` stat of the event's metadata;
`jax.named_scope` puts it there) that starts with one of `SCOPES`, or None:
the compiler gives an operation it inserts or hoists (a layout copy, a
convert moved out of the layer loop) the `op_name` of what it serves, or
none, and what carries none is unscoped, not guessed at. `jax.profiler.
ProfileData` does not show metadata stats, so the file is read with
`google.protobuf` against the few fields of xplane.proto declared here.

`spans` are the events of the `/host:CPU` plane named `step`, `step.<phase>`,
`admit` or `admit.<part>`: the `jax.profiler.TraceAnnotation`s the batcher's
worker thread writes while a capture records (`dnn_tpu/obs/timeline.py`
`_StepSpans` for the StepClock phases, `ContinuousBatcher.submit` for an
admission and its parts). They are on the device planes' clock. A parent
is the span that encloses a child on the same thread, so only one
thread's line is kept: the one with the most `step` spans, the batcher's
worker (`worker_line`). Such a span written by another thread is left
out, and the idle time under it counts as `outside`. `step` and `rid` are
event stats.

**Idle time by span** (`idle_pct`). The device's idle time is the
complement of the merged "XLA Ops" intervals inside the traced extent
(first operation's start to the last one's end over all device planes),
mean over the planes: `tracered.reduce_trace`'s definition, so the shares
here add up to `*_device_idle_pct`. Each idle interval is divided by
overlap among the innermost spans covering it; a parent keeps what its
children leave; what no span covers is `outside`: the worker between
`step()` calls (queue `get`, cancels, control operations) or the asyncio
side holding the GIL.

**Device time by scope** (`scope_share_pct`). Each operation's own time
(an enclosing loop keeps what its children leave, `tracered._self_times`)
goes to its scope; a share is over the sum, which is the busy time.

**Counters.** `metrics0` / `metrics1` are the /metrics pages at the
window's start and end. The StepClock totals (`step_steps_total`,
`step_tokens_advanced_total`, `step_phase_seconds_total{phase=}`,
`step_admit_seconds_total{part=}`) are exact at a scrape to the last ended
step, so their difference covers the whole window and not the newest 256
steps of `/stepz`'s ring. A reader returns None when a series it needs is
missing (a program that predates the series), and the harness leaves the
metric out.
A program that predates the spans gets no idle share for the same reason;
one that predates a scope name has its operations counted as unscoped.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Optional

from chipbench import tracered

__all__ = ["SCOPES", "load_capture", "worker_line", "capture_of",
           "idle_by_span", "scope_seconds", "idle_pct", "scope_share_pct",
           "pure_host_share_pct", "occupancy_win_pct", "queue_wait_ms",
           "gauge_at_end"]

#: the scope names the program gives its device work (prefixes):
#: attention kernels, the paged KV pool, the layer loop's own slicing of
#: its stacked weights and pool, the sampling tail, the model's own blocks
SCOPES = ("attn.", "kv_pool.", "layers.scan", "sample", "gpt.")
SPAN_ROOTS = ("step", "admit")


# ----------------------------------------------------------------------
# .xplane.pb -> plain lists
# ----------------------------------------------------------------------

@functools.lru_cache(maxsize=1)
def _xspace_class():
    """The XSpace message, from the fields of tsl/profiler/protobuf/
    xplane.proto read here (a map is a repeated key/value entry on the
    wire; fields not declared are skipped by the parser)."""
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory

    T = descriptor_pb2.FieldDescriptorProto
    fd = descriptor_pb2.FileDescriptorProto(
        name="chipbench_xplane.proto", package="chipbench.xplane",
        syntax="proto3")

    def message(name, *fields):
        m = fd.message_type.add(name=name)
        for fname, number, ftype, repeated in fields:
            f = m.field.add(
                name=fname, number=number,
                label=T.LABEL_REPEATED if repeated else T.LABEL_OPTIONAL)
            if isinstance(ftype, str):
                f.type, f.type_name = T.TYPE_MESSAGE, \
                    f".chipbench.xplane.{ftype}"
            else:
                f.type = ftype

    message("XStat", ("metadata_id", 1, T.TYPE_INT64, False),
            ("double_value", 2, T.TYPE_DOUBLE, False),
            ("uint64_value", 3, T.TYPE_UINT64, False),
            ("int64_value", 4, T.TYPE_INT64, False),
            ("str_value", 5, T.TYPE_STRING, False),
            ("ref_value", 7, T.TYPE_UINT64, False))
    message("XEvent", ("metadata_id", 1, T.TYPE_INT64, False),
            ("offset_ps", 2, T.TYPE_INT64, False),
            ("duration_ps", 3, T.TYPE_INT64, False),
            ("stats", 4, "XStat", True))
    message("XLine", ("name", 2, T.TYPE_STRING, False),
            ("timestamp_ns", 3, T.TYPE_INT64, False),
            ("events", 4, "XEvent", True))
    message("XEventMetadata", ("id", 1, T.TYPE_INT64, False),
            ("name", 2, T.TYPE_STRING, False),
            ("stats", 5, "XStat", True))
    message("XStatMetadata", ("id", 1, T.TYPE_INT64, False),
            ("name", 2, T.TYPE_STRING, False))
    message("EventMetadataEntry", ("key", 1, T.TYPE_INT64, False),
            ("value", 2, "XEventMetadata", False))
    message("StatMetadataEntry", ("key", 1, T.TYPE_INT64, False),
            ("value", 2, "XStatMetadata", False))
    message("XPlane", ("name", 2, T.TYPE_STRING, False),
            ("lines", 3, "XLine", True),
            ("event_metadata", 4, "EventMetadataEntry", True),
            ("stat_metadata", 5, "StatMetadataEntry", True))
    message("XSpace", ("planes", 1, "XPlane", True))
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("chipbench.xplane.XSpace"))


def scope_of(op_name: Optional[str]) -> Optional[str]:
    """`jit(decode_step)/while/body/closed_call/gpt.block.attn/attn.paged_
    decode/paged_decode_attention/pallas_call:` -> `attn.paged_decode`."""
    for part in reversed((op_name or "").split("/")):
        if part.startswith(SCOPES):
            return part
    return None


def _stat_values(stats, stat_names) -> dict:
    """{stat name: value}; a `ref_value` names another stat's metadata."""
    out = {}
    for st in stats:
        if st.str_value:
            value = st.str_value
        elif st.ref_value:
            value = stat_names.get(st.ref_value, "")
        else:
            value = st.int64_value or st.uint64_value or st.double_value
        out[stat_names.get(st.metadata_id, "")] = value
    return out


def worker_line(lines: List[list]) -> list:
    """Of the host lines' span lists, the one with the most `step` spans:
    the batcher's worker thread. Nesting by time means something only
    within one thread."""
    return max(lines, key=lambda spans: sum(s[0] == "step" for s in spans),
               default=[])


def load_capture(path: str) -> dict:
    """The plain-lists form of one `.xplane.pb` (module docstring)."""
    space = _xspace_class()()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    devices, lines = [], []
    for plane in space.planes:
        device = bool(tracered.DEVICE_PLANE.match(plane.name))
        if not device and plane.name != tracered.HOST_PLANE:
            continue
        stat_names = {e.key: e.value.name for e in plane.stat_metadata}
        meta = {e.key: e.value for e in plane.event_metadata}
        if device:
            scope = {k: scope_of(_stat_values(m.stats, stat_names)
                                 .get("tf_op")) for k, m in meta.items()}
            ops = [[int(line.timestamp_ns + ev.offset_ps / 1e3),
                    int(ev.duration_ps / 1e3), scope.get(ev.metadata_id)]
                   for line in plane.lines if line.name == tracered.OPS_LINE
                   for ev in line.events]
            if ops:
                devices.append({"name": plane.name, "ops": ops})
            continue
        wanted = {k for k, m in meta.items()
                  if m.name.split(".")[0] in SPAN_ROOTS}
        for line in plane.lines:
            lines.append([[meta[ev.metadata_id].name,
                           int(line.timestamp_ns + ev.offset_ps / 1e3),
                           int(ev.duration_ps / 1e3),
                           _stat_values(ev.stats, stat_names)]
                          for ev in line.events if ev.metadata_id in wanted])
    return {"devices": devices, "spans": worker_line(lines)}


def capture_of(facts) -> Optional[dict]:
    """The run's capture in plain lists, parsed once and kept on `facts`;
    None when the run has no capture or it holds no device operation."""
    if "spans_capture" not in facts:
        cap = None
        root = facts.get("trace_capture")
        path = tracered.find_xplane(root) if root else None
        if path:
            cap = load_capture(path)
            if not cap["devices"]:
                cap = None
        facts["spans_capture"] = cap
    return facts["spans_capture"]


# ----------------------------------------------------------------------
# the arithmetic, on plain lists
# ----------------------------------------------------------------------

def _innermost(spans) -> List[list]:
    """Disjoint [start, end, name] segments: at each moment the innermost
    span open (a parent shows through where no child covers it)."""
    segs, stack, cur = [], [], 0

    def advance(to):
        nonlocal cur
        if stack and to > cur:
            segs.append([cur, to, stack[-1][0]])
        cur = max(cur, to)

    for name, start, dur in sorted((s[:3] for s in spans),
                                   key=lambda s: (s[1], -s[2])):
        while stack and stack[-1][1] <= start:
            advance(stack[-1][1])
            stack.pop()
        advance(start)
        stack.append((name, start + dur))
    while stack:
        advance(stack[-1][1])
        stack.pop()
    return segs


def idle_by_span(capture: dict) -> dict:
    """{"window_s", "idle_s", "by": {span name or "outside": seconds}}:
    the devices' idle time inside the traced extent (mean over the
    planes), divided by overlap among the innermost host spans."""
    per_dev = [tracered._merge([o[0], o[0] + o[1]] for o in d["ops"])
               for d in capture["devices"]]
    t_first = min(m[0][0] for m in per_dev)
    t_last = max(m[-1][1] for m in per_dev)
    segs = _innermost(capture["spans"])
    by: Dict[str, float] = {}
    idle_ns = 0
    for merged in per_dev:
        edges = [t_first] + [t for iv in merged for t in iv] + [t_last]
        i = 0
        for g0, g1 in zip(edges[0::2], edges[1::2]):
            if g1 <= g0:
                continue
            idle_ns += g1 - g0
            covered = 0
            while i < len(segs) and segs[i][1] <= g0:
                i += 1
            j = i
            while j < len(segs) and segs[j][0] < g1:
                over = min(segs[j][1], g1) - max(segs[j][0], g0)
                by[segs[j][2]] = by.get(segs[j][2], 0) + over
                covered += over
                j += 1
            by["outside"] = by.get("outside", 0) + (g1 - g0) - covered
    n = len(per_dev)
    return {"window_s": (t_last - t_first) / 1e9, "idle_s": idle_ns / n / 1e9,
            "by": {k: v / n / 1e9 for k, v in by.items()}}


def scope_seconds(capture: dict) -> Dict[Optional[str], float]:
    """{scope or None: seconds of the operations' own time}, mean over
    the device planes; the values sum to the busy time."""
    out: Dict[Optional[str], float] = {}
    for d in capture["devices"]:
        # tracered's events are [name, start, duration]: the scope rides
        # in the name's place
        ordered, own = tracered._self_times(
            [[o[2], o[0], o[1]] for o in d["ops"]])
        for (scope, _, _), ns in zip(ordered, own):
            out[scope] = out.get(scope, 0.0) + ns
    n = len(capture["devices"])
    return {k: v / n / 1e9 for k, v in out.items()}


# ----------------------------------------------------------------------
# the readers layers/*.json name
# ----------------------------------------------------------------------

def _under(name: str, root: str) -> bool:
    return name == root or name.startswith(root + ".")


def idle_pct(facts, *, under: str) -> Optional[float]:
    """Device idle time under the spans named `under` or `under.<x>`
    (`"outside"`: under no span), as a share of the traced extent."""
    cap = capture_of(facts)
    if cap is None or not cap["spans"]:
        return None
    facts.setdefault("spans_idle", idle_by_span(cap))
    idle = facts["spans_idle"]
    part = sum(v for k, v in idle["by"].items() if _under(k, under))
    return 100.0 * part / idle["window_s"] if idle["window_s"] else None


def scope_share_pct(facts, *, scopes: Optional[list]) -> Optional[float]:
    """Device time of the operations whose scope starts with one of
    `scopes` (None: the operations with no scope), over busy time."""
    cap = capture_of(facts)
    if cap is None:
        return None
    facts.setdefault("spans_scopes", scope_seconds(cap))
    secs = facts["spans_scopes"]
    total = sum(secs.values())
    if not total:
        return None
    if scopes is None:
        part = secs.get(None, 0.0)
    else:
        part = sum(v for k, v in secs.items()
                   if k is not None and k.startswith(tuple(scopes)))
    return 100.0 * part / total


def _delta(facts, series: str) -> Optional[float]:
    m0, m1 = facts.get("metrics0") or {}, facts.get("metrics1") or {}
    if series not in m0 or series not in m1:
        return None
    return m1[series] - m0[series]


def _delta_sum(facts, family: str, label: str, values) -> Optional[float]:
    parts = [_delta(facts, f'{family}{{{label}="{v}"}}') for v in values]
    return None if any(p is None for p in parts) else sum(parts)


PHASES = ("admit", "host", "dispatch", "wait", "commit", "obs")


def pure_host_share_pct(facts) -> Optional[float]:
    """Over the window: seconds the worker spent in host work the device
    does not overlap — admission's own (`self`) and its eager installs,
    and the `host`, `commit` and `obs` phases of a step — over the seconds
    of all six StepClock phases. The prefill an admission dispatches and
    waits for is left out: it is the device's time, not the host's."""
    host = _delta_sum(facts, "step_phase_seconds_total", "phase",
                      ("host", "commit", "obs"))
    admit = _delta_sum(facts, "step_admit_seconds_total", "part",
                       ("self", "install"))
    total = _delta_sum(facts, "step_phase_seconds_total", "phase", PHASES)
    if host is None or admit is None or not total:
        return None
    return 100.0 * (host + admit) / total


def occupancy_win_pct(facts) -> Optional[float]:
    """Tokens advanced per step over the slots, over the whole window."""
    tokens = _delta(facts, "step_tokens_advanced_total")
    steps = _delta(facts, "step_steps_total")
    if tokens is None or not steps:
        return None
    slots = facts["config"]["run"]["serve_flags"]["slots"]
    return 100.0 * tokens / (steps * slots)


def queue_wait_ms(facts) -> Optional[float]:
    """Mean wait of the requests admitted in the window, from enqueue to
    the `submit()` that admits them (the daemon's own clock)."""
    total = _delta(facts, "serving_queue_wait_seconds_sum")
    count = _delta(facts, "serving_queue_wait_seconds_count")
    if total is None or not count:
        return None
    return 1e3 * total / count


def gauge_at_end(facts, *, series: str) -> Optional[float]:
    """A gauge as the window's last scrape shows it."""
    return (facts.get("metrics1") or {}).get(series)
