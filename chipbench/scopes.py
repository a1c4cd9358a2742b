"""Readers for a model family that brings its own scope names and
counters: device time by scope with the recognised prefixes taken from the
CONFIGURATION's file (`"trace": {"known_scopes": [...]}`: the scopes its
programs write, so the next family brings data files only and a share
that several families report is one entry), ratios of window differences
of /metrics counters, and a scoped kernel's roofline share.
`layers/<metric>.json` names them as `"scopes:<function>"`.

`spans.py` reduces every operation's HLO `op_name` to one of ITS prefixes
(`spans.SCOPES`, GPT-2's) while it reads the capture, so a `llama.*` or
`moe.*` scope is gone by the time a reader sees the operation. Here the
capture is read again, with the same few fields of xplane.proto
(`spans._xspace_class`), and each operation keeps its whole `op_name`:

    {"devices": [{"name": "/device:TPU:0",
                  "ops": [[start_ns, duration_ns, op_name or None], ...]}]}

which is also the shape of `tests/recorded_spans.json.gz` (whose third
field is the name already cut to a scope: cutting is idempotent).

**Scope of an operation** (`scope_of`): the innermost `/`-separated
component of its `op_name` that starts with one of `known`, the prefixes
ALL the scope metrics of a cell recognise together, which is its
configuration's `trace.known_scopes`; None if there is none. A metric's
share is the own time (`tracered._self_times`) of the operations whose
scope starts with one of ITS `scopes` over the busy time; `scopes: null` is
the operations with no scope. Give each prefix of a configuration's
`known_scopes` to exactly one of the metrics its cells report: the shares
then add up to 100. A configuration without the block has no scope shares
(the GPT-2 cells are read by `spans.py`'s built-in `SCOPES`).

**A kernel's roofline share** (`experts_roofline_pct`): least time of the
expert layers of one decode step over the device time of the operations
under the scope inside the program, per execution of it. The operations
and bytes are computed here, from the configuration's widths and the
program's own counters over the window:

    FLOPs = rows through experts x 3 matmuls x 2 x hidden x expert width
    bytes = experts with at least one row x 3 x hidden x expert width x w
            + rows x hidden x w, in and out
    least = max(bytes / peak bytes/s, FLOPs / peak FLOP/s)

with w the operand width of the configuration's compute dtype (2 B for
bfloat16): what the best program must move, not what today's moves — a
program that holds float32 weights and converts them every step moves
three times that and reads a low share; one that stops converting rises
toward 100 and cannot pass it.

A reader returns None where what it reads is not there (no capture, a
program that predates the counters), and the harness leaves the metric out.
"""

from __future__ import annotations

from typing import Dict, Optional

from chipbench import spans, tracered

__all__ = ["scope_of", "load_ops", "known_scopes", "scope_seconds",
           "share_pct", "counter_ratio", "experts_least_s",
           "experts_roofline_pct"]

OPERAND_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def scope_of(op_name: Optional[str], known) -> Optional[str]:
    """`jit(decode_step)/layers.scan/while/body/llama.block.mlp/moe.experts/
    ragged_dot` -> `moe.experts`, for known = (..., "moe.experts", ...)."""
    known = tuple(known)
    for part in reversed((op_name or "").split("/")):
        if part.startswith(known):
            return part
    return None


def load_ops(path: str) -> dict:
    """The device operations of one `.xplane.pb`, each with its `op_name`
    (the `tf_op` stat of the event's metadata)."""
    space = spans._xspace_class()()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    devices = []
    for plane in space.planes:
        if not tracered.DEVICE_PLANE.match(plane.name):
            continue
        stat_names = {e.key: e.value.name for e in plane.stat_metadata}
        names = {e.key: spans._stat_values(e.value.stats, stat_names)
                 .get("tf_op") or None for e in plane.event_metadata}
        ops = [[int(line.timestamp_ns + ev.offset_ps / 1e3),
                int(ev.duration_ps / 1e3), names.get(ev.metadata_id)]
               for line in plane.lines if line.name == tracered.OPS_LINE
               for ev in line.events]
        if ops:
            devices.append({"name": plane.name, "ops": ops})
    return {"devices": devices}


def _capture_of(facts) -> Optional[dict]:
    """The run's capture with whole op_names, parsed once and kept on
    `facts`; None when the run has no capture or no device operation."""
    if "scopes_capture" not in facts:
        root = facts.get("trace_capture")
        path = tracered.find_xplane(root) if root else None
        cap = load_ops(path) if path else None
        facts["scopes_capture"] = cap if cap and cap["devices"] else None
    return facts["scopes_capture"]


def known_scopes(facts) -> Optional[list]:
    """The prefixes the cell's configuration declares (`trace.known_scopes`
    of its file); None where it declares none."""
    return facts["config"].get("trace", {}).get("known_scopes")


def scope_seconds(capture: dict, known, *,
                  inside: Optional[str] = None) -> Dict[Optional[str], float]:
    """{scope or None: seconds of the operations' own time}, mean over the
    device planes. `inside`: only the operations whose op_name starts with
    it (`"jit(decode_step)/"`: one program's)."""
    out: Dict[Optional[str], float] = {}
    for d in capture["devices"]:
        # tracered's events are [name, start, duration]
        ordered, own = tracered._self_times(
            [[o[2], o[0], o[1]] for o in d["ops"]])
        for (name, _, _), ns in zip(ordered, own):
            if inside is not None and not (name or "").startswith(inside):
                continue
            scope = scope_of(name, known)
            out[scope] = out.get(scope, 0.0) + ns
    n = len(capture["devices"])
    return {k: v / n / 1e9 for k, v in out.items()}


def share_pct(facts, *, scopes: Optional[list]) -> Optional[float]:
    """Device time of the operations whose scope (among the
    configuration's `known_scopes`) starts with one of `scopes` (None: the
    operations with no scope), over busy time."""
    cap, known = _capture_of(facts), known_scopes(facts)
    if cap is None or not known:
        return None
    cache = facts.setdefault("scopes_seconds", {})
    key = tuple(known)
    if key not in cache:
        cache[key] = scope_seconds(cap, known)
    secs = cache[key]
    total = sum(secs.values())
    if not total:
        return None
    if scopes is None:
        part = secs.get(None, 0.0)
    else:
        part = sum(v for k, v in secs.items()
                   if k is not None and k.startswith(tuple(scopes)))
    return 100.0 * part / total


def _deltas(facts, series: list) -> Optional[list]:
    parts = [spans._delta(facts, s) for s in series]
    return None if any(p is None for p in parts) else parts


def counter_ratio(facts, *, num: str, den: str,
                  times_config: Optional[str] = None) -> Optional[float]:
    """Window difference of series `num` over that of `den` (optionally
    times the configuration's `times_config` key)."""
    d = _deltas(facts, [num, den])
    if d is None or not d[1]:
        return None
    scale = facts["config"][times_config] if times_config else 1.0
    return scale * d[0] / d[1]


def experts_least_s(config: dict, *, rows: float, active_experts: float,
                    peaks: dict) -> dict:
    """The least time the expert layers can take for `rows` (token,
    expert) rows spread over `active_experts` experts (sums over the
    layer calls in question), and which peak binds. See the module
    docstring for the operations and bytes."""
    d, f = config["hidden_size"], config["intermediate_size"]
    w = OPERAND_BYTES[config["run"]["dtype"]]
    flops = rows * 3 * 2 * d * f
    nbytes = active_experts * 3 * d * f * w + 2 * rows * d * w
    t_flops = flops / peaks["bf16_flops_per_s"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return {"least_s": max(t_flops, t_bytes), "flops": flops,
            "bytes": nbytes,
            "bound": "bandwidth" if t_bytes >= t_flops else "compute"}


def experts_roofline_pct(facts, *, program: str, inside: str, scope: str,
                         label: str) -> Optional[float]:
    """Least time of the expert layers of one execution of `program` (from
    the window's `moe_*{program=label}` counters) over the device time of
    the operations under `scope` inside it (op_name prefix `inside`), per
    execution of it in the capture."""
    cap, t, peaks = _capture_of(facts), facts.get("trace"), facts.get("peaks")
    known = known_scopes(facts)
    if cap is None or not known or not t or not peaks \
            or program not in t["programs"]:
        return None
    d = _deltas(facts, [f'moe_{name}{{program="{label}"}}' for name in
                       ("layer_calls_total", "assignments_total",
                        "active_experts_total")])
    if d is None or not d[0]:
        return None
    steps = d[0] / facts["config"]["num_hidden_layers"]
    least = experts_least_s(facts["config"], rows=d[1] / steps,
                            active_experts=d[2] / steps, peaks=peaks)
    secs = scope_seconds(cap, known, inside=inside)
    spent = sum(v for k, v in secs.items()
                if k is not None and k.startswith(scope))
    if not spent:
        return None
    per_step = spent / t["programs"][program]["count"]
    return 100.0 * least["least_s"] / per_step
