"""Readers of per-layer metrics. Each `layers/<metric>.json` names one of
these functions (`"reducer": "<function>"`, or `"<module>:<function>"` for
a reader a later PR keeps in a module of its own under chipbench/) and its
arguments. A reader gets the run's `facts` and returns a number, or None
when what it reads is not there — the harness then leaves the metric out.

`facts` holds what the harness gathered:
  trace     the summary of `tracered.reduce_trace`, or None
  metrics0 / metrics1   /metrics series at the window's start and end
  client    statistics from the client's timestamps
  config / traffic      the cell's files
  peaks     the table row of the attached device kind, or None
  memory_peak_bytes     peak bytes in use on the fullest chip, or None
  notes     rows a reader leaves for the run's output beside its number
            (which resource bounds a roofline), printed before the result
"""

from __future__ import annotations

from typing import Optional

from chipbench import peaks as pk


def client_stat(facts, *, stat: str) -> Optional[float]:
    return facts["client"].get(stat)


def metrics_ratio_pct(facts, *, num: str, den: list) -> Optional[float]:
    """100 * series `num` over the sum of series `den`, at window end."""
    m = facts.get("metrics1") or {}
    if num not in m or any(d not in m for d in den):
        return None
    total = sum(m[d] for d in den)
    return 100.0 * m[num] / total if total else None


def trace_program_mean_ms(facts, *, program: str) -> Optional[float]:
    t = facts.get("trace")
    p = t and t["programs"].get(program)
    return p["mean_ms"] if p else None


def trace_programs_ms_per(facts, *, programs: list, per: str) -> Optional[float]:
    """Device time of `programs` together, per execution of `per`."""
    t = facts.get("trace")
    if not t or per not in t["programs"]:
        return None
    total = sum(t["programs"][p]["total_s"] for p in programs
                if p in t["programs"])
    return 1e3 * total / t["programs"][per]["count"]


def trace_ops_ms_per(facts, *, contains: str, per: str) -> Optional[float]:
    """Device time of the operations whose name contains `contains`,
    per execution of program `per`."""
    t = facts.get("trace")
    if not t or per not in t["programs"]:
        return None
    total = sum(s for name, s in t["op_s"].items() if contains in name)
    return 1e3 * total / t["programs"][per]["count"]


def trace_idle_pct(facts) -> Optional[float]:
    t = facts.get("trace")
    if not t or not t["window_s"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])


def memory_peak_gb(facts) -> Optional[float]:
    b = facts.get("memory_peak_bytes")
    return b / 1e9 if b else None


def decode_step_roofline_pct(facts, *, program: str) -> Optional[float]:
    """Least time for one decode step (weights once plus every live cache
    position once over peak bytes/s, or its FLOPs over peak FLOP/s,
    whichever is larger) over the step's mean device time."""
    t, peaks = facts.get("trace"), facts.get("peaks")
    live = facts["client"].get("mean_live_positions")
    tokens = facts["client"].get("mean_live_requests")
    if not t or not peaks or program not in t["programs"] or not live:
        return None
    run = facts["config"]["run"]
    least = pk.decode_step_least_s(
        facts["config"], tokens=tokens, live_positions=live,
        bytes_per_param=run["weight_bytes_per_param"],
        kv_bytes=run["kv_bytes_per_element"], peaks=peaks)
    step_ms = t["programs"][program]["mean_ms"]
    facts.setdefault("notes", []).append(
        {"roofline": program, "bound": least["bound"],
         "least_ms": 1e3 * least["least_s"], "step_ms": step_ms,
         "bytes": least["bytes"], "flops": least["flops"]})
    return 100.0 * least["least_s"] * 1e3 / step_ms


def forward_mfu_pct(facts) -> Optional[float]:
    """Forward FLOPs of the tokens through per second over the chips' bf16
    peak: an end-to-end utilization, not a kernel's roofline share."""
    peaks, rate = facts.get("peaks"), facts["client"].get("tok_s")
    if not peaks or not rate:
        return None
    tr = facts["traffic"]
    per_token = pk.gpt_forward_flops(facts["config"], 1, tr["seq"]) / tr["seq"]
    chips = facts["config"]["run"]["chips"]
    return 100.0 * rate * per_token / (chips * peaks["bf16_flops_per_s"])
