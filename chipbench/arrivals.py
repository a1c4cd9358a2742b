"""Seeded, deterministic open-loop arrival processes.

Copied from dnn_tpu/workloads/arrivals.py (PR 23) so that the yardstick
lives where a later PR cannot change it; the original is listed in
PERF.md's open questions for deletion. Its `poisson_arrivals` was not
copied: `traffic.arrival_times` draws the exponential gaps itself, because
it permutes them inside blocks.

An open-loop generator decides WHEN requests arrive independently of
how the server is doing — the arrival schedule is fixed before the
first request fires, so a saturated server faces the same demand a
healthy one does (closed-loop load self-throttles and hides collapse;
STUDIES §17's admit-then-deadline-cancel pathology is only visible
open-loop).

Determinism contract (same as dnn_tpu/chaos/plan.py): every draw comes
from `uniform(seed, name, i)` — a blake2s hash of the triple — so the
same seed yields the identical arrival times and client scripts on any
host, any Python build, any thread timing. No `random`, no numpy RNG
(whose bit streams are version-pinned promises we don't control), no
wall clock. Tests pin golden schedules.

The envelope kept here:

  * `bursty_arrivals(...)` — inhomogeneous Poisson by THINNING
    (Lewis-Shedler): candidates are drawn at the peak rate and each is
    kept with probability rate(t)/peak, where rate(t) follows
    `diurnal_envelope` — a smooth raised-cosine day/night cycle with a
    configurable burst factor. Thinning keeps the determinism trivial
    (two draws per candidate, both counter-indexed) and is exact, not
    an approximation.
"""

from __future__ import annotations

import hashlib
import math
from typing import List

__all__ = ["uniform", "bursty_arrivals", "diurnal_envelope"]


def uniform(seed: int, name: str, i: int) -> float:
    """Pure seeded draw in [0, 1) for the i-th use of `name` — the one
    source of randomness in this package (chaos/plan.decide's idiom,
    kept separate so workload schedules and fault schedules can never
    collide on a seam name)."""
    h = hashlib.blake2s(
        f"wl:{seed}:{name}:{i}".encode(), digest_size=8).digest()
    return int.from_bytes(h, "big") / 2.0 ** 64


def diurnal_envelope(t: float, period_s: float, *,
                     burst_factor: float = 4.0) -> float:
    """Rate multiplier in [1, burst_factor] at offset `t` of a
    raised-cosine day/night cycle: trough 1.0 at t=0, peak
    `burst_factor` at t=period/2. A compressed 'diurnal' shape — real
    traffic's 24 h cycle scaled down to a bench-runnable period."""
    if period_s <= 0:
        raise ValueError(f"period_s must be > 0, got {period_s}")
    if burst_factor < 1.0:
        raise ValueError(
            f"burst_factor must be >= 1, got {burst_factor}")
    phase = 0.5 * (1.0 - math.cos(2.0 * math.pi * t / period_s))
    return 1.0 + (burst_factor - 1.0) * phase


def bursty_arrivals(base_rate_hz: float, duration_s: float, *,
                    seed: int, burst_factor: float = 4.0,
                    period_s: float = 20.0,
                    name: str = "bursty") -> List[float]:
    """Arrival offsets of an inhomogeneous Poisson process whose rate
    follows `base_rate_hz * diurnal_envelope(t)` — bursts up to
    `burst_factor` x base at each period's peak. Exact Lewis-Shedler
    thinning: candidates at the peak rate, each kept with probability
    rate(t)/peak; both draws are counter-indexed so the schedule is a
    pure function of the seed."""
    if base_rate_hz <= 0:
        raise ValueError(f"base_rate_hz must be > 0, got {base_rate_hz}")
    peak = base_rate_hz * burst_factor
    out: List[float] = []
    t, i = 0.0, 0
    while True:
        u = uniform(seed, f"{name}:gap", i)
        keep = uniform(seed, f"{name}:keep", i)
        i += 1
        t += -math.log(1.0 - u) / peak
        if t >= duration_s:
            return out
        rate_t = base_rate_hz * diurnal_envelope(
            t, period_s, burst_factor=burst_factor)
        if keep < rate_t / peak:
            out.append(t)
