"""Percentiles and window arithmetic on client-side timestamps.

Every function is pure: lists of seconds in, numbers out. A request is
described by `t0` (when its clock starts: the due time in an open loop,
the submit time in a backlog) and `times` (arrival time of each streamed
token, ascending).
"""

from __future__ import annotations

from typing import Iterable, List, Optional, Sequence

__all__ = ["percentile", "tokens_in_window", "gaps_in_window",
           "ttfts_in_window", "mean_live_positions", "longest_silence"]


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """The q-th percentile (0..100) by linear interpolation between
    closest ranks (numpy's default); None for no values."""
    if not values:
        return None
    if not 0.0 <= q <= 100.0:
        raise ValueError(f"q must be in [0, 100], got {q}")
    v = sorted(values)
    rank = q / 100.0 * (len(v) - 1)
    lo = int(rank)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (rank - lo)


def tokens_in_window(token_times: Iterable[Sequence[float]], start: float,
                     end: float) -> int:
    """Tokens whose arrival falls in [start, end), over all requests —
    whether or not their request began or completed inside it."""
    return sum(1 for times in token_times for t in times if start <= t < end)


def gaps_in_window(token_times: Iterable[Sequence[float]], start: float,
                   end: float) -> List[float]:
    """Gaps between consecutive tokens of one request, all requests
    pooled; a gap belongs to the window when its LATER token arrives in
    [start, end). The first token of a request closes no gap."""
    out = []
    for times in token_times:
        for a, b in zip(times, times[1:]):
            if start <= b < end:
                out.append(b - a)
    return out


def ttfts_in_window(requests: Iterable[tuple], start: float,
                    end: float) -> List[float]:
    """First-token times of the requests whose clock starts in
    [start, end): `requests` yields (t0, times); a request with no token
    yet is left out (the caller counts it as unanswered)."""
    return [times[0] - t0 for t0, times in requests
            if start <= t0 < end and times]


def mean_live_positions(requests: Iterable[tuple], start: float,
                        end: float) -> float:
    """Time-average over [start, end) of the cache positions held by
    decoding requests: `requests` yields (prompt_len, times); a request
    holds prompt_len + k positions between its k-th and (k+1)-th token and
    nothing before its first or after its last."""
    if end <= start:
        raise ValueError("empty window")
    total = 0.0
    for prompt_len, times in requests:
        for k, (a, b) in enumerate(zip(times, times[1:]), start=1):
            lo, hi = max(a, start), min(b, end)
            if hi > lo:
                total += (prompt_len + k) * (hi - lo)
    return total / (end - start)


def longest_silence(token_times: Iterable[Sequence[float]], start: float,
                    end: float) -> tuple:
    """(length, offset from `start`) of the longest stretch of
    [start, end) in which no token of any request arrived: a stalled
    daemon, client or machine shows here, where a rate only reads low."""
    if end <= start:
        raise ValueError("empty window")
    marks = sorted(t for times in token_times for t in times
                   if start <= t < end)
    edges = [start] + marks + [end]
    length, at = max((b - a, a) for a, b in zip(edges, edges[1:]))
    return length, at - start
